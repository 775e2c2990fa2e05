"""Kernels, sequence: the share of the scan's cells that are padding,
1 - seq_ops / seq_op_cells over the window of the resend cell (`DocFleet.metrics`: real ops
dispatched over rows x width of the op columns handed to the device). An
exact count."""


def read(ctx):
    counters = ctx['facts'].get('fleet_counters') or {}
    cells = counters.get('seq_op_cells')
    if not cells:
        return None
    return 100.0 * (1.0 - counters['seq_ops'] / cells)
