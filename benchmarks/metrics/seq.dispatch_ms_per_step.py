"""Seam, sequence staging: wall milliseconds a step spends in
`DocFleet._dispatch_seq` (span `dispatch_seq` of fleet/backend.py: placing
the touched rows, building the op columns on the host, enqueueing one
`apply_seq_batch_donated` a size class), summed over the window and divided
by its steps. The device's time is not in it: the enqueue returns before the
scan ends."""

from spans_util import span_ms_per_step


def read(ctx):
    return span_ms_per_step(ctx, ('dispatch_seq',))
