"""Kernels, grid: the share of the grid kernel's op cells that are padding,
1 - ops / cells over the window: `cells`, the rows times width that each
call's `grid.columns` span says it laid out, summed; ops, the fleet's
`device_ops` over the window (the real op rows dispatched; a call without
sequence ops, as here, dispatches only grid rows). Under a skewed key
choice one hot document's chain sets every row's width. None from a program
whose span carries no `cells`."""


def read(ctx):
    window = ctx['facts'].get('window_ns')
    counters = ctx['facts'].get('fleet_counters') or {}
    if not window or 'device_ops' not in counters:
        return None
    cells = sum(span['attrs']['cells'] for span in ctx['spans']
                if span['name'] == 'grid.columns' and
                'cells' in (span.get('attrs') or {}) and
                span['t0_ns'] >= window[0] and span['t1_ns'] <= window[1])
    if not cells:
        return None
    return 100.0 * (1.0 - counters['device_ops'] / cells)
