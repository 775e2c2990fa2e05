"""Seam: wall milliseconds a step spends in `turbo_stage` (fleet/backend.py:
everything between the commit and the grid's enqueue, and in a call without
grid rows the sequence rows' staging and dispatch too), summed over the
window and divided by its steps. The `seam.stage_*` metrics are its
sub-phases."""

from spans_util import span_ms_per_step


def read(ctx):
    return span_ms_per_step(ctx, ('turbo_stage',))
