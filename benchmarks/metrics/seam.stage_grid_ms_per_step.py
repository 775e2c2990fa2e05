"""Seam: wall milliseconds a step spends in `stage.grid` (PR 39, a
sub-phase of `turbo_stage`, only in a call that holds root rows: capacity,
the laid-out grid or register columns, the kill lanes; its parts
`grid.lanes`, `grid.columns`, `grid.kills`), summed over the window and
divided by its steps. None from a program that records no such span."""

from spans_util import span_ms_per_step


def read(ctx):
    return span_ms_per_step(ctx, ('stage.grid',))
