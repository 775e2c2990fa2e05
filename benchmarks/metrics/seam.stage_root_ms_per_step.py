"""Seam: wall milliseconds a step spends in `stage.root` (PR 39, a
sub-phase of `turbo_stage`: the root rows' slots, their keys interned, the
packed ids, the dangling-pred oracle fed; its parts `root.rows`,
`root.keys`, `root.index`), summed over the window and divided by its
steps. None from a program that records no such span."""

from spans_util import span_ms_per_step


def read(ctx):
    return span_ms_per_step(ctx, ('stage.root',))
