"""Seam: wall milliseconds a step spends in `stage.actors` (PR 39, a
sub-phase of `turbo_stage`: the applied actors found and interned, the
fleet's actor remaps, `actor_map`, `slot_of_doc`), summed over the window
and divided by its steps. None from a program that records no such span."""

from spans_util import span_ms_per_step


def read(ctx):
    return span_ms_per_step(ctx, ('stage.actors',))
