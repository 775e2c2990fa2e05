"""Seam: wall milliseconds a step spends in `stage.values` (PR 39, a
sub-phase of `turbo_stage`: the value and flag columns copied, the make
ops' registration and link values, typed and arena-boxed payloads
interned), summed over the window and divided by its steps. None from a
program that records no such span."""

from spans_util import span_ms_per_step


def read(ctx):
    return span_ms_per_step(ctx, ('stage.values',))
