"""Seam: wall milliseconds a step spends in `stage.seq_rows` (PR 39: the
kept sequence rows brought into the fleet's numbering ahead of
`_dispatch_seq`: ids remapped, pred lanes, device rows resolved, payloads
re-interned, the op tuple stacked; under `turbo_stage` in a call without
grid rows, under `turbo_dispatch` otherwise), summed over the window and
divided by its steps. None from a program that records no such span."""

from spans_util import span_ms_per_step


def read(ctx):
    return span_ms_per_step(ctx, ('stage.seq_rows',))
