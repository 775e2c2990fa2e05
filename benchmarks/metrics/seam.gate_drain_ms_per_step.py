"""Seam: wall milliseconds a step spends in the general causal gate,
`HashGraph._drain_queue` over each off-chain document (`gate.drain` spans
of fleet/backend.py, one a document inside `gate.general`), summed over the
window and divided by its steps. None where the program records no such
span."""

from spans_util import span_ms_per_step


def read(ctx):
    return span_ms_per_step(ctx, ('gate.drain',))
