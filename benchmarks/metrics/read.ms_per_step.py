"""Read: wall milliseconds a step spends inside `materialize_docs` (the root
span `read_batch` of fleet/backend.py: routing, the device gather, the
render, the host's documents), summed over the window and divided by its
steps. None from a program that records no such span."""

from spans_util import span_ms_per_step


def read(ctx):
    return span_ms_per_step(ctx, ('read_batch',))
