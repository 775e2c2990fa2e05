"""Seam: wall milliseconds a step spends in the commit's columnar
sub-phase: head and max-op scatters, seam segments, frontier-index staging
and clock lanes of the documents on the chain path (`commit.columnar` span
of fleet/backend.py), summed over the window and divided by its steps. None
where the program records no such span."""

from spans_util import span_ms_per_step


def read(ctx):
    return span_ms_per_step(ctx, ('commit.columnar',))
