"""Seam: wall milliseconds a step spends in the commit's staged
sub-phase: the per-change tail loop over every off-chain document
(`commit.staged` span of fleet/backend.py), summed over the window and
divided by its steps. None where the program records no such span."""

from spans_util import span_ms_per_step


def read(ctx):
    return span_ms_per_step(ctx, ('commit.staged',))
