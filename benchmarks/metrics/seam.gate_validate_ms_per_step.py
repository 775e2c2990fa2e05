"""Seam: wall milliseconds a step spends in the gate's last sub-phase: the
kept-row mask, the duplicate-opId sort, the dangling-pred check and the
ingest counters (`gate.validate` span of fleet/backend.py), summed over the
window and divided by its steps. None where the program records no such
span."""

from spans_util import span_ms_per_step


def read(ctx):
    return span_ms_per_step(ctx, ('gate.validate',))
