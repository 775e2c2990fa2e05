"""Seam: wall milliseconds a step spends in the general causal gate
(`gate.general` span of fleet/backend.py, a sub-phase of `turbo_gate`: for
every document neither the chain gate nor the DAG gate took, its changes
and its held-back ones as per-change headers (`gate.meta`) through the
reference's fixed-point loop (`gate.drain`), in Python), summed over the
window and divided by its steps. None where the program records no such
span."""

from spans_util import span_ms_per_step


def read(ctx):
    return span_ms_per_step(ctx, ('gate.general',))
