"""Seam: wall milliseconds a step spends in the native DAG gate
(`gate.dag` span of fleet/backend.py, a sub-phase of `turbo_gate`: the
documents the chain check refused, gated as causally ordered logs over the
codec's pool), summed over the window and divided by its steps. None where
the program records no such span."""

from spans_util import span_ms_per_step


def read(ctx):
    return span_ms_per_step(ctx, ('gate.dag',))
