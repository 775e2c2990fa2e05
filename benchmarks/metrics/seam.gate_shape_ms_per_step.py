"""Seam: wall milliseconds a step spends in the shape check (`gate.shape`
span of fleet/backend.py, a sub-phase of `turbo_gate`, after `gate.dag`
since PR 33: a call that holds sequence, make or nested ops leaves for the
exact path there if a document is neither on the chain nor DAG-ordered, and
every op's object is looked up), summed over the window and divided by its
steps. None where the program records no such span."""

from spans_util import span_ms_per_step


def read(ctx):
    return span_ms_per_step(ctx, ('gate.shape',))
