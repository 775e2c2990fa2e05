"""Seam: wall milliseconds a step spends in the host gate, columnar commit
and staging phases (`turbo_gate` + `turbo_commit` + `turbo_stage` spans of
fleet/backend.py), summed over the window and divided by its steps."""

from spans_util import span_ms_per_step


def read(ctx):
    return span_ms_per_step(ctx, ('turbo_gate', 'turbo_commit',
                                  'turbo_stage'))
