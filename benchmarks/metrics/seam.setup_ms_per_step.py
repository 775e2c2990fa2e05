"""Seam: wall milliseconds a step spends in `turbo_setup` (fleet/backend.py:
handles to engines, then the flat buffer list), summed over the window and
divided by its steps."""

from spans_util import span_ms_per_step


def read(ctx):
    return span_ms_per_step(ctx, ('turbo_setup',))
