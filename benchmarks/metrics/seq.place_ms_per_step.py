"""Seam, sequence staging: wall milliseconds a step spends placing the
touched rows (span `seq.place` of `_dispatch_seq`, PR 29: writers noted,
rows reserved, placed, migrated), summed over the window and divided by
its steps."""

from spans_util import span_ms_per_step


def read(ctx):
    return span_ms_per_step(ctx, ('seq.place',))
