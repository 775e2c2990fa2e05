"""Seam, sequence staging: wall milliseconds a step spends building the
padded op columns on the host (span `seq.columns`, a sub-phase of
`dispatch_seq`), summed over the window and divided by its steps."""

from spans_util import span_ms_per_step


def read(ctx):
    return span_ms_per_step(ctx, ('seq.columns',))
