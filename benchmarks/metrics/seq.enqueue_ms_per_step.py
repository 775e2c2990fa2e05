"""Seam, sequence staging: wall milliseconds a step spends handing the
padded columns to the device (span `seq.enqueue` of `_dispatch_seq`, PR
29, one a size class: the jitted call's argument handling, the transfer's
enqueue and the launch, with the kernel ledger's wrapper while the harness
has it on), summed over the window and divided by its steps."""

from spans_util import span_ms_per_step


def read(ctx):
    return span_ms_per_step(ctx, ('seq.enqueue',))
