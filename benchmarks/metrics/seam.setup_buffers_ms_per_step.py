"""Seam: wall milliseconds a step spends in `setup.buffers` (PR 39, a
sub-phase of `turbo_setup`: the flat buffer list with the queued changes'
buffers behind a document's own, the type scan, `change_doc`, the
rebased-slot check), summed over the window and divided by its steps. None
from a program that records no such span."""

from spans_util import span_ms_per_step


def read(ctx):
    return span_ms_per_step(ctx, ('setup.buffers',))
