"""Read: wall milliseconds a step spends in `read.gather` (a phase of
`read_batch`: the gather of the asked rows on the device and their transfer
to the host), summed over the window and divided by its steps. None from a
program that records no such span."""

from spans_util import span_ms_per_step


def read(ctx):
    return span_ms_per_step(ctx, ('read.gather',))
