"""Read: wall milliseconds a step spends in `read.render` (a phase of
`read_batch`: the gathered rows turned into {key: value}, values looked up
in the value table), summed over the window and divided by its steps. None
from a program that records no such span."""

from spans_util import span_ms_per_step


def read(ctx):
    return span_ms_per_step(ctx, ('read.render',))
