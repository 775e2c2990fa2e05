"""Native codec: wall milliseconds a step spends in the codec's parse+SHA
(the program's `turbo_parse` phase span, which tiles `native_parse`), summed
over the window and divided by its steps."""

from spans_util import span_ms_per_step


def read(ctx):
    return span_ms_per_step(ctx, ('turbo_parse',))
