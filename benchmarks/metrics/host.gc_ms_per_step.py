"""Host runtime: wall milliseconds a step spends in Python's cycle
collector, from the program's own `gc` spans (observability/spans.py, one a
collection, under the span it interrupted), summed over the window and
divided by its steps. 0 where the window holds spans and no collection;
None where it holds no span at all."""

from span_tree_util import window_spans


def read(ctx):
    spans, steps = window_spans(ctx)
    if not spans:
        return None
    return sum(span['dur_ns'] for span in spans
               if span['name'] == 'gc') / 1e6 / steps
