"""Seam: spans the program's ring lost to wraparound by the time the
readers run (`spans.spans_dropped()`). Anything but 0 means every span
metric of this run read a tail of the window, not the window."""


def read(ctx):
    from automerge_tpu.observability import spans
    return spans.spans_dropped()
