"""Seam: wall milliseconds a step spends outside every span of the
program: the window's length less the union of the root spans (`parent`
is None) that the calling thread recorded inside it, per step. In the bulk
cell that is `init_docs`, the block on the device and whatever else of a
step nobody wrapped. None where the ring holds no root span (a program
whose spans carry no parent)."""

import threading

from trace_reduce import merge


def read(ctx):
    window = ctx['facts'].get('window_ns')
    steps = ctx['facts'].get('steps')
    if not window or not steps:
        return None
    # the readers run on the thread that ran the driver's window
    thread = threading.get_ident()
    roots = [(span['t0_ns'], span['t1_ns']) for span in ctx['spans']
             if span.get('parent', 0) is None and span['tid'] == thread and
             span['t0_ns'] >= window[0] and span['t1_ns'] <= window[1]]
    if not roots:
        return None
    covered = sum(end - start for start, end in merge(roots))
    return (window[1] - window[0] - covered) / 1e6 / steps
