"""Seam: wall milliseconds of a call that come BEFORE its first device
enqueue: per root `apply_batch`, from its start to the start of the first
`seq.enqueue` or `dispatch.enqueue` span under it, summed over the window
and divided by its steps. A step counts after the driver's block, so the
chip has nothing to do while this runs: it is the program's part of
`device_idle_share.*`, and what enqueuing step n+1 while step n runs would
hide. None from a program that records neither span."""

from span_tree_util import split_ms_per_step


def read(ctx):
    return split_ms_per_step(ctx, 'pre')
