"""Seam: wall milliseconds of a call that come AFTER its last device
enqueue: per root `apply_batch`, from the end of the last `seq.enqueue` or
`dispatch.enqueue` span under it to the call's end, summed over the window
and divided by its steps: host work the chip overlaps (higher is better at
a given call time: work moved behind the enqueue shows here). None from a
program that records neither span."""

from span_tree_util import split_ms_per_step


def read(ctx):
    return split_ms_per_step(ctx, 'post')
