"""Seam: milliseconds a step's calling thread spent OFF the CPU inside
`apply_changes_docs`: the root spans' (`apply_batch`) wall time less their
`thread_cpu_ns` (PR 39: the thread's CPU clock, read at a call's two
ends). It holds the wait for the native parse pool, which is by design,
and on top of it whatever took the core away: it tells a step that ran
slowly from one that did not run. The chip host's CPU clock moves in 10 ms
ticks, so under a millisecond a step this is noise and can read below
zero. None from a program whose spans carry no CPU clock."""

from span_tree_util import call_offcpu_ms_per_step


def read(ctx):
    return call_offcpu_ms_per_step(ctx)
