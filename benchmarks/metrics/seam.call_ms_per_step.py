"""Seam: wall milliseconds a step spends inside `apply_changes_docs` (the
root span `apply_batch` of fleet/backend.py: the six `turbo_*` phases, the
collections the call's pause put off, and the call's own bookkeeping),
summed over the window's calls and divided by its steps. The whole that
`seam.pre_enqueue_ms_per_step`, the enqueue and
`seam.post_enqueue_ms_per_step` split."""

from span_tree_util import call_ms_per_step


def read(ctx):
    return call_ms_per_step(ctx)
