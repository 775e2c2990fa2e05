"""Seam: documents whose queue is not empty after a step
(`DocFleet.metrics` `heldback_docs`, PR 37: held-back changes live on the
turbo path), over the window, per step. An exact count. None from a program
that does not keep the counter."""


def read(ctx):
    counters = ctx['facts'].get('fleet_counters') or {}
    steps = ctx['facts'].get('steps')
    if 'heldback_docs' not in counters or not steps:
        return None
    return counters['heldback_docs'] / steps
