"""Seam: changes a step brought and queued, their dependency not there yet
(`DocFleet.metrics` `heldback_changes`, PR 37: held-back changes live on the
turbo path), over the window, per step. An exact count. None from a program
that does not keep the counter."""


def read(ctx):
    counters = ctx['facts'].get('fleet_counters') or {}
    steps = ctx['facts'].get('steps')
    if 'heldback_changes' not in counters or not steps:
        return None
    return counters['heldback_changes'] / steps
