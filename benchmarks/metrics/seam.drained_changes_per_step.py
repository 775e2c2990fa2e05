"""Seam: changes a step applied out of a document's queue, their dependency arrived
(`DocFleet.metrics` `drained_changes`, PR 37: held-back changes live on the
turbo path), over the window, per step. An exact count. None from a program
that does not keep the counter."""


def read(ctx):
    counters = ctx['facts'].get('fleet_counters') or {}
    steps = ctx['facts'].get('steps')
    if 'drained_changes' not in counters or not steps:
        return None
    return counters['drained_changes'] / steps
