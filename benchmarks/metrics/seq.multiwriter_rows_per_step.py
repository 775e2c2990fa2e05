"""Seam, sequence staging: rows of a step's dispatches whose op list holds
more than one actor (`DocFleet.metrics` `seq_multiwriter_rows`, PR 33: the
rows in which the scan's skip walk can have work), over the window, per
step. An exact count. None from a program that does not keep the
counter."""


def read(ctx):
    counters = ctx['facts'].get('fleet_counters') or {}
    steps = ctx['facts'].get('steps')
    if 'seq_multiwriter_rows' not in counters or not steps:
        return None
    return counters['seq_multiwriter_rows'] / steps
