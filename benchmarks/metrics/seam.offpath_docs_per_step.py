"""Seam: documents a step did NOT carry in the one fused dispatch —
`DocFleet.metrics` fallbacks + promotions + turbo_commit_fallback_docs over
the window's fleets, per step. An exact count; reads 0 on the device
path."""


def read(ctx):
    counters = ctx['facts'].get('fleet_counters')
    steps = ctx['facts'].get('steps')
    if not counters or not steps:
        return None
    off = (counters['fallbacks'] + counters['promotions'] +
           counters['turbo_commit_fallback_docs'])
    return off / steps
