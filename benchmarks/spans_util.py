"""Shared by the span readers: the program's spans
(observability/spans.py) that opened and closed inside the measured
window."""


def span_ms_per_step(ctx, names):
    """Milliseconds of the named spans inside the window, per step of the
    driver, or None when the ring holds none of them (nothing to read)."""
    window = ctx['facts'].get('window_ns')
    steps = ctx['facts'].get('steps')
    if not window or not steps:
        return None
    total, found = 0, False
    for span in ctx['spans']:
        if span['name'] in names and span['t0_ns'] >= window[0] and \
                span['t1_ns'] <= window[1]:
            total += span['dur_ns']
            found = True
    return total / 1e6 / steps if found else None
