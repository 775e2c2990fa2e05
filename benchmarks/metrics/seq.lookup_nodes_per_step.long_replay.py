"""Kernels, sequence: the nodes a step's referent lookups compare with its
refs, rows x nodes of every dispatched size class (`DocFleet.metrics`
`seq_lookup_nodes`, which `seq.enqueue` also carries as its attribute
`lookup_nodes`), over the window, per step. An exact count: two rows of
the 33,554,432-slot class read 67,108,870. None from a program that keeps
neither."""


def read(ctx):
    counters = ctx['facts'].get('fleet_counters') or {}
    steps = ctx['facts'].get('steps')
    if not steps:
        return None
    if 'seq_lookup_nodes' in counters:
        return counters['seq_lookup_nodes'] / steps
    window = ctx['facts'].get('window_ns')
    nodes = [span['attrs']['lookup_nodes'] for span in ctx['spans']
             if span['name'] == 'seq.enqueue' and
             'lookup_nodes' in span.get('attrs', {}) and window and
             span['t0_ns'] >= window[0] and span['t1_ns'] <= window[1]]
    return sum(nodes) / steps if nodes else None
