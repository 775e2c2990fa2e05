"""Kernels, sequence: bytes the sequence pools hold for every node (a slot or
a sentinel) of every row, `seq_pool_bytes / seq_nodes` of `DocFleet.metrics`
at the window's end: 8 + 13 x lanes, and a few bytes a row of cursors."""


def read(ctx):
    nodes = ctx['facts'].get('seq_nodes')
    if not nodes:
        return None
    return ctx['facts']['seq_pool_bytes'] / nodes
