"""Seam: what a step's general gate asked of documents' history indexes
(`DocFleet.metrics` `history_probes`, PR 38: dependencies that are neither
a change of the run nor a current head, own hashes of changes whose seq
their actor's clock has reached), over the window, per step. An exact
count. None from a program that does not keep the counter."""

from span_tree_util import counter_per_step


def read(ctx):
    return counter_per_step(ctx, 'history_probes')
