"""Seam: documents a step committed through the DAG gate that hold sequence
ops (`DocFleet.metrics` `dag_seq_docs`, PR 33: concurrent writers on a Text,
applied on the device in buffer order), over the window, per step. An exact
count; reads the number of documents in the rounds cell. None from a program
that does not keep the counter."""


def read(ctx):
    counters = ctx['facts'].get('fleet_counters') or {}
    steps = ctx['facts'].get('steps')
    if 'dag_seq_docs' not in counters or not steps:
        return None
    return counters['dag_seq_docs'] / steps
