"""Read: documents a step's reads did NOT take from the device rows (the
fleet's `read_host_docs`: del_fallback, grid_overflow, inexact or promoted
documents, served by the host mirror or engine), over the window, per
step. An exact count; reads 0 where every read is a device read. None from
a program that does not keep the counter."""

from span_tree_util import counter_per_step


def read(ctx):
    return counter_per_step(ctx, 'read_host_docs')
