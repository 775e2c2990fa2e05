"""Kernels, read: the least time the chip could take for a step's point
read (roofline_read.gather_bytes of the rows the step's gather moved, over
the peak bandwidth; bound = memory) as a share of the device time the trace
shows for the `gather_grid_rows` programs, averaged over the gathers that
lie inside the trace. The rows a step moves are the fleet's `read_rows`
counter over the window, per step (one gather a step). None from a program
without the gather or the counter."""

from roofline import least_seconds
from roofline_read import gather_bytes

KERNEL = 'gather_grid_rows'


def read(ctx):
    rows = [row for name, row in ctx['trace']['modules'].items()
            if KERNEL in name]
    count = sum(row[0] for row in rows)
    seconds = sum(row[1] for row in rows)
    facts = ctx['facts']
    counters = facts.get('fleet_counters') or {}
    if not count or not seconds or ctx['peaks'] is None or \
            not counters.get('read_rows') or not facts.get('steps') or \
            not facts.get('grid_row_bytes'):
        return None
    least, _bound = least_seconds(
        gather_bytes(counters['read_rows'] / facts['steps'],
                     facts['grid_row_bytes']), 0, ctx['peaks'])
    return 100.0 * least * count / seconds
