"""Kernels, grid: the least time the chip could take for one step's grid
merge (roofline.grid_merge_bytes of the step's ops and of the cells they
fill, over the peak bandwidth; bound = memory) as a share of the device
time the trace shows for the `apply_op_batch*` programs, averaged over the
steps that lie whole inside the trace."""

from roofline import grid_merge_bytes, least_seconds

KERNEL = 'apply_op_batch'


def read(ctx):
    rows = [row for name, row in ctx['trace']['modules'].items()
            if KERNEL in name]
    count = sum(row[0] for row in rows)
    seconds = sum(row[1] for row in rows)
    facts = ctx['facts']
    if not count or not seconds or ctx['peaks'] is None or \
            not facts.get('ops_per_step'):
        return None
    least, _bound = least_seconds(
        grid_merge_bytes(facts['ops_per_step'], facts['cells_per_step']),
        0, ctx['peaks'])
    return 100.0 * least * count / seconds
