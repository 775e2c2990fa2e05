"""Kernels, sequence: the least time the chip could take for a dispatch's
REAL ops (roofline_seq.seq_apply_bytes: every op reads its row's elem_id
once, and its own columns; bound = memory) as a share of the device time the
trace shows for the `apply_seq_batch*` programs. The ops of a dispatch and
its rows' size class come from the program's `seq.enqueue` spans in the
window (attributes `ops`, `cls`), averaged over the window's dispatches and
multiplied by the programs that ran inside the trace. Padding, the pointer
walk and the register writes are not counted, so it is a floor."""

from roofline import least_seconds
from roofline_seq import seq_apply_bytes

KERNEL = 'apply_seq_batch'


def read(ctx):
    rows = [row for name, row in ctx['trace']['modules'].items()
            if KERNEL in name]
    count = sum(row[0] for row in rows)
    seconds = sum(row[1] for row in rows)
    window = ctx['facts'].get('window_ns')
    nodes_of = ctx['facts'].get('seq_nodes_by_cls')
    if not count or not seconds or ctx['peaks'] is None or not window \
            or not nodes_of:
        return None
    dispatches = [span['attrs'] for span in ctx['spans']
                  if span['name'] == 'seq.enqueue' and 'attrs' in span and
                  span['t0_ns'] >= window[0] and span['t1_ns'] <= window[1]]
    if not dispatches:
        return None
    moved = sum(seq_apply_bytes(attrs['ops'], nodes_of[attrs['cls']])
                for attrs in dispatches) / len(dispatches)
    least, _bound = least_seconds(moved, 0, ctx['peaks'])
    return 100.0 * least * count / seconds
