"""Kernels, sequence: the time the chip would take at its peak for the
least bytes of a dispatch as the kernel is built now
(roofline_seq_rows.seq_row_read_bytes: every dispatched row's `elem_id`
read once, and each real op's columns; bound = memory) as a share of the
device time the trace shows for the `apply_seq_batch*` programs. The rows,
size class and ops of a dispatch come from the program's `seq.enqueue`
spans in the window (attributes `rows`, `cls`, `ops`), averaged over the
window's dispatches and multiplied by the programs that ran inside the
trace."""

from roofline import least_seconds
from roofline_seq_rows import seq_row_read_bytes

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
                  if span['name'] == 'seq.enqueue' and
                  {'rows', 'cls', 'ops'} <= set(span.get('attrs', ())) and
                  span['t0_ns'] >= window[0] and span['t1_ns'] <= window[1]]
    if not dispatches:
        return None
    moved = sum(seq_row_read_bytes(attrs['rows'], nodes_of[attrs['cls']],
                                   attrs['ops'])
                for attrs in dispatches) / len(dispatches)
    least, _bound = least_seconds(moved, 0, ctx['peaks'])
    return 100.0 * least * count / seconds
