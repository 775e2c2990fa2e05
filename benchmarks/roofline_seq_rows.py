"""A floor for one dispatch of the sequence kernel (`apply_seq_batch*`) as
it is built since PR 32, from its shapes alone, kept with the benchmark so
that no PR that claims a gain can move it.

The dispatch finds all of its ops' referents ahead of the op scan, in one
read of each dispatched row's `elem_id` (`_referent_lookup`). So the least
it must move is that read, `nodes` int32 for every row of the dispatched
class, and each real op's own columns (roofline_seq.OP_COLUMN_BYTES); the
bound is memory. The pointer walk, the register cells an op writes and the
padding ops are not counted. `roofline_seq.seq_apply_bytes`, which counts a
whole-row read for every op, is the older design's yardstick and stays as
it was."""

from roofline_seq import OP_COLUMN_BYTES


def seq_row_read_bytes(rows, nodes, n_ops):
    """Bytes a dispatch over `rows` rows of `nodes` nodes carrying `n_ops`
    real ops has to move at the least."""
    return rows * 4 * nodes + n_ops * OP_COLUMN_BYTES
