"""What one dispatch of the sequence kernel (`apply_seq_batch*`) needs at the
least, from its shapes alone, kept with the benchmark so that no PR that
claims a gain can move it.

The design finds an op's referent (or target) by comparing the row's whole
`elem_id` array with it, so every REAL op reads `nodes` int32 once, besides
its own columns (kind, ref, packed, value as int32, four preds as int32, the
flag as one byte). Padding ops, the pointer walk of an insert and the
handful of register cells an op writes are not counted: it is a floor, and
the bound is memory (the compare is one operation a word)."""

OP_COLUMN_BYTES = 4 * 4 + 4 * 4 + 1


def seq_apply_bytes(n_ops, nodes):
    """Bytes `n_ops` real ops have to move on rows of `nodes` nodes."""
    return n_ops * (4 * nodes + OP_COLUMN_BYTES)
