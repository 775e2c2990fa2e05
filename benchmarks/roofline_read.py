"""What one gather of a point read (`gather_grid_rows`, fleet/apply.py) needs
at the least, from its shapes alone, kept with the benchmark so that no PR
that claims a gain can move it.

A read of `rows` distinct documents reads each one's row of the three int32
grids (winners, values, counters) once and writes it once into the array
that goes to the host, besides reading the index of every row (one int32).
Rows padded onto the gather's power-of-two size class are not counted, nor
is the transfer to the host. It does no arithmetic worth counting: the
bound is memory."""


def gather_bytes(rows, row_bytes):
    """Bytes a gather of `rows` rows of `row_bytes` bytes (all three grids)
    has to move."""
    return rows * (2 * row_bytes + 4)
