"""What a kernel's call needs at the least, computed from the call's shapes
alone (never from the implementation), and the least time a chip could take
for it. Kept with the benchmark so that no PR that claims a gain can move
it."""


def grid_merge_bytes(n_ops, n_cells):
    """Bytes one grid merge has to move: the op columns read once (key,
    packed opId and value as int32; is_set, is_del and valid as one byte
    each), and the `n_cells` cells that those ops fill written once in
    each of the three int32 grids (winners, values, counters). Cells that
    no op touches are padding and are not counted. It does no arithmetic
    worth counting: the bound is memory."""
    columns = n_ops * (3 * 4 + 3 * 1)
    cells = 3 * n_cells * 4
    return columns + cells


def least_seconds(bytes_moved, flops, peaks):
    """(seconds, 'memory' | 'compute'): the larger of bytes over the peak
    bandwidth and operations over the peak rate."""
    by_memory = bytes_moved / peaks['hbm_bytes_per_s']
    by_compute = flops / peaks['bf16_flops_per_s']
    return (by_memory, 'memory') if by_memory >= by_compute \
        else (by_compute, 'compute')
