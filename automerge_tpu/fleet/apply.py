"""Batched change application: the whole fleet's merge in one XLA dispatch.

This is the tensorized equivalent of the reference's per-document op-merge
loop (ref backend/new.js:1052-1290 mergeDocChangeOps + seekToOp): instead of
a streaming two-pointer merge per document, all documents' ops land as padded
[N, P] columns and per-key LWW resolution becomes a scatter-max of packed
opIds over the [N, K] key grid. Counter accumulation is a scatter-add.

A batch row is a document: row r is doc `rows[r]` (one slot a row; a
padding row's lanes are all invalid), or, with no `rows`, row i is doc i:
a batch at the fleet's capacity, the form a sharded fleet takes, whose
row numbers are an iota the partitioner keeps on each device's rows.

Everything is static-shape, fusion-friendly gather/scatter on the VPU; no
data-dependent Python control flow, so the whole step is one `jit` region
that XLA pipelines across the fleet.
"""

import jax
import jax.numpy as jnp

from ..observability.perf import instrument_kernel
from .tensor_doc import FleetState


def _row_docs(state, rows, shape):
    """The doc of every lane of `shape` [rows, lanes]: the batch row's
    slot (`rows`), or the row's own index where rows is None."""
    if rows is None:
        rows = jnp.arange(state.winners.shape[0], dtype=jnp.int32)
    return jnp.broadcast_to(rows[:, None], shape)


def _apply_op_batch_impl(state, ops, rows=None):
    """Apply one OpBatch to the fleet. Returns (new_state, stats).

    `stats` is a per-fleet vector of ops applied (useful as a psum'd health
    metric when the fleet is sharded across hosts).
    """
    n_slots = state.winners.shape[1]
    doc_idx = _row_docs(state, rows, ops.key_id.shape)

    # Padded/invalid lanes scatter into the scratch column (n_slots - 1)
    scratch = n_slots - 1
    set_mask = ops.is_set & ops.valid
    inc_mask = ops.is_inc & ops.valid
    set_key = jnp.where(set_mask, ops.key_id, scratch)
    inc_key = jnp.where(inc_mask, ops.key_id, scratch)

    # LWW winner: scatter-max of packed opIds (unique per fleet, so ties are
    # impossible; overwritten ops always lose to their successors)
    winners = state.winners.at[doc_idx, set_key].max(
        jnp.where(set_mask, ops.packed, 0))

    # Find which op (if any) became the winner of its key, and scatter its
    # value. Packed opIds are unique per fleet, so at most one op per
    # (doc, key) matches; losing lanes write garbage into the scratch column.
    won = set_mask & (ops.packed == winners[doc_idx, ops.key_id])
    win_key = jnp.where(won, ops.key_id, scratch)
    values = state.values.at[doc_idx, win_key].set(jnp.where(won, ops.value, 0))

    # Counters accumulate (inc ops are successors that add, not overwrite,
    # ref new.js:937-965) — but a key whose winner changed this batch starts
    # from a fresh base: the old accumulator belonged to the overwritten op
    # (a redundant re-delivery of the standing winner leaves it intact).
    # Known corner: ops don't carry pred info on device, so an inc targeting
    # the *old* counter that lands in the same batch as the overwriting set
    # is credited to the new winner; the host mirror (fleet.backend) remains
    # exact there, and per-op pred ingest is the planned fix.
    keep = winners == state.winners
    counters = jnp.where(keep, state.counters, 0)
    counters = counters.at[doc_idx, inc_key].add(
        jnp.where(inc_mask, ops.value, 0))

    stats = jnp.sum(ops.valid, dtype=jnp.int32)
    return FleetState(winners, values, counters), stats


apply_op_batch = instrument_kernel(
    'apply_op_batch', jax.jit(_apply_op_batch_impl))


def _apply_op_batch_noinc_impl(state, ops, rows=None):
    """Set-only batches (no inc lanes — the caller checks host-side):
    skips the counter machinery entirely. The counter grid passes
    through UNTOUCHED — with donation that is a buffer alias, so the
    dispatch saves the winners==old compare (2 grid reads), the
    counter where() rewrite, and the inc scatter: ~3 whole-grid memory
    passes on a path whose cost IS memory traffic.

    SOUNDNESS GATE (the caller's, not this kernel's): with all-False
    is_inc the general kernel still RESETS the accumulator of any key
    whose winner changed — so skipping the counter machinery is only
    byte-identical while the counter grid is all-zero. DocFleet tracks
    that with `_counters_touched`: the first batch carrying an inc lane
    (or a bulk load installing counter cells) pins the fleet to the
    general kernel for good. Pinned against the general kernel by
    test_noinc_kernel_matches_general."""
    scratch = state.winners.shape[1] - 1
    doc_idx = _row_docs(state, rows, ops.key_id.shape)
    set_mask = ops.is_set & ops.valid
    set_key = jnp.where(set_mask, ops.key_id, scratch)
    winners = state.winners.at[doc_idx, set_key].max(
        jnp.where(set_mask, ops.packed, 0))
    won = set_mask & (ops.packed == winners[doc_idx, ops.key_id])
    win_key = jnp.where(won, ops.key_id, scratch)
    values = state.values.at[doc_idx, win_key].set(
        jnp.where(won, ops.value, 0))
    stats = jnp.sum(ops.valid, dtype=jnp.int32)
    return FleetState(winners, values, state.counters), stats


apply_op_batch_noinc_donated = instrument_kernel(
    'apply_op_batch_noinc_donated',
    jax.jit(_apply_op_batch_noinc_impl, donate_argnums=(0,)))


def _apply_op_batch_noinc_fresh_impl(ops, n_docs, n_keys, rows=None):
    return _apply_op_batch_noinc_impl(
        FleetState.empty(n_docs, n_keys, xp=jnp), ops, rows)


apply_op_batch_noinc_fresh = instrument_kernel(
    'apply_op_batch_noinc_fresh',
    jax.jit(_apply_op_batch_noinc_fresh_impl, static_argnums=(1, 2)))


def _apply_op_batch_kills_impl(state, ops, kill_key, kill_packed,
                               rows=None):
    """Apply one OpBatch plus delete "kill lanes" with the reference's
    pred-scoped delete semantics (ref backend/new.js:1204-1217: a delete
    adds succ entries ONLY to the ops it preds; concurrent sets it never
    saw stay visible and resurrect the key).

    kill_key/kill_packed are [N, Q] lanes, row for row with the batch's:
    each carries the packed opId a delete op preds (0 = unused lane) and
    the fleet key the delete targets. A kill (1) clears the standing
    winner iff it holds exactly that packed opId, and (2) masks any
    same-batch set lane carrying that opId. Nothing else is touched — in
    particular a concurrent set with a LOWER packed id than the delete
    wins the key afterwards, which the old tombstone-scatter model got
    wrong (the delete's own opId beat it).

    Causality makes this exact for single-winner semantics across
    batches: a delete can only pred ops its change causally saw, so an op
    arriving in a LATER batch can never be a target of this delete —
    clearing to 0 and letting later scatter-max resurrect is precisely
    the reference's succNum == 0 visibility rule, projected onto the
    grid's Lamport-max single-winner view."""
    scratch = state.winners.shape[1] - 1
    kvalid = kill_packed > 0
    kdoc = _row_docs(state, rows, kill_key.shape)
    kkey = jnp.where(kvalid, kill_key, scratch)
    standing = state.winners[kdoc, kkey]
    hit = kvalid & (standing == kill_packed)
    killed = jnp.zeros(state.winners.shape, dtype=jnp.bool_) \
        .at[kdoc, jnp.where(hit, kill_key, scratch)].max(hit)
    # The scratch column absorbs miss lanes; its contents are garbage by
    # contract, so clearing it along the way is harmless
    cleared = FleetState(jnp.where(killed, 0, state.winners),
                         jnp.where(killed, 0, state.values),
                         jnp.where(killed, 0, state.counters))
    # Same-batch kills: a set lane whose packed id any kill lane names
    # never lands (the delete pred'd it). Per-doc sorted membership test
    # — a dense [N, P, Q] one-hot would scale device memory with
    # doc_capacity x batch_width x kill_lanes (GBs on delete-heavy
    # flushes of large fleets), while sort + searchsorted stays
    # O(N x (P + Q)).
    int32_max = jnp.iinfo(jnp.int32).max
    kill_sorted = jnp.sort(
        jnp.where(kvalid, kill_packed, int32_max), axis=1)
    pos = jax.vmap(jnp.searchsorted)(kill_sorted, ops.packed)
    pos = jnp.clip(pos, 0, kill_sorted.shape[1] - 1)
    lane_killed = (jnp.take_along_axis(kill_sorted, pos, axis=1) ==
                   ops.packed) & (ops.packed > 0)
    masked = type(ops)(ops.key_id, ops.packed, ops.value,
                       ops.is_set & ~lane_killed, ops.is_inc, ops.valid)
    return _apply_op_batch_impl(cleared, masked, rows)


apply_op_batch_kills = instrument_kernel(
    'apply_op_batch_kills', jax.jit(_apply_op_batch_kills_impl))
apply_op_batch_kills_donated = instrument_kernel(
    'apply_op_batch_kills_donated',
    jax.jit(_apply_op_batch_kills_impl, donate_argnums=(0,)))

# The fleet's own dispatch paths donate the input state: the scatters then
# update the [docs, keys] grids in place instead of rewriting ~all of HBM
# per dispatch (the state is replaced by the result at every call site, so
# the donated buffers are never read again). External callers use the
# non-donating apply_op_batch, which keeps the input alive for reuse.
#
# Failure contract: if a donated dispatch fails at execution time (e.g.
# transient device OOM), the input buffers are already gone and the fleet's
# device state is unrecoverable — unlike the non-donating path, the error
# is not retryable in place. That is an accepted trade: the host-side
# change logs remain the source of truth, so documents rebuild into a
# fresh fleet (or promote to the host engine) from their logs; device
# state is always a derived cache.
apply_op_batch_donated = instrument_kernel(
    'apply_op_batch_donated',
    jax.jit(_apply_op_batch_impl, donate_argnums=(0,)))


def _apply_op_batch_fresh_impl(ops, n_docs, n_keys, rows=None):
    """First dispatch of a FRESH fleet: the zero state is created inside
    the jit, so XLA fuses the fill with the scatter instead of running a
    separate whole-grid memset dispatch first — a fresh 10k-doc x 1k-key
    grid otherwise pays a ~120 MB zero-fill (60-85 ms host-side in a
    CPU-era run, not measured on the chip) before its first merge.
    Shapes are static args: one compile per capacity step, same as the
    growth path."""
    return _apply_op_batch_impl(FleetState.empty(n_docs, n_keys, xp=jnp),
                                ops, rows)


apply_op_batch_fresh = instrument_kernel(
    'apply_op_batch_fresh',
    jax.jit(_apply_op_batch_fresh_impl, static_argnums=(1, 2)))


def _apply_op_batch_kills_fresh_impl(ops, kill_key, kill_packed, n_docs,
                                     n_keys, rows=None):
    """Kills-aware variant of the fused fresh-state dispatch (kills
    against an all-zero grid cannot hit, but the lane masking of
    same-batch sets must still run)."""
    return _apply_op_batch_kills_impl(
        FleetState.empty(n_docs, n_keys, xp=jnp), ops, kill_key,
        kill_packed, rows)


apply_op_batch_kills_fresh = instrument_kernel(
    'apply_op_batch_kills_fresh',
    jax.jit(_apply_op_batch_kills_fresh_impl, static_argnums=(3, 4)))


def _zero_doc_rows_impl(state, idx):
    """Zero the given docs' rows across every grid array — ONE fused
    kernel, so a batched free is genuinely one device dispatch (duplicate
    indices are fine: zeroing is idempotent, which lets callers pad idx to
    a power of two to bound recompiles)."""
    return FleetState(state.winners.at[idx].set(0),
                      state.values.at[idx].set(0),
                      state.counters.at[idx].set(0))


zero_doc_rows_donated = instrument_kernel(
    'zero_doc_rows_donated',
    jax.jit(_zero_doc_rows_impl, donate_argnums=(0,)))


def _gather_grid_rows_impl(state, idx):
    """The given docs' rows of the three grids, stacked [3, len(idx), K+1]
    (winners, values, counters): a point read moves these rows to the host
    in one transfer, not the fleet. Callers pad idx to a power of two (a
    repeated index is read twice), so a read compiles once a size class."""
    return jnp.stack([state.winners[idx], state.values[idx],
                      state.counters[idx]])


gather_grid_rows = instrument_kernel('gather_grid_rows',
                                     jax.jit(_gather_grid_rows_impl))


def fleet_merge(state, op_batches):
    """Apply a sequence of OpBatches (e.g. one per change round)."""
    total = 0
    for ops in op_batches:
        state, stats = apply_op_batch(state, ops)
        total += int(stats)
    return state, total
