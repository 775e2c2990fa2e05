"""Exact multi-value registers on device: actor-slotted visible sets.

The scatter-max engine (fleet/apply.py) materializes LWW winners only; the
reference's per-key state is richer — a *multi-value register* holding every
op with no successor (new.js:1204-1217), which is what conflict sets,
concurrent set-vs-delete resurrection, and per-op counter accumulation are
read from. This engine stores that state exactly, on device:

    reg     [N, K+1, A] int32  packed opId of actor-slot a's live set op
    killed  [N, K+1, A] bool   that op has a successor (overwritten/deleted)
    value   [N, K+1, A] int32  the op's payload (inline int / table ref)
    counter [N, K+1, A] int32  per-op accumulated inc deltas (new.js:937-965)

Key observation: in causally well-formed histories each actor's newest set
op on a key supersedes that actor's previous one (the frontend always preds
its own visible op, frontend/context.js:576-586), so the visible set holds
at most one op per actor and an actor-indexed slot axis of width A (a small
power of two >= the fleet's actor count) represents it losslessly. Deletes
kill exactly their preds — never concurrent ops — and increments accumulate
into the *target op's* slot, so both reference corner cases the LWW engine
documents away (set-vs-delete resurrection, counter overwrite) are exact
here.

Ops carry their pred lists (from the native parser's pred columns,
codec.cpp) padded to a static width D. Application is ordered *within* a
document — a lax.scan over the op axis, with every document's op-i applied
in one [N]-wide step (the same vmap-over-docs x scan-over-ops shape as the
sequence engine) — because a successor can arrive in the same batch as the
op it kills.

Histories outside the one-op-per-actor shape (an actor overwriting its own
key without pred'ing it — only constructible by hand-built changes) and ops
with more than D preds raise an `inexact` per-doc flag instead of silently
diverging; callers route flagged documents to the host engine.
"""

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..observability.perf import instrument_kernel
from .tensor_doc import MAX_ACTORS, register_pytrees

ACTOR_MASK = MAX_ACTORS - 1


class RegisterState:
    """Pytree of actor-slotted register tensors + per-doc inexact flags."""

    def __init__(self, reg, killed, value, counter, inexact):
        self.reg = reg
        self.killed = killed
        self.value = value
        self.counter = counter
        self.inexact = inexact   # [N] bool: doc needs the host engine

    @classmethod
    def empty(cls, n_docs, n_keys, n_actor_slots, xp=np):
        shape = (n_docs, n_keys + 1, n_actor_slots)
        return cls(xp.zeros(shape, dtype=np.int32),
                   xp.zeros(shape, dtype=bool),
                   xp.zeros(shape, dtype=np.int32),
                   xp.zeros(shape, dtype=np.int32),
                   xp.zeros((n_docs,), dtype=bool))

    def tree_flatten(self):
        return ((self.reg, self.killed, self.value, self.counter,
                 self.inexact), None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


class RegisterOpBatch:
    """Sequenced op columns [N, P] + pred lists [N, P, D].

    kind: 0 pad, 1 set, 2 del, 3 inc. Ops apply in column order per doc.
    preds are packed opIds (0 = unused lane); an op with more than D preds
    must set `overflow` for its lane (flags the doc inexact)."""

    def __init__(self, kind, key_id, packed, value, preds, overflow):
        self.kind = kind
        self.key_id = key_id
        self.packed = packed
        self.value = value
        self.preds = preds
        self.overflow = overflow

    def tree_flatten(self):
        return ((self.kind, self.key_id, self.packed, self.value, self.preds,
                 self.overflow), None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


register_pytrees(RegisterState, RegisterOpBatch)

PAD, SET, DEL, INC = 0, 1, 2, 3


def _apply_step(state, op, n_slots, n_actor_slots):
    """Apply op-column i (one op per document, [N] wide)."""
    reg, killed, value, counter, inexact = state
    kind, key_id, packed, val, preds, overflow = op
    n_docs = reg.shape[0]
    docs = jnp.arange(n_docs, dtype=jnp.int32)
    scratch = n_slots - 1

    live = kind != PAD
    k = jnp.where(live, key_id, scratch)

    reg_row = reg[docs, k]          # [N, A]
    killed_row = killed[docs, k]
    value_row = value[docs, k]
    counter_row = counter[docs, k]

    # Kill preds: each pred lane targets its actor's slot; the kill lands
    # only if that slot still holds exactly the pred'd op. Increments do
    # not kill (they are successors that accumulate, new.js:937-965).
    # A pred that resolves to no live slot is NOT flagged: it can be a
    # legitimately already-superseded op (killed rows are reclaimed when the
    # same actor writes again), which the reference also accepts as a no-op
    # succ entry.
    kills = kind != INC
    slot_oob = jnp.zeros((n_docs,), dtype=bool)
    d_preds = preds.shape[1]
    for d in range(d_preds):
        p = preds[:, d]
        s = (p & ACTOR_MASK).astype(jnp.int32)
        slot_oob |= live & (p != 0) & (s >= n_actor_slots)
        hit = live & (p != 0) & (s < n_actor_slots) & (reg_row[docs, s] == p)
        do_kill = hit & kills
        killed_row = killed_row.at[docs, s].set(killed_row[docs, s] | do_kill)

    # INC: an increment on a conflicted counter carries one pred per
    # conflicting set op (the frontend preds every conflict opId). The
    # reference attributes such an inc to the LAMPORT-MAX pred — even a
    # dead one: `counterStates[succOp] = counterState` overwrites earlier
    # sets' registrations (new.js:942-945) — and every other pred'd set
    # never completes its counter state, so it stays invisible forever
    # (round-4 50x-chaos find, seed 18). Device equivalent: add to the
    # max pred's lane iff that lane still holds it live; kill every other
    # live pred'd lane (a dead max pred consumes the inc silently, and
    # the lower branches hide either way).
    is_inc = kind == INC
    max_pred = jnp.zeros((n_docs,), dtype=jnp.int32)
    any_live_hit = jnp.zeros((n_docs,), dtype=bool)
    for d in range(d_preds):
        p = preds[:, d]
        s = (p & ACTOR_MASK).astype(jnp.int32)
        max_pred = jnp.where(is_inc & (p != 0),
                             jnp.maximum(max_pred, p), max_pred)
        any_live_hit |= is_inc & (p != 0) & (s < n_actor_slots) & \
            (reg_row[docs, s] == p) & ~killed_row[docs, s]
    s_max = (max_pred & ACTOR_MASK).astype(jnp.int32)
    max_live = is_inc & (max_pred != 0) & (s_max < n_actor_slots) & \
        (reg_row[docs, s_max] == max_pred) & ~killed_row[docs, s_max]
    counter_row = counter_row.at[
        docs, jnp.where(max_live, s_max, n_actor_slots)].add(
        jnp.where(max_live, val, 0), mode='drop')
    for d in range(d_preds):
        p = preds[:, d]
        s = (p & ACTOR_MASK).astype(jnp.int32)
        lose = is_inc & (p != 0) & (s < n_actor_slots) & \
            (reg_row[docs, s] == p) & ~killed_row[docs, s] & (p != max_pred)
        killed_row = killed_row.at[docs, s].set(killed_row[docs, s] | lose)
    inc_hit = any_live_hit | max_live

    # SET: occupy own actor slot. If the slot already holds a live op this
    # op did NOT pred, the reference would keep both visible — outside the
    # one-op-per-actor shape, so flag the doc instead of losing data.
    a = (packed & ACTOR_MASK).astype(jnp.int32)
    is_set = kind == SET
    own_prev = reg_row[docs, a]
    own_pred = jnp.zeros((n_docs,), dtype=bool)
    for d in range(d_preds):
        own_pred |= preds[:, d] == own_prev
    self_conflict = is_set & (own_prev != 0) & ~killed_row[docs, a] & \
        ~own_pred & (own_prev != packed)
    # An inc whose target is missing/killed is invalid input (the exact
    # paths reject it up front); under turbo it flags the doc for replay.
    # Actor numbers beyond the configured slot width also flag (the write
    # below would otherwise silently drop).
    bad_inc = (kind == INC) & ~inc_hit
    actor_oob = live & (a >= n_actor_slots)
    inexact = inexact | self_conflict | overflow | bad_inc | slot_oob | \
        actor_oob

    set_slot = jnp.where(is_set & ~actor_oob, a, n_actor_slots)
    reg_row = reg_row.at[docs, set_slot].set(packed, mode='drop')
    killed_row = killed_row.at[docs, set_slot].set(False, mode='drop')
    value_row = value_row.at[docs, set_slot].set(val, mode='drop')
    counter_row = counter_row.at[docs, set_slot].set(0, mode='drop')

    reg = reg.at[docs, k].set(reg_row)
    killed = killed.at[docs, k].set(killed_row)
    value = value.at[docs, k].set(value_row)
    counter = counter.at[docs, k].set(counter_row)
    return (reg, killed, value, counter, inexact), live.astype(jnp.int32)


def _apply_register_batch_impl(state, ops):
    n_slots = state.reg.shape[1]
    n_actor_slots = state.reg.shape[2]

    def step(carry, op):
        return _apply_step(carry, op, n_slots, n_actor_slots)

    xs = (ops.kind.T, ops.key_id.T, ops.packed.T, ops.value.T,
          jnp.transpose(ops.preds, (1, 0, 2)), ops.overflow.T)
    carry = (state.reg, state.killed, state.value, state.counter,
             state.inexact)
    carry, applied = lax.scan(step, carry, xs)
    return RegisterState(*carry), jnp.sum(applied)


apply_register_batch = instrument_kernel(
    'apply_register_batch', jax.jit(_apply_register_batch_impl))
# In-place variant for the fleet's own dispatch paths (see
# apply.apply_op_batch_donated): the register tensors update without a
# full-state rewrite; callers must replace their state reference.
apply_register_batch_donated = instrument_kernel(
    'apply_register_batch_donated',
    jax.jit(_apply_register_batch_impl, donate_argnums=(0,)))


def _zero_register_rows_impl(state, idx):
    """Zero the given docs' rows across every register array — ONE fused
    kernel (idempotent under duplicate indices, so callers may pad idx)."""
    return RegisterState(state.reg.at[idx].set(0),
                         state.killed.at[idx].set(False),
                         state.value.at[idx].set(0),
                         state.counter.at[idx].set(0),
                         state.inexact.at[idx].set(False))


zero_register_rows_donated = instrument_kernel(
    'zero_register_rows_donated',
    jax.jit(_zero_register_rows_impl, donate_argnums=(0,)))


def _visible_registers_impl(state):
    """(visible [N, K+1, A] bool, winner_slot [N, K+1] int32,
    winner_packed [N, K+1] int32): the multi-value register contents and the
    Lamport winner per key (packed ids order like lamportCompare because
    actor numbers are hex-sorted, see fleet/backend._SortedActorTable)."""
    visible = (state.reg != 0) & ~state.killed
    masked = jnp.where(visible, state.reg, -1)
    winner_slot = jnp.argmax(masked, axis=-1).astype(jnp.int32)
    winner_packed = jnp.max(jnp.where(visible, state.reg, 0), axis=-1)
    return visible, winner_slot, winner_packed


visible_registers = instrument_kernel(
    'visible_registers', jax.jit(_visible_registers_impl))


def _gather_register_rows_impl(state, idx):
    """The given docs' rows of every register tensor and their inexact
    flags, as a RegisterState of len(idx) docs: what a point read moves to
    the host (padded to a power of two by the caller, as the grid's)."""
    return RegisterState(state.reg[idx], state.killed[idx],
                         state.value[idx], state.counter[idx],
                         state.inexact[idx])


gather_register_rows = instrument_kernel(
    'gather_register_rows', jax.jit(_gather_register_rows_impl))


def rows_to_register_batch(doc_ids, flags, key_ids, packed, values,
                           pred_off, pred, n_docs, d_preds=4,
                           force_overflow=None):
    """Lay flat native-ingest op rows (application order, doc-contiguous)
    into a RegisterOpBatch [n_docs, P]. Inputs are the arrays the native
    parser emits with with_meta=True — flags (1 = set/del, 2 = inc; dels
    carry value -1), pred_off/pred per-row pred lists — already remapped to
    fleet key/actor numbering by the caller. Stable layout preserves each
    document's op order (the scan applies columns in order)."""
    doc_ids = np.asarray(doc_ids, dtype=np.int64)
    n_rows = len(doc_ids)
    counts = np.bincount(doc_ids, minlength=n_docs) if n_rows else \
        np.zeros(n_docs, dtype=np.int64)
    width = max(int(counts.max()) if n_rows else 0, 1)
    order = np.argsort(doc_ids, kind='stable')
    doc_sorted = doc_ids[order]
    pos = np.arange(n_rows) - np.searchsorted(doc_sorted, doc_sorted,
                                              side='left')
    kind = np.zeros((n_docs, width), dtype=np.int32)
    key_col = np.zeros((n_docs, width), dtype=np.int32)
    packed_col = np.zeros((n_docs, width), dtype=np.int32)
    value_col = np.zeros((n_docs, width), dtype=np.int32)
    preds_col = np.zeros((n_docs, width, d_preds), dtype=np.int32)
    overflow = np.zeros((n_docs, width), dtype=bool)

    flags = np.asarray(flags)
    values = np.asarray(values)
    kinds_flat = np.where(flags == 2, INC,
                          np.where(values == -1, DEL, SET)).astype(np.int32)
    kind[doc_sorted, pos] = kinds_flat[order]
    key_col[doc_sorted, pos] = np.asarray(key_ids)[order]
    packed_col[doc_sorted, pos] = np.asarray(packed)[order]
    # -1 is the DEL sentinel only for set/del rows; an inc delta of -1 is a
    # legitimate negative increment and must pass through untouched
    value_col[doc_sorted, pos] = np.where(
        (values == -1) & (flags != 2), 0, values)[order]

    pred_off = np.asarray(pred_off)
    pred = np.asarray(pred)
    pred_counts = np.diff(pred_off)
    oflow_flat = pred_counts > d_preds
    if force_overflow is not None:
        # Caller-detected per-row badness (e.g. a pred naming an actor the
        # fleet has never seen): route the doc to host replay via inexact
        oflow_flat = oflow_flat | np.asarray(force_overflow, dtype=bool)
    overflow[doc_sorted, pos] = oflow_flat[order]
    for d in range(d_preds):
        has = pred_counts > d
        lane = np.zeros(n_rows, dtype=np.int32)
        lane[has] = pred[pred_off[:-1][has] + d]
        preds_col[doc_sorted, pos, d] = lane[order]
    return RegisterOpBatch(kind, key_col, packed_col, value_col, preds_col,
                           overflow)


def materialize_registers(state, keys, value_table=None):
    """Host-side read: per doc {key: (winner_value, conflict_dict)} where
    conflict_dict maps packed opId -> value for every visible op (empty for
    unanimous keys). Counter accumulators are added to their op's base."""
    visible, winner_slot, winner_packed = jax.device_get(
        visible_registers(state))
    reg = np.asarray(jax.device_get(state.reg))
    value = np.asarray(jax.device_get(state.value))
    counter = np.asarray(jax.device_get(state.counter))

    def decode(v, c):
        out = value_table[-v - 2] if v <= -2 and value_table is not None else v
        if isinstance(out, TypedValue):
            return out.value + int(c) if out.datatype == 'counter' \
                else out.value
        if isinstance(out, int) and not isinstance(out, bool):
            out += int(c)
        return out

    docs = []
    for n in range(reg.shape[0]):
        doc = {}
        for k in range(len(keys)):
            vis = np.flatnonzero(visible[n, k])
            if not len(vis):
                continue
            w = winner_slot[n, k]
            winner_value = decode(int(value[n, k, w]), counter[n, k, w])
            conflicts = {int(reg[n, k, s]): decode(int(value[n, k, s]),
                                                   counter[n, k, s])
                         for s in vis} if len(vis) > 1 else {}
            doc[keys[k]] = (winner_value, conflicts)
        docs.append(doc)
    return docs


def typed_wire_tags():
    """Wire value-type tag -> datatype string for root-map set values that
    must box as TypedValue (uint/counter/timestamp ride int32 value lanes;
    the datatype survives only via the box). The single source of truth for
    every ingest path — native rows, turbo, and the mixed Python decode —
    so device-served patches emit identical datatype leaves regardless of
    which path a change took."""
    from ..columnar import VALUE_TYPE
    return {VALUE_TYPE['LEB128_UINT']: 'uint',
            VALUE_TYPE['COUNTER']: 'counter',
            VALUE_TYPE['TIMESTAMP']: 'timestamp'}


class TypedValue:
    """Boxed register value carrying its wire datatype (uint / timestamp /
    counter / float64 …) so device-served patches reproduce the host patch
    grammar exactly (datatype survives the int32 value lanes)."""

    __slots__ = ('value', 'datatype')

    def __init__(self, value, datatype):
        self.value = value
        self.datatype = datatype

    def __repr__(self):
        return f'TypedValue({self.value!r}, {self.datatype!r})'

    def __eq__(self, other):
        return isinstance(other, TypedValue) and \
            other.value == self.value and other.datatype == self.datatype

    def __hash__(self):
        return hash(('TypedValue', self.value, self.datatype))


def _patch_leaf(raw, counter_fold, value_table):
    """One visible register lane -> host-grammar patch value leaf."""
    boxed = value_table[-raw - 2] if raw <= -2 and value_table is not None \
        else raw
    if isinstance(boxed, TypedValue):
        value = boxed.value
        if boxed.datatype == 'counter':
            value += int(counter_fold)
        return {'type': 'value', 'value': value, 'datatype': boxed.datatype}
    if isinstance(boxed, bool) or boxed is None or isinstance(boxed, str):
        return {'type': 'value', 'value': boxed}
    if isinstance(boxed, float):
        return {'type': 'value', 'value': boxed, 'datatype': 'float64'}
    if isinstance(boxed, int):
        return {'type': 'value', 'value': boxed, 'datatype': 'int'}
    return None    # links / unsupported payloads: caller uses the mirror


