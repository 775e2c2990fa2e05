"""Batched RGA sequence engine: list/text CRDTs as device tensors.

This is the tensorized equivalent of the reference's list-insertion path
(ref backend/new.js:50-192 seekWithinBlock, :145-163 concurrent-insert skip;
host mirror: automerge_tpu/backend/op_set.py ObjState.insert_rga): a fleet of
N sequence documents (one Text or list object each) lives as padded [N, S]
slot tensors plus a linked-list `nxt` pointer array encoding RGA order. A
loaded row lays its elements out in id order (the bulk loader); an insert
takes the next free slot and never moves, and splices pointers, so an op's
work is its referent lookup + an O(skip) pointer walk, with NO data movement
of the sequence itself — the analogue of the reference editing a block in
place instead of reshuffling the array.
That holds of the compiled program too because a dispatch defers its splices:
inside the op scan `elem_id` and `nxt` are only read, the batch's new slots
and repointed nodes live in a per-row overlay as wide as the batch, and each
array takes its overlay in one scatter when the scan is over (a scan step that
wrote them while the skip walk's loop held them had both copied whole, every
step: see _apply_seq_batch_impl). And since the scan never writes `elem_id`,
the lookups of a whole batch are made before it (_referent_lookup): a scan
step reads no node array in full, only the overlay, as wide as the batch. A
long row whose slots hold ascending ids (a loaded or new row whose inserts
have since come in ascending id order, as one writer's do) is searched, in
log2(capacity) gathers (search_pays); any other row is read once, each part
of it compared with many refs while it is on the chip.

Application is a `vmap` over docs of a `lax.scan` over each doc's op stream:
ops within one doc apply in causal order (as the reference's per-change op
loop does), while the fleet axis is embarrassingly parallel — the SURVEY §7
"vmap'd masked scan" formulation. Extraction back to sequence order
(`linearize`) is pointer-doubling list ranking: O(log S) rounds of gathers,
fully parallel, replacing the reference's visibleCount block walk
(new.js:225-240).

Packed opIds: (counter << ACTOR_BITS) | actorNum, as in tensor_doc, for a
row whose counters stay inside the packed window; a row past it is WIDE,
its ids (counter << b) | rank, the rank of the actor among the row's
writers in b bits (DocFleet._seq_wide). A row's layout is all the kernel
has to know: the RGA skip and the per-element Lamport winner compare ids
of ONE row, and an op batch gives each row the mask of its actor bits
(SeqOpBatch.actor_mask). For the integer comparisons to agree with the
host engine's Lamport order (counter, actorId-hex-string), actor numbers
and ranks MUST follow ascending lexicographic order of the actor hex ids
(the reference's columnar format sorts its actor table the same way, ref
backend/columnar.js:133-170).

Per-element overwrite state is an exact multi-value register (the
fleet/registers.py design applied to sequence elements): each element keeps
a visible set of a few lanes — packed opId + payload per lane, one lane an
actor that wrote the element, found by value and in no order — with a
`killed` bit marking ops that have a successor (ref new.js:1204-1217's
succNum == 0 visibility rule). A SET/DEL kills exactly its preds, never
concurrent ops, so the two shapes where single-winner LWW diverges from the
reference — concurrent set-vs-set (conflict sets) and set-vs-delete
(element resurrection, ref test/new_backend_test.js:1660) — are exact on
device, and counters inside sequences accumulate exactly in per-lane
counter registers with the reference's Lamport-max attribution
(new.js:942-945). The remaining host-only shapes (same-actor overwrites
that don't pred their own op, pred lists past SEQ_PRED_LANES) flag the row
`inexact` and route reads to the host mirror.
"""

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..observability.perf import instrument_kernel
from .tensor_doc import ACTOR_BITS, MAX_ACTORS, pack_op_id, register_pytrees

# Op kinds in a SeqOpBatch
PAD, INSERT, SET, DEL, INC = 0, 1, 2, 3, 4

HEAD_REF = 0  # `ref == 0` means insert at the head ('_head' in the reference)

INT32_MAX = np.int32(2**31 - 1)

ACTOR_MASK = MAX_ACTORS - 1

# Static pred-lane width: ops with more preds flag their row inexact. A pred
# list wider than the element's current conflict set cannot occur, so lanes
# bound the *representable* conflict width, matching registers.RegisterOpBatch.
SEQ_PRED_LANES = 4

# Default lane width for new states; a pool widens (pow2) when a row in it
# has had more writers.
DEFAULT_ACTOR_SLOTS = 4


# Node-id layout, front-anchored so every per-node array shares one shape
# [N, capacity + 3] and capacity can grow (or pad for sharding) by appending
# at the tail without moving the sentinels:
#
#   0        HEAD sentinel (its nxt is the first element)
#   1        END sentinel / pointer-scratch (masked pointer writes land here;
#            its outgoing pointer is never followed)
#   2        slot-scratch (masked writes of per-slot arrays land here)
#   3..S+2   real slots: a loaded row's in id order, then one a new
#            element in the order its insert was applied
HEAD, END, SCRATCH, SLOT0 = 0, 1, 2, 3


class SeqState:
    """Pytree of per-doc sequence tensors.

    Element identity / order (node-id indexed, [N, S+3]):
      elem_id  packed elemId per slot (0 = unallocated)
      nxt      linked-list next pointers over node ids

    Per-element multi-value registers ([N, A * (S+3)]: lane l of node i at
    column l * (S+3) + i, A node-indexed segments side by side. Not a third
    axis: a TPU pads an array's last axis to 128 lanes, so a trailing axis
    of 4 takes 32 times its size, and XLA moves a lane axis there for the
    scan's gathers wherever it is declared. An unordered set of A lanes,
    one for each actor that wrote the element — at most one live op
    per actor per element in causally well-formed histories, since the
    frontend always preds its own visible op, ref
    frontend/context.js:576-586 — so A follows the writers of a pool's
    rows, never the fleet's actor table):
      reg      packed opId of the lane's op on this element (0 = empty)
      killed   that op has a successor (overwritten / deleted)
      val      the op's payload (char code / value-table ref)
      counter  accumulated inc deltas for the lane's op, bit-packed as
               (sum << 2) | count-bits, where the count bits are 0, 1,
               or 3 (3 = two or more incs consumed) — the reference defers
               a counter element's whole-doc patch through its counter
               state, and the edit shape depends on the count (0 or 1 inc
               emits `insert`, >= 2 emits `update` via the transient
               remove->update conversion) — so the patch walk replays a
               shape-equivalent row sequence; display value =
               val + (counter >> 2), ref new.js:937-965

    Plus [N] allocation cursors `n` and [N] `inexact` flags (device state
    diverged from reference semantics — self conflicts, pred overflow,
    unknown referents — so reads must come from the host mirror, cf.
    registers.RegisterState)."""

    def __init__(self, elem_id, nxt, reg, killed, val, counter, n,
                 inexact=None):
        self.elem_id = elem_id
        self.nxt = nxt
        self.reg = reg
        self.killed = killed
        self.val = val
        self.counter = counter
        self.n = n              # slots allocated per doc
        if inexact is None:
            # .shape is static even on tracers, so this default is jit-safe
            inexact = np.zeros((n.shape[0],), dtype=bool)
        self.inexact = inexact  # row needs the host mirror for reads

    @property
    def capacity(self):
        return self.elem_id.shape[1] - 3

    @property
    def actor_slots(self):
        return self.reg.shape[1] // self.elem_id.shape[1]

    @classmethod
    def empty(cls, n_docs, capacity, actor_slots=DEFAULT_ACTOR_SLOTS, xp=np):
        nodes = (n_docs, capacity + 3)
        lanes = (n_docs, actor_slots * (capacity + 3))
        nxt = xp.full(nodes, END, dtype=np.int32)
        return cls(
            xp.zeros(nodes, dtype=np.int32),
            nxt,
            xp.zeros(lanes, dtype=np.int32),
            xp.zeros(lanes, dtype=bool),
            xp.zeros(lanes, dtype=np.int32),
            xp.zeros(lanes, dtype=np.int32),
            xp.zeros((n_docs,), dtype=np.int32),
            xp.zeros((n_docs,), dtype=bool))

    def tree_flatten(self):
        return ((self.elem_id, self.nxt, self.reg, self.killed, self.val,
                 self.counter, self.n, self.inexact), None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def lane_segments(arr, actor_slots):
    """The A node-indexed segments of a lane array [N, A * nodes], each
    [N, nodes]."""
    nodes = arr.shape[1] // actor_slots
    return [arr[:, l * nodes:(l + 1) * nodes] for l in range(actor_slots)]


def grow_seq_state(state, n_rows, capacity, actor_slots=None):
    """Host-side resize to at least (n_rows rows, capacity slots,
    actor_slots lanes): new rows/slots/lanes are zeroed/END-filled; existing
    node ids and actor lanes never move (the sentinels are front-anchored
    precisely so capacity can grow by appending at the tail). Returns
    `state` unchanged if already big enough."""
    old_r, old_nodes = state.elem_id.shape
    old_cap = old_nodes - 3
    old_a = state.actor_slots
    want_a = old_a if actor_slots is None else actor_slots
    if n_rows <= old_r and capacity <= old_cap and want_a <= old_a:
        return state
    r, cap = max(n_rows, old_r), max(capacity, old_cap)
    a = max(want_a, old_a)

    def pad(arr, fill, dtype):
        out = jnp.full((r, cap + 3), fill, dtype=dtype)
        return out.at[:old_r, :old_nodes].set(arr)

    def pad_lane(arr, fill, dtype):
        segments = [pad(lane, fill, dtype)
                    for lane in lane_segments(arr, old_a)]
        segments += [jnp.full((r, cap + 3), fill, dtype=dtype)] * (a - old_a)
        return jnp.concatenate(segments, axis=1)

    def pad_vec(arr, dtype):
        out = jnp.zeros((r,), dtype=dtype)
        return out.at[:old_r].set(arr)

    return SeqState(
        pad(state.elem_id, 0, jnp.int32),
        pad(state.nxt, END, jnp.int32),
        pad_lane(state.reg, 0, jnp.int32),
        pad_lane(state.killed, False, bool),
        pad_lane(state.val, 0, jnp.int32),
        pad_lane(state.counter, 0, jnp.int32),
        pad_vec(state.n, jnp.int32),
        pad_vec(state.inexact, bool))


class SeqOpBatch:
    """One batch of sequence ops, parallel columns [N, P].

    - kind   int32: PAD / INSERT / SET / DEL
    - ref    int32: INSERT → packed elemId to insert after (0 = head);
                    SET/DEL → packed elemId of the target element
    - packed int32: the op's own packed opId (INSERT: the new elemId)
    - value  int32: INSERT/SET payload
    - preds  int32 [N, P, SEQ_PRED_LANES]: packed opIds this op supersedes
      (0 = unused lane, negative = pred naming an actor unknown to the
      fleet). The device kills exactly these lanes in the target element's
      register; concurrent ops survive (multi-value / resurrection
      semantics, ref new.js:1204-1217).
    - kind INC increments a counter element: ref targets the element,
      value carries the delta, preds name the counter set op(s) — the
      Lamport-max pred is the attribution target (new.js:942-945).
    - flag   bool: host-detected inexactness for this row (pred-lane
      overflow, object elements in Text rows): applied unconditionally.
    - actor_mask int32 [N] or None: the actor bits of each row's ids
      (ACTOR_MASK in the fleet-wide layout, fewer in a wide row's); None
      where every row is in the fleet-wide layout.
    - ordered bool [] or None: every row that has an op holds its allocated
      slots' ids in ascending order, so the referent lookup may search
      (_referent_lookup). A traced value: a dispatch of either kind runs
      the same program. None, where the search would not pay
      (search_pays), scans, in a program without the search.
    """

    def __init__(self, kind, ref, packed, value, preds=None, flag=None,
                 actor_mask=None, ordered=None):
        self.kind = kind
        self.ref = ref
        self.packed = packed
        self.value = value
        shape = np.shape(kind)
        if preds is None:
            preds = np.zeros(shape + (SEQ_PRED_LANES,), dtype=np.int32)
        self.preds = preds
        self.flag = np.zeros(shape, dtype=bool) if flag is None else flag
        self.actor_mask = actor_mask
        self.ordered = ordered

    def tree_flatten(self):
        return ((self.kind, self.ref, self.packed, self.value, self.preds,
                 self.flag, self.actor_mask, self.ordered), None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


register_pytrees(SeqState, SeqOpBatch)


def _pick(at, row):
    """The element of `row` where the mask `at` holds; `at` holds in one
    place at most, and where in none this reads 0."""
    return jnp.sum(jnp.where(at, row, 0))


def _apply_one_doc(carry, op, elem_id, nxt, n0, actor_mask):
    """One op against one doc. `elem_id`, `nxt` and `n0` are the row as the
    dispatch found it, read and never written here; the batch's splices
    live in the carry's overlay (see _apply_seq_batch_impl). `actor_mask`
    is the row's actor bits.
    carry = (ov_id, ov_nxt, rp_node, rp_nxt, reg, killed, val, counter, n,
    inexact); the op's `in_row` is its referent's node in the row as the
    dispatch found it, or `nodes` (_referent_lookup).

    What is indexed and what is selected. The step reads or writes ONE
    element of many arrays at a computed place. Of the four lane arrays
    (`reg`, `killed`, `val`, `counter`: A * nodes a row, up to a million
    elements) it gathers the target's A lanes once and scatters them back
    once, and `elem_id` and `nxt` it only gathers from: those are as long
    as the document, and an index is the only way to touch one element of
    them without reading the rest. The SMALL arrays — the overlay, as wide
    as the batch, and the target's register row, A wide — are not indexed
    at all: a write is `where(iota == at, new, old)`, a read a sum over the
    same compare (`_pick`). On the chip a scatter in a scan step is a
    program of its own, 7 to 14 us for 128 rows however little it moves,
    and a gather 1 to 2: the ten scatters and some fourteen gathers that
    these arrays took were half of the step, 0.22 ms, and without them it
    is 0.12 (PERF.md section 6, PR 36). Compares and selects over
    [rows, width] and [rows, A] fuse with their neighbours into a few
    passes, and cost less than the scatters at every width read, 4 to
    16,384: there is one form and no threshold. The masked forms keep
    their meaning: a place of `width` (a write that is masked) or a
    negative one (a read that the outer `where` throws away) matches no
    entry."""
    ov_id, ov_nxt, rp_node, rp_nxt, reg, killed, val, counter, n, \
        inexact = carry
    kind, ref, in_row, packed, value, preds, flag = op
    # lane l of node i is at l * nodes + i of the lane arrays
    nodes = elem_id.shape[0]
    capacity = nodes - 3
    lanes = np.arange(reg.shape[0] // nodes, dtype=np.int32)
    new0 = SLOT0 + n0       # the first node this batch allocates

    width = ov_id.shape[0]
    entries = np.arange(width, dtype=np.int32)

    def id_at(j):
        return jnp.where(j >= new0, _pick(entries == j - new0, ov_id),
                         elem_id[j])

    def nxt_at(j):
        """(the node after j, the entry of j's pair or -1)"""
        pair = rp_node == j         # at most one: an old node has one entry
        e = jnp.max(jnp.where(pair, entries, -1))
        old = jnp.where(e >= 0, _pick(pair, rp_nxt), nxt[j])
        return jnp.where(j >= new0, _pick(entries == j - new0, ov_nxt),
                         old), e

    is_ins = kind == INSERT
    is_upd = (kind == SET) | (kind == DEL)
    is_inc = kind == INC

    # Referent / target node: the row's own (`in_row`, looked up before
    # the scan) or one of the batch's inserts, found by an equality one-hot
    # over the overlay. A miss (op referencing an elemId not in the doc,
    # e.g. one dropped by a capacity overflow) must not resolve to an
    # arbitrary slot.
    in_batch = jnp.min(jnp.where(ov_id == ref, entries, width))
    found = (in_row < nodes) | (in_batch < width)
    match = jnp.where(in_row < nodes, in_row,
                      jnp.where(in_batch < width, new0 + in_batch, 0))

    # ---- INSERT: RGA splice -------------------------------------------
    # Start after the referent (HEAD sentinel for ref==0), then skip any
    # following elements whose insertion opId is greater than ours — the
    # concurrent-insert rule (ref new.js:145-163; op_set.insert_rga).
    r0 = jnp.where(ref == HEAD_REF, jnp.int32(HEAD), match)
    # Non-insert ops must not walk: an impossible comparison key stalls the
    # loop immediately.
    my_key = jnp.where(is_ins, packed, INT32_MAX)

    def skip_cond(state):
        r, j, h, e = state
        # Sentinels/scratch hold elem_id 0, which can never exceed a real
        # packed opId, so the walk stops at END (or list end) by itself; the
        # hop counter is a termination backstop so a corrupted/cyclic nxt
        # chain cannot hang the device kernel (a well-formed list has at
        # most capacity+3 nodes).
        return (id_at(j) > my_key) & (h < capacity + 3)

    def skip_body(state):
        r, j, h, e = state
        after, e_j = nxt_at(j)
        return j, after, h + 1, e_j

    after, e_r0 = nxt_at(r0)
    r, j, _, e_r = lax.while_loop(skip_cond, skip_body,
                                  (r0, after, jnp.int32(0), e_r0))

    # Inserts past capacity or after an unknown referent are dropped
    # (reported via the per-op applied flag) rather than silently corrupting
    # state: scratch and the sentinels must never be written by a live
    # insert, and a missed referent lookup must not splice after node 0.
    can_ins = is_ins & (n < capacity) & ((ref == HEAD_REF) | found)
    slot = SLOT0 + jnp.minimum(n, capacity - 1)  # allocation cursor, clamped

    # The splice, in the overlay: entry k is the new slot's id and next
    # pointer (k counts this batch's inserts, so it is below the width);
    # the node before it is a slot of this batch, or an old node whose pair
    # is rewritten if the batch has repointed it before and else takes
    # entry k (one entry a node: the write-back needs no order). A write
    # that is masked is placed past the overlay's end, where no entry is.
    k = n - n0
    at_k = entries == jnp.where(can_ins, k, width)
    at_r = entries == jnp.where(can_ins & (r >= new0), r - new0, width)
    at_e = entries == jnp.where(can_ins & (r < new0),
                                jnp.where(e_r >= 0, e_r, k), width)
    ov_id = jnp.where(at_k, packed, ov_id)
    ov_nxt = jnp.where(at_k, j, jnp.where(at_r, slot, ov_nxt))
    rp_node = jnp.where(at_e, r, rp_node)
    rp_nxt = jnp.where(at_e, slot, rp_nxt)
    n = n + can_ins.astype(jnp.int32)

    # ---- SET / DEL / INC: exact multi-value register update -------------
    # ref == HEAD_REF (0) marks a malformed update (no target): it would
    # "match" every unallocated slot's zero elem_id, so reject it explicitly.
    upd_ok = is_upd & found & (ref != HEAD_REF)
    inc_ok = is_inc & found & (ref != HEAD_REF)
    # (an insert's node is its fresh slot: no rule below touches its row,
    # and its lane 0 is written with the row, further down)
    tgt = jnp.where(can_ins, slot,
                    jnp.where(upd_ok | inc_ok, match, jnp.int32(SCRATCH)))
    lanes_at = tgt + nodes * lanes    # the target's A lanes
    reg_row = reg[lanes_at]
    killed_row = killed[lanes_at]
    val_row = val[lanes_at]
    counter_row = counter[lanes_at]

    # Kill preds: a pred's lane is the one that holds exactly the pred'd
    # op (lanes are an unordered conflict set, found by value); a pred
    # naming an already-superseded op finds none, a legitimate no-op succ
    # entry that the reference also accepts. Concurrent ops are never
    # killed — that is the multi-value / resurrection rule
    # (new.js:1204-1217). A negative pred names an actor unknown to the
    # fleet and flags the row.
    held = jnp.any((preds[:, None] > 0) & (reg_row == preds[:, None]),
                   axis=0)          # [A]: the lane holds a pred'd op
    bad_pred = (upd_ok | inc_ok) & jnp.any(preds < 0)
    killed_row = killed_row | (upd_ok & held)

    # INC: counter attribution follows the reference (new.js:942-945):
    # the inc is consumed by its LAMPORT-MAX pred (even a dead one); it
    # accumulates into that lane iff the lane still holds the op live, and
    # every OTHER live pred'd lane hides forever (its counter state never
    # completes). Same rule as registers._apply_step.
    max_pred = jnp.max(jnp.where(is_inc & (preds > 0), preds, 0))
    any_live_hit = inc_ok & jnp.any(held & ~killed_row)
    max_hit = (max_pred != 0) & (reg_row == max_pred) & ~killed_row
    max_live = inc_ok & jnp.any(max_hit)
    s_max = jnp.argmax(max_hit).astype(jnp.int32)
    at_s = lanes == s_max
    # (sum << 2) | count-bits packing (bits 0 -> 1 -> 3, 3 = "two or
    # more", saturating) — see the SeqState docstring. The shifted add
    # leaves the count bits alone. The ingest-side guards bound each
    # DELTA to +/-2^29, but the accumulated SUM can still leave the
    # packed envelope (two +2^28 incs): flag the row inexact when it
    # does, mirroring the bulk loader's counter_over rule, so live-applied
    # and bulk-loaded replicas agree instead of wrapping silently.
    old_cnt = _pick(at_s, counter_row)
    new_sum = (old_cnt >> 2) + value
    bad_sum = max_live & (jnp.abs(new_sum) >= jnp.int32(1 << 29))
    stepped = (old_cnt & ~3) + (value << 2)
    stepped = stepped | jnp.where((old_cnt & 3) == 0, 1, 3)
    counter_row = jnp.where(at_s & max_live, stepped, counter_row)
    killed_row = killed_row | (inc_ok & held & (reg_row != max_pred))
    bad_inc = inc_ok & ~any_live_hit & ~max_live

    # SET: occupy the lane that holds this actor's op on the element, else
    # the first empty one. If the actor's lane already holds a live op this
    # op did NOT pred, the reference would keep both visible — outside the
    # one-op-per-actor shape (only constructible by hand-built changes), so
    # flag the doc instead of losing data. No lane left (more writers on
    # the element than the pool has lanes: the fleet widens a pool before
    # that, from the actors that wrote the row) flags the row too.
    is_set_live = upd_ok & (kind == SET)
    mine = (reg_row != 0) & ((reg_row & actor_mask) == (packed & actor_mask))
    empty = reg_row == 0
    a_ok = jnp.any(mine | empty)
    a_c = jnp.where(jnp.any(mine), jnp.argmax(mine),
                    jnp.argmax(empty)).astype(jnp.int32)
    at_c = lanes == a_c
    own_prev = _pick(at_c, reg_row)
    own_pred = jnp.any(preds == own_prev)
    self_conflict = is_set_live & a_ok & (own_prev != 0) & \
        ~jnp.any(at_c & killed_row) & ~own_pred & (own_prev != packed)
    no_lane = is_set_live & ~a_ok

    w_set = is_set_live & a_ok
    # Reclaiming a lane whose previous op consumed incs loses the dead
    # counter's phantom-remove patch trace (the reference's dangling inc
    # rows still emit edits for it): flag the row inexact instead
    reclaim_incd = w_set & ((_pick(at_c, counter_row) & 3) != 0)
    reg_row = jnp.where(at_c & w_set, packed, reg_row)
    killed_row = killed_row & ~(at_c & w_set)
    val_row = jnp.where(at_c & w_set, value, val_row)
    counter_row = jnp.where(at_c & w_set, 0, counter_row)

    # An insert takes a fresh slot, whose lanes are all empty: the element's
    # first op (the insert IS its first set op) goes in lane 0. Only
    # `killed` can change in more than one lane of the row (the preds), so
    # its A lanes are scattered; of the others one element is written, the
    # set's or insert's lane or the inc's, picked out of the row by a
    # select and placed by arithmetic (lane l of the target is at
    # tgt + l * nodes): a scatter into a lane array takes the device as
    # long as it has elements, and each array has one in a step.
    w_lane = jnp.where(can_ins, 0, a_c)
    c_lane = jnp.where(is_inc, s_max, w_lane)
    at_w = lanes == w_lane
    killed_row = killed_row & ~(at_w & can_ins)
    reg = reg.at[tgt + nodes * w_lane].set(
        jnp.where(can_ins, packed, _pick(at_w, reg_row)))
    val = val.at[tgt + nodes * w_lane].set(
        jnp.where(can_ins, value, _pick(at_w, val_row)))
    counter = counter.at[tgt + nodes * c_lane].set(
        jnp.where(can_ins, 0, _pick(lanes == c_lane, counter_row)))
    killed = killed.at[lanes_at].set(killed_row)

    # Dropped ops (over-capacity or unknown-referent inserts, SET/DELs on
    # unknown targets) report as not-applied so callers can detect loss from
    # the stats instead of getting silent truncation.
    applied = jnp.where(is_ins, can_ins, jnp.where(is_inc, inc_ok, upd_ok))
    # Inexactness: host-flagged ops (pred overflow), any dropped live op,
    # a set with no lane left, self conflicts, preds naming unknown actors,
    # and incs with no consumable target
    inexact = inexact | flag | self_conflict | bad_pred | no_lane | \
        bad_inc | bad_sum | reclaim_incd | ((kind > PAD) & ~applied)
    return (ov_id, ov_nxt, rp_node, rp_nxt, reg, killed, val, counter, n,
            inexact), applied


# Nodes of a row that the referent lookup compares with a batch's refs at a
# time. The chip settled it (PERF.md section 6, PR 32): the lookup is bound
# by the compares, 2.4 ms a dispatch at 128 rows x 64 refs x 262,147 nodes
# where one pass over the row for every ref took 11.6; blocks of 1,024 to
# 8,192 nodes read within a tenth of one another, and the whole row at once
# or blocks of 32,768 were slower at that shape and at 64 rows x 16,384 refs.
LOOKUP_BLOCK = 2048


def _referent_lookup(elem_id, ref, n=None, ordered=None):
    """[N, P]: for every op cell the least node of its row whose `elem_id`
    is the cell's `ref`, or `nodes` where the row has none. Packed elemIds
    are unique and non-zero, so an equality one-hot over the node axis finds
    the referent; `ref == 0` (a head insert, a PAD cell) finds node 0, since
    the sentinels and unallocated slots keep elem_id 0. The row is what the
    dispatch found: the batch's own inserts are looked up in the scan.

    Where `ordered` (a traced bool) holds, every row that has a ref other
    than 0 keeps the ids of its `n` allocated slots in ascending order, and
    the lookup is a binary search of them (_referent_search), with the same
    answers; else, and where `ordered` is None, it scans (_referent_scan).
    Either way it is one program: the search runs always, and the scan's
    loop over blocks runs no round where `ordered` holds. (A `lax.cond` of
    the two had the chip's compiler copy the row into each branch's own
    layout: 134 MB a dispatch at 128 rows of 262,147 nodes.)"""
    with jax.named_scope('seq.referent_lookup'):
        if ordered is None:
            return _referent_scan(elem_id, ref)
        return jnp.where(ordered, _referent_search(elem_id, ref, n),
                         _referent_scan(elem_id, ref, ordered))


def search_rounds(nodes):
    """Halvings the binary search takes to close a range of up to
    `nodes - SLOT0` slots."""
    return max(nodes - SLOT0, 1).bit_length()


# What a searched id costs the chip against a node the block scan compares
# with one ref: a round of the search gathers one id a cell, 140 us for the
# 8,192 cells of 128 rows x 64 refs, where the scan compares those refs with
# 262,147 nodes a row in 2.3 ms (the chip, PERF.md section 6, PR 46). So
# the search pays where a row has more than about this many nodes a round.
SEARCH_NODES_PER_ROUND = 16384


def search_pays(nodes):
    """Whether a dispatch over rows of `nodes` nodes in id order should
    search them: from the 2^19-slot class up, not at 2^18 and below."""
    return nodes >= SEARCH_NODES_PER_ROUND * search_rounds(nodes)


def _referent_search(elem_id, ref, n):
    """_referent_lookup for rows whose allocated slots [SLOT0, SLOT0 + n)
    hold ascending ids: a lower bound of each ref among them, in
    search_rounds(nodes) rounds of one [N, P] gather, then an equality
    check. Where a row repeats an id the lower bound is its least node, as
    the scan's is."""
    nodes = elem_id.shape[1]

    def halve(_, bounds):
        lo, hi = bounds
        mid = jnp.minimum((lo + hi) >> 1, nodes - 1)
        below = jnp.take_along_axis(elem_id, mid, axis=1) < ref
        live = lo < hi
        return (jnp.where(live & below, mid + 1, lo),
                jnp.where(live & ~below, mid, hi))

    lo, _ = lax.fori_loop(
        0, search_rounds(nodes), halve,
        (jnp.full(ref.shape, SLOT0, jnp.int32),
         jnp.broadcast_to((SLOT0 + n)[:, None], ref.shape)))
    # past a row's last allocated slot lie 0s, which only ref 0 equals
    at = jnp.take_along_axis(elem_id, jnp.minimum(lo, nodes - 1), axis=1)
    found = jnp.where(at == ref, lo, nodes)
    return jnp.where(ref == HEAD_REF, jnp.int32(HEAD), found)


def _referent_scan(elem_id, ref, skip=None):
    """_referent_lookup for any row: the row is read once, a block of nodes
    at a time, and each block is compared with all of its row's refs while
    it is on the chip; the compare, select and min of a block fuse, so the
    [N, P, nodes] product is never laid out, and where a backend does not
    fuse them (XLA's CPU one) what it lays out is a block wide. Where the
    traced bool `skip` holds, the loop reads no block."""
    nodes = elem_id.shape[1]
    # blocks tile the slots (a pool's capacity is a power of two); what is
    # left over, the three nodes of the sentinels' offset, is a block of
    # its own
    block = max(min(LOOKUP_BLOCK, nodes - SLOT0), 1)

    def among(start, size):
        part = lax.dynamic_slice_in_dim(elem_id, start, size, axis=1)
        node_ids = start + lax.iota(jnp.int32, size)
        return jnp.min(jnp.where(part[:, None, :] == ref[:, :, None],
                                 node_ids, nodes), axis=2)

    blocks = nodes // block
    if skip is not None:
        blocks = jnp.where(skip, 0, blocks)
    least = lax.fori_loop(
        0, blocks,
        lambda k, least: jnp.minimum(least, among(k * block, block)),
        jnp.full(ref.shape, nodes, jnp.int32))
    rest = nodes % block
    if rest:
        least = jnp.minimum(least, among(nodes - rest, rest))
    return least


def _apply_seq_batch_impl(state, ops):
    """Deferred splice: inside the scan `elem_id` and `nxt` are read-only
    (an array the scan step wrote while the skip walk's `while` held it was
    copied whole every step), and the batch's splices live in a per-row
    overlay as wide as the batch: the ids and next pointers of the slots it
    allocates (slot SLOT0 + n0 + k is entry k, n0 the row's cursor at
    entry) and the repointed old nodes as (node, new next) pairs. Each
    array takes the overlay in one scatter after the scan. For the same
    reason every op's referent among the row's old nodes is found before
    the scan, for the whole batch at once (_referent_lookup: a binary
    search where `ops.ordered` holds, else one pass over the rows), and not
    by a search of `elem_id` in every scan step."""
    rows, width = ops.kind.shape
    width = max(width, 1)       # an empty batch still traces the step
    actor_mask = jnp.full((rows,), ACTOR_MASK, jnp.int32) \
        if ops.actor_mask is None else ops.actor_mask

    def per_doc(elem_id, nxt, reg, killed, val, counter, n, inexact,
                kind, ref, in_row, packed, value, preds, flag, actor_mask,
                zeros):
        carry = (zeros, zeros, zeros - 1, zeros, reg, killed, val, counter,
                 n, inexact)
        xs = (kind, ref, in_row, packed, value, preds, flag)
        carry, applied = lax.scan(
            lambda c, x: _apply_one_doc(c, x, elem_id, nxt, n, actor_mask),
            carry, xs)
        ov_id, ov_nxt, rp_node, rp_nxt, reg, killed, val, counter, n_out, \
            inexact = carry
        # live entries name distinct nodes; the others go past the row's
        # end and are dropped, like the scan's masked overlay writes
        nodes = elem_id.shape[0]
        new = SLOT0 + n + jnp.arange(width, dtype=jnp.int32)
        new = jnp.where(new < SLOT0 + n_out, new, nodes)
        elem_id = elem_id.at[new].set(ov_id, mode='drop')
        nxt = nxt.at[jnp.concatenate(
            [new, jnp.where(rp_node >= 0, rp_node, nodes)])].set(
                jnp.concatenate([ov_nxt, rp_nxt]), mode='drop')
        return (elem_id, nxt, reg, killed, val, counter, n_out, inexact), \
            jnp.sum(applied, dtype=jnp.int32)

    carry, applied = jax.vmap(per_doc)(
        state.elem_id, state.nxt, state.reg, state.killed, state.val,
        state.counter, state.n, state.inexact, ops.kind, ops.ref,
        _referent_lookup(state.elem_id, ops.ref, state.n, ops.ordered),
        ops.packed, ops.value,
        ops.preds, ops.flag, actor_mask,
        # the empty overlay, batched like the rest of the scan's carry
        jnp.zeros((rows, width), jnp.int32))
    return SeqState(*carry), jnp.sum(applied)


apply_seq_batch = instrument_kernel(
    'apply_seq_batch', jax.jit(_apply_seq_batch_impl))
# In-place variant for the fleet's own dispatch paths (see
# apply.apply_op_batch_donated)
apply_seq_batch_donated = instrument_kernel(
    'apply_seq_batch_donated',
    jax.jit(_apply_seq_batch_impl, donate_argnums=(0,)))


def _visible_impl(state):
    """Per-element visibility and Lamport winner from the registers:
    (vis [N, S+3] bool, winner [N, S+3] int32 packed, value [N, S+3],
    counter [N, S+3] — the winning lane's accumulated inc deltas)."""
    a = state.actor_slots
    vis = winner = value = cnt = None
    # lane by lane, [N, S+3] each: a later lane takes over where its live
    # op is the greater (live packed opIds are > 0 and distinct)
    for reg, killed, val, counter in zip(
            lane_segments(state.reg, a), lane_segments(state.killed, a),
            lane_segments(state.val, a), lane_segments(state.counter, a)):
        live = jnp.where((reg != 0) & ~killed, reg, 0)
        if winner is None:
            vis, winner, value, cnt = live != 0, live, val, counter
            continue
        better = live > winner
        vis = vis | (live != 0)
        value = jnp.where(better, val, value)
        cnt = jnp.where(better, counter, cnt)
        winner = jnp.maximum(winner, live)
    return vis, winner, value, cnt


element_visibility = instrument_kernel(
    'element_visibility', jax.jit(_visible_impl))


def _linearize_impl(state):
    """List-rank every node: returns (pos [N, S+3], length [N]).

    pos is node-indexed (sentinels at 0..2, real slots from SLOT0=3, a
    loaded row's in id order and each later insert's in the order it was
    applied): pos[d, SLOT0 + k] is the 0-based sequence index of the
    element in doc d's slot k; sentinel and unallocated entries are
    garbage — mask with SLOT0 <= node < SLOT0 + n.
    Pointer doubling (Wyllie's list ranking): dist[i] = hops from node i to
    END, accumulated over ceil(log2(nodes)) rounds of jumps. Then
    pos = dist[HEAD] - dist - 1.
    """
    nodes = state.nxt.shape[1]

    def per_doc(nxt):
        dist = jnp.ones((nodes,), dtype=jnp.int32).at[END].set(0)
        ptr = nxt.at[END].set(END)

        def round_(i, s):
            dist, ptr = s
            return dist + dist[ptr], ptr[ptr]

        steps = int(np.ceil(np.log2(nodes)))
        dist, ptr = lax.fori_loop(0, steps, round_, (dist, ptr))
        return dist[HEAD] - dist - 1

    pos = jax.vmap(per_doc)(state.nxt)
    return pos, state.n


linearize = instrument_kernel('linearize', jax.jit(_linearize_impl))


def _materialize_impl(state):
    """Return (vals [N, S], cnts [N, S], vis [N, S], length [N]) in
    sequence order.

    vals/cnts/vis are scattered into order positions; entries at index >=
    length are zeros. Visible-only extraction (for text strings / patch
    indexes) is a host-side compress over the vis mask. Values are the
    per-element Lamport winners over the visible register set (conflict
    sets render their winner, like the reference's applyProperties rule,
    frontend/apply_patch.js:57-79); cnts carry the winning lane's
    accumulated counter deltas (display value = val + cnt for counter
    payloads)."""
    capacity = state.elem_id.shape[1] - 3
    pos, n = _linearize_impl(state)
    e_vis, _winner, e_val, e_cnt = _visible_impl(state)

    def per_doc(pos, vis, val, cnt, n):
        node_ids = jnp.arange(capacity + 3, dtype=jnp.int32)
        alloc = (node_ids >= SLOT0) & (node_ids < SLOT0 + n)
        # Scatter into sequence order; masked lanes land on a trailing
        # scratch column that the [:capacity] slice drops
        tgt = jnp.where(alloc, jnp.clip(pos, 0, capacity), capacity)
        out_val = jnp.zeros((capacity + 1,), val.dtype).at[tgt].set(
            jnp.where(alloc, val, 0))
        out_cnt = jnp.zeros((capacity + 1,), cnt.dtype).at[tgt].set(
            jnp.where(alloc, cnt, 0))
        out_vis = jnp.zeros((capacity + 1,), jnp.bool_).at[tgt].set(
            jnp.where(alloc, vis, False))
        return out_val[:capacity], out_cnt[:capacity], out_vis[:capacity]

    vals, cnts, vis = jax.vmap(per_doc)(pos, e_vis, e_val, e_cnt, state.n)
    return vals, cnts, vis, state.n


materialize = instrument_kernel('materialize', jax.jit(_materialize_impl))


def visible_text(state):
    """Host helper: decode each doc's visible values as a Python string
    (values interpreted as Unicode code points)."""
    vals, _cnts, vis, n = jax.device_get(materialize(state))
    out = []
    for d in range(vals.shape[0]):
        row_vis = vis[d]
        out.append(''.join(chr(int(c)) for c in vals[d][row_vis]))
    return out


def element_conflicts(state, row):
    """Host read of one doc's per-element conflict sets: {packed elemId:
    {packed opId: value}} for every element whose visible register holds
    more than one op (the raw-engine view of what
    fleet.backend._FlatEngine._device_patch_diffs serves as patch edits)."""
    a = state.actor_slots                                   # to [S+3, A]
    reg = np.asarray(jax.device_get(state.reg[row])).reshape(a, -1).T
    killed = np.asarray(jax.device_get(state.killed[row])).reshape(a, -1).T
    val = np.asarray(jax.device_get(state.val[row])).reshape(a, -1).T
    elem = np.asarray(jax.device_get(state.elem_id[row]))
    live = (reg != 0) & ~killed
    out = {}
    for node in np.flatnonzero(live.sum(axis=-1) > 1):
        lanes = np.flatnonzero(live[node])
        out[int(elem[node])] = {int(reg[node, s]): int(val[node, s])
                                for s in lanes}
    return out


class SeqEncoder:
    """Host-side helper turning 'ctr@actor' string ops into SeqOpBatch
    columns for one fleet. Actor numbers are assigned by ascending hex order
    over a fixed, pre-registered actor set (required for packed-opId
    comparisons to match host Lamport order). SET/DEL ops default their
    pred to the target elemId (the element's insert op) when none is given —
    the common shape for linear edit traces."""

    def __init__(self, actors):
        self.actor_num = {a: i for i, a in enumerate(sorted(actors))}

    def pack(self, op_id):
        if op_id in ('_head', None):
            return HEAD_REF
        ctr_s, _, actor = op_id.partition('@')
        return pack_op_id(int(ctr_s), self.actor_num[actor])

    def batch(self, per_doc_ops, pad_to=None):
        """per_doc_ops: list (per doc) of op dicts
        {kind: 'insert'|'set'|'del', ref/target: opId str, id: opId str,
         value: int, pred: [opId str, ...]}. Returns a SeqOpBatch of numpy
        columns [N, P]."""
        n_docs = len(per_doc_ops)
        width = max((len(ops) for ops in per_doc_ops), default=0)
        if pad_to is not None:
            width = max(width, pad_to)
        kind = np.zeros((n_docs, width), dtype=np.int32)
        ref = np.zeros((n_docs, width), dtype=np.int32)
        packed = np.zeros((n_docs, width), dtype=np.int32)
        value = np.zeros((n_docs, width), dtype=np.int32)
        preds = np.zeros((n_docs, width, SEQ_PRED_LANES), dtype=np.int32)
        flag = np.zeros((n_docs, width), dtype=bool)
        kinds = {'insert': INSERT, 'set': SET, 'del': DEL,
                 'inc': INC}
        for d, ops in enumerate(per_doc_ops):
            for i, op in enumerate(ops):
                kind[d, i] = kinds[op['kind']]
                target = op.get('ref') or op.get('target')
                ref[d, i] = self.pack(target)
                packed[d, i] = self.pack(op['id'])
                value[d, i] = op.get('value', 0)
                pred_ids = op.get('pred')
                if pred_ids is None and op['kind'] in ('set', 'del'):
                    pred_ids = [target]
                pred_ids = pred_ids or []
                if len(pred_ids) > SEQ_PRED_LANES:
                    flag[d, i] = True
                    pred_ids = pred_ids[:SEQ_PRED_LANES]
                for l, p in enumerate(pred_ids):
                    preds[d, i, l] = self.pack(p)
                if op.get('flag'):
                    flag[d, i] = True
        return SeqOpBatch(kind, ref, packed, value, preds, flag)


def _copy_rows_impl(d, s, si, di):
    """State `d` with its rows `di` overwritten by rows `si` of state `s`
    (no more nodes than d's: a prefix copy, the END-filled tail stays
    inert). Where the lane widths differ the narrower one's lanes are
    copied: an element's used lanes are a prefix, and the caller has made
    `d` as wide as the rows' writers need."""
    nodes, d_nodes = s.elem_id.shape[1], d.elem_id.shape[1]
    lanes = min(s.actor_slots, d.actor_slots)

    def put(darr, sarr):
        return darr.at[di, :nodes].set(sarr[si])

    def put_lanes(darr, sarr):
        for l, lane in enumerate(lane_segments(sarr, s.actor_slots)[:lanes]):
            darr = darr.at[di, l * d_nodes:l * d_nodes + nodes].set(lane[si])
        return darr

    return SeqState(
        put(d.elem_id, s.elem_id), put(d.nxt, s.nxt),
        put_lanes(d.reg, s.reg), put_lanes(d.killed, s.killed),
        put_lanes(d.val, s.val), put_lanes(d.counter, s.counter),
        d.n.at[di].set(s.n[si]), d.inexact.at[di].set(s.inexact[si]))


_copy_rows = instrument_kernel('seq_copy_rows', jax.jit(_copy_rows_impl))


def _repack_rows_impl(state, rows, bits_in, bits_out, lut):
    """State with the ids of its rows `rows` ([k]) repacked: an id of row
    rows[i] packs (counter << bits_in[i]) | a, and becomes (counter <<
    bits_out[i]) | lut[i, a]. Zero stays zero. Only `elem_id` and `reg`
    hold ids; the pointers, payloads and flags stay where they are."""

    def repack(arr):
        part = arr[rows]
        b_in, b_out = bits_in[:, None], bits_out[:, None]
        actor = part & ((1 << b_in) - 1)
        new = ((part >> b_in) << b_out) | jnp.take_along_axis(
            lut, actor, axis=1)
        return arr.at[rows].set(jnp.where(part != 0, new, 0))

    return SeqState(repack(state.elem_id), state.nxt, repack(state.reg),
                    state.killed, state.val, state.counter, state.n,
                    state.inexact)


repack_rows = instrument_kernel(
    'seq_repack_rows',
    jax.jit(_repack_rows_impl, donate_argnums=(0,)))


class SeqPools:
    """Size-class pools of sequence rows.

    A single SeqState is rectangular: one 10k-element document would force
    every row in the fleet to 10k slots × A actor lanes — the long-document
    analogue of padding a whole batch to its longest member. Pools bucket
    rows by pow2 capacity class (class c holds rows of capacity
    `base << c`), so memory follows each document's own length; a row that
    outgrows its class migrates up by a prefix copy (front-anchored
    sentinels make the tail padding inert, see the node-layout note above).
    The per-flush cost is one apply dispatch per ACTIVE class instead of
    one total — bounded by log2(longest/base) — which is the same
    size-class trick the sync driver uses for variable Bloom filter sizes
    (fleet/bloom.py).

    Addressing: callers hold (cls, idx) placements; this object owns the
    per-class SeqStates, free lists, and growth/migration. It is
    deliberately host-side bookkeeping — all device work stays in the
    SeqState kernels."""

    def __init__(self, base_capacity=64):
        self.base = base_capacity
        self.pools = {}     # cls -> SeqState
        self.free = {}      # cls -> [idx, ...]
        self.used = {}      # cls -> high-water row count
        self.grow_events = 0   # device-copy growths (reserve() keeps this
                               # at ~1 per class per dispatch, not per row)

    def cls_for(self, capacity):
        c = 0
        while (self.base << c) < capacity:
            c += 1
        return c

    def capacity(self, cls):
        return self.base << cls

    def state(self, cls):
        return self.pools.get(cls)

    def _ensure(self, cls, n_rows, actor_slots):
        import jax.numpy as jnp
        pow2 = 1
        while pow2 < n_rows:
            pow2 *= 2
        st = self.pools.get(cls)
        if st is None:
            self.pools[cls] = SeqState.empty(
                pow2, self.capacity(cls), actor_slots=actor_slots, xp=jnp)
            self.grow_events += 1
        else:
            grown = grow_seq_state(st, pow2, self.capacity(cls),
                                   actor_slots)
            if grown is not st:
                self.grow_events += 1
            self.pools[cls] = grown
        return self.pools[cls]

    def alloc(self, cls, actor_slots):
        free = self.free.setdefault(cls, [])
        if free:
            # a pool built for rows with fewer writers must still widen
            # its lane axis before the recycled row is written
            self._ensure(cls, self.used.get(cls, 1), actor_slots)
            return free.pop()
        idx = self.used.get(cls, 0)
        self.used[cls] = idx + 1
        self._ensure(cls, idx + 1, actor_slots)
        return idx

    def reserve(self, cls, count, actor_slots):
        """Pre-size a pool for `count` upcoming alloc() calls in one
        growth, and widen it to `actor_slots` lanes: growing inside each
        alloc re-pads the whole pool's arrays eagerly on device per pow2
        step (~log2(rows) growths of 8 arrays each for a batch of fresh
        rows — a dispatch storm on a real TPU). Reservation is
        capacity-only; alloc() still does the bookkeeping, it just finds
        the pool already big enough."""
        fresh = max(count - len(self.free.get(cls, ())), 0)
        self._ensure(cls, self.used.get(cls, 0) + fresh, actor_slots)

    def release(self, cls, idx):
        """Zero a row and return it to its class's free list."""
        self.release_rows({cls: [idx]})

    def release_rows(self, by_cls):
        """Zero rows and return them to their free lists; one batched
        indexed update per touched class ({cls: [idx, ...]})."""
        import jax.numpy as jnp
        for cls, idxs in by_cls.items():
            st = self.pools.get(cls)
            live = [i for i in idxs if st is not None and
                    i < st.elem_id.shape[0]]
            if live:
                i = jnp.asarray(np.array(live, dtype=np.int32))
                self.pools[cls] = SeqState(
                    st.elem_id.at[i].set(0),
                    st.nxt.at[i].set(END),
                    st.reg.at[i].set(0),
                    st.killed.at[i].set(False),
                    st.val.at[i].set(0),
                    st.counter.at[i].set(0),
                    st.n.at[i].set(0),
                    st.inexact.at[i].set(False))
            self.free.setdefault(cls, []).extend(idxs)

    def copy_row(self, src, dst):
        """Copy row (cls, idx) -> (cls2, idx2); dst class must be >= src
        (prefix copy; END-filled tail stays inert)."""
        self.copy_rows(src[0], [src[1]], dst[0], [dst[1]])

    def copy_rows(self, src_cls, src_idxs, dst_cls, dst_idxs):
        """Batched row copies between two classes (dst capacity >= src),
        one program for all the arrays."""
        import jax.numpy as jnp
        self.pools[dst_cls] = _copy_rows(
            self.pools[dst_cls], self.pools[src_cls],
            jnp.asarray(np.array(src_idxs, dtype=np.int32)),
            jnp.asarray(np.array(dst_idxs, dtype=np.int32)))

    def migrate(self, cls, idx, new_cls, actor_slots):
        """Move a row to a bigger class; returns its new idx."""
        new_idx = self.alloc(new_cls, actor_slots)
        self.copy_row((cls, idx), (new_cls, new_idx))
        self.release(cls, idx)
        return new_idx
