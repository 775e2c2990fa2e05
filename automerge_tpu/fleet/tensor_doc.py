"""Padded device-tensor representation of a document fleet.

A fleet of N map documents with a key universe of size K (dictionary-encoded
per fleet on the host) is:

- `winners`   [N, K+1] int32 — packed opId (counter << ACTOR_BITS | actorNum)
  of the LWW winner per key; 0 = key absent. Column K is a scratch slot that
  padded scatter lanes write into.
- `values`    [N, K+1] int32 — value-table index of the winner's value.
- `counters`  [N, K+1] int32 — accumulated increment total per key (counter
  CRDT semantics: inc ops add instead of overwriting; ref new.js:937-965).

Ops arrive as an OpBatch of parallel columns [N, P] (P = padded ops per doc),
mirroring the reference's columnar storage (ref backend/columnar.js:56-70)
so host decode feeds the device directly.

The packed-opId trick: Automerge op visibility means the LWW winner of a key
is simply the op with the greatest (counter, actorNum) among all set ops for
that key — an overwritten op always has a successor with a greater opId — so
per-key conflict resolution vectorizes to a scatter-max of packed opIds.
Deletion is a set with value TOMBSTONE (correct for causally-ordered deletes;
concurrent set-vs-delete resurrection routes through the host engine).
"""

import numpy as np

ACTOR_BITS = 8               # up to 256 distinct actors per fleet
MAX_ACTORS = 1 << ACTOR_BITS
# Packed counters occupy 23 bits (~8.4M) in the fleet-wide layout — a
# WINDOW, not a history cap. The LWW grid rebases each slot's window as
# counters grow (DocFleet.ctr_base / _rebase_slot): only a slot's
# live-winner counter spread is window-bounded (beyond that, reads use the
# host mirror). A sequence row cannot rebase (any old element can be a
# referent), so a row whose ids pass the window is repacked WIDE: its ids
# pack (counter, the actor's rank among the row's writers) with as many
# actor bits as its writers need (DocFleet._seq_wide), up to
# SEQ_CTR_LIMIT. The map register engine still packs raw counters.
CTR_LIMIT = 1 << (31 - ACTOR_BITS)
SEQ_CTR_LIMIT = 1 << 30
TOMBSTONE = -1               # value-table index marking a deleted key


def pack_op_id(counter, actor_num):
    """Pack (counter, actorNum) into one int32 preserving Lamport order."""
    if isinstance(counter, (int, np.integer)):
        if counter >= CTR_LIMIT:
            raise ValueError(f'op counter {counter} exceeds packing limit {CTR_LIMIT}')
        if actor_num >= MAX_ACTORS:
            raise ValueError(f'actor index {actor_num} exceeds {MAX_ACTORS}')
    return (counter << ACTOR_BITS) | actor_num


def unpack_op_id(packed):
    return packed >> ACTOR_BITS, packed & (MAX_ACTORS - 1)


class FleetState:
    """Immutable pytree of fleet tensors."""

    def __init__(self, winners, values, counters):
        self.winners = winners
        self.values = values
        self.counters = counters

    @classmethod
    def empty(cls, n_docs, n_keys, xp=np):
        shape = (n_docs, n_keys + 1)
        return cls(xp.zeros(shape, dtype=np.int32),
                   xp.zeros(shape, dtype=np.int32),
                   xp.zeros(shape, dtype=np.int32))

    def tree_flatten(self):
        return (self.winners, self.values, self.counters), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


class OpBatch:
    """One batch of ops for the whole fleet, as parallel columns [N, P].

    - key_id  int32: dictionary-encoded key (scratch column K for padding)
    - packed  int32: packed opId of the op
    - value   int32: value-table index (set ops) or increment delta (inc ops)
    - is_set  bool:  set/makeX/del op (participates in LWW)
    - is_inc  bool:  increment op (accumulates into counters)
    - valid   bool:  padding mask
    """

    def __init__(self, key_id, packed, value, is_set, is_inc, valid):
        self.key_id = key_id
        self.packed = packed
        self.value = value
        self.is_set = is_set
        self.is_inc = is_inc
        self.valid = valid

    def tree_flatten(self):
        return ((self.key_id, self.packed, self.value, self.is_set,
                 self.is_inc, self.valid), None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def register_pytrees(*classes):
    """Register container classes (with tree_flatten/tree_unflatten) as JAX
    pytree nodes; idempotent."""
    from jax import tree_util
    for klass in classes:
        try:
            tree_util.register_pytree_node(
                klass,
                lambda obj: obj.tree_flatten(),
                klass.tree_unflatten)
        except ValueError:
            pass  # already registered


register_pytrees(FleetState, OpBatch)
