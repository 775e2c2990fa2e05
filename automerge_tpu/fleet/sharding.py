"""Fleet sharding across a device mesh.

The parallelism story for a CRDT fleet (SURVEY.md §2.12): documents are
independent, so the fleet batch axis shards data-parallel across chips; the
per-document key grid can shard across a second mesh axis when the key
universe is large. XLA inserts the collectives (scatter updates crossing the
key axis become all-to-alls; fleet-wide stats are psums riding ICI).

No NCCL/MPI translation — this is `jax.sharding.Mesh` + NamedSharding over
the fleet pytree, the idiomatic JAX equivalent of the reference's
transport-agnostic peer protocol scaled to a sharded fleet service.
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .tensor_doc import FleetState
from .apply import apply_op_batch
from ..observability.perf import instrument_kernel


def fleet_mesh(devices=None, keys_axis=1):
    """Build a (docs, keys) mesh over the available devices."""
    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    if keys_axis > 1 and n % keys_axis == 0:
        shape = (n // keys_axis, keys_axis)
    else:
        shape = (n, 1)
    import numpy as np
    return Mesh(np.array(devices).reshape(shape), ('docs', 'keys'))


def fleet_sharding(mesh):
    """NamedShardings for FleetState ([docs, keys] grid) and OpBatch
    ([docs, ops] columns, replicated over the keys axis)."""
    state_spec = NamedSharding(mesh, P('docs', 'keys'))
    ops_spec = NamedSharding(mesh, P('docs', None))
    return state_spec, ops_spec


def shard_fleet(state, mesh):
    state_spec, _ = fleet_sharding(mesh)
    return FleetState(*(jax.device_put(x, state_spec)
                        for x in (state.winners, state.values, state.counters)))


def shard_ops(ops, mesh):
    _, ops_spec = fleet_sharding(mesh)
    import jax.tree_util as tree
    return tree.tree_map(lambda x: jax.device_put(x, ops_spec),
                         ops)


def seq_sharding(mesh):
    """NamedShardings for SeqState / SeqOpBatch, data-parallel over the docs
    axis only — the per-doc slot axis stays local (the RGA pointer walk is a
    per-document scan; sharding it would put pointer chasing on ICI). Arrays
    pick their spec by rank: [docs] vectors, [docs, slots] node arrays,
    [docs, lanes * slots] register arrays, and [docs, ops, preds] pred
    columns."""
    by_ndim = {1: NamedSharding(mesh, P('docs')),
               2: NamedSharding(mesh, P('docs', None)),
               3: NamedSharding(mesh, P('docs', None, None))}
    return by_ndim


def _put_by_ndim(tree_obj, by_ndim):
    import jax.tree_util as tree
    return tree.tree_map(
        lambda x: jax.device_put(x, by_ndim[x.ndim]), tree_obj)


def _constrain_by_ndim(tree_obj, by_ndim):
    import jax.tree_util as tree
    return tree.tree_map(
        lambda x: jax.lax.with_sharding_constraint(x, by_ndim[x.ndim]),
        tree_obj)


def shard_seq(state, mesh):
    return _put_by_ndim(state, seq_sharding(mesh))


def shard_seq_ops(ops, mesh):
    return _put_by_ndim(ops, seq_sharding(mesh))


def sharded_seq_apply(mesh):
    """Jitted sequence-fleet step, data-parallel over docs."""
    from .sequence import _apply_seq_batch_impl
    by_ndim = seq_sharding(mesh)

    def _step(state, ops):
        new_state, stats = _apply_seq_batch_impl(state, ops)
        return _constrain_by_ndim(new_state, by_ndim), stats
    return instrument_kernel('sharded_seq_apply', jax.jit(_step))


def long_seq_sharding(mesh):
    """NamedShardings for the LONG-document regime: a handful of very long
    sequences whose slot axis shards across every device of the mesh (the
    CRDT analogue of sequence/context parallelism, SURVEY.md §2.12/§5 — the
    document is too long for one chip's memory/bandwidth, so its element
    slots, pointers, and values stripe over the whole mesh)."""
    every_axis = mesh.axis_names
    by_ndim = {1: NamedSharding(mesh, P()),
               2: NamedSharding(mesh, P(None, every_axis)),
               3: NamedSharding(mesh, P(None, every_axis, None))}
    return by_ndim


def shard_long_seq(state, mesh):
    """Shard a long-document SeqState's node axis across the whole mesh,
    tail-padding to a device-count multiple first (safe because sentinels
    are front-anchored and padded tail slots read as unallocated)."""
    from .sequence import END, SeqState, lane_segments
    by_ndim = long_seq_sharding(mesh)
    n_dev = int(np.prod(mesh.devices.shape))
    size = state.elem_id.shape[1]
    pad = (-size) % n_dev

    def padded(x, fill):
        if pad == 0:
            return x
        out = jnp.full((x.shape[0], size + pad), fill, dtype=x.dtype)
        return out.at[:, :size].set(x)

    def padded_lanes(x, fill):
        # a lane array is A node-indexed segments side by side
        return jnp.concatenate(
            [padded(lane, fill)
             for lane in lane_segments(x, state.actor_slots)], axis=1)

    return SeqState(*(
        jax.device_put(arr, by_ndim[arr.ndim]) for arr in (
            padded(state.elem_id, 0), padded(state.nxt, END),
            padded_lanes(state.reg, 0), padded_lanes(state.killed, False),
            padded_lanes(state.val, 0), padded_lanes(state.counter, 0),
            jnp.asarray(state.n), jnp.asarray(state.inexact))))


def sharded_long_seq_apply(mesh):
    """Jitted op application for slot-sharded long documents. A dispatch
    looks all its ops' referents up at once, before the op scan
    (sequence._referent_lookup): the partitioner gathers `elem_id`, 4 of a
    node's 60 bytes, once a dispatch for it, where the lookup in the scan
    cost an all-reduce an op (read from the compiled program on virtual
    devices; not measured on chips). Per-op work is the RGA pointer walk's
    scalar gathers, an all-reduce each. Causality keeps the op stream itself
    sequential — the win is that the rest of the document's state never has
    to fit one chip."""
    from .sequence import _apply_seq_batch_impl
    by_ndim = long_seq_sharding(mesh)

    def _step(state, ops):
        new_state, stats = _apply_seq_batch_impl(state, ops)
        return _constrain_by_ndim(new_state, by_ndim), stats
    return instrument_kernel('sharded_long_seq_apply', jax.jit(_step))


def sharded_long_seq_materialize(mesh):
    """Jitted sequence-order extraction for slot-sharded long documents.

    This is the bandwidth-heavy read path and the part that genuinely
    parallelizes: pointer-doubling list ranking (Wyllie's algorithm) runs
    ceil(log2 S) rounds of gathers over the sharded pointer array, with XLA
    inserting the cross-shard collectives — the segmented-scan trick the
    survey names as the long-context equivalent (SURVEY.md §5)."""
    from .sequence import _materialize_impl
    slots = long_seq_sharding(mesh)[2]

    def _run(state):
        vals, cnts, vis, n = _materialize_impl(state)
        return (jax.lax.with_sharding_constraint(vals, slots),
                jax.lax.with_sharding_constraint(cnts, slots),
                jax.lax.with_sharding_constraint(vis, slots), n)
    return instrument_kernel('sharded_long_seq_materialize', jax.jit(_run))


def sharded_apply(mesh):
    """A jitted fleet step with explicit output shardings: data-parallel over
    docs, key grid sharded over the second mesh axis. The scatter by key_id
    crossing key shards compiles to XLA collectives; the stats reduction is a
    global psum over the mesh."""
    state_spec, _ = fleet_sharding(mesh)

    def _step(state, ops):
        new_state, stats = apply_op_batch(state, ops)
        new_state = FleetState(
            *(jax.lax.with_sharding_constraint(x, state_spec)
              for x in (new_state.winners, new_state.values, new_state.counters)))
        return new_state, stats
    return instrument_kernel('sharded_apply', jax.jit(_step))
