"""Fleet engine tests: differential validation of the batched JAX kernels
against the host OpSet engine (the correctness oracle), Bloom wire
compatibility, and multi-device sharding on the virtual CPU mesh."""

import random

import numpy as np
import pytest

import automerge_tpu.backend as Backend
from automerge_tpu.backend.sync import BloomFilter
from automerge_tpu.columnar import encode_change
from automerge_tpu.fleet import (
    FleetState, OpBatch, apply_op_batch, pack_op_id,
    build_bloom_filters, probe_bloom_filters, bloom_filter_bytes,
)
from automerge_tpu.fleet import apply as fleet_apply
from automerge_tpu.fleet.bloom import hashes_to_words, num_filter_bits
from automerge_tpu.fleet.tensor_doc import ACTOR_BITS


def random_map_workload(rng, n_docs, n_keys, n_actors, rounds, ops_per_round):
    """Generate per-doc concurrent map-set/inc workloads. Returns a list of
    round batches: per doc, list of (key, ctr, actor, kind, value)."""
    workloads = []
    ctr_base = 1
    for _ in range(rounds):
        round_ops = []
        for d in range(n_docs):
            ops = []
            for i in range(ops_per_round):
                key = rng.randrange(n_keys)
                actor = rng.randrange(n_actors)
                ctr = ctr_base + i
                kind = 'set'
                value = rng.randrange(1, 1000)
                ops.append((key, ctr, actor, kind, value))
            round_ops.append(ops)
        workloads.append(round_ops)
        ctr_base += ops_per_round
    return workloads


def to_op_batch(round_ops, n_docs, max_ops):
    key_id = np.zeros((n_docs, max_ops), dtype=np.int32)
    packed = np.zeros((n_docs, max_ops), dtype=np.int32)
    value = np.zeros((n_docs, max_ops), dtype=np.int32)
    is_set = np.zeros((n_docs, max_ops), dtype=bool)
    is_inc = np.zeros((n_docs, max_ops), dtype=bool)
    valid = np.zeros((n_docs, max_ops), dtype=bool)
    for d, ops in enumerate(round_ops):
        for j, (key, ctr, actor, kind, val) in enumerate(ops):
            key_id[d, j] = key
            packed[d, j] = pack_op_id(ctr, actor)
            value[d, j] = val
            is_set[d, j] = kind == 'set'
            is_inc[d, j] = kind == 'inc'
            valid[d, j] = True
    return OpBatch(key_id, packed, value, is_set, is_inc, valid)


class TestFleetMergeDifferential:
    def test_lww_matches_host_engine(self):
        """The fleet kernel's per-key winners must match the host OpSet
        engine's visible values for concurrent multi-actor map workloads."""
        rng = random.Random(42)
        n_docs, n_keys, n_actors = 8, 12, 4
        rounds = 3
        ops_per_round = 10
        workloads = random_map_workload(rng, n_docs, n_keys, n_actors,
                                        rounds, ops_per_round)

        # Fleet path
        state = FleetState.empty(n_docs, n_keys)
        for round_ops in workloads:
            batch = to_op_batch(round_ops, n_docs, ops_per_round)
            state, _ = apply_op_batch(state, batch)
        winners = np.asarray(state.winners)
        values = np.asarray(state.values)

        # Host oracle: apply the same ops as binary changes, one doc at a time
        actors = [f'{i:02x}' * 3 for i in range(n_actors)]
        for d in range(n_docs):
            backend = Backend.init()
            seqs = {}
            # group by (round, actor): each actor's ops in one change
            for round_ops in workloads:
                by_actor = {}
                for (key, ctr, actor, kind, val) in round_ops[d]:
                    by_actor.setdefault(actor, []).append((key, ctr, kind, val))
                for actor, ops in by_actor.items():
                    ops.sort(key=lambda o: o[1])
                    start_op = ops[0][1]
                    # ops in a change must have consecutive counters; split runs
                    runs = []
                    run = [ops[0]]
                    for op in ops[1:]:
                        if op[1] == run[-1][1] + 1:
                            run.append(op)
                        else:
                            runs.append(run)
                            run = [op]
                    runs.append(run)
                    for run in runs:
                        seq = seqs.get(actor, 0) + 1
                        seqs[actor] = seq
                        change = {
                            'actor': actors[actor], 'seq': seq,
                            'startOp': run[0][1], 'time': 0, 'message': '',
                            'deps': Backend.get_heads(backend) if seq > 1 or True
                            else [],
                            'ops': [{'action': 'set', 'obj': '_root',
                                     'key': f'k{key}', 'value': val,
                                     'datatype': 'int', 'pred': []}
                                    for (key, ctr, kind, val) in run],
                        }
                        backend, _ = Backend.apply_changes(
                            backend, [encode_change(change)])
            patch = Backend.get_patch(backend)
            props = patch['diffs']['props']
            for key in range(n_keys):
                key_name = f'k{key}'
                if key_name in props:
                    # host LWW winner = greatest opId among the conflict set
                    host_values = props[key_name]
                    from automerge_tpu.common import lamport_key
                    win_op = max(host_values.keys(), key=lamport_key)
                    host_val = host_values[win_op]['value']
                    assert values[d, key] == host_val, \
                        f'doc {d} key {key}: fleet {values[d, key]} != host {host_val}'
                else:
                    assert winners[d, key] == 0

    def test_counters_accumulate(self):
        n_docs = 4
        state = FleetState.empty(n_docs, 2)
        # Round 1: create counters (set), round 2-3: concurrent incs
        b1 = to_op_batch([[(0, 1, a % 3, 'set', 10)] for a in range(n_docs)],
                         n_docs, 1)
        b2 = to_op_batch([[(0, 2 + a % 2, a % 3, 'inc', 5)] for a in range(n_docs)],
                         n_docs, 1)
        b3 = to_op_batch([[(0, 4, (a + 1) % 3, 'inc', 7)] for a in range(n_docs)],
                         n_docs, 1)
        for b in (b1, b2, b3):
            state, _ = apply_op_batch(state, b)
        counters = np.asarray(state.counters)
        values = np.asarray(state.values)
        # counter value = initial set value + accumulated incs
        assert all(values[:, 0] == 10)
        assert all(counters[:, 0] == 12)

    def test_padding_lanes_ignored(self):
        state = FleetState.empty(2, 3)
        batch = to_op_batch([[(0, 1, 0, 'set', 42)], []], 2, 4)
        state, stats = apply_op_batch(state, batch)
        assert int(stats) == 1
        values = np.asarray(state.values)
        winners = np.asarray(state.winners)
        assert values[0, 0] == 42
        assert np.all(winners[1, :3] == 0)


def random_batch(rng, n_docs, n_keys, ops_per_doc, ctr0=1):
    shape = (n_docs, ops_per_doc)
    key_id = rng.integers(0, n_keys, shape, dtype=np.int32)
    actor = rng.integers(0, 4, shape, dtype=np.int32)
    ctrs = ctr0 + np.broadcast_to(np.arange(ops_per_doc, dtype=np.int32), shape)
    packed = (ctrs.astype(np.int32) << ACTOR_BITS) | actor
    value = rng.integers(-50, 1000, shape, dtype=np.int32)
    is_set = rng.random(shape) < 0.7
    valid = rng.random(shape) < 0.9
    return OpBatch(key_id, packed, value, is_set, ~is_set, valid)


def assert_states_match(a, b, n_keys):
    """All real key columns; the scratch column absorbs masked scatter
    lanes by design and holds garbage."""
    for name in ('winners', 'values', 'counters'):
        got = np.asarray(getattr(a, name))[:, :n_keys]
        want = np.asarray(getattr(b, name))[:, :n_keys]
        np.testing.assert_array_equal(got, want, err_msg=name)


def without_incs(ops):
    return OpBatch(ops.key_id, ops.packed, ops.value, ops.is_set,
                   np.zeros_like(ops.is_inc), ops.valid)


# entry point -> (starts from a standing state, takes inc lanes, call);
# the donating ones consume the state they are handed
GRID_VARIANTS = {
    'donated': (True, True, lambda state, ops, n_docs, n_keys:
                fleet_apply.apply_op_batch_donated(state, ops)),
    'fresh': (False, True, lambda state, ops, n_docs, n_keys:
              fleet_apply.apply_op_batch_fresh(ops, n_docs, n_keys)),
    'kills_no_kill_lanes': (
        True, True, lambda state, ops, n_docs, n_keys:
        fleet_apply.apply_op_batch_kills(
            state, ops, np.zeros((n_docs, 4), np.int32),
            np.zeros((n_docs, 4), np.int32))),
    'noinc_donated': (
        True, False, lambda state, ops, n_docs, n_keys:
        fleet_apply.apply_op_batch_noinc_donated(state, ops)),
    'noinc_fresh': (False, False, lambda state, ops, n_docs, n_keys:
                    fleet_apply.apply_op_batch_noinc_fresh(ops, n_docs,
                                                           n_keys)),
}


@pytest.mark.parametrize('variant', sorted(GRID_VARIANTS))
@pytest.mark.parametrize('n_docs,n_keys,p', [
    (8, 17, 12),      # everything unaligned
    (128, 127, 32),   # 128 documents exactly
    (200, 300, 16),   # keys past 256
    (16, 40, 200),    # more ops a document than keys
    (8, 130, 300),    # both
])
def test_grid_variants_agree(n_docs, n_keys, p, variant):
    """Every grid entry point the fleet dispatches holds apply_op_batch's
    state on the same batch. The set-only kernels get the batch with its
    inc lanes cleared (their soundness gate: a counter-free grid); the
    fresh ones start from the empty grid they build inside the jit, the
    others from the state two earlier rounds left, one of older opIds
    than the batch's and one of newer, so its sets both win and lose."""
    standing, takes_incs, call = GRID_VARIANTS[variant]
    rng = np.random.default_rng(n_docs + n_keys)

    def batch(ctr0):
        ops = random_batch(rng, n_docs, n_keys, p, ctr0=ctr0)
        return ops if takes_incs else without_incs(ops)

    state = FleetState.empty(n_docs, n_keys)
    if standing:
        for ctr0 in (1, 1 + 2 * p):
            state, _ = apply_op_batch(state, batch(ctr0))
    ops = batch(1 + p if standing else 1)
    want, want_n = apply_op_batch(state, ops)
    got, got_n = call(state, ops, n_docs, n_keys)   # last: may consume state
    assert int(got_n) == int(want_n)
    assert_states_match(got, want, n_keys)


def grid_rule(state, ops):
    """The grid's merge rule stated in plain NumPy, one op at a time: per
    (doc, key) the largest packed opId among valid sets wins and its
    value is stored; a key whose winner changed restarts its counter;
    valid incs add. Imports nothing of fleet/apply.py."""
    winners, values, counters = (np.array(np.asarray(x)) for x in (
        state.winners, state.values, state.counters))
    key_id, packed, value, is_set, is_inc, valid = (
        np.asarray(c) for c in (ops.key_id, ops.packed, ops.value,
                                ops.is_set, ops.is_inc, ops.valid))
    before = winners.copy()
    n_docs, width = key_id.shape
    for d in range(n_docs):
        for j in range(width):
            k = key_id[d, j]
            if valid[d, j] and is_set[d, j] and packed[d, j] >= winners[d, k]:
                winners[d, k] = packed[d, j]
                values[d, k] = value[d, j]
    counters[winners != before] = 0
    for d in range(n_docs):
        for j in range(width):
            if valid[d, j] and is_inc[d, j]:
                counters[d, key_id[d, j]] += value[d, j]
    return FleetState(winners, values, counters), int(valid.sum())


def one_op_batch(n_docs, key, packed, value, is_set):
    full = lambda v, dtype: np.full((n_docs, 1), v, dtype)
    return OpBatch(full(key, np.int32), full(packed, np.int32),
                   full(value, np.int32), full(is_set, bool),
                   full(not is_set, bool), full(True, bool))


class TestGridRule:
    """apply_op_batch against grid_rule: the kernel's oracle that is not
    another JAX program."""

    def check_round(self, state, ops, n_keys):
        want, want_n = grid_rule(state, ops)
        got, got_n = apply_op_batch(state, ops)
        assert int(got_n) == want_n
        assert_states_match(got, want, n_keys)
        return got

    def test_duplicate_delivery_is_idempotent(self):
        """The same op delivered twice (same packed opId, same value: the
        sync path can re-send) selects the winner's value once; it does
        not sum it."""
        rng = np.random.default_rng(42)
        n_docs, n_keys, p = 12, 23, 160
        ops = random_batch(rng, n_docs, n_keys, p)
        cols = np.stack([ops.key_id, ops.packed, ops.value,
                         ops.is_set.astype(np.int32),
                         ops.is_inc.astype(np.int32),
                         ops.valid.astype(np.int32)])
        src = rng.integers(0, p // 2, 30)
        dst = p - 1 - rng.permutation(30)   # mirrored into the far lanes
        cols[:, :, dst] = cols[:, :, src]
        dup = OpBatch(cols[0], cols[1], cols[2], cols[3] != 0, cols[4] != 0,
                      cols[5] != 0)
        self.check_round(FleetState.empty(n_docs, n_keys), dup, n_keys)

    def test_multiple_rounds_carry_state(self):
        rng = np.random.default_rng(7)
        n_docs, n_keys = 16, 33
        state = FleetState.empty(n_docs, n_keys)
        for r in range(3):
            ops = random_batch(rng, n_docs, n_keys, 8, ctr0=1 + 8 * r)
            state = self.check_round(state, ops, n_keys)

    def test_counter_accumulation_and_overwrite(self):
        """Counters add across batches; a later set overwrites an earlier
        one and its accumulator restarts with it."""
        n_docs, n_keys = 4, 8
        key = np.zeros((n_docs, 2), dtype=np.int32)
        packed = np.tile(np.array([[1 << ACTOR_BITS, 2 << ACTOR_BITS]],
                                  dtype=np.int32), (n_docs, 1))
        value = np.tile(np.array([[5, 7]], dtype=np.int32), (n_docs, 1))
        is_set = np.tile(np.array([[True, False]]), (n_docs, 1))
        ops = OpBatch(key, packed, value, is_set, ~is_set,
                      np.ones((n_docs, 2), dtype=bool))
        state = self.check_round(FleetState.empty(n_docs, n_keys), ops,
                                 n_keys)
        assert np.asarray(state.values)[0, 0] == 5
        assert np.asarray(state.counters)[0, 0] == 7
        state = self.check_round(
            state, one_op_batch(n_docs, 0, 9 << ACTOR_BITS, 42, True), n_keys)
        assert np.asarray(state.values)[0, 0] == 42
        assert np.asarray(state.winners)[0, 0] == 9 << ACTOR_BITS
        assert np.asarray(state.counters)[0, 0] == 0

    def test_counter_reset(self):
        """A changed winner restarts the counter; the standing winner
        delivered again keeps it."""
        n_docs, n_keys = 4, 8
        state = FleetState.empty(n_docs, n_keys)
        for key, packed, value, is_set in (
                (0, 1 << ACTOR_BITS, 10, True),     # counter base
                (0, 2 << ACTOR_BITS, -4, False),    # negative inc
                (0, 1 << ACTOR_BITS, 10, True),     # delivered again: kept
                (0, 9 << ACTOR_BITS, 100, True),    # overwrite: restart
                (0, 11 << ACTOR_BITS, 2, False)):   # inc on the new winner
            state = self.check_round(
                state, one_op_batch(n_docs, key, packed, value, is_set),
                n_keys)
        assert np.asarray(state.counters)[0, 0] == 2
        assert np.asarray(state.values)[0, 0] == 100


class TestFleetBloom:
    def test_wire_compatible_with_host_bloom(self):
        """Batched filters must serialize byte-identically to the reference
        BloomFilter over the same hashes."""
        import hashlib
        n_docs, n_hashes = 5, 8
        hashes = [[hashlib.sha256(f'{d}:{i}'.encode()).hexdigest()
                   for i in range(n_hashes)] for d in range(n_docs)]
        words, valid = hashes_to_words(hashes)
        bits = build_bloom_filters(words, valid, n_hashes)
        for d in range(n_docs):
            batched = bloom_filter_bytes(np.asarray(bits)[d], n_hashes)
            host = BloomFilter(hashes[d]).bytes
            assert batched == host, f'doc {d} filter bytes differ'

    def test_batched_probe_matches_host(self):
        import hashlib
        n_docs, n_hashes = 4, 16
        member = [[hashlib.sha256(f'{d}:{i}'.encode()).hexdigest()
                   for i in range(n_hashes)] for d in range(n_docs)]
        queries = [[hashlib.sha256(f'q{d}:{i}'.encode()).hexdigest()
                    for i in range(n_hashes)] for d in range(n_docs)]
        words, valid = hashes_to_words(member)
        bits = build_bloom_filters(words, valid, n_hashes)
        qwords, qvalid = hashes_to_words(queries)
        batched = np.asarray(probe_bloom_filters(bits, qwords, qvalid))
        for d in range(n_docs):
            host = BloomFilter(member[d])
            for i, q in enumerate(queries[d]):
                assert batched[d, i] == host.contains_hash(q)

    def test_members_always_hit(self):
        import hashlib
        hashes = [[hashlib.sha256(f'{i}'.encode()).hexdigest()
                   for i in range(10)]]
        words, valid = hashes_to_words(hashes)
        bits = build_bloom_filters(words, valid, 10)
        hits = np.asarray(probe_bloom_filters(bits, words, valid))
        assert hits.all()


class TestFleetSharding:
    def test_sharded_apply_on_virtual_mesh(self):
        """Multi-device path: the fleet step under a (docs, keys) mesh on the
        8-device virtual CPU backend."""
        import jax
        from automerge_tpu.fleet.sharding import (
            fleet_mesh, shard_fleet, shard_ops, sharded_apply)
        if len(jax.devices()) < 2:
            pytest.skip('needs multiple devices')
        mesh = fleet_mesh(keys_axis=2)
        n_docs = 16
        n_keys = 15  # +1 scratch -> 16 columns, divisible by 2 key shards
        state = shard_fleet(FleetState.empty(n_docs, n_keys), mesh)
        batch = to_op_batch(
            [[(k % n_keys, 1 + k, k % 3, 'set', 100 + k) for k in range(4)]
             for _ in range(n_docs)], n_docs, 4)
        batch = shard_ops(batch, mesh)
        step = sharded_apply(mesh)
        new_state, stats = step(state, batch)
        assert int(stats) == n_docs * 4
        # Same result as the unsharded kernel
        ref_state, _ = apply_op_batch(FleetState.empty(n_docs, n_keys),
                                      to_op_batch(
            [[(k % n_keys, 1 + k, k % 3, 'set', 100 + k) for k in range(4)]
             for _ in range(n_docs)], n_docs, 4))
        np.testing.assert_array_equal(np.asarray(new_state.values),
                                      np.asarray(ref_state.values))
        np.testing.assert_array_equal(np.asarray(new_state.winners),
                                      np.asarray(ref_state.winners))
