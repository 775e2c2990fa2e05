"""Columnar turbo commit (ISSUE-12 "melt the serial floor"): the
struct-of-arrays doc state + lazily-folded log segments must be
byte-identical to the per-doc commit loop they replace — including over
parked docs (delta-tail append, parked-prefix log indexing, revive
through `changes`) — and the fast path must run with ZERO per-doc
commit-loop iterations (the regression guard that keeps the serial
floor from creeping back).
"""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from automerge_tpu.columnar import decode_change, encode_change  # noqa: E402
from automerge_tpu.fleet import backend as fleet_backend         # noqa: E402
from automerge_tpu.fleet.backend import (                        # noqa: E402
    DocFleet, init_docs, apply_changes_docs, park_docs)
from automerge_tpu import native                                 # noqa: E402

pytestmark = pytest.mark.skipif(not native.available(),
                                reason='columnar commit needs the native '
                                       'codec (turbo path)')


def _change(actor, seq, start_op, deps, key, val):
    return encode_change({
        'actor': actor, 'seq': seq, 'startOp': start_op, 'time': 0,
        'message': '', 'deps': list(deps),
        'ops': [{'action': 'set', 'obj': '_root', 'key': key,
                 'value': val, 'datatype': 'int', 'pred': []}]})


def _chain(actor, n, start_seq=1, deps=(), key='k', base=0):
    """A linear chain of n changes for one actor, returning (buffers,
    heads) continuing from `deps`."""
    out, heads = [], list(deps)
    for i in range(n):
        buf = _change(actor, start_seq + i, start_seq + i, heads, key,
                      base + i)
        heads = [decode_change(buf)['hash']]
        out.append(buf)
    return out, heads


def _apply_rounds(fleet, handles, rounds, base_seq=1):
    for r in range(rounds):
        per_doc = [[_change(f'{d:04x}' * 4, base_seq + r, base_seq + r,
                            fleet_backend.get_heads(handles[d]),
                            f'k{r}', d * 10 + r)]
                   for d in range(len(handles))]
        handles, _ = apply_changes_docs(handles, per_doc, mirror=False)
    return handles


class TestParkedColumnarCommit:
    """The delta+main write path through the columnar commit: parked
    docs' accepted buffers append to the delta tail with parked-prefix
    bases, byte-identical to the pre-refactor per-doc loop (whose
    output equals a from-scratch replay — the form we pin against)."""

    def test_parked_live_mixed_batch_byte_identical(self):
        n = 6
        fleet = DocFleet(doc_capacity=n, key_capacity=8)
        handles = _apply_rounds(fleet, init_docs(n, fleet), 2)
        # park half in-fleet (device state + causal state stay live)
        parked_idx = [0, 2, 4]
        assert park_docs([handles[i] for i in parked_idx]) == 3
        for i in parked_idx:
            assert handles[i]['state']._impl._doc_pending is not None
        # one mixed batch over every doc: parked docs take the delta
        # tail, live docs the plain columnar append — SAME fused call
        per_doc = [[_change(f'{d:04x}' * 4, 3, 3,
                            fleet_backend.get_heads(handles[d]),
                            'kx', 100 + d)] for d in range(n)]
        tails = {d: list(per_doc[d]) for d in range(n)}
        handles, _ = apply_changes_docs(handles, per_doc, mirror=False)
        # parked docs: chunk still parked, tail holds ONLY the delta
        for i in parked_idx:
            impl = handles[i]['state']._impl
            assert impl._doc_pending is not None
            assert list(impl._changes) == tails[i]
            assert impl._parked_n == 2
        # byte-identity: every doc's full history (revive-through-
        # `changes` for parked ones) must equal a from-scratch replay's
        for d in range(n):
            state = handles[d]['state']
            log = [bytes(b) for b in state.changes]   # materializes parked
            ref_fleet = DocFleet(doc_capacity=1, key_capacity=8)
            ref = init_docs(1, ref_fleet)
            ref, _ = apply_changes_docs(ref, [log], mirror=False)
            assert bytes(state.save()) == bytes(ref[0]['state'].save())
            assert fleet_backend.get_heads(handles[d]) == \
                fleet_backend.get_heads(ref[0])
            assert state._impl.clock == ref[0]['state']._impl.clock
            assert state._impl.max_op == ref[0]['state']._impl.max_op

    def test_parked_prefix_log_indexing_through_graph(self):
        """Deferred-graph records written by the columnar commit carry
        parked-prefix-aware bases: hash-graph queries over a parked doc
        with a delta tail must resolve every change (prefix AND tail) at
        its true log index."""
        fleet = DocFleet(doc_capacity=1, key_capacity=8)
        handles = _apply_rounds(fleet, init_docs(1, fleet), 3)
        all_hashes = [decode_change(bytes(b))['hash']
                      for b in handles[0]['state'].changes]
        assert park_docs(handles) == 1
        # two more columnar commits onto the parked doc (multi-batch
        # pending segments fold in commit order)
        for r in (3, 4):
            per_doc = [[_change('0000' * 4, r + 1, r + 1,
                                fleet_backend.get_heads(handles[0]),
                                f'k{r}', r)]]
            handles, _ = apply_changes_docs(handles, per_doc, mirror=False)
        state = handles[0]['state']
        tail_hashes = [decode_change(bytes(b))['hash']
                       for b in state._impl._changes]
        assert len(tail_hashes) == 2
        # graph query: every change retrievable by its hash, in order
        for i, h in enumerate(all_hashes + tail_hashes):
            buf = state.get_change_by_hash(h)
            assert buf is not None
            assert decode_change(bytes(buf))['hash'] == h
            assert bytes(state.changes[i]) == bytes(buf)

    def test_fold_limit_and_slot_recycling(self):
        """Past _SEAM_FOLD_LIMIT outstanding seam records the fleet
        folds everything; freed slots' pending segments die with the
        doc (a recycled slot must never inherit them)."""
        from automerge_tpu.fleet.backend import _SEAM_FOLD_LIMIT
        fleet = DocFleet(doc_capacity=4, key_capacity=8)
        handles = init_docs(2, fleet)
        heads = [[], []]
        for r in range(_SEAM_FOLD_LIMIT + 4):
            per_doc = []
            for d in range(2):
                buf = _change(f'{d:04x}' * 4, r + 1, r + 1, heads[d],
                              'k', r)
                heads[d] = [decode_change(buf)['hash']]
                per_doc.append([buf])
            handles, _ = apply_changes_docs(handles, per_doc, mirror=False)
        assert len(fleet._pend_seams) <= _SEAM_FOLD_LIMIT + 1
        assert len(handles[0]['state'].changes) == _SEAM_FOLD_LIMIT + 4
        # free doc 1 with un-folded segments pending, then recycle its slot
        handles2 = init_docs(1, fleet)
        slot_before = handles[1]['state']._impl.slot
        fleet_backend.free_docs([handles[1]])
        fresh = init_docs(1, fleet)
        assert fresh[0]['state']._impl.slot == slot_before  # recycled
        assert fresh[0]['state'].changes == []
        assert fleet_backend.get_heads(fresh[0]) == []
        chain, _ = _chain('ee' * 16, 2)
        fresh, _ = apply_changes_docs(fresh, [chain], mirror=False)
        assert [bytes(b) for b in fresh[0]['state'].changes] == \
            [bytes(b) for b in chain]
        del handles2


class TestCommitRegressionGuard:
    """The commit-phase guard (ISSUE-12 satellite): fast-path docs make
    ZERO per-doc commit-loop iterations, and the columnar commit keeps
    the O(1)-dispatch contract — the floor cannot silently creep back."""

    def test_fast_path_zero_fallback_iterations(self):
        n = 64
        fleet = DocFleet(doc_capacity=n, key_capacity=8)
        handles = init_docs(n, fleet)
        per_doc = [_chain(f'{d:04x}' * 4, 3)[0] for d in range(n)]
        handles, _ = apply_changes_docs(handles, per_doc, mirror=False)
        assert fleet.metrics.turbo_calls == 1
        assert fleet.metrics.fallbacks == 0
        assert fleet.metrics.turbo_commit_fallback_docs == 0
        # second batch (docs now hold state: gate reads the columnar
        # heads/clock) — still zero per-doc iterations
        per_doc2 = []
        for d in range(n):
            c, _ = _chain(f'{d:04x}' * 4, 2, start_seq=4,
                          deps=fleet_backend.get_heads(handles[d]), base=50)
            per_doc2.append(c)
        handles, _ = apply_changes_docs(handles, per_doc2, mirror=False)
        assert fleet.metrics.turbo_commit_fallback_docs == 0

    def test_slow_docs_are_counted(self):
        """Out-of-order delivery routes through the general gate — those
        docs DO take the per-doc tail loop and must be counted (the
        counter is the guard's tripwire, so it must actually move)."""
        fleet = DocFleet(doc_capacity=2, key_capacity=8)
        handles = init_docs(2, fleet)
        chain, _ = _chain('aa' * 16, 3)
        fast, _ = _chain('bb' * 16, 3)
        # doc 0: reversed order (causally premature head first)
        handles, _ = apply_changes_docs(
            handles, [[chain[1], chain[0], chain[2]], fast], mirror=False)
        assert fleet.metrics.turbo_commit_fallback_docs == 1
        assert [bytes(b) for b in handles[0]['state'].changes] == \
            [bytes(b) for b in chain]

    def test_seam_commit_dispatches_flat(self):
        """One device dispatch per turbo batch, independent of doc
        count."""
        for n in (8, 64):
            fleet = DocFleet(doc_capacity=n, key_capacity=8)
            handles = init_docs(n, fleet)
            d0 = fleet.metrics.dispatches
            for r in range(3):
                per_doc = []
                for d in range(n):
                    c, _ = _chain(f'{d:04x}' * 4, 1, start_seq=r + 1,
                                  deps=fleet_backend.get_heads(handles[d]),
                                  base=r)
                    per_doc.append(c)
                handles, _ = apply_changes_docs(handles, per_doc,
                                                mirror=False)
            assert fleet.metrics.dispatches - d0 == 3


class TestColumnarDocState:
    """The _DocCols property views must stay coherent through every
    writer — multi-head frontiers, lane-overflowing clocks, and the
    exact/slow paths that assign whole attributes."""

    def test_clock_lane_overflow_matches_reference(self):
        """> CLOCK_LANES actors on one doc: the commit degrades that
        doc's clock to dict mode (counted fallback) and every later
        read/gate still sees the exact reference clock."""
        from automerge_tpu.fleet.backend import _DocCols
        n_actors = _DocCols.CLOCK_LANES + 2
        actors = [f'{i:02x}' * 16 for i in range(n_actors)]
        fleet = DocFleet(doc_capacity=1, key_capacity=8)
        handles = init_docs(1, fleet)
        heads = []
        bufs = []
        for i, actor in enumerate(actors):
            buf = _change(actor, 1, i + 1, heads, f'k{i}', i)
            heads = [decode_change(buf)['hash']]
            bufs.append(buf)
        handles, _ = apply_changes_docs(handles, [bufs], mirror=False)
        assert handles[0]['state']._impl.clock == \
            {actor: 1 for actor in actors}
        assert fleet.metrics.turbo_commit_fallback_docs >= 1
        # follow-up chain by one actor still gates + commits correctly
        nxt = _change(actors[0], 2, n_actors + 1, heads, 'kz', 99)
        handles, _ = apply_changes_docs(handles, [[nxt]], mirror=False)
        clock = handles[0]['state']._impl.clock
        assert clock[actors[0]] == 2

    def test_multihead_frontier_attr_mode_gate(self):
        """Two concurrent branches -> a 2-head frontier (attr-mode
        columns); a change dep'ing on BOTH heads takes the host
        first-change compare (doc_hostcheck) and commits columnar,
        collapsing the frontier to one head."""
        fleet = DocFleet(doc_capacity=1, key_capacity=8)
        handles = init_docs(1, fleet)
        a1 = _change('aa' * 16, 1, 1, [], 'ka', 1)
        b1 = _change('bb' * 16, 1, 1, [], 'kb', 2)
        handles, _ = apply_changes_docs(handles, [[a1, b1]], mirror=False)
        heads = fleet_backend.get_heads(handles[0])
        assert len(heads) == 2 and heads == sorted(heads)
        merge = _change('aa' * 16, 2, 3, heads, 'kc', 3)
        handles, _ = apply_changes_docs(handles, [[merge]], mirror=False)
        assert fleet_backend.get_heads(handles[0]) == \
            [decode_change(merge)['hash']]
        impl = handles[0]['state']._impl
        assert fleet.doc_cols.head_n[impl.slot] == 1

    def test_exact_path_assignments_round_trip(self):
        """Whole-attribute writes (the exact/slow paths' pattern) land
        in the columns and read back exactly."""
        fleet = DocFleet(doc_capacity=1, key_capacity=8)
        impl = init_docs(1, fleet)[0]['state']._impl
        h = 'ab' * 32
        impl.heads = [h]
        assert impl.heads == [h]
        assert fleet.doc_cols.head_n[impl.slot] == 1
        assert fleet.doc_cols.head32[impl.slot].tobytes().hex() == h
        impl.heads = []
        assert impl.heads == []
        multi = sorted(['ab' * 32, 'cd' * 32])
        impl.heads = multi
        assert impl.heads == multi
        assert fleet.doc_cols.head_n[impl.slot] == -1
        impl.clock = {'aa' * 16: 3}
        assert impl.clock == {'aa' * 16: 3}
        big = {f'{i:02x}' * 16: i + 1 for i in range(9)}
        impl.clock = big
        assert impl.clock == big
        impl.max_op = 17
        assert impl.max_op == 17
        impl.stale = True
        assert impl.stale is True
        impl.binary_doc = b'xyz'
        assert impl.binary_doc == b'xyz'

    def test_shrinking_clock_assignment_clears_stale_lanes(self):
        """A SHRINKING whole-dict clock assignment (restore_all's
        rollback shape) must clear the tail lanes — a stale lane would
        hand the gate a phantom seq base and fast-commit a change the
        causal gate should queue."""
        from automerge_tpu.errors import InvalidChange
        A, B = 'aa' * 16, 'bb' * 16
        fleet = DocFleet(doc_capacity=1, key_capacity=8)
        handles = init_docs(1, fleet)
        a1 = _change(A, 1, 1, [], 'k', 1)
        handles, _ = apply_changes_docs(handles, [[a1]], mirror=False)
        impl = handles[0]['state']._impl
        impl.clock = {A: 1, B: 1}
        impl.clock = {A: 1}              # rollback-shaped shrink
        assert (fleet.doc_cols.ck_actor[impl.slot, 1:] == -1).all()
        assert impl.clock == {A: 1}
        # behavioral pin: B seq=2 arriving now does NOT extend the clock
        # (B:1 was rolled back) — the reference's error for a skipped
        # seq, never a fast commit
        b2 = _change(B, 2, 2, impl.heads, 'k', 2)
        with pytest.raises(InvalidChange,
                           match='Skipped sequence number 1 for actor ' + B):
            apply_changes_docs(handles, [[b2]], mirror=False)
        assert len(handles[0]['state'].changes) == 1
        assert handles[0]['state'].clock == {A: 1}

    def test_freed_engine_is_severed_from_columns(self):
        """A raw engine reference leaked across free must fail LOUDLY
        on use (slot severed), never alias the slot's next tenant."""
        fleet = DocFleet(doc_capacity=2, key_capacity=8)
        handles = init_docs(1, fleet)
        impl = handles[0]['state']._impl
        fleet_backend.free_docs(handles)
        assert impl.slot == 'freed'
        with pytest.raises((TypeError, IndexError)):
            impl.heads
        with pytest.raises((TypeError, IndexError)):
            impl.max_op = 5


class TestNoIncKernel:
    def test_noinc_kernel_matches_general(self):
        """The set-only merge kernel must produce exactly the general
        kernel's state on inc-free batches over a counter-free grid."""
        import jax
        from automerge_tpu.fleet.tensor_doc import FleetState, OpBatch
        from automerge_tpu.fleet.apply import (
            apply_op_batch, _apply_op_batch_noinc_impl)
        rng = np.random.default_rng(3)
        n_docs, n_keys, P = 16, 8, 4
        state = FleetState.empty(n_docs, n_keys)
        for _ in range(3):
            ops = OpBatch(
                rng.integers(0, n_keys, (n_docs, P)).astype(np.int32),
                rng.integers(1, 1 << 16, (n_docs, P)).astype(np.int32),
                rng.integers(1, 1 << 16, (n_docs, P)).astype(np.int32),
                np.ones((n_docs, P), bool), np.zeros((n_docs, P), bool),
                rng.random((n_docs, P)) < 0.8)
            ref, _ = apply_op_batch(state, ops)
            got, _ = jax.jit(_apply_op_batch_noinc_impl)(state, ops)
            np.testing.assert_array_equal(np.asarray(ref.winners),
                                          np.asarray(got.winners))
            np.testing.assert_array_equal(np.asarray(ref.values),
                                          np.asarray(got.values))
            np.testing.assert_array_equal(np.asarray(ref.counters),
                                          np.asarray(got.counters))
            state = ref

    def test_counters_pin_general_kernel(self):
        """The first inc lane pins the fleet to the general kernel —
        and a later set overwriting the counter resets its accumulator
        (the exact semantics the no-inc shortcut must never skip)."""
        fleet = DocFleet(doc_capacity=1, key_capacity=8)
        handles = init_docs(1, fleet)
        assert not fleet._counters_touched
        heads = []
        c1 = encode_change({
            'actor': 'aa' * 16, 'seq': 1, 'startOp': 1, 'time': 0,
            'message': '', 'deps': [],
            'ops': [{'action': 'set', 'obj': '_root', 'key': 'n',
                     'value': 5, 'datatype': 'counter', 'pred': []}]})
        heads = [decode_change(c1)['hash']]
        c2 = encode_change({
            'actor': 'aa' * 16, 'seq': 2, 'startOp': 2, 'time': 0,
            'message': '', 'deps': heads,
            'ops': [{'action': 'inc', 'obj': '_root', 'key': 'n',
                     'value': 3, 'pred': ['1@' + 'aa' * 16]}]})
        heads = [decode_change(c2)['hash']]
        handles, _ = apply_changes_docs(handles, [[c1, c2]], mirror=False)
        assert fleet._counters_touched
        assert handles[0]['state'].materialize() == {'n': 8}
        c3 = _change('aa' * 16, 3, 3, heads, 'n', 42)
        handles, _ = apply_changes_docs(handles, [[c3]], mirror=False)
        assert handles[0]['state'].materialize() == {'n': 42}
