"""Device sequence-engine tests: RGA ordering unit cases mirroring reference
test/new_backend_test.js:725-880 (same-position and head concurrent inserts),
plus differential fuzzing against the full host engine (public API with
multi-actor Text editing and merge) — the wasm.js-style cross-implementation
harness, with the host OpSet as the oracle."""

import functools
import random

import numpy as np
import pytest

import automerge_tpu as A
from automerge_tpu.columnar import decode_change
from automerge_tpu.fleet.sequence import (
    DEL, INSERT, PAD, SET, SeqEncoder, SeqOpBatch, SeqState,
    apply_seq_batch, linearize, materialize, visible_text)

A1, A2, A3 = '01234567', '89abcdef', 'fedcba98'


def run_ops(per_doc_ops, actors, capacity=64):
    enc = SeqEncoder(actors)
    batch = enc.batch(per_doc_ops)
    state = SeqState.empty(len(per_doc_ops), capacity)
    state, applied = apply_seq_batch(state, batch)
    return state


def ins(ref, op_id, ch):
    return {'kind': 'insert', 'ref': ref, 'id': op_id, 'value': ord(ch)}


class TestRGAOrdering:
    def test_typewriter(self):
        ops = [ins('_head', f'2@{A1}', 'h'), ins(f'2@{A1}', f'3@{A1}', 'i')]
        state = run_ops([ops], [A1])
        assert visible_text(state) == ['hi']

    def test_same_position_concurrent(self):
        """Concurrent siblings at the same insertion point order descending
        by opId (ref new.js:145-163); asserted against the host oracle
        rather than hand-derived."""
        ops = [ins('_head', f'2@{A1}', 'a'),
               ins(f'2@{A1}', f'3@{A1}', 'c'),
               ins(f'2@{A1}', f'3@{A2}', 'b')]
        state = run_ops([ops], [A1, A2])
        # Host oracle on identical ops
        assert visible_text(state) == [host_text(ops, [A1, A2])]

    def test_head_concurrent(self):
        ops = [ins('_head', f'2@{A1}', 'd'),
               ins('_head', f'3@{A1}', 'c'),
               ins('_head', f'3@{A2}', 'a'),
               ins(f'3@{A2}', f'4@{A2}', 'b')]
        state = run_ops([ops], [A1, A2])
        assert visible_text(state) == [host_text(ops, [A1, A2])]

    def test_delete(self):
        ops = [ins('_head', f'2@{A1}', 'h'),
               ins(f'2@{A1}', f'3@{A1}', 'x'),
               ins(f'3@{A1}', f'4@{A1}', 'i'),
               {'kind': 'del', 'target': f'3@{A1}', 'id': f'5@{A1}'}]
        state = run_ops([ops], [A1])
        assert visible_text(state) == ['hi']

    def test_set_updates_value(self):
        ops = [ins('_head', f'2@{A1}', 'a'),
               ins(f'2@{A1}', f'3@{A1}', 'b'),
               {'kind': 'set', 'target': f'3@{A1}', 'id': f'4@{A1}',
                'value': ord('B')}]
        state = run_ops([ops], [A1])
        assert visible_text(state) == ['aB']

    def test_insert_after_deleted_elem(self):
        ops = [ins('_head', f'2@{A1}', 'a'),
               {'kind': 'del', 'target': f'2@{A1}', 'id': f'3@{A1}'},
               ins(f'2@{A1}', f'4@{A1}', 'b')]
        state = run_ops([ops], [A1])
        assert visible_text(state) == ['b']

    def test_multiple_docs_independent(self):
        doc0 = [ins('_head', f'2@{A1}', 'x')]
        doc1 = [ins('_head', f'2@{A1}', 'a'), ins(f'2@{A1}', f'3@{A1}', 'b'),
                ins(f'3@{A1}', f'4@{A1}', 'c')]
        doc2 = []
        state = run_ops([doc0, doc1, doc2], [A1])
        assert visible_text(state) == ['x', 'abc', '']

    def test_empty_batch_is_a_no_op(self):
        state = run_ops([[ins('_head', f'2@{A1}', 'a')], []], [A1])
        again, applied = apply_seq_batch(
            state, SeqEncoder([A1]).batch([[], []]))
        assert int(applied) == 0
        for a, b in zip(state.tree_flatten()[0], again.tree_flatten()[0]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_incremental_batches(self):
        """State carries correctly across separate apply_seq_batch calls."""
        enc = SeqEncoder([A1, A2])
        state = SeqState.empty(1, 64)
        b1 = enc.batch([[ins('_head', f'2@{A1}', 'a'),
                         ins(f'2@{A1}', f'3@{A1}', 'c')]])
        state, _ = apply_seq_batch(state, b1)
        b2 = enc.batch([[ins(f'2@{A1}', f'3@{A2}', 'b')]])
        state, _ = apply_seq_batch(state, b2)
        ops = [ins('_head', f'2@{A1}', 'a'), ins(f'2@{A1}', f'3@{A1}', 'c'),
               ins(f'2@{A1}', f'3@{A2}', 'b')]
        assert visible_text(state) == [host_text(ops, [A1, A2])]

    def test_capacity_overflow_drops_and_reports(self):
        """Inserts past capacity are dropped (not silently corrupting), and
        the applied-count stat exposes the overflow."""
        ops = [ins('_head' if i == 0 else f'{i + 1}@{A1}', f'{i + 2}@{A1}',
                   chr(ord('a') + i)) for i in range(6)]
        enc = SeqEncoder([A1])
        state = SeqState.empty(1, 4)
        state, applied = apply_seq_batch(state, enc.batch([ops]))
        assert int(applied) == 4  # two inserts dropped
        assert visible_text(state) == ['abcd']

    def test_unknown_target_is_dropped(self):
        """Ops referencing an elemId absent from the doc (e.g. one dropped by
        overflow) are dropped and reported, not resolved to slot 0."""
        ops = [ins('_head', f'2@{A1}', 'a'),
               {'kind': 'del', 'target': f'99@{A1}', 'id': f'3@{A1}'},
               ins(f'98@{A1}', f'4@{A1}', 'z')]
        enc = SeqEncoder([A1])
        state = SeqState.empty(1, 8)
        state, applied = apply_seq_batch(state, enc.batch([ops]))
        assert int(applied) == 1
        assert visible_text(state) == ['a']

    def test_concurrent_sets_keep_both_values(self):
        """Two actors concurrently overwrite the same element: both ops stay
        in the element's visible register (multi-value conflict), the
        Lamport winner renders, and the row is NOT inexact (ref
        new.js:1204-1217 succ visibility rule)."""
        from automerge_tpu.fleet.sequence import element_conflicts
        ops = [ins('_head', f'2@{A1}', 'a'),
               {'kind': 'set', 'target': f'2@{A1}', 'id': f'3@{A1}',
                'value': ord('X'), 'pred': [f'2@{A1}']},
               {'kind': 'set', 'target': f'2@{A1}', 'id': f'3@{A2}',
                'value': ord('Y'), 'pred': [f'2@{A1}']}]
        state = run_ops([ops], [A1, A2])
        assert not bool(np.asarray(state.inexact)[0])
        # winner: same counter 3, higher actor hex (A2='89abcdef' > A1)
        assert visible_text(state) == ['Y']
        enc = SeqEncoder([A1, A2])
        conf = element_conflicts(state, 0)
        assert conf == {enc.pack(f'2@{A1}'): {
            enc.pack(f'3@{A1}'): ord('X'), enc.pack(f'3@{A2}'): ord('Y')}}

    def test_concurrent_set_vs_del_resurrects(self):
        """A set racing a delete of the same element: the delete kills only
        its pred, the concurrent set survives — element stays visible with
        the set's value, exactly (ref test/new_backend_test.js:1660), and
        the row is NOT inexact."""
        for del_last in (False, True):
            edits = [
                {'kind': 'set', 'target': f'2@{A1}', 'id': f'3@{A1}',
                 'value': ord('Z'), 'pred': [f'2@{A1}']},
                {'kind': 'del', 'target': f'2@{A1}', 'id': f'3@{A2}',
                 'pred': [f'2@{A1}']}]
            if del_last:
                edits.reverse()
            ops = [ins('_head', f'2@{A1}', 'a')] + edits
            state = run_ops([ops], [A1, A2])
            assert not bool(np.asarray(state.inexact)[0])
            assert visible_text(state) == ['Z']

    def test_conflict_then_overwrite_multi_pred(self):
        """Resolving a two-op conflict preds BOTH visible ops: the new set
        kills both lanes and becomes the sole visible value."""
        ops = [ins('_head', f'2@{A1}', 'a'),
               {'kind': 'set', 'target': f'2@{A1}', 'id': f'3@{A1}',
                'value': ord('X'), 'pred': [f'2@{A1}']},
               {'kind': 'set', 'target': f'2@{A1}', 'id': f'3@{A2}',
                'value': ord('Y'), 'pred': [f'2@{A1}']},
               {'kind': 'set', 'target': f'2@{A1}', 'id': f'4@{A1}',
                'value': ord('R'), 'pred': [f'3@{A1}', f'3@{A2}']}]
        state = run_ops([ops], [A1, A2])
        from automerge_tpu.fleet.sequence import element_conflicts
        assert not bool(np.asarray(state.inexact)[0])
        assert visible_text(state) == ['R']
        assert element_conflicts(state, 0) == {}

    def test_concurrent_dels_both_kill(self):
        """Two concurrent deletes of one element: idempotent, element gone,
        row exact."""
        ops = [ins('_head', f'2@{A1}', 'a'), ins(f'2@{A1}', f'3@{A1}', 'b'),
               {'kind': 'del', 'target': f'2@{A1}', 'id': f'4@{A1}',
                'pred': [f'2@{A1}']},
               {'kind': 'del', 'target': f'2@{A1}', 'id': f'4@{A2}',
                'pred': [f'2@{A1}']}]
        state = run_ops([ops], [A1, A2])
        assert not bool(np.asarray(state.inexact)[0])
        assert visible_text(state) == ['b']

    def test_self_overwrite_without_pred_flags_inexact(self):
        """An actor overwriting an element without pred'ing its own visible
        op (only constructible by hand-built changes) leaves the exact
        shape: flagged, reads route to the mirror."""
        ops = [ins('_head', f'2@{A1}', 'a'),
               {'kind': 'set', 'target': f'2@{A1}', 'id': f'3@{A1}',
                'value': ord('X'), 'pred': [f'2@{A1}']},
               {'kind': 'set', 'target': f'2@{A1}', 'id': f'4@{A1}',
                'value': ord('Y'), 'pred': []}]
        state = run_ops([ops], [A1])
        assert bool(np.asarray(state.inexact)[0])

    def test_linearize_positions(self):
        from automerge_tpu.fleet.sequence import SLOT0
        ops = [ins('_head', f'2@{A1}', 'a'), ins(f'2@{A1}', f'3@{A1}', 'b')]
        state = run_ops([ops], [A1])
        pos, n = linearize(state)
        pos, n = np.asarray(pos), np.asarray(n)
        assert n[0] == 2
        # pos is indexed by node id; slots allocate from SLOT0 in op order
        assert pos[0, SLOT0] == 0 and pos[0, SLOT0 + 1] == 1


def host_text(seq_ops, actors, key='text'):
    """Oracle: run the same elemId-level ops through the host OpSet engine,
    one single-op change per op (deps = current heads, so any stream order
    that respects per-elem causality is a valid causal order)."""
    from automerge_tpu.backend.op_set import OpSet
    from automerge_tpu.columnar import encode_change
    backend = OpSet()
    make = {'actor': actors[0], 'seq': 1, 'startOp': 1, 'time': 0, 'deps': [],
            'ops': [{'action': 'makeText', 'obj': '_root', 'key': key,
                     'insert': False, 'pred': []}]}
    obj = f'1@{actors[0]}'
    backend.apply_changes([encode_change(make)])
    seqs = {a: (2 if a == actors[0] else 1) for a in actors}
    for op in seq_ops:
        ctr_s, _, actor = op['id'].partition('@')
        if op['kind'] == 'insert':
            o = {'action': 'set', 'obj': obj, 'elemId': op['ref'],
                 'insert': True, 'value': chr(op['value']), 'pred': []}
        elif op['kind'] == 'set':
            o = {'action': 'set', 'obj': obj, 'elemId': op['target'],
                 'insert': False, 'value': chr(op['value']),
                 'pred': [op['target']]}
        else:
            o = {'action': 'del', 'obj': obj, 'elemId': op['target'],
                 'insert': False, 'pred': [op['target']]}
        change = {'actor': actor, 'seq': seqs[actor], 'startOp': int(ctr_s),
                  'time': 0, 'deps': list(backend.heads), 'ops': [o]}
        seqs[actor] += 1
        backend.apply_changes([encode_change(change)])
    return patch_text(backend.get_patch(), key)


def patch_text(patch, key='text'):
    """Fold a whole-document patch's text edits into a string."""
    props = patch['diffs'].get('props', {})
    if key not in props or not props[key]:
        return ''
    obj_patch = next(iter(props[key].values()))
    chars = []
    for edit in obj_patch.get('edits', []):
        if edit['action'] == 'insert':
            chars.insert(edit['index'], edit['value']['value'])
        elif edit['action'] == 'multi-insert':
            for i, v in enumerate(edit['values']):
                chars.insert(edit['index'] + i, v)
        elif edit['action'] == 'update':
            chars[edit['index']] = edit['value']['value']
        elif edit['action'] == 'remove':
            del chars[edit['index']:edit['index'] + edit['count']]
    return ''.join(str(c) for c in chars)


class TestDifferentialFuzz:
    """Multi-actor Text editing through the public API as oracle; the same
    ops (recovered from the merged doc's change log) through the device
    sequence engine (wasm.js-pattern differential harness)."""

    def _device_ops_from_doc(self, doc):
        """Decode the merged doc's changes back to elemId-level seq ops."""
        changes = A.get_all_changes(doc)
        text_obj = None
        seq_ops = []
        actors = set()
        for buf in changes:
            change = decode_change(buf)
            actors.add(change['actor'])
            for idx, op in enumerate(change['ops']):
                if op['action'] == 'makeText' and op.get('obj') == '_root':
                    # the single text object in these fuzz docs
                    text_obj = f"{change['startOp'] + idx}@{change['actor']}"
                    continue
                if text_obj is None or op.get('obj') != text_obj:
                    continue
                op_id = f"{change['startOp'] + idx}@{change['actor']}"
                if op['action'] == 'set' and op.get('insert'):
                    seq_ops.append({'kind': 'insert', 'ref': op['elemId'],
                                    'id': op_id, 'value': ord(op['value'])})
                elif op['action'] == 'set':
                    seq_ops.append({'kind': 'set', 'target': op['elemId'],
                                    'id': op_id, 'value': ord(op['value']),
                                    'pred': op.get('pred')})
                elif op['action'] == 'del':
                    seq_ops.append({'kind': 'del', 'target': op['elemId'],
                                    'id': op_id, 'pred': op.get('pred')})
        return seq_ops, actors

    @pytest.mark.parametrize('seed', [0, 1, 2])
    def test_random_trace_matches_public_api(self, seed):
        rng = random.Random(seed)
        actors = [A1, A2, A3]
        base = A.from_({'text': A.Text()}, actors[0])
        docs = [base] + [A.merge(A.init(a), base) for a in actors[1:]]
        alphabet = 'abcdefghijklmnopqrstuvwxyz'

        for round_ in range(6):
            for i in range(len(docs)):
                for _ in range(rng.randrange(0, 4)):
                    def edit(d, rng=rng):
                        t = d['text']
                        roll = rng.random()
                        if len(t) and roll < 0.3:
                            t.delete_at(rng.randrange(len(t)))
                        elif len(t) and roll < 0.5:
                            # overwrites: merged replicas produce the
                            # concurrent set-vs-set / set-vs-del shapes the
                            # element registers must resolve exactly
                            t.set(rng.randrange(len(t)),
                                  rng.choice(alphabet).upper())
                        else:
                            t.insert_at(rng.randrange(len(t) + 1),
                                        rng.choice(alphabet))
                    docs[i] = A.change(docs[i], edit)
            # random pairwise merge
            i, j = rng.sample(range(len(docs)), 2)
            docs[i] = A.merge(docs[i], docs[j])

        final = docs[0]
        for d in docs[1:]:
            final = A.merge(final, d)
        expected = str(final['text'])

        seq_ops, seen_actors = self._device_ops_from_doc(final)
        enc = SeqEncoder(seen_actors)
        batch = enc.batch([seq_ops])
        state = SeqState.empty(1, max(64, len(seq_ops) + 1))
        state, _ = apply_seq_batch(state, batch)
        assert visible_text(state) == [expected]
        # every shape in this trace (incl. concurrent overwrites/deletes)
        # must resolve exactly on device — no mirror fallback
        assert not bool(np.asarray(state.inexact)[0])


class TestLongDocSharding:
    """Slot-axis sharding for very long documents (sequence/context
    parallelism): sharded apply + materialize must equal the single-device
    path bit-for-bit."""

    def _build_long_doc(self, length, seed=0):
        import numpy as np
        from automerge_tpu.fleet.sequence import (
            INSERT, SET, DEL, SeqOpBatch, SeqState, apply_seq_batch)
        from automerge_tpu.fleet.tensor_doc import ACTOR_BITS
        rng = np.random.default_rng(seed)
        kind = np.full((1, length), INSERT, dtype=np.int32)
        value = rng.integers(97, 123, (1, length), dtype=np.int32)
        actor = rng.integers(0, 3, (1, length), dtype=np.int32)
        ctr = 2 + np.arange(length, dtype=np.int32)
        packed = ((ctr[None, :] << ACTOR_BITS) | actor).astype(np.int32)
        ref = np.zeros((1, length), dtype=np.int32)
        for i in range(1, length):
            j = int(rng.integers(0, i))
            ref[0, i] = packed[0, j]
        batch = SeqOpBatch(kind, ref, packed, value)
        state = SeqState.empty(1, length + 61)  # odd capacity: uneven shards
        state, applied = apply_seq_batch(state, batch)
        assert int(applied) == length
        return state, packed

    def test_sharded_matches_local(self):
        import jax
        import numpy as np
        from automerge_tpu.fleet.sequence import (
            DEL, SET, SeqOpBatch, apply_seq_batch, materialize, visible_text)
        from automerge_tpu.fleet.sharding import (
            fleet_mesh, shard_long_seq, sharded_long_seq_apply,
            sharded_long_seq_materialize)
        state, packed = self._build_long_doc(500)
        mesh = fleet_mesh(jax.devices()[:8], keys_axis=2)
        sharded = shard_long_seq(state, mesh)

        # More edits through the sharded apply vs the local apply
        extra = SeqOpBatch(
            np.array([[SET, DEL]], dtype=np.int32),
            np.array([[int(packed[0, 10]), int(packed[0, 20])]],
                     dtype=np.int32),
            np.array([[(600 << 8) | 0, (601 << 8) | 1]], dtype=np.int32),
            np.array([[90, 0]], dtype=np.int32))
        local, _ = apply_seq_batch(state, extra)
        sharded, _ = sharded_long_seq_apply(mesh)(sharded, extra)

        lv, _lc, lvis, ln = jax.device_get(materialize(local))
        sv, _sc, svis, sn = jax.device_get(
            sharded_long_seq_materialize(mesh)(sharded))
        # The sharded state may be tail-padded to a device-count multiple;
        # padded slots are unallocated, so the real prefix must match exactly
        np.testing.assert_array_equal(lv, sv[:, :lv.shape[1]])
        np.testing.assert_array_equal(lvis, svis[:, :lvis.shape[1]])
        assert not svis[:, lvis.shape[1]:].any()
        assert visible_text(local) == visible_text(sharded)


class TestCounterSumOverflow:
    """Round-4 advisor finding: the INC kernel's (sum << 2) bit-packed
    counter lane must flag the row inexact when the ACCUMULATED sum leaves
    the +/-2^29 envelope — each delta passes the ingest guards, but two
    +2^28 incs would wrap the packed int32 silently, diverging live-applied
    replicas from bulk-loaded ones (loader.py's counter_over rule)."""

    def _inc_trace(self, deltas):
        ops = [ins('_head', f'2@{A1}', 'a')]
        for i, d in enumerate(deltas):
            ops.append({'kind': 'inc', 'ref': f'2@{A1}', 'id': f'{3 + i}@{A1}',
                        'value': d, 'pred': [f'2@{A1}']})
        return run_ops([ops], [A1], capacity=8)

    def test_in_envelope_sum_stays_exact(self):
        state = self._inc_trace([(1 << 28), (1 << 28) - 1])
        assert not bool(np.asarray(state.inexact)[0])
        # accumulated value reads back exactly
        from automerge_tpu.fleet.sequence import element_visibility
        _, _, _, cnt = element_visibility(state)
        sums = np.asarray(cnt) >> 2
        assert (1 << 29) - 1 in sums[0]

    def test_overflowing_sum_flags_inexact(self):
        state = self._inc_trace([(1 << 28), (1 << 28)])
        assert bool(np.asarray(state.inexact)[0])

    def test_negative_overflow_flags_inexact(self):
        state = self._inc_trace([-(1 << 28), -(1 << 28)])
        assert bool(np.asarray(state.inexact)[0])


# ---- deferred splice (the batch's inserts live in an overlay until the
# dispatch ends): what a batch does to nodes of its own ------------------

def _typed(ref, start, n, actor=A1):
    """A typing run: n inserts, each after the one before, the first after
    `ref`; ids start@actor, start+1@actor, ..."""
    ops = []
    for i in range(n):
        ops.append(ins(ref, f'{start + i}@{actor}', chr(ord('a') + i % 26)))
        ref = f'{start + i}@{actor}'
    return ops


def _del(target, op_id):
    return {'kind': 'del', 'target': target, 'id': op_id}


def _set(target, op_id, ch):
    return {'kind': 'set', 'target': target, 'id': op_id, 'value': ord(ch)}


# name -> (capacity, width (None: 16; every dispatch of the case is padded to
#          it, so that the cases share compiled programs), ops of an earlier
#          dispatch, the batch, indexes of its ops that the kernel must drop)
SPLICE_CASES = {
    # each insert names the insert before it, the first an old element
    'run_of_8': (64, None, _typed('_head', 2, 2),
                 _typed(f'2@{A1}', 4, 8), ()),
    # SET and DEL of elements of the same batch, an insert after the
    # deleted one, a backspace of the character just typed
    'set_del_same_batch': (64, None, _typed('_head', 2, 2), [
        ins(f'3@{A1}', f'4@{A1}', 'x'), ins(f'4@{A1}', f'5@{A1}', 'y'),
        _set(f'4@{A1}', f'6@{A1}', 'X'), _del(f'5@{A1}', f'7@{A1}'),
        ins(f'5@{A1}', f'8@{A1}', 'z'), ins(f'8@{A1}', f'9@{A1}', 'w'),
        _del(f'9@{A1}', f'10@{A1}')], ()),
    # the same old node repointed again, and another old node once
    'two_after_same_old': (64, None, _typed('_head', 2, 3), [
        ins(f'2@{A1}', f'5@{A1}', 'p'), ins(f'2@{A1}', f'6@{A1}', 'q'),
        ins(f'3@{A1}', f'7@{A1}', 'r')], ()),
    # ... and a third time, by an insert that first skips the other two
    'three_after_same_old': (64, None, _typed('_head', 2, 3), [
        ins(f'2@{A1}', f'5@{A2}', 'p'), ins(f'2@{A1}', f'6@{A1}', 'q'),
        ins(f'2@{A1}', f'5@{A1}', 'r'), ins('_head', f'7@{A1}', 's'),
        ins('_head', f'8@{A1}', 't'), ins('_head', f'6@{A3}', 'u')], ()),
    # skip walks that go old node -> slot of this batch -> old node, and
    # stop on a slot of this batch
    'walk_old_new_old': (64, None, [
        ins('_head', f'2@{A1}', 'a'), ins(f'2@{A1}', f'9@{A2}', 'p'),
        ins(f'2@{A1}', f'7@{A2}', 'q')], [
        ins(f'2@{A1}', f'8@{A2}', 'n'), ins(f'2@{A1}', f'3@{A1}', 'b'),
        ins(f'2@{A1}', f'6@{A3}', 'm'), ins(f'2@{A1}', f'4@{A1}', 'c')], ()),
    # the row fills midway: the tail is dropped and reported (the last
    # insert names a dropped one, the DEL too), wider than the row
    'capacity_midway': (8, None, _typed('_head', 2, 2),
                        _typed(f'3@{A1}', 4, 8) + [
                            _set(f'9@{A1}', f'12@{A1}', 'Y'),
                            _del(f'10@{A1}', f'13@{A1}')], (6, 7, 9)),
    'unknown_referent_midway': (64, None, _typed('_head', 2, 2), [
        ins(f'3@{A1}', f'4@{A1}', 'c'), ins(f'99@{A1}', f'5@{A1}', '?'),
        ins(f'4@{A1}', f'6@{A1}', 'd'), _del(f'5@{A1}', f'7@{A1}'),
        ins(f'6@{A1}', f'8@{A1}', 'e')], (1, 3)),
    'width_1': (64, 1, _typed('_head', 2, 2),
                [ins(f'2@{A1}', f'4@{A1}', 'x')], ()),
    'width_64': (64, 64, _typed('_head', 2, 3),
                 _typed(f'3@{A1}', 5, 20) + [_del(f'24@{A1}', f'25@{A1}')]
                 + _typed(f'23@{A1}', 26, 12, A2)
                 + [ins(f'3@{A1}', f'40@{A1}', 'k')], ()),
    'wider_than_the_row': (8, None, _typed('_head', 2, 2),
                           _typed(f'2@{A1}', 4, 5)
                           + [_del(f'8@{A1}', f'9@{A1}')], ()),
}


class TestDeferredSplice:
    ACTORS = [A1, A2, A3]
    # a second row with another cursor, so that a row's overlay is
    # numbered from its own n0
    OTHER_OLD = _typed('_head', 2, 2, A2)
    OTHER_NEW = _typed(f'2@{A2}', 4, 2, A2) + [_del(f'5@{A2}', f'6@{A2}')]

    def _apply(self, state, parts, width):
        """One dispatch a part: row 0 takes the part, row 1 as large a
        share of OTHER_NEW."""
        enc = SeqEncoder(self.ACTORS)
        cuts = [0, 2, 3] if len(parts) == 2 else [0, 3]
        total = 0
        for part, lo, hi in zip(parts, cuts, cuts[1:]):
            state, applied = apply_seq_batch(
                state, enc.batch([part, self.OTHER_NEW[lo:hi]],
                                 pad_to=width or 16))
            total += int(applied)
        return state, total

    @pytest.mark.parametrize('name', sorted(SPLICE_CASES))
    def test_batch_on_its_own_nodes(self, name):
        from automerge_tpu.fleet.sequence import END, HEAD, SCRATCH
        capacity, width, old, batch, dropped = SPLICE_CASES[name]
        enc = SeqEncoder(self.ACTORS)
        base, _ = apply_seq_batch(
            SeqState.empty(2, capacity),
            enc.batch([old, self.OTHER_OLD], pad_to=width or 16))
        assert not np.asarray(base.inexact).any()

        one, applied = self._apply(base, [batch], width)
        half = len(batch) // 2
        two, applied2 = self._apply(base, [batch[:half], batch[half:]],
                                    width)
        for a, b in zip(one.tree_flatten()[0], two.tree_flatten()[0]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

        kept = [op for i, op in enumerate(batch) if i not in dropped]
        assert applied == applied2 == len(kept) + len(self.OTHER_NEW)
        assert visible_text(one) == [
            host_text(old + kept, self.ACTORS),
            host_text(self.OTHER_OLD + self.OTHER_NEW, self.ACTORS)]
        assert np.asarray(one.inexact).tolist() == [bool(dropped), False]
        inserts = sum(op['kind'] == 'insert' for op in old + kept)
        assert np.asarray(one.n).tolist() == [inserts, 4]
        # the sentinels are never written by a live or a dropped insert
        elem, nxt = np.asarray(one.elem_id), np.asarray(one.nxt)
        assert (elem[:, [HEAD, END, SCRATCH]] == 0).all()
        assert (nxt[:, [END, SCRATCH]] == END).all()
        assert (elem[0, 3 + inserts:] == 0).all()


# ---- the scan step against a plain one: the step reads and writes its small
# arrays (the overlay, a node's register row) by compare and select, and a
# masked read or write is one whose place matches no entry; the plain step
# below indexes the row's own arrays, one op at a time, and has no overlay --

def _plain_apply(state, ops):
    """The host's oracle for a dispatch's STATE: every op of every row in
    order, by plain indexing of numpy arrays; returns the eight arrays in
    SeqState's order, the applied flags [rows, width] and nothing else.
    Written from the rules (sequence.py's module docstring, new.js), not
    from the kernel: splices go to `elem_id` and `nxt` at once."""
    from automerge_tpu.fleet.sequence import (
        ACTOR_MASK, END, HEAD, INC, SCRATCH, SLOT0)
    elem, nxt, reg, killed, val, cnt, n, inexact = [
        np.array(a) for a in state.tree_flatten()[0]]
    rows, nodes = elem.shape
    capacity, lanes = nodes - 3, reg.shape[1] // nodes
    kinds, refs, ids, values, pred_lists, flags = [
        np.asarray(a) for a in ops.tree_flatten()[0][:6]]
    masks = np.full(len(kinds), ACTOR_MASK) if ops.actor_mask is None \
        else np.asarray(ops.actor_mask)
    applied = np.zeros(kinds.shape, dtype=bool)
    for d in range(rows):
        for p in range(kinds.shape[1]):
            kind, ref, op_id = kinds[d, p], int(refs[d, p]), int(ids[d, p])
            value = int(values[d, p])
            preds = [int(x) for x in pred_lists[d, p]]
            inexact[d] |= flags[d, p]
            if kind == PAD:
                continue
            where = np.flatnonzero(elem[d] == ref) if ref else []
            if kind == INSERT:
                ok = n[d] < capacity and (ref == 0 or len(where))
                if ok:
                    r = int(where[0]) if ref else HEAD
                    j = int(nxt[d, r])
                    while elem[d, j] > op_id:
                        r, j = j, int(nxt[d, j])
                    slot = SLOT0 + int(n[d])
                    elem[d, slot], nxt[d, slot], nxt[d, r] = op_id, j, slot
                    reg[d, slot], val[d, slot] = op_id, value    # lane 0
                    killed[d, slot], cnt[d, slot] = False, 0
                    n[d] += 1
            else:
                ok = bool(ref and len(where))
            applied[d, p] = ok
            inexact[d] |= not ok
            if not ok or kind == INSERT:
                continue
            at = int(where[0]) + nodes * np.arange(lanes)   # the A lanes
            named = [x for x in preds if x > 0]
            held = np.array([reg[d, i] in named for i in at])
            bad = any(x < 0 for x in preds)
            if kind == INC:
                top = max(named, default=0)
                hit = [i for i in at
                       if top and reg[d, i] == top and not killed[d, i]]
                bad |= not hit and not (held & ~killed[d, at]).any()
                if hit:
                    old = int(cnt[d, hit[0]])
                    bad |= abs((old >> 2) + value) >= 1 << 29
                    stepped = ((old & ~3) + (value << 2)) | \
                        (3 if old & 3 else 1)
                    # (flagged above when it leaves the envelope, and
                    # then wraps as the device's int32 does)
                    cnt[d, hit[0]] = (stepped + (1 << 31)) % (1 << 32) \
                        - (1 << 31)
                killed[d, at] |= held & (reg[d, at] != top)
                inexact[d] |= bad
                continue
            killed[d, at] |= held
            if kind == SET:
                mine = [i for i in at if reg[d, i] and
                        (reg[d, i] & masks[d]) == (op_id & masks[d])]
                free = mine or [i for i in at if reg[d, i] == 0]
                if not free:
                    bad = True      # more writers than lanes
                else:
                    i, prev = free[0], int(reg[d, free[0]])
                    bad |= bool(prev) and not killed[d, i] and \
                        prev not in preds and prev != op_id
                    bad |= (cnt[d, i] & 3) != 0
                    reg[d, i], val[d, i] = op_id, value
                    killed[d, i], cnt[d, i] = False, 0
            inexact[d] |= bad
    assert (elem[:, [HEAD, END, SCRATCH]] == 0).all()
    return [elem, nxt, reg, killed, val, cnt, n, inexact], applied


def _inc(target, op_id, delta, pred):
    return {'kind': 'inc', 'ref': target, 'id': op_id, 'value': delta,
            'pred': pred}


def _set_p(target, op_id, ch, pred):
    return dict(_set(target, op_id, ch), pred=pred)


# name -> (capacity, width, ops of an earlier dispatch, the batch, what the
#          is the host's OpSet asked what the row reads after both (not
#          of counters, nor where the kernel must drop an op))
STEP_CASES = {
    'width_1': (64, 1, [ins('_head', f'2@{A1}', 'a')],
                [ins(f'2@{A1}', f'3@{A1}', 'b')], True),
    # eight inserts in a batch eight wide: entry 7, the overlay's last, is
    # written, read by the walk of the insert after it and written back
    'overlay_filled_to_its_last_entry': (64, 8, _typed('_head', 2, 2), (
        _typed(f'2@{A1}', 4, 7) + [ins(f'10@{A1}', f'11@{A2}', 'z')]), True),
    # the row fills at the batch's third insert: the others' writes are
    # placed past the overlay's end, and so is the DEL's of a dropped one
    'insert_over_capacity': (4, 8, _typed('_head', 2, 1), (
        _typed(f'2@{A1}', 3, 6) + [_del(f'7@{A1}', f'9@{A1}'),
                                   _del(f'4@{A1}', f'10@{A1}')]), False),
    'insert_after_unknown_referent': (64, 8, _typed('_head', 2, 2), [
        ins(f'77@{A2}', f'4@{A1}', '?'), ins(f'3@{A1}', f'5@{A1}', 'c'),
        ins(f'4@{A1}', f'6@{A1}', '?'), _set(f'4@{A1}', f'7@{A1}', '?'),
        ins(f'5@{A1}', f'8@{A1}', 'd')], False),
    # old node 2@A1 gets a pair at the first insert (entry 0) and has it
    # rewritten twice (e_r >= 0); old node 3@A1 takes entry 2 meanwhile
    'same_old_node_repointed_twice': (64, 8, _typed('_head', 2, 3), [
        ins(f'2@{A1}', f'5@{A1}', 'p'), ins(f'2@{A1}', f'6@{A1}', 'q'),
        ins(f'3@{A1}', f'7@{A1}', 'r'), ins(f'2@{A1}', f'8@{A1}', 's'),
        ins(f'4@{A1}', f'9@{A1}', 't')], True),
    # every insert but the first goes after a slot of this batch (at_r),
    # the last two after the same one, the later of them walking over
    # nothing (its id is the greater) and the A2 one over both
    'insert_after_a_slot_of_this_batch': (64, 8, _typed('_head', 2, 1), [
        ins(f'2@{A1}', f'3@{A1}', 'b'), ins(f'3@{A1}', f'4@{A1}', 'c'),
        ins(f'4@{A1}', f'5@{A1}', 'd'), ins(f'3@{A1}', f'6@{A1}', 'e'),
        ins(f'3@{A1}', f'5@{A2}', 'f'), _del(f'5@{A2}', f'7@{A1}')], True),
    # lane 0 holds A1's insert, lane 1 A2's set of it: A3's inc of that set
    # counts in lane 1 (s_max) while A3's own lane would be 2 (a_c); the
    # second inc finds the count bits at 1; the third names the dead insert
    # beside the set and a lane that holds neither
    'inc_lane_differs_from_own_lane': (64, 8, [
        ins('_head', f'2@{A1}', 'a'),
        _set_p(f'2@{A1}', f'3@{A2}', 'n', [f'2@{A1}'])], [
        _inc(f'2@{A1}', f'4@{A3}', 5, [f'3@{A2}']),
        _inc(f'2@{A1}', f'5@{A3}', -2, [f'3@{A2}']),
        _inc(f'2@{A1}', f'6@{A1}', 7, [f'2@{A1}', f'3@{A2}'])], False),
    # an inc whose preds are all dead or unknown, and one past the packed
    # sum's envelope
    'inc_with_no_live_target': (64, 8, [
        ins('_head', f'2@{A1}', 'a'), ins(f'2@{A1}', f'3@{A1}', 'b'),
        _del(f'2@{A1}', f'4@{A2}')], [
        _inc(f'2@{A1}', f'5@{A3}', 1, [f'2@{A1}']),
        _inc(f'3@{A1}', f'6@{A3}', 1 << 28, [f'3@{A1}']),
        _inc(f'3@{A1}', f'7@{A3}', 1 << 28, [f'3@{A1}'])], False),
    # A2's set kills A1's insert in lane 0 and takes lane 1; A1's next set
    # preds A2's and takes lane 0 back, live again; A2 then overwrites its
    # own dead op in lane 1
    'set_reclaims_a_lane': (64, 8, [
        ins('_head', f'2@{A1}', 'a'), ins(f'2@{A1}', f'3@{A1}', 'b')], [
        _set_p(f'2@{A1}', f'4@{A2}', 'X', [f'2@{A1}']),
        _set_p(f'2@{A1}', f'5@{A1}', 'Y', [f'4@{A2}']),
        _set_p(f'2@{A1}', f'6@{A2}', 'Z', [f'5@{A1}']),
        _set_p(f'3@{A1}', f'7@{A3}', 'W', [f'3@{A1}'])], True),
    # a lane whose dead op consumed an inc is reclaimed: flagged
    'set_reclaims_a_counted_lane': (64, 8, [
        ins('_head', f'2@{A1}', 'a'),
        _inc(f'2@{A1}', f'3@{A2}', 4, [f'2@{A1}'])], [
        _set_p(f'2@{A1}', f'4@{A1}', 'b', [f'2@{A1}'])], False),
}


class TestStepAgainstPlainStep:
    ACTORS = [A1, A2, A3]
    OTHER = _typed('_head', 2, 3, A2)   # row 1: another cursor, other ids

    def _both(self, state, cols):
        want, want_applied = _plain_apply(state, cols)
        got, applied = apply_seq_batch(state, cols)
        for name, a, b in zip(
                ('elem_id', 'nxt', 'reg', 'killed', 'val', 'counter', 'n',
                 'inexact'), got.tree_flatten()[0], want):
            np.testing.assert_array_equal(np.asarray(a), b, err_msg=name)
        assert int(applied) == int(want_applied.sum())
        return got, want_applied

    @pytest.mark.parametrize('lanes', [4, 8])
    @pytest.mark.parametrize('name', sorted(STEP_CASES))
    def test_masked_corners(self, name, lanes):
        capacity, width, old, batch, on_host = STEP_CASES[name]
        enc = SeqEncoder(self.ACTORS)
        state = SeqState.empty(2, capacity, actor_slots=lanes)
        state, _ = self._both(state, enc.batch([old, self.OTHER],
                                               pad_to=width))
        assert not np.asarray(state.inexact).any()
        # row 1 takes the same batch on its own ids' row: what it names is
        # unknown there or another element, and masked the other way
        state, applied = self._both(
            state, enc.batch([batch, batch[:width // 2]], pad_to=width))
        if on_host:
            assert applied[0, :len(batch)].all()
            assert not np.asarray(state.inexact)[0]
            assert visible_text(state)[0] == host_text(old + batch,
                                                       self.ACTORS)

    @pytest.mark.parametrize('lanes', [4, 8])
    @pytest.mark.parametrize('seed', [0, 1, 2])
    def test_random_columns(self, seed, lanes):
        """Columns no frontend would send (ids a row never had, preds of
        any op, incs of anything, a row that fills) through four
        dispatches: the step and the plain step agree on every array."""
        from automerge_tpu.fleet.sequence import INC, SEQ_PRED_LANES
        from automerge_tpu.fleet.tensor_doc import ACTOR_BITS
        rng = np.random.default_rng(seed)
        rows, width = 4, 16
        state = SeqState.empty(rows, 24, actor_slots=lanes)  # a row fills
        seen = [[0] for _ in range(rows)]
        ctr = 2
        for _dispatch in range(4):
            shape = (rows, width)
            kind = rng.choice([PAD, INSERT, INSERT, INSERT, SET, SET, DEL,
                               INC], size=shape).astype(np.int32)
            ref = np.zeros(shape, np.int32)
            packed = np.zeros(shape, np.int32)
            preds = np.zeros(shape + (SEQ_PRED_LANES,), np.int32)
            for d in range(rows):
                for p in range(width):
                    packed[d, p] = (ctr << ACTOR_BITS) | rng.integers(0, 6)
                    ctr += 1
                    recent = seen[d][-1 - int(rng.geometric(0.3) - 1)
                                     % len(seen[d])]
                    ref[d, p] = rng.choice(
                        [recent, 0, (999 << ACTOR_BITS) | 1],
                        p=[0.8, 0.12, 0.08])
                    for lane in range(rng.integers(0, SEQ_PRED_LANES + 1)):
                        preds[d, p, lane] = rng.choice(
                            [ref[d, p], seen[d][rng.integers(len(seen[d]))],
                             -1], p=[0.5, 0.45, 0.05])
                    if kind[d, p] in (INSERT, SET):
                        seen[d].append(packed[d, p])
            value = np.where(kind == INC,
                             rng.choice([1, -3, 1 << 28], size=shape),
                             rng.integers(-5, 1 << 20, size=shape))
            cols = SeqOpBatch(kind, ref, packed, value.astype(np.int32),
                              preds, rng.random(shape) < 0.01)
            state, _ = self._both(state, cols)


# ---- the referent lookup, hoisted out of the scan: every op cell's referent
# among the row's old nodes is found before the scan, for the whole batch ---

# ids 2..5 of A1 in both rows, at other nodes: row 0 typed them in order
# (nodes 3, 4, 5, 6: "abcd"), row 1 took 3, 2, 5, 4 (id 2 at node 4, 3 at 3,
# 4 at 6, 5 at 5: "bdac"), so a lookup that read another row's `elem_id`
# would splice after the wrong character
SAME_IDS_OLD = (_typed('_head', 2, 4), [
    ins('_head', f'3@{A1}', 'b'), ins('_head', f'2@{A1}', 'a'),
    ins(f'3@{A1}', f'5@{A1}', 'd'), ins(f'2@{A1}', f'4@{A1}', 'c')])

# name -> (capacity, width the batch is padded to (0: as wide as it is),
#          the batch (both rows take it), indexes of the ops both rows drop)
LOOKUP_CASES = {
    'old_element': (64, 16, [
        ins(f'3@{A1}', f'6@{A1}', 'x'), _del(f'4@{A1}', f'7@{A1}'),
        _set(f'2@{A1}', f'8@{A1}', 'A'), ins(f'5@{A1}', f'9@{A1}', 'y')], ()),
    'insert_earlier_in_the_batch': (64, 16, [
        ins(f'4@{A1}', f'6@{A1}', 'x'), ins(f'6@{A1}', f'7@{A1}', 'y'),
        _del(f'6@{A1}', f'8@{A1}'), ins(f'7@{A1}', f'9@{A1}', 'z')], ()),
    'head': (64, 16, [
        ins('_head', f'6@{A1}', 'x'), ins('_head', f'7@{A2}', 'y'),
        ins(f'2@{A1}', f'8@{A1}', 'z')], ()),
    'id_the_row_never_had': (64, 16, [
        ins(f'2@{A1}', f'6@{A1}', 'x'), ins(f'2@{A2}', f'7@{A1}', '?'),
        ins(f'6@{A1}', f'8@{A1}', 'y')], (1,)),
    # four old elements in a row of 8: the fifth insert is dropped, and so
    # are the insert and the DEL that name it
    'insert_dropped_at_capacity': (8, 16, _typed(f'2@{A1}', 6, 4) + [
        ins(f'9@{A1}', f'10@{A1}', 'z'), ins(f'10@{A1}', f'11@{A1}', 'w'),
        _del(f'10@{A1}', f'12@{A1}'), _set(f'3@{A1}', f'13@{A1}', 'B')],
        (4, 5, 6)),
    'width_1': (64, 0, [ins(f'4@{A1}', f'6@{A1}', 'x')], ()),
    'empty': (64, 0, [], ()),
    # a typing run's start repeated: every op names the same old element
    'all_one_old_id': (64, 16, [
        ins(f'3@{A1}', f'{6 + i}@{A1}', chr(ord('p') + i))
        for i in range(8)] + [_set(f'3@{A1}', f'14@{A1}', 'B')], ()),
}


def _plain_lookup(elem, ref):
    """The least node of its row that holds each ref, or the row's length."""
    return np.array([[min(np.flatnonzero(row == r), default=len(row))
                      for r in refs] for row, refs in zip(elem, ref)],
                    dtype=np.int32).reshape(np.shape(ref))


class TestReferentLookup:
    ACTORS = [A1, A2]

    @pytest.mark.parametrize('name', sorted(LOOKUP_CASES))
    def test_batch_against_the_host(self, name):
        from automerge_tpu.fleet.sequence import _referent_lookup
        capacity, width, batch, dropped = LOOKUP_CASES[name]
        enc = SeqEncoder(self.ACTORS)
        base, _ = apply_seq_batch(
            SeqState.empty(2, capacity),
            enc.batch(list(SAME_IDS_OLD), pad_to=16))
        elem = np.asarray(base.elem_id)
        assert sorted(elem[0, 3:7]) == sorted(elem[1, 3:7])
        assert (elem[0, 3:7] != elem[1, 3:7]).all()

        cols = enc.batch([batch, batch], pad_to=width)
        np.testing.assert_array_equal(
            np.asarray(_referent_lookup(base.elem_id, cols.ref)),
            _plain_lookup(elem, cols.ref))

        state, applied = apply_seq_batch(base, cols)
        kept = [op for i, op in enumerate(batch) if i not in dropped]
        assert int(applied) == 2 * len(kept)
        assert visible_text(state) == [
            host_text(old + kept, self.ACTORS) for old in SAME_IDS_OLD]
        assert np.asarray(state.inexact).tolist() == [bool(dropped)] * 2
        inserts = 4 + sum(op['kind'] == 'insert' for op in kept)
        assert np.asarray(state.n).tolist() == [inserts] * 2


@pytest.mark.parametrize('capacity', [1, 5, 2048, 4196, 6144])
def test_lookup_in_blocks(capacity):
    """The lookup over rows shorter than a block, of whole blocks and with
    a last block of its own length, against a plain search; and where a row
    is longer than a block the compiled lookup has no array of
    [rows, width, nodes]: what a backend lays out that does not fuse the
    compare into the min (this one) is a block wide."""
    import jax
    from automerge_tpu.fleet.sequence import LOOKUP_BLOCK, _referent_lookup
    rows, width, nodes = 3, 5, capacity + 3
    rng = np.random.default_rng(capacity)
    elem = np.zeros((rows, nodes), dtype=np.int32)
    used = capacity - capacity // 4
    for row in elem:
        row[3:3 + used] = rng.permutation(3 * capacity)[:used] + 1
    ref = elem[:, rng.integers(0, nodes, size=width)]
    ref[0, 0], ref[1, 1], ref[2, 2] = 0, 3 * capacity + 9, elem[2, nodes - 1]
    lookup = jax.jit(_referent_lookup)
    np.testing.assert_array_equal(np.asarray(lookup(elem, ref)),
                                  _plain_lookup(elem, ref))
    if nodes > LOOKUP_BLOCK:
        text = lookup.lower(elem, ref).compile().as_text()
        assert f'[{rows},{width},{LOOKUP_BLOCK}]' in text
        assert f'[{rows},{width},{nodes}]' not in text


# ---- the lookup's search: rows whose allocated slots hold ascending ids --

def _ascending_ids(rng, count, lo, hi):
    """Up to `count` distinct ids of [lo, hi), ascending."""
    return np.unique(rng.integers(lo, hi, size=count))[:count]


@pytest.mark.parametrize('layout', ['fleet', 'wide'])
def test_search_answers_as_the_scan(layout):
    """On rows whose allocated slots hold ascending ids, the lookup's
    binary search answers every ref as the block scan and a plain search
    do: hits (a row's first and last slot among them), misses between,
    below and past a row's ids, 0 (node 0, HEAD), -1 and INT32_MAX, on an
    empty row, a full one and rows between. The ids are those of a loaded
    or one-writer row in the fleet layout ((counter << 8) | actor), or of
    a wide row, (counter << 1) | rank with counters past the 2^23 window.
    A row out of id order whose refs are all 0 reads node 0 either way.
    Both branches are one program."""
    import jax
    from automerge_tpu.fleet.sequence import (
        INT32_MAX, SLOT0, _referent_lookup, _referent_scan)
    from automerge_tpu.fleet.tensor_doc import CTR_LIMIT
    rows, width, capacity = 5, 24, 300
    nodes = capacity + SLOT0
    lo, hi = (1 << 8, CTR_LIMIT << 8) if layout == 'fleet' else \
        (CTR_LIMIT << 1, INT32_MAX - 1)
    lookup = jax.jit(_referent_lookup)
    rng = np.random.default_rng(46)
    programs = None
    for trial in range(4):
        elem = np.zeros((rows, nodes), np.int32)
        ref = np.zeros((rows, width), np.int32)
        n = np.zeros(rows, np.int32)
        for r in range(rows):
            want = (0, capacity)[r] if r < 2 else \
                int(rng.integers(1, capacity))
            ids = _ascending_ids(rng, want, lo, hi)
            n[r] = len(ids)
            elem[r, SLOT0:SLOT0 + n[r]] = ids
            if r == rows - 1:               # out of order, refs all 0
                elem[r, SLOT0:SLOT0 + n[r]] = rng.permutation(ids)
                continue
            pool = [0, -1, INT32_MAX, lo - 1, hi]
            if len(ids):
                gaps = ids[:-1][np.diff(ids) > 1] + 1
                pool += [ids[0], ids[-1], ids[0] - 1, ids[-1] + 1]
                pool += rng.choice(ids, 8).tolist() + gaps[:6].tolist()
            ref[r] = rng.choice(pool, width)
        scanned = _plain_lookup(elem, ref)
        np.testing.assert_array_equal(
            np.asarray(_referent_scan(elem, ref)), scanned)
        for ordered in (True, False):
            np.testing.assert_array_equal(
                np.asarray(lookup(elem, ref, n, np.bool_(ordered))), scanned)
            programs = programs or lookup._cache_size()
    assert lookup._cache_size() == programs


def _one_writer_edits(rng, actor, state, count):
    """`count` random edits of one writer: inserts after a live or deleted
    element, a new one of the same batch among them, or the head; sets and
    deletes of elements no op has set or deleted yet. `state` is the row's
    (next counter, [element ids], [untouched ids]), carried from batch to
    batch."""
    ctr, elems, untouched = state
    ops = []
    for _ in range(count):
        pick = rng.random()
        if pick < 0.6 or not untouched:
            ref = '_head' if not elems or rng.random() < 0.1 else \
                elems[rng.integers(len(elems))]
            op_id = f'{ctr}@{actor}'
            ops.append(ins(ref, op_id, chr(ord('a') + ctr % 26)))
            elems.append(op_id)
            untouched.append(op_id)
        else:
            target = untouched.pop(rng.integers(len(untouched)))
            ops.append(_set(target, f'{ctr}@{actor}', 'X') if pick < 0.8
                       else _del(target, f'{ctr}@{actor}'))
        ctr += 1
    return ops, (ctr, elems, untouched)


@pytest.mark.parametrize('seed', range(2))
def test_an_ordered_dispatch_applies_as_a_scanned_one(seed):
    """Three dispatches of one writer's random edits a row, each applied to
    a state whose lookups searched and to one whose lookups scanned: every
    array of the two states stays equal and the text is the host's. The
    batches' inserts name old elements, the head and elements of the same
    batch (which the lookup leaves to the scan's overlay); a third row
    inserts after an id it never held (a miss: the op is dropped and the
    row flagged), and a fourth has no op at all."""
    actors = [A1, A2, A3]
    enc = SeqEncoder(actors)
    rng = np.random.default_rng(seed)
    rows = [(2, [], []) for _ in actors]
    history = [[] for _ in actors]
    searched = scanned = SeqState.empty(4, 64)
    for step in range(3):
        per_row = []
        for r, actor in enumerate(actors):
            ops, rows[r] = _one_writer_edits(rng, actor, rows[r], 10)
            per_row.append(ops)
            history[r] += ops
        per_row[2][4] = ins(f'999@{A3}', per_row[2][4]['id'], '?')
        batch = enc.batch(per_row + [[]], pad_to=16)
        batch.ordered = np.bool_(True)
        searched, applied = apply_seq_batch(searched, batch)
        batch.ordered = np.bool_(False)
        scanned, applied_too = apply_seq_batch(scanned, batch)
        assert int(applied) == int(applied_too)
        for a, b in zip(searched.tree_flatten()[0],
                        scanned.tree_flatten()[0]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    elem, n = np.asarray(searched.elem_id), np.asarray(searched.n)
    for r in range(3):
        ids = elem[r, 3:3 + n[r]]
        assert (np.diff(ids) > 0).all()
    assert np.asarray(searched.inexact).tolist() == [False, False, True,
                                                     False]
    assert visible_text(searched)[:2] == [
        host_text(history[0], actors), host_text(history[1], actors[1:])]


@functools.lru_cache(maxsize=None)
def _compiled_text(rows, capacity, width, lanes=4):
    """The optimized program of one dispatch at these shapes, as text."""
    import jax
    import jax.numpy as jnp
    from automerge_tpu.fleet import sequence
    nodes = capacity + 3

    def spec(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype)

    state = SeqState(
        spec((rows, nodes)), spec((rows, nodes)),
        spec((rows, lanes * nodes)), spec((rows, lanes * nodes), jnp.bool_),
        spec((rows, lanes * nodes)), spec((rows, lanes * nodes)),
        spec((rows,)), spec((rows,), jnp.bool_))
    ops = SeqOpBatch(
        spec((rows, width)), spec((rows, width)), spec((rows, width)),
        spec((rows, width)),
        spec((rows, width, sequence.SEQ_PRED_LANES)),
        spec((rows, width), jnp.bool_))
    return sequence.apply_seq_batch_donated.__wrapped__ \
        .lower(state, ops).compile().as_text()


def _computations(text):
    """{name: (is the entry, [instruction lines])} of a program's text."""
    import re
    out, name = {}, None
    for line in text.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith('{'):
            name = re.match(r'(?:ENTRY )?%?([\w.\-]+)', line).group(1)
            out[name] = (line.startswith('ENTRY'), [])
        elif name and ' = ' in line:
            out[name][1].append(line.strip())
    return out


_CALLED = r'(?:calls|to_apply|body|condition)=%?([\w.\-]+)'
# name, dimensions, opcode and operands of an instruction that yields an
# array
_INSTR = (r'(?:ROOT )?%?([\w.\-]+) = \w+\[([0-9,]*)\]'
          r'(?:\{[^}]*\})? ([\w\-]+)\(([^)]*)\)')


def _parsed(comps):
    """{computation: [(match of _INSTR, line)]}"""
    import re
    instr = re.compile(_INSTR)
    return {name: [(m, line) for line in lines
                   for m in [instr.match(line)] if m]
            for name, (_entry, lines) in comps.items()}


def _scan_side(comps):
    """The scan's side of a program: every computation reached from a
    `while` that is not the lookup's own."""
    import re
    called = re.compile(_CALLED)
    todo = [name for _entry, lines in comps.values() for line in lines
            if ' while(' in line and 'seq.referent_lookup' not in line
            for name in called.findall(line)]
    assert todo, 'no scan found: the text was not parsed'
    scan_side = set()
    while todo:
        name = todo.pop()
        if name in scan_side or name not in comps:
            continue
        scan_side.add(name)
        todo += [c for line in comps[name][1] for c in called.findall(line)]
    return scan_side


def test_scan_body_copies_no_node_array():
    """The compiled program may not copy an array of elem_id's shape
    anywhere but in its entry computation: a scan step that writes an
    array which the skip walk's `while` holds is given a whole-array copy
    of it every step (two 134 MB copies a step were 54 % of the device
    time of the text cell before the splices were deferred)."""
    import re
    rows, capacity, width = 8, 256, 16
    comps = _computations(_compiled_text(rows, capacity, width))
    node_copy = re.compile(
        r'= s32\[%d,%d\](\{[^}]*\})? copy\(' % (rows, capacity + 3))
    found = [line for entry, lines in comps.values() if not entry
             for line in lines if node_copy.search(line)]
    assert len(comps) > 1, \
        'no computation but the entry: the text was not parsed'
    assert not found, found


@pytest.mark.parametrize('rows,capacity,width', [(8, 256, 16),
                                                 (4, 512, 512)])
def test_scan_body_searches_no_node_array(rows, capacity, width):
    """Only the lookup ahead of the scan may compare or reduce an array of
    elem_id's shape: a scan step that searched the row for its op's
    referent read 134 MB a step in the text cell, 39 % of its device time,
    for what one pass over the row finds for the whole batch. And that pass
    may not lay the row out once an op cell: no instruction outside a
    fusion yields [rows, width, nodes] (8.6 GB at the cell's shapes)."""
    import re
    nodes = capacity + 3
    comps = _computations(_compiled_text(rows, capacity, width))
    parsed = _parsed(comps)
    scan_side = _scan_side(comps)

    row_shape = f'{rows},{nodes}'
    searches, lookups = [], 0
    for name, found in parsed.items():
        dims = {m.group(1): m.group(2) for m, _line in found}
        for m, line in found:
            if m.group(3) not in ('compare', 'reduce', 'reduce-window'):
                continue
            lookups += 'seq.referent_lookup' in line
            operands = re.findall(r'%([\w.\-]+)', m.group(4))
            if name in scan_side and row_shape in (
                    m.group(2), *(dims.get(o) for o in operands)):
                searches.append(f'{name}: {line[:160]}')
    assert not searches, searches
    assert lookups, 'the lookup ahead of the scan was not found'

    fused = {c for _entry, lines in comps.values() for line in lines
             if ' fusion(' in line for c in re.findall(_CALLED, line)}
    laid_out = [f'{name}: {line[:160]}' for name, found in parsed.items()
                if name not in fused for m, line in found
                if m.group(2) == f'{rows},{width},{nodes}']
    assert not laid_out, laid_out


@pytest.mark.parametrize('lanes', [4, 8])
@pytest.mark.parametrize('rows,capacity,width', [(8, 256, 16),
                                                 (4, 512, 512)])
def test_scan_body_indexes_no_small_array(rows, capacity, width, lanes):
    """On the scan's side no scatter, gather, dynamic-slice or
    dynamic-update-slice reads or writes an array of the overlay's shape
    ([rows, width]) or of a node's register row ([rows, lanes]): their
    elements are read and written by compares and selects, which fuse,
    where on the chip every index was a program of its own and those of
    the small arrays half of the scan step (PERF.md section 6, PR 36).
    What is still indexed there is as long as the document: each lane array
    ([rows, lanes * nodes]) is gathered from once and scattered into
    once, and `elem_id` and `nxt` ([rows, nodes]) are gathered from and
    never written. (The array an instruction indexes is its first
    operand; the op columns the scan slices its steps from are
    [width, rows], and the lane gather's indices are [rows, lanes] by
    right.)"""
    import re
    nodes = capacity + 3
    comps = _computations(_compiled_text(rows, capacity, width, lanes))
    small = {f'{rows},{width}': 'overlay', f'{rows},{lanes}': 'register row'}
    lane_shape, node_shape = f'{rows},{lanes * nodes}', f'{rows},{nodes}'
    assert len({lane_shape, node_shape, *small}) == 4
    indexed = {'scatter': [], 'gather': []}
    on_small = []
    scan_side = _scan_side(comps)
    for name, found in _parsed(comps).items():
        if name not in scan_side:
            continue
        dims = {m.group(1): m.group(2) for m, _line in found}
        for m, line in found:
            if m.group(3) not in ('scatter', 'gather', 'dynamic-slice',
                                  'dynamic-update-slice'):
                continue
            operand = dims.get(re.findall(r'%([\w.\-]+)', m.group(4))[0])
            writes = m.group(3) in ('scatter', 'dynamic-update-slice')
            if operand in small or (writes and m.group(2) in small):
                on_small.append(f'{small.get(operand)}: {name}: {line[:160]}')
            if m.group(3) in indexed:
                indexed[m.group(3)].append(operand)
    assert not on_small, on_small
    assert indexed['scatter'] and \
        set(indexed['scatter']) == {lane_shape}, indexed['scatter']
    assert len(indexed['scatter']) <= 4, indexed['scatter']
    assert set(indexed['gather']) == {lane_shape, node_shape}, \
        indexed['gather']
    assert indexed['gather'].count(lane_shape) <= 4, indexed['gather']
