"""The device-routed fleet backend (automerge_tpu.fleet.backend): drop-in
Backend-contract conformance, differential equivalence against the host
backend, promotion/fallback, device materialization, and sync interop.

Modeled on the reference's alternative-backend harness (test/wasm.js:27-36):
the same change streams go through the host backend and the fleet backend,
asserting identical patches, state, and serialization."""

import copy

import numpy as np
import pytest

import automerge_tpu as am
from automerge_tpu import backend as host_backend
from automerge_tpu import native
from automerge_tpu.columnar import encode_change
from automerge_tpu.fleet import backend as fleet_backend
from automerge_tpu.fleet.backend import DocFleet, FleetBackend, FleetDoc

ACTORS = ['aa' * 16, 'bb' * 16, 'cc' * 16, '11' * 16]


def change_buf(actor, seq, start_op, ops, deps=(), time=0, message=''):
    return encode_change({
        'actor': actor, 'seq': seq, 'startOp': start_op, 'time': time,
        'message': message, 'deps': sorted(deps), 'ops': ops,
    })


def fresh_pair():
    """A host backend handle and a fleet backend handle on a private fleet."""
    fb = FleetBackend(DocFleet(doc_capacity=4, key_capacity=4))
    return host_backend.init(), fb.init(), fb


def apply_both(hb, gb, changes):
    hb2, hp = host_backend.apply_changes(hb, changes)
    gb2, gp = fleet_backend.apply_changes(gb, changes)
    assert hp == gp
    return hb2, gb2


class TestDifferential:
    def test_simple_sets_and_patches(self):
        hb, gb, _ = fresh_pair()
        c1 = change_buf(ACTORS[0], 1, 1, [
            {'action': 'set', 'obj': '_root', 'key': 'bird', 'value': 'magpie',
             'pred': []},
            {'action': 'set', 'obj': '_root', 'key': 'n', 'value': 7,
             'datatype': 'int', 'pred': []},
        ])
        hb, gb = apply_both(hb, gb, [c1])
        c2 = change_buf(ACTORS[0], 2, 3, [
            {'action': 'set', 'obj': '_root', 'key': 'bird', 'value': 'wren',
             'pred': [f'1@{ACTORS[0]}']},
        ], deps=host_backend.get_heads(hb))
        hb, gb = apply_both(hb, gb, [c2])
        assert host_backend.get_patch(hb) == fleet_backend.get_patch(gb)
        assert gb['state'].materialize() == {'bird': 'wren', 'n': 7}

    def test_concurrent_conflict_sets(self):
        hb, gb, _ = fresh_pair()
        c1 = change_buf(ACTORS[0], 1, 1, [
            {'action': 'set', 'obj': '_root', 'key': 'x', 'value': 1,
             'datatype': 'int', 'pred': []}])
        c2 = change_buf(ACTORS[1], 1, 1, [
            {'action': 'set', 'obj': '_root', 'key': 'x', 'value': 2,
             'datatype': 'int', 'pred': []}])
        hb, gb = apply_both(hb, gb, [c1, c2])
        hp = host_backend.get_patch(hb)
        assert set(hp['diffs']['props']['x'].keys()) == \
            {f'1@{ACTORS[0]}', f'1@{ACTORS[1]}'}
        assert hp == fleet_backend.get_patch(gb)
        # Lamport winner: equal counters, higher actor id wins
        assert gb['state'].materialize() == {'x': 2}

    def test_counter_accumulation(self):
        hb, gb, _ = fresh_pair()
        c1 = change_buf(ACTORS[0], 1, 1, [
            {'action': 'set', 'obj': '_root', 'key': 'c', 'value': 10,
             'datatype': 'counter', 'pred': []}])
        hb, gb = apply_both(hb, gb, [c1])
        c2 = change_buf(ACTORS[0], 2, 2, [
            {'action': 'inc', 'obj': '_root', 'key': 'c', 'value': 4,
             'pred': [f'1@{ACTORS[0]}']}],
            deps=host_backend.get_heads(hb))
        c3 = change_buf(ACTORS[1], 1, 2, [
            {'action': 'inc', 'obj': '_root', 'key': 'c', 'value': -2,
             'pred': [f'1@{ACTORS[0]}']}])
        hb, gb = apply_both(hb, gb, [c2, c3])
        hp = host_backend.get_patch(hb)
        assert hp['diffs']['props']['c'][f'1@{ACTORS[0]}'] == \
            {'type': 'value', 'value': 12, 'datatype': 'counter'}
        assert hp == fleet_backend.get_patch(gb)
        assert gb['state'].materialize() == {'c': 12}

    def test_delete_and_empty_props(self):
        hb, gb, _ = fresh_pair()
        c1 = change_buf(ACTORS[0], 1, 1, [
            {'action': 'set', 'obj': '_root', 'key': 'k', 'value': 1,
             'datatype': 'int', 'pred': []}])
        hb, gb = apply_both(hb, gb, [c1])
        c2 = change_buf(ACTORS[0], 2, 2, [
            {'action': 'del', 'obj': '_root', 'key': 'k',
             'pred': [f'1@{ACTORS[0]}']}], deps=host_backend.get_heads(hb))
        hb2, hp = host_backend.apply_changes(hb, [c2])
        gb2, gp = fleet_backend.apply_changes(gb, [c2])
        assert hp == gp
        assert hp['diffs']['props']['k'] == {}
        assert host_backend.get_patch(hb2) == fleet_backend.get_patch(gb2)
        assert gb2['state'].materialize() == {}

    def test_save_load_round_trip(self):
        hb, gb, fb = fresh_pair()
        c1 = change_buf(ACTORS[0], 1, 1, [
            {'action': 'set', 'obj': '_root', 'key': 'a', 'value': 'x',
             'pred': []}])
        c2 = change_buf(ACTORS[1], 1, 1, [
            {'action': 'set', 'obj': '_root', 'key': 'b', 'value': True,
             'pred': []}])
        hb, gb = apply_both(hb, gb, [c1, c2])
        assert bytes(host_backend.save(hb)) == bytes(fleet_backend.save(gb))
        # Load the saved doc back through the fleet backend
        gb2 = fb.load(host_backend.save(hb))
        assert fleet_backend.get_patch(gb2) == host_backend.get_patch(hb)
        assert gb2['state'].is_fleet

    def test_queueing_missing_deps(self):
        hb, gb, _ = fresh_pair()
        c1 = change_buf(ACTORS[0], 1, 1, [
            {'action': 'set', 'obj': '_root', 'key': 'k', 'value': 1,
             'datatype': 'int', 'pred': []}])
        h1 = am.decode_change(c1)['hash']
        c2 = change_buf(ACTORS[0], 2, 2, [
            {'action': 'set', 'obj': '_root', 'key': 'k', 'value': 2,
             'datatype': 'int', 'pred': [f'1@{ACTORS[0]}']}], deps=[h1])
        hb, gb = apply_both(hb, gb, [c2])   # queued: dep missing
        assert fleet_backend.get_missing_deps(gb) == [h1]
        hb, gb = apply_both(hb, gb, [c1])   # both drain
        assert host_backend.get_patch(hb) == fleet_backend.get_patch(gb)
        assert gb['state'].materialize() == {'k': 2}

    def test_error_parity_and_rollback(self):
        for bad_ops, msg in [
            ([{'action': 'set', 'obj': '_root', 'key': 'k', 'value': 9,
               'datatype': 'int', 'pred': [f'9@{ACTORS[1]}']}],
             'no matching operation for pred'),
            ([{'action': 'inc', 'obj': '_root', 'key': 'z', 'value': 1,
               'pred': []}], 'unknown counter'),
        ]:
            hb, gb, _ = fresh_pair()
            setup = change_buf(ACTORS[0], 1, 1, [
                {'action': 'set', 'obj': '_root', 'key': 'k', 'value': 1,
                 'datatype': 'int', 'pred': []}])
            hb, gb = apply_both(hb, gb, [setup])
            bad = change_buf(ACTORS[0], 2, 2, bad_ops,
                             deps=host_backend.get_heads(hb))
            with pytest.raises(ValueError, match=msg):
                host_backend.apply_changes(hb, [bad])
            hb2, gb2, _ = fresh_pair()
            hb2, gb2 = apply_both(hb2, gb2, [setup])
            with pytest.raises(ValueError, match=msg):
                fleet_backend.apply_changes(gb2, [bad])
            # Fleet state must be unchanged after the failed call
            assert fleet_backend.get_patch(gb2) == host_backend.get_patch(hb2)
            assert gb2['state'].materialize() == {'k': 1}

    def test_seq_gate_errors(self):
        _, gb, _ = fresh_pair()
        c = change_buf(ACTORS[0], 3, 1, [
            {'action': 'set', 'obj': '_root', 'key': 'k', 'value': 1,
             'datatype': 'int', 'pred': []}])
        with pytest.raises(ValueError, match='Skipped sequence number'):
            fleet_backend.apply_changes(gb, [c])

    def test_randomized_differential(self):
        rng = np.random.default_rng(7)
        for trial in range(6):
            hb, gb, fb = fresh_pair()
            seqs = {a: 0 for a in ACTORS[:3]}
            ctrs = {a: 0 for a in ACTORS[:3]}
            visible = {}    # key -> set of opIds (tracked for pred choice)
            values = ['x', -5, 3.25, None, True, 1 << 40, 'yy']
            for step in range(30):
                actor = ACTORS[int(rng.integers(0, 3))]
                key = f'k{int(rng.integers(0, 5))}'
                seqs[actor] += 1
                ctr = max(ctrs.values()) + 1
                kind = rng.random()
                vis = sorted(visible.get(key, set()))
                if kind < 0.55 or not vis:
                    value = values[int(rng.integers(0, len(values)))] \
                        if rng.random() < 0.5 else int(rng.integers(0, 100))
                    pred = vis if rng.random() < 0.7 else []
                    op = {'action': 'set', 'obj': '_root', 'key': key,
                          'value': value, 'pred': pred}
                    if isinstance(value, int) and not isinstance(value, bool):
                        op['datatype'] = 'int'
                    visible.setdefault(key, set()).difference_update(pred)
                    visible[key].add(f'{ctr}@{actor}')
                elif kind < 0.8:
                    pred = vis
                    op = {'action': 'del', 'obj': '_root', 'key': key,
                          'pred': pred}
                    visible[key].difference_update(pred)
                else:
                    value = int(rng.integers(0, 50))
                    pred = vis
                    op = {'action': 'set', 'obj': '_root', 'key': key,
                          'value': value, 'datatype': 'counter', 'pred': pred}
                    visible[key].difference_update(pred)
                    visible[key].add(f'{ctr}@{actor}')
                deps = host_backend.get_heads(hb) if rng.random() < 0.8 else []
                buf = change_buf(actor, seqs[actor], ctr, [op], deps=deps)
                ctrs[actor] = ctr
                hb, gb = apply_both(hb, gb, [buf])
            assert host_backend.get_patch(hb) == fleet_backend.get_patch(gb)
            assert bytes(host_backend.save(hb)) == bytes(fleet_backend.save(gb))


class TestDeviceMaterialization:
    def test_device_matches_mirror(self):
        fb = FleetBackend(DocFleet(doc_capacity=4, key_capacity=4))
        rng = np.random.default_rng(3)
        handles = fleet_backend.init_docs(6, fb.fleet)
        seqs = [0] * 6
        per_doc = [[] for _ in range(6)]
        for d in range(6):
            ctr = 0
            vis = {}
            actor = ACTORS[d % 2]
            for _ in range(12):
                key = f'k{int(rng.integers(0, 6))}'
                ctr += 1
                if rng.random() < 0.3 and vis.get(key):
                    op = {'action': 'del', 'obj': '_root', 'key': key,
                          'pred': sorted(vis[key])}
                    vis[key] = set()
                else:
                    op = {'action': 'set', 'obj': '_root', 'key': key,
                          'value': int(rng.integers(0, 1000)),
                          'datatype': 'int', 'pred': sorted(vis.get(key, set()))}
                    vis[key] = {f'{ctr}@{actor}'}
                seqs[d] += 1
                deps = host_backend.get_heads(handles[d]) if seqs[d] > 1 else []
                per_doc[d].append(change_buf(actor, seqs[d], ctr,
                                             [op], deps=deps))
            handles[d], _ = fleet_backend.apply_changes(handles[d], per_doc[d])
        mirror = [h['state'].materialize() for h in handles]
        device = fleet_backend.materialize_docs(handles)
        assert device == mirror

    def test_conflicted_counter_increment_matches_reference(self):
        """An inc on a conflicted counter preds EVERY conflicting set; the
        reference attributes it to the Lamport-MAX pred'd set
        (counterStates[succOp] overwrites earlier registrations,
        new.js:942-945) and the other conflicting sets never complete
        their counter state — they stay invisible. The register engine
        must do the same: add to the max live pred'd lane, hide the rest
        (round-4 50x-chaos find, seed 18)."""
        import automerge_tpu as am
        a, b, c = ACTORS[0], ACTORS[1], ACTORS[2]
        c1 = change_buf(a, 1, 1, [
            {'action': 'makeMap', 'obj': '_root', 'key': 'm', 'pred': []}])
        h1 = am.decode_change(c1)['hash']
        # concurrent counter creations under the same key -> conflict
        c2 = change_buf(a, 2, 2, [
            {'action': 'set', 'obj': f'1@{a}', 'key': 'y', 'value': 0,
             'datatype': 'counter', 'pred': []}], deps=[h1])
        c3 = change_buf(b, 1, 2, [
            {'action': 'set', 'obj': f'1@{a}', 'key': 'y', 'value': 3,
             'datatype': 'counter', 'pred': []}], deps=[h1])
        h2 = am.decode_change(c2)['hash']
        h3 = am.decode_change(c3)['hash']
        # an actor that has seen BOTH increments the conflicted counter:
        # pred lists every conflicting set op
        c4 = change_buf(c, 1, 3, [
            {'action': 'inc', 'obj': f'1@{a}', 'key': 'y', 'value': 1,
             'datatype': 'counter', 'pred': [f'2@{a}', f'2@{b}']}],
            deps=sorted([h2, h3]))
        hb = host_backend.init()
        for ch in (c1, c2, c3, c4):
            hb, _ = host_backend.apply_changes(hb, [ch])
        want = host_backend.get_patch(hb)
        for turbo in (False, True):
            fleet = DocFleet(doc_capacity=2, key_capacity=8,
                             exact_device=True)
            gb = fleet_backend.init(fleet)
            if turbo:
                [gb], _ = fleet_backend.apply_changes_docs(
                    [gb], [[c1, c2, c3, c4]], mirror=False)
            else:
                for ch in (c1, c2, c3, c4):
                    gb, _ = fleet_backend.apply_changes(gb, [ch])
            got = fleet_backend.get_patch(gb)
            assert got == want, turbo
            assert fleet.metrics.mirror_rebuilds == 0
            # winner (higher actor) shows base 3 + the shared inc
            assert fleet_backend.materialize_docs([gb]) == [{'m': {'y': 4}}]

    def test_conflicted_counter_inc_with_dead_max_pred(self):
        """The attribution target is the Lamport-max pred even when that
        set was already overwritten: the inc is consumed silently by the
        dead set, and the LIVE lower branch still hides (its succ never
        completes). The reference shows only the overwriting value."""
        import automerge_tpu as am
        a, b, c = ACTORS[0], ACTORS[1], ACTORS[2]
        c1 = change_buf(a, 1, 1, [
            {'action': 'makeMap', 'obj': '_root', 'key': 'm', 'pred': []}])
        h1 = am.decode_change(c1)['hash']
        c2 = change_buf(a, 2, 2, [
            {'action': 'set', 'obj': f'1@{a}', 'key': 'y', 'value': 0,
             'datatype': 'counter', 'pred': []}], deps=[h1])
        c3 = change_buf(b, 1, 2, [
            {'action': 'set', 'obj': f'1@{a}', 'key': 'y', 'value': 3,
             'datatype': 'counter', 'pred': []}], deps=[h1])
        h2 = am.decode_change(c2)['hash']
        h3 = am.decode_change(c3)['hash']
        # b overwrites its own counter with a plain value...
        c4 = change_buf(b, 2, 3, [
            {'action': 'set', 'obj': f'1@{a}', 'key': 'y', 'value': 9,
             'datatype': 'int', 'pred': [f'2@{b}']}], deps=[h3])
        h4 = am.decode_change(c4)['hash']
        # ...while c, who saw only the two counters, incs the conflict
        c5 = change_buf(c, 1, 3, [
            {'action': 'inc', 'obj': f'1@{a}', 'key': 'y', 'value': 1,
             'datatype': 'counter', 'pred': [f'2@{a}', f'2@{b}']}],
            deps=sorted([h2, h3]))
        hb = host_backend.init()
        for ch in (c1, c2, c3, c4, c5):
            hb, _ = host_backend.apply_changes(hb, [ch])
        want = host_backend.get_patch(hb)
        for turbo in (False, True):
            fleet = DocFleet(doc_capacity=2, key_capacity=8,
                             exact_device=True)
            gb = fleet_backend.init(fleet)
            if turbo:
                [gb], _ = fleet_backend.apply_changes_docs(
                    [gb], [[c1, c2, c3, c4, c5]], mirror=False)
            else:
                for ch in (c1, c2, c3, c4, c5):
                    gb, _ = fleet_backend.apply_changes(gb, [ch])
            got = fleet_backend.get_patch(gb)
            assert got == want, turbo
            assert fleet_backend.materialize_docs([gb]) == \
                [{'m': {'y': 9}}], turbo

    def test_counter_inc_of_overwritten_set_not_served_wrong(self):
        """Round-4 chaos find: the grid's counter cell cannot attribute an
        inc to its pred, so an inc whose counter set lost (or was
        overwritten in the same batch) was credited to the winning counter
        and materialize_docs served base+1. The host winner mirror now
        flags such slots into grid_overflow and reads fall back to the
        exact mirror (ref new.js:937-965 counter succ semantics)."""
        import automerge_tpu as am
        a, b = ACTORS[0], ACTORS[1]
        c1 = change_buf(a, 1, 1, [
            {'action': 'set', 'obj': '_root', 'key': 'x', 'value': 5,
             'datatype': 'counter', 'pred': []}])
        h1 = am.decode_change(c1)['hash']
        c2 = change_buf(a, 2, 2, [
            {'action': 'inc', 'obj': '_root', 'key': 'x', 'value': 1,
             'datatype': 'counter', 'pred': [f'1@{a}']}], deps=[h1])
        c3 = change_buf(b, 1, 3, [
            {'action': 'set', 'obj': '_root', 'key': 'x', 'value': 6,
             'datatype': 'counter', 'pred': [f'1@{a}']}], deps=[h1])
        for split in (False, True):
            for mirror in (True, False):
                fleet = DocFleet(doc_capacity=2, key_capacity=4)
                h = fleet_backend.init(fleet)
                groups = [[c1, c2], [c3]] if split else [[c1, c2, c3]]
                for g in groups:
                    if mirror:
                        h, _ = fleet_backend.apply_changes(h, g)
                    else:
                        [h], _ = fleet_backend.apply_changes_docs(
                            [h], [g], mirror=False)
                assert fleet_backend.materialize_docs([h]) == [{'x': 6}], \
                    (split, mirror)
        # The happy path — incs of the standing winner — must NOT flag
        fleet = DocFleet(doc_capacity=2, key_capacity=4)
        h = fleet_backend.init(fleet)
        [h], _ = fleet_backend.apply_changes_docs([h], [[c1, c2]],
                                                  mirror=False)
        assert fleet_backend.materialize_docs([h]) == [{'x': 6}]
        assert 0 not in fleet.grid_overflow

    def test_negative_inc_delta_device_parity(self):
        """Negative inc deltas must land inline in the value column, not as
        value-table references (regression: device counters were corrupted
        by the table index)."""
        fb = FleetBackend(DocFleet(doc_capacity=2, key_capacity=2))
        gb = fb.init()
        c1 = change_buf(ACTORS[0], 1, 1, [
            {'action': 'set', 'obj': '_root', 'key': 'c', 'value': 10,
             'datatype': 'counter', 'pred': []}])
        gb, _ = fleet_backend.apply_changes(gb, [c1])
        c2 = change_buf(ACTORS[0], 2, 2, [
            {'action': 'inc', 'obj': '_root', 'key': 'c', 'value': -5,
             'pred': [f'1@{ACTORS[0]}']}], deps=fleet_backend.get_heads(gb))
        gb, _ = fleet_backend.apply_changes(gb, [c2])
        assert gb['state'].materialize() == {'c': 5}
        assert fleet_backend.materialize_docs([gb]) == [{'c': 5}]

    def test_counter_overwrite_resets_device_accumulator(self):
        """A causally-later plain set over a counter must not inherit the
        counter's accumulated increments on the device read path
        (regression: the counters column was never reset)."""
        fb = FleetBackend(DocFleet(doc_capacity=2, key_capacity=2))
        gb = fb.init()
        c1 = change_buf(ACTORS[0], 1, 1, [
            {'action': 'set', 'obj': '_root', 'key': 'c', 'value': 10,
             'datatype': 'counter', 'pred': []}])
        gb, _ = fleet_backend.apply_changes(gb, [c1])
        c2 = change_buf(ACTORS[0], 2, 2, [
            {'action': 'inc', 'obj': '_root', 'key': 'c', 'value': 3,
             'pred': [f'1@{ACTORS[0]}']}], deps=fleet_backend.get_heads(gb))
        gb, _ = fleet_backend.apply_changes(gb, [c2])
        # Flush so the overwrite arrives in a separate device batch
        assert fleet_backend.materialize_docs([gb]) == [{'c': 13}]
        c3 = change_buf(ACTORS[0], 3, 3, [
            {'action': 'set', 'obj': '_root', 'key': 'c', 'value': 100,
             'datatype': 'int', 'pred': [f'1@{ACTORS[0]}']}],
            deps=fleet_backend.get_heads(gb))
        gb, _ = fleet_backend.apply_changes(gb, [c3])
        assert gb['state'].materialize() == {'c': 100}
        assert fleet_backend.materialize_docs([gb]) == [{'c': 100}]

    def test_actor_renumbering_tie_break(self):
        """Equal op counters, actors arriving in non-sorted order: the device
        scatter-max must still pick the reference's Lamport winner."""
        fb = FleetBackend(DocFleet(doc_capacity=2, key_capacity=2))
        gb = fb.init()
        # 'bb…' arrives first (gets number 0), then 'aa…' must renumber
        c1 = change_buf(ACTORS[1], 1, 1, [
            {'action': 'set', 'obj': '_root', 'key': 'x', 'value': 1,
             'datatype': 'int', 'pred': []}])
        gb, _ = fleet_backend.apply_changes(gb, [c1])
        fb.fleet.flush()
        c2 = change_buf(ACTORS[0], 1, 1, [
            {'action': 'set', 'obj': '_root', 'key': 'x', 'value': 2,
             'datatype': 'int', 'pred': []}])
        gb, _ = fleet_backend.apply_changes(gb, [c2])
        assert fleet_backend.materialize_docs([gb]) == [{'x': 1}]
        assert gb['state'].materialize() == {'x': 1}

    def test_batched_apply_one_dispatch(self):
        fb = FleetBackend(DocFleet(doc_capacity=8, key_capacity=8))
        handles = fleet_backend.init_docs(5, fb.fleet)
        per_doc = []
        for d in range(5):
            per_doc.append([change_buf(ACTORS[0], 1, 1, [
                {'action': 'set', 'obj': '_root', 'key': f'k{d}', 'value': d,
                 'datatype': 'int', 'pred': []}])])
        before = fb.fleet.dispatches
        handles, patches = fleet_backend.apply_changes_docs(handles, per_doc)
        assert fb.fleet.dispatches == before + 1
        assert all(p is not None for p in patches)
        docs = fleet_backend.materialize_docs(handles)
        assert docs == [{f'k{d}': d} for d in range(5)]

    def test_key_grid_growth(self):
        fb = FleetBackend(DocFleet(doc_capacity=2, key_capacity=2))
        gb = fb.init()
        for i in range(20):
            c = change_buf(ACTORS[0], i + 1, i + 1, [
                {'action': 'set', 'obj': '_root', 'key': f'key{i}', 'value': i,
                 'datatype': 'int', 'pred': []}],
                deps=fleet_backend.get_heads(gb))
            gb, _ = fleet_backend.apply_changes(gb, [c])
            fb.fleet.flush()
        expected = {f'key{i}': i for i in range(20)}
        assert fleet_backend.materialize_docs([gb]) == [expected]

    def test_clone_and_free(self):
        fb = FleetBackend(DocFleet(doc_capacity=2, key_capacity=2))
        gb = fb.init()
        c = change_buf(ACTORS[0], 1, 1, [
            {'action': 'set', 'obj': '_root', 'key': 'a', 'value': 5,
             'datatype': 'int', 'pred': []}])
        gb, _ = fleet_backend.apply_changes(gb, [c])
        gb2 = fleet_backend.clone(gb)
        c2 = change_buf(ACTORS[1], 1, 2, [
            {'action': 'set', 'obj': '_root', 'key': 'b', 'value': 6,
             'datatype': 'int', 'pred': []}],
            deps=fleet_backend.get_heads(gb2))
        gb2, _ = fleet_backend.apply_changes(gb2, [c2])
        assert fleet_backend.materialize_docs([gb2]) == [{'a': 5, 'b': 6}]
        assert gb['state'].materialize() == {'a': 5}
        fleet_backend.free(gb2)
        assert gb2['state'] is None


class TestTurboPath:
    def _workload(self, n_docs, n_changes, rng):
        per_doc = []
        for d in range(n_docs):
            changes, heads = [], []
            for c in range(n_changes):
                buf = change_buf(ACTORS[d % 3], c + 1, c + 1, [
                    {'action': 'set', 'obj': '_root',
                     'key': f'k{int(rng.integers(0, 4))}',
                     'value': int(rng.integers(0, 500)),
                     'datatype': 'int', 'pred': []}], deps=heads)
                heads = [am.decode_change(buf)['hash']]
                changes.append(buf)
            per_doc.append(changes)
        return per_doc

    def test_turbo_matches_exact(self):
        rng = np.random.default_rng(11)
        per_doc = self._workload(5, 8, rng)
        fb1 = FleetBackend(DocFleet(doc_capacity=8, key_capacity=8))
        fb2 = FleetBackend(DocFleet(doc_capacity=8, key_capacity=8))
        exact = fleet_backend.init_docs(5, fb1.fleet)
        turbo = fleet_backend.init_docs(5, fb2.fleet)
        exact, ep = fleet_backend.apply_changes_docs(exact, per_doc)
        turbo, tp = fleet_backend.apply_changes_docs(turbo, per_doc,
                                                     mirror=False)
        assert all(p is None for p in tp)
        assert fleet_backend.materialize_docs(exact) == \
            fleet_backend.materialize_docs(turbo)
        # Mirrors rebuild lazily and agree with the exact path
        for e, t in zip(exact, turbo):
            assert t['state']._impl.stale
            assert fleet_backend.get_patch(t) == fleet_backend.get_patch(e)
            assert not t['state']._impl.stale
            assert fleet_backend.get_heads(t) == fleet_backend.get_heads(e)
            assert bytes(fleet_backend.save(t)) == bytes(fleet_backend.save(e))

    def test_turbo_then_exact_interleave(self):
        fb = FleetBackend(DocFleet(doc_capacity=4, key_capacity=4))
        handles = fleet_backend.init_docs(2, fb.fleet)
        c1 = [[change_buf(ACTORS[0], 1, 1, [
            {'action': 'set', 'obj': '_root', 'key': 'a', 'value': d + 1,
             'datatype': 'int', 'pred': []}])] for d in range(2)]
        handles, _ = fleet_backend.apply_changes_docs(handles, c1,
                                                      mirror=False)
        # Exact call on a stale doc rebuilds the mirror and keeps going
        h0 = handles[0]
        c2 = change_buf(ACTORS[0], 2, 2, [
            {'action': 'set', 'obj': '_root', 'key': 'b', 'value': 9,
             'datatype': 'int', 'pred': []}],
            deps=fleet_backend.get_heads(h0))
        h0, patch = fleet_backend.apply_changes(h0, [c2])
        assert patch['diffs']['props']['b'] == \
            {f'2@{ACTORS[0]}': {'type': 'value', 'value': 9,
                                'datatype': 'int'}}
        assert h0['state'].materialize() == {'a': 1, 'b': 9}
        assert fleet_backend.materialize_docs([h0, handles[1]]) == \
            [{'a': 1, 'b': 9}, {'a': 2}]

    def test_turbo_queues_missing_deps(self):
        fb = FleetBackend(DocFleet(doc_capacity=4, key_capacity=4))
        handles = fleet_backend.init_docs(1, fb.fleet)
        c1 = change_buf(ACTORS[0], 1, 1, [
            {'action': 'set', 'obj': '_root', 'key': 'k', 'value': 1,
             'datatype': 'int', 'pred': []}])
        h1 = am.decode_change(c1)['hash']
        c2 = change_buf(ACTORS[0], 2, 2, [
            {'action': 'set', 'obj': '_root', 'key': 'k', 'value': 2,
             'datatype': 'int', 'pred': [f'1@{ACTORS[0]}']}], deps=[h1])
        handles, _ = fleet_backend.apply_changes_docs(handles, [[c2]],
                                                      mirror=False)
        assert fleet_backend.get_missing_deps(handles[0]) == [h1]
        # Dep arrives; queued change drains through the exact path
        handles, _ = fleet_backend.apply_changes_docs(handles, [[c1]],
                                                      mirror=False)
        assert handles[0]['state'].materialize() == {'k': 2}
        assert fleet_backend.materialize_docs(handles) == [{'k': 2}]

    def test_turbo_atomic_across_docs(self):
        """A gate error on one doc must roll back every doc in the turbo
        call (regression: earlier docs kept hash-graph entries whose ops
        never reached the device)."""
        fb = FleetBackend(DocFleet(doc_capacity=4, key_capacity=4))
        handles = fleet_backend.init_docs(2, fb.fleet)
        good = change_buf(ACTORS[0], 1, 1, [
            {'action': 'set', 'obj': '_root', 'key': 'a', 'value': 7,
             'datatype': 'int', 'pred': []}])
        bad = change_buf(ACTORS[1], 3, 1, [     # seq skip
            {'action': 'set', 'obj': '_root', 'key': 'b', 'value': 1,
             'datatype': 'int', 'pred': []}])
        with pytest.raises(ValueError, match='Skipped sequence number'):
            fleet_backend.apply_changes_docs(handles, [[good], [bad]],
                                             mirror=False)
        assert fleet_backend.get_heads(handles[0]) == []
        assert handles[0]['state'].materialize() == {}
        assert fleet_backend.materialize_docs(handles) == [{}, {}]

    def test_turbo_queue_only_no_dispatch_no_interning(self):
        """A turbo call where everything queues must not issue a device
        dispatch nor intern the queued changes' keys (regression)."""
        fb = FleetBackend(DocFleet(doc_capacity=4, key_capacity=4))
        handles = fleet_backend.init_docs(1, fb.fleet)
        dangling = change_buf(ACTORS[0], 1, 1, [
            {'action': 'set', 'obj': '_root', 'key': 'ghostkey', 'value': 1,
             'datatype': 'int', 'pred': []}], deps=['ab' * 32])
        before = fb.fleet.dispatches
        handles, _ = fleet_backend.apply_changes_docs(handles, [[dangling]],
                                                      mirror=False)
        assert fb.fleet.dispatches == before
        assert len(fb.fleet.keys) == 0
        assert fleet_backend.get_missing_deps(handles[0]) == ['ab' * 32]

    def test_turbo_duplicate_op_id_rejected(self):
        fb = FleetBackend(DocFleet(doc_capacity=4, key_capacity=4))
        handles = fleet_backend.init_docs(1, fb.fleet)
        c1 = change_buf(ACTORS[0], 1, 1, [
            {'action': 'set', 'obj': '_root', 'key': 'a', 'value': 1,
             'datatype': 'int', 'pred': []}])
        # Same opId (1@actor) from a different change in the same batch
        c2 = change_buf(ACTORS[0], 2, 1, [
            {'action': 'set', 'obj': '_root', 'key': 'b', 'value': 2,
             'datatype': 'int', 'pred': []}],
            deps=[am.decode_change(c1)['hash']])
        with pytest.raises(ValueError, match='duplicate operation ID'):
            fleet_backend.apply_changes_docs(handles, [[c1, c2]],
                                             mirror=False)
        assert fleet_backend.get_heads(handles[0]) == []

    def test_turbo_sync_without_rebuild(self):
        """Sync needs only the hash graph: a turbo doc syncs to a host doc
        without its mirror ever being rebuilt."""
        fb = FleetBackend(DocFleet(doc_capacity=4, key_capacity=4))
        handles = fleet_backend.init_docs(1, fb.fleet)
        c1 = [[change_buf(ACTORS[0], 1, 1, [
            {'action': 'set', 'obj': '_root', 'key': 'k', 'value': 5,
             'datatype': 'int', 'pred': []}])]]
        handles, _ = fleet_backend.apply_changes_docs(handles, c1,
                                                      mirror=False)
        gb, hb = handles[0], host_backend.init()
        s1, s2 = fleet_backend.init_sync_state(), host_backend.init_sync_state()
        for _ in range(8):
            s1, m = fleet_backend.generate_sync_message(gb, s1)
            if m is not None:
                hb, s2, _ = host_backend.receive_sync_message(hb, s2, m)
            s2, r = host_backend.generate_sync_message(hb, s2)
            if r is not None:
                gb, s1, _ = fleet_backend.receive_sync_message(gb, s1, r)
            if m is None and r is None:
                break
        assert host_backend.get_heads(hb) == fleet_backend.get_heads(gb)
        assert host_backend.get_patch(hb)['diffs']['props']['k'] == \
            {f'1@{ACTORS[0]}': {'type': 'value', 'value': 5,
                                'datatype': 'int'}}


class TestPromotion:
    def test_nested_maps_stay_fleet_resident(self):
        fb = FleetBackend(DocFleet(doc_capacity=2, key_capacity=2))
        hb = host_backend.init()
        gb = fb.init()
        flat = change_buf(ACTORS[0], 1, 1, [
            {'action': 'set', 'obj': '_root', 'key': 'a', 'value': 1,
             'datatype': 'int', 'pred': []}])
        hb, gb = apply_both(hb, gb, [flat])
        assert gb['state'].is_fleet
        nested = change_buf(ACTORS[0], 2, 2, [
            {'action': 'makeMap', 'obj': '_root', 'key': 'm', 'pred': []},
            {'action': 'set', 'obj': f'2@{ACTORS[0]}', 'key': 'x', 'value': 9,
             'datatype': 'int', 'pred': []}],
            deps=host_backend.get_heads(hb))
        hb, gb = apply_both(hb, gb, [nested])
        assert gb['state'].is_fleet          # two-level key interning
        assert gb['state'].fleet.metrics.promotions == 0
        assert host_backend.get_patch(hb) == fleet_backend.get_patch(gb)
        # Nested-map docs materialize from the device grid
        from automerge_tpu.fleet.backend import materialize_docs
        assert materialize_docs([gb]) == [{'a': 1, 'm': {'x': 9}}]
        more = change_buf(ACTORS[0], 3, 4, [
            {'action': 'set', 'obj': '_root', 'key': 'a', 'value': 2,
             'datatype': 'int', 'pred': [f'1@{ACTORS[0]}']}],
            deps=host_backend.get_heads(hb))
        hb, gb = apply_both(hb, gb, [more])
        assert bytes(host_backend.save(hb)) == bytes(fleet_backend.save(gb))

    def test_object_inside_sequence_stays_fleet_resident(self):
        """Rows-in-lists (a map created as a list element,
        ref new.js:1461-1528) ride the device: the element value links to
        the child object, whose keys intern as (objectId, key) grid
        columns like any nested map."""
        fb = FleetBackend(DocFleet(doc_capacity=2, key_capacity=2))
        hb = host_backend.init()
        gb = fb.init()
        nested_in_list = change_buf(ACTORS[0], 1, 1, [
            {'action': 'makeList', 'obj': '_root', 'key': 'l', 'pred': []},
            {'action': 'makeMap', 'obj': f'1@{ACTORS[0]}', 'elemId': '_head',
             'insert': True, 'pred': []},
            {'action': 'set', 'obj': f'2@{ACTORS[0]}', 'key': 'row',
             'value': 3, 'datatype': 'int', 'pred': []}])
        hb, gb = apply_both(hb, gb, [nested_in_list])
        assert gb['state'].is_fleet
        assert fb.fleet.metrics.promotions == 0
        from automerge_tpu.fleet.backend import materialize_docs
        assert materialize_docs([gb]) == [{'l': [{'row': 3}]}]
        assert bytes(host_backend.save(hb)) == bytes(fleet_backend.save(gb))

    def test_turbo_rows_in_lists_no_fallback(self):
        """The native turbo parser emits make-inside-sequence rows (flags
        11-14), so rows-in-lists workloads keep the wire->device path:
        one turbo call, zero fallbacks, device reads and saves identical
        to the host engine."""
        import automerge_tpu as am
        a = ACTORS[0]
        ops1 = [
            {'action': 'makeList', 'obj': '_root', 'key': 'todo',
             'pred': []},
            {'action': 'makeMap', 'obj': f'1@{a}', 'elemId': '_head',
             'insert': True, 'pred': []},
            {'action': 'set', 'obj': f'2@{a}', 'key': 't', 'value': 'wash',
             'pred': []},
            {'action': 'makeList', 'obj': f'1@{a}', 'elemId': f'2@{a}',
             'insert': True, 'pred': []},
            {'action': 'set', 'obj': f'4@{a}', 'elemId': '_head',
             'insert': True, 'value': 1, 'datatype': 'int', 'pred': []},
        ]
        c1 = change_buf(a, 1, 1, ops1)
        c2 = change_buf(a, 2, 6, [
            {'action': 'set', 'obj': f'2@{a}', 'key': 'n', 'value': 5,
             'datatype': 'int', 'pred': []},
            {'action': 'set', 'obj': f'4@{a}', 'elemId': f'5@{a}',
             'insert': True, 'value': 2, 'datatype': 'int', 'pred': []}],
            deps=[am.decode_change(c1)['hash']])
        for exact in (False, True):
            fleet = DocFleet(doc_capacity=2, key_capacity=8,
                             exact_device=exact)
            handles = fleet_backend.init_docs(2, fleet)
            handles, _ = fleet_backend.apply_changes_docs(
                handles, [[c1, c2]] * 2, mirror=False)
            assert fleet.metrics.turbo_calls == 1, exact
            assert fleet.metrics.fallbacks == 0, exact
            assert fleet.metrics.promotions == 0, exact
            want = {'todo': [{'t': 'wash', 'n': 5}, [1, 2]]}
            assert fleet_backend.materialize_docs(handles) == [want] * 2
            hb = host_backend.init()
            hb, _ = host_backend.apply_changes(hb, [c1, c2])
            assert bytes(host_backend.save(hb)) == \
                bytes(fleet_backend.save(handles[0]))

    def test_link_op_rejected_loudly(self):
        """`link` is a reserved action the reference never applies
        (new.js:893 TODO); both engines reject it with the same error
        instead of silently promoting or storing a dangling child edge."""
        fb = FleetBackend(DocFleet(doc_capacity=2, key_capacity=2))
        gb = fb.init()
        link = change_buf(ACTORS[0], 1, 1, [
            {'action': 'link', 'obj': '_root', 'key': 'x',
             'child': f'1@{ACTORS[1]}', 'pred': []}])
        with pytest.raises(ValueError, match='link operations are not supported'):
            fleet_backend.apply_changes(gb, [link])
        with pytest.raises(ValueError, match='link operations are not supported'):
            host_backend.apply_changes(host_backend.init(), [link])
        # The rejection must be free: no promotion, no lost device slot
        assert gb['state'].is_fleet
        assert fb.fleet.metrics.promotions == 0
        # The failed call must not corrupt the handle: it still applies
        # ordinary changes afterwards, still fleet-resident
        ok = change_buf(ACTORS[0], 1, 1, [
            {'action': 'set', 'obj': '_root', 'key': 'k', 'value': 5,
             'datatype': 'int', 'pred': []}])
        gb, patch = fleet_backend.apply_changes(gb, [ok])
        assert patch['clock'] == {ACTORS[0]: 1}
        assert gb['state'].is_fleet

    def test_promotion_preserves_queue(self):
        fb = FleetBackend(DocFleet(doc_capacity=2, key_capacity=2))
        gb = fb.init()
        c1 = change_buf(ACTORS[0], 1, 1, [
            {'action': 'set', 'obj': '_root', 'key': 'k', 'value': 1,
             'datatype': 'int', 'pred': []}])
        h1 = am.decode_change(c1)['hash']
        c2 = change_buf(ACTORS[0], 2, 2, [
            {'action': 'set', 'obj': '_root', 'key': 'k', 'value': 2,
             'datatype': 'int', 'pred': [f'1@{ACTORS[0]}']}], deps=[h1])
        gb, patch = fleet_backend.apply_changes(gb, [c2])
        assert patch['pendingChanges'] == 1
        # A sequence make past the packed-counter window stays on the
        # device: the grid rebases the key's window and the list's row
        # packs wide once its ids pass it
        from automerge_tpu.fleet.tensor_doc import CTR_LIMIT
        big = change_buf(ACTORS[1], 1, CTR_LIMIT + 1, [
            {'action': 'makeList', 'obj': '_root', 'key': 'l', 'pred': []}])
        gb, _ = fleet_backend.apply_changes(gb, [big])
        assert gb['state'].is_fleet
        gb, patch = fleet_backend.apply_changes(gb, [c1])
        assert patch['pendingChanges'] == 0
        props = fleet_backend.get_patch(gb)['diffs']['props']
        assert props['k'] == {f'2@{ACTORS[0]}':
                              {'type': 'value', 'value': 2, 'datatype': 'int'}}


class TestSyncInterop:
    def test_fleet_host_sync_convergence(self):
        fb = FleetBackend(DocFleet(doc_capacity=2, key_capacity=2))
        gb = fb.init()
        hb = host_backend.init()
        c1 = change_buf(ACTORS[0], 1, 1, [
            {'action': 'set', 'obj': '_root', 'key': 'fleet', 'value': 1,
             'datatype': 'int', 'pred': []}])
        gb, _ = fleet_backend.apply_changes(gb, [c1])
        c2 = change_buf(ACTORS[1], 1, 1, [
            {'action': 'set', 'obj': '_root', 'key': 'host', 'value': 2,
             'datatype': 'int', 'pred': []}])
        hb, _ = host_backend.apply_changes(hb, [c2])

        s1, s2 = fleet_backend.init_sync_state(), host_backend.init_sync_state()
        for _ in range(10):
            s1, msg = fleet_backend.generate_sync_message(gb, s1)
            if msg is not None:
                hb, s2, _ = host_backend.receive_sync_message(hb, s2, msg)
            s2, reply = host_backend.generate_sync_message(hb, s2)
            if reply is not None:
                gb, s1, _ = fleet_backend.receive_sync_message(gb, s1, reply)
            if msg is None and reply is None:
                break
        assert fleet_backend.get_heads(gb) == host_backend.get_heads(hb)
        assert fleet_backend.get_patch(gb) == host_backend.get_patch(hb)
        assert gb['state'].materialize() == {'fleet': 1, 'host': 2}


class TestDropIn:
    def test_set_default_backend_public_api(self):
        fb = FleetBackend(DocFleet(doc_capacity=4, key_capacity=4))
        am.set_default_backend(fb)
        try:
            d1 = am.init(ACTORS[0])
            d1 = am.change(d1, lambda doc: doc.update({'title': 'fleet'}))
            d2 = am.init(ACTORS[1])
            d2 = am.merge(d2, d1)
            d2 = am.change(d2, lambda doc: doc.update({'count': 3}))
            d1 = am.merge(d1, d2)
            assert d1['title'] == 'fleet'
            assert d1['count'] == 3
            data = am.save(d1)
            d3 = am.load(data)
            assert am.equals(d3, d1)
            # Nested objects trigger transparent promotion
            d1 = am.change(d1, lambda doc: doc.update({'nested': {'x': 1}}))
            assert d1['nested']['x'] == 1
        finally:
            am.set_default_backend(host_backend)


class TestExactDeviceMode:
    """DocFleet(exact_device=True): the multi-value register engine as the
    fleet's device state — resurrection/conflict/counter corners exact on
    the device read path, not just the host mirror."""

    def _fb(self):
        return FleetBackend(DocFleet(doc_capacity=4, key_capacity=4,
                                     exact_device=True))

    def test_resurrection_exact_on_device(self):
        fb = self._fb()
        gb = fb.init()
        c1 = change_buf(ACTORS[0], 1, 1, [
            {'action': 'set', 'obj': '_root', 'key': 'k', 'value': 5,
             'datatype': 'int', 'pred': []}])
        gb, _ = fleet_backend.apply_changes(gb, [c1])
        # Concurrent: bb overwrites (2@bb), cc deletes with greater opId
        c2 = change_buf(ACTORS[1], 1, 2, [
            {'action': 'set', 'obj': '_root', 'key': 'k', 'value': 7,
             'datatype': 'int', 'pred': [f'1@{ACTORS[0]}']}],
            deps=fleet_backend.get_heads(gb))
        c3 = change_buf(ACTORS[2], 1, 9, [
            {'action': 'del', 'obj': '_root', 'key': 'k',
             'pred': [f'1@{ACTORS[0]}']}],
            deps=[am.decode_change(c1)['hash']])
        gb, _ = fleet_backend.apply_changes(gb, [c2, c3])
        # Device read path must keep bb's set alive (the LWW grid would
        # have shown the key deleted: 9@cc > 2@bb)
        assert fleet_backend.materialize_docs([gb]) == [{'k': 7}]
        assert gb['state'].materialize() == {'k': 7}

    def test_conflicts_on_device(self):
        fb = self._fb()
        gb = fb.init()
        c1 = change_buf(ACTORS[0], 1, 1, [
            {'action': 'set', 'obj': '_root', 'key': 'x', 'value': 1,
             'datatype': 'int', 'pred': []}])
        c2 = change_buf(ACTORS[1], 1, 1, [
            {'action': 'set', 'obj': '_root', 'key': 'x', 'value': 2,
             'datatype': 'int', 'pred': []}])
        gb, _ = fleet_backend.apply_changes(gb, [c1, c2])
        conflicts = fb.fleet.conflicts_all()[gb['state']._impl.slot]
        assert set(conflicts) == {'x'}
        assert sorted(conflicts['x'].values()) == [1, 2]
        assert fleet_backend.materialize_docs([gb]) == [{'x': 2}]

    def test_counter_exact_on_device(self):
        fb = self._fb()
        gb = fb.init()
        cs = []
        heads = []
        specs = [
            {'action': 'set', 'obj': '_root', 'key': 'c', 'value': 10,
             'datatype': 'counter', 'pred': []},
            {'action': 'inc', 'obj': '_root', 'key': 'c', 'value': 3,
             'pred': [f'1@{ACTORS[0]}']},
            {'action': 'set', 'obj': '_root', 'key': 'c', 'value': 100,
             'datatype': 'int', 'pred': [f'1@{ACTORS[0]}']},
        ]
        for i, op in enumerate(specs):
            buf = change_buf(ACTORS[0], i + 1, i + 1, [op], deps=heads)
            heads = [am.decode_change(buf)['hash']]
            cs.append(buf)
        gb, _ = fleet_backend.apply_changes(gb, cs[:2])
        assert fleet_backend.materialize_docs([gb]) == [{'c': 13}]
        gb, _ = fleet_backend.apply_changes(gb, [cs[2]])
        assert fleet_backend.materialize_docs([gb]) == [{'c': 100}]

    def test_turbo_exact_device_string_values(self):
        """Turbo on int workloads, Python-ingest flush on string values —
        both land in the same register state."""
        fb = self._fb()
        handles = fleet_backend.init_docs(2, fb.fleet)
        ints = [[change_buf(ACTORS[0], 1, 1, [
            {'action': 'set', 'obj': '_root', 'key': 'n', 'value': d + 1,
             'datatype': 'int', 'pred': []}])] for d in range(2)]
        handles, patches = fleet_backend.apply_changes_docs(handles, ints,
                                                           mirror=False)
        assert all(p is None for p in patches)
        strs = [[change_buf(ACTORS[1], 1, 5, [
            {'action': 'set', 'obj': '_root', 'key': 's', 'value': f'doc{d}',
             'pred': []}])] for d in range(2)]
        handles, _ = fleet_backend.apply_changes_docs(handles, strs)
        assert fleet_backend.materialize_docs(handles) == \
            [{'n': 1, 's': 'doc0'}, {'n': 2, 's': 'doc1'}]

    def test_actor_renumber_in_register_mode(self):
        fb = self._fb()
        gb = fb.init()
        c1 = change_buf(ACTORS[1], 1, 1, [
            {'action': 'set', 'obj': '_root', 'key': 'x', 'value': 1,
             'datatype': 'int', 'pred': []}])
        gb, _ = fleet_backend.apply_changes(gb, [c1])
        fb.fleet.flush()
        c2 = change_buf(ACTORS[0], 1, 1, [
            {'action': 'set', 'obj': '_root', 'key': 'x', 'value': 2,
             'datatype': 'int', 'pred': []}])
        gb, _ = fleet_backend.apply_changes(gb, [c2])
        assert fleet_backend.materialize_docs([gb]) == [{'x': 1}]

    def test_randomized_exact_device_differential(self):
        rng = np.random.default_rng(23)
        fb = self._fb()
        hb = host_backend.init()
        gb = fb.init()
        vis = {}
        heads = []
        seqs = {a: 0 for a in ACTORS[:2]}
        ctr = 0
        for step in range(25):
            actor = ACTORS[int(rng.integers(0, 2))]
            key = f'k{int(rng.integers(0, 4))}'
            ctr += 1
            seqs[actor] += 1
            cur = sorted(vis.get(key, set()))
            if rng.random() < 0.25 and cur:
                op = {'action': 'del', 'obj': '_root', 'key': key,
                      'pred': cur}
                vis[key] = set()
            else:
                op = {'action': 'set', 'obj': '_root', 'key': key,
                      'value': int(rng.integers(0, 100)), 'datatype': 'int',
                      'pred': cur}
                vis[key] = {f'{ctr}@{actor}'}
            buf = change_buf(actor, seqs[actor], ctr, [op], deps=heads)
            heads = [am.decode_change(buf)['hash']]
            hb, hp = host_backend.apply_changes(hb, [buf])
            gb, gp = fleet_backend.apply_changes(gb, [buf])
            assert hp == gp
        assert fleet_backend.materialize_docs([gb]) == \
            [gb['state'].materialize()]
        assert host_backend.get_patch(hb) == fleet_backend.get_patch(gb)

    def test_negative_one_inc_delta(self):
        """inc by -1 must not be mistaken for the DEL value sentinel
        (regression)."""
        fb = self._fb()
        gb = fb.init()
        c1 = change_buf(ACTORS[0], 1, 1, [
            {'action': 'set', 'obj': '_root', 'key': 'c', 'value': 10,
             'datatype': 'counter', 'pred': []}])
        gb, _ = fleet_backend.apply_changes(gb, [c1])
        c2 = change_buf(ACTORS[0], 2, 2, [
            {'action': 'inc', 'obj': '_root', 'key': 'c', 'value': -1,
             'pred': [f'1@{ACTORS[0]}']}], deps=fleet_backend.get_heads(gb))
        gb, _ = fleet_backend.apply_changes(gb, [c2])
        assert fleet_backend.materialize_docs([gb]) == [{'c': 9}]

    def test_renumber_beyond_slot_capacity_grows_first(self):
        """Inserting an actor that pushes an existing actor's slot past the
        current width must grow the axis, not drop registers (regression)."""
        fb = FleetBackend(DocFleet(doc_capacity=2, key_capacity=2,
                                   exact_device=True, actor_slot_capacity=1))
        gb = fb.init()
        c1 = change_buf(ACTORS[2], 1, 1, [        # 'cc…' gets slot 0
            {'action': 'set', 'obj': '_root', 'key': 'x', 'value': 9,
             'datatype': 'int', 'pred': []}])
        gb, _ = fleet_backend.apply_changes(gb, [c1])
        fb.fleet.flush()
        c2 = change_buf(ACTORS[0], 1, 1, [        # 'aa…' sorts first
            {'action': 'set', 'obj': '_root', 'key': 'y', 'value': 1,
             'datatype': 'int', 'pred': []}])
        gb, _ = fleet_backend.apply_changes(gb, [c2])
        assert fleet_backend.materialize_docs([gb]) == [{'x': 9, 'y': 1}]

    def test_turbo_after_lazy_exact_preserves_order(self):
        """A turbo call must land lazily-pending earlier changes first: a
        delete arriving via turbo after a pending set must win (regression:
        the flush ran after the register dispatch, resurrecting the key)."""
        fb = self._fb()
        gb = fb.init()
        c1 = change_buf(ACTORS[0], 1, 1, [
            {'action': 'set', 'obj': '_root', 'key': 'k', 'value': 1,
             'datatype': 'int', 'pred': []}])
        gb, _ = fleet_backend.apply_changes(gb, [c1])   # pending, no flush
        c2 = change_buf(ACTORS[0], 2, 2, [
            {'action': 'del', 'obj': '_root', 'key': 'k',
             'pred': [f'1@{ACTORS[0]}']}], deps=fleet_backend.get_heads(gb))
        handles, _ = fleet_backend.apply_changes_docs([gb], [[c2]],
                                                      mirror=False)
        assert fleet_backend.materialize_docs(handles) == [{}]


class TestAdviceRegressions:
    """Round-1 advisor findings (fixed in PR 1): turbo multi-chunk buffers,
    unknown pred actors, null-value register materialization."""

    def test_turbo_multichunk_buffer_not_dropped(self):
        """A buffer holding two concatenated change chunks must apply BOTH
        chunks (turbo's native parser reads one chunk per buffer, so such
        buffers must fall back to the exact path)."""
        from automerge_tpu.columnar import decode_change
        fb = FleetBackend(DocFleet(doc_capacity=2, key_capacity=4))
        gb = fb.init()
        c1 = change_buf(ACTORS[0], 1, 1, [
            {'action': 'set', 'obj': '_root', 'key': 'x', 'value': 1,
             'datatype': 'int', 'pred': []}])
        h1 = decode_change(c1)['hash']
        c2 = change_buf(ACTORS[0], 2, 2, [
            {'action': 'set', 'obj': '_root', 'key': 'y', 'value': 2,
             'datatype': 'int', 'pred': []}], deps=[h1])
        h2 = decode_change(c2)['hash']
        handles, _ = fleet_backend.apply_changes_docs(
            [gb], [[bytes(c1) + bytes(c2)]], mirror=False)
        assert fleet_backend.materialize_docs(handles) == [{'x': 1, 'y': 2}]
        assert handles[0]['heads'] == [h2]
        # save() must agree with heads/clock (the old bug diverged them)
        reloaded = fb.load(fleet_backend.save(handles[0]))
        assert fleet_backend.get_heads(reloaded) == [h2]

    def test_turbo_unknown_pred_actor_raises(self):
        """A pred naming an actor the fleet never registered is a
        dangling pred: turbo now rejects it at apply time with the exact
        path's error (round 5 — it used to defer to the next mirror
        rebuild via an inexact flag), and actor 0's register survives
        untouched via rollback."""
        fb = FleetBackend(DocFleet(doc_capacity=2, key_capacity=4,
                                   exact_device=True))
        gb = fb.init()
        c1 = change_buf(ACTORS[0], 1, 1, [          # 'aa…' -> actor 0
            {'action': 'set', 'obj': '_root', 'key': 'x', 'value': 7,
             'datatype': 'int', 'pred': []}])
        handles, _ = fleet_backend.apply_changes_docs([gb], [[c1]],
                                                      mirror=False)
        # actor 'cc…' never authored a change with this fleet: '1@cc…'
        # dangles, exactly like the exact path's reject
        c2 = change_buf(ACTORS[1], 1, 1, [
            {'action': 'del', 'obj': '_root', 'key': 'x',
             'pred': [f'1@{ACTORS[2]}']}],
            deps=handles[0]['heads'])
        with pytest.raises(ValueError,
                           match='no matching operation for pred'):
            fleet_backend.apply_changes_docs(handles, [[c2]], mirror=False)
        fleet = fb.fleet
        fleet.flush()
        slot = handles[0]['state']._impl.slot
        # actor 0's register for key 'x' must NOT have been killed
        kx = fleet.keys.index['x']
        a0 = fleet.actors.index[ACTORS[0]]
        assert not bool(np.asarray(fleet.reg_state.killed)[slot, kx, a0])
        assert fleet_backend.materialize_docs(handles) == [{'x': 7}]

    def test_null_value_survives_register_materialize(self):
        """A key legitimately set to null must appear (as None) in
        exact-device bulk materialization, matching the host mirror."""
        fb = FleetBackend(DocFleet(doc_capacity=2, key_capacity=4,
                                   exact_device=True))
        gb = fb.init()
        c1 = change_buf(ACTORS[0], 1, 1, [
            {'action': 'set', 'obj': '_root', 'key': 'k', 'value': None,
             'pred': []},
            {'action': 'set', 'obj': '_root', 'key': 'm', 'value': 3,
             'datatype': 'int', 'pred': []}])
        gb, _ = fleet_backend.apply_changes(gb, [c1])
        assert fleet_backend.materialize_docs([gb]) == [{'k': None, 'm': 3}]
        # and it matches the host mirror's view
        assert gb['state'].materialize() == {'k': None, 'm': 3}

    def test_cap_docs_stable_on_non_pow2_mesh_capacity(self):
        """Round-4 advisor finding: on a non-pow2 docs axis the stored
        doc_cap is mesh-rounded (e.g. 66 on 6 devices); _cap_docs must
        return it unchanged when sufficient instead of re-deriving
        pow2(66)=128 -> 132 and regrowing state ~2x on every flush."""
        import jax
        from jax.sharding import Mesh
        mesh = Mesh(np.array(jax.devices()[:6]), ('docs',))
        fleet = DocFleet(doc_capacity=4, key_capacity=4, mesh=mesh)
        fleet.doc_cap = 66  # a previously mesh-rounded capacity
        assert fleet._cap_docs(10) == 66
        assert fleet._cap_docs(66) == 66
        # growth past capacity still pow2-then-rounds
        assert fleet._cap_docs(67) == 132
        # and an actual growth sequence reaches a fixed point: growing to
        # the value just returned must be a no-op
        cap = fleet._cap_docs(67)
        assert fleet._cap_docs(cap) >= cap
        fleet.doc_cap = cap
        assert fleet._cap_docs(cap) == cap


class TestSequenceTermination:
    def test_cyclic_nxt_chain_terminates(self):
        """A corrupted cyclic nxt chain whose nodes all compare greater than
        the inserted key must terminate via the hop-counter backstop instead
        of hanging the device kernel."""
        from automerge_tpu.fleet import sequence as seq
        state = seq.SeqState.empty(1, 4)
        # Two real slots pointing at each other, both with huge elem_ids
        state.nxt[0, seq.HEAD] = seq.SLOT0
        state.nxt[0, seq.SLOT0] = seq.SLOT0 + 1
        state.nxt[0, seq.SLOT0 + 1] = seq.SLOT0       # cycle
        state.elem_id[0, seq.SLOT0] = 2**30
        state.elem_id[0, seq.SLOT0 + 1] = 2**30 + 1
        state.n[0] = 2
        batch = seq.SeqOpBatch(
            np.array([[seq.INSERT]], dtype=np.int32),
            np.array([[seq.HEAD_REF]], dtype=np.int32),
            np.array([[1 << 8]], dtype=np.int32),   # packed opId 1@actor0
            np.array([[65]], dtype=np.int32))
        out, _ = seq.apply_seq_batch(state, batch)   # must not hang
        assert out.n.shape == (1,)


class TestSequenceSeam:
    """Text/list documents through the Backend seam: fleet-resident device
    state (SeqState rows), zero promotions for plain sequence docs, host
    mirror fallback only for shapes outside device LWW semantics.
    Ref: backend/new.js:50-192 (the reference's list-insertion hot path)."""

    def _fb(self):
        return FleetBackend(DocFleet(doc_capacity=4, key_capacity=8))

    def test_text_doc_stays_fleet_resident(self):
        fb = self._fb()
        hb, gb = host_backend.init(), fb.init()
        A = ACTORS[0]
        c1 = change_buf(A, 1, 1, [
            {'action': 'makeText', 'obj': '_root', 'key': 't', 'pred': []},
            {'action': 'set', 'obj': f'1@{A}', 'elemId': '_head',
             'insert': True, 'value': 'h', 'pred': []},
            {'action': 'set', 'obj': f'1@{A}', 'elemId': f'2@{A}',
             'insert': True, 'value': 'i', 'pred': []}])
        hb, gb = apply_both(hb, gb, [c1])
        c2 = change_buf(A, 2, 4, [
            {'action': 'del', 'obj': f'1@{A}', 'elemId': f'2@{A}',
             'pred': [f'2@{A}']}], deps=host_backend.get_heads(hb))
        hb, gb = apply_both(hb, gb, [c2])
        assert gb['state'].is_fleet
        assert fb.fleet.metrics.promotions == 0
        assert fleet_backend.materialize_docs([gb]) == [{'t': 'i'}]
        # device row stayed exact: the render above came from the device
        fb.fleet.flush()
        assert not fb.fleet.seq_row_inexact(0)
        # patches match host throughout (apply_both asserted) and so does
        # the serialized document
        assert bytes(fleet_backend.save(gb)) == bytes(host_backend.save(hb))

    def test_list_values_device_render(self):
        fb = self._fb()
        gb = fb.init()
        A = ACTORS[0]
        c1 = change_buf(A, 1, 1, [
            {'action': 'makeList', 'obj': '_root', 'key': 'l', 'pred': []},
            {'action': 'set', 'obj': f'1@{A}', 'elemId': '_head',
             'insert': True, 'value': 7, 'datatype': 'int', 'pred': []},
            {'action': 'set', 'obj': f'1@{A}', 'elemId': f'2@{A}',
             'insert': True, 'value': 'str', 'pred': []},
            {'action': 'set', 'obj': f'1@{A}', 'elemId': f'3@{A}',
             'insert': True, 'value': -5, 'datatype': 'int', 'pred': []}])
        gb, _ = fleet_backend.apply_changes(gb, [c1])
        assert fleet_backend.materialize_docs([gb]) == [{'l': [7, 'str', -5]}]
        fb.fleet.flush()
        assert not fb.fleet.seq_row_inexact(0)

    def test_rga_concurrent_insert_order_matches_host(self):
        """Two actors inserting at the same position: device RGA order must
        equal the host engine's (ref new.js:145-163)."""
        from automerge_tpu.columnar import decode_change
        fb = self._fb()
        hb, gb = host_backend.init(), fb.init()
        A, B = ACTORS[0], ACTORS[1]
        c1 = change_buf(A, 1, 1, [
            {'action': 'makeText', 'obj': '_root', 'key': 't', 'pred': []},
            {'action': 'set', 'obj': f'1@{A}', 'elemId': '_head',
             'insert': True, 'value': 'm', 'pred': []}])
        h1 = decode_change(c1)['hash']
        hb, gb = apply_both(hb, gb, [c1])
        c2 = change_buf(A, 2, 3, [
            {'action': 'set', 'obj': f'1@{A}', 'elemId': '_head',
             'insert': True, 'value': 'a', 'pred': []}], deps=[h1])
        c3 = change_buf(B, 1, 3, [
            {'action': 'set', 'obj': f'1@{A}', 'elemId': '_head',
             'insert': True, 'value': 'b', 'pred': []}], deps=[h1])
        hb, gb = apply_both(hb, gb, [c2, c3])
        expect = host_backend.get_patch(hb)
        got = fleet_backend.get_patch(gb)
        assert expect == got
        # device render agrees with the host's element order
        mat = fleet_backend.materialize_docs([gb])[0]['t']
        fb.fleet.flush()
        assert not fb.fleet.seq_row_inexact(0)
        assert mat == 'bam'   # higher actor's concurrent insert first

    def test_concurrent_set_vs_del_stays_exact_on_device(self):
        """Delete concurrent with a set: the reference keeps the element
        visible (the del only kills its preds, ref new.js:1204-1217). The
        actor-slotted element registers resolve this exactly on device —
        the row must NOT flag inexact."""
        from automerge_tpu.columnar import decode_change
        fb = self._fb()
        gb = fb.init()
        A, B = ACTORS[0], ACTORS[1]
        c1 = change_buf(A, 1, 1, [
            {'action': 'makeList', 'obj': '_root', 'key': 'l', 'pred': []},
            {'action': 'set', 'obj': f'1@{A}', 'elemId': '_head',
             'insert': True, 'value': 1, 'datatype': 'int', 'pred': []}])
        h1 = decode_change(c1)['hash']
        gb, _ = fleet_backend.apply_changes(gb, [c1])
        c2 = change_buf(A, 2, 3, [
            {'action': 'set', 'obj': f'1@{A}', 'elemId': f'2@{A}',
             'value': 9, 'datatype': 'int', 'pred': [f'2@{A}']}], deps=[h1])
        c3 = change_buf(B, 1, 3, [
            {'action': 'del', 'obj': f'1@{A}', 'elemId': f'2@{A}',
             'pred': [f'2@{A}']}], deps=[h1])
        gb, _ = fleet_backend.apply_changes(gb, [c2, c3])
        # reference semantics: the concurrent set survives the delete
        assert fleet_backend.materialize_docs([gb]) == [{'l': [9]}]
        fb.fleet.flush()
        assert not fb.fleet.seq_row_inexact(0)

    def test_counter_in_list_exact_on_device(self):
        """Counters inside sequences accumulate exactly in per-lane
        counter registers (round 4) — no inexact fallback; device reads
        fold the winning lane's deltas onto the boxed counter base."""
        fb = self._fb()
        gb = fb.init()
        A = ACTORS[0]
        c1 = change_buf(A, 1, 1, [
            {'action': 'makeList', 'obj': '_root', 'key': 'l', 'pred': []},
            {'action': 'set', 'obj': f'1@{A}', 'elemId': '_head',
             'insert': True, 'value': 10, 'datatype': 'counter', 'pred': []}])
        gb, _ = fleet_backend.apply_changes(gb, [c1])
        c2 = change_buf(A, 2, 3, [
            {'action': 'inc', 'obj': f'1@{A}', 'elemId': f'2@{A}',
             'value': 5, 'pred': [f'2@{A}']}],
            deps=fleet_backend.get_heads(gb))
        gb, _ = fleet_backend.apply_changes(gb, [c2])
        assert fleet_backend.materialize_docs([gb]) == [{'l': [15]}]
        fb.fleet.flush()
        assert not fb.fleet.seq_row_inexact(0)

    def test_counter_in_list_patch_shapes_match_host(self):
        """Whole-doc patches for counters in lists replay the reference's
        counterStates edit shapes: insert for 0/1 consumed incs, the
        remove->update conversion for >= 2 — across per-doc, turbo, and
        bulk-load paths in both fleet modes."""
        import automerge_tpu as am
        from automerge_tpu.fleet.loader import load_docs
        A, B = ACTORS[0], ACTORS[1]
        for n_incs in (1, 2, 3):
            ops = [{'action': 'makeList', 'obj': '_root', 'key': 'l',
                    'pred': []},
                   {'action': 'set', 'obj': f'1@{A}', 'elemId': '_head',
                    'insert': True, 'value': 10, 'datatype': 'counter',
                    'pred': []}]
            for i in range(n_incs):
                ops.append({'action': 'inc', 'obj': f'1@{A}',
                            'elemId': f'2@{A}', 'value': i + 1,
                            'datatype': 'counter', 'pred': [f'2@{A}']})
            c1 = change_buf(A, 1, 1, ops)
            hb = host_backend.init()
            hb, _ = host_backend.apply_changes(hb, [c1])
            want = host_backend.get_patch(hb)
            saved = bytes(host_backend.save(hb))
            for exact in (False, True):
                for turbo in (False, True):
                    fleet = DocFleet(doc_capacity=2, key_capacity=8,
                                     exact_device=exact)
                    gb = fleet_backend.init(fleet)
                    if turbo:
                        [gb], _ = fleet_backend.apply_changes_docs(
                            [gb], [[c1]], mirror=False)
                    else:
                        gb, _ = fleet_backend.apply_changes(gb, [c1])
                    assert fleet_backend.get_patch(gb) == want, \
                        (n_incs, exact, turbo)
                    assert bytes(fleet_backend.save(gb)) == saved
                fresh = DocFleet(doc_capacity=2, key_capacity=8,
                                 exact_device=exact)
                hb2 = load_docs([saved], fresh)[0]
                assert fresh.metrics.docs_bulk_loaded == 1
                assert fleet_backend.get_patch(hb2) == want, \
                    ('bulk', n_incs, exact)

    def test_randomized_sequence_counter_differential(self):
        """Backend-level fuzz of counters inside lists with REAL
        concurrency: two replicas diverge (each creating counter elements
        and incrementing what they see) and periodically merge, so
        conflicted counter sets, cross-branch incs, and deletes all occur;
        every converged state is compared across host and both fleet modes
        (patches, reads, and save bytes)."""
        import automerge_tpu as am
        rng = np.random.default_rng(7)
        A, B = ACTORS[0], ACTORS[1]

        for trial in range(4):
            # Two host replicas drive op generation (their visible state
            # decides preds, like a real frontend would)
            reps = [host_backend.init(), host_backend.init()]
            boot = change_buf(A, 1, 1, [
                {'action': 'makeList', 'obj': '_root', 'key': 'l',
                 'pred': []}])
            for i in (0, 1):
                reps[i], _ = host_backend.apply_changes(reps[i], [boot])
            list_id = f'1@{A}'
            seqs = {A: 1, B: 0}

            def visible_elems(rep):
                """[(elemId, [set opIds], is_counter)] via the host patch."""
                diffs = host_backend.get_patch(rep)['diffs']
                lst = diffs['props'].get('l', {}).get(list_id)
                out = []
                if not lst:
                    return out
                idx = -1
                for edit in lst.get('edits', []):
                    if edit['action'] in ('insert', 'update'):
                        ops = [edit['opId']]
                        val = edit['value']
                        out.append((edit.get('elemId', ops[0]), ops,
                                    isinstance(val, dict) and
                                    val.get('datatype') == 'counter'))
                return out

            for step in range(int(rng.integers(12, 20))):
                r = int(rng.integers(0, 2))
                actor = (A, B)[r]
                rep = reps[r]
                elems = visible_elems(rep)
                roll = rng.random()
                counters = [e for e in elems if e[2]]
                if roll < 0.45 or not elems:
                    # insert a counter (or plain) element at random ref
                    ref = '_head' if not elems or rng.random() < 0.4 \
                        else elems[int(rng.integers(0, len(elems)))][0]
                    op = {'action': 'set', 'obj': list_id, 'elemId': ref,
                          'insert': True,
                          'value': int(rng.integers(0, 50)),
                          'pred': []}
                    if rng.random() < 0.7:
                        op['datatype'] = 'counter'
                    else:
                        op['datatype'] = 'int'
                elif roll < 0.8 and counters:
                    eid, preds, _ = counters[int(rng.integers(
                        0, len(counters)))]
                    op = {'action': 'inc', 'obj': list_id, 'elemId': eid,
                          'value': int(rng.integers(-3, 9)),
                          'datatype': 'counter', 'pred': preds}
                else:
                    eid, preds, _ = elems[int(rng.integers(0, len(elems)))]
                    op = {'action': 'del', 'obj': list_id, 'elemId': eid,
                          'pred': preds}
                seqs[actor] += 1
                # startOp = maxOp + 1 like the reference frontend: op
                # counters must exceed every causally-seen op's counter
                start = host_backend.get_patch(rep)['maxOp'] + 1
                buf = change_buf(actor, seqs[actor], start, [op],
                                 deps=host_backend.get_heads(rep))
                reps[r], _ = host_backend.apply_changes(reps[r], [buf])
                if rng.random() < 0.3:
                    # merge the other replica in (concurrency point):
                    # get_changes_added(a, b) = changes in b missing
                    # from a
                    other = reps[1 - r]
                    missing = host_backend.get_changes_added(reps[r], other)
                    if missing:
                        reps[r], _ = host_backend.apply_changes(
                            reps[r], [bytes(c) for c in missing])

            # converge both replicas, then differentially replay the full
            # history through both fleet modes
            for r in (0, 1):
                missing = host_backend.get_changes_added(reps[r],
                                                         reps[1 - r])
                if missing:
                    reps[r], _ = host_backend.apply_changes(
                        reps[r], [bytes(c) for c in missing])
            assert host_backend.get_heads(reps[0]) == \
                host_backend.get_heads(reps[1])
            history = [bytes(c) for c in
                       host_backend.get_all_changes(reps[0])]
            want = host_backend.get_patch(reps[0])
            saved = bytes(host_backend.save(reps[0]))
            for exact in (False, True):
                fleet = DocFleet(doc_capacity=2, key_capacity=8,
                                 exact_device=exact)
                gb = fleet_backend.init(fleet)
                gb, _ = fleet_backend.apply_changes(gb, history)
                assert fleet_backend.get_patch(gb) == want, (trial, exact)
                assert bytes(fleet_backend.save(gb)) == saved, \
                    (trial, exact)

    def test_clone_and_free_with_seq_rows(self):
        fb = self._fb()
        gb = fb.init()
        A = ACTORS[0]
        c1 = change_buf(A, 1, 1, [
            {'action': 'makeText', 'obj': '_root', 'key': 't', 'pred': []},
            {'action': 'set', 'obj': f'1@{A}', 'elemId': '_head',
             'insert': True, 'value': 'x', 'pred': []}])
        gb, _ = fleet_backend.apply_changes(gb, [c1])
        clone = fleet_backend.clone(gb)
        # divergent edits after cloning must not interfere
        c2 = change_buf(A, 2, 3, [
            {'action': 'set', 'obj': f'1@{A}', 'elemId': f'2@{A}',
             'insert': True, 'value': 'y', 'pred': []}],
            deps=fleet_backend.get_heads(gb))
        gb, _ = fleet_backend.apply_changes(gb, [c2])
        assert fleet_backend.materialize_docs([gb, clone]) == \
            [{'t': 'xy'}, {'t': 'x'}]
        fleet_backend.free(clone)
        assert fleet_backend.materialize_docs([gb]) == [{'t': 'xy'}]

    def test_actor_renumber_remaps_seq_rows(self):
        """A later actor that sorts before existing ones renumbers packed
        elemIds in device rows; RGA order must stay correct."""
        from automerge_tpu.columnar import decode_change
        fb = self._fb()
        gb = fb.init()
        A, early = ACTORS[2], ACTORS[3]     # 'cc…' then '11…' (sorts first)
        c1 = change_buf(A, 1, 1, [
            {'action': 'makeText', 'obj': '_root', 'key': 't', 'pred': []},
            {'action': 'set', 'obj': f'1@{A}', 'elemId': '_head',
             'insert': True, 'value': 'a', 'pred': []}])
        h1 = decode_change(c1)['hash']
        gb, _ = fleet_backend.apply_changes(gb, [c1])
        fb.fleet.flush()                     # device rows exist pre-renumber
        c2 = change_buf(early, 1, 3, [
            {'action': 'set', 'obj': f'1@{A}', 'elemId': f'2@{A}',
             'insert': True, 'value': 'b', 'pred': []}], deps=[h1])
        gb, _ = fleet_backend.apply_changes(gb, [c2])
        assert fleet_backend.materialize_docs([gb]) == [{'t': 'ab'}]
        fb.fleet.flush()
        assert not fb.fleet.seq_row_inexact(0)

    def test_public_api_text_promotionless(self):
        import automerge_tpu as am
        from automerge_tpu import Text
        import automerge_tpu.frontend as fe
        fb = self._fb()
        old = am.Backend()
        am.set_default_backend(fb)
        try:
            d = am.init(ACTORS[0])
            d = am.change(d, lambda doc: doc.__setitem__('t', Text('hello')))
            d = am.change(d, lambda doc: doc['t'].insert_at(5, '!', '?'))
            d = am.change(d, lambda doc: doc['t'].delete_at(0, 2))
            assert str(d['t']) == 'llo!?'
            handle = fe.get_backend_state(d)
            assert handle['state'].is_fleet
            assert fb.fleet.metrics.promotions == 0
            assert fleet_backend.materialize_docs([handle]) == \
                [{'t': 'llo!?'}]
            loaded = am.load(am.save(d))
            assert str(loaded['t']) == 'llo!?'
        finally:
            am.set_default_backend(old)

    def test_turbo_renumber_remaps_seq_rows(self):
        """Turbo applies that insert an early-sorting actor must remap the
        actor bits of live SeqState rows, exactly as flush() does
        (regression: the turbo site skipped _remap_seq_actors, leaving
        stale packed elemIds in every device text row)."""
        from automerge_tpu.columnar import decode_change
        fb = self._fb()
        g1, g2 = fb.init(), fb.init()
        A, early = ACTORS[2], ACTORS[3]     # 'cc…' index 0, then '11…'
        c1 = change_buf(A, 1, 1, [
            {'action': 'makeText', 'obj': '_root', 'key': 't', 'pred': []},
            {'action': 'set', 'obj': f'1@{A}', 'elemId': '_head',
             'insert': True, 'value': 'a', 'pred': []}])
        h1 = decode_change(c1)['hash']
        g1, _ = fleet_backend.apply_changes(g1, [c1])
        fb.fleet.flush()                     # text row live on device
        # flat turbo batch on another doc by an actor sorting before 'cc…'
        flat = change_buf(early, 1, 1, [
            {'action': 'set', 'obj': '_root', 'key': 'k', 'value': 1,
             'datatype': 'int', 'pred': []}])
        handles, _ = fleet_backend.apply_changes_docs([g2], [[flat]],
                                                      mirror=False)
        g2 = handles[0]
        # the text row's packed elemIds must reflect the new numbering:
        # further edits (packed with new actor numbers) must still hit
        c2 = change_buf(A, 2, 3, [
            {'action': 'del', 'obj': f'1@{A}', 'elemId': f'2@{A}',
             'pred': [f'2@{A}']}], deps=[h1])
        g1, _ = fleet_backend.apply_changes(g1, [c2])
        assert fleet_backend.materialize_docs([g1, g2]) == \
            [{'t': ''}, {'k': 1}]
        fb.fleet.flush()
        assert not fb.fleet.seq_row_inexact(0)


class TestTurboSequence:
    """mirror=False applies with sequence ops: op columns go wire -> native
    C++ parse -> SeqState dispatch with no per-op Python objects and no
    mirror work; reads come straight from the device."""

    def _fb(self):
        return FleetBackend(DocFleet(doc_capacity=4, key_capacity=8))

    def _text_changes(self):
        from automerge_tpu.columnar import decode_change
        A = ACTORS[0]
        c1 = change_buf(A, 1, 1, [
            {'action': 'makeText', 'obj': '_root', 'key': 't', 'pred': []},
            {'action': 'set', 'obj': f'1@{A}', 'elemId': '_head',
             'insert': True, 'value': 'a', 'pred': []},
            {'action': 'set', 'obj': f'1@{A}', 'elemId': f'2@{A}',
             'insert': True, 'value': 'b', 'pred': []},
            {'action': 'set', 'obj': f'1@{A}', 'elemId': f'3@{A}',
             'insert': True, 'value': 'c', 'pred': []}])
        h1 = decode_change(c1)['hash']
        c2 = change_buf(A, 2, 5, [
            {'action': 'del', 'obj': f'1@{A}', 'elemId': f'3@{A}',
             'pred': [f'3@{A}']}], deps=[h1])
        return c1, c2

    def test_turbo_text_no_mirror_no_fallback(self):
        fb = self._fb()
        g = fb.init()
        c1, c2 = self._text_changes()
        handles, _ = fleet_backend.apply_changes_docs([g], [[c1, c2]],
                                                      mirror=False)
        assert fb.fleet.metrics.fallbacks == 0
        assert fb.fleet.metrics.turbo_calls == 1
        assert fleet_backend.materialize_docs(handles) == [{'t': 'ac'}]
        # the device served the read: no lazy mirror rebuild happened
        assert fb.fleet.metrics.mirror_rebuilds == 0
        assert not fb.fleet.seq_row_inexact(0)

    def test_turbo_text_differential_vs_exact(self):
        """Turbo and exact paths produce identical patches and bytes."""
        fb, fb2 = self._fb(), self._fb()
        g, g2 = fb.init(), fb2.init()
        c1, c2 = self._text_changes()
        A = ACTORS[0]
        handles, _ = fleet_backend.apply_changes_docs([g], [[c1, c2]],
                                                      mirror=False)
        c3 = change_buf(A, 3, 6, [
            {'action': 'set', 'obj': f'1@{A}', 'elemId': f'4@{A}',
             'insert': True, 'values': ['€', 'x'], 'pred': []}],
            deps=fleet_backend.get_heads(handles[0]))
        handles, _ = fleet_backend.apply_changes_docs(handles, [[c3]],
                                                      mirror=False)
        assert fb.fleet.metrics.fallbacks == 0
        for c in (c1, c2, c3):
            g2, _ = fleet_backend.apply_changes(g2, [c])
        assert fleet_backend.materialize_docs(handles) == [{'t': 'ac€x'}]
        assert fleet_backend.get_patch(handles[0]) == \
            fleet_backend.get_patch(g2)
        assert bytes(fleet_backend.save(handles[0])) == \
            bytes(fleet_backend.save(g2))

    def test_turbo_seq_register_mode(self):
        """Turbo sequence dispatch under exact_device (register) mode."""
        fb = FleetBackend(DocFleet(doc_capacity=4, key_capacity=8,
                                   exact_device=True))
        g = fb.init()
        c1, c2 = self._text_changes()
        handles, _ = fleet_backend.apply_changes_docs([g], [[c1, c2]],
                                                      mirror=False)
        assert fb.fleet.metrics.fallbacks == 0
        assert fleet_backend.materialize_docs(handles) == [{'t': 'ac'}]

    def test_turbo_unknown_seq_object_falls_back(self):
        """Ops on an object the fleet has never seen route to the exact
        path (which raises the reference's error)."""
        A = ACTORS[0]
        fb = self._fb()
        g = fb.init()
        bogus = change_buf(A, 1, 1, [
            {'action': 'set', 'obj': f'9@{A}', 'elemId': '_head',
             'insert': True, 'value': 'x', 'pred': []}])
        with pytest.raises(ValueError, match='unknown object'):
            fleet_backend.apply_changes_docs([g], [[bogus]], mirror=False)


class TestTurboNestedMaps:
    """Nested map/table changes take the native turbo wire->device path
    (the parser emits keyed rows with their containing object; makes
    flag-code as 9/10) — no fallback to the Python decode."""

    @pytest.mark.parametrize('exact', [False, True])
    def test_nested_tree_through_turbo(self, exact):
        from automerge_tpu.columnar import encode_change, decode_change_meta
        A1 = ACTORS[0]
        fleet = DocFleet(doc_capacity=4, key_capacity=16,
                         exact_device=exact)
        fb = FleetBackend(fleet)
        handles = [fb.init() for _ in range(2)]
        per_doc = []
        for d in range(2):
            c1 = encode_change({
                'actor': A1, 'seq': 1, 'startOp': 1, 'time': 0,
                'message': '', 'deps': [], 'ops': [
                    {'action': 'makeMap', 'obj': '_root', 'key': 'cfg',
                     'pred': []},
                    {'action': 'set', 'obj': f'1@{A1}', 'key': 'x',
                     'value': 5 + d, 'datatype': 'int', 'pred': []},
                    {'action': 'makeTable', 'obj': '_root', 'key': 'tbl',
                     'pred': []}]})
            heads = [decode_change_meta(c1, True)['hash']]
            c2 = encode_change({
                'actor': A1, 'seq': 2, 'startOp': 4, 'time': 0,
                'message': '', 'deps': heads, 'ops': [
                    {'action': 'set', 'obj': f'1@{A1}', 'key': 'y',
                     'value': 7, 'datatype': 'int', 'pred': []},
                    {'action': 'del', 'obj': f'1@{A1}', 'key': 'x',
                     'pred': [f'2@{A1}']},
                    {'action': 'set', 'obj': '_root', 'key': 'top',
                     'value': 1, 'datatype': 'int', 'pred': []}]})
            per_doc.append([c1, c2])
        handles, _ = fleet_backend.apply_changes_docs(handles, per_doc,
                                                      mirror=False)
        assert fleet.metrics.turbo_calls == 1
        assert fleet.metrics.fallbacks == 0
        mats = fleet_backend.materialize_docs(handles)
        assert mats == [{'cfg': {'y': 7}, 'tbl': {}, 'top': 1}] * 2
        if exact:
            # nested patches still device-served after turbo
            patch = fleet_backend.get_patch(handles[0])
            cfg = patch['diffs']['props']['cfg'][f'1@{A1}']
            assert cfg['props']['y'] == {
                f'4@{A1}': {'type': 'value', 'value': 7,
                            'datatype': 'int'}}
            assert fleet.metrics.mirror_rebuilds == 0

    @pytest.mark.parametrize('exact', [False, True])
    def test_boxed_values_ride_turbo(self, exact):
        """Strings, bools, None, floats, negative ints, and nested trees
        built with the real frontend all take the turbo wire->device path
        (the native parser boxes non-inline payloads via its value arena)
        with reads and patches identical to the host."""
        import automerge_tpu as A
        fleet = DocFleet(doc_capacity=8, key_capacity=64,
                         exact_device=exact)
        src = []
        for i in range(3):
            d = A.from_({'cfg': {'name': f'doc{i}', 'opts': {'d': 2}},
                         'tbl': A.Table(), 'n': i, 'f': 2.5, 'ok': True,
                         'nil': None, 'neg': -7}, ACTORS[0])
            d = A.change(d, lambda r: (
                r['cfg'].__setitem__('rev', 3),
                r['tbl'].add({'row': 'textual'})))
            d = A.change(d, lambda r: r['cfg']['opts'].__setitem__(
                'extra', 'yes!'))
            src.append(d)
        per_doc = [[bytes(c) for c in A.get_all_changes(d)] for d in src]
        fb = FleetBackend(fleet)
        handles = [fb.init() for _ in src]
        handles, _ = fleet_backend.apply_changes_docs(handles, per_doc,
                                                      mirror=False)
        assert fleet.metrics.turbo_calls == 1
        assert fleet.metrics.fallbacks == 0
        mats = fleet_backend.materialize_docs(handles)
        assert mats[0]['cfg'] == {'name': 'doc0',
                                  'opts': {'d': 2, 'extra': 'yes!'},
                                  'rev': 3}
        assert mats[1]['f'] == 2.5 and mats[1]['nil'] is None
        assert mats[2]['neg'] == -7 and mats[2]['ok'] is True
        expected = [host_backend.get_patch(host_backend.load(A.save(d)))
                    for d in src]
        got = [fleet_backend.get_patch(h) for h in handles]
        assert got == expected
        if exact:
            assert fleet.metrics.mirror_rebuilds == 0

    def test_undecodable_boxed_payload_falls_back_cleanly(self):
        """A crafted wire change whose boxed payload decode_value rejects
        (uint64 past the 2^53 read limit — constructible only by a foreign
        or malicious peer, our encoder caps at 53 bits) must route to the
        exact path BEFORE the turbo commit point: the doc stays untouched
        instead of heads/logs advancing around a raised decode."""
        from automerge_tpu.columnar import encode_container, \
            CHUNK_TYPE_CHANGE
        from automerge_tpu.encoding import Encoder, RLEEncoder
        A1 = ACTORS[0]

        def uleb(v):
            out = bytearray()
            while True:
                b = v & 0x7f
                v >>= 7
                out.append(b | (0x80 if v else 0))
                if not v:
                    return bytes(out)

        raw = uleb(2 ** 60)                 # 9-byte LEB128 uint
        ks = RLEEncoder('utf8')
        ks.append_value('x')
        ks.finish()
        act = RLEEncoder('uint')
        act.append_value(1)                 # set
        act.finish()
        vlen = RLEEncoder('uint')
        vlen.append_value((len(raw) << 4) | 3)   # LEB128_UINT tag
        vlen.finish()
        pn = RLEEncoder('uint')
        pn.append_value(0)
        pn.finish()
        cols = [(0x15, ks.buffer), (0x42, act.buffer),
                (0x56, vlen.buffer), (0x57, raw), (0x70, pn.buffer)]
        body = Encoder()
        body.append_uint53(0)               # deps
        body.append_hex_string(A1)
        body.append_uint53(1)               # seq
        body.append_uint53(1)               # startOp
        body.append_int53(0)                # time
        body.append_prefixed_string('')     # message
        body.append_uint53(0)               # other actors
        body.append_uint53(len(cols))
        for cid, buf in cols:
            body.append_uint53(cid)
            body.append_uint53(len(buf))
        for _cid, buf in cols:
            body.append_raw_bytes(buf)
        _h, big = encode_container(CHUNK_TYPE_CHANGE, body.buffer)

        fleet = DocFleet(doc_capacity=2, key_capacity=8)
        fb = FleetBackend(fleet)
        handle = fb.init()
        with pytest.raises(ValueError):
            fleet_backend.apply_changes_docs([handle], [[big]],
                                             mirror=False)
        # the turbo guard bailed pre-commit; the exact path raised with
        # the doc untouched
        assert fleet.metrics.turbo_calls == 0
        assert handle['state'].heads == []
        assert len(handle['state'].changes) == 0

    def test_dangling_nested_object_falls_back(self):
        """A keyed op on an unknown map object routes to the exact path
        (which raises the reference's error) instead of corrupting."""
        from automerge_tpu.columnar import encode_change
        A1 = ACTORS[0]
        fleet = DocFleet(doc_capacity=2, key_capacity=8)
        fb = FleetBackend(fleet)
        handle = fb.init()
        bad = encode_change({
            'actor': A1, 'seq': 1, 'startOp': 1, 'time': 0, 'message': '',
            'deps': [], 'ops': [
                {'action': 'set', 'obj': f'99@{A1}', 'key': 'x',
                 'value': 1, 'datatype': 'int', 'pred': []}]})
        with pytest.raises(Exception):
            fleet_backend.apply_changes_docs([handle], [[bad]],
                                             mirror=False)


class TestSeqSizeClasses:
    """Sequence rows live in pow2 size-class pools (fleet/sequence.py
    SeqPools): memory follows each document's own length, and a long
    document no longer pads the whole fleet's sequence arrays."""

    def _text_doc(self, fb, actor, text):
        import automerge_tpu as A
        from automerge_tpu import backend as _hb
        d = A.from_({'t': A.Text(text)}, actor)
        gb = fb.init()
        gb, _ = fleet_backend.apply_changes(
            gb, [bytes(c) for c in A.get_all_changes(d)])
        return gb

    def test_long_doc_does_not_inflate_small_class(self):
        fleet = DocFleet(doc_capacity=4, key_capacity=8)
        fb = FleetBackend(fleet)
        short = self._text_doc(fb, ACTORS[0], 'hi')
        long = self._text_doc(fb, ACTORS[1], 'x' * 300)
        fleet.flush()
        assert fleet_backend.materialize_docs([short, long]) == \
            [{'t': 'hi'}, {'t': 'x' * 300}]
        pools = fleet.seq_pools
        classes = sorted(pools.pools)
        assert len(classes) >= 2
        # the small class stays at base capacity: the 300-element doc
        # lives in its own class instead of padding everyone
        assert pools.state(classes[0]).capacity == fleet.seq_elem_cap
        assert pools.state(classes[-1]).capacity >= 300
        short_place = fleet.seq_place[fleet.slot_seq[
            short['state']._impl.slot].popitem()[1]]
        assert short_place[0] == classes[0]

    def test_row_migrates_up_classes_preserving_content(self):
        import automerge_tpu as A
        fleet = DocFleet(doc_capacity=2, key_capacity=8)
        A.set_default_backend(FleetBackend(fleet))
        try:
            d = A.from_({'t': A.Text('ab')}, ACTORS[0])
            fleet.flush()
            row = next(iter(fleet.slot_seq[list(fleet.slot_seq)[0]].values()))
            first_place = fleet.seq_place[row]
            for chunk in range(6):
                d = A.change(d, lambda r: r['t'].insert_at(
                    len(r['t']), *('y' * 40)))
            fleet.flush()
            assert str(d['t']) == 'ab' + 'y' * 240
            second_place = fleet.seq_place[row]
            assert second_place[0] > first_place[0]   # moved up a class
            # the vacated idx is reusable
            assert first_place[1] in fleet.seq_pools.free.get(
                first_place[0], [])
        finally:
            A.set_default_backend(host_backend)

    def test_tail_sorted_new_actor_widens_lanes(self):
        """A 5th actor whose hex id sorts AFTER all existing actors causes
        no remap (identity perm); the pools must still widen their lane
        axis before its ops apply, or the row would flag inexact and lose
        the device path forever."""
        import automerge_tpu as A
        fleet = DocFleet(doc_capacity=8, key_capacity=8)
        A.set_default_backend(FleetBackend(fleet))
        try:
            first = ['01' * 8, '22' * 8, '44' * 8, '66' * 8]
            base = A.from_({'t': A.Text('abcd')}, first[0])
            replicas = [base] + [A.merge(A.init(a), base) for a in first[1:]]
            for i, rep in enumerate(replicas[1:], start=1):
                replicas[i] = A.change(rep, lambda r, i=i: r['t'].set(i, '!'))
            merged = replicas[0]
            for rep in replicas[1:]:
                merged = A.merge(merged, rep)
            fleet.flush()
            assert len(fleet.actors) == 4
            # 5th actor sorts after every existing one -> identity perm
            late = A.merge(A.init('ff' * 8), merged)
            late = A.change(late, lambda r: r['t'].insert_at(0, 'Z'))
            fleet.flush()
            for row, info in enumerate(fleet.seq_rows):
                if info is not None:
                    assert not fleet.seq_row_inexact(row)
            assert str(late['t']) == 'Za!!!'
        finally:
            A.set_default_backend(host_backend)

    def test_free_slot_releases_pool_rows(self):
        fleet = DocFleet(doc_capacity=2, key_capacity=8)
        fb = FleetBackend(fleet)
        gb = self._text_doc(fb, ACTORS[0], 'abc')
        fleet.flush()
        slot = gb['state']._impl.slot
        row = next(iter(fleet.slot_seq[slot].values()))
        place = fleet.seq_place[row]
        assert place is not None
        fleet_backend.free(gb)
        assert place[1] in fleet.seq_pools.free.get(place[0], [])
        # the freed idx is handed to the next allocation in that class
        gb2 = self._text_doc(fb, ACTORS[0], 'def')
        fleet.flush()
        row2 = next(iter(fleet.slot_seq[gb2['state']._impl.slot].values()))
        assert fleet.seq_place[row2] == place
        assert fleet_backend.materialize_docs([gb2]) == [{'t': 'def'}]


class TestValueTableDedup:
    def test_boxed_values_dedup_by_value(self):
        """Repeated boxed values (strings across a long change log) intern
        once: the value table grows with distinct values, not op count
        (round-2 VERDICT weak item 7 — long-run fleet memory leak)."""
        fb = FleetBackend(DocFleet(doc_capacity=2, key_capacity=4))
        gb = fb.init()
        heads = []
        for seq in range(1, 21):
            buf = change_buf(ACTORS[0], seq, seq, [
                {'action': 'set', 'obj': '_root', 'key': 'status',
                 'value': 'active' if seq % 2 else 'idle',
                 'pred': [f'{seq - 1}@{ACTORS[0]}'] if seq > 1 else []}],
                deps=heads)
            heads = [am.decode_change(buf)['hash']]
            gb, _ = fleet_backend.apply_changes(gb, [buf])
        fleet = gb['state'].fleet
        fleet.flush()
        boxed = [v for v in fleet.value_table if isinstance(v, str)]
        assert sorted(set(boxed)) == ['active', 'idle']
        assert len(boxed) == 2


class TestCounterRebasing:
    """Packed-opId headroom (round-2 VERDICT item 9): op counters past the
    int32 packing window (CTR_LIMIT = 2^23) rebase the slot's window on
    device instead of crashing or promoting — history length is unbounded;
    only the LIVE counter spread is window-bounded."""

    def _chain(self, start_ops, key_of=None):
        """Chained single-op changes at the given startOps."""
        A = ACTORS[0]
        changes, heads = [], []
        for seq, start in enumerate(start_ops, 1):
            buf = change_buf(A, seq, start, [
                {'action': 'set', 'obj': '_root',
                 'key': key_of(seq) if key_of else 'k',
                 'value': seq, 'datatype': 'int',
                 'pred': []}], deps=heads)
            heads = [am.decode_change(buf)['hash']]
            changes.append(buf)
        return changes

    def test_counters_past_the_window_stay_fleet_resident(self):
        # A long-lived doc whose winners keep advancing (the editing-trace
        # regime): each overwrite moves the live window forward, so rebasing
        # keeps the doc on the grid across multiple windows of history
        from automerge_tpu.fleet.tensor_doc import CTR_LIMIT
        fb = FleetBackend(DocFleet(doc_capacity=2, key_capacity=4))
        gb = fb.init()
        step = CTR_LIMIT - 100
        starts = [1, step, 2 * step, 3 * step, 4 * step]   # ~4 windows deep
        fleet = gb['state'].fleet
        A = ACTORS[0]
        heads, pred = [], []
        for seq, start in enumerate(starts, 1):
            buf = change_buf(A, seq, start, [
                {'action': 'set', 'obj': '_root', 'key': 'k',
                 'value': seq, 'datatype': 'int', 'pred': pred}],
                deps=heads)
            heads = [am.decode_change(buf)['hash']]
            pred = [f'{start}@{A}']
            gb, _ = fleet_backend.apply_changes(gb, [buf])
            fleet.flush()      # incremental flushes: live window advances
        assert gb['state'].is_fleet
        assert fleet.metrics.promotions == 0
        from automerge_tpu.fleet.backend import materialize_docs
        assert materialize_docs([gb]) == [{'k': len(starts)}]
        # The grid itself served the read (no overflow fallback): the live
        # winner advanced with each overwrite, so every rebase succeeded
        assert gb['state']._impl.slot not in fleet.grid_overflow
        assert fleet.ctr_base[gb['state']._impl.slot] > 0

    def test_irreducible_spread_falls_back_to_mirror(self):
        # A key set once at counter 1 and never touched again, then an op
        # past 2*CTR_LIMIT: the live spread cannot fit one window; reads
        # stay correct via the host mirror, still without promotion
        from automerge_tpu.fleet.tensor_doc import CTR_LIMIT
        fb = FleetBackend(DocFleet(doc_capacity=2, key_capacity=4))
        gb = fb.init()
        starts = [1, 2 * CTR_LIMIT + 3]
        for buf in self._chain(starts, key_of=lambda s: f'k{s}'):
            gb, _ = fleet_backend.apply_changes(gb, [buf])
        fleet = gb['state'].fleet
        fleet.flush()
        assert gb['state'].is_fleet
        assert fleet.metrics.promotions == 0
        from automerge_tpu.fleet.backend import materialize_docs
        assert materialize_docs([gb]) == [{'k1': 1, 'k2': 2}]
        assert gb['state']._impl.slot in fleet.grid_overflow

    def test_exact_device_promotes_cleanly_at_the_boundary(self):
        from automerge_tpu.fleet.tensor_doc import CTR_LIMIT
        fb = FleetBackend(DocFleet(doc_capacity=2, key_capacity=4,
                                   exact_device=True))
        gb = fb.init()
        for buf in self._chain([1, CTR_LIMIT + 1], key_of=lambda s: f'k{s}'):
            gb, _ = fleet_backend.apply_changes(gb, [buf])
        # Registers pack raw counters: past the window the doc promotes
        # (pre-commit, no partial state) and stays correct on host
        assert not gb['state'].is_fleet
        assert fleet_backend.get_patch(gb)['diffs']['props']['k2'] == {
            f'{CTR_LIMIT + 1}@{ACTORS[0]}': {
                'type': 'value', 'value': 2, 'datatype': 'int'}}

    def test_clone_carries_counter_window_state(self):
        # A clone of a rebased/overflowed slot must not read its grid row
        # as authoritative with the wrong base (review regression)
        from automerge_tpu.fleet.tensor_doc import CTR_LIMIT
        from automerge_tpu.fleet.backend import materialize_docs
        fb = FleetBackend(DocFleet(doc_capacity=4, key_capacity=4))
        gb = fb.init()
        A = ACTORS[0]
        b1 = change_buf(A, 1, CTR_LIMIT - 10, [
            {'action': 'set', 'obj': '_root', 'key': 'k', 'value': 111,
             'datatype': 'int', 'pred': []}])
        h1 = am.decode_change(b1)['hash']
        b2 = change_buf(A, 2, 2 * CTR_LIMIT + 3, [
            {'action': 'set', 'obj': '_root', 'key': 'k', 'value': 222,
             'datatype': 'int', 'pred': [f'{CTR_LIMIT - 10}@{A}']}],
            deps=[h1])
        gb, _ = fleet_backend.apply_changes(gb, [b1])
        gb['state'].fleet.flush()
        gb, _ = fleet_backend.apply_changes(gb, [b2])
        gb['state'].fleet.flush()
        clone = fleet_backend.clone(gb)
        assert materialize_docs([gb]) == [{'k': 222}]
        assert materialize_docs([clone]) == [{'k': 222}]

    def test_rebased_slot_does_not_disable_fleet_turbo(self):
        # One long-lived doc crossing the window must not push every OTHER
        # doc in the fleet off the native/turbo paths (review regression)
        from automerge_tpu.fleet.tensor_doc import CTR_LIMIT
        fleet = DocFleet(doc_capacity=4, key_capacity=4)
        fb = FleetBackend(fleet)
        gb = fb.init()
        step = CTR_LIMIT - 100
        heads, pred = [], []
        for seq, start in enumerate([1, step, 2 * step], 1):
            buf = change_buf(ACTORS[0], seq, start, [
                {'action': 'set', 'obj': '_root', 'key': 'k', 'value': seq,
                 'datatype': 'int', 'pred': pred}], deps=heads)
            heads = [am.decode_change(buf)['hash']]
            pred = [f'{start}@{ACTORS[0]}']
            gb, _ = fleet_backend.apply_changes(gb, [buf])
            fleet.flush()
        assert fleet.ctr_base          # the long doc rebased
        other = fb.init()
        before = fleet.metrics.turbo_calls
        handles, _ = fleet_backend.apply_changes_docs(
            [other], [[change_buf(ACTORS[1], 1, 1, [
                {'action': 'set', 'obj': '_root', 'key': 'x', 'value': 1,
                 'datatype': 'int', 'pred': []}])]], mirror=False)
        assert fleet.metrics.turbo_calls == before + 1


class TestRegisterPatches:
    """Exact-device get_patch comes straight from RegisterState — no mirror
    rebuild (round-2 VERDICT item 10). Differentially equal to the host
    backend's patch on the same history."""

    def _scenarios(self):
        A, B = ACTORS[0], ACTORS[1]
        c1 = change_buf(A, 1, 1, [
            {'action': 'set', 'obj': '_root', 'key': 'bird',
             'value': 'magpie', 'pred': []},
            {'action': 'set', 'obj': '_root', 'key': 'n', 'value': 7,
             'datatype': 'int', 'pred': []},
            {'action': 'set', 'obj': '_root', 'key': 'u', 'value': 3,
             'datatype': 'uint', 'pred': []},
            {'action': 'set', 'obj': '_root', 'key': 'f', 'value': 2.5,
             'datatype': 'float64', 'pred': []},
            {'action': 'set', 'obj': '_root', 'key': 'ok', 'value': True,
             'pred': []},
            {'action': 'set', 'obj': '_root', 'key': 'nothing',
             'value': None, 'pred': []},
            {'action': 'set', 'obj': '_root', 'key': 'when',
             'value': 1589032171000, 'datatype': 'timestamp', 'pred': []},
            {'action': 'set', 'obj': '_root', 'key': 'score', 'value': 10,
             'datatype': 'counter', 'pred': []}])
        h1 = am.decode_change(c1)['hash']
        # concurrent conflicting writes + counter inc + delete
        c2 = change_buf(A, 2, 9, [
            {'action': 'inc', 'obj': '_root', 'key': 'score', 'value': 5,
             'pred': [f'8@{A}']},
            {'action': 'del', 'obj': '_root', 'key': 'nothing',
             'pred': [f'6@{A}']}], deps=[h1])
        c3 = change_buf(B, 1, 9, [
            {'action': 'set', 'obj': '_root', 'key': 'bird',
             'value': 'wren', 'pred': [f'1@{A}']}], deps=[h1])
        return [c1, c2, c3]

    def test_patch_differential_and_no_mirror_rebuilds(self):
        changes = self._scenarios()
        hb = host_backend.init()
        for c in changes:
            hb, _ = host_backend.apply_changes(hb, [c])
        expected = host_backend.get_patch(hb)

        fleet = DocFleet(doc_capacity=2, key_capacity=16, exact_device=True)
        fb = FleetBackend(fleet)
        gb = fb.init()
        for c in changes:
            gb, _ = fleet_backend.apply_changes(gb, [c])
        got = fleet_backend.get_patch(gb)
        assert got == expected
        assert gb['state'].is_fleet
        assert fleet.metrics.mirror_rebuilds == 0

    def test_typed_values_survive_mixed_exact_flush(self):
        """A flush batch mixing one doc's typed root sets (counter + inc)
        with another doc's sequence ops routes through _flush_exact_mixed —
        which must box datatypes like changes_to_op_rows does, or the
        device-served patch degrades counters to plain ints."""
        changes = self._scenarios()
        hb = host_backend.init()
        for c in changes:
            hb, _ = host_backend.apply_changes(hb, [c])
        expected = host_backend.get_patch(hb)

        fleet = DocFleet(doc_capacity=4, key_capacity=16, exact_device=True)
        fb = FleetBackend(fleet)
        gb = fb.init()
        other = fb.init()
        A = ACTORS[0]
        seq_change = change_buf(A, 1, 1, [
            {'action': 'makeText', 'obj': '_root', 'key': 't', 'pred': []},
            {'action': 'set', 'obj': f'1@{A}', 'elemId': '_head',
             'insert': True, 'value': 'x', 'pred': []}])
        for c in changes:
            gb, _ = fleet_backend.apply_changes(gb, [c])
        # same pending batch: forces the mixed exact flush for every doc
        other, _ = fleet_backend.apply_changes(other, [seq_change])
        fleet.flush()
        got = fleet_backend.get_patch(gb)
        assert got == expected
        assert fleet.metrics.mirror_rebuilds == 0

    def test_typed_values_survive_turbo_exact(self):
        """The turbo wire->device path on an exact fleet must box typed
        root sets (counter/uint/timestamp) before the register dispatch so
        device-served patches keep datatypes and counter folds."""
        changes = self._scenarios()
        hb = host_backend.init()
        for c in changes:
            hb, _ = host_backend.apply_changes(hb, [c])
        expected = host_backend.get_patch(hb)

        fleet = DocFleet(doc_capacity=2, key_capacity=16, exact_device=True)
        fb = FleetBackend(fleet)
        handles = [fb.init()]
        handles, patches = fleet_backend.apply_changes_docs(
            handles, [changes], mirror=False)
        if fleet.metrics.turbo_calls:
            got = fleet_backend.get_patch(handles[0])
            assert got == expected
            assert fleet.metrics.mirror_rebuilds == 0

    def _differential(self, changes, turbo=False):
        """Apply `changes` to host and exact fleet; device patch must equal
        the host patch with zero mirror rebuilds."""
        hb = host_backend.init()
        for c in changes:
            hb, _ = host_backend.apply_changes(hb, [c])
        expected = host_backend.get_patch(hb)
        fleet = DocFleet(doc_capacity=2, key_capacity=32, exact_device=True)
        fb = FleetBackend(fleet)
        gb = fb.init()
        if turbo:
            handles, _ = fleet_backend.apply_changes_docs(
                [gb], [list(changes)], mirror=False)
            gb = handles[0]
        else:
            for c in changes:
                gb, _ = fleet_backend.apply_changes(gb, [c])
        got = fleet_backend.get_patch(gb)
        assert got == expected
        assert fleet.metrics.mirror_rebuilds == 0
        return fleet, gb

    def test_text_patch_from_device(self):
        """Whole-doc patches for text documents come straight from the
        device sequence registers (round-3 extension of VERDICT item 10)."""
        A, B = ACTORS[0], ACTORS[1]
        c1 = change_buf(A, 1, 1, [
            {'action': 'makeText', 'obj': '_root', 'key': 't', 'pred': []},
            {'action': 'set', 'obj': f'1@{A}', 'elemId': '_head',
             'insert': True, 'value': 'h', 'pred': []},
            {'action': 'set', 'obj': f'1@{A}', 'elemId': f'2@{A}',
             'insert': True, 'value': 'i', 'pred': []}])
        h1 = am.decode_change(c1)['hash']
        c2 = change_buf(B, 1, 4, [
            {'action': 'set', 'obj': f'1@{A}', 'elemId': f'2@{A}',
             'value': 'H', 'pred': [f'2@{A}']},
            {'action': 'del', 'obj': f'1@{A}', 'elemId': f'3@{A}',
             'pred': [f'3@{A}']}], deps=[h1])
        for turbo in (False, True):
            self._differential([c1, c2], turbo=turbo)

    def test_list_conflict_and_resurrection_patch_from_device(self):
        """Concurrent set-vs-set (conflict edits) and set-vs-del
        (resurrection) on list elements patch identically to the host."""
        A, B = ACTORS[0], ACTORS[1]
        c1 = change_buf(A, 1, 1, [
            {'action': 'makeList', 'obj': '_root', 'key': 'l', 'pred': []},
            {'action': 'set', 'obj': f'1@{A}', 'elemId': '_head',
             'insert': True, 'value': 1, 'datatype': 'int', 'pred': []},
            {'action': 'set', 'obj': f'1@{A}', 'elemId': f'2@{A}',
             'insert': True, 'value': 2, 'datatype': 'int', 'pred': []}])
        h1 = am.decode_change(c1)['hash']
        c2 = change_buf(A, 2, 4, [
            {'action': 'set', 'obj': f'1@{A}', 'elemId': f'2@{A}',
             'value': 10, 'datatype': 'int', 'pred': [f'2@{A}']}],
            deps=[h1])
        c3 = change_buf(B, 1, 4, [
            {'action': 'set', 'obj': f'1@{A}', 'elemId': f'2@{A}',
             'value': 20, 'datatype': 'int', 'pred': [f'2@{A}']},
            {'action': 'del', 'obj': f'1@{A}', 'elemId': f'3@{A}',
             'pred': [f'3@{A}']}], deps=[h1])
        for turbo in (False, True):
            self._differential([c1, c2, c3], turbo=turbo)

    def test_nested_tree_patch_from_device(self):
        """Nested map/table trees patch from the two-level device grid."""
        A = ACTORS[0]
        c1 = change_buf(A, 1, 1, [
            {'action': 'makeMap', 'obj': '_root', 'key': 'cfg', 'pred': []},
            {'action': 'set', 'obj': f'1@{A}', 'key': 'inner', 'value': 5,
             'datatype': 'int', 'pred': []},
            {'action': 'makeMap', 'obj': f'1@{A}', 'key': 'deep',
             'pred': []},
            {'action': 'set', 'obj': f'3@{A}', 'key': 'leaf',
             'value': 'v', 'pred': []},
            {'action': 'makeTable', 'obj': '_root', 'key': 'tbl',
             'pred': []},
            {'action': 'set', 'obj': '_root', 'key': 'top', 'value': True,
             'pred': []}])
        for turbo in (False, True):
            self._differential([c1], turbo=turbo)

    def test_objects_inside_lists_patch_from_device(self):
        """Rows-in-lists serve whole-doc patches straight from the device
        registers (round 4): the make element rows flow through the same
        child-linking path map cells use, no mirror rebuild."""
        A = ACTORS[0]
        c1 = change_buf(A, 1, 1, [
            {'action': 'makeList', 'obj': '_root', 'key': 'todo',
             'pred': []},
            {'action': 'makeMap', 'obj': f'1@{A}', 'elemId': '_head',
             'insert': True, 'pred': []},
            {'action': 'set', 'obj': f'2@{A}', 'key': 't', 'value': 'wash',
             'pred': []},
            {'action': 'makeList', 'obj': f'1@{A}', 'elemId': f'2@{A}',
             'insert': True, 'pred': []},
            {'action': 'set', 'obj': f'4@{A}', 'elemId': '_head',
             'insert': True, 'value': 7, 'datatype': 'int', 'pred': []},
            {'action': 'set', 'obj': f'1@{A}', 'elemId': f'4@{A}',
             'insert': True, 'value': 3, 'datatype': 'int', 'pred': []}])
        h1 = am.decode_change(c1)['hash']
        c2 = change_buf(A, 2, 7, [
            {'action': 'set', 'obj': f'2@{A}', 'key': 'n', 'value': 5,
             'datatype': 'int', 'pred': []}], deps=[h1])
        for turbo in (False, True):
            self._differential([c1, c2], turbo=turbo)

    def test_typed_list_elements_patch_from_device(self):
        """uint/timestamp/float64 list elements keep their datatypes in
        device-served patches (TypedValue boxing on the seq paths)."""
        A = ACTORS[0]
        c1 = change_buf(A, 1, 1, [
            {'action': 'makeList', 'obj': '_root', 'key': 'l', 'pred': []},
            {'action': 'set', 'obj': f'1@{A}', 'elemId': '_head',
             'insert': True, 'value': 3, 'datatype': 'uint', 'pred': []},
            {'action': 'set', 'obj': f'1@{A}', 'elemId': f'2@{A}',
             'insert': True, 'value': 1589032171000,
             'datatype': 'timestamp', 'pred': []},
            {'action': 'set', 'obj': f'1@{A}', 'elemId': f'3@{A}',
             'insert': True, 'value': 2.5, 'datatype': 'float64',
             'pred': []}])
        for turbo in (False, True):
            fleet, gb = self._differential([c1], turbo=turbo)
            # reads unwrap the boxed TypedValues back to plain payloads
            assert fleet_backend.materialize_docs([gb]) == \
                [{'l': [3, 1589032171000, 2.5]}]

    def test_conflict_patch_from_device(self):
        A, B = ACTORS[0], ACTORS[1]
        c1 = change_buf(A, 1, 1, [
            {'action': 'set', 'obj': '_root', 'key': 'x', 'value': 1,
             'datatype': 'int', 'pred': []}])
        c2 = change_buf(B, 1, 1, [
            {'action': 'set', 'obj': '_root', 'key': 'x', 'value': 2,
             'datatype': 'int', 'pred': []}])
        fleet = DocFleet(doc_capacity=2, key_capacity=4, exact_device=True)
        fb = FleetBackend(fleet)
        gb = fb.init()
        gb, _ = fleet_backend.apply_changes(gb, [c1])
        gb, _ = fleet_backend.apply_changes(gb, [c2])
        patch = fleet_backend.get_patch(gb)
        assert patch['diffs']['props']['x'] == {
            f'1@{A}': {'type': 'value', 'value': 1, 'datatype': 'int'},
            f'1@{B}': {'type': 'value', 'value': 2, 'datatype': 'int'}}
        assert fleet.metrics.mirror_rebuilds == 0


class TestBulkInitEquivalence:
    def test_bulk_init_matches_constructor(self):
        """init_docs' allocation-only constructor (_FlatEngine._bulk_new)
        must initialize exactly the attributes the real constructor chain
        does — the keep-in-sync contract for the bulk fast path."""
        from automerge_tpu.fleet.backend import _FlatEngine

        fleet = DocFleet(doc_capacity=4, key_capacity=4)
        via_bulk = fleet_backend.init_docs(1, fleet)[0]['state']._impl
        via_ctor = _FlatEngine(fleet, fleet.alloc_slot())

        def slot_attrs(obj):
            out = {}
            for klass in type(obj).__mro__:
                for name in getattr(klass, '__slots__', ()):
                    if hasattr(obj, name):
                        out[name] = type(getattr(obj, name))
            return out

        a, b = slot_attrs(via_bulk), slot_attrs(via_ctor)
        assert a == b
        # every HashGraph slot must be live on both (nothing skipped) —
        # several are property shadows over the fleet's _DocCols columns
        # (heads/clock/max_op/changes/_deferred), which hasattr resolves
        # the same way
        from automerge_tpu.backend.hash_graph import HashGraph
        for name in HashGraph.__slots__:
            assert name in a, name


class TestBatchedInitFreeDispatches:
    """O(1)-dispatch contracts for the batched host-side seam paths: an
    N-doc init and an N-doc free must issue a size-independent number of
    device dispatches (DocFleet.dispatches()), and the batched paths must
    produce state identical to the per-doc paths they replace."""

    def _seed(self, handles, n_changes=2):
        per_doc = []
        for d in range(len(handles)):
            changes, heads = [], []
            for c in range(n_changes):
                buf = change_buf(ACTORS[d % 3], c + 1, c + 1, [
                    {'action': 'set', 'obj': '_root', 'key': f'k{c}',
                     'value': d * 10 + c, 'datatype': 'int', 'pred': []}],
                    deps=heads)
                heads = [am.decode_change(buf)['hash']]
                changes.append(buf)
            per_doc.append(changes)
        handles, _ = fleet_backend.apply_changes_docs(handles, per_doc,
                                                      mirror=False)
        return handles

    def test_init_docs_dispatches_size_independent(self):
        counts = {}
        for n in (4, 32):
            fb = FleetBackend(DocFleet(doc_capacity=64, key_capacity=8))
            # materialize device state first: a fresh fleet's lazy init
            # would trivially dispatch nothing
            seeded = self._seed(fleet_backend.init_docs(1, fb.fleet))
            fb.fleet.flush()
            before = fb.fleet.dispatches
            handles = fleet_backend.init_docs(n, fb.fleet)
            counts[n] = fb.fleet.dispatches - before
            handles = self._seed(handles)
            assert fleet_backend.materialize_docs(handles) == \
                [{'k0': d * 10, 'k1': d * 10 + 1} for d in range(n)]
        assert counts[4] == counts[32], counts
        assert counts[32] <= 2, counts   # grid (+ registers when present)

    def test_init_docs_fresh_fleet_zero_dispatches(self):
        fb = FleetBackend(DocFleet(doc_capacity=64, key_capacity=8))
        before = fb.fleet.dispatches
        fleet_backend.init_docs(32, fb.fleet)
        assert fb.fleet.dispatches == before   # lazy: first flush allocates

    def test_free_docs_dispatches_size_independent(self):
        counts = {}
        for n in (4, 16):
            fb = FleetBackend(DocFleet(doc_capacity=32, key_capacity=8))
            handles = self._seed(fleet_backend.init_docs(n, fb.fleet))
            fb.fleet.flush()
            before = fb.fleet.dispatches
            fleet_backend.free_docs(handles)
            counts[n] = fb.fleet.dispatches - before
            assert all(h['state'] is None and h['frozen'] for h in handles)
        assert counts[4] == counts[16], counts
        assert counts[16] <= 2, counts

    def test_alloc_slots_zero_is_noop(self):
        """alloc_slots(0) must not touch the free list or n_slots (the
        [-0:] slice aliases the whole list; a 0-doc init or an all-bad
        bulk load would otherwise hand live slots to the next alloc)."""
        fb = FleetBackend(DocFleet(doc_capacity=8, key_capacity=8))
        handles = self._seed(fleet_backend.init_docs(3, fb.fleet))
        fleet_backend.free_docs(handles[1:2])
        free_before = list(fb.fleet.free_slots)
        n_before = fb.fleet.n_slots
        assert fb.fleet.alloc_slots(0) == []
        assert fb.fleet.free_slots == free_before
        assert fb.fleet.n_slots == n_before

    def test_free_docs_matches_per_doc_free(self):
        """Batched free leaves device state identical to the per-doc
        free() chain: same zeroed rows, same recycled slots on re-init."""
        fleets = []
        for batched in (False, True):
            fb = FleetBackend(DocFleet(doc_capacity=16, key_capacity=8))
            handles = self._seed(fleet_backend.init_docs(6, fb.fleet))
            fb.fleet.flush()
            victims = [handles[i] for i in (1, 3, 4)]
            if batched:
                fleet_backend.free_docs(victims)
            else:
                for h in victims:
                    fleet_backend.free(h)
            survivors = [handles[i] for i in (0, 2, 5)]
            assert fleet_backend.materialize_docs(survivors) == \
                [{'k0': d * 10, 'k1': d * 10 + 1} for d in (0, 2, 5)]
            fleets.append(fb.fleet)
        a, b = fleets
        assert np.array_equal(np.asarray(a.state.winners),
                              np.asarray(b.state.winners))
        assert np.array_equal(np.asarray(a.state.values),
                              np.asarray(b.state.values))
        assert sorted(a.free_slots) == sorted(b.free_slots)
        # recycled slots hand out in the same order afterwards
        assert a.alloc_slots(3) == [b.alloc_slot() for _ in range(3)]

    def test_batched_init_matches_per_doc_init(self):
        """init_docs handles are byte-identical (materialize + save) to
        per-doc FleetBackend.init() handles under the same turbo applies."""
        fb1 = FleetBackend(DocFleet(doc_capacity=8, key_capacity=8))
        fb2 = FleetBackend(DocFleet(doc_capacity=8, key_capacity=8))
        batched = fleet_backend.init_docs(4, fb1.fleet)
        perdoc = [fb2.init() for _ in range(4)]
        batched = self._seed(batched)
        perdoc = self._seed(perdoc)
        assert fleet_backend.materialize_docs(batched) == \
            fleet_backend.materialize_docs(perdoc)
        for hb, hp in zip(batched, perdoc):
            assert fleet_backend.get_heads(hb) == fleet_backend.get_heads(hp)
            assert bytes(fleet_backend.save(hb)) == \
                bytes(fleet_backend.save(hp))


class TestDeleteResurrection:
    """Pred-scoped delete semantics in the default (LWW grid) mode, ref
    new.js:1204-1217 / test/new_backend_test.js:1660-class histories: a
    delete kills ONLY the ops it preds. A concurrent set the delete never
    saw stays visible — even when the delete's own opId packs higher —
    and a causally-later straggler set resurrects a deleted key."""

    A, B = 'aa' * 16, 'bb' * 16   # sorted: A -> actor 0, B -> actor 1

    def _chain(self):
        from automerge_tpu.columnar import decode_change_meta
        c1 = change_buf(self.A, 1, 1, [
            {'action': 'set', 'obj': '_root', 'key': 'k', 'value': 1,
             'datatype': 'int', 'pred': []}])
        h1 = decode_change_meta(c1, True)['hash']
        # concurrent wrt each other; the del's packed id (2@B) is HIGHER
        # than the concurrent set's (2@A)
        c_del = change_buf(self.B, 1, 2, [
            {'action': 'del', 'obj': '_root', 'key': 'k',
             'pred': [f'1@{self.A}']}], deps=[h1])
        c_set = change_buf(self.A, 2, 2, [
            {'action': 'set', 'obj': '_root', 'key': 'k', 'value': 7,
             'datatype': 'int', 'pred': [f'1@{self.A}']}], deps=[h1])
        return c1, c_del, c_set

    def _host_result(self, batches):
        doc = am.init()
        for chs in batches:
            doc, _ = am.apply_changes(doc, [bytes(b) for b in chs])
        return dict(doc)

    @pytest.mark.parametrize('mirror', [True, False])
    def test_concurrent_del_and_set_same_batch(self, mirror):
        c1, c_del, c_set = self._chain()
        fb = FleetBackend(DocFleet(doc_capacity=2, key_capacity=4))
        handles = fleet_backend.init_docs(1, fb.fleet)
        handles, _ = fleet_backend.apply_changes_docs(
            handles, [[c1, c_del, c_set]], mirror=mirror)
        want = self._host_result([[c1, c_del, c_set]])
        assert fleet_backend.materialize_docs(handles) == [want]
        assert want == {'k': 7}   # the un-pred'd set survives

    @pytest.mark.parametrize('mirror', [True, False])
    def test_concurrent_del_then_set_across_batches(self, mirror):
        """Standing-winner kill first, then the concurrent set arrives in
        a LATER apply: the key must resurrect with the set's value."""
        c1, c_del, c_set = self._chain()
        fb = FleetBackend(DocFleet(doc_capacity=2, key_capacity=4))
        handles = fleet_backend.init_docs(1, fb.fleet)
        handles, _ = fleet_backend.apply_changes_docs(
            handles, [[c1, c_del]], mirror=mirror)
        assert fleet_backend.materialize_docs(handles) == [{}]
        handles, _ = fleet_backend.apply_changes_docs(
            handles, [[c_set]], mirror=mirror)
        want = self._host_result([[c1, c_del], [c_set]])
        assert fleet_backend.materialize_docs(handles) == [want] == [{'k': 7}]

    @pytest.mark.parametrize('mirror', [True, False])
    def test_delete_still_deletes_when_it_pred_everything(self, mirror):
        c1, c_del, _ = self._chain()
        fb = FleetBackend(DocFleet(doc_capacity=2, key_capacity=4))
        handles = fleet_backend.init_docs(1, fb.fleet)
        handles, _ = fleet_backend.apply_changes_docs(
            handles, [[c1, c_del]], mirror=mirror)
        assert fleet_backend.materialize_docs(handles) == \
            [self._host_result([[c1, c_del]])] == [{}]

    @pytest.mark.parametrize('mirror', [True, False])
    def test_set_after_delete_overwrites(self, mirror):
        """A set that preds the delete's surviving state (normal causal
        overwrite after deletion) lands as usual."""
        from automerge_tpu.columnar import decode_change_meta
        c1, c_del, _ = self._chain()
        h_del = decode_change_meta(c_del, True)['hash']
        c_new = change_buf(self.B, 2, 3, [
            {'action': 'set', 'obj': '_root', 'key': 'k', 'value': 9,
             'datatype': 'int', 'pred': []}], deps=[h_del])
        fb = FleetBackend(DocFleet(doc_capacity=2, key_capacity=4))
        handles = fleet_backend.init_docs(1, fb.fleet)
        handles, _ = fleet_backend.apply_changes_docs(
            handles, [[c1, c_del, c_new]], mirror=mirror)
        want = self._host_result([[c1, c_del, c_new]])
        assert fleet_backend.materialize_docs(handles) == [want] == \
            [{'k': 9}]


class TestDeleteHiddenLosers:
    """Round-5 review finds: the single-winner grid cannot resurrect a
    concurrent LOSER it never stored. (1) When a delete clears a standing
    winner while other visible ops remain from earlier batches, the slot
    must go mirror-authoritative and reads must still match the
    reference. (2) The host winner mirror must replicate the device's
    same-batch lane masking, or later counter-attribution checks pass
    against a winner the device never kept."""

    A, B, C = 'aa' * 16, 'bb' * 16, 'cc' * 16

    def _host(self, batches):
        doc = am.init()
        for chs in batches:
            doc, _ = am.apply_changes(doc, [bytes(b) for b in chs])
        return dict(doc)

    def test_cross_batch_kill_with_hidden_loser(self):
        """Batch 1: concurrent sets 1@A (loses LWW) and 1@C (wins).
        Batch 2: delete preds ONLY 1@C. Reference: 1@A resurrects
        (k = 5). The grid dropped 1@A's value, so the slot must fall
        back to the mirror and still answer k = 5."""
        from automerge_tpu.columnar import decode_change_meta
        cA = change_buf(self.A, 1, 1, [
            {'action': 'set', 'obj': '_root', 'key': 'k', 'value': 5,
             'datatype': 'int', 'pred': []}])
        cC = change_buf(self.C, 1, 1, [
            {'action': 'set', 'obj': '_root', 'key': 'k', 'value': 9,
             'datatype': 'int', 'pred': []}])
        hC = decode_change_meta(cC, True)['hash']
        c_del = change_buf(self.B, 1, 2, [
            {'action': 'del', 'obj': '_root', 'key': 'k',
             'pred': [f'1@{self.C}']}], deps=[hC])
        for mirror in (True, False):
            fb = FleetBackend(DocFleet(doc_capacity=2, key_capacity=4))
            handles = fleet_backend.init_docs(1, fb.fleet)
            handles, _ = fleet_backend.apply_changes_docs(
                handles, [[cA, cC]], mirror=mirror)
            handles, _ = fleet_backend.apply_changes_docs(
                handles, [[c_del]], mirror=mirror)
            want = self._host([[cA, cC], [c_del]])
            got = fleet_backend.materialize_docs(handles)
            assert got == [want] == [{'k': 5}], f'mirror={mirror}: {got}'
            fb.fleet.flush()
            slot = handles[0]['state']._impl.slot
            assert slot in fb.fleet.del_fallback

    def test_mirror_replicates_same_batch_lane_masking(self):
        """Same batch: set 2@B (pred 1@A), del pred [2@B], concurrent
        set 2@A. Device winner is 2@A; the mirror must agree — and a
        later inc pred'ing the dead 2@B must flag, not pass."""
        from automerge_tpu.columnar import decode_change_meta
        c1 = change_buf(self.A, 1, 1, [
            {'action': 'set', 'obj': '_root', 'key': 'k', 'value': 1,
             'datatype': 'int', 'pred': []}])
        h1 = decode_change_meta(c1, True)['hash']
        cB = change_buf(self.B, 1, 2, [
            {'action': 'set', 'obj': '_root', 'key': 'k', 'value': 2,
             'datatype': 'int', 'pred': [f'1@{self.A}']}], deps=[h1])
        hB = decode_change_meta(cB, True)['hash']
        c_del = change_buf(self.C, 1, 3, [
            {'action': 'del', 'obj': '_root', 'key': 'k',
             'pred': [f'2@{self.B}']}], deps=[hB])
        cA2 = change_buf(self.A, 2, 2, [
            {'action': 'set', 'obj': '_root', 'key': 'k', 'value': 7,
             'datatype': 'int', 'pred': [f'1@{self.A}']}], deps=[h1])
        for mirror in (True, False):
            fb = FleetBackend(DocFleet(doc_capacity=2, key_capacity=4))
            handles = fleet_backend.init_docs(1, fb.fleet)
            handles, _ = fleet_backend.apply_changes_docs(
                handles, [[c1, cB, c_del, cA2]], mirror=mirror)
            want = self._host([[c1, cB, c_del, cA2]])
            got = fleet_backend.materialize_docs(handles)
            assert got == [want] == [{'k': 7}], f'mirror={mirror}: {got}'
            fleet = fb.fleet
            fleet.flush()
            fleet._fold_pending_winners()
            slot = handles[0]['state']._impl.slot
            kx = fleet.keys.index['k']
            a_num = fleet.actors.index[self.A]
            # mirror holds the device's winner 2@A, not the masked 2@B
            assert int(fleet.host_winners[slot, kx]) == (2 << 8) | a_num, \
                f'mirror={mirror}'


class TestDeleteChains:
    """Round-5 second-review finds: same-batch supersession chains and
    shared preds across concurrent ops — shapes where single-winner
    bookkeeping is provably insufficient, so the slot must serve reads
    from the exact mirror and match the reference."""

    A, B, C = 'aa' * 16, 'bb' * 16, 'cc' * 16

    def _host(self, batches):
        doc = am.init()
        for chs in batches:
            doc, _ = am.apply_changes(doc, [bytes(b) for b in chs])
        return dict(doc)

    @pytest.mark.parametrize('mirror', [True, False])
    def test_set_then_delete_same_batch_after_standing_winner(self, mirror):
        """Batch 1: set k=1 (1@A). Batch 2 (one flush): overwrite set
        k=2 (2@A pred 1@A) then del (3@A pred 2@A). Reference: key
        deleted. An ordinary sequential edit split across two syncs."""
        from automerge_tpu.columnar import decode_change_meta
        c1 = change_buf(self.A, 1, 1, [
            {'action': 'set', 'obj': '_root', 'key': 'k', 'value': 1,
             'datatype': 'int', 'pred': []}])
        h1 = decode_change_meta(c1, True)['hash']
        c2 = change_buf(self.A, 2, 2, [
            {'action': 'set', 'obj': '_root', 'key': 'k', 'value': 2,
             'datatype': 'int', 'pred': [f'1@{self.A}']}], deps=[h1])
        h2 = decode_change_meta(c2, True)['hash']
        c3 = change_buf(self.A, 3, 3, [
            {'action': 'del', 'obj': '_root', 'key': 'k',
             'pred': [f'2@{self.A}']}], deps=[h2])
        fb = FleetBackend(DocFleet(doc_capacity=2, key_capacity=4))
        handles = fleet_backend.init_docs(1, fb.fleet)
        handles, _ = fleet_backend.apply_changes_docs(
            handles, [[c1]], mirror=mirror)
        handles, _ = fleet_backend.apply_changes_docs(
            handles, [[c2, c3]], mirror=mirror)
        want = self._host([[c1], [c2, c3]])
        got = fleet_backend.materialize_docs(handles)
        assert got == [want] == [{}], f'mirror={mirror}: {got}'

    @pytest.mark.parametrize('mirror', [True, False])
    def test_concurrent_ops_sharing_a_pred(self, mirror):
        """Concurrent set 2@A and del 2@B both pred the same 1@A (both
        causally saw only it), with a hidden concurrent loser 1@C from
        batch 1; batch 3 deletes the surviving winner. Reference: the
        hidden loser 1@C resurrects (k = 9)."""
        from automerge_tpu.columnar import decode_change_meta
        cA = change_buf(self.A, 1, 1, [
            {'action': 'set', 'obj': '_root', 'key': 'k', 'value': 5,
             'datatype': 'int', 'pred': []}])
        hA = decode_change_meta(cA, True)['hash']
        cC = change_buf(self.C, 1, 1, [
            {'action': 'set', 'obj': '_root', 'key': 'k', 'value': 9,
             'datatype': 'int', 'pred': []}])
        set2 = change_buf(self.A, 2, 2, [
            {'action': 'set', 'obj': '_root', 'key': 'k', 'value': 6,
             'datatype': 'int', 'pred': [f'1@{self.A}']}], deps=[hA])
        h2 = decode_change_meta(set2, True)['hash']
        del2 = change_buf(self.B, 1, 2, [
            {'action': 'del', 'obj': '_root', 'key': 'k',
             'pred': [f'1@{self.A}']}], deps=[hA])
        hd = decode_change_meta(del2, True)['hash']
        del3 = change_buf(self.B, 2, 3, [
            {'action': 'del', 'obj': '_root', 'key': 'k',
             'pred': [f'2@{self.A}']}], deps=sorted([h2, hd]))
        fb = FleetBackend(DocFleet(doc_capacity=2, key_capacity=4))
        handles = fleet_backend.init_docs(1, fb.fleet)
        handles, _ = fleet_backend.apply_changes_docs(
            handles, [[cA, cC]], mirror=mirror)
        handles, _ = fleet_backend.apply_changes_docs(
            handles, [[set2, del2]], mirror=mirror)
        handles, _ = fleet_backend.apply_changes_docs(
            handles, [[del3]], mirror=mirror)
        want = self._host([[cA, cC], [set2, del2], [del3]])
        got = fleet_backend.materialize_docs(handles)
        assert got == [want] == [{'k': 9}], f'mirror={mirror}: {got}'


class TestTurboDanglingPreds:
    """Round-5 VERDICT item 4: the turbo path rejects dangling preds at
    apply time with the exact path's error and full rollback, instead of
    deferring detection to the next mirror rebuild."""

    def _setup_turbo(self, exact=False):
        fb = FleetBackend(DocFleet(doc_capacity=2, key_capacity=8,
                                   exact_device=exact))
        handles = fleet_backend.init_docs(1, fb.fleet)
        setup = change_buf(ACTORS[0], 1, 1, [
            {'action': 'set', 'obj': '_root', 'key': 'k', 'value': 1,
             'datatype': 'int', 'pred': []}])
        handles, _ = fleet_backend.apply_changes_docs(handles, [[setup]],
                                                      mirror=False)
        return fb, handles

    @pytest.mark.parametrize('exact', [False, True])
    def test_dangling_pred_raises_and_rolls_back(self, exact):
        fb, handles = self._setup_turbo(exact)
        heads = handles[0]['heads']
        bad = change_buf(ACTORS[0], 2, 2, [
            {'action': 'set', 'obj': '_root', 'key': 'k', 'value': 9,
             'datatype': 'int', 'pred': [f'9@{ACTORS[1]}']}], deps=heads)
        with pytest.raises(ValueError,
                           match='no matching operation for pred'):
            fleet_backend.apply_changes_docs(handles, [[bad]], mirror=False)
        # state unchanged, handle still live
        assert handles[0]['state'].heads == heads
        assert fleet_backend.materialize_docs(handles) == [{'k': 1}]

    # -- the standing applied-op index, asked a batch at a time ----------

    def _standing(self, n_docs, actor=ACTORS[0]):
        """n_docs documents whose key 'k' was set (op 1@actor, value d)
        by an earlier turbo call: a pred of 1@actor on 'k' resolves only
        from the standing index."""
        fleet = DocFleet(doc_capacity=n_docs + 2, key_capacity=8)
        handles = fleet_backend.init_docs(n_docs, fleet)
        per_doc = [[change_buf(actor, 1, 1, [
            {'action': 'set', 'obj': '_root', 'key': 'k', 'value': d,
             'datatype': 'int', 'pred': []}])] for d in range(n_docs)]
        handles, _ = fleet_backend.apply_changes_docs(handles, per_doc,
                                                      mirror=False)
        return fleet, handles

    @staticmethod
    def _over(handle, value, pred, key='k', actor=ACTORS[0], seq=2,
              start=2):
        """The handle's next change, by ACTORS[0] (op start@): sets `key`
        to `value` over the ops of `actor` that `pred` counts."""
        return change_buf(ACTORS[0], seq, start, [
            {'action': 'set', 'obj': '_root', 'key': key, 'value': value,
             'datatype': 'int', 'pred': [f'{p}@{actor}' for p in pred]}],
            deps=handle['heads'])

    def _refused(self, handles, per_doc, pred, doc_index):
        """The call raises the exact path's DanglingPred for `pred` in
        document `doc_index`, and every document is as it was."""
        from automerge_tpu.errors import DanglingPred
        heads = [h['state'].heads for h in handles]
        before = fleet_backend.materialize_docs(handles)
        with pytest.raises(DanglingPred) as err:
            fleet_backend.apply_changes_docs(handles, per_doc, mirror=False)
        assert str(err.value) == f'no matching operation for pred: {pred}'
        assert err.value.doc_index == doc_index
        assert [h['state'].heads for h in handles] == heads
        assert fleet_backend.materialize_docs(handles) == before

    def _oracle_many_slots(self):
        fleet, handles = self._standing(48)
        asked = []
        lookup = fleet._index_lookup
        fleet._index_lookup = lambda s, c: asked.append(len(s)) or \
            lookup(s, c)
        per_doc = [[self._over(h, 100 + d, [1])]
                   for d, h in enumerate(handles)]
        handles, _ = fleet_backend.apply_changes_docs(handles, per_doc,
                                                      mirror=False)
        assert asked == [48]               # one lookup for the whole call
        assert fleet.metrics.standing_preds == 48
        assert fleet_backend.materialize_docs(handles) == \
            [{'k': 100 + d} for d in range(48)]

    def _oracle_dangling_mid_batch(self):
        fleet, handles = self._standing(5)
        preds = {2: 7, 4: 8}               # the first in pred order wins
        per_doc = [[self._over(h, 100 + d, [preds.get(d, 1)])]
                   for d, h in enumerate(handles)]
        self._refused(handles, per_doc, f'7@{ACTORS[0]}', 2)
        # the same call less its dangling preds applies
        per_doc = [[self._over(h, 100 + d, [1])]
                   for d, h in enumerate(handles)]
        handles, _ = fleet_backend.apply_changes_docs(handles, per_doc,
                                                      mirror=False)
        assert fleet_backend.materialize_docs(handles) == \
            [{'k': 100 + d} for d in range(5)]

    def _oracle_unknown_actor(self):
        fleet, handles = self._standing(3)
        assert ACTORS[3] not in fleet.actors.index
        per_doc = [[self._over(h, 100 + d, [1],
                               actor=ACTORS[3] if d == 1 else ACTORS[0])]
                   for d, h in enumerate(handles)]
        self._refused(handles, per_doc, f'1@{ACTORS[3]}', 1)

    def _oracle_unknown_key(self):
        fleet, handles = self._standing(3)
        assert 'fresh' not in fleet.keys.index
        per_doc = [[self._over(h, 100 + d, [1],
                               key='fresh' if d == 2 else 'k')]
                   for d, h in enumerate(handles)]
        self._refused(handles, per_doc, f'1@{ACTORS[0]}', 2)

    def _oracle_incomplete_slot(self):
        fleet, handles = self._standing(2)
        fleet._op_index_incomplete.add(handles[0]['state']._impl.slot)
        per_doc = [[self._over(handles[0], 100, [9])],     # not checked
                   [self._over(handles[1], 101, [1])]]
        handles, _ = fleet_backend.apply_changes_docs(handles, per_doc,
                                                      mirror=False)
        assert fleet.metrics.standing_preds == 1
        assert fleet_backend.materialize_docs(handles) == \
            [{'k': 100}, {'k': 101}]

    def _oracle_after_clone(self):
        fleet, handles = self._standing(2)
        twin = fleet_backend.clone(handles[1])
        self._refused([twin], [[self._over(twin, 5, [9])]],
                      f'9@{ACTORS[0]}', 0)
        (twin,), _ = fleet_backend.apply_changes_docs(
            [twin], [[self._over(twin, 5, [1])]], mirror=False)
        assert fleet_backend.materialize_docs([handles[1], twin]) == \
            [{'k': 1}, {'k': 5}]

    def _oracle_after_free(self):
        fleet, handles = self._standing(2)
        slot = handles[0]['state']._impl.slot
        fleet_backend.free(handles[0])
        (fresh,) = fleet_backend.init_docs(1, fleet)
        assert fresh['state']._impl.slot == slot   # the slot is recycled
        # its last tenant's 1@A on 'k' is gone with it
        first = change_buf(ACTORS[0], 1, 2, [
            {'action': 'set', 'obj': '_root', 'key': 'k', 'value': 7,
             'datatype': 'int', 'pred': [f'1@{ACTORS[0]}']}])
        self._refused([fresh], [[first]], f'1@{ACTORS[0]}', 0)
        first = change_buf(ACTORS[0], 1, 1, [
            {'action': 'set', 'obj': '_root', 'key': 'k', 'value': 7,
             'datatype': 'int', 'pred': []}])
        (fresh,), _ = fleet_backend.apply_changes_docs([fresh], [[first]],
                                                       mirror=False)
        (fresh,), _ = fleet_backend.apply_changes_docs(
            [fresh], [[self._over(fresh, 8, [1])]], mirror=False)
        assert fleet_backend.materialize_docs([fresh, handles[1]]) == \
            [{'k': 8}, {'k': 1}]

    def _oracle_after_rebase(self):
        # A rebased slot's calls take the exact path, so the turbo gate
        # never asks for it; the index still moves with the slot's window:
        # the standing op is found at its rebased packed id, not its old
        from automerge_tpu.fleet.tensor_doc import ACTOR_BITS, CTR_LIMIT
        fleet = DocFleet(doc_capacity=2, key_capacity=4)
        gb = FleetBackend(fleet).init()
        A, step, heads, pred = ACTORS[0], CTR_LIMIT - 100, [], []
        for seq, start in enumerate([1, step, 2 * step], 1):
            buf = change_buf(A, seq, start, [
                {'action': 'set', 'obj': '_root', 'key': 'k', 'value': seq,
                 'datatype': 'int', 'pred': pred}], deps=heads)
            heads = [am.decode_change(buf)['hash']]
            pred = [f'{start}@{A}']
            gb, _ = fleet_backend.apply_changes(gb, [buf])
            fleet.flush()
        slot = gb['state']._impl.slot
        base = fleet.ctr_base[slot]
        assert base > 0
        key, actor = fleet.keys.index['k'], fleet.actors.index[A]

        def combo(ctr):
            return (key << 32) | ((ctr - base) << ACTOR_BITS) | actor

        assert list(fleet._index_lookup(
            [slot, slot, slot], [combo(2 * step), combo(2 * step + base),
                                 combo(2 * step - 1)])) == \
            [True, False, False]

    def _oracle_after_actor_resort(self):
        # ACTORS[3] sorts before ACTORS[1]: registering it renumbers every
        # op of ACTORS[1] the index holds, asked (so handed over) or not
        fleet, handles = self._standing(2, actor=ACTORS[1])
        handles, _ = fleet_backend.apply_changes_docs(handles, [
            [change_buf(ACTORS[1], 2, 2, [
                {'action': 'set', 'obj': '_root', 'key': 'k', 'value': 5 + d,
                 'datatype': 'int', 'pred': [f'1@{ACTORS[1]}']}],
                deps=h['heads'])] for d, h in enumerate(handles)],
            mirror=False)
        assert fleet.metrics.standing_preds == 2
        was = fleet.actors.index[ACTORS[1]]
        (h0,), _ = fleet_backend.apply_changes_docs(
            [handles[0]], [[change_buf(ACTORS[3], 1, 3, [
                {'action': 'set', 'obj': '_root', 'key': 'm', 'value': 1,
                 'datatype': 'int', 'pred': []}], deps=handles[0]['heads'])]],
            mirror=False)
        assert fleet.actors.index[ACTORS[1]] != was
        handles = [h0, handles[1]]
        self._refused(handles, [[self._over(h, 9, [3], actor=ACTORS[1],
                                            seq=1, start=4)]
                                for h in handles], f'3@{ACTORS[1]}', 0)
        # 1@ was handed over before the re-sort, 2@ after it
        per_doc = [[self._over(h, 10 + d, [1, 2], actor=ACTORS[1], seq=1,
                               start=4)] for d, h in enumerate(handles)]
        handles, _ = fleet_backend.apply_changes_docs(handles, per_doc,
                                                      mirror=False)
        assert fleet.metrics.standing_preds == 6
        assert fleet_backend.materialize_docs(handles) == \
            [{'k': 10, 'm': 1}, {'k': 11}]

    @pytest.mark.skipif(not native.available(),
                        reason='the index is native')
    def test_native_op_index_matches_a_set_reference(self):
        """native.OpIndex against per-slot Python sets, through appends out
        of order, handed over in two batches, and every maintenance call."""
        rng = np.random.default_rng(7)
        index, ref = native.op_index(), {}

        def add(n, n_slots):
            slots = rng.integers(0, n_slots, n)
            combos = (rng.integers(0, 6, n) << 32) | \
                (rng.integers(1, 40, n) << 8) | rng.integers(0, 4, n)
            index.add(slots, combos)
            for s, c in zip(slots.tolist(), combos.tolist()):
                ref.setdefault(s, []).append(c)

        def agree():
            held = [(s, c) for s, cs in ref.items() for c in cs]
            probes = held + [(s, c + 256) for s, c in held] + \
                [(99, 1), (-1, 1)]
            slots, combos = map(np.array, zip(*probes))
            want = [c in ref.get(s, ()) for s, c in probes]
            assert index.contains(slots, combos).tolist() == want
            assert index.rows == len(held) and index.nbytes == 8 * len(held)

        add(400, 20)
        agree()
        add(30, 24)                   # a slot's later rows, out of order
        agree()
        perm = rng.permutation(256)
        index.remap(perm)
        ref = {s: [(c & ~255) | int(perm[c & 255]) for c in cs]
               for s, cs in ref.items()}
        agree()
        index.rebase(3, 5 << 8)
        ref[3] = [(c & ~0xffffffff) | max((c & 0xffffffff) - (5 << 8), 0)
                  for c in ref[3]]
        agree()
        index.copy(4, 30)
        ref[30] = list(ref[4])
        index.copy(98, 5)             # a slot the index never held
        ref[5] = []
        agree()
        index.drop([1, 2, 30, 97])
        for s in (1, 2, 30):
            ref.pop(s, None)
        agree()
        with pytest.raises(ValueError):
            index.add([-1], [1])

    @pytest.mark.skipif(not native.available(),
                        reason='the turbo path needs the native codec')
    @pytest.mark.parametrize('case', [
        'many_slots', 'dangling_mid_batch', 'unknown_actor', 'unknown_key',
        'incomplete_slot', 'after_clone', 'after_free', 'after_rebase',
        'after_actor_resort'])
    def test_standing_index_oracle(self, case):
        """The preds a turbo call's own rows do not resolve are asked of
        the slot's standing applied-op index, all in one lookup: a valid
        pred applies, and the first dangling pred in pred order (unknown
        actor, unknown key or not indexed) raises the exact path's error
        for its document with every document rolled back; slots marked
        incomplete are not checked; and the index follows its slots
        through clone, free, counter rebase and actor re-sort."""
        getattr(self, f'_oracle_{case}')()

    @pytest.mark.parametrize('exact', [False, True])
    def test_dangling_inc_pred_raises(self, exact):
        fb, handles = self._setup_turbo(exact)
        bad = change_buf(ACTORS[0], 2, 2, [
            {'action': 'inc', 'obj': '_root', 'key': 'k', 'value': 1,
             'pred': [f'7@{ACTORS[0]}']}], deps=handles[0]['heads'])
        with pytest.raises(ValueError,
                           match='no matching operation for pred'):
            fleet_backend.apply_changes_docs(handles, [[bad]], mirror=False)

    def test_valid_preds_still_apply(self):
        """Overwrites pred'ing standing ops, batch-internal preds, and
        preds resolved via the op index across separate turbo calls."""
        from automerge_tpu.columnar import decode_change_meta
        fb, handles = self._setup_turbo()
        c2 = change_buf(ACTORS[0], 2, 2, [
            {'action': 'set', 'obj': '_root', 'key': 'k', 'value': 2,
             'datatype': 'int', 'pred': [f'1@{ACTORS[0]}']}],
            deps=handles[0]['heads'])
        h2 = decode_change_meta(c2, True)['hash']
        c3 = change_buf(ACTORS[0], 3, 3, [
            {'action': 'set', 'obj': '_root', 'key': 'k', 'value': 3,
             'datatype': 'int', 'pred': [f'2@{ACTORS[0]}']}], deps=[h2])
        # same batch (batch-internal pred) ...
        handles, _ = fleet_backend.apply_changes_docs(handles, [[c2, c3]],
                                                      mirror=False)
        assert fleet_backend.materialize_docs(handles) == [{'k': 3}]
        # ... and across calls (standing-index pred)
        c4 = change_buf(ACTORS[0], 4, 4, [
            {'action': 'set', 'obj': '_root', 'key': 'k', 'value': 4,
             'datatype': 'int', 'pred': [f'3@{ACTORS[0]}']}],
            deps=handles[0]['heads'])
        handles, _ = fleet_backend.apply_changes_docs(handles, [[c4]],
                                                      mirror=False)
        assert fleet_backend.materialize_docs(handles) == [{'k': 4}]

    def test_mixed_exact_then_turbo_pred_resolves(self):
        """Ops applied via the EXACT path must be visible to the turbo
        pred check (index fed from every ingest path)."""
        fb, handles = self._setup_turbo()
        c2 = change_buf(ACTORS[1], 1, 2, [
            {'action': 'set', 'obj': '_root', 'key': 'm', 'value': 5,
             'datatype': 'int', 'pred': []}], deps=handles[0]['heads'])
        handles, _ = fleet_backend.apply_changes_docs(handles, [[c2]],
                                                      mirror=True)
        c3 = change_buf(ACTORS[1], 2, 3, [
            {'action': 'set', 'obj': '_root', 'key': 'm', 'value': 6,
             'datatype': 'int', 'pred': [f'2@{ACTORS[1]}']}],
            deps=handles[0]['heads'])
        handles, _ = fleet_backend.apply_changes_docs(handles, [[c3]],
                                                      mirror=False)
        assert fleet_backend.materialize_docs(handles) == [{'k': 1, 'm': 6}]

    def test_loaded_docs_validate_preds(self):
        """Bulk-loaded docs feed the op index at LOAD time (round-5
        VERDICT weak #6 closed): a dangling pred against loaded history
        raises the exact path's error with full rollback, while valid
        preds against loaded ops still apply."""
        from automerge_tpu.fleet.loader import load_docs
        fb, handles = self._setup_turbo()
        data = fleet_backend.save(handles[0])
        fresh = DocFleet(doc_capacity=2, key_capacity=8)
        loaded = load_docs([data], fresh)
        assert fresh.metrics.docs_bulk_loaded == 1   # native path taken
        heads = loaded[0]['heads']
        bad = change_buf(ACTORS[0], 2, 2, [
            {'action': 'set', 'obj': '_root', 'key': 'k', 'value': 9,
             'datatype': 'int', 'pred': [f'9@{ACTORS[1]}']}], deps=heads)
        with pytest.raises(ValueError,
                           match='no matching operation for pred'):
            fleet_backend.apply_changes_docs(loaded, [[bad]], mirror=False)
        assert loaded[0]['state'].heads == heads     # rolled back
        c2 = change_buf(ACTORS[0], 2, 2, [
            {'action': 'set', 'obj': '_root', 'key': 'k', 'value': 2,
             'datatype': 'int', 'pred': [f'1@{ACTORS[0]}']}], deps=heads)
        loaded, _ = fleet_backend.apply_changes_docs(loaded, [[c2]],
                                                     mirror=False)
        assert fleet_backend.materialize_docs(loaded) == [{'k': 2}]

    def test_loaded_docs_validate_overwritten_pred(self):
        """An op pred'ing a LOADED, already-overwritten op is still valid
        (concurrent writer that never saw the overwrite) — the load-time
        index must cover dead rows, not just the visible winners."""
        from automerge_tpu.fleet.loader import load_docs
        fb, handles = self._setup_turbo()
        c2 = change_buf(ACTORS[0], 2, 2, [
            {'action': 'set', 'obj': '_root', 'key': 'k', 'value': 2,
             'datatype': 'int', 'pred': [f'1@{ACTORS[0]}']}],
            deps=handles[0]['heads'])
        handles, _ = fleet_backend.apply_changes_docs(handles, [[c2]],
                                                      mirror=False)
        data = fleet_backend.save(handles[0])
        fresh = DocFleet(doc_capacity=2, key_capacity=8)
        loaded = load_docs([data], fresh)
        assert fresh.metrics.docs_bulk_loaded == 1
        # Concurrent actor B saw only 1@A (now overwritten by 2@A): its
        # pred must resolve against the loaded dead row, creating a
        # conflict rather than a false reject
        conc = change_buf(ACTORS[1], 1, 5, [
            {'action': 'set', 'obj': '_root', 'key': 'k', 'value': 7,
             'datatype': 'int', 'pred': [f'1@{ACTORS[0]}']}],
            deps=loaded[0]['heads'])
        loaded, _ = fleet_backend.apply_changes_docs(loaded, [[conc]],
                                                     mirror=False)
        assert fleet_backend.materialize_docs(loaded) == [{'k': 7}]


class TestFleetRebuild:
    """The donation-failure contract (fleet/apply.py): after a device
    state loss, documents rebuild into a fresh fleet from their change
    logs — heads, reads, and further edits identical to never losing
    the device."""

    def test_rebuild_from_logs(self):
        from automerge_tpu.columnar import encode_change, decode_change_meta
        fb = FleetBackend(DocFleet(doc_capacity=4, key_capacity=8))
        handles = fleet_backend.init_docs(3, fb.fleet)
        actor = ACTORS[0]
        per_doc = []
        for d in range(3):
            c1 = change_buf(actor, 1, 1, [
                {'action': 'set', 'obj': '_root', 'key': 'k',
                 'value': d, 'datatype': 'int', 'pred': []}])
            h1 = decode_change_meta(c1, True)['hash']
            c2 = change_buf(actor, 2, 2, [
                {'action': 'set', 'obj': '_root', 'key': 's',
                 'value': 'x' * (d + 1), 'pred': []}], deps=[h1])
            per_doc.append([c1, c2])
        handles, _ = fleet_backend.apply_changes_docs(handles, per_doc,
                                                      mirror=False)
        want = fleet_backend.materialize_docs(handles)
        heads = [h['heads'] for h in handles]
        # simulate total device loss: rebuild into a FRESH fleet
        fresh = DocFleet(doc_capacity=4, key_capacity=8)
        rebuilt = fleet_backend.rebuild_docs(handles, fresh)
        assert [h['heads'] for h in rebuilt] == heads
        assert fleet_backend.materialize_docs(rebuilt) == want
        # further edits land on the new fleet
        c3 = change_buf(actor, 3, 3, [
            {'action': 'set', 'obj': '_root', 'key': 'k', 'value': 99,
             'datatype': 'int', 'pred': [f'1@{actor}']}], deps=heads[0])
        rebuilt, _ = fleet_backend.apply_changes_docs(
            rebuilt, [[c3], [], []], mirror=False)
        assert fleet_backend.materialize_docs(rebuilt)[0]['k'] == 99

    def test_rebuild_requeues_held_back_changes(self):
        """Causally-premature queue entries survive the rebuild and apply
        once their deps arrive."""
        from automerge_tpu.columnar import encode_change, decode_change_meta
        fb = FleetBackend(DocFleet(doc_capacity=2, key_capacity=8))
        handles = fleet_backend.init_docs(1, fb.fleet)
        actor = ACTORS[0]
        c1 = change_buf(actor, 1, 1, [
            {'action': 'set', 'obj': '_root', 'key': 'a', 'value': 1,
             'datatype': 'int', 'pred': []}])
        h1 = decode_change_meta(c1, True)['hash']
        c2 = change_buf(actor, 2, 2, [
            {'action': 'set', 'obj': '_root', 'key': 'b', 'value': 2,
             'datatype': 'int', 'pred': []}], deps=[h1])
        h2 = decode_change_meta(c2, True)['hash']
        c3 = change_buf(actor, 3, 3, [
            {'action': 'set', 'obj': '_root', 'key': 'c', 'value': 3,
             'datatype': 'int', 'pred': []}], deps=[h2])
        # apply c1 and c3 (c3 queues: missing c2)
        handles, _ = fleet_backend.apply_changes_docs(handles, [[c1, c3]],
                                                      mirror=False)
        assert fleet_backend.materialize_docs(handles) == [{'a': 1}]
        rebuilt = fleet_backend.rebuild_docs(
            handles, DocFleet(doc_capacity=2, key_capacity=8))
        assert fleet_backend.materialize_docs(rebuilt) == [{'a': 1}]
        # c2 arrives: the re-queued c3 must drain
        rebuilt, _ = fleet_backend.apply_changes_docs(rebuilt, [[c2]],
                                                      mirror=False)
        assert fleet_backend.materialize_docs(rebuilt) == \
            [{'a': 1, 'b': 2, 'c': 3}]


class TestMakeKindMemo:
    def test_same_opid_different_make_kinds_across_docs(self):
        """Round-5 review find: one turbo batch where the SAME packed
        opId is makeMap on doc A but makeText on doc B (independent docs
        share actor numbering). Each doc must get its own object type —
        the memo must not leak doc A's kind into doc B."""
        actor = ACTORS[0]
        cA = change_buf(actor, 1, 1, [
            {'action': 'makeMap', 'obj': '_root', 'key': 'obj', 'pred': []},
            {'action': 'set', 'obj': f'1@{actor}', 'key': 'x', 'value': 1,
             'datatype': 'int', 'pred': []}])
        cB = change_buf(actor, 1, 1, [
            {'action': 'makeText', 'obj': '_root', 'key': 'obj',
             'pred': []},
            {'action': 'set', 'obj': f'1@{actor}', 'elemId': '_head',
             'insert': True, 'value': 'h', 'pred': []}])
        fb = FleetBackend(DocFleet(doc_capacity=4, key_capacity=8))
        handles = fleet_backend.init_docs(2, fb.fleet)
        handles, _ = fleet_backend.apply_changes_docs(
            handles, [[cA], [cB]], mirror=False)
        got = fleet_backend.materialize_docs(handles)
        assert got[0] == {'obj': {'x': 1}}, got[0]
        assert got[1] == {'obj': 'h'}, got[1]
        # engine-side object registries agree with the types
        eA = handles[0]['state']._impl
        eB = handles[1]['state']._impl
        assert f'1@{actor}' in eA.map_objects
        assert f'1@{actor}' in eB.seq_objects


class TestSeqPoolReserve:
    def test_bulk_fresh_rows_grow_each_pool_once(self):
        """Placing N fresh sequence rows one alloc at a time grew the
        size-class pool ~log2(N) times, each growth an eager device
        re-pad of all 8 pool arrays — a dispatch storm wherever a
        dispatch has a fixed cost. The reserve() pre-pass must bound
        growth to O(1) device copies per size class per dispatch."""
        actor = ACTORS[0]
        n_docs = 64
        c1 = change_buf(actor, 1, 1, [
            {'action': 'makeList', 'obj': '_root', 'key': 'l', 'pred': []},
            {'action': 'set', 'obj': f'1@{actor}', 'elemId': '_head',
             'insert': True, 'value': 7, 'datatype': 'int', 'pred': []}])
        fb = FleetBackend(DocFleet(doc_capacity=n_docs, key_capacity=8))
        handles = fleet_backend.init_docs(n_docs, fb.fleet)
        handles, _ = fleet_backend.apply_changes_docs(
            handles, [[c1]] * n_docs, mirror=False)
        pools = fb.fleet.seq_pools
        # one class in play (all rows are 1-element lists): the initial
        # empty() plus at most one growth — NOT ~log2(64) regrowths
        assert pools.grow_events <= 2, pools.grow_events
        assert fleet_backend.materialize_docs(handles) == \
            [{'l': [7]}] * n_docs


class TestParkDocs:
    """park_docs demotes a live doc's host state to its canonical chunk
    (BASELINE.md's 100k-doc host-memory plan): reads, history, saves,
    sync, and further turbo applies must be observationally unchanged."""

    def _mk_handles(self, n=3):
        actor = ACTORS[0]
        fb = FleetBackend(DocFleet(doc_capacity=4, key_capacity=16))
        handles = fleet_backend.init_docs(n, fb.fleet)
        per_doc = []
        for d in range(n):
            c1 = change_buf(actor, 1, 1, [
                {'action': 'set', 'obj': '_root', 'key': 'k',
                 'value': d, 'datatype': 'int', 'pred': []},
                {'action': 'makeText', 'obj': '_root', 'key': 't',
                 'pred': []}])
            from automerge_tpu.columnar import decode_change_meta
            h1 = decode_change_meta(c1, True)['hash']
            c2 = change_buf(actor, 2, 3, [
                {'action': 'set', 'obj': f'2@{actor}', 'elemId': '_head',
                 'insert': True, 'value': 'x', 'pred': []}], deps=[h1])
            per_doc.append([c1, c2])
        handles, _ = fleet_backend.apply_changes_docs(handles, per_doc,
                                                      mirror=False)
        return fb, handles

    def test_park_preserves_reads_history_saves_and_applies(self):
        fb, handles = self._mk_handles()
        want_reads = fleet_backend.materialize_docs(handles)
        want_saves = [bytes(fleet_backend.save(h)) for h in handles]
        want_changes = [[bytes(b) for b in
                         fleet_backend.get_changes(h, [])] for h in handles]
        heads = [h['heads'] for h in handles]
        before = fleet_backend.host_memory_stats(handles)
        assert fleet_backend.park_docs(handles) == 3
        after = fleet_backend.host_memory_stats(handles)
        assert after['change_log_bytes'] == 0
        assert after['parked_doc_bytes'] > 0
        assert before['change_log_bytes'] > 0
        # device reads, saves, heads: unchanged
        assert fleet_backend.materialize_docs(handles) == want_reads
        assert [h['heads'] for h in handles] == heads
        assert [bytes(fleet_backend.save(h)) for h in handles] == want_saves
        # history rematerializes from the chunk, hash-identical
        got = [[bytes(b) for b in fleet_backend.get_changes(h, [])]
               for h in handles]
        assert got == want_changes
        # further changes land through the turbo gate on parked docs
        actor = ACTORS[0]
        c3 = change_buf(actor, 3, 4, [
            {'action': 'set', 'obj': '_root', 'key': 'k', 'value': 99,
             'datatype': 'int', 'pred': [f'1@{actor}']}],
            deps=handles[0]['heads'])
        handles, _ = fleet_backend.apply_changes_docs(
            handles, [[c3], [], []], mirror=False)
        reads = fleet_backend.materialize_docs(handles)
        assert reads[0]['k'] == 99
        assert reads[1:] == want_reads[1:]

    def test_repark_drops_rematerialized_history(self):
        """Review find (round 6): a history read between parks revives
        the change log; re-parking must drop it (and the accounting must
        surface it while it lingers). The NATIVE extractor never pins
        decoded change dicts at all — docs_with_decoded_history counts
        only the Python-fallback path's decoded dicts."""
        from automerge_tpu import native
        fb, handles = self._mk_handles(1)
        assert fleet_backend.park_docs(handles) == 1
        fleet_backend.get_changes(handles[0], [])   # rematerializes
        stats = fleet_backend.host_memory_stats(handles)
        expect_decoded = 0 if native.available() else 1
        assert stats['docs_with_decoded_history'] == expect_decoded
        assert stats['change_log_bytes'] > 0
        assert fleet_backend.park_docs(handles) == 1
        stats = fleet_backend.host_memory_stats(handles)
        assert stats['docs_with_decoded_history'] == 0
        assert stats['change_log_bytes'] == 0
        assert handles[0]['state']._impl._doc_decoded is None

    def test_park_then_sync_converges(self):
        fb, handles = self._mk_handles(1)
        assert fleet_backend.park_docs(handles) == 1
        handle = handles[0]
        peer = host_backend.init()
        s1, s2 = am.init_sync_state(), am.init_sync_state()
        for _ in range(12):
            s1, msg = fleet_backend.generate_sync_message(handle, s1)
            if msg is not None:
                peer, s2, _ = host_backend.receive_sync_message(peer, s2,
                                                                msg)
            s2, msg2 = host_backend.generate_sync_message(peer, s2)
            if msg2 is not None:
                handle, s1, _ = fleet_backend.receive_sync_message(
                    handle, s1, msg2)
            if msg is None and msg2 is None:
                break
        assert host_backend.get_heads(peer) == \
            fleet_backend.get_heads(handle)

    def test_park_skips_queued_docs(self):
        actor = ACTORS[0]
        fb = FleetBackend(DocFleet(doc_capacity=2, key_capacity=8))
        handles = fleet_backend.init_docs(1, fb.fleet)
        from automerge_tpu.columnar import decode_change_meta
        c1 = change_buf(actor, 1, 1, [
            {'action': 'set', 'obj': '_root', 'key': 'a', 'value': 1,
             'datatype': 'int', 'pred': []}])
        h1 = decode_change_meta(c1, True)['hash']
        c2 = change_buf(actor, 2, 2, [
            {'action': 'set', 'obj': '_root', 'key': 'b', 'value': 2,
             'datatype': 'int', 'pred': []}], deps=[h1])
        h2 = decode_change_meta(c2, True)['hash']
        c3 = change_buf(actor, 3, 3, [
            {'action': 'set', 'obj': '_root', 'key': 'c', 'value': 3,
             'datatype': 'int', 'pred': []}], deps=[h2])
        handles, _ = fleet_backend.apply_changes_docs(handles, [[c1, c3]],
                                                      mirror=False)
        assert fleet_backend.park_docs(handles) == 0   # c3 queued
