"""Device-resident frontier index (fleet/hashindex.py): the
open-addressing table must answer EXACTLY like a Python-set oracle in
both storage modes, survive collision-chain fills and grow-by-migration
byte-identically, and its fleet wiring (commit staging, slot-free space
release, batched sync probes, incoming-change dedup, the quiet-tick
frontier compare) must never disagree with the hash-graph dicts it
replaces.
"""

import hashlib
import os
import random
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from automerge_tpu.backend import init_sync_state                # noqa: E402
from automerge_tpu.columnar import decode_change_meta, encode_change  # noqa: E402
from automerge_tpu.fleet import backend as fleet_backend         # noqa: E402
from automerge_tpu.fleet import hashindex as hashindex           # noqa: E402
from automerge_tpu.fleet.backend import (                        # noqa: E402
    DocFleet, apply_changes_docs, free_docs, init_docs)
from automerge_tpu.fleet.hashindex import (                      # noqa: E402
    HashIndex, frontier_compare, hashes_to_rows)
from automerge_tpu.fleet.sync_driver import (                    # noqa: E402
    generate_sync_messages_docs, receive_sync_messages_docs)
from automerge_tpu import native                                 # noqa: E402


def _h(i):
    return hashlib.sha256(f'key-{i}'.encode()).hexdigest()


def _colliding_rows(n, cap, pos=3):
    """n distinct 32-byte keys whose first uint32 word is congruent mod
    `cap` — every one of them lands on probe slot `pos` first, forcing
    an n-long collision chain."""
    rows = np.zeros((n, 32), dtype=np.uint8)
    for i in range(n):
        word = pos + cap * (i + 1)
        rows[i, :4] = np.frombuffer(
            np.uint32(word).tobytes(), dtype=np.uint8)
        rows[i, 4:12] = np.frombuffer(
            hashlib.sha256(str(i).encode()).digest()[:8], dtype=np.uint8)
    return rows


class TestHashIndexCore:
    def test_host_and_device_modes_answer_identically(self):
        traces = []
        rng = random.Random(7)
        for step in range(600):
            traces.append((rng.randrange(4), _h(rng.randrange(120)),
                           rng.random() < 0.5))
        answers = []
        for device_min in (10 ** 9, 1):     # forever-host vs device-now
            ix = HashIndex(capacity=16, device_min=device_min)
            sids = [ix.new_space() for _ in range(4)]
            out = []
            for s, h, is_insert in traces:
                if is_insert:
                    ix.insert(sids[s], [h])
                else:
                    out.append(bool(ix.probe(sids[s], [h])[0]))
            answers.append((ix.mode, out))
        assert answers[0][0] == 'host' and answers[1][0] == 'device'
        assert answers[0][1] == answers[1][1]

    def test_collision_chain_fill_to_load_factor(self):
        ix = HashIndex(capacity=64, device_min=1, load_max=0.6)
        sid = ix.new_space()
        rows = _colliding_rows(38, 64)      # just under 0.6 * 64
        assert ix.insert(sid, rows) == 38
        assert ix.mode == 'device'
        assert ix.probe(sid, rows).all()
        # absent keys sharing the same chain still answer False
        absent = _colliding_rows(10, 64)
        absent[:, 20] ^= 0xFF
        assert not ix.probe(sid, absent).any()
        # idempotent re-insert: no new keys (capacity MAY grow — the
        # sizing is conservative, it cannot know a batch is all dups)
        assert ix.insert(sid, rows) == 0
        assert ix.n_keys == 38 and ix.occupancy == 38
        assert ix.probe(sid, rows).all()

    def test_grow_by_migration_matches_oracle(self):
        rng = random.Random(3)
        ix = HashIndex(capacity=8, device_min=1, load_max=0.5)
        oracle = {}
        sids = [ix.new_space() for _ in range(6)]
        for sid in sids:
            oracle[sid] = set()
        for i in range(800):
            sid = rng.choice(sids)
            h = _h(i)
            ix.insert(sid, [h])
            oracle[sid].add(h)
        assert ix.grows >= 3            # 8 -> ... with load_max 0.5
        # release two spaces, then force one more migration: dead keys
        # must be reclaimed AND stay invisible
        for sid in sids[:2]:
            ix.release_space(sid)
            oracle[sid] = set()
        occ_with_dead = ix.occupancy
        more = [_h(10_000 + i) for i in range(600)]
        ix.insert(sids[2], more)
        oracle[sids[2]].update(more)
        assert ix.occupancy < occ_with_dead + 600   # garbage reclaimed
        for sid in sids:
            universe = [_h(i) for i in range(0, 800, 7)] + more[:50]
            got = ix.probe(sid, universe).tolist()
            want = [h in oracle[sid] for h in universe]
            assert got == want, f'space {sid} diverged from oracle'

    def test_in_batch_duplicates_land_once(self):
        ix = HashIndex(capacity=16, device_min=1)
        sid = ix.new_space()
        batch = [_h(1)] * 5 + [_h(2)] * 3 + [_h(3)]
        assert ix.insert(sid, batch) == 3
        assert ix.n_keys == 3
        assert ix.probe(sid, [_h(1), _h(2), _h(3), _h(4)]).tolist() == \
            [True, True, True, False]

    def test_spaces_are_disjoint_and_dead_spaces_answer_false(self):
        ix = HashIndex(capacity=16, device_min=1)
        a, b = ix.new_space(), ix.new_space()
        ix.insert(a, [_h(1)])
        assert ix.probe(b, [_h(1)]).tolist() == [False]
        ix.release_space(a)
        # dead space: probes mask it even before any migration
        assert ix.probe(a, [_h(1)]).tolist() == [False]
        # unknown space ids never crash, never match
        assert ix.probe(np.array([999], dtype=np.int32),
                        [_h(1)]).tolist() == [False]

    def test_probe_is_one_dispatch_in_device_mode(self):
        ix = HashIndex(capacity=128, device_min=1)
        sid = ix.new_space()
        ix.insert(sid, [_h(i) for i in range(20)])
        n0 = hashindex.dispatch_count()
        ix.probe(sid, [_h(i) for i in range(80)])
        assert hashindex.dispatch_count() - n0 == 1
        n0 = hashindex.dispatch_count()
        ix.insert(sid, [_h(i) for i in range(20)])   # pure duplicates
        assert hashindex.dispatch_count() - n0 == 1

    def test_differential_fuzz_trace(self):
        # the tools/fuzz_wire.py hashindex target's tier-1 dose: random
        # insert/probe traces with space churn, table vs set oracle
        rng = random.Random(0xF00D)
        ix = HashIndex(capacity=8, device_min=64, load_max=0.7)
        oracle, live = {}, []
        for step in range(1500):
            op = rng.random()
            if op < 0.05 or not live:
                sid = ix.new_space()
                oracle[sid] = set()
                live.append(sid)
            elif op < 0.08 and len(live) > 1:
                sid = live.pop(rng.randrange(len(live)))
                ix.release_space(sid)
                oracle[sid] = set()
            elif op < 0.55:
                sid = rng.choice(live)
                hs = [_h(rng.randrange(400))
                      for _ in range(rng.randrange(1, 8))]
                ix.insert(sid, hs)
                oracle[sid].update(hs)
            else:
                sid = rng.choice(live)
                hs = [_h(rng.randrange(400))
                      for _ in range(rng.randrange(1, 8))]
                got = ix.probe(sid, hs).tolist()
                assert got == [h in oracle[sid] for h in hs], f'step {step}'
        assert ix.mode == 'device'   # the trace must cross the threshold


class TestFrontierCompare:
    def test_compare_semantics(self):
        rng = np.random.default_rng(1)
        cur = rng.integers(0, 256, (6, 32)).astype(np.uint8)
        doc = cur.copy()
        doc[2] ^= 1                      # byte-diverged single head
        cur_n = np.array([1, 0, 1, 1, 2, 0], np.int32)
        doc_n = np.array([1, 0, 1, 0, 2, 1], np.int32)
        out = frontier_compare(cur, cur_n, doc, doc_n)
        # [eq-1head, both-empty, diverged, count-mismatch, multi-head
        #  (never quiet on device), count-mismatch]
        assert out.tolist() == [True, True, False, False, False, False]

    def test_compare_is_one_dispatch_and_pads_safely(self):
        cur = np.zeros((3, 32), dtype=np.uint8)
        doc = np.zeros((3, 32), dtype=np.uint8)
        n = np.zeros(3, np.int32)
        n0 = hashindex.dispatch_count()
        out = frontier_compare(cur, n, doc, n)
        assert hashindex.dispatch_count() - n0 == 1
        assert out.shape == (3,) and out.all()


needs_native = pytest.mark.skipif(
    not native.available(), reason='fleet wiring tests ride the turbo path')


def _change(actor, seq, start_op, deps, key, val):
    return encode_change({
        'actor': actor, 'seq': seq, 'startOp': start_op, 'time': 0,
        'message': '', 'deps': list(deps),
        'ops': [{'action': 'set', 'obj': '_root', 'key': key,
                 'value': val, 'datatype': 'int', 'pred': []}]})


def _grow_docs(handles, fleet, rounds, tag='k', start_seq=1):
    """Apply `rounds` turbo chains to every doc; returns (handles,
    per-doc head hash lists per round)."""
    n = len(handles)
    frontiers = [list(h['heads']) for h in handles]
    history = [[] for _ in range(n)]
    for r in range(rounds):
        seq = start_seq + r
        per_doc = []
        for d in range(n):
            buf = _change(f'{d % 99:02x}' * 8, seq, seq,
                          frontiers[d], f'{tag}{r}', d * 100 + r)
            frontiers[d] = [decode_change_meta(buf, True)['hash']]
            history[d].append(frontiers[d][0])
            per_doc.append([buf])
        handles, _ = apply_changes_docs(handles, per_doc, mirror=False)
    return handles, history


@needs_native
class TestFleetWiring:
    @pytest.mark.parametrize('exact', [False, True],
                             ids=['lww', 'exact'])
    def test_index_matches_graph_dicts_over_churn(self, exact):
        fleet = DocFleet(exact_device=exact)
        handles = init_docs(6, fleet)
        handles, history = _grow_docs(handles, fleet, 5)
        ix = fleet.frontier_index()
        engines = [h['state']._impl for h in handles]
        # registration backfill + staged commits: every applied hash
        # answers True, foreign hashes False — exactly get_change_by_hash
        for d, engine in enumerate(engines):
            probes = history[d] + history[(d + 1) % 6][:2] + [_h(d)]
            flags = ix.probe_pairs([engine] * len(probes), probes)
            want = [engine.get_change_by_hash(h) is not None
                    for h in probes]
            assert flags.tolist() == want
        # more commits AFTER registration ride the staging hook
        handles, history2 = _grow_docs(handles, fleet, 3, tag='m',
                                       start_seq=6)
        engines = [h['state']._impl for h in handles]
        for d, engine in enumerate(engines):
            flags = ix.probe_pairs([engine] * 3, history2[d])
            assert flags.all()

    def test_freed_slots_release_their_space(self):
        fleet = DocFleet()
        handles = init_docs(3, fleet)
        handles, history = _grow_docs(handles, fleet, 3)
        ix = fleet.frontier_index()
        engines = [h['state']._impl for h in handles]
        assert ix.probe_pairs([engines[1]], [history[1][0]]).all()
        victim_slot = engines[1].slot
        free_docs([handles[1]])
        assert victim_slot not in ix._spaces
        # a recycled slot's fresh doc never inherits the old tenant
        fresh = init_docs(1, fleet)
        fresh, fresh_hist = _grow_docs(fresh, fleet, 1)
        engine = fresh[0]['state']._impl
        assert engine.slot == victim_slot
        flags = ix.probe_pairs([engine, engine],
                               [history[1][0], fresh_hist[0][0]])
        assert flags.tolist() == [False, True]

    def test_drop_slots_purges_staged_batches_per_row(self):
        # regression (round-18 review): staged COMMIT batches carry an
        # ndarray of slots per entry — freeing a slot while its rows
        # await flush must neither crash nor drop OTHER slots' rows from
        # the same batch
        fleet = DocFleet()
        handles = init_docs(3, fleet)
        ix = fleet.frontier_index()       # index on BEFORE the commits
        engines = [h['state']._impl for h in handles]
        for e in engines:
            ix.space_of(e)                # register (empty backfill)
        handles, history = _grow_docs(handles, fleet, 2)
        assert ix._staged                 # commit rows await flush
        victim = handles[1]['state']._impl.slot
        free_docs([handles[1]])           # purges victim rows, keeps rest
        e0 = handles[0]['state']._impl
        e2 = handles[2]['state']._impl
        flags = ix.probe_pairs([e0] * len(history[0]) +
                               [e2] * len(history[2]),
                               history[0] + history[2])
        assert flags.all()
        assert victim not in ix._spaces
        assert all((int(s) != victim) for arr, _ in ix._staged
                   for s in arr)

    def test_sync_round_probes_are_batched_dispatches(self):
        fleet = DocFleet()
        handles = init_docs(8, fleet)
        handles, history = _grow_docs(handles, fleet, 4)
        states = [init_sync_state() for _ in handles]
        # a peer that synced at depth 2: lastSync/theirHeads at round 2
        for d, state in enumerate(states):
            state['theirHeads'] = [history[d][1]]
            state['theirHave'] = [{'lastSync': [history[d][1]],
                                   'bloom': b''}]
            state['theirNeed'] = []
        ix = fleet.frontier_index(device_min=1)   # force the device table
        engines = [h['state']._impl for h in handles]
        for e in engines:
            ix.space_of(e)          # warm registration outside the pin
        ix.flush()
        n0 = hashindex.dispatch_count()
        new_states, messages = generate_sync_messages_docs(handles, states)
        used = hashindex.dispatch_count() - n0
        # our_need candidates + theirHave reconciliation ride ONE merged
        # probe — a flat dispatch count regardless of doc count
        assert 1 <= used <= 2, f'{used} index dispatches for the round'
        assert all(m is not None for m in messages)

    def test_reset_branch_agrees_with_host_dicts(self):
        fleet = DocFleet()
        handles = init_docs(2, fleet)
        handles, history = _grow_docs(handles, fleet, 3)
        states = [init_sync_state() for _ in handles]
        # doc 0: peer lastSync we HOLD -> no reset; doc 1: unknown
        # lastSync -> full-resync reset message
        states[0]['theirHeads'] = [history[0][-1]]
        states[0]['theirHave'] = [{'lastSync': [history[0][0]],
                                   'bloom': b''}]
        states[0]['theirNeed'] = []
        states[1]['theirHeads'] = [_h('bogus')]
        states[1]['theirHave'] = [{'lastSync': [_h('bogus')],
                                   'bloom': b''}]
        states[1]['theirNeed'] = []
        _states, messages = generate_sync_messages_docs(handles, states)
        from automerge_tpu.backend.sync import decode_sync_message
        m1 = decode_sync_message(messages[1])
        # the reset frame: empty lastSync, EMPTY bloom, no changes
        assert m1['have'] == [{'lastSync': [], 'bloom': b''}]
        assert m1['changes'] == []
        # the known-lastSync doc runs a normal round: real filter bytes
        # and the resend the peer's empty bloom solicits
        m0 = decode_sync_message(messages[0])
        assert bytes(m0['have'][0]['bloom']) != b''
        # candidates = changes past the peer's lastSync (depth 1 of 3):
        # the empty peer bloom solicits both of them
        assert len(m0['changes']) == 2

    def test_receive_dedups_known_changes_byte_identically(self):
        # a resent known change (Bloom false negative / replayed wire)
        # must be dropped by the batched index probe BEFORE the apply —
        # committed state byte-identical, and the turbo fast path keeps
        # its zero-fallback property instead of demoting to the general
        # gate
        results = {}
        for dedup in (True, False):
            fleet = DocFleet()
            handles = init_docs(2, fleet)
            handles, history = _grow_docs(handles, fleet, 3)
            if dedup:
                fleet.frontier_index()   # index on: dedup engages
            new_b1 = _change('ee' * 16, 1, 50, list(handles[0]['heads']),
                             'fresh', 7)
            from automerge_tpu.backend.sync import encode_sync_message
            msg0 = encode_sync_message({
                'heads': [decode_change_meta(new_b1, True)['hash']],
                'need': [], 'have': [],
                'changes': [  # one known (resent) + one genuinely new
                    handles[0]['state'].get_change_by_hash(history[0][0]),
                    new_b1]})
            states = [init_sync_state() for _ in handles]
            out = receive_sync_messages_docs(
                handles, states, [msg0, None])
            new_handles = out[0]
            results[dedup] = (
                sorted(new_handles[0]['heads']),
                bytes(new_handles[0]['state'].save()),
                fleet.metrics.turbo_commit_fallback_docs,
            )
        assert results[True][0] == results[False][0]
        assert results[True][1] == results[False][1]
        # with dedup the resent change never reaches the gate, so the
        # turbo fast path holds (no per-doc fallback iterations)
        assert results[True][2] == 0

    def test_mid_round_promotion_contained(self):
        # regression (round-18 review): a received change with a
        # fleet-unsupported op (inc delta past int32) PROMOTES its doc
        # to the host engine mid-round, freeing the slot — the
        # post-apply received-heads probe must re-derive from the
        # post-apply backends instead of crashing on the stale engine,
        # and the healthy neighbour's sync state must still advance
        from automerge_tpu.backend.sync import encode_sync_message
        fleet = DocFleet()
        handles = init_docs(2, fleet)
        handles, history = _grow_docs(handles, fleet, 2)
        fleet.frontier_index()
        # warm the index so the probe path is live
        _s, _m = generate_sync_messages_docs(
            handles, [init_sync_state() for _ in handles])
        from automerge_tpu.fleet.tensor_doc import SEQ_CTR_LIMIT
        # a makeText past what a wide sequence row packs: fleet-unsupported
        # (promotes), host-valid (applies cleanly after promotion)
        big_inc = encode_change({
            'actor': 'dd' * 16, 'seq': 1, 'startOp': SEQ_CTR_LIMIT + 10,
            'time': 0, 'message': '', 'deps': list(handles[0]['heads']),
            'ops': [{'action': 'makeText', 'obj': '_root', 'key': 'deep',
                     'pred': []}]})
        plain = _change('ee' * 16, 1, 60, list(handles[1]['heads']),
                        'fresh', 5)
        msgs = [encode_sync_message({
                    'heads': [decode_change_meta(buf, True)['hash']],
                    'need': [], 'have': [], 'changes': [buf]})
                for buf in (big_inc, plain)]
        states = [init_sync_state() for _ in handles]
        new_handles, new_states, _p, errors = receive_sync_messages_docs(
            handles, states, msgs, on_error='quarantine')
        assert errors == [None, None]
        assert not new_handles[0]['state'].is_fleet     # promoted
        assert new_handles[1]['state'].is_fleet
        # both docs' sharedHeads advanced to the peer's (known) heads
        for i, buf in enumerate((big_inc, plain)):
            want = [decode_change_meta(buf, True)['hash']]
            assert new_states[i]['sharedHeads'] == want

    def test_frontier_toggle_covers_single_doc_path(self):
        # regression (round-18 review): AUTOMERGE_TPU_FRONTIER_INDEX=0 /
        # set_frontier_enabled(False) must pin the classic path on the
        # single-doc probe too, not just the batched driver
        from automerge_tpu.fleet.hashindex import set_frontier_enabled
        fleet = DocFleet()
        handles = init_docs(1, fleet)
        handles, history = _grow_docs(handles, fleet, 2)
        ix = fleet.frontier_index()
        ix.space_of(handles[0]['state']._impl)
        assert handles[0]['state'].probe_hashes(history[0]) is not None
        prev = set_frontier_enabled(False)
        try:
            assert handles[0]['state'].probe_hashes(history[0]) is None
            from automerge_tpu.fleet.sync_driver import _frontier_of
            assert _frontier_of(handles) is None
        finally:
            set_frontier_enabled(prev)

    def test_single_doc_protocol_rides_warm_index(self):
        from automerge_tpu.backend.sync import known_hash_flags
        fleet = DocFleet()
        handles = init_docs(2, fleet)
        handles, history = _grow_docs(handles, fleet, 3)
        # cold: no index space yet -> dict path (probe_hashes None)
        assert handles[0]['state'].probe_hashes([history[0][0]]) is None
        flags = known_hash_flags(handles[0], [history[0][0], _h(1)])
        assert flags == [True, False]
        # warm the index through the batched driver, then the single-doc
        # helper serves from it — identically
        ix = fleet.frontier_index()
        ix.space_of(handles[0]['state']._impl)
        probed = handles[0]['state'].probe_hashes([history[0][0], _h(1)])
        assert [bool(f) for f in probed] == [True, False]
        assert known_hash_flags(handles[0], [history[0][0], _h(1)]) == \
            [True, False]


@needs_native
class TestLazyHeads:
    def test_commit_fast_path_materializes_no_hex(self):
        fleet = DocFleet()
        handles = init_docs(4, fleet)
        handles, _ = _grow_docs(handles, fleet, 2)
        cols = fleet.doc_cols
        slots = [h['state']._impl.slot for h in handles]
        # the residual-floor pin: after a turbo fast-path commit the hex
        # memo columns are EMPTY — nothing hexed 4 head hashes nobody read
        assert all(cols.head_hex[s] is None for s in slots)
        assert all(cols.head_obj[s] is None for s in slots)
        # first genuine access materializes (and memoizes) exactly then
        heads = handles[0]['state'].heads
        assert len(heads) == 1 and len(heads[0]) == 64
        assert cols.head_hex[slots[0]] == heads[0]

    def test_stale_handle_answers_its_own_generation(self):
        fleet = DocFleet()
        handles = init_docs(1, fleet)
        handles, hist1 = _grow_docs(handles, fleet, 1)
        gen1 = handles[0]
        handles2, hist2 = _grow_docs(handles, fleet, 1, tag='z',
                                     start_seq=2)
        # the stale handle's lazy heads are the row captured at ITS
        # commit — not the engine's current frontier
        assert gen1['heads'] == [hist1[0][0]]
        assert handles2[0]['heads'] == [hist2[0][0]]
        assert gen1['heads'] != handles2[0]['heads']
