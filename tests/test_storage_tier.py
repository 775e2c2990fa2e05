"""Mmap-backed MainStore + cost-based tiering (ISSUE-15): the on-disk
segment arena (park -> discard-churn -> vacuum -> revive cycles, segment
swap under concurrently-held views, crash/kill recovery mid-vacuum), the
head-prefix probe short-circuit, the cost model replacing dead_fraction
triggers (brownout stage as pressure input), the clock auto-demote
policy, and mixed-batch sync routing through the frontier index.
"""

import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from automerge_tpu import native                                  # noqa: E402
from automerge_tpu.columnar import DocChunkView, encode_change    # noqa: E402
from automerge_tpu.fleet import backend as fleet_backend          # noqa: E402
from automerge_tpu.fleet.backend import DocFleet, init_docs       # noqa: E402
from automerge_tpu.fleet.segment import SegmentArena              # noqa: E402
from automerge_tpu.fleet.storage import MainStore, StorageEngine  # noqa: E402
from automerge_tpu.fleet.tiering import (                         # noqa: E402
    ClockDemote, CostModel, TieringController, tiering_stats)


def _change(actor, seq, start_op, deps, key, val):
    return encode_change({
        'actor': actor, 'seq': seq, 'startOp': start_op, 'time': 0,
        'message': '', 'deps': list(deps),
        'ops': [{'action': 'set', 'obj': '_root', 'key': key,
                 'value': val, 'datatype': 'int', 'pred': []}]})


def _workload(fleet, n, rounds=2):
    handles = init_docs(n, fleet)
    for r in range(rounds):
        per_doc = [[_change(f'{d:04x}' * 4, r + 1, r + 1,
                            fleet_backend.get_heads(handles[d]),
                            f'k{r}', d * 10 + r)]
                   for d in range(n)]
        handles, _ = fleet_backend.apply_changes_docs(handles, per_doc,
                                                      mirror=False)
    return handles


class TestDiskArena:
    """The tentpole mechanics: chunk bytes on mmap'd segment files under
    the RAM-resident causal index."""

    def test_park_discard_vacuum_revive_park_cycles(self, tmp_path):
        fleet = DocFleet()
        eng = StorageEngine(fleet, path=str(tmp_path / 'arena'))
        handles = _workload(fleet, 12)
        saves = [bytes(h['state'].save()) for h in handles]
        ids = eng.park(handles)
        assert all(i is not None for i in ids)
        for cycle in range(3):
            # churn: discard a third, vacuum underneath held ids
            eng.discard(ids[:4])
            assert eng.vacuums >= cycle  # dead_fraction policy may fire
            eng.vacuum_now()
            for i, save in zip(ids[4:], saves[4:]):
                assert bytes(eng.chunk(i)) == save
                assert eng.heads(i)
            # revive the rest, verify byte identity, re-park
            back = eng.revive(ids[4:])
            assert [bytes(h['state'].save()) for h in back] == saves[4:]
            assert len(eng.main) == 0
            new_ids = eng.park(back)
            assert all(i is not None for i in new_ids)
            # re-admit the first third for the next cycle
            front = eng.revive(new_ids[:0]) if False else None  # noqa
            restored = eng.ingest_chunks(saves[:4])
            ids = restored + new_ids
            saves = saves[:4] + saves[4:]

    def test_chunk_reads_are_zero_copy_views(self, tmp_path):
        fleet = DocFleet()
        eng = StorageEngine(fleet, path=str(tmp_path / 'arena'))
        handles = _workload(fleet, 3)
        saves = [bytes(h['state'].save()) for h in handles]
        ids = eng.park(handles)
        view = eng.chunk(ids[0])
        assert isinstance(view, memoryview)
        assert bytes(view) == saves[0]
        # DocChunkView parses the view in place (no chunk copy)
        dcv = DocChunkView(view)
        assert sorted(dcv.heads) == eng.heads(ids[0])
        if native.available():
            got = native.extract_changes([view])
            want = native.extract_changes([saves[0]])
            assert got == want and got[0] is not None

    def test_held_view_survives_segment_swap(self, tmp_path):
        fleet = DocFleet()
        eng = StorageEngine(fleet, path=str(tmp_path / 'arena'),
                            vacuum_dead_fraction=None)
        handles = _workload(fleet, 10)
        saves = [bytes(h['state'].save()) for h in handles]
        ids = eng.park(handles)
        held = eng.chunk(ids[7])
        held_want = saves[7]
        eng.discard(ids[:5])
        eng.vacuum_now()          # segment rewrite + atomic swap
        # the old epoch's files are unlinked, but the exported view pins
        # its mapping: reads through it stay byte-identical
        assert bytes(held) == held_want
        # and fresh reads address the NEW epoch correctly
        assert bytes(eng.chunk(ids[7])) == held_want
        del held
        eng.vacuum_now()

    def test_segment_rollover_and_reopen(self, tmp_path):
        fleet = DocFleet()
        root = str(tmp_path / 'arena')
        eng = StorageEngine(fleet, path=root, segment_bytes=1 << 10)
        handles = _workload(fleet, 16)
        saves = [bytes(h['state'].save()) for h in handles]
        ids = eng.park(handles)
        assert len(eng.main._arena.segments) > 1   # rolled over
        for i, save in zip(ids, saves):
            assert bytes(eng.chunk(i)) == save
        eng.main.sync()
        eng2 = StorageEngine.open(root, segment_bytes=1 << 10)
        assert sorted(eng2._row_of) == sorted(ids)
        for i, save in zip(ids, saves):
            assert bytes(eng2.chunk(i)) == save
            assert eng2.heads(i) == eng.heads(i)
            assert eng2.clock(i) == eng.clock(i)

    @pytest.mark.parametrize('point', ['pre_commit', 'post_manifest'])
    def test_crash_mid_vacuum_recovers_byte_identical(self, tmp_path,
                                                      point):
        fleet = DocFleet()
        root = str(tmp_path / 'arena')
        eng = StorageEngine(fleet, path=root, vacuum_dead_fraction=None)
        handles = _workload(fleet, 10)
        saves = [bytes(h['state'].save()) for h in handles]
        ids = eng.park(handles)
        eng.discard(ids[:4])
        eng.main.sync()
        eng.main._arena.fault_point = point
        with pytest.raises(RuntimeError, match='injected arena fault'):
            eng.vacuum_now()
        # pre_commit: the OLD epoch is authoritative; post_manifest: the
        # NEW one is. Either way recovery is byte-identical and complete.
        eng2 = StorageEngine.open(root)
        assert sorted(eng2._row_of) == ids[4:]
        for i in ids[4:]:
            assert bytes(eng2.chunk(i)) == saves[i]
            assert eng2.needs_sync(i, []) is True

    def test_kill_mid_vacuum_recovers(self, tmp_path):
        """Hard kill (os._exit inside the swap window) in a subprocess;
        the parent recovers the arena byte-identically."""
        root = str(tmp_path / 'arena')
        script = f'''
import sys; sys.path.insert(0, {os.path.dirname(os.path.dirname(os.path.abspath(__file__)))!r})
from tests.test_storage_tier import _workload
from automerge_tpu.fleet.backend import DocFleet
from automerge_tpu.fleet.storage import StorageEngine
fleet = DocFleet()
eng = StorageEngine(fleet, path={root!r}, vacuum_dead_fraction=None)
handles = _workload(fleet, 8)
saves = [bytes(h['state'].save()) for h in handles]
import json, pathlib
pathlib.Path({root!r} + '.expect').write_bytes(b''.join(saves[4:]))
ids = eng.park(handles)
eng.discard(ids[:4])
eng.main.sync()
eng.main._arena.fault_point = 'exit:post_manifest'
eng.vacuum_now()           # never returns
'''
        env = dict(os.environ, JAX_PLATFORMS='cpu')
        proc = subprocess.run([sys.executable, '-c', script], env=env,
                              capture_output=True, timeout=300)
        assert proc.returncode == 71, proc.stderr.decode()[-2000:]
        eng2 = StorageEngine.open(root)
        assert len(eng2._row_of) == 4
        got = b''.join(bytes(eng2.chunk(i)) for i in sorted(eng2._row_of))
        with open(root + '.expect', 'rb') as f:
            assert got == f.read()

    def test_torn_append_tail_dropped(self, tmp_path):
        root = str(tmp_path / 'arena')
        arena = SegmentArena(root)
        addr = [arena.append(i, b'payload-%d' % i * 20) for i in range(6)]
        arena.sync()
        seg_path = arena.segments[-1].path
        size = os.path.getsize(seg_path)
        arena.close()
        with open(seg_path, 'r+b') as f:
            f.truncate(size - 5)            # torn mid-frame
        arena2, records = SegmentArena.open(root)
        assert sorted(records) == list(range(5))
        for i in range(5):
            seg, off, ln = records[i]
            assert bytes(arena2.view(seg, off, ln)) == b'payload-%d' % i * 20
        # and the arena appends cleanly past the truncated tail
        seg, off, ln = arena2.append(99, b'fresh')
        assert bytes(arena2.view(seg, off, ln)) == b'fresh'
        del addr

    def test_repark_preserves_ids_on_disk(self, tmp_path):
        fleet = DocFleet()
        root = str(tmp_path / 'arena')
        eng = StorageEngine(fleet, path=root)
        handles = _workload(fleet, 4)
        saves = [bytes(h['state'].save()) for h in handles]
        ids = eng.park(handles)
        back = eng.revive(ids)
        eng.repark(back, ids)
        assert sorted(eng._row_of) == sorted(ids)
        eng.main.sync()
        # the arena frames carry the ORIGINAL ids: recovery agrees
        eng2 = StorageEngine.open(root)
        assert sorted(eng2._row_of) == sorted(ids)
        for i, save in zip(ids, saves):
            assert bytes(eng2.chunk(i)) == save

    def test_resident_vs_disk_split(self, tmp_path):
        fleet = DocFleet()
        eng = StorageEngine(fleet, path=str(tmp_path / 'arena'))
        handles = _workload(fleet, 32)
        eng.park(handles)
        stats = eng.memory_stats()
        assert stats['n_docs'] == 32
        assert stats['disk_bytes'] >= stats['chunk_bytes'] > 0
        # the chunk bytes are NOT resident: RSS pays the causal index
        assert stats['resident_bytes'] < stats['chunk_bytes'] + \
            stats['overhead_bytes']
        assert stats['resident_per_doc'] < 512, stats


class TestPrefixShortCircuit:
    """contains_head satellite: the 8-byte prefix set past the row
    threshold keeps miss probes O(1) and stays correct through discard
    churn and vacuum."""

    def test_probe_correct_above_threshold(self, monkeypatch):
        monkeypatch.setattr(MainStore, 'PREFIX_MIN_ROWS', 8)
        fleet = DocFleet()
        eng = StorageEngine(fleet)
        handles = _workload(fleet, 12)
        heads = [list(h['state'].heads) for h in handles]
        ids = eng.park(handles)
        assert eng.main._head_prefixes is None
        # misses short-circuit through the set; hits still row-scan
        assert not eng.contains_head(ids[0], 'ee' * 32)
        assert eng.main._head_prefixes is not None
        for i, hs in zip(ids, heads):
            assert eng.contains_head(i, hs[0])
            assert not eng.contains_head(i, heads[(ids.index(i) + 1)
                                                  % len(ids)][0]) or \
                hs[0] == heads[(ids.index(i) + 1) % len(ids)][0]

    def test_prefixes_survive_churn_and_vacuum(self, monkeypatch):
        monkeypatch.setattr(MainStore, 'PREFIX_MIN_ROWS', 8)
        fleet = DocFleet()
        eng = StorageEngine(fleet, vacuum_dead_fraction=None)
        handles = _workload(fleet, 16)
        heads = [list(h['state'].heads) for h in handles]
        ids = eng.park(handles)
        assert not eng.contains_head(ids[-1], 'aa' * 32)   # build set
        eng.discard(ids[:8])
        # stale prefixes from discarded rows only fall through to the
        # exact scan — never a wrong answer
        for i, hs in zip(ids[8:], heads[8:]):
            assert eng.contains_head(i, hs[0])
        eng.vacuum_now()
        assert eng.main._head_prefixes is None             # rebuilt lazily
        for i, hs in zip(ids[8:], heads[8:]):
            assert eng.contains_head(i, hs[0])
        assert not eng.contains_head(ids[8], 'bb' * 32)

    def test_additions_maintain_built_set(self, monkeypatch):
        monkeypatch.setattr(MainStore, 'PREFIX_MIN_ROWS', 4)
        fleet = DocFleet()
        eng = StorageEngine(fleet)
        handles = _workload(fleet, 6)
        ids = eng.park(handles)
        assert not eng.contains_head(ids[0], 'cc' * 32)    # build set
        more = _workload(fleet, 3)
        heads = [list(h['state'].heads) for h in more]
        more_ids = eng.park(more)
        for i, hs in zip(more_ids, heads):
            assert eng.contains_head(i, hs[0])


class _FakeDurable:
    def __init__(self):
        self.debt = {'bytes': 0, 'records': 0}
        self.compactions = 0

    def replay_debt(self):
        return dict(self.debt)

    def maybe_compact(self, force=False):
        self.compactions += 1
        self.debt = {'bytes': 0, 'records': 0}
        return True


class TestCostModel:
    """The dead_fraction byte trigger replaced by the write-amp vs
    read-latency vs replay-debt ledger, with brownout stage 2 as a
    pressure INPUT instead of a hard override."""

    def _churned_engine(self, n=16, discard=12):
        fleet = DocFleet()
        eng = StorageEngine(fleet, vacuum_dead_fraction=None)
        handles = _workload(fleet, n)
        ids = eng.ingest_chunks([bytes(h['state'].save())
                                 for h in handles])
        eng.discard(ids[:discard])
        return eng, ids

    def test_vacuum_fires_when_garbage_dominates(self):
        model = CostModel(min_garbage_bytes=1)
        eng, ids = self._churned_engine()
        assert eng.main.garbage_bytes > eng.main.chunk_bytes
        assert model.vacuum_due(eng.main, stage=0)
        eng.cost_model = model
        assert eng._maybe_vacuum()
        assert eng.vacuums == 1
        # post-vacuum: no garbage, model idles
        assert not model.vacuum_due(eng.main, stage=0)

    def test_vacuum_defers_under_brownout_stage2(self):
        model = CostModel(min_garbage_bytes=1, stage_write_penalty=1000.0)
        eng, _ids = self._churned_engine()
        before = tiering_stats()['tiering_deferred']
        assert model.vacuum_due(eng.main, stage=0)
        assert not model.vacuum_due(eng.main, stage=2)   # pressure defers
        assert tiering_stats()['tiering_deferred'] == before + 1

    def test_vacuum_still_fires_under_pressure_when_debt_overwhelms(self):
        # stage 2 raises the bar; it does not close the gate
        model = CostModel(min_garbage_bytes=1, stage_write_penalty=0.5)
        eng, _ids = self._churned_engine(n=16, discard=15)
        assert model.vacuum_due(eng.main, stage=2)

    def test_compact_decision_weighs_replay_debt(self):
        model = CostModel(min_replay_bytes=1024)
        dur = _FakeDurable()
        dur.debt = {'bytes': 512, 'records': 4}
        assert not model.compact_due(dur, stage=0)       # under floor
        dur.debt = {'bytes': 1 << 20, 'records': 5000}
        assert model.compact_due(dur, stage=0)
        # pressure defers the same debt...
        model2 = CostModel(min_replay_bytes=1024, stage_write_penalty=50.0,
                           replay_record_cost=0.0)
        assert not model2.compact_due(dur, stage=2)
        # ...until the record term overwhelms it
        dur.debt = {'bytes': 1 << 20, 'records': 10_000_000}
        model3 = CostModel(min_replay_bytes=1024, stage_write_penalty=50.0)
        assert model3.compact_due(dur, stage=2)


class TestClockDemote:
    """Auto-demote: the clock hand feeds StorageEngine.park with zero
    manual park calls; touched docs get their second chance."""

    def test_demotes_cold_docs_under_pressure(self):
        fleet = DocFleet()
        eng = StorageEngine(fleet)
        handles = _workload(fleet, 12)
        resident = {'docs': 12}
        # synthetic watermark source: pressure until <= 4 docs live
        policy = ClockDemote(eng, budget_bytes=4,
                             source=lambda: resident['docs'], batch=4)
        policy.register(handles)
        hot = handles[:3]
        parked_total = []
        for _tick in range(8):
            policy.touch(hot)          # the request path keeps 3 docs hot
            parked = policy.tick()
            parked_total.extend(parked)
            resident['docs'] = 12 - len(parked_total)
            if resident['docs'] <= 4:
                break
        assert len(parked_total) >= 8
        assert len(eng.main) == len(parked_total)
        # the hot docs survived the sweeps
        assert all(not h.get('frozen') for h in hot)
        assert tiering_stats()['tiering_demoted_docs'] >= 8

    def test_no_pressure_no_demotion(self):
        fleet = DocFleet()
        eng = StorageEngine(fleet)
        handles = _workload(fleet, 4)
        policy = ClockDemote(eng, budget_bytes=100, source=lambda: 1)
        policy.register(handles)
        assert policy.tick() == []
        assert len(eng.main) == 0


class TestTieringController:
    def test_controller_replaces_threshold_and_drives_all_planes(self):
        fleet = DocFleet()
        eng = StorageEngine(fleet)           # default dead_fraction 0.5
        dur = _FakeDurable()
        dur.debt = {'bytes': 4 << 20, 'records': 10_000}
        ctrl = TieringController(
            engine=eng, durable=dur,
            model=CostModel(min_garbage_bytes=1, min_replay_bytes=1024))
        assert eng.vacuum_dead_fraction is None          # model owns it
        assert eng.cost_model is ctrl.model
        handles = _workload(fleet, 16)
        ids = eng.ingest_chunks([bytes(h['state'].save())
                                 for h in handles])
        # discard churn between ticks: the engine's own discard hook now
        # consults the model instead of dead_fraction
        eng.discard(ids[:12])
        out = ctrl.tick(stage=0)
        assert out['compacted'] and dur.compactions == 1
        assert eng.vacuums >= 1                          # model fired

    def test_service_pump_routes_through_controller(self):
        from automerge_tpu.service import DocService
        fleet = DocFleet()
        eng = StorageEngine(fleet)
        ctrl = TieringController(engine=eng,
                                 model=CostModel(min_garbage_bytes=1))
        svc = DocService(fleet=fleet, tiering=ctrl)
        handles = _workload(fleet, 16)
        ids = eng.ingest_chunks([bytes(h['state'].save())
                                 for h in handles])
        for i in ids[:12]:
            eng.main.discard(eng._row_of.pop(i))
        assert eng.main.dead_fraction > 0.5
        svc.pump()
        assert eng.vacuums >= 1          # the pump's tick fired the model


class TestMixedBatchRouting:
    """Sync-driver satellite: one promoted host doc in a batch no longer
    reverts the round to dict probes — the fleet subset rides the
    hashindex, stragglers route classic, outputs byte-identical."""

    def _mixed_batch(self, fleet, n=4):
        from automerge_tpu.fleet.tensor_doc import SEQ_CTR_LIMIT
        handles = _workload(fleet, n, rounds=2)
        # promote doc 0 to the host engine via a fleet-unsupported op
        big = encode_change({
            'actor': 'dd' * 16, 'seq': 1, 'startOp': SEQ_CTR_LIMIT + 10,
            'time': 0, 'message': '', 'deps': list(handles[0]['heads']),
            'ops': [{'action': 'makeText', 'obj': '_root', 'key': 'deep',
                     'pred': []}]})
        handles, _ = fleet_backend.apply_changes_docs(
            handles, [[big]] + [[] for _ in handles[1:]], mirror=False)
        assert not handles[0]['state'].is_fleet
        assert all(h['state'].is_fleet for h in handles[1:])
        return handles

    def test_generate_byte_identical_with_straggler(self):
        from automerge_tpu.backend import init_sync_state
        from automerge_tpu.fleet.hashindex import set_frontier_enabled
        from automerge_tpu.fleet.sync_driver import (
            _stats as sync_stats, generate_sync_messages_docs)
        fleet = DocFleet()
        handles = self._mixed_batch(fleet)
        fleet.frontier_index()
        states = [init_sync_state() for _ in handles]
        for h, s in zip(handles, states):
            s['theirHeads'] = list(h['heads'])
            s['theirHave'] = [{'lastSync': list(h['heads']), 'bloom': b''}]
            s['theirNeed'] = []
        members0 = sync_stats['sync_frontier_member_docs']
        strag0 = sync_stats['sync_frontier_straggler_docs']
        new_states, messages = generate_sync_messages_docs(
            handles, [dict(s) for s in states])
        # the fleet subset rode the index; the promoted doc went classic
        assert sync_stats['sync_frontier_member_docs'] == members0 + 3
        assert sync_stats['sync_frontier_straggler_docs'] == strag0 + 1
        prev = set_frontier_enabled(False)
        try:
            classic_states, classic_msgs = generate_sync_messages_docs(
                handles, [dict(s) for s in states])
        finally:
            set_frontier_enabled(prev)
        assert [None if m is None else bytes(m) for m in messages] == \
            [None if m is None else bytes(m) for m in classic_msgs]
        assert new_states == classic_states

    def test_receive_mixed_batch_advances_all_docs(self):
        from automerge_tpu.backend import init_sync_state
        from automerge_tpu.backend.sync import encode_sync_message
        from automerge_tpu.columnar import decode_change_meta
        from automerge_tpu.fleet.sync_driver import (
            receive_sync_messages_docs)
        fleet = DocFleet()
        handles = self._mixed_batch(fleet)
        fleet.frontier_index()
        bufs = [_change('ee' * 16, 1, 60 + i, list(h['heads']), 'new', i)
                for i, h in enumerate(handles)]
        msgs = [encode_sync_message({
                    'heads': [decode_change_meta(b, True)['hash']],
                    'need': [], 'have': [], 'changes': [b]})
                for b in bufs]
        states = [init_sync_state() for _ in handles]
        new_handles, new_states, _p, errors = receive_sync_messages_docs(
            handles, states, msgs, on_error='quarantine')
        assert errors == [None, None, None, None]
        for i, b in enumerate(bufs):
            want = [decode_change_meta(b, True)['hash']]
            assert new_states[i]['sharedHeads'] == want


@pytest.mark.slow
def test_disk_tier_million_docs_resident_budget(tmp_path):
    """1M parked docs on the DISK arena: the RSS cost is the causal
    index (~100-130 B/doc reserved), the chunk bytes are a disk number.
    Distinct causal rows per doc, shared chunk payloads (the arena
    appends each one, so disk grows per doc — the honest part — while
    the header decode is precomputed once per distinct chunk)."""
    import resource
    n = 1_000_000
    distinct = 2048
    fleet = DocFleet()
    eng = StorageEngine(fleet, path=str(tmp_path / 'arena'))
    handles = init_docs(distinct, fleet)
    per_doc = [[_change(f'{d % 128:04x}' * 4, 1, 1, [], f'k{d}', d)]
               for d in range(distinct)]
    handles, _ = fleet_backend.apply_changes_docs(handles, per_doc,
                                                  mirror=False)
    chunks = [bytes(h['state'].save()) for h in handles]
    views = [DocChunkView(c) for c in chunks]
    rows = [(v.heads, v.clock, v.max_op, v.n_changes) for v in views]
    rss0 = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss  # KiB
    eng.main.reserve(n)
    for i in range(0, n, distinct):
        k = min(distinct, n - i)
        eng.ingest_chunks(chunks[:k], rows=rows[:k])
    assert len(eng.main) == n
    stats = eng.memory_stats()
    assert stats['resident_per_doc'] < 256, stats
    assert stats['disk_bytes'] > 100 << 20          # chunks went to disk
    rss1 = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    grew_kib = rss1 - rss0
    # the ceiling a 10M-doc host extrapolates from: resident lanes only
    assert grew_kib < 300 << 10, f'RSS grew {grew_kib} KiB'
    # spot-check far-end reads and a revive round trip off the map
    assert eng.n_changes(n - 1) == 1
    back = eng.revive([n - 1])
    assert bytes(back[0]['state'].save()) == chunks[(n % distinct or
                                                     distinct) - 1]
