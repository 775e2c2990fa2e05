"""Fleet-scale sync fabric (ISSUE-16): per-peer sentHashes as peer-spaces
in the shared frontier table, fused generate/receive dispatches across
every live link, and the satellites that ride the same plane.

The load-bearing contracts pinned here:

- Fused multi-peer rounds are BYTE-IDENTICAL to the classic per-peer
  loop — across host backends, lww fleet docs, and exact-device fleet
  docs, including a mid-round disconnect/reconnect (released peer-space,
  fresh space id, full resend) and a promoted host doc riding a mixed
  batch.
- Dispatch counts per round are FLAT in the link count: 16 links and
  1024 links cost the same number of hashindex + Bloom kernel launches.
- Disconnect/reset release their peer-space everywhere the sync states
  die (service close/release/reset, cluster pair reset) — space ids are
  never reused, so a reconnecting peer can never inherit its
  predecessor's sent set.
- The batched SYNC path feeds doc recency into the ClockDemote ring
  (sync-hot docs are not demotion fodder), and `max_chain` escalation
  routes through the CostModel ledger with flight-recorded verdict
  flips.
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from automerge_tpu import backend as Backend                     # noqa: E402
from automerge_tpu import native                                 # noqa: E402
from automerge_tpu.backend import init_sync_state                # noqa: E402
from automerge_tpu.backend.sync import (                         # noqa: E402
    generate_sync_message, receive_sync_message)
from automerge_tpu.columnar import (                             # noqa: E402
    decode_change_meta, encode_change)
from automerge_tpu.fleet import backend as fleet_backend         # noqa: E402
from automerge_tpu.fleet import bloom as fleet_bloom             # noqa: E402
from automerge_tpu.fleet import hashindex                        # noqa: E402
from automerge_tpu.fleet.backend import (                        # noqa: E402
    DocFleet, apply_changes_docs, init_docs)
from automerge_tpu.fleet.hashindex import (                      # noqa: E402
    PeerSentSet, release_sync_state)
from automerge_tpu.fleet.sync_driver import (                    # noqa: E402
    generate_sync_messages_docs, receive_sync_messages_docs)

needs_native = pytest.mark.skipif(
    not native.available(), reason='fleet modes ride the turbo path')


def _change(actor, seq, start_op, deps, key, val):
    return encode_change({
        'actor': actor, 'seq': seq, 'startOp': start_op, 'time': 0,
        'message': '', 'deps': list(deps),
        'ops': [{'action': 'set', 'obj': '_root', 'key': key,
                 'value': val, 'datatype': 'int', 'pred': []}]})


def _doc_change_rows(n, per_doc=2):
    """Per-doc linear change chains as raw bytes — both universes of a
    differential run are built from the SAME bytes."""
    rows = []
    for i in range(n):
        deps, row = [], []
        for s in range(1, per_doc + 1):
            buf = _change(f'{i:02x}' * 16, s, s, deps, f'd{i}', s)
            deps = [decode_change_meta(buf, True)['hash']]
            row.append(buf)
        rows.append(row)
    return rows


def _peer_change_rows(n, k):
    """One private root change per (doc, peer) link — traffic flows both
    directions of every link."""
    return [[[_change(f'{0xa0 + i:02x}{j:02x}' * 8, 1, 1, [],
                      f'p{i}_{j}', 100 * i + j)]
             for j in range(k)] for i in range(n)]


def _host_doc(change_rows):
    b = Backend.init()
    b, _ = Backend.apply_changes(b, list(change_rows))
    return b


def _build_universe(mode, doc_rows, peer_rows, fused):
    """One complete sync universe: server docs (host backends or fleet
    handles), per-link sync states, and host peer replicas."""
    if mode == 'host':
        docs = [_host_doc(row) for row in doc_rows]
    else:
        fleet = DocFleet(exact_device=(mode == 'exact'))
        docs = init_docs(len(doc_rows), fleet)
        docs, _ = apply_changes_docs(docs, doc_rows, mirror=False)
        if fused:
            fleet.frontier_index(device_min=1)   # force the device table
    n, k = len(doc_rows), len(peer_rows[0])
    states = [[init_sync_state() for _ in range(k)] for _ in range(n)]
    peers = [[_host_doc(peer_rows[i][j]) for j in range(k)]
             for i in range(n)]
    peer_states = [[init_sync_state() for _ in range(k)]
                   for _ in range(n)]
    return docs, states, peers, peer_states


def _drive_rounds(docs, states, peers, peer_states, fused, rounds,
                  on_round=None):
    """Drive `rounds` full sync rounds over every (doc, peer) link;
    fused=True batches the server side exactly like the exchange fabric
    (one generate dispatch set per round, receive in transpose waves
    over distinct dst docs); fused=False is the classic per-peer loop.
    Returns the byte transcript of every server and peer message."""
    n, k = len(docs), len(peers[0])
    transcript = []
    for r in range(rounds):
        if on_round is not None:
            on_round(r, states, peers, peer_states)
        # --- server generate (the fabric's fused half) ---
        if fused:
            flat_docs = [docs[i] for i in range(n) for _ in range(k)]
            flat_states = [states[i][j]
                           for i in range(n) for j in range(k)]
            new_states, flat_msgs = generate_sync_messages_docs(
                flat_docs, flat_states)
            out = [[None] * k for _ in range(n)]
            for idx in range(n * k):
                i, j = divmod(idx, k)
                states[i][j] = new_states[idx]
                out[i][j] = flat_msgs[idx]
        else:
            out = [[None] * k for _ in range(n)]
            for i in range(n):
                for j in range(k):
                    states[i][j], out[i][j] = generate_sync_message(
                        docs[i], states[i][j])
        transcript.append([[None if m is None else bytes(m)
                            for m in row] for row in out])
        # --- peers receive + reply (classic host loop, both universes) ---
        replies = [[None] * k for _ in range(n)]
        for i in range(n):
            for j in range(k):
                if out[i][j] is not None:
                    peers[i][j], peer_states[i][j], _ = \
                        receive_sync_message(peers[i][j],
                                             peer_states[i][j], out[i][j])
                peer_states[i][j], replies[i][j] = generate_sync_message(
                    peers[i][j], peer_states[i][j])
        transcript.append([[None if m is None else bytes(m)
                            for m in row] for row in replies])
        # --- server receive ---
        if fused:
            queues = {i: [(j, replies[i][j]) for j in range(k)
                          if replies[i][j] is not None]
                      for i in range(n)}
            queues = {i: q for i, q in queues.items() if q}
            while queues:
                wave = [(i, q.pop(0)) for i, q in queues.items()]
                new_docs, new_states, _p = receive_sync_messages_docs(
                    [docs[i] for i, _ in wave],
                    [states[i][j] for i, (j, _m) in wave],
                    [m for _i, (_j, m) in wave])
                for (i, (j, _m)), doc, state in zip(wave, new_docs,
                                                    new_states):
                    docs[i] = doc
                    states[i][j] = state
                queues = {i: q for i, q in queues.items() if q}
        else:
            for i in range(n):
                for j in range(k):
                    if replies[i][j] is not None:
                        docs[i], states[i][j], _ = receive_sync_message(
                            docs[i], states[i][j], replies[i][j])
    return transcript


def _heads(doc):
    if isinstance(doc, dict) and 'heads' in doc:
        return sorted(doc['heads'])
    return sorted(Backend.get_heads(doc))


class TestFusedByteIdentity:
    """Tentpole contract: the fused fabric is byte-identical on the wire
    to the classic per-peer protocol loop, in every engine mode."""

    @pytest.mark.parametrize('mode', ['host', 'lww', 'exact'])
    def test_multi_peer_rounds_with_mid_round_disconnect(self, mode):
        if mode != 'host' and not native.available():
            pytest.skip('fleet modes ride the turbo path')
        n, k, rounds = 3, 3, 6
        doc_rows = _doc_change_rows(n)
        peer_rows = _peer_change_rows(n, k)
        released = {}

        def disconnect(r, states, peers, peer_states):
            # round 3: link (0, 1) drops mid-conversation and the peer
            # comes back having LOST its replica — both ends handshake
            # from fresh states and the server must resend everything
            # through a brand-new peer-space
            if r != 3:
                return
            old = states[0][1].get('sentHashes')
            if isinstance(old, PeerSentSet):
                released['ps'] = old
            release_sync_state(states[0][1])
            states[0][1] = init_sync_state()
            peers[0][1] = Backend.init()
            peer_states[0][1] = init_sync_state()

        fused_u = _build_universe(mode, doc_rows, peer_rows, fused=True)
        classic_u = _build_universe(mode, doc_rows, peer_rows, fused=False)
        t_fused = _drive_rounds(*fused_u, fused=True, rounds=rounds,
                                on_round=disconnect)
        t_classic = _drive_rounds(*classic_u, fused=False, rounds=rounds,
                                  on_round=disconnect)
        assert t_fused == t_classic     # every message, every round
        docs_f, states_f, peers_f, _ = fused_u
        docs_c, _, peers_c, _ = classic_u
        for i in range(n):
            assert _heads(docs_f[i]) == _heads(docs_c[i])
            for j in range(k):
                assert _heads(peers_f[i][j]) == _heads(peers_c[i][j])
                assert _heads(peers_f[i][j]) == _heads(docs_f[i])
        if mode == 'host':
            return
        # every member link that sent changes promoted to a peer-space,
        # and the dropped link's old space died with the disconnect —
        # its reconnect re-promoted into a FRESH (higher) space id
        sent = [states_f[i][j]['sentHashes']
                for i in range(n) for j in range(k)]
        assert all(isinstance(s, PeerSentSet) for s in sent)
        assert len({s.sid for s in sent}) == n * k   # one space per link
        old = released['ps']
        assert not old.alive
        assert not old.table._live[old.sid]
        assert states_f[0][1]['sentHashes'].sid > old.sid
        # converged fleet twins save byte-identically
        for df, dc in zip(docs_f, docs_c):
            assert bytes(df['state'].save()) == bytes(dc['state'].save())

    @needs_native
    def test_promoted_host_doc_rides_mixed_batch(self):
        """One doc promoted OFF the fleet (SEQ_CTR_LIMIT-overflow op) rides
        the same fused multi-peer round as its fleet neighbours —
        byte-identical to the classic loop, fleet links still promote
        their sentHashes, the straggler keeps a plain set."""
        from automerge_tpu.fleet.tensor_doc import SEQ_CTR_LIMIT
        n, k = 3, 2
        doc_rows = _doc_change_rows(n)
        peer_rows = _peer_change_rows(n, k)
        universes = []
        for fused in (True, False):
            docs, states, peers, peer_states = _build_universe(
                'lww', doc_rows, peer_rows, fused)
            big = encode_change({
                'actor': 'dd' * 16, 'seq': 1, 'startOp': SEQ_CTR_LIMIT + 10,
                'time': 0, 'message': '', 'deps': list(docs[0]['heads']),
                'ops': [{'action': 'makeText', 'obj': '_root',
                         'key': 'deep', 'pred': []}]})
            docs, _ = apply_changes_docs(
                docs, [[big]] + [[] for _ in docs[1:]], mirror=False)
            assert not docs[0]['state'].is_fleet
            assert all(d['state'].is_fleet for d in docs[1:])
            universes.append((docs, states, peers, peer_states))
        t_fused = _drive_rounds(*universes[0], fused=True, rounds=5)
        t_classic = _drive_rounds(*universes[1], fused=False, rounds=5)
        assert t_fused == t_classic
        docs_f, states_f, _peers, _ps = universes[0]
        for j in range(k):
            assert isinstance(states_f[0][j]['sentHashes'], set)
            assert isinstance(states_f[1][j]['sentHashes'], PeerSentSet)


@needs_native
class TestDispatchPins:
    def test_generate_round_dispatches_flat_16_vs_1024_links(self):
        """The fabric's O(1)-dispatch property: a steady-state generate
        round over N links costs the SAME number of hashindex + Bloom
        kernel launches at 16 links as at 1024."""
        deltas = {}
        for n_links in (16, 1024):
            fleet = DocFleet()
            handles = init_docs(1, fleet)
            rows = _doc_change_rows(1, per_doc=3)
            handles, _ = apply_changes_docs(handles, rows, mirror=False)
            fleet.frontier_index(device_min=1)
            # every link's peer solicits a full resend (empty bloom):
            # the cold round sends changes on all links, staging and
            # promoting each link's sentHashes to a peer-space
            states = []
            for _ in range(n_links):
                s = init_sync_state()
                s['theirHeads'] = []
                s['theirHave'] = [{'lastSync': [], 'bloom': b''}]
                s['theirNeed'] = []
                states.append(s)
            flat = [handles[0]] * n_links
            states, msgs = generate_sync_messages_docs(flat, states)
            assert all(m is not None for m in msgs)
            assert all(isinstance(s['sentHashes'], PeerSentSet)
                       for s in states)
            # round 2 (steady state): the sent filter rides the FUSED
            # peer-space probe across all links at once
            h0 = hashindex.dispatch_count()
            b0 = fleet_bloom.dispatch_count()
            states, msgs = generate_sync_messages_docs(flat, states)
            deltas[n_links] = (hashindex.dispatch_count() - h0,
                               fleet_bloom.dispatch_count() - b0)
            assert all(m is not None for m in msgs)
        assert deltas[16] == deltas[1024], \
            f'dispatches scale with links: {deltas}'
        assert sum(deltas[16]) <= 8     # a round is a handful, not O(links)

    def test_probe_window_env_and_setter(self):
        from automerge_tpu.fleet.hashindex import (probe_window,
                                                   set_probe_window)
        base = probe_window()
        prev = set_probe_window(8)
        try:
            assert prev == base
            assert probe_window() == 8
            # clamped to the legal range
            set_probe_window(10 ** 9)
            assert probe_window() == 1024
            # correctness is window-independent
            for width in (1, 8, 64):
                set_probe_window(width)
                ix = hashindex.HashIndex(capacity=8, device_min=1)
                sid = ix.new_space()
                import hashlib
                keys = [hashlib.sha256(bytes([i])).hexdigest()
                        for i in range(12)]
                ix.insert(sid, keys[:9])
                got = ix.probe(sid, keys).tolist()
                assert got == [True] * 9 + [False] * 3
        finally:
            set_probe_window(base)


@needs_native
class TestReleaseWiring:
    """Every path that drops a sync state hands its peer-space back."""

    def _serve_until_promoted(self, svc, session, client, state,
                              max_rounds=8):
        for _ in range(max_rounds):
            state, msg = generate_sync_message(client, state)
            t = svc.submit(session, 'sync', msg)
            svc.pump()
            assert t.status == 'ok'
            if t.result is not None:
                client, state, _ = receive_sync_message(
                    client, state, t.result)
            if isinstance(session.sync_state.get('sentHashes'),
                          PeerSentSet):
                return client, state
        pytest.fail('session sentHashes never promoted to a peer-space')

    def _service(self):
        from automerge_tpu.service import DocService
        fleet = DocFleet(doc_capacity=8, key_capacity=64)
        svc = DocService(fleet=fleet, tenant_rate=10_000.0,
                         tenant_burst=1000.0)
        fleet.frontier_index(device_min=1)
        return svc, fleet

    def test_service_reset_and_close_release_peer_spaces(self):
        svc, fleet = self._service()
        table = fleet.frontier_index().table
        session = svc.open_session('t0')
        t = svc.submit(session, 'apply',
                       [_change('aa' * 16, 1, 1, [], 'k', 7)])
        svc.pump()
        assert t.status == 'ok'
        client, state = self._serve_until_promoted(
            svc, session, Backend.init(), init_sync_state())
        old = session.sync_state['sentHashes']
        old_sid = old.sid
        # client reconnect with reset=True: fresh handshake, the old
        # link's space handed back NOW (not at GC)
        state = init_sync_state()
        state, msg = generate_sync_message(client, state)
        t = svc.submit(session, 'sync', msg, reset=True)
        svc.pump()
        assert t.status == 'ok'
        assert not old.alive and not table._live[old_sid]
        assert not isinstance(session.sync_state.get('sentHashes'),
                              PeerSentSet) or \
            session.sync_state['sentHashes'].sid > old_sid
        # new server-side content so the reconnected link sends again
        # (lazy promotion: a quiet link never re-promotes) — then
        # close_session releases whatever the session holds
        t = svc.submit(session, 'apply',
                       [_change('aa' * 16, 2, 2,
                                list(session.handle['heads']), 'k', 8)])
        svc.pump()
        assert t.status == 'ok'
        client2, state2 = self._serve_until_promoted(
            svc, session, client, state)
        ps2 = session.sync_state['sentHashes']
        svc.close_session(session)
        assert not ps2.alive and not table._live[ps2.sid]

    def test_cluster_pair_reset_releases_both_spaces(self):
        from automerge_tpu.shard.cluster import _Tenant
        ix = hashindex.HashIndex(capacity=16, device_min=1)
        a = PeerSentSet(ix)
        b = PeerSentSet(ix)
        a.add('ab' * 32)
        a.flush()

        class _Rec:
            pass

        rec = _Rec()
        rec.state_home = dict(init_sync_state(), sentHashes=a)
        rec.state_rep = dict(init_sync_state(), sentHashes=b)
        _Tenant._reset_pair(rec)
        assert not a.alive and not b.alive
        assert not ix._live[a.sid] and not ix._live[b.sid]
        assert isinstance(rec.state_home['sentHashes'], set)
        assert isinstance(rec.state_rep['sentHashes'], set)
        assert rec.inbox_home == [] and rec.inbox_rep == []

    def test_sync_serve_touches_demote_ring(self):
        """Satellite: the batched SYNC path stamps access recency, so a
        read-mostly doc answering handshakes is not demotion fodder."""
        from automerge_tpu.service import DocService

        class _FakeDemote:
            def __init__(self):
                self.registered, self.touched = [], []

            def register(self, handles):
                self.registered.extend(handles)

            def touch(self, handles):
                self.touched.extend(handles)

        class _FakeTiering:
            demote = None

            def tick(self, **kw):
                pass

        tiering = _FakeTiering()
        tiering.demote = _FakeDemote()
        svc = DocService(fleet=DocFleet(doc_capacity=8, key_capacity=64),
                         tiering=tiering, tenant_rate=10_000.0,
                         tenant_burst=1000.0)
        session = svc.open_session('t0')
        t = svc.submit(session, 'apply',
                       [_change('ab' * 16, 1, 1, [], 'k', 1)])
        svc.pump()
        assert t.status == 'ok'
        state, msg = generate_sync_message(Backend.init(),
                                           init_sync_state())
        t = svc.submit(session, 'sync', msg)
        svc.pump()
        assert t.status == 'ok'
        assert session.handle in tiering.demote.registered
        assert session.handle in tiering.demote.touched


class _StubDurable:
    def __init__(self, segments, tail_bytes, base):
        self._debt = {'segments': segments, 'bytes': tail_bytes}
        self._base = base

    def chain_debt(self):
        return dict(self._debt)

    def base_bytes(self):
        return self._base


class TestChainEscalationLedger:
    """Satellite: `max_chain` escalation routes through the CostModel —
    stitch debt (tail bytes + per-segment overhead) vs full-rewrite
    cost, pressure-scaled, verdict flips flight-recorded."""

    def _model(self):
        from automerge_tpu.fleet.tiering import CostModel
        return CostModel()

    def test_empty_chain_never_fires(self):
        m = self._model()
        assert m.chain_escalate_due(_StubDurable(0, 0, 1 << 20)) is False

    def test_stitch_debt_dominating_rewrite_fires(self):
        m = self._model()
        # tail ~= base: benefit 2x bytes + per-segment overhead beats
        # the (base + tail) rewrite
        dur = _StubDurable(4, 1 << 20, 1 << 20)
        assert m.chain_escalate_due(dur) is True

    def test_huge_base_defers_escalation(self):
        m = self._model()
        # one tiny segment over a huge base: rewriting everything to
        # retire 1KB of stitch debt never pays
        dur = _StubDurable(1, 1 << 10, 100 << 20)
        assert m.chain_escalate_due(dur) is False

    def test_many_tiny_segments_fire_on_stitch_overhead(self):
        m = self._model()
        # bytes alone would not justify it; the per-segment open/
        # validate overhead does
        dur = _StubDurable(32, 16 << 10, 64 << 10)
        assert m.chain_escalate_due(dur) is True

    def test_pressure_defers_and_flight_records_the_flip(self):
        from automerge_tpu.observability import recorder
        m = self._model()
        dur = _StubDurable(4, 1 << 20, 1 << 20)
        assert m.chain_escalate_due(dur, stage=0) is True
        recorder.clear_events()
        # stage 2: the write-cost bar rises ~8x; same debt now defers,
        # and the verdict FLIP lands in the flight ring
        assert m.chain_escalate_due(dur, stage=2) is False
        evs = [e for e in recorder.recent_events()
               if e['kind'] == 'tiering' and e.get('action') == 'chain']
        assert evs and evs[-1]['verdict'] == 'defer'
        assert evs[-1]['stage'] == 2

    def test_compact_escalates_early_when_ledger_says_so(self, tmp_path):
        """Integration: a DurableFleet whose attached model deems the
        chain's stitch debt due checkpoints EARLY (chain collapses to a
        fresh base) while max_chain stays the hard backstop."""
        from automerge_tpu.fleet.durability import DurableFleet
        path = str(tmp_path / 'dur')
        mgr = DurableFleet(path, max_chain=8)

        def grow(handles, round_no):
            per_doc = [[_change(f'{i:02x}' * 16, round_no, round_no,
                                fleet_backend.get_heads(h),
                                'k', round_no)]
                       for i, h in enumerate(handles)]
            out, _patches, errors = mgr.apply_changes(handles, per_doc)
            assert not any(errors)
            return out

        handles = mgr.init_docs(2)
        handles = grow(handles, 1)
        assert mgr.maybe_compact(force=True)        # cuts the base
        handles = grow(handles, 2)
        assert mgr.maybe_compact(force=True)        # first segment
        assert len(mgr.chain) == 2

        class _Always:
            def chain_escalate_due(self, durable, stage=0):
                return True

        mgr.cost_model = _Always()
        handles = grow(handles, 3)
        assert mgr.maybe_compact(force=True)
        assert len(mgr.chain) == 1      # escalated well before max_chain

        class _Never:
            def chain_escalate_due(self, durable, stage=0):
                return False

        mgr.cost_model = _Never()
        for r in range(4, 7):
            handles = grow(handles, r)
            mgr.maybe_compact(force=True)
        assert len(mgr.chain) == 4      # ledger says wait: chain grows
        mgr.close()
