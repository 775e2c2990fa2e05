"""Batched fleet sync driver: differential equality with the host
per-document protocol and single-dispatch filter batching
(fleet/sync_driver.py; ref backend/sync.js:234-306)."""

import pytest

import automerge_tpu as A
from automerge_tpu import backend as Backend
from automerge_tpu.backend import init_sync_state
from automerge_tpu.backend.sync import (
    generate_sync_message, receive_sync_message)
from automerge_tpu.fleet import bloom as fleet_bloom
from automerge_tpu.fleet.sync_driver import (
    generate_sync_messages_docs, receive_sync_messages_docs)
from automerge_tpu.frontend import get_backend_state


def _backend_of(doc):
    return get_backend_state(doc)


def _make_pairs(n_docs, rounds=3):
    """n_docs local/remote doc pairs with divergent histories."""
    pairs = []
    for d in range(n_docs):
        a = A.init(f'{d:02x}' * 4 + 'aa')
        for i in range(1 + d % 3):
            a = A.change(a, {'time': 0},
                         lambda doc, i=i: doc.update({'x': i}))
        b = A.merge(A.init(f'{d:02x}' * 4 + 'bb'), a) if d % 2 else \
            A.init(f'{d:02x}' * 4 + 'bb')
        for i in range(d % 4):
            b = A.change(b, {'time': 0},
                         lambda doc, i=i: doc.update({'y': i}))
        pairs.append((a, b))
    return pairs


class TestDifferentialEquality:
    def test_messages_byte_identical_to_host(self):
        pairs = _make_pairs(12)
        batch_sa = [init_sync_state() for _ in pairs]
        batch_sb = [init_sync_state() for _ in pairs]
        host_sa = [init_sync_state() for _ in pairs]
        host_sb = [init_sync_state() for _ in pairs]
        # Backend handles freeze on use: host and batch drivers need their
        # own copies of every document
        host_a = [Backend.clone(_backend_of(a)) for a, _ in pairs]
        host_b = [Backend.clone(_backend_of(b)) for _, b in pairs]
        batch_a = [Backend.clone(_backend_of(a)) for a, _ in pairs]
        batch_b = [Backend.clone(_backend_of(b)) for _, b in pairs]

        for round_no in range(6):
            batch_sa, msgs_ab = generate_sync_messages_docs(batch_a, batch_sa)
            host_out = [generate_sync_message(doc, s)
                        for doc, s in zip(host_a, host_sa)]
            host_sa = [o[0] for o in host_out]
            host_msgs = [o[1] for o in host_out]
            for i in range(len(pairs)):
                assert (msgs_ab[i] is None) == (host_msgs[i] is None), \
                    f'round {round_no} doc {i} presence'
                if msgs_ab[i] is not None:
                    assert bytes(msgs_ab[i]) == bytes(host_msgs[i]), \
                        f'round {round_no} doc {i} bytes'

            # deliver a->b on both drivers
            batch_b, batch_sb, _ = receive_sync_messages_docs(
                batch_b, batch_sb,
                [m for m in msgs_ab])
            for i, m in enumerate(host_msgs):
                if m is not None:
                    host_b[i], host_sb[i], _ = receive_sync_message(
                        host_b[i], host_sb[i], m)

            # and the reply direction b->a
            batch_sb, msgs_ba = generate_sync_messages_docs(batch_b, batch_sb)
            host_out = [generate_sync_message(doc, s)
                        for doc, s in zip(host_b, host_sb)]
            host_sb = [o[0] for o in host_out]
            host_msgs_ba = [o[1] for o in host_out]
            for i in range(len(pairs)):
                assert (msgs_ba[i] is None) == (host_msgs_ba[i] is None)
                if msgs_ba[i] is not None:
                    assert bytes(msgs_ba[i]) == bytes(host_msgs_ba[i]), \
                        f'round {round_no} reply doc {i} bytes'
            batch_a, batch_sa, _ = receive_sync_messages_docs(
                batch_a, batch_sa, [m for m in msgs_ba])
            for i, m in enumerate(host_msgs_ba):
                if m is not None:
                    host_a[i], host_sa[i], _ = receive_sync_message(
                        host_a[i], host_sa[i], m)

        # Everyone converged
        for i in range(len(pairs)):
            assert Backend.get_heads(batch_a[i]) == \
                Backend.get_heads(batch_b[i]), f'doc {i} diverged'
            assert Backend.get_heads(batch_a[i]) == \
                Backend.get_heads(host_a[i])

    def _count_dispatches(self, monkeypatch):
        calls = {'build': 0, 'probe': 0}
        orig_build = fleet_bloom._build_flat_packed
        orig_probe = fleet_bloom._probe_flat_packed

        def count_build(*args):
            calls['build'] += 1
            return orig_build(*args)

        def count_probe(*args):
            calls['probe'] += 1
            return orig_probe(*args)
        monkeypatch.setattr(fleet_bloom, '_build_flat_packed', count_build)
        monkeypatch.setattr(fleet_bloom, '_probe_flat_packed', count_probe)
        return calls

    def test_two_filter_dispatches_per_generate(self, monkeypatch):
        # Uniform histories: every filter lands in one size class, so a
        # whole generate round is exactly one build (and, once peer filters
        # have arrived, exactly one probe) dispatch
        pairs = []
        for d in range(10):
            a = A.init(f'{d:02x}' * 4 + 'aa')
            b = A.init(f'{d:02x}' * 4 + 'bb')
            for i in range(3):
                a = A.change(a, {'time': 0},
                             lambda doc, i=i: doc.update({'x': i}))
                b = A.change(b, {'time': 0},
                             lambda doc, i=i: doc.update({'y': i}))
            pairs.append((a, b))
        a_docs = [_backend_of(a) for a, _ in pairs]
        b_docs = [_backend_of(b) for _, b in pairs]
        sa = [init_sync_state() for _ in pairs]
        sb = [init_sync_state() for _ in pairs]
        calls = self._count_dispatches(monkeypatch)

        # Round 1: both sides generate (build only: no peer filters yet)
        sa, msgs = generate_sync_messages_docs(a_docs, sa)
        assert calls['build'] == 1
        assert calls['probe'] == 0
        b_docs, sb, _ = receive_sync_messages_docs(b_docs, sb, msgs)
        # Round 2: the replies probe the received filters in ONE dispatch
        calls['build'] = calls['probe'] = 0
        sb, msgs2 = generate_sync_messages_docs(b_docs, sb)
        assert calls['probe'] == 1

    def test_skewed_filter_sizes_one_dispatch(self, monkeypatch):
        # One high-churn peer must neither inflate every row to its width
        # (the flat packed layout gives each filter its exact byte span)
        # nor split the batch into extra dispatches: skew or not, the whole
        # build is ONE device dispatch, and every filter stays
        # byte-identical to the host BloomFilter
        import hashlib
        from automerge_tpu.fleet.bloom import build_bloom_filters_batch
        from automerge_tpu.backend.sync import BloomFilter
        calls = self._count_dispatches(monkeypatch)
        hash_lists = [[hashlib.sha256(f'{i}:{j}'.encode()).hexdigest()
                       for j in range(3)] for i in range(20)]
        hash_lists.append([hashlib.sha256(f'big:{j}'.encode()).hexdigest()
                           for j in range(500)])
        built = build_bloom_filters_batch(hash_lists)
        assert calls['build'] == 1
        for row, fb in zip(hash_lists, built):
            assert bytes(fb) == bytes(BloomFilter(row).bytes)

    def test_skewed_probe_one_dispatch(self, monkeypatch):
        # Probe side of the same guarantee: filters of wildly different
        # sizes probe in ONE gather dispatch through the flat byte layout
        import hashlib
        from automerge_tpu.fleet.bloom import (
            build_bloom_filters_batch, probe_bloom_filters_batch)
        calls = self._count_dispatches(monkeypatch)
        sizes = [1, 3, 40, 500, 7]
        hash_lists = [[hashlib.sha256(f'{i}:{j}'.encode()).hexdigest()
                       for j in range(n)] for i, n in enumerate(sizes)]
        built = build_bloom_filters_batch(hash_lists)
        calls['build'] = calls['probe'] = 0
        hits = probe_bloom_filters_batch(built, hash_lists)
        assert calls['probe'] == 1
        # a filter contains everything it was built over (no false negatives)
        assert all(all(row) for row in hits)
        # and cross-probing mostly misses (bit-layout sanity, not just True)
        cross = probe_bloom_filters_batch(built[1:] + built[:1], hash_lists)
        assert not all(all(row) for row in cross)

    def test_generate_round_dispatches_size_independent(self):
        # THE O(1)-dispatch contract for sync rounds: a generate round over
        # 4x the peers issues exactly the same number of device dispatches
        # (2: one flat Bloom build, one flat probe), observed through the
        # observability roll-up
        from automerge_tpu.observability import dispatch_counts
        counts = {}
        for n in (6, 24):
            pairs = _make_pairs(n)
            docs = [_backend_of(a) for a, _ in pairs]
            states = [init_sync_state() for _ in docs]
            # prime theirHave/theirNeed so the probe phase runs too
            states, msgs = generate_sync_messages_docs(docs, states)
            docs_b = [_backend_of(b) for _, b in pairs]
            states_b = [init_sync_state() for _ in docs]
            docs_b, states_b, _ = receive_sync_messages_docs(
                docs_b, states_b, msgs)
            states_b, replies = generate_sync_messages_docs(docs_b, states_b)
            docs, states, _ = receive_sync_messages_docs(docs, states,
                                                         replies)
            before = dispatch_counts()
            states, msgs = generate_sync_messages_docs(docs, states)
            after = dispatch_counts()
            counts[n] = after['total'] - before['total']
            assert after['bloom'] - before['bloom'] == counts[n]
        assert counts[6] == counts[24] == 2, counts

    def test_empty_and_missing_messages(self):
        pairs = _make_pairs(4)
        docs = [_backend_of(a) for a, _ in pairs]
        states = [init_sync_state() for _ in pairs]
        out_docs, out_states, patches = receive_sync_messages_docs(
            docs, states, [None] * len(pairs))
        assert out_docs == docs
        assert out_states == states
        assert patches == [None] * len(pairs)


class TestParkedGate:
    """The StorageEngine.needs_sync parked gate (round-13 satellite): a
    sync round over a mixed live/parked population revives ONLY the docs
    a peer actually needs; quiet converged handshakes are answered
    compute-on-compressed with the doc still parked."""

    def _converged_population(self, n=6):
        """n (fleet doc, host peer) pairs driven to sync quiescence, plus
        the sync states of both sides."""
        from automerge_tpu.columnar import encode_change, decode_change_meta
        from automerge_tpu.fleet import backend as fleet_backend
        from automerge_tpu.fleet.backend import DocFleet, init_docs

        fleet = DocFleet()
        docs = init_docs(n, fleet)
        heads = [[] for _ in range(n)]
        for r in range(3):
            per_doc = []
            for d in range(n):
                buf = encode_change({
                    'actor': f'{d:04x}' * 4, 'seq': r + 1,
                    'startOp': r + 1, 'time': 0, 'message': '',
                    'deps': heads[d],
                    'ops': [{'action': 'set', 'obj': '_root',
                             'key': f'k{r}', 'value': d * 10 + r,
                             'datatype': 'int', 'pred': []}]})
                heads[d] = [decode_change_meta(buf, True)['hash']]
                per_doc.append([buf])
            docs, _ = fleet_backend.apply_changes_docs(docs, per_doc,
                                                       mirror=False)
        peers = [Backend.init() for _ in range(n)]
        ls = [init_sync_state() for _ in range(n)]
        ps = [init_sync_state() for _ in range(n)]
        for _ in range(10):
            traffic = False
            ls, msgs = generate_sync_messages_docs(docs, ls)
            for i, m in enumerate(msgs):
                if m is not None:
                    traffic = True
                    peers[i], ps[i], _ = Backend.receive_sync_message(
                        peers[i], ps[i], m)
            replies = []
            for i in range(n):
                ps[i], back = generate_sync_message(peers[i], ps[i])
                replies.append(back)
                if back is not None:
                    traffic = True
            docs, ls, _ = receive_sync_messages_docs(docs, ls, replies)
            if not traffic:
                break
        for i in range(n):
            assert Backend.get_heads(peers[i]) == \
                sorted(docs[i]['state'].heads)
        return fleet, docs, peers, ls, ps

    def test_quiet_parked_docs_stay_parked(self):
        from automerge_tpu.fleet.storage import StorageEngine
        from automerge_tpu.fleet.sync_driver import (
            generate_sync_messages_mixed, receive_sync_messages_mixed)
        from automerge_tpu.observability import health_counts

        fleet, docs, peers, ls, ps = self._converged_population()
        eng = StorageEngine(fleet)
        ids = eng.park(docs)
        assert all(i is not None for i in ids)
        before = health_counts()['storage_parked_syncs_skipped']
        out_docs, out_ls, msgs = generate_sync_messages_mixed(eng, ids, ls)
        assert msgs == [None] * len(ids)
        assert out_docs == ids               # nothing revived
        assert len(eng.main) == len(ids)
        assert out_ls == ls
        assert health_counts()['storage_parked_syncs_skipped'] > before
        # a quiet peer message (no changes, heads == ours) is absorbed
        # parked too
        ps2, peer_msgs = zip(*[generate_sync_message(p, dict(
            s, lastSentHeads=None)) for p, s in zip(peers, ps)])
        out_docs, out_ls, _patches = receive_sync_messages_mixed(
            eng, ids, out_ls, list(peer_msgs))
        assert out_docs == ids
        assert len(eng.main) == len(ids)
        for i, state in enumerate(out_ls):
            assert sorted(state['theirHeads']) == eng.heads(ids[i])

    def test_enveloped_messages_pass_the_parked_gate(self):
        """A trace-enveloped sync message from a tracing peer must be
        stripped BEFORE the parked gate's decode — unstripped, the
        0x54 magic read as hostile bytes and a valid quiet message was
        quarantined (regression: the strip lived only in the batched
        receive entry point)."""
        from automerge_tpu.fleet.storage import StorageEngine
        from automerge_tpu.fleet.sync_driver import (
            receive_sync_messages_mixed)
        from automerge_tpu.observability import tracecontext as tc

        import automerge_tpu.observability as obs

        fleet, docs, peers, ls, ps = self._converged_population()
        eng = StorageEngine(fleet)
        ids = eng.park(docs)
        ps2, peer_msgs = zip(*[generate_sync_message(p, dict(
            s, lastSentHeads=None)) for p, s in zip(peers, ps)])
        ctxs = [tc.mint() for _ in peer_msgs]
        wrapped = [tc.wrap(m, c) for m, c in zip(peer_msgs, ctxs)]
        obs.enable()
        obs.clear_spans()
        try:
            # on_error='raise': an unstripped envelope raises typed here
            out_docs, out_ls, _patches = receive_sync_messages_mixed(
                eng, ids, ls, wrapped)
            spans = {s['name']: s for s in obs.iter_spans()}
        finally:
            obs.disable()
        assert out_docs == ids               # quiet: still parked
        for i, state in enumerate(out_ls):
            assert sorted(state['theirHeads']) == eng.heads(ids[i])
        # the mixed entry point ADOPTS the stripped envelope's trace id
        # (first one wins), not just tolerates it — stitching works for
        # parked populations too
        assert spans['sync_parked_gate']['attrs']['trace'] == \
            ctxs[0].trace_id

    def test_divergent_peer_revives_only_its_doc(self):
        from automerge_tpu.fleet.storage import StorageEngine
        from automerge_tpu.fleet.sync_driver import (
            generate_sync_messages_mixed, receive_sync_messages_mixed)

        fleet, docs, peers, ls, ps = self._converged_population()
        n = len(docs)
        eng = StorageEngine(fleet)
        ids = eng.park(docs)
        # peer 2 edits: its doc (and only its doc) must revive
        from automerge_tpu.columnar import encode_change
        edit = encode_change({
            'actor': 'dd' * 16, 'seq': 1, 'startOp': 100, 'time': 0,
            'message': '', 'deps': Backend.get_heads(peers[2]),
            'ops': [{'action': 'set', 'obj': '_root', 'key': 'new',
                     'value': 1, 'datatype': 'int', 'pred': []}]})
        peers[2], _ = Backend.apply_changes(peers[2], [edit])
        mixed = list(ids)
        for _ in range(10):
            traffic = False
            replies = []
            for i in range(n):
                ps[i], back = generate_sync_message(peers[i], ps[i])
                replies.append(back)
                traffic = traffic or back is not None
            mixed, ls, _ = receive_sync_messages_mixed(eng, mixed, ls,
                                                       replies)
            mixed, ls, msgs = generate_sync_messages_mixed(eng, mixed, ls)
            for i, m in enumerate(msgs):
                if m is not None:
                    traffic = True
                    peers[i], ps[i], _ = Backend.receive_sync_message(
                        peers[i], ps[i], m)
            if not traffic:
                break
        # only doc 2 left the main store
        assert [isinstance(x, int) for x in mixed] == \
            [i != 2 for i in range(n)]
        assert len(eng.main) == n - 1
        assert sorted(mixed[2]['state'].heads) == \
            Backend.get_heads(peers[2])

    def test_deadline_abort_leaves_storage_whole(self):
        """All-or-nothing over the parked gate: a deadline firing at
        entry touches nothing, and one firing mid-round (after the gate
        already revived) re-parks the revived docs under their ORIGINAL
        ids — the caller's handles never dangle."""
        from automerge_tpu.errors import DeadlineExceeded
        from automerge_tpu.fleet.storage import StorageEngine
        from automerge_tpu.fleet.sync_driver import (
            generate_sync_messages_mixed)
        from automerge_tpu.service.deadline import Deadline

        fleet, docs, peers, ls, ps = self._converged_population(3)
        eng = StorageEngine(fleet)
        ids = eng.park(docs)
        heads_before = [eng.heads(i) for i in ids]
        # make the round NOT quiet so the gate wants to revive
        fresh = [dict(s, theirHeads=None) for s in ls]
        # expired at entry: nothing revived, nothing discarded
        past = Deadline(-1.0, clock=lambda: 0.0)
        with pytest.raises(DeadlineExceeded):
            generate_sync_messages_mixed(eng, ids, fresh, deadline=past)
        assert len(eng.main) == len(ids)
        # expires BETWEEN the entry check and the sub-round's own check:
        # the revived docs must re-park under their original ids
        ticks = [0.0]

        def clock():
            ticks[0] += 1.0
            return ticks[0]
        mid = Deadline(1.5, clock=clock)      # 1st check ok, 2nd late
        with pytest.raises(DeadlineExceeded):
            generate_sync_messages_mixed(eng, ids, fresh, deadline=mid)
        assert len(eng.main) == len(ids)
        for i, heads in zip(ids, heads_before):
            assert eng.heads(i) == heads
