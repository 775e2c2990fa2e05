"""Forensic flight-recorder dumps at the fault seams, phase attribution
of the hot paths, and the sync-round dispatch-count regression.

The contract under test: "quarantined_docs moved by 1" must come with a
forensic record naming WHICH doc (slot + durable id), WHAT phase, and
WHAT typed error, with the surrounding events — for hostile bytes on the
wire (batched apply, sync receive) and on disk (recovery)."""

import os

import pytest

from automerge_tpu import native, observability
from automerge_tpu.backend import init_sync_state
from automerge_tpu.columnar import encode_change
from automerge_tpu.fleet import backend as fleet_backend
from automerge_tpu.fleet.backend import DocFleet, init_docs
from automerge_tpu.fleet.durability import DurableFleet
from automerge_tpu.fleet.sync_driver import (generate_sync_messages_docs,
                                             receive_sync_messages_docs)
from automerge_tpu.observability import recorder

pytestmark = pytest.mark.skipif(not native.available(),
                                reason='native codec unavailable')


@pytest.fixture(autouse=True)
def _clean_recorder():
    recorder.clear_events()
    yield
    recorder.clear_events()
    observability.disable()


def _change(actor, key, value, seq=1, deps=()):
    return encode_change({
        'actor': actor, 'seq': seq, 'startOp': seq, 'time': 0,
        'message': '', 'deps': list(deps),
        'ops': [{'action': 'set', 'obj': '_root', 'key': key,
                 'value': value, 'datatype': 'int', 'pred': []}]})


def _flip(buf, pos=10):
    out = bytearray(buf)
    out[pos] ^= 0xFF
    return bytes(out)


def test_quarantine_produces_forensic_dump():
    """A quarantining batch apply that rejects a doc must dump a flight
    record naming the doc's slot, phase ('decode'), and typed error."""
    n = 5
    fleet = DocFleet(doc_capacity=8, key_capacity=16)
    handles = init_docs(n, fleet)
    per_doc = [[_change(f'{i:02x}' * 16, f'k{i}', i)] for i in range(n)]
    per_doc[2] = [_flip(per_doc[2][0])]
    dumps_before = recorder.flight_stats()['flight_dumps']

    from automerge_tpu.observability import hist as obs_hist
    obs_hist.reset()
    observability.enable()
    try:
        _h, _p, errors = fleet_backend.apply_changes_docs(
            handles, per_doc, mirror=False, on_error='quarantine')
    finally:
        observability.disable()
    assert errors[2] is not None
    # the quarantine retry loop re-parses survivors; their byte sizes
    # must still be recorded exactly ONCE (on the committing attempt)
    assert observability.histogram_snapshot()['doc_change_bytes'][
        'count'] == n - 1
    obs_hist.reset()

    assert recorder.flight_stats()['flight_dumps'] == dumps_before + 1
    report = observability.last_flight_record()
    assert report['trigger'] == 'quarantine'
    (err,) = report['detail']['errors']
    assert err['doc'] == 2
    assert err['stage'] == 'decode'
    assert err['error'] == 'MalformedChange'
    # the event ring carries the same rejection with a bytes digest
    ev = [e for e in report['events'] if e['kind'] == 'quarantine'][-1]
    assert ev['doc'] == 2 and ev['error'] == 'MalformedChange'
    assert ev['change_bytes'] > 0 and len(ev['digest']) == 16


def test_quarantine_dump_names_durable_id(tmp_path):
    """Journaled fleets: the forensic dump carries the document's durable
    journal id (the id recovery and the on-disk journal speak), not just
    the batch slot."""
    n = 4
    mgr = DurableFleet(str(tmp_path / 'fleet'))
    handles = mgr.init_docs(n)
    # one clean round assigns durable ids to every doc
    clean = [[_change(f'{i:02x}' * 16, 'k', i)] for i in range(n)]
    handles, _p, errs = mgr.apply_changes(handles, clean)
    assert not any(errs)
    dur_ids = [h['state']._dur_id for h in handles]

    poisoned = [[_change(f'{i:02x}' * 16, 'k2', i, seq=2,
                         deps=fleet_backend.get_heads(handles[i]))]
                for i in range(n)]
    poisoned[1] = [_flip(poisoned[1][0])]
    handles, _p, errors = mgr.apply_changes(handles, poisoned)
    assert errors[1] is not None
    report = observability.last_flight_record()
    (err,) = report['detail']['errors']
    assert err['doc'] == 1
    assert err['durable_id'] == dur_ids[1]
    assert err['error'] == 'MalformedChange'
    mgr.close()


def test_recovery_rot_produces_forensic_dump(tmp_path):
    """Mid-journal rot: recovery quarantines exactly the victim doc and
    dumps a flight record naming its durable id, the 'replay' stage, and
    the typed journal error."""
    path = str(tmp_path / 'fleet')
    mgr = DurableFleet(path)
    handles = mgr.init_docs(3)
    handles, _p, errs = mgr.apply_changes(
        handles, [[_change(f'{i:02x}' * 16, 'k', i)] for i in range(3)])
    assert not any(errs)
    victim_id = handles[1]['state']._dur_id
    mgr.journal.sync()
    journal_path = mgr.journal.path
    mgr.journal.close()

    # rot one byte inside the victim's journal payload (scan for a frame
    # byte whose flip recovery reports as rot for doc 1)
    data = bytearray(open(journal_path, 'rb').read())
    data[len(data) // 2] ^= 0xFF
    open(journal_path, 'wb').write(bytes(data))

    mgr2, rec_handles, report = DurableFleet.recover(path)
    assert report.rotted_records >= 1 or report.quarantined
    flight = observability.last_flight_record()
    assert flight['trigger'] == 'recovery'
    detail = flight['detail']
    assert detail['rotted_records'] == report.rotted_records
    if report.quarantined:
        assert any(e['durable_id'] in report.quarantined
                   for e in detail['errors'])
        assert all(e['error'] for e in detail['errors'])
    # rot events in the ring name the damaged byte offset
    rots = [e for e in flight['events'] if e['kind'] == 'journal_rot']
    assert rots, flight['events']
    del victim_id
    mgr2.close()


def test_sync_receive_decode_quarantine_dumps():
    n = 3
    fleet = DocFleet(doc_capacity=2 * n, key_capacity=16)
    src = init_docs(n, fleet)
    src, _ = fleet_backend.apply_changes_docs(
        src, [[_change(f'{i:02x}' * 16, 'k', i)] for i in range(n)],
        mirror=False)
    dst = init_docs(n, fleet)
    sa = [init_sync_state() for _ in range(n)]
    sb = [init_sync_state() for _ in range(n)]
    sa, msgs = generate_sync_messages_docs(src, sa)
    msgs = list(msgs)
    msgs[0] = b'\xff\x00garbage'
    dst, sb, _p, errors = receive_sync_messages_docs(
        dst, sb, msgs, mirror=False, on_error='quarantine')
    assert errors[0] is not None and errors[0].stage == 'decode'
    report = observability.last_flight_record()
    assert report['trigger'] == 'quarantine'
    assert report['detail']['errors'][0]['error'] == 'MalformedSyncMessage'


def test_doc_materialization_attributed():
    """Satellite: the parked-history revive (~700µs/doc; ROADMAP native
    change-list extraction) must show up as a span, accumulated
    metrics.seconds, and a doc_materialize_s histogram sample."""
    fleet = DocFleet(doc_capacity=4, key_capacity=8)
    handles = init_docs(2, fleet)
    handles, _ = fleet_backend.apply_changes_docs(
        handles, [[_change(f'{i:02x}' * 16, 'k', i)] for i in range(2)],
        mirror=False)
    assert fleet_backend.park_docs(handles) == 2
    assert handles[0]['state']._impl._doc_pending is not None
    observability.enable()
    try:
        handles[0]['state']._impl.changes      # property get revives
    finally:
        observability.disable()
    assert fleet.metrics.doc_materializations >= 1
    assert fleet.metrics.seconds['doc_materializations'] > 0
    spans = [s for s in observability.iter_spans()
             if s['name'] == 'doc_materialize']
    assert spans and spans[-1]['attrs']['chunk_bytes'] > 0
    hist = observability.histogram_snapshot()['doc_materialize_s']
    assert hist['count'] == 1 and hist['p50'] > 0


def test_sync_round_dispatches_flat_across_fleet_sizes():
    """Tier-1 regression for the round-6 O(1)-dispatch sync contract,
    measured through a FULL round (generate -> receive -> reply ->
    receive, fleet backends on both ends): 4x the docs must cost exactly
    the same device dispatches per round — on the chip, where every
    dispatch pays a fixed launch cost, this is the difference between a
    flat per-round cost and one that grows with fleet size."""
    per_round = {}
    for n in (6, 24):
        fleet = DocFleet(doc_capacity=2 * n, key_capacity=16)
        src = init_docs(n, fleet)
        src, _ = fleet_backend.apply_changes_docs(
            src, [[_change(f'{i:02x}' * 16, 'k', i)] for i in range(n)],
            mirror=False)
        dst = init_docs(n, fleet)
        sa = [init_sync_state() for _ in range(n)]
        sb = [init_sync_state() for _ in range(n)]
        rounds = []
        for _round in range(3):
            before = observability.dispatch_counts([fleet])
            sa, msgs = generate_sync_messages_docs(src, sa)
            dst, sb, _p = receive_sync_messages_docs(dst, sb, msgs,
                                                     mirror=False)
            sb, replies = generate_sync_messages_docs(dst, sb)
            src, sa, _p = receive_sync_messages_docs(src, sa, replies,
                                                     mirror=False)
            after = observability.dispatch_counts([fleet])
            rounds.append(after['total'] - before['total'])
        # the content must actually have moved (the count means something)
        assert fleet_backend.get_heads(dst[0]) == \
            fleet_backend.get_heads(src[0])
        per_round[n] = tuple(rounds)
    assert per_round[6] == per_round[24], per_round
