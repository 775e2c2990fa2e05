"""Fleet-scale smoke: hundreds of documents through the batched public
surface in one process — capacity growth, actor-table renumbering, turbo
ingest, the batched sync driver, bulk load, and whole-fleet readback all
interact at a size the per-feature suites (doc_capacity 2-8) never reach.
Shapes stay small enough for the CI budget; deployment-scale runs are
the benchmark's cells (benchmarks/)."""

import numpy as np
import pytest

from automerge_tpu import native
from automerge_tpu import backend as host_backend
from automerge_tpu.backend import init_sync_state
from automerge_tpu.columnar import encode_change, decode_change_meta
from automerge_tpu.fleet import backend as fleet_backend
from automerge_tpu.fleet.backend import DocFleet, materialize_docs
from automerge_tpu.fleet.loader import load_docs
from automerge_tpu.fleet.sync_driver import generate_sync_messages_docs

N_DOCS = 512


@pytest.mark.skipif(not native.available(),
                    reason='native codec unavailable')
def test_fleet_of_512_docs_end_to_end():
    rng = np.random.default_rng(11)
    # Actors arrive in descending hex order so later batches force live
    # actor-table renumbering over grown device state
    actors = [f'{0xf0 - d // 64:02x}' * 16 for d in range(N_DOCS)]

    # Start small: capacity must grow doc axis (4 -> 512) and key axis
    fleet = DocFleet(doc_capacity=4, key_capacity=4)
    handles = fleet_backend.init_docs(N_DOCS, fleet)

    def chain(d, n_changes, start_seq=1, heads=(), start_op=1):
        out, hs = [], list(heads)
        for c in range(n_changes):
            buf = encode_change({
                'actor': actors[d], 'seq': start_seq + c,
                'startOp': start_op + c, 'time': 0, 'message': '',
                'deps': hs,
                'ops': [{'action': 'set', 'obj': '_root',
                         'key': f'k{int(rng.integers(0, 24))}',
                         'value': int(rng.integers(0, 1 << 20)),
                         'datatype': 'int', 'pred': []}]})
            hs = [decode_change_meta(buf, True)['hash']]
            out.append(buf)
        return out, hs

    # Wave 1: turbo across the whole fleet
    per_doc, heads = [], []
    for d in range(N_DOCS):
        chg, hs = chain(d, 4)
        per_doc.append(chg)
        heads.append(hs)
    handles, _ = fleet_backend.apply_changes_docs(handles, per_doc,
                                                  mirror=False)
    assert fleet.metrics.turbo_calls == 1
    assert fleet.metrics.fallbacks == 0

    # Wave 2: more changes per doc (exercises grown state + deferred graph)
    per_doc2 = []
    for d in range(N_DOCS):
        chg, _ = chain(d, 3, start_seq=5, heads=heads[d], start_op=5)
        per_doc2.append(chg)
    handles, _ = fleet_backend.apply_changes_docs(handles, per_doc2,
                                                  mirror=False)
    assert all(h['state'].is_fleet for h in handles)
    assert fleet.metrics.promotions == 0

    # Whole-fleet readback in one transfer; spot-check against the host
    mats = materialize_docs(handles)
    assert len(mats) == N_DOCS
    for d in (0, N_DOCS // 2, N_DOCS - 1):
        hb = host_backend.init()
        hb, _ = host_backend.apply_changes(hb, per_doc[d] + per_doc2[d])
        host_view = {k: v['value'] for k, v in
                     host_backend.get_patch(hb)['diffs']['props'].items()
                     for v in [max(v.values(),
                                   key=lambda x: x.get('value', 0))]}
        assert set(mats[d]) == set(
            host_backend.get_patch(hb)['diffs']['props'])
        assert bytes(fleet_backend.save(handles[d])) == \
            bytes(host_backend.save(hb))

    # Batched sync generate round over the whole fleet
    states = [init_sync_state() for _ in handles]
    _, messages = generate_sync_messages_docs(handles, states)
    assert sum(m is not None for m in messages) == N_DOCS

    # Bulk-load every save into a fresh fleet; reads must match
    saves = [bytes(fleet_backend.save(h)) for h in handles]
    fresh = DocFleet(doc_capacity=8, key_capacity=8)
    loaded = load_docs(saves, fresh)
    assert fresh.metrics.docs_bulk_loaded == N_DOCS
    assert materialize_docs(loaded) == mats
