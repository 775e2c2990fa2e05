"""The span tree (ISSUE-27): every recorded span has an id, a parent and a
root; `self_times` takes children out of a span; the turbo seam's
`turbo_gate` and `turbo_commit` are tiled by `gate.*` / `commit.*`
sub-phases and say why documents left the chain path; collections are `gc`
spans under what they interrupted; and nothing of it happens while spans
are off."""

import gc
import threading

import pytest

from automerge_tpu import native, observability
from automerge_tpu.columnar import decode_change, encode_change
from automerge_tpu.fleet.backend import (DocFleet, apply_changes_docs,
                                         init_docs)
from automerge_tpu.observability import spans as obs_spans


@pytest.fixture(autouse=True)
def _spans_as_found():
    """Spans off afterwards; the collector paused, so that only the test
    that asks for a collection finds a `gc` span."""
    gc.disable()
    yield
    gc.enable()
    observability.disable()


def by_name(spans):
    out = {}
    for span in spans:
        out.setdefault(span['name'], []).append(span)
    return out


# ---------------------------------------------------------------------------
# parents and roots
# ---------------------------------------------------------------------------

def nested_span():
    with observability.span('a'):
        with observability.span('b'):
            with observability.span('c'):
                pass
        with observability.span('d'):
            pass
    with observability.span('e'):
        pass
    return {'a': (None, 'a'), 'b': ('a', 'a'), 'c': ('b', 'a'),
            'd': ('a', 'a'), 'e': (None, 'e')}


def nested_span_seq():
    outer = observability.span_seq()
    outer.mark('p1')
    inner = observability.span_seq()
    inner.mark('p1.x')
    with observability.span('leaf'):
        pass
    inner.mark('p1.y')
    inner.done()
    outer.mark('p2')
    observability.record_span('slice', 1, 2, tid=99)
    observability.record_span('handed', 1, 2, tid=99, parent=outer)
    outer.done()
    return {'p1': (None, 'p1'), 'p1.x': ('p1', 'p1'), 'leaf': ('p1.x', 'p1'),
            'p1.y': ('p1', 'p1'), 'p2': (None, 'p2'), 'slice': ('p2', 'p2'),
            'handed': ('p2', 'p2')}


def raising_block():
    with pytest.raises(KeyError):
        with observability.span('outer'):
            with observability.span('inner'):
                raise KeyError('boom')
    with observability.span('after'):
        pass
    return {'outer': (None, 'outer'), 'inner': ('outer', 'outer'),
            'after': (None, 'after')}


def abandoned_sequence():
    with observability.span('outer'):
        seq = observability.span_seq()
        seq.mark('left_open')           # never done(), so never recorded
    # and it must not adopt what this thread opens next
    with observability.span('after'):
        pass
    return {'outer': (None, 'outer'), 'after': (None, 'after')}


def two_threads():
    go = threading.Barrier(2, timeout=30)

    def work(tag):
        with observability.span(f'{tag}.root'):
            go.wait()                   # both roots are open at once
            with observability.span(f'{tag}.child'):
                pass
            go.wait()

    threads = [threading.Thread(target=work, args=(tag,)) for tag in 'xy']
    for thread in threads:
        thread.start()
    for thread in threads:
        thread.join(timeout=30)
        assert not thread.is_alive()
    return {'x.root': (None, 'x.root'), 'x.child': ('x.root', 'x.root'),
            'y.root': (None, 'y.root'), 'y.child': ('y.root', 'y.root')}


@pytest.mark.parametrize('scenario', [nested_span, nested_span_seq,
                                      raising_block, abandoned_sequence,
                                      two_threads])
def test_parents_and_roots(scenario):
    obs_spans.enable(capacity=64)
    want = scenario()
    spans = observability.iter_spans()
    ids = {span['name']: span['id'] for span in spans}
    assert len(ids) == len(spans) == len(set(ids.values()))
    for name, (parent, root) in want.items():
        (span,) = [s for s in spans if s['name'] == name]
        assert span['parent'] == (ids[parent] if parent else None), name
        assert span['root'] == ids[root], name
    # nothing is left open on the calling thread
    with observability.span('probe') as probe:
        assert probe.parent is None and probe.root == probe.id


def test_a_collection_is_a_child_of_the_span_it_interrupted():
    obs_spans.enable(capacity=16)
    with observability.span('phase') as phase:
        gc.collect()
    gc.collect()                        # interrupts nothing: a root
    inside, recorded, outside = observability.iter_spans()
    assert recorded['name'] == 'phase' and recorded['id'] == phase.id
    assert inside['name'] == outside['name'] == 'gc'
    assert inside['parent'] == phase.id and inside['root'] == phase.id
    assert inside['attrs']['generation'] == 2
    assert 'collected' in inside['attrs']
    assert outside['parent'] is None and outside['root'] == outside['id']
    own = observability.self_times(observability.iter_spans())
    assert own[phase.id] == recorded['dur_ns'] - inside['dur_ns']
    observability.disable()
    assert obs_spans._on_gc not in gc.callbacks


# ---------------------------------------------------------------------------
# self time
# ---------------------------------------------------------------------------

def node(sid, parent, t0, t1):
    return {'id': sid, 'parent': parent, 't0_ns': t0, 't1_ns': t1}


@pytest.mark.parametrize('tree,want', [
    # a leaf is all its own
    ([node(1, None, 0, 100)], {1: 100}),
    # two children that tile part of the parent
    ([node(1, None, 0, 100), node(2, 1, 10, 40), node(3, 1, 40, 70)],
     {1: 40, 2: 30, 3: 30}),
    # overlapping children (pool workers) count once; grandchildren come
    # out of their parent, not of the root
    ([node(1, None, 0, 100), node(2, 1, 10, 60), node(3, 1, 30, 80),
      node(4, 2, 20, 30)], {1: 30, 2: 40, 3: 50, 4: 10}),
    # a child that sticks out (an externally timed slice) is clipped
    ([node(1, None, 50, 100), node(2, 1, 0, 60), node(3, 1, 90, 200)],
     {1: 30, 2: 60, 3: 110}),
    # a child whose parent fell off the ring changes nothing
    ([node(5, 4, 0, 10)], {5: 10}),
])
def test_self_times_on_a_hand_built_tree(tree, want):
    assert observability.self_times(tree) == want


# ---------------------------------------------------------------------------
# the seam: turbo_gate and turbo_commit tiled
# ---------------------------------------------------------------------------

def change(actor, seq, deps, key, value):
    buf = encode_change({
        'actor': actor, 'seq': seq, 'startOp': seq, 'time': 0,
        'message': '', 'deps': list(deps),
        'ops': [{'action': 'set', 'obj': '_root', 'key': key,
                 'value': value, 'datatype': 'int', 'pred': []}]})
    return buf, decode_change(buf)['hash']


def two_headed_log(doc, n=3):
    """Two actors that never see each other, interleaved."""
    log, heads = [], {'aa' * 16: [], 'bb' * 16: []}
    for i in range(1, n + 1):
        for actor in heads:
            buf, digest = change(actor, i, heads[actor],
                                 f'k{i}', 10 * doc + i)
            heads[actor] = [digest]
            log.append(buf)
    return log


def linear_log(doc, n=6):
    log, head = [], []
    for i in range(1, n + 1):
        buf, digest = change('aa' * 16, i, head, f'k{i}', 10 * doc + i)
        head = [digest]
        log.append(buf)
    return log


def out_of_order_log(doc, n=3):
    """The two-headed log with one actor's first two changes swapped: not
    causally ordered, so the DAG gate refuses it too."""
    log = two_headed_log(doc, n)
    log[0], log[2] = log[2], log[0]
    return log


GATE = ['gate.chain', 'gate.dag', 'gate.shape', 'gate.decode',
        'gate.general', 'gate.order', 'gate.validate']
COMMIT = ['commit.columnar', 'commit.staged', 'commit.handles']


@pytest.mark.skipif(not native.available(), reason='needs the native codec')
@pytest.mark.parametrize('make_log,off_chain,dag', [
    (two_headed_log, True, True),       # off the chain, DAG-ordered
    (linear_log, False, False),
    (out_of_order_log, True, False),    # the general gate, one native call
])
def test_sub_phases_tile_turbo_gate_and_turbo_commit(make_log, off_chain,
                                                     dag):
    n_docs = 4
    fleet = DocFleet(doc_capacity=n_docs, key_capacity=8)
    handles = init_docs(n_docs, fleet)
    per_doc = [make_log(d) for d in range(n_docs)]
    obs_spans.enable(capacity=256)
    apply_changes_docs(handles, per_doc, mirror=False)
    observability.disable()
    spans = observability.iter_spans()
    assert observability.spans_dropped() == 0
    named = by_name(spans)

    for parent_name, parts in (('turbo_gate', GATE),
                               ('turbo_commit', COMMIT)):
        (parent,) = named[parent_name]
        subs = [named[name][0] for name in parts]
        assert all(len(named[name]) == 1 for name in parts)
        assert all(sub['parent'] == parent['id'] for sub in subs)
        for before, after in zip(subs, subs[1:]):
            assert after['t0_ns'] == before['t1_ns']
        assert 0 <= subs[0]['t0_ns'] - parent['t0_ns'] < 50_000
        assert 0 <= parent['t1_ns'] - subs[-1]['t1_ns'] < 50_000

    # every span of the call, on the calling thread, under its apply_batch
    (batch,) = named['apply_batch']
    assert batch['parent'] is None
    mine = [s for s in spans if s['tid'] == threading.get_ident()]
    assert len(mine) > 12 and all(s['root'] == batch['id'] for s in mine)

    refused = n_docs if off_chain else 0    # by the chain check
    taken = n_docs if dag else 0            # of those, by the DAG gate
    off = refused - taken                   # what reaches the general gate
    reasons = {k: v for k, v in named['turbo_gate'][0]['attrs'].items()
               if k.startswith('offchain_')}
    assert reasons == {'offchain_native': refused, 'offchain_heads': 0,
                       'offchain_seq': 0, 'offchain_dag': taken}
    assert (fleet.metrics.offchain_native, fleet.metrics.offchain_heads,
            fleet.metrics.offchain_seq, fleet.metrics.offchain_dag) == \
        (refused, 0, 0, taken)
    assert fleet.metrics.turbo_commit_fallback_docs == off
    # the general gate is one native call for all its documents: the phase
    # says how many reached it and what it asked of their history indexes
    # (here nothing: every dependency is a change of the run), and opens no
    # span a document
    assert named['gate.general'][0]['attrs'] == {'docs': off,
                                                 'history_probes': 0}
    assert fleet.metrics.history_probes == 0
    assert named['commit.staged'][0]['attrs'] == {'docs': off}
    general = named['gate.general'][0]
    assert not [s for s in spans if s['parent'] == general['id']]
    assert 'gate.meta' not in named and 'gate.drain' not in named
    if not off:
        # nothing staged: the phase is there and as good as empty
        staged = named['commit.staged'][0]
        assert staged['dur_ns'] < named['turbo_commit'][0]['dur_ns'] / 2


@pytest.mark.skipif(not native.available(), reason='needs the native codec')
def test_a_skipped_seq_is_its_own_reason_and_a_raise_closes_every_phase():
    from automerge_tpu.errors import InvalidChange
    fleet = DocFleet(doc_capacity=2, key_capacity=8)
    handles = init_docs(2, fleet)
    late, _ = change('aa' * 16, 2, [], 'k', 1)     # seq 2 on an empty clock
    fine, _ = change('bb' * 16, 1, [], 'k', 2)
    obs_spans.enable(capacity=64)
    with pytest.raises(InvalidChange):
        apply_changes_docs(handles, [[late], [fine]], mirror=False)
    named = by_name(observability.iter_spans())
    (gate,) = named['turbo_gate']
    assert gate['attrs'] == {'offchain_native': 0, 'offchain_heads': 0,
                             'offchain_seq': 1, 'offchain_dag': 0}
    assert fleet.metrics.offchain_seq == 1
    # the general gate raised inside gate.general: each open phase closed,
    # the error typed where it is raised and named on the call's root
    general = named['gate.general'][0]
    assert general['attrs'] == {'docs': 1}     # raised before it could note
    assert general['parent'] == gate['id']
    assert general['t1_ns'] <= gate['t1_ns']
    assert 'gate.order' not in named and 'turbo_commit' not in named
    assert named['apply_batch'][0]['error'] == 'InvalidChange'
    with observability.span('probe') as probe:
        assert probe.parent is None


@pytest.mark.skipif(not native.available(), reason='needs the native codec')
def test_what_the_general_gate_asks_of_history_is_noted_on_its_phase():
    """A linear log less its second change: the third names a hash that is
    neither a change of the run nor a head, which only the document's
    history index can answer (no); the phase carries the count, and a
    document on the chain beside it adds nothing to it."""
    fleet = DocFleet(doc_capacity=2, key_capacity=8)
    handles = init_docs(2, fleet)
    log = linear_log(0)
    obs_spans.enable(capacity=64)
    handles, _ = apply_changes_docs(handles, [log[:1] + log[2:], linear_log(1)],
                                    mirror=False)
    observability.disable()
    named = by_name(observability.iter_spans())
    assert named['gate.general'][0]['attrs'] == {'docs': 1,
                                                 'history_probes': 1}
    assert fleet.metrics.history_probes == 1
    assert named['turbo_gate'][0]['attrs']['heldback_changes'] == 4
    assert len(handles[0]['state'].queue) == 4


# ---------------------------------------------------------------------------
# off is off
# ---------------------------------------------------------------------------

@pytest.mark.skipif(not native.available(), reason='needs the native codec')
def test_with_spans_off_nothing_is_recorded_and_no_annotation_is_made(
        monkeypatch):
    obs_spans.enable(capacity=8)       # an empty ring to watch
    observability.disable()

    def refuse(*_name):
        raise AssertionError('a TraceAnnotation with spans off')
    # neither the lazy import nor the class an earlier enable() found
    monkeypatch.setattr(obs_spans, '_import_annotation', refuse)
    monkeypatch.setattr(obs_spans, '_annotation', refuse)
    assert obs_spans._on_gc not in gc.callbacks
    fleet = DocFleet(doc_capacity=2, key_capacity=8)
    handles = init_docs(2, fleet)
    apply_changes_docs(handles, [two_headed_log(0), linear_log(1)],
                       mirror=False)
    gc.collect()
    assert observability.iter_spans() == []
    assert observability.span_count() == 0
    assert getattr(obs_spans._open_spans, 'stack', []) == []
    assert fleet.metrics.offchain_native == 1


def test_the_ring_works_where_jax_cannot_be_imported(monkeypatch):
    def no_jax():
        raise ImportError('no module named jax')
    monkeypatch.setattr(obs_spans, '_import_annotation', no_jax)
    obs_spans.enable(capacity=8)
    assert obs_spans._annotation is None
    with observability.span('outer'):
        with observability.span('inner'):
            pass
    inner, outer = observability.iter_spans()
    assert inner['parent'] == outer['id'] and outer['parent'] is None
