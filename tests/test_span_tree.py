"""The span tree (ISSUE-27): every recorded span has an id, a parent and a
root; `self_times` takes children out of a span; the turbo seam's
`turbo_gate` and `turbo_commit` are tiled by `gate.*` / `commit.*`
sub-phases and say why documents left the chain path; collections are `gc`
spans under what they interrupted; and nothing of it happens while spans
are off. Since ISSUE-39 `turbo_stage`, `turbo_dispatch` and `turbo_setup`
are tiled too (`stage.*`, `dispatch.*`, `setup.*`, exactly: a sub-phase
opens AT its parent's mark), `stage.root` and `stage.grid` once more
(`root.*`, `grid.*`; `grid.columns` notes the document runs it lays out
and the grid cells they fill), and a call's root span carries
`thread_cpu_ns`. A bulk read is the root span `read_batch`, tiled by
`read.*`. All of it is held structurally: order, parentage, shared
instants, no clock budget."""

import gc
import threading
import time

import pytest

from automerge_tpu import native, observability
from automerge_tpu.columnar import decode_change, encode_change
from automerge_tpu.fleet.backend import (DocFleet, apply_changes_docs,
                                         init_docs, materialize_docs)
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

    # exactly, since ISSUE-39: a sub-phase opens at its parent's own mark
    # (no gap to bound by a clock: ROADMAP D12)
    tiles(named, 'turbo_gate', GATE)
    tiles(named, 'turbo_commit', COMMIT)

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


def overwrite(seq, deps, key, value, pred):
    """One set by actor aa..: op seq@aa, overwriting the ops in `pred`."""
    buf = encode_change({
        'actor': 'aa' * 16, 'seq': seq, 'startOp': seq, 'time': 0,
        'message': '', 'deps': list(deps),
        'ops': [{'action': 'set', 'obj': '_root', 'key': key,
                 'value': value, 'datatype': 'int',
                 'pred': [f'{p}@{"aa" * 16}' for p in pred]}]})
    return buf, decode_change(buf)['hash']


@pytest.mark.skipif(not native.available(), reason='needs the native codec')
def test_what_the_gate_asks_of_the_applied_op_index_is_noted_on_its_phase():
    """Each document overwrites a key an earlier call set (a pred only the
    standing applied-op index answers), then overwrites it again (a pred
    the call's own rows answer): the first kind is counted, once a pred,
    and noted on gate.validate; the second is not. A text-only call asks
    the index nothing."""
    fleet = DocFleet(doc_capacity=4, key_capacity=8)
    handles, _ = apply_changes_docs(
        init_docs(3, fleet), [linear_log(d, n=2) for d in range(3)],
        mirror=False)
    assert fleet.metrics.standing_preds == 0      # no preds at all
    per_doc = []
    for d, handle in enumerate(handles):
        buf3, h3 = overwrite(3, handle['heads'], 'k1', 100 + d, [1])
        buf4, _ = overwrite(4, [h3], 'k1', 200 + d, [3])
        per_doc.append([buf3, buf4])
    obs_spans.enable(capacity=256)
    handles, _ = apply_changes_docs(handles, per_doc, mirror=False)
    observability.disable()
    named = by_name(observability.iter_spans())
    assert named['gate.validate'][0]['attrs'] == {'standing_preds': 3}
    assert fleet.metrics.standing_preds == 3
    assert [doc['k1'] for doc in materialize_docs(handles)] == \
        [200, 201, 202]

    fleet, handles, per_doc = text_only({})
    obs_spans.enable(capacity=256)
    apply_changes_docs(handles, per_doc, mirror=False)
    observability.disable()
    named = by_name(observability.iter_spans())
    assert named['gate.validate'][0]['attrs'] == {'standing_preds': 0}
    assert fleet.metrics.standing_preds == 0


# ---------------------------------------------------------------------------
# the seam: turbo_setup, turbo_stage and turbo_dispatch tiled (ISSUE-39)
# ---------------------------------------------------------------------------

SETUP = ['setup.engines', 'setup.buffers']
ROOT = ['root.rows', 'root.keys', 'root.index']
GRID = ['grid.lanes', 'grid.columns', 'grid.kills']
SEQ = ['stage.seq_rows', 'stage.seq_dispatch']
STAGE = ['stage.flush', 'stage.actors', 'stage.values', 'stage.root']


def text_logs(n_keystrokes=5):
    """(the change that makes a Text at a root key, one-keystroke changes
    on it): the first holds a root row AND sequence rows, the rest only
    sequence rows."""
    import automerge_tpu as am
    doc = am.from_({'text': am.Text('ab')}, 'cc' * 16)
    for i in range(n_keystrokes):
        doc = am.change(doc, lambda d, i=i: d['text'].insert_at(i, 'x'))
    log = [bytes(c) for c in am.get_all_changes(doc)]
    return log[:1], log[1:]


def map_only(fleet_kw):
    fleet = DocFleet(doc_capacity=2, key_capacity=8, **fleet_kw)
    return fleet, init_docs(2, fleet), [two_headed_log(0), linear_log(1)]


def text_only(fleet_kw):
    """A Text that is already there: the call brings keystrokes alone."""
    fleet = DocFleet(doc_capacity=2, key_capacity=8, **fleet_kw)
    make, keystrokes = text_logs()
    handles, _ = apply_changes_docs(init_docs(2, fleet), [make, make],
                                    mirror=False)
    return fleet, handles, [keystrokes, keystrokes[:3]]


def both(fleet_kw):
    """One document makes a Text and types into it, one sets map keys."""
    fleet = DocFleet(doc_capacity=2, key_capacity=8, **fleet_kw)
    make, keystrokes = text_logs()
    return fleet, init_docs(2, fleet), [make + keystrokes, linear_log(1)]


def tiles(named, parent_name, parts):
    """`parts`, each recorded once, are children of the one `parent_name`
    span in this order, each opening at the instant the one before closes,
    the first where the parent opens and the last where it closes."""
    (parent,) = named[parent_name]
    assert all(len(named.get(name, ())) == 1 for name in parts), \
        (parent_name, {name: len(named.get(name, ())) for name in parts})
    subs = [named[name][0] for name in parts]
    assert all(sub['parent'] == parent['id'] for sub in subs), parent_name
    assert all(sub['root'] == parent['root'] for sub in subs)
    for before, after in zip(subs, subs[1:]):
        assert after['t0_ns'] == before['t1_ns'], (before['name'],
                                                   after['name'])
    assert subs[0]['t0_ns'] == parent['t0_ns'], parent_name
    assert subs[-1]['t1_ns'] == parent['t1_ns'], parent_name
    # and nothing else is a child of the parent but what a part calls
    others = {s['name'] for spans in named.values() for s in spans
              if s['parent'] == parent['id']} - set(parts)
    assert others <= {'gc'}, (parent_name, others)


CALLS = pytest.mark.parametrize('make_call,root_rows,seq_rows', [
    (map_only, True, False),
    (text_only, False, True),
    (both, True, True),
])


@pytest.mark.skipif(not native.available(), reason='needs the native codec')
@CALLS
def test_sub_phases_tile_turbo_setup_stage_and_dispatch(
        make_call, root_rows, seq_rows):
    tiled_call(make_call, root_rows, seq_rows, {})


@pytest.mark.skipif(not native.available(), reason='needs the native codec')
@CALLS
def test_sub_phases_tile_a_call_on_the_register_engine(
        make_call, root_rows, seq_rows):
    """The same tiling where root rows go to `apply_register_batch_donated`
    and not to the grid: no `dispatch.note`, no `grid.kills`. (A family of
    its own: each compiles its engine's kernels, and `test_slow_audit`
    budgets a family.)"""
    tiled_call(make_call, root_rows, seq_rows, {'exact_device': True})


def tiled_call(make_call, root_rows, seq_rows, fleet_kw):
    fleet, handles, per_doc = make_call(fleet_kw)
    obs_spans.enable(capacity=256)
    apply_changes_docs(handles, per_doc, mirror=False)
    observability.disable()
    assert fleet.metrics.fallbacks == 0
    spans = observability.iter_spans()
    assert observability.spans_dropped() == 0
    named = by_name(spans)

    tiles(named, 'turbo_setup', SETUP)
    assert named['setup.buffers'][0]['attrs'] == {'queued': 0}
    tiles(named, 'turbo_gate', GATE)
    tiles(named, 'turbo_commit', COMMIT)
    # the work's name says which work it is, its parent where it ran:
    # sequence rows are staged under turbo_stage in a call without root
    # rows, and behind the grid's (or the registers') enqueue otherwise
    grid = ['stage.grid'] if root_rows else []
    tiles(named, 'turbo_stage',
          STAGE + grid + (SEQ if seq_rows and not root_rows else []))
    tiles(named, 'stage.root', ROOT)
    if root_rows:
        note = [] if fleet_kw else ['dispatch.note']
        tiles(named, 'turbo_dispatch',
              ['dispatch.enqueue'] + note + (SEQ if seq_rows else []))
        tiles(named, 'stage.grid', GRID[:2] if fleet_kw else GRID)
        if not fleet_kw:
            # the grid's columns are laid out a document run at a time
            # (ISSUE-40): both documents have root rows, six each in
            # `map_only`; in `both` the Text's make is one, the map's six.
            # `cells` is rows times width: two rows of six, or of eight
            # (the power of two) where the runs differ
            assert named['grid.columns'][0]['attrs'] == {
                'runs': 2, 'ragged': 0 if make_call is map_only else 1,
                'cells': 12 if make_call is map_only else 16}
        assert named['turbo_dispatch'][0]['t0_ns'] == \
            named['turbo_stage'][0]['t1_ns']
    else:
        assert 'turbo_dispatch' not in named and 'stage.grid' not in named
    # the children the issue leaves where they were
    (flush,) = named['fleet_flush']
    assert flush['parent'] == named['stage.flush'][0]['id']
    if root_rows and not fleet_kw:
        assert named['dispatch_grid'][0]['parent'] == \
            named['dispatch.enqueue'][0]['id']
    if seq_rows:
        (rows,) = named['stage.seq_rows']
        n_ops = 7 if root_rows else 8      # keystrokes; the make's 'ab' too
        assert rows['attrs'] == {'rows': n_ops,
                                 'objects': 1 if root_rows else 2}
        (dispatch,) = named['dispatch_seq']
        assert dispatch['parent'] == named['stage.seq_dispatch'][0]['id']
        assert [s['parent'] for s in named['seq.enqueue']] == \
            [dispatch['id']]
    else:
        assert not set(SEQ) & set(named)
    # the six phases tile the call's turbo part as before
    phases = [named[name][0] for name in (
        'turbo_setup', 'turbo_parse', 'turbo_gate', 'turbo_commit',
        'turbo_stage') + (('turbo_dispatch',) if root_rows else ())]
    for before, after in zip(phases, phases[1:]):
        assert after['t0_ns'] == before['t1_ns']
    (batch,) = named['apply_batch']
    mine = [s for s in spans if s['tid'] == threading.get_ident()]
    assert all(s['root'] == batch['id'] for s in mine)
    # the CPU clock is read at the call's two ends (it is a system call:
    # two reads a call, not forty) and inside no phase
    assert isinstance(batch['thread_cpu_ns'], int)
    assert all(s['thread_cpu_ns'] is None for s in mine if s is not batch)
    assert obs_spans._open_spans.stack == []    # nothing is left open


@pytest.mark.skipif(not native.available(), reason='needs the native codec')
def test_a_raise_inside_the_stage_closes_every_phase(monkeypatch):
    fleet, handles, per_doc = both({})

    def refuse(*_columns):
        raise ZeroDivisionError('the op index is full')
    monkeypatch.setattr(fleet, '_index_ops', refuse)
    obs_spans.enable(capacity=256)
    with pytest.raises(ZeroDivisionError):
        apply_changes_docs(handles, per_doc, mirror=False)
    observability.disable()
    named = by_name(observability.iter_spans())
    # raised inside root.index, inside stage.root, inside turbo_stage:
    # all three closed at one instant, nothing after them opened, and the
    # error is named on the call's root
    tiles(named, 'stage.root', ROOT)
    tiles(named, 'turbo_stage', STAGE)
    assert not {'stage.grid', 'turbo_dispatch', 'dispatch.enqueue',
                'stage.seq_rows'} & set(named)
    assert named['apply_batch'][0]['error'] == 'ZeroDivisionError'
    assert named['turbo_stage'][0]['root'] == named['apply_batch'][0]['id']
    assert obs_spans._open_spans.stack == []


@pytest.mark.skipif(not native.available(), reason='needs the native codec')
def test_a_call_that_queues_everything_ends_with_its_commit():
    """No change is causally ready: nothing is staged or dispatched, and
    `turbo_commit` closes where its last sub-phase does."""
    fleet = DocFleet(doc_capacity=2, key_capacity=8)
    handles = init_docs(2, fleet)
    obs_spans.enable(capacity=64)
    handles, _ = apply_changes_docs(
        handles, [linear_log(0)[1:], linear_log(1)[2:]], mirror=False)
    observability.disable()
    named = by_name(observability.iter_spans())
    assert [len(h['state'].queue) for h in handles] == [5, 4]
    tiles(named, 'turbo_setup', SETUP)
    tiles(named, 'turbo_gate', GATE)
    tiles(named, 'turbo_commit', COMMIT)
    assert not {'turbo_stage', 'turbo_dispatch', 'stage.flush'} & set(named)
    assert obs_spans._open_spans.stack == []


READ = ['read.flush', 'read.gather', 'read.render', 'read.host']


@pytest.mark.skipif(not native.available(), reason='needs the native codec')
@pytest.mark.parametrize('routed', [False, True])
def test_read_phases_tile_a_bulk_read(routed):
    """A `materialize_docs` call is the root span `read_batch`, tiled by
    `read.flush` / `read.gather` / `read.render` / `read.host`: a slot
    asked twice is gathered once, and a slot routed to the host mirror is
    not gathered at all."""
    fleet, handles, per_doc = map_only({})
    handles, _ = apply_changes_docs(handles, per_doc, mirror=False)
    if routed:
        fleet.del_fallback.add(handles[1]['state']._impl.slot)
    obs_spans.enable(capacity=64)
    views = materialize_docs([handles[1], handles[0], handles[1]])
    observability.disable()
    named = by_name(observability.iter_spans())
    tiles(named, 'read_batch', READ)
    (batch,) = named['read_batch']
    assert batch['parent'] is None and batch['attrs'] == {'docs': 3}
    assert isinstance(batch['thread_cpu_ns'], int)
    assert named['read.gather'][0]['attrs'] == {'rows': 1 if routed else 2}
    assert named['read.host'][0]['attrs'] == {'docs': 2 if routed else 0}
    assert views[0] == views[2] == handles[1]['state'].materialize()
    assert obs_spans._open_spans.stack == []


def test_a_sequence_that_tiles_a_phase_shares_its_marks():
    obs_spans.enable(capacity=16)
    outer, inner = observability.span_seq(), observability.span_seq()
    inner.mark('p1.x', at=outer.mark('p1'))
    inner.mark('p1.y')
    inner.mark('p2.x', at=outer.mark('p2', at=inner.done()))
    assert inner.done(at=None) is not None
    assert outer.done(at=inner.done()) is not None     # inner: not running
    named = by_name(observability.iter_spans())
    tiles(named, 'p1', ['p1.x', 'p1.y'])
    assert named['p2.x'][0]['t0_ns'] == named['p2'][0]['t0_ns'] == \
        named['p1'][0]['t1_ns']
    assert named['p2.x'][0]['parent'] == named['p2'][0]['id']
    # off, marks hand nothing on and take anything
    observability.disable()
    off = observability.span_seq()
    assert off.mark('q', at=None) is None and off.done(at=None) is None


# ---------------------------------------------------------------------------
# the thread's CPU clock beside the wall clock (ISSUE-39)
# ---------------------------------------------------------------------------

def one_span():
    with observability.span('timed'):
        pass


def one_phase():
    seq = observability.span_seq()
    seq.mark('timed')
    seq.done()


def one_collection():
    gc.collect()


def one_slice():
    observability.record_span('timed', 1, 2, tid=99)


def under(depth, record):
    """`record` run `depth` spans deep."""
    def nested():
        if depth:
            with observability.span(f'level{depth}'):
                under(depth - 1, record)()
        else:
            record()
    return nested


@pytest.mark.parametrize('record,name,has_clock', [
    (one_span, 'timed', True), (one_phase, 'timed', True),
    (one_collection, 'gc', True), (one_slice, 'timed', False),
    # under a root the clock (a system call) is not read
    (under(1, one_span), 'timed', False),
    (under(1, one_phase), 'timed', False),
    (under(2, one_collection), 'gc', False)])
def test_thread_cpu_ns_is_on_a_root_span_and_on_nothing_under_it(
        record, name, has_clock):
    obs_spans.enable(capacity=8)
    record()
    (span,) = [s for s in observability.iter_spans()
               if not s['name'].startswith('level')]
    assert span['name'] == name
    if has_clock:
        assert isinstance(span['thread_cpu_ns'], int)
        assert span['thread_cpu_ns'] >= 0
    else:
        assert span['thread_cpu_ns'] is None
    (event,) = [e for e in observability.export_chrome_trace()
                if e['name'] == name]
    assert ('thread_cpu_ns' in event['args']) == has_clock


def test_a_sleeping_span_burns_no_cpu():
    """Wall time passes, the thread's CPU time does not, however loaded
    the machine is: the difference is what a span spent off the CPU."""
    obs_spans.enable(capacity=8)
    with observability.span('asleep'):
        time.sleep(0.02)
    seq = observability.span_seq()
    seq.mark('asleep')
    time.sleep(0.02)
    seq.done()
    for span in observability.iter_spans():
        assert span['dur_ns'] >= 20_000_000
        assert span['thread_cpu_ns'] < span['dur_ns'] / 2



def test_the_report_keeps_thread_cpu_apart_from_its_cpu_column(tmp_path):
    """`tools/obs_report.py` calls summed durations "cpu": the thread's CPU
    time has a column of its own, and a flight dump's spans carry it."""
    import io
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), 'tools'))
    import obs_report
    obs_spans.enable(capacity=8)
    with observability.span('asleep'):
        time.sleep(0.02)
    observability.record_span('slice', 1, 2, tid=99)
    path = tmp_path / 'trace.json'
    observability.export_chrome_trace(str(path))
    out = io.StringIO()
    obs_report.render_trace(str(path), out=out)
    header, *rows = out.getvalue().splitlines()[1:]
    assert header.split()[-2:] == ['thread_cpu', 'ms']
    table = {row.split()[0]: row.split() for row in rows}
    assert table['slice'][-1] == '-'
    assert float(table['asleep'][2]) >= 20.0            # "cpu ms": duration
    assert float(table['asleep'][-1]) < 10.0            # what the thread ran
    dump = tmp_path / 'flight.json'
    dump.write_text(__import__('json').dumps(
        {'recent_spans': observability.iter_spans()}))
    ran = obs_report.thread_cpu_ms(obs_report.load_events(str(dump)))
    assert set(ran) == {'asleep'} and ran['asleep'] < 10.0


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

    def no_cpu_clock():
        raise AssertionError('the CPU clock read with spans off')
    monkeypatch.setattr(time, 'thread_time_ns', no_cpu_clock)
    assert obs_spans._on_gc not in gc.callbacks
    fleet = DocFleet(doc_capacity=2, key_capacity=8)
    handles = init_docs(2, fleet)
    handles, _ = apply_changes_docs(
        handles, [two_headed_log(0), linear_log(1)], mirror=False)
    # and a call that stages grid rows and sequence rows, and one that
    # stages sequence rows alone
    make, keystrokes = text_logs()
    handles, _ = apply_changes_docs(handles, [make + keystrokes[:2], []],
                                    mirror=False)
    apply_changes_docs(handles, [keystrokes[2:], []], mirror=False)
    assert fleet.metrics.fallbacks == 0 and fleet.metrics.seq_ops == 7
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
