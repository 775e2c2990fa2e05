"""The DAG gate (`native.dag_gate`, codec.cpp am_dag_gate) and the columnar
commit of multi-head documents.

A document the chain check refuses but whose batch is causally ORDERED
(concurrent branches in one buffer, merge changes naming two heads) is
gated natively and committed by the columnar commit. Held here byte for
byte to the Python gate it replaces for those documents (the same call
with the kernel's verdict forced to all-false) and to the host backend;
and every shape the kernel must refuse takes the Python gate with the
outcome it always had.
"""

import os
import random
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from automerge_tpu import backend as host                        # noqa: E402
from automerge_tpu import native                                 # noqa: E402
from automerge_tpu.columnar import decode_change, encode_change  # noqa: E402
from automerge_tpu.errors import InvalidChange                   # noqa: E402
from automerge_tpu.fleet.backend import (                        # noqa: E402
    DocFleet, apply_changes_docs, init_docs, materialize_docs)

pytestmark = pytest.mark.skipif(not native.available(),
                                reason='the DAG gate is the native codec')

KEYS = [f'k{i}' for i in range(6)]


def _actor(i):
    return f'{i + 0xa0:02x}' * 16


class _Log:
    """A causally ordered change log written actor by actor: every change
    follows its actor's last one, and with probability `merge` also the
    newest change of another actor (a merge change, two deps). Each actor
    sets keys over what its causal past shows, with the preds that past
    gives, so concurrent sets conflict and merged ones overwrite."""

    def __init__(self, seed, n_actors, merge):
        self.rng = random.Random(seed)
        self.actors = [_actor(i) for i in range(n_actors)]
        self.merge = merge
        self.last = {}        # actor -> hash of its newest change
        self.seq = {}
        self.past = {}        # hash -> frozenset of hashes, itself included
        self.ops = {}         # hash -> (key, opId, preds)
        self.max_op = {}      # hash -> greatest counter in its past
        self.value = 0

    def change(self, actor=None):
        rng = self.rng
        actor = actor or rng.choice(self.actors)
        deps = [self.last[actor]] if actor in self.last else []
        others = [a for a in self.last if a != actor]
        if others and rng.random() < self.merge:
            other = self.last[rng.choice(others)]
            if other not in deps:
                deps.append(other)
        past = frozenset().union(*(self.past[d] for d in deps)) \
            if deps else frozenset()
        key = rng.choice(KEYS)
        on_key = [self.ops[h] for h in past if self.ops[h][0] == key]
        killed = {p for _k, _id, preds in on_key for p in preds}
        preds = sorted(op_id for _k, op_id, _p in on_key
                       if op_id not in killed)
        start = max((self.max_op[d] for d in deps), default=0) + 1
        self.seq[actor] = self.seq.get(actor, 0) + 1
        self.value += 1
        buf = encode_change({
            'actor': actor, 'seq': self.seq[actor], 'startOp': start,
            'time': 0, 'message': '', 'deps': sorted(deps),
            'ops': [{'action': 'set', 'obj': '_root', 'key': key,
                     'value': self.value, 'datatype': 'int',
                     'pred': preds}]})
        digest = decode_change(buf)['hash']
        self.last[actor] = digest
        self.past[digest] = past | {digest}
        self.ops[digest] = (key, f'{start}@{actor}', preds)
        self.max_op[digest] = start
        return buf


def make_log(seed, n_actors, n_changes, merge):
    log = _Log(seed, n_actors, merge)
    # every actor writes at least once, so the log has its concurrency
    bufs = [log.change(actor) for actor in log.actors]
    bufs += [log.change() for _ in range(n_changes - n_actors)]
    return bufs


def _no_dag(monkeypatch):
    """`native.dag_gate` answering all-false: every document the chain
    check refuses takes the Python gate, as before the kernel existed."""
    real = native.dag_gate

    def all_false(*args):
        out = real(*args)
        if out is None:
            return None
        ok, off, heads = out
        return np.zeros_like(ok), np.zeros_like(off), heads[:0]
    monkeypatch.setattr(native, 'dag_gate', all_false)


def _fleet_run(batches, index=False):
    """Apply `batches` (each a per-document list of buffers) one call
    after the other, with a frontier index attached first where asked."""
    n_docs = len(batches[0])
    fleet = DocFleet(doc_capacity=n_docs, key_capacity=len(KEYS) + 2)
    handles = init_docs(n_docs, fleet)
    if index:
        ix = fleet.frontier_index()
        for handle in handles:
            ix.space_of(handle['state']._impl)
    for per_doc in batches:
        handles, _patches = apply_changes_docs(handles, per_doc,
                                               mirror=False)
    return fleet, handles


def _observe(fleet, handles, hashes_by_doc):
    """Everything a caller can read back, per document. Handle heads
    first: they are the commit's own answer, before any read folds or
    rebuilds anything."""
    seen = []
    views = materialize_docs(handles)
    for d, handle in enumerate(handles):
        state = handle['state']
        impl = state._impl
        seen.append({
            'handle_heads': list(handle['heads']),
            'heads': list(state.heads),
            'clock': dict(state.clock),
            'max_op': state.max_op,
            'changes': [bytes(b) for b in state.changes],
            'save': bytes(state.save()),
            'get_changes': [bytes(b) for b in state.get_changes([])],
            'missing': state.get_missing_deps(),
            'view': views[d],
            'queue': len(state.queue),
            'member': None if fleet._hash_index is None else
            [bool(x) for x in impl.probe_hashes(
                hashes_by_doc[d] + ['ee' * 32])],
        })
    return seen


def _host_run(batches):
    n_docs = len(batches[0])
    docs = [host.init() for _ in range(n_docs)]
    for per_doc in batches:
        for d, buffers in enumerate(per_doc):
            if buffers:
                docs[d], _patch = host.apply_changes(docs[d], buffers)
    return docs


def _host_view(doc):
    """Last-writer-wins over the host patch's conflict sets: the greatest
    opId by (counter, actor), which is what the device grid keeps."""
    props = host.get_patch(doc)['diffs']['props']
    view = {}
    for key, by_op in props.items():
        if by_op:
            ctr, actor = max((int(op.split('@')[0]), op.split('@')[1])
                             for op in by_op)
            view[key] = by_op[f'{ctr}@{actor}']['value']
    return view


def _hashes(batches, n_docs):
    return [[decode_change(b)['hash'] for per_doc in batches
             for b in per_doc[d]] for d in range(n_docs)]


# name: (actors, changes a document, merge probability, frontier index)
SHAPES = {
    'two-actors-no-merge': (2, 12, 0.0, False),
    'three-actors-merges': (3, 18, 0.35, False),
    'five-actors-merges': (5, 24, 0.25, False),       # past CLOCK_LANES
    'four-actors-index': (4, 16, 0.3, True),
}


@pytest.mark.parametrize('n_batches', [1, 2], ids=['one-batch', 'two-batches'])
@pytest.mark.parametrize('shape', sorted(SHAPES))
@pytest.mark.parametrize('seed', [11, 2147483659])
def test_dag_route_equals_python_gate_and_host(monkeypatch, seed, shape,
                                               n_batches):
    n_actors, n_changes, merge, index = SHAPES[shape]
    assert (n_actors > DocFleet().doc_cols.CLOCK_LANES) == \
        (shape == 'five-actors-merges')
    n_docs = 3
    logs = [make_log(seed + d, n_actors, n_changes, merge)
            for d in range(n_docs)]
    if n_batches == 1:
        batches = [logs]
    else:
        # cut where the frontier has several heads, so the second batch
        # lands on a multi-head document
        batches = [[log[:n_actors + 2] for log in logs],
                   [log[n_actors + 2:] for log in logs]]
    hashes = _hashes(batches, n_docs)

    fleet, handles = _fleet_run(batches, index)
    dag = _observe(fleet, handles, hashes)
    took = fleet.metrics.offchain_dag
    off_chain = fleet.metrics.offchain_native + \
        fleet.metrics.offchain_heads + fleet.metrics.offchain_seq
    # the first batch: every document. A second batch is taken unless a
    # change follows one that is in history and no head any more (its
    # actor's last change, merged by another since): the Python gate's
    if n_batches == 1 or merge == 0.0:
        assert took == off_chain == n_docs * n_batches
    else:
        assert n_docs <= took <= off_chain
    assert fleet.metrics.fallbacks == 0 and fleet.metrics.turbo_calls == \
        n_batches
    # of the documents taken, only actor populations past the clock
    # lanes go through a counted loop
    if n_actors <= fleet.doc_cols.CLOCK_LANES:
        assert fleet.metrics.turbo_commit_fallback_docs == off_chain - took
    if n_batches == 2:
        first = _observe(*_fleet_run(batches[:1]), hashes)
        assert all(len(doc['heads']) > 1 for doc in first), \
            'the cut is meant to leave several heads'

    _no_dag(monkeypatch)
    py_fleet, py_handles = _fleet_run(batches, index)
    python = _observe(py_fleet, py_handles, hashes)
    assert py_fleet.metrics.offchain_dag == 0
    assert py_fleet.metrics.turbo_commit_fallback_docs >= n_docs
    assert dag == python

    host_docs = _host_run(batches)
    for d, doc in enumerate(host_docs):
        assert dag[d]['heads'] == host.get_heads(doc) == dag[d]['handle_heads']
        assert dag[d]['clock'] == doc['state'].clock
        assert dag[d]['max_op'] == doc['state'].max_op
        assert dag[d]['changes'] == [bytes(b) for b in
                                     host.get_all_changes(doc)]
        assert dag[d]['save'] == bytes(host.save(doc))
        assert dag[d]['missing'] == host.get_missing_deps(doc) == []
        assert dag[d]['view'] == _host_view(doc)
        assert dag[d]['queue'] == 0
        if index:
            assert dag[d]['member'] == [True] * len(hashes[d]) + [False]


def test_a_merge_change_collapses_the_frontier_to_one_head():
    """Branches and the change that merges them in ONE batch: off the
    chain, DAG-ordered, and the frontier is a single columnar head."""
    log = _Log(5, 2, 0.0)
    a, b = log.actors
    bufs = [log.change(a), log.change(b), log.change(a), log.change(b)]
    log.merge = 1.0
    bufs.append(log.change(a))
    assert len(decode_change(bufs[-1])['deps']) == 2
    fleet, handles = _fleet_run([[bufs]])
    assert fleet.metrics.offchain_dag == 1
    impl = handles[0]['state']._impl
    assert fleet.doc_cols.head_n[impl.slot] == 1
    assert handles[0]['heads'] == [decode_change(bufs[-1])['hash']]
    (doc,) = _host_run([[bufs]])
    assert bytes(handles[0]['state'].save()) == bytes(host.save(doc))


def test_a_second_batch_onto_two_heads_with_a_merge_and_a_sibling():
    """The frontier [a2, b2] passed in as the ragged blob: a3 merges both
    heads, b3 follows b2 alone; both are DAG-ordered, the new frontier is
    [a3, b3] again."""
    log = _Log(6, 2, 0.0)
    a, b = log.actors
    first = [log.change(a), log.change(b), log.change(a), log.change(b)]
    log.merge = 1.0
    a3 = log.change(a)
    log.merge = 0.0
    second = [a3, log.change(b)]
    assert [len(decode_change(c)['deps']) for c in second] == [2, 1]
    fleet, handles = _fleet_run([[first], [second]])
    assert fleet.metrics.offchain_dag == 2
    assert fleet.metrics.turbo_commit_fallback_docs == 0
    assert handles[0]['heads'] == sorted(decode_change(c)['hash']
                                         for c in second)
    (doc,) = _host_run([[first + second]])
    assert bytes(handles[0]['state'].save()) == bytes(host.save(doc))
    assert handles[0]['state'].clock == {a: 3, b: 3}


def test_chain_documents_never_reach_the_dag_gate(monkeypatch):
    def refuse(*_args):
        raise AssertionError('a chain-shaped batch asked the DAG gate')
    monkeypatch.setattr(native, 'dag_gate', refuse)
    logs = [make_log(3 + d, 1, 6, 0.0) for d in range(4)]
    fleet, _handles = _fleet_run([logs])
    assert fleet.metrics.offchain_dag == 0
    assert fleet.metrics.turbo_commit_fallback_docs == 0


# ---------------------------------------------------------------------------
# what the kernel must refuse: the Python gate, with its outcome
# ---------------------------------------------------------------------------

def _two_branches(seed=7, n=8):
    return make_log(seed, 2, n, 0.0)


def _refused(batches, monkeypatch):
    """Run with the kernel as it is, then with it forced off: a refused
    shape reads the same either way, and the kernel took nothing."""
    n_docs = len(batches[0])
    hashes = _hashes(batches, n_docs)
    fleet, handles = _fleet_run(batches)
    got = _observe(fleet, handles, hashes)
    with monkeypatch.context() as patch:
        _no_dag(patch)
        py_fleet, py_handles = _fleet_run(batches)
        assert got == _observe(py_fleet, py_handles, hashes)
    return fleet, got


def test_out_of_order_delivery_takes_the_python_gate(monkeypatch):
    log = _two_branches()
    late = list(log)
    # an actor's second change before its first
    first = next(i for i, b in enumerate(log)
                 if decode_change(b)['seq'] == 2)
    dep = decode_change(log[first])['deps'][0]
    earlier = next(i for i, b in enumerate(log)
                   if decode_change(b)['hash'] == dep)
    late[first], late[earlier] = late[earlier], late[first]
    fleet, got = _refused([[late]], monkeypatch)
    assert fleet.metrics.offchain_dag == 0
    assert fleet.metrics.turbo_commit_fallback_docs == 1
    (doc,) = _host_run([[late]])
    assert got[0]['heads'] == host.get_heads(doc)
    assert got[0]['save'] == bytes(host.save(doc))
    assert sorted(got[0]['changes']) == sorted(log)


def test_a_dependency_outside_batch_and_heads_queues_then_drains(monkeypatch):
    log = _two_branches()
    held = next(i for i, b in enumerate(log) if decode_change(b)['seq'] == 2)
    missing = decode_change(log[held])['deps'][0]
    gap = next(i for i, b in enumerate(log)
               if decode_change(b)['hash'] == missing)
    without = [b for i, b in enumerate(log) if i != gap]
    fleet, got = _refused([[without]], monkeypatch)
    assert fleet.metrics.offchain_dag == 0
    assert got[0]['queue'] > 0 and got[0]['missing'] == [missing]
    # the missing change arrives: the queue drains, and it all adds up
    fleet, got = _refused([[without], [[log[gap]]]], monkeypatch)
    # ... on the turbo path (PR 37): the held-back changes are gated again
    # behind the change that frees them, and that run is DAG-ordered
    assert fleet.metrics.fallbacks == 0 and fleet.metrics.exact_calls == 0
    assert fleet.metrics.turbo_calls == 2 and fleet.metrics.offchain_dag == 1
    assert fleet.metrics.heldback_changes == fleet.metrics.drained_changes > 0
    (doc,) = _host_run([[log]])
    assert got[0]['queue'] == 0 and got[0]['missing'] == []
    assert got[0]['heads'] == host.get_heads(doc)
    assert got[0]['view'] == _host_view(doc)
    assert sorted(got[0]['changes']) == sorted(log)


def test_a_duplicate_inside_the_batch_takes_the_python_gate(monkeypatch):
    log = _two_branches()
    doubled = log[:5] + [log[2]] + log[5:]
    fleet, got = _refused([[doubled]], monkeypatch)
    assert fleet.metrics.offchain_dag == 0
    (doc,) = _host_run([[log]])
    assert got[0]['changes'] == log
    assert got[0]['save'] == bytes(host.save(doc))


def test_a_change_already_applied_and_sent_again_is_skipped(monkeypatch):
    log = _two_branches(n=10)
    again = [log[:6], log[4:]]                  # 4 and 5 arrive twice
    fleet, got = _refused([[b] for b in again], monkeypatch)
    assert fleet.metrics.offchain_dag == 1      # the first call alone
    (doc,) = _host_run([[log]])
    assert got[0]['changes'] == log
    assert got[0]['save'] == bytes(host.save(doc))
    # ... and so is a head sent again on its own with what follows it
    heads_again = [log[:6], log[5:]]
    fleet, got = _refused([[b] for b in heads_again], monkeypatch)
    assert fleet.metrics.offchain_dag == 1
    assert got[0]['changes'] == log


def _raw(actor, seq, deps, key='k0', value=1):
    buf = encode_change({
        'actor': actor, 'seq': seq, 'startOp': seq, 'time': 0,
        'message': '', 'deps': list(deps),
        'ops': [{'action': 'set', 'obj': '_root', 'key': key,
                 'value': value, 'datatype': 'int', 'pred': []}]})
    return buf, decode_change(buf)['hash']


@pytest.mark.parametrize('second_seq,words', [(3, 'Skipped sequence number'),
                                              (1, 'Reuse of sequence number')],
                         ids=['skipped-seq', 'reused-seq'])
def test_a_bad_seq_raises_typed_and_rolls_every_document_back(second_seq,
                                                              words):
    a, b = _actor(0), _actor(1)
    a1, a1_hash = _raw(a, 1, [])
    b1, _ = _raw(b, 1, [], key='k1')
    bad, _ = _raw(a, second_seq, [a1_hash], value=2)
    per_doc = [_two_branches(), [a1, b1, bad], make_log(9, 1, 4, 0.0)]
    fleet = DocFleet(doc_capacity=3, key_capacity=len(KEYS) + 2)
    handles = init_docs(3, fleet)
    with pytest.raises(InvalidChange, match=words) as raised:
        apply_changes_docs(handles, per_doc, mirror=False)
    assert raised.value.doc_index == 1
    # document 0 is DAG-ordered and was taken; nothing of it was written
    assert fleet.metrics.offchain_dag == 1
    for handle in handles:
        state = handle['state']
        assert state.heads == [] and state.clock == {}
        assert state.max_op == 0 and list(state.changes) == []
        assert not handle.get('frozen')
    assert materialize_docs(handles) == [{}, {}, {}]
    # the same batch, quarantining: the bad document alone is rejected
    out, _patches, errors = apply_changes_docs(
        handles, per_doc, mirror=False, on_error='quarantine')
    assert [e is not None for e in errors] == [False, True, False]
    assert len(out[0]['heads']) == 2 and out[1]['heads'] == []
    (doc,) = _host_run([[per_doc[0]]])
    assert bytes(out[0]['state'].save()) == bytes(host.save(doc))


# ---------------------------------------------------------------------------
# the kernel alone, on hand-built columns (it never hashes: any 32 bytes do)
# ---------------------------------------------------------------------------

def _h(i):
    return bytes([i]) * 32


def _columns(docs):
    """`docs`: per document a list of (hash, deps). Returns the columns
    `native.dag_gate` reads."""
    doc_off = np.cumsum([0] + [len(d) for d in docs])
    changes = [c for d in docs for c in d]
    hash32 = np.frombuffer(b''.join(h for h, _ in changes) or b'\0' * 32,
                           dtype=np.uint8).reshape(-1, 32)[:len(changes)]
    deps_off = np.cumsum([0] + [len(deps) for _, deps in changes])
    deps_blob = b''.join(dep for _, deps in changes for dep in deps)
    return doc_off, hash32, deps_off, deps_blob


KERNEL_CASES = {
    # name: (changes, current heads, verdict, new heads)
    'two-roots': ([(_h(9), []), (_h(3), [])], [], True, [_h(3), _h(9)]),
    'an-old-head-nothing-names-stays':
        ([(_h(5), [_h(7)])], [_h(7), _h(2)], True, [_h(2), _h(5)]),
    'a-merge-of-both-old-heads':
        ([(_h(5), [_h(7), _h(2)])], [_h(2), _h(7)], True, [_h(5)]),
    'one-columnar-head': ([(_h(5), [_h(7)]), (_h(6), [_h(7)])], [_h(7)],
                          True, [_h(5), _h(6)]),
    'a-dep-nobody-has': ([(_h(5), [_h(8)])], [_h(7)], False, []),
    'a-dep-on-a-later-change':
        ([(_h(5), [_h(6)]), (_h(6), [])], [], False, []),
    'the-same-hash-twice': ([(_h(5), []), (_h(5), [])], [], False, []),
    'a-current-head-sent-again': ([(_h(7), [])], [_h(7)], False, []),
}


@pytest.mark.parametrize('case', sorted(KERNEL_CASES))
def test_the_kernel_on_hand_built_columns(case):
    changes, heads, verdict, new_heads = KERNEL_CASES[case]
    # document 1 is the case; 0 and 2 are DAG-ordered, 2 is not marked
    docs = [[(_h(40), []), (_h(41), [])], changes,
            [(_h(50), []), (_h(51), [])]]
    head32 = np.zeros((3, 32), dtype=np.uint8)
    head_n = np.zeros(3, dtype=np.int32)
    multi = {}
    if len(heads) == 1:
        head32[1] = np.frombuffer(heads[0], dtype=np.uint8)
        head_n[1] = 1
    elif heads:
        head_n[1] = -1
        multi[1] = b''.join(heads)
    cand = np.array([1, 1, 0], dtype=np.uint8)
    ok, off, nh = native.dag_gate(*_columns(docs), head32, head_n, multi,
                                  cand)
    assert ok.tolist() == [True, verdict, False]
    got = [[nh[j].tobytes() for j in range(off[d], off[d + 1])]
           for d in range(3)]
    assert got == [[_h(40), _h(41)], new_heads, []]


def test_the_kernel_refuses_columns_that_disagree():
    doc_off, hash32, deps_off, deps_blob = _columns([[(_h(1), [])]])
    with pytest.raises(ValueError, match='disagree'):
        native.dag_gate(doc_off, hash32, deps_off[:-1], deps_blob,
                        np.zeros((1, 32), np.uint8), np.zeros(1, np.int32),
                        {}, np.ones(1, np.uint8))
