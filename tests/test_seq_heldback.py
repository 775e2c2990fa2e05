"""Held-back changes live on the turbo path (PR 37).

Automerge's sync protocol withholds a change now and then (a Bloom false
positive): its dependents arrive first and wait in the document's queue
until the change is asked for again. A call that brings such changes, or
meets a queue that is not empty, stays on the device path for ALL its
documents: the general gate applies what is causally ready, in a causal
order, and queues the rest. Every case is held to the host backend
(`backend/op_set.py`) after EVERY call: heads, clock, greatest op, queue
length, `get_missing_deps()`, the text (against `reference_text.Rga` over
the changes the oracle applied), and `get_patch` and `save()` once nothing
is queued; and to its routing: `turbo_calls` moving, `fallbacks` 0,
`exact_calls` 0, no row inexact.
"""

import os
import random
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, 'benchmarks'))
sys.path.insert(0, os.path.join(ROOT, 'tests'))

import automerge_tpu as am                                       # noqa: E402
from automerge_tpu import backend as host                        # noqa: E402
from automerge_tpu import native                                 # noqa: E402
from automerge_tpu.columnar import decode_change, encode_change  # noqa: E402
from automerge_tpu.errors import DanglingPred, InvalidChange     # noqa: E402
from automerge_tpu.fleet import backend as fleet_backend         # noqa: E402
from automerge_tpu.fleet.backend import (                        # noqa: E402
    DocFleet, apply_changes_docs, init_docs, materialize_docs)
from test_seq_concurrent import Room, rga_text, typing           # noqa: E402

pytestmark = pytest.mark.skipif(not native.available(),
                                reason='the turbo path is the native codec')

SEEDS = [3, 17, 2147483659]


def hash_of(buf):
    return decode_change(bytes(buf))['hash']


def some_rounds(room, rng, n_rounds, k=4):
    """`n_rounds` rounds of a two-writer room: [[writer 0's changes, writer
    1's], ...]; a writer's first change of a round follows both heads of
    the round before (the writers merge between rounds)."""
    out = []
    for _ in range(n_rounds):
        out.append(room.round([
            typing(rng, len(str(doc['text'])), k, deletes=0.25)
            for doc in room.docs]))
        room.sync()
    return out


class MapRoom:
    """A map document with two writers setting keys, one change a set; the
    same shape of rounds as `Room`'s."""

    def __init__(self, n_writers=2):
        self.actors = [f'{0x31 + 7 * i:02x}' * 16 for i in range(n_writers)]
        first = am.from_({'k0': 0}, self.actors[0])
        self.base = list(am.get_all_changes(first))
        saved = am.save(first)
        self.docs = [first] + [am.load(saved, a) for a in self.actors[1:]]
        self.n = 0

    def round(self, k):
        out = []
        for w in range(len(self.docs)):
            doc, made = self.docs[w], []
            for _ in range(k):
                self.n += 1
                key, value = f'k{self.n % 5}', self.n

                def edit(d, key=key, value=value):
                    d[key] = value
                doc = am.change(doc, edit)
                made.append(am.get_last_local_change(doc))
            self.docs[w] = doc
            out.append(made)
        for w in range(len(self.docs)):
            for v in range(len(self.docs)):
                if v != w:
                    self.docs[w] = am.merge(self.docs[w], self.docs[v])
        return out


class Held:
    """A fleet of documents beside the host oracle's: `call(per_doc)`
    applies one `apply_changes_docs(mirror=False)` to the fleet and the
    same changes to every oracle, then holds the two to each other."""

    def __init__(self, bases, text=True):
        self.n = len(bases)
        self.fleet = DocFleet(doc_capacity=max(self.n, 2), key_capacity=8)
        self.handles = init_docs(self.n, self.fleet)
        self.oracles = [host.init() for _ in range(self.n)]
        self.text = [text] * self.n if isinstance(text, bool) else text
        self.calls = 0
        self.call(bases)

    def call(self, per_doc, general=None):
        """`general`: how many documents the call should send through the
        general gate (not checked where None)."""
        before = self.fleet.metrics.snapshot()
        self.handles, _ = apply_changes_docs(self.handles, per_doc,
                                             mirror=False)
        moved = self.fleet.metrics.delta(before)
        self.calls += 1
        assert moved['turbo_calls'] == 1
        assert moved['fallbacks'] == moved['exact_calls'] == 0
        assert moved['mirror_rebuilds'] == moved['promotions'] == 0
        if general is not None:
            assert moved['turbo_commit_fallback_docs'] == general
        for d, changes in enumerate(per_doc):
            if changes:
                self.oracles[d], _ = host.apply_changes(self.oracles[d],
                                                        list(changes))
        self.hold()
        return moved

    def hold(self):
        views = materialize_docs(self.handles)
        for d in range(self.n):
            want = self.oracles[d]['state']
            got = self.handles[d]['state']
            assert sorted(fleet_backend.get_heads(self.handles[d])) == \
                sorted(host.get_heads(self.oracles[d]))
            assert dict(got.clock) == dict(want.clock)
            assert got._impl.max_op == want.max_op
            assert len(got._impl.queue) == len(want.queue)
            assert sorted(hash_of(c['buffer']) for c in got._impl.queue) == \
                sorted(c['hash'] for c in want.queue)
            assert fleet_backend.get_missing_deps(self.handles[d]) == \
                host.get_missing_deps(self.oracles[d])
            if self.text[d]:
                assert views[d]['text'] == rga_text(
                    host.get_all_changes(self.oracles[d]))
        assert self.fleet.metrics.fallbacks == 0
        assert self.fleet.metrics.exact_calls == 0
        assert self.fleet.metrics.turbo_calls == self.calls
        for st in self.fleet.seq_pools.pools.values():
            assert not np.asarray(st.inexact).any()

    def queued(self, d=0):
        return len(self.handles[d]['state']._impl.queue)

    def hold_reads(self):
        """Nothing is queued any more: patches and saved bytes, too."""
        for d in range(self.n):
            assert self.queued(d) == 0
            assert fleet_backend.get_patch(self.handles[d]) == \
                host.get_patch(self.oracles[d])
            assert bytes(fleet_backend.save(self.handles[d])) == \
                bytes(host.save(self.oracles[d]))
        assert self.fleet.metrics.fallbacks == 0
        assert self.fleet.metrics.exact_calls == 0


def less(round_, withheld):
    """A round's changes in buffer order (writer 0's chain, then writer
    1's) without the `withheld` ones."""
    drop = {bytes(b) for b in withheld}
    return [b for chain in round_ for b in chain if bytes(b) not in drop]


# ---------------------------------------------------------------------------
# one change withheld, then delivered ahead of the next round
# ---------------------------------------------------------------------------

@pytest.mark.parametrize('seed', SEEDS[1:])
@pytest.mark.parametrize('where', ['middle', 'start', 'end'])
def test_a_withheld_change_arrives_a_call_later(where, seed):
    rng = random.Random(seed)
    room = Room(2)
    first, second, third = some_rounds(room, rng, 3, k=5)
    at = {'middle': 2, 'start': 0, 'end': 4}[where]
    gone = first[rng.randrange(2)][at]
    held = Held([room.base])
    moved = held.call([less(first, [gone])])
    # the chain's tail waits for it (nothing does behind a chain's end)
    assert held.queued() == 4 - at
    assert moved['heldback_changes'] == 4 - at
    assert moved['heldback_docs'] == (1 if at < 4 else 0)
    if at < 4:
        assert fleet_backend.get_missing_deps(held.handles[0]) == \
            [hash_of(gone)]
    moved = held.call([[gone] + less(second, [])])
    assert held.queued() == 0
    assert moved['drained_changes'] == 4 - at and moved['heldback_docs'] == 0
    held.call([less(third, [])], general=0)
    held.hold_reads()


@pytest.mark.parametrize('seed', SEEDS)
def test_both_writers_withheld_in_one_round(seed):
    rng = random.Random(seed)
    room = Room(2)
    first, second = some_rounds(room, rng, 2, k=6)
    gone = [first[0][rng.randrange(5)], first[1][rng.randrange(5)]]
    held = Held([room.base])
    held.call([less(first, gone)], general=1)
    assert held.queued() >= 2
    assert fleet_backend.get_missing_deps(held.handles[0]) == \
        sorted(hash_of(b) for b in gone)
    # one comes back alone: its own chain drains, the other still waits
    held.call([[gone[1]]])
    assert fleet_backend.get_missing_deps(held.handles[0]) == \
        [hash_of(gone[0])]
    held.call([[gone[0]] + less(second, [])], general=1)
    held.hold_reads()


@pytest.mark.parametrize('seed', SEEDS)
def test_a_new_withhold_while_the_queue_is_not_empty(seed):
    """The cell's steady state: every call delivers what the last one
    withheld, then its own round less what it withholds."""
    rng = random.Random(seed)
    room = Room(2)
    rounds = some_rounds(room, rng, 6, k=5)
    held = Held([room.base])
    late = []
    heldback = drained = 0
    for round_ in rounds:
        gone = [round_[rng.randrange(2)][rng.randrange(4)]]
        moved = held.call([late + less(round_, gone)], general=1)
        assert held.queued() >= 1
        heldback += moved['heldback_changes']
        drained += moved['drained_changes']
        late = gone
    moved = held.call([late])
    assert heldback == drained + moved['drained_changes'] > 0
    held.hold_reads()


@pytest.mark.parametrize('seed', SEEDS)
def test_two_changes_swapped_inside_one_call(seed):
    """Nothing is missing, but the applied order is not the buffer's: a
    change stands before the change it depends on."""
    rng = random.Random(seed)
    room = Room(2)
    first, second = some_rounds(room, rng, 2, k=5)
    held = Held([room.base])
    swapped = less(first, [])
    i = rng.randrange(4)
    swapped[i], swapped[i + 1] = swapped[i + 1], swapped[i]
    moved = held.call([swapped], general=1)
    assert moved['heldback_changes'] == moved['drained_changes'] == 0
    # ... and a whole round back to front
    held.call([less(second, [])[::-1]], general=1)
    held.hold_reads()


@pytest.mark.parametrize('seed', SEEDS)
def test_a_queued_change_delivered_again_is_applied_once(seed):
    rng = random.Random(seed)
    room = Room(2)
    first, second = some_rounds(room, rng, 2, k=5)
    gone = first[0][1]
    held = Held([room.base])
    held.call([less(first, [gone])])
    assert held.queued() == 3
    # a dependent comes again while it still waits (the oracle queues it
    # twice, and so does the fleet), then with the change that frees it
    held.call([[first[0][3]]])
    assert held.queued() == 4
    held.call([[first[0][2], gone] + less(second, [])])
    # ... and once more when it is long applied
    held.call([[first[0][2], gone]], general=1)
    held.hold_reads()
    applied = [hash_of(b) for b in
               fleet_backend.get_all_changes(held.handles[0])]
    assert len(applied) == len(set(applied)) == len(room.base) + 20


@pytest.mark.parametrize('seed', SEEDS)
def test_a_change_never_delivered(seed):
    """Round after round piles up behind the one change that never comes;
    the other writer's rounds queue too (their first change follows both
    heads), and the document stays what the oracle says it is."""
    rng = random.Random(seed)
    room = Room(2)
    rounds = some_rounds(room, rng, 4, k=4)
    gone = rounds[0][1][2]
    held = Held([room.base])
    held.call([less(rounds[0], [gone])])
    for n, round_ in enumerate(rounds[1:], start=1):
        held.call([less(round_, [])], general=1)
        assert held.queued() == 1 + 8 * n
        assert fleet_backend.get_missing_deps(held.handles[0]) == \
            [hash_of(gone)]
    held.call([[gone]])
    held.hold_reads()


# ---------------------------------------------------------------------------
# a faulty change out of the queue: typed, and the queue as it was
# ---------------------------------------------------------------------------

@pytest.mark.parametrize('seed', SEEDS[:2])
@pytest.mark.parametrize('fault', ['seq_gap', 'dangling_pred'])
def test_a_faulty_drained_change_raises_typed_and_restores_the_queue(fault,
                                                                     seed):
    rng = random.Random(seed)
    rooms = [Room(2, title='draft'), Room(2, title='draft')]
    rounds = [some_rounds(room, rng, 1, k=4)[0] for room in rooms]
    gone = rounds[1][0][1]
    last = decode_change(bytes(rounds[1][0][-1]))
    bad = encode_change({
        'actor': last['actor'], 'time': 0, 'message': '',
        'deps': [last['hash']], 'startOp': last['startOp'] + 1,
        'seq': last['seq'] + (2 if fault == 'seq_gap' else 1),
        'ops': [{'action': 'del', 'obj': '_root', 'key': 'title',
                 'pred': [f"99@{last['actor']}" if fault == 'dangling_pred'
                          else f'2@{rooms[1].actors[0]}']}]})
    fleet = DocFleet(doc_capacity=2, key_capacity=8)
    handles = init_docs(2, fleet)
    handles, _ = apply_changes_docs(handles, [r.base for r in rooms],
                                    mirror=False)
    # the faulty change waits behind the chain's tail
    handles, _ = apply_changes_docs(
        handles, [less(rounds[0], []), less(rounds[1], [gone]) + [bad]],
        mirror=False)
    impl = handles[1]['state']._impl
    assert len(impl.queue) == 3
    was = (fleet_backend.get_heads(handles[1]), dict(impl.clock),
           [hash_of(c['buffer']) for c in impl.queue])
    other = fleet_backend.get_heads(handles[0])
    with pytest.raises(DanglingPred if fault == 'dangling_pred'
                       else InvalidChange,
                       match='no matching operation for pred'
                       if fault == 'dangling_pred'
                       else 'Skipped sequence number') as raised:
        apply_changes_docs(handles, [[], [gone]], mirror=False)
    assert raised.value.doc_index == 1
    assert (fleet_backend.get_heads(handles[1]), dict(impl.clock),
            [hash_of(c['buffer']) for c in impl.queue]) == was
    assert fleet_backend.get_heads(handles[0]) == other
    assert fleet_backend.get_missing_deps(handles[1]) == [hash_of(gone)]
    assert fleet.metrics.fallbacks == 0 and fleet.metrics.exact_calls == 0


# ---------------------------------------------------------------------------
# many documents in one call, maps among them
# ---------------------------------------------------------------------------

@pytest.mark.parametrize('seed', SEEDS[1:])
def test_sixteen_documents_five_of_them_holding_back(seed):
    rng = random.Random(seed)
    rooms = [Room(2) for _ in range(12)] + [MapRoom() for _ in range(4)]
    is_text = [isinstance(room, Room) for room in rooms]
    held = Held([room.base for room in rooms], text=is_text)
    holding = sorted(rng.sample(range(12), 4) + [12 + rng.randrange(4)])
    late = [[] for _ in rooms]
    for n in range(2):
        per_doc = []
        for d, room in enumerate(rooms):
            if is_text[d]:
                round_ = some_rounds(room, rng, 1, k=3)[0]
            else:
                round_ = room.round(3)
            gone = [round_[rng.randrange(2)][rng.randrange(2)]] \
                if d in holding else []
            per_doc.append(late[d] + less(round_, gone))
            late[d] = gone
        # the eleven others are on the chain or DAG-ordered: untouched by
        # the general gate
        moved = held.call(per_doc, general=5)
        assert moved['heldback_docs'] == 5
        assert sorted(d for d in range(16) if held.queued(d)) == holding
    held.call(late)
    held.hold_reads()


@pytest.mark.parametrize('seed', SEEDS)
def test_a_map_document_drains_on_the_turbo_path(seed):
    rng = random.Random(seed)
    room = MapRoom()
    held = Held([room.base], text=False)
    first, second = room.round(5), room.round(5)
    gone = first[rng.randrange(2)][rng.randrange(4)]
    moved = held.call([less(first, [gone])], general=1)
    assert moved['heldback_changes'] >= 1
    views = materialize_docs(held.handles)
    moved = held.call([[gone] + less(second, [])], general=1)
    assert moved['drained_changes'] >= 1
    held.hold_reads()
    assert materialize_docs(held.handles)[0] != views[0]
    assert materialize_docs(held.handles)[0] == \
        {key: value for key, value in am.merge(*room.docs).items()}


# ---------------------------------------------------------------------------
# a loaded document, the register engine, the journal, and the one exit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize('seed', SEEDS)
def test_a_loaded_two_head_document_holds_back_and_drains(seed):
    """The benchmark's shape: the history comes through `load_docs` (parked,
    never replayed), the general gate's first visit reads it, and the
    staged commit appends behind it."""
    from automerge_tpu.fleet import loader
    rng = random.Random(seed)
    room = Room(2)
    some_rounds(room, rng, 3, k=6)
    start = am.save(room.docs[0])          # merged, and left with two heads
    first, second = some_rounds(room, rng, 2, k=5)
    fleet = DocFleet(doc_capacity=2, key_capacity=8)
    handles = loader.load_docs([start], fleet)
    oracle = host.load(start)
    assert len(fleet_backend.get_heads(handles[0])) == 2
    gone = first[rng.randrange(2)][1]
    for n, call in enumerate(([less(first, [gone])],
                              [[gone] + less(second, [])])):
        handles, _ = apply_changes_docs(handles, call, mirror=False)
        oracle, _ = host.apply_changes(oracle, call[0])
        state, want = handles[0]['state'], oracle['state']
        assert fleet.metrics.turbo_calls == n + 1
        assert fleet.metrics.fallbacks == fleet.metrics.exact_calls == 0
        assert fleet.metrics.mirror_rebuilds == 0
        assert sorted(fleet_backend.get_heads(handles[0])) == \
            sorted(host.get_heads(oracle))
        assert dict(state.clock) == dict(want.clock)
        assert len(state.queue) == len(want.queue) == (3 if n == 0 else 0)
        assert fleet_backend.get_missing_deps(handles[0]) == \
            host.get_missing_deps(oracle) == ([hash_of(gone)] if n == 0
                                              else [])
        assert materialize_docs(handles)[0]['text'] == rga_text(
            host.get_all_changes(oracle))
    assert fleet_backend.get_patch(handles[0]) == host.get_patch(oracle)
    assert bytes(fleet_backend.save(handles[0])) == bytes(host.save(oracle))


@pytest.mark.parametrize('seed', SEEDS[1:])
def test_the_register_engine_gets_its_rows_in_applied_order(seed):
    """exact_device: the register batch applies a document's ops as they
    stand, predecessors before what overwrites them. Two writers set the
    same five keys again and again; a round arrives back to front and with
    a change a call late."""
    rng = random.Random(seed)
    room = MapRoom()
    fleet = DocFleet(doc_capacity=2, key_capacity=8, exact_device=True)
    handles = init_docs(1, fleet)
    oracle = host.init()
    first, second = room.round(6), room.round(6)
    gone = first[rng.randrange(2)][rng.randrange(5)]
    calls = [room.base, less(first, [gone])[::-1],
             [gone] + less(second, [])[::-1]]
    for call in calls:
        handles, _ = apply_changes_docs(handles, [call], mirror=False)
        oracle, _ = host.apply_changes(oracle, call)
    assert fleet.metrics.turbo_calls == 3
    assert fleet.metrics.fallbacks == fleet.metrics.exact_calls == 0
    assert not handles[0]['state'].queue
    assert not fleet.inexact_slots()
    assert materialize_docs(handles)[0] == \
        {key: value for key, value in am.merge(*room.docs).items()}
    assert fleet_backend.get_patch(handles[0]) == host.get_patch(oracle)
    assert bytes(fleet_backend.save(handles[0])) == bytes(host.save(oracle))


@pytest.mark.parametrize('on_error', ['raise', 'quarantine'])
def test_a_drained_change_is_journaled_once(tmp_path, on_error):
    from automerge_tpu.fleet.durability import DurableFleet
    rng = random.Random(5)
    room = Room(2)
    first, second = some_rounds(room, rng, 2, k=5)
    gone = first[0][2]
    calls = [room.base, less(first, [gone]), [gone] + less(second, [])]
    path = str(tmp_path / 'dur')
    mgr = DurableFleet(path)
    handles = mgr.init_docs(1)
    records = [mgr.fleet.memory_stats()['journal']['records']]
    for call in calls:
        handles = mgr.apply_changes(handles, [call], mirror=False,
                                    on_error=on_error)[0]
        records.append(mgr.fleet.memory_stats()['journal']['records'])
    assert mgr.fleet.metrics.fallbacks == mgr.fleet.metrics.exact_calls == 0
    assert not handles[0]['state'].queue
    # every buffer of every call, the held-back ones with the call that
    # brought them and not again with the call that drained them
    assert [b - a for a, b in zip(records, records[1:])] == \
        [len(call) for call in calls]
    saved = bytes(fleet_backend.save(handles[0]))
    mgr.close()
    _mgr, recovered, report = DurableFleet.recover(path)
    assert report.ok
    assert bytes(fleet_backend.save(recovered[0])) == saved


def test_the_texts_make_sent_again_does_not_leave_the_device_path():
    """A change that is long applied comes again (the one that made the
    Text among them) beside a round that holds a change back: the copies
    are skipped, not mistaken for makes that wait in the queue."""
    rng = random.Random(9)
    room = Room(2)
    first, second = some_rounds(room, rng, 2, k=5)
    gone = first[1][2]
    held = Held([room.base])
    held.call([room.base + less(first, [gone])], general=1)
    assert held.queued() == 2
    held.call([[gone] + room.base + less(second, [])], general=1)
    held.hold_reads()


def test_an_op_inside_an_object_whose_make_is_held_back_takes_the_exact_path():
    """No causal history gives this (the op's change would follow the
    make's): a change that names an object made by a change it does NOT
    depend on, while that one waits in the queue. The turbo path has no
    row for the object; the call leaves for the exact path, whole, with
    the gate's state restored, and the exact path raises its error."""
    actor, other = '11' * 16, '22' * 16
    base = encode_change({
        'actor': actor, 'seq': 1, 'startOp': 1, 'time': 0, 'message': '',
        'deps': [], 'ops': [{'action': 'set', 'obj': '_root', 'key': 'k',
                             'value': 1, 'datatype': 'int', 'pred': []}]})
    nowhere = 'ab' * 32
    make = encode_change({
        'actor': actor, 'seq': 2, 'startOp': 2, 'time': 0, 'message': '',
        'deps': [nowhere], 'ops': [{'action': 'makeText', 'obj': '_root',
                                    'key': 'text', 'pred': []}]})
    inside = encode_change({
        'actor': other, 'seq': 1, 'startOp': 3, 'time': 0, 'message': '',
        'deps': [hash_of(base)],
        'ops': [{'action': 'set', 'obj': f'2@{actor}', 'elemId': '_head',
                 'insert': True, 'value': 'x', 'pred': []}]})
    fleet = DocFleet(doc_capacity=2, key_capacity=8)
    handles = init_docs(1, fleet)
    handles, _ = apply_changes_docs(handles, [[base]], mirror=False)
    heads = fleet_backend.get_heads(handles[0])
    with pytest.raises(Exception):
        apply_changes_docs(handles, [[make, inside]], mirror=False)
    assert fleet.metrics.turbo_calls == 1 and fleet.metrics.fallbacks == 1
    assert fleet_backend.get_heads(handles[0]) == heads
    assert not handles[0]['state'].queue


@pytest.mark.parametrize('loaded', [False, True])
def test_the_general_gate_asks_history_without_building_the_graph(loaded):
    """Call after call through the general gate and not one hash-graph
    build: the gate's view of history (`_history_index`) is kept up from
    the deferred log, on the chain, DAG-ordered and staged commits alike,
    and is the graph's own key set when something does read the graph."""
    from automerge_tpu.fleet import loader
    rng = random.Random(23)
    room = Room(2)
    history = some_rounds(room, rng, 2, k=5)
    fleet = DocFleet(doc_capacity=2, key_capacity=8)
    if loaded:
        handles = loader.load_docs([am.save(room.docs[0])], fleet)
    else:
        handles = init_docs(1, fleet)
        handles, _ = apply_changes_docs(
            handles, [room.base + [b for r in history for b in less(r, [])]],
            mirror=False)
    rounds_ = some_rounds(room, rng, 6, k=5)
    late, sent = [], 0
    for n, round_ in enumerate(rounds_):
        # every third round comes whole, in order and onto an empty queue:
        # the DAG gate's, which the general gate never sees
        gone = [round_[n % 2][1 + n % 3]] if n % 3 == 0 else []
        call = late + less(round_, gone)
        handles, _ = apply_changes_docs(handles, [call], mirror=False)
        late, sent = gone, sent + len(call)
    impl = handles[0]['state']._impl
    assert fleet.metrics.turbo_commit_fallback_docs == 4
    assert fleet.metrics.offchain_dag >= 2
    # the dependents of a withheld change name a hash that is in neither
    # the run nor the heads: the gate asked, and the index answered
    assert fleet.metrics.history_probes > 0
    assert fleet.metrics.graph_builds == 0 and not impl.change_index_by_hash
    seen = population(impl._history_index())
    assert fleet.metrics.graph_builds == 0
    graph = set(handles[0]['state'].change_index_by_hash)   # builds it
    assert fleet.metrics.graph_builds == 1
    assert seen == graph == population(impl._history_index())
    assert len(graph) == len(room.base) + 20 + sent - len(impl.queue) \
        if not loaded else len(graph) > sent - len(impl.queue)


def population(index):
    """The hashes a history index holds, as the graph's keys are (hex);
    every one of them once, the table as many as the gate has entered."""
    hashes = [row.tobytes().hex() for row in index.rows[:index.n]]
    assert len(set(hashes)) == len(hashes)
    assert int((index.table >= 0).sum()) == index.entered <= index.n
    return set(hashes)


# ---------------------------------------------------------------------------
# the native general gate against the host oracle's gate
# ---------------------------------------------------------------------------

def _a_delivery(rng):
    """An applied history (hashes, heads, clock) and a delivery for it:
    chains of several writers, forks off earlier changes and merges, some
    changes withheld, now and then a change of the history or of the
    delivery itself delivered again, a queue's tail moved ahead, two
    changes swapped, a seq that is not the actor's next."""
    import hashlib
    actors = [f'{a:02x}' * 16 for a in range(rng.randint(1, 4))]
    known, heads, clock, tips, log = {}, [], {}, {}, []

    def change(actor, deps, seq):
        digest = hashlib.sha256(repr(
            (actor, deps, seq, rng.random())).encode()).hexdigest()
        return {'hash': digest, 'deps': list(deps), 'actor': actor,
                'seq': seq}

    for _ in range(rng.randint(0, 6)):
        actor = rng.choice(actors)
        deps = [tips[actor]] if actor in tips else []
        if heads and rng.random() < .3:
            deps = sorted(set(deps + [rng.choice(heads)]))
        c = change(actor, deps, clock.get(actor, 0) + 1)
        known[c['hash']] = len(known)
        clock[actor] = c['seq']
        tips[actor] = c['hash']
        heads = [h for h in heads if h not in deps] + [c['hash']]
        log.append(c)
    ahead, tips, seen, run = dict(clock), dict(tips), list(heads), []
    for _ in range(rng.randint(1, 30)):
        actor = rng.choice(actors)
        deps = [tips[actor]] if actor in tips else []
        toss = rng.random()
        if toss < .15 and seen:
            deps = sorted(set(deps + [rng.choice(seen)]))
        elif toss < .2 and not deps and seen:
            deps = [rng.choice(seen)]
        seq = ahead.get(actor, 0) + 1
        if rng.random() < .02:
            seq += rng.choice([-1, 1])
        c = change(actor, deps, seq)
        ahead[actor], tips[actor] = seq, c['hash']
        seen.append(c['hash'])
        run.append(c)
    run = [c for c in run if rng.random() > .1]
    if log and rng.random() < .2:
        run.insert(rng.randint(0, len(run)), rng.choice(log))
    if run and rng.random() < .2:
        run.insert(rng.randint(0, len(run)), rng.choice(run))
    if len(run) > 2 and rng.random() < .4:
        cut = rng.randint(1, len(run) - 1)
        run = run[cut:] + run[:cut]
    if len(run) > 2 and rng.random() < .3:
        i, j = rng.sample(range(len(run)), 2)
        run[i], run[j] = run[j], run[i]
    return actors, known, sorted(heads), clock, run


def _columns(actors, runs):
    """The parser's per-change columns for `runs`, a document each, as
    `native.general_gate` reads them."""
    changes = [c for run in runs for c in run]
    n = len(changes)
    deps_off = np.zeros(n + 1, dtype=np.int64)
    deps_off[1:] = np.cumsum([len(c['deps']) for c in changes])
    return {
        'doc_off': np.cumsum([0] + [len(run) for run in runs]),
        'hash32': np.frombuffer(
            b''.join(bytes.fromhex(c['hash']) for c in changes),
            dtype=np.uint8).reshape(n, 32),
        'deps_off': deps_off,
        'deps_blob': b''.join(bytes.fromhex(d) for c in changes
                              for d in c['deps']),
        'actor': np.array([actors.index(c['actor']) for c in changes],
                          dtype=np.int32),
        'seq': np.array([c['seq'] for c in changes], dtype=np.int64)}


def _index_of(hashes):
    from automerge_tpu.fleet.hashindex import HistoryIndex, hashes_to_rows
    index = HistoryIndex()
    index.extend(hashes_to_rows(list(hashes)))
    return index


def _native_gate(actors, docs, cand=None):
    """`native.general_gate` over `docs`, per document (known hashes or a
    history index, heads, clock, run): per document the oracle's shape of
    an answer, (applied, left, heads, clock) with the changes as places in
    the run, or the reference's error text; and the questions put to
    history."""
    cols = _columns(actors, [run for _known, _heads, _clock, run in docs])
    n_docs = len(docs)
    head32 = np.zeros((n_docs, 32), dtype=np.uint8)
    head_n = np.zeros(n_docs, dtype=np.int32)
    multi = {}
    for d, (_known, heads, _clock, _run) in enumerate(docs):
        if len(heads) == 1:
            head32[d] = np.frombuffer(bytes.fromhex(heads[0]), dtype=np.uint8)
            head_n[d] = 1
        elif heads:
            head_n[d] = -1
            multi[d] = bytes.fromhex(''.join(heads))
    groups = native.turbo_gate(
        cols['doc_off'], cols['actor'], cols['seq'], cols['hash32'],
        cols['deps_off'], cols['deps_blob'], head32, head_n)
    g_doc, g_actor = groups[3], groups[4]
    base = np.array([docs[d][2].get(actors[a], 0)
                     for d, a in zip(g_doc.tolist(), g_actor.tolist())],
                    dtype=np.int64)
    asked = []

    def history(d):
        asked.append(d)
        index = docs[d][0]
        return _index_of(index) if isinstance(index, dict) else index

    out = native.general_gate(
        cols['doc_off'], cols['actor'], cols['seq'], cols['hash32'],
        cols['deps_off'], cols['deps_blob'], head32, head_n, multi,
        np.ones(n_docs, dtype=np.uint8) if cand is None else cand,
        g_doc, g_actor, base, history)
    applied, app_off, left, left_off, nh, nh_off, g_seq, errors, probes = out
    assert (probes > 0) == bool(asked) and len(set(asked)) == len(asked)
    failed = {d: (i, expected) for d, i, expected in errors}
    answers = []
    for d, (_known, _heads, clock, run) in enumerate(docs):
        lo = int(cols['doc_off'][d])
        if d in failed:
            i, expected = failed[d]
            seq, actor = run[i - lo]['seq'], run[i - lo]['actor']
            answers.append(
                f'Reuse of sequence number {seq} for actor {actor}'
                if seq < expected else
                f'Skipped sequence number {expected} for actor {actor}')
            continue
        after = dict(clock)
        for g in np.flatnonzero(g_doc == d).tolist():
            if g_seq[g] != base[g]:
                after[actors[g_actor[g]]] = int(g_seq[g])
        answers.append((
            (applied[app_off[d]:app_off[d + 1]] - lo).tolist(),
            (left[left_off[d]:left_off[d + 1]] - lo).tolist(),
            [nh[j].tobytes().hex() for j in range(nh_off[d], nh_off[d + 1])],
            after))
    return answers, probes


def _oracle_gate(known, heads, clock, run):
    """`HashGraph._drain_queue` on the same delivery."""
    from automerge_tpu.backend.hash_graph import HashGraph
    oracle = HashGraph()
    oracle.heads, oracle.clock = list(heads), dict(clock)
    oracle.change_index_by_hash = known
    try:
        applied, queue = oracle._drain_queue(
            [dict(c, at=i) for i, c in enumerate(run)], lambda c: None)
        return ([c['at'] for c in applied], [c['at'] for c in queue],
                oracle.heads, oracle.clock)
    except ValueError as exc:
        return str(exc)


@pytest.mark.parametrize('seed', range(12))
def test_the_gate_over_chain_segments_is_the_host_oracles_gate(seed):
    """`native.general_gate` against `HashGraph._drain_queue`: the changes
    applied and their order, the queue and its order, heads, clock, and
    the error's text, over seeded deliveries; the run stands behind another
    document's changes in the batch, as a document's run does. (The name is
    PR 37's, when the gate ran in Python over chain segments.)"""
    import hashlib
    rng = random.Random(seed)
    errors = queued = reordered = history = 0
    for _ in range(500):
        actors, known, heads, clock, run = _a_delivery(rng)
        if not run:
            continue
        want = _oracle_gate(known, heads, clock, run)
        pad = rng.randint(0, 3)
        other = [{'hash': hashlib.sha256(bytes([i])).hexdigest(), 'deps': [],
                  'actor': actors[0], 'seq': i + 1} for i in range(pad)]
        (_, got), probes = _native_gate(
            actors, [({}, [], {}, other), (known, heads, clock, run)],
            cand=np.array([0, 1], dtype=np.uint8))
        assert got == want, (seed, run)
        errors += isinstance(want, str)
        history += bool(probes)
        if not isinstance(want, str):
            queued += bool(want[1])
            reordered += want[0] != sorted(want[0])
    # the deliveries hold what the gate has to tell apart
    assert errors and queued > 100 and reordered > 50
    assert 0 < history < 500     # history is asked only where it is needed


@pytest.mark.parametrize('seed', [5, 2147483659])
def test_many_documents_in_one_native_call_are_each_the_oracles(seed):
    """Documents are independent: four hundred deliveries gated by ONE
    native call (the gate fans them over the pool) read, document by
    document, as the oracle gating each alone, and the same at one pool
    thread and at four."""
    rng = random.Random(seed)
    actors = [f'{a:02x}' * 16 for a in range(4)]
    docs = []
    while len(docs) < 400:
        _actors, known, heads, clock, run = _a_delivery(rng)
        if run:
            docs.append((known, heads, clock, run))
    want = [_oracle_gate(*doc) for doc in docs]
    assert sum(isinstance(w, str) for w in want) >= 1
    assert sum(not isinstance(w, str) and bool(w[1]) for w in want) >= 50
    was = native.set_native_threads(1)
    try:
        alone, probes = _native_gate(actors, docs)
        native.set_native_threads(4)
        fanned, probes_fanned = _native_gate(actors, docs)
    finally:
        native.set_native_threads(was)
    assert alone == want and fanned == want
    assert probes == probes_fanned > 0


def _x(prefix, rest):
    """A hash (hex): 8 bytes of `prefix`, 24 of `rest`."""
    return f'{prefix:02x}' * 8 + f'{rest:02x}' * 24


def _c(hash_, deps, actor=0, seq=1):
    return {'hash': hash_, 'deps': list(deps), 'actor': f'{actor:02x}' * 16,
            'seq': seq}


HAND_BUILT = {
    # name: (history, heads, clock by actor number, run,
    #        applied, left, new heads, questions put to history)
    'a-prefix-is-a-slot-of-the-index-never-an-answer': (
        [_x(7, 1), _x(7, 2)], [_x(7, 2)], {0: 2},
        [_c(_x(9, 1), [_x(7, 1)], 1, 1), _c(_x(9, 2), [_x(7, 3)], 2, 1)],
        [0], [1], [_x(7, 2), _x(9, 1)], 2),
    'a-prefix-is-a-slot-of-the-runs-table-never-an-answer': (
        [], [], {},
        [_c(_x(5, 2), [_x(5, 9)], 1, 1), _c(_x(5, 3), [_x(5, 1)], 2, 1),
         _c(_x(5, 1), [], 0, 1)],
        [2, 1], [0], [_x(5, 3)], 1),
    'a-hash-delivered-twice-in-one-run-is-applied-once': (
        [], [], {},
        [_c(_x(1, 1), []), _c(_x(1, 2), [_x(1, 1)], 0, 2), _c(_x(1, 1), []),
         _c(_x(1, 2), [_x(1, 1)], 0, 2)],
        [0, 1], [], [_x(1, 2)], 0),
    'a-dependency-met-only-by-history': (
        [_x(3, 1), _x(3, 2), _x(3, 3)], [_x(3, 3)], {0: 3},
        [_c(_x(4, 1), [_x(3, 1)], 1, 1)],
        [0], [], [_x(3, 3), _x(4, 1)], 1),
    'a-change-of-the-history-delivered-again-with-what-follows-it': (
        [_x(3, 1), _x(3, 2)], [_x(3, 2)], {0: 2},
        [_c(_x(3, 1), [], 0, 1), _c(_x(6, 1), [_x(3, 1)], 1, 1)],
        [1], [], [_x(3, 2), _x(6, 1)], 1),
    'a-head-delivered-again-asks-nothing': (
        [_x(3, 1), _x(3, 2)], [_x(3, 2)], {0: 2},
        [_c(_x(3, 2), [_x(3, 1)], 0, 2), _c(_x(3, 4), [_x(3, 2)], 0, 3)],
        [1], [], [_x(3, 4)], 0),
    'what-waits-for-a-change-that-waits-stays-in-the-runs-order': (
        [], [], {},
        [_c(_x(2, 3), [_x(2, 2)], 0, 3), _c(_x(2, 2), [_x(2, 1)], 0, 2),
         _c(_x(8, 1), [], 1, 1)],
        [2], [0, 1], [_x(8, 1)], 1),
}


@pytest.mark.parametrize('case', sorted(HAND_BUILT))
def test_the_general_gate_on_hand_built_runs(case):
    history, heads, clock, run, applied, left, new_heads, asked = \
        HAND_BUILT[case]
    actors = [f'{a:02x}' * 16 for a in range(3)]
    clock = {actors[a]: seq for a, seq in clock.items()}
    known = {h: i for i, h in enumerate(history)}
    want = _oracle_gate(known, sorted(heads), clock, run)
    assert want[:3] == (applied, left, sorted(new_heads))
    (got,), probes = _native_gate(actors, [(known, sorted(heads), clock, run)])
    assert got == want and probes == asked


def test_the_history_index_across_its_growth():
    """Fed a few hashes at a time past several of its table's and its
    rows' growths, the index holds every hash once, finds each and no
    other, and stays under 48 bytes a hash."""
    import hashlib
    from automerge_tpu.fleet.hashindex import HistoryIndex, hashes_to_rows
    rng = random.Random(7)
    hashes = [hashlib.sha256(bytes([i % 251, i // 251])).hexdigest()
              for i in range(6000)]
    index = HistoryIndex()
    tables, fed = set(), 0
    while fed < len(hashes):
        k = rng.randint(1, 97)
        index.extend(hashes_to_rows(hashes[fed:fed + k] + hashes[:fed][-2:]))
        fed += k
        tables.add(len(index.table))
        assert 3 * index.n <= 2 * len(index.table)
        if rng.random() < .2:
            # asked now and then, as a document is: the gate enters what
            # has come since, beside what the table held
            _native_gate(['aa' * 16], [(index, [], {}, [_c(_x(1, 1), [_x(1, 2)],
                                                          0xaa)])])
            assert index.entered == index.n
    assert len(tables) > 5
    held = {row.tobytes().hex() for row in index.rows[:index.n]}
    assert held == set(hashes)
    assert index.nbytes < 48 * index.n
    # every hash a dependency: met where the index holds it
    absent = [hashlib.sha256(b'no' + bytes([i])).hexdigest()
              for i in range(50)]
    asks = rng.sample(hashes, 150) + absent
    rng.shuffle(asks)
    actors = ['aa' * 16]
    run = [_c(hashlib.sha256(b'run' + bytes([i])).hexdigest(), [dep], 0xaa,
              seq=i + 1) for i, dep in enumerate(asks)]
    # seqs run on only while every change is applied: one actor a change
    actors = [f'{i:04x}' * 8 for i in range(len(run))]
    run = [dict(c, actor=actors[i], seq=1) for i, c in enumerate(run)]
    (got,), probes = _native_gate(actors, [(index, [], {}, run)])
    assert got[0] == [i for i, dep in enumerate(asks) if dep in held]
    assert got[1] == [i for i, dep in enumerate(asks) if dep not in held]
    assert probes == len(asks)
    # what was fed twice stands twice in the rows and once in the table
    assert index.entered == index.n > len(hashes)
    assert int((index.table >= 0).sum()) == len(hashes)


def test_the_history_index_holds_sixty_thousand_hashes_in_48_bytes_each():
    from automerge_tpu.fleet.hashindex import HistoryIndex
    rng = np.random.default_rng(11)
    rows = rng.integers(0, 256, size=(60_000, 32), dtype=np.uint8)
    at_once, grown = HistoryIndex(), HistoryIndex()
    at_once.extend(rows)
    for lo in range(0, len(rows), 31):
        grown.extend(rows[lo:lo + 31])
    for index in (at_once, grown):
        assert index.nbytes / index.n < 48
        assert (index.rows[:index.n] == rows).all()
        # asked of all 60,000: the one that is there is found, and its
        # neighbour by the first 31 bytes is not
        near = rows[59_999].copy()
        near[31] ^= 1
        run = [_c(_x(1, 1), [rows[59_999].tobytes().hex()], 0xaa),
               _c(_x(1, 2), [near.tobytes().hex()], 0xbb)]
        (got,), probes = _native_gate(['aa' * 16, 'bb' * 16],
                                      [(index, [], {}, run)])
        assert got[:2] == ([0], [1]) and probes == 2
        assert index.entered == index.n == 60_000 == \
            int((index.table >= 0).sum())


@pytest.mark.parametrize('between', ['park', 'graph_read'])
def test_the_history_index_is_built_again_where_the_log_was_replaced(between):
    """A document that asked history and then was parked, or had its graph
    read, asks again: the index is built anew from what the history has
    become, and the gate answers as the oracle."""
    rng = random.Random(29)
    room = Room(2)
    rounds_ = some_rounds(room, rng, 4, k=5)
    held = Held([room.base])
    gone = rounds_[0][0][1]
    held.call([less(rounds_[0], [gone])], general=1)
    held.call([[gone] + less(rounds_[1], [])], general=1)
    impl = held.handles[0]['state']._impl
    first = impl._history[0]
    assert held.fleet.metrics.history_probes > 0 and first.n > 0
    if between == 'park':
        assert fleet_backend.park_docs(held.handles) == 1
    else:
        assert held.handles[0]['state'].change_index_by_hash
    asked = held.fleet.metrics.history_probes
    gone = rounds_[2][1][2]
    held.call([less(rounds_[2], [gone])], general=1)
    assert held.fleet.metrics.history_probes > asked
    assert impl._history[0] is not first
    # (the oracle's checks read the graph after every call, so the next
    # question builds it again, too) it holds the log's hashes, each once
    assert population(impl._history_index()) == \
        {hash_of(b) for b in impl.changes}
    held.call([[gone] + less(rounds_[3], [])], general=1)
    held.hold_reads()


@pytest.mark.parametrize('seq,text', [
    (4, 'Skipped sequence number 3 for actor '),
    (2, 'Reuse of sequence number 2 for actor ')],
    ids=['skipped', 'reused'])
def test_a_seq_error_of_the_native_gate_has_the_references_text(seq, text):
    """A ready change whose seq is not its actor's next, in the second of
    three documents: the reference's text, typed, with the document's
    place in the call; no document is touched."""
    rng = random.Random(31)
    rooms = [Room(2), Room(2), Room(2)]
    rounds_ = [some_rounds(room, rng, 1, k=3)[0] for room in rooms]
    fleet = DocFleet(doc_capacity=4, key_capacity=8)
    handles = init_docs(3, fleet)
    handles, _ = apply_changes_docs(handles, [r.base for r in rooms],
                                    mirror=False)
    was = [(fleet_backend.get_heads(h), dict(h['state'].clock))
           for h in handles]
    second = decode_change(bytes(rounds_[1][0][1]))
    assert second['seq'] == 3
    bad = encode_change({**second, 'seq': seq, 'hash': None})
    per_doc = [less(r, []) for r in rounds_]
    per_doc[0] = per_doc[0][::-1]             # the general gate's, and fine
    per_doc[1] = [bad if bytes(b) == bytes(rounds_[1][0][1]) else b
                  for b in per_doc[1]][::-1]
    with pytest.raises(InvalidChange) as raised:
        apply_changes_docs(handles, per_doc, mirror=False)
    assert str(raised.value) == text + second['actor']
    assert raised.value.doc_index == 1
    assert [(fleet_backend.get_heads(h), dict(h['state'].clock))
            for h in handles] == was
    assert fleet.metrics.fallbacks == 0 and fleet.metrics.exact_calls == 0
    with pytest.raises(ValueError, match=text):
        host.apply_changes(host.apply_changes(host.init(), rooms[1].base)[0],
                           per_doc[1])


def test_heads_that_are_no_hashes_send_the_call_to_the_exact_path():
    """A frontier the native gates cannot read as 32-byte hashes: the
    turbo call ends before it counts or writes anything, and the exact
    path answers."""
    rng = random.Random(37)
    room = Room(2)
    round_ = some_rounds(room, rng, 1, k=3)[0]
    fleet = DocFleet(doc_capacity=2, key_capacity=8)
    handles = init_docs(1, fleet)
    handles, _ = apply_changes_docs(handles, [room.base], mirror=False)
    impl = handles[0]['state']._impl
    impl.heads = impl.heads + ['not-a-hash']
    before = fleet.metrics.snapshot()
    try:
        apply_changes_docs(handles, [less(round_, [])[::-1]], mirror=False)
    except Exception:
        pass                  # whatever the exact path makes of such heads
    moved = fleet.metrics.delta(before)
    assert moved['turbo_calls'] == 0 and moved['fallbacks'] == 1
    assert moved['offchain_native'] == moved['history_probes'] == 0
