"""Sequence ops on concurrent branches ride the turbo path.

Several writers type into one Text at once: a call whose documents are off
the linear chain but causally ORDERED in buffer order (the DAG gate's
verdict) keeps its sequence, make and nested ops on the device. Every case
is held to the host backend (`backend/op_set.py`: text, patches, `save()`
bytes) and to the benchmark's plain reference (`reference_text.Rga`, which
imports nothing of the program), in both buffer orders, and asserts the
routing: `turbo_calls` >= 1, `fallbacks` 0, `exact_calls` 0, no row
inexact. One case holds the exit that went with PR 37: a document NEITHER
gate accepts passes the general gate and stays on the device, exact.
"""

import os
import random
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, 'benchmarks'))

import automerge_tpu as am                                       # noqa: E402
from automerge_tpu import backend as host                        # noqa: E402
from automerge_tpu import native                                 # noqa: E402
from automerge_tpu.columnar import decode_change, encode_change  # noqa: E402
from automerge_tpu.common import parse_op_id                     # noqa: E402
from automerge_tpu.errors import DanglingPred                    # noqa: E402
from automerge_tpu.fleet import backend as fleet_backend         # noqa: E402
from automerge_tpu.fleet import loader                           # noqa: E402
from automerge_tpu.fleet.backend import (                        # noqa: E402
    DocFleet, apply_changes_docs, init_docs, materialize_docs)
import reference_text                                            # noqa: E402

pytestmark = pytest.mark.skipif(not native.available(),
                                reason='the turbo path is the native codec')

LETTERS = 'abcdefghijklmnopqrstuvwxyz '


def _actor(i):
    # not in creation order, so that which writer's id is greater varies
    return f'{(i * 0x5b + 0x21) % 251:02x}' * 16


class Room:
    """One document and its writers, each a frontend document of its own.
    `round(edits)` lets every writer edit concurrently from what it has
    merged (one keystroke a change) and returns each writer's new changes;
    `sync()` merges everybody's changes into every writer."""

    def __init__(self, n_writers, text='the quick brown fox', **keys):
        self.actors = [_actor(i) for i in range(n_writers)]
        first = am.from_({'text': am.Text(text), **keys}, self.actors[0])
        self.base = list(am.get_all_changes(first))
        saved = am.save(first)
        self.docs = [first] + [am.load(saved, a) for a in self.actors[1:]]

    def round(self, edits):
        out = []
        for w, keystrokes in enumerate(edits):
            doc, made = self.docs[w], []
            for keystroke in keystrokes:
                doc = am.change(doc, keystroke)
                made.append(am.get_last_local_change(doc))
            self.docs[w] = doc
            out.append(made)
        return out

    def sync(self):
        for w in range(len(self.docs)):
            for v in range(len(self.docs)):
                if v != w:
                    self.docs[w] = am.merge(self.docs[w], self.docs[v])


def insert(index, char):
    return lambda d: d['text'].insert_at(index, char)


def delete(index):
    return lambda d: d['text'].delete_at(index)


def typing(rng, length, n, at=None, deletes=0.0):
    """n keystrokes of a writer whose document shows `length` characters:
    a run typed at a cursor (`at`, or a drawn index), with backspaces."""
    cursor = rng.randrange(length + 1) if at is None else at
    out = []
    for _ in range(n):
        if cursor and rng.random() < deletes:
            cursor -= 1
            out.append(delete(cursor))
            length -= 1
        else:
            out.append(insert(cursor, rng.choice(LETTERS)))
            cursor += 1
            length += 1
    return out


# ---------------------------------------------------------------------------
# the three answers a case is held to
# ---------------------------------------------------------------------------

def host_state(changes):
    state = host.init()
    state, _patch = host.apply_changes(state, list(changes))
    return state


def text_object_id(changes):
    for buf in changes:
        change = decode_change(bytes(buf))
        for i, op in enumerate(change['ops']):
            if op['action'] == 'makeText' and op.get('key') == 'text':
                return f"{change['startOp'] + i}@{change['actor']}"
    raise AssertionError('no makeText at root key text')


def rga_text(changes):
    """The text by the plain reference: every op on the Text applied in
    the order given, ids compared as (counter, actor)."""
    obj = text_object_id(changes)
    rga = reference_text.Rga()

    def elem(op_id):
        return None if op_id == '_head' else parse_op_id(op_id)

    for buf in changes:
        change = decode_change(bytes(buf))
        op_ctr = change['startOp']
        for op in change['ops']:
            if op.get('obj') == obj:
                op_id = (op_ctr, change['actor'])
                if op['action'] == 'del':
                    rga.delete(op_id, elem(op['elemId']))
                else:
                    assert op.get('insert') and op['action'] == 'set'
                    values = op.get('values', [op.get('value')])
                    ref = elem(op['elemId'])
                    for k, value in enumerate(values):
                        rga.insert((op_ctr + k, change['actor']), ref, value)
                        ref = (op_ctr + k, change['actor'])
                    op_ctr += len(values) - 1
            op_ctr += 1
    return rga.text()


def apply_calls(calls, start=None):
    """A fresh one-document fleet (or one loaded from `start`, a saved
    document), the calls applied one after the other with mirror=False."""
    fleet = DocFleet(doc_capacity=2, key_capacity=8)
    if start is None:
        handles = init_docs(1, fleet)
    else:
        handles = loader.load_docs([start], fleet)
    for call in calls:
        handles, _ = apply_changes_docs(handles, [list(call)], mirror=False)
    return fleet, handles


def assert_on_device(fleet, calls):
    metrics = fleet.metrics
    assert metrics.turbo_calls == len(calls) and metrics.turbo_calls >= 1
    assert metrics.fallbacks == 0 and metrics.exact_calls == 0
    assert metrics.promotions == 0
    for st in fleet.seq_pools.pools.values():
        assert not np.asarray(st.inexact).any()


def hold(history, round_orders, start=None, multiwriter=True):
    """Apply `history` (a list of calls) and then the last call in each of
    `round_orders` (the same changes in different buffer orders), each on a
    fleet of its own, and hold every outcome to the host backend and the
    reference and to one another."""
    states = []
    for order in round_orders:
        calls = list(history) + [order]
        fleet, handles = apply_calls(calls, start)
        assert_on_device(fleet, calls)
        assert fleet.metrics.dag_seq_docs >= 1
        if multiwriter:
            assert fleet.metrics.seq_multiwriter_rows >= 1
        applied = [buf for call in calls for buf in call]
        if start is not None:
            applied = list(host.get_all_changes(host.load(start))) + applied
        oracle = host_state(applied)
        view = materialize_docs(handles)[0]
        want = host.get_patch(oracle)
        assert fleet_backend.get_patch(handles[0]) == want
        assert view['text'] == rga_text(applied)
        assert sorted(fleet_backend.get_heads(handles[0])) == \
            sorted(host.get_heads(oracle))
        assert bytes(fleet_backend.save(handles[0])) == \
            bytes(host.save(oracle))
        # the device served the read
        assert_on_device(fleet, calls)
        states.append((view, want))
    assert all(state == states[0] for state in states[1:])
    return states[0][0]


def both_orders(per_writer):
    flat = [list(changes) for changes in per_writer]
    forward = [buf for changes in flat for buf in changes]
    backward = [buf for changes in reversed(flat) for buf in changes]
    return [forward, backward]


# ---------------------------------------------------------------------------
# two writers: the kinds of conflict of crdt-benchmarks B2
# ---------------------------------------------------------------------------

def test_merge_change_after_a_round():
    """A writer merges the other's round and types on: a change with two
    dependencies, in the same call as the round and in a later one. (First
    in the file: a family of one case pays the file's first compiles.)"""
    rng = random.Random(31)
    room = Room(2)
    made = room.round([typing(rng, 19, 5, at=7), typing(rng, 19, 4, at=7)])
    room.sync()
    length = len(str(room.docs[0]['text']))
    merged = room.round([typing(rng, length, 3, deletes=0.3), []])[0]
    assert len(decode_change(bytes(merged[0]))['deps']) == 2
    hold([room.base], [made[0] + made[1] + merged,
                       made[1] + made[0] + merged])
    hold([room.base, made[0] + made[1]], [merged], multiwriter=False)


@pytest.mark.parametrize('seed', [1, 2, 3])
def test_same_index_inserts(seed):
    """B2.1: both writers insert a run at ONE index, so the second chain's
    first insert walks past the first chain's run or stops before it."""
    rng = random.Random(seed)
    room = Room(2)
    at = rng.randrange(len('the quick brown fox') + 1)
    made = room.round([typing(rng, 19, rng.randrange(3, 9), at=at)
                       for _ in range(2)])
    view = hold([room.base], both_orders(made))
    assert len(view['text']) == 19 + sum(len(m) for m in made)


@pytest.mark.parametrize('seed', [11, 12, 13])
def test_random_positions(seed):
    """B2.2: both insert single characters at positions drawn apart."""
    rng = random.Random(seed)
    room = Room(2)
    made = room.round([[insert(rng.randrange(19 + k), rng.choice(LETTERS))
                        for k in range(rng.randrange(4, 12))]
                       for _ in range(2)])
    hold([room.base], both_orders(made))


@pytest.mark.parametrize('seed', [21, 22, 23])
def test_insert_and_delete_one_element_deleted_by_both(seed):
    """B2.4: both insert and delete; one element gets two concurrent
    deletes (both writers start with a backspace at one cursor)."""
    rng = random.Random(seed)
    room = Room(2)
    at = rng.randrange(2, 19)
    made = room.round([[delete(at - 1)] +
                       typing(rng, 18, rng.randrange(4, 10), at=at - 1,
                              deletes=0.3)
                       for _ in range(2)])
    targets = [decode_change(bytes(changes[0]))['ops'][0]
               for changes in made]
    assert [op['action'] for op in targets] == ['del', 'del']
    assert targets[0]['elemId'] == targets[1]['elemId']
    view = hold([room.base], both_orders(made))
    # deleted twice, gone once: the frontends' own merge agrees
    assert view['text'] == str(am.merge(*room.docs)['text'])


# ---------------------------------------------------------------------------
# more writers; rounds; merges; loaded documents; other objects
# ---------------------------------------------------------------------------

@pytest.mark.parametrize('n_writers', [2, 3, 5])
def test_many_writers(n_writers):
    """2 / 3 / 5 writers at once; a row that five have written widens its
    pool to 8 lanes."""
    rng = random.Random(100 + n_writers)
    room = Room(n_writers)
    made = room.round([typing(rng, 19, rng.randrange(3, 8),
                              at=rng.choice([4, None]), deletes=0.2)
                       for _ in range(n_writers)])
    orders = both_orders(made)
    shuffled = list(made)
    rng.shuffle(shuffled)
    orders.append([buf for changes in shuffled for buf in changes])
    hold([room.base], orders)
    fleet, _handles = apply_calls([room.base, orders[0]])
    (pool,) = fleet.seq_pools.pools.values()
    assert pool.actor_slots == (8 if n_writers == 5 else 4)
    assert len(fleet.seq_writers[0]) == n_writers


@pytest.mark.parametrize('seed', [41, 42])
def test_second_round_onto_two_heads(seed):
    """After a round the document has two heads; in the next round each
    writer's first change depends on both (it has merged the other's
    round), as the benchmark's cell sends them."""
    rng = random.Random(seed)
    room = Room(2)
    first = room.round([typing(rng, 19, 6, at=3, deletes=0.2),
                        typing(rng, 19, 6, at=3, deletes=0.2)])
    room.sync()
    length = len(str(room.docs[0]['text']))
    at = rng.randrange(1, length)
    second = room.round([typing(rng, length, 7, at=at, deletes=0.3),
                         typing(rng, length, 5, at=at, deletes=0.3)])
    for changes in second:
        assert len(decode_change(bytes(changes[0]))['deps']) == 2
    _fleet, handles = apply_calls([room.base, first[0] + first[1]])
    assert len(fleet_backend.get_heads(handles[0])) == 2
    hold([room.base, first[1] + first[0]], both_orders(second))


@pytest.mark.parametrize('seed', [51, 52])
def test_loaded_two_head_document_then_a_round(seed):
    """A saved document with two writers and two heads, loaded by
    load_docs, then a round onto it."""
    rng = random.Random(seed)
    room = Room(2)
    first = room.round([typing(rng, 19, 8, at=5, deletes=0.25),
                        typing(rng, 19, 8, at=5, deletes=0.25)])
    saved = bytes(host.save(host_state(room.base + first[0] + first[1])))
    assert len(host.get_heads(host.load(saved))) == 2
    room.sync()
    length = len(str(room.docs[0]['text']))
    at = rng.randrange(1, length)
    second = room.round([typing(rng, length, 6, at=at, deletes=0.3),
                         typing(rng, length, 6, at=at, deletes=0.3)])
    fleet, handles = apply_calls([], start=saved)
    assert fleet.metrics.docs_bulk_loaded == 1
    assert len(fleet_backend.get_heads(handles[0])) == 2
    assert len(fleet.seq_writers[0]) == 2
    hold([], both_orders(second), start=saved)


def test_makes_and_nested_maps_on_a_concurrent_branch():
    """One writer types; the other, concurrently, makes a map with a
    nested map and a list and fills them, and types too."""
    rng = random.Random(61)
    room = Room(2)

    def make_map(d):
        d['meta'] = {'title': 'draft', 'tags': {'a': 1}}

    def make_list(d):
        d['items'] = [1, 2, 3]

    def nested_set(d):
        d['meta']['tags']['b'] = 2

    def list_insert(d):
        d['items'].insert_at(1, 9)

    made = room.round([
        typing(rng, 19, 6, at=2),
        [make_map, insert(2, 'z'), make_list, nested_set, list_insert,
         insert(3, 'y')]])
    view = hold([room.base], both_orders(made))
    assert view['meta'] == {'title': 'draft', 'tags': {'a': 1, 'b': 2}}
    assert view['items'] == [1, 9, 2, 3]


def test_map_key_overwritten_and_deleted_on_both_branches():
    """Beside their typing one writer overwrites a root key and deletes it
    again, the other deletes the same key and sets another: the second
    branch's predecessors name an op the first has already succeeded."""
    room = Room(2, title='draft', n=1)

    def retitle(d):
        d['title'] = 'final'

    def untitle(d):
        del d['title']

    def count(d):
        d['n'] = 2

    made = room.round([
        [retitle, insert(0, 'a'), untitle, count, insert(1, 'b')],
        [insert(0, 'c'), untitle, count, insert(1, 'd')]])
    view = hold([room.base], both_orders(made))
    assert 'title' not in view and view['n'] == 2


def test_rounds_in_one_call_of_many_documents():
    """Several documents in one call, each with its own writers and round,
    one of them on the chain: the call stays whole on the device."""
    rng = random.Random(71)
    rooms = [Room(2, text=f'document {d} ' * 2) for d in range(4)]
    fleet = DocFleet(doc_capacity=4, key_capacity=8)
    handles = init_docs(4, fleet)
    handles, _ = apply_changes_docs(handles, [r.base for r in rooms],
                                    mirror=False)
    rounds = []
    for d, room in enumerate(rooms):
        made = room.round([typing(rng, 22, rng.randrange(2, 9), at=4),
                           typing(rng, 22, rng.randrange(2, 9), at=4)
                           if d else []])
        rounds.append(made[1] + made[0] if d % 2 else made[0] + made[1])
    handles, _ = apply_changes_docs(handles, rounds, mirror=False)
    assert_on_device(fleet, [None, None])
    assert fleet.metrics.dag_seq_docs == 3
    assert fleet.metrics.seq_multiwriter_rows == 3
    views = materialize_docs(handles)
    for d, room in enumerate(rooms):
        applied = room.base + rounds[d]
        oracle = host_state(applied)
        assert fleet_backend.get_patch(handles[d]) == host.get_patch(oracle)
        assert views[d]['text'] == rga_text(applied)
        assert bytes(fleet_backend.save(handles[d])) == \
            bytes(host.save(oracle))


# ---------------------------------------------------------------------------
# the exit that went (PR 37): out of order, and still on the device
# ---------------------------------------------------------------------------

def test_out_of_order_document_stays_on_the_device_path():
    """A call with sequence ops in which one document is out of order (a
    change before the change it depends on: neither gate accepts it) stays
    on the turbo path, whole: the general gate applies that document's
    changes in a causal order, its op rows follow, and it is exact."""
    rng = random.Random(81)
    rooms = [Room(2), Room(2)]
    fleet = DocFleet(doc_capacity=2, key_capacity=8)
    handles = init_docs(2, fleet)
    handles, _ = apply_changes_docs(handles, [r.base for r in rooms],
                                    mirror=False)
    made = [room.round([typing(rng, 19, 4, at=6), typing(rng, 19, 4, at=6)])
            for room in rooms]
    good = made[0][0] + made[0][1]
    swapped = list(made[1][0])
    swapped[0], swapped[1] = swapped[1], swapped[0]
    bad = swapped + made[1][1]
    before = fleet.metrics.snapshot()
    handles, _ = apply_changes_docs(handles, [good, bad], mirror=False)
    delta = fleet.metrics.delta(before)
    assert delta['turbo_calls'] == 1 and delta['fallbacks'] == 0
    assert delta['exact_calls'] == 0
    assert delta['turbo_commit_fallback_docs'] == 1     # the general gate
    views = materialize_docs(handles)
    for d, (room, applied) in enumerate(zip(rooms, (good, bad))):
        oracle = host_state(room.base + applied)
        assert views[d]['text'] == str(am.merge(*room.docs)['text'])
        assert fleet_backend.get_patch(handles[d]) == host.get_patch(oracle)
        assert bytes(fleet_backend.save(handles[d])) == \
            bytes(host.save(oracle))
    assert fleet.metrics.fallbacks == 0 and fleet.metrics.exact_calls == 0
    for st in fleet.seq_pools.pools.values():
        assert not np.asarray(st.inexact).any()


@pytest.mark.parametrize('fault', ['dangling_pred', 'seq_gap'])
def test_faulty_round_raises_typed_and_leaves_the_document(fault):
    """A round whose second branch holds a delete of a root key with a
    predecessor no op has (typed DanglingPred, from the turbo path's
    validator after `restore_all`) or skips a sequence number (its run
    does not extend the clock, so neither gate takes the document and the
    exact path raises what it always raised): the document keeps its heads
    and the sound round then applies on the device."""
    rng = random.Random(91)
    room = Room(2, title='draft')
    made = room.round([typing(rng, 19, 4, at=6), typing(rng, 19, 4, at=6)])
    last = decode_change(bytes(made[1][-1]))
    bad = {'actor': last['actor'], 'time': 0, 'message': '',
           'deps': [last['hash']], 'startOp': last['startOp'] + 1,
           'seq': last['seq'] + (2 if fault == 'seq_gap' else 1),
           'ops': [{'action': 'del', 'obj': '_root', 'key': 'title',
                    'pred': [f"99@{last['actor']}" if fault ==
                             'dangling_pred' else f'2@{room.actors[0]}']}]}
    fleet, handles = apply_calls([room.base])
    heads = fleet_backend.get_heads(handles[0])
    with pytest.raises(DanglingPred if fault == 'dangling_pred'
                       else ValueError,
                       match='no matching operation for pred'
                       if fault == 'dangling_pred'
                       else 'Skipped sequence number'):
        apply_changes_docs(handles, [made[0] + made[1] +
                                     [encode_change(bad)]], mirror=False)
    assert fleet_backend.get_heads(handles[0]) == heads
    before = fleet.metrics.snapshot()
    handles, _ = apply_changes_docs(handles, [made[0] + made[1]],
                                    mirror=False)
    delta = fleet.metrics.delta(before)
    assert delta['turbo_calls'] == 1 and delta['fallbacks'] == 0
    oracle = host_state(room.base + made[0] + made[1])
    assert fleet_backend.get_patch(handles[0]) == host.get_patch(oracle)
