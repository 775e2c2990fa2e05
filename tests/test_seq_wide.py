"""Sequence rows past the packed op-counter window stay on the device.

A fleet id packs (counter << 8) | actor number into an int32, so counters
stop at CTR_LIMIT (2^23). A Text or list whose ids pass it becomes a WIDE
row: its ids pack (counter, the actor's rank among the row's writers) in
as few actor bits as its writers need (DocFleet._seq_wide), and a writer
that joins it repacks that row alone. Every case here builds histories
whose `startOp` lies past CTR_LIMIT and holds the device to the host
backend (`backend/op_set.py`: text, patches, `save()` bytes) and to the
benchmark's plain reference (`reference_text.Rga`, which imports nothing
of the program), and asserts the routing: no fallback, no promotion, no
row inexact.
"""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, 'benchmarks'))

from automerge_tpu import backend as host                        # noqa: E402
from automerge_tpu import native                                 # noqa: E402
from automerge_tpu.columnar import decode_change, encode_change  # noqa: E402
from automerge_tpu.fleet import backend as fleet_backend         # noqa: E402
from automerge_tpu.fleet import loader                           # noqa: E402
from automerge_tpu.fleet.backend import (                        # noqa: E402
    DocFleet, apply_changes_docs, init_docs, materialize_docs)
from automerge_tpu.fleet.tensor_doc import (                     # noqa: E402
    ACTOR_BITS, CTR_LIMIT, SEQ_CTR_LIMIT)
from automerge_tpu.observability import spans                    # noqa: E402
import reference_text                                            # noqa: E402

pytestmark = pytest.mark.skipif(not native.available(),
                                reason='the turbo path is the native codec')

W = CTR_LIMIT + 7          # a start past the window
A, B, C, E = 'aa' * 16, '55' * 16, 'cc' * 16, 'ee' * 16


class Text:
    """One Text's history, change by change: a makeText by `owner` at op 1,
    then keystroke changes whose startOps the caller picks."""

    def __init__(self, owner=A):
        self.changes = []
        self.seq = {}
        self.heads = []
        self.obj = f'1@{owner}'
        self.add(owner, 1, [{'action': 'makeText', 'obj': '_root',
                             'key': 'text', 'insert': False, 'pred': []}])

    def add(self, actor, start, ops, deps=None):
        seq = self.seq.get(actor, 0) + 1
        buf = encode_change({'actor': actor, 'seq': seq, 'startOp': start,
                             'time': 0, 'message': '',
                             'deps': sorted(self.heads if deps is None
                                            else deps), 'ops': ops})
        self.seq[actor] = seq
        self.changes.append(buf)
        self.heads = [decode_change(buf)['hash']]
        return buf

    def typing(self, actor, start, after, chars, deps=None):
        """One change: `chars` typed one after another after `after`."""
        ops, ref = [], after
        for i, char in enumerate(chars):
            ops.append({'action': 'set', 'obj': self.obj, 'elemId': ref,
                        'insert': True, 'value': char, 'pred': []})
            ref = f'{start + i}@{actor}'
        return self.add(actor, start, ops, deps)

    def delete(self, actor, start, elem, deps=None):
        return self.add(actor, start, [{'action': 'del', 'obj': self.obj,
                                        'elemId': elem, 'insert': False,
                                        'pred': [elem]}], deps)

    def overwrite(self, actor, start, elem, char, deps=None):
        return self.add(actor, start, [{'action': 'set', 'obj': self.obj,
                                        'elemId': elem, 'insert': False,
                                        'value': char, 'pred': [elem]}],
                        deps)


def rga_text(changes):
    """The text by the plain reference, ids compared as (counter, actor)."""
    rga = reference_text.Rga()

    def elem(op_id):
        if op_id == '_head':
            return None
        ctr, _, actor = op_id.partition('@')
        return (int(ctr), actor)

    for buf in changes:
        change = decode_change(bytes(buf))
        for i, op in enumerate(change['ops']):
            if op['obj'] == '_root':
                continue
            op_id = (change['startOp'] + i, change['actor'])
            if op['action'] == 'del':
                rga.delete(op_id, elem(op['elemId']))
            elif op.get('insert'):
                rga.insert(op_id, elem(op['elemId']), op['value'])
    return rga.text()


def host_of(changes):
    state, _patch = host.apply_changes(host.init(), list(changes))
    return state


def assert_on_device(fleet):
    m = fleet.metrics
    assert m.fallbacks == 0 and m.promotions == 0
    for st in fleet.seq_pools.pools.values():
        assert not np.asarray(st.inexact).any()


def assert_matches(handle, changes, text_of_rga=True):
    oracle = host_of(changes)
    if text_of_rga:
        assert materialize_docs([handle])[0]['text'] == rga_text(changes)
    assert fleet_backend.get_patch(handle)['diffs'] == \
        host.get_patch(oracle)['diffs']
    assert bytes(fleet_backend.save(handle)) == bytes(host.save(oracle))


def one_text_past_the_window():
    doc = Text()
    doc.typing(A, W, '_head', 'hello world')
    doc.delete(A, W + 20, f'{W + 4}@{A}')
    return doc


def test_a_text_past_the_window_loads_and_edits_on_the_device():
    """load_docs installs the row wide, in the 2^25 class's layout for one
    writer (no actor bits); keystrokes past the window ride the turbo path
    and read back as the host and the reference have them."""
    doc = one_text_past_the_window()
    fleet = DocFleet(doc_capacity=2)
    handles = loader.load_docs([host.save(host_of(doc.changes))], fleet)
    assert handles[0]['state'].is_fleet
    (row,) = fleet.slot_seq[handles[0]['state']._impl.slot].values()
    assert fleet.seq_wide[row]['bits'] == 0
    assert fleet.metrics.seq_wide_rows == 1
    calls = [doc.typing(A, W + 21, f'{W + 10}@{A}', '!'),
             doc.delete(A, W + 22, f'{W}@{A}'),
             doc.typing(A, W + 23, '_head', 'H')]
    for buf in calls:
        handles, _ = apply_changes_docs(handles, [[buf]], mirror=False)
    assert fleet.metrics.turbo_calls == len(calls)
    assert fleet.metrics.exact_calls == 0
    assert_on_device(fleet)
    assert materialize_docs(handles)[0]['text'] == 'Hell world!'
    assert_matches(handles[0], doc.changes)
    assert fleet.metrics.seq_repacks == 0


def test_a_row_under_the_window_keeps_the_fleet_layout():
    """The layout of every row whose counters stay under the window is
    the fleet's: (counter << 8) | actor number, nothing repacked."""
    doc = Text()
    doc.typing(A, 2, '_head', 'abc')
    fleet = DocFleet(doc_capacity=2)
    handles, _ = apply_changes_docs(init_docs(1, fleet), [doc.changes],
                                    mirror=False)
    (row,) = fleet.slot_seq[handles[0]['state']._impl.slot].values()
    assert fleet.seq_wide[row] is None and fleet.metrics.seq_wide_rows == 0
    cls, idx = fleet.seq_place[row]
    ids = np.asarray(fleet.seq_pools.state(cls).elem_id[idx])
    num = fleet.actors.intern(A)
    assert sorted(ids[ids != 0].tolist()) == \
        [(c << ACTOR_BITS) | num for c in (2, 3, 4)]
    assert_matches(handles[0], doc.changes)


def test_a_second_writer_repacks_that_row_alone():
    """A writer joining a wide row rewrites that row's ids (one bit of
    rank now); a narrow row of the same fleet keeps its ids as they were
    (the writer sorts last in the fleet's table, so nothing renumbers
    it)."""
    doc = one_text_past_the_window()
    small = Text(owner=C)
    small.typing(C, 2, '_head', 'xyz')
    fleet = DocFleet(doc_capacity=4)
    handles, _ = apply_changes_docs(init_docs(2, fleet),
                                    [doc.changes, small.changes],
                                    mirror=False)
    (wide_row,) = fleet.slot_seq[handles[0]['state']._impl.slot].values()
    (narrow_row,) = fleet.slot_seq[handles[1]['state']._impl.slot].values()
    cls, idx = fleet.seq_place[narrow_row]
    before = np.asarray(fleet.seq_pools.state(cls).elem_id[idx]).copy()
    repacks = fleet.metrics.seq_repacks
    buf = doc.typing(E, W + 30, f'{W + 2}@{A}', 'Q')
    handles, _ = apply_changes_docs(handles, [[buf], []], mirror=False)
    assert fleet.metrics.seq_repacks == repacks + 1
    assert fleet.seq_wide[wide_row]['writers'] == [A, E]
    assert fleet.seq_wide[wide_row]['bits'] == 1
    cls, idx = fleet.seq_place[narrow_row]
    np.testing.assert_array_equal(
        np.asarray(fleet.seq_pools.state(cls).elem_id[idx]), before)
    assert_on_device(fleet)
    assert_matches(handles[0], doc.changes)
    assert materialize_docs(handles)[1]['text'] == 'xyz'


@pytest.mark.parametrize('first', [A, B])
def test_concurrent_inserts_at_one_referent_keep_lamport_order(first):
    """Two writers insert after one element at once, at equal and at
    unequal counters past the window: the greater (counter, actor) goes
    first, in either buffer order."""
    doc = one_text_past_the_window()
    base = list(doc.heads)
    at = f'{W + 5}@{A}'
    mine = doc.typing(A, W + 40, at, 'AA', deps=base)
    head_a = list(doc.heads)
    theirs = doc.typing(B, W + 40, at, 'bb', deps=base)
    later = doc.typing(B, W + 45, at, 'c', deps=list(doc.heads))
    doc.heads = sorted(head_a + doc.heads)
    merge = doc.typing(A, W + 50, at, 'D')
    history = doc.changes[:3]
    rounds = [mine, theirs, later] if first == A else \
        [theirs, later, mine]
    fleet = DocFleet(doc_capacity=2)
    handles, _ = apply_changes_docs(init_docs(1, fleet), [history],
                                    mirror=False)
    handles, _ = apply_changes_docs(handles, [rounds], mirror=False)
    handles, _ = apply_changes_docs(handles, [[merge]], mirror=False)
    assert fleet.metrics.exact_calls == 0
    assert_on_device(fleet)
    applied = history + rounds + [merge]
    assert materialize_docs(handles)[0]['text'] == rga_text(applied)
    assert_matches(handles[0], applied)


def test_a_writer_that_sorts_first_in_the_fleet_leaves_wide_rows_alone():
    """A new actor sorting before every other renumbers the fleet's table
    (and the narrow rows' ids); a wide row ranks its own writers, whose
    order that leaves as it was, so its ids stay."""
    doc = one_text_past_the_window()
    fleet = DocFleet(doc_capacity=4)
    handles, _ = apply_changes_docs(init_docs(1, fleet), [doc.changes],
                                    mirror=False)
    (row,) = fleet.slot_seq[handles[0]['state']._impl.slot].values()
    cls, idx = fleet.seq_place[row]
    before = np.asarray(fleet.seq_pools.state(cls).elem_id[idx]).copy()
    remaps = fleet.metrics.remaps
    early = Text(owner='00' * 16)
    early.typing('00' * 16, 2, '_head', 'new')
    more, _ = apply_changes_docs(init_docs(1, fleet), [early.changes],
                                 mirror=False)
    assert fleet.metrics.remaps > remaps
    cls, idx = fleet.seq_place[row]
    np.testing.assert_array_equal(
        np.asarray(fleet.seq_pools.state(cls).elem_id[idx]), before)
    assert_matches(handles[0], doc.changes)
    assert_matches(more[0], early.changes)


def test_save_round_trips_past_the_window():
    """save() of a wide row's document is the host's bytes, and loading
    them gives a fleet the same text and the same bytes again."""
    doc = one_text_past_the_window()
    doc.overwrite(A, W + 60, f'{W + 1}@{A}', 'E')
    fleet = DocFleet(doc_capacity=2)
    handles, _ = apply_changes_docs(init_docs(1, fleet), [doc.changes],
                                    mirror=False)
    saved = bytes(fleet_backend.save(handles[0]))
    assert saved == bytes(host.save(host_of(doc.changes)))
    again = DocFleet(doc_capacity=2)
    loaded = loader.load_docs([saved], again)
    assert again.metrics.seq_wide_rows == 1
    assert materialize_docs(loaded) == materialize_docs(handles)
    assert bytes(fleet_backend.save(loaded[0])) == saved


def test_exact_device_patches_read_wide_rows_from_the_device():
    """The register fleet serves whole-document patches from the device
    lanes: a wide row's ids read back in the fleet's layout, and the patch
    is the host's, with no mirror rebuilt."""
    doc = one_text_past_the_window()
    doc.typing(B, W + 30, f'{W + 2}@{A}', 'Q')
    fleet = DocFleet(doc_capacity=2, exact_device=True)
    handles, _ = apply_changes_docs(init_docs(1, fleet), [doc.changes],
                                    mirror=False)
    engine = handles[0]['state']._impl
    assert engine._register_patch_diffs() == \
        host.get_patch(host_of(doc.changes))['diffs']
    assert fleet.metrics.mirror_rebuilds == 0
    assert_on_device(fleet)


def test_the_per_change_path_packs_wide():
    """The exact (mirror) path's flush packs a row past the window wide
    too, change by change."""
    doc = one_text_past_the_window()
    doc.typing(B, W + 30, f'{W + 2}@{A}', 'Q')
    fleet = DocFleet(doc_capacity=2)
    handle = init_docs(1, fleet)[0]
    for buf in doc.changes:
        handle, _ = fleet_backend.apply_changes(handle, [buf])
    assert_matches(handle, doc.changes)
    assert handle['state'].is_fleet
    assert fleet.metrics.seq_wide_rows == 1
    assert_on_device(fleet)


def test_a_layout_past_its_bits_leaves_the_row_to_the_mirror():
    """Three writers need two bits of rank, which leave counters below
    2^29: a row with more is flagged inexact and read from the host
    mirror, exactly, instead of wrapping."""
    high = (1 << 29) + 5
    doc = Text()
    doc.typing(A, high, '_head', 'ab')
    doc.typing(B, high + 10, f'{high}@{A}', 'c')
    doc.typing(C, high + 20, f'{high + 1}@{A}', 'd')
    fleet = DocFleet(doc_capacity=2)
    handles, _ = apply_changes_docs(init_docs(1, fleet), [doc.changes],
                                    mirror=False)
    (row,) = fleet.slot_seq[handles[0]['state']._impl.slot].values()
    assert fleet.seq_wide[row]['lost']
    assert fleet.seq_ordered[row] == -1      # its ids left Lamport order
    assert fleet.seq_row_inexact(row)
    assert_matches(handles[0], doc.changes)
    again = DocFleet(doc_capacity=2)
    loaded = loader.load_docs([host.save(host_of(doc.changes))], again)
    row = _row_of(again, loaded[0])
    assert again.seq_wide[row]['lost'] and again.seq_ordered[row] == -1
    assert_matches(loaded[0], doc.changes)


def test_the_parser_widens_sequence_ids_and_refuses_map_ones():
    """The native parse gives a sequence op's own id, referent and preds
    past the window as int64 fleet-form ids; a map-key op past it is
    refused, as before (the grid rebases it on the exact path)."""
    doc = one_text_past_the_window()
    rows = native.ingest_changes([bytes(b) for b in doc.changes], None,
                                 with_meta=True, with_seq=True)[0]
    assert rows['packed'].dtype == np.int64
    ctr = rows['packed'] >> 8
    assert sorted(ctr.tolist()) == [1] + list(range(W, W + 11)) + [W + 20]
    deleting = np.flatnonzero(rows['flags'] == 5)
    assert (rows['pred'] >> 8).tolist() == [W + 4]
    assert (rows['ref'][deleting] >> 8).tolist() == [W + 4]
    narrow = Text()
    narrow.typing(A, 2, '_head', 'ab')
    rows = native.ingest_changes([bytes(b) for b in narrow.changes], None,
                                 with_meta=True, with_seq=True)[0]
    assert rows['packed'].dtype == np.int32
    key = encode_change({'actor': A, 'seq': 1, 'startOp': W, 'time': 0,
                         'message': '', 'deps': [],
                         'ops': [{'action': 'set', 'obj': '_root',
                                  'key': 'k', 'value': 1,
                                  'datatype': 'int', 'pred': []}]})
    assert native.ingest_changes([key], None, with_meta=True,
                                 with_seq=True) is None


def test_a_dispatch_notes_its_repacks_and_lookup_nodes():
    """The first dispatch past the window repacks its row under
    `seq.place` (span `seq.repack`, attribute `rows`); every
    `seq.enqueue` carries the nodes its lookup reads, which
    `seq_lookup_nodes` sums: rows x nodes where it scans, as it does rows
    of 64 slots (search_pays)."""
    doc = Text()
    doc.typing(A, 2, '_head', 'ab')
    fleet = DocFleet(doc_capacity=2)
    handles, _ = apply_changes_docs(init_docs(1, fleet), [doc.changes],
                                    mirror=False)
    before = fleet.metrics.seq_lookup_nodes
    spans.enable(capacity=4096)
    try:
        spans.clear()
        buf = doc.typing(A, W, f'3@{A}', 'c')
        handles, _ = apply_changes_docs(handles, [[buf]], mirror=False)
        recorded = spans.iter_spans()
    finally:
        spans.disable()
    (repack,) = [s for s in recorded if s['name'] == 'seq.repack']
    (place,) = [s for s in recorded if s['name'] == 'seq.place']
    assert repack['parent'] == place['id']
    assert repack['attrs'] == {'rows': 1}
    (enqueue,) = [s for s in recorded if s['name'] == 'seq.enqueue']
    assert enqueue['attrs']['search'] == 0
    assert enqueue['attrs']['lookup_nodes'] == \
        enqueue['attrs']['rows'] * (64 + 3)
    assert fleet.metrics.seq_lookup_nodes - before == \
        enqueue['attrs']['lookup_nodes']
    assert fleet.metrics.seq_repacks == 1
    assert_matches(handles[0], doc.changes)


def test_a_sequence_make_past_what_a_wide_row_packs_promotes():
    """A Text made at or past SEQ_CTR_LIMIT could hold no element a wide
    row packs: the document goes to the host engine, which applies it."""
    doc = Text()
    doc.add(B, SEQ_CTR_LIMIT + 3, [{'action': 'makeText', 'obj': '_root',
                                    'key': 'late', 'insert': False,
                                    'pred': []}])
    fleet = DocFleet(doc_capacity=2)
    handle = init_docs(1, fleet)[0]
    handle, _ = fleet_backend.apply_changes(handle, doc.changes)
    assert not handle['state'].is_fleet
    assert fleet.metrics.promotions == 1
    assert fleet_backend.get_patch(handle)['diffs'] == \
        host.get_patch(host_of(doc.changes))['diffs']


# ---- rows in id order: the lookup searches them -----------------------------

@pytest.fixture
def searching(monkeypatch):
    """The search at every row length: by default it pays from rows of
    2^19 slots up (sequence.search_pays), which a test cannot afford."""
    from automerge_tpu.fleet import sequence
    monkeypatch.setattr(sequence, 'SEARCH_NODES_PER_ROUND', 0)


def test_the_search_pays_on_long_rows_only():
    """The chip's costs set where the lookup searches: not the 262,144-slot
    class of the text cells, whose scan took 2.3 ms a dispatch where the
    search's 19 rounds took 2.7, but the 2^19-slot class up, and the 2^25
    one of long documents, where the scan took 21.6 ms and the search
    0.07."""
    from automerge_tpu.fleet.sequence import SLOT0, search_pays
    assert not search_pays((1 << 18) + SLOT0)
    assert search_pays((1 << 19) + SLOT0)
    assert search_pays((1 << 25) + SLOT0)


def _row_of(fleet, handle):
    (row,) = fleet.slot_seq[handle['state']._impl.slot].values()
    return row


def _device_ids(fleet, row):
    """The ids of a row's allocated slots, as the device holds them."""
    from automerge_tpu.fleet.sequence import SLOT0
    cls, idx = fleet.seq_place[row]
    st = fleet.seq_pools.state(cls)
    return np.asarray(st.elem_id[idx])[SLOT0:SLOT0 + int(st.n[idx])]


def _searched(handles, changes):
    """apply_changes_docs(handles, changes), and the `search` attribute of
    each of its `seq.enqueue` spans."""
    spans.enable(capacity=4096)
    try:
        spans.clear()
        handles, _ = apply_changes_docs(handles, changes, mirror=False)
        recorded = spans.iter_spans()
    finally:
        spans.disable()
    return handles, [s['attrs']['search'] for s in recorded
                     if s['name'] == 'seq.enqueue']


def test_one_writer_keeps_its_row_in_id_order_through_a_migration(searching):
    """One writer's keystrokes at random places past the window keep the
    row's slots in id order: every dispatch searches, the row's move from
    the 64-slot class to the 128-slot one included, and
    `seq_search_dispatches` counts them all."""
    rng = np.random.default_rng(7)
    doc = Text()
    doc.typing(A, W, '_head', 'abcdefgh')
    elems = [f'{W + i}@{A}' for i in range(8)]
    fleet = DocFleet(doc_capacity=2)
    handles, found = _searched(init_docs(1, fleet), [doc.changes])
    ctr = W + 8
    bufs = []
    for _ in range(12):
        at = elems[rng.integers(len(elems))]
        bufs.append(doc.typing(A, ctr, at, 'uvwxy'))
        elems += [f'{ctr + i}@{A}' for i in range(5)]
        ctr += 5
    handles, more = _searched(handles, [bufs])
    row = _row_of(fleet, handles[0])
    assert fleet.metrics.seq_migrations == 1
    assert found + more == [1, 1]
    assert fleet.metrics.seq_search_dispatches == 2
    assert fleet.seq_ordered[row] == ctr - 1
    assert (np.diff(_device_ids(fleet, row)) > 0).all()
    assert_on_device(fleet)
    assert_matches(handles[0], doc.changes)


def test_rows_keep_their_order_apart(searching):
    """One call's inserts are read row by row: a row whose counters lie
    below another's in the same call stays in id order."""
    high, low = Text(), Text(owner=C)
    high.typing(A, W, '_head', 'abc')
    low.typing(C, 2, '_head', 'xy')
    fleet = DocFleet(doc_capacity=4)
    handles, found = _searched(init_docs(2, fleet),
                               [high.changes, low.changes])
    assert [fleet.seq_ordered[_row_of(fleet, h)] for h in handles] == \
        [W + 2, 3]
    assert found == [1]


def test_a_writer_joining_a_wide_row_keeps_it_in_id_order(searching):
    """A writer that sorts before the row's own and has seen all of it
    joins: the repack moves the owner to rank 1, and the row stays in id
    order, so the next dispatch searches it."""
    doc = one_text_past_the_window()
    fleet = DocFleet(doc_capacity=2)
    handles, _ = apply_changes_docs(init_docs(1, fleet), [doc.changes],
                                    mirror=False)
    row = _row_of(fleet, handles[0])
    repacks = fleet.metrics.seq_repacks
    handles, found = _searched(
        handles, [[doc.typing(B, W + 30, f'{W + 2}@{A}', 'QR')]])
    assert fleet.metrics.seq_repacks == repacks + 1
    assert fleet.seq_wide[row]['writers'] == [B, A]
    handles, more = _searched(
        handles, [[doc.typing(A, W + 40, f'{W + 31}@{B}', 'st')]])
    assert found + more == [1, 1]
    assert fleet.seq_ordered[row] == W + 41
    assert (np.diff(_device_ids(fleet, row)) > 0).all()
    assert_on_device(fleet)
    assert_matches(handles[0], doc.changes)


@pytest.mark.parametrize('counter', ['equal', 'lower', 'equal_in_one_call'])
def test_a_concurrent_insert_takes_its_row_out_of_id_order(counter,
                                                           searching):
    """A second writer's insert concurrent with the owner's, at the
    counter of the row's greatest element or a lower one, in a later call
    or in the owner's, takes the row out of id order. The dispatch that
    brings it still searches (its lookups read the row as it was before);
    the next one scans, with no program compiled for it, and the text is
    the host's and the reference's."""
    from automerge_tpu.fleet import sequence
    jitted = sequence.apply_seq_batch_donated.__wrapped__
    doc = Text()
    doc.typing(A, W, '_head', 'abcd')
    base = list(doc.heads)
    fleet = DocFleet(doc_capacity=2)
    handles, found = _searched(init_docs(1, fleet), [doc.changes])
    row = _row_of(fleet, handles[0])
    mine = doc.typing(A, W + 10, f'{W + 1}@{A}', 'wxyz', deps=base)
    head_a = list(doc.heads)
    if counter != 'equal_in_one_call':
        handles, more = _searched(handles, [[mine]])
        found += more
        assert fleet.seq_ordered[row] == W + 13
    start = W + 5 if counter == 'lower' else W + 13
    theirs = doc.typing(B, start, f'{W + 1}@{A}', 'pqrs', deps=base)
    handles, more = _searched(
        handles, [[theirs] if counter != 'equal_in_one_call' else
                  [mine, theirs]])
    found += more
    assert fleet.seq_ordered[row] == -1
    doc.heads = sorted(head_a + doc.heads)
    programs = jitted._cache_size()         # the first call's width, 4
    handles, more = _searched(
        handles, [[doc.typing(A, W + 20, f'{W + 11}@{A}', 'MNOP')]])
    found += more
    assert found == [1] * (len(found) - 1) + [0]
    assert fleet.metrics.seq_search_dispatches == len(found) - 1
    assert jitted._cache_size() == programs
    assert_on_device(fleet)
    assert materialize_docs(handles)[0]['text'] == rga_text(doc.changes)
    assert_matches(handles[0], doc.changes)


@pytest.mark.parametrize('start', [2, W])
def test_a_loaded_row_lies_in_id_order_and_reads_as_before(start,
                                                           searching):
    """load_docs lays a row's elements out in id order where its list
    order is another: typing at the head, a second writer, concurrent
    inserts at one element, a delete and an overwrite. The text, the
    patches (read off the device by the register fleet too) and save()
    are the host's, before and after a keystroke that the lookup finds by
    its search."""
    s = start
    doc = Text()
    doc.typing(A, s, '_head', 'abc')
    doc.typing(A, s + 10, '_head', 'xyz')
    doc.typing(B, s + 20, f'{s + 1}@{A}', 'mn')
    base = list(doc.heads)
    doc.typing(A, s + 30, f'{s}@{A}', 'Q', deps=base)
    head_a = list(doc.heads)
    doc.typing(B, s + 30, f'{s}@{A}', 'R', deps=base)
    doc.heads = sorted(head_a + doc.heads)
    doc.delete(A, s + 40, f'{s + 11}@{A}')
    doc.overwrite(B, s + 41, f'{s + 2}@{A}', 'C')
    loaded = list(doc.changes)
    saved = host.save(host_of(loaded))
    for exact in (True, False):
        fleet = DocFleet(doc_capacity=2, exact_device=exact)
        handles = loader.load_docs([saved], fleet)
        row = _row_of(fleet, handles[0])
        ids = _device_ids(fleet, row)
        assert len(ids) == 10 and (np.diff(ids) > 0).all()
        assert fleet.seq_ordered[row] == s + 30
        if exact:
            engine = handles[0]['state']._impl
            assert engine._register_patch_diffs() == \
                host.get_patch(host_of(loaded))['diffs']
            continue
        assert materialize_docs(handles)[0]['text'] == 'xzaQRbmnC'
        assert_matches(handles[0], doc.changes, text_of_rga=False)
        handles, found = _searched(
            handles, [[doc.typing(A, s + 50, f'{s + 21}@{B}', '!')]])
        assert found == [1]
        assert_on_device(fleet)
        assert_matches(handles[0], doc.changes, text_of_rga=False)
