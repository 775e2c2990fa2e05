"""Sequence register lanes follow a row's writers, not the fleet's actor
table (ISSUE 29): a fleet of single-author Text documents keeps 4 lanes an
element however many actors it holds, concurrent writers with high actor
numbers still find lanes, a fifth live writer of one element widens its
pool, and a late actor that sorts first renumbers values without moving
lanes. Every read is held against the host backend, byte for byte. Also
here: `_dispatch_seq`'s one width rule, its counters and its three
sub-phase spans.
"""

import random

import numpy as np
import pytest

import automerge_tpu as am
from automerge_tpu import native
from automerge_tpu.fleet import backend as fleet_backend
from automerge_tpu.fleet import sequence
from automerge_tpu.fleet.backend import DocFleet
from automerge_tpu.fleet.loader import load_docs
from automerge_tpu.observability import spans

pytestmark = pytest.mark.skipif(not native.available(),
                                reason='native codec unavailable')


def actor_ids(n, seed=29):
    rng = random.Random(seed)
    return [rng.randbytes(16).hex() for _ in range(n)]


def single_author_doc(actor, rng):
    """A Text that one actor typed into, deleted from and typed on."""
    doc = am.from_({'t': am.Text('lorem ipsum')}, actor)
    for _ in range(2):
        at = rng.randrange(len(doc['t']))
        doc = am.change(doc, lambda r: r['t'].insert_at(at, 'x', 'y'))
        gone = rng.randrange(len(doc['t']))
        doc = am.change(doc, lambda r: r['t'].delete_at(gone))
    return doc


def lanes_of(fleet):
    return {st.actor_slots for st in fleet.seq_pools.pools.values()}


def assert_equals_host(handles, docs):
    """materialize_docs and save() against the host backend's."""
    views = fleet_backend.materialize_docs(handles)
    for handle, view, doc in zip(handles, views, docs):
        assert view == {'t': str(doc['t'])}
        assert bytes(fleet_backend.save(handle)) == bytes(am.save(doc))


def forty_documents():
    rng = random.Random(1)
    return [single_author_doc(actor, rng) for actor in actor_ids(40)]


def assert_four_lanes_and_exact(fleet, n_actors=40):
    assert len(fleet.actors) == n_actors
    assert lanes_of(fleet) == {sequence.DEFAULT_ACTOR_SLOTS}
    assert not any(fleet.seq_row_inexact(row)
                   for row in range(len(fleet.seq_rows)))
    # a slot costs 8 + 13 x 4 bytes, and a few bytes a row of cursors
    assert 60.0 <= fleet.metrics.seq_pool_bytes / fleet.metrics.seq_nodes \
        < 60.1


def test_forty_single_author_documents_keep_four_lanes_applied():
    docs = forty_documents()
    fleet = DocFleet(doc_capacity=64)
    handles, _ = fleet_backend.apply_changes_docs(
        fleet_backend.init_docs(len(docs), fleet),
        [am.get_all_changes(doc) for doc in docs], mirror=False)
    assert_four_lanes_and_exact(fleet)
    assert_equals_host(handles, docs)


def test_forty_single_author_documents_keep_four_lanes_loaded():
    docs = forty_documents()
    fleet = DocFleet(doc_capacity=64)
    handles = load_docs([am.save(doc) for doc in docs], fleet)
    assert_four_lanes_and_exact(fleet)
    assert_equals_host(handles, docs)
    # one more keystroke a document, on top of the loaded rows
    edited = [am.change(doc, lambda r: r['t'].insert_at(1, 'z'))
              for doc in docs]
    handles, _ = fleet_backend.apply_changes_docs(
        handles, [[am.get_last_local_change(doc)] for doc in edited],
        mirror=False)
    assert_four_lanes_and_exact(fleet)
    assert_equals_host(handles, edited)


def concurrent_writers(n_writers, first_actor_byte):
    """One list whose element 0 `n_writers` actors set concurrently, then
    merged: n_writers live values on one element."""
    base = am.from_({'l': ['base', 'tail']}, '00' * 16)
    forks = []
    for i in range(n_writers):
        actor = f'{first_actor_byte + i:02x}' * 16
        fork = am.merge(am.init(actor), base)
        forks.append(am.change(
            fork, lambda r: r['l'].__setitem__(0, f'from-{actor[:2]}')))
    merged = forks[0]
    for fork in forks[1:]:
        merged = am.merge(merged, fork)
    return merged


def concurrent_writers_read_like_the_host(n_writers, lanes):
    """Writers whose fleet actor numbers are past the lane count (six
    single-author documents sort before them) find lanes by value; the
    pool has `lanes` lanes, and no read is wrong."""
    rng = random.Random(2)
    fillers = [single_author_doc(f'{i + 1:02x}' * 16, rng) for i in range(6)]
    merged = concurrent_writers(n_writers, 0xf0)
    docs = fillers + [merged]
    fleet = DocFleet(doc_capacity=8)
    handles, _ = fleet_backend.apply_changes_docs(
        fleet_backend.init_docs(len(docs), fleet),
        [am.get_all_changes(doc) for doc in docs], mirror=False)
    assert min(fleet.actors.index[f'{0xf0 + i:02x}' * 16]
               for i in range(n_writers)) > sequence.DEFAULT_ACTOR_SLOTS
    row = fleet.slot_seq[handles[-1]['state']._impl.slot]
    (row,) = row.values()
    cls, idx = fleet.seq_place[row]
    assert fleet.seq_pools.state(cls).actor_slots == lanes
    assert not fleet.seq_row_inexact(row)
    assert len(sequence.element_conflicts(
        fleet.seq_pools.state(cls), idx)) == 1
    assert fleet_backend.materialize_docs(handles)[-1] == \
        {'l': list(merged['l'])}
    host = am.backend.load(am.save(merged))
    assert fleet_backend.get_patch(handles[-1]) == am.backend.get_patch(host)
    assert bytes(fleet_backend.save(handles[-1])) == bytes(am.save(merged))


def test_three_concurrent_writers_above_the_lane_count():
    concurrent_writers_read_like_the_host(3, 4)


def test_a_fifth_live_writer_of_one_element_widens_the_pool():
    concurrent_writers_read_like_the_host(5, 8)


def test_an_actor_that_sorts_first_arrives_later():
    """The late actor renumbers every packed opId on the device
    (_remap_seq_actors); the lanes stay, and reads equal the host's with
    both actors' edits on the renumbered elements."""
    late, early = '00' * 16, 'ee' * 16
    doc = am.from_({'t': am.Text('abc')}, early)
    fleet = DocFleet(doc_capacity=2)
    handles, _ = fleet_backend.apply_changes_docs(
        fleet_backend.init_docs(1, fleet), [am.get_all_changes(doc)],
        mirror=False)
    fleet.flush()
    remaps = fleet.metrics.remaps
    have = {bytes(c) for c in am.get_all_changes(doc)}
    # the late actor writes into the early one's document, which goes on
    fork = am.merge(am.init(late), doc)
    fork = am.change(fork, lambda r: r['t'].insert_at(2, 'q'))
    fork = am.change(fork, lambda r: r['t'].delete_at(0))
    doc = am.change(doc, lambda r: r['t'].insert_at(2, 'w'))
    merged = am.merge(doc, fork)
    news = [c for c in am.get_all_changes(merged) if bytes(c) not in have]
    handles, _ = fleet_backend.apply_changes_docs(handles, [news],
                                                  mirror=False)
    assert fleet.metrics.remaps > remaps
    assert fleet.actors.index[late] == 0 and fleet.actors.index[early] == 1
    assert lanes_of(fleet) == {sequence.DEFAULT_ACTOR_SLOTS}
    assert_equals_host(handles, [merged])
    assert not fleet.seq_row_inexact(0)


def keystrokes(doc, n):
    """`doc` after n more one-op changes, and those changes."""
    out = []
    for i in range(n):
        doc = am.change(doc, lambda r: r['t'].insert_at(i % 3, 'k'))
        out.append(am.get_last_local_change(doc))
    return doc, out


def test_one_program_for_lists_of_37_and_51_and_the_counters():
    """The op columns' width is bucketed to a power of two, so two calls
    whose longest lists are 37 and 51 run ONE apply_seq_batch_donated
    program; seq_ops counts the real ops, seq_op_cells what the device was
    handed."""
    (actor,) = actor_ids(1, seed=4)
    doc = am.from_({'t': am.Text('x' * 130)}, actor)     # the 256 class
    fleet = DocFleet(doc_capacity=2)
    handles, _ = fleet_backend.apply_changes_docs(
        fleet_backend.init_docs(1, fleet), [am.get_all_changes(doc)],
        mirror=False)
    jitted = sequence.apply_seq_batch_donated.__wrapped__
    for longest in (37, 51):
        doc, changes = keystrokes(doc, longest)
        before = fleet.metrics.snapshot()
        programs = jitted._cache_size()
        handles, _ = fleet_backend.apply_changes_docs(handles, [changes],
                                                      mirror=False)
        if longest == 51:
            assert jitted._cache_size() == programs
        moved = fleet.metrics.delta(before)
        assert moved['seq_ops'] == longest
        assert moved['seq_op_cells'] == 64        # one pool row x width
    assert_equals_host(handles, [doc])


def test_three_sub_phases_tile_dispatch_seq():
    (actor,) = actor_ids(1, seed=5)
    doc = am.from_({'t': am.Text('abc')}, actor)
    fleet = DocFleet(doc_capacity=2)
    handles = fleet_backend.init_docs(1, fleet)
    spans.enable(capacity=4096)
    try:
        spans.clear()
        handles, _ = fleet_backend.apply_changes_docs(
            handles, [am.get_all_changes(doc)], mirror=False)
        recorded = spans.iter_spans()
    finally:
        spans.disable()
    (whole,) = [s for s in recorded if s['name'] == 'dispatch_seq']
    parts = [s for s in recorded if s['parent'] == whole['id']]
    assert [s['name'] for s in parts] == ['seq.place', 'seq.columns',
                                          'seq.enqueue']
    # they tile it: each starts where the one before ends
    assert all(a['t1_ns'] == b['t0_ns'] for a, b in zip(parts, parts[1:]))
    assert whole['t0_ns'] <= parts[0]['t0_ns'] and \
        parts[-1]['t1_ns'] <= whole['t1_ns']
    # rows of 64 slots are too short for the search to pay: a scan
    assert parts[-1]['attrs'] == {'cls': 0, 'rows': 1, 'width': 4, 'ops': 3,
                                  'multiwriter_rows': 0,
                                  'lookup_nodes': 1 * (64 + 3), 'search': 0}
    assert np.asarray(fleet.seq_pools.state(0).n)[0] == 3
    # a row that needs 70 slots moves up a size class, and reads the same
    assert fleet._place_seq_rows([0], [70]) == [(1, 0)]
    assert fleet.metrics.seq_migrations == 1
    assert fleet_backend.materialize_docs(handles) == [{'t': 'abc'}]
