"""The point read: ``materialize_docs`` over some of a fleet's handles moves
and renders only the asked slots' rows (``DocFleet.gather_rows`` /
``render_rows``), and answers what ``materialize_all()`` answers for those
slots and what the host OpSet answers for those documents, on the LWW grid
and on the register engine alike. Slots the device cannot serve
(``del_fallback``, ``grid_overflow``, promoted documents) go to the host as
before. One seeded YCSB workload A run, at 1,000 records, holds every
step's reads to the benchmark's own reference (benchmarks/reference_ycsb.py).
"""

import os
import sys

import pytest

import automerge_tpu as am
from automerge_tpu import native
from automerge_tpu.backend.op_set import OpSet
from automerge_tpu.columnar import decode_change, encode_change
from automerge_tpu.fleet.backend import (DocFleet, _leaf_value,
                                         apply_changes_docs, free_docs,
                                         init_docs, materialize_docs)
from automerge_tpu.fleet.tensor_doc import CTR_LIMIT

A, B = 'aa' * 16, 'bb' * 16
LONG = [('%03d' % i) * 34 for i in range(4)]       # 102 characters
LONG = [s[:100] for s in LONG]

pytestmark = pytest.mark.skipif(not native.available(),
                                reason='needs the native codec')


def change(actor, seq, start_op, ops, deps=()):
    return encode_change({'actor': actor, 'seq': seq, 'startOp': start_op,
                          'time': 0, 'message': '', 'deps': sorted(deps),
                          'ops': ops})


def chain(actor, changes):
    """Buffers of one actor's changes, each after the one before: each
    entry is (start_op, ops)."""
    out, deps = [], []
    for seq, (start_op, ops) in enumerate(changes, 1):
        buf = change(actor, seq, start_op, ops, deps)
        deps = [decode_change(buf)['hash']]
        out.append(buf)
    return out


def put(key, value, pred=(), obj='_root', **extra):
    return {'action': 'set', 'obj': obj, 'key': key, 'value': value,
            'pred': list(pred), **extra}


def logs():
    """Name -> one document's log; every shape the render tells apart."""
    text = am.from_({'text': am.Text('ab')}, B)
    text = am.change(text, lambda d: d['text'].insert_at(1, 'x'))
    return {
        'plain': chain(A, [(1, [put('x', 1), put('y', 2)])]),
        'strings': chain(A, [
            (1, [put(f'field{i}', LONG[i]) for i in range(3)]),
            (4, [put('field1', LONG[3], pred=[f'2@{A}'])])]),
        'counter': chain(A, [
            (1, [put('c', 10, datatype='counter')]),
            (2, [{'action': 'inc', 'obj': '_root', 'key': 'c', 'value': 5,
                  'pred': [f'1@{A}']}])]),
        'nested': chain(A, [(1, [
            {'action': 'makeMap', 'obj': '_root', 'key': 'inner',
             'pred': []},
            put('x', 'y', obj=f'1@{A}'), put('top', 3)])]),
        'deleted': chain(A, [
            (1, [put('k', 1), put('j', 2)]),
            (3, [{'action': 'del', 'obj': '_root', 'key': 'k',
                  'pred': [f'1@{A}']}])]),
        'overflow': chain(A, [(1, [put('k1', 1)]),
                              (2 * CTR_LIMIT + 3, [put('k2', 2)])]),
        'promoted': chain(B, [(1, [put('p', 'q')])]),
        'text': [bytes(c) for c in am.get_all_changes(text)],
        'freed': chain(A, [(1, [put('gone', 1)])]),
        'freed_too': chain(B, [(1, [put('gone', 2)])]),
    }


def oracle(log):
    ops = OpSet()
    ops.apply_changes(log)
    return _leaf_value(ops.get_patch()['diffs'])


@pytest.fixture(scope='module', params=[False, True],
                ids=['grid', 'registers'])
def store(request):
    """(fleet, name -> handle, name -> the host OpSet's view)."""
    written = logs()
    names = list(written)
    fleet = DocFleet(doc_capacity=4, key_capacity=4,
                     exact_device=request.param)
    handles, _ = apply_changes_docs(init_docs(len(names), fleet),
                                    [written[n] for n in names],
                                    mirror=False)
    by_name = dict(zip(names, handles))
    by_name['promoted']['state'].promote()
    free_docs([by_name.pop('freed'), by_name.pop('freed_too')])
    return fleet, by_name, {n: oracle(written[n]) for n in by_name}


CASES = {
    'out_of_order_and_repeated': ['nested', 'plain', 'nested', 'strings',
                                  'plain', 'text'],
    'free_slots': ['plain', 'strings', 'counter', 'nested', 'text'],
    'del_fallback': ['deleted', 'plain'],
    'grid_overflow': ['overflow'],
    'promoted': ['promoted', 'plain'],
    'nested_map': ['nested'],
    'counter': ['counter'],
    'strings_100_bytes': ['strings'],
    'empty': [],
}


@pytest.mark.parametrize('case', list(CASES))
def test_a_point_read_answers_what_the_whole_fleet_and_the_oracle_do(
        store, case):
    fleet, handles, want = store
    asked = [handles[name] for name in CASES[case]]
    whole = fleet.materialize_all()
    before = fleet.metrics.snapshot()
    got = materialize_docs(asked)
    moved = fleet.metrics.delta(before)
    assert got == [want[name] for name in CASES[case]]
    served = [h['state']._impl.slot for h in asked
              if h['state'].is_fleet and
              h['state']._impl.slot not in fleet.grid_overflow | \
              fleet.del_fallback]
    for slot, handle, view in zip(
            [h['state']._impl.slot if h['state'].is_fleet else None
             for h in asked], asked, got):
        if slot in served:
            assert view == whole[slot]
    # the gather moved each device-served slot once, and no other row
    assert moved['read_rows'] == len(set(served))
    assert moved['read_docs'] == len(asked)
    assert moved['read_host_docs'] == len(asked) - sum(
        h['state'].is_fleet and h['state']._impl.slot in served
        for h in asked)
    if case == 'free_slots':
        # two freed and one promoted; the register engine promotes the
        # overflowing document too
        assert len(fleet.free_slots) == 3 + fleet.exact_device
        assert all(whole[slot] == {} for slot in fleet.free_slots)
    if case == 'out_of_order_and_repeated':
        assert got[0] is got[2]                    # one row, one dict
    if case in ('del_fallback', 'grid_overflow') and \
            not fleet.exact_device:
        assert moved['read_host_docs'] == 1


def test_a_read_over_every_slot_is_the_whole_fleet(store):
    fleet, handles, want = store
    live = [h for h in handles.values() if h['state'].is_fleet]
    slots = [h['state']._impl.slot for h in live]
    before = fleet.metrics.snapshot()
    got = materialize_docs(live)
    whole = fleet.materialize_all()
    assert fleet.metrics.delta(before)['read_rows'] == len(set(slots)) - \
        len(set(slots) & (fleet.grid_overflow | fleet.del_fallback))
    assert got == [want[name] for name, h in handles.items()
                   if h['state'].is_fleet]
    assert len(whole) == fleet.n_slots
    for slot, view in zip(slots, got):
        if slot not in fleet.grid_overflow | fleet.del_fallback:
            assert view == whole[slot]


# ---------------------------------------------------------------------------
# YCSB workload A: every step's reads against the benchmark's reference
# ---------------------------------------------------------------------------

BENCH_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), 'benchmarks')


def test_ycsb_a_reads_match_the_reference_step_by_step():
    """1,000 records, ten steps of the configuration's own draws (a step's
    reads, then its updates), every read against the reference as of the
    step's start, and every record at the end."""
    if BENCH_DIR not in sys.path:
        sys.path.insert(0, BENCH_DIR)
    import harness
    from reference_ycsb import Reference
    found = harness.resolve('map-store-ycsb.update_heavy')
    driver = found['driver']
    config = {**found['config'], 'records': 1000, 'load_batch': 1000,
              'ops_per_step': 1024}
    state = driver.setup(config, found['mix'], 2 ** 33 + 7)
    store, fleet = state['store'], state['fleet']
    reference = Reference(store.load_values, store.fields, store.field_bytes)
    for _ in range(10):
        plan = store.encode()
        before = fleet.metrics.snapshot()
        views = driver.step(state, plan)
        assert views == [reference.record(r) for r in plan.reads]
        assert fleet.metrics.delta(before)['read_rows'] == \
            len(set(plan.reads))
        reference.update(plan.records, plan.fields, plan.values)
    assert fleet.metrics.fallbacks == 0 and fleet.metrics.exact_calls == 0
    assert materialize_docs(state['handles']) == [
        reference.record(r) for r in range(store.records)]
