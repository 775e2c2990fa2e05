"""The text configuration's own tests, on the CPU at a tiny size:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_text_trace.py -q

The cell runs and reads ``correct``; each of the control's faults reads it
false; the benchmark's writer of text changes and documents (wire_text.py)
is read back by its own reader, by the program's decoder and loader, and its
hashes are the program's; the plain reference (reference_text.py) equals the
host backend on concurrent inserts at one position; the generated trace has
the source's counts.
"""

import json
import os
import sys

import numpy as np
import pytest

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
for _path in (ROOT, BENCH_DIR):
    if _path not in sys.path:
        sys.path.insert(0, _path)

import faults                                       # noqa: E402
import harness                                      # noqa: E402
import reference_text                               # noqa: E402
import wire_text                                    # noqa: E402

CELL = 'text-trace.replay'
TINY = {'docs': 6, 'start_offset_ops': [300, 400], 'prefix_change_ops': 64,
        'encode_for_seconds': 0.3, 'step_floor_ms': 2.0}


def driver_and_config():
    found = harness.resolve(CELL)
    return found['driver'], dict(found['config'], **TINY), found['mix']


def run_tiny(seed=7, seconds=0.3):
    return harness.run_cell(CELL, seed, seconds, 0, cpu=True,
                            overrides=TINY)


def test_the_cell_runs_and_reads_correct():
    result = run_tiny(seed=(1 << 31) + 29)
    assert result['correct'] is True and result['failed'] == 0
    assert result['attempted'] > 0
    assert set(result['compared']) == {
        'docs_missing', 'text_mismatches', 'save_mismatches',
        'inexact_rows'}
    assert set(result['metrics']) == {'ingest_changes_per_s', 'setup_s'}


@pytest.mark.parametrize('fault', faults.FAULTS)
def test_a_fault_under_the_timed_path_reads_not_correct(fault):
    undo = faults.plant(fault)
    try:
        result = run_tiny()
    finally:
        undo()
    assert result['correct'] is False
    failed = {name for name, n in result['compared'].items()
              if n['value'] > n['limit']}
    assert failed & {'text_mismatches', 'save_mismatches'}, failed


def test_the_same_seed_gives_the_same_documents_and_changes():
    driver, config, mix = driver_and_config()
    states = [driver.setup(config, mix, 5) for _ in range(2)]
    for state in states:
        driver.encode(state, [9] * state['n_docs'])
    one, two = states
    assert one['actors'] == two['actors'] and one['offsets'] == two['offsets']
    assert len(set(one['actors'])) == one['n_docs']
    assert one['queue'] == two['queue'] and one['last_head'] == \
        two['last_head']
    other = driver.setup(config, mix, 6)
    assert other['actors'] != one['actors']


def test_k_is_geometric_between_one_and_the_cap():
    driver, config, mix = driver_and_config()
    state = {'mix': mix, 'n_docs': 128, 'rng': np.random.default_rng(3)}
    k = driver.draw(state, 400)
    assert k.shape == (400, 128) and k.min() == 1 and k.max() == 64
    assert 14.5 < k.mean() < 16.5       # mean 16, less the redrawn tail
    assert (k.max(axis=1) > 32).mean() > 0.95


# ---------------------------------------------------------------------------
# the writer, its reader, and the program's decoder and loader
# ---------------------------------------------------------------------------

def small_trace(n=400, seed=5):
    driver, config, _mix = driver_and_config()
    trace = driver.Trace(np.random.default_rng(seed), config['insert_share'],
                         config['typing_run_mean'],
                         config['backspace_share'])
    trace.extend(n)
    return driver, trace


def op_counters(trace, lo, hi):
    ref = np.array(trace.ref[lo:hi])
    return np.array(trace.is_insert[lo:hi]), np.where(ref > 0, ref + 1, 0)


def test_changes_round_trip_and_are_what_the_program_decodes():
    """A change of many keystrokes and a change of one, through the
    benchmark's reader and through the program's decoder: the same ops,
    and the hash the writer computed."""
    from automerge_tpu.columnar import decode_change
    _driver, trace = small_trace()
    actor = 'c3' * 16
    _buf, head = wire_text.make_text_change(actor)
    is_insert, ref_ctr = op_counters(trace, 1, 301)
    chars = bytes(97 + i % 26 for i in range(int(is_insert.sum())))
    data, digest = wire_text.keystrokes_change(
        actor, 2, 2, [head], wire_text.keystroke_columns(is_insert, ref_ctr),
        chars)
    read = wire_text.read_keystrokes_change(data)
    typed = iter(chars.decode())
    want = [(bool(i), int(r), next(typed) if i else None)
            for i, r in zip(is_insert, ref_ctr)]
    assert read['ops'] == want and read['hash'] == digest
    assert (read['deps'], read['seq'], read['start_op']) == ([head], 2, 2)
    change = decode_change(data)
    assert change['hash'] == digest and len(change['ops']) == 300
    for op, (insert, ref, char) in zip(change['ops'], want):
        elem = f'{ref}@{actor}' if ref else '_head'
        assert op['elemId'] == elem and bool(op.get('insert')) == insert
        assert op['action'] == ('set' if insert else 'del')
        assert op.get('value') == char
        assert op['pred'] == ([] if insert else [elem])
    # the change of one keystroke, by hand, is the general writer's
    for t in range(301, 341):
        is_insert, ref_ctr = op_counters(trace, t, t + 1)
        general, want_hash = wire_text.keystrokes_change(
            actor, t, t + 1, [digest],
            wire_text.keystroke_columns(is_insert, ref_ctr),
            b'q' if is_insert[0] else b'')
        by_hand, got_hash = wire_text.keystroke_change(
            bytes.fromhex(actor), t, t + 1, bytes.fromhex(digest),
            bool(is_insert[0]), int(ref_ctr[0]), b'q')
        assert by_hand == general and got_hash.hex() == want_hash
    # and an insert at the head, which has no key actor
    general, want_hash = wire_text.keystrokes_change(
        actor, 9, 400, [digest],
        wire_text.keystroke_columns([True], [0]), b'h')
    by_hand, got_hash = wire_text.keystroke_change(
        bytes.fromhex(actor), 9, 400, bytes.fromhex(digest), True, 0, b'h')
    assert by_hand == general and got_hash.hex() == want_hash


def written(n_docs=3, seed=11):
    driver, config, mix = driver_and_config()
    config['docs'] = n_docs
    return driver, driver.setup(config, mix, seed)


def test_a_written_document_loads_saves_and_reads_back():
    """Writer -> reader, and writer -> load_docs -> save() -> reader: the
    program keeps what the benchmark wrote, and hashes its history to the
    head the benchmark computed."""
    from automerge_tpu.fleet import backend as fleet_backend
    driver, state = written()
    for d, handle in enumerate(state['handles']):
        rga = driver.expected(state, d)
        saved = bytes(fleet_backend.save(handle))
        assert driver.saved_differs(state, d, rga, saved) is None
        doc = wire_text.read_text_document(saved)
        assert doc['actors'] == [state['actors'][d]]
        assert len(doc['elements']) == sum(
            state['trace'].is_insert[:state['offsets'][d] + 1])
        # the history's changes, rebuilt by the program from the document,
        # hash to the benchmark's head
        changes = fleet_backend.get_all_changes(handle)
        assert len(changes) == state['n_history_changes'][d]
        assert fleet_backend.get_heads(handle) == [state['last_head'][d]]
    views = fleet_backend.materialize_docs(state['handles'])
    assert [v['text'] for v in views] == [
        driver.expected(state, d).text() for d in range(state['n_docs'])]


def test_the_saved_comparison_sees_a_wrong_op():
    from automerge_tpu.fleet import backend as fleet_backend
    driver, state = written(n_docs=1)
    rga = driver.expected(state, 0)
    saved = bytes(fleet_backend.save(state['handles'][0]))
    assert driver.saved_differs(state, 0, rga, saved) is None
    state['trace'].ref[7] = 0 if state['trace'].ref[7] else 1
    assert 'elements' in driver.saved_differs(state, 0, rga, saved)
    assert 'does not read back' in driver.saved_differs(
        state, 0, rga, saved[:-3] + b'\x00\x00\x00')


# ---------------------------------------------------------------------------
# the reference, and the trace
# ---------------------------------------------------------------------------

def test_the_reference_equals_the_host_backend_on_concurrent_inserts():
    """Three actors insert runs at ONE position concurrently, and delete:
    the reference's text is the host backend's (backend/op_set.py), for
    every order the changes may arrive in causally."""
    import automerge_tpu as am
    from automerge_tpu.columnar import decode_change
    rng = np.random.default_rng(17)
    for _trial in range(6):
        base = am.from_({'text': am.Text('ab')}, '55' * 16)
        forks = []
        for a in ('11', '99', 'ee'):
            fork = am.merge(am.init(a * 16), base)
            for run in range(int(rng.integers(1, 4))):
                at = int(rng.integers(0, 2))      # one of two positions
                fork = am.change(fork, lambda r: r['text'].insert_at(
                    at, *(a[0] + str(run))))
            if rng.random() < 0.5:
                fork = am.change(fork, lambda r: r['text'].delete_at(
                    len(r['text']) - 1))
            forks.append(fork)
        merged = forks[0]
        for fork in forks[1:]:
            merged = am.merge(merged, fork)
        rga = reference_text.Rga()
        for change in map(decode_change, am.get_all_changes(merged)):
            for i, op in enumerate(change['ops']):
                op_id = (change['startOp'] + i, change['actor'])
                if op['action'] == 'makeText':
                    continue

                def named(elem):
                    ctr, actor = elem.split('@')
                    return (int(ctr), actor)
                if op.get('insert'):
                    rga.insert(op_id, None if op['elemId'] == '_head'
                               else named(op['elemId']), op['value'])
                else:
                    assert op['action'] == 'del'
                    rga.delete(op_id, named(op['elemId']))
        assert rga.text() == str(merged['text'])


def test_the_reference_skips_greater_ids_and_keeps_deleted_elements():
    rga = reference_text.Rga()
    rga.insert((2, 'a'), None, 'x')
    rga.insert((3, 'b'), None, 'y')       # greater id: before x
    rga.insert((3, 'a'), None, 'z')       # (3, a) < (3, b): after y
    rga.delete((4, 'a'), (3, 'b'))
    assert rga.text() == 'zx'
    assert [e[0] for e in rga.elements()] == [(3, 'b'), (3, 'a'), (2, 'a')]
    with pytest.raises(KeyError):
        rga.insert((9, 'a'), (8, 'a'), 'q')


def test_the_generated_trace_has_the_sources_counts():
    """259,778 ops, 182,315 inserts, 77,463 deletes within 0.5 %, every
    referent live or the head, one cursor."""
    with open(os.path.join(BENCH_DIR, 'configs', 'text-trace.json')) as f:
        config = json.load(f)
    driver, _config, _mix = driver_and_config()
    trace = driver.Trace(np.random.default_rng([3, 1]),
                         config['insert_share'], config['typing_run_mean'],
                         config['backspace_share'])
    trace.extend(config['trace_ops'])
    inserts = sum(trace.is_insert)
    assert len(trace) == config['trace_ops'] == 259778
    assert abs(inserts - config['trace_inserts']) < 0.005 * 182315
    assert abs(len(trace) - inserts - config['trace_deletes']) < \
        0.005 * 77463 + 0.005 * 182315
    # the text that is left is what the source's counts leave
    live = len(trace.left) + len(trace.right)
    assert live == inserts - (len(trace) - inserts)
    assert len(trace.order()) == inserts
    # no element is deleted twice, and an insert's referent came before it
    deleted = [r for i, r in zip(trace.is_insert[1:], trace.ref[1:])
               if not i]
    assert len(set(deleted)) == len(deleted)
    assert all(r < t for t, r in enumerate(trace.ref) if t)
