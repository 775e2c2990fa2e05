"""The `text-concurrent` configuration's own tests, on the CPU at a tiny size:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_text_concurrent.py -q

The cell runs and reads ``correct`` with every limit 0; the probe ends the
run on a program made to fall back; each of the control's faults reads
``correct`` false; the benchmark's writer of two-writer changes and
documents (wire_text_multi.py) is read back by its own reader, by the
program's decoder and loader, and its hashes are the program's; the
generated rounds are the configuration's; the new metric readers read what
they say and nothing from a program without the counters.
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
import wire_text_multi as wire                      # noqa: E402

CELL = 'text-concurrent.rounds'
TINY = {'docs': 6, 'history_ops_per_writer': 150, 'history_spread_ops': 60,
        'encode_for_seconds': 0.3, 'step_floor_ms': 2.0, 'warmup_steps': 8}
LIMITS = {'docs_missing', 'text_mismatches', 'heads_mismatches',
          'save_mismatches', 'inexact_rows', 'offpath_calls'}


def driver_and_config(**more):
    found = harness.resolve(CELL)
    return found['driver'], {**found['config'], **TINY, **more}, \
        found['mix']


def run_tiny(seed=7, seconds=0.3):
    return harness.run_cell(CELL, seed, seconds, 0, cpu=True,
                            overrides=TINY)


def test_the_cell_runs_and_reads_correct():
    result = run_tiny(seed=(1 << 31) + 29)
    assert result['correct'] is True and result['failed'] == 0
    assert result['attempted'] > 0
    assert set(result['compared']) == LIMITS
    assert all(entry == {'value': 0, 'limit': 0}
               for entry in result['compared'].values())
    assert set(result['metrics']) == {'ingest_changes_per_s', 'setup_s'}


def test_every_round_rides_the_device_path_and_meets_two_writers():
    driver, config, mix = driver_and_config()
    state = driver.setup(config, mix, 3)
    driver.warmup(state)
    out = driver.window(state, 0.2, harness.Tracer(False, 0))
    counters = out['facts']['fleet_counters']
    steps, n_docs = out['facts']['steps'], state['n_docs']
    assert counters['fallbacks'] == counters['exact_calls'] == 0
    assert counters['turbo_calls'] == steps
    assert counters['dag_seq_docs'] == steps * n_docs
    assert counters['seq_multiwriter_rows'] == steps * n_docs
    assert counters['turbo_commit_fallback_docs'] == 0
    compared = driver.audit(state)
    assert all(value == 0 for value, _limit in compared.values())
    # every document has two heads: the two writers' last changes
    from automerge_tpu.fleet import backend as fleet_backend
    for d, handle in enumerate(state['handles']):
        heads = fleet_backend.get_heads(handle)
        assert len(heads) == 2 and sorted(heads) == driver.heads_of(state, d)


def test_the_probe_ends_the_run_on_a_program_that_falls_back(monkeypatch):
    """With the DAG gate answering all-false a two-writer round leaves the
    turbo path, as on the parent's program: the first call of all ends the
    run, one document touched."""
    from automerge_tpu import native
    real = native.dag_gate

    def all_false(*args):
        ok, off, heads = real(*args)
        return np.zeros_like(ok), np.zeros_like(off), heads[:0]
    monkeypatch.setattr(native, 'dag_gate', all_false)
    driver, config, mix = driver_and_config()
    state = driver.setup(config, mix, 5)
    with pytest.raises(harness.BenchError, match='left the device path'):
        driver.warmup(state)
    metrics = state['fleet'].metrics
    assert metrics.fallbacks == 1 and metrics.exact_calls == 1
    assert sum(state['applied']) == 1


@pytest.mark.parametrize('fault', faults.FAULTS)
def test_a_fault_under_the_timed_path_reads_not_correct(fault):
    undo = faults.plant(fault)
    try:
        result = run_tiny()
    finally:
        undo()
    assert result['correct'] is False
    over = {name for name, n in result['compared'].items()
            if n['value'] > n['limit']}
    if fault == 'one_answer':
        # a dropped change leaves the next round out of order: the call
        # takes the exact path, and the document lacks a change
        assert over & {'text_mismatches', 'heads_mismatches',
                       'offpath_calls'}, over
    else:
        assert {'text_mismatches', 'heads_mismatches'} <= over, over


def test_the_same_seed_gives_the_same_documents_and_rounds():
    driver, config, mix = driver_and_config()
    states = [driver.setup(config, mix, 5) for _ in range(2)]
    for state in states:
        driver.encode(state, [3] * state['n_docs'])
    one, two = states
    assert one['actors'] == two['actors'] and one['starts'] == two['starts']
    assert len({a for pair in one['actors'] for a in pair}) == \
        2 * one['n_docs']
    assert one['queue'] == two['queue']
    assert one['first_heads'] == two['first_heads']
    other = driver.setup(config, mix, 6)
    assert other['actors'] != one['actors']


# ---------------------------------------------------------------------------
# the generated rounds
# ---------------------------------------------------------------------------

def grown(n_rounds=600, seed=9):
    driver, config, mix = driver_and_config()
    rounds = driver.Rounds(np.random.default_rng([seed, 1]), config, mix)
    rounds.extend(n_rounds)
    return driver, rounds


def test_the_rounds_are_the_configurations():
    """k geometric between 1 and the cap a writer; both chains start at one
    counter; a quarter of the rounds start at one spot; 70 % inserts; a
    writer names only what it can have seen, and deletes nothing twice."""
    driver, rounds = grown()
    ks = np.array(rounds.k[1:])
    assert ks.min() >= 1 and ks.max() <= 64 and 13.5 < ks.mean() < 16.5
    assert 0.19 < np.mean(rounds.same_spot[1:]) < 0.31
    n_ops = sum(len(chain) for ops in rounds.ops[1:] for chain in ops)
    inserts = sum(ins for ops in rounds.ops[1:] for chain in ops
                  for ins, _ref in chain)
    assert 0.67 < inserts / n_ops < 0.74
    made = {}                       # element -> round
    deleted = [set(), set()]
    for r in range(1, len(rounds) + 1):
        assert rounds.base[r + 1] == rounds.base[r] + max(rounds.k[r])
        for w in range(2):
            for j, (is_insert, ref) in enumerate(rounds.ops[r][w]):
                op = driver.code(rounds.base[r] + 1 + j, w)
                if ref:
                    # an earlier round's element, or the writer's own
                    assert made[ref] < r or (ref & 1 == w and ref < op)
                if is_insert:
                    made[op] = r
                else:
                    assert ref and ref not in deleted[w]
                    deleted[w].add(ref)
    # two writers did meet: an element both deleted, and rounds in which
    # both first inserted at one spot
    assert deleted[0] & deleted[1]
    assert sum(same and ops[0][0] == ops[1][0] and ops[0][0][0]
               for same, ops in zip(rounds.same_spot[1:], rounds.ops[1:]))
    # the list, for either order of the two ids, is the reference's
    for flip in range(2):
        pair = ('11' * 16, '99' * 16) if not flip else ('99' * 16, '11' * 16)
        rga = reference_text.Rga()
        for r in range(1, len(rounds) + 1):
            for w in (1, 0):
                for j, (is_insert, ref) in enumerate(rounds.ops[r][w]):
                    op = (rounds.base[r] + 1 + j, pair[w])
                    name = (ref >> 1, pair[ref & 1]) if ref else None
                    if is_insert:
                        rga.insert(op, name, 'x')
                    else:
                        rga.delete(op, name)
        assert [(e >> 1, pair[e & 1]) for e in rounds.order(flip)] == \
            [elem for elem, _char, _gone in rga.elements()]


def test_the_skip_rule_off_reads_another_text_where_two_writers_met():
    plain, rga = None, reference_text.Rga()
    driver, _config, _mix = driver_and_config()
    plain = driver.NoSkipRga()
    for each in (rga, plain):
        each.insert((2, 'a'), None, 'x')
        each.insert((3, 'b'), (2, 'a'), 'B')    # b's run after x
        each.insert((4, 'b'), (3, 'b'), 'C')
        each.insert((3, 'a'), (2, 'a'), 'a')    # a's, concurrent, smaller
    assert rga.text() == 'xBCa' and plain.text() == 'xaBC'


# ---------------------------------------------------------------------------
# the writer, its reader, and the program's decoder and loader
# ---------------------------------------------------------------------------

def test_the_list_encoders_are_the_column_encoders():
    rng = np.random.default_rng(4)
    for _trial in range(300):
        n = int(rng.integers(1, 70))
        values = rng.integers(0, 4, size=n) * rng.integers(0, 2, size=n)
        null = rng.random(n) < rng.choice([0.0, 0.3, 1.0])
        listed = [None if gone else int(v) for v, gone in zip(values, null)]
        assert wire.rle_list(listed) == wire_text.rle_column(values,
                                                             null=null)
        big = np.cumsum(rng.integers(-300, 300, size=n)) + 70000
        assert wire.delta_list([None if gone else int(v)
                                for v, gone in zip(big, null)]) == \
            wire_text.delta_column(big, null=null)
        flags = rng.random(n) < 0.5
        assert wire.boolean_list(flags.tolist()) == \
            wire_text.boolean_column(flags)


def test_changes_round_trip_and_are_what_the_program_decodes():
    """A round's chain as ONE change and keystroke by keystroke, through
    the benchmark's reader and the program's decoder: the same ops, the
    hash the writer computed, and the bytes the program's own encoder
    gives for what it decoded (the encoding is the canonical one)."""
    from automerge_tpu.columnar import decode_change, encode_change
    _driver, rounds = grown(80)
    pair = ('c3' * 16, '5a' * 16)
    ids = [bytes.fromhex(a) for a in pair]
    head = bytes.fromhex(wire.make_text_change(pair[0])[1])
    lists_other = set()
    for r in range(1, 81):
        for w in range(2):
            ops = rounds.ops[r][w]
            n_ins = sum(ins for ins, _ref in ops)
            chars = bytes(97 + i % 26 for i in range(n_ins))
            columns = wire.round_columns(ops, w)
            lists_other.add((w, columns[2]))
            data, digest = wire.round_change(
                ids[w], ids[1 - w], r + 1 - w, rounds.base[r] + 1, [head],
                columns, chars)
            typed = iter(chars.decode())
            want = [(ins, ref >> 1, pair[ref & 1] if ref else None,
                     next(typed) if ins else None) for ins, ref in ops]
            read = wire.read_keystrokes_change(data)
            assert read['ops'] == want and read['hash'] == digest.hex()
            assert (read['deps'], read['actor'], read['seq'],
                    read['start_op']) == ([head.hex()], pair[w], r + 1 - w,
                                          rounds.base[r] + 1)
            assert read['text_made_by'] == pair[0]
            change = decode_change(data)
            assert change['hash'] == digest.hex()
            again = bytes(encode_change(change))
            # (the program deflates a change of 256 bytes or more; its
            # hash is of the plain form)
            assert again == data if again[8] == 1 else \
                decode_change(again)['hash'] == digest.hex()
            assert len(change['ops']) == len(ops)
            for op, (ins, ctr, who, char) in zip(change['ops'], want):
                elem = f'{ctr}@{who}' if ctr else '_head'
                assert op['obj'] == f'1@{pair[0]}'
                assert op['elemId'] == elem and bool(op.get('insert')) == ins
                assert op['action'] == ('set' if ins else 'del')
                assert op.get('value') == char
                assert op['pred'] == ([] if ins else [elem])
            # keystroke by keystroke: one and two dependencies
            deps = (head, digest)
            for j, (ins, ref) in enumerate(ops[:6]):
                one, one_hash = wire.keystroke_change(
                    ids[w], ids[1 - w], w == 0, 900 + j,
                    rounds.base[r] + 1 + j, deps, ins, ref >> 1,
                    (ref & 1) != w, b'q')
                change = decode_change(one)
                assert change['hash'] == one_hash.hex()
                assert bytes(encode_change(change)) == one
                assert change['deps'] == sorted(h.hex() for h in deps)
                assert wire.read_keystrokes_change(one)['ops'] == [
                    (ins, ref >> 1, pair[ref & 1] if ref else None,
                     'q' if ins else None)]
                deps = (one_hash,)
    # the first writer's changes list the other only where they name it
    assert (0, True) in lists_other and (0, False) in lists_other
    assert (1, False) not in lists_other


def written(n_docs=3, seed=11, **more):
    driver, config, mix = driver_and_config(docs=n_docs, **more)
    return driver, driver.setup(config, mix, seed)


def test_a_written_document_loads_saves_and_reads_back():
    """Writer -> the host backend's loader, and writer -> load_docs ->
    a round -> save() -> reader: the program keeps what the benchmark
    wrote, and hashes its history to the heads the benchmark computed."""
    from automerge_tpu import backend as host
    from automerge_tpu.fleet import backend as fleet_backend
    driver, state = written()
    for d, handle in enumerate(state['handles']):
        assert sorted(fleet_backend.get_heads(handle)) == \
            state['first_heads'][d]
        assert len(state['fleet'].seq_writers[d]) == 2
        changes = fleet_backend.get_all_changes(handle)
        assert len(changes) == 2 * state['starts'][d] + 1
        # the host backend, which hashes every change it rebuilds from the
        # document, accepts the same bytes and reads the same text
        loaded = host.load(bytes(fleet_backend.save(handle)))
        assert sorted(host.get_heads(loaded)) == state['first_heads'][d]
    driver.encode(state, [2] * state['n_docs'])
    driver.step(state)
    driver.step(state)
    metrics = state['fleet'].metrics
    assert metrics.turbo_calls == 2 and metrics.fallbacks == 0
    views = fleet_backend.materialize_docs(state['handles'])
    for d, handle in enumerate(state['handles']):
        rga, _plain = driver.expected(state, d)
        assert views[d]['text'] == rga.text()
        saved = bytes(fleet_backend.save(handle))
        assert driver.saved_differs(state, d, rga, saved) is None
        doc = wire.read_text_document(saved)
        assert doc['actors'] == sorted(state['actors'][d])
        assert len(doc['heads']) == 2
        assert {actor for _ctr, actor, _ref, _char, _succ in
                doc['elements']} == set(state['actors'][d])
        # and the host backend reads that save as the same document
        assert sorted(host.get_heads(host.load(saved))) == doc['heads']


def test_the_saved_comparison_sees_a_wrong_op_and_a_wrong_head():
    from automerge_tpu.fleet import backend as fleet_backend
    driver, state = written(n_docs=1)
    driver.encode(state, [1])
    driver.step(state)
    rga, _plain = driver.expected(state, 0)
    saved = bytes(fleet_backend.save(state['handles'][0]))
    assert driver.saved_differs(state, 0, rga, saved) is None
    some = next(iter(state['rounds'].ref_of))
    kept = state['rounds'].ref_of[some]
    state['rounds'].ref_of[some] = 0 if kept else 5
    assert 'elements' in driver.saved_differs(state, 0, rga, saved)
    state['rounds'].ref_of[some] = kept
    assert 'does not read back' in driver.saved_differs(
        state, 0, rga, saved[:-3] + b'\x00\x00\x00')
    state['applied'][0] = 0
    assert 'heads' in driver.saved_differs(state, 0, rga, saved)


# ---------------------------------------------------------------------------
# the files, and the readers of the new per-layer metrics
# ---------------------------------------------------------------------------

def test_the_configuration_states_its_source_cuts_and_guarantees():
    with open(os.path.join(ROOT, 'BENCHMARK.json')) as handle:
        bench = json.load(handle)
    entry = harness.by_name(bench['configs'], 'text-concurrent', 'config')
    with open(os.path.join(ROOT, entry['file'])) as handle:
        config = json.load(handle)
    assert 'crdt-benchmarks' in entry['source'] and 'B2' in entry['source']
    assert len(entry['source']) <= 200 and len(entry['why']) <= 200
    assert entry['reduced'] == config['reduced']
    assert set(config['reduced']) <= set(config)
    assert config['docs'] == 128 and config['writers_per_doc'] == 2
    assert config['same_spot_share'] == 0.25
    assert config['history_ops_per_writer'] == 30000
    for key in ('assumed', 'guarantees', 'reduced_why', 'on_device',
                'source'):
        assert config[key]
    cell = harness.by_name(bench['workloads'], CELL, 'workload')
    assert cell['chips'] == 1 and len(cell['why']) <= 200
    listed = {e['name'] for e in bench['per_layer']
              if CELL in e.get('workloads', ())}
    assert {'seam.gate_shape_ms_per_step', 'seam.dag_seq_docs_per_step',
            'seq.multiwriter_rows_per_step', 'seq.pad_share.rounds',
            'device_idle_share.rounds', 'xla.compiles_in_window.rounds',
            'seam.gate_dag_ms_per_step', 'apply_seq_batch_roofline'} <= listed
    for name in listed:
        assert os.path.isfile(os.path.join(BENCH_DIR, 'metrics',
                                           name + '.py'))


def test_the_new_readers_read_what_they_say():
    found = harness.resolve(CELL)
    readers = found['readers']
    window = (1000, 9000)
    ctx = {
        'facts': {'steps': 4, 'window_ns': window, 'fleet_counters': {
            'dag_seq_docs': 512, 'seq_multiwriter_rows': 500,
            'seq_ops': 300, 'seq_op_cells': 1200}},
        'spans': [{'name': 'gate.shape', 't0_ns': 2000, 't1_ns': 4000,
                   'dur_ns': 2000},
                  {'name': 'gate.shape', 't0_ns': 500, 't1_ns': 1500,
                   'dur_ns': 1000}],
        'compiles': {'compilations': 0}, 'trace_window_s': 2.0,
        'trace': {'busy_s': 1.5},
    }
    assert readers['seam.dag_seq_docs_per_step'].read(ctx) == 128
    assert readers['seq.multiwriter_rows_per_step'].read(ctx) == 125
    assert readers['seam.gate_shape_ms_per_step'].read(ctx) == \
        pytest.approx(2000 / 1e6 / 4)
    assert readers['seq.pad_share.rounds'].read(ctx) == pytest.approx(75.0)
    assert readers['device_idle_share.rounds'].read(ctx) == \
        pytest.approx(25.0)
    assert readers['xla.compiles_in_window.rounds'].read(ctx) == 0
    # a program without the counters and the span: nothing to read
    bare = dict(ctx, spans=[], facts={'steps': 4, 'window_ns': window,
                                      'fleet_counters': {}})
    for name in ('seam.dag_seq_docs_per_step',
                 'seq.multiwriter_rows_per_step',
                 'seam.gate_shape_ms_per_step', 'seq.pad_share.rounds'):
        assert readers[name].read(bare) is None
