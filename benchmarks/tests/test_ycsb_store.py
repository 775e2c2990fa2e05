"""The `map-store-ycsb` configuration's own tests, on the CPU at a tiny size:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_ycsb_store.py -q

The cell end to end reads ``correct`` with every limit 0, and false under
each of the control's faults; the key chooser is YCSB's scrambled zipfian;
the benchmark's writer makes the changes it says (read back by the
program's own decoder); the new metric readers read what they say, and
nothing from a program without the spans and counters.
"""

import hashlib
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
import reference_ycsb                               # noqa: E402
import wire_ycsb                                    # noqa: E402

CELL = 'map-store-ycsb.update_heavy'
TINY = {'records': 2000, 'ops_per_step': 512, 'encode_for_seconds': 3.0,
        'load_batch': 1024, 'warmup_steps': 3, 'audit_saves': 3}
LIMITS = {'read_mismatches', 'docs_missing', 'view_mismatches',
          'save_mismatches', 'offpath_calls'}
NEW_METRICS = ('read.ms_per_step', 'read.gather_ms_per_step',
               'read.render_ms_per_step', 'read.host_docs_per_step',
               'read_gather_roofline', 'seam.grid_pad_share.update_heavy',
               'device_idle_share.update_heavy',
               'xla.compiles_in_window.update_heavy')


def run_tiny(seed=2 ** 33 + 5, seconds=0.4):
    return harness.run_cell(CELL, seed, seconds, 0, cpu=True,
                            overrides=TINY)


def test_the_cell_runs_and_reads_correct():
    result = run_tiny()
    assert result['correct'] is True
    assert set(result['compared']) == LIMITS
    assert all(n['value'] == 0 == n['limit']
               for n in result['compared'].values())
    assert result['attempted'] > 0 and result['failed'] == 0
    assert set(result['metrics']) == {'ingest_changes_per_s', 'setup_s'}


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
    assert {'read_mismatches', 'view_mismatches'} <= over, over


def test_the_probe_ends_the_run_on_a_program_that_falls_back(monkeypatch):
    from automerge_tpu.fleet import backend as fleet_backend
    found = harness.resolve(CELL)
    driver = found['driver']
    state = driver.setup({**found['config'], **TINY}, found['mix'], 9)
    monkeypatch.setattr(fleet_backend, '_apply_changes_turbo',
                        lambda *args, **kwargs: None)
    with pytest.raises(harness.BenchError, match='left the device path'):
        driver.warmup(state)
    assert state['fleet'].metrics.fallbacks == 1


def test_the_warm_up_runs_every_grid_shape_of_the_window_first():
    """Each grid shape among the window's steps has been run by a step of
    the warm-up, and the steps run for a shape are held to the reference
    like the others."""
    found = harness.resolve(CELL)
    driver = found['driver']
    state = driver.setup({**found['config'], **TINY}, found['mix'], 11)
    driver.warmup(state)
    plan, ran = state['plan'], state['next_step']
    shapes = {driver.grid_shape(p.records) for p in plan}
    assert len(shapes) > 1
    assert shapes == {driver.grid_shape(p.records) for p in plan[:ran]}
    assert ran > int(TINY['warmup_steps']) + 1
    assert all(n == 0 for n, _ in driver.audit(state).values())


@pytest.mark.parametrize('records, shape', [
    ([7, 7, 7, 3, 3, 3], (2, 3)),
    ([7, 7, 7, 3, 3, 5], (4, 4)),
    ([1, 2, 3, 4, 5], (8, 1)),
    ([9] * 129 + [1], (2, 256)),
])
def test_grid_shape_is_the_grid_paths(records, shape):
    assert harness.resolve(CELL)['driver'].grid_shape(records) == shape


def test_a_program_that_reads_the_whole_fleet_ends_before_the_load(
        monkeypatch):
    """A read answered from the whole fleet's rows (as materialize_all
    gives them, and with no `read_rows` counted) ends the run at set-up's
    check, before any record is loaded."""
    from automerge_tpu.fleet import backend as fleet_backend

    def whole_fleet(handles):
        return [h['state'].fleet.materialize_all()[h['state']._impl.slot]
                for h in handles]
    monkeypatch.setattr(fleet_backend, 'materialize_docs', whole_fleet)
    found = harness.resolve(CELL)
    with pytest.raises(harness.BenchError, match='rows alone'):
        found['driver'].setup({**found['config'], **TINY}, found['mix'], 9)


# ---------------------------------------------------------------------------
# the key chooser and the writer are the configuration's
# ---------------------------------------------------------------------------

def java_fnvhash64(value):
    """Utils.fnvhash64 in Java's signed 64-bit arithmetic, one int."""
    mask = (1 << 64) - 1
    out = 0xCBF29CE484222325
    for _ in range(8):
        out ^= value & 0xff
        value >>= 8
        out = (out * 1099511628211) & mask
    signed = out - (1 << 64) if out >> 63 else out
    return abs(signed)


def test_the_key_chooser_is_ycsbs_scrambled_zipfian():
    found = harness.resolve(CELL)
    driver = found['driver']
    config = found['config']
    assert (config['zipfian_constant'], config['zipfian_items'],
            config['fields'], config['field_bytes'], config['records']) == \
        (0.99, 10 ** 10, 10, 100, 1_000_000)
    items = [0, 1, 2, 255, 256, 12345, 10 ** 10, 2 ** 40 + 3]
    assert driver.fnvhash64(items).tolist() == \
        [java_fnvhash64(i) for i in items]
    chooser = driver.KeyChooser(config, np.random.default_rng(3))
    draws = chooser.draw(400_000)
    assert draws.min() >= 0 and draws.max() < config['records']
    counts = np.bincount(draws, minlength=config['records'])
    # item 0 takes 1 / zetan of the draws (3.78 %), item 1 0.5^0.99 of it
    hottest = chooser.hottest()
    assert counts.argmax() == hottest
    assert 0.035 < counts[hottest] / len(draws) < 0.0405
    second = java_fnvhash64(1) % (config['records'] + 1)
    assert 0.017 < counts[second] / len(draws) < 0.021


def test_the_writer_makes_the_changes_it_says():
    from automerge_tpu.columnar import decode_change
    loader, client = b'\x11' * 16, b'\xee' * 16
    values = b''.join(bytes([65 + f]) * 100 for f in range(10))
    buf, digest = wire_ycsb.LoadWriter(loader, 10, 100).change(values)
    load = decode_change(buf)
    assert load['hash'] == digest.hex() and load['deps'] == []
    assert (load['actor'], load['seq'], load['startOp']) == \
        (loader.hex(), 1, 1)
    assert [(op['key'], op['value'], op['pred']) for op in load['ops']] == \
        [(f'field{f}', chr(65 + f) * 100, []) for f in range(10)]
    writer = wire_ycsb.UpdateWriter(10, 100, [loader, client])
    for pred_actor in (0, 1):
        buf, update_hash = writer.change(1, 3, 12345, digest, 7,
                                         b'z' * 100, 9, pred_actor)
        update = decode_change(buf)
        assert update['hash'] == update_hash.hex()
        assert update['deps'] == [digest.hex()]
        assert (update['actor'], update['seq'], update['startOp']) == \
            (client.hex(), 3, 12345)
        (op,) = update['ops']
        assert (op['action'], op['key'], op['value'], op['pred']) == \
            ('set', 'field7', 'z' * 100,
             [f'9@{(loader, client)[pred_actor].hex()}'])
    assert hashlib.sha256(buf[8:]).digest()[:4] == buf[4:8]


def test_a_save_is_held_to_its_history():
    """The reader and the comparison on a record saved by the program:
    its load and two updates of one field read back exactly, and a history
    that leaves one update out does not."""
    from automerge_tpu.fleet import backend as fleet_backend
    loader, client = b'\x11' * 16, b'\xee' * 16
    values = b''.join(bytes([65 + f]) * 100 for f in range(10))
    load, head = wire_ycsb.LoadWriter(loader, 10, 100).change(values)
    writer = wire_ycsb.UpdateWriter(10, 100, [loader, client])
    first, head1 = writer.change(1, 1, 11, head, 2, b'x' * 100, 3, 0)
    second, head2 = writer.change(1, 2, 12, head1, 2, b'y' * 100, 11, 1)
    fleet = fleet_backend.DocFleet(doc_capacity=2, key_capacity=16)
    handles = fleet_backend.init_docs(1, fleet)
    handles, _ = fleet_backend.apply_changes_docs(
        handles, [[load, first, second]], mirror=False)
    saved = bytes(fleet_backend.save(handles[0]))
    lo, cl = loader.hex(), client.hex()
    history = {
        'heads': [head2.hex()],
        'changes': [(lo, 1, 10, set()), (cl, 1, 11, {(lo, 1)}),
                    (cl, 2, 12, {(cl, 1)})],
        'ops': [(f'field{f}', f + 1, lo, chr(65 + f) * 100)
                for f in range(10)] +
               [('field2', 11, cl, 'x' * 100), ('field2', 12, cl, 'y' * 100)]}
    assert reference_ycsb.saved_record_differs(saved, history) is None
    short = dict(history, ops=history['ops'][:-1])
    assert reference_ycsb.saved_record_differs(saved, short) is not None
    ref = reference_ycsb.Reference(
        np.frombuffer(values, dtype=np.uint8)[None, :], 10, 100)
    ref.update([0, 0], [2, 2], [b'x' * 100, b'y' * 100])
    assert fleet_backend.materialize_docs(handles) == [ref.record(0)]


# ---------------------------------------------------------------------------
# the new metric readers
# ---------------------------------------------------------------------------

def test_the_cell_lists_its_metrics_and_the_readers_read():
    with open(os.path.join(ROOT, 'BENCHMARK.json')) as handle:
        bench = json.load(handle)
    found = harness.resolve(CELL)
    assert found['cell']['chips'] == 1
    names = {e['name'] for e in found['per_layer']}
    assert set(NEW_METRICS) <= names
    assert {'codec.parse_ms_per_step', 'seam.call_ms_per_step',
            'seam.stage_grid_ms_per_step', 'apply_op_batch_roofline',
            'host.gc_ms_per_step', 'trace.spans_dropped'} <= names
    assert CELL in next(e for e in bench['end_to_end']
                        if e['name'] == 'ingest_changes_per_s')['workloads']
    window = (1000, 9000)
    ctx = {'facts': {'steps': 4, 'window_ns': window, 'grid_row_bytes': 204,
                     'fleet_counters': {'read_host_docs': 2,
                                        'read_rows': 4000,
                                        'device_ops': 100}},
           'spans': [{'name': 'read_batch', 't0_ns': 2000, 't1_ns': 6000,
                      'dur_ns': 4000},
                     {'name': 'read.gather', 't0_ns': 2000, 't1_ns': 3000,
                      'dur_ns': 1000},
                     {'name': 'read.render', 't0_ns': 3000, 't1_ns': 5000,
                      'dur_ns': 2000},
                     {'name': 'grid.columns', 't0_ns': 7000, 't1_ns': 7100,
                      'dur_ns': 100, 'attrs': {'runs': 3, 'ragged': 2,
                                               'cells': 400}},
                     {'name': 'grid.columns', 't0_ns': 500, 't1_ns': 600,
                      'dur_ns': 100, 'attrs': {'runs': 3, 'ragged': 2,
                                               'cells': 4000}}],
           'compiles': {'compilations': 0}, 'trace_window_s': 2.0,
           'trace': {'busy_s': 0.5,
                     'modules': {'jit__gather_grid_rows_impl': [4, 4e-5]}},
           'peaks': {'hbm_bytes_per_s': 1e9, 'bf16_flops_per_s': 1e12}}
    readers = found['readers']
    assert readers['read.ms_per_step'].read(ctx) == 0.001
    assert readers['read.gather_ms_per_step'].read(ctx) == 0.00025
    assert readers['read.render_ms_per_step'].read(ctx) == 0.0005
    assert readers['read.host_docs_per_step'].read(ctx) == 0.5
    assert readers['seam.grid_pad_share.update_heavy'].read(ctx) == 75.0
    assert readers['device_idle_share.update_heavy'].read(ctx) == 75.0
    assert readers['xla.compiles_in_window.update_heavy'].read(ctx) == 0
    # 1,000 rows a gather of 2 x 204 + 4 bytes, 4 gathers in 40 us at 1 GB/s
    assert readers['read_gather_roofline'].read(ctx) == \
        pytest.approx(100.0 * 1000 * 412 / 1e9 * 4 / 4e-5)
    # a program without the spans and counters (the parent): nothing read
    bare = {'facts': {'steps': 4, 'window_ns': window, 'grid_row_bytes': 204,
                      'fleet_counters': {'fallbacks': 0}},
            'spans': [{'name': 'grid.columns', 't0_ns': 7000,
                       't1_ns': 7100, 'dur_ns': 100,
                       'attrs': {'runs': 3, 'ragged': 2}}],
            'compiles': {'compilations': 0}, 'trace_window_s': None,
            'trace': {'busy_s': 0.0, 'modules': {}},
            'peaks': ctx['peaks']}
    for name in NEW_METRICS[:6]:
        assert readers[name].read(bare) is None, name
