"""The benchmark's own tests, on the CPU at tiny sizes:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q

They rehearse the driver through ``harness.run_cell`` (run.py itself
refuses to run without a TPU), plant the faults a cell can have underneath
the timed path and see ``correct`` come out false, and pin the harness's
contract: files found by name, the result's keys, the names and units of
BENCHMARK.json, the trace reducer on a recorded trace.
"""

import glob
import json
import os
import re
import shutil
import sys

import pytest

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
for _path in (ROOT, BENCH_DIR):
    if _path not in sys.path:
        sys.path.insert(0, _path)

import faults                                       # noqa: E402
import harness                                      # noqa: E402
import trace_reduce                                 # noqa: E402
import wire                                         # noqa: E402
from reference import map_view, saved_document_differs   # noqa: E402

TINY = {
    'map-merge-10k.bulk': {'docs': 96, 'keys_per_doc': 16,
                           'sets_per_doc': 12},
}
FIXTURES = sorted(glob.glob(os.path.join(
    ROOT, 'traces', 'bench', 'plugins', 'profile', '*', 'vm.xplane.pb')))


def run_tiny(workload, seed=7, seconds=0.5, **kw):
    return harness.run_cell(workload, seed, seconds, 0, cpu=True,
                            overrides=TINY[workload], **kw)


# ---------------------------------------------------------------------------
# BENCHMARK.json and the files it names
# ---------------------------------------------------------------------------

NAME = re.compile(r'[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}\Z')
UNIT = re.compile(r'[A-Za-z0-9_/%.\-]{1,16}\Z')


def bench():
    with open(os.path.join(ROOT, 'BENCHMARK.json')) as handle:
        return json.load(handle)


def test_benchmark_json_names_units_and_keys():
    b = bench()
    assert set(b) == {'command', 'paths', 'run_seconds', 'configs',
                      'workloads', 'end_to_end', 'per_layer'}
    assert 1 <= b['run_seconds'] <= 51
    for config in b['configs']:
        assert set(config) == {'name', 'source', 'file', 'reduced', 'why'}
        assert NAME.match(config['name'])
        assert config['file'].startswith(b['paths'][0] + '/')
        assert len(config['source']) <= 200 and len(config['why']) <= 200
    for cell in b['workloads']:
        assert set(cell) == {'name', 'config', 'traffic', 'chips', 'why'}
        assert NAME.match(cell['name']) and NAME.match(cell['traffic'])
        assert cell['chips'] in (1, 4) and len(cell['why']) <= 200
    e2e = {'name', 'unit', 'better', 'bound', 'source'}
    layer = {'name', 'unit', 'better', 'source', 'layer', 'moves'}
    names = set()
    for metric in b['end_to_end'] + b['per_layer']:
        keys = set(metric) - {'workloads'}
        assert keys == (e2e if 'bound' in metric else layer), metric
        assert NAME.match(metric['name']) and UNIT.match(metric['unit'])
        assert metric['better'] in ('lower', 'higher')
        assert metric['name'] not in names
        names.add(metric['name'])
    for metric in b['end_to_end']:
        assert metric['source'] in ('host_clock', 'device_trace')
        assert 0.01 <= metric['bound'] <= 0.25
    assert 'setup_s' in names
    e2e_names = {m['name'] for m in b['end_to_end']}
    cells = {c['name'] for c in b['workloads']}
    for metric in b['per_layer']:
        assert metric['moves'] in e2e_names
        assert metric['workloads'] and set(metric['workloads']) <= cells
        if metric['name'].endswith('_roofline'):
            assert metric['unit'] == '%'
    assert {c['config'] for c in b['workloads']} == \
        {c['name'] for c in b['configs']}


@pytest.mark.parametrize('workload', sorted(TINY))
def test_every_cell_resolves_to_its_files(workload):
    found = harness.resolve(workload)
    assert found['mix']['driver'] == 'bulk_merge'
    for fn in ('setup', 'warmup', 'window', 'audit'):
        assert callable(getattr(found['driver'], fn))
    reported = {e['name'] for e in found['end_to_end']}
    assert 'setup_s' in reported and len(reported) >= 2
    assert found['per_layer'], 'a cell reports at least one per-layer metric'
    for entry in found['per_layer']:
        assert entry['moves'] in reported
        assert callable(found['readers'][entry['name']].read)
    config = found['config']
    assert config['source'] == next(
        c['source'] for c in found['bench']['configs']
        if c['name'] == found['cell']['config'])
    assert config['guarantees'] and 'assumed' in config


def write_bench(root, edit):
    b = bench()
    edit(b)
    with open(os.path.join(root, 'BENCHMARK.json'), 'w') as handle:
        json.dump(b, handle)


@pytest.fixture
def copy_root(tmp_path):
    """A second root: the benchmark's directory copied, the program
    reached through the real checkout."""
    shutil.copytree(BENCH_DIR, tmp_path / 'benchmarks',
                    ignore=shutil.ignore_patterns('__pycache__', 'tests'))
    return tmp_path


@pytest.mark.parametrize('what,edit,missing', [
    ('workload', lambda b: None, 'unknown workload'),
    ('traffic', lambda b: b['workloads'][0].update(traffic='nosuch'),
     'traffic mix nosuch'),
    ('config', lambda b: b['configs'][0].update(
        file='benchmarks/configs/nosuch.json'), 'config map-merge-10k'),
    ('metric', lambda b: b['per_layer'].append(
        {'name': 'nosuch.metric', 'unit': '1', 'better': 'lower',
         'source': 'program_counter', 'layer': 'seam',
         'moves': 'ingest_changes_per_s',
         'workloads': ['map-merge-10k.bulk']}),
     'per-layer metric nosuch.metric'),
])
def test_a_missing_file_is_a_clear_error(copy_root, what, edit, missing):
    write_bench(copy_root, edit)
    name = 'nosuch.cell' if what == 'workload' else 'map-merge-10k.bulk'
    with pytest.raises(harness.BenchError, match=missing):
        harness.resolve(name, str(copy_root),
                        str(copy_root / 'benchmarks'))


def test_a_new_cell_runs_from_files_it_alone_brought(copy_root):
    """A later PR's cell: one new configuration file, one new mix file,
    three new entries, no edit to a file that was there."""
    bdir = copy_root / 'benchmarks'
    config = json.loads((bdir / 'configs' / 'map-merge-10k.json').read_text())
    config.update(name='map-merge-tiny', **TINY['map-merge-10k.bulk'])
    (bdir / 'configs' / 'map-merge-tiny.json').write_text(json.dumps(config))
    mix = json.loads((bdir / 'traffic' / 'bulk.json').read_text())
    mix.update(warmup_steps=1, audit_saves=8)
    (bdir / 'traffic' / 'bulk-once.json').write_text(json.dumps(mix))

    def edit(b):
        b['configs'].append({
            'name': 'map-merge-tiny', 'source': config['source'],
            'file': 'benchmarks/configs/map-merge-tiny.json',
            'reduced': [], 'why': 'test'})
        b['workloads'].append({
            'name': 'map-merge-tiny.bulk-once', 'config': 'map-merge-tiny',
            'traffic': 'bulk-once', 'chips': 1, 'why': 'test'})
        for metric in b['end_to_end'] + b['per_layer']:
            if 'map-merge-10k.bulk' in metric.get('workloads', ()):
                metric['workloads'].append('map-merge-tiny.bulk-once')
    write_bench(copy_root, edit)
    result = harness.run_cell('map-merge-tiny.bulk-once', 3, 0.3, 0,
                              cpu=True, root=str(copy_root),
                              bench_dir=str(bdir))
    assert result['correct'] is True
    assert set(result['metrics']) == {'ingest_changes_per_s', 'setup_s'}


def test_a_device_that_is_not_in_the_table_of_peaks_is_an_error():
    assert harness.peaks_for('TPU v5 lite')['hbm_bytes_per_s'] == 819e9
    with pytest.raises(harness.BenchError, match='not in peaks.json'):
        harness.peaks_for('cpu')


# ---------------------------------------------------------------------------
# the result line
# ---------------------------------------------------------------------------

@pytest.mark.parametrize('workload,metrics', [
    ('map-merge-10k.bulk', {'ingest_changes_per_s', 'setup_s'}),
])
def test_a_tiny_run_is_correct_and_its_line_has_the_contracts_keys(
        workload, metrics):
    result = run_tiny(workload)
    keys = list(result)
    assert keys[:5] == list(harness.RESULT_KEYS)
    assert keys[-1] == 'compared' and set(keys[5:]) == {'compared'}
    assert result['correct'] is True
    assert set(result['metrics']) == metrics
    for value in result['metrics'].values():
        assert set(value) == {'value', 'unit'} and value['value'] > 0
    assert set(result['device']) == {'platform', 'kind', 'count',
                                     'memory_peak_bytes'}
    assert result['attempted'] > 0 and result['failed'] >= 0
    for number in result['compared'].values():
        assert number['value'] <= number['limit'] == 0
    json.dumps(result)


def test_run_py_refuses_to_run_without_a_tpu():
    import subprocess
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, 'run.py'), '--workload',
         'map-merge-10k.bulk', '--seed', '1', '--seconds', '1'],
        env=dict(os.environ, JAX_PLATFORMS='cpu'), cwd=ROOT,
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ''
    assert 'needs a TPU' in proc.stderr


# ---------------------------------------------------------------------------
# the reference and the controls: each fault makes `correct` false
# ---------------------------------------------------------------------------

def test_map_view_is_lamport_last_writer_wins():
    ops = [(1, 'aa', 'k', 10), (3, 'aa', 'k', 30), (3, 'bb', 'k', 31),
           (2, 'bb', 'j', 5)]
    assert map_view(ops) == {'k': 31, 'j': 5}
    assert map_view(reversed(ops)) == {'k': 31, 'j': 5}


@pytest.mark.parametrize('fault', faults.FAULTS)
@pytest.mark.parametrize('workload', sorted(TINY))
def test_a_fault_under_the_timed_path_reads_not_correct(workload, fault):
    """The rest of a run with the timed path broken underneath: a state
    returned unchanged, half of the batch left out, one answer altered
    where it is produced."""
    undo = faults.plant(fault)
    try:
        result = run_tiny(workload)
    finally:
        undo()
    assert result['correct'] is False
    failed = {name for name, n in result['compared'].items()
              if n['value'] > n['limit']}
    assert failed & {'view_mismatches', 'save_mismatches'}, failed


def test_the_control_fails_the_audit():
    """The control breaks one stated guarantee from the reference's side:
    the same run, but the reference misses one change that the system
    acknowledged and holds."""
    workload = 'map-merge-10k.bulk'
    found = harness.resolve(workload)
    driver = found['driver']
    config = dict(found['config'], **TINY[workload])
    state = driver.setup(config, found['mix'], 11)
    driver.warmup(state)
    driver.window(state, 0.3, harness.Tracer(False, 0))
    assert all(value <= limit
               for value, limit in driver.audit(state).values())
    state['views'] = [map_view(ops[:-1]) for _b, ops, _h in state['logs']]
    for _buffers, ops, _heads in state['logs']:
        ops.pop()
    compared = driver.audit(state)
    assert compared['save_mismatches'][0] == \
        min(found['mix']['audit_saves'], config['docs'])
    assert compared['view_mismatches'][0] > 0


def test_a_log_has_two_concurrent_heads_and_distinct_keys_per_actor():
    import numpy as np
    driver = harness.resolve('map-merge-10k.bulk')['driver']
    buffers, ops, heads = driver.make_log(np.random.default_rng(5), 40, 32)
    assert len(buffers) == len(ops) == 40 and len(set(heads)) == 2
    for actor in driver.ACTORS:
        mine = [op for op in ops if op[1] == actor]
        assert [op[0] for op in mine] == list(range(1, 21))
        assert len({op[2] for op in mine}) == 20
    both = {op[2] for op in ops if op[1] == driver.ACTORS[0]} & \
        {op[2] for op in ops if op[1] == driver.ACTORS[1]}
    assert both, 'no key that both actors set: nothing concurrent to settle'


def test_the_benchmarks_encoder_writes_what_the_programs_reads():
    """wire.set_change imports nothing of the program; the program's
    decoder reads its bytes back to the same change and hash."""
    from automerge_tpu.columnar import decode_change
    head = []
    for seq, value in enumerate((0, 1, -1, 63, 64, -65, 8192, 1 << 20), 1):
        data, digest = wire.set_change('ab' * 16, seq, seq + 3, head,
                                       f'k{seq}', value)
        change = decode_change(data)
        assert change['hash'] == digest and change['deps'] == head
        assert (change['actor'], change['seq'], change['startOp']) == \
            ('ab' * 16, seq, seq + 3)
        (op,) = change['ops']
        assert (op['action'], op['key'], op['value'], op['pred']) == \
            ('set', f'k{seq}', value, [])
        head = [digest]


def test_a_saved_document_is_read_back_without_the_program():
    """reference.saved_document_differs on a real save(): silent on the
    log as recorded, and it names a flipped byte, a missing op, a changed
    value and a wrong head."""
    found = harness.resolve('map-merge-10k.bulk')
    driver = found['driver']
    config = dict(found['config'], docs=2, sets_per_doc=600,
                  keys_per_doc=400)
    state = driver.setup(config, found['mix'], 3)
    driver.step(state)
    from automerge_tpu.fleet import backend as fleet_backend
    data = bytes(fleet_backend.save(state['last'][1][0]))
    _buffers, ops, heads = state['logs'][0]
    assert saved_document_differs(data, ops, heads) is None
    doc = wire.read_document(data)
    assert len(doc['ops']) == len(doc['changes']) == 600
    flipped = bytearray(data)
    flipped[len(data) // 2] ^= 1
    assert 'does not read back' in saved_document_differs(
        bytes(flipped), ops, heads)
    assert 'ops' in saved_document_differs(data, ops[:-1], heads)
    counter, actor, key, value = ops[0]
    assert 'ops' in saved_document_differs(
        data, [(counter, actor, key, value + 1)] + ops[1:], heads)
    assert 'heads' in saved_document_differs(data, ops, [heads[0], '0' * 64])


# ---------------------------------------------------------------------------
# the trace reducer, on the recorded v5e traces
# ---------------------------------------------------------------------------

@pytest.mark.skipif(not FIXTURES, reason='no recorded trace in traces/bench')
def test_trace_reducer_on_a_recorded_trace():
    reduced = trace_reduce.reduce_trace(FIXTURES[0])
    assert reduced['devices'] == 1
    idle = sum(seconds for _label, seconds in reduced['idle_gaps'])
    # busy + idle = the device's span, first op to last, read here again
    from jax.profiler import ProfileData
    (plane,) = [pl for pl in ProfileData.from_file(FIXTURES[0]).planes
                if pl.name.startswith(trace_reduce.DEVICE_PREFIX)]
    ops = trace_reduce.line_events(plane, 'XLA Ops')
    span = (max(e for _n, _s, e in ops) - min(s for _n, s, _e in ops)) / 1e9
    assert 0 < reduced['busy_s'] < span
    assert reduced['busy_s'] + idle == pytest.approx(span, rel=1e-9)
    assert any('apply_op_batch' in name for name in reduced['modules'])
    count, seconds = reduced['modules']['jit__apply_op_batch_impl']
    assert count >= 1 and seconds > 0
    assert sum(row[0] for row in reduced['modules'].values()) == 5
    names = [name for name, _s in reduced['top_ops']]
    assert '%fusion' in names and all(' = ' not in n for n in names)
    seconds = [s for _n, s in reduced['top_ops']]
    assert seconds == sorted(seconds, reverse=True)


def test_trace_reducer_merges_intervals_and_labels_gaps():
    assert trace_reduce.merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == \
        [[0, 3], [5, 8]]
    assert trace_reduce.short_name('jit_f(123)') == 'jit_f'
    assert trace_reduce.short_name('%a.1 = s32[2]{0} add(x, y)') == '%a.1'
    annotations = trace_reduce.annotation_arrays(
        [('pump', 0, 10), ('generate', 10, 14), ('tick', 0, 14)])
    assert trace_reduce.label_gap((2, 6), annotations) == 'pump'
    assert trace_reduce.label_gap((10, 13), annotations) == 'generate'
    assert trace_reduce.label_gap((20, 30), annotations) == 'unannotated'
    assert trace_reduce.label_gap((8, 13), annotations) == 'tick'


def test_roofline_bytes_come_from_shapes():
    from roofline import grid_merge_bytes, least_seconds
    moved = grid_merge_bytes(2000000, 1500000)
    assert moved == 2000000 * 15 + 3 * 1500000 * 4
    seconds, bound = least_seconds(moved, 0, harness.peaks_for('TPU v5 lite'))
    assert bound == 'memory' and seconds == pytest.approx(moved / 819e9)
