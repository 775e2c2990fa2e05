"""The text-trace-x100 configuration's own tests, on the CPU at a tiny size:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_text_trace_x100.py -q

The reference computed a pass at a time equals the plain Rga over the whole
history; the cell runs and reads ``correct``, with a short history put past
the packed window by ``op_base`` as well as under it; the control's faults
read it false; the probe ends a program that leaves a row past the window
off the device, before the big documents are written; the benchmark's
column reader reads back what its writer writes; the per-layer readers
read the counters and spans they name, and nothing from a program that has
none.
"""

import os
import sys
import time

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
from roofline_seq import OP_COLUMN_BYTES            # noqa: E402
from roofline_seq_rows import seq_row_read_bytes    # noqa: E402

CELL = 'text-trace-x100.long_replay'
WINDOW = 1 << 23
TINY = {'trace_ops': 2000, 'start_pass': 3, 'start_offset_ops': [300, 400],
        'prefix_change_ops': 64, 'encode_for_seconds': 0.3,
        'warmup_steps': 2}


def driver_and_config(**over):
    found = harness.resolve(CELL)
    return found['driver'], dict(found['config'], **dict(TINY, **over)), \
        found['mix']


def run_tiny(seed=7, seconds=0.3, **over):
    return harness.run_cell(CELL, seed, seconds, 0, cpu=True,
                            overrides=dict(TINY, **over))


def small_passes(driver, config, ops=2000, seed=5):
    return driver.Passes(driver.Trace(
        np.random.default_rng(seed), config['insert_share'],
        config['typing_run_mean'], config['backspace_share']), ops)


@pytest.mark.parametrize('n', [3 * 2000, 2 * 2000 + 1234])
def test_the_pass_shifted_reference_is_the_plain_rga(n):
    """Three passes of 2,000 ops (and two and a part): the reference a
    pass at a time, and the writer's own order, are the plain Rga over the
    whole history, element for element, deletes too."""
    driver, config, _mix = driver_and_config()
    passes = small_passes(driver, config)
    is_insert, ref = passes.keystrokes(1, n + 1)
    rga = reference_text.Rga()
    for t, (insert, r) in enumerate(zip(is_insert.tolist(), ref.tolist()),
                                    1):
        if insert:
            rga.insert(t, r or None, str(t))
        else:
            rga.delete(t, r)
    plain = rga.elements()
    for order_of in (driver.reference_orders(passes),
                     driver.writer_order(passes)):
        elems, refs, gone = driver.history_elements(passes, n, order_of)
        assert elems.tolist() == [e for e, _c, _d in plain]
        assert gone.tolist() == [d[0] if d else 0 for _e, _c, d in plain]
        assert refs.tolist() == ref[elems - 1].tolist()


@pytest.mark.parametrize('op_base', [0, WINDOW])
def test_the_cell_runs_and_reads_correct(op_base):
    result = run_tiny(seed=(1 << 31) + 29, op_base=op_base)
    assert result['correct'] is True and result['failed'] == 0
    assert result['attempted'] > 0
    assert set(result['compared']) == {
        'docs_missing', 'text_mismatches', 'save_mismatches',
        'inexact_rows', 'host_docs', 'floor_waits'}
    assert all(entry == {'value': 0, 'limit': 0}
               for entry in result['compared'].values())
    assert set(result['metrics']) == {'ingest_changes_per_s', 'setup_s'}


def test_past_the_window_the_rows_are_wide_and_nothing_compiles_in_the_window():
    # (about 4,400 elements a row: the window's keystrokes stay inside the
    # 8,192-slot class, as the cell's stay inside 2^25)
    driver, config, mix = driver_and_config(op_base=WINDOW, start_pass=4)
    state = driver.setup(config, mix, 11)
    driver.warmup(state)
    counter = harness.CompileCounter()
    counter.install()
    out = driver.window(state, 0.3, harness.Tracer(False, 0))
    assert counter.snapshot()['compilations'] == 0
    fleet = state['fleet']
    assert out['facts']['seq_wide_rows'] == state['n_docs'] == 2
    assert [layout['bits'] for layout in fleet.seq_wide
            if layout is not None] == [0, 0]
    counters = out['facts']['fleet_counters']
    assert counters['seq_repacks'] == 0 and counters['fallbacks'] == 0
    assert all(v == (0, 0) for v in driver.audit(state).values())


@pytest.mark.parametrize('fault', faults.FAULTS)
def test_a_fault_under_the_timed_path_reads_not_correct(fault):
    undo = faults.plant(fault)
    try:
        result = run_tiny(op_base=WINDOW)
    finally:
        undo()
    assert result['correct'] is False
    failed = {name for name, n in result['compared'].items()
              if n['value'] > n['limit']}
    assert failed & {'text_mismatches', 'save_mismatches'}, failed


@pytest.mark.parametrize('how', ['load', 'apply'])
def test_the_probe_ends_a_program_that_leaves_the_row_off_the_device(
        monkeypatch, how):
    """A program whose loader leaves a Text past the window to the host
    engine, or whose batched apply falls back for it, ends at the probe
    (the harness's exit code 2): one small document loaded, none of the
    cell's written."""
    from automerge_tpu import native
    from automerge_tpu.fleet import backend as fleet_backend
    from automerge_tpu.fleet import loader
    loads = []
    real = loader.load_docs

    def counted(buffers, fleet=None):
        loads.append(len(bytes(buffers[0])))
        if how == 'load':
            return [fleet_backend.load(bytes(b), fleet) for b in buffers]
        return real(buffers, fleet)

    monkeypatch.setattr(loader, 'load_docs', counted)
    if how == 'apply':
        monkeypatch.setattr(native, 'ingest_changes',
                            lambda *args, **kwargs: None)
    t0 = time.perf_counter()
    with pytest.raises(harness.BenchError, match='probe'):
        run_tiny()
    assert time.perf_counter() - t0 < 60
    assert len(loads) == 1 and loads[0] < 4096


def test_the_column_reader_reads_back_what_the_writer_writes():
    driver, _config, _mix = driver_and_config()
    rng = np.random.default_rng(3)
    n = 500
    elems = 10 + np.arange(n) * 3
    refs = np.r_[0, elems[:-1]]
    refs[[7, 300]] = 0
    gone = np.where(rng.random(n) < 0.3, elems + 1, 0)
    chars = bytes(rng.integers(97, 123, size=n).astype(np.uint8))
    head = 'ab' * 32
    data = wire_text.text_document('c4' * 16, head, [1, 5, int(gone.max())],
                                   elems, refs, chars, gone)
    doc = driver.read_saved(data)
    assert doc['actors'] == ['c4' * 16] and doc['heads'] == [head]
    assert doc['changes']['max_op'].tolist() == [1, 5, int(gone.max())]
    assert doc['changes']['deps_index'].tolist() == [0, 1]
    ops = doc['ops']
    assert ops['id_ctr'].tolist() == elems.tolist()
    assert ops['key_ctr'].tolist() == refs.tolist()
    assert ops['key_actor'].tolist() == np.where(refs > 0, 0, -1).tolist()
    assert bytes(ops['chars'].astype(np.uint8)) == chars
    assert ops['succ_num'].tolist() == (gone > 0).astype(int).tolist()
    assert ops['succ_ctr'].tolist() == gone[gone > 0].tolist()
    broken = bytearray(data)
    broken[-3] ^= 0xff
    with pytest.raises(ValueError):
        driver.read_saved(bytes(broken))


def test_the_readers_read_their_counters_and_spans():
    found = harness.resolve(CELL)
    readers = found['readers']
    assert sorted(readers) == sorted([
        'device_idle_share.long_replay', 'xla.compiles_in_window.long_replay',
        'seq.lookup_nodes_per_step.long_replay',
        'seq.row_read_roofline.long_replay'])
    nodes = (1 << 25) + 3
    spans = [{'name': 'seq.enqueue', 't0_ns': 10, 't1_ns': 20,
              'attrs': {'cls': 19, 'rows': 2, 'ops': 128,
                        'lookup_nodes': 2 * nodes}}] * 4
    ctx = {'facts': {'steps': 4, 'window_ns': (0, 100),
                     'fleet_counters': {'seq_lookup_nodes': 8 * nodes},
                     'seq_nodes_by_cls': {19: nodes}},
           'spans': spans, 'trace_window_s': 2.0,
           'trace': {'busy_s': 1.5,
                     'modules': {'jit_apply_seq_batch_donated': (4, 0.2)}},
           'compiles': {'compilations': 0},
           'peaks': {'hbm_bytes_per_s': 819e9, 'bf16_flops_per_s': 197e12}}
    lookup = readers['seq.lookup_nodes_per_step.long_replay']
    assert lookup.read(ctx) == 2 * nodes
    del ctx['facts']['fleet_counters']['seq_lookup_nodes']
    assert lookup.read(ctx) == 2 * nodes          # from the spans
    moved = seq_row_read_bytes(2, nodes, 128)
    assert moved == 2 * 4 * nodes + 128 * OP_COLUMN_BYTES
    roof = readers['seq.row_read_roofline.long_replay'].read(ctx)
    assert roof == pytest.approx(100.0 * moved / 819e9 * 4 / 0.2)
    assert 0 < roof < 100
    assert readers['device_idle_share.long_replay'].read(ctx) == 25.0
    assert readers['xla.compiles_in_window.long_replay'].read(ctx) == 0
    # a program that keeps neither the counter nor the attribute
    bare = dict(ctx, spans=[{'name': 'seq.enqueue', 't0_ns': 10,
                             't1_ns': 20, 'attrs': {'cls': 19}}],
                facts=dict(ctx['facts'], fleet_counters={}))
    assert lookup.read(bare) is None
