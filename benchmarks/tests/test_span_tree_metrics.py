"""The readers of PR 39 on hand-built contexts: the split of a call at its
enqueue, the off-CPU time, the program's `gc` spans, the counters; and
every new entry of BENCHMARK.json has its file and names cells that exist.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""

import json
import os
import sys

import pytest

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
for _path in (ROOT, BENCH_DIR):
    if _path not in sys.path:
        sys.path.insert(0, _path)

import harness                                      # noqa: E402

NEW = {
    'seam.call_ms_per_step', 'seam.pre_enqueue_ms_per_step',
    'seam.post_enqueue_ms_per_step', 'seam.setup_ms_per_step',
    'seam.setup_buffers_ms_per_step', 'seam.stage_ms_per_step',
    'seam.stage_actors_ms_per_step', 'seam.stage_values_ms_per_step',
    'seam.stage_root_ms_per_step', 'seam.stage_grid_ms_per_step',
    'seam.stage_seq_rows_ms_per_step', 'seq.place_ms_per_step',
    'seq.enqueue_ms_per_step', 'seam.offcpu_ms_per_step',
    'host.gc_ms_per_step', 'seam.history_probes_per_step',
    'trace.spans_dropped',
}
MS = 1_000_000


def reader(name):
    return harness.load_module(
        os.path.join(BENCH_DIR, 'metrics', name + '.py'), name)


def span(sid, name, t0_ms, t1_ms, parent=None, root=None, cpu_ms=None):
    out = {'id': sid, 'name': name, 't0_ns': int(t0_ms * MS),
           't1_ns': int(t1_ms * MS), 'dur_ns': int((t1_ms - t0_ms) * MS),
           'parent': parent, 'root': sid if root is None else root,
           'tid': 1}
    if cpu_ms is not None or name != 'parse_chunk':
        out['thread_cpu_ns'] = None if cpu_ms is None else int(cpu_ms * MS)
    return out


def context(spans, steps=2, window=(100, 200), counters=None):
    return {'spans': spans,
            'facts': {'steps': steps, 'fleet_counters': counters or {},
                      'window_ns': (window[0] * MS, window[1] * MS)}}


def a_window():
    """Three whole calls and one that straddles the window's end. Call 1
    (100-120) enqueues once, 110-112; call 2 (130-160) twice, 140-142 and
    150-153 (two size classes); call 3 (170-175) not at all (everything
    queued); call 4 (195-205) sticks out of the window."""
    return [
        span(1, 'apply_batch', 100, 120, cpu_ms=17),
        span(2, 'turbo_setup', 100, 102, 1, 1, cpu_ms=2),
        span(3, 'setup.buffers', 101, 102, 2, 1, cpu_ms=1),
        span(4, 'turbo_stage', 104, 113, 1, 1, cpu_ms=8),
        span(5, 'stage.actors', 104, 105, 4, 1, cpu_ms=1),
        span(6, 'stage.seq_rows', 105, 107, 4, 1, cpu_ms=2),
        span(7, 'seq.place', 108, 110, 4, 1, cpu_ms=2),
        span(8, 'seq.enqueue', 110, 112, 4, 1, cpu_ms=1.5),
        span(9, 'gc', 113, 119, 1, 1, cpu_ms=6),
        span(10, 'apply_batch', 130, 160, cpu_ms=21),
        span(11, 'turbo_gate', 131, 135, 10, 10, cpu_ms=3),
        span(12, 'turbo_stage', 136, 154, 10, 10, cpu_ms=10),
        span(13, 'seq.enqueue', 140, 142, 12, 10, cpu_ms=2),
        span(14, 'dispatch.enqueue', 150, 153, 12, 10, cpu_ms=3),
        # a slice timed on a pool worker: no CPU clock of ours
        span(15, 'parse_chunk', 132, 133, 11, 10),
        span(16, 'apply_batch', 170, 175, cpu_ms=5),
        span(17, 'turbo_commit', 171, 173, 16, 16, cpu_ms=2),
        span(18, 'apply_batch', 195, 205, cpu_ms=1),
        span(19, 'seq.enqueue', 196, 197, 18, 18, cpu_ms=1),
        span(20, 'turbo_stage', 195.5, 199, 18, 18, cpu_ms=1),
        # an enqueue before the window, its call too
        span(21, 'apply_batch', 80, 99),
        span(22, 'seq.enqueue', 90, 91, 21, 21, cpu_ms=1),
    ]


@pytest.mark.parametrize('name,want', [
    # the three whole calls: 20 + 30 + 5 ms over two steps
    ('seam.call_ms_per_step', 27.5),
    # start to the first enqueue's start: 10 + 10; the call without an
    # enqueue has no split; the straddling call is left out
    ('seam.pre_enqueue_ms_per_step', 10.0),
    # the last enqueue's end to the call's end: 8 + 7
    ('seam.post_enqueue_ms_per_step', 7.5),
    ('seam.setup_ms_per_step', 1.0),
    ('seam.setup_buffers_ms_per_step', 0.5),
    # 9 + 18: the straddling call's stage lies inside the window (195.5 to
    # 199), and a span is judged by its own edges, as span_ms_per_step does
    ('seam.stage_ms_per_step', 15.25),
    ('seam.stage_actors_ms_per_step', 0.5),
    ('seam.stage_seq_rows_ms_per_step', 1.0),
    ('seq.place_ms_per_step', 1.0),
    # 2 + 2 + 1 (196-197 lies inside the window)
    ('seq.enqueue_ms_per_step', 2.5),
    # wall less CPU of the three whole calls: (20-17) + (30-21) + (5-5)
    ('seam.offcpu_ms_per_step', 6.0),
    ('host.gc_ms_per_step', 3.0),
    # no such span in this ring
    ('seam.stage_values_ms_per_step', None),
    ('seam.stage_root_ms_per_step', None),
    ('seam.stage_grid_ms_per_step', None),
])
def test_a_reader_on_a_hand_built_window(name, want):
    assert reader(name).read(context(a_window())) == want


def test_the_split_and_the_enqueue_make_up_the_call():
    """pre + (first enqueue's start to last enqueue's end) + post is the
    call, for the calls that have an enqueue."""
    ctx = context([s for s in a_window() if s['root'] in (1, 10)])
    pre = reader('seam.pre_enqueue_ms_per_step').read(ctx)
    post = reader('seam.post_enqueue_ms_per_step').read(ctx)
    call = reader('seam.call_ms_per_step').read(ctx)
    between = ((112 - 110) + (153 - 140)) / 2
    assert pre + between + post == call == 25.0


@pytest.mark.parametrize('name', sorted(NEW - {'trace.spans_dropped'}))
def test_nothing_to_read_is_none(name):
    read = reader(name).read
    # no window, no steps (a driver that reports neither)
    assert read({'spans': a_window(), 'facts': {}}) is None
    # an empty ring, no counters: the parent's program, or spans off
    assert read(context([])) is None


def test_a_window_without_a_collection_reads_zero_not_none():
    spans = [s for s in a_window() if s['name'] != 'gc']
    assert reader('host.gc_ms_per_step').read(context(spans)) == 0.0


def test_a_call_without_an_enqueue_has_no_split():
    spans = [s for s in a_window() if s['root'] == 16]
    ctx = context(spans)
    assert reader('seam.call_ms_per_step').read(ctx) == 2.5
    assert reader('seam.pre_enqueue_ms_per_step').read(ctx) is None
    assert reader('seam.post_enqueue_ms_per_step').read(ctx) is None


def test_a_span_without_the_cpu_clock_is_skipped_not_zero():
    """A program from before PR 39 records no `thread_cpu_ns`, and a slice
    carries None: neither reads as a span that burnt no CPU."""
    read = reader('seam.offcpu_ms_per_step').read
    old = [{k: v for k, v in s.items() if k != 'thread_cpu_ns'}
           for s in a_window()]
    assert read(context(old)) is None
    mixed = [span(1, 'apply_batch', 110, 120, cpu_ms=None),
             span(2, 'apply_batch', 120, 130, cpu_ms=7)]
    assert read(context(mixed, steps=1)) == 3.0


def test_a_nested_apply_batch_is_no_call():
    """Only a ROOT `apply_batch` is a call (a served tick wraps its own)."""
    spans = [span(1, 'service_tick', 100, 150),
             span(2, 'apply_batch', 110, 140, parent=1, root=1),
             span(3, 'seq.enqueue', 120, 121, parent=2, root=1)]
    ctx = context(spans)
    assert reader('seam.call_ms_per_step').read(ctx) is None
    assert reader('seam.pre_enqueue_ms_per_step').read(ctx) is None


def test_the_counter_readers():
    ctx = context([], steps=4, counters={'history_probes': 210})
    assert reader('seam.history_probes_per_step').read(ctx) == 52.5
    ctx = context([], steps=4, counters={'heldback_changes': 1})
    assert reader('seam.history_probes_per_step').read(ctx) is None


def test_spans_dropped_is_the_rings_own_count():
    from automerge_tpu.observability import spans
    spans.enable(capacity=4)
    try:
        for _ in range(6):
            with spans.span('x'):
                pass
        assert reader('trace.spans_dropped').read(context([])) == 2
        spans.enable(capacity=4)
        assert reader('trace.spans_dropped').read(context([])) == 0
    finally:
        spans.disable()


def test_every_new_entry_has_its_file_and_lists_cells_that_exist():
    with open(os.path.join(ROOT, 'BENCHMARK.json')) as handle:
        bench = json.load(handle)
    cells = {cell['name'] for cell in bench['workloads']}
    entries = {e['name']: e for e in bench['per_layer']}
    assert NEW <= set(entries)
    # appended behind what was there, in the issue's order of layers
    assert {e['name'] for e in bench['per_layer'][-len(NEW):]} == NEW
    for name in NEW:
        entry = entries[name]
        assert callable(reader(name).read), name
        assert entry['workloads'] and set(entry['workloads']) <= cells, name
        assert entry['moves'] == 'ingest_changes_per_s'
        assert entry['source'] in ('program_span', 'program_counter')
        assert entry['layer'] in ('seam', 'seam: sequence staging',
                                  'host runtime')
    # a metric file without an entry would never be read
    files = {f[:-3] for f in os.listdir(os.path.join(BENCH_DIR, 'metrics'))
             if f.endswith('.py')}
    assert files == set(entries)
