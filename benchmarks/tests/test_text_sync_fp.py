"""The `text-sync-fp` configuration's own tests, on the CPU at a tiny size:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_text_sync_fp.py -q

The reference of causal delivery (reference_causal.py) against hand-built
cases and against the program's own fixed-point loop on seeded deliveries;
the cell end to end, ``correct`` with every limit 0 and changes held back
and drained on the device path; each of the control's faults reads
``correct`` false; the share of changes the driver withholds is the
configuration's; the new metric readers read what they say and nothing from
a program without the counters.
"""

import hashlib
import json
import os
import random
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
import reference_causal                             # noqa: E402

CELL = 'text-sync-fp.resend'
# a share three times the configuration's, so that a window of a few
# steps over six documents holds changes back
TINY = {'docs': 6, 'history_ops_per_writer': 150, 'history_spread_ops': 60,
        'encode_for_seconds': 0.3, 'settle_seconds': 0.2,
        'step_floor_ms': 1.0, 'warmup_steps': 8,
        'withheld_share': 0.03}
LIMITS = {'docs_missing', 'text_mismatches', 'heads_mismatches',
          'save_mismatches', 'inexact_rows', 'offpath_calls', 'floor_waits',
          'held_text_mismatches', 'held_heads_mismatches',
          'pending_mismatches', 'missing_mismatches', 'undrained_docs',
          'applied_mismatch'}
NEW_METRICS = ('seam.gate_general_ms_per_step',
               'seam.heldback_changes_per_step',
               'seam.drained_changes_per_step',
               'seam.heldback_docs_per_step', 'seq.pad_share.resend',
               'device_idle_share.resend', 'xla.compiles_in_window.resend')


def driver_and_config(**more):
    found = harness.resolve(CELL)
    return found['driver'], {**found['config'], **TINY, **more}, \
        found['mix']


def run_tiny(seed=7, seconds=0.3):
    return harness.run_cell(CELL, seed, seconds, 0, cpu=True,
                            overrides=TINY)


# ---------------------------------------------------------------------------
# the reference of causal delivery
# ---------------------------------------------------------------------------

def test_the_reference_holds_back_and_drains_by_hand():
    ref = reference_causal.Causal(applied=['h'], heads=['h'])
    # a chain a1 <- a2 <- a3 and a sibling b1, all after h; a2 is withheld
    assert ref.deliver([('a1', ['h']), ('a3', ['a2']), ('b1', ['h'])]) == 2
    assert ref.order == ['a1', 'b1'] and ref.heads == {'a1', 'b1'}
    assert ref.queue == [('a3', ['a2'])] and ref.missing() == ['a2']
    # what follows both heads of the round waits behind the queue too
    assert ref.deliver([('c1', ['a3', 'b1'])]) == 0
    assert [c for c, _deps in ref.queue] == ['c1', 'a3']
    assert ref.missing() == ['a2']
    # a queued change delivered again, still waiting: it waits twice
    assert ref.deliver([('a3', ['a2'])]) == 0 and len(ref.queue) == 3
    # the withheld change arrives: everything drains, in a causal order
    assert ref.deliver([('a2', ['a1'])]) == 3
    assert ref.order == ['a1', 'b1', 'a2', 'a3', 'c1']
    assert ref.queue == [] and ref.missing() == []
    assert ref.heads == {'c1'}
    # delivered again when long applied: nothing
    assert ref.deliver([('a2', ['a1']), ('c1', ['a3', 'b1'])]) == 0
    assert len(ref.order) == 5 and ref.heads == {'c1'}


@pytest.mark.parametrize('seed', range(8))
def test_the_reference_agrees_with_the_programs_fixed_point(seed):
    """Seeded DAGs of two actors' changes, delivered in calls with changes
    withheld, swapped and repeated: applied order, queue, heads and
    missing dependencies call by call against hash_graph.HashGraph."""
    from automerge_tpu.backend.hash_graph import HashGraph
    rng = random.Random(seed)
    log, heads, seqs = [], [], {'a': 0, 'b': 0}
    tips = {'a': None, 'b': None}
    for n in range(60):
        actor = rng.choice('ab')
        deps = sorted(heads) if rng.random() < 0.3 or tips[actor] is None \
            else [tips[actor]]
        seqs[actor] += 1
        digest = hashlib.sha256(f'{seed}-{n}'.encode()).hexdigest()
        if tips[actor] is not None and tips[actor] not in deps:
            deps = sorted(set(deps) | {tips[actor]})   # its own last one
        log.append({'hash': digest, 'deps': deps, 'actor': actor,
                    'seq': seqs[actor], 'startOp': n + 1, 'ops': [None]})
        heads = [h for h in heads if h not in deps] + [digest]
        tips[actor] = digest
    graph, ref = HashGraph(), reference_causal.Causal()
    pending, late = list(log), []
    while pending or late:
        call = late + pending[:rng.randrange(1, 9)]
        pending = pending[len(call) - len(late):]
        late = [c for c in call if rng.random() < 0.2]
        call = [c for c in call if c not in late]
        if len(call) > 1 and rng.random() < 0.5:
            i = rng.randrange(len(call) - 1)
            call[i], call[i + 1] = call[i + 1], call[i]
        if graph.queue and rng.random() < 0.3:
            call.append(rng.choice(graph.queue))
        applied, graph.queue = graph._drain_queue(
            [dict(c) for c in call], lambda change: None)
        for change in applied:
            graph.changes.append(b'')
            graph._record_applied(dict(change, buffer=b''))
        n = ref.deliver([(c['hash'], c['deps']) for c in call])
        assert n == len(applied)
        assert ref.order[len(ref.order) - n:] == [c['hash'] for c in applied]
        assert [c for c, _d in ref.queue] == [c['hash'] for c in graph.queue]
        assert sorted(ref.heads) == graph.heads
        assert ref.missing() == graph.get_missing_deps()
    assert len(ref.order) == len(log) and not ref.queue


# ---------------------------------------------------------------------------
# the cell, end to end
# ---------------------------------------------------------------------------

def test_the_cell_runs_and_reads_correct():
    result = run_tiny(seed=(1 << 31) + 29)
    assert result['correct'] is True and result['failed'] == 0
    assert result['attempted'] > 0
    assert set(result['compared']) == LIMITS
    assert all(entry == {'value': 0, 'limit': 0}
               for entry in result['compared'].values())
    assert set(result['metrics']) == {'ingest_changes_per_s', 'setup_s'}


def test_changes_are_held_back_and_drained_on_the_device_path():
    driver, config, mix = driver_and_config()
    state = driver.setup(config, mix, 3)
    driver.warmup(state)
    assert all(state['has_held'])
    metrics = state['fleet'].metrics
    assert metrics.fallbacks == metrics.exact_calls == 0
    assert metrics.mirror_rebuilds == metrics.promotions == 0
    out = driver.window(state, 0.3, harness.Tracer(False, 0))
    counters = out['facts']['fleet_counters']
    steps = out['facts']['steps']
    assert counters['fallbacks'] == counters['exact_calls'] == 0
    assert counters['mirror_rebuilds'] == counters['promotions'] == 0
    assert counters['seq_inexact_reads'] == 0
    assert counters['turbo_calls'] == steps
    assert counters['heldback_changes'] > 0
    assert counters['drained_changes'] > 0
    assert 0 < counters['turbo_commit_fallback_docs'] < steps * 6
    # the plan's books are the program's
    assert state['window_applied'] == counters['changes_ingested']
    assert out['attempted'] == state['window_applied']
    # something still waits when the window closes, and the audit sees it
    compared = driver.audit(state)
    assert all(value == 0 for value, _limit in compared.values()), compared
    assert all(not handle['state'].queue for handle in state['handles'])


def test_the_probe_ends_the_run_on_a_program_that_falls_back(monkeypatch):
    """With the turbo path refusing a queue as the parent's did, the probe's
    second call leaves the device path: the run ends there, one document
    touched."""
    from automerge_tpu.fleet import backend as fleet_backend
    real = fleet_backend._apply_changes_turbo

    def as_the_parent(handles, per_doc, parsed=None):
        if any(h['state'].queue for h in handles):
            return None
        return real(handles, per_doc, parsed)
    monkeypatch.setattr(fleet_backend, '_apply_changes_turbo', as_the_parent)
    driver, config, mix = driver_and_config()
    state = driver.setup(config, mix, 5)
    with pytest.raises(harness.BenchError, match='left the device path'):
        driver.warmup(state)
    assert state['fleet'].metrics.fallbacks == 1
    assert sum(state['applied']) == 0


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
        # the dropped change is a dependency nobody sends again: the
        # program queues what it frees and says which hash it misses
        assert over & {'missing_mismatches', 'pending_mismatches',
                       'undrained_docs'}, over
    else:
        assert {'held_text_mismatches', 'text_mismatches',
                'applied_mismatch'} <= over, over


# ---------------------------------------------------------------------------
# the delivery is the configuration's
# ---------------------------------------------------------------------------

def test_the_driver_withholds_the_configured_share():
    """100,000 changes and more through `plan` at the configuration's own
    share: between 0.9 and 1.1 % are withheld, each resent exactly once,
    at the head of the next send."""
    found = harness.resolve(CELL)
    config = dict(found['config'])
    assert config['withheld_share'] == 0.01
    assert (config['bloom_bits_per_entry'], config['bloom_probes'],
            config['resend_after_steps']) == (10, 7, 1)
    driver = found['driver']
    n_docs, n_rounds = 4, 800
    rng = np.random.default_rng(11)
    ks = [[(int(a), int(b)) for a, b in rng.integers(1, 65, (n_rounds, 2))]
          for _ in range(n_docs)]

    class Fork:
        def __init__(self, k):
            self.k = [None] + k
    state = {
        'share': config['withheld_share'],
        'draws': [np.random.default_rng([11, 4, d]) for d in range(n_docs)],
        'forks': [Fork(k) for k in ks], 'starts': [0] * n_docs,
        'queue': [[([(d, i, j) for j in range(a + b)], None, i % 2, b'')
                   for i, (a, b) in enumerate(k)] for d, k in enumerate(ks)],
        'planned': [0] * n_docs, 'late': [[] for _ in range(n_docs)],
        'withheld': [{} for _ in range(n_docs)],
        'applies': [[] for _ in range(n_docs)], 'carry': [0] * n_docs,
        'has_held': [False] * n_docs}
    total = withheld = 0
    for d in range(n_docs):
        driver.plan(state, d, n_rounds)
        sent = [c for send, *_ in state['queue'][d] for c in send]
        total += sum(a + b for a, b in ks[d])
        withheld += sum(map(len, state['withheld'][d].values()))
        # everything is sent once, but what the last round withheld
        assert len(sent) == len(set(sent))
        assert len(sent) + len(state['late'][d]) == \
            sum(a + b for a, b in ks[d])
        for i, held in state['withheld'][d].items():
            if i + 1 < n_rounds:
                assert state['queue'][d][i + 1][0][:len(held)] == \
                    [c for _j, c in held]
        # the books: every change is applied in its own step or the next
        assert sum(state['applies'][d]) + state['carry'][d] == \
            sum(a + b for a, b in ks[d])
    assert total > 100_000
    assert 0.009 < withheld / total < 0.011, withheld / total


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
    assert 'seam.commit_staged_ms_per_step' in names
    rounds = {e['name'] for e in bench['per_layer']
              if 'text-concurrent.rounds' in e.get('workloads', ())
              and not e['name'].endswith('.rounds')}
    assert rounds <= names
    ctx = {'facts': {'steps': 4, 'window_ns': (1000, 9000),
                     'fleet_counters': {'heldback_changes': 40,
                                        'drained_changes': 36,
                                        'heldback_docs': 10,
                                        'seq_ops': 25, 'seq_op_cells': 100}},
           'spans': [{'name': 'gate.general', 't0_ns': 2000, 't1_ns': 6000,
                      'dur_ns': 4000},
                     {'name': 'gate.general', 't0_ns': 500, 't1_ns': 1500,
                      'dur_ns': 1000}],
           'compiles': {'compilations': 0}, 'trace_window_s': 2.0,
           'trace': {'busy_s': 0.5}}
    readers = found['readers']
    assert readers['seam.gate_general_ms_per_step'].read(ctx) == 0.001
    assert readers['seam.heldback_changes_per_step'].read(ctx) == 10
    assert readers['seam.drained_changes_per_step'].read(ctx) == 9
    assert readers['seam.heldback_docs_per_step'].read(ctx) == 2.5
    assert readers['seq.pad_share.resend'].read(ctx) == 75.0
    assert readers['device_idle_share.resend'].read(ctx) == 75.0
    assert readers['xla.compiles_in_window.resend'].read(ctx) == 0
    # a program without the spans and counters (the parent): nothing read
    bare = {'facts': {'steps': 4, 'window_ns': (1000, 9000),
                      'fleet_counters': {'fallbacks': 0}}, 'spans': [],
            'compiles': {'compilations': 0}, 'trace_window_s': None,
            'trace': {'busy_s': 0.0}}
    for name in NEW_METRICS[:6]:
        assert readers[name].read(bare) is None, name
