"""Driver ``bulk_merge``: a store catching N documents up from their change
logs. One step = a new DocFleet, ``init_docs``, ONE
``apply_changes_docs(mirror=False)`` over every document's whole log,
``block_until_ready`` on the fleet state; the step before's fleet is let go
when the next one stands. Steps run back to back; the window closes at the
first step boundary at or after ``--seconds`` and every step in it counts,
over the seconds it really took. Python's collector is left as it is.

A document's log is BASELINE.json's config 1: two actors that never see
each other, each setting ``sets_per_doc / 2`` distinct keys of the
document's ``keys_per_doc`` in one-op changes that follow only its own last
one, so the document has two concurrent heads and every key both actors
chose holds a conflict that the Lamport order settles. The changes are
written by the benchmark's own encoder (wire.py), not the program's.

The step loop is copied from chip_smoke.py ``leg_seam`` (bench.py ``seam``
section), bounded by time instead of by count.
"""

import statistics
import sys
import time

import numpy as np

from reference import map_view, saved_document_differs
from wire import set_change

ACTORS = ('aa' * 16, 'bb' * 16)


def make_log(rng, n_sets, n_keys):
    """One document's log: (change buffers in a causal order, the ops as
    [(counter, actor, key, value)], the two heads)."""
    per_actor = n_sets // len(ACTORS)
    chains = []
    for actor in ACTORS:
        keys = rng.choice(n_keys, size=per_actor, replace=False)
        values = rng.integers(1, 1 << 20, size=per_actor)
        chain, head = [], []
        for i, (key, value) in enumerate(zip(keys.tolist(),
                                             values.tolist()), 1):
            buf, digest = set_change(actor, i, i, head, f'k{key}', value)
            head = [digest]
            chain.append((buf, (i, actor, f'k{key}', value)))
        chains.append((chain, head[0]))
    # a seeded merge of the two chains: each actor's own order kept
    turns = rng.permutation(np.repeat(np.arange(len(ACTORS)), per_actor))
    at = [0] * len(ACTORS)
    buffers, ops = [], []
    for a in turns.tolist():
        buf, op = chains[a][0][at[a]]
        at[a] += 1
        buffers.append(buf)
        ops.append(op)
    return buffers, ops, sorted(head for _chain, head in chains)


def setup(config, mix, seed):
    rng = np.random.default_rng(seed)
    n_docs = config['docs']
    # every document has a log of its own: no two share a change
    logs = [make_log(rng, config['sets_per_doc'], config['keys_per_doc'])
            for _ in range(n_docs)]
    views = [map_view(ops) for _buffers, ops, _heads in logs]
    return {
        'config': config, 'mix': mix, 'rng': rng, 'logs': logs,
        'n_docs': n_docs, 'views': views,
        'per_doc': [buffers for buffers, _ops, _heads in logs],
        'changes_per_step': n_docs * config['sets_per_doc'],
        # cells of the grids that a step's ops fill: the state a step
        # leaves on the device, padding not counted
        'cells_per_step': sum(len(view) for view in views),
        'last': None,
    }


def step(state):
    import jax
    from jax.profiler import TraceAnnotation
    from automerge_tpu.fleet.backend import (DocFleet, apply_changes_docs,
                                             init_docs)
    config = state['config']
    with TraceAnnotation('init_docs'):
        fleet = DocFleet(doc_capacity=state['n_docs'],
                         key_capacity=config['keys_per_doc'] + 1)
        handles = init_docs(state['n_docs'], fleet)
    with TraceAnnotation('apply_changes_docs'):
        handles, _ = apply_changes_docs(handles, state['per_doc'],
                                        mirror=False)
    with TraceAnnotation('block'):
        jax.block_until_ready(fleet.state)
    state['last'] = (fleet, handles)
    return fleet


def warmup(state):
    for _ in range(int(state['mix'].get('warmup_steps', 2))):
        step(state)


def window(state, seconds, tracer):
    counters = {'fallbacks': 0, 'promotions': 0,
                'turbo_commit_fallback_docs': 0}
    steps = 0
    ends = []
    start_ns = time.perf_counter_ns()
    start = time.perf_counter()
    while True:
        tracer.poll()
        fleet = step(state)
        steps += 1
        now = time.perf_counter()
        ends.append(now)
        for name in counters:
            counters[name] += getattr(fleet.metrics, name)
        if now - start >= seconds:
            break
    elapsed = now - start
    took = [b - a for a, b in zip([start] + ends, ends)]
    print(f'# bulk window: {steps} steps, median step '
          f'{statistics.median(took) * 1e3:.2f} ms, fastest '
          f'{min(took) * 1e3:.2f} ms, slowest {max(took) * 1e3:.2f} ms',
          file=sys.stderr, flush=True)
    changes = steps * state['changes_per_step']
    return {
        'attempted': changes, 'failed': 0,
        'metrics': {'ingest_changes_per_s': changes / elapsed},
        'facts': {'steps': steps, 'elapsed_s': elapsed,
                  'window_ns': (start_ns, time.perf_counter_ns()),
                  'fleet_counters': counters,
                  'ops_per_step': state['changes_per_step'],
                  'cells_per_step': state['cells_per_step']},
    }


def audit(state):
    """The last timed step's fleet against the reference: every
    document's ``materialize_docs`` view against ``map_view`` of the ops
    the generator recorded, and ``save()`` of a seeded sample read back by
    the benchmark's own reader (wire.py) against the log as recorded."""
    from automerge_tpu.fleet import backend as fleet_backend
    fleet, handles = state['last']
    views = fleet_backend.materialize_docs(handles)
    view_mismatches = sum(1 for view, want in zip(views, state['views'])
                          if view != want)
    sample = state['rng'].choice(
        state['n_docs'], size=min(int(state['mix']['audit_saves']),
                                  state['n_docs']), replace=False).tolist()
    save_mismatches = 0
    for d in sample:
        _buffers, ops, heads = state['logs'][d]
        why = saved_document_differs(
            bytes(fleet_backend.save(handles[d])), ops, heads)
        if why:
            save_mismatches += 1
            print(f'# save of document {d}: {why}', file=sys.stderr)
    return {
        'docs_missing': (state['n_docs'] - len(views), 0),
        'view_mismatches': (view_mismatches, 0),
        'save_mismatches': (save_mismatches, 0),
    }
