#!/usr/bin/env python3
"""Chip smoke: the main path, once, on the attached TPU.

Runs four legs in ONE process (a chip belongs to one process at a time),
each through the entry points a user calls, at the size the repo calls
its headline, on data made from ``--seed``, and checks every leg against
the host OpSet oracle (``automerge_tpu.backend``) outside any timing:

  seam    init_docs + apply_changes_docs(mirror=False) on one DocFleet:
          10,000 docs x 1,000 keys x 20 changes, 256 distinct chains
  text    the same seam through the sequence engine: 64 docs, each a
          3-actor 10,000-op insert/delete trace (actors taking turns),
          plus 8 docs whose three actors edit concurrently
  sync    fleet/sync_driver.py generate/receive rounds to quiescence:
          10,000 docs x 2 peers, 8 changes of divergence per pair
  served  tools/loadgen.py run_leg('clean') -> DocService: 10,000
          sessions, 256 tenants, 20,000 requests, sync_fraction 0.25

It refuses to start unless JAX comes up on a TPU and the native codec
is loaded, and exits non-zero when any leg fails. It prints two stdout
lines, each one JSON object. The first is the report: {"report":
"chip_smoke", "legs": {...}, ...}. The per-leg seconds, dispatch counts,
compilation counts and peak bytes in it are OBSERVATIONS for planning,
not metrics: nothing is warmed or repeated the way a benchmark would, so
they are not to be quoted as rates. The last is the verdict, these keys
and no others, the device as JAX reports it:
{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}.

``--cpu-rehearsal`` runs the same legs at a tiny size on the CPU to
debug this script before chip time is spent; without it a non-TPU
backend is a failure. ``--legs`` runs a subset for debugging; a subset
never reports ok.
"""

import argparse
import importlib.metadata
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, 'tools'))

LEGS = ('seam', 'text', 'sync', 'served')

# sizes: BASELINE.json configs 1, 2 and 4, and tools/loadgen.py's clean
# leg at 10,000 sessions
FULL = {
    'seam': dict(docs=10000, keys=1000, changes=20, chains=256, audit=64),
    'text': dict(docs=64, concurrent_docs=8, ops=10000, actors=3,
                 ops_per_change=32),
    'sync': dict(docs=10000, shared=2, divergence=8, audit=64,
                 device_min=None),
    'served': dict(sessions=10000, tenants=256, requests=20000),
}
# device_min=0 puts the rehearsal's few hundred hashes on the device
# table, so the insert/probe kernels run there as they do at full size
REHEARSAL = {
    'seam': dict(docs=96, keys=16, changes=6, chains=8, audit=8),
    'text': dict(docs=4, concurrent_docs=2, ops=120, actors=3,
                 ops_per_change=8),
    'sync': dict(docs=24, shared=2, divergence=4, audit=4, device_min=0),
    'served': dict(sessions=48, tenants=6, requests=160),
}


class SmokeFailure(Exception):
    """A check of a leg did not hold."""


def check(cond, message):
    if not cond:
        raise SmokeFailure(message)


# ---------------------------------------------------------------------------
# observations: XLA compilations, kernel-family dispatches, device memory
# ---------------------------------------------------------------------------

class CompileCounter:
    """Counts XLA backend compilations and persistent-cache outcomes
    through jax.monitoring (the events jit itself emits)."""

    def __init__(self):
        self.compilations = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0

    def install(self):
        import jax.monitoring as monitoring
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_kw):
        # one per executable requested from the backend; on a persistent
        # cache hit the duration is the retrieval, not a compile
        if event == '/jax/core/compile/backend_compile_duration':
            self.compilations += 1
            self.compile_s += duration

    def _on_event(self, event, **_kw):
        if event == '/jax/compilation_cache/cache_hits':
            self.cache_hits += 1
        elif event == '/jax/compilation_cache/cache_misses':
            self.cache_misses += 1

    def snapshot(self):
        return {'compilations': self.compilations,
                'compile_s': self.compile_s,
                'cache_hits': self.cache_hits,
                'cache_misses': self.cache_misses}


def dispatch_snapshot():
    from automerge_tpu.fleet import bloom, hashindex
    from automerge_tpu.observability import perf
    out = {kind: row['dispatches']
           for kind, row in perf.kernel_snapshot().items()}
    out['bloom.dispatch_count'] = bloom.dispatch_count()
    out['hashindex.dispatch_count'] = hashindex.dispatch_count()
    return out


def delta(after, before):
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v - before.get(k, 0)}


def family_total(dispatches, *prefixes):
    return sum(n for kind, n in dispatches.items()
               if kind.startswith(prefixes))


def timed(fn):
    start = time.perf_counter()
    out = fn()
    return out, round(time.perf_counter() - start, 3)


# ---------------------------------------------------------------------------
# seeded data + the host oracle
# ---------------------------------------------------------------------------

def host_oracle(changes):
    """(save bytes, materialized view) of the host OpSet engine fed
    `changes` through the frontend (whose default backend is the host
    engine) — the reference every leg is held to."""
    import automerge_tpu as A
    doc, _ = A.apply_changes(A.init(), list(changes))
    view = {k: str(v) if isinstance(v, A.Text) else v
            for k, v in dict(doc).items()}
    return bytes(A.save(doc)), view


def map_chain(rng, n_changes, n_keys, actors, start_seq=None, deps=(),
              start_op=1, prefix='k'):
    """One causally-linear chain of single-op int `set` changes, the
    actor of each change drawn from `actors`. Returns (buffers, heads,
    next_op)."""
    from automerge_tpu.columnar import decode_change_meta, encode_change
    seqs = dict(start_seq or {})
    heads = list(deps)
    out = []
    for i in range(n_changes):
        actor = actors[int(rng.integers(0, len(actors)))]
        seqs[actor] = seqs.get(actor, 0) + 1
        buf = encode_change({
            'actor': actor, 'seq': seqs[actor], 'startOp': start_op + i,
            'time': 0, 'message': '', 'deps': heads,
            'ops': [{'action': 'set', 'obj': '_root',
                     'key': f'{prefix}{int(rng.integers(0, n_keys))}',
                     'value': int(rng.integers(1, 1 << 20)),
                     'datatype': 'int', 'pred': []}]})
        heads = [decode_change_meta(buf, True)['hash']]
        out.append(buf)
    return out, heads, start_op + n_changes


def text_trace(rng, n_ops, n_actors, ops_per_change, concurrent):
    """Binary changes of one Text document edited by `n_actors` actors:
    a makeText change, then rounds of insert (80%) / delete (20%) runs.
    Serialized (`concurrent=False`), the actors take turns and every
    change depends on exactly the one before it — the linear chains the
    turbo seam takes. Concurrent, every actor extends the SAME heads in
    each round, so the RGA has same-position inserts, equal counters
    under different actors and double deletes to resolve; such a batch
    is causally ordered in buffer order, so the seam's DAG gate keeps it
    on the turbo path."""
    from automerge_tpu.columnar import decode_change_meta, encode_change
    # every document has actor ids of its own, as every device has: shared
    # ids would hide what a fleet's actor table costs the sequence pools
    actors = [rng.bytes(16).hex() for _ in range(n_actors)]
    first = encode_change({
        'actor': actors[0], 'seq': 1, 'startOp': 1, 'time': 0,
        'message': '', 'deps': [],
        'ops': [{'action': 'makeText', 'obj': '_root', 'key': 't',
                 'pred': []}]})
    obj = f'1@{actors[0]}'
    changes = [first]
    heads = [decode_change_meta(first, True)['hash']]
    seqs = [1] + [0] * (n_actors - 1)
    max_op = 1
    alive = []                      # elemIds visible at the round's start
    left = n_ops
    turn = 0
    while left > 0:
        if concurrent:
            writers = list(range(n_actors))
        else:
            writers = [turn % n_actors]
            turn += 1
        width = min(ops_per_change, -(-left // len(writers)))
        round_heads, deleted, born = [], set(), []
        for a in writers:
            if left <= 0:
                break
            k = min(width, left)
            left -= k
            view = list(alive)      # this actor's visible elements
            ops, prev = [], None
            for i in range(k):
                if view and rng.random() < 0.2:
                    victim = view.pop(int(rng.integers(0, len(view))))
                    deleted.add(victim)
                    ops.append({'action': 'del', 'obj': obj,
                                'elemId': victim, 'pred': [victim]})
                    if victim == prev:
                        prev = None
                    continue
                if prev is not None and rng.random() < 0.5:
                    ref = prev      # a typing run
                elif view:
                    ref = view[int(rng.integers(0, len(view)))]
                else:
                    ref = '_head'
                me = f'{max_op + 1 + i}@{actors[a]}'
                ops.append({'action': 'set', 'obj': obj, 'elemId': ref,
                            'insert': True,
                            'value': chr(97 + int(rng.integers(0, 26))),
                            'pred': []})
                view.append(me)
                born.append(me)
                prev = me
            seqs[a] += 1
            buf = encode_change({
                'actor': actors[a], 'seq': seqs[a], 'startOp': max_op + 1,
                'time': 0, 'message': '', 'deps': sorted(heads),
                'ops': ops})
            round_heads.append(decode_change_meta(buf, True)['hash'])
            changes.append(buf)
        max_op += width
        heads = round_heads
        alive = [e for e in alive + born if e not in deleted]
    return changes


# ---------------------------------------------------------------------------
# legs
# ---------------------------------------------------------------------------

def leg_seam(size, seed):
    import jax
    import numpy as np
    from automerge_tpu.fleet import backend as fleet_backend
    from automerge_tpu.fleet.backend import (
        DocFleet, apply_changes_docs, init_docs, materialize_docs)
    rng = np.random.default_rng(seed)
    actors = ['aa' * 16, 'bb' * 16]
    chains = [map_chain(rng, size['changes'], size['keys'], actors)[0]
              for _ in range(size['chains'])]
    n_docs = size['docs']
    per_doc = [list(chains[d % len(chains)]) for d in range(n_docs)]
    submitted = n_docs * size['changes']

    def run():
        fleet = DocFleet(doc_capacity=n_docs, key_capacity=size['keys'] + 1)
        handles = init_docs(n_docs, fleet)
        handles, _ = apply_changes_docs(handles, per_doc, mirror=False)
        jax.block_until_ready(fleet.state.winners)
        return fleet, handles

    (fleet, handles), first_s = timed(run)
    first_metrics = fleet.metrics.snapshot()
    del fleet, handles
    (fleet, handles), warm_s = timed(run)
    metrics = fleet.metrics.snapshot()
    for name, m in (('first', first_metrics), ('warm', metrics)):
        check(m['turbo_calls'] >= 1, f'{name}: turbo_calls {m}')
        for zero in ('fallbacks', 'promotions',
                     'turbo_commit_fallback_docs'):
            check(m[zero] == 0, f'{name}: {zero}={m[zero]}')
        check(m['device_ops'] == submitted,
              f'{name}: device_ops {m["device_ops"]} != {submitted}')
    audit = rng.choice(n_docs, size=min(size['audit'], n_docs),
                       replace=False).tolist()
    views = materialize_docs([handles[d] for d in audit])
    for d, view in zip(audit, views):
        want_save, want_view = host_oracle(per_doc[d])
        check(bytes(fleet_backend.save(handles[d])) == want_save,
              f'doc {d}: save() bytes differ from the host oracle')
        check(view == want_view,
              f'doc {d}: materialize_docs differs from the host oracle')
    metrics = {k: v for k, v in metrics.items() if k != 'seconds' and v}
    return {'first_s': first_s, 'warm_s': warm_s, 'docs': n_docs,
            'changes': submitted, 'chains': len(chains),
            'audited_docs': len(audit), 'fleet_metrics': metrics}


def leg_text(size, seed):
    import jax
    import numpy as np
    from automerge_tpu.fleet import backend as fleet_backend
    from automerge_tpu.fleet.backend import (
        DocFleet, apply_changes_docs, init_docs, materialize_docs)
    rng = np.random.default_rng(seed + 1)

    def apply(per_doc):
        fleet = DocFleet(doc_capacity=len(per_doc), key_capacity=4)
        handles = init_docs(len(per_doc), fleet)
        handles, _ = apply_changes_docs(handles, per_doc, mirror=False)
        jax.block_until_ready(
            [p.nxt for p in fleet.seq_pools.pools.values()])
        return fleet, handles

    def audit(tag, fleet, handles, per_doc):
        """Every doc's device-rendered text and save() against the host."""
        m = fleet.metrics.snapshot()
        check(m['promotions'] == 0, f'{tag}: {m["promotions"]} promotions')
        views = materialize_docs(handles)
        fleet.flush()
        inexact = sum(1 for row, meta in enumerate(fleet.seq_rows)
                      if meta is not None and fleet.seq_row_inexact(row))
        check(inexact == 0,
              f'{tag}: {inexact} sequence rows flagged device-inexact: '
              f'their text was not rendered from the device')
        chars = 0
        for d, changes in enumerate(per_doc):
            want_save, want_view = host_oracle(changes)
            check(views[d] == want_view,
                  f'{tag} doc {d}: visible text differs from the host')
            check(bytes(fleet_backend.save(handles[d])) == want_save,
                  f'{tag} doc {d}: save() bytes differ from the host')
            chars += len(want_view['t'])
        return m, chars

    # serialized traces: the turbo seam (linear chains)
    per_doc = [text_trace(rng, size['ops'], size['actors'],
                          size['ops_per_change'], concurrent=False)
               for _ in range(size['docs'])]
    (fleet, handles), first_s = timed(lambda: apply(per_doc))
    del fleet, handles
    (fleet, handles), warm_s = timed(lambda: apply(per_doc))
    m, chars = audit('serialized', fleet, handles, per_doc)
    check(m['turbo_calls'] >= 1 and m['fallbacks'] == 0,
          f'serialized text left the turbo path: {m}')
    # concurrent traces: same-position inserts and actor tie-breaks on
    # the device RGA. Their batches are causally ordered in buffer order,
    # so the DAG gate keeps them on the turbo path: asserted
    conc = [text_trace(rng, size['ops'], size['actors'],
                       size['ops_per_change'], concurrent=True)
            for _ in range(size['concurrent_docs'])]
    (cfleet, chandles), conc_s = timed(lambda: apply(conc))
    cm, cchars = audit('concurrent', cfleet, chandles, conc)
    check(cm['turbo_calls'] >= 1 and cm['exact_calls'] == 0 and
          cm['fallbacks'] == 0,
          f'concurrent text left the turbo path: {cm}')
    return {'first_s': first_s, 'warm_s': warm_s, 'docs': len(per_doc),
            'ops_per_doc': size['ops'] + 1, 'visible_chars': chars,
            'device_ops': m['device_ops'], 'dispatches': m['dispatches'],
            'concurrent': {'docs': len(conc), 'first_s': conc_s,
                           'visible_chars': cchars,
                           'turbo_calls': cm['turbo_calls'],
                           'exact_calls': cm['exact_calls'],
                           'fallbacks': cm['fallbacks'],
                           'device_ops': cm['device_ops'],
                           'dispatches': cm['dispatches']}}


def leg_sync(size, seed):
    import numpy as np
    from automerge_tpu import backend as host_backend
    from automerge_tpu.fleet.backend import (
        DocFleet, apply_changes_docs, init_docs)
    from automerge_tpu.fleet.sync_driver import (
        generate_sync_messages_docs, receive_sync_messages_docs)
    rng = np.random.default_rng(seed + 2)
    n_docs = size['docs']
    half = size['divergence'] // 2
    actor_a, actor_b = 'aa' * 16, 'bb' * 16
    hist_a, hist_b = [], []
    for _ in range(n_docs):
        base, heads, op = map_chain(rng, size['shared'], 16, [actor_a])
        own_a, _, _ = map_chain(rng, half, 16, [actor_a],
                                start_seq={actor_a: size['shared']},
                                deps=heads, start_op=op, prefix='a')
        own_b, _, _ = map_chain(rng, size['divergence'] - half, 16,
                                [actor_b], deps=heads, start_op=op,
                                prefix='b')
        hist_a.append(base + own_a)
        hist_b.append(base + own_b)

    def run():
        sides = []
        for hist in (hist_a, hist_b):
            fleet = DocFleet(doc_capacity=n_docs, key_capacity=64)
            if size['device_min'] is not None:
                fleet.frontier_index(device_min=size['device_min'])
            docs, _ = apply_changes_docs(init_docs(n_docs, fleet), hist,
                                         mirror=False)
            sides.append([fleet, docs,
                          [host_backend.init_sync_state()
                           for _ in range(n_docs)]])
        a, b = sides
        rounds = 0
        for rounds in range(1, 17):
            quiet = True
            for src, dst in ((a, b), (b, a)):
                src[2], messages = generate_sync_messages_docs(src[1],
                                                               src[2])
                if any(m is not None for m in messages):
                    quiet = False
                    dst[1], dst[2], _ = receive_sync_messages_docs(
                        dst[1], dst[2], messages, mirror=False)
            if quiet:
                break
        return a, b, rounds

    (a, b, rounds), first_s = timed(run)
    del a, b
    (a, b, rounds), warm_s = timed(run)
    check(rounds < 16, 'sync did not reach quiescence in 16 rounds')
    for d in range(n_docs):
        heads = host_backend.get_heads(a[1][d])
        check(heads == host_backend.get_heads(b[1][d]),
              f'pair {d}: heads differ after sync')
        check(len(heads) == 2, f'pair {d}: {len(heads)} heads, expected 2')
    modes = [side[0].frontier_index(create=False).table.mode
             for side in (a, b)]
    check(modes == ['device', 'device'],
          f'frontier index stayed below device_min: modes {modes}')
    for side in (a, b):
        m = side[0].metrics
        check(m.promotions == 0, f'sync leg promoted {m.promotions} docs')
    # the merged documents against the host engine fed both histories
    for d in rng.choice(n_docs, size=min(size['audit'], n_docs),
                        replace=False).tolist():
        merged = hist_a[d] + hist_b[d][size['shared']:]
        want_save, _ = host_oracle(merged)
        for side in (a, b):
            check(bytes(host_backend.save(side[1][d])) == want_save,
                  f'pair {d}: merged save() differs from the host oracle')
    return {'first_s': first_s, 'warm_s': warm_s, 'pairs': n_docs,
            'rounds_to_quiescence': rounds,
            'index_keys': [len(side[0].frontier_index(create=False).table)
                           for side in (a, b)]}


def leg_served(size, seed):
    from loadgen import run_leg

    def run():
        return run_leg('clean', sessions=size['sessions'],
                       tenants=size['tenants'], requests=size['requests'],
                       sync_fraction=0.25, seed=seed)

    first, first_s = timed(run)
    report, warm_s = timed(run)
    for name, leg in (('first', first), ('warm', report)):
        conv = leg['convergence']
        audit = leg['slo_audit'] or {}
        check(leg['submitted'] == size['requests'] and
              leg['completed_ok'] == leg['submitted'],
              f"{name}: {leg['completed_ok']} ok of {leg['submitted']} "
              f"submitted, {size['requests']} offered")
        check(leg['untyped_escapes'] == 0,
              f"{name}: {leg['untyped_escapes']} untyped escapes")
        # admission may refuse a whale tenant's burst (typed, before
        # submit); any other rejection in a clean leg is a failure
        check(set(leg['rejections']) <= {'TenantThrottled'},
              f"{name}: rejections {leg['rejections']}")
        check(conv['edit_mismatches'] == 0,
              f"{name}: {conv['edit_mismatches']} edit docs differ from "
              f"the unloaded control fleet")
        check(conv['sync_converged'] == conv['sync_drained'],
              f"{name}: sync convergence {conv}")
        check(audit.get('mismatches') == [], f'{name}: slo_audit {audit}')
    return {'first_s': first_s, 'warm_s': warm_s,
            'requests': report['completed_ok'], 'ticks': report['ticks'],
            'rejections': report['rejections'],
            'convergence': report['convergence'],
            'slo_pairs_checked': report['slo_audit']['pairs_checked']}


# dispatches a leg must show: kernel-ledger kinds by prefix, and the
# modules' own counters by the names dispatch_snapshot gives them
REQUIRED_DISPATCHES = {
    'seam': [('apply_op_batch',)],
    'text': [('apply_seq_batch',)],
    'sync': [('bloom_',), ('hashindex_',), ('bloom.dispatch_count',),
             ('hashindex.dispatch_count',)],
    'served': [('apply_op_batch', 'apply_register_batch'), ('bloom_',)],
}


def run_leg(name, fn, counter):
    """Run one leg; a failure is recorded (and fails the run), never
    swallowed. Returns the leg's record."""
    import jax
    print(f'# leg {name} ...', file=sys.stderr, flush=True)
    d0, c0 = dispatch_snapshot(), counter.snapshot()
    start = time.perf_counter()
    try:
        record = fn()
        record['dispatches_by_kernel'] = delta(dispatch_snapshot(), d0)
        for prefixes in REQUIRED_DISPATCHES[name]:
            check(family_total(record['dispatches_by_kernel'], *prefixes),
                  f'no device dispatch of {"/".join(prefixes)}*')
        record['ok'] = True
    except Exception as exc:        # recorded; the run exits non-zero
        traceback.print_exc()
        record = {'ok': False, 'error': f'{type(exc).__name__}: {exc}',
                  'dispatches_by_kernel': delta(dispatch_snapshot(), d0)}
    comp = {k: v - c0[k] for k, v in counter.snapshot().items()}
    comp['compile_s'] = round(comp['compile_s'], 3)
    record.update(comp)
    record['leg_s'] = round(time.perf_counter() - start, 3)
    stats = jax.devices()[0].memory_stats() or {}
    record['peak_bytes_in_use'] = stats.get('peak_bytes_in_use')
    print(f'# leg {name}: {"ok" if record["ok"] else "FAILED"} '
          f'{json.dumps(record)}', file=sys.stderr, flush=True)
    return record


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    parser.add_argument('--seed', type=int, default=0)
    parser.add_argument('--cpu-rehearsal', action='store_true',
                        help='tiny sizes on the CPU, to debug this script')
    parser.add_argument('--legs', default=','.join(LEGS),
                        help='comma-separated subset (debugging; a subset '
                             'never reports ok)')
    args = parser.parse_args(argv)
    legs = [name for name in args.legs.split(',') if name]
    unknown = sorted(set(legs) - set(LEGS))
    if unknown:
        parser.error(f'unknown legs {unknown}; one of {LEGS}')

    from automerge_tpu import jaxenv, native
    from automerge_tpu.observability import perf
    cache_dir = jaxenv.configure_compile_cache()
    counter = CompileCounter()
    counter.install()
    # raises unless the backend comes up as tpu (or the rehearsal flag
    # asked for the CPU): nothing below runs on a fallback
    stamp = jaxenv.require_platform(cpu=args.cpu_rehearsal)
    import jax
    import jaxlib
    versions = {'jax': jax.__version__, 'jaxlib': jaxlib.__version__,
                'libtpu': importlib.metadata.version('libtpu')}
    print(f'# platform: {stamp["platform"]} device_kind: '
          f'{stamp["device_kind"]} n_devices: {stamp["n_devices"]} '
          f'versions: {versions}', file=sys.stderr)
    print(f'# compile cache: {cache_dir}', file=sys.stderr)
    if not native.available():
        print(f'chip_smoke: native codec unavailable: '
              f'{native._load_error!r}', file=sys.stderr)
        return 2
    print(f'# native codec: {native.native_threads()} threads',
          file=sys.stderr, flush=True)
    perf.enable_ledger()

    sizes = REHEARSAL if args.cpu_rehearsal else FULL
    fns = {
        'seam': lambda: leg_seam(sizes['seam'], args.seed),
        'text': lambda: leg_text(sizes['text'], args.seed),
        'sync': lambda: leg_sync(sizes['sync'], args.seed),
        'served': lambda: leg_served(sizes['served'], args.seed),
    }
    start = time.perf_counter()
    records = {name: run_leg(name, fns[name], counter) for name in legs}
    skipped = [name for name in LEGS if name not in legs]
    ok = not skipped and all(r['ok'] for r in records.values())
    verdict = {
        'ok': ok,
        'device': {'platform': stamp['platform'],
                   'kind': stamp['device_kind'],
                   'count': stamp['n_devices']},
    }
    report = {
        'report': 'chip_smoke',
        'observations_not_metrics': True,
        'rehearsal': args.cpu_rehearsal,
        'seed': args.seed,
        'versions': versions,
        'native_available': True,
        'native_threads': native.native_threads(),
        'compile_cache_dir': cache_dir,
        'total_s': round(time.perf_counter() - start, 3),
        'legs': records,
        'legs_skipped': skipped,
    }
    total = counter.snapshot()
    total['compile_s'] = round(total['compile_s'], 3)
    report.update(total)
    report['peak_bytes_in_use'] = max(
        (r['peak_bytes_in_use'] or 0 for r in records.values()), default=0)
    print(json.dumps(report))
    # the last stdout line: exactly these keys, nothing after it
    print(json.dumps(verdict), flush=True)
    return 0 if ok else 1


if __name__ == '__main__':
    sys.exit(main())
