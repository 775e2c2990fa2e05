"""Offline chaos dose runner (round-5 VERDICT item 7).

Runs the cross-backend chaos differential at the deep-dose knobs
(default 30 seeds x 200 steps x 5 actors — the harness's founders +
mid-run joiners) plus a fleet drop/rebuild-from-logs leg exercising the
donation failure contract (fleet/apply.py: device state is a derived
cache; documents rebuild into a fresh fleet from their change logs),
then writes a summary artifact (default: chaos_dose.json under the
system temp directory) so the dose is reproducible evidence, not a claim.

Usage: python tools/chaos_dose.py [out.json]
Knobs: CHAOS_SEEDS / CHAOS_STEPS / REBUILD_LEGS env vars.
"""

import json
import os
import random
import subprocess
import sys
import tempfile
import time

# the dose is a host-semantics differential on eight virtual CPU devices
# (the tests/conftest.py set-up); it never takes the chip
os.environ['JAX_PLATFORMS'] = 'cpu'
flags = os.environ.get('XLA_FLAGS', '')
if '--xla_force_host_platform_device_count' not in flags:
    os.environ['XLA_FLAGS'] = (
        flags + ' --xla_force_host_platform_device_count=8').strip()

SEEDS = int(os.environ.get('CHAOS_SEEDS', '30'))
STEPS = int(os.environ.get('CHAOS_STEPS', '200'))
REBUILD_LEGS = int(os.environ.get('REBUILD_LEGS', '10'))
OUT = sys.argv[1] if len(sys.argv) > 1 else os.path.join(
    tempfile.gettempdir(), 'chaos_dose.json')
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


CHUNK = int(os.environ.get('CHAOS_CHUNK', '5'))


def run_differential():
    """Run the dose as fresh pytest processes of CHUNK seeds each: one
    long-lived process accumulating 30 seeds of XLA CPU compile cache has
    segfaulted the compiler mid-dose (seen at seed 7 of a 30x200 run);
    per-chunk process isolation makes the dose crash-proof and resumable."""
    t0 = time.time()
    chunks = []
    for base in range(0, SEEDS, CHUNK):
        n = min(CHUNK, SEEDS - base)
        env = dict(os.environ, CHAOS_SEEDS=str(n), CHAOS_STEPS=str(STEPS),
                   CHAOS_SEED_BASE=str(base))
        try:
            proc = subprocess.run(
                [sys.executable, '-m', 'pytest', 'tests/test_chaos.py', '-q',
                 '--tb=line', '-p', 'no:cacheprovider'],
                env=env, cwd=ROOT, capture_output=True, text=True,
                timeout=2 * 3600)
            rc = proc.returncode
            tail = (proc.stdout.strip().splitlines() or [''])[-1]
        except subprocess.TimeoutExpired:
            # a hung chunk must not discard the completed chunks' records
            rc, tail = -1, 'TIMEOUT after 2h'
        chunks.append({'seed_base': base, 'seeds': n,
                       'passed': rc == 0,
                       'returncode': rc, 'pytest_tail': tail})
        print(f'chunk seeds {base}..{base + n - 1}: '
              f'{"pass" if rc == 0 else f"FAIL rc={rc}"} '
              f'({tail})', flush=True)
    return {
        'seeds': SEEDS, 'steps': STEPS,
        'actors': '3 founders + 2 mid-run joiners (5)',
        'universes': ['host', 'fleet-lww', 'fleet-exact'],
        'mid_run_device_loss_rebuild': 'every fleet universe, step STEPS//2',
        'passed': all(c['passed'] for c in chunks),
        'chunks': chunks,
        'elapsed_s': round(time.time() - t0, 1),
    }


def run_rebuild_legs():
    sys.path.insert(0, ROOT)
    import automerge_tpu as A
    from automerge_tpu.fleet.backend import (
        DocFleet, init_docs, apply_changes_docs, materialize_docs,
        rebuild_docs)

    alpha = 'abcdefghij'
    mismatches = 0
    t0 = time.time()
    for seed in range(REBUILD_LEGS):
        rng = random.Random(1000 + seed)
        a1, a2 = '11' * 8, 'ee' * 8
        d1 = A.change(A.init(a1), {'time': 0},
                      lambda r: r.update({'t': A.Text('ab'), 'm': {},
                                          'cnt': A.Counter(0)}))
        d2 = A.merge(A.init(a2), d1)
        for step in range(40):
            which = rng.random()
            src = d1 if rng.random() < 0.5 else d2

            def edit(r, rng=rng):
                roll = rng.random()
                if roll < 0.3:
                    r[rng.choice(alpha)] = rng.randrange(100)
                elif roll < 0.5:
                    r['t'].insert_at(rng.randrange(len(r['t']) + 1),
                                     rng.choice(alpha))
                elif roll < 0.7:
                    r['m'][rng.choice(alpha)] = rng.choice(
                        ['s', 1.5, True, None])
                elif roll < 0.85 and 'cnt' in r and \
                        hasattr(r['cnt'], 'increment'):
                    r['cnt'].increment(rng.randrange(-2, 5))
                else:
                    k = rng.choice(alpha)
                    if k in r:
                        del r[k]   # never t/m/c: alpha keys only
            if src is d1:
                d1 = A.change(d1, {'time': 0}, edit)
            else:
                d2 = A.change(d2, {'time': 0}, edit)
            if which < 0.2:
                d1 = A.merge(d1, d2)
            elif which > 0.9:
                d2 = A.merge(d2, d1)
        final = A.merge(A.clone(d1), d2)
        changes = [bytes(b) for b in A.get_all_changes(final)]
        cut = len(changes) // 2
        fleet = DocFleet(doc_capacity=4, key_capacity=64)
        handles = init_docs(2, fleet)
        handles, _ = apply_changes_docs(
            handles, [changes[:cut], changes[:cut]], mirror=False)
        # drop the device: rebuild BOTH docs into a fresh fleet from logs
        rebuilt = rebuild_docs(handles, DocFleet(doc_capacity=4,
                                                 key_capacity=64))
        rebuilt, _ = apply_changes_docs(
            rebuilt, [changes[cut:], changes[cut:]], mirror=False)
        want = dict(final)
        got = materialize_docs(rebuilt)
        from automerge_tpu.backend import get_heads
        from automerge_tpu import frontend as F
        want_heads = get_heads(F.get_backend_state(final, 'dose'))
        for g, h in zip(got, rebuilt):
            if g != want or h['heads'] != want_heads:
                mismatches += 1
    return {'legs': REBUILD_LEGS, 'edits_per_leg': 40,
            'mismatches': mismatches,
            'elapsed_s': round(time.time() - t0, 1)}


def main():
    out = {
        'round': 5,
        'differential': run_differential(),
        'fleet_drop_rebuild': run_rebuild_legs(),
    }
    out['ok'] = out['differential']['passed'] and \
        out['fleet_drop_rebuild']['mismatches'] == 0
    with open(os.path.join(ROOT, OUT), 'w') as f:
        json.dump(out, f, indent=2)
        f.write('\n')
    print(json.dumps(out, indent=2))


if __name__ == '__main__':
    main()
