"""Bring-up gates (ISSUE 21): chip_smoke.py's refusal to pass without a
TPU, the native codec or a matching oracle; the placeable compile cache
(automerge_tpu/jaxenv.py); the device stamp jaxenv hands every entry point.

The entry points are exercised the way the driver runs them — as child
processes — on the CPU, at rehearsal size. What only the chip can show
(donation, scatter order, while_loop termination at full size) is
chip_smoke.py's job there, not this file's.
"""

import json
import os
import random
import subprocess
import sys

import pytest

from automerge_tpu import jaxenv

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, 'chip_smoke.py')


def _env(tmp_path, **extra):
    """A plain JAX_PLATFORMS=cpu environment: one CPU device (not the
    suite's eight), the compile cache placed under tmp_path."""
    env = dict(os.environ, JAX_PLATFORMS='cpu',
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / 'cache'))
    env.pop('XLA_FLAGS', None)
    env.update(extra)
    return env


def _run(args, env, timeout=300):
    return subprocess.run([sys.executable] + args, env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=timeout)


def _smoke_lines(proc):
    """chip_smoke.py's two stdout lines: (report, verdict). The verdict
    is the LAST line and carries exactly the keys the driver reads."""
    report, verdict = map(json.loads, proc.stdout.strip().splitlines())
    assert list(verdict) == ['ok', 'device']
    assert list(verdict['device']) == ['platform', 'kind', 'count']
    assert isinstance(verdict['ok'], bool)
    assert isinstance(verdict['device']['count'], int)
    return report, verdict


# ---- chip_smoke.py ---------------------------------------------------------

def test_smoke_rehearsal_runs_every_leg(tmp_path):
    proc = _run([SMOKE, '--cpu-rehearsal'], _env(tmp_path))
    assert proc.returncode == 0, proc.stderr[-2000:]
    result, verdict = _smoke_lines(proc)
    assert verdict == {'ok': True, 'device': {'platform': 'cpu',
                                              'kind': 'cpu', 'count': 1}}
    assert result['rehearsal'] is True
    assert set(result['legs']) == {'seam', 'text', 'sync', 'served'}
    assert all(leg['ok'] for leg in result['legs'].values())
    assert result['native_available'] is True
    assert result['compile_cache_dir'] == str(tmp_path / 'cache')
    seam = result['legs']['seam']['fleet_metrics']
    assert seam['turbo_calls'] >= 1 and 'fallbacks' not in seam
    # the families the chip run must show moving are moving here too
    sync = result['legs']['sync']['dispatches_by_kernel']
    assert sync['hashindex.dispatch_count'] > 0
    assert sync['bloom.dispatch_count'] > 0
    assert result['compilations'] > 0


def test_smoke_refuses_cpu_without_the_rehearsal_flag(tmp_path):
    proc = _run([SMOKE], _env(tmp_path))
    assert proc.returncode != 0
    assert "platform 'cpu'" in proc.stderr      # says what it found
    assert '# leg' not in proc.stderr           # before any leg
    assert proc.stdout.strip() == ''            # and prints no result


def _smoke_with(patch, argv, env):
    """chip_smoke.main(argv) in a child, after `patch` (python source run
    with `chip_smoke` and `native` imported) has tampered with it."""
    code = (f'import sys; sys.path.insert(0, {ROOT!r})\n'
            f'import automerge_tpu.native as native\n'
            f'import chip_smoke\n{patch}\n'
            f'sys.exit(chip_smoke.main({argv!r}))')
    return _run(['-c', code], env)


def test_smoke_fails_without_the_native_codec(tmp_path):
    proc = _smoke_with(
        "native._lib = None; native._load_error = OSError('no g++ here')",
        ['--cpu-rehearsal'], _env(tmp_path))
    assert proc.returncode != 0
    assert 'native codec unavailable' in proc.stderr
    assert 'no g++ here' in proc.stderr         # prints native._load_error
    assert '# leg' not in proc.stderr
    assert proc.stdout.strip() == ''


def test_smoke_fails_when_a_leg_disagrees_with_the_oracle(tmp_path):
    # the oracle is fed one change fewer than the fleet: save() bytes and
    # the materialized view can no longer agree
    proc = _smoke_with(
        'real = chip_smoke.host_oracle\n'
        'chip_smoke.host_oracle = lambda changes: real(list(changes)[:-1])',
        ['--cpu-rehearsal', '--legs', 'seam'], _env(tmp_path))
    assert proc.returncode != 0
    result, verdict = _smoke_lines(proc)
    assert verdict['ok'] is False
    seam = result['legs']['seam']
    assert seam['ok'] is False and 'host oracle' in seam['error']


def test_smoke_subset_never_reports_ok(tmp_path):
    proc = _run([SMOKE, '--cpu-rehearsal', '--legs', 'seam'],
                _env(tmp_path))
    result, verdict = _smoke_lines(proc)
    assert result['legs']['seam']['ok'] is True
    assert verdict['ok'] is False and proc.returncode != 0
    assert result['legs_skipped'] == ['text', 'sync', 'served']


# ---- the compile cache -----------------------------------------------------

_JIT_PROBE = '''
import sys
sys.path.insert(0, {root!r})
import jax, numpy as np
from automerge_tpu import jaxenv
events = []
jax.monitoring.register_event_listener(lambda e, **kw: events.append(e))
print(jaxenv.configure_compile_cache())
print(jaxenv.configure_compile_cache())
print(jax.config.jax_compilation_cache_dir)
x = np.arange(8, dtype=np.float32)      # no device op but the jit itself
jax.jit(lambda x: x * {const!r} + 1.0)(x).block_until_ready()
print(events.count('/jax/compilation_cache/cache_hits'),
      events.count('/jax/compilation_cache/cache_misses'))
'''


def _jit_probe(env, const):
    proc = _run(['-c', _JIT_PROBE.format(root=ROOT, const=const)], env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    first, second, configured, counts = proc.stdout.strip().splitlines()
    hits, misses = map(int, counts.split())
    return first, second, configured, hits, misses


def _listing(path):
    return sorted(os.listdir(path)) if os.path.isdir(path) else None


def test_compile_cache_placed_from_outside(tmp_path):
    placed = str(tmp_path / 'placed')
    before = _listing(jaxenv.COMPILE_CACHE_DIR)
    first, second, configured, hits, misses = _jit_probe(
        _env(tmp_path, JAX_COMPILATION_CACHE_DIR=placed), random.random())
    assert first == second == configured == placed
    assert (hits, misses) == (0, 1)
    assert os.listdir(placed)                   # the entry landed there
    # ... and nowhere else: the in-checkout default was not touched
    assert _listing(jaxenv.COMPILE_CACHE_DIR) == before


def test_compile_cache_defaults_to_one_fixed_path_in_the_checkout(tmp_path):
    env = _env(tmp_path)
    del env['JAX_COMPILATION_CACHE_DIR']
    const = random.random()     # a program no earlier run has cached
    assert jaxenv.COMPILE_CACHE_DIR == os.path.join(ROOT, '.jax_cache')
    cold = _jit_probe(env, const)
    warm = _jit_probe(env, const)
    for first, second, configured, _h, _m in (cold, warm):
        assert first == second == configured == jaxenv.COMPILE_CACHE_DIR
    # sub-second, tiny kernels are cached at all (thresholds lowered),
    # and a second PROCESS finds the first one's entry
    assert cold[3:] == (0, 1)
    assert warm[3:] == (1, 0)


def test_require_platform_names_what_it_found():
    stamp = jaxenv.require_platform(cpu=True)
    assert stamp['platform'] == 'cpu' and stamp['n_devices'] >= 1
    assert stamp == jaxenv.device_stamp()
    with pytest.raises(RuntimeError, match="platform 'cpu'"):
        jaxenv.require_platform()
