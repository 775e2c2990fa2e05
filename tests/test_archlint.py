"""Contract-linter tests (ISSUE-19): per-rule positive/negative
fixtures (violation detected at the right file:line; idiomatic code
passes), the suppression-baseline round-trip, the real-tree tier-1
gate, and pinning tests for the violations the linter surfaced and
this round fixed."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from automerge_tpu import analysis
from automerge_tpu.analysis import scopes

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def lint(src, path, rule_ids=None):
    return analysis.lint_source(textwrap.dedent(src), path,
                                analysis.get_rules(rule_ids))


def violations(src, path, rule_ids=None):
    return [f for f in lint(src, path, rule_ids) if not f.suppressed]


# ---------------------------------------------------------------------------
# rule: typed-errors
# ---------------------------------------------------------------------------

class TestTypedErrors:
    def test_decode_surface_bare_raise_detected(self):
        src = '''\
        from automerge_tpu.errors import MalformedChange


        def decode_frame(buf):
            if not buf:
                raise ValueError('empty frame')
            return buf
        '''
        found = violations(src, 'automerge_tpu/backend/wire.py',
                           ['typed-errors'])
        assert len(found) == 1
        assert found[0].line == 6
        assert found[0].path == 'automerge_tpu/backend/wire.py'
        assert 'decode_frame' in found[0].message

    def test_guarded_boundary_and_typed_raise_pass(self):
        src = '''\
        from automerge_tpu.errors import MalformedChange, as_wire_error


        def decode_frame(buf):
            try:
                if not buf:
                    raise ValueError('empty frame')
                if buf[0] != 7:
                    raise MalformedChange('bad magic')
                return buf
            except Exception as exc:
                raise as_wire_error(exc, MalformedChange, 'decode_frame')
        '''
        assert violations(src, 'automerge_tpu/backend/wire.py',
                          ['typed-errors']) == []

    def test_funnel_modules_exempt(self):
        src = '''\
        def decode_column(buf):
            raise ValueError('internal funnel style')
        '''
        assert violations(src, 'automerge_tpu/columnar.py',
                          ['typed-errors']) == []

    def test_except_pass_detected(self):
        src = '''\
        def f():
            try:
                g()
            except Exception:
                pass
        '''
        found = violations(src, 'tools/anything.py', ['typed-errors'])
        assert len(found) == 1 and found[0].line == 4

    def test_narrowed_except_pass_ok(self):
        src = '''\
        def f():
            try:
                g()
            except (OSError, KeyError):
                pass
        '''
        assert violations(src, 'tools/anything.py', ['typed-errors']) == []

    def test_message_string_match_detected(self):
        src = '''\
        def f():
            try:
                g()
            except ValueError as exc:
                if 'session closed' in str(exc):
                    return None
                raise
        '''
        found = violations(src, 'automerge_tpu/shard/router.py',
                           ['typed-errors'])
        assert len(found) == 1 and found[0].line == 5
        assert 'typed class' in found[0].message

    def test_isinstance_dispatch_ok(self):
        src = '''\
        from automerge_tpu.errors import SessionClosed


        def f():
            try:
                g()
            except ValueError as exc:
                if isinstance(exc, SessionClosed):
                    return None
                raise
        '''
        assert violations(src, 'automerge_tpu/shard/router.py',
                          ['typed-errors']) == []


# ---------------------------------------------------------------------------
# rule: counter-discipline
# ---------------------------------------------------------------------------

class TestCounterDiscipline:
    def test_raw_dict_stats_detected(self):
        src = '''\
        _stats = {'decoded': 0, 'rejected': 0}
        '''
        found = violations(src, 'automerge_tpu/fleet/newmod.py',
                           ['counter-discipline'])
        assert len(found) == 1 and found[0].line == 1
        assert 'Counters' in found[0].message

    def test_reserved_source_name_detected(self):
        src = '''\
        from automerge_tpu.observability import register_health_source

        register_health_source('fleet3', lambda: 0)
        '''
        found = violations(src, 'automerge_tpu/fleet/newmod.py',
                           ['counter-discipline'])
        assert len(found) == 1 and found[0].line == 3
        assert 'reserved' in found[0].message

    def test_counters_and_local_dicts_pass(self):
        src = '''\
        from automerge_tpu.observability.metrics import Counters

        _stats = Counters({'decoded': 0})


        def summarize():
            link_stats = {}
            link_stats['x'] = 1
            return link_stats
        '''
        assert violations(src, 'automerge_tpu/fleet/newmod.py',
                          ['counter-discipline']) == []


# ---------------------------------------------------------------------------
# rule: kernel-ledger
# ---------------------------------------------------------------------------

class TestKernelLedger:
    def test_unwrapped_jits_detected(self):
        src = '''\
        import functools

        import jax


        @jax.jit
        def f(x):
            return x


        g = jax.jit(lambda x: x)


        @functools.partial(jax.jit, static_argnums=(0,))
        def h(x):
            return x
        '''
        found = violations(src, 'automerge_tpu/fleet/newkern.py',
                           ['kernel-ledger'])
        assert [f.line for f in found] == [6, 11, 14]

    def test_instrumented_jit_passes(self):
        src = '''\
        import jax

        from automerge_tpu.observability.perf import instrument_kernel


        def _impl(x):
            return x


        k = instrument_kernel('k', jax.jit(_impl, donate_argnums=(0,)))
        '''
        assert violations(src, 'automerge_tpu/fleet/newkern.py',
                          ['kernel-ledger']) == []

    def test_per_doc_jnp_loop_detected(self):
        src = '''\
        import jax.numpy as jnp


        def pump(docs):
            out = []
            for d in docs:
                out.append(jnp.asarray(d))
            return out
        '''
        found = violations(src, 'automerge_tpu/service/pump.py',
                           ['kernel-ledger'])
        assert len(found) == 1 and found[0].line == 7
        assert 'per-doc loop' in found[0].message

    def test_per_class_pool_loop_passes(self):
        src = '''\
        import jax.numpy as jnp


        def grow(pools):
            for cls, st in pools.items():
                pools[cls] = jnp.zeros(st)
        '''
        assert violations(src, 'automerge_tpu/fleet/loader2.py',
                          ['kernel-ledger']) == []


# ---------------------------------------------------------------------------
# rule: determinism
# ---------------------------------------------------------------------------

class TestDeterminism:
    def test_wall_clock_and_unseeded_random_detected(self):
        src = '''\
        import random
        import time


        def tick():
            return time.time()


        def jitter():
            return random.random()
        '''
        found = violations(src, 'automerge_tpu/fleet/clock.py',
                           ['determinism'])
        assert [f.line for f in found] == [6, 10]

    def test_seeded_rng_and_out_of_scope_clock_pass(self):
        src = '''\
        import random
        import time


        def jitter(seed):
            return random.Random(seed).random()


        def stamp():
            return time.time()
        '''
        # seeded instance passes even in scope; the wall clock still
        # flags there, and nothing flags OUT of the deterministic scope
        # (observability legitimately timestamps real time)
        in_scope = violations(src, 'automerge_tpu/fleet/clock.py',
                              ['determinism'])
        assert [f.line for f in in_scope] == [10]
        assert violations(src, 'automerge_tpu/observability/x.py',
                          ['determinism']) == []

    def test_unsorted_encode_iteration_detected(self):
        src = '''\
        def encode_row(d, out):
            for k, v in d.items():
                out.append(k)
        '''
        found = violations(src, 'automerge_tpu/backend/enc.py',
                           ['determinism'])
        assert len(found) == 1 and found[0].line == 2
        assert 'sorted' in found[0].message

    def test_sorted_encode_iteration_passes(self):
        src = '''\
        def encode_row(d, out):
            for k, v in sorted(d.items()):
                out.append(k)
            all_ids = set()
            for inner in d.values():
                all_ids |= inner
        '''
        assert violations(src, 'automerge_tpu/backend/enc.py',
                          ['determinism']) == []


# ---------------------------------------------------------------------------
# rule: lock-discipline
# ---------------------------------------------------------------------------

class TestLockDiscipline:
    def test_unlocked_module_state_mutation_detected(self):
        src = '''\
        _tbl = {}


        def put(k, v):
            _tbl[k] = v
        '''
        found = violations(src, 'automerge_tpu/observability/export.py',
                           ['lock-discipline'])
        assert len(found) == 1 and found[0].line == 5
        assert 'race candidate' in found[0].message

    def test_locked_mutation_and_counters_pass(self):
        src = '''\
        import threading

        from automerge_tpu.observability.metrics import Counters

        _tbl = {}
        _LOCK = threading.Lock()
        _stats = Counters({'hits': 0})


        def put(k, v):
            with _LOCK:
                _tbl[k] = v
            _stats.inc('hits')
        '''
        assert violations(src, 'automerge_tpu/observability/export.py',
                          ['lock-discipline']) == []

    def test_rule_scoped_to_threaded_modules(self):
        src = '''\
        _tbl = {}


        def put(k, v):
            _tbl[k] = v
        '''
        assert violations(src, 'automerge_tpu/frontend/views2.py',
                          ['lock-discipline']) == []


# ---------------------------------------------------------------------------
# suppression + baseline round-trip
# ---------------------------------------------------------------------------

VIOLATING = '''_tbl = {}


def put(k, v):
    # archlint: ok[lock-discipline] fixture: registration is import-time only
    _tbl[k] = v
'''


class TestSuppressionBaseline:
    def _write(self, root, body):
        mod = os.path.join(root, 'automerge_tpu', 'observability')
        os.makedirs(mod, exist_ok=True)
        path = os.path.join(mod, 'export.py')
        with open(path, 'w') as fh:
            fh.write(body)
        return path

    def test_round_trip(self, tmp_path):
        root = str(tmp_path)
        self._write(root, VIOLATING)
        bl = os.path.join(root, 'baseline.json')
        rules = analysis.get_rules(['lock-discipline'])

        # suppressed inline, but NOT yet in the baseline -> check fails
        findings, _, _ = analysis.lint_paths(['automerge_tpu'], rules,
                                             root=root)
        assert len(findings) == 1 and findings[0].suppressed
        checked = analysis.check_findings(findings,
                                          analysis.load_baseline(bl))
        assert not checked['violations'] and len(checked['unlisted']) == 1

        # record it -> check passes and the justification is on record
        entries = analysis.write_baseline(bl, findings)
        assert entries[0]['justification'].startswith('fixture:')
        checked = analysis.check_findings(findings,
                                          analysis.load_baseline(bl))
        assert not (checked['violations'] or checked['unlisted'] or
                    checked['stale'])

        # remove the inline comment -> violation AND stale entry
        self._write(root, VIOLATING.replace(
            '    # archlint: ok[lock-discipline] fixture: registration '
            'is import-time only\n', ''))
        findings, _, _ = analysis.lint_paths(['automerge_tpu'], rules,
                                             root=root)
        checked = analysis.check_findings(findings,
                                          analysis.load_baseline(bl))
        assert len(checked['violations']) == 1
        assert len(checked['stale']) == 1

    def test_unjustified_marker_does_not_suppress(self, tmp_path):
        root = str(tmp_path)
        self._write(root, VIOLATING.replace(
            'fixture: registration is import-time only', ''))
        findings, _, _ = analysis.lint_paths(
            ['automerge_tpu'], analysis.get_rules(['lock-discipline']),
            root=root)
        assert len(findings) == 1 and not findings[0].suppressed
        assert 'no justification' in findings[0].message

    def test_wrong_rule_marker_does_not_suppress(self, tmp_path):
        root = str(tmp_path)
        self._write(root, VIOLATING.replace('ok[lock-discipline]',
                                            'ok[determinism]'))
        findings, _, _ = analysis.lint_paths(
            ['automerge_tpu'], analysis.get_rules(['lock-discipline']),
            root=root)
        assert len(findings) == 1 and not findings[0].suppressed


# ---------------------------------------------------------------------------
# the tier-1 gate: the REAL tree is clean under the checked-in baseline
# ---------------------------------------------------------------------------

def test_real_tree_is_clean_under_checked_in_baseline():
    proc = subprocess.run(
        [sys.executable, os.path.join('tools', 'archlint.py'),
         '--check', '--json', '-'],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    # with --json -, stdout is the pure payload (the human report goes
    # to stderr so the output pipes into `obs_report --archlint -`)
    payload = json.loads(proc.stdout)
    assert payload['violations'] == 0
    assert payload['unlisted'] == 0 and payload['stale'] == []
    assert len(payload['rules']) == 5
    # acceptance: the suppression baseline stays small and justified
    assert payload['baseline_size'] <= 10
    assert all(f['justification'] for f in payload['findings']
               if f['suppressed'])


def test_scope_tables_name_real_files():
    # a scope table pointing at renamed/deleted modules checks nothing
    for rel in sorted(scopes.FUNNEL_MODULES | scopes.THREADED_MODULES):
        assert os.path.exists(os.path.join(REPO, rel)), rel


# ---------------------------------------------------------------------------
# pinning tests for the real violations this round fixed
# ---------------------------------------------------------------------------

class TestFixedViolations:
    def test_zero_width_bloom_header_raises_typed(self):
        from automerge_tpu.backend.sync import read_filter_header
        from automerge_tpu.encoding import Decoder, Encoder
        from automerge_tpu.errors import MalformedSyncMessage
        enc = Encoder()
        enc.append_uint32(4)     # num_entries > 0
        enc.append_uint32(0)     # bits_per_entry == 0 -> zero-width
        enc.append_uint32(0)
        with pytest.raises(MalformedSyncMessage):
            read_filter_header(Decoder(enc.buffer))

    def test_native_inflate_garbage_raises_typed(self):
        from automerge_tpu import native
        from automerge_tpu.errors import MalformedChange
        if not native.available():
            pytest.skip('native toolchain unavailable')
        with pytest.raises(MalformedChange):
            native.inflate_raw(b'\xffgarbage-not-deflate\xff',
                               max_size=1 << 16)

    def test_fixed_jit_entry_points_are_in_the_ledger(self):
        from automerge_tpu.fleet import registers, sharding
        from automerge_tpu.observability import perf
        assert registers.visible_registers.kernel_kind == \
            'visible_registers'
        mesh = sharding.fleet_mesh()
        for factory, kind in (
                (sharding.sharded_seq_apply, 'sharded_seq_apply'),
                (sharding.sharded_long_seq_apply,
                 'sharded_long_seq_apply'),
                (sharding.sharded_long_seq_materialize,
                 'sharded_long_seq_materialize'),
                (sharding.sharded_apply, 'sharded_apply')):
            assert factory(mesh).kernel_kind == kind
        # wrap-time registration makes them visible to the ledger even
        # before the first dispatch (kernel_snapshot shows them once
        # dispatched; kernel_kinds lists every wired kind)
        kinds = set(perf.kernel_kinds())
        assert {'visible_registers', 'sharded_seq_apply',
                'sharded_apply'} <= kinds

    def test_register_source_registration_is_locked(self):
        # the round-13 registries now take the counters lock; pin the
        # behavioral contract (register + read back) rather than the
        # lock itself — archlint pins the lock statically
        from automerge_tpu.observability import metrics
        metrics.register_health_source('archlint_pin', lambda: 41)
        try:
            assert metrics.health_counts()['archlint_pin'] == 41
        finally:
            with metrics._COUNTERS_LOCK:
                metrics._health_sources.pop('archlint_pin', None)
