"""Performance observatory coverage (ISSUE-13).

- DRIFT DETECTOR NOISE IMMUNITY: the seam-baseline detector replayed
  against per-event deltas sampled from a RECORDED ±40%
  noisy-box history must fire ZERO alerts across 5 clean windows, and
  must detect a synthetic 1.3x slowdown within 2 windows — the
  windowed-mean aggregation (window_events events per judgment) is
  what earns both at once.
- KERNEL COST LEDGER: off = no counting; on = per-kind dispatches /
  blocking seconds / one signature per distinct compilation, with XLA
  cost_analysis resolved lazily and cached.
- MEMORY WATERMARKS: tier sources sampled with sticky process-lifetime
  highs; RSS always present.
- ATOMIC COUNTERS: Counters.inc is exact under a thread hammer
  (the round-15 undercount), and a pump_threads>1 ShardRouter run
  lands EXACT service health counts.
"""

import json
import threading

import numpy as np
import pytest

from automerge_tpu.observability import hist as obs_hist
from automerge_tpu.observability import perf as obs_perf
from automerge_tpu.observability import recorder as obs_recorder
from automerge_tpu.observability.metrics import Counters, health_counts
from automerge_tpu.observability.perf import PerfBaselines, SeamSpec


@pytest.fixture(autouse=True)
def _clean_perf_state():
    obs_perf.disable_observatory()
    obs_hist.disable()
    obs_perf.reset_ledger()
    yield
    obs_perf.disable_observatory()
    obs_hist.disable()
    obs_perf.reset_ledger()


# ---- recorded noise: a ±40% history ---------------------------------------

# Seam rates (changes/s) the CPU era recorded on one box with one code
# path: the round-7 headline, its same-day control, the round-6 headline,
# then the round-7 thread sweep at 1, 2 and 4 threads (BASELINE.md keeps
# the story; the record files themselves went in PR 31). The order is the
# order the detector has always been replayed against.
_RECORDED_SEAM_RATES = (415181.0, 486581.0, 708847.0,
                        505387.0, 517576.0, 415767.0)


def _recorded_deltas():
    """Relative deltas of the recorded rates (the measurement history
    that repeatedly blamed the box), each vs their common median."""
    med = float(np.median(_RECORDED_SEAM_RATES))
    deltas = [v / med - 1.0 for v in _RECORDED_SEAM_RATES]
    # the recorded swing really is the ±40% story the ISSUE cites
    assert max(deltas) - min(deltas) > 0.4
    return deltas


class TestDriftDetector:
    def _replay(self, reg, seam, base_s, n_windows, scale=1.0, start=0):
        """Feed n_windows full windows of per-event latencies sampled
        from the recorded delta table, then tick once per window."""
        deltas = _recorded_deltas()
        k = start
        for _ in range(n_windows):
            for _ in range(reg.window_events):
                reg.record(seam, base_s * scale *
                           (1.0 + deltas[k % len(deltas)]))
                k += 1
            reg.tick()
        return k

    def test_zero_false_fires_on_recorded_noise_then_detects_1p3x(self):
        reg = PerfBaselines(seams=(SeamSpec('probe', 'probe_hist_s'),),
                            window_events=32, drift_pct=0.20,
                            up_ticks=2, min_windows=2)
        fired0 = obs_perf.perf_stats()['perf_alerts_fired']
        # 5 clean windows of recorded ±40% per-event noise: quiet
        k = self._replay(reg, 'probe', 0.1, 5)
        assert obs_perf.perf_stats()['perf_alerts_fired'] == fired0
        assert not reg.active_alerts()
        state = reg.seams['probe']
        assert state.windows == 5
        assert 0.9 < state.drift < 1.1        # window means concentrated
        # synthetic 1.3x slowdown: detected within 2 windows
        self._replay(reg, 'probe', 0.1, 2, scale=1.3, start=k)
        assert obs_perf.perf_stats()['perf_alerts_fired'] == fired0 + 1
        assert reg.active_alerts() == ['probe']
        assert state.drift == pytest.approx(1.3, rel=0.1)

    def test_baseline_freezes_under_drift_and_alert_is_edge_triggered(self):
        reg = PerfBaselines(seams=(SeamSpec('probe', 'x'),),
                            window_events=8, drift_pct=0.20,
                            up_ticks=2, min_windows=2)
        self._replay(reg, 'probe', 0.1, 5)
        baseline_before = reg.seams['probe'].ewma
        fired0 = obs_perf.perf_stats()['perf_alerts_fired']
        # a sustained regression must not teach the baseline its own
        # slowdown (else the alert would self-clear)
        self._replay(reg, 'probe', 0.1, 6, scale=1.4)
        assert reg.seams['probe'].ewma == \
            pytest.approx(baseline_before, rel=0.15)
        # edge-triggered: ONE fire despite 6 drifting windows
        assert obs_perf.perf_stats()['perf_alerts_fired'] == fired0 + 1

    def test_alert_clears_after_recovery(self):
        """The clear rule judges EXCESS drift (drift - 1): a recovered
        seam back at its baseline (drift ~1.0) must clear within
        down_ticks windows — not demand the seam run 40% FASTER than
        baseline (the raw-ratio-into-_Alert bug)."""
        reg = PerfBaselines(seams=(SeamSpec('probe', 'x'),),
                            window_events=8, drift_pct=0.20,
                            up_ticks=2, down_ticks=4, min_windows=2)
        self._replay(reg, 'probe', 0.1, 5)
        self._replay(reg, 'probe', 0.1, 4, scale=1.5)
        assert reg.active_alerts() == ['probe']
        cleared0 = obs_perf.perf_stats()['perf_alerts_cleared']
        # full recovery to baseline, same recorded noise
        self._replay(reg, 'probe', 0.1, 8)
        assert reg.active_alerts() == []
        assert obs_perf.perf_stats()['perf_alerts_cleared'] == \
            cleared0 + 1

    def test_fire_lands_in_flight_recorder(self):
        obs_recorder.clear_events()
        reg = PerfBaselines(seams=(SeamSpec('probe', 'x'),),
                            window_events=8, drift_pct=0.20,
                            up_ticks=2, min_windows=2)
        self._replay(reg, 'probe', 0.1, 4)
        self._replay(reg, 'probe', 0.1, 3, scale=1.5)
        kinds = [e['kind'] for e in obs_recorder.recent_events()]
        assert 'perf_drift' in kinds
        dump = obs_recorder.last_flight_record()
        assert dump['trigger'] == 'perf'
        assert dump['detail']['seam'] == 'probe'
        assert dump['detail']['drift'] >= 1.2
        assert len(dump['detail']['window_means_s']) >= 4

    def test_histogram_feed_and_gauges(self):
        obs_hist.enable()
        reg = obs_perf.enable_baselines(window_events=4, min_windows=1)
        try:
            for _ in range(8):
                obs_hist.record_value('apply_batch_s', 0.05, scale=1e9,
                                      unit='s')
            reg.tick()
            gauges = obs_perf.baseline_gauges()
            assert 'apply_batch' in gauges
            g = gauges['apply_batch']
            assert g['window_s'] == pytest.approx(0.05)
            assert g['windows'] == 2
            assert g['alert'] == 0
        finally:
            obs_perf.disable_baselines()

    def test_service_tick_drives_default_registry(self):
        from automerge_tpu.fleet.backend import DocFleet
        from automerge_tpu.service import DocService
        reg = obs_perf.enable_baselines()
        try:
            service = DocService(fleet=DocFleet(), slo=False)
            before = reg.ticks
            service.pump()
            assert reg.ticks == before + 1
        finally:
            obs_perf.disable_baselines()


# ---- kernel cost ledger ----------------------------------------------------

class TestKernelLedger:
    def test_off_by_default_counts_when_enabled(self):
        import jax
        import jax.numpy as jnp
        fn = obs_perf.instrument_kernel(
            'probe_kernel', jax.jit(lambda x: jnp.sum(x * 2)))
        fn(jnp.arange(8))
        assert 'probe_kernel' not in obs_perf.kernel_snapshot()
        obs_perf.enable_ledger()
        fn(jnp.arange(8))
        fn(jnp.arange(8))
        fn(jnp.arange(16))          # a second compilation signature
        snap = obs_perf.kernel_snapshot()['probe_kernel']
        assert snap['dispatches'] == 3
        assert snap['signatures'] == 2
        assert snap['seconds'] > 0

    def test_report_resolves_and_caches_cost_analysis(self):
        import jax
        import jax.numpy as jnp
        fn = obs_perf.instrument_kernel(
            'probe_cost', jax.jit(lambda x: x @ x))
        obs_perf.enable_ledger()
        fn(jnp.ones((16, 16)))
        report = obs_perf.kernel_report()['probe_cost']
        sig = report['signatures'][0]
        assert sig['dispatches'] == 1
        # CPU XLA reports flops for a matmul; tolerate backends that
        # return an error dict, but never a crash
        assert 'cost' in sig
        if 'flops' in sig['cost']:
            assert sig['cost']['flops'] > 0
            assert report['flops_total'] > 0

    def test_dump_ledger_is_floor_readable(self, tmp_path):
        import jax
        import jax.numpy as jnp
        fn = obs_perf.instrument_kernel(
            'probe_dump', jax.jit(lambda x: x + 1))
        obs_perf.enable_ledger()
        fn(jnp.arange(4))
        path = obs_perf.dump_ledger(str(tmp_path / 'ledger.json'))
        with open(path) as f:
            dump = json.load(f)
        assert dump['kind'] == 'kernel_ledger'
        assert 'probe_dump' in dump['kernels']


# ---- memory watermarks -----------------------------------------------------

class TestWatermarks:
    def test_rss_and_sticky_highs(self):
        obs_perf.reset_watermarks()
        value = [1000]
        obs_perf.register_mem_source('probe_tier', lambda: value[0])
        try:
            cur = obs_perf.sample_watermarks()
            assert cur['rss'] > 0
            assert cur['probe_tier'] == 1000
            value[0] = 5000
            obs_perf.sample_watermarks()
            value[0] = 200
            snap = obs_perf.watermark_snapshot()
            assert snap['current']['probe_tier'] == 200
            assert snap['high']['probe_tier'] == 5000   # sticky
            assert snap['high']['rss'] >= snap['current']['rss'] > 0
        finally:
            obs_perf._mem_sources.pop('probe_tier', None)

    def test_fleet_and_store_tiers_registered(self):
        from automerge_tpu.fleet.backend import DocFleet, init_docs
        from automerge_tpu.fleet.storage import MainStore
        fleet = DocFleet()
        init_docs(4, fleet)
        store = MainStore()
        store.add(b'x' * 100, ['ab' * 32], {'ab' * 32: 1}, 3, 1)
        cur = obs_perf.sample_watermarks()
        assert cur['mainstore_bytes'] >= 100
        assert 'fleet_resident_bytes' in cur
        assert store.resident_bytes() >= 100 + 32


# ---- atomic counters under threads -----------------------------------------

class TestAtomicCounters:
    def test_inc_exact_under_hammer(self):
        c = Counters({'hits': 0})
        threads, per_thread = 6, 10000

        def hammer():
            for _ in range(per_thread):
                c.inc('hits')

        ts = [threading.Thread(target=hammer) for _ in range(threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        # a plain dict loses updates here (the round-15 undercount);
        # the locked inc must be EXACT
        assert c['hits'] == threads * per_thread

    def test_inc_negative_and_missing_key(self):
        c = Counters()
        assert c.inc('gauge') == 1
        assert c.inc('gauge', -1) == 0
        c['reset_me'] = 7
        c['reset_me'] = 0
        assert c['reset_me'] == 0

    def test_threaded_router_pump_counts_exact(self):
        """The satellite pin: at pump_threads>1, module health counters
        land EXACT (they are Counters now, not bare dict increments)."""
        from automerge_tpu import native
        if not native.available():
            pytest.skip('native codec unavailable')
        from automerge_tpu.columnar import encode_change
        from automerge_tpu.service.backoff import Backoff
        from automerge_tpu.shard import ShardRouter
        clk = [0.0]
        router = ShardRouter(n_shards=4, clock=lambda: clk[0],
                             pump_threads=4, lease_ticks=3,
                             backoff=Backoff(base=0.02, factor=1.5,
                                             cap=0.32, retries=14,
                                             seed=1))
        n_tenants, per_tenant = 12, 3
        try:
            for i in range(n_tenants):
                router.open_tenant(f't{i}')
            before = health_counts()
            tickets = []
            for i in range(n_tenants):
                for seq in range(1, per_tenant + 1):
                    tickets.append(router.submit(
                        f't{i}', 'apply', [encode_change({
                            'actor': f'{i:02x}' * 16, 'seq': seq,
                            'startOp': seq, 'time': 0, 'message': '',
                            'deps': [],
                            'ops': [{'action': 'set', 'obj': '_root',
                                     'key': 'k', 'value': seq,
                                     'datatype': 'int', 'pred': []}]})]))
            for _ in range(400):
                if all(t.done for t in tickets):
                    break
                router.pump(now=clk[0])
                clk[0] += 0.02
            assert all(t.status == 'ok' for t in tickets), \
                [(t.status, t.error) for t in tickets if not t.done
                 or t.status != 'ok'][:4]
            after = health_counts()
            moved = {k: after[k] - before.get(k, 0)
                     for k in after if after[k] != before.get(k, 0)}
            n = n_tenants * per_tenant
            # no retries in a clean router: submit == dispatch == done
            assert moved.get('shard_retries', 0) == 0
            assert moved.get('service_requests') == n, moved
            assert moved.get('service_completed') == n, moved
        finally:
            router.close()
