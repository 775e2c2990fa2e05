"""The per-layer metrics that read the seam's span tree (PR 27), in a tiny
traced run on the CPU: all six are reported, the timed ones fit inside
`seam.host_ms_per_step`, and the program's spans are in the profiler
capture's host plane, inside the driver's own annotation.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""

import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
for _path in (ROOT, BENCH_DIR):
    if _path not in sys.path:
        sys.path.insert(0, _path)

import harness                                      # noqa: E402
import trace_reduce                                 # noqa: E402
import test_benchmark                               # noqa: E402

TIMED = ('seam.gate_meta_ms_per_step', 'seam.gate_drain_ms_per_step',
         'seam.gate_validate_ms_per_step',
         'seam.commit_columnar_ms_per_step',
         'seam.commit_staged_ms_per_step')
SPANS = ('turbo_gate', 'gate.drain', 'commit.staged')


def test_a_tiny_traced_run_reports_the_span_tree_metrics(monkeypatch):
    host = {}

    def reduce_on_cpu(path, annotation_names=()):
        """A CPU capture has no /device:TPU plane for reduce_trace to
        read; keep its host plane's events and hand back an idle device."""
        from jax.profiler import ProfileData
        for plane in ProfileData.from_file(path).planes:
            if plane.name == trace_reduce.HOST_PLANE:
                for line in plane.lines:
                    for e in line.events:
                        host.setdefault(e.name, []).append(
                            (e.start_ns, e.start_ns + e.duration_ns))
        return {'busy_s': 0.0, 'devices': 1, 'modules': {}, 'top_ops': [],
                'idle_gaps': []}

    monkeypatch.setattr(trace_reduce, 'reduce_trace', reduce_on_cpu)
    workload = 'map-merge-10k.bulk'
    result = harness.run_cell(workload, 11, 0.5, 1, cpu=True,
                              overrides=test_benchmark.TINY[workload])
    from automerge_tpu.observability import spans
    spans.disable()
    assert result['correct'] is True
    metrics = {name: entry['value']
               for name, entry in result['metrics'].items()}
    assert set(TIMED) | {'seam.untraced_ms_per_step'} <= set(metrics)
    for name in TIMED:
        assert 0 <= metrics[name] <= metrics['seam.host_ms_per_step'], name
    # two-headed logs: the work is in the off-chain sub-phases
    assert metrics['seam.gate_meta_ms_per_step'] > 0
    assert metrics['seam.commit_staged_ms_per_step'] > \
        metrics['seam.commit_columnar_ms_per_step']
    assert metrics['seam.untraced_ms_per_step'] > 0
    assert spans.spans_dropped() == 0

    # the bridge: each program span is in /host:CPU on the profiler's
    # clock, inside one of the driver's apply_changes_docs annotations
    calls = host['apply_changes_docs']
    for name in SPANS:
        assert host.get(name), f'{name} is not in the capture'
        for start, end in host[name]:
            assert any(lo <= start and end <= hi for lo, hi in calls), name

    test_benchmark.test_benchmark_json_names_units_and_keys()
