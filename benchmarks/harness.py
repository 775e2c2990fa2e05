"""The benchmark's harness: one process, one cell, once.

``run_cell`` resolves a cell of ``BENCHMARK.json`` to its files by name
(configuration, traffic mix, driver, per-layer metric readers), takes the
chip, lets the driver set up and warm up (``setup_s``), measures for
``seconds``, reads the device's peak memory, runs the driver's audit (the
comparison that decides ``correct``) and returns the contract's result
object. Nothing here knows a cell, a mix or a metric by name: a later PR
adds files and entries and edits nothing.

A driver is ``benchmarks/drivers/<name>.py`` with four functions:

    setup(config, mix, seed) -> state     data, sessions, fleets
    warmup(state)                         every shape the window uses
    window(state, seconds, tracer) -> dict
        {'attempted', 'failed', 'metrics': {name: value},
         'facts': {...}}                  facts feed the metric readers
    audit(state) -> {number: (value, limit)}   correct iff value <= limit

A per-layer metric is ``benchmarks/metrics/<name>.py`` with ``read(ctx) ->
number or None`` (None: nothing to read, left out).
"""

import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)

RESULT_KEYS = ('correct', 'attempted', 'failed', 'metrics', 'device')


class BenchError(Exception):
    """The cell cannot be run as asked: a missing file, an unknown name,
    a device that is not in the table of peaks."""


def log(message):
    print(f'# {message}', file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# resolving a cell to its files
# ---------------------------------------------------------------------------

def load_json(path, what):
    if not os.path.isfile(path):
        raise BenchError(f'{what}: no file {path}')
    with open(path) as handle:
        return json.load(handle)


def load_module(path, what):
    if not os.path.isfile(path):
        raise BenchError(f'{what}: no file {path}')
    name = 'bench_' + os.path.basename(path)[:-3].replace('.', '_')
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def by_name(entries, name, what):
    for entry in entries:
        if entry['name'] == name:
            return entry
    known = ', '.join(e['name'] for e in entries)
    raise BenchError(f'unknown {what} {name!r}; BENCHMARK.json has: {known}')


def metrics_of(entries, cell):
    """The entries of one metric list that this cell reports: those that
    list the cell under ``workloads``, and those without the key, which
    every cell reports."""
    return [entry for entry in entries
            if cell in entry.get('workloads', (cell,))]


def resolve(workload, root=ROOT, bench_dir=BENCH_DIR):
    """Everything the run needs, found by the names in BENCHMARK.json."""
    bench = load_json(os.path.join(root, 'BENCHMARK.json'), 'benchmark')
    cell = by_name(bench['workloads'], workload, 'workload')
    config_entry = by_name(bench['configs'], cell['config'], 'config')
    config = load_json(os.path.join(root, config_entry['file']),
                       f"config {cell['config']}")
    mix = load_json(os.path.join(bench_dir, 'traffic',
                                 cell['traffic'] + '.json'),
                    f"traffic mix {cell['traffic']}")
    if 'driver' not in mix:
        raise BenchError(f"traffic mix {cell['traffic']} names no driver")
    driver = load_module(os.path.join(bench_dir, 'drivers',
                                      mix['driver'] + '.py'),
                         f"driver {mix['driver']}")
    end_to_end = metrics_of(bench['end_to_end'], workload)
    per_layer = metrics_of(bench['per_layer'], workload)
    readers = {e['name']: load_module(
        os.path.join(bench_dir, 'metrics', e['name'] + '.py'),
        f"per-layer metric {e['name']}") for e in per_layer}
    return {'bench': bench, 'cell': cell, 'config': config, 'mix': mix,
            'driver': driver, 'end_to_end': end_to_end,
            'per_layer': per_layer, 'readers': readers,
            'bench_dir': bench_dir}


def peaks_for(device_kind, bench_dir=BENCH_DIR):
    table = load_json(os.path.join(bench_dir, 'peaks.json'), 'peaks')
    if device_kind not in table['devices']:
        raise BenchError(
            f'device_kind {device_kind!r} is not in peaks.json '
            f"({', '.join(table['devices'])}): no default peak exists")
    return table['devices'][device_kind]


# ---------------------------------------------------------------------------
# observations: compilations, the profiler
# ---------------------------------------------------------------------------

class CompileCounter:
    """Counts what jit asks of the backend through jax.monitoring: one
    duration event per executable (a persistent-cache hit is a retrieval,
    not a compile) and the cache's hit/miss events. A copy of
    chip_smoke.CompileCounter."""

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


def log_spans(span_list, window_ns, top=14):
    """The program's spans inside the window, summed by name, to standard
    error: where the host's time went (for PERF.md, not a metric)."""
    if not window_ns:
        return
    totals = {}
    for span in span_list:
        if span['t0_ns'] >= window_ns[0] and span['t1_ns'] <= window_ns[1]:
            row = totals.setdefault(span['name'], [0, 0])
            row[0] += 1
            row[1] += span['dur_ns']
    for name, (count, ns) in sorted(totals.items(),
                                    key=lambda kv: -kv[1][1])[:top]:
        log(f'span {name}: {count} in the window, {ns / 1e6:.3f} ms')


class CollectorClock:
    """Wall seconds spent inside Python's cycle collector, through
    gc.callbacks. It watches; the collector is left as the program and a
    deployment leave it."""

    def __init__(self):
        self.seconds = 0.0
        self.collections = 0
        self.longest = 0.0
        self._t0 = 0.0

    def _on_gc(self, phase, _info):
        if phase == 'start':
            self._t0 = time.perf_counter()
        else:
            took = time.perf_counter() - self._t0
            self.seconds += took
            self.collections += 1
            self.longest = max(self.longest, took)

    def __enter__(self):
        gc.callbacks.append(self._on_gc)
        return self

    def __exit__(self, *_exc):
        gc.callbacks.remove(self._on_gc)
        return False


class Tracer:
    """Wraps the last ``trace_seconds`` of the window in the JAX profiler.
    The driver calls ``poll()`` at every step or tick boundary; the harness
    calls ``stop()`` once the window has closed. Off (``--trace 0``) both
    do nothing."""

    def __init__(self, enabled, trace_seconds):
        self.enabled = enabled
        self.trace_seconds = trace_seconds
        self.start_at = None          # set by arm()
        self.dir = None
        self.started = None
        self.stopped = None

    def arm(self, window_start, seconds):
        if self.enabled:
            self.start_at = window_start + max(
                0.0, seconds - self.trace_seconds)

    def poll(self):
        if self.start_at is None or self.dir is not None or \
                time.perf_counter() < self.start_at:
            return
        import jax
        self.dir = tempfile.mkdtemp(prefix='bench_trace_')
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self.started = time.perf_counter()

    def stop(self):
        """Ends the trace and returns (path of the .xplane.pb, window_s),
        or (None, None) when no trace was started."""
        if self.dir is None:
            return None, None
        import jax
        self.stopped = time.perf_counter()
        jax.profiler.stop_trace()
        for base, _dirs, files in os.walk(self.dir):
            for name in files:
                if name.endswith('.xplane.pb'):
                    return (os.path.join(base, name),
                            self.stopped - self.started)
        raise BenchError(f'the profiler wrote no .xplane.pb under {self.dir}')

    def cleanup(self):
        if self.dir is not None:
            shutil.rmtree(self.dir, ignore_errors=True)


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def traced_result(result, found, ctx, trace_path):
    """Fill a ``--trace 1`` result: reduce the trace, let every per-layer
    reader of the cell read, and keep the breakdown. What the readers do
    not turn into a metric goes to standard error, for PERF.md."""
    from trace_reduce import reduce_trace
    reduced = reduce_trace(trace_path, ctx['mix'].get('annotations', ()))
    ctx['trace'] = reduced
    for name, (count, secs) in sorted(
            reduced['modules'].items(), key=lambda kv: -kv[1][1])[:12]:
        log(f'device program {name}: {count} runs, {secs:.6f} s')
    log_spans(ctx['spans'], ctx['facts'].get('window_ns'))
    result['device']['busy_s'] = reduced['busy_s']
    result['device']['window_s'] = ctx['trace_window_s']
    for entry in found['per_layer']:
        value = found['readers'][entry['name']].read(ctx)
        if value is not None:
            result['metrics'][entry['name']] = {'value': value,
                                                'unit': entry['unit']}
    result['breakdown'] = {'device_ops': reduced['top_ops'][:10],
                           'idle_gaps': reduced['idle_gaps'][:10]}


def run_cell(workload, seed, seconds, trace, *, process_start=None,
             cpu=False, root=ROOT, bench_dir=BENCH_DIR, overrides=None):
    """Run one cell once and return the result object. ``cpu``
    and ``overrides`` (a dict laid over the configuration's sizes) exist
    for the benchmark's own tests, which rehearse the drivers at a tiny
    size; run.py passes neither."""
    process_start = time.perf_counter() if process_start is None \
        else process_start
    for path in (root, bench_dir):
        if path not in sys.path:
            sys.path.insert(0, path)
    found = resolve(workload, root, bench_dir)
    cell, mix, driver = found['cell'], found['mix'], found['driver']
    config = dict(found['config'])
    config.update(overrides or {})
    from automerge_tpu import jaxenv, native
    cache_dir = jaxenv.configure_compile_cache()
    counter = CompileCounter()
    counter.install()
    stamp = jaxenv.require_platform(cpu=cpu)
    if stamp['n_devices'] < cell['chips']:
        raise BenchError(f"cell {workload} needs {cell['chips']} chips; JAX "
                         f"reports {stamp['n_devices']}")
    if not native.available():
        raise BenchError(f'native codec unavailable: {native._load_error!r}')
    import jax
    devices = jax.devices()[:cell['chips']]
    peaks = None if cpu else peaks_for(stamp['device_kind'], bench_dir)
    log(f"platform {stamp['platform']} kind {stamp['device_kind']} "
        f"count {stamp['n_devices']} cache {cache_dir} "
        f'native threads {native.native_threads()}')

    from automerge_tpu.observability import perf, spans
    if trace:
        # the program's own spans and kernel ledger, for the readers
        spans.enable(capacity=1 << 17)
        perf.enable_ledger()

    state = driver.setup(config, mix, seed)
    driver.warmup(state)
    tracer = Tracer(bool(trace), float(mix.get('trace_seconds', 3.0)))
    compiles_before = counter.snapshot()
    window_start = time.perf_counter()
    setup_s = window_start - process_start
    tracer.arm(window_start, seconds)
    collector = CollectorClock()
    with collector:
        out = driver.window(state, seconds, tracer)
    trace_path, trace_window_s = tracer.stop()
    compiles = {k: v - compiles_before[k]
                for k, v in counter.snapshot().items()}
    memory_peak = max((d.memory_stats() or {}).get('peak_bytes_in_use', 0)
                      for d in devices)
    out['facts']['collector_s'] = collector.seconds
    log(f'set-up {setup_s:.3f} s, window {out["facts"].get("elapsed_s")} s, '
        f'in-window compilations {compiles["compilations"]} '
        f'(cache misses {compiles["cache_misses"]}), collector '
        f'{collector.seconds:.3f} s in {collector.collections} collections, '
        f'longest {collector.longest * 1e3:.1f} ms')

    device = {'platform': stamp['platform'], 'kind': stamp['device_kind'],
              'count': cell['chips'], 'memory_peak_bytes': memory_peak}
    result = {'correct': False, 'attempted': out['attempted'],
              'failed': out['failed'], 'metrics': {}, 'device': device}
    try:
        if trace:
            ctx = {'config': config, 'mix': mix, 'facts': out['facts'],
                   'trace_window_s': trace_window_s,
                   'spans': list(spans.iter_spans()),
                   'kernels': perf.kernel_snapshot(),
                   'compiles': compiles, 'peaks': peaks}
            traced_result(result, found, ctx, trace_path)
        else:
            values = dict(out['metrics'], setup_s=setup_s)
            for entry in found['end_to_end']:
                if entry['name'] not in values:
                    raise BenchError(
                        f"driver {mix['driver']} gave no {entry['name']}")
                result['metrics'][entry['name']] = {
                    'value': values[entry['name']], 'unit': entry['unit']}
    finally:
        tracer.cleanup()

    # the comparison that decides `correct`: after the window, after the
    # peak was read, outside setup_s
    audit_start = time.perf_counter()
    compared = driver.audit(state)
    log(f'audit {time.perf_counter() - audit_start:.3f} s')
    result['correct'] = all(value <= limit
                            for value, limit in compared.values())
    # comes last in the line: each number compared beside its limit
    result['compared'] = {name: {'value': value, 'limit': limit}
                          for name, (value, limit) in compared.items()}
    for name, (value, limit) in compared.items():
        print(f'compared {name}: {value} (limit {limit})', file=sys.stderr)
    sys.stderr.flush()
    return result


def main(argv, process_start):
    import argparse
    parser = argparse.ArgumentParser(
        description='Run one cell of BENCHMARK.json once.')
    parser.add_argument('--workload', required=True)
    parser.add_argument('--seed', type=int, required=True)
    parser.add_argument('--seconds', type=float, required=True)
    parser.add_argument('--trace', type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          args.trace, process_start=process_start)
    except BenchError as exc:
        print(f'benchmark: {exc}', file=sys.stderr)
        return 2
    # the last stdout line: the contract's one JSON object
    print(json.dumps(result), flush=True)
    return 0
