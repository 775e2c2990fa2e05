"""Phase-attribution report from a host-span Chrome trace (or a flight
recorder forensic dump), and the cross-peer trace stitcher.

Usage:
    python tools/obs_report.py traces/obs_host_trace.json
    python tools/obs_report.py --flight flight-quarantine-1.json
    python tools/obs_report.py --flight after.json before.json
    python tools/obs_report.py --stitch peer_a.json peer_b.json \\
                               [-o stitched_trace.json]
    python tools/obs_report.py --stitch shard0=a.json shard1=b.json
    python tools/obs_report.py --metrics metrics_snapshot.prom
    python tools/archlint.py --check --json - | \\
                               python tools/obs_report.py --archlint -
    python tools/obs_report.py --floor kernel_ledger.json [trace.json]
    python tools/obs_report.py --control control_ledger.json [--json]
    python tools/obs_report.py --control flight-quarantine-1.json

Floor mode renders the RESIDUAL-FLOOR table the ROADMAP used to carry
as a hand-measured note: per device-kernel-kind dispatch counts,
blocking wall time, and XLA cost_analysis (flops / bytes accessed /
achieved GB/s) from a ``perf.dump_ledger`` JSON, beside the host
phases of an optional span trace — so "native parse vs scatter
dispatch vs host phases" reads from live data
(``observability.perf.instrument_kernel`` wraps every jitted entry
point; ``perf.dump_ledger`` writes the ledger dump, and
``traces/kernel_ledger.json`` is a recorded one).

Metrics mode reads a Prometheus exposition page (a MetricsExporter
``write_snapshot`` file or a curl'd /metrics body) and surfaces the
shard-labeled operational counters — per-shard slipped ticks (the
tick-overrun telemetry: which failure domain's pump does not fit the
serving cadence) and pump seconds — plus any non-zero health counters.

Trace mode reads the Chrome trace-event JSON that
``observability.export_chrome_trace`` writes (a bare event list or a
``{"traceEvents": [...]}`` wrapper — the same shapes Perfetto accepts)
and renders, per span name: call count, total/mean/max milliseconds,
share of the trace's wall-clock, and SELF time — the span's duration
less what its child spans cover (``spans.self_times`` over the ``id`` /
``parent`` the export carries in ``args``) — the per-phase merge-cost
breakdown the ROADMAP's parse/merge-overlap work needs (cf. the
differential-merge phase analysis in PAPERS.md "Fast Updates on
Read-Optimized Databases"). Spans nest (native_parse inside turbo_parse,
gate.general inside turbo_gate inside apply_batch), so the wall column
counts a millisecond once per level; the self column counts it once, in
the narrowest span that held it, and sums to the traced wall of each
thread. A collection is a ``gc`` span under the phase it interrupted.
The last column, ``thread_cpu ms``, is the spans' own ``thread_cpu_ns``
(what their thread ran; ``-`` where the export carries none): "cpu ms"
is summed durations and says nothing about the CPU.

Flight mode pretty-prints a forensic dump: trigger, per-doc errors
(slot, durable id, stage, typed error), then the surrounding event ring.
With a second (baseline) dump, the health counters print as the DELTA
between the two dumps — the counter twin of the histogram delta, so two
forensic snapshots bracket an incident.

Control mode renders the control plane's why-did-it-act timeline from
a ``Controller.dump_decisions`` ledger or a flight dump's
control_decision events: per decision the tick, policy/action/target,
direction, applied/shadow/refused flag, the input signal snapshot that
justified it, and the trace ids of affected in-flight requests —
reversals flagged inline. ``--json`` keeps stdout a single
machine-readable JSON object (the ``--archlint -`` pipe discipline),
and ``-`` reads either payload shape from stdin.

Stitch mode merges span exports from MULTIPLE peers — Chrome traces
(export_chrome_trace) or flight dumps (their ``recent_spans``) — into
ONE Perfetto-loadable trace: each input renders as its own named
process, each file's clock is rebased to its own start (perf_counter
epochs do not align across processes), and spans that share a
``trace``/``links`` id are reported so a request minted on one peer can
be followed into the other peer's generate/receive span tree. Inputs
may be ``shard0=path.json`` to label each process track with its shard
id, and any input whose span ring wrapped (a restarted shard exports a
partial window) has its truncation DISCLOSED in the report — trace ids
stay continuous across the gap, so a failover still stitches.

stdlib only — usable on a box with nothing else installed (the counter
delta and the self-time helpers are loaded straight from
automerge_tpu/observability/metrics.py and spans.py by file path, which
keeps one implementation without importing the package).
"""

import importlib
import json
import os
import sys
import types


def _obs_mod(name):
    """observability/<name>.py (metrics, spans) loaded from its directory
    under a stand-in package, so that the module's relative imports
    resolve without running the package's __init__ and its import chain
    (stdlib importlib only): one counts_delta, one self_times."""
    if '_obs' not in sys.modules:
        package = types.ModuleType('_obs')
        package.__path__ = [os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), 'automerge_tpu', 'observability')]
        sys.modules['_obs'] = package
    return importlib.import_module('_obs.' + name)


def load_events(path, phases=('X',)):
    with open(path) as f:
        data = json.load(f)
    if isinstance(data, dict):
        if 'traceEvents' in data:
            data = data.get('traceEvents', [])
        elif 'recent_spans' in data:
            # a flight dump: its span tail in iter_spans() shape
            data = [{'ph': 'X', 'name': s['name'],
                     'ts': s['t0_ns'] / 1000.0,
                     'dur': s['dur_ns'] / 1000.0,
                     'tid': s.get('tid', 0) % 1_000_000,
                     'args': dict(s.get('attrs') or {}, id=s.get('id'),
                                  parent=s.get('parent'),
                                  thread_cpu_ns=s.get('thread_cpu_ns'))}
                    for s in data['recent_spans']]
        else:
            data = []
    return [e for e in data if e.get('ph') in phases]


def _union(intervals):
    """Total µs covered by the union of (lo, hi) intervals."""
    total = 0.0
    cur_lo = cur_hi = None
    for lo, hi in sorted(intervals):
        if cur_hi is None or lo > cur_hi:
            if cur_hi is not None:
                total += cur_hi - cur_lo
            cur_lo, cur_hi = lo, hi
        else:
            cur_hi = max(cur_hi, hi)
    if cur_hi is not None:
        total += cur_hi - cur_lo
    return total


def attribution(events):
    """Per-name rollup: count, cpu (summed durations), wall (union of the
    name's intervals — with the multi-core parse, spans of one name run
    CONCURRENTLY on pool workers, so cpu > wall measures parallelism),
    mean/max duration (µs), wall share, self (summed durations less what
    each span's children cover; an export without span ids has no tree,
    and every span is then its own leaf). Returns (rows sorted by cpu
    desc, wall_us)."""
    stats = {}
    ivs = {}
    lo, hi = None, None
    tree = []
    for k, e in enumerate(events, 1):
        args = e.get('args') or {}
        ts, dur = float(e.get('ts', 0.0)), float(e.get('dur', 0.0))
        # an event without a span id (an older export) gets one of its own
        tree.append({'id': args.get('id') or -k, 'parent': args.get('parent'),
                     't0_ns': ts * 1000.0, 't1_ns': (ts + dur) * 1000.0})
    self_ns = _obs_mod('spans').self_times(tree)
    for e, node in zip(events, tree):
        name = e.get('name', '?')
        dur = float(e.get('dur', 0.0))
        ts = float(e.get('ts', 0.0))
        ent = stats.setdefault(name, [0, 0.0, 0.0, 0.0])
        ent[0] += 1
        ent[1] += dur
        if dur > ent[2]:
            ent[2] = dur
        ent[3] += self_ns[node['id']] / 1000.0
        ivs.setdefault(name, []).append((ts, ts + dur))
        lo = ts if lo is None else min(lo, ts)
        hi = ts + dur if hi is None else max(hi, ts + dur)
    wall = (hi - lo) if events else 0.0
    # % wall from the UNION, not the cpu sum: concurrent same-name spans
    # (pool workers) would otherwise print shares past 100%
    rows = [(name, n, tot, _union(ivs[name]), tot / n, mx,
             (100.0 * _union(ivs[name]) / wall) if wall else 0.0, own)
            for name, (n, tot, mx, own) in stats.items()]
    rows.sort(key=lambda r: -r[2])
    return rows, wall


def thread_cpu_ms(events):
    """{name: ms} of the spans' own `thread_cpu_ns` (what each span's
    thread RAN inside it, spans.py), over the events that carry one. Not
    the table's "cpu ms", which is summed DURATIONS: a span that slept,
    or whose thread lost the core, is long there and short here."""
    out = {}
    for e in events:
        ns = (e.get('args') or {}).get('thread_cpu_ns')
        if ns is not None:
            name = e.get('name', '?')
            out[name] = out.get(name, 0.0) + ns / 1e6
    return out


def render_trace(path, out=None):
    events = load_events(path)
    rows, wall = attribution(events)
    ran = thread_cpu_ms(events)
    print(f'# {path}: {len(events)} spans, wall {wall / 1000.0:.2f} ms',
          file=out)
    print(f'{"phase":<24}{"calls":>7}{"cpu ms":>10}{"wall ms":>10}'
          f'{"par":>6}{"mean ms":>10}{"max ms":>10}{"% wall":>8}'
          f'{"self ms":>10}{"% self":>8}{"thread_cpu ms":>15}', file=out)
    for name, n, tot, wall_n, mean, mx, pct, own in rows:
        par = tot / wall_n if wall_n else 1.0
        on_cpu = f'{ran[name]:.3f}' if name in ran else '-'
        print(f'{name:<24}{n:>7}{tot / 1000.0:>10.3f}'
              f'{wall_n / 1000.0:>10.3f}{par:>6.2f}'
              f'{mean / 1000.0:>10.3f}{mx / 1000.0:>10.3f}{pct:>8.1f}'
              f'{own / 1000.0:>10.3f}'
              f'{100.0 * own / wall if wall else 0.0:>8.1f}'
              f'{on_cpu:>15}', file=out)
    # Pool view: per-slice parse spans carry worker/chunk attrs; cpu/wall
    # over them is the measured pool parallelism, and occupancy relates
    # that to the configured lane count when the spans recorded it.
    chunk = [e for e in events if e.get('name') == 'parse_chunk']
    if chunk:
        cpu = sum(float(e.get('dur', 0.0)) for e in chunk)
        wall_c = _union([(float(e['ts']), float(e['ts']) + float(e['dur']))
                         for e in chunk])
        workers = {(e.get('args') or {}).get('worker') for e in chunk}
        lanes = [e for e in events if e.get('name') == 'native_parse']
        threads = max(((e.get('args') or {}).get('threads') or 0)
                      for e in lanes) if lanes else len(workers)
        occ = (100.0 * cpu / (wall_c * threads)) if wall_c and threads \
            else 0.0
        print(f'# parse pool: {len(chunk)} slices over {len(workers)} '
              f'workers, cpu {cpu / 1000.0:.3f} ms / wall '
              f'{wall_c / 1000.0:.3f} ms = {cpu / wall_c if wall_c else 1:.2f}x '
              f'parallel, occupancy {occ:.0f}% of {threads} lanes', file=out)
    return rows


def _event_trace_ids(event):
    """Trace ids an event references: its own ``trace`` attr plus any
    batch-span ``links`` (the fused-dispatch -> member-request edges)."""
    args = event.get('args') or {}
    ids = set()
    if args.get('trace'):
        ids.add(args['trace'])
    for link in args.get('links') or ():
        ids.add(link)
    return ids


def _split_labeled(arg):
    """A stitch input may be ``shardname=path`` (the shard label a
    multi-shard deployment names its exports by) or a bare path (the
    basename then labels the process). Only treat ``lhs=`` as a label
    when the whole arg isn't itself an existing file (paths may contain
    '=')."""
    if '=' in arg and not os.path.exists(arg):
        label, _, path = arg.partition('=')
        if label and path:
            return label, path
    return None, arg


def stitch(paths, out_path=None):
    """Merge multiple peers' span exports into one Perfetto trace (see
    the module docstring). Each input may be ``shard=path`` to label its
    process track. Returns (events, shared_trace_ids, truncated) where
    shared ids appear in MORE than one input — the stitched requests —
    and truncated maps labels whose span ring wrapped (a restarted or
    long-lived shard) to their dropped-span counts: the window loss is
    DISCLOSED, and trace ids still correlate across the gap (they ride
    the surviving spans, not the ring indices)."""
    events = []
    ids_by_file = []
    truncated = {}
    seen_labels = set()
    for pid, arg in enumerate(paths, start=1):
        label, path = _split_labeled(arg)
        if label is None:
            label = os.path.basename(path)
        if label in seen_labels:
            # two unlabeled inputs sharing a basename must not merge
            # their process tracks or truncation disclosures
            label = f'{label}#{pid}'
        seen_labels.add(label)
        file_events = load_events(path, phases=('X', 'I'))
        t0 = min((float(e.get('ts', 0.0)) for e in file_events),
                 default=0.0)
        ids = set()
        events.append({'ph': 'M', 'name': 'process_name', 'pid': pid,
                       'tid': 0, 'args': {'name': label}})
        for e in file_events:
            e = dict(e)
            e['pid'] = pid
            # each process's perf_counter epoch is private: rebase every
            # file to its own start so the peers render side by side
            # (cross-host clocks cannot be aligned; the trace ids are
            # the correlation, not the timestamps)
            e['ts'] = float(e.get('ts', 0.0)) - t0
            e.setdefault('tid', 0)
            if e.get('ph') == 'I' and e.get('name') == 'spans_dropped':
                # the export's in-band truncation marker: this ring
                # wrapped (or was restarted) and older spans are gone
                truncated[label] = truncated.get(label, 0) + \
                    int((e.get('args') or {}).get('dropped', 0))
            events.append(e)
            ids |= _event_trace_ids(e)
        ids_by_file.append(ids)
    shared = set()
    for i, ids in enumerate(ids_by_file):
        for other in ids_by_file[i + 1:]:
            shared |= ids & other
    if out_path is not None:
        with open(out_path, 'w') as f:
            json.dump({'traceEvents': events, 'displayTimeUnit': 'ms'},
                      f)
    return events, shared, truncated


def render_stitch(paths, out_path, out=None):
    events, shared, truncated = stitch(paths, out_path)
    spans = [e for e in events if e.get('ph') == 'X']
    print(f'# stitched {len(paths)} peers: {len(spans)} spans'
          f'{" -> " + out_path if out_path else ""}', file=out)
    for label, dropped in sorted(truncated.items()):
        print(f'# shard {label}: span ring truncated ({dropped} older '
              f'spans dropped) — window is partial; trace ids remain '
              f'continuous across the gap', file=out)
    by_trace = {}
    for e in spans:
        for tid in _event_trace_ids(e) & shared:
            by_trace.setdefault(tid, []).append(e)
    for trace_id in sorted(shared):
        rows = by_trace.get(trace_id, [])
        peers = sorted({e['pid'] for e in rows})
        names = sorted({e.get('name', '?') for e in rows})
        print(f'# trace {trace_id}: {len(rows)} spans across peers '
              f'{peers} ({", ".join(names)})', file=out)
    if not shared:
        print('# no trace ids shared across inputs (were the messages '
              'enveloped? generate with trace_ctx=...)', file=out)
    return shared


def render_flight(path, baseline=None, out=None):
    with open(path) as f:
        report = json.load(f)
    print(f'# flight record: trigger={report.get("trigger")!r} '
          f'seq={report.get("seq")}', file=out)
    detail = report.get('detail') or {}
    for err in detail.get('errors', []):
        print(f'  doc {err.get("doc")} (durable id '
              f'{err.get("durable_id")}): {err.get("error")} at stage '
              f'{err.get("stage")!r} — {err.get("message")}', file=out)
    for key in ('torn_tail_bytes', 'rotted_records', 'global_max'):
        if detail.get(key):
            print(f'  {key}: {detail[key]}', file=out)
    events = report.get('events', [])
    print(f'# surrounding events ({len(events)}):', file=out)
    for ev in events:
        kind = ev.get('kind')
        rest = {k: v for k, v in ev.items() if k not in ('kind', 'ts_ns')}
        print(f'  [{kind}] {rest}', file=out)
    spans = report.get('recent_spans', [])
    if spans:
        print(f'# phase timeline around the fault ({len(spans)} spans):',
              file=out)
        for s in spans:
            extra = f' {s["attrs"]}' if s.get('attrs') else ''
            err = f' ERROR={s["error"]}' if s.get('error') else ''
            print(f'  {s["name"]:<22}{s["dur_ns"] / 1e6:9.3f} ms'
                  f'{extra}{err}', file=out)
    health = report.get('health') or {}
    if baseline is not None:
        with open(baseline) as f:
            base_health = json.load(f).get('health') or {}
        moved = {k: v for k, v in _obs_mod('metrics').counts_delta(
            health, base_health).items() if v}
        if moved:
            print(f'# health counters moved since {baseline}: {moved}',
                  file=out)
    else:
        moved = {k: v for k, v in health.items() if v}
        if moved:
            print(f'# health counters at dump: {moved}', file=out)
    return report


def render_metrics(path, out=None):
    """Pretty-print a Prometheus exposition page (a MetricsExporter
    ``write_snapshot`` file, or anything curl'd from /metrics): the
    shard-labeled operational counters first — per-shard slipped ticks
    (tick-overrun telemetry) and pump seconds — then the health-counter
    roll-up, so a shard deployment's cadence health reads at a glance
    without a Prometheus server in the loop."""
    with open(path) as f:
        lines = [ln for ln in f.read().splitlines()
                 if ln and not ln.startswith('#')]
    slips, pumps, health = [], [], []
    for ln in lines:
        name = ln.split('{', 1)[0].split(' ', 1)[0]
        if name.endswith('shard_ticks_slipped_total'):
            slips.append(ln)
        elif name.endswith('shard_pump_seconds'):
            pumps.append(ln)
        elif name.endswith('health_total'):
            health.append(ln)
    if slips:
        print('# per-shard slipped ticks (pump overran the serving '
              'cadence):', file=out)
        for ln in slips:
            print(f'  {ln}', file=out)
    if pumps:
        print('# per-shard last pump seconds:', file=out)
        for ln in pumps:
            print(f'  {ln}', file=out)
    moved = [ln for ln in health if not ln.rstrip().endswith(' 0')]
    if moved:
        print('# health counters (non-zero):', file=out)
        for ln in moved:
            print(f'  {ln}', file=out)
    if not (slips or pumps or moved):
        print('# no shard telemetry or non-zero health counters in '
              f'{path}', file=out)
    return 0


def render_archlint(path, out=None):
    """Pretty-print an ``archlint --json`` payload (file or ``-`` for
    stdin): the per-rule violation/suppression roll-up, every violation
    with its file:line, and the justified suppressions — the static-
    contract counterpart of the runtime health-counter report."""
    if path == '-':
        data = json.load(sys.stdin)
    else:
        with open(path) as f:
            data = json.load(f)
    if data.get('version') != 1:
        print(f'unsupported archlint payload version '
              f'{data.get("version")!r}', file=sys.stderr)
        return 2
    per_rule = {}
    for f in data.get('findings', []):
        bucket = 'suppressed' if f.get('suppressed') else 'violations'
        per_rule.setdefault(f['rule'], {'violations': 0,
                                        'suppressed': 0})[bucket] += 1
    print(f'# archlint over {data.get("files")} files: '
          f'{data.get("violations")} violations, '
          f'{data.get("suppressed")} suppressed '
          f'({data.get("unlisted")} unlisted, '
          f'{len(data.get("stale", []))} stale baseline entries)',
          file=out)
    for rule in data.get('rules', []):
        rid = rule['id']
        counts = per_rule.get(rid, {'violations': 0, 'suppressed': 0})
        print(f'  {rid:20s} {counts["violations"]:3d} violations  '
              f'{counts["suppressed"]:3d} suppressed', file=out)
    for f in data.get('findings', []):
        if not f.get('suppressed'):
            print(f'  VIOLATION {f["path"]}:{f["line"]}: [{f["rule"]}] '
                  f'{f["message"]}', file=out)
    for f in data.get('findings', []):
        if f.get('suppressed'):
            print(f'  suppressed {f["path"]}:{f["line"]} [{f["rule"]}]: '
                  f'{f.get("justification")}', file=out)
    for e in data.get('stale', []):
        print(f'  STALE baseline entry {e.get("fingerprint")} '
              f'[{e.get("rule")}] {e.get("path")}', file=out)
    for e in data.get('errors', []):
        print(f'  UNPARSEABLE {e.get("path")}: {e.get("message")}',
              file=out)
    return 0 if not (data.get('violations') or data.get('unlisted') or
                     data.get('stale') or data.get('errors')) else 1


def _control_payload(path):
    """Load a ``--control`` input: a ``Controller.dump_decisions``
    ledger, a flight-recorder dump (its control_decision events), or
    ``-`` for either on stdin. Returns (decisions, gauges, mode)."""
    if path == '-':
        data = json.load(sys.stdin)
    else:
        with open(path) as f:
            data = json.load(f)
    if data.get('kind') == 'control_ledger':
        return (data.get('decisions', []), data.get('gauges', {}),
                data.get('mode'))
    if 'events' in data:         # a dump_flight_record forensic report
        decisions = [e for e in data['events']
                     if e.get('kind') == 'control_decision']
        return decisions, {}, None
    raise ValueError(
        f'{path}: neither a control ledger (kind=control_ledger) nor '
        f'a flight dump (events=[...])')


def render_control(path, json_out=False, out=None):
    """The why-did-it-act timeline: every control-plane decision with
    the signal snapshot that justified it and the trace ids of the
    in-flight requests it touched. With ``json_out`` the report is a
    single machine-readable JSON object on stdout (the ``--archlint
    --json -`` pipe discipline: nothing else lands on stdout)."""
    try:
        decisions, gauges, mode = _control_payload(path)
    except (ValueError, KeyError) as exc:
        print(f'unsupported --control payload: {exc}', file=sys.stderr)
        return 2
    per_policy = {}
    reversals = {}
    applied = shadow = 0
    for d in decisions:
        key = (d.get('policy', '?'), d.get('action', '?'))
        per_policy[key] = per_policy.get(key, 0) + 1
        if d.get('reversal'):
            pol = d.get('policy', '?')
            reversals[pol] = reversals.get(pol, 0) + 1
        if d.get('mode') == 'shadow':
            shadow += 1
        elif d.get('applied'):
            applied += 1
    if json_out:
        report = {'kind': 'control_report', 'mode': mode,
                  'decisions': len(decisions), 'applied': applied,
                  'shadow': shadow,
                  'per_policy': {f'{p}/{a}': n
                                 for (p, a), n in sorted(per_policy.items())},
                  'reversals': reversals, 'gauges': gauges,
                  'timeline': decisions}
        json.dump(report, sys.stdout, indent=1, default=repr)
        sys.stdout.write('\n')
        return 0 if decisions or gauges else 1
    out = out if out is not None else sys.stdout
    mode_s = f' mode={mode}' if mode else ''
    print(f'# control plane: {len(decisions)} decisions'
          f' ({applied} applied, {shadow} shadow,'
          f' {sum(reversals.values())} reversals){mode_s}', file=out)
    for (pol, act), n in sorted(per_policy.items()):
        rev = reversals.get(pol, 0)
        print(f'  {pol:<16} {act:<16} {n:3d} decisions'
              + (f'  {rev} reversals' if rev else ''), file=out)
    if gauges:
        print(f'# windows={gauges.get("windows")} '
              f'ticks={gauges.get("ticks")} '
              f'last_decision_tick={gauges.get("last_decision_tick")} '
              f'decide_s_last={gauges.get("decide_s_last", 0):.6f} '
              f'decide_s_max={gauges.get("decide_s_max", 0):.6f}',
              file=out)
        active = gauges.get('active') or {}
        for key, value in sorted(active.items(), key=repr):
            print(f'  active {key}: {value}', file=out)
    if decisions:
        print('# timeline (oldest first):', file=out)
    for d in decisions:
        flags = []
        if d.get('mode') == 'shadow':
            flags.append('SHADOW')
        elif d.get('applied'):
            flags.append('applied')
        else:
            flags.append('REFUSED')
        if d.get('reversal'):
            flags.append('REVERSAL')
        head = (f'  tick {d.get("tick", "?"):>6} '
                f'{d.get("policy", "?")}/{d.get("action", "?")} '
                f'{d.get("target", "")} '
                f'dir={d.get("direction", "")} [{" ".join(flags)}]')
        print(head, file=out)
        if d.get('detail'):
            print(f'    why: {d["detail"]}', file=out)
        sig = d.get('signals') or {}
        adm = sig.get('admission') or {}
        bits = []
        if adm:
            bits.append(f'reject_frac={adm.get("reject_frac", 0):.3f} '
                        f'queue={adm.get("queue_pressure", 0):.3f}')
        ten = sig.get('tenant') or {}
        if ten:
            bits.append(f'tenant admitted_d={ten.get("admitted_d")} '
                        f'throttled_d={ten.get("throttled_d")} '
                        f'rate={ten.get("rate")}')
        wm = (sig.get('watermark') or {}).get('pressure')
        if wm is not None:
            bits.append(f'watermark={wm:.3f}')
        if 'pump_mean_s' in sig:
            bits.append(f'pump_mean_s={sig["pump_mean_s"]:.6f} '
                        f'misplaced={len(sig.get("misplaced", ()))}')
        if bits:
            print(f'    signals: {"; ".join(bits)}', file=out)
        traces = d.get('traces') or []
        if traces:
            print(f'    traces: {", ".join(str(t) for t in traces)}',
                  file=out)
    if not decisions:
        print('# no control decisions in the window '
              '(a quiet controller is a converged controller)', file=out)
    return 0


def render_floor(ledger_path, trace_path=None, out=None):
    """The residual-floor table: device kernels (cost ledger) and,
    when a trace is given, the host phases they compete with."""
    out = out if out is not None else sys.stdout
    with open(ledger_path) as f:
        dump = json.load(f)
    kernels = dump.get('kernels', {})
    print(f'# device-kernel cost ledger ({ledger_path}):', file=out)
    if not kernels:
        print('  (no dispatches recorded — was the ledger enabled? '
              'perf.enable_ledger() / enable_observatory())', file=out)
    else:
        # "host ms" = host-blocking wall (execution on the sync CPU
        # backend; enqueue time on async devices — perf.py caveat)
        print(f'  {"kernel":<30}{"disp":>6}{"host ms":>10}'
              f'{"ms/disp":>9}{"MFLOP":>9}{"MB acc":>9}{"GB/s":>7}',
              file=out)
        rows = sorted(kernels.items(),
                      key=lambda kv: -kv[1].get('seconds', 0.0))
        for kind, row in rows:
            disp = row.get('dispatches', 0)
            wall = row.get('seconds', 0.0) * 1000.0
            flops = row.get('flops_total')
            acc = row.get('bytes_accessed_total')
            gbs = row.get('gbytes_per_s')
            print(f'  {kind:<30}{disp:>6}{wall:>10.2f}'
                  f'{wall / max(disp, 1):>9.3f}'
                  f'{(flops or 0) / 1e6:>9.2f}'
                  f'{(acc or 0) / 1e6:>9.2f}'
                  f'{gbs if gbs is not None else 0:>7.2f}', file=out)
        errors = [(kind, sig['cost']['error'])
                  for kind, row in kernels.items()
                  for sig in row.get('signatures', ())
                  if 'error' in (sig.get('cost') or {})]
        for kind, err in errors:
            print(f'  # {kind}: cost_analysis unavailable ({err})',
                  file=out)
    if trace_path:
        print(f'# host phases beside them ({trace_path}):', file=out)
        events = load_events(trace_path)
        rows, wall = attribution(events)
        for name, n, tot, wall_n, mean, mx, pct, own in rows[:12]:
            print(f'  {name:<30}{n:>6}{tot / 1000.0:>10.2f} ms cpu '
                  f'({pct:>5.1f}% of wall), {own / 1000.0:.2f} ms self',
                  file=out)
    mem = dump.get('watermarks')
    if mem:
        print('# memory watermarks (bytes, current / high):', file=out)
        for tier in sorted(mem.get('current', {})):
            cur = mem['current'][tier]
            high = mem.get('high', {}).get(tier, cur)
            print(f'  {tier:<30}{cur:>14,} / {high:,}', file=out)
    return 0


def main(argv):
    if not argv or argv[0] in ('-h', '--help'):
        print(__doc__.strip())
        return 2
    if argv[0] == '--floor':
        if len(argv) < 2:
            print('--floor needs a kernel-ledger JSON path '
                  '(perf.dump_ledger)',
                  file=sys.stderr)
            return 2
        return render_floor(argv[1], argv[2] if len(argv) > 2 else None)
    if argv[0] == '--archlint':
        if len(argv) < 2:
            print('--archlint needs an `archlint --json` payload path '
                  '(or - for stdin)', file=sys.stderr)
            return 2
        return render_archlint(argv[1])
    if argv[0] == '--control':
        rest = [a for a in argv[1:] if a != '--json']
        json_out = '--json' in argv[1:]
        if not rest:
            print('--control needs a control-ledger JSON '
                  '(Controller.dump_decisions), a flight dump, '
                  'or - for stdin', file=sys.stderr)
            return 2
        return render_control(rest[0], json_out=json_out)
    if argv[0] == '--metrics':
        if len(argv) < 2:
            print('--metrics needs an exposition-file path',
                  file=sys.stderr)
            return 2
        return render_metrics(argv[1])
    if argv[0] == '--flight':
        if len(argv) < 2:
            print('--flight needs a dump path', file=sys.stderr)
            return 2
        render_flight(argv[1], baseline=argv[2] if len(argv) > 2 else None)
        return 0
    if argv[0] == '--stitch':
        paths = []
        out_path = 'stitched_trace.json'
        rest = argv[1:]
        while rest:
            arg = rest.pop(0)
            if arg == '-o':
                if not rest:
                    print('-o needs a path', file=sys.stderr)
                    return 2
                out_path = rest.pop(0)
            else:
                paths.append(arg)
        if len(paths) < 2:
            print('--stitch needs at least two exports', file=sys.stderr)
            return 2
        render_stitch(paths, out_path)
        return 0
    render_trace(argv[0])
    return 0


if __name__ == '__main__':
    try:
        sys.exit(main(sys.argv[1:]))
    except BrokenPipeError:      # | head
        os_devnull = open('/dev/null', 'w')
        sys.stdout = os_devnull
        sys.exit(0)
