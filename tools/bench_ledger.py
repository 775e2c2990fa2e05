"""Bench ledger: the repo's perf trajectory as one append-only JSONL.

Every ``bench.py`` run appends ONE row to ``BENCH_LEDGER.jsonl`` at the
repo root (the ``regress`` section does it; ``BENCH_LEDGER=0``
disables, ``BENCH_LEDGER_PATH`` redirects). A row is self-describing:

    {"schema": 1, "ts": ..., "date": "YYYY-MM-DD",
     "source": "bench" | "backfill:BENCH_r07.json",
     "round": 7 | null, "git_sha": "...",
     "box": {"box_id", "cpus", "machine", "python", "platform",
             "device_kind", "n_devices"},   # the last three from JAX
     "metrics": {"seam_rate": 708847.0, ...},       # flat floats only
     "reps": {"seam_rate": [...]}}                  # per-rep samples,
                                                    # when recorded

``reps`` is what makes the regression gate noise-AWARE: thresholds in
``tools/perf_gate.py`` derive from recorded rep spread, never from a
single-run median (the measurement history's ±40% unpaired swings are
exactly why — BENCH_r07 notes).

Durability contract: ``append_row`` writes one line with a trailing
newline through a single buffered write+flush on an O_APPEND handle —
readers tolerate a TORN TAIL (a crash mid-append leaves a partial last
line, which ``read_rows`` skips and reports rather than dying on), so
the ledger never needs a rewrite cycle and two appenders never corrupt
each other's complete lines.

``backfill`` seeds the ledger from the historical ``BENCH_r*.json``
artifacts (all four generations of their schema), idempotently (a
source file already in the ledger is skipped). ``render_trajectory``
prints the per-round table + sparkline the ROADMAP's "no trajectory
tracking" complaint asks for.

stdlib only (numpy optional) — usable on a box with nothing installed.

Usage:
    python tools/bench_ledger.py --backfill [--ledger PATH]
    python tools/bench_ledger.py --render  [--ledger PATH]
"""

import glob
import hashlib
import json
import os
import platform as _platform
import re
import subprocess
import sys
import time

SCHEMA = 1
LEDGER_NAME = 'BENCH_LEDGER.jsonl'
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the headline + the per-section keys worth tracking across rounds
# (anything else in a row's metrics rides along untracked)
TRAJECTORY_KEYS = (
    'seam_rate', 'seam_commit_rate', 'host_rate',
    'service_clean_rps', 'slo_render_series_per_s',
    'storage_recovery_docs_per_s', 'query_materialize_docs_per_s',
    'shards_rps_4',
)


def default_ledger_path():
    return os.environ.get('BENCH_LEDGER_PATH') or \
        os.path.join(_ROOT, LEDGER_NAME)


def git_sha(root=_ROOT):
    try:
        out = subprocess.run(['git', 'rev-parse', '--short', 'HEAD'],
                             cwd=root, capture_output=True, text=True,
                             timeout=10)
        sha = out.stdout.strip()
        return sha or None
    except Exception:                               # noqa: BLE001
        return None


def box_fingerprint(device=None):
    """The box identity rows are grouped by: a same-box baseline means
    a same-fingerprint baseline (an 8-core replacement box must never
    be judged against this 2-core one's numbers, nor a chip run against
    a CPU run). `device` is the {'platform', 'device_kind', 'n_devices'}
    stamp the caller read from JAX (automerge_tpu.jaxenv.device_stamp);
    this module never guesses it — a caller that holds no backend gets
    platform None."""
    device = device or {}
    info = {
        'cpus': os.cpu_count(),
        'machine': _platform.machine(),
        'python': _platform.python_version(),
        'platform': device.get('platform'),
        'device_kind': device.get('device_kind'),
        'n_devices': device.get('n_devices'),
    }
    digest = hashlib.sha256(
        json.dumps(info, sort_keys=True).encode()).hexdigest()[:12]
    info['box_id'] = digest
    return info


def make_row(metrics, reps=None, source='bench', round_no=None,
             ts=None, date=None, box=None, sha=None, notes=None):
    """Assemble one schema-1 row. ``metrics`` is filtered to finite
    numbers; ``reps`` to lists of finite numbers."""
    clean = {}
    for key, value in (metrics or {}).items():
        if isinstance(value, bool) or not isinstance(value, (int, float)):
            continue
        if value != value or value in (float('inf'), float('-inf')):
            continue
        clean[str(key)] = float(value)
    row = {
        'schema': SCHEMA,
        'ts': float(ts if ts is not None else time.time()),
        'date': date or time.strftime('%Y-%m-%d'),
        'source': source,
        'round': round_no,
        'git_sha': sha if sha is not None else git_sha(),
        'box': box if box is not None else box_fingerprint(),
        'metrics': clean,
    }
    if reps:
        row['reps'] = {str(k): [float(x) for x in v]
                       for k, v in reps.items()
                       if v and all(isinstance(x, (int, float))
                                    and x == x for x in v)}
    if notes:
        row['notes'] = notes
    return row


def append_row(row, path=None):
    """Append one row as one JSONL line. Single write+flush on an
    append-mode handle: complete lines never interleave, and a crash
    mid-write leaves at most one torn tail line that ``read_rows``
    tolerates. Appending AFTER a torn tail first closes the partial
    line with a newline — the torn fragment then reads as one skipped
    corrupt line instead of corrupting the new row too."""
    path = path or default_ledger_path()
    line = json.dumps(row, sort_keys=True) + '\n'
    try:
        with open(path, 'rb') as f:
            f.seek(0, os.SEEK_END)
            if f.tell() > 0:
                f.seek(-1, os.SEEK_END)
                if f.read(1) != b'\n':
                    line = '\n' + line
    except OSError:
        pass
    with open(path, 'a') as f:
        f.write(line)
        f.flush()
        os.fsync(f.fileno())
    return path


def read_rows(path=None):
    """(rows, report) — every decodable row, oldest first. ``report``
    says what was skipped: ``torn_tail`` (the final line was partial —
    the documented crash-mid-append artifact) and ``corrupt`` (a
    non-final undecodable line, which should never happen and is
    therefore counted loudly rather than hidden)."""
    path = path or default_ledger_path()
    report = {'torn_tail': False, 'corrupt': 0}
    rows = []
    try:
        with open(path) as f:
            raw = f.read()
    except FileNotFoundError:
        return rows, report
    lines = raw.split('\n')
    ends_clean = raw.endswith('\n') or raw == ''
    if ends_clean and lines and lines[-1] == '':
        lines.pop()
    for i, line in enumerate(lines):
        if not line.strip():
            continue
        try:
            rows.append(json.loads(line))
        except json.JSONDecodeError:
            if i == len(lines) - 1 and not ends_clean:
                report['torn_tail'] = True
            else:
                report['corrupt'] += 1
    return rows, report


# ---- backfill from the historical BENCH_r*.json artifacts ------------------

def _flat_floats(d, out=None):
    """Flatten numeric leaves of a (possibly nested) dict; nested keys
    keep their LEAF name when unique, else 'parent_leaf'."""
    out = {} if out is None else out
    for key, value in d.items():
        if isinstance(value, dict):
            for k2, v2 in value.items():
                if isinstance(v2, (int, float)) and \
                        not isinstance(v2, bool):
                    name = k2 if k2 not in out else f'{key}_{k2}'
                    out[name] = float(v2)
        elif isinstance(value, (int, float)) and \
                not isinstance(value, bool):
            out.setdefault(key, float(value))
    return out


def _parse_bench_artifact(path):
    """One historical BENCH_r*.json -> (metrics, round_no, date). Four
    generations of artifact schema:
    - r01-r07: {'n', 'parsed': {'metric', 'value', ...}, ...}
    - r08/r11/r12: {'round', 'section', 'results': {...}, 'date'}
    - r09/r10: flat {'section', '<key>': float, ...}
    - r13: composite {'round', 'seam': {...}, 'seam_commit': {...}, ...}
    """
    with open(path) as f:
        data = json.load(f)
    name = os.path.basename(path)
    m = re.match(r'BENCH_r(\d+)', name)
    file_round = int(m.group(1)) if m else None
    metrics = {}
    round_no = data.get('round', data.get('n', file_round))
    date = data.get('date')
    if 'parsed' in data and isinstance(data['parsed'], dict):
        parsed = data['parsed']
        if isinstance(parsed.get('value'), (int, float)):
            # the e2e seam headline tracks as seam_rate; anything else
            # (round 1's kernel-only metric) keeps its own name — a
            # 13e9 kernel rate must not pollute the seam trajectory
            key = 'seam_rate' if parsed.get('metric') == \
                'changes_per_sec_backend_seam_e2e' else \
                str(parsed.get('metric') or 'value')
            metrics[key] = float(parsed['value'])
        for key in ('vs_baseline', 'seam_dispatches_per_round',
                    'init_dispatches', 'sync_dispatches_per_round'):
            if isinstance(parsed.get(key), (int, float)):
                metrics[key] = float(parsed[key])
    elif 'results' in data and isinstance(data['results'], dict):
        _flat_floats(data['results'], metrics)
    else:
        # flat section artifact or the composite shape: flatten numeric
        # leaves one level down (composite subsections keep leaf names)
        body = {k: v for k, v in data.items()
                if k not in ('round', 'issue', 'date', 'config', 'notes',
                             'headline')}
        _flat_floats(body, metrics)
        if isinstance(data.get('headline'), dict):
            v = data['headline'].get('seam_rate_changes_per_s')
            if isinstance(v, (int, float)):
                metrics.setdefault('seam_rate', float(v))
    return metrics, round_no, date


def backfill(path=None, root=_ROOT):
    """Append one row per historical BENCH_r*.json not already in the
    ledger (idempotent by source name). Returns the added sources."""
    path = path or default_ledger_path()
    rows, _ = read_rows(path)
    seen = {r.get('source') for r in rows}
    added = []
    for art in sorted(glob.glob(os.path.join(root, 'BENCH_r*.json'))):
        source = f'backfill:{os.path.basename(art)}'
        if source in seen:
            continue
        try:
            metrics, round_no, date = _parse_bench_artifact(art)
        except (OSError, json.JSONDecodeError) as exc:
            print(f'# skip {art}: {exc}', file=sys.stderr)
            continue
        if not metrics:
            print(f'# skip {art}: no numeric metrics', file=sys.stderr)
            continue
        ts = os.path.getmtime(art)
        append_row(make_row(metrics, source=source, round_no=round_no,
                            ts=ts, date=date or
                            time.strftime('%Y-%m-%d',
                                          time.localtime(ts)),
                            sha=None), path)
        added.append(source)
    return added


# ---- trajectory rendering --------------------------------------------------

_BARS = ' .:-=+*#%@'


def _spark(values):
    lo, hi = min(values), max(values)
    if hi <= lo:
        return _BARS[-1] * len(values)
    return ''.join(_BARS[min(int((v - lo) / (hi - lo) *
                                 (len(_BARS) - 1) + 0.5),
                             len(_BARS) - 1)] for v in values)


def render_trajectory(path=None, out=None,
                      keys=TRAJECTORY_KEYS):
    """Per-round table + sparkline over the tracked keys."""
    out = out if out is not None else sys.stdout
    rows, report = read_rows(path)
    if report['torn_tail']:
        print('# ledger has a torn tail line (crash mid-append) — '
              'skipped', file=out)
    if report['corrupt']:
        print(f'# ledger has {report["corrupt"]} corrupt line(s) — '
              f'skipped', file=out)
    if not rows:
        print('# ledger empty (run tools/bench_ledger.py --backfill, '
              'or bench.py regress)', file=out)
        return 0
    rows = sorted(rows, key=lambda r: (r.get('ts') or 0))
    print(f'# {len(rows)} ledger rows, '
          f'{rows[0].get("date")} .. {rows[-1].get("date")}', file=out)
    for key in keys:
        series = [(r.get('round'), r['metrics'][key], r.get('source'))
                  for r in rows if key in r.get('metrics', {})]
        if not series:
            continue
        values = [v for _, v, _ in series]
        newest = series[-1]
        print(f'{key:<32}{_spark(values)}  n={len(values)} '
              f'last={newest[1]:.4g} (round {newest[0]}) '
              f'min={min(values):.4g} max={max(values):.4g}', file=out)
    return 0


def main(argv):
    path = None
    do_backfill = do_render = False
    rest = list(argv)
    while rest:
        arg = rest.pop(0)
        if arg == '--ledger':
            path = rest.pop(0)
        elif arg == '--backfill':
            do_backfill = True
        elif arg == '--render':
            do_render = True
        else:
            print(__doc__.strip())
            return 2
    if not (do_backfill or do_render):
        do_render = True
    if do_backfill:
        added = backfill(path)
        print(f'# backfilled {len(added)} artifact(s): '
              f'{", ".join(a.split(":", 1)[1] for a in added) or "none"}')
    if do_render:
        render_trajectory(path)
    return 0


if __name__ == '__main__':
    sys.exit(main(sys.argv[1:]))
