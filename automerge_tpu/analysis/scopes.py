"""Which contract applies where. Paths are repo-relative posix.

The scope tables are deliberately explicit rather than clever: a rule
that silently widens its own scope is how a linter starts crying wolf,
and one that silently narrows is how it stops catching anything. Every
entry names the PR-learned reason it is (or is not) in scope.
"""

import re

ANALYSIS_PREFIX = 'automerge_tpu/analysis/'


def in_package(path):
    return path.startswith('automerge_tpu/') and \
        not path.startswith(ANALYSIS_PREFIX)


def lintable(path):
    """Everything the tree-wide checks (except-pass, message-matching,
    counter discipline) cover: the package and the tools."""
    return in_package(path) or path.startswith('tools/')


# --- typed-errors -----------------------------------------------------------
# The funnel modules hold the reference decoder's internal raise style
# (hundreds of intentional bare ValueErrors, converted at the guarded
# entry points); their boundary discipline is enforced DYNAMICALLY by
# tools/fuzz_wire.py, so the static rule exempts them and watches every
# other module's decode-named surface.
FUNNEL_MODULES = frozenset({
    'automerge_tpu/columnar.py',
    'automerge_tpu/encoding.py',
})

# Public functions with these name shapes are decode surfaces: hostile
# bytes (wire, disk, cursor) reach them, so only automerge_tpu.errors
# classes may escape. encode_/generate_/receive_/ingest_ names are NOT
# here on purpose: encode direction never sees hostile bytes, and the
# receive/ingest surfaces raise API-misuse errors (array-shape guards,
# fallback-routing signals) that are caller bugs, not wire corruption.
DECODE_NAME_RE = re.compile(
    r'^(decode_|parse_|read_|split_|inflate)')


def typed_raise_scope(path):
    return in_package(path) and path not in FUNNEL_MODULES


# --- kernel-ledger ----------------------------------------------------------
def kernel_scope(path):
    return in_package(path)


# Host-path modules where a `jnp.` dispatch inside a per-document loop
# breaks the O(1)-dispatch contract (round 6/16: one fused dispatch per
# batch, never one per doc). The iterable-name heuristic below keeps the
# legitimate bounded loops out: loader.py/backend.py iterate per
# SEQUENCE-CLASS pool (`self.seq_pools.pools.items()`) and per fixed
# array tuple during capacity grows — bounded by class/arity, not fleet
# size — and none of those iterables match the doc-shaped names.
PER_DOC_ITER_RE = re.compile(
    r'\b(docs|doc_ids|doc_indices|doc_handles|handles|peers|links|'
    r'subscribers|sessions|tenants|n_docs|num_docs)\b')


def host_loop_scope(path):
    return in_package(path) and (
        path.startswith(('automerge_tpu/fleet/', 'automerge_tpu/service/',
                         'automerge_tpu/shard/', 'automerge_tpu/query/',
                         'automerge_tpu/backend/')))


# --- determinism ------------------------------------------------------------
# The deterministic replica paths: two replicas applying the same
# changes must produce byte-identical state, so wall-clock and unseeded
# randomness are banned (round-6 injected-clock rule). observability/
# and frontend/ are deliberately OUT: the perf ledger timestamps real
# time, the recorder rate-limits on real time, and the frontend's
# change-timestamp default is the reference API's documented behavior.
DETERMINISTIC_RE = re.compile(
    r'^automerge_tpu/(fleet|backend|service|shard|query)/')


def deterministic_scope(path):
    return bool(DETERMINISTIC_RE.match(path))


ENCODE_NAME_RE = re.compile(r'(^|_)encode')


def encode_scope(path):
    return in_package(path)


# --- counter-discipline -----------------------------------------------------
STATS_NAME_RE = re.compile(r'(_stats|_counters|_health)$')
RESERVED_SOURCE_RE = re.compile(r'total|fleet\d+')


def counter_scope(path):
    return lintable(path)


# --- lock-discipline --------------------------------------------------------
# Modules whose module-level state is reachable from more than one
# thread: the native pool's completion callbacks, the Prometheus
# exporter's scrape thread, the service's pump threads, the recorder's
# ring consumers, the kernel-ledger wrapper. Mutating a module-level
# container here outside a `with <lock>` block (and outside Counters,
# which locks internally) is a static race candidate.
THREADED_MODULES = frozenset({
    'automerge_tpu/native/__init__.py',
    'automerge_tpu/observability/metrics.py',
    'automerge_tpu/observability/export.py',
    'automerge_tpu/observability/recorder.py',
    'automerge_tpu/observability/spans.py',
    'automerge_tpu/observability/perf.py',
    'automerge_tpu/service/core.py',
    'automerge_tpu/fleet/exchange.py',
    # the control plane: its gauges are read by the exporter's scrape
    # thread while the pump thread commits decisions (the controller
    # lock brackets both sides; module stats are Counters)
    'automerge_tpu/control/signals.py',
    'automerge_tpu/control/policies.py',
    'automerge_tpu/control/controller.py',
})


def threaded_scope(path):
    return path in THREADED_MODULES
