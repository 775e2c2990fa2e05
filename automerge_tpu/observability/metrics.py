"""Monotonic counters and the dispatch/health roll-up registries.

- `Metrics`: cheap monotonic counters every DocFleet maintains
  (`fleet.metrics`): device dispatches, ops applied on device, changes
  ingested, bytes ingested, host fallbacks, actor renumber remaps,
  capacity growths, and the sequence engine's ops, padding and pool size. `snapshot()` returns a plain dict; `delta(prev)`
  diffs two snapshots — subtract around a workload to get per-phase
  counts.
- `trace(path)`: the operator's one entry to a capture — a context
  manager around `jax.profiler.trace` that turns the host-phase spans on
  for the block, so the written trace holds the device's ops and, in
  its `/host:CPU` plane, every program span on the same clock.
- `register_dispatch_source(name, fn)` / `dispatch_counts(fleets)`: one
  roll-up of every device-dispatch counter in the system. DocFleet counts
  its dispatches in `fleet.metrics.dispatches`, but some batched paths run
  over HOST backends with no fleet in sight (the sync driver's Bloom
  build/probe lives in `fleet/bloom.py` module state); those modules
  register a monotonic counter here, so the dispatch-count regression
  tests (tests/test_sync_driver.py) can diff total device dispatches
  around a workload without knowing which modules dispatched.
- `register_health_source(name, fn)` / `health_counts()`: the same
  roll-up pattern for fault-containment counters — quarantined docs,
  rejected changes/filters, sync retries, injected wire faults, fuzz
  corpus size, and the durability layer's checkpoint/compaction/
  journal-fsync/replay/truncation/rot counters (fleet/durability.py).

The roll-up key space is shared with the synthetic keys `dispatch_counts`
itself emits ('total', and 'fleet<N>' per passed fleet), so those names
are RESERVED: registering a source under one would silently corrupt the
roll-up (the module counter overwritten by — or summed into — the
synthetic key). Both register functions reject them with ValueError.
"""

import contextlib
import re
import threading

__all__ = ['Counters', 'Metrics', 'trace',
           'register_dispatch_source', 'dispatch_counts',
           'register_health_source', 'health_counts',
           'counts_delta', 'health_delta', 'dispatch_delta']


# One process-global lock for every Counters family: stat increments are
# rare events (health counters, not per-op work), so contention on a
# shared lock is cheaper than a lock object per module — and a single
# lock means two families incremented from one code path can never
# deadlock against each other.
_COUNTERS_LOCK = threading.Lock()


class Counters(dict):
    """A module-stats dict whose increments are ATOMIC under threads.

    ``d[key] += n`` on a plain dict is a read-modify-write that the GIL
    can split between threads — which is exactly how the round-15
    thread-per-shard pump pool undercounted health counters (two pumps
    read the same value, both wrote value+1). Every module `_stats`
    family is now one of these, and every increment goes through
    ``inc``, which holds the shared lock across the whole
    read-add-write. Plain reads and whole-value assignments
    (``d[key] = 0`` resets, gauge sets) stay ordinary dict operations —
    each is a single GIL-atomic bytecode effect.
    """

    __slots__ = ()

    def inc(self, key, n=1):
        """Atomically add ``n`` (may be negative) to ``key`` (missing
        keys start at 0). Returns the new value."""
        with _COUNTERS_LOCK:
            value = self.get(key, 0) + n
            self[key] = value
        return value


class Metrics:
    """Monotonic counters; plain attributes so incrementing is one add."""

    _FIELDS = (
        'dispatches',            # device merge dispatches issued
        'device_ops',            # real op rows applied on device (padding excluded)
        'changes_ingested',      # binary changes accepted by apply paths
        'bytes_ingested',        # wire bytes parsed
        'turbo_calls',           # batched turbo applies
        'exact_calls',           # mirror-exact applies
        'fallbacks',             # turbo calls routed to the exact path
        'promotions',            # documents promoted to the host engine
        'remaps',                # actor renumber dispatches
        'grows',                 # capacity regrowths (doc/key axes)
        'mirror_rebuilds',       # lazy mirror replays after turbo
        'graph_builds',          # deferred hash-graph materializations
        'docs_bulk_loaded',      # documents installed by the native loader
        'doc_materializations',  # bulk-loaded docs whose history was read
        'turbo_commit_fallback_docs',  # per-doc commit-loop iterations
                                 # (staged/slow docs only; the columnar
                                 # fast path contributes ZERO — pinned
                                 # by the commit-phase regression guard)
        # why a document left the turbo chain path for the general gate
        # (and so the staged commit): the first check that refused it
        'offchain_native',       # native.turbo_gate: chain links, deps
                                 # counts, heads against the columnar rows
        'offchain_heads',        # the host compare of a multi-head frontier
        'offchain_seq',          # an actor's first seq does not extend
                                 # the document's clock
        'offchain_dag',          # of those, the documents native.dag_gate
                                 # took back into the columnar commit
                                 # (causally ordered, just not one chain);
                                 # the three reasons summed, less this, is
                                 # what reached the general gate
        'dag_seq_docs',          # of offchain_dag, the documents that
                                 # hold sequence ops: concurrent writers
                                 # on a Text or list, applied on the device
                                 # in buffer order
        # held-back changes on the turbo path: a change whose dependency
        # has not arrived waits in its document's queue and is parsed and
        # gated again with what the next call brings for the document
        'heldback_changes',      # changes a call brought and queued
        'drained_changes',       # changes a call applied out of a queue
        'heldback_docs',         # documents of a call whose queue is not
                                 # empty after it
        'history_probes',        # what the general gate asked of history
                                 # indexes: dependencies neither the run
                                 # nor the heads meet, own hashes of
                                 # changes their actor's clock has reached
        'standing_preds',        # preds the turbo gate asked of the
                                 # applied-op index: map-key preds the
                                 # call's own rows do not resolve
        # the sequence engine (fleet/backend.py _dispatch_seq)
        'seq_ops',               # real sequence ops dispatched
        'seq_op_cells',          # rows x width of the op columns handed to
                                 # the device; less seq_ops, it is padding
        'seq_migrations',        # rows moved up a size class
        'seq_multiwriter_rows',  # rows of a dispatch whose op list holds
                                 # more than one actor
        'seq_inexact_reads',     # rows a bulk render found flagged
                                 # inexact and left to the host mirror
        'seq_repacks',           # rows whose ids were rewritten on the
                                 # device into a wide layout (a row past
                                 # the packed window, or a wide row that
                                 # gained a writer)
        'seq_lookup_nodes',      # the nodes the referent lookups read:
                                 # rows x nodes of a class they scanned,
                                 # rows x width x rounds of one searched
        'seq_search_dispatches',  # dispatches whose lookup searched rows
                                  # in id order (sequence._referent_lookup)
        # bulk reads (fleet/backend.py materialize_docs)
        'read_docs',             # handles asked of the fleet
        'read_rows',             # rows the device gather moved to the host
        'read_host_docs',        # of read_docs, the documents the host
                                 # mirror or engine served
        # gauges, not counters: what the pools hold after the last
        # dispatch or bulk load (delta() gives their change)
        'seq_pool_bytes',        # bytes of every pool's arrays
        'seq_nodes',             # rows x nodes over all pools
        'seq_wide_rows',         # rows holding a counter at or past the
                                 # packed window (tensor_doc.CTR_LIMIT)
    )

    def __init__(self):
        for name in self._FIELDS:
            setattr(self, name, 0)
        self.seconds = {}        # phase name -> accumulated wall seconds

    def snapshot(self):
        out = {name: getattr(self, name) for name in self._FIELDS}
        out['seconds'] = dict(self.seconds)
        return out

    def delta(self, prev):
        """Counters accumulated since `prev` (an earlier snapshot())."""
        now = self.snapshot()
        out = {k: now[k] - prev.get(k, 0) for k in self._FIELDS}
        out['seconds'] = {k: v - prev.get('seconds', {}).get(k, 0.0)
                          for k, v in now['seconds'].items()}
        return out

    def __repr__(self):
        parts = [f'{k}={getattr(self, k)}' for k in self._FIELDS
                 if getattr(self, k)]
        return f'Metrics({", ".join(parts)})'


# ---- device-dispatch roll-up ----------------------------------------------

_dispatch_sources = {}

# 'total' and 'fleet<N>' are synthesized by dispatch_counts itself; a
# module registering under either would corrupt the roll-up (round-7
# satellite: the collision was silent before this guard).
_RESERVED = re.compile(r'total|fleet\d+')


def _check_source_name(name):
    if not isinstance(name, str) or _RESERVED.fullmatch(name):
        raise ValueError(
            f'{name!r} is reserved: dispatch_counts() synthesizes '
            f"'total' and 'fleet<N>' keys, so sources may not register "
            f'under those names')


def register_dispatch_source(name, fn):
    """Register a zero-arg callable returning a module's monotonic device
    dispatch count (e.g. fleet.bloom registers its batched build/probe
    counter at import). Re-registering a name replaces the source.
    Raises ValueError for the reserved roll-up keys ('total',
    'fleet<N>')."""
    _check_source_name(name)
    with _COUNTERS_LOCK:
        _dispatch_sources[name] = fn


def dispatch_counts(fleets=()):
    """Snapshot every registered module dispatch counter plus the given
    fleets' `metrics.dispatches`, with a 'total' sum. Take one snapshot
    before and one after a workload and subtract per key (the counters are
    monotonic) to get dispatches attributable to that workload."""
    out = {name: int(fn()) for name, fn in _dispatch_sources.items()}
    for i, fleet in enumerate(fleets):
        out[f'fleet{i}'] = int(fleet.metrics.dispatches)
    out['total'] = sum(out.values())
    return out


# ---- fault-containment health roll-up -------------------------------------

_health_sources = {}


def register_health_source(name, fn):
    """Register a zero-arg callable returning a module's monotonic
    fault-containment counter (quarantined docs, rejected changes, sync
    retries, injected wire faults, ...). Re-registering a name replaces
    the source — same contract (and same reserved-name rejection) as
    register_dispatch_source."""
    _check_source_name(name)
    with _COUNTERS_LOCK:
        _health_sources[name] = fn


def health_counts():
    """Snapshot every registered health counter. Counters are monotonic;
    subtract two snapshots around a workload to attribute events to it."""
    return {name: int(fn()) for name, fn in _health_sources.items()}


# ---- snapshot/delta over counter roll-ups ---------------------------------
#
# The counter twin of Histogram.snapshot()/delta(): the roll-ups return
# plain monotonic dicts, and every consumer used to subtract them by hand
# (obs_report dump comparisons, now the SLO
# windows every tick). One shared subtraction keeps the semantics in one
# place: keys are unioned, a key missing from either side reads 0.

def counts_delta(now, prev):
    """Per-key difference of two counter snapshots (``now - prev``).
    Keys are unioned; a key absent from one side counts as 0 there, so
    a counter that appeared (or a source registered) between the two
    snapshots still contributes its full movement."""
    out = {}
    for k, v in now.items():
        out[k] = v - prev.get(k, 0)
    for k, v in prev.items():
        if k not in now:
            out[k] = -v
    return out


def health_delta(prev):
    """Health counters accumulated since ``prev`` (an earlier
    health_counts() snapshot)."""
    return counts_delta(health_counts(), prev)


def dispatch_delta(prev, fleets=()):
    """Device dispatches accumulated since ``prev`` (an earlier
    dispatch_counts() snapshot over the same fleets)."""
    return counts_delta(dispatch_counts(fleets), prev)


@contextlib.contextmanager
def trace(log_dir):
    """JAX profiler trace of every dispatch inside the block, with the
    program's host-phase spans in it: spans are turned on for the block
    (and left as they were found), and while on each span is a
    `TraceAnnotation` in the capture's `/host:CPU` plane, on the device
    planes' clock. View the written trace with TensorBoard's profile
    plugin or Perfetto."""
    import jax
    from . import spans
    was_on = spans.on()
    if not was_on:
        spans.enable()
    try:
        with jax.profiler.trace(str(log_dir)):
            yield
    finally:
        if not was_on:
            spans.disable()
