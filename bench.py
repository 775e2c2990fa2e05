#!/usr/bin/env python
"""Fleet benchmark: batched change application over a document fleet.

The HEADLINE metric is the end-to-end Backend-seam rate: binary changes ->
header decode + SHA-256 hash graph + causal gate (host) -> native C++ column
parse -> one device merge dispatch, via fleet.backend.apply_changes_docs
(mirror=False). That is the full setDefaultBackend-pluggable pipeline a user
of the reference would hit — nothing skipped. Kernel-only numbers (device
merge on pre-built batches) are reported separately and labeled as such.

All key rates are medians over BENCH_REPS (default 5) timed runs after a
compile warmup.

Note: the reference JS backend cannot run in this image (no Node.js, no JS
engine wheels, no network — attempts recorded in BASELINE.md), so the
recorded baseline is our host reference engine (CPython OpSet); V8 would be
several times faster, so treat vs_baseline as vs-CPython.

Platform: the run needs a TPU. An explicit JAX_PLATFORMS=cpu is honoured
for debugging and shows as "platform": "cpu" on every JSON line; anything
else that does not come up as a TPU exits non-zero before the first
section (no CPU fallback). Every JSON line carries platform, device_kind
and n_devices as JAX reports them. A chip belongs to one process at a
time, so whichever process runs sections owns it and starts no child that
needs it.

Section modes:
- BENCH_SECTION=<name> runs ONE section standalone (fresh process, fenced)
  and prints {"section": name, ...} — the reproducibility answer to bench
  lines that moved 178x with section ordering. BENCH_SECTION=list prints
  the section names; BENCH_SECTION=all runs every section but `trace` in
  one process.
- BENCH_SANITY=1 runs a scaled-down full pass and then key sections
  standalone, each as a child process, one after another (the parent
  stays off JAX), and fails (exit 1) if any full-run rate disagrees with
  its standalone rate by more than 2x.

Dispatch accounting: the seam section reports device dispatches for an
N-doc init and per apply round (DocFleet.metrics.dispatches), and the sync
driver section reports Bloom build+probe dispatches per 10k-peer generate
round (fleet.bloom.dispatch_count()) — both must be O(1), size-independent.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np

REPS = int(os.environ.get('BENCH_REPS', 5))

# {'platform', 'device_kind', 'n_devices'} as JAX reports them; set by
# _init_platform before the first section and stamped on every JSON line
DEVICE = {}


def _init_platform():
    """Bring the backend up before any section runs. An explicit
    JAX_PLATFORMS=cpu is honoured (and shows as platform: cpu on every
    line printed); anything else must come up as a TPU or the run dies
    here — there is no fallback that would keep writing the same metric
    names from a different platform. This process then owns the chip:
    no section may start a child that needs it."""
    from automerge_tpu import jaxenv, native
    jaxenv.configure_compile_cache()
    DEVICE.update(jaxenv.require_platform(
        cpu=os.environ.get('JAX_PLATFORMS') == 'cpu'))
    if not native.available():
        sys.exit(f'bench: native codec unavailable ({native._load_error!r})'
                 f' — every seam batch would take the per-doc exact path')
    print(f'# platform {DEVICE["platform"]} ({DEVICE["device_kind"]} x '
          f'{DEVICE["n_devices"]}), {native.native_threads()} native '
          f'threads', file=sys.stderr)


def median_rate(run, total, reps=None):
    """Median ops-per-second over `reps` timed runs of run()."""
    rates = []
    for _ in range(reps or REPS):
        start = time.perf_counter()
        run()
        rates.append(total / (time.perf_counter() - start))
    return float(np.median(rates))


def build_workload(n_docs, n_keys, n_actors, rounds, ops_per_round, seed=0):
    """Concurrent map-set workload as per-round op columns [N, P]."""
    from automerge_tpu.fleet import OpBatch
    from automerge_tpu.fleet.tensor_doc import ACTOR_BITS
    rng = np.random.default_rng(seed)
    batches = []
    ctr = 1
    for _ in range(rounds):
        shape = (n_docs, ops_per_round)
        key_id = rng.integers(0, n_keys, shape, dtype=np.int32)
        actor = rng.integers(0, n_actors, shape, dtype=np.int32)
        ctrs = ctr + np.broadcast_to(
            np.arange(ops_per_round, dtype=np.int32), shape)
        packed = (ctrs.astype(np.int32) << ACTOR_BITS) | actor
        value = rng.integers(1, 1 << 20, shape, dtype=np.int32)
        ones = np.ones(shape, dtype=bool)
        batches.append(OpBatch(key_id, packed, value, ones,
                               np.zeros(shape, dtype=bool), ones))
        ctr += ops_per_round
    return batches


def bench_fleet(n_docs, n_keys, rounds, ops_per_round, use_pallas=False,
                pallas_variant='dense'):
    import functools
    import jax
    from automerge_tpu.fleet import FleetState, apply_op_batch
    if use_pallas:
        from automerge_tpu.fleet.pallas_merge import pallas_apply_op_batch
        apply_op_batch = functools.partial(pallas_apply_op_batch,
                                           variant=pallas_variant)

    batches = build_workload(n_docs, n_keys, 2, rounds, ops_per_round)
    state = FleetState.empty(n_docs, n_keys)
    device_batches = [jax.device_put(b) for b in batches]
    state = jax.tree_util.tree_map(jax.device_put, state)

    # Warmup / compile
    warm, _ = apply_op_batch(state, device_batches[0])
    jax.block_until_ready(warm.winners)

    def run():
        s = state
        for b in device_batches:
            s, _stats = apply_op_batch(s, b)
        jax.block_until_ready(s.winners)

    total_ops = n_docs * ops_per_round * rounds
    return median_rate(run, total_ops), None


def bench_pallas_merge(n_docs, n_keys, rounds, ops_per_round):
    """Fused Pallas merge kernel (interpret=False: real Mosaic compile),
    dense variant, on the same workload as bench_fleet, after a
    correctness cross-check against the jnp path. Runs whenever a TPU is
    the default backend (or BENCH_PALLAS=1 forces it elsewhere) and
    returns (rate, variant); (None, None) only where it does not run. A
    Mosaic compile failure or a mismatch fails the run."""
    import jax
    if not os.environ.get('BENCH_PALLAS') and \
            jax.default_backend() != 'tpu':
        return None, None
    from automerge_tpu.fleet import FleetState, apply_op_batch
    from automerge_tpu.fleet.pallas_merge import pallas_apply_op_batch
    variant = 'dense'
    # differential check on a small batch before timing
    check = build_workload(64, n_keys, 3, 1, 32)[0]
    st0 = FleetState.empty(64, n_keys)
    want, _ = apply_op_batch(st0, check)
    got, _ = pallas_apply_op_batch(st0, check, interpret=False,
                                   variant=variant)
    for name in ('winners', 'values', 'counters'):
        w = np.asarray(getattr(want, name))[:, :n_keys]
        g = np.asarray(getattr(got, name))[:, :n_keys]
        if not np.array_equal(w, g):
            raise AssertionError(f'pallas/jnp mismatch in {name}')
    rate, _ = bench_fleet(n_docs, n_keys, rounds, ops_per_round,
                          use_pallas=True, pallas_variant=variant)
    return rate, variant


def capture_trace(n_docs, n_keys, ops_per_round, pallas_variant=None):
    """Write a jax.profiler trace of steady-state merge + sequence + (when
    compiled) Pallas dispatches to BENCH_TRACE_DIR (default traces/bench).
    Runs on a real TPU backend, or anywhere with BENCH_TRACE=1; the trace is
    the evidence base for BASELINE.md's bandwidth accounting. Returns the
    trace dir, or None where it does not run; a profiler failure fails
    the run."""
    import jax
    if not os.environ.get('BENCH_TRACE') and jax.default_backend() != 'tpu':
        return None
    from automerge_tpu import observability
    from automerge_tpu.fleet import FleetState, apply_op_batch
    from automerge_tpu.fleet.sequence import (
        SeqState, apply_seq_batch, SeqOpBatch, INSERT, SEQ_PRED_LANES)
    from automerge_tpu.fleet.tensor_doc import ACTOR_BITS
    batches = [jax.device_put(b) for b in
               build_workload(n_docs, n_keys, 2, 3, ops_per_round)]
    state = jax.tree_util.tree_map(jax.device_put,
                                   FleetState.empty(n_docs, n_keys))
    warm, _ = apply_op_batch(state, batches[0])    # compile outside
    jax.block_until_ready(warm.winners)
    # small sequence batch: chained inserts per doc
    sd, sl = 256, 64
    kind = np.full((sd, sl), INSERT, dtype=np.int32)
    ctrs = 2 + np.arange(sl, dtype=np.int32)
    packed = np.broadcast_to(ctrs << ACTOR_BITS, (sd, sl)).astype(np.int32)
    ref = np.zeros((sd, sl), dtype=np.int32)
    ref[:, 1:] = packed[:, :-1]
    seq_batch = jax.device_put(SeqOpBatch(
        kind, ref, packed, np.full((sd, sl), 97, dtype=np.int32),
        np.zeros((sd, sl, SEQ_PRED_LANES), dtype=np.int32)))
    seq_state = jax.tree_util.tree_map(jax.device_put,
                                       SeqState.empty(sd, sl + 1))
    warm_seq, _ = apply_seq_batch(seq_state, seq_batch)
    jax.block_until_ready(warm_seq.nxt)
    if pallas_variant:
        from automerge_tpu.fleet.pallas_merge import pallas_apply_op_batch
        warm_p, _ = pallas_apply_op_batch(state, batches[0],
                                          variant=pallas_variant)
        jax.block_until_ready(warm_p.winners)
    trace_dir = os.environ.get('BENCH_TRACE_DIR', 'traces/bench')
    with observability.trace(trace_dir):
        s = state
        for b in batches:
            s, _ = apply_op_batch(s, b)
        jax.block_until_ready(s.winners)
        out, _ = apply_seq_batch(seq_state, seq_batch)
        jax.block_until_ready(out.nxt)
        if pallas_variant:
            s2, _ = pallas_apply_op_batch(state, batches[0],
                                          variant=pallas_variant)
            jax.block_until_ready(s2.winners)
    return trace_dir


def bench_host(n_docs, n_keys, rounds, ops_per_round, seed=0):
    """Same workload shape through the host OpSet engine (single-op changes,
    matching the backend_test.js concurrent-key-set shape)."""
    from automerge_tpu import backend as Backend
    from automerge_tpu.columnar import encode_change
    rng = np.random.default_rng(seed)
    actors = ['aa' * 4, 'bb' * 4]

    # Pre-encode all changes (decode cost is part of applyChanges either way;
    # encode cost is the remote peer's problem)
    docs = []
    for d in range(n_docs):
        changes = []
        seqs = {0: 0, 1: 0}
        ctr = 1
        for _ in range(rounds):
            for i in range(ops_per_round):
                a = int(rng.integers(0, 2))
                seqs[a] += 1
                changes.append(encode_change({
                    'actor': actors[a], 'seq': seqs[a], 'startOp': ctr,
                    'time': 0, 'message': '', 'deps': [],
                    'ops': [{'action': 'set', 'obj': '_root',
                             'key': f'k{int(rng.integers(0, n_keys))}',
                             'value': int(rng.integers(1, 1 << 20)),
                             'datatype': 'int', 'pred': []}],
                }))
                ctr += 1
        docs.append(changes)

    def run():
        for changes in docs:
            backend = Backend.init()
            state = backend['state']
            # seq contiguity: interleave per actor in recorded order
            state.apply_changes(changes)

    total_ops = n_docs * rounds * ops_per_round
    return median_rate(run, total_ops, reps=3), None


def bench_pipeline(n_docs, n_keys, changes_per_doc, seed=0):
    """Full wire-to-device pipeline: binary changes -> native C++ column
    decode -> dictionary encoding -> device merge."""
    import jax
    from automerge_tpu.columnar import encode_change
    from automerge_tpu.fleet import FleetState, apply_op_batch
    from automerge_tpu.fleet.ingest import (
        changes_to_op_batch, KeyInterner, ActorInterner)
    rng = np.random.default_rng(seed)
    actors = ['aa' * 4, 'bb' * 4]
    per_doc = []
    for d in range(n_docs):
        changes = []
        seqs = [0, 0]
        for c in range(changes_per_doc):
            a = int(rng.integers(0, 2))
            seqs[a] += 1
            changes.append(encode_change({
                'actor': actors[a], 'seq': seqs[a], 'startOp': c + 1,
                'time': 0, 'message': '', 'deps': [],
                'ops': [{'action': 'set', 'obj': '_root',
                         'key': f'k{int(rng.integers(0, n_keys))}',
                         'value': int(rng.integers(1, 1 << 20)),
                         'datatype': 'int', 'pred': []}]}))
        per_doc.append(changes)

    def run():
        ki, ai = KeyInterner(), ActorInterner()
        batch = changes_to_op_batch(per_doc, ki, ai)
        state = FleetState.empty(n_docs, max(len(ki), 1))
        state, _ = apply_op_batch(state, batch)
        jax.block_until_ready(state.winners)

    run()  # warmup: jit compile for these shapes
    return median_rate(run, n_docs * changes_per_doc), None


def bench_backend_pipeline(n_docs, n_keys, changes_per_doc, seed=0,
                           chunks=1, ops_per_change=1, reps=None):
    """Wire-to-device through the Backend seam (fleet.backend turbo path):
    header decode + SHA-256 hash graph + causal gate on host, native C++
    column parse, one device merge dispatch per chunk. This is the full
    setDefaultBackend-pluggable pipeline, unlike bench_pipeline which skips
    the causal/hash-graph bookkeeping.

    chunks > 1 routes the batch through apply_changes_docs_pipelined with
    that many sub-batches: the NATIVE PARSE of sub-batch k+1 runs on a
    background thread (GIL released, chunk-parallel over the codec's
    thread pool) while the host gate/commit and async device dispatch of
    sub-batch k proceed — real CPU overlap, not just dispatch asynchrony
    (the round-6 4-chunk loop split serial work without adding cores and
    REGRESSED the seam ~2x; this path replaced it).

    ops_per_change > 1 packs that many flat-int set ops into each change —
    the op-density control for the mixed-docs gap (a fractional value like
    4.8 is honored by mixing change sizes to that average).

    One change chain is shared by every doc (the bench_backend_text
    pattern): the measured pipeline memoizes nothing by content — every
    buffer is parsed, hashed, and gated per document — so this only makes
    the 10k-doc setup affordable, not the measurement cheaper.

    Returns (changes_per_sec, info) where info records the device dispatch
    counts: {'init_dispatches', 'apply_dispatches', 'rounds',
    'ops_per_change'} — the O(1)-dispatch evidence for the seam."""
    from automerge_tpu.columnar import encode_change, decode_change_meta
    from automerge_tpu.fleet.backend import (
        DocFleet, init_docs, apply_changes_docs, materialize_docs)
    rng = np.random.default_rng(seed)
    actors = ['aa' * 16, 'bb' * 16]
    changes, heads = [], []
    seqs = [0, 0]
    op_counts = []
    acc = 0.0
    for c in range(changes_per_doc):
        # realize a fractional average op density by alternating sizes
        acc += ops_per_change
        k = max(int(round(acc)), 1)
        acc -= k
        op_counts.append(k)
    start_op = 1
    for c in range(changes_per_doc):
        a = c % 2
        seqs[a] += 1
        ops = [{'action': 'set', 'obj': '_root',
                'key': f'k{int(rng.integers(0, n_keys))}',
                'value': int(rng.integers(1, 1 << 20)),
                'datatype': 'int', 'pred': []}
               for _ in range(op_counts[c])]
        buf = encode_change({
            'actor': actors[a], 'seq': seqs[a], 'startOp': start_op,
            'time': 0, 'message': '', 'deps': heads, 'ops': ops})
        start_op += op_counts[c]
        heads = [decode_change_meta(buf, True)['hash']]
        changes.append(buf)
    per_doc = [list(changes) for _ in range(n_docs)]
    # actual sub-batch count: the pipelined driver splits per doc at
    # step = ceil(changes/chunks) and DROPS empty tail sub-batches, so
    # e.g. chunks=8 over 20 changes yields 7 rounds, not 8
    if max(chunks, 1) > 1:
        step = -(-changes_per_doc // max(chunks, 1))
        n_rounds = -(-changes_per_doc // step)
    else:
        n_rounds = 1
    info = {'rounds': n_rounds,
            'ops_per_change': sum(op_counts) / len(op_counts)}

    def run():
        import jax
        from automerge_tpu.fleet.backend import apply_changes_docs_pipelined
        fleet = DocFleet(doc_capacity=n_docs, key_capacity=n_keys + 1)
        d0 = fleet.metrics.dispatches
        handles = init_docs(n_docs, fleet)
        info['init_dispatches'] = fleet.metrics.dispatches - d0
        d1 = fleet.metrics.dispatches
        if n_rounds > 1:
            handles, _ = apply_changes_docs_pipelined(
                handles, per_doc, sub_batches=n_rounds)
        else:
            handles, _ = apply_changes_docs(handles, per_doc, mirror=False)
        jax.block_until_ready(fleet.state.winners)
        info['apply_dispatches'] = fleet.metrics.dispatches - d1
        return handles

    run()  # warmup compile
    return median_rate(run, n_docs * changes_per_doc, reps=reps), info


def bench_sync_bloom(n_docs, hashes_per_doc, seed=0):
    """Config 4 (BASELINE.md): sync Bloom-filter throughput. Device path:
    per-peer filters for the whole fleet built in one scatter dispatch and
    probed in one gather dispatch ([docs, bits] bit tensors); host baseline:
    the per-peer BloomFilter loop the reference runs per sync message
    (ref sync.js:38-125). Returns (device_hashes_per_sec, host_hashes_per_sec)."""
    import hashlib
    import jax
    from automerge_tpu.backend.sync import BloomFilter
    from automerge_tpu.fleet.bloom import (
        build_bloom_filters, probe_bloom_filters, hashes_to_words)
    hashes = [[hashlib.sha256(f'{d}:{i}:{seed}'.encode()).hexdigest()
               for i in range(hashes_per_doc)] for d in range(n_docs)]
    words, valid = hashes_to_words(hashes)
    words = jax.device_put(words)
    valid = jax.device_put(valid)
    bits = build_bloom_filters(words, valid, hashes_per_doc)  # warmup build
    probe_bloom_filters(bits, words, valid).block_until_ready()
    start = time.perf_counter()
    bits = build_bloom_filters(words, valid, hashes_per_doc)
    hit = probe_bloom_filters(bits, words, valid)
    jax.block_until_ready(hit)
    device_rate = (2 * n_docs * hashes_per_doc) / (time.perf_counter() - start)

    host_docs = max(n_docs // 100, 1)
    start = time.perf_counter()
    for d in range(host_docs):
        f = BloomFilter(hashes[d])
        for h in hashes[d]:
            assert f.contains_hash(h)
    host_rate = (2 * host_docs * hashes_per_doc) / (time.perf_counter() - start)
    return device_rate, host_rate


def bench_sync_driver(n_docs, changes_per_doc=8, seed=0):
    """Batched fleet sync driver (fleet/sync_driver.py) vs the host per-doc
    protocol loop: one generate round over n_docs peers, ALL Bloom builds
    in one device dispatch (flat packed layout — size-class count no
    longer matters). Returns (batched_docs_per_sec, host_docs_per_sec,
    dispatches_per_round)."""
    from automerge_tpu import backend as Backend
    from automerge_tpu.backend import init_sync_state
    from automerge_tpu.backend.sync import generate_sync_message
    from automerge_tpu.columnar import encode_change, decode_change_meta
    from automerge_tpu.fleet import bloom as fleet_bloom
    from automerge_tpu.fleet.sync_driver import generate_sync_messages_docs
    rng = np.random.default_rng(seed)

    def build_docs(n):
        docs = []
        for d in range(n):
            backend = Backend.init()
            changes, heads = [], []
            for c in range(changes_per_doc):
                buf = encode_change({
                    'actor': f'{d:04x}' * 4, 'seq': c + 1, 'startOp': c + 1,
                    'time': 0, 'message': '', 'deps': heads,
                    'ops': [{'action': 'set', 'obj': '_root',
                             'key': f'k{int(rng.integers(0, 16))}',
                             'value': int(rng.integers(1, 1 << 20)),
                             'datatype': 'int', 'pred': []}]})
                heads = [decode_change_meta(buf, True)['hash']]
                changes.append(buf)
            backend = Backend.load_changes(backend, changes)
            docs.append(backend)
        return docs

    docs = build_docs(n_docs)
    states = [init_sync_state() for _ in docs]
    generate_sync_messages_docs(docs, states)    # warmup compile
    d0 = fleet_bloom.dispatch_count()
    start = time.perf_counter()
    _, messages = generate_sync_messages_docs(docs, states)
    batched_rate = n_docs / (time.perf_counter() - start)
    dispatches = fleet_bloom.dispatch_count() - d0
    assert all(m is not None for m in messages)

    host_n = max(n_docs // 20, 1)
    start = time.perf_counter()
    for doc, state in zip(docs[:host_n], states[:host_n]):
        generate_sync_message(doc, state)
    host_rate = host_n / (time.perf_counter() - start)
    return batched_rate, host_rate, dispatches


def bench_zipf(n_docs, zipf_a=1.5, max_per_doc=256, round_width=32, seed=0):
    """Config 5 (BASELINE.md stretch): large fleet with Zipf-skewed per-doc
    change rates, mixed set/inc/del ops. Skew is the scatter design's worst
    case: padded [N, P] rounds are sized by the hottest doc, so effective
    throughput = real ops/s (padding excluded) is reported alongside the
    occupancy (real ops / padded lanes)."""
    import jax
    from automerge_tpu.fleet import FleetState, OpBatch, TOMBSTONE, apply_op_batch
    from automerge_tpu.fleet.tensor_doc import ACTOR_BITS
    rng = np.random.default_rng(seed)
    n_keys = 64
    counts = np.minimum(rng.zipf(zipf_a, n_docs), max_per_doc)
    total_ops = int(counts.sum())
    rounds = int(np.ceil(counts.max() / round_width))
    batches = []
    ctr = 1
    for r in range(rounds):
        todo = np.clip(counts - r * round_width, 0, round_width)
        shape = (n_docs, round_width)
        lane = np.arange(round_width)[None, :]
        valid = lane < todo[:, None]
        key_id = rng.integers(0, n_keys, shape, dtype=np.int32)
        actor = rng.integers(0, 4, shape, dtype=np.int32)
        packed = ((ctr + lane).astype(np.int32) << ACTOR_BITS) | actor
        kind = rng.random(shape)
        value = rng.integers(1, 1 << 20, shape, dtype=np.int32)
        value = np.where(kind < 0.1, TOMBSTONE, value)          # 10% deletes
        is_inc = (kind >= 0.8) & valid                          # 20% incs
        is_set = (kind < 0.8) & valid
        batches.append(OpBatch(key_id, packed, value.astype(np.int32),
                               is_set, is_inc, valid))
        ctr += round_width
    state = FleetState.empty(n_docs, n_keys)
    device_batches = [jax.device_put(b) for b in batches]
    state = jax.tree_util.tree_map(jax.device_put, state)
    warm, _ = apply_op_batch(state, device_batches[0])
    jax.block_until_ready(warm.winners)
    start = time.perf_counter()
    s = state
    for b in device_batches:
        s, _ = apply_op_batch(s, b)
    jax.block_until_ready(s.winners)
    elapsed = time.perf_counter() - start
    occupancy = total_ops / (n_docs * round_width * rounds)
    return total_ops / elapsed, occupancy


def bench_registers(n_docs, n_keys=64, n_actor_slots=4, p=128, seed=0):
    """Exact multi-value register engine: ordered scan over the op axis,
    [n_docs]-wide steps (conflict sets / resurrection / counter semantics
    exact on device, unlike the scatter-max LWW engine)."""
    import jax
    from automerge_tpu.fleet.registers import (
        RegisterOpBatch, RegisterState, apply_register_batch)
    rng = np.random.default_rng(seed)
    kind = rng.integers(1, 4, (n_docs, p), dtype=np.int32)
    key = rng.integers(0, n_keys, (n_docs, p), dtype=np.int32)
    actor = rng.integers(0, n_actor_slots - 1, (n_docs, p), dtype=np.int32)
    packed = ((1 + np.arange(p, dtype=np.int32))[None, :] << 8) | actor
    value = rng.integers(0, 1000, (n_docs, p), dtype=np.int32)
    preds = np.zeros((n_docs, p, 2), dtype=np.int32)
    preds[:, 1:, 0] = packed[:, :-1]     # chain preds (kill previous)
    overflow = np.zeros((n_docs, p), dtype=bool)
    batch = RegisterOpBatch(kind, key, packed, value, preds, overflow)
    state = RegisterState.empty(n_docs, n_keys, n_actor_slots)
    state, _ = apply_register_batch(state, batch)
    jax.block_until_ready(state.reg)
    start = time.perf_counter()
    state, stats = apply_register_batch(state, batch)
    jax.block_until_ready(state.reg)
    return (n_docs * p) / (time.perf_counter() - start)


def bench_text(n_docs, trace_len, n_actors=3, seed=0):
    """KERNEL-ONLY config 2 shape: batched text editing traces through the
    raw device sequence engine on pre-built packed columns (no wire decode,
    no hash graph) — the device ceiling, not an end-to-end number; see
    bench_backend_text for the honest seam rate."""
    import jax
    from automerge_tpu.fleet.sequence import (
        DEL, INSERT, SeqOpBatch, SeqState, apply_seq_batch)
    from automerge_tpu.fleet.tensor_doc import ACTOR_BITS
    rng = np.random.default_rng(seed)

    # Randomized trace as packed columns [N, P]: ~80% inserts (after a random
    # earlier insert; head for the first), ~20% deletes of a random earlier
    # insert. The insert/delete column pattern is shared across docs so every
    # ref targets a real elemId; referents and actors vary per doc.
    is_del = rng.random(trace_len) < 0.2
    is_del[0] = False
    kind = np.where(is_del, DEL, INSERT).astype(np.int32)
    kind = np.broadcast_to(kind, (n_docs, trace_len)).copy()
    value = rng.integers(97, 123, (n_docs, trace_len), dtype=np.int32)
    actor = rng.integers(0, n_actors, (n_docs, trace_len), dtype=np.int32)
    ctr = 2 + np.arange(trace_len, dtype=np.int32)
    packed = ((ctr[None, :] << ACTOR_BITS) | actor).astype(np.int32)
    ref = np.zeros((n_docs, trace_len), dtype=np.int32)
    insert_cols = np.flatnonzero(~is_del)
    rows = np.arange(n_docs)
    for i in range(1, trace_len):
        prior = insert_cols[insert_cols < i]
        choice = prior[rng.integers(0, len(prior), n_docs)]
        ref[:, i] = packed[rows, choice]
    # DELs kill exactly their preds (multi-value register semantics): the
    # pred is the insert op being deleted, i.e. the ref elemId itself
    from automerge_tpu.fleet.sequence import SEQ_PRED_LANES
    preds = np.zeros((n_docs, trace_len, SEQ_PRED_LANES), dtype=np.int32)
    preds[:, :, 0] = np.where(kind == DEL, ref, 0)
    batch = SeqOpBatch(kind, ref, packed, value, preds)

    state = SeqState.empty(n_docs, trace_len + 1)
    batch = jax.device_put(batch)
    state = jax.tree_util.tree_map(jax.device_put, state)
    warm, _ = apply_seq_batch(state, batch)
    jax.block_until_ready(warm.nxt)

    def run():
        out, _ = apply_seq_batch(state, batch)
        jax.block_until_ready(out.nxt)

    return median_rate(run, n_docs * trace_len), None


def bench_backend_text(n_docs, trace_len, ops_per_change=32, seed=0):
    """End-to-end text editing through the Backend seam: binary change
    chains (makeText + insert/delete runs) -> turbo wire->device into the
    SeqState fleet. Returns median text ops/s across the fleet."""
    from automerge_tpu.columnar import encode_change, decode_change_meta
    from automerge_tpu.fleet.backend import (
        DocFleet, init_docs, apply_changes_docs)
    rng = np.random.default_rng(seed)
    A = 'aa' * 16
    # One trace shared by every doc: makeText, then chained changes of
    # insert/delete ops (deletes target a random still-visible element)
    ops, elems, alive = [], [], []
    ops.append({'action': 'makeText', 'obj': '_root', 'key': 't',
                'pred': []})
    obj = f'1@{A}'
    op_num = 2
    prev = '_head'
    while len(ops) < trace_len + 1:
        if alive and rng.random() < 0.2:
            i = int(rng.integers(0, len(alive)))
            victim = alive.pop(i)
            ops.append({'action': 'del', 'obj': obj, 'elemId': victim,
                        'pred': [victim]})
        else:
            ref = prev if not alive or rng.random() < 0.5 else \
                alive[int(rng.integers(0, len(alive)))]
            me = f'{op_num}@{A}'
            ops.append({'action': 'set', 'obj': obj, 'elemId': ref,
                        'insert': True,
                        'value': chr(97 + int(rng.integers(0, 26))),
                        'pred': []})
            alive.append(me)
            prev = me
        op_num += 1
    changes, heads = [], []
    seq = 0
    for start in range(0, len(ops), ops_per_change):
        chunk = ops[start:start + ops_per_change]
        seq += 1
        buf = encode_change({'actor': A, 'seq': seq, 'startOp': start + 1,
                             'time': 0, 'message': '', 'deps': heads,
                             'ops': chunk})
        heads = [decode_change_meta(buf, True)['hash']]
        changes.append(buf)
    per_doc = [list(changes) for _ in range(n_docs)]
    n_ops = len(ops) * n_docs

    def run():
        import jax
        fleet = DocFleet(doc_capacity=n_docs, key_capacity=4)
        handles = init_docs(n_docs, fleet)
        handles, _ = apply_changes_docs(handles, per_doc, mirror=False)
        assert fleet.metrics.fallbacks == 0
        jax.block_until_ready([p.nxt for p in fleet.seq_pools.pools.values()])

    run()  # warmup compile

    # Host baseline on the same trace (config 2's "vs" column): the host
    # OpSet engine applying the identical change chain, scaled-down doc
    # count, rate-normalized
    from automerge_tpu import backend as Backend
    host_docs = max(n_docs // 50, 1)

    def run_host():
        for _ in range(host_docs):
            backend = Backend.init()
            Backend.apply_changes(backend, changes)
    host_rate = median_rate(run_host, len(ops) * host_docs, reps=3)
    return median_rate(run, n_ops), host_rate


def bench_bulk_load(n_docs, n_changes=40, seed=0):
    """Fleet bulk load (native document parse -> device state, no replay)
    vs the ordinary per-doc load path (Python document decode + host OpSet
    replay). Returns (bulk docs/s, per-doc docs/s)."""
    import jax
    from automerge_tpu import backend as Backend
    from automerge_tpu.columnar import encode_change, decode_change_meta
    from automerge_tpu.fleet.backend import DocFleet
    from automerge_tpu.fleet import backend as fleet_backend
    from automerge_tpu.fleet.loader import load_docs
    rng = np.random.default_rng(seed)
    A = 'bb' * 16
    # One representative saved document, cloned across the fleet with
    # distinct trailing writes so contents differ per doc
    base = Backend.init()
    heads = []
    for c in range(n_changes):
        ops = [{'action': 'set', 'obj': '_root', 'key': f'k{int(k)}',
                'value': int(rng.integers(0, 1 << 20)),
                'datatype': 'int', 'pred': []}
               for k in rng.integers(0, 64, size=8)]
        buf = encode_change({'actor': A, 'seq': c + 1,
                             'startOp': c * 8 + 1, 'time': 0,
                             'message': '', 'deps': heads, 'ops': ops})
        heads = [decode_change_meta(buf, True)['hash']]
        base, _ = Backend.apply_changes(base, [buf])
    saved = Backend.save(base)
    bufs = [saved] * n_docs

    def run_bulk():
        fleet = DocFleet(doc_capacity=n_docs, key_capacity=128)
        handles = load_docs(bufs, fleet)
        if fleet.metrics.docs_bulk_loaded != n_docs:
            raise RuntimeError('bulk load fell back to the per-doc path')
        if fleet.state is not None:
            jax.block_until_ready(fleet.state.winners)

    host_docs = max(n_docs // 100, 1)

    def run_host():
        fleet = DocFleet(doc_capacity=host_docs, key_capacity=128)
        for buf in bufs[:host_docs]:
            fleet_backend.load(buf, fleet)

    host = median_rate(run_host, host_docs, reps=3)
    from automerge_tpu import native
    if not native.available():
        return None, host      # no native codec: bulk path unavailable
    run_bulk()   # warmup compile
    bulk = median_rate(run_bulk, n_docs, reps=3)
    return bulk, host


def bench_backend_mixed(n_docs, n_changes=16, seed=0):
    """End-to-end seam rate on a REALISTIC document shape: nested config
    maps, rows-in-lists, strings/floats/bools — workloads that used to
    fall off the turbo path entirely (flat-int-only) and now ride the
    native parser's nested rows + value arena + seq-make rows. Returns
    (turbo changes/s, host changes/s)."""
    import jax
    import automerge_tpu as am
    from automerge_tpu import backend as Backend
    from automerge_tpu.fleet.backend import (
        DocFleet, init_docs, apply_changes_docs)
    rng = np.random.default_rng(seed)
    d = am.from_({'cfg': {'name': 'base', 'opts': {'depth': 1}},
                  'tags': {}, 'todo': [{'t': 'first', 'done': False}],
                  'n': 0, 'rate': 1.5, 'on': True}, 'ab' * 16)
    for c in range(n_changes - 1):
        k = f'k{int(rng.integers(0, 12))}'

        def edit(r, c=c, k=k):
            r['cfg']['opts'][k] = f'value-{c}'
            r['tags'][k] = float(c) if c % 3 else c
            r['n'] = c
            if c % 4 == 0:
                r['todo'].append({'t': f'task-{c}', 'done': False})
            else:
                r['todo'][0]['done'] = c % 2 == 1
        d = am.change(d, edit)
    changes = [bytes(b) for b in am.get_all_changes(d)]
    per_doc = [list(changes) for _ in range(n_docs)]
    n_total = n_changes * n_docs
    # ops per change differs from the flat-int headline's 1: report it so
    # the changes/s gap between the two seams can be read per-op
    from automerge_tpu.columnar import decode_change
    ops_per_change = sum(len(decode_change(b)['ops'])
                         for b in changes) / len(changes)

    def run():
        fleet = DocFleet(doc_capacity=n_docs, key_capacity=64)
        handles = init_docs(n_docs, fleet)
        handles, _ = apply_changes_docs(handles, per_doc, mirror=False)
        assert fleet.metrics.fallbacks == 0 and fleet.metrics.turbo_calls
        if fleet.state is not None:
            jax.block_until_ready(fleet.state.winners)

    run()
    rate = median_rate(run, n_total, reps=3)
    host_docs = max(n_docs // 50, 1)

    def run_host():
        for _ in range(host_docs):
            backend = Backend.init()
            Backend.apply_changes(backend, changes)
    host = median_rate(run_host, n_changes * host_docs, reps=3)
    return rate, host, ops_per_change


def bench_native_save(n_changes=200, seed=0):
    """Mirror-free native save (C++ change-log replay + canonical encode)
    vs the host OpSet replay + Python encode, same change log. Returns
    (native saves/s, host saves/s) or (None, host) without the codec."""
    from automerge_tpu import native
    from automerge_tpu import backend as Backend
    from automerge_tpu.backend.op_set import OpSet
    from automerge_tpu.columnar import encode_change, decode_change_meta
    rng = np.random.default_rng(seed)
    A = 'cc' * 16
    changes, heads = [], []
    for c in range(n_changes):
        ops = [{'action': 'set', 'obj': '_root', 'key': f'k{int(k)}',
                'value': int(rng.integers(0, 1 << 20)), 'datatype': 'int',
                'pred': []} for k in rng.integers(0, 64, size=8)]
        buf = encode_change({'actor': A, 'seq': c + 1, 'startOp': c * 8 + 1,
                             'time': 0, 'message': '', 'deps': heads,
                             'ops': ops})
        heads = [decode_change_meta(buf, True)['hash']]
        changes.append(buf)

    def run_host():
        ops = OpSet()
        ops.apply_changes(list(changes))
        ops.binary_doc = None
        ops.save()
    host = median_rate(run_host, 1, reps=3)
    if not native.available():
        return None, host

    def run_native():
        assert native.build_document(changes, heads) is not None
    return median_rate(run_native, 1, reps=3), host


def _fence():
    """Collect cyclic garbage between bench sections. Fleets sit in
    engine<->fleet reference cycles, so a finished section's device pools
    and multi-million-object host heap stay live until a gen-2 collection;
    left to chance, the NEXT section pays for them (gen-2 pauses mid-rep,
    device memory pressure). The round-5 on-chip run measured the mixed
    seam 10x slower inside the full suite than standalone for exactly
    this cross-section bleed."""
    import gc
    gc.collect()


# ---------------------------------------------------------------------------
# Sections: each runs standalone (BENCH_SECTION=<name>) or as part of the
# full pass, writes its results into R, and prints its own stderr lines.
# ---------------------------------------------------------------------------

R = {}
SECTIONS = {}
# section name -> R key whose full-run and standalone values must agree
# within 2x (the BENCH_SANITY contract)
SANITY_KEYS = {'seam': 'seam_rate', 'registers': 'reg_rate',
               'mixed': 'mixed_rate', 'seam_dense': 'seam_dense_rate',
               'observability': 'obs_off_rate',
               'service': 'service_clean_rps',
               # recovery rate, not materialize-us: the latter is NaN on
               # hosts without the native codec, which the sanity ratio
               # would turn into an unconditional FAIL
               'storage': 'storage_recovery_docs_per_s',
               # park throughput over the mmap arena: a pure host+disk
               # rate, stable across run order
               'storage_tier': 'tier_park_docs_per_s',
               'query': 'query_materialize_docs_per_s',
               # render throughput, not the overhead percentage: the
               # paired delta is a noise-sensitive difference that can
               # legitimately cross zero run to run
               'slo': 'slo_render_series_per_s',
               # the paced aggregate rate: cadence-bound, so stable
               # across run order by construction
               'shards': 'shards_rps_4',
               # the perf plane's throughput twin of obs_off_rate (the
               # overhead percentage itself is a noise-sensitive paired
               # delta, same reason the slo section pins throughput)
               'perf': 'perf_off_rate',
               # the ISSUE-20 acceptance number itself: a paired delta,
               # so `_pct` keys compare by ABSOLUTE difference (<= 2
               # percentage points) rather than the 2x ratio — a paired
               # overhead near zero legitimately crosses zero run to
               # run, which would blow up a max/min ratio
               'control': 'control_overhead_pct',
               # the gate's deterministic synthetic self-test: 1 in any
               # healthy tree, full-run and standalone alike
               'regress': 'regress_check_ok',
               # the depth-flatness RATIO (two p50s from one process):
               # ~1.0 in a healthy tree and self-normalizing against box
               # load, unlike the raw millisecond legs
               'frontier': 'frontier_depth_ratio',
               # links served per second at the top leg: a throughput
               # rate, stable across run order like the other rates
               'sync_fabric': 'fabric_links_per_s'}


def section(name):
    def deco(fn):
        SECTIONS[name] = fn
        return fn
    return deco


def _env(name, default):
    return int(os.environ.get(name, default))


def _interval_union_us(spans):
    """Total microseconds covered by the union of (ts, ts+dur) intervals."""
    ivs = sorted((s['ts'], s['ts'] + s['dur']) for s in spans)
    total = 0.0
    cur_lo = cur_hi = None
    for lo, hi in ivs:
        if cur_hi is None or lo > cur_hi:
            if cur_hi is not None:
                total += cur_hi - cur_lo
            cur_lo, cur_hi = lo, hi
        else:
            cur_hi = max(cur_hi, hi)
    if cur_hi is not None:
        total += cur_hi - cur_lo
    return total


def _measure_pipeline_overlap(n_docs, n_keys, sub_batches):
    """Run ONE pipelined seam batch under the span rig and measure, from
    the exported Perfetto trace, how much parse wall-clock (native_parse /
    per-slice parse_chunk spans, background + pool threads) overlaps the
    gate/commit/stage/dispatch phases of the PREVIOUS sub-batch (main
    thread). Returns (overlap_ms, dispatch_overlap_ms, parse_ms,
    main_thread_parse_stall_ms, trace_path or None) — the acceptance
    evidence that sub-batch k+1's parse tiles under sub-batch k's
    pipeline tail instead of serializing behind it."""
    from automerge_tpu import observability as obs
    from automerge_tpu.columnar import encode_change, decode_change_meta
    from automerge_tpu.fleet.backend import (
        DocFleet, init_docs, apply_changes_docs_pipelined)
    rng = np.random.default_rng(7)
    actors = ['aa' * 16, 'bb' * 16]
    changes, heads = [], []
    seqs = [0, 0]
    for c in range(20):
        a = c % 2
        seqs[a] += 1
        buf = encode_change({
            'actor': actors[a], 'seq': seqs[a], 'startOp': c + 1,
            'time': 0, 'message': '', 'deps': heads,
            'ops': [{'action': 'set', 'obj': '_root',
                     'key': f'k{int(rng.integers(0, n_keys))}',
                     'value': int(rng.integers(1, 1 << 20)),
                     'datatype': 'int', 'pred': []}]})
        heads = [decode_change_meta(buf, True)['hash']]
        changes.append(buf)
    per_doc = [list(changes) for _ in range(n_docs)]
    # warmup universe: compile the dispatch shapes so the traced batch
    # shows steady-state phase widths, not one giant XLA compile
    warm = DocFleet(doc_capacity=n_docs, key_capacity=n_keys + 1)
    apply_changes_docs_pipelined(init_docs(n_docs, warm), per_doc,
                                 sub_batches=sub_batches)
    del warm
    _fence()
    fleet = DocFleet(doc_capacity=n_docs, key_capacity=n_keys + 1)
    handles = init_docs(n_docs, fleet)
    obs.enable()
    obs.clear_spans()
    apply_changes_docs_pipelined(handles, per_doc, sub_batches=sub_batches)
    trace_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              'traces', 'seam_pipeline_trace.json')
    try:
        events = obs.export_chrome_trace(trace_path)
    except OSError:
        events = obs.export_chrome_trace()
        trace_path = None
    obs.disable()
    parse_spans = [e for e in events
                   if e['name'] in ('native_parse', 'parse_chunk')]

    def overlap_with(names):
        civs = sorted((s['ts'], s['ts'] + s['dur']) for s in events
                      if s['name'] in names)
        total = 0.0
        for p in parse_spans:
            lo, hi = p['ts'], p['ts'] + p['dur']
            if p['name'] == 'parse_chunk':
                continue   # slices nest inside native_parse: no double count
            for clo, chi in civs:
                o = min(hi, chi) - max(lo, clo)
                if o > 0:
                    total += o
        return total

    parse_us = _interval_union_us(parse_spans)
    # A prefetched parse can only coincide with the PREVIOUS sub-batch
    # (its own gate/commit start after it completes), so overlap with
    # these phase names IS overlap with sub-batch k's pipeline tail.
    overlap_us = overlap_with(('turbo_gate', 'turbo_commit', 'turbo_stage',
                               'turbo_dispatch'))
    dispatch_us = overlap_with(('turbo_dispatch',))
    # "No serial gap": with the parse prefetched, the main thread's
    # turbo_parse phase collapses to a table lookup for every sub-batch
    # after the first — this is the direct evidence the parse no longer
    # serializes the pipeline (the round-6 4-chunk path's failure mode).
    stalls = sorted(e['dur'] for e in events if e['name'] == 'turbo_parse')
    stall_us = sum(stalls[:-1]) if len(stalls) > 1 else 0.0
    del fleet, handles, per_doc
    _fence()
    return (overlap_us / 1000.0, dispatch_us / 1000.0, parse_us / 1000.0,
            stall_us / 1000.0, trace_path)


@section('seam')
def _sec_seam():
    # HEADLINE: end-to-end Backend seam (wire -> hash graph + causal gate ->
    # native parse -> device merge), median over reps. Measured single-shot
    # AND pipelined (the native multi-core parse of sub-batch k+1
    # overlapping the host commit + device dispatch of sub-batch k via
    # apply_changes_docs_pipelined); the headline is the better of the
    # two — both are the identical public pipeline.
    # 10k docs = the BASELINE.json north-star config ("changes/sec on a
    # 10k-doc concurrent-merge batch")
    n_keys = _env('BENCH_KEYS', 1000)
    seam_docs = _env('BENCH_SEAM_DOCS', 10000)
    seam_chunks = _env('BENCH_SEAM_CHUNKS', 4)
    seam_rate_1, info1 = bench_backend_pipeline(seam_docs, n_keys, 20)
    seam_rate_k, infok = bench_backend_pipeline(seam_docs, n_keys, 20,
                                                chunks=seam_chunks)
    seam_rate = max(seam_rate_1, seam_rate_k)
    # Cross-round continuity: rounds 1-3 measured the seam at 2000 docs
    seam_rate_2k, _ = bench_backend_pipeline(2000, n_keys, 20)
    from automerge_tpu import native as _native
    R.update(seam_rate=seam_rate, seam_rate_1=seam_rate_1,
             seam_rate_k=seam_rate_k, seam_rate_2k=seam_rate_2k,
             seam_docs=seam_docs, seam_native_threads=_native.native_threads(),
             seam_init_dispatches=info1['init_dispatches'],
             seam_dispatches_per_round=info1['apply_dispatches'] /
             info1['rounds'],
             seam_pipeline_dispatches_per_round=infok['apply_dispatches'] /
             infok['rounds'])
    print(f'# HEADLINE backend-seam end-to-end (turbo, incl. hash graph, '
          f'{seam_docs}-doc north-star config, '
          f'{_native.native_threads()} native threads): '
          f'{seam_rate:.0f} changes/s (median of {REPS}; single-dispatch '
          f'{seam_rate_1:.0f}, {seam_chunks}-sub-batch pipelined '
          f'{seam_rate_k:.0f}; rounds 1-3 config at 2000 docs: '
          f'{seam_rate_2k:.0f})', file=sys.stderr)
    print(f'# seam dispatch accounting ({seam_docs} docs): '
          f'{info1["init_dispatches"]} dispatches for init_docs, '
          f'{info1["apply_dispatches"] / info1["rounds"]:.1f} '
          f'dispatches/apply round single-shot, '
          f'{infok["apply_dispatches"] / infok["rounds"]:.1f} per pipelined '
          f'sub-batch (O(1), size-independent)',
          file=sys.stderr)
    # Overlap proof: the span-rig trace must show sub-batch k+1's parse
    # running concurrently with sub-batch k's pipeline tail — no serial
    # gap (ISSUE 6 acceptance). On this box the prefetched parse usually
    # finishes INSIDE the previous gate phase (hidden even before the
    # dispatch); the dispatch-phase share is reported separately.
    overlap_ms, dispatch_ms, parse_ms, stall_ms, trace_path = \
        _measure_pipeline_overlap(seam_docs, n_keys, seam_chunks)
    R.update(pipeline_overlap_ms=overlap_ms,
             pipeline_dispatch_overlap_ms=dispatch_ms,
             pipeline_parse_ms=parse_ms,
             pipeline_parse_stall_ms=stall_ms)
    print(f'# pipelined-parse overlap: {overlap_ms:.1f} ms of sub-batch '
          f'k+1 parse concurrent with sub-batch k\'s gate/commit/dispatch '
          f'({dispatch_ms:.1f} ms of it under the device-dispatch phase; '
          f'parse total {parse_ms:.1f} ms, main-thread parse stall past '
          f'sub-batch 0: {stall_ms:.2f} ms = no serial gap'
          f'{", trace " + trace_path if trace_path else ""})',
          file=sys.stderr)


@section('seam_commit')
def _sec_seam_commit():
    # Host commit-phase breakdown (ISSUE-12 "melt the serial floor"):
    # ONE steady-state seam batch under the span rig, tiled into the
    # turbo phase spans (setup/parse/gate/commit/stage/dispatch — they
    # tile the batch interval with no unattributed gap), reported as ms
    # per phase. The COMMIT phase is the columnar scatter (struct-of-
    # arrays doc state + lazily-folded log segments) and the GATE phase
    # is the native am_turbo_gate call — the two serial-floor terms this
    # round melts; the per-doc fallback counter proves the fast path ran
    # with ZERO per-doc commit-loop iterations, and the dispatch count
    # pins the O(1)-dispatch contract alongside the phase widths.
    from automerge_tpu import observability as obs
    from automerge_tpu.columnar import encode_change, decode_change_meta
    from automerge_tpu.fleet.backend import (
        DocFleet, init_docs, apply_changes_docs)
    import jax
    n_keys = _env('BENCH_KEYS', 1000)
    n_docs = _env('BENCH_SEAM_DOCS', 10000)
    rng = np.random.default_rng(11)
    actors = ['aa' * 16, 'bb' * 16]
    changes, heads = [], []
    seqs = [0, 0]
    for c in range(20):
        a = c % 2
        seqs[a] += 1
        buf = encode_change({
            'actor': actors[a], 'seq': seqs[a], 'startOp': c + 1,
            'time': 0, 'message': '', 'deps': heads,
            'ops': [{'action': 'set', 'obj': '_root',
                     'key': f'k{int(rng.integers(0, n_keys))}',
                     'value': int(rng.integers(1, 1 << 20)),
                     'datatype': 'int', 'pred': []}]})
        heads = [decode_change_meta(buf, True)['hash']]
        changes.append(buf)
    per_doc = [list(changes) for _ in range(n_docs)]
    # warmup universe: steady-state phase widths, not XLA compiles
    warm = DocFleet(doc_capacity=n_docs, key_capacity=n_keys + 1)
    apply_changes_docs(init_docs(n_docs, warm), per_doc, mirror=False)
    jax.block_until_ready(warm.state.winners)
    del warm
    _fence()
    fleet = DocFleet(doc_capacity=n_docs, key_capacity=n_keys + 1)
    handles = init_docs(n_docs, fleet)
    d0 = fleet.metrics.dispatches
    f0 = fleet.metrics.turbo_commit_fallback_docs
    obs.enable()
    obs.clear_spans()
    t0 = time.perf_counter()
    apply_changes_docs(handles, per_doc, mirror=False)
    jax.block_until_ready(fleet.state.winners)
    wall_ms = (time.perf_counter() - t0) * 1000.0
    obs.disable()
    phases = {}
    for s in obs.iter_spans():
        if s['name'].startswith('turbo_'):
            key = s['name'][len('turbo_'):]
            phases[key] = phases.get(key, 0.0) + s['dur_ns'] / 1e6
    dispatches = fleet.metrics.dispatches - d0
    fallback_docs = fleet.metrics.turbo_commit_fallback_docs - f0
    commit_ms = phases.get('commit', 0.0)
    rate = n_docs * 20 / (wall_ms / 1000.0)
    R.update(seam_commit_rate=rate,
             seam_commit_phase_ms={k: round(v, 2)
                                   for k, v in sorted(phases.items())},
             seam_commit_wall_ms=round(wall_ms, 1),
             seam_commit_ms=round(commit_ms, 2),
             seam_commit_dispatches=dispatches,
             seam_commit_fallback_docs=fallback_docs)
    breakdown = ', '.join(f'{k} {v:.1f}' for k, v in
                          sorted(phases.items(),
                                 key=lambda kv: -kv[1]))
    print(f'# seam_commit phase breakdown ({n_docs} docs x 20 changes, '
          f'one traced steady-state batch, {wall_ms:.0f} ms wall): '
          f'{breakdown} ms; commit phase {commit_ms:.1f} ms, '
          f'{dispatches} device dispatch(es), '
          f'{fallback_docs} per-doc commit-loop fallback iterations '
          f'(columnar fast path = 0)', file=sys.stderr)


@section('seam_threads')
def _sec_seam_threads():
    # Thread-scaling sweep: the single-shot seam at a 1/2/4-lane native
    # parse pool (the multi-core contract's measured curve; BASELINE.md
    # "Multi-core contract"). Determinism makes the pool width a pure
    # perf knob, so the SAME workload runs at each width. Widths past the
    # machine's cores are still recorded — the curve's flattening point
    # is the evidence of core saturation (this box reports os.cpu_count
    # in the JSON for that reason).
    from automerge_tpu import native as _native
    n_keys = _env('BENCH_KEYS', 1000)
    seam_docs = _env('BENCH_SEAM_DOCS', 10000)
    sweep = {}
    default = _native.native_threads()
    for t in (1, 2, 4):
        _native.set_native_threads(t)
        rate, _ = bench_backend_pipeline(seam_docs, n_keys, 20)
        sweep[str(t)] = rate
        _fence()
    _native.set_native_threads(default)
    R.update(seam_thread_scaling=sweep, bench_cpus=os.cpu_count())
    base = sweep['1']
    scaled = ', '.join(f'{t}T {r:.0f} ({r / base:.2f}x)'
                       for t, r in sweep.items())
    print(f'# seam thread-scaling sweep ({seam_docs} docs, single-shot, '
          f'{os.cpu_count()} cpus visible): {scaled}', file=sys.stderr)


@section('host')
def _sec_host():
    # Host reference engine on the same workload shape (rate-based).
    # 500 docs x 20 changes (round-4 VERDICT weak #3): the host engine
    # is linear per doc — measured flat between 20 and 500 docs — but a
    # 20-doc extrapolation was not apples-to-apples with the 10k-doc
    # fleet run; 500 docs at the seam's exact per-doc change count keeps
    # the denominator honest.
    host_rate, _ = bench_host(_env('BENCH_HOST_DOCS', 500),
                              _env('BENCH_KEYS', 1000), 1, 20)
    R['host_rate'] = host_rate
    print(f'# host reference engine (CPython, full pipeline): '
          f'{host_rate:.0f} changes/s', file=sys.stderr)


@section('seam_text')
def _sec_seam_text():
    # End-to-end text editing through the seam (config 2, honest number)
    seam_text_rate, host_text_rate = bench_backend_text(
        _env('BENCH_SEAM_TEXT_DOCS', 200), _env('BENCH_SEAM_TEXT_LEN', 512))
    R.update(seam_text_rate=seam_text_rate, host_text_rate=host_text_rate)
    print(f'# backend-seam text editing end-to-end: '
          f'{seam_text_rate:.0f} ops/s (median of {REPS}) vs host '
          f'{host_text_rate:.0f} ops/s '
          f'({seam_text_rate / host_text_rate:.1f}x)', file=sys.stderr)


@section('kernel_merge')
def _sec_kernel_merge():
    # KERNEL-ONLY numbers (device ceilings on pre-built batches — NOT
    # end-to-end; decode/hashing excluded):
    fleet_rate, _ = bench_fleet(_env('BENCH_DOCS', 10000),
                                _env('BENCH_KEYS', 1000),
                                _env('BENCH_ROUNDS', 10),
                                _env('BENCH_OPS', 100))
    R['fleet_rate'] = fleet_rate
    print(f'# kernel-only device merge (pre-built batches): '
          f'{fleet_rate:.0f} ops/s', file=sys.stderr)


@section('pallas')
def _sec_pallas():
    pallas_rate, pallas_variant = bench_pallas_merge(
        _env('BENCH_DOCS', 10000), _env('BENCH_KEYS', 1000),
        _env('BENCH_ROUNDS', 10), _env('BENCH_OPS', 100))
    R.update(pallas_rate=pallas_rate, pallas_variant=pallas_variant)
    if pallas_rate is not None:
        vs = f' ({pallas_rate / R["fleet_rate"]:.2f}x the jnp scatter ' \
             f'path)' if R.get('fleet_rate') else ''
        print(f'# fused pallas merge kernel ({pallas_variant}, '
              f'interpret=False, differentially checked): '
              f'{pallas_rate:.0f} ops/s{vs}', file=sys.stderr)


@section('kernel_pipe')
def _sec_kernel_pipe():
    pipe_rate, _ = bench_pipeline(_env('BENCH_PIPE_DOCS', 500),
                                  _env('BENCH_KEYS', 1000), 20)
    R['pipe_rate'] = pipe_rate
    print(f'# kernel-only pipeline (native decode, no hash graph): '
          f'{pipe_rate:.0f} changes/s', file=sys.stderr)


@section('kernel_text')
def _sec_kernel_text():
    text_rate, _ = bench_text(_env('BENCH_TEXT_DOCS', 2000),
                              _env('BENCH_TEXT_LEN', 512))
    R['text_rate'] = text_rate
    print(f'# kernel-only sequence engine (packed text traces): '
          f'{text_rate:.0f} ops/s', file=sys.stderr)


@section('bloom')
def _sec_bloom():
    # Config 4: sync Bloom filters, device fleet vs per-peer host loop
    bloom_dev, bloom_host = bench_sync_bloom(
        _env('BENCH_BLOOM_DOCS', 10000), _env('BENCH_BLOOM_HASHES', 32))
    R.update(bloom_dev=bloom_dev, bloom_host=bloom_host)
    print(f'# sync bloom build+probe: device {bloom_dev:.0f} hashes/s, '
          f'host {bloom_host:.0f} hashes/s', file=sys.stderr)


@section('sync_driver')
def _sec_sync_driver():
    # Batched sync driver: one generate round over the whole peer fleet
    n = _env('BENCH_SYNCDRV_DOCS', 10000)
    syncdrv_batched, syncdrv_host, syncdrv_disp = bench_sync_driver(n)
    R.update(syncdrv_batched=syncdrv_batched, syncdrv_host=syncdrv_host,
             syncdrv_dispatches_per_round=syncdrv_disp)
    print(f'# batched sync driver, one {n}-peer generate round: '
          f'{syncdrv_batched:.0f} docs/s batched vs {syncdrv_host:.0f} '
          f'docs/s host loop ({syncdrv_batched / syncdrv_host:.1f}x); '
          f'{syncdrv_disp} Bloom device dispatches/round (O(1), '
          f'size-independent)', file=sys.stderr)


@section('faults')
def _sec_faults():
    # Fault-containment cost + health-counter reporting: one quarantine
    # round (N docs, 2 poisoned) vs the clean batch, and one lossy-wire
    # sync; per-round deltas of every registered health counter.
    from automerge_tpu import observability
    from automerge_tpu.columnar import encode_change
    from automerge_tpu.fleet import backend as fleet_backend
    from automerge_tpu.fleet.backend import DocFleet, init_docs
    n = _env('BENCH_FAULT_DOCS', 2000)

    def workload(count):
        # actors cycle under the 256-per-fleet cap; one change per doc
        return [[encode_change({
            'actor': f'{d % 128:04x}' * 4, 'seq': 1, 'startOp': 1,
            'time': 0, 'message': '', 'deps': [],
            'ops': [{'action': 'set', 'obj': '_root', 'key': 'k',
                     'value': d, 'datatype': 'int', 'pred': []}]})]
            for d in range(count)]

    warm = DocFleet()                      # JIT warmup for the dispatch shapes
    fleet_backend.apply_changes_docs(init_docs(n, warm), workload(n),
                                     mirror=False)

    fleet = DocFleet()
    handles = init_docs(n, fleet)
    per_doc = workload(n)
    for bad in (1, n // 2):
        buf = bytearray(per_doc[bad][0])
        buf[10] ^= 0xFF
        per_doc[bad] = [bytes(buf)]
    h0 = observability.health_counts()
    start = time.perf_counter()
    _, _, errors = fleet_backend.apply_changes_docs(
        handles, per_doc, mirror=False, on_error='quarantine')
    quarantine_rate = n / (time.perf_counter() - start)
    health_delta = {k: v for k, v in
                    observability.health_delta(h0).items() if v}

    fleet2 = DocFleet()
    handles2 = init_docs(n, fleet2)
    clean_doc = workload(n)
    start = time.perf_counter()
    fleet_backend.apply_changes_docs(handles2, clean_doc, mirror=False)
    clean_rate = n / (time.perf_counter() - start)
    R.update(quarantine_rate=quarantine_rate, clean_rate=clean_rate,
             quarantine_health=health_delta)
    print(f'# fault containment, {n}-doc round with 2 poisoned: '
          f'{quarantine_rate:.0f} docs/s quarantined vs {clean_rate:.0f} '
          f'docs/s clean ({quarantine_rate / clean_rate:.2f}x); '
          f'health counters this round: {health_delta} '
          f'(K rejected docs cost one host re-validate, zero extra '
          f'dispatches)', file=sys.stderr)


@section('durability')
def _sec_durability():
    # Crash-safe durability cost: journaled vs bare apply throughput at
    # the 10k-doc seam (the ISSUE-3 budget is <= 15% overhead), plus
    # recovery wall-clock vs fleet size (snapshot-chain stitch +
    # journal-suffix replay through the quarantining batch apply;
    # includes recovery's closing O(replayed) re-journal — the full
    # return-to-serving cost. The storage section benches this at the
    # crashtest scale with rep medians).
    import shutil
    import tempfile
    from automerge_tpu.columnar import encode_change
    from automerge_tpu.fleet import backend as fleet_backend
    from automerge_tpu.fleet.backend import DocFleet, init_docs
    from automerge_tpu.fleet.durability import DurableFleet
    n = _env('BENCH_DUR_DOCS', 10000)

    def workload(count):
        return [[encode_change({
            'actor': f'{d % 128:04x}' * 4, 'seq': 1, 'startOp': 1,
            'time': 0, 'message': '', 'deps': [],
            'ops': [{'action': 'set', 'obj': '_root', 'key': 'k',
                     'value': d, 'datatype': 'int', 'pred': []}]})]
            for d in range(count)]

    warm = DocFleet()                  # JIT warmup for the dispatch shapes
    fleet_backend.apply_changes_docs(init_docs(n, warm), workload(n),
                                     mirror=False)
    del warm
    _fence()

    # PAIRED interleaved reps: single-shot rates on this box swing 4-40%
    # with GC/page-cache state, so the overhead claim uses the median of
    # per-rep (on - off)/off deltas — pairing cancels the drift an
    # unpaired median-of-rates amplifies. The first pair is warmup and
    # discarded, and the rep floor is raised above the global default:
    # per-rep deltas here spread -20..+50% on a busy box, and even a
    # 9-rep median of that distribution wobbles by ~10 points.
    dur_reps = max(3 * REPS, 15)
    root = tempfile.mkdtemp(prefix='bench-dur-')
    try:
        off_rates, on_rates, strict_rates = [], [], []
        deltas, strict_deltas = [], []

        def settle():
            # flush background writeback OUTSIDE the timed regions: a
            # previous rep's dirty journal pages otherwise steal IO/CPU
            # from the next timed section (measured as fake overhead)
            _fence()
            try:
                os.sync()
            except (AttributeError, OSError):
                pass

        for rep in range(dur_reps + 1):
            fleet = DocFleet()
            handles = init_docs(n, fleet)
            per_doc = workload(n)
            settle()
            start = time.perf_counter()
            fleet_backend.apply_changes_docs(handles, per_doc, mirror=False)
            off_s = time.perf_counter() - start
            del fleet, handles, per_doc

            # group-commit config (fsync batching, the deployable default
            # for a batched seam: one fsync per fsync_bytes of journal)
            mgr = DurableFleet(os.path.join(root, f'seam{rep}'),
                               compact_bytes=1 << 40,  # no mid-run compact
                               fsync_bytes=4 << 20)
            handles = mgr.init_docs(n)
            per_doc = workload(n)
            settle()
            start = time.perf_counter()
            fleet_backend.apply_changes_docs(handles, per_doc,
                                             mirror=False)
            on_s = time.perf_counter() - start
            mgr.close()
            del mgr, handles, per_doc
            if rep == 0:
                continue
            off_rates.append(n / off_s)
            on_rates.append(n / on_s)
            deltas.append(on_s - off_s)
        # strict config: fsync on EVERY group commit (zero loss window).
        # Benched in its own loop against the paired baseline medians —
        # interleaving it into the A/B pairs entangles its fsyncs with
        # the other configs' writeback on ordered-mode filesystems.
        off_s_med = float(np.median([n / r for r in off_rates]))
        for rep in range(max(dur_reps // 2, 3) + 1):
            mgr = DurableFleet(os.path.join(root, f'strict{rep}'),
                               compact_bytes=1 << 40)
            handles = mgr.init_docs(n)
            per_doc = workload(n)
            settle()
            start = time.perf_counter()
            fleet_backend.apply_changes_docs(handles, per_doc,
                                             mirror=False)
            strict_s = time.perf_counter() - start
            mgr.close()
            del mgr, handles, per_doc
            if rep == 0:
                continue
            strict_rates.append(n / strict_s)
            strict_deltas.append(strict_s - off_s_med)
        off_rate = float(np.median(off_rates))
        on_rate = float(np.median(on_rates))
        strict_rate = float(np.median(strict_rates))
        # overhead = median ABSOLUTE per-pair delta over the median bare
        # time: a per-rep ratio explodes whenever the off-leg of one pair
        # stalls (this box stalls whole reps by 2-5x), while the paired
        # difference cancels shared drift and the median kills outliers
        off_med_s = n / off_rate
        overhead = float(np.median(deltas)) / off_med_s * 100.0
        strict_overhead = float(np.median(strict_deltas)) / off_med_s * 100.0

        recovery = {}
        for size in sorted({max(n // 10, 100), n}):
            path = os.path.join(root, f'rec{size}')
            m = DurableFleet(path, compact_bytes=1 << 40)
            hs = m.init_docs(size)
            hs, _p = m.apply_changes(hs, workload(size), on_error='raise')
            m.checkpoint()
            hs, _p = m.apply_changes(hs, [
                [encode_change({
                    'actor': f'{d % 128:04x}' * 4, 'seq': 2, 'startOp': 2,
                    'time': 0, 'message': '',
                    'deps': fleet_backend.get_heads(hs[d]),
                    'ops': [{'action': 'set', 'obj': '_root', 'key': 'k2',
                             'value': d, 'datatype': 'int', 'pred': []}]})]
                for d in range(size)], on_error='raise')
            m.close()
            start = time.perf_counter()
            m2, _rec, report = DurableFleet.recover(path)
            recovery[size] = time.perf_counter() - start
            # guard the measurement itself: recovery must have loaded the
            # snapshot AND replayed the journal suffix (a frozen-handle
            # bug here once timed snapshot-load only)
            assert report.snapshot_docs == size and \
                report.replayed_records == size and not \
                report.quarantined, report
            m2.close()
            _fence()
    finally:
        shutil.rmtree(root, ignore_errors=True)

    R.update(dur_on_rate=on_rate, dur_off_rate=off_rate,
             dur_strict_rate=strict_rate,
             dur_overhead_pct=overhead,
             dur_strict_overhead_pct=strict_overhead,
             **{f'dur_recovery_{size}_s': secs
                for size, secs in recovery.items()})
    rec_str = ', '.join(f'{size} docs in {secs:.2f}s '
                        f'({size / secs:.0f} docs/s)'
                        for size, secs in sorted(recovery.items()))
    print(f'# durability: journal-on {on_rate:.0f} docs/s vs journal-off '
          f'{off_rate:.0f} docs/s at the {n}-doc seam '
          f'({overhead:+.1f}% overhead group-commit, budget 15%; '
          f'{strict_overhead:+.1f}% with fsync-every-commit at '
          f'{strict_rate:.0f} docs/s); recovery (snapshot load + '
          f'quarantining replay + re-journal): {rec_str}',
          file=sys.stderr)


@section('storage')
def _sec_storage():
    # Delta+main storage engine: (a) materialize cost — the native
    # change-list extractor (codec.cpp am_extract_changes) vs the Python
    # decode_document + encode_change round trip it replaces, PAIRED
    # interleaved reps over the same chunk set (BENCH_r08 methodology;
    # the acceptance bar is >= 5x vs the recorded ~700us/doc);
    # (b) durability recovery throughput at the crashtest scale —
    # snapshot load + journal-suffix replay + the O(replayed) re-journal
    # finish (acceptance >= 20k docs/s); (c) main-store residency —
    # per-doc host overhead from MainStore.memory_stats (acceptance:
    # measurably below the ~3.3 KB/doc of in-fleet parked residency).
    import shutil
    import tempfile
    from automerge_tpu import native
    from automerge_tpu.columnar import (decode_document, encode_change,
                                        decode_change_meta)
    from automerge_tpu.fleet import backend as fleet_backend
    from automerge_tpu.fleet.backend import DocFleet, init_docs
    from automerge_tpu.fleet.durability import DurableFleet
    from automerge_tpu.fleet.storage import StorageEngine

    n_docs = _env('BENCH_STORAGE_DOCS', 512)
    n_changes = _env('BENCH_STORAGE_CHANGES', 8)

    # one fleet of linear-history docs -> parked chunks
    fleet = DocFleet()
    handles = init_docs(n_docs, fleet)
    heads = [[] for _ in range(n_docs)]
    for c in range(n_changes):
        per_doc = []
        for d in range(n_docs):
            buf = encode_change({
                'actor': f'{d % 128:04x}' * 4, 'seq': c + 1,
                'startOp': 2 * c + 1, 'time': 0, 'message': '',
                'deps': heads[d],
                'ops': [{'action': 'set', 'obj': '_root', 'key': f'k{c}',
                         'value': d * 1000 + c, 'datatype': 'int',
                         'pred': []},
                        {'action': 'set', 'obj': '_root', 'key': 'hot',
                         'value': c, 'datatype': 'int', 'pred': []}]})
            heads[d] = [decode_change_meta(buf, True)['hash']]
            per_doc.append([buf])
        handles, _ = fleet_backend.apply_changes_docs(handles, per_doc,
                                                      mirror=False)
    chunks = [bytes(h['state'].save()) for h in handles]
    del fleet, handles
    _fence()

    # ---- (a) materialize: native extract vs Python decode+re-encode ----
    have_native = native.available()
    nat_times, py_times = [], []
    py_sample = max(n_docs // 8, 32)
    for rep in range(max(REPS, 5) + 1):
        if have_native:
            start = time.perf_counter()
            out = native.extract_changes(chunks)
            nat_s = time.perf_counter() - start
            assert out is not None and all(r is not None for r in out), \
                'extractor bailed on bench chunks'
        else:
            nat_s = float('nan')
        start = time.perf_counter()
        for chunk in chunks[:py_sample]:
            [encode_change(ch) for ch in decode_document(chunk)]
        py_s = time.perf_counter() - start
        if rep == 0:
            continue
        nat_times.append(nat_s / n_docs * 1e6)
        py_times.append(py_s / py_sample * 1e6)
    nat_us = float(np.median(nat_times)) if have_native else float('nan')
    py_us = float(np.median(py_times))
    speedup = py_us / nat_us if have_native else float('nan')

    # ---- (b) recovery throughput at the crashtest scale ----
    rec_n = _env('BENCH_STORAGE_RECOVERY_DOCS', 10000)
    root = tempfile.mkdtemp(prefix='bench-storage-')
    try:
        path = os.path.join(root, 'rec')
        m = DurableFleet(path, compact_bytes=1 << 40,
                         fsync_bytes=4 << 20)
        hs = m.init_docs(rec_n)
        per_doc = [[encode_change({
            'actor': f'{d % 128:04x}' * 4, 'seq': 1, 'startOp': 1,
            'time': 0, 'message': '', 'deps': [],
            'ops': [{'action': 'set', 'obj': '_root', 'key': 'k',
                     'value': d, 'datatype': 'int', 'pred': []}]})]
            for d in range(rec_n)]
        hs, _p = m.apply_changes(hs, per_doc, on_error='raise')
        m.checkpoint()
        hs, _p = m.apply_changes(hs, [
            [encode_change({
                'actor': f'{d % 128:04x}' * 4, 'seq': 2, 'startOp': 2,
                'time': 0, 'message': '',
                'deps': fleet_backend.get_heads(hs[d]),
                'ops': [{'action': 'set', 'obj': '_root', 'key': 'k2',
                         'value': d, 'datatype': 'int', 'pred': []}]})]
            for d in range(rec_n)], on_error='raise')
        m.close()
        _fence()
        # median over reps, each on a fresh COPY of the directory
        # (recovery rewrites the journal generation; page-cache state is
        # shared so reps measure compute, not cold reads) — single-shot
        # recovery on this box swings ±40% with writeback state
        rec_times = []
        for rep in range(max(REPS, 5) + 1):
            dst = os.path.join(root, f'rec-rep{rep}')
            shutil.copytree(path, dst)
            _fence()
            start = time.perf_counter()
            m2, _rec, report = DurableFleet.recover(dst)
            rec_rep_s = time.perf_counter() - start
            assert report.snapshot_docs == rec_n and \
                report.replayed_records == rec_n and not \
                report.quarantined, report
            m2.close()
            shutil.rmtree(dst, ignore_errors=True)
            if rep == 0:
                continue
            rec_times.append(rec_rep_s)
        rec_s = float(np.median(rec_times))
    finally:
        shutil.rmtree(root, ignore_errors=True)
    rec_rate = rec_n / rec_s
    _fence()

    # ---- (c) main-store residency ----
    eng = StorageEngine(DocFleet())
    eng.ingest_chunks(chunks)
    stats = eng.memory_stats()
    overhead_per_doc = stats['overhead_per_doc']
    chunk_per_doc = stats['chunk_bytes'] / stats['n_docs']
    del eng
    _fence()

    R.update(storage_materialize_native_us=nat_us,
             storage_materialize_python_us=py_us,
             storage_materialize_speedup=speedup,
             storage_recovery_docs_per_s=rec_rate,
             storage_recovery_s=rec_s,
             storage_recovery_docs=rec_n,
             storage_overhead_bytes_per_doc=overhead_per_doc,
             storage_chunk_bytes_per_doc=chunk_per_doc)
    print(f'# storage: materialize {nat_us:.0f}us/doc native vs '
          f'{py_us:.0f}us/doc python ({speedup:.1f}x, {n_changes} '
          f'changes/doc); recovery {rec_n} docs in {rec_s:.2f}s '
          f'({rec_rate:.0f} docs/s); main-store residency '
          f'{overhead_per_doc:.0f} B/doc overhead + '
          f'{chunk_per_doc:.0f} B/doc chunk', file=sys.stderr)


@section('storage_tier')
def _sec_storage_tier():
    # Mmap-backed MainStore + cost-based tiering (ISSUE-15): the chunk
    # arena on disk under the RAM-resident causal index. Measures
    # (a) park (bulk ingest) throughput at BENCH_TIER_DOCS (default 1M;
    # raise to 10M for the full residency headline), with RSS growth and
    # resident-per-doc against the acceptance ceiling; (b) revive and
    # materialize_at throughput off the mapped arena, WARM page cache,
    # against a RAM-resident-arena baseline at the same batch scale
    # (acceptance: >= 0.8x); (c) the COLD leg — posix_fadvise DONTNEED
    # drops the arena's pages, major-fault delta recorded, revive
    # re-measured from actual disk.
    import shutil
    import tempfile
    from automerge_tpu.columnar import DocChunkView, decode_change_meta, \
        encode_change
    from automerge_tpu.fleet import backend as fleet_backend
    from automerge_tpu.fleet.backend import DocFleet, init_docs
    from automerge_tpu.fleet.storage import StorageEngine
    from automerge_tpu.observability.perf import page_fault_counts, \
        rss_bytes
    from automerge_tpu.query import materialize_at_docs

    n_docs = _env('BENCH_TIER_DOCS', 1_000_000)
    distinct = min(_env('BENCH_TIER_DISTINCT', 2048), n_docs)
    ram_n = min(n_docs, _env('BENCH_TIER_RAM_DOCS', 100_000))
    revive_batch = min(_env('BENCH_TIER_REVIVE', 1024), distinct)
    mat_batch = min(_env('BENCH_TIER_MAT', 256), distinct)

    # corpus: `distinct` two-change linear docs, causal rows precomputed
    # once (the arena append + lane install per doc stay honest; only
    # the header decode is memoized across the repeats)
    fleet = DocFleet()
    handles = init_docs(distinct, fleet)
    frontier = [[] for _ in range(distinct)]
    for c in range(2):
        per_doc = []
        for d in range(distinct):
            buf = encode_change({
                'actor': f'{d % 128:04x}' * 4, 'seq': c + 1,
                'startOp': c + 1, 'time': 0, 'message': '',
                'deps': frontier[d],
                'ops': [{'action': 'set', 'obj': '_root', 'key': f'k{c}',
                         'value': d * 1000 + c, 'datatype': 'int',
                         'pred': []}]})
            frontier[d] = [decode_change_meta(buf, True)['hash']]
            per_doc.append([buf])
        handles, _ = fleet_backend.apply_changes_docs(handles, per_doc,
                                                      mirror=False)
    chunks = [bytes(h['state'].save()) for h in handles]
    rows = [(v.heads, v.clock, v.max_op, v.n_changes)
            for v in (DocChunkView(c) for c in chunks)]
    fleet_backend.free_docs(handles)
    del handles
    _fence()

    def ingest_all(eng, n):
        start = time.perf_counter()
        i = 0
        while i < n:
            k = min(distinct, n - i)
            eng.ingest_chunks(chunks[:k], rows=rows[:k])
            i += k
        return n / (time.perf_counter() - start)

    def revive_rate(eng, windows, n):
        # clamp every window into the parked id range: a mid-range
        # BENCH_TIER_DOCS must shift the legs, not KeyError the section
        max_w = max(n // revive_batch - 1, 0)
        rates = []
        for w in windows:
            w = min(w, max_w)
            ids = list(range(w * revive_batch,
                             min((w + 1) * revive_batch, n)))
            start = time.perf_counter()
            got = eng.revive(ids)
            rate = len(ids) / (time.perf_counter() - start)
            eng.repark(got, ids)       # restore the store for the next leg
            rates.append(rate)
        return float(np.median(rates))

    def mat_rate(eng, eng_fleet, base, n):
        base = max(0, min(base, n - mat_batch))
        sources = [(eng, base + i) for i in range(mat_batch)]
        heads_list = [eng.heads(base + i) for i in range(mat_batch)]
        rates = []
        for _ in range(3):
            start = time.perf_counter()
            outs = materialize_at_docs(sources, heads_list, fleet=eng_fleet)
            rates.append(mat_batch / (time.perf_counter() - start))
            fleet_backend.free_docs(outs)
        return float(np.median(rates))

    # ---- RAM-resident baseline at the sub-scale ----
    ram_fleet = DocFleet()
    ram = StorageEngine(ram_fleet)
    ram_park = ingest_all(ram, ram_n)
    ram_revive = revive_rate(ram, [1, 3, 5], ram_n)
    ram_mat = mat_rate(ram, ram_fleet, 7 * revive_batch, ram_n)
    del ram, ram_fleet
    _fence()

    # ---- disk-backed engine at full scale ----
    root = tempfile.mkdtemp(prefix='bench-tier-')
    try:
        disk_fleet = DocFleet()
        eng = StorageEngine(disk_fleet, path=os.path.join(root, 'arena'))
        eng.main.reserve(n_docs)
        rss0 = rss_bytes()[0]
        tier_park = ingest_all(eng, n_docs)
        rss1 = rss_bytes()[0]
        stats = eng.memory_stats()
        tier_revive = revive_rate(eng, [1, 3, 5], n_docs)
        tier_mat = mat_rate(eng, disk_fleet, 7 * revive_batch, n_docs)
        # cold leg: drop the arena's clean pages, read from actual disk
        mn0, mj0 = page_fault_counts()
        eng.main._arena.advise_cold()
        tier_revive_cold = revive_rate(eng, [9, 11, 13], n_docs)
        _mn1, mj1 = page_fault_counts()
        eng.close()
        del eng, disk_fleet
    finally:
        shutil.rmtree(root, ignore_errors=True)
    _fence()

    R.update(tier_docs=n_docs,
             tier_park_docs_per_s=tier_park,
             tier_revive_docs_per_s=tier_revive,
             tier_revive_cold_docs_per_s=tier_revive_cold,
             tier_materialize_docs_per_s=tier_mat,
             tier_ram_park_docs_per_s=ram_park,
             tier_ram_revive_docs_per_s=ram_revive,
             tier_ram_materialize_docs_per_s=ram_mat,
             tier_park_ratio=tier_park / ram_park,
             tier_revive_ratio=tier_revive / ram_revive,
             tier_materialize_ratio=tier_mat / ram_mat,
             tier_resident_bytes_per_doc=stats['resident_per_doc'],
             tier_rss_grow_bytes=max(0, rss1 - rss0),
             tier_disk_bytes=stats['disk_bytes'],
             tier_cold_major_faults=mj1 - mj0)
    print(f'# storage_tier: {n_docs} docs on disk — park {tier_park:.0f} '
          f'docs/s ({R["tier_park_ratio"]:.2f}x ram), revive warm '
          f'{tier_revive:.0f} docs/s ({R["tier_revive_ratio"]:.2f}x ram) '
          f'/ cold {tier_revive_cold:.0f} docs/s '
          f'({mj1 - mj0} major faults), materialize '
          f'{tier_mat:.0f} docs/s ({R["tier_materialize_ratio"]:.2f}x '
          f'ram); resident {stats["resident_per_doc"]:.0f} B/doc, RSS '
          f'+{(rss1 - rss0) / (1 << 20):.0f} MiB, arena '
          f'{stats["disk_bytes"] / (1 << 20):.0f} MiB on disk',
          file=sys.stderr)


@section('observability')
def _sec_observability():
    # Tracing cost + attribution quality at the 10k-doc seam. Two
    # numbers: (a) spans+histograms enabled vs disabled, PAIRED reps with
    # the legs ALTERNATING order each pair (a fixed on-after-off order
    # biases the median several points through allocator/GC drift on this
    # box — measured +6.5% fixed-order vs -0.4% alternating for the SAME
    # build), median paired delta over the median off time, budget <= 2%;
    # (b) phase coverage — one traced batch's Chrome trace must account
    # for >= 90% of the measured batch wall-clock across the named host
    # phases (no unattributed gap), which is what makes the trace usable
    # for the ROADMAP's parse/merge-overlap attribution work.
    from automerge_tpu import observability as obs
    from automerge_tpu.columnar import encode_change
    from automerge_tpu.fleet import backend as fleet_backend
    from automerge_tpu.fleet.backend import DocFleet, init_docs
    n = _env('BENCH_OBS_DOCS', 10000)

    def workload(count):
        return [[encode_change({
            'actor': f'{d % 128:04x}' * 4, 'seq': 1, 'startOp': 1,
            'time': 0, 'message': '', 'deps': [],
            'ops': [{'action': 'set', 'obj': '_root', 'key': 'k',
                     'value': d, 'datatype': 'int', 'pred': []}]})]
            for d in range(count)]

    warm = DocFleet()
    fleet_backend.apply_changes_docs(init_docs(n, warm), workload(n),
                                     mirror=False)
    del warm
    _fence()

    def one(enabled):
        if enabled:
            obs.enable()
            obs.clear_spans()
        fleet = DocFleet()
        handles = init_docs(n, fleet)
        per_doc = workload(n)
        start = time.perf_counter()
        fleet_backend.apply_changes_docs(handles, per_doc, mirror=False)
        elapsed = time.perf_counter() - start
        if enabled:
            obs.disable()
        del fleet, handles, per_doc
        return elapsed

    obs_reps = max(2 * REPS, 12)
    off_times, on_times = [], []
    deltas = []
    for rep in range(obs_reps + 1):
        if rep % 2:
            on_s = one(True)
            off_s = one(False)
        else:
            off_s = one(False)
            on_s = one(True)
        if rep == 0:
            continue
        off_times.append(off_s)
        on_times.append(on_s)
        deltas.append(on_s - off_s)
    off_med = float(np.median(off_times))
    overhead = float(np.median(deltas)) / off_med * 100.0

    # phase coverage of one traced seam batch
    PHASES = ('turbo_setup', 'turbo_parse', 'turbo_gate', 'turbo_commit',
              'turbo_stage', 'turbo_dispatch', 'journal_append')
    obs.enable()
    fleet = DocFleet()
    handles = init_docs(n, fleet)
    per_doc = workload(n)
    obs.clear_spans()
    start = time.perf_counter()
    fleet_backend.apply_changes_docs(handles, per_doc, mirror=False)
    wall_ns = (time.perf_counter() - start) * 1e9
    trace_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              'traces', 'obs_host_trace.json')
    try:
        events = obs.export_chrome_trace(trace_path)
    except OSError:
        events = obs.export_chrome_trace()
        trace_path = None
    # Union of the phase intervals, NOT the sum of durations: with the
    # multi-core parse, spans from pool workers / the pipelined prefetch
    # thread legitimately run concurrently with the main thread's phases,
    # so summed durations can tile wall-time past 100% — the union keeps
    # "coverage" meaning "fraction of the batch wall accounted for".
    phase_ns = _interval_union_us(
        [e for e in events if e['name'] in PHASES]) * 1000.0
    coverage = phase_ns / wall_ns * 100.0
    hists = obs.histogram_snapshot()
    apply_p50 = (hists.get('apply_batch_s') or {}).get('p50')
    obs.disable()
    del fleet, handles, per_doc
    _fence()

    R.update(obs_off_rate=n / off_med,
             obs_on_rate=n / float(np.median(on_times)),
             obs_overhead_pct=overhead, obs_coverage_pct=coverage)
    print(f'# observability: spans+histograms on {R["obs_on_rate"]:.0f} '
          f'docs/s vs off {R["obs_off_rate"]:.0f} docs/s at the {n}-doc '
          f'seam ({overhead:+.2f}% overhead, paired alternating-order '
          f'medians, budget 2%); traced batch phase coverage '
          f'{coverage:.1f}% of wall (budget >= 90%'
          f'{", trace " + trace_path if trace_path else ""}); '
          f'apply_batch_s p50 {apply_p50}', file=sys.stderr)


@section('perf')
def _sec_perf():
    # Performance-observatory overhead (ISSUE-13 acceptance): the FULL
    # perf plane — seam baselines (histograms + per-rep drift tick),
    # kernel cost ledger, memory-watermark sampling — on vs off at the
    # seam, PAIRED reps with the legs alternating order each pair (the
    # same methodology as the observability/slo sections; fixed order
    # biases this box several points), budget <= 2%. Also dumps the
    # cost ledger for `obs_report --floor` and reports the watermark
    # highs the tiering ROADMAP item will consume.
    from automerge_tpu.columnar import decode_change_meta, encode_change
    from automerge_tpu.fleet import backend as fleet_backend
    from automerge_tpu.fleet.backend import DocFleet, init_docs
    from automerge_tpu.observability import perf as obs_perf
    from automerge_tpu.observability import hist as obs_hist
    n = _env('BENCH_PERF_DOCS', _env('BENCH_SEAM_DOCS', 10000))
    n_keys = _env('BENCH_KEYS', 1000)
    # the seam_commit workload shape (20 chained changes per doc): legs
    # run ~10x longer than the 1-change shape, which is what averages
    # this box's per-leg scheduling noise down far enough for a 2%
    # judgment to mean anything (the 1-change legs swing ±25% pair to
    # pair — the measurement lesson this PR's ledger exists to record)
    rng = np.random.default_rng(23)
    actors = ['aa' * 16, 'bb' * 16]
    changes, heads = [], []
    seqs = [0, 0]
    for c in range(20):
        a = c % 2
        seqs[a] += 1
        buf = encode_change({
            'actor': actors[a], 'seq': seqs[a], 'startOp': c + 1,
            'time': 0, 'message': '', 'deps': heads,
            'ops': [{'action': 'set', 'obj': '_root',
                     'key': f'k{int(rng.integers(0, n_keys))}',
                     'value': int(rng.integers(1, 1 << 20)),
                     'datatype': 'int', 'pred': []}]})
        heads = [decode_change_meta(buf, True)['hash']]
        changes.append(buf)

    def workload(count):
        return [list(changes) for _ in range(count)]

    warm = DocFleet(doc_capacity=n, key_capacity=n_keys + 1)
    fleet_backend.apply_changes_docs(init_docs(n, warm), workload(n),
                                     mirror=False)
    del warm
    _fence()
    reg_holder = [None]

    def one(enabled):
        if enabled:
            reg_holder[0] = obs_perf.enable_observatory()
        fleet = DocFleet(doc_capacity=n, key_capacity=n_keys + 1)
        handles = init_docs(n, fleet)
        per_doc = workload(n)
        start = time.perf_counter()
        fleet_backend.apply_changes_docs(handles, per_doc, mirror=False)
        if enabled:
            reg_holder[0].tick()
            obs_perf.sample_watermarks()
        elapsed = time.perf_counter() - start
        if enabled:
            obs_perf.disable_observatory()
            obs_hist.disable()
        del fleet, handles, per_doc
        _fence()
        return elapsed

    # POOLED paired runs (the round-14 SLO methodology, BENCH_r11: that
    # measurement's per-run medians flip-flopped [-0.26%, +3.83%] on
    # this box while the pooled-pair median held 1.9% — single-run pair
    # medians at these leg widths are exactly the noise artifact the
    # ledger exists to retire): several alternating-order pair passes,
    # every pair's delta pooled, the overhead judged on the POOLED
    # median with the per-run medians reported beside it.
    runs = _env('BENCH_PERF_RUNS', 3)
    pairs_per_run = max(REPS, 7)
    off_times, on_times, deltas = [], [], []
    run_medians = []
    for run in range(runs):
        run_deltas = []
        for rep in range(pairs_per_run + 1):
            if rep % 2:
                on_s = one(True)
                off_s = one(False)
            else:
                off_s = one(False)
                on_s = one(True)
            if rep == 0:
                continue       # each run's first pair is warmup
            off_times.append(off_s)
            on_times.append(on_s)
            run_deltas.append(on_s - off_s)
        deltas.extend(run_deltas)
        run_medians.append(float(np.median(run_deltas)))
        _fence()
    off_med = float(np.median(off_times))
    overhead = float(np.median(deltas)) / off_med * 100.0
    ledger_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               'traces', 'kernel_ledger.json')
    try:
        from automerge_tpu.observability import perf as _p
        _p.dump_ledger(ledger_path,
                       extra={'watermarks': _p.watermark_snapshot(
                           sample=False)})
    except OSError:
        ledger_path = None
    snap = obs_perf.kernel_snapshot()
    wm = obs_perf.watermark_snapshot(sample=False)
    R.update(perf_off_rate=n * 20 / off_med,
             perf_on_rate=n * 20 / float(np.median(on_times)),
             perf_overhead_pct=overhead,
             perf_kernel_dispatches=sum(r['dispatches']
                                        for r in snap.values()),
             perf_rss_high_mb=wm['high'].get('rss', 0) / 1e6,
             perf_pairs_pooled=len(deltas),
             perf_run_medians_pct=[round(m / off_med * 100.0, 2)
                                   for m in run_medians],
             perf_pair_deltas_s=[round(d, 4) for d in deltas])
    print(f'# perf plane: observatory on {R["perf_on_rate"]:.0f} '
          f'changes/s vs off {R["perf_off_rate"]:.0f} changes/s at the '
          f'{n}-doc x 20-change seam '
          f'({overhead:+.2f}% overhead, POOLED median of {len(deltas)} '
          f'alternating-order pairs over {runs} runs, per-run medians '
          f'{R["perf_run_medians_pct"]}%, budget 2%); '
          f'{R["perf_kernel_dispatches"]} '
          f'ledger-counted kernel dispatch(es), RSS high '
          f'{R["perf_rss_high_mb"]:.0f} MB'
          f'{", ledger " + ledger_path if ledger_path else ""}',
          file=sys.stderr)


@section('control')
def _sec_control():
    # Control-plane overhead (ISSUE-20 acceptance): a controller-ON
    # service pump vs the IDENTICAL episode with no controller, budget
    # <= 2%. The ON leg runs the controller in SHADOW mode: the full
    # decision path — SignalBus sample, policy hysteresis, ledger,
    # flight-recorder event per decision — with zero actuation, so the
    # paired delta isolates the controller's measurement cost. (An
    # ACTIVE controller is systematically FASTER than off on this
    # workload — raising the flooded tenants' rates converts typed
    # TenantThrottled exceptions into admitted work — which is feedback
    # the overhead number must not launder.) The episode still floods:
    # every decision window carries real decisions, not idle ticks.
    #
    # Pairing is TICK-LEVEL LOCKSTEP, not episode-level: both services
    # advance through the same tick loop, each tick of each leg timed
    # separately with order alternating per tick. Episode-level pairs
    # cannot resolve a 2% budget on a shared box — frequency ramps and
    # co-tenant load swing whole episodes +-10% in one direction — but
    # in lockstep both legs see the same box conditions tick-by-tick,
    # and the per-tick-index MEDIAN across passes drops preemption
    # spikes while the sum over tick indices keeps the window-tick
    # decision cost in (a plain median-of-ticks would hide it: 9 of 10
    # ticks are off-window by construction).
    #
    # Also reported: per-window decision latency from an ACTIVE run's
    # gauges, and SHADOW-VS-ACTIVE PARITY — the shadow decision
    # sequence must be byte-for-byte the active one (minus the apply),
    # which is what makes a shadow deployment's graphs trustworthy.
    from automerge_tpu.control import Controller
    from automerge_tpu.errors import AutomergeError
    from automerge_tpu.service import DocService
    ticks = _env('BENCH_CONTROL_TICKS', 400)
    tenants = _env('BENCH_CONTROL_TENANTS', 8)
    # 20 submits/tenant/tick saturates the tick (every tenant blows
    # through its burst every tick): the controller's per-window cost
    # is FIXED (reported absolutely as control_decide_us_*), so the
    # overhead PERCENTAGE is only meaningful against a loaded serving
    # tick, not an idle one
    submits = _env('BENCH_CONTROL_SUBMITS', 20)
    # the Controller's default decision cadence — the configuration a
    # deployment gets by not choosing; the loadgen chaos leg and the
    # unit tests deliberately run a tighter window=5 to stress the
    # decision path harder than the default
    window = _env('BENCH_CONTROL_WINDOW', 10)
    # passes floor of 9: each pass rebuilds both services, and allocator
    # placement can bias one leg's whole pass a few points — the
    # per-tick median needs enough passes to outvote a skewed layout
    passes = _env('BENCH_CONTROL_PASSES', max(REPS, 9))

    def build(mode):
        ctrl = Controller(mode=mode, window=window) if mode else None
        svc = DocService(control=ctrl, tenant_rate=2.0,
                         tenant_burst=4.0)
        sessions = [svc.open_session(f'tenant{t}')
                    for t in range(tenants)]
        return ctrl, svc, sessions

    def run_tick(svc, sessions, now):
        for s in sessions:
            for _i in range(submits):
                try:
                    svc.submit(s, 'sync', None)
                except AutomergeError:
                    pass
        svc.pump(now)

    def lockstep(order_flip):
        """One pass: a shadow-controlled service and a bare one driven
        through the same tick loop, each leg's tick timed separately.
        Returns (off_ns, on_ns, shadow_decision_log)."""
        import gc
        ctrl, svc_on, ses_on = build('shadow')
        _c, svc_off, ses_off = build(None)
        off_ns = np.empty(ticks)
        on_ns = np.empty(ticks)
        now = 0.0
        # cyclic GC off while timing: collections trigger on allocation
        # counts, and the ON leg allocates more (signal dicts, ledger
        # entries), so gen-2 pauses land disproportionately inside ON
        # ticks — a bursty whole-heap scan billed to whichever tick
        # tripped it, not a controller cost. _fence() collects the
        # deferred garbage between passes.
        gc.disable()
        try:
            for i in range(ticks):
                first_on = (i + order_flip) % 2
                for leg in (first_on, 1 - first_on):
                    start = time.perf_counter_ns()
                    if leg:
                        run_tick(svc_on, ses_on, now)
                    else:
                        run_tick(svc_off, ses_off, now)
                    elapsed = time.perf_counter_ns() - start
                    (on_ns if leg else off_ns)[i] = elapsed
                now += 0.1
        finally:
            gc.enable()
        log = ctrl.decision_log()
        del ctrl, svc_on, ses_on, svc_off, ses_off
        _fence()
        return off_ns, on_ns, log

    off_mat, on_mat = [], []
    shadow_log = None
    pass_pcts = []
    for p in range(passes + 1):
        off_ns, on_ns, shadow_log = lockstep(p % 2)
        if p == 0:
            continue           # first pass is warmup
        off_mat.append(off_ns)
        on_mat.append(on_ns)
        pass_pcts.append(round(
            float((on_ns.sum() - off_ns.sum()) / off_ns.sum()) * 100.0,
            2))
    off_tick_med = np.median(np.array(off_mat), axis=0)
    on_tick_med = np.median(np.array(on_mat), axis=0)
    off_total = float(off_tick_med.sum()) / 1e9
    on_total = float(on_tick_med.sum()) / 1e9
    overhead = (on_total - off_total) / off_total * 100.0
    # one ACTIVE episode: decision latency gauges + the parity check
    a_ctrl, a_svc, a_sessions = build('active')
    now = 0.0
    for _ in range(ticks):
        run_tick(a_svc, a_sessions, now)
        now += 0.1
    gauges = a_ctrl.gauges()
    log = a_ctrl.decision_log()
    del a_ctrl, a_svc, a_sessions
    _fence()

    def strip(entries):
        return [(e['tick'], e['policy'], e['action'], e['target'],
                 e['direction']) for e in entries]
    parity = int(strip(shadow_log) == strip(log))
    reqs = ticks * tenants * submits
    R.update(control_off_rate=reqs / off_total,
             control_on_rate=reqs / on_total,
             control_overhead_pct=overhead,
             control_decisions=len(log),
             control_windows=gauges['windows'],
             control_decide_us_last=gauges['decide_s_last'] * 1e6,
             control_decide_us_max=gauges['decide_s_max'] * 1e6,
             control_shadow_parity=parity,
             control_passes=len(off_mat),
             control_pass_pcts=pass_pcts)
    print(f'# control plane: on {R["control_on_rate"]:.0f} req/s vs off '
          f'{R["control_off_rate"]:.0f} req/s over {ticks} ticks x '
          f'{tenants} tenants ({overhead:+.2f}% overhead, tick-lockstep '
          f'pairing, per-tick median over {len(off_mat)} passes, '
          f'per-pass {pass_pcts}%, budget 2%); '
          f'{len(log)} decisions / {gauges["windows"]} windows, '
          f'decide p-max {R["control_decide_us_max"]:.0f}us, '
          f'shadow parity {"OK" if parity else "FAIL"}',
          file=sys.stderr)


@section('service')
def _sec_service():
    # Multi-tenant serving core (ISSUE-7): the three standing loadgen
    # legs — clean, chaos client, 2x overload — at 10k concurrent
    # sessions, reporting p99 request latency and sustained rounds/s per
    # leg. Acceptance lives in the report itself: every rejection typed
    # (untyped_escapes == 0), every edit doc byte-identical to the
    # unloaded control, every drained sync session converged, brownout
    # transitions visible under overload.
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), 'tools'))
    from loadgen import run_standard_legs
    sessions = _env('BENCH_SERVICE_SESSIONS', 10000)
    requests = _env('BENCH_SERVICE_REQUESTS', max(20000, sessions * 2))
    tenants = _env('BENCH_SERVICE_TENANTS', 256)
    legs = run_standard_legs(sessions=sessions, tenants=tenants,
                             requests=requests, seed=0)
    for leg in legs:
        name = leg['leg']
        conv = leg['convergence'] or {}
        R[f'service_{name}_p99_ms'] = leg['p99_ms']
        R[f'service_{name}_rps'] = leg['requests_per_s']
        R[f'service_{name}_rounds_per_s'] = leg['rounds_per_s']
        ok = leg['untyped_escapes'] == 0 and \
            conv.get('edit_mismatches', 0) == 0 and \
            conv.get('sync_converged') == conv.get('sync_drained')
        R[f'service_{name}_ok'] = int(ok)
        print(f"# service {name}: {leg['completed_ok']}/{leg['submitted']}"
              f" ok at {sessions} sessions/{tenants} tenants, p99 "
              f"{leg['p99_ms']}ms, {leg['rounds_per_s']} rounds/s, "
              f"{leg['requests_per_s']} req/s, rejections "
              f"{ {k: v for k, v in leg['rejections'].items()} }, "
              f"brownout transitions {leg['brownout_transitions']}, "
              f"convergence {conv}, {'OK' if ok else 'FAIL'}",
              file=sys.stderr)
    R['service_legs_all_ok'] = int(all(
        R[f"service_{leg['leg']}_ok"] for leg in legs))


@section('slo')
def _sec_slo():
    # SLO telemetry plane (ISSUE-10), three numbers:
    # (a) SLO accounting + trace-context overhead on the CLEAN service
    #     leg — the whole per-request accounting path (classify, tally,
    #     histogram record, forensics deque, trace mint) plus the
    #     per-tick window/burn evaluation, measured as paired
    #     alternating-order run_leg reps slo-on vs slo=False (the same
    #     methodology as the observability section: fixed order biases
    #     several points on this box), budget <= 2%. Minting rides the
    #     on-leg (submit mints iff slo-on or spans recording); batch
    #     span-LINK assembly is span-gated and so rides the PR 4 spans
    #     budget, not this one;
    # (b) exposition render time at 10k+ series (the Prometheus page a
    #     scraper pulls mid-tick);
    # (c) alert-detection latency: a synthetic full latency step into a
    #     clean registry, ticks until the fast window fires (acceptance
    #     bound: <= 10).
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), 'tools'))
    from loadgen import run_leg
    from automerge_tpu.errors import TenantThrottled
    from automerge_tpu.observability.export import render_prometheus
    from automerge_tpu.observability.slo import SloPolicy, SloRegistry

    sessions = _env('BENCH_SLO_SESSIONS', 10000)
    requests = _env('BENCH_SLO_REQUESTS', max(20000, sessions * 2))
    tenants = _env('BENCH_SLO_TENANTS', 256)
    pairs = _env('BENCH_SLO_PAIRS', 6)

    def leg(slo_on, seed):
        report = run_leg('clean', sessions=sessions, tenants=tenants,
                         requests=requests, seed=seed, convergence=False,
                         service_kwargs=None if slo_on else
                         {'slo': False})
        _fence()
        return report['elapsed_s']

    deltas, on_times, off_times = [], [], []
    for rep in range(pairs + 1):
        if rep % 2:
            on_s = leg(True, rep)
            off_s = leg(False, rep)
        else:
            off_s = leg(False, rep)
            on_s = leg(True, rep)
        if rep == 0:
            continue               # warmup pair (JIT compiles, pools)
        on_times.append(on_s)
        off_times.append(off_s)
        deltas.append(on_s - off_s)
    off_med = float(np.median(off_times))
    overhead = float(np.median(deltas)) / off_med * 100.0

    # direct accounting cost, free of per-leg box drift: one more REAL
    # on-leg with the registry's record/tick wrapped in wall-clock
    # accumulators — the exact code path at the exact volume, measured
    # from inside. Per-leg drift on this host is ±1s+, the same order
    # as the paired delta itself, so this in-leg number (a slight
    # OVERestimate: the wrapper's own perf_counter pairs are counted)
    # is what separates "the accounting got expensive" from "the box
    # was busy this minute"; the paired medians above bound the
    # end-to-end effect, the in-leg number attributes it.
    from automerge_tpu.observability import slo as _slo_mod
    acc = [0.0]
    orig_record = _slo_mod.SloRegistry.record
    orig_tick = _slo_mod.SloRegistry.tick

    def _timed_record(self, *args, **kwargs):
        t0 = time.perf_counter()
        out = orig_record(self, *args, **kwargs)
        acc[0] += time.perf_counter() - t0
        return out

    def _timed_tick(self, *args, **kwargs):
        t0 = time.perf_counter()
        out = orig_tick(self, *args, **kwargs)
        acc[0] += time.perf_counter() - t0
        return out

    _slo_mod.SloRegistry.record = _timed_record
    _slo_mod.SloRegistry.tick = _timed_tick
    try:
        instr_s = leg(True, pairs + 1)
    finally:
        _slo_mod.SloRegistry.record = orig_record
        _slo_mod.SloRegistry.tick = orig_tick
    direct_s = acc[0]
    direct_pct = direct_s / max(instr_s - direct_s, 1e-9) * 100.0

    # ---- (b) exposition render at scale ----
    # ~50 exposition lines per (tenant, kind) pair at 3 kinds: 80
    # tenants land the page just past the 10k-series acceptance scale
    series_tenants = _env('BENCH_SLO_SERIES_TENANTS', 80)
    reg = SloRegistry()
    for t in range(series_tenants):
        tenant = f'tenant{t}'
        for kind in ('apply', 'sync', 'subscribe'):
            reg.record(tenant, kind, 0.003)
            reg.record(tenant, kind, 0.2)
            reg.record(tenant, kind, 0.0, TenantThrottled(
                'bench', tenant=tenant, retry_after=0.1))
    reg.tick()
    render_times = []
    page = ''
    for _ in range(max(REPS, 3)):
        start = time.perf_counter()
        page = render_prometheus(slo=reg)
        render_times.append(time.perf_counter() - start)
    render_s = float(np.median(render_times))
    n_series = sum(1 for line in page.splitlines()
                   if line and not line.startswith('#'))

    # ---- (c) alert-detection latency under a synthetic step ----
    reg2 = SloRegistry(policies={
        'latency': SloPolicy(0.999, threshold_s=0.05)})
    for _ in range(70):
        for _ in range(20):
            reg2.record('victim', 'apply', 0.002)
        reg2.tick()
    detect = None
    for t in range(1, 21):
        for _ in range(20):
            reg2.record('victim', 'apply', 0.4)
        reg2.tick()
        if any(w == 'fast' for *_rest, w in reg2.active_alerts()):
            detect = t
            break

    R.update(slo_overhead_pct=overhead,
             slo_on_leg_s=float(np.median(on_times)),
             slo_off_leg_s=off_med,
             slo_pair_deltas_s=[round(d, 3) for d in deltas],
             slo_inleg_accounting_s=direct_s,
             slo_inleg_accounting_pct=direct_pct,
             slo_render_ms=render_s * 1e3,
             slo_render_series=n_series,
             slo_render_series_per_s=n_series / render_s,
             slo_alert_detect_ticks=detect)
    print(f'# slo: accounting+trace overhead {overhead:+.2f}% paired on '
          f'the {sessions}-session clean leg ({pairs} alternating-order '
          f'pairs, deltas {[round(d, 2) for d in deltas]}s, on '
          f'{np.median(on_times):.2f}s vs off {off_med:.2f}s); in-leg '
          f'instrumented accounting cost {direct_s:.3f}s = '
          f'{direct_pct:.2f}% of the leg (budget 2%); exposition render '
          f'{render_s * 1e3:.1f}ms at {n_series} series '
          f'({n_series / render_s:.0f} series/s); fast-window alert '
          f'detected a full latency step in {detect} ticks '
          f'(budget <= 10)', file=sys.stderr)


@section('shards')
def _sec_shards():
    # Shard scale-out (ISSUE-11), two numbers:
    # (a) aggregate acked req/s on the CLEAN leg at 1/2/4 shards. The
    #     serving tick is a CADENCE (tick_dt bounds batching latency),
    #     so the legs run wall-paced: per-shard capacity is the modeled
    #     per-core device budget (batch_limit applies per fused tick),
    #     aggregate throughput = capacity x shards IF each tick's work
    #     fits the cadence on this box — overruns are counted and
    #     reported (ticks_slipped), never silently absorbed. Pumps run
    #     thread-per-shard; replication group-commits every 4 ticks
    #     (the ack contract — changes on home AND replica before the
    #     ticket resolves — is cadence-independent).
    # (b) failover MTTR: an UNPACED kill-one-of-4 chaos leg (lossy
    #     replication links), reporting ticks from the kill to the
    #     first acked request served by a re-homed tenant, plus the
    #     zero-acked-loss / byte-identical-convergence audits.
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), 'tools'))
    from loadgen import run_shard_leg
    tenants = _env('BENCH_SHARD_TENANTS', 96)
    requests = _env('BENCH_SHARD_REQUESTS', 1200)
    kill_requests = _env('BENCH_SHARD_KILL_REQUESTS', 400)

    # warm the JIT paths on a throwaway cluster so the 1-shard leg
    # doesn't pay compilation inside its paced window
    run_shard_leg('warmup', n_shards=2, tenants=8, requests=100,
                  arrivals_per_tick=8,
                  service_kwargs={'batch_limit': 8}, seed=0)
    _fence()

    sweep = {}
    slips = {}
    for n in (1, 2, 4):
        leg = run_shard_leg(
            f'clean_{n}', n_shards=n, tenants=tenants,
            requests=requests, arrivals_per_tick=max(8, tenants // 2),
            seed=0, tick_dt=0.03, subscribe_fraction=0.1,
            sync_fraction=0.05, service_kwargs={'batch_limit': 8},
            pump_threads=2, repl_every=4, pace=True)
        sweep[str(n)] = leg['requests_per_s']
        slips[str(n)] = leg['ticks_slipped']
        R[f'shards_rps_{n}'] = leg['requests_per_s']
        R[f'shards_clean_{n}_ok'] = int(leg['ok'])
        _fence()
    monotonic = sweep['1'] < sweep['2'] < sweep['4']
    R['shards_scaling_monotonic'] = int(monotonic)

    kill = run_shard_leg(
        'kill_one_of_four', n_shards=4, tenants=max(8, tenants // 8),
        requests=kill_requests, arrivals_per_tick=8, chaos=True,
        seed=5, kills=((12, 1, 40),), mttr_bound=12)
    mttr = kill['mttr_ticks'][0] if kill['mttr_ticks'] else None
    R['shards_failover_mttr_ticks'] = mttr
    R['shards_kill_leg_ok'] = int(kill['ok'])
    R['shards_kill_acked_lost'] = kill['final_audit']['acked_lost']
    R['shards_kill_replica_mismatches'] = \
        kill['final_audit']['replica_mismatches']
    _fence()

    scaled = ', '.join(
        f'{n}S {r:.0f} req/s ({r / sweep["1"]:.2f}x, '
        f'{slips[n]} slipped)' for n, r in sweep.items())
    print(f'# shards clean paced sweep ({tenants} tenants, '
          f'batch_limit 8/tick/shard, tick 30ms, repl_every 4): '
          f'{scaled}, monotonic {"OK" if monotonic else "FAIL"}',
          file=sys.stderr)
    print(f'# shards kill-one-of-four: MTTR {mttr} ticks (lease '
          f'{kill["lease_ticks"]}), acked lost '
          f'{kill["final_audit"]["acked_lost"]}, replica mismatches '
          f'{kill["final_audit"]["replica_mismatches"]}, '
          f'{"OK" if kill["ok"] else "FAIL"}', file=sys.stderr)


@section('query')
def _sec_query():
    # Query engine (ISSUE-9): (a) batched time-travel reads — N docs
    # materialized at historical frontiers through ONE fused replay
    # dispatch (query.materialize_at_docs), reported as docs/s with the
    # dispatch count pinned; (b) the subscription tick at fleet scale —
    # S subscribers over D docs grouped into (doc, cursor) equivalence
    # classes, reporting tick p99, the per-tick device dispatch count
    # (must be 0: pure hash-graph work), and the one-diff-per-class
    # reuse ratio.
    from automerge_tpu.columnar import decode_change_meta, encode_change
    from automerge_tpu.fleet import backend as fleet_backend
    from automerge_tpu.fleet.backend import DocFleet, init_docs
    from automerge_tpu.query import SubscriptionHub, materialize_at_docs

    n_docs = _env('BENCH_QUERY_DOCS', 1000)
    n_subs = _env('BENCH_QUERY_SUBS', 10000)
    n_changes = 6

    fleet = DocFleet()
    handles = init_docs(n_docs, fleet)
    frontiers = [[] for _ in range(n_docs)]   # current heads per doc
    mid_frontier = [None] * n_docs            # heads at the halfway point
    for c in range(n_changes):
        per_doc = []
        for d in range(n_docs):
            buf = encode_change({
                'actor': f'{d % 128:04x}' * 4, 'seq': c + 1,
                'startOp': c + 1, 'time': 0, 'message': '',
                'deps': frontiers[d],
                'ops': [{'action': 'set', 'obj': '_root', 'key': f'k{c}',
                         'value': d * 100 + c, 'datatype': 'int',
                         'pred': []}]})
            frontiers[d] = [decode_change_meta(buf, True)['hash']]
            if c == n_changes // 2:
                mid_frontier[d] = list(frontiers[d])
            per_doc.append([buf])
        handles, _ = fleet_backend.apply_changes_docs(handles, per_doc,
                                                      mirror=False)
    _fence()

    # ---- (a) batched materialize-at ----
    mat_times = []
    dispatches = None
    for rep in range(max(REPS, 3) + 1):
        before = fleet.metrics.dispatches
        start = time.perf_counter()
        outs = materialize_at_docs(handles, mid_frontier, fleet=fleet)
        mat_s = time.perf_counter() - start
        dispatches = fleet.metrics.dispatches - before
        fleet_backend.free_docs(outs)
        if rep == 0:
            continue
        mat_times.append(mat_s)
    mat_s = float(np.median(mat_times))
    mat_rate = n_docs / mat_s

    # ---- (b) the subscription tick at fan-out scale ----
    # subscribers spread over the docs at 3 cursor classes per doc
    # (empty / mid / at-head), so the expected reuse ratio at S >> 3D is
    # ~1 - 3D/S
    hub = SubscriptionHub()
    for d in range(n_docs):
        hub.register(d, handles[d])
    classes = [[], None, 'head']
    for s in range(n_subs):
        d = s % n_docs
        cls = classes[(s // n_docs) % 3]
        cursor = mid_frontier[d] if cls is None else \
            (frontiers[d] if cls == 'head' else [])
        hub.subscribe(d, cursor=cursor)
    tick_times = []
    tick_dispatches = 0
    reuse_ratio = 0.0
    n_ticks = max(REPS, 5)
    for rep in range(n_ticks + 1):
        # advance every doc one change so each tick has real diffs
        per_doc = []
        for d in range(n_docs):
            buf = encode_change({
                'actor': f'{d % 128:04x}' * 4, 'seq': n_changes + rep + 1,
                'startOp': n_changes + rep + 1, 'time': 0, 'message': '',
                'deps': frontiers[d],
                'ops': [{'action': 'set', 'obj': '_root', 'key': 'hot',
                         'value': rep, 'datatype': 'int', 'pred': []}]})
            frontiers[d] = [decode_change_meta(buf, True)['hash']]
            per_doc.append([buf])
        handles, _ = fleet_backend.apply_changes_docs(handles, per_doc,
                                                      mirror=False)
        for d in range(n_docs):
            hub.update_source(d, handles[d])
        computed0 = hub.stats['diffs_computed']
        reused0 = hub.stats['diffs_reused']
        before = fleet.metrics.dispatches
        start = time.perf_counter()
        events = hub.tick()
        tick_s = time.perf_counter() - start
        tick_dispatches = fleet.metrics.dispatches - before
        assert len(events) == n_subs
        if rep == 0:
            continue
        computed = hub.stats['diffs_computed'] - computed0
        reused = hub.stats['diffs_reused'] - reused0
        reuse_ratio = reused / max(computed + reused, 1)
        tick_times.append(tick_s)
    tick_p99_ms = float(np.percentile(tick_times, 99)) * 1e3
    tick_p50_ms = float(np.median(tick_times)) * 1e3
    del hub, handles, fleet
    _fence()

    R.update(query_materialize_docs_per_s=mat_rate,
             query_materialize_dispatches=dispatches,
             query_tick_subs=n_subs,
             query_tick_p50_ms=tick_p50_ms,
             query_tick_p99_ms=tick_p99_ms,
             query_tick_dispatches=tick_dispatches,
             query_diff_reuse_ratio=reuse_ratio)
    print(f'# query: batched materialize-at {mat_rate:.0f} docs/s '
          f'({n_docs} docs/batch, {dispatches} dispatches/batch); '
          f'{n_subs}-subscriber tick over {n_docs} docs p50 '
          f'{tick_p50_ms:.1f}ms / p99 {tick_p99_ms:.1f}ms, '
          f'{tick_dispatches} device dispatches/tick, diff reuse '
          f'{reuse_ratio:.3f}', file=sys.stderr)


@section('frontier')
def _sec_frontier():
    # Device-resident frontier index (ISSUE-14): (a) sync-round
    # membership cost vs HISTORY DEPTH at fixed batch — warm rounds ride
    # one batched index dispatch, so the sweep must be FLAT (<=1.2x from
    # 1k to 100k, the acceptance pin), while the fresh-doc contrast leg
    # shows what the index removes: the O(history) hash-graph dict build
    # a converged handshake used to force on a freshly loaded doc;
    # (b) the 10k-subscriber ALL-QUIET tick collapsed to exactly one
    # frontier-compare dispatch, p50 vs the per-class host scan.
    from automerge_tpu.backend import init_sync_state
    from automerge_tpu.columnar import decode_change_meta, encode_change
    from automerge_tpu.fleet import backend as fleet_backend
    from automerge_tpu.fleet import hashindex, sync_driver
    from automerge_tpu.fleet.backend import DocFleet, init_docs
    from automerge_tpu.fleet.loader import load_docs
    from automerge_tpu.query import SubscriptionHub

    depths = [int(x) for x in os.environ.get(
        'BENCH_FRONTIER_DEPTHS', '1000,100000').split(',')]
    behind = _env('BENCH_FRONTIER_BEHIND', 64)
    k_docs = _env('BENCH_FRONTIER_DOCS', 4)

    def chain(n):
        bufs, hashes, deps = [], [], []
        for i in range(n):
            buf = encode_change({
                'actor': 'f1' * 16, 'seq': i + 1, 'startOp': i + 1,
                'time': 0, 'message': '', 'deps': deps,
                'ops': [{'action': 'set', 'obj': '_root',
                         'key': f'k{i % 7}', 'value': i,
                         'datatype': 'int', 'pred': []}]})
            deps = [decode_change_meta(buf, True)['hash']]
            bufs.append(buf)
            hashes.append(deps[0])
        return bufs, hashes

    depth_p50 = {}
    fresh_ms = {}
    # one table GEOMETRY for the whole sweep (provisioned for the
    # deepest leg): the sweep pins cost vs HISTORY DEPTH, and a tiny
    # table's cache-resident probes would otherwise flatter the shallow
    # leg by ~0.3ms of pure L2-vs-RAM gather difference
    table_cap = 2 * k_docs * max(depths)
    for H in depths:
        bufs, hashes = chain(H)
        fleet = DocFleet()
        handles = init_docs(k_docs, fleet)
        step = 20000
        for lo in range(0, H, step):
            handles, _ = fleet_backend.apply_changes_docs(
                handles, [bufs[lo:lo + step]] * k_docs, mirror=False)
        doc_chunk = bytes(handles[0]['state'].save())
        anchor = hashes[H - behind - 1]

        def mk_states(heads):
            out = []
            for _ in range(k_docs):
                s = init_sync_state()
                s['sharedHeads'] = list(heads)
                s['theirHeads'] = list(heads)
                s['theirHave'] = [{'lastSync': list(heads), 'bloom': b''}]
                s['theirNeed'] = []
                out.append(s)
            return out

        # warm: index registration backfill + graph walk caches, then
        # measure steady-state rounds with a peer `behind` changes back.
        # device_min=1 pins the DEVICE table at every depth — the sweep
        # compares depth, not host-vs-device storage modes
        fleet.frontier_index(device_min=1, capacity=table_cap)
        sync_driver.generate_sync_messages_docs(handles,
                                                mk_states([anchor]))
        times = []
        for _ in range(max(REPS, 5)):
            states = mk_states([anchor])
            start = time.perf_counter()
            _s, msgs = sync_driver.generate_sync_messages_docs(handles,
                                                               states)
            times.append(time.perf_counter() - start)
            assert all(m is not None for m in msgs)
        depth_p50[H] = float(np.median(times)) * 1e3
        del handles, fleet, bufs
        _fence()

        # fresh-doc converged round, index on vs off: the one-time cost
        # a revive pays to answer a quiet handshake (extractor hash-lane
        # backfill vs the full Python hash-graph dict build)
        row = {}
        for label, enabled in (('new', True), ('old', False)):
            prev = sync_driver.set_frontier_enabled(enabled)
            try:
                fleet2 = DocFleet()
                if enabled:
                    fleet2.frontier_index(device_min=1,
                                          capacity=table_cap)
                loaded = load_docs([doc_chunk] * k_docs, fleet2)
                heads = list(loaded[0]['heads'])
                start = time.perf_counter()
                sync_driver.generate_sync_messages_docs(
                    loaded, mk_states(heads))
                row[label] = (time.perf_counter() - start) * 1e3
            finally:
                sync_driver.set_frontier_enabled(prev)
            del fleet2, loaded
            _fence()
        fresh_ms[H] = row

    lo_h, hi_h = depths[0], depths[-1]
    depth_ratio = depth_p50[hi_h] / depth_p50[lo_h]

    # ---- (b) the all-quiet tick at fan-out scale ----
    n_docs = _env('BENCH_FRONTIER_TICK_DOCS', 1000)
    n_subs = _env('BENCH_FRONTIER_TICK_SUBS', 10000)
    fleet = DocFleet()
    handles = init_docs(n_docs, fleet)
    per_doc, frontiers = [], []
    for d in range(n_docs):
        buf = encode_change({
            'actor': f'{d % 128:04x}' * 4, 'seq': 1, 'startOp': 1,
            'time': 0, 'message': '', 'deps': [],
            'ops': [{'action': 'set', 'obj': '_root', 'key': 'k',
                     'value': d, 'datatype': 'int', 'pred': []}]})
        frontiers.append([decode_change_meta(buf, True)['hash']])
        per_doc.append([buf])
    handles, _ = fleet_backend.apply_changes_docs(handles, per_doc,
                                                  mirror=False)
    hub = SubscriptionHub()
    for d in range(n_docs):
        hub.register(d, handles[d])
    for s in range(n_subs):
        hub.subscribe(s % n_docs, cursor=frontiers[s % n_docs])
    hub.tick()                      # warm (plan build, jit)
    tick_p50 = {}
    tick_dispatches = None
    for label, batch in (('batched', True), ('scan', False)):
        hub.batch_quiet = batch
        times = []
        for _ in range(max(REPS, 7)):
            n0 = hashindex.dispatch_count()
            d0 = fleet.metrics.dispatches
            start = time.perf_counter()
            events = hub.tick()
            times.append(time.perf_counter() - start)
            assert events == {}
            if batch:
                tick_dispatches = (hashindex.dispatch_count() - n0,
                                   fleet.metrics.dispatches - d0)
                assert tick_dispatches == (1, 0), tick_dispatches
        tick_p50[label] = float(np.median(times)) * 1e3
    del hub, handles, fleet
    _fence()

    quiet_speedup = tick_p50['scan'] / tick_p50['batched']
    # flat scalar keys (the standalone JSON line and the bench ledger
    # both drop nested values)
    for h in depths:
        R[f'frontier_round_p50_ms_{h}'] = depth_p50[h]
        R[f'frontier_fresh_new_ms_{h}'] = fresh_ms[h]['new']
        R[f'frontier_fresh_old_ms_{h}'] = fresh_ms[h]['old']
    R.update(
        frontier_depth_ratio=depth_ratio,
        frontier_fresh_speedup=fresh_ms[hi_h]['old'] /
            max(fresh_ms[hi_h]['new'], 1e-9),
        frontier_quiet_tick_p50_ms=tick_p50['batched'],
        frontier_quiet_scan_p50_ms=tick_p50['scan'],
        frontier_quiet_speedup=quiet_speedup,
        frontier_quiet_tick_dispatches=1)
    print(f'# frontier: sync-round p50 '
          + ' / '.join(f'{h}ch {depth_p50[h]:.2f}ms' for h in depths)
          + f' (ratio {depth_ratio:.2f}x, budget <=1.2x); fresh-doc '
          f'converged round at {hi_h}ch: index {fresh_ms[hi_h]["new"]:.0f}ms '
          f'vs dicts {fresh_ms[hi_h]["old"]:.0f}ms; {n_subs}-sub all-quiet '
          f'tick p50 {tick_p50["batched"]:.2f}ms (1 dispatch) vs scan '
          f'{tick_p50["scan"]:.2f}ms = {quiet_speedup:.1f}x',
          file=sys.stderr)


@section('sync_fabric')
def _sec_sync_fabric():
    # Fleet-scale sync fabric (ISSUE-16): a shard serving N peer links
    # out of its doc set, every link's sentHashes a peer-space in the
    # shared frontier table. (a) steady-state round p50 across a link
    # sweep with per-round hashindex + Bloom dispatch counts (the O(1)
    # pin: counts must not move with N); (b) fused round vs the classic
    # per-peer generate loop the fabric replaced (subsampled and
    # extrapolated; acceptance >=3x at the 10k leg); (c) the probe-
    # window sweep behind AUTOMERGE_TPU_PROBE_WINDOW.
    from automerge_tpu.backend import init_sync_state
    from automerge_tpu.backend.sync import generate_sync_message
    from automerge_tpu.columnar import decode_change_meta, encode_change
    from automerge_tpu.fleet import backend as fleet_backend
    from automerge_tpu.fleet import bloom as fleet_bloom
    from automerge_tpu.fleet import hashindex, sync_driver
    from automerge_tpu.fleet.backend import DocFleet, init_docs
    from automerge_tpu.fleet.hashindex import PeerSentSet, set_probe_window

    link_sweep = [int(x) for x in os.environ.get(
        'BENCH_FABRIC_LINKS', '1000,10000,100000').split(',')]
    n_docs = _env('BENCH_FABRIC_DOCS', 4)
    depth = _env('BENCH_FABRIC_DEPTH', 8)
    loop_sample = _env('BENCH_FABRIC_LOOP_SAMPLE', 512)
    windows = [int(x) for x in os.environ.get(
        'BENCH_FABRIC_WINDOWS', '8,16,32').split(',')]

    def chain(actor, n):
        bufs, hashes, deps = [], [], []
        for i in range(n):
            buf = encode_change({
                'actor': actor, 'seq': i + 1, 'startOp': i + 1,
                'time': 0, 'message': '', 'deps': deps,
                'ops': [{'action': 'set', 'obj': '_root',
                         'key': f'k{i % 5}', 'value': i,
                         'datatype': 'int', 'pred': []}]})
            deps = [decode_change_meta(buf, True)['hash']]
            bufs.append(buf)
            hashes.append(deps[0])
        return bufs, hashes

    def solicit(states):
        # every peer asks for a full resend (empty bloom): the round
        # must answer membership for every candidate on every link —
        # the fabric's worst-case steady state
        for s in states:
            s['theirHeads'] = []
            s['theirHave'] = [{'lastSync': [], 'bloom': b''}]
            s['theirNeed'] = []

    round_p50, loop_ms, host_loop_ms, disp = {}, {}, {}, {}
    for n_links in link_sweep:
        fleet = DocFleet()
        handles = init_docs(n_docs, fleet)
        doc_rows = [chain(f'{0xe0 + d:02x}' * 16, depth)
                    for d in range(n_docs)]
        handles, _ = fleet_backend.apply_changes_docs(
            handles, [bufs for bufs, _ in doc_rows], mirror=False)
        fleet.frontier_index(device_min=1,
                             capacity=2 * n_links * depth)
        flat_docs = [handles[i % n_docs] for i in range(n_links)]
        states = [init_sync_state() for _ in range(n_links)]
        solicit(states)
        # cold round: every link sends its doc's changes, staging and
        # promoting its sentHashes into a peer-space
        states, msgs = sync_driver.generate_sync_messages_docs(
            flat_docs, states)
        assert all(isinstance(s['sentHashes'], PeerSentSet)
                   for s in states)
        solicit(states)
        # warm round: flushes the staged spaces + compiles steady shapes
        states, _msgs = sync_driver.generate_sync_messages_docs(
            flat_docs, states)
        times = []
        for _ in range(max(REPS, 5)):
            solicit(states)
            h0 = hashindex.dispatch_count()
            b0 = fleet_bloom.dispatch_count()
            start = time.perf_counter()
            states, msgs = sync_driver.generate_sync_messages_docs(
                flat_docs, states)
            times.append(time.perf_counter() - start)
            disp[n_links] = (hashindex.dispatch_count() - h0,
                             fleet_bloom.dispatch_count() - b0)
        assert all(m is not None for m in msgs)
        round_p50[n_links] = float(np.median(times)) * 1e3

        # the per-peer loop this PR replaced (exchange.py/cluster.py
        # before the fabric): one driver call PER PEER PAIR, so every
        # link pays its own Bloom-build + membership-probe dispatches.
        # Subsampled and extrapolated to the full link set (strictly
        # per-link work, so the extrapolation is linear by construction)
        m_links = min(n_links, loop_sample)

        def run_loop():
            sub = states[:m_links]
            solicit(sub)
            start = time.perf_counter()
            for i in range(m_links):
                new, _m = sync_driver.generate_sync_messages_docs(
                    [flat_docs[i]], [states[i]])
                states[i] = new[0]
            return time.perf_counter() - start

        run_loop()                                   # warm n=1 shapes
        loop_reps = [run_loop() for _ in range(max(REPS, 3))]
        loop_ms[n_links] = float(np.median(loop_reps)) * 1e3 \
            * (n_links / m_links)

        # secondary reference: the single-doc HOST protocol with plain-
        # set sentHashes (no device work at all) — the floor the shared
        # per-link host assembly cost imposes on both paths
        host_states = []
        for i in range(m_links):
            s = init_sync_state()
            s['sentHashes'] = set(doc_rows[i % n_docs][1])
            host_states.append(s)

        def run_host_loop():
            solicit(host_states)
            start = time.perf_counter()
            for i in range(m_links):
                host_states[i], _m = generate_sync_message(
                    handles[i % n_docs], host_states[i])
            return time.perf_counter() - start

        run_host_loop()                              # warm
        host_reps = [run_host_loop() for _ in range(max(REPS, 3))]
        host_loop_ms[n_links] = float(np.median(host_reps)) * 1e3 \
            * (n_links / m_links)

        if n_links == link_sweep[len(link_sweep) // 2]:
            # probe-window sweep at the middle leg: the 16-slot default
            # vs narrower/wider windows (static jit arg -> each width
            # compiles once, then steady rounds)
            for width in windows:
                prev = set_probe_window(width)
                try:
                    solicit(states)
                    states, _msgs = sync_driver.\
                        generate_sync_messages_docs(flat_docs, states)
                    wtimes = []
                    for _ in range(max(REPS, 3)):
                        solicit(states)
                        start = time.perf_counter()
                        states, _msgs = sync_driver.\
                            generate_sync_messages_docs(flat_docs, states)
                        wtimes.append(time.perf_counter() - start)
                    R[f'fabric_window_p50_ms_{width}'] = \
                        float(np.median(wtimes)) * 1e3
                finally:
                    set_probe_window(prev)
        del fleet, handles, flat_docs, states, host_states, msgs
        _fence()

    mid = min(link_sweep, key=lambda n: abs(n - 10_000))
    top = link_sweep[-1]
    flat = len({d for d in disp.values()}) == 1
    for n_links in link_sweep:
        R[f'fabric_round_p50_ms_{n_links}'] = round_p50[n_links]
        R[f'fabric_loop_round_ms_{n_links}'] = loop_ms[n_links]
        R[f'fabric_host_loop_round_ms_{n_links}'] = host_loop_ms[n_links]
        R[f'fabric_fused_vs_loop_{n_links}'] = \
            loop_ms[n_links] / round_p50[n_links]
        R[f'fabric_round_hashindex_dispatches_{n_links}'] = \
            disp[n_links][0]
        R[f'fabric_round_bloom_dispatches_{n_links}'] = disp[n_links][1]
    R.update(
        fabric_links_per_s=top / round_p50[top] * 1e3,
        fabric_fused_vs_loop_ratio=loop_ms[mid] / round_p50[mid],
        fabric_dispatches_flat=int(flat))
    print(f'# sync fabric: round p50 '
          + ' / '.join(f'{n}lk {round_p50[n]:.1f}ms' for n in link_sweep)
          + f'; dispatches/round {disp[top]} '
          f'({"FLAT" if flat else "SCALING"} across the sweep); fused vs '
          f'per-peer loop at {mid} links: {loop_ms[mid]:.0f}ms -> '
          f'{round_p50[mid]:.1f}ms = '
          f'{loop_ms[mid] / round_p50[mid]:.1f}x (budget >=3x; host-'
          f'protocol floor {host_loop_ms[mid]:.0f}ms); '
          f'window sweep '
          + ' / '.join(f'w{w} {R.get(f"fabric_window_p50_ms_{w}", 0):.1f}ms'
                       for w in windows),
          file=sys.stderr)


@section('zipf')
def _sec_zipf():
    # Config 5 (stretch): Zipf-skewed change rates over a large fleet
    zipf_rate, zipf_occ = bench_zipf(_env('BENCH_ZIPF_DOCS', 100000))
    R.update(zipf_rate=zipf_rate, zipf_occ=zipf_occ)
    print(f'# zipf 100k-doc fleet: {zipf_rate:.0f} effective ops/s '
          f'(occupancy {zipf_occ:.2f})', file=sys.stderr)


@section('registers')
def _sec_registers():
    # Exact multi-value register engine (ordered scan formulation)
    reg_rate = bench_registers(_env('BENCH_REG_DOCS', 4000))
    R['reg_rate'] = reg_rate
    print(f'# exact register engine: {reg_rate:.0f} ops/s', file=sys.stderr)


@section('bulk_load')
def _sec_bulk_load():
    # Bulk document load: native parse straight to device state vs the
    # per-doc Python decode + host replay path
    bulk_rate, perdoc_rate = bench_bulk_load(_env('BENCH_LOAD_DOCS', 2000))
    R.update(bulk_rate=bulk_rate, perdoc_rate=perdoc_rate)
    if bulk_rate is not None:
        print(f'# bulk document load (native parse -> device state): '
              f'{bulk_rate:.0f} docs/s vs per-doc path '
              f'{perdoc_rate:.0f} docs/s '
              f'({bulk_rate / perdoc_rate:.1f}x)', file=sys.stderr)
    else:
        print(f'# bulk document load: native codec unavailable '
              f'(per-doc path {perdoc_rate:.0f} docs/s)', file=sys.stderr)


@section('native_save')
def _sec_native_save():
    save_native, save_host = bench_native_save(
        _env('BENCH_SAVE_CHANGES', 200))
    R.update(save_native=save_native, save_host=save_host)
    if save_native is not None:
        print(f'# mirror-free native save (200-change log): '
              f'{save_native:.1f} saves/s vs host replay+encode '
              f'{save_host:.1f} saves/s ({save_native / save_host:.1f}x)',
              file=sys.stderr)


@section('mixed')
def _sec_mixed():
    mixed_rate, mixed_host, mixed_opc = bench_backend_mixed(
        _env('BENCH_MIXED_DOCS', 500))
    R.update(mixed_rate=mixed_rate, mixed_host=mixed_host,
             mixed_opc=mixed_opc)
    print(f'# backend-seam e2e, realistic mixed docs (nested trees, '
          f'strings/floats/bools): {mixed_rate:.0f} changes/s vs host '
          f'{mixed_host:.0f} changes/s ({mixed_rate / mixed_host:.1f}x); '
          f'{mixed_opc:.1f} ops/change -> {mixed_rate * mixed_opc:.0f} '
          f'ops/s (headline is 1 op/change)', file=sys.stderr)


@section('seam_dense')
def _sec_seam_dense():
    # Op-density control for the mixed-vs-flat gap (round-5 VERDICT weak
    # #3): the FLAT-int seam at the mixed config's measured op density
    # (~4.8 ops/change). If changes/s here lands near the mixed rate, op
    # density explains the gap and the per-op framing stands; any residual
    # is mixed-content cost (nested objects, value arena, seq rows).
    opc = float(os.environ.get('BENCH_DENSE_OPC',
                               R.get('mixed_opc', 4.8) or 4.8))
    rate, info = bench_backend_pipeline(
        _env('BENCH_MIXED_DOCS', 500), 64, 16, ops_per_change=opc)
    R.update(seam_dense_rate=rate, seam_dense_opc=info['ops_per_change'])
    extra = ''
    if R.get('mixed_rate'):
        extra = f'; mixed config measured {R["mixed_rate"]:.0f} changes/s ' \
                f'-> density explains {rate / R["mixed_rate"]:.2f}x of the ' \
                f'flat-headline gap'
    print(f'# op-density control: flat ints at '
          f'{info["ops_per_change"]:.1f} ops/change: {rate:.0f} changes/s '
          f'({rate * info["ops_per_change"]:.0f} ops/s){extra}',
          file=sys.stderr)


@section('regress')
def _sec_regress():
    # Bench ledger + regression gate (ISSUE-13): measure the seam with
    # RECORDED per-rep samples (the rep spread is what makes the gate's
    # thresholds noise-aware), append one row to BENCH_LEDGER.jsonl,
    # judge HEAD against the ledger's trailing same-box history with
    # tools/perf_gate.judge, and run the gate's synthetic self-test
    # (--check): zero false fires across 5 clean paired runs, a 1.3x
    # slowdown detected. BENCH_LEDGER=0 skips the append (the sanity
    # harness sets it so scaled-down runs don't pollute the trajectory).
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), 'tools'))
    import bench_ledger
    import perf_gate
    docs = _env('BENCH_REGRESS_DOCS', 2000)
    n_keys = _env('BENCH_KEYS', 1000)
    reps = []
    info = None
    for _ in range(max(REPS, 5)):
        rate, info = bench_backend_pipeline(docs, n_keys, 20, reps=1)
        reps.append(rate)
        _fence()
    metric = f'regress_seam_rate_{docs}d'
    head_metrics = {metric: float(np.median(reps))}
    # a row is appended only by a run that knows where it ran: DEVICE is
    # what JAX reported to _init_platform, never an environment variable
    ledger_on = os.environ.get('BENCH_LEDGER', '1') != '0' and \
        bool(DEVICE.get('platform'))
    if ledger_on:
        # ride the full run's section numbers along (standalone runs
        # carry only the regress metric). Skipped when the append is
        # off (the sanity harness's SCALED-DOWN runs set BENCH_LEDGER=0:
        # judging a 1000-doc seam_rate against the ledger's full-scale
        # history would manufacture a regression out of the config)
        for key in ('seam_rate', 'seam_commit_rate', 'host_rate',
                    'service_clean_rps', 'slo_render_series_per_s',
                    'storage_recovery_docs_per_s',
                    'tier_park_docs_per_s', 'tier_revive_docs_per_s',
                    'tier_materialize_docs_per_s',
                    'query_materialize_docs_per_s', 'shards_rps_4',
                    'fabric_links_per_s', 'fabric_fused_vs_loop_ratio',
                    'obs_overhead_pct', 'perf_overhead_pct',
                    'control_overhead_pct'):
            if isinstance(R.get(key), (int, float)):
                head_metrics[key] = float(R[key])
    row = bench_ledger.make_row(
        head_metrics, reps={metric: reps},
        box=bench_ledger.box_fingerprint(DEVICE),
        notes={'regress_docs': docs})
    rows, report = bench_ledger.read_rows()
    verdict = perf_gate.judge(row, rows)
    if ledger_on:
        bench_ledger.append_row(row)
    check_ok = perf_gate.check(out=sys.stderr)
    judged = [f for f in verdict['findings']
              if f['verdict'] != 'insufficient']
    R.update(regress_seam_rate=head_metrics[metric],
             regress_docs=docs,
             regress_gate_ok=int(verdict['ok']),
             regress_check_ok=int(check_ok),
             regress_metrics_judged=len(judged),
             regress_ledger_rows=len(rows) + int(ledger_on),
             regress_ledger_torn_tail=int(report['torn_tail']))
    for f in verdict['regressions']:
        print(f'# REGRESSION {f["metric"]}: head {f["head"]:.5g} vs '
              f'baseline {f["baseline"]:.5g} ({f["delta_pct"]:+.1f}% '
              f'past the ±{f["threshold_pct"]:.1f}% noise gate)',
              file=sys.stderr)
    print(f'# regress: {metric} {head_metrics[metric]:.0f} changes/s '
          f'(reps {[round(r) for r in reps]}), gate '
          f'{"OK" if verdict["ok"] else "REGRESSION"} over '
          f'{len(judged)} judged metric(s) / {len(rows)} ledger rows'
          f'{"" if ledger_on else " (append skipped)"}; '
          f'perf_gate --check {"OK" if check_ok else "FAIL"}',
          file=sys.stderr)


@section('archlint')
def _sec_archlint():
    # the static-contract gate rides the bench: a perf number appended
    # to the ledger is only trajectory-comparable when the kernel-ledger
    # / counter / determinism contracts held while it was measured. The
    # analysis package is stdlib-only, so this costs ~1s of AST time.
    import time as _time
    from automerge_tpu import analysis
    root = os.path.dirname(os.path.abspath(__file__))
    t0 = _time.perf_counter()
    findings, files, errors = analysis.lint_paths(
        ['automerge_tpu', 'tools', 'bench.py'], analysis.get_rules(),
        root=root)
    baseline = analysis.load_baseline(
        os.path.join(root, 'tools', 'archlint_baseline.json'))
    checked = analysis.check_findings(findings, baseline)
    R['archlint_violations'] = (
        len(checked['violations']) + len(checked['unlisted']) +
        len(checked['stale']) + len(errors))
    R['archlint_suppressed'] = len(checked['suppressed'])
    R['archlint_files'] = len(files)
    R['archlint_s'] = round(_time.perf_counter() - t0, 3)
    print(f'# archlint: {len(files)} files, '
          f'{R["archlint_violations"]} violations, '
          f'{R["archlint_suppressed"]} suppressed '
          f'({R["archlint_s"]}s)', file=sys.stderr)


@section('trace')
def _sec_trace():
    trace_dir = capture_trace(_env('BENCH_DOCS', 10000),
                              _env('BENCH_KEYS', 1000),
                              _env('BENCH_OPS', 100),
                              pallas_variant=R.get('pallas_variant'))
    R['trace_dir'] = trace_dir
    if trace_dir is not None:
        pv = R.get('pallas_variant')
        print(f'# profiler trace (merge + sequence'
              f'{" + pallas " + pv if pv else ""}) '
              f'written to {trace_dir}', file=sys.stderr)


def _final_json():
    from automerge_tpu.observability import health_counts
    result = {
        'metric': 'changes_per_sec_backend_seam_e2e',
        'value': round(R['seam_rate']),
        'unit': 'changes/s',
        'vs_baseline': round(R['seam_rate'] / R['host_rate'], 2),
        'seam_dispatches_per_round': R.get('seam_dispatches_per_round'),
        'init_dispatches': R.get('seam_init_dispatches'),
        'sync_dispatches_per_round': R.get('syncdrv_dispatches_per_round'),
        'archlint_violations': R.get('archlint_violations'),
        'health': health_counts(),
        **DEVICE,
    }
    print(json.dumps(result))


def _run_standalone(name):
    """BENCH_SECTION=<name>: one section, fenced, with its own JSON line.
    BENCH_SECTION=all runs every section but `trace` in this one process
    and prints the same kind of line (the sanity harness's full pass)."""
    if name == 'list':
        print(' '.join(SECTIONS))
        return
    if name == 'all':
        names = [n for n in SECTIONS if n != 'trace']
    elif name in SECTIONS:
        names = [name]
    else:
        print(f'unknown BENCH_SECTION {name!r}; one of: '
              f'{" ".join(SECTIONS)}', file=sys.stderr)
        sys.exit(2)
    _init_platform()
    for n in names:
        _fence()
        SECTIONS[n]()
    out = {'section': name}
    out.update({k: v for k, v in R.items()
                if isinstance(v, (int, float, str, type(None)))})
    out.update(DEVICE)
    print(json.dumps(out))


def _run_sanity():
    """Scaled-down full pass, then key sections standalone; fail if any
    full-run rate and its standalone rate disagree by > 2x. Every pass is
    a CHILD process, run one after another, and this parent never
    touches JAX: a chip belongs to one process at a time, so a parent
    that held it would starve every child it started."""
    import subprocess
    small = {'BENCH_SEAM_DOCS': '1000', 'BENCH_DOCS': '1000',
             'BENCH_HOST_DOCS': '50', 'BENCH_SEAM_TEXT_DOCS': '50',
             'BENCH_TEXT_DOCS': '200', 'BENCH_BLOOM_DOCS': '1000',
             'BENCH_SYNCDRV_DOCS': '500', 'BENCH_ZIPF_DOCS': '5000',
             'BENCH_DUR_DOCS': '1000', 'BENCH_OBS_DOCS': '1000',
             'BENCH_REG_DOCS': '500', 'BENCH_LOAD_DOCS': '200',
             'BENCH_SAVE_CHANGES': '50', 'BENCH_MIXED_DOCS': '100',
             'BENCH_SERVICE_SESSIONS': '500',
             'BENCH_SERVICE_REQUESTS': '3000',
             'BENCH_SERVICE_TENANTS': '32',
             'BENCH_SLO_SESSIONS': '500',
             'BENCH_SLO_REQUESTS': '3000',
             'BENCH_SLO_TENANTS': '32',
             'BENCH_SLO_PAIRS': '2',
             'BENCH_SLO_SERIES_TENANTS': '60',
             'BENCH_QUERY_DOCS': '200',
             'BENCH_QUERY_SUBS': '1000',
             'BENCH_TIER_DOCS': '20000',
             'BENCH_TIER_RAM_DOCS': '20000',
             'BENCH_TIER_DISTINCT': '512',
             'BENCH_TIER_REVIVE': '256',
             'BENCH_TIER_MAT': '128',
             # sanity cares about the RATIO's full-vs-standalone
             # agreement, not the absolute depth; 8k keeps the fixture
             # build off the critical path
             'BENCH_FRONTIER_DEPTHS': '1000,8000',
             'BENCH_FRONTIER_TICK_DOCS': '200',
             'BENCH_FRONTIER_TICK_SUBS': '2000',
             # tenants stay at the default: the paced sweep needs the
             # closed-loop writer pool to SATURATE per-shard capacity
             # (tenants >> shards x batch x ack-latency) or the legs go
             # latency-bound and the scaling curve flattens
             'BENCH_SHARD_REQUESTS': '600',
             'BENCH_SHARD_KILL_REQUESTS': '240',
             'BENCH_PERF_DOCS': '1000',
             'BENCH_CONTROL_TICKS': '150',
             'BENCH_REGRESS_DOCS': '500',
             'BENCH_FABRIC_LINKS': '256,1024',
             'BENCH_FABRIC_LOOP_SAMPLE': '64',
             # scaled-down sanity rows must not pollute the trajectory
             'BENCH_LEDGER': '0',
             'BENCH_REPS': '3'}
    env = dict(os.environ)
    for k, v in small.items():
        env.setdefault(k, v)

    def child(section, timeout, **extra):
        """(last-line JSON or None, failure text) of one bench child."""
        try:
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__)],
                env=dict(env, BENCH_SECTION=section, **extra),
                capture_output=True, text=True, timeout=timeout)
        except subprocess.TimeoutExpired:
            return None, f'timed out ({timeout}s)'
        if section == 'all':
            sys.stderr.write(proc.stderr)   # the full pass's report lines
        try:
            return json.loads(proc.stdout.strip().splitlines()[-1]), None
        except (IndexError, ValueError):
            return None, (f'no JSON line (rc={proc.returncode}, '
                          f'stderr={proc.stderr[-300:]!r})')

    # the stamp the full pass read from JAX; every standalone pass must
    # report the same platform or the comparison is across platforms
    device = dict.fromkeys(('platform', 'device_kind', 'n_devices'))
    full, err = child('all', 4 * 3600)
    if full is None:
        print(json.dumps({'sanity': 'FAIL',
                          'failures': [f'full pass: {err}'], **device}))
        sys.exit(1)
    device.update((k, full.get(k)) for k in device)
    failures = []
    for name, key in SANITY_KEYS.items():
        full_val = full.get(key)
        if full_val is None or (not full_val and
                                not key.endswith('_pct')):
            continue
        extra = {}
        if name == 'seam_dense' and full.get('seam_dense_opc'):
            # the full pass benched at the measured mixed_opc; the
            # standalone run must use the same density or the comparison
            # measures op density, not run-order sensitivity
            extra['BENCH_DENSE_OPC'] = env.get(
                'BENCH_DENSE_OPC', str(full['seam_dense_opc']))
        alone_row, err = child(name, 1800, **extra)
        if alone_row is None or key not in alone_row:
            failures.append(f'{name}: standalone run produced no {key} '
                            f'({err})')
            continue
        if alone_row.get('platform') != device['platform']:
            failures.append(f'{name}: standalone ran on '
                            f'{alone_row.get("platform")!r}, the full '
                            f'pass on {device["platform"]!r}')
            continue
        alone = alone_row[key]
        if key.endswith('_pct'):
            # paired-delta percentages cross zero legitimately: judge
            # by absolute percentage-point difference, not the ratio
            delta = abs(full_val - alone)
            status = 'OK' if delta <= 2.0 else 'FAIL'
            print(f'# sanity {name}.{key}: full {full_val:.2f}% vs '
                  f'standalone {alone:.2f}% ({delta:.2f}pp) {status}',
                  file=sys.stderr)
            if delta > 2.0:
                failures.append(f'{name}.{key}: full {full_val:.2f}% vs '
                                f'standalone {alone:.2f}% = '
                                f'{delta:.2f}pp > 2pp')
            continue
        ratio = max(full_val, alone) / max(min(full_val, alone), 1e-9)
        status = 'OK' if ratio <= 2.0 else 'FAIL'
        print(f'# sanity {name}.{key}: full {full_val:.0f} vs standalone '
              f'{alone:.0f} ({ratio:.2f}x) {status}', file=sys.stderr)
        if ratio > 2.0:
            failures.append(f'{name}.{key}: full {full_val:.0f} vs '
                            f'standalone {alone:.0f} = {ratio:.2f}x > 2x')
    # not a rate ratio: the static-contract gate must read exactly zero
    # (BENCH_SANITY is the harness CI leans on, so a contract violation
    # fails it even when every throughput ratio agrees)
    av = full.get('archlint_violations')
    if av != 0:
        failures.append(f'archlint_violations={av!r} (want 0)')
    print(f'# sanity archlint.archlint_violations: {av!r} '
          f'{"OK" if av == 0 else "FAIL"}', file=sys.stderr)
    if failures:
        print(json.dumps({'sanity': 'FAIL', 'failures': failures,
                          **device}))
        sys.exit(1)
    print(json.dumps({'sanity': 'OK',
                      'sections_checked': list(SANITY_KEYS) +
                      ['archlint'], **device}))


def main():
    standalone = os.environ.get('BENCH_SECTION')
    if standalone:
        _run_standalone(standalone)
        return
    if os.environ.get('BENCH_SANITY'):
        _run_sanity()
        return
    _init_platform()
    for name, fn in SECTIONS.items():
        fn()
        _fence()
    _final_json()


if __name__ == '__main__':
    main()
