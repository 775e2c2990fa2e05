"""Driver ``text_long_replay``: a server that holds long-lived Text documents
whose histories passed millions of edits (crdt-benchmarks B4x100: the
editing trace applied 100 times to one Text), loaded after a start, drains
the keystrokes its editors queued meanwhile.

Set-up first PROBES: a document of a few kilobytes whose op counters lie
past the 2^23 packed window must load and take one change on the device,
or the run ends with exit code 2 (a program that cannot hold such a row
would otherwise spend minutes loading 26 M ops into the host engine). Then
it WRITES every document's saved container at its offset in the passes
(wire_text.py's writer) and calls ``load_docs`` once; nothing is replayed.
A step is ONE ``apply_changes_docs(mirror=False)`` over both documents,
each giving its next `changes_per_step` changes, then a block on every
sequence pool's arrays. Steps run back to back, one caller; the window
closes at the first step boundary at or after ``--seconds``.

The trace is text-trace's generator (text_replay.Trace) run for ONE pass of
`trace_ops` keystrokes; pass p is that pass shifted by p passes of ops
(`Passes`). Every change is one op and follows the change before. Keystroke
t is op t + 1 + `op_base`: 0 in the configuration; the tests' tiny
rehearsals set it to put a short history past the packed window.

The audit holds every document's text to the plain reference
(reference_text.Rga) computed a pass at a time (`reference_order`: each
pass is one run of the list, so one Rga over pass 0 and one over the
unfinished pass give the whole order), reads one document's ``save()``
back with a column reader of its own (`read_saved`: the history is 26 M
ops, past what the by-value reader of wire_text reads in a run's time),
and counts the rows the device does not serve and the documents the host
serves.
"""

import hashlib
import os
import statistics
import sys
import time
import zlib

import numpy as np

import harness
import reference_text
import wire_text
from harness import BenchError
from wire import MAGIC, Reader, rle_string

replay = harness.load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 'text_replay.py'), 'driver text_replay')
ALPHABET, Trace = replay.ALPHABET, replay.Trace

PROBE_KEYSTROKES = 48
# documents of at least this many keystrokes are written (and the one the
# audit saves is written again) by processes beside this one, as many as
# WRITER_PROCESSES and the host's cores allow (the chip's host has 13)
SPAWN_FROM = 100_000
WRITER_PROCESSES = 12


class Passes:
    """Keystrokes of the passes, by global index t (from 1; keystroke t is
    op t + 1): pass p = (t - 1) // L holds keystrokes p * L + 1 ..
    (p + 1) * L, each pass 0's keystroke shifted by p * L; a referent of
    0 (the head) stays the head."""

    def __init__(self, trace, pass_ops):
        trace.extend(pass_ops)
        self.ops = pass_ops
        self.is_insert0 = np.array(trace.is_insert[:pass_ops + 1], bool)
        self.ref0 = np.array(trace.ref[:pass_ops + 1], dtype=np.int64)
        # inserts among a pass's first k keystrokes, and the insert rank of
        # every keystroke of a pass (its character's place among the pass's
        # characters)
        self.inserts0 = np.cumsum(self.is_insert0)
        self.rank0 = self.inserts0 - 1
        self.inserts = int(self.inserts0[-1])
        dels = np.flatnonzero(~self.is_insert0[1:]) + 1
        self.deleted_by0 = np.zeros(pass_ops + 1, dtype=np.int64)
        self.deleted_by0[self.ref0[dels]] = dels

    def keystrokes(self, lo, hi):
        """(is_insert, referent) of keystrokes lo .. hi - 1."""
        t = np.arange(lo, hi, dtype=np.int64)
        p, local = (t - 1) // self.ops, (t - 1) % self.ops + 1
        ref = self.ref0[local]
        return self.is_insert0[local], np.where(ref > 0, ref + p * self.ops,
                                                0)

    def insert_rank(self, t):
        """Global insert rank of the insert keystrokes t (an array)."""
        p, local = (t - 1) // self.ops, (t - 1) % self.ops + 1
        return p * self.inserts + self.rank0[local]

    def inserts_before(self, n):
        """Inserts among keystrokes 1 .. n."""
        p, k = divmod(n, self.ops)
        return p * self.inserts + int(self.inserts0[k])


def linked_order(passes, k):
    """The elements (local keystrokes) of the first k keystrokes of a pass
    in list order, by the writer's own rule: one author's insert lands
    right after its referent (its id is the greatest yet)."""
    after = {0: 0}
    is_insert, ref = passes.is_insert0, passes.ref0
    for t in range(1, k + 1):
        if is_insert[t]:
            r = int(ref[t])
            after[t] = after[r]
            after[r] = t
    out, at = [], after[0]
    while at:
        out.append(at)
        at = after[at]
    return np.array(out, dtype=np.int64)


def reference_order(passes, k):
    """The plain reference's list of the first k keystrokes of a pass:
    (elements, the keystroke that deleted each or 0), in local keystrokes,
    from reference_text.Rga (ids are local counters: one actor)."""
    rga = reference_text.Rga()
    is_insert, ref = passes.is_insert0, passes.ref0
    for t in range(1, k + 1):
        r = int(ref[t])
        if is_insert[t]:
            rga.insert(t, r if r else None, '')
        else:
            rga.delete(t, r)
    elements = rga.elements()
    order = np.array([e[0] for e in elements], dtype=np.int64)
    gone = np.array([e[2][0] if e[2] else 0 for e in elements],
                    dtype=np.int64)
    return order, gone


def history_elements(passes, n, order_of):
    """Every element of keystrokes 1 .. n in list order: (element, its
    referent, the keystroke that deleted it or 0), global keystrokes.
    `order_of(k)` gives (order, deleted_by) of a pass's first k keystrokes
    (a whole pass is asked once); pass p is one run of the list, in front
    of passes 0 .. p - 1."""
    L = passes.ops
    full, k = divmod(n, L)
    whole = order_of(L) if full else None
    blocks = []
    if k:
        order, gone = order_of(k)
        blocks.append((full, order, gone))
    for p in range(full - 1, -1, -1):
        blocks.append((p, whole[0], whole[1]))
    elems, refs, dels = [], [], []
    for p, order, gone in blocks:
        shift = p * L
        ref = passes.ref0[order]
        elems.append(order + shift)
        refs.append(np.where(ref > 0, ref + shift, 0))
        dels.append(np.where(gone > 0, gone + shift, 0))
    if not blocks:
        empty = np.zeros(0, dtype=np.int64)
        return empty, empty, empty
    return np.concatenate(elems), np.concatenate(refs), np.concatenate(dels)


def writer_order(passes):
    memo = {}

    def order_of(k):
        if k not in memo:
            order = linked_order(passes, k)
            gone = passes.deleted_by0[order]
            memo[k] = (order, np.where(gone <= k, gone, 0))
        return memo[k]
    return order_of


def reference_orders(passes):
    memo = {}

    def order_of(k):
        if k not in memo:
            memo[k] = reference_order(passes, k)
        return memo[k]
    return order_of


def write_documents(passes, actors, offsets, chars, block, base, workers):
    """Every document's saved container at its offset: [(bytes, head hash,
    max_op of every change)]. The history is the makeText and then changes
    of `block` keystrokes, the same blocks in every document: a block's
    columns are encoded once, its change once a document. Keystroke t is
    op t + 1 + base. With `workers` > 1 the columns of the blocks and the
    documents' op columns are encoded by that many processes (they need
    no chip); the hash chain is computed here."""
    blocks = sorted({(lo, min(lo + block, offset + 1))
                     for offset in offsets
                     for lo in range(1, offset + 1, block)})

    def keystrokes(lo, hi):
        is_insert, ref = passes.keystrokes(lo, hi)
        return is_insert, np.where(ref > 0, ref + 1 + base, 0)

    order_of = writer_order(passes)
    history = []
    for offset in offsets:
        elems, refs, gone = history_elements(passes, offset, order_of)
        history.append((elems + 1 + base, np.where(refs > 0, refs + 1 + base,
                                                   0),
                        np.where(gone > 0, gone + 1 + base, 0),
                        passes.insert_rank(elems)))
    with _pool(workers) as pool:
        columns = dict(zip(blocks, pool.map(
            wire_text.keystroke_columns,
            *zip(*(keystrokes(lo, hi) for lo, hi in blocks)),
            chunksize=max(len(blocks) // (8 * workers), 1))))
        heads, max_ops = [], []
        for d, (actor, offset) in enumerate(zip(actors, offsets)):
            _buf, head = wire_text.make_text_change(actor)
            ops = [1]
            for lo in range(1, offset + 1, block):
                hi = min(lo + block, offset + 1)
                ranks = np.arange(passes.inserts_before(lo - 1),
                                  passes.inserts_before(hi - 1))
                _buf, head = wire_text.keystrokes_change(
                    actor, len(ops) + 1, lo + 1 + base, [head],
                    columns[lo, hi], chars[d][ranks].tobytes())
                ops.append(hi + base)
            heads.append(head)
            max_ops.append(ops)
        del columns
        documents = list(pool.map(
            wire_text.text_document, actors, heads, max_ops,
            *zip(*((ids, refs, chars[d][rank].tobytes(), gone)
                   for d, (ids, refs, gone, rank) in enumerate(history)))))
    return list(zip(documents, heads, max_ops))


class _Serial:
    """What _pool gives for one worker: map and submit in this process."""

    def __enter__(self):
        return self

    def __exit__(self, *_exc):
        return False

    @staticmethod
    def map(fn, *iterables, chunksize=1):
        return map(fn, *iterables)

    @staticmethod
    def submit(fn, *args):
        done = fn(*args)
        return type('Done', (), {'result': staticmethod(lambda: done)})


def _pool(workers):
    """A pool of `workers` processes that start afresh (spawn: no copy of
    this process's JAX state), or this process alone for one."""
    if workers <= 1:
        return _Serial()
    import concurrent.futures
    import multiprocessing
    return concurrent.futures.ProcessPoolExecutor(
        max_workers=workers, mp_context=multiprocessing.get_context('spawn'))


# ---------------------------------------------------------------------------
# the probe: a small document past the packed window, on the device
# ---------------------------------------------------------------------------

def probe(seed):
    """Load a Text of PROBE_KEYSTROKES keystrokes whose ops start past
    2^23, apply one more keystroke, and raise BenchError unless both stay
    on the device: the handle is the fleet's, no call fell back to the
    exact path or promoted, no row is inexact. (Whether the text is right
    is the audit's to say.)"""
    import jax
    from automerge_tpu.fleet import backend as fleet_backend
    from automerge_tpu.fleet import loader
    from automerge_tpu.fleet.backend import DocFleet
    rng = np.random.default_rng([seed, 3])
    actor = rng.bytes(16).hex()
    start = (1 << 23) + int(rng.integers(1, 1000))
    n = PROBE_KEYSTROKES
    # type a word, then take its last character back, one op a change
    is_insert = np.ones(n, dtype=bool)
    is_insert[-1] = False
    ref_ctr = np.r_[0, start + np.arange(n - 2), start + n - 2]
    chars = ALPHABET[rng.integers(0, len(ALPHABET), size=n)]
    _buf, head = wire_text.make_text_change(actor)
    _buf, head = wire_text.keystrokes_change(
        actor, 2, start, [head], wire_text.keystroke_columns(is_insert,
                                                             ref_ctr),
        chars[is_insert].tobytes())
    elem_ctr = start + np.arange(n - 1)
    deleted = np.zeros(n - 1, dtype=np.int64)
    deleted[-1] = start + n - 1
    data = wire_text.text_document(
        actor, head, [1, start + n - 1], elem_ctr,
        np.r_[0, elem_ctr[:-1]], chars[:n - 1].tobytes(), deleted)
    fleet = DocFleet(doc_capacity=1)
    handles = loader.load_docs([data], fleet)
    buf, _head = wire_text.keystroke_change(
        bytes.fromhex(actor), 3, start + n, bytes.fromhex(head), True,
        int(elem_ctr[-2]), b'!')
    why = []
    if not handles[0]['state'].is_fleet:
        why.append('the load left it to the host engine')
    else:
        handles, _ = fleet_backend.apply_changes_docs(handles, [[buf]],
                                                      mirror=False)
        jax.block_until_ready([st.tree_flatten()[0] for st in
                               fleet.seq_pools.pools.values()])
        m = fleet.metrics
        if not handles[0]['state'].is_fleet or m.promotions or \
                m.fallbacks or m.exact_calls:
            why.append(f'the change left the device path (fallbacks '
                       f'{m.fallbacks}, exact calls {m.exact_calls}, '
                       f'promotions {m.promotions})')
        elif any(bool(np.asarray(st.inexact).any())
                 for st in fleet.seq_pools.pools.values()):
            why.append('its row is flagged inexact')
    fleet_backend.free_docs(handles)
    if why:
        raise BenchError('probe: a Text whose op counters pass 2^23 does '
                         'not stay on the device: ' + '; '.join(why))


# ---------------------------------------------------------------------------
# set-up, steps, window
# ---------------------------------------------------------------------------

def setup(config, mix, seed):
    from automerge_tpu.fleet import loader
    from automerge_tpu.fleet.backend import DocFleet
    mix = {key: config.get(key, value) for key, value in mix.items()}
    t0 = time.perf_counter()
    probe(seed)
    t1 = time.perf_counter()
    rng = np.random.default_rng(seed)
    n_docs = config['docs']
    passes = Passes(
        Trace(np.random.default_rng([seed, 1]), config['insert_share'],
              config['typing_run_mean'], config['backspace_share']),
        config['trace_ops'])
    lo, hi = config['start_offset_ops']
    before = (config['start_pass'] - 1) * config['trace_ops']
    offsets = (before + rng.integers(lo, hi + 1, size=n_docs)).tolist()
    actors = [rng.bytes(16).hex() for _ in range(n_docs)]
    char_rngs = [np.random.default_rng([seed, 2, d]) for d in range(n_docs)]
    chars = [ALPHABET[r.integers(0, len(ALPHABET),
                                 size=passes.inserts_before(offset))]
             for r, offset in zip(char_rngs, offsets)]
    t2 = time.perf_counter()
    base = int(config.get('op_base', 0))
    workers = min(WRITER_PROCESSES, os.cpu_count() or 1) \
        if max(offsets) >= SPAWN_FROM else 1
    written = write_documents(passes, actors, offsets, chars,
                              config['prefix_change_ops'], base, workers)
    t3 = time.perf_counter()
    from jax.profiler import TraceAnnotation
    fleet = DocFleet(doc_capacity=n_docs)
    handles = []
    with TraceAnnotation('load_docs'):
        # a document a call: the host's arrays for one 26 M-op document
        # at a time
        for data, _h, _m in written:
            handles += loader.load_docs([data], fleet)
    t4 = time.perf_counter()
    print(f'# long text set-up: probe {t1 - t0:.2f} s, trace {t2 - t1:.2f} '
          f's, documents written {t3 - t2:.2f} s '
          f'({sum(len(w[0]) for w in written)} bytes, offsets {offsets}), '
          f'load_docs {t4 - t3:.2f} s', file=sys.stderr, flush=True)
    return {
        'config': config, 'mix': mix, 'rng': rng, 'n_docs': n_docs,
        'passes': passes, 'actors': actors, 'offsets': offsets,
        'op_base': base,
        'char_rngs': char_rngs, 'fleet': fleet, 'handles': handles,
        # per document: the hash its next change follows, as bytes; the
        # characters of the history's inserts and of the keystrokes typed
        # after the offset; the changes encoded and not yet applied
        'head': [bytes.fromhex(head) for _d, head, _m in written],
        'last_head': [head for _d, head, _m in written],
        'history_chars': chars,
        'history_max_ops': [m for _d, _h, m in written],
        'n_history_changes': [len(m) for _d, _h, m in written],
        'typed': [[] for _ in range(n_docs)],
        'queue': [[] for _ in range(n_docs)],
        'encoded': [0] * n_docs, 'applied': [0] * n_docs,
        'steps_planned': 0, 'next_step': 0, 'floor_waits': 0,
    }


def encode(state, n):
    """Append to every document's queue its next `n` changes: one
    keystroke each, following the change before."""
    passes = state['passes']
    for d in range(state['n_docs']):
        first = state['offsets'][d] + state['encoded'][d] + 1
        is_insert, ref = passes.keystrokes(first, first + n)
        actor = bytes.fromhex(state['actors'][d])
        chars = ALPHABET[state['char_rngs'][d].integers(
            0, len(ALPHABET), size=n)].tobytes()
        state['typed'][d].append(chars)
        head = state['head'][d]
        seq = state['n_history_changes'][d] + state['encoded'][d]
        queue = state['queue'][d]
        base = state['op_base']
        for i, (ins, r) in enumerate(zip(is_insert.tolist(), ref.tolist())):
            seq += 1
            buf, head = wire_text.keystroke_change(
                actor, seq, first + i + 1 + base, head, ins,
                r + 1 + base if r else 0, chars[i:i + 1])
            queue.append((buf, head))
        state['head'][d] = head
        state['encoded'][d] += n


def step(state):
    """One apply_changes_docs over both documents, each giving its next
    changes_per_step changes, then the block. Returns the changes."""
    import jax
    from jax.profiler import TraceAnnotation
    from automerge_tpu.fleet import backend as fleet_backend
    n = int(state['mix']['changes_per_step'])
    per_doc = []
    for d in range(state['n_docs']):
        queue, at = state['queue'][d], state['applied'][d]
        if at + n > len(queue):
            raise BenchError(
                f'document {d} has {len(queue) - at} encoded changes left '
                f'and the step asks for {n}: the window outran what '
                'set-up encoded')
        per_doc.append([buf for buf, _head in queue[at:at + n]])
    with TraceAnnotation('apply_changes_docs'):
        state['handles'], _ = fleet_backend.apply_changes_docs(
            state['handles'], per_doc, mirror=False)
    with TraceAnnotation('block'):
        jax.block_until_ready(
            [st.tree_flatten()[0]
             for st in state['fleet'].seq_pools.pools.values()])
    for d in range(state['n_docs']):
        state['applied'][d] += n
    return n * state['n_docs']


def warmup(state):
    """`warmup_steps` steps as the window's, then, from their measured
    time, the encoding of twice the changes a window can use: the clients'
    keystrokes, whose amount follows from how fast the program steps, so
    their seconds go to ``state['traffic_s']``."""
    mix = state['mix']
    n = int(mix['changes_per_step'])
    warm = int(mix['warmup_steps'])
    encode(state, n * warm)
    took = []
    for _ in range(warm):
        t0 = time.perf_counter()
        step(state)
        took.append(time.perf_counter() - t0)
    steady = max(min(took[1:] or took), float(mix['step_floor_ms']) / 1e3)
    t0 = time.perf_counter()
    steps = int(2 * float(mix['encode_for_seconds']) / steady) + 2
    encode(state, n * steps)
    state['steps_planned'] = steps
    state['traffic_s'] = time.perf_counter() - t0
    print(f'# long text warm-up: steps {[round(t, 3) for t in took]} s; '
          f'{steps} steps ({steps * n * state["n_docs"]} changes) encoded '
          f'in {state["traffic_s"]:.2f} s (traffic_s)', file=sys.stderr,
          flush=True)


def window(state, seconds, tracer):
    fleet = state['fleet']
    floor = float(state['mix']['step_floor_ms']) / 1e3
    before = fleet.metrics.snapshot()
    steps = attempted = failed = floor_waits = 0
    ends = []
    start_ns = time.perf_counter_ns()
    start = time.perf_counter()
    while True:
        tracer.poll()
        if state['next_step'] >= state['steps_planned']:
            raise BenchError(
                f'the window used all {state["steps_planned"]} steps that '
                'set-up encoded changes for')
        state['next_step'] += 1
        count = int(state['mix']['changes_per_step']) * state['n_docs']
        attempted += count
        began = time.perf_counter()
        try:
            step(state)
        except BenchError:
            raise
        except Exception as exc:   # the step's changes count as failed
            failed += count
            print(f'# step {steps} raised {exc!r}', file=sys.stderr)
        steps += 1
        short = began + floor - time.perf_counter()
        if short > 0:
            floor_waits += 1
            time.sleep(short)
        now = time.perf_counter()
        ends.append(now)
        if now - start >= seconds:
            break
    elapsed = now - start
    took = [b - a for a, b in zip([start] + ends, ends)]
    state['floor_waits'] = floor_waits
    counters = fleet.metrics.delta(before)
    print(f'# long replay window: {steps} steps, median step '
          f'{statistics.median(took) * 1e3:.2f} ms, fastest '
          f'{min(took) * 1e3:.2f} ms, slowest {max(took) * 1e3:.2f} ms; '
          f'floor_waits {floor_waits}; seq_wide_rows '
          f'{getattr(fleet.metrics, "seq_wide_rows", None)}, seq_repacks '
          f'{counters.get("seq_repacks")}', file=sys.stderr, flush=True)
    return {
        'attempted': attempted, 'failed': failed,
        'metrics': {'ingest_changes_per_s': (attempted - failed) / elapsed},
        'facts': {'steps': steps, 'elapsed_s': elapsed,
                  'window_ns': (start_ns, time.perf_counter_ns()),
                  'fleet_counters': counters,
                  'seq_pool_bytes': getattr(fleet.metrics, 'seq_pool_bytes',
                                            None),
                  'seq_nodes': getattr(fleet.metrics, 'seq_nodes', None),
                  'seq_wide_rows': getattr(fleet.metrics, 'seq_wide_rows',
                                           None),
                  'seq_nodes_by_cls': {
                      cls: st.elem_id.shape[1] for cls, st in
                      fleet.seq_pools.pools.items()}},
    }


# ---------------------------------------------------------------------------
# the audit
# ---------------------------------------------------------------------------

def doc_chars(state, d):
    """The character of every insert of document d's history and applied
    changes, by global insert rank."""
    n = state['offsets'][d] + state['applied'][d]
    passes = state['passes']
    typed = np.frombuffer(b''.join(state['typed'][d]), dtype=np.uint8)
    first = state['offsets'][d] + 1
    is_insert, _ref = passes.keystrokes(first, n + 1)
    return np.concatenate([state['history_chars'][d],
                           typed[:n - first + 1][is_insert]])


def expected(state, d, order_of):
    """Document d by the reference: (elements, referents, deleting
    keystrokes, characters of the elements), global keystrokes."""
    passes = state['passes']
    n = state['offsets'][d] + state['applied'][d]
    elems, refs, gone = history_elements(passes, n, order_of)
    return elems, refs, gone, doc_chars(state, d)[passes.insert_rank(elems)]


def audit(state):
    """Every document's text against the reference; ``save()`` of a seeded
    document read back by the benchmark's own column reader against the
    reference and the record of what was applied; the rows the device
    does not serve; the documents the host serves; the steps of the window
    that the step floor waited out. A document that is not served from
    the device is counted as such and not read (the host would replay
    26 M ops)."""
    from automerge_tpu.fleet import backend as fleet_backend
    fleet, handles = state['fleet'], state['handles']
    n_docs = state['n_docs']
    host = [d for d in range(len(handles))
            if not handles[d]['state'].is_fleet]
    served = [d for d in range(len(handles)) if d not in host and not any(
        fleet.seq_row_inexact(row) for row in fleet.slot_seq.get(
            handles[d]['state']._impl.slot, {}).values())]
    inexact = sum(int(np.asarray(st.inexact).sum())
                  for st in fleet.seq_pools.pools.values())
    before = fleet.metrics.read_host_docs
    views = fleet_backend.materialize_docs([handles[d] for d in served])
    host_reads = fleet.metrics.read_host_docs - before
    order_of = reference_orders(state['passes'])
    text_mismatches = n_docs - len(served)
    for d, view in zip(served, views):
        elems, _refs, gone, chars = expected(state, d, order_of)
        want = chars[gone == 0].tobytes().decode()
        if view.get(wire_text.TEXT_KEY) != want:
            text_mismatches += 1
    save_mismatches = 0
    d = int(state['rng'].integers(0, n_docs))
    if d in served:
        # what the document should save, written in another process while
        # this one saves it
        with _pool(2 if state['offsets'][d] >= SPAWN_FROM else 1) as pool:
            written = pool.submit(wire_text.text_document,
                                  *expected_document(state, d, order_of))
            saved = bytes(fleet_backend.save(handles[d]))
            why = saved_differs(state, d, order_of, saved, written.result())
    else:
        why = 'not served from the device'
    if why:
        save_mismatches += 1
        print(f'# save of document {d}: {why}', file=sys.stderr)
    return {
        'docs_missing': (n_docs - len(handles), 0),
        'text_mismatches': (text_mismatches, 0),
        'save_mismatches': (save_mismatches, 0),
        'inexact_rows': (inexact, 0),
        'host_docs': (len(host) + host_reads, 0),
        'floor_waits': (state['floor_waits'], 0),
    }


def recorded(state, d):
    """(actor, head, max_op of every change) of document d as set-up and
    the window recorded them."""
    applied, base = state['applied'][d], state['op_base']
    head = state['queue'][d][applied - 1][1].hex() if applied \
        else state['last_head'][d]
    return state['actors'][d], head, state['history_max_ops'][d] + list(
        range(state['offsets'][d] + 2 + base,
              state['offsets'][d] + applied + 2 + base))


def expected_document(state, d, order_of):
    """wire_text.text_document's arguments for document d as the reference
    and the record of what was applied have it."""
    base = state['op_base']
    elems, refs, gone, chars = expected(state, d, order_of)
    return (*recorded(state, d), elems + 1 + base,
            np.where(refs > 0, refs + 1 + base, 0), chars.tobytes(),
            np.where(gone > 0, gone + 1 + base, 0))


def saved_differs(state, d, order_of, data, written):
    """None where the saved document holds exactly the loaded history and
    the applied changes, else what differs: the one head, the changes
    (each one's actor, sequence number, greatest opId and dependency), and
    every op's id, referent, insert flag, character and successors in
    sequence order. What they should be is the reference's list and the
    record of what was applied; where the saved bytes are not `written`,
    what wire_text writes for it (expected_document), they are read back
    column by column."""
    if data == written:
        return None
    try:
        doc = read_saved(data)
    except (ValueError, IndexError, KeyError, zlib.error) as exc:
        return f'does not read back: {exc}'
    actor, head, max_ops = recorded(state, d)
    if doc['actors'] != [actor] or doc['heads'] != [head]:
        return f"actors {doc['actors']}, heads {doc['heads']}"
    c = doc['changes']
    n = len(max_ops)
    if len(c['seq']) != n:
        return f"{len(c['seq'])} changes, {n} recorded"
    for what, got, want in (
            ('actors', c['actor'], np.zeros(n, np.int64)),
            ('sequence numbers', c['seq'], np.arange(1, n + 1)),
            ('greatest opIds', c['max_op'], max_ops),
            ('dependency counts', c['deps_num'],
             np.r_[0, np.ones(n - 1, np.int64)]),
            ('dependencies', c['deps_index'], np.arange(n - 1))):
        if not np.array_equal(got, want):
            return f'the changes read other {what}'
    base = state['op_base']
    elems, refs, gone, chars = expected(state, d, order_of)
    ops = doc['ops']
    checks = {
        'ids': (ops['id_ctr'], elems + 1 + base),
        'id actors': (ops['id_actor'], np.zeros_like(elems)),
        'referents': (ops['key_ctr'], np.where(refs > 0, refs + 1 + base,
                                               0)),
        'referent actors': (ops['key_actor'],
                            np.where(refs > 0, 0, -1)),
        'characters': (ops['chars'], chars),
        'successor counts': (ops['succ_num'], (gone > 0).astype(np.int64)),
        'successors': (ops['succ_ctr'], gone[gone > 0] + 1 + base),
        'successor actors': (ops['succ_actor'],
                             np.zeros(int((gone > 0).sum()), np.int64)),
    }
    for what, (got, want) in checks.items():
        if len(got) != len(want) or not np.array_equal(got, want):
            return f'{len(elems)} elements by the reference: the {what} ' \
                   'differ'
    return None


# ---------------------------------------------------------------------------
# the benchmark's column reader of a saved one-Text document
# ---------------------------------------------------------------------------

def leb_values(data):
    """Every LEB128 number of `data`: (unsigned, signed) int64 arrays."""
    b = np.frombuffer(data, dtype=np.uint8)
    if not len(b):
        empty = np.zeros(0, dtype=np.int64)
        return empty, empty
    ends = np.flatnonzero(b < 0x80)
    if not len(ends) or ends[-1] != len(b) - 1:
        raise ValueError('a LEB128 number runs past its column')
    starts = np.r_[0, ends[:-1] + 1]
    lengths = ends - starts + 1
    if lengths.max() > 9:
        raise ValueError('a LEB128 number past 63 bits')
    k = np.arange(len(b)) - np.repeat(starts, lengths)
    unsigned = np.add.reduceat((b & 0x7f).astype(np.int64) << (7 * k),
                               starts)
    negative = (b[ends] & 0x40) != 0
    signed = unsigned - np.where(negative, np.left_shift(
        np.int64(1), 7 * lengths), 0)
    return unsigned, signed


def rle_values(data, signed):
    """An RLE column: (values int64, null mask). A run is (count > 0,
    value), a literal group (-count, values...), a null run (0, count)."""
    unsigned, signed_v = leb_values(data)
    values = signed_v if signed else unsigned
    if not len(values):
        empty = np.zeros(0, dtype=np.int64)
        return empty, np.zeros(0, dtype=bool)
    heads = signed_v.tolist()
    tok, count, mode = [], [], []
    i, m = 0, len(heads)
    while i < m:
        h = heads[i]
        if h > 0:
            tok.append(i + 1), count.append(h), mode.append(0)
            i += 2
        elif h < 0:
            tok.append(i + 1), count.append(-h), mode.append(1)
            i += 1 - h
        else:
            tok.append(i + 1), count.append(int(unsigned[i + 1]))
            mode.append(2)
            i += 2
    if i != m:
        raise ValueError('an RLE column ends inside a run')
    tok, count, mode = (np.array(x, dtype=np.int64) for x in
                        (tok, count, mode))
    out_start = np.cumsum(count) - count
    at = np.arange(int(count.sum())) - np.repeat(out_start, count)
    src = np.repeat(tok, count) + at * np.repeat(mode == 1, count)
    null = np.repeat(mode == 2, count)
    return np.where(null, 0, values[np.where(null, 0, src)]), null


def delta_values(data):
    values, null = rle_values(data, True)
    out = np.zeros(len(values), dtype=np.int64)
    out[~null] = np.cumsum(values[~null])
    return out, null


def boolean_values(data):
    runs, _signed = leb_values(data)
    return np.repeat(np.arange(len(runs)) % 2 == 1, runs)


def read_saved(data):
    """A saved one-Text document, read back column by column:
    {'actors', 'heads', 'changes': {actor, seq, max_op, deps_num,
    deps_index}, 'ops': {id_ctr, id_actor, key_ctr, key_actor (-1 for the
    head), chars, succ_num, succ_actor, succ_ctr}} for the elements (the
    makeText, op 0, checked and left out). Raises ValueError on anything
    else."""
    data = bytes(data)
    if data[:4] != MAGIC:
        raise ValueError('no magic bytes')
    chunk = Reader(data[8:])
    kind, length = chunk.take(1)[0], chunk.uleb()
    start = 8 + chunk.at
    if kind != wire_text.CHUNK_DOCUMENT or start + length != len(data):
        raise ValueError(f'chunk type {kind}: not one document chunk')
    if hashlib.sha256(data[8:]).digest()[:4] != data[4:8]:
        raise ValueError('checksum does not match')
    body = Reader(data[start:])
    actors = [body.take(body.uleb()).hex() for _ in range(body.uleb())]
    heads = [body.take(32).hex() for _ in range(body.uleb())]
    infos = [[(body.uleb(), body.uleb()) for _ in range(body.uleb())]
             for _group in range(2)]
    change_cols, op_cols = wire_text._columns(body, infos)

    def uint(cols, cid):
        return rle_values(cols.get(cid, b''), False)

    c_actor, _ = uint(change_cols, wire_text.CHANGE_ACTOR)
    c_seq, _ = delta_values(change_cols.get(wire_text.CHANGE_SEQ, b''))
    c_max, _ = delta_values(change_cols.get(wire_text.CHANGE_MAX_OP, b''))
    deps_num, _ = uint(change_cols, wire_text.CHANGE_DEPS_NUM)
    deps_index, _ = delta_values(
        change_cols.get(wire_text.CHANGE_DEPS_INDEX, b''))
    id_ctr, _ = delta_values(op_cols.get(wire_text.OP_ID_CTR, b''))
    n = len(id_ctr)

    def padded(values, null=None):
        if len(values) > n:
            raise ValueError('a column longer than the ops')
        out = np.zeros(n, dtype=np.int64)
        out[:len(values)] = values
        miss = np.ones(n, dtype=bool)
        miss[:len(values)] = False if null is None else null
        return out, miss

    id_actor, _ = padded(*uint(op_cols, wire_text.OP_ID_ACTOR))
    obj_ctr, obj_null = padded(*uint(op_cols, wire_text.OP_OBJ_CTR))
    obj_actor, _ = padded(*uint(op_cols, wire_text.OP_OBJ_ACTOR))
    key_ctr, key_null = padded(*delta_values(
        op_cols.get(wire_text.OP_KEY_CTR, b'')))
    key_actor, key_actor_null = padded(*uint(op_cols,
                                             wire_text.OP_KEY_ACTOR))
    actions, _ = padded(*uint(op_cols, wire_text.OP_ACTION))
    inserts = padded(boolean_values(op_cols.get(wire_text.OP_INSERT,
                                                b'')))[0]
    val_len, _ = padded(*uint(op_cols, wire_text.OP_VAL_LEN))
    succ_num, _ = padded(*uint(op_cols, wire_text.OP_SUCC_NUM))
    succ_actor, _ = uint(op_cols, wire_text.OP_SUCC_ACTOR)
    succ_ctr, _ = delta_values(op_cols.get(wire_text.OP_SUCC_CTR, b''))
    key_str = rle_string(op_cols.get(wire_text.OP_KEY_STR, b''))
    raw = np.frombuffer(op_cols.get(wire_text.OP_VAL_RAW, b''), np.uint8)
    if not n or actions[0] != wire_text.ACTION_MAKE_TEXT or \
            not key_str or key_str[0] != wire_text.TEXT_KEY or \
            not obj_null[0] or inserts[0]:
        raise ValueError('the first op does not make the Text at root '
                         f'key {wire_text.TEXT_KEY!r}')
    if any(k is not None for k in key_str[1:]):
        raise ValueError('an element carries a map key')
    text = (obj_ctr[1:] == id_ctr[0]) & (obj_actor[1:] == id_actor[0]) & \
        ~obj_null[1:]
    if not (text.all() and (actions[1:] == wire_text.ACTION_SET).all() and
            inserts[1:].all() and
            (val_len[1:] == wire_text.ONE_CHAR).all()):
        raise ValueError('an op is no one-character insert into the Text')
    if len(raw) != n - 1 or int(succ_num.sum()) != len(succ_ctr):
        raise ValueError(f'{len(raw)} bytes of values for {n - 1} '
                         'elements, or successors that do not add up')
    key_ctr = np.where(key_null, 0, key_ctr)
    return {
        'actors': actors, 'heads': heads,
        'changes': {'actor': c_actor, 'seq': c_seq, 'max_op': c_max,
                    'deps_num': deps_num, 'deps_index': deps_index},
        'ops': {'id_ctr': id_ctr[1:], 'id_actor': id_actor[1:],
                'key_ctr': key_ctr[1:],
                'key_actor': np.where(key_actor_null, -1, key_actor)[1:],
                'chars': raw.astype(np.int64), 'succ_num': succ_num[1:],
                'succ_actor': succ_actor, 'succ_ctr': succ_ctr},
    }
