"""Driver ``text_replay``: a collaborative-text server that has loaded its
long-lived documents after a start and drains the keystrokes its editors
queued meanwhile. Set-up WRITES every document's saved container at its
offset in the editing trace (wire_text.py) and calls ``load_docs`` once;
nothing is replayed. A step is ONE ``apply_changes_docs(mirror=False)`` over
all documents on the RESIDENT fleet, document d giving its next k changes (k
drawn for every document and step), then a block on every sequence pool's
arrays. Steps run back to back, one caller; the window closes at the first
step boundary at or after ``--seconds``.

The trace is generated, not read (the configuration's ``assumed``): one
author typing in runs at a cursor that jumps, with the source's counts. All
documents share the trace's structure and differ in actor id, offset and
characters. Every change is one op and follows the change before.
"""

import statistics
import sys
import time

import numpy as np

import reference_text
import wire_text
from harness import BenchError

ALPHABET = np.frombuffer(b'abcdefghijklmnopqrstuvwxyz ', dtype=np.uint8)


class Trace:
    """The structure of one author's editing trace, grown on demand:
    ``is_insert[t]`` and ``ref[t]`` for keystroke t (from 1; index 0 unused)
    — an insert goes after element ``ref`` (a keystroke's index, 0: the
    head), a delete removes element ``ref``. ``order()`` is every element in
    sequence order, deleted ones too."""

    def __init__(self, rng, insert_share, run_mean, backspace_share):
        self.rng = rng
        self.insert_share = insert_share
        self.run_mean = run_mean
        self.backspace_share = backspace_share
        self.is_insert = [False]
        self.ref = [0]
        # the visible text as a gap buffer around the cursor: `left` ends
        # at the cursor, `right` is what follows it, reversed
        self.left, self.right = [], []
        self.run_left = 0
        self.after = [0]          # element (0: head) -> the element after it

    def __len__(self):
        return len(self.ref) - 1

    def _jump(self, u):
        """Cursor to just after the live element drawn by u in [0, 1)."""
        left, right = self.left, self.right
        at = int(u * (len(left) + len(right))) + 1
        if at < len(left):
            right.extend(reversed(left[at:]))
            del left[at:]
        elif at > len(left):
            take = at - len(left)
            left.extend(reversed(right[-take:]))
            del right[-take:]

    def extend(self, n_total):
        more = n_total - len(self)
        if more <= 0:
            return
        rng = self.rng
        kinds = (rng.random(more) < self.insert_share).tolist()
        backspace = (rng.random(more) < self.backspace_share).tolist()
        where = rng.random((more, 2)).tolist()
        runs = rng.geometric(1.0 / self.run_mean, size=more).tolist()
        left, after = self.left, self.after
        for i in range(more):
            t = len(self.ref)
            live = len(left) + len(self.right)
            if self.run_left == 0:
                if live:
                    self._jump(where[i][0])
                self.run_left = runs[i]
            self.run_left -= 1
            if kinds[i] or not live:
                ref = left[-1] if left else 0
                self.is_insert.append(True)
                self.ref.append(ref)
                after.append(after[ref])
                after[ref] = t
                left.append(t)
            else:
                if not backspace[i] or not left:
                    self._jump(where[i][1])
                self.is_insert.append(False)
                self.ref.append(left.pop())
                after.append(0)

    def order(self):
        out, at = [], self.after[0]
        while at:
            out.append(at)
            at = self.after[at]
        return np.array(out, dtype=np.int64)


def write_documents(trace, actors, offsets, chars, block):
    """Every document's saved container at its offset, and what the audit
    holds a save against: [(bytes, head hash, max_op of every change)]. The
    history is the makeText and then changes of `block` keystrokes; the
    columns of a whole block are the same in every document but for the
    characters, so they are encoded once."""
    is_insert = np.array(trace.is_insert, dtype=bool)
    ref = np.array(trace.ref, dtype=np.int64)
    order = trace.order()
    deleted_by = np.zeros(len(ref), dtype=np.int64)
    dels = np.flatnonzero(~is_insert[1:]) + 1
    deleted_by[ref[dels]] = dels
    # chars[d][j] is the character of document d's j-th insert
    insert_rank = np.cumsum(is_insert) - 1

    def columns(lo, hi):
        # keystroke t is op t + 1, and so is the element it inserts
        return wire_text.keystroke_columns(
            is_insert[lo:hi], np.where(ref[lo:hi] > 0, ref[lo:hi] + 1, 0))

    whole = {}
    out = []
    for actor, offset, doc_chars in zip(actors, offsets, chars):
        _buf, head = wire_text.make_text_change(actor)
        max_ops = [1]
        for lo in range(1, offset + 1, block):
            hi = min(lo + block, offset + 1)
            if hi - lo == block:
                if lo not in whole:
                    whole[lo] = columns(lo, hi)
                cols = whole[lo]
            else:
                cols = columns(lo, hi)
            typed = doc_chars[insert_rank[lo - 1] + 1:insert_rank[hi - 1] + 1]
            _buf, head = wire_text.keystrokes_change(
                actor, len(max_ops) + 1, lo + 1, [head], cols,
                typed.tobytes())
            max_ops.append(hi)
        elems = order[order <= offset]
        gone = deleted_by[elems]
        data = wire_text.text_document(
            actor, head, max_ops, elems + 1,
            np.where(ref[elems] > 0, ref[elems] + 1, 0),
            doc_chars[insert_rank[elems]].tobytes(),
            np.where((gone > 0) & (gone <= offset), gone + 1, 0))
        out.append((data, head, max_ops))
    return out


def setup(config, mix, seed):
    from automerge_tpu.fleet import loader
    from automerge_tpu.fleet.backend import DocFleet
    # a configuration key of a mix parameter's name overrides it (the
    # tests' tiny sizes)
    mix = {key: config.get(key, value) for key, value in mix.items()}
    rng = np.random.default_rng(seed)
    n_docs = config['docs']
    t0 = time.perf_counter()
    trace = Trace(np.random.default_rng([seed, 1]), config['insert_share'],
                  config['typing_run_mean'], config['backspace_share'])
    lo, hi = config['start_offset_ops']
    offsets = rng.integers(lo, hi + 1, size=n_docs).tolist()
    trace.extend(max(offsets))
    actors = [rng.bytes(16).hex() for _ in range(n_docs)]
    char_rngs = [np.random.default_rng([seed, 2, d]) for d in range(n_docs)]
    chars = [ALPHABET[r.integers(0, len(ALPHABET), size=offset)]
             for r, offset in zip(char_rngs, offsets)]
    t1 = time.perf_counter()
    written = write_documents(trace, actors, offsets, chars,
                              config['prefix_change_ops'])
    t2 = time.perf_counter()
    from jax.profiler import TraceAnnotation
    fleet = DocFleet(doc_capacity=n_docs)
    with TraceAnnotation('load_docs'):
        handles = loader.load_docs([data for data, _h, _m in written], fleet)
    t3 = time.perf_counter()
    print(f'# text set-up: trace {t1 - t0:.2f} s, documents written '
          f'{t2 - t1:.2f} s ({sum(len(w[0]) for w in written)} bytes), '
          f'load_docs {t3 - t2:.2f} s', file=sys.stderr, flush=True)
    return {
        'config': config, 'mix': mix, 'rng': rng, 'n_docs': n_docs,
        'trace': trace, 'actors': actors, 'offsets': offsets,
        'char_rngs': char_rngs, 'fleet': fleet, 'handles': handles,
        # per document: the hash its next change follows, as bytes; the
        # characters typed after the offset; the changes encoded and not
        # yet applied; keystrokes applied or encoded so far
        'head': [bytes.fromhex(head) for _d, head, _m in written],
        'history_chars': chars,
        'history_max_ops': [m for _d, _h, m in written],
        'n_history_changes': [len(m) for _d, _h, m in written],
        'typed': [[] for _ in range(n_docs)],
        'queue': [[] for _ in range(n_docs)],
        'encoded': [0] * n_docs,
        'applied': [0] * n_docs,
        'last_head': [head for _d, head, _m in written],
        'plan': None, 'next_step': 0,
    }


def encode(state, counts):
    """Append to every document's queue its next `counts[d]` changes: one
    keystroke each, following the change before."""
    trace = state['trace']
    trace.extend(max(offset + done + int(n) for offset, done, n in
                     zip(state['offsets'], state['encoded'], counts)))
    is_insert, ref = trace.is_insert, trace.ref
    for d, n in enumerate(counts):
        n = int(n)
        if not n:
            continue
        first = state['offsets'][d] + state['encoded'][d] + 1
        actor = bytes.fromhex(state['actors'][d])
        chars = ALPHABET[state['char_rngs'][d].integers(
            0, len(ALPHABET), size=n)].tobytes()
        state['typed'][d].append(chars)
        head = state['head'][d]
        seq = state['n_history_changes'][d] + state['encoded'][d]
        queue = state['queue'][d]
        for i, t in enumerate(range(first, first + n)):
            seq += 1
            buf, head = wire_text.keystroke_change(
                actor, seq, t + 1, head, is_insert[t],
                ref[t] + 1 if ref[t] else 0, chars[i:i + 1])
            queue.append((buf, head))
        state['head'][d] = head
        state['encoded'][d] += n


def draw(state, steps):
    """k for every document of `steps` steps: geometric with the mix's
    mean, a draw over its cap drawn again."""
    mean, cap = state['mix']['changes_mean'], state['mix']['changes_cap']
    k = state['rng'].geometric(1.0 / mean, size=(steps, state['n_docs']))
    while (k > cap).any():
        again = k > cap
        k[again] = state['rng'].geometric(1.0 / mean, size=int(again.sum()))
    return k


def step(state, counts):
    """One apply_changes_docs over all documents, document d giving its
    next counts[d] changes, then the block. Returns the changes applied."""
    import jax
    from jax.profiler import TraceAnnotation
    from automerge_tpu.fleet import backend as fleet_backend
    per_doc = []
    for d, n in enumerate(counts):
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
    for d, n in enumerate(counts):
        state['applied'][d] += n
    return sum(counts)


def warmup(state):
    """One step at every bucketed width the draw can reach, `warmup_steps`
    steps as the window's, and then, from their measured time, the encoding
    of twice the changes a window can use."""
    mix = state['mix']
    cap = int(mix['changes_cap'])
    widths = [1 << b for b in range(cap.bit_length()) if 1 << b <= cap]
    plan = [[w] * state['n_docs'] for w in widths] + \
        draw(state, int(mix['warmup_steps'])).tolist()
    encode(state, np.sum(plan, axis=0))
    took = []
    for counts in plan:
        t0 = time.perf_counter()
        step(state, counts)
        took.append(time.perf_counter() - t0)
    # no step is taken to be shorter than the mix's floor: a program that
    # returns at once (the control's faults) would ask for changes without
    # end
    steady = max(min(took[len(widths):]), float(mix['step_floor_ms']) / 1e3)
    steps = int(2 * float(mix['encode_for_seconds']) / steady) + 2
    t0 = time.perf_counter()
    state['plan'] = draw(state, steps)
    encode(state, state['plan'].sum(axis=0))
    print(f'# text warm-up: steps {[round(t, 3) for t in took]} s; '
          f'{steps} steps ({int(state["plan"].sum())} changes) encoded in '
          f'{time.perf_counter() - t0:.2f} s', file=sys.stderr, flush=True)


def window(state, seconds, tracer):
    fleet = state['fleet']
    floor = float(state['mix']['step_floor_ms']) / 1e3
    before = fleet.metrics.snapshot()
    steps = attempted = failed = 0
    ends = []
    start_ns = time.perf_counter_ns()
    start = time.perf_counter()
    while True:
        tracer.poll()
        if state['next_step'] >= len(state['plan']):
            raise BenchError(
                f'the window used all {len(state["plan"])} steps that '
                'set-up encoded changes for')
        counts = state['plan'][state['next_step']].tolist()
        state['next_step'] += 1
        attempted += sum(counts)
        began = time.perf_counter()
        try:
            step(state, counts)
        except BenchError:
            raise
        except Exception as exc:   # the step's changes count as failed
            failed += sum(counts)
            print(f'# step {steps} raised {exc!r}', file=sys.stderr)
        steps += 1
        # a step shorter than the floor did not run the scan (the
        # control's faults): wait the floor out, so that such a program
        # cannot drain what set-up encoded; a sound step never waits
        time.sleep(max(0.0, began + floor - time.perf_counter()))
        now = time.perf_counter()
        ends.append(now)
        if now - start >= seconds:
            break
    elapsed = now - start
    took = [b - a for a, b in zip([start] + ends, ends)]
    print(f'# replay window: {steps} steps, median step '
          f'{statistics.median(took) * 1e3:.2f} ms, fastest '
          f'{min(took) * 1e3:.2f} ms, slowest {max(took) * 1e3:.2f} ms',
          file=sys.stderr, flush=True)
    counters = fleet.metrics.delta(before)
    return {
        'attempted': attempted, 'failed': failed,
        'metrics': {'ingest_changes_per_s': (attempted - failed) / elapsed},
        'facts': {'steps': steps, 'elapsed_s': elapsed,
                  'window_ns': (start_ns, time.perf_counter_ns()),
                  'fleet_counters': counters,
                  # gauges at the window's end (None from a program that
                  # does not keep them)
                  'seq_pool_bytes': getattr(fleet.metrics, 'seq_pool_bytes',
                                            None),
                  'seq_nodes': getattr(fleet.metrics, 'seq_nodes', None),
                  # nodes of a row by size class, for the roofline
                  'seq_nodes_by_cls': {
                      cls: st.elem_id.shape[1] for cls, st in
                      fleet.seq_pools.pools.items()}},
    }


def expected(state, d):
    """Document d by the reference: its Rga over the history and the
    applied changes."""
    trace = state['trace']
    offset, applied = state['offsets'][d], state['applied'][d]
    n = offset + applied
    # the character of every keystroke: the history's by the rank of the
    # insert (as its changes hold them), the window's by the keystroke
    char_of = np.zeros(n + 1, dtype=np.uint8)
    inserts = np.flatnonzero(trace.is_insert[:offset + 1])
    char_of[inserts] = state['history_chars'][d][:len(inserts)]
    char_of[offset + 1:] = np.frombuffer(
        b''.join(state['typed'][d]), dtype=np.uint8)[:applied]
    # one actor wrote the document: an id is its counter (keystroke t is
    # op t + 1)
    rga = reference_text.Rga()
    for op, (is_insert, ref, char) in enumerate(zip(
            trace.is_insert[1:n + 1], trace.ref[1:n + 1],
            char_of[1:].tobytes().decode()), 2):
        if is_insert:
            rga.insert(op, ref + 1 if ref else None, char)
        else:
            rga.delete(op, ref + 1)
    return rga


def audit(state):
    """Every document's text against the reference; ``save()`` of a seeded
    sample read back by the benchmark's own reader against the reference
    and the record of what was applied; the rows the device does not
    serve."""
    from automerge_tpu.fleet import backend as fleet_backend
    handles = state['handles']
    views = fleet_backend.materialize_docs(handles)
    sample = set(state['rng'].choice(
        state['n_docs'], size=min(int(state['mix']['audit_saves']),
                                  state['n_docs']), replace=False).tolist())
    text_mismatches = save_mismatches = 0
    for d in range(len(views)):
        rga = expected(state, d)
        if views[d].get(wire_text.TEXT_KEY) != rga.text():
            text_mismatches += 1
        if d in sample:
            why = saved_differs(state, d, rga,
                                bytes(fleet_backend.save(handles[d])))
            if why:
                save_mismatches += 1
                print(f'# save of document {d}: {why}', file=sys.stderr)
    inexact = sum(int(np.asarray(st.inexact).sum())
                  for st in state['fleet'].seq_pools.pools.values())
    return {
        'docs_missing': (state['n_docs'] - len(views), 0),
        'text_mismatches': (text_mismatches, 0),
        'save_mismatches': (save_mismatches, 0),
        'inexact_rows': (inexact, 0),
    }


def saved_differs(state, d, rga, data):
    """None where the saved document holds exactly the loaded history and
    the applied changes, else what differs: the one head, the changes (each
    one's actor, sequence number, greatest opId and dependency), and every
    op's id, referent, insert flag, character and successors in sequence
    order."""
    try:
        doc = wire_text.read_text_document(data)
    except (ValueError, IndexError, TypeError, KeyError) as exc:
        return f'does not read back: {exc}'
    actor, applied = state['actors'][d], state['applied'][d]
    head = state['queue'][d][applied - 1][1].hex() if applied \
        else state['last_head'][d]
    if doc['heads'] != [head]:
        return f"heads {doc['heads']}, recorded {[head]}"
    n_history = state['n_history_changes'][d]
    if len(doc['changes']) != n_history + applied:
        return (f"{len(doc['changes'])} changes, {n_history + applied} "
                'recorded')
    # the window's changes are one op each, after the history's
    max_ops = state['history_max_ops'][d] + list(range(
        state['offsets'][d] + 2, state['offsets'][d] + applied + 2))
    for i, (who, seq, max_op, deps) in enumerate(doc['changes']):
        if (who, seq, max_op) != (actor, i + 1, max_ops[i]) or \
                deps != ({(actor, i)} if i else set()):
            return f'change {i + 1} reads {(who, seq, max_op, deps)}'
    trace = state['trace']
    want = [(op, actor,
             (trace.ref[op - 1] + 1, actor) if trace.ref[op - 1] else None,
             char, [(gone, actor) for gone in deleted])
            for op, char, deleted in rga.elements()]
    if doc['elements'] != want:
        both = sum(1 for a, b in zip(doc['elements'], want) if a == b)
        return (f"{len(doc['elements'])} elements, {len(want)} by the "
                f'reference, {both} alike in place')
    return None
