"""Driver ``text_rounds``: a collaborative-text server whose rooms have two
people typing at once, merging each sync round of both into the resident
document. Set-up WRITES every document's saved container (two actors, two
heads; wire_text_multi.py) and calls ``load_docs`` once; nothing is
replayed. A step is ONE ``apply_changes_docs(mirror=False)`` over all
documents on the RESIDENT fleet, document d giving ONE round: each of its
two writers its next k one-op changes, one writer's chain after the
other's in the buffer (which first is drawn a round), then a block on every
sequence pool's arrays. Steps run back to back, one caller; the window
closes at the first step boundary at or after ``--seconds``.

A round: both writers edit concurrently from the state the last round
left. A writer's first change of the round depends on BOTH heads of the
last round, each further one on its own predecessor; both chains start at
the same op counter; after the round the document has two heads.

The rounds are generated, not read (the configuration's ``assumed``). The
documents' loaded histories are prefixes of one seeded sequence of rounds
and differ in actor ids (so in which writer's id is the greater), in the
round they start after and in characters; from there every document goes on
with rounds of its own draws. The FIRST call of all is a probe: one
document, one round; if the program took it off the device path the driver
ends there (the configuration's guarantee, before 127 more documents can
take the same way).
"""

import copy
import gc
import statistics
import sys
import time

import numpy as np

import reference_text
import wire_text_multi as wire
from harness import BenchError

ALPHABET = np.frombuffer(b'abcdefghijklmnopqrstuvwxyz ', dtype=np.uint8)
HEAD = 0          # an element is the code of its insert op; no op has 0


def code(ctr, writer):
    """An op (or the element it inserts) as one int: counter, writer."""
    return ctr * 2 + writer


class Rounds:
    """The shared structure of two writers' rounds, grown on demand. Round
    r (from 1) holds, for writer w, ``k[r][w]`` keystrokes ``ops[r][w] =
    (is_insert, ref)``: an insert goes after element ``ref`` (HEAD: the
    head), a delete removes element ``ref``. Writer w's keystroke j of
    round r is op ``code(base[r] + 1 + j, w)``: both chains start at one
    counter, and the next round starts past the longer of them.

    Each writer types at a cursor of its own (text_replay's keystroke
    model), seeing what the last round left and its own ops of this round;
    in ``same_spot_share`` of the rounds both start at ONE drawn element.
    ``order(flip)`` is every element in sequence order, for the writer
    whose id is the greater: writer 1 (flip 0) or writer 0 (flip 1).

    The documents share the rounds of their history; after the round a
    document starts at, it goes on with a ``fork`` of its own: the writers'
    state as that round left it, and draws of its own from there on (its
    own k, spots and keystrokes). A fork keeps no order and no record of
    referents and deleters: its rounds are applied, not written."""

    def __init__(self, rng, config, mix):
        self.rng = rng
        self.insert_share = config['insert_share']
        self.run_mean = config['typing_run_mean']
        self.backspace_share = config['backspace_share']
        self.same_spot_share = config['same_spot_share']
        self.mean, self.cap = mix['changes_mean'], mix['changes_cap']
        self.k = [None]
        self.base = [None, 1]          # op 1 is the makeText
        self.ops = [None]
        self.same_spot = [False]
        self.ops_before = [0, 0]       # keystrokes in the rounds before r
        self.live, self.at = [], {}    # live elements, and where in `live`
        # a writer's cursor: the element it follows, the elements typed
        # since it last jumped, and how much of its typing run is left
        self.cursor = [HEAD, HEAD]
        self.typed = [[], []]
        self.run_left = [0, 0]
        # every element, for the documents and the audit: the element it
        # was inserted after, the ops that deleted it, and the list itself
        # for either order of the two ids
        self.ref_of = {}
        self.deleted_by = {}
        self.after = [{HEAD: HEAD}, {HEAD: HEAD}]

    def fork(self, rng):
        """The rounds so far, to be gone on with under `rng`."""
        other = copy.copy(self)
        other.rng = rng
        for name in ('k', 'base', 'ops', 'same_spot', 'ops_before', 'live',
                     'cursor', 'run_left'):
            setattr(other, name, list(getattr(self, name)))
        other.at = dict(self.at)
        other.typed = [list(typed) for typed in self.typed]
        other.ref_of = other.deleted_by = other.after = None
        return other

    def __len__(self):
        return len(self.k) - 1

    def _draw_k(self):
        while True:
            k = int(self.rng.geometric(1.0 / self.mean))
            if k <= self.cap:
                return k

    def _splice(self, elem, ref):
        """RGA: after `ref`, past elements with a greater id."""
        for flip, after in enumerate(self.after):
            mine = elem ^ flip
            at, nxt = ref, after[ref]
            while nxt and (nxt ^ flip) > mine:
                at, nxt = nxt, after[nxt]
            after[elem] = nxt
            after[at] = elem

    def extend(self, n_rounds):
        rng = self.rng
        live, at = self.live, self.at
        while len(self) < n_rounds:
            r = len(self.k)
            base = self.base[r]
            k = (self._draw_k(), self._draw_k())
            same = bool(live) and rng.random() < self.same_spot_share
            if same:
                spot = live[int(rng.random() * len(live))]
                for w in range(2):
                    self.cursor[w], self.typed[w] = spot, []
                    self.run_left[w] = int(rng.geometric(1.0 / self.run_mean))
            made = []
            for w in range(2):
                made.append(self._chain(w, base, k[w]))
            for _ops, new, gone in made:
                for elem in new:
                    if elem not in gone:
                        at[elem] = len(live)
                        live.append(elem)
            for _ops, _new, gone in made:
                for elem in gone:
                    where = at.pop(elem, None)
                    if where is not None:
                        last = live.pop()
                        if last != elem:
                            live[where] = last
                            at[last] = where
            self.k.append(k)
            self.ops.append((made[0][0], made[1][0]))
            self.same_spot.append(same)
            self.base.append(base + max(k))
            self.ops_before.append(self.ops_before[r] + k[0] + k[1])

    def _chain(self, w, base, k):
        """Writer w's k keystrokes of a round: ([(is_insert, ref)], the
        elements it inserted, the set it deleted)."""
        rng = self.rng
        live, at = self.live, self.at
        new, new_set, gone = [], set(), set()
        kinds = (rng.random(k) < self.insert_share).tolist()
        backspace = (rng.random(k) < self.backspace_share).tolist()
        runs = rng.geometric(1.0 / self.run_mean, size=k).tolist()
        cursor, typed = self.cursor[w], self.typed[w]
        ops = []

        def drawn():
            # a live element of this writer's view: what the last round
            # left and what it inserted since, less what it deleted
            if len(live) + len(new) <= len(gone):
                return HEAD
            while True:
                i = int(rng.random() * (len(live) + len(new)))
                elem = live[i] if i < len(live) else new[i - len(live)]
                if elem not in gone:
                    return elem

        for j in range(k):
            op = code(base + 1 + j, w)
            seen = cursor == HEAD or (
                (cursor in at or cursor in new_set) and cursor not in gone)
            if self.run_left[w] <= 0 or not seen:
                cursor, typed = drawn(), []
                self.run_left[w] = runs[j]
            self.run_left[w] -= 1
            n_live = len(live) + len(new) - len(gone)
            if kinds[j] or not n_live:
                ops.append((True, cursor))
                if self.after is not None:
                    self.ref_of[op] = cursor
                    self.deleted_by[op] = []
                    self._splice(op, cursor)
                new.append(op)
                new_set.add(op)
                typed.append(cursor)
                cursor = op
            else:
                if backspace[j] and cursor != HEAD:
                    target = cursor
                    if typed:
                        cursor = typed.pop()
                    else:
                        # the character before it is not known without
                        # the order: the cursor jumps at the next keystroke
                        cursor, self.run_left[w] = HEAD, 0
                else:
                    target = drawn()
                    self.run_left[w] = 0
                ops.append((False, target))
                if self.after is not None:
                    self.deleted_by[target].append(op)
                gone.add(target)
        self.cursor[w], self.typed[w] = cursor, typed
        return ops, new, gone

    def order(self, flip):
        out, at = [], self.after[flip][HEAD]
        while at:
            out.append(at)
            at = self.after[flip][at]
        return np.array(out, dtype=np.int64)


def write_documents(rounds, actors, starts, chars):
    """Every document's saved container after its first `starts[d]` rounds,
    each writer's round ONE change (the configuration's
    ``history_change_ops``): [(bytes, [head of writer 0's chain, of writer
    1's] as bytes)]. A change's columns are the same in every document but
    for the characters, the table of changes is a prefix of one table, and
    an element's place, referent and deleters are the same in every
    document whose writers' ids compare alike: all worked out once."""
    last = max(starts)
    columns = [None]
    made_in = {}                 # element or delete op -> its round
    insert_at = []               # the keystrokes that insert, as they come
    inserts_before = [0, 0]      # ... and how many before round r's chains
    table = history_table(rounds, last)
    for r in range(1, last + 1):
        columns.append([wire.round_columns(rounds.ops[r][w], w)
                        for w in range(2)])
        at = rounds.ops_before[r]
        for w in range(2):
            for j, (is_insert, _ref) in enumerate(rounds.ops[r][w]):
                made_in[code(rounds.base[r] + 1 + j, w)] = r
                if is_insert:
                    insert_at.append(at + j)
            at += rounds.k[r][w]
            inserts_before.append(len(insert_at))
    insert_at = np.array(insert_at, dtype=np.int64)
    # every element of those rounds in sequence order, for either order of
    # the two ids: the round that made it, its referent, where its
    # character is among a document's, and the (at most two) ops that
    # deleted it, in the order of their ids, with their rounds
    shared = []
    for flip in range(2):
        elems = [int(e) for e in rounds.order(flip)
                 if made_in.get(int(e), last + 1) <= last]
        rounds_of = np.array([made_in[e] for e in elems], dtype=np.int64)
        ref = np.array([rounds.ref_of[e] for e in elems], dtype=np.int64)
        char_at = np.array(
            [rounds.ops_before[made_in[e]] +
             (rounds.k[made_in[e]][0] if e & 1 else 0) +
             (e >> 1) - rounds.base[made_in[e]] - 1 for e in elems],
            dtype=np.int64)
        gone = np.zeros((len(elems), 2), dtype=np.int64)
        gone_in = np.full((len(elems), 2), last + 1, dtype=np.int64)
        for i, e in enumerate(elems):
            ops = sorted((g for g in rounds.deleted_by[e]
                          if made_in.get(g, last + 1) <= last),
                         key=lambda g: g ^ flip)
            for c, g in enumerate(ops):
                gone[i, c], gone_in[i, c] = g, made_in[g]
        shared.append((np.array(elems, dtype=np.int64), rounds_of, ref,
                       char_at, gone, gone_in))
    out = []
    for pair, start, doc_chars in zip(actors, starts, chars):
        ids = [bytes.fromhex(actor) for actor in pair]
        typed = doc_chars[insert_at[:inserts_before[2 * start + 1]]].tobytes()
        first = bytes.fromhex(wire.make_text_change(pair[0])[1])
        heads = [first, first]
        for r in range(1, start + 1):
            deps = sorted(set(heads))
            base = rounds.base[r] + 1
            heads = [wire.round_change(
                ids[w], ids[1 - w], r + 1 - w, base, deps, columns[r][w],
                typed[inserts_before[2 * r - 1 + w]:
                      inserts_before[2 * r + w]])[1] for w in range(2)]
        elems, rounds_of, ref, char_at, gone, gone_in = \
            shared[int(pair[0] > pair[1])]
        keep = rounds_of <= start
        gone_then = gone_in[keep] <= start
        succ = gone[keep][gone_then]
        # the heads in the order of their hashes, and their changes: the
        # last two of the table (one, the makeText, before any round)
        ends = [(heads[w].hex(), 2 * start - 1 + w) for w in range(2)] \
            if start else [(first.hex(), 0)]
        data = wire.text_document(
            pair, [h for h, _i in sorted(ends)],
            {name: column[:2 * start + 1] for name, column in table.items()},
            [i for _h, i in sorted(ends)], elems[keep] >> 1, elems[keep] & 1,
            ref[keep] >> 1, ref[keep] & 1,
            doc_chars[char_at[keep]].tobytes(), gone_then.sum(axis=1),
            succ >> 1, succ & 1)
        out.append((data, heads))
    return out


def history_table(rounds, n_rounds):
    """The changes of a history of `n_rounds` rounds, as columns: the
    makeText (change 0, by writer 0), then writer 0's and writer 1's change
    of every round; each one's writer, sequence number, greatest op and
    the indexes of the changes it follows (both of the round before)."""
    writer, seq, max_op, deps = [0], [1], [1], [[]]
    for r in range(1, n_rounds + 1):
        follows = [2 * r - 3, 2 * r - 2] if r > 1 else [0]
        for w in range(2):
            writer.append(w)
            seq.append(r + 1 - w)
            max_op.append(rounds.base[r] + rounds.k[r][w])
            deps.append(follows)
    return {'writer': np.array(writer), 'seq': np.array(seq),
            'max_op': np.array(max_op), 'deps': deps}


def history_record(rounds, pair, start):
    """{(actor, seq): (greatest op, {(actor, seq) of each dependency})} of
    a document's loaded history, for the audit of a save."""
    table = history_table(rounds, start)
    names = [(pair[w], int(n)) for w, n in zip(table['writer'],
                                               table['seq'])]
    return {name: (int(max_op), {names[i] for i in follows})
            for name, max_op, follows in zip(names, table['max_op'],
                                             table['deps'])}


def setup(config, mix, seed):
    from automerge_tpu.fleet import loader
    from automerge_tpu.fleet.backend import DocFleet
    # a configuration key of a mix parameter's name overrides it (the
    # tests' tiny sizes)
    mix = {key: config.get(key, value) for key, value in mix.items()}
    rng = np.random.default_rng(seed)
    n_docs = config['docs']
    t0 = time.perf_counter()
    # a document starts after a round at whose end each writer has made
    # history_ops_per_writer changes, give or take history_spread_ops; no
    # two documents start after the same round where there are enough.
    # The shared rounds are generated twice from one seed: once to see
    # where such rounds lie, once more forking every document's own rounds
    # off at the round it starts after
    want, spread = (config['history_ops_per_writer'],
                    config['history_spread_ops'])
    rounds = Rounds(np.random.default_rng([seed, 1]), config, mix)
    while rounds.ops_before[-1] < 2 * (want + spread):
        rounds.extend(len(rounds) + 64)
    per_writer = np.array(rounds.ops_before[1:]) / 2
    fit = np.flatnonzero((per_writer >= want - spread) &
                         (per_writer <= want + spread))
    if not len(fit):
        fit = np.array([int(np.abs(per_writer - want).argmin())])
    starts = rng.choice(fit, size=n_docs,
                        replace=len(fit) < n_docs).tolist()
    rounds = Rounds(np.random.default_rng([seed, 1]), config, mix)
    forks = [None] * n_docs
    for d in sorted(range(n_docs), key=starts.__getitem__):
        rounds.extend(starts[d])
        forks[d] = rounds.fork(np.random.default_rng([seed, 3, d]))
    actors = [(rng.bytes(16).hex(), rng.bytes(16).hex())
              for _ in range(n_docs)]
    char_rngs = [np.random.default_rng([seed, 2, d]) for d in range(n_docs)]
    chars = [ALPHABET[r.integers(0, len(ALPHABET),
                                 size=rounds.ops_before[start + 1])]
             for r, start in zip(char_rngs, starts)]
    t1 = time.perf_counter()
    written = write_documents(rounds, actors, starts, chars)
    t2 = time.perf_counter()
    from jax.profiler import TraceAnnotation
    fleet = DocFleet(doc_capacity=n_docs)
    with TraceAnnotation('load_docs'):
        handles = loader.load_docs([data for data, _h in written], fleet)
    t3 = time.perf_counter()
    print(f'# rounds set-up: {len(rounds)} rounds {t1 - t0:.2f} s, '
          f'documents written {t2 - t1:.2f} s '
          f'({sum(len(w[0]) for w in written)} bytes, '
          f'{min(rounds.ops_before[s + 1] for s in starts)}-'
          f'{max(rounds.ops_before[s + 1] for s in starts)} ops), '
          f'load_docs {t3 - t2:.2f} s', file=sys.stderr, flush=True)
    return {
        'config': config, 'mix': mix, 'rng': rng, 'n_docs': n_docs,
        'rounds': rounds, 'forks': forks, 'actors': actors,
        'starts': starts,
        'char_rngs': char_rngs, 'fleet': fleet, 'handles': handles,
        'history_chars': chars,
        # per document: its own rounds (`forks`, which go on from the
        # shared history); the two hashes its next round follows (writer 0's
        # chain's end, writer 1's), as bytes; each writer's next sequence
        # number; the rounds encoded and not yet applied, each (buffers,
        # heads after it, the writer whose chain came first, characters);
        # rounds applied
        'heads': [heads for _d, heads in written],
        'first_heads': [sorted({h.hex() for h in heads})
                        for _d, heads in written],
        'seq': [[start + 2, start + 1] for start in starts],
        'queue': [[] for _ in range(n_docs)],
        'applied': [0] * n_docs,
        'plan': None, 'next_step': 0, 'window_counters': None,
    }


def encode(state, counts):
    """Append to every document's queue its next `counts[d]` rounds: each
    writer's keystrokes one change each, the first following both heads of
    the round before, each further one the change before it."""
    for d, n in enumerate(counts):
        rounds = state['forks'][d]
        rounds.extend(state['starts'][d] + len(state['queue'][d]) + int(n))
        pair = [bytes.fromhex(a) for a in state['actors'][d]]
        heads, seq, queue = state['heads'][d], state['seq'][d], \
            state['queue'][d]
        for _ in range(int(n)):
            r = state['starts'][d] + len(queue) + 1
            k, base = rounds.k[r], rounds.base[r]
            chars = ALPHABET[state['char_rngs'][d].integers(
                0, len(ALPHABET), size=k[0] + k[1])].tobytes()
            both = heads if heads[0] != heads[1] else heads[:1]
            chains, ends = [], []
            for w in range(2):
                deps, bufs = both, []
                for j, (is_insert, ref) in enumerate(rounds.ops[r][w]):
                    at = j + w * k[0]
                    buf, digest = wire.keystroke_change(
                        pair[w], pair[1 - w], w == 0, seq[w], base + 1 + j,
                        deps, is_insert, ref >> 1, (ref & 1) != w,
                        chars[at:at + 1])
                    seq[w] += 1
                    deps = (digest,)
                    bufs.append(buf)
                chains.append(bufs)
                ends.append(digest)
            first = int(state['rng'].integers(0, 2))
            heads = ends
            queue.append((chains[first] + chains[1 - first], ends, first,
                          chars))
        state['heads'][d] = heads


def step(state, docs=None):
    """One apply_changes_docs over all documents, each of `docs` (all of
    them where None) giving its next round, then the block. Returns the
    changes applied."""
    import jax
    from jax.profiler import TraceAnnotation
    from automerge_tpu.fleet import backend as fleet_backend
    per_doc = [()] * state['n_docs']
    for d in range(state['n_docs']) if docs is None else docs:
        queue, at = state['queue'][d], state['applied'][d]
        if at >= len(queue):
            raise BenchError(
                f'document {d} has no encoded round left: the window '
                'outran what set-up encoded')
        per_doc[d] = queue[at][0]
    with TraceAnnotation('apply_changes_docs'):
        state['handles'], _ = fleet_backend.apply_changes_docs(
            state['handles'], per_doc, mirror=False)
    with TraceAnnotation('block'):
        jax.block_until_ready(
            [st.tree_flatten()[0]
             for st in state['fleet'].seq_pools.pools.values()])
    for d in range(state['n_docs']) if docs is None else docs:
        state['applied'][d] += 1
    return sum(len(changes) for changes in per_doc)


def probe(state):
    """The first call of all: document 0, one round, alone. A program that
    takes it off the device path (`fallbacks` or `exact_calls` move) does
    not give this configuration's guarantee: the run ends here, before the
    other documents' host mirrors can be rebuilt too."""
    from automerge_tpu.fleet import backend as fleet_backend
    fleet = state['fleet']
    before = fleet.metrics.snapshot()
    t0 = time.perf_counter()
    out, _ = fleet_backend.apply_changes_docs(
        state['handles'][:1], [state['queue'][0][0][0]], mirror=False)
    state['handles'][:1] = out
    state['applied'][0] += 1
    moved = fleet.metrics.delta(before)
    print(f'# probe: one document, one round of '
          f'{len(state["queue"][0][0][0])} changes, '
          f'{time.perf_counter() - t0:.3f} s: turbo_calls '
          f'{moved["turbo_calls"]}, fallbacks {moved["fallbacks"]}, '
          f'exact_calls {moved["exact_calls"]}', file=sys.stderr, flush=True)
    if moved['fallbacks'] or moved['exact_calls']:
        raise BenchError(
            'the probe (one document, one round of two concurrent writers) '
            f'left the device path: fallbacks {moved["fallbacks"]}, '
            f'exact_calls {moved["exact_calls"]}; this configuration '
            'guarantees that every round is applied on the device')


def warmup(state):
    """The probe; then a step at every power-of-two list width the
    documents' next rounds can make (a step of the documents whose next
    round is that long, the others sitting it out), `warmup_steps` steps
    as the window's, and then, from their measured time, the encoding of
    twice the rounds a window can use."""
    mix = state['mix']
    n_docs = state['n_docs']
    forks = state['forks']
    cap = 2 * int(mix['changes_cap'])
    widths = [1 << b for b in range(1, cap.bit_length()) if 1 << b <= cap]
    encode(state, [len(widths) + int(mix['warmup_steps']) + 1] * n_docs)
    probe(state)
    took, made = [], []
    for width in widths[:-1]:
        docs = [d for d in range(n_docs)
                if width // 2 < sum(forks[d].k[
                    state['starts'][d] + state['applied'][d] + 1]) <= width]
        if docs:
            step(state, docs)
            made.append(width)
    for _ in range(int(mix['warmup_steps'])):
        t0 = time.perf_counter()
        step(state)
        took.append(time.perf_counter() - t0)
    # no step is taken to be shorter than the mix's floor: a program that
    # returns at once (the control's faults) would ask for rounds without
    # end
    steady = max(min(took), float(mix['step_floor_ms']) / 1e3)
    steps = int(2 * float(mix['encode_for_seconds']) / steady) + 2
    t0 = time.perf_counter()
    left = [len(queue) - at for queue, at in
            zip(state['queue'], state['applied'])]
    encode(state, [max(steps - n, 0) for n in left])
    state['plan'] = steps
    # Set-up's own garbage is collected in set-up. Encoding leaves some ten
    # million references behind (128 generators' live elements, a million
    # buffers), and the full collection CPython owes for them would
    # otherwise fall somewhere in the window: 0.7-1.1 s of a 20 s window in
    # two runs of four on the chip. The generators' state goes first (no
    # round is drawn after this; the audit reads k, base and ops); the
    # collector's thresholds stay as the program leaves them
    for fork in forks:
        fork.live = fork.at = fork.cursor = fork.typed = None
    gc.collect()
    print(f'# rounds warm-up: widths {made} and {widths[-1]}, steps '
          f'{[round(t, 3) for t in took]} s; {steps} steps encoded in '
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
        if state['next_step'] >= state['plan']:
            raise BenchError(
                f'the window used all {state["plan"]} steps that set-up '
                'encoded rounds for')
        state['next_step'] += 1
        began = time.perf_counter()
        asked = sum(len(queue[at][0]) for queue, at in
                    zip(state['queue'], state['applied']))
        attempted += asked
        try:
            step(state)
        except BenchError:
            raise
        except Exception as exc:   # the step's changes count as failed
            failed += asked
            print(f'# step {steps} raised {exc!r}', file=sys.stderr)
        steps += 1
        # a step shorter than the floor did not run the scan (the
        # control's faults): wait the floor out, so that such a program
        # cannot drain what set-up encoded; a sound step never waits
        short = began + floor - time.perf_counter()
        if short > 0:
            time.sleep(short)
        now = time.perf_counter()
        ends.append(now)
        if now - start >= seconds:
            break
    elapsed = now - start
    took = [b - a for a, b in zip([start] + ends, ends)]
    counters = fleet.metrics.delta(before)
    state['window_counters'] = counters
    print(f'# rounds window: {steps} steps, median step '
          f'{statistics.median(took) * 1e3:.2f} ms, fastest '
          f'{min(took) * 1e3:.2f} ms, slowest {max(took) * 1e3:.2f} ms; '
          f'seq_migrations {counters.get("seq_migrations")}, dag_seq_docs '
          f'{counters.get("dag_seq_docs")}, seq_multiwriter_rows '
          f'{counters.get("seq_multiwriter_rows")}, fallbacks '
          f'{counters["fallbacks"]}, exact_calls {counters["exact_calls"]}',
          file=sys.stderr, flush=True)
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


class NoSkipRga(reference_text.Rga):
    """The reference with the concurrent-insert rule OFF: an insert lands
    right after its referent, whatever follows. Where its text differs
    from the program's, two writers met and the skip walk had work."""

    def insert(self, op_id, referent, char):
        if referent not in self._next:
            raise KeyError(f'insert {op_id} after {referent}, which is not '
                           'in the list')
        self._next[op_id] = self._next[referent]
        self._next[referent] = op_id
        self._char[op_id] = char
        self._deleted_by[op_id] = []


def copy_into(rga, other):
    other._next = dict(rga._next)
    other._char = dict(rga._char)
    other._deleted_by = {e: list(ops) for e, ops in rga._deleted_by.items()}
    return other


def expected(state, d):
    """Document d by the reference: (its Rga over the history and the
    applied rounds, the same with the applied rounds' inserts not walking,
    the op or element that each of the reference's ids names). A round's
    two chains are applied in the order the buffer did NOT use; the
    history's, writer 1's first. An id is one int, `counter * 2 + the rank
    of the actor's id among the two`: ints in the order of (counter,
    actor), which is all the reference asks of an id."""
    rounds, pair = state['forks'][d], state['actors'][d]
    start, applied = state['starts'][d], state['applied'][d]
    flip = int(pair[0] > pair[1])       # writer w's id ranks w ^ flip

    def play(rga, r, order, chars):
        k, base = rounds.k[r], rounds.base[r]
        for w in order:
            at = w * k[0]
            op = code(base + 1, w) ^ flip
            for j, (is_insert, ref) in enumerate(rounds.ops[r][w]):
                if is_insert:
                    rga.insert(op, ref ^ flip if ref else None,
                               chars[at + j])
                else:
                    rga.delete(op, ref ^ flip)
                op += 2

    rga = reference_text.Rga()
    history = state['history_chars'][d].tobytes().decode()
    for r in range(1, start + 1):
        at = rounds.ops_before[r]
        play(rga, r, (1, 0), history[at:at + sum(rounds.k[r])])
    plain = copy_into(rga, NoSkipRga())
    for i in range(applied):
        _bufs, _ends, first, chars = state['queue'][d][i]
        for each in (rga, plain):
            play(each, start + 1 + i, (1 - first, first), chars.decode())
    return rga, plain


def audit(state):
    """Every document's text and heads against the reference and the
    record of what was applied; ``save()`` of a seeded sample read back by
    the benchmark's own reader; the rows the device does not serve; the
    calls of the window that left the device path. Beside the limits, as a
    fact: `conflict_docs`, the documents whose text differs from the
    program's once the applied rounds' inserts do not walk."""
    from automerge_tpu.fleet import backend as fleet_backend
    handles = state['handles']
    views = fleet_backend.materialize_docs(handles)
    sample = set(state['rng'].choice(
        state['n_docs'], size=min(int(state['mix']['audit_saves']),
                                  state['n_docs']), replace=False).tolist())
    text_mismatches = heads_mismatches = save_mismatches = conflicts = 0
    for d in range(len(views)):
        rga, plain = expected(state, d)
        text = views[d].get(wire.TEXT_KEY)
        if text != rga.text():
            text_mismatches += 1
        if text != plain.text():
            conflicts += 1
        if sorted(fleet_backend.get_heads(handles[d])) != heads_of(state, d):
            heads_mismatches += 1
        if d in sample:
            why = saved_differs(state, d, rga,
                                bytes(fleet_backend.save(handles[d])))
            if why:
                save_mismatches += 1
                print(f'# save of document {d}: {why}', file=sys.stderr)
    inexact = sum(int(np.asarray(st.inexact).sum())
                  for st in state['fleet'].seq_pools.pools.values())
    counters = state['window_counters'] or {}
    state['conflict_docs'] = conflicts
    print(f'fact conflict_docs: {conflicts} of {len(views)} documents read '
          'another text with the skip rule off', file=sys.stderr, flush=True)
    return {
        'docs_missing': (state['n_docs'] - len(views), 0),
        'text_mismatches': (text_mismatches, 0),
        'heads_mismatches': (heads_mismatches, 0),
        'save_mismatches': (save_mismatches, 0),
        'inexact_rows': (inexact, 0),
        'offpath_calls': (counters.get('fallbacks', 0) +
                          counters.get('exact_calls', 0), 0),
    }


def heads_of(state, d):
    """The recorded heads of document d, sorted: the last change of each
    writer's chain in the last round applied (or loaded)."""
    applied = state['applied'][d]
    if not applied:
        return state['first_heads'][d]
    return sorted(h.hex() for h in state['queue'][d][applied - 1][1])


def saved_differs(state, d, rga, data):
    """None where the saved document holds exactly the loaded history and
    the applied rounds, else what differs: the heads, the changes (each
    one's actor, sequence number, greatest opId and dependencies), and
    every element's id, referent, character and successors, both actors'
    ids among them, in sequence order."""
    try:
        doc = wire.read_text_document(data)
    except (ValueError, IndexError, TypeError, KeyError) as exc:
        return f'does not read back: {exc}'
    rounds, pair = state['forks'][d], state['actors'][d]
    start, applied = state['starts'][d], state['applied'][d]
    if doc['actors'] != sorted(pair):
        return f"actors {doc['actors']}"
    if doc['heads'] != heads_of(state, d):
        return f"heads {doc['heads']}, recorded {heads_of(state, d)}"
    want = history_record(rounds, pair, start)
    last = {(pair[0], start + 1), (pair[1], start)} if start \
        else {(pair[0], 1)}
    seq = [start + 2, start + 1]
    for i in range(applied):
        r = start + 1 + i
        ends = set()
        for w in range(2):
            deps = last
            for j in range(rounds.k[r][w]):
                want[(pair[w], seq[w])] = (rounds.base[r] + 1 + j, deps)
                deps = {(pair[w], seq[w])}
                seq[w] += 1
            ends |= deps
        last = ends
    got = {(who, n): (max_op, deps)
           for who, n, max_op, deps in doc['changes']}
    if len(doc['changes']) != len(got) or got != want:
        wrong = [key for key in want if got.get(key) != want[key]]
        return (f"{len(doc['changes'])} changes, {len(want)} recorded, "
                f'{len(wrong)} of them read otherwise, the first '
                f'{wrong[:1]} as {got.get(wrong[0]) if wrong else None}')

    flip = int(pair[0] > pair[1])
    # every element's referent: the history's as the shared rounds
    # recorded them, the applied rounds' from their keystrokes
    ref_of = state['rounds'].ref_of
    later = {code(rounds.base[r] + 1 + j, w): ref
             for r in range(start + 1, start + 1 + applied)
             for w in range(2)
             for j, (is_insert, ref) in enumerate(rounds.ops[r][w])
             if is_insert}

    def name(ranked):
        # a reference id back to (counter, actor)
        return (ranked >> 1, pair[(ranked & 1) ^ flip])

    def referent(ranked):
        elem = ranked ^ flip
        ref = later[elem] if elem in later else ref_of[elem]
        return name(ref ^ flip) if ref else None

    want = [(*name(op), referent(op), char,
             [name(g) for g in sorted(deleted)])
            for op, char, deleted in rga.elements()]
    if doc['elements'] != want:
        both = sum(1 for a, b in zip(doc['elements'], want) if a == b)
        return (f"{len(doc['elements'])} elements, {len(want)} by the "
                f'reference, {both} alike in place')
    return None
