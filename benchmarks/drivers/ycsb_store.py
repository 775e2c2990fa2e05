"""Driver ``ycsb_store``: a document store under YCSB core workload A
("update heavy"), one record a map document of `fields` string fields.

Set-up makes every record's load (ONE change by one loader actor that sets
every field to a seeded `field_bytes`-byte string, written by the benchmark's
own writer, wire_ycsb.py), calls ``init_docs`` once and applies the loads
with ``apply_changes_docs(mirror=False)``, `load_batch` records a call. A
step draws `ops_per_step` operations independently: a read with probability
`read_proportion`, else an update, each of a record drawn by YCSB's scrambled
zipfian. The step is ONE ``materialize_docs`` over the step's reads (a
record drawn twice is asked twice), then ONE
``apply_changes_docs(mirror=False)`` over the step's updates, then a block
on the grid. An update is a one-op change by its client thread's actor
(thread = the op's index in the step mod `client_actors`): it sets one field,
drawn uniformly, to a fresh seeded string, names the field's last op as its
predecessor and follows the record's head, so that a record's updates in a
step are one causal chain in draw order. Steps run back to back, one caller,
on the RESIDENT fleet; the window closes at the first step boundary at or
after ``--seconds``. ``ingest_changes_per_s`` counts the updates.

Before the load, a read of one record of a two-record fleet must move one
row (the fleet's `read_rows`), or the run ends there: the store serves a
read from the asked records' rows. The first step is a probe: `fallbacks` or
`exact_calls` moving ends the run. The warm-up runs `warmup_steps` steps
more, encodes from the fastest of them twice the steps a window of
`encode_for_seconds` can use, `max_plan_steps` at most (``traffic_s``, no
part of ``setup_s``: the clients' writes, their amount set by the program's
speed). Before it encodes them it runs, for every grid shape among the
window's draws that no step has run yet, one step of the same draws with
fresh values, and after it reads once at every size class of read the plan
makes: nothing compiles in the window. No step
is waited out: a program that returns sooner has more steps encoded for
it, as many as its own warm-up steps ask. A window that uses every encoded
step (a program more than twice as fast as its own warm-up, or past the
cap, as the control's `state_unchanged` fault is) closes there and says
so; its rate is over the seconds it ran.
"""

import gc
import statistics
import sys
import time

import numpy as np

import wire_ycsb
from harness import BenchError
from reference_ycsb import Reference, saved_record_differs

# YCSB's RandomByteIterator writes printable characters; 64 of them here
ALPHABET = np.frombuffer(
    b'ABCDEFGHIJKLMNOPQRSTUVWXYZabcdefghijklmnopqrstuvwxyz0123456789+/',
    dtype=np.uint8)
FNV_OFFSET_BASIS_64 = 0xCBF29CE484222325
FNV_PRIME_64 = 1099511628211


def fnvhash64(values):
    """YCSB's Utils.fnvhash64 of each int64 in `values` (FNV-1 over the
    eight low-first octets, then Math.abs of the signed result)."""
    rest = np.asarray(values, dtype=np.int64).astype(np.uint64)
    out = np.full(rest.shape, FNV_OFFSET_BASIS_64, dtype=np.uint64)
    for _ in range(8):
        out ^= rest & np.uint64(0xff)
        out *= np.uint64(FNV_PRIME_64)
        rest >>= np.uint64(8)
    return np.abs(out.view(np.int64))


class KeyChooser:
    """YCSB's ScrambledZipfianGenerator as CoreWorkload builds it for
    `requestdistribution=zipfian`: a ZipfianGenerator over
    `zipfian_items` + 1 items with the precomputed `zetan` (the constant
    0.99 case), each draw FNV-hashed onto the key space CoreWorkload gives
    it, the records plus the inserts it expects (none under workload A)
    plus one; a key not yet inserted is drawn again."""

    def __init__(self, config, rng):
        theta = float(config['zipfian_constant'])
        self.items = int(config['zipfian_items']) + 1
        self.zetan = float(config['zetan'])
        self.records = int(config['records'])
        self.second = 1.0 + 0.5 ** theta            # zeta(2, theta)
        self.alpha = 1.0 / (1.0 - theta)
        self.eta = (1.0 - (2.0 / self.items) ** (1.0 - theta)) / \
            (1.0 - self.second / self.zetan)
        self.rng = rng

    def draw(self, n):
        out = np.empty(n, dtype=np.int64)
        todo = np.arange(n)
        while len(todo):
            u = self.rng.random(len(todo))
            item = (self.items * (self.eta * u - self.eta + 1.0) **
                    self.alpha).astype(np.int64)
            item[u * self.zetan < self.second] = 1
            item[u * self.zetan < 1.0] = 0
            key = fnvhash64(item) % (self.records + 1)
            kept = key < self.records
            out[todo[kept]] = key[kept]
            todo = todo[~kept]
        return out

    def hottest(self):
        """The record item 0 lands on: the one a zipfian draw gives most
        often (1 / zetan of the draws)."""
        return int(fnvhash64([0])[0] % (self.records + 1))


class Step:
    """One step's operations, encoded: `reads` (records, in draw order),
    `docs` (the records updated, in the order of their first update) with
    their change chains `buffers[bounds[i]:bounds[i + 1]]`, and the updates
    for the reference: `records`, `fields`, `values` in draw order."""

    __slots__ = ('reads', 'docs', 'buffers', 'bounds', 'records', 'fields',
                 'values', 'cells')

    def per_doc(self):
        buffers, bounds = self.buffers, self.bounds
        return [buffers[a:b] for a, b in zip(bounds, bounds[1:])]


class Store:
    """The clients' side: the records' loads and every update, each encoded
    by the benchmark's own writer, and what each record's next change
    follows (its head, its greatest op, each field's last op, each
    client's sequence number in it). The records in `sampled` keep their
    whole history, for the audit's saves."""

    def __init__(self, config, mix, seed):
        self.records = int(config['records'])
        self.fields = int(config['fields'])
        self.field_bytes = int(config['field_bytes'])
        self.read_proportion = float(config['read_proportion'])
        self.ops_per_step = int(mix['ops_per_step'])
        ids = np.random.default_rng([seed, 0])
        self.loader = ids.bytes(16)
        self.clients = [ids.bytes(16) for _ in range(
            int(config['client_actors']))]
        self.actors = [self.loader] + self.clients    # by field_actor
        self.chooser = KeyChooser(config, np.random.default_rng([seed, 1]))
        self.ops_rng = np.random.default_rng([seed, 2])
        self.extra_rng = np.random.default_rng([seed, 5])
        self.load_values = ALPHABET[np.random.default_rng([seed, 3]).integers(
            0, len(ALPHABET), size=(self.records,
                                    self.fields * self.field_bytes),
            dtype=np.uint8)]
        self.writer = wire_ycsb.UpdateWriter(self.fields, self.field_bytes,
                                             self.actors)
        # flat lists, a record's fields side by side: a few objects the
        # collector walks, not one a record
        self.head = [None] * self.records
        self.max_op = [self.fields] * self.records
        self.field_ctr = list(range(1, self.fields + 1)) * self.records
        self.field_actor = [0] * (self.records * self.fields)
        self.seq = {}                  # record * clients + thread -> seq
        self.history = {}              # sampled record -> its changes, ops
        self.steps = 0

    def load_changes(self):
        """Every record's load change, in record order."""
        load = wire_ycsb.LoadWriter(self.loader, self.fields,
                                    self.field_bytes)
        blob = self.load_values.tobytes()
        size = self.fields * self.field_bytes
        out = []
        for r in range(self.records):
            buf, self.head[r] = load.change(blob[r * size:(r + 1) * size])
            out.append(buf)
        return out

    def sample(self, records):
        """Keep the whole history of `records` from here on (before any
        update), for the audit's saves."""
        loader = self.loader.hex()
        size = self.field_bytes
        for r in records:
            row = self.load_values[r].tobytes().decode()
            self.history[r] = {
                'changes': [(loader, 1, self.fields, set(), -1)],
                'ops': [(f'field{f}', f + 1, loader,
                         row[f * size:(f + 1) * size], -1)
                        for f in range(self.fields)],
                'heads': [(self.head[r].hex(), -1)]}

    def draw(self):
        """The next step's draws: (is_read, records, fields, values)."""
        n = self.ops_per_step
        is_read = self.ops_rng.random(n) < self.read_proportion
        records = self.chooser.draw(n)
        fields = self.ops_rng.integers(0, self.fields, size=n)
        n_updates = int((~is_read).sum())
        return is_read, records, fields, self._values(self.ops_rng, n_updates)

    def _values(self, rng, n):
        return ALPHABET[rng.integers(0, len(ALPHABET),
                                     size=n * self.field_bytes,
                                     dtype=np.uint8)].tobytes()

    def redraw_values(self, draw):
        """`draw`'s operations with fresh values (a step of the same
        shape, outside the draws of the plan)."""
        is_read, records, fields, _ = draw
        return is_read, records, fields, self._values(
            self.extra_rng, int((~is_read).sum()))

    def encode(self, draw=None):
        """The next step, encoded (Step): of `draw`, or of the next draw."""
        is_read, records, fields, blob = draw or self.draw()
        step = Step()
        step.reads = records[is_read].tolist()
        update_at = np.flatnonzero(~is_read)
        upd_records = records[update_at]
        upd_fields = fields[update_at]
        size = self.field_bytes
        values = [blob[i * size:(i + 1) * size]
                  for i in range(len(update_at))]
        # a record's updates, grouped in the order of its first one and in
        # draw order within it: one chain a record
        docs, first = np.unique(upd_records, return_index=True)
        rank = np.empty(len(docs), dtype=np.int64)
        rank[np.argsort(first, kind='stable')] = np.arange(len(docs))
        doc_of = rank[np.searchsorted(docs, upd_records)]
        order = np.argsort(doc_of, kind='stable')
        counts = np.bincount(doc_of, minlength=len(docs))
        step.docs = docs[np.argsort(first, kind='stable')].tolist()
        step.bounds = np.r_[0, np.cumsum(counts)].tolist()
        n_clients, n_fields = len(self.clients), self.fields
        records_of, fields_of = upd_records.tolist(), upd_fields.tolist()
        threads = (update_at % n_clients).tolist()
        head, max_op, seq = self.head, self.max_op, self.seq
        field_ctr, field_actor = self.field_ctr, self.field_actor
        change, history = self.writer.change, self.history
        buffers = []
        for j in order.tolist():
            r, f, t = records_of[j], fields_of[j], threads[j]
            key = r * n_clients + t
            n = seq.get(key, 0) + 1
            seq[key] = n
            start = max_op[r] + 1
            max_op[r] = start
            cell = r * n_fields + f
            buf, digest = change(t + 1, n, start, head[r], f, values[j],
                                 field_ctr[cell], field_actor[cell])
            buffers.append(buf)
            if r in history:
                self._record(r, t, n, start, f, values[j], digest)
            head[r] = digest
            field_ctr[cell] = start
            field_actor[cell] = t + 1
        step.buffers = buffers
        step.records, step.fields = records_of, fields_of
        step.values = values
        step.cells = len(np.unique(upd_records * self.fields + upd_fields))
        self.steps += 1
        return step

    def _record(self, r, thread, seq, start, field, value, digest):
        history = self.history[r]
        actor = self.clients[thread].hex()
        at = self.steps
        last = history['changes'][-1]
        history['changes'].append((actor, seq, start, {last[:2]}, at))
        history['ops'].append((f'field{field}', start, actor,
                               value.decode(), at))
        history['heads'].append((digest.hex(), at))

    def applied_history(self, r, steps):
        """Record r's history after its load and the first `steps` steps:
        what `saved_record_differs` holds a save to."""
        history = self.history[r]
        return {'changes': [c[:4] for c in history['changes']
                            if c[4] < steps],
                'ops': [o[:4] for o in history['ops'] if o[4] < steps],
                'heads': [[h for h, at in history['heads'] if at < steps][-1]]}


def point_read_check(store):
    """A store serves a read from the rows of the records it is asked for:
    two records loaded into a fleet of their own, one of them read, and
    the fleet's `read_rows` must have moved by one. A program that reads a
    whole fleet to answer for a few records (seconds a step at this size)
    ends the run here, before the load, with a BenchError."""
    from automerge_tpu.fleet import backend as fleet_backend
    fleet = fleet_backend.DocFleet(doc_capacity=2,
                                   key_capacity=store.fields + 1)
    load = wire_ycsb.LoadWriter(store.loader, store.fields,
                                store.field_bytes)
    handles, _ = fleet_backend.apply_changes_docs(
        fleet_backend.init_docs(2, fleet),
        [[load.change(store.load_values[r % store.records].tobytes())[0]]
         for r in range(2)], mirror=False)
    before = fleet.metrics.snapshot()
    fleet_backend.materialize_docs(handles[:1])
    moved = fleet.metrics.delta(before).get('read_rows')
    if moved != 1:
        raise BenchError(
            'a read of one record of two moved '
            f'{"no counted" if moved is None else moved} rows (the fleet\'s '
            'read_rows); this configuration needs a store that reads the '
            'asked records\' rows alone')


def setup(config, mix, seed):
    from jax.profiler import TraceAnnotation
    from automerge_tpu.fleet import backend as fleet_backend
    from automerge_tpu.fleet.backend import DocFleet, init_docs
    # a configuration key of a mix parameter's name overrides it (the
    # tests' tiny sizes)
    mix = {key: config.get(key, value) for key, value in mix.items()}
    t0 = time.perf_counter()
    store = Store(config, mix, seed)
    point_read_check(store)
    loads = store.load_changes()
    # the audit saves the hottest record, records of the first draws and
    # one drawn uniformly
    picks = np.random.default_rng([seed, 4])
    sampled = {store.chooser.hottest(), int(picks.integers(store.records))}
    sampled.update(int(r) for r in KeyChooser(
        config, picks).draw(int(mix['audit_saves'])))
    store.sample(sorted(r for r in sampled if r < store.records))
    t1 = time.perf_counter()
    fleet = DocFleet(doc_capacity=store.records,
                     key_capacity=store.fields + 1)
    with TraceAnnotation('init_docs'):
        handles = init_docs(store.records, fleet)
    t2 = time.perf_counter()
    batch = int(mix['load_batch'])
    with TraceAnnotation('load'):
        for lo in range(0, store.records, batch):
            out, _ = fleet_backend.apply_changes_docs(
                handles[lo:lo + batch],
                [[buf] for buf in loads[lo:lo + batch]], mirror=False)
            handles[lo:lo + batch] = out
    del loads
    t3 = time.perf_counter()
    print(f'# store set-up: {store.records} records written in '
          f'{t1 - t0:.2f} s, init_docs {t2 - t1:.2f} s, loaded in '
          f'{t3 - t2:.2f} s ({len(fleet.value_table)} values)',
          file=sys.stderr, flush=True)
    return {
        'config': config, 'mix': mix, 'store': store, 'fleet': fleet,
        'handles': handles, 'plan': [], 'next_step': 0, 'views': [],
        'window_counters': None,
    }


def step(state, plan):
    """ONE materialize_docs over the step's reads, ONE apply_changes_docs
    over its updates, a block. Returns what the reads answered."""
    import jax
    from jax.profiler import TraceAnnotation
    from automerge_tpu.fleet import backend as fleet_backend
    handles = state['handles']
    with TraceAnnotation('materialize_docs'):
        views = fleet_backend.materialize_docs([handles[r]
                                                for r in plan.reads])
    with TraceAnnotation('apply_changes_docs'):
        out, _ = fleet_backend.apply_changes_docs(
            [handles[r] for r in plan.docs], plan.per_doc(), mirror=False)
    for r, handle in zip(plan.docs, out):
        handles[r] = handle
    with TraceAnnotation('block'):
        jax.block_until_ready(state['fleet'].state)
    return views


def run_planned(state):
    """The plan's next step; what its reads answered is kept for the
    audit (a dict of strings is no object the collector tracks)."""
    plan = state['plan'][state['next_step']]
    state['next_step'] += 1
    state['views'].append(step(state, plan))
    return plan


def probe(state):
    """The first step, the store's first call: a program that takes it off
    the device path (`fallbacks` or `exact_calls` move) does not give this
    configuration's guarantee, and the run ends here."""
    fleet = state['fleet']
    before = fleet.metrics.snapshot()
    t0 = time.perf_counter()
    plan = run_planned(state)
    moved = fleet.metrics.delta(before)
    print(f'# probe: {len(plan.reads)} reads, {len(plan.buffers)} updates '
          f'over {len(plan.docs)} records, {time.perf_counter() - t0:.3f} s: '
          f'turbo_calls {moved["turbo_calls"]}, fallbacks '
          f'{moved["fallbacks"]}, exact_calls {moved["exact_calls"]}',
          file=sys.stderr, flush=True)
    if moved['fallbacks'] or moved['exact_calls']:
        raise BenchError(
            'the probe (one step of reads and updates) left the device '
            f'path: fallbacks {moved["fallbacks"]}, exact_calls '
            f'{moved["exact_calls"]}; this configuration guarantees that '
            'every update is applied on the device')


def read_classes(plan):
    """The power-of-two size classes of the gathers a plan's reads make."""
    return {max(64, 1 << (len(set(p.reads)) - 1).bit_length())
            for p in plan if p.reads}


def grid_shape(records):
    """The shape the grid path lays out a step's updates to `records` in:
    rows the power of two that holds the records, width the power of two
    that holds the longest chain (that length itself where every chain is
    as long). A shape no step has run compiles."""
    _, chains = np.unique(records, return_counts=True)
    if not len(chains):
        return None
    longest = int(chains.max())
    width = longest if chains.min() == longest else \
        1 << (longest - 1).bit_length()
    return 1 << (len(chains) - 1).bit_length(), width


def warmup(state):
    """The probe, `warmup_steps` steps, then the draws, from their
    measured time, of twice the steps a window can use, a step run for
    each grid shape among them not yet run, their encoding (the draws and
    the encoding timed as ``state['traffic_s']`` with the collection of
    its garbage), and one read at every size class the plan's reads
    make."""
    from automerge_tpu.fleet import backend as fleet_backend
    mix, store = state['mix'], state['store']
    n_warm = int(mix['warmup_steps'])
    state['plan'] = [store.encode() for _ in range(n_warm + 1)]
    probe(state)
    took = []
    for _ in range(n_warm):
        t0 = time.perf_counter()
        run_planned(state)
        took.append(time.perf_counter() - t0)
    # the fastest: a warm-up step may hold the fold of the load's log
    # segments and the collection after it (seconds), which no window step
    # repeats
    steady = min(took)
    t0 = time.perf_counter()
    steps = min(int(2 * float(mix['encode_for_seconds']) / steady) + 2,
                int(mix['max_plan_steps']))
    draws = [store.draw() for _ in range(steps)]
    drawn_s = time.perf_counter() - t0
    # a window step of a grid shape the warm-up has not run would compile
    # in the window (under the skew, about one step in a hundred has its
    # longest chain a class shorter): a step of the same draws, with fresh
    # values, runs it first
    shapes = {grid_shape(p.records) for p in state['plan']}
    extra = []
    for draw in draws:
        shape = grid_shape(draw[1][~draw[0]])
        if shape not in shapes:
            shapes.add(shape)
            extra.append(shape)
            state['plan'].append(store.encode(store.redraw_values(draw)))
            run_planned(state)
    n_run = len(state['plan'])
    t0 = time.perf_counter()
    state['plan'] += [store.encode(draw) for draw in draws]
    del draws
    gc.collect()
    state['traffic_s'] = drawn_s + time.perf_counter() - t0
    warmed = read_classes(state['plan'][:n_run])
    for size in sorted(read_classes(state['plan']) - warmed):
        fleet_backend.materialize_docs(state['handles'][:size])
    print(f'# store warm-up: steps {[round(t, 4) for t in took]} s; '
          f'{steps} steps ({sum(len(p.buffers) for p in state["plan"])} '
          f'updates) encoded in {state["traffic_s"]:.2f} s (traffic_s); '
          f'grid shapes {sorted(shapes)}, run first {extra}; '
          f'read classes {sorted(read_classes(state["plan"]))}',
          file=sys.stderr, flush=True)


def window(state, seconds, tracer):
    fleet = state['fleet']
    before = fleet.metrics.snapshot()
    steps = attempted = failed = cells = 0
    ends = []
    start_ns = time.perf_counter_ns()
    start = time.perf_counter()
    now = start
    while True:
        tracer.poll()
        if state['next_step'] >= len(state['plan']):
            print(f'# store window: closed early, at {now - start:.3f} s, '
                  f'having used all {len(state["plan"])} steps that set-up '
                  'encoded', file=sys.stderr, flush=True)
            break
        plan = state['plan'][state['next_step']]
        attempted += len(plan.buffers)
        try:
            run_planned(state)
        except BenchError:
            raise
        except Exception as exc:   # the step's updates count as failed
            failed += len(plan.buffers)
            state['views'].append(None)
            print(f'# step {steps} raised {exc!r}', file=sys.stderr)
        cells += plan.cells
        steps += 1
        now = time.perf_counter()
        ends.append(now)
        if now - start >= seconds:
            break
    elapsed = now - start
    took = [b - a for a, b in zip([start] + ends, ends)]
    counters = fleet.metrics.delta(before)
    state['window_counters'] = counters
    print(f'# store window: {steps} steps, median step '
          f'{statistics.median(took) * 1e3:.2f} ms, fastest '
          f'{min(took) * 1e3:.2f} ms, slowest {max(took) * 1e3:.2f} ms; '
          f'reads {counters.get("read_docs")}, rows gathered '
          f'{counters.get("read_rows")}, host reads '
          f'{counters.get("read_host_docs")}; fallbacks '
          f'{counters["fallbacks"]}, exact_calls {counters["exact_calls"]}',
          file=sys.stderr, flush=True)
    return {
        'attempted': attempted, 'failed': failed,
        'metrics': {'ingest_changes_per_s': (attempted - failed) / elapsed},
        'facts': {'steps': steps, 'elapsed_s': elapsed,
                  'window_ns': (start_ns, time.perf_counter_ns()),
                  'fleet_counters': counters,
                  'ops_per_step': (attempted - failed) / steps,
                  'cells_per_step': cells / steps,
                  # one row of the three int32 grids, for the read
                  # roofline
                  'grid_row_bytes': None if fleet.state is None else
                  3 * 4 * fleet.state.winners.shape[1]},
    }


def audit(state):
    """Every read the store answered, against the reference's record as
    of its step's start; every record's view at the end; ``save()`` of the
    sampled records read back by the benchmark's own reader against the
    history recorded; the calls of the window that left the device path."""
    from automerge_tpu.fleet import backend as fleet_backend
    store = state['store']
    reference = Reference(store.load_values, store.fields, store.field_bytes)
    read_mismatches = 0
    for plan, views in zip(state['plan'], state['views']):
        if views is None:
            read_mismatches += len(plan.reads)
        else:
            read_mismatches += sum(
                view != reference.record(r)
                for r, view in zip(plan.reads, views))
        reference.update(plan.records, plan.fields, plan.values)
    applied = len(state['views'])
    views = fleet_backend.materialize_docs(state['handles'])
    view_mismatches = sum(view != reference.record(r)
                          for r, view in enumerate(views))
    save_mismatches = 0
    for r in store.history:
        why = saved_record_differs(
            bytes(fleet_backend.save(state['handles'][r])),
            store.applied_history(r, applied))
        if why:
            save_mismatches += 1
            print(f'# save of record {r}: {why}', file=sys.stderr)
    counters = state['window_counters'] or {}
    return {
        'read_mismatches': (read_mismatches, 0),
        'docs_missing': (store.records - len(views), 0),
        'view_mismatches': (view_mismatches, 0),
        'save_mismatches': (save_mismatches, 0),
        'offpath_calls': (counters.get('fallbacks', 0) +
                          counters.get('exact_calls', 0), 0),
    }
