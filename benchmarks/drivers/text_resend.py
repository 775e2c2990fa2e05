"""Driver ``text_resend``: a collaborative-text server whose clients speak
Automerge's sync protocol, two people typing in each room. The protocol
withholds a change now and then (a false positive of the peer's Bloom
filter: ``withheld_share`` of the configuration): its dependents arrive
first and wait in the document's queue, the receiver asks for the missing
hash again, and the change arrives one message round later.

The fleet, the rounds and their encoding are ``text_rounds``' (loaded here
by path; ``wire_text_multi.py`` through it): only the DELIVERY differs. A
step is ONE ``apply_changes_docs(mirror=False)`` over all documents on the
RESIDENT fleet, document d giving: the changes withheld from it in the last
step, then ONE round LESS the changes withheld in this step; then a block
on every sequence pool's arrays. A change behind a withheld one in its
chain is delivered and queued; the step after, the withheld change comes
ahead of the next round, and the queue drains in that call. The window's
loop, its step and the final audit are ``text_rounds``': this driver turns
every encoded round of a document's queue into what is SENT for it
(`plan`), keeps the books of what each step applies, and audits the
held-back state against ``reference_causal.Causal``.

The FIRST call of all is a probe: document 0 alone, a round with one
change withheld from the middle of a chain, then a second call that
delivers it. If the program took either off the device path the driver
ends there (the configuration's guarantee).
"""

import gc
import hashlib
import os
import sys
import time

import numpy as np

import harness
import reference_causal
from harness import BenchError

rounds = harness.load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 'text_rounds.py'), 'driver text_rounds')
wire = rounds.wire              # wire_text_multi.py, as text_rounds has it


def links(buf):
    """(hash, [the hashes it follows]) of one change as it was delivered,
    from its bytes: the hash is SHA-256 of the chunk past the 8-byte
    header; the chunk is its type, its length, then the body, which starts
    with the number of dependencies and their 32-byte hashes."""
    at = 9
    while buf[at] & 0x80:
        at += 1
    at += 1
    return hashlib.sha256(buf[8:]).digest(), \
        [buf[at + 1 + 32 * i:at + 33 + 32 * i] for i in range(buf[at])]


def setup(config, mix, seed):
    if int(config['resend_after_steps']) != 1:
        raise BenchError('this driver resends a withheld change one step '
                         'later (resend_after_steps 1) and no other way')
    state = rounds.setup(config, mix, seed)
    n_docs = state['n_docs']
    state.update({
        'share': float(config['withheld_share']),
        'draws': [np.random.default_rng([seed, 4, d])
                  for d in range(n_docs)],
        # per document: the rounds of its queue turned into sends; the
        # changes its last planned round withheld (they open the next
        # send); round -> [(place in the round's buffer, the change)] of
        # what a round withheld; how many changes each planned round's
        # step applies, and how many of the last planned round wait for
        # the step after (withheld, or queued behind a withheld one)
        'planned': [0] * n_docs,
        'late': [[] for _ in range(n_docs)],
        'withheld': [{} for _ in range(n_docs)],
        'applies': [[] for _ in range(n_docs)],
        'carry': [0] * n_docs,
        'has_held': [False] * n_docs,
        # (the round of document 0 that the probe sent with a change
        # withheld, [what it sent for it, call by call])
        'probe_sends': (None, None),
    })
    return state


def chains_of(state, d, i):
    """The two chains' bounds in the buffer of document d's round i:
    ((from, to) of the chain that comes first, of the other)."""
    _bufs, _ends, first, _chars = state['queue'][d][i]
    k = state['forks'][d].k[state['starts'][d] + i + 1]
    return (0, k[first]), (k[first], k[0] + k[1])


def plan(state, d, upto, share=None, force=False):
    """Turn document d's encoded rounds, from the first not yet planned to
    round `upto` (not included), into what is sent for them: what the
    round before withheld, then the round less what it withholds itself
    (each change with probability `share`; with `force`, one that another
    change of its chain follows where the draw withheld none). The queue's
    entry keeps its place and form, so text_rounds' step sends it."""
    share = state['share'] if share is None else share
    queue, rng = state['queue'][d], state['draws'][d]
    for i in range(state['planned'][d], upto):
        bufs, ends, first, chars = queue[i]
        n = len(bufs)
        gone = np.flatnonzero(rng.random(n) < share) if share else ()
        spans = chains_of(state, d, i)
        if force and not len(gone):
            lo, hi = max(spans, key=lambda span: span[1] - span[0])
            if hi - lo > 1:
                gone = np.array([int(rng.integers(lo, hi - 1))])
        # a chain is applied up to its first withheld change; what stands
        # behind that waits for the step after, withheld or queued
        applied = n
        if len(gone):
            applied = sum(
                int(gone[(gone >= lo) & (gone < hi)].min(initial=hi)) - lo
                for lo, hi in spans)
            held = [(j, bufs[j]) for j in gone.tolist()]
            state['withheld'][d][i] = held
            if n - applied > len(held):
                state['has_held'][d] = True
            drop = set(gone.tolist())
            send = state['late'][d] + [buf for j, buf in enumerate(bufs)
                                       if j not in drop]
            state['late'][d] = [buf for _j, buf in held]
        else:
            send = state['late'][d] + bufs if state['late'][d] else bufs
            state['late'][d] = []
        state['applies'][d].append(state['carry'][d] + applied)
        state['carry'][d] = n - applied
        queue[i] = (send, ends, first, chars)
    state['planned'][d] = max(state['planned'][d], upto)


def call(state, per_doc):
    """One apply_changes_docs and the block, as text_rounds' step makes
    them; returns what the fleet's counters moved by."""
    import jax
    from jax.profiler import TraceAnnotation
    from automerge_tpu.fleet import backend as fleet_backend
    before = state['fleet'].metrics.snapshot()
    with TraceAnnotation('apply_changes_docs'):
        state['handles'], _ = fleet_backend.apply_changes_docs(
            state['handles'], per_doc, mirror=False)
    with TraceAnnotation('block'):
        jax.block_until_ready(
            [st.tree_flatten()[0]
             for st in state['fleet'].seq_pools.pools.values()])
    return state['fleet'].metrics.delta(before)


def probe(state):
    """The first calls of all: document 0 alone, its first round with one
    change withheld from the middle of the longer chain, then that change.
    A program that takes either off the device path (`fallbacks` or
    `exact_calls` move) does not give this configuration's guarantee: the
    run ends here, before the other documents' host mirrors can be rebuilt
    too."""
    others = [()] * (state['n_docs'] - 1)
    # a first round too short to have a middle is sent whole, the next
    # one asked (one round in seventy has no chain of three changes)
    for i in range(len(state['queue'][0])):
        bufs = state['queue'][0][i][0]
        lo, hi = max(chains_of(state, 0, i),
                     key=lambda span: span[1] - span[0])
        if hi - lo >= 3:
            break
        call(state, [bufs] + others)
        state['applies'][0].append(len(bufs))
    at = (lo + hi) // 2
    sends = [bufs[:at] + bufs[at + 1:], [bufs[at]]]
    for what, send in zip(('a round less one change of a chain\'s middle',
                           'the withheld change'), sends):
        t0 = time.perf_counter()
        moved = call(state, [send] + others)
        print(f'# probe: one document, {what} ({len(send)} changes), '
              f'{time.perf_counter() - t0:.3f} s: turbo_calls '
              f'{moved["turbo_calls"]}, fallbacks {moved["fallbacks"]}, '
              f'exact_calls {moved["exact_calls"]}, heldback_changes '
              f'{moved.get("heldback_changes")}, drained_changes '
              f'{moved.get("drained_changes")}', file=sys.stderr, flush=True)
        if moved['fallbacks'] or moved['exact_calls']:
            raise BenchError(
                f'the probe (one document: {what}) left the device path: '
                f'fallbacks {moved["fallbacks"]}, exact_calls '
                f'{moved["exact_calls"]}; this configuration guarantees '
                'that every call is applied on the device')
    state['probe_sends'] = (i, sends)
    state['planned'][0] = state['applied'][0] = i + 1
    state['applies'][0].append(len(bufs))


def warmup(state):
    """The probe; a step at every power-of-two list width a window can
    make (2 to 128: the documents whose next round is that long, sent
    whole, the others sitting out; 256: one document that gives rounds at
    once until they are longer than a round can be, as a round and the
    tail that drains with it are); a step in which every
    document withholds a change, so that
    each has held back and drained before the window; `warmup_steps` steps
    as the window's; then, from their measured time, the encoding and the
    delivery plan of twice the rounds a window can use, timed as
    ``state['traffic_s']``; then steps as the window's for
    `settle_seconds`, so that the window opens at the pace the steps settle
    at after the encoding."""
    mix = state['mix']
    n_docs, forks = state['n_docs'], state['forks']
    applied, starts = state['applied'], state['starts']
    cap = 2 * int(mix['changes_cap'])
    widths = [1 << b for b in range(1, cap.bit_length()) if 1 << b <= cap]
    tries, wide = 4, 16
    rounds.encode(state, [len(widths) + 1 + wide + tries +
                          int(mix['warmup_steps'])] * n_docs)
    probe(state)

    def next_total(d, ahead=0):
        return sum(forks[d].k[starts[d] + applied[d] + 1 + ahead])

    made = []
    for width in widths:
        docs = [d for d in range(n_docs)
                if width // 2 < next_total(d) <= width]
        if docs:
            for d in docs:
                plan(state, d, applied[d] + 1, share=0.0)
            rounds.step(state, docs)
            made.append(width)
    # the widest: a document gives rounds at once until they are longer
    # than one round can be (no longer than two can: a round and a tail).
    # The document that needs the fewest rounds for it
    def rounds_past_cap(d):
        total = n = 0
        while total <= cap:
            total += next_total(d, n)
            n += 1
        return n
    n, d = min((rounds_past_cap(d), d) for d in range(n_docs))
    plan(state, d, applied[d] + n, share=0.0)
    per_doc = [()] * n_docs
    per_doc[d] = [buf for entry in state['queue'][d][applied[d]:applied[d] + n]
                  for buf in entry[0]]
    applied[d] += n
    call(state, per_doc)
    made.append(2 * cap)
    # every document holds a change back and drains a step later, once at
    # least: the general gate's first visit reads a document's whole
    # history, and that is start-up, not traffic
    forced = 0
    for _ in range(tries):
        if all(state['has_held']):
            break
        for d in range(n_docs):
            plan(state, d, applied[d] + 1, force=not state['has_held'][d])
        rounds.step(state)
        forced += 1
    took = []
    for _ in range(int(mix['warmup_steps'])):
        for d in range(n_docs):
            plan(state, d, applied[d] + 1)
        t0 = time.perf_counter()
        rounds.step(state)
        took.append(time.perf_counter() - t0)
    steady = max(min(took), float(mix['step_floor_ms']) / 1e3)
    # from here on: the window's traffic, as text_rounds makes and times
    # it, and ahead of it what the settling steps below use
    t0 = time.perf_counter()
    settle_s = float(mix['settle_seconds'])
    spare = int(settle_s / steady) + 1
    steps = int(2 * float(mix['encode_for_seconds']) / steady) + 2
    left = [len(queue) - at for queue, at in zip(state['queue'], applied)]
    rounds.encode(state, [max(steps + spare - n, 0) for n in left])
    for d in range(n_docs):
        plan(state, d, len(state['queue'][d]))
    for fork in forks:
        fork.live = fork.at = fork.cursor = fork.typed = None
    gc.collect()
    state['traffic_s'] = time.perf_counter() - t0
    # The encoding is a long stretch of work on the host alone. The steps
    # right after it run faster than the pace they settle at a few seconds
    # later, when the caller again waits on the device in every step, and
    # how long that lasts differs from run to run: the window opens at the
    # settled pace. Steps as the window's, counted as set-up
    t0 = time.perf_counter()
    settled = []
    while len(settled) < spare and time.perf_counter() - t0 < settle_s:
        t1 = time.perf_counter()
        rounds.step(state)
        settled.append(time.perf_counter() - t1)
    state['plan'] = steps
    print(f'# resend warm-up: widths {made}; {forced} steps with a change '
          f'withheld in every document that had held none back '
          f'({sum(state["has_held"])} of {n_docs} have), steps '
          f'{[round(t, 3) for t in took]} s; {steps + spare} steps encoded, '
          f'planned and their garbage collected in '
          f'{state["traffic_s"]:.2f} s (traffic_s); {len(settled)} steps '
          f'to settle, the first ten a median of '
          f'{sorted(settled[:10])[len(settled[:10]) // 2] * 1e3:.1f} ms, '
          f'the last ten of '
          f'{sorted(settled[-10:])[len(settled[-10:]) // 2] * 1e3:.1f} ms',
          file=sys.stderr, flush=True)


def window(state, seconds, tracer):
    """text_rounds' window over the planned sends; a change counts in the
    step that APPLIES it, by the plan's books."""
    first = list(state['applied'])
    out = rounds.window(state, seconds, tracer)
    applied = sum(sum(state['applies'][d][first[d]:state['applied'][d]])
                  for d in range(state['n_docs']))
    sent = out['attempted'] - out['failed']
    out['attempted'] = applied + out['failed']
    out['metrics'] = {'ingest_changes_per_s':
                      applied / out['facts']['elapsed_s']}
    state['window_applied'] = applied
    counters = out['facts']['fleet_counters']
    print(f'# resend window: {sent} changes sent, {applied} applied by the '
          f'plan, changes_ingested {counters["changes_ingested"]}; '
          f'heldback_changes {counters.get("heldback_changes")}, '
          f'drained_changes {counters.get("drained_changes")}, '
          f'heldback_docs {counters.get("heldback_docs")}, '
          f'turbo_commit_fallback_docs '
          f'{counters["turbo_commit_fallback_docs"]}, mirror_rebuilds '
          f'{counters["mirror_rebuilds"]}, promotions '
          f'{counters["promotions"]}, seq_inexact_reads '
          f'{counters["seq_inexact_reads"]} over '
          f'{out["facts"]["steps"]} steps', file=sys.stderr, flush=True)
    return out


def replayed(state, d):
    """Document d by the reference of causal delivery: everything sent for
    it so far, call by call, read from the bytes."""
    loaded = [bytes.fromhex(h) for h in state['first_heads'][d]]
    ref = reference_causal.Causal(applied=loaded, heads=loaded)
    for i in range(state['applied'][d]):
        sends = state['probe_sends'][1] if (d, i) == (
            0, state['probe_sends'][0]) else [state['queue'][d][i][0]]
        for send in sends:
            ref.deliver([links(buf) for buf in send])
    return ref


def held_text(state, d, ref):
    """The text of document d by the reference while changes are held back:
    every round before the last one sent, whole (text_rounds' `expected`),
    then of the last one each chain as far as the reference applied it."""
    sent = state['applied'][d]
    if not sent:
        return rounds.expected(state, d)[0].text()
    i = sent - 1
    held = dict(state['withheld'][d].get(i, ()))
    bufs = list(state['queue'][d][i][0][
        len(state['withheld'][d].get(i - 1, ())):])
    for j in sorted(held):
        bufs.insert(j, held[j])
    spans = chains_of(state, d, i)
    took = []
    for lo, hi in spans:
        there = [links(buf)[0] in ref.applied for buf in bufs[lo:hi]]
        took.append(sum(there))
        if there != sorted(there, reverse=True):
            raise BenchError(f'document {d}: the reference applied a '
                             'change behind one it did not, in one chain')
    state['applied'][d] = i
    try:
        rga, _plain = rounds.expected(state, d)
    finally:
        state['applied'][d] = sent
    forks, pair = state['forks'][d], state['actors'][d]
    _bufs, _ends, first, chars = state['queue'][d][i]
    r = state['starts'][d] + i + 1
    flip = int(pair[0] > pair[1])
    chars = chars.decode()
    # the chain the buffer has second goes first, as text_rounds' audit
    for w, n in ((1 - first, took[1]), (first, took[0])):
        at = w * forks.k[r][0]
        op = rounds.code(forks.base[r] + 1, w) ^ flip
        for j, (is_insert, ref_elem) in enumerate(forks.ops[r][w][:n]):
            if is_insert:
                rga.insert(op, ref_elem ^ flip if ref_elem else None,
                           chars[at + j])
            else:
                rga.delete(op, ref_elem ^ flip)
            op += 2
    return rga.text()


def audit(state):
    """Twice. The state as the window left it, changes still withheld or
    queued: every document's text against the reference's text of what the
    reference of causal delivery applied, its heads, the length of its
    queue and `get_missing_deps()` against that reference's. Then ONE more
    call delivers what is withheld, and text_rounds' audit holds the
    drained fleet (texts, heads, saves, inexact rows, calls off the device
    path, floor waits); `undrained_docs` counts the documents that then
    still queue a change or miss one, and `applied_mismatch` is the
    distance between the changes the plan says the window applied and the
    program's own `changes_ingested`."""
    from automerge_tpu.fleet import backend as fleet_backend
    handles, n_docs = state['handles'], state['n_docs']
    views = fleet_backend.materialize_docs(handles)
    held = {'held_text_mismatches': 0, 'held_heads_mismatches': 0,
            'pending_mismatches': 0, 'missing_mismatches': 0}
    waiting = 0
    for d in range(min(n_docs, len(views))):
        ref = replayed(state, d)
        waiting += len(ref.queue)
        if views[d].get(wire.TEXT_KEY) != held_text(state, d, ref):
            held['held_text_mismatches'] += 1
        if sorted(fleet_backend.get_heads(handles[d])) != \
                sorted(h.hex() for h in ref.heads):
            held['held_heads_mismatches'] += 1
        if len(handles[d]['state'].queue) != len(ref.queue):
            held['pending_mismatches'] += 1
        if fleet_backend.get_missing_deps(handles[d]) != \
                [h.hex() for h in ref.missing()]:
            held['missing_mismatches'] += 1
    # what the last round sent withheld (the plan has it ahead of the next
    # round's send, which the window did not reach and nobody sends)
    late = [[buf for _j, buf in state['withheld'][d].get(
        state['applied'][d] - 1, ())] for d in range(n_docs)]
    moved = call(state, late)
    print(f'fact held back at the window\'s end: {waiting} changes queued '
          f'by the reference, {sum(map(len, late))} withheld; the call '
          f'that delivers them: drained_changes '
          f'{moved.get("drained_changes")}, fallbacks {moved["fallbacks"]}, '
          f'exact_calls {moved["exact_calls"]}', file=sys.stderr, flush=True)
    undrained = sum(
        1 for handle in state['handles']
        if handle['state'].queue or fleet_backend.get_missing_deps(handle))
    counters = state['window_counters'] or {}
    compared = rounds.audit(state)
    value, limit = compared['offpath_calls']
    compared['offpath_calls'] = (
        value + moved['fallbacks'] + moved['exact_calls'] +
        counters.get('promotions', 0) + counters.get('mirror_rebuilds', 0) +
        counters.get('seq_inexact_reads', 0), limit)
    compared.update({name: (value, 0) for name, value in held.items()})
    compared['undrained_docs'] = (undrained, 0)
    compared['applied_mismatch'] = (
        abs(state.get('window_applied', 0) -
            counters.get('changes_ingested', 0)), 0)
    return compared
