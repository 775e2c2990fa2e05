#!/usr/bin/env python
"""Zipf-tenant open-loop load generator + chaos client for the service.

The standing scenario testbed for ``automerge_tpu.service`` (ISSUE-7):
an OPEN-LOOP arrival process (arrivals do not wait for completions — the
honest overload model; a closed loop self-throttles and hides collapse)
over a Zipf-skewed tenant population (tenant 1 is the whale, the tail is
long — the distribution under which per-tenant fairness actually earns
its keep), with an optional CHAOS CLIENT that does everything a hostile
or broken real client does:

- corrupts sync/apply payloads in flight (seeded bit flips/truncation on
  a per-attempt transport draw, so service-side retries genuinely
  re-draw — some attempts arrive clean);
- violates deadlines (submits work with deadlines it cannot meet);
- replays already-delivered changes (idempotency probe);
- floods (bursts far past its token bucket, eating typed throttles);
- disconnects sessions mid-flight and abandons their queued work.

Three standard legs — ``clean``, ``chaos``, ``overload`` (2x arrival
rate into reduced admission capacity) — each reporting p50/p95/p99
request latency, sustained rounds/s and requests/s, every rejection
bucketed BY TYPE (an untyped escape anywhere fails the run), brownout
ladder transitions, and a convergence audit: every edit session's doc
must be byte-identical to an unloaded control fleet fed exactly the
committed requests, and every sync session's client replica must reach
head-equality with its service doc after a drain. Every leg also runs
the SLO AUDIT (ISSUE-10): the service SloRegistry's per-tenant outcome
tallies must match the client-observed typed outcomes EXACTLY, so a
double-count or missed-reject in the accounting plane fails the leg —
and ``latency_step=(tick, extra_s)`` injects a synthetic mid-leg
latency regression for timing the burn-rate alert's detection. Used by
tests/test_service_chaos.py (small doses) and chip_smoke.py's ``served``
leg (10k sessions).

Standalone:  python tools/loadgen.py            # default three legs
             LOADGEN_SESSIONS=10000 LOADGEN_REQUESTS=40000 \
             python tools/loadgen.py
"""

import bisect
import json
import os
import random
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import automerge_tpu as A                                     # noqa: E402
from automerge_tpu import backend as host_backend             # noqa: E402
from automerge_tpu.backend import get_change_by_hash          # noqa: E402
from automerge_tpu.columnar import (encode_change,            # noqa: E402
                                    decode_change_meta)
from automerge_tpu.errors import AutomergeError                # noqa: E402
from automerge_tpu.fleet import backend as fleet_backend      # noqa: E402
from automerge_tpu.fleet.backend import DocFleet              # noqa: E402
from automerge_tpu.fleet.faults import LossyLink              # noqa: E402
from automerge_tpu.control import Controller                  # noqa: E402
from automerge_tpu.observability.slo import outcome_class     # noqa: E402
from automerge_tpu.service import Backoff, DocService         # noqa: E402
from automerge_tpu.shard import ShardRouter, shard_stats      # noqa: E402

__all__ = ['ZipfSampler', 'ChaosClient', 'run_leg', 'run_standard_legs',
           'run_shard_leg']


class ZipfSampler:
    """Zipf(s) over n tenants: weight(k) ~ 1/k^s, sampled via one
    bisect on the cumulative table."""

    def __init__(self, n, s=1.2):
        weights = [1.0 / (k + 1) ** s for k in range(n)]
        total = sum(weights)
        self.cum = []
        acc = 0.0
        for w in weights:
            acc += w / total
            self.cum.append(acc)

    def draw(self, rng):
        return bisect.bisect_left(self.cum, rng.random())


class ChaosClient:
    """Per-attempt transport mischief, seeded. ``wrap(payload)`` returns
    a payload_fn whose every call is one transport draw: usually the
    clean bytes, sometimes flipped/truncated/None. The service's retry
    path re-draws through it, so corruption is genuinely transient."""

    def __init__(self, seed, p_corrupt=0.3, p_truncate=0.1, p_drop=0.05):
        self.rng = random.Random(seed)
        self.p_corrupt = p_corrupt
        self.p_truncate = p_truncate
        self.p_drop = p_drop
        self.draws = 0
        self.corrupted = 0

    def _mangle_one(self, buf):
        roll = self.rng.random()
        if roll < self.p_drop:
            self.corrupted += 1
            return None
        if roll < self.p_drop + self.p_truncate and len(buf) > 1:
            self.corrupted += 1
            return buf[:self.rng.randrange(1, len(buf))]
        if roll < self.p_drop + self.p_truncate + self.p_corrupt and buf:
            self.corrupted += 1
            out = bytearray(buf)
            pos = self.rng.randrange(len(out))
            out[pos] ^= 1 << self.rng.randrange(8)
            return bytes(out)
        return buf

    def wrap_changes(self, buffers):
        """payload_fn for an 'apply' request (list of change bytes)."""
        clean = [bytes(b) for b in buffers]

        def draw():
            self.draws += 1
            out = []
            for buf in clean:
                got = self._mangle_one(buf)
                if got is None:
                    return None           # transport delivered nothing
                out.append(got)
            return out
        return draw

    def wrap_message(self, message):
        """payload_fn for a 'sync' request (one message or None)."""
        clean = None if message is None else bytes(message)

        def draw():
            self.draws += 1
            if clean is None:
                return None
            return self._mangle_one(clean)
        return draw


class _EditSession:
    """An apply-only client: a stream of seq-consecutive changes from
    one actor. Tracks what COMMITTED for the control-fleet audit."""

    __slots__ = ('session', 'actor', 'seq', 'committed', 'inflight')

    def __init__(self, session, actor):
        self.session = session
        self.actor = actor
        self.seq = 0
        self.committed = []        # payloads whose tickets resolved ok
        self.inflight = []         # (ticket, payload)

    def next_payload(self, rng):
        self.seq += 1
        return [encode_change({
            'actor': self.actor, 'seq': self.seq, 'startOp': self.seq,
            'time': 0, 'message': '', 'deps': [],
            'ops': [{'action': 'set', 'obj': '_root',
                     'key': f'k{rng.randrange(8)}',
                     'value': rng.randrange(10_000), 'datatype': 'int',
                     'pred': []}]})]

    def harvest(self):
        still = []
        for ticket, payload in self.inflight:
            if not ticket.done:
                still.append((ticket, payload))
            elif ticket.status == 'ok':
                self.committed.append(payload)
        self.inflight = still


class _SyncSession:
    """A sync client: a host-backend replica editing locally and
    reconciling with its service doc through the sync protocol."""

    __slots__ = ('session', 'actor', 'doc', 'state', 'seq', '_prev_state')

    def __init__(self, session, actor):
        self.session = session
        self.actor = actor
        doc = A.init(actor)
        self.doc = A.frontend.get_backend_state(doc, f'loadgen-{actor}')
        self.state = host_backend.init_sync_state()
        self.seq = 0
        self._prev_state = None

    def edit(self, rng):
        """One local change on the client replica (seq-consecutive,
        one op per change, deps = current replica heads)."""
        self.seq += 1
        change = encode_change({
            'actor': self.actor, 'seq': self.seq, 'startOp': self.seq,
            'time': 0, 'message': '',
            'deps': host_backend.get_heads(self.doc),
            'ops': [{'action': 'set', 'obj': '_root',
                     'key': f's{rng.randrange(4)}',
                     'value': rng.randrange(10_000), 'datatype': 'int',
                     'pred': []}]})
        self.doc, _ = host_backend.apply_changes(self.doc, [change])

    def generate(self):
        self._prev_state = self.state
        self.state, message = host_backend.generate_sync_message(
            self.doc, self.state)
        return message

    def rollback(self):
        """The generated message never left the client (admission
        refused the submit): restore the pre-generate sync state, or the
        optimistic sentHashes would poison the handshake exactly like a
        dropped wire message."""
        if self._prev_state is not None:
            self.state = self._prev_state

    def reconnect(self):
        """Client-side reconnect: fresh sync state (idempotent delivery
        makes this always safe; it costs re-advertisement only)."""
        self.state = host_backend.init_sync_state()

    def receive(self, reply):
        if reply is None:
            return
        try:
            self.doc, self.state, _ = host_backend.receive_sync_message(
                self.doc, self.state, bytes(reply))
        except AutomergeError:
            pass                   # corrupt reply == drop (containment)


def run_leg(name, *, sessions=1000, tenants=64, zipf_s=1.2,
            requests=10_000, arrivals_per_tick=64, sync_fraction=0.25,
            chaos=False, overload=False, seed=0, exact_device=False,
            durable_dir=None, fleet=None, deadline_s=None,
            service_kwargs=None, max_ticks=200_000, convergence=True,
            tick_dt=None, collect_saves=False, latency_step=None):
    """One leg. Returns the report dict (see module docstring).

    `tick_dt` switches the service onto a FAKE clock advanced by that
    many seconds per pump — the whole leg becomes a deterministic
    function of its seed (the cross-device-mode byte-identity tests run
    the same script twice and diff the saves). `collect_saves` adds
    `session_saves` ({session_id: (actor, save_hex)}) to the report.

    `latency_step=(tick, extra_s)` injects a SYNTHETIC latency
    regression mid-leg (requires `tick_dt`): from that tick until the
    leg's arrivals end, every pump advances the fake clock by an extra
    `extra_s`, so every in-flight request's measured latency jumps by
    it — the controlled fault the SLO fast-window burn alert must catch
    (tests/test_slo.py times its detection). The report then carries `slo_step_tick` and
    `slo_alerts`.

    Every leg whose service keeps the default SLO accounting ends with
    the SLO AUDIT: the registry's per-tenant outcome tallies must match
    the client-side typed-outcome counts EXACTLY (`slo_audit` in the
    report; tools and main() fail on any mismatch) — the double-count /
    missed-reject detector for the accounting plane under quarantine
    storms."""
    rng = random.Random(seed)
    zipf = ZipfSampler(tenants, zipf_s)
    chaos_client = ChaosClient(seed + 1) if chaos else None

    durable = None
    if durable_dir is not None:
        from automerge_tpu.fleet.durability import DurableFleet
        durable = DurableFleet(durable_dir, exact_device=exact_device,
                               fsync_bytes=1 << 16)
    elif fleet is None:
        fleet = DocFleet(exact_device=exact_device)
    kwargs = dict(tenant_rate=500.0, tenant_burst=200.0, tenant_queue=256,
                  max_queued=max(64, sessions * 2), batch_limit=4096)
    if overload:
        # 2x overload: offered load is twice what the service serves per
        # tick (batch_limit pins per-tick capacity at the base arrival
        # rate), into halved admission headroom — backlog builds, the
        # pressure signal sustains, and the brownout ladder must engage
        # while every rejection stays typed and fair
        # max_queued bounds absolute BACKLOG (latency), not sessions: at
        # 2x offered load the queue pins against it and the sustained
        # queue-pressure signal is what walks the brownout ladder
        kwargs.update(tenant_rate=125.0, tenant_burst=50.0,
                      tenant_queue=64,
                      max_queued=max(32, min(512, sessions)),
                      batch_limit=max(32, arrivals_per_tick))
        arrivals_per_tick *= 2
    if service_kwargs:
        kwargs.update(service_kwargs)
    if latency_step is not None and tick_dt is None:
        raise ValueError('latency_step needs the tick_dt fake clock')
    _clk = [0.0]
    if tick_dt is not None:
        kwargs.setdefault('clock', lambda: _clk[0])
    service = DocService(fleet=fleet, durable=durable, **kwargs)
    _inject = [False]              # latency_step currently applying

    def pump():
        if _inject[0]:
            # the injected regression: age every in-flight request by
            # extra_s before the tick serves it
            _clk[0] += latency_step[1]
        service.pump()
        if tick_dt is not None:
            _clk[0] += tick_dt

    tenant_names = [f'tenant{t}' for t in range(tenants)]
    tenant_of_session = [zipf.draw(rng) for _ in range(sessions)]
    raw = service.open_sessions(
        [tenant_names[t] for t in tenant_of_session])
    by_tenant = {}
    clients = []
    for i, session in enumerate(raw):
        # sessions draw from a bounded actor pool: the fleet interns
        # actor strings fleet-wide with a 256-actor ceiling, and actor
        # seq numbering is PER DOCUMENT, so distinct sessions (distinct
        # docs) sharing an actor string are fully independent
        actor = f'{i % 192:08x}' + 'ab' * 12
        if rng.random() < sync_fraction:
            client = _SyncSession(session, actor)
        else:
            client = _EditSession(session, actor)
        clients.append(client)
        by_tenant.setdefault(tenant_of_session[i], []).append(client)

    counts = {'ok': 0}
    # the client-side half of the SLO audit: every typed outcome this
    # client observes, tallied (tenant, budget class) — the registry's
    # server-side tallies must match these EXACTLY
    client_tally = {}
    latencies = []
    untyped = 0
    submitted = 0
    ticks = 0
    disconnected = 0
    replayed = 0

    def tally(tenant, error):
        key = (tenant, outcome_class(error))
        client_tally[key] = client_tally.get(key, 0) + 1

    def note(ticket):
        nonlocal untyped
        tally(ticket.tenant, ticket.error)
        if ticket.status == 'ok':
            counts['ok'] += 1
            if ticket.latency is not None:
                latencies.append(ticket.latency)
        else:
            err = ticket.error
            key = type(err).__name__
            counts[key] = counts.get(key, 0) + 1
            if not isinstance(err, AutomergeError):
                untyped += 1

    tickets = []

    def submit(client, kind, payload=None, payload_fn=None, timeout=None,
               priority=1):
        nonlocal untyped, submitted
        try:
            ticket = service.submit(client.session, kind, payload,
                                    payload_fn=payload_fn,
                                    timeout=timeout, priority=priority)
        except AutomergeError as exc:
            key = type(exc).__name__
            counts[key] = counts.get(key, 0) + 1
            tally(client.session.tenant, exc)
            return None
        except Exception as exc:       # would be an untyped escape
            counts[f'UNTYPED:{type(exc).__name__}'] = \
                counts.get(f'UNTYPED:{type(exc).__name__}', 0) + 1
            untyped += 1
            return None
        submitted += 1
        tickets.append((ticket, client))
        return ticket

    start = time.perf_counter()
    while (submitted < requests or not service.idle()) and \
            ticks < max_ticks:
        ticks += 1
        if latency_step is not None:
            # the regression applies only while arrivals keep coming
            # (mid-leg): the drain after the loop must converge clean
            _inject[0] = ticks >= latency_step[0] and submitted < requests
        # -- arrivals (open loop: these do not wait for completions)
        n_arrive = min(arrivals_per_tick, requests - submitted)
        for _ in range(max(0, n_arrive)):
            tenant = zipf.draw(rng)
            pool = by_tenant.get(tenant)
            if not pool:
                continue
            client = pool[rng.randrange(len(pool))]
            if client.session.closed:
                continue
            timeout = deadline_s
            priority = 1 if rng.random() < 0.7 else 0
            if chaos and rng.random() < 0.05:
                timeout = 0.0          # deadline the service cannot meet
            if isinstance(client, _EditSession):
                payload = client.next_payload(rng)
                if chaos and rng.random() < 0.3:
                    ticket = submit(client, 'apply',
                                    payload_fn=chaos_client.wrap_changes(
                                        payload),
                                    timeout=timeout, priority=priority)
                else:
                    ticket = submit(client, 'apply', payload,
                                    timeout=timeout, priority=priority)
                if ticket is not None:
                    client.inflight.append((ticket, payload))
                else:
                    # admission refused it: the client keeps the seq and
                    # re-mints it later (a seq gap would poison the
                    # actor's whole suffix)
                    client.seq -= 1
                if chaos and rng.random() < 0.05 and client.committed:
                    # replay an already-committed change (idempotency)
                    replayed += 1
                    submit(client, 'apply',
                           client.committed[rng.randrange(
                               len(client.committed))],
                           timeout=timeout, priority=priority)
            else:
                client.edit(rng)
                message = client.generate()
                if chaos and rng.random() < 0.3:
                    ticket = submit(client, 'sync',
                                    payload_fn=chaos_client.wrap_message(
                                        message),
                                    timeout=timeout, priority=priority)
                else:
                    ticket = submit(client, 'sync', message,
                                    timeout=timeout, priority=priority)
                if ticket is None:
                    # admission refused: the message never left the
                    # client — un-poison sentHashes
                    client.rollback()
            if chaos and rng.random() < 0.002 and \
                    len(service.sessions) > sessions // 2:
                # hard disconnect: abandon the session and its queue
                service.close_session(client.session)
                disconnected += 1
        # -- one service tick
        pump()
        # -- completions: sync clients consume replies, edit clients
        #    book their committed payloads
        still = []
        for ticket, client in tickets:
            if not ticket.done:
                still.append((ticket, client))
                continue
            note(ticket)
            if isinstance(client, _SyncSession) and ticket.status == 'ok':
                client.receive(ticket.result)
        tickets = still
        for client in clients:
            if isinstance(client, _EditSession):
                client.harvest()
    elapsed = time.perf_counter() - start
    _inject[0] = False

    # -- SLO audit: the registry's per-tenant outcome tallies vs the
    #    client-observed typed outcomes. Exact equality or the
    #    accounting plane double-counted / missed a reject somewhere in
    #    the retry/quarantine/disconnect machinery.
    slo_audit = None
    if service.slo is not None:
        pending = sum(1 for t, _ in tickets if not t.done)
        if pending:
            slo_audit = {'skipped': f'{pending} tickets still pending '
                                    f'at max_ticks'}
        else:
            server_tally = {}
            for (tenant, _kind), outcomes in service.slo.tallies().items():
                for cls, n in outcomes.items():
                    key = (tenant, cls)
                    server_tally[key] = server_tally.get(key, 0) + n
            mismatches = []
            for key in sorted(set(server_tally) | set(client_tally)):
                want = client_tally.get(key, 0)
                got = server_tally.get(key, 0)
                if want != got:
                    mismatches.append({'tenant': key[0], 'outcome': key[1],
                                       'client': want, 'registry': got})
            slo_audit = {'pairs_checked': len(set(server_tally) |
                                              set(client_tally)),
                         'mismatches': mismatches}

    # -- drain: finish the sync handshakes fault-free so convergence is
    #    assertable (the wire is quiet, the service keeps admitting)
    converged_sync = drained = 0
    if convergence:
        for client in clients:
            if not isinstance(client, _SyncSession) or \
                    client.session.closed:
                continue
            drained += 1
            # both ends may leave the loaded phase with poisoned
            # handshake state (failed/shredded requests are wire drops);
            # a drain is a RECONNECT — fresh client state, and the
            # service side resets through its own stall machinery
            client.reconnect()
            stalled = 0
            fresh = True
            for _ in range(96):
                message = client.generate()
                ticket = None
                for _ in range(1000):   # ride out throttling, typed —
                    try:                # whale tenants refill at rate
                        ticket = service.submit(client.session, 'sync',
                                                message, priority=5,
                                                reset=fresh)
                        break
                    except AutomergeError:
                        pump()
                fresh = False
                if ticket is None:
                    client.rollback()
                    break
                while not ticket.done:
                    pump()
                if ticket.status != 'ok':
                    client.rollback()   # never processed: un-poison
                    continue
                client.receive(ticket.result)
                service_heads = host_backend.get_heads(
                    client.session.handle)
                client_heads = host_backend.get_heads(client.doc)
                if message is None and ticket.result is None and \
                        service_heads == client_heads:
                    converged_sync += 1
                    break
                stalled += 1
                if stalled % 24 == 23:  # belt-and-braces reconnect
                    client.reconnect()
                    fresh = True

    # -- control audit: an unloaded fleet fed exactly the committed
    #    edits must byte-match the loaded service docs
    mismatches = 0
    audited = 0
    if convergence:
        control_fleet = DocFleet(exact_device=exact_device)
        edit_clients = [c for c in clients
                        if isinstance(c, _EditSession)
                        and not c.session.closed and c.committed]
        if edit_clients:
            control = fleet_backend.init_docs(len(edit_clients),
                                              control_fleet)
            control, _ = fleet_backend.apply_changes_docs(
                control, [[b for payload in c.committed for b in payload]
                          for c in edit_clients], mirror=False)
            for client, ctrl in zip(edit_clients, control):
                audited += 1
                if bytes(host_backend.save(client.session.handle)) != \
                        bytes(host_backend.save(ctrl)):
                    mismatches += 1

    latencies.sort()

    def pct(p):
        if not latencies:
            return None
        return latencies[min(len(latencies) - 1,
                             int(p * len(latencies)))]

    report = {
        'leg': name,
        'sessions': sessions,
        'tenants': tenants,
        'requests_offered': requests,
        'submitted': submitted,
        'completed_ok': counts['ok'],
        'rejections': {k: v for k, v in sorted(counts.items())
                       if k != 'ok'},
        'untyped_escapes': untyped,
        'elapsed_s': round(elapsed, 3),
        'ticks': ticks,
        'rounds_per_s': round(ticks / elapsed, 1) if elapsed else None,
        'requests_per_s': round(counts['ok'] / elapsed, 1)
        if elapsed else None,
        'p50_ms': round(pct(0.50) * 1e3, 3) if latencies else None,
        'p95_ms': round(pct(0.95) * 1e3, 3) if latencies else None,
        'p99_ms': round(pct(0.99) * 1e3, 3) if latencies else None,
        'brownout_stage_final': service.brownout.stage,
        'brownout_transitions': len(service.brownout.transitions),
        'disconnected': disconnected,
        'replayed': replayed,
        'chaos_draws': chaos_client.draws if chaos_client else 0,
        'chaos_corrupted': chaos_client.corrupted if chaos_client else 0,
        'convergence': {
            'edit_docs_audited': audited,
            'edit_mismatches': mismatches,
            'sync_drained': drained,
            'sync_converged': converged_sync,
        } if convergence else None,
        'slo_audit': slo_audit,
    }
    if service.slo is not None:
        report['slo_alerts'] = [
            {'tick': t, 'tenant': tenant, 'kind': kind, 'sli': sli,
             'window': window, 'edge': edge, 'burn': burn}
            for t, tenant, kind, sli, window, edge, burn in
            service.slo.alert_log]
        if latency_step is not None:
            report['slo_step_tick'] = latency_step[0]
    if collect_saves:
        report['session_saves'] = {
            c.session.id: (c.actor,
                           bytes(host_backend.save(c.session.handle)).hex())
            for c in clients if not c.session.closed}
    if durable is not None:
        durable.close()
    return report


class _ShardWriter:
    """One tenant's write stream in shard mode: seq-consecutive changes
    from one actor, at most one apply in flight (seq ordering survives
    router-level retries), failed payloads RETRANSMITTED byte-identical
    (a re-minted seq with fresh content would collide with a copy the
    crash actually preserved — idempotent-by-hash replay is the safe
    retry)."""

    __slots__ = ('name', 'actor', 'seq', 'acked', 'inflight', 'stash')

    def __init__(self, name, actor):
        self.name = name
        self.actor = actor
        self.seq = 0
        self.acked = []          # payloads whose router tickets acked
        self.inflight = None     # (ticket, payload)
        self.stash = None        # failed payload awaiting retransmit

    def next_payload(self, rng):
        if self.stash is not None:
            payload, self.stash = self.stash, None
            return payload
        self.seq += 1
        return [encode_change({
            'actor': self.actor, 'seq': self.seq, 'startOp': self.seq,
            'time': 0, 'message': '', 'deps': [],
            'ops': [{'action': 'set', 'obj': '_root',
                     'key': f'k{rng.randrange(8)}',
                     'value': rng.randrange(10_000), 'datatype': 'int',
                     'pred': []}]})]


def run_shard_leg(name, *, n_shards=4, tenants=16, requests=800,
                  arrivals_per_tick=8, kills=(), chaos=False, seed=0,
                  lease_ticks=3, tick_dt=0.02, subscribe_fraction=0.2,
                  sync_fraction=0.1, rebalance_after_revive=True,
                  audit_rounds=True, exact_device=False,
                  link_budget=48, max_ticks=60_000, mttr_bound=None,
                  service_kwargs=None, pump_threads=None, repl_every=1,
                  pace=False, control=None, control_window=5,
                  settle_bound=None):
    """The kill-and-recover chaos leg for the shard cluster (ISSUE-11).

    Drives an open-loop workload (applies + subscription pulls + sync
    solicits) through a ``ShardRouter`` while crashing and reviving
    shards on a schedule: ``kills`` is a sequence of
    ``(kill_tick, shard_index, revive_tick)``. With ``chaos=True`` the
    inter-shard replication links are budgeted ``LossyLink``s
    (drop/dup/reorder/truncate/flip), so replication itself rides a
    hostile wire; the budget runs dry before the drain, which is what
    makes the post-quiet audit assertable.

    The two contract audits (run after each revive round when
    ``audit_rounds``, and always at the end):

    - ZERO ACKNOWLEDGED-WRITE LOSS: every change of every acked apply
      is present (by hash) on the tenant's CURRENT home doc — across
      every kill, failover, and rebalance in the schedule.
    - BYTE-IDENTICAL CONVERGENCE: after replication goes quiet, every
      tenant's home doc and replica doc save() to identical bytes.

    Plus the standing properties: zero untyped escapes (every failed
    ticket carries an AutomergeError), and failover MTTR — ticks from
    each kill to the first acked request served by a re-homed tenant's
    replica — reported per kill (``mttr_bound`` asserts a ceiling).

    ``control='active'|'shadow'`` rides a ``control.Controller`` on the
    router's pump (the self-driving leg, ISSUE-20): under ACTIVE
    control the leg's hardcoded ``rebalance_after_revive`` call is
    disabled — post-revive placement healing is exactly the control
    plane's heal lane, and this leg is where it earns that job. The
    leg's ``ok`` then also requires <= 2 direction reversals per policy
    (the anti-oscillation bound), a decision-free CONVERGENCE HOLD (10
    quiet decision windows pumped after the drain — an oscillating
    controller keeps hunting and fails it), and, with ``settle_bound``,
    that the last decision lands within that many ticks of the last
    revive. Both audits (zero acked-write loss, byte-identical
    convergence) run unchanged: a controller that converges by losing
    writes fails the same assert the chaos schedule does."""
    rng = random.Random(seed)
    clk = [0.0]
    link_seed = [seed * 7919 + 13]
    if control is not None and control not in ('active', 'shadow'):
        raise ValueError(f"control must be None, 'active' or 'shadow', "
                         f'got {control!r}')
    ctrl = Controller(mode=control, window=control_window) \
        if control is not None else None
    if control == 'active':
        # the heal lane owns post-revive placement now; the hardcoded
        # rebalance would fight it (and mask it)
        rebalance_after_revive = False

    def link_factory(src, dst):
        if not chaos:
            return None
        link_seed[0] += 1
        return LossyLink(seed=link_seed[0], p_drop=0.05, p_dup=0.02,
                         p_reorder=0.02, p_truncate=0.02, p_flip=0.02,
                         budget=link_budget)

    router = ShardRouter(
        n_shards=n_shards, clock=lambda: clk[0],
        lease_ticks=lease_ticks, link_factory=link_factory,
        exact_device=exact_device, service_kwargs=service_kwargs,
        pump_threads=pump_threads, repl_every=repl_every,
        # paced legs declare the cadence to the router too, so slipped
        # ticks are attributed PER SHARD (Shard.ticks_slipped -> the
        # labeled Prometheus counter), not just counted in this loop
        tick_budget_s=tick_dt if pace else None,
        control=ctrl,
        backoff=Backoff(base=tick_dt, factor=1.5, cap=tick_dt * 16,
                        retries=16, jitter=0.5, seed=seed + 3))
    shard_ids = router.ring.shard_ids()
    tenant_names = [f'tenant{t}' for t in range(tenants)]
    writers = {}
    for i, t in enumerate(tenant_names):
        router.open_tenant(t)
        writers[t] = _ShardWriter(t, f'{i % 192:08x}' + 'cd' * 12)

    counts = {'ok': 0}
    untyped = 0
    submitted = 0
    aux = []                    # subscribe/sync tickets in flight
    audits = []
    mttrs = []                  # one record per kill
    kill_list = sorted(kills)
    revive_pending = []         # (revive_tick, shard_id)
    last_revive_tick = None
    base_health = shard_stats()

    def pump():
        router.pump(now=clk[0])
        clk[0] += tick_dt

    def note_error(err):
        nonlocal untyped
        key = type(err).__name__
        counts[key] = counts.get(key, 0) + 1
        if not isinstance(err, AutomergeError):
            untyped += 1

    def harvest():
        for t, w in writers.items():
            if w.inflight is None:
                continue
            ticket, payload = w.inflight
            if not ticket.done:
                continue
            w.inflight = None
            if ticket.status == 'ok':
                counts['ok'] += 1
                w.acked.append(payload)
                for m in mttrs:
                    if m['mttr_ticks'] is None and t in m['tenants'] and \
                            router.tenant_record(t).home != m['shard']:
                        m['mttr_ticks'] = router.ticks - m['kill_tick']
            else:
                note_error(ticket.error)
                w.stash = payload        # retransmit the SAME bytes
        still = []
        for ticket in aux:
            if not ticket.done:
                still.append(ticket)
                continue
            if ticket.status == 'ok':
                counts['ok'] += 1
            else:
                note_error(ticket.error)
        aux[:] = still

    def writers_idle():
        return all(w.inflight is None for w in writers.values())

    def drain_quiet(budget=1200):
        for _ in range(budget):
            if router.idle() and router.replication_quiet() and \
                    not router.migrating() and writers_idle() and not aux:
                return True
            pump()
            harvest()
        return False

    def audit(tag):
        checked = lost = pairs = mismatched = homeless = 0
        for t, w in writers.items():
            rec = router.tenant_record(t)
            if rec.home is None or rec.session is None:
                homeless += 1
                continue
            for payload in w.acked:
                for b in payload:
                    checked += 1
                    h = decode_change_meta(bytes(b), True)['hash']
                    if get_change_by_hash(rec.session.handle, h) is None:
                        lost += 1
            if rec.replica_handle is not None:
                pairs += 1
                home_bytes = bytes(host_backend.save(rec.session.handle))
                rep_bytes = bytes(host_backend.save(rec.replica_handle))
                if home_bytes != rep_bytes:
                    mismatched += 1
        record = {'tag': tag, 'tick': router.ticks,
                  'acked_changes_checked': checked, 'acked_lost': lost,
                  'replica_pairs': pairs,
                  'replica_mismatches': mismatched,
                  'homeless_tenants': homeless}
        audits.append(record)
        return record

    start = time.perf_counter()
    slipped = 0
    while submitted < requests or not writers_idle() or aux or \
            kill_list or revive_pending:
        if router.ticks >= max_ticks:
            break
        if pace:
            # the serving tick is a CADENCE (tick_dt bounds batching
            # latency): sleep to the tick boundary, and when the tick's
            # work overran it, count the slip — a box whose per-tick
            # work does not fit the cadence shows it here instead of
            # silently reporting free-run throughput
            deadline = start + router.ticks * tick_dt
            wait = deadline - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            else:
                slipped += 1
        while kill_list and router.ticks >= kill_list[0][0]:
            ktick, sidx, rtick = kill_list.pop(0)
            sid = shard_ids[sidx]
            doomed = set(router.tenants_on(sid))
            router.kill_shard(sid)
            mttrs.append({'shard': sid, 'kill_tick': router.ticks,
                          'tenants': doomed, 'mttr_ticks': None})
            revive_pending.append((rtick, sid))
        for rtick, sid in list(revive_pending):
            if router.ticks >= rtick:
                revive_pending.remove((rtick, sid))
                router.revive_shard(sid)
                last_revive_tick = router.ticks
                if rebalance_after_revive:
                    router.rebalance()
                if audit_rounds:
                    # one recovery round settles: arrivals pause, the
                    # cluster drains to quiet, both audits run, then
                    # the workload resumes against the healed topology
                    drain_quiet()
                    audit(f'post-revive-{sid}')
        n_arrive = min(arrivals_per_tick, requests - submitted)
        for _ in range(max(0, n_arrive)):
            t = tenant_names[rng.randrange(tenants)]
            w = writers[t]
            roll = rng.random()
            if roll < subscribe_fraction:
                aux.append(router.submit(t, 'subscribe'))
                submitted += 1
            elif roll < subscribe_fraction + sync_fraction:
                aux.append(router.submit(t, 'sync', None))
                submitted += 1
            else:
                if w.inflight is not None:
                    continue             # writer busy: seq order first
                payload = w.next_payload(rng)
                ticket = router.submit(t, 'apply', payload)
                w.inflight = (ticket, payload)
                submitted += 1
        pump()
        harvest()
    drained = drain_quiet(budget=2400)
    fixed_point = None
    if ctrl is not None:
        # the convergence hold: pump 10 quiet decision windows with no
        # arrivals — a converged controller makes ZERO further
        # decisions (an oscillating one keeps hunting and fails here)
        before = len(ctrl.decision_log())
        for _ in range(10 * control_window):
            pump()
        harvest()
        fixed_point = len(ctrl.decision_log()) == before
    elapsed = time.perf_counter() - start   # serving window: audits are
    final = audit('final')                  # verification, not serving

    health = shard_stats()
    link_stats = {}
    for (src, dst), link in router._links.items():
        if link is not None:
            link_stats[f'{src}->{dst}'] = dict(link.stats)
    report = {
        'leg': name,
        'shards': n_shards,
        'tenants': tenants,
        'requests_offered': requests,
        'submitted': submitted,
        'completed_ok': counts['ok'],
        'rejections': {k: v for k, v in sorted(counts.items())
                       if k != 'ok'},
        'untyped_escapes': untyped,
        'elapsed_s': round(elapsed, 3),
        'ticks': router.ticks,
        'requests_per_s': round(counts['ok'] / elapsed, 1)
        if elapsed else None,
        'lease_ticks': lease_ticks,
        'paced': bool(pace),
        'ticks_slipped': slipped if pace else None,
        'ticks_slipped_per_shard': {sid: router.shards[sid].ticks_slipped
                                    for sid in shard_ids} if pace else None,
        'scrub_mismatches': len(router.scrub_mismatches),
        'kills': len(mttrs),
        'failovers': len(router.failovers),
        'mttr_ticks': [m['mttr_ticks'] for m in mttrs],
        'drained': drained,
        'audits': audits,
        'final_audit': final,
        'shard_health_delta': {k: health[k] - base_health.get(k, 0)
                               for k in health
                               if health[k] != base_health.get(k, 0)},
        'link_stats': link_stats,
    }
    ok = (untyped == 0 and final['acked_lost'] == 0 and
          final['replica_mismatches'] == 0 and
          all(a['acked_lost'] == 0 and a['replica_mismatches'] == 0
              for a in audits) and drained)
    if mttr_bound is not None:
        ok = ok and all(m['mttr_ticks'] is not None and
                        m['mttr_ticks'] <= mttr_bound
                        for m in mttrs if m['tenants'])
    if ctrl is not None:
        gauges = ctrl.gauges()
        per_policy = {}
        for (policy, _action, _mode), n in gauges['decisions'].items():
            per_policy[policy] = per_policy.get(policy, 0) + n
        last_tick = gauges['last_decision_tick']
        settle = None
        if last_revive_tick is not None and last_tick is not None and \
                last_tick > last_revive_tick:
            settle = last_tick - last_revive_tick
        report['control'] = {
            'mode': control,
            'window': control_window,
            'windows': gauges['windows'],
            'decisions': per_policy,
            'actuations': sum(
                n for (_p, _a, mode), n in gauges['decisions'].items()
                if mode == 'active'),
            'reversals': gauges['reversals'],
            'last_decision_tick': last_tick,
            'last_revive_tick': last_revive_tick,
            'settle_ticks': settle,
            'fixed_point': fixed_point,
            'decide_s_max': gauges['decide_s_max'],
            'ledger_entries': len(ctrl.decision_log()),
        }
        # the anti-oscillation bound: a policy flip-flopping on one
        # target more than twice in an episode is hunting, not
        # converging — and the post-drain hold must be decision-free
        ok = ok and all(n <= 2 for n in gauges['reversals'].values())
        ok = ok and fixed_point
        if settle_bound is not None and last_revive_tick is not None:
            ok = ok and (settle is None or settle <= settle_bound)
    report['ok'] = ok
    router.close()
    return report


def run_standard_legs(sessions=1000, tenants=64, requests=10_000,
                      seed=0, exact_device=False, sync_fraction=0.25):
    """The three standing legs: clean, chaos, 2x overload."""
    legs = []
    legs.append(run_leg('clean', sessions=sessions, tenants=tenants,
                        requests=requests, seed=seed,
                        sync_fraction=sync_fraction,
                        exact_device=exact_device))
    legs.append(run_leg('chaos', sessions=sessions, tenants=tenants,
                        requests=requests, chaos=True, seed=seed + 1,
                        sync_fraction=sync_fraction,
                        exact_device=exact_device))
    legs.append(run_leg('overload', sessions=sessions, tenants=tenants,
                        requests=requests, overload=True, seed=seed + 2,
                        sync_fraction=sync_fraction,
                        exact_device=exact_device))
    return legs


def run_tier_leg(name='tier_hybrid', *, docs=512, hot=48, rounds=30,
                 writes_per_round=24, seed=0, budget_docs=None,
                 stage_schedule=None, path=None):
    """Hybrid live/parked storage-tier leg (ISSUE-15 acceptance): a
    hot-skewed write stream over a doc population living under a
    RESIDENT-BYTES ceiling, with the cost-based tiering plane doing ALL
    demotion — zero manual ``park`` calls — fed by the round-17 memory
    watermarks (``fleet_resident_bytes``). Parked docs that take writes
    revive through the engine (live/parked churn -> arena garbage ->
    cost-model vacuums), and a brownout stage schedule (stage 2 mid-leg
    by default) runs the model's defer/fire ledger, flight-recorded.

    Final CONVERGENCE AUDIT: every doc — live or parked — must be
    byte-identical to a control fleet fed exactly the committed
    changes (parked docs compare their canonical chunk bytes; no
    revive). Returns the leg report dict; ``ok`` summarizes."""
    import shutil
    import tempfile
    from automerge_tpu.fleet.backend import init_docs
    from automerge_tpu.fleet.storage import StorageEngine
    from automerge_tpu.fleet.tiering import (ClockDemote, CostModel,
                                             TieringController,
                                             tiering_stats)
    from automerge_tpu.observability.perf import sample_watermarks

    rng = random.Random(seed)
    root = path or tempfile.mkdtemp(prefix='loadgen-tier-')
    own_root = path is None
    fleet = DocFleet()
    eng = StorageEngine(fleet, path=os.path.join(root, 'arena'))

    # the demote signal: LIVE (unfrozen) docs. The fleet's device grids
    # are capacity-sized (fleet_resident_bytes cannot fall when a doc
    # parks — only a capacity shrink moves it), so the leg budgets the
    # per-doc HOST cost directly: live-doc count against a doc budget,
    # with the byte watermarks sampled into the report for the record.
    def resident():
        return sum(1 for h in by_doc.values()
                   if h is not None and not h.get('frozen'))

    handles = init_docs(docs, fleet)
    ledger = [[] for _ in range(docs)]       # committed changes per doc
    seqs = [0] * docs
    by_doc = {d: handles[d] for d in range(docs)}   # live handle or None
    parked_id = [None] * docs

    def write_round(targets):
        per_handle, hs = [], []
        for d in targets:
            seqs[d] += 1
            heads = fleet_backend.get_heads(by_doc[d])
            buf = encode_change({
                'actor': f'{d:04x}' * 4, 'seq': seqs[d],
                'startOp': seqs[d], 'time': 0, 'message': '',
                'deps': heads,
                'ops': [{'action': 'set', 'obj': '_root',
                         'key': f'k{seqs[d] % 4}', 'value': d * 100 + seqs[d],
                         'datatype': 'int', 'pred': []}]})
            ledger[d].append(buf)
            per_handle.append([buf])
            hs.append(by_doc[d])
        out, _ = fleet_backend.apply_changes_docs(hs, per_handle,
                                                  mirror=False)
        for d, h in zip(targets, out):
            by_doc[d] = h
        return out

    # seed every doc with one change so parked chunks are non-trivial
    write_round(list(range(docs)))
    if budget_docs is None:
        budget_docs = max(hot * 2, docs // 4)
    budget = budget_docs
    policy = ClockDemote(eng, budget_bytes=budget,
                         source=resident, batch=64)
    # the seam returns FRESH handle dicts each apply (the old ones
    # freeze): register the post-write handles, and re-register after
    # every round below — stale ring entries prune themselves
    policy.register(list(by_doc.values()))
    # an eager model at leg scale: revive-discard garbage pays for a
    # rewrite quickly at stage 0, while the stage-2 write penalty defers
    # it — both verdicts land in the flight record over one leg
    ctrl = TieringController(engine=eng, demote=policy,
                             model=CostModel(min_garbage_bytes=1024,
                                             garbage_byte_cost=8.0))
    t0 = dict(tiering_stats())
    if stage_schedule is None:
        stage_schedule = [0] * (rounds // 3) + [2] * (rounds // 3) + \
            [0] * (rounds - 2 * (rounds // 3))

    pressures = []
    revived = 0
    for r in range(rounds):
        # hot-skewed target draw: 80% hot set, 20% tail
        targets = sorted({
            rng.randrange(hot) if rng.random() < 0.8
            else rng.randrange(docs) for _ in range(writes_per_round)})
        # revive any parked targets through the engine (hybrid churn)
        need = [d for d in targets if by_doc[d] is None]
        if need:
            got = eng.revive([parked_id[d] for d in need])
            revived += len(need)
            for d, h in zip(need, got):
                by_doc[d] = h
                parked_id[d] = None
            policy.register(got)
        out = write_round(targets)
        policy.register(out)
        policy.touch(out)
        stage = stage_schedule[min(r, len(stage_schedule) - 1)]
        ctrl.tick(stage=stage)
        # fold the tick's parks back into the doc map (handle -> id
        # pairs from the clock, so a later write can revive by id)
        if policy.last_parked:
            doc_of = {id(h): d for d, h in by_doc.items()
                      if h is not None}
            for h, i in policy.last_parked:
                d = doc_of.get(id(h))
                if d is not None:
                    by_doc[d] = None
                    parked_id[d] = i
        pressures.append(policy.pressure())

    # ---- convergence audit: control fleet fed exactly the ledger ----
    control_fleet = DocFleet()
    control = init_docs(docs, control_fleet)
    control, _ = fleet_backend.apply_changes_docs(
        control, [list(l) for l in ledger], mirror=False)
    mismatches = 0
    for d in range(docs):
        want = bytes(control[d]['state'].save())
        if by_doc[d] is not None:
            got = bytes(by_doc[d]['state'].save())
        elif parked_id[d] is not None:
            got = bytes(eng.chunk(parked_id[d]))
        else:
            mismatches += 1
            continue
        if got != want:
            mismatches += 1
    t1 = dict(tiering_stats())
    final_pressure = policy.pressure()
    marks = sample_watermarks()
    report = {
        'leg': name, 'docs': docs, 'rounds': rounds,
        'watermarks': {k: marks.get(k, 0) for k in
                       ('rss', 'mainstore_bytes', 'mainstore_disk_bytes')},
        'demoted': t1['tiering_demoted_docs'] - t0['tiering_demoted_docs'],
        'model_vacuums': t1['tiering_vacuums'] - t0['tiering_vacuums'],
        'engine_vacuums': eng.vacuums,
        'deferred': t1['tiering_deferred'] - t0['tiering_deferred'],
        'revived': revived,
        'manual_parks': 0,
        'budget_bytes': budget,
        'final_pressure': round(final_pressure, 3),
        'max_late_pressure': round(max(pressures[rounds // 2:]), 3),
        'audit_mismatches': mismatches,
        'parked_final': len(eng.main),
    }
    report['ok'] = mismatches == 0 and report['demoted'] > 0 and \
        final_pressure <= 1.05
    eng.close()
    if own_root:
        shutil.rmtree(root, ignore_errors=True)
    return report


def run_tier_kill_leg(name='tier_kill', *, docs=32, seed=0, path=None):
    """Kill-driven vacuum leg: a CHILD process parks a doc population
    on the mmap arena, discards a slice, and hard-dies (os._exit)
    INSIDE the vacuum's manifest swap; the parent recovers the arena
    via StorageEngine.open and audits every surviving doc byte-for-byte
    against the child's pre-kill expectations."""
    import shutil
    import subprocess
    import tempfile
    root = path or tempfile.mkdtemp(prefix='loadgen-tierkill-')
    own_root = path is None
    arena = os.path.join(root, 'arena')
    expect_path = os.path.join(root, 'expect.bin')
    script = f'''
import os, sys
sys.path.insert(0, {os.path.dirname(os.path.dirname(os.path.abspath(__file__)))!r})
os.environ['JAX_PLATFORMS'] = 'cpu'   # the parent may hold the chip
from automerge_tpu.columnar import encode_change
from automerge_tpu.fleet import backend as fb
from automerge_tpu.fleet.backend import DocFleet, init_docs
from automerge_tpu.fleet.storage import StorageEngine
fleet = DocFleet()
eng = StorageEngine(fleet, path={arena!r}, vacuum_dead_fraction=None)
handles = init_docs({docs}, fleet)
per = [[encode_change({{'actor': f'{{d:04x}}' * 4, 'seq': 1, 'startOp': 1,
        'time': 0, 'message': '', 'deps': [],
        'ops': [{{'action': 'set', 'obj': '_root', 'key': 'k',
                 'value': d, 'datatype': 'int', 'pred': []}}]}})]
       for d in range({docs})]
handles, _ = fb.apply_changes_docs(handles, per, mirror=False)
saves = [bytes(h['state'].save()) for h in handles]
ids = eng.park(handles)
keep = ids[{docs} // 3:]
import struct
with open({expect_path!r}, 'wb') as f:
    for i in keep:
        f.write(struct.pack('<qI', i, len(saves[i])) + saves[i])
eng.discard(ids[:{docs} // 3])
eng.main.sync()
eng.main._arena.fault_point = 'exit:post_manifest'
eng.vacuum_now()       # never returns
'''
    proc = subprocess.run([sys.executable, '-c', script],
                          capture_output=True, timeout=600)
    report = {'leg': name, 'docs': docs,
              'child_exit': proc.returncode}
    if proc.returncode != 71:
        report['ok'] = False
        report['stderr'] = proc.stderr.decode()[-1000:]
        return report
    import struct
    from automerge_tpu.fleet.storage import StorageEngine
    expect = {}
    with open(expect_path, 'rb') as f:
        while True:
            head = f.read(12)
            if len(head) < 12:
                break
            i, ln = struct.unpack('<qI', head)
            expect[i] = f.read(ln)
    eng = StorageEngine.open(arena)
    mismatches = sum(
        1 for i, want in expect.items()
        if i not in eng._row_of or bytes(eng.chunk(i)) != want)
    missing = sorted(set(eng._row_of) - set(expect))
    report.update(recovered=len(eng._row_of),
                  expected=len(expect),
                  audit_mismatches=mismatches,
                  resurrected=len(missing),
                  ok=mismatches == 0 and not missing and
                  len(eng._row_of) == len(expect))
    eng.close()
    if own_root:
        shutil.rmtree(root, ignore_errors=True)
    return report


def main():
    from automerge_tpu import jaxenv
    jaxenv.configure_compile_cache()
    sessions = int(os.environ.get('LOADGEN_SESSIONS', 1000))
    tenants = int(os.environ.get('LOADGEN_TENANTS', 64))
    requests = int(os.environ.get('LOADGEN_REQUESTS', 10_000))
    seed = int(os.environ.get('LOADGEN_SEED', 0))
    n_shards = int(os.environ.get('LOADGEN_SHARDS', 0))
    if os.environ.get('LOADGEN_TIER'):
        # storage-tier mode: the hybrid auto-demote leg + the
        # kill-mid-vacuum recovery leg (ISSUE-15 acceptance)
        legs = [
            run_tier_leg(docs=int(os.environ.get('LOADGEN_TIER_DOCS',
                                                 512)), seed=seed),
            run_tier_kill_leg(seed=seed + 1),
        ]
        for leg in legs:
            print(json.dumps(leg))
            print(f"# {leg['leg']}: {'OK' if leg['ok'] else 'FAIL'} "
                  f"{leg}", file=sys.stderr)
            if not leg['ok']:
                sys.exit(1)
        return
    if n_shards:
        # multi-shard mode: a clean leg plus a kill-one-shard chaos leg
        # (kill at 1/3 of the arrival window, revive at 2/3).
        # LOADGEN_CONTROL=active|shadow adds the self-driving leg: the
        # same kill schedule with a control.Controller on the pump and
        # the hardcoded post-revive rebalance handed to its heal lane.
        arrivals = 8
        window = max(1, requests // arrivals)
        legs = [
            run_shard_leg('shard_clean', n_shards=n_shards,
                          tenants=tenants, requests=requests, seed=seed),
            run_shard_leg('shard_kill', n_shards=n_shards,
                          tenants=tenants, requests=requests,
                          chaos=True, seed=seed + 1,
                          kills=((window // 3, 0, 2 * window // 3),)),
        ]
        control_mode = os.environ.get('LOADGEN_CONTROL')
        if control_mode:
            legs.append(run_shard_leg(
                'shard_control', n_shards=n_shards, tenants=tenants,
                requests=requests, chaos=True, seed=seed + 2,
                kills=((window // 3, 0, 2 * window // 3),),
                control=control_mode, settle_bound=400))
        for leg in legs:
            print(json.dumps(leg))
            ctl = leg.get('control')
            ctl_s = (f", control {ctl['decisions']} decisions "
                     f"{ctl['reversals']} reversals "
                     f"settle {ctl['settle_ticks']} ticks") if ctl else ''
            print(f"# {leg['leg']}: {leg['completed_ok']}/"
                  f"{leg['submitted']} ok, {leg['failovers']} failovers, "
                  f"mttr {leg['mttr_ticks']} ticks, audit "
                  f"{leg['final_audit']}{ctl_s}, "
                  f"{'OK' if leg['ok'] else 'FAIL'}", file=sys.stderr)
            if not leg['ok']:
                sys.exit(1)
        return
    for leg in run_standard_legs(sessions=sessions, tenants=tenants,
                                 requests=requests, seed=seed):
        print(json.dumps(leg))
        audit = leg.get('slo_audit')
        # a SKIPPED audit (tickets still pending at max_ticks) fails the
        # leg like a mismatch would — same contract the test harness's
        # assert_leg_ok enforces; silently passing it would mask a hung
        # or backlogged leg
        ok = leg['untyped_escapes'] == 0 and (
            leg['convergence'] is None or
            leg['convergence']['edit_mismatches'] == 0) and (
            audit is None or ('mismatches' in audit
                              and not audit['mismatches']))
        print(f"# {leg['leg']}: {leg['completed_ok']}/{leg['submitted']} "
              f"ok, p99 {leg['p99_ms']}ms, {leg['rounds_per_s']} rounds/s, "
              f"stage {leg['brownout_stage_final']}, "
              f"{'OK' if ok else 'FAIL'}", file=sys.stderr)
        if not ok:
            sys.exit(1)


if __name__ == '__main__':
    main()
