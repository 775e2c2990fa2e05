"""Host-phase spans: a near-zero-overhead-when-off begin/end recorder.

``span(name, **attrs)`` is the one call sites use. When tracing is OFF
(the default) it returns a shared no-op context manager — the entire cost
of an instrumented seam is one module-flag check and two empty method
calls, which is why the hot paths (turbo apply, journal commit, Bloom
build) can stay instrumented permanently instead of behind copy-pasted
``if`` guards. When ON (``enable()``), every span close records
``(name, t0_ns, t1_ns, thread, attrs, error, thread_cpu_ns, id, parent,
root)`` into a bounded ring — old spans fall off the end, so a
long-running fleet never grows memory.

Beside the wall clock the begin and the end of a ROOT span (a call of
``apply_changes_docs``, a service tick, a recovery: a span nothing encloses)
read the calling thread's CPU clock (``time.thread_time_ns``): the record's
``thread_cpu_ns`` is what the thread RAN between the two, so ``dur_ns -
thread_cpu_ns`` is the time it was off the CPU (descheduled by a shared
host, waiting on a lock, a pool or the device) — what tells a call that ran
slowly from one that did not run. It is None on every span under a root and
on a ``record_span`` slice. Why not at every mark: that clock is a system
call, and where system calls are intercepted (the chip's host: about 30 us
a read against 0.3 on a plain kernel, in ticks of 10 ms; PERF.md, PR 39) a
read at each of a turbo call's forty marks made the traced call a tenth
longer and moved every phase's reading; two reads a call, outside every
phase, move none, and a 10 ms tick says nothing about a phase of a
millisecond anyway. Only while
recording is on: off, no clock is read.

Spans form a TREE: ``id`` is a process-wide counter, ``parent`` the id of
the innermost span open on the same thread when this one opened (``None``
for a root), ``root`` the id of the outermost ancestor — what every span of
one ``apply_changes_docs`` call, one service tick, one recovery shares.
The open spans of a thread are a thread-local stack that exists only while
recording is on. ``self_times(spans)`` is a span's duration less what its
children cover. While recording is on a ``gc.callbacks`` hook records every
collection as a ``gc`` span, a child of whatever it interrupted, so a
phase's self time can be read without the collector.

``span_seq()`` is the shape the multi-phase seams use (turbo apply,
recovery): ``mark(name)`` closes the previous phase and opens the next at
the SAME timestamp, so consecutive phases tile an interval with no
unattributed gap — that contiguity is what lets the benchmark's
``seam.untraced_ms_per_step`` (benchmarks/metrics/) read the part of a
seam call no phase accounts for. ``mark()`` and ``done()`` return the
instant they read (wall ns; None while off) and take one as ``at=``: a
second sequence that tiles a phase of the first opens its first sub-phase
and closes its last AT the parent's own marks, on one clock read for both
(the turbo seam's ``gate.*`` / ``commit.*`` / ``stage.*`` / ``dispatch.*``
/ ``setup.*`` under the six ``turbo_*``).

Spans stay in THIS ring only; the flight recorder reads the ring's tail
at dump time (recorder.dump_flight_record) rather than mirroring every
close into its own event ring — a traced run would otherwise flood the
small fault-event ring with span closes and evict exactly the
quarantine/rot events a forensic dump exists to preserve.

While recording is on every ``Span`` / ``SpanSeq`` phase is also a
``jax.profiler.TraceAnnotation`` of the same name for its lifetime: outside
a profiler session that is a flag check inside the profiler, inside one the
span lands in the capture's ``/host:CPU`` plane on the clock the
``/device:TPU:*`` planes use, so a device gap can be put down to the phase
the host was in (``observability.trace`` is the operator's entry). Where
JAX is absent the ring works alone.

``export_chrome_trace(path)`` writes the ring as Chrome trace-event JSON
("X" complete events, microsecond timestamps), the format Perfetto and
chrome://tracing load directly. Its clock is ``time.perf_counter_ns``, NOT
the profiler's: to see host phases beside the device timeline read them
from the profiler capture itself.
"""

import gc
import itertools
import json
import threading
import time

from .metrics import register_health_source

__all__ = ['enable', 'disable', 'on', 'span', 'span_seq', 'spanned',
           'clear', 'iter_spans', 'export_chrome_trace', 'Span',
           'record_span', 'spans_dropped', 'self_times']

_on = False                 # the master switch; module-global for one-load checks
_ring = []                  # preallocated record slots (None until written)
_cap = 0
_idx = 0                    # next write position
_total = 0                  # lifetime spans recorded (wraparound-aware)
_dropped_lifetime = 0       # spans evicted by wraparound, never reset
_lock = threading.Lock()    # guards ring writes only; reads copy under it
_ids = itertools.count(1)   # span ids (next() is one GIL-atomic call)
_open_spans = threading.local()   # .stack: this thread's open spans, outermost first
_annotation = None          # jax.profiler.TraceAnnotation while on, where JAX is
_gc_span = None             # the collection in flight (collections do not nest)

# a wrapped ring silently truncating a trace is the no-silent-caps rule's
# textbook violation: the health counter makes the loss countable, and
# export_chrome_trace emits a synthetic marker event so the Perfetto view
# itself discloses that older spans fell off
register_health_source('spans_dropped', lambda: _dropped_lifetime)


def on():
    """True when span recording is enabled (the fast-path guard)."""
    return _on


def _import_annotation():
    from jax.profiler import TraceAnnotation
    return TraceAnnotation


def enable(capacity=4096):
    """Turn span recording on with a bounded ring of `capacity` spans,
    bridge the spans into the JAX profiler's host plane (where JAX can be
    imported) and start recording collections as ``gc`` spans."""
    global _on, _ring, _cap, _idx, _total, _annotation
    try:
        _annotation = _import_annotation()
    except ImportError:
        _annotation = None
    with _lock:
        _ring = [None] * int(capacity)
        _cap = int(capacity)
        _idx = 0
        _total = 0
        _on = True
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)


def disable():
    """Turn span recording off. The ring is kept until enable() resets it
    so a forensic dump can still read the tail of a disabled trace."""
    global _on
    _on = False
    if _on_gc in gc.callbacks:
        gc.callbacks.remove(_on_gc)


def clear():
    """Drop every recorded span (keeps the enabled state and capacity)."""
    global _idx, _total
    with _lock:
        for i in range(_cap):
            _ring[i] = None
        _idx = 0
        _total = 0


def _cpu_now(is_root):
    """The calling thread's CPU ns at an edge of a root span; None under a
    root, where the clock (a system call) is not read."""
    return time.thread_time_ns() if is_root else None


def _cpu_between(cpu0, cpu1):
    return None if cpu0 is None or cpu1 is None else cpu1 - cpu0


def _record(name, t0, t1, cpu, attrs, error, ids, tid=None):
    global _idx, _total, _dropped_lifetime
    rec = (name, t0, t1,
           threading.get_ident() if tid is None else tid, attrs, error,
           cpu) + ids
    with _lock:
        if not _cap:
            return
        if _ring[_idx] is not None:
            _dropped_lifetime += 1
        _ring[_idx] = rec
        _idx = (_idx + 1) % _cap
        _total += 1


def _stack():
    try:
        return _open_spans.stack
    except AttributeError:
        _open_spans.stack = []
        return _open_spans.stack


def _new_ids(stack):
    """(id, parent, root) of a span opening under `stack`'s innermost."""
    sid = next(_ids)
    return (sid, stack[-1], stack[0]) if stack else (sid, None, sid)


def _open(name):
    """Open a span on this thread: ((id, parent, root), annotation)."""
    stack = _stack()
    ids = _new_ids(stack)
    stack.append(ids[0])
    annotation = None
    if _annotation is not None:
        annotation = _annotation(name)
        annotation.__enter__()
    return ids, annotation


def _close(sid, annotation):
    if annotation is not None:
        annotation.__exit__(None, None, None)
    stack = _stack()
    if stack and stack[-1] == sid:
        stack.pop()
    elif sid in stack:
        # a phase sequence left open above this span (never done()) must
        # not become the parent of what this thread opens next
        del stack[stack.index(sid):]


def record_span(name, t0_ns, t1_ns, tid=None, parent=None, **attrs):
    """Inject an externally-timed span into the ring. For phases measured
    outside Python — the native codec's pool workers time their parse
    slices against CLOCK_MONOTONIC, the same epoch ``perf_counter_ns``
    reads on Linux, so injected slices line up with host-phase spans in
    one Perfetto timeline. ``tid`` (default: calling thread) lets each
    worker render as its own track. ``parent`` is the live ``Span`` or
    ``SpanSeq`` the slice worked for; by default the innermost span open
    on the calling thread."""
    if not _on:
        return
    if parent is not None:
        ids = (next(_ids), parent.id, parent.root)
    else:
        ids = _new_ids(_stack())
    # timed by another thread: what THAT thread ran is not ours to read
    _record(name, t0_ns, t1_ns, None, attrs or None, None, ids, tid=tid)


class _Node:
    """What Span and SpanSeq share: the place in the tree of the span (or
    running phase), and its profiler annotation."""

    __slots__ = ('_ids', '_annotation')

    def __init__(self):
        self._ids = (None, None, None)
        self._annotation = None

    id = property(lambda self: self._ids[0])
    parent = property(lambda self: self._ids[1])
    root = property(lambda self: self._ids[2])


class Span(_Node):
    """A live span: records on close (including exceptional close, with
    the exception type attached as the ``error`` field — every begin has
    an end even when the guarded block raises). ``id``, ``parent`` and
    ``root`` are set once it is entered."""

    __slots__ = ('_name', '_t0', '_cpu0', '_attrs')

    def __init__(self, name, attrs):
        super().__init__()
        self._name = name
        self._attrs = attrs or None
        self._t0 = self._cpu0 = 0

    def __enter__(self):
        self._ids, self._annotation = _open(self._name)
        self._t0 = time.perf_counter_ns()
        self._cpu0 = _cpu_now(self._ids[1] is None)
        return self

    def set(self, **attrs):
        """Attach attributes discovered mid-span."""
        if self._attrs is None:
            self._attrs = {}
        self._attrs.update(attrs)
        return self

    def __exit__(self, exc_type, exc, tb):
        t1 = time.perf_counter_ns()
        cpu1 = _cpu_now(self._cpu0 is not None)
        _close(self._ids[0], self._annotation)
        _record(self._name, self._t0, t1, _cpu_between(self._cpu0, cpu1),
                self._attrs,
                exc_type.__name__ if exc_type is not None else None,
                self._ids)
        return False


class _NullSpan:
    """Shared do-nothing span returned while recording is off."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return False

    def set(self, **attrs):
        return self


def _on_gc(phase, info):
    """gc.callbacks hook, installed while recording is on: one ``gc`` span
    per collection, on the thread it ran on, under the span it stopped."""
    global _gc_span
    if phase == 'start':
        if _on:
            _gc_span = Span('gc', {'generation': info['generation']})
            _gc_span.__enter__()
    elif _gc_span is not None:
        gc_span, _gc_span = _gc_span, None
        gc_span.set(collected=info['collected'])
        gc_span.__exit__(None, None, None)


class SpanSeq(_Node):
    """Sequential phase spans: each mark() closes the running phase and
    opens the next at the same instant, so the phases tile the interval.
    The running phase is on the thread's stack of open spans like any
    other, so a sequence started inside a phase records that phase's
    children. ``id``, ``parent`` and ``root`` are the running phase's.
    ``mark()`` and ``done()`` return the instant they read (wall ns) and
    take one as ``at=`` (from another sequence's mark): a sequence that
    tiles a phase of another shares that phase's edges exactly."""

    __slots__ = ('_name', '_t0', '_cpu0', '_attrs')

    def __init__(self):
        super().__init__()
        self._name = None
        self._t0 = self._cpu0 = 0
        self._attrs = None

    def _finish(self, at, cpu, error):
        _close(self._ids[0], self._annotation)
        _record(self._name, self._t0, at, _cpu_between(self._cpu0, cpu),
                self._attrs, error, self._ids)
        self._name = None

    def _cpu_here(self):
        """The CPU clock at a mark: the next phase takes the running one's
        place in the tree, the first goes under what the thread has open."""
        return _cpu_now(self._ids[1] is None if self._name is not None
                        else not _stack())

    def mark(self, name, at=None, **attrs):
        if at is None:
            at = time.perf_counter_ns()
        cpu = self._cpu_here()
        if self._name is not None:
            self._finish(at, cpu, None)
        self._ids, self._annotation = _open(name)
        self._name = name
        self._t0, self._cpu0 = at, cpu
        self._attrs = attrs or None
        return at

    def note(self, **attrs):
        """Attach attributes to the running phase (recorded when the next
        mark() or done() closes it)."""
        if self._name is None:
            return
        if self._attrs is None:
            self._attrs = {}
        self._attrs.update(attrs)

    def done(self, error=None, at=None, **attrs):
        if self._name is None:
            return at
        self.note(**attrs)
        if at is None:
            at = time.perf_counter_ns()
        self._finish(at, self._cpu_here(), error)
        return at


class _NullSeq:
    __slots__ = ()

    def mark(self, name, at=None, **attrs):
        pass

    def note(self, **attrs):
        pass

    def done(self, error=None, at=None, **attrs):
        pass


_NULL = _NullSpan()
_NULL_SEQ = _NullSeq()


def span(name, **attrs):
    """Open a span. Off: returns the shared no-op context manager. On:
    returns a recording Span — use as ``with span('native_parse', n=5):``."""
    if not _on:
        return _NULL
    return Span(name, attrs)


def span_seq():
    """A sequential-phase recorder (see SpanSeq); no-op when off."""
    if not _on:
        return _NULL_SEQ
    return SpanSeq()


def spanned(name):
    """Decorator recording the whole call as one span. For per-batch
    seams only: the off cost is one flag check + two no-op calls per
    invocation, fine per batch, too much per op."""
    import functools

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with span(name):
                return fn(*args, **kwargs)
        return wrapper
    return deco


def iter_spans():
    """Recorded spans, oldest first, as dicts. ``thread_cpu_ns`` is the
    CPU time the span's own thread ran inside it (a root span; None under
    one and on a `record_span` slice); ``dur_ns`` less it is the time that
    thread was off the CPU.
    Copies the ring under the lock, so it is safe against concurrent
    recording."""
    with _lock:
        if _total >= _cap:
            raw = _ring[_idx:] + _ring[:_idx]
        else:
            raw = _ring[:_idx]
    out = []
    for rec in raw:
        if rec is None:
            continue
        name, t0, t1, tid, attrs, error, cpu, sid, parent, root = rec
        d = {'name': name, 't0_ns': t0, 't1_ns': t1,
             'dur_ns': t1 - t0, 'thread_cpu_ns': cpu, 'tid': tid,
             'id': sid, 'parent': parent, 'root': root}
        if attrs:
            d['attrs'] = dict(attrs)
        if error:
            d['error'] = error
        out.append(d)
    return out


def self_times(spans):
    """{id: ns} for iter_spans()-shaped dicts: each span's duration less
    the union of its children's intervals (clipped to its own), i.e. the
    time it spent in no narrower span. Children on other threads (the
    parse pool's slices) count: what they cover the parent waited for."""
    children = {}
    for s in spans:
        if s.get('parent') is not None:
            children.setdefault(s['parent'], []).append(
                (s['t0_ns'], s['t1_ns']))
    out = {}
    for s in spans:
        lo, hi = s['t0_ns'], s['t1_ns']
        covered, edge = 0, lo
        for c0, c1 in sorted(children.get(s['id'], ())):
            c0, c1 = max(c0, edge), min(c1, hi)
            if c1 > c0:
                covered += c1 - c0
                edge = c1
        out[s['id']] = (hi - lo) - covered
    return out


def span_count():
    """Lifetime spans recorded since enable()/clear() (past wraparound)."""
    return _total


def spans_dropped():
    """Spans evicted from the CURRENT ring by wraparound — the count of
    older spans an export of this ring is missing (0 = the ring holds
    the full trace). The 'spans_dropped' health counter is the lifetime
    total across enable()/clear() cycles."""
    return max(0, _total - _cap) if _cap else 0


def export_chrome_trace(path=None, pid=1):
    """The recorded spans as Chrome trace-event 'X' (complete) events —
    the JSON Perfetto / chrome://tracing load. Timestamps are the raw
    ``time.perf_counter_ns`` microseconds (not the JAX profiler's clock);
    host spans from one process share that clock, so phases nest
    correctly. ``args`` carries each span's ``id``, ``parent`` and
    ``root``, and its ``thread_cpu_ns`` where it has one. Returns the
    event list; writes ``{"traceEvents": [...]}`` to `path` when given."""
    events = []
    for rec in iter_spans():
        ev = {'ph': 'X', 'name': rec['name'], 'pid': pid,
              'tid': rec['tid'] % 1_000_000,
              'ts': rec['t0_ns'] / 1000.0,
              'dur': rec['dur_ns'] / 1000.0}
        args = dict(rec.get('attrs') or {})
        if rec.get('error'):
            args['error'] = rec['error']
        args.update(id=rec['id'], parent=rec['parent'], root=rec['root'])
        if rec['thread_cpu_ns'] is not None:
            args['thread_cpu_ns'] = rec['thread_cpu_ns']
        ev['args'] = args
        events.append(ev)
    dropped = spans_dropped()
    if dropped and events:
        # truncation disclosure (no-silent-caps): a wrapped ring means
        # this trace is a TAIL, not the run — say so inside the trace
        # itself, as an instant event at the surviving window's start
        events.insert(0, {
            'ph': 'I', 'name': 'spans_dropped', 'pid': pid, 'tid': 0,
            's': 'g', 'ts': events[0]['ts'],
            'args': {'dropped': dropped,
                     'note': 'span ring wrapped; this trace is the '
                             f'newest window only ({dropped} older '
                             'spans lost)'}})
    if path is not None:
        with open(path, 'w') as f:
            json.dump({'traceEvents': events,
                       'displayTimeUnit': 'ms'}, f, default=repr)
    return events
