"""Shared by the readers that take the program's span TREE apart
(observability/spans.py; PR 39): a call of `apply_changes_docs` is its root
span `apply_batch` and every span that carries its id as `root`; the call is
split at its first device enqueue into what the chip waits for and what it
overlaps; a root span's `thread_cpu_ns` beside its `dur_ns` says how long
its thread was off the CPU. Everything is read from spans wholly inside the
measured window, per step of the driver, and is None where the ring holds
nothing to read (a program without the span or the field)."""

# the spans that hand the device its work: one a size class of a sequence
# dispatch, one for the grid kernel's (or the register engine's) call
ENQUEUE = ('seq.enqueue', 'dispatch.enqueue')


def window_spans(ctx):
    """(the spans wholly inside the window, its steps), or ((), None)."""
    window = ctx['facts'].get('window_ns')
    steps = ctx['facts'].get('steps')
    if not window or not steps:
        return (), None
    return [span for span in ctx['spans']
            if span['t0_ns'] >= window[0] and span['t1_ns'] <= window[1]], \
        steps


def calls(spans):
    """The calls among `spans`: the root spans named `apply_batch`."""
    return [span for span in spans if span['name'] == 'apply_batch' and
            span.get('parent', 0) is None]


def call_ms_per_step(ctx):
    """Milliseconds a step spends inside calls."""
    spans, steps = window_spans(ctx)
    roots = calls(spans)
    if not roots:
        return None
    return sum(span['dur_ns'] for span in roots) / 1e6 / steps


def split_ms_per_step(ctx, side):
    """The split of a call at its enqueue, summed over the calls that made
    one: `pre`, from the call's start to the START of its first enqueue
    span (the chip has nothing of this call yet: a step counts after the
    driver's block, so it waits); `post`, from the END of its last enqueue
    span to the call's end (the chip works under it). A call without an
    enqueue has no split and is left out; None where no call has one."""
    spans, steps = window_spans(ctx)
    enqueues = {}
    for span in spans:
        if span['name'] in ENQUEUE:
            enqueues.setdefault(span.get('root'), []).append(span)
    total, found = 0, False
    for call in calls(spans):
        mine = enqueues.get(call['id'])
        if not mine:
            continue
        found = True
        if side == 'pre':
            total += min(span['t0_ns'] for span in mine) - call['t0_ns']
        else:
            total += call['t1_ns'] - max(span['t1_ns'] for span in mine)
    return total / 1e6 / steps if found else None


def call_offcpu_ms_per_step(ctx):
    """Wall time less the thread's CPU time of the calls: what the calling
    thread spent off the CPU inside them (descheduled, or waiting on a
    lock, the parse pool, the device). A call without the CPU clock (a
    program from before PR 39) is skipped, not counted as zero; None where
    none has it."""
    spans, steps = window_spans(ctx)
    total, found = 0, False
    for span in calls(spans):
        if span.get('thread_cpu_ns') is not None:
            total += span['dur_ns'] - span['thread_cpu_ns']
            found = True
    return total / 1e6 / steps if found else None


def counter_per_step(ctx, name):
    """A `DocFleet.metrics` counter's movement over the window, per step;
    None from a program that does not keep it."""
    counters = ctx['facts'].get('fleet_counters') or {}
    steps = ctx['facts'].get('steps')
    if name not in counters or not steps:
        return None
    return counters[name] / steps
