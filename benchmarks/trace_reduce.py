"""From a profiler trace (.xplane.pb) to what the metrics read: the device's
busy intervals, device time per XLA module and per op by name, and the idle
gaps labelled by what the host was doing. Uses nothing but
``jax.profiler.ProfileData``.

What a v5e trace looks like (read by hand, traces/bench fixtures and my
chip runs): one plane ``/device:TPU:<n>`` per chip with the lines ``XLA
Modules`` (one event per executed program, named ``jit_<fn>(<hash>)``) and
``XLA Ops`` (the program's fusions, copies and scatters, named by their HLO
text ``%name = ...``); host threads are lines of the plane ``/host:CPU``,
where a ``jax.profiler.TraceAnnotation`` shows under its own name. Device
and host events share one clock.
"""

import numpy as np

DEVICE_PREFIX = '/device:TPU:'
HOST_PLANE = '/host:CPU'


def merge(intervals):
    """Union of (start, end) intervals as a sorted list of disjoint ones."""
    out = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1][1] = end
        else:
            out.append([start, end])
    return out


def short_name(name):
    """``%fusion.3 = s32[...] fusion(...)`` -> ``%fusion.3``;
    ``jit_f(123)`` -> ``jit_f``."""
    name = name.split(' = ')[0]
    if name.endswith(')') and '(' in name:
        name = name[:name.rindex('(')]
    return name


def line_events(plane, line_name):
    for line in plane.lines:
        if line.name == line_name:
            return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events]
    return []


def label_gap(gap, annotations):
    """The annotation that covers most of the gap (the shortest such one,
    so the innermost of nested ones), or 'unannotated'. `annotations` is
    (names, starts, ends) with numpy arrays sorted shortest first."""
    names, starts, ends = annotations
    if not names:
        return 'unannotated'
    cover = np.minimum(ends, gap[1]) - np.maximum(starts, gap[0])
    best = int(np.argmax(cover))
    return names[best] if cover[best] > 0 else 'unannotated'


def annotation_arrays(events):
    """(names, starts, ends) of (name, start, end) events, shortest first."""
    events = sorted(events, key=lambda a: a[2] - a[1])
    return ([a[0] for a in events],
            np.array([a[1] for a in events], dtype=np.float64),
            np.array([a[2] for a in events], dtype=np.float64))


def reduce_trace(path, annotation_names=()):
    """{'busy_s', 'devices', 'modules': {name: [count, s]},
    'top_ops': [[name, s]...], 'idle_gaps': [[label, s]...]}.

    busy_s is the union of the device's op intervals, averaged over the
    device planes. idle_gaps sums, per label, the gaps between busy intervals of
    the first device, labelled by the benchmark's own TraceAnnotations
    (``annotation_names``), longest first."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    wanted = set(annotation_names)
    annotations = []
    busy_by_device = []
    modules = {}
    ops = {}
    gaps_by_label = {}
    for plane in data.planes:
        if plane.name == HOST_PLANE and wanted:
            for line in plane.lines:
                annotations.extend(
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events if e.name in wanted)
    annotations = annotation_arrays(annotations)
    for plane in data.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        module_events = line_events(plane, 'XLA Modules')
        op_events = line_events(plane, 'XLA Ops') or module_events
        for name, start, end in module_events:
            row = modules.setdefault(short_name(name), [0, 0.0])
            row[0] += 1
            row[1] += (end - start) / 1e9
        for name, start, end in op_events:
            key = short_name(name)
            ops[key] = ops.get(key, 0.0) + (end - start) / 1e9
        busy = merge((s, e) for _n, s, e in op_events)
        busy_by_device.append(sum(e - s for s, e in busy) / 1e9)
        if len(busy_by_device) == 1:
            for before, after in zip(busy, busy[1:]):
                gap = (before[1], after[0])
                label = label_gap(gap, annotations)
                gaps_by_label[label] = gaps_by_label.get(label, 0.0) + \
                    (gap[1] - gap[0]) / 1e9
    if not busy_by_device:
        raise ValueError(f'{path}: no {DEVICE_PREFIX}* plane in the trace')
    return {
        'busy_s': sum(busy_by_device) / len(busy_by_device),
        'devices': len(busy_by_device),
        'modules': modules,
        'top_ops': [[n, s] for n, s in sorted(ops.items(),
                                              key=lambda kv: -kv[1])],
        'idle_gaps': [[n, s] for n, s in sorted(gaps_by_label.items(),
                                                key=lambda kv: -kv[1])],
    }
