"""Reduction of a JAX profiler trace (``.xplane.pb``) to device busy time,
the device time of each XLA module (a jitted program: ``jit_<kernel>``), the
operations that took most of it, and the idle gaps by what the host was
doing in them.  Kept with the benchmark so that every PR reads the same
number the same way; ``selfcheck`` runs it on a small recorded trace.

The traced stretch is marked by two host annotations, ``WINDOW_START`` and
``WINDOW_STOP``, each carrying the host's ``time.monotonic_ns()`` as the
stat ``t_ns``: they bound the window and tie the trace's clock to the
clock of the host samples.
"""

import bisect
import re

WINDOW_START, WINDOW_STOP = "bench_window_start", "bench_window_stop"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
FINGERPRINT = re.compile(r"\(\d+\)$")  # jit_bins_update(1234567890)
INSTRUCTION = re.compile(r"%?([A-Za-z0-9_.\-]+)")  # %fusion.2 = f32[...] ...
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
NO_MODULE = "no_module"  # an operation outside every module event
MIN_GAP_NS = 1_000_000  # shorter idle gaps are summed as "short_gaps"
TOP = 10


def _marks(profile):
    """{annotation name: (trace ns, host monotonic ns)}."""
    out = {}
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in (WINDOW_START, WINDOW_STOP):
                    stats = dict(ev.stats)
                    out[ev.name] = (int(ev.start_ns), int(stats["t_ns"]))
    return out


def _device_events(profile, line_name):
    """{device ordinal: [(start ns, end ns, name)]} of one line of the
    device planes, sorted by start."""
    out = {}
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        evs = []
        for line in plane.lines:
            if line.name == line_name:
                evs.extend((int(e.start_ns), int(e.start_ns + e.duration_ns),
                            e.name) for e in line.events)
        out[int(m.group(1))] = sorted(evs)
    return out


def _op_name(event_name):
    """``%dynamic-update-slice.7 = f32[...] ...`` -> the instruction's
    name: one operation of one program, summed over the sizes a kernel was
    compiled at (they share the module's name and, as a rule, these)."""
    m = INSTRUCTION.match(event_name)
    return m.group(1) if m else event_name


def _union(intervals):
    """Merged, sorted [start, end) intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _short(name):
    return re.sub(r"[^A-Za-z0-9_.:/\-]", "_", name)[:64]


def reduce(profile, host_samples=()):
    """``host_samples`` is [(host monotonic ns, label)], what the host's main
    thread was running at that time.  Returns busy_s (mean over the device
    planes), window_s, modules ({module: [seconds, dispatches]} of the
    ``XLA Modules`` line, clipped to the stretch like the operations, means
    over the device planes), device_ops (``<module>/<hlo instruction>``, the
    operation attributed to the module event that holds its start) and
    idle_gaps (lists of [name, seconds], at most ten each, the gaps of the
    busiest device's complement), or None where the trace holds no marked
    window or no device operation in it."""
    marks = _marks(profile)
    if WINDOW_START not in marks or WINDOW_STOP not in marks:
        return None
    w0, w1 = marks[WINDOW_START][0], marks[WINDOW_STOP][0]
    host_to_trace = marks[WINDOW_START][0] - marks[WINDOW_START][1]
    per_device = _device_events(profile, OPS_LINE)
    module_events = _device_events(profile, MODULES_LINE)
    busy, ops, modules, busiest = [], {}, {}, None
    for ordinal, evs in per_device.items():
        # the program's name without the fingerprint the profiler appends
        mods = [(s, e, FINGERPRINT.sub("", n))
                for s, e, n in module_events.get(ordinal, [])]
        starts = [s for s, _, _ in mods]
        clipped = []
        for s, e, n in evs:
            if e <= w0 or s >= w1:
                continue
            i = bisect.bisect_right(starts, s) - 1
            owner = mods[i][2] if i >= 0 and s < mods[i][1] else NO_MODULE
            clipped.append((max(s, w0), min(e, w1),
                            f"{owner}/{_op_name(n)}"))
        for s, e, n in mods:
            if e > w0 and s < w1:
                entry = modules.setdefault(n, [0, 0])
                entry[0] += min(e, w1) - max(s, w0)
                entry[1] += 1
        merged = _union((s, e) for s, e, _ in clipped)
        busy_ns = sum(e - s for s, e in merged)
        busy.append(busy_ns)
        if busiest is None or busy_ns > busiest[0]:
            busiest = (busy_ns, merged)
        for s, e, n in clipped:
            ops[n] = ops.get(n, 0) + (e - s)
    if not busy or max(busy) <= 0 or w1 <= w0:
        return None
    edges = [w0] + [t for s, e in busiest[1] for t in (s, e)] + [w1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    samples = sorted((t + host_to_trace, label) for t, label in host_samples)
    times = [t for t, _ in samples]
    by_label = {}
    for g0, g1 in gaps:
        if g1 - g0 < MIN_GAP_NS:
            label_ns = {"short_gaps": g1 - g0}
        else:
            inside = [lab for _, lab in samples[
                bisect.bisect_left(times, g0):bisect.bisect_left(times, g1)]]
            share = (g1 - g0) / len(inside) if inside else 0
            label_ns = {}
            for lab in inside or ["unsampled"]:
                label_ns[lab] = label_ns.get(lab, 0) + (share or g1 - g0)
        for lab, ns in label_ns.items():
            by_label[lab] = by_label.get(lab, 0) + ns
    n_dev = len(busy)

    def top(table, scale):
        ranked = sorted(table.items(), key=lambda kv: -kv[1])[:TOP]
        return [[_short(k), v / scale] for k, v in ranked]

    return {"busy_s": sum(busy) / n_dev / 1e9, "window_s": (w1 - w0) / 1e9,
            "devices": n_dev,
            "modules": {k: [ns / 1e9 / n_dev, n / n_dev]
                        for k, (ns, n) in modules.items()},
            "device_ops": top(ops, 1e9 * n_dev),
            "idle_gaps": top(by_label, 1e9)}


def reduce_file(path, host_samples=()):
    import jax

    return reduce(jax.profiler.ProfileData.from_file(path), host_samples)
