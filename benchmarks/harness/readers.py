"""The few generic readers that per-layer metrics are made of.  A metric's
file names one with its arguments; a reader that finds nothing to read
returns None and the metric is left out of the line."""

import statistics


def counter_names(spec):
    """The program counters a metric's reader wants snapshotted at the
    ticks ('@...' names are the harness's own quantities)."""
    if spec["reader"] == "module_roofline":
        return list(spec["work"])
    if spec["reader"] != "counter_ratio":
        return []
    return [n for n in spec["num"] + spec["den"] if not n.startswith("@")]


def _total(names, obs):
    own = {"@events_mev": obs["window"].events / 1e6, "@one": 1}
    return sum(own[n] if n in own
               else obs["counters_close"][n] - obs["counters_origin"][n]
               for n in names)


def counter_ratio(spec, obs):
    """scale x sum(num) / sum(den) of counter deltas between the ticks."""
    den = _total(spec["den"], obs)
    if den == 0:
        return None
    return spec.get("scale", 1) * _total(spec["num"], obs) / den


def phase_per_mev(spec, obs):
    """Seconds of the program's profiler phases between the ticks, per
    million events: the phases listed under ``include``, or every work phase
    but those under ``exclude``."""
    if obs["phases_origin"] is None:
        return None
    total = 0.0
    for (op, phase), secs in obs["phases_close"].items():
        if ("include" in spec and phase not in spec["include"]
                or phase in spec.get("exclude", ())):
            continue
        total += secs - obs["phases_origin"].get((op, phase), 0.0)
    return total / (obs["window"].events / 1e6) if total > 0 else None


def span_per_period(spec, obs):
    """Per period between two ticks, the summed milliseconds of the program's
    flight-recorder spans of that name that ended in it; the percentile asked
    for over the periods."""
    periods = obs["window"].periods
    if not periods or not obs["spans"]:
        return None
    sums = []
    for _, at, _, secs in periods:
        lo = at - secs
        sums.append(sum(dur for end, dur in obs["spans"].get(spec["span"], ())
                        if lo < end <= at) * 1e3)
    if not any(sums):
        return None
    if spec["percentile"] == 50:
        return statistics.median(sums)
    return statistics.quantiles(sums, n=100)[spec["percentile"] - 1]


def memory_stat(spec, obs):
    """A key of ``memory_stats()`` on the fullest device, scaled."""
    values = [m[spec["key"]] for m in obs["memory"] if spec["key"] in m]
    return max(values) * spec.get("scale", 1) if values else None


def trace_field(spec, obs):
    """A quantity of the reduced device trace."""
    t = obs["trace"]
    if t is None:
        return None
    if spec["field"] == "idle_share_pct":
        return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
    return t[spec["field"]]


def module_roofline(spec, obs):
    """A kernel's share of one peak of the chip, in percent: the least
    seconds its work could take (``work``: program counters, each with the
    bytes or operations one unit of it needs whatever implements it, over
    the ``peak`` of ``peaks.json``) over the device seconds of the XLA module
    ``module``.  Work and seconds are both of the traced stretch: the
    counters' deltas between the two trace marks."""
    t, peaks = obs["trace"], obs["peaks"]
    start, stop = (obs.get(k) for k in ("counters_trace_start",
                                        "counters_trace_stop"))
    if (None in (t, peaks, start, stop)
            or spec["module"] not in t["modules"]):
        return None
    seconds = t["modules"][spec["module"]][0]
    work = sum(per_unit * (stop[n] - start[n])
               for n, per_unit in spec["work"].items())
    if work <= 0 or seconds <= 0:
        return None
    return 100.0 * work / (peaks[spec["peak"]] * t["devices"]) / seconds


def compile_listener(spec, obs):
    """XLA compile seconds (or requests) between the ticks."""
    count, secs, _ = obs["compiles"]
    return {"seconds": secs, "count": count}[spec["field"]]


READERS = {f.__name__: f for f in (counter_ratio, phase_per_mev,
                                   span_per_period, memory_stat, trace_field,
                                   module_roofline, compile_listener)}


def read(spec, obs):
    return READERS[spec["reader"]](spec, obs)
