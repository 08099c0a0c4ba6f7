"""Self-checks of the yardstick's own arithmetic, run by ``rehearse.py`` and
by the tests beside it (never by the repo's tier-1 tests): the contract's
limits on ``BENCHMARK.json``, the tick arithmetic on a synthetic arrival log,
the trace reducer and the roofline reader on a small trace recorded on the
chip, the controls, and the yardstick's generator copy against the program's
source."""

import json
import os
import re

from harness import compare, readers, spec, ticks as tk, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _load(name):
    with open(os.path.join(HERE, name)) as f:
        return json.load(f)


def manifest():
    """The limits of the contract that a typo would break."""
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        text = f.read()
    assert len(text.encode()) <= 64 * 1024
    b = json.loads(text)
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}, sorted(b)
    assert 1 <= b["run_seconds"] <= 51 and isinstance(b["run_seconds"], int)
    configs = {c["name"]: c for c in b["configs"]}
    cells = {w["name"] for w in b["workloads"]}
    e2e = {m["name"] for m in b["end_to_end"]}
    assert "setup_s" in e2e
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}, c
        assert NAME.match(c["name"]) and len(c["source"]) <= 200
        assert len(c["why"]) <= 200 and len(c["reduced"]) <= 16
        assert c["file"].startswith(tuple(p + "/" for p in b["paths"]))
        with open(os.path.join(spec.ROOT, c["file"])) as f:
            stated = json.load(f)
        assert stated["reduced"] == c["reduced"], c["name"]
        assert stated["source"] == c["source"], c["name"]
        for key in c["reduced"]:
            assert NAME.match(key) and key in stated, key
    assert len({c["file"] for c in b["configs"]}) == len(configs)
    used = set()
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}, w
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200, (w["name"], len(w["why"]))
        used.add(w["config"])
        cell = spec.load_cell(w["name"])  # every file it names is there
        assert cell.end_to_end and cell.per_layer, w["name"]
    assert used == set(configs), "a configuration no cell uses"
    four = sum(w["chips"] == 4 for w in b["workloads"])
    assert four <= max(len(b["workloads"]) // 2, 1)
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}, m
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}, m
        assert m["moves"] in e2e and len(m["layer"]) <= 200
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert set(m.get("workloads", ())) <= cells
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(names) == len(set(names))


def ticks():
    """``arrivals.json``: a synthetic arrival log, one window delivered in two
    batches, with the window the arithmetic has to find."""
    case = _load("arrivals.json")
    ends = [int(e * 1e6) for e, _ in case["log"]]
    ats = [a for _, a in case["log"]]
    tick_map = tk.ticks(ends, ats)
    origin = int(case["origin_s"] * 1e6)
    assert tk.close_of(tick_map, origin, 1e9) is None
    w = tk.measure(tick_map, origin, case["seconds"], case["event_rate"])
    want = case["want"]
    assert w.close_end == int(want["close_s"] * 1e6), w
    assert w.events == want["events"] and len(w.periods) == want["periods"]
    assert abs(w.seconds - want["seconds"]) < 1e-9, w.seconds
    assert abs(w.events_per_s - want["events_per_s"]) < 1e-6
    assert sum(p[2] for p in w.periods) == w.events
    assert abs(sum(p[3] for p in w.periods) - w.seconds) < 1e-9


def trace_reducer():
    """``sample.xplane.pb``: ``record_trace.py``'s known pattern of device work
    and host sleeps, recorded on the chip, with what the host saw of it."""
    want = _load("sample_trace.json")
    got = trace_reduce.reduce_file(
        os.path.join(HERE, "sample.xplane.pb"),
        [tuple(s) for s in want["host_samples"]])
    assert got is not None and got["devices"] == want["devices"]
    assert abs(got["window_s"] - want["host_window_s"]) < 0.02, got
    assert 0 < got["busy_s"] < got["window_s"]
    gaps = sum(s for _, s in got["idle_gaps"])
    assert abs(gaps + got["busy_s"] - got["window_s"]) < 1e-6, got
    # the host slept between the rounds, so the device idled at least so long
    assert got["window_s"] - got["busy_s"] >= want["host_slept_s"] * 0.9
    assert len(got["device_ops"]) >= 1
    for key in ("busy_s", "window_s"):
        assert abs(got[key] - want["reduced"][key]) < 1e-9, (key, got[key])
    # by module: the modules' seconds are their operations' (three ops, so
    # the top ten hold them all), and every operation is named by its module
    by_module = sum(s for s, _ in got["modules"].values())
    by_op = sum(s for _, s in got["device_ops"])
    assert abs(by_module - by_op) <= 0.01 * by_op, (by_module, by_op)
    assert all(n >= 1 for _, n in got["modules"].values()), got["modules"]
    for name, _ in got["device_ops"]:
        assert name.split("/")[0] in got["modules"], name


def roofline():
    """``module_roofline`` over the sample trace: work that needs half of the
    module's device seconds at the peak reads 50 %; a module that never ran,
    a device without peaks, or counters never read at the marks, nothing."""
    trace = trace_reduce.reduce_file(os.path.join(HERE, "sample.xplane.pb"))
    seconds = trace["modules"]["jit__lambda"][0]
    metric = {"reader": "module_roofline", "module": "jit__lambda",
              "peak": "bytes_per_s", "work": {"cells": 8, "rows": 2}}
    assert readers.counter_names(metric) == ["cells", "rows"]
    obs = {"trace": trace, "peaks": {"bytes_per_s": 10 / seconds},
           "counters_trace_start": {"cells": 7, "rows": 1},
           "counters_trace_stop": {"cells": 7.5, "rows": 1.5}}
    assert abs(readers.read(metric, obs) - 50.0) < 1e-9
    for broken in (dict(obs, peaks=None), dict(obs, trace=None),
                   dict(obs, counters_trace_stop=obs["counters_trace_start"]),
                   {k: v for k, v in obs.items()
                    if k != "counters_trace_stop"}):
        assert readers.read(metric, broken) is None, broken
    assert readers.read(dict(metric, module="jit_never_ran"), obs) is None


def generator():
    """Every family of ``references/nexmark_gen.py`` (bids for q5; persons
    and auctions wait for q8, PERF.md Open questions) equals, event for
    event, what the program's source draws for the same seed."""
    import numpy as np

    from arroyo_tpu.connectors.nexmark import (NexmarkConfig,
                                               NexmarkGenerator, make_splits)
    from references import nexmark_gen

    base, rate, size, n = 1_700_000_000_000_000, 1_000_000, 8192, 40_000
    for seed in (0, 2_147_483_999):
        cfg = NexmarkConfig(event_rate=rate, num_events=n, seed=seed,
                            generate_strings=False)
        theirs = NexmarkGenerator(cfg, base, *make_splits(cfg, base, 1)[0],
                                  seed=seed)
        theirs.set_rate(rate, 1)
        for mine in nexmark_gen.batches(
                seed, n, size, base, rate, before_micros=base + 10**12,
                families=("person", "auction", "bid")):
            batch, _ = theirs.next_batch(size)
            assert np.array_equal(mine.pop("ts"), batch.timestamp), seed
            for column, values in mine.items():
                assert np.array_equal(values, batch.columns[column]), column


def controls():
    """A reference that breaks exactly-once as the configuration's
    ``controls`` say (a batch delivered twice; half a batch left out) has to
    come out as not correct; the reference itself as correct."""
    for w in spec.manifest()["workloads"]:
        cell = spec.load_cell(w["name"], rehearsal=True)
        base = cell.config["stream"]["base_time_micros"]
        for seed in (0, 1, 2_147_483_999):
            t_end = base + 40_000_000
            stream = cell.reference_stream(seed, t_end)
            want = cell.reference.rows(stream, t_end)
            assert compare.verdict(compare.compare(want, want))
            k = 30 + seed % 50
            for fault in cell.config["controls"]:
                broken = cell.reference.rows(stream, t_end, **{fault: k})
                numbers = compare.compare(broken, want)
                assert not compare.verdict(numbers), (w["name"], fault)
