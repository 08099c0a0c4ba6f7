"""Nexmark benchmark suite over the SQL-planned engine on the available
accelerator (BASELINE.md configs):

  q1  stateless currency-conversion map over bids
  q5  hot items: sliding-window count + windowed max join   [headline]
  q7  highest bid: tumbling global max joined back to bids
  q8  monitor new users: persons joined to their auctions per window

Prints ONE JSON line {"metric", "value", "unit", "vs_baseline", "device"}
for the query named by BENCH_QUERY (default q5, the headline the driver
records).  BENCH_ALL=1 (default) runs every query, each in its own child,
and embeds the non-headline results under ``queries``.

The process started by ``python bench.py`` is a supervisor that never
imports JAX: a chip belongs to one process at a time, so it is the only
parent and runs every chip-holding child in sequence.  Without a TPU the
children fail — and so does the run, with no metric line — unless the
caller set ``JAX_PLATFORMS=cpu`` in so many words; any failed child or
family makes the exit code non-zero.  The benchmark sets no engine
placement switch: what it measures is the default configuration.

``--autoscale`` runs the elasticity benchmark instead: an impulse flood
through a real controller with the closed-loop autoscaler enabled, the
JSON line carrying the decision timeline and throughput-vs-parallelism
samples (``autoscale`` key) rather than a steady-state headline.

Baseline: the reference publishes no numbers and its Rust CPU backend
cannot run in this image (no cargo toolchain, BASELINE.md) — so
``vs_baseline`` is measured against an honest, clearly-labeled CONTROL:
a straightforward single-thread numpy implementation of the same query
semantics over the same generator stream, timed in-process right before
the engine runs (see ``CONTROLS``).  The control is the "what you'd
write without the engine" number, not the reference.  BENCH_CONTROL=0
skips it (vs_baseline then omitted).
"""

import json
import os
import subprocess
import sys
import tempfile
import time

NUM_EVENTS = int(os.environ.get("BENCH_EVENTS", 2_000_000))
# 128k-row batches measured consistently >= 64k on q5/q7/q8 (fewer
# per-batch host passes, fewer and larger transfers)
BATCH = int(os.environ.get("BENCH_BATCH", 131072))


def bench_device() -> dict:
    """The device this process measures on, as JAX reports it — every
    result line carries it.  A benchmark process that finds no TPU
    fails, unless the caller chose the CPU in so many words
    (``JAX_PLATFORMS=cpu``): a number from a CPU run is then labelled
    ``platform: cpu`` and is not a device metric."""
    import jax

    from arroyo_tpu.config import require_backend

    platform = require_backend()
    if platform != "tpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
        raise SystemExit(
            f"bench: backend is {platform!r}, not 'tpu', and the caller "
            "did not set JAX_PLATFORMS=cpu — no result")
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


SRC = """
CREATE TABLE nexmark WITH (
  connector = 'nexmark', event_rate = '1000000',
  num_events = '{n}', rate_limited = 'false', batch_size = '{b}'
);
"""

Q1 = SRC + """
SELECT bid.auction as auction, bid.bidder as bidder,
       bid.price * 0.908 as price_dol, bid.datetime as datetime
FROM nexmark WHERE bid is not null
"""

Q5 = SRC + """
WITH bids as (SELECT bid.auction as auction, bid.datetime as datetime
    FROM nexmark where bid is not null)
SELECT AuctionBids.auction as auction, AuctionBids.num as num
FROM (
  SELECT B1.auction, HOP(INTERVAL '2' SECOND, INTERVAL '10' SECOND)
         as window, count(*) AS num
  FROM bids B1 GROUP BY 1, 2
) AS AuctionBids
JOIN (
  SELECT max(num) AS maxn, window
  FROM (
    SELECT count(*) AS num,
           HOP(INTERVAL '2' SECOND, INTERVAL '10' SECOND) AS window
    FROM bids B2 GROUP BY B2.auction, 2
  ) AS CountBids
  GROUP BY 2
) AS MaxBids
ON AuctionBids.num = MaxBids.maxn and AuctionBids.window = MaxBids.window
"""

Q7 = SRC + """
WITH bids as (SELECT bid.auction as auction, bid.price as price,
                     bid.bidder as bidder, bid.datetime as datetime
    FROM nexmark where bid is not null)
SELECT B.auction as auction, B.price as price, B.bidder as bidder
FROM bids B
JOIN (
  SELECT max(price) AS maxprice, TUMBLE(INTERVAL '10' SECOND) as window
  FROM bids GROUP BY 2
) AS M
ON B.price = M.maxprice
WHERE B.datetime >= M.window_start AND B.datetime < M.window_end
"""

Q8 = SRC + """
SELECT P.id as id, P.np as np, A.na as na
FROM (
  SELECT person.id as id, TUMBLE(INTERVAL '10' SECOND) as window,
         count(*) as np
  FROM nexmark WHERE person is not null GROUP BY 1, 2
) AS P
JOIN (
  SELECT auction.seller as seller, TUMBLE(INTERVAL '10' SECOND) as window,
         count(*) as na
  FROM nexmark WHERE auction is not null GROUP BY 1, 2
) AS A
ON P.id = A.seller and P.window = A.window
"""

QUERIES = {"q1": Q1, "q5": Q5, "q7": Q7, "q8": Q8}


# -- measured single-thread control (the honest vs_baseline denominator) -----


def _control_events(n: int, want):
    """Generate the bench's nexmark stream once (same generator, same
    seed/proportions as the engine's source) and return the raw column
    arrays the controls aggregate."""
    import numpy as np

    from arroyo_tpu.connectors.nexmark import (
        NexmarkConfig,
        NexmarkGenerator,
        make_splits,
    )

    cfg = NexmarkConfig(num_events=n, rate_limited=False,
                        batch_size=BATCH, projection=list(want))
    split = make_splits(cfg, 0, 1)[0]
    gen = NexmarkGenerator(cfg, 0, split[0], split[1], split[2], seed=0)
    gen.set_rate(cfg.event_rate, 1)
    cols = {c: [] for c in want}
    cols["event_type"] = []
    ts_parts = []
    while gen.has_next:
        batch, _ = gen.next_batch(BATCH)
        for c in cols:
            cols[c].append(np.asarray(batch.columns[c]))
        ts_parts.append(batch.timestamp)
    out = {c: np.concatenate(v) for c, v in cols.items()}
    out["__ts"] = np.concatenate(ts_parts)
    return out


def _group_counts(keys, ends):
    """Single-thread (key, window_end) counts via lexsort+reduceat.
    Returns (uniq_keys, uniq_ends, counts)."""
    import numpy as np

    order = np.lexsort((ends, keys))
    k, e = keys[order], ends[order]
    first = np.ones(len(k), dtype=bool)
    first[1:] = (k[1:] != k[:-1]) | (e[1:] != e[:-1])
    starts = first.nonzero()[0]
    cnt = np.diff(np.append(starts, len(k)))
    return k[starts], e[starts], cnt


def _hop_expand(ts, slide, width):
    import numpy as np

    W = width // slide
    first_end = (ts // slide + 1) * slide
    return (first_end[:, None]
            + (np.arange(W, dtype=np.int64) * slide)[None, :])


def control_q5(n: int) -> int:
    """q5 semantics, single thread: hop-window counts per auction, per-
    window max, emit (auction, window) rows whose count equals the max."""
    import numpy as np

    ev = _control_events(n, ("bid_auction",))
    bid = ev["event_type"] == 2  # EVENT_BID
    auc = ev["bid_auction"][bid]
    ts = ev["__ts"][bid]
    ends = _hop_expand(ts, 2_000_000, 10_000_000)
    W = ends.shape[1]
    k, e, cnt = _group_counts(np.repeat(auc, W), ends.reshape(-1))
    # max count per window, then the equi-join back
    order = np.lexsort((cnt, e))
    es, cs = e[order], cnt[order]
    last = np.ones(len(es), dtype=bool)
    last[:-1] = es[1:] != es[:-1]
    uw, umax = es[last], cs[last]
    idx = np.searchsorted(uw, e)
    return int(np.sum(cnt == umax[idx]))


def control_q1(n: int) -> int:
    import numpy as np

    ev = _control_events(n, ("bid_auction", "bid_bidder", "bid_price"))
    bid = ev["event_type"] == 2
    price_dol = ev["bid_price"][bid] * 0.908
    return int(np.sum(price_dol >= 0))


def control_q7(n: int) -> int:
    import numpy as np

    ev = _control_events(n, ("bid_auction", "bid_price", "bid_bidder"))
    bid = ev["event_type"] == 2
    price, ts = ev["bid_price"][bid], ev["__ts"][bid]
    wend = (ts // 10_000_000 + 1) * 10_000_000
    order = np.lexsort((price, wend))
    ws, ps = wend[order], price[order]
    last = np.ones(len(ws), dtype=bool)
    last[:-1] = ws[1:] != ws[:-1]
    uw, umax = ws[last], ps[last]
    idx = np.searchsorted(uw, wend)
    return int(np.sum(price == umax[idx]))


def control_q8(n: int) -> int:
    import numpy as np

    ev = _control_events(n, ("person_id", "auction_seller"))
    ts = ev["__ts"]
    person, auction = ev["event_type"] == 0, ev["event_type"] == 1
    wend_p = (ts[person] // 10_000_000 + 1) * 10_000_000
    wend_a = (ts[auction] // 10_000_000 + 1) * 10_000_000
    pk, pe, pc = _group_counts(ev["person_id"][person], wend_p)
    ak, ae, ac = _group_counts(ev["auction_seller"][auction], wend_a)
    pa = set(zip(pk.tolist(), pe.tolist()))
    return sum(1 for s, w in zip(ak.tolist(), ae.tolist()) if (s, w) in pa)


CONTROLS = {"q1": control_q1, "q5": control_q5, "q7": control_q7,
            "q8": control_q8}


def run_control(name: str) -> dict:
    """Time the single-thread numpy control of query ``name`` over the
    same generated stream (generation included, as it is for the engine).
    Returns {} when disabled or unavailable."""
    if os.environ.get("BENCH_CONTROL", "1") in ("0", "false", "no"):
        return {}
    fn = CONTROLS.get(name)
    if fn is None:
        return {}
    n = min(NUM_EVENTS, int(os.environ.get("BENCH_CONTROL_EVENTS",
                                           1_000_000)))
    fn(min(n, 20_000))  # warmup: one-time imports/allocator costs, same
    # courtesy the engine run gets from its warm pass
    t0 = time.perf_counter()
    n_out = fn(n)
    dt = time.perf_counter() - t0
    assert n_out > 0, f"control {name} produced no output"
    return {"control_events_per_sec": round(n / dt, 1),
            "control": "numpy-singlethread",
            "control_events": n}


JOIN_STATE_COUNTERS = (
    "join_state_merges", "join_state_resorts", "join_state_compactions",
    "join_state_promotions", "join_state_demotions",
    "join_state_device_merges", "join_state_ring_regrows",
    "join_device_gather_rows", "join_host_gather_rows",
)

SESSION_COUNTERS = (
    "session_merge_dispatches", "session_merge_device_dispatches",
    "session_device_merge_rows", "session_host_merge_rows",
    "udaf_channel_rows", "udaf_host_rows",
)


def _gather_share(stats: dict) -> dict:
    """Device-gather share of materialized join rows (PR 15's payload
    residency as a measured number): rows emitted through resident
    payload planes over all rows emitted.  ``None`` when the run
    materialized no join rows at all."""
    dev = stats.get("join_device_gather_rows", 0)
    host = stats.get("join_host_gather_rows", 0)
    return {"device_gather_share":
            (round(dev / (dev + host), 4) if dev + host else None)}


def bench_parallelism() -> int:
    """Subtasks per operator for the throughput runs.  The in-process
    LocalRunner executes EVERY subtask on one event-loop thread — only
    XLA kernels and executor-offloaded source generation release the
    GIL — so extra subtasks add shuffle hops and queue churn without
    adding compute: measured on a 2-core box, q5/q7/q8 all run ~1.7-1.8x
    FASTER at parallelism 1 than 2 (r06).  Default to 1; distributed
    multi-worker runs (where parallelism means real cores) set
    BENCH_PARALLELISM explicitly."""
    env = os.environ.get("BENCH_PARALLELISM")
    if env:
        return max(1, int(env))
    return 1


def operator_flight_stats(before: dict, after: dict) -> dict:
    """Per-operator deltas of the flight-recorder counters across the
    timed runs (obs.metrics.job_operator_summary snapshots): where the
    kernel seconds, backpressure stalls, and per-batch latency landed —
    the per-operator breakdown the driver reads to see WHICH operator a
    regression lives in, not just that events/s moved."""
    ops = {}
    for op, cur in after.items():
        prev = before.get(op, {})
        d = {k: v - prev.get(k, 0.0) for k, v in cur.items()}
        row = {}
        for key, out in (("kernel_seconds_total", "kernel_seconds"),
                         ("backpressure_seconds_total",
                          "backpressure_seconds"),
                         ("messages_sent_total", "messages_sent")):
            if d.get(key, 0.0) > 0:
                row[out] = round(d[key], 4)
        for fam, out in (("batch_processing_seconds", "batch_latency_avg"),
                         ("event_time_lag_seconds", "event_time_lag_avg")):
            c = d.get(fam + "_count", 0.0)
            if c > 0:
                row[out] = round(d.get(fam + "_sum", 0.0) / c, 6)
        if row:
            ops[op] = row
    return ops


def preflight_validate(prog, metric: str) -> int:
    """Plan-validator pre-flight: a benchmark pipeline that fails
    graph-level validation OR shardcheck's sharding/transfer
    verification must exit non-zero with its diagnostics and NO metric
    line, not run to a recorded 0 events/s (the round-5 failure mode was
    exactly a broken pipeline scoring zero silently).  Returns the plan
    report's ``predicted_reshards`` so the bench line can carry the
    static prediction next to the measured ``mesh.reshards`` counter —
    the same pairing the smoke drift gate asserts on."""
    from arroyo_tpu.analysis.plan_validator import errors_of, plan_report

    rep = plan_report(prog)
    errs = errors_of(rep["diagnostics"])
    if errs:
        raise SystemExit(f"{metric}: plan validation failed: " + json.dumps(
            [d.to_json() for d in errs]))
    return rep["predicted_reshards"]


def run_query(name: str, sql_template: str) -> dict:
    from arroyo_tpu.connectors.memory import clear_sink, sink_output
    from arroyo_tpu.engine.coalesce import coalescing_enabled
    from arroyo_tpu.engine.engine import LocalRunner
    from arroyo_tpu.graph.chaining import chaining_enabled
    from arroyo_tpu.obs import perf
    from arroyo_tpu.obs.metrics import job_operator_summary
    from arroyo_tpu.sql import plan_sql

    sql = sql_template.format(n=NUM_EVENTS, b=BATCH)
    # warmup: one full run of the SAME program (the jit cache is keyed by
    # the program's expression fns, so re-planning would recompile inside
    # the timed run), then best-of-2 timed runs
    par = bench_parallelism()
    prog = plan_sql(sql, parallelism=par)
    predicted_reshards = preflight_validate(
        prog, f"nexmark_{name}_events_per_sec")
    clear_sink("results")
    LocalRunner(prog).run()

    flight_before = job_operator_summary("local-job")
    dispatches_before = perf.counter("kernel_dispatches")
    join_before = {k: perf.counter(k) for k in JOIN_STATE_COUNTERS}
    from arroyo_tpu.parallel import shuffle as _shuffle

    shuffle_before = _shuffle.shuffle_stats()
    n_runs = 2
    best_dt = None
    for _ in range(n_runs):
        clear_sink("results")
        # fresh per-buffer stats registry per run, so the aggregated
        # join-state shape reflects ONE run's buffers (not warmup's)
        perf.note("join_state_registry", {})
        t0 = time.perf_counter()
        LocalRunner(prog).run()
        dt = time.perf_counter() - t0
        best_dt = dt if best_dt is None else min(best_dt, dt)
    dt = best_dt
    dispatches = perf.counter("kernel_dispatches") - dispatches_before
    flight = operator_flight_stats(flight_before,
                                   job_operator_summary("local-job"))
    outs = sink_output("results")
    n_out = sum(len(b) for b in outs)
    assert n_out > 0, f"{name} produced no output"

    eps = NUM_EVENTS / dt
    result = {
        "metric": f"nexmark_{name}_events_per_sec",
        "value": round(eps, 1),
        "unit": "events/sec",
        "parallelism": par,
        # chaining/coalescing state + amortization evidence: kernel
        # dispatches per source event across the timed runs (the number
        # chaining + expression fusion + coalescing exists to reduce)
        "chain": chaining_enabled(),
        "coalesce": coalescing_enabled(),
        "dispatches_per_event": round(
            dispatches / max(NUM_EVENTS * n_runs, 1), 6),
    }
    # factor-window shape of THIS plan: how many correlated-window
    # groups the cost model shared (q5 after CSE holds ONE hop
    # aggregate, so its decision is "no correlated group" — the
    # correlated_windows family carries the factored-vs-unfactored
    # before/after numbers)
    decisions = [d.to_json() for d in getattr(prog, "factor_decisions", [])]
    result["factor"] = {
        "shared_panes": sum(1 for d in decisions if d["shared"]),
        "derived_windows": sum(len(d["members"]) for d in decisions
                               if d["shared"]),
        "decisions": decisions,
    }
    # sharded-data-plane evidence: mesh shape + the reshard invariant
    # (reshards MUST stay 0 across the timed runs — a nonzero value
    # means some kernel's inputs arrived mis-partitioned) and how many
    # host shuffles the on-device path replaced
    import jax as _jax

    from arroyo_tpu.parallel.mesh_window import mesh_key_shards

    shuffle_delta = {k: v - shuffle_before[k]
                     for k, v in _shuffle.shuffle_stats().items()}
    result["mesh"] = {
        "width": mesh_key_shards(),
        "devices": len(_jax.devices()),
        "reshards": shuffle_delta["reshards"],
        # shardcheck's plan-time prediction for the same counter — the
        # pair the smoke drift gate asserts equal in both directions
        "predicted_reshards": predicted_reshards,
        "shuffle_collectives": shuffle_delta["collectives"],
        "host_shuffle_routes": shuffle_delta["host_routes"],
    }
    if flight:
        result["operators"] = flight
    # join-state shape: merge-vs-resort dispatch counts across the timed
    # runs plus the last hot-partition/spill snapshot — the numbers the
    # partition-adaptive join state exists to move (state/join_state.py)
    join_stats = {k.replace("join_state_", ""):
                  perf.counter(k) - join_before[k]
                  for k in JOIN_STATE_COUNTERS}
    if any(join_stats.values()):
        from arroyo_tpu.state.join_state import aggregate_stats_registry

        join_stats.update(aggregate_stats_registry(
            perf.get_note("join_state_registry")))
        # payload-residency evidence for the q7/q8 headline lines: with
        # device payloads on, hot partitions must emit through the
        # resident planes (host rows come only from cold partitions,
        # keys-only rings, and the string sticky fallback)
        join_stats.update(_gather_share(join_stats))
        result["join_state"] = join_stats
    ctl = run_control(name)
    result.update(ctl)
    if "control_events_per_sec" in ctl:
        # vs_baseline = engine / measured single-thread control (see
        # module docstring; the reference's backend can't run here)
        result["vs_baseline"] = round(
            eps / ctl["control_events_per_sec"], 3)
    result.update(phase_profile(name, sql_template))
    result.update(sanitize_overhead(name, sql_template))
    return result


def sanitize_overhead(name: str, sql_template: str) -> dict:
    """ARROYO_SANITIZE cost evidence: re-run a slice of the stream with
    the arroyosan runtime sanitizer off and on and record the relative
    slowdown.  The off run doubles as the zero-cost check — the
    sanitizer hook sites must compile down to `is not None` tests when
    disarmed (BENCH_SANITIZE=0 skips the measurement)."""
    if os.environ.get("BENCH_SANITIZE", "1") in ("0", "false", "no"):
        return {}
    from arroyo_tpu.connectors.memory import clear_sink
    from arroyo_tpu.engine.engine import LocalRunner
    from arroyo_tpu.sql import plan_sql

    n = min(NUM_EVENTS, 300_000)
    prog = plan_sql(sql_template.format(n=n, b=BATCH),
                    parallelism=bench_parallelism())
    prev = os.environ.get("ARROYO_SANITIZE")

    def timed(armed: str) -> float:
        os.environ["ARROYO_SANITIZE"] = armed
        clear_sink("results")
        t0 = time.perf_counter()
        LocalRunner(prog).run()
        return time.perf_counter() - t0

    try:
        timed("0")  # warm (jit cache shared by both arms)
        dt_off = timed("0")
        dt_on = timed("1")
    finally:
        if prev is None:
            os.environ.pop("ARROYO_SANITIZE", None)
        else:
            os.environ["ARROYO_SANITIZE"] = prev
    return {"sanitize_overhead_pct": round(
        (dt_on - dt_off) / dt_off * 100.0, 2)}


def phase_profile(name: str, sql_template: str) -> dict:
    """Measured per-phase host-time table (obs/profiler.py): re-run a
    slice of the stream with the phase profiler armed and record where
    every microsecond of the hot path went — source decode, operator
    host compute, kernel dispatch, shuffle prep, coalesce merge,
    watermark/window fires, emission encode — plus the share of wall
    time NO phase accounts for (``unattributed_share``: the
    falsifiability check that keeps the instrumentation honest as the
    engine evolves).  ``host_time_share`` is now this measured phase
    sum over wall time (clamped to 1; executor-offloaded source
    generation overlaps the event loop, so the raw ``attributed_share``
    may exceed 1 and is reported alongside).
    Profiler overhead is measured as armed-vs-off wall time on the same
    slice.  BENCH_PHASES=0 skips."""
    if os.environ.get("BENCH_PHASES", "1") in ("0", "false", "no"):
        return {}
    from arroyo_tpu.connectors.memory import clear_sink
    from arroyo_tpu.engine.engine import LocalRunner
    from arroyo_tpu.obs import profiler
    from arroyo_tpu.sql import plan_sql

    n = min(NUM_EVENTS, 500_000)
    prog = plan_sql(sql_template.format(n=n, b=BATCH),
                    parallelism=bench_parallelism())

    def timed() -> float:
        clear_sink("results")
        t0 = time.perf_counter()
        LocalRunner(prog).run()
        return time.perf_counter() - t0

    timed()  # warm (compiles shared by both arms)
    dt_off = min(timed(), timed())  # best-of-2 on BOTH arms: the
    # overhead claim must not ride single-run noise
    prof = profiler.arm("local-job")
    try:
        dt_on = None
        for _ in range(2):
            prof.reset()
            dt = timed()
            if dt_on is None or dt < dt_on:
                dt_on, snap = dt, prof.snapshot()
    finally:
        profiler.disarm()
    # the snapshot's wall includes arm-to-run slack; use the run wall
    phases = snap["phases"]
    attributed = sum(phases.values())
    out = {
        "phases": {k: round(v, 4) for k, v in phases.items()},
        "phase_waits": {k: round(v, 4) for k, v in snap["waits"].items()},
        "phase_wall_secs": round(dt_on, 4),
        "attributed_share": round(attributed / dt_on, 4),
        "unattributed_share": round(
            max(1.0 - attributed / dt_on, 0.0), 4),
        "host_time_share": round(min(attributed / dt_on, 1.0), 3),
        "profile_overhead_pct": round(
            (dt_on - dt_off) / dt_off * 100.0, 2),
        "watchdog_stalls": snap["watchdog"]["stalls"],
        "event_loop_lag_p99_ms": round(
            snap["watchdog"]["lag_p99_secs"] * 1e3, 3),
    }
    # ingest throughput through the decode phase alone: events per
    # second of source_decode time (the number the vectorized serde
    # fast path exists to move; the decode microbench isolates the
    # same family outside the engine)
    decode_secs = phases.get("source_decode", 0.0)
    if decode_secs > 0:
        out["ingest_rows_per_s"] = round(n / decode_secs, 1)
    if attributed > dt_on:
        out["phases_overlapped"] = True  # executor-side source decode
        # runs concurrently with the loop
    return out


def run_decode_microbench() -> dict:
    """Decode-family microbench: JSON lines -> Batch through each serde
    path (legacy per-row json.loads pivot, bulk one-shot array parse,
    pyarrow columnar reader with the schema-once lock) plus the egress
    mirror (Batch -> JSON payloads, template render vs per-row dumps).
    Isolates the formats.py layer from the engine so the BENCH_r0*
    trajectory shows the serde speedup independent of pipeline effects.
    The fast paths must emit identical rows (asserted here — a parity
    break is a bench failure, not a silent wrong-number).
    BENCH_DECODE=0 skips."""
    from arroyo_tpu.formats import JsonFormat, batch_to_rows

    import numpy as np

    n = int(os.environ.get("BENCH_DECODE_ROWS", 200_000))
    rng = np.random.default_rng(42)
    auction = rng.integers(1000, 2000, n)
    price = rng.integers(1, 10_000_000, n)
    bidder = rng.integers(0, 5000, n)
    payloads = [
        (f'{{"auction": {auction[i]}, "bidder": {bidder[i]}, '
         f'"price": {price[i]}, "channel": "ch{bidder[i] % 10}", '
         f'"ts": {1700000000000000 + i}}}').encode()
        for i in range(n)
    ]
    chunks = [payloads[i:i + BATCH] for i in range(0, n, BATCH)]

    def timed_decode(mode):
        prev = os.environ.get("ARROYO_FAST_DECODE")
        os.environ["ARROYO_FAST_DECODE"] = "0" if mode == "legacy" else "1"
        try:
            best, batches = None, None
            for _ in range(2):
                fmt = JsonFormat()  # fresh schema lock per run
                if mode == "bulk":
                    fmt._arrow_ok = False
                t0 = time.perf_counter()
                out = [fmt.batch(c, "ts") for c in chunks]
                dt = time.perf_counter() - t0
                if best is None or dt < best:
                    best, batches = dt, out
            return best, batches
        finally:
            if prev is None:
                os.environ.pop("ARROYO_FAST_DECODE", None)
            else:
                os.environ["ARROYO_FAST_DECODE"] = prev

    modes = ["legacy", "bulk"]
    try:
        import pyarrow.json  # noqa: F401
        modes.append("arrow")
    except ImportError:
        pass
    result = {"metric": "decode_microbench", "rows": n, "batch": BATCH}
    batches_by_mode = {}
    for mode in modes:
        dt, batches = timed_decode(mode)
        batches_by_mode[mode] = batches
        result[f"decode_{mode}_rows_per_s"] = round(n / dt, 1)
    for mode in modes[1:]:
        # parity is part of the bench contract: a fast path that drifts
        # from the legacy rows must fail loudly here. Compare every chunk:
        # the arrow path only engages its schema-once lock from chunk 1 on,
        # so first-chunk-only parity would miss exactly the locked path.
        for ci, (fast_b, legacy_b) in enumerate(
                zip(batches_by_mode[mode], batches_by_mode["legacy"])):
            assert batch_to_rows(fast_b) == batch_to_rows(legacy_b), \
                f"decode parity break: {mode} vs legacy (chunk {ci})"
        result[f"decode_{mode}_speedup"] = round(
            result[f"decode_{mode}_rows_per_s"]
            / result["decode_legacy_rows_per_s"], 2)

    # egress mirror: Batch -> JSON payloads
    batch = batches_by_mode[modes[-1]][0]

    def timed_encode(flag):
        prev = os.environ.get("ARROYO_FAST_DECODE")
        os.environ["ARROYO_FAST_DECODE"] = flag
        try:
            fmt = JsonFormat()
            best, out = None, None
            for _ in range(2):
                t0 = time.perf_counter()
                res = [fmt.serialize_batch(batch) for _ in range(10)]
                dt = time.perf_counter() - t0
                if best is None or dt < best:
                    best, out = dt, res[0]
            return best, out
        finally:
            if prev is None:
                os.environ.pop("ARROYO_FAST_DECODE", None)
            else:
                os.environ["ARROYO_FAST_DECODE"] = prev

    rows_enc = 10 * len(batch)
    dt_legacy, enc_legacy = timed_encode("0")
    dt_fast, enc_fast = timed_encode("1")
    assert enc_fast == enc_legacy, "egress parity break: fast vs legacy"
    result["encode_legacy_rows_per_s"] = round(rows_enc / dt_legacy, 1)
    result["encode_fast_rows_per_s"] = round(rows_enc / dt_fast, 1)
    result["encode_fast_speedup"] = round(dt_legacy / dt_fast, 2)
    return result


LAT_SQL = """
CREATE TABLE nexmark WITH (
  connector = 'nexmark', event_rate = '{rate}', num_events = '{n}',
  rate_limited = 'true', batch_size = '{b}', base_time_micros = '{base}'
);
SELECT bid.auction as auction,
       HOP(INTERVAL '2' SECOND, INTERVAL '10' SECOND) as window,
       count(*) AS num
FROM nexmark WHERE bid is not null GROUP BY 1, 2
"""


def run_latency() -> dict:
    """End-to-end p50/p99 latency (BASELINE.md headline): run the q5-shaped
    hop aggregate against a RATE-LIMITED source and measure, per emitted
    pane, sink arrival wallclock minus the moment the pane became
    computable (its window end + allowed lateness reaching the source).
    """
    import numpy as np

    from arroyo_tpu.connectors.memory import (
        clear_sink,
        sink_arrivals,
        sink_output,
    )
    from arroyo_tpu.engine.engine import LocalRunner
    from arroyo_tpu.obs import perf
    from arroyo_tpu.sql import plan_sql

    rate = float(os.environ.get("BENCH_LAT_RATE", 100_000))
    secs = float(os.environ.get("BENCH_LAT_SECS", 6))
    if secs <= 0:
        return {}
    lat_batch = min(BATCH, 8192)
    base = int(time.time() * 1e6)
    sql = LAT_SQL.format(rate=int(rate), n=int(rate * secs),
                         b=lat_batch, base=base)
    prog = plan_sql(sql)
    preflight_validate(prog, "latency_e2e_ms")
    # warm run of the same program: compiles must not pollute the
    # measured latency distribution (jit cache is keyed by program fns)
    clear_sink("results")
    LocalRunner(prog).run()
    perf.reset()
    clear_sink("results")
    LocalRunner(prog).run()
    outs = sink_output("results")
    arrivals = sink_arrivals("results")
    # latency per pane = sink arrival minus the wallclock at which the
    # source emitted the event that made the pane computable (the first
    # event advancing the watermark past window end + lateness), read from
    # the source's emission log — pipeline latency, not rate-schedule
    # error.  The watermark wait (lateness + batch granularity) is part of
    # the measured latency.
    #
    # the end-of-stream flush emits every still-open pane regardless of the
    # watermark — not steady-state latency.  The flush arrives in one burst
    # at the very end, so drop output batches arriving within 250ms of the
    # last arrival and keep only in-stream-fired panes.
    from arroyo_tpu.sql.schema_provider import nexmark_lateness_micros

    emit_log = perf.get_note("nexmark_emit_log") or []
    emit_ts = np.array([t for t, _ in emit_log], dtype=np.int64)
    emit_wall = np.array([w for _, w in emit_log])
    lateness = nexmark_lateness_micros(rate)
    last_arrival = max(arrivals) if arrivals else 0.0
    samples = []
    for b, arr in zip(outs, arrivals):
        if arr > last_arrival - 0.25 or not len(emit_ts):
            continue
        wend = np.asarray(b.columns["window_end"], dtype=np.int64)
        idx = np.searchsorted(emit_ts, wend + lateness)
        ok = idx < len(emit_wall)
        samples.extend((arr - emit_wall[idx[ok]]).tolist())
    samples = np.asarray(samples)
    samples = np.maximum(samples, 0.0)  # clip scheduler jitter
    if not len(samples):
        return {}
    return {
        "latency_p50_ms": round(float(np.percentile(samples, 50)) * 1e3, 1),
        "latency_p99_ms": round(float(np.percentile(samples, 99)) * 1e3, 1),
        "latency_rate_events_per_sec": int(rate),
    }


def run_latency_family() -> dict:
    """Latency-observatory family (obs/latency.py): the record-level
    sampled measurement, as opposed to run_latency's external
    pane-computable clock.

    Three parts: (a) sampling overhead — the SAME unthrottled hop
    aggregate timed with the observatory disarmed vs armed at 1-in-64,
    best-of-3 each (the <2% budget is the acceptance bar for keeping
    sampling on in production); (b) a latency-vs-throughput curve —
    the rate-limited pipeline at fractions of BENCH_LAT_RATE, p50/p99
    from the observatory's per-sink rolling windows at each point;
    (c) the critical-path attribution at the headline rate."""
    from arroyo_tpu.config import reset_config
    from arroyo_tpu.connectors.memory import clear_sink, sink_output
    from arroyo_tpu.engine.engine import LocalRunner
    from arroyo_tpu.obs import latency
    from arroyo_tpu.sql import plan_sql

    sample_n = int(os.environ.get("BENCH_LAT_SAMPLE_N", 64))

    def timed(prog, armed: bool) -> float:
        latency.disarm()
        if armed:
            os.environ["ARROYO_LATENCY_SAMPLE_N"] = str(sample_n)
        else:
            os.environ.pop("ARROYO_LATENCY_SAMPLE_N", None)
        reset_config()
        clear_sink("results")
        t0 = time.perf_counter()
        LocalRunner(prog).run()
        dt = time.perf_counter() - t0
        assert sum(len(b) for b in sink_output("results")) > 0
        return dt

    # (a) overhead: unthrottled, so the stamp hooks sit on the hottest
    # possible path; one program -> one jit cache for both arms
    n_ovh = int(os.environ.get("BENCH_LAT_OVH_EVENTS", 400_000))
    base = int(time.time() * 1e6)
    ovh_sql = LAT_SQL.format(rate=1_000_000, n=n_ovh, b=8192,
                             base=base).replace(
        "rate_limited = 'true'", "rate_limited = 'false'")
    prog = plan_sql(ovh_sql)
    timed(prog, armed=False)  # warm: compiles stay out of both arms
    off = min(timed(prog, armed=False) for _ in range(3))
    on = min(timed(prog, armed=True) for _ in range(3))
    overhead_pct = round((on - off) / off * 100.0, 2)
    out = {
        "sample_n": sample_n,
        "overhead": {
            "events": n_ovh,
            "off_secs": round(off, 4),
            "on_secs": round(on, 4),
            "latency_overhead_pct": overhead_pct,
            "budget_pct": 2.0,
            "within_budget": overhead_pct < 2.0,
        },
    }

    # (b) the latency-vs-throughput curve: sampled p50/p99 as the offered
    # rate rises toward the headline rate
    rate_hi = float(os.environ.get("BENCH_LAT_RATE", 100_000))
    secs = float(os.environ.get("BENCH_LAT_CURVE_SECS", 3))
    fracs = [float(f) for f in os.environ.get(
        "BENCH_LAT_CURVE", "0.25,0.5,1.0").split(",")]
    curve = []
    for frac in fracs:
        rate = max(int(rate_hi * frac), 1000)
        n = int(rate * secs)
        sql = LAT_SQL.format(rate=rate, n=n, b=min(BATCH, 8192),
                             base=int(time.time() * 1e6))
        cprog = plan_sql(sql)
        timed(cprog, armed=True)  # warm per-shape compiles
        dt = timed(cprog, armed=True)
        lat = latency.active()
        sinks = lat.sink_quantiles() if lat is not None else {}
        q = next(iter(sinks.values()), {})
        curve.append({
            "rate_events_per_sec": rate,
            "achieved_events_per_sec": round(n / dt, 1),
            "p50_ms": q.get("p50_ms"),
            "p99_ms": q.get("p99_ms"),
            "samples": int(q.get("count", 0)),
        })
    out["curve"] = curve
    if curve:
        out["p50_ms"] = curve[-1]["p50_ms"]
        out["p99_ms"] = curve[-1]["p99_ms"]

    # (c) where the time went at the headline rate
    lat = latency.active()
    if lat is not None:
        cp = lat.critical_path()
        out["critical_path"] = {"dominant": cp["dominant"],
                                "dominant_share": cp["dominant_share"]}
    latency.disarm()
    os.environ.pop("ARROYO_LATENCY_SAMPLE_N", None)
    reset_config()
    return out


CONFIG5_SQL = """
CREATE TABLE ev (
  k BIGINT, v DOUBLE, ts BIGINT,
  event_time TIMESTAMP GENERATED ALWAYS AS
    (CAST(from_unixtime(ts) as TIMESTAMP))
) WITH (
  connector = 'kafka', bootstrap_servers = 'memory://bench5',
  topic = 'sess', type = 'source', format = 'json',
  event_time_field = 'event_time', batch_size = '{b}',
  max_messages = '{n}'
);
CREATE TABLE out WITH (connector = 'memory', name = 'results');
INSERT INTO out
SELECT k, median(v) as med, count(*) as cnt,
       session(INTERVAL '1' SECOND) as window
FROM ev GROUP BY 1, 4
"""


def _config5_produce(broker_name: str, n: int, t0_micros: int,
                     spacing_micros: int) -> None:
    """Fill the in-process kafka topic with n bursty-keyed JSON events:
    64 keys are active per block of 6400 events, then retire — so 1s-gap
    sessions continuously close as event time advances."""
    import json as _json

    import numpy as np

    from arroyo_tpu.connectors.kafka import InMemoryKafkaBroker

    InMemoryKafkaBroker.reset(broker_name)
    broker = InMemoryKafkaBroker.get(broker_name)
    broker.create_topic("sess", partitions=1)
    P, burst = 64, 100
    i = np.arange(n, dtype=np.int64)
    keys = (i % P) + (i // (P * burst)) * P
    ts = t0_micros + i * spacing_micros
    vals = (i % 997).astype(np.float64) / 7.0
    for j in range(n):
        broker.produce("sess", _json.dumps(
            {"k": int(keys[j]), "v": float(vals[j]),
             "ts": int(ts[j]) * 1000}).encode(), partition=0)


def _session_stats(before: dict, n_events: int) -> dict:
    """Session-state counter deltas since ``before`` + the last state
    registry snapshot.  ``state_bounded`` asserts live session rows
    track the ACTIVE key horizon (64 keys/burst block, a handful of
    open sessions each), not the stream length — the contract the
    expire mask-compression exists to keep."""
    from arroyo_tpu.obs import perf
    from arroyo_tpu.state.session_state import aggregate_session_registry

    out = {k: perf.counter(k) - before[k] for k in SESSION_COUNTERS}
    total_merge = out["session_device_merge_rows"] + \
        out["session_host_merge_rows"]
    out["device_merge_share"] = round(
        out["session_device_merge_rows"] / total_merge, 4) \
        if total_merge else None
    reg = aggregate_session_registry(
        perf.get_note("session_state_registry"))
    if reg:
        out["state"] = reg
        out["state_bounded"] = reg["rows"] < 4096 and \
            reg["rows"] < max(n_events // 8, 1024)
    return out


def run_config5() -> dict:
    """BASELINE.md config #5: session-window aggregation with a UDAF
    (median) over the Kafka source with 1s periodic checkpointing ON.
    Throughput over a pre-filled topic; p50/p99 end-to-end latency from
    a separate rate-limited run where event time == scheduled produce
    wall time."""
    import tempfile

    import numpy as np

    from arroyo_tpu.connectors.memory import clear_sink, sink_output
    from arroyo_tpu.engine.engine import LocalRunner
    from arroyo_tpu.sql import SchemaProvider, plan_sql

    n = int(os.environ.get("BENCH_C5_EVENTS", 200_000))
    p = SchemaProvider()
    p.register_udaf("median", np.median)
    sql = CONFIG5_SQL.format(b=4096, n=n)
    ckpt = tempfile.mkdtemp(prefix="bench5-ckpt-")

    # ONE program for warmup and timed runs: the jit cache is keyed by
    # the program's expression fns, so re-planning would put recompiles
    # inside the timed run (same discipline as run_query).  The warmup
    # topic holds fewer events than max_messages, so the warm run drains
    # it and exits via the idle-spin bound — a bounded one-time cost.
    # The single-partition topic caps SOURCE parallelism at 1; the keyed
    # session/aggregate stages still fan out.
    prog = plan_sql(sql, p, parallelism=bench_parallelism())
    preflight_validate(prog, "baseline5_session_udaf_kafka_events_per_sec")

    def timed_run():
        clear_sink("results")
        t0 = time.perf_counter()
        LocalRunner(prog, checkpoint_url=f"file://{ckpt}").run(
            checkpoint_interval_secs=1.0)
        dt = time.perf_counter() - t0
        outs = sink_output("results")
        n_out = sum(len(b) for b in outs)
        assert n_out > 0, "config5 produced no sessions"
        return dt, n_out

    # full-size warmup: a truncated topic under-warms — the end-of-run
    # flush aggregates every closed session in ONE segment dispatch, so
    # its padded-bucket shape scales with n and a smaller warmup leaves
    # that compile INSIDE the timed run (profiled at ~12% of wall)
    _config5_produce("bench5", n, 0, 10)
    clear_sink("results")
    LocalRunner(prog).run()
    _config5_produce("bench5", n, 0, 10)
    from arroyo_tpu.obs import perf

    before = {k: perf.counter(k) for k in SESSION_COUNTERS}
    perf.note("session_state_registry", {})
    dt, n_out = timed_run()
    result = {
        "metric": "baseline5_session_udaf_kafka_events_per_sec",
        "value": round(n / dt, 1),
        "unit": "events/sec",
        "sessions_emitted": n_out,
        "checkpoint_interval_secs": 1.0,
        # session-state shape of the timed run: merge dispatches + the
        # device/host row split the PR 19 state layout exists to move,
        # plus the hot-partition/staging snapshot and the bounded-state
        # verdict (state/session_state.py)
        "sessions": _session_stats(before, n),
    }

    # latency: produce in real time at a fixed rate; event time equals the
    # scheduled produce wall time, so a session row's computable moment is
    # wall_base + (window_end + lateness - t0) / 1e6
    # well below the config's drain capacity (~460k/s after the r4 merge
    # vectorization): latency at saturation is queueing delay, not
    # pipeline latency
    rate = float(os.environ.get("BENCH_C5_LAT_RATE", 50_000))
    secs = float(os.environ.get("BENCH_C5_LAT_SECS", 5))
    n_lat = int(rate * secs)
    # warm the latency program too (batch_size differs -> own compiles)
    lat_prog = plan_sql(CONFIG5_SQL.format(b=512, n=n_lat), p)
    _config5_produce("bench5", 4_000, 0, 10)
    clear_sink("results")
    LocalRunner(lat_prog).run()
    lat = _config5_latency(lat_prog, rate, n_lat,
                           checkpoint_url=f"file://{ckpt}")
    if lat:
        result["latency_p50_ms"] = lat["p50_ms"]
        result["latency_p99_ms"] = lat["p99_ms"]
        result["latency_rate_events_per_sec"] = lat["rate_events_per_sec"]
        # grouped view for the driver artifact, same shape as the q5
        # headline's latency object (flat keys stay for continuity)
        result["latency"] = lat
    return result


def _config5_latency(lat_prog, rate: float, n_lat: int,
                     checkpoint_url=None):
    """Rate-limited real-time latency run over the config5 topic with an
    already-warmed program: event time == scheduled produce wall time,
    so a session row's computable moment is wall_base + (window_end +
    lateness - t0) / 1e6.  Returns {p50_ms, p99_ms,
    rate_events_per_sec} or None when no steady-state samples landed."""
    import threading

    import numpy as np

    from arroyo_tpu.connectors.kafka import InMemoryKafkaBroker
    from arroyo_tpu.connectors.memory import (
        clear_sink,
        sink_arrivals,
        sink_output,
    )
    from arroyo_tpu.engine.engine import LocalRunner

    InMemoryKafkaBroker.reset("bench5")
    broker = InMemoryKafkaBroker.get("bench5")
    broker.create_topic("sess", partitions=1)
    # time.monotonic throughout: sink_arrivals records monotonic, so the
    # computable-moment math must live on the same clock
    wall_base = time.monotonic()
    t0_micros = int(time.time() * 1e6)

    def producer():
        import json as _json

        P, burst = 64, 100
        # chunked pacing: one wakeup per ~8ms burst — a per-message pace
        # at this rate would busy-spin and starve the engine of the GIL
        chunk = max(int(rate * 0.008), 1)
        for c0 in range(0, n_lat, chunk):
            target = wall_base + c0 / rate
            lag = target - time.monotonic()
            if lag > 0:
                time.sleep(lag)
            for i in range(c0, min(c0 + chunk, n_lat)):
                ts = t0_micros + int(i / rate * 1e6)
                broker.produce("sess", _json.dumps(
                    {"k": (i % P) + (i // (P * burst)) * P,
                     "v": float(i % 997) / 7.0, "ts": ts * 1000}).encode(),
                    partition=0)

    th = threading.Thread(target=producer, daemon=True)
    clear_sink("results")
    th.start()
    runner = (LocalRunner(lat_prog, checkpoint_url=checkpoint_url)
              if checkpoint_url else LocalRunner(lat_prog))
    if checkpoint_url:
        runner.run(checkpoint_interval_secs=1.0)
    else:
        runner.run()
    th.join()
    outs = sink_output("results")
    arrivals = sink_arrivals("results")
    lateness = 1_000_000  # DDL-table default (TableDef dataclass default)
    last_arrival = max(arrivals) if arrivals else 0.0
    samples = []
    for b, arr in zip(outs, arrivals):
        if arr > last_arrival - 0.25:
            continue  # end-of-stream flush burst, not steady state
        wend = np.asarray(b.columns["window_end"], dtype=np.int64)
        computable = wall_base + (wend + lateness - t0_micros) / 1e6
        samples.extend(np.maximum(arr - computable, 0.0).tolist())
    if not samples:
        return None
    s = np.asarray(samples)
    return {"p50_ms": round(float(np.percentile(s, 50)) * 1e3, 1),
            "p99_ms": round(float(np.percentile(s, 99)) * 1e3, 1),
            "rate_events_per_sec": int(rate)}


def run_sessions_family() -> dict:
    """The ``sessions`` family: the config5 shape swept over the PR 19
    knob matrix — session state {device sorted-runs, legacy per-key
    dicts} x UDAF execution {vectorized channels, per-segment host
    loop} — so the artifact shows WHERE the config5 speedup comes from
    and that both axes produce identical rows.

    Each combo records events/s, the session-merge dispatch counts and
    device/host row split, the hot-partition/spill snapshot, and the
    bounded-state verdict; the two session-state modes additionally
    carry a short rate-limited latency block.  Before each timed run a
    small SANITIZED run cross-checks row parity: every combo must hash
    to the same sorted row digest."""
    import hashlib

    import numpy as np

    from arroyo_tpu.connectors.memory import clear_sink, sink_output
    from arroyo_tpu.engine.engine import LocalRunner
    from arroyo_tpu.obs import perf
    from arroyo_tpu.sql import SchemaProvider, plan_sql

    n = int(os.environ.get("BENCH_SESS_EVENTS", 120_000))
    p = SchemaProvider()
    p.register_udaf("median", np.median)
    prog = plan_sql(CONFIG5_SQL.format(b=4096, n=n), p,
                    parallelism=bench_parallelism())
    lat_rate = float(os.environ.get("BENCH_SESS_LAT_RATE", 30_000))
    lat_secs = float(os.environ.get("BENCH_SESS_LAT_SECS", 2))
    n_lat = int(lat_rate * lat_secs)
    lat_prog = plan_sql(CONFIG5_SQL.format(b=512, n=n_lat), p)

    knobs = ("ARROYO_SESSION_STATE", "ARROYO_UDAF_CHANNELS",
             "ARROYO_SANITIZE")
    saved = {k: os.environ.get(k) for k in knobs}

    def digest_rows():
        outs = sink_output("results")
        rows = []
        for b in outs:
            names = sorted(b.columns)
            for i in range(len(b)):
                rows.append(tuple(
                    round(float(b.columns[c][i]), 6) for c in names))
        return hashlib.sha256(repr(sorted(rows)).encode()).hexdigest()[:16]

    family: dict = {"events": n}
    digests = {}
    try:
        for state in ("device", "legacy"):
            for chan in ("on", "off"):
                combo = f"{state}_{'channels' if chan == 'on' else 'host'}"
                os.environ["ARROYO_SESSION_STATE"] = state
                os.environ["ARROYO_UDAF_CHANNELS"] = chan
                # parity probe: small run with the runtime sanitizer
                # armed; doubles as the per-combo warmup
                os.environ["ARROYO_SANITIZE"] = "1"
                _config5_produce("bench5", 6_000, 0, 10)
                clear_sink("results")
                LocalRunner(prog).run()
                digests[combo] = digest_rows()
                os.environ["ARROYO_SANITIZE"] = "0"
                _config5_produce("bench5", n, 0, 10)
                clear_sink("results")
                before = {k: perf.counter(k) for k in SESSION_COUNTERS}
                perf.note("session_state_registry", {})
                t0 = time.perf_counter()
                LocalRunner(prog).run()
                dt = time.perf_counter() - t0
                n_out = sum(len(b) for b in sink_output("results"))
                assert n_out > 0, f"sessions family {combo}: no output"
                entry = {
                    "events_per_sec": round(n / dt, 1),
                    "sessions_emitted": n_out,
                    "sessions": _session_stats(before, n),
                }
                if chan == "on":
                    lat = _config5_latency(lat_prog, lat_rate, n_lat)
                    if lat:
                        entry["latency"] = lat
                family[combo] = entry
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    family["parity_ok"] = len(set(digests.values())) == 1
    family["row_digests"] = digests
    dev = family.get("device_channels", {}).get("events_per_sec", 0)
    leg = family.get("legacy_host", {}).get("events_per_sec", 0)
    if leg:
        family["speedup_vs_legacy_host"] = round(dev / leg, 2)
    return family


# -- kernel-level accelerator microbench ------------------------------------
#
# Exercises exactly the device hot path the engine uses, outside the
# engine: the keyed-bin update kernel (one packed host->device transfer
# per step, scatter-add into resident state), the pane-emission
# gather/reduce, the join kernels, plus raw transfer bandwidth and
# dispatch latency.


def run_kernel_microbench() -> dict:
    import numpy as np

    import jax
    import jax.numpy as jnp

    from arroyo_tpu.ops import keyed_bins as kb

    device = bench_device()
    dev = jax.devices()[0]
    out = {"backend": device["platform"], "device": device,
           "jax": jax.__version__, "numpy": np.__version__}

    def timeit(fn, warmup=3, iters=20):
        for _ in range(warmup):
            fn()
        t0 = time.perf_counter()
        for _ in range(iters):
            fn()
        return (time.perf_counter() - t0) / iters

    # dispatch latency: tiny jitted op round-trip
    one = jax.device_put(jnp.float32(1.0), dev)
    f = jax.jit(lambda x: x + 1)
    jax.block_until_ready(f(one))
    out["dispatch_ms"] = round(
        timeit(lambda: jax.block_until_ready(f(one)), iters=50) * 1e3, 3)

    # host->device transfer bandwidth (8 MB f32, the engine's batch scale)
    buf = np.random.default_rng(0).standard_normal(
        (2 * 1024 * 1024,)).astype(np.float32)
    dt = timeit(lambda: jax.block_until_ready(jax.device_put(buf, dev)),
                warmup=2, iters=10)
    out["h2d_MBps"] = round(buf.nbytes / dt / 1e6, 1)

    # device->host: both directions matter and need not be symmetric —
    # per-transfer latency (tiny array) and bandwidth (8 MB) separately.
    # jax Arrays cache their host copy after the first np.asarray, so
    # each readback goes through a fresh jitted no-op result.
    bump = jax.jit(lambda x: x + 1)
    buf_d = jax.block_until_ready(jax.device_put(buf, dev))
    tiny_d = jax.block_until_ready(jax.device_put(
        np.zeros(16, np.float32), dev))
    jax.block_until_ready(bump(tiny_d))
    out["d2h_lat_ms"] = round(
        timeit(lambda: np.asarray(bump(tiny_d)), warmup=2, iters=10)
        * 1e3, 3)
    jax.block_until_ready(bump(buf_d))
    dt = timeit(lambda: np.asarray(bump(buf_d)), warmup=2, iters=10)
    out["d2h_MBps"] = round(buf.nbytes / dt / 1e6, 1)

    # update kernel: the q5-shaped hot loop.  C keys x B bins resident
    # state, n pre-aggregated (key,bin) cells per step, one i32[2, n]
    # index + one f64[k+1, n] value transfer per step — exactly
    # KeyedBinState.update's device path (keyed_bins._update_kernel), with
    # i32 counts state as the engine holds it.
    kinds = ("count", "sum", "max")
    C, B, n = 8192, 16, 16384
    kern = kb._update_kernel(kinds, C, B, n)
    state = list(jax.device_put(kb.init_planes(kinds, C, B), dev))
    rng = np.random.default_rng(1)
    # n distinct (slot, bin) cells: the engine pre-aggregates, so a
    # dispatch never carries a cell twice
    cells = rng.choice(C * B, size=n, replace=False)
    idx_np = np.stack([cells // B, cells % B]).astype(np.int32)
    packed_np = np.empty((1 + len(kinds), n), np.float64)
    packed_np[0] = 1.0
    packed_np[1:] = rng.standard_normal((len(kinds), n))

    def step():
        # two transfers per step (indices stay i32, values exact f64);
        # the kernel takes the planes donated, so the handles it returns
        # replace the ones it was given, as in the engine
        idx = jax.device_put(idx_np, dev)
        packed = jax.device_put(packed_np, dev)
        state[0], state[1] = kern(state[0], state[1], idx, packed)
        jax.block_until_ready(state[1])

    dt = timeit(step, warmup=3, iters=20)
    out["update_step_ms"] = round(dt * 1e3, 3)
    out["update_cells_per_sec"] = round(n / dt, 1)

    # emit kernel: k panes gathered from the ring and reduced per key
    k = 64
    W = 5
    ring = np.tile(np.arange(W, dtype=np.int32), (k, 1))
    bin_ok = np.ones((k, W), dtype=bool)
    ek = kb._emit_kernel(kinds, C, B, W, k)
    ring_d = jax.device_put(ring, dev)
    ok_d = jax.device_put(bin_ok, dev)

    def estep():
        r, cnt = ek(state[0], state[1], ring_d, ok_d)
        jax.block_until_ready(cnt)

    dt = timeit(estep, warmup=3, iters=20)
    out["emit_step_ms"] = round(dt * 1e3, 3)
    out["emit_key_panes_per_sec"] = round(C * k / dt, 1)

    # join kernels: sort/probe/expand on device (ops/join.py — the q8
    # windowed-join hot path).  Two numbers: the device kernels alone
    # (state in, indices computed, one block — what a resident-state
    # engine pays), and the full join_pairs including result readback
    # (what the host-materializing engine pays — see d2h_lat_ms).
    from arroyo_tpu.ops import join as dj

    nl = nr = 8192
    jrng = np.random.default_rng(2)
    lk = jrng.integers(0, 4096, nl).astype(np.uint64)
    rk = jrng.integers(0, 4096, nr).astype(np.uint64)
    nlp, nrp = dj._bucket(nl), dj._bucket(nr)
    lk_p = np.full(nlp, dj.SENTINEL, np.uint64)
    lk_p[:nl] = lk
    rk_p = np.full(nrp, dj.SENTINEL, np.uint64)
    rk_p[:nr] = rk
    sk, pk = dj._sort_kernel(nlp), dj._probe_kernel(nlp, nrp, True)
    _, lks_d = sk(lk_p)
    _, rks_d = sk(rk_p)
    _, counts_d, cum_d = pk(lks_d, rks_d, nl, nr)
    m = dj._bucket(int(np.asarray(counts_d)[:nl].sum()))
    ek = dj._expand_kernel(nlp, m)

    def jkernels():
        lo_d, lks = sk(lk_p)
        ro_d, rks = sk(rk_p)
        start_d, cnt_d, cm_d = pk(lks, rks, nl, nr)
        jax.block_until_ready(ek(start_d, cm_d))

    dt = timeit(jkernels, warmup=3, iters=20)
    out["join_kernels_ms"] = round(dt * 1e3, 3)
    out["join_kernel_rows_per_sec"] = round((nl + nr) / dt, 1)

    def jstep():
        dj.join_pairs(lk, rk)

    dt = timeit(jstep, warmup=3, iters=10)
    out["join_step_ms"] = round(dt * 1e3, 3)
    out["join_rows_per_sec"] = round((nl + nr) / dt, 1)

    # resident-ring probe + payload materialization (PR 15): the
    # pre-PR-15 hot path — emulated-u64 ring probe, pair readback, host
    # fancy-index payload gather — vs the split-hash i32 ring with the
    # fused expand+verify+gather dispatch.  On an accelerator the new
    # path must win >= 5x (the u64 compares are emulated there and the
    # per-match readback pays d2h_lat_ms); on CPU the pair of numbers
    # still records and ``ring_probe_parity`` carries the gate.
    ns = nq = 16384
    from arroyo_tpu.types import hash_u64

    srng = np.random.default_rng(3)
    # realistic keys: full-entropy u64 hashes of an 8k id space (~2
    # state rows per key), exactly what key_by feeds the join state —
    # the split-hash layout relies on top-32 entropy, which real
    # key_hash columns always have
    skeys = np.sort(hash_u64(srng.integers(0, 8192, ns)))
    sts = srng.integers(0, 1 << 40, ns)
    scols = {"v0": srng.standard_normal(ns),
             "v1": srng.integers(0, 1 << 50, ns),
             "v2": srng.standard_normal(ns),
             "v3": srng.integers(0, 1 << 30, ns)}
    qk = np.sort(hash_u64(srng.integers(0, 8192, nq)))
    cap = dj._bucket(ns)
    mq = dj._bucket(nq)
    # baseline ring: u64 keys, probe kernels on u64, gather on host
    ring64 = np.full(cap, dj.SENTINEL, np.uint64)
    ring64[:ns] = skeys
    ring64_d = jax.device_put(ring64, dev)
    qp = np.full(mq, dj.SENTINEL, np.uint64)
    qp[:nq] = qk
    pk64 = dj._probe_kernel(mq, cap, dj._merged_probe())
    start0, counts0, _ = pk64(qp, ring64_d, nq, ns)
    total = int(np.asarray(counts0)[:nq].sum())
    mb = dj._bucket(total)
    ex64 = dj._expand_kernel(mq, mb)

    def u64_host():
        start_d, cnt_d, cum_d = pk64(qp, ring64_d, nq, ns)
        lidx_d, ridx_d = ex64(start_d, cum_d)
        lidx = np.asarray(lidx_d)[:total]
        ridx = np.asarray(ridx_d)[:total]
        rows = {c: v[ridx] for c, v in scols.items()}
        rows["ts"] = sts[ridx]
        return lidx, ridx, rows

    dt = timeit(u64_host, warmup=3, iters=10)
    out["ring_probe_u64_host_ms"] = round(dt * 1e3, 3)

    # payload planes engage because sorted_cols is passed explicitly —
    # the ARROYO_JOIN_PAYLOAD_DEVICE knob gates the buffer layer, not
    # these kernel-level calls
    ring = dj.stage_ring(skeys, device=dev, sorted_ts=sts,
                         sorted_cols=scols)

    def split_fused():
        hit = dj.probe_ring(ring, qk, ns)
        t = int(hit.counts.sum())
        lidx, ridx, valid, gf, gi = dj.expand_gather(ring, hit, t)
        ts2, cols2 = dj.unpack_payload(ring, gf, gi)
        return lidx, ridx, valid, ts2, cols2

    dt2 = timeit(split_fused, warmup=3, iters=10)
    out["ring_probe_split_fused_ms"] = round(dt2 * 1e3, 3)
    out["ring_probe_rows"] = total
    out["ring_probe_speedup"] = round(dt / dt2, 2)
    # parity: the fused path must emit exactly the baseline's pairs and
    # payload bytes (the verify plane may only kill non-matches; this
    # fixture has none by construction of the exact u64 baseline probe)
    bl, br, brows = u64_host()
    fl, fr, fvalid, fts, fcols = split_fused()
    out["ring_probe_parity"] = bool(
        fvalid.all() and (bl == fl).all() and (br == fr).all()
        and (brows["ts"] == fts).all()
        and all((brows[c] == fcols[c]).all() for c in scols))

    # ring-pane emission kernel (long-window bin-sharded sweep): on a
    # single chip the mesh degenerates to 1 shard but the kernel (cumsum
    # sweep + halo plumbing) is the one the engine runs at W>=64
    from arroyo_tpu.parallel.ring_panes import _ring_step_2d

    Cr, Lr, Wr = 1024, 512, 300
    rfn, rsharding = _ring_step_2d("sum", 1, Cr, Lr, Wr)
    rbins = jax.device_put(
        jnp.asarray(rng.standard_normal((Cr, Lr)), jnp.float64),
        rsharding)

    def rstep():
        jax.block_until_ready(rfn(rbins))

    dt = timeit(rstep, warmup=3, iters=20)
    out["ring_step_ms"] = round(dt * 1e3, 3)
    out["ring_key_bins_per_sec"] = round(Cr * Lr / dt, 1)

    return out


def run_join_stress() -> dict:
    """Join-stress family: a skewed (Zipf-ish) keyed two-stream INNER
    join with LONG event-time TTL — the shape where the legacy flat join
    buffers collapsed (every arriving batch re-sorted the whole opposite
    buffer; every watermark re-materialized both sides).  Records
    events/s, the merge-vs-resort dispatch split, the hot/spill state
    shape, and whether state stayed bounded (valid-range eviction must
    hold resident rows near 2 * TTL_rate, not grow with the stream)."""
    import numpy as np

    from arroyo_tpu import Stream
    from arroyo_tpu.connectors.memory import clear_sink, sink_output
    from arroyo_tpu.engine.engine import LocalRunner
    from arroyo_tpu.obs import perf
    from arroyo_tpu.types import hash_u64

    n = int(os.environ.get("BENCH_JOIN_STRESS_EVENTS", 400_000))
    ttl = 30_000_000  # 30s event time; 1ms/event -> ~30k live rows/side
    base = 1_700_000_000_000_000

    def zipf_map(side: int):
        def fn(cols):
            c = np.asarray(cols["counter"], dtype=np.int64)
            if side == 1:
                # probe side: uniform keys, so output stays ~linear while
                # the skewed build side's hot partitions carry the stress
                key = (hash_u64(c + 7_919) % np.uint64(100_000)).astype(
                    np.int64)
            else:
                u = (hash_u64(c) >> np.uint64(11)).astype(
                    np.float64) / float(1 << 53)
                u = np.maximum(u, 1e-12)
                # Zipf(s~1) ranks over 100k keys: the head keys take a
                # constant fraction of rows — the PanJoin skew scenario
                key = np.exp(u * np.log(100_000.0)).astype(np.int64)
            return {"k": key, f"v{side}": c}

        return fn

    def build():
        left = (Stream.source("impulse", {
            "event_rate": 1e9, "message_count": n,
            "event_time_interval_micros": 1000,
            "base_time_micros": base, "batch_size": 8192})
            .watermark(max_lateness_micros=0)
            .udf(zipf_map(0), name="zl").key_by("k"))
        right = (Stream.source("impulse", {
            "event_rate": 1e9, "message_count": n,
            "event_time_interval_micros": 1000,
            "base_time_micros": base, "batch_size": 8192},
            program=left.program)
            .watermark(max_lateness_micros=0)
            .udf(zipf_map(1), name="zr").key_by("k"))
        return left.join_with_expiration(
            right, ttl, ttl, name="stress_join").sink(
            "memory", {"name": "join_stress"})

    from arroyo_tpu.state.join_state import aggregate_stats_registry

    prog = build()
    clear_sink("join_stress")
    LocalRunner(prog).run()  # warm (compiles, allocator)
    before = {k: perf.counter(k) for k in JOIN_STATE_COUNTERS}
    clear_sink("join_stress")
    perf.note("join_state_registry", {})  # this run's buffers only
    t0 = time.perf_counter()
    LocalRunner(build()).run()
    dt = time.perf_counter() - t0
    out_rows = sum(len(b) for b in sink_output("join_stress"))
    stats = {k.replace("join_state_", ""):
             perf.counter(k) - before[k] for k in JOIN_STATE_COUNTERS}
    snap = aggregate_stats_registry(perf.get_note("join_state_registry"))
    stats.update(_gather_share(stats))
    live_rows = snap.get("rows")
    # payload rings are pow2(partition rows incl. the <= 2x dead-row
    # estimate lag), so their summed capacity must ALSO track the TTL
    # horizon: a ring that regrows without demoting/compacting (a
    # payload-plane leak) blows this bound long before host state does
    ring_cap = snap.get("ring_cap_rows", 0)
    return {
        "metric": "join_stress_events_per_sec",
        "value": round(2 * n / dt, 1), "unit": "events/sec",
        "events": 2 * n, "output_rows": out_rows,
        "ttl_micros": ttl,
        "join_state": {**stats, **snap},
        # bounded-state check: resident rows (both sides summed, with
        # the dead-estimate's up-to-8-eviction lag) must track the TTL
        # horizon (~ttl/interval per side), not the stream length —
        # and so must the device payload-ring capacity
        "state_bounded": (live_rows is not None
                          and live_rows < 6 * (ttl // 1000)
                          and ring_cap < 12 * (ttl // 1000)),
    }


def run_autoscale_bench() -> dict:
    """``--autoscale`` mode: elasticity, not steady state.  Run an
    impulse flood through a real controller with the autoscaler enabled
    on the bottleneck aggregate and record (a) the decision timeline and
    (b) throughput-vs-parallelism samples, so BENCH_* artifacts show how
    the system tracks load, not just its peak."""
    import asyncio

    from arroyo_tpu import AggKind, AggSpec, Stream
    from arroyo_tpu.autoscale import BacklogDrainPolicy, PolicyConfig
    from arroyo_tpu.controller.controller import ControllerServer
    from arroyo_tpu.controller.scheduler import InProcessScheduler
    from arroyo_tpu.controller.state_machine import JobState

    n = int(os.environ.get("BENCH_AUTOSCALE_EVENTS", 400_000))
    rate = float(os.environ.get("BENCH_AUTOSCALE_RATE", 30_000.0))
    os.environ.setdefault("HEARTBEAT_INTERVAL_SECS", "0.2")
    # the explicit --autoscale flag wins over an ambient escape hatch:
    # without this, ARROYO_AUTOSCALE=0 in the environment would crash
    # the elasticity benchmark instead of measuring it
    os.environ["ARROYO_AUTOSCALE"] = "1"
    import arroyo_tpu.config as _cfg

    _cfg.reset_config()

    out_path = os.path.join(tempfile.mkdtemp(prefix="arroyo_as_"),
                            "out.jsonl")

    async def scenario():
        from arroyo_tpu.types import now_micros

        ctrl = ControllerServer(InProcessScheduler())
        await ctrl.start()
        prog = (
            # backlog replay: event times start 10 minutes behind the
            # wall clock, so the watermark-lag signal drives catch-up
            # provisioning while the rate limit keeps the run long
            # enough to capture a decision timeline
            Stream.source("impulse", {"event_rate": rate,
                                      "message_count": n,
                                      "event_time_interval_micros": 1000,
                                      "base_time_micros":
                                          now_micros() - 600_000_000,
                                      "batch_size": 256}, parallelism=1)
            .watermark(max_lateness_micros=0)
            .map(lambda c: {"counter": c["counter"],
                            "bucket": c["counter"] % 8}, name="b")
            .key_by("bucket")
            .tumbling_aggregate(
                500 * 1000, [AggSpec(AggKind.COUNT, None, "cnt")],
                parallelism=1)
            .sink("single_file", {"path": out_path}, parallelism=1)
        )
        agg_id = next(node.operator_id for node in prog.nodes()
                      if "aggregator" in node.operator_id)
        t0 = time.perf_counter()
        job_id = await ctrl.submit_job(prog, n_workers=1)
        points = []
        try:
            scaler = ctrl.autoscalers[job_id]
            scaler.policy = BacklogDrainPolicy(PolicyConfig(
                interval_secs=0.3, high_water=0.3, up_sustain=1,
                lag_warn_secs=0.5, lag_high_secs=5.0,
                up_cooldown_secs=8.0, down_cooldown_secs=600.0,
                max_parallelism=1,
                per_op={agg_id: {"min": 1, "max": 4}}))
            scaler.set_enabled(True)
            while not ctrl.jobs[job_id].fsm.state.terminal:
                await asyncio.sleep(0.5)
                roll = {r["operator_id"]: r
                        for r in ctrl.job_rollup(job_id)}
                par = {node.operator_id: node.parallelism
                       for node in prog.nodes()}
                # mid-rescale the rollup can omit the aggregate (workers
                # restarting): record null, never a substituted total
                agg_rate = roll.get(agg_id, {}).get("records_per_sec")
                points.append({
                    "t": round(time.perf_counter() - t0, 2),
                    "parallelism": par[agg_id],
                    "total_parallelism": sum(par.values()),
                    "records_per_sec": (None if agg_rate is None
                                        else round(agg_rate, 1)),
                    "backpressure": roll.get(agg_id, {}).get(
                        "backpressure"),
                })
            state = await ctrl.wait_for_state(job_id, JobState.FINISHED,
                                              timeout=10)
            dt = time.perf_counter() - t0
            timeline = [d.to_json() for d in scaler.ledger.decisions()
                        if d.action != "hold"]
            return {
                "state": state.value, "wall_secs": round(dt, 2),
                "events": n,
                "events_per_sec": round(n / dt, 1),
                "final_parallelism": prog.node(agg_id).parallelism,
                "actuations": scaler.ledger.actuations,
                "vetoes": scaler.ledger.vetoes,
                "decision_timeline": timeline[-64:],
                "throughput_vs_parallelism": points,
            }
        finally:
            await ctrl.scheduler.stop_workers(job_id)
            await ctrl.stop()

    result = asyncio.run(scenario())
    with open(out_path) as f:
        produced = sum(json.loads(line)["cnt"] for line in f)
    result["output_events"] = produced
    result["exactly_once"] = produced == n
    return {"metric": "autoscale_elasticity", "unit": "decisions",
            "value": result["actuations"], "autoscale": result}


def run_correlated_windows() -> dict:
    """Correlated-windows family (factor-window sharing,
    graph/factor_windows.py): K in {2, 4, 8} sliding aggregates over
    the SAME input/keys with distinct widths (shared 2s slide), each K
    measured with factoring on (ARROYO_FACTOR_WINDOWS=auto) and off
    (=0).  Records events/s, pane-update kernel-dispatch counts per
    event, and the factor decision (shared_panes / derived_windows /
    cost_model_decision) per point.  The claim under test: factored
    per-event cost grows ~O(panes) — the shared ring pays ONE update
    per batch regardless of K — while unfactored cost grows ~O(K)
    (K private rings, K scatters).  ``cost_o_panes_ok`` asserts the
    factored dispatch growth from K=2 to K=8 stays well below the
    unfactored growth."""
    from arroyo_tpu.connectors.memory import clear_sink, sink_output
    from arroyo_tpu.engine.engine import LocalRunner
    from arroyo_tpu.obs import perf
    from arroyo_tpu.sql import plan_sql

    n = int(os.environ.get("BENCH_CORRELATED_EVENTS", 300_000))
    widths = [10, 4, 20, 6, 16, 8, 30, 14]  # seconds; slide 2s for all

    def sql_for(k: int) -> str:
        # 8k batches (not the headline 128k): pane firing must happen
        # continuously mid-stream, or the whole family degenerates to
        # one final flush and measures nothing about steady-state cost
        parts = [SRC.format(n=n, b=8192)]
        for i in range(k):
            parts.append(
                f"CREATE TABLE cw{i} (auction BIGINT, window_end BIGINT,"
                f" num BIGINT, tot BIGINT) WITH (connector = 'memory',"
                f" name = 'cw{i}', type = 'sink');")
            parts.append(
                f"INSERT INTO cw{i}\n"
                f"SELECT bid.auction as auction,\n"
                f"  HOP(INTERVAL '2' SECOND, INTERVAL '{widths[i]}'"
                f" SECOND) as window,\n"
                f"  count(*) AS num, sum(bid.price) AS tot\n"
                f"FROM nexmark WHERE bid is not null GROUP BY 1, 2;")
        return "\n".join(parts)

    prev = os.environ.get("ARROYO_FACTOR_WINDOWS")

    def measure(k: int, flag: str) -> dict:
        os.environ["ARROYO_FACTOR_WINDOWS"] = flag
        prog = plan_sql(sql_for(k), parallelism=bench_parallelism())
        preflight_validate(prog, "correlated_windows")
        decisions = [d.to_json()
                     for d in getattr(prog, "factor_decisions", [])]
        shared = [d for d in decisions if d["shared"]]
        for i in range(k):
            clear_sink(f"cw{i}")
        LocalRunner(prog).run()  # warm (compiles shared by both arms)
        before = {c: perf.counter(c)
                  for c in ("kernel_dispatches", "pane_update_rows",
                            "pane_update_dispatches")}
        for i in range(k):
            clear_sink(f"cw{i}")
        t0 = time.perf_counter()
        LocalRunner(prog).run()
        dt = time.perf_counter() - t0
        delta = {c: perf.counter(c) - v for c, v in before.items()}
        rows = sum(sum(len(b) for b in sink_output(f"cw{i}"))
                   for i in range(k))
        assert rows > 0, f"correlated_windows k={k} produced no output"
        return {
            "events_per_sec": round(n / dt, 1),
            "dispatches_per_event": round(
                delta["kernel_dispatches"] / max(n, 1), 6),
            # rows entering pane-update state per source event: ~K
            # unfactored (every private ring sees every event), ~1 +
            # O(panes) factored (derived rings see fired pane cells)
            "pane_update_rows_per_event": round(
                delta["pane_update_rows"] / max(n, 1), 4),
            "pane_update_dispatches": delta["pane_update_dispatches"],
            "output_rows": rows,
            "factor": {
                "shared_panes": len(shared),
                "derived_windows": sum(len(d["members"]) for d in shared),
                "pane_micros": (shared[0]["pane_micros"]
                                if shared else None),
                "cost_model_decision": (shared[0]["reason"] if shared
                                        else (decisions[0]["reason"]
                                              if decisions else
                                              "no_correlated_group")),
            },
        }

    points = []
    try:
        for k in (2, 4, 8):
            factored = measure(k, "auto")
            unfactored = measure(k, "0")
            assert factored["factor"]["shared_panes"] == 1, \
                f"k={k}: the factor pass did not share"
            assert factored["factor"]["derived_windows"] == k
            points.append({"k": k, "factored": factored,
                           "unfactored": unfactored})
            print(json.dumps({"correlated_windows_point": points[-1]}),
                  file=sys.stderr)
    finally:
        if prev is None:
            os.environ.pop("ARROYO_FACTOR_WINDOWS", None)
        else:
            os.environ["ARROYO_FACTOR_WINDOWS"] = prev

    by_k = {p["k"]: p for p in points}
    growth_f = (by_k[8]["factored"]["pane_update_rows_per_event"]
                / max(by_k[2]["factored"]["pane_update_rows_per_event"],
                      1e-12))
    growth_u = (by_k[8]["unfactored"]["pane_update_rows_per_event"]
                / max(by_k[2]["unfactored"]["pane_update_rows_per_event"],
                      1e-12))
    return {
        "metric": "correlated_windows",
        "events": n,
        "points": points,
        # K doubled twice (2 -> 8): unfactored pane-update work scales
        # ~4x (K private rings each consuming every event); factored
        # stays ~O(panes) — the shared ring consumes each event once and
        # the derived rings consume fired pane CELLS, whose count tracks
        # the pane grid, not K x events.  The margin absorbs the
        # real-but-small per-K derived-cell cost.
        "update_rows_growth_factored_2_to_8": round(growth_f, 3),
        "update_rows_growth_unfactored_2_to_8": round(growth_u, 3),
        "cost_o_panes_ok": bool(growth_f <= max(0.5 * growth_u, 1.25)),
        "speedup_at_8": round(
            by_k[8]["factored"]["events_per_sec"]
            / max(by_k[8]["unfactored"]["events_per_sec"], 1e-9), 3),
    }


def main_mesh_child() -> None:
    """One point of the mesh-scaling sweep: q5 (and a reduced join-
    stress run) at ONE mesh width, in its own process — XLA's device
    count and the mesh shape are frozen at backend init, so the sweep
    cannot share a process across widths.  Prints one JSON line with
    events/s plus the sharded-data-plane counters (reshards MUST be 0:
    the no-resharding invariant, measured per width)."""
    os.environ.setdefault("BATCH_SIZE", str(BATCH))
    os.environ.setdefault("STATE_CAPACITY", str(1 << 17))
    device = bench_device()
    from arroyo_tpu.connectors.memory import clear_sink, sink_output
    from arroyo_tpu.engine.engine import LocalRunner
    from arroyo_tpu.parallel import shuffle as _shuffle
    from arroyo_tpu.parallel.mesh_window import mesh_key_shards
    from arroyo_tpu.sql import plan_sql

    width = int(os.environ["BENCH_MESH_CHILD"])
    n = int(os.environ.get("BENCH_MESH_EVENTS", 300_000))
    prog = plan_sql(QUERIES["q5"].format(n=n, b=BATCH),
                    parallelism=bench_parallelism())
    preflight_validate(prog, "mesh_scaling_q5")
    clear_sink("results")
    LocalRunner(prog).run()  # warm: compiles out of the timed window
    before = _shuffle.shuffle_stats()
    best = None
    for _ in range(2):
        clear_sink("results")
        t0 = time.perf_counter()
        LocalRunner(prog).run()
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    assert sum(len(b) for b in sink_output("results")) > 0, \
        "mesh-sweep q5 produced no output"
    delta = {k: v - before[k]
             for k, v in _shuffle.shuffle_stats().items()}
    out = {
        "width": width,
        "device": device,
        "mesh_width": mesh_key_shards(),
        "events": n,
        "events_per_sec": round(n / best, 1),
        "reshards": delta["reshards"],
        "collectives": delta["collectives"],
        "host_shuffle_routes": delta["host_routes"],
    }
    if os.environ.get("BENCH_MESH_JOIN", "1") not in ("0", "false", "no"):
        os.environ.setdefault("BENCH_JOIN_STRESS_EVENTS", "120000")
        js = run_join_stress()
        out["join_stress_events_per_sec"] = js["value"]
        out["join_state"] = {
            k: js.get("join_state", {}).get(k)
            for k in ("hot_partitions", "ring_devices")}
    print(json.dumps(out))


def main_kernels_child() -> None:
    print(json.dumps(run_kernel_microbench()))


# families embedded in the headline child's line: (key, off-switch, runner).
# A family that raises fails the child, and with it the run.
FAMILIES = (
    ("latency", "BENCH_LATENCY", run_latency_family),
    ("config5", "BENCH_CONFIG5", run_config5),
    ("sessions_family", "BENCH_SESSIONS", run_sessions_family),
    ("join_stress", "BENCH_JOIN_STRESS", run_join_stress),
    ("decode", "BENCH_DECODE", run_decode_microbench),
    ("correlated_windows", "BENCH_FACTOR", run_correlated_windows),
)


def main_child() -> None:
    """One benchmark process: the query named by BENCH_QUERY plus the
    families its environment leaves on.  It holds the chip for its whole
    life and starts no process of its own — a chip belongs to one
    process at a time, so every child that needs it is launched by the
    supervisor (``main``), which stays off JAX."""
    os.environ.setdefault("BATCH_SIZE", str(BATCH))
    # pre-size keyed state near the expected Nexmark key cardinality so the
    # timed run never pays a capacity-growth recompile (config.py hint);
    # 2M-event q5 sees >32k distinct auctions, so 128k slots (~67 MB of
    # f64 state at B=16) keeps the whole run growth-free
    os.environ.setdefault("STATE_CAPACITY", str(1 << 17))
    device = bench_device()
    print(f"device: {json.dumps(device)}", file=sys.stderr)
    name = os.environ.get("BENCH_QUERY", "q5")
    result = run_query(name, QUERIES[name])
    result["backend"] = device["platform"]
    result["device"] = device
    result.update(run_latency())
    for key, switch, run in FAMILIES:
        if os.environ.get(switch, "1") not in ("0", "false", "no"):
            result[key] = run()
            print(json.dumps({key: result[key]}), file=sys.stderr)
    print(json.dumps(result))


# -- supervisor ---------------------------------------------------------------
#
# The supervisor never imports JAX, so it never holds the chip: it is the
# ONLY parent, and launches every chip-holding child in sequence (one per
# query, one per mesh width, one for the kernel microbench).  A child that
# fails, times out or prints nothing ends the run with a non-zero exit and
# no metric line.

BENCH_TIMEOUT = float(os.environ.get("BENCH_TIMEOUT", 2400))
# families ride the headline child only
FAMILIES_OFF = dict({switch: "0" for _, switch, _ in FAMILIES},
                    BENCH_LAT_SECS="0")


def run_child(label: str, timeout: float = BENCH_TIMEOUT, **env) -> dict:
    """Run one benchmark child to its end and return its JSON line."""
    try:
        r = subprocess.run(
            [sys.executable, os.path.abspath(__file__)],
            env=dict(os.environ, **env), stdout=subprocess.PIPE,
            timeout=timeout, text=True)
    except subprocess.TimeoutExpired:
        raise SystemExit(f"bench child {label} timed out after "
                         f"{timeout:.0f}s") from None
    if r.returncode != 0 or not r.stdout.strip():
        raise SystemExit(f"bench child {label} failed (rc={r.returncode})")
    line = json.loads(r.stdout.strip().splitlines()[-1])
    print(json.dumps({label: line}), file=sys.stderr)
    return line


def run_mesh_scaling() -> dict:
    """Mesh-scaling family: q5 + the join-stress family swept across
    mesh widths, one child per width.  With ``JAX_PLATFORMS=cpu`` the
    widths are fake XLA host devices
    (``--xla_force_host_platform_device_count``); otherwise the host's
    real chips carry the mesh, and a width beyond them fails its child.
    Records events/s per width, scaling efficiency vs width 1, and the
    reshard/collective counters."""
    widths = [int(w) for w in os.environ.get(
        "BENCH_MESH_WIDTHS", "1,2,4,8").split(",") if w.strip()]
    points = []
    for w in widths:
        env = {"BENCH_MESH_CHILD": str(w),
               "ARROYO_MESH": str(w) if w > 1 else "off"}
        flags = os.environ.get("XLA_FLAGS", "")
        if (os.environ.get("JAX_PLATFORMS") == "cpu"
                and "xla_force_host_platform_device_count" not in flags):
            env["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count="
                f"{max(widths)}").strip()
        points.append(run_child(
            f"mesh_width_{w}",
            float(os.environ.get("BENCH_MESH_TIMEOUT", 420)), **env))
    base = next((p["events_per_sec"] for p in points if p["width"] == 1),
                None)
    for p in points:
        if base:
            p["speedup_vs_width1"] = round(p["events_per_sec"] / base, 3)
            p["scaling_efficiency"] = round(
                p["events_per_sec"] / (base * max(p["width"], 1)), 3)
    return {"metric": "mesh_scaling", "widths": widths, "points": points}


def host_fingerprint() -> dict:
    """Machine/env fingerprint so cross-round numbers can be attributed.
    No jax import — the supervisor never touches the chip."""
    import platform

    fp = {
        "hostname": platform.node(),
        "machine": platform.machine(),
        "python": platform.python_version(),
        "cpu_count": os.cpu_count(),
    }
    r = subprocess.run(
        ["git", "-C", os.path.dirname(os.path.abspath(__file__)),
         "rev-parse", "--short", "HEAD"],
        capture_output=True, text=True, timeout=10)
    if r.returncode == 0:  # a bare copy of the tree has no .git
        fp["git"] = r.stdout.strip()
    with open("/proc/loadavg") as f:
        fp["loadavg_1m"] = float(f.read().split()[0])
    return fp


def main() -> None:
    headline = os.environ.get("BENCH_QUERY", "q5")
    if headline not in QUERIES:
        raise SystemExit(f"unknown BENCH_QUERY {headline!r}; "
                         f"choose from {sorted(QUERIES)}")
    queries = {}
    if os.environ.get("BENCH_ALL", "1") not in ("0", "false", "no", ""):
        # one child per query: queries measured in a shared process
        # degrade the later ones (allocator growth, jit-cache churn — q5
        # measured ~2x lower after three predecessors).  Every per-query
        # result is EMBEDDED in the single headline JSON line so the
        # driver artifact carries all BASELINE configs, not just q5
        for name in sorted(QUERIES):
            if name != headline:
                queries[name] = run_child(name, BENCH_CHILD="1",
                                          BENCH_QUERY=name, **FAMILIES_OFF)
    line = run_child(headline, BENCH_CHILD="1", BENCH_QUERY=headline)
    if queries:
        line["queries"] = queries
    if os.environ.get("BENCH_MESH_SWEEP", "1") not in ("0", "false", "no"):
        line["mesh_scaling"] = run_mesh_scaling()
    if os.environ.get("BENCH_KERNELS", "1") not in ("0", "false", "no"):
        line["kernel_bench"] = run_child(
            "kernels", float(os.environ.get("BENCH_KERNEL_TIMEOUT", 420)),
            BENCH_KERNELS_CHILD="1")
    line["fingerprint"] = host_fingerprint()
    print(json.dumps(line))


if __name__ == "__main__":
    if "--autoscale" in sys.argv[1:]:
        # elasticity mode: one process (InProcessScheduler workers), its
        # own line; it measures the control loop, not kernels
        bench_device()
        print(json.dumps(run_autoscale_bench()))
    elif os.environ.get("BENCH_KERNELS_CHILD"):
        main_kernels_child()
    elif os.environ.get("BENCH_MESH_CHILD"):
        main_mesh_child()
    elif os.environ.get("BENCH_CHILD"):
        main_child()
    else:
        main()
