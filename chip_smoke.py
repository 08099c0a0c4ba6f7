"""chip_smoke.py — the quickest proof that arroyo_tpu still starts on the chip.

Drives the main path once, through the entry points a user calls
(``arroyo_tpu.sql.plan_sql`` -> ``LocalRunner(prog).run()``, what
``python -m arroyo_tpu run q.sql`` does), in ONE process, the only one that
touches JAX:

  Phase A  NEXMark q5 (hot items) at a real size: 40 M events at an
           event-time rate of 1 M events/s (40 s of event time, 20 slides,
           four disjoint 10 s windows), ~2.4 M keys, the keyed bin planes
           growing past 0.5 GB of HBM (STATE_CAPACITY starts two doublings
           short, so capacity growth runs on the chip).
  Phase B  every other family the roadmap calls a cell, once, at default
           placement: q1 and q7 at bench.py's 2 M events, q8 at 12 M (its
           window join promotes a partition to a device ring only after a
           merge of >= 4096 rows, which takes one full 10 s window — at
           2 M events the device join cannot engage on any backend), and
           the config5 shape (session(1 s) + median UDAF over the
           in-process Kafka broker with 1 s checkpoints, 200 k events).

Every phase is compared row for row with a plain numpy reference of the
same semantics computed here from the same seed, and proves from the
engine's own counters that the device did the work.  No ``ARROYO_*``
switch and no ``JAX_PLATFORMS`` is set by this script, and nothing is
caught that is not re-raised: a phase that raises, a comparison that
fails, a device counter at zero, a missing native library or a platform
other than ``tpu`` all end in a non-zero exit with no result line.

The last line of a passing run is one JSON object:
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Sizes can be cut with explicit arguments (the cut is printed, never
silent).  ``--rehearse`` runs the phases on whatever backend JAX has
(``JAX_PLATFORMS=cpu`` in the sandbox) to debug the script itself; a
rehearsal still exits non-zero and prints no result line.
"""

import argparse
import json
import os
import sys
import tempfile
import time

FULL_A, FULL_B, FULL_Q8, FULL_C5 = 40_000_000, 2_000_000, 12_000_000, 200_000
BATCH = 131072
RATE = 1_000_000  # event-time events/s (bench.py's rate)
BASE_TIME = 1_700_000_000_000_000  # pinned event-time origin, 10 s aligned
EXIT_NOT_TPU = 3

SRC = """
CREATE TABLE nexmark WITH (
  connector = 'nexmark', event_rate = '{rate}', num_events = '{n}',
  rate_limited = 'false', batch_size = '{b}',
  base_time_micros = '{base}', seed = '{seed}'
);
"""

# query bodies: the text of bench.py's Q1/Q5/Q7/Q8
# (tests/test_chip_smoke.py holds the two in step)
Q1 = """
SELECT bid.auction as auction, bid.bidder as bidder,
       bid.price * 0.908 as price_dol, bid.datetime as datetime
FROM nexmark WHERE bid is not null
"""

Q5 = """
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

Q7 = """
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

Q8 = """
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

CONFIG5 = """
CREATE TABLE ev (
  k BIGINT, v DOUBLE, ts BIGINT,
  event_time TIMESTAMP GENERATED ALWAYS AS
    (CAST(from_unixtime(ts) as TIMESTAMP))
) WITH (
  connector = 'kafka', bootstrap_servers = 'memory://chipsmoke5',
  topic = 'sess', type = 'source', format = 'json',
  event_time_field = 'event_time', batch_size = '4096',
  max_messages = '{n}'
);
CREATE TABLE out WITH (connector = 'memory', name = 'results');
INSERT INTO out
SELECT k, median(v) as med, count(*) as cnt,
       session(INTERVAL '1' SECOND) as window
FROM ev GROUP BY 1, 4
"""

SEC = 1_000_000
SESSION_COUNTERS = ("session_device_merge_rows", "session_host_merge_rows",
                    "udaf_channel_rows", "udaf_host_rows")
MEDIAN_RTOL = 1e-9  # f64 on the TPU is emulated, not bit-IEEE
PRICE_RTOL = 1e-9   # (docs/architecture.md "Numeric fidelity policy")


def log(msg: str) -> None:
    print(msg, flush=True)


# -- compile accounting (jax.monitoring) --------------------------------------


class CompileClock:
    """Seconds JAX spent obtaining executables (XLA compile or a
    persistent-cache load: both sit inside the backend_compile event),
    how many of them came from the persistent cache, and which engine
    call site asked for each (the jitted kernels share one name, so the
    innermost ``arroyo_tpu`` frame on the compiling thread's stack names
    them)."""

    def __init__(self) -> None:
        import jax.monitoring as mon

        self.secs = 0.0
        self.requests = self.hits = 0
        self.sites = {}  # "file.py:function" -> [compiles, seconds]
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, name: str, secs: float, **_kw) -> None:
        if name != "/jax/core/compile/backend_compile_duration":
            return
        self.secs += secs
        f = sys._getframe()
        while f is not None and (
                "/arroyo_tpu/" not in f.f_code.co_filename
                or f.f_code.co_filename.endswith("obs/perf.py")):
            f = f.f_back
        site = ("(outside arroyo_tpu)" if f is None else
                f"{os.path.basename(f.f_code.co_filename)}:"
                f"{f.f_code.co_name}")
        entry = self.sites.setdefault(site, [0, 0.0])
        entry[0] += 1
        entry[1] += secs

    def _on_event(self, name: str, **_kw) -> None:
        if name == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif name == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def top_sites(self, n: int = 8) -> list:
        ranked = sorted(self.sites.items(), key=lambda kv: -kv[1][1])[:n]
        return [f"{site} x{cnt} {secs:.1f}s" for site, (cnt, secs) in ranked]


CLOCK = None  # the process's CompileClock, set by main()


# -- workload generation (shared by the engine's source and the references) ---


def nexmark_events(n: int, seed: int, want) -> dict:
    """The stream the engine's nexmark source emits for this (n, seed):
    same generator, same batch blocking, same pinned event-time origin."""
    import numpy as np

    from arroyo_tpu.connectors.nexmark import (
        NexmarkConfig,
        NexmarkGenerator,
        make_splits,
    )

    cfg = NexmarkConfig(num_events=n, event_rate=RATE, rate_limited=False,
                        batch_size=BATCH, projection=list(want))
    split = make_splits(cfg, BASE_TIME, 1)[0]
    gen = NexmarkGenerator(cfg, BASE_TIME, split[0], split[1], split[2],
                           seed=seed)
    gen.set_rate(cfg.event_rate, 1)
    names = ("event_type",) + tuple(want)
    parts = {c: [] for c in names}
    ts_parts = []
    while gen.has_next:
        batch, _ = gen.next_batch(BATCH)
        for c in names:
            parts[c].append(np.asarray(batch.columns[c]))
        ts_parts.append(batch.timestamp)
    out = {c: np.concatenate(v) for c, v in parts.items()}
    out["__ts"] = np.concatenate(ts_parts)
    return out


def _rows(*cols):
    """Canonical row set for comparison: an [n, k] array lexsorted by
    every column, so two multisets of rows are equal iff the arrays are."""
    import numpy as np

    m = np.stack([np.asarray(c) for c in cols], axis=1)
    return m[np.lexsort(m.T[::-1])]


def _rows_with(value, *cols):
    """(_rows(*cols), value in the same order): integer columns that must
    be exact beside one float column compared to a tolerance (ties among
    the integer rows break on the value)."""
    import numpy as np

    m = np.stack([np.asarray(c) for c in cols], axis=1)
    o = np.lexsort((value,) + tuple(m.T[::-1]))
    return m[o], np.asarray(value)[o]


# -- plain numpy references ---------------------------------------------------


def ref_q5(ev: dict):
    """Hot items from per-(auction, 2 s bin) counts and a 5-bin sliding
    sum: for every 10 s window sliding by 2 s, the auctions whose bid
    count equals the window's maximum.  Returns (rows[auction, num],
    windows with data, distinct auctions)."""
    import numpy as np

    bid = ev["event_type"] == 2
    auctions, a_idx = np.unique(ev["bid_auction"][bid], return_inverse=True)
    bins = ev["__ts"][bid] // (2 * SEC)
    b0 = int(bins.min())
    nb = int(bins.max()) - b0 + 1
    cell, cell_cnt = np.unique(a_idx * nb + (bins - b0), return_counts=True)
    cell_a, cell_b = cell // nb, cell % nb
    out_a, out_n = [], []
    windows = 0
    for last in range(nb + 4):  # window = bins (last-4 .. last)
        sel = (cell_b >= last - 4) & (cell_b <= last)
        if not sel.any():
            continue
        windows += 1
        cnt = np.bincount(cell_a[sel], weights=cell_cnt[sel],
                          minlength=len(auctions)).astype(np.int64)
        hot = np.nonzero(cnt == cnt.max())[0]
        out_a.append(auctions[hot])
        out_n.append(cnt[hot])
    return (_rows(np.concatenate(out_a), np.concatenate(out_n)), windows,
            len(auctions))


def ref_q1(ev: dict):
    import numpy as np

    bid = ev["event_type"] == 2
    return _rows_with(ev["bid_price"][bid].astype(np.float64) * 0.908,
                      ev["bid_auction"][bid], ev["bid_bidder"][bid],
                      ev["__ts"][bid])


def ref_q7(ev: dict):
    import numpy as np

    bid = ev["event_type"] == 2
    price, ts = ev["bid_price"][bid], ev["__ts"][bid]
    w = ts // (10 * SEC)
    w0 = int(w.min())
    wmax = np.zeros(int(w.max()) - w0 + 1, dtype=price.dtype)
    np.maximum.at(wmax, w - w0, price)
    top = price == wmax[w - w0]
    return (_rows(ev["bid_auction"][bid][top], price[top],
                  ev["bid_bidder"][bid][top]), len(wmax))


def ref_q8(ev: dict):
    import numpy as np

    ts = ev["__ts"]
    w = ts // (10 * SEC)
    person, auction = ev["event_type"] == 0, ev["event_type"] == 1
    span = int(w.max()) + 1

    pk, pc = np.unique(ev["person_id"][person] * span + w[person],
                       return_counts=True)
    ak, ac = np.unique(ev["auction_seller"][auction] * span + w[auction],
                       return_counts=True)
    both, pi, ai = np.intersect1d(pk, ak, return_indices=True)
    return _rows(both // span, pc[pi], ac[ai]), len(np.unique(both % span))


def config5_events(n: int, seed: int):
    """config5's traffic shape (bench.py ``_config5_produce``): 64 keys
    active per block of 6400 events, then retired, events 10 us apart —
    so 1 s-gap sessions keep closing as event time advances.  Values come
    from the seed, on a dyadic grid so a median is exact in any f64."""
    import numpy as np

    P, burst = 64, 100
    i = np.arange(n, dtype=np.int64)
    keys = (i % P) + (i // (P * burst)) * P
    ts = i * 10
    vals = np.random.default_rng(seed).integers(0, 1 << 20, n) / 8.0
    return keys, ts, vals


def ref_sessions(keys, ts, vals, gap: int = SEC):
    """Per-key session oracle (__graft_entry__.dryrun_multichip's): a key's
    events sorted by time split wherever the gap exceeds ``gap``; each run
    is one row (k, window_start, window_end, count, median)."""
    import numpy as np

    order = np.lexsort((ts, keys))
    k, t, v = keys[order], ts[order], vals[order]
    new = np.ones(len(k), dtype=bool)
    new[1:] = (k[1:] != k[:-1]) | (t[1:] - t[:-1] > gap)
    starts = np.nonzero(new)[0]
    ends = np.append(starts[1:], len(k))
    med = np.array([np.median(v[s:e]) for s, e in zip(starts, ends)])
    return _rows_with(med, k[starts], t[starts], t[ends - 1] + gap,
                      ends - starts)


# -- running a query through the normal entry points --------------------------


def run_sql(sql: str, provider=None, checkpoint_url=None):
    """plan_sql -> LocalRunner.run() (with 1 s checkpoints when a
    checkpoint URL is given); returns (sink batches, stats)."""
    from arroyo_tpu.connectors.memory import (
        clear_sink,
        sink_arrivals,
        sink_output,
    )
    from arroyo_tpu.engine.engine import LocalRunner
    from arroyo_tpu.obs import perf
    from arroyo_tpu.parallel import shuffle
    from arroyo_tpu.sql import plan_sql

    prog = plan_sql(sql, provider)
    clear_sink("results")
    k0 = perf.counter("kernel_dispatches")
    r0 = shuffle.shuffle_stats()["reshards"]
    c0 = CLOCK.secs
    t0 = time.monotonic()
    if checkpoint_url:
        LocalRunner(prog, checkpoint_url=checkpoint_url).run(
            checkpoint_interval_secs=1.0)
    else:
        LocalRunner(prog).run()
    t1 = time.monotonic()
    arrivals = sink_arrivals("results")
    stats = {
        "wall_secs": round(t1 - t0, 2),
        "first_result_secs": (round(min(arrivals) - t0, 2)
                              if arrivals else None),
        "compile_secs": round(CLOCK.secs - c0, 2),
        "kernel_dispatches": perf.counter("kernel_dispatches") - k0,
        "reshards": shuffle.shuffle_stats()["reshards"] - r0,
    }
    return list(sink_output("results")), stats


def nexmark_sql(body: str, n: int, seed: int) -> str:
    return SRC.format(rate=RATE, n=n, b=BATCH, base=BASE_TIME,
                      seed=seed) + body


def _col(outs, name):
    import numpy as np

    return np.concatenate([np.asarray(b.columns[name]) for b in outs])


def _require(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def _same_rows(got, want, what: str) -> None:
    import numpy as np

    _require(got.shape == want.shape,
             f"{what}: {len(got)} rows, reference has {len(want)}")
    bad = np.nonzero((got != want).any(axis=1))[0]
    _require(len(bad) == 0,
             f"{what}: {len(bad)} rows differ from the reference, first "
             f"got {got[bad[:1]].tolist()} want {want[bad[:1]].tolist()}")


def _device_did_work(stats: dict, what: str, stateless: bool = False
                     ) -> None:
    """Keyed state lives on the device, so a stateful phase must have
    dispatched kernels.  q1 (a stateless map) and q7 (planned as a raw
    per-window argmax over the host-resident stream, with no keyed state
    at all) keep nothing on the device: where the CPU backend sits beside
    the chip their chains run on the host ingest spine — the roadmap's
    "device does nothing" controls — so their dispatch count is printed,
    not required."""
    _require(stateless or stats["kernel_dispatches"] > 0,
             f"{what}: kernel_dispatches == 0, the device did no work")
    _require(stats["reshards"] == 0,
             f"{what}: {stats['reshards']} implicit reshards")


# -- phases -------------------------------------------------------------------


def phase_q5(n: int, seed: int, min_state_bytes: int) -> dict:
    import numpy as np

    from arroyo_tpu.obs.latency import device_state_tables

    outs, stats = run_sql(nexmark_sql(Q5, n, seed))
    want, windows, n_keys = ref_q5(nexmark_events(n, seed, ("bid_auction",)))
    got = _rows(_col(outs, "auction"), _col(outs, "num"))
    _same_rows(got, want, "q5")  # counts and maxima: exact
    _device_did_work(stats, "q5")
    state_bytes = device_state_tables().get("panes", 0)
    _require(state_bytes >= min_state_bytes,
             f"q5: device state {state_bytes} B < {min_state_bytes} B")
    fired = len(np.unique(np.concatenate([b.timestamp for b in outs])))
    _require(fired == windows,
             f"q5: {fired} windows fired, reference has {windows}")
    stats.update(events=n, event_time_secs=n / RATE, rows=len(got),
                 panes_fired=fired, keys=n_keys, state_bytes=state_bytes)
    return stats


def phase_q1(n: int, seed: int) -> dict:
    import numpy as np

    outs, stats = run_sql(nexmark_sql(Q1, n, seed))
    want_i, want_p = ref_q1(nexmark_events(
        n, seed, ("bid_auction", "bid_bidder", "bid_price")))
    # price * 0.908 is f64 arithmetic (emulated on the chip): the integer
    # columns sort the rows and must be exact, the product to a tolerance
    ints, price = _rows_with(_col(outs, "price_dol"), _col(outs, "auction"),
                             _col(outs, "bidder"), _col(outs, "datetime"))
    _same_rows(ints, want_i, "q1")
    err = float(np.max(np.abs(price - want_p) / np.abs(want_p)))
    _require(err <= PRICE_RTOL, f"q1: price_dol relative error {err}")
    _device_did_work(stats, "q1", stateless=True)
    stats.update(events=n, rows=len(ints), price_rel_err=err)
    return stats


def phase_q7(n: int, seed: int) -> dict:
    outs, stats = run_sql(nexmark_sql(Q7, n, seed))
    want, windows = ref_q7(nexmark_events(
        n, seed, ("bid_auction", "bid_price", "bid_bidder")))
    got = _rows(_col(outs, "auction"), _col(outs, "price"),
                _col(outs, "bidder"))
    _same_rows(got, want, "q7")
    _device_did_work(stats, "q7", stateless=True)
    stats.update(events=n, rows=len(got), panes_fired=windows)
    return stats


def phase_q8(n: int, seed: int) -> dict:
    from arroyo_tpu.obs import perf

    g0 = perf.counter("join_device_gather_rows")
    outs, stats = run_sql(nexmark_sql(Q8, n, seed))
    want, windows = ref_q8(nexmark_events(
        n, seed, ("person_id", "auction_seller")))
    got = _rows(_col(outs, "id"), _col(outs, "np"), _col(outs, "na"))
    _same_rows(got, want, "q8")
    _device_did_work(stats, "q8")
    gathered = perf.counter("join_device_gather_rows") - g0
    _require(gathered > 0, "q8: join_device_gather_rows == 0, the join "
             "did not materialise through the device payload rings")
    stats.update(events=n, rows=len(got), panes_fired=windows,
                 join_device_gather_rows=gathered)
    return stats


def phase_config5(n: int, seed: int) -> dict:
    import numpy as np

    from arroyo_tpu.connectors.kafka import InMemoryKafkaBroker
    from arroyo_tpu.obs import perf
    from arroyo_tpu.sql import SchemaProvider

    keys, ts, vals = config5_events(n, seed)
    InMemoryKafkaBroker.reset("chipsmoke5")
    broker = InMemoryKafkaBroker.get("chipsmoke5")
    broker.create_topic("sess", partitions=1)
    for k, v, t in zip(keys.tolist(), vals.tolist(), ts.tolist()):
        broker.produce("sess", b'{"k": %d, "v": %r, "ts": %d}'
                       % (k, v, t * 1000), partition=0)
    provider = SchemaProvider()
    provider.register_udaf("median", np.median)
    before = {c: perf.counter(c) for c in SESSION_COUNTERS}
    with tempfile.TemporaryDirectory(prefix="chipsmoke5-ckpt-") as ckpt:
        outs, stats = run_sql(CONFIG5.format(n=n), provider,
                              checkpoint_url=f"file://{ckpt}")
    want_i, want_med = ref_sessions(keys, ts, vals)
    ints, med = _rows_with(
        _col(outs, "med"), _col(outs, "k"), _col(outs, "window_start"),
        _col(outs, "window_end"), _col(outs, "cnt"))
    _same_rows(ints, want_i, "config5")
    err = float(np.max(np.abs(med - want_med)
                       / np.maximum(np.abs(want_med), 1.0)))
    _require(err <= MEDIAN_RTOL, f"config5: median relative error {err}")
    _device_did_work(stats, "config5")
    delta = {c: perf.counter(c) - before[c] for c in SESSION_COUNTERS}
    _require(delta["session_device_merge_rows"] > 0,
             "config5: session_device_merge_rows == 0, sessions merged "
             "on the host")
    _require(delta["udaf_host_rows"] == 0,
             f"config5: udaf_host_rows == {delta['udaf_host_rows']}, the "
             "UDAF ran the per-segment host loop")
    stats.update(events=n, rows=len(ints), median_rel_err=err, **delta)
    return stats


# -- main ---------------------------------------------------------------------


def device_report() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_report() -> list:
    """Bytes in use and peak per device, so state that sits on device 0
    only shows on a four-chip host."""
    import jax

    out = []
    for d in jax.devices():
        ms = d.memory_stats() or {}
        out.append({"id": d.id, "bytes_in_use": ms.get("bytes_in_use"),
                    "peak_bytes_in_use": ms.get("peak_bytes_in_use")})
    return out


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--events-a", type=int, default=FULL_A,
                    help="Phase A (q5) events")
    ap.add_argument("--events-b", type=int, default=FULL_B,
                    help="Phase B events for q1 and q7")
    ap.add_argument("--events-q8", type=int, default=FULL_Q8,
                    help="Phase B events for q8")
    ap.add_argument("--events-c5", type=int, default=FULL_C5,
                    help="Phase B config5 events")
    ap.add_argument("--state-capacity", type=int, default=1 << 20,
                    help="initial keyed-state slots (STATE_CAPACITY)")
    ap.add_argument("--min-state-gb", type=float, default=0.5,
                    help="Phase A must hold at least this much device state")
    ap.add_argument("--rehearse", action="store_true",
                    help="run the phases on a backend that is not a TPU "
                         "(debugging this script); still exits non-zero")
    args = ap.parse_args(argv)

    # sizes the engine reads from the environment, as bench.py sets them;
    # neither is an engine switch
    os.environ["STATE_CAPACITY"] = str(args.state_capacity)
    os.environ["BATCH_SIZE"] = str(BATCH)

    import jax

    import arroyo_tpu  # noqa: F401  (enables x64 before any array exists)
    from arroyo_tpu import native
    from arroyo_tpu.config import require_backend
    from arroyo_tpu.engine.aot import enable_persistent_cache
    from arroyo_tpu.parallel.mesh_window import mesh_key_shards

    global CLOCK
    CLOCK = CompileClock()
    cache_dir = enable_persistent_cache()
    platform = require_backend()
    dev = device_report()
    log(f"device: platform={dev['platform']} device_kind={dev['kind']} "
        f"count={dev['count']} mesh_width={mesh_key_shards()}")
    log(f"HAVE_NATIVE={native.HAVE_NATIVE} native_lib={native.library_path()}")
    log(f"compile_cache_dir={cache_dir} jax={jax.__version__} "
        f"seed={args.seed}")
    # what the machine handed us (read, never set): placement follows it —
    # the host ingest spine needs a CPU backend beside the chip
    from arroyo_tpu.ops.expr import _host_eval_device

    log(f"env: JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r} "
        f"JAX_COMPILATION_CACHE_DIR="
        f"{os.environ.get('JAX_COMPILATION_CACHE_DIR')!r} "
        f"cpu_backend_beside_chip={_host_eval_device() is not None} "
        f"host_cpus={os.cpu_count()}")
    if platform != "tpu" and not args.rehearse:
        log(f"chip_smoke: platform is {platform!r}, not 'tpu' — no result")
        return EXIT_NOT_TPU
    _require(native.HAVE_NATIVE, "native host library did not build/load: "
             "the numpy fallbacks are not the measured host path")
    for name, full in (("events-a", FULL_A), ("events-b", FULL_B),
                       ("events-q8", FULL_Q8), ("events-c5", FULL_C5)):
        got = getattr(args, name.replace("-", "_"))
        if got < full:
            log(f"CUT: --{name} {got} < {full} (scale only; widths, "
                "batch size and key distribution unchanged)")

    t_start = time.monotonic()
    phases = [
        ("A:q5", lambda: phase_q5(args.events_a, args.seed,
                                  int(args.min_state_gb * 1e9))),
        ("B:q1", lambda: phase_q1(args.events_b, args.seed)),
        ("B:q7", lambda: phase_q7(args.events_b, args.seed)),
        ("B:q8", lambda: phase_q8(args.events_q8, args.seed)),
        ("B:config5", lambda: phase_config5(args.events_c5, args.seed)),
    ]
    for name, fn in phases:
        log(f"phase {name} ...")
        stats = fn()
        stats["after_first_result_secs"] = (
            None if stats["first_result_secs"] is None else
            round(stats["wall_secs"] - stats["first_result_secs"], 2))
        log(f"phase {name} OK {json.dumps(stats)}")
        log(f"memory after {name}: {json.dumps(memory_report())}")
    log(f"total: wall_secs={time.monotonic() - t_start:.1f} "
        f"compile_secs={CLOCK.secs:.1f} compile_requests={CLOCK.requests} "
        f"persistent_cache_hits={CLOCK.hits}")
    log(f"compile seconds by call site: {CLOCK.top_sites()}")
    if platform != "tpu":
        log(f"chip_smoke: REHEARSAL on {platform!r} passed its comparisons; "
            "this is not a chip result")
        return EXIT_NOT_TPU
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
