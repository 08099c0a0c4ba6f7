"""Window operator correctness vs numpy oracles: tumbling/sliding bin
aggregation (the reference's aggregating_window semantics), generic windows,
sessions (merge/extend, windows.rs:430-636 test analog), TopN, and joins."""

import asyncio

import numpy as np
import pytest

from arroyo_tpu import AggKind, AggSpec, Batch, Program, SessionWindow, \
    SlidingWindow, Stream, TumblingWindow
from arroyo_tpu.connectors.memory import clear_sink, sink_output
from arroyo_tpu.engine.engine import LocalRunner

MS = 1_000  # micros
SEC = 1_000_000


def make_events(rng, n=5000, n_keys=20, t0=0, span=10 * SEC):
    ts = np.sort(rng.integers(t0, t0 + span, n)).astype(np.int64)
    keys = rng.integers(0, n_keys, n).astype(np.int64)
    vals = rng.integers(1, 100, n).astype(np.int64)
    return Batch(ts, {"k": keys, "v": vals})


def run_pipeline(batches, build, sink="out"):
    clear_sink(sink)
    prog = build(Stream.source("memory", {"batches": batches})
                 .watermark(max_lateness_micros=0))
    LocalRunner(prog).run()
    outs = sink_output(sink)
    return Batch.concat(outs) if outs else None


def oracle_windows(ts, keys, vals, width, slide):
    """Expected (key, window_end) -> (count, sum, min, max)."""
    out = {}
    for t, k, v in zip(ts.tolist(), keys.tolist(), vals.tolist()):
        first_end = (t // slide + 1) * slide
        e = first_end
        while e - width <= t < e:
            c, s, mn, mx = out.get((k, e), (0, 0, 1 << 60, -(1 << 60)))
            out[(k, e)] = (c + 1, s + v, min(mn, v), max(mx, v))
            e += slide
    return out


@pytest.mark.parametrize("width,slide", [(SEC, SEC), (2 * SEC, SEC),
                                         (SEC, 250 * MS)])
def test_bin_agg_matches_oracle(rng, width, slide):
    ev = make_events(rng)
    aggs = [AggSpec(AggKind.COUNT, None, "cnt"),
            AggSpec(AggKind.SUM, "v", "total"),
            AggSpec(AggKind.MIN, "v", "lo"),
            AggSpec(AggKind.MAX, "v", "hi")]
    out = run_pipeline(
        [ev],
        lambda s: s.key_by("k").sliding_aggregate(width, slide, aggs)
        .sink("memory", {"name": "out"}),
    )
    assert out is not None
    expected = oracle_windows(ev.timestamp, ev.columns["k"], ev.columns["v"],
                              width, slide)
    got = {}
    for i in range(len(out)):
        key = (int(out.columns["k"][i]), int(out.columns["window_end"][i]))
        got[key] = (int(out.columns["cnt"][i]), int(out.columns["total"][i]),
                    int(out.columns["lo"][i]), int(out.columns["hi"][i]))
    assert got == expected


def test_tumbling_agg_multiple_batches(rng):
    evs = [make_events(rng, n=1000, t0=i * SEC, span=SEC) for i in range(5)]
    aggs = [AggSpec(AggKind.COUNT, None, "cnt")]
    out = run_pipeline(
        evs,
        lambda s: s.key_by("k").tumbling_aggregate(SEC, aggs)
        .sink("memory", {"name": "out"}),
    )
    total = int(out.columns["cnt"].sum())
    assert total == 5000  # every event in exactly one tumbling window


def test_generic_window_aggregate(rng):
    ev = make_events(rng, n=2000, span=4 * SEC)
    aggs = [AggSpec(AggKind.COUNT, None, "cnt"),
            AggSpec(AggKind.AVG, "v", "avg_v")]
    out = run_pipeline(
        [ev],
        lambda s: s.key_by("k").window(TumblingWindow(SEC), aggs)
        .sink("memory", {"name": "out"}),
    )
    assert int(out.columns["cnt"].sum()) == 2000
    # avg within plausible range
    assert np.all(out.columns["avg_v"] >= 1) and np.all(out.columns["avg_v"] < 100)
    # key column values preserved
    assert "k" in out.columns


def test_generic_window_flatten(rng):
    ev = make_events(rng, n=500, span=2 * SEC)
    out = run_pipeline(
        [ev],
        lambda s: s.key_by("k").window(TumblingWindow(SEC), flatten=True)
        .sink("memory", {"name": "out"}),
    )
    assert len(out) == 500
    assert "window_end" in out.columns


def test_session_windows_merge():
    # key 1: events at 0, 1s, 2s with 1.5s gap -> one session [0, 2s+gap)
    # key 2: events at 0 and 5s -> two sessions
    gap = 1500 * MS
    ts = np.array([0, 1 * SEC, 2 * SEC, 0, 5 * SEC], dtype=np.int64)
    keys = np.array([1, 1, 1, 2, 2], dtype=np.int64)
    vals = np.ones(5, dtype=np.int64)
    ev = Batch(ts, {"k": keys, "v": vals})
    aggs = [AggSpec(AggKind.COUNT, None, "cnt")]
    out = run_pipeline(
        [ev],
        lambda s: s.key_by("k").window(SessionWindow(gap), aggs)
        .sink("memory", {"name": "out"}),
    )
    rows = sorted(
        (int(out.columns["k"][i]), int(out.columns["cnt"][i]),
         int(out.columns["window_start"][i]))
        for i in range(len(out)))
    assert rows == [(1, 3, 0), (2, 1, 0), (2, 1, 5 * SEC)]


def test_session_windows_max_size_clamp_splits():
    """Events chaining past the MAX_SESSION_SIZE clamp must START a new
    session (reference windows.rs clamp), not be swallowed by the
    vectorized interval merge (r4 review finding: the clamped union
    would silently drop the tail events)."""
    from arroyo_tpu.engine.operators_window import MAX_SESSION_SIZE_MICROS

    gap = 10 * SEC
    MAX = MAX_SESSION_SIZE_MICROS
    # batch 1: a 9s-spaced chain to MAX-5s — the per-event path (span_ok
    # routes there) clamps the merged session to [0, MAX).  batch 2:
    # events at MAX-1 (inside the clamped session) and MAX+2 — the
    # interval merge would clamp-truncate past MAX+2, so it must fall
    # back and split: MAX-1 joins session 1, MAX+2 opens session 2.
    ts1 = np.arange(0, MAX - 5 * SEC + 1, 9 * SEC, dtype=np.int64)
    ts2 = np.array([MAX - 1, MAX + 2], dtype=np.int64)
    aggs = [AggSpec(AggKind.COUNT, None, "cnt")]
    out = run_pipeline(
        [Batch(ts1, {"k": np.full(len(ts1), 7, np.int64),
                     "v": np.ones(len(ts1), np.int64)}),
         Batch(ts2, {"k": np.full(2, 7, np.int64),
                     "v": np.ones(2, np.int64)})],
        lambda s: s.key_by("k").window(SessionWindow(gap), aggs)
        .sink("memory", {"name": "out"}),
    )
    rows = sorted((int(out.columns["window_start"][i]),
                   int(out.columns["cnt"][i]))
                  for i in range(len(out)))
    assert rows == [(0, len(ts1) + 1), (MAX + 2, 1)], rows


def test_tumbling_top_n(rng):
    ev = make_events(rng, n=3000, n_keys=50, span=3 * SEC)
    out = run_pipeline(
        [ev],
        lambda s: s.key_by("k")
        .tumbling_aggregate(SEC, [AggSpec(AggKind.COUNT, None, "cnt")])
        .tumbling_top_n(SEC, 5, "cnt")
        .sink("memory", {"name": "out"}),
    )
    # at most 5 rows per window
    from collections import Counter

    per_window = Counter(out.columns["window_end"].tolist())
    assert all(v <= 5 for v in per_window.values())
    assert len(out) > 0


def test_window_join():
    # left: persons, right: auctions keyed by person/seller id
    t = lambda s: s * SEC
    lts = np.array([t(0.1), t(0.2), t(1.2)], dtype=np.int64)
    l = Batch(lts, {"pid": np.array([1, 2, 3], dtype=np.int64),
                    "name": np.array(["a", "b", "c"], dtype=object)})
    rts = np.array([t(0.3), t(0.4), t(0.5), t(1.5)], dtype=np.int64)
    r = Batch(rts, {"pid": np.array([1, 1, 9, 3], dtype=np.int64),
                    "auction": np.array([10, 11, 12, 13], dtype=np.int64)})

    clear_sink("out")
    from arroyo_tpu.graph.logical import TumblingWindow

    left = (Stream.source("memory", {"batches": [l]})
            .watermark(max_lateness_micros=0).key_by("pid"))
    right = (Stream.source("memory", {"batches": [r]},
                           program=left.program)
             .watermark(max_lateness_micros=0).key_by("pid"))
    prog = (left.window_join(right, TumblingWindow(SEC))
            .sink("memory", {"name": "out"}))
    LocalRunner(prog).run()
    out = Batch.concat(sink_output("out"))
    # window [0,1s): person 1 matches auctions 10,11; window [1s,2s): person 3 -> 13
    pairs = sorted(zip(out.columns["pid"].tolist(),
                       out.columns["auction"].tolist()))
    assert pairs == [(1, 10), (1, 11), (3, 13)]


def test_join_with_expiration():
    t = lambda s: int(s * SEC)
    l = Batch(np.array([t(0.1)], dtype=np.int64),
              {"id": np.array([7], dtype=np.int64),
               "lv": np.array([100], dtype=np.int64)})
    r = Batch(np.array([t(0.2)], dtype=np.int64),
              {"id": np.array([7], dtype=np.int64),
               "rv": np.array([200], dtype=np.int64)})
    clear_sink("out")
    left = (Stream.source("memory", {"batches": [l]})
            .watermark(max_lateness_micros=0).key_by("id"))
    right = (Stream.source("memory", {"batches": [r]}, program=left.program)
             .watermark(max_lateness_micros=0).key_by("id"))
    prog = (left.join_with_expiration(right, 10 * SEC, 10 * SEC)
            .sink("memory", {"name": "out"}))
    LocalRunner(prog).run()
    out = Batch.concat(sink_output("out"))
    assert len(out) == 1
    assert int(out.columns["lv"][0]) == 100 and int(out.columns["rv"][0]) == 200


def test_non_window_aggregate(rng, monkeypatch):
    from arroyo_tpu.types import UPDATE_OP_COLUMN

    # refinement granularity is per input batch: input coalescing would
    # legitimately merge the two fragments into one create — disable it
    # so this test keeps pinning the create-then-update sequence
    monkeypatch.setenv("ARROYO_COALESCE", "0")
    ev1 = Batch(np.array([100, 200], dtype=np.int64),
                {"k": np.array([1, 1], dtype=np.int64),
                 "v": np.array([10, 20], dtype=np.int64)})
    ev2 = Batch(np.array([300], dtype=np.int64),
                {"k": np.array([1], dtype=np.int64),
                 "v": np.array([5], dtype=np.int64)})
    out = run_pipeline(
        [ev1, ev2],
        lambda s: s.key_by("k")
        .non_window_aggregate(60 * SEC, [AggSpec(AggKind.SUM, "v", "total")])
        .sink("memory", {"name": "out"}),
    )
    totals = out.columns["total"].tolist()
    ops = out.columns[UPDATE_OP_COLUMN].tolist()
    assert totals == [30.0, 35.0]
    assert ops == [0, 1]  # create then update


def test_out_of_order_within_lateness():
    """Events arriving out of order (within lateness) still land in the right
    windows — the watermark holds back by max_lateness."""
    ts = np.array([2 * SEC, SEC // 2, 3 * SEC, SEC + 100], dtype=np.int64)
    ev = Batch(ts, {"k": np.zeros(4, dtype=np.int64),
                    "v": np.ones(4, dtype=np.int64)})
    clear_sink("out")
    prog = (Stream.source("memory", {"batches": [ev]})
            .watermark(max_lateness_micros=4 * SEC)
            .key_by("k")
            .tumbling_aggregate(SEC, [AggSpec(AggKind.COUNT, None, "cnt")])
            .sink("memory", {"name": "out"}))
    LocalRunner(prog).run()
    out = Batch.concat(sink_output("out"))
    per_window = {int(out.columns["window_end"][i]): int(out.columns["cnt"][i])
                  for i in range(len(out))}
    assert per_window == {SEC: 1, 2 * SEC: 1, 3 * SEC: 1, 4 * SEC: 1}


def test_null_skipping_aggregates(rng):
    """Nulls (None in object columns -> NaN) are SKIPPED by SUM/MIN/MAX/AVG
    and by COUNT(col), and AVG divides by the NON-NULL row count — not the
    pane row count (reference nulls-skipping semantics,
    aggregating_window.rs; round-1 bug: avg used the shared pane count)."""
    n = 400
    ts = np.sort(rng.integers(0, 2 * SEC, n)).astype(np.int64)
    keys = rng.integers(0, 5, n).astype(np.int64)
    vals = rng.integers(1, 100, n).astype(np.int64)
    null_mask = rng.random(n) < 0.4
    col = np.array([None if m else int(v)
                    for v, m in zip(vals, null_mask)], dtype=object)
    ev = Batch(ts, {"k": keys, "v": col})
    aggs = [AggSpec(AggKind.COUNT, None, "cnt"),
            AggSpec(AggKind.COUNT, "v", "cnt_v"),
            AggSpec(AggKind.SUM, "v", "total"),
            AggSpec(AggKind.AVG, "v", "mean"),
            AggSpec(AggKind.MIN, "v", "lo"),
            AggSpec(AggKind.MAX, "v", "hi")]
    out = run_pipeline(
        [ev],
        lambda s: s.key_by("k").tumbling_aggregate(SEC, aggs)
        .sink("memory", {"name": "out"}),
    )
    assert out is not None
    # oracle over non-null rows per (key, window)
    exp = {}
    for t, k, v, m in zip(ts.tolist(), keys.tolist(), vals.tolist(),
                          null_mask.tolist()):
        e = (t // SEC + 1) * SEC
        c_all, c_v, s, mn, mx = exp.get((k, e), (0, 0, 0, None, None))
        c_all += 1
        if not m:
            c_v += 1
            s += v
            mn = v if mn is None else min(mn, v)
            mx = v if mx is None else max(mx, v)
        exp[(k, e)] = (c_all, c_v, s, mn, mx)
    seen = set()
    for i in range(len(out)):
        key = (int(out.columns["k"][i]), int(out.columns["window_end"][i]))
        c_all, c_v, s, mn, mx = exp[key]
        seen.add(key)
        assert int(out.columns["cnt"][i]) == c_all
        assert int(out.columns["cnt_v"][i]) == c_v
        if c_v == 0:  # all-null pane: every column agg is NULL (NaN)
            for c in ("total", "mean", "lo", "hi"):
                assert np.isnan(out.columns[c][i]), (key, c)
        else:
            assert int(out.columns["total"][i]) == s
            assert out.columns["mean"][i] == pytest.approx(s / c_v, rel=1e-5)
            assert int(out.columns["lo"][i]) == mn
            assert int(out.columns["hi"][i]) == mx
    assert seen == set(exp)


def test_sum_exactness_hot_key_large_magnitudes(rng):
    """Numeric-fidelity policy (keyed_bins.ACC_DTYPE): SUM of int64 prices
    over a hot key must equal the exact integer oracle even when the
    per-cell magnitude passes 2^24 (where f32 accumulators drift — the
    reference aggregates in exact i64, aggregating_window.rs).  500k rows
    into ONE (key, bin) cell with values ~10^6 sums to ~5*10^11 >> 2^24."""
    from arroyo_tpu.ops.keyed_bins import KeyedBinState
    from arroyo_tpu.graph.logical import AggKind, AggSpec

    n = 500_000
    ts = rng.integers(0, SEC, n).astype(np.int64)  # all in one bin
    keys = np.zeros(n, dtype=np.int64)  # one hot key
    vals = rng.integers(1_000_000, 2_000_000, n).astype(np.int64)
    from arroyo_tpu.types import hash_columns

    kh = hash_columns([keys])
    aggs = (AggSpec(AggKind.SUM, "v", "total"),
            AggSpec(AggKind.COUNT, None, "cnt"),
            AggSpec(AggKind.AVG, "v", "mean"))
    st = KeyedBinState(aggs, SEC, SEC, capacity=16)
    # feed in chunks so cross-batch accumulation is exercised too
    for s in range(0, n, 50_000):
        e = s + 50_000
        st.update(kh[s:e], ts[s:e], {"v": vals[s:e]})
    f = st.fire_panes(1 << 60, final=True)
    assert f is not None
    _kk, oc, _wend, _cnt, _slots = f
    exact = int(vals.sum())  # ~7.5e11, exact in int64 and in f64 < 2^53
    assert int(oc["total"][0]) == exact
    assert int(oc["cnt"][0]) == n
    assert oc["mean"][0] == pytest.approx(exact / n, rel=1e-12)


def test_mesh_sum_exactness_hot_key(rng):
    """Same exactness pin for the mesh-sharded state."""
    from arroyo_tpu.parallel.mesh_window import MeshKeyedBinState
    from arroyo_tpu.graph.logical import AggKind, AggSpec
    from arroyo_tpu.types import hash_columns

    n = 200_000
    ts = rng.integers(0, SEC, n).astype(np.int64)
    keys = np.zeros(n, dtype=np.int64)
    vals = rng.integers(1_000_000, 2_000_000, n).astype(np.int64)
    kh = hash_columns([keys])
    aggs = (AggSpec(AggKind.SUM, "v", "total"),)
    st = MeshKeyedBinState(aggs, SEC, SEC, capacity=16, n_shards=4)
    for s in range(0, n, 50_000):
        e = s + 50_000
        st._lookup_or_insert(kh[s:e])
        st.update(kh[s:e], ts[s:e], {"v": vals[s:e]})
    f = st.fire_panes(1 << 60, final=True)
    assert f is not None
    _kk, oc, _wend, _cnt, _slots = f
    assert int(oc["total"][0]) == int(vals.sum())


def test_ring_growth_does_not_ghost_duplicate(rng):
    """Two interleaved streams with far-apart time bases (e.g. impulse
    splits whose wall-clock bases drifted during jit compiles) force a
    mid-stream ring growth: growing must NOT replicate old ring slots
    into the newly-spanned bin range.  Regression for the ghost
    duplication where _grow_ring copied [min, max] AFTER the new batch
    had already extended the bounds."""
    from arroyo_tpu.graph.logical import AggKind, AggSpec
    from arroyo_tpu.ops.keyed_bins import KeyedBinState
    from arroyo_tpu.types import hash_columns

    aggs = (AggSpec(AggKind.COUNT, None, "cnt"),
            AggSpec(AggKind.SUM, "v", "total"))
    nA = nB = 2000
    tsA = np.sort(rng.integers(0, 120_000, nA)).astype(np.int64)
    tsB = np.sort(rng.integers(1_500_000, 1_620_000, nB)).astype(np.int64)
    kA = rng.integers(0, 4, nA).astype(np.int64)
    kB = rng.integers(0, 4, nB).astype(np.int64)
    vA = rng.integers(1, 100, nA).astype(np.int64)
    vB = rng.integers(1, 100, nB).astype(np.int64)
    khA, khB = hash_columns([kA]), hash_columns([kB])

    exp = {}
    for ts, kh, vv in ((tsA, khA, vA), (tsB, khB, vB)):
        for t, k, v in zip(ts.tolist(), kh.tolist(), vv.tolist()):
            b = t // 100_000
            for e in (b, b + 1):  # W/slide = 2 panes per event
                c, s = exp.get((k, e), (0, 0))
                exp[(k, e)] = (c + 1, s + v)

    st = KeyedBinState(aggs, 100_000, 200_000, capacity=16)
    got = {}

    def fire(wm, final=False):
        f = st.fire_panes(wm, final=final)
        if f:
            kk, oc, wend, *_ = f
            for j in range(len(kk)):
                key = (int(kk[j]), int(wend[j]) // 100_000 - 1)
                assert key not in got, f"pane refire {key}"
                got[key] = (int(oc["cnt"][j]), int(oc["total"][j]))

    stepsA = np.array_split(np.arange(nA), 4)
    stepsB = np.array_split(np.arange(nB), 4)
    for ia, ib in zip(stepsA, stepsB):
        st.update(khA[ia], tsA[ia], {"v": vA[ia]})
        st.update(khB[ib], tsB[ib], {"v": vB[ib]})
        fire(int(min(tsA[ia[-1]], tsB[ib[0]])))
    fire(1 << 60, final=True)
    assert got == exp


def test_min_max_beyond_float32_range():
    """MIN/MAX null identities are f64 extremes: values beyond the f32
    range (+/-3.4e38) must survive both aggregation paths instead of
    clipping to the identity."""
    from arroyo_tpu.graph.logical import AggKind, AggSpec
    from arroyo_tpu.ops.keyed_bins import KeyedBinState
    from arroyo_tpu.ops.segment import segment_aggregate
    from arroyo_tpu.types import hash_columns

    vals = np.array([-1e300, 1e300, np.nan], dtype=np.float64)
    ts = np.array([100, 200, 300], dtype=np.int64)
    kh = hash_columns([np.zeros(3, dtype=np.int64)])
    aggs = (AggSpec(AggKind.MIN, "v", "lo"), AggSpec(AggKind.MAX, "v", "hi"))

    st = KeyedBinState(aggs, SEC, SEC, capacity=16)
    st.update(kh, ts, {"v": vals})
    _k, oc, _w, _c, _s = st.fire_panes(1 << 60, final=True)
    assert oc["lo"][0] == -1e300 and oc["hi"][0] == 1e300

    _u, cols, _t, _rc, _vc = segment_aggregate(kh, ts, {"v": vals}, aggs)
    assert cols["lo"][0] == -1e300 and cols["hi"][0] == 1e300


def test_segment_aggregate_host_branch_parity(rng, monkeypatch):
    """The host-pinned numpy-reduceat branch of segment_aggregate
    (ops/segment._segment_host) must match the device kernel on every
    channel kind — sums to f64 association tolerance, min/max/count
    exactly — including null skipping and all-null segments."""
    from arroyo_tpu.graph.logical import AggKind, AggSpec
    from arroyo_tpu.ops.segment import segment_aggregate

    n = 4000
    kh = rng.integers(0, 60, n).astype(np.uint64)
    ts = rng.integers(0, 10**7, n).astype(np.int64)
    v = rng.standard_normal(n)
    v[rng.random(n) < 0.15] = np.nan
    v[kh == kh.min()] = np.nan  # one all-null segment
    aggs = (AggSpec(AggKind.SUM, "v", "s"), AggSpec(AggKind.MIN, "v", "mn"),
            AggSpec(AggKind.MAX, "v", "mx"),
            AggSpec(AggKind.COUNT, None, "c"),
            AggSpec(AggKind.AVG, "v", "a"),
            AggSpec(AggKind.COUNT, "v", "cv"))
    monkeypatch.setenv("ARROYO_SEGMENT_HOST", "0")
    dev = segment_aggregate(kh, ts, {"v": v}, aggs)
    monkeypatch.setenv("ARROYO_SEGMENT_HOST", "1")
    host = segment_aggregate(kh, ts, {"v": v}, aggs)
    np.testing.assert_array_equal(dev[0], host[0])
    for k in ("s", "a"):
        np.testing.assert_allclose(dev[1][k], host[1][k], rtol=1e-12,
                                   equal_nan=True, err_msg=k)
    for k in ("mn", "mx", "c", "cv"):
        np.testing.assert_array_equal(dev[1][k], host[1][k], err_msg=k)
    np.testing.assert_array_equal(dev[3], host[3])
    for k in dev[4]:
        np.testing.assert_array_equal(dev[4][k], host[4][k], err_msg=k)


def test_apply_top_n_host_device_boundary_parity(rng):
    """_apply_top_n routes to the device segment_top_k only at >= 512
    rows: the kept-row set AND the materialized rank column must agree
    across the boundary (same data, padded to cross it)."""
    from arroyo_tpu.engine.operators_window import _apply_top_n

    n = 511
    part = rng.integers(0, 23, n).astype(np.int64)
    vals = rng.integers(0, 40, n).astype(np.int64)  # ties included

    def run(nn):
        b = Batch(np.zeros(nn, dtype=np.int64),
                  {"p": part[:nn] if nn <= n else np.concatenate(
                      [part, part[:nn - n]]),
                   "v": vals[:nn] if nn <= n else np.concatenate(
                      [vals, vals[:nn - n]])})
        out = _apply_top_n(b, ("p",), "v", 3, rank_column="rn")
        return out

    # host path (511) vs device path (512: one duplicated row appended)
    host = run(511)
    dev = run(512)
    def canon(o, limit):
        return sorted(zip(o.columns["p"].tolist()[:limit],
                          o.columns["v"].tolist()[:limit],
                          o.columns["rn"].tolist()[:limit]))
    # the appended row can displace at most itself; compare the common
    # prefix semantics: per-partition (value, rank) multisets must agree
    # for partitions untouched by the duplicate
    dup_part = int(part[0])
    hrows = [(p, v, r) for p, v, r in canon(host, len(host))
             if p != dup_part]
    drows = [(p, v, r) for p, v, r in canon(dev, len(dev))
             if p != dup_part]
    assert hrows == drows
    assert set(host.columns["rn"].tolist()) <= {1, 2, 3}
    assert set(dev.columns["rn"].tolist()) <= {1, 2, 3}


def test_device_topk_matches_host_lexsort(rng):
    """ops/topk.segment_top_k == the host lexsort rank-per-partition, at
    sizes crossing the device-dispatch threshold, with ties."""
    from arroyo_tpu.ops.topk import segment_top_k

    for n, k in [(700, 3), (4096, 5), (513, 1)]:
        part = rng.integers(0, 37, n).astype(np.int64)
        vals = rng.integers(0, 50, n).astype(np.int64)  # plenty of ties
        got = segment_top_k(part, vals, k)
        order = np.lexsort((-vals.astype(np.float64), part))
        ps = part[order]
        is_start = np.ones(n, dtype=bool)
        is_start[1:] = ps[1:] != ps[:-1]
        seg_id = np.cumsum(is_start) - 1
        rank = np.arange(n) - is_start.nonzero()[0][seg_id]
        exp = np.sort(order[rank < k])
        np.testing.assert_array_equal(got, exp)


@pytest.mark.parametrize("probe", ["search", "merged"])
def test_device_join_pairs_matches_host(rng, monkeypatch, probe):
    """ops/join.join_pairs: the device sort/probe/expand kernels must
    produce exactly the host fallback's (lo, ro, lidx, ridx, counts) —
    including multi-match fan-out, empty intersections, and sizes
    crossing the pad buckets — on both the searchsorted probe and the
    TPU merged-rank probe (ops/join._merged_probe)."""
    from arroyo_tpu.ops import join as dj

    monkeypatch.setenv("ARROYO_JOIN_PROBE", probe)
    for nl, nr, span in [(5, 7, 4), (600, 300, 50), (2048, 4096, 130),
                         (1000, 1, 9), (1, 1000, 9)]:
        lk = rng.integers(0, span, nl).astype(np.uint64)
        rk = rng.integers(0, span, nr).astype(np.uint64)
        if span == 130:
            # exercise the hi/lo word split: keys above 2^32 whose low
            # words collide across distinct high words
            hi = rng.integers(0, 3, nl).astype(np.uint64) << np.uint64(32)
            lk = lk | hi
            rk = rk | (rng.integers(0, 3, nr).astype(np.uint64)
                       << np.uint64(32))
        monkeypatch.setenv("ARROYO_DEVICE_JOIN", "off")
        h = dj.join_pairs(lk, rk)
        monkeypatch.setenv("ARROYO_DEVICE_JOIN", "on")
        d = dj.join_pairs(lk, rk)
        for name, hv, dv in zip(("lo", "ro", "lidx", "ridx", "counts"),
                                h, d):
            np.testing.assert_array_equal(hv, dv, err_msg=f"{name} "
                                          f"nl={nl} nr={nr}")


def test_device_join_sentinel_collision_falls_back(monkeypatch):
    """A real key equal to the pad sentinel routes to the host path and
    still joins correctly."""
    from arroyo_tpu.ops import join as dj

    monkeypatch.setenv("ARROYO_DEVICE_JOIN", "on")
    lk = np.array([3, dj.SENTINEL, 5], dtype=np.uint64)
    rk = np.array([dj.SENTINEL, 5], dtype=np.uint64)
    lo, ro, lidx, ridx, counts = dj.join_pairs(lk, rk)
    pairs = {(int(lk[lo[i]]), int(rk[ro[j]]))
             for i, j in zip(lidx.tolist(), ridx.tolist())}
    assert pairs == {(int(dj.SENTINEL), int(dj.SENTINEL)), (5, 5)}


def test_i32_counts_plane_promotes_to_i64(monkeypatch):
    """COUNT(*) reads the i32 counts plane directly (no f64 channel rides
    the transfer), so once total ingested rows could wrap an i32 cell or
    pane sum the plane must promote to i64 — otherwise a hot key wraps to
    a negative count (code-review r4 finding)."""
    import jax.numpy as jnp

    from arroyo_tpu.ops.keyed_bins import KeyedBinState

    monkeypatch.setattr(KeyedBinState, "_i32_promote", 600)
    aggs = (AggSpec(kind=AggKind.COUNT, column=None, output="n"),)
    st = KeyedBinState(aggs, slide_micros=1000, width_micros=1000,
                       capacity=16)
    rng = np.random.default_rng(3)
    total = 0
    for _ in range(5):
        n = 200
        keys = rng.integers(0, 3, n).astype(np.uint64)
        ts = np.zeros(n, dtype=np.int64)  # one bin, one hot pane
        st.update(keys, ts, {})
        total += n
    assert st.counts.dtype == jnp.int64  # crossed the promotion threshold
    # total_rows survives a checkpoint round-trip (snapshot before the
    # final fire: firing evicts the bins, legitimately zeroing the mass)
    st2 = KeyedBinState(aggs, 1000, 1000, capacity=16)
    st2.restore(st.snapshot())
    assert st2.total_rows == total
    keys_o, cols, wend, cnts, _slots = st.fire_panes(10**9, final=True)
    assert int(cols["n"].sum()) == total  # every row counted, no wrap
    # ring emission follows the promoted dtype instead of recasting i32
    monkeypatch.setenv("ARROYO_RING", "on")
    st3 = KeyedBinState(aggs, 1000, 1000, capacity=16)
    st3.restore(st2.snapshot())
    assert st3.counts.dtype == jnp.int64
    k3, c3, w3, n3, _s3 = st3.fire_panes(10**9, final=True)
    assert n3.dtype == np.int64
    assert int(c3["n"].sum()) == total


def test_count_star_skips_f64_transfer():
    """A bare COUNT(*) query ships no f64 emit channels at all — the
    aggregate IS the counts plane (a smaller transfer); mixed
    aggs keep their channels and stay correct alongside it."""
    from arroyo_tpu.ops.keyed_bins import KeyedBinState

    aggs = (AggSpec(kind=AggKind.COUNT, column=None, output="n"),
            AggSpec(kind=AggKind.SUM, column="v", output="s"))
    st = KeyedBinState(aggs, slide_micros=1000, width_micros=2000,
                       capacity=16)
    assert st._dup_ch == (0,)
    # channels that ride the transfer: SUM + its validity, not COUNT(*)
    assert st._ch_kinds[st._xfer_ch[0]] == "sum"
    rng = np.random.default_rng(4)
    n = 500
    keys = rng.integers(0, 5, n).astype(np.uint64)
    ts = rng.integers(0, 5000, n).astype(np.int64)
    v = rng.normal(size=n)
    st.update(keys, ts, {"v": v})
    keys_o, cols, wend, cnts, _slots = st.fire_panes(10**9, final=True)
    assert int(cols["n"].sum()) == 2 * n  # each row in W=2 panes
    np.testing.assert_array_equal(cols["n"], cnts)  # COUNT(*) == row count
    oracle = {}
    for k, t, vv in zip(keys, ts, v):
        b = t // 1000
        for pane in range(b, b + 2):
            key = (int(k), int((pane + 1) * 1000))
            c, s = oracle.get(key, (0, 0.0))
            oracle[key] = (c + 1, s + vv)
    for i in range(len(keys_o)):
        c, s = oracle[(int(keys_o[i]), int(wend[i]))]
        assert cols["n"][i] == c
        assert np.isclose(cols["s"][i], s, rtol=1e-12)


def test_compact_emission_matches_dense(monkeypatch):
    """Device-compacted emission (two-phase nnz + gather) returns exactly
    the dense path's rows, in the same row-major order, for every agg
    kind incl. null-skipping AVG."""
    from arroyo_tpu.ops.keyed_bins import KeyedBinState

    aggs = (AggSpec(kind=AggKind.COUNT, column=None, output="n"),
            AggSpec(kind=AggKind.SUM, column="v", output="s"),
            AggSpec(kind=AggKind.AVG, column="w", output="a"),
            AggSpec(kind=AggKind.MIN, column="v", output="mn"))
    rng = np.random.default_rng(11)
    n = 4000
    keys = rng.integers(0, 50, n).astype(np.uint64)
    ts = rng.integers(0, 9000, n).astype(np.int64)
    v = rng.normal(size=n)
    w = rng.normal(size=n)
    w[rng.random(n) < 0.4] = np.nan

    def dense_emit(self, ring, bin_ok, kpad):
        """The fire's cells from the dense emit kernel and a host scan."""
        import jax.numpy as jnp

        from arroyo_tpu.ops import keyed_bins

        kernel = keyed_bins._emit_kernel(self._ch_kinds, self.C, self.B,
                                         self.W, kpad, self._xfer_ch)
        outs, cnts = kernel(self.values, self.counts, jnp.asarray(ring),
                            jnp.asarray(bin_ok))
        used = self.next_slot
        outs = keyed_bins._readback(self, outs)[:, :used]
        cnts = keyed_bins._readback(self, cnts)[:used]
        key_idx, pane_idx = np.nonzero(cnts)
        # the head's half hands over (nnz, (idx2, counts, channels))
        return len(key_idx), (np.stack([key_idx, pane_idx]),
                              cnts[key_idx, pane_idx],
                              outs[:, key_idx, pane_idx])

    def run(mode):
        if mode == "dense":
            monkeypatch.setattr(KeyedBinState, "_emit_compact", dense_emit)
        else:
            monkeypatch.undo()
        st = KeyedBinState(aggs, slide_micros=1000, width_micros=4000,
                           capacity=64)
        out = []
        for i in range(0, n, 800):
            sl = slice(i, i + 800)
            st.update(keys[sl], ts[sl], {"v": v[sl], "w": w[sl]})
            r = st.fire_panes(int(ts[sl].max()))  # mid-stream fires too
            if r is not None:
                out.append(r)
        r = st.fire_panes(10 ** 9, final=True)
        if r is not None:
            out.append(r)
        return out

    dense = run("dense")
    comp = run("compact")
    assert len(dense) == len(comp) >= 2
    for (k1, c1, w1, n1, s1), (k2, c2, w2, n2, s2) in zip(dense, comp):
        np.testing.assert_array_equal(k1, k2)
        np.testing.assert_array_equal(s1, s2)
        np.testing.assert_array_equal(w1, w2)
        np.testing.assert_array_equal(n1, n2)
        for name in ("n", "s", "a", "mn"):
            np.testing.assert_allclose(c1[name].astype(float),
                                       c2[name].astype(float),
                                       rtol=1e-12, atol=1e-15)


def test_count_over_u16_survives_restore():
    """70k rows in one (key, bin) cell once wrapped COUNT(*) to
    70000 % 65536 = 4464 through a checkpoint round-trip, when a fire
    read its counts back as u16 on a bound that restore had emptied
    (code-review r4 finding, live repro).  A fire now reads the live
    cells' i32 counts; the restored mass still has to come out whole."""
    from arroyo_tpu.ops.keyed_bins import KeyedBinState

    aggs = (AggSpec(kind=AggKind.COUNT, column=None, output="n"),)
    st = KeyedBinState(aggs, slide_micros=1000, width_micros=1000,
                       capacity=16)
    n = 70_000
    st.update(np.full(n, 5, np.uint64), np.zeros(n, np.int64), {})
    st2 = KeyedBinState(aggs, 1000, 1000, capacity=16)
    st2.restore(st.snapshot())
    assert st2.total_rows == n  # restore sees the restored mass
    keys_o, cols, wend, cnts, _slots = st2.fire_panes(10 ** 9, final=True)
    assert int(cols["n"][0]) == n  # not n % 65536


def test_group_by_window_flush_is_idempotent():
    """A record re-created for an already-released window (late panes —
    e.g. a racing upstream) must NOT emit a second final row: q5's join
    would match the stale partial max and duplicate output rows
    (observed once as a 6th q5 row on a cold-compile run)."""
    from arroyo_tpu.engine.operators_window import NonWindowAggOperator
    from arroyo_tpu.state.store import StateStore
    from arroyo_tpu.types import TaskInfo

    class Ctx:
        def __init__(self, store, last_watermark=None):
            self.state = store
            self.last_watermark = last_watermark
            self.out = []

        async def collect(self, batch):
            self.out.append(batch)

        async def broadcast(self, msg):
            pass

    op = NonWindowAggOperator(
        "max_per_window", 86_400_000_000,
        (AggSpec(AggKind.MAX, "num", "maxn"),), flush_key="window_end")
    store = StateStore.new_in_memory(
        TaskInfo("job", "op", "max_per_window", 0, 1))
    ctx = Ctx(store)

    async def drive():
        await op.on_start(ctx)
        wend = 10_000_000
        b1 = Batch(np.array([wend - 1, wend - 1], dtype=np.int64),
                   {"window_end": np.array([wend, wend], dtype=np.int64),
                    "num": np.array([5, 7], dtype=np.int64)},
                   np.array([1, 1], dtype=np.uint64), ("window_end",))
        await op.process_batch(b1, ctx)
        await op.handle_watermark(wend, ctx)  # releases the window
        assert len(ctx.out) == 1
        assert int(ctx.out[0].columns["maxn"][0]) == 7
        # late re-creation: more rows for the SAME window after release
        b2 = Batch(np.array([wend - 1], dtype=np.int64),
                   {"window_end": np.array([wend], dtype=np.int64),
                    "num": np.array([7], dtype=np.int64)},
                   np.array([1], dtype=np.uint64), ("window_end",))
        await op.process_batch(b2, ctx)
        await op.handle_watermark(wend + 2_000_000, ctx)
        assert len(ctx.out) == 1, "late re-creation must not re-emit"

        # the guard survives a checkpoint restore: a fresh operator whose
        # context restores at watermark `wend` must also drop the late
        # re-creation instead of emitting a duplicate final row
        op2 = NonWindowAggOperator(
            "max_per_window", 86_400_000_000,
            (AggSpec(AggKind.MAX, "num", "maxn"),), flush_key="window_end")
        ctx2 = Ctx(StateStore.new_in_memory(
            TaskInfo("job", "op", "max_per_window", 0, 1)),
            last_watermark=wend)
        await op2.on_start(ctx2)
        await op2.process_batch(b2, ctx2)
        await op2.handle_watermark(wend + 2_000_000, ctx2)
        assert len(ctx2.out) == 0, "restored guard must drop late windows"

    asyncio.run(drive())


def test_window_argmax_skips_null_values():
    """SQL NULL (NaN) values never equal the join's max — one all-null
    aggregate row must not poison the window extremum and drop every
    row (pre-fix: vals.max() returned NaN and nothing matched)."""
    from arroyo_tpu.engine.operators_window import WindowArgmaxOperator
    from arroyo_tpu.state.store import StateStore
    from arroyo_tpu.types import TaskInfo

    class Ctx:
        def __init__(self, store):
            self.state = store
            self.last_watermark = None
            self.out = []
            self.timers = self

        def schedule(self, t, key):
            self._timer = (t, key)

        async def collect(self, batch):
            self.out.append(batch)

        async def broadcast(self, msg):
            pass

    op = WindowArgmaxOperator("am", "num", "max",
                              (("mx", "num"),), 1_000_000)
    ctx = Ctx(StateStore.new_in_memory(TaskInfo("j", "o", "am", 0, 1)))

    async def drive():
        await op.on_start(ctx)
        wend = 1_000_000
        b = Batch(np.full(3, wend - 1, np.int64),
                  {"window_end": np.full(3, wend, np.int64),
                   "k": np.array([1, 2, 3], np.int64),
                   "num": np.array([5.0, np.nan, 7.0])},
                  np.array([9, 9, 9], np.uint64), ("window_end",))
        await op.process_batch(b, ctx)
        await op.handle_timer(wend, ("am", wend), None, ctx)
        assert len(ctx.out) == 1
        out = ctx.out[0]
        assert out.columns["k"].tolist() == [3]  # the non-null max row
        assert out.columns["num"].tolist() == [7.0]
        assert out.columns["mx"].tolist() == [7.0]

        # an ALL-null window emits nothing (no row can equal the max)
        wend2 = 2_000_000
        b2 = Batch(np.full(2, wend2 - 1, np.int64),
                   {"window_end": np.full(2, wend2, np.int64),
                    "k": np.array([1, 2], np.int64),
                    "num": np.array([np.nan, np.nan])},
                   np.array([9, 9], np.uint64), ("window_end",))
        await op.process_batch(b2, ctx)
        await op.handle_timer(wend2, ("am", wend2), None, ctx)
        assert len(ctx.out) == 1  # nothing new

    asyncio.run(drive())


def test_window_argmax_raw_restore_late_rows():
    """Raw mode across a (simulated) restore: the released-window guard
    re-arms from the checkpoint watermark and late rows match the
    PERSISTED final extrema — a late tying row emits exactly as the
    TTL'd join it replaces would, a non-tying or unknown-window late
    row drops, and the released window never re-fires wholesale."""
    from arroyo_tpu.engine.operators_window import WindowArgmaxOperator
    from arroyo_tpu.state.store import StateStore
    from arroyo_tpu.types import TaskInfo

    class Ctx:
        def __init__(self, store, last_watermark=None):
            self.state = store
            self.last_watermark = last_watermark
            self.out = []
            self.timers = self

        def schedule(self, t, key):
            pass

        async def collect(self, batch):
            self.out.append(batch)

    W = 1_000_000
    store = StateStore.new_in_memory(TaskInfo("j", "o", "am", 0, 1))

    def make_op():
        return WindowArgmaxOperator("am", "v", "max", (("mx", "v"),), W,
                                    raw=True, late_ttl_micros=3600 * W)

    def rows(wend, vals, keys):
        n = len(vals)
        return Batch(np.full(n, wend - 1, np.int64),
                     {"window_end": np.full(n, wend, np.int64),
                      "window_start": np.full(n, wend - W, np.int64),
                      "k": np.asarray(keys, np.int64),
                      "v": np.asarray(vals, float)},
                     np.full(n, 9, np.uint64), ("window_end",))

    async def drive():
        op1 = make_op()
        ctx1 = Ctx(store)
        await op1.on_start(ctx1)
        await op1.process_batch(rows(W, [9.0, 3.0], [1, 2]), ctx1)
        await op1.handle_timer(W, ("am", W), None, ctx1)
        assert len(ctx1.out) == 1
        assert ctx1.out[0].columns["k"].tolist() == [1]

        # "restore": fresh operator over the same state, checkpoint
        # watermark at the released window end
        op2 = make_op()
        ctx2 = Ctx(store, last_watermark=W)
        await op2.on_start(ctx2)
        # late batch: a tie (emits via the persisted final), a dominated
        # value (drops), and an unknown released window (drops)
        await op2.process_batch(rows(W, [9.0, 8.0], [3, 5]), ctx2)
        assert len(ctx2.out) == 1
        out = ctx2.out[0]
        assert out.columns["k"].tolist() == [3]
        assert out.columns["mx"].tolist() == [9.0]
        await op2.process_batch(rows(W // 2, [4.0], [7]), ctx2)
        assert len(ctx2.out) == 1  # nothing new, window never existed

    asyncio.run(drive())


def _planes_from_rows(st, batches):
    """The planes a state must hold after ``batches`` of (key hashes,
    timestamps, column v), by numpy scatter over the rows: the counts
    plane, then one plane per channel of ``st._ch_kinds`` (a hidden
    validity channel counts rows, as no v is null here)."""
    counts = np.zeros((st.C, st.B), np.int64)
    planes = [np.full((st.C, st.B), {"min": np.inf, "max": -np.inf}.get(
        kind, 0.0)) for kind in st._ch_kinds]
    for kh, ts, v in batches:
        at = (st.slot_of_sorted[np.searchsorted(st.key_sorted, kh)],
              (ts // st.slide) % st.B)
        np.add.at(counts, at, 1)
        for j, kind in enumerate(st._ch_kinds):
            counted = j >= len(st.aggs) or st.aggs[j].kind == AggKind.COUNT
            if kind == "min":
                np.minimum.at(planes[j], at, v)
            elif kind == "max":
                np.maximum.at(planes[j], at, v)
            else:
                np.add.at(planes[j], at, 1.0 if counted else v)
    return counts, planes


def _assert_planes_equal_rows(st, batches):
    """The state's planes, read back as they lie (bin rows first), equal
    ``_planes_from_rows``' numpy scatter of ``batches``."""
    from arroyo_tpu.ops import keyed_bins

    counts, planes = _planes_from_rows(st, batches)
    got, got_counts = st.host_planes()
    np.testing.assert_array_equal(got_counts.T, counts)
    for j, kind in enumerate(st._ch_kinds):
        # an untouched min/max cell holds the f64 extreme, the oracle's inf
        want = np.clip(planes[j], keyed_bins.NEG_INF, keyed_bins.POS_INF)
        np.testing.assert_array_equal(got[j].T, want, err_msg=f"{j}:{kind}")


@pytest.mark.parametrize("agg_kinds,capacity,n_keys,batch_rows,shapes", [
    # additive channels, a few hundred cells a batch, one flush at the end
    ((AggKind.COUNT, AggKind.SUM), 64, 40, (700,) * 4, {256}),
    # one batch of more cells than the flush bound flushes by itself at
    # twice the floor; what the next leaves goes at the floor: both of
    # the shapes warm_fire compiles
    ((AggKind.COUNT, AggKind.SUM), 1 << 16, 30_000, (150_000, 500),
     {131_072, 65_536}),
    # min and max beside a COUNT(*) that rides no transfer
    ((AggKind.COUNT, AggKind.MIN, AggKind.MAX), 64, 40, (700,) * 4, {256}),
    # every slot taken, and then (a batch of 0 rows stands for it) a flush
    # that is all padding but one cell at (C - 1, 0): where every pad cell
    # was aimed, with a zero, while the pads stayed inside the plane
    ((AggKind.COUNT, AggKind.SUM, AggKind.MIN), 64, 64, (700, 0), {256}),
], ids=["additive", "over_flush_bound", "minmax_with_count_star",
        "last_cell"])
def test_update_kernel_equals_numpy_scatter(monkeypatch, agg_kinds, capacity,
                                            n_keys, batch_rows, shapes):
    """What ``update`` -> ``flush_updates`` leaves in the planes is what
    ``np.add.at`` / ``np.minimum.at`` / ``np.maximum.at`` leave over the
    same rows, at the padded shapes the one rule gives."""
    from arroyo_tpu.ops import keyed_bins

    seen = set()
    kernel_of = keyed_bins._update_kernel

    def recording(kinds, C, B, n, dup=()):
        seen.add(n)
        return kernel_of(kinds, C, B, n, dup)

    monkeypatch.setattr(keyed_bins, "_update_kernel", recording)
    aggs = tuple(AggSpec(kind=k, column=None if k == AggKind.COUNT else "v",
                         output=k.value) for k in agg_kinds)
    st = keyed_bins.KeyedBinState(aggs, slide_micros=SEC, width_micros=SEC,
                                  capacity=capacity)
    rng = np.random.default_rng(7)
    batches = []
    for m in batch_rows:
        kh = rng.integers(0, n_keys, m).astype(np.uint64)
        ts = rng.integers(0, 4 * SEC, m).astype(np.int64)
        v = rng.integers(-1000, 1000, m).astype(np.float64)
        if m == 0:  # one row for the last slot's first bin, flushed alone
            st.flush_updates()
            assert st.next_slot == st.C == capacity
            kh, ts, v = (st.slot_to_key[[st.C - 1]], np.zeros(1, np.int64),
                         np.array([-7.0]))
        st.update(kh, ts, {"v": v})
        batches.append((kh, ts, v))
        assert m or st._pending_cells == 1
    st.flush_updates()
    assert not st._pending and seen == shapes
    _assert_planes_equal_rows(st, batches)


def _compiled_aliases(kernel, *args):
    """{parameter number: output number} of the input-output aliases of
    ``kernel`` compiled for ``args``, and the bytes they cover."""
    import re

    compiled = kernel.lower(*args).compile()
    head = compiled.as_text().split("\n", 1)[0]
    aliases = re.search(r"input_output_alias=\{(.*?)\}, entry_", head)
    assert aliases, head
    pairs = re.findall(r"\{(\d+)\}: \((\d+), \{\}", aliases.group(1))
    return ({int(p): int(o) for o, p in pairs},
            compiled.memory_analysis().alias_size_in_bytes)


@pytest.mark.parametrize("kernel", ["update", "evict"])
@pytest.mark.parametrize("kinds,dup", [
    (("count",), (0,)), (("min", "max", "sum", "sum"), ()),
], ids=["count_star", "min_max"])
def test_update_and_evict_write_the_planes_they_are_given(kernel, kinds,
                                                          dup):
    """The two programs that rewrite the planes take them donated, and
    the compiled program aliases every plane to the output of its place:
    no second copy of the state while one runs.  At the tiny shape
    ``tests/test_obs.py`` names its kernels at, and at a min/max plan
    with its two hidden validity channels."""
    import jax.numpy as jnp

    from arroyo_tpu.ops import keyed_bins

    C, B, n = 64, 8, 256
    values, counts = keyed_bins.init_planes(kinds, C, B)
    if kernel == "update":
        fn = keyed_bins._update_kernel(kinds, C, B, n, dup)
        rest = (jnp.zeros((2, n), jnp.int32),
                jnp.zeros((1 + len(kinds) - len(dup), n)))
    else:
        fn = keyed_bins._evict_kernel(kinds, C, B)
        rest = (jnp.zeros(8, jnp.int32), jnp.zeros(8, bool))
    aliases, nbytes = _compiled_aliases(fn, values, counts, *rest)
    # parameters and outputs are the flattened (values..., counts)
    assert aliases == {i: i for i in range(len(kinds) + 1)}
    assert nbytes == sum(v.nbytes for v in values) + counts.nbytes
    out_values, out_counts = fn(values, counts, *rest)
    assert counts.is_deleted() and all(v.is_deleted() for v in values)
    assert len(out_values) == len(kinds) and out_counts.shape == (B * C,)


def test_every_reader_survives_the_donated_writes(monkeypatch):
    """One state through every program that reads or replaces the planes,
    each after a donated write: no handle from before an update or an
    evict is used again ("Array has been deleted"), and at the end the
    planes hold what numpy's scatter of the surviving rows holds."""
    from arroyo_tpu.ops import keyed_bins

    aggs = (AggSpec(kind=AggKind.COUNT, column=None, output="n"),
            AggSpec(kind=AggKind.SUM, column="v", output="s"),
            AggSpec(kind=AggKind.MAX, column="v", output="mx"))
    rng = np.random.default_rng(33)

    def rows(n_keys, t0, t1, m=300, key0=0):
        kh = rng.integers(key0, key0 + n_keys, m).astype(np.uint64)
        ts = rng.integers(t0, t1, m).astype(np.int64)
        return kh, ts, rng.integers(-99, 99, m).astype(np.float64)

    def feed(st, batch):
        st.update(batch[0], batch[1], {"v": batch[2]})

    # W == 1, so drain_deltas applies; the promotion of the counts plane
    # to i64 (a new array on the way) falls between two updates
    monkeypatch.setattr(keyed_bins.KeyedBinState, "_i32_promote", 500)
    st = keyed_bins.KeyedBinState(aggs, slide_micros=SEC, width_micros=SEC,
                                  capacity=64)
    first = rows(40, 0, 2 * SEC)
    feed(st, first)
    st.flush_updates()
    assert st.counts.dtype == np.int32
    snap = st.snapshot()
    assert int(snap["bin_counts"].sum()) == 300
    second = rows(40, SEC, 3 * SEC)
    feed(st, second)
    assert st.counts.dtype == np.int64  # 600 rows >= 500: promoted
    assert st.device_bytes() == (len(st._ch_kinds) * 8 + 8) * st.C * st.B
    fired = st.fire_panes(SEC)  # fires bin 0, evicts it
    assert fired is not None and int(fired[3].sum()) == int(
        (first[1] < SEC).sum())
    drained = st.drain_deltas()  # reads bins 1..2, resets them (evict)
    assert drained is not None and int(drained[3].sum()) == 600 - int(
        (first[1] < SEC).sum())
    third = rows(200, 2 * SEC, 3 * SEC, key0=1000)  # past 64 slots: _grow
    feed(st, third)
    assert st.C == 256
    fourth = rows(40, 2 * SEC, 3 * SEC)
    feed(st, fourth)
    st.flush_updates()
    _assert_planes_equal_rows(st, [third, fourth])
    # a restore replaces the planes; updates and warm_fire's two empty
    # flushes go on from there
    st2 = keyed_bins.KeyedBinState(aggs, slide_micros=SEC,
                                   width_micros=SEC, capacity=64)
    st2.restore(st.snapshot())
    fifth = rows(40, 2 * SEC, 4 * SEC)
    feed(st2, fifth)
    assert st2.warm_fire() >= 3
    st2.flush_updates()
    _assert_planes_equal_rows(st2, [third, fourth, fifth])
    # and the state the snapshot was taken from is still whole
    _assert_planes_equal_rows(st, [third, fourth])


@pytest.mark.parametrize("route", ["native", "numpy", "merge_inputs"])
def test_dispatch_cells_never_gets_a_cell_twice(monkeypatch, route):
    """No (slot, bin) comes twice in a dispatch, so every cell takes one
    add and the planes are the same bit for bit whatever order the device
    applies a dispatch's cells in (and a scatter may one day be told so:
    ``unique_indices``).  That holds on every route into
    ``_dispatch_cells``: a single run as ``update`` pre-aggregated it
    (natively or in numpy), several runs through ``_merge_cells``, the
    merge-input path, and ring-modular bins after the ring wrapped or
    was laid out anew."""
    import arroyo_tpu.native as native
    from arroyo_tpu.ops import keyed_bins

    if route == "numpy":
        monkeypatch.setattr(native, "HAVE_NATIVE", False)
    elif not native.HAVE_NATIVE:
        pytest.skip("no native host library")
    monkeypatch.setattr(keyed_bins, "UPDATE_FLUSH_CELLS", 2048)
    dispatched = []
    dispatch = keyed_bins.KeyedBinState._dispatch_cells

    def checking(self, slots_c, bins_c, rowcnt, vals_c):
        cells = np.asarray(slots_c, np.int64) * self.B + bins_c
        assert len(np.unique(cells)) == len(cells)
        assert (np.asarray(slots_c) < self.C).all() and (rowcnt > 0).all()
        assert (0 <= np.asarray(bins_c)).all() and (bins_c < self.B).all()
        dispatched.append(len(cells))
        return dispatch(self, slots_c, bins_c, rowcnt, vals_c)

    monkeypatch.setattr(keyed_bins.KeyedBinState, "_dispatch_cells",
                        checking)
    aggs = (AggSpec(kind=AggKind.COUNT, column=None, output="n"),
            AggSpec(kind=AggKind.SUM, column="v", output="s"),
            AggSpec(kind=AggKind.MIN, column="v", output="mn"))
    st = keyed_bins.KeyedBinState(aggs, slide_micros=SEC,
                                  width_micros=2 * SEC, capacity=256)
    if route == "merge_inputs":
        st.set_merge_inputs({j: f"c{j}" for j in st._xfer_ch}, "rows")
    rng = np.random.default_rng(len(route))
    total = fired = 0
    for step in range(40):
        # hot keys, so rows repeat a cell within a batch and across the
        # batches of one flush; time runs on, so bins wrap round the ring
        # (B = 8); step 25 jumps ahead by more than the ring holds
        m = int(rng.integers(1, 1500))
        kh = rng.zipf(1.3, m).astype(np.uint64) % 600
        t0 = (step // 3 + (40 if step >= 25 else 0)) * SEC
        ts = rng.integers(t0, t0 + 3 * SEC, m).astype(np.int64)
        cols = {"v": rng.normal(size=m)}
        if route == "merge_inputs":
            cols = {f"c{j}": rng.normal(size=m) for j in st._xfer_ch}
            cols["rows"] = np.ones(m)
        st.update(kh, ts, cols)
        total += m
        if step % 7 == 6:
            out = st.fire_panes(t0)
            fired += 0 if out is None else 1
    st.flush_updates()
    assert st.total_rows == total and fired >= 3
    assert len(dispatched) >= 6 and max(dispatched) > 1


def test_flushes_dispatch_only_the_warmed_shapes(monkeypatch):
    """After ``warm_fire`` a stream of batches of varied sizes and its
    fires ask ``_update_kernel`` for no shape beyond the two warmed: the
    flushes and the warm-up are packed by one helper from one rule.  The
    bound is a module constant a test can still move."""
    from arroyo_tpu.ops import keyed_bins

    monkeypatch.setattr(keyed_bins, "UPDATE_FLUSH_CELLS", 1024)
    aggs = (AggSpec(kind=AggKind.COUNT, column=None, output="n"),
            AggSpec(kind=AggKind.SUM, column="v", output="s"))
    st = keyed_bins.KeyedBinState(aggs, slide_micros=SEC, width_micros=SEC,
                                  capacity=4096)
    assert st._update_rows_floor() == 1024
    cold = keyed_bins._update_kernel.cache_info().misses
    counted = keyed_bins.perf.counter("pane_update_dispatches")
    assert st.warm_fire() == 2 + 1 + 3  # flush x 2, scan, pick 1024..4096
    warmed = keyed_bins._update_kernel.cache_info().misses
    assert warmed - cold <= 2
    # the warm-up is no dispatch of the stream's: the counter ratios that
    # read update_pad_share and rows per dispatch do not see it
    assert keyed_bins.perf.counter("pane_update_dispatches") == counted
    rng = np.random.default_rng(5)
    rows = fired = 0
    for step, m in enumerate((40, 900, 333, 1000, 7, 650, 980, 120, 64, 811)):
        kh = rng.integers(0, 3000, m).astype(np.uint64)
        ts = rng.integers(step * SEC, (step + 1) * SEC, m).astype(np.int64)
        st.update(kh, ts, {"v": np.ones(m)})
        rows += m
        if step in (4, 9):
            _keys, cols, _wend, _cnts, _slots = st.fire_panes((step + 1) * SEC)
            fired += int(cols["n"].sum())
    assert fired == rows
    assert keyed_bins._update_kernel.cache_info().misses == warmed
    assert keyed_bins.perf.counter("pane_update_dispatches") - counted >= 3
