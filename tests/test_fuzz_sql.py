"""Differential random testing: randomly generated window/aggregate
queries run through the FULL SQL engine and are checked against an
independent pure-python oracle — the breadth net behind the
hand-written correctness suites (arroyo-sql-testing's
correctness_run_codegen analog, generalized).

Deterministic: seeds are fixed per case; failures reproduce by seed.
"""

import numpy as np
import pytest

from arroyo_tpu import Batch
from arroyo_tpu.connectors.memory import clear_sink, sink_output
from arroyo_tpu.engine.engine import LocalRunner
from arroyo_tpu.sql import SchemaProvider, plan_sql

SEC = 1_000_000


def _make_table(rng, n, n_keys, span_secs, null_frac):
    ts = np.sort(rng.integers(0, span_secs * SEC, n)).astype(np.int64)
    k = rng.integers(0, n_keys, n).astype(np.int64)
    v = rng.integers(-1000, 1000, n).astype(np.float64)
    nulls = rng.random(n) < null_frac
    v[nulls] = np.nan
    return ts, k, v


def _windows_of(t, mode, width, slide):
    """Window ends a row at time t contributes to (tumble/hop)."""
    if mode == "tumble":
        return [(t // width + 1) * width]
    out = []
    e = (t // slide + 1) * slide
    while e - width <= t < e:
        out.append(e)
        e += slide
    return out


def _session_windows(times, gap):
    """Gap-merged session (start, end) list for one key's sorted times."""
    sessions = []
    for t in times:
        if sessions and t < sessions[-1][1]:
            s, e = sessions[-1]
            sessions[-1] = (s, max(e, t + gap))
        else:
            sessions.append((t, t + gap))
    return sessions


def _oracle(mode, ts, k, v, width, slide, gap, where_min):
    """{(key, window_end): (cnt_star, cnt_v, sum, min, max, avg)} with
    SQL null-skipping semantics, after `WHERE v >= where_min OR v IS
    NULL` pre-filtering (nulls kept so null-skipping is exercised)."""
    keep = ~(np.nan_to_num(v, nan=where_min) < where_min)
    ts, k, v = ts[keep], k[keep], v[keep]
    cells = {}
    if mode == "session":
        for key in np.unique(k):
            times = ts[k == key]
            for (s, e) in _session_windows(np.sort(times).tolist(), gap):
                sel = (k == key) & (ts >= s) & (ts < e)
                cells[(int(key), e)] = v[sel]
    else:
        tmp = {}
        for t, key, val in zip(ts.tolist(), k.tolist(), v.tolist()):
            for e in _windows_of(t, mode, width, slide):
                tmp.setdefault((key, e), []).append(val)
        cells = {key: np.asarray(vals) for key, vals in tmp.items()}
    out = {}
    for key, vals in cells.items():
        vv = vals[~np.isnan(vals)]
        out[key] = (
            len(vals), len(vv),
            vv.sum() if len(vv) else None,
            vv.min() if len(vv) else None,
            vv.max() if len(vv) else None,
            vv.mean() if len(vv) else None,
        )
    return out


CASES = [
    # (seed, mode, width_s, slide_s, gap_s, n, keys, span_s, null_frac)
    (1, "tumble", 1, 1, None, 3000, 7, 6, 0.0),
    (2, "tumble", 2, 2, None, 5000, 40, 9, 0.3),
    (3, "hop", 2, 1, None, 4000, 12, 7, 0.0),
    (4, "hop", 3, 1, None, 6000, 25, 8, 0.2),
    (5, "hop", 4, 2, None, 2500, 5, 10, 0.5),
    (6, "session", None, None, 1, 2000, 9, 8, 0.0),
    (7, "session", None, None, 2, 3000, 15, 12, 0.25),
    (8, "tumble", 1, 1, None, 800, 3, 3, 0.9),  # nearly-all-null
    (9, "hop", 2, 1, None, 1, 1, 1, 0.0),       # single row
    (10, "session", None, None, 1, 1200, 4, 20, 0.1),  # sparse keys
]


@pytest.mark.parametrize(
    "seed,mode,width_s,slide_s,gap_s,n,keys,span_s,null_frac", CASES,
    ids=[f"s{c[0]}-{c[1]}" for c in CASES])
def test_fuzz_window_aggregates(seed, mode, width_s, slide_s, gap_s, n,
                                keys, span_s, null_frac):
    _run_window_fuzz(seed, mode, width_s, slide_s, gap_s, n, keys,
                     span_s, null_frac)


PARALLEL_CASES = [
    # (seed, mode, width_s, slide_s, gap_s, n, keys, span_s, null_frac,
    #  n_batches, parallelism) — shuffle fan-out + multi-subtask panes
    (61, "tumble", 2, 2, None, 5000, 30, 9, 0.2, 5, 2),
    (62, "hop", 3, 1, None, 4000, 12, 8, 0.0, 4, 3),
    (63, "session", None, None, 1, 2500, 10, 25, 0.15, 6, 2),
    (64, "hop", 2, 1, None, 3000, 40, 7, 0.5, 3, 2),
]


@pytest.mark.parametrize(
    "seed,mode,width_s,slide_s,gap_s,n,keys,span_s,null_frac,nb,par",
    PARALLEL_CASES, ids=[f"s{c[0]}-{c[1]}-p{c[10]}"
                         for c in PARALLEL_CASES])
def test_fuzz_window_aggregates_parallel(seed, mode, width_s, slide_s,
                                         gap_s, n, keys, span_s,
                                         null_frac, nb, par):
    """The same differential window fuzz through SHUFFLED multi-subtask
    plans: batches split across arrivals, query_parallelism > 1 — the
    fan-in watermark and per-subtask pane paths must still match the
    single-threaded oracle exactly."""
    _run_window_fuzz(seed, mode, width_s, slide_s, gap_s, n, keys,
                     span_s, null_frac, n_batches=nb, parallelism=par)


RING_CASES = [
    # (seed, width_s, slide_s, n, keys, span_s, null_frac) — W >= 64 so
    # fire_panes takes the bin-sharded ring emission on the 8-dev mesh
    (41, 100, 1, 4000, 9, 220, 0.2),
    (42, 300, 1, 2500, 5, 650, 0.0),
    (43, 128, 2, 3000, 20, 500, 0.4),
]


@pytest.mark.parametrize(
    "seed,width_s,slide_s,n,keys,span_s,null_frac",
    # the W100/W300 cases span hundreds of seconds of event time
    # through wide rings — the heaviest fuzz cases; W64 keeps the ring
    # path covered in tier-1
    [pytest.param(*c, marks=pytest.mark.slow) if c[1] // c[2] >= 100
     else c for c in RING_CASES],
    ids=[f"s{c[0]}-W{c[1] // c[2]}" for c in RING_CASES])
def test_fuzz_long_window_ring_path(seed, width_s, slide_s, n, keys,
                                    span_s, null_frac, monkeypatch):
    """Same differential window fuzz, forced through the ring-pane
    emission (long-window bin-sharding, ops/keyed_bins._emit_ring)."""
    monkeypatch.setenv("ARROYO_RING", "on")
    _run_window_fuzz(seed, "hop", width_s, slide_s, None, n, keys,
                     span_s, null_frac)


def _run_window_fuzz(seed, mode, width_s, slide_s, gap_s, n,
                     keys, span_s, null_frac, n_batches=1,
                     parallelism=1):
    from arroyo_tpu.sql.planner import Planner

    rng = np.random.default_rng(seed)
    ts, k, v = _make_table(rng, n, keys, span_s, null_frac)
    where_min = float(rng.integers(-500, 0))

    bounds = np.linspace(0, n, n_batches + 1).astype(int)
    p = SchemaProvider()
    p.add_memory_table("t", {"k": "i", "v": "f"}, [
        Batch(ts[a:b], {"k": k[a:b], "v": v[a:b]})
        for a, b in zip(bounds[:-1], bounds[1:]) if b > a])
    if mode == "tumble":
        win = f"TUMBLE(INTERVAL '{width_s}' SECOND)"
    elif mode == "hop":
        win = (f"HOP(INTERVAL '{slide_s}' SECOND, "
               f"INTERVAL '{width_s}' SECOND)")
    else:
        win = f"SESSION(INTERVAL '{gap_s}' SECOND)"
    sql = f"""
    SELECT k, {win} as window,
           count(*) as c_star, count(v) as c_v,
           sum(v) as s, min(v) as lo, max(v) as hi, avg(v) as mean
    FROM t WHERE v >= {where_min} OR v IS NULL
    GROUP BY 1, 2
    """
    clear_sink("results")
    prog = Planner(p).plan(sql, query_parallelism=parallelism)
    # every fuzz-generated plan must pass graph-level validation (the
    # same gate Engine applies before building operators)
    from arroyo_tpu.analysis.plan_validator import (
        errors_of,
        validate_program,
    )

    assert not errors_of(validate_program(prog)), (
        seed, [d.render() for d in validate_program(prog)])
    LocalRunner(prog).run()
    outs = sink_output("results")
    out = Batch.concat(outs) if outs else None

    exp = _oracle(mode, ts, k, v,
                  (width_s or 0) * SEC, (slide_s or 0) * SEC,
                  (gap_s or 0) * SEC, where_min)
    got = {}
    if out is not None:
        for j in range(len(out)):
            key = (int(out.columns["k"][j]),
                   int(out.columns["window_end"][j]))
            assert key not in got, f"window emitted twice: {key}"
            got[key] = j
    assert set(got) == set(exp), (
        f"seed {seed}: windows differ "
        f"(missing {sorted(set(exp) - set(got))[:5]}, "
        f"extra {sorted(set(got) - set(exp))[:5]})")
    for key, (c_star, c_v, s_, lo, hi, mean) in exp.items():
        j = got[key]
        assert int(out.columns["c_star"][j]) == c_star, (seed, key)
        assert int(out.columns["c_v"][j]) == c_v, (seed, key)
        for col, want in (("s", s_), ("lo", lo), ("hi", hi),
                          ("mean", mean)):
            have = out.columns[col][j]
            if want is None:
                assert np.isnan(have), (seed, key, col, have)
            else:
                assert have == pytest.approx(want, rel=1e-9, abs=1e-9), (
                    seed, key, col, have, want)


@pytest.mark.parametrize("mutation", ["drop_shuffle", "key_mismatch",
                                      "orphan"])
@pytest.mark.parametrize("seed", [1, 2])
def test_fuzz_plan_validator_rejects_mutations(seed, mutation):
    """Fuzz-generated plans pass the plan validator untouched (asserted
    inside _run_window_fuzz); the SAME plans with a seeded mutation —
    a dropped shuffle edge, a mismatched join key schema, an orphaned
    node — must be rejected with the matching diagnostic code."""
    from arroyo_tpu.analysis.plan_validator import (
        PlanValidationError,
        check_program,
        errors_of,
        validate_program,
    )
    from arroyo_tpu.graph.logical import (
        ColumnExpr,
        EdgeType,
        LogicalOperator,
        OpKind,
    )
    from arroyo_tpu.sql.planner import Planner

    rng = np.random.default_rng(seed)
    ts, k, v = _make_table(rng, 2000, 9, 6, 0.1)
    p = SchemaProvider()
    p.add_memory_table("t", {"k": "i", "v": "f"},
                       [Batch(ts, {"k": k, "v": v})])
    p.add_memory_table("u", {"k": "i", "w": "f"},
                       [Batch(ts, {"k": k, "w": v})])
    if mutation == "key_mismatch":
        sql = """
        SELECT a.k as k, a.c as c, b.d as d
        FROM (SELECT k, TUMBLE(INTERVAL '1' SECOND) as window,
                     count(*) as c FROM t GROUP BY 1, 2) a
        JOIN (SELECT k, TUMBLE(INTERVAL '1' SECOND) as window,
                     count(*) as d FROM u GROUP BY 1, 2) b
        ON a.k = b.k AND a.window = b.window
        """
    else:
        sql = """
        SELECT k, TUMBLE(INTERVAL '1' SECOND) as window, count(*) as c
        FROM t GROUP BY 1, 2
        """
    prog = Planner(p).plan(sql, query_parallelism=2)
    assert not errors_of(validate_program(prog))  # valid as planned

    if mutation == "drop_shuffle":
        for src, dst, data in prog.graph.edges(data=True):
            node = prog.node(dst)
            if (data["edge"].typ is EdgeType.SHUFFLE
                    and node.max_parallelism != 1
                    and node.operator.kind
                    in (OpKind.TUMBLING_WINDOW_AGGREGATOR,
                        OpKind.WINDOW)):
                data["edge"].typ = EdgeType.FORWARD
                break
        else:
            raise AssertionError("no shuffle edge found to mutate")
        want = "keyed-not-shuffled"
    elif mutation == "key_mismatch":
        for src, dst, data in prog.graph.edges(data=True):
            if data["edge"].typ is EdgeType.SHUFFLE_JOIN_RIGHT:
                data["edge"].key_schema = "k,extra_col"
                break
        else:
            raise AssertionError("no join edge found to mutate")
        want = "key-schema-mismatch"
    else:  # orphan: a node whose inputs were dropped entirely
        prog.add_node(LogicalOperator(
            OpKind.EXPRESSION, "orphan",
            expr=ColumnExpr("orphan", lambda c: c)))
        want = "dangling-node"

    errs = errors_of(validate_program(prog))
    assert any(d.code == want for d in errs), (mutation, errs)
    with pytest.raises(PlanValidationError):
        check_program(prog)


@pytest.mark.parametrize("seed", [51, 52, 53, 54])
def test_fuzz_group_by_window_consolidation(seed):
    """Random GROUP BY-window re-aggregations (q5 MaxBids shape) at
    random parallelism and batch splits: exactly ONE final row per
    window, values matching the oracle — the watermark-consolidation
    invariant under every interleaving."""
    import collections

    from arroyo_tpu.sql.planner import Planner

    rng = np.random.default_rng(seed)
    n = int(rng.integers(1500, 6000))
    width_s = int(rng.integers(1, 4))
    nkeys = int(rng.integers(3, 25))
    par = int(rng.integers(1, 4))
    agg = rng.choice(["max", "min", "sum"])
    nbatch = int(rng.integers(1, 7))
    ts = np.sort(rng.integers(0, 8 * SEC, n)).astype(np.int64)
    k = rng.integers(0, nkeys, n).astype(np.int64)
    bounds = np.linspace(0, n, nbatch + 1).astype(int)
    provider = SchemaProvider()
    provider.add_memory_table("events", {"k": "i"}, [
        Batch(ts[a:b], {"k": k[a:b]})
        for a, b in zip(bounds[:-1], bounds[1:]) if b > a])
    clear_sink("results")
    prog = Planner(provider).plan(f"""
        SELECT {agg}(num) AS m, window FROM (
          SELECT count(*) AS num,
                 TUMBLE(INTERVAL '{width_s}' SECOND) AS window
          FROM events GROUP BY k, 2
        ) GROUP BY 2
    """, query_parallelism=par)
    LocalRunner(prog).run()
    out = Batch.concat(sink_output("results"))
    per_w = collections.Counter(int(w) for w in out.columns["window_end"])
    assert all(v == 1 for v in per_w.values()), (seed, per_w)
    want = collections.defaultdict(collections.Counter)
    for t, kk in zip(ts.tolist(), k.tolist()):
        wend = (t // (width_s * SEC) + 1) * width_s * SEC
        want[wend][kk] += 1
    assert set(per_w) == set(want), seed
    fn = {"max": max, "min": min, "sum": sum}[agg]
    got = {int(w): int(m) for w, m in zip(out.columns["window_end"],
                                          out.columns["m"])}
    for wend, cnt in want.items():
        assert got[wend] == fn(cnt.values()), (seed, agg, wend)


@pytest.mark.parametrize("device_join", ["off", "on"])
@pytest.mark.parametrize("seed", [11, 12, 13])
def test_fuzz_windowed_join(seed, device_join, monkeypatch):
    """Random windowed equi-joins (q8 shape) against a set oracle —
    both the host numpy path and the device sort/probe/expand kernels
    (ops/join.py) must produce identical results."""
    monkeypatch.setenv("ARROYO_DEVICE_JOIN", device_join)
    rng = np.random.default_rng(seed)
    n = int(rng.integers(500, 3000))
    ts_a, ka, _ = _make_table(rng, n, int(rng.integers(3, 20)), 6, 0.0)
    ts_b, kb, _ = _make_table(rng, n, int(rng.integers(3, 20)), 6, 0.0)

    p = SchemaProvider()
    p.add_memory_table("a", {"u": "i"}, [Batch(ts_a, {"u": ka})])
    p.add_memory_table("b", {"s": "i"}, [Batch(ts_b, {"s": kb})])
    sql = """
    SELECT P.u as u, P.np as np, A.na as na
    FROM (SELECT u, TUMBLE(INTERVAL '1' SECOND) as window, count(*) as np
          FROM a GROUP BY 1, 2) AS P
    JOIN (SELECT s, TUMBLE(INTERVAL '1' SECOND) as window, count(*) as na
          FROM b GROUP BY 1, 2) AS A
    ON P.u = A.s and P.window = A.window
    """
    clear_sink("results")
    LocalRunner(plan_sql(sql, p)).run()
    outs = sink_output("results")

    def counts(ts, k):
        out = {}
        for t, key in zip(ts.tolist(), k.tolist()):
            e = (t // SEC + 1) * SEC
            out[(key, e)] = out.get((key, e), 0) + 1
        return out

    ca, cb = counts(ts_a, ka), counts(ts_b, kb)
    exp = {kw: (ca[kw], cb[kw]) for kw in set(ca) & set(cb)}
    got = {}
    for b in outs:
        for j in range(len(b)):
            kw = (int(b.columns["u"][j]), int(b.timestamp[j]) + 1)
            got[kw] = (int(b.columns["np"][j]), int(b.columns["na"][j]))
    assert got == exp, f"seed {seed}"


@pytest.mark.parametrize("device_join", ["off", "on"])
@pytest.mark.parametrize("seed,kind", [
    (21, "LEFT"), (22, "RIGHT"), (23, "FULL"),
    (24, "LEFT"), (25, "FULL")])
def test_fuzz_outer_join_net_result(seed, kind, device_join, monkeypatch):
    """Random LEFT/RIGHT/FULL joins: after applying __op retractions,
    the net row multiset must equal the standard SQL outer-join result
    regardless of arrival interleaving."""
    from collections import Counter

    monkeypatch.setenv("ARROYO_DEVICE_JOIN", device_join)
    rng = np.random.default_rng(seed)
    nl = int(rng.integers(5, 60))
    nr = int(rng.integers(5, 60))
    lids = rng.integers(0, 20, nl).astype(np.int64)
    rids = rng.integers(0, 20, nr).astype(np.int64)
    lvs = rng.integers(0, 1000, nl).astype(np.int64)
    rvs = rng.integers(0, 1000, nr).astype(np.int64)

    p = SchemaProvider()
    p.add_memory_table("l", {"id": "i", "lv": "i"}, [
        Batch(np.sort(rng.integers(0, 1000, nl)).astype(np.int64),
              {"id": lids, "lv": lvs})])
    p.add_memory_table("r", {"id": "i", "rv": "i"}, [
        Batch(np.sort(rng.integers(0, 1000, nr)).astype(np.int64),
              {"id": rids, "rv": rvs})])
    clear_sink("results")
    LocalRunner(plan_sql(
        f"SELECT l.id as lid, r.id as rid, lv, rv FROM l "
        f"{kind} JOIN r ON l.id = r.id", p)).run()
    outs = sink_output("results")

    def cell(x):
        return None if (isinstance(x, float) and np.isnan(x)) else int(x)

    net = Counter()
    for b in outs:
        ops = b.columns["__op"]
        for j in range(len(b)):
            row = tuple(cell(b.columns[c][j])
                        for c in ("lid", "rid", "lv", "rv"))
            if int(ops[j]) == 2:
                net[row] -= 1
            else:
                net[row] += 1
    net = +net  # drop zero entries

    exp = Counter()
    r_by_id = {}
    for i in range(nr):
        r_by_id.setdefault(int(rids[i]), []).append(int(rvs[i]))
    for i in range(nl):
        lid, lv = int(lids[i]), int(lvs[i])
        if lid in r_by_id:
            for rv in r_by_id[lid]:
                exp[(lid, lid, lv, rv)] += 1
        elif kind in ("LEFT", "FULL"):
            exp[(lid, None, lv, None)] += 1
    if kind in ("RIGHT", "FULL"):
        lkeys = set(lids.tolist())
        for i in range(nr):
            rid, rv = int(rids[i]), int(rvs[i])
            if rid not in lkeys:
                exp[(None, rid, None, rv)] += 1
    assert net == exp, (
        f"seed {seed} {kind}: net/exp differ "
        f"(net-exp={+(net - exp)!r}, exp-net={+(exp - net)!r})")


@pytest.mark.parametrize("seed", [
    31, pytest.param(32, marks=pytest.mark.slow), 33, 34, 35, 36, 37])
def test_fuzz_checkpoint_restore_exactly_once(seed, tmp_path):
    """Random pipeline shapes x random crash points: checkpoint, crash,
    restore — output must be exactly-once (no gaps, no duplicates)
    whatever window type, parallelism, or crash timing the seed drew."""
    import asyncio
    import json as _json

    from arroyo_tpu import AggKind, AggSpec, SessionWindow, Stream
    from arroyo_tpu.engine.engine import Engine
    from arroyo_tpu.types import StopMode

    rng = np.random.default_rng(seed)
    total = int(rng.integers(2000, 5000))
    n_buckets = int(rng.integers(3, 11))
    par = int(rng.integers(1, 3))
    mode = ["tumble", "slide", "session"][int(rng.integers(0, 3))]
    crash_after = float(rng.uniform(0.02, 0.12))
    url = f"file://{tmp_path}/ckpt"
    out_path = f"{tmp_path}/out.jsonl"
    job = f"fuzz-restore-{seed}"

    def build():
        s = (Stream.source("impulse", {
                "event_rate": 40_000.0, "message_count": total,
                "event_time_interval_micros": 1000, "batch_size": 128},
                parallelism=par)
             .watermark(max_lateness_micros=0)
             .map(lambda c: {"counter": c["counter"],
                             "bucket": c["counter"] % n_buckets}, name="b")
             .key_by("bucket"))
        aggs = [AggSpec(AggKind.COUNT, None, "cnt"),
                AggSpec(AggKind.SUM, "counter", "sum_c")]
        if mode == "tumble":
            s = s.tumbling_aggregate(100 * 1000, aggs)
        elif mode == "slide":
            s = s.sliding_aggregate(200 * 1000, 100 * 1000, aggs)
        else:
            s = s.window(SessionWindow(50 * 1000), aggs)
        return s.sink("single_file", {"path": out_path}, parallelism=1)

    async def run_with_crash():
        """Crash mid-stream after checkpoint 1; returns False when the
        bounded stream finished before the crash landed (machine-load
        dependent) — the restore phase is skipped in that case."""
        eng = Engine.for_local(build(), job, checkpoint_url=url)
        running = eng.start()
        join_t = asyncio.ensure_future(running.join())
        await asyncio.sleep(crash_after)
        if join_t.done():
            return False
        await running.checkpoint(1)
        ok = await running.wait_for_checkpoint(1)
        if not ok or join_t.done():
            # stream drained before the barrier sealed: nothing to crash
            await asyncio.wait([join_t])
            return False
        await running.stop(StopMode.IMMEDIATE)
        try:
            await join_t
        except RuntimeError:
            pass
        return True

    async def run_restored():
        eng = Engine.for_local(build(), job, checkpoint_url=url,
                               restore_epoch=1)
        await eng.start().join()

    crashed = asyncio.run(run_with_crash())
    if crashed:
        asyncio.run(run_restored())

    rows = [_json.loads(line) for line in open(out_path)]
    mult = 2 if mode == "slide" else 1  # each event feeds width/slide panes
    assert sum(r["cnt"] for r in rows) == total * mult, (seed, mode)
    # impulse splits message_count across subtasks and each split's
    # counter restarts at 0
    splits = [total // par + (1 if i < total % par else 0)
              for i in range(par)]
    exp_sum = mult * sum(c * (c - 1) // 2 for c in splits)
    assert sum(r["sum_c"] for r in rows) == exp_sum, (seed, mode)
    seen = set()
    for r in rows:
        key = (r["bucket"], r["window_end"])
        assert key not in seen, f"duplicate emission {key} (seed {seed})"
        seen.add(key)


@pytest.mark.parametrize("seed", [41, 42, 43, 44])
def test_fuzz_distinct_udaf_having(seed):
    """The buffered (non-mergeable) window path: COUNT(DISTINCT), a
    median UDAF, and HAVING, against a python oracle."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(800, 4000))
    keys = int(rng.integers(3, 12))
    width_s = int(rng.integers(1, 4))
    having_min = int(rng.integers(2, 12))
    ts, k, _ = _make_table(rng, n, keys, 8, 0.0)
    # small domain -> dups; a null fraction pins SQL null semantics:
    # COUNT(DISTINCT) excludes NULLs (pre-fix, NaN != NaN made every
    # null row its own "distinct" value), UDAFs see non-null rows only
    v = rng.integers(0, 25, n).astype(np.float64)
    v[rng.random(n) < 0.2] = np.nan

    from arroyo_tpu.sql.functions import UDAFS

    p = SchemaProvider()
    if "med" not in UDAFS:  # registration is global across param cases
        p.register_udaf("med", np.median)
    p.add_memory_table("t", {"k": "i", "v": "f"},
                       [Batch(ts, {"k": k, "v": v})])
    sql = f"""
    SELECT k, TUMBLE(INTERVAL '{width_s}' SECOND) as window,
           count(distinct v) as dv, med(v) as med, count(*) as c
    FROM t GROUP BY 1, 2 HAVING count(*) >= {having_min}
    """
    clear_sink("results")
    LocalRunner(plan_sql(sql, p)).run()
    outs = sink_output("results")
    out = Batch.concat(outs) if outs else None

    width = width_s * SEC
    cells = {}
    for t_, key, val in zip(ts.tolist(), k.tolist(), v.tolist()):
        (e,) = _windows_of(t_, "tumble", width, None)
        cells.setdefault((key, e), []).append(val)

    def cell_exp(vals):
        vv = [x for x in vals if not np.isnan(x)]
        return (len(set(vv)),
                float(np.median(vv)) if vv else float("nan"),
                len(vals))

    exp = {key: cell_exp(vals)
           for key, vals in cells.items() if len(vals) >= having_min}

    got = {}
    if out is not None:
        for j in range(len(out)):
            key = (int(out.columns["k"][j]),
                   int(out.columns["window_end"][j]))
            assert key not in got
            got[key] = (int(out.columns["dv"][j]),
                        float(out.columns["med"][j]),
                        int(out.columns["c"][j]))
    assert set(got) == set(exp), f"seed {seed}"
    for key in exp:
        assert got[key][0] == exp[key][0], (seed, key, "distinct")
        assert got[key][1] == pytest.approx(exp[key][1], nan_ok=True), \
            (seed, key, "med")
        assert got[key][2] == exp[key][2], (seed, key, "count")


def _gen_expr(rng, depth):
    """Random scalar expression tree -> (sql_text, python_eval_fn).
    eval fn takes (k:int, v:float-or-None) and returns the SQL
    three-valued result (None = NULL)."""
    def num_leaf():
        c = int(rng.integers(0, 3))
        if c == 0:
            return "k", lambda k, v: k
        if c == 1:
            return "v", lambda k, v: v
        lit = int(rng.integers(-20, 20))
        return str(lit), lambda k, v, _l=lit: _l

    if depth <= 0:
        return num_leaf()
    c = int(rng.integers(0, 4))
    if c == 0:  # arithmetic
        ls, lf = _gen_expr(rng, depth - 1)
        rs, rf = _gen_expr(rng, depth - 1)
        op = ["+", "-", "*"][int(rng.integers(0, 3))]
        pyop = {"+": lambda a, b: a + b, "-": lambda a, b: a - b,
                "*": lambda a, b: a * b}[op]

        def f(k, v, _lf=lf, _rf=rf, _o=pyop):
            a, b = _lf(k, v), _rf(k, v)
            return None if a is None or b is None else _o(a, b)
        return f"({ls} {op} {rs})", f
    if c == 1:  # CASE WHEN cmp THEN x ELSE y END
        ls, lf = _gen_expr(rng, depth - 1)
        rs, rf = _gen_expr(rng, depth - 1)
        xs, xf = _gen_expr(rng, depth - 1)
        ys, yf = _gen_expr(rng, depth - 1)
        op = ["<", ">", "=", "<=", ">="][int(rng.integers(0, 5))]
        pyop = {"<": lambda a, b: a < b, ">": lambda a, b: a > b,
                "=": lambda a, b: a == b, "<=": lambda a, b: a <= b,
                ">=": lambda a, b: a >= b}[op]

        def f(k, v, _lf=lf, _rf=rf, _xf=xf, _yf=yf, _o=pyop):
            a, b = _lf(k, v), _rf(k, v)
            cond = None if a is None or b is None else _o(a, b)
            # SQL: NULL condition selects the ELSE branch
            return _xf(k, v) if cond else _yf(k, v)
        return (f"(CASE WHEN {ls} {op} {rs} THEN {xs} ELSE {ys} END)", f)
    if c == 2:  # COALESCE
        ls, lf = _gen_expr(rng, depth - 1)
        rs, rf = _gen_expr(rng, depth - 1)

        def f(k, v, _lf=lf, _rf=rf):
            a = _lf(k, v)
            return a if a is not None else _rf(k, v)
        return f"COALESCE({ls}, {rs})", f
    # ABS
    ls, lf = _gen_expr(rng, depth - 1)

    def f(k, v, _lf=lf):
        a = _lf(k, v)
        return None if a is None else abs(a)
    return f"ABS({ls})", f


@pytest.mark.parametrize("seed", list(range(51, 91)))
def test_fuzz_scalar_expressions(seed):
    """Random expression trees (arithmetic, CASE, COALESCE, ABS) over a
    nullable float column, evaluated through the full engine and checked
    row-by-row against a python three-valued-logic interpreter."""
    rng = np.random.default_rng(seed)
    n = 400
    ts = np.arange(n, dtype=np.int64) * 100
    k = rng.integers(-10, 10, n).astype(np.int64)
    v = rng.integers(-50, 50, n).astype(np.float64)
    v[rng.random(n) < 0.3] = np.nan

    sql_e, f = _gen_expr(rng, 3)
    p = SchemaProvider()
    p.add_memory_table("t", {"k": "i", "v": "f"},
                       [Batch(ts, {"k": k, "v": v})])
    clear_sink("results")
    LocalRunner(plan_sql(
        f"SELECT k, v, {sql_e} as e FROM t", p)).run()
    out = Batch.concat(sink_output("results"))
    assert len(out) == n
    # rows keep source order per batch; match by (k, v) row identity via
    # the original index column k/v pairs in order
    for j in range(n):
        kk = int(out.columns["k"][j])
        vv = out.columns["v"][j]
        vv = None if (isinstance(vv, float) and np.isnan(vv)) else float(vv)
        want = f(kk, vv)
        have = out.columns["e"][j]
        if want is None:
            assert (have is None
                    or (isinstance(have, float) and np.isnan(have))), (
                seed, sql_e, j, kk, vv, have)
        else:
            assert have == pytest.approx(float(want), rel=1e-9), (
                seed, sql_e, j, kk, vv, have, want)


@pytest.mark.parametrize("seed", [61, 62, 63, 64, 65, 66])
def test_fuzz_rescale_reshard(seed):
    """Random N->M rescales mid-stream: snapshot N KeyedBinState
    partitions, re-shard to M by key range (filter + merge, the
    restore-time re-partitioning path), finish the stream, and compare
    every pane against the oracle — duplicates and losses both fail."""
    from arroyo_tpu.graph.logical import AggKind, AggSpec
    from arroyo_tpu.ops.keyed_bins import (
        KeyedBinState,
        filter_canonical_snapshot,
        merge_canonical_snapshots,
    )
    from arroyo_tpu.types import hash_columns, range_for_server

    rng = np.random.default_rng(seed)
    n_from = int(rng.integers(1, 5))
    n_to = int(rng.integers(1, 5))
    n = int(rng.integers(1500, 4000))
    n_keys = int(rng.integers(5, 40))
    width_s = int(rng.integers(1, 4))
    aggs = (AggSpec(AggKind.COUNT, None, "cnt"),
            AggSpec(AggKind.SUM, "v", "total"),
            AggSpec(AggKind.MIN, "v", "lo"),
            AggSpec(AggKind.MAX, "v", "hi"))

    ts = np.sort(rng.integers(0, 6 * SEC, n)).astype(np.int64)
    k = rng.integers(0, n_keys, n).astype(np.int64)
    v = rng.integers(-100, 100, n).astype(np.int64)
    kh = hash_columns([k])
    half = n // 2
    width = width_s * SEC

    def owner(khs, n_parts, idx):
        lo, hi = range_for_server(idx, n_parts)
        return (khs >= np.uint64(lo)) & (khs <= np.uint64(hi))

    got = {}

    def drain(f):
        if f is None:
            return
        kk, oc, wend, *_ = f
        for j in range(len(kk)):
            key = (int(kk[j]), int(wend[j]))
            assert key not in got, f"pane duplicated across shards: {key}"
            got[key] = (int(oc["cnt"][j]), int(oc["total"][j]),
                        int(oc["lo"][j]), int(oc["hi"][j]))

    # phase 1: N partitions consume the first half, fire to mid watermark
    wm = int(ts[half - 1]) - width  # behind: keep panes open across rescale
    snaps = []
    for i in range(n_from):
        own = owner(kh[:half], n_from, i)
        st = KeyedBinState(aggs, SEC, width, capacity=32)
        if own.any():
            st.update(kh[:half][own], ts[:half][own],
                      {"v": v[:half][own]})
        drain(st.fire_panes(wm))
        snaps.append({kk_: np.asarray(v_) for kk_, v_ in
                      st.snapshot().items()})

    # phase 2: M partitions each restore the merged overlap of ALL
    # parents filtered to their own range, then consume the second half
    for i in range(n_to):
        merged: dict = {}
        for s in snaps:
            part = filter_canonical_snapshot(
                s, range_for_server(i, n_to))
            merged = merge_canonical_snapshots(merged, part)
        st = KeyedBinState(aggs, SEC, width, capacity=32)
        if merged:
            st.restore(merged)
        own = owner(kh[half:], n_to, i)
        if own.any():
            st.update(kh[half:][own], ts[half:][own],
                      {"v": v[half:][own]})
        drain(st.fire_panes(1 << 60, final=True))

    exp = {}
    for t, key, val in zip(ts.tolist(), kh.tolist(), v.tolist()):
        e = (t // SEC + 1) * SEC
        while e - width <= t < e:
            c, s_, lo, hi = exp.get((key, e), (0, 0, 1 << 60, -(1 << 60)))
            exp[(key, e)] = (c + 1, s_ + val, min(lo, val), max(hi, val))
            e += SEC
    assert got == exp, (
        f"seed {seed} {n_from}->{n_to}: "
        f"missing {len(set(exp) - set(got))}, extra {len(set(got) - set(exp))}")


@pytest.mark.parametrize("seed", [71, 72, 73, 74])
def test_fuzz_multi_source_fanin_no_drops_within_lateness(seed):
    """Two sources with skewed time bases and shuffled batch arrivals,
    UNION ALL'd into one window aggregate: the fan-in watermark is the
    MIN across sources, so every row within the configured lateness
    must be aggregated — no interleaving may drop data or fire a pane
    early.  Oracle = exact per-(key, window) counts over both streams."""
    import collections

    rng = np.random.default_rng(seed)
    na, nb = int(rng.integers(800, 2500)), int(rng.integers(800, 2500))
    skew = int(rng.integers(0, 3)) * SEC  # source b lags by up to 2s
    lateness = 4 * SEC                    # > skew + batch disorder
    width_s = int(rng.integers(1, 4))
    nkeys = int(rng.integers(3, 15))

    def mk(n, base):
        ts = base + np.sort(rng.integers(0, 6 * SEC, n)).astype(np.int64)
        k = rng.integers(0, nkeys, n).astype(np.int64)
        nb_ = int(rng.integers(2, 6))
        bounds = np.linspace(0, n, nb_ + 1).astype(int)
        return ts, k, [Batch(ts[x:y], {"k": k[x:y]})
                       for x, y in zip(bounds[:-1], bounds[1:]) if y > x]

    ts_a, k_a, batches_a = mk(na, 0)
    ts_b, k_b, batches_b = mk(nb, skew)
    p = SchemaProvider()
    p.add_memory_table("a", {"k": "i"}, batches_a,
                       lateness_micros=lateness)
    p.add_memory_table("b", {"k": "i"}, batches_b,
                       lateness_micros=lateness)
    clear_sink("results")
    LocalRunner(plan_sql(f"""
        SELECT k, TUMBLE(INTERVAL '{width_s}' SECOND) as window,
               count(*) as cnt
        FROM (SELECT k FROM a UNION ALL SELECT k FROM b)
        GROUP BY 1, 2
    """, p)).run()
    out = Batch.concat(sink_output("results"))
    exp = collections.Counter()
    for ts, k in ((ts_a, k_a), (ts_b, k_b)):
        for t, kk in zip(ts.tolist(), k.tolist()):
            exp[(int(kk), (t // (width_s * SEC) + 1) * width_s * SEC)] += 1
    got = {}
    for j in range(len(out)):
        key = (int(out.columns["k"][j]), int(out.columns["window_end"][j]))
        assert key not in got, f"pane fired twice: {key}"
        got[key] = int(out.columns["cnt"][j])
    assert got == dict(exp), (
        f"seed {seed}: missing {sorted(set(exp) - set(got))[:5]}, "
        f"extra {sorted(set(got) - set(exp))[:5]}")


@pytest.mark.parametrize("seed,shape", [
    (81, "order_limit"), (82, "row_number"), (83, "order_limit"),
    (84, "row_number"), (85, "row_number")])
def test_fuzz_windowed_topn(seed, shape):
    """Random windowed TopN: both the fused ORDER BY-LIMIT plan and the
    ROW_NUMBER() OVER rewrite, random window kinds/limits/key skew.
    Per window: at most k rows, the returned counts are exactly the
    true top-k multiset, and each returned key's count is its own."""
    import collections

    rng = np.random.default_rng(seed)
    n = int(rng.integers(1500, 5000))
    nkeys = int(rng.integers(4, 40))
    k_lim = int(rng.integers(1, 5))
    width_s = int(rng.integers(1, 4)) * 2
    slide_s = width_s if rng.random() < 0.5 else width_s // 2
    ts = np.sort(rng.integers(0, 8 * SEC, n)).astype(np.int64)
    keys = (rng.zipf(1.3, n) % nkeys).astype(np.int64)  # skewed
    p = SchemaProvider()
    p.add_memory_table("t", {"k": "i"}, [Batch(ts, {"k": keys})])
    win = (f"TUMBLE(INTERVAL '{width_s}' SECOND)" if slide_s == width_s
           else f"HOP(INTERVAL '{slide_s}' SECOND, "
                f"INTERVAL '{width_s}' SECOND)")
    if shape == "order_limit":
        sql = f"""
        CREATE TABLE out WITH (connector='memory', name='results');
        INSERT INTO out
        SELECT k, {win} as window, count(*) as num
        FROM t GROUP BY 1, 2 ORDER BY num DESC LIMIT {k_lim}
        """
    else:
        sql = f"""
        CREATE TABLE out WITH (connector='memory', name='results');
        INSERT INTO out
        SELECT k, num, window FROM (
          SELECT k, count(*) AS num, {win} as window,
                 ROW_NUMBER() OVER (PARTITION BY window
                                    ORDER BY num DESC) as rn
          FROM t GROUP BY 1, 3
        ) WHERE rn <= {k_lim}
        """
    clear_sink("results")
    LocalRunner(plan_sql(sql, p)).run()
    out = Batch.concat(sink_output("results"))
    want = collections.defaultdict(collections.Counter)
    W = width_s * SEC
    S = slide_s * SEC
    for t, kk in zip(ts.tolist(), keys.tolist()):
        e = (t // S + 1) * S
        while e - W <= t < e:
            want[e][kk] += 1
            e += S
    per_w = collections.defaultdict(list)
    for i in range(len(out)):
        per_w[int(out.columns["window_end"][i])].append(
            (int(out.columns["k"][i]), int(out.columns["num"][i])))
    assert set(per_w) <= set(want), seed
    # every window with data must appear (top-k of a non-empty window
    # is non-empty)
    assert set(per_w) == set(want), (
        f"seed {seed}: missing windows {sorted(set(want) - set(per_w))[:4]}")
    for wend, rows_ in per_w.items():
        assert len(rows_) <= k_lim, (seed, wend)
        true_top = sorted(want[wend].values(), reverse=True)[:k_lim]
        assert sorted((c for _, c in rows_), reverse=True) == true_top, (
            seed, wend)
        for kk, c in rows_:
            assert want[wend][kk] == c, (seed, wend, kk)


@pytest.mark.parametrize("seed", [91, 92, 93])
def test_fuzz_session_max_size_clamp(seed):
    """Sessions chaining across the 24h MAX_SESSION_SIZE clamp: random
    near-gap spacings force chains that the engine must split exactly
    where the incremental per-event clamp splits them.  Oracle replays
    the reference's windows.rs clamp semantics event by event."""
    import collections

    from arroyo_tpu.engine.operators_window import MAX_SESSION_SIZE_MICROS

    rng = np.random.default_rng(seed)
    MAX = MAX_SESSION_SIZE_MICROS
    gap_s = int(rng.integers(2, 10))
    gap = gap_s * SEC
    nkeys = 3
    ts_parts, k_parts = [], []
    for key in range(nkeys):
        # a chain that crosses the clamp: spacings mostly just under the
        # gap, sprinkled with over-gap breaks
        m = int(rng.integers(40, 90))
        steps = rng.integers(1, gap + gap // 4, m)  # some exceed gap
        base = int(rng.integers(0, 5 * SEC))
        # scale the chain so cumulative span crosses MAX at least once
        scale = max(1, int((MAX * 1.5) // max(int(steps.sum()), 1)))
        t = base + np.cumsum(steps.astype(np.int64) * scale)
        # re-derive effective spacings vs gap after scaling: keep raw
        ts_parts.append(t)
        k_parts.append(np.full(m, key, dtype=np.int64))
    ts = np.concatenate(ts_parts)
    keys = np.concatenate(k_parts)
    o = np.argsort(ts, kind="stable")
    ts, keys = ts[o], keys[o]

    p = SchemaProvider()
    nb = int(rng.integers(1, 5))
    bounds = np.linspace(0, len(ts), nb + 1).astype(int)
    p.add_memory_table("t", {"k": "i"}, [
        Batch(ts[a:b], {"k": keys[a:b]})
        for a, b in zip(bounds[:-1], bounds[1:]) if b > a])
    clear_sink("results")
    LocalRunner(plan_sql(f"""
        SELECT k, count(*) as cnt,
               SESSION(INTERVAL '{gap_s}' SECOND) as window
        FROM t GROUP BY 1, 3
    """, p)).run()
    out = Batch.concat(sink_output("results"))

    # oracle: the reference's incremental merge + clamp, per event
    def sessions_of(times):
        sess = []  # (start, end) clamped
        for t in times:
            placed = False
            for i, (s, e) in enumerate(sess):
                if s - gap <= t < e:
                    ns, ne = min(s, t), max(e, t + gap)
                    if ne - ns > MAX:
                        ne = ns + MAX
                    sess[i] = (ns, ne)
                    placed = True
                    break
            if not placed:
                sess.append((t, t + gap))
            sess.sort()
            merged = []
            for s, e in sess:
                if merged and s <= merged[-1][1]:
                    ps, pe = merged[-1]
                    ne = max(pe, e)
                    if ne - ps > MAX:
                        ne = ps + MAX
                    merged[-1] = (ps, ne)
                else:
                    merged.append((s, e))
            sess = merged
        return sess

    exp = collections.Counter()
    for key in range(nkeys):
        times = np.sort(ts[keys == key]).tolist()
        for (s, e) in sessions_of(times):
            cnt = sum(1 for t in times if s <= t < e)
            if cnt:
                exp[(key, s, cnt)] += 1
    got = collections.Counter(
        (int(out.columns["k"][j]), int(out.columns["window_start"][j]),
         int(out.columns["cnt"][j])) for j in range(len(out)))
    assert got == exp, (
        f"seed {seed}: missing {sorted((exp - got).keys())[:4]}, "
        f"extra {sorted((got - exp).keys())[:4]}")


@pytest.mark.parametrize("seed", [61, 62, 63, 64, 65, 66])
def test_fuzz_common_subplan_elimination(seed, monkeypatch):
    """Random q5-SHAPED self-join-on-window-aggregate queries: the
    duplicated inner aggregate must merge into one chain (the pass's
    whole point) and the merged plan's rows must equal the unmerged
    plan's rows exactly — across agg kinds, window shapes, parallelism,
    and batch splits."""
    import os

    from arroyo_tpu.sql.planner import Planner

    rng = np.random.default_rng(seed)
    n = int(rng.integers(1000, 5000))
    hop = bool(rng.integers(0, 2))
    width_s = int(rng.choice([2, 3, 4]))
    # slide must divide width (bin-path invariant, as in the reference)
    slide_s = (int(rng.choice([d for d in (1, 2) if width_s % d == 0]))
               if hop else width_s)
    nkeys = int(rng.integers(3, 30))
    par = int(rng.integers(1, 4))
    inner = rng.choice(["count(*)", "sum(v)", "max(v)"])
    outer = rng.choice(["max", "min"])
    nbatch = int(rng.integers(1, 6))
    ts = np.sort(rng.integers(0, 9 * SEC, n)).astype(np.int64)
    k = rng.integers(0, nkeys, n).astype(np.int64)
    v = rng.integers(1, 50, n).astype(np.int64)
    bounds = np.linspace(0, n, nbatch + 1).astype(int)
    win = (f"HOP(INTERVAL '{slide_s}' SECOND, INTERVAL '{width_s}' SECOND)"
           if hop else f"TUMBLE(INTERVAL '{width_s}' SECOND)")
    sql = f"""
        WITH ev AS (SELECT k AS k, v AS v FROM events)
        SELECT A.k AS k, A.num AS num
        FROM (
          SELECT T1.k, {win} AS window, {inner} AS num
          FROM ev T1 GROUP BY 1, 2
        ) AS A
        JOIN (
          SELECT {outer}(num) AS mx, window FROM (
            SELECT {inner} AS num, {win} AS window
            FROM ev T2 GROUP BY T2.k, 2
          ) AS B0 GROUP BY 2
        ) AS B
        ON A.num = B.mx AND A.window = B.window
    """

    def run():
        provider = SchemaProvider()
        provider.add_memory_table("events", {"k": "i", "v": "i"}, [
            Batch(ts[a:b], {"k": k[a:b], "v": v[a:b]})
            for a, b in zip(bounds[:-1], bounds[1:]) if b > a])
        clear_sink("results")
        prog = Planner(provider).plan(sql, query_parallelism=par)
        n_aggs = sum(1 for nd in prog.graph.nodes
                     if "window_aggregator" in nd
                     and "non_window" not in nd)
        LocalRunner(prog).run()
        rows = []
        for b in sink_output("results"):
            for i in range(len(next(iter(b.columns.values())))):
                rows.append((int(b.columns["k"][i]),
                             int(b.columns["num"][i])))
        return n_aggs, sorted(rows)

    # pin the CSE-specific shape: the argmax fusion would otherwise
    # rewrite these self-joins entirely (it has its own fuzz family)
    monkeypatch.setenv("ARROYO_ARGMAX", "0")
    monkeypatch.delenv("ARROYO_CSE", raising=False)
    merged_aggs, merged = run()
    assert merged_aggs == 1, (seed, "inner aggregate did not merge")
    monkeypatch.setenv("ARROYO_CSE", "0")
    dup_aggs, unmerged = run()
    assert dup_aggs == 2, seed
    assert merged == unmerged, (seed, len(merged), len(unmerged))
    assert len(merged) > 0, seed


@pytest.mark.parametrize("seed", [71, 72, 73, 74, 75, 76])
def test_fuzz_window_argmax_fusion(seed, monkeypatch):
    """Random q5-shaped self-joins on a window aggregate: the argmax
    fusion must replace the whole join subplan with a WindowArgmax
    operator (no window_join, ONE aggregate) and emit exactly the rows
    the unfused join emits — across inner agg kinds, outer max/min,
    window shapes, parallelism, batch splits, and tie multiplicity."""
    from arroyo_tpu.sql.planner import Planner

    rng = np.random.default_rng(seed)
    n = int(rng.integers(1000, 5000))
    hop = bool(rng.integers(0, 2))
    width_s = int(rng.choice([2, 3, 4]))
    slide_s = (int(rng.choice([d for d in (1, 2) if width_s % d == 0]))
               if hop else width_s)
    nkeys = int(rng.integers(3, 30))
    par = int(rng.integers(1, 4))
    inner = rng.choice(["count(*)", "sum(v)", "max(v)"])
    outer = rng.choice(["max", "min"])
    nbatch = int(rng.integers(1, 6))
    ts = np.sort(rng.integers(0, 9 * SEC, n)).astype(np.int64)
    k = rng.integers(0, nkeys, n).astype(np.int64)
    # small value range -> plenty of cross-key ties at the window max;
    # a null fraction makes some (key, window) aggregates SQL NULL —
    # NULL never equals the max, and must not poison the extremum
    # (an all-NaN pane once dropped the whole window's rows)
    v = rng.integers(1, 8, n).astype(np.float64)
    v[rng.random(n) < 0.15] = np.nan
    bounds = np.linspace(0, n, nbatch + 1).astype(int)
    win = (f"HOP(INTERVAL '{slide_s}' SECOND, INTERVAL '{width_s}' SECOND)"
           if hop else f"TUMBLE(INTERVAL '{width_s}' SECOND)")
    sql = f"""
        WITH ev AS (SELECT k AS k, v AS v FROM events)
        SELECT A.k AS k, A.num AS num, B.mx AS mx
        FROM (
          SELECT T1.k, {win} AS window, {inner} AS num
          FROM ev T1 GROUP BY 1, 2
        ) AS A
        JOIN (
          SELECT {outer}(num) AS mx, window FROM (
            SELECT {inner} AS num, {win} AS window
            FROM ev T2 GROUP BY T2.k, 2
          ) AS B0 GROUP BY 2
        ) AS B
        ON A.num = B.mx AND A.window = B.window
    """

    def run():
        provider = SchemaProvider()
        provider.add_memory_table("events", {"k": "i", "v": "f"}, [
            Batch(ts[a:b], {"k": k[a:b], "v": v[a:b]})
            for a, b in zip(bounds[:-1], bounds[1:]) if b > a])
        clear_sink("results")
        prog = Planner(provider).plan(sql, query_parallelism=par)
        shapes = {"join": sum(1 for nd in prog.graph.nodes
                              if "window_join" in nd),
                  "argmax": sum(1 for nd in prog.graph.nodes
                                if "window_argmax" in nd),
                  "aggs": sum(1 for nd in prog.graph.nodes
                              if "window_aggregator" in nd
                              and "non_window" not in nd)}
        LocalRunner(prog).run()
        rows = []
        for b in sink_output("results"):
            for i in range(len(next(iter(b.columns.values())))):
                rows.append((int(b.columns["k"][i]),
                             int(b.columns["num"][i]),
                             int(b.columns["mx"][i])))
        return shapes, sorted(rows)

    monkeypatch.delenv("ARROYO_ARGMAX", raising=False)
    fshape, fused = run()
    assert fshape == {"join": 0, "argmax": 1, "aggs": 1}, (seed, fshape)
    monkeypatch.setenv("ARROYO_ARGMAX", "0")
    ushape, unfused = run()
    assert ushape["join"] == 1 and ushape["argmax"] == 0, (seed, ushape)
    assert fused == unfused, (seed, len(fused), len(unfused))
    assert len(fused) > 0, seed
    # the synthesized mx column really is the join's: mx == num everywhere
    assert all(num == mx for _, num, mx in fused), seed


@pytest.mark.parametrize("seed", [91, 92, 93, 94, 95, 96])
def test_fuzz_raw_argmax_fusion(seed, monkeypatch):
    """Random q7-shaped raw-stream joins against a per-window max with a
    window-range WHERE: the raw argmax fusion (event-time provenance
    proof) must drop the whole join AND the max-side aggregate, and emit
    exactly the rows the unfused TTL-join plan emits — across window
    widths, max/min, NULL values in the maximized column, tie
    multiplicity, parallelism, and batch splits."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(1000, 5000))
    width_s = int(rng.choice([2, 3, 5]))
    par = int(rng.integers(1, 4))
    outer = rng.choice(["max", "min"])
    nbatch = int(rng.integers(1, 6))
    ts = np.sort(rng.integers(0, 11 * SEC, n)).astype(np.int64)
    a = rng.integers(0, 25, n).astype(np.int64)
    # small value range -> heavy exact-tie multiplicity; NULLs never
    # equal the extremum and must not poison it
    v = rng.integers(1, 9, n).astype(np.float64)
    v[rng.random(n) < 0.15] = np.nan
    # a late trailing slice (timestamps far behind the watermark by the
    # time it arrives): the fused plan must match these against the
    # released windows' retained final extrema exactly as the TTL join
    # still holding the max row would
    late_frac = float(rng.choice([0.0, 0.1]))
    if late_frac:
        nlate = max(int(n * late_frac), 1)
        sel = rng.permutation(n)[:nlate]
        keep = np.setdiff1d(np.arange(n), sel)
        ts = np.concatenate([ts[keep], ts[sel]])
        a = np.concatenate([a[keep], a[sel]])
        v = np.concatenate([v[keep], v[sel]])
    bounds = np.linspace(0, n, nbatch + 1).astype(int)
    sql = f"""
        SELECT B.a AS a, B.v AS v
        FROM rawbids B
        JOIN (
          SELECT {outer}(v) AS mx,
                 TUMBLE(INTERVAL '{width_s}' SECOND) AS window
          FROM rawbids GROUP BY 2
        ) AS M
        ON B.v = M.mx
        WHERE B.et >= M.window_start AND B.et < M.window_end
    """

    def run():
        provider = SchemaProvider()
        provider.add_memory_table(
            "rawbids", {"a": "i", "v": "f", "et": "t"},
            [Batch(ts[lo:hi], {"a": a[lo:hi], "v": v[lo:hi],
                               "et": ts[lo:hi].copy()})
             for lo, hi in zip(bounds[:-1], bounds[1:]) if hi > lo],
            event_time_field="et")
        clear_sink("results")
        prog = Planner(provider).plan(sql, query_parallelism=par)
        shapes = {"join": sum(1 for nd in prog.graph.nodes
                              if "join" in nd),
                  "argmax": sum(1 for nd in prog.graph.nodes
                                if "window_argmax" in nd),
                  "aggs": sum(1 for nd in prog.graph.nodes
                              if "aggregator" in nd)}
        LocalRunner(prog).run()
        rows = []
        for b in sink_output("results"):
            for i in range(len(next(iter(b.columns.values())))):
                rows.append((int(b.columns["a"][i]),
                             float(b.columns["v"][i])))
        return shapes, sorted(rows)

    from arroyo_tpu.sql.planner import Planner

    monkeypatch.delenv("ARROYO_ARGMAX", raising=False)
    fshape, fused = run()
    assert fshape == {"join": 0, "argmax": 1, "aggs": 0}, (seed, fshape)
    monkeypatch.setenv("ARROYO_ARGMAX", "0")
    ushape, unfused = run()
    assert ushape["join"] >= 1 and ushape["argmax"] == 0, (seed, ushape)
    assert fused == unfused, (seed, len(fused), len(unfused))
    assert len(fused) > 0, seed
    if late_frac == 0.0:
        # every emitted row achieves its window's extremum in the numpy
        # oracle (with late rows, which rows the watermark drops from
        # the aggregate depends on batch boundaries — the differential
        # fused==unfused assertion above is the oracle there)
        ends = (ts // (width_s * SEC) + 1) * (width_s * SEC)
        best = {}
        for e, val in zip(ends.tolist(), v.tolist()):
            if np.isnan(val):
                continue
            cur = best.get(e)
            best[e] = (val if cur is None
                       else (max(cur, val) if outer == "max"
                             else min(cur, val)))
        exp = sorted((int(ai), float(vi))
                     for ai, vi, e in zip(a.tolist(), v.tolist(),
                                          ends.tolist())
                     if not np.isnan(vi) and vi == best.get(e))
        assert fused == exp, (seed, len(fused), len(exp))


@pytest.mark.parametrize("seed", [85, 86])
def test_fuzz_raw_argmax_checkpoint_restore(seed, tmp_path):
    """Crash/restore through the RAW argmax plan (q7's fused shape):
    the candidate buffer, its timers, the released-window guard, and
    the persisted final-extrema table must round-trip so the restored
    run emits exactly what an uncrashed run of the same program does."""
    import asyncio
    import json as _json

    from arroyo_tpu.engine.engine import Engine
    from arroyo_tpu.sql.planner import Planner
    from arroyo_tpu.types import StopMode

    rng = np.random.default_rng(seed)
    total = int(rng.integers(40000, 70000))
    crash_after = float(rng.uniform(0.05, 0.25))
    url = f"file://{tmp_path}/ckpt"

    def sql(out_path):
        # price % 97 gives heavy tie multiplicity at each window max
        return f"""
        CREATE TABLE nexmark WITH (connector = 'nexmark',
          event_rate = '20000', num_events = '{total}',
          batch_size = '2048', rate_limited = 'false',
          base_time_micros = '1700000000000000');
        CREATE TABLE outj (auction BIGINT, p BIGINT) WITH (
          connector = 'single_file', path = '{out_path}', type = 'sink');
        INSERT INTO outj
        WITH bids AS (SELECT bid.auction AS auction,
                             bid.price % 97 AS p,
                             bid.datetime AS et
            FROM nexmark WHERE bid IS NOT NULL)
        SELECT B.auction AS auction, B.p AS p
        FROM bids B
        JOIN (
          SELECT max(p) AS mx, TUMBLE(INTERVAL '1' SECOND) AS window
          FROM bids GROUP BY 2
        ) AS M ON B.p = M.mx
        WHERE B.et >= M.window_start AND B.et < M.window_end
        """

    def plan(out_path):
        prog = Planner(SchemaProvider()).plan(sql(out_path))
        assert any("window_argmax" in n for n in prog.graph.nodes)
        assert not any("join" in n for n in prog.graph.nodes)
        return prog

    oracle_path = f"{tmp_path}/oracle.jsonl"
    crash_path = f"{tmp_path}/crash.jsonl"

    async def run_plain():
        await Engine.for_local(plan(oracle_path),
                               f"rawam-oracle-{seed}").start().join()

    async def run_with_crash():
        eng = Engine.for_local(plan(crash_path), f"rawam-{seed}",
                               checkpoint_url=url)
        running = eng.start()
        join_t = asyncio.ensure_future(running.join())
        await asyncio.sleep(crash_after)
        if join_t.done():
            return False
        await running.checkpoint(1)
        ok = await running.wait_for_checkpoint(1)
        if not ok or join_t.done():
            await asyncio.wait([join_t])
            return False
        await running.stop(StopMode.IMMEDIATE)
        try:
            await join_t
        except RuntimeError:
            pass
        return True

    async def run_restored():
        eng = Engine.for_local(plan(crash_path), f"rawam-{seed}",
                               checkpoint_url=url, restore_epoch=1)
        await eng.start().join()

    asyncio.run(run_plain())
    if asyncio.run(run_with_crash()):
        asyncio.run(run_restored())
    exp = sorted((r["auction"], r["p"]) for r in
                 (_json.loads(line) for line in open(oracle_path)))
    got = sorted((r["auction"], r["p"]) for r in
                 (_json.loads(line) for line in open(crash_path)))
    assert got == exp, (seed, len(got), len(exp))
    assert len(exp) > 0, seed


@pytest.mark.parametrize("seed", [81, 82, 83])
def test_fuzz_argmax_fusion_checkpoint_restore(seed, tmp_path):
    """Crash/restore through the FUSED argmax plan: the WindowArgmax
    buffer and its timers must round-trip state so the restored run
    still emits exactly the unfused join's rows."""
    import asyncio
    import json as _json

    from arroyo_tpu.engine.engine import Engine
    from arroyo_tpu.sql.planner import Planner
    from arroyo_tpu.types import StopMode

    rng = np.random.default_rng(seed)
    total = int(rng.integers(3000, 6000))
    crash_after = float(rng.uniform(0.05, 0.2))
    out_path = f"{tmp_path}/out.jsonl"
    url = f"file://{tmp_path}/ckpt"
    job = f"argmax-restore-{seed}"
    sql = f"""
    CREATE TABLE imp WITH (connector = 'impulse', event_rate = '30000',
      message_count = '{total}', batch_size = '128',
      event_time_interval_micros = '1000',
      base_time_micros = '1700000000000000');
    CREATE TABLE outj (k BIGINT, num BIGINT) WITH (
      connector = 'single_file', path = '{out_path}', type = 'sink');
    INSERT INTO outj
    SELECT A.k AS k, A.num AS num
    FROM (
      SELECT counter % 7 AS k, TUMBLE(INTERVAL '1' SECOND) AS window,
             count(*) AS num
      FROM imp GROUP BY 1, 2
    ) AS A
    JOIN (
      SELECT max(num) AS mx, window FROM (
        SELECT count(*) AS num, counter % 7 AS k,
               TUMBLE(INTERVAL '1' SECOND) AS window
        FROM imp GROUP BY 2, 3
      ) AS B0 GROUP BY 2
    ) AS B ON A.num = B.mx AND A.window = B.window
    """

    def plan():
        prog = Planner(SchemaProvider()).plan(sql)
        assert any("window_argmax" in n for n in prog.graph.nodes)
        return prog

    async def run_with_crash():
        eng = Engine.for_local(plan(), job, checkpoint_url=url)
        running = eng.start()
        join_t = asyncio.ensure_future(running.join())
        await asyncio.sleep(crash_after)
        if join_t.done():
            return False
        await running.checkpoint(1)
        ok = await running.wait_for_checkpoint(1)
        if not ok or join_t.done():
            await asyncio.wait([join_t])
            return False
        await running.stop(StopMode.IMMEDIATE)
        try:
            await join_t
        except RuntimeError:
            pass
        return True

    async def run_restored():
        eng = Engine.for_local(plan(), job, checkpoint_url=url,
                               restore_epoch=1)
        await eng.start().join()

    if asyncio.run(run_with_crash()):
        asyncio.run(run_restored())
    got = sorted((r["k"], r["num"]) for r in
                 (_json.loads(line) for line in open(out_path)))

    # oracle: per tumbling second, the keys achieving the max count
    counters = np.arange(total, dtype=np.int64)
    ts = 1_700_000_000_000_000 + counters * 1000
    k = counters % 7
    wend = (ts // SEC + 1) * SEC
    exp = []
    for w in np.unique(wend):
        sel = wend == w
        ks, cnts = np.unique(k[sel], return_counts=True)
        mx = cnts.max()
        exp.extend((int(kk), int(mx)) for kk in ks[cnts == mx])
    assert got == sorted(exp), (seed, len(got), len(exp))
