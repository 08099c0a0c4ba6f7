"""Operator chaining + adaptive batch coalescing (PR 4).

Covers: the chaining pass's fuse/break rules, chain-off topology parity
(ARROYO_CHAIN=0 bit-for-bit), chain-on output equivalence with fewer
tasks, per-member flight-recorder attribution, jitted expression fusion
reducing kernel dispatches, chain-aware rescale override expansion, the
coalescer's boundary behavior (target/linger/schema-change/watermark
ordering), and the headline round-trip: an UN-chained checkpoint of a
Nexmark q5 plan restored CHAINED at a different parallelism with
exactly-once output."""

import asyncio
import json
import time

import numpy as np
import pytest

from arroyo_tpu import AggKind, AggSpec, Batch, Stream
from arroyo_tpu.connectors.memory import clear_sink, sink_output
from arroyo_tpu.engine.coalesce import BatchCoalescer
from arroyo_tpu.engine.engine import Engine, LocalRunner
from arroyo_tpu.graph.chaining import (
    chain_annotations,
    expand_overrides,
    plan_chains,
    validate_chain_plan,
)
from arroyo_tpu.types import StopMode

SEC = 1_000_000


def _map_filter_prog(sink_name, n=2000):
    return (
        Stream.source("impulse", {"event_rate": 0.0, "message_count": n,
                                  "batch_size": 128})
        .map(lambda c: {"counter": c["counter"],
                        "doubled": c["counter"] * 2}, name="double")
        .map(lambda c: {"counter": c["counter"],
                        "tripled": c["doubled"] + c["counter"]},
             name="triple")
        .filter(lambda c: c["tripled"] % 2 == 0, name="evens")
        .sink("memory", {"name": sink_name})
    )


# -- the planning pass -------------------------------------------------------


def test_plan_chains_fuse_and_break_rules():
    prog = (
        Stream.source("impulse", {"event_rate": 0.0, "message_count": 10},
                      parallelism=2)
        .map(lambda c: {"counter": c["counter"], "b": c["counter"] % 3},
             name="m1", )
        .map(lambda c: dict(c), name="m2")
        .key_by("b")
        .count()  # SHUFFLE edge in: breaks the chain
        .sink("memory", {"name": "pc"}, parallelism=1)
    )
    plan = plan_chains(prog)
    validate_chain_plan(prog, plan)
    assert len(plan.groups) == 1
    kinds = [prog.node(m).operator.kind.value for m in plan.groups[0]]
    # source and sink never chain; the shuffle into count breaks it
    assert kinds == ["expression", "expression", "key_by"]
    heads = chain_annotations(prog)
    assert set(heads.values()) == {plan.groups[0][0]}


def test_plan_chains_breaks_on_parallelism_change():
    from arroyo_tpu.graph.logical import (ColumnExpr, LogicalOperator,
                                          OpKind)

    s = Stream.source("impulse", {"event_rate": 0.0, "message_count": 10},
                      parallelism=2)
    m = s.map(lambda c: dict(c), name="m1")
    m2 = m._chain(
        LogicalOperator(OpKind.EXPRESSION, "m2",
                        expr=ColumnExpr("m2", lambda c: dict(c))),
        parallelism=4)  # rebalance edge: must not chain across it
    m3 = m2.map(lambda c: dict(c), name="m3")
    m3.sink("memory", {"name": "pf"})
    plan = plan_chains(m.program)
    validate_chain_plan(m.program, plan)
    for grp in plan.groups:
        pars = {m.program.node(x).parallelism for x in grp}
        assert len(pars) == 1
    # m1 (p=2) never groups with m2 (p=4); m2+m3 (both p=4) do
    assert any(len(grp) == 2 for grp in plan.groups)


def test_chain_disabled_is_empty_plan(monkeypatch):
    monkeypatch.setenv("ARROYO_CHAIN", "0")
    prog = _map_filter_prog("off-plan")
    plan = plan_chains(prog)
    assert not plan.groups and not plan.head_of
    assert chain_annotations(prog) == {}


def test_expand_overrides_addresses_whole_chain():
    prog = (
        Stream.source("impulse", {"event_rate": 0.0, "message_count": 10},
                      parallelism=2)
        .map(lambda c: dict(c), name="m1")
        .map(lambda c: dict(c), name="m2")
        .key_by("counter")
        .count()
        .sink("memory", {"name": "eo"}, parallelism=1)
    )
    plan = plan_chains(prog)
    (chain,) = plan.groups
    out = expand_overrides(prog, {chain[1]: 6})
    # the override lands on every member of the chain, nothing else
    assert out == {m: 6 for m in chain}
    # max_parallelism of ANY member caps the whole chain
    prog.node(chain[0]).max_parallelism = 3
    out = expand_overrides(prog, {chain[1]: 6})
    assert out == {m: 3 for m in chain}
    # unchained operators pass through untouched
    count_id = next(n.operator_id for n in prog.nodes()
                    if n.operator_id.endswith("_count"))
    assert expand_overrides(prog, {count_id: 2}) == {count_id: 2}


# -- topology + equivalence --------------------------------------------------


def _run_engine(prog, job_id):
    async def scenario():
        engine = Engine.for_local(prog, job_id)
        running = engine.start()
        await running.join()
        return engine

    return asyncio.run(scenario())


def test_chain_off_reproduces_per_operator_topology(monkeypatch):
    """ARROYO_CHAIN=0: one task per logical operator subtask, singleton
    member lists — today's topology bit-for-bit."""
    monkeypatch.setenv("ARROYO_CHAIN", "0")
    clear_sink("topo-off")
    prog = _map_filter_prog("topo-off")
    engine = _run_engine(prog, "topo-off-job")
    n_ops = len(prog.nodes())
    assert len(engine.subtasks) == n_ops == 5
    for (op_id, _), h in engine.subtasks.items():
        assert h.member_ids == [op_id]
        assert h.task_info.operator_id == op_id


def test_chain_on_equivalent_output_fewer_tasks(monkeypatch):
    monkeypatch.setenv("ARROYO_CHAIN", "0")
    clear_sink("eq-off")
    off_engine = _run_engine(_map_filter_prog("eq-off"), "eq-off-job")
    monkeypatch.setenv("ARROYO_CHAIN", "1")
    clear_sink("eq-on")
    on_engine = _run_engine(_map_filter_prog("eq-on"), "eq-on-job")

    rows_off = Batch.concat(sink_output("eq-off"))
    rows_on = Batch.concat(sink_output("eq-on"))
    assert sorted(rows_on.columns["counter"].tolist()) == \
        sorted(rows_off.columns["counter"].tolist())
    np.testing.assert_array_equal(
        np.sort(rows_on.columns["tripled"]),
        np.sort(rows_off.columns["tripled"]))
    # map+map+filter collapsed into one task: 3 runners instead of 5
    assert len(on_engine.subtasks) == 3 < len(off_engine.subtasks)
    chained = next(h for h in on_engine.subtasks.values()
                   if len(h.member_ids) > 1)
    assert len(chained.member_ids) == 3


def test_chained_members_keep_flight_recorder_attribution(monkeypatch):
    """Rollups still attribute per-member kernel-seconds / message
    counts after fusion — the autoscaler's policy input is unchanged.
    Pinned to the jitted composed-expr mode: the host ingest spine
    (tested separately below) dispatches no kernels at all."""
    from arroyo_tpu.obs.metrics import job_operator_summary

    monkeypatch.setenv("ARROYO_CHAIN", "1")
    monkeypatch.setenv("ARROYO_CHAIN_FUSE_INGEST", "0")
    clear_sink("attr")
    prog = _map_filter_prog("attr", n=4000)
    engine = _run_engine(prog, "attr-job")
    chained = next(h for h in engine.subtasks.values()
                   if len(h.member_ids) > 1)
    summary = job_operator_summary("attr-job")
    for m in chained.member_ids:
        assert m in summary, f"member {m} missing from rollup"
        assert summary[m].get("messages_recv_total", 0) >= 4000
        # event-time lag is observed per member, fused or not — the
        # autoscaler's lag signal stays per-operator
        assert summary[m].get("event_time_lag_seconds_count", 0) > 0
    # batch latency + kernel time attribute to each execution step's
    # FIRST member (a fused expression run is one dispatch); the two
    # step entries here are the fused double+triple head and the filter
    head = chained.member_ids[0]
    tail = chained.member_ids[-1]
    assert summary[head].get("batch_processing_seconds_count", 0) > 0
    assert summary[tail].get("batch_processing_seconds_count", 0) > 0
    assert summary[head].get("kernel_seconds_total", 0) > 0


def test_expression_fusion_reduces_dispatches(monkeypatch):
    """map→map→(filter) chains jit-compose: fewer kernel dispatches per
    run than the unchained topology over identical data.  Coalescing is
    pinned OFF: with it on, both topologies collapse to a handful of
    merged batches and the margin shrinks to ±1 dispatch — one stray
    async dispatch from a neighboring test then flips the comparison
    (observed flake at (6, 5))."""
    from arroyo_tpu.obs import perf

    monkeypatch.setenv("ARROYO_COALESCE", "0")

    def dispatches(chain):
        monkeypatch.setenv("ARROYO_CHAIN", chain)
        sink = f"disp-{chain}"
        clear_sink(sink)
        prog = _map_filter_prog(sink, n=8000)
        before = perf.counter("kernel_dispatches")
        _run_engine(prog, f"disp-job-{chain}")
        return perf.counter("kernel_dispatches") - before

    d_off = dispatches("0")
    d_on = dispatches("1")
    assert d_on < d_off, (d_on, d_off)


def test_chained_checkpoint_reports_every_member(monkeypatch):
    """One checkpoint_completed per (member operator, subtask): the
    controller's epoch tracker sees the same completions as unchained."""
    monkeypatch.setenv("ARROYO_CHAIN", "1")
    clear_sink("ckptm")

    async def scenario():
        prog = (
            Stream.source("impulse", {"event_rate": 5_000.0,
                                      "message_count": 2000,
                                      "batch_size": 100})
            .map(lambda c: {"counter": c["counter"]}, name="ident")
            .map(lambda c: {"counter": c["counter"] + 0}, name="ident2")
            .sink("memory", {"name": "ckptm"})
        )
        engine = Engine.for_local(prog, "ckptm-job")
        running = engine.start()
        await asyncio.sleep(0.05)
        await running.checkpoint(epoch=1)
        assert await running.wait_for_checkpoint(1)
        resps = await running.join()
        return prog, engine, resps

    prog, engine, resps = asyncio.run(scenario())
    assert len(engine.subtasks) == 3  # source, chain(ident,ident2), sink
    completed = {(r.operator_id, r.task_index) for r in resps
                 if r.kind == "checkpoint_completed"
                 and r.subtask_metadata.epoch == 1}
    expected = {(n.operator_id, 0) for n in prog.nodes()}
    assert completed == expected  # 4 member completions from 3 runners
    out = Batch.concat(sink_output("ckptm"))
    assert len(out) == 2000


# -- coalescer ---------------------------------------------------------------


def _batch(vals, ts0=1000):
    v = np.asarray(vals, dtype=np.int64)
    return Batch(np.arange(ts0, ts0 + len(v), dtype=np.int64), {"v": v})


def test_coalescer_target_and_passthrough():
    c = BatchCoalescer(target=10, linger_secs=60.0)
    assert c.add(0, _batch([])) == []  # empty: nothing buffered
    assert not c.pending
    # singleton below target buffers; deadline armed
    assert c.add(0, _batch([1, 2, 3])) == []
    assert c.pending and c.deadline is not None
    # crossing the target releases ONE merged batch
    out = c.add(0, _batch([4, 5, 6, 7, 8, 9, 10]))
    assert len(out) == 1
    side, merged = out[0]
    assert side == 0 and len(merged) == 10
    assert merged.columns["v"].tolist() == list(range(1, 11))
    assert not c.pending and c.deadline is None
    # a batch already >= target passes straight through, unmerged
    big = _batch(list(range(20)))
    out = c.add(1, big)
    assert out == [(1, big)]


def test_coalescer_schema_change_flushes_in_order():
    c = BatchCoalescer(target=100, linger_secs=60.0)
    c.add(0, _batch([1, 2]))
    other = Batch(np.array([5], dtype=np.int64),
                  {"w": np.array([9], dtype=np.int64)})
    out = c.add(0, other)
    # the incompatible batch releases the old run FIRST (order preserved)
    assert len(out) == 1 and out[0][1].columns["v"].tolist() == [1, 2]
    flushed = c.flush_all()
    assert len(flushed) == 1 and flushed[0][1].columns["w"].tolist() == [9]


def test_coalescer_sides_never_mix():
    c = BatchCoalescer(target=100, linger_secs=60.0)
    c.add(0, _batch([1]))
    c.add(1, _batch([2]))
    flushed = c.flush_all()
    assert [(s, b.columns["v"].tolist()) for s, b in flushed] == \
        [(0, [1]), (1, [2])]


def test_coalescer_linger_bound_honored_e2e(monkeypatch):
    """A rate-limited trickle (every batch far below target) must still
    flow: each fragment waits at most the linger bound."""
    monkeypatch.setenv("ARROYO_COALESCE", "1")
    monkeypatch.setenv("COALESCE_LINGER_MICROS", "5000")
    import arroyo_tpu.config as cfg

    cfg.reset_config()
    try:
        clear_sink("linger")
        prog = (
            Stream.source("impulse", {"event_rate": 2_000.0,
                                      "message_count": 400,
                                      "batch_size": 16})
            .map(lambda c: {"counter": c["counter"]}, name="ident")
            .sink("memory", {"name": "linger"})
        )
        t0 = time.perf_counter()
        LocalRunner(prog).run()
        wall = time.perf_counter() - t0
        out = Batch.concat(sink_output("linger"))
        assert len(out) == 400
        # 400 events at 2k/s is ~0.2s of stream; a broken linger (e.g.
        # waiting for the 8k-row target forever) would stall until
        # end-of-stream flush — bound the wall generously
        assert wall < 10.0
    finally:
        cfg.reset_config()


def test_coalesce_preserves_watermark_ordering(monkeypatch):
    """Windowed aggregation over many tiny batches: coalesced and
    uncoalesced runs must produce identical window contents — buffered
    records are never reordered past a watermark."""
    from arroyo_tpu.graph.logical import AggKind, AggSpec

    rng = np.random.default_rng(7)
    n = 5_000
    ts = np.sort(rng.integers(0, 3 * SEC, n)).astype(np.int64)
    src = Batch(ts, {"k": rng.integers(0, 16, n).astype(np.int64),
                     "v": rng.integers(0, 100, n).astype(np.int64)})
    # many tiny batches: memory source splits per configured batch
    batches = [src.select(np.arange(i, min(i + 64, n)))
               for i in range(0, n, 64)]

    def run_once(coalesce):
        monkeypatch.setenv("ARROYO_COALESCE", coalesce)
        clear_sink("wmord")
        prog = (Stream.source("memory", {"batches": batches})
                .watermark(max_lateness_micros=0)
                .key_by("k")
                .tumbling_aggregate(SEC // 2, [
                    AggSpec(AggKind.COUNT, None, "cnt"),
                    AggSpec(AggKind.SUM, "v", "s")])
                .sink("memory", {"name": "wmord"}))
        LocalRunner(prog).run()
        out = Batch.concat(sink_output("wmord"))
        order = np.lexsort((out.columns["window_end"],
                            np.asarray(out.key_hash, dtype=np.uint64)))
        return {c: out.columns[c][order].tolist()
                for c in ("cnt", "s", "window_end")}

    a = run_once("0")
    b = run_once("1")
    assert a == b


# -- checkpoint / restore / rescale round-trip (chained q5) ------------------


Q5_INSERT = """
CREATE TABLE nexmark WITH (
  connector = 'nexmark', event_rate = '1000000', num_events = '{n}',
  rate_limited = 'false', batch_size = '1024',
  base_time_micros = '1700000000000000'
);
CREATE TABLE sinkt (auction BIGINT, num BIGINT) WITH (
  connector = 'single_file', path = '{out}', type = 'sink');
INSERT INTO sinkt
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


def _q5_rows(path):
    rows = [json.loads(line) for line in open(path)]
    return sorted((r["auction"], r["num"]) for r in rows)


@pytest.mark.slow
def test_q5_unchained_checkpoint_restores_chained_with_rescale(
        tmp_path, monkeypatch):
    """The headline round-trip: checkpoint a q5 plan UN-chained, restore
    it CHAINED at higher parallelism (overrides expanded chain-wide),
    and assert exactly-once output against an uninterrupted reference.
    Proves per-member state naming survives fusion in both directions."""
    from arroyo_tpu.sql import plan_sql

    n = 120_000
    ref_path = tmp_path / "ref.jsonl"
    out_path = tmp_path / "out.jsonl"
    url = f"file://{tmp_path}/ckpt"

    # uninterrupted chained reference
    monkeypatch.setenv("ARROYO_CHAIN", "1")
    LocalRunner(plan_sql(Q5_INSERT.format(n=n, out=ref_path),
                         parallelism=2)).run()
    reference = _q5_rows(ref_path)
    assert reference

    # phase 1: run UN-chained, checkpoint-then-stop mid-stream
    monkeypatch.setenv("ARROYO_CHAIN", "0")
    prog = plan_sql(Q5_INSERT.format(n=n, out=out_path), parallelism=2)

    async def run_phase1():
        engine = Engine.for_local(prog, "q5-rt", checkpoint_url=url)
        running = engine.start()
        await asyncio.sleep(0.35)
        await running.checkpoint(epoch=1, then_stop=True)
        assert await running.wait_for_checkpoint(1, timeout=60)
        try:
            await running.join()
        except RuntimeError:
            pass

    asyncio.run(run_phase1())

    # phase 2: rescale the aggregate CHAIN (override expanded to all
    # members) and restore CHAINED from the un-chained checkpoint
    monkeypatch.setenv("ARROYO_CHAIN", "1")
    agg_id = next(nd.operator_id for nd in prog.nodes()
                  if "aggregator" in nd.operator_id)
    overrides = expand_overrides(prog, {agg_id: 3})
    assert len(overrides) > 1, "aggregate should sit in a chain"
    prog.update_parallelism(overrides)
    chain = plan_chains(prog).group_for(agg_id)
    assert chain is not None
    assert {prog.node(m).parallelism for m in chain} == {3}

    async def run_phase2():
        engine = Engine.for_local(prog, "q5-rt", checkpoint_url=url,
                                  restore_epoch=1)
        running = engine.start()
        await running.join()

    asyncio.run(run_phase2())
    assert _q5_rows(out_path) == reference


# -- ingest-spine fusion / shuffle-1 chaining / update coalescing (PR 9) -----


def test_ingest_spine_zero_dispatches_same_rows(monkeypatch):
    """The host spine runs elementwise chains with no kernel dispatch at
    all, emitting exactly the rows the jitted per-member path emits."""
    from arroyo_tpu.obs import perf

    monkeypatch.setenv("ARROYO_CHAIN", "1")
    monkeypatch.setenv("ARROYO_COALESCE", "0")

    def run(fuse):
        monkeypatch.setenv("ARROYO_CHAIN_FUSE_INGEST", fuse)
        sink = f"spine-{fuse}"
        clear_sink(sink)
        before = perf.counter("kernel_dispatches")
        _run_engine(_map_filter_prog(sink, n=6000), f"spine-job-{fuse}")
        d = perf.counter("kernel_dispatches") - before
        return d, Batch.concat(sink_output(sink))

    d_jit, rows_jit = run("0")
    d_spine, rows_spine = run("1")
    assert d_spine == 0, d_spine
    assert d_jit > 0
    np.testing.assert_array_equal(
        np.sort(rows_spine.columns["tripled"]),
        np.sort(rows_jit.columns["tripled"]))
    assert sorted(rows_spine.columns["counter"].tolist()) == \
        sorted(rows_jit.columns["counter"].tolist())


def test_spine_member_counts_survive_filters(monkeypatch):
    """Per-member recv/sent rollups stay exact through a spine whose
    predicate drops rows — the autoscaler's per-operator signals must
    not blur when members fuse."""
    from arroyo_tpu.obs.metrics import job_operator_summary

    monkeypatch.setenv("ARROYO_CHAIN", "1")
    monkeypatch.setenv("ARROYO_CHAIN_FUSE_INGEST", "1")
    clear_sink("spine-counts")
    prog = _map_filter_prog("spine-counts", n=4000)
    engine = _run_engine(prog, "spine-counts-job")
    chained = next(h for h in engine.subtasks.values()
                   if len(h.member_ids) > 1)
    assert len(chained.member_ids) == 3
    summary = job_operator_summary("spine-counts-job")
    double, triple, evens = chained.member_ids
    # maps are 1:1; the filter keeps counter % 2 == 0 (tripled = 3c)
    assert summary[double].get("messages_sent_total") == 4000
    assert summary[triple].get("messages_recv_total") == 4000
    assert summary[triple].get("messages_sent_total") == 4000
    assert summary[evens].get("messages_recv_total") == 4000
    assert summary[evens].get("messages_sent_total") == 2000


def test_shuffle1_chains_through_keyed_window(monkeypatch):
    """A parallelism-1 keyed window pipeline fuses into one task across
    the (routing-trivial) shuffle edge, with identical output rows."""
    rng = np.random.default_rng(7)
    ts = np.sort(rng.integers(0, 4 * SEC, 4000)).astype(np.int64)
    batches = [Batch(ts[i:i + 256],
                     {"k": rng.integers(0, 9, len(ts[i:i + 256])),
                      "v": np.ones(len(ts[i:i + 256]), dtype=np.int64)})
               for i in range(0, len(ts), 256)]

    from arroyo_tpu import AggSpec, TumblingWindow

    def build(sink):
        return (Stream.source("memory", {"batches": batches})
                .watermark(max_lateness_micros=0)
                .key_by("k")
                .window(TumblingWindow(SEC),
                        [AggSpec(AggKind.COUNT, None, "n")])
                .sink("memory", {"name": sink}))

    def run(flag):
        monkeypatch.setenv("ARROYO_CHAIN_SHUFFLE1", flag)
        sink = f"sh1-{flag}"
        clear_sink(sink)
        engine = _run_engine(build(sink), f"sh1-job-{flag}")
        rows = Batch.concat(sink_output(sink))
        key = sorted(zip(rows.columns["k"].tolist(),
                         rows.columns["window_end"].tolist(),
                         rows.columns["n"].tolist()))
        return len(engine.subtasks), key

    n_off, rows_off = run("0")
    n_on, rows_on = run("1")
    assert rows_on == rows_off
    assert n_on < n_off, (n_on, n_off)


def test_shuffle_chains_only_at_parallelism_1():
    """A plain SHUFFLE edge joins a chain iff both ends run at
    parallelism 1 (identity routing); at any other parallelism it
    breaks the chain exactly as before."""
    def build():
        return (
            Stream.source("impulse", {"event_rate": 0.0,
                                      "message_count": 10})
            .map(lambda c: {"counter": c["counter"],
                            "b": c["counter"] % 3}, name="m1")
            .key_by("b")
            .count()
            .sink("memory", {"name": "sh2"})
        )

    prog = build()
    plan = plan_chains(prog)
    count_id = next(n.operator_id for n in prog.nodes()
                    if n.operator_id.endswith("_count"))
    grp = plan.group_for(count_id)
    assert grp is not None, "p1 shuffle should chain into the count"
    # now the same shape at parallelism 2: the shuffle breaks the chain
    prog2 = build()
    for n in prog2.nodes():
        if n.operator.kind.value != "connector_sink":
            n.parallelism = 2
    plan2 = plan_chains(prog2)
    validate_chain_plan(prog2, plan2)
    for g in plan2.groups:
        for u, v in zip(g, g[1:]):
            assert prog2.edge(u, v).typ.value == "forward"


def test_update_coalescing_parity_with_snapshot_roundtrip():
    """Deferred window-state scatters are invisible to emission and
    checkpointing: same fired panes as a flush after every update, and
    a snapshot taken mid-buffer flushes first (a restore of it resumes
    bit-identically)."""
    from arroyo_tpu.ops.keyed_bins import KeyedBinState

    rng = np.random.default_rng(3)
    aggs = (AggSpec(AggKind.COUNT, None, "n"), AggSpec(AggKind.SUM, "v", "s"))

    def feed(state, upto):
        for i in range(upto):
            kh = rng2.integers(0, 50, 300).astype(np.uint64)
            t = rng2.integers(i * SEC, (i + 1) * SEC, 300).astype(np.int64)
            v = rng2.integers(1, 9, 300).astype(np.float64)
            state.update(kh, t, {"v": v})
            if not deferred:  # the oracle: every batch lands at once
                state.flush_updates()

    def fire(state):
        out = state.fire_panes(10 * SEC)
        if out is None:
            return None
        keys, cols, wend, cnts, _slots = out
        return sorted(zip(keys.tolist(), wend.tolist(),
                          cols["n"].tolist(), cols["s"].tolist()))

    results = {}
    for deferred in (False, True):
        rng2 = np.random.default_rng(11)
        st = KeyedBinState(aggs, SEC, 2 * SEC, capacity=64)
        feed(st, 6)
        snap = {k: np.copy(v) for k, v in st.snapshot().items()}
        # restore the mid-stream snapshot into a fresh state and finish
        st2 = KeyedBinState(aggs, SEC, 2 * SEC, capacity=64)
        st2.restore(snap)
        feed(st2, 2)
        results[deferred] = fire(st2)
    assert results[True] == results[False]
    assert results[True] is not None
