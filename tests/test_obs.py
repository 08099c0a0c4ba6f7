"""Observability: metric names/labels parity, admin server endpoints,
logfmt JSON logging."""

import asyncio
import json
import logging

import httpx
import pytest

from arroyo_tpu.obs.admin import AdminServer
from arroyo_tpu.obs.logging_setup import LogfmtJsonFormatter, init_logging
from arroyo_tpu.obs.metrics import (REGISTRY, TaskMetrics, render_metrics,
                                    snapshot)
from arroyo_tpu.types import TaskInfo


def _ti(idx=0):
    return TaskInfo("job-m", "op-1", "window-agg", idx, 2)


def test_metric_names_match_reference():
    m = TaskMetrics(_ti())
    m.messages_recv.inc(10)
    m.messages_sent.inc(4)
    m.bytes_sent.inc(100)
    m.tx_queue_size.set(4096)
    text = render_metrics().decode()
    # exact names from arroyo-types/src/lib.rs:734-739
    for name in ("arroyo_worker_messages_recv",
                 "arroyo_worker_messages_sent",
                 "arroyo_worker_bytes_recv",
                 "arroyo_worker_bytes_sent",
                 "arroyo_worker_tx_queue_size",
                 "arroyo_worker_tx_queue_rem"):
        assert name in text, name
    # labels from TaskInfo::metric_label_map (lib.rs:579-585)
    assert 'operator_id="op-1"' in text
    assert 'subtask_idx="0"' in text
    assert 'operator_name="window-agg"' in text


def test_engine_run_populates_metrics():
    from arroyo_tpu import Stream
    from arroyo_tpu.engine.engine import LocalRunner

    prog = (Stream.source("impulse", {"event_rate": 0.0,
                                      "message_count": 300,
                                      "batch_size": 64})
            .map(lambda c: {"counter": c["counter"]}, name="m")
            .sink("blackhole", {}))
    LocalRunner(prog).run()
    snap = snapshot()
    recv = {k: v for k, v in snap.items()
            if k.startswith("arroyo_worker_messages_recv")}
    # map + sink subtasks each count 300 records received
    assert any(v >= 300 for v in recv.values()), snap


def test_admin_server_endpoints():
    async def scenario():
        admin = AdminServer("worker", details=lambda: {"tasks": 3})
        port = await admin.start()
        async with httpx.AsyncClient(
                base_url=f"http://127.0.0.1:{port}") as c:
            r = await c.get("/status")
            assert r.json()["status"] == "ok"
            assert r.json()["service"] == "arroyo-worker"
            r = await c.get("/name")
            assert r.text == "arroyo-worker"
            r = await c.get("/details")
            assert r.json()["details"] == {"tasks": 3}
            r = await c.get("/metrics")
            assert r.status_code == 200
            assert "arroyo_worker" in r.text
        await admin.stop()

    asyncio.new_event_loop().run_until_complete(scenario())


def test_logfmt_json_formatter():
    fmt = LogfmtJsonFormatter()
    rec = logging.LogRecord("arroyo.engine", logging.WARNING, "f.py", 1,
                            "task %s failed", ("op-1",), None)
    rec.job_id = "j1"
    out = json.loads(fmt.format(rec))
    assert out["level"] == "warning"
    assert out["message"] == "task op-1 failed"
    assert out["target"] == "arroyo.engine"
    assert out["job_id"] == "j1"
    assert out["ts"].endswith("Z")


def test_init_logging_sets_excepthook(monkeypatch):
    import sys

    old = sys.excepthook
    try:
        init_logging("test-svc")
        assert sys.excepthook is not old  # panic hook installed
    finally:
        sys.excepthook = old


@pytest.mark.slow
def test_admin_profile_capture():
    """POST /debug/profile captures a jax profiler (Perfetto) trace — the
    pyroscope continuous-profiling analog."""
    import asyncio
    import urllib.request
    import json as _json

    import jax.numpy as jnp

    from arroyo_tpu.obs.admin import AdminServer

    async def scenario(tmp):
        admin = AdminServer("test")
        port = await admin.start()

        async def work():
            # some device work inside the profiling window
            for _ in range(20):
                (jnp.ones((256, 256)) @ jnp.ones((256, 256))).block_until_ready()
                await asyncio.sleep(0.01)

        async def capture():
            loop = asyncio.get_event_loop()
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/debug/profile",
                data=_json.dumps({"seconds": 0.5, "dir": tmp}).encode(),
                method="POST",
                headers={"Content-Type": "application/json"})
            return await loop.run_in_executor(
                None, lambda: _json.loads(
                    urllib.request.urlopen(req, timeout=30).read()))

        _, resp = await asyncio.gather(work(), capture())
        await admin.stop()
        return resp

    import tempfile
    with tempfile.TemporaryDirectory() as tmp:
        resp = asyncio.run(scenario(tmp))
    assert resp["traces"], f"no trace files captured: {resp}"


def test_tx_queue_gauges_wired():
    """Backpressure visibility: the collector keeps the tx-queue
    capacity/remaining gauges current (round-1 gap: gauges existed but
    were never set)."""
    from arroyo_tpu import Stream
    from arroyo_tpu.connectors.memory import clear_sink
    from arroyo_tpu.engine.engine import LocalRunner
    from arroyo_tpu.obs.metrics import snapshot
    import numpy as np
    from arroyo_tpu.types import Batch

    clear_sink("qg")
    ts = np.arange(500, dtype=np.int64)
    prog = (Stream.source("memory", {"batches": [
                Batch(ts, {"v": ts.copy()})]})
            .map(lambda c: {"v": c["v"]}, name="m")
            .sink("memory", {"name": "qg"}))
    LocalRunner(prog).run()
    snap = snapshot()
    sizes = {k: v for k, v in snap.items()
             if k.startswith("arroyo_worker_tx_queue_size")}
    rems = {k: v for k, v in snap.items()
            if k.startswith("arroyo_worker_tx_queue_rem")}
    assert any(v > 0 for v in sizes.values()), sizes
    assert any(v > 0 for v in rems.values()), rems


def test_table_size_gauge_updates_at_checkpoint(tmp_path):
    """arroyo_worker_table_size_keys (the reference's per-table state-size
    gauge) reflects key counts after a checkpoint barrier."""
    import asyncio

    from arroyo_tpu import Stream
    from arroyo_tpu.engine.engine import Engine
    from arroyo_tpu.graph.logical import AggKind, AggSpec
    from arroyo_tpu.obs.metrics import snapshot
    from arroyo_tpu.types import StopMode

    prog = (Stream.source("impulse", {"event_rate": 50_000.0,
                                      "message_count": 50_000,
                                      "event_time_interval_micros": 1000,
                                      "batch_size": 512})
            .watermark(max_lateness_micros=0)
            .map(lambda c: {"counter": c["counter"],
                            "bucket": c["counter"] % 9}, name="b")
            .key_by("bucket")
            .tumbling_aggregate(1_000_000,
                                [AggSpec(AggKind.COUNT, None, "cnt")])
            .sink("blackhole", {}))

    async def run():
        eng = Engine.for_local(prog, "gauge-job",
                               checkpoint_url=f"file://{tmp_path}/ck")
        running = eng.start()
        await asyncio.sleep(0.1)
        await running.checkpoint(1)
        assert await running.wait_for_checkpoint(1)
        vals = snapshot("arroyo_worker_table_size_keys")
        await running.stop(StopMode.IMMEDIATE)
        try:
            await running.join()
        except RuntimeError:
            pass
        return vals

    vals = asyncio.run(run())
    assert vals, "no table-size gauges recorded"
    assert any(v > 0 for v in vals.values())


# ---------------------------------------------------------------------------
# flight recorder: lag/latency histograms, trace spans, checkpoint cost
# ---------------------------------------------------------------------------


def test_lag_and_latency_histograms_populated():
    """The per-operator flight-recorder histograms (event-time lag,
    watermark lag, batch latency, queue wait) fill in during a normal
    watermarked run."""
    from arroyo_tpu import Stream
    from arroyo_tpu.engine.engine import LocalRunner

    prog = (Stream.source("impulse", {"event_rate": 100_000.0,
                                      "message_count": 20_000,
                                      "event_time_interval_micros": 100,
                                      "batch_size": 512})
            .watermark(max_lateness_micros=0)
            .map(lambda c: {"counter": c["counter"]}, name="lagmap")
            .sink("blackhole", {}))
    LocalRunner(prog).run()
    snap = snapshot()

    def count_of(metric):
        return sum(v for k, v in snap.items()
                   if k.startswith(metric + "_count"))

    for metric in ("arroyo_worker_event_time_lag_seconds",
                   "arroyo_worker_watermark_lag_seconds",
                   "arroyo_worker_batch_processing_seconds",
                   "arroyo_worker_queue_wait_seconds"):
        assert count_of(metric) > 0, (metric, sorted(snap)[:40])
    # histograms render with reference-compatible names + labels
    text = render_metrics().decode()
    assert 'arroyo_worker_event_time_lag_seconds_bucket{' in text
    assert 'operator_name=' in text


def test_admin_trace_endpoint_serves_chrome_trace():
    """GET /trace returns Chrome-trace JSON (Perfetto-loadable): ph=X
    complete events with ts/dur microseconds, filterable by category."""
    from arroyo_tpu.obs import tracing

    async def scenario():
        tracing.reset()
        with tracing.span("checkpoint.sync", "checkpoint", tid="op-1-0",
                          args={"epoch": 3}):
            pass
        with tracing.span("kernel", "kernel", tid="op-2-0"):
            pass
        admin = AdminServer("worker")
        port = await admin.start()
        async with httpx.AsyncClient(
                base_url=f"http://127.0.0.1:{port}") as c:
            r = await c.get("/trace")
            assert r.status_code == 200
            doc = r.json()
            evs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
            names = {e["name"] for e in evs}
            assert {"checkpoint.sync", "kernel"} <= names
            ck = next(e for e in evs if e["name"] == "checkpoint.sync")
            assert ck["args"]["epoch"] == 3
            assert ck["tid"] == "op-1-0"
            assert ck["dur"] >= 0 and ck["ts"] > 0
            # category filter
            r = await c.get("/trace", params={"cat": "kernel"})
            names = {e["name"] for e in r.json()["traceEvents"]
                     if e["ph"] == "X"}
            assert names == {"kernel"}
        await admin.stop()

    asyncio.new_event_loop().run_until_complete(scenario())


def test_checkpoint_metrics_and_spans(tmp_path):
    """After a checkpointed run: per-subtask checkpoint duration/bytes
    histogram samples, per-table cost gauges, and checkpoint trace spans
    all appear."""
    from arroyo_tpu import Stream
    from arroyo_tpu.engine.engine import Engine
    from arroyo_tpu.graph.logical import AggKind, AggSpec
    from arroyo_tpu.obs import tracing
    from arroyo_tpu.types import StopMode

    tracing.reset()
    prog = (Stream.source("impulse", {"event_rate": 50_000.0,
                                      "message_count": 50_000,
                                      "event_time_interval_micros": 1000,
                                      "batch_size": 512})
            .watermark(max_lateness_micros=0)
            .map(lambda c: {"counter": c["counter"],
                            "bucket": c["counter"] % 9}, name="ckb")
            .key_by("bucket")
            .tumbling_aggregate(1_000_000,
                                [AggSpec(AggKind.COUNT, None, "cnt")])
            .sink("blackhole", {}))

    async def run():
        eng = Engine.for_local(prog, "ckpt-metrics-job",
                               checkpoint_url=f"file://{tmp_path}/ck")
        running = eng.start()
        await asyncio.sleep(0.1)
        await running.checkpoint(1)
        assert await running.wait_for_checkpoint(1)
        await running.stop(StopMode.IMMEDIATE)
        try:
            await running.join()
        except RuntimeError:
            pass

    asyncio.new_event_loop().run_until_complete(run())
    snap = snapshot()
    dur = {k: v for k, v in snap.items()
           if k.startswith("arroyo_worker_checkpoint_duration_seconds_count")
           and "ckpt-metrics-job" in k}
    assert any(v > 0 for v in dur.values()), sorted(snap)[:40]
    tbl = snapshot("arroyo_worker_checkpoint_table_bytes")
    assert any(v > 0 and "ckpt-metrics-job" in k for k, v in tbl.items()), tbl
    cats = {s[0] for s in tracing.spans("checkpoint")}
    assert "checkpoint.sync" in cats
    assert "checkpoint.table" in cats


def test_kernel_time_attributed_per_operator():
    """timed_device dispatch time lands in the active task's
    arroyo_worker_kernel_seconds_total counter (the always-cheap
    per-operator accumulator)."""
    from arroyo_tpu.obs import perf

    ti = TaskInfo("kacc-job", "op-k", "kernels", 0, 1)
    tm = TaskMetrics(ti)
    acc = perf.KernelAccumulator(ti, tm)
    token = perf.set_active_task(acc)
    try:
        out = perf.timed_device(lambda x: x * 2, 21)
    finally:
        perf.reset_active_task(token)
    assert out == 42
    vals = {k: v for k, v in snapshot(
        "arroyo_worker_kernel_seconds").items()
        if "kacc-job" in k and "_total" in k}
    assert any(v > 0 for v in vals.values()), vals


# every jitted kernel factory of ops/keyed_bins.py, with arguments for a
# tiny state, and the stable name its kernel carries into XLA's module
# line (jit_<name>), the trace ring and the named dispatch counters
BIN_KERNELS = {
    "_update_kernel": ((("count",), 64, 8, 256), "bins_update"),
    "_emit_kernel": ((("count",), 64, 8, 5, 1), "bins_emit"),
    "_argmax_nnz_kernel": ((64, 8, 5, 1, "max"), "bins_argmax_nnz"),
    "_argmax_gather_kernel": ((64, 8, 5, 1, 8), "bins_argmax_gather"),
    "_emit_count_kernel": ((64, 8, 5, 1), "bins_emit_count"),
    "_emit_compact_kernel": ((("count",), 64, 8, 5, 1, (), 256),
                             "bins_emit_compact"),
    "_linearize_kernel": ((("count",), 64, 8, 8), "bins_linearize"),
    "_evict_kernel": ((("count",), 64, 8), "bins_evict"),
}


@pytest.mark.parametrize("factory", sorted(BIN_KERNELS))
def test_bin_kernels_carry_stable_names(factory):
    import ast
    import inspect

    from arroyo_tpu.ops import keyed_bins

    args, name = BIN_KERNELS[factory]
    kernel = getattr(keyed_bins, factory)(*args)
    assert kernel.__name__ == name and kernel.__qualname__ == name
    names = [n for _, n in BIN_KERNELS.values()]
    assert len(set(names)) == len(names)
    # the table above misses no factory: every @jax.jit of the module
    jitted = [
        f.name for f in ast.parse(inspect.getsource(keyed_bins)).body
        if isinstance(f, ast.FunctionDef) and any(
            isinstance(g, ast.FunctionDef) and g.decorator_list
            and "jax.jit" in ast.unparse(g.decorator_list[0])
            for g in ast.walk(f))]
    assert sorted(jitted) == sorted(BIN_KERNELS)


def test_named_dispatch_counters_and_kernel_spans():
    """A dispatch through timed_device bumps kernel_dispatches.<name>
    beside the total, and its trace-ring span is named for the kernel."""
    import numpy as np

    from arroyo_tpu.graph.logical import AggKind, AggSpec
    from arroyo_tpu.obs import perf, tracing
    from arroyo_tpu.ops.keyed_bins import KeyedBinState

    ti = TaskInfo("knames-job", "op-n", "kernels", 0, 1)
    token = perf.set_active_task(perf.KernelAccumulator(ti, None))
    perf.reset()
    tracing.reset()
    try:
        st = KeyedBinState((AggSpec(AggKind.COUNT, None, "n"),),
                           1_000_000, 2_000_000, capacity=64)
        kh = np.arange(1, 41, dtype=np.uint64)
        st.update(kh, np.arange(40, dtype=np.int64) * 100_000, {})
        fired = st.fire_panes(3_000_000)
    finally:
        perf.reset_active_task(token)
    assert fired is not None
    assert perf.counter("kernel_dispatches.bins_update") == 1
    # a fire compacts on the device: the scan, then the pick
    assert perf.counter("kernel_dispatches.bins_emit_count") == 1
    assert perf.counter("kernel_dispatches.bins_emit_compact") == 1
    assert perf.counter("kernel_dispatches.bins_evict") == 1
    # the evict was a direct call before the kernels were named: it counts
    # under its name, and the unnamed total keeps its old meaning
    assert perf.counter("kernel_dispatches") == 3
    assert perf.counter("pane_update_cells") == 40
    assert perf.counter("pane_update_pad_cells") == 256 - 40
    # the live count, the cells' (key, pane) and their counts; a bare
    # COUNT(*) has no channel block to read
    assert perf.counter("d2h_syncs") == 3 and perf.counter("d2h_bytes") > 0
    assert perf.counter("pane_emit_cells") == len(fired[0]) > 0
    assert perf.counter("pane_scan_cells") == 40 * 3 * 2  # slots x k x W
    names = {s[0] for s in tracing.spans("kernel")}
    assert names and names <= {"bins_update", "bins_emit_count",
                               "bins_emit_compact", "bins_evict"}


def _fire_accounts(state, drain):
    """Counters and spans one pass over ``state`` leaves: 30 keys in the
    first second, then a watermark fire or a checkpoint drain."""
    import numpy as np

    from arroyo_tpu.obs import perf, tracing

    kh = np.arange(1, 31, dtype=np.uint64)
    state.update(kh, np.arange(30, dtype=np.int64) * 10_000,
                 {"v": np.ones(30)})
    perf.reset()
    tracing.reset()
    fired = state.drain_deltas() if drain else state.fire_panes(2_000_000)
    assert fired is not None and state._d2h == []
    return ({k: perf.counter(k) for k in (
        "window_fires", "pane_drains", "d2h_syncs", "d2h_bytes")},
        [s for s in tracing.spans("window") if s[0] == "window.fire.d2h"])


@pytest.mark.parametrize("mesh", [False, True], ids=["single", "mesh"])
@pytest.mark.parametrize("drain", [False, True], ids=["fire", "drain"])
def test_fire_and_drain_accounts_on_both_states(mesh, drain):
    """Both bin states keep the same accounts of a pass that reads panes
    back: a watermark fire counts ``window_fires`` and leaves one
    ``window.fire.d2h`` span with its watermark, a checkpoint drain counts
    ``pane_drains`` and no span; the readbacks are counted either way."""
    from arroyo_tpu.graph.logical import AggKind, AggSpec
    from arroyo_tpu.ops.keyed_bins import KeyedBinState
    from arroyo_tpu.parallel.mesh_window import MeshKeyedBinState

    aggs = (AggSpec(AggKind.SUM, "v", "total"),)
    if mesh:
        state = MeshKeyedBinState(aggs, 1_000_000, 1_000_000, capacity=64,
                                  n_shards=2)
    else:
        state = KeyedBinState(aggs, 1_000_000, 1_000_000, capacity=64)
    counts, spans = _fire_accounts(state, drain)
    assert counts["window_fires"] == (0 if drain else 1)
    assert counts["pane_drains"] == (1 if drain else 0)
    # mesh: as before; single: a drain reads the dense grid (values,
    # counts), a fire the live count and the compacted cells' (key, pane),
    # counts and channel block
    assert counts["d2h_syncs"] == (2 if drain and not mesh else 4)
    assert counts["d2h_bytes"] > 0
    if drain:
        assert spans == []
    else:
        (span,) = spans
        assert span[3] > 0 and span[6] == {"watermark": 2_000_000}


def test_controller_job_rollup_aggregates_heartbeat_snapshots():
    """The controller folds per-worker heartbeat summaries into job-level
    per-operator rollups: counters sum across workers, rates come from
    sample deltas, lag is worst-across-workers, backpressure from the
    queue gauges."""
    from arroyo_tpu import Stream
    from arroyo_tpu.controller.controller import (ControllerServer, Job,
                                                  WorkerInfo)

    prog = (Stream.source("impulse", {"event_rate": 1.0,
                                      "message_count": 1})
            .sink("blackhole", {}))
    ctrl = ControllerServer.__new__(ControllerServer)  # no sockets needed
    ctrl.jobs = {}
    job = Job("rj", prog, "file:///tmp/x", 1)
    ctrl.jobs["rj"] = job
    w = WorkerInfo("w0", "", "", 1)
    w.prev_snapshot = {"opA": {"messages_sent_total": 100.0,
                               "event_time_lag_seconds_sum": 1.0,
                               "event_time_lag_seconds_count": 10.0}}
    w.prev_time = 100.0
    w.metric_snapshot = {"opA": {"messages_sent_total": 300.0,
                                 "messages_recv_total": 300.0,
                                 "event_time_lag_seconds_sum": 3.0,
                                 "event_time_lag_seconds_count": 20.0,
                                 "tx_queue_size": 100.0,
                                 "tx_queue_rem": 25.0,
                                 "kernel_seconds_total": 1.5}}
    w.snapshot_time = 102.0
    w2 = WorkerInfo("w1", "", "", 1)
    w2.metric_snapshot = {"opA": {"messages_sent_total": 50.0,
                                  "event_time_lag_seconds_sum": 50.0,
                                  "event_time_lag_seconds_count": 10.0}}
    w2.snapshot_time = 102.0
    job.workers = {"w0": w, "w1": w2}
    (agg,) = ctrl.job_rollup("rj")
    assert agg["operator_id"] == "opA"
    assert agg["workers"] == 2
    assert agg["messages_sent"] == 350.0
    assert agg["records_per_sec"] == pytest.approx(100.0)  # (300-100)/2s
    # worst lag across workers: w0's window avg 0.2s vs w1's lifetime 5s
    assert agg["event_time_lag"] == pytest.approx(5.0)
    assert agg["backpressure"] == pytest.approx(0.75)
    assert agg["kernel_seconds"] == pytest.approx(1.5)


def test_job_rollup_lag_is_worst_subtask_not_worker_average():
    """Workers ship per-subtask histogram pairs (`fam_sum@idx`) so the
    rollup reports the worst co-located subtask, not the worker-wide
    average that would hide one hot subtask among idle siblings."""
    from arroyo_tpu import Stream
    from arroyo_tpu.controller.controller import (ControllerServer, Job,
                                                  WorkerInfo)

    prog = (Stream.source("impulse", {"event_rate": 1.0,
                                      "message_count": 1})
            .sink("blackhole", {}))
    ctrl = ControllerServer.__new__(ControllerServer)
    ctrl.jobs = {}
    job = Job("rj2", prog, "file:///tmp/x", 1)
    ctrl.jobs["rj2"] = job
    w = WorkerInfo("w0", "", "", 1)
    # one worker hosting 4 subtasks: three at 0.1s avg lag, one at 60s.
    # The flat worker-summed pair averages to ~15.1s; the per-subtask
    # pairs must surface 60s.
    snap = {"event_time_lag_seconds_sum": 60.3,
            "event_time_lag_seconds_count": 4.0,
            # one subtask saturated (rem 0), three idle: summed gauges
            # say backpressure 0.25, worst subtask says 1.0
            "tx_queue_size": 400.0, "tx_queue_rem": 300.0}
    for i, (s, c) in enumerate([(0.1, 1.0), (0.1, 1.0), (0.1, 1.0),
                                (60.0, 1.0)]):
        snap[f"event_time_lag_seconds_sum@{i}"] = s
        snap[f"event_time_lag_seconds_count@{i}"] = c
        snap[f"tx_queue_size@{i}"] = 100.0
        snap[f"tx_queue_rem@{i}"] = 0.0 if i == 3 else 100.0
    w.metric_snapshot = {"opA": snap}
    w.snapshot_time = 102.0
    job.workers = {"w0": w}
    (agg,) = ctrl.job_rollup("rj2")
    assert agg["event_time_lag"] == pytest.approx(60.0)
    assert agg["backpressure"] == pytest.approx(1.0)
    assert "_bp_worst" not in agg

    # legacy/flat payloads (no @ keys) still roll up via the summed pair
    w.metric_snapshot = {"opA": {"event_time_lag_seconds_sum": 60.3,
                                 "event_time_lag_seconds_count": 4.0}}
    (agg,) = ctrl.job_rollup("rj2")
    assert agg["event_time_lag"] == pytest.approx(60.3 / 4.0)


def test_job_operator_summary_ships_per_subtask_lag_pairs():
    """The heartbeat summary carries per-subtask `_sum@idx/_count@idx`
    pairs for the lag/latency families alongside the worker-summed flat
    pair (which bench.py and legacy consumers keep reading)."""
    from arroyo_tpu.obs.metrics import job_operator_summary

    TaskMetrics(TaskInfo("subjob", "opS", "opS", 0, 2)) \
        .event_time_lag.observe(0.1)
    TaskMetrics(TaskInfo("subjob", "opS", "opS", 1, 2)) \
        .event_time_lag.observe(60.0)
    g = job_operator_summary("subjob")["opS"]
    assert g["event_time_lag_seconds_count"] == pytest.approx(2.0)
    assert g["event_time_lag_seconds_sum"] == pytest.approx(60.1)
    assert g["event_time_lag_seconds_sum@0"] == pytest.approx(0.1)
    assert g["event_time_lag_seconds_sum@1"] == pytest.approx(60.0)
    assert g["event_time_lag_seconds_count@1"] == pytest.approx(1.0)
