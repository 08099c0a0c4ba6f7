"""Real multi-process cluster: ProcessScheduler spawns worker OS
processes (schedulers/mod.rs:77-233 analog); the controller drives them
over gRPC and data crosses process boundaries on the TCP shuffle plane.
"""

import asyncio
import json

import pytest

from arroyo_tpu import Stream
from arroyo_tpu.controller.controller import ControllerServer
from arroyo_tpu.controller.scheduler import ProcessScheduler
from arroyo_tpu.controller.state_machine import JobState
from arroyo_tpu.graph.logical import AggKind, AggSpec



def test_process_cluster_pipeline(tmp_path):
    out_path = tmp_path / "out.jsonl"

    async def scenario():
        sched = ProcessScheduler()
        ctrl = ControllerServer(sched)
        await ctrl.start()
        prog = (
            Stream.source("impulse", {"event_rate": 0.0,
                                      "message_count": 3000,
                                      "event_time_interval_micros": 1000,
                                      "batch_size": 128}, parallelism=2)
            .watermark(max_lateness_micros=0)
            .map(lambda c: {"counter": c["counter"],
                            "bucket": c["counter"] % 7}, name="b")
            .key_by("bucket")
            .tumbling_aggregate(
                300 * 1000, [AggSpec(AggKind.COUNT, None, "cnt")],
                parallelism=2)
            .sink("single_file", {"path": str(out_path)}, parallelism=1)
        )
        job_id = await ctrl.submit_job(
            prog, checkpoint_url=f"file://{tmp_path}/ckpt", n_workers=2)
        try:
            # two real OS processes must register as workers
            for _ in range(300):
                if len(ctrl.jobs[job_id].workers) >= 2:
                    break
                await asyncio.sleep(0.1)
            assert len(ctrl.jobs[job_id].workers) >= 2, "workers never came"
            pids = sched.workers_for_job(job_id)
            assert len(pids) == 2 and all(p.startswith("pid-")
                                          for p in pids)
            state = await ctrl.wait_for_state(job_id, JobState.FINISHED,
                                              timeout=120)
        finally:
            await sched.stop_workers(job_id)
            await ctrl.stop()
        return state

    state = asyncio.run(scenario())
    assert state == JobState.FINISHED
    rows = [json.loads(line) for line in open(out_path)]
    assert sum(r["cnt"] for r in rows) == 3000
    assert len({r["bucket"] for r in rows}) == 7



def test_process_scheduler_stop_kills_workers(tmp_path):
    async def scenario():
        sched = ProcessScheduler()
        ctrl = ControllerServer(sched)
        await ctrl.start()
        prog = (
            Stream.source("impulse", {"event_rate": 50.0,
                                      "message_count": 10_000_000,
                                      "batch_size": 64})
            .map(lambda c: {"counter": c["counter"]}, name="m")
            .sink("blackhole", {})
        )
        job_id = await ctrl.submit_job(
            prog, checkpoint_url=f"file://{tmp_path}/ckpt", n_workers=1)
        await ctrl.wait_for_state(job_id, JobState.RUNNING, timeout=60)
        assert len(sched.workers_for_job(job_id)) == 1
        await sched.stop_workers(job_id, force=True)
        assert sched.workers_for_job(job_id) == []
        await ctrl.stop()

    asyncio.run(scenario())


def test_worker_kill_mid_run_recovers_exactly_once(tmp_path, monkeypatch):
    """Fault injection the reference lacks: SIGKILL a real worker process
    mid-stream; the controller must detect the dead worker, restart the
    job from the last checkpoint, and the output must be exactly-once."""
    import os
    import signal

    monkeypatch.setenv("HEARTBEAT_INTERVAL_SECS", "0.3")
    monkeypatch.setenv("HEARTBEAT_TIMEOUT_SECS", "2.0")
    monkeypatch.setenv("CHECKPOINT_INTERVAL_SECS", "0.5")
    from arroyo_tpu.config import reset_config

    reset_config()
    out_path = tmp_path / "out.jsonl"
    N = 40_000

    async def scenario():
        sched = ProcessScheduler()
        ctrl = ControllerServer(sched)
        await ctrl.start()
        prog = (
            Stream.source("impulse", {"event_rate": 8000.0,
                                      "message_count": N,
                                      "event_time_interval_micros": 1000,
                                      "batch_size": 256})
            .watermark(max_lateness_micros=0)
            .map(lambda c: {"counter": c["counter"],
                            "bucket": c["counter"] % 5}, name="b")
            .key_by("bucket")
            .tumbling_aggregate(
                500 * 1000, [AggSpec(AggKind.COUNT, None, "cnt")])
            .sink("single_file", {"path": str(out_path)})
        )
        job_id = await ctrl.submit_job(
            prog, checkpoint_url=f"file://{tmp_path}/ckpt", n_workers=1)
        try:
            # wait until at least one checkpoint has completed
            for _ in range(600):
                if (ctrl.jobs[job_id].last_successful_epoch or 0) >= 1:
                    break
                await asyncio.sleep(0.05)
            assert (ctrl.jobs[job_id].last_successful_epoch or 0) >= 1

            # SIGKILL the worker process, mid-stream
            [pid_s] = sched.workers_for_job(job_id)
            os.kill(int(pid_s.split("-", 1)[1]), signal.SIGKILL)

            state = await ctrl.wait_for_state(job_id, JobState.FINISHED,
                                              timeout=120)
        finally:
            await sched.stop_workers(job_id)
            await ctrl.stop()
        return state

    try:
        state = asyncio.run(scenario())
    finally:
        # drop the cached fast-heartbeat config so later tests re-read the
        # (restored) env
        reset_config()
    assert state == JobState.FINISHED
    rows = [json.loads(line) for line in open(out_path)]
    assert sum(r["cnt"] for r in rows) == N  # exactly-once across the kill


@pytest.mark.slow
def test_mesh_sharded_state_inside_cluster_worker(tmp_path, monkeypatch):
    """A real TPU pod is one worker x many chips: run the mesh-sharded
    BinAgg state INSIDE a process-cluster worker (ARROYO_MESH=8 over the
    8-device CPU mesh the worker inherits), checkpoint mid-stream, SIGKILL
    the worker, and recover — exactly-once output AND the checkpoint must
    provably have been written by the 8-shard mesh state."""
    import os
    import signal

    import numpy as np

    monkeypatch.setenv("ARROYO_MESH", "8")  # inherited by the worker proc
    monkeypatch.setenv("HEARTBEAT_INTERVAL_SECS", "0.3")
    monkeypatch.setenv("HEARTBEAT_TIMEOUT_SECS", "2.0")
    monkeypatch.setenv("CHECKPOINT_INTERVAL_SECS", "0.5")
    from arroyo_tpu.config import reset_config

    reset_config()
    out_path = tmp_path / "out.jsonl"
    N = 30_000

    async def scenario():
        sched = ProcessScheduler()
        ctrl = ControllerServer(sched)
        await ctrl.start()
        prog = (
            Stream.source("impulse", {"event_rate": 8000.0,
                                      "message_count": N,
                                      "event_time_interval_micros": 1000,
                                      "batch_size": 256})
            .watermark(max_lateness_micros=0)
            .map(lambda c: {"counter": c["counter"],
                            "bucket": c["counter"] % 5}, name="b")
            .key_by("bucket")
            .sliding_aggregate(
                500 * 1000, 250 * 1000,
                [AggSpec(AggKind.COUNT, None, "cnt")])
            .sink("single_file", {"path": str(out_path)})
        )
        job_id = await ctrl.submit_job(
            prog, checkpoint_url=f"file://{tmp_path}/ckpt", n_workers=1)
        try:
            for _ in range(600):
                if (ctrl.jobs[job_id].last_successful_epoch or 0) >= 1:
                    break
                await asyncio.sleep(0.05)
            assert (ctrl.jobs[job_id].last_successful_epoch or 0) >= 1

            [pid_s] = sched.workers_for_job(job_id)
            os.kill(int(pid_s.split("-", 1)[1]), signal.SIGKILL)

            state = await ctrl.wait_for_state(job_id, JobState.FINISHED,
                                              timeout=120)
        finally:
            await sched.stop_workers(job_id)
            await ctrl.stop()
        return state

    try:
        state = asyncio.run(scenario())
    finally:
        reset_config()
    assert state == JobState.FINISHED

    # exactly-once: every sliding pane counted, no pane twice.  Each event
    # feeds width/slide = 2 panes.
    rows = [json.loads(line) for line in open(out_path)]
    assert sum(r["cnt"] for r in rows) == 2 * N
    assert len({r["bucket"] for r in rows}) == 5

    # the checkpoint must carry the mesh provenance marker: the device
    # table snapshot was written by the 8-shard MeshKeyedBinState (the
    # canonical format stores arrays as __array__<name> rows)
    import io

    import pyarrow.parquet as pq

    shards_seen = set()
    for root, _dirs, files in os.walk(tmp_path / "ckpt"):
        for f in files:
            if not f.endswith(".parquet"):
                continue
            table = pq.read_table(os.path.join(root, f))
            for key, val in zip(table.column("key").to_pylist(),
                                table.column("value").to_pylist()):
                if bytes(key) == b"__array__mesh_shards":
                    arr = np.load(io.BytesIO(bytes(val)),
                                  allow_pickle=True)
                    shards_seen.add(int(arr[0]))
    assert 8 in shards_seen, (
        f"no 8-shard mesh checkpoint found (saw {shards_seen})")


@pytest.mark.slow
def test_controller_crash_resumes_job_from_durable_store(tmp_path, monkeypatch):
    """Durable controller (states/mod.rs:577-628 analog): submit a
    checkpointing job, CRASH the controller (no graceful stop — workers
    orphaned), start a fresh controller on the same sqlite store: it must
    reap the orphans, re-adopt the job, return it to Running, and finish
    with exactly-once output from the last checkpoint."""
    import os

    monkeypatch.setenv("HEARTBEAT_INTERVAL_SECS", "0.3")
    monkeypatch.setenv("HEARTBEAT_TIMEOUT_SECS", "2.0")
    monkeypatch.setenv("CHECKPOINT_INTERVAL_SECS", "0.5")
    from arroyo_tpu.config import reset_config

    reset_config()
    out_path = tmp_path / "out.jsonl"
    db_path = str(tmp_path / "controller.db")
    N = 40_000

    def make_prog():
        return (
            Stream.source("impulse", {"event_rate": 8000.0,
                                      "message_count": N,
                                      "event_time_interval_micros": 1000,
                                      "batch_size": 256})
            .watermark(max_lateness_micros=0)
            .map(lambda c: {"counter": c["counter"],
                            "bucket": c["counter"] % 5}, name="b")
            .key_by("bucket")
            .tumbling_aggregate(
                500 * 1000, [AggSpec(AggKind.COUNT, None, "cnt")])
            .sink("single_file", {"path": str(out_path)})
        )

    async def incarnation_one():
        sched = ProcessScheduler()
        ctrl = ControllerServer(sched, db_path=db_path)
        await ctrl.start()
        job_id = await ctrl.submit_job(
            make_prog(), checkpoint_url=f"file://{tmp_path}/ckpt",
            n_workers=1)
        await ctrl.wait_for_state(job_id, JobState.RUNNING, timeout=60)
        for _ in range(600):
            if (ctrl.jobs[job_id].last_successful_epoch or 0) >= 1:
                break
            await asyncio.sleep(0.05)
        assert (ctrl.jobs[job_id].last_successful_epoch or 0) >= 1
        orphan_pids = sched.workers_for_job(job_id)
        assert orphan_pids
        # CRASH: cancel the supervisor and drop the rpc server without
        # stopping workers or touching the scheduler
        ctrl.jobs[job_id].supervisor.cancel()
        await ctrl.rpc.stop()
        ctrl.store.close()
        return job_id, orphan_pids

    async def incarnation_two(job_id, orphan_pids):
        sched = ProcessScheduler()
        ctrl = ControllerServer(sched, db_path=db_path)
        await ctrl.start()  # resumes from the store
        try:
            assert job_id in ctrl.jobs, "job not re-adopted from store"
            state = await ctrl.wait_for_state(
                job_id, JobState.RUNNING, JobState.FINISHED, timeout=90)
            assert state in (JobState.RUNNING, JobState.FINISHED)
            # the first incarnation's workers must be gone (reaped or
            # self-terminated); pids must not linger running our worker
            for p in orphan_pids:
                pid = int(p.split("-", 1)[1])
                try:
                    with open(f"/proc/{pid}/cmdline", "rb") as f:
                        assert b"arroyo_tpu.worker.server" not in f.read()
                except OSError:
                    pass  # gone — good
            state = await ctrl.wait_for_state(job_id, JobState.FINISHED,
                                              timeout=120)
            # durable store converged too
            rows = ctrl.store.resumable()
            assert all(r.job_id != job_id for r in rows)
        finally:
            await sched.stop_workers(job_id)
            await ctrl.stop()
        return state

    try:
        job_id, orphans = asyncio.run(incarnation_one())
        state = asyncio.run(incarnation_two(job_id, orphans))
    finally:
        reset_config()
    assert state == JobState.FINISHED
    rows = [json.loads(line) for line in open(out_path)]
    assert sum(r["cnt"] for r in rows) == N
    assert len({r["bucket"] for r in rows}) == 5


def test_expired_ttl_job_settles_on_controller_restart(tmp_path):
    """A preview (ttl) job whose deadline passed while the controller
    was down must settle to Stopped on resume — not run forever (the
    API-side reaper died with the old process; the deadline lives in
    the durable store)."""
    from arroyo_tpu.controller.scheduler import InProcessScheduler

    db_path = str(tmp_path / "c.db")

    async def one():
        sched = InProcessScheduler()
        ctrl = ControllerServer(sched, db_path=db_path)
        await ctrl.start()
        prog = (
            Stream.source("impulse", {"event_rate": 50.0,
                                      "message_count": 10_000_000,
                                      "batch_size": 32})
            .map(lambda c: {"counter": c["counter"]}, name="m")
            .sink("blackhole", {})
        )
        jid = await ctrl.submit_job(
            prog, checkpoint_url=f"file://{tmp_path}/ckpt",
            ttl_secs=1.0)
        await ctrl.wait_for_state(jid, JobState.RUNNING, timeout=60)
        # crash without stopping the job; in-process workers die with
        # the process, so kill them too (leaving their grpc servers to
        # the GC raises unraisable-exception noise on loop close)
        ctrl.jobs[jid].supervisor.cancel()
        await sched.stop_workers(jid, force=True)
        await ctrl.rpc.stop()
        ctrl.store.close()
        return jid

    async def two(jid):
        await asyncio.sleep(1.2)  # deadline passes while "down"
        ctrl = ControllerServer(InProcessScheduler(), db_path=db_path)
        await ctrl.start()
        try:
            assert jid not in ctrl.jobs, "expired ttl job was resumed"
            rows = ctrl.store.resumable()
            assert all(r.job_id != jid for r in rows)
        finally:
            await ctrl.stop()

    jid = asyncio.run(one())
    asyncio.run(two(jid))


@pytest.mark.slow
def test_live_ttl_survives_controller_restart(tmp_path):
    """A ttl job restarted BEFORE its deadline resumes — and the new
    controller's supervisor still stops it when the deadline passes."""
    from arroyo_tpu.controller.scheduler import InProcessScheduler

    db_path = str(tmp_path / "c.db")

    async def one():
        sched = InProcessScheduler()
        ctrl = ControllerServer(sched, db_path=db_path)
        await ctrl.start()
        prog = (
            Stream.source("impulse", {"event_rate": 50.0,
                                      "message_count": 10_000_000,
                                      "batch_size": 32})
            .map(lambda c: {"counter": c["counter"]}, name="m")
            .sink("blackhole", {})
        )
        jid = await ctrl.submit_job(
            prog, checkpoint_url=f"file://{tmp_path}/ckpt",
            ttl_secs=6.0)
        await ctrl.wait_for_state(jid, JobState.RUNNING, timeout=60)
        ctrl.jobs[jid].supervisor.cancel()
        await sched.stop_workers(jid, force=True)
        await ctrl.rpc.stop()
        ctrl.store.close()
        return jid

    async def two(jid):
        ctrl = ControllerServer(InProcessScheduler(), db_path=db_path)
        await ctrl.start()
        try:
            assert jid in ctrl.jobs, "live ttl job not resumed"
            assert ctrl.jobs[jid].ttl_deadline is not None
            state = await ctrl.wait_for_state(
                jid, JobState.STOPPED, timeout=60)
            assert state == JobState.STOPPED, state
        finally:
            await ctrl.stop()

    jid = asyncio.run(one())
    asyncio.run(two(jid))


def test_rescaled_parallelism_survives_controller_restart(tmp_path):
    """rescale_job persists the updated program; a controller crash
    right after the rescale must resume the job at the NEW parallelism,
    not the submitted one."""
    from arroyo_tpu.controller.scheduler import InProcessScheduler

    db_path = str(tmp_path / "c.db")

    async def one():
        sched = InProcessScheduler()
        ctrl = ControllerServer(sched, db_path=db_path)
        await ctrl.start()
        prog = (
            Stream.source("impulse", {"event_rate": 4000.0,
                                      "message_count": 10_000_000,
                                      "event_time_interval_micros": 1000,
                                      "batch_size": 256})
            .watermark(max_lateness_micros=0)
            .map(lambda c: {"counter": c["counter"],
                            "bucket": c["counter"] % 5}, name="b")
            .key_by("bucket")
            .tumbling_aggregate(
                500 * 1000, [AggSpec(AggKind.COUNT, None, "cnt")],
                parallelism=1)
            .sink("blackhole", {})
        )
        jid = await ctrl.submit_job(
            prog, checkpoint_url=f"file://{tmp_path}/ckpt")
        await ctrl.wait_for_state(jid, JobState.RUNNING, timeout=60)
        for _ in range(400):  # need a checkpoint for the rescale stop
            if (ctrl.jobs[jid].last_successful_epoch or 0) >= 1:
                break
            await asyncio.sleep(0.05)
        agg_ops = [n.operator_id
                   for n in ctrl.jobs[jid].program.nodes()
                   if "aggregator" in n.operator_id]
        await ctrl.rescale_job(jid, {op: 2 for op in agg_ops})
        await ctrl.wait_for_state(jid, JobState.RUNNING, timeout=60)
        # crash
        ctrl.jobs[jid].supervisor.cancel()
        await sched.stop_workers(jid, force=True)
        await ctrl.rpc.stop()
        ctrl.store.close()
        return jid, agg_ops

    async def two(jid, agg_ops):
        ctrl = ControllerServer(InProcessScheduler(), db_path=db_path)
        await ctrl.start()
        try:
            assert jid in ctrl.jobs
            await ctrl.wait_for_state(jid, JobState.RUNNING, timeout=60)
            prog = ctrl.jobs[jid].program
            for op in agg_ops:
                assert prog.node(op).parallelism == 2, op
            await ctrl.stop_job(jid, checkpoint=False)
            await ctrl.wait_for_state(jid, JobState.STOPPED, timeout=60)
        finally:
            await ctrl.stop()

    import os
    os.environ["CHECKPOINT_INTERVAL_SECS"] = "0.5"
    from arroyo_tpu.config import reset_config

    reset_config()
    try:
        jid, agg_ops = asyncio.run(one())
        asyncio.run(two(jid, agg_ops))
    finally:
        os.environ.pop("CHECKPOINT_INTERVAL_SECS", None)
        reset_config()


def test_spawn_inherits_jax_platforms_unchanged(monkeypatch):
    """A worker process gets the parent's environment as it is:
    JAX_PLATFORMS passes through unchanged, and none is added when the
    parent has none — a ProcessScheduler job on a chip host must not
    silently run on the CPU."""
    import subprocess

    from arroyo_tpu.worker import spawn

    seen = []

    class FakePopen:
        def __init__(self, argv, env):
            seen.append(env)

    monkeypatch.setattr(subprocess, "Popen", FakePopen)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    spawn.spawn_worker_process("j", "http://c", 4)
    monkeypatch.delenv("JAX_PLATFORMS")
    spawn.spawn_worker_process("j", "http://c", 4)
    assert seen[0]["JAX_PLATFORMS"] == "tpu,cpu"
    assert "JAX_PLATFORMS" not in seen[1]
    assert seen[1]["JOB_ID"] == "j" and seen[1]["TASK_SLOTS"] == "4"


def test_worker_that_dies_before_registering_fails_the_job(monkeypatch):
    """More worker processes than chips: the extra worker cannot claim a
    device and exits non-zero before registering.  The job must FAIL at
    scheduling, promptly and with the exit code — not hang out the
    registration deadline, and not run anywhere else."""
    import subprocess
    import sys
    import time

    from arroyo_tpu.worker import spawn

    monkeypatch.setattr(
        spawn, "spawn_worker_process",
        lambda *a, **k: subprocess.Popen(
            [sys.executable, "-c", "import sys; sys.exit(3)"]))

    async def scenario():
        sched = ProcessScheduler()
        ctrl = ControllerServer(sched)
        await ctrl.start()
        prog = (Stream.source("impulse", {"event_rate": 0.0,
                                          "message_count": 10,
                                          "batch_size": 5})
                .sink("blackhole", {}))
        t0 = time.monotonic()
        job_id = await ctrl.submit_job(prog, n_workers=1)
        try:
            state = await ctrl.wait_for_state(job_id, JobState.FAILED,
                                              timeout=30)
            return (state, ctrl.jobs[job_id].fsm.failure_message,
                    time.monotonic() - t0)
        finally:
            await sched.stop_workers(job_id)
            await ctrl.stop()

    state, message, took = asyncio.run(scenario())
    assert state == JobState.FAILED
    assert "died before registering" in message and "rc=3" in message
    assert took < 30
