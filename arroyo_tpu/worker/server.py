"""WorkerServer: the worker process main — registers with the controller,
hosts the engine for its assigned subtasks, relays control responses, and
heartbeats (analog of /root/reference/arroyo-worker/src/lib.rs:252-670).

Serves WorkerGrpc {StartExecution, Checkpoint, Commit, StopExecution,
JobFinished, LoadCompactedData} (lib.rs:489-670) over the msgpack transport
and opens the TCP data plane for cross-worker edges."""

from __future__ import annotations

import asyncio
import logging
import os
import cloudpickle as pickle
import uuid
from typing import Any, Dict, Optional, Tuple

from ..config import config
from ..engine.engine import Engine, RunningEngine
from ..network.data_plane import NetworkManager
from ..rpc.transport import RpcClient, RpcServer
from ..state.backend import ParquetBackend
from ..types import CheckpointBarrier, ControlMessage, ControlResp, StopMode, now_micros

logger = logging.getLogger(__name__)


class WorkerServer:
    def __init__(self, controller_addr: str, job_id: str,
                 slots: Optional[int] = None,
                 worker_id: Optional[str] = None,
                 host: str = "127.0.0.1"):
        self.controller_addr = controller_addr
        self.job_id = job_id
        self.slots = slots or config().task_slots
        self.worker_id = worker_id or f"worker-{uuid.uuid4().hex[:8]}"
        self.host = host
        self.network = NetworkManager(job_id=job_id or "")
        self.rpc = RpcServer()
        self.controller = RpcClient(controller_addr, "ControllerGrpc")
        self.engine: Optional[Engine] = None
        self.running: Optional[RunningEngine] = None
        self._relay_task: Optional[asyncio.Task] = None
        self._hb_task: Optional[asyncio.Task] = None
        self._hb_stop = None  # threading.Event, set by _heartbeat_loop
        self._done = asyncio.Event()

    # -- lifecycle ---------------------------------------------------------

    async def start(self) -> None:
        data_port = await self.network.open_listener(self.host)
        self.rpc.add_service("WorkerGrpc", {
            "StartExecution": self._start_execution,
            "Checkpoint": self._checkpoint,
            "Commit": self._commit,
            "StopExecution": self._stop_execution,
            "JobFinished": self._job_finished,
            "LoadCompactedData": self._load_compacted,
        })
        rpc_port = await self.rpc.start(self.host)
        await self.controller.wait_ready()
        await self.controller.call("RegisterWorker", {
            "worker_id": self.worker_id,
            "job_id": self.job_id,
            "rpc_address": f"{self.host}:{rpc_port}",
            "data_address": f"{self.host}:{data_port}",
            "slots": self.slots,
            "run_id": "0",
        })
        self._hb_task = asyncio.ensure_future(self._heartbeat_loop())
        logger.info("worker %s registered (rpc=%s data=%s)",
                    self.worker_id, rpc_port, data_port)

    async def wait_done(self) -> None:
        await self._done.wait()

    async def shutdown(self) -> None:
        if self._hb_stop is not None:
            # stop the heartbeat thread directly: cancelling the parked
            # task is not enough on every shutdown path, and a surviving
            # daemon thread keeps dialing the dead controller
            self._hb_stop.set()
        for t in (self._hb_task, self._relay_task):
            if t is not None:
                t.cancel()
        await self.network.close()
        await self.rpc.stop()
        await self.controller.close()
        self._done.set()

    async def _heartbeat_loop(self) -> None:
        """Heartbeats run on a dedicated thread with their own event loop
        and channel: a worker stalled in a long synchronous jit compile is
        busy, not dead, and must not trip the controller's 30s timeout
        (the reference's heartbeat likewise lives on the control thread,
        arroyo-worker/src/lib.rs:467-476)."""
        import threading

        interval = config().heartbeat_interval_secs
        controller_addr = self.controller_addr
        worker_id, job_id = self.worker_id, self.job_id
        stop = threading.Event()
        self._hb_stop = stop

        def run() -> None:
            async def beat() -> None:
                client = RpcClient(controller_addr, "ControllerGrpc")
                rollup_warned = False
                while not stop.is_set():
                    # chunked sleep: exit promptly on shutdown
                    slept = 0.0
                    while slept < interval and not stop.is_set():
                        await asyncio.sleep(0.2)
                        slept += 0.2
                    if stop.is_set():
                        break
                    try:
                        # piggyback a compact per-operator metric rollup on
                        # the heartbeat: the controller aggregates these
                        # into job-level rates/lag/backpressure without
                        # ever scraping workers over HTTP (registry
                        # collection is thread-safe, so reading it from
                        # the heartbeat thread is fine).  msgpack-packed:
                        # the proto field is opaque bytes so the nested
                        # {op: {metric: value}} map needs no proto schema
                        try:
                            from ..obs.metrics import job_operator_summary
                            from ..rpc.transport import _ser_msgpack

                            summary = _ser_msgpack(
                                job_operator_summary(job_id))
                        except Exception:
                            # heartbeats must keep flowing without the
                            # rollup, but say so once: a persistent pack
                            # failure otherwise silently blanks every
                            # job-level rollup the console serves
                            if not rollup_warned:
                                rollup_warned = True
                                logger.warning(
                                    "heartbeat metrics rollup failed; "
                                    "heartbeats continue without metrics",
                                    exc_info=True)
                            summary = None
                        await client.call("Heartbeat", {
                            "worker_id": worker_id, "job_id": job_id,
                            "time": now_micros(), "metrics": summary})
                    except Exception as e:
                        if not stop.is_set():
                            logger.warning("heartbeat failed: %s", e)
                await client.close()

            asyncio.run(beat())

        threading.Thread(target=run, name="heartbeat", daemon=True).start()
        # keep the asyncio task interface: park until cancelled, then stop
        # the thread
        try:
            await asyncio.Event().wait()
        finally:
            stop.set()

    # -- WorkerGrpc handlers ----------------------------------------------

    async def _start_execution(self, req: Dict) -> Dict:
        # return immediately: deserializing the program and building the
        # engine can take seconds (first jax init in a fresh process), and
        # the controller's RPC deadline must not ride on it — failures are
        # reported through WorkerError (the reference's StartExecution also
        # returns before tasks run, arroyo-worker/src/lib.rs:489-545)
        asyncio.ensure_future(self._start_execution_async(req))
        return {}

    async def _start_execution_async(self, req: Dict) -> None:
        try:
            program = pickle.loads(req["program"])
            assignments = {
                (t["operator_id"], t["subtask_index"]): t["worker_id"]
                for t in req["tasks"]}
            addrs = dict(req.get("worker_data_addrs") or {})
            for wid, addr in addrs.items():
                if wid != self.worker_id:
                    await self.network.connect(addr)
            backend = ParquetBackend.for_url(
                req.get("checkpoint_url") or config().checkpoint_url)
            self.engine = Engine(
                program, self.job_id, backend=backend,
                restore_epoch=req.get("restore_epoch"),
                assignments=assignments, my_worker_id=self.worker_id,
                worker_data_addrs=addrs, network=self.network)
            self.running = self.engine.start()
            self._relay_task = asyncio.ensure_future(self._relay_loop())
        except Exception as e:
            logger.error("StartExecution failed: %s", e, exc_info=True)
            try:
                await self.controller.call("WorkerError", {
                    "worker_id": self.worker_id, "job_id": self.job_id,
                    "error": f"StartExecution failed: {e}"})
            except Exception:
                pass

    async def _relay_loop(self) -> None:
        """Forward engine ControlResps to the controller (the reference's
        control thread, arroyo-worker/src/lib.rs:369-487)."""
        n_tasks = len(self.engine.subtasks)
        finished = 0
        while finished < n_tasks:
            resp: ControlResp = await self.engine.control_resp.get()
            try:
                await self._relay_one(resp)
            except Exception as e:
                logger.warning("relay to controller failed: %s", e)
            if resp.kind in ("task_finished", "task_failed"):
                finished += 1
        try:
            await self.controller.call("WorkerFinished", {
                "worker_id": self.worker_id, "job_id": self.job_id})
        except Exception as e:
            logger.warning("WorkerFinished failed: %s", e)

    async def _relay_one(self, resp: ControlResp) -> None:
        base = {"job_id": self.job_id, "operator_id": resp.operator_id,
                "subtask": resp.task_index}
        if resp.kind == "task_started":
            await self.controller.call("TaskStarted",
                                       base | {"worker_id": self.worker_id})
        elif resp.kind == "checkpoint_event":
            ev = resp.checkpoint_event
            await self.controller.call("TaskCheckpointEvent", base | {
                "epoch": ev.checkpoint_epoch,
                "event_type": ev.event_type.value, "time": ev.time})
        elif resp.kind == "checkpoint_completed":
            m = resp.subtask_metadata
            await self.controller.call("TaskCheckpointCompleted", base | {
                "epoch": m.epoch, "bytes": m.bytes,
                "watermark": m.watermark, "start_time": m.start_time,
                "finish_time": m.finish_time,
                "has_committing_data": bool(m.committing_data)})
        elif resp.kind == "task_finished":
            await self.controller.call("TaskFinished", base)
        elif resp.kind == "task_failed":
            await self.controller.call("TaskFailed",
                                       base | {"error": resp.error or ""})

    async def _await_started(self, timeout: float = 120.0) -> None:
        """StartExecution returns before the engine is built; control RPCs
        that need the running engine park here until it exists."""
        deadline = asyncio.get_event_loop().time() + timeout
        while self.running is None:
            if asyncio.get_event_loop().time() > deadline:
                raise RuntimeError("engine not started")
            await asyncio.sleep(0.05)

    async def _checkpoint(self, req: Dict) -> Dict:
        await self._await_started()
        barrier = CheckpointBarrier(req["epoch"], req.get("min_epoch", 0),
                                    req.get("timestamp", now_micros()),
                                    req.get("then_stop", False))
        # barriers are injected at sources only (§3.3)
        for q in self.running.source_controls():
            await q.put(ControlMessage.checkpoint(barrier))
        return {}

    async def _commit(self, req: Dict) -> Dict:
        await self._await_started()
        await self.running.commit(req["epoch"])
        return {}

    async def _stop_execution(self, req: Dict) -> Dict:
        if self.running is not None:
            mode = StopMode(req.get("stop_mode", "graceful"))
            await self.running.stop(mode)
        return {}

    async def _job_finished(self, req: Dict) -> Dict:
        asyncio.ensure_future(self.shutdown())
        return {}

    async def _load_compacted(self, req: Dict) -> Dict:
        # Hot-swap compacted checkpoint files (LoadCompactedData,
        # arroyo-worker/src/lib.rs:602-631): forward to the operator's tasks.
        if self.running is not None:
            await self.running.load_compacted(
                req.get("operator_id", ""),
                # operator_id rides in the payload so a chained task can
                # route the hot-swap to the right member
                {"operator_id": req.get("operator_id", ""),
                 "epoch": req.get("epoch"), "files": req.get("files", []),
                 "dropped": req.get("dropped", [])})
        return {}


async def run_worker(controller_addr: str, job_id: str,
                     slots: Optional[int] = None,
                     worker_id: Optional[str] = None) -> None:
    w = WorkerServer(controller_addr, job_id, slots, worker_id=worker_id)
    await w.start()
    await w.wait_done()


def main() -> None:
    logging.basicConfig(level=logging.INFO)
    # claim the device BEFORE registering: a worker process that cannot
    # get a chip (one process per chip) exits non-zero here, which the
    # controller turns into a failed job — it neither hangs mid-job nor
    # runs the job on the CPU (config.require_backend)
    from ..config import require_backend

    logger.info("worker backend: %s", require_backend())
    asyncio.run(run_worker(
        os.environ["CONTROLLER_ADDR"], os.environ["JOB_ID"],
        int(os.environ.get("TASK_SLOTS", "16")),
        # the node daemon assigns the id so its WorkerFinished reports
        # match what the controller registered
        os.environ.get("WORKER_ID")))


if __name__ == "__main__":
    main()
