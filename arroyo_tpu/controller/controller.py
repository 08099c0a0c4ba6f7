"""ControllerServer: the control plane — job submission, scheduling,
supervision, checkpoint coordination, recovery.

Folds together the reference's controller pieces:
* gRPC service + job registry (arroyo-controller/src/lib.rs)
* Scheduling state: slots = max operator parallelism, round-robin slot
  packing, wait-for-registration (states/scheduling.rs:44-290)
* JobController supervision: 30s heartbeat timeout, periodic checkpoints,
  epoch bookkeeping, two-phase commit, cleanup (job_controller/mod.rs)
* CheckpointState aggregation of per-subtask events into a job-level
  checkpoint record (checkpointer.rs:67-410)
"""

from __future__ import annotations

import asyncio
import json
import logging
import cloudpickle as pickle
import time
import uuid
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Set, Tuple

from ..config import config
from ..graph.logical import Program
from ..rpc.transport import RpcClient, RpcServer
from ..state.backend import ParquetBackend
from ..types import now_micros
from .scheduler import InProcessScheduler, Scheduler
from .state_machine import JobState, StateMachine

logger = logging.getLogger(__name__)


@dataclass
class WorkerInfo:
    worker_id: str
    rpc_address: str
    data_address: str
    slots: int
    last_heartbeat: float = field(default_factory=time.monotonic)
    client: Optional[RpcClient] = None
    finished: bool = False
    # flight-recorder rollup scraped from the heartbeat payload:
    # {operator_id: {metric_key: value}}, plus the previous sample so
    # job-level rate math has a delta to work with
    metric_snapshot: Optional[Dict[str, Dict[str, float]]] = None
    snapshot_time: float = 0.0
    prev_snapshot: Optional[Dict[str, Dict[str, float]]] = None
    prev_time: float = 0.0


@dataclass
class CheckpointTracker:
    """Aggregates per-subtask checkpoint completions for one epoch
    (CheckpointState, checkpointer.rs:186-410)."""

    epoch: int
    n_subtasks: int
    completed: Set[Tuple[str, int]] = field(default_factory=set)
    has_committing: bool = False
    started: float = field(default_factory=time.monotonic)

    @property
    def done(self) -> bool:
        return len(self.completed) >= self.n_subtasks


class Job:
    def __init__(self, job_id: str, program: Program,
                 checkpoint_url: str, parallelism: int):
        self.job_id = job_id
        self.program = program
        self.checkpoint_url = checkpoint_url
        self.parallelism = parallelism
        self.fsm = StateMachine(job_id)
        self.workers: Dict[str, WorkerInfo] = {}
        self.epoch = 0
        self.min_epoch = 0
        self.trackers: Dict[int, CheckpointTracker] = {}
        self.last_successful_epoch: Optional[int] = None
        self.n_subtasks = sum(n.parallelism for n in program.nodes())
        self.finished_tasks: Set[Tuple[str, int]] = set()
        self.failure: Optional[str] = None
        self.supervisor: Optional[asyncio.Task] = None
        self.stop_requested = False
        # absolute wall deadline (time.time()) after which the
        # supervisor stops the job — preview pipelines (reference
        # pipelines.rs ttl_micros); persisted so a restarted controller
        # still reaps resumed previews
        self.ttl_deadline: Optional[float] = None
        # latency SLO (obs/latency.py): seeded from config env, REST PUT
        # can replace it live; the evaluator keeps the burn-rate ring
        from ..obs.latency import Slo, SloEvaluator

        self.slo = Slo.from_config()
        self.slo_eval = SloEvaluator(job_id, self.slo)

    def set_slo(self, slo) -> None:
        """Replace the job's SLO live (REST PUT): the evaluator keeps
        its sample/event history — only the targets change."""
        self.slo = slo
        self.slo_eval.slo = slo

    @property
    def slots_needed(self) -> int:
        return max(n.parallelism for n in self.program.nodes())


class ControllerServer:
    # class-level so test doubles built via __new__ still have it
    _metrics_decode_warned = False

    def __init__(self, scheduler: Optional[Scheduler] = None,
                 host: str = "127.0.0.1",
                 db_path: Optional[str] = None):
        import os

        if scheduler is None:
            if os.environ.get("SCHEDULER"):
                from .scheduler import scheduler_from_env

                scheduler = scheduler_from_env()
            else:
                scheduler = InProcessScheduler()
        self.scheduler = scheduler
        self.host = host
        self.rpc = RpcServer()
        # arroyosan: the controller-side half of checkpoint-completeness
        # (workers check their own runners; only the controller sees the
        # whole job).  None unless ARROYO_SANITIZE is armed.
        from ..analysis.sanitizer import maybe_sanitizer

        self.sanitizer = maybe_sanitizer("controller")
        self.jobs: Dict[str, Job] = {}
        # per-job autoscalers (arroyo_tpu/autoscale): one per accepted
        # job so the decision ledger + REST surface always exist; the
        # loop itself only runs while the job's autoscaler is enabled
        self.autoscalers: Dict[str, Any] = {}
        self.addr: Optional[str] = None
        self.sink_subscribers: Dict[str, List[asyncio.Queue]] = {}
        # durable job state (states/mod.rs:577-628 analog): every
        # non-terminal job in the sqlite store is resumed on start()
        db_path = db_path or os.environ.get("CONTROLLER_DB")
        if db_path:
            from .store import ControllerStore

            self.store: Optional[ControllerStore] = ControllerStore(db_path)
        else:
            self.store = None

    # -- lifecycle ---------------------------------------------------------

    async def start(self, port: int = 0) -> str:
        import os

        self.rpc.add_service("ControllerGrpc", {
            "RegisterWorker": self._register_worker,
            "Heartbeat": self._heartbeat,
            "TaskStarted": self._task_started,
            "TaskCheckpointEvent": self._task_ckpt_event,
            "TaskCheckpointCompleted": self._task_ckpt_completed,
            "TaskFinished": self._task_finished,
            "TaskFailed": self._task_failed,
            "WorkerFinished": self._worker_finished,
            "WorkerError": self._worker_error,
            "SendSinkData": self._send_sink_data,
        }, stream_methods={"SubscribeToOutput": self._subscribe_output})
        p = await self.rpc.start(self.host, port)
        # the address workers DIAL: a 0.0.0.0 bind is not dialable, so
        # deployments advertise the service address instead
        self.addr = os.environ.get(
            "CONTROLLER_ADVERTISE_ADDR",
            f"{'127.0.0.1' if self.host == '0.0.0.0' else self.host}:{p}")
        if self.store is not None:
            await self._resume_persisted()
        return self.addr

    async def _resume_persisted(self) -> None:
        """Adopt every non-terminal job from the durable store: reap
        orphaned workers from the previous controller incarnation, then
        re-drive each job's FSM with restore=True so it resumes from its
        last completed checkpoint (states/mod.rs:577-628)."""
        for row in self.store.resumable():
            if row.stop_requested:
                # a stop was in flight when the controller died; without
                # live workers there is nothing left to checkpoint-stop —
                # the job's last completed checkpoint already exists
                self.store.set_state(row.job_id, JobState.STOPPED.value)
                continue
            try:
                program = pickle.loads(row.program)
            except Exception as e:
                logger.error("job %s: stored program unreadable: %s",
                             row.job_id, e)
                self.store.set_state(row.job_id, JobState.FAILED.value,
                                     f"stored program unreadable: {e}")
                continue
            await self.scheduler.reap(row.job_id,
                                      self.store.workers(row.job_id))
            self.store.set_workers(row.job_id, [])
            if (row.ttl_deadline is not None
                    and time.time() > row.ttl_deadline):
                # an expired preview from the previous incarnation: its
                # workers are already reaped below via the worker table —
                # settle it instead of resuming
                await self.scheduler.reap(row.job_id,
                                          self.store.workers(row.job_id))
                self.store.set_workers(row.job_id, [])
                self.store.set_state(row.job_id, JobState.STOPPED.value)
                continue
            job = Job(row.job_id, program, row.checkpoint_url,
                      max(n.parallelism for n in program.nodes()))
            job.epoch = row.epoch
            job.min_epoch = row.min_epoch
            job.ttl_deadline = row.ttl_deadline
            self._attach_store(job, row.n_workers)
            self.jobs[row.job_id] = job
            self._attach_autoscaler(row.job_id)
            self._restore_autoscaler(row.job_id, row.autoscale)
            logger.info("resuming job %s from durable store (stored "
                        "state %s, epoch %d)", row.job_id, row.state,
                        row.epoch)
            job.supervisor = asyncio.ensure_future(
                self._drive(job, row.n_workers, restore=True))

    def _attach_store(self, job: Job, n_workers: int) -> None:
        """Persist FSM transitions + progress for this job."""
        if self.store is None:
            return
        store = self.store

        def on_transition(prev: JobState, to: JobState) -> None:
            store.set_state(job.job_id, to.value, job.fsm.failure_message)

        job.fsm.on_transition = on_transition

    async def stop(self) -> None:
        for job in self.jobs.values():
            if job.supervisor:
                job.supervisor.cancel()
            await self._close_worker_clients(job)
        for scaler in self.autoscalers.values():
            scaler.stop()
        await self.rpc.stop()
        if self.store is not None:
            self.store.close()

    @staticmethod
    async def _close_worker_clients(job: "Job") -> None:
        """Close per-worker grpc channels before dropping WorkerInfo refs.
        An unclosed aio channel's completion-queue dealloc joins its poller
        thread from whatever thread GC happens to run on — after the owning
        event loop is gone that join can block forever, so the channel must
        be closed while the loop is still alive."""
        for w in list(job.workers.values()):
            if w.client is not None:
                try:
                    await w.client.close()
                except Exception:
                    pass
                w.client = None

    def _attach_autoscaler(self, job_id: str) -> None:
        """One JobAutoscaler per accepted job (ledger + REST surface);
        the evaluation loop starts only when enabled — by default via
        ARROYO_AUTOSCALE_DEFAULT, or later through the REST PUT.
        ARROYO_AUTOSCALE=0 keeps the subsystem entirely out."""
        cfg = config()
        if not cfg.autoscale_enabled:
            return
        from ..autoscale.supervisor import JobAutoscaler

        prev = self.autoscalers.get(job_id)
        if prev is not None:
            # a resubmitted job_id must not leak the old loop: two live
            # loops would race rescale_job against each other
            prev.stop()
        scaler = JobAutoscaler(self, job_id)
        self.autoscalers[job_id] = scaler
        if cfg.autoscale_default_on:
            scaler.set_enabled(True)
        # keep the store in sync: a resubmitted job_id must not inherit
        # the previous incarnation's persisted spec on the next restart
        # (the resume path overwrites this again from the stored row)
        self.persist_autoscaler(job_id)

    def persist_autoscaler(self, job_id: str) -> None:
        """Persist the per-job autoscaler spec (enabled + policy) so a
        restarted controller resumes it with the job (the REST PUT calls
        this after every change)."""
        if self.store is None:
            return
        scaler = self.autoscalers.get(job_id)
        if scaler is not None:
            self.store.set_autoscale(job_id, json.dumps({
                "enabled": scaler.enabled,
                "policy": scaler.policy.cfg.to_json()}))

    def _restore_autoscaler(self, job_id: str,
                            spec_json: Optional[str]) -> None:
        """Re-arm a resumed job's autoscaler from its stored spec."""
        scaler = self.autoscalers.get(job_id)
        if not spec_json or scaler is None:
            return
        try:
            spec = json.loads(spec_json)
        except Exception:
            # a corrupt spec must not block the job resume itself
            logger.warning("job %s: stored autoscaler spec unreadable",
                           job_id, exc_info=True)
            return
        if spec.get("policy"):
            try:
                from ..autoscale.policy import (BacklogDrainPolicy,
                                                PolicyConfig)

                cfg = PolicyConfig(**spec["policy"])
                # same range gate as the REST merge path: a stored
                # interval_secs=0 would busy-spin the controller loop
                cfg._check_ranges()
                scaler.policy = BacklogDrainPolicy(cfg)
            except Exception:
                logger.warning("job %s: stored autoscaler policy "
                               "invalid; keeping defaults", job_id,
                               exc_info=True)
        # unconditional, and applied even when the policy was unusable:
        # a persisted enabled:false must override an
        # ARROYO_AUTOSCALE_DEFAULT=1 enable from the attach — the
        # operator explicitly turned this job's autoscaler off
        scaler.set_enabled(bool(spec.get("enabled")))
        self.persist_autoscaler(job_id)

    # -- job API (what arroyo-api calls via gRPC/DB) ----------------------

    async def submit_job(self, program: Program, job_id: Optional[str] = None,
                         checkpoint_url: Optional[str] = None,
                         n_workers: int = 1,
                         restore: bool = False,
                         ttl_secs: Optional[float] = None) -> str:
        job_id = job_id or f"job-{uuid.uuid4().hex[:8]}"
        # factor-window rewrite BEFORE slot assignment: the controller's
        # assignments are keyed by operator id, so the factor nodes must
        # exist here, not only in each worker's engine-side (idempotent)
        # re-application
        from ..graph.factor_windows import apply_factor_windows

        apply_factor_windows(program)
        job = Job(job_id, program,
                  checkpoint_url or config().checkpoint_url,
                  max(n.parallelism for n in program.nodes()))
        if ttl_secs is not None:
            job.ttl_deadline = time.time() + float(ttl_secs)
        self.jobs[job_id] = job
        if self.store is not None:
            self.store.upsert_job(job_id, pickle.dumps(program),
                                  job.checkpoint_url, n_workers,
                                  JobState.CREATED.value,
                                  ttl_deadline=job.ttl_deadline)
            self._attach_store(job, n_workers)
        self._attach_autoscaler(job_id)
        job.supervisor = asyncio.ensure_future(
            self._drive(job, n_workers, restore))
        return job_id

    async def stop_job(self, job_id: str, checkpoint: bool = True) -> None:
        job = self.jobs[job_id]
        job.stop_requested = True
        if self.store is not None:
            self.store.set_stop_requested(job_id)
        if job.fsm.state == JobState.RUNNING:
            if checkpoint:
                job.fsm.transition(JobState.CHECKPOINT_STOPPING)
                await self._trigger_checkpoint(job, then_stop=True)
            else:
                job.fsm.transition(JobState.STOPPING)
                await self._broadcast_workers(job, "StopExecution",
                                              {"job_id": job_id,
                                               "stop_mode": "graceful"})

    async def rescale_job(self, job_id: str,
                          overrides: Dict[str, int]) -> None:
        """Rescaling path (states/rescaling.rs): checkpoint-stop, update
        parallelism, reschedule with state re-sharded by key range.

        A chain is the unit of parallelism: overrides addressed to any
        chained operator are expanded to the whole chain (otherwise the
        rescale would split the chain and lose the fusion).  So is a
        factor-window group: the factor -> derived FORWARD edges carry
        keyed co-partitioning, which a parallelism split would break."""
        from ..graph.chaining import expand_overrides
        from ..graph.factor_windows import (
            expand_overrides as expand_factor_overrides,
        )

        job = self.jobs[job_id]
        # fixpoint: factor expansion can add members whose CHAINS then
        # need the override too (a derived window chaining with its
        # post-projection), and vice versa — iterate until stable
        # (override sets only grow, bounded by the node count)
        prev: Dict[str, int] = {}
        while overrides != prev:
            prev = overrides
            overrides = expand_overrides(job.program, overrides)
            overrides = expand_factor_overrides(job.program, overrides)
        # worker count from the controller's own registry, BEFORE the
        # stop: schedulers' live listings are empty once workers exit
        n_workers = max(len(job.workers), 1)
        job.fsm.transition(JobState.RESCALING)
        await self._trigger_checkpoint(job, then_stop=True)
        stop_ok = await self._await_workers_finished(job, timeout=30)
        # the stop must ALSO have produced a completed checkpoint at the
        # stop epoch: a broadcast-failure fallback (plain graceful stop)
        # or a finished-before-finalize race would otherwise restore an
        # OLDER epoch under the new topology -> duplicate output
        stop_ok = stop_ok and job.last_successful_epoch == job.epoch
        if not stop_ok:
            # the stop-checkpoint did not complete: DON'T restore from an
            # older epoch with the new topology (rewound sources would
            # duplicate output past the restore point) — abort the rescale
            # and recover the job at its CURRENT parallelism
            logger.warning("rescale of %s aborted: stop-checkpoint "
                           "incomplete", job_id)
            if job.fsm.try_recover("rescale stop-checkpoint incomplete"):
                await self._restart_workers(job, n_workers, force_stop=True)
            raise TimeoutError(
                f"rescale of {job_id} aborted (stop-checkpoint incomplete); "
                "job recovered at its previous parallelism")
        # fresh workers sized for the NEW parallelism (the old ones were
        # checkpoint-stopped above); restore re-shards state by key range
        job.program.update_parallelism(overrides)
        job.n_subtasks = sum(n.parallelism for n in job.program.nodes())
        if self.store is not None:
            self.store.set_program(job.job_id, pickle.dumps(job.program),
                                   n_workers)
        await self._restart_workers(job, n_workers, force_stop=False)
        # the rescale's restore point is now the only epoch the new
        # topology can resume from — prune retention behind it so the
        # stop-checkpoint of every rescale doesn't grow storage unbounded
        await self._prune_checkpoints(job)

    def job_state(self, job_id: str) -> JobState:
        return self.jobs[job_id].fsm.state

    async def wait_for_state(self, job_id: str, *states: JobState,
                             timeout: float = 60.0) -> JobState:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            s = self.jobs[job_id].fsm.state
            if s in states or s.terminal:
                return s
            await asyncio.sleep(0.05)
        raise TimeoutError(
            f"job {job_id} did not reach {states} (now "
            f"{self.jobs[job_id].fsm.state})")

    # -- driving the FSM ---------------------------------------------------

    async def _drive(self, job: Job, n_workers: int, restore: bool) -> None:
        try:
            job.fsm.transition(JobState.COMPILING)
            # AOT build pass (engine/aot.py): construct every physical
            # operator so a bad pipeline fails HERE, not on a worker
            # (states/compiling.rs contract); runs off-loop — expression
            # compilation can trace
            from ..engine.aot import compile_program

            report = await asyncio.get_event_loop().run_in_executor(
                None, compile_program, job.program)
            if not report.ok:
                job.fsm.fail("; ".join(report.errors))
                return
            job.fsm.transition(JobState.SCHEDULING)
            await self.scheduler.start_workers(
                job.job_id, self.addr, n_workers,
                max(1, (job.slots_needed + n_workers - 1) // n_workers))
            self._persist_workers(job)
            await self._schedule(job, n_workers, restore)
            job.fsm.transition(JobState.RUNNING)
            await self._supervise(job)
        except asyncio.CancelledError:
            raise
        except Exception as e:
            logger.exception("job %s driver failed", job.job_id)
            if not job.fsm.state.terminal:
                job.fsm.fail(str(e))

    async def _schedule(self, job: Job, n_workers: int, restore: bool) -> None:
        # wait for registrations to satisfy the slot requirement
        # (scheduling.rs:255-290; reference timeout 10min, ours shorter)
        deadline = time.monotonic() + 60
        while sum(w.slots for w in job.workers.values()) < job.slots_needed:
            dead = self.scheduler.dead_workers(job.job_id)
            if dead:
                # e.g. more worker processes than chips: the extra ones
                # cannot claim a device and exit before registering
                raise RuntimeError(
                    f"worker(s) died before registering for {job.job_id}: "
                    + ", ".join(dead))
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"workers did not register enough slots for {job.job_id}")
            await asyncio.sleep(0.05)

        restore_epoch = None
        if restore:
            restore_epoch = self._find_restore_epoch(job)

        assignments = self._compute_assignments(job)
        tasks_payload = [
            {"operator_id": op, "subtask_index": idx, "worker_id": w}
            for (op, idx), w in assignments.items()]
        addrs = {w.worker_id: w.data_address for w in job.workers.values()}
        program_bytes = pickle.dumps(job.program)
        for w in job.workers.values():
            await w.client.call("StartExecution", {
                "job_id": job.job_id,
                "program": program_bytes,
                "tasks": tasks_payload,
                "restore_epoch": restore_epoch,
                "worker_data_addrs": addrs,
                "checkpoint_url": job.checkpoint_url,
            }, timeout=30)
        if restore_epoch is not None:
            job.epoch = restore_epoch
            job.last_successful_epoch = restore_epoch

    def _compute_assignments(self, job: Job) -> Dict[Tuple[str, int], str]:
        """Round-robin slot packing (scheduling.rs:52-75)."""
        slots: List[str] = []
        for w in sorted(job.workers.values(), key=lambda w: w.worker_id):
            slots.extend([w.worker_id] * w.slots)
        out: Dict[Tuple[str, int], str] = {}
        for node in job.program.nodes():
            for idx in range(node.parallelism):
                out[(node.operator_id, idx)] = slots[idx % len(slots)]
        return out

    def _find_restore_epoch(self, job: Job) -> Optional[int]:
        """Last checkpoint whose job-level metadata marks it complete."""
        backend = ParquetBackend.for_url(job.checkpoint_url)
        best = None
        for f in backend.storage.list(f"{job.job_id}/checkpoints"):
            if f.endswith("/metadata.json") and "checkpoint-" in f:
                part = f.split("checkpoint-")[1].split("/")[0]
                try:
                    meta = json.loads(backend.storage.get(f))
                    if meta.get("complete"):
                        ep = int(part)
                        best = ep if best is None or ep > best else best
                except Exception:
                    continue
        return best

    async def _supervise(self, job: Job) -> None:
        """JobController::progress (job_controller/mod.rs:460-584)."""
        cfg = config()
        last_ckpt = time.monotonic()
        last_slo = 0.0
        while True:
            await asyncio.sleep(0.1)
            state = job.fsm.state
            if state.terminal:
                return
            # task failure -> recovery; checked BEFORE the all-finished
            # check so a failed task draining downstream (end_of_data on
            # failure) can't race the job into FINISHED with partial output
            if state == JobState.RUNNING and job.failure is not None:
                err = job.failure
                job.failure = None
                await self._recover(job, err)
                continue
            # all workers finished?
            if job.workers and all(w.finished for w in job.workers.values()):
                if state == JobState.RUNNING:
                    job.fsm.transition(JobState.FINISHED)
                elif state in (JobState.CHECKPOINT_STOPPING,
                               JobState.STOPPING):
                    job.fsm.transition(JobState.STOPPED)
                elif state in (JobState.RESCALING, JobState.SCHEDULING,
                               JobState.RECOVERING):
                    # mid-rescale/recovery: the OLD workers drained; keep
                    # supervising — fresh workers are about to register
                    # (returning here orphaned post-rescale jobs)
                    continue
                return
            if state != JobState.RUNNING:
                continue
            # ttl reap (preview pipelines): enforced HERE so a durable
            # controller restart keeps the deadline armed
            if (job.ttl_deadline is not None
                    and time.time() > job.ttl_deadline
                    and not job.stop_requested):
                logger.info("job %s ttl expired; stopping", job.job_id)
                try:
                    await self.stop_job(job.job_id, checkpoint=False)
                except Exception:
                    logger.warning("ttl stop of %s failed", job.job_id,
                                   exc_info=True)
                continue
            # heartbeat timeout (30s)
            now = time.monotonic()
            for w in job.workers.values():
                if (not w.finished
                        and now - w.last_heartbeat > cfg.heartbeat_timeout_secs):
                    await self._recover(
                        job, f"worker {w.worker_id} heartbeat timeout")
                    break
            # SLO burn evaluation (obs/latency.py): judge the rollup's
            # headline p99/staleness against the job's declared targets
            # about once a second — violations land in the evaluator's
            # event ring + metrics, and the burn rate feeds the
            # autoscaler's latency signal
            if job.slo.configured() and now - last_slo >= 1.0:
                last_slo = now
                try:
                    lat = self.latency_shape(self.job_rollup(job.job_id))
                    job.slo_eval.evaluate(lat["p99_ms"], lat["staleness_ms"])
                except Exception:
                    logger.warning("slo evaluation for %s failed",
                                   job.job_id, exc_info=True)
            # periodic checkpoints
            if now - last_ckpt >= cfg.checkpoint_interval_secs:
                last_ckpt = now
                await self._trigger_checkpoint(job)

    async def _recover(self, job: Job, error: str) -> None:
        """Running -> Recovering -> Scheduling -> Running (states/mod.rs
        :196-202, recovering.rs)."""
        logger.warning("job %s recovering: %s", job.job_id, error)
        if not job.fsm.try_recover(error):
            await self.scheduler.stop_workers(job.job_id, force=True)
            return
        n_workers = max(len(job.workers), 1)
        await self._broadcast_workers(job, "StopExecution", {
            "job_id": job.job_id, "stop_mode": "immediate"}, ignore_errors=True)
        await self._restart_workers(job, n_workers, force_stop=True)

    async def _restart_workers(self, job: Job, n_workers: int,
                               force_stop: bool) -> None:
        """Shared stop -> clear -> Scheduling -> start -> schedule -> Running
        tail of recovery and rescale (single source for slot sizing)."""
        await self.scheduler.stop_workers(job.job_id, force=force_stop)
        await self._close_worker_clients(job)
        job.workers.clear()
        job.finished_tasks.clear()
        job.trackers.clear()
        job.fsm.transition(JobState.SCHEDULING)
        await self.scheduler.start_workers(
            job.job_id, self.addr, n_workers,
            max(1, (job.slots_needed + n_workers - 1) // n_workers))
        self._persist_workers(job)
        await self._schedule(job, n_workers, restore=True)
        job.fsm.transition(JobState.RUNNING)

    async def _trigger_checkpoint(self, job: Job,
                                  then_stop: bool = False) -> None:
        job.epoch += 1
        # incomplete epochs that missed a worker can never finish; prune
        # them so trackers don't accumulate over a long-running job
        for e in [e for e in job.trackers
                  if e <= job.epoch - 8 and not job.trackers[e].done]:
            del job.trackers[e]
        job.trackers[job.epoch] = CheckpointTracker(job.epoch, job.n_subtasks)
        payload = {
            "job_id": job.job_id, "epoch": job.epoch,
            "min_epoch": job.min_epoch, "timestamp": now_micros(),
            "then_stop": then_stop, "is_commit": False}
        if not then_stop:
            # a worker stalled in a long jit compile must not fail the
            # driver: a periodic epoch that can't reach every worker simply
            # never completes and a later one supersedes it; heartbeat
            # timeout catches real deaths
            await self._broadcast_workers(job, "Checkpoint", payload,
                                          ignore_errors=True)
            return
        try:
            await self._broadcast_workers(job, "Checkpoint", payload)
        except Exception as e:
            # a stop-checkpoint that can't reach every worker must still
            # stop the job: fall back to a plain graceful stop (the final
            # state is simply not snapshotted, as with stop(checkpoint
            # =False))
            logger.warning(
                "job %s stop-checkpoint broadcast failed (%s); falling "
                "back to graceful stop", job.job_id, e)
            await self._broadcast_workers(
                job, "StopExecution",
                {"job_id": job.job_id, "stop_mode": "graceful"},
                ignore_errors=True)

    def _persist_workers(self, job: Job) -> None:
        """Record the scheduler's external worker ids so a restarted
        controller can reap this incarnation's orphans."""
        if self.store is None:
            return
        try:
            self.store.set_workers(job.job_id,
                                   self.scheduler.workers_for_job(job.job_id))
        except NotImplementedError:
            pass

    async def _broadcast_workers(self, job: Job, method: str, payload: Dict,
                                 ignore_errors: bool = False) -> None:
        for w in job.workers.values():
            if w.finished:
                continue
            try:
                await w.client.call(method, payload)
            except Exception as e:
                if not ignore_errors:
                    raise
                logger.debug("broadcast %s to %s failed: %s", method,
                             w.worker_id, e)

    async def _await_workers_finished(self, job: Job,
                                      timeout: float) -> bool:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if all(w.finished for w in job.workers.values()):
                return True
            await asyncio.sleep(0.05)
        return False

    # -- ControllerGrpc handlers ------------------------------------------

    async def _register_worker(self, req: Dict) -> Dict:
        job = self.jobs.get(req["job_id"])
        if job is None:
            return {"error": "unknown job"}
        w = WorkerInfo(req["worker_id"], req["rpc_address"],
                       req["data_address"], req["slots"])
        w.client = RpcClient(w.rpc_address, "WorkerGrpc")
        job.workers[w.worker_id] = w
        return {}

    async def _heartbeat(self, req: Dict) -> Dict:
        job = self.jobs.get(req["job_id"])
        if job and req["worker_id"] in job.workers:
            w = job.workers[req["worker_id"]]
            w.last_heartbeat = time.monotonic()
            metrics = req.get("metrics")
            if isinstance(metrics, (bytes, bytearray)) and metrics:
                # msgpack over the wire (see rpc.proto HeartbeatReq)
                try:
                    from ..rpc.transport import _deser_msgpack

                    metrics = _deser_msgpack(bytes(metrics))
                except Exception:
                    # keep accepting heartbeats, but a persistent decode
                    # failure (worker/controller version skew) would
                    # silently blank every job rollup — say so once
                    if not self._metrics_decode_warned:
                        self._metrics_decode_warned = True
                        logger.warning(
                            "undecodable heartbeat metrics payload from "
                            "worker %s; job rollups will be empty",
                            req["worker_id"], exc_info=True)
                    metrics = None
            if metrics:
                w.prev_snapshot, w.prev_time = (w.metric_snapshot,
                                                w.snapshot_time)
                w.metric_snapshot, w.snapshot_time = (metrics,
                                                      time.monotonic())
        return {}

    # -- job-level metric aggregation -------------------------------------

    @staticmethod
    def _rollup_op(agg: Dict[str, Any], cur: Dict[str, float],
                   prev: Optional[Dict[str, float]], dt: float) -> None:
        """Fold one worker's per-operator summary into the job rollup.
        Counters/sums add across workers; rates come from the worker's own
        two newest heartbeat samples."""

        def get(src, key):
            # prometheus_client exposes counters with a _total suffix
            return src.get(key, src.get(key + "_total", 0.0)) if src else 0.0

        for key in ("messages_recv", "messages_sent", "bytes_recv",
                    "bytes_sent", "kernel_seconds", "backpressure_seconds"):
            agg[key] = agg.get(key, 0.0) + get(cur, key)
        for key in ("tx_queue_size", "tx_queue_rem"):
            agg[key] = agg.get(key, 0.0) + cur.get(key, 0.0)
        for k, v in (cur or {}).items():
            # phase profiler ride-alongs (obs/profiler.py): phase/wait
            # seconds and stall counts sum across workers; the event-loop
            # lag quantile gauges take the worst worker — one stalled
            # loop is the signal, averaging would hide it
            if k.startswith(("phase_seconds.", "wait_seconds.")) \
                    or k.startswith("event_loop_stalls") \
                    or k.startswith(("critical_path.", "device_bytes.")) \
                    or k == "e2e_latency.count":
                agg[k] = agg.get(k, 0.0) + v
            elif k.startswith("event_loop_lag") \
                    or k.startswith("e2e_latency.") \
                    or k in ("wm_age_ms", "latency_sample_n"):
                # latency quantiles / watermark ages: the worst worker
                # is the signal, summing would fabricate latencies
                agg[k] = max(agg.get(k, 0.0), v)
        # per-subtask queue pairs → worst-subtask backpressure (same
        # rationale as the lag families below: the summed gauges dilute
        # one saturated subtask among idle siblings)
        for k in cur:
            if k.startswith("tx_queue_size@"):
                size = cur[k]
                rem = cur.get("tx_queue_rem@" + k.rsplit("@", 1)[1], 0.0)
                if size > 0:
                    agg["_bp_worst"] = max(agg.get("_bp_worst", 0.0),
                                           1.0 - rem / size)
        if prev is not None and dt > 0:
            agg["records_per_sec"] = agg.get("records_per_sec", 0.0) + max(
                get(cur, "messages_sent") - get(prev, "messages_sent"),
                0.0) / dt
        # lag/latency: average over the newest heartbeat window (delta of
        # the histogram _sum/_count pair); the lifetime average only on
        # the very first sample.  A window with no new samples contributes
        # nothing — falling back to the lifetime average there would pin
        # a startup backlog's lag on the rollup forever after the
        # operator goes idle.
        for short, fam in (("event_time_lag", "event_time_lag_seconds"),
                           ("watermark_lag", "watermark_lag_seconds"),
                           ("batch_latency", "batch_processing_seconds"),
                           ("queue_wait", "queue_wait_seconds"),
                           ("checkpoint_duration",
                            "checkpoint_duration_seconds")):
            # worst across subtasks AND workers: a single lagging subtask
            # is the signal, averaging it away would hide it.  Workers
            # ship per-subtask pairs (`fam_sum@idx`) for the lag families
            # so co-located subtasks don't get averaged together; the
            # worker-summed flat pair is the fallback (checkpoint
            # histograms, legacy payloads, tests)
            sub_pairs = [(k, fam + "_count@" + k.rsplit("@", 1)[1])
                         for k in cur if k.startswith(fam + "_sum@")]
            for sk, ck in sub_pairs or [(fam + "_sum", fam + "_count")]:
                s, c = cur.get(sk, 0.0), cur.get(ck, 0.0)
                if prev is not None:
                    s -= prev.get(sk, 0.0)
                    c -= prev.get(ck, 0.0)
                if c > 0:
                    agg[short] = max(agg.get(short, 0.0), s / c)

    @staticmethod
    def _finalize_rollup(agg: Dict[str, Any],
                         age_secs: Optional[float]) -> None:
        qsize = agg.get("tx_queue_size", 0.0)
        # aggregate ratio as the floor (flat/legacy payloads), worst
        # subtask on top when the per-subtask pairs were shipped
        agg["backpressure"] = max(
            1.0 - agg.get("tx_queue_rem", 0.0) / qsize
            if qsize > 0 else 0.0,
            agg.pop("_bp_worst", 0.0))
        agg["age_secs"] = age_secs

    @classmethod
    def rollup_from_summary(
            cls, summary: Dict[str, Dict[str, float]]) -> List[Dict[str, Any]]:
        """Job-rollup-shaped aggregation of one in-process registry
        summary — the REST fallback for embedded/LocalRunner jobs the
        controller never scheduled, kept here so the fold + finalize
        logic has a single home."""
        ops = []
        for op, cur in sorted(summary.items()):
            # one in-process registry == one contributing worker
            agg: Dict[str, Any] = {"operator_id": op, "workers": 1}
            cls._rollup_op(agg, cur, None, 0.0)
            cls._finalize_rollup(agg, 0.0)  # live scrape: zero age
            ops.append(agg)
        return ops

    def job_rollup(self, job_id: str) -> List[Dict[str, Any]]:
        """Controller-aggregated per-operator rollup for one job, built
        from worker heartbeat snapshots (records/s, lag, backpressure per
        operator — what the console's DAG overlay and the REST metrics
        routes serve)."""
        job = self.jobs.get(job_id)
        if job is None:
            return []
        ops: Dict[str, Dict[str, Any]] = {}
        now = time.monotonic()
        stale_after = config().heartbeat_timeout_secs
        oldest: Optional[float] = None
        for w in job.workers.values():
            if not w.metric_snapshot:
                continue
            # finished or heartbeat-dead workers no longer describe the
            # running job: max()-ing their last (possibly backpressured)
            # snapshot in would pin the rollup hot until recovery
            if w.finished or now - w.last_heartbeat > stale_after:
                continue
            oldest = (w.snapshot_time if oldest is None
                      else min(oldest, w.snapshot_time))
            dt = w.snapshot_time - w.prev_time
            for op, cur in w.metric_snapshot.items():
                agg = ops.setdefault(op, {"operator_id": op, "workers": 0})
                agg["workers"] += 1
                self._rollup_op(
                    agg, cur,
                    (w.prev_snapshot or {}).get(op) if w.prev_snapshot
                    else None, dt)
        for agg in ops.values():
            # age of the OLDEST contributing snapshot — the newest would
            # hide one worker's staleness behind a livelier sibling's
            self._finalize_rollup(
                agg, round(now - oldest, 1) if oldest else None)
        return sorted(ops.values(), key=lambda g: g["operator_id"])

    @staticmethod
    def profile_shape(rows: List[Dict[str, Any]]) -> Dict[str, Any]:
        """Reshape job-rollup rows into the profile view the REST
        ``profile_rollups`` route and the console DAG hover serve:
        per-operator phase/wait second maps plus host/device seconds
        (device = the always-on kernel dispatch counter), and the
        worker-level event-loop watchdog numbers aggregated under the
        ``__worker__`` pseudo-operator."""
        ops: List[Dict[str, Any]] = []
        worker: Dict[str, Any] = {}
        for row in rows:
            op = row.get("operator_id", "")
            phases = {k[len("phase_seconds."):]: round(v, 6)
                      for k, v in row.items()
                      if k.startswith("phase_seconds.")}
            waits = {k[len("wait_seconds."):]: round(v, 6)
                     for k, v in row.items()
                     if k.startswith("wait_seconds.")}
            if op == "__worker__":
                worker = {
                    "event_loop_lag_p50_secs": row.get(
                        "event_loop_lag_seconds_p50", 0.0),
                    "event_loop_lag_p99_secs": row.get(
                        "event_loop_lag_seconds_p99", 0.0),
                    "event_loop_stalls": row.get(
                        "event_loop_stalls_total",
                        row.get("event_loop_stalls", 0.0)),
                }
                continue
            if not phases and not waits:
                continue
            # host vs device split from the profiler's OWN phase table:
            # dispatch is the kernel-bound span, every other phase is
            # pure host envelope.  (kernel_seconds is the
            # same non-blocking dispatch wall as the `dispatch` phase —
            # re-reading it as "device" would count that span twice; it
            # only serves as the fallback when no dispatch phase was
            # recorded, e.g. a legacy worker without the profiler's
            # timed_device hook.)
            device = phases.get("dispatch", 0.0)
            host = sum(phases.values()) - device
            if device == 0.0:
                device = row.get("kernel_seconds", 0.0)
            ops.append({
                "operator_id": op,
                "phases": phases,
                "waits": waits,
                "host_seconds": round(host, 6),
                "device_seconds": round(device, 6),
                # of this operator's measured time, how much was host
                # envelope vs kernel-bound dispatch — the per-node
                # coloring the console DAG uses
                "host_share": round(host / (host + device), 4)
                if host + device > 0 else None,
            })
        total = sum(o["host_seconds"] for o in ops)
        for o in ops:
            o["job_share"] = (round(o["host_seconds"] / total, 4)
                              if total > 0 else 0.0)
        return {"operators": ops, "worker": worker}

    @staticmethod
    def latency_shape(rows: List[Dict[str, Any]]) -> Dict[str, Any]:
        """Reshape job-rollup rows into the latency view (REST
        ``/v1/jobs/{id}/latency`` and the console latency panel):
        per-sink e2e quantiles, per-operator watermark ages, the
        worker-level critical-path stage decomposition and the
        device-memory ledger, plus the headline p99/staleness the SLO
        evaluator judges."""
        sinks: Dict[str, Dict[str, float]] = {}
        wm_ages: Dict[str, float] = {}
        critical: Dict[str, float] = {}
        device: Dict[str, int] = {}
        sample_n = 0
        for row in rows:
            op = row.get("operator_id", "")
            if op == "__worker__":
                for k, v in row.items():
                    if k.startswith("critical_path."):
                        critical[k[len("critical_path."):]] = round(v, 6)
                    elif k.startswith("device_bytes."):
                        device[k[len("device_bytes."):]] = int(v)
                sample_n = int(row.get("latency_sample_n", 0))
            if "e2e_latency.p99_ms" in row:
                sinks[op] = {
                    "p50_ms": round(row.get("e2e_latency.p50_ms", 0.0), 3),
                    "p99_ms": round(row.get("e2e_latency.p99_ms", 0.0), 3),
                    "last_ms": round(row.get("e2e_latency.last_ms", 0.0), 3),
                    "count": int(row.get("e2e_latency.count", 0)),
                }
            if "wm_age_ms" in row:
                wm_ages[op] = round(row["wm_age_ms"], 3)
        total = sum(critical.values())
        dominant = (max(critical, key=critical.__getitem__)
                    if critical else None)
        p99 = max((q["p99_ms"] for q in sinks.values()), default=None)
        stale = max(wm_ages.values(), default=None)
        return {
            "sample_n": sample_n,
            "sinks": sinks,
            "watermark_age_ms": wm_ages,
            "critical_path": {
                "stages": critical,
                "total_secs": round(total, 6),
                "dominant": dominant,
                "dominant_share": (round(critical[dominant] / total, 4)
                                   if dominant and total > 0 else 0.0),
            },
            "device_state_bytes": device,
            "p99_ms": p99,
            "staleness_ms": stale,
        }

    def job_latency(self, job_id: str) -> Optional[Dict[str, Any]]:
        """Latency view + SLO verdict for one controller-owned job
        (None when the job is unknown — REST falls back to the local
        in-process registry there)."""
        job = self.jobs.get(job_id)
        if job is None:
            return None
        out = self.latency_shape(self.job_rollup(job_id))
        out["slo"] = job.slo_eval.to_json()
        return out

    def job_profile_rollup(self, job_id: str) -> Dict[str, Any]:
        """Phase-profile view of one job's heartbeat rollups (empty
        ``operators`` when no worker has a profiler armed)."""
        return self.profile_shape(self.job_rollup(job_id))

    async def _task_started(self, req: Dict) -> Dict:
        return {}

    async def _task_ckpt_event(self, req: Dict) -> Dict:
        return {}

    async def _task_ckpt_completed(self, req: Dict) -> Dict:
        job = self.jobs.get(req["job_id"])
        if job is None:
            return {}
        tracker = job.trackers.get(req["epoch"])
        if tracker is None:
            tracker = job.trackers.setdefault(
                req["epoch"], CheckpointTracker(req["epoch"], job.n_subtasks))
        san = getattr(self, "sanitizer", None)  # doubles skip __init__
        if san is not None:
            key = (req["operator_id"], req["subtask"])
            san.event("ckpt-done", f"{key[0]}-{key[1]}",
                      {"epoch": req["epoch"], "via": "controller"})
            if key in tracker.completed:
                # trackers are cleared on restart/rescale, so a
                # duplicate inside one tracker's life means two
                # snapshots raced for the same (member, subtask, epoch)
                san.violation(
                    "duplicate-checkpoint",
                    f"{key[0]}-{key[1]} reported checkpoint epoch "
                    f"{req['epoch']} twice within one job run")
        tracker.completed.add((req["operator_id"], req["subtask"]))
        tracker.has_committing |= bool(req.get("has_committing_data"))
        if tracker.done:
            await self._finalize_checkpoint(job, tracker)
        return {}

    async def _finalize_checkpoint(self, job: Job,
                                   tracker: CheckpointTracker) -> None:
        backend = ParquetBackend.for_url(job.checkpoint_url)
        backend.storage.put(
            f"{job.job_id}/checkpoints/checkpoint-{tracker.epoch:07d}/"
            "metadata.json",
            json.dumps({
                "complete": True, "epoch": tracker.epoch,
                "n_subtasks": tracker.n_subtasks,
                "time": now_micros(),
            }).encode())
        job.last_successful_epoch = tracker.epoch
        del job.trackers[tracker.epoch]
        if self.store is not None:
            self.store.set_progress(job.job_id, job.epoch, job.min_epoch,
                                    job.last_successful_epoch)
        # two-phase commit for sinks with commit behavior
        if tracker.has_committing:
            await self._broadcast_workers(
                job, "Commit", {"job_id": job.job_id, "epoch": tracker.epoch},
                ignore_errors=True)
        # compaction every COMPACT_EVERY epochs (mod.rs:30-31, 388-394):
        # merge per-subtask gen-0 files into key-range-partitioned gen-1
        # files, then tell workers to hot-swap (LoadCompactedData)
        compact_every = config().compact_every
        if (compact_every and tracker.epoch % compact_every == 0
                and hasattr(backend, "compact_operator")):
            loop = asyncio.get_running_loop()
            ckpt_dir = backend.checkpoint_dir(job.job_id, tracker.epoch) + "/"
            op_ids = set()
            for f in backend.storage.list(ckpt_dir):
                part = f[len(ckpt_dir):].split("/", 1)[0]
                if part.startswith("operator-"):
                    op_ids.add(part[len("operator-"):])
            for op_id in sorted(op_ids):
                # sync parquet I/O off the controller's event loop
                result = await loop.run_in_executor(
                    None, backend.compact_operator, job.job_id, op_id,
                    tracker.epoch)
                if result["to_load"]:
                    await self._broadcast_workers(
                        job, "LoadCompactedData",
                        {"job_id": job.job_id, "epoch": tracker.epoch,
                         "operator_id": op_id, "files": result["to_load"],
                         "dropped": result["to_drop"]},
                        ignore_errors=True)
        # epoch cleanup: keep the last N checkpoints (mod.rs:30, 388-394)
        await self._prune_checkpoints(job, backend=backend)

    async def _prune_checkpoints(self, job: Job, backend=None) -> None:
        """Prune to the last ``checkpoint_retention`` completed epochs.
        Runs after every successful checkpoint AND after every rescale
        restore point (state/backend cleanup_before does the listing and
        deletes, which can hit object storage — so off the event loop)."""
        if job.last_successful_epoch is None:
            return
        keep = config().checkpoint_retention
        min_epoch = max(job.last_successful_epoch - keep + 1, 0)
        if min_epoch <= job.min_epoch:
            return
        job.min_epoch = min_epoch
        if backend is None:
            backend = ParquetBackend.for_url(job.checkpoint_url)
        if self.store is not None:
            self.store.set_progress(job.job_id, job.epoch, job.min_epoch,
                                    job.last_successful_epoch)
        try:
            await asyncio.get_running_loop().run_in_executor(
                None, backend.cleanup_before, job.job_id, min_epoch)
        except Exception:
            # retention is best-effort: a storage hiccup must not fail
            # a checkpoint finalize or a completed rescale
            logger.warning("checkpoint pruning for %s failed", job.job_id,
                           exc_info=True)

    async def _task_finished(self, req: Dict) -> Dict:
        job = self.jobs.get(req["job_id"])
        if job:
            job.finished_tasks.add((req["operator_id"], req["subtask"]))
        return {}

    async def _task_failed(self, req: Dict) -> Dict:
        job = self.jobs.get(req["job_id"])
        if job:
            job.failure = (f"{req['operator_id']}-{req['subtask']}: "
                           f"{req.get('error', '')}")
        return {}

    async def _worker_finished(self, req: Dict) -> Dict:
        job = self.jobs.get(req["job_id"])
        if job and req["worker_id"] in job.workers:
            job.workers[req["worker_id"]].finished = True
        return {}

    async def _worker_error(self, req: Dict) -> Dict:
        job = self.jobs.get(req["job_id"])
        if job:
            job.failure = req.get("error", "worker error")
        return {}

    async def _send_sink_data(self, req: Dict) -> Dict:
        for q in self.sink_subscribers.get(req["job_id"], []):
            await q.put(req)
        return {}

    async def _subscribe_output(self, req: Dict):
        q: asyncio.Queue = asyncio.Queue()
        self.sink_subscribers.setdefault(req["job_id"], []).append(q)
        try:
            while True:
                item = await q.get()
                yield item
                if item.get("done"):
                    return
        finally:
            self.sink_subscribers[req["job_id"]].remove(q)


def main() -> None:
    """``python -m arroyo_tpu.controller.controller``: standalone
    controller (deploy/ role 'controller'; the API talks to it over
    gRPC from another pod)."""
    import os

    from ..obs.logging_setup import init_logging

    async def serve() -> None:
        init_logging("controller")
        ctrl = ControllerServer(host=os.environ.get("CONTROLLER_HOST",
                                                    "0.0.0.0"))
        await ctrl.start(port=int(os.environ.get("CONTROLLER_PORT",
                                                 "9190")))
        logger.info("controller grpc at %s (advertised: set "
                    "CONTROLLER_ADVERTISE_ADDR for cross-pod dialing)",
                    ctrl.addr)
        await asyncio.Event().wait()

    asyncio.run(serve())


if __name__ == "__main__":
    main()
