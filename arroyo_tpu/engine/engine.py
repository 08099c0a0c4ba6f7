"""Engine: physical graph construction and execution.

Analog of /root/reference/arroyo-worker/src/engine.rs: expands the logical
graph by parallelism into subtasks (engine.rs:597-705), wires Forward (1:1)
vs Shuffle (all-to-all) channels, spawns one asyncio task per subtask
(``Engine::start``/``schedule_node``/``run_locally``, engine.rs:813-1102) and
exposes source/operator control handles (``RunningEngine``, engine.rs:720-811).

``Engine.for_local`` + :class:`LocalRunner` reproduce the reference's
in-process multi-task "cluster" (engine.rs:606-619, 837-863): the full
physical graph — all parallel subtasks, real queues, real state — in one
process.  This is the standard test fixture and the single-host execution
mode; multi-host splits this same graph across workers with network channels.
"""

from __future__ import annotations

import asyncio
import logging
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

from ..config import config
from ..graph.logical import EdgeType, Program, StreamNode
from ..state.backend import BackingStore, InMemoryBackend, ParquetBackend
from ..state.store import StateStore
from ..types import (
    CheckpointBarrier,
    ControlMessage,
    ControlResp,
    Message,
    StopMode,
    TaskInfo,
    now_micros,
)
from .build import build_operator
from .context import Collector, Context, OutQueue
from .operator import SourceOperator
from .task import TaskRunner

logger = logging.getLogger(__name__)


@dataclass
class SubtaskHandle:
    task_info: TaskInfo
    runner: TaskRunner
    control_tx: asyncio.Queue  # ControlMessage -> task
    is_source: bool
    task: Optional[asyncio.Task] = None
    # logical operators executed by this runner — [op_id] for a plain
    # subtask, the full member list (head first) for a chained one
    member_ids: List[str] = field(default_factory=list)


class Engine:
    def __init__(self, program: Program, job_id: str = "local-job",
                 run_id: str = "0",
                 backend: Optional[BackingStore] = None,
                 restore_epoch: Optional[int] = None,
                 assignments: Optional[Dict[Tuple[str, int], str]] = None,
                 my_worker_id: Optional[str] = None,
                 worker_data_addrs: Optional[Dict[str, str]] = None,
                 network: Optional[Any] = None):
        """``assignments`` maps (operator_id, subtask_idx) -> worker_id; when
        given with ``my_worker_id``, only this worker's subtasks are built and
        cross-worker edges ride the network data plane (``network`` must be a
        NetworkManager, ``worker_data_addrs`` maps worker_id -> host:port)."""
        # factor-window sharing for Stream-API-built programs (SQL plans
        # arrive already rewritten by the planner; the pass is idempotent
        # — rewritten plans have no eligible member groups left).  Must
        # run before validation so the validator sees the factored shape.
        from ..graph.factor_windows import apply_factor_windows

        self.factor_decisions = apply_factor_windows(program)
        errors = program.validate()
        if errors:
            raise ValueError("; ".join(errors))
        # full plan-time validation (analysis.plan_validator): keyed
        # state behind shuffles, join key schemas, dangling nodes —
        # reject before any operator is built
        from .build import validate_before_build

        validate_before_build(program)
        self.program = program
        self.job_id = job_id
        self.run_id = run_id
        self.backend = backend if backend is not None else InMemoryBackend()
        self.restore_epoch = restore_epoch
        self.assignments = assignments
        self.my_worker_id = my_worker_id
        self.worker_data_addrs = worker_data_addrs or {}
        self.network = network
        self.control_resp: asyncio.Queue = asyncio.Queue()
        self.sanitizer: Optional[Any] = None  # set by start()
        self.subtasks: Dict[Tuple[str, int], SubtaskHandle] = {}
        self.resps: List[ControlResp] = []  # responses drained so far

    def _is_mine(self, op_id: str, idx: int) -> bool:
        if self.assignments is None:
            return True
        return self.assignments.get((op_id, idx)) == self.my_worker_id

    def _worker_of(self, op_id: str, idx: int) -> Optional[str]:
        if self.assignments is None:
            return None
        return self.assignments.get((op_id, idx))

    @staticmethod
    def for_local(program: Program, job_id: str = "local-job",
                  checkpoint_url: Optional[str] = None,
                  restore_epoch: Optional[int] = None) -> "Engine":
        backend: BackingStore
        if checkpoint_url:
            backend = ParquetBackend.for_url(checkpoint_url)
        else:
            backend = InMemoryBackend()
        return Engine(program, job_id, backend=backend, restore_epoch=restore_epoch)

    # ------------------------------------------------------------------

    def start(self) -> "RunningEngine":
        """Build the physical graph and spawn all subtask loops."""
        # engine rebuilds and worker restarts reuse XLA executables from
        # the persistent compilation cache instead of recompiling
        from .aot import enable_persistent_cache

        enable_persistent_cache()
        # arroyosan runtime sanitizer: one instance per engine run (so a
        # rescale restore starts from fresh invariant state); None unless
        # ARROYO_SANITIZE armed it — the hook sites then cost nothing
        from ..analysis.sanitizer import maybe_sanitizer

        sanitizer = maybe_sanitizer(self.job_id)
        self.sanitizer = sanitizer
        # phase profiler (obs/profiler.py): armed by ARROYO_PROFILE=1 or
        # an explicit profiler.arm() (bench, tests) — must happen before
        # subtask construction so Collectors/coalescers capture it; the
        # hook sites cost one `is not None` test when disarmed
        from ..obs import profiler as _profiler

        prof = _profiler.ensure_armed(self.job_id)
        # latency observatory (obs/latency.py): armed by
        # ARROYO_LATENCY_SAMPLE_N>0 or an explicit latency.arm() — same
        # before-subtask-construction + None-when-disarmed contract as
        # the profiler
        from ..obs import latency as _latency

        _latency.ensure_armed(self.job_id)
        g = self.program.graph
        # operator chaining (graph/chaining.py): maximal linear runs of
        # same-parallelism forward-edge operators execute inside ONE
        # TaskRunner — no intermediate queues, one alignment per chain.
        # ARROYO_CHAIN=0 yields an empty plan and reproduces the
        # per-operator topology bit-for-bit.
        from ..graph.chaining import plan_chains, validate_chain_plan

        chain_plan = plan_chains(self.program)
        validate_chain_plan(self.program, chain_plan)
        chain_interior = {m for grp in chain_plan.groups for m in grp[1:]}
        # observable mesh carriage: how many chain-interior SHUFFLE
        # edges the active mesh carries as on-device all_to_all (0 when
        # ARROYO_MESH=off — those edges are then plain identity-routed
        # queue hops inside the chain).  Set UNCONDITIONALLY: the gauge
        # is process-global per job_id, so a re-plan that lost its
        # carried edges (rescale past parallelism 1, chaining off) must
        # drop it back to 0, not report the previous topology forever.
        from ..obs.metrics import mesh_carried_gauge
        from ..parallel.mesh_window import mesh_key_shards

        mesh_carried_gauge(self.job_id).set(
            len(chain_plan.shuffle_edges)
            if chain_plan.shuffle_edges and mesh_key_shards() > 1 else 0)
        # factor-window shape (set unconditionally: a re-plan that lost
        # its factored groups must drop the gauges to 0, same policy as
        # the mesh-carried gauge)
        from ..graph.logical import OpKind as _OpKind
        from ..obs.metrics import (factor_derived_windows_gauge,
                                   factor_shared_panes_gauge)

        kinds = [n.operator.kind for n in self.program.nodes()]
        factor_shared_panes_gauge(self.job_id).set(
            kinds.count(_OpKind.WINDOW_FACTOR))
        factor_derived_windows_gauge(self.job_id).set(
            kinds.count(_OpKind.DERIVED_WINDOW))
        # queues[(src_id, src_idx, dst_id, dst_idx)] — the reference's Quad
        queues: Dict[Tuple[str, int, str, int], asyncio.Queue] = {}
        qsize = config().queue_size

        def queue_for(quad: Tuple[str, int, str, int]) -> asyncio.Queue:
            if quad not in queues:
                queues[quad] = asyncio.Queue(maxsize=qsize)
            return queues[quad]

        def out_queue(quad: Tuple[str, int, str, int]) -> OutQueue:
            """Local queue or remote network sender for an outgoing edge."""
            _, _, dst_op, dst_idx = quad
            w = self._worker_of(dst_op, dst_idx)
            if w is None or w == self.my_worker_id:
                return OutQueue(queue_for(quad))
            addr = self.worker_data_addrs[w]
            return OutQueue(sender=self.network.remote_sender(addr, quad))

        def in_queue(quad: Tuple[str, int, str, int]) -> asyncio.Queue:
            """Local queue for an incoming edge; remote sources are demuxed
            into it by the network listener."""
            src_op, src_idx, _, _ = quad
            q = queue_for(quad)
            w = self._worker_of(src_op, src_idx)
            if w is not None and w != self.my_worker_id:
                self.network.register_in_edge(quad, q)
            return q

        def build_subtask(ms: List[str], idx: int) -> None:
            """One runner for the member run ``ms`` (a full chain, or a
            single operator) at subtask index ``idx``."""
            head_id, tail_id = ms[0], ms[-1]
            head_node: StreamNode = self.program.node(head_id)
            parallelism = head_node.parallelism
            out_edges = list(g.out_edges(tail_id, data=True))
            in_edges = list(g.in_edges(head_id, data=True))

            # output edge groups (one group per downstream operator),
            # leaving from the chain TAIL
            edge_groups: List[List[OutQueue]] = []
            for _, dst, data in out_edges:
                dst_par = self.program.node(dst).parallelism
                typ: EdgeType = data["edge"].typ
                if typ == EdgeType.FORWARD:
                    # equal parallelism: 1:1 chain; mismatched: rebalance —
                    # fan-in (src i -> dst i % dst_par) or fan-out
                    # (src i -> every dst j with j % src_par == i,
                    # round-robined per batch by the Collector)
                    if dst_par > parallelism:
                        group = [out_queue((tail_id, idx, dst, j))
                                 for j in range(dst_par)
                                 if j % parallelism == idx]
                    else:
                        group = [out_queue((tail_id, idx, dst,
                                            idx % dst_par))]
                else:
                    group = [out_queue((tail_id, idx, dst, j))
                             for j in range(dst_par)]
                edge_groups.append(group)

            # input channels into the chain HEAD: (side, queue) per
            # upstream subtask
            inputs: List[Tuple[int, asyncio.Queue]] = []
            for src, _, data in sorted(
                    in_edges, key=lambda e: e[2]["edge"].typ.value):
                src_par = self.program.node(src).parallelism
                typ = data["edge"].typ
                side = typ.join_side or 0  # shuffle_join_N carries N
                if typ == EdgeType.FORWARD:
                    if parallelism > src_par:
                        inputs.append((side, in_queue(
                            (src, idx % src_par, head_id, idx))))
                    else:
                        for j in range(src_par):
                            if j % parallelism == idx:
                                inputs.append((side, in_queue(
                                    (src, j, head_id, idx))))
                else:
                    for j in range(src_par):
                        inputs.append((side, in_queue((src, j, head_id,
                                                       idx))))

            from ..obs.metrics import (CHAIN_MEMBERS, TaskMetrics,
                                       gauge_for_task)

            infos = [TaskInfo(self.job_id, m,
                              self.program.node(m).operator.name, idx,
                              parallelism) for m in ms]
            metrics_list = [TaskMetrics(ti) for ti in infos]
            stores = [StateStore(ti, self.backend, self.restore_epoch)
                      for ti in infos]
            for st in stores:
                st.sanitizer = sanitizer
            collector = Collector(edge_groups, metrics_list[-1],
                                  op_id=tail_id, sanitizer=sanitizer,
                                  subtask=idx)
            if len(ms) == 1:
                operator = build_operator(head_node.operator)
                rwm = (stores[0].restore_watermark()
                       if self.restore_epoch else None)
                ctx = Context(infos[0], collector, n_inputs=len(inputs),
                              state_store=stores[0],
                              control_tx=self.control_resp,
                              restore_watermark=rwm,
                              metrics=metrics_list[0])
            else:
                from .chained import ChainedOperator

                ops = [build_operator(self.program.node(m).operator)
                       for m in ms]
                operator = ChainedOperator(infos, ops)
                ctxs: List[Context] = []
                for i, (ti, st, mx) in enumerate(
                        zip(infos, stores, metrics_list)):
                    coll = (collector if i == len(ms) - 1
                            else operator.make_link(i))
                    rwm = (st.restore_watermark()
                           if self.restore_epoch else None)
                    ctxs.append(Context(
                        ti, coll,
                        n_inputs=len(inputs) if i == 0 else 1,
                        state_store=st, control_tx=self.control_resp,
                        restore_watermark=rwm, metrics=mx))
                operator.bind(ctxs)
                ctx = ctxs[0]
            gauge_for_task(infos[0], CHAIN_MEMBERS,
                           "operators fused into this task").set(len(ms))
            control_rx: asyncio.Queue = asyncio.Queue()
            runner = TaskRunner(infos[0], operator, ctx, inputs,
                                control_rx, self.control_resp,
                                sanitizer=sanitizer)
            ctx._runner = runner  # sources poll control via the runner
            self.subtasks[(head_id, idx)] = SubtaskHandle(
                infos[0], runner, control_rx,
                isinstance(operator, SourceOperator),
                member_ids=list(ms))

        # construct subtasks in topo order (chain heads only; interior
        # members are built inside their head's runner)
        for op_id in self.program.topo_order():
            if op_id in chain_interior:
                continue
            members = chain_plan.members_of.get(op_id, [op_id])
            for idx in range(self.program.node(op_id).parallelism):
                mine = [m for m in members if self._is_mine(m, idx)]
                if not mine:
                    continue
                if len(mine) == len(members):
                    build_subtask(members, idx)
                else:
                    # split assignment across workers (the controller's
                    # slot packing never produces this, but defensively):
                    # run each local member unchained so cross-worker
                    # member edges ride the data plane
                    for m in mine:
                        build_subtask([m], idx)

        for handle in self.subtasks.values():
            handle.task = asyncio.ensure_future(handle.runner.start())
        if prof is not None:
            # event-loop stall watchdog: one ticker per loop (idempotent),
            # sampler thread started lazily; the task dies with its loop
            prof.watchdog.ensure_ticker()
        return RunningEngine(self)


class RunningEngine:
    """Control handles over a started engine (engine.rs:720-811)."""

    def __init__(self, engine: Engine):
        self.engine = engine

    def source_controls(self) -> List[asyncio.Queue]:
        return [h.control_tx for h in self.engine.subtasks.values() if h.is_source]

    def operator_controls(self) -> Dict[str, List[asyncio.Queue]]:
        """Per-operator control queues; every member of a chained task
        maps to its runner's queue, so operator-addressed control
        (compaction hot-swaps) still reaches fused operators."""
        out: Dict[str, List[asyncio.Queue]] = {}
        for (op_id, _), h in sorted(self.engine.subtasks.items()):
            for m in (h.member_ids or [op_id]):
                out.setdefault(m, []).append(h.control_tx)
        return out

    def sink_controls(self) -> List[asyncio.Queue]:
        sink_ids = {n.operator_id for n in self.engine.program.sinks()}
        return [h.control_tx for (op_id, _), h in self.engine.subtasks.items()
                if op_id in sink_ids]

    async def checkpoint(self, epoch: int, min_epoch: int = 0,
                         then_stop: bool = False) -> None:
        """Inject a barrier at all sources (§3.3: barriers enter at sources)."""
        barrier = CheckpointBarrier(epoch, min_epoch, now_micros(), then_stop)
        for q in self.source_controls():
            await q.put(ControlMessage.checkpoint(barrier))

    async def wait_for_checkpoint(self, epoch: int,
                                  timeout: float = 30.0) -> bool:
        """Block until every subtask reported checkpoint_completed for
        ``epoch`` — only then is the epoch restorable (the reference's
        controller CheckpointState aggregation, checkpointer.rs:186-410).
        Returns False on timeout."""
        import time as _time

        # one completion per (member operator, subtask index): a chained
        # runner reports each member separately, so counting runners
        # would return before unrelated tasks (e.g. the source) finished
        expected = {(m, idx) for (op, idx), h in self.engine.subtasks.items()
                    for m in (h.member_ids or [op])}
        deadline = _time.monotonic() + timeout
        done = {(r.operator_id, r.task_index) for r in self.engine.resps
                if r.kind == "checkpoint_completed"
                and r.subtask_metadata.epoch == epoch}
        while not expected <= done:
            remain = deadline - _time.monotonic()
            if remain <= 0:
                return False
            try:
                resp = await asyncio.wait_for(
                    self.engine.control_resp.get(),
                    timeout=min(remain, 0.25))
            except asyncio.TimeoutError:
                # a barrier that raced a draining bounded stream can
                # never seal once every subtask has exited — bail
                # immediately instead of sitting the full deadline on a
                # queue nobody will ever write to (measured: six fuzz
                # restore tests each burned the whole 30s here)
                if self.engine.control_resp.empty() and all(
                        h.task is None or h.task.done()
                        for h in self.engine.subtasks.values()):
                    return False
                continue
            self.engine.resps.append(resp)
            if (resp.kind == "checkpoint_completed"
                    and resp.subtask_metadata.epoch == epoch):
                done.add((resp.operator_id, resp.task_index))
        return True

    async def stop(self, mode: StopMode = StopMode.GRACEFUL) -> None:
        if mode == StopMode.IMMEDIATE:
            # kill-style stop reaches every subtask directly (the reference's
            # recovering path SIGKILLs workers; in-process we signal all loops)
            for h in self.engine.subtasks.values():
                await h.control_tx.put(ControlMessage.stop(mode))
        else:
            for q in self.source_controls():
                await q.put(ControlMessage.stop(mode))

    async def commit(self, epoch: int) -> None:
        for q in self.sink_controls():
            await q.put(ControlMessage.commit(epoch))

    async def load_compacted(self, operator_id: str, payload) -> None:
        """Deliver a compaction hot-swap notice to one operator's subtasks."""
        for q in self.operator_controls().get(operator_id, []):
            await q.put(ControlMessage("load_compacted", compacted=payload))

    async def join(self) -> List[ControlResp]:
        """Wait for all subtasks to finish; drain + return control responses."""
        tasks = [h.task for h in self.engine.subtasks.values() if h.task]
        await asyncio.gather(*tasks, return_exceptions=True)
        resps: List[ControlResp] = self.engine.resps
        while not self.engine.control_resp.empty():
            resps.append(self.engine.control_resp.get_nowait())
        failures = [r for r in resps if r.kind == "task_failed"]
        if failures:
            raise RuntimeError(
                f"{len(failures)} task(s) failed: "
                + "; ".join(f"{f.operator_id}-{f.task_index}: {f.error}"
                            for f in failures[:5]))
        return resps


class LocalRunner:
    """Run a bounded pipeline to completion in-process
    (``LocalRunner``, arroyo-worker/src/lib.rs:213-250)."""

    def __init__(self, program: Program, job_id: str = "local-job",
                 checkpoint_url: Optional[str] = None,
                 restore_epoch: Optional[int] = None):
        self.engine = Engine.for_local(program, job_id,
                                       checkpoint_url=checkpoint_url,
                                       restore_epoch=restore_epoch)

    async def run_async(self, checkpoint_interval_secs: Optional[float] = None
                        ) -> List[ControlResp]:
        running = self.engine.start()
        epoch = [self.engine.restore_epoch or 0]
        ticker: Optional[asyncio.Task] = None
        if checkpoint_interval_secs:
            async def tick():
                while True:
                    await asyncio.sleep(checkpoint_interval_secs)
                    epoch[0] += 1
                    e = epoch[0]
                    await running.checkpoint(e)
                    # act as the mini-controller: once the epoch is sealed,
                    # drive the commit phase so two-phase sinks finalize
                    if await running.wait_for_checkpoint(e):
                        await running.commit(e)

            ticker = asyncio.ensure_future(tick())
        try:
            return await running.join()
        finally:
            if ticker:
                ticker.cancel()

    def run(self, checkpoint_interval_secs: Optional[float] = None
            ) -> List[ControlResp]:
        return asyncio.run(self.run_async(checkpoint_interval_secs))
