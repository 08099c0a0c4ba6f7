"""TaskRunner — the generic per-subtask event loop.

This one class replaces everything the reference's proc-macros generate per
operator (/root/reference/arroyo-macro/src/lib.rs): the tokio task + Context
construction (:568-627), the select! loop with fair input fan-in and
barrier-alignment blocking (:511-566, 414-475), ``handle_control_message``
(:629-704), ``checkpoint()`` (:706-736) and watermark-driven timer firing
(:738-753).

Barrier alignment: when a barrier arrives on one input channel, that channel's
pump parks until barriers have arrived on *all* channels (the reference pushes
the blocked stream aside in InQReader; we park the pump coroutine on an
event), then state snapshots and the barrier is rebroadcast downstream.
"""

from __future__ import annotations

import asyncio
import logging
import time as _time
import traceback
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ..obs import perf, profiler, tracing
from ..state.store import StateStore
from ..types import (
    CheckpointBarrier,
    CheckpointEvent,
    CheckpointEventType,
    ControlMessage,
    ControlResp,
    Message,
    MessageKind,
    StopMode,
    TaskInfo,
    Watermark,
    now_micros,
    MAX_TIMESTAMP,
)
from .context import Context
from .operator import Operator, SourceFinishType, SourceOperator

logger = logging.getLogger(__name__)


class _Pump:
    """Forwards one input channel into the merged queue; parks on barriers."""

    def __init__(self, idx: int, side: int, queue: asyncio.Queue,
                 merged: asyncio.Queue):
        self.idx = idx
        self.side = side
        self.queue = queue
        self.merged = merged
        self.resume = asyncio.Event()
        self.task: Optional[asyncio.Task] = None

    async def run(self) -> None:
        while True:
            msg: Message = await self.queue.get()
            await self.merged.put((self.idx, self.side, msg))
            if msg.kind == MessageKind.BARRIER:
                # block this input until alignment completes
                self.resume.clear()
                await self.resume.wait()
            if msg.is_end:
                return


class TaskRunner:
    def __init__(
        self,
        task_info: TaskInfo,
        operator: Operator,
        ctx: Context,
        inputs: List[Tuple[int, asyncio.Queue]],  # (side, queue)
        control_rx: asyncio.Queue,  # ControlMessage from worker
        control_tx: Optional[asyncio.Queue] = None,  # ControlResp to worker
        sanitizer: Optional[Any] = None,  # arroyosan runtime hooks
    ):
        self.task_info = task_info
        self.operator = operator
        # arroyosan (analysis/sanitizer.py): None unless ARROYO_SANITIZE
        # armed it at engine build — every hook site below guards on a
        # local `is not None`, so the disabled path costs nothing
        self.sanitizer = sanitizer
        operator.sanitizer = sanitizer
        self.ctx = ctx
        # a ChainedOperator's runner ctx is the HEAD member's (input
        # alignment, timers); downstream broadcasts (barriers, stop/eod,
        # idle forward) leave from the TAIL member's context
        self.out_ctx: Context = getattr(operator, "tail_ctx", None) or ctx
        self.inputs = inputs
        self.control_rx = control_rx
        self.control_tx = control_tx
        self.merged: asyncio.Queue = asyncio.Queue(maxsize=len(inputs) * 4 + 16)
        # phase profiler (obs/profiler.py): None unless armed at engine
        # build — every hook site guards on a local `is not None`
        self._prof = profiler.active()
        # latency observatory (obs/latency.py): same None-when-disarmed
        # contract.  A terminal task (no outgoing edges) is where sampled
        # stamps are turned into emit-minus-ingest observations; chained
        # terminal tasks observe at the chain-tail feed instead so a
        # window fire inside the chain is measured at its actual
        # emission, not at pane input (engine/chained.py).
        from ..obs import latency as _latency

        self._lat = _latency.active()
        self._lat_terminal = (self._lat is not None
                              and not self.out_ctx.collector.edge_groups
                              and not operator.own_batch_metrics)
        self.pumps: List[_Pump] = []
        self.finished = asyncio.Event()
        self.failed: Optional[BaseException] = None
        self._align_start: Dict[int, float] = {}  # epoch -> trace us

    # ------------------------------------------------------------------

    async def start(self) -> None:
        # kernel-time attribution: every timed_device dispatch inside this
        # coroutine's context accrues to this subtask's counter
        token = perf.set_active_task(
            perf.KernelAccumulator(self.task_info, self.ctx.metrics))
        run_start = tracing.now_us()
        try:
            await self._run()
        except asyncio.CancelledError:
            raise
        except BaseException as e:  # report task failure to the controller
            self.failed = e
            logger.error("task %s failed: %s\n%s", self.task_info.task_id, e,
                         traceback.format_exc())
            await self.ctx.report(ControlResp(
                kind="task_failed", operator_id=self.task_info.operator_id,
                task_index=self.task_info.task_index, error=str(e)))
            # drain downstream so a local run can't deadlock waiting on
            # inputs that will never end (the controller tears the job
            # down in distributed mode; end_of_data is the local analog)
            try:
                await self.out_ctx.broadcast(Message.end_of_data())
            except Exception:
                pass
        finally:
            self.operator.abandon()  # nothing after a normal close
            tracing.record_span(
                "task.run", "task", run_start,
                tracing.now_us() - run_start, tid=self.task_info.task_id,
                args={"failed": self.failed is not None})
            perf.reset_active_task(token)
            self.finished.set()

    async def _run(self) -> None:
        # register tables, restore persisted timers, on_start — per
        # member for chained operators (Operator.open)
        await self.operator.open(self.ctx)
        await self.ctx.report(ControlResp(
            kind="task_started", operator_id=self.task_info.operator_id,
            task_index=self.task_info.task_index))

        if isinstance(self.operator, SourceOperator):
            await self._run_source()
        else:
            await self._run_processor()

        await self.ctx.report(ControlResp(
            kind="task_finished", operator_id=self.task_info.operator_id,
            task_index=self.task_info.task_index))

    # -- source ---------------------------------------------------------

    async def _run_source(self) -> None:
        finish = await self.operator.run(self.ctx)
        # drain the source-side coalescer before any end-of-stream
        # marker: buffered fragments must precede the final watermark /
        # stop downstream (and must be emitted at all — their resume
        # positions are already recorded in source state)
        await self.operator.flush_pending(self.ctx)
        if finish == SourceFinishType.FINAL:
            # final watermark flushes all windows downstream
            await self.out_ctx.broadcast(Message.wm(Watermark.event_time(int(MAX_TIMESTAMP))))
            await self.out_ctx.broadcast(Message.end_of_data())
        elif finish == SourceFinishType.GRACEFUL:
            await self.out_ctx.broadcast(Message.stop())
        else:
            pass  # immediate: just exit

    async def poll_source_control(self) -> Optional[ControlMessage]:
        """Non-blocking control poll used by sources between batches.  Handles
        checkpoint barriers inline (sources are where barriers enter the
        graph); returns Stop messages to the source loop."""
        try:
            cm: ControlMessage = self.control_rx.get_nowait()
        except asyncio.QueueEmpty:
            return None
        if cm.kind == "checkpoint":
            # source-side coalescer ordering: payloads buffered at the
            # source boundary carry resume positions the snapshot below
            # records — they must reach downstream BEFORE the barrier
            await self.operator.flush_pending(self.ctx)
            await self.run_checkpoint(cm.barrier)
            if cm.barrier.then_stop:
                # checkpoint-then-stop (arroyo-types lib.rs:746): the source
                # must stop producing after snapshotting
                return ControlMessage.stop(StopMode.IMMEDIATE)
            return cm
        if cm.kind == "commit":
            await self.operator.handle_commit(cm.epoch, self.ctx)
            return cm
        if cm.kind == "load_compacted":
            await self.operator.handle_load_compacted(cm.compacted, self.ctx)
            return cm
        return cm  # stop etc: source loop decides

    # -- processor -------------------------------------------------------

    async def _run_processor(self) -> None:
        for i, (side, q) in enumerate(self.inputs):
            pump = _Pump(i, side, q, self.merged)
            pump.task = asyncio.ensure_future(pump.run())
            self.pumps.append(pump)

        ended = 0
        stop_mode: Optional[StopMode] = None
        n_inputs = len(self.inputs)
        then_stop = False
        pending_barriers: Dict[int, CheckpointBarrier] = {}
        # persistent futures: recreated only after completion (hot loop —
        # avoids two ensure_future + one cancel per message)
        get_merged: Optional[asyncio.Future] = None
        get_control: Optional[asyncio.Future] = None
        metrics = self.ctx.metrics
        coal = self._make_coalescer()
        san = self.sanitizer
        tid = self.task_info.task_id
        prof = self._prof
        op_id = self.task_info.operator_id
        try:
            while ended < n_inputs:
                if get_merged is None or get_merged.done():
                    get_merged = asyncio.ensure_future(self.merged.get())
                if get_control is None or get_control.done():
                    get_control = asyncio.ensure_future(self.control_rx.get())
                timeout = None
                if coal is not None and coal.pending:
                    # bounded linger: wake up to flush even if no more
                    # input arrives
                    timeout = max(coal.deadline - _time.monotonic(), 0.0)
                wait_t0 = _time.perf_counter()
                done, _ = await asyncio.wait(
                    [get_merged, get_control],
                    return_when=asyncio.FIRST_COMPLETED, timeout=timeout)
                if metrics is not None or prof is not None:
                    # time this loop sat waiting for input (starvation —
                    # the upstream-is-slow half of backpressure analysis)
                    waited = _time.perf_counter() - wait_t0
                    if metrics is not None:
                        metrics.queue_wait.observe(waited)
                    if prof is not None:
                        # a wait bounded by the coalescer's linger
                        # deadline is latency the coalescer added, not
                        # upstream starvation — attribute it apart
                        prof.add(op_id, "coalesce_wait" if timeout
                                 is not None else "queue_wait",
                                 waited, wait=True)
                if (coal is not None and coal.pending
                        and _time.monotonic() >= coal.deadline):
                    # linger expired — flush whether or not new input
                    # arrived (a continuous sub-target trickle must not
                    # defer the flush until the size target is reached)
                    for cside, cbatch in coal.flush_all():
                        await self._process_record(cbatch, cside)
                if not done:
                    continue
                if get_control in done:
                    # arroyolint: disable=async-blocking -- future is in asyncio.wait's done set; .result() cannot block
                    cm = get_control.result()
                    if cm.kind == "commit":
                        await self.operator.handle_commit(cm.epoch, self.ctx)
                    elif cm.kind == "load_compacted":
                        await self.operator.handle_load_compacted(
                            cm.compacted, self.ctx)
                    elif cm.kind == "stop" and cm.stop_mode == StopMode.IMMEDIATE:
                        return
                if get_merged not in done:
                    continue
                # arroyolint: disable=async-blocking -- future is in asyncio.wait's done set; .result() cannot block
                idx, side, msg = get_merged.result()

                if msg.kind == MessageKind.RECORD:
                    if san is not None:
                        san.on_record((tid, idx), msg.batch)
                        san.on_record_during_alignment(tid, idx,
                                                       self.ctx.counter)
                    if metrics is not None:
                        metrics.messages_recv.inc(len(msg.batch))
                    if coal is not None:
                        for cside, cbatch in coal.add(side, msg.batch):
                            await self._process_record(cbatch, cside)
                    else:
                        await self._process_record(msg.batch, side)
                elif msg.kind == MessageKind.WATERMARK:
                    # buffered records arrived BEFORE this watermark on
                    # their channels: flush so they are never reordered
                    # past it (a window could otherwise fire without them)
                    if coal is not None and coal.pending:
                        for cside, cbatch in coal.flush_all():
                            await self._process_record(cbatch, cside)
                    if san is not None:
                        san.before_control(tid, "watermark", coal)
                        san.on_watermark((tid, idx), msg.watermark)
                    advanced = self.ctx.observe_watermark(idx, msg.watermark)
                    if advanced is not None:
                        await self._advance_watermark(advanced)
                    elif (msg.watermark.is_idle
                          and self.ctx.watermarks.all_idle()):
                        # a fire's tail still in flight goes before it
                        await self.operator.settle(self.ctx)
                        await self.out_ctx.broadcast(
                            Message.wm(Watermark.idle()))
                elif msg.kind == MessageKind.BARRIER:
                    # same ordering rule as watermarks: pre-barrier
                    # records must be in operator state before snapshot
                    if coal is not None and coal.pending:
                        for cside, cbatch in coal.flush_all():
                            await self._process_record(cbatch, cside)
                    b = msg.barrier
                    if san is not None:
                        san.before_control(tid, "barrier", coal)
                        san.on_barrier(tid, idx, b.epoch)
                    pending_barriers[b.epoch] = b
                    self._align_start.setdefault(b.epoch, tracing.now_us())
                    await self._report_event(b, CheckpointEventType.STARTED_ALIGNMENT)
                    if self.ctx.counter.observe(idx, b.epoch):
                        del pending_barriers[b.epoch]
                        await self.run_checkpoint(b)
                        for p in self.pumps:
                            p.resume.set()
                        if b.then_stop:
                            then_stop = True
                            break
                elif msg.is_end:
                    if coal is not None and coal.pending:
                        for cside, cbatch in coal.flush_all():
                            await self._process_record(cbatch, cside)
                    if san is not None:
                        san.before_control(tid, "end", coal)
                    ended += 1
                    if msg.kind == MessageKind.STOP:
                        stop_mode = StopMode.GRACEFUL
                    # a finished input can't deliver barriers: re-check
                    # alignment for epochs already in flight
                    for epoch in self.ctx.counter.mark_closed(idx):
                        b = pending_barriers.pop(epoch, None)
                        if b is not None:
                            await self.run_checkpoint(b)
                            for p in self.pumps:
                                p.resume.set()
                            if b.then_stop:
                                then_stop = True
                    if then_stop:
                        break
        finally:
            for f in (get_merged, get_control):
                if f is not None and not f.done():
                    f.cancel()
            for p in self.pumps:
                if p.task is not None:
                    p.task.cancel()
            # unblock upstreams possibly parked on a full queue (matters on
            # immediate stop, where this task exits while producers still run)
            for _, q in self.inputs:
                while not q.empty():
                    try:
                        q.get_nowait()
                    except asyncio.QueueEmpty:
                        break

        await self._await_pending_commit()
        await self.operator.on_close(self.ctx)
        if then_stop or stop_mode is not None:
            await self.out_ctx.broadcast(Message.stop())
        else:
            await self.out_ctx.broadcast(Message.end_of_data())

    def _make_coalescer(self):
        """Input-side adaptive micro-batch coalescer (see engine/
        coalesce.py); None when disabled via ARROYO_COALESCE=0."""
        from ..config import config
        from .coalesce import BatchCoalescer, coalescing_enabled

        if not coalescing_enabled():
            return None
        cfg = config()
        target = cfg.coalesce_target or cfg.target_batch_size
        hist = (self.ctx.metrics.coalesce_batches
                if self.ctx.metrics is not None else None)
        return BatchCoalescer(target, cfg.coalesce_linger_micros / 1e6,
                              hist, prof=self._prof,
                              prof_op=self.task_info.operator_id)

    async def _process_record(self, batch, side: int) -> None:
        """Run one (possibly coalesced) record batch through the
        operator with the task-level flight-recorder observations —
        unless the operator attributes per-member metrics itself
        (ChainedOperator)."""
        lat = self._lat
        if lat is not None:
            from ..obs import latency as _latency

            if self._lat_terminal and batch.lat_stamp is not None:
                # sink boundary: a sampled stamp becomes one
                # emit-minus-ingest observation
                lat.observe_sink(self.task_info, batch.lat_stamp)
            # park the input stamp for the duration of process_batch so
            # Context.collect re-attaches it to operator-built batches
            # (chain tails included — each member's Context reads it)
            _latency.set_current(batch.lat_stamp)
        try:
            await self._process_record_inner(batch, side)
        finally:
            if lat is not None:
                _latency.set_current(None)

    async def _process_record_inner(self, batch, side: int) -> None:
        metrics = self.ctx.metrics
        if metrics is None or self.operator.own_batch_metrics:
            # a ChainedOperator opens its own per-member `proc` phases
            await self.operator.process_batch(batch, self.ctx, side)
            return
        prof = self._prof
        if len(batch):
            # event-time lag at this operator: processing wall clock vs
            # the freshest event in the batch.  Sentinels are excluded by
            # testing the timestamp itself (unset/MIN and final-flush
            # MAX), not by bounding the lag — a historical replay's
            # months-of-backlog lag is exactly the signal the histogram
            # exists to carry
            ts = int(np.max(batch.timestamp))
            if 0 < ts < int(MAX_TIMESTAMP) - 1:
                metrics.event_time_lag.observe(
                    max((now_micros() - ts) / 1e6, 0.0))
        frame = (prof.begin(self.task_info.operator_id, "proc")
                 if prof is not None else None)
        t0 = _time.perf_counter()
        try:
            await self.operator.process_batch(batch, self.ctx, side)
        finally:
            if frame is not None:
                prof.end(frame)
        metrics.batch_latency.observe(_time.perf_counter() - t0)

    async def _await_pending_commit(self, timeout: float = 30.0) -> None:
        """A two-phase sink whose pre-commits were sealed by the final
        (possibly then_stop) checkpoint must not exit before the controller's
        Commit arrives — otherwise the last epoch's output is never
        finalized (the reference parks sink tasks until Commit,
        job_controller/mod.rs:326-371)."""
        has_pending = getattr(self.operator, "has_pending_commits", None)
        if has_pending is None or not has_pending(self.ctx):
            return
        try:
            while True:
                cm = await asyncio.wait_for(self.control_rx.get(),
                                            timeout=timeout)
                if cm.kind == "commit":
                    await self.operator.handle_commit(cm.epoch, self.ctx)
                    if not has_pending(self.ctx):
                        return
                elif cm.kind == "stop" and cm.stop_mode == StopMode.IMMEDIATE:
                    # abandon the wait: pre-commits re-commit on restore
                    return
        except asyncio.TimeoutError:
            logger.warning(
                "task %s closed with uncommitted pre-commits (no Commit "
                "within %.0fs); they will be re-committed on restore",
                self.task_info.task_id, timeout)

    async def _advance_watermark(self, wm: int) -> None:
        if (self.ctx.metrics is not None
                and 0 < wm < int(MAX_TIMESTAMP) - 1):
            # watermark lag at this operator: wall clock vs its (newly
            # advanced) input watermark; the MIN/unset and final-flush
            # MAX sentinels are not real event times, but an arbitrarily
            # large replay lag is
            self.ctx.metrics.watermark_lag.observe(
                max((now_micros() - wm) / 1e6, 0.0))
        # fire expired event-time timers first (macro lib.rs:738-753)
        prof = self._prof
        frame = (prof.begin(self.task_info.operator_id, "watermark")
                 if prof is not None else None)
        try:
            for time, key, payload in self.ctx.timers.fire(wm):
                await self.operator.handle_timer(time, key, payload, self.ctx)
            await self.operator.handle_watermark(wm, self.ctx)
        finally:
            if frame is not None:
                prof.end(frame)

    # -- checkpoint (macro lib.rs:706-736) -------------------------------

    async def run_checkpoint(self, barrier: CheckpointBarrier) -> None:
        tid = self.task_info.task_id
        align_start = self._align_start.pop(barrier.epoch, None)
        if align_start is not None:
            align_us = tracing.now_us() - align_start
            tracing.record_span("barrier.align", "checkpoint", align_start,
                                align_us, tid=tid,
                                args={"epoch": barrier.epoch})
            if self._lat is not None:
                # critical path: records queued behind this alignment
                # waited exactly this long (the profiler has no phase
                # for it — pumps park outside any frame)
                self._lat.note_stage("barrier_align", align_us / 1e6)
        await self._report_event(barrier, CheckpointEventType.STARTED_CHECKPOINTING)
        # snapshot state (per member for chained operators — the
        # controller's epoch tracker expects one completion per logical
        # (operator, subtask), and per-member metadata keeps chained
        # checkpoints restorable un-chained and vice versa)
        prof = self._prof
        frame = (prof.begin(self.task_info.operator_id, "checkpoint")
                 if prof is not None else None)
        try:
            metadatas = await self.operator.checkpoint_state(barrier,
                                                             self.ctx)
        finally:
            if frame is not None:
                prof.end(frame)
        if self.sanitizer is not None:
            # completeness: exactly one completion per distinct
            # (member, subtask) per epoch — a duplicate means two
            # snapshots raced for the same slot
            for md in metadatas:
                self.sanitizer.on_checkpoint_completed(
                    md.operator_id, md.subtask_index, md.epoch)
        await self._report_event(barrier, CheckpointEventType.FINISHED_SYNC)
        for metadata in metadatas:
            await self.ctx.report(ControlResp(
                kind="checkpoint_completed",
                operator_id=metadata.operator_id,
                task_index=metadata.subtask_index,
                subtask_metadata=metadata))
        # rebroadcast barrier downstream
        await self.out_ctx.broadcast(Message.barrier_msg(barrier))

    async def _report_event(self, b: CheckpointBarrier,
                            et: CheckpointEventType) -> None:
        await self.ctx.report(ControlResp(
            kind="checkpoint_event",
            operator_id=self.task_info.operator_id,
            task_index=self.task_info.task_index,
            checkpoint_event=CheckpointEvent(
                b.epoch, self.task_info.operator_id,
                self.task_info.task_index, now_micros(), et)))
