"""Physical operator base classes.

The reference generates each operator's runtime loop with proc-macros
(``#[process_fn]``/``#[source_fn]``/``#[co_process_fn]``,
/root/reference/arroyo-macro/src/lib.rs:292-371); hooks like
``on_start/on_close/handle_timer/handle_watermark/handle_commit/tables``
(lib.rs:763-822) become overridable methods here, and a single generic
:class:`~arroyo_tpu.engine.task.TaskRunner` replaces the generated loops.

Operators process whole columnar batches; hot paths are jitted JAX functions
the operator owns."""

from __future__ import annotations

import asyncio
from enum import Enum
from typing import Any, Dict, List, Optional

from ..state.tables import TableDescriptor
from ..types import Batch, CheckpointBarrier, ControlMessage
from .context import Context


class SourceFinishType(Enum):
    """SourceFinishType (arroyo-worker/src/lib.rs): how a source loop ended."""

    FINAL = "final"  # emit final watermark + EndOfData
    GRACEFUL = "graceful"  # stop requested; checkpoint state is current
    IMMEDIATE = "immediate"


class Operator:
    """Base for single-input (and generic) operators."""

    # True when the operator records its own per-batch lag/latency
    # metrics (ChainedOperator attributes them per member); the
    # TaskRunner then skips its task-level observation to avoid
    # double-counting.
    own_batch_metrics = False

    # arroyosan runtime sanitizer (analysis/sanitizer.py); the
    # TaskRunner installs the engine's instance here, None when
    # ARROYO_SANITIZE is off — hook sites guard on `is not None`
    sanitizer: Optional[Any] = None

    def __init__(self, name: str):
        self.name = name

    def tables(self) -> List[TableDescriptor]:
        return []

    async def open(self, ctx: Context) -> None:
        """Task startup: register state tables, restore persisted timers
        (reserved table '[' — arroyo-worker/src/lib.rs:152), then
        ``on_start``.  ChainedOperator overrides to open every member
        against its own per-member context."""
        for desc in self.tables():
            ctx.state.register(desc)
        timer_table = ctx.state.get_global_keyed_state("[", "timers")
        saved_timers = timer_table.get("timers")
        if saved_timers:
            ctx.timers.restore(saved_timers)
        await self.on_start(ctx)

    async def checkpoint_state(self, barrier: CheckpointBarrier,
                               ctx: Context) -> List[Any]:
        """Snapshot this operator's state at a barrier; returns the
        ``SubtaskCheckpointMetadata`` list to report (one entry here; a
        ChainedOperator returns one per member so chained checkpoints
        stay restorable un-chained and vice versa)."""
        from ..obs import tracing

        tid = ctx.task_info.task_id
        with tracing.span("checkpoint.pre", "checkpoint", tid=tid,
                          args={"epoch": barrier.epoch}):
            await self.pre_checkpoint(barrier, ctx)
        ctx.state.get_global_keyed_state("[").insert(
            "timers", ctx.timers.snapshot())
        with tracing.span("checkpoint.sync", "checkpoint", tid=tid,
                          args={"epoch": barrier.epoch}):
            metadata = ctx.state.checkpoint(barrier.epoch,
                                            ctx.last_watermark)
        if ctx.metrics is not None:
            ctx.metrics.checkpoint_duration.observe(max(
                (metadata.finish_time - metadata.start_time) / 1e6, 0.0))
            ctx.metrics.checkpoint_bytes.observe(metadata.bytes)
        return [metadata]

    async def on_start(self, ctx: Context) -> None:
        pass

    async def process_batch(self, batch: Batch, ctx: Context, side: int = 0) -> None:
        raise NotImplementedError

    async def handle_timer(self, time: int, key: Any, payload: Any,
                           ctx: Context) -> None:
        pass

    async def handle_watermark(self, watermark: int, ctx: Context) -> None:
        """Called when the combined input watermark advances (after timers
        fire).  Default: forward it downstream.  Overriders that hold back or
        transform the watermark are responsible for their own forwarding."""
        from ..types import Message, Watermark

        await ctx.broadcast(Message.wm(Watermark.event_time(watermark)))

    async def pre_checkpoint(self, barrier: CheckpointBarrier, ctx: Context) -> None:
        """Flush any state living outside registered tables into them; called
        right before the state store snapshot."""
        pass

    async def settle(self, ctx: Context) -> None:
        """Await what this operator started beside its serial path and
        has not yet sent downstream (a window fire's tail,
        ``BinAggOperator.handle_watermark``); an exception raised there is
        raised here.  The operator calls it wherever order asks for it
        (the next fire, a barrier, a commit, the close); the runner calls it
        before it forwards past the operator a message the operator does
        not handle itself (an idle watermark)."""
        pass

    def abandon(self) -> None:
        """The task ends without ``on_close`` (a failure, an immediate
        stop, a cancel): drop what ``settle`` would have awaited.  Called
        by the runner as its last act."""
        pass

    async def handle_commit(self, epoch: int, ctx: Context) -> None:
        """Second phase of two-phase commit (sinks only)."""
        pass

    async def handle_load_compacted(self, payload: Any, ctx: Context) -> None:
        """Compaction hot-swap notice (ControlMessage::LoadCompacted): the
        operator's checkpoint files were merged into a compacted generation.
        Live state is in memory/HBM, so the default is a no-op; operators
        that lazily page state from checkpoint files override this."""
        pass

    async def on_close(self, ctx: Context) -> None:
        """Called when all inputs have finished, before EndOfData propagates."""
        pass


class SourceOperator(Operator):
    """Base for sources: drives its own loop instead of reacting to inputs
    (``#[source_fn]``, arroyo-macro/src/lib.rs:292-316)."""

    # source-side coalescer (engine/coalesce.py SourceBatcher): None
    # unless the connector installed one via make_batcher
    _batcher: Optional[Any] = None

    async def run(self, ctx: Context) -> SourceFinishType:
        raise NotImplementedError

    def make_batcher(self, ctx: Context, decode: Any,
                     target: int = 0, batch_always: bool = False) -> Any:
        """Install a :class:`~arroyo_tpu.engine.coalesce.SourceBatcher`
        assembling target-size batches at the source boundary.  The
        TaskRunner drains it via ``flush_pending`` before checkpoint
        barriers and stops, so connectors may record resume positions
        at fetch time without breaking exactly-once.  ``batch_always``
        is for connectors that assembled target-size batches themselves
        before the boundary batcher existed: their batching survives
        ``ARROYO_COALESCE=0`` (only the linger is escape-hatched)."""
        from .coalesce import SourceBatcher

        self._batcher = SourceBatcher(
            ctx, decode, target, prof_op=ctx.task_info.operator_id,
            batch_always=batch_always)
        return self._batcher

    async def flush_pending(self, ctx: Context) -> None:
        """Emit any payloads buffered at the source boundary.  Called by
        the TaskRunner before a checkpoint snapshots source state and
        when the source loop ends — buffered rows are always downstream
        of the state that claims them."""
        if self._batcher is not None:
            await self._batcher.flush()

    async def process_batch(self, batch: Batch, ctx: Context, side: int = 0) -> None:
        raise RuntimeError("sources have no inputs")

    # Helper: sources call this between emissions to service control messages
    # (checkpoint barriers are *injected at sources*, §3.3 of SURVEY.md).
    async def check_control(self, ctx: Context, runner) -> Optional[ControlMessage]:
        return await runner.poll_source_control()
