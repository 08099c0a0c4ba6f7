"""In-memory vec source/sink for unit tests (plays the role the reference's
test harness queues play, engine.rs:316-343)."""

from __future__ import annotations

import asyncio
from typing import Any, Dict, List, Optional

import numpy as np

from ..engine.context import Context
from ..engine.operator import Operator, SourceFinishType, SourceOperator
from ..obs import perf
from ..types import Batch
from .registry import ConnectorMeta, register_connector

_SINKS: Dict[str, List[Batch]] = {}
_SINK_ARRIVALS: Dict[str, List[float]] = {}


def sink_output(name: str) -> List[Batch]:
    return _SINKS.setdefault(name, [])


def sink_arrivals(name: str) -> List[float]:
    """Wallclock (time.monotonic — same clock the rate-limited sources
    pace on) arrival time of each sink batch: the measurement end of the
    bench's end-to-end latency probe."""
    return _SINK_ARRIVALS.setdefault(name, [])


def clear_sink(name: str) -> None:
    _SINKS.pop(name, None)
    _SINK_ARRIVALS.pop(name, None)


class MemorySource(SourceOperator):
    """Emits a preloaded list of batches, then finishes."""

    def __init__(self, cfg: Dict[str, Any]):
        super().__init__("memory_source")
        self.batches: List[Batch] = cfg.get("batches", [])

    async def run(self, ctx: Context) -> SourceFinishType:
        if ctx.task_info.task_index != 0:
            return SourceFinishType.FINAL  # single-reader source
        runner = getattr(ctx, "_runner", None)
        from ..obs import latency as _latency
        for b in self.batches:
            _latency.maybe_stamp(ctx.task_info.operator_id, b)
            await ctx.collect(b)
            if runner is not None:
                cm = await runner.poll_source_control()
                if cm is not None and cm.kind == "stop":
                    return SourceFinishType.GRACEFUL
            await asyncio.sleep(0)
        return SourceFinishType.FINAL


class MemorySink(Operator):
    def __init__(self, cfg: Dict[str, Any]):
        super().__init__("memory_sink")
        self.name = cfg.get("name", "default")

    async def process_batch(self, batch: Batch, ctx: Context, side: int = 0) -> None:
        import time

        perf.count("sink_rows", len(batch))
        sink_output(self.name).append(batch)
        sink_arrivals(self.name).append(time.monotonic())


register_connector(ConnectorMeta(
    name="memory",
    description="in-memory batches source/sink for tests",
    source_factory=MemorySource,
    sink_factory=MemorySink,
))
