"""ChainedOperator — N fused operators executed by one TaskRunner.

The engine's chaining pass (graph/chaining.py) proves a linear run of
same-parallelism forward-edge operators; this class executes that run
inside a single task: a batch flows member-to-member as a **synchronous
await chain** — no intermediate asyncio queues, no Batch
re-materialization, one watermark/barrier alignment per chain.

Identity survives fusion:

* each member keeps its own ``Context`` — its own ``StateStore`` (so
  checkpoint state tables keep per-member names and restores from
  un-chained checkpoints work), its own ``TimerHeap``, its own
  ``TaskMetrics`` (flight-recorder rollups still attribute
  kernel-seconds/lag/latency to individual members), and its own
  ``KernelAccumulator`` installed around that member's processing;
* ``checkpoint_state`` snapshots every member in chain order and
  returns one metadata entry per member, so the controller's epoch
  tracker sees exactly the per-(operator, subtask) completions it would
  see un-chained.

Where adjacent members are RECORD-returning expression kernels, their
column functions are composed into a **single jitted dispatch** (XLA
fuses them into one kernel), eliminating per-hop padding and dispatch
overhead entirely; composition is row-preserving (RECORD maps are 1:1),
so interior members' message counters stay exact.
``ARROYO_CHAIN_FUSE_EXPR=0`` disables only the jit composition while
keeping the queue-hop elimination.

**Ingest-spine fusion (this PR):** runs of elementwise members —
predicates, record/UDF projections, key_bys — execute as ONE host
step (`_SpineStep`): each member's column fn runs eagerly pinned to
the CPU backend (ops/expr.py ``CompiledExpr.eval_host``), with no
padding, no jit and **zero accelerator dispatches**.  The batch on
both sides of these members is host-resident by construction (sources
decode to numpy; window state pre-aggregates on host before its
scatter), so the per-member pad→dispatch→readback round trip was pure
envelope — Flare's argument applied to the ingest path.  Combined
with parallelism-1 shuffle chaining (graph/chaining.py), a
source→project→key_by→window pipeline becomes one task whose
per-batch work is a single Python step plus the window's (deferred,
coalesced) state scatter.  ``ARROYO_CHAIN_FUSE_INGEST=0`` restores
the jitted per-member path bit-for-bit; ``ARROYO_CHAIN_FUSE_EXPR=0``
(the PR 4 escape) disables BOTH fused forms — jit composition and the
spine — so one knob always yields plain per-member execution.
"""

from __future__ import annotations

import logging
import os
import time as _time
import weakref
from contextvars import ContextVar
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ..graph.logical import ColumnExpr, ExprReturnType
from ..obs import perf, profiler
from ..types import (
    Batch,
    CheckpointBarrier,
    Message,
    MessageKind,
    TaskInfo,
    Watermark,
    now_micros,
    MAX_TIMESTAMP,
)
from .context import Context
from .operator import Operator
from .operators_basic import ExpressionOperator, KeyByOperator, UdfOperator

logger = logging.getLogger(__name__)

# child-inclusive seconds of the ``_feed`` call under way in this asyncio
# task (a one-element list, None outside any): per task, because a window
# fire's tail feeds the members behind its aggregate beside the runner's
# own feed of the next batch
_LAT_CHILD: ContextVar[Optional[List[float]]] = ContextVar(
    "arroyo_chain_lat_child", default=None)


def ingest_fusion_enabled() -> bool:
    """``ARROYO_CHAIN_FUSE_INGEST=0`` disables host-spine fusion (the
    eager CPU-pinned evaluation of elementwise chain members), keeping
    the jitted per-member / composed-expr path."""
    return os.environ.get("ARROYO_CHAIN_FUSE_INGEST", "1") not in (
        "0", "off", "false")


class _ChainLink:
    """Collector stand-in for a non-tail member: ``collect`` feeds the
    next member synchronously, ``broadcast`` routes watermarks through
    the remaining members' watermark pipeline."""

    metrics = None  # Collector-duck attribute (Context reads it)

    def __init__(self, chain: "ChainedOperator", nxt: int):
        self.chain = chain
        self.nxt = nxt

    async def collect(self, batch: Batch) -> None:
        if len(batch) == 0:
            return  # parity with Collector.collect: empties never cross
        m = self.chain.ctxs[self.nxt - 1].metrics
        if m is not None:
            m.messages_sent.inc(len(batch))
        await self.chain._feed(self.nxt, batch)

    async def broadcast(self, msg: Message) -> None:
        await self.chain._control(self.nxt, msg)


def _fusible(op: Operator) -> bool:
    return (isinstance(op, ExpressionOperator)
            and op.return_type == ExprReturnType.RECORD)


# composed-fn cache keyed by the FIRST member's fn (weak) then the ids of
# the rest: logical expression fns persist across engine rebuilds (bench
# warm runs, restarts), so the composed closure — and with it the jit
# cache entry — must too
_FUSED_CACHE: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def _compose_exprs(exprs: List[ColumnExpr]) -> ColumnExpr:
    """One ColumnExpr running the members' fns back to back inside a
    single jit.  Timestamp rewrites propagate exactly as the unfused
    eval_record_expr chain would; host (string) columns bypass jit in
    both forms and re-attach once at the end."""
    fns = [e.fn for e in exprs]
    # key by the fn OBJECTS (strong refs, hashable) — ids would be
    # reused after gc and could silently serve a stale composition
    key = tuple(fns[1:])
    try:
        cached = _FUSED_CACHE.setdefault(fns[0], {})
    except TypeError:  # non-weakref-able callable
        cached = {}
    fused = cached.get(key)
    if fused is None:
        def fused(cols, _fns=tuple(fns)):
            cur = dict(cols)
            ts = cur["__timestamp"]
            for f in _fns:
                out = dict(f(cur))
                ts = out.pop("__timestamp", ts)
                cur = {"__timestamp": ts, **out}
            return cur  # always carries __timestamp (rewrites included)

        used = set()
        for e in exprs:
            ecols = getattr(e.fn, "used_cols", None)
            if ecols is None:
                used = None
                break
            used.update(ecols)
        if used is not None:
            fused.used_cols = used  # superset is safe: it only widens
            # the set of batch columns coerced into the jit
        cached[key] = fused
    name = "+".join(e.name for e in exprs)
    return ColumnExpr(name, fused, ExprReturnType.RECORD,
                      sql="; ".join(e.sql for e in exprs if e.sql))


def _spineable(op: Operator) -> bool:
    """Members the host spine can execute: pure elementwise transforms
    with no state, timers, broadcasts or side effects."""
    return isinstance(op, (ExpressionOperator, UdfOperator,
                           KeyByOperator))


class _SpineStep(Operator):
    """One fused execution step running a run of elementwise chain
    members (predicates / record exprs / UDFs / key_bys) eagerly on the
    host — semantics member-for-member identical to the unfused path
    (same column layouts, same row drops, same key hashes), with zero
    accelerator dispatches.  Does its own per-member recv/sent/lag
    accounting (``own_member_counts``) because predicates change the
    row count mid-run."""

    own_member_counts = True

    def __init__(self, chain: "ChainedOperator", idxs: List[int]):
        members = [chain.members[i] for i in idxs]
        super().__init__(
            "spine(" + "+".join(m.name for m in members) + ")")
        self.chain = chain
        self.idxs = idxs
        self._plan: List[Tuple[int, str, Operator]] = []
        for mi, op in zip(idxs, members):
            if isinstance(op, KeyByOperator):
                kind = "key"
            elif isinstance(op, UdfOperator):
                kind = "udf"
            elif op.return_type == ExprReturnType.PREDICATE:
                kind = "pred"
            elif op.return_type == ExprReturnType.RECORD:
                kind = "record"
            else:
                kind = "opt"  # OPTIONAL_RECORD: record + __valid select
            self._plan.append((mi, kind, op))

    def _observe(self, mi: int, batch: Batch) -> None:
        """Mirror ChainedOperator._feed's per-member bookkeeping."""
        m = self.chain.ctxs[mi].metrics
        if m is None:
            return
        n = len(batch)
        if mi != 0:
            # the head member's recv is counted by the runner
            m.messages_recv.inc(n)
        if n:
            ts = int(np.max(batch.timestamp))
            if 0 < ts < int(MAX_TIMESTAMP) - 1:
                m.event_time_lag.observe(
                    max((now_micros() - ts) / 1e6, 0.0))

    async def process_batch(self, batch: Batch, ctx: Context,
                            side: int = 0) -> None:
        from ..ops.expr import (eval_host_expr, eval_predicate,
                                eval_record_expr)

        b = batch
        last = self._plan[-1][0]
        for mi, kind, op in self._plan:
            self._observe(mi, b)
            if kind == "pred":
                mask = eval_predicate(op.compiled, b, host=True)
                if not mask.any():
                    return  # legacy predicate: empty results never emit
                b = b.select(mask)
            elif kind == "record":
                b = eval_record_expr(op.compiled, b, host=True)
            elif kind == "opt":
                b = eval_record_expr(op.compiled, b, host=True)
                if "__valid" in b.columns:
                    vm = b.columns.pop("__valid").astype(bool)
                    b = b.select(vm)
            elif kind == "udf":
                b = eval_host_expr(op.fn, b)
            else:  # key
                b = b.with_key(list(op.key_cols))
            if mi != last:
                m = self.chain.ctxs[mi].metrics
                if m is not None:
                    # interior sent = rows this member emitted; the last
                    # member's sent is counted by its collector (link or
                    # tail Collector), exactly as unfused
                    m.messages_sent.inc(len(b))
        if len(b):
            await ctx.collect(b)


class ChainedOperator(Operator):
    """Executes chain members in order inside one task (see module
    docstring).  ``bind(ctxs)`` must be called with one Context per
    member before the runner starts; ``ctxs[0]`` doubles as the
    runner's context and ``tail_ctx`` carries the real output
    Collector."""

    own_batch_metrics = True  # per-member lag/latency recorded here

    def __init__(self, infos: List[TaskInfo], members: List[Operator]):
        super().__init__(
            "chain(" + "->".join(op.name for op in members) + ")")
        assert len(infos) == len(members) >= 2
        self.infos = infos
        self.members = members
        self.ctxs: List[Context] = []
        self.tail_ctx: Optional[Context] = None
        self._accs: List[perf.KernelAccumulator] = []
        # execution steps: (exec_operator, member_indices, exec_ctx_idx)
        self._steps: List[Tuple[Operator, List[int], int]] = []
        self._step_by_start: Dict[int, Tuple[Operator, List[int], int]] = {}
        # latency observatory: when this chain ends the dataflow (tail
        # Collector has no outgoing edges), the feed into the tail
        # member is the sink boundary — observing there (not at chain
        # input) means a window fire inside the chain is measured at
        # its actual emission, watermark hold included
        self._lat: Optional[Any] = None
        self._lat_tail_start: Optional[int] = None

    # -- wiring ------------------------------------------------------------

    def make_link(self, member_index: int) -> _ChainLink:
        """The collector for member ``member_index`` (routes to the next
        member); the tail member uses the engine's real Collector."""
        return _ChainLink(self, member_index + 1)

    def bind(self, ctxs: List[Context]) -> None:
        assert len(ctxs) == len(self.members)
        self.ctxs = list(ctxs)
        self.tail_ctx = ctxs[-1]
        self._accs = [perf.KernelAccumulator(ti, c.metrics)
                      for ti, c in zip(self.infos, ctxs)]
        self._build_steps()
        from ..obs import latency as _latency

        self._lat = _latency.active()
        if (self._lat is not None
                and not self.tail_ctx.collector.edge_groups):
            self._lat_tail_start = self._steps[-1][1][0]

    def _build_steps(self) -> None:
        from ..ops.expr import _host_eval_device

        fuse = os.environ.get("ARROYO_CHAIN_FUSE_EXPR", "1") not in (
            "0", "off", "false")
        # FUSE_EXPR=0 is the "no fused execution of members at all"
        # escape: it must also force the spine off, or flipping the
        # documented knob would silently change nothing for spineable
        # members (they'd still run fused inside _SpineStep)
        spine = (fuse and ingest_fusion_enabled()
                 and _host_eval_device() is not None)
        self._steps = []
        i = 0
        while i < len(self.members):
            j = i
            if spine and _spineable(self.members[i]):
                # host spine: a maximal run of elementwise members runs
                # as one eager host step — no per-member dispatch at all
                while (j + 1 < len(self.members)
                       and _spineable(self.members[j + 1])):
                    j += 1
                step_op: Operator = _SpineStep(self, list(range(i, j + 1)))
            elif fuse and _fusible(self.members[i]):
                while (j + 1 < len(self.members)
                       and _fusible(self.members[j + 1])):
                    j += 1
                if j > i:
                    fused = _compose_exprs(
                        [self.members[k].expr for k in range(i, j + 1)])
                    step_op = ExpressionOperator(fused.name, fused)
                else:
                    step_op = self.members[i]
            else:
                step_op = self.members[i]
            # execute against the LAST covered member's context so
            # collect() routes to the member after the fused run
            self._steps.append((step_op, list(range(i, j + 1)), j))
            i = j + 1
        self._step_by_start = {step[1][0]: step for step in self._steps}

    # -- lifecycle ---------------------------------------------------------

    async def open(self, ctx: Context) -> None:
        for member, mctx in zip(self.members, self.ctxs):
            await Operator.open(member, mctx)

    async def on_close(self, ctx: Context) -> None:
        for member, mctx in zip(self.members, self.ctxs):
            await member.on_close(mctx)

    async def settle(self, ctx: Context) -> None:
        for member, mctx in zip(self.members, self.ctxs):
            await member.settle(mctx)

    def abandon(self) -> None:
        for member in self.members:
            member.abandon()

    async def checkpoint_state(self, barrier: CheckpointBarrier,
                               ctx: Context) -> List[Any]:
        metas: List[Any] = []
        for member, mctx in zip(self.members, self.ctxs):
            metas.extend(await member.checkpoint_state(barrier, mctx))
        return metas

    async def handle_commit(self, epoch: int, ctx: Context) -> None:
        for member, mctx in zip(self.members, self.ctxs):
            await member.handle_commit(epoch, mctx)

    async def handle_load_compacted(self, payload: Any,
                                    ctx: Context) -> None:
        target = (payload.get("operator_id")
                  if isinstance(payload, dict) else None)
        for ti, member, mctx in zip(self.infos, self.members, self.ctxs):
            if not target or ti.operator_id == target:
                await member.handle_load_compacted(payload, mctx)

    # -- dataflow ----------------------------------------------------------

    async def process_batch(self, batch: Batch, ctx: Context,
                            side: int = 0) -> None:
        await self._feed(0, batch, side)

    async def _feed(self, start: int, batch: Batch, side: int = 0) -> None:
        step_op, idxs, ectx_idx = self._step_by_start[start]
        if (self._lat_tail_start is not None
                and start == self._lat_tail_start
                and batch.lat_stamp is not None):
            # sink boundary of a terminal chain: one emit-minus-ingest
            # observation per sampled batch reaching the tail member
            self._lat.observe_sink(self.infos[-1], batch.lat_stamp)
        if self.sanitizer is not None and start > 0:
            # interior chain edges keep the same per-edge schema
            # stability contract as real queues (the head edge is
            # checked by the runner)
            self.sanitizer.on_record(
                (self.infos[start].task_id, "chain"), batch)
        n = len(batch)
        if not getattr(step_op, "own_member_counts", False):
            # a _SpineStep counts per member itself (predicates change
            # the row count member to member)
            ts = int(np.max(batch.timestamp)) if n else 0
            now = now_micros()
            for mi in idxs:
                m = self.ctxs[mi].metrics
                if m is None:
                    continue
                if mi != 0:
                    # the head member's recv is counted by the runner;
                    # every other member counts here (fused interiors
                    # included — RECORD exprs are 1:1, so the
                    # pass-through count is exact)
                    m.messages_recv.inc(n)
                if 0 < ts < int(MAX_TIMESTAMP) - 1:
                    m.event_time_lag.observe(max((now - ts) / 1e6, 0.0))
            for mi in idxs[:-1]:
                m = self.ctxs[mi].metrics
                if m is not None:
                    m.messages_sent.inc(n)
        # exclusive latency: inclusive minus time spent in downstream
        # members this call recursed into (collect is synchronous)
        parent, child = _LAT_CHILD.get(), [0.0]
        lat_token = _LAT_CHILD.set(child)
        token = perf.set_active_task(self._accs[idxs[0]])
        prof = profiler.active()
        frame = (prof.begin(self.infos[idxs[0]].operator_id, "proc")
                 if prof is not None else None)
        t0 = _time.perf_counter()
        try:
            await step_op.process_batch(
                batch, self.ctxs[ectx_idx], side if start == 0 else 0)
        finally:
            if frame is not None:
                # nested member frames subtract automatically, so each
                # member's `proc` phase is exclusive like its latency
                prof.end(frame)
            perf.reset_active_task(token)
            inclusive = _time.perf_counter() - t0
            _LAT_CHILD.reset(lat_token)
            if parent is not None:
                parent[0] += inclusive
            m0 = self.ctxs[idxs[0]].metrics
            if m0 is not None:
                m0.batch_latency.observe(max(inclusive - child[0], 0.0))

    # -- watermarks / timers ----------------------------------------------

    async def handle_timer(self, time: int, key: Any, payload: Any,
                           ctx: Context) -> None:
        # the runner fires the HEAD member's timer heap (ctx is ctxs[0])
        await self.members[0].handle_timer(time, key, payload,
                                           self.ctxs[0])

    async def handle_watermark(self, watermark: int, ctx: Context) -> None:
        # head member's watermark handling; its default broadcast rides
        # the chain link into _control -> the next member, and so on
        # until the tail broadcasts downstream for real
        await self.members[0].handle_watermark(watermark, self.ctxs[0])

    async def _control(self, i: int, msg: Message) -> None:
        if msg.kind == MessageKind.WATERMARK:
            await self._member_watermark(i, msg.watermark)
            return
        # members only ever broadcast watermarks mid-stream; anything
        # else (defensive) goes straight downstream
        logger.debug("chain %s: member broadcast of %s forwarded to tail",
                     self.name, msg.kind)
        await self.tail_ctx.broadcast(msg)

    async def _member_watermark(self, i: int, wm: Watermark) -> None:
        """The per-member slice of TaskRunner's watermark advancement:
        observe, fire that member's timers, then its handle_watermark
        (whose default broadcast continues down the chain)."""
        mctx = self.ctxs[i]
        if self.sanitizer is not None:
            self.sanitizer.on_watermark((self.infos[i].task_id, "chain"),
                                        wm)
        advanced = mctx.observe_watermark(0, wm)
        if advanced is not None:
            if (mctx.metrics is not None
                    and 0 < advanced < int(MAX_TIMESTAMP) - 1):
                mctx.metrics.watermark_lag.observe(
                    max((now_micros() - advanced) / 1e6, 0.0))
            prof = profiler.active()
            frame = (prof.begin(self.infos[i].operator_id, "watermark")
                     if prof is not None else None)
            try:
                for t, key, payload in mctx.timers.fire(advanced):
                    await self.members[i].handle_timer(t, key, payload, mctx)
                await self.members[i].handle_watermark(advanced, mctx)
            finally:
                if frame is not None:
                    prof.end(frame)
        elif wm.is_idle and mctx.watermarks.all_idle():
            # what the member still owes downstream goes before the idle
            await self.members[i].settle(mctx)
            await mctx.broadcast(Message.wm(Watermark.idle()))
