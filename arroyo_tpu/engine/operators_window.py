"""Windowed / keyed-state physical operators — the device-state heart of the
engine.

Maps the reference's window operator suite onto batched device kernels:

* :class:`BinAggOperator` — Operator::SlidingWindowAggregator /
  TumblingWindowAggregator (aggregating_window.rs:14-258,
  tumbling_aggregating_window.rs): per-(key, bin) pre-aggregates in HBM via
  :class:`~arroyo_tpu.ops.keyed_bins.KeyedBinState`, panes emitted on
  watermark advance by one device kernel over all pending panes.
* :class:`WindowOperator` — Operator::Window / KeyedWindowFunc
  (windows.rs:160-197): buffer rows, trigger at window end, segment-reduce on
  device; supports tumbling/sliding/instant windows, aggregate or flatten.
* :class:`SessionWindowOperator` — SessionWindowFunc (windows.rs:200-427):
  host-managed per-key gap-merged window sets (data-dependent merging stays
  on host, as the reference keeps it in KeyedState), aggregation on device.
* :class:`TumblingTopNOperator` — TumblingTopN (tumbling_top_n_window.rs);
  the fused SlidingAggregatingTopN lives as the ``top_n`` mode of
  :class:`BinAggOperator` (sliding_top_n_aggregating_window.rs).
* :class:`WindowJoinOperator` — Operator::WindowJoin (joins.rs:14-181):
  dual-sided buffers, sorted-merge join per fired window.
* :class:`JoinWithExpirationOperator` — JoinWithExpiration
  (join_with_expiration.rs): TTL'd buffers, inner/left/right/full with
  updating output.
* :class:`NonWindowAggOperator` — NonWindowAggregator
  (updating_aggregate.rs): running per-key aggregates with expiration,
  emitting updating (create/update) rows.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import asyncio
import logging
import time as _time

import numpy as np

from ..graph.logical import (
    AggKind,
    AggSpec,
    InstantWindow,
    JoinType,
    LogicalOperator,
    OpKind,
    SessionWindow,
    SlidingWindow,
    TumblingWindow,
)
from ..ops.expr import CompiledExpr, eval_record_expr
from ..ops.join import join_pairs
from ..ops.keyed_bins import KeyedBinState
from ..ops.segment import segment_aggregate
from ..state.tables import DeviceTable, TableDescriptor, TableType
from ..types import Batch, Message, UpdateOp, UPDATE_OP_COLUMN, Watermark
from .build import register_builder
from .context import Context
from .operator import Operator

logger = logging.getLogger(__name__)

MAX_SESSION_SIZE_MICROS = 24 * 3600 * 1_000_000  # windows.rs:17


def _window_params(typ) -> Tuple[int, int]:
    """(width, slide) micros for uniform window types."""
    if isinstance(typ, TumblingWindow):
        return typ.width_micros, typ.width_micros
    if isinstance(typ, SlidingWindow):
        return typ.width_micros, typ.slide_micros
    if isinstance(typ, InstantWindow):
        return 1, 1
    raise TypeError(f"not a uniform window: {typ}")


def _lat_track(pending: Optional[Tuple[int, float]], batch: Batch
               ) -> Optional[Tuple[int, float]]:
    """Latency-observatory pane inheritance, input side: fold one
    incoming batch's ingest stamp into the operator's pending
    ``(max_stamp, arrival_monotonic)``.  A fired pane inherits the MAX
    contributing stamp (the newest sampled record still waiting — the
    conservative bound on how fresh the pane's output can claim to be)."""
    if batch.lat_stamp is None:
        return pending
    stamp = (batch.lat_stamp if pending is None
             else max(pending[0], batch.lat_stamp))
    return (stamp, _time.monotonic())


def _lat_consume(pending: Optional[Tuple[int, float]]) -> Optional[int]:
    """Latency-observatory pane inheritance, fire side: consume the
    pending max-stamp.  Returns the stamp to attach to the fired batch
    and charges the ``watermark_hold`` critical-path stage with how
    long the sample sat in pane state waiting for the watermark."""
    if pending is None:
        return None
    from ..obs import latency as _latency

    lat = _latency.active()
    stamp, arrival = pending
    if lat is not None:
        lat.note_stage("watermark_hold",
                       max(_time.monotonic() - arrival, 0.0))
    return stamp


def _first_occurrence_cols(batch: Batch, uniq_keys: np.ndarray
                           ) -> Dict[str, np.ndarray]:
    """Key-column values for each unique key (first occurrence wins)."""
    if not batch.key_cols:
        return {}
    order = np.argsort(batch.key_hash, kind="stable")
    kh = batch.key_hash[order]
    _, first = np.unique(kh, return_index=True)
    rows = order[first]  # one row per unique key, aligned with sorted uniq
    return {c: batch.columns[c][rows] for c in batch.key_cols
            if c in batch.columns}


class _SlotKeyValues:
    """Host-side slot -> key-column-values store for bin-state operators."""

    def __init__(self) -> None:
        self.cols: Dict[str, np.ndarray] = {}
        self.size = 0

    def ensure(self, batch: Batch, slots: np.ndarray, prev_next: int,
               new_next: int) -> None:
        if new_next <= self.size and self.cols:
            return
        cap = max(new_next, 64)
        for c in list(self.cols):
            old = self.cols[c]
            if len(old) < cap:
                grown = np.empty(cap * 2, dtype=old.dtype)
                grown[:len(old)] = old
                self.cols[c] = grown
        for c in batch.key_cols:
            if c in batch.columns and c not in self.cols:
                self.cols[c] = np.empty(
                    cap * 2, dtype=batch.columns[c].dtype)
        new_mask = slots >= prev_next
        if new_mask.any():
            idx = new_mask.nonzero()[0]
            for c in batch.key_cols:
                if c in batch.columns:
                    self.cols[c][slots[idx]] = batch.columns[c][idx]
        self.size = max(self.size, new_next)

    def gather(self, slot_idx: np.ndarray) -> Dict[str, np.ndarray]:
        return {c: v[slot_idx] for c, v in self.cols.items()}

    def snapshot(self) -> Dict[str, np.ndarray]:
        return {f"kv_{c}": v[:self.size] for c, v in self.cols.items()} | {
            "kv_size": np.array([self.size])}

    def restore(self, arrays: Dict[str, np.ndarray]) -> None:
        self.size = int(arrays["kv_size"][0])
        for k, v in arrays.items():
            if k.startswith("kv_") and k != "kv_size":
                self.cols[k[3:]] = v.copy()


# what ``perf.run_offloaded`` calls the parts of a fire's two hops in the
# flight recorder: the pick-up and the way back share a name between the
# head and the tail (``args.part`` tells them apart); the tail's run is
# ``window.fire.d2h`` and the flatten, and has no span of its own
_HEAD_HOP = {"cat": "window", "queue": "window.fire.hop",
             "run": "window.fire.head.run",
             "resume": "window.fire.loop_wait"}
_TAIL_HOP = {"cat": "window", "queue": "window.fire.hop",
             "resume": "window.fire.loop_wait"}


class BinAggOperator(Operator):
    """Two-phase binned window aggregate over device state (sliding or
    tumbling; SURVEY kernel #2)."""

    def __init__(self, name: str, width_micros: int, slide_micros: int,
                 aggs: Tuple[AggSpec, ...], projection=None,
                 top_n: Optional[Tuple[Tuple[str, ...], str, int]] = None,
                 argmax_local: Optional[Tuple[str, str]] = None):
        super().__init__(name)
        from ..parallel.mesh_window import make_bin_state

        self.width = width_micros
        self.slide = slide_micros
        self.aggs = aggs
        # mesh-sharded state when >1 device is available (all_to_all re-key
        # over ICI instead of a host shuffle); single-device KeyedBinState
        # otherwise
        self.state = make_bin_state(aggs, slide_micros, width_micros)
        if argmax_local is not None and hasattr(self.state, "set_argmax_local"):
            # emission pre-filters to local per-pane argmax candidates
            # (sole consumer is a WindowArgmax stage — planner-proven)
            self.state.set_argmax_local(*argmax_local)
        self.keyvals = _SlotKeyValues()
        self.projection = (CompiledExpr(projection.name, projection.fn)
                           if projection else None)
        self.top_n = top_n  # (partition_cols, sort_column, max_elements)
        self._key_cols: Tuple[str, ...] = ()
        self._offload: Optional[bool] = None  # decided at first batch
        # latency-observatory pane inheritance: (max contributing ingest
        # stamp, monotonic arrival) pending until the next pane fire
        self._lat_pending: Optional[Tuple[int, float]] = None
        self._ledger_updates = 0  # throttles the pane_state_registry note
        # the last task started beside the serial path (handle_watermark):
        # a fire's tail, or a watermark's forward queued behind it;
        # awaited by ``settle``
        self._tail: Optional[asyncio.Future] = None
        # the executor half of the last batch's update, where transfers
        # block (``perf.Offloaded``); at most one, awaited by the next
        # hand-off, a grow, a firing head and ``settle``
        self._update = None

    def _offload_transfers(self) -> bool:
        """Run device update/emit in an executor thread on accelerators:
        host<->device transfers there block the caller, and off the
        event loop sibling operators' transfers overlap instead of
        serializing.  On the CPU backend transfers are free, so the
        thread hop is pure overhead."""
        if self._offload is None:
            import jax

            self._offload = jax.default_backend() != "cpu"
        return self._offload

    def tables(self) -> List[TableDescriptor]:
        return []  # registered as a device table in on_start

    async def on_start(self, ctx: Context) -> None:
        from ..ops.keyed_bins import filter_canonical_snapshot

        par = ctx.task_info.parallelism
        if par > 1 and hasattr(self.state, "set_route_shift"):
            # subtask key ranges consume the TOP hash bits; the mesh
            # must route on the bits below them or this subtask's whole
            # key slice funnels onto ~nk/parallelism devices.  Must run
            # before register_device: a restore re-shards by _shard_of.
            # The shift expression is the shared contract in
            # types.route_shift_for — shardcheck's static model uses the
            # SAME function and its wiring audit pins this call site.
            from ..types import route_shift_for

            self.state.set_route_shift(route_shift_for(par))

        def snap():
            out = self.state.snapshot() | self.keyvals.snapshot()
            if self._lat_pending is not None:
                # pending pane stamp survives checkpoint/restore so a
                # sampled record held in pane state at barrier time is
                # still measured after recovery (restart cost included)
                out["__lat_stamp"] = np.array([self._lat_pending[0]],
                                              np.int64)
            return out

        def restore(arrays, _kr=ctx.task_info.key_range):
            st = arrays.pop("__lat_stamp", None)
            if st is not None:
                self._lat_pending = (int(st[0]), _time.monotonic())
            # rescale re-partitioning: keep only the keys this subtask owns
            arrays = filter_canonical_snapshot(arrays, _kr)
            self.state.restore(arrays)
            self.keyvals.restore(arrays)

        ctx.state.register_device(
            TableDescriptor("a", TableType.DEVICE, "bin aggregates",
                            retention_micros=self.width),
            DeviceTable(snap, restore))
        if hasattr(self.state, "warm_fire"):  # not the mesh state's
            self.state.warm_fire()

    async def _run_state(self, fn, *args, span=None, span_args=None):
        """``fn(*args)`` of the state, in an executor thread where
        transfers block (``_offload_transfers``).  Safe to offload: the
        serial path awaits it, and a fire's tail touches its handle
        alone.  ``span`` / ``span_args`` are ``perf.run_offloaded``'s: a
        fire names the parts of its hops, the mesh state's update does
        not."""
        if self._offload_transfers():
            from ..obs import perf

            return await perf.run_offloaded(asyncio.get_running_loop(),
                                            fn, *args, span=span,
                                            span_args=span_args)
        return fn(*args)

    async def process_batch(self, batch: Batch, ctx: Context, side: int = 0) -> None:
        """A batch's update in two halves where the state has them
        (``KeyedBinState.admit`` and ``apply``).  The loop half settles
        what the next batch and a fire's check read: the bins, the
        directory, the key columns.  The executor half (the row mass,
        the reduce, the enqueue, at the bound the flush) is handed to
        the executor and left in flight while the next batch's loop half
        runs: at most one, in order, awaited by the next hand-off, by a
        loop half that may grow the planes, by a firing head and by
        ``settle``.  Where transfers are free it runs inline.  The mesh
        state's update is whole and awaited."""
        assert batch.key_hash is not None, f"{self.name} requires keyed input"
        from ..obs import perf

        if await self._tail_in_flight(ctx):
            perf.count("fire_overlap_batches")
        if self._update_in_flight():
            perf.count("update_overlap_batches")
        self._lat_pending = _lat_track(self._lat_pending, batch)
        self._key_cols = batch.key_cols
        state = self.state
        admit = getattr(state, "admit", None)  # not the mesh state's
        bins = None
        if admit is not None:
            bins = state.assign(batch.timestamp)
            if state.replaces_planes(len(batch), bins):
                await self._await_update()  # it holds the planes
        prev = state.next_slot
        slots = state._lookup_or_insert(batch.key_hash)
        self.keyvals.ensure(batch, slots, prev, state.next_slot)
        if admit is None:
            await self._run_state(state.update, batch.key_hash,
                                  batch.timestamp, batch.columns)
        else:
            rows = admit(batch.key_hash, batch.timestamp, batch.columns,
                         slots, bins)
            await self._await_update()
            if rows is not None and self._offload_transfers():
                self._update = perf.Offloaded(asyncio.get_running_loop(),
                                              state.apply, rows)
            elif rows is not None:
                state.apply(rows)
        self._ledger_updates += 1
        if self._ledger_updates % 16 == 1 and hasattr(self.state,
                                                      "device_bytes"):
            # throttled device-memory ledger note (join_state_registry
            # idiom): one entry per operator instance, metadata-only
            reg = perf.get_note("pane_state_registry")
            if not isinstance(reg, dict):
                reg = {}
                perf.note("pane_state_registry", reg)
            reg[self.name] = self.state.device_bytes()

    def _update_in_flight(self) -> bool:
        """Whether the update handed off last is still on the executor;
        one that is over is let go of here (its hop counted, the loop's
        look its resume), and what it raised is raised."""
        upd = self._update
        if upd is not None and upd.done():
            self._update = None
            upd.result()
        return self._update is not None

    async def _await_update(self) -> None:
        """The serial path waits for the update in flight, if any: the
        wait is counted (``wait_us.update_wait``, always; the profiler's
        ``offload_wait`` frame, armed) and what the update raised is
        raised."""
        upd = self._update
        if upd is None:
            return
        if not upd.done():
            from ..obs import perf, profiler

            prof = profiler.active()
            frame = (prof.begin(perf.active_operator_id() or self.name,
                                "offload_wait", wait=True)
                     if prof is not None else None)
            t0 = _time.perf_counter_ns()
            try:
                await asyncio.wait((upd.future,))
            finally:
                if frame is not None:
                    prof.end(frame)
                perf.count("wait_us.update_wait",
                           (_time.perf_counter_ns() - t0) // 1000)
        self._update = None
        upd.result()

    async def handle_watermark(self, watermark: int, ctx: Context) -> None:
        """A fire in two halves.  The head is what the operator's serial
        path waits for: everything that reads or writes what an update
        does (``KeyedBinState.fire_head``).  The tail, the read-back's
        wait, the fired batch, its collect and the watermark's broadcast,
        touches the head's handle alone and, where transfers block, runs
        as a task of its own beside the next batches.  What leaves the
        operator leaves in the serial order: a fire's tail starts when
        the tail before it is over (the head waits for it: two fires'
        outputs are on the device for no longer), and a watermark that
        fires nothing (sources send one a batch) is forwarded from behind
        the tail in flight without waiting for it here; ``settle`` awaits
        them all.  Whether a watermark fires is checked here on the loop
        (``KeyedBinState.fire_due``), from what the loop half of a
        batch's update settled: one that fires nothing neither hops nor
        waits for the update in flight, one that fires waits for it
        first (the head flushes what it enqueues).  Span
        ``window.fire.hold`` is the head, ``window.fire``
        a fire's watermark in to its batch sent on.  Where the halves hop
        to an executor thread, three more spans say what a fire waited
        for besides its own work (``_HEAD_HOP``, ``_TAIL_HOP``):
        ``window.fire.head.run`` the executor's part of the head,
        ``window.fire.hop`` the pick-up of the head's and of the tail's
        job (``args.part``), ``window.fire.loop_wait`` every stretch in
        which the fire waited for the loop: each hop's way back into its
        coroutine and the tail task's wait for its first step."""
        from ..obs import tracing
        from ..types import MAX_TIMESTAMP

        final = watermark >= int(MAX_TIMESTAMP) - 1
        t0 = tracing.now_us()
        tid, args = tracing.ctx_tid(ctx), {"watermark": int(watermark)}
        forward = Message.wm(Watermark.event_time(watermark))
        with tracing.span("window.fire.hold", "window", tid=tid, args=args):
            in_flight = await self._tail_in_flight(ctx)
            self._update_in_flight()  # one that failed fails here
            # the mesh state has no head and tail: its fire is serial
            tail = getattr(self.state, "fire_tail", None)
            fire = None
            if tail is None or self.state.fire_due(watermark, final):
                # the head flushes what the update in flight enqueues
                await self._await_update()
                fire = await self._run_state(
                    self.state.fire_panes if tail is None
                    else self.state.fire_head, watermark, final,
                    span=dict(_HEAD_HOP, tid=tid),
                    span_args=dict(args, part="head"))
            if fire is None:
                tracing.record_span("window.fire", "window", t0,
                                    tracing.now_us() - t0, tid=tid,
                                    args=args)
                if in_flight:
                    self._tail = asyncio.ensure_future(self._beside(
                        self._tail, ctx.broadcast, forward))
                else:
                    await ctx.broadcast(forward)
                return
            # the stamp leaves with this fire: the next batch's would
            # overwrite it before the tail built its batch
            lat, self._lat_pending = self._lat_pending, None
            await self.settle(ctx)
            rest = (fire, tail, lat, forward, t0, ctx)
            if tail is None or not self._offload_transfers():
                await self._finish_fire(*rest)
            else:
                self._tail = asyncio.ensure_future(self._beside(
                    None, self._finish_fire, *rest,
                    queued=(tracing.now_us(), tid, args)))

    async def _finish_fire(self, fire, tail, lat, forward: Message,
                           t0: float, ctx: Context) -> None:
        """A fire's tail: the pane emission's device_get, the biggest
        device->host transfer in the pipeline (offloaded as the update
        is), the fired batch downstream, then the watermark."""
        from ..obs import tracing

        watermark = int(forward.watermark.time)
        try:
            if tail is not None:
                fire = await self._run_state(
                    tail, fire, span=dict(_TAIL_HOP, tid=tracing.ctx_tid(ctx)),
                    span_args={"watermark": watermark, "part": "tail"})
            await self._emit(fire, ctx, lat, watermark)
        finally:
            # flight-recorder tap: pane firing is where windowed pipelines
            # spend their watermark-driven time
            tracing.record_span("window.fire", "window", t0,
                                tracing.now_us() - t0,
                                tid=tracing.ctx_tid(ctx),
                                args={"watermark": watermark})
        await ctx.broadcast(forward)

    async def _beside(self, prev, fn, *args, queued=None) -> None:
        """``fn(*args)`` as a task of its own, after the task ``prev``:
        its profiler frames on a stack of their own, under a ``watermark``
        phase as the runner opens around ``handle_watermark``.  A fire's
        tail hands in ``queued``, (when its task was made, trace track,
        span args): how long it waited for its first step is a
        ``window.fire.loop_wait`` span."""
        from ..obs import perf, profiler, tracing

        if queued is not None:
            t_us, tid, span_args = queued
            tracing.record_span("window.fire.loop_wait", "window", t_us,
                                tracing.now_us() - t_us, tid=tid,
                                args=dict(span_args, part="start"))
        if prev is not None:
            await prev
        profiler.detach_stack()
        tok = perf.begin_phase("watermark")
        try:
            await fn(*args)
        finally:
            perf.end_phase(tok)

    async def _tail_in_flight(self, ctx: Context) -> bool:
        """Whether a task started beside the serial path still runs; one
        that is over is let go of here, and what it raised is raised."""
        if self._tail is not None and self._tail.done():
            await self._settle_tail()
        return self._tail is not None

    async def _settle_tail(self) -> None:
        if self._tail is not None:
            try:
                await self._tail  # raises what the tail raised
            finally:
                self._tail = None

    async def settle(self, ctx: Context) -> None:
        await self._await_update()
        await self._settle_tail()

    def abandon(self) -> None:
        upd, self._update = self._update, None
        tail, self._tail = self._tail, None
        for fut, what in ((upd and upd.future, "an update"),
                          (tail, "a fire's tail")):
            if fut is None:
                continue
            if not fut.done():
                fut.cancel()
            elif not fut.cancelled() and fut.exception() is not None:
                logger.error("%s: %s failed: %r", self.name, what,
                             fut.exception())

    async def pre_checkpoint(self, barrier, ctx: Context) -> None:
        # a window fired before the barrier is downstream before it, and
        # the snapshot's last_fired_pane has no un-emitted row behind it
        await self.settle(ctx)

    async def handle_commit(self, epoch: int, ctx: Context) -> None:
        # chain members behind this one commit with no batch on its way
        await self.settle(ctx)

    async def on_close(self, ctx: Context) -> None:
        await self.settle(ctx)

    async def _emit(self, fired, ctx: Context, lat=None,
                    watermark: Optional[int] = None) -> None:
        from ..obs import perf, tracing

        # the host half of the fire after the readbacks: the `emit` phase
        # and, for a watermark fire, the `window.fire.emit` span
        # (``watermark`` ties it to its `window.fire`; a checkpoint drain
        # has none); ``ctx.collect`` below keeps its own phases and, for a
        # watermark fire, is the `window.fire.collect` span: what the
        # chain runs downstream (projections, a key map) and the
        # hand-over to the next task
        tok = perf.begin_phase("emit")
        t0 = tracing.now_us()
        try:
            out = self._fired_batch(fired, lat)
        finally:
            perf.end_phase(tok)
        if watermark is None:
            await ctx.collect(out)
            return
        tid, args = tracing.ctx_tid(ctx), {"watermark": watermark}
        tracing.record_span("window.fire.emit", "window", t0,
                            tracing.now_us() - t0, tid=tid, args=args)
        with tracing.span("window.fire.collect", "window", tid=tid,
                          args=args):
            await ctx.collect(out)

    def _fired_batch(self, fired, lat=None) -> Batch:
        """The output batch of fired cells; ``lat`` is the latency stamp
        that was pending when they fired (``_lat_pending``'s form)."""
        keys, out_cols, window_end, _counts, slots = fired
        cols: Dict[str, np.ndarray] = {}
        # the state hands over each row's slot (FiredPanes): the key
        # columns are gathered by it, no key is looked up by hash here
        cols.update(self.keyvals.gather(slots))
        cols["window_start"] = window_end - self.width
        cols["window_end"] = window_end
        cols.update(out_cols)
        ts = window_end - 1  # emit at w.end - 1ns analog (windows.rs:95)
        key_cols = self._key_cols or tuple(self.keyvals.cols)
        out = Batch(ts, cols, keys.astype(np.uint64), key_cols,
                    lat_stamp=_lat_consume(lat))

        if self.top_n is not None:
            out = _apply_top_n(out, *self.top_n)
        if self.projection is not None:
            out = eval_record_expr(self.projection, out)
        return out


class FactorPaneOperator(BinAggOperator):
    """The shared half of a factor-window rewrite
    (graph/factor_windows.py): a width == slide == pane BinAggOperator
    maintaining the member queries' decomposed partial aggregates once
    per pane.  Watermark fires emit completed panes exactly like any
    tumbling aggregate; the one extra behavior is the checkpoint-barrier
    DRAIN — pending (watermark-incomplete) panes ship downstream as
    deltas and reset on device BEFORE the snapshot, so this operator's
    own table never holds un-shipped mass and a factored checkpoint
    restores into an unfactored plan epoch for epoch (derived rings
    merge deltas losslessly; see ``KeyedBinState.drain_deltas``)."""

    def __init__(self, name: str, pane_micros: int,
                 aggs: Tuple[AggSpec, ...]):
        super().__init__(name, pane_micros, pane_micros, aggs)

    async def pre_checkpoint(self, barrier, ctx: Context) -> None:
        await super().pre_checkpoint(barrier, ctx)  # the fire's tail first
        fired = await self._run_state(self.state.drain_deltas)
        if fired is not None:
            lat, self._lat_pending = self._lat_pending, None
            await self._emit(fired, ctx, lat)


class DerivedWindowOperator(BinAggOperator):
    """The per-query half of a factor-window rewrite: a BinAggOperator
    with the MEMBER's original (width, slide, aggs, projection) whose
    ring runs in merge-input mode — updates consume fired factor panes
    (one row per (key, pane), ``__f_*`` partial columns) instead of raw
    events, so the per-event scatter cost lives once in the shared
    factor while this ring pays only O(panes).  Channel layout, state
    table name and canonical snapshot format are EXACTLY the unfactored
    member's, so checkpoints interchange between factored and
    unfactored plans (incl. rescale key-range filtering)."""

    def __init__(self, name: str, width_micros: int, slide_micros: int,
                 pane_micros: int, aggs: Tuple[AggSpec, ...],
                 projection=None):
        from ..graph.factor_windows import ROWS_COLUMN, derived_channel_cols

        assert slide_micros % pane_micros == 0, \
            "factor pane must divide the derived slide"
        super().__init__(name, width_micros, slide_micros, aggs, projection)
        self.pane = pane_micros
        self.state.set_merge_inputs(derived_channel_cols(aggs), ROWS_COLUMN)


def _topn_partition(batch: Batch, partition_cols: Tuple[str, ...]
                    ) -> np.ndarray:
    if partition_cols:
        from ..types import hash_columns

        # the window instance is always part of the partition: TopN ranks
        # within a window, never across windows
        cols = [batch.columns[c] for c in partition_cols]
        if "window_end" in batch.columns:
            cols.append(batch.columns["window_end"])
        return hash_columns(cols)
    return batch.columns.get("window_end", np.zeros(len(batch), np.int64))


def _apply_top_n(batch: Batch, partition_cols: Tuple[str, ...],
                 sort_column: str, max_elements: Optional[int],
                 rank_column: Optional[str] = None) -> Batch:
    """Keep the top ``max_elements`` rows by ``sort_column`` (desc) per
    partition — one device program over (partition, window) segments
    (ops/topk.py; SURVEY #14/#15 device top-k).  Tiny batches stay on a
    host lexsort: kernel dispatch costs more than the sort itself.

    ``max_elements=None`` ranks without pruning; ``rank_column`` emits
    the 1-based per-partition rank (ROW_NUMBER() materialized) — ranks
    are computed on the (small) surviving row set on host.

    Every selection of a non-empty batch is one `topn.select` span and
    counts itself (``topn_selects``) and its rows in and out, wherever
    it runs: the TopN operator's timer or a bin aggregate's fused fire."""
    if len(batch) == 0:
        return batch
    from ..obs import perf, tracing

    perf.count("topn_selects")
    perf.count("topn_rows_in", len(batch))
    with tracing.span("topn.select", "window"):
        out = _top_n_rows(batch, partition_cols, sort_column, max_elements,
                          rank_column)
    perf.count("topn_rows_out", len(out))
    return out


def _top_n_rows(batch: Batch, partition_cols: Tuple[str, ...],
                sort_column: str, max_elements: Optional[int],
                rank_column: Optional[str]) -> Batch:
    sort_val = batch.columns[sort_column]
    part = _topn_partition(batch, partition_cols)
    if max_elements is not None:
        if len(batch) >= 512:
            from ..ops.topk import segment_top_k

            keep = segment_top_k(part, sort_val, max_elements)
        else:
            order = np.lexsort((-np.asarray(sort_val, dtype=np.float64),  # arroyolint: disable=host-sync -- intentional pane-emission readback: fired panes must materialize on the host to become output batch columns
                                part))
            part_sorted = np.asarray(part)[order]  # arroyolint: disable=host-sync -- intentional pane-emission readback: fired panes must materialize on the host to become output batch columns
            is_start = np.ones(len(order), dtype=bool)
            is_start[1:] = part_sorted[1:] != part_sorted[:-1]
            seg_id = np.cumsum(is_start) - 1
            seg_start = is_start.nonzero()[0]
            rank = np.arange(len(order)) - seg_start[seg_id]
            keep = order[rank < max_elements]
            keep.sort()
        batch = batch.select(keep)
        if rank_column is None:
            return batch
        part = np.asarray(part)[keep]  # arroyolint: disable=host-sync -- intentional pane-emission readback: fired panes must materialize on the host to become output batch columns
        sort_val = batch.columns[sort_column]
    if rank_column is None:
        return batch
    order = np.lexsort((-np.asarray(sort_val, dtype=np.float64), part))  # arroyolint: disable=host-sync -- intentional pane-emission readback: fired panes must materialize on the host to become output batch columns
    part_sorted = np.asarray(part)[order]  # arroyolint: disable=host-sync -- intentional pane-emission readback: fired panes must materialize on the host to become output batch columns
    is_start = np.ones(len(order), dtype=bool)
    is_start[1:] = part_sorted[1:] != part_sorted[:-1]
    seg_start = is_start.nonzero()[0]
    seg_id = np.cumsum(is_start) - 1
    ranks = np.empty(len(order), dtype=np.int64)
    ranks[order] = np.arange(len(order)) - seg_start[seg_id] + 1
    cols = dict(batch.columns)
    cols[rank_column] = ranks
    return Batch(batch.timestamp, cols, batch.key_hash, batch.key_cols,
                 lat_stamp=batch.lat_stamp)


class WindowOperator(Operator):
    """Generic keyed window function: buffer + trigger-at-window-end +
    device segment aggregation (KeyedWindowFunc, windows.rs:160-197)."""

    def __init__(self, name: str, typ, aggs: Tuple[AggSpec, ...],
                 flatten: bool, projection=None):
        super().__init__(name)
        self.typ = typ
        self.width, self.slide = _window_params(typ)
        self.aggs = aggs
        self.flatten = flatten or not aggs
        self.projection = (CompiledExpr(projection.name, projection.fn)
                           if projection else None)

    def tables(self) -> List[TableDescriptor]:
        return [TableDescriptor("w", TableType.BATCH_BUFFER, "window buffer",
                                retention_micros=self.width)]

    async def on_start(self, ctx: Context) -> None:
        self.buffer = ctx.state.get_batch_buffer("w")
        self._lat_pending: Optional[Tuple[int, float]] = None

    async def process_batch(self, batch: Batch, ctx: Context, side: int = 0) -> None:
        assert batch.key_hash is not None
        self._lat_pending = _lat_track(self._lat_pending, batch)
        self.buffer.append(batch)
        # one timer per distinct window end (not per key): rows at ts belong
        # to windows ending at slide-aligned points in (ts, ts+width]
        first_end = (batch.timestamp // self.slide + 1) * self.slide
        if isinstance(self.typ, SlidingWindow):
            ends = np.unique(np.concatenate([
                first_end + i * self.slide
                for i in range(self.width // self.slide)]))
        else:
            ends = np.unique(first_end - self.slide + self.width)
        for e in ends.tolist():
            ctx.timers.schedule(int(e), ("w", int(e)))

    async def handle_timer(self, time: int, key: Any, payload: Any,
                           ctx: Context) -> None:
        end = key[1]
        start = end - self.width
        rows = self.buffer.query_range(start, end)
        if rows is not None and len(rows):
            if self.flatten:
                out_cols = dict(rows.columns)
                out_cols["window_start"] = np.full(len(rows), start, np.int64)
                out_cols["window_end"] = np.full(len(rows), end, np.int64)
                out = Batch(np.full(len(rows), end - 1, np.int64), out_cols,
                            rows.key_hash, rows.key_cols)
            else:
                uniq, agg_cols, _, _cnt, _vc = segment_aggregate(
                    rows.key_hash, rows.timestamp, rows.columns, self.aggs)
                cols = _first_occurrence_cols(rows, uniq)
                cols["window_start"] = np.full(len(uniq), start, np.int64)
                cols["window_end"] = np.full(len(uniq), end, np.int64)
                cols.update(agg_cols)
                out = Batch(np.full(len(uniq), end - 1, np.int64), cols,
                            uniq.astype(np.uint64), rows.key_cols)
            out.lat_stamp = _lat_consume(self._lat_pending)
            self._lat_pending = None
            if self.projection is not None:
                out = eval_record_expr(self.projection, out)
            await ctx.collect(out)
        # evict rows no future window needs
        self.buffer.evict_before(end - self.width + self.slide)


class SessionWindowOperator(Operator):
    """Session windows with gap merging: per-key window sets on host
    (SessionWindowFunc / WindowGroup, windows.rs:200-427)."""

    def __init__(self, name: str, gap_micros: int, aggs: Tuple[AggSpec, ...],
                 flatten: bool, projection=None):
        super().__init__(name)
        self.gap = gap_micros
        self.aggs = aggs
        self.flatten = flatten or not aggs
        self.projection = (CompiledExpr(projection.name, projection.fn)
                           if projection else None)
        self._pending_fires: List[Tuple[int, int, int]] = []
        self._min_end: Optional[int] = None  # no-fire fast-path bound

    def tables(self) -> List[TableDescriptor]:
        return [
            TableDescriptor("s", TableType.BATCH_BUFFER, "session data"),
            TableDescriptor("v", TableType.KEYED, "session windows per key"),
        ]

    async def on_start(self, ctx: Context) -> None:
        from ..state.session_state import SessionRunState

        self.buffer = ctx.state.get_batch_buffer("s")
        # partition-adaptive sorted interval runs unless
        # ARROYO_SESSION_STATE=legacy; both layouts speak the KeyedState
        # interface, so the per-key clamp path below runs unchanged
        self.windows = ctx.state.get_session_state("v")
        self._device_state = isinstance(self.windows, SessionRunState)
        self._lat_pending: Optional[Tuple[int, float]] = None

    def _merge_key(self, kh: int, times: np.ndarray, ctx: Context) -> None:
        """handle_event extend/merge/create (windows.rs:232-302)."""
        sessions: List[Tuple[int, int]] = list(self.windows.get(kh) or [])
        for t in np.sort(times).tolist():
            placed = False
            for i, (s, e) in enumerate(sessions):
                if s - self.gap <= t < e:
                    ns, ne = min(s, t), max(e, t + self.gap)
                    if ne - ns > MAX_SESSION_SIZE_MICROS:
                        ne = ns + MAX_SESSION_SIZE_MICROS
                    sessions[i] = (ns, ne)
                    placed = True
                    break
            if not placed:
                sessions.append((t, t + self.gap))
            # merge overlapping sessions
            sessions.sort()
            merged: List[Tuple[int, int]] = []
            for s, e in sessions:
                if merged and s <= merged[-1][1]:
                    ps, pe = merged[-1]
                    merged[-1] = (ps, max(pe, e))
                else:
                    merged.append((s, e))
            sessions = merged
        self.windows.insert(int(times.max()), kh, sessions)
        if sessions:
            me = min(e for _, e in sessions)
            if self._min_end is not None and me < self._min_end:
                self._min_end = me

    async def process_batch(self, batch: Batch, ctx: Context, side: int = 0) -> None:
        assert batch.key_hash is not None
        self._lat_pending = _lat_track(self._lat_pending, batch)
        self.buffer.append(batch)
        # collapse events -> candidate session intervals for the WHOLE
        # batch in three vector ops (events within gap of their
        # predecessor merge, so a burst becomes ONE interval): the
        # per-key python work then scales with interval count, not event
        # count — the config5 hot loop (windows.rs:232-302 semantics)
        order = np.lexsort((batch.timestamp, batch.key_hash))
        kh = batch.key_hash[order]
        ts = batch.timestamp[order]
        n = len(kh)
        newkey = np.empty(n, dtype=bool)
        newkey[0] = True
        newkey[1:] = kh[1:] != kh[:-1]
        brk = newkey.copy()
        brk[1:] |= (ts[1:] - ts[:-1]) > self.gap
        ist = ts[brk]                      # interval starts
        ien = ts[np.append(brk[1:], True)] + self.gap  # last of group + gap
        ikh = kh[brk]
        kb = newkey[brk].nonzero()[0]      # key boundaries among intervals
        kb = np.append(kb, len(ikh))
        span_ok = (ien - ist) <= MAX_SESSION_SIZE_MICROS
        key_starts = np.append(newkey.nonzero()[0], n)
        if self._device_state:
            await self._merge_batch_device(kh, ts, ikh, ist, ien, kb,
                                           span_ok, key_starts, ctx)
            return
        from ..state.session_state import _count_merge

        _count_merge(0, n)  # legacy layout: every event merges on host
        for i in range(len(kb) - 1):
            k = int(ikh[kb[i]])
            lo, hi = kb[i], kb[i + 1]
            if not span_ok[lo:hi].all() or not self._merge_key_intervals(
                    k, ist[lo:hi].tolist(), ien[lo:hi].tolist(),
                    int(ts[key_starts[i + 1] - 1]), ctx):
                # a burst longer than MAX_SESSION_SIZE, or a merge that
                # would clamp-truncate past an incoming interval's end
                # (events beyond the clamp must START a new session, and
                # only the per-event path knows their positions): rare —
                # the incremental-clamp-splitting path is authoritative
                self._merge_key(k, ts[key_starts[i]:key_starts[i + 1]],
                                ctx)

    async def _merge_batch_device(self, kh, ts, ikh, ist, ien, kb,
                                  span_ok, key_starts, ctx: Context) -> None:
        """Device-state merge: ONE vectorized interval-union dispatch
        covers every in-bounds key; keys the clamp touches (overlong
        bursts, or merged spans crossing MAX_SESSION_SIZE) re-run the
        authoritative per-key path against the same state object — the
        device/host row split is counted, and sanitized parity vs
        ARROYO_SESSION_STATE=legacy is asserted by the smoke gate."""
        from ..obs import perf, profiler
        from ..state.session_state import _count_merge

        nkeys = len(kb) - 1
        # per-interval key ordinal + per-key last event time (the KEYED
        # snapshot time column, matching the legacy insert(max_t, ...))
        key_maxt = ts[key_starts[1:] - 1]
        counts = np.diff(kb)
        itm = np.repeat(key_maxt, counts)
        # keys with an overlong burst go straight to the per-event path:
        # only it knows the event positions past the clamp
        key_ord = np.repeat(np.arange(nkeys), counts)
        bad = np.unique(key_ord[~span_ok])
        good_iv = ~np.isin(key_ord, bad)
        prof = profiler.active()
        frame = (prof.begin(perf.active_operator_id() or self.name,
                            "session_merge") if prof is not None else None)
        try:
            flagged = self.windows.merge_intervals(
                ikh[good_iv], ist[good_iv], ien[good_iv], itm[good_iv])
        finally:
            if prof is not None:
                prof.end(frame)
        if len(bad) or len(flagged):
            keys_arr = ikh[kb[:-1]]  # sorted ascending (lexsort by key)
            fb = set(bad.tolist())
            if len(flagged):
                fb.update(np.searchsorted(keys_arr, flagged).tolist())
            host_events = 0
            for i in sorted(fb):
                lo, hi = key_starts[i], key_starts[i + 1]
                host_events += int(hi - lo)
                self._merge_key(int(keys_arr[i]), ts[lo:hi], ctx)
            _count_merge(0, host_events)
        # exact no-fire bound straight off the runs (cheap: P partition
        # minima), replacing the legacy conservative tracking
        self._min_end = self.windows.min_end()

    def _merge_key_intervals(self, kh: int, ists: List[int],
                             iens: List[int], max_t: int,
                             ctx: Context) -> bool:
        """Union sorted candidate intervals into the key's sorted session
        list — linear two-pointer sweep with the same touching-merges and
        incremental max-size clamp as the per-event path.  Returns False
        WITHOUT touching state when a clamp would truncate below a
        contributing interval's end (events past the clamp would be
        silently swallowed; the caller re-runs the per-event path)."""
        old: List[Tuple[int, int]] = list(self.windows.get(kh) or [])
        merged: List[Tuple[int, int]] = []
        i = j = 0
        no, ni = len(old), len(ists)
        while i < no or j < ni:
            if i < no and (j >= ni or old[i][0] <= ists[j]):
                s, e = old[i]
                i += 1
            else:
                s, e = ists[j], iens[j]
                j += 1
            if merged and s <= merged[-1][1]:
                ps, pe = merged[-1]
                ne = max(pe, e)
                if ne - ps > MAX_SESSION_SIZE_MICROS:
                    if ps + MAX_SESSION_SIZE_MICROS < e:
                        return False  # clamp would swallow interval tail
                    ne = ps + MAX_SESSION_SIZE_MICROS
                merged[-1] = (ps, ne)
            else:
                if e - s > MAX_SESSION_SIZE_MICROS:
                    return False  # guarded by span_ok; belt-and-braces
                merged.append((s, e))
        self.windows.insert(max_t, kh, merged if merged != old else old)
        if merged:
            # keep the no-fire fast-path bound conservative: a fresh
            # short session may end before the cached minimum
            me = min(e for _, e in merged)
            if self._min_end is not None and me < self._min_end:
                self._min_end = me
        return True

    def _collect_expired(self, watermark: int, ctx: Context) -> None:
        """Move every session with end <= watermark into the pending-fire
        list.  Event-time timers only ever fire on watermark advance, so
        scanning the (bounded, active) per-key session map at each
        watermark is equivalent to a per-session timer heap — without
        the heap churn of cancel/reschedule on every batch that extends
        a session (measured ~13% of the config5 run).  A min-end bound
        skips the scan entirely while nothing can fire (many dormant
        keys, slowly advancing watermark)."""
        if self._min_end is not None and watermark < self._min_end:
            return
        if self._device_state:
            # mask-compress every closed session out of the runs in one
            # vector pass per partition — no key iteration
            fk, fs, fe, removed = self.windows.expire(watermark)
            self._pending_fires.extend(
                zip((int(k) for k in fk.tolist()), fs.tolist(),
                    fe.tolist()))
            for kh in removed:
                ctx.state.note_delete("v", kh)
            self._min_end = self.windows.min_end()
            return
        expired_keys = []
        min_end = None
        for kh, sessions in self.windows.items():
            fire = [(s, e) for (s, e) in sessions if e <= watermark]
            if not fire:
                for (_s, e) in sessions:
                    if min_end is None or e < min_end:
                        min_end = e
                continue
            remain = [(s, e) for (s, e) in sessions if e > watermark]
            if remain:
                self.windows.insert(watermark, kh, remain)
                for (_s, e) in remain:
                    if min_end is None or e < min_end:
                        min_end = e
            else:
                expired_keys.append(kh)
            self._pending_fires.extend((int(kh), s, e) for (s, e) in fire)
        for kh in expired_keys:
            self.windows.remove(kh)
            ctx.state.note_delete("v", kh)
        self._min_end = min_end

    async def _flush_fires(self, ctx: Context) -> None:
        fires = self._pending_fires
        if not fires:
            return
        self._pending_fires = []
        rows = self.buffer.query_range(min(s for _, s, _ in fires),
                                       max(e for _, _, e in fires))
        if rows is None or not len(rows):
            return
        # assign every buffered row to its fired session in ONE combined
        # sweep: sessions (as start events) and rows merge-sort by
        # (key, time, starts-first); a running count of starts gives each
        # row the global index of the latest session start at-or-before
        # it — valid iff that session shares the row's key and the row
        # precedes its end.  No per-key python, no buffer argsort.
        m = len(fires)
        fk = np.array([k for k, _, _ in fires], dtype=np.uint64)  # arroyolint: disable=host-sync -- intentional pane-emission readback: fired panes must materialize on the host to become output batch columns
        fs = np.array([s for _, s, _ in fires], dtype=np.int64)  # arroyolint: disable=host-sync -- intentional pane-emission readback: fired panes must materialize on the host to become output batch columns
        fe = np.array([e for _, _, e in fires], dtype=np.int64)  # arroyolint: disable=host-sync -- intentional pane-emission readback: fired panes must materialize on the host to become output batch columns
        fo = np.lexsort((fs, fk))
        fk, fs, fe = fk[fo], fs[fo], fe[fo]
        n = len(rows)
        all_kh = np.concatenate([fk, rows.key_hash])
        all_t = np.concatenate([fs, rows.timestamp])
        prio = np.concatenate([np.zeros(m, np.int8), np.ones(n, np.int8)])
        o = np.lexsort((prio, all_t, all_kh))
        started = np.cumsum(o < m)
        pos = np.empty(m + n, dtype=np.int64)
        pos[o] = np.arange(m + n)
        si = started[pos[m:]] - 1  # per row: global session ordinal
        sic = np.clip(si, 0, m - 1)
        ok = ((si >= 0) & (fk[sic] == rows.key_hash)
              & (rows.timestamp < fe[sic]))
        if not ok.any():
            return
        sel = ok.nonzero()[0]
        segs = sic[sel].astype(np.uint64)
        sub = rows.select(sel)
        seg_kh_a, seg_s_a, seg_e_a = fk, fs, fe

        if self.flatten:
            si = segs.astype(np.int64)
            cols = dict(sub.columns)
            cols["window_start"] = seg_s_a[si]
            cols["window_end"] = seg_e_a[si]
            out = Batch(seg_e_a[si] - 1, cols, sub.key_hash, sub.key_cols)
        else:
            uniq, agg_cols, _, _cnt, _vc = segment_aggregate(
                segs, sub.timestamp, sub.columns, self.aggs)
            ui = uniq.astype(np.int64)
            # key columns: first row of each emitted segment
            cols: Dict[str, np.ndarray] = {}
            if sub.key_cols:
                so = np.argsort(segs, kind="stable")
                seg_sorted = segs[so]
                _, first = np.unique(seg_sorted, return_index=True)
                first_rows = so[first]  # aligned with sorted uniq
                cols = {c: sub.columns[c][first_rows] for c in sub.key_cols
                        if c in sub.columns}
            cols["window_start"] = seg_s_a[ui]
            cols["window_end"] = seg_e_a[ui]
            cols.update(agg_cols)
            out = Batch(seg_e_a[ui] - 1, cols, seg_kh_a[ui], sub.key_cols)
        out.lat_stamp = _lat_consume(self._lat_pending)
        self._lat_pending = None
        if self.projection is not None:
            out = eval_record_expr(self.projection, out)
        await ctx.collect(out)

    async def handle_watermark(self, watermark: int, ctx: Context) -> None:
        self._collect_expired(watermark, ctx)
        await self._flush_fires(ctx)
        # evict data older than every live session start
        if self._device_state:
            ls = self.windows.min_live_start()
        else:
            live_starts = [s for _, sessions in self.windows.items()
                           for (s, _) in sessions]
            ls = min(live_starts) if live_starts else None
        self.buffer.evict_before(
            ls if ls is not None else watermark - MAX_SESSION_SIZE_MICROS)
        await ctx.broadcast(Message.wm(Watermark.event_time(watermark)))


class TumblingTopNOperator(Operator):
    """Windowed TopN (TumblingTopNWindowFunc, tumbling_top_n_window.rs)."""

    def __init__(self, name: str, width_micros: int,
                 max_elements: Optional[int],
                 sort_column: str, partition_cols: Tuple[str, ...],
                 projection=None, rank_column: Optional[str] = None):
        super().__init__(name)
        self.width = width_micros
        self.max_elements = max_elements
        self.sort_column = sort_column
        self.partition_cols = partition_cols
        self.rank_column = rank_column
        self.projection = (CompiledExpr(projection.name, projection.fn)
                           if projection else None)

    def tables(self) -> List[TableDescriptor]:
        return [TableDescriptor("t", TableType.BATCH_BUFFER, "topn buffer",
                                retention_micros=self.width)]

    async def on_start(self, ctx: Context) -> None:
        self.buffer = ctx.state.get_batch_buffer("t")

    async def process_batch(self, batch: Batch, ctx: Context, side: int = 0) -> None:
        from ..obs import perf

        perf.count("topn_buffer_rows", len(batch))
        self.buffer.append(batch)
        ends = np.unique((batch.timestamp // self.width + 1) * self.width)
        for e in ends.tolist():
            ctx.timers.schedule(int(e), ("tn", int(e)))

    async def handle_timer(self, time: int, key: Any, payload: Any,
                           ctx: Context) -> None:
        from ..obs import tracing

        # one window's query of the buffer, its selection (`topn.select`
        # inside), the projection and the hand-over downstream
        with tracing.span("topn.fire", "window", tid=tracing.ctx_tid(ctx),
                          args={"window_end": int(key[1])}):
            await self._fire(key[1], ctx)

    async def _fire(self, end: int, ctx: Context) -> None:
        start = end - self.width
        rows = self.buffer.query_range(start, end)
        if rows is not None and len(rows):
            out_cols = dict(rows.columns)
            # rows that already carry window columns (a global TopN merge
            # over upstream windowed aggregates) keep them: this stage's
            # 1us buckets are an implementation detail, not the window
            if "window_start" not in out_cols:
                out_cols["window_start"] = np.full(len(rows), start,
                                                   np.int64)
            if "window_end" not in out_cols:
                out_cols["window_end"] = np.full(len(rows), end, np.int64)
            out = Batch(np.full(len(rows), end - 1, np.int64), out_cols,
                        rows.key_hash, rows.key_cols)
            out = _apply_top_n(out, self.partition_cols, self.sort_column,
                               self.max_elements, self.rank_column)
            if self.projection is not None:
                out = eval_record_expr(self.projection, out)
            await ctx.collect(out)
        self.buffer.evict_before(end)


def _null_column(n: int, like: Optional[np.ndarray] = None,
                 kind: str = "") -> np.ndarray:
    """A NULL-filled column: None for object/string columns, NaN (f64)
    for everything else — the engine's null conventions."""
    stringy = (kind == "s" if like is None
               else (like.dtype == object or like.dtype.kind in "US"))
    if stringy:
        return np.full(n, None, dtype=object)
    return np.full(n, np.nan, dtype=np.float64)


def _join_name_maps(l_names, r_names, l_prefix: str = "",
                    r_prefix: str = ""):
    """Column-name mapping for a join output (left names win; colliding
    right names get the ``r_`` prefix) — one definition so matched-pair,
    padded, and retraction batches of the same join all agree."""
    lmap: Dict[str, str] = {}
    for c in l_names:
        lmap[c] = (l_prefix + c) if (c in r_names or l_prefix) else c
    rmap: Dict[str, str] = {}
    taken = set(lmap.values())
    for c in r_names:
        name = (r_prefix + c) if (c in l_names or r_prefix) else c
        if name in taken:
            name = "r_" + name
        rmap[c] = name
        taken.add(name)
    return lmap, rmap


def _internal_join_col(name: str) -> bool:
    """Planner-internal join key columns: ``__jk<i>`` + ``__jknonce``."""
    return name.startswith("__jk")


def _drop_null_keyed(batch: Batch) -> Optional[Batch]:
    """Strip rows whose ``__jknonce`` is nonzero — SQL-NULL join keys
    hashed to a unique nonce, so they can never match ANY row on any
    side.  The one home of the nonce-drop rule: buffering such rows on
    a side that cannot emit them padded is pure state growth until TTL
    (the round-4 deferral, retired).  Returns None when nothing
    survives."""
    nonce = batch.columns.get("__jknonce")
    if nonce is None:
        return batch
    keep = np.asarray(nonce) == 0  # arroyolint: disable=host-sync -- nonce is a host-resident key column (null-key routing never enters jit)
    if keep.all():
        return batch
    if not keep.any():
        return None
    return batch.select(keep)


def _stable_join_part(left_cols: Dict[str, np.ndarray],
                      right_cols: Dict[str, np.ndarray], n: int,
                      key_names: Sequence[str],
                      l_prefix: str = "", r_prefix: str = ""
                      ) -> Dict[str, np.ndarray]:
    """One joined-output column layout per join, regardless of which
    side a row came from or whether a side is a null pad (arroyosan's
    schema-stability invariant surfaced that matched pairs carried the
    buffered batch's internal ``__jk*`` columns through the ``r_``
    mapping while spec-template pads did not — the edge layout then
    flipped with arrival order, forcing a coalescer flush and a full
    data-plane frame on every flip).

    The rule: the right role never carries internal join-key columns
    (duplicates for matched rows, meaningless nulls for pads); the left
    role always carries them — filled when the left role is itself a
    pad — in the planner's layout (keys first, ``__jknonce`` last).
    Pad fills for the key columns use same-dtype zeros (witnessed from
    the right role's dropped internals) so the key dtype never flips
    between emission paths: an f64 NaN fill would flip the Arrow edge
    schema per path and concat-promote u64 keys past 2^53."""
    witness = {c: v for c, v in right_cols.items()
               if _internal_join_col(c)}
    right_cols = {c: v for c, v in right_cols.items()
                  if not _internal_join_col(c)}

    def _key_fill(c: str) -> np.ndarray:
        w = witness.get(c)
        if w is not None:
            return np.zeros(n, dtype=w.dtype)
        return _null_column(n)

    ordered: Dict[str, Optional[np.ndarray]] = {}
    for c in key_names:
        if c != "__jknonce":
            ordered[c] = left_cols.get(c)
    for c, v in left_cols.items():
        if c not in ordered and c != "__jknonce":
            ordered[c] = v
    if "__jknonce" in key_names:
        ordered["__jknonce"] = left_cols.get("__jknonce")
    cols = {c: (v if v is not None else _key_fill(c))
            for c, v in ordered.items()}
    lmap, rmap = _join_name_maps(list(cols), list(right_cols),
                                 l_prefix, r_prefix)
    out = {lmap[c]: v for c, v in cols.items()}
    for c, v in right_cols.items():
        out[rmap[c]] = v
    return out


class _SideTemplate:
    """Column template for null-padding one side of an outer join: prefers
    the dtypes of batches actually seen on that side, falls back to the
    planner-provided (name, kind) schema before any batch arrives."""

    def __init__(self, spec_cols: Tuple[Tuple[str, str], ...]):
        self.spec_cols = tuple(spec_cols)
        self.seen: Optional[Dict[str, np.dtype]] = None

    def observe(self, batch: Batch) -> None:
        self.seen = {c: v.dtype for c, v in batch.columns.items()}

    def names(self) -> List[str]:
        if self.seen is not None:
            return list(self.seen)
        return [c for c, _k in self.spec_cols]

    def null_cols(self, n: int) -> Dict[str, np.ndarray]:
        if self.seen is not None:
            return {c: _null_column(n, like=np.empty(0, dtype=dt))
                    for c, dt in self.seen.items()}
        return {c: _null_column(n, kind=k) for c, k in self.spec_cols}


class WindowJoinOperator(Operator):
    """Windowed stream-stream hash join (SURVEY kernel #3): both sides
    buffered, joined per fired window by sorted-merge on key hash
    (WindowedHashJoin, joins.rs:14-181).  Outer kinds null-pad the
    unmatched side per fired window — append-only, no retractions, since
    each window fires exactly once (the reference's list-merge codegen,
    arroyo-sql/src/expressions.rs:134-230)."""

    def __init__(self, name: str, typ, join_type: JoinType = JoinType.INNER,
                 left_cols: Tuple[Tuple[str, str], ...] = (),
                 right_cols: Tuple[Tuple[str, str], ...] = ()):
        super().__init__(name)
        self.typ = typ
        self.join_type = join_type
        self.width, self.slide = _window_params(typ)
        self._tmpl = (_SideTemplate(left_cols), _SideTemplate(right_cols))

    def tables(self) -> List[TableDescriptor]:
        return [
            TableDescriptor("l", TableType.BATCH_BUFFER, "left buffer",
                            retention_micros=self.width),
            TableDescriptor("r", TableType.BATCH_BUFFER, "right buffer",
                            retention_micros=self.width),
        ]

    async def on_start(self, ctx: Context) -> None:
        from ..state.join_state import PartitionedJoinBuffer

        self.left = ctx.state.get_join_buffer("l")
        self.right = ctx.state.get_join_buffer("r")
        self._partitioned = isinstance(self.left, PartitionedJoinBuffer) \
            and isinstance(self.right, PartitionedJoinBuffer)
        self._lat_pending: Optional[Tuple[int, float]] = None

    def _drop_never_emitting(self, batch: Batch,
                             side: int) -> Optional[Batch]:
        """Null-keyed rows stay ONLY when this side's unmatched rows
        null-pad at fire; otherwise they can never emit
        (:func:`_drop_null_keyed`)."""
        padded = self.join_type in (
            (JoinType.LEFT, JoinType.FULL) if side == 0
            else (JoinType.RIGHT, JoinType.FULL))
        if padded:
            return batch
        return _drop_null_keyed(batch)

    async def process_batch(self, batch: Batch, ctx: Context, side: int = 0) -> None:
        assert batch.key_hash is not None, "window join requires keyed inputs"
        self._lat_pending = _lat_track(self._lat_pending, batch)
        self._tmpl[side].observe(batch)
        buffered = self._drop_never_emitting(batch, side)
        if buffered is not None and len(buffered):
            (self.left if side == 0 else self.right).append(buffered)
        first_end = (batch.timestamp // self.slide + 1) * self.slide
        if isinstance(self.typ, SlidingWindow):
            ends = np.unique(np.concatenate([
                first_end + i * self.slide
                for i in range(self.width // self.slide)]))
        else:
            ends = np.unique(first_end - self.slide + self.width)
        for e in ends.tolist():
            ctx.timers.schedule(int(e), ("wj", int(e)))

    async def handle_timer(self, time: int, key: Any, payload: Any,
                           ctx: Context) -> None:
        from ..obs import tracing

        end = key[1]
        # flight-recorder tap, the join's like of `window.fire`: probe,
        # gather, assembly and the hand-over downstream
        with tracing.span("join.fire", "window", tid=tracing.ctx_tid(ctx),
                          args={"window_end": int(end)}):
            await self._fire(end, ctx)
        evict_to = end - self.width + self.slide
        self.left.evict_before(evict_to)
        self.right.evict_before(evict_to)

    async def _fire(self, end: int, ctx: Context) -> None:
        from ..obs import tracing

        start = end - self.width
        how = self.join_type
        if self._partitioned:
            # sorted-run fire: mask-compress each partition's resident
            # run to the window range (stays key-sorted — no sort) and
            # merge-probe; only matched/unmatched rows materialize
            lg, rg, lu, ru = self.left.range_join(self.right, start, end)
            have_l = bool(len(lg) or len(lu))
            have_r = bool(len(rg) or len(ru))
            fire = ((have_l and have_r)
                    or (have_l and how in (JoinType.LEFT, JoinType.FULL))
                    or (have_r and how in (JoinType.RIGHT, JoinType.FULL)))
            if fire:
                l_rows = self.left.gather(lg)
                r_rows = self.right.gather(rg)
                if not len(l_rows.columns):
                    l_rows = _empty_like_side(self._tmpl[0], r_rows)
                if not len(r_rows.columns):
                    r_rows = _empty_like_side(self._tmpl[1], l_rows)
                key_cols = (self.left.key_cols or self.right.key_cols
                            or l_rows.key_cols)
                # unmatched rows only materialize on the side that pads
                # them — an INNER fire's cost scales with matches, not
                # window size
                l_un = (self.left.gather(lu)
                        if how in (JoinType.LEFT, JoinType.FULL) else None)
                r_un = (self.right.gather(ru)
                        if how in (JoinType.RIGHT, JoinType.FULL)
                        else None)
                tid, args = tracing.ctx_tid(ctx), {"window_end": int(end)}
                # the device gathers' blocking readbacks as ONE span, as
                # `window.fire.d2h` is: from the first one's start, as
                # long as all of them together
                d2h = sorted(self.left.take_readbacks()
                             + self.right.take_readbacks())
                if d2h:
                    tracing.record_span(
                        "join.fire.d2h", "window", d2h[0][0],
                        sum(dur for _, dur in d2h), tid=tid, args=args)
                t0 = tracing.now_us()
                out = _assemble_join_output(
                    l_rows, r_rows, l_un, r_un, end, how, key_cols,
                    tmpl=(self._tmpl[0], self._tmpl[1]))
                tracing.record_span("join.fire.emit", "window", t0,
                                    tracing.now_us() - t0, tid=tid,
                                    args=args)
                if len(out):
                    out.lat_stamp = _lat_consume(self._lat_pending)
                    self._lat_pending = None
                    await ctx.collect(out)
        else:
            l = self.left.query_range(start, end)
            r = self.right.query_range(start, end)
            have_l = l is not None and len(l)
            have_r = r is not None and len(r)
            fire = ((have_l and have_r)
                    or (have_l and how in (JoinType.LEFT, JoinType.FULL))
                    or (have_r and how in (JoinType.RIGHT, JoinType.FULL)))
            if fire:
                if not have_l:
                    l = _empty_like_side(self._tmpl[0], r)
                if not have_r:
                    r = _empty_like_side(self._tmpl[1], l)
                out = join_batches(l, r, end, how=how,
                                   tmpl=(self._tmpl[0], self._tmpl[1]))
                if len(out):
                    out.lat_stamp = _lat_consume(self._lat_pending)
                    self._lat_pending = None
                    await ctx.collect(out)


class WindowArgmaxOperator(Operator):
    """Fused ``A JOIN (SELECT max(x), window FROM A GROUP BY window)``
    (the optimizer's argmax rewrite, WindowArgmaxSpec): rows arrive
    keyed by window, buffer per window until the watermark passes, then
    emit exactly the rows achieving the window's max/min of
    ``value_col`` — ties included, like the self-join — plus the pruned
    side's synthesized columns.

    Sound at any upstream parallelism: every global argmax row is also
    a local argmax row in its upstream subtask (value <= local max <=
    global max, with equality required end-to-end), so upstream may
    pre-filter to local candidates and this window-keyed stage settles
    the global answer."""

    def __init__(self, name: str, value_col: str, minmax: str,
                 synth_cols: Tuple[Tuple[str, str], ...],
                 width_micros: int, raw: bool = False,
                 late_ttl_micros: int = 0):
        super().__init__(name)
        self.value_col = value_col
        self.minmax = minmax
        self.synth_cols = synth_cols
        self.width = max(int(width_micros), 1)
        self.raw = raw
        # raw mode must bound the final-extrema table: with no TTL the
        # table would grow one entry per window forever (the SQL planner
        # always passes the join TTL it replaced; direct Stream API users
        # who omit it get one window span — the tightest bound that
        # still catches in-flight stragglers)
        self.late_ttl = (max(int(late_ttl_micros), self.width)
                         if raw else max(int(late_ttl_micros), 0))
        # raw mode: per-window running extremum for the admission
        # pre-filter.  Memory only — on restore the buffer holds exactly
        # the rows that survived the filter, so an empty dict merely
        # means the first post-restore batch per window is admitted
        # unfiltered (correctness never depends on it)
        self._running: Dict[int, float] = {}
        self._released_wm: Optional[int] = None

    def tables(self) -> List[TableDescriptor]:
        tables = [TableDescriptor("b", TableType.BATCH_BUFFER,
                                  "per-window candidate rows",
                                  retention_micros=self.width)]
        if self.raw:
            # released windows' FINAL extrema, retained for the TTL of
            # the join this fusion replaced: a genuinely-late row still
            # matches exactly as it would have against the TTL'd max row
            tables.append(TableDescriptor(
                "f", TableType.TIME_KEY_MAP,
                "released-window final extrema",
                retention_micros=self.late_ttl))
        return tables

    async def on_start(self, ctx: Context) -> None:
        self.buf = ctx.state.get_batch_buffer("b")
        self.final = (ctx.state.get_time_key_map("f") if self.raw
                      else None)
        if ctx.last_watermark is not None:
            # windows at or below the checkpoint watermark fired before
            # the crash; re-arming the guard keeps a late replayed row
            # from re-emitting a whole partial duplicate window (late
            # rows instead match the persisted final extrema)
            self._released_wm = ctx.last_watermark

    def ctx_watermark(self, ctx: Context) -> Optional[int]:
        """Release threshold: the operator's current input watermark,
        floored by the last timer-fired window end (covers restore, where
        both are checkpointed together)."""
        wm = ctx.last_watermark
        if self._released_wm is not None:
            wm = (self._released_wm if wm is None
                  else max(wm, self._released_wm))
        return wm

    async def _admit(self, batch: Batch, ctx: Context) -> Optional[Batch]:
        """Raw mode admission: SQL-NULL values drop (they never equal an
        extremum); rows of already-released windows match the window's
        retained FINAL extremum and emit immediately (the TTL'd join
        this operator replaces would still hold the max row — a late
        tying probe emits there too, and expires the same way once the
        TTL evicts it); live rows strictly dominated by the window's
        running extremum drop (the extremum only tightens, so a
        dominated row can never tie the final answer; ties at the
        current extremum must stay).  Returns the batch to buffer."""
        ends = np.asarray(batch.columns["window_end"], dtype=np.int64)  # arroyolint: disable=host-sync -- intentional pane-emission readback: fired panes must materialize on the host to become output batch columns
        vals = np.asarray(batch.columns[self.value_col])  # arroyolint: disable=host-sync -- intentional pane-emission readback: fired panes must materialize on the host to become output batch columns
        keep = (~np.isnan(vals) if vals.dtype.kind == "f"
                else np.ones(len(vals), dtype=bool))
        # lateness keys off the operator's CURRENT input watermark: any
        # row with window_end <= watermark is late, whether or not that
        # window ever fired.  Keying off the last-fired window end let a
        # late row for an EMPTY middle window (no on-time rows, so no
        # timer, so _released_wm never advanced past it) re-open the
        # window and emit as its max — the unfused TTL-join plan (and the
        # reference, whose aggregate drops late rows) emits nothing
        # there.  _released_wm stays as a lower bound for timer-released
        # windows at equal watermark.
        released = self.ctx_watermark(ctx)
        if released is not None:
            late = keep & (ends <= released)
            if late.any():
                keep &= ~late
                hit = np.zeros(len(ends), dtype=bool)
                for e in np.unique(ends[late]).tolist():
                    best = self.final.get(e, "x")
                    if best is not None:
                        hit |= late & (ends == e) & (vals == best)
                if hit.any():
                    await self._emit(batch.select(np.nonzero(hit)[0]), ctx)
        sign = 1.0 if self.minmax == "max" else -1.0
        for e in np.unique(ends[keep]).tolist():
            m = keep & (ends == e)
            best = self._running.get(e)
            if best is not None:
                m_new = m & (sign * vals >= best)
                keep &= ~m | m_new
                m = m_new
            if m.any():
                local = (sign * vals[m]).max()
                self._running[e] = (local if best is None
                                    else max(best, local))
        if keep.all():
            return batch
        if not keep.any():
            return None
        return batch.select(np.nonzero(keep)[0])

    async def _emit(self, rows: Batch, ctx: Context) -> None:
        cols = dict(rows.columns)
        for out_name, src in self.synth_cols:
            cols[out_name] = cols[src]
        await ctx.collect(Batch(rows.timestamp, cols, rows.key_hash,
                                rows.key_cols))

    async def process_batch(self, batch: Batch, ctx: Context,
                            side: int = 0) -> None:
        if self.raw:
            admitted = await self._admit(batch, ctx)
            if admitted is None:
                return
            batch = admitted
        self.buf.append(batch)
        # one timer per distinct window end; aggregate rows stamp
        # timestamp = window_end - 1 (operator _emit convention)
        for e in np.unique(
                np.asarray(batch.columns["window_end"],  # arroyolint: disable=host-sync -- intentional pane-emission readback: fired panes must materialize on the host to become output batch columns
                           dtype=np.int64)).tolist():
            ctx.timers.schedule(int(e), ("am", int(e)))

    async def handle_timer(self, time: int, key: Any, payload: Any,
                           ctx: Context) -> None:
        end = key[1]
        rows = self.buf.query_range(end - 1, end)  # ts == end - 1
        self.buf.evict_before(end)
        self._running.pop(end, None)
        self._released_wm = (end if self._released_wm is None
                             else max(self._released_wm, end))
        if rows is None or not len(rows):
            return
        vals = np.asarray(rows.columns[self.value_col])  # arroyolint: disable=host-sync -- intentional pane-emission readback: fired panes must materialize on the host to become output batch columns
        # SQL NULL values (NaN — e.g. SUM over an all-null pane) never
        # equal the max in the join this operator replaces; a plain
        # vals.max() would let one NaN poison the extremum and drop the
        # whole window's rows
        valid = (~np.isnan(vals) if vals.dtype.kind == "f"
                 else np.ones(len(vals), dtype=bool))
        if not valid.any():
            return
        vv = vals[valid]
        best = vv.max() if self.minmax == "max" else vv.min()
        if self.final is not None:
            self.final.insert(end, "x", best)
            if self.late_ttl:
                self.final.evict_before(end - self.late_ttl)
        sel = np.nonzero(valid & (vals == best))[0]
        await self._emit(rows.select(sel), ctx)


def _empty_like_side(tmpl: "_SideTemplate", other: Batch) -> Batch:
    """A 0-row batch shaped like one join side (for windows where that
    side saw no data)."""
    cols = {c: v[:0] for c, v in tmpl.null_cols(0).items()}
    return Batch(np.zeros(0, dtype=np.int64), cols,
                 np.zeros(0, dtype=np.uint64), other.key_cols)


def _concat_col(parts: List[np.ndarray]) -> np.ndarray:
    """Concatenate column fragments, promoting to object when any
    fragment is (None-padded rows mix with typed rows).

    int64 fragments mixed with NaN-padded (outer-join null) fragments
    promote to float64 — the engine-wide nullable-int convention
    (docs/architecture.md): BIGINT values above 2^53 lose precision in
    outer-join output batches that mix matched and unmatched rows.
    Nexmark ids and realistic key spaces sit far below that bound; a
    lossless alternative (object dtype with None pads) would take every
    downstream vectorized op off the fast path."""
    if any(p.dtype == object for p in parts):
        out = np.empty(sum(len(p) for p in parts), dtype=object)
        at = 0
        for p in parts:
            out[at:at + len(p)] = p
            at += len(p)
        return out
    return np.concatenate(parts)


def _assemble_join_output(l_rows: Batch, r_rows: Batch,
                          l_un: Optional[Batch], r_un: Optional[Batch],
                          end: int, how: JoinType, key_cols,
                          l_prefix: str = "", r_prefix: str = "",
                          tmpl: Optional[Tuple["_SideTemplate",
                                               "_SideTemplate"]] = None,
                          r_fallback: Optional[Batch] = None,
                          l_fallback: Optional[Batch] = None) -> Batch:
    """Build one join-output batch from aligned matched rows plus the
    per-side unmatched rows — the single emission home for BOTH the
    legacy re-sort path and the partitioned sorted-run path.  Every part
    goes through the same layout normalization so matched, left-padded
    and right-padded rows of one join share ONE column layout (and so do
    successive fires on the same edge)."""
    key_names = tuple(key_cols)
    parts: List[Tuple[Dict[str, np.ndarray], np.ndarray]] = []  # (cols, kh)
    parts.append((_stable_join_part(
        dict(l_rows.columns), dict(r_rows.columns), len(l_rows),
        key_names, l_prefix, r_prefix), l_rows.key_hash))

    if how in (JoinType.LEFT, JoinType.FULL) and l_un is not None \
            and len(l_un):
        pad = ((tmpl[1].null_cols(len(l_un))) if tmpl is not None
               else {c: _null_column(len(l_un), like=v)
                     for c, v in (r_fallback or r_rows).columns.items()})
        parts.append((_stable_join_part(
            dict(l_un.columns), pad, len(l_un), key_names,
            l_prefix, r_prefix), l_un.key_hash))
    if how in (JoinType.RIGHT, JoinType.FULL) and r_un is not None \
            and len(r_un):
        pad = ((tmpl[0].null_cols(len(r_un))) if tmpl is not None
               else {c: _null_column(len(r_un), like=v)
                     for c, v in (l_fallback or l_rows).columns.items()})
        parts.append((_stable_join_part(
            pad, dict(r_un.columns), len(r_un), key_names,
            l_prefix, r_prefix), r_un.key_hash))

    if len(parts) == 1:
        cols, kh = parts[0]
        ts = np.full(len(kh), end - 1, dtype=np.int64)
        return Batch(ts, cols, kh, key_names)
    names = list(parts[0][0])
    out_cols = {c: _concat_col([p[0][c] for p in parts]) for c in names}
    kh = np.concatenate([p[1] for p in parts])
    ts = np.full(len(kh), end - 1, dtype=np.int64)
    return Batch(ts, out_cols, kh, key_names)


def join_batches(l: Batch, r: Batch, end: int,
                 l_prefix: str = "", r_prefix: str = "",
                 how: JoinType = JoinType.INNER,
                 tmpl: Optional[Tuple["_SideTemplate", "_SideTemplate"]] = None
                 ) -> Batch:
    """Sorted-merge equi-join of two keyed batches on key_hash, with
    LEFT/RIGHT/FULL null-padding of unmatched rows (the reference's
    windowed list-merge, arroyo-sql/src/expressions.rs:134-230).

    This is the legacy full re-sort path (both key arrays argsorted per
    call); the partitioned sorted-run fire path computes the same four
    row groups from incrementally maintained state (state/join_state.py)
    and shares the assembly/normalization above."""
    lo, ro, lidx, ridx, counts = join_pairs(l.key_hash, r.key_hash)

    l_rows = l.select(lo[lidx])
    r_rows = r.select(ro[ridx])
    l_un = (l.select(lo[counts == 0])
            if how in (JoinType.LEFT, JoinType.FULL) else None)
    r_un = None
    if how in (JoinType.RIGHT, JoinType.FULL):
        r_matched = np.zeros(len(r.key_hash), dtype=bool)
        if len(ridx):
            r_matched[ro[ridx]] = True
        r_un = r.select(~r_matched)
    from ..state.join_state import _count_gather

    _count_gather(0, len(l_rows) + len(r_rows)
                  + (len(l_un) if l_un is not None else 0)
                  + (len(r_un) if r_un is not None else 0))
    return _assemble_join_output(l_rows, r_rows, l_un, r_un, end, how,
                                 l.key_cols, l_prefix, r_prefix, tmpl,
                                 r_fallback=r, l_fallback=l)


class JoinWithExpirationOperator(Operator):
    """Unwindowed stream-stream join with TTL state
    (join_with_expiration.rs:14-483).  Inner joins emit append rows; outer
    joins emit updating (``__op``) rows: an arriving row with no opposite
    match emits a null-padded CREATE, and when the FIRST opposite-side row
    for that key later arrives, the padded rows are retracted (DELETE) and
    replaced by joined CREATEs — the reference's ``UpdatingData::Update
    {old, new}`` model (join_with_expiration.rs:80-95, 162-218)."""

    def __init__(self, name: str, left_ttl: int, right_ttl: int,
                 join_type: JoinType,
                 left_cols: Tuple[Tuple[str, str], ...] = (),
                 right_cols: Tuple[Tuple[str, str], ...] = ()):
        super().__init__(name)
        self.left_ttl = left_ttl
        self.right_ttl = right_ttl
        self.join_type = join_type
        self._tmpl = (_SideTemplate(left_cols), _SideTemplate(right_cols))

    def tables(self) -> List[TableDescriptor]:
        return [
            TableDescriptor("l", TableType.BATCH_BUFFER, "left state",
                            retention_micros=self.left_ttl),
            TableDescriptor("r", TableType.BATCH_BUFFER, "right state",
                            retention_micros=self.right_ttl),
        ]

    async def on_start(self, ctx: Context) -> None:
        from ..state.join_state import PartitionedJoinBuffer

        self.left = ctx.state.get_join_buffer("l")
        self.right = ctx.state.get_join_buffer("r")
        self._partitioned = isinstance(self.left, PartitionedJoinBuffer) \
            and isinstance(self.right, PartitionedJoinBuffer)
        self._lat_pending: Optional[Tuple[int, float]] = None

    def _orient(self, mine_rows: Batch, opp_cols: Dict[str, np.ndarray],
                side: int, end: int, op: Optional[int],
                kh: Optional[np.ndarray] = None) -> Batch:
        """Build an output batch from rows of MY side joined against
        already-named opposite-side columns, in left-right orientation.
        All four emission paths (matched, padded, retraction, either
        arrival side) route through ``_stable_join_part`` so the edge
        carries one column layout for the life of the join."""
        n = len(mine_rows)
        key_names = tuple(mine_rows.key_cols)
        if side == 0:
            cols = _stable_join_part(dict(mine_rows.columns),
                                     dict(opp_cols), n, key_names)
        else:
            cols = _stable_join_part(dict(opp_cols),
                                     dict(mine_rows.columns), n,
                                     key_names)
        if op is not None:
            cols[UPDATE_OP_COLUMN] = np.full(n, op, np.int8)
        ts = np.full(n, end - 1, dtype=np.int64)
        return Batch(ts, cols,
                     mine_rows.key_hash if kh is None else kh,
                     mine_rows.key_cols)

    async def process_batch(self, batch: Batch, ctx: Context, side: int = 0) -> None:
        assert batch.key_hash is not None
        if not len(batch):
            return
        how = self.join_type
        self._tmpl[side].observe(batch)
        mine, other = ((self.left, self.right) if side == 0
                       else (self.right, self.left))
        my_tmpl, opp_tmpl = self._tmpl[side], self._tmpl[1 - side]
        # is MY side / the OPPOSITE side null-padded when unmatched?
        my_outer = how in ((JoinType.LEFT, JoinType.FULL) if side == 0
                           else (JoinType.RIGHT, JoinType.FULL))
        opp_outer = how in ((JoinType.RIGHT, JoinType.FULL) if side == 0
                            else (JoinType.LEFT, JoinType.FULL))
        updating = how != JoinType.INNER
        op_create = UpdateOp.CREATE.value if updating else None

        # emptiness check must stay O(P): len() counts LIVE rows with a
        # full timestamp scan; resident-but-dead rows are fine here (the
        # probe filters them), so non-empty partitions suffice
        have_opp = (any(part.n for part in other.parts)
                    if self._partitioned else len(other) > 0)
        end = int(batch.timestamp.max()) + 1

        # 1. retract padded opposite rows: keys NEW to my buffer that
        #    match existing opposite rows previously emitted as
        #    (null, opp) — the reference's first_left/first_right Update.
        #    Caveat shared with the reference: "new" is judged from the
        #    CURRENT buffer, so after TTL eviction a re-arriving key can
        #    retract a padded row that was already retracted (the
        #    reference's first_right is likewise recomputed from post-
        #    eviction state, join_with_expiration.rs:420-430) — accepted
        #    as parity behavior for expired-state edge cases
        if opp_outer and have_opp:
            batch_keys = np.unique(batch.key_hash)
            new_keys = batch_keys[~mine.contains_keys(batch_keys)]
            if len(new_keys):
                if self._partitioned:
                    # sorted-run probe for exactly the hit rows — the
                    # opposite buffer is never materialized or re-sorted
                    padded = other.rows_with_keys(new_keys)
                else:
                    opp_all = other.all()
                    padded = opp_all.select(
                        np.isin(opp_all.key_hash, new_keys))
                    from ..state.join_state import _count_gather

                    _count_gather(0, len(padded))
                if len(padded):
                    # the hit rows are OPPOSITE-side rows whose padded
                    # (null, row) emission is now stale; my side is the pad
                    pad = my_tmpl.null_cols(len(padded))
                    out = self._orient(padded, pad, 1 - side, end,
                                       UpdateOp.DELETE.value)
                    await ctx.collect(out)

        # 2. joined CREATEs for matched pairs.  Partitioned state probes
        #    the arriving batch against each partition's resident sorted
        #    run (only the batch's delta gets sorted); the legacy path
        #    re-sorts both sides per call (ops/join.py kernels).
        if have_opp:
            if self._partitioned:
                bsel, opp_rows, counts = other.probe_batch(batch)
                if len(bsel):
                    my_rows = batch.select(bsel)
                    out = self._orient(my_rows, dict(opp_rows.columns),
                                       side, end, op_create)
                    await ctx.collect(out)
                unmatched = counts == 0
            else:
                opp = other.all()
                lo, ro, lidx, ridx, counts = join_pairs(batch.key_hash,
                                                        opp.key_hash)
                if len(lidx):
                    my_rows = batch.select(lo[lidx])
                    opp_rows = opp.select(ro[ridx])
                    from ..state.join_state import _count_gather

                    _count_gather(0, len(opp_rows))
                    out = self._orient(my_rows, dict(opp_rows.columns),
                                       side, end, op_create)
                    await ctx.collect(out)
                unmatched = np.zeros(len(batch), dtype=bool)
                unmatched[lo[counts == 0]] = True  # back to original order
        else:
            unmatched = np.ones(len(batch), dtype=bool)

        # 3. null-padded CREATEs for my unmatched rows
        if my_outer and unmatched.any():
            un = batch.select(unmatched)
            pad = opp_tmpl.null_cols(len(un))
            out = self._orient(un, pad, side, end, op_create)
            await ctx.collect(out)

        # 4. buffer — EXCEPT null-keyed rows: their pad (if any) was
        #    emitted above and can never be matched or retracted
        #    (no opposite row shares the nonce), so they never enter
        #    state (_drop_null_keyed)
        batch = _drop_null_keyed(batch)
        if batch is not None and len(batch):
            mine.append(batch)

    async def handle_watermark(self, watermark: int, ctx: Context) -> None:
        self.left.evict_before(watermark - self.left_ttl)
        self.right.evict_before(watermark - self.right_ttl)
        await ctx.broadcast(Message.wm(Watermark.event_time(watermark)))


class MultiWayJoinOperator(Operator):
    """N-ary INNER equi-join over sides sharing one key (the planner's
    cascaded-join rewrite; MultiWayJoinSpec).  Per fire (windowed mode)
    or per arriving batch (TTL mode), the per-key cross product across
    ALL sides expands directly from the sides' sorted runs — no pairwise
    intermediate is ever materialized, re-keyed, or re-buffered."""

    def __init__(self, name: str, typ, ttl_micros: int, n_sides: int):
        super().__init__(name)
        self.typ = typ
        self.ttl = ttl_micros
        self.n_sides = n_sides
        if typ is not None:
            self.width, self.slide = _window_params(typ)
        else:
            self.width = self.slide = 0

    def tables(self) -> List[TableDescriptor]:
        retention = self.width if self.typ is not None else self.ttl
        return [TableDescriptor(f"j{i}", TableType.BATCH_BUFFER,
                                f"join side {i}",
                                retention_micros=retention)
                for i in range(self.n_sides)]

    async def on_start(self, ctx: Context) -> None:
        # always partitioned: the N-ary probe needs sorted runs (the
        # checkpoint form is the same BATCH_BUFFER batch either way)
        self.bufs = [ctx.state.get_join_buffer(f"j{i}",
                                               force_partitioned=True)
                     for i in range(self.n_sides)]

    # -- shared expansion --------------------------------------------------

    @staticmethod
    def _expand(counts: List[np.ndarray]
                ) -> Tuple[np.ndarray, List[np.ndarray]]:
        """Cross-product expansion: for groups g with per-side match
        counts ``counts[i][g]``, return (group_id per output row, per-side
        offset within the group's side-i match list)."""
        from ..ops.join import expand_counts

        S = len(counts)
        m = counts[0].astype(np.int64).copy()
        for c in counts[1:]:
            m *= c
        gid, within = expand_counts(m)
        offs: List[np.ndarray] = [np.zeros(0, np.int64)] * S
        stride = np.ones(len(m), dtype=np.int64)
        for i in range(S - 1, -1, -1):
            ci = np.maximum(counts[i].astype(np.int64), 1)
            offs[i] = (within // stride[gid]) % ci[gid]
            stride = stride * ci
        return gid, offs

    def _emit_sides(self, side_rows: List[Batch], end: int,
                    ctx: Context) -> Batch:
        """Assemble the joined output left-to-right: side 0 plays the
        left role (carries the internal join-key columns), every later
        side folds in through the same layout normalization the pairwise
        join uses — one stable column layout per edge."""
        key_names = tuple(side_rows[0].key_cols)
        cols = dict(side_rows[0].columns)
        n = len(side_rows[0])
        for rows in side_rows[1:]:
            cols = _stable_join_part(cols, dict(rows.columns), n,
                                     key_names)
        ts = np.full(n, end - 1, dtype=np.int64)
        return Batch(ts, cols, side_rows[0].key_hash, key_names)

    # -- windowed mode -----------------------------------------------------

    async def process_batch(self, batch: Batch, ctx: Context,
                            side: int = 0) -> None:
        assert batch.key_hash is not None, "multi-way join requires keys"
        if not len(batch):
            return
        # inner-only: null-keyed rows can never match any side — never
        # buffered (_drop_null_keyed)
        batch = _drop_null_keyed(batch)
        if batch is None or not len(batch):
            return
        if self.typ is None:
            await self._probe_ttl(batch, side, ctx)
            self.bufs[side].append(batch)
            return
        self.bufs[side].append(batch)
        first_end = (batch.timestamp // self.slide + 1) * self.slide
        if isinstance(self.typ, SlidingWindow):
            ends = np.unique(np.concatenate([
                first_end + i * self.slide
                for i in range(self.width // self.slide)]))
        else:
            ends = np.unique(first_end - self.slide + self.width)
        for e in ends.tolist():
            ctx.timers.schedule(int(e), ("mw", int(e)))

    async def handle_timer(self, time: int, key: Any, payload: Any,
                           ctx: Context) -> None:
        end = key[1]
        start = end - self.width
        P = self.bufs[0].P
        out_parts: List[Batch] = []
        for p in range(P):
            views = [b.parts[p].range_view(start, end) for b in self.bufs]
            if any(len(k) == 0 for k, _pos in views):
                continue
            # keys present on EVERY side (all views key-sorted)
            uk = np.unique(views[0][0])
            for k, _pos in views[1:]:
                idx = np.searchsorted(k, uk)
                ok = idx < len(k)
                ok[ok] = k[idx[ok]] == uk[ok]
                uk = uk[ok]
                if not len(uk):
                    break
            if not len(uk):
                continue
            starts: List[np.ndarray] = []
            cnts: List[np.ndarray] = []
            for k, _pos in views:
                s = np.searchsorted(k, uk, side="left")
                e = np.searchsorted(k, uk, side="right")
                starts.append(s)
                cnts.append(e - s)
            gid, offs = self._expand(cnts)
            if not len(gid):
                continue
            side_rows = []
            for i, (k, pos) in enumerate(views):
                rows = starts[i][gid] + offs[i]
                side_rows.append(self.bufs[i].gather(
                    p * (1 << 48) + pos[rows]))
            out_parts.append(self._emit_sides(side_rows, end, ctx))
        if out_parts:
            out = (out_parts[0] if len(out_parts) == 1
                   else Batch.concat(out_parts))
            if len(out):
                await ctx.collect(out)
        evict_to = end - self.width + self.slide
        for b in self.bufs:
            b.evict_before(evict_to)

    # -- TTL mode ----------------------------------------------------------

    async def _probe_ttl(self, batch: Batch, side: int,
                         ctx: Context) -> None:
        n = len(batch)
        kh = batch.key_hash
        sorter = np.argsort(kh, kind="stable")
        counts: List[np.ndarray] = []
        groups: List[Optional[Tuple[np.ndarray, np.ndarray]]] = []
        for i, buf in enumerate(self.bufs):
            if i == side:
                counts.append(np.ones(n, dtype=np.int64))
                groups.append(None)
                continue
            qidx, gpos = buf.probe_positions(kh[sorter], pre_sorted=True)
            order = np.argsort(qidx, kind="stable")
            qidx, gpos = qidx[order], gpos[order]
            c = np.bincount(qidx, minlength=n)
            counts.append(c)
            groups.append((np.cumsum(c) - c, gpos))
        gid, offs = self._expand(counts)
        if not len(gid):
            return
        end = int(batch.timestamp.max()) + 1
        side_rows = []
        for i, buf in enumerate(self.bufs):
            if i == side:
                side_rows.append(batch.select(sorter[gid]))
            else:
                starts, gpos = groups[i]
                side_rows.append(buf.gather(gpos[starts[gid] + offs[i]]))
        out = self._emit_sides(side_rows, end, ctx)
        if len(out):
            await ctx.collect(out)

    async def handle_watermark(self, watermark: int, ctx: Context) -> None:
        if self.typ is None:
            for b in self.bufs:
                b.evict_before(watermark - self.ttl)
        await ctx.broadcast(Message.wm(Watermark.event_time(watermark)))


class SemiJoinOperator(Operator):
    """Streaming semi-join — the executor behind ``x IN (SELECT ...)``:
    left rows emit EXACTLY ONCE when a matching right key exists (now or
    within the TTL), never duplicated per right-side match.

    Left rows without a current match wait in a batch buffer; when a right
    key is seen for the first time, matching buffered left rows emit and
    leave the buffer.  Right keys live in keyed state with the right TTL.
    """

    def __init__(self, name: str, left_ttl: int, right_ttl: int):
        super().__init__(name)
        self.left_ttl = left_ttl
        self.right_ttl = right_ttl

    def tables(self) -> List[TableDescriptor]:
        return [
            TableDescriptor("l", TableType.BATCH_BUFFER, "left pending",
                            retention_micros=self.left_ttl),
            TableDescriptor("r", TableType.KEYED, "right keys seen",
                            retention_micros=self.right_ttl),
        ]

    async def on_start(self, ctx: Context) -> None:
        self.left = ctx.state.get_batch_buffer("l")
        self.rkeys = ctx.state.get_keyed_state("r")

    def _right_has(self, kh: np.ndarray) -> np.ndarray:
        uniq = np.unique(kh)
        known = np.array([self.rkeys.get(int(k)) is not None  # arroyolint: disable=host-sync -- intentional pane-emission readback: fired panes must materialize on the host to become output batch columns
                          for k in uniq])
        return known[np.searchsorted(uniq, kh)]

    async def process_batch(self, batch: Batch, ctx: Context, side: int = 0) -> None:
        assert batch.key_hash is not None
        if side == 0:  # left: emit matches now, buffer the rest
            mask = self._right_has(batch.key_hash)
            if mask.any():
                await ctx.collect(batch.select(mask))
            if not mask.all():
                self.left.append(batch.select(~mask))
            return
        # right: refresh every key's timestamp (a continuously-hot key
        # must not expire off its FIRST sighting; a LATE re-sighting must
        # not move it backward); first sightings release waiting left rows
        uniq, first = np.unique(batch.key_hash, return_index=True)
        fresh = np.array([self.rkeys.get(int(k)) is None for k in uniq])  # arroyolint: disable=host-sync -- intentional pane-emission readback: fired panes must materialize on the host to become output batch columns
        for k, i in zip(uniq.tolist(), first.tolist()):
            prev_t = self.rkeys.get_time(int(k))
            t = int(batch.timestamp[i])
            self.rkeys.insert(t if prev_t is None else max(t, prev_t),
                              int(k), True)
        if not fresh.any():
            return
        new_keys = uniq[fresh]
        pending = self.left.all()
        if pending is not None and len(pending):
            m = np.isin(pending.key_hash, new_keys)
            if m.any():
                await ctx.collect(pending.select(m))
                self.left.remove_keys(new_keys)

    async def handle_watermark(self, watermark: int, ctx: Context) -> None:
        self.left.evict_before(watermark - self.left_ttl)
        for t, k, _v in self.rkeys.snapshot():
            if t < watermark - self.right_ttl:
                self.rkeys.remove(k)
        await ctx.broadcast(Message.wm(Watermark.event_time(watermark)))


class NonWindowAggOperator(Operator):
    """Running per-key aggregates over an updating stream with expiration
    (UpdatingAggregateOperator, updating_aggregate.rs:11-150): each batch
    merges into per-key running state and emits create/update rows.

    With ``flush_key`` set (GROUP BY the window of a windowed input, q5's
    MaxBids shape), refinements are instead CONSOLIDATED in state and each
    key emits its final row exactly once, when the watermark passes the
    named key column — upstream panes always precede the watermark that
    releases them (shuffle fan-in takes the min across subtasks), so this
    is append-only-correct even when one window's rows arrive in several
    batches from several upstream subtasks."""

    def __init__(self, name: str, expiration_micros: int,
                 aggs: Tuple[AggSpec, ...], projection=None,
                 flush_key: Optional[str] = None):
        super().__init__(name)
        self.expiration = expiration_micros
        self.aggs = aggs
        self.flush_key = flush_key
        # highest flush bound already released: a record re-created for a
        # window at or below it is a LATE refinement (its panes arrived
        # after the watermark released the window) — emitting it again
        # would duplicate the window's final row downstream
        self._released_wm: Optional[int] = None
        self.projection = (CompiledExpr(projection.name, projection.fn)
                           if projection else None)

    def tables(self) -> List[TableDescriptor]:
        return [TableDescriptor("u", TableType.KEYED, "running aggregates",
                                retention_micros=self.expiration)]

    async def on_start(self, ctx: Context) -> None:
        self.table = ctx.state.get_keyed_state("u")
        # re-arm the duplicate-flush guard across restore: every window at
        # or below the checkpoint watermark was already released before
        # the crash (flush runs on each watermark ahead of the barrier),
        # so restored records at or below it are late re-creations
        if ctx.last_watermark is not None:
            self._released_wm = ctx.last_watermark

    async def process_batch(self, batch: Batch, ctx: Context, side: int = 0) -> None:
        assert batch.key_hash is not None
        uniq, agg_cols, max_ts, row_counts, valid_counts = segment_aggregate(
            batch.key_hash, batch.timestamp, batch.columns, self.aggs)
        key_cols = _first_occurrence_cols(batch, uniq)
        n = len(uniq)
        ops = np.zeros(n, dtype=np.int8)
        out_cols: Dict[str, List] = {a.output: [] for a in self.aggs}
        for i, k in enumerate(uniq.tolist()):
            prev = self.table.get(k)
            merged: Dict[str, float] = {}
            for a in self.aggs:
                new = agg_cols[a.output][i]
                # an all-null segment contributes nothing to the running
                # aggregate (NaN marks SQL NULL from segment_aggregate)
                new_null = (new is None
                            or (isinstance(new, (float, np.floating))
                                and np.isnan(new)))
                if a.kind == AggKind.AVG:
                    # mergeable avg: store (sum, non-null count) internally
                    nv = int(valid_counts[a.output][i])
                    new_sum = 0.0 if new_null else float(new) * nv
                    old_sum = prev[f"{a.output}__sum"] if prev else 0.0
                    old_cnt = prev[f"{a.output}__cnt"] if prev else 0
                    merged[f"{a.output}__sum"] = old_sum + new_sum
                    merged[f"{a.output}__cnt"] = old_cnt + nv
                    cnt = merged[f"{a.output}__cnt"]
                    merged[a.output] = (merged[f"{a.output}__sum"] / cnt
                                        if cnt else float("nan"))
                elif prev is None:
                    merged[a.output] = new
                else:
                    old = prev[a.output]
                    old_null = (old is None
                                or (isinstance(old, (float, np.floating))
                                    and np.isnan(old)))
                    if new_null:
                        merged[a.output] = old
                    elif old_null:
                        merged[a.output] = new
                    elif a.kind in (AggKind.SUM, AggKind.COUNT):
                        merged[a.output] = old + new
                    elif a.kind == AggKind.MAX:
                        merged[a.output] = max(old, new)
                    elif a.kind == AggKind.MIN:
                        merged[a.output] = min(old, new)
                out_cols[a.output].append(merged[a.output])
            ops[i] = (UpdateOp.CREATE.value if prev is None
                      else UpdateOp.UPDATE.value)
            if self.flush_key is not None:
                # stash key-column values for the watermark-time emission
                # (state-resident, so a restore can still flush correctly)
                for c, arr in key_cols.items():
                    merged[f"__kc::{c}"] = arr[i]
            self.table.insert(int(max_ts[i]), k, merged)
        if self.flush_key is not None:
            return  # emission happens at watermark passage
        cols = dict(key_cols)
        for a in self.aggs:
            arr = np.asarray(out_cols[a.output])  # arroyolint: disable=host-sync -- intentional pane-emission readback: fired panes must materialize on the host to become output batch columns
            if a.kind == AggKind.COUNT:
                arr = arr.astype(np.int64)
            cols[a.output] = arr
        cols[UPDATE_OP_COLUMN] = ops
        out = Batch(max_ts, cols, uniq.astype(np.uint64), batch.key_cols)
        if self.projection is not None:
            out = eval_record_expr(self.projection, out)
        await ctx.collect(out)

    async def handle_watermark(self, watermark: int, ctx: Context) -> None:
        if self.flush_key is not None:
            await self._flush_ready(watermark, ctx)
        await ctx.broadcast(Message.wm(Watermark.event_time(watermark)))

    async def _flush_ready(self, watermark: int, ctx: Context) -> None:
        fk = f"__kc::{self.flush_key}"
        ready = []
        for t, k, rec in list(self.table.snapshot()):
            bound = rec.get(fk)
            # integer comparison: window_end is epoch micros (~1.8e18,
            # above 2^53), where a float round-trip can round DOWN and
            # flush a window before a lagging subtask's pane arrives
            if bound is None or int(bound) <= watermark:
                if (bound is not None and self._released_wm is not None
                        and int(bound) <= self._released_wm):
                    # late re-creation of an already-released window:
                    # its final row went downstream at an earlier
                    # watermark — a second (partial) row would duplicate
                    # it.  Late panes drop, matching lateness semantics.
                    self.table.remove(k)
                    continue
                ready.append((t, k, rec))
        self._released_wm = (watermark if self._released_wm is None
                             else max(self._released_wm, watermark))
        if not ready:
            return
        ts = np.array([t for t, _, _ in ready], dtype=np.int64)  # arroyolint: disable=host-sync -- intentional pane-emission readback: fired panes must materialize on the host to become output batch columns
        kh = np.array([k for _, k, _ in ready], dtype=np.uint64)  # arroyolint: disable=host-sync -- intentional pane-emission readback: fired panes must materialize on the host to become output batch columns
        kc_names = [n[len("__kc::"):] for n in ready[0][2]
                    if n.startswith("__kc::")]
        cols: Dict[str, np.ndarray] = {}
        for c in kc_names:
            cols[c] = np.asarray([rec[f"__kc::{c}"] for _, _, rec in ready])  # arroyolint: disable=host-sync -- intentional pane-emission readback: fired panes must materialize on the host to become output batch columns
        for a in self.aggs:
            arr = np.asarray([rec[a.output] for _, _, rec in ready])  # arroyolint: disable=host-sync -- intentional pane-emission readback: fired panes must materialize on the host to become output batch columns
            if a.kind == AggKind.COUNT:
                arr = arr.astype(np.int64)
            cols[a.output] = arr
        for _, k, _ in ready:
            self.table.remove(k)
        out = Batch(ts, cols, kh, tuple(kc_names))
        if self.projection is not None:
            out = eval_record_expr(self.projection, out)
        await ctx.collect(out)


# -- builder registration ----------------------------------------------------


@register_builder(OpKind.SLIDING_WINDOW_AGGREGATOR)
def _build_sliding(op: LogicalOperator) -> Operator:
    s = op.spec
    return BinAggOperator(op.name, s.width_micros, s.slide_micros, s.aggs,
                          s.projection,
                          argmax_local=getattr(s, "argmax_local", None))


@register_builder(OpKind.TUMBLING_WINDOW_AGGREGATOR)
def _build_tumbling(op: LogicalOperator) -> Operator:
    s = op.spec
    return BinAggOperator(op.name, s.width_micros, s.width_micros, s.aggs,
                          s.projection,
                          argmax_local=getattr(s, "argmax_local", None))


@register_builder(OpKind.WINDOW_FACTOR)
def _build_window_factor(op: LogicalOperator) -> Operator:
    s = op.spec
    return FactorPaneOperator(op.name, s.pane_micros, s.aggs)


@register_builder(OpKind.DERIVED_WINDOW)
def _build_derived_window(op: LogicalOperator) -> Operator:
    s = op.spec
    return DerivedWindowOperator(op.name, s.width_micros, s.slide_micros,
                                 s.pane_micros, s.aggs, s.projection)


@register_builder(OpKind.SLIDING_AGGREGATING_TOP_N)
def _build_sliding_topn(op: LogicalOperator) -> Operator:
    s = op.spec
    return BinAggOperator(op.name, s.width_micros, s.slide_micros, s.aggs,
                          s.projection,
                          top_n=(s.partition_cols, s.sort_column,
                                 s.max_elements))


@register_builder(OpKind.WINDOW)
def _build_window(op: LogicalOperator) -> Operator:
    s = op.spec
    if isinstance(s.typ, SessionWindow):
        return SessionWindowOperator(op.name, s.typ.gap_micros, s.aggs,
                                     s.flatten, s.projection)
    return WindowOperator(op.name, s.typ, s.aggs, s.flatten, s.projection)


@register_builder(OpKind.TUMBLING_TOP_N)
def _build_topn(op: LogicalOperator) -> Operator:
    s = op.spec
    return TumblingTopNOperator(op.name, s.width_micros, s.max_elements,
                                s.sort_column, s.partition_cols, s.projection,
                                getattr(s, "rank_column", None))


@register_builder(OpKind.WINDOW_JOIN)
def _build_window_join(op: LogicalOperator) -> Operator:
    s = op.spec
    return WindowJoinOperator(op.name, s.typ,
                              getattr(s, "join_type", JoinType.INNER),
                              getattr(s, "left_cols", ()),
                              getattr(s, "right_cols", ()))


@register_builder(OpKind.WINDOW_ARGMAX)
def _build_window_argmax(op: LogicalOperator) -> Operator:
    s = op.spec
    return WindowArgmaxOperator(op.name, s.value_col, s.minmax,
                                s.synth_cols, s.width_micros,
                                raw=getattr(s, "raw", False),
                                late_ttl_micros=getattr(
                                    s, "late_ttl_micros", 0))


@register_builder(OpKind.JOIN_WITH_EXPIRATION)
def _build_join_exp(op: LogicalOperator) -> Operator:
    s = op.spec
    if s.join_type == JoinType.SEMI:
        return SemiJoinOperator(op.name, s.left_expiration_micros,
                                s.right_expiration_micros)
    return JoinWithExpirationOperator(op.name, s.left_expiration_micros,
                                      s.right_expiration_micros, s.join_type,
                                      s.left_cols, s.right_cols)


@register_builder(OpKind.MULTI_WAY_JOIN)
def _build_multi_way_join(op: LogicalOperator) -> Operator:
    s = op.spec
    return MultiWayJoinOperator(op.name, s.typ, s.ttl_micros,
                                len(s.side_cols))


@register_builder(OpKind.NON_WINDOW_AGGREGATOR)
def _build_nonwindow(op: LogicalOperator) -> Operator:
    s = op.spec
    return NonWindowAggOperator(op.name, s.expiration_micros, s.aggs,
                                s.projection,
                                getattr(s, "flush_key", None))
