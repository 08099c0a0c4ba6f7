"""Adaptive micro-batch coalescing at task inputs.

Per-dispatch overhead and tiny-batch padding dominate steady-state cost
when batches are small: a stream of sub-``target_batch_size`` batches
pays one kernel dispatch, one padding pass and one queue hop *per
fragment*.  The coalescer merges consecutive
RECORD batches arriving at a task (chain) input into one batch before
the operator sees them, amortizing dispatch and killing shape-churn
recompiles.

Ordering guarantees (the invariants the tests pin):

* a buffered batch is **never reordered past a watermark, barrier or
  end-of-stream marker** — the task loop flushes all buffers before
  handling any non-record message;
* batches only merge within one input *side* (join sides never mix) and
  only while schema/key layout match — a mismatch flushes the old
  buffer first;
* a buffer never outlives the **linger bound**: the first buffered
  fragment starts a deadline, and the task loop flushes on expiry even
  if the target size was never reached.

``ARROYO_COALESCE=0`` disables coalescing entirely; ``COALESCE_TARGET``
(default: ``target_batch_size``) and ``COALESCE_LINGER_MICROS`` bound
size and added latency.
"""

from __future__ import annotations

import os
import time as _time
from typing import Any, Dict, List, Optional, Tuple

from ..types import Batch


def coalescing_enabled() -> bool:
    """``ARROYO_COALESCE=0`` is the escape hatch (read per call so tests
    can toggle without a config reset)."""
    return os.environ.get("ARROYO_COALESCE", "1") not in ("0", "off",
                                                          "false")


def _signature(batch: Batch) -> Tuple:
    """Concat compatibility key: column names, key columns, and whether
    a key hash rides along.  Dtypes are left out — numpy concat promotes
    them, which is exactly what an un-coalesced downstream would see
    across successive batches anyway."""
    return (tuple(batch.columns.keys()), batch.key_cols,
            batch.key_hash is not None)


class _SideBuffer:
    __slots__ = ("sig", "batches", "rows")

    def __init__(self, sig: Tuple, batch: Batch):
        self.sig = sig
        self.batches: List[Batch] = [batch]
        self.rows = len(batch)


class BatchCoalescer:
    """Per-side accumulation of record batches up to ``target`` rows
    within a ``linger`` deadline.  The task loop drives it: ``add``
    returns any batches that became ready, ``flush_all`` drains before
    control messages / on linger expiry."""

    def __init__(self, target: int, linger_secs: float,
                 histogram: Optional[Any] = None,
                 prof: Optional[Any] = None, prof_op: str = ""):
        self.target = max(int(target), 1)
        self.linger = max(float(linger_secs), 0.0)
        self.histogram = histogram  # batches merged per flush
        # phase profiler (obs/profiler.py): None unless armed — the
        # merge concat is then charged to the `coalesce_merge` phase
        self.prof = prof
        self.prof_op = prof_op
        self._bufs: Dict[int, _SideBuffer] = {}  # side -> buffer (ordered)
        self._deadline: Optional[float] = None

    @property
    def pending(self) -> bool:
        return bool(self._bufs)

    @property
    def deadline(self) -> Optional[float]:
        """Monotonic time by which pending buffers must flush."""
        return self._deadline

    def _merge(self, buf: _SideBuffer) -> Batch:
        if self.histogram is not None:
            self.histogram.observe(len(buf.batches))
        if len(buf.batches) == 1:
            return buf.batches[0]
        if self.prof is None:
            return Batch.concat(buf.batches)
        frame = self.prof.begin(self.prof_op, "coalesce_merge")
        try:
            return Batch.concat(buf.batches)
        finally:
            self.prof.end(frame)

    def add(self, side: int, batch: Batch) -> List[Tuple[int, Batch]]:
        """Buffer one incoming batch; returns ``[(side, merged_batch)]``
        for anything that became ready to process (a schema change can
        release the previous buffer AND the new batch in one call)."""
        out: List[Tuple[int, Batch]] = []
        if len(batch) == 0:
            return out  # empty fragments carry nothing to merge
        sig = _signature(batch)
        buf = self._bufs.get(side)
        if buf is not None and buf.sig != sig:
            # incompatible layout: release the old run first, in order
            out.append((side, self._merge(buf)))
            del self._bufs[side]
            buf = None
        if buf is None:
            if len(batch) >= self.target:
                # already at target: pass through, no copy, no linger
                if self.histogram is not None:
                    self.histogram.observe(1)
                out.append((side, batch))
                self._retime()
                return out
            self._bufs[side] = _SideBuffer(sig, batch)
            if self._deadline is None:
                self._deadline = _time.monotonic() + self.linger
            return out
        buf.batches.append(batch)
        buf.rows += len(batch)
        if buf.rows >= self.target:
            out.append((side, self._merge(buf)))
            del self._bufs[side]
            self._retime()
        return out

    def flush_all(self) -> List[Tuple[int, Batch]]:
        """Drain every buffer in arrival order (called before any
        watermark/barrier/end handling and on linger expiry)."""
        out = [(side, self._merge(buf)) for side, buf in self._bufs.items()]
        self._bufs.clear()
        self._deadline = None
        return out

    def _retime(self) -> None:
        if not self._bufs:
            self._deadline = None


class SourceBatcher:
    """Source-boundary coalescing of raw connector fragments.

    Connectors that read small fragments (kafka partition fetches,
    kinesis shard reads, HTTP polls) historically decoded and emitted
    each fragment as its own Batch — one format decode, one collect and
    one downstream envelope per fragment.  The batcher accumulates raw
    payloads *before* decode and hands the engine one target-size batch:
    decode amortizes (the vectorized formats fast path parses the whole
    run in one pass) and the per-batch dispatch envelope is paid once.

    Exactly-once contract: connectors record their resume positions
    (offsets / sequence numbers) at fetch time, so buffered payloads
    must be flushed downstream **before** any checkpoint snapshots that
    state and before the source returns — otherwise a restore would
    skip them.  The TaskRunner guarantees this by awaiting the source's
    ``flush_pending`` before handling a checkpoint barrier or stop, and
    after the source loop returns; connectors additionally flush on
    linger expiry (``maybe_flush``) so a sub-target trickle still
    emits within the bounded latency.
    """

    def __init__(self, ctx: Any, decode: Any, target: int,
                 linger_secs: Optional[float] = None,
                 prof_op: str = "", batch_always: bool = False):
        from ..config import config
        from ..obs import profiler

        self.ctx = ctx
        self.decode = decode  # payload list -> Batch
        cfg = config()
        self.target = max(int(target or cfg.coalesce_target
                              or cfg.target_batch_size), 1)
        self.linger = (cfg.coalesce_linger_micros / 1e6
                       if linger_secs is None else max(linger_secs, 0.0))
        self.prof = profiler.active()
        self.prof_op = prof_op
        # batch_always: the connector assembled target-size batches
        # itself BEFORE this PR (e.g. the SSE event buffer), so target
        # batching must survive ARROYO_COALESCE=0 — the escape disables
        # only the linger, restoring the pre-coalescer behavior instead
        # of regressing to one decode+collect per fragment
        self.batch_always = batch_always
        self._payloads: List[Any] = []
        self._deadline: Optional[float] = None

    @property
    def pending(self) -> bool:
        return bool(self._payloads)

    @property
    def expired(self) -> bool:
        return (self._deadline is not None
                and _time.monotonic() >= self._deadline)

    async def add(self, payloads: List[Any]) -> None:
        """Buffer one fragment's payloads; decodes + emits when the
        target size is reached (coalescing is buffering-only: enabled/
        disabled emits the same rows in the same order)."""
        if not payloads:
            return
        coalescing = coalescing_enabled()
        if not coalescing and not self.batch_always:
            await self._emit(list(payloads))
            return
        self._payloads.extend(payloads)
        if len(self._payloads) >= self.target:
            await self.flush()
        elif coalescing and self._deadline is None:
            # batch_always without coalescing: no linger deadline — the
            # buffer flushes at target size and at the runner's
            # checkpoint/stop/end boundaries, as pre-coalescer
            self._deadline = _time.monotonic() + self.linger

    async def maybe_flush(self) -> None:
        """Flush iff the linger deadline passed (called once per source
        poll round)."""
        if self.expired:
            await self.flush()

    async def flush(self) -> None:
        """Decode and emit everything buffered (called by the source on
        linger expiry and by the TaskRunner before checkpoints/stop)."""
        payloads, self._payloads = self._payloads, []
        self._deadline = None
        if payloads:
            await self._emit(payloads)

    async def _emit(self, payloads: List[Any]) -> None:
        if self.prof is None:
            batch = self.decode(payloads)
        else:
            frame = self.prof.begin(self.prof_op, "source_decode")
            try:
                batch = self.decode(payloads)
            finally:
                self.prof.end(frame)
        if batch is not None and len(batch):
            from ..obs import latency as _latency

            lat = _latency.active()
            if lat is not None:
                # latency sampling at the source boundary: stamp the
                # batch carrying the next 1-in-N sampled record with its
                # ingest wall-clock (side-channel annotation — the
                # schema signature above never sees it)
                stamp = lat.source_stamp(self.prof_op or "source",
                                         len(batch))
                if stamp is not None:
                    batch.lat_stamp = stamp
            await self.ctx.collect(batch)
