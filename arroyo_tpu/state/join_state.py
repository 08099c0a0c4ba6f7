"""Partition-adaptive join state (PanJoin-style; PAPERS.md).

The legacy join path kept each side in a flat :class:`BatchBuffer` and
re-sorted BOTH sides' full key arrays on every probe or window fire
(``ops/join.join_pairs`` argsorts ``lk``/``rk`` each call), and every
TTL eviction re-materialized the surviving rows with a full copy.  Under
long-TTL skewed streams both costs grow with *state*, not with the
arriving batch.

This module replaces that with hash-partitioned, incrementally sorted
state:

* each side's rows hash-partition by the low bits of ``key_hash`` (the
  subtask key ranges split on the HIGH bits, so partitioning stays
  orthogonal to rescale);
* each partition maintains its rows as an **incrementally maintained
  sorted run**: an arriving delta is sorted alone (O(m log m)) and
  merged against the resident run with one vectorized positional merge
  (O(n+m) moves, no comparisons beyond a searchsorted) — never a full
  re-sort of resident state;
* TTL eviction is a **valid-range advance**: ``evict_before`` just
  raises the partition's ``valid_from`` bound; dead rows are filtered
  out of probe results by timestamp and physically compacted only when
  they outnumber live rows (amortized O(1) per row);
* **hot partitions** (by observed row frequency, EWMA with hysteresis)
  keep their sorted key run device-resident in a preallocated
  power-of-two ring, maintained by a single scatter-merge kernel
  dispatch per append and probed on device (``ops/join.py``); cold
  partitions stay host numpy ("spill").  Promotion/demotion depends
  only on the observed data sequence, so it is deterministic;
* hot rings are **fully device-resident** (PR 15): keys store as
  native-i32 split-hash planes (top-32 sort key + low-32 collision
  verify — no emulated-u64 argsort on TPU) and, with
  ``ARROYO_JOIN_PAYLOAD_DEVICE`` on (default auto), the partition's
  payload columns ride co-located device planes in the same layout,
  maintained by the SAME scatter-merge dispatch.  Probes then emit
  matches through ONE fused expand+verify+gather dispatch instead of a
  host fancy-index per match (``join_device_gather_rows`` vs
  ``join_host_gather_rows`` count the split).  Object (string) columns
  cannot ride the device: the first string column observed flips the
  buffer's STICKY host-gather fallback (rings stay keys-only, the
  emission layout never flips mid-stream).

Checkpoint contract: :class:`PartitionedJoinBuffer` subclasses
:class:`BatchBuffer` and keeps its ``snapshot_batch``/``restore_batch``
interface, so checkpoints serialize the same Arrow batch form the
legacy buffer wrote, restores filter by key range for rescale exactly
as before, and the two state layouts are checkpoint-compatible in both
directions.

Knobs (see docs/operations.md):
  ARROYO_JOIN_STATE=partitioned|legacy   state layout (default partitioned)
  ARROYO_JOIN_PARTITIONS=16              partitions per side (power of two)
  ARROYO_JOIN_HOT_PARTITIONS=<auto>      device-resident partition budget:
                                         unset, as many partitions as fit
                                         ``_RING_BUDGET_BYTES`` of rings
  ARROYO_JOIN_HOT_MIN_ROWS=4096          EWMA rows to qualify as hot
  ARROYO_JOIN_PAYLOAD_DEVICE=auto|off    payload planes on hot rings
"""

from __future__ import annotations

import itertools
import os
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..obs import perf, profiler
from ..types import Batch
from .tables import BatchBuffer

_NEG_INF = np.iinfo(np.int64).min

# dtype kinds the payload planes can transport (ops/join.payload_plan);
# anything else — object/str — flips the buffer's sticky host fallback
_PAYLOAD_KINDS = "fiubMm"


def _count_gather(dev_rows: int, host_rows: int) -> None:
    """Account materialized join rows to the device/host gather split
    (perf counters + prometheus mirrors) — the payload-residency
    invariant is a measured number, not an assumption."""
    from ..obs.metrics import join_gather_counter

    if dev_rows:
        perf.count("join_device_gather_rows", dev_rows)
        join_gather_counter("device").inc(dev_rows)
    if host_rows:
        perf.count("join_host_gather_rows", host_rows)
        join_gather_counter("host").inc(host_rows)


def _fill_cols(cols: Dict[str, np.ndarray], n: int, sel: Any,
               pcols: Dict[str, np.ndarray]) -> None:
    """Fill output rows ``sel`` from one partition's gathered columns,
    null-initializing and dtype-promoting so a partition lacking a
    column (late schema drift) can never expose garbage."""
    for c, v in pcols.items():
        if c not in cols:
            if v.dtype == object:
                cols[c] = np.full(n, None, dtype=object)
            elif v.dtype.kind == "f":
                cols[c] = np.full(n, np.nan, dtype=v.dtype)
            else:
                cols[c] = np.zeros(n, dtype=v.dtype)
        tgt = cols[c]
        if tgt.dtype != v.dtype:
            cols[c] = tgt = tgt.astype(
                object if (tgt.dtype == object or v.dtype == object)
                else np.result_type(tgt.dtype, v.dtype))
        tgt[sel] = v


def partitioned_join_enabled() -> bool:
    return os.environ.get("ARROYO_JOIN_STATE", "partitioned") != "legacy"


def join_partitions() -> int:
    p = int(os.environ.get("ARROYO_JOIN_PARTITIONS", 16))
    # clamp to a power of two so routing is a mask
    b = 1
    while b * 2 <= max(p, 1):
        b *= 2
    return b


# device bytes one side's resident rings may hold when nobody sets a count
_RING_BUDGET_BYTES = 256 << 20


def _hot_budget(parts: Sequence["_Partition"], payload: bool) -> int:
    """How many partitions of one side may hold device rings.  A set
    ``ARROYO_JOIN_HOT_PARTITIONS`` is the count; otherwise the budget
    follows what the rings hold: as many as fit ``_RING_BUDGET_BYTES`` at
    the largest partition's ring (split-hash key planes, plus the payload
    stacks when they ride along).  A numeric side of a few columns then
    keeps every partition's gathered rows on the device; wide or huge
    sides fall back to the hottest few."""
    forced = os.environ.get("ARROYO_JOIN_HOT_PARTITIONS")
    if forced is not None:
        return int(forced)
    from ..ops.join import ring_cap

    rows = ring_cap(max((part.n for part in parts), default=0))
    slots = 1 + max((len(part.cols) for part in parts), default=0)
    row_bytes = 8 + (8 * slots if payload else 0)
    return min(len(parts), _RING_BUDGET_BYTES // (rows * row_bytes))


def _hot_min_rows() -> float:
    return float(os.environ.get("ARROYO_JOIN_HOT_MIN_ROWS", 4096))


def _grow(arr: np.ndarray, cap: int) -> np.ndarray:
    out = np.empty(cap, dtype=arr.dtype)
    out[: len(arr)] = arr
    return out


class _Partition:
    """One hash partition of one join side: columnar storage in arrival
    order plus an incrementally merged key-sorted run over it."""

    __slots__ = ("cols", "keys", "ts", "n", "cap", "order", "skeys",
                 "sts", "valid_from", "dead", "_evicts_since_scan",
                 "touches", "dev", "dev_device", "payload_on", "max_ts")

    def __init__(self) -> None:
        self.cols: Dict[str, np.ndarray] = {}
        self.keys = np.empty(0, dtype=np.uint64)
        self.ts = np.empty(0, dtype=np.int64)
        self.n = 0
        self.cap = 0
        # sorted run: order[i] = storage position of the i-th smallest key
        # (stable by arrival); skeys/sts mirror keys/ts in sorted order
        self.order = np.empty(0, dtype=np.int64)
        self.skeys = np.empty(0, dtype=np.uint64)
        self.sts = np.empty(0, dtype=np.int64)
        self.valid_from = _NEG_INF
        self.max_ts = _NEG_INF  # newest resident row (all dead below it)
        self.dead = 0  # estimated rows below valid_from
        self._evicts_since_scan = 0
        self.touches = 0.0  # EWMA of rows handled per operation
        # device-resident split-hash ring (ops/join.SplitRing): i32 key
        # planes + optionally the co-located payload stacks
        self.dev: Optional[Any] = None
        # mesh device owning this partition's ring (None = default chip;
        # parallel.shuffle.partition_device spreads hot rings over the
        # ("keys",) mesh so joins stop funneling through one device)
        self.dev_device: Optional[Any] = None
        self.payload_on = False  # buffer policy at last promotion

    # -- storage -----------------------------------------------------------

    def _ensure_cap(self, need: int) -> None:
        if need <= self.cap:
            return
        cap = max(self.cap, 256)
        while cap < need:
            cap *= 2
        self.keys = _grow(self.keys[: self.n], cap)
        self.ts = _grow(self.ts[: self.n], cap)
        for c in list(self.cols):
            self.cols[c] = _grow(self.cols[c][: self.n], cap)
        self.cap = cap

    def _coerce_col(self, name: str, v: np.ndarray) -> np.ndarray:
        """Dtype-promote storage when a later batch widens a column (the
        engine's nullable-int convention can flip int64 -> float64)."""
        cur = self.cols.get(name)
        if cur is None or cur.dtype == v.dtype:
            return v
        if cur.dtype == object or v.dtype == object:
            tgt = np.dtype(object)
        else:
            tgt = np.result_type(cur.dtype, v.dtype)
        if cur.dtype != tgt:
            self.cols[name] = self.cols[name].astype(tgt)
        return v.astype(tgt) if v.dtype != tgt else v

    def append(self, keys: np.ndarray, ts: np.ndarray,
               cols: Dict[str, np.ndarray]) -> None:
        m = len(keys)
        if m == 0:
            return
        n = self.n
        self._ensure_cap(n + m)
        self.keys[n:n + m] = keys
        self.ts[n:n + m] = ts
        self.max_ts = max(self.max_ts, int(ts.max()))
        for c, v in cols.items():
            if c not in self.cols:
                col = np.empty(self.cap, dtype=v.dtype)
                if n:  # column appeared late: null-fill history
                    if v.dtype == object:
                        col[:n] = None
                    elif v.dtype.kind == "f":
                        col[:n] = np.nan
                    else:
                        col = col.astype(np.float64)
                        col[:n] = np.nan
                self.cols[c] = col
            v = self._coerce_col(c, v)
            self.cols[c][n:n + m] = v
        for c in self.cols:
            if c not in cols:  # missing column: null-fill the delta
                cur = self.cols[c]
                if cur.dtype == object:
                    cur[n:n + m] = None
                else:
                    if cur.dtype.kind != "f":
                        self.cols[c] = cur = cur.astype(np.float64)
                    cur[n:n + m] = np.nan

        # incremental sorted-run maintenance: sort ONLY the delta, then
        # positionally merge against the resident run (one searchsorted
        # + two scatters — the tentpole replacement for re-sorting both
        # sides per probe)
        dorder = np.argsort(keys, kind="stable")
        dkeys = keys[dorder]
        ins = np.searchsorted(self.skeys[:n], dkeys, side="right")
        dpos = ins + np.arange(m, dtype=np.int64)
        total = n + m
        new_order = np.empty(total, dtype=np.int64)
        new_skeys = np.empty(total, dtype=np.uint64)
        new_sts = np.empty(total, dtype=np.int64)
        keep = np.ones(total, dtype=bool)
        keep[dpos] = False
        new_order[dpos] = n + dorder
        new_skeys[dpos] = dkeys
        new_sts[dpos] = ts[dorder]
        new_order[keep] = self.order[:n]
        new_skeys[keep] = self.skeys[:n]
        new_sts[keep] = self.sts[:n]
        self.order, self.skeys, self.sts = new_order, new_skeys, new_sts
        self.n = total
        perf.count("join_state_merges")
        self.touches = 0.9 * self.touches + 0.1 * m * 10  # EWMA over ops
        if self.dev is not None:
            dts = ts[dorder]
            dcols = ({c: self.cols[c][n:n + m][dorder]
                      for c in self.cols}
                     if self.dev.plan is not None else None)
            self._device_merge(dkeys, dpos, keep, dts, dcols)

    # -- device residency --------------------------------------------------

    @perf.in_phase("join_merge")
    def _device_merge(self, dkeys: np.ndarray, dpos: np.ndarray,
                      keep: np.ndarray, dts: np.ndarray,
                      dcols: Optional[Dict[str, np.ndarray]]) -> None:
        from ..ops import join as dj

        ring = self.dev
        if self.n > ring.cap:
            # ring overflow: regrow to the next power-of-two ring — the
            # restage keeps key AND payload placement in lockstep
            perf.count("join_state_ring_regrows")
            self.promote()
            return
        if self.payload_on:
            # payload plan drift (a column appeared, widened, or went
            # string): restage so the planes always mirror storage.  A
            # string schema keeps a KEYS-ONLY ring without restaging
            # every merge (payload_plan stays None for it).
            want = {c: v.dtype for c, v in self.cols.items()}
            want_plan = dj.payload_plan(want)
            if want_plan is not None and (
                    ring.plan is None or ring.plan_schema() != want):
                self.promote()
                return
            if want_plan is None and ring.plan is not None:
                self.promote()
                return
        elif ring.plan is not None:
            self.promote()  # payload switched off: drop the planes
            return
        res_pos = np.nonzero(keep)[0].astype(np.int64)
        merged = dj.merge_ring(ring, res_pos, dkeys, dpos,
                               delta_ts=dts, delta_cols=dcols)
        if merged is None:  # delta hit the top-32 sentinel: exactness
            self.demote()   # over speed — the host mirror takes over
            return
        self.dev = merged
        perf.count("join_state_device_merges")

    @perf.in_phase("join_merge")
    def promote(self, device: Any = None,
                payload: Optional[bool] = None) -> None:
        """Stage this partition's sorted keys — plus, when the buffer's
        payload policy is on, its payload columns in the same sorted-run
        order — into preallocated power-of-two device planes
        (idempotent; also used to regrow and to re-plan after schema
        drift — restages keep the mesh device the first promotion
        pinned)."""
        from ..ops import join as dj

        if device is not None:
            self.dev_device = device
        if payload is not None:
            self.payload_on = payload
        n = self.n
        cols = None
        if self.payload_on:
            order = self.order[:n]
            cols = {c: v[:n][order] for c, v in self.cols.items()}
        ring = dj.stage_ring(self.skeys[:n], device=self.dev_device,
                             sorted_ts=self.sts[:n], sorted_cols=cols)
        if ring is None:
            # a key's top-32 bits collide with the ring sentinel
            # (~2^-32/row): this partition stays host — exactness first
            self.dev = None
            return
        self.dev = ring
        perf.count("join_state_promotions")

    def demote(self) -> None:
        if self.dev is not None:
            self.dev = None
            perf.count("join_state_demotions")

    # -- TTL ---------------------------------------------------------------

    def evict_before(self, t: int) -> None:
        """Valid-range advance: no data movement here.  The dead-row
        rescan (an O(n) timestamp compare) is throttled to every 8th
        advance, so per-watermark work stays amortized O(1)/row even
        when watermarks arrive per batch; compaction runs only when
        dead rows outnumber live ones."""
        if t <= self.valid_from or self.n == 0:
            return
        self.valid_from = t
        if self.max_ts < t:
            # every resident row is dead (a tumbling window's fire): drop
            # them without a scan or a copy.  Storage and the device ring
            # keep their capacity, so the next window's rows land at the
            # same shapes and nothing is staged or compiled again
            self.n = self.dead = self._evicts_since_scan = 0
            self.order = self.order[:0]
            self.skeys = self.skeys[:0]
            self.sts = self.sts[:0]
            self.max_ts = _NEG_INF
            return
        self._evicts_since_scan += 1
        if self.n >= 1024 and self._evicts_since_scan >= 8:
            self._evicts_since_scan = 0
            self.dead = int((self.sts[: self.n] < t).sum())
            if self.dead * 2 > self.n:
                self._compact()

    def _compact(self) -> None:
        live = self.ts[: self.n] >= self.valid_from
        for c in list(self.cols):
            self.cols[c] = self.cols[c][: self.n][live].copy()
        self.keys = self.keys[: self.n][live].copy()
        self.ts = self.ts[: self.n][live].copy()
        self.n = int(live.sum())
        self.cap = self.n
        # rebuild the sorted run from the compacted storage: positions
        # shifted by the cumulative dead count before them
        shift = np.cumsum(~live) if len(live) else np.zeros(0, np.int64)
        old_order = self.order[: len(live)]
        okeep = live[old_order]
        kept = old_order[okeep]
        self.order = (kept - shift[kept]).astype(np.int64)
        self.skeys = self.skeys[: len(live)][okeep].copy()
        self.sts = self.sts[: len(live)][okeep].copy()
        self.dead = 0
        perf.count("join_state_compactions")
        if self.dev is not None:
            self.promote()  # restage the compacted run

    # -- queries -----------------------------------------------------------

    def live_mask_sorted(self, start: Optional[int] = None,
                         end: Optional[int] = None) -> np.ndarray:
        sts = self.sts[: self.n]
        m = sts >= (self.valid_from if start is None
                    else max(self.valid_from, start))
        if end is not None:
            m &= sts < end
        return m

    def live_count(self) -> int:
        if self.n == 0:
            return 0
        if self.valid_from == _NEG_INF:
            return self.n
        return int((self.ts[: self.n] >= self.valid_from).sum())

    def probe(self, qkeys_sorted: np.ndarray
              ) -> Tuple[np.ndarray, np.ndarray]:
        """Match ranges of sorted query keys against the resident run.
        Returns (qidx, spos): for every (query row, live matching state
        row) pair, the index into ``qkeys_sorted`` and the SORTED-RUN
        position of the match (``gather`` maps to storage, or straight
        into the device payload planes)."""
        n = self.n
        if n == 0 or len(qkeys_sorted) == 0:
            z = np.zeros(0, dtype=np.int64)
            return z, z
        self.touches = 0.9 * self.touches + 0.1 * len(qkeys_sorted) * 10
        if self.dev is not None:
            from ..ops import join as dj

            hit = dj.probe_ring(self.dev, qkeys_sorted, n)
            total = int(hit.counts.sum())
            if total == 0:
                z = np.zeros(0, dtype=np.int64)
                return z, z
            qidx, sidx = dj.expand_hit(self.dev, hit, total)
            # full-key collision verify on the host mirror: device
            # candidates are top-32-equal ranges; the rare
            # i32-equal-but-u64-distinct rows die here
            ok = self.skeys[sidx] == qkeys_sorted[qidx]
            if not ok.all():
                qidx, sidx = qidx[ok], sidx[ok]
        else:
            skeys = self.skeys[:n]
            start = np.searchsorted(skeys, qkeys_sorted, side="left")
            end = np.searchsorted(skeys, qkeys_sorted, side="right")
            counts = end - start
            if not counts.any():
                z = np.zeros(0, dtype=np.int64)
                return z, z
            from ..ops.join import expand_counts

            qidx, offs = expand_counts(counts)
            sidx = np.repeat(start, counts) + offs  # sorted-run positions
        if self.valid_from != _NEG_INF and len(sidx):
            alive = self.sts[sidx] >= self.valid_from
            qidx, sidx = qidx[alive], sidx[alive]
        return qidx, sidx

    def probe_rows(self, qkeys_sorted: np.ndarray
                   ) -> Tuple[np.ndarray, np.ndarray,
                              Optional[Dict[str, np.ndarray]],
                              Optional[np.ndarray]]:
        """Fused probe + payload materialization: like :meth:`probe`
        but, when this partition's payload planes are resident, the
        candidate expansion, the full-key collision verify AND the
        payload gather run as ONE device dispatch
        (``ops/join.expand_gather``) — no host fancy-index per match.
        Returns (qidx, spos, cols, ts); cols/ts are None when the
        caller must host-gather (cold partition or keys-only ring)."""
        ring = self.dev
        if ring is None or ring.plan is None:
            qidx, spos = self.probe(qkeys_sorted)
            return qidx, spos, None, None
        n = self.n
        if n == 0 or len(qkeys_sorted) == 0:
            z = np.zeros(0, dtype=np.int64)
            return z, z, None, None
        self.touches = 0.9 * self.touches + 0.1 * len(qkeys_sorted) * 10
        from ..ops import join as dj

        hit = dj.probe_ring(ring, qkeys_sorted, n)
        total = int(hit.counts.sum())
        if total == 0:
            z = np.zeros(0, dtype=np.int64)
            return z, z, None, None
        qidx, sidx, valid, gf, gi = dj.expand_gather(ring, hit, total)
        keep = valid
        if self.valid_from != _NEG_INF:
            keep = keep & (gi[0] >= self.valid_from)
        if not keep.all():
            qidx, sidx = qidx[keep], sidx[keep]
            gf, gi = gf[:, keep], gi[:, keep]
        ts, cols = dj.unpack_payload(ring, gf, gi)
        return qidx, sidx, cols, ts

    def range_view(self, start: Optional[int], end: Optional[int]
                   ) -> Tuple[np.ndarray, np.ndarray]:
        """(keys_sorted, sorted_run_positions) of live rows with
        start <= ts < end — mask-compress of the sorted run, which stays
        key-sorted, so fires never re-sort."""
        if self.n == 0:
            return (np.zeros(0, dtype=np.uint64),
                    np.zeros(0, dtype=np.int64))
        m = self.live_mask_sorted(start, end)
        return self.skeys[: self.n][m], np.nonzero(m)[0]


class PartitionedJoinBuffer(BatchBuffer):
    """Drop-in BatchBuffer replacement for join sides: partition-adaptive
    incrementally sorted state (module docstring).  The checkpoint
    interface (``snapshot_batch``/``restore_batch``) is inherited
    behavior-compatibly, so epochs written by either layout restore into
    the other."""

    def __init__(self, n_partitions: Optional[int] = None):
        super().__init__()
        self.P = n_partitions or join_partitions()
        self.parts = [_Partition() for _ in range(self.P)]
        self.key_cols: Tuple[str, ...] = ()
        self._schema: Dict[str, np.dtype] = {}
        self._appends = 0
        self._uid = next(_BUF_UIDS)
        # STICKY string fallback: the first object/string column flips
        # payload residency off for this buffer's whole life — the
        # emission layout (and the edge's sharding spec) never flips
        # mid-stream (shardcheck's sticky-route contract)
        self._payload_sticky_host = False
        # (start, duration) in tracing microseconds of the device gathers'
        # blocking readbacks since a fire last took them
        self._d2h: List[Tuple[float, float]] = []

    def take_readbacks(self) -> List[Tuple[float, float]]:
        out, self._d2h = self._d2h, []
        return out

    # -- routing -----------------------------------------------------------

    def _route(self, kh: np.ndarray) -> np.ndarray:
        return (kh & np.uint64(self.P - 1)).astype(np.int64)

    def _device_active(self) -> bool:
        from ..ops.join import device_join_enabled

        return device_join_enabled(1 << 30)  # state-resident: size-free

    def _payload_active(self) -> bool:
        from ..ops.join import payload_device_enabled

        return payload_device_enabled() and not self._payload_sticky_host

    @perf.in_phase("join_append")
    def append(self, batch: Batch) -> None:
        if not len(batch):
            return
        assert batch.key_hash is not None, "join state requires keyed rows"
        perf.count("join_rows_appended", len(batch))
        if batch.key_cols:
            self.key_cols = batch.key_cols
        self._schema = {c: v.dtype for c, v in batch.columns.items()}
        if not self._payload_sticky_host and any(
                dt.kind not in _PAYLOAD_KINDS
                for dt in self._schema.values()):
            self._payload_sticky_host = True
        dest = self._route(batch.key_hash)
        order = np.argsort(dest, kind="stable")
        bounds = np.searchsorted(dest[order], np.arange(self.P + 1))
        device_on = self._device_active()
        for p in range(self.P):
            lo, hi = bounds[p], bounds[p + 1]
            if lo == hi:
                continue
            rows = order[lo:hi]
            self.parts[p].append(
                batch.key_hash[rows], batch.timestamp[rows],
                {c: v[rows] for c, v in batch.columns.items()})
        if device_on:
            self._rebalance_hot()
        elif any(pt.dev is not None for pt in self.parts):
            for pt in self.parts:
                pt.demote()
        self._appends += 1
        if self._appends % 16 == 1:  # throttled flight-recorder note:
            # one registry entry per buffer (a query has >= 2 side
            # buffers; a single last-writer-wins note would misattribute
            # the state shape) — bench clears and aggregates the registry
            reg = perf.get_note("join_state_registry")
            if not isinstance(reg, dict):
                reg = {}
                perf.note("join_state_registry", reg)
            reg[self._uid] = self.stats()

    def _rebalance_hot(self) -> None:
        """Deterministic hot-set maintenance: the top-``budget``
        partitions by EWMA row frequency hold device rings, with 2x
        hysteresis so borderline partitions don't flap.  Every
        partition's EWMA decays here too — a formerly hot partition
        that stops seeing rows must cool below the demotion floor, or
        its score would freeze and resident rings could exceed the
        budget forever after a skew shift."""
        payload = self._payload_active()
        budget = _hot_budget(self.parts, payload)
        floor = _hot_min_rows()
        for part in self.parts:
            part.touches *= 0.98
        ranked = sorted(range(self.P),
                        key=lambda p: (-self.parts[p].touches, p))
        hot = {p for p in ranked[:budget]
               if self.parts[p].touches >= floor}
        # rank-based demotion with 2-slot hysteresis: a resident ring
        # demotes when it cools below floor/2 OR falls out of the top
        # budget+2 ranking — resident rings are hard-capped near the
        # budget even when ALL partitions keep moderate traffic (an
        # absolute floor alone would let rings accumulate to P)
        grace = set(ranked[: budget + 2])
        from ..parallel.shuffle import partition_device

        for p, part in enumerate(self.parts):
            if p in hot and part.dev is None:
                # sharded device placement over the same ("keys",) mesh
                # axis the window state uses: partition p's ring lives on
                # mesh device p % nk (deterministic — promotion stays a
                # pure function of the observed data sequence); payload
                # planes ride the same device in lockstep
                part.promote(device=partition_device(p), payload=payload)
            elif part.dev is not None and p not in hot and (
                    part.touches < floor / 2 or p not in grace):
                part.demote()

    # -- BatchBuffer interface --------------------------------------------

    def evict_before(self, time: int) -> None:
        for part in self.parts:
            part.evict_before(time)

    def _materialize(self, start: Optional[int] = None,
                     end: Optional[int] = None) -> Optional[Batch]:
        parts: List[Batch] = []
        for part in self.parts:
            n = part.n
            if n == 0:
                continue
            ts = part.ts[:n]
            m = ts >= (part.valid_from if start is None
                       else max(part.valid_from, start))
            if end is not None:
                m &= ts < end
            if not m.any():
                continue
            cols = {c: v[:n][m] for c, v in part.cols.items()}
            parts.append(Batch(ts[m], cols, part.keys[:n][m],
                               self.key_cols))
        if not parts:
            return None
        return parts[0] if len(parts) == 1 else Batch.concat(parts)

    def all(self) -> Optional[Batch]:
        return self._materialize()

    def query_range(self, start: int, end: int) -> Optional[Batch]:
        return self._materialize(start, end)

    def contains_keys(self, key_hashes: np.ndarray) -> np.ndarray:
        out = np.zeros(len(key_hashes), dtype=bool)
        if not len(key_hashes):
            return out
        sorter = np.argsort(key_hashes, kind="stable")
        qidx, _pos = self.probe_positions(key_hashes[sorter],
                                          pre_sorted=True)
        if len(qidx):
            out[sorter[np.unique(qidx)]] = True
        return out

    def remove_keys(self, key_hashes: np.ndarray) -> None:
        for part in self.parts:
            n = part.n
            if n == 0:
                continue
            keep = ~np.isin(part.keys[:n], key_hashes)
            if keep.all():
                continue
            # key removal is rare (semi-join only): compact via mask
            live = keep & (part.ts[:n] >= part.valid_from)
            for c in list(part.cols):
                part.cols[c] = part.cols[c][:n][live].copy()
            part.keys = part.keys[:n][live].copy()
            part.ts = part.ts[:n][live].copy()
            part.n = int(live.sum())
            part.cap = part.n
            part.order = np.argsort(part.keys, kind="stable")
            part.skeys = part.keys[part.order].copy()
            part.sts = part.ts[part.order].copy()
            part.dead = 0
            perf.count("join_state_resorts")
            if part.dev is not None:
                part.promote()

    def __len__(self) -> int:
        return sum(part.live_count() for part in self.parts)

    def snapshot_batch(self) -> Optional[Batch]:
        return self._materialize()

    def restore_batch(self, batch: Optional[Batch]) -> None:
        self.parts = [_Partition() for _ in range(self.P)]
        if batch is not None and len(batch):
            if batch.key_hash is None and batch.key_cols:
                batch = batch.with_key(batch.key_cols)
            self.append(batch)

    # -- join probes -------------------------------------------------------

    def probe_positions(self, qkeys_sorted: np.ndarray, pre_sorted: bool
                        ) -> Tuple[np.ndarray, np.ndarray]:
        """(qidx, (part, pos) encoded) for every live match of the sorted
        query keys; used by contains_keys and rows_with_keys."""
        assert pre_sorted
        dest = self._route(qkeys_sorted)
        qi_parts: List[np.ndarray] = []
        gp_parts: List[np.ndarray] = []
        for p in range(self.P):
            sel = np.nonzero(dest == p)[0]
            if not len(sel):
                continue
            qidx, pos = self.parts[p].probe(qkeys_sorted[sel])
            if len(qidx):
                qi_parts.append(sel[qidx])
                gp_parts.append(p * (1 << 48) + pos)
        if not qi_parts:
            z = np.zeros(0, dtype=np.int64)
            return z, z
        return np.concatenate(qi_parts), np.concatenate(gp_parts)

    def _empty_rows(self) -> Batch:
        cols = {c: np.empty(0, dtype=dt)
                for c, dt in self._schema.items()}
        return Batch(np.zeros(0, dtype=np.int64), cols,
                     np.zeros(0, dtype=np.uint64), self.key_cols)

    def gather(self, gpos: np.ndarray) -> Batch:
        """Materialize rows by encoded (part, sorted-run pos) global
        positions, preserving the given order (pair alignment).  Hot
        partitions with resident payload planes gather ON DEVICE (one
        fused dispatch per partition, ``ops/join.gather_ring``); cold
        partitions host-gather through the sorted-run order mapping —
        the split is counted (``join_device_gather_rows`` /
        ``join_host_gather_rows``) and profiled (``gather`` phase)."""
        n = len(gpos)
        if n == 0:
            return self._empty_rows()
        part_of = (gpos >> 48).astype(np.int64)
        pos = (gpos & ((1 << 48) - 1)).astype(np.int64)
        ts = np.empty(n, dtype=np.int64)
        kh = np.empty(n, dtype=np.uint64)
        cols: Dict[str, np.ndarray] = {}
        dev_rows = host_rows = 0
        prof = profiler.active()
        frame = (prof.begin(perf.active_operator_id() or "join",
                            "gather") if prof is not None else None)
        try:
            for p in np.unique(part_of).tolist():
                part = self.parts[p]
                sel = part_of == p
                spos = pos[sel]
                kh[sel] = part.skeys[spos]
                ring = part.dev
                if ring is not None and ring.plan is not None:
                    from ..ops import join as dj

                    gf, gi = dj.gather_ring(ring, spos, self._d2h)
                    pts, pcols = dj.unpack_payload(ring, gf, gi)
                    ts[sel] = pts
                    dev_rows += len(spos)
                else:
                    ts[sel] = part.sts[spos]
                    rows = part.order[spos]
                    pcols = {c: v[rows] for c, v in part.cols.items()}
                    host_rows += len(spos)
                _fill_cols(cols, n, sel, pcols)
        finally:
            if frame is not None:
                prof.end(frame)
        _count_gather(dev_rows, host_rows)
        return Batch(ts, cols, kh, self.key_cols)

    def probe_batch(self, batch: Batch
                    ) -> Tuple[np.ndarray, Batch, np.ndarray]:
        """Join an arriving batch against this (opposite-side) state
        WITHOUT materializing or re-sorting the state: sort only the
        batch's keys, probe each partition's resident run.  Hot
        partitions with payload planes take the fused
        probe->expand->gather device path (:meth:`_Partition.probe_rows`)
        so matched state rows materialize without a host fancy-index.

        Returns ``(bsel, state_rows, counts)``: matched-pair batch row
        indices, the aligned state rows, and per-batch-row live match
        counts (original batch order) for outer-join unmatched masks."""
        kh = batch.key_hash
        nq = len(kh)
        sorter = np.argsort(kh, kind="stable")
        qk = kh[sorter]
        dest = self._route(qk)
        counts = np.zeros(nq, dtype=np.int64)
        qi_parts: List[np.ndarray] = []
        blocks: List[Tuple[_Partition, np.ndarray,
                           Optional[Dict[str, np.ndarray]],
                           Optional[np.ndarray]]] = []
        total = 0
        for p in range(self.P):
            sel = np.nonzero(dest == p)[0]
            if not len(sel) or self.parts[p].n == 0:
                continue
            qidx, spos, dcols, dts = self.parts[p].probe_rows(qk[sel])
            if not len(qidx):
                continue
            qi_parts.append(sel[qidx])
            blocks.append((self.parts[p], spos, dcols, dts))
            total += len(qidx)
        if not total:
            return np.zeros(0, dtype=np.int64), self._empty_rows(), counts
        bsel = sorter[np.concatenate(qi_parts)]
        np.add.at(counts, bsel, 1)
        return bsel, self._assemble_blocks(blocks, total), counts

    def _assemble_blocks(self, blocks, total: int) -> Batch:
        """One output batch from per-partition probe results, device- or
        host-gathered per block (same null-init/promotion rules as
        :meth:`gather`)."""
        ts = np.empty(total, dtype=np.int64)
        kh = np.empty(total, dtype=np.uint64)
        cols: Dict[str, np.ndarray] = {}
        dev_rows = host_rows = 0
        at = 0
        prof = profiler.active()
        frame = (prof.begin(perf.active_operator_id() or "join",
                            "gather") if prof is not None else None)
        try:
            for part, spos, dcols, dts in blocks:
                m = len(spos)
                sel = slice(at, at + m)
                kh[sel] = part.skeys[spos]
                if dcols is not None:
                    ts[sel] = dts
                    pcols = dcols
                    dev_rows += m
                else:
                    ts[sel] = part.sts[spos]
                    rows = part.order[spos]
                    pcols = {c: v[rows] for c, v in part.cols.items()}
                    host_rows += m
                _fill_cols(cols, total, sel, pcols)
                at += m
        finally:
            if frame is not None:
                prof.end(frame)
        _count_gather(dev_rows, host_rows)
        return Batch(ts, cols, kh, self.key_cols)

    def rows_with_keys(self, keys: np.ndarray) -> Batch:
        """Live rows whose key is in ``keys`` (each row once)."""
        ks = np.sort(np.asarray(keys, dtype=np.uint64))
        _qidx, gpos = self.probe_positions(ks, pre_sorted=True)
        return self.gather(gpos)

    @perf.in_phase("join_probe")
    def range_join(self, other: "PartitionedJoinBuffer", start: int,
                   end: int) -> Tuple[np.ndarray, np.ndarray,
                                      np.ndarray, np.ndarray]:
        """Equi-join both sides' rows with ts in [start, end): per
        partition, mask-compress each sorted run (stays key-sorted — no
        sort) and merge-probe the two.  Returns (l_gpos, r_gpos — aligned
        pair positions; l_unmatched_gpos, r_unmatched_gpos)."""
        lg: List[np.ndarray] = []
        rg: List[np.ndarray] = []
        lu: List[np.ndarray] = []
        ru: List[np.ndarray] = []
        for p in range(self.P):
            lk, lpos = self.parts[p].range_view(start, end)
            rk, rpos = other.parts[p].range_view(start, end)
            perf.count("join_rows_probed", len(lk) + len(rk))
            enc_l = p * (1 << 48) + lpos
            enc_r = p * (1 << 48) + rpos
            if len(lk) == 0 or len(rk) == 0:
                if len(lk):
                    lu.append(enc_l)
                if len(rk):
                    ru.append(enc_r)
                continue
            s = np.searchsorted(rk, lk, side="left")
            e = np.searchsorted(rk, lk, side="right")
            counts = e - s
            if counts.any():
                from ..ops.join import expand_counts

                lidx, offs = expand_counts(counts)
                ridx = np.repeat(s, counts) + offs
                lg.append(enc_l[lidx])
                rg.append(enc_r[ridx])
                rmatched = np.zeros(len(rk), dtype=bool)
                rmatched[ridx] = True
                if not rmatched.all():
                    ru.append(enc_r[~rmatched])
            else:
                ru.append(enc_r)
            lun = counts == 0
            if lun.any():
                lu.append(enc_l[lun])
        z = np.zeros(0, dtype=np.int64)
        cat = lambda xs: np.concatenate(xs) if xs else z  # noqa: E731
        return cat(lg), cat(rg), cat(lu), cat(ru)

    def stats(self) -> Dict[str, Any]:
        """Join-state shape for bench/ops: hot partitions, spill bytes
        (host-resident bytes while the device path is active), rows.
        ``rows`` uses the maintained resident/dead estimates — stats run
        on the append hot path and must not rescan timestamps."""
        hot = sum(1 for part in self.parts if part.dev is not None)
        host_bytes = 0
        for part in self.parts:
            if part.dev is not None:
                continue
            n = part.n
            host_bytes += int(sum(v[:n].nbytes if v.dtype != object
                                  else n * 8 for v in part.cols.values())
                              + part.keys[:n].nbytes + part.ts[:n].nbytes)
        rows = sum(max(part.n - part.dead, 0) for part in self.parts)
        # mesh spread of resident rings: >1 means hot partitions are NOT
        # funneling through one device (the q7/q8 sharded-placement win)
        ring_devs = {str(part.dev_device) for part in self.parts
                     if part.dev is not None
                     and part.dev_device is not None}
        # payload residency shape: rings carrying co-located payload
        # planes, their device bytes, and total ring capacity — bench's
        # state_bounded check holds these against the TTL horizon so a
        # regrow leak is a failed gate, not a silent OOM
        payload_rings = ring_cap = payload_bytes = 0
        for part in self.parts:
            if part.dev is None:
                continue
            ring_cap += part.dev.cap
            if part.dev.plan is not None:
                payload_rings += 1
                payload_bytes += part.dev.payload_bytes()
        return {"partitions": self.P, "hot_partitions": hot,
                "spill_bytes": host_bytes, "rows": rows,
                "ring_devices": len(ring_devs),
                "payload_rings": payload_rings,
                "payload_ring_bytes": payload_bytes,
                "ring_cap_rows": ring_cap}


_BUF_UIDS = itertools.count()


def aggregate_stats_registry(reg: Optional[Dict[Any, Dict[str, Any]]]
                             ) -> Dict[str, Any]:
    """Fold the per-buffer stats registry into one shape summary:
    additive fields sum across buffers, ``partitions`` reports the
    per-side setting."""
    entries = list((reg or {}).values())
    if not entries:
        return {}
    out = {"partitions": max(e.get("partitions", 0) for e in entries),
           "buffers": len(entries)}
    for k in ("hot_partitions", "spill_bytes", "rows", "payload_rings",
              "payload_ring_bytes", "ring_cap_rows"):
        out[k] = int(sum(e.get(k, 0) for e in entries))
    # mesh spread is per buffer; the fold reports the widest one
    out["ring_devices"] = int(max(e.get("ring_devices", 0)
                                  for e in entries))
    return out


def make_join_buffer() -> BatchBuffer:
    """The join side buffer for the configured state layout."""
    return (PartitionedJoinBuffer() if partitioned_join_enabled()
            else BatchBuffer())
