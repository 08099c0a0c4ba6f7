"""Partition-adaptive session-window state (the join-state machinery
generalized; PanJoin's tiered-residency stance, PAPERS.md).

The legacy session path kept each key's ``[(start, end), ...]`` session
list in a :class:`~arroyo_tpu.state.tables.KeyedState` dict and merged
arriving intervals with a Python loop per key (plus a ``sessions.sort()``
per event on the per-event fallback) — the config5 hot loop.  This
module keeps ALL keys' live sessions as hash-partitioned,
**incrementally sorted interval runs**:

* one flat ``(key_hash, start, end)`` run per partition, sorted by
  ``(key, start)`` — partitions route on the LOW hash bits
  (``kh & (P-1)``), orthogonal to the subtask key ranges on the HIGH
  bits, so rescale never re-partitions (the ``state/join_state.py``
  contract);
* an arriving batch's candidate intervals merge in **one vectorized
  interval-union dispatch for all keys at once**
  (:func:`arroyo_tpu.ops.session.union_sorted_intervals`): only the
  touched keys' resident rows join the scan, untouched rows splice back
  positionally — never a full re-sort of resident state;
* the max-session-size clamp keeps the per-key path authoritative: any
  key whose unclamped union span exceeds the clamp is returned to the
  caller, which re-runs the legacy merge for exactly that key — the
  device/host split is counted (``session_device_merge_rows`` /
  ``session_host_merge_rows``), never assumed;
* watermark fires are a **mask-compress**: ``expire()`` splits each
  partition's run at ``end <= watermark`` in O(rows) vector ops instead
  of iterating the key dict;
* **hot partitions** (EWMA row frequency with hysteresis, the join-state
  policy) keep ``(start, end)`` planes staged on a mesh device
  (``parallel/mesh_window.place_session_partition``), so accelerator
  backends run the union scan against resident planes; cold partitions
  stay host numpy;
* **accumulator channels** (``configure``): each session row may carry
  mergeable partials of its rows — counts and sums folded by addition,
  minima and maxima by min / max, key-column values by the first row —
  beside ``(key, start, end)``.  They ride every path the interval rows
  take (pull, global sort, splice, expire, the clamp re-entry) and the
  union folds them at its session boundaries exactly as it folds the
  update time, so a fire reads each closed session's aggregates off its
  row instead of re-reading the events.

Checkpoint contract: :class:`SessionRunState` duck-types
:class:`~arroyo_tpu.state.tables.KeyedState` — ``snapshot()`` emits the
same ``[(time, key, sessions)]`` entries (a session is ``(start, end,
*channels)``) and ``restore()`` accepts them, so the table keeps
``TableType.KEYED`` form on disk: epochs written by either layout
restore into the other, and rescale's key-range entry filtering
(state/backend.py) applies unchanged.

Knobs (see docs/operations.md):
  ARROYO_SESSION_STATE=device|legacy    state layout (default device)
  ARROYO_SESSION_PARTITIONS=16          partitions (power of two)
  ARROYO_SESSION_HOT_PARTITIONS=4       device-staged partition budget
  ARROYO_SESSION_HOT_MIN_ROWS=512       EWMA rows to qualify as hot
  ARROYO_SESSION_DEVICE=auto|on|off     union scan as a device kernel
"""

from __future__ import annotations

import itertools
import os
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from ..obs import perf

_SESSION_UIDS = itertools.count()


def session_state_enabled() -> bool:
    return os.environ.get("ARROYO_SESSION_STATE", "device") != "legacy"


def session_partitions() -> int:
    p = int(os.environ.get("ARROYO_SESSION_PARTITIONS", 16))
    b = 1
    while b * 2 <= max(p, 1):
        b *= 2
    return b


def _hot_budget() -> int:
    return int(os.environ.get("ARROYO_SESSION_HOT_PARTITIONS", 4))


def _hot_min_rows() -> float:
    return float(os.environ.get("ARROYO_SESSION_HOT_MIN_ROWS", 512))


# channel fold kinds: partial counts and sums add, minima and maxima fold
# by min / max, key-column values keep their session's first row
FOLD_KINDS = ("add", "min", "max", "first")


def fold_channels(kinds: Sequence[str], chans: Sequence[np.ndarray],
                  first: np.ndarray) -> List[np.ndarray]:
    """Fold row channels into the runs that start at the row indices
    ``first`` (sorted, ``first[0] == 0``): one ``reduceat`` a channel."""
    if len(first) == 0:
        return [c[:0] for c in chans]
    out: List[np.ndarray] = []
    for kind, c in zip(kinds, chans):
        if kind == "add":
            out.append(np.add.reduceat(c, first))
        elif kind == "min":
            out.append(np.minimum.reduceat(c, first))
        elif kind == "max":
            out.append(np.maximum.reduceat(c, first))
        else:
            out.append(c[first])
    return out


def fold_pair(kinds: Sequence[str], a: Sequence[Any], b: Sequence[Any]
              ) -> Tuple[Any, ...]:
    """Fold two sessions' channel tuples (``a`` the earlier start): the
    per-key paths' form of :func:`fold_channels`."""
    return tuple(x + y if k == "add" else min(x, y) if k == "min"
                 else max(x, y) if k == "max" else x
                 for k, x, y in zip(kinds, a, b))


def _count_merge(dev_rows: int, host_rows: int) -> None:
    """Account merged interval rows to the device/host split (perf
    counters + prometheus mirrors) — the vectorized-merge share is a
    measured number, not an assumption."""
    from ..obs.metrics import session_merge_counter

    if dev_rows:
        perf.count("session_device_merge_rows", dev_rows)
        session_merge_counter("device").inc(dev_rows)
    if host_rows:
        perf.count("session_host_merge_rows", host_rows)
        session_merge_counter("host").inc(host_rows)


class _SessionPartition:
    """One hash partition: a flat session-interval run sorted by
    ``(key, start)`` plus per-row last-update times (the KEYED snapshot
    ``t`` column) and the rows' accumulator channels."""

    __slots__ = ("kh", "st", "en", "tm", "ch", "touches", "dev",
                 "dev_device")

    def __init__(self) -> None:
        self.kh = np.empty(0, dtype=np.uint64)
        self.st = np.empty(0, dtype=np.int64)
        self.en = np.empty(0, dtype=np.int64)
        self.tm = np.empty(0, dtype=np.int64)
        self.ch: List[np.ndarray] = []
        self.touches = 0.0  # EWMA of rows handled per merge
        # staged (start, end) device planes for hot partitions; host
        # arrays stay the checkpoint/fallback mirror
        self.dev: Optional[Any] = None
        self.dev_device: Optional[Any] = None

    @property
    def n(self) -> int:
        return len(self.kh)

    def set_rows(self, kh: np.ndarray, st: np.ndarray, en: np.ndarray,
                 tm: np.ndarray, ch: Sequence[np.ndarray]) -> None:
        self.kh, self.st, self.en, self.tm = kh, st, en, tm
        self.ch = list(ch)
        if self.dev is not None:
            self.stage()

    def key_slice(self, kh: int) -> slice:
        k = np.uint64(kh)
        lo = int(np.searchsorted(self.kh, k, side="left"))
        hi = int(np.searchsorted(self.kh, k, side="right"))
        return slice(lo, hi)

    def touched_mask(self, keys_sorted: np.ndarray) -> np.ndarray:
        """Row mask of resident rows whose key is in ``keys_sorted`` —
        one flag-array cumsum over the per-key searchsorted ranges, no
        per-key loop."""
        n = self.n
        if n == 0 or len(keys_sorted) == 0:
            return np.zeros(n, dtype=bool)
        lo = np.searchsorted(self.kh, keys_sorted, side="left")
        hi = np.searchsorted(self.kh, keys_sorted, side="right")
        f = np.zeros(n + 1, dtype=np.int64)
        np.add.at(f, lo, 1)
        np.add.at(f, hi, -1)
        return np.cumsum(f[:-1]) > 0

    def splice(self, keep: np.ndarray, bkh: np.ndarray, bst: np.ndarray,
               ben: np.ndarray, btm: np.ndarray,
               bch: Sequence[np.ndarray]) -> None:
        """Replace this run with (kept resident rows) ∪ (replacement
        rows ``b*``, sorted by (key, start), keys disjoint from the kept
        rows' keys) — one positional merge, no comparison sort of
        resident state."""
        akh = self.kh[keep]
        ast_ = self.st[keep]
        aen = self.en[keep]
        atm = self.tm[keep]
        ach = [c[keep] for c in self.ch]
        na, nb = len(akh), len(bkh)
        if nb == 0:
            self.set_rows(akh, ast_, aen, atm, ach)
            return
        # all rows of one key live on one side, so a key-level
        # searchsorted places every replacement row correctly
        ins = np.searchsorted(akh, bkh, side="left")
        bpos = ins + np.arange(nb, dtype=np.int64)
        total = na + nb
        okh = np.empty(total, dtype=np.uint64)
        ost = np.empty(total, dtype=np.int64)
        oen = np.empty(total, dtype=np.int64)
        otm = np.empty(total, dtype=np.int64)
        kmask = np.ones(total, dtype=bool)
        kmask[bpos] = False
        okh[bpos], ost[bpos], oen[bpos], otm[bpos] = bkh, bst, ben, btm
        okh[kmask], ost[kmask], oen[kmask], otm[kmask] = (akh, ast_, aen,
                                                          atm)
        och: List[np.ndarray] = []
        for ac, bc in zip(ach, bch):
            oc = np.empty(total, dtype=_common_dtype((ac, bc)))
            oc[bpos] = bc
            oc[kmask] = ac
            och.append(oc)
        self.set_rows(okh, ost, oen, otm, och)

    # -- device residency --------------------------------------------------

    def stage(self, device: Any = None) -> None:
        """Stage the ``(start, end)`` interval planes onto this
        partition's mesh device (idempotent; restaged after every
        splice while hot so the planes always mirror the run)."""
        import jax
        import jax.numpy as jnp

        if device is not None:
            self.dev_device = device
        st = jnp.asarray(self.st)
        en = jnp.asarray(self.en)
        if self.dev_device is not None:
            st = jax.device_put(st, self.dev_device)
            en = jax.device_put(en, self.dev_device)
        self.dev = (st, en)
        perf.count("session_state_stages")

    def unstage(self) -> None:
        if self.dev is not None:
            self.dev = None
            perf.count("session_state_unstages")


class SessionRunState:
    """Device-capable session-window state (module docstring).  Duck-
    types :class:`~arroyo_tpu.state.tables.KeyedState` — the per-key
    API (``get``/``insert``/``remove``/``items``) keeps the legacy
    clamp path and checkpoint interchange working against the same
    object that serves the vectorized batch merge."""

    def __init__(self, n_partitions: Optional[int] = None,
                 max_span: Optional[int] = None):
        from ..engine.operators_window import MAX_SESSION_SIZE_MICROS

        self.P = n_partitions or session_partitions()
        self.parts = [_SessionPartition() for _ in range(self.P)]
        self.max_span = (MAX_SESSION_SIZE_MICROS if max_span is None
                         else max_span)
        self._uid = next(_SESSION_UIDS)
        self._merges = 0
        # accumulator channels (``configure``): none until an operator
        # that folds aggregates at the merge asks for them
        self.kinds: Tuple[str, ...] = ()
        self.dtypes: Tuple[np.dtype, ...] = ()

    def configure(self, kinds: Sequence[str],
                  dtypes: Sequence[Any]) -> None:
        """Carry one accumulator channel per ``kinds`` entry (a
        :data:`FOLD_KINDS` name) beside every session row.  The partial
        channels hold ``dtypes``, and channels a restore brought are
        cast to them.  A "first" channel (a key column's values) takes
        the type of its values and widens as they do, as the column
        itself does from batch to batch (an integer column that meets a
        NULL turns float, or object); its dtype here only types the
        empty channels, which never decide a widened dtype."""
        assert all(k in FOLD_KINDS for k in kinds), kinds
        self.kinds = tuple(kinds)
        self.dtypes = tuple(np.dtype(d) for d in dtypes)
        for part in self.parts:
            if len(part.ch) == len(self.kinds):
                part.ch = [c if k == "first" else c.astype(d, copy=False)
                           for k, c, d in zip(self.kinds, part.ch,
                                              self.dtypes)]
            else:
                assert part.n == 0, "sessions without their channels"
                part.ch = [np.empty(0, dtype=d) for d in self.dtypes]

    # -- routing -----------------------------------------------------------

    def _route(self, kh: np.ndarray) -> np.ndarray:
        return (kh & np.uint64(self.P - 1)).astype(np.int64)

    def _part_of(self, kh: int) -> _SessionPartition:
        return self.parts[int(kh) & (self.P - 1)]

    # -- vectorized batch merge --------------------------------------------

    def merge_intervals(self, ikh: np.ndarray, ist: np.ndarray,
                        ien: np.ndarray, itm: np.ndarray,
                        ich: Sequence[np.ndarray] = ()) -> np.ndarray:
        """Merge a batch's candidate session intervals (sorted by
        ``(key, start)``, gap already applied to ends, with their
        partials ``ich`` in the configured channels) into the resident
        runs — ONE union dispatch across every touched key.  Returns the
        keys whose merged span would cross the max-session clamp; their
        resident rows are left UNTOUCHED for the caller's authoritative
        per-key re-merge."""
        m = len(ikh)
        if m == 0:
            return np.zeros(0, dtype=np.uint64)
        from ..ops.session import session_device_enabled, union_sorted_intervals

        dest = self._route(ikh)
        touched_parts = np.unique(dest).tolist()
        dkeys = np.unique(ikh)
        # 1. pull the touched keys' resident rows out of each partition
        pulled: Dict[int, Tuple[Any, ...]] = {}
        keeps: Dict[int, np.ndarray] = {}
        for p in touched_parts:
            part = self.parts[p]
            tm_mask = part.touched_mask(dkeys)
            keeps[p] = ~tm_mask
            pulled[p] = (part.kh[tm_mask], part.st[tm_mask],
                         part.en[tm_mask], part.tm[tm_mask],
                         [c[tm_mask] for c in part.ch])
        # 2. one global (key, start) sort of touched-resident + delta
        #    rows, then ONE vectorized union for ALL keys at once
        ckh = np.concatenate([pulled[p][0] for p in touched_parts] + [ikh])
        cst = np.concatenate([pulled[p][1] for p in touched_parts] + [ist])
        cen = np.concatenate([pulled[p][2] for p in touched_parts] + [ien])
        ctm = np.concatenate([pulled[p][3] for p in touched_parts] + [itm])
        cch = [concat_channels([pulled[p][4][j] for p in touched_parts]
                               + [ich[j]])
               for j in range(len(self.kinds))]
        order = np.lexsort((cst, ckh))
        ckh, cst, cen, ctm = ckh[order], cst[order], cen[order], ctm[order]
        dev = session_device_enabled()
        m_kh, m_st, m_en, _sid, sess_first = union_sorted_intervals(
            ckh, cst, cen, device=dev)
        m_tm = np.maximum.reduceat(ctm, sess_first)
        # the partials fold at the same session boundaries as the time
        m_ch = fold_channels(self.kinds, [c[order] for c in cch], sess_first)
        self._merges += 1
        perf.count("session_merge_dispatches")
        if dev:
            perf.count("session_merge_device_dispatches")
        # 3. clamp detection: an unclamped union span over the max is
        #    EXACTLY the condition under which the legacy per-key merge
        #    would have clamped (ops/session.py module docstring) —
        #    those keys fall back wholesale, state untouched
        over = (m_en - m_st) > self.max_span
        if over.any():
            flagged = np.unique(m_kh[over])
            ok_rows = ~np.isin(m_kh, flagged)
            m_kh, m_st, m_en, m_tm = (m_kh[ok_rows], m_st[ok_rows],
                                      m_en[ok_rows], m_tm[ok_rows])
            m_ch = [c[ok_rows] for c in m_ch]
            flag_mask = np.isin(ikh, flagged)
            host_rows = int(flag_mask.sum())
        else:
            flagged = np.zeros(0, dtype=np.uint64)
            host_rows = 0
        _count_merge(m - host_rows, 0)  # caller counts fallback rows
        # 4. splice merged runs back per partition; flagged keys keep
        #    their resident rows (restored from the pulled copies)
        mdest = self._route(m_kh)
        for p in touched_parts:
            part = self.parts[p]
            sel = mdest == p
            bkh, bst, ben, btm = (m_kh[sel], m_st[sel], m_en[sel],
                                  m_tm[sel])
            bch = [c[sel] for c in m_ch]
            if len(flagged):
                # resident rows of flagged keys re-enter untouched;
                # their keys are disjoint from the merged keys so the
                # combined replacement stays (key, start)-sortable
                rkh, rst, ren, rtm, rch = pulled[p]
                fm = np.isin(rkh, flagged)
                if fm.any():
                    bkh = np.concatenate([bkh, rkh[fm]])
                    bst = np.concatenate([bst, rst[fm]])
                    ben = np.concatenate([ben, ren[fm]])
                    btm = np.concatenate([btm, rtm[fm]])
                    bch = [concat_channels([b, r[fm]])
                           for b, r in zip(bch, rch)]
                    o = np.lexsort((bst, bkh))
                    bkh, bst, ben, btm = bkh[o], bst[o], ben[o], btm[o]
                    bch = [c[o] for c in bch]
            part.splice(keeps[p], bkh, bst, ben, btm, bch)
            part.touches = 0.9 * part.touches + 0.1 * int(sel.sum()) * 10
        self._rebalance_hot()
        if self._merges % 16 == 1:
            reg = perf.get_note("session_state_registry")
            if not isinstance(reg, dict):
                reg = {}
                perf.note("session_state_registry", reg)
            reg[self._uid] = self.stats()
        return flagged

    def _rebalance_hot(self) -> None:
        """Join-state hot-set policy: top-``budget`` partitions by EWMA
        row frequency keep device-staged interval planes, with decay and
        2-slot hysteresis so borderline partitions don't flap."""
        from ..ops.session import session_device_enabled

        if not session_device_enabled():
            for part in self.parts:
                part.unstage()
            return
        budget = _hot_budget()
        floor = _hot_min_rows()
        for part in self.parts:
            part.touches *= 0.98
        ranked = sorted(range(self.P),
                        key=lambda p: (-self.parts[p].touches, p))
        hot = {p for p in ranked[:budget]
               if self.parts[p].touches >= floor}
        grace = set(ranked[: budget + 2])
        from ..parallel.mesh_window import place_session_partition

        for p, part in enumerate(self.parts):
            if p in hot and part.dev is None:
                part.stage(device=place_session_partition(p))
            elif part.dev is not None and p not in hot and (
                    part.touches < floor / 2 or p not in grace):
                part.unstage()

    # -- watermark fires ---------------------------------------------------

    def expire(self, watermark: int
               ) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                          List[np.ndarray], List[int]]:
        """Mask-compress every session with ``end <= watermark`` out of
        the runs.  Returns ``(keys, starts, ends, channels)`` of the
        fired sessions plus the fully-expired keys (for ``note_delete``
        tombstones).  Remaining rows of partially fired keys take
        ``watermark`` as their update time — the legacy
        ``windows.insert(watermark, kh, remain)`` contract."""
        fk: List[np.ndarray] = []
        fs: List[np.ndarray] = []
        fe: List[np.ndarray] = []
        fch: List[List[np.ndarray]] = [[] for _ in self.kinds]
        removed: List[int] = []
        for part in self.parts:
            if part.n == 0:
                continue
            fired = part.en <= watermark
            fi = np.flatnonzero(fired)
            if not len(fi):
                continue
            fk.append(part.kh[fi])
            fs.append(part.st[fi])
            fe.append(part.en[fi])
            for out, c in zip(fch, part.ch):
                out.append(c[fi])
            kept = ~fired
            ki = np.flatnonzero(kept)
            # per key run of the (key, start)-sorted partition: how many
            # of its sessions stay — none: the key is gone; some, with
            # some fired: its remaining rows take the watermark as time
            n = part.n
            newkey = np.empty(n, dtype=bool)
            newkey[0] = True
            newkey[1:] = part.kh[1:] != part.kh[:-1]
            run_first = np.flatnonzero(newkey)
            run_len = np.diff(np.append(run_first, n))
            run_kept = np.add.reduceat(kept.astype(np.int64), run_first)
            removed.extend(part.kh[run_first[run_kept == 0]].tolist())
            partial = np.repeat((run_kept > 0) & (run_kept < run_len),
                                run_len)[ki]
            ktm = np.where(partial, np.int64(watermark), part.tm[ki])
            part.set_rows(part.kh[ki], part.st[ki], part.en[ki], ktm,
                          [c[ki] for c in part.ch])
        if not fk:
            z = np.zeros(0, dtype=np.int64)
            return (np.zeros(0, dtype=np.uint64), z, z.copy(),
                    [np.empty(0, dtype=d) for d in self.dtypes], removed)
        return (np.concatenate(fk), np.concatenate(fs),
                np.concatenate(fe), [np.concatenate(c) for c in fch],
                removed)

    def min_end(self) -> Optional[int]:
        ends = [int(part.en.min()) for part in self.parts if part.n]
        return min(ends) if ends else None

    def min_live_start(self) -> Optional[int]:
        starts = [int(part.st.min()) for part in self.parts if part.n]
        return min(starts) if starts else None

    # -- KeyedState duck interface (per-key fallback + checkpoints) --------

    def insert(self, time: int, key: Any, value: Sequence[Tuple[Any, ...]]
               ) -> None:
        """Replace ``key``'s sessions with ``value``, ``(start, end,
        *channels)`` tuples."""
        part = self._part_of(key)
        sl = part.key_slice(key)
        keep = np.ones(part.n, dtype=bool)
        keep[sl] = False
        rows = sorted(value, key=lambda r: (int(r[0]), int(r[1])))
        nb = len(rows)
        bkh = np.full(nb, np.uint64(key), dtype=np.uint64)
        bst = np.fromiter((r[0] for r in rows), dtype=np.int64, count=nb)
        ben = np.fromiter((r[1] for r in rows), dtype=np.int64, count=nb)
        btm = np.full(nb, int(time), dtype=np.int64)
        bch = [channel_array([r[2 + j] for r in rows], d)
               for j, d in enumerate(self.dtypes)]
        part.splice(keep, bkh, bst, ben, btm, bch)

    @staticmethod
    def _sessions(part: _SessionPartition, lo: int, hi: int
                  ) -> List[Tuple[Any, ...]]:
        """Rows ``lo:hi`` of a partition as ``(start, end, *channels)``
        tuples (channels as numpy scalars, so their dtypes survive a
        checkpoint)."""
        return list(zip(part.st[lo:hi].tolist(), part.en[lo:hi].tolist(),
                        *(c[lo:hi] for c in part.ch)))

    def get(self, key: Any) -> Optional[List[Tuple[Any, ...]]]:
        part = self._part_of(key)
        sl = part.key_slice(key)
        if sl.start == sl.stop:
            return None
        return self._sessions(part, sl.start, sl.stop)

    def get_time(self, key: Any) -> Optional[int]:
        part = self._part_of(key)
        sl = part.key_slice(key)
        if sl.start == sl.stop:
            return None
        return int(part.tm[sl].max())

    def remove(self, key: Any) -> None:
        part = self._part_of(key)
        sl = part.key_slice(key)
        if sl.start == sl.stop:
            return
        keep = np.ones(part.n, dtype=bool)
        keep[sl] = False
        z = np.zeros(0, dtype=np.int64)
        part.splice(keep, np.zeros(0, dtype=np.uint64), z, z.copy(),
                    z.copy(), [c[:0] for c in part.ch])

    def items(self) -> Iterator[Tuple[int, List[Tuple[Any, ...]]]]:
        for part in self.parts:
            n = part.n
            if n == 0:
                continue
            bounds = np.nonzero(np.concatenate(
                [[True], part.kh[1:] != part.kh[:-1]]))[0]
            bounds = np.append(bounds, n)
            for i in range(len(bounds) - 1):
                lo, hi = int(bounds[i]), int(bounds[i + 1])
                yield int(part.kh[lo]), self._sessions(part, lo, hi)

    def snapshot(self) -> List[Tuple[int, Any, Any]]:
        """The KEYED table entry form — ``[(time, key, sessions)]`` —
        so epochs interchange with the legacy KeyedState layout in both
        directions (and rescale's key-range filter applies per key)."""
        out: List[Tuple[int, Any, Any]] = []
        for kh, sessions in self.items():
            out.append((self.get_time(kh) or 0, kh, sessions))
        return out

    def restore(self, entries: Sequence[Tuple[int, Any, Any]]) -> None:
        """Bulk-load KEYED entries (either layout wrote them) into
        sorted runs: one lexsort per partition, not one splice per
        key.  Sessions are ``(start, end, *channels)``; each channel
        takes the common type of its values (:func:`channel_array`)."""
        rows_kh: List[int] = []
        rows_st: List[int] = []
        rows_en: List[int] = []
        rows_tm: List[int] = []
        rows_ch: List[Tuple[Any, ...]] = []
        latest: Dict[int, Tuple[int, Any]] = {}
        for t, k, v in entries:
            latest[int(k)] = (int(t), v)  # last write wins (restore order)
        for k, (t, v) in latest.items():
            for sess in v:
                rows_kh.append(k)
                rows_st.append(int(sess[0]))
                rows_en.append(int(sess[1]))
                rows_tm.append(t)
                rows_ch.append(tuple(sess[2:]))
        n_ch = len(rows_ch[0]) if rows_ch else len(self.kinds)
        assert all(len(c) == n_ch for c in rows_ch), "mixed session forms"
        dtypes = (self.dtypes if n_ch == len(self.dtypes)
                  else (np.dtype(object),) * n_ch)  # types empty ones
        kh = np.array(rows_kh, dtype=np.uint64)
        st = np.array(rows_st, dtype=np.int64)
        en = np.array(rows_en, dtype=np.int64)
        tm = np.array(rows_tm, dtype=np.int64)
        ch = [channel_array([c[j] for c in rows_ch], d)
              for j, d in enumerate(dtypes)]
        dest = self._route(kh) if len(kh) else np.zeros(0, dtype=np.int64)
        for p in range(self.P):
            sel = dest == p
            pkh, pst, pen, ptm = kh[sel], st[sel], en[sel], tm[sel]
            o = np.lexsort((pst, pkh))
            self.parts[p].set_rows(pkh[o], pst[o], pen[o], ptm[o],
                                   [c[sel][o] for c in ch])

    def n_keys(self) -> int:
        total = 0
        for part in self.parts:
            if part.n:
                total += 1 + int((part.kh[1:] != part.kh[:-1]).sum())
        return total

    def __len__(self) -> int:
        return self.n_keys()  # KeyedState len() counts keys

    # -- observability -----------------------------------------------------

    def stats(self) -> Dict[str, Any]:
        """Session-state shape for bench/ops: resident rows (live
        session intervals), keys, hot (device-staged) partitions, and
        host-resident bytes while staging is active — bench's
        ``state_bounded`` gate holds ``rows`` against the session-churn
        horizon."""
        rows = sum(part.n for part in self.parts)
        hot = sum(1 for part in self.parts if part.dev is not None)
        host_bytes = sum(part.kh.nbytes + part.st.nbytes + part.en.nbytes
                         + part.tm.nbytes + sum(c.nbytes for c in part.ch)
                         for part in self.parts if part.dev is None)
        dev_set = {str(part.dev_device) for part in self.parts
                   if part.dev is not None and part.dev_device is not None}
        return {"partitions": self.P, "rows": rows, "keys": self.n_keys(),
                "hot_partitions": hot, "spill_bytes": host_bytes,
                "staged_devices": len(dev_set),
                "merge_dispatches": self._merges}


def channel_array(values: List[Any], dtype: Any = object) -> np.ndarray:
    """One channel's values as an array of their common type: numeric
    numpy scalars promote as ``np.concatenate`` promotes their arrays
    (int64 with float64 is float64), and any other value (None, a
    string) makes the channel object, holding exactly the values given.
    ``dtype`` types an empty channel."""
    if not values:
        return np.empty(0, dtype=dtype)
    types = {type(v) for v in values}
    if all(issubclass(t, (np.number, np.bool_)) for t in types):
        dt = np.result_type(*(np.dtype(t) for t in types))
    else:
        dt = np.dtype(object)
    out = np.empty(len(values), dtype=dt)
    out[:] = values
    return out


def _common_dtype(parts: Sequence[np.ndarray]) -> np.dtype:
    """The dtype ``np.concatenate`` gives ``parts``, the empty ones left
    out: an empty channel's dtype is a placeholder, never a value's."""
    full = [p.dtype for p in parts if len(p)]
    return np.result_type(*full) if full else parts[0].dtype


def concat_channels(parts: Sequence[np.ndarray]) -> np.ndarray:
    """Concatenate one channel's pieces, typed by the non-empty ones."""
    full = [p for p in parts if len(p)]
    return np.concatenate(full) if full else parts[0]


def aggregate_session_registry(reg: Optional[Dict[Any, Dict[str, Any]]]
                               ) -> Dict[str, Any]:
    """Fold the per-state stats registry into one shape summary for the
    bench counters block."""
    entries = list((reg or {}).values())
    if not entries:
        return {}
    out = {"partitions": max(e.get("partitions", 0) for e in entries),
           "states": len(entries)}
    for k in ("rows", "keys", "hot_partitions", "spill_bytes",
              "merge_dispatches"):
        out[k] = int(sum(e.get(k, 0) for e in entries))
    out["staged_devices"] = int(max(e.get("staged_devices", 0)
                                    for e in entries))
    return out
