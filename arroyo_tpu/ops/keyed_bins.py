"""Keyed binned aggregation state in device memory — the engine's core
windowing kernel (SURVEY.md "Core TPU kernel #2").

This is the TPU re-design of the reference's two-phase sliding aggregator
(/root/reference/arroyo-worker/src/operators/aggregating_window.rs:14-258):
the reference keeps per-(key, bin) pre-aggregates in a TimeKeyMap and, on
watermark advance, adds/retracts bins from an in-memory per-key view.  Here:

* the **key directory** lives on host: a sorted uint64 array of known key
  hashes with a parallel slot array (lookups are one vectorized
  ``np.searchsorted`` per batch; inserts are a vectorized merge);
* the **bin ring** lives in HBM as flat planes, one device array per
  channel (``values``) and one of row counts (``counts``) — C key slots x
  B time bins of ``slide`` width each, bin-major: the cell of slot ``s`` in
  ring bin ``b`` lies at ``b * C + s``.  A plane is scatter-reduced per
  flush by one jitted kernel that takes it donated and writes into it;
* **pane emission** on watermark advance is one device kernel over all
  pending panes at once: a gather of the panes' bin rows (a bin's C slots
  lie together in a plane) and a window reduce;
* eviction resets the expired bin rows on device, in place.

Capacity doubles when the key directory fills; shapes are powers of two so
recompiles are O(log keys).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..graph.logical import AggKind, AggSpec
from ..obs import perf, tracing
from ..obs.perf import in_phase as _in_phase, kernel_name, timed_device

# f64 extremes (the accumulation channels are float64, see ACC_DTYPE):
# f32 extremes here would clip MIN/MAX values beyond +/-3.4e38.
NEG_INF = float(jnp.finfo(jnp.float64).min)
POS_INF = float(jnp.finfo(jnp.float64).max)

# Numeric-fidelity policy (VERDICT r2 #5; the reference aggregates in exact
# i64/f64, aggregating_window.rs): all accumulation channels are
# float64 — int64 SUM/COUNT stay exact to 2^53, MIN/MAX preserve full int64
# comparisons below that, AVG divides exactly-summed numerators.  MIN/MAX
# null identities are f64 extremes (NEG_INF/POS_INF above) so values
# beyond +/-3.4e38 never clip.
ACC_DTYPE = np.float64


def _init_value(kind: AggKind) -> float:
    if kind == AggKind.MIN:
        return POS_INF
    if kind == AggKind.MAX:
        return NEG_INF
    return 0.0


def init_planes(ch_kinds: Tuple[str, ...], C: int, B: int):
    """Empty bin planes for C slots x B ring bins: one flat f64[B * C] per
    channel, filled with the channel's identity, and the flat i32 row counts.
    A plane is bin-major (slot ``s`` of ring bin ``b`` at ``b * C + s``)
    and one-dimensional because that is the form the TPU scatters into
    where it lies: a ``[C, B]`` operand is tiled (8, 128) in HBM, and each
    scatter over it was a copy of the plane to a line and, in a loop of B
    slices, back (PERF.md section 6, PR 33).  Each channel is an array of
    its own so that each is donated and aliased to its output by itself."""
    values = tuple(jnp.full((B * C,), _init_value(AggKind(kind)),
                            jnp.float64) for kind in ch_kinds)
    return values, jnp.zeros((B * C,), jnp.int32)


_LANES = 128  # the minor extent of a TPU tile


def _bin_rows(plane, C: int, B: int):
    """A flat plane by ring bin: ``[B, C / 128, 128]``, row ``b`` the C
    slots of bin ``b`` (``[B, 1, C]`` under 128 slots).  This and not
    ``[B, C]``, because with 128 slots innermost the tiled form of the
    view is the line itself and the reshape moves nothing; ``[B, C]``
    tiles bins with slots, and the TPU compiler copied the plane into
    that form, in a loop of B slices, in every program that read it."""
    lanes = min(C, _LANES)
    return plane.reshape(B, C // lanes, lanes)


@functools.lru_cache(maxsize=256)
def _update_kernel(kinds: Tuple[str, ...], C: int, B: int, n: int,
                   dup: Tuple[int, ...] = ()):
    dup_set = frozenset(dup)
    # a cell's place in a plane, and the one past its end, are int32
    assert B * C < 2 ** 31, (C, B)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    @kernel_name("bins_update")
    def run(values, counts, idx, packed):
        # TWO packed inputs (two host->device transfers: indices stay
        # i32 instead of riding the f64 pack at twice the bytes):
        # idx i32[2, n] rows are [slots, bins]; packed f64[k+1, n] rows
        # are [rowcount, channel values...] per pre-aggregated (key, bin)
        # cell.  rowcount 0 marks padding.  Channels in ``dup`` (COUNT(*))
        # accumulate exactly the rowcount, so their input never rides the
        # transfer — the kernel reconstructs it from packed[0].
        #
        # The planes arrive donated and every scatter writes into the
        # array it was given.  Padding and cells out of range are sent
        # past the plane's end and dropped there: they touch no cell.
        # (The host hands over each (slot, bin) at most once per
        # dispatch, so a cell takes one add whatever order the device
        # applies them in; a scatter told so, ``unique_indices``, ran no
        # faster on the chip: PERF.md section 6, PR 33.)
        slots = idx[0]
        bins = idx[1]
        rowcnt = packed[0]
        vals = packed[1:]
        ok = ((rowcnt > 0.5) & (slots >= 0) & (slots < C)
              & (bins >= 0) & (bins < B))
        at = jnp.where(ok, bins * C + slots, B * C)
        with jax.named_scope("scatter_counts"):
            counts = counts.at[at].add(rowcnt.astype(counts.dtype),
                                       mode="drop")
        outs = []
        r = 0
        for i, kind in enumerate(kinds):
            v = values[i]
            if i in dup_set:
                x = rowcnt
            else:
                x = vals[r]
                r += 1
            with jax.named_scope("scatter_accumulate"):
                if kind in ("sum", "avg", "count"):
                    v = v.at[at].add(x, mode="drop")
                elif kind == "min":
                    v = v.at[at].min(x, mode="drop")
                elif kind == "max":
                    v = v.at[at].max(x, mode="drop")
                else:
                    raise ValueError(kind)
            outs.append(v)
        return tuple(outs), counts

    return run


def _pane_reduce(kind: str, plane, C: int, B: int, ring, bin_ok):
    """One channel's pane aggregates ``[k, C]``: the bin rows of each
    pane's window (``ring[k, W]``, those in range marked by ``bin_ok``)
    gathered from the flat plane and reduced over W (shared by the dense
    and compacted emit kernels so the two paths cannot diverge)."""
    g = _bin_rows(plane, C, B)[ring]  # [k, W, C / 128, 128]
    ok = bin_ok[:, :, None, None]
    if kind in ("sum", "avg", "count"):
        r = jnp.sum(jnp.where(ok, g, 0), axis=1)  # the plane's dtype
    elif kind == "min":
        r = jnp.min(jnp.where(ok, g, POS_INF), axis=1)
    elif kind == "max":
        r = jnp.max(jnp.where(ok, g, NEG_INF), axis=1)
    else:
        raise ValueError(kind)
    return r.reshape(-1, C)


def _pane_counts(counts, C: int, B: int, ring, bin_ok):
    """Rows per key per pane ``[C, k]`` from the flat counts plane."""
    return _pane_reduce("sum", counts, C, B, ring, bin_ok).T


@functools.lru_cache(maxsize=256)
def _emit_kernel(kinds: Tuple[str, ...], C: int, B: int, W: int, k: int,
                 keep: Optional[Tuple[int, ...]] = None):
    """Compute per-key aggregates for k panes.  ``ring[k, W]`` (int32) and
    ``bin_ok[k, W]`` are computed on host from the absolute (int64) bin
    indices — keeping 64-bit bin arithmetic out of jit, where x64-disabled
    JAX would truncate it.  ``keep`` selects the channels that ride the
    device->host transfer (COUNT(*) channels are dropped — their pane
    output is exactly the counts plane, which transfers as integers
    anyway)."""
    if keep is None:
        keep = tuple(range(len(kinds)))

    @jax.jit
    @kernel_name("bins_emit")
    def run(values, counts, ring, bin_ok):
        with jax.named_scope("window_reduce_counts"):
            cnt = _pane_counts(counts, C, B, ring, bin_ok)

        outs = []
        for i in keep:
            # (avg division happens on host from the validity-count
            # channel — NOT from cnt, which counts null rows too)
            with jax.named_scope("window_reduce"):
                outs.append(_pane_reduce(kinds[i], values[i], C, B, ring,
                                         bin_ok).T)
        return (jnp.stack(outs) if outs else jnp.zeros((0, C, k))), cnt

    return run


@functools.lru_cache(maxsize=256)
def _argmax_nnz_kernel(C: int, B: int, W: int, k: int, minmax: str):
    """Phase 1 of argmax emission: pane counts + per-pane extremum stay
    device-resident; only the candidate total crosses (4 bytes).  The
    candidate mask is (cnt == pane extremum) & (cnt > 0) — every global
    argmax row is a local candidate, so this is a sound pre-filter for
    the downstream WindowArgmax stage."""

    @jax.jit
    @kernel_name("bins_argmax_nnz")
    def run(counts, ring, bin_ok):
        with jax.named_scope("window_reduce"):
            cnt = _pane_counts(counts, C, B, ring, bin_ok)
        with jax.named_scope("pane_extremum"):
            if minmax == "max":
                ext = jnp.max(cnt, axis=0)  # counts >= 0: empty cells lose
            else:
                big = jnp.iinfo(cnt.dtype).max
                ext = jnp.min(jnp.where(cnt > 0, cnt, big), axis=0)
        with jax.named_scope("candidate_select"):
            sel = (cnt == ext[None, :]) & (cnt > 0)
            return cnt, sel, jnp.sum(sel)

    return run


def _first_set_flags(flat, npad: int):
    """Flat indices of the first ``npad`` set flags of the 1-D bool
    ``flat``, in order, and ``len(flat)`` in the places past the count:
    ``jnp.nonzero(flat, size=npad, fill_value=len(flat))[0]`` element for
    element.  ``c[i]`` counts the set flags in ``flat[:i + 1]``, so the
    r-th set flag sits at the first ``i`` with ``c[i] >= r`` — ``npad``
    binary searches over the prefix sum, log2(len) gathers each, and a
    rank above the count finds ``len(flat)``.  ``jnp.nonzero`` takes the
    same prefix sum and then histograms it (``bincount``): a scatter-add
    with one update per FLAG, which the TPU applies one after another."""
    c = jnp.cumsum(flat, dtype=jnp.int32)
    return jnp.searchsorted(c, jnp.arange(1, npad + 1, dtype=jnp.int32),
                            side="left")


def _live_cells(flat):
    """Indices of the set flags of the 1-D bool ``flat``, ascending, and
    ``len(flat)`` in every place past their count:
    ``jnp.nonzero(flat, size=len(flat), fill_value=len(flat))[0]`` element
    for element, by ONE sort of int32 keys (a set flag's key is its index,
    a clear one's ``len(flat)``).  Where a fire picks a handful
    ``_first_set_flags`` is the cheaper; where it picks every live key its
    ``npad`` binary searches are log2(len) dependent gathers each: over
    2^21 flags the chip took 163 ms for 262,144 picks and 3.2 ms for this
    sort, whatever the count (PERF.md section 6, PR 29, names the run).
    The sort is the part that compiles slowly (15 s on the chip), so it
    sits in the scan's kernel, compiled once, and the pick at each
    readback size takes its first rows."""
    n = flat.shape[0]
    return jnp.sort(jnp.where(flat, jnp.arange(n, dtype=jnp.int32), n))


@functools.lru_cache(maxsize=256)
def _argmax_gather_kernel(C: int, B: int, W: int, k: int, npad: int):
    """Phase 2: gather ONLY the candidate cells' (key, pane, count).  The
    candidates are picked by ``_first_set_flags``, not by ``nonzero``:
    that one's per-flag scatter took 0.25 s of a fire over 2^22 flags on
    the chip, where the prefix sum both share takes under 1 ms and this
    pick's cost beyond it follows ``npad``, not C x k."""
    # the prefix sum, the ranks and the fill value C * k are int32
    assert C * k < 2 ** 31, (C, k)

    @jax.jit
    @kernel_name("bins_argmax_gather")
    def run(cnt, sel):
        flat = sel.reshape(-1)
        idx = _first_set_flags(flat, npad)
        ok = idx < C * k
        safe = jnp.where(ok, idx, 0)
        idx2 = jnp.stack([(safe // k).astype(jnp.int32),
                          (safe % k).astype(jnp.int32)])
        cnt_c = jnp.where(ok, cnt.reshape(-1)[safe], 0)
        return idx2, cnt_c

    return run


@functools.lru_cache(maxsize=256)
def _emit_count_kernel(C: int, B: int, W: int, k: int):
    """Phase 1 of compacted emission: pane counts and the live cells'
    positions (``_live_cells``, row-major) stay device-resident; only the
    live-cell total crosses (4 bytes instead of the [C, k] grid — the
    scalar sizes phase 2's static-shape compaction)."""
    # the positions and their fill value C * k are int32
    assert C * k < 2 ** 31, (C, k)

    @jax.jit
    @kernel_name("bins_emit_count")
    def run(counts, ring, bin_ok):
        cnt = _pane_counts(counts, C, B, ring, bin_ok)
        flat = cnt.reshape(-1) > 0
        return cnt, _live_cells(flat), jnp.sum(flat)

    return run


@functools.lru_cache(maxsize=256)
def _emit_compact_kernel(kinds: Tuple[str, ...], C: int, B: int, W: int,
                         k: int, keep: Tuple[int, ...], npad: int):
    """Phase 2: gather ONLY live (key, pane) cells.  The dense pane grid
    is C*k cells of which a fire typically touches a few percent (keys
    active inside one window span vs every key ever seen) — compacting on
    device shrinks the readback by that ratio and replaces the
    host-side np.nonzero scan.  The cells are the first ``npad`` of the
    live positions that phase 1 left on the device (row-major order, as
    ``nonzero`` gives them; ``C * k`` past the live count)."""

    @jax.jit
    @kernel_name("bins_emit_compact")
    def run(values, cnt, live, ring, bin_ok):
        flat = cnt.reshape(-1)  # [C * k]
        idx = (live[:npad] if npad <= C * k else jnp.concatenate(
            [live, jnp.full(npad - C * k, C * k, jnp.int32)]))
        ok = idx < C * k
        safe = jnp.where(ok, idx, 0)
        key_idx = (safe // k).astype(jnp.int32)
        pane_idx = (safe % k).astype(jnp.int32)
        cnt_c = jnp.where(ok, flat[safe], 0)
        outs = []
        for i in keep:
            r = _pane_reduce(kinds[i], values[i], C, B, ring, bin_ok)
            outs.append(r[pane_idx, key_idx])
        idx2 = jnp.stack([key_idx, pane_idx])
        return idx2, cnt_c, (jnp.stack(outs) if outs else
                             jnp.zeros((0, npad), jnp.float64))

    return run


@functools.lru_cache(maxsize=64)
def _linearize_kernel(kinds: Tuple[str, ...], C: int, B: int, L: int):
    """Materialize the LINEAR bin span [C, L] from the modular ring —
    one gather; bins outside the live range read as each channel's
    aggregation identity.  Feeds the ring-pane emission path."""

    @jax.jit
    @kernel_name("bins_linearize")
    def run(values, counts, ring_idx, ok):
        outs = []
        for i, kind in enumerate(kinds):
            g = _bin_rows(values[i], C, B)[ring_idx].reshape(L, C).T
            outs.append(jnp.where(ok[None, :], g,
                                  _init_value(AggKind(kind))))
        cg = jnp.where(ok[None, :],
                       _bin_rows(counts, C, B)[ring_idx].reshape(L, C).T, 0)
        return (jnp.stack(outs) if outs else
                jnp.zeros((0, C, L), jnp.float64)), cg

    return run


@functools.lru_cache(maxsize=256)
def _evict_kernel(kinds: Tuple[str, ...], C: int, B: int):
    @functools.partial(jax.jit, donate_argnums=(0, 1))
    @kernel_name("bins_evict")
    def run(values, counts, ring_slots, slot_valid):
        # reset the expired ring bins' rows, each plane where it lies
        mask = jnp.zeros((B,), dtype=bool).at[
            jnp.where(slot_valid, ring_slots, 0)].max(slot_valid)

        def reset(plane, init):
            return jnp.where(mask[:, None, None], init,
                             _bin_rows(plane, C, B)).reshape(-1)

        return (tuple(reset(values[i], _init_value(AggKind(kind)))
                      for i, kind in enumerate(kinds)),
                reset(counts, 0))

    return run


def _bucket(n: int, floor: int = 8) -> int:
    b = floor
    while b < n:
        b <<= 1
    return b


# Every shape a fire hands to ``jit`` is a function of the state's capacity
# (C, B), of the window (W) or of a power-of-two row bucket, never of
# ``next_slot``, of a density or of a row count as the data gives it: a
# job whose keys never return would otherwise compile in every fire.
_EMIT_ROWS_FLOOR = 1024  # smallest compacted readback, in rows


def restored_count_state(raw_counts: np.ndarray, promote_at: int
                         ) -> Tuple[int, np.dtype]:
    """(total restored rows, counts-plane dtype) for a snapshot restore —
    the single policy both KeyedBinState and MeshKeyedBinState apply: the
    plane dtype must cover pane SUMS (bounded by total mass), so restored
    mass at or beyond the promotion threshold restores straight into i64
    (fire_panes may run before any update(), where promotion normally
    triggers)."""
    total = int(raw_counts.sum())
    return total, (np.int64 if total >= promote_at else np.int32)


def _prefetch_host(*arrays) -> None:
    """Start device->host copies for every array before any blocking
    ``np.asarray``: every readback is a device->host sync, so N
    sequential materializations wait N times while prefetched ones
    overlap into ~one."""
    for a in arrays:
        start = getattr(a, "copy_to_host_async", None)
        if start is not None:
            try:
                start()
            except Exception:  # pragma: no cover - non-committed arrays
                pass


@_in_phase("h2d")
def _h2d(*host_arrays):
    """``jnp.asarray`` of a kernel's host inputs."""
    return [jnp.asarray(a) for a in host_arrays]


def _readback(state, dev) -> np.ndarray:
    """One blocking device->host readback of a fire or drain: the
    ``d2h_wait`` phase, counted, and kept on ``state._d2h`` for the fire's
    ``window.fire.d2h`` span (``state`` is whoever keeps that list: a
    state while the head of a fire or a drain runs, the
    :class:`PendingFire` in a fire's tail)."""
    tok = perf.begin_phase("d2h_wait")
    t0 = tracing.now_us()
    try:
        out = np.asarray(dev)  # arroyolint: disable=host-sync -- intentional pane-emission readback: fired panes must materialize on the host to become output batch columns
    finally:
        state._d2h.append((t0, tracing.now_us() - t0))
        perf.end_phase(tok)
    perf.count("d2h_syncs")
    perf.count("d2h_bytes", out.nbytes)
    return out


def _fire_done(state, watermark: Optional[int]) -> None:
    """Close the accounts of a pass that read panes back.  A watermark
    fire counts ``window_fires`` and records its readbacks as ONE
    ``window.fire.d2h`` span (from the first readback's start, as long as
    all of them together) carrying the watermark that ``window.fire`` and
    ``window.fire.emit`` carry; a checkpoint drain (``watermark`` None)
    has no ``window.fire`` around it and counts ``pane_drains``."""
    d2h, state._d2h = state._d2h, []
    if watermark is None:
        perf.count("pane_drains")
        return
    perf.count("window_fires")
    tracing.record_span(
        "window.fire.d2h", "window", d2h[0][0], sum(dur for _, dur in d2h),
        tid=perf.active_task_id(), args={"watermark": watermark})


# -- shared channel + directory semantics (single-device AND mesh state) -----
#
# The null-skipping accumulation rules and the host key directory are THE
# shared semantics between KeyedBinState and parallel/mesh_window's
# MeshKeyedBinState; they live here once so a fix cannot apply to one
# implementation and silently miss the other.


def build_channels(aggs: Tuple[AggSpec, ...]
                   ) -> Tuple[Tuple[str, ...], Dict[int, int]]:
    """(kernel channel kinds, visible-agg -> hidden-validity-channel map).

    One accumulation channel per visible agg (AVG accumulates as a sum),
    plus a hidden additive validity-count channel per column-reading agg
    so null (NaN) rows neither poison SUM/MIN/MAX nor inflate AVG's
    divisor (reference nulls-skipping semantics, aggregating_window.rs)."""
    ch_kinds: List[str] = []
    for a in aggs:
        ch_kinds.append("sum" if a.kind == AggKind.AVG else a.kind.value)
    valid_ch: Dict[int, int] = {}
    for i, a in enumerate(aggs):
        if a.column is not None and a.kind != AggKind.COUNT:
            valid_ch[i] = len(ch_kinds)
            ch_kinds.append("sum")
    return tuple(ch_kinds), valid_ch


def channel_input(aggs: Tuple[AggSpec, ...], ch_kinds: Tuple[str, ...],
                  valid_of: Dict[int, int], j: int,
                  agg_inputs: Dict[str, np.ndarray], n: int) -> np.ndarray:
    """Per-row contribution of channel ``j`` with nulls (NaN) masked to the
    channel's identity so they are skipped, not aggregated.
    ``valid_of`` maps hidden channel index -> source visible agg index."""
    from ..formats import coerce_float

    src = valid_of.get(j)
    if src is not None:  # hidden validity count for agg `src`
        raw = coerce_float(agg_inputs[aggs[src].column], ACC_DTYPE)
        return (~np.isnan(raw)).astype(ACC_DTYPE)
    a = aggs[j]
    if a.column is None:
        return np.ones(n, dtype=ACC_DTYPE)
    raw = coerce_float(agg_inputs[a.column], ACC_DTYPE)
    ok = ~np.isnan(raw)
    if a.kind == AggKind.COUNT:  # COUNT(col) counts non-null rows
        return ok.astype(ACC_DTYPE)
    ident = _init_value(AggKind(ch_kinds[j]))
    return np.where(ok, raw, ACC_DTYPE(ident)).astype(ACC_DTYPE)


def channel_inits(ch_kinds: Tuple[str, ...]) -> np.ndarray:
    """Per-channel aggregation identity values ([n_ch]), carried
    inside canonical snapshots so topology-level merges can pad
    uncovered bin spans with the right identity (+inf for MIN, -inf for
    MAX) instead of 0 — a 0-pad makes a post-rescale MIN/MAX window
    wrongly emit 0 for bins one parent never held."""
    return np.array([_init_value(AggKind(k)) for k in ch_kinds],  # arroyolint: disable=host-sync -- intentional canonical-snapshot/ring-relayout readback: rescale merges and ring growth operate on host copies by design
                    dtype=ACC_DTYPE)


def preaggregate(kh: np.ndarray, bins: np.ndarray,
                 ch_kinds: Tuple[str, ...], vals: np.ndarray
                 ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Two-phase aggregation, local half: reduce rows with the same
    (key, bin) on the host BEFORE device dispatch (the reference's
    TumblingLocalAggregator, plan_graph.rs:71-83 / optimizations.rs:241-291
    — pre-aggregate without shuffle, then the global phase merges bins).

    Every channel kind is reducible (sum/count add, min/max reduce), so
    this is lossless; under hot-key skew it collapses a 64k-row batch to
    a few thousand (key, bin) cells — less scatter work AND a smaller
    host->device transfer.

    Returns (unique key hashes, bins, per-cell row counts, reduced
    channel values [n_ch, n_cells]); inputs must be live rows only.
    """
    order = np.lexsort((bins, kh))
    kh_s, bin_s = kh[order], bins[order]
    is_first = np.ones(len(kh_s), dtype=bool)
    is_first[1:] = (kh_s[1:] != kh_s[:-1]) | (bin_s[1:] != bin_s[:-1])
    starts = is_first.nonzero()[0]
    vals_s = vals[:, order]
    out = np.empty((len(ch_kinds), len(starts)), dtype=ACC_DTYPE)
    for j, kind in enumerate(ch_kinds):
        if kind == "min":
            out[j] = np.minimum.reduceat(vals_s[j], starts)
        elif kind == "max":
            out[j] = np.maximum.reduceat(vals_s[j], starts)
        else:  # sum / count channels are additive
            out[j] = np.add.reduceat(vals_s[j], starts)
    rowcnt = np.diff(np.append(starts, len(kh_s))).astype(ACC_DTYPE)
    return kh_s[starts], bin_s[starts], rowcnt, out


# Pending-cell count at which buffered updates flush even without a
# reader: it bounds the host memory of the pending runs and the size of one
# scatter.  The shapes ``warm_fire`` compiles follow from it
# (``_update_rows_floor``), so it is a constant and not a setting.
UPDATE_FLUSH_CELLS = 65536


def _merge_cells(slots: np.ndarray, bins: np.ndarray, rowcnt: np.ndarray,
                 vals: np.ndarray, ch_kinds: Tuple[str, ...]
                 ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Reduce duplicate (slot, bin) cells across buffered batch runs —
    the cross-batch half of :func:`preaggregate`: value channels reduce
    by their kind, row counts add.  Keeps the flushed scatter no larger
    than the live cell set."""
    order = np.lexsort((bins, slots))
    s, b = slots[order], bins[order]
    is_first = np.ones(len(s), dtype=bool)
    is_first[1:] = (s[1:] != s[:-1]) | (b[1:] != b[:-1])
    starts = is_first.nonzero()[0]
    if len(starts) == len(s):
        return slots, bins, rowcnt, vals  # already unique
    v = vals[:, order]
    out = np.empty((vals.shape[0], len(starts)), dtype=ACC_DTYPE)
    for j, kind in enumerate(ch_kinds):
        if kind == "min":
            out[j] = np.minimum.reduceat(v[j], starts)
        elif kind == "max":
            out[j] = np.maximum.reduceat(v[j], starts)
        else:  # sum / count channels are additive
            out[j] = np.add.reduceat(v[j], starts)
    rc = np.add.reduceat(rowcnt[order], starts)
    return s[starts], b[starts], rc, out


def directory_insert(state, kh: np.ndarray, ensure_capacity) -> np.ndarray:
    """Vectorized key-hash -> slot lookup over the host directory attrs
    (``key_sorted``, ``slot_of_sorted``, ``next_slot``, ``slot_to_key``),
    inserting unknown keys.  ``ensure_capacity(total_slots, new_keys)`` is
    the growth hook (device-array growth for KeyedBinState, shard-count
    accounting + device growth for the mesh state).

    Fast path: when the state carries a native C++ hash directory
    (``state._ndir``), the per-row lookup is one O(n) linear-probe pass;
    the sorted arrays are still maintained (from the much smaller new-key
    set) because checkpointing and emission-time lookups read them."""
    ndir = getattr(state, "_ndir", None)
    if ndir is not None:
        slots, new_keys = ndir.insert(kh, state.next_slot)
        _append_new_keys(state, new_keys, ensure_capacity)
        return slots
    uniq = np.unique(kh)
    pos = np.searchsorted(state.key_sorted, uniq)
    pos_c = np.minimum(pos, max(len(state.key_sorted) - 1, 0))
    known = (len(state.key_sorted) > 0) & (
        state.key_sorted[pos_c] == uniq if len(state.key_sorted) else
        np.zeros(len(uniq), dtype=bool))
    new_keys = uniq[~known] if len(state.key_sorted) else uniq
    _append_new_keys(state, new_keys, ensure_capacity)
    idx = np.searchsorted(state.key_sorted, kh)
    return state.slot_of_sorted[idx]


def _append_new_keys(state, new_keys: np.ndarray, ensure_capacity) -> None:
    """Register new keys: sequential slots from ``next_slot`` (the order
    the native dir already assigned), slot_to_key update, and sorted-array
    merge.  Shared by the native and numpy directory paths so the
    checkpointable arrays stay bit-identical between builds."""
    if not len(new_keys):
        return
    n_new = len(new_keys)
    perf.count("keys_inserted", n_new)
    ensure_capacity(state.next_slot + n_new, new_keys)
    new_slots = np.arange(state.next_slot, state.next_slot + n_new)
    state.slot_to_key[new_slots] = new_keys
    state.next_slot += n_new
    merged = np.concatenate([state.key_sorted, new_keys])
    merged_slots = np.concatenate([state.slot_of_sorted, new_slots])
    order = np.argsort(merged, kind="stable")
    state.key_sorted = merged[order]
    state.slot_of_sorted = merged_slots[order]


class BinsOf(NamedTuple):
    """Each row's ring bin and liveness, and the live rows' least and
    greatest absolute bin (None where no row is live):
    :meth:`KeyedBinState.assign`."""

    bins: np.ndarray
    live: np.ndarray
    n_live: int
    lo: Optional[int]
    hi: Optional[int]


class AdmittedRows(NamedTuple):
    """What the loop half of an update (:meth:`KeyedBinState.admit`)
    hands its executor half (:meth:`KeyedBinState.apply`): every row's
    slot and ring bin, which rows are live and how many, and the
    batch's columns."""

    slots: np.ndarray
    bins: np.ndarray
    live: np.ndarray
    n_live: int
    agg_inputs: Dict[str, np.ndarray]


class FiredPanes(NamedTuple):
    """What a fire or a drain hands the operator: a row per fired
    (key, pane) cell.  ``slots`` is each row's host slot, the index the
    operator's key columns are stored under: a state resolves it itself
    (:class:`KeyedBinState` fires slots and keeps them; the mesh state
    fires cells of a shard and looks the slot up by hash, counted in
    ``fire_slot_lookups``), so the operator never asks which state it
    holds."""

    keys: np.ndarray
    cols: Dict[str, np.ndarray]
    window_end: np.ndarray
    counts: np.ndarray
    slots: np.ndarray


@dataclasses.dataclass(eq=False)
class PendingFire:
    """What the head of a fire (:meth:`KeyedBinState.fire_head`) leaves
    its tail (:meth:`KeyedBinState.fire_tail`): everything the read-back
    and the flatten need, so that the tail touches nothing an update
    reads or writes and may run beside the next batches.

    ``devs`` are the pick's outputs on the device, their copies to the
    host already started (``idx2[2, npad]``, the counts, and the channel
    block where channels ride the transfer), ``nnz`` how many of their
    rows are live; the bin-sharded ring route reads back in the head and
    leaves the flattened host ``cells`` instead.  ``slot_to_key`` is the
    array the head saw (a grow replaces the state's, an insert writes
    only past the slots that fired).  ``_d2h`` are the spans of the
    fire's blocking read-backs so far (:func:`_readback`)."""

    watermark: int
    pane_ends: np.ndarray
    slot_to_key: np.ndarray
    nnz: int
    devs: tuple = ()
    cells: Optional[tuple] = None
    _d2h: List[Tuple[float, float]] = dataclasses.field(default_factory=list)


class KeyedBinState:
    """Sharded keyed bin-ring aggregation state for one subtask."""

    # rows after which the i32 counts plane could wrap (class attr so
    # tests can exercise the promotion without 2^31 rows)
    _i32_promote = 2**31 - 1

    def __init__(self, aggs: Tuple[AggSpec, ...], slide_micros: int,
                 width_micros: int, capacity: int = 0):
        if capacity <= 0:
            # pre-size from config: capacity growth doubles the arrays and
            # recompiles the kernels, so starting near the expected key
            # cardinality avoids O(log C) recompile stalls mid-stream
            from ..config import config

            capacity = config().state_capacity
        assert width_micros % slide_micros == 0, (
            "window width must be a multiple of slide")
        self.aggs = aggs
        self.kinds = tuple(a.kind.value for a in aggs)
        self._ch_kinds, self._valid_ch = build_channels(aggs)
        self._valid_of = {v: k for k, v in self._valid_ch.items()}
        # COUNT(*) channels accumulate exactly the per-cell row count that
        # the i32 counts plane already holds — they never ride a
        # transfer: updates reconstruct them on device from the rowcount
        # row, emission reads them from the counts output (state still
        # carries them so canonical snapshots stay topology-portable)
        self._dup_ch = tuple(i for i, a in enumerate(aggs)
                             if a.kind == AggKind.COUNT and a.column is None)
        dup_set = frozenset(self._dup_ch)
        self._xfer_ch = tuple(j for j in range(len(self._ch_kinds))
                              if j not in dup_set)
        self._xfer_pos = {j: r for r, j in enumerate(self._xfer_ch)}
        self.slide = slide_micros
        self.W = width_micros // slide_micros  # bins per window
        # ring must hold all open bins: W for the widest window plus headroom
        # for out-of-order arrivals ahead of the watermark
        self.B = _bucket(2 * self.W + 4, floor=8)
        self.C = _bucket(capacity)

        self.key_sorted = np.zeros(0, dtype=np.uint64)  # sorted known hashes
        self.slot_of_sorted = np.zeros(0, dtype=np.int64)
        self.next_slot = 0
        self.slot_to_key = np.zeros(self.C, dtype=np.uint64)
        from ..native import NativeDir

        self._ndir = NativeDir.create(self.C)

        # the planes (``init_planes`` has their layout): ``values`` one
        # flat f64 array per channel, ``counts`` the flat row counts.  The
        # update and the evict take them donated, so a handle read before
        # such a call is dead after it: every caller rebinds both at once
        self.values, self.counts = init_planes(self._ch_kinds, self.C,
                                               self.B)

        self.min_bin: Optional[int] = None  # oldest retained absolute bin
        self.max_bin: Optional[int] = None
        self.last_fired_pane: Optional[int] = None
        # rows ever accumulated into the counts plane: any cell or pane
        # sum is bounded by it, so while it stays below 2^31 the i32 plane
        # (and the COUNT(*) outputs read from it) cannot wrap — once it
        # could, update() promotes the plane to i64 (one recompile)
        self.total_rows = 0
        # set via set_argmax_local: emission keeps only local per-pane
        # argmax candidates (planner-proven sole consumer settles the
        # global answer); only COUNT(*) values qualify (see planner)
        self._argmax_local: Optional[str] = None  # 'max' | 'min'
        # update coalescing: per-batch pre-aggregated cell runs buffer
        # HERE and flush to the device in one merged scatter when a reader
        # needs the planes (pane fire, snapshot, ring relayout) or the
        # buffer reaches UPDATE_FLUSH_CELLS — one dispatch + one h2d transfer
        # amortizes across many batches (the dominant per-batch device
        # cost once the ingest spine killed the expression dispatches)
        self._pending: List[Tuple[np.ndarray, np.ndarray, np.ndarray,
                                  np.ndarray]] = []
        self._pending_cells = 0
        # merge-input mode (factor windows, graph/factor_windows.py):
        # channel j reads an ALREADY-AGGREGATED per-pane partial column
        # instead of deriving its contribution from raw rows, and the
        # counts plane accumulates the per-pane row-mass column — the
        # derived-window ring is then bit-compatible with the ring the
        # unfactored member would have built from the same rows
        self._merge_cols: Optional[Dict[int, str]] = None
        self._rows_col: Optional[str] = None
        # (start, duration) in tracing microseconds of each blocking
        # readback of the fire under way
        self._d2h: List[Tuple[float, float]] = []

    # -- key directory -----------------------------------------------------

    @_in_phase("dir_insert")
    def _lookup_or_insert(self, kh: np.ndarray) -> np.ndarray:
        """Vectorized key hash -> slot id, inserting unknown keys."""
        def ensure(total, _new_keys):
            if total > self.C:
                self._grow(total)

        return directory_insert(self, kh, ensure)

    def _grow(self, needed: int) -> None:
        perf.count("state_grows")
        newC = self.C
        while newC < needed:
            newC <<= 1
        pad = newC - self.C

        def widen(plane, init):
            # every bin row grows by ``pad`` empty slots
            return jnp.concatenate(
                [plane.reshape(self.B, self.C),
                 jnp.full((self.B, pad), init, plane.dtype)],
                axis=1).reshape(-1)

        self.values = tuple(
            widen(v, _init_value(AggKind(kind)))
            for v, kind in zip(self.values, self._ch_kinds))
        self.counts = widen(self.counts, 0)
        self.slot_to_key = np.concatenate(
            [self.slot_to_key, np.zeros(pad, dtype=np.uint64)])
        self.C = newC

    # -- update ------------------------------------------------------------

    def set_merge_inputs(self, channel_cols: Dict[int, str],
                         rows_col: str) -> None:
        """Arm merge-input mode (must run before any row lands): channel
        ``j`` reads ``channel_cols[j]`` — a per-(key, pane) partial of
        its own kind (NaN = pane had no contributing rows, masked to the
        channel identity) — and the per-cell row count accumulates
        ``rows_col`` so COUNT(*) dup channels and the u16-proof bounds
        stay exact row masses, not pane-arrival counts."""
        assert self.next_slot == 0 and self.total_rows == 0, \
            "merge inputs must be set before any key is admitted"
        for j in self._xfer_ch:
            assert j in channel_cols, f"no merge column for channel {j}"
        self._merge_cols = dict(channel_cols)
        self._rows_col = rows_col

    def update(self, key_hash: np.ndarray, timestamps: np.ndarray,
               agg_inputs: Dict[str, np.ndarray]) -> None:
        """An update in one call: its loop half (:meth:`admit`) and its
        executor half (:meth:`apply`), one after the other."""
        rows = self.admit(key_hash, timestamps, agg_inputs)
        if rows is not None:
            self.apply(rows)

    def assign(self, timestamps: np.ndarray) -> BinsOf:
        """Each row's ring bin and liveness against ``last_fired_pane``,
        and the live rows' least and greatest absolute bin: one native
        pass that writes nothing, so its caller can ask
        :meth:`replaces_planes` before :meth:`admit` takes it."""
        from ..native import assign_bins

        threshold = (self.last_fired_pane - self.W + 2
                     if self.last_fired_pane is not None else None)
        return BinsOf(*assign_bins(timestamps, self.slide, self.B,
                                   threshold))

    def replaces_planes(self, n_rows: int, bins: BinsOf) -> bool:
        """Whether admitting ``n_rows`` rows of ``bins`` may rebind the
        planes: a ``_grow`` (the rows bound their new keys) or a
        ``_grow_ring``.  An update in flight holds the planes, so its
        caller waits for it first."""
        if self.next_slot + n_rows > self.C:
            return True
        if bins.n_live == 0:
            return False
        lo = bins.lo if self.min_bin is None else min(self.min_bin, bins.lo)
        hi = bins.hi if self.max_bin is None else max(self.max_bin, bins.hi)
        return hi - lo >= self.B

    @_in_phase("preagg")  # its directory lookup nests
    def admit(self, key_hash: np.ndarray, timestamps: np.ndarray,
              agg_inputs: Dict[str, np.ndarray],
              slots: Optional[np.ndarray] = None,
              bins: Optional[BinsOf] = None) -> Optional[AdmittedRows]:
        """The loop half of an update: what a later batch's admission or
        a fire's check reads.  The bins (``_admit_bins``: liveness,
        ``min_bin`` / ``max_bin``, a ring growth) and the directory (the
        rows' slots, new keys inserted, a ``_grow``).  ``slots`` and
        ``bins`` where the caller has them already (its key columns are
        stored by slot; :meth:`replaces_planes` took the bins).  Returns
        what :meth:`apply` needs, or None where no row is live."""
        n = len(key_hash)
        if n == 0:
            return None
        # the factor-window cost claim, made measurable: rows entering
        # pane-update state per event is ~K unfactored (every ring sees
        # every event) vs ~1 + O(panes) factored (derived rings see only
        # fired pane cells) — the correlated_windows bench reads these
        perf.count("pane_update_rows", n)
        admitted = self._admit_bins(timestamps, bins)
        if admitted is None:
            return None
        if slots is None:
            slots = self._lookup_or_insert(key_hash)
        return AdmittedRows(slots, admitted[0], admitted[1],
                            int(admitted[2]), agg_inputs)

    @_in_phase("preagg")  # its h2d and dispatch nest
    def apply(self, rows: AdmittedRows) -> None:
        """The executor half of an update: the row mass, the channel
        inputs, the per-(slot, bin) reduce, the enqueue and, at the
        bound, the flush.  It reads the slots and bins :meth:`admit`
        settled and never the directory, so it may run beside the next
        batch's :meth:`admit` (one at a time, in order: the caller's)."""
        if self._merge_cols is not None:
            self._apply_merged(rows)
            return
        slots, bins_mod, live, n_live, agg_inputs = rows
        n = len(slots)
        self._note_mass(n_live)
        # two-phase, local half: reduce rows per (slot, bin) on the host
        # before any device work (TumblingLocalAggregator analog) — under
        # hot-key skew this collapses the batch by orders of magnitude
        # COUNT(*) channels are reconstructed from the rowcount on device;
        # only the remaining channels are materialized, pre-aggregated, and
        # shipped (for a bare COUNT(*) query the f64 pack shrinks to the
        # rowcount row alone — half the h2d bytes per batch)
        xfer = self._xfer_ch
        xfer_kinds = tuple(self._ch_kinds[j] for j in xfer)
        vals = np.empty((len(xfer), n), dtype=ACC_DTYPE)
        for r, j in enumerate(xfer):
            vals[r] = self._channel_input(j, agg_inputs, n)
        from ..native import HAVE_NATIVE, agg_cells

        if HAVE_NATIVE:
            # one O(n) native hash pass (liveness filter folded in)
            slots_c, bins_c, rowcnt, vals_c = agg_cells(
                slots, bins_mod, None if live.all() else live,
                self.B, vals, xfer_kinds)
        else:
            if not live.all():
                idx = live.nonzero()[0]
                slots, bins_mod, vals = \
                    slots[idx], bins_mod[idx], vals[:, idx]
            slots_c, bins_c, rowcnt, vals_c = preaggregate(
                slots, bins_mod, xfer_kinds, vals)
        self._enqueue_cells(slots_c, bins_c, rowcnt, vals_c)

    def _admit_bins(self, timestamps: np.ndarray,
                    bins: Optional[BinsOf] = None
                    ) -> Optional[Tuple[np.ndarray, np.ndarray, int,
                                        int, int]]:
        """Shared update prologue (raw AND merge-input paths): a row in
        bin b feeds panes b..b+W-1 and is late (dropped) only when all
        those panes already fired — the reference's drop-behind-watermark
        semantics.  Bin assignment + liveness + min/max run as one
        native pass (:meth:`assign`, or ``bins`` where the caller ran
        it); returns (bins_mod, live, n_live, lo, hi), or None when
        nothing is live."""
        bins_mod, live, n_live, lo, hi = (self.assign(timestamps)
                                          if bins is None else bins)
        if n_live == 0:
            return None
        lo_new = lo if self.min_bin is None else min(self.min_bin, lo)
        hi_new = hi if self.max_bin is None else max(self.max_bin, hi)
        # ring capacity check BEFORE extending min/max: _grow_ring copies
        # the ring span [min_bin, max_bin] into the wider ring, so the
        # bounds must still describe what the OLD ring actually holds —
        # growing after extending them replicated old slots into the
        # about-to-be-written range (ghost duplicates under far-apart
        # sources, e.g. two impulse splits with staggered time bases)
        if hi_new - lo_new >= self.B:
            self._grow_ring(hi_new - lo_new + 1)
            bins_mod = ((timestamps // self.slide) % self.B).astype(np.int32)
        self.min_bin = lo_new
        self.max_bin = hi_new
        return bins_mod, live, n_live, lo, hi

    def _note_mass(self, mass: int) -> None:
        """Count accumulated row mass; the next accumulation could wrap
        an i32 cell or pane sum once the total crosses the promotion
        threshold, so promote BEFORE it lands (kernels retrace on the
        new dtype).  Shared by both update paths."""
        self.total_rows += mass
        if (self.total_rows >= self._i32_promote
                and self.counts.dtype == jnp.int32):
            self.counts = self.counts.astype(jnp.int64)

    def _enqueue_cells(self, slots_c: np.ndarray, bins_c: np.ndarray,
                       rowcnt: np.ndarray, vals_c: np.ndarray) -> None:
        """Shared update tail: buffer the pre-aggregated cell run (one
        merged scatter carries many batches; the planes are only read at
        pane fires / snapshots, and every reader flushes) and flush at
        the bound."""
        self._pending.append((slots_c, bins_c, rowcnt, vals_c))
        self._pending_cells += len(slots_c)
        if self._pending_cells >= UPDATE_FLUSH_CELLS:
            self.flush_updates()

    def _apply_merged(self, rows: AdmittedRows) -> None:
        """Merge-input update (derived windows), its executor half:
        inputs are fired factor panes, one row per (key, pane) — channel
        values come straight from the mapped partial columns (their kinds
        reduce partial → partial losslessly) and the per-cell rowcount is
        the SUM of the pane row-mass column, so the resulting ring is the
        one the unfactored member would hold after the same raw rows."""
        from ..formats import coerce_float

        slots, bins_mod, live, _n_live, agg_inputs = rows
        n = len(slots)
        w = coerce_float(agg_inputs[self._rows_col], ACC_DTYPE)
        w = np.where(np.isnan(w), 0.0, w)
        self._note_mass(int(np.ceil(w[live].sum())))

        xfer = self._xfer_ch
        xfer_kinds = tuple(self._ch_kinds[j] for j in xfer)
        vals = np.empty((len(xfer), n), dtype=ACC_DTYPE)
        for r, j in enumerate(xfer):
            raw = coerce_float(agg_inputs[self._merge_cols[j]], ACC_DTYPE)
            ident = ACC_DTYPE(_init_value(AggKind(self._ch_kinds[j])))
            vals[r] = np.where(np.isnan(raw), ident, raw)
        if not live.all():
            idx = live.nonzero()[0]
            slots, bins_mod = slots[idx], bins_mod[idx]
            vals, w = vals[:, idx], w[idx]
        # the row mass rides the cell reduction as one extra additive
        # channel so duplicate (slot, bin) cells sum their masses —
        # preaggregate's own rowcnt would count PANE ARRIVALS, which
        # COUNT(*) outputs and the u16 proof must never see
        ext_kinds = xfer_kinds + ("sum",)
        slots_c, bins_c, _arrivals, red = preaggregate(
            slots, bins_mod, ext_kinds, np.concatenate([vals, w[None]]))
        rowcnt = red[-1]
        vals_c = red[:-1]
        self._enqueue_cells(slots_c, bins_c, rowcnt, vals_c)

    @_in_phase("preagg")
    def flush_updates(self) -> None:
        """Apply every buffered pre-aggregated cell run to the device
        planes in ONE scatter dispatch.  Called by every plane reader
        (fire_panes, snapshot, ring relayout) and when the buffer
        crosses the cell bound, so deferral is invisible to emission,
        checkpoint and rescale semantics."""
        if not self._pending:
            return
        pend, self._pending = self._pending, []
        self._pending_cells = 0
        if len(pend) == 1:
            slots_c, bins_c, rowcnt, vals_c = pend[0]
        else:
            xfer_kinds = tuple(self._ch_kinds[j] for j in self._xfer_ch)
            slots_c, bins_c, rowcnt, vals_c = _merge_cells(
                np.concatenate([p[0] for p in pend]),
                np.concatenate([p[1] for p in pend]),
                np.concatenate([p[2] for p in pend]),
                np.concatenate([p[3] for p in pend], axis=1), xfer_kinds)
        self._dispatch_cells(slots_c, bins_c, rowcnt, vals_c)

    def _dispatch_cells(self, slots_c: np.ndarray, bins_c: np.ndarray,
                        rowcnt: np.ndarray, vals_c: np.ndarray) -> None:
        m = len(slots_c)
        perf.count("pane_update_dispatches")
        perf.count("pane_update_cells", m)
        npad = _bucket(m, floor=self._update_rows_floor())
        perf.count("pane_update_pad_cells", npad - m)
        kernel, idx, packed = self._pack_cells(
            slots_c, bins_c, rowcnt, vals_c, npad)
        self.values, self.counts = timed_device(
            kernel, self.values, self.counts, *_h2d(idx, packed))

    def _pack_cells(self, slots_c: np.ndarray, bins_c: np.ndarray,
                    rowcnt: np.ndarray, vals_c: np.ndarray, npad: int):
        """The update kernel at ``npad`` cells and its two input blocks
        for one (possibly empty) cell run: i32[2, npad] of slots and bins,
        f64[n_xfer + 1, npad] of rowcount and transferred channels;
        rowcount 0 marks padding.  The one place a dispatch's shapes are
        made, for the stream's flushes and for ``warm_fire`` alike."""
        m = len(slots_c)
        idx = np.zeros((2, npad), dtype=np.int32)
        idx[0, :m] = slots_c
        idx[1, :m] = bins_c
        packed = np.zeros((len(self._xfer_ch) + 1, npad), dtype=ACC_DTYPE)
        packed[0, :m] = rowcnt
        packed[1:, :m] = vals_c
        kernel = _update_kernel(self._ch_kinds, self.C, self.B, npad,
                                self._dup_ch)
        return kernel, idx, packed

    def _update_rows_floor(self) -> int:
        """Least cells an update dispatch is padded to.  A flush carries
        what gathered up to the flush bound, or, before a fire, whatever
        is left under it: padded to the bound's bucket (capped by the
        capacity, which small states stay under), the flushes of a stream
        take the two shapes ``warm_fire`` knows (a third only where one
        batch brings more cells than the bound), and not one per size of
        remainder."""
        return min(_bucket(UPDATE_FLUSH_CELLS), max(self.C, 256))

    def _channel_input(self, j: int, agg_inputs: Dict[str, np.ndarray],
                       n: int) -> np.ndarray:
        return channel_input(self.aggs, self._ch_kinds, self._valid_of, j,
                             agg_inputs, n)

    def _grow_ring(self, needed: int) -> None:
        """Rare: data spans more bins than the ring; re-layout host-side."""
        # buffered cell runs carry ring indices mod the OLD B — they must
        # land before the ring re-layout redefines the modulus
        self.flush_updates()
        perf.count("state_grows")
        newB = self.B
        while newB < needed:
            newB <<= 1
        vals, cnts = self.host_planes()
        oldB, self.B = self.B, newB
        new_vals, new_cnts = self._empty_host_planes(cnts.dtype)
        if self.min_bin is not None and self.max_bin is not None:
            for ab in range(self.min_bin, self.max_bin + 1):
                new_vals[:, ab % newB] = vals[:, ab % oldB]
                new_cnts[ab % newB] = cnts[ab % oldB]
        self._set_planes(new_vals, new_cnts)

    # -- pane emission ------------------------------------------------------

    def _use_ring(self) -> bool:
        """Select bin-dimension ring-parallel emission (SURVEY §5
        sequence-parallel discipline) for long windows: the [C, k, W]
        pane gather materializes W copies of the state, while the ring
        path does one linear gather plus a cumulative sweep with
        ``ppermute`` halos — worthwhile once W is large (long window /
        short slide) and there is a mesh to shard bins over."""
        import os

        mode = os.environ.get("ARROYO_RING", "auto")
        if mode == "off":
            return False
        if mode == "on":
            return True
        w_min = int(os.environ.get("ARROYO_RING_MIN_W", 64))
        return self.W >= w_min and len(jax.devices()) > 1

    def set_argmax_local(self, agg_out: str, minmax: str) -> None:
        """Enable candidate-only emission for the given COUNT(*) agg
        (the value IS the counts plane — enforced here, not just by the
        planner: a non-count target would silently rank by row counts)."""
        target = next((i for i, a in enumerate(self.aggs)
                       if a.output == agg_out), None)
        assert target is not None and target in self._dup_ch, (
            f"argmax_local target {agg_out!r} is not a bare COUNT(*) "
            f"aggregate of this state")
        assert minmax in ("max", "min"), minmax
        self._argmax_local = minmax

    def _argmax_candidates(self) -> bool:
        return self._argmax_local is not None and not self._xfer_ch

    def warm_fire(self) -> int:
        """Compile (or load from the persistent cache) the kernels of a
        one-pane fire before the first event, from the plan and the
        capacity alone: the flush of the buffered updates, the scan, and
        the pick at every row bucket from ``_EMIT_ROWS_FLOOR`` up to C, so
        that no fire meets a kernel it has not run, however many cells it
        finds live.  Each is called once on the empty planes (no bin is in
        range, so it reads nothing live).  Returns how many kernels it ran.  A later ``_grow``
        changes C and compiles what it then needs, as the update does."""
        ran = 0
        # the flush before a fire: at the floor and, for a flush at the
        # bound, one bucket above (an empty run is all padding: the planes
        # come back as they went in)
        empty = np.zeros(0, np.int32)
        no_vals = np.zeros((len(self._xfer_ch), 0), ACC_DTYPE)
        floor = self._update_rows_floor()
        for npad in (floor, 2 * floor):
            kernel, idx, packed = self._pack_cells(
                empty, empty, empty, no_vals, npad)
            self.values, self.counts = kernel(
                self.values, self.counts, *_h2d(idx, packed))
            ran += 1
        if self._use_ring():
            return ran  # the ring sweep's shapes follow the open span
        ring_j, ok_j = _h2d(np.zeros((1, self.W), np.int32),
                            np.zeros((1, self.W), bool))
        if self._argmax_candidates():
            nk = _argmax_nnz_kernel(self.C, self.B, self.W, 1,
                                    self._argmax_local)
            cnt_dev, sel_dev, _ = nk(self.counts, ring_j, ok_j)
            _argmax_gather_kernel(self.C, self.B, self.W, 1, 8)(
                cnt_dev, sel_dev)
            return ran + 2
        cnt_dev, live_dev, _ = _emit_count_kernel(
            self.C, self.B, self.W, 1)(self.counts, ring_j, ok_j)
        ran, npad = ran + 1, _EMIT_ROWS_FLOOR
        while True:
            _emit_compact_kernel(self._ch_kinds, self.C, self.B, self.W, 1,
                                 self._xfer_ch, npad)(
                self.values, cnt_dev, live_dev, ring_j, ok_j)
            ran += 1
            if npad >= self.C:
                return ran
            npad <<= 1

    def _emit_argmax(self, ring: np.ndarray, bin_ok: np.ndarray, kpad: int
                     ) -> Tuple[int, tuple]:
        """Candidate-only emission, the head's half: scan, the live count
        read back, the pick dispatched and its copies to the host started.
        ``(nnz, (idx2, counts))`` for :meth:`_read_picked`: only the cells
        at their pane's count extremum, a ~1000x smaller readback
        (ties-per-pane instead of every (key, pane) cell)."""
        ring_j, ok_j = _h2d(ring, bin_ok)
        nk = _argmax_nnz_kernel(self.C, self.B, self.W, kpad,
                                self._argmax_local)
        cnt_dev, sel_dev, nnz_dev = timed_device(
            nk, self.counts, ring_j, ok_j)
        nnz = int(_readback(self, nnz_dev))  # waits for the whole scan
        perf.count("pane_emit_cells", nnz)
        if nnz == 0:
            return 0, ()
        npad = _bucket(nnz, floor=8)
        gk = _argmax_gather_kernel(self.C, self.B, self.W, kpad, npad)
        devs = tuple(timed_device(gk, cnt_dev, sel_dev))
        _prefetch_host(*devs)
        return nnz, devs

    def _emit_compact(self, ring: np.ndarray, bin_ok: np.ndarray, kpad: int
                      ) -> Tuple[int, tuple]:
        """The live cells only, compacted on the device (row-major order:
        the dense path's ``np.nonzero`` order), the head's half: scan, the
        live count read back (one scalar sizes the pick), the pick
        dispatched on the planes as they are now and its copies to the
        host started.  ``(nnz, (idx2, counts[, channels]))`` for
        :meth:`_read_picked`."""
        ring_j, ok_j = _h2d(ring, bin_ok)
        ck = _emit_count_kernel(self.C, self.B, self.W, kpad)
        cnt_dev, live_dev, nnz_dev = timed_device(ck, self.counts, ring_j,
                                                   ok_j)
        nnz = int(_readback(self, nnz_dev))  # one scalar sizes phase 2
        perf.count("pane_emit_cells", nnz)
        if nnz == 0:
            return 0, ()
        gk = _emit_compact_kernel(self._ch_kinds, self.C, self.B, self.W,
                                  kpad, self._xfer_ch,
                                  _bucket(nnz, _EMIT_ROWS_FLOOR))
        idx2_d, cnt_d, ch_d = timed_device(gk, self.values, cnt_dev,
                                           live_dev, ring_j, ok_j)
        devs = (idx2_d, cnt_d) + ((ch_d,) if self._xfer_ch else ())
        _prefetch_host(*devs)
        return nnz, devs

    def _read_picked(self, fire: PendingFire
                     ) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                                np.ndarray]:
        """The tail's half of :meth:`_emit_compact` and
        :meth:`_emit_argmax`: the blocking read-backs of the pick's
        outputs.  (key_idx, pane_idx, counts, channel values [n_xfer,
        nnz]); reads the handle only."""
        n = fire.nnz
        idx2 = _readback(fire, fire.devs[0])
        return (idx2[0, :n].astype(np.int64), idx2[1, :n].astype(np.int64),
                _readback(fire, fire.devs[1])[:n],
                (_readback(fire, fire.devs[2])[:, :n] if len(fire.devs) > 2
                 else np.zeros((len(self._xfer_ch), n))))

    def _ring_shards(self) -> int:
        nk = 1
        while nk * 2 <= len(jax.devices()):
            nk *= 2
        return nk

    def _emit_ring(self, pane_ends: np.ndarray, k: int):
        """Pane aggregates for the contiguous ``pane_ends`` range via the
        bin-sharded ring kernel (parallel/ring_panes.py): linearize the
        span once, then one trailing-W sweep per channel."""
        from ..parallel.ring_panes import _ring_step_2d

        nk = self._ring_shards()
        a_lo = self.min_bin if self.min_bin is not None else 0
        a_hi = int(pane_ends[-1])
        L0 = a_hi - a_lo + 1
        L = max(-(-L0 // nk) * nk, nk)
        padl = L - L0
        abs_bins = np.arange(a_lo - padl, a_hi + 1, dtype=np.int64)
        ok = (abs_bins >= a_lo) & (abs_bins <= self.max_bin)
        ring_idx = (abs_bins % self.B).astype(np.int32)
        lin = _linearize_kernel(self._ch_kinds, self.C, self.B, L)
        g, cg = timed_device(lin, self.values, self.counts,
                             *_h2d(ring_idx, ok))
        # dispatch every channel sweep, then materialize: the transfers
        # overlap instead of each paying its own sync.
        # Channel set matches _emit_kernel's ``keep`` (COUNT(*) channels
        # come from the count sweep, which rides as i32)
        devs = []
        for i in self._xfer_ch:
            fn, sharding = _ring_step_2d(self._ch_kinds[i], nk, self.C,
                                         L // nk, self.W)
            out = timed_device(fn, jax.device_put(g[i], sharding))
            devs.append(out[:, -k:])  # slice on device: transfer k panes
        fn, sharding = _ring_step_2d("count", nk, self.C, L // nk, self.W)
        cdev = timed_device(fn, jax.device_put(cg.astype(jnp.float64),
                                               sharding))[:, -k:]
        _prefetch_host(*devs, cdev)
        outs = [_readback(self, d) for d in devs]
        # match the plane dtype: a promoted i64 plane can hold pane sums
        # beyond i32 (the sweep itself is exact in f64 to 2^53)
        cnt_np = (np.int64 if self.counts.dtype == jnp.int64 else np.int32)
        cnts = _readback(self, cdev).astype(cnt_np)
        return (np.stack(outs) if outs else
                np.zeros((0, self.C, k))), cnts

    def fire_panes(self, watermark: int, final: bool = False
                   ) -> Optional[FiredPanes]:
        """Emit all panes whose window end <= watermark: a fire's head and
        its tail, one after the other.

        Pane with absolute end-bin e covers bins (e-W, e]; its window end time
        is (e+1)*slide.  Returns (keys, {agg_output: values}, window_end,
        counts, slots) flattened over (pane, key-with-data), or None.
        """
        fire = self.fire_head(watermark, final)
        return None if fire is None else self.fire_tail(fire)

    def _panes_due(self, watermark: int, final: bool
                   ) -> Optional[Tuple[int, int]]:
        """The first and last absolute pane ``watermark`` closes, or None
        where it closes none.  Reads only what the loop half of an
        update settles (``admit``: ``max_bin``, ``min_bin``,
        ``next_slot``) and a fire's head (``last_fired_pane``)."""
        if self.max_bin is None or self.next_slot == 0:
            return None
        if final:
            # flush every window containing data: the last data bin feeds
            # panes up to max_bin + W - 1
            last_pane = self.max_bin + self.W - 1
        else:
            last_pane = min(int(watermark // self.slide) - 1, self.max_bin)
        first_pane = (self.last_fired_pane + 1
                      if self.last_fired_pane is not None
                      else (self.min_bin or 0))
        if last_pane < first_pane:
            return None
        return first_pane, last_pane

    def fire_due(self, watermark: int, final: bool = False) -> bool:
        """Whether :meth:`fire_head` would close a pane: its check alone,
        which an update in flight cannot change, so a watermark that
        fires nothing need not wait for one."""
        return self._panes_due(watermark, final) is not None

    def fire_head(self, watermark: int, final: bool = False
                  ) -> Optional[PendingFire]:
        """The half of a fire that reads or writes what a later update
        reads or writes, so the half its caller waits for before the next
        batch: the flush of the buffered updates, the pane arithmetic, the
        scan and the read-back of its live count, the dispatch of the pick
        (on the planes as they are now: the evict and the next update take
        them donated) and of the evict, ``last_fired_pane`` and ``min_bin``
        (an update drops its late rows by them).  Returns what
        :meth:`fire_tail` needs, or None where no row fires."""
        due = self._panes_due(watermark, final)
        if due is None:
            return None
        first_pane, last_pane = due
        # panes will actually fire: buffered batch updates must be in the
        # planes first (the early return above keeps no-op watermark
        # advances from forcing a flush per batch)
        self.flush_updates()
        pane_ends = np.arange(first_pane, last_pane + 1, dtype=np.int64)
        k = len(pane_ends)
        kpad = _bucket(k, floor=1)
        # host-side 64-bit bin arithmetic -> small int32 ring indices for jit
        offs = np.arange(self.W, dtype=np.int64) - (self.W - 1)
        abs_bins = pane_ends[:, None] + offs[None, :]  # [k, W] int64
        ring = np.zeros((kpad, self.W), dtype=np.int32)
        ring[:k] = (abs_bins % self.B).astype(np.int32)
        bin_ok = np.zeros((kpad, self.W), dtype=bool)
        # only bins in [min_bin, max_bin] are live in the ring; anything
        # outside is either evicted/dropped or never written (and its ring
        # slot may alias a live bin)
        lo = self.min_bin if self.min_bin is not None else 0
        bin_ok[:k] = (abs_bins >= lo) & (abs_bins <= self.max_bin)

        # what the scan has to read whatever implements it: the bins of
        # the occupied slots' firing panes
        perf.count("pane_scan_cells", self.next_slot * k * self.W)
        devs, cells = (), None
        if self._use_ring():
            # the bin-sharded sweep reads back here, whole in the head:
            # its reads are sized by the open span and no cell runs it
            cells = self._flatten_dense(*self._emit_ring(pane_ends, k), k)
            nnz = len(cells[0])
        elif self._argmax_candidates():
            # candidate-only emission: every output column derives from
            # the counts plane (bare COUNT(*) aggs), so nothing else
            # needs to ride the transfer; with f64 channels present the
            # compacted path runs and the downstream argmax stage filters
            nnz, devs = self._emit_argmax(ring, bin_ok, kpad)
        else:
            # live cells only, compacted on the device: a readback sized
            # by a bucket of the live count, at any density
            nnz, devs = self._emit_compact(ring, bin_ok, kpad)

        self.last_fired_pane = last_pane
        # evict bins that no future pane needs: abs bins <= last_pane - W + 1
        new_min = last_pane - self.W + 2
        if self.min_bin is not None and new_min > self.min_bin:
            expired = np.arange(self.min_bin, min(new_min, self.max_bin + 1))
            if len(expired):
                epad = _bucket(len(expired), floor=8)
                ring = np.zeros(epad, dtype=np.int32)
                ring[:len(expired)] = expired % self.B
                ev = np.zeros(epad, dtype=bool)
                ev[:len(expired)] = True
                ek = _evict_kernel(self._ch_kinds, self.C, self.B)
                self.values, self.counts = timed_device(
                    ek, self.values, self.counts, *_h2d(ring, ev),
                    in_total=False)
            self.min_bin = new_min

        fire = PendingFire(int(watermark), pane_ends, self.slot_to_key,
                           nnz, devs, cells, self._d2h)
        self._d2h = []
        if nnz == 0:
            _fire_done(fire, fire.watermark)
            return None
        return fire

    def fire_tail(self, fire: PendingFire) -> FiredPanes:
        """The half of a fire that touches its :class:`PendingFire` alone:
        the blocking read-backs of the pick's outputs, the fire's accounts
        and the flatten.  Nothing here reads the planes, the directory or
        a field an update sets, so it may run while the next batches are
        inserted and applied."""
        cells = fire.cells if fire.cells is not None else \
            self._read_picked(fire)
        _fire_done(fire, fire.watermark)
        return self._fired(*cells, fire.pane_ends, fire.slot_to_key)

    def _c_slice(self) -> int:
        """Key rows a dense read transfers: the power-of-two bucket of the
        occupied slots, so a state that only gains keys slices at
        log2(C) shapes in its life and not at one per fire."""
        return min(_bucket(max(self.next_slot, 1), floor=256), self.C)

    def _read_dense(self, ring: np.ndarray, bin_ok: np.ndarray, kpad: int
                    ) -> Tuple[np.ndarray, np.ndarray]:
        """Dense emit-kernel read of a checkpoint drain (W == 1; a fire
        compacts on the device instead): dispatch, device-slice to the
        occupied keys' bucket, then overlap the round-trips.  The panes
        stay padded to ``kpad``: a slice to ``k`` would be one more shape
        that follows the data."""
        c_slice = self._c_slice()
        kernel = _emit_kernel(self._ch_kinds, self.C, self.B, 1, kpad,
                              self._xfer_ch)
        outs, cnts = timed_device(kernel, self.values, self.counts,
                                  *_h2d(ring, bin_ok))
        outs_d = outs[:, :c_slice]  # [n_xfer, c_slice, kpad]
        cnts_d = cnts[:c_slice]  # [c_slice, kpad]
        _prefetch_host(outs_d, cnts_d)
        return _readback(self, outs_d), _readback(self, cnts_d)

    @_in_phase("fire_flatten")
    def _flatten_dense(self, outs: np.ndarray, cnts: np.ndarray, k: int
                       ) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                                  np.ndarray]:
        """(key_idx, pane_idx, counts, channel values) for the live
        cells of a dense read — shared fire/drain flatten."""
        C_used = self.next_slot
        cnts_u = cnts[:C_used, :k]
        key_idx, pane_idx = np.nonzero(cnts_u)
        cnt_sel = cnts_u[key_idx, pane_idx]
        ch_sel = outs[:, :C_used, :k][:, key_idx, pane_idx]
        return key_idx, pane_idx, cnt_sel, ch_sel

    @_in_phase("fire_flatten")
    def _out_cols(self, cnt_sel: np.ndarray, ch_sel: np.ndarray
                  ) -> Dict[str, np.ndarray]:
        """Visible aggregate columns from flattened fired cells (shared
        by fire_panes and drain_deltas so the two emission paths cannot
        diverge)."""
        out_cols: Dict[str, np.ndarray] = {}
        dup_set = frozenset(self._dup_ch)
        for i, a in enumerate(self.aggs):
            if i in dup_set:
                # COUNT(*): the counts plane IS the aggregate (integer
                # counts, no f64 channel was ever transferred)
                out_cols[a.output] = cnt_sel.astype(np.int64)
                continue
            col = ch_sel[self._xfer_pos[i]]
            if a.kind == AggKind.COUNT:
                col = col.astype(np.int64)
            elif i in self._valid_ch:
                # nulls-skipping semantics from the validity-count channel:
                # AVG divides by non-null rows; an all-null pane is NULL
                nv = ch_sel[self._xfer_pos[self._valid_ch[i]]]
                if a.kind == AggKind.AVG:
                    col = col / np.maximum(nv, 1)
                col = np.where(nv > 0, col, np.nan)
            out_cols[a.output] = col
        return out_cols

    def _fired(self, key_idx: np.ndarray, pane_idx: np.ndarray,
               cnt_sel: np.ndarray, ch_sel: np.ndarray,
               pane_ends: np.ndarray, slot_to_key: np.ndarray
               ) -> Optional[FiredPanes]:
        """The fired value of flattened cells (shared by fire_tail and
        drain_deltas).  ``key_idx`` is the cell's slot on every route
        (the compaction, the argmax pick and the dense flatten index the
        planes by slot), so it goes to the operator as it is;
        ``slot_to_key`` is the directory's array as the pass that read the
        cells saw it."""
        if len(key_idx) == 0:
            return None
        window_end = (pane_ends[pane_idx] + 1) * self.slide
        return FiredPanes(slot_to_key[key_idx],
                          self._out_cols(cnt_sel, ch_sel), window_end,
                          cnt_sel, key_idx)

    def drain_deltas(self) -> Optional[FiredPanes]:
        """Checkpoint-barrier drain for FACTOR pane rings (W == 1): read
        every un-fired (key, bin) cell as a pane DELTA and reset those
        cells to their channel identities — WITHOUT advancing
        ``last_fired_pane``/``min_bin``, so rows arriving after the
        drain re-accumulate in the same bins and ship as a later delta.
        Derived-window rings merge deltas losslessly (their channels
        reduce partial-into-partial), so the factor's own snapshot holds
        no un-shipped mass and factored checkpoints restore into
        unfactored plans epoch for epoch.  Same return shape as
        ``fire_panes``; None when nothing is pending."""
        assert self.W == 1, "drain_deltas is the factor-pane path (W == 1)"
        if self.max_bin is None or self.next_slot == 0:
            return None
        self.flush_updates()
        first_pane = (self.last_fired_pane + 1
                      if self.last_fired_pane is not None
                      else (self.min_bin or 0))
        last_pane = self.max_bin
        if last_pane < first_pane:
            return None
        pane_ends = np.arange(first_pane, last_pane + 1, dtype=np.int64)
        k = len(pane_ends)
        kpad = _bucket(k, floor=1)
        ring = np.zeros((kpad, 1), dtype=np.int32)
        ring[:k, 0] = (pane_ends % self.B).astype(np.int32)
        bin_ok = np.zeros((kpad, 1), dtype=bool)
        lo = self.min_bin if self.min_bin is not None else 0
        bin_ok[:k, 0] = (pane_ends >= lo) & (pane_ends <= self.max_bin)

        outs, cnts = self._read_dense(ring, bin_ok, kpad)
        _fire_done(self, None)

        # reset the drained bins to identity; bookkeeping stays put
        drained = pane_ends[bin_ok[:k, 0]]
        if len(drained):
            epad = _bucket(len(drained), floor=8)
            rslots = np.zeros(epad, dtype=np.int32)
            rslots[:len(drained)] = (drained % self.B).astype(np.int32)
            ev = np.zeros(epad, dtype=bool)
            ev[:len(drained)] = True
            ek = _evict_kernel(self._ch_kinds, self.C, self.B)
            self.values, self.counts = timed_device(
                ek, self.values, self.counts, *_h2d(rslots, ev),
                in_total=False)

        key_idx, pane_idx, cnt_sel, ch_sel = self._flatten_dense(
            outs, cnts, k)
        return self._fired(key_idx, pane_idx, cnt_sel, ch_sel, pane_ends,
                           self.slot_to_key)

    # -- checkpoint ---------------------------------------------------------
    #
    # Snapshots use the CANONICAL topology-independent bin-state format
    # shared with MeshKeyedBinState (parallel/mesh_window.py): compact
    # per-key LINEAR bin columns (column j = absolute bin lo+j) plus the
    # host key directory, so a checkpoint taken single-device restores
    # onto any mesh and vice versa (restore-time re-partitioning,
    # parquet.rs:194-218 analog).

    def device_bytes(self) -> int:
        """Resident device footprint of the bin planes (metadata-only:
        reads ``.nbytes`` off the array handles, no transfer) — feeds
        the per-job device-memory ledger (obs/latency.py)."""
        return (sum(int(v.nbytes) for v in self.values)
                + int(self.counts.nbytes))

    def _empty_host_planes(self, counts_dtype
                           ) -> Tuple[np.ndarray, np.ndarray]:
        """Host planes in ``host_planes``' form holding no row: every
        channel at its identity, the counts (of ``counts_dtype``) zero."""
        values = np.empty((len(self._ch_kinds), self.B, self.C), ACC_DTYPE)
        values[:] = channel_inits(self._ch_kinds)[:, None, None]
        return values, np.zeros((self.B, self.C), counts_dtype)

    def host_planes(self) -> Tuple[np.ndarray, np.ndarray]:
        """Host copies of the planes as they lie, bin rows first:
        ``values[n_ch, B, C]`` and ``counts[B, C]`` (a blocking readback;
        buffered updates are not flushed here)."""
        _prefetch_host(*self.values, self.counts)
        values = np.empty((len(self._ch_kinds), self.B, self.C), ACC_DTYPE)
        for j, v in enumerate(self.values):
            values[j] = np.asarray(v).reshape(self.B, self.C)  # arroyolint: disable=host-sync -- intentional canonical-snapshot/ring-relayout readback: rescale merges and ring growth operate on host copies by design
        counts = np.asarray(self.counts).reshape(self.B, self.C)  # arroyolint: disable=host-sync -- intentional canonical-snapshot/ring-relayout readback: rescale merges and ring growth operate on host copies by design
        return values, counts

    def _set_planes(self, values: np.ndarray, counts: np.ndarray) -> None:
        """Replace the planes by host arrays in ``host_planes``' form."""
        self.values = tuple(jnp.asarray(v.reshape(-1)) for v in values)
        self.counts = jnp.asarray(counts.reshape(-1))

    def snapshot(self) -> Dict[str, np.ndarray]:
        self.flush_updates()  # buffered cells belong to this epoch
        n = self.next_slot
        values, counts = self.host_planes()
        if self.min_bin is not None and self.max_bin is not None:
            lo = self.min_bin
            cols = (np.arange(lo, self.max_bin + 1) % self.B)
        else:
            lo = -1
            cols = np.zeros(0, dtype=np.int64)
        return {
            "bin_keys": self.slot_to_key[:n],
            # canonical form: a row per key, a column per linear bin
            "bin_vals": np.ascontiguousarray(
                values[:, cols, :n].transpose(0, 2, 1)),
            "bin_counts": np.ascontiguousarray(counts[cols, :n].T),
            "ch_init": channel_inits(self._ch_kinds),
            "mesh_shards": np.array([1], dtype=np.int64),
            "key_sorted": self.key_sorted,
            "slot_of_sorted": self.slot_of_sorted,
            "slot_to_key": self.slot_to_key[:n],
            "meta": np.array([
                n, lo,  # lo == min_bin: first linear column's absolute bin
                -1 if self.max_bin is None else self.max_bin,
                -1 if self.last_fired_pane is None else self.last_fired_pane,
            ], dtype=np.int64),
        }

    def restore(self, arrays: Dict[str, np.ndarray]) -> None:
        # buffered updates from a pre-restore life are void
        self._pending = []
        self._pending_cells = 0
        meta = arrays["meta"]
        self.next_slot = int(meta[0])
        lo = int(meta[1])
        self.max_bin = None if meta[2] < 0 else int(meta[2])
        self.last_fired_pane = None if meta[3] < 0 else int(meta[3])
        self.min_bin = None if lo < 0 else lo
        self.key_sorted = arrays["key_sorted"].astype(np.uint64)
        self.slot_of_sorted = arrays["slot_of_sorted"].astype(np.int64)
        from ..native import NativeDir

        self._ndir = NativeDir.create(max(self.next_slot, 8))
        if self._ndir is not None:
            self._ndir.load(self.key_sorted, self.slot_of_sorted)
        self.C = _bucket(max(self.next_slot, 8))
        self.slot_to_key = np.zeros(self.C, dtype=np.uint64)
        self.slot_to_key[:self.next_slot] = \
            arrays["slot_to_key"].astype(np.uint64)[:self.next_slot]

        bin_keys = arrays["bin_keys"].astype(np.uint64)
        bin_vals = np.asarray(arrays["bin_vals"], dtype=ACC_DTYPE)
        raw_counts = np.asarray(arrays["bin_counts"])
        self.total_rows, cnt_dtype = restored_count_state(
            raw_counts, self._i32_promote)
        bin_counts = raw_counts.astype(cnt_dtype)
        span = bin_vals.shape[-1]
        self.B = _bucket(max(span, 2 * self.W + 4), floor=8)
        values, counts = self._empty_host_planes(cnt_dtype)
        if len(bin_keys) and span and lo >= 0:
            # bin rows land at their DIRECTORY slot (restores from a mesh
            # snapshot may order rows differently than this host's slots)
            idx = np.searchsorted(self.key_sorted, bin_keys)
            slots = self.slot_of_sorted[idx]
            cols = (np.arange(lo, lo + span) % self.B)
            values[:, cols[None, :], slots[:, None]] = bin_vals
            counts[cols[None, :], slots[:, None]] = bin_counts
        self._set_planes(values, counts)


def filter_canonical_snapshot(arrays: Dict[str, np.ndarray],
                              key_range: Tuple[int, int]
                              ) -> Dict[str, np.ndarray]:
    """Restrict a canonical bin-state snapshot (snapshot()/restore()
    format, incl. the operator's kv_* key-column arrays) to the keys a
    subtask OWNS under its key range.

    Restore-time re-partitioning (parquet.rs:194-218 analog): on a
    rescale every new subtask reads the full device-table snapshot, and
    without this filter each would hold (and re-fire panes for) every
    key — duplicate output.  Entry/batch tables are range-filtered in the
    backend; the canonical array format is filtered here where its slot
    relationships are understood."""
    lo, hi = np.uint64(key_range[0]), np.uint64(key_range[1])
    slot_to_key = arrays["slot_to_key"].astype(np.uint64)
    n_old = len(slot_to_key)
    own_slot = (slot_to_key >= lo) & (slot_to_key <= hi)
    if own_slot.all():
        return arrays  # 1:1 restore: nothing to drop
    old_slots = own_slot.nonzero()[0]  # kept keys, old slot order
    kept_keys = slot_to_key[old_slots]

    out = dict(arrays)
    out["slot_to_key"] = kept_keys
    order = np.argsort(kept_keys, kind="stable")
    out["key_sorted"] = kept_keys[order]
    # new slots are positions in old-slot order
    out["slot_of_sorted"] = np.arange(len(kept_keys), dtype=np.int64)[order]

    bin_keys = arrays["bin_keys"].astype(np.uint64)
    own_row = (bin_keys >= lo) & (bin_keys <= hi)
    out["bin_keys"] = bin_keys[own_row]
    out["bin_vals"] = arrays["bin_vals"][:, own_row]
    out["bin_counts"] = arrays["bin_counts"][own_row]

    meta = arrays["meta"].copy()
    meta[0] = len(kept_keys)
    out["meta"] = meta

    # operator key-column values are indexed by OLD slot: gather into the
    # new slot order
    for name, arr in arrays.items():
        if name.startswith("kv_") and name != "kv_size":
            if len(arr) < n_old:
                # the snapshot invariant is kv rows == occupied slots; a
                # short array silently mis-aligned would emit WRONG key
                # columns — fail loudly instead
                raise ValueError(
                    f"canonical snapshot kv array {name!r} has {len(arr)} "
                    f"rows for {n_old} slots")
            out[name] = arr[old_slots]
    if "kv_size" in arrays:
        out["kv_size"] = np.array([len(kept_keys)])
    return out


def merge_canonical_snapshots(a: Dict[str, np.ndarray],
                              b: Dict[str, np.ndarray]
                              ) -> Dict[str, np.ndarray]:
    """Merge two canonical bin-state snapshots from DIFFERENT parent
    subtasks (disjoint key ranges) into one, for restore-time
    re-partitioning (a rescale N->M reads every parent overlapping the
    new range; parquet.rs:194-218).  A naive dict merge would keep only
    one parent's arrays — silent state loss."""
    if not a:
        return b
    if not b:
        return a
    am, bm = a["meta"], b["meta"]
    if am[0] == 0:
        return b
    if bm[0] == 0:
        return a

    # unified linear-column span over absolute bins [lo, hi]
    spans = []
    for arrs, m in ((a, am), (b, bm)):
        lo = int(m[1])
        span = arrs["bin_vals"].shape[-1]
        spans.append((lo, span))
    los = [lo for lo, s in spans if lo >= 0]
    his = [lo + s - 1 for lo, s in spans if lo >= 0]
    lo_u = min(los) if los else -1
    hi_u = max(his) if his else -1
    width = (hi_u - lo_u + 1) if lo_u >= 0 else 0

    n_ch = a["bin_vals"].shape[0]
    # per-channel aggregation identities: bins one parent never held must
    # pad to +inf/-inf for MIN/MAX channels, not 0 (a 0-pad would make a
    # merged window emit min/max == 0 for keys spanning the gap)
    ch_init = None
    for arrs in (a, b):
        if "ch_init" in arrs:
            ch_init = np.asarray(arrs["ch_init"], dtype=ACC_DTYPE)
            break
    if ch_init is None or len(ch_init) != n_ch:
        import logging

        logging.getLogger(__name__).warning(
            "merging bin-state snapshots without ch_init (pre-upgrade "
            "checkpoint): MIN/MAX channels pad uncovered bins with 0")
        ch_init = np.zeros(n_ch, dtype=ACC_DTYPE)
    parts_keys, parts_vals, parts_counts = [], [], []
    kv_parts: Dict[str, List[np.ndarray]] = {}
    slot_parts: List[np.ndarray] = []
    for arrs, (lo, span) in ((a, spans[0]), (b, spans[1])):
        keys = arrs["bin_keys"].astype(np.uint64)
        vals = np.asarray(arrs["bin_vals"], dtype=ACC_DTYPE)
        counts = np.asarray(arrs["bin_counts"])
        if width and len(keys):
            pv = np.broadcast_to(ch_init[:, None, None],
                                 (n_ch, len(keys), width)).copy()
            pc = np.zeros((len(keys), width), counts.dtype)
            if lo >= 0 and span:
                off = lo - lo_u
                pv[:, :, off:off + span] = vals
                pc[:, off:off + span] = counts
            vals, counts = pv, pc
        parts_keys.append(keys)
        parts_vals.append(vals)
        parts_counts.append(counts)
        slot_parts.append(arrs["slot_to_key"].astype(np.uint64))
        for k, v in arrs.items():
            if k.startswith("kv_") and k != "kv_size":
                kv_parts.setdefault(k, []).append(
                    v[:int(arrs["meta"][0])] if len(v) >= int(arrs["meta"][0])
                    else v)

    out: Dict[str, np.ndarray] = {}
    out["bin_keys"] = np.concatenate(parts_keys)
    out["bin_vals"] = (np.concatenate(parts_vals, axis=1) if width else
                       a["bin_vals"][:, :0])
    out["bin_counts"] = (np.concatenate(parts_counts, axis=0) if width else
                         a["bin_counts"][:0])
    slot_to_key = np.concatenate(slot_parts)
    out["slot_to_key"] = slot_to_key
    order = np.argsort(slot_to_key, kind="stable")
    out["key_sorted"] = slot_to_key[order]
    out["slot_of_sorted"] = np.arange(len(slot_to_key), dtype=np.int64)[order]
    for k, vs in kv_parts.items():
        out[k] = np.concatenate(vs) if len(vs) > 1 else vs[0]
    out["kv_size"] = np.array([len(slot_to_key)])
    out["ch_init"] = ch_init
    # panes fired under the SAME aligned barrier: parents agree; max is
    # the safe choice if they ever differ (never re-fire an emitted pane)
    out["meta"] = np.array([
        len(slot_to_key), lo_u,
        max(int(am[2]), int(bm[2])),
        max(int(am[3]), int(bm[3])),
    ], dtype=np.int64)
    return out
