"""Device segment aggregation: group-by-key over a sorted batch.

The TPU replacement for the reference's per-record aggregator loops
(windows.rs:19-59 built-in vec/count/min/max/sum aggregators): rows are
sorted by key hash, segment ids assigned by run-length, and aggregates
computed with jax.ops.segment_* in one fused XLA program.  Shapes are
bucketed to powers of two so each operator compiles O(log n) kernels.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..graph.logical import AggKind, AggSpec
from ..obs.perf import kernel_name
from .expr import bucket_size

# f64 extremes: the aggregation channels are float64 (numeric-fidelity
# policy, ops/keyed_bins.ACC_DTYPE) so the null identities must not clip
# values beyond the float32 range
NEG_INF = jnp.finfo(jnp.float64).min
POS_INF = jnp.finfo(jnp.float64).max


@functools.lru_cache(maxsize=256)
def _segment_agg_kernel(n_padded: int, n_segments: int, agg_kinds: Tuple[str, ...]):
    """Jitted kernel: (values[k, n], segment_ids[n], valid[n]) ->
    per-segment aggregates [k, n_segments] + counts [n_segments]."""

    @jax.jit
    @kernel_name("segment_agg")
    def run(values: jnp.ndarray, segment_ids: jnp.ndarray, valid: jnp.ndarray):
        # invalid rows go to a trash segment
        sid = jnp.where(valid, segment_ids, n_segments)
        counts = jax.ops.segment_sum(
            jnp.where(valid, 1, 0), sid, num_segments=n_segments + 1)[:n_segments]
        outs = []
        for i, kind in enumerate(agg_kinds):
            v = values[i]
            if kind == "sum":
                r = jax.ops.segment_sum(jnp.where(valid, v, 0.0), sid,
                                        num_segments=n_segments + 1)[:n_segments]
            elif kind == "min":
                r = jax.ops.segment_min(jnp.where(valid, v, POS_INF), sid,
                                        num_segments=n_segments + 1)[:n_segments]
            elif kind == "max":
                r = jax.ops.segment_max(jnp.where(valid, v, NEG_INF), sid,
                                        num_segments=n_segments + 1)[:n_segments]
            elif kind == "count":
                r = counts.astype(jnp.float64)
            else:
                raise ValueError(kind)
            outs.append(r)
        return jnp.stack(outs) if outs else jnp.zeros((0, n_segments)), counts

    return run


def _segment_host() -> bool:
    """True when the per-fire segment reduce should run as numpy
    reduceat instead of the device kernel: expressions are pinned to
    host (``ARROYO_EXPR_DEVICE=cpu``) while an accelerator backend is
    active.  This reduce runs once per watermark flush and its result
    is consumed on host immediately, so it follows the expressions.
    ARROYO_SEGMENT_HOST forces either path (tests cover the host branch
    from the CPU mesh this way)."""
    import os

    forced = os.environ.get("ARROYO_SEGMENT_HOST")
    if forced is not None:
        return forced.lower() not in ("", "0", "off", "false", "no")
    from .expr import _expr_device

    return _expr_device() is not None and jax.default_backend() != "cpu"


def _segmented_median(v: np.ndarray, kh_sorted: np.ndarray,
                      uniq: np.ndarray, seg_start: np.ndarray
                      ) -> np.ndarray:
    """Per-segment median in three vector ops (the np.median UDAF fast
    path): in-segment value sort via one lexsort, NaNs last, then the
    two middle elements of each segment's non-null prefix."""
    n = len(v)
    if n == 0 or len(seg_start) == 0:
        return np.zeros(0, dtype=np.float64)
    so = np.lexsort((v, kh_sorted))  # NaN sorts after every number
    vs = v[so]
    sizes = np.diff(np.append(seg_start, n))
    nn = sizes - np.add.reduceat(np.isnan(vs).astype(np.int64), seg_start)
    lo_i = seg_start + np.maximum(nn - 1, 0) // 2
    hi_i = seg_start + np.maximum(nn, 1) // 2
    med = 0.5 * (vs[np.minimum(lo_i, n - 1)] + vs[np.minimum(hi_i, n - 1)])
    return np.where(nn > 0, med, np.nan)


def segment_aggregate(
    key_hash: np.ndarray,
    timestamps: np.ndarray,
    agg_inputs: Dict[str, np.ndarray],
    aggs: Tuple[AggSpec, ...],
) -> Tuple[np.ndarray, Dict[str, np.ndarray], np.ndarray, np.ndarray,
           Dict[str, np.ndarray]]:
    """Group rows by key_hash and compute ``aggs``.

    Returns (unique_keys, {output_name: values}, max_ts_per_key,
    row_counts_per_key, {output_name: non_null_counts} for column aggs).
    Nulls (NaN after coercion) are skipped: they feed the aggregate its
    identity, COUNT(col) counts non-null rows only, AVG divides by the
    non-null count, and an all-null segment emits NaN (SQL NULL).  Host
    does the sort (numpy argsort, C speed) — the reduce runs on device.
    """
    n = len(key_hash)
    order = np.argsort(key_hash, kind="stable")
    kh = key_hash[order]
    uniq, seg_start = np.unique(kh, return_index=True)
    seg_ids = np.searchsorted(uniq, kh).astype(np.int32)
    n_seg = len(uniq)

    npad = bucket_size(n)
    spad = bucket_size(n_seg)
    valid = np.zeros(npad, dtype=bool)
    valid[:n] = True
    sid_p = np.zeros(npad, dtype=np.int32)
    sid_p[:n] = seg_ids

    # COUNT(DISTINCT x) is inherently sort-based: host np.unique over
    # (key, value) pairs (not mergeable across bins, hence only available on
    # the buffered window path — matching the reference's two-phase
    # exclusion of non-mergeable aggregates, operators.rs:165-167)
    distinct_results: Dict[str, np.ndarray] = {}
    host_valid_counts: Dict[str, np.ndarray] = {}
    device_aggs = []
    # channel layout accumulators (device_aggs append theirs below;
    # UDAF plans append theirs inside the dispatch loop)
    from ..formats import coerce_float

    kinds: List[str] = []
    rows: List[np.ndarray] = []
    udaf_specs: List[Tuple[AggSpec, "UdafPlan", Dict[str, int]]] = []

    def _host_segments(column: np.ndarray):
        """(values-in-order, per-row validity, per-segment row groups) —
        the shared scaffolding for every host-reduced aggregate (UDAFs,
        string MIN/MAX)."""
        from ..formats import nan_validity

        v = column[order]
        ok = nan_validity(v, None)
        ok_rows = (np.ones(len(v), dtype=bool) if ok is None
                   else np.asarray(ok))  # arroyolint: disable=host-sync -- host-segment fallback path: UDAF/string/object columns cannot ride the f64 device channels; these are host numpy arrays
        return v, ok_rows, np.split(np.arange(n), seg_start[1:])

    from ..obs import perf as _perf

    for a in aggs:
        if a.kind == AggKind.UDAF:
            from .udaf import channel_rows, udaf_channels_enabled, udaf_plan

            col_raw = np.asarray(agg_inputs[a.column])  # arroyolint: disable=host-sync -- aggregate inputs on this generic path are host numpy columns (device-channel rows never reach it)
            if (a.fn is np.median and col_raw.dtype.kind in "if"  # arroyolint: disable=host-sync -- host-segment fallback path: UDAF/string/object columns cannot ride the f64 device channels; these are host numpy arrays
                    and udaf_channels_enabled()):
                # vectorized across ALL segments: one in-segment sort,
                # then middle-element picks — NaNs sort last inside each
                # segment, so the non-null count bounds the true middle
                # (order statistics don't decompose into channels; this
                # exact path counts on the vectorized side of the split)
                _perf.count("udaf_channel_rows", n)
                distinct_results[a.output] = _segmented_median(
                    np.asarray(agg_inputs[a.column][order],  # arroyolint: disable=host-sync -- host-segment fallback path: UDAF/string/object columns cannot ride the f64 device channels; these are host numpy arrays
                               dtype=np.float64), kh, uniq, seg_start)
                continue
            # numeric UDAF expressible over mergeable partials: compile
            # onto channels (ops/udaf.py probe algebra) — object/string
            # columns stay on the counted sticky host fallback
            plan = (udaf_plan(a.fn) if col_raw.dtype.kind in "ifbu"
                    else None)
            if plan is not None:
                raw = coerce_float(col_raw[order], np.float64)
                ok = ~np.isnan(raw)
                chmap: Dict[str, int] = {}
                for ch in plan.channels:
                    kind, rowv = channel_rows(ch, raw, ok)
                    chmap[ch] = len(kinds)
                    kinds.append(kind)
                    rows.append(rowv)
                udaf_specs.append((a, plan, chmap))
                _perf.count("udaf_channel_rows", n)
                continue
            # per-segment host call over non-null values (non-mergeable —
            # only reachable via buffered window paths, like the
            # reference's wasm UDFs, operators/mod.rs:347-494)
            _perf.count("udaf_host_rows", n)
            v, ok_rows, groups = _host_segments(agg_inputs[a.column])
            out = []
            cnt = np.zeros(n_seg, dtype=np.int64)
            for j, g in enumerate(groups):
                gv = v[g[ok_rows[g]]]
                cnt[j] = len(gv)
                out.append(a.fn(gv) if len(gv) else np.nan)
            distinct_results[a.output] = np.asarray(out)  # arroyolint: disable=host-sync -- host-segment fallback path: UDAF/string/object columns cannot ride the f64 device channels; these are host numpy arrays
            # same valid_counts contract as the compiled-channel path:
            # the knob must not change the result SHAPE, only the route
            host_valid_counts[a.output] = cnt
        elif (a.kind in (AggKind.MIN, AggKind.MAX)
              and np.asarray(agg_inputs[a.column]).dtype == object):  # arroyolint: disable=host-sync -- host-segment fallback path: UDAF/string/object columns cannot ride the f64 device channels; these are host numpy arrays
            # string MIN/MAX (lexicographic, NULLs skipped): object
            # columns can't ride the f64 device channels — per-segment
            # host reduce, like the reference's accumulator for Utf8
            v, ok_rows, groups = _host_segments(agg_inputs[a.column])
            pick = min if a.kind == AggKind.MIN else max
            outv = []
            for g in groups:
                gv = v[g[ok_rows[g]]]
                outv.append(pick(gv) if len(gv) else None)
            distinct_results[a.output] = np.asarray(outv, dtype=object)  # arroyolint: disable=host-sync -- host-segment fallback path: UDAF/string/object columns cannot ride the f64 device channels; these are host numpy arrays
        elif a.kind == AggKind.COUNT_DISTINCT:
            from ..formats import nan_validity

            v = agg_inputs[a.column][order]
            # SQL excludes NULLs from COUNT(DISTINCT) — and NaN != NaN
            # would otherwise make every null row its own "distinct"
            # value
            ok = nan_validity(v, None)
            if ok is not None and not np.asarray(ok).all():  # arroyolint: disable=host-sync -- host-segment fallback path: UDAF/string/object columns cannot ride the f64 device channels; these are host numpy arrays
                keep = np.asarray(ok)  # arroyolint: disable=host-sync -- host-segment fallback path: UDAF/string/object columns cannot ride the f64 device channels; these are host numpy arrays
                vv0, kv0 = v[keep], kh[keep]
            else:
                vv0, kv0 = v, kh
            m = len(vv0)
            pair_sort = np.lexsort((vv0, kv0))
            kv, vv = kv0[pair_sort], vv0[pair_sort]
            is_new = np.ones(m, dtype=bool)
            is_new[1:] = (kv[1:] != kv[:-1]) | (vv[1:] != vv[:-1])
            per_key = np.zeros(n_seg, dtype=np.int64)
            np.add.at(per_key, np.searchsorted(uniq, kv[is_new]), 1)
            distinct_results[a.output] = per_key
        else:
            device_aggs.append(a)

    # Channel layout: one kernel channel per agg, plus a hidden additive
    # validity-count channel per column-reading agg so nulls are skipped
    # (same scheme as ops/keyed_bins.py)
    specs: List[Tuple[AggSpec, int, Optional[int]]] = []
    for a in device_aggs:
        if a.column is None:  # COUNT(*) — all rows
            specs.append((a, len(kinds), None))
            kinds.append("count")
            rows.append(np.zeros(n, dtype=np.float64))
            continue
        raw = coerce_float(agg_inputs[a.column][order],
                           np.float64)
        ok = ~np.isnan(raw)
        if a.kind == AggKind.COUNT:  # COUNT(col) — non-null rows
            specs.append((a, len(kinds), None))
            kinds.append("sum")
            rows.append(ok.astype(np.float64))
            continue
        ident = np.float64(0.0 if a.kind in (AggKind.SUM, AggKind.AVG)
                           else (POS_INF if a.kind == AggKind.MIN
                                 else NEG_INF))
        specs.append((a, len(kinds), len(kinds) + 1))
        kinds.append("sum" if a.kind == AggKind.AVG else a.kind.value)
        rows.append(np.where(ok, raw, ident).astype(np.float64))
        kinds.append("sum")
        rows.append(ok.astype(np.float64))

    if _segment_host():
        row_counts = np.diff(np.append(seg_start, n))
        outs = np.empty((len(kinds), n_seg), dtype=np.float64)
        for i, kind in enumerate(kinds):
            row = rows[i]
            if kind == "sum":
                outs[i] = np.add.reduceat(row, seg_start)
            elif kind == "min":
                outs[i] = np.minimum.reduceat(row, seg_start)
            elif kind == "max":
                outs[i] = np.maximum.reduceat(row, seg_start)
            else:  # count: rows per segment
                outs[i] = row_counts
        counts = row_counts
    else:
        vals = np.zeros((len(kinds), npad), dtype=np.float64)
        for i, row in enumerate(rows):
            vals[i, :n] = row

        from ..obs.perf import timed_device

        kernel = _segment_agg_kernel(npad, spad, tuple(kinds))
        outs, counts = timed_device(kernel, jnp.asarray(vals),
                                    jnp.asarray(sid_p), jnp.asarray(valid))
        outs = np.asarray(outs)[:, :n_seg]  # arroyolint: disable=host-sync -- host-segment fallback path: UDAF/string/object columns cannot ride the f64 device channels; these are host numpy arrays
    out_cols = dict(distinct_results)
    valid_counts: Dict[str, np.ndarray] = dict(host_valid_counts)
    for a, plan, chmap in udaf_specs:
        parts = {ch: np.asarray(outs[i], dtype=np.float64)  # arroyolint: disable=host-sync -- outs was pulled above; these are host slices of the already-read kernel result
                 for ch, i in chmap.items()}
        nnz = parts["nnz"]
        with np.errstate(all="ignore"):
            col = plan.combine(parts)
        # all-null segments emit NaN — exactly what the host loop's
        # "empty gv" branch produces
        out_cols[a.output] = np.where(nnz > 0, col, np.nan)
        valid_counts[a.output] = nnz.astype(np.int64)
    for a, ci, vi in specs:
        col = outs[ci]
        if vi is not None:
            nv = outs[vi]
            valid_counts[a.output] = nv.astype(np.int64)
            if a.kind == AggKind.AVG:
                col = col / np.maximum(nv, 1)
            col = np.where(nv > 0, col, np.nan)
        if a.kind == AggKind.COUNT:
            col = col.astype(np.int64)
            valid_counts[a.output] = col
        out_cols[a.output] = col

    # per-key max timestamp (host; used for emitted record timestamps)
    ts_sorted = timestamps[order]
    max_ts = np.maximum.reduceat(ts_sorted, seg_start)
    return (uniq, out_cols, max_ts,
            np.asarray(counts)[:n_seg].astype(np.int64), valid_counts)  # arroyolint: disable=host-sync -- host-segment fallback path: UDAF/string/object columns cannot ride the f64 device channels; these are host numpy arrays
