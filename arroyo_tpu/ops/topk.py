"""Device segment top-k: the TopN hot path (SURVEY #14/#15).

The reference keeps per-partition sorted retention on the heap
(tumbling_top_n_window.rs, sliding_top_n_aggregating_window.rs); here the
whole (partition, window) top-k is ONE fused device sort: sort rows by
(segment, -value) with a single ``lax.sort``, rank within segment via a
cumulative max over segment starts, and keep rank < K.  Ties preserve
row order (stable sort), matching the host lexsort semantics.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.perf import kernel_name
from .expr import bucket_size

_PAD_SEG = np.int32(2**31 - 1)  # padding rows sort after all segments


@functools.lru_cache(maxsize=128)
def _topk_kernel(n_pad: int, k: int):
    @jax.jit
    @kernel_name("topk_topk")
    def run(seg, neg_val):
        # seg: i32[n_pad] (padding = _PAD_SEG); neg_val: f64[n_pad]
        idx = jnp.arange(n_pad, dtype=jnp.int32)
        s_seg, _s_val, s_idx = jax.lax.sort(
            (seg, neg_val, idx), num_keys=2, is_stable=True)
        pos = jnp.arange(n_pad, dtype=jnp.int32)
        is_first = jnp.ones(n_pad, bool).at[1:].set(s_seg[1:] != s_seg[:-1])
        run_start = jax.lax.cummax(jnp.where(is_first, pos, 0))
        rank = pos - run_start
        keep = (rank < k) & (s_seg != _PAD_SEG)
        return s_idx, keep

    return run


def segment_top_k(part: np.ndarray, values: np.ndarray, k: int
                  ) -> np.ndarray:
    """Row indices (in original order) of the top ``k`` rows by ``values``
    (descending) within each ``part`` group."""
    n = len(part)
    # segment ids: dense i32 from the (arbitrary-dtype) partition column
    uniq = np.unique(part)
    seg = np.searchsorted(uniq, part).astype(np.int32)
    n_pad = bucket_size(n)
    seg_p = np.full(n_pad, _PAD_SEG, np.int32)
    seg_p[:n] = seg
    val_p = np.zeros(n_pad, np.float64)
    val_p[:n] = -np.asarray(values, dtype=np.float64)  # arroyolint: disable=host-sync -- intentional top-k emission readback: surviving rows must select on host

    from ..obs.perf import timed_device

    s_idx, keep = timed_device(_topk_kernel(n_pad, k),
                               jnp.asarray(seg_p), jnp.asarray(val_p))
    s_idx = np.asarray(s_idx)  # arroyolint: disable=host-sync -- intentional top-k emission readback: surviving rows must select on host
    keep = np.asarray(keep)  # arroyolint: disable=host-sync -- intentional top-k emission readback: surviving rows must select on host
    out = s_idx[keep]
    out.sort()  # restore original row order
    return out
