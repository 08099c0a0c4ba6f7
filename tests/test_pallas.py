"""Pallas MXU scatter kernel vs numpy reference and vs the XLA scatter path.

On CPU the kernel runs in interpret mode (same code path as TPU, minus
mosaic compilation), so these tests validate kernel semantics everywhere.
"""

import numpy as np
import jax.numpy as jnp

from arroyo_tpu.graph.logical import AggKind, AggSpec
from arroyo_tpu.ops.keyed_bins import KeyedBinState
from arroyo_tpu.ops.pallas_kernels import (CHUNK, pad_batch,
                                           scatter_add_channels)


def _ref_scatter(slots, bins, w, C, B):
    out = np.zeros((w.shape[0], C, B), dtype=np.float64)
    for i, (s, b) in enumerate(zip(slots, bins)):
        out[:, s, b] += w[:, i]
    return out


def test_scatter_add_matches_numpy():
    rng = np.random.default_rng(7)
    C, B, n = 64, 16, 1000
    slots = rng.integers(0, C, n)
    bins = rng.integers(0, B, n)
    w = np.stack([np.ones(n), rng.normal(size=n) * 50]).astype(np.float32)
    s, b, wp = pad_batch(slots, bins, w)
    got = np.asarray(scatter_add_channels(s, b, wp, C, B))
    want = _ref_scatter(slots, bins, w, C, B)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)


def test_scatter_add_large_tiled():
    rng = np.random.default_rng(11)
    C, B, n = 2048, 32, 3 * CHUNK + 17  # exercises C tiling + chunk padding
    slots = rng.integers(0, C, n)
    bins = rng.integers(0, B, n)
    w = np.ones((1, n), dtype=np.float32)
    s, b, wp = pad_batch(slots, bins, w)
    got = np.asarray(scatter_add_channels(s, b, wp, C, B))
    want = _ref_scatter(slots, bins, w, C, B)
    np.testing.assert_allclose(got, want, rtol=1e-4)


def _run_state(monkeypatch, use_pallas: bool):
    monkeypatch.setenv("ARROYO_PALLAS", "1" if use_pallas else "0")
    aggs = (AggSpec(kind=AggKind.COUNT, column=None, output="n"),
            AggSpec(kind=AggKind.SUM, column="price", output="total"))
    st = KeyedBinState(aggs, slide_micros=1_000_000,
                       width_micros=5_000_000, capacity=64)
    rng = np.random.default_rng(3)
    for _ in range(4):
        m = 700
        kh = rng.integers(0, 40, m).astype(np.uint64)
        ts = rng.integers(0, 20_000_000, m).astype(np.int64)
        price = rng.uniform(1, 100, m)
        st.update(kh, ts, {"price": price})
    out = st.fire_panes(watermark=50_000_000, final=True)
    assert out is not None
    keys, cols, wend, cnts = out
    order = np.lexsort((keys, wend))
    return (keys[order], {k: v[order] for k, v in cols.items()},
            wend[order], cnts[order])


def test_keyed_bin_state_pallas_equals_xla(monkeypatch):
    k1, c1, w1, n1 = _run_state(monkeypatch, use_pallas=False)
    k2, c2, w2, n2 = _run_state(monkeypatch, use_pallas=True)
    np.testing.assert_array_equal(k1, k2)
    np.testing.assert_array_equal(w1, w2)
    np.testing.assert_array_equal(n1, n2)
    np.testing.assert_array_equal(c1["n"], c2["n"])
    np.testing.assert_allclose(c1["total"], c2["total"], rtol=1e-4)
