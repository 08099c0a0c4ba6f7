"""The argmax fire's candidate pick (ops/keyed_bins.py): the helper
against ``jnp.nonzero``, the state's candidate-only emission against the
dense path, and the lowered gather program's freedom from scatters."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from arroyo_tpu import AggKind, AggSpec
from arroyo_tpu.ops import keyed_bins
from arroyo_tpu.ops.keyed_bins import KeyedBinState

C_PICK = 2048


def _flag_positions(count: int, n: int, rng) -> np.ndarray:
    """``count`` distinct flat positions, the first and the last cell
    among them as soon as there is room for both."""
    if count == 0:
        return np.zeros(0, np.int64)
    if count == 1:
        return np.array([n - 1])
    inner = rng.choice(np.arange(1, n - 1), size=count - 2, replace=False)
    return np.concatenate([[0, n - 1], inner])


@pytest.mark.parametrize("k", [1, 4])
@pytest.mark.parametrize("npad", [8, 16, 1024])
@pytest.mark.parametrize("count", [0, 1, 7, 8, 9, 1000])
def test_pick_equals_nonzero(count, npad, k):
    """``_first_set_flags`` is ``jnp.nonzero(size=npad, fill_value=C*k)``
    element for element: below, at and above ``npad`` candidates, with
    the first and the last cell flagged; and the gather kernel around it
    returns those cells' (key, pane, count), zeros past the count."""
    n = C_PICK * k
    rng = np.random.default_rng(1000 * count + 10 * npad + k)
    flat = np.zeros(n, bool)
    flat[_flag_positions(count, n, rng)] = True
    want = np.asarray(jnp.nonzero(jnp.asarray(flat), size=npad,
                                  fill_value=n)[0])
    got = np.asarray(keyed_bins._first_set_flags(jnp.asarray(flat), npad))
    np.testing.assert_array_equal(got, want)
    if count == 1:  # the lone flag in the first cell too
        first = np.zeros(n, bool)
        first[0] = True
        got0 = np.asarray(
            keyed_bins._first_set_flags(jnp.asarray(first), npad))
        np.testing.assert_array_equal(got0, [0] + [n] * (npad - 1))

    cnt = rng.integers(1, 1 << 20, size=(C_PICK, k)).astype(np.int32)
    kernel = keyed_bins._argmax_gather_kernel(C_PICK, 8, 5, k, npad)
    idx2, cnt_c = kernel(jnp.asarray(cnt), jnp.asarray(flat.reshape(-1, k)))
    hit = np.flatnonzero(flat)[:npad]
    pad = npad - len(hit)
    np.testing.assert_array_equal(
        np.asarray(idx2), [np.r_[hit // k, [0] * pad],
                           np.r_[hit % k, [0] * pad]])
    np.testing.assert_array_equal(np.asarray(cnt_c),
                                  np.r_[cnt.reshape(-1)[hit], [0] * pad])
    assert np.asarray(idx2).dtype == np.int32


def test_gather_kernel_refuses_more_cells_than_int32_counts():
    with pytest.raises(AssertionError):
        keyed_bins._argmax_gather_kernel(1 << 30, 8, 5, 2, 8)


@pytest.mark.parametrize("k", [1, 4])
def test_gather_program_has_no_scatter(k):
    """``jnp.nonzero`` histograms its prefix sum with ``bincount``: one
    scatter update per flag, 0.25 s a fire over 2^22 flags on the chip
    (PERF.md, PR 26).  The lowered gather program holds no scatter, so a
    later edit cannot bring that back unseen."""
    C = (1 << 16) // k
    kernel = keyed_bins._argmax_gather_kernel(C, 16, 5, k, 8)
    text = kernel.lower(jax.ShapeDtypeStruct((C, k), jnp.int32),
                        jax.ShapeDtypeStruct((C, k), jnp.bool_)).as_text()
    assert "scatter" not in text
    # and the probe sees one where there is one
    with_nonzero = jax.jit(
        lambda f: jnp.nonzero(f, size=8, fill_value=C * k)[0]).lower(
            jax.ShapeDtypeStruct((C * k,), jnp.bool_)).as_text()
    assert "scatter" in with_nonzero


def _fire_all(minmax, slide, width, keys, ts, step):
    """Rows (key, window end, count) of every fire of a COUNT(*) state
    fed ``step`` rows at a time and fired mid-stream and at the end;
    ``minmax`` None takes the dense path."""
    aggs = (AggSpec(kind=AggKind.COUNT, column=None, output="n"),)
    st = KeyedBinState(aggs, slide_micros=slide, width_micros=width,
                       capacity=64)
    if minmax is not None:
        st.set_argmax_local("n", minmax)
    fires = []
    for i in range(0, len(keys), step):
        sl = slice(i, i + step)
        st.update(keys[sl], ts[sl], {})
        fires.append(st.fire_panes(int(ts[sl].max())))
    fires.append(st.fire_panes(10 ** 9, final=True))
    return [None if r is None else (r[0], r[2], r[3]) for r in fires]


@pytest.mark.parametrize("slide,width", [(1000, 1000), (1000, 4000)])
@pytest.mark.parametrize("minmax", ["max", "min"])
def test_argmax_local_fires_dense_rows_at_each_panes_extremum(
        minmax, slide, width):
    """Candidate-only emission returns exactly the dense path's rows
    whose count is their pane's extremum, in the dense path's order:
    ties kept, several panes in one fire (the final fire flushes W)."""
    rng = np.random.default_rng(26)
    n = 3000
    keys = rng.integers(0, 40, n).astype(np.uint64)
    ts = np.sort(rng.integers(0, 9000, n)).astype(np.int64)
    # a twin of every row of key 0 under key 99: a tie wherever key 0 leads
    twin = keys == 0
    keys = np.concatenate([keys, np.full(twin.sum(), 99, np.uint64)])
    ts = np.concatenate([ts, ts[twin]])
    order = np.argsort(ts, kind="stable")
    keys, ts = keys[order], ts[order]

    dense = _fire_all(None, slide, width, keys, ts, 700)
    local = _fire_all(minmax, slide, width, keys, ts, 700)
    assert len(dense) == len(local)
    tied = multi_pane = 0
    for d, a in zip(dense, local):
        if d is None:
            assert a is None
            continue
        d_keys, d_wend, d_cnt = d
        ext = {w: (d_cnt[d_wend == w].max() if minmax == "max"
                   else d_cnt[d_wend == w].min()) for w in np.unique(d_wend)}
        keep = d_cnt == np.array([ext[w] for w in d_wend])
        np.testing.assert_array_equal(a[0], d_keys[keep])
        np.testing.assert_array_equal(a[1], d_wend[keep])
        np.testing.assert_array_equal(a[2], d_cnt[keep])
        tied += int(keep.sum()) - len(ext)
        multi_pane += len(ext) > 1
    assert tied > 0 and multi_pane > 0  # the data did exercise both
