"""Integer sums through the bin state and the segment top-k, exact.

``nexmark_topn_price.catchup`` compares sums of bid prices near 2^33 with the
limit 0, on a chip that has no f64 (a float64 plane is a pair of f32 there,
some 48 bits).  These tests send known integers through each step alone:
``KeyedBinState.update`` (the host's per-cell sums, the transfer, the
scatter-add into the plane), ``_pane_reduce`` over the five bins of a hop
window and ``_emit_compact`` (the read-back), then ``segment_top_k`` (the
f64 sort key and its negation).

Tier-1 runs them on the CPU.  The same file is the chip's test, run without
``conftest.py`` (which pins JAX to the CPU):

    chiprun -- python -m pytest tests/test_exact_sums.py --noconftest -q -s \
        -p no:cacheprovider -W "ignore:Transparent hugepages:UserWarning"

(``pytest.ini`` makes a UserWarning an error, and JAX warns of the chip
machine's page settings as it starts.)

``-s`` shows, from the probe at the end, the largest magnitude at which a sum
still came out exact on the backend it ran on."""

import jax
import numpy as np
import pytest

import arroyo_tpu  # noqa: F401  (enables x64 before any array exists)
from arroyo_tpu import AggKind, AggSpec
from arroyo_tpu.ops.keyed_bins import KeyedBinState
from arroyo_tpu.ops.topk import segment_top_k

SLIDE, WIDTH = 2_000_000, 10_000_000
W = WIDTH // SLIDE
BACKEND = jax.default_backend()


def _window_sums(crosses, keys=96):
    """The sums of ``keys`` keys over one HOP(2 s, 10 s) window, out of the
    fire, beside what Python's integers make of the same rows.  A key's cell
    in each of the five bins takes three dispatches: the first leaves it
    just under ``2**crosses``, the second carries it over, the third adds
    1, so every scatter-add crosses the magnitude with the low bits in use
    and the reduce over the five bins reaches five times it; the first key
    stays small beside them and the last key bids in one bin alone."""
    st = KeyedBinState((AggSpec(kind=AggKind.SUM, column="x", output="sx"),),
                       slide_micros=SLIDE, width_micros=WIDTH, capacity=256)
    assert (st.W, st._ch_kinds) == (W, ("sum", "sum"))
    b0 = 40  # absolute slide of the window's first bin
    part = 1 << crosses
    kh = np.arange(1, keys + 1, dtype=np.uint64)
    want = {int(k): 0 for k in kh}
    for b in range(b0, b0 + W):
        for step in range(3):
            x = np.array([(part - 1 - 3 * int(k) - b,
                           7 + 5 * int(k) + 2 * b, 1)[step] for k in kh],
                         dtype=np.int64)
            x[0] = 3 + step  # a small sum in the same plane
            live = np.ones(keys, bool)
            if b != b0 + 2:
                live[-1] = False  # one bin only
            ts = np.full(keys, b * SLIDE + 17 * step, np.int64)
            st.update(kh[live], ts[live], {"x": x[live].astype(np.float64)})
            st.flush_updates()  # each step a scatter-add of its own
            for k, v in zip(kh[live].tolist(), x[live].tolist()):
                want[k] += v
    fired = st.fire_panes((b0 + W) * SLIDE)
    last = fired.window_end == (b0 + W) * SLIDE  # the one full window
    got = dict(zip(fired.keys[last].tolist(), fired.cols["sx"][last].tolist()))
    return got, want


@pytest.mark.parametrize("crosses", [24, 31, 40])
def test_integer_sums_leave_the_fire_exact(crosses):
    """Cells past f32's 2^24, past i32 and past 2^40 (the configuration's
    bound on a window's sum): every key's window sum, up to five times the
    cell, is the integer to the unit."""
    got, want = _window_sums(crosses)
    assert set(got) == set(want) and want[1] < 64
    assert all(v > 1 << crosses for k, v in want.items() if k > 1)
    wrong = {k: (got[k], want[k]) for k in want if got[k] != want[k]}
    assert not wrong, (BACKEND, crosses, len(wrong), sorted(wrong.items())[:4])


def _lexsort_top_k(part, values, k):
    order = np.lexsort((-values, part))  # stable: ties keep their row order
    p = part[order]
    first = np.r_[True, p[1:] != p[:-1]]
    start = np.maximum.accumulate(np.where(first, np.arange(len(p)), 0))
    keep = order[np.arange(len(p)) - start < k]
    keep.sort()
    return keep


@pytest.mark.parametrize("segments", [1, 5])
def test_segment_top_k_at_a_windows_size(segments):
    """600,000 rows (one window's live auctions; five when a fire carries
    five windows), k = 3, integer values up to 2^40: the rows a stable
    numpy lexsort keeps, with ties planted inside the first three and at the
    cut (the earlier row wins), and neighbours that differ by 1 near 2^33
    and 2^40, which an f32 key alone would not tell apart."""
    n, k = 600_000, 3
    rng = np.random.default_rng(36 + segments)
    part = np.sort(rng.integers(0, segments, n)).astype(np.int64) * 2_000_000
    values = rng.integers(100, 1 << 33, n).astype(np.float64)
    bounds = np.flatnonzero(np.r_[True, part[1:] != part[:-1], True])
    for s, (lo, hi) in enumerate(zip(bounds[:-1], bounds[1:])):
        rows = rng.choice(np.arange(lo, hi), 6, replace=False)
        top = float((1 << 40) - 3 * s) if s % 2 == 0 else float(1 << 33)
        if s == 0:
            # a tie for the first place and, one unit lower, for the cut
            values[rows[:2]] = top
            values[rows[2:5]] = top - 1
            values[rows[5]] = top - 2
        else:
            # three neighbours win, three more sit one unit under the cut
            values[rows[:3]] = top + np.arange(3, 0, -1)
            values[rows[3:]] = top
    got = segment_top_k(part, values, k)
    want = _lexsort_top_k(part, values, k)
    np.testing.assert_array_equal(got, want, err_msg=BACKEND)
    assert len(got) == k * segments


@pytest.mark.parametrize("near", [33, 40])
def test_top_k_orders_sums_that_differ_by_one(near):
    """Every row within 2,048 units of 2^near, each value once, shuffled:
    the three largest are found, whatever carries the f64 key (on the chip
    a pair of f32, whose high halves are equal across 2^(near - 23) units)."""
    n = 4096
    rng = np.random.default_rng(near)
    values = ((1 << near) - n // 2 + rng.permutation(n)).astype(np.float64)
    got = segment_top_k(np.zeros(n, np.int64), values, 3)
    np.testing.assert_array_equal(got, np.sort(np.argsort(-values)[:3]),
                                  err_msg=BACKEND)


def test_where_integer_sums_stop_being_exact():
    """The probe behind PERF.md's reading: the largest of these magnitudes
    (of a cell; a window's sum is five times it) at which the fire's sums
    are still the integers.  float64 itself holds to 2^53; the
    configuration promises 2^40, and that is what is asserted on any
    backend."""
    exact = []
    for crosses in (40, 42, 44, 45, 46, 47, 48, 50):
        got, want = _window_sums(crosses, keys=24)
        if any(got[k] != want[k] for k in want):
            break
        exact.append(crosses)
    print(f"\nexact_sums backend={BACKEND} exact_through=2^"
          f"{exact[-1] if exact else None} tried_next="
          f"{'none' if len(exact) == 8 else 'failed'}")
    assert exact and exact[-1] >= 40, (BACKEND, exact)
    if BACKEND == "cpu":
        assert exact[-1] == 50
