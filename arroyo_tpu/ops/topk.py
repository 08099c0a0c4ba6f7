"""Device segment top-k: the TopN hot path (SURVEY #14/#15).

The reference keeps per-partition sorted retention on the heap
(tumbling_top_n_window.rs, sliding_top_n_aggregating_window.rs); here the
whole (partition, window) top-k is ONE device program over the rows grouped
by segment: ``k`` passes, each of which finds every segment's best
remaining row at once (a segmented scan of the running best, whose value
at a segment's last row is handed back over the segment) and retires it.
Ties keep row order (the earlier row is the better), matching the host
lexsort semantics.

The sort value never reaches the device as a float: the host turns it into
an integer with the same order, carried as two int32 words, so the order
is exact on a chip that has no f64 (a float64 is a pair of f32 there, some
48 bits) and every comparison is an integer's.  A single ``lax.sort`` on
(segment, value, row) keys did the same work, but the TPU compiler takes
31 s over such a sort at 2^14 rows and 91 s at 2^17, and a job whose
windows fill to the 2^20 of a NEXMark window met four such buckets: 400 s
of compile before its first full window (PERF.md section 6, PR 36), where
this program compiles in seconds, once a row bucket whatever ``k``.  Its
cost on the device follows ``k`` times the rows: meant for the few rows a
TopN keeps, not for a LIMIT of thousands over millions of rows.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.perf import count, kernel_name, timed_device

_I32_MIN, _I32_MAX = np.int32(-2**31), np.int32(2**31 - 1)
_I64_MIN = np.int64(-2**63)


def _ranks_first(a, b):
    """Row ``a`` before row ``b``: the larger (hi, lo) key, then the
    earlier row.  Each is a (hi, lo, row) triple of int32 arrays."""
    (ha, la, ra), (hb, lb, rb) = a, b
    return (ha > hb) | ((ha == hb) & ((la > lb) | ((la == lb) & (ra < rb))))


def _best_so_far(a, b):
    """Segmented running best, the scan's operator: ``b`` follows ``a`` in
    scan order and carries the flag that opens a segment there."""
    (fa, *ka), (fb, *kb) = a, b
    take_a = _ranks_first(ka, kb) & ~fb
    return (fa | fb, *(jnp.where(take_a, x, y) for x, y in zip(ka, kb)))


_ROW = 1024  # the kernel's arrays are [n_pad / _ROW, _ROW], row-major


def _best_so_far_rows(opens, key):
    """Every row's best-so-far key over the row-major order of ``[R, C]``
    arrays, restarted wherever ``opens`` is set: a scan along each line, a
    scan of the lines' last results down the lines, and the two joined.
    In two dimensions and not over the flat array, whose scan the TPU
    compiler takes minutes to lay out at 2^20 rows."""
    line = jax.lax.associative_scan(_best_so_far, (opens, *key), axis=1)
    upto = jax.lax.associative_scan(
        _best_so_far, tuple(x[:, -1:] for x in line), axis=0)
    # what the lines before this one came to; before the first, nothing
    before = tuple(jnp.concatenate([x[:1], x[:-1]]) for x in upto)
    first = jax.lax.broadcasted_iota(jnp.int32, before[0].shape, 0) == 0
    return _best_so_far(before, (line[0] | first, *line[1:]))[1:]


def _next_marked(v):
    """Each row's least value at or after it in row-major order: where
    only a segment's last row is marked (the rest ``_I32_MAX``) and the
    marks grow with the segments, the mark of the row's own segment."""
    line = jax.lax.cummin(v, axis=1, reverse=True)
    later = jax.lax.cummin(line[:, :1], axis=0, reverse=True)
    later = jnp.concatenate([later[1:], jnp.full((1, 1), _I32_MAX)])
    return jnp.minimum(line, later)


def _bucket_rows(n: int) -> int:
    """The kernel's row count for ``n`` rows: powers of four, so that the
    windows of a job that fills (a NEXMark hop window grows from 120,000
    to 600,000 rows over its first five fires) meet two programs and not
    four; a program costs its trace and its load every start, and the pad
    costs 9 B a row of transfer."""
    b = 256
    while b < n:
        b <<= 2
    return b


def _lines(n_pad: int):
    return n_pad // min(n_pad, _ROW), min(n_pad, _ROW)


@functools.lru_cache(maxsize=128)
def _topk_kernel(n_pad: int):
    shape = _lines(n_pad)

    @jax.jit
    @kernel_name("topk_topk")
    def run(bounds, hi, lo, k):
        # all [R, C] in row-major order.  bounds: i8, bit 0 on a segment's
        # first row, bit 1 on its last, bit 2 on every row that is not
        # padding; (hi, lo): the row's sort key, larger first; k: i32
        opens, closes, real = ((bounds & b) != 0 for b in (1, 2, 4))
        row = (jax.lax.broadcasted_iota(jnp.int32, shape, 0) * shape[1]
               + jax.lax.broadcasted_iota(jnp.int32, shape, 1))

        def retire_best(_, alive):
            # a retired row holds the least key and no row at all, so it
            # wins nothing; a segment with no row left retires none.  The
            # running best reaches its segment's best at the segment's
            # last row, and a segment's rows lie before the next one's
            key = (jnp.where(alive, hi, _I32_MIN),
                   jnp.where(alive, lo, _I32_MIN),
                   jnp.where(alive, row, _I32_MAX))
            best = _best_so_far_rows(opens, key)[2]
            return alive & (row != _next_marked(
                jnp.where(closes, best, _I32_MAX)))

        alive = jax.lax.fori_loop(0, k, retire_best, real)
        return real & ~alive

    return run


def _ordered_words(values: np.ndarray):
    """Two int32 arrays whose lexicographic order is the values' order
    (NaN below everything, as the host lexsort on the negated value has
    it): an integer column as it is, anything else through float64's own
    bits (a float's order is its sign-magnitude integer's)."""
    values = np.asarray(values)  # arroyolint: disable=host-sync -- intentional top-k emission readback: surviving rows must select on host
    if values.dtype.kind in "ib" or (values.dtype.kind == "u"
                                     and values.dtype.itemsize < 8):
        key = values.astype(np.int64)
    else:
        v = values.astype(np.float64) + 0.0  # -0.0 ranks with 0.0
        bits = v.view(np.int64)
        key = np.where(bits < 0, bits ^ np.int64(2**63 - 1), bits)
        key[np.isnan(v)] = _I64_MIN
    hi = (key >> 32).astype(np.int32)
    lo = ((key & 0xFFFFFFFF) - 2**31).astype(np.int32)  # unsigned order
    return hi, lo


def segment_top_k(part: np.ndarray, values: np.ndarray, k: int
                  ) -> np.ndarray:
    """Row indices (in original order) of the top ``k`` rows by ``values``
    (descending) within each ``part`` group."""
    n = len(part)
    part = np.asarray(part)  # arroyolint: disable=host-sync -- intentional top-k emission readback: surviving rows must select on host
    hi, lo = _ordered_words(values)
    # the device wants a segment's rows together: one window's rows are
    # (a constant partition column), anything else is grouped here
    order = None
    if (part[1:] < part[:-1]).any():
        order = np.argsort(part, kind="stable")
        part, hi, lo = part[order], hi[order], lo[order]
    n_pad = _bucket_rows(n)
    opens = np.r_[True, part[1:] != part[:-1]][:n]
    bounds = np.zeros(n_pad, np.int8)
    bounds[:n] = 4 + opens + 2 * np.r_[opens[1:], True]
    hi_p, lo_p = np.zeros((2, n_pad), np.int32)
    hi_p[:n], lo_p[:n] = hi, lo

    count("topk_rows", n)  # the selection's rows, not the pad they ride in
    keep = timed_device(_topk_kernel(n_pad),
                        *(jnp.asarray(x.reshape(_lines(n_pad)))
                          for x in (bounds, hi_p, lo_p)),
                        np.int32(min(k, n)))
    out = np.flatnonzero(np.asarray(keep).reshape(-1))  # arroyolint: disable=host-sync -- intentional top-k emission readback: surviving rows must select on host
    if order is not None:
        out = np.sort(order[out])  # back to original rows, in their order
    return out
