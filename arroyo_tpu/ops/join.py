"""Device-side equi-join pair computation (SURVEY "Core TPU kernel #3";
reference: arroyo-worker/src/operators/joins.rs:14-181, per-record Rust
hash-join loops re-designed as batched XLA kernels).

The join's compute — sorting both key columns, probing match ranges,
prefix-summing match counts, and expanding (left, right) index pairs for
the cross product — runs as static-shape jitted kernels on the device.
On the legacy one-shot path (``join_pairs``) the final materialization
(gathering payload columns by the computed indices) stays on host; the
partition-adaptive resident rings below close that last host hop — hot
partitions co-locate their payload columns on device and the probe ->
expand -> gather pipeline emits matched rows without touching the host
mirror (strings keep the host path via the buffer's sticky fallback).

Static shapes: inputs pad to power-of-two buckets (sentinel keys sort to
the end and are excluded by valid-count masking), and the pair output
pads to the bucket of the exact total from the probe's prefix sum — so
each (bucket_l, bucket_r, bucket_m) triple compiles once.

Dispatch discipline: one sort per side, one probe, one expansion = four
device round trips per fired window, independent of fan-out.
"""

from __future__ import annotations

import functools
import os
from typing import Any, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..obs import perf, tracing
from ..obs.perf import kernel_name, timed_device

# padding key: sorts after every real hash; a real key colliding with it
# (probability ~2^-64 per row) routes the call to the host fallback
SENTINEL = np.uint64(0xFFFFFFFFFFFFFFFF)


def _bucket(n: int, floor: int = 512) -> int:
    b = floor
    while b < n:
        b <<= 1
    return b


def _merged_probe() -> bool:
    """True when ``searchsorted`` must be avoided on device: XLA lowers
    it to a sequential per-bit scan that measured ~78 ms for 16k queries
    on a TPU v5 (the u64 argsort itself is fast there, 0.03 ms — the
    sort was never the problem).  The merged-rank probe computes the
    same bounds from one extra stable sort (~0.1 ms).
    ARROYO_JOIN_PROBE=merged|search forces either path on any backend
    so the CPU test mesh can check parity."""
    forced = os.environ.get("ARROYO_JOIN_PROBE")
    if forced == "merged":
        return True
    if forced == "search":
        return False
    return jax.default_backend() == "tpu"


@functools.lru_cache(maxsize=64)
def _sort_kernel(n: int):
    @jax.jit
    @kernel_name("join_sort")
    def run(keys):
        order = jnp.argsort(keys, stable=True)
        return order, keys[order]

    return run


@functools.lru_cache(maxsize=64)
def _probe_kernel(nl: int, nr: int, merged: bool):
    if not merged:
        @jax.jit
        @kernel_name("join_probe")
        def run(lk_sorted, rk_sorted, nl_valid, nr_valid):
            start = jnp.searchsorted(rk_sorted, lk_sorted, side="left")
            end = jnp.searchsorted(rk_sorted, lk_sorted, side="right")
            # right padding lives in [nr_valid, nr): clamp both bounds
            start = jnp.minimum(start, nr_valid)
            end = jnp.minimum(end, nr_valid)
            counts = jnp.where(jnp.arange(nl) < nl_valid, end - start, 0)
            cum = jnp.cumsum(counts)
            return start, counts, cum

        return run

    @jax.jit
    @kernel_name("join_probe_merged")
    def run(lk_sorted, rk_sorted, nl_valid, nr_valid):
        # merged-rank probe: for every (already sorted) left key, how
        # many right keys are < / <= it falls out of its position in a
        # stably sorted concatenation.  With the right side placed
        # first, equal right keys sort before a left key, so
        # pos - own_rank = #(right <= key); left-first gives
        # #(right < key).
        iota = jnp.arange(nl, dtype=jnp.int32)
        pos = jnp.arange(nl + nr, dtype=jnp.int32)
        o_lf = jnp.argsort(jnp.concatenate([lk_sorted, rk_sorted]),
                           stable=True)
        inv_lf = jnp.zeros(nl + nr, jnp.int32).at[o_lf].set(pos)
        start = inv_lf[:nl] - iota
        o_rf = jnp.argsort(jnp.concatenate([rk_sorted, lk_sorted]),
                           stable=True)
        inv_rf = jnp.zeros(nl + nr, jnp.int32).at[o_rf].set(pos)
        end = inv_rf[nr:] - iota
        nr_valid = jnp.asarray(nr_valid, jnp.int32)
        start = jnp.minimum(start, nr_valid)
        end = jnp.minimum(end, nr_valid)
        counts = jnp.where(iota < jnp.asarray(nl_valid, jnp.int32),
                           end - start, 0)
        cum = jnp.cumsum(counts)
        return start, counts, cum

    return run


@functools.lru_cache(maxsize=64)
def _expand_kernel(nl: int, m: int):
    @jax.jit
    @kernel_name("join_expand")
    def run(start, cum):
        # pair j belongs to the left row whose cumulative-count interval
        # contains j (cum[i-1] <= j < cum[i]), i.e.
        # lidx[j] = #{i: cum[i] <= j}: scatter each interval end into a
        # histogram and inclusive-prefix-sum it — searchsorted computes
        # the same thing but lowers to a sequential scan on TPU
        # (measured 78 ms for 16k pairs vs ~0.1 ms for this form)
        dt = cum.dtype
        mark = jnp.zeros(m + 1, dt).at[cum].add(1, mode="drop")
        lidx = jnp.cumsum(mark[:m]).clip(0, nl - 1)
        before = jnp.where(lidx > 0, cum[lidx - 1], 0)
        ridx = start[lidx] + (jnp.arange(m, dtype=dt) - before)
        return lidx, ridx

    return run


def expand_counts(counts: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Flatten per-group match counts into (group_idx, within_offset)
    pairs — the one home of the repeat/cumsum expansion idiom shared by
    the host join fallback and the partitioned sorted-run probes."""
    total = int(counts.sum())
    gidx = np.repeat(np.arange(len(counts)), counts)
    offs = np.arange(total) - np.repeat(np.cumsum(counts) - counts,
                                        counts)
    return gidx, offs


def _host_pairs(lk_sorted: np.ndarray, rk_sorted: np.ndarray
                ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Host fallback: identical contract, numpy end to end."""
    left_start = np.searchsorted(rk_sorted, lk_sorted, side="left")
    left_end = np.searchsorted(rk_sorted, lk_sorted, side="right")
    counts = left_end - left_start
    lidx, offs = expand_counts(counts)
    ridx = np.repeat(left_start, counts) + offs
    return lidx, ridx, counts


def device_join_enabled(n_rows: int) -> bool:
    """auto: device path on a real accelerator for batches big enough to
    amortize dispatch (on the CPU backend the "device" is the same
    core, so kernel dispatch is pure overhead — measured ~9% on q5);
    on: always (tests/fuzz parity); off: host numpy only."""
    mode = os.environ.get("ARROYO_DEVICE_JOIN", "auto")
    if mode == "off":
        return False
    if mode == "on":
        return True
    if jax.default_backend() == "cpu":
        return False
    return n_rows >= int(os.environ.get("ARROYO_DEVICE_JOIN_MIN", 2048))


def payload_device_enabled() -> bool:
    """Should hot-partition rings co-locate payload planes?  ``auto``
    (default) rides along whenever the device-join path is active (a
    ring without its payload pays a host gather per match — the hop
    this layer exists to kill); ``off`` keeps today's keys-only rings;
    ``on`` is the same as auto (the ring itself is still gated by
    ``device_join_enabled``, so forcing payload on a host-only join is
    meaningless).  Strings always stay host via the buffer's sticky
    fallback regardless of this knob."""
    mode = os.environ.get("ARROYO_JOIN_PAYLOAD_DEVICE", "auto").lower()
    if mode in ("off", "0", "false"):
        return False
    return bool(jax.config.jax_enable_x64)


# -- partition-adaptive resident rings (state/join_state.py) -----------------
#
# Hot join-state partitions keep their sorted key run device-resident in a
# preallocated power-of-two ring (sentinel-padded).  Maintenance is ONE
# scatter-merge dispatch per arriving delta (positions computed on the host
# mirror — the delta was already sorted there) and probes run against the
# resident ring without re-uploading state.
#
# SPLIT-HASH LAYOUT (native-i32): within a partition the partition id
# already fixes the LOW hash bits (state/join_state.py routes on
# ``kh & (P-1)``), so the ring does not need 64-bit keys for ordering.
# The host run is sorted by the full u64 hash; its TOP 32 bits are an
# order-consistent prefix of that sort, so the ring stores them as a
# bias-mapped i32 ``hi`` plane (``u32 ^ 0x80000000`` viewed i32 — the
# standard order-preserving unsigned->signed transform) that sorts,
# probes and merges in NATIVE int32 — no emulated-u64 argsort (the
# chip has no 64-bit integers).  The remaining 32 bits live in a
# collision-disambiguation ``lo`` plane (i32 bit-view, equality only):
# probe candidates are hi-equal ranges, and the rare
# i32-equal-but-u64-distinct rows are killed by a full-key verify (on
# device in the fused gather kernel, against the host mirror otherwise).
#
# PAYLOAD PLANES: when payload residency is on, the ring co-locates the
# partition's payload columns in the same power-of-two layout — one f64
# stack (floats) and one i64 stack (ints/uints/bools/timestamps as
# bit-views; slot 0 reserved for the sorted event-time run) — kept in
# key+payload lockstep by the SAME single scatter-merge dispatch per
# delta.  Strings (object dtype) cannot ride the device: the buffer's
# sticky fallback keeps such sides host-gathered (state/join_state.py).

# biased-i32 images of u32 0xFFFFFFFF: the ring's padding values.  A
# real key whose TOP 32 hash bits are all ones would be ambiguous with
# the hi pad, so such partitions refuse staging and stay host
# (probability ~2^-32 per row; parity-pinned by test).
SENT32_HI = np.int32(0x7FFFFFFF)
SENT32_LO = np.int32(-1)
_HI_BIAS = np.uint32(0x80000000)


def split_hi32(keys: np.ndarray) -> np.ndarray:
    """Order-preserving i32 image of the top 32 key-hash bits."""
    hi = (keys >> np.uint64(32)).astype(np.uint32)
    return (hi ^ _HI_BIAS).view(np.int32)


def split_lo32(keys: np.ndarray) -> np.ndarray:
    """i32 bit-view of the low 32 key-hash bits (equality only)."""
    return (keys & np.uint64(0xFFFFFFFF)).astype(np.uint32).view(np.int32)


def ring_stageable(keys: np.ndarray) -> bool:
    """False when any key's top-32 image would collide with the hi pad
    (the partition then keeps the host probe — exactness over speed)."""
    if not len(keys):
        return True
    return int(keys.max() >> np.uint64(32)) != 0xFFFFFFFF


def _pay_to_i64(v: np.ndarray) -> np.ndarray:
    if v.dtype == np.uint64 or v.dtype.kind in "Mm":
        return v.view(np.int64)  # bit-preserving
    if v.dtype == np.int64:
        return v
    return v.astype(np.int64)


def _pay_from_i64(v: np.ndarray, dtype: np.dtype) -> np.ndarray:
    if dtype == np.uint64 or dtype.kind in "Mm":
        return v.view(dtype)
    if dtype == np.bool_:
        return v != 0
    return v.astype(dtype)


def payload_plan(schema: "dict[str, np.dtype]"
                 ) -> Optional[Tuple[Tuple[str, str, int, Any], ...]]:
    """(name, stack, slot, dtype) transport plan for a partition's
    payload columns, or None when any column cannot ride the device
    (strings/objects -> the sticky host-gather fallback).  i-stack slot
    0 is reserved for the sorted event-time run; floats ride the f64
    stack losslessly (f32 round-trips exactly), everything else
    bit-views or widens into i64."""
    if not jax.config.jax_enable_x64:
        return None  # f64/i64 stacks would truncate
    plan = []
    nf, ni = 0, 1  # i-stack slot 0: timestamps
    for name, dt in schema.items():
        k = dt.kind
        if k == "f":
            plan.append((name, "f", nf, dt))
            nf += 1
        elif k in "iubMm":
            plan.append((name, "i", ni, dt))
            ni += 1
        else:
            return None
    return tuple(plan)


class SplitRing:
    """One hot partition's device residency: split-hash key planes plus
    (optionally) the co-located payload stacks, all in the sorted-run
    order of the host mirror and all padded to one power-of-two ``cap``.
    ``plan`` is None for a keys-only ring (payload residency off or the
    schema holds strings)."""

    __slots__ = ("hi", "lo", "cap", "fstack", "istack", "plan",
                 "nf", "ni", "device")

    def __init__(self, hi, lo, cap, fstack, istack, plan, nf, ni, device):
        self.hi = hi
        self.lo = lo
        self.cap = cap
        self.fstack = fstack
        self.istack = istack
        self.plan = plan
        self.nf = nf
        self.ni = ni
        self.device = device

    def plan_schema(self) -> "dict[str, Any]":
        return {name: dt for name, _s, _i, dt in (self.plan or ())}

    def payload_bytes(self) -> int:
        return self.cap * (8 + 8 * (self.nf + self.ni))


def _plan_dims(plan) -> Tuple[int, int]:
    nf = sum(1 for _n, s, _i, _d in plan if s == "f")
    ni = 1 + sum(1 for _n, s, _i, _d in plan if s == "i")
    return nf, ni


def _pack_stacks(plan, nf, ni, width, n, cols, ts):
    fv = np.zeros((nf, width), np.float64)
    iv = np.zeros((ni, width), np.int64)
    iv[0, :n] = ts
    for name, stack, idx, _dt in plan:
        if stack == "f":
            fv[idx, :n] = cols[name]
        else:
            iv[idx, :n] = _pay_to_i64(cols[name])
    return fv, iv


def ring_cap(n: int) -> int:
    """Rows a ring that holds ``n`` is allocated for: the one bucket
    function every ring shape goes through."""
    return _bucket(max(n, 1))


def stage_ring(sorted_keys: np.ndarray, device: Any = None,
               sorted_ts: Optional[np.ndarray] = None,
               sorted_cols: Optional["dict[str, np.ndarray]"] = None
               ) -> Optional[SplitRing]:
    """Put a sorted key run (plus payload columns when given, all in
    the same sorted-run order) into a fresh power-of-two sentinel-padded
    device ring: empty planes of ``ring_cap(n)`` rows, then the run
    merged in as one delta by the same ``join_merge32`` dispatch that
    every later append takes, so a ring's first window compiles what its
    later windows run.  ``device`` pins the ring to one mesh device
    (state/join_state.py spreads hot partitions over the ``("keys",)``
    mesh via ``parallel.shuffle.partition_device`` so q7/q8-style joins
    stop funneling every ring through chip 0); None keeps the default
    placement.  Later ``merge_ring``/``probe_ring`` dispatches follow
    the committed planes' device automatically.  Returns None when the
    run is not stageable (top-32 sentinel collision)."""
    if not ring_stageable(sorted_keys):
        return None
    n = len(sorted_keys)
    cap = ring_cap(n)
    plan = (payload_plan({c: v.dtype for c, v in sorted_cols.items()})
            if sorted_cols is not None else None)
    fstack = istack = None
    nf = ni = 0
    if plan is not None:
        nf, ni = _plan_dims(plan)
        fstack = jax.device_put(np.zeros((nf, cap), np.float64), device)
        istack = jax.device_put(np.zeros((ni, cap), np.int64), device)
    empty = SplitRing(
        jax.device_put(np.full(cap, SENT32_HI, np.int32), device),
        jax.device_put(np.full(cap, SENT32_LO, np.int32), device),
        cap, fstack, istack, plan, nf, ni, device)
    return merge_ring(empty, np.zeros(0, np.int64), sorted_keys,
                      np.arange(n, dtype=np.int64), delta_ts=sorted_ts,
                      delta_cols=sorted_cols if plan is not None else None)


@functools.lru_cache(maxsize=64)
def _merge32_kernel(cap: int, db: int, nf: int, ni: int):
    @jax.jit
    @kernel_name("join_merge32")
    def run(hi, lo, fstack, istack, res_pos, d_hi, d_lo, d_f, d_i,
            delta_pos):
        out_hi = jnp.full(cap, SENT32_HI, jnp.int32)
        out_hi = out_hi.at[res_pos].set(hi, mode="drop")
        out_hi = out_hi.at[delta_pos].set(d_hi, mode="drop")
        out_lo = jnp.full(cap, SENT32_LO, jnp.int32)
        out_lo = out_lo.at[res_pos].set(lo, mode="drop")
        out_lo = out_lo.at[delta_pos].set(d_lo, mode="drop")
        if not ni:
            return out_hi, out_lo
        out_f = jnp.zeros((nf, cap), jnp.float64)
        if nf:
            out_f = out_f.at[:, res_pos].set(fstack, mode="drop")
            out_f = out_f.at[:, delta_pos].set(d_f, mode="drop")
        out_i = jnp.zeros((ni, cap), jnp.int64)
        out_i = out_i.at[:, res_pos].set(istack, mode="drop")
        out_i = out_i.at[:, delta_pos].set(d_i, mode="drop")
        return out_hi, out_lo, out_f, out_i

    return run


def merge_ring(ring: SplitRing, res_pos: np.ndarray,
               delta_sorted: np.ndarray, delta_pos: np.ndarray,
               delta_ts: Optional[np.ndarray] = None,
               delta_cols: Optional["dict[str, np.ndarray]"] = None
               ) -> Optional[SplitRing]:
    """ONE scatter-merge dispatch moving resident entries to ``res_pos``
    and landing the (already sorted) delta — keys AND payload planes in
    lockstep — at ``delta_pos``.  Positions beyond the caller-tracked
    valid length are padded to >= cap and dropped.  Returns None when
    the delta is not stageable (the caller demotes to host)."""
    if not ring_stageable(delta_sorted):
        return None
    cap = ring.cap
    n_res = len(res_pos)
    db = _bucket(max(len(delta_sorted), 1))
    rp = np.full(cap, cap, np.int64)
    rp[:n_res] = res_pos
    d_hi = np.full(db, SENT32_HI, np.int32)
    d_lo = np.full(db, SENT32_LO, np.int32)
    d_hi[: len(delta_sorted)] = split_hi32(delta_sorted)
    d_lo[: len(delta_sorted)] = split_lo32(delta_sorted)
    dp = np.full(db, cap, np.int64)
    dp[: len(delta_pos)] = delta_pos
    if ring.plan is None:
        out_hi, out_lo = timed_device(
            _merge32_kernel(cap, db, 0, 0), ring.hi, ring.lo, 0, 0,
            rp, d_hi, d_lo, 0, 0, dp)
        return SplitRing(out_hi, out_lo, cap, None, None, None, 0, 0,
                         ring.device)
    m = len(delta_sorted)
    d_f, d_i = _pack_stacks(ring.plan, ring.nf, ring.ni, db, m,
                            delta_cols, delta_ts)
    out_hi, out_lo, out_f, out_i = timed_device(
        _merge32_kernel(cap, db, ring.nf, ring.ni), ring.hi, ring.lo,
        ring.fstack, ring.istack, rp, d_hi, d_lo, d_f, d_i, dp)
    return SplitRing(out_hi, out_lo, cap, out_f, out_i, ring.plan,
                     ring.nf, ring.ni, ring.device)


class ProbeHit:
    """One ring probe's device-resident intermediates: candidate match
    ranges by the i32 hi plane (a SUPERSET of true matches — hi-equal,
    full-key-unverified) with ``start``/``cum`` still on device so the
    fused expand+gather dispatch consumes them without a round trip."""

    __slots__ = ("start_d", "cum_d", "counts", "q_hi", "q_lo", "mq", "m")

    def __init__(self, start_d, cum_d, counts, q_hi, q_lo, mq, m):
        self.start_d = start_d
        self.cum_d = cum_d
        self.counts = counts
        self.q_hi = q_hi
        self.q_lo = q_lo
        self.mq = mq
        self.m = m


def probe_ring(ring: SplitRing, qkeys_sorted: np.ndarray,
               n_valid: int) -> ProbeHit:
    """Candidate match ranges of sorted query keys against a resident
    ring — native-i32 compares on the hi plane (the merged-rank variant
    keeps TPU off searchsorted's sequential scan AND off the emulated
    u64 argsort).  Candidates still need the full-key collision verify
    (``expand_hit`` / ``expand_gather``)."""
    m = len(qkeys_sorted)
    mq = _bucket(max(m, 1))
    q_hi = np.full(mq, SENT32_HI, np.int32)
    q_lo = np.full(mq, SENT32_LO, np.int32)
    q_hi[:m] = split_hi32(qkeys_sorted)
    q_lo[:m] = split_lo32(qkeys_sorted)
    start_d, counts_d, cum_d = timed_device(
        _probe_kernel(mq, ring.cap, _merged_probe()), q_hi, ring.hi,
        m, n_valid)
    counts = np.asarray(counts_d)[:m].astype(np.int64)  # arroyolint: disable=host-sync -- intentional probe readback: candidate totals size the static-shape expansion
    return ProbeHit(start_d, cum_d, counts, q_hi, q_lo, mq, m)


def expand_hit(ring: SplitRing, hit: ProbeHit, total: int
               ) -> Tuple[np.ndarray, np.ndarray]:
    """Keys-only expansion of candidate ranges: (qidx, ring positions),
    UNVERIFIED — the caller must kill i32 collisions against its host
    mirror (``skeys[spos] == qkeys[qidx]``)."""
    mb = _bucket(total)
    lidx_d, ridx_d = timed_device(_expand_kernel(hit.mq, mb),
                                  hit.start_d, hit.cum_d)
    lidx = np.asarray(lidx_d)[:total].astype(np.int64)  # arroyolint: disable=host-sync -- intentional probe readback: match pairs drive host-side verify/gather
    ridx = np.asarray(ridx_d)[:total].astype(np.int64)  # arroyolint: disable=host-sync -- intentional probe readback: match pairs drive host-side verify/gather
    return lidx, ridx


@functools.lru_cache(maxsize=64)
def _expand_gather_kernel(mq: int, cap: int, m: int, nf: int, ni: int):
    """The fused hot-path dispatch: candidate expansion (the histogram
    + prefix-sum form — searchsorted lowers to a sequential scan on
    TPU), full-key collision verify against the lo plane, and the
    payload-plane gather for BOTH stacks, all in one jitted call."""

    @jax.jit
    @kernel_name("join_expand_gather")
    def run(start, cum, hi, lo, q_hi, q_lo, fstack, istack):
        dt = cum.dtype
        mark = jnp.zeros(m + 1, dt).at[cum].add(1, mode="drop")
        lidx = jnp.cumsum(mark[:m]).clip(0, mq - 1)
        before = jnp.where(lidx > 0, cum[lidx - 1], 0)
        ridx = (start[lidx]
                + (jnp.arange(m, dtype=dt) - before)).clip(0, cap - 1)
        valid = ((hi[ridx] == q_hi[lidx])
                 & (lo[ridx] == q_lo[lidx]))
        gf = (fstack[:, ridx] if nf
              else jnp.zeros((0, m), jnp.float64))
        gi = istack[:, ridx]
        return lidx, ridx, valid, gf, gi

    return run


def expand_gather(ring: SplitRing, hit: ProbeHit, total: int
                  ) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                             np.ndarray, np.ndarray]:
    """probe -> expand -> payload materialization, fused: ONE dispatch
    turns the (still device-resident) candidate ranges into verified
    pair indices plus the gathered payload stacks.  Returns
    (qidx, ring_pos, valid, f_rows, i_rows) sliced to ``total``
    candidates; ``valid`` is the on-device full-key verify (i32-equal-
    but-u64-distinct rows are False)."""
    mb = _bucket(total)
    lidx_d, ridx_d, valid_d, gf_d, gi_d = timed_device(
        _expand_gather_kernel(hit.mq, ring.cap, mb, ring.nf, ring.ni),
        hit.start_d, hit.cum_d, ring.hi, ring.lo, hit.q_hi, hit.q_lo,
        ring.fstack if ring.nf else np.zeros((0, ring.cap), np.float64),
        ring.istack)
    lidx = np.asarray(lidx_d)[:total].astype(np.int64)  # arroyolint: disable=host-sync -- intentional join-emission readback: gathered payload rows become the output batch
    ridx = np.asarray(ridx_d)[:total].astype(np.int64)  # arroyolint: disable=host-sync -- intentional join-emission readback: gathered payload rows become the output batch
    valid = np.asarray(valid_d)[:total]  # arroyolint: disable=host-sync -- intentional join-emission readback: gathered payload rows become the output batch
    gf = np.asarray(gf_d)[:, :total]  # arroyolint: disable=host-sync -- intentional join-emission readback: gathered payload rows become the output batch
    gi = np.asarray(gi_d)[:, :total]  # arroyolint: disable=host-sync -- intentional join-emission readback: gathered payload rows become the output batch
    return lidx, ridx, valid, gf, gi


@functools.lru_cache(maxsize=64)
def _gather32_kernel(cap: int, m: int, nf: int, ni: int):
    @jax.jit
    @kernel_name("join_gather32")
    def run(idx, fstack, istack):
        gf = (fstack[:, idx] if nf
              else jnp.zeros((0, m), jnp.float64))
        gi = istack[:, idx]
        return gf, gi

    return run


def gather_ring(ring: SplitRing, spos: np.ndarray,
                readbacks: Optional[list] = None
                ) -> Tuple[np.ndarray, np.ndarray]:
    """Fire-path payload gather: materialize payload stacks for the
    given sorted-run positions (already exact — window fires match on
    the host mirror's full keys) in one dispatch.  Returns
    (f_rows, i_rows) sliced to ``len(spos)``; the blocking readback's
    (start, duration) in tracing microseconds is appended to
    ``readbacks`` for the caller's ``join.fire.d2h`` span."""
    n = len(spos)
    mb = _bucket(max(n, 1))
    idx = np.zeros(mb, np.int64)
    idx[:n] = spos
    gf_d, gi_d = timed_device(
        _gather32_kernel(ring.cap, mb, ring.nf, ring.ni), idx,
        ring.fstack if ring.nf else np.zeros((0, ring.cap), np.float64),
        ring.istack)
    t0 = tracing.now_us()
    gf = np.asarray(gf_d)  # arroyolint: disable=host-sync -- intentional join-emission readback: gathered payload rows become the output batch
    gi = np.asarray(gi_d)  # arroyolint: disable=host-sync -- intentional join-emission readback: gathered payload rows become the output batch
    if readbacks is not None:
        readbacks.append((t0, tracing.now_us() - t0))
    return gf[:, :n], gi[:, :n]


def unpack_payload(ring: SplitRing, gf: np.ndarray, gi: np.ndarray
                   ) -> Tuple[np.ndarray, "dict[str, np.ndarray]"]:
    """(timestamps, columns) from gathered payload stacks, restoring
    each column's exact storage dtype (bit-views for u64/datetimes,
    lossless narrowing for f32/int32/bool)."""
    ts = gi[0].astype(np.int64, copy=False)
    cols = {}
    for name, stack, idx, dt in ring.plan:
        cols[name] = (gf[idx] if dt == np.float64
                      else gf[idx].astype(dt) if stack == "f"
                      else _pay_from_i64(gi[idx], dt))
    return ts, cols


def join_pairs(lk: np.ndarray, rk: np.ndarray
               ) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                          np.ndarray, np.ndarray]:
    """(lo, ro, lidx, ridx, counts) for an equi-join of two uint64 key
    arrays: ``lo``/``ro`` sort each side, ``lidx``/``ridx`` index pairs
    into the sorted orders, ``counts`` is per-sorted-left-row match
    count (for outer-join unmatched masks)."""
    perf.count("join_state_resorts")  # full re-sort of both sides (the
    # legacy path the partitioned sorted runs exist to avoid)
    nl, nr = len(lk), len(rk)
    if not device_join_enabled(nl + nr) or nl == 0 or nr == 0 \
            or (lk == SENTINEL).any() or (rk == SENTINEL).any():
        lo = np.argsort(lk, kind="stable")
        ro = np.argsort(rk, kind="stable")
        lidx, ridx, counts = _host_pairs(lk[lo], rk[ro])
        return lo, ro, lidx, ridx, counts

    nlp, nrp = _bucket(nl), _bucket(nr)
    lk_p = np.full(nlp, SENTINEL, np.uint64)
    lk_p[:nl] = lk
    rk_p = np.full(nrp, SENTINEL, np.uint64)
    rk_p[:nr] = rk
    lo_d, lks_d = timed_device(_sort_kernel(nlp), lk_p)
    ro_d, rks_d = timed_device(_sort_kernel(nrp), rk_p)
    start_d, counts_d, cum_d = timed_device(
        _probe_kernel(nlp, nrp, _merged_probe()), lks_d, rks_d, nl, nr)
    counts = np.asarray(counts_d)[:nl]  # arroyolint: disable=host-sync -- intentional join-emission readback: matched pairs must land on host to build output batch
    total = int(counts.sum())
    if total:
        m = _bucket(total)
        lidx_d, ridx_d = timed_device(_expand_kernel(nlp, m),
                                      start_d, cum_d)
        lidx = np.asarray(lidx_d)[:total]  # arroyolint: disable=host-sync -- intentional join-emission readback: matched pairs must land on host to build output batch
        ridx = np.asarray(ridx_d)[:total]  # arroyolint: disable=host-sync -- intentional join-emission readback: matched pairs must land on host to build output batch
    else:
        lidx = np.zeros(0, dtype=np.int64)
        ridx = np.zeros(0, dtype=np.int64)
    lo = np.asarray(lo_d)[:nl]  # arroyolint: disable=host-sync -- intentional join-emission readback: matched pairs must land on host to build output batch
    ro = np.asarray(ro_d)[:nr]  # arroyolint: disable=host-sync -- intentional join-emission readback: matched pairs must land on host to build output batch
    return lo, ro, lidx, ridx, counts
