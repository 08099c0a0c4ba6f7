"""Pallas TPU kernels for the hot scatter-reduce in windowed aggregation.

The reference's sliding-window aggregator updates per-(key, bin) accumulators
one record at a time (/root/reference/arroyo-worker/src/operators/
aggregating_window.rs:114-160, map.insert per element).  The XLA translation
of that is ``values.at[slots, bins].add(x)`` — a scatter, which TPUs execute
serially.  This module reformulates the additive scatter as a **one-hot
matmul on the MXU**:

    delta[c, p] = sum_i onehot_slots[i, c] * packed[i, p]

where ``packed`` carries, along the lane axis, one column group per
aggregation channel: ``packed[i, g*B + b] = (bin_i == b) * w_g,i``.  The
Pallas kernel materializes the [CHUNK, TILE_C] slot one-hot in VMEM on the
fly (it never touches HBM) and contracts it against the packed block with a
single DEFAULT-precision matmul.  Two tricks keep that both exact and fast:

* the slot one-hot is 0/1 — exact in bf16, so no HIGHEST-precision passes;
* each weighted channel is split into bf16 hi + lo column groups
  (w = hi + lo), recovering ~f32 accuracy at 2 exact-product columns
  instead of 6 multi-pass matmul passes.

The grid covers only **active** key tiles (slots actually in use), not the
full capacity, and the batch-chunk axis is innermost so each [TILE_C, P]
accumulator stays VMEM-resident and is written to HBM exactly once.

Used for sum/count/avg channels (min/max stay on the XLA scatter path —
they are not additive and are rare in the hot queries).  On non-TPU
backends the kernel runs in interpret mode so tests exercise the same code.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl

from ..obs.perf import kernel_name

LANES = 128  # TPU lane width
CHUNK = 1024  # batch rows per grid step
TILE_C = 512  # key slots per grid tile


def pallas_enabled() -> bool:
    """Pallas update path is opt-in (ARROYO_PALLAS=1) on every backend:
    on real TPU v5 hardware the XLA scatter update measured 1.17 ms per
    16k-cell step against the engine's 8192x16 resident state while this
    kernel measured 52-76 ms at the identical shape across three
    sessions (record since deleted: git 4daf76b, a shared remote chip,
    not reproducible) — the one-hot MXU scatter does not pay off at
    bin-ring widths, so defaulting it on would silently cost the q5 hot
    loop ~44x."""
    return os.environ.get("ARROYO_PALLAS", "0") not in ("0", "false", "no")


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _scatter_kernel(tile_c: int, P: int):
    def kernel(slots_ref, packed_ref, out_ref):
        t = pl.program_id(0)
        c = pl.program_id(1)

        @pl.when(c == 0)
        def _():
            out_ref[:] = jnp.zeros_like(out_ref)

        slots = slots_ref[:]  # i32 [CHUNK, 1] (global slot ids; -1 invalid)
        base = t * tile_c
        c_iota = jax.lax.broadcasted_iota(jnp.int32, (CHUNK, tile_c), 1)
        onehot_s = jnp.where(c_iota + base == slots, 1.0, 0.0)
        # [tile_c, CHUNK] @ [CHUNK, P], single MXU pass: both operands are
        # explicitly bf16 and every packed entry is bf16-representable (the
        # hi/lo split happens on host), so the cast loses nothing and the
        # products accumulate exactly in f32
        out_ref[:] += jax.lax.dot_general(
            onehot_s.astype(jnp.bfloat16),
            packed_ref[:].astype(jnp.bfloat16),
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    return kernel


@functools.lru_cache(maxsize=256)
def _scatter_call(C_act: int, P: int, n_chunks: int, interpret: bool):
    tile_c = min(C_act, TILE_C)
    assert C_act % tile_c == 0
    grid = (C_act // tile_c, n_chunks)

    return pl.pallas_call(
        _scatter_kernel(tile_c, P),
        out_shape=jax.ShapeDtypeStruct((C_act, P), jnp.float32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((CHUNK, 1), lambda t, c: (c, 0)),
            pl.BlockSpec((CHUNK, P), lambda t, c: (c, 0)),
        ],
        out_specs=pl.BlockSpec((tile_c, P), lambda t, c: (t, 0)),
        interpret=interpret,
    )


@functools.lru_cache(maxsize=256)
def _scatter_multi(k2: int, B: int, C_act: int, n_chunks: int,
                   interpret: bool):
    """k2 bf16-exact weight channels -> [k2, C_act, B] via one matmul."""
    P = ((k2 * B + LANES - 1) // LANES) * LANES
    call = _scatter_call(C_act, P, n_chunks, interpret)
    n = n_chunks * CHUNK

    @jax.jit
    @kernel_name("pallas_scatter_multi")
    def run(slots, bins, weights):
        # packed[i, g*B + b] = (bin_i == b) * w_g,i ; every entry is
        # bf16-representable because the hi/lo split happened on host
        onehot_b = jnp.where(
            bins[:, None] == jnp.arange(B, dtype=jnp.int32)[None, :],
            1.0, 0.0)  # [n, B]
        groups = [onehot_b * weights[g][:, None] for g in range(k2)]
        packed = jnp.concatenate(groups, axis=1)
        packed = jnp.pad(packed, ((0, 0), (0, P - k2 * B)))
        out = call(slots.reshape(n, 1), packed)  # [C_act, P]
        return jnp.stack(
            [out[:, g * B:(g + 1) * B] for g in range(k2)])

    return run


def _split_hi_lo(weights: np.ndarray) -> np.ndarray:
    """[k, n] f32 -> [2k, n] f32 with every entry bf16-representable."""
    import ml_dtypes

    hi = weights.astype(ml_dtypes.bfloat16).astype(np.float32)
    lo = (weights - hi).astype(ml_dtypes.bfloat16).astype(np.float32)
    return np.concatenate([hi, lo], axis=0)


def scatter_add_channels(slots: np.ndarray, bins: np.ndarray,
                         weights: np.ndarray, C_act: int, B: int
                         ) -> jnp.ndarray:
    """Batched scatter-add of ``k`` weight channels into [k, C_act, B].

    ``slots`` must be in [0, C_act) for real rows and -1 (or any
    out-of-range value) for padding; ``C_act`` must be a power of two
    (multiple of TILE_C when larger).
    """
    k, n = weights.shape
    assert n % CHUNK == 0 and len(slots) == n
    w2 = _split_hi_lo(np.asarray(weights, np.float32))  # arroyolint: disable=host-sync -- kernel input packing reads host arrays; no device round-trip on this path
    run = _scatter_multi(2 * k, B, C_act, n // CHUNK, _interpret())
    # every operand is 32-bit; trace under x32 — Mosaic's TPU lowering
    # rejects the 64-bit index types that global x64 mode introduces
    with jax.enable_x64(False):
        out = run(jnp.asarray(slots, jnp.int32),
                  jnp.asarray(bins, jnp.int32),
                  jnp.asarray(w2))  # [2k, C_act, B]
    return out[:k] + out[k:]


@functools.lru_cache(maxsize=256)
def _update_delta_call(k: int, B: int, C_act: int, n_chunks: int,
                       interpret: bool):
    """One x32 dispatch producing the per-batch [k, C_act, B] deltas from
    the packed pallas scatter (hi + lo recombined).

    Channel 0 is the count channel; channels 1..k map to values[0..k-1].
    """
    run = _scatter_multi(2 * k, B, C_act, n_chunks, interpret)

    @jax.jit
    def apply(packed):
        # ONE packed f32 input (one transfer): [slots, bins, w2 hi/lo...]
        slots = packed[0].astype(jnp.int32)
        bins = packed[1].astype(jnp.int32)
        out = run(slots, bins, packed[2:])
        return out[:k] + out[k:]

    return apply


@functools.lru_cache(maxsize=64)
def _apply_delta_call(k: int, C_act: int):
    @jax.jit
    def apply(values, counts, deltas):
        counts = counts.at[:C_act].add(deltas[0].astype(counts.dtype))
        if k > 1:
            values = values.at[:, :C_act].add(
                deltas[1:].astype(values.dtype))
        return values, counts

    return apply


def update_bin_state(values: jnp.ndarray, counts: jnp.ndarray,
                     slots: np.ndarray, bins: np.ndarray,
                     weights: np.ndarray, C_act: int, B: int):
    """Fused state update; returns (values, counts). weights[0] is the
    count channel, weights[1:] the aggregate channels.

    Two dispatches: the pallas scatter runs under x32 (Mosaic's TPU
    lowering rejects 64-bit index types), while the state add runs under
    the session's x64 so the f64 accumulator state is NOT silently
    downcast (the numeric-fidelity policy in keyed_bins.ACC_DTYPE)."""
    k, n = weights.shape
    assert n % CHUNK == 0
    # slot ids ride an f32 row: exact only below 2^24 (same guard as the
    # XLA packing in keyed_bins.update)
    assert C_act <= 1 << 24, "key capacity exceeds f32-exact packing"
    w2 = _split_hi_lo(np.asarray(weights, np.float32))  # arroyolint: disable=host-sync -- kernel input packing reads host arrays; no device round-trip on this path
    packed = np.empty((2 + w2.shape[0], n), dtype=np.float32)
    packed[0] = slots  # small ints: exact in f32
    packed[1] = bins
    packed[2:] = w2
    delta = _update_delta_call(k, B, C_act, n // CHUNK, _interpret())
    with jax.enable_x64(False):
        deltas = delta(jnp.asarray(packed))
    return _apply_delta_call(k, C_act)(values, counts, deltas)


def pad_batch(slots: np.ndarray, bins: np.ndarray,
              weights: np.ndarray) -> tuple:
    """Pad 1-D batch arrays up to a CHUNK multiple.

    Padding rows get slot -1 (matches no tile) and weight 0.
    """
    n = len(slots)
    npad = ((n + CHUNK - 1) // CHUNK) * CHUNK
    s = np.full(npad, -1, dtype=np.int32)
    s[:n] = slots
    b = np.zeros(npad, dtype=np.int32)
    b[:n] = bins
    w = np.zeros((weights.shape[0], npad), dtype=np.float32)
    w[:, :n] = weights
    return s, b, w


def active_capacity(used: int, total_c: int) -> int:
    """Smallest pallas-friendly slot count covering ``used`` slots."""
    c = 8
    while c < used:
        c <<= 1
    if c > TILE_C:
        c = ((used + TILE_C - 1) // TILE_C) * TILE_C
    return min(c, total_c)
