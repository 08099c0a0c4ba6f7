"""Ring-parallel pane aggregation over the bin (time) dimension — the
engine's sequence-parallelism discipline (SURVEY §5: "window panes =
sequence blocks; ring-style rotation of bins across devices via
``ppermute`` when a single key's window exceeds one device's memory").

The keyed mesh state (parallel/mesh_window.py) shards the KEY dimension;
this kernel shards the BIN dimension instead, for the degenerate-skew
case where ONE key's window spans more bins than a single device can
hold (a very long window with a very short slide).  Layout: the global
bin ring ``[n_bins]`` lives block-sharded over a 1-D ``("bins",)`` mesh,
shard d holding bins ``[d*Bl, (d+1)*Bl)``.  A pane ending at bin t
aggregates bins ``(t-W, t]``, which crosses shard boundaries whenever
W > 1: each shard needs a HALO of the previous shards' trailing bins.

The halo moves like a ring-attention block pass: ``ceil((W-1)/Bl)``
``ppermute`` rotations forward around the ring, each shard accumulating
the received block into its sliding prefix (contributions that would
wrap past global bin 0 are masked to the aggregation identity).  Compute
stays fully on-device and per-step communication is one block — the
standard ring-parallel cost model (the public ring-attention recipe
applied to window panes instead of attention blocks).

The reference has no analog (its per-key window state lives on one
subtask, aggregating_window.rs); this is TPU-first scale-out headroom.
"""

from __future__ import annotations

import functools
from typing import Tuple

import numpy as np

from ..graph.logical import AggKind
from ..ops.keyed_bins import _init_value


@functools.lru_cache(maxsize=64)
def _ring_step(kind: str, nk: int, Bl: int, W: int):
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    from .mesh_window import _keys_mesh

    ident = _init_value(AggKind(kind))
    additive = kind in ("sum", "count")
    mesh = _keys_mesh(nk)
    n_rot = max((W - 1 + Bl - 1) // Bl, 0)  # ring rotations needed

    def combine(a, b):
        return jnp.minimum(a, b) if kind == "min" else jnp.maximum(a, b)

    def sliding(ext):
        """Width-W aggregate ending at each of the LAST Bl positions of
        ``ext`` (length (n_rot+1)*Bl >= W + Bl - 1)."""
        if additive:
            c = jnp.cumsum(ext)
            lo = jnp.arange(Bl) + (ext.shape[0] - Bl) - W
            hi = jnp.arange(Bl) + (ext.shape[0] - Bl)
            return c[hi] - jnp.where(lo >= 0, c[jnp.maximum(lo, 0)], 0.0)
        # min/max: van Herk block decomposition — per W-block running
        # extrema from both directions, then window [j-W+1, j] =
        # combine(suffix[j-W+1], prefix[j]).  O(L) memory (a naive
        # [Bl, W] gather would materialize the very windows this module
        # exists to avoid holding).
        import jax.lax as lax

        L = ext.shape[0]
        P = ((L + W - 1) // W) * W
        x = jnp.concatenate(
            [jnp.full((P - L,), ident, ext.dtype), ext]).reshape(-1, W)
        op = lax.cummax if kind == "max" else lax.cummin
        pre = op(x, axis=1).reshape(-1)
        suf = op(x[:, ::-1], axis=1)[:, ::-1].reshape(-1)
        j = jnp.arange(P - Bl, P)  # the last Bl padded positions
        # j >= W-1 always: P >= L >= W + Bl - 1, so j - W + 1 >= 0
        return combine(suf[j - W + 1], pre[j])

    def shard_fn(local):  # [Bl] per shard
        d = jax.lax.axis_index("keys")
        # accumulate halos: blocks from shards d-1, d-2, ... d-n_rot
        ext = local
        block = local
        for r in range(1, n_rot + 1):
            block = jax.lax.ppermute(
                block, "keys", perm=[(i, (i + 1) % nk) for i in range(nk)])
            # the block now held came from shard d-r; wrap-around past
            # global bin 0 contributes the identity
            valid = d - r >= 0
            ext = jnp.concatenate(
                [jnp.where(valid, block, ident), ext])
        return sliding(ext)

    fn = shard_map(shard_fn, mesh=mesh, in_specs=P("keys"),
                   out_specs=P("keys"))
    sharding = NamedSharding(mesh, P("keys"))
    return jax.jit(fn), sharding


@functools.lru_cache(maxsize=64)
def _ring_step_2d(kind: str, nk: int, C: int, Bl: int, W: int):
    """[C, n_bins] variant of :func:`_ring_step`: every key's bin ring is
    aggregated at once, bin axis block-sharded, ``ppermute`` halos —
    the engine's long-window emission kernel (KeyedBinState._emit_ring
    selects it instead of the [C, k, W] gather when W is large)."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    from .mesh_window import _keys_mesh

    ident = _init_value(AggKind(kind))
    additive = kind in ("sum", "count")
    mesh = _keys_mesh(nk)
    n_rot = max((W - 1 + Bl - 1) // Bl, 0)

    def combine(a, b):
        return jnp.minimum(a, b) if kind == "min" else jnp.maximum(a, b)

    def sliding(ext):  # [C, L] -> [C, Bl]
        L = ext.shape[1]
        if additive:
            c = jnp.cumsum(ext, axis=1)
            lo = jnp.arange(Bl) + (L - Bl) - W
            hi = jnp.arange(Bl) + (L - Bl)
            head = jnp.where(lo >= 0, c[:, jnp.maximum(lo, 0)], 0.0)
            return c[:, hi] - head
        import jax.lax as lax

        Pp = ((L + W - 1) // W) * W
        x = jnp.concatenate(
            [jnp.full((ext.shape[0], Pp - L), ident, ext.dtype), ext],
            axis=1).reshape(ext.shape[0], -1, W)
        op = lax.cummax if kind == "max" else lax.cummin
        pre = op(x, axis=2).reshape(ext.shape[0], -1)
        suf = op(x[:, :, ::-1], axis=2)[:, :, ::-1].reshape(
            ext.shape[0], -1)
        j = jnp.arange(Pp - Bl, Pp)
        return combine(suf[:, j - W + 1], pre[:, j])

    def shard_fn(local):  # [C, Bl] per shard
        d = jax.lax.axis_index("keys")
        ext = local
        block = local
        for r in range(1, n_rot + 1):
            block = jax.lax.ppermute(
                block, "keys", perm=[(i, (i + 1) % nk) for i in range(nk)])
            valid = d - r >= 0
            ext = jnp.concatenate(
                [jnp.where(valid, block, ident), ext], axis=1)
        return sliding(ext)

    fn = shard_map(shard_fn, mesh=mesh, in_specs=P(None, "keys"),
                   out_specs=P(None, "keys"))
    sharding = NamedSharding(mesh, P(None, "keys"))
    return jax.jit(fn), sharding


def ring_pane_aggregate_2d(bins: "np.ndarray", width_bins: int, kind: str,
                           n_shards: int) -> np.ndarray:
    """[C, n_bins] batch form of :func:`ring_pane_aggregate`."""
    import jax
    import jax.numpy as jnp

    if kind not in ("sum", "count", "min", "max"):
        raise ValueError(f"ring_pane_aggregate_2d: unsupported {kind!r}")
    C, n = bins.shape
    assert n % n_shards == 0
    fn, sharding = _ring_step_2d(kind, n_shards, C, n // n_shards,
                                 int(width_bins))
    dev = jax.device_put(jnp.asarray(bins, jnp.float64), sharding)
    return np.asarray(jax.device_get(fn(dev)))


def ring_pane_aggregate(bins: np.ndarray, width_bins: int, kind: str,
                        n_shards: int) -> np.ndarray:
    """Aggregate of the trailing ``width_bins`` bins ending at every bin
    position, computed with the bin dimension block-sharded over
    ``n_shards`` devices and halos exchanged by ring ``ppermute``.

    ``bins`` length must divide evenly by ``n_shards``; positions whose
    window starts before bin 0 aggregate only the existing prefix
    (identity-padded), matching a stream's warm-up panes.
    """
    import jax
    import jax.numpy as jnp

    if kind not in ("sum", "count", "min", "max"):
        # avg must divide by the per-pane non-null count — callers
        # combine a sum ring with a count ring instead (as keyed_bins
        # does); accepting 'avg' here would silently return sums
        raise ValueError(f"ring_pane_aggregate: unsupported kind {kind!r}")
    n = len(bins)
    assert n % n_shards == 0, "bin count must divide the shard count"
    Bl = n // n_shards
    assert width_bins >= 1
    fn, sharding = _ring_step(kind, n_shards, Bl, int(width_bins))
    dev = jax.device_put(jnp.asarray(bins, jnp.float64), sharding)
    return np.asarray(jax.device_get(fn(dev)))
