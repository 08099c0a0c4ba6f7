"""Mesh-sharded keyed bin aggregation — the engine's multi-chip data plane.

This is the production form of the SPMD windowed-aggregation step: the
same keyed bin-ring state as :class:`~arroyo_tpu.ops.keyed_bins.KeyedBinState`
but sharded over a 1-D ``("keys",)`` device mesh, so the reference's entire
scale-out tier — collector hash routing
(/root/reference/arroyo-worker/src/engine.rs:183-240) plus the TCP shuffle
(/root/reference/arroyo-worker/src/network_manager.rs:221-307) — becomes ONE
jitted step whose shuffle is ``jax.lax.all_to_all`` over ICI:

1. **route**: incoming rows (sharded over the mesh as the data-parallel
   axis) compute their key-range owner (``server_for_hash`` semantics:
   top bits of the u64 key hash) and exchange buckets with ``all_to_all``;
2. **merge**: each key shard keeps a *sorted* uint64 key table (EMPTY
   sentinel padding) plus per-channel bin accumulators ``[n_ch, C, B]``;
   new keys merge via one fused ``lax.sort``, old state re-scatters to the
   new slot layout, and routed rows scatter-add/min/max in;
3. **fire**: pane emission and eviction are separate jitted calls driven
   by the host watermark, identical in semantics to the single-device
   ``KeyedBinState`` (panes fire once, in order, per key).

Zero-loss guarantees are HOST-enforced (the device never silently drops):

* per-slice row buffers are sized to the padded batch, so the route
  bucketing structurally cannot overflow — a device-side counter proves it;
* the host key directory tracks per-shard key cardinality exactly and
  grows device capacity BEFORE a batch that would overflow dispatches —
  the device key-drop counter proves it;
* bin-ring occupancy is linear (base-relative, rolled on watermark
  advance) and the host grows ``B`` when data runs ahead of the watermark.

Aggregate channels reuse the null-skipping layout of ``keyed_bins``:
hidden additive validity-count channels per column-reading agg, min/max
as native scatter-min/max (VERDICT round-1 item #5: min/max support,
no silent drops, overflow counters).
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..graph.logical import AggKind, AggSpec
from ..obs.perf import kernel_name
from ..ops.keyed_bins import (
    NEG_INF,
    POS_INF,
    FiredPanes,
    KeyedBinState,
    _bucket,
    _fire_done,
    _init_value,
    _prefetch_host,
    _readback,
    build_channels,
    channel_inits,
    channel_input,
    directory_insert,
    preaggregate,
)

EMPTY = np.uint64(0xFFFF_FFFF_FFFF_FFFF)  # sentinel: empty key slot
_MIN_ROWS = 256  # per-slice row-buffer floor (power-of-two bucketed)


def mesh_key_shards() -> int:
    """Number of key shards the engine should use: ``ARROYO_MESH`` = 'off'
    (1), an explicit integer, or 'auto' (largest power of two <= device
    count — the planner's "use the mesh when there is one" policy)."""
    import os

    import jax

    mode = os.environ.get("ARROYO_MESH", "auto").lower()
    if mode in ("off", "0", "1", "none"):
        return 1
    n = len(jax.devices())
    if mode.isdigit():
        # routing uses the top log2(nk) key bits, so the shard count must
        # be a power of two — round down, and never exceed the devices
        n = min(int(mode), n)
    p = 1
    while p * 2 <= n:
        p *= 2
    return p


@functools.lru_cache(maxsize=8)
def _keys_mesh(nk: int):
    import jax
    from jax.sharding import Mesh

    devs = jax.devices()
    assert len(devs) >= nk, f"mesh wants {nk} devices, have {len(devs)}"
    return Mesh(np.array(devs[:nk]), ("keys",))


def _init_filled(ch_kinds: Tuple[str, ...], shape: Tuple[int, ...]
                 ) -> np.ndarray:
    """[n_ch, *shape] float32 array filled with each channel's identity."""
    out = np.zeros((len(ch_kinds),) + shape, np.float64)
    for j, k in enumerate(ch_kinds):
        out[j] = _init_value(AggKind(k))
    return out


def _channel_rows(aggs, ch_kinds, valid_of, agg_inputs, n) -> np.ndarray:
    """[n_ch, n] per-row channel contributions, nulls masked to identity
    (shared semantics: ops/keyed_bins.channel_input)."""
    vals = np.zeros((len(ch_kinds), n), dtype=np.float64)
    for j in range(len(ch_kinds)):
        vals[j] = channel_input(aggs, ch_kinds, valid_of, j, agg_inputs, n)
    return vals


# ---------------------------------------------------------------------------
# jitted steps (cached per shape signature)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=256)
def _update_step(ch_kinds: Tuple[str, ...], nk: int, C: int, B: int, N: int,
                 shift: int = 0):
    """shard_map step: route rows over the mesh, merge keys, scatter bins.

    Global shapes: keys u64[nk*C]; bins f32[n_ch, nk*C, B];
    counts i32[nk*C, B]; of i32[nk, 2] (route-drop, key-drop counters);
    rows: key u64[nk*N], bin i32[nk*N], vals f32[n_ch, nk*N], ok bool[nk*N].

    ``shift`` skips the top key-hash bits already consumed by subtask
    key ranges (``set_route_shift``): at operator parallelism P > 1 each
    subtask only ever sees a 1/P top-bit slice, and routing on those
    same bits would funnel the whole mesh onto ~nk/P devices.

    Compiled with explicit ``in_shardings``/``out_shardings`` over the
    ``("keys",)`` axis (SNIPPETS [1][2]): state outputs carry exactly
    the shardings the next call's inputs declare, so chained dispatches
    hand off pre-partitioned device arrays with zero implicit
    resharding — measured by ``parallel/shuffle.ensure_sharded``.
    """
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    n_ch = len(ch_kinds)
    lg = int(np.log2(nk)) if nk > 1 else 0
    inits = tuple(float(_init_value(AggKind(k))) for k in ch_kinds)

    def shard_fn(keys, bins, counts, of, r_key, r_bin, r_vals, r_ok):
        # per-shard views: keys u64[C]; bins [n_ch, C, B]; counts [C, B];
        # of i32[1, 2]; rows: this slice's N rows
        # ---- route: bucket rows by destination shard, all_to_all over ICI
        if nk > 1:
            routed = (r_key << np.uint64(shift)) if shift else r_key
            dest = (routed >> np.uint64(64 - lg)).astype(jnp.int32)
            order = jnp.argsort(dest)
            d_s = dest[order]
            k_s, b_s = r_key[order], r_bin[order]
            v_s, ok_s = r_vals[:, order], r_ok[order]
            onehot = jax.nn.one_hot(d_s, nk, dtype=jnp.int32)
            pos = (jnp.cumsum(onehot, axis=0) - onehot)
            pos = jnp.sum(pos * onehot, axis=1)
            # bucket capacity == slice size N: a slice holds at most N rows
            # total, so per-dest position can never reach N — structurally
            # zero route drops; the counter proves it stays that way
            slot_ok = pos < N
            route_drop = jnp.sum(ok_s & ~slot_ok)
            tgt = d_s * N + jnp.where(slot_ok, pos, 0)
            buf_key = jnp.full((nk * N,), EMPTY, jnp.uint64).at[tgt].set(
                jnp.where(ok_s & slot_ok, k_s, EMPTY), mode="drop")
            buf_bin = jnp.zeros((nk * N,), jnp.int32).at[tgt].set(
                jnp.where(slot_ok, b_s, 0), mode="drop")
            buf_ok = jnp.zeros((nk * N,), bool).at[tgt].set(
                ok_s & slot_ok, mode="drop")
            buf_val = jnp.zeros((n_ch + 1, nk * N),
                                jnp.float64).at[:, tgt].set(
                jnp.where(slot_ok, v_s, 0.0), mode="drop")
            buf_key = jax.lax.all_to_all(
                buf_key.reshape(nk, N), "keys", 0, 0).reshape(-1)
            buf_bin = jax.lax.all_to_all(
                buf_bin.reshape(nk, N), "keys", 0, 0).reshape(-1)
            buf_ok = jax.lax.all_to_all(
                buf_ok.reshape(nk, N), "keys", 0, 0).reshape(-1)
            buf_val = jax.lax.all_to_all(
                buf_val.reshape(n_ch + 1, nk, N), "keys", 1,
                1).reshape(n_ch + 1, -1)
        else:
            route_drop = jnp.int32(0)
            buf_key = jnp.where(r_ok, r_key, EMPTY)
            buf_bin, buf_ok, buf_val = r_bin, r_ok, r_vals
        R = buf_key.shape[0]

        # ---- merge: one fused sort of (old keys ++ incoming keys)
        all_keys = jnp.concatenate([keys, buf_key])
        s_keys, = jax.lax.sort((all_keys,), num_keys=1)
        is_first = jnp.ones_like(s_keys, dtype=bool).at[1:].set(
            s_keys[1:] != s_keys[:-1])
        is_real = is_first & (s_keys != EMPTY)
        rank = jnp.cumsum(is_real) - 1
        key_drop = jnp.sum(is_real & (rank >= C))
        slot_ok2 = is_real & (rank < C)
        tgt2 = jnp.where(slot_ok2, rank, C)
        new_keys = jnp.full((C,), EMPTY, jnp.uint64).at[tgt2].set(
            jnp.where(slot_ok2, s_keys, EMPTY), mode="drop")

        def count_less(table, q_sorted):
            # #(table < q_i) per (sorted) query — searchsorted-left
            # semantics without jnp.searchsorted, which lowers to a
            # sequential per-bit scan on TPU (measured 78 ms per 16k
            # queries; BASELINE.md round-4).  Stable argsort of the
            # concatenation with queries FIRST (equal table entries sort
            # after equal queries), inverse-permute, subtract own rank.
            nq = q_sorted.shape[0]
            nt = nq + table.shape[0]
            o = jnp.argsort(jnp.concatenate([q_sorted, table]),
                            stable=True)
            inv = jnp.zeros(nt, jnp.int32).at[o].set(
                jnp.arange(nt, dtype=jnp.int32))
            return inv[:nq] - jnp.arange(nq, dtype=jnp.int32)

        # ---- re-map old per-key state into the new slot layout
        # (keys is sorted: it was built as new_keys by the previous step)
        old_idx = count_less(new_keys, keys).clip(0, C - 1)
        old_found = (new_keys[old_idx] == keys) & (keys != EMPTY)
        o_tgt = jnp.where(old_found, old_idx, C)
        new_counts = jnp.zeros_like(counts).at[o_tgt].add(
            jnp.where(old_found[:, None], counts, 0), mode="drop")
        chs = []
        for j, kind in enumerate(ch_kinds):
            base = jnp.full((C, B), inits[j], jnp.float64)
            src = jnp.where(old_found[:, None], bins[j],
                            jnp.float64(inits[j]))
            if kind in ("sum", "count"):
                ch = base.at[o_tgt].add(
                    jnp.where(old_found[:, None], bins[j], 0.0), mode="drop")
            elif kind == "min":
                ch = base.at[o_tgt].min(src, mode="drop")
            else:  # max
                ch = base.at[o_tgt].max(src, mode="drop")
            chs.append(ch)

        # ---- scatter routed cells (host pre-aggregated per (key, bin):
        # row 0 of the value payload is the per-cell ROW COUNT)
        qo = jnp.argsort(buf_key, stable=True)
        row_idx = jnp.zeros(R, jnp.int32).at[qo].set(
            count_less(new_keys, buf_key[qo])).clip(0, C - 1)
        row_found = (new_keys[row_idx] == buf_key) & buf_ok
        si = jnp.where(row_found, row_idx, C)
        bi = jnp.where(row_found, buf_bin, 0).clip(0, B - 1)
        new_counts = new_counts.at[si, bi].add(
            jnp.where(row_found, buf_val[0], 0.0).astype(new_counts.dtype),
            mode="drop")
        for j, kind in enumerate(ch_kinds):
            x = buf_val[j + 1]
            if kind in ("sum", "count"):
                chs[j] = chs[j].at[si, bi].add(
                    jnp.where(row_found, x, 0.0), mode="drop")
            elif kind == "min":
                chs[j] = chs[j].at[si, bi].min(
                    jnp.where(row_found, x, POS_INF), mode="drop")
            else:
                chs[j] = chs[j].at[si, bi].max(
                    jnp.where(row_found, x, NEG_INF), mode="drop")
        new_bins = jnp.stack(chs)
        new_of = of + jnp.stack([route_drop, key_drop]).astype(jnp.int32)[
            None, :]
        return new_keys, new_bins, new_counts, new_of

    mesh = _keys_mesh(nk)
    fn = shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P("keys"), P(None, "keys", None), P("keys", None),
                  P("keys", None), P("keys"), P("keys"),
                  P(None, "keys"), P("keys")),
        out_specs=(P("keys"), P(None, "keys", None), P("keys", None),
                   P("keys", None)),
        check_vma=False,
    )
    s1 = NamedSharding(mesh, P("keys"))
    s_bins = NamedSharding(mesh, P(None, "keys", None))
    s2 = NamedSharding(mesh, P("keys", None))
    s_vals = NamedSharding(mesh, P(None, "keys"))
    return jax.jit(fn,
                   in_shardings=(s1, s_bins, s2, s2, s1, s1, s_vals, s1),
                   out_shardings=(s1, s_bins, s2, s2))


@functools.lru_cache(maxsize=256)
def _fire_step(ch_kinds: Tuple[str, ...], nk: int, C: int, B: int, W: int):
    """Pane emission: aggregate window bins for panes in
    [first_rel, wm_rel].  Pure read — eviction is the separate roll step.
    Explicit in/out shardings: the state arrives exactly as the update
    step left it (no implicit resharding between chained dispatches)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    # panes at relative index 0..B+W-2: the last ring bin (B-1) still
    # feeds panes up to B-1+W-1, which must be emittable on final flush
    PANES = B + W - 1

    @kernel_name("mesh_fire_step")
    def run(keys, bins, counts, lims):
        first_rel, wm_rel = lims[0], lims[1]
        pane = jnp.arange(PANES, dtype=jnp.int32)
        offs = jnp.arange(W, dtype=jnp.int32) - (W - 1)
        win = pane[:, None] + offs[None, :]  # [PANES, W] linear bin index
        win_ok = (win >= 0) & (win < B)
        wc = win.clip(0, B - 1)
        pane_ok = (pane >= first_rel) & (pane <= wm_rel)
        cnt_g = counts[:, wc]  # [CT, PANES, W]
        cnts = jnp.sum(jnp.where(win_ok[None], cnt_g, 0), axis=-1)
        outs = []
        for j, kind in enumerate(ch_kinds):
            g = bins[j][:, wc]
            if kind in ("sum", "count"):
                r = jnp.sum(jnp.where(win_ok[None], g, 0.0), axis=-1)
            elif kind == "min":
                r = jnp.min(jnp.where(win_ok[None], g, POS_INF), axis=-1)
            else:
                r = jnp.max(jnp.where(win_ok[None], g, NEG_INF), axis=-1)
            outs.append(r)
        mask = pane_ok[None, :] & (cnts > 0) & (keys != EMPTY)[:, None]
        return (jnp.stack(outs) if outs else
                jnp.zeros((0,) + cnts.shape)), cnts, mask

    mesh = _keys_mesh(nk)
    s1 = NamedSharding(mesh, P("keys"))
    s_bins = NamedSharding(mesh, P(None, "keys", None))
    s2 = NamedSharding(mesh, P("keys", None))
    rep = NamedSharding(mesh, P())
    return jax.jit(run,
                   in_shardings=(s1, s_bins, s2, rep),
                   out_shardings=(NamedSharding(mesh, P(None, "keys",
                                                        None)), s2, s2))


@functools.lru_cache(maxsize=256)
def _reset_span_step(ch_kinds: Tuple[str, ...], nk: int, C: int, B: int):
    """Reset the relative bin columns in [lims[0], lims[1]] to each
    channel's identity (counts to 0) — the barrier-drain half of the
    factor-pane path: drained cells must read as empty for the next
    fire WITHOUT moving the ring base the way the roll step does.
    Output shardings match the update step's state inputs."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    inits = tuple(float(_init_value(AggKind(k))) for k in ch_kinds)

    @kernel_name("mesh_reset_span_step")
    def run(bins, counts, lims):
        idx = jnp.arange(B, dtype=jnp.int32)
        m = (idx >= lims[0]) & (idx <= lims[1])
        counts = jnp.where(m[None, :], 0, counts)
        outs = [jnp.where(m[None, :], jnp.float64(inits[j]), bins[j])
                for j in range(len(ch_kinds))]
        return jnp.stack(outs), counts

    mesh = _keys_mesh(nk)
    s_bins = NamedSharding(mesh, P(None, "keys", None))
    s2 = NamedSharding(mesh, P("keys", None))
    return jax.jit(run,
                   in_shardings=(s_bins, s2, NamedSharding(mesh, P())),
                   out_shardings=(s_bins, s2))


@functools.lru_cache(maxsize=256)
def _roll_step(ch_kinds: Tuple[str, ...], nk: int, C: int, B: int):
    """Evict bins below the new base: shift the linear bin axis left by
    ``shift`` and fill the tail with each channel's identity.  Output
    shardings match the update step's state inputs, so the roll hands
    the ring back pre-partitioned."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    inits = tuple(float(_init_value(AggKind(k))) for k in ch_kinds)

    @kernel_name("mesh_roll_step")
    def run(bins, counts, shift):
        idx = jnp.arange(B, dtype=jnp.int32) + shift
        ok = idx < B
        ic = idx.clip(0, B - 1)
        counts = jnp.where(ok[None, :], counts[:, ic], 0)
        outs = [jnp.where(ok[None, :], bins[j][:, ic], jnp.float64(inits[j]))
                for j in range(len(ch_kinds))]
        return jnp.stack(outs), counts

    mesh = _keys_mesh(nk)
    s_bins = NamedSharding(mesh, P(None, "keys", None))
    s2 = NamedSharding(mesh, P("keys", None))
    return jax.jit(run,
                   in_shardings=(s_bins, s2, NamedSharding(mesh, P())),
                   out_shardings=(s_bins, s2))


# ---------------------------------------------------------------------------
# host wrapper: KeyedBinState-compatible API over the mesh
# ---------------------------------------------------------------------------


class MeshKeyedBinState:
    """Drop-in replacement for :class:`KeyedBinState` whose state lives
    sharded across the ``("keys",)`` device mesh.

    The host keeps the key directory (key-hash -> slot, for key-column
    value recovery and exact per-shard cardinality tracking), window
    bookkeeping (base bin, last fired pane), and admission control; the
    device holds keys/bins/counts sharded by key range and does route +
    merge + scatter + fire as jitted SPMD programs.
    """

    GROW_AT = 0.85  # per-shard occupancy that triggers host-side growth

    def __init__(self, aggs: Tuple[AggSpec, ...], slide_micros: int,
                 width_micros: int, capacity: int = 0,
                 n_shards: Optional[int] = None):
        import jax

        assert jax.config.jax_enable_x64, (
            "MeshKeyedBinState requires jax_enable_x64: u64 key hashes "
            "travel through jit and would truncate to uint32")
        if capacity <= 0:
            from ..config import config

            capacity = config().state_capacity
        assert width_micros % slide_micros == 0
        self.aggs = aggs
        self.kinds = tuple(a.kind.value for a in aggs)
        self._ch_kinds, self._valid_ch = build_channels(aggs)
        self._valid_of = {v: k for k, v in self._valid_ch.items()}
        self.slide = slide_micros
        self.W = width_micros // slide_micros
        self.B = _bucket(2 * self.W + 4, floor=8)
        self.nk = n_shards or mesh_key_shards()
        self.C = _bucket(max(capacity // self.nk, 64))  # per-shard slots
        self.mesh = _keys_mesh(self.nk)
        # key-hash bits to skip when routing (set_route_shift): subtask
        # key ranges consume the TOP bits, so a parallel operator's mesh
        # must route on the bits below them or every row funnels to the
        # few shards covering this subtask's top-bit slice
        self.route_shift = 0

        # host key directory (same layout as KeyedBinState for _emit)
        self.key_sorted = np.zeros(0, dtype=np.uint64)
        self.slot_of_sorted = np.zeros(0, dtype=np.int64)
        self.next_slot = 0
        self.slot_to_key = np.zeros(64, dtype=np.uint64)
        from ..native import NativeDir

        self._ndir = NativeDir.create(self.C)
        self.shard_counts = np.zeros(self.nk, dtype=np.int64)

        # window bookkeeping (absolute bins; device works base-relative)
        self.base_bin: Optional[int] = None
        self.min_bin: Optional[int] = None
        self.max_bin: Optional[int] = None
        self.last_fired_pane: Optional[int] = None
        # (start, duration) of the readbacks of the fire under way
        # (keyed_bins._readback / _fire_done)
        self._d2h: List[Tuple[float, float]] = []
        self.late_rows = 0
        # mirror of KeyedBinState.total_rows: bounds any cell/pane count
        # sum, driving i32 -> i64 plane promotion before a wrap is possible
        self.total_rows = 0
        # merge-input mode (factor windows): see
        # KeyedBinState.set_merge_inputs — channels read per-pane partial
        # columns, the counts plane accumulates the row-mass column
        self._merge_cols: Optional[Dict[int, str]] = None
        self._rows_col: Optional[str] = None

        self._alloc_device()

    # -- device state ------------------------------------------------------

    def _alloc_device(self) -> None:
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        CT = self.nk * self.C
        put = functools.partial(jax.device_put)
        self.d_keys = put(jnp.full((CT,), EMPTY, jnp.uint64),
                          NamedSharding(self.mesh, P("keys")))
        bins = _init_filled(self._ch_kinds, (CT, self.B))
        self.d_bins = put(jnp.asarray(bins),
                          NamedSharding(self.mesh, P(None, "keys", None)))
        self.d_counts = put(jnp.zeros((CT, self.B), jnp.int32),
                            NamedSharding(self.mesh, P("keys", None)))
        self.d_of = put(jnp.zeros((self.nk, 2), jnp.int32),
                        NamedSharding(self.mesh, P("keys", None)))

    def device_bytes(self) -> int:
        """Resident device footprint of the sharded planes (metadata
        only — ``.nbytes`` off the handles, no transfer); feeds the
        per-job device-memory ledger (obs/latency.py)."""
        return (int(self.d_keys.nbytes) + int(self.d_bins.nbytes)
                + int(self.d_counts.nbytes) + int(self.d_of.nbytes))

    def set_route_shift(self, shift: int) -> None:
        """Skip the top ``shift`` key-hash bits when routing rows to
        shards (host directory AND device route step stay in lockstep).
        Set by BinAggOperator before any row lands when the operator
        runs at parallelism > 1: subtask ranges split the top bits, so
        without the shift every subtask's keys collapse onto the
        ~nk/parallelism shards covering its range — the mesh silently
        degenerates to one device per subtask."""
        assert self.next_slot == 0 and self.total_rows == 0, \
            "route shift must be set before any key is admitted"
        assert 0 <= shift <= 32
        self.route_shift = int(shift)

    def _shard_of(self, kh: np.ndarray) -> np.ndarray:
        if self.nk == 1:
            return np.zeros(len(kh), dtype=np.int64)
        lg = int(np.log2(self.nk))
        if self.route_shift:
            kh = kh << np.uint64(self.route_shift)
        return (kh >> np.uint64(64 - lg)).astype(np.int64)

    # -- host key directory ------------------------------------------------

    def _lookup_or_insert(self, kh: np.ndarray) -> np.ndarray:
        kh = np.where(kh == EMPTY, EMPTY - np.uint64(1), kh)  # sentinel

        def ensure(total, new_keys):
            if total > len(self.slot_to_key):
                grown = np.zeros(_bucket(total, floor=64), np.uint64)
                grown[:self.next_slot] = self.slot_to_key[:self.next_slot]
                self.slot_to_key = grown
            np.add.at(self.shard_counts, self._shard_of(new_keys), 1)
            # grow BEFORE any shard can overflow: exact host-side counts
            while self.shard_counts.max() > self.GROW_AT * self.C:
                self._grow_capacity()

        return directory_insert(self, kh, ensure)

    def _grow_capacity(self) -> None:
        """Double per-shard capacity: host re-layout, sharded re-upload."""
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        import jax

        keys = np.asarray(jax.device_get(self.d_keys)).reshape(self.nk,
                                                               self.C)
        bins = np.asarray(jax.device_get(self.d_bins)).reshape(
            len(self._ch_kinds), self.nk, self.C, self.B)
        counts = np.asarray(jax.device_get(self.d_counts)).reshape(
            self.nk, self.C, self.B)
        C2 = self.C * 2
        keys2 = np.full((self.nk, C2), EMPTY, np.uint64)
        keys2[:, :self.C] = keys  # EMPTY pads sort AFTER real keys
        bins2 = _init_filled(self._ch_kinds, (self.nk, C2, self.B))
        bins2[:, :, :self.C] = bins
        counts2 = np.zeros((self.nk, C2, self.B), counts.dtype)
        counts2[:, :self.C] = counts
        self.C = C2
        self.d_keys = jax.device_put(
            jnp.asarray(keys2.reshape(-1)),
            NamedSharding(self.mesh, P("keys")))
        self.d_bins = jax.device_put(
            jnp.asarray(bins2.reshape(len(self._ch_kinds), -1, self.B)),
            NamedSharding(self.mesh, P(None, "keys", None)))
        self.d_counts = jax.device_put(
            jnp.asarray(counts2.reshape(-1, self.B)),
            NamedSharding(self.mesh, P("keys", None)))

    def _grow_ring(self, needed: int) -> None:
        """Data ran ahead of the watermark beyond the bin ring: widen B."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        B2 = self.B
        while B2 < needed:
            B2 <<= 1
        bins = np.asarray(jax.device_get(self.d_bins))
        counts = np.asarray(jax.device_get(self.d_counts))
        CT = bins.shape[1]
        bins2 = _init_filled(self._ch_kinds, (CT, B2))
        bins2[:, :, :self.B] = bins
        counts2 = np.zeros((CT, B2), counts.dtype)
        counts2[:, :self.B] = counts
        self.B = B2
        self.d_bins = jax.device_put(
            jnp.asarray(bins2), NamedSharding(self.mesh,
                                              P(None, "keys", None)))
        self.d_counts = jax.device_put(
            jnp.asarray(counts2), NamedSharding(self.mesh, P("keys", None)))

    def _rebase(self, new_base: int) -> None:
        """Out-of-order rows landed below the ring base while their panes
        are still unfired: shift the linear columns right (host re-layout,
        rare) so column 0 becomes ``new_base``."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        off = self.base_bin - new_base
        B2 = _bucket(off + self.B, floor=8)
        bins = np.asarray(jax.device_get(self.d_bins))
        counts = np.asarray(jax.device_get(self.d_counts))
        CT = bins.shape[1]
        bins2 = _init_filled(self._ch_kinds, (CT, B2))
        bins2[:, :, off:off + self.B] = bins
        counts2 = np.zeros((CT, B2), counts.dtype)
        counts2[:, off:off + self.B] = counts
        self.B = B2
        self.base_bin = new_base
        self.d_bins = jax.device_put(
            jnp.asarray(bins2), NamedSharding(self.mesh,
                                              P(None, "keys", None)))
        self.d_counts = jax.device_put(
            jnp.asarray(counts2), NamedSharding(self.mesh, P("keys", None)))

    # -- update ------------------------------------------------------------

    def set_merge_inputs(self, channel_cols: Dict[int, str],
                         rows_col: str) -> None:
        """Arm merge-input mode (factor windows) — same contract as
        :meth:`KeyedBinState.set_merge_inputs`; every channel (visible
        and hidden validity) must have a mapped partial column because
        the mesh ships all channels through the route step."""
        assert self.next_slot == 0 and self.total_rows == 0, \
            "merge inputs must be set before any key is admitted"
        for j in range(len(self._ch_kinds)):
            assert j in channel_cols, f"no merge column for channel {j}"
        self._merge_cols = dict(channel_cols)
        self._rows_col = rows_col

    def update(self, key_hash: np.ndarray, timestamps: np.ndarray,
               agg_inputs: Dict[str, np.ndarray]) -> None:
        n = len(key_hash)
        if n == 0:
            return
        from ..obs import perf as _perf

        # factor-window cost evidence (see KeyedBinState.update); the
        # DISPATCH counter increments next to the actual scatter below,
        # so all-late batches that never dispatch are not counted
        _perf.count("pane_update_rows", n)
        kh = np.where(key_hash == EMPTY, EMPTY - np.uint64(1),
                      key_hash.astype(np.uint64))
        self._lookup_or_insert(kh)  # idempotent; ensures capacity

        abs_bin = (timestamps // self.slide).astype(np.int64)
        # a row in bin b feeds panes b..b+W-1; it is late (dropped) ONLY
        # when all those panes already fired — same threshold as the
        # single-device KeyedBinState (NOT the first batch's minimum:
        # out-of-order rows before any fire are always live)
        if self.last_fired_pane is not None:
            thr = self.last_fired_pane - self.W + 2
            live = abs_bin >= thr
        else:
            live = np.ones(n, dtype=bool)
        self.late_rows += int((~live).sum())
        if not live.any():
            return
        if self._merge_cols is not None:
            from ..formats import coerce_float

            w_rows = coerce_float(agg_inputs[self._rows_col], np.float64)
            w_rows = np.where(np.isnan(w_rows), 0.0, w_rows)
            self.total_rows += int(np.ceil(w_rows[live].sum()))
        else:
            w_rows = None
            self.total_rows += int(live.sum())
        if self.total_rows >= KeyedBinState._i32_promote:
            import jax.numpy as _jnp

            if self.d_counts.dtype == _jnp.int32:
                # promote BEFORE the crossing batch lands (same policy as
                # KeyedBinState.update; kernels retrace on the new dtype)
                self.d_counts = self.d_counts.astype(_jnp.int64)
        lo = int(abs_bin[live].min())
        hi = int(abs_bin[live].max())
        self.min_bin = lo if self.min_bin is None else min(self.min_bin, lo)
        self.max_bin = hi if self.max_bin is None else max(self.max_bin, hi)
        if self.base_bin is None:
            self.base_bin = lo
        elif lo < self.base_bin:
            # live rows BELOW the ring base (out-of-order arrivals before
            # their panes fired): rebase the linear ring downward
            self._rebase(lo)
        if hi - self.base_bin >= self.B:
            self._grow_ring(hi - self.base_bin + 1)
        rel = (abs_bin - self.base_bin).astype(np.int32)

        if self._merge_cols is not None:
            # merge-input mode: channels read already-aggregated partial
            # columns (NaN masked to the channel identity); the row mass
            # rides as one extra additive channel so duplicate cells sum
            # their true masses instead of counting pane arrivals
            from ..formats import coerce_float

            vals = np.zeros((len(self._ch_kinds), n), dtype=np.float64)
            for j, kind in enumerate(self._ch_kinds):
                raw = coerce_float(agg_inputs[self._merge_cols[j]],
                                   np.float64)
                ident = np.float64(_init_value(AggKind(kind)))
                vals[j] = np.where(np.isnan(raw), ident, raw)
            if not live.all():
                idx = live.nonzero()[0]
                kh, rel, vals = kh[idx], rel[idx], vals[:, idx]
                w_rows = w_rows[idx]
            kh_c, rel_c, _arrivals, red = preaggregate(
                kh, rel, self._ch_kinds + ("sum",),
                np.concatenate([vals, w_rows[None]]))
            rowcnt = red[-1]
            vals_c = red[:-1]
        else:
            vals = _channel_rows(self.aggs, self._ch_kinds, self._valid_of,
                                 agg_inputs, n)
            # two-phase, local half: reduce rows per (key, bin) on the host
            # BEFORE routing (TumblingLocalAggregator analog) — shrinks both
            # the all_to_all payload and the per-shard scatter
            if not live.all():
                idx = live.nonzero()[0]
                kh, rel, vals = kh[idx], rel[idx], vals[:, idx]
            kh_c, rel_c, rowcnt, vals_c = preaggregate(
                kh, rel, self._ch_kinds, vals)
        m = len(kh_c)
        # pad to nk * N (N power-of-two cells per mesh slice); each slice
        # holds <= N cells so route buckets cannot overflow
        N = _bucket(-(-m // self.nk), floor=_MIN_ROWS)
        total = self.nk * N
        kh_p = np.full(total, EMPTY, np.uint64)
        kh_p[:m] = kh_c
        rel_p = np.zeros(total, np.int32)
        rel_p[:m] = rel_c
        ok_p = np.zeros(total, bool)
        ok_p[:m] = True
        vals_p = np.zeros((len(self._ch_kinds) + 1, total), np.float64)
        vals_p[0, :m] = rowcnt
        vals_p[1:, :m] = vals_c

        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..obs.perf import timed_device
        from . import shuffle as _shuffle

        shard1 = NamedSharding(self.mesh, P("keys"))
        # resharding invariant: state arrays must still carry the exact
        # shardings the previous step's out_shardings pinned — a
        # mismatch here is counted (and healed), never silently absorbed
        s_bins = NamedSharding(self.mesh, P(None, "keys", None))
        s2 = NamedSharding(self.mesh, P("keys", None))
        d_keys = _shuffle.ensure_sharded(self.d_keys, shard1)
        d_bins = _shuffle.ensure_sharded(self.d_bins, s_bins)
        d_counts = _shuffle.ensure_sharded(self.d_counts, s2)
        d_of = _shuffle.ensure_sharded(self.d_of, s2)
        step = _update_step(self._ch_kinds, self.nk, self.C, self.B, N,
                            self.route_shift)
        _perf.count("pane_update_dispatches")
        if self.nk > 1:
            # the route half of this step IS the keyed shuffle: one
            # all_to_all over ICI instead of a host exchange
            from ..obs import perf as _perf

            _perf.count(_shuffle.COLLECTIVES)
            _perf.count(_shuffle.COLLECTIVE_ROWS, m)
        self.d_keys, self.d_bins, self.d_counts, self.d_of = timed_device(
            step, d_keys, d_bins, d_counts, d_of,
            jax.device_put(jnp.asarray(kh_p), shard1),
            jax.device_put(jnp.asarray(rel_p), shard1),
            jax.device_put(jnp.asarray(vals_p),
                           NamedSharding(self.mesh, P(None, "keys"))),
            jax.device_put(jnp.asarray(ok_p), shard1))

    # -- pane emission -----------------------------------------------------

    def overflow_counters(self) -> Tuple[int, int]:
        """(route_dropped, keys_dropped) — both stay 0 under the host's
        admission control; exposed for metrics and tests."""
        import jax

        of = np.asarray(jax.device_get(self.d_of))
        return int(of[:, 0].sum()), int(of[:, 1].sum())

    def _read_fired(self, first_rel: int, wm_rel: int):
        """Run the fire step over relative panes [first_rel, wm_rel] and
        materialize (outs, cnts, mask, keys) on host — the shared read
        half of :meth:`fire_panes` and :meth:`drain_deltas` (transfer
        only the fired range; prefetch so the readbacks overlap into
        ~one round-trip)."""
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..obs.perf import timed_device
        from . import shuffle as _shuffle

        d_keys = _shuffle.ensure_sharded(
            self.d_keys, NamedSharding(self.mesh, P("keys")))
        d_bins = _shuffle.ensure_sharded(
            self.d_bins, NamedSharding(self.mesh, P(None, "keys", None)))
        d_counts = _shuffle.ensure_sharded(
            self.d_counts, NamedSharding(self.mesh, P("keys", None)))
        self.d_keys, self.d_bins, self.d_counts = d_keys, d_bins, d_counts
        fire = _fire_step(self._ch_kinds, self.nk, self.C, self.B, self.W)
        outs, cnts, mask = timed_device(
            fire, d_keys, d_bins, d_counts,
            jnp.asarray([first_rel, wm_rel], jnp.int32))
        k = wm_rel - first_rel + 1
        outs_d = outs[:, :, first_rel:first_rel + k]
        cnts_d = cnts[:, first_rel:first_rel + k]
        mask_d = mask[:, first_rel:first_rel + k]
        _prefetch_host(outs_d, cnts_d, mask_d, self.d_keys)
        return tuple(_readback(self, d)
                     for d in (outs_d, cnts_d, mask_d, self.d_keys))

    def _flatten_fired(self, outs, cnts, mask, keys_h, base: int,
                       first_rel: int):
        """Visible aggregate columns from the fired-cell grid — ONE home
        for both emission paths (mirrors KeyedBinState._out_cols) so a
        null/AVG semantics fix cannot apply to fire_panes and silently
        miss drain_deltas."""
        cell_idx, pane_idx = np.nonzero(mask)
        if len(cell_idx) == 0:
            return None
        keys = keys_h[cell_idx]
        # a fired cell is a row of a shard's sorted table, not a host
        # slot: the slot is found by hash, here and not in the operator
        from ..obs import perf as _perf

        _perf.count("fire_slot_lookups", len(keys))
        slots = self.slot_of_sorted[np.searchsorted(self.key_sorted, keys)]
        # pane_idx is relative to the transferred slice [first_rel, ..]
        window_end = (base + first_rel + pane_idx.astype(np.int64) + 1) \
            * self.slide
        out_cols: Dict[str, np.ndarray] = {}
        for i, a in enumerate(self.aggs):
            col = outs[i][cell_idx, pane_idx]
            if a.kind == AggKind.COUNT:
                col = col.astype(np.int64)
            elif i in self._valid_ch:
                nv = outs[self._valid_ch[i]][cell_idx, pane_idx]
                if a.kind == AggKind.AVG:
                    col = col / np.maximum(nv, 1)
                col = np.where(nv > 0, col, np.nan)
            out_cols[a.output] = col
        return FiredPanes(keys, out_cols, window_end,
                          cnts[cell_idx, pane_idx], slots)

    def fire_panes(self, watermark: int, final: bool = False):
        if self.max_bin is None or self.next_slot == 0:
            return None
        if final:
            last_pane = self.max_bin + self.W - 1
        else:
            last_pane = min(int(watermark // self.slide) - 1,
                            self.max_bin + self.W - 1)
        first_pane = (self.last_fired_pane + 1
                      if self.last_fired_pane is not None
                      else (self.min_bin or 0))
        if last_pane < first_pane:
            return None
        base = self.base_bin if self.base_bin is not None else 0
        # rel pane range is always within [0, B+W-2]: last_pane is capped
        # at max_bin + W - 1 and max_bin < base + B
        wm_rel = last_pane - base
        first_rel = first_pane - base
        outs, cnts, mask, keys_h = self._read_fired(first_rel, wm_rel)

        import jax.numpy as jnp

        self.last_fired_pane = last_pane
        # evict: roll the base forward past bins no future pane needs
        new_base = last_pane - self.W + 2
        if new_base > base:
            shift = int(min(new_base - base, self.B))
            roll = _roll_step(self._ch_kinds, self.nk, self.C, self.B)
            self.d_bins, self.d_counts = roll(self.d_bins, self.d_counts,
                                              jnp.int32(shift))
            self.base_bin = base + shift
            if self.min_bin is not None:
                self.min_bin = max(self.min_bin, self.base_bin)

        _fire_done(self, int(watermark))
        return self._flatten_fired(outs, cnts, mask, keys_h, base,
                                   first_rel)

    def drain_deltas(self):
        """Checkpoint-barrier drain for FACTOR pane rings (W == 1): same
        contract as :meth:`KeyedBinState.drain_deltas` — read every
        un-fired (key, bin) cell as a pane delta, reset those cells on
        device, leave ``last_fired_pane``/``base_bin`` untouched."""
        assert self.W == 1, "drain_deltas is the factor-pane path (W == 1)"
        if self.max_bin is None or self.next_slot == 0:
            return None
        first_pane = (self.last_fired_pane + 1
                      if self.last_fired_pane is not None
                      else (self.min_bin or 0))
        last_pane = self.max_bin
        if last_pane < first_pane:
            return None
        base = self.base_bin if self.base_bin is not None else 0
        first_rel = max(first_pane - base, 0)
        wm_rel = last_pane - base
        outs, cnts, mask, keys_h = self._read_fired(first_rel, wm_rel)

        import jax.numpy as jnp

        # reset the drained relative bin span on device (base stays put:
        # later rows for these bins re-accumulate and ship as new deltas)
        rs = _reset_span_step(self._ch_kinds, self.nk, self.C, self.B)
        self.d_bins, self.d_counts = rs(
            self.d_bins, self.d_counts,
            jnp.asarray([first_rel, wm_rel], jnp.int32))

        _fire_done(self, None)
        return self._flatten_fired(outs, cnts, mask, keys_h, base,
                                   first_rel)

    # -- checkpoint --------------------------------------------------------

    def snapshot(self) -> Dict[str, np.ndarray]:
        """Canonical topology-independent snapshot (same format as
        KeyedBinState.snapshot): compacted per-key LINEAR bin columns
        (column j = absolute bin lo+j) + host key directory, so restore
        can re-shard onto any mesh OR a single device (rescale by key
        range, parquet.rs:194-218 analog)."""
        import jax

        keys = np.asarray(jax.device_get(self.d_keys))
        bins = np.asarray(jax.device_get(self.d_bins))
        counts = np.asarray(jax.device_get(self.d_counts))
        real = keys != EMPTY
        base = self.base_bin if self.base_bin is not None else -1
        if base >= 0 and self.max_bin is not None:
            lo = max(base, self.min_bin if self.min_bin is not None else base)
            span = self.max_bin - lo + 1
            first = lo - base  # device columns are base-relative
        else:
            lo, span, first = -1, 0, 0
        return {
            "bin_keys": keys[real],
            "bin_vals": bins[:, real][:, :, first:first + span],
            "bin_counts": counts[real][:, first:first + span],
            "ch_init": channel_inits(self._ch_kinds),
            # provenance marker (ignored by restore — the format is
            # topology-independent): lets tests/operators verify a
            # checkpoint was written by an N-shard mesh state
            "mesh_shards": np.array([self.nk], dtype=np.int64),
            "key_sorted": self.key_sorted,
            "slot_of_sorted": self.slot_of_sorted,
            "slot_to_key": self.slot_to_key[:self.next_slot],
            "meta": np.array([
                self.next_slot, lo,  # lo == min_bin (min_bin >= base_bin)
                -1 if self.max_bin is None else self.max_bin,
                -1 if self.last_fired_pane is None else self.last_fired_pane,
            ], dtype=np.int64),
        }

    def restore(self, arrays: Dict[str, np.ndarray]) -> None:
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        meta = arrays["meta"]
        self.next_slot = int(meta[0])
        lo = int(meta[1])
        self.max_bin = None if meta[2] < 0 else int(meta[2])
        self.last_fired_pane = None if meta[3] < 0 else int(meta[3])
        self.min_bin = None if lo < 0 else lo
        # base starts at the oldest stored bin (column 0); update()'s
        # _rebase lowers it on demand if live out-of-order rows arrive
        # below it (eagerly reserving columns down to the late threshold
        # could allocate a huge ring when the watermark lags behind data)
        self.base_bin = lo if lo >= 0 else None
        self.key_sorted = arrays["key_sorted"].astype(np.uint64)
        self.slot_of_sorted = arrays["slot_of_sorted"].astype(np.int64)
        from ..native import NativeDir

        self._ndir = NativeDir.create(max(self.next_slot, 64))
        if self._ndir is not None:
            self._ndir.load(self.key_sorted, self.slot_of_sorted)
        self.slot_to_key = np.zeros(
            _bucket(max(self.next_slot, 1), floor=64), np.uint64)
        self.slot_to_key[:self.next_slot] = \
            arrays["slot_to_key"].astype(np.uint64)[:self.next_slot]

        keys = arrays["bin_keys"].astype(np.uint64)
        bins = np.asarray(arrays["bin_vals"], dtype=np.float64)
        raw_counts = np.asarray(arrays["bin_counts"])
        from ..ops.keyed_bins import restored_count_state

        self.total_rows, cnt_dtype = restored_count_state(
            raw_counts, KeyedBinState._i32_promote)
        counts = raw_counts.astype(cnt_dtype)
        span = bins.shape[-1]
        self.B = _bucket(max(span, 2 * self.W + 4), floor=8)
        if span < self.B:  # pad linear columns out to the ring width
            bins_p = _init_filled(self._ch_kinds, bins.shape[1:-1] + (self.B,))
            bins_p[..., :span] = bins
            bins = bins_p
            counts_p = np.zeros(counts.shape[:-1] + (self.B,), cnt_dtype)
            counts_p[..., :span] = counts
            counts = counts_p
        # admission control counts come from the HOST directory (a strict
        # superset of device-resident keys — late-only keys included), so
        # growth still triggers before any shard can overflow
        self.shard_counts = np.bincount(
            self._shard_of(self.key_sorted), minlength=self.nk)
        # re-shard: place each key into its owner shard's sorted table
        shard = self._shard_of(keys)
        while self.shard_counts.max() > self.GROW_AT * self.C:
            self.C *= 2
        keys2 = np.full((self.nk, self.C), EMPTY, np.uint64)
        bins2 = _init_filled(self._ch_kinds, (self.nk, self.C, self.B))
        counts2 = np.zeros((self.nk, self.C, self.B), counts.dtype)
        for s in range(self.nk):
            sel = shard == s
            ks = keys[sel]
            order = np.argsort(ks)
            m = len(ks)
            keys2[s, :m] = ks[order]
            bins2[:, s, :m] = bins[:, sel][:, order]
            counts2[s, :m] = counts[sel][order]
        self.d_keys = jax.device_put(
            jnp.asarray(keys2.reshape(-1)),
            NamedSharding(self.mesh, P("keys")))
        self.d_bins = jax.device_put(
            jnp.asarray(bins2.reshape(len(self._ch_kinds), -1, self.B)),
            NamedSharding(self.mesh, P(None, "keys", None)))
        self.d_counts = jax.device_put(
            jnp.asarray(counts2.reshape(-1, self.B)),
            NamedSharding(self.mesh, P("keys", None)))
        self.d_of = jax.device_put(
            jnp.zeros((self.nk, 2), jnp.int32),
            NamedSharding(self.mesh, P("keys", None)))


def place_session_partition(p: int):
    """Mesh device owning session-state partition ``p``.  Session runs
    spread over the same ``("keys",)`` axis the window state shards on
    (round-robin, the join-ring placement policy): hot partitions of a
    sessionized job never funnel through one chip while a mesh windowed
    aggregate holds the others.  None when the mesh is off — staged
    planes then live on the default device."""
    from .shuffle import partition_device

    return partition_device(p)


def make_bin_state(aggs: Tuple[AggSpec, ...], slide_micros: int,
                   width_micros: int, capacity: int = 0):
    """State factory for BinAggOperator: mesh-sharded when more than one
    device is available (ARROYO_MESH=auto), single-device otherwise.

    Long-window/short-slide shapes (W = width/slide >= ARROYO_RING_MIN_W,
    e.g. HOP(1s, 300s)) shard the BIN dimension instead of the key
    dimension: KeyedBinState's ring-pane emission (ops/keyed_bins.py
    _emit_ring + parallel/ring_panes.py) replaces the [C, k, W] gather
    that dominates memory at large W — SURVEY §5's sequence-parallel
    discipline, selected automatically."""
    import os

    import jax

    nk = mesh_key_shards()
    W = width_micros // max(slide_micros, 1)
    ring_min = int(os.environ.get("ARROYO_RING_MIN_W", 64))
    ring_shape = (W >= ring_min
                  and os.environ.get("ARROYO_RING", "auto") != "off")
    # the mesh path ships uint64 key hashes through jit: without x64 JAX
    # would truncate them to uint32 (silently wrong merges/routes), so
    # fall back to the x32-safe single-device kernels
    if nk > 1 and jax.config.jax_enable_x64 and not ring_shape:
        return MeshKeyedBinState(aggs, slide_micros, width_micros,
                                 capacity=capacity, n_shards=nk)
    from ..ops.keyed_bins import KeyedBinState

    return KeyedBinState(aggs, slide_micros, width_micros,
                         capacity=capacity)
