"""Sharded-by-default data plane: on-device co-located shuffles and the
resharding invariant.

This module generalizes ``mesh_window.py``'s route step — bucket rows by
their destination shard, exchange buckets with one ``all_to_all`` over
ICI — into a reusable exchange any SHUFFLE edge can ride when its
producer and consumer subtasks are **co-located** (same process, same
mesh).  The host path (``native.partition_route`` into per-subtask
queues, or the TCP data plane across workers) remains the fallback:
``ARROYO_MESH=off`` reproduces the host topology bit-for-bit.

Two measured invariants live here, so "no resharding" is a number and
not a hope:

* **reshard counter** (``ensure_sharded``): a device-resident array that
  reaches a kernel whose explicit ``in_shardings`` contract it does not
  satisfy is re-placed — and counted (``perf`` counter
  ``reshard_transfers``, prometheus ``arroyo_worker_reshards_total``,
  profiler phase ``reshard``).  Operator kernels compile with matched
  ``out_shardings``/``in_shardings`` (SNIPPETS [1][2]), so chained
  dispatches hand off pre-partitioned device arrays and this counter
  stays **0 in steady state** — asserted by the smoke gate and recorded
  per bench run.  Host->device staging of fresh row batches is counted
  separately (``mesh_ingest_transfers``): it is the expected ingest
  boundary, not a resharding defect.
* **collective counter** (``shuffle_collectives`` /
  ``arroyo_worker_shuffle_collectives_total`` + profiler phase
  ``shuffle_collective``): every on-device exchange that replaced a host
  shuffle.  A co-located SHUFFLE edge carried here moves **zero**
  data-plane frames.

Destination semantics are bit-identical to the host Collector's
(``server_for_hash``: ``min(kh // (U64_MAX // n), n - 1)``), and the
exchange preserves the host path's row order per destination (stable by
destination, original order within), so mesh-on and mesh-off runs emit
identical rows — pinned by the smoke equivalence gate.

Knobs (docs/operations.md):
  ARROYO_SHUFFLE_DEVICE=auto|on|off   co-located device shuffle.  auto =
      on when the mesh is active AND the backend is a real accelerator
      (on the CPU backend the "device" is the same core, so the exchange
      is pure overhead — same policy as ARROYO_DEVICE_JOIN); on forces
      it (the CPU test mesh uses this for parity gates).
  ARROYO_MESH=auto|off|<n>            the mesh itself (mesh_window.py).
"""

from __future__ import annotations

import functools
import os
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ..obs import perf, profiler
from ..types import U64_MAX, Batch

# perf-counter keys (cheap process-wide ints; prometheus mirrors ride
# the increment sites)
RESHARDS = "reshard_transfers"
COLLECTIVES = "shuffle_collectives"
COLLECTIVE_ROWS = "shuffle_collective_rows"
HOST_ROUTES = "shuffle_host_routes"
INGEST_TRANSFERS = "mesh_ingest_transfers"

_MIN_ROWS = 256  # per-slice row floor (power-of-two bucketed)


def _bucket(n: int, floor: int = _MIN_ROWS) -> int:
    b = floor
    while b < n:
        b <<= 1
    return b


def device_shuffle_enabled(n_dests: int) -> bool:
    """Should an ``n_dests``-way co-located SHUFFLE edge ride the device
    exchange?  Requires the mesh on with enough devices and a
    power-of-two fan-out; ``auto`` additionally requires a non-CPU
    backend (device hop on the CPU backend is pure overhead)."""
    mode = os.environ.get("ARROYO_SHUFFLE_DEVICE", "auto").lower()
    if mode in ("off", "0", "false", "none"):
        return False
    if n_dests < 2 or n_dests & (n_dests - 1):
        return False
    from .mesh_window import mesh_key_shards

    if mesh_key_shards() < n_dests:
        return False
    import jax

    if not jax.config.jax_enable_x64:
        return False  # u64 key hashes would truncate inside jit
    if mode == "on":
        return True
    return jax.default_backend() != "cpu"


def keys_sharding(nk: int, *spec_axes) -> Any:
    """NamedSharding over the ``("keys",)`` mesh — the one axis every
    sharded operator kernel partitions on."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from .mesh_window import _keys_mesh

    return NamedSharding(_keys_mesh(nk), P(*spec_axes))


def partition_device(p: int) -> Optional[Any]:
    """Mesh device owning join-state partition ``p`` (round-robin over
    the active mesh), or None when the mesh is off — hot join rings then
    stay on the default device exactly as before.  Spreading rings over
    the same ``("keys",)`` mesh axis the window state shards on keeps
    q7/q8-style joins from funneling every hot partition through one
    chip."""
    from .mesh_window import mesh_key_shards

    nk = mesh_key_shards()
    if nk <= 1:
        return None
    import jax

    return jax.devices()[p % nk]


def shuffle_stats() -> Dict[str, int]:
    """Process-wide sharded-data-plane counter snapshot (bench lines and
    tests read deltas of this)."""
    return {
        "reshards": perf.counter(RESHARDS),
        "collectives": perf.counter(COLLECTIVES),
        "collective_rows": perf.counter(COLLECTIVE_ROWS),
        "host_routes": perf.counter(HOST_ROUTES),
        "ingest_transfers": perf.counter(INGEST_TRANSFERS),
    }


# ---------------------------------------------------------------------------
# resharding invariant
# ---------------------------------------------------------------------------


def ensure_sharded(arr: Any, sharding: Any, op_id: str = "__mesh__") -> Any:
    """Return ``arr`` guaranteed to satisfy ``sharding``.

    Device-resident arrays that already match pass through untouched —
    the zero-cost steady state.  A mismatch is an **implicit reshard**:
    counted, profiled (``reshard`` phase), and re-placed, so a kernel
    whose inputs arrive mis-partitioned still computes correctly while
    the regression is measured instead of silently absorbed by XLA.
    Host (numpy) inputs are ingest staging, counted separately."""
    import jax

    cur = getattr(arr, "sharding", None)
    if cur is None:
        perf.count(INGEST_TRANSFERS)
        return jax.device_put(arr, sharding)
    if cur == sharding:
        return arr
    try:
        if cur.is_equivalent_to(sharding, getattr(arr, "ndim", 1)):
            return arr
    except Exception:
        pass
    perf.count(RESHARDS)
    from ..obs.metrics import reshard_counter

    reshard_counter().inc()
    prof = profiler.active()
    frame = (prof.begin(op_id, "reshard") if prof is not None else None)
    try:
        return jax.device_put(arr, sharding)
    finally:
        if frame is not None:
            prof.end(frame)


# ---------------------------------------------------------------------------
# co-located on-device shuffle
# ---------------------------------------------------------------------------
#
# Payload model: a keyed Batch is packed into two stacked transports —
# one f64 stack (float columns; f32 round-trips losslessly through f64)
# and one i64 stack (ints, bools, and u64 bit-views including key_hash
# and the timestamp) — so the whole exchange is THREE all_to_all calls
# (f-stack, i-stack, validity) regardless of column count.  Object
# (string) columns cannot ride the device; such edges fall back to the
# host route, sticky per edge so the output sharding spec never flips
# mid-stream (the sanitizer's sharding-stability invariant).


@functools.lru_cache(maxsize=128)
def _route_step(nk: int, nf: int, ni: int, N: int):
    """shard_map exchange: each of the ``nk`` mesh slices holds N rows
    (data-parallel), buckets them by ``server_for_hash`` destination and
    exchanges buckets with ``all_to_all``.  Per-slice bucket capacity is
    N (a slice holds at most N rows total), so routing structurally
    cannot drop rows.  Returns, per shard, that shard's rows from every
    source slice in source order — globally the host path's stable
    destination order."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    from .mesh_window import _keys_mesh

    range_size = np.uint64(int(U64_MAX) // nk)

    def shard_fn(kh, fv, iv, ok):
        # per-slice views: kh u64[N] (routing only — the VALUE already
        # rides the i-stack's reserved slot 1, so exchanging it again
        # would be a third collective's worth of dead volume);
        # fv f64[nf, N]; iv i64[ni, N]; ok bool[N]
        dest = jnp.minimum((kh // range_size).astype(jnp.int32), nk - 1)
        dest = jnp.where(ok, dest, 0)
        onehot = jax.nn.one_hot(dest, nk, dtype=jnp.int32)
        pos = jnp.cumsum(onehot, axis=0) - onehot
        pos = jnp.sum(pos * onehot, axis=1)
        tgt = dest * N + pos  # pos < N structurally: slice holds N rows
        buf_ok = jnp.zeros((nk * N,), bool).at[tgt].set(ok, mode="drop")
        buf_f = jnp.zeros((nf, nk * N), jnp.float64).at[:, tgt].set(
            jnp.where(ok, fv, 0.0), mode="drop") if nf else \
            jnp.zeros((0, nk * N), jnp.float64)
        buf_i = jnp.zeros((ni, nk * N), jnp.int64).at[:, tgt].set(
            jnp.where(ok, iv, 0), mode="drop") if ni else \
            jnp.zeros((0, nk * N), jnp.int64)
        buf_ok = jax.lax.all_to_all(
            buf_ok.reshape(nk, N), "keys", 0, 0).reshape(-1)
        if nf:
            buf_f = jax.lax.all_to_all(
                buf_f.reshape(nf, nk, N), "keys", 1, 1).reshape(nf, -1)
        if ni:
            buf_i = jax.lax.all_to_all(
                buf_i.reshape(ni, nk, N), "keys", 1, 1).reshape(ni, -1)
        return buf_ok, buf_f, buf_i

    mesh = _keys_mesh(nk)
    fn = shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P("keys"), P(None, "keys"), P(None, "keys"), P("keys")),
        out_specs=(P("keys"), P(None, "keys"), P(None, "keys")),
        check_vma=False)
    shard1 = NamedSharding(mesh, P("keys"))
    stack = NamedSharding(mesh, P(None, "keys"))
    # explicit in/out shardings: inputs staged by route() already carry
    # exactly these placements, so the dispatch never implicitly
    # re-partitions (SNIPPETS [1]: matched axis resources)
    return jax.jit(fn,
                   in_shardings=(shard1, stack, stack, shard1),
                   out_shardings=(shard1, stack, stack))


# column transport kinds
_F_KINDS = "f"          # float -> f64 stack
_I_KINDS = "iub?mM"     # int/uint/bool (u64 as bit-view) -> i64 stack


def _to_i64(v: np.ndarray) -> np.ndarray:
    if v.dtype == np.uint64:
        return v.view(np.int64)  # bit-preserving
    if v.dtype == np.int64:
        return v
    return v.astype(np.int64)


def _from_i64(v: np.ndarray, dtype: np.dtype) -> np.ndarray:
    if dtype == np.uint64:
        return v.view(np.uint64)
    if dtype == np.bool_:
        return v != 0
    return v.astype(dtype)


class DeviceShuffle:
    """Route keyed batches across ``n`` co-located destinations with one
    on-device all_to_all exchange per batch.  ``route`` returns the
    per-destination sub-batches (only non-empty ones) or ``None`` when
    this edge cannot ride the device (non-numeric columns, sticky), in
    which case the caller takes the host path."""

    def __init__(self, n: int, op_id: str = ""):
        self.n = n
        self.op_id = op_id
        self._disabled = False  # sticky host fallback (sharding-stable)
        self._mesh_sh: Optional[Tuple[Any, Any]] = None

    def _shardings(self):
        if self._mesh_sh is None:
            self._mesh_sh = (keys_sharding(self.n, "keys"),
                             keys_sharding(self.n, None, "keys"))
        return self._mesh_sh

    def _plan(self, batch: Batch) -> Optional[List[Tuple[str, str, Any, int]]]:
        """(name, stack, dtype, index) per column, or None if any column
        cannot ride the device transport."""
        plan: List[Tuple[str, str, Any, int]] = []
        nf = 0
        ni = 2  # i-stack slots 0/1 reserved: timestamp, key_hash bit-view
        for name, v in batch.columns.items():
            k = v.dtype.kind
            if k in _F_KINDS:
                plan.append((name, "f", v.dtype, nf))
                nf += 1
            elif k in "iub":
                plan.append((name, "i", v.dtype, ni))
                ni += 1
            else:
                return None
        return plan

    def route(self, batch: Batch
              ) -> Optional[List[Tuple[int, Batch]]]:
        if self._disabled or batch.key_hash is None:
            return None
        plan = self._plan(batch)
        if plan is None:
            self._disabled = True  # sticky: the edge's output sharding
            # spec must not flip batch to batch
            return None
        import jax

        nk = self.n
        m = len(batch)
        N = _bucket(-(-m // nk))
        total = nk * N
        nf = sum(1 for _c, s, _d, _i in plan if s == "f")
        ni = 2 + sum(1 for _c, s, _d, _i in plan if s == "i")

        kh_p = np.zeros(total, np.uint64)
        kh_p[:m] = batch.key_hash
        ok_p = np.zeros(total, bool)
        ok_p[:m] = True
        fv = np.zeros((nf, total), np.float64)
        iv = np.zeros((ni, total), np.int64)
        iv[0, :m] = batch.timestamp
        iv[1, :m] = _to_i64(batch.key_hash)
        for name, stack, _dt, idx in plan:
            if stack == "f":
                fv[idx, :m] = batch.columns[name]
            else:
                iv[idx, :m] = _to_i64(batch.columns[name])

        shard1, stacked = self._shardings()
        prof = profiler.active()
        frame = (prof.begin(self.op_id, "shuffle_collective")
                 if prof is not None else None)
        try:
            step = _route_step(nk, nf, ni, N)
            out_ok, out_f, out_i = step(
                jax.device_put(kh_p, shard1),
                jax.device_put(fv, stacked),
                jax.device_put(iv, stacked),
                jax.device_put(ok_p, shard1))
            # one transfer per output buffer; each destination's rows are
            # the d-th block of nk*N entries
            ok_h = np.asarray(jax.device_get(out_ok))
            f_h = np.asarray(jax.device_get(out_f)) if nf else None
            i_h = np.asarray(jax.device_get(out_i))
        finally:
            if frame is not None:
                prof.end(frame)
        perf.count(COLLECTIVES)
        perf.count(COLLECTIVE_ROWS, m)
        # device-memory ledger (obs/latency.py): the staging stacks are
        # the shuffle's transient device footprint for this batch
        perf.note("shuffle_stack_bytes",
                  int(kh_p.nbytes + ok_p.nbytes + fv.nbytes + iv.nbytes))
        from ..obs.metrics import shuffle_collective_counter

        shuffle_collective_counter().inc()

        block = nk * N
        parts: List[Tuple[int, Batch]] = []
        for d in range(nk):
            sel = ok_h[d * block:(d + 1) * block]
            if not sel.any():
                continue
            lo = d * block
            idxs = np.nonzero(sel)[0] + lo
            cols: Dict[str, np.ndarray] = {}
            for name, stack, dt, idx in plan:
                if stack == "f":
                    col = f_h[idx][idxs]
                    cols[name] = (col if dt == np.float64
                                  else col.astype(dt))
                else:
                    cols[name] = _from_i64(i_h[idx][idxs], dt)
            sub = Batch(i_h[0][idxs], cols,
                        _from_i64(i_h[1][idxs], np.dtype(np.uint64)),
                        batch.key_cols, lat_stamp=batch.lat_stamp)
            parts.append((d, sub))
        return parts
