"""Columnar expression execution on device.

Replaces the reference's SQL-expression-to-Rust-source pipeline
(arroyo-sql/src/expressions.rs -> ExpressionOperator bodies,
arroyo-datastream/src/lib.rs:1430-1505): expressions here are jnp-traceable
functions over a dict of columns, jit-compiled once per (schema, size-bucket).

XLA constraints shape the design:
* batches vary in length -> pad rows up to power-of-two buckets so each
  expression compiles O(log max_batch) times, not per batch;
* string/object columns can't live on device -> they bypass the jitted fn and
  are re-attached (or pre-hashed) on the host;
* predicates return a device bool mask; selection happens host-side where the
  batch lives.
"""

from __future__ import annotations

import functools
from contextlib import nullcontext
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import weakref

from ..obs.perf import kernel_name
from ..types import Batch

_MIN_BUCKET = 256


def bucket_size(n: int) -> int:
    b = _MIN_BUCKET
    while b < n:
        b <<= 1
    return b


def _is_device_dtype(dt: np.dtype) -> bool:
    return dt != np.dtype(object) and (
        np.issubdtype(dt, np.number) or np.issubdtype(dt, np.bool_))


def _expr_device():
    """Placement for jitted expressions: ``ARROYO_EXPR_DEVICE=cpu`` pins
    elementwise expression kernels to the host CPU backend while keyed
    window state stays on the accelerator.  Elementwise projections are
    HBM-bandwidth-bound, not MXU work, and their batches are
    host-resident on both sides — shipping every batch to the chip and
    back for a map/filter can cost more than the compute saves."""
    import os

    if os.environ.get("ARROYO_EXPR_DEVICE", "").lower() == "cpu":
        try:
            return jax.devices("cpu")[0]
        except RuntimeError:
            return None
    return None


def _host_eval_device():
    """CPU device for eager host-side expression evaluation (the chain
    ingest spine); None when the CPU platform is unavailable — callers
    must then keep the jitted path."""
    try:
        return jax.devices("cpu")[0]
    except RuntimeError:
        return None


def _looks_stringy(v: np.ndarray) -> bool:
    """First non-None value (of a prefix) is a str: the column would stay
    on the host path rather than coerce to a device dtype."""
    for x in v[:64]:
        if x is not None:
            return isinstance(x, str)
    return False


class CompiledExpr:
    """A ColumnExpr jitted over padded numeric columns.

    ``fn(cols)`` may return a dict of columns (record exprs) or a single
    array (predicates).  ``__timestamp`` is always available as a column.
    ``valid`` (bool[n]) marks real rows in the padded batch; expressions never
    see it but predicate results are AND-ed with it.
    """

    # jitted-executable cache shared process-wide, keyed by the underlying
    # expression fn (weakly — closures die with their program) and the
    # batch schema: rebuilding the physical graph from the same logical
    # program (engine restarts, bench warm runs) reuses compiled kernels
    _JIT_CACHE = weakref.WeakKeyDictionary()

    def __init__(self, name: str, fn: Callable[[Dict[str, Any]], Any]):
        self.name = name
        self.fn = fn
        # columns the fn actually reads (attached by the SQL planner from
        # the compile-time AST; None = unknown, coerce everything)
        self.used_cols = getattr(fn, "used_cols", None)
        try:
            self._jitted = CompiledExpr._JIT_CACHE.setdefault(fn, {})
        except TypeError:  # non-weakref-able callable: private cache
            self._jitted = {}

    def _get_jitted(self, schema_key: Tuple) -> Callable:
        f = self._jitted.get(schema_key)
        if f is None:
            fn = self.fn

            @jax.jit
            @kernel_name("expr_compiled")
            def run(num_cols: Dict[str, jnp.ndarray]):
                return fn(dict(num_cols))

            dev = _expr_device()
            if dev is not None:
                jitted = run

                def run_on(num_cols, _j=jitted, _d=dev):
                    return _j({k: jax.device_put(v, _d)
                               for k, v in num_cols.items()})

                f = run_on
            else:
                f = run
            self._jitted[schema_key] = f
        return f

    def _split_cols(self, batch: Batch
                    ) -> Tuple[Dict[str, np.ndarray], Dict[str, np.ndarray]]:
        """(numeric env, host passthrough cols) for this expression over
        one batch — the single definition of which columns enter the fn
        and which bypass it, shared by the jitted path and the host
        (ingest-spine) path so the two produce identical layouts."""
        num_cols: Dict[str, np.ndarray] = {"__timestamp": batch.timestamp}
        host_cols: Dict[str, np.ndarray] = {}
        used = self.used_cols
        for k, v in batch.columns.items():
            if used is not None and k not in used:
                # untouched by the expression: skip coercion/padding.
                # STRING-like object columns stay visible for host
                # passthrough (where they land today); nullable-numeric
                # object columns would have been coerced-then-dropped by
                # the projection, so drop them here too.
                if v.dtype == object and _looks_stringy(v):
                    host_cols[k] = v
                continue
            if v.dtype == object:
                # nullable scalar columns (bool/int with Nones) become a
                # typed column + __mask_ validity so they can enter jit
                from ..formats import coerce_object_col

                vals, mask = coerce_object_col(v)
                if vals.dtype != object:
                    num_cols[k] = vals
                    if mask is not None:
                        num_cols["__mask_" + k] = mask
                    continue
                host_cols[k] = v
            elif _is_device_dtype(v.dtype):
                num_cols[k] = v
            else:
                host_cols[k] = v
        return num_cols, host_cols

    def eval_host(self, batch: Batch) -> Any:
        """Evaluate the expression eagerly on the HOST — no padding, no
        jit, no accelerator dispatch.  The fn's jnp ops run op-by-op
        pinned to the CPU backend, so on an accelerator box the batch
        never crosses the transfer boundary.  Used by the chain ingest
        spine (engine/chained.py), where the batch is host-resident on
        both sides of the expression and a per-batch kernel dispatch is
        pure envelope.  Returns the same ``(out, n, host_cols)``
        contract as ``__call__``."""
        n = len(batch)
        num_cols, host_cols = self._split_cols(batch)
        dev = _host_eval_device()
        ctx = jax.default_device(dev) if dev is not None else nullcontext()
        with ctx:
            out = self.fn(dict(num_cols))
        return out, n, host_cols

    def __call__(self, batch: Batch) -> Any:
        n = len(batch)
        padded = bucket_size(n)
        num_cols, host_cols = self._split_cols(batch)

        padded_cols = {
            k: np.concatenate([v, np.zeros(padded - n, dtype=v.dtype)])
            if padded > n else v
            for k, v in num_cols.items()
        }
        schema_key = tuple(sorted((k, str(v.dtype), padded)
                                  for k, v in padded_cols.items())
                           ) + (_expr_device() is not None,)
        from ..obs.perf import timed_device

        out = timed_device(self._get_jitted(schema_key), padded_cols)
        return out, n, host_cols


def eval_record_expr(expr: CompiledExpr, batch: Batch,
                     host: bool = False) -> Batch:
    """Record expression: fn(cols) -> dict of output columns.
    ``host=True`` evaluates eagerly on the CPU backend (ingest spine) —
    identical output layout, no padding/jit/dispatch."""
    out, n, host_cols = expr.eval_host(batch) if host else expr(batch)
    assert isinstance(out, dict), f"record expr {expr.name} must return a dict"
    cols: Dict[str, np.ndarray] = {}
    ts = batch.timestamp
    for k, v in out.items():
        if k == "__timestamp":
            ts = np.asarray(v)[:n]  # arroyolint: disable=host-sync -- record-expr output must materialize as host numpy batch columns
            continue
        arr = np.asarray(v)  # arroyolint: disable=host-sync -- record-expr output must materialize as host numpy batch columns
        cols[k] = arr[:n] if arr.ndim >= 1 and arr.shape[0] >= n else arr
    # host (string) columns referenced in output pass through by name
    for k, v in host_cols.items():
        if k not in cols:
            cols[k] = v
    return Batch(ts, cols, batch.key_hash, batch.key_cols,
                 lat_stamp=batch.lat_stamp)


def eval_predicate(expr: CompiledExpr, batch: Batch,
                   host: bool = False) -> np.ndarray:
    out, n, _ = expr.eval_host(batch) if host else expr(batch)
    mask = np.asarray(out)  # arroyolint: disable=host-sync -- predicate mask materializes on host where batch.select runs
    assert mask.dtype == np.bool_ or np.issubdtype(mask.dtype, np.bool_), (
        f"predicate {expr.name} must return bool")
    if mask.ndim == 0:
        # constant predicate (e.g. a now()-only comparison): broadcast
        # to the batch — Batch.select(scalar_bool) would otherwise
        # numpy-index every column into a dimension-lifted (1, n) shape
        # that crashes the next operator's padding.  (Mirrored in
        # planner._host_filter for the host path — the two sites cannot
        # share code because this one receives post-trace output while
        # that one runs eagerly inside the UDF.)
        return np.full(len(batch), bool(mask))
    return mask[:n]


def eval_host_expr(fn: Callable[[Dict[str, np.ndarray]], Any], batch: Batch
                   ) -> Batch:
    """Host-side (non-jitted) record expression over raw numpy columns —
    the UDF escape hatch (the reference runs UDFs in wasmtime,
    operators/mod.rs:347-494; ours run as plain Python over the batch).

    When expressions are pinned to host (``ARROYO_EXPR_DEVICE=cpu``),
    any jnp call the function makes internally must ALSO stay off the
    accelerator: an uncommitted jnp op lands on the default backend, and
    converting its result back is a device->host sync per column."""
    dev = _expr_device()
    ctx = jax.default_device(dev) if dev is not None else nullcontext()
    with ctx:
        cols = {"__timestamp": batch.timestamp, **batch.columns}
        out = fn(cols)
        assert isinstance(out, dict)
        ts = np.asarray(out.pop("__timestamp", batch.timestamp))  # arroyolint: disable=host-sync -- host UDF path: outputs are host numpy by contract
        return Batch(ts, {k: np.asarray(v) for k, v in out.items()},  # arroyolint: disable=host-sync -- host UDF path: outputs are host numpy by contract
                     batch.key_hash, batch.key_cols)
