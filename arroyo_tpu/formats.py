"""Wire formats: bytes <-> rows <-> columnar Batch.

Analog of the reference's serde layer
(/root/reference/arroyo-worker/src/formats.rs:11-131): JSON deserialization
with confluent-schema-registry framing (5-byte header strip), unstructured
("raw json into a single `value` column") mode, raw string format, and a
``DataSerializer`` that renders batches back to bytes for sinks — including
the ``include_schema`` envelope and Debezium-style updating envelopes
(arroyo-types/src/lib.rs:315-507 retraction model).

Everything is batch-oriented: a connector hands a list of raw payloads to
``Format.deserialize`` and gets one columnar :class:`~arroyo_tpu.types.Batch`
back, ready for the jitted device operators.
"""

from __future__ import annotations

import functools
import json
import math
import os
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from .types import Batch, now_micros


def fast_decode_enabled() -> bool:
    """``ARROYO_FAST_DECODE=0`` disables every vectorized serde fast
    path — decode *and* encode — so the formats reproduce the
    row-at-a-time legacy path bit-for-bit (the full escape hatch the
    fast-vs-legacy smoke gate and parity tests pin).  Read per call so
    tests can toggle it without rebuilding format instances."""
    return os.environ.get("ARROYO_FAST_DECODE", "1") not in ("0", "off",
                                                             "false")

# Debezium operation codes -> our UpdateOp-style ops.  The reference models
# these as UpdatingData::{Append,Update,Retract} (arroyo-types/src/lib.rs:359-420).
_DEBEZIUM_OPS = {"c": "append", "r": "append", "u": "update", "d": "retract"}

# Reserved column carrying the updating-op for retraction streams; matches
# the planner's convention for UpdatingData flows.
OP_COLUMN = "__op"


def rows_to_columns(rows: Sequence[Dict[str, Any]]) -> Dict[str, np.ndarray]:
    """Pivot a list of JSON-ish dict rows into typed numpy columns.

    Columns with missing fields become float64 with NaN (all-numeric) or
    object columns keeping the Nones; fully-present columns coerce to
    bool/int64/float64 and otherwise stay ``object`` (string) columns,
    mirroring arrow's permissive JSON reader.
    """
    names: Dict[str, None] = {}
    # arroyolint: disable=row-loop -- THE pinned legacy pivot: the fast decode paths fall back to exactly this on schema drift / ARROYO_FAST_DECODE=0
    for r in rows:
        for k in r:
            names.setdefault(k)
    cols: Dict[str, np.ndarray] = {}
    for k in names:
        # arroyolint: disable=row-loop -- THE pinned legacy pivot: the fast decode paths fall back to exactly this on schema drift / ARROYO_FAST_DECODE=0
        vs = [r.get(k) for r in rows]
        # Dispatch on the *JSON* types, never by attempted coercion: a column
        # of digit strings ("01234") must stay a string column.
        present = [v for v in vs if v is not None]
        has_none = len(present) < len(vs)
        if not present:
            arr = np.array(vs, dtype=object)  # untyped: keep the Nones
        elif all(isinstance(v, bool) for v in present):
            # nullable bool stays a bool-typed (object) column so sinks
            # emit true/false consistently whether or not the batch had a
            # null; numeric consumers coerce via coerce_float
            arr = (np.array(vs, dtype=object) if has_none
                   else np.array(vs, dtype=bool))
        elif all(isinstance(v, int) and not isinstance(v, bool)
                 for v in present):
            if has_none:
                arr = np.array([np.nan if v is None else v for v in vs],
                               dtype=np.float64)
            else:
                try:
                    arr = np.array(vs, dtype=np.int64)
                except OverflowError:
                    arr = np.array(vs, dtype=object)
        elif all(isinstance(v, (int, float)) and not isinstance(v, bool)
                 for v in present):
            arr = np.array([np.nan if v is None else v for v in vs],
                           dtype=np.float64)
        else:
            arr = np.array(vs, dtype=object)
        cols[k] = arr
    return cols


def batch_from_rows(rows: Sequence[Dict[str, Any]],
                    timestamp_field: Optional[str] = None) -> Batch:
    """Build a Batch from dict rows; event time from ``timestamp_field``
    (int64 micros) or ingestion time."""
    cols = rows_to_columns(rows)
    if timestamp_field and timestamp_field in cols:
        ts = cols[timestamp_field].astype(np.int64)
    else:
        ts = np.full(len(rows), now_micros(), dtype=np.int64)
    return Batch(ts, cols)


def coerce_object_col(v: np.ndarray):
    """Lift an object-dtype nullable column into (typed values, validity).

    JSON rows with missing bools/ints produce object arrays; device code
    rejects object dtype, so Nones become the validity mask and the rest
    gets its natural dtype (None fills: False / NaN).  Columns whose
    non-null values aren't scalars (strings, lists) return unchanged with
    mask None — those stay on the host path.
    """
    # fast path: a string in front means a string column — skip the O(n)
    # scans (if a later row were numeric the column is mixed-type and the
    # host path is the correct destination anyway)
    for x in v[:64]:
        if x is not None:
            if isinstance(x, str):
                return v, None
            break
    mask = np.fromiter((x is not None for x in v), bool, len(v))
    present = [x for x in v if x is not None]
    if not present:
        return np.zeros(len(v), dtype=np.float32), mask
    # type decisions look at every value — mixed-type columns (number in
    # one row, string in another) must stay on the host path, not crash
    if all(isinstance(x, bool) for x in present):
        vals = np.fromiter((x if x is not None else False for x in v),
                           bool, len(v))
        return vals, (None if mask.all() else mask)
    if all(isinstance(x, (int, float)) and not isinstance(x, bool)
           for x in present):
        vals = np.array([np.nan if x is None else float(x) for x in v],
                        dtype=np.float64)
        return vals, (None if mask.all() else mask)
    return v, None


def nan_validity(v, m):
    """Combine an explicit validity mask with the engine's implicit NULL
    encodings: NaN rows in float columns and None rows in unmasked
    object columns.  Returns the combined mask, or None when every row
    is valid.  THE single definition — IS NULL, COUNT(col) indicators,
    UDAF null filters, and any other null-sensitive consumer must route
    through here so the modalities cannot drift."""
    import jax.numpy as jnp

    if isinstance(v, np.ndarray) and v.dtype == object:
        nn = np.array([x is not None and x == x for x in v], dtype=bool)
        return nn if m is None else (m & nn)
    if isinstance(v, np.ndarray) and v.dtype.kind == "f":
        # numpy fast path: host callers (join-key nonces, the
        # COUNT(DISTINCT) sort) must not bounce through the default
        # device — a transfer and a sync for a host-only value
        nn = ~np.isnan(v)
        return nn if m is None else (m & nn)
    if hasattr(v, "dtype") and jnp.issubdtype(v.dtype, jnp.floating):
        nn = ~jnp.isnan(v)
        return nn if m is None else (m & nn)
    return m


def coerce_float(arr: np.ndarray, dtype=np.float32) -> np.ndarray:
    """Numeric view of a column for aggregation inputs: None (in object
    columns from nullable JSON) becomes NaN instead of raising."""
    if arr.dtype == object:
        return np.array([np.nan if v is None else float(v) for v in arr],
                        dtype=dtype)
    return arr.astype(dtype)


def batch_to_rows(batch: Batch) -> List[Dict[str, Any]]:
    names = list(batch.columns)
    cols = [batch.columns[n] for n in names]
    # arroyolint: disable=row-loop -- the row-path escape: only envelope formats and inexpressible columns reach this materialization
    return [
        {n: _py(c[i]) for n, c in zip(names, cols)}
        for i in range(len(batch))
    ]


def _py(v: Any) -> Any:
    if isinstance(v, np.integer):
        return int(v)
    if isinstance(v, np.floating):
        f = float(v)
        return None if f != f else f
    if isinstance(v, np.bool_):
        return bool(v)
    if isinstance(v, np.ndarray):
        return v.tolist()
    if isinstance(v, bytes):
        return v.decode("utf-8", "replace")
    return v


# ---------------------------------------------------------------------------
# Vectorized JSON egress (the decode fast path's mirror image)
# ---------------------------------------------------------------------------


def _float_cell(v: float, nan_literal: str) -> str:
    # json.dumps renders floats with float.__repr__ and the non-finite
    # literals below; NaN is the caller's choice because the two legacy
    # encoders disagree (JsonFormat nulls it via _py, the single_file
    # sink's default hook keeps the NaN literal)
    if v != v:
        return nan_literal
    if math.isinf(v):
        return "Infinity" if v > 0 else "-Infinity"
    return repr(v)


def _json_cells(col: np.ndarray, nan_literal: str) -> Optional[List[str]]:
    """One JSON-encoded text cell per row for a whole column, dispatched
    by dtype instead of per value.  ``None`` means the column holds
    something the vectorized encoders don't express (nested lists,
    dicts, arbitrary objects) and the caller must take the legacy
    row-at-a-time path."""
    kind = col.dtype.kind
    if kind in "iu":
        return col.astype(str).tolist()
    if kind == "f":
        return [_float_cell(v, nan_literal) for v in col.tolist()]
    if kind == "b":
        return np.where(col, "true", "false").tolist()
    if col.dtype == object or kind == "U":
        out: List[str] = []
        dumps = json.dumps
        # tolist() for BOTH kinds: a 'U' column would otherwise yield
        # np.str_ cells that walk the whole isinstance chain per cell
        for v in col.tolist():
            if v is None:
                out.append("null")
            elif type(v) is str:
                out.append(dumps(v))
            elif isinstance(v, (bool, np.bool_)):
                out.append("true" if v else "false")
            elif isinstance(v, (int, np.integer)):
                out.append(str(int(v)))
            elif isinstance(v, np.floating):
                # must precede the plain-float branch: np.float64
                # SUBCLASSES float, and repr(np.float64) renders
                # 'np.float64(x)' under numpy>=2 — corrupt JSON; the
                # legacy _py path also nulls np.floating NaN, which the
                # python-float branch's 'NaN' literal would not
                out.append(_float_cell(float(v), nan_literal))
            elif isinstance(v, float):
                # a python-float NaN in an object column survives _py
                # untouched, so legacy json.dumps emits the literal
                out.append(_float_cell(v, "NaN"))
            elif isinstance(v, np.str_):
                out.append(dumps(str(v)))
            elif isinstance(v, bytes):
                out.append(dumps(v.decode("utf-8", "replace")))
            else:
                return None  # nested lists/dicts: row path handles them
        return out
    return None  # datetimes etc: no vectorized encoder


@functools.lru_cache(maxsize=256)
def _row_template(names: tuple) -> str:
    """Schema-once render template: the per-row byte layout is fixed by
    the column names, so the object framing, key quoting and the legacy
    ``json.dumps`` separators are baked in exactly once per schema."""
    # arroyolint: disable=row-loop -- iterates column NAMES once per schema (lru_cache), never per row
    return "{" + ", ".join(
        json.dumps(n).replace("%", "%%") + ": %s" for n in names) + "}"


def encode_json_lines(batch: Batch,
                      nan_literal: str = "null") -> Optional[List[str]]:
    """Render a whole Batch to JSON-object text lines with zero per-row
    Python: one encoded-cell pass per column, one template substitution
    per row.  Returns ``None`` when a column isn't expressible — the
    caller falls back to its legacy per-row ``json.dumps`` loop (whose
    output this function otherwise matches byte for byte)."""
    names = tuple(batch.columns)
    if not names:
        return ["{}"] * len(batch)
    cells: List[List[str]] = []
    for n in names:
        c = _json_cells(batch.columns[n], nan_literal)
        if c is None:
            return None
        cells.append(c)
    template = _row_template(names)
    return [template % t for t in zip(*cells)]


# ---------------------------------------------------------------------------
# Formats
# ---------------------------------------------------------------------------


class _TransientColumnarError(ValueError):
    """Columnar fast-path failure caused by one batch's DATA (not the
    stream's structure): fall back for that batch without disabling the
    fast path."""


class Format:
    """bytes[] -> rows and rows -> bytes[].  Stateless and reusable."""

    name = "abstract"

    def deserialize(self, payloads: Sequence[bytes]) -> List[Dict[str, Any]]:
        raise NotImplementedError

    def serialize(self, rows: Sequence[Dict[str, Any]]) -> List[bytes]:
        raise NotImplementedError

    # Convenience: straight to/from Batch.
    def batch(self, payloads: Sequence[bytes],
              timestamp_field: Optional[str] = None) -> Batch:
        return batch_from_rows(self.deserialize(payloads), timestamp_field)

    def serialize_batch(self, batch: Batch) -> List[bytes]:
        return self.serialize(batch_to_rows(batch))


@dataclass
class JsonFormat(Format):
    """JSON object per payload (formats.rs JsonFormat).

    - ``confluent_schema_registry``: strip the 5-byte magic+schema-id header
      the confluent serializers prepend (formats.rs:30-41).
    - ``unstructured``: don't parse fields; put the whole payload string in a
      single ``value`` column (formats.rs "raw json").
    - ``include_schema``: on serialize, wrap rows in a Kafka-Connect-style
      ``{"schema": ..., "payload": ...}`` envelope.
    - ``debezium``: payloads are Debezium envelopes; unwrap before/after into
      rows carrying an ``__op`` retraction column.
    """

    name: str = "json"
    confluent_schema_registry: bool = False
    unstructured: bool = False
    include_schema: bool = False
    debezium: bool = False

    def _strip(self, p: bytes) -> bytes:
        if self.confluent_schema_registry and len(p) >= 5 and p[0] == 0:
            return p[5:]
        return p

    def batch(self, payloads: Sequence[bytes],
              timestamp_field: Optional[str] = None) -> Batch:
        """Columnar fast path: plain JSON objects parse as one NDJSON
        block through pyarrow (~5x the per-row json.loads path — the
        kafka/json hot loop) with the stream's Arrow schema locked
        after the first batch; without pyarrow, one C-level bulk parse
        of the whole batch feeds the exact legacy pivot (~3x).
        Structural shapes the columnar reader can't express (debezium
        envelopes, unstructured, schema envelopes) and
        ``ARROYO_FAST_DECODE=0`` take the legacy row path."""
        if (self.debezium or self.unstructured or self.include_schema
                or not fast_decode_enabled()):
            return batch_from_rows(self.deserialize(payloads),
                                   timestamp_field)
        if getattr(self, "_arrow_ok", True):
            try:
                return self._batch_arrow(payloads, timestamp_field)
            except ImportError:
                # no pyarrow in this environment: never retry the import
                # on the hot path — the bulk path below takes over
                self._arrow_ok = False
            except _TransientColumnarError:
                # per-record data glitch (e.g. one payload missing the
                # timestamp field): row-path THIS batch only, keep the
                # fast path for the well-formed rest of the stream
                return batch_from_rows(self.deserialize(payloads),
                                       timestamp_field)
            except Exception:
                # payload shape the arrow reader can't express (nested
                # objects, arrays, mixed types): the bulk path pivots
                # through the legacy type rules, which express anything
                # the row path does — switch to it for this stream
                self._arrow_ok = False
        return self._batch_bulk(payloads, timestamp_field)

    def _join_payloads(self, payloads: Sequence[bytes], sep: bytes):
        """Frame a batch of payloads as ONE buffer for a single parser
        invocation — the shared framing home of the arrow and bulk fast
        paths (the two must never drift).  Hot path: a list of bytes
        with nothing to strip joins directly (a None/str mid-list
        raises TypeError there and falls to the general path).
        Returns ``(buf, count)``; ``(None, 0)`` when nothing remains."""
        if not self.confluent_schema_registry and isinstance(
                payloads, list) and payloads and \
                isinstance(payloads[0], bytes):
            try:
                return sep.join(payloads), len(payloads)
            except TypeError:
                pass  # mixed payload types: general path below
        # arroyolint: disable=row-loop -- mixed-type payload framing fallback; the bytes-only hot path is the single join above
        raw = [self._strip(p if isinstance(p, bytes) else str(p).encode())
               for p in payloads if p is not None]
        if not raw:
            return None, 0
        return sep.join(raw), len(raw)

    def _batch_bulk(self, payloads: Sequence[bytes],
                    timestamp_field: Optional[str]) -> Batch:
        """Vectorized fallback without pyarrow: ONE ``json.loads`` of
        the whole batch (payloads joined into a JSON array) replaces
        len(payloads) parser invocations; the pivot is the same
        :func:`rows_to_columns`, so null/bool/digit-string semantics
        are the legacy path's by construction.  After 3 consecutive
        failures the stream stops paying the doomed join+parse and
        stays on the row path."""
        if getattr(self, "_bulk_fails", 0) < 3:
            try:
                buf, _ = self._join_payloads(payloads, b",")
                objs = json.loads(b"[" + buf + b"]") if buf is not None \
                    else []
                self._bulk_fails = 0
                return batch_from_rows(self._normalize_objs(objs),
                                       timestamp_field)
            except Exception:
                # a payload the array join mis-frames (embedded control
                # chars, truncated docs): the row path is authoritative
                # — it surfaces the real error or succeeds
                self._bulk_fails = getattr(self, "_bulk_fails", 0) + 1
        return batch_from_rows(self.deserialize(payloads), timestamp_field)

    def _normalize_objs(self, objs: List[Any]) -> List[Dict[str, Any]]:
        """Parsed-object -> row normalization shared by the bulk fast
        path and (modulo parsing) ``deserialize``: arrays flatten to
        their dict elements, scalars wrap in a ``value`` column."""
        rows: List[Dict[str, Any]] = []
        for obj in objs:
            if isinstance(obj, dict):
                rows.append(obj)
            elif isinstance(obj, list):
                rows.extend(o for o in obj if isinstance(o, dict))
            else:
                rows.append({"value": obj})
        return rows

    def _batch_arrow(self, payloads: Sequence[bytes],
                     timestamp_field: Optional[str]) -> Batch:
        buf, n = self._join_payloads(payloads, b"\n")
        if buf is None:
            return Batch(np.zeros(0, dtype=np.int64), {})
        return self._batch_arrow_raw(buf, n, timestamp_field)

    def _batch_arrow_raw(self, buf: bytes, n_rows: int,
                         timestamp_field: Optional[str]) -> Batch:
        import io

        import pyarrow as pa
        import pyarrow.json as paj

        # schema-once: the first batch locks the stream's Arrow schema;
        # later batches parse against it explicitly (no per-batch type
        # inference, and the column set stays stable — a field absent
        # from one batch null-fills instead of vanishing, which keeps
        # the downstream coalescer/data-plane signatures from flapping).
        # Genuinely new fields still appear via unexpected-field
        # inference; a type conflict is schema drift: re-read with
        # inference and re-lock.
        locked = getattr(self, "_pa_schema", None)
        try:
            if locked is not None:
                tbl = paj.read_json(io.BytesIO(buf), parse_options=(
                    paj.ParseOptions(explicit_schema=locked)))
            else:
                tbl = paj.read_json(io.BytesIO(buf))
        except pa.ArrowInvalid:
            if locked is None:
                raise
            self._pa_schema = None
            tbl = paj.read_json(io.BytesIO(buf))
        self._pa_schema = tbl.schema
        if len(tbl) != n_rows:
            raise ValueError("row-count mismatch (multi-object payloads)")
        cols: Dict[str, np.ndarray] = {}
        for name in tbl.column_names:
            col = tbl.column(name).combine_chunks()
            t = col.type
            if pa.types.is_integer(t) and col.null_count == 0:
                cols[name] = col.to_numpy().astype(np.int64)
            elif pa.types.is_floating(t) or (
                    pa.types.is_integer(t) and col.null_count):
                cols[name] = col.to_numpy(zero_copy_only=False).astype(
                    np.float64)
            elif pa.types.is_boolean(t) and col.null_count == 0:
                cols[name] = col.to_numpy(zero_copy_only=False)
            elif (pa.types.is_string(t) or pa.types.is_large_string(t)
                  or pa.types.is_null(t) or pa.types.is_boolean(t)):
                out = np.empty(len(col), dtype=object)
                out[:] = col.to_pylist()
                cols[name] = out
            else:  # struct/list/timestamp payloads: row path handles them
                raise ValueError(f"non-scalar column {name}: {t}")
        if timestamp_field and timestamp_field in cols:
            tcol = cols[timestamp_field]
            if tcol.dtype.kind == "f" and not np.isfinite(tcol).all():
                # a payload missing the timestamp field surfaced as a
                # null -> NaN, and astype(int64) on NaN is undefined
                # behavior (platform-dependent garbage event times); the
                # row path handles missing fields explicitly.  This is a
                # per-record data glitch, not a structural payload shape
                # — it must NOT latch the fast path off for the stream.
                raise _TransientColumnarError(
                    f"null {timestamp_field!r} in columnar JSON batch")
            ts = tcol.astype(np.int64)
        else:
            ts = np.full(n_rows, now_micros(), dtype=np.int64)
        return Batch(ts, cols)

    def deserialize(self, payloads: Sequence[bytes]) -> List[Dict[str, Any]]:
        rows: List[Dict[str, Any]] = []
        for p in payloads:
            if p is None:
                continue
            raw = self._strip(p if isinstance(p, bytes) else str(p).encode())
            if self.unstructured:
                rows.append({"value": raw.decode("utf-8", "replace")})
                continue
            obj = json.loads(raw)
            if self.debezium:
                rows.extend(self._unwrap_debezium(obj))
            elif isinstance(obj, dict) and self.include_schema and \
                    "payload" in obj and "schema" in obj:
                rows.append(obj["payload"])
            elif isinstance(obj, list):
                rows.extend(o for o in obj if isinstance(o, dict))
            elif isinstance(obj, dict):
                rows.append(obj)
            else:
                rows.append({"value": obj})
        return rows

    def _unwrap_debezium(self, obj: Dict[str, Any]) -> List[Dict[str, Any]]:
        env = obj.get("payload", obj)
        op = _DEBEZIUM_OPS.get(env.get("op", "c"), "append")
        out: List[Dict[str, Any]] = []
        if op == "update":
            # update = retract(before) + append(after), the reference's
            # UpdatingData::Update {old, new} (arroyo-types/src/lib.rs:364-372)
            if env.get("before") is not None:
                out.append({**env["before"], OP_COLUMN: "retract"})
            if env.get("after") is not None:
                out.append({**env["after"], OP_COLUMN: "append"})
        elif op == "retract":
            if env.get("before") is not None:
                out.append({**env["before"], OP_COLUMN: "retract"})
        else:
            if env.get("after") is not None:
                out.append({**env["after"], OP_COLUMN: "append"})
        return out

    def serialize(self, rows: Sequence[Dict[str, Any]]) -> List[bytes]:
        out = []
        for r in rows:
            if self.debezium:
                op = r.get(OP_COLUMN, "append")
                body = {k: v for k, v in r.items() if k != OP_COLUMN}
                env = {"before": body if op == "retract" else None,
                       "after": None if op == "retract" else body,
                       "op": "d" if op == "retract" else "c"}
                out.append(json.dumps(env, default=_py).encode())
            elif self.include_schema:
                env = {"schema": json_schema_for_rows([r]), "payload": r}
                out.append(json.dumps(env, default=_py).encode())
            else:
                out.append(json.dumps(r, default=_py).encode())
        return out

    def serialize_batch(self, batch: Batch) -> List[bytes]:
        """Vectorized egress: one encoded-cell pass per column plus a
        schema-once row template replace the per-row dict build and
        ``json.dumps`` (~2x, byte-identical output).  Envelope modes
        (debezium / include_schema) and ``ARROYO_FAST_DECODE=0`` keep
        the legacy row path; so does any column the cell encoders
        can't express."""
        if (self.debezium or self.include_schema
                or not fast_decode_enabled()):
            return self.serialize(batch_to_rows(batch))
        lines = encode_json_lines(batch)
        if lines is None:
            return self.serialize(batch_to_rows(batch))
        # arroyolint: disable=row-loop -- one C-level encode per outgoing payload; the JSON render itself is vectorized (encode_json_lines)
        return [line.encode() for line in lines]


@dataclass
class RawStringFormat(Format):
    """One UTF-8 string per payload in/out of a single ``value`` column
    (formats.rs RawStringFormat)."""

    name: str = "raw_string"

    def deserialize(self, payloads: Sequence[bytes]) -> List[Dict[str, Any]]:
        return [{"value": (p if isinstance(p, str)
                           else p.decode("utf-8", "replace"))}
                for p in payloads if p is not None]

    def serialize(self, rows: Sequence[Dict[str, Any]]) -> List[bytes]:
        out = []
        for r in rows:
            v = r.get("value")
            if v is None and len(r) == 1:
                v = next(iter(r.values()))
            elif v is None:
                v = json.dumps(r, default=_py)
            out.append(str(v).encode())
        return out


def json_schema_for_rows(rows: Sequence[Dict[str, Any]]) -> Dict[str, Any]:
    """Infer a JSON-schema-shaped descriptor from sample rows — the analog of
    the reference's DataSerializer json-schema generation (formats.rs:90-131)
    and the API's schema inference (arroyo-api/src/json_schema.rs)."""
    props: Dict[str, Dict[str, Any]] = {}
    for r in rows:
        for k, v in r.items():
            t = _json_type(v)
            if k not in props:
                props[k] = {"type": t}
            elif props[k]["type"] != t and v is not None:
                props[k]["type"] = "string"  # widen on conflict
    return {"type": "object", "properties": props}


def _json_type(v: Any) -> str:
    if isinstance(v, bool):
        return "boolean"
    if isinstance(v, (int, np.integer)):
        return "integer"
    if isinstance(v, (float, np.floating)):
        return "number"
    if v is None:
        return "null"
    if isinstance(v, (list, np.ndarray)):
        return "array"
    if isinstance(v, dict):
        return "object"
    return "string"


def make_format(name: str, **opts: Any) -> Format:
    """Format factory keyed by the connector config's ``format`` field."""
    if name in ("json", "debezium_json"):
        return JsonFormat(debezium=(name == "debezium_json"), **opts)
    if name in ("raw", "raw_string"):
        return RawStringFormat()
    if name == "avro":
        return AvroFormat(**opts)
    raise ValueError(f"unknown format: {name!r}")


def columns_from_json_schema(schema: Dict[str, Any]) -> List[Dict[str, str]]:
    """JSON schema -> column list (the API's test_schema path,
    arroyo-api/src/json_schema.rs: schemas must flatten to typed
    columns).  Raises on non-object roots and unsupported types."""
    t0 = schema.get("type")
    if isinstance(t0, list):  # nullable object root/nested
        t0 = next((x for x in t0 if x != "null"), None)
    if t0 != "object":
        raise ValueError("schema root must be an object")
    kind_of = {"integer": "bigint", "number": "double", "string": "text",
               "boolean": "boolean"}
    cols = []
    for name, spec in (schema.get("properties") or {}).items():
        t = spec.get("type")
        if isinstance(t, list):  # nullable union like ["integer", "null"]
            t = next((x for x in t if x != "null"), None)
        if t == "object":
            for sub in columns_from_json_schema(spec):
                cols.append({"name": f"{name}.{sub['name']}",
                             "type": sub["type"]})
            continue
        if t not in kind_of:
            raise ValueError(f"unsupported type {t!r} for field {name!r}")
        fmt = spec.get("format", "")
        cols.append({"name": name,
                     "type": "timestamp" if "date-time" in fmt
                     else kind_of[t]})
    if not cols:
        raise ValueError("schema has no supported properties")
    return cols


# ---------------------------------------------------------------------------
# Avro (binary encoding, pure python)
# ---------------------------------------------------------------------------


def _zigzag_encode(n: int) -> bytes:
    """Avro long: zigzag + varint."""
    z = (n << 1) ^ (n >> 63)
    out = bytearray()
    while True:
        b = z & 0x7F
        z >>= 7
        if z:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def _zigzag_decode(buf: bytes, pos: int) -> Tuple[int, int]:
    shift = 0
    acc = 0
    while True:
        b = buf[pos]
        pos += 1
        acc |= (b & 0x7F) << shift
        if not (b & 0x80):
            break
        shift += 7
    return (acc >> 1) ^ -(acc & 1), pos


def avro_schema_for_rows(rows: Sequence[Dict[str, Any]],
                         name: str = "Record") -> Dict[str, Any]:
    """Infer an Avro record schema from sample rows (nullable unions for
    every field, mirroring json_schema_for_rows)."""
    fields: Dict[str, str] = {}
    for r in rows:
        for k, v in r.items():
            if isinstance(v, bool):
                t = "boolean"
            elif isinstance(v, (int, np.integer)):
                t = "long"
            elif isinstance(v, (float, np.floating)):
                t = "double"
            elif v is None:
                continue
            else:
                t = "string"
            prev = fields.get(k)
            fields[k] = t if prev in (None, t) else "string"
    return {"type": "record", "name": name,
            "fields": [{"name": k, "type": ["null", t]}
                       for k, t in fields.items()]}


class AvroFormat(Format):
    """Avro binary serde against a record schema.

    The reference leaves Avro as a TODO (formats.rs:11-131 handles json/raw
    only); this implements the single-record binary encoding with optional
    Confluent wire framing (magic 0 + 4-byte schema id), the layout Kafka
    schema-registry producers emit.  Schemas: every field is a nullable
    union ``["null", T]`` with T in {boolean, long, double, string, bytes}.
    """

    def __init__(self, schema: Optional[Dict[str, Any]] = None,
                 confluent_schema_registry: bool = False,
                 schema_id: int = 0,
                 schema_registry_url: Optional[str] = None,
                 subject: Optional[str] = None, **_ignored):
        if isinstance(schema, str):
            schema = json.loads(schema)
        self.schema = schema
        self.confluent = confluent_schema_registry or bool(
            schema_registry_url)
        self.schema_id = schema_id
        self.registry_url = schema_registry_url
        self.subject = subject
        # schema-json -> registered id (inferred schemas can change
        # batch to batch, so memoize per schema, not per instance)
        self._registered: Dict[str, int] = {}
        self._fts_by_id: Dict[int, List[Tuple[str, str]]] = {}

    def _registry(self):
        from .connectors.schema_registry import registry_client

        return registry_client(self.registry_url)

    SUPPORTED = {"boolean", "int", "long", "float", "double", "string",
                 "bytes"}

    def _field_types(self, schema=None) -> List[Tuple[str, str]]:
        schema = schema or self.schema
        if schema is None:
            raise ValueError("avro format needs a schema")
        out = []
        for f in schema["fields"]:
            t = f["type"]
            # the wire layout implemented here is exactly ["null", T]
            # unions (null = branch 0); anything else would be silently
            # mis-framed, so reject it loudly
            if not (isinstance(t, list) and len(t) == 2 and t[0] == "null"):
                raise ValueError(
                    f"avro field {f['name']!r}: only [\"null\", T] unions "
                    f"are supported (got {t!r})")
            t = t[1]
            if isinstance(t, dict):
                # logical types annotate an underlying type whose WIRE
                # encoding is authoritative (uuid -> string, decimal ->
                # bytes, timestamp-micros -> long)
                t = t.get("type", "string")
            if t not in self.SUPPORTED:
                raise ValueError(
                    f"avro field {f['name']!r}: unsupported type {t!r}")
            out.append((f["name"], t))
        return out

    # -- encode -------------------------------------------------------

    def _encode_value(self, t: str, v: Any) -> bytes:
        import struct

        if t == "boolean":
            return b"\x01" if v else b"\x00"
        if t in ("long", "int"):
            return _zigzag_encode(int(v))
        if t == "double":
            return struct.pack("<d", float(v))
        if t == "float":
            return struct.pack("<f", float(v))
        if t == "bytes":
            raw = bytes(v)
            return _zigzag_encode(len(raw)) + raw
        # string (default)
        raw = str(v).encode()
        return _zigzag_encode(len(raw)) + raw

    def serialize(self, rows: Sequence[Dict[str, Any]]) -> List[bytes]:
        # no configured schema: infer per call (Format contract says
        # stateless; a job needing a stable cross-batch schema must
        # configure one)
        schema = self.schema or avro_schema_for_rows(rows)
        fts = self._field_types(schema)
        out = []
        sid = self.schema_id
        if self.registry_url:
            # register (memoized per schema text — the inferred schema
            # can change batch to batch); the returned global id rides
            # the confluent wire header so any registry-aware consumer
            # can resolve the writer schema
            text = json.dumps(schema, sort_keys=True)
            if text not in self._registered:
                self._registered[text] = self._registry().register(
                    self.subject or f"{schema.get('name', 'record')}-value",
                    schema)
            sid = self._registered[text]
        header = (b"\x00" + sid.to_bytes(4, "big")
                  if self.confluent else b"")
        for r in rows:
            buf = bytearray(header)
            for name, t in fts:
                v = r.get(name)
                if v is None:
                    buf += _zigzag_encode(0)  # union branch 0 = null
                else:
                    buf += _zigzag_encode(1)  # union branch 1 = T
                    buf += self._encode_value(t, v)
            out.append(bytes(buf))
        return out

    # -- decode -------------------------------------------------------

    def _decode_value(self, t: str, buf: bytes, pos: int) -> Tuple[Any, int]:
        import struct

        if t == "boolean":
            return buf[pos] != 0, pos + 1
        if t in ("long", "int"):
            return _zigzag_decode(buf, pos)
        if t == "double":
            return struct.unpack_from("<d", buf, pos)[0], pos + 8
        if t == "float":
            return struct.unpack_from("<f", buf, pos)[0], pos + 4
        n, pos = _zigzag_decode(buf, pos)
        raw = buf[pos:pos + n]
        return (raw if t == "bytes" else raw.decode()), pos + n

    def deserialize(self, payloads: Sequence[bytes]) -> List[Dict[str, Any]]:
        own_fts = self._field_types() if self.schema is not None else None
        rows = []
        for p in payloads:
            # confluent framing guard (mirrors JsonFormat): only strip the
            # 5-byte header when it is actually present
            pos = 5 if (self.confluent and len(p) >= 5 and p[0] == 0) else 0
            if pos and self.registry_url:
                # resolve the WRITER schema from the header id — payloads
                # may be written under a different (evolved) schema than
                # the table DDL declares.  Per-payload, memoized by id, so
                # a framed payload's schema never leaks onto an unframed
                # neighbor in the same batch
                sid = int.from_bytes(p[1:5], "big")
                fts = self._fts_by_id.get(sid)
                if fts is None:
                    fts = self._field_types(self._registry().get_schema(sid))
                    self._fts_by_id[sid] = fts
            else:
                fts = own_fts
            if fts is None:
                raise ValueError(
                    "avro format needs a schema (or a schema_registry_url "
                    "with confluent framing)")
            row: Dict[str, Any] = {}
            for name, t in fts:
                branch, pos = _zigzag_decode(p, pos)
                if branch == 0:
                    row[name] = None
                else:
                    row[name], pos = self._decode_value(t, p, pos)
            rows.append(row)
        return rows
