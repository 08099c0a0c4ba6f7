"""ctypes bindings for the C++ host runtime library (native/src/host_ops.cpp).

Loads ``libarroyo_host-<source hash>.so`` next to this file, building it
from source on first use when a toolchain is available.  The hash in the
file name covers ``native/src/host_ops.cpp`` and ``native/Makefile``, so
a binary built from other sources (an older checkout, an edited tree) is
never loaded: it simply is not the file this checkout looks for.  Every
binding has a numpy fallback with identical semantics; ``HAVE_NATIVE``
reports which path is active and ``ARROYO_NATIVE=0`` forces the fallback.
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import logging
import os
import subprocess
from typing import Optional, Tuple

import numpy as np

logger = logging.getLogger(__name__)

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC_DIR = os.path.join(_DIR, "..", "..", "native")
_SOURCES = ("src/host_ops.cpp", "Makefile")

_lib: Optional[ctypes.CDLL] = None


def source_hash(src_dir: str = _SRC_DIR) -> Optional[str]:
    """Digest of the files the library is built from; None when the
    sources are not there (an installed package without ``native/``)."""
    h = hashlib.sha256()
    try:
        for rel in _SOURCES:
            with open(os.path.join(src_dir, rel), "rb") as f:
                h.update(rel.encode() + b"\0" + f.read() + b"\0")
    except OSError:
        return None
    return h.hexdigest()[:12]


def library_path(src_dir: str = _SRC_DIR, out_dir: str = _DIR
                 ) -> Optional[str]:
    """The one binary this checkout will load — named after its sources."""
    digest = source_hash(src_dir)
    if digest is None:
        return None
    return os.path.join(out_dir, f"libarroyo_host-{digest}.so")


def _build(so: str) -> bool:
    """Build the library, safe against concurrent workers: an exclusive
    lockfile serializes builds, and make writes the final .so via the
    compiler in one pass so a loader never sees a half-written file that
    a racing builder produced under the lock."""
    import fcntl

    try:
        with open(os.path.join(_DIR, "build.lock"), "w") as lock:
            fcntl.flock(lock, fcntl.LOCK_EX)
            if os.path.exists(so):  # another process won the race
                return True
            tmp = so + f".tmp{os.getpid()}"
            subprocess.run(
                ["make", "-C", _SRC_DIR, f"OUT={tmp}"], check=True,
                capture_output=True, timeout=120)
            os.replace(tmp, so)  # atomic publish
            # binaries of other source revisions are dead weight
            for old in glob.glob(os.path.join(_DIR, "libarroyo_host*.so")):
                if old != so:
                    os.unlink(old)
            return True
    except (subprocess.SubprocessError, OSError) as e:
        detail = getattr(e, "stderr", b"") or b""
        logger.warning("native build failed, using numpy fallbacks: %s %s",
                       e, detail.decode(errors="replace")[-500:])
        return False


def _load() -> Optional[ctypes.CDLL]:
    if os.environ.get("ARROYO_NATIVE", "1") in ("0", "false", "no"):
        return None
    so = library_path()
    if so is None or (not os.path.exists(so) and not _build(so)):
        return None
    try:
        lib = ctypes.CDLL(so)
    except OSError as e:  # foreign-arch or truncated binary
        logger.warning("native lib unusable, numpy fallbacks: %s", e)
        return None

    u64p = np.ctypeslib.ndpointer(np.uint64, flags="C_CONTIGUOUS")
    i64p = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
    i32p = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")
    u8p = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")

    lib.arroyo_hash_u64.argtypes = [u64p, u64p, ctypes.c_int64]
    lib.arroyo_hash_combine.argtypes = [u64p, u64p, ctypes.c_int64]
    lib.arroyo_partition_route.argtypes = [
        u64p, ctypes.c_int64, ctypes.c_int32, i32p, i64p, i64p]
    lib.arroyo_assign_bins.argtypes = [
        i64p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_int64, i32p, u8p,
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64)]
    lib.arroyo_assign_bins.restype = ctypes.c_int64
    f64p = np.ctypeslib.ndpointer(np.float64, flags="C_CONTIGUOUS")
    lib.arroyo_dir_new.argtypes = [ctypes.c_int64]
    lib.arroyo_dir_new.restype = ctypes.c_void_p
    lib.arroyo_dir_free.argtypes = [ctypes.c_void_p]
    lib.arroyo_dir_load.argtypes = [ctypes.c_void_p, u64p, i64p,
                                    ctypes.c_int64]
    lib.arroyo_dir_insert.argtypes = [ctypes.c_void_p, u64p, ctypes.c_int64,
                                      ctypes.c_int64, i64p, u64p]
    lib.arroyo_dir_insert.restype = ctypes.c_int64
    lib.arroyo_dir_lookup.argtypes = [ctypes.c_void_p, u64p, ctypes.c_int64,
                                      i64p]
    lib.arroyo_agg_cells.argtypes = [
        i64p, i32p, ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
        f64p, u8p, ctypes.c_int32, i64p, i32p, f64p, f64p]
    lib.arroyo_agg_cells.restype = ctypes.c_int64
    return lib


_lib = _load()
HAVE_NATIVE = _lib is not None


def hash_u64(x: np.ndarray) -> np.ndarray:
    """splitmix64 finalizer; bit-identical to types.hash_u64."""
    xs = np.ascontiguousarray(x, dtype=np.uint64)
    if _lib is None:
        from ..types import _py_hash_u64

        return _py_hash_u64(xs)
    out = np.empty_like(xs)
    _lib.arroyo_hash_u64(xs, out, len(xs))
    return out


def hash_combine(acc: np.ndarray, h: np.ndarray) -> np.ndarray:
    """acc = splitmix64(acc * 31 + h), elementwise; mutates a copy."""
    a = np.ascontiguousarray(acc, dtype=np.uint64).copy()
    hs = np.ascontiguousarray(h, dtype=np.uint64)
    if _lib is None:
        from ..types import _py_hash_u64

        with np.errstate(over="ignore"):
            return _py_hash_u64(a * np.uint64(31) + hs)
    _lib.arroyo_hash_combine(a, hs, len(a))
    return a


def partition_route(key_hash: np.ndarray, n_parts: int
                    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(dest[n] i32, order[n] i64 stable by dest, bounds[n_parts+1] i64).

    ``order[bounds[p]:bounds[p+1]]`` are the row indices destined for
    shard ``p`` — one O(n) pass in native code vs argsort in numpy.
    """
    kh = np.ascontiguousarray(key_hash, dtype=np.uint64)
    n = len(kh)
    if _lib is None:
        from ..types import server_for_hash_array

        dest = server_for_hash_array(kh, n_parts).astype(np.int32)
        order = np.argsort(dest, kind="stable").astype(np.int64)
        bounds = np.searchsorted(
            dest[order], np.arange(n_parts + 1)).astype(np.int64)
        return dest, order, bounds
    dest = np.empty(n, dtype=np.int32)
    order = np.empty(n, dtype=np.int64)
    bounds = np.empty(n_parts + 1, dtype=np.int64)
    _lib.arroyo_partition_route(kh, n, n_parts, dest, order, bounds)
    return dest, order, bounds


def assign_bins(ts: np.ndarray, slide: int, ring: int,
                threshold: Optional[int]
                ) -> Tuple[np.ndarray, np.ndarray, int, Optional[int],
                           Optional[int]]:
    """Window-bin assignment + liveness: (bins i32, live bool, n_live,
    abs_min, abs_max) where abs_* cover live rows only."""
    t = np.ascontiguousarray(ts, dtype=np.int64)
    n = len(t)
    thr = -(2**63) if threshold is None else int(threshold)
    if _lib is None:
        abs_bins = t // slide
        live = abs_bins >= thr
        bins = (abs_bins % ring).astype(np.int32)
        n_live = int(live.sum())
        if n_live:
            lo = int(abs_bins[live].min())
            hi = int(abs_bins[live].max())
        else:
            lo = hi = None
        return bins, live, n_live, lo, hi
    bins = np.empty(n, dtype=np.int32)
    live = np.empty(n, dtype=np.uint8)
    lo = ctypes.c_int64()
    hi = ctypes.c_int64()
    n_live = _lib.arroyo_assign_bins(t, n, slide, ring, thr, bins, live,
                                     ctypes.byref(lo), ctypes.byref(hi))
    if n_live == 0:
        return bins, live.astype(bool), 0, None, None
    return bins, live.astype(bool), int(n_live), lo.value, hi.value

class NativeDir:
    """Persistent open-addressing key directory (key hash -> slot) backed
    by the C++ table; ``None``-like when the native lib is unavailable —
    callers must check :data:`HAVE_NATIVE` or use ``NativeDir.create()``."""

    __slots__ = ("_h",)

    @classmethod
    def create(cls, cap_hint: int = 1024) -> Optional["NativeDir"]:
        return cls(cap_hint) if _lib is not None else None

    def __init__(self, cap_hint: int = 1024):
        self._h = _lib.arroyo_dir_new(int(cap_hint))

    def __del__(self):
        if _lib is not None and getattr(self, "_h", None):
            _lib.arroyo_dir_free(self._h)
            self._h = None

    def load(self, keys: np.ndarray, slots: np.ndarray) -> None:
        """Bulk-load explicit (key, slot) pairs (checkpoint restore)."""
        k = np.ascontiguousarray(keys, dtype=np.uint64)
        s = np.ascontiguousarray(slots, dtype=np.int64)
        _lib.arroyo_dir_load(self._h, k, s, len(k))

    def insert(self, kh: np.ndarray, next_slot: int
               ) -> Tuple[np.ndarray, np.ndarray]:
        """Lookup-or-insert: returns (slots[n], new_keys) where unknown
        keys got sequential slots from ``next_slot`` in first-seen order."""
        k = np.ascontiguousarray(kh, dtype=np.uint64)
        n = len(k)
        slots = np.empty(n, dtype=np.int64)
        new_keys = np.empty(n, dtype=np.uint64)
        n_new = _lib.arroyo_dir_insert(self._h, k, n, int(next_slot),
                                       slots, new_keys)
        return slots, new_keys[:n_new]

    def lookup(self, kh: np.ndarray) -> np.ndarray:
        """Slots for known keys, -1 for unknown."""
        k = np.ascontiguousarray(kh, dtype=np.uint64)
        out = np.empty(len(k), dtype=np.int64)
        _lib.arroyo_dir_lookup(self._h, k, len(k), out)
        return out


def agg_cells(slots: np.ndarray, bins: np.ndarray,
              live: Optional[np.ndarray], ring: int,
              vals: np.ndarray, ch_kinds: Tuple[str, ...]
              ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """(slot, bin)-cell pre-aggregation in one native hash pass: returns
    (cell_slots, cell_bins, cell_rowcounts f64, cell_vals [n_ch, n_cells])
    — the lexsort+reduceat ``preaggregate`` path's fast twin.  ``live``
    filters rows; returns cells in first-appearance order.  Accumulation
    is f64 (exact int sums to 2^53 — the numeric-fidelity policy)."""
    assert _lib is not None
    s = np.ascontiguousarray(slots, dtype=np.int64)
    b = np.ascontiguousarray(bins, dtype=np.int32)
    n = len(s)
    v = np.ascontiguousarray(vals, dtype=np.float64)
    kinds = np.array([1 if k == "min" else 2 if k == "max" else 0
                      for k in ch_kinds], dtype=np.uint8)
    n_ch = len(ch_kinds)
    out_slot = np.empty(n, dtype=np.int64)
    out_bin = np.empty(n, dtype=np.int32)
    out_cnt = np.empty(n, dtype=np.float64)
    out_vals = np.empty((n_ch, n), dtype=np.float64)
    lv = (None if live is None
          else np.ascontiguousarray(live, dtype=np.uint8))
    lp = lv.ctypes.data_as(ctypes.c_void_p) if lv is not None else None
    m = _lib.arroyo_agg_cells(s, b, lp, n, int(ring), v, kinds, n_ch,
                              out_slot, out_bin, out_cnt, out_vals)
    return out_slot[:m], out_bin[:m], out_cnt[:m], out_vals[:, :m]
