"""Vectorized session-interval union: ONE merge dispatch per batch.

The legacy :class:`~arroyo_tpu.engine.operators_window.SessionWindowOperator`
gap-merged per-key Python lists — a ``sessions.sort()`` and a linear
scan per key per batch (windows.rs:232-302 semantics) that made config5
the slowest headline workload.  This module computes the SAME union for
ALL keys at once over ``(key_hash, start, end)`` interval rows sorted by
``(key, start)``:

1. a **segmented running max of ends** (Hillis-Steele log-doubling with
   a same-key guard — int64-exact; the classic per-group offset trick
   would overflow int64 with micros timestamps),
2. a *new-session* flag wherever an interval's start exceeds the running
   end of every prior interval of its key (touching intervals merge,
   matching the reference's ``s <= merged[-1][1]``),
3. per-session merged bounds by ``reduceat`` over the flag boundaries.

The max-size clamp is NOT vectorized: a merged span exceeding
``MAX_SESSION_SIZE_MICROS`` is exactly the condition under which the
legacy path would have clamped (the unclamped union span bounds every
intermediate span from above, and equals the legacy span when no clamp
fires), so flagged keys are returned for the caller to re-run through
the authoritative per-key path — bit-for-bit parity by construction.

The same scan compiles as a jitted kernel (``ARROYO_SESSION_DEVICE``)
so accelerator backends keep the merge on device; numpy is the default
on CPU where the dispatch envelope would dominate.
"""

from __future__ import annotations

import functools
import os
from typing import Tuple

import numpy as np

from ..obs.perf import kernel_name
from .expr import bucket_size

_I64_MIN = np.iinfo(np.int64).min
_I64_MAX = np.iinfo(np.int64).max


def session_device_enabled() -> bool:
    """Should the union scan run as a jitted device kernel?  ``auto``
    keeps it on host for the CPU backend (the scan is memory-bound and
    the dispatch envelope dominates at session-state sizes) and on
    device for accelerators."""
    mode = os.environ.get("ARROYO_SESSION_DEVICE", "auto").lower()
    if mode in ("off", "0", "false", "no"):
        return False
    if mode in ("on", "1", "true", "force"):
        return True
    import jax

    return jax.default_backend() != "cpu"


def _segmented_running_max(en: np.ndarray, newkey: np.ndarray) -> np.ndarray:
    """Inclusive per-key prefix max of ``en`` (keys contiguous, flagged
    by ``newkey``).  Log-doubling: O(n log n) pure vector ops, exact in
    int64."""
    run = en.copy()
    gid = np.cumsum(newkey)
    n = len(run)
    d = 1
    while d < n:
        same = gid[d:] == gid[:-d]
        np.copyto(run[d:], np.maximum(run[d:], run[:-d]), where=same)
        d <<= 1
    return run


@functools.lru_cache(maxsize=64)
def _union_kernel(npad: int):
    """Jitted union scan: (kh, st, en, valid) -> (new_flags, run_en).
    Padded rows carry valid=False and become singleton trash sessions;
    the host compresses them away.  int64 arithmetic relies on the
    package-wide x64 enable (arroyo_tpu/__init__.py)."""
    import jax
    import jax.numpy as jnp

    steps = max(npad - 1, 1).bit_length()

    @jax.jit
    @kernel_name("session_union")
    def run(kh: "jnp.ndarray", st: "jnp.ndarray", en: "jnp.ndarray",
            valid: "jnp.ndarray"):
        newkey = jnp.ones(npad, dtype=bool)
        if npad > 1:
            newkey = newkey.at[1:].set((kh[1:] != kh[:-1])
                                       | ~valid[1:] | ~valid[:-1])
        gid = jnp.cumsum(newkey.astype(jnp.int64))
        run_en = en
        for i in range(steps):
            d = 1 << i
            same = gid[d:] == gid[:-d]
            run_en = run_en.at[d:].set(
                jnp.where(same, jnp.maximum(run_en[d:], run_en[:-d]),
                          run_en[d:]))
        new = newkey
        if npad > 1:
            new = new.at[1:].set(newkey[1:] | (st[1:] > run_en[:-1]))
        return new, run_en

    return run


def union_sorted_intervals(
    kh: np.ndarray, st: np.ndarray, en: np.ndarray,
    device: bool = False,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Union interval rows sorted by ``(key, start)`` into disjoint
    sessions per key (touching intervals merge).

    Returns ``(m_kh, m_st, m_en, sid, sess_first)``: merged session
    keys/bounds (still sorted by ``(key, start)``), the per-input-row
    merged-session ordinal ``sid`` (for folding per-row metadata into
    its session), and the first input row of each session."""
    n = len(kh)
    if n == 0:
        z64 = np.zeros(0, dtype=np.int64)
        return (np.zeros(0, dtype=np.uint64), z64.copy(), z64.copy(),
                z64.copy(), z64.copy())
    if device and n > 1:
        import jax.numpy as jnp

        from ..obs.perf import timed_device

        npad = bucket_size(n)
        khp = np.zeros(npad, dtype=np.uint64)
        stp = np.full(npad, _I64_MAX, dtype=np.int64)
        enp = np.full(npad, _I64_MIN, dtype=np.int64)
        vp = np.zeros(npad, dtype=bool)
        khp[:n], stp[:n], enp[:n], vp[:n] = kh, st, en, True
        new_d, _run = timed_device(_union_kernel(npad), jnp.asarray(khp),
                                   jnp.asarray(stp), jnp.asarray(enp),
                                   jnp.asarray(vp))
        new = np.asarray(new_d)[:n]  # arroyolint: disable=host-sync -- merged-session boundaries must materialize on host to splice the session run (pane-emission-class readback)
    else:
        newkey = np.empty(n, dtype=bool)
        newkey[0] = True
        newkey[1:] = kh[1:] != kh[:-1]
        run_en = _segmented_running_max(en, newkey)
        new = newkey
        new[1:] |= st[1:] > run_en[:-1]
    sess_first = np.nonzero(new)[0]
    sid = np.cumsum(new) - 1
    m_kh = kh[sess_first]
    m_st = st[sess_first]  # sorted by start: first interval owns the min
    m_en = np.maximum.reduceat(en, sess_first)
    return m_kh, m_st, m_en, sid.astype(np.int64), sess_first
