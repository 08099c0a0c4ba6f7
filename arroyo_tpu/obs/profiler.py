"""Phase-attributed host/device profiler: account for every microsecond
of the hot path.

ROADMAP item 5 ("kill the host path") needs the host time *decomposed*
before anyone can kill it: the bench's old ``host_time_share`` was a
residual (1 - device/wall) with zero attribution.  This module measures
every batch's journey as named phases at the runtime's existing choke
points:

==================  =========================================================
phase               measured where
==================  =========================================================
``source_decode``   connector decode / generation (nexmark generator on its
                    executor thread, kafka format decode, single_file JSON
                    parse, impulse batch assembly)
``proc``            operator ``process_batch`` host compute, EXCLUSIVE of
                    the nested phases below (per chain member for fused
                    operators)
``dir_insert``      key directory lookup/insert of the bin state
                    (``KeyedBinState._lookup_or_insert``: the native
                    ``insert`` plus ``_append_new_keys``)
``preagg``          the bin state's host half of an update: bin admission,
                    the per-(slot, bin) reduce, the cross-batch merge of a
                    coalesced flush, the pad and pack of the kernel inputs
``h2d``             ``jnp.asarray`` of a bin-state kernel's host inputs
                    (update, fire, evict)
``dispatch``        host-side kernel dispatch wall time (``perf.timed_device``,
                    never blocking — the Python/jax envelope around XLA)
``d2h_wait``        every blocking device->host readback of a pane fire
                    (the thread sits in it until the device has finished)
``fire_flatten``    fired cells -> flat key/pane/value columns on the host
``emit``            ``BinAggOperator._emit`` up to ``ctx.collect``: key
                    columns gathered, the output batch built, top-n and
                    projection applied
``shuffle_prep``    Collector partition/route/select CPU before fan-out
``coalesce_merge``  input-side batch concat in the coalescer
``watermark``       timer fires + ``handle_watermark`` (window fires live
                    here)
``checkpoint``      state snapshot sync phase at a barrier
``emit_encode``     sink-side encode (single_file JSON lines, ...)
``frame_encode``    data-plane Arrow IPC encode per frame
``frame_decode``    data-plane decode on the receiving worker
``reshard``         device arrays re-placed because their resident
                    sharding mismatched a kernel's explicit in_sharding
                    (parallel/shuffle.ensure_sharded — steady state
                    should show NO such phase at all)
``shuffle_collective``  on-device ``all_to_all`` exchange carrying a
                    co-located SHUFFLE edge (parallel/shuffle.py route
                    dispatch + per-shard readback)
``gather``          join payload materialization per emitted match set
                    (state/join_state.py — device-plane gather dispatch
                    or host fancy-index, whichever path ran; the
                    device/host row split rides the
                    ``join_*_gather_rows`` counters)
``join_append``     ``PartitionedJoinBuffer.append``: routing a keyed batch
                    to its partitions, the sort of each delta and the
                    positional merge into the host sorted run (exclusive
                    of ``join_merge``)
``join_merge``      the device half of an append: packing a delta and the
                    ``join_merge32`` scatter-merge dispatch of one hot
                    partition's ring (staging a new ring included)
``join_probe``      a window join's fire up to the matched positions:
                    ``range_join``'s mask-compress and merge-probe of
                    every partition's two sorted runs
==================  =========================================================

plus overlapping **wait** phases (reported separately, never summed into
the work table): ``queue_wait``, ``coalesce_wait``, ``send_wait``
(backpressure enqueue), ``net_flush`` (socket drain), ``offload_wait``
(the loop-side await of ``perf.run_offloaded`` while an executor thread
runs the bin state's fire, and of ``BinAggOperator._await_update`` for
an update still in flight when the serial path needs it).  ``send_wait`` and ``offload_wait``
frames also add their microseconds to the ``perf`` counters
``wait_us.send_wait`` / ``wait_us.offload_wait``, summed over operators
(:data:`COUNTED_WAITS`).

Every frame also reads the **CPU seconds of its thread**
(``time.thread_time_ns``) where it reads the wall clock, and a work
frame's CPU is exclusive exactly as its wall is (a wait child takes with
it the thread CPU that passed while it was open: on the loop thread that
is other tasks' work).  Wall less CPU is what the thread spent off the
CPU inside the phase: blocked on the device (``d2h_wait``), waiting for
the GIL behind another thread, or without a core.  The CPU microseconds
of every work phase also land on the ``perf`` counter ``cpu_us.<phase>``,
summed over operators, and the loop thread's whole CPU, frames or not, on
``cpu_us.thread.loop`` (the watchdog's ticker adds its own thread's).

Accounting model
----------------

Each asyncio task (and each executor thread) owns its own frame stack
(contextvar-held, thread-id-guarded), so ``begin``/``end`` pairs nest
LIFO within one task.  A frame's recorded time is **exclusive**: child
frames — including *wait* frames that span awaits — subtract their full
inclusive span from the parent.  Work phases are only opened around
synchronous blocks (their only interior awaits are wrapped as wait
children — the executor hop of ``perf.run_offloaded`` as ``offload_wait``),
so no other task's or thread's work can ever be charged to them: summed
work phases per thread can never exceed that thread's busy wall time.
Executor-side work (source prefetch, offloaded update and fire) overlaps
the event loop by design, so a job's work phases summed over ALL threads
may exceed wall time — the bench reports the raw ratio and flags the
overlap.

While armed, every work frame is mirrored as a
``jax.profiler.TraceAnnotation(phase, op=op_id)`` on the thread that runs
it, so a device trace taken meanwhile (``POST /debug/profile``, the
benchmark's ``--trace 1``) shows what each host thread was doing on the
trace's own clock, beside ``XLA Ops``.  Wait frames are not mirrored, and
the open work annotations of a task are closed for as long as one of its
wait frames lasts: an annotation covers only time the thread really spent
in that phase.

Off-path discipline (same as arroyosan): every instrumentation site
holds a local that is ``None`` unless the profiler was armed
(``ARROYO_PROFILE=1`` at engine build, or an explicit :func:`arm`), so
the disabled path is a single ``is not None`` test.

The event-loop **stall watchdog** pairs an on-loop ticker task with a
sampler thread: the ticker heartbeats a timestamp every few ms; when
the thread sees the heartbeat stall past the threshold it captures the
loop thread's live stack (``sys._current_frames()``) — naming the
blocking call *while it blocks*, the runtime cross-check of the
arroyolint ``async-blocking`` static pass.
"""

from __future__ import annotations

import os
import sys
import threading
import time
import traceback
import weakref
from collections import deque
from contextlib import contextmanager
from contextvars import ContextVar
from typing import Any, Dict, Iterator, List, Optional, Tuple

__all__ = [
    "Profiler",
    "LoopWatchdog",
    "profile_enabled",
    "active",
    "arm",
    "disarm",
    "ensure_armed",
    "WORK_PHASES",
    "WAIT_PHASES",
]

WORK_PHASES = ("source_decode", "proc", "dir_insert", "preagg", "h2d",
               "dispatch", "d2h_wait", "fire_flatten", "emit",
               "shuffle_prep", "coalesce_merge", "watermark", "checkpoint",
               "emit_encode", "frame_encode", "frame_decode", "reshard",
               "shuffle_collective", "gather", "session_merge",
               "join_append", "join_merge", "join_probe")
WAIT_PHASES = ("queue_wait", "coalesce_wait", "send_wait", "net_flush",
               "offload_wait")


def profile_enabled() -> bool:
    """``ARROYO_PROFILE=1`` arms the profiler at engine build (read per
    build, not at import, so tests and bench can toggle per run)."""
    return os.environ.get("ARROYO_PROFILE", "0") not in ("0", "off",
                                                         "false", "")


_ACTIVE: Optional["Profiler"] = None


def active() -> Optional["Profiler"]:
    """The armed profiler, or ``None`` — the instrumentation sites'
    single cheap test."""
    return _ACTIVE


def arm(job_id: str = "") -> "Profiler":
    """Arm the process-wide profiler (idempotent: an already-armed
    profiler is returned unchanged, keeping its buckets)."""
    global _ACTIVE
    if _ACTIVE is None:
        _ACTIVE = Profiler(job_id)
    return _ACTIVE


def disarm() -> None:
    global _ACTIVE
    prof = _ACTIVE
    _ACTIVE = None
    if prof is not None:
        prof.watchdog.stop()


def ensure_armed(job_id: str = "") -> Optional["Profiler"]:
    """Engine-build hook: arm iff ``ARROYO_PROFILE`` asks for it (or an
    explicit :func:`arm` already did); returns the active profiler or
    ``None``."""
    if _ACTIVE is not None:
        return _ACTIVE
    if profile_enabled():
        return arm(job_id)
    return None


# the wait frames whose microseconds also land on a ``perf`` counter
# ``wait_us.<phase>`` (summed over operators), where a counter reader can
# take them between two ticks: the benchmark's ``source_blocked_s_per_mev``
# and ``offload_wait_s_per_mev``.  The waits per operator are in
# :meth:`Profiler.wait_snapshot`.
COUNTED_WAITS = frozenset(("send_wait", "offload_wait"))


# -- frame stacks ------------------------------------------------------------

# Per-task stacks: a contextvar gives every asyncio task its own box (so
# begin/end pairs nest LIFO even when awaits interleave tasks); the tid
# guard gives executor threads a fresh box when a copied context (e.g.
# perf.run_offloaded) would otherwise share the loop task's live list
# across threads.
class _StackBox:
    __slots__ = ("tid", "frames")

    def __init__(self, tid: int):
        self.tid = tid
        self.frames: List[list] = []


_STACK: ContextVar[Optional[_StackBox]] = ContextVar(
    "arroyo_profiler_stack", default=None)


def detach_stack() -> None:
    """Give the current asyncio task a frame stack of its own.  A task
    started from inside another task (a window fire's tail) copies a
    context whose box is its parent's live one, same thread and all."""
    _STACK.set(None)


# frame layout: [op_id, phase, is_wait, t0, child_inclusive_secs,
#                open TraceAnnotation or None, thread CPU ns at begin,
#                child_inclusive_cpu_ns]
_OP, _PHASE, _WAIT, _T0, _CHILD, _ANN, _C0, _CCHILD = range(8)


class Profiler:
    """Process-wide phase accounting (one job per worker process; the
    embedded multi-job scheduler shares one profiler, documented)."""

    def __init__(self, job_id: str = ""):
        self.job_id = job_id
        self._lock = threading.Lock()
        self._work: Dict[Tuple[str, str], float] = {}
        self._waits: Dict[Tuple[str, str], float] = {}
        self._counts: Dict[Tuple[str, str], int] = {}
        # exclusive work seconds by the thread that ran the frame: the
        # check of "per thread, work never exceeds the thread's wall"
        self._thread_work: Dict[str, float] = {}
        # the CPU twins of ``_work`` and ``_thread_work``: exclusive
        # seconds of ``time.thread_time_ns`` (frames only)
        self._cpu: Dict[Tuple[str, str], float] = {}
        self._thread_cpu: Dict[str, float] = {}
        self._t0 = time.perf_counter()
        self.watchdog = LoopWatchdog(job_id=job_id)
        from jax.profiler import TraceAnnotation

        from . import perf  # perf imports this module

        self._annotation = TraceAnnotation
        self._count = perf.count

    # -- hot-path API ------------------------------------------------------

    def _frames(self) -> List[list]:
        box = _STACK.get()
        tid = threading.get_ident()
        if box is None or box.tid != tid:
            box = _StackBox(tid)
            _STACK.set(box)
        return box.frames

    def begin(self, op_id: str, phase: str, wait: bool = False) -> list:
        """Open a phase frame; returns the token for :meth:`end`.  Work
        frames must not span an await except through nested wait
        children (the site discipline the accounting model rests on).
        A frame ends on the thread that began it: its stack is that
        thread's (``_frames``), and its CPU is the difference of two
        readings of one thread's clock."""
        frames = self._frames()
        ann = None
        if wait:
            # the thread leaves the enclosing work phases for the await
            for g in frames:
                if g[_ANN] is not None:
                    g[_ANN].__exit__(None, None, None)
                    g[_ANN] = None
        else:
            ann = self._annotation(phase, op=op_id)
            ann.__enter__()
        f = [op_id, phase, wait, time.perf_counter(), 0.0, ann,
             time.thread_time_ns(), 0]
        frames.append(f)
        return f

    def end(self, f: list) -> None:
        cpu = time.thread_time_ns() - f[_C0]  # read inside the wall span
        now = time.perf_counter()
        if f[_ANN] is not None:
            f[_ANN].__exit__(None, None, None)
            f[_ANN] = None
        frames = self._frames()
        if frames and frames[-1] is f:
            frames.pop()
        else:
            # defensive: a corrupted interleaving (shouldn't happen with
            # per-task stacks) degrades to attribution blur, never an
            # exception or unbounded stack growth
            try:
                frames.remove(f)
            except ValueError:
                pass
        dt = now - f[_T0]
        excl = dt - f[_CHILD]
        if excl < 0.0:
            excl = 0.0
        if frames:
            frames[-1][_CHILD] += dt
            # a wait child's share is what the thread burnt for others
            frames[-1][_CCHILD] += cpu
        if f[_WAIT]:
            if f[_PHASE] in COUNTED_WAITS:
                self._count("wait_us." + f[_PHASE], int(dt * 1e6))
            if not any(g[_WAIT] for g in frames):
                # back from the await: the work phases go on
                for g in frames:
                    g[_ANN] = self._annotation(g[_PHASE], op=g[_OP])
                    g[_ANN].__enter__()
        key = (f[_OP], f[_PHASE])
        with self._lock:
            d = self._waits if f[_WAIT] else self._work
            d[key] = d.get(key, 0.0) + excl
            self._counts[key] = self._counts.get(key, 0) + 1
            if not f[_WAIT]:
                name = threading.current_thread().name
                self._thread_work[name] = self._thread_work.get(
                    name, 0.0) + excl
                excl_cpu = max(cpu - f[_CCHILD], 0)
                secs = excl_cpu / 1e9
                self._cpu[key] = self._cpu.get(key, 0.0) + secs
                self._thread_cpu[name] = self._thread_cpu.get(
                    name, 0.0) + secs
                # three threads write these keys: only under this lock
                self._count("cpu_us." + f[_PHASE], excl_cpu // 1000)

    def add(self, op_id: str, phase: str, secs: float,
            wait: bool = False, count: int = 1) -> None:
        """Direct accounting for sites that measure their own span and
        cannot nest (the task loop's input waits); knows no thread, so
        records no CPU."""
        key = (op_id, phase)
        with self._lock:
            d = self._waits if wait else self._work
            d[key] = d.get(key, 0.0) + secs
            self._counts[key] = self._counts.get(key, 0) + count

    @contextmanager
    def phase(self, op_id: str, phase: str,
              wait: bool = False) -> Iterator[None]:
        """Context-manager convenience for non-hot paths."""
        f = self.begin(op_id, phase, wait)
        try:
            yield
        finally:
            self.end(f)

    # -- reads -------------------------------------------------------------

    def reset(self) -> None:
        with self._lock:
            self._work.clear()
            self._waits.clear()
            self._counts.clear()
            self._thread_work.clear()
            self._cpu.clear()
            self._thread_cpu.clear()
            self._t0 = time.perf_counter()
        self.watchdog.reset()

    def work_snapshot(self) -> Dict[Tuple[str, str], float]:
        with self._lock:
            return dict(self._work)

    def wait_snapshot(self) -> Dict[Tuple[str, str], float]:
        with self._lock:
            return dict(self._waits)

    def snapshot(self) -> Dict[str, Any]:
        """Full structured snapshot: per-operator work/wait phase maps,
        job-level phase totals (wall, CPU, and wall less CPU), exclusive
        work seconds by the thread that ran the frames (``threads``,
        ``threads_cpu``; frames only: :meth:`add` knows no thread), wall
        since arm/reset, watchdog stats."""
        with self._lock:
            work, waits = dict(self._work), dict(self._waits)
            counts = dict(self._counts)
            threads = dict(self._thread_work)
            cpu, threads_cpu = dict(self._cpu), dict(self._thread_cpu)
            wall = time.perf_counter() - self._t0
        ops: Dict[str, Dict[str, Any]] = {}
        phases: Dict[str, float] = {}
        wait_totals: Dict[str, float] = {}
        for (op, ph), secs in work.items():
            ops.setdefault(op, {"phases": {}, "waits": {}})[
                "phases"][ph] = round(secs, 6)
            phases[ph] = phases.get(ph, 0.0) + secs
        for (op, ph), secs in waits.items():
            ops.setdefault(op, {"phases": {}, "waits": {}})[
                "waits"][ph] = round(secs, 6)
            wait_totals[ph] = wait_totals.get(ph, 0.0) + secs
        cpu_phases: Dict[str, float] = {}
        for (_op, ph), secs in cpu.items():
            cpu_phases[ph] = cpu_phases.get(ph, 0.0) + secs
        attributed = sum(phases.values())
        return {
            "job_id": self.job_id,
            "wall_secs": round(wall, 6),
            "phases": {k: round(v, 6) for k, v in sorted(phases.items())},
            "cpu_phases": {k: round(v, 6) for k, v in sorted(
                cpu_phases.items())},
            "off_cpu_phases": {k: round(max(v - cpu_phases.get(k, 0.0), 0.0),
                                        6) for k, v in sorted(phases.items())},
            "waits": {k: round(v, 6) for k, v in sorted(
                wait_totals.items())},
            "attributed_secs": round(attributed, 6),
            "attributed_share": round(attributed / wall, 4) if wall > 0
            else 0.0,
            "unattributed_share": round(
                max(1.0 - attributed / wall, 0.0), 4) if wall > 0 else 0.0,
            "operators": {op: v for op, v in sorted(ops.items())},
            "threads": {t: round(v, 6) for t, v in sorted(threads.items())},
            "threads_cpu": {t: round(v, 6) for t, v in sorted(
                threads_cpu.items())},
            "counts": {f"{op}/{ph}": n for (op, ph), n in sorted(
                counts.items())},
            "watchdog": self.watchdog.stats(),
        }

    def collapsed_stacks(self) -> str:
        """pprof/flamegraph folded-stack text: one ``job;operator;phase
        <microseconds>`` line per bucket (waits carry a ``(wait)``
        leaf so they are visually separable from summed work)."""
        job = self.job_id or "job"
        lines: List[str] = []
        with self._lock:
            work, waits = dict(self._work), dict(self._waits)
        for (op, ph), secs in sorted(work.items()):
            lines.append(f"{job};{op};{ph} {int(secs * 1e6)}")
        for (op, ph), secs in sorted(waits.items()):
            lines.append(f"{job};{op};{ph} (wait) {int(secs * 1e6)}")
        return "\n".join(lines) + ("\n" if lines else "")


# -- event-loop stall watchdog -----------------------------------------------


class LoopWatchdog:
    """Scheduling-lag sampler + blocking-call catcher.

    The on-loop ticker (:meth:`run`) sleeps ``interval`` and records how
    late the loop woke it — the scheduling lag every other coroutine on
    that loop also experiences — and adds the CPU its thread burnt since
    the tick before to the ``perf`` counter ``cpu_us.thread.loop``: the
    loop thread on the CPU, inside a profiler frame or not (asyncio's own
    turns, a caller polling on the same loop); the rest of the wall it was
    parked in ``select`` or waiting for the GIL.  A daemon sampler thread watches the
    ticker's heartbeat; when it stalls past ``stall_threshold`` the
    thread snapshots the loop thread's current Python stack, so the
    blocking call is named **while it is still blocking** (the runtime
    cross-check of arroyolint's ``async-blocking`` pass).  One stall
    episode records once, however long it lasts.
    """

    def __init__(self, interval: float = 0.02,
                 stall_threshold: Optional[float] = None,
                 job_id: str = ""):
        self.interval = interval
        self.stall_threshold = stall_threshold if stall_threshold is not None \
            else float(os.environ.get("ARROYO_PROFILE_STALL_MS", "250")) / 1e3
        self.job_id = job_id
        self.lags: deque = deque(maxlen=1024)  # recent lag samples (secs)
        self.stalls: deque = deque(maxlen=64)  # {t, lag, stack}
        self.stall_count = 0
        self._last_tick = time.perf_counter()
        self._loop_tid: Optional[int] = None
        self._stop = threading.Event()
        self._sampler_started = False
        self._tickers: "weakref.WeakKeyDictionary" = \
            weakref.WeakKeyDictionary()  # loop -> ticker task
        self._lock = threading.Lock()

    # -- lifecycle ---------------------------------------------------------

    def ensure_ticker(self) -> None:
        """Idempotently start the ticker task on the running loop (and
        the sampler thread on first use).  Called from Engine.start when
        the profiler is armed; the task dies with its loop."""
        import asyncio

        loop = asyncio.get_running_loop()
        t = self._tickers.get(loop)
        if t is not None and not t.done():
            return
        self._tickers[loop] = asyncio.ensure_future(self.run())

    async def run(self) -> None:
        self._loop_tid = threading.get_ident()
        self._last_tick = time.perf_counter()
        if not self._sampler_started:
            self._sampler_started = True
            self._stop.clear()
            threading.Thread(target=self._sample, name="arroyo-loop-watchdog",
                             daemon=True).start()
        import asyncio

        from . import perf
        from .metrics import event_loop_lag_gauge, event_loop_stalls_counter

        gauge_p50 = event_loop_lag_gauge(self.job_id, "p50")
        gauge_p99 = event_loop_lag_gauge(self.job_id, "p99")
        stalls_c = event_loop_stalls_counter(self.job_id)
        reported_stalls = 0
        last_gauge = 0.0
        cpu0 = time.thread_time_ns()
        try:
            while True:
                t0 = time.perf_counter()
                await asyncio.sleep(self.interval)
                now = time.perf_counter()
                self._last_tick = now
                cpu = time.thread_time_ns()
                perf.count("cpu_us.thread.loop", (cpu - cpu0) // 1000)
                cpu0 = cpu
                self.lags.append(max(now - t0 - self.interval, 0.0))
                if now - last_gauge >= 1.0:
                    last_gauge = now
                    p50, p99 = self._percentiles()
                    gauge_p50.set(p50)
                    gauge_p99.set(p99)
                    if self.stall_count > reported_stalls:
                        stalls_c.inc(self.stall_count - reported_stalls)
                        reported_stalls = self.stall_count
        finally:
            # the loop is going away: freeze the heartbeat far in the
            # future so the sampler never mistakes shutdown for a stall
            self._last_tick = float("inf")

    def stop(self) -> None:
        self._stop.set()
        self._sampler_started = False

    # -- sampling ----------------------------------------------------------

    def _percentiles(self) -> Tuple[float, float]:
        lags = sorted(self.lags)
        if not lags:
            return 0.0, 0.0
        return (lags[len(lags) // 2],
                lags[min(int(len(lags) * 0.99), len(lags) - 1)])

    def _sample(self) -> None:
        poll = max(self.interval / 2, 0.005)
        while not self._stop.is_set():
            time.sleep(poll)
            last = self._last_tick
            if last == float("inf"):
                continue
            lag = time.perf_counter() - last
            if lag < self.stall_threshold or self._loop_tid is None:
                continue
            frame = sys._current_frames().get(self._loop_tid)
            stack = ("".join(traceback.format_stack(frame, limit=12))
                     if frame is not None else "<no frame>")
            with self._lock:
                self.stall_count += 1
                self.stalls.append({
                    "t": round(time.time(), 3),
                    "lag_secs": round(lag, 4),
                    "stack": stack,
                })
            # one record per stall episode: wait for the loop to tick
            # again before re-arming (bounded so a dead loop can't wedge
            # the sampler thread forever)
            deadline = time.perf_counter() + 60.0
            while (self._last_tick <= last
                   and time.perf_counter() < deadline
                   and not self._stop.is_set()):
                time.sleep(poll)

    # -- reads -------------------------------------------------------------

    def reset(self) -> None:
        with self._lock:
            self.lags.clear()
            self.stalls.clear()
            self.stall_count = 0

    def stats(self) -> Dict[str, Any]:
        p50, p99 = self._percentiles()
        with self._lock:
            stalls = list(self.stalls)
            count = self.stall_count
        return {
            "lag_p50_secs": round(p50, 6),
            "lag_p99_secs": round(p99, 6),
            "stalls": count,
            "stall_threshold_secs": self.stall_threshold,
            "recent_stalls": [
                {"t": s["t"], "lag_secs": s["lag_secs"],
                 # last frames name the blocking call; full stack stays
                 # in-process (admin /profile/phases?fmt=json serves it)
                 "stack_tail": s["stack"].strip().splitlines()[-4:]}
                for s in stalls[-8:]],
        }
