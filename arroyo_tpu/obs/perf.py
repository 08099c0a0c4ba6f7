"""Lightweight performance accounting.

**Always-cheap per-operator accumulator** — every ``timed_device`` call
made while a task has installed a :class:`KernelAccumulator` (the
TaskRunner does this) adds its dispatch wall time to that operator's
``arroyo_worker_kernel_seconds_total`` counter and, for spans above a
floor, to the flight-recorder trace ring under the kernel's own name.
Dispatch is *not* blocked on, so the cost is two ``perf_counter_ns``
reads per kernel — safe in production.  Device time is read from a
device trace (``POST /debug/profile``), where every program carries the
name :func:`kernel_name` gave it.
"""

from __future__ import annotations

import contextvars
import functools
import time
from contextvars import ContextVar
from typing import Any, Dict, Optional

from . import profiler as _profiler

_COUNTERS: Dict[str, int] = {}
_NOTES: Dict[str, Any] = {}

# spans shorter than this don't earn a trace-ring entry (the counter
# still accumulates them); keeps micro-kernels from flooding the ring
_TRACE_FLOOR_NS = 50_000


class KernelAccumulator:
    """Per-subtask kernel-time sink: a prometheus counter child plus
    identity for trace spans.  Installed by the TaskRunner for the
    duration of its coroutine (contextvars flow through awaits, so every
    kernel the operator dispatches on the event loop lands here)."""

    __slots__ = ("task_id", "operator_id", "counter")

    def __init__(self, task_info, metrics=None):
        self.task_id = task_info.task_id
        self.operator_id = task_info.operator_id
        self.counter = getattr(metrics, "kernel_time", None)

    def add(self, ns: int, kernel: str = "kernel") -> None:
        if self.counter is not None:
            self.counter.inc(ns / 1e9)
        if ns >= _TRACE_FLOOR_NS:
            from . import tracing

            end = tracing.now_us()
            tracing.record_span(kernel, "kernel", end - ns / 1e3,
                                ns / 1e3, tid=self.task_id)


_ACTIVE_TASK: ContextVar[Optional[KernelAccumulator]] = ContextVar(
    "arroyo_active_kernel_acc", default=None)


def set_active_task(acc: Optional[KernelAccumulator]):
    """Install the accumulator for the current (coroutine) context;
    returns a token for ``reset_active_task``."""
    return _ACTIVE_TASK.set(acc)


def reset_active_task(token) -> None:
    _ACTIVE_TASK.reset(token)


def active_operator_id() -> Optional[str]:
    """Operator id of the current (coroutine) context's task, or None
    off-task — lets state-layer code (join gather, ring maintenance)
    attribute profiler phases without threading ids through every
    call."""
    acc = _ACTIVE_TASK.get()
    return acc.operator_id if acc is not None else None


def active_task_id() -> str:
    """Trace track id of the current context's task ('' off-task)."""
    acc = _ACTIVE_TASK.get()
    return acc.task_id if acc is not None else ""


def begin_phase(phase: str):
    """Open profiler work phase ``phase`` for the current context's
    operator — for state-layer code that has no operator id at hand.
    Returns the token for :func:`end_phase`: ``None`` while the profiler
    is off, so a site costs one call and one test."""
    prof = _profiler.active()
    if prof is None:
        return None
    return prof, prof.begin(active_operator_id() or "kernel", phase)


def end_phase(token) -> None:
    if token is not None:
        token[0].end(token[1])


def in_phase(phase: str):
    """Decorator: the call is profiler work phase ``phase`` of the active
    operator (one test per call while the profiler is off)."""
    def wrap(fn):
        @functools.wraps(fn)
        def phased(*args, **kwargs):
            tok = begin_phase(phase)
            try:
                return fn(*args, **kwargs)
            finally:
                end_phase(tok)

        return phased

    return wrap


class Offloaded:
    """``fn(*args)`` handed to the loop's executor at once, with the
    caller's contextvars (executor threads don't inherit the caller's
    context, so kernels dispatched from an offloaded transfer would
    otherwise bypass the active task's accumulator and report zero
    kernel time exactly on the accelerator backends where offload is
    enabled), and collected later by :meth:`result` on the loop thread:
    what :func:`run_offloaded` awaits, and what a bin state's update
    leaves in flight (``BinAggOperator.process_batch``).

    Where the hop goes is counted at its collection (four clock reads,
    four counters): ``offload_us.queue``, from the submit on the loop
    thread to the executor thread's first instruction (the pool's
    pick-up, a thread's start, its first wait for the GIL);
    ``offload_us.run``, the executor's own wall around ``fn``;
    ``offload_us.resume``, from the executor's last instruction to the
    loop thread's look at the result (for an awaited hop
    ``call_soon_threadsafe``, the loop's wake-up, and every task that held
    the loop before this one got it back; for an update left in flight,
    the loop's next look at it); ``offload_hops``."""

    __slots__ = ("future", "_t_submit", "_t_start", "_t_end")

    def __init__(self, loop, fn, *args):
        ctx = contextvars.copy_context()
        self._t_start = self._t_end = 0

        def job():
            self._t_start = time.perf_counter_ns()
            try:
                return ctx.run(fn, *args)
            finally:
                self._t_end = time.perf_counter_ns()

        self._t_submit = time.perf_counter_ns()
        self.future = loop.run_in_executor(None, job)

    def done(self) -> bool:
        return self.future.done()

    def result(self):
        """What ``fn`` returned, or raise what it raised, once
        :meth:`done`; counts the hop with now as the loop's look at it."""
        self.stamp(time.perf_counter_ns())
        return self.future.result()

    def stamp(self, t_resume: int, span=None, span_args=None) -> None:
        """Count the hop's parts (:func:`run_offloaded` has ``span``)."""
        t_submit, t_start, t_end = self._t_submit, self._t_start, self._t_end
        if not t_end:  # cancelled before the executor was through
            return
        self._t_end = 0  # counted once
        count("offload_us.queue", (t_start - t_submit) // 1000)
        count("offload_us.run", (t_end - t_start) // 1000)
        count("offload_us.resume", (t_resume - t_end) // 1000)
        count("offload_hops")
        if span:
            from . import tracing

            to_us = tracing.now_us() - time.perf_counter_ns() / 1e3
            cat = span.get("cat", "offload")
            tid = span.get("tid") or active_task_id()
            for part, t0, t1 in (("queue", t_submit, t_start),
                                 ("run", t_start, t_end),
                                 ("resume", t_end, t_resume)):
                if part in span:
                    tracing.record_span(span[part], cat,
                                        to_us + t0 / 1e3,
                                        (t1 - t0) / 1e3, tid=tid,
                                        args=span_args)


async def run_offloaded(loop, fn, *args, span=None, span_args=None):
    """``fn(*args)`` on the executor, awaited (:class:`Offloaded`, whose
    four counters it bumps).  The await is an ``offload_wait`` wait child
    of the caller's profiler frame: what the executor thread does is
    accounted on its own stack, and never charged to the caller's
    ``proc``/``watermark`` as well.

    ``span`` names flight-recorder spans for the hop's parts, ``{"queue" |
    "run" | "resume": span name, "cat": category, "tid": trace track}``,
    recorded with ``span_args``; a part it leaves out, and every part of
    a hop that names none, stays off the ring."""
    prof = _profiler.active()
    frame = (prof.begin(active_operator_id() or "offload", "offload_wait",
                        wait=True) if prof is not None else None)
    job = Offloaded(loop, fn, *args)
    try:
        return await job.future
    finally:
        t_resume = time.perf_counter_ns()
        if frame is not None:
            prof.end(frame)
        job.stamp(t_resume, span, span_args)


def kernel_name(name: str):
    """Decorator under ``@jax.jit``: gives the traced function a stable
    name, so XLA's module reads ``jit_<name>`` in a device trace and
    :func:`timed_device` counts and spans the kernel under it."""
    def rename(fn):
        fn.__name__ = fn.__qualname__ = name
        return fn

    return rename


def reset() -> None:
    _COUNTERS.clear()
    _NOTES.clear()


def counter(key: str) -> int:
    """Counter read (plain counts like ``kernel_dispatches`` — the number
    of device-kernel dispatches made through :func:`timed_device`, which
    bench.py turns into dispatches-per-event — and the microsecond sums
    ``wait_us.<phase>``, ``cpu_us.<phase>`` and ``offload_us.<part>``)."""
    return _COUNTERS.get(key, 0)


def count(key: str, n: int = 1) -> None:
    """Increment a plain process-wide counter (join-state merge/spill
    accounting, bench attribution).  Cheap: one dict update, and no lock:
    a key has one writing thread (the loop thread's ``offload_us.*`` and
    ``wait_us.*``, a fire's counters on whichever thread runs it, one
    hop at a time), but for ``cpu_us.<phase>``, which the profiler bumps
    from the loop, the source and the executor thread and therefore only
    under its own lock (``Profiler.end``)."""
    _COUNTERS[key] = _COUNTERS.get(key, 0) + n


def note(key: str, value: Any) -> None:
    _NOTES[key] = value


def get_note(key: str, default: Any = None) -> Any:
    return _NOTES.get(key, default)


def timed_device(call, *args, in_total: bool = True):
    """Run a jitted kernel call, never blocking on its result: attribute
    the dispatch wall time to the active task's kernel accumulator and
    count it, in total (``kernel_dispatches``) and under the callee's name
    (``kernel_dispatches.<name>``).  With the phase profiler armed the
    span also lands in the phase table as ``dispatch`` — nested, so the
    enclosing phase stays exclusive.  ``in_total=False`` is for the calls
    that bypassed this function until the kernels were named (the evict):
    they count under their name only, so that the unnamed total, which
    the benchmark's ``kernel_dispatches_per_mev`` reads, keeps counting
    what it always counted."""
    acc = _ACTIVE_TASK.get()
    if acc is None:
        return call(*args)
    name = getattr(call, "__name__", "kernel")
    prof = _profiler.active()
    frame = (prof.begin(acc.operator_id, "dispatch")
             if prof is not None else None)
    if in_total:
        count("kernel_dispatches")
    count("kernel_dispatches." + name)
    t0 = time.perf_counter_ns()
    try:
        out = call(*args)
    finally:
        dt = time.perf_counter_ns() - t0
        if frame is not None:
            prof.end(frame)
    acc.add(dt, name)
    return out
