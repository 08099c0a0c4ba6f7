"""One run of one cell: the job goes through the entry a user's
``python -m arroyo_tpu run`` takes (``plan_sql`` -> ``Engine.for_local`` ->
``start`` / ``join``, all that ``LocalRunner.run_async`` wraps) into a
``memory`` sink, and the harness only watches the sink's arrival log.
Set-up ends at the origin tick; the measured window closes at the first tick
at or after ``seconds`` later, and the job is then stopped gracefully."""

import asyncio
import os
import shutil
import sys
import threading
import time

import numpy as np

from . import readers, ticks
from .trace_reduce import WINDOW_START, WINDOW_STOP

POLL_S = 0.002
SAMPLE_S = 0.005
SETUP_LIMIT_S = 1100  # a cold first run compiles; the driver allows 1200
DRAIN_LIMIT_S = 120


class HostSampler(threading.Thread):
    """What the event-loop thread is running, every few milliseconds: the
    innermost ``arroyo_tpu`` frame, as ``file.py:function``."""

    def __init__(self, thread_id):
        super().__init__(daemon=True)
        self.thread_id = thread_id
        self.samples = []  # (monotonic ns, label)
        self._halt = threading.Event()

    def run(self):
        while not self._halt.wait(SAMPLE_S):
            f = sys._current_frames().get(self.thread_id)
            while f is not None and "/arroyo_tpu/" not in f.f_code.co_filename:
                f = f.f_back
            label = ("outside_arroyo_tpu" if f is None else
                     f"{os.path.basename(f.f_code.co_filename)}:"
                     f"{f.f_code.co_name}")
            self.samples.append((time.monotonic_ns(), label))

    def stop(self):
        self._halt.set()
        if self.is_alive():
            self.join()


class Observer:
    """Reads the program's counters, phases and state bytes at a tick."""

    def __init__(self, cell):
        self.names = sorted({n for _, spec in cell.per_layer
                             for n in readers.counter_names(spec)})

    def counters(self):
        from arroyo_tpu.obs import perf
        from arroyo_tpu.parallel import shuffle

        stats = shuffle.shuffle_stats()
        return {n: (stats[n.split(".", 1)[1]]
                    if n.startswith("shuffle_stats.") else perf.counter(n))
                for n in self.names}

    def phases(self):
        from arroyo_tpu.obs import profiler

        prof = profiler.active()
        return None if prof is None else prof.work_snapshot()

    @staticmethod
    def state_bytes():
        from arroyo_tpu.obs.latency import device_state_tables

        return device_state_tables()

    @staticmethod
    def spans():
        """{span name: [(end, monotonic seconds; duration, seconds)]}."""
        from arroyo_tpu.obs import tracing

        to_monotonic = time.monotonic() - tracing.now_us() / 1e6
        out = {}
        for name, _cat, start_us, dur_us, *_ in tracing.spans():
            out.setdefault(name, []).append(
                ((start_us + dur_us) / 1e6 + to_monotonic, dur_us / 1e6))
        return out


def start_trace(trace_dir):
    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation(WINDOW_START,
                                      t_ns=time.monotonic_ns()):
        pass


def mark_trace_stop():
    """The end of the traced stretch; the profiler itself is stopped only
    after the close tick, because writing the trace stalls the job."""
    import jax

    with jax.profiler.TraceAnnotation(WINDOW_STOP, t_ns=time.monotonic_ns()):
        pass


def stop_trace():
    import jax

    jax.profiler.stop_trace()


async def drive(cell, seed, seconds, trace_dir=None):
    """Returns a dict: ``batches`` (the sink's), ``tick_map``, ``window``
    (ticks.Window), what the Observer read at the origin and close ticks and
    once the stop has drained the queues,
    ``state_bytes`` per tick, and in a traced run ``host_samples`` and the
    counters at the two marks of the traced stretch."""
    from arroyo_tpu.connectors.memory import (clear_sink, sink_arrivals,
                                              sink_output)
    from arroyo_tpu.engine.engine import Engine
    from arroyo_tpu.sql import plan_sql
    from arroyo_tpu.types import StopMode

    cfg = cell.config
    stream, win = cfg["stream"], cfg["window"]
    period = int(win["period_s"] * 1_000_000)
    origin_end = stream["base_time_micros"] + int(win["origin_s"] * 1_000_000)
    trace_stop_end = origin_end + win["trace_periods"] * period
    watch = Observer(cell)
    sink = cfg["sink"]
    clear_sink(sink)
    outs, arrivals = sink_output(sink), sink_arrivals(sink)
    running = Engine.for_local(plan_sql(cell.sql(seed))).start()
    joined = asyncio.ensure_future(running.join())
    started = time.monotonic()

    log = {"state_bytes": {}, "host_samples": [], "phases_at": {}}
    ends, ats = [], []
    seen, phase, tracing_on, marked, sampler = 0, "setup", False, False, None
    stop_sent_at = None

    def sweep():
        """Log the sink batches that arrived since the last sweep; returns
        the newest window end among them, or None."""
        nonlocal seen
        n = len(arrivals)
        for i in range(seen, n):
            for end in np.unique(outs[i].timestamp).tolist():
                ends.append(end + 1)  # a fired row carries window_end - 1
                ats.append(arrivals[i])
        fresh, seen = ends[-1] if n > seen else None, n
        return fresh

    def mark_stop():
        nonlocal marked
        mark_trace_stop()
        log["counters_trace_stop"] = watch.counters()
        marked = True

    try:
        while not joined.done():
            fresh = sweep()
            if fresh is not None:
                if fresh not in log["state_bytes"]:
                    log["state_bytes"][fresh] = watch.state_bytes()
                    log["phases_at"][fresh] = watch.phases()
                if phase == "setup" and fresh >= origin_end:
                    phase = "window"
                    log["counters_origin"] = watch.counters()
                    log["phases_origin"] = watch.phases()
                    if trace_dir is not None:
                        sampler = HostSampler(threading.get_ident())
                        sampler.start()
                        start_trace(trace_dir)
                        log["counters_trace_start"] = watch.counters()
                        tracing_on = True
                elif phase == "window":
                    if tracing_on and not marked and fresh >= trace_stop_end:
                        mark_stop()
                    if ticks.close_of(ticks.ticks(ends, ats), origin_end,
                                      seconds) is not None:
                        phase = "closing"
                        log["counters_close"] = watch.counters()
                        log["phases_close"] = watch.phases()
                        if sampler is not None:
                            sampler.stop()
                        if tracing_on and not marked:
                            mark_stop()  # a window under the stretch
                        await running.stop(StopMode.GRACEFUL)
                        stop_sent_at = time.monotonic()
                        if tracing_on:
                            stop_trace()
                            tracing_on = False
            now = time.monotonic()
            if (phase == "setup" and now - started > SETUP_LIMIT_S
                    or stop_sent_at and now - stop_sent_at > DRAIN_LIMIT_S):
                await running.stop(StopMode.IMMEDIATE)
                raise TimeoutError(
                    f"no origin tick after {SETUP_LIMIT_S} s" if not
                    stop_sent_at else f"job still running {DRAIN_LIMIT_S} s "
                    "after its graceful stop")
            await asyncio.sleep(POLL_S)
        await joined  # raises what a task of the job raised
        sweep()
        log["counters_drained"] = watch.counters()
        log["state_bytes_drained"] = watch.state_bytes()
    finally:
        if sampler is not None:
            sampler.stop()
        if tracing_on:
            stop_trace()
    if phase != "closing":
        raise RuntimeError(
            f"the stream ended in phase {phase!r}, before the window closed: "
            f"{cfg['stream_s']} s of event time is too short for this "
            f"cell at {seconds} s")
    log["spans"] = watch.spans()
    if sampler is not None:
        log["host_samples"] = sampler.samples
    log["batches"] = list(outs)
    clear_sink(sink)
    log["tick_map"] = ticks.ticks(ends, ats)
    log["window"] = ticks.measure(log["tick_map"], origin_end, seconds,
                                  stream["event_rate"])
    log["drained_s"] = time.monotonic() - stop_sent_at
    log["started_at"], log["first_arrival_at"] = started, ats[0]
    return log
