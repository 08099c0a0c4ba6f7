"""The benchmark's entry: one run of one cell of ``BENCHMARK.json``.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process holds the chip(s), plans the cell's SQL, drives the job into a
``memory`` sink and measures between two sink ticks (``harness/ticks.py``).
Everything but the result goes to standard error; the last line of standard
output is the result object.  Without a TPU, with another number of chips
than the cell asks for, or without the program beside it, it exits non-zero
and prints no result.
"""

import time

T_PROCESS = time.monotonic()  # set-up is counted from here

import argparse  # noqa: E402
import asyncio  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
for _p in (ROOT, BENCH_DIR):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from harness import compare, readers, spec, trace_reduce  # noqa: E402
from harness.compile_clock import CompileClock  # noqa: E402
from harness.drive import SAMPLE_S, drive  # noqa: E402

EXIT_NO_CHIP = 3
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def peaks_table():
    with open(os.path.join(BENCH_DIR, "harness", "peaks.json")) as f:
        return json.load(f)


def device_report(chips):
    """The devices as JAX reports them; None unless they are exactly the
    TPU chips the cell asks for, of a kind the peaks table knows."""
    import jax

    peaks = peaks_table()
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if dev["platform"] != "tpu" or dev["count"] != chips:
        log(f"run.py: the cell asks for {chips} TPU chip(s), JAX has {dev}")
        return None
    if dev["kind"] not in peaks:
        raise KeyError(f"device kind {dev['kind']!r} is not in the peaks "
                       f"table harness/peaks.json: {sorted(peaks)}")
    return dev


M_TRIM_THRESHOLD, M_MMAP_THRESHOLD = -1, -3  # glibc's <malloc.h>
MMAP_THRESHOLD_BYTES = 32 << 20  # DEFAULT_MMAP_THRESHOLD_MAX on 64 bits
TRIM_THRESHOLD_BYTES = 1 << 30  # over any heap top a run frees: no trim


def pin_allocator():
    """glibc raises its mmap threshold to the size of every mmapped block a
    process frees, up to 32 MiB, and keeps the trim threshold at twice that.
    How far a run's own history carries it decides whether the key
    directory's whole-array temporaries come from the heap or from fresh
    pages: a run that loaded every kernel from the cache stops at 3 x C
    bytes, one that compiled anything at 6 x C, and they differ by half in
    ``events_per_s`` (PERF.md section 6).  The mmap threshold is set here to
    the end of glibc's own adjustment, where it stays for good, so that
    every run of every cell measures the same allocator.  The trim threshold
    is set over anything a run frees: at glibc's 64 MiB the heap's top was
    given back and faulted in again in every batch once three directory-long
    arrays passed it, from the 13th to the 19th period of a run and at 0.5 to
    1.1 s a period, and the check read that as a spread of 6 to 9 %."""
    import ctypes

    libc = ctypes.CDLL(None)
    for knob, value in ((M_MMAP_THRESHOLD, MMAP_THRESHOLD_BYTES),
                        (M_TRIM_THRESHOLD, TRIM_THRESHOLD_BYTES)):
        if libc.mallopt(knob, value) != 1:
            raise OSError(f"mallopt({knob}, {value}) was refused: the runs "
                          "of this benchmark would fall into two modes")


def memory_stats():
    import jax

    return [d.memory_stats() or {} for d in jax.local_devices()]


def _top(table, n=3):
    ranked = sorted(table.items(), key=lambda kv: -kv[1])[:n]
    return " ".join(f"{k}={v:.3f}" for k, v in ranked)


def _period_table(window, run, clock):
    """Per period: events, wall seconds, compile seconds, the state's bytes
    and, in a traced run, the seconds of the program's top host phases and
    the host frames that the sampler saw most (seconds of samples)."""
    log("period  window_end_s  events  wall_s  events_per_s  compile_s  "
        "state_bytes  [phases | frames]")
    prev_at, prev_end = window.origin_at, window.origin_end
    samples = run["host_samples"]
    for i, (end, at, events, secs) in enumerate(window.periods):
        _, compile_s, _ = clock.between(prev_at, at)
        state = sum(run["state_bytes"].get(end, {}).values())
        extra = ""
        p0, p1 = (run["phases_at"].get(e) for e in (prev_end, end))
        if p0 is not None and p1 is not None:
            by_phase = {}
            for (_op, ph), s in p1.items():
                by_phase[ph] = by_phase.get(ph, 0.0) + s - p0.get((_op, ph), 0)
            frames = {}
            for t, label in samples:
                if prev_at * 1e9 < t <= at * 1e9:
                    frames[label] = frames.get(label, 0.0) + SAMPLE_S
            extra = f"  [{_top(by_phase)} | {_top(frames)}]"
        log(f"{i:6d}  {(end - window.origin_end) / 1e6:12.1f}  {events:6d}  "
            f"{secs:.4f}  {events / secs:12.1f}  {compile_s:9.4f}  {state}"
            f"{extra}")
        prev_at, prev_end = at, end


def _module_table(trace, run):
    """The traced stretch by XLA module, and the counters of work that the
    program made between the same two marks."""
    log(f"traced stretch: busy_s={trace['busy_s']:.6f} "
        f"window_s={trace['window_s']:.6f} modules_s="
        f"{sum(s for s, _ in trace['modules'].values()):.6f}")
    log("module  device_s  dispatches  s_per_dispatch")
    for name, (secs, n) in sorted(trace["modules"].items(),
                                  key=lambda kv: -kv[1][0]):
        log(f"{name}  {secs:.6f}  {n:g}  {secs / n:.6f}")
    start, stop = (run.get(k) for k in ("counters_trace_start",
                                        "counters_trace_stop"))
    if start is not None and stop is not None:
        log("counters between the trace marks: "
            + " ".join(f"{k}={stop[k] - start[k]}" for k in sorted(stop)))


def run_cell(cell, seed, seconds, trace, t_process, device, control=False):
    """Drive the cell once and return the result object.  ``device`` is
    ``device_report``'s; the tests hand in a made-up one to run on the CPU."""
    import arroyo_tpu  # noqa: F401  (enables x64 before any array exists)
    from arroyo_tpu import config as program_config
    from arroyo_tpu.engine.aot import enable_persistent_cache
    from arroyo_tpu.obs import profiler, tracing

    pin_allocator()
    # the one deployment setting the configuration's file states
    os.environ["STATE_CAPACITY"] = str(cell.config["state_capacity"])
    program_config.reset_config()
    cache_dir = enable_persistent_cache()
    clock = CompileClock()
    trace_dir = os.path.join(TRACE_DIR, cell.name) if trace else None
    if trace:
        profiler.arm(cell.name)
        tracing.set_capacity(1 << 20)  # hold every span of the window
    log(f"cell={cell.name} seed={seed} seconds={seconds} trace={trace} "
        f"device={device} compile_cache_dir={cache_dir} "
        f"state_capacity={cell.config['state_capacity']}")

    t_drive = time.monotonic()
    run = asyncio.run(drive(cell, seed, seconds, trace_dir))
    window = run["window"]
    memory = memory_stats()
    if trace:
        profiler.disarm()
    setup_s = window.origin_at - t_process
    in_window = clock.between(window.origin_at, window.close_at)
    in_setup = clock.between(0.0, window.origin_at)
    log(f"setup_s={setup_s:.3f} compiles_in_setup={in_setup[0]} "
        f"compile_s_in_setup={in_setup[1]:.3f} cache_requests="
        f"{clock.requests} cache_hits={clock.hits}")
    log(f"setup by stage: start_and_imports_s={t_drive - t_process:.3f} "
        f"plan_and_engine_s={run['started_at'] - t_drive:.3f} "
        f"to_first_sink_batch_s="
        f"{run['first_arrival_at'] - run['started_at']:.3f} "
        f"to_origin_tick_s={window.origin_at - run['first_arrival_at']:.3f}")
    log(f"window: events={window.events} seconds={window.seconds:.4f} "
        f"overshoot_s={window.seconds - seconds:.4f} "
        f"fires_in_window={len(window.periods)} "
        f"drained_s={run['drained_s']:.3f}")
    log(f"compiles_in_window={in_window[0]} compile_s_in_window="
        f"{in_window[1]:.4f} by_site={in_window[2]}")
    _period_table(window, run, clock)
    first, last = (run["state_bytes"].get(e, {})
                   for e in (window.origin_end, window.close_end))
    log(f"state_bytes at origin tick {first} at close tick {last} "
        f"after the drain {run['state_bytes_drained']}")
    log("counters at the close tick and after the drain: " + " ".join(
        f"{k}={run['counters_close'][k]}/{v}"
        for k, v in sorted(run["counters_drained"].items())))

    # the reference runs only now: the peak is read and the state is freed
    got = compare.sink_rows(run.pop("batches"), cell.config["result_columns"],
                            window.close_end)
    gc.collect()
    t0 = time.monotonic()
    stream = cell.reference_stream(seed, window.close_end)
    want = cell.reference.rows(stream, window.close_end)
    numbers = compare.compare(got, want)
    n_windows = len(set(want[:, 0].tolist()))
    log(f"reference: {len(want)} rows in {n_windows} "
        f"windows over {stream['n_events']} events in "
        f"{time.monotonic() - t0:.2f} s; the sink has {len(got)} rows")
    if control:
        size = stream["batch_size"]
        first_batch = (window.origin_end - cell.config["stream"][
            "base_time_micros"]) * cell.config["stream"]["event_rate"] \
            // 1_000_000 // size + 1
        k = int(first_batch + seed % max(stream["n_events"] // size
                                         - first_batch, 1))
        for kind in ("controls", "also_read"):
            for fault in cell.config.get(kind, ()):
                broken = compare.compare(
                    cell.reference.rows(stream, window.close_end,
                                        **{fault: k}), want)
                log(f"{kind} {fault}={k}: {broken} "
                    f"correct={compare.verdict(broken)}")

    if trace:
        obs = dict(run, memory=memory, compiles=in_window, trace=None,
                   peaks=peaks_table().get(device["kind"]))
        files = glob.glob(os.path.join(
            trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
        if files:
            obs["trace"] = trace_reduce.reduce_file(files[0],
                                                    run["host_samples"])
        shutil.rmtree(trace_dir, ignore_errors=True)
        if obs["trace"] is not None:
            _module_table(obs["trace"], run)
        metrics = {}
        for entry, reader in cell.per_layer:
            value = readers.read(reader, obs)
            if value is not None:
                metrics[entry["name"]] = {"value": value,
                                          "unit": entry["unit"]}
    else:
        values = {"events_per_s": window.events_per_s, "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}

    device = dict(device, memory_peak_bytes=max(
        (m.get("peak_bytes_in_use", 0) for m in memory), default=0))
    result = {"correct": compare.verdict(numbers),
              "attempted": n_windows,
              "failed": numbers["windows_wrong"], "metrics": metrics,
              "device": device}
    if trace and obs["trace"] is not None:
        device.update(busy_s=obs["trace"]["busy_s"],
                      window_s=obs["trace"]["window_s"])
        result["breakdown"] = {k: obs["trace"][k]
                               for k in ("device_ops", "idle_gaps")}
        result["modules"] = obs["trace"]["modules"]
    result["compared"] = {k: {"value": numbers[k], "limit": limit}
                          for k, limit in compare.LIMITS.items()}
    for line in compare.lines(numbers):
        log(line)
    return result


def main(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    t_main = time.monotonic()
    device = device_report(cell.chips)
    if device is None:
        return EXIT_NO_CHIP
    log(f"start by stage: interpreter_and_harness_imports_s="
        f"{t_main - T_PROCESS:.3f} jax_import_and_devices_s="
        f"{time.monotonic() - t_main:.3f}")
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      T_PROCESS, device)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
