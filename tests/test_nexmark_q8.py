"""NEXMark q8 (two all-keys tumbling counts into a windowed join) through
the entry a user takes, ``plan_sql`` -> engine, against the benchmark's plain
reference; and the rule the fire and the join keep: no shape that a fire or a
join hands to ``jit`` follows the data, so after the first tumble nothing
compiles, however the keys grow and the join's row counts vary."""

import os
import sys
import time

import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import compare, spec  # noqa: E402

from arroyo_tpu.ops import keyed_bins  # noqa: E402

COMPILES = []  # monotonic end time of every backend compile of the process


def _on_compile(name, _secs, **_kw):
    if name == "/jax/core/compile/backend_compile_duration":
        COMPILES.append(time.monotonic())


def _run_q8(seed, stream_s, batch_size=4096, capacity=32768,
            cell_name="nexmark_q8.catchup"):
    """A cell's own SQL (q8's, or the cell named) at a CPU size, run to the
    end of its stream; returns (cell, sink batches, sink arrival times)."""
    from arroyo_tpu import config as program_config
    from arroyo_tpu.connectors.memory import (clear_sink, sink_arrivals,
                                              sink_output)
    from arroyo_tpu.engine.engine import LocalRunner
    from arroyo_tpu.sql import plan_sql

    cell = spec.load_cell(cell_name, rehearsal=True)
    cell.config["stream_s"] = stream_s
    cell.traffic["source_options"]["batch_size"] = batch_size
    os.environ["STATE_CAPACITY"] = str(capacity)
    # one chip, as the cell has it: the bin state of a single device (the
    # test session's eight virtual devices would give the mesh state,
    # which sizes its exchange by the batch)
    os.environ["ARROYO_MESH"] = "off"
    program_config.reset_config()
    sink = cell.config["sink"]
    clear_sink(sink)
    outs, arrivals = sink_output(sink), sink_arrivals(sink)
    try:
        LocalRunner(plan_sql(cell.sql(seed))).run()
        return cell, list(outs), list(arrivals)
    finally:
        clear_sink(sink)
        del os.environ["STATE_CAPACITY"], os.environ["ARROYO_MESH"]
        program_config.reset_config()


@pytest.mark.parametrize("seed", [0, 7, 2_147_483_999])
def test_q8_rows_equal_the_reference(seed):
    cell, batches, _ = _run_q8(seed, stream_s=35)
    t_end = cell.config["stream"]["base_time_micros"] + 30_000_000
    got = compare.sink_rows(batches, cell.config["result_columns"], t_end)
    want = cell.reference.rows(cell.reference_stream(seed, t_end), t_end)
    numbers = compare.compare(got, want)
    assert compare.verdict(numbers), numbers
    assert len(np.unique(want[:, 0])) == 3 and len(want) > 3000


@pytest.fixture(scope="module")
def device_join_run():
    """One q8 run of seven tumbles with the join on device rings, the
    profiler armed and every backend compile of the process timed."""
    import jax.monitoring as mon

    from arroyo_tpu.obs import perf, profiler, tracing

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("ARROYO_DEVICE_JOIN", "on")
        # a partition holds 250 rows a tumble here, 12,500 in the cell
        mp.setenv("ARROYO_JOIN_HOT_MIN_ROWS", "64")
        mon.register_event_duration_secs_listener(_on_compile)
        perf.reset()
        tracing.reset()
        prof = profiler.arm("q8-test")
        try:
            cell, batches, arrivals = _run_q8(11, stream_s=72)
            phases = {}
            for (_op, phase), secs in prof.work_snapshot().items():
                phases[phase] = phases.get(phase, 0.0) + secs
        finally:
            profiler.disarm()
    counters = {n: perf.counter(n) for n in (
        "join_device_gather_rows", "join_host_gather_rows",
        "state_grows", "join_rows_appended", "join_rows_probed",
        "pane_emit_cells", "pane_scan_cells")}
    return {"cell": cell, "batches": batches, "arrivals": arrivals,
            "phases": phases, "counters": counters,
            "spans": tracing.spans("window")}


def test_nothing_compiles_after_the_first_tumble(device_join_run):
    """Six more tumbles after the first: the key directories grow by some
    6,000 keys a tumble (the keys never return), the sellers and the joined
    rows of a tumble vary, the join runs on device rings: no fire, no
    append and no projection may compile again."""
    run = device_join_run
    ends = [int(b.timestamp[0]) + 1 for b in run["batches"]]
    base = run["cell"].config["stream"]["base_time_micros"]
    first = max(at for end, at in zip(ends, run["arrivals"])
                if end == base + 10_000_000)
    assert len(set(ends)) >= 7
    sizes = {end: 0 for end in ends}
    for end, b in zip(ends, run["batches"]):
        sizes[end] += len(b)
    assert len(set(sizes.values())) >= 5, sizes  # row counts do vary
    late = [t for t in COMPILES if t > first]
    assert late == [], f"{len(late)} compiles after the first tumble"
    assert run["counters"]["state_grows"] == 0


def test_join_rows_stay_on_the_device(device_join_run):
    """Every partition of both sides holds a ring (the budget follows what
    the rings hold), so no gathered row comes from the host."""
    c = device_join_run["counters"]
    assert c["join_device_gather_rows"] > 0
    assert c["join_host_gather_rows"] == 0


def test_join_counters_add_up(device_join_run):
    c, batches = device_join_run["counters"], device_join_run["batches"]
    # both sides' rows of a pair are gathered
    assert c["join_device_gather_rows"] == 2 * sum(len(b) for b in batches)
    # every fired cell of both aggregates is appended, then probed once
    assert c["join_rows_appended"] == c["pane_emit_cells"]
    assert c["join_rows_probed"] == c["join_rows_appended"]
    assert c["pane_scan_cells"] > c["pane_emit_cells"]


@pytest.mark.parametrize("span", ["join.fire", "join.fire.d2h",
                                  "join.fire.emit"])
def test_join_fire_spans(device_join_run, span):
    """A join's fire leaves `join.fire` and, inside it, one `join.fire.d2h`
    and one `join.fire.emit` that carry its window end."""
    spans = device_join_run["spans"]
    fires = {s[6]["window_end"]: s for s in spans if s[0] == "join.fire"}
    mine = [s for s in spans if s[0] == span]
    assert len(fires) >= 7 and len(mine) == len(fires)
    for _name, _cat, start, dur, _pid, _tid, args in mine:
        _, _, f_start, f_dur, *_ = fires[args["window_end"]]
        assert dur > 0
        assert f_start <= start and start + dur <= f_start + f_dur + 1.0


@pytest.mark.parametrize("phase", ["join_append", "join_merge",
                                   "join_probe", "gather"])
def test_join_phases_are_recorded(device_join_run, phase):
    from arroyo_tpu.obs import profiler

    assert phase in profiler.WORK_PHASES
    assert device_join_run["phases"].get(phase, 0.0) > 0


def _nonzero_form(values, cnt, k, npad):
    """What ``_emit_compact_kernel`` returned while it picked its cells with
    ``jnp.nonzero(size=npad)`` (COUNT(*) state: no channel block)."""
    flat = cnt.reshape(-1)
    n = flat.shape[0]
    idx = jnp.nonzero(flat > 0, size=npad, fill_value=n)[0]
    ok = idx < n
    safe = jnp.where(ok, idx, 0)
    return (jnp.stack([(safe // k).astype(jnp.int32),
                       (safe % k).astype(jnp.int32)]),
            jnp.where(ok, flat[safe], 0))


@pytest.mark.parametrize("k", [1, 4])
@pytest.mark.parametrize("density", [0.0, 0.01, 0.3, 1.0])
def test_compact_pick_equals_the_nonzero_form(density, k):
    C, B, W = 512, 8, 1
    rng = np.random.default_rng(int(density * 100) + k)
    cnt = (rng.integers(1, 1000, size=(C, k))
           * (rng.random((C, k)) < density)).astype(np.int32)
    if density == 1.0:
        assert (cnt > 0).all()
    nnz = int((cnt > 0).sum())
    npad = keyed_bins._bucket(nnz, keyed_bins._EMIT_ROWS_FLOOR)
    assert npad >= nnz and npad in (1024, 2048)
    values = jnp.zeros((1, C, B))
    ring, ok = jnp.zeros((k, W), jnp.int32), jnp.ones((k, W), bool)
    flat = jnp.asarray(cnt).reshape(-1) > 0
    live = keyed_bins._live_cells(flat)
    np.testing.assert_array_equal(
        np.asarray(live),
        np.asarray(jnp.nonzero(flat, size=C * k, fill_value=C * k)[0]))
    kernel = keyed_bins._emit_compact_kernel(("count",), C, B, W, k, (),
                                             npad)
    idx2, cnt_c, ch = kernel(values, jnp.asarray(cnt), live, ring, ok)
    want_idx2, want_cnt = _nonzero_form(values, jnp.asarray(cnt), k, npad)
    np.testing.assert_array_equal(np.asarray(idx2), np.asarray(want_idx2))
    np.testing.assert_array_equal(np.asarray(cnt_c), np.asarray(want_cnt))
    assert ch.shape == (0, npad)


def test_fire_shapes_follow_buckets_not_the_data():
    """The sizes a fire hands to ``jit`` come from one rule, ``_bucket``: the
    power of two at or above the live cells (from 1024) for the compacted
    readback, that of the occupied slots for a drain's dense read."""
    assert [keyed_bins._bucket(n, keyed_bins._EMIT_ROWS_FLOOR) for n in
            (0, 1, 1024, 1025, 4096, 110_000, 200_000, 262_145)] == [
        1024, 1024, 1024, 2048, 4096, 131_072, 262_144, 524_288]
    from arroyo_tpu.graph.logical import AggKind, AggSpec

    st = keyed_bins.KeyedBinState((AggSpec(AggKind.COUNT, None, "n"),),
                                  1000, 1000, capacity=1 << 14)
    seen = set()
    for step in range(1, 40):
        st.next_slot = step * 397
        seen.add(st._c_slice())
    assert seen == {512, 1024, 2048, 4096, 8192, 16384}
    # the flush at two sizes, the scan, the pick at 1024, 2048 ... 16384 = C
    assert st.warm_fire() == 2 + 1 + 5
