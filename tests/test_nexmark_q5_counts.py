"""NEXMark q5's inner relation (every auction's bid count out of every
HOP(2 s, 10 s) fire) through the entry a user takes, ``plan_sql`` -> engine,
against the benchmark's plain reference; the hop window's sums on keys whose
bins lie at its edges; the compacted fire at W = 5 against the planes; and
PR 29's rule at W = 5: once the first full window has fired nothing compiles,
whatever the live count."""

import numpy as np
import pytest
# q8's test file has the runner of a cell's SQL and the compile listener,
# and puts ``benchmarks/`` on the path
from test_nexmark_q8 import COMPILES, _on_compile, _run_q8

from harness import compare  # noqa: E402

from arroyo_tpu import AggKind, AggSpec
from arroyo_tpu.ops import keyed_bins
from arroyo_tpu.ops.keyed_bins import KeyedBinState

SLIDE, WIDTH = 2_000_000, 10_000_000


def _run_counts(seed, stream_s):
    """The cell's own SQL at a CPU size on one device's bin state, run to
    the end of its stream: (cell, sink batches, sink arrival times)."""
    return _run_q8(seed, stream_s, capacity=65536,
                   cell_name="nexmark_q5_counts.catchup")


@pytest.fixture(scope="module")
def counts_run():
    """One run of 42 s of the stream (16 full windows after the five that
    grow) with every backend compile of the process timed, the counters
    and the fire's spans kept."""
    import jax.monitoring as mon

    from arroyo_tpu.obs import perf, tracing

    mon.register_event_duration_secs_listener(_on_compile)
    perf.reset()
    tracing.reset()
    seed = 2_147_483_999
    cell, batches, arrivals = _run_counts(seed, stream_s=42)
    t_end = cell.config["stream"]["base_time_micros"] + 40_000_000
    return {"cell": cell, "seed": seed, "batches": batches,
            "arrivals": arrivals, "t_end": t_end,
            "got": compare.sink_rows(batches, cell.config["result_columns"],
                                     t_end),
            "counters": {n: perf.counter(n) for n in (
                "sink_rows", "pane_emit_cells", "state_grows",
                "window_fires", "kernel_dispatches.bins_emit_compact",
                "kernel_dispatches.bins_argmax_nnz")},
            "spans": tracing.spans("window")}


def _reference(run, **faults):
    cell = run["cell"]
    return cell.reference.rows(
        cell.reference_stream(run["seed"], run["t_end"]), run["t_end"],
        **faults)


def test_counts_rows_equal_the_reference(counts_run):
    want = _reference(counts_run)
    numbers = compare.compare(counts_run["got"], want)
    assert compare.verdict(numbers), numbers
    # 20 windows, and in a full one every auction bid on in 10 s
    assert len(np.unique(want[:, 0])) == 20 and len(want) > 200_000


def test_counts_rows_equal_the_reference_on_a_second_seed():
    cell, batches, _ = _run_counts(7, stream_s=22)
    t_end = cell.config["stream"]["base_time_micros"] + 20_000_000
    got = compare.sink_rows(batches, cell.config["result_columns"], t_end)
    want = cell.reference.rows(cell.reference_stream(7, t_end), t_end)
    numbers = compare.compare(got, want)
    assert compare.verdict(numbers), numbers
    assert len(np.unique(want[:, 0])) == 10 and len(want) > 80_000


@pytest.mark.parametrize("fault", ["replay_batch", "drop_half_of_batch"])
def test_each_control_reads_not_correct(counts_run, fault):
    """A batch delivered twice, or half of one left out, shows in the rows
    of the run itself: the sink covers every key of every window."""
    assert fault in counts_run["cell"].config["controls"]
    for k in (3, 100, 190):  # in the first window, a full one, the last
        numbers = compare.compare(counts_run["got"],
                                  _reference(counts_run, **{fault: k}))
        assert not compare.verdict(numbers), (fault, k, numbers)
        assert numbers["windows_missing"] == numbers["windows_extra"] == 0
        # the batch's bids lie in at most two slides, so in at most six
        # windows, and no other window differs
        assert 1 <= numbers["windows_wrong"] <= 6, numbers


def test_the_plan_is_one_compacted_fire(counts_run):
    """The relation leaves the aggregate as it stands: no argmax fused into
    the fire, every live (auction, window) cell read back and sunk."""
    c = counts_run["counters"]
    assert c["kernel_dispatches.bins_argmax_nnz"] == 0
    assert c["kernel_dispatches.bins_emit_compact"] == c["window_fires"] > 20
    assert c["sink_rows"] == c["pane_emit_cells"]
    assert c["sink_rows"] == sum(len(b) for b in counts_run["batches"])


def test_fire_collect_span(counts_run):
    """A watermark fire leaves one `window.fire.collect` after its
    `window.fire.emit`, inside its `window.fire`, under its watermark."""
    spans = counts_run["spans"]
    fires = {s[6]["watermark"]: s for s in spans if s[0] == "window.fire"}
    emits = {s[6]["watermark"]: s for s in spans
             if s[0] == "window.fire.emit"}
    collects = [s for s in spans if s[0] == "window.fire.collect"]
    assert len(collects) == len(emits) == \
        counts_run["counters"]["window_fires"]
    for _name, _cat, start, dur, _pid, _tid, args in collects:
        _, _, f_start, f_dur, *_ = fires[args["watermark"]]
        _, _, e_start, e_dur, *_ = emits[args["watermark"]]
        assert dur > 0 and start >= e_start + e_dur
        assert start + dur <= f_start + f_dur + 1.0


def test_nothing_compiles_after_the_first_full_window(counts_run):
    """Fifteen more fires after the first full window (whose fire is the
    first to evict a slide): the directory grows by 2,400 keys a slide and
    the live cells differ from fire to fire, yet no fire, flush, evict or
    projection compiles again: every shape follows the capacity, W or a
    bucket, and ``warm_fire`` ran the pick at every bucket up to C.  (The
    stream's end flushes four windows in one fire, a shape no watermark
    fire has: it lies after the stretch.)"""
    run = counts_run
    ends = [int(b.timestamp[0]) + 1 for b in run["batches"]]
    base = run["cell"].config["stream"]["base_time_micros"]
    first_full, last = (max(at for end, at in zip(ends, run["arrivals"])
                            if end == base + t)
                        for t in (WIDTH, 40_000_000))
    sizes = {}
    for end, b in zip(ends, run["batches"]):
        sizes[end] = sizes.get(end, 0) + len(b)
    # the five windows that grow take the pick through three buckets
    assert [keyed_bins._bucket(sizes[base + (i + 1) * SLIDE],
                               keyed_bins._EMIT_ROWS_FLOOR)
            for i in range(5)] == [4096, 8192, 8192, 16384, 16384]
    later = [n for end, n in sizes.items()
             if base + WIDTH < end <= base + 40_000_000]
    assert len(later) == 15 and len(set(later)) >= 5, sizes
    assert any(t <= first_full for t in COMPILES)  # the listener hears
    late = [t for t in COMPILES if first_full < t <= last]
    assert late == [], f"{len(late)} compiles after the first full window"
    assert run["counters"]["state_grows"] == 0


def _count_state(capacity=64, extra=()):
    aggs = (AggSpec(kind=AggKind.COUNT, column=None, output="num"),) + extra
    return KeyedBinState(aggs, slide_micros=SLIDE, width_micros=WIDTH,
                         capacity=capacity)


def test_hop_sums_on_keys_at_the_windows_edges():
    """Key 7's bids fall in two adjacent slides and key 9's in one slide
    alone, which is the oldest of the last window that holds it; key 1 bids
    in every slide and keeps the fires coming.  One fire a slide, as the
    cell has it: each key comes out of exactly the windows that hold a bid
    of it, with that window's sum, and of none after."""
    st = _count_state()
    b0 = 40  # absolute slide of the first bid
    bids = {1: {b: 1 for b in range(b0, b0 + 14)},
            7: {b0 + 2: 3, b0 + 3: 4},
            9: {b0 + 2: 5}}
    got = {}
    for b in range(b0, b0 + 14):
        keys = np.array([k for k, per in bids.items()
                         for _ in range(per.get(b, 0))], np.uint64)
        ts = b * SLIDE + (np.arange(len(keys)) * 997) % SLIDE
        st.update(keys, ts.astype(np.int64), {})
        fired = st.fire_panes((b + 1) * SLIDE)
        assert fired is not None and set(fired[2]) == {(b + 1) * SLIDE}
        for key, end, num in zip(fired[0], fired[2], fired[3]):
            got[int(key), int(end) // SLIDE - 1] = int(num)
    want = {}
    for key, per in bids.items():
        for last in range(b0, b0 + 14):  # the window's newest slide
            total = sum(n for b, n in per.items() if last - 5 < b <= last)
            if total:
                want[key, last] = total
    assert got == want
    assert [got.get((9, b0 + 2 + i)) for i in range(6)] == [5] * 5 + [None]
    assert [got.get((7, b0 + 2 + i)) for i in range(7)] == [
        3, 7, 7, 7, 7, 4, None]


@pytest.mark.parametrize("with_channels", [False, True])
def test_emit_compact_at_w5_equals_the_planes(with_channels):
    """``_emit_compact`` over two panes of five bins (``kpad`` 2) against
    numpy over the planes as they lie: the live (key, pane) cells in
    row-major order, each with the sum of its count and of its channels
    over the bins in range, bins out of range (an aliased ring row) left
    out."""
    extra = ((AggSpec(kind=AggKind.SUM, column="x", output="sx"),
              AggSpec(kind=AggKind.MAX, column="x", output="mx"))
             if with_channels else ())
    st = _count_state(capacity=512, extra=extra)
    assert (st.W, st.B) == (5, 16)
    rng = np.random.default_rng(34 + with_channels)
    C, B = st.C, st.B
    counts = (rng.integers(1, 50, size=(B, C))
              * (rng.random((B, C)) < 0.2)).astype(np.int32)
    values, _ = st._empty_host_planes(np.int32)
    for j, kind in enumerate(st._ch_kinds):
        x = rng.integers(-1000, 1000, size=(B, C)).astype(np.float64)
        values[j] = np.where(counts > 0, counts if kind == "count" else x,
                             values[j])
    st._set_planes(values, counts)
    st.next_slot = C
    # pane 0 covers ring rows 14, 15, 0, 1, 2 with row 14 out of range;
    # pane 1 rows 15, 0, 1, 2, 3
    ring = np.array([[14, 15, 0, 1, 2], [15, 0, 1, 2, 3]], np.int32)
    bin_ok = np.ones((2, 5), bool)
    bin_ok[0, 0] = False
    nnz, devs = st._emit_compact(ring, bin_ok, 2)  # the head's half
    key_idx, pane_idx, cnt, ch = st._read_picked(
        keyed_bins.PendingFire(0, None, None, nnz, devs))

    def reduce(plane, how, empty):
        rows = np.where(bin_ok[:, :, None], plane[ring], empty)
        return how(rows, axis=1).T  # [C, panes]

    want_cnt = reduce(counts, np.sum, 0)
    want_key, want_pane = np.nonzero(want_cnt)
    assert len(want_key) > 300 and (want_cnt == 0).sum() > 100
    np.testing.assert_array_equal(key_idx, want_key)
    np.testing.assert_array_equal(pane_idx, want_pane)
    np.testing.assert_array_equal(cnt, want_cnt[want_key, want_pane])
    assert ch.shape == (len(st._xfer_ch), len(want_key))
    for r, j in enumerate(st._xfer_ch):
        how, empty = {"sum": (np.sum, 0.0),
                      "max": (np.max, keyed_bins.NEG_INF)}[st._ch_kinds[j]]
        np.testing.assert_array_equal(
            ch[r], reduce(values[j], how, empty)[want_key, want_pane])
