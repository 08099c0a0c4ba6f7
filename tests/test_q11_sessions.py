"""NEXMark q11, user sessions (the bids of each bidder per activity session,
a session closing after 10 s without a bid), through the entry a user takes,
``plan_sql`` -> engine, against the benchmark's plain reference at the
rehearsal's size: the rows on two seeds and at a gap of 1 s, with the union
scan on the host and as the jitted kernel; a replayed or half-dropped batch
reads not correct; the session fire's span and counters; and the
``watermark`` tick rule a session cell stands on; the fire reads each
session's own accumulators, and a median-UDAF session query still
re-reads the buffered rows (every session that ends at
or before a watermark reaches the sink before it, and the sink logs and
forwards every watermark in order)."""

import asyncio
import os
import sys

import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import compare, drive, spec, ticks  # noqa: E402

CONFIG = "nexmark_q11_sessions"
GAP_SQL = "INTERVAL '10' SECOND"
STREAM_S = 24  # sessions that end by 22 s hold the bids before some 11 s
COUNTERS = ("session_fires", "session_rows_read", "session_flush_us",
            "session_acc_sessions",
            "session_union_rows", "session_merge_dispatches",
            "kernel_dispatches.session_union", "sink_rows")


def _cell():
    """The benchmark's q11 cell at its configuration's rehearsal sizes."""
    return spec.load_cell(CONFIG + ".catchup", rehearsal=True)


def _run_q11(seed, stream_s=STREAM_S, gap_s=10, edit=None, provider=None):
    """The cell's own SQL at its rehearsal size (``gap_s`` in place of the
    query's 10 s, ``edit`` applied to the text), run to the end of its
    stream; returns (cell, sink batches)."""
    from arroyo_tpu.connectors.memory import clear_sink, sink_output
    from arroyo_tpu.engine.engine import LocalRunner
    from arroyo_tpu.sql import plan_sql

    cell = _cell()
    cell.config["stream_s"] = stream_s
    sql = cell.sql(seed)
    assert sql.count(GAP_SQL) == 1
    sql = sql.replace(GAP_SQL, f"INTERVAL '{gap_s}' SECOND")
    if edit is not None:
        sql = edit(sql)
    sink = cell.config["sink"]
    clear_sink(sink)
    outs = sink_output(sink)
    try:
        LocalRunner(plan_sql(sql, provider)).run()
        return cell, list(outs)
    finally:
        clear_sink(sink)


def _t_end(cell, stream_s=STREAM_S):
    return cell.config["stream"]["base_time_micros"] + (stream_s - 2) * 10**6


def _compared(cell, batches, seed, t_end, gap_s=10, **faults):
    got = compare.sink_rows(batches, cell.config["result_columns"], t_end)
    want = cell.reference.rows(cell.reference_stream(seed, t_end), t_end,
                               gap_micros=gap_s * 10**6, **faults)
    return compare.compare(got, want), want


def _counted_run(seed, **kw):
    """One run with the union scan as the jitted kernel (the path a TPU
    takes by itself), the counters and spans kept, and the rows every
    ``query_range`` of the session buffer returned summed."""
    from arroyo_tpu.obs import perf, tracing
    from arroyo_tpu.state.tables import BatchBuffer

    returned = []
    query_range = BatchBuffer.query_range

    def counted(self, start, end):
        rows = query_range(self, start, end)
        returned.append(0 if rows is None else len(rows))
        return rows

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("ARROYO_SESSION_DEVICE", "on")
        mp.setattr(BatchBuffer, "query_range", counted)
        perf.reset()
        tracing.reset()
        cell, batches = _run_q11(seed, **kw)
    return {"cell": cell, "seed": seed, "batches": batches,
            "rows_returned": sum(returned),
            "counters": {n: perf.counter(n) for n in COUNTERS},
            "fires": [s for s in tracing.spans("window")
                      if s[0] == "session.fire"]}


@pytest.fixture(scope="module")
def device_run():
    return _counted_run(2_147_483_999)


@pytest.mark.parametrize("seed,gap_s", [(7, 10), (2_147_483_777, 10),
                                        (11, 1)])
def test_sessions_equal_the_reference(seed, gap_s):
    cell, batches = _run_q11(seed, gap_s=gap_s)
    numbers, want = _compared(cell, batches, seed, _t_end(cell), gap_s)
    assert compare.verdict(numbers), numbers
    bidders, sessions = np.unique(want[:, 1], return_counts=True)
    assert len(want) > 5000 and want[:, 2].max() > 100
    if gap_s == 1:
        # sessions close and open again within the stream: a bidder is
        # active for about a second of event time at this rate
        assert sessions.max() >= 2 and len(want) > len(bidders)


def test_device_union_sessions_equal_the_reference(device_run):
    run = device_run
    numbers, want = _compared(run["cell"], run["batches"], run["seed"],
                              _t_end(run["cell"]))
    assert compare.verdict(numbers), numbers
    assert len(want) > 5000


@pytest.mark.parametrize("fault", ["replay_batch", "drop_half_of_batch"])
def test_a_faulted_stream_reads_not_correct(device_run, fault):
    """A batch of the first seconds delivered twice, or half of it left
    out, changes the counts of the sessions that hold its bids."""
    run = device_run
    assert run["cell"].config["controls"] == ["replay_batch",
                                              "drop_half_of_batch"]
    numbers, _ = _compared(run["cell"], run["batches"], run["seed"],
                           _t_end(run["cell"]), **{fault: 20})
    assert not compare.verdict(numbers), numbers
    assert numbers["rows_differ"] > 100


def test_session_counters_and_spans_are_recorded(device_run):
    c, run = device_run["counters"], device_run
    # q11 counts: the fire reads the sessions' accumulators, no row
    assert c["session_fires"] > 10 and c["session_rows_read"] == 0
    assert c["session_flush_us"] > 0
    dispatches = c["kernel_dispatches.session_union"]
    assert dispatches > 10 and dispatches == c["session_merge_dispatches"]
    # interval rows, not the power-of-two pad they ride in
    assert c["session_union_rows"] > dispatches
    # one span a watermark that fired, carrying it
    assert len(run["fires"]) == c["session_fires"]
    assert all(dur > 0 and args["watermark"] > 0
               for _n, _c, _s, dur, _p, _t, args in run["fires"])
    assert c["sink_rows"] == sum(len(b) for b in run["batches"])
    assert c["session_acc_sessions"] == c["sink_rows"]


def test_session_rows_read_are_the_rows_query_range_returned(device_run):
    """q11 takes the accumulator path: no fire re-reads the buffer, and
    every session in the sink came off its own partials."""
    c = device_run["counters"]
    assert c["session_rows_read"] == device_run["rows_returned"] == 0
    assert c["session_acc_sessions"] == c["sink_rows"] > 5000


def test_median_udaf_sessions_read_the_rows_query_range_returned():
    """A median UDAF does not fold into partials, so its session query
    keeps the row buffer: the fire re-reads the rows ``query_range``
    returns, counted as such, and the counts still equal the
    reference's."""
    from arroyo_tpu.sql import SchemaProvider
    from arroyo_tpu.sql.functions import UDAFS

    provider = SchemaProvider()
    # registrations are process-wide: a name no other test registers,
    # taken back after the run
    provider.register_udaf("q11_median", np.median)

    def with_median(sql):
        sql = sql.replace("bid.datetime as datetime",
                          "bid.datetime as datetime, bid.price as price")
        return sql.replace(" as window\nFROM",
                           " as window, q11_median(price) as med\nFROM")

    seed = 2_147_483_651
    try:
        run = _counted_run(seed, stream_s=16, edit=with_median,
                           provider=provider)
    finally:
        UDAFS.pop("q11_median", None)
    c = run["counters"]
    assert c["session_rows_read"] == run["rows_returned"]
    # the re-read: a fire reads far more rows than the sessions it fires
    assert run["rows_returned"] > 10 * c["sink_rows"] > 0
    assert c["session_acc_sessions"] == 0
    assert all("med" in b.columns for b in run["batches"])
    numbers, want = _compared(run["cell"], run["batches"], seed,
                              _t_end(run["cell"], 16))
    assert compare.verdict(numbers), numbers
    assert len(want) > 1000


# -- the ``watermark`` tick rule, as benchmarks/tests/test_watermark_ticks.py
# -- holds the harness to it

BASE = 1_700_000_000_000_000


class _Ctx:
    def __init__(self):
        self.sent = []

    async def broadcast(self, msg):
        self.sent.append(msg.watermark.time)


def test_sink_logs_every_watermark_in_order_after_the_batches_before_it():
    from arroyo_tpu.connectors.memory import (MemorySink, clear_sink,
                                              sink_arrivals)
    from arroyo_tpu.types import Batch

    async def feed(sink, ctx):
        for i, wm in enumerate((BASE + 5, BASE + 9, BASE + 9_000)):
            batch = Batch(np.array([BASE + i], dtype=np.int64),
                          {"x": np.array([i])})
            await sink.process_batch(batch, ctx)
            await sink.handle_watermark(wm, ctx)

    clear_sink("hooked")
    with drive.sink_watermarks("hooked") as marks:
        other = _Ctx()
        asyncio.run(feed(MemorySink({"name": "other"}), other))
        ctx = _Ctx()
        asyncio.run(feed(MemorySink({"name": "hooked"}), ctx))
    assert "handle_watermark" not in MemorySink.__dict__  # hook removed
    assert [w for w, _ in marks] == [BASE + 5, BASE + 9, BASE + 9_000]
    assert ctx.sent == other.sent == [w for w, _ in marks]  # forwarded
    arrivals = sink_arrivals("hooked")
    for i, (_, at) in enumerate(marks):
        assert arrivals[i] <= at
        assert i + 1 == len(arrivals) or at <= arrivals[i + 1]
    clear_sink("hooked")
    clear_sink("other")


@pytest.mark.parametrize("seed", [7, 2_147_483_999])
def test_q11_sessions_arrive_before_the_watermark_past_their_end(seed):
    from arroyo_tpu.connectors.memory import (clear_sink, sink_arrivals,
                                              sink_output)
    from arroyo_tpu.engine.engine import LocalRunner
    from arroyo_tpu.sql import plan_sql

    cell = _cell()
    cell.config["stream"]["event_rate"] = 20_000
    cell.config["stream_s"] = 20
    clear_sink("results")
    with drive.sink_watermarks("results") as marks:
        LocalRunner(plan_sql(cell.sql(seed))).run()
    outs, arrivals = sink_output("results"), sink_arrivals("results")
    watermarks = [w for w, _ in marks]
    assert watermarks == sorted(set(watermarks)) and len(watermarks) > 40
    assert watermarks[-1] >= ticks.END_OF_STREAM
    ends = np.concatenate([np.asarray(b.timestamp) + 1 for b in outs])
    at = np.concatenate([np.full(len(b), a) for b, a in zip(outs, arrivals)])
    for watermark, t in marks:
        due = ends <= watermark
        assert (at[due] <= t).all(), (watermark, int((at[due] > t).sum()))
    # sessions end anywhere: no grid of window ends to tick on
    assert len(ends) > 1000 and len(set(ends.tolist())) > len(ends) // 2
    clear_sink("results")
