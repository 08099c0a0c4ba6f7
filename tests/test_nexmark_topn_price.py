"""Arroyo's ``top_n`` query (the three auctions with the largest sum of bid
prices out of every HOP(2 s, 10 s) window, by ``ROW_NUMBER() OVER (PARTITION
BY window ORDER BY price DESC) < 4``) through the entry a user takes,
``plan_sql`` -> engine, against the benchmark's plain reference: the rows,
the ranks, windows with fewer than three auctions and with ties, the
reference's own bid stream against the connector's, the TopN stage's spans
and counters, and PR 29's rule for the TopN's sort: once the first full
window has fired nothing compiles."""

import numpy as np
import pytest
# q8's test file has the runner of a cell's SQL and the compile listener,
# and puts ``benchmarks/`` on the path
from test_nexmark_q8 import COMPILES, _on_compile, _run_q8

from harness import compare, spec  # noqa: E402
from references import nexmark_gen, nexmark_q5_counts  # noqa: E402
from references import nexmark_topn_price as reference  # noqa: E402

from arroyo_tpu.ops.topk import _bucket_rows

CELL = "nexmark_topn_price.catchup"
SLIDE, WIDTH = 2_000_000, 10_000_000
COUNTERS = ("topn_selects", "topn_rows_in", "topn_rows_out", "topk_rows",
            "topn_buffer_rows", "kernel_dispatches.topk_topk",
            "pane_emit_cells", "sink_rows", "state_grows", "window_fires")


def _run_topn(seed, stream_s, batch_size=4096):
    return _run_q8(seed, stream_s, batch_size=batch_size, capacity=65536,
                   cell_name=CELL)


def _sink(cell, batches, t_end, columns=None):
    return compare.sink_rows(batches, columns
                             or cell.config["result_columns"], t_end)


@pytest.fixture(scope="module")
def topn_run():
    """One run of 42 s of the stream (16 full windows after the five that
    grow) with every backend compile of the process timed, the counters
    and the spans kept."""
    import jax.monitoring as mon

    from arroyo_tpu.obs import perf, tracing

    mon.register_event_duration_secs_listener(_on_compile)
    perf.reset()
    tracing.reset()
    seed = 2_147_483_999
    cell, batches, arrivals = _run_topn(seed, stream_s=42)
    t_end = cell.config["stream"]["base_time_micros"] + 40_000_000
    return {"cell": cell, "seed": seed, "batches": batches,
            "arrivals": arrivals, "t_end": t_end,
            "got": _sink(cell, batches, t_end),
            "counters": {n: perf.counter(n) for n in COUNTERS},
            "spans": tracing.spans("window")}


def _reference(run, **faults):
    cell = run["cell"]
    return cell.reference.rows(
        cell.reference_stream(run["seed"], run["t_end"]), run["t_end"],
        **faults)


def test_topn_rows_equal_the_reference(topn_run):
    want = _reference(topn_run)
    numbers = compare.compare(topn_run["got"], want)
    assert compare.verdict(numbers), numbers
    # 20 windows of three rows, sums well past f32's integers
    assert len(want) == 60 and len(np.unique(want[:, 0])) == 20
    assert want[:, 2].min() > 1 << 30


@pytest.mark.parametrize("batch_size", [4096, 8192])
@pytest.mark.parametrize("seed", [7, 2_147_483_777])
def test_topn_rows_equal_the_reference_at(seed, batch_size):
    cell, batches, _ = _run_topn(seed, stream_s=22, batch_size=batch_size)
    t_end = cell.config["stream"]["base_time_micros"] + 20_000_000
    want = cell.reference.rows(cell.reference_stream(seed, t_end), t_end)
    numbers = compare.compare(_sink(cell, batches, t_end), want)
    assert compare.verdict(numbers), numbers
    assert len(want) == 30


def test_row_number_follows_the_sums(topn_run):
    """``row_number`` is 1, 2, 3 within every window, the larger sum the
    lower number; ``price`` leaves the engine as float64 and holds an
    integer (``assumed.result_row``)."""
    run = topn_run
    assert all(b.columns["price"].dtype == np.float64
               and b.columns["auction"].dtype == np.int64
               for b in run["batches"])
    rows = _sink(run["cell"], run["batches"], run["t_end"],
                 ["row_number", "price"])
    price = np.concatenate([b.columns["price"] for b in run["batches"]])
    assert (price == np.floor(price)).all() and price.max() < 1 << 40
    for end in np.unique(rows[:, 0]):
        of = rows[rows[:, 0] == end]
        of = of[np.argsort(of[:, 1])]
        assert of[:, 1].tolist() == [1, 2, 3]
        assert of[0, 2] > of[1, 2] > of[2, 2], of


@pytest.mark.parametrize("k", [3, 100, 190])
def test_a_batch_delivered_twice_reads_not_correct(topn_run, k):
    """In the first window, a full one and the last: a replayed batch
    doubles the sums of the hot auctions it holds, which then lead."""
    assert topn_run["cell"].config["controls"] == ["replay_batch"]
    numbers = compare.compare(topn_run["got"],
                              _reference(topn_run, replay_batch=k))
    assert not compare.verdict(numbers), (k, numbers)
    assert numbers["windows_missing"] == numbers["windows_extra"] == 0
    assert 1 <= numbers["windows_wrong"] <= 6, numbers


def test_half_a_batch_left_out_shows_only_on_a_leading_auction(topn_run):
    """The sink holds three auctions of a window: half a batch dropped
    changes a row where the batch holds bids of one of them (found here
    from the reference's own stream), and not otherwise, which is why the
    configuration reads it under ``also_read`` and not as a control."""
    run = topn_run
    assert run["cell"].config["also_read"] == ["drop_half_of_batch"]
    want = _reference(run)
    stream = run["cell"].reference_stream(run["seed"], run["t_end"])
    leader = want[want[:, 0] == run["t_end"]][0]
    lost = [(auction[len(auction) // 2:] == leader[1]).sum()
            for _ts, auction, _price in reference.bids(**stream)]
    hit = int(np.argmax(lost))
    assert lost[hit] > 50
    numbers = compare.compare(run["got"],
                              _reference(run, drop_half_of_batch=hit))
    assert not compare.verdict(numbers) and numbers["windows_wrong"] >= 1
    misses = [k for k in range(60, 70)
              if compare.verdict(compare.compare(
                  run["got"], _reference(run, drop_half_of_batch=k)))]
    assert misses, "every dropped half showed: make it a control"


def test_topn_spans_and_counters(topn_run):
    """Every window's selection is one `topn.select` inside one `topn.fire`;
    the plan is unfused, so every live auction's row of a window enters the
    TopN's buffer and its selection, and three leave."""
    run, c = topn_run, topn_run["counters"]
    fires = [s for s in run["spans"] if s[0] == "topn.fire"]
    selects = [s for s in run["spans"] if s[0] == "topn.select"]
    # 20 windows by the watermark and the five that the stream's end fires
    assert len(fires) == len(selects) == c["topn_selects"] == 25
    assert len({s[6]["window_end"] for s in fires}) == 25
    for (_n, _c, s0, d, *_), (_n2, _c2, f0, fd, *_2) in zip(selects, fires):
        assert d > 0 and f0 <= s0 and s0 + d <= f0 + fd + 1.0
    assert c["topn_rows_out"] == c["sink_rows"] == 75
    assert c["topn_rows_in"] == c["topn_buffer_rows"] == c["pane_emit_cells"]
    assert c["topn_rows_in"] / c["topn_selects"] > 5_000
    # every selection here is over the host path's 512 rows: one sort each
    assert c["kernel_dispatches.topk_topk"] == 25
    assert c["topk_rows"] == c["topn_rows_in"]


def test_nothing_compiles_after_the_first_full_window(topn_run):
    """Fifteen more windows after the first full one: the live auctions
    differ from window to window (the counts cell's reference says by how
    much), yet the TopN's sort, its transfer and the projections around it
    compile nothing: the sort's shape is a bucket of the rows.  (The
    stream's end fires five shrinking windows, smaller buckets: after the
    stretch.)"""
    run = topn_run
    ends = [int(b.timestamp[0]) + 1 for b in run["batches"]]
    base = run["cell"].config["stream"]["base_time_micros"]
    first_full, last = (max(at for end, at in zip(ends, run["arrivals"])
                            if end == base + t)
                        for t in (WIDTH, 40_000_000))
    counts = nexmark_q5_counts.rows(
        run["cell"].reference_stream(run["seed"], run["t_end"]),
        run["t_end"])
    sizes = dict(zip(*np.unique(counts[:, 0], return_counts=True)))
    assert [_bucket_rows(sizes[base + (i + 1) * SLIDE])
            for i in range(5)] == [4096, 16384, 16384, 16384, 16384]
    later = [n for end, n in sizes.items() if end > base + WIDTH]
    assert len(later) == 15 and len(set(later)) >= 5, sizes
    assert {_bucket_rows(n) for n in later} == {16384}
    assert any(t <= first_full for t in COMPILES)  # the listener hears
    late = [t for t in COMPILES if first_full < t <= last]
    assert late == [], f"{len(late)} compiles after the first full window"
    assert run["counters"]["state_grows"] == 0


# -- a stream made by hand through the configuration's own query -----------


def _run_bids(ts, auction, price):
    """The configuration's query over a ``nexmark`` table that holds just
    these bids; the sink's
    rows as ``(window_end, row_number, auction, price)`` tuples, sorted."""
    from arroyo_tpu.connectors.memory import clear_sink, sink_output
    from arroyo_tpu.engine.engine import LocalRunner
    from arroyo_tpu.sql import plan_sql
    from arroyo_tpu.sql.schema_provider import SchemaProvider, nexmark_table
    from arroyo_tpu.types import Batch

    cell = spec.load_cell(CELL, rehearsal=True)
    with open(f"{spec.BENCH_DIR}/{cell.config['query']}") as f:
        query = f.read()
    table = nexmark_table({})
    table.connector, table.default_lateness_micros = "memory", 0
    table.config = {"batches": [Batch(np.asarray(ts, np.int64), {
        "event_type": np.full(len(ts), 2, np.int8),
        "bid_auction": np.asarray(auction, np.int64),
        "bid_price": np.asarray(price, np.int64)})]}
    provider = SchemaProvider()
    provider.tables["nexmark"] = table
    sink = cell.config["sink"]
    clear_sink(sink)
    try:
        LocalRunner(plan_sql(query, provider)).run()
        out = Batch.concat(sink_output(sink))
    finally:
        clear_sink(sink)
    return sorted(zip((out.timestamp + 1).tolist(),
                      out.columns["row_number"].tolist(),
                      out.columns["auction"].tolist(),
                      out.columns["price"].tolist()))


def test_a_window_with_fewer_than_three_auctions():
    """Two auctions in the first slide, a third in the second: the windows
    that hold two give two rows, numbered 1 and 2."""
    s = 1_000_000
    rows = _run_bids([0, 1, 2, 3 * s], [11, 12, 11, 13], [10, 20, 5, 7])
    by_end = {}
    for end, *row in rows:
        by_end.setdefault(end, []).append(tuple(row))
    assert sorted(by_end) == [2 * s * i for i in range(1, 7)]
    assert by_end[2 * s] == [(1, 12, 20.0), (2, 11, 15.0)]
    for end in (4 * s, 6 * s, 8 * s, 10 * s):
        assert by_end[end] == [(1, 12, 20.0), (2, 11, 15.0), (3, 13, 7.0)]
    assert by_end[12 * s] == [(1, 13, 7.0)]


def test_a_tie_inside_the_first_three():
    """Auctions 21 and 22 tie for the first place, above 23 and 24: both
    are in the rows with the same sum, so the multiset the cell compares
    holds whichever is numbered 1.  The rule (``assumed.ties``): equal sums
    keep the order of the rows as the aggregate fired them (the order of
    the keys' slots in its state), at the cut too: the earlier row stays."""
    from arroyo_tpu.engine.operators_window import _apply_top_n
    from arroyo_tpu.types import Batch

    rows = _run_bids([0, 1, 2, 3, 4, 5], [22, 21, 23, 24, 21, 22],
                     [30, 30, 9, 8, 5, 5])
    first = [row for end, *row in rows if end == 2_000_000]
    assert [r[0] for r in first] == [1, 2, 3] and first[2] == [3, 23, 9.0]
    assert sorted(r[1:] for r in first[:2]) == [[21, 35.0], [22, 35.0]]
    assert len(rows) == 15  # the same three out of all five windows
    # the selection alone, on both of its paths (the host's under 512 rows)
    for n in (8, 600):
        price = np.full(n, 1.0)
        price[[5, 2, 7, 3]] = [9.0, 9.0, 4.0, 4.0]
        fired = Batch(np.full(n, 1_999_999, np.int64), {
            "auction": np.arange(n), "price": price,
            "window_end": np.full(n, 2_000_000, np.int64)})
        out = _apply_top_n(fired, ("window_end",), "price", 3, "row_number")
        assert out.columns["auction"].tolist() == [2, 3, 5]
        assert out.columns["row_number"].tolist() == [1, 3, 2]


# -- the reference's own stream --------------------------------------------


@pytest.mark.parametrize("batch_size", [4096, 8192])
@pytest.mark.parametrize("seed", [0, 2_147_483_999])
def test_reference_bids_equal_the_connectors(seed, batch_size):
    """``references/nexmark_topn_price.py`` brings the bid's price itself
    (``nexmark_gen`` throws it away): its ``(ts, auction, price)`` equal the
    program's source bit for bit, and its ``(ts, auction)`` the yardstick's
    generator, cut at the same event time."""
    from arroyo_tpu.connectors.nexmark import (EVENT_BID, NexmarkConfig,
                                               NexmarkGenerator, make_splits)

    base, rate, n = 1_700_000_000_000_000, 20_000, 60_000
    before = base + 2_500_000  # inside the last batch
    stream = dict(seed=seed, n_events=n, batch_size=batch_size,
                  base_time_micros=base, event_rate=rate,
                  before_micros=before)
    cfg = NexmarkConfig(event_rate=rate, num_events=n, seed=seed,
                        generate_strings=False)
    theirs = NexmarkGenerator(cfg, base, *make_splits(cfg, base, 1)[0],
                              seed=seed)
    theirs.set_rate(rate, 1)
    gen = nexmark_gen.batches(families=("bid",), **stream)
    total = 0
    for ts, auction, price in reference.bids(**stream):
        batch, _ = theirs.next_batch(batch_size)
        keep = ((batch.columns["event_type"] == EVENT_BID)
                & (batch.timestamp < before))
        np.testing.assert_array_equal(ts, batch.timestamp[keep])
        np.testing.assert_array_equal(auction,
                                      batch.columns["bid_auction"][keep])
        np.testing.assert_array_equal(price,
                                      batch.columns["bid_price"][keep])
        assert price.dtype == np.int64 and (price >= 100).all()
        mine = next(gen)
        is_bid = mine["event_type"] == nexmark_gen.BID
        np.testing.assert_array_equal(ts, mine["ts"][is_bid])
        np.testing.assert_array_equal(auction, mine["bid_auction"][is_bid])
        total += len(ts)
    assert next(gen, None) is None and not theirs.has_next
    assert 40_000 < total < n * 46 // 50


@pytest.mark.parametrize("fault", ["replay_batch", "drop_half_of_batch"])
def test_reference_faults_cut_as_the_generators(fault):
    """A faulted batch of ``bids`` holds the rows that the same fault
    leaves in ``nexmark_gen.batches``."""
    base = 1_700_000_000_000_000
    stream = dict(seed=5, n_events=40_000, batch_size=8192,
                  base_time_micros=base, event_rate=20_000,
                  before_micros=base + 10**12)
    mine = list(reference.bids(**stream, **{fault: 2}))
    gens = list(nexmark_gen.batches(families=("bid",), **stream,
                                    **{fault: 2}))
    assert len(mine) == len(gens) == 5 + (fault == "replay_batch")
    for (ts, auction, _price), theirs in zip(mine, gens):
        is_bid = theirs["event_type"] == nexmark_gen.BID
        np.testing.assert_array_equal(ts, theirs["ts"][is_bid])
        np.testing.assert_array_equal(auction, theirs["bid_auction"][is_bid])
