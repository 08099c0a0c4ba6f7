"""C++ host library vs numpy fallback parity (bit-exact where required).

The native library carries sharding-critical semantics (splitmix64, key
ranges), so these tests compare it directly against the pure-numpy
reference implementations on randomized inputs.
"""

import numpy as np
import pytest

from arroyo_tpu import native
from arroyo_tpu.types import _py_hash_u64, server_for_hash_array


@pytest.fixture(scope="module")
def rng():
    return np.random.default_rng(42)


def test_native_loaded():
    # the image ships g++, so the library must build and load
    assert native.HAVE_NATIVE


def test_hash_u64_bit_exact(rng):
    x = rng.integers(0, 2**63, 100_000, dtype=np.uint64)
    x[:5] = [0, 1, 2**64 - 1, 2**63, 12345]
    np.testing.assert_array_equal(native.hash_u64(x), _py_hash_u64(x))


def test_hash_combine_bit_exact(rng):
    a = rng.integers(0, 2**63, 50_000, dtype=np.uint64)
    h = rng.integers(0, 2**63, 50_000, dtype=np.uint64)
    with np.errstate(over="ignore"):
        want = _py_hash_u64(a * np.uint64(31) + h)
    np.testing.assert_array_equal(native.hash_combine(a, h), want)


@pytest.mark.parametrize("n_parts", [1, 2, 3, 7, 16])
def test_partition_route_matches_reference(rng, n_parts):
    kh = rng.integers(0, 2**64, 20_000, dtype=np.uint64)
    kh[:3] = [0, 2**64 - 1, 2**63]
    dest, order, bounds = native.partition_route(kh, n_parts)
    np.testing.assert_array_equal(
        dest, server_for_hash_array(kh, n_parts).astype(np.int32))
    # order is a permutation, stable within each destination
    assert sorted(order) == list(range(len(kh)))
    for p in range(n_parts):
        seg = order[bounds[p]:bounds[p + 1]]
        assert (dest[seg] == p).all()
        assert (np.diff(seg) > 0).all()  # stability = ascending row index
    assert bounds[0] == 0 and bounds[-1] == len(kh)


def test_assign_bins_matches_numpy(rng):
    ts = rng.integers(0, 10**9, 30_000).astype(np.int64)
    slide, ring, thr = 1_000_000, 16, 250
    bins, live, n_live, lo, hi = native.assign_bins(ts, slide, ring, thr)
    abs_bins = ts // slide
    want_live = abs_bins >= thr
    np.testing.assert_array_equal(live, want_live)
    np.testing.assert_array_equal(bins, (abs_bins % ring).astype(np.int32))
    assert n_live == int(want_live.sum())
    assert lo == int(abs_bins[want_live].min())
    assert hi == int(abs_bins[want_live].max())


def test_assign_bins_negative_ts_floor_semantics():
    ts = np.array([-1, -1_000_000, -1_500_000, 0, 999_999], dtype=np.int64)
    bins, live, n_live, lo, hi = native.assign_bins(ts, 1_000_000, 8, None)
    abs_bins = ts // 1_000_000  # numpy floors
    np.testing.assert_array_equal(bins, (abs_bins % 8).astype(np.int32))
    assert lo == int(abs_bins.min()) and hi == int(abs_bins.max())


def test_assign_bins_all_dead():
    ts = np.arange(5, dtype=np.int64)
    bins, live, n_live, lo, hi = native.assign_bins(ts, 1, 8, 100)
    assert n_live == 0 and lo is None and hi is None


def test_collector_split_parity(rng):
    """partition_route drives the collector; segments must reassemble the
    batch exactly."""
    kh = rng.integers(0, 2**64, 5_000, dtype=np.uint64)
    for n in (2, 5):
        _, order, bounds = native.partition_route(kh, n)
        pieces = [order[bounds[p]:bounds[p + 1]] for p in range(n)]
        got = np.concatenate([kh[p] for p in pieces])
        assert sorted(got.tolist()) == sorted(kh.tolist())


def test_native_dir_matches_sorted_directory(rng):
    """NativeDir.insert agrees with the numpy sorted-array directory on
    slots, new-key order, and lookups across growth."""
    from arroyo_tpu.native import NativeDir

    d = NativeDir(16)
    # reference model
    seen = {}
    next_slot = 0
    for round_ in range(5):
        kh = rng.integers(0, 2**64, 3_000, dtype=np.uint64)
        kh = kh[rng.integers(0, 1_000, 3_000)]  # heavy duplicates
        slots, new_keys = d.insert(kh, next_slot)
        expect_new = []
        expect_slots = []
        for k in kh.tolist():
            if k not in seen:
                seen[k] = next_slot + len(expect_new)
                expect_new.append(k)
            expect_slots.append(seen[k])
        next_slot += len(expect_new)
        assert new_keys.tolist() == expect_new
        assert slots.tolist() == expect_slots
    probe = np.array(list(seen)[:100] + [1, 2, 3], dtype=np.uint64)
    got = d.lookup(probe)
    want = np.array([seen.get(int(k), -1) for k in probe], dtype=np.int64)
    np.testing.assert_array_equal(got, want)


def test_agg_cells_matches_preaggregate(rng):
    """Native (slot,bin)-cell aggregation is a lossless reordering of the
    lexsort+reduceat preaggregate path for every channel kind."""
    from arroyo_tpu.native import agg_cells
    from arroyo_tpu.ops.keyed_bins import preaggregate

    n = 4_000
    ring = 16
    slots = rng.integers(0, 200, n).astype(np.int64)
    bins = rng.integers(0, ring, n).astype(np.int32)
    kinds = ("sum", "min", "max", "count")
    vals = rng.random((len(kinds), n)).astype(np.float32)
    live = (rng.random(n) < 0.8)

    cs, cb, cc, cv = agg_cells(slots, bins, live, ring, vals, kinds)
    idx = live.nonzero()[0]
    es, eb, ec, ev = preaggregate(slots[idx], bins[idx], kinds, vals[:, idx])

    # same cells, possibly different order: compare as sorted tuples
    def canon(s, b, c, v):
        order = np.lexsort((b, s))
        return (s[order], b[order], c[order], v[:, order])

    cs2, cb2, cc2, cv2 = canon(cs, cb, cc, cv)
    es2, eb2, ec2, ev2 = canon(es, eb, ec, ev)
    np.testing.assert_array_equal(cs2, es2)
    np.testing.assert_array_equal(cb2, eb2)
    np.testing.assert_array_equal(cc2, ec2)
    np.testing.assert_allclose(cv2, ev2, rtol=1e-5)


def test_projection_pushdown_output_identical():
    """The planner-injected source projection must not change query
    results — only skip generating unused columns."""
    import json

    from arroyo_tpu.connectors.memory import clear_sink, sink_output
    from arroyo_tpu.engine.engine import LocalRunner
    from arroyo_tpu.sql import plan_sql
    from arroyo_tpu.types import Batch

    sql = """
    CREATE TABLE nexmark WITH (
      connector = 'nexmark', event_rate = '1000000', num_events = '20000',
      rate_limited = 'false', batch_size = '4096',
      base_time_micros = '1600000000000000'
    );
    SELECT bid.auction as auction,
           HOP(INTERVAL '2' SECOND, INTERVAL '10' SECOND) as window,
           count(*) AS num
    FROM nexmark WHERE bid is not null GROUP BY 1, 2
    """

    def run(prog):
        clear_sink("results")
        LocalRunner(prog).run()
        rows = Batch.concat(sink_output("results"))
        return sorted(zip(rows.columns["auction"].tolist(),
                          rows.columns["window_start"].tolist(),
                          rows.columns["num"].tolist()))

    prog = plan_sql(sql)
    src_cfg = prog.sources()[0].operator.spec.config
    # event time rides the batch timestamp, so only the key + presence
    # columns are needed
    assert src_cfg.get("projection") == ["bid_auction", "event_type"]
    with_pushdown = run(prog)

    prog_full = plan_sql(sql)
    prog_full.sources()[0].operator.spec.config.pop("projection")
    without = run(prog_full)
    assert with_pushdown == without and len(with_pushdown) > 0


def test_projection_pushdown_struct_and_join_keep_columns():
    """A bare struct reference keeps the whole struct's columns; a join
    records both sides' column usage (reviewer-found leaks)."""
    from arroyo_tpu.sql import plan_sql

    # bare struct passthrough: bid's fields must survive pushdown
    prog = plan_sql("""
    CREATE TABLE nexmark WITH (connector = 'nexmark', num_events = '100',
                               rate_limited = 'false');
    SELECT bid FROM nexmark WHERE bid is not null
    """)
    proj = prog.sources()[0].operator.spec.config.get("projection")
    assert proj is not None
    assert {"bid_auction", "bid_bidder", "bid_price",
            "bid_datetime"} <= set(proj)

    # join: columns used only in SELECT resolve against the joined schema
    # and must still reach each side's source projection
    prog2 = plan_sql("""
    CREATE TABLE nexmark WITH (connector = 'nexmark', num_events = '100',
                               rate_limited = 'false');
    SELECT P.name as name, A.seller as seller
    FROM (SELECT person.name as name, person.id as id,
                 TUMBLE(INTERVAL '10' SECOND) as window
          FROM nexmark WHERE person is not null GROUP BY 1, 2, 3) P
    JOIN (SELECT auction.seller as seller,
                 TUMBLE(INTERVAL '10' SECOND) as window
          FROM nexmark WHERE auction is not null GROUP BY 1, 2) A
    ON P.id = A.seller and P.window = A.window
    """)
    projs = [n.operator.spec.config.get("projection")
             for n in prog2.sources()]
    assert any(p and "person_name" in p for p in projs)
    assert any(p and "auction_seller" in p for p in projs)


def test_loader_ties_binary_to_its_sources(tmp_path):
    """The library's file name carries a hash of host_ops.cpp + Makefile,
    so a binary built from other sources — a stale one copied along with
    the tree — is never the file the loader opens."""
    import os
    import shutil

    src = tmp_path / "native"
    shutil.copytree(os.path.join(os.path.dirname(native.__file__),
                                 "..", "..", "native"), src)
    here = native.library_path()
    assert here is not None and os.path.exists(here)
    assert native.source_hash() in os.path.basename(here)
    # the loaded library is exactly the one named after the committed sources
    assert native.library_path(str(src), os.path.dirname(here)) == here
    with open(src / "src" / "host_ops.cpp", "a") as f:
        f.write("\n// edited\n")
    edited = native.library_path(str(src), os.path.dirname(here))
    assert edited != here and not os.path.exists(edited)
    with open(src / "Makefile", "a") as f:
        f.write("\n# edited\n")
    assert native.library_path(str(src), os.path.dirname(here)) != edited
    # no sources (an installed package without native/): nothing to load
    assert native.library_path(str(tmp_path / "missing")) is None
