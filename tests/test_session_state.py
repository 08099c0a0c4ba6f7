"""Device-resident session window state (state/session_state.py, PR 19).

The correctness spine the ISSUE names:

- sanitized device-vs-legacy parity: identical rows out of the session
  operator under both state layouts, on fuzzed multi-batch streams;
- the max-session clamp falls back per key to the authoritative host
  merge — bit-for-bit with legacy (the union-span>MAX condition is
  EXACTLY the legacy clamp condition, ops/session.py docstring);
- state stays bounded under session churn (expire mask-compresses rows
  out; nothing leaks);
- checkpoint interchange: both layouts snapshot as the same KEYED
  ``[(time, key, sessions)]`` entries, so epochs restore legacy->device
  and device->legacy, and rescale's key-range entry filter applies
  (2 -> 3 split emulated at the table level + a full engine
  crash/restore flip in both directions);
- the vectorized interval-union kernel agrees with a brute-force
  oracle on fuzzed inputs;
- the accumulator fire (each session's COUNT / SUM / MIN / MAX / AVG
  partials folded at the merge) emits exactly the batches the buffer
  fire's re-read does, under both layouts and both union scans, folds
  through the clamp's per-key paths, and takes over an epoch the buffer
  path wrote; flatten, COUNT DISTINCT, UDAF and string MIN / MAX plans
  keep the buffer.
"""

import asyncio
import json
import os

import numpy as np
import pytest

from arroyo_tpu import AggKind, AggSpec, Batch, SessionWindow, Stream
from arroyo_tpu.connectors.memory import clear_sink, sink_output
from arroyo_tpu.engine.engine import Engine, LocalRunner
from arroyo_tpu.obs import perf
from arroyo_tpu.state.session_state import SessionRunState
from arroyo_tpu.state.tables import KeyedState
from arroyo_tpu.types import StopMode

MS = 1_000
SEC = 1_000_000

AGGS = [AggSpec(AggKind.COUNT, None, "cnt"),
        AggSpec(AggKind.SUM, "v", "total"),
        AggSpec(AggKind.MIN, "v", "lo"),
        AggSpec(AggKind.MAX, "v", "hi"),
        AggSpec(AggKind.AVG, "v", "mean")]


def _run_sessions(batches, mode, gap=300 * MS, aggs=AGGS, sink="ss_out",
                  lateness=0):
    """Run the session pipeline with ARROYO_SESSION_STATE=mode; the
    sanitizer is armed by conftest for every run."""
    prev = os.environ.get("ARROYO_SESSION_STATE")
    os.environ["ARROYO_SESSION_STATE"] = mode
    try:
        clear_sink(sink)
        prog = (Stream.source("memory", {"batches": batches})
                .watermark(max_lateness_micros=lateness)
                .key_by("k")
                .window(SessionWindow(gap), aggs)
                .sink("memory", {"name": sink}))
        LocalRunner(prog).run()
        outs = sink_output(sink)
        return Batch.concat(outs) if outs else None
    finally:
        if prev is None:
            os.environ.pop("ARROYO_SESSION_STATE", None)
        else:
            os.environ["ARROYO_SESSION_STATE"] = prev


def _rows(out):
    if out is None:
        return []
    names = sorted(out.columns)
    return sorted(
        tuple(round(float(out.columns[c][i]), 9) for c in names)
        for i in range(len(out)))


def _session_batches(rng, n_batches=4, n=1200, n_keys=40, span=4 * SEC):
    """Bursty per-key event times so sessions both merge and close."""
    batches = []
    t0 = 0
    for _ in range(n_batches):
        ts = np.sort(rng.integers(t0, t0 + span, n)).astype(np.int64)
        batches.append(Batch(ts, {
            "k": rng.integers(0, n_keys, n).astype(np.int64),
            "v": rng.integers(1, 100, n).astype(np.int64)}))
        t0 += span + rng.integers(0, SEC)
    return batches


def test_device_vs_legacy_parity_fuzz(rng):
    """The acceptance spine: identical rows out of the session operator
    under device sorted-run state vs the legacy per-key dict path, with
    the sanitizer armed, on a fuzzed multi-batch stream."""
    batches = _session_batches(rng)
    dev = _run_sessions(batches, "device")
    leg = _run_sessions(batches, "legacy")
    assert dev is not None and leg is not None
    assert _rows(dev) == _rows(leg)
    assert len(dev) > 50  # non-vacuous: real session churn happened


def test_device_parity_single_key_dense(rng):
    """One hot key with dense timestamps: maximal interval-merge work
    per dispatch (every batch touches the same resident run)."""
    batches = []
    t0 = 0
    for _ in range(3):
        ts = np.sort(rng.integers(t0, t0 + 2 * SEC, 500)).astype(np.int64)
        batches.append(Batch(ts, {"k": np.zeros(500, np.int64),
                                  "v": np.ones(500, np.int64)}))
        t0 += 3 * SEC  # gap > session gap: prior session closes
    dev = _run_sessions(batches, "device")
    leg = _run_sessions(batches, "legacy")
    assert _rows(dev) == _rows(leg)
    assert len(dev) >= 3


def test_clamp_fallback_parity_and_counted(rng):
    """Events chaining past MAX_SESSION_SIZE route through the per-key
    host fallback (the union-span>MAX flag) and must match legacy
    bit-for-bit; the fallback is COUNTED (session_host_merge_rows), so
    a config5 triage can see sessions riding host."""
    from arroyo_tpu.engine.operators_window import MAX_SESSION_SIZE_MICROS

    MAX = MAX_SESSION_SIZE_MICROS
    ts1 = np.arange(0, MAX - 5 * SEC + 1, 9 * SEC, dtype=np.int64)
    ts2 = np.array([MAX - 1, MAX + 2], dtype=np.int64)
    batches = [
        Batch(ts1, {"k": np.full(len(ts1), 7, np.int64),
                    "v": np.ones(len(ts1), np.int64)}),
        Batch(ts2, {"k": np.full(2, 7, np.int64),
                    "v": np.ones(2, np.int64)})]
    before = perf.counter("session_host_merge_rows")
    dev = _run_sessions(batches, "device", gap=10 * SEC)
    host_rows = perf.counter("session_host_merge_rows") - before
    leg = _run_sessions(batches, "legacy", gap=10 * SEC)
    assert _rows(dev) == _rows(leg)
    assert host_rows > 0, \
        "clamp chain must exercise the counted host fallback"


# ---------------------------------------------------------------------------
# table-level: union oracle, bounded churn, snapshot interchange
# ---------------------------------------------------------------------------


def _oracle_merge(sessions, st, en):
    """Brute-force insert [st, en) into a sorted interval list, merging
    on touch-or-overlap (the union kernel's st <= prev_en rule)."""
    sessions = sorted(sessions + [(st, en)])
    out = [sessions[0]]
    for s, e in sessions[1:]:
        ps, pe = out[-1]
        if s <= pe:
            out[-1] = (ps, max(pe, e))
        else:
            out.append((s, e))
    return out


def test_merge_intervals_matches_oracle_fuzz(rng):
    state = SessionRunState(n_partitions=8, max_span=1 << 62)
    oracle = {}
    gap = 50
    for _ in range(30):
        nk = int(rng.integers(1, 12))
        keys = rng.choice(
            np.arange(1, 25, dtype=np.uint64), nk, replace=False)
        ikh, ist, ien, itm = [], [], [], []
        for k in np.sort(keys):
            for _ in range(int(rng.integers(1, 5))):
                t = int(rng.integers(0, 10_000))
                ikh.append(k)
                ist.append(t)
                ien.append(t + gap)
                itm.append(t)
        order = np.lexsort((np.array(ist), np.array(ikh, dtype=np.uint64)))
        ikh = np.array(ikh, dtype=np.uint64)[order]
        ist = np.array(ist, dtype=np.int64)[order]
        ien = np.array(ien, dtype=np.int64)[order]
        itm = np.array(itm, dtype=np.int64)[order]
        flagged = state.merge_intervals(ikh, ist, ien, itm)
        assert len(flagged) == 0
        for k, s, e in zip(ikh.tolist(), ist.tolist(), ien.tolist()):
            oracle[k] = _oracle_merge(oracle.get(k, []), s, e)
    for k, expect in oracle.items():
        assert state.get(np.uint64(k)) == expect, k
    assert state.n_keys() == len(oracle)


def test_expire_fires_and_stays_bounded(rng):
    """Session churn: repeated merge + expire cycles mask-compress rows
    out; fired sessions match the oracle and the table drains to empty
    (the state_bounded contract)."""
    state = SessionRunState(n_partitions=4, max_span=1 << 62)
    n_fired = 0
    live = {}
    t0 = 0
    for _round in range(12):
        keys = np.sort(rng.choice(
            np.arange(1, 30, dtype=np.uint64), 8, replace=False))
        st = np.array([t0 + int(rng.integers(0, 50)) for _ in keys],
                      dtype=np.int64)
        ikh = keys
        ien = st + 40
        state.merge_intervals(ikh, st, ien, st.copy())
        for k, s, e in zip(ikh.tolist(), st.tolist(), ien.tolist()):
            live[k] = _oracle_merge(live.get(k, []), s, e)
        t0 += 200  # next round starts past every open end
        fk, fs, fe, _fch, removed = state.expire(t0)
        got = sorted(zip(fk.tolist(), fs.tolist(), fe.tolist()))
        expect = sorted((k, s, e) for k, ivs in live.items()
                        for s, e in ivs if e <= t0)
        assert got == expect
        for k in list(live):
            live[k] = [iv for iv in live[k] if iv[1] > t0]
            if not live[k]:
                del live[k]
                assert k in [int(r) for r in removed]
        n_fired += len(got)
    assert not live
    assert len(state) == 0 and state.stats()["rows"] == 0
    assert n_fired >= 12 * 8  # every inserted session fired exactly once


def test_snapshot_interchange_both_directions(rng):
    """Both layouts emit the same KEYED [(time, key, sessions)] entry
    form: device snapshot restores into the legacy dict table and back,
    preserving every key's sessions and timestamps."""
    state = SessionRunState(n_partitions=8, max_span=1 << 62)
    for k in range(1, 20):
        kh = np.uint64(k * 1031)
        n = int(rng.integers(1, 4))
        sts = np.sort(rng.choice(
            np.arange(0, 50, dtype=np.int64) * 100, n, replace=False))
        state.merge_intervals(
            np.full(n, kh, dtype=np.uint64), sts.astype(np.int64),
            (sts + 60).astype(np.int64),
            np.full(n, int(sts.max()), np.int64))
    snap = state.snapshot()

    legacy = KeyedState()
    legacy.restore(snap)
    assert legacy.n_keys() == state.n_keys()
    for t, k, v in snap:
        assert legacy.get(k) == state.get(k)
        assert legacy.get_time(k) == state.get_time(k)

    back = SessionRunState(n_partitions=2, max_span=1 << 62)
    back.restore(legacy.snapshot())
    assert back.n_keys() == state.n_keys()
    for _t, k, _v in snap:
        assert back.get(k) == state.get(k)
        assert back.get_time(k) == state.get_time(k)


def test_rescale_entry_filter_2_to_3(rng):
    """Rescale restores each subtask from a key-range FILTER of the
    snapshot entries (state/backend.py _deserialize_rows): emulate the
    2 -> 3 split at the table level — three disjoint filtered restores
    must partition the key set exactly, with no key owned twice."""
    state = SessionRunState(n_partitions=8, max_span=1 << 62)
    keys = rng.choice(np.arange(1, 1 << 20, dtype=np.uint64), 64,
                      replace=False)
    for kh in keys:
        t = int(rng.integers(0, 1000))
        state.merge_intervals(
            np.array([kh], dtype=np.uint64),
            np.array([t], dtype=np.int64),
            np.array([t + 10], dtype=np.int64),
            np.array([t], dtype=np.int64))
    snap = state.snapshot()
    hi = 1 << 20
    cuts = [0, hi // 3, 2 * hi // 3, hi]
    shards = []
    for i in range(3):
        part = SessionRunState(n_partitions=4, max_span=1 << 62)
        part.restore([(t, k, v) for (t, k, v) in snap
                      if cuts[i] <= int(k) < cuts[i + 1]])
        shards.append(part)
    owned = [set(int(k) for k, _v in s.items()) for s in shards]
    assert not (owned[0] & owned[1]) and not (owned[1] & owned[2]) \
        and not (owned[0] & owned[2])
    assert owned[0] | owned[1] | owned[2] == set(int(k) for k in keys)
    for s in shards:
        for k, sessions in s.items():
            assert sessions == state.get(np.uint64(k))


# ---------------------------------------------------------------------------
# full engine: checkpoint under one layout, restore under the other
# ---------------------------------------------------------------------------


def _mixed_bucket(c):
    """The first batch's key column is int64; later ones are float64
    with a NULL (NaN) key every fifth event, as the JSON decoders type a
    nullable integer column by the batch."""
    ctr = c["counter"]
    b = ctr % 7
    if int(ctr.min()) >= 100:
        b = np.where(ctr % 5 == 0, np.nan, b.astype(np.float64))
    _MIXED_SEEN.append(int(ctr.max()))
    return {"counter": ctr, "bucket": b}


_MIXED_SEEN = []


def _session_restore_flip(tmp_path, first_mode, second_mode,
                          first_buffer=False, mixed_key=False):
    """Crash a session job after epoch 1 under ``first_mode`` (with the
    row buffer forced where ``first_buffer``: the form epochs took
    before the accumulators), restore under ``second_mode``, and hold
    the output to exactly-once.  ``mixed_key``: the key column turns
    from int64 to float64 with NULLs after the first batch, and the
    checkpoint waits until the NULL key's session is live."""
    from arroyo_tpu.engine import operators_window

    url = f"file://{tmp_path}/ckpt"
    out_path = f"{tmp_path}/out.jsonl"
    job = f"session-flip-{first_mode}-{second_mode}"
    total = 2000
    _MIXED_SEEN.clear()

    def build():
        bucket = (_mixed_bucket if mixed_key else
                  lambda c: {"counter": c["counter"],
                             "bucket": c["counter"] % 7})
        return (Stream.source("impulse", {
                    "event_rate": 30_000.0, "message_count": total,
                    "event_time_interval_micros": 1000, "batch_size": 100})
                .watermark(max_lateness_micros=0)
                .map(bucket, name="b")
                .key_by("bucket")
                .window(SessionWindow(20 * MS),
                        [AggSpec(AggKind.COUNT, None, "cnt"),
                         AggSpec(AggKind.SUM, "counter", "sum_c")])
                .sink("single_file", {"path": out_path}))

    async def run_with_crash():
        eng = Engine.for_local(build(), job, checkpoint_url=url)
        running = eng.start()
        await asyncio.sleep(0.04)
        while mixed_key and max(_MIXED_SEEN, default=0) < 400:
            await asyncio.sleep(0.001)
        await running.checkpoint(1)
        assert await running.wait_for_checkpoint(1)
        await running.stop(StopMode.IMMEDIATE)
        try:
            await running.join()
        except RuntimeError:
            pass

    async def run_restored():
        eng = Engine.for_local(build(), job, checkpoint_url=url,
                               restore_epoch=1)
        running = eng.start()
        await running.join()

    prev = os.environ.get("ARROYO_SESSION_STATE")
    try:
        os.environ["ARROYO_SESSION_STATE"] = first_mode
        with pytest.MonkeyPatch.context() as mp:
            if first_buffer:
                mp.setattr(operators_window, "session_acc_channels",
                           lambda aggs, flatten: None)
            asyncio.run(run_with_crash())
        os.environ["ARROYO_SESSION_STATE"] = second_mode
        asyncio.run(run_restored())
    finally:
        if prev is None:
            os.environ.pop("ARROYO_SESSION_STATE", None)
        else:
            os.environ["ARROYO_SESSION_STATE"] = prev

    rows = [json.loads(l) for l in open(out_path)]
    if mixed_key:  # the NULL key's sessions came out, with a NULL key
        assert any(r["bucket"] != r["bucket"] for r in rows)
    # exactly-once across the layout flip: every event counted once
    assert sum(r["cnt"] for r in rows) == total
    assert sum(r["sum_c"] for r in rows) == total * (total - 1) // 2
    seen = set()
    for r in rows:
        key = (r["bucket"], r["window_start"])
        assert key not in seen, f"duplicate session emission {key}"
        seen.add(key)


def test_checkpoint_device_then_restore_legacy(tmp_path):
    """Open sessions checkpointed by the sorted-run layout restore into
    the legacy dict layout exactly-once (rollback interchange)."""
    _session_restore_flip(tmp_path, "device", "legacy")


def test_checkpoint_legacy_then_restore_device(tmp_path):
    """Legacy-epoch checkpoints upgrade in place into the sorted-run
    layout on restore (forward interchange: the get_session_state
    in-place upgrade path)."""
    _session_restore_flip(tmp_path, "legacy", "device")


# ---------------------------------------------------------------------------
# the accumulator fire against the buffer fire it replaces
# ---------------------------------------------------------------------------

NULL_AGGS = [AggSpec(AggKind.COUNT, None, "cnt"),
             AggSpec(AggKind.COUNT, "v", "nv"),
             AggSpec(AggKind.SUM, "v", "total"),
             AggSpec(AggKind.MIN, "v", "lo"),
             AggSpec(AggKind.MAX, "v", "hi"),
             AggSpec(AggKind.AVG, "v", "mean")]


def _force_buffer(mp):
    """Make every session plan keep the row buffer (the fire as it was
    before the accumulators): the reference side of the parity tests."""
    from arroyo_tpu.engine import operators_window

    mp.setattr(operators_window, "session_acc_channels",
               lambda aggs, flatten: None)


def _null_batches(rng, n_batches=4, n=1500, n_keys=40, span=3 * SEC):
    """Integer-valued f64 values (sums exact in any order), a fifth of
    them NULL, and key 0 NULL throughout: all-NULL sessions."""
    batches = []
    t0 = 0
    for _ in range(n_batches):
        ts = rng.integers(t0, t0 + span, n).astype(np.int64)
        k = rng.integers(0, n_keys, n).astype(np.int64)
        v = rng.integers(-50, 100, n).astype(np.float64)
        v[(rng.random(n) < 0.2) | (k == 0)] = np.nan
        batches.append(Batch(ts, {"k": k, "v": v}))
        t0 += span + int(rng.integers(0, SEC))
    return batches


def _assert_same_batch(got, want):
    assert list(got.columns) == list(want.columns)
    np.testing.assert_array_equal(got.timestamp, want.timestamp)
    np.testing.assert_array_equal(got.key_hash, want.key_hash)
    assert got.key_cols == want.key_cols
    for c in want.columns:
        a, b = np.asarray(got.columns[c]), np.asarray(want.columns[c])
        assert a.dtype == b.dtype, c
        np.testing.assert_array_equal(a, b, err_msg=c)  # NaN == NaN


@pytest.mark.parametrize("mode,union", [("device", "off"), ("device", "on"),
                                        ("legacy", "off")])
def test_accumulator_fire_equals_the_buffer_fire(rng, monkeypatch, mode,
                                                 union):
    """The acceptance spine of the accumulator path: COUNT(*), COUNT(col),
    SUM, MIN, MAX and AVG over a NULL-able column, with an all-NULL
    session, batch for batch as the buffer fire's re-read emits them —
    the columns, their dtypes, the rows and their order — on the host
    and the jitted union, under both layouts."""
    monkeypatch.setenv("ARROYO_SESSION_DEVICE", union)
    monkeypatch.setenv("ARROYO_COALESCE", "0")  # a merge and fire a batch
    batches = _null_batches(rng)
    acc0, read0 = (perf.counter("session_acc_sessions"),
                   perf.counter("session_rows_read"))
    acc = _run_sessions(batches, mode, gap=200 * MS, aggs=NULL_AGGS)
    assert perf.counter("session_rows_read") == read0
    assert perf.counter("session_acc_sessions") - acc0 == len(acc)
    with pytest.MonkeyPatch.context() as mp:
        _force_buffer(mp)
        buf = _run_sessions(batches, mode, gap=200 * MS, aggs=NULL_AGGS)
    assert perf.counter("session_acc_sessions") - acc0 == len(acc)
    assert perf.counter("session_rows_read") > read0
    _assert_same_batch(acc, buf)
    assert len(acc) > 100 and int(acc.columns["cnt"].sum()) == 4 * 1500
    null_key = acc.columns["k"] == 0
    assert null_key.any() and np.isnan(acc.columns["total"][null_key]).all()
    assert (acc.columns["nv"][null_key] == 0).all()


@pytest.mark.parametrize("mode", ["device", "legacy"])
def test_clamp_paths_fold_the_partials(monkeypatch, mode):
    """A small max session size sends in-order chains through the clamp:
    the per-key interval sweep and the per-event path fold the partials
    as they extend and merge sessions (the device layout's flagged keys
    included, counted as host merge rows); the layouts agree and every
    event counts once."""
    from arroyo_tpu.engine import operators_window

    monkeypatch.setattr(operators_window, "MAX_SESSION_SIZE_MICROS", 2 * SEC)
    # batches merge one by one, so a resident chain crosses the clamp
    monkeypatch.setenv("ARROYO_COALESCE", "0")
    batches = []
    for b in range(4):
        ts = np.arange(b * 900 * MS, (b + 1) * 900 * MS, 30 * MS,
                       dtype=np.int64)
        n = len(ts)
        k = np.tile(np.arange(3, dtype=np.int64), n // 3 + 1)[:n]
        batches.append(Batch(ts, {"k": k,
                                  "v": (ts // MS % 97).astype(np.float64)}))
    before = perf.counter("session_host_merge_rows")
    got = _run_sessions(batches, mode, gap=200 * MS, aggs=NULL_AGGS)
    host_rows = perf.counter("session_host_merge_rows") - before
    other = _run_sessions(batches, "legacy" if mode == "device" else
                          "device", gap=200 * MS, aggs=NULL_AGGS)
    _assert_same_batch(got, other)
    n_events = sum(len(b) for b in batches)
    assert int(got.columns["cnt"].sum()) == n_events
    assert got.columns["total"].sum() == sum(
        float(b.columns["v"].sum()) for b in batches)
    # sessions longer than the clamp were cut: more rows than keys
    assert len(got) > 3
    assert (got.columns["window_end"] - got.columns["window_start"]
            <= 2 * SEC + 200 * MS).all()
    assert host_rows > 0


@pytest.mark.parametrize("second_mode", ["device", "legacy"])
def test_restore_of_a_buffer_epoch_rebuilds_the_partials(tmp_path,
                                                        monkeypatch,
                                                        second_mode):
    """An epoch written before the accumulators — ``(start, end)``
    sessions and the buffered rows they hold — restores by one rebuild
    of each live session's partials from its rows, exactly-once."""
    from arroyo_tpu.engine.operators_window import SessionWindowOperator

    rebuilt = []
    inner = SessionWindowOperator._rebuild_from_rows

    def spy(self, rows, *layout):
        rebuilt.append(len(rows) if rows is not None else 0)
        inner(self, rows, *layout)
        assert len(self.buffer) == 0

    monkeypatch.setattr(SessionWindowOperator, "_rebuild_from_rows", spy)
    _session_restore_flip(tmp_path, second_mode, second_mode,
                          first_buffer=True)
    assert rebuilt and rebuilt[0] > 0


@pytest.mark.parametrize("aggs,values", [
    ([], "v"),
    ([AggSpec(AggKind.COUNT_DISTINCT, "v", "dv")], "v"),
    ([AggSpec(AggKind.UDAF, "v", "med", fn=np.median)], "v"),
    ([AggSpec(AggKind.COUNT, None, "cnt"),
      AggSpec(AggKind.MIN, "name", "first_name")], "name"),
], ids=["flatten", "count_distinct", "udaf", "string_min"])
def test_buffer_path_keeps_the_plans_that_do_not_fold(rng, aggs, values):
    """Flatten, COUNT DISTINCT, a UDAF and string MIN keep the row
    buffer: their fires re-read rows and none comes off accumulators."""
    batches = _session_batches(rng, n_batches=2, n=400)
    for b in batches:
        b.columns["name"] = np.array([f"n{int(x)}" for x in b.columns["v"]],
                                     dtype=object)
    acc0, read0 = (perf.counter("session_acc_sessions"),
                   perf.counter("session_rows_read"))
    out = _run_sessions(batches, "device", aggs=aggs)
    assert out is not None and len(out) > 0
    assert perf.counter("session_acc_sessions") == acc0
    assert perf.counter("session_rows_read") > read0
    if not aggs:
        assert len(out) == 800  # flatten: every row out


@pytest.mark.parametrize("mode", ["device", "legacy"])
def test_the_clamp_cuts_no_event_on_either_path(monkeypatch, mode):
    """The rule both fires share at the max session size: the clamp
    stops a session's end from growing but never moves it back, so a
    late event that extends a session's start joins it with every event
    it holds.  The accumulator fire and the buffer fire's re-read then
    emit the same session, and every event counts once."""
    from arroyo_tpu.engine import operators_window

    monkeypatch.setattr(operators_window, "MAX_SESSION_SIZE_MICROS", 2 * SEC)
    monkeypatch.setenv("ARROYO_COALESCE", "0")
    gap = 200 * MS
    # key 7: a chain 0.3 s .. 2.1 s, a session [0.3 s, 2.3 s) at the
    # clamp; then a late event at 0.1 s: [0.1 s, 2.3 s) is longer than
    # the clamp, whose end 2.1 s would leave the event at 2.1 s out
    chain = np.arange(300 * MS, 2100 * MS + 1, 100 * MS, dtype=np.int64)
    other = np.arange(0, 2500 * MS, 250 * MS, dtype=np.int64)
    ts1 = np.concatenate([chain, other])
    k1 = np.concatenate([np.full(len(chain), 7), np.full(len(other), 3)])
    batches = [Batch(ts1, {"k": k1.astype(np.int64),
                           "v": (ts1 // MS).astype(np.float64)}),
               Batch(np.array([100 * MS], np.int64),
                     {"k": np.array([7], np.int64),
                      "v": np.array([100.0])})]
    acc = _run_sessions(batches, mode, gap=gap, aggs=NULL_AGGS,
                        lateness=5 * SEC)
    with pytest.MonkeyPatch.context() as mp:
        _force_buffer(mp)
        buf = _run_sessions(batches, mode, gap=gap, aggs=NULL_AGGS,
                            lateness=5 * SEC)
    _assert_same_batch(acc, buf)
    assert int(acc.columns["cnt"].sum()) == len(ts1) + 1
    seven = acc.columns["k"] == 7
    assert acc.columns["window_start"][seven].tolist() == [100 * MS]
    assert acc.columns["window_end"][seven].tolist() == [2300 * MS]
    assert acc.columns["cnt"][seven].tolist() == [len(chain) + 1]
    assert acc.columns["hi"][seven].tolist() == [2100.0]


@pytest.mark.parametrize("later", ["nan", "none"])
def test_key_channels_widen_with_their_values(later):
    """A key column's "first" channel takes the type its values bring:
    int64 keys, then a NULL key as float64 NaN or object None, through
    the merge, the clamp path's insert, a snapshot and a restore into
    either layout (the restore's ``configure`` casts no key channel),
    and the expire."""
    kinds, dtypes = ("add", "first"), (np.int64, np.int64)
    null = np.float64(np.nan) if later == "nan" else None
    st = SessionRunState(n_partitions=4)
    st.configure(kinds, dtypes)
    i64 = np.array([10, 20], np.int64)
    st.merge_intervals(np.array([1, 2], np.uint64),
                       np.array([0, 0], np.int64),
                       np.array([50, 50], np.int64),
                       np.array([0, 0], np.int64),
                       [np.array([1, 1], np.int64), i64])
    nk = (np.array([np.nan]) if later == "nan"
          else np.array([None], dtype=object))
    st.merge_intervals(np.array([3], np.uint64), np.array([10], np.int64),
                       np.array([60], np.int64), np.array([10], np.int64),
                       [np.array([2], np.int64), nk])
    st.insert(20, np.uint64(4), [(5, 70, np.int64(3), null)])

    def keyvals(table):  # a partition's channel widens as a whole
        return sorted((int(k), None if v[0][3] is None
                       else "nan" if v[0][3] != v[0][3] else float(v[0][3]))
                      for k, v in table.items())

    want = keyvals(st)
    assert len(want) == 4
    snap = st.snapshot()
    back = SessionRunState(n_partitions=2)
    back.restore(snap)
    back.configure(kinds, dtypes)
    assert keyvals(back) == want
    legacy = KeyedState()
    legacy.restore(snap)
    assert keyvals(legacy) == want
    fk, fs, fe, fch, removed = back.expire(100)
    assert sorted(removed) == [1, 2, 3, 4]
    vals = dict(zip(fk.tolist(), fch[1].tolist()))
    assert vals[1] == 10 and vals[2] == 20
    assert (np.isnan(vals[3]) and np.isnan(vals[4]) if later == "nan"
            else vals[3] is None and vals[4] is None)
    assert fch[0].dtype == np.int64 and fch[0].sum() == 1 + 1 + 2 + 3


@pytest.mark.parametrize("first_mode,second_mode",
                         [("device", "legacy"), ("legacy", "device")])
def test_a_key_column_that_meets_a_null_restores(tmp_path, first_mode,
                                                 second_mode):
    """A key column that is int64 in the first batch and float64 with
    NULL keys later: the NULL key's session is live at the checkpoint,
    restores under the other layout, and every session fires
    exactly-once."""
    _session_restore_flip(tmp_path, first_mode, second_mode, mixed_key=True)


@pytest.mark.parametrize("later", ["numbers", "strings"])
def test_rows_that_tell_nothing_wait_on_the_buffer(rng, monkeypatch,
                                                   later):
    """A MIN / MAX column whose first rows are all NULL arrives as
    object None, which says nothing of its type.  The rows ride the
    buffer until rows that tell arrive.  Numbers take the accumulator
    path, rebuilding the live sessions from the buffered rows: the
    output is that of the same NULLs given as NaN, every session off
    accumulators.  Strings keep the buffer."""
    monkeypatch.setenv("ARROYO_COALESCE", "0")  # the rows as they come
    n0 = 200
    head_ts = np.sort(rng.integers(0, 100 * MS, n0)).astype(np.int64)
    head_k = rng.integers(0, 40, n0).astype(np.int64)
    rest = _null_batches(rng)
    for b in rest:  # after the head, whose sessions are live until then
        b.timestamp = b.timestamp + 100 * MS
    if later == "strings":
        for b in rest:
            b.columns["v"] = np.array(
                [None if x != x else f"s{int(x)}" for x in b.columns["v"]],
                dtype=object)
        aggs = [AggSpec(AggKind.COUNT, None, "cnt"),
                AggSpec(AggKind.MIN, "v", "lo")]
    else:
        aggs = NULL_AGGS

    def batches(null):
        head = Batch(head_ts, {"k": head_k, "v": np.full(n0, null)})
        return [head] + rest

    acc0, read0 = (perf.counter("session_acc_sessions"),
                   perf.counter("session_rows_read"))
    got = _run_sessions(batches(None), "device", gap=200 * MS, aggs=aggs)
    fired_acc = perf.counter("session_acc_sessions") - acc0
    assert int(got.columns["cnt"].sum()) == n0 + 4 * 1500
    if later == "strings":
        assert fired_acc == 0
        assert perf.counter("session_rows_read") > read0
        return
    assert fired_acc == len(got)
    assert perf.counter("session_rows_read") == read0
    _assert_same_batch(got, _run_sessions(batches(np.nan), "device",
                                          gap=200 * MS, aggs=aggs))
