"""Dead keys leave the bin state (``KeyedBinState._release_dead``): a slot
whose key's last bin has been evicted goes back to a free list one fire
later, and new keys take freed slots before fresh ones.  So a stream whose
keys churn holds the keys of its retained bins and not every key it has
seen: it never grows, its rows are a numpy reference's window for window,
a key that returns is counted afresh, a fire's tail still emits the keys a
later fire freed, a fixed key set frees nothing, and a checkpoint holds
each live key once."""

import asyncio

import numpy as np
import pytest

from arroyo_tpu import AggKind, AggSpec, Batch
from arroyo_tpu.engine.context import Context
from arroyo_tpu.engine.operators_window import BinAggOperator
from arroyo_tpu.obs import perf
from arroyo_tpu.ops.keyed_bins import (KeyedBinState,
                                       filter_canonical_snapshot,
                                       merge_canonical_snapshots)
from arroyo_tpu.types import MAX_TIMESTAMP, MessageKind, TaskInfo, \
    hash_columns

SEC = 1_000_000
COUNT = AggSpec(AggKind.COUNT, None, "n")
SUM = AggSpec(AggKind.SUM, "v", "s")
AGGS = (COUNT, SUM)
FINAL = int(MAX_TIMESTAMP)
# window -> (width, slide) in seconds
WINDOWS = {"hop": (4, 1), "tumble": (2, 2)}


@pytest.fixture(autouse=True)
def _single_device_uncoalesced(monkeypatch):
    monkeypatch.setenv("ARROYO_MESH", "off")  # KeyedBinState, not the mesh's
    monkeypatch.setenv("ARROYO_COALESCE", "0")  # a message in, a message on


def _churn(seed, seconds, new_per_s=80, rows=200, fixed=None, back=None):
    """("batch", Batch) and ("wm", micros) in turn, a second of event time
    a batch: key ids drawn from the ``2 * new_per_s`` ids that start at
    ``second * new_per_s``, so a key lives two seconds and ``new_per_s``
    keys are new every second (``fixed``: from ``range(fixed)`` instead;
    ``back``: {second: id} of an old key that has three rows there);
    integer-valued f64 values, whose sums are exact; a watermark half a
    second into the batch's second after it."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(seconds):
        ts = i * SEC + rng.integers(0, SEC, rows)
        if fixed is None:
            ids = i * new_per_s + rng.integers(0, 2 * new_per_s, rows)
        else:
            ids = np.concatenate([np.arange(fixed),
                                  rng.integers(0, fixed, rows - fixed)])
        if back and i in back:
            ids[:3] = back[i]
        cols = {"k": ids.astype(np.int64),
                "v": rng.integers(1, 1 << 30, rows).astype(np.float64)}
        out.append(("batch", Batch(ts.astype(np.int64), cols,
                                   hash_columns([cols["k"]]), ("k",))))
        out.append(("wm", i * SEC + SEC // 2))
    return out


def _reference(script, width, slide):
    """{(k, window_end): (rows, sum)} of every window with rows."""
    k = np.concatenate([x.columns["k"] for kind, x in script
                        if kind == "batch"])
    ts = np.concatenate([x.timestamp for kind, x in script
                         if kind == "batch"])
    v = np.concatenate([x.columns["v"] for kind, x in script
                        if kind == "batch"])
    w = width // slide
    ends = ((ts // slide + 1)[None, :] + np.arange(w)[:, None]) * slide
    kk = np.broadcast_to(k, ends.shape).ravel()
    vv = np.broadcast_to(v, ends.shape).ravel()
    ends = ends.ravel()
    order = np.lexsort((ends, kk))
    kk, ends, vv = kk[order], ends[order], vv[order]
    first = np.ones(len(kk), bool)
    first[1:] = (kk[1:] != kk[:-1]) | (ends[1:] != ends[:-1])
    at = first.nonzero()[0]
    n = np.diff(np.append(at, len(kk)))
    s = np.add.reduceat(vv, at)
    return {(int(a), int(b)): (int(c), float(d))
            for a, b, c, d in zip(kk[at], ends[at], n, s)}


def _rows(q):
    """{(k, window_end): (n, s)} of what left the operator; no cell twice."""
    got = {}
    while not q.empty():
        msg = q.get_nowait()
        if msg.kind != MessageKind.RECORD:
            continue
        c = msg.batch.columns
        np.testing.assert_array_equal(msg.batch.key_hash,
                                      hash_columns([c["k"]]))
        for k, e, n, s in zip(c["k"].tolist(), c["window_end"].tolist(),
                              c["n"].tolist(), c["s"].tolist()):
            assert (k, e) not in got, (k, e)
            got[(k, e)] = (n, s)
    return got


def _operator(window="hop", capacity=1 << 10, overlapped=True):
    width, slide = WINDOWS[window]
    op = BinAggOperator("agg", width * SEC, slide * SEC, AGGS)
    op.state = KeyedBinState(AGGS, slide * SEC, width * SEC,
                             capacity=capacity)
    op._offload = overlapped  # the executor hops and the tail's task
    return op


async def _run(op, script, ctx):
    for kind, x in script:
        if kind == "batch":
            await op.process_batch(x, ctx)
        else:
            await op.handle_watermark(x, ctx)


@pytest.mark.parametrize("overlapped", [False, True],
                         ids=["inline", "overlapped"])
@pytest.mark.parametrize("window", list(WINDOWS))
def test_churning_keys_stay_in_a_small_state(run_async, window, overlapped):
    """50,000 keys through 1,024 slots: no grow, and every window's rows
    are the reference's."""
    script = _churn(11, 680)
    width, slide = WINDOWS[window]
    keys = np.unique(np.concatenate([x.columns["k"] for kind, x in script
                                     if kind == "batch"]))
    assert len(keys) > 49_000

    async def go():
        op = _operator(window, overlapped=overlapped)
        ctx, q = Context.new_for_test()
        perf.reset()
        await _run(op, script, ctx)
        await op.handle_watermark(FINAL, ctx)
        await op.on_close(ctx)
        return op, _rows(q)

    op, got = run_async(go())
    assert perf.counter("state_grows") == 0 and op.state.C == 1 << 10
    # a key lives two seconds, and no key comes back
    assert perf.counter("keys_inserted") == len(keys)
    assert perf.counter("keys_evicted") > len(keys) - 1024
    assert op.state.next_slot <= 1 << 10
    assert got == _reference(script, width * SEC, slide * SEC)


def test_a_key_that_returns_is_counted_afresh():
    """A key whose bins were all evicted leaves the directory; when it
    comes back twenty bins later it takes a slot again and its new window
    counts only its new rows."""
    st = KeyedBinState(AGGS, SEC, 2 * SEC, capacity=256)
    back = np.uint64(12345)
    perf.reset()
    fired = []
    for i in range(30):
        kh = (np.arange(50) + 1000 * (i + 1)).astype(np.uint64)
        ts = i * SEC + np.arange(50, dtype=np.int64) * 1000
        if i in (0, 20):
            kh[:{0: 3, 20: 7}[i]] = back
        st.update(kh, ts, {"v": np.full(50, 2.0)})
        got = st.fire_panes(i * SEC + SEC // 2)
        if got is not None:
            fired.append(got)
        if i == 10:
            assert back not in st.key_sorted
            assert back not in st.slot_to_key[st.live_slots()]
    got = st.fire_panes(FINAL, final=True)
    fired.append(got)
    rows = [(int(e), int(n), float(s)) for f in fired
            for k, e, n, s in zip(f.keys, f.window_end, f.counts,
                                  f.cols["s"]) if k == back]
    assert rows == [(1 * SEC, 3, 6.0), (2 * SEC, 3, 6.0),
                    (21 * SEC, 7, 14.0), (22 * SEC, 7, 14.0)]
    # every key once, but the one that came back twice
    assert perf.counter("keys_inserted") == 30 * 50 - (3 - 1) - (7 - 1)
    assert perf.counter("state_grows") == 0


def test_a_held_tail_emits_the_keys_a_later_fire_freed():
    """The one-fire lag: fire k frees the keys that died at fire k - 1's
    evict, some of which fire k - 1's panes hold.  That tail, still in
    flight, emits them under their own keys; the new keys that take their
    slots afterwards fire under theirs."""
    st = KeyedBinState((COUNT,), SEC, 2 * SEC, capacity=64)
    old = np.arange(1, 11, dtype=np.uint64)
    new = np.arange(101, 111, dtype=np.uint64)
    st.update(old, np.zeros(10, np.int64), {})  # bin 0
    assert st.fire_panes(SEC) is not None  # pane 0
    st.update(np.array([7], np.uint64), np.full(1, SEC, np.int64), {})
    head1 = st.fire_head(2 * SEC)  # pane 1: bins 0 and 1; bin 0 evicted
    st.update(np.array([50], np.uint64), np.full(1, 2 * SEC, np.int64), {})
    perf.reset()
    head2 = st.fire_head(3 * SEC)  # pane 2: frees the keys last in bin 0
    assert perf.counter("keys_evicted") == 9
    freed = np.sort(st._free)
    np.testing.assert_array_equal(np.sort(st.slot_to_key[freed]),
                                  np.setdiff1d(old, [7]))
    tail1 = st.fire_tail(head1)
    assert sorted(zip(tail1.keys.tolist(), tail1.counts.tolist())) == \
        sorted([(int(k), 1) for k in old if k != 7] + [(7, 2)])
    np.testing.assert_array_equal(st.slot_to_key[tail1.slots], tail1.keys)
    # new keys take the freed slots, first seen first
    slots = st._lookup_or_insert(new)
    np.testing.assert_array_equal(slots[:9], freed)
    assert slots[9] == st.next_slot - 1 == 11
    st.update(new, np.full(10, 2 * SEC, np.int64), {})
    tail2 = st.fire_tail(head2)
    assert sorted(tail2.keys.tolist()) == [7, 50]
    last = st.fire_panes(FINAL, final=True)
    assert sorted(set(last.keys.tolist())) == [50] + new.tolist()
    np.testing.assert_array_equal(st.slot_to_key[last.slots], last.keys)


def test_held_tails_on_churning_keys_leave_the_serial_output(run_async):
    """The operator with every fire's tail held open while the next
    batches insert keys into the slots that fire freed sends what the
    serial operator sends, rows and key columns alike."""
    import threading

    script = _churn(17, 60, new_per_s=40, rows=120)

    async def overlapped():
        op = _operator("hop", capacity=256)
        # a fire's tail waits until the batch after its head is in, or
        # the stream is over
        batches, over, cond = [0], [False], threading.Condition()
        head, tail = op.state.fire_head, op.state.fire_tail

        def marked_head(*a):
            fire = head(*a)
            if fire is not None:
                fire.after = batches[0] + 1
            return fire

        def held_tail(fire):
            with cond:
                assert cond.wait_for(
                    lambda: over[0] or batches[0] >= fire.after, 30)
            return tail(fire)

        op.state.fire_head, op.state.fire_tail = marked_head, held_tail
        ctx, q = Context.new_for_test()
        perf.reset()
        for kind, x in script:
            if kind == "batch":
                await op.process_batch(x, ctx)
                with cond:
                    batches[0] += 1
                    cond.notify_all()
            else:
                await op.handle_watermark(x, ctx)
        with cond:
            over[0] = True
            cond.notify_all()
        await op.handle_watermark(FINAL, ctx)
        await op.on_close(ctx)
        assert perf.counter("fire_overlap_batches") > 30
        assert perf.counter("keys_evicted") > 2000
        return _rows(q)

    async def serial():
        op = _operator("hop", capacity=256, overlapped=False)
        ctx, q = Context.new_for_test()
        await _run(op, script, ctx)
        await op.handle_watermark(FINAL, ctx)
        await op.on_close(ctx)
        return _rows(q)

    got = run_async(overlapped())
    assert got == run_async(serial())
    assert got == _reference(script, 4 * SEC, SEC)


def test_a_fixed_key_set_frees_nothing(run_async, monkeypatch):
    """Every key in every bin: no slot is freed, and the output is the
    output of a state that never frees, bit for bit."""
    script = _churn(23, 40, fixed=150)

    async def go():
        op = _operator("hop", capacity=256)
        ctx, q = Context.new_for_test()
        await _run(op, script, ctx)
        await op.handle_watermark(FINAL, ctx)
        await op.on_close(ctx)
        return _rows(q), op.state

    perf.reset()
    got, st = run_async(go())
    assert perf.counter("keys_evicted") == 0 and not len(st._free)
    assert st.next_slot == 150
    monkeypatch.setattr(KeyedBinState, "_release_dead", lambda self: None)
    want, _ = run_async(go())
    assert got == want == _reference(script, 4 * SEC, SEC)


def _snap_table(op):
    return op._ctx.state.tables["a"]


async def _started(window="hop", task=(0, 1)):
    op = _operator(window, capacity=512)
    ctx, q = Context.new_for_test(TaskInfo("job", "op", "agg", *task))
    await op.on_start(ctx)
    op._ctx = ctx
    return op, ctx, q


def test_checkpoints_hold_each_live_key_once(run_async):
    """A snapshot after evictions holds the directory's keys, each once,
    compacted, with their key columns beside them; it restores into an
    operator that goes on as the first one does; split by key range and
    merged back, it restores to the same."""
    script = _churn(29, 80, back={45: 5})
    cut = 2 * 50  # fifty seconds in, mid-window

    async def go():
        a, ctx_a, q_a = await _started()
        await _run(a, script[:cut], ctx_a)
        await a.pre_checkpoint(None, ctx_a)
        st = a.state
        assert len(st._free) > 0 and st.key_sorted.size < st.next_slot
        snap = {k: np.array(v, copy=True)
                for k, v in _snap_table(a).snapshot().items()}
        n = int(snap["meta"][0])
        keys = snap["bin_keys"]
        assert n == len(keys) == len(np.unique(keys)) == len(st.key_sorted)
        np.testing.assert_array_equal(np.sort(keys), st.key_sorted)
        np.testing.assert_array_equal(snap["slot_to_key"], keys)
        np.testing.assert_array_equal(
            snap["slot_of_sorted"], np.argsort(keys, kind="stable"))
        np.testing.assert_array_equal(hash_columns([snap["kv_k"]]), keys)
        assert int(snap["kv_size"][0]) == n
        # a key that died and came back is there once; keys that died
        # and stayed away are not there
        assert (keys == hash_columns([np.array([5])])[0]).sum() == 1
        assert not np.isin(keys, hash_columns([np.arange(6, 160)])).any()
        while not q_a.empty():
            q_a.get_nowait()

        halves = [filter_canonical_snapshot(dict(snap), (lo, hi))
                  for lo, hi in (TaskInfo("j", "o", "a", 0, 2).key_range,
                                 TaskInfo("j", "o", "a", 1, 2).key_range)]
        assert sum(int(h["meta"][0]) for h in halves) == n
        for h in halves:
            np.testing.assert_array_equal(hash_columns([h["kv_k"]]),
                                          h["bin_keys"])
        merged = merge_canonical_snapshots(halves[0], halves[1])
        assert len(np.unique(merged["bin_keys"])) == n
        np.testing.assert_array_equal(np.sort(merged["key_sorted"]),
                                      np.sort(keys))

        outs = []
        for arrays in (snap, merged):
            b, ctx_b, q_b = await _started()
            _snap_table(b).restore(dict(arrays))
            await _run(b, script[cut:], ctx_b)
            await b.handle_watermark(FINAL, ctx_b)
            await b.on_close(ctx_b)
            outs.append(_rows(q_b))
        await _run(a, script[cut:], ctx_a)
        await a.handle_watermark(FINAL, ctx_a)
        await a.on_close(ctx_a)
        return _rows(q_a), outs

    want, (restored, remerged) = run_async(go())
    assert len(want) > 1000
    assert restored == want and remerged == want
