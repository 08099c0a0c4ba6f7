"""A batch's update in two halves (``BinAggOperator.process_batch``,
``KeyedBinState.admit`` / ``apply``): the loop half settles the bins and
the directory, the executor half (the reduce, the enqueue, the flush at the
bound) is left in flight while the next batch's loop half runs.  At most
one is in flight and they run in order; a hand-off, a grow, a watermark
that fires and a settle wait for it, a watermark that fires nothing does
not.  Every test drives the overlapped path (the operator's ``_offload``
field set, as on an accelerator), holds updates open with a gate, and
compares what left the operator, message for message, with the inline
operator on the same stream."""

import asyncio
import threading

import numpy as np
import pytest

from arroyo_tpu import AggKind, AggSpec, Batch
from arroyo_tpu.engine.context import Context
from arroyo_tpu.engine.operators_window import BinAggOperator
from arroyo_tpu.obs import perf
from arroyo_tpu.ops import keyed_bins
from arroyo_tpu.ops.keyed_bins import KeyedBinState
from arroyo_tpu.types import MAX_TIMESTAMP, MessageKind, hash_columns

SEC = 1_000_000
COUNT = AggSpec(AggKind.COUNT, None, "n")
SUM = AggSpec(AggKind.SUM, "v", "s")
# a COUNT stream, and COUNT + an f64 SUM whose sums cross 2^24
AGGS = {"count": (COUNT,), "count_sum_f64": (COUNT, SUM)}
FINAL = int(MAX_TIMESTAMP)


@pytest.fixture(autouse=True)
def _single_device_uncoalesced(monkeypatch):
    monkeypatch.setenv("ARROYO_MESH", "off")  # KeyedBinState, not the mesh's
    monkeypatch.setenv("ARROYO_COALESCE", "0")  # a message in, a message on


def _stream(seed, n_batches=12, rows=100, fresh_from=None, late=True):
    """("batch", Batch) and ("wm", micros) in turn: half a second of event
    time a batch, 40 keys, a watermark behind every batch (every other one
    closes a pane), in every third batch a tenth of the rows three or
    more seconds late; from batch ``fresh_from`` on, 60 rows a batch bring
    keys never seen before (a small capacity grows)."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n_batches):
        ts = i * SEC // 2 + rng.integers(0, SEC // 2, rows)
        if late and i % 3 == 2:
            ts[: rows // 10] -= rng.integers(3, 6, rows // 10) * SEC
        key = rng.integers(0, 40, rows).astype(np.int64)
        if fresh_from is not None and i >= fresh_from:
            key[:60] = 1000 * (i + 1) + np.arange(60)
        cols = {"k": key, "v": rng.integers(1, 1 << 22, rows).astype(
            np.float64)}
        out.append(("batch", Batch(np.maximum(ts, 0), cols,
                                   hash_columns([key]), ("k",))))
        out.append(("wm", int(i * SEC // 2)))
    return out


def _operator(aggs="count", overlapped=True, capacity=None):
    op = BinAggOperator("agg", 4 * SEC, SEC, AGGS[aggs])
    if capacity is not None:
        op.state = KeyedBinState(AGGS[aggs], SEC, 4 * SEC, capacity=capacity)
    assert isinstance(op.state, KeyedBinState)
    op._offload = overlapped  # the executor hand-off and the tail's task
    return op


class _Gate:
    """Holds every update of ``op`` on the executor until a permit is
    released for it (one a ``release()``); remembers the threads that ran
    the executor halves."""

    def __init__(self, op, fail=False):
        self.permits = threading.Semaphore(0)
        self.threads = []
        inner = op.state.apply

        def held(rows):
            self.threads.append(threading.get_ident())
            assert self.permits.acquire(timeout=30), "no permit came"
            if fail:
                raise RuntimeError("the update's reduce failed")
            return inner(rows)

        op.state.apply = held

    def release(self):
        self.permits.release()


def _said(msg):
    """A message as the comparison reads it: a batch is its rows."""
    if msg.kind == MessageKind.RECORD:
        b = msg.batch
        return ("rows", b.timestamp.tolist(), b.key_hash.tolist(),
                {c: np.asarray(v).tolist() for c, v in b.columns.items()})
    if msg.kind == MessageKind.WATERMARK:
        return ("wm", int(msg.watermark.time))
    return (msg.kind.value,)


def _drain(q):
    out = []
    while not q.empty():
        out.append(_said(q.get_nowait()))
    return out


def _snapshot(op):
    return op.state.snapshot() | op.keyvals.snapshot()


def _held(op):
    return op._update is not None and not op._update.done()


async def _serial(aggs, script, capacity=None, barrier_at=None):
    """What the inline operator sends on ``script``, and its snapshot at
    the barrier."""
    op = _operator(aggs, overlapped=False, capacity=capacity)
    ctx, q = Context.new_for_test()
    snap = None
    for i, (kind, x) in enumerate(script):
        if i == barrier_at:
            await op.pre_checkpoint(None, ctx)
            snap = _snapshot(op)
        if kind == "batch":
            await op.process_batch(x, ctx)
        else:
            await op.handle_watermark(x, ctx)
    await op.on_close(ctx)
    assert op._update is None
    return _drain(q), snap


async def _waits(coro, gate, secs=0.02):
    """Run ``coro`` and say whether it waited for the held update: it is
    still running ``secs`` later, and returns once a permit is released."""
    task = asyncio.ensure_future(coro)
    await asyncio.sleep(secs)
    waited = not task.done()
    if waited:
        gate.release()
    await asyncio.wait_for(task, 30)
    return waited


async def _until(cond, what, secs=30.0):
    deadline = asyncio.get_running_loop().time() + secs
    while not cond():
        assert asyncio.get_running_loop().time() < deadline, what
        await asyncio.sleep(0.002)


def _dir_calls(monkeypatch):
    """The thread of every ``ndir.insert`` and ``_append_new_keys``."""
    from arroyo_tpu.native import NativeDir

    calls = []
    insert, append = NativeDir.insert, keyed_bins._append_new_keys

    def rec_insert(self, *a):
        calls.append(("insert", threading.get_ident()))
        return insert(self, *a)

    def rec_append(*a):
        calls.append(("append", threading.get_ident()))
        return append(*a)

    monkeypatch.setattr(NativeDir, "insert", rec_insert)
    monkeypatch.setattr(keyed_bins, "_append_new_keys", rec_append)
    return calls


# -- (a) parity: batches, watermarks, a grow, a barrier, the close -------------


@pytest.mark.parametrize("aggs", list(AGGS))
def test_a_held_update_leaves_the_inline_sequence(run_async, monkeypatch,
                                                 aggs):
    script = _stream(83, fresh_from=6)
    # mid-window, before the seventh batch's watermark: every other
    # watermark fires, and the batch after one that does not grows
    barrier_at = 13
    calls = _dir_calls(monkeypatch)

    async def overlapped():
        op = _operator(aggs, capacity=256)
        gate = _Gate(op)
        ctx, q = Context.new_for_test()
        perf.reset()
        seen = {"beside": 0, "grow_held": 0, "quiet_wm": 0, "fire_wm": 0}
        snap = None
        for i, (kind, x) in enumerate(script):
            if i == barrier_at:
                assert _held(op)
                assert await _waits(op.pre_checkpoint(None, ctx), gate)
                assert op._update is None
                snap = _snapshot(op)
            held = _held(op)
            if kind == "batch":
                st = op.state
                grows = st.replaces_planes(len(x), st.assign(x.timestamp))
                n_dir = len(calls)
                task = asyncio.ensure_future(op.process_batch(x, ctx))
                if held:
                    seen["beside"] += 1
                    # it waits: before its lookup where a grow may come,
                    # at the hand-off after its loop half where none can
                    if not grows:
                        await _until(lambda: len(calls) > n_dir,
                                     "the loop half beside the update")
                    await asyncio.sleep(0.02)
                    assert not task.done()
                    assert (len(calls) == n_dir) == grows, (i, grows)
                    seen["grow_held"] += grows
                    gate.release()
                await asyncio.wait_for(task, 30)
                # handed off, and held
                assert _held(op)
            else:
                fires = op.state.fire_due(x)
                if held and fires:
                    assert await _waits(op.handle_watermark(x, ctx), gate)
                    seen["fire_wm"] += 1
                else:
                    # a watermark that fires nothing returns beside it
                    await asyncio.wait_for(op.handle_watermark(x, ctx), 5)
                    assert _held(op) == held
                    seen["quiet_wm"] += held
        assert await _waits(op.on_close(ctx), gate)
        assert op._update is None and op._tail is None
        assert perf.counter("update_overlap_batches") == seen["beside"]
        assert perf.counter("wait_us.update_wait") >= 15_000 * (
            seen["beside"] + seen["fire_wm"])
        assert perf.counter("state_grows") >= 1
        return _drain(q), snap, seen, gate.threads

    got, snap, seen, threads = run_async(overlapped())
    want, want_snap = run_async(_serial(aggs, script, capacity=256,
                                        barrier_at=barrier_at))
    assert seen == {"beside": 6, "grow_held": 1, "quiet_wm": 7, "fire_wm": 4}
    assert sum(m[0] == "rows" for m in want) >= 4
    assert got == want
    assert snap.keys() == want_snap.keys()
    for k in snap:
        np.testing.assert_array_equal(snap[k], want_snap[k], err_msg=k)
    # the directory on the loop thread, the executor halves beside it
    loop_thread = threading.get_ident()
    assert {t for _, t in calls} == {loop_thread}
    assert {name for name, _ in calls} == {"insert", "append"}
    assert threads and loop_thread not in threads


# -- (b) which watermarks wait for the update in flight ------------------------


def test_a_quiet_watermark_returns_and_a_firing_one_waits(run_async):
    script = _stream(89, n_batches=4, late=False)

    async def go():
        op = _operator("count_sum_f64")
        gate = _Gate(op)
        ctx, q = Context.new_for_test()
        await op.process_batch(script[0][1], ctx)
        assert _held(op)
        # half a second of event time: the first pane is still open
        quiet = script[0][1].timestamp.min() + SEC // 4
        assert not op.state.fire_due(quiet)
        await asyncio.wait_for(op.handle_watermark(int(quiet), ctx), 5)
        assert _held(op) and _drain(q) == [("wm", int(quiet))]
        # the final watermark closes every pane: it waits for the update
        assert op.state.fire_due(FINAL, True)
        assert await _waits(op.handle_watermark(FINAL, ctx), gate)
        await op.on_close(ctx)
        out = _drain(q)
        assert out[-1] == ("wm", FINAL) and out[0][0] == "rows"
        return sum(sum(m[3]["n"]) for m in out if m[0] == "rows")

    # every row of the batch is in the final fire: the update was applied
    assert run_async(go()) == 4 * len(script[0][1])


# -- (c) an update that raises --------------------------------------------------


@pytest.mark.parametrize("then", ["batch", "quiet_watermark",
                                  "firing_watermark", "settle", "close"])
def test_an_update_that_raises_surfaces_next(run_async, then):
    script = _stream(97, n_batches=3, late=False)

    async def go():
        op = _operator("count")
        gate = _Gate(op, fail=True)
        ctx, _q = Context.new_for_test()
        await op.process_batch(script[0][1], ctx)
        gate.release()
        await asyncio.sleep(0.05)  # it has failed, and nobody looked yet
        assert op._update is not None and op._update.done()
        nxt = {"batch": lambda: op.process_batch(script[2][1], ctx),
               "quiet_watermark": lambda: op.handle_watermark(0, ctx),
               "firing_watermark": lambda: op.handle_watermark(FINAL, ctx),
               "settle": lambda: op.settle(ctx),
               "close": lambda: op.on_close(ctx)}[then]
        if then == "batch":
            gate.release()  # the batch's own update may pass
        with pytest.raises(RuntimeError, match="reduce failed"):
            await nxt()
        assert op._update is None  # raised once, in no forgotten future
        await op.on_close(ctx)
        assert op._update is None and op._tail is None

    run_async(go())


def test_abandon_lets_a_held_update_go(run_async):
    script = _stream(101, n_batches=1, late=False)

    async def go():
        op = _operator("count")
        gate = _Gate(op)
        ctx, _q = Context.new_for_test()
        await op.process_batch(script[0][1], ctx)
        fut = op._update.future
        op.abandon()
        assert op._update is None
        gate.release()
        await asyncio.sleep(0.05)
        return fut

    assert run_async(go()).done()


# -- (d) the counters ------------------------------------------------------------


@pytest.mark.parametrize("overlapped", [True, False],
                         ids=["offloaded", "inline"])
def test_the_counters_count_where_updates_are_handed_off(run_async,
                                                         overlapped):
    batches = [x for kind, x in _stream(103, n_batches=6, late=False)
               if kind == "batch"]

    async def go():
        op = _operator("count", overlapped=overlapped)
        gate = _Gate(op) if overlapped else None
        ctx, _q = Context.new_for_test()
        perf.reset()
        await op.process_batch(batches[0], ctx)
        for b in batches[1:]:
            if overlapped:
                assert await _waits(op.process_batch(b, ctx), gate)
            else:
                await op.process_batch(b, ctx)
        if overlapped:
            gate.release()
        await op.on_close(ctx)
        return (perf.counter("update_overlap_batches"),
                perf.counter("wait_us.update_wait"),
                perf.counter("offload_hops"))

    beside, waited, hops = run_async(go())
    if overlapped:
        # every batch but the first began beside its predecessor's update
        # and waited for it at the hand-off: the gate's 20 ms or more
        assert beside == 5 and waited >= 5 * 15_000 and hops == 6
    else:
        assert (beside, waited, hops) == (0, 0, 0)


def test_the_state_halves_are_the_update():
    """``update`` is ``admit`` then ``apply``; the mesh state keeps its
    update whole (no halves, the serial await)."""
    from arroyo_tpu.parallel.mesh_window import MeshKeyedBinState

    batches = [x for kind, x in _stream(107, n_batches=5, fresh_from=2)
               if kind == "batch"]
    a = KeyedBinState(AGGS["count_sum_f64"], SEC, 4 * SEC, capacity=64)
    b = KeyedBinState(AGGS["count_sum_f64"], SEC, 4 * SEC, capacity=64)
    for x in batches:
        a.update(x.key_hash, x.timestamp, x.columns)
        rows = b.admit(x.key_hash, x.timestamp, x.columns)
        assert rows is not None and len(rows.slots) == len(x)
        b.apply(rows)
    sa, sb = a.snapshot(), b.snapshot()
    assert sa.keys() == sb.keys()
    for k in sa:
        np.testing.assert_array_equal(sa[k], sb[k], err_msg=k)
    assert a.C > 64  # the keys outgrew the capacity on the way
    for name in ("admit", "apply", "fire_due"):
        assert not hasattr(MeshKeyedBinState, name)
