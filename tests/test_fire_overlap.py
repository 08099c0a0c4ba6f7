"""A fire in two halves (``BinAggOperator.handle_watermark``): the head is
what the operator's serial path waits for, the tail (the read-back's wait,
the fired batch, its collect, the watermark's broadcast) runs as a task of
its own beside the next batches.  Order and guarantees are the serial
program's, to the row: every test drives the overlapped path (the
operator's ``_offload`` field set, as on an accelerator), holds the tail
open with a gate while the stream goes on, and compares what left the
operator, message for message, with the serial operator on the same
stream."""

import asyncio
import threading

import numpy as np
import pytest

from arroyo_tpu import AggKind, AggSpec, Batch
from arroyo_tpu.engine.context import Collector, Context, OutQueue
from arroyo_tpu.engine.operators_window import (BinAggOperator,
                                                FactorPaneOperator)
from arroyo_tpu.engine.task import TaskRunner
from arroyo_tpu.obs import perf, tracing
from arroyo_tpu.ops.keyed_bins import KeyedBinState
from arroyo_tpu.state.store import StateStore
from arroyo_tpu.types import (MAX_TIMESTAMP, CheckpointBarrier,
                              ControlMessage, Message, MessageKind,
                              StopMode, TaskInfo, Watermark, hash_columns)

SEC = 1_000_000
COUNT = AggSpec(AggKind.COUNT, None, "n")
SUM = AggSpec(AggKind.SUM, "v", "s")
# fire -> (aggs, argmax-local): the compacted fire of an all-COUNT state,
# the candidates-only fire, and a state whose f64 sum rides the read-back
FIRES = {"compact": ((COUNT,), False), "argmax": ((COUNT,), True),
         "sum_f64": ((COUNT, SUM), False)}
FINAL = int(MAX_TIMESTAMP)


@pytest.fixture(autouse=True)
def _single_device_uncoalesced(monkeypatch):
    monkeypatch.setenv("ARROYO_MESH", "off")  # KeyedBinState, not the mesh's
    monkeypatch.setenv("ARROYO_COALESCE", "0")  # a message in, a message on


def _stream(seed, n_batches=8, rows=300, late=True, idle_wms=False):
    """("batch", Batch) and ("wm", micros) in turn: a second of event time
    a batch, 40 keys, a watermark half a second behind after every batch
    (with ``idle_wms`` a second one 0.2 s later, which closes no pane),
    and in every third batch a tenth of the rows three or more seconds
    late."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n_batches):
        ts = i * SEC + rng.integers(0, SEC, rows)
        if late and i % 3 == 2:
            ts[: rows // 10] -= rng.integers(3, 6, rows // 10) * SEC
        key = rng.integers(0, 40, rows).astype(np.int64)
        # sums cross 2^24: an f32 would lose the unit
        cols = {"k": key, "v": rng.integers(1, 1 << 22, rows).astype(
            np.float64)}
        out.append(("batch", Batch(np.maximum(ts, 0), cols,
                                   hash_columns([key]), ("k",))))
        out.append(("wm", int((i + 1) * SEC - SEC // 2)))
        if idle_wms:
            out.append(("wm", int((i + 1) * SEC - SEC // 2 + SEC // 5)))
    return out


def _closes_a_pane(wm):
    return wm % SEC == SEC // 2


def _operator(fire="compact", overlapped=True, factor=False):
    aggs, argmax = FIRES[fire]
    if factor:
        op = FactorPaneOperator("agg", SEC, aggs)
    else:
        op = BinAggOperator("agg", 4 * SEC, SEC, aggs,
                            argmax_local=("n", "max") if argmax else None)
    assert isinstance(op.state, KeyedBinState)
    op._offload = overlapped  # the executor hop and the tail's task, or none
    return op


class _Gate:
    """Holds every tail of ``op`` open until ``open()``; counts them."""

    def __init__(self, op):
        self.event = threading.Event()
        self.started = 0
        inner = op.state.fire_tail

        def held(fire):
            self.started += 1
            assert self.event.wait(30), "the gate was never opened"
            return inner(fire)

        op.state.fire_tail = held

    def open(self):
        self.event.set()

    def close(self):
        self.event.clear()


def _said(msg):
    """A message as the comparison reads it: a batch is its rows."""
    if msg.kind == MessageKind.RECORD:
        b = msg.batch
        return ("rows", b.timestamp.tolist(), b.key_hash.tolist(),
                {c: np.asarray(v).tolist() for c, v in b.columns.items()})
    if msg.kind == MessageKind.WATERMARK:
        w = msg.watermark
        return ("idle",) if w.is_idle else ("wm", int(w.time))
    if msg.kind == MessageKind.BARRIER:
        return ("barrier", msg.barrier.epoch)
    return (msg.kind.value,)


def _drain(q):
    out = []
    while not q.empty():
        out.append(_said(q.get_nowait()))
    return out


async def _until(cond, what, secs=30.0):
    deadline = asyncio.get_running_loop().time() + secs
    while not cond():
        assert asyncio.get_running_loop().time() < deadline, what
        await asyncio.sleep(0.002)


def _in_flight(op):
    return op._tail is not None and not op._tail.done()


async def _serial(fire, script, factor=False, barrier_after=None):
    """What the serial operator sends on ``script``."""
    op = _operator(fire, overlapped=False, factor=factor)
    ctx, q = Context.new_for_test()
    for i, (kind, x) in enumerate(script):
        if kind == "batch":
            await op.process_batch(x, ctx)
        elif kind == "wm":
            await op.handle_watermark(x, ctx)
        if barrier_after == i:
            await op.pre_checkpoint(None, ctx)
    await op.on_close(ctx)
    assert op._tail is None
    return _drain(q)


# -- (a) the stream goes on while the tail is held open -----------------------


@pytest.mark.parametrize("fire", list(FIRES))
def test_batches_beside_a_held_tail_leave_the_serial_sequence(run_async,
                                                              fire):
    script = _stream(37, idle_wms=True)

    async def overlapped():
        op = _operator(fire)
        gate = _Gate(op)
        ctx, q = Context.new_for_test()
        perf.reset()
        beside = 0
        for kind, x in script:
            held = _in_flight(op)
            sent = q.qsize()
            if kind == "batch":
                await op.process_batch(x, ctx)
                beside += held
            elif _closes_a_pane(x):
                gate.open()
                await op.settle(ctx)
                gate.close()
                await op.handle_watermark(x, ctx)
                continue
            else:
                # a watermark that fires nothing does not wait for the
                # tail: it is forwarded from behind it
                await op.handle_watermark(x, ctx)
            if held:
                # the batch was inserted and applied beside the tail, and
                # nothing has left: not the fire, not the later watermark
                assert _in_flight(op) and q.qsize() == sent
        gate.open()
        await op.on_close(ctx)
        assert gate.started >= 4 and beside == gate.started - 1
        assert perf.counter("fire_overlap_batches") == beside
        return _drain(q)

    got, want = run_async(overlapped()), run_async(_serial(fire, script))
    assert sum(m[0] == "rows" for m in want) >= 4
    assert [m for m in want if m[0] == "wm"] == [
        ("wm", x) for kind, x in script if kind == "wm"]
    assert got == want
    # the late rows were dropped, by the same rule at the same point
    fed = sum(len(x) for kind, x in script if kind == "batch")
    assert sum(sum(m[3]["n"]) for m in want if m[0] == "rows") < 4 * fed


# -- (e) the next watermark finds the tail before it still in flight ----------


@pytest.mark.parametrize("fire", list(FIRES))
def test_a_second_watermark_waits_for_the_first_tail(run_async, fire):
    script = _stream(41, n_batches=5, late=False)

    async def overlapped():
        op = _operator(fire)
        gate = _Gate(op)
        ctx, q = Context.new_for_test()
        loop = asyncio.get_running_loop()
        for kind, x in script:
            if kind == "batch":
                await op.process_batch(x, ctx)
                continue
            if _in_flight(op):
                sent = q.qsize()
                loop.call_later(0.05, gate.open)
                await op.handle_watermark(x, ctx)
                # the fire before left whole before this head ran
                assert q.qsize() >= sent + 2
                gate.close()
            else:
                await op.handle_watermark(x, ctx)
        gate.open()
        await op.on_close(ctx)
        return _drain(q)

    assert run_async(overlapped()) == run_async(_serial(fire, script))


# -- through the runner: barrier, close, stop, idle, failure ------------------


class _Job:
    """The operator under a real ``TaskRunner``: an input queue, the
    messages it sends on, and a state store whose backend outlives it."""

    def __init__(self, op, backend=None, restore_epoch=None):
        self.op = op
        ti = TaskInfo("test-job", "op-0", "agg", 0, 1)
        self.store = (StateStore.new_in_memory(ti) if backend is None
                      else StateStore(ti, backend, restore_epoch))
        self.outq = asyncio.Queue()
        self.ctx = Context(ti, Collector([[OutQueue(queue=self.outq)]]), 1,
                           state_store=self.store)
        self.inq = asyncio.Queue()
        self.control = asyncio.Queue()
        self.runner = TaskRunner(ti, op, self.ctx, [(0, self.inq)],
                                 self.control, asyncio.Queue())
        self.task = asyncio.ensure_future(self.runner.start())

    async def feed(self, script):
        for kind, x in script:
            await self.inq.put(
                Message.record(x) if kind == "batch" else
                Message.wm(Watermark.event_time(x)) if kind == "wm" else x)

    async def forwarded(self, script):
        """Wait until every watermark of ``script`` has been sent on: the
        runner is through the script and no tail is in flight."""
        n = sum(kind == "wm" for kind, _ in script)
        await _until(lambda: sum(
            m.kind == MessageKind.WATERMARK for m in self.outq._queue) == n,
            "the script's watermarks")

    async def finished(self):
        await asyncio.wait_for(self.runner.finished.wait(), 30)
        await self.task
        return _drain(self.outq)


async def _runner_serial(fire, script, last=None):
    job = _Job(_operator(fire, overlapped=False))
    await job.feed(script + [last or ("end", Message.end_of_data())])
    return await job.finished()


@pytest.mark.parametrize("fire", ["compact", "sum_f64"])
def test_a_barrier_finds_the_fired_rows_downstream_and_restore_replays_once(
        run_async, fire):
    script = _stream(43, n_batches=9)
    cut = 8  # four batches and four watermarks, then the barrier
    barrier = CheckpointBarrier(1, 0, 0, False)

    async def overlapped():
        op = _operator(fire)
        gate = _Gate(op)
        job = _Job(op)
        gate.open()
        await job.feed(script[:cut - 1])
        await job.forwarded(script[:cut - 1])
        gate.close()
        before = job.outq.qsize()
        await job.feed(script[cut - 1:cut])  # a watermark that fires
        await _until(lambda: _in_flight(op), "the held tail")
        await job.feed([("barrier", Message.barrier_msg(barrier))])
        await asyncio.sleep(0.05)
        # the barrier waits for the tail, and nothing has overtaken it
        assert _in_flight(op) and job.outq.qsize() == before
        gate.open()
        await job.feed(script[cut:] + [("end", Message.end_of_data())])
        first = await job.finished()
        at = first.index(("barrier", 1))
        # the fire before the barrier is downstream before it, whole
        assert first[at - 2][0] == "rows" and first[at - 1] == (
            "wm", script[cut - 1][1])
        assert [m[0] for m in first[:before]].count("barrier") == 0

        # restore from the barrier's snapshot and replay what followed it
        op2 = _operator(fire)
        job2 = _Job(op2, job.store.backend, restore_epoch=1)
        await job2.feed(script[cut:] + [("end", Message.end_of_data())])
        second = await job2.finished()
        assert op2.state.last_fired_pane is not None
        return first, at, second

    first, at, second = run_async(overlapped())
    want = run_async(_runner_serial(fire, script))
    assert first[:at] + first[at + 1:] == want
    # each window once: the restored run sends exactly what followed
    assert first[:at] + second == want


@pytest.mark.parametrize("end", ["end_of_data", "stop"])
@pytest.mark.parametrize("final_watermark", [True, False],
                         ids=["final_wm", "no_final_wm"])
def test_the_close_loses_no_window_with_a_tail_in_flight(run_async, end,
                                                         final_watermark):
    script = _stream(47, n_batches=4, late=False)
    if final_watermark:
        script.append(("wm", FINAL))
    last = ("end", Message.end_of_data() if end == "end_of_data"
            else Message.stop())

    async def overlapped():
        op = _operator("sum_f64")
        gate = _Gate(op)
        job = _Job(op)
        gate.open()
        await job.feed(script[:-1])
        await job.forwarded(script[:-1])
        gate.close()
        before = job.outq.qsize()
        await job.feed(script[-1:])
        await _until(lambda: _in_flight(op), "the held tail")
        await job.feed([last])
        await asyncio.sleep(0.05)
        assert _in_flight(op) and job.outq.qsize() == before
        gate.open()
        out = await job.finished()
        assert job.runner.failed is None and op._tail is None
        return out

    got = run_async(overlapped())
    assert got == run_async(_runner_serial("sum_f64", script, last))
    assert got[-1] == (end,) and got[-2][0] == "wm" and got[-3][0] == "rows"
    if final_watermark:
        assert got[-2] == ("wm", FINAL)


def test_an_idle_watermark_does_not_overtake_a_tail(run_async):
    script = _stream(53, n_batches=3, late=False)
    tail_msgs = [("idle", Message.wm(Watermark.idle())),
                 ("end", Message.end_of_data())]

    async def overlapped():
        op = _operator("compact")
        gate = _Gate(op)
        job = _Job(op)
        gate.open()
        await job.feed(script)
        await job.forwarded(script)
        gate.close()
        # one more fire, held, and an idle watermark behind it
        more = _stream(59, n_batches=5, late=False)[-2:]
        before = job.outq.qsize()
        await job.feed(more)
        await _until(lambda: _in_flight(op), "the held tail")
        await job.feed(tail_msgs[:1])
        await asyncio.sleep(0.05)
        assert job.outq.qsize() == before
        gate.open()
        await job.feed(tail_msgs[1:])
        return await job.finished(), more

    got, more = run_async(overlapped())
    assert [m[0] for m in got[-4:]] == ["rows", "wm", "idle", "end_of_data"]

    async def serial():
        job = _Job(_operator("compact", overlapped=False))
        await job.feed(script + more + tail_msgs)
        return await job.finished()

    assert got == run_async(serial())


# -- (d) a tail that raises fails the task -------------------------------------


@pytest.mark.parametrize("then", ["batch", "watermark", "barrier", "close"])
def test_an_exception_in_the_tail_fails_the_task(run_async, then):
    script = _stream(61, n_batches=3, late=False)
    follow = {"batch": script[4:5], "watermark": [("wm", 9 * SEC)],
              "barrier": [("barrier", Message.barrier_msg(
                  CheckpointBarrier(1, 0, 0, False)))],
              "close": []}[then]

    async def go():
        op = _operator("compact")

        def boom(fire):
            raise RuntimeError("the tail's read-back failed")

        op.state.fire_tail = boom
        job = _Job(op)
        await job.feed(script[:4] + follow
                       + [("end", Message.end_of_data())])
        out = await job.finished()
        return job, out

    job, out = run_async(go())
    assert isinstance(job.runner.failed, RuntimeError)
    assert "read-back failed" in str(job.runner.failed)
    assert job.op._tail is None  # in no forgotten future
    # nothing of the failed fire left, and nothing after it but the end
    # (the first watermark closes no window and is forwarded as it comes)
    assert out == [("wm", script[1][1]), ("end_of_data",)]


def test_an_immediate_stop_abandons_the_tail(run_async):
    script = _stream(67, n_batches=2, late=False)

    async def go():
        op = _operator("compact")
        gate = _Gate(op)
        job = _Job(op)
        await job.feed(script)
        await _until(lambda: _in_flight(op), "the held tail")
        tail = op._tail
        await job.control.put(ControlMessage.stop(StopMode.IMMEDIATE))
        await asyncio.wait_for(job.runner.finished.wait(), 30)
        assert op._tail is None
        gate.open()
        with pytest.raises(asyncio.CancelledError):
            await tail
        return job.runner.failed

    assert run_async(go()) is None


# -- (f) the factor pane's drain follows the awaited tail ----------------------


@pytest.mark.parametrize("fire", ["compact", "sum_f64"])
def test_the_factor_drain_follows_an_in_flight_tail(run_async, fire):
    script = _stream(71, n_batches=4, late=False)
    cut = 6  # the third watermark fires; the drain comes on its heels

    async def overlapped():
        op = _operator(fire, factor=True)
        gate = _Gate(op)
        ctx, q = Context.new_for_test()
        loop = asyncio.get_running_loop()
        for i, (kind, x) in enumerate(script):
            if kind == "batch":
                await op.process_batch(x, ctx)
                continue
            gate.open()
            await op.settle(ctx)
            gate.close()
            await op.handle_watermark(x, ctx)
            if i == cut - 1:
                assert _in_flight(op)
                sent = q.qsize()
                loop.call_later(0.05, gate.open)
                await op.pre_checkpoint(None, ctx)
                # fired rows, their watermark, then the drained deltas
                assert op._tail is None and q.qsize() == sent + 3
                gate.close()
        gate.open()
        await op.on_close(ctx)
        return _drain(q)

    got = run_async(overlapped())
    want = run_async(_serial(fire, script, factor=True,
                             barrier_after=cut - 1))
    assert got == want
    at = [i for i, m in enumerate(got) if m == ("wm", script[cut - 1][1])]
    assert got[at[0] - 1][0] == "rows" and got[at[0] + 1][0] == "rows"


# -- the state's halves and the accounts ---------------------------------------


@pytest.mark.parametrize("fire", list(FIRES))
def test_a_tail_reads_its_handle_alone(fire):
    """Between a head and its tail the state takes new keys, grows and
    fires again: the first tail still returns what ``fire_panes`` would
    have returned at the head's time."""
    aggs, argmax = FIRES[fire]

    def state():
        st = KeyedBinState(aggs, SEC, 4 * SEC, capacity=64)
        if argmax:
            st.set_argmax_local("n", "max")
        return st

    def feed(st, batches):
        for _, b in batches:
            st._lookup_or_insert(b.key_hash)
            st.update(b.key_hash, b.timestamp, b.columns)

    script = [m for m in _stream(73, n_batches=4, late=False)
              if m[0] == "batch"]
    a, b = state(), state()
    feed(a, script[:2])
    feed(b, script[:2])
    want = a.fire_panes(2 * SEC)
    head = b.fire_head(2 * SEC)
    assert head is not None and b.last_fired_pane == a.last_fired_pane
    # 5,000 new keys: the planes and slot_to_key are replaced by the grow
    rng = np.random.default_rng(5)
    kh = rng.integers(1, 1 << 62, 5000).astype(np.uint64)
    ts = np.full(5000, 3 * SEC, np.int64)
    old = b.slot_to_key
    b._lookup_or_insert(kh)
    b.update(kh, ts, {"k": np.zeros(5000, np.int64), "v": np.ones(5000)})
    assert b.C > 64 and b.slot_to_key is not old
    second = b.fire_panes(4 * SEC)
    assert second is not None
    got = b.fire_tail(head)
    for x, y in zip(got[:1] + got[2:], want[:1] + want[2:]):
        np.testing.assert_array_equal(x, y)
    assert got.cols.keys() == want.cols.keys()
    for c in want.cols:
        np.testing.assert_array_equal(got.cols[c], want.cols[c])


def test_hold_span_and_overlap_counter(run_async):
    """``window.fire.hold`` ends when ``handle_watermark`` returns, inside
    the ``window.fire`` of the same watermark, which ends with the tail;
    the mesh state has no halves and keeps the serial await."""
    script = _stream(79, n_batches=3, late=False)

    async def go():
        op = _operator("sum_f64")
        gate = _Gate(op)
        ctx, q = Context.new_for_test()
        tracing.reset()
        perf.reset()
        for kind, x in script[:4]:
            if kind == "batch":
                await op.process_batch(x, ctx)
            else:
                await op.handle_watermark(x, ctx)
        assert _in_flight(op)
        await op.process_batch(script[4][1], ctx)
        await asyncio.sleep(0.05)
        gate.open()
        await op.on_close(ctx)

    run_async(go())
    spans = {}
    for name, _cat, start, dur, _pid, _tid, args in tracing.spans("window"):
        spans.setdefault(name, {})[args["watermark"]] = (start, start + dur)
    fired = script[3][1]
    hold, fire = spans["window.fire.hold"][fired], spans["window.fire"][fired]
    assert fire[0] <= hold[0] + 1 and hold[1] < fire[1]
    assert fire[1] - hold[1] > 40_000  # the gate's 50 ms are not held
    for child in ("window.fire.d2h", "window.fire.emit",
                  "window.fire.collect"):
        lo, hi = spans[child][fired]
        assert fire[0] <= lo and hi <= fire[1] + 1
    assert perf.counter("fire_overlap_batches") == 1
    assert set(spans["window.fire.hold"]) == set(spans["window.fire"])

    from arroyo_tpu.parallel.mesh_window import MeshKeyedBinState

    assert not hasattr(MeshKeyedBinState, "fire_head")
    assert not hasattr(MeshKeyedBinState, "fire_tail")
