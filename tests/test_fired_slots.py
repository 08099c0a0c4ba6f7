"""A fired cell keeps its slot from the state to the output batch
(``FiredPanes.slots``): the single-device state hands over the slot it
fired, on every route and after a grow or a restore, and looks nothing up;
the mesh state, which fires cells of a shard, finds the slot by hash inside
itself and counts it; ``BinAggOperator._fired_batch`` gathers the key
columns by the slots and asks neither which state it holds."""

import collections

import numpy as np
import pytest

from arroyo_tpu import AggKind, AggSpec, Batch
from arroyo_tpu.obs import perf
from arroyo_tpu.ops.keyed_bins import FiredPanes, KeyedBinState
from arroyo_tpu.parallel.mesh_window import MeshKeyedBinState
from arroyo_tpu.types import hash_columns

SEC = 1_000_000
COUNT = (AggSpec(AggKind.COUNT, None, "n"),)
COUNT_SUM = COUNT + (AggSpec(AggKind.SUM, "v", "s"),)


def _key_batches(rng, n_keys, n_batches, rows):
    """Batches of random 64-bit hashes drawn from ``n_keys`` keys: a key's
    slot follows its first arrival, so slot order is not hash order."""
    keys = rng.integers(1, 1 << 63, n_keys).astype(np.uint64)
    return [keys[rng.integers(0, n_keys, rows)] for _ in range(n_batches)]


def _feed(st, batches, t0, span):
    for i, kh in enumerate(batches):
        ts = t0 + (np.arange(len(kh), dtype=np.int64) * 7919 + i) % span
        st._lookup_or_insert(kh)  # as BinAggOperator.process_batch does
        st.update(kh, ts, {"v": np.ones(len(kh))})


# route -> (aggs, width in slides, argmax-local, fire)
ROUTES = {
    "compact_w5": (COUNT_SUM, 5, False,
                   lambda st: st.fire_panes(2 * SEC)),
    "argmax": (COUNT, 5, True, lambda st: st.fire_panes(2 * SEC)),
    "dense_drain_w1": (COUNT_SUM, 1, False, lambda st: st.drain_deltas()),
}


@pytest.mark.parametrize("stage", ["fresh", "after_grow", "after_restore"])
@pytest.mark.parametrize("route", list(ROUTES))
def test_single_device_state_hands_over_the_slots_it_fired(
        rng, route, stage):
    aggs, w, argmax, fire = ROUTES[route]
    # 700 keys: a capacity of 64 grows four times on the way
    capacity = 64 if stage == "after_grow" else 1024

    def new_state():
        st = KeyedBinState(aggs, SEC, w * SEC, capacity=capacity)
        if argmax:
            st.set_argmax_local("n", "max")
        return st

    st = new_state()
    batches = _key_batches(rng, 700, 6, 400)
    _feed(st, batches[:3], 0, SEC)
    if stage == "after_restore":
        snap = {k: np.asarray(v) for k, v in st.snapshot().items()}
        st = new_state()
        st.restore(snap)
    _feed(st, batches[3:], 0, SEC)
    if stage == "after_grow":
        assert st.C == 1024 and st.next_slot > 64
    # slot order is not hash order, or the test would show nothing
    assert (np.diff(st.slot_to_key[:st.next_slot].astype(np.float64))
            < 0).any()

    perf.reset()
    called = collections.Counter()
    for name in ("_emit_compact", "_emit_argmax", "_flatten_dense"):
        orig = getattr(st, name)

        def counted(*a, _orig=orig, _name=name, **kw):
            called[_name] += 1
            return _orig(*a, **kw)

        setattr(st, name, counted)
    fired = fire(st)
    want_route = {"compact_w5": "_emit_compact", "argmax": "_emit_argmax",
                  "dense_drain_w1": "_flatten_dense"}[route]
    assert dict(called) == {want_route: 1}

    assert isinstance(fired, FiredPanes) and len(fired) == 5
    keys, slots = fired.keys, fired.slots
    assert len(keys) == len(slots) == len(fired.window_end) > 0
    if not argmax:  # an all-keys fire: every key of the first second
        assert len(np.unique(keys)) == st.next_slot
    assert slots.dtype.kind == "i" and slots.max() < st.next_slot
    np.testing.assert_array_equal(st.slot_to_key[slots], keys)
    np.testing.assert_array_equal(
        st.slot_of_sorted[np.searchsorted(st.key_sorted, keys)], slots)
    assert perf.counter("fire_slot_lookups") == 0


@pytest.mark.parametrize("drain", [False, True], ids=["fire", "drain"])
def test_mesh_state_finds_its_slots_itself_and_counts_them(rng, drain):
    import jax

    if len(jax.devices()) < 4 or not jax.config.jax_enable_x64:
        pytest.skip("needs four virtual devices and x64")
    w = 1 if drain else 2
    st = MeshKeyedBinState(COUNT_SUM, SEC, w * SEC, capacity=64, n_shards=4)
    single = KeyedBinState(COUNT_SUM, SEC, w * SEC, capacity=64)
    # the operator's slot -> key column store, filled as process_batch does
    keycol = np.full(4096, -1, np.int64)
    for kh in _key_batches(rng, 300, 5, 200):
        ts = (np.arange(len(kh), dtype=np.int64) * 7919) % SEC
        for state in (st, single):
            slots = state._lookup_or_insert(kh)
            state.update(kh, ts, {"v": np.ones(len(kh))})
            if state is st:
                keycol[slots] = (kh % np.uint64(1 << 40)).astype(np.int64)

    def fire(state):
        return state.drain_deltas() if drain else state.fire_panes(
            1 << 60, final=True)

    perf.reset()
    want = fire(single)
    assert perf.counter("fire_slot_lookups") == 0
    fired = fire(st)
    # one shape of fired value for both states
    assert type(fired) is type(want) is FiredPanes
    assert fired._fields == want._fields
    assert [np.asarray(f).dtype for f in (fired.keys, fired.window_end,
                                          fired.slots)] == \
        [np.asarray(f).dtype for f in (want.keys, want.window_end,
                                       want.slots)]
    assert len(fired.slots) == len(fired.keys) == len(want.keys) > 300 * (
        w - 1)
    assert perf.counter("fire_slot_lookups") == len(fired.keys)
    np.testing.assert_array_equal(st.slot_to_key[fired.slots], fired.keys)
    # the slots gather the key column that belongs to each row's hash
    np.testing.assert_array_equal(
        keycol[fired.slots],
        (fired.keys % np.uint64(1 << 40)).astype(np.int64))

    # and the rows are the single-device state's
    def rows(f):
        return sorted(zip(f.keys.tolist(), f.window_end.tolist(),
                          f.cols["n"].tolist(), f.cols["s"].tolist()))

    assert rows(fired) == rows(want)


def _hop_count_through_sql(monkeypatch, rng, mesh):
    """SELECT k, HOP(2 s, 10 s), count(*) over 12 batches that bring 3,000
    keys in an order of their own, from a state of 256 slots: (events'
    timestamps, events' keys, sink rows as one batch)."""
    from arroyo_tpu import config as program_config
    from arroyo_tpu.connectors.memory import clear_sink, sink_output
    from arroyo_tpu.engine.engine import LocalRunner
    from arroyo_tpu.sql import SchemaProvider, plan_sql

    monkeypatch.setenv("STATE_CAPACITY", "256")
    monkeypatch.setenv("ARROYO_MESH", "4" if mesh else "off")
    program_config.reset_config()
    n_batches, rows = 12, 1500
    pool = rng.permutation(1 << 20)[:3000].astype(np.int64) * 7 + 3
    ts = np.sort(rng.integers(0, 6 * SEC, n_batches * rows)).astype(np.int64)
    # a batch draws from the keys seen so far and 250 new ones
    keys = np.concatenate([pool[rng.integers(0, 250 * (i + 1), rows)]
                           for i in range(n_batches)])
    p = SchemaProvider()
    cuts = [slice(i * rows, (i + 1) * rows) for i in range(n_batches)]
    p.add_memory_table("events", {"k": "i"},
                       [Batch(ts[c], {"k": keys[c]}) for c in cuts])
    clear_sink("fired_slots")
    perf.reset()
    try:
        LocalRunner(plan_sql(
            "CREATE TABLE out WITH (connector='memory', name='fired_slots');"
            "INSERT INTO out SELECT k, HOP(INTERVAL '2' SECOND, INTERVAL"
            " '10' SECOND) as window, count(*) as num "
            "FROM events GROUP BY 1, 2", p)).run()
        return ts, keys, Batch.concat(sink_output("fired_slots"))
    finally:
        clear_sink("fired_slots")
        monkeypatch.undo()
        program_config.reset_config()


@pytest.mark.parametrize("mesh", [False, True], ids=["single", "mesh"])
def test_sql_hop_count_rows_carry_their_own_key_column(
        monkeypatch, rng, mesh):
    import jax

    if mesh and (len(jax.devices()) < 4 or not jax.config.jax_enable_x64):
        pytest.skip("needs four virtual devices and x64")
    if not mesh:
        # the operator's batch may look no key up by hash: fail if it tries
        from arroyo_tpu.engine.operators_window import BinAggOperator

        inner = BinAggOperator._fired_batch
        seen = []

        def guarded(self, fired, lat=None):
            assert isinstance(self.state, KeyedBinState)

            def refuse(*_a, **_kw):
                raise AssertionError("np.searchsorted in _fired_batch")

            with pytest.MonkeyPatch.context() as mp:
                mp.setattr(np, "searchsorted", refuse)
                out = inner(self, fired, lat)
            seen.append(len(fired.slots))
            return out

        monkeypatch.setattr(BinAggOperator, "_fired_batch", guarded)
    ts, keys, out = _hop_count_through_sql(monkeypatch, rng, mesh)
    if not mesh:
        assert seen and sum(seen) == len(out)
        # 3,000 keys into 256 slots: the last fire comes after the grows
        assert perf.counter("state_grows") >= 2
        assert perf.counter("fire_slot_lookups") == 0
    else:
        assert perf.counter("fire_slot_lookups") == len(out)

    # every row's key column is the one its key_hash is the hash of
    assert out.key_hash is not None and len(out) > 3000
    np.testing.assert_array_equal(hash_columns([out.columns["k"]]),
                                  out.key_hash)
    # and the rows are a plain count: an event in slide b feeds the five
    # windows that end at (b + 1 .. b + 5) slides
    want = collections.Counter()
    for t, k in zip((ts // (2 * SEC)).tolist(), keys.tolist()):
        for j in range(1, 6):
            want[k, (t + j) * 2 * SEC] += 1
    got = collections.Counter()
    for k, end, num in zip(out.columns["k"].tolist(),
                           out.columns["window_end"].tolist(),
                           out.columns["num"].tolist()):
        assert (k, end) not in got, f"pane emitted twice: {(k, end)}"
        got[k, end] = num
    assert got == want
