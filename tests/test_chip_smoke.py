"""chip_smoke.py cannot rot between chip runs: its phases run here at a
tiny size on the CPU mesh against their own numpy references, and its
exit contract (non-zero and no result line off the TPU) is pinned."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import bench  # noqa: E402
import chip_smoke as cs  # noqa: E402


def test_query_text_is_the_benchmarks():
    for name in ("Q1", "Q5", "Q7", "Q8"):
        body = getattr(cs, name)
        assert getattr(bench, name) == bench.SRC + body, name
    assert (cs.CONFIG5.replace("memory://chipsmoke5", "memory://bench5")
            .replace("batch_size = '4096'", "batch_size = '{b}'")
            == bench.CONFIG5_SQL)


@pytest.fixture
def tiny(monkeypatch):
    """Tiny sizes + the device paths forced on: their ``auto`` policy
    means "on when the backend is not the CPU", so the CPU mesh reaches
    them only by force — the script itself sets no switch."""
    monkeypatch.setenv("ARROYO_DEVICE_JOIN", "on")
    monkeypatch.setenv("ARROYO_SESSION_DEVICE", "on")
    # one 10 s window is 1 M events at this rate, and a 1.3 M-event q8
    # promotes join partitions at this floor (12 M / 4096 on the chip)
    monkeypatch.setenv("ARROYO_JOIN_HOT_MIN_ROWS", "256")
    monkeypatch.setattr(cs, "RATE", 100_000)
    monkeypatch.setattr(cs, "BATCH", 16384)
    monkeypatch.setenv("STATE_CAPACITY", "4096")
    monkeypatch.setenv("BATCH_SIZE", "16384")
    from arroyo_tpu.config import reset_config

    reset_config()
    monkeypatch.setattr(cs, "CLOCK", cs.CompileClock())
    yield
    reset_config()


def test_phases_against_their_references(tiny):
    a = cs.phase_q5(300_000, 1, 0)
    assert a["panes_fired"] >= 5 and a["kernel_dispatches"] > 0
    assert a["state_bytes"] > 0 and a["keys"] > 4096  # capacity grew
    assert cs.phase_q1(50_000, 1)["rows"] == 46_000
    assert cs.phase_q7(50_000, 1)["rows"] >= 1
    q8 = cs.phase_q8(1_300_000, 1)
    assert q8["join_device_gather_rows"] > 0 and q8["panes_fired"] == 2
    c5 = cs.phase_config5(12_800, 1)
    assert c5["rows"] == 128 and c5["udaf_host_rows"] == 0


def test_a_wrong_answer_fails_the_phase(tiny, monkeypatch):
    real = cs.ref_q7

    def off_by_one(ev):
        rows, windows = real(ev)
        rows = rows.copy()
        rows[0, 1] += 1
        return rows, windows

    monkeypatch.setattr(cs, "ref_q7", off_by_one)
    with pytest.raises(AssertionError, match="q7: 1 rows differ"):
        cs.phase_q7(50_000, 1)


def test_a_zero_device_counter_fails_the_phase(tiny, monkeypatch):
    monkeypatch.setenv("ARROYO_DEVICE_JOIN", "off")
    with pytest.raises(AssertionError, match="join_device_gather_rows"):
        cs.phase_q8(200_000, 1)


def test_exits_nonzero_without_a_result_when_not_on_tpu(capsys, tiny):
    # (``tiny`` owns STATE_CAPACITY/BATCH_SIZE, which main() sets for its
    # own process: the fixture restores them for the tests that follow)
    assert cs.main([]) == cs.EXIT_NOT_TPU
    out = capsys.readouterr().out
    assert "platform=cpu" in out and '"ok"' not in out


def test_fails_alone_in_a_bare_directory(tmp_path):
    import shutil

    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode != 0 and '"ok"' not in r.stdout


def test_bench_fails_without_tpu_and_without_explicit_cpu():
    """No TPU and no JAX_PLATFORMS=cpu from the caller: non-zero exit and
    no metric line — never a CPU number under a device metric's name."""
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    r = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                       env=dict(env, BENCH_EVENTS="20000"), cwd=REPO,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"metric"' not in r.stdout and '"value"' not in r.stdout
    assert "JAX_PLATFORMS=cpu" in r.stderr  # says how to choose the CPU


def test_require_backend_refuses_jaxs_quiet_fallback(monkeypatch):
    from jax._src import xla_bridge

    from arroyo_tpu.config import require_backend

    assert require_backend() == "cpu"  # conftest chose it in so many words
    monkeypatch.setattr(xla_bridge, "_backend_errors",
                        {"tpu": "TPU initialization failed"})
    with pytest.raises(RuntimeError, match="JAX_PLATFORMS=cpu"):
        require_backend()
