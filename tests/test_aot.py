"""AOT compile stage (engine/aot.py) — the reference compiler.rs analog."""

import numpy as np
import pytest

from arroyo_tpu import Stream
from arroyo_tpu.engine.aot import (
    CompileReport,
    compile_program,
    deserialize_step,
    enable_persistent_cache,
    load_step,
    serialize_step,
    store_step,
)


def test_compile_program_ok():
    prog = (Stream.source("impulse", {"event_rate": 0.0, "message_count": 10})
            .map(lambda c: {"x": c["counter"] * 2}, name="m")
            .sink("blackhole", {}))
    report = compile_program(prog)
    assert report.ok and len(report.operators) == 3
    assert "ImpulseSource" in report.operators.values()


def test_compile_program_construction_error_fails_early():
    from arroyo_tpu.connectors.registry import (
        ConnectorMeta,
        register_connector,
    )

    class BrokenSink:
        def __init__(self, cfg):
            raise RuntimeError("cannot reach upstream service")

    register_connector(ConnectorMeta(
        name="_aot_broken", description="test", sink_factory=BrokenSink))
    prog = (Stream.source("impulse", {"event_rate": 0.0, "message_count": 10})
            .sink("_aot_broken", {}))
    # operator construction fails -> error lands in the report (not an
    # exception mid-scheduling), naming the operator
    report = compile_program(prog)
    assert not report.ok
    assert any("cannot reach upstream" in e for e in report.errors)


def test_filesystem_format_typo_rejected_at_plan_time(tmp_path):
    import pytest as _pytest

    with _pytest.raises(Exception):
        (Stream.source("impulse", {"event_rate": 0.0, "message_count": 10})
         .sink("filesystem", {"path": f"file://{tmp_path}",
                              "format": "not-a-format"}))


def test_compile_program_invalid_graph():
    from arroyo_tpu.graph.logical import AggKind, AggSpec

    prog = (Stream.source("impulse", {"event_rate": 0.0, "message_count": 10})
            .key_by("counter")
            .tumbling_aggregate(1000, [AggSpec(AggKind.COUNT, None, "c")])
            .sink("blackhole", {}))
    # window without watermark: validation error surfaces in the report
    report = compile_program(prog)
    assert not report.ok


def test_serialize_step_roundtrip(tmp_path):
    import jax.numpy as jnp

    def step(x, y):
        return (x * y).sum(axis=0), x + 1

    x = jnp.arange(12, dtype=jnp.float32).reshape(3, 4)
    y = jnp.ones((3, 4), jnp.float32)
    data = serialize_step(step, (x, y))
    assert isinstance(data, (bytes, bytearray)) and len(data) > 100

    fn = deserialize_step(data)
    out_s, out_x = fn(x, y)
    np.testing.assert_allclose(np.asarray(out_s), np.asarray((x * y).sum(0)))

    # artifact-store roundtrip (compiler.rs:247-259 analog)
    url = f"file://{tmp_path}/artifacts"
    store_step(url, "flagship_step", data)
    fn2 = load_step(url, "flagship_step")
    np.testing.assert_allclose(np.asarray(fn2(x, y)[1]),
                               np.asarray(x + 1))


_CACHE_DIR_PROBE = (
    "import jax; from arroyo_tpu.engine.aot import enable_persistent_cache;"
    "d = enable_persistent_cache();"
    "assert jax.config.jax_compilation_cache_dir == d; print(d)")


def _cache_dir_in_subprocess(env):
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run([sys.executable, "-c", _CACHE_DIR_PROBE], env=env,
                       cwd=repo, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-500:]
    return r.stdout.strip().splitlines()[-1]


def test_compile_cache_dir_rule(tmp_path):
    """One rule for where the compile cache lives: JAX_COMPILATION_CACHE_DIR
    if set, else the fixed in-checkout directory — the same in every
    process (a host-derived or temp path never hits on a fresh machine),
    and nothing under /tmp."""
    import glob
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    before = set(glob.glob("/tmp/*jax_cache*"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "placed")
    assert _cache_dir_in_subprocess(env) == str(tmp_path / "placed")
    del env["JAX_COMPILATION_CACHE_DIR"]
    first = _cache_dir_in_subprocess(env)
    # a second process, another cwd-independent resolution, other XLA flags
    # (the old default hashed the flags into the path)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    second = _cache_dir_in_subprocess(env)
    assert first == second == os.path.join(repo, ".jax_cache")
    assert set(glob.glob("/tmp/*jax_cache*")) == before
    # in this process conftest placed the cache in a temp dir: honoured
    assert enable_persistent_cache() == os.environ["JAX_COMPILATION_CACHE_DIR"]
