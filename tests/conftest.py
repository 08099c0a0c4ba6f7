"""Test configuration: force JAX onto a virtual 8-device CPU mesh so sharding
tests run without TPU hardware, and keep the compile cache of a test run
out of the checkout.
"""

import atexit
import os
import shutil
import tempfile

os.environ["JAX_PLATFORMS"] = "cpu"  # tests always run on the CPU mesh

# The program's compile cache is JAX_COMPILATION_CACHE_DIR if set, else
# <checkout>/.jax_cache (engine/aot.py).  Tests must not fill the latter:
# the chip tool copies the checkout as it stands on disk, and XLA:CPU
# executables are dead weight (and load-time warnings) on the chip
# machine.  A per-session temp directory, inherited by every worker
# subprocess a test spawns, removed at exit.
_cache_dir = tempfile.mkdtemp(prefix="arroyo-test-jax-cache-")
os.environ["JAX_COMPILATION_CACHE_DIR"] = _cache_dir
atexit.register(shutil.rmtree, _cache_dir, ignore_errors=True)

# arroyosan runtime sanitizer: tier-1 runs with the streaming-invariant
# assertions armed (watermark monotonicity, barrier alignment, coalescer
# flush-before-control, snapshot/upload atomicity, checkpoint
# completeness) — a violation fails the offending test with the event
# ring instead of passing on corrupted output.  setdefault so a test or
# dev run can still opt out with ARROYO_SANITIZE=0.
os.environ.setdefault("ARROYO_SANITIZE", "1")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import asyncio  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def run_async():
    def _run(coro):
        return asyncio.run(coro)

    return _run


@pytest.fixture
def rng():
    return np.random.default_rng(42)
