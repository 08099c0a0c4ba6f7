"""Records ``sample.xplane.pb`` and ``sample_trace.json`` on the chip: eight
rounds of a short chain of matrix products, the host asleep for 30 ms after
each, between the two window marks.  Run once by the builder:

    chiprun -- python3 benchmarks/selfcheck/record_trace.py chiprun_out/sample
"""

import glob
import json
import os
import shutil
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness import trace_reduce  # noqa: E402
from harness.drive import HostSampler, start_trace, stop_trace  # noqa: E402


def main(out_dir):
    import jax
    import jax.numpy as jnp

    assert jax.devices()[0].platform == "tpu", jax.devices()
    step = jax.jit(lambda x: (x @ x) / jnp.float32(512.0))
    x = step(jnp.ones((2048, 2048), jnp.float32)).block_until_ready()
    trace_dir = os.path.join(out_dir, "trace")
    sampler = HostSampler(threading.get_ident())
    start_trace(trace_dir)
    t0 = time.monotonic()
    sampler.start()
    slept = 0.0
    for _ in range(8):
        for _ in range(20):
            x = step(x)
        x.block_until_ready()
        s0 = time.monotonic()
        time.sleep(0.03)
        slept += time.monotonic() - s0
    sampler.stop()
    window = time.monotonic() - t0
    stop_trace()
    path = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    shutil.copy(path, os.path.join(out_dir, "sample.xplane.pb"))
    reduced = trace_reduce.reduce_file(path, sampler.samples)
    with open(os.path.join(out_dir, "sample_trace.json"), "w") as f:
        json.dump({"devices": len(jax.devices()), "host_window_s": window,
                   "host_slept_s": slept, "host_samples": sampler.samples,
                   "reduced": reduced}, f)
    shutil.rmtree(trace_dir)
    print(os.path.getsize(os.path.join(out_dir, "sample.xplane.pb")),
          json.dumps(reduced))


if __name__ == "__main__":
    os.makedirs(sys.argv[1], exist_ok=True)
    main(sys.argv[1])
