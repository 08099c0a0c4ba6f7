"""Rehearsal on the CPU, at a tiny size: the self-checks of the tick
arithmetic, the trace reducer and the roofline reader, the contract's limits
on ``BENCHMARK.json``, the controls, and one run of every cell through the
harness at the sizes of its configuration's
``selfcheck/rehearsal/<configuration>.json``.  It debugs the benchmark's own
files; nothing it prints is a chip result.

    JAX_PLATFORMS=cpu python3 benchmarks/rehearse.py [--seconds 3] [--trace 1]

With ``--chip 1`` (the builder's, on the chip) the cells run at their own
size on the devices JAX has, and each run also reads the configuration's
controls and ``also_read`` faults at the cell's own size:

    python3 benchmarks/rehearse.py --chip 1 --workload <cell> --seed <n> --seconds <s>
"""

import argparse
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)

import run  # noqa: E402  (puts the checkout's root on the path)
from harness import spec  # noqa: E402
from selfcheck import check  # noqa: E402


def main(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--seed", type=int, default=2_147_483_777)
    ap.add_argument("--workload", action="append")
    ap.add_argument("--chip", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    check.manifest()
    check.ticks()
    check.trace_reducer()
    check.roofline()
    check.controls()
    check.generator()
    for w in spec.manifest()["workloads"]:
        if args.workload and w["name"] not in args.workload:
            continue
        cell = spec.load_cell(w["name"], rehearsal=not args.chip)
        if args.chip:
            device = run.device_report(cell.chips)
            if device is None:
                return run.EXIT_NO_CHIP
        else:
            device = {"platform": "rehearsal-not-a-chip", "kind": "cpu",
                      "count": 1}
        result = run.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), time.monotonic(), device,
                              control=True)
        print("CONTROLS AT THE CELL'S OWN SIZE:" if args.chip else
              "REHEARSAL (not a chip result):", json.dumps(result))
        if not result["correct"]:
            return 1
    print("rehearsal passed")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
