"""A traced run of the harness at the rehearsal's size on the CPU: every
per-layer metric that reads the program's own spans, phases and counters has
to come out with a value (the device's and the compiler's have nothing that
must be there without a chip)."""

import json
import os
import time

import pytest

import run
from harness import spec
from selfcheck import check

PROGRAM_READERS = {"counter_ratio", "phase_per_mev", "span_per_period"}


@pytest.fixture(scope="module")
def traced():
    with open(os.path.join(spec.BENCH_DIR, "selfcheck",
                           "rehearsal.json")) as f:
        sizes = json.load(f)
    cell = spec.load_cell("nexmark_q5.catchup", sizes["nexmark_q5"])
    device = {"platform": "test-not-a-chip", "kind": "cpu", "count": 1}
    return run.run_cell(cell, 2_147_483_901, 2.0, True, time.monotonic(),
                        device)


def test_manifest_holds_with_the_longer_list():
    check.manifest()


@pytest.mark.parametrize("metric", [
    entry["name"]
    for entry, reader in spec.load_cell("nexmark_q5.catchup").per_layer
    if reader["reader"] in PROGRAM_READERS])
def test_traced_run_reports_metric(traced, metric):
    assert traced["correct"], traced["compared"]
    got = traced["metrics"].get(metric)
    assert got is not None, sorted(traced["metrics"])
    assert got["value"] >= 0
    # no growth is the cell's design; the executor hop is an accelerator's
    if metric not in ("state_grows_in_window", "offload_wait_s_per_mev"):
        assert got["value"] > 0, (metric, got)
