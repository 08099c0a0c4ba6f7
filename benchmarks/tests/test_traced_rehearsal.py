"""A traced run of the harness at the rehearsal's size on the CPU: every
per-layer metric that reads the program's own spans, phases and counters has
to come out with a value (the device's and the compiler's have nothing that
must be there without a chip)."""

import time

import pytest

import run
from harness import spec
from selfcheck import check

PROGRAM_READERS = {"counter_ratio", "phase_per_mev", "span_per_period"}


CELLS = [w["name"] for w in spec.manifest()["workloads"]]


@pytest.fixture(scope="module")
def traced():
    """One traced run per cell, made when its first metric asks for it."""
    runs = {}

    def of(workload):
        if workload not in runs:
            cell = spec.load_cell(workload, rehearsal=True)
            device = {"platform": "test-not-a-chip", "kind": "cpu",
                      "count": 1}
            runs[workload] = run.run_cell(cell, 2_147_483_901, 2.0, True,
                                          time.monotonic(), device)
        return runs[workload]

    return of


def test_manifest_holds_with_the_longer_list():
    check.manifest()


@pytest.mark.parametrize("workload,metric", [
    (workload, entry["name"])
    for workload in CELLS
    for entry, reader in spec.load_cell(workload).per_layer
    if reader["reader"] in PROGRAM_READERS])
def test_traced_run_reports_metric(traced, workload, metric):
    result = traced(workload)
    assert result["correct"], result["compared"]
    got = result["metrics"].get(metric)
    assert got is not None, sorted(result["metrics"])
    assert got["value"] >= 0
    # no growth is the cell's design; the executor hop is an accelerator's
    if metric not in ("state_grows_in_window", "offload_wait_s_per_mev"):
        assert got["value"] > 0, (metric, got)
