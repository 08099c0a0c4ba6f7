"""The timed path broken underneath a whole run of the harness (all but its
look for a chip), at the rehearsal's size on the CPU: ``correct`` has to come
out false for every fault a cell can have, and true for the sound program."""

import time

import numpy as np
import pytest

import run
from harness import spec

FAULTS = ["sound", "answer_altered", "half_of_each_batch_left_out",
          "state_left_unchanged"]


def _plant(fault, monkeypatch, cell):
    if fault == "answer_altered":
        from arroyo_tpu.connectors.memory import MemorySink

        sound = MemorySink.process_batch
        seen = []

        async def altered(self, batch, ctx, side=0):
            seen.append(len(batch))
            if len(seen) == 3 and len(batch):  # one row of the third batch
                col = cell.config["result_columns"][-1]
                batch.columns[col] = np.array(batch.columns[col])
                batch.columns[col][0] += 1
            await sound(self, batch, ctx, side)

        monkeypatch.setattr(MemorySink, "process_batch", altered)
    elif fault == "half_of_each_batch_left_out":
        from arroyo_tpu.connectors.nexmark import NexmarkGenerator
        from arroyo_tpu.types import Batch

        sound = NexmarkGenerator.next_batch

        def halved(self, size):
            b, nums = sound(self, size)
            h = len(nums) // 2
            return Batch(b.timestamp[:h],
                         {c: v[:h] for c, v in b.columns.items()}), nums

        monkeypatch.setattr(NexmarkGenerator, "next_batch", halved)
    elif fault == "state_left_unchanged":
        from arroyo_tpu.ops.keyed_bins import KeyedBinState

        sound = KeyedBinState.update
        calls = []

        def skipped(self, *a, **kw):
            calls.append(1)
            if len(calls) % 7 == 0:  # the step returns its state unchanged
                return None
            return sound(self, *a, **kw)

        monkeypatch.setattr(KeyedBinState, "update", skipped)


@pytest.mark.parametrize("fault", FAULTS)
@pytest.mark.parametrize("workload", [w["name"] for w in
                                      spec.manifest()["workloads"]])
def test_fault_is_not_correct(workload, fault, monkeypatch):
    cell = spec.load_cell(workload, rehearsal=True)
    _plant(fault, monkeypatch, cell)
    device = {"platform": "test-not-a-chip", "kind": "cpu", "count": 1}
    result = run.run_cell(cell, 2_147_483_900, 1.0, False, time.monotonic(),
                          device)
    assert result["correct"] is (fault == "sound"), result["compared"]
    assert list(result)[-1] == "compared"
