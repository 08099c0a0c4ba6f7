"""A second configuration and its cell land as new files and appended
entries: a copy of the benchmark's tree is laid out in ``tmp_path`` with links
to every file that is there, and beside them a renamed copy of q5's
configuration, query, reference and rehearsal sizes and a metric over a span
that nobody records.  The yardstick's own tools have to resolve both cells
from there, and nothing under ``benchmarks/`` may be written."""

import json
import os
import shutil
import time

import pytest

import references
import rehearse
import run
from harness import spec
from selfcheck import check

REAL_BENCH = spec.BENCH_DIR
FIRST, SECOND = "nexmark_q5", "second_config"
GHOST = "ghost_span_ms_p50"


def _listing():
    out = {}
    for folder, _, files in os.walk(REAL_BENCH):
        if "__pycache__" in folder:
            continue
        for name in files:
            st = os.stat(os.path.join(folder, name))
            out[os.path.join(folder, name)] = (st.st_size, st.st_mtime_ns)
    return out


def _copy_renamed(src, dst, **changes):
    with open(src) as f:
        stated = json.load(f)
    stated.update(changes)
    with open(dst, "w") as f:
        json.dump(stated, f)


@pytest.fixture
def tree(tmp_path, monkeypatch):
    """``tmp_path`` as a checkout: the manifest with the second configuration,
    its cell and the ghost metric appended, every directory a cell's files
    are looked up in linked file by file, and the new files beside them."""
    bench = tmp_path / "benchmarks"
    for sub in ("configs", "queries", "metrics", "traffic", "references",
                os.path.join("selfcheck", "rehearsal")):
        os.makedirs(bench / sub)
        for name in os.listdir(os.path.join(REAL_BENCH, sub)):
            if name.endswith((".json", ".sql")):
                os.symlink(os.path.join(REAL_BENCH, sub, name),
                           bench / sub / name)
    _copy_renamed(os.path.join(REAL_BENCH, "configs", FIRST + ".json"),
                  bench / "configs" / (SECOND + ".json"), name=SECOND,
                  source="the second deployment of a test",
                  query=f"queries/{SECOND}.sql", reference=SECOND)
    for sub, ext in (("queries", ".sql"), ("references", ".py"),
                     (os.path.join("selfcheck", "rehearsal"), ".json")):
        shutil.copy(os.path.join(REAL_BENCH, sub, FIRST + ext),
                    bench / sub / (SECOND + ext))
    with open(bench / "metrics" / (GHOST + ".json"), "w") as f:
        json.dump({"reader": "span_per_period", "span": "no.such.span",
                   "percentile": 50}, f)
    manifest = spec.manifest()
    first = manifest["configs"][0]
    manifest["configs"].append(dict(
        first, name=SECOND, source="the second deployment of a test",
        file=f"benchmarks/configs/{SECOND}.json"))
    cell = SECOND + ".catchup"
    manifest["workloads"].append(dict(manifest["workloads"][0], name=cell,
                                      config=SECOND))
    for m in manifest["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(cell)
    manifest["per_layer"].append(dict(
        manifest["per_layer"][0], name=GHOST, unit="ms", workloads=[cell]))
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(manifest, f)
    monkeypatch.setattr(spec, "ROOT", str(tmp_path))
    monkeypatch.setattr(spec, "BENCH_DIR", str(bench))
    monkeypatch.setattr(references, "__path__", list(references.__path__)
                        + [str(bench / "references")])
    return cell


def test_second_configuration_is_files_and_entries(tree, capsys):
    before = _listing()
    check.manifest()
    check.controls()
    assert {w["name"] for w in spec.manifest()["workloads"]} == {
        FIRST + ".catchup", tree}
    assert spec.load_cell(tree).reference.__name__ == "references." + SECOND
    assert rehearse.main(["--seconds", "1"]) == 0
    out = capsys.readouterr().out
    assert out.count("REHEARSAL (not a chip result):") == 2, out
    assert out.rstrip().endswith("rehearsal passed")
    assert _listing() == before


def test_configuration_without_rehearsal_sizes_is_named(tree):
    os.remove(os.path.join(spec.BENCH_DIR, "selfcheck", "rehearsal",
                           SECOND + ".json"))
    with pytest.raises(FileNotFoundError, match=SECOND):
        check.controls()


def test_metric_over_a_span_nobody_recorded_is_left_out(tree):
    cell = spec.load_cell(tree, rehearsal=True)
    assert GHOST in [entry["name"] for entry, _ in cell.per_layer]
    device = {"platform": "test-not-a-chip", "kind": "cpu", "count": 1}
    result = run.run_cell(cell, 2_147_483_902, 1.0, True, time.monotonic(),
                          device)
    line = json.loads(json.dumps(result))  # the line main() prints
    assert line["correct"], line["compared"]
    assert GHOST not in line["metrics"]
    assert line["metrics"]["fire_ms_p50"]["value"] > 0
