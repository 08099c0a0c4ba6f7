"""Everything a cell is made of, found by the names in ``BENCHMARK.json``:
``configs/<configuration>.json``, ``traffic/<mix>.json``,
``metrics/<metric>.json`` and, named by the configuration's file,
``queries/*.sql`` and ``references/<name>.py``.  A configuration also brings
``selfcheck/rehearsal/<configuration>.json``, the sizes at which the
yardstick's own tools (``rehearse.py``, ``selfcheck``, the tests) run its
cells on the CPU.  A later PR adds a cell, a mix, a configuration or a metric
over an existing reader by adding files and entries; no file that is there
needs an edit (``tests/test_second_config.py`` holds the harness to it)."""

import importlib
import json
import os
from dataclasses import dataclass

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _merged(base, over):
    out = dict(base)
    for k, v in over.items():
        out[k] = (_merged(out[k], v)
                  if isinstance(v, dict) and isinstance(out.get(k), dict)
                  else v)
    return out


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list  # BENCHMARK.json entries that this cell reports
    per_layer: list  # (entry, metrics/<name>.json) pairs likewise
    reference: object  # module with rows(stream, t_end_micros, **faults)

    def sql(self, seed):
        """The job as a user would submit it: the source table over the
        stream and traffic options, then the query, letter for letter."""
        stream, cfg = self.config["stream"], self.config
        opts = dict(self.traffic["source_options"], seed=seed,
                    event_rate=stream["event_rate"],
                    base_time_micros=stream["base_time_micros"],
                    num_events=int(stream["event_rate"] * cfg["stream_s"]))
        with open(os.path.join(BENCH_DIR, cfg["source_table"])) as f:
            table = f.read().format(**opts)
        with open(os.path.join(BENCH_DIR, cfg["query"])) as f:
            return table + f.read()

    def reference_stream(self, seed, t_end_micros):
        """Arguments of the reference's generator for the events before
        ``t_end_micros`` (absolute event time): whole source batches up to
        the one that holds the last such event."""
        stream = self.config["stream"]
        size = self.traffic["source_options"]["batch_size"]
        delay = max(int(1_000_000.0 / stream["event_rate"]), 1)
        first_beyond = (t_end_micros - stream["base_time_micros"]) // delay
        whole = -(-int(first_beyond) // size) * size
        total = int(stream["event_rate"] * self.config["stream_s"])
        return {"seed": seed, "n_events": min(whole, total),
                "batch_size": size, "before_micros": t_end_micros,
                "base_time_micros": stream["base_time_micros"],
                "event_rate": stream["event_rate"]}


def _applies(entry, cell_name):
    return "workloads" not in entry or cell_name in entry["workloads"]


def manifest():
    return _json(ROOT, "BENCHMARK.json")


def rehearsal_sizes(config_name):
    """The configuration's ``selfcheck/rehearsal/<name>.json``: sizes merged
    over the configuration and, under their ``traffic`` key, over the mix."""
    path = os.path.join(BENCH_DIR, "selfcheck", "rehearsal",
                        config_name + ".json")
    if not os.path.exists(path):
        raise FileNotFoundError(
            f"configuration {config_name!r} brings no rehearsal sizes: "
            f"{path} is missing")
    return _json(path)["sizes"]


def load_cell(name, rehearsal=False):
    """``rehearsal`` (the yardstick's own tools and tests only) shrinks the
    cell to its configuration's rehearsal sizes; never a chip result."""
    bench = manifest()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json: "
                       f"{sorted(cells)}")
    w = cells[name]
    files = {c["name"]: c["file"] for c in bench["configs"]}
    overrides = dict(rehearsal_sizes(w["config"]) if rehearsal else {})
    traffic = _merged(_json(BENCH_DIR, "traffic", w["traffic"] + ".json"),
                      overrides.pop("traffic", {}))
    config = _merged(_json(ROOT, files[w["config"]]), overrides)
    reference = importlib.import_module("references." + config["reference"])
    per_layer = [(m, _json(BENCH_DIR, "metrics", m["name"] + ".json"))
                 for m in bench["per_layer"] if _applies(m, name)]
    return Cell(name, w["chips"], config, traffic,
                [m for m in bench["end_to_end"] if _applies(m, name)],
                per_layer, reference)
