"""Find a cell's data files by the names in ``BENCHMARK.json``.

A cell (an entry of ``workloads``) names a configuration and a traffic mix;
``BENCHMARK.json`` names the metrics.  Each of them is a file of its own:

* the configuration: the ``file`` of its ``configs`` entry;
* the traffic mix: ``<benchmark>/traffic/<traffic>.json`` (``kind`` names
  the job module ``<benchmark>/jobs/<kind>.py``);
* a metric: ``<benchmark>/end_to_end/<name>.json`` or
  ``<benchmark>/layer_metrics/<name>.json`` (``reader`` names the module
  ``<benchmark>/readers/<reader>.py``, ``args`` its arguments, ``what`` says
  what it is).  Unit, direction, source, layer, bound and the cells that
  report it are ``BENCHMARK.json``'s alone: a later cell that reports a
  metric which is there adds its name to that entry's ``workloads`` and
  touches no file here.  A reader that finds nothing to read returns
  nothing, and the metric is left out of the line.

Nothing here knows the name of a cell, a configuration or a metric.
"""

import dataclasses
import json
import os

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
METRIC_DIRS = {"end_to_end": "end_to_end", "per_layer": "layer_metrics"}


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict
    metrics: dict       # "end_to_end" / "per_layer" -> [BENCHMARK.json's
                        # entry + its file's reader and args]


def load_benchmark(root: str = ROOT) -> dict:
    return _json(os.path.join(root, "BENCHMARK.json"))


def cell_names(root: str = ROOT) -> list:
    return [w["name"] for w in load_benchmark(root)["workloads"]]


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = load_benchmark(root)
    bench_dir = os.path.join(root, bench["paths"][0])
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; it has "
                       f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = _json(os.path.join(bench_dir, "traffic", w["traffic"] + ".json"))
    metrics = {}
    for group, sub in METRIC_DIRS.items():
        metrics[group] = [
            {**_json(os.path.join(bench_dir, sub, m["name"] + ".json")), **m}
            for m in bench[group]
            if "workloads" not in m or name in m["workloads"]]
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                traffic_name=w["traffic"], config=config, traffic=traffic,
                metrics=metrics)
