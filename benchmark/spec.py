"""BENCHMARK.json and the files it names, resolved for one workload.

Everything is found by name: a workload names its configuration and its
traffic mix, the configuration's entry names its file, the traffic mix is
``benchmark/traffic/<traffic>.json``, a per-layer metric ``<name>`` is read
by ``benchmark/layer_metrics/<name>.py`` (dots and dashes in the name
become underscores; ``<name>.<suffix>`` falls back to ``<name>``'s reader). An unknown name anywhere is an error, never a default.
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class SpecError(ValueError):
    pass


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config_file: str
    config: dict            # the configuration's file, read
    traffic_name: str
    traffic_file: str
    traffic: dict           # the traffic mix's file, read
    end_to_end: list        # metric entries this cell reports (setup_s among them)
    per_layer: list
    run_seconds: int


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _read_json(path: str, what: str) -> dict:
    if not os.path.isfile(path):
        raise SpecError(f"{what}: no file {path}")
    with open(path) as f:
        return json.load(f)


def _in_cell(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(workload: str, root: str = ROOT) -> Cell:
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SpecError(f"unknown workload {workload!r}; BENCHMARK.json has "
                        f"{sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"workload {workload}: unknown config {w['config']!r}")
    config_file = os.path.join(root, configs[w["config"]]["file"])
    config = _read_json(config_file, f"config {w['config']}")
    traffic_file = os.path.join(root, "benchmark", "traffic",
                                w["traffic"] + ".json")
    traffic = _read_json(traffic_file, f"traffic {w['traffic']}")
    e2e = [m for m in bench["end_to_end"] if _in_cell(m, workload)]
    per_layer = [m for m in bench["per_layer"] if _in_cell(m, workload)]
    return Cell(workload, int(w["chips"]), w["config"], config_file, config,
                w["traffic"], traffic_file, traffic, e2e, per_layer,
                int(bench["run_seconds"]))


def module_name(metric: str) -> str:
    return re.sub(r"[^0-9A-Za-z_]", "_", metric)


def layer_reader(metric: str):
    """The ``read(ctx)`` of a per-layer metric's own module. A quantity
    split by what it moves (``queue_wait_mean_ms.docqa``) may share the
    reader of its base name (``queue_wait_mean_ms``)."""
    for name in (metric, metric.rsplit(".", 1)[0]):
        try:
            return importlib.import_module(
                "benchmark.layer_metrics." + module_name(name)).read
        except ModuleNotFoundError:
            continue
    raise SpecError(f"per-layer metric {metric!r} has no reader "
                    f"benchmark/layer_metrics/{module_name(metric)}.py")


def generator(kind: str):
    try:
        mod = importlib.import_module("benchmark.generators." + kind)
    except ModuleNotFoundError as e:
        raise SpecError(f"traffic generator {kind!r} has no module "
                        f"benchmark/generators/{kind}.py") from e
    return mod.generate
