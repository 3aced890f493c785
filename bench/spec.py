"""Where the benchmark finds its pieces, by the names in BENCHMARK.json.

A cell names a configuration and a traffic mix; a per-layer metric names a
reader. Each lives in a file of its own under this directory, found by name:

  configs/<config>.json      the model, its precision policy and its check
  traffic/<traffic>.json     a mix: {"generator": <name>, ...parameters}
  traffic/<generator>.py     a generator, one class `Generator`
  metrics/<metric>.py        a per-layer reader, one function `read(run)`

so a later change adds a configuration, a mix or a metric as new files and
new entries in BENCHMARK.json, and edits nothing that is here.
"""
from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                   f"{[w['name'] for w in bench['workloads']]}")


def config(bench: dict, name: str, root: str = ROOT) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return load_json(os.path.join(root, c["file"]))
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def traffic(name: str, here: str = HERE) -> dict:
    return load_json(os.path.join(here, "traffic", f"{name}.json"))


def _module(path: str, what: str):
    if not os.path.exists(path):
        raise FileNotFoundError(f"{what}: no file {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_" + os.path.splitext(os.path.basename(path))[0]
        .replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def generator(name: str, here: str = HERE):
    """The Generator class of traffic/<name>.py."""
    return _module(os.path.join(here, "traffic", f"{name}.py"),
                   f"generator {name!r}").Generator


def metric_reader(name: str, here: str = HERE):
    """The read(run) function of metrics/<name>.py."""
    return _module(os.path.join(here, "metrics", f"{name}.py"),
                   f"metric {name!r}").read


def metrics_for(bench: dict, workload: str, kind: str) -> list[dict]:
    """The cell's metrics of one kind ("end_to_end" or "per_layer"): those
    that list it under "workloads", or that list no cells at all."""
    return [m for m in bench[kind]
            if "workloads" not in m or workload in m["workloads"]]
