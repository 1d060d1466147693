"""Find a cell's files by the names in ``BENCHMARK.json``.

``workloads/<cell>.json``   the cell: its config, driver and job parameters
``configs/<config>.json``   the configuration as it is run
``configs/<config>.py``     its plain reference and FLOP count
``drivers/<driver>.py``     how a window drives one phase entry
``metrics/<metric>.py``     reads one per-layer metric

A later cell, phase or metric is a new file here, never an edit.
"""

from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_ROOT = os.path.dirname(os.path.dirname(BENCH_DIR))


def _path(*parts):
    return os.path.join(BENCH_DIR, *parts)


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(path, name):
    """Import a file whose name need not be a Python identifier."""
    if not os.path.isfile(path):
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark(root=REPO_ROOT):
    return load_json(os.path.join(root, "BENCHMARK.json"))


def workload(name):
    return load_json(_path("workloads", f"{name}.json"))


def config(name):
    return load_json(_path("configs", f"{name}.json"))


def reference(name):
    return load_module(_path("configs", f"{name}.py"),
                       f"chipbench_config_{name.replace('-', '_')}")


def driver(name):
    return load_module(_path("drivers", f"{name}.py"),
                       f"chipbench_driver_{name}")


def metric_reader(name):
    return load_module(_path("metrics", f"{name}.py"),
                       "chipbench_metric_" + name.replace(".", "_"))


def cell_metrics(bench, cell_name):
    """(end-to-end metric entries, per-layer metric entries) that
    ``cell_name`` reports: a metric with a ``workloads`` key names its
    cells; one without it is reported wherever what it moves is."""
    def listed(m):
        return cell_name in m.get("workloads", [cell_name])

    e2e = [m for m in bench["end_to_end"] if listed(m)]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (cell_name in m["workloads"] if "workloads" in m
                     else m["moves"] in names)]
    return e2e, per_layer


def cell_entry(bench, cell_name):
    for w in bench["workloads"]:
        if w["name"] == cell_name:
            return w
    raise KeyError(f"no cell {cell_name!r} in BENCHMARK.json")
