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
import re

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


_ABSENT = object()
# a width is never cut (the model-configs guide, section 4): hidden,
# intermediate, latent, state and projection sizes, head sizes,
# expansion factors and experts per token; the vocabulary is a count
WIDTH = re.compile(r"(^d_|_dim$|_rank$|_size$|_ratio$|^expand$|^top_k$"
                   r"|^num_experts_per_tok$)")


def _lookup(tree, key):
    """The value under dotted ``key`` in nested dicts, or ``_ABSENT``."""
    for part in key.split("."):
        if not isinstance(tree, dict) or part not in tree:
            return _ABSENT
        tree = tree[part]
    return tree


def check_config(entry, data):
    """A configuration's ``reduced`` is documented in its file.

    ``entry`` is its item in ``BENCHMARK.json``'s ``configs``, ``data``
    its file.  Nothing cut: ``reduced`` is ``[]`` in both.  A cut is
    accepted only where both list the same keys and each key
    (dotted for nested ones) names a value of the file, at its top level
    as the catalog's config keys sit there or else in its ``model``
    block; its ``published`` object gives the key's published value,
    which differs from the one run; the key is no width; and the file's
    ``deployment`` says over how many chips each layer is divided, and
    how.  Raises ``ValueError`` naming the first fault."""
    name = entry["name"]
    reduced = entry["reduced"]
    if data.get("reduced") != reduced:
        raise ValueError(f"{name}: BENCHMARK.json reduces {reduced}, "
                         f"the file {data.get('reduced')}")
    if not reduced:
        return
    deployment = data.get("deployment")
    if not (isinstance(deployment, str) and re.search(r"\d", deployment)):
        raise ValueError(f"{name}: a cut needs a 'deployment' that says over "
                         "how many chips each layer is divided, and how")
    published = data.get("published", {})
    for key in reduced:
        run = _lookup(data, key)
        if run is _ABSENT:
            run = _lookup(data.get("model"), key)
        if run is _ABSENT:
            raise ValueError(f"{name}: reduced key {key!r} is not in the "
                             "file or its model block")
        leaf = key.rsplit(".", 1)[-1]
        if WIDTH.search(leaf) and leaf != "vocab_size":
            raise ValueError(f"{name}: {key!r} is a width, never cut")
        if key not in published:
            raise ValueError(f"{name}: 'published' gives no value for the "
                             f"reduced key {key!r}")
        if published[key] == run:
            raise ValueError(f"{name}: {key!r} is run at its published "
                             f"value {run!r}: not a cut")


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
