"""Every cell of ``BENCHMARK.json`` resolves, by name, to the files the
harness loads, and the file keeps the benchmark contract's shape."""

import json
import pathlib
import re

import pytest

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent.parent
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in SPEC["workloads"]]


def _load_registry():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "chipbench_registry", BENCH / "harness" / "registry.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_top_level_keys_and_limits():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["benchmarks/chip"]
    assert SPEC["command"][1] == "benchmarks/chip/run.py"
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    names = ([c["name"] for c in SPEC["configs"]] + CELLS
             + [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]])
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" for m in SPEC["end_to_end"])


def test_configs_files_and_reduced():
    reg = _load_registry()
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"] == f"benchmarks/chip/configs/{c['name']}.json"
        data = json.loads((ROOT / c["file"]).read_text())
        assert data["name"] == c["name"]
        reg.check_config(c, data)
        assert (BENCH / "configs" / f"{c['name']}.py").is_file()
        assert any(w["config"] == c["name"] for w in SPEC["workloads"])


# a cut in the form the model-configs guide asks for: 8 of 64 experts
# and an eighth of the vocabulary held, the file stating both
CUT = {"name": "cut", "reduced": ["moe.num_experts", "vocab_size"],
       "model": {"vocab_size": 20480, "d_model": 2048,
                 "moe": {"num_experts": 8, "top_k": 6}},
       "published": {"moe.num_experts": 64, "vocab_size": 163840},
       "deployment": "8 chips share every layer: 8 experts and 1/8 of the "
                     "vocabulary each"}


def _cut(**change):
    data = json.loads(json.dumps(CUT))
    data.update(change)
    return {"name": "cut", "reduced": data["reduced"]}, data


@pytest.mark.parametrize("form", ["model_block", "catalog_keys"])
def test_check_config_takes_a_documented_cut(form):
    """A nested key of the ``model`` block, or a catalog key at the file's
    top level, as the catalog's config holds it."""
    change = {} if form == "model_block" else {
        "reduced": ["num_hidden_layers"], "num_hidden_layers": 5,
        "published": {"num_hidden_layers": 27}}
    _load_registry().check_config(*_cut(**change))


@pytest.mark.parametrize("fault,change,says", [
    ("undocumented", {"published": {"vocab_size": 163840}},
     "gives no value"),
    ("published_as_run", {"published": {"moe.num_experts": 8,
                                        "vocab_size": 163840}},
     "not a cut"),
    ("not_in_model", {"reduced": ["moe.num_layers"],
                      "published": {"moe.num_layers": 27}},
     "not in the file"),
    ("no_deployment", {"deployment": ""}, "deployment"),
    ("width", {"reduced": ["moe.top_k"], "published": {"moe.top_k": 8}},
     "a width"),
    ("entry_differs", {}, "BENCHMARK.json reduces"),
])
def test_check_config_refuses_an_undocumented_cut(fault, change, says):
    entry, data = _cut(**change)
    if fault == "entry_differs":
        entry["reduced"] = ["vocab_size"]
    with pytest.raises(ValueError, match=says):
        _load_registry().check_config(entry, data)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_by_name(cell):
    reg = _load_registry()
    entry = reg.cell_entry(SPEC, cell)
    assert set(entry) == {"name", "config", "traffic", "chips", "why"}
    assert entry["chips"] == 1 and len(entry["why"]) <= 200
    w = reg.workload(cell)
    assert w["config"] == entry["config"]
    assert w["driver"] == entry["traffic"]
    reg.config(w["config"])
    drv = reg.driver(entry["traffic"])
    for fn in ("setup", "prime", "size", "window", "free", "follow",
               "numbers"):
        assert callable(getattr(drv, fn))
    ref = reg.reference(w["config"])
    for fn in ("init", "device_forward", "aux_loss", "server_loss",
               "flops_per_sample"):
        assert callable(getattr(ref, fn))
    e2e, per_layer = reg.cell_metrics(SPEC, cell)
    assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
    assert per_layer
    for m in per_layer:
        assert callable(reg.metric_reader(m["name"]).read)
    assert set(w["limits"]) and all(v >= 0 for v in w["limits"].values())


def test_per_layer_metrics_move_what_their_cells_report():
    reg = _load_registry()
    layers = {m["layer"] for m in SPEC["per_layer"]}
    for m in SPEC["per_layer"]:
        assert m["moves"] in {e["name"] for e in SPEC["end_to_end"]}
        for cell in m["workloads"]:
            e2e, _ = reg.cell_metrics(SPEC, cell)
            assert m["moves"] in {e["name"] for e in e2e}, (m["name"], cell)
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    assert layers <= {"server phase", "device round", "device"}


def test_files_under_paths_are_named_from_names():
    for p in BENCH.rglob("*"):
        rel = p.relative_to(ROOT).as_posix()
        if "/." in "/" + rel or "__pycache__" in rel:
            continue
        assert re.match(r"^[A-Za-z0-9_./-]+$", rel), rel
