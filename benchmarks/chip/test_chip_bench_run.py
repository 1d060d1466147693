"""The harness end to end on the CPU: every cell at a small size, with
the chip check skipped, comes out correct; on a machine without a TPU,
or without the program beside it, a run fails and prints no result."""

import importlib.util
import json
import pathlib
import shutil
import subprocess
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent.parent
CELLS = [w["name"] for w in
         json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]


@pytest.fixture(scope="module")
def run_mod():
    spec = importlib.util.spec_from_file_location("chipbench_run",
                                                  BENCH / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod._paths()
    return mod


def small_run(run_mod, cell, seed=2 ** 33 + 7, trace=False):
    from harness.small import edit

    return run_mod.run_cell(cell, seed, 1.0, trace, allow_cpu=True,
                            smoke=True, edit=edit, cache=False)


@pytest.mark.parametrize("cell", CELLS)
def test_small_cell_is_correct(run_mod, cell):
    r = small_run(run_mod, cell)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] >= 1
    assert list(r)[-2:] == ["checks", "_log"]
    assert r["_log"]["units_done"] == r["_log"]["units"]
    assert "setup_s" in r["metrics"] and len(r["metrics"]) >= 2
    for c in r["checks"].values():
        assert c["value"] <= c["limit"]


def test_no_tpu_no_result(capsys, monkeypatch):
    spec = importlib.util.spec_from_file_location("chipbench_run_main",
                                                  BENCH / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    # keep this test process's compile cache settings as they are
    monkeypatch.setattr(mod, "_jax_setup", lambda cache=True: None)
    rc = mod.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                   "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0 and out.out == ""
    assert "no TPU" in out.err


def test_without_the_program_no_result(tmp_path):
    (tmp_path / "benchmarks").mkdir()
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns(".*", "__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    p = subprocess.run([sys.executable, "benchmarks/chip/run.py",
                        "--workload", CELLS[0], "--seed", "1", "--seconds",
                        "1", "--trace", "0"], cwd=tmp_path,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout == ""
