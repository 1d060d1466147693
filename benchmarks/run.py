"""Benchmark entry point: one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run            # quick pass (~15 min)
  PYTHONPATH=src python -m benchmarks.run --full     # full training curves
  PYTHONPATH=src python -m benchmarks.run --only table1_comm_rounds,fig10

Analytic benchmarks (Tables 1/2/5, Figs 3/6/7/9) are exact at the paper's
full scale; training benchmarks (Figs 8/10/11, Table 4) run the real
federated systems at smoke scale on synthetic non-IID data.  The roofline
benchmark reads the dry-run matrix results when present.

``bench_step`` / ``bench_fleet`` / ``bench_attention`` are the
perf-trajectory gates (not paper figures): they time the step paths /
fleet paths / flash-attention kernels and write ``BENCH_step.json`` /
``BENCH_fleet.json`` / ``BENCH_attention.json`` at the repo root —
``{"config": {...}, "times_s": {name: best-of-N seconds}, ...}``.
Run one alone with ``--only bench_step``; compare two snapshots with
``python scripts/check_bench_regression.py old.json new.json`` (exits
nonzero on step-time regression).

``--gate`` is the CI mode (``scripts/ci.sh``): it snapshots the committed
``BENCH_*.json``, re-runs just the gate benchmarks, and fails if any
``times_s`` entry regressed beyond ``--gate-threshold`` (default 25% —
CPU CI boxes are noisy; the trend lives in the committed snapshots).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time
import traceback

from benchmarks import (
    bench_attention,
    bench_cut,
    bench_fleet,
    bench_step,
    fig3_fig6_splitpoint,
    fig7_aux_ratio,
    fig8_accuracy_time,
    fig9_device_compute,
    fig10_noniid,
    fig11_consolidation,
    roofline,
    table1_comm_rounds,
    table2_sizes,
    table4_epochs,
    table5_comm_volume,
)

BENCHMARKS = {
    "table1_comm_rounds": table1_comm_rounds.run,
    "table2_sizes": table2_sizes.run,
    "fig3_fig6_splitpoint": fig3_fig6_splitpoint.run,
    "fig7_aux_ratio": fig7_aux_ratio.run,
    "table5_comm_volume": table5_comm_volume.run,
    "fig9_device_compute": fig9_device_compute.run,
    "fig8_accuracy_time": fig8_accuracy_time.run,
    "fig10_noniid": fig10_noniid.run,
    "fig11_consolidation": fig11_consolidation.run,
    "table4_epochs": table4_epochs.run,
    "roofline": roofline.run,
    "bench_step": bench_step.run,
    "bench_fleet": bench_fleet.run,
    "bench_attention": bench_attention.run,
    "bench_cut": bench_cut.run,
}

# gate benchmarks: name -> committed snapshot they rewrite
GATED = {"bench_step": bench_step.BENCH_PATH,
         "bench_fleet": bench_fleet.BENCH_PATH,
         "bench_attention": bench_attention.BENCH_PATH,
         "bench_cut": bench_cut.BENCH_PATH}


def run_gate(threshold: float) -> int:
    """Re-run the gate benchmarks and compare against the committed
    BENCH files.  The committed snapshot is restored afterwards — gating
    never moves the baseline (updating it is an explicit
    ``--only bench_step`` / ``--only bench_fleet`` run that gets
    committed), so a failed gate keeps failing on retry."""
    from benchmarks import common

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    os.chdir(root)   # bench modules write repo-root-relative BENCH paths
    check = os.path.join(root, "scripts", "check_bench_regression.py")
    rc = 0
    for name, path in GATED.items():
        if not os.path.exists(path):
            print(f"[gate] {name}: no committed {path}; writing fresh "
                  f"baseline")
            BENCHMARKS[name](quick=True)
            continue
        # the bench rewrites its committed snapshot AND its results/ copy;
        # snapshot both so gating leaves the workspace exactly as it was
        touched = {path: None,
                   os.path.join(common.RESULTS_DIR, f"{name}.json"): None}
        for p in touched:
            if os.path.exists(p):
                with open(p) as f:
                    touched[p] = f.read()
        with tempfile.NamedTemporaryFile("w", suffix=".json",
                                         delete=False) as tf:
            tf.write(touched[path])
            baseline = tf.name
        try:
            print(f"\n===== gate: {name} =====", flush=True)
            BENCHMARKS[name](quick=True)
            res = subprocess.run(
                [sys.executable, check, baseline, path,
                 "--threshold", str(threshold)])
            if res.returncode != 0:
                rc = 1
        finally:
            for p, content in touched.items():  # gate never moves baselines
                if content is not None:
                    with open(p, "w") as f:
                        f.write(content)
                elif os.path.exists(p):
                    os.unlink(p)
            os.unlink(baseline)
    return rc


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default="")
    ap.add_argument("--gate", action="store_true",
                    help="run only the gate benchmarks and fail on "
                         "regression vs the committed BENCH_*.json")
    ap.add_argument("--gate-threshold", type=float, default=0.25)
    ap.add_argument("--profile", action="store_true",
                    help="wrap the benchmark run in jax.profiler.trace "
                         "(dump under results/profile)")
    args = ap.parse_args(argv)
    from repro.platform import enable_compile_cache
    enable_compile_cache()
    if args.gate:
        sys.exit(run_gate(args.gate_threshold))
    only = [s for s in args.only.split(",") if s]

    if args.profile:
        from repro.observability.profiling import profile_run
        profile_cm = profile_run(os.path.join("results", "profile"))
    else:
        import contextlib
        profile_cm = contextlib.nullcontext()

    failures = []
    with profile_cm:
        for name, fn in BENCHMARKS.items():
            if only and not any(o in name for o in only):
                continue
            t0 = time.time()
            print(f"\n===== {name} =====", flush=True)
            try:
                fn(quick=not args.full)
                print(f"[{name}] ok in {time.time()-t0:.1f}s", flush=True)
            except Exception:
                traceback.print_exc()
                failures.append(name)
                print(f"[{name}] FAILED", flush=True)
    print(f"\n{len(BENCHMARKS) - len(failures)}/{len(BENCHMARKS)} "
          f"benchmarks ok" + (f"; failed: {failures}" if failures else ""))
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
