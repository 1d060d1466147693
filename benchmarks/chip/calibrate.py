#!/usr/bin/env python3
"""Readings that the limits of one cell's check are set from.

    python3 benchmarks/chip/calibrate.py --workload <cell> \\
        --seeds 11,12,13 [--extra 3]

For each seed, in one process (one compile): the cell's set-up and its
first steps through the window's own call, exactly as a benchmark run
makes them, then the numbers compared for

* the program against the reference (the lower reading),
* on the first ``--extra`` seeds also the control, the reference
  computed in bfloat16 and put in the program's place, against the
  reference (the upper reading), and a planted fault: the reference
  with half of each batch left out of the mean (a training cell), or
  with one answer altered (a consolidation cell).

One JSON line per seed and reading; the benchmark's own runs never run
this.  It needs the chip, as ``run.py`` does.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
import types

import run as R

# the control: the nearest precision below the configuration's float32
CONTROL = "bf16"


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--extra", type=int, default=3)
    args = ap.parse_args(argv)
    R._paths()
    R._jax_setup()
    from harness import registry
    from harness.build import build
    from repro.observability import Observability

    bench = registry.benchmark(R.ROOT)
    entry = registry.cell_entry(bench, args.workload)
    R.find_device(entry["chips"])
    cell = registry.workload(args.workload)
    cfg = registry.config(cell["config"])
    driver = registry.driver(entry["traffic"])
    fault = "half_batch" if entry["traffic"] != "consolidate" else "altered"
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        h = types.SimpleNamespace(cfg=cfg, cell=cell, seed=seed,
                                  ref=registry.reference(cell["config"]),
                                  model_dict=cfg["model"])
        h.b = build(cfg, cell, seed, Observability(enabled=True))
        driver.setup(h)
        driver.prime(h)
        driver.free(h)
        gc.collect()
        hi = driver.follow(h)
        out = {"seed": seed, "setup_and_prime_s": time.perf_counter() - t0}
        out["program"], out["where"] = driver.numbers(h.prog, hi)
        if i < args.extra:
            out[CONTROL], _ = driver.numbers(driver.follow(h, CONTROL), hi)
            out[fault], _ = driver.numbers(
                driver.follow(h, fault=fault), hi)
        out["losses"] = h.prog.get("losses")
        print(json.dumps(out), flush=True)
        del h
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
