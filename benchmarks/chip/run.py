#!/usr/bin/env python3
"""Chip benchmark of the Ampere training system: one cell, one run.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

In one process: load the cell named in ``BENCHMARK.json`` (its files
under ``workloads/``, ``configs/``, ``drivers/``, ``metrics/``), build
the model, data and trainer through the program's normal path, warm up
(set-up), run the window, check what the window produced against the
plain reference at the configuration's precision, and print one JSON
line as the last line of standard output::

    {"correct": ..., "attempted": ..., "failed": ..., "metrics": {...},
     "device": {...}, "breakdown": {...}, "checks": {...}}

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiled window (plus ``busy_s``/``window_s``
and the breakdown).  The checks compared, each with its limit, are the
last lines of standard error and the last key of the result.

The run fails, with no result, when JAX finds no TPU or fewer chips than
the cell asks for, when the chip's ``device_kind`` has no entry in the
table of peaks, or when the program is not beside this directory.  JAX's
persistent compilation cache is kept at a fixed path inside the
checkout (``benchmarks/chip/.jax_cache``), whatever the environment
says, with no minimum compile time, so that only a cell's first run in
a checkout compiles.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(BENCH_DIR))
CACHE_DIR = os.path.join(BENCH_DIR, ".jax_cache")
TRACE_DIR = os.path.join(BENCH_DIR, ".trace")
# the program's tracer spans that a trace's idle gaps are labelled by
PROGRAM_SPANS = ("device.round", "aux_eval", "server.epoch", "merged_eval",
                 "consolidate")


class NoChip(RuntimeError):
    """No accelerator the cell can run on."""


def _paths():
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise FileNotFoundError(
            f"the program (src/repro) is not in the checkout at {ROOT}")
    for p in (src, BENCH_DIR):
        if p not in sys.path:
            sys.path.insert(0, p)


def _jax_setup(cache=True):
    import jax

    if not cache:
        return jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # no eviction: an evicting cache reads every entry's access-time file,
    # and one missing file fails every later write
    jax.config.update("jax_compilation_cache_max_size", -1)
    return jax


class CompileCounter:
    """Backend compiles and persistent-cache hits, from JAX's events."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def read(self):
        return self.compiles, self.cache_hits

    def close(self):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._dur)
        jax.monitoring.unregister_event_listener(self._event)


def find_device(chips, allow_cpu=False):
    import jax

    from harness.registry import load_json

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu" and not allow_cpu:
        raise NoChip(f"no TPU: JAX's devices are {dev.platform!r} "
                     f"({dev.device_kind})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX has {len(devs)}")
    peaks = load_json(os.path.join(BENCH_DIR, "harness", "peaks.json"))
    peak = peaks["device_kinds"].get(dev.device_kind)
    if peak is None and not allow_cpu:
        raise NoChip(f"device kind {dev.device_kind!r} is not in the "
                     "table of peaks (harness/peaks.json)")
    return dev, devs[:chips], peak


def _spans_in(tracer, t0, t1):
    return [e for e in tracer.events
            if e.kind == "span" and e.t_wall >= t0 and
            e.t_wall + e.dur_wall <= t1 + 1e-9]


def _traced_window(h, driver, cell_name):
    import jax

    from harness import trace as T

    logdir = os.path.join(TRACE_DIR, cell_name)
    shutil.rmtree(logdir, ignore_errors=True)
    with jax.profiler.trace(logdir, profiler_options=T.trace_options()):
        with jax.profiler.TraceAnnotation(T.WINDOW_SPAN):
            out = driver.window(h)
    tr = T.compact(T.find_xplane(logdir), set(PROGRAM_SPANS)
                   | {T.WINDOW_SPAN})
    shutil.rmtree(logdir, ignore_errors=True)
    return out, T.reduce(tr)


def run_cell(cell_name, seed, seconds, trace, *, allow_cpu=False,
             edit=None, smoke=False, cache=True):
    """One run of one cell; returns the result dict (the last line).

    ``allow_cpu``, ``edit``, ``smoke`` and ``cache`` exist for
    the harness's own tests: they run a cell on the CPU at a small size
    (``edit(cfg, cell)`` changes the loaded files in memory) and leave
    the process's compile cache settings alone."""
    _paths()
    _jax_setup(cache)
    counter = CompileCounter()
    try:
        return _run_cell(cell_name, seed, seconds, trace, counter,
                         allow_cpu=allow_cpu, edit=edit, smoke=smoke)
    finally:
        counter.close()


def _run_cell(cell_name, seed, seconds, trace, counter, *, allow_cpu,
              edit, smoke):
    from harness import registry
    from harness.build import build
    from repro.observability import Observability

    bench = registry.benchmark(ROOT)
    entry = registry.cell_entry(bench, cell_name)
    e2e_defs, layer_defs = registry.cell_metrics(bench, cell_name)
    dev, devs, peak = find_device(entry["chips"], allow_cpu=allow_cpu)
    cell = registry.workload(cell_name)
    if cell["config"] != entry["config"]:
        raise ValueError(f"workloads/{cell_name}.json names config "
                         f"{cell['config']!r}, BENCHMARK.json "
                         f"{entry['config']!r}")
    if cell["driver"] != entry["traffic"]:
        raise ValueError(f"workloads/{cell_name}.json names driver "
                         f"{cell['driver']!r}, BENCHMARK.json traffic "
                         f"{entry['traffic']!r}")
    cfg = registry.config(cell["config"])
    if edit is not None:
        edit(cfg, cell)
    driver = registry.driver(entry["traffic"])
    h = types.SimpleNamespace(cfg=cfg, cell=cell, seed=seed,
                              ref=registry.reference(cell["config"]),
                              model_dict=cfg["model"])
    obs = Observability(enabled=True, profile=bool(trace))
    h.b = build(cfg, cell, seed, obs, smoke=smoke)
    driver.setup(h)
    driver.prime(h)
    span = min(seconds, cell["trace_seconds"]) if trace else seconds
    driver.size(h, span)
    setup_s = time.perf_counter() - T_START

    c0 = counter.read()
    tracer = obs.tracer
    w0 = time.perf_counter() - tracer.t0
    if trace:
        (e2e, info), reduced = _traced_window(h, driver, cell_name)
    else:
        e2e, info = driver.window(h)
        reduced = None
    w1 = time.perf_counter() - tracer.t0
    c1 = counter.read()
    mem_peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in devs)

    metrics = {}
    if trace:
        ctx = types.SimpleNamespace(
            cell=cell_name, driver=entry["traffic"], e2e=e2e, info=info,
            spans=_spans_in(tracer, w0, w1), trace=reduced, peak=peak,
            flops=lambda part: h.ref.flops_per_sample(
                cfg["model"], cfg["split"], part))
        for m in layer_defs:
            v = registry.metric_reader(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in e2e_defs:
            v = setup_s if m["name"] == "setup_s" else e2e[m["name"]]
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    driver.free(h)
    obs.tracer.events.clear()
    gc.collect()
    nums, where = driver.numbers(h.prog, driver.follow(h))
    limits = cell["limits"]
    if not set(limits) <= set(nums):
        raise ValueError(f"limits {sorted(limits)} name numbers the driver "
                         f"does not compute ({sorted(nums)})")
    checks = {k: {"value": nums[k], "limit": limits[k]}
              for k in sorted(limits)}
    correct = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
                  for c in checks.values())
    correct = correct and info["units"] == h.units

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": mem_peak}
    if trace:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
    result = {"correct": bool(correct), "attempted": int(info["attempted"]),
              "failed": int(info["failed"]), "metrics": metrics,
              "device": device}
    if trace:
        result["breakdown"] = reduced["breakdown"]
    result["checks"] = checks
    result["_log"] = {
        "setup_s": setup_s, "units": h.units, "units_done": info["units"],
        "window_s": info["window_s"],
        # a persistent-cache hit still reports a backend compile event
        "compiles_in_window": (c1[0] - c0[0]) - (c1[1] - c0[1]),
        "cache_loads_in_window": c1[1] - c0[1],
        "compiles_total": c1[0] - c1[1],
        "worst_leaf": where}
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 3
    log = result.pop("_log")
    print("run.py: " + json.dumps(log), file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
