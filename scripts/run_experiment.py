#!/usr/bin/env python
"""Declarative experiment runner.

    PYTHONPATH=src python scripts/run_experiment.py SPEC.json
    PYTHONPATH=src python scripts/run_experiment.py SPEC.json --dry-run

One spec file drives every listed system (Ampere, SFL family, FedAvg)
over one shared setup — same model init, same non-IID partition, and
(when the spec carries a fleet section) one shared JSONL fleet trace —
writing a single results directory with ``summary.json`` plus
per-system history files.

``--dry-run`` validates the spec, resolves every system from the
registry, and reports the plan without building a model; CI uses it to
exercise spec validation and the registry on every run.
"""

import argparse
import json
import os
import sys


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("spec", help="ExperimentSpec JSON file")
    ap.add_argument("--dry-run", action="store_true",
                    help="validate the spec + registry, print the plan, "
                         "run nothing")
    ap.add_argument("--results-dir", default=None,
                    help="override spec.results_dir")
    ap.add_argument("--echo", action="store_true",
                    help="echo per-round metrics lines")
    ap.add_argument("--profile", action="store_true",
                    help="wrap the run in jax.profiler.trace and couple "
                         "spans to TraceAnnotation (forces observability "
                         "on; XLA dump lands under <results>/profile)")
    ap.add_argument("--role", choices=("device", "server"), default=None,
                    help="two-process socket mode: run only this side of "
                         "the Ampere pipeline (see repro.transport.roles)")
    ap.add_argument("--host", default=None,
                    help="socket mode: override spec.transport.host")
    ap.add_argument("--port", type=int, default=None,
                    help="socket mode: override spec.transport.port")
    args = ap.parse_args(argv)

    if args.role == "device":
        # the device role models an edge device: pin it to the host CPU
        # before JAX initialises, leaving the accelerator to the server
        # role's process (a chip belongs to one process at a time)
        os.environ["JAX_PLATFORMS"] = "cpu"
    from repro.platform import enable_compile_cache
    enable_compile_cache()

    from repro.configs.base import replace
    from repro.experiments import ExperimentSpec, run_experiment

    spec = ExperimentSpec.load(args.spec)
    if args.results_dir is not None:
        spec = replace(spec, results_dir=args.results_dir)
    if args.profile:
        from repro.experiments import ObservabilitySpec
        obs_spec = spec.observability or ObservabilitySpec()
        spec = replace(spec, observability=replace(
            obs_spec, enabled=True, profile=True))

    problems = spec.validate()
    if problems:
        print(f"INVALID spec {args.spec}:", file=sys.stderr)
        for p in problems:
            print(f"  - {p}", file=sys.stderr)
        return 1

    if args.role is not None:
        from repro.transport import roles
        if args.role == "device":
            out = roles.run_device_role(spec, host=args.host,
                                        port=args.port, echo=args.echo)
        else:
            out = roles.run_server_role(spec, host=args.host,
                                        port=args.port, echo=args.echo,
                                        results_dir=args.results_dir)
        print(json.dumps(out.get("summary") or out.get("result"), indent=1))
        return 0

    if args.dry_run:
        out = run_experiment(spec, dry_run=True)
        plan = {
            "spec": args.spec,
            "name": spec.name,
            "arch": spec.arch + (" (smoke)" if spec.smoke else ""),
            "systems": out["systems"],
            "rounds": spec.max_rounds or spec.run.fed.device_epochs,
            "server_epochs": (spec.max_server_epochs
                              or spec.run.fed.server_epochs),
            "clients": spec.run.fed.num_clients,
            "trace": spec.trace_path or ("<simulated from fleet cfg>"
                                         if spec.fleet else None),
            "results_dir": spec.results_dir or f"results/{spec.name}",
        }
        print(json.dumps(plan, indent=1))
        print("dry-run OK")
        return 0

    if args.profile:
        from repro.observability.profiling import profile_run
        logdir = os.path.join(
            spec.results_dir or f"results/{spec.name}", "profile")
        with profile_run(logdir):
            out = run_experiment(spec, log_echo=args.echo)
        print(f"profiler trace (if jax.profiler is available): {logdir}")
    else:
        out = run_experiment(spec, log_echo=args.echo)
    print(json.dumps(out["summary"], indent=1))
    if spec.observability is not None and spec.observability.enabled:
        from repro.observability.metrics import format_phase_table
        for name, system in sorted(out["summary"].items()):
            rows = system.get("phases")
            if rows:
                print()
                print(format_phase_table(rows, title=name))
    print(f"wrote {out['results_dir']}/summary.json")
    return 0


if __name__ == "__main__":
    sys.exit(main())
