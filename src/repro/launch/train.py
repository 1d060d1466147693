"""Training launcher.

Full Ampere / baseline federated training on synthetic non-IID data
through the trainers in core/uit.py and core/baselines/*: cohort
sampling, dropout, straggler deadlines, checkpoint/restart and the
activation store.  ``--smoke`` selects the reduced same-family config
(CPU scale); without it the published widths are built.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch mobilenet-l \
      --algo ampere --device-rounds 30 --server-epochs 10
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b --smoke \
      --algo ampere --device-rounds 5 --server-epochs 2 --seq-len 64
"""

from __future__ import annotations

import argparse
import json
import sys

import numpy as np

from repro.configs import registry
from repro.configs.base import (FedConfig, OptimConfig, RunConfig,
                                SplitConfig, replace)
from repro.core.baselines import FedAvgTrainer, SFLTrainer
from repro.core.uit import AmpereTrainer
from repro.data import federate, make_dataset_for_model
from repro.models import build_model
from repro.platform import enable_compile_cache


def build_run_cfg(args) -> RunConfig:
    return RunConfig(
        arch=args.arch,
        algo=args.algo,
        split=SplitConfig(split_point=args.split_point,
                          aux_ratio=args.aux_ratio,
                          quantize_activations=args.quantize_acts),
        fed=FedConfig(num_clients=args.clients,
                      clients_per_round=args.cohort,
                      local_steps=args.local_steps,
                      device_batch_size=args.batch_size,
                      server_batch_size=args.server_batch,
                      dirichlet_alpha=args.alpha,
                      drop_prob=args.drop_prob,
                      straggler_deadline_factor=args.deadline,
                      seed=args.seed),
        optim=OptimConfig(name=args.optimizer, lr=args.lr,
                          schedule="inverse_time", decay_gamma=0.005),
        checkpoint_dir=args.workdir or "",
        checkpoint_every=args.checkpoint_every,
        seed=args.seed,
    )


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mobilenet-l",
                    choices=registry.list_archs())
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config (CPU scale)")
    ap.add_argument("--algo", default="ampere",
                    choices=["ampere", "ampere-noconsolidation", "splitfed",
                             "splitfedv2", "splitgp", "scaffold", "pipar",
                             "fedavg"])
    ap.add_argument("--split-point", type=int, default=1)
    ap.add_argument("--aux-ratio", type=float, default=0.5)
    ap.add_argument("--quantize-acts", action="store_true")
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--cohort", type=int, default=4)
    ap.add_argument("--local-steps", type=int, default=8)
    ap.add_argument("--batch-size", type=int, default=16)
    ap.add_argument("--server-batch", type=int, default=32)
    ap.add_argument("--alpha", type=float, default=0.33)
    ap.add_argument("--drop-prob", type=float, default=0.0)
    ap.add_argument("--deadline", type=float, default=0.0)
    ap.add_argument("--optimizer", default="momentum")
    ap.add_argument("--lr", type=float, default=0.2)
    ap.add_argument("--device-rounds", type=int, default=30)
    ap.add_argument("--server-epochs", type=int, default=10)
    ap.add_argument("--train-samples", type=int, default=2048)
    ap.add_argument("--eval-samples", type=int, default=512)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--patience", type=int, default=15)
    ap.add_argument("--workdir", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=0)
    ap.add_argument("--quiet", action="store_true")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = (registry.get_smoke_config(args.arch) if args.smoke
           else registry.get_config(args.arch))
    model = build_model(cfg)
    run_cfg = build_run_cfg(args)

    train = make_dataset_for_model(model, args.train_samples,
                                   seq_len=args.seq_len, seed=args.seed)
    evald = make_dataset_for_model(model, args.eval_samples,
                                   seq_len=args.seq_len, seed=args.seed + 1)
    clients = federate(train, args.clients, args.alpha, seed=args.seed)

    echo = not args.quiet
    if args.algo.startswith("ampere"):
        trainer = AmpereTrainer(
            model, run_cfg, clients, evald, workdir=args.workdir,
            patience=args.patience, log_echo=echo,
            consolidate=(args.algo == "ampere"))
        out = trainer.run_all(max_device_rounds=args.device_rounds,
                              max_server_epochs=args.server_epochs)
        hist = out["history"]
        final = hist["server"][-1] if hist["server"] else {}
    elif args.algo == "fedavg":
        trainer = FedAvgTrainer(model, run_cfg, clients, evald,
                                workdir=args.workdir,
                                patience=args.patience, log_echo=echo)
        out = trainer.run_rounds(args.device_rounds)
        hist = out["history"]
        final = hist["rounds"][-1] if hist["rounds"] else {}
    else:
        trainer = SFLTrainer(model, run_cfg, clients, evald,
                             variant=args.algo, workdir=args.workdir,
                             patience=args.patience, log_echo=echo)
        out = trainer.run_rounds(args.device_rounds)
        hist = out["history"]
        final = hist["rounds"][-1] if hist["rounds"] else {}

    summary = {
        "arch": args.arch, "algo": args.algo,
        "final": final,
        "comm_bytes": hist.get("comm_bytes", 0),
        "sim_time_s": hist.get("sim_time", 0.0),
    }
    print(json.dumps(summary, default=float))
    return 0


if __name__ == "__main__":
    sys.exit(main())
