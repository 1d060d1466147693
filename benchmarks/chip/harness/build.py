"""Build one cell's system through the program's normal path.

``resolve_setup`` makes the model and the seeded synthetic, Dirichlet-
partitioned data from an ``ExperimentSpec``; the trainer is constructed
as ``AmpereSystem._trainer`` constructs it, and the weights come from its
``_init_states`` as ``AmpereSystem.run`` calls it, here in one jitted
call on the device.  Every seed the program takes is derived from the
benchmark's ``--seed``.
"""

from __future__ import annotations

import hashlib
import types

PATIENCE = 10 ** 9     # no early stop can end a window's phase


def derive_seed(seed, name):
    """A 31-bit seed for ``name`` from the run's seed (any integer)."""
    h = hashlib.sha256(f"{int(seed)}/{name}".encode()).digest()
    return int.from_bytes(h[:4], "little") & 0x7FFFFFFF


def make_spec(cfg, cell, seed, smoke=False):
    from repro.configs.base import (FedConfig, OptimConfig, RunConfig,
                                    SplitConfig)
    from repro.experiments import DataSpec, ExperimentSpec

    fed = FedConfig(**cell["fed"], seed=derive_seed(seed, "fed"))
    run = RunConfig(arch=cfg["arch"], split=SplitConfig(**cfg["split"]),
                    fed=fed, optim=OptimConfig(**cfg["optim"]),
                    seed=derive_seed(seed, "run"),
                    device_pool_budget_mb=cell["device_pool_budget_mb"])
    data = DataSpec(train_samples=cell["data"]["train_samples"],
                    eval_samples=cell["data"]["eval_samples"],
                    seq_len=cell["data"].get("seq_len", 0),
                    train_seed=derive_seed(seed, "train"),
                    eval_seed=derive_seed(seed, "eval"),
                    partition_seed=derive_seed(seed, "partition"))
    return ExperimentSpec(name=f"chipbench_{cfg['name']}", systems=("ampere",),
                          arch=cfg["arch"], smoke=smoke, run=run, data=data,
                          patience=PATIENCE)


def check_model(cfg, model):
    """The configuration file holds the configuration as it is run: each
    key of its ``model`` block equals the model's field; an object is a
    dataclass sub-config, compared field by field (``moe.num_experts``)."""
    def check(want, got, path):
        for k, v in want.items():
            key = f"{path}{k}"
            if not hasattr(got, k):
                raise ValueError(f"{cfg['name']}: the model has no {key}")
            have = getattr(got, k)
            if isinstance(v, dict):
                check(v, have, key + ".")
            elif (tuple(v) if isinstance(v, list) else v) != have:
                raise ValueError(f"{cfg['name']}: the model's {key} is "
                                 f"{have!r}, the configuration file says "
                                 f"{v!r}")

    check(cfg["model"], model.cfg, "")


def sample_keys(model):
    """(input key, target key) of a model's samples, as the program keys
    them: a token model reads and predicts its tokens."""
    return ("tokens", "tokens") if model.kind == "lm" \
        else ("images", "labels")


def build(cfg, cell, seed, obs, smoke=False):
    """A namespace with spec, model, clients, eval_data, trainer and the
    initial ``dev_state`` / ``srv`` weights."""
    import jax

    from repro.core.uit import AmpereTrainer
    from repro.experiments.api import resolve_setup

    spec = make_spec(cfg, cell, seed, smoke=smoke)
    problems = spec.validate()
    if problems:
        raise ValueError("; ".join(problems))
    spec, model, clients, eval_data = resolve_setup(spec)
    check_model(cfg, model)
    trainer = AmpereTrainer(model, spec.run, clients, eval_data,
                            workdir=None, patience=spec.patience,
                            log_echo=False, transport=None, quorum_frac=1.0,
                            obs=obs, cuts=None)
    key = jax.random.PRNGKey(spec.run.seed)
    dev, srv, aux = jax.jit(trainer._init_states)(key)
    return types.SimpleNamespace(
        spec=spec, model=model, clients=clients, eval_data=eval_data,
        trainer=trainer, key=key, dev_state={"device": dev, "aux": aux},
        srv=srv, obs=obs)


def pool_inputs(clients, key):
    """The samples in the order the program pools them: every client's
    shard, client after client (``client_pool`` and the consolidated
    store both keep this order)."""
    import numpy as np

    return np.concatenate([c.dataset.arrays[key] for c in clients])
