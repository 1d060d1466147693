"""Evaluation helpers (validation metrics drive the paper's early stopping)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import losses, steps
from repro.observability.tracer import NULL_TRACER


def make_eval_step(model):
    cfg = model.cfg

    def eval_step(params, batch):
        if model.kind == "lm":
            out = model.apply(params, batch["tokens"], remat="none")
            loss, _ = losses.lm_loss_from_logits(out["logits"], batch["tokens"])
            pred = jnp.argmax(out["logits"][:, :-1], axis=-1)
            acc = jnp.mean((pred == batch["tokens"][:, 1:]).astype(jnp.float32))
        else:
            out = model.apply(params, batch["images"])
            loss, m = losses.classification_loss(out["logits"], batch["labels"])
            acc = m["acc"]
        return loss, acc

    return jax.jit(steps.named("eval_step", eval_step))


def evaluate(model, params, dataset, batch_size: int = 64,
             max_batches: int = 50, eval_step=None,
             tracer=NULL_TRACER) -> dict:
    """Mean loss and accuracy over the first ``max_batches`` full
    batches; each batch's wait for its two scalars is an ``eval.wait``
    span of ``tracer``."""
    step = eval_step or make_eval_step(model)
    n = len(dataset)
    batch_size = min(batch_size, n)
    ls, accs, cnt = [], [], 0
    for s in range(0, n - batch_size + 1, batch_size):
        idx = np.arange(s, s + batch_size)
        batch = {k: v[idx] for k, v in dataset.arrays.items()}
        loss, acc = step(params, batch)
        with tracer.span("eval.wait", track="eval"):
            ls.append(float(loss))
            accs.append(float(acc))
        cnt += 1
        if cnt >= max_batches:
            break
    return {"loss": float(np.mean(ls)) if ls else float("nan"),
            "acc": float(np.mean(accs)) if accs else float("nan")}


class EarlyStopper:
    """Paper §5.2.1: stop when no validation improvement for ``patience``
    consecutive epochs."""

    def __init__(self, patience: int = 15, mode: str = "max",
                 min_delta: float = 1e-4):
        self.patience = patience
        self.mode = mode
        self.min_delta = min_delta
        self.best = -np.inf if mode == "max" else np.inf
        self.bad = 0
        self.best_round = 0
        self.round = 0

    def update(self, value: float) -> bool:
        """Returns True when training should STOP."""
        self.round += 1
        better = (value > self.best + self.min_delta if self.mode == "max"
                  else value < self.best - self.min_delta)
        if better:
            self.best = value
            self.bad = 0
            self.best_round = self.round
        else:
            self.bad += 1
        return self.bad >= self.patience

    # ------------------------------------------------------------------
    # checkpointable state: a resumed coordinator must stop at the same
    # round an uninterrupted run would have (Runner persists this in the
    # checkpoint metadata; json handles the +-inf sentinel)
    def state_dict(self) -> dict:
        return {"best": float(self.best), "bad": int(self.bad),
                "best_round": int(self.best_round), "round": int(self.round)}

    def load_state_dict(self, state: dict):
        self.best = float(state["best"])
        self.bad = int(state["bad"])
        self.best_round = int(state["best_round"])
        self.round = int(state["round"])
