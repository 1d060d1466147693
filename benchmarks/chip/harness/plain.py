"""Plain training loops for the references: the optimizer recipe of a
configuration written out, with no import of the program.

* :func:`server_steps` — a few steps of SGD or AdamW on one loss,
  gradients clipped by their global norm, learning rate
  ``lr / (1 + gamma * t)`` or constant.
* :func:`device_rounds` — federated rounds: every client runs ``H``
  plain SGD steps from the round's weights, then the weighted mean.

Both return what the training check compares: each step's (round's)
loss, per-leaf norms of the first update over its learning rate, and of
the parameters' change after all steps.  An optimizer key that the
configuration leaves out takes the value the program's ``OptimConfig``
gives it, since the program reads the same file.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from harness.compare import diff_norms


def lr_at(optim, t):
    lr = optim.get("lr", 0.05)
    sched = optim.get("schedule", "inverse_time")
    if sched == "inverse_time":
        return lr / (1.0 + optim.get("decay_gamma", 1e-3) * t)
    if sched == "constant":
        return lr
    raise ValueError(f"the plain loop has no schedule {sched!r}")


def _sgd_only(optim):
    if optim.get("name", "sgd") != "sgd" or optim.get("weight_decay", 0.0):
        raise ValueError("the plain device rounds implement plain SGD only")


def _clip(g, clip):
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g)))
    return jax.tree.map(lambda x: x * jnp.minimum(
        1.0, clip / jnp.maximum(norm, 1e-12)), g)


def _adamw(optim, clip):
    """AdamW as ``torch.optim.AdamW`` documents it.  At step ``t`` (from
    1) with gradient ``g``: ``m = b1 m + (1 - b1) g``, ``v = b2 v +
    (1 - b2) g^2``, then ``p -= lr * wd * p`` (the decay decoupled from
    the gradient) and ``p -= lr * m_hat / (sqrt(v_hat) + eps)``, where
    ``m_hat = m / (1 - b1^t)`` and ``v_hat = v / (1 - b2^t)``."""
    b1, b2 = optim.get("beta1", 0.9), optim.get("beta2", 0.999)
    eps, wd = optim.get("eps", 1e-8), optim.get("weight_decay", 0.0)

    @jax.jit
    def step(p, g, m, v, lr, c1, c2):
        if clip:
            g = _clip(g, clip)
        m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
        v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
        p = jax.tree.map(lambda p, m, v: p - lr * wd * p - lr * (m / c1)
                         / (jnp.sqrt(v / c2) + eps), p, m, v)
        return p, m, v

    def update(p, g, lr, t, state):
        m, v = state or (jax.tree.map(jnp.zeros_like, p),) * 2
        # the bias corrections in float64, on the host
        p, m, v = step(p, g, m, v, lr, 1.0 - b1 ** (t + 1),
                       1.0 - b2 ** (t + 1))
        return p, (m, v)

    return update


def server_steps(loss_fn, params, batches, optim):
    """``loss_fn(params, *batch) -> scalar``; ``batches``: one tuple of
    arrays per step.  ``optim["name"]``: ``"sgd"`` (no weight decay) or
    ``"adamw"``."""
    name = optim.get("name", "sgd")
    if name not in ("sgd", "adamw") or (
            name == "sgd" and optim.get("weight_decay", 0.0)):
        raise ValueError(f"the plain server loop implements SGD without "
                         f"weight decay and AdamW, not {name!r}")
    clip = optim.get("grad_clip", 0.0)
    vg = jax.jit(jax.value_and_grad(loss_fn))

    @jax.jit
    def sgd(p, g, lr):
        if clip:
            g = _clip(g, clip)
        return jax.tree.map(lambda a, b: a - lr * b, p, g)

    adamw = _adamw(optim, clip) if name == "adamw" else None
    p0, p, losses, grad, state = params, params, [], None, None
    for t, batch in enumerate(batches):
        loss, g = vg(p, *batch)
        losses.append(float(loss))
        if adamw is None:
            new = sgd(p, g, lr_at(optim, t))
        else:
            new, state = adamw(p, g, lr_at(optim, t), t, state)
        if t == 0:
            grad = diff_norms(p0, new, 1.0 / lr_at(optim, 0))
        p = new
    return {"losses": losses, "grad": grad, "change": diff_norms(p, p0)}


def device_rounds(loss_fn, params, rounds, optim):
    """``rounds``: per round ``(batches, weights)`` where ``batches`` is a
    list over clients of a list over local steps of ``(x, y)``, and
    ``weights`` the clients' aggregation weights."""
    _sgd_only(optim)
    vg = jax.jit(jax.value_and_grad(loss_fn))
    sgd = jax.jit(lambda p, g, lr: jax.tree.map(lambda a, b: a - lr * b,
                                                p, g))
    p0, p, losses, grad = params, params, [], None
    for r, (clients, weights) in enumerate(rounds):
        lr = lr_at(optim, r)
        w = np.asarray(weights, np.float64)
        w = w / w.sum()
        trained, mean_losses = [], []
        for steps in clients:
            q, ls = p, []
            for x, y in steps:
                loss, g = vg(q, x, y)
                ls.append(float(loss))
                q = sgd(q, g, lr)
            trained.append(q)
            mean_losses.append(np.mean(ls))
        new = jax.tree.map(lambda *xs: sum(float(wi) * x for wi, x in
                                           zip(w, xs)), *trained)
        losses.append(float(np.dot(w, mean_losses)))
        if r == 0:
            grad = diff_norms(p0, new, 1.0 / lr)
        p = new
    return {"losses": losses, "grad": grad, "change": diff_norms(p, p0)}
