"""Plain training loops for the references: the optimizer recipe of a
configuration written out, with no import of the program.

* :func:`server_steps` — a few steps of SGD on one loss, gradients
  clipped by their global norm, learning rate ``lr / (1 + gamma * t)``.
* :func:`device_rounds` — federated rounds: every client runs ``H``
  plain SGD steps from the round's weights, then the weighted mean.

Both return what the training check compares: each step's (round's)
loss, per-leaf norms of the first update over its learning rate, and of
the parameters' change after all steps.
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


def _check_recipe(optim):
    if optim.get("name", "sgd") != "sgd" or optim.get("weight_decay", 0.0):
        raise ValueError("the plain loop implements plain SGD only")


def server_steps(loss_fn, params, batches, optim):
    """``loss_fn(params, *batch) -> scalar``; ``batches``: one tuple of
    arrays per step."""
    _check_recipe(optim)
    clip = optim.get("grad_clip", 0.0)
    vg = jax.jit(jax.value_and_grad(loss_fn))

    @jax.jit
    def update(p, g, lr):
        if clip:
            norm = jnp.sqrt(sum(jnp.sum(jnp.square(x))
                                for x in jax.tree.leaves(g)))
            g = jax.tree.map(lambda x: x * jnp.minimum(
                1.0, clip / jnp.maximum(norm, 1e-12)), g)
        return jax.tree.map(lambda a, b: a - lr * b, p, g)

    p0, p, losses, grad = params, params, [], None
    for t, batch in enumerate(batches):
        loss, g = vg(p, *batch)
        losses.append(float(loss))
        new = update(p, g, lr_at(optim, t))
        if t == 0:
            grad = diff_norms(p0, new, 1.0 / lr_at(optim, 0))
        p = new
    return {"losses": losses, "grad": grad, "change": diff_norms(p, p0)}


def device_rounds(loss_fn, params, rounds, optim):
    """``rounds``: per round ``(batches, weights)`` where ``batches`` is a
    list over clients of a list over local steps of ``(x, y)``, and
    ``weights`` the clients' aggregation weights."""
    _check_recipe(optim)
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
