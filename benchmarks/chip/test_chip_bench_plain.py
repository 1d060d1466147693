"""The plain loop's AdamW against the program's, on the CPU.

Five steps on a small seeded tree, at JAX's highest matmul precision, so
both sides get the same gradients to the last bit and differ only in
the optimizer's own arithmetic.  The plain loop writes AdamW as
``torch.optim.AdamW`` documents it and takes its bias corrections in
float64; the program computes ``1 - beta ** t`` in float32, where
``beta2 = 0.999`` rounds to 0.99900001, so ``1 - beta2`` is off by
1.3e-5 of itself and ``sqrt(v_hat)`` by 6.4e-6.  Adam's update is
``lr * m_hat / sqrt(v_hat)`` per element, so that is also the relative
gap of each step, and five steps stay well inside TOL: a sign or an
order of the decay, a missing bias correction, or a clip after the
update are off by tens of percent.
"""

import importlib.util
import pathlib

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parent
STEPS = 5
TOL = 1e-4


@pytest.fixture(scope="module")
def run_mod():
    spec = importlib.util.spec_from_file_location("chipbench_run_plain",
                                                  BENCH / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod._paths()
    return mod


def _problem(seed):
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    params = {"w": jnp.asarray(rng.standard_normal((8, 4)), jnp.float32),
              "b": jnp.asarray(rng.standard_normal(4), jnp.float32)}
    batches = [(jnp.asarray(rng.standard_normal((16, 8)), jnp.float32),
                jnp.asarray(rng.standard_normal((16, 4)), jnp.float32))
               for _ in range(STEPS)]

    def loss_fn(p, x, y):
        return jnp.mean(jnp.square(jnp.tanh(x @ p["w"] + p["b"]) - y))

    return params, batches, loss_fn


def _program(loss_fn, params, batches, optim):
    """The program's optimizer, schedule and clip, in the order of its
    server step."""
    import jax

    from harness.compare import diff_norms
    from repro.configs.base import OptimConfig
    from repro.optim import (clip_by_global_norm, make_optimizer,
                             make_schedule)

    cfg = OptimConfig(**optim)
    opt, sched = make_optimizer(cfg), make_schedule(cfg)
    vg = jax.jit(jax.value_and_grad(loss_fn))
    p, state, losses, grad = params, opt.init(params), [], None
    for t, batch in enumerate(batches):
        loss, g = vg(p, *batch)
        losses.append(float(loss))
        if cfg.grad_clip:
            g, _ = clip_by_global_norm(g, cfg.grad_clip)
        new, state = opt.update(g, state, p, sched(t))
        if t == 0:
            grad = diff_norms(params, new, 1.0 / float(sched(0)))
        p = new
    return {"losses": losses, "grad": grad,
            "change": diff_norms(p, params)}, p


def _plain_params(loss_fn, params, batches, optim):
    import jax

    from harness.plain import _adamw, lr_at

    update = _adamw(optim, optim.get("grad_clip", 0.0))
    vg = jax.jit(jax.value_and_grad(loss_fn))
    p, state = params, None
    for t, batch in enumerate(batches):
        _, g = vg(p, *batch)
        p, state = update(p, g, lr_at(optim, t), t, state)
    return p


@pytest.mark.parametrize("clip", [0.0, 0.05])
@pytest.mark.parametrize("wd", [0.0, 0.1])
def test_plain_adamw_matches_the_programs(run_mod, clip, wd):
    import jax

    from harness.plain import server_steps

    optim = {"name": "adamw", "lr": 0.01, "schedule": "inverse_time",
             "decay_gamma": 0.1, "weight_decay": wd, "grad_clip": clip}
    params, batches, loss_fn = _problem(17)
    with jax.default_matmul_precision("highest"):
        if clip:        # the clip binds at every step
            g = jax.grad(loss_fn)(params, *batches[0])
            assert float(sum(np.sum(np.square(x)) for x in
                             jax.tree.leaves(g))) ** 0.5 > 2 * clip
        prog, p_prog = _program(loss_fn, params, batches, optim)
        plain = server_steps(loss_fn, params, batches, optim)
        p_plain = _plain_params(loss_fn, params, batches, optim)
    np.testing.assert_allclose(plain["losses"], prog["losses"], rtol=TOL)
    for k in ("grad", "change"):
        for leaf, v in prog[k].items():
            assert abs(plain[k][leaf] - v) <= TOL * v, (k, leaf)
    for a, b, p0 in zip(jax.tree.leaves(p_plain), jax.tree.leaves(p_prog),
                        jax.tree.leaves(params)):
        moved = np.asarray(b) - np.asarray(p0)
        np.testing.assert_allclose(np.asarray(a) - np.asarray(p0), moved,
                                   rtol=0, atol=TOL * np.max(np.abs(moved)))


def test_sgd_only_in_the_device_rounds(run_mod):
    from harness.plain import device_rounds

    with pytest.raises(ValueError, match="SGD only"):
        device_rounds(None, {}, [], {"name": "adamw"})
