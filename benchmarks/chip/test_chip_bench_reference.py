"""The plain references beside the chip benchmark's configurations agree
with the program on seeded weights at the repo's smoke widths (CPU).

Each reference covers the split model: the device block, the auxiliary
net and its loss with gradients, and the server block's loss with
gradients.  On the CPU float32 products are exact to float32, so the
tolerances are a few float32 ulps of accumulated rounding.  Every
configuration of ``BENCHMARK.json`` is a case; a token model's batches
are sequences of its vocabulary, keyed as the program keys them.
"""

import importlib.util
import json
import pathlib

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent.parent
CONFIGS = [c["name"] for c in
           json.loads((ROOT / "BENCHMARK.json").read_text())["configs"]]


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def run_mod():
    mod = _load(BENCH / "run.py", "chipbench_run")
    mod._paths()
    return mod


def _setup(name, seed):
    import jax

    from harness.registry import config
    from harness.small import smoke_model
    from repro.configs import registry
    from repro.configs.base import RunConfig, SplitConfig
    from repro.core.uit import AmpereTrainer
    from repro.models import build_model

    arch = config(name)["arch"]
    model = build_model(registry.get_smoke_config(arch))
    split = {"split_point": 1, "aux_ratio": 0.5}
    run = RunConfig(arch=arch, split=SplitConfig(**split))
    tr = AmpereTrainer(model, run, [], None)
    key = jax.random.PRNGKey(seed)
    prog = jax.jit(tr._init_states)(key)     # as the benchmark's set-up
    ref = _load(BENCH / "configs" / f"{name}.py", f"ref_{name}")
    m = smoke_model(arch)
    return model, run, split, key, prog, ref, m, ref.init(key, m, split)


def _close(a, b, tol):
    """Leaf by leaf within ``tol`` of the leaf's largest value.  A leaf
    that is all but zero in ``b`` (under a thousandth of the tree's
    largest: a gradient that does not reach the loss, round-off on both
    sides) has to be all but zero in ``a`` too."""
    import jax

    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert jax.tree.structure(a) == jax.tree.structure(b)
    top = max(float(np.max(np.abs(np.asarray(y)))) for y in lb)
    for x, y in zip(la, lb):
        x, y = np.asarray(x, np.float64), np.asarray(y, np.float64)
        assert x.shape == y.shape
        if top and np.max(np.abs(y)) < 1e-3 * top:
            assert np.max(np.abs(x)) < 1e-3 * top
            continue
        assert np.max(np.abs(x - y)) <= tol * max(np.max(np.abs(y)), 1e-30)


def _data(model, n, seed):
    """(inputs, targets, the batch as the program keys it)."""
    from harness.build import sample_keys
    from harness.small import SMOKE_SEQ_LEN

    rng = np.random.default_rng(seed)
    if model.kind == "lm":
        x = y = rng.integers(0, model.cfg.vocab_size,
                             (n, SMOKE_SEQ_LEN)).astype(np.int32)
    else:
        s = model.cfg.img_size
        x = rng.standard_normal((n, s, s, 3)).astype(np.float32)
        y = rng.integers(0, model.cfg.num_classes, n).astype(np.int32)
    inp, lab = sample_keys(model)
    return x, y, {inp: x, lab: y}


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_weights_are_the_programs(run_mod, name):
    _, _, _, _, prog, _, _, ref = _setup(name, 3)
    _close(prog, ref, 0.0)


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_device_block_and_aux_loss(run_mod, name):
    import jax

    from repro.core import auxiliary, splitting

    model, run, split, _, (dev, _, aux), ref, m, _ = _setup(name, 4)
    x, y, batch = _data(model, 16, 5)
    params = {"device": dev, "aux": aux}

    def prog_loss(p):
        acts = splitting.device_forward(model, p["device"], x, 1)
        return auxiliary.aux_loss(model, p["aux"], p["device"], acts,
                                  batch, run.split)[0]

    def ref_loss(p):
        return ref.aux_loss(p, x, y, m, split, "f32")

    _close(splitting.device_forward(model, dev, x, 1),
           ref.device_forward(dev, x, m, "f32"), 1e-5)
    lp, gp = jax.value_and_grad(prog_loss)(params)
    lr, gr = jax.value_and_grad(ref_loss)(params)
    assert abs(float(lp) - float(lr)) <= 1e-5 * abs(float(lr))
    _close(gp, gr, 1e-4)


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_server_loss_and_gradients(run_mod, name):
    import jax

    from repro.core import losses, splitting

    model, _, _, _, (dev, srv, _), ref, m, _ = _setup(name, 6)
    x, y, _ = _data(model, 16, 7)
    acts = splitting.device_forward(model, dev, x, 1)

    def prog_loss(p):
        # the loss the program's server step differentiates
        out = splitting.server_forward(model, p, acts, 1)
        if model.kind == "lm":
            loss = losses.lm_loss_from_hidden(
                out["hidden"], splitting.server_head_weight(p), y,
                softcap=model.cfg.final_softcap)[0]
        else:
            loss = losses.classification_loss(out["logits"], y)[0]
        return loss + out["aux"]

    lp, gp = jax.value_and_grad(prog_loss)(srv)
    lr, gr = jax.value_and_grad(
        lambda p: ref.server_loss(p, acts, y, m, "f32"))(srv)
    assert abs(float(lp) - float(lr)) <= 1e-5 * abs(float(lr))
    _close(gp, gr, 1e-4)


@pytest.mark.parametrize("name", CONFIGS)
def test_bf16_control_departs_from_reference(run_mod, name):
    """The lower-precision control computes something else: its server
    loss differs from the float32 reference by far more than rounding."""
    model, _, _, _, (dev, srv, _), ref, m, _ = _setup(name, 8)
    x, y, _ = _data(model, 16, 9)
    acts = ref.device_forward(dev, x, m, "f32")
    hi = float(ref.server_loss(srv, acts, y, m, "f32"))
    lo = float(ref.server_loss(srv, acts, y, m, "bf16"))
    assert abs(hi - lo) > 1e-5 * abs(hi)
