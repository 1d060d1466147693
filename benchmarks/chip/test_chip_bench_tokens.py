"""A token model through the harness on the CPU, with no file of its own.

The repo's ``qwen2-moe-a2.7b`` smoke config (sparse experts beside
shared ones) is written as a configuration file and a server cell would
write it, in memory: its ``moe`` sub-config as an object, AdamW, a
sequence length that the small-size edit cuts to 32, and a pool that
streams from the host.  ``build`` and the server driver's set-up, first
epoch and window then run as a benchmark run drives them, and the
consolidation driver reads the store back by the same keys.
"""

import importlib.util
import math
import pathlib
import types

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parent
ARCH = "qwen2-moe-a2.7b"
SEED = 2 ** 33 + 29


@pytest.fixture(scope="module")
def run_mod():
    spec = importlib.util.spec_from_file_location("chipbench_run_tokens",
                                                  BENCH / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod._paths()
    return mod


def _files():
    """A configuration file and a server cell, as dicts."""
    cfg = {"name": "qwen2-moe-smoke", "arch": ARCH, "model": {},
           "reduced": [], "split": {"split_point": 1, "aux_ratio": 0.5},
           "optim": {"name": "adamw", "lr": 1e-3, "schedule": "constant",
                     "weight_decay": 0.1, "grad_clip": 1.0}}
    cell = {"config": cfg["name"], "driver": "server",
            "fed": {"num_clients": 120, "clients_per_round": 12,
                    "local_steps": 8, "dirichlet_alpha": 0.33,
                    "device_batch_size": 32, "server_batch_size": 256},
            "data": {"train_samples": 50000, "eval_samples": 10000,
                     "seq_len": 8192},
            "device_pool_budget_mb": 1024, "server_pool": "streamed"}
    return cfg, cell


@pytest.fixture(scope="module")
def primed(run_mod):
    from harness.build import build
    from harness.registry import driver
    from harness.small import SMOKE_SEQ_LEN, edit
    from repro.observability import Observability

    cfg, cell = _files()
    edit(cfg, cell)
    assert cell["data"]["seq_len"] == SMOKE_SEQ_LEN
    assert cell["device_pool_budget_mb"] == 0
    assert isinstance(cfg["model"]["moe"], dict)
    h = types.SimpleNamespace(cfg=cfg, cell=cell, seed=SEED, ref=None,
                              model_dict=cfg["model"])
    h.b = build(cfg, cell, SEED, Observability(enabled=True), smoke=True)
    srv = driver("server")
    srv.setup(h)
    srv.prime(h)
    return h, srv


@pytest.mark.parametrize("key,value", [("moe.num_experts", 7),
                                       ("moe.capacity", 1.0)])
def test_a_sub_config_is_checked_field_by_field(primed, key, value):
    import copy

    from harness.build import check_model

    h, _ = primed
    check_model(h.cfg, h.b.model)
    cfg = copy.deepcopy(h.cfg)
    group, field = key.split(".")
    cfg["model"][group][field] = value
    with pytest.raises(ValueError, match=key.replace(".", r"\.")):
        check_model(cfg, h.b.model)


def test_the_tap_carries_the_pools_tokens(primed):
    import jax

    from harness.build import pool_inputs
    from repro.core import splitting

    h, srv = primed
    b = h.b
    tokens = pool_inputs(b.clients, "tokens")
    assert tokens.shape[1:] == (32,) and h.inp_key == h.lab_key == "tokens"
    rows = np.concatenate(h.rows)
    assert len(h.rows) == srv.CHECK_STEPS and len(rows) == srv.CHECK_STEPS \
        * h.bs
    # the first steps were fed the device block's output on these rows
    acts = jax.jit(lambda p, x: splitting.device_forward(b.model, p, x, 1))(
        b.dev_state["device"], tokens[rows])
    np.testing.assert_allclose(h.prog["feed"], np.asarray(acts),
                               rtol=1e-5, atol=1e-6)
    assert all(math.isfinite(x) for x in h.prog["losses"])


def test_the_window_counts_sequences(primed):
    h, srv = primed
    srv.size(h, 1.0)
    e2e, info = srv.window(h)
    per_epoch = (h.store.num_samples() // h.bs) * h.bs
    assert h.store.num_samples() == sum(len(c) for c in h.b.clients)
    assert info["samples"] == info["units"] * per_epoch
    assert info["failed"] == 0
    rate = e2e["server_samples_per_s"]
    assert math.isfinite(rate) and rate > 0
    assert rate == pytest.approx(info["samples"] / info["window_s"])


def test_consolidation_reads_token_rows_back(primed):
    from harness.registry import driver

    h, _ = primed
    con = driver("consolidate")
    c = types.SimpleNamespace(cfg=h.cfg, cell=h.cell, seed=SEED, b=h.b)
    con.setup(c)
    con.prime(c)
    assert c.prog["clients_short"] == 0 and c.prog["labels_wrong"] == 0
    assert len(c.prog["acts"]) == con.SAMPLE_ROWS
    assert all(a is not None and a.shape == (32, h.b.model.cfg.d_model)
               for a in c.prog["acts"])
