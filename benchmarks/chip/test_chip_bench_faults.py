"""A run whose timed path is broken underneath comes out not correct.

Each test breaks the program's own step, at a small size on the CPU with
the harness's look for a chip skipped, and drives the rest of a run: the
check against the reference, with the cell's committed limits, has to
read ``correct: false``.  The faults are the ones each cell can have: a
training step that returns its state unchanged, half of the batch left
out of the mean, and an answer altered (or half of it dropped) where
consolidation produces it; a server phase fed activations kept in
bfloat16; and in a device round, aggregation weights off the cell's
rule or a client's batch drawn outside its shard.  No
cell crosses chips, so no exchange can be left out.
"""

import importlib.util
import json
import pathlib

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent.parent
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
TRAINING = [w["name"] for w in SPEC["workloads"]
            if w["traffic"] in ("server", "device")]
CONSOLIDATION = [w["name"] for w in SPEC["workloads"]
                 if w["traffic"] == "consolidate"]
DEVICE = [w["name"] for w in SPEC["workloads"] if w["traffic"] == "device"]
SERVER = [w["name"] for w in SPEC["workloads"] if w["traffic"] == "server"]


@pytest.fixture(scope="module")
def run_mod():
    spec = importlib.util.spec_from_file_location("chipbench_run_faults",
                                                  BENCH / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod._paths()
    return mod


def _small(run_mod, cell):
    from harness.small import edit

    return run_mod.run_cell(cell, 2 ** 32 + 11, 1.0, False, allow_cpu=True,
                            smoke=True, edit=edit, cache=False)


def _unchanged(make):
    def make_broken(*args, **kwargs):
        step = make(*args, **kwargs)

        def broken(state, *rest):
            new, m = step(state, *rest)
            return state, m
        return broken
    return make_broken


@pytest.mark.parametrize("cell", TRAINING)
def test_state_left_unchanged_is_not_correct(run_mod, cell, monkeypatch):
    from repro.core import steps

    monkeypatch.setattr(steps, "make_server_train_step",
                        _unchanged(steps.make_server_train_step))
    monkeypatch.setattr(steps, "make_device_round_pool_step",
                        _unchanged(steps.make_device_round_pool_step))
    r = _small(run_mod, cell)
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("cell", TRAINING)
def test_half_batch_mean_is_not_correct(run_mod, cell, monkeypatch):
    from repro.core import losses

    full = losses.classification_loss

    def half(logits, labels):
        n = logits.shape[0] // 2
        return full(logits[:n], labels[:n])

    monkeypatch.setattr(losses, "classification_loss", half)
    r = _small(run_mod, cell)
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("cell", CONSOLIDATION)
@pytest.mark.parametrize("fault", ["altered", "half"])
def test_consolidated_answer_broken_is_not_correct(run_mod, cell, fault,
                                                   monkeypatch):
    from repro.core import splitting

    fwd = splitting.device_forward

    def broken(model, params, inputs, p, **kw):
        out = fwd(model, params, inputs, p, **kw)
        if fault == "altered":
            return out.at[0].multiply(1.5)
        return out[: max(1, out.shape[0] // 2)]

    monkeypatch.setattr(splitting, "device_forward", broken)
    r = _small(run_mod, cell)
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("cell", DEVICE)
def test_aggregation_weights_off_the_rule_are_not_correct(run_mod, cell,
                                                          monkeypatch):
    from repro.core import aggregation

    sample = aggregation.sample_cohort

    def skewed(*args, **kwargs):
        c = sample(*args, **kwargs)
        w = np.arange(1.0, len(c["weights"]) + 1.0)
        c["weights"] = w / w.sum()
        return c

    monkeypatch.setattr(aggregation, "sample_cohort", skewed)
    r = _small(run_mod, cell)
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("cell", DEVICE)
def test_rows_outside_the_clients_shard_are_not_correct(run_mod, cell,
                                                        monkeypatch):
    from repro.data.pipeline import ClientData

    draw = ClientData.batch_indices

    def strayed(self, batch_size, steps):
        idx = draw(self, batch_size, steps)
        idx[0, 0] = len(self)           # the next client's first row
        return idx

    monkeypatch.setattr(ClientData, "batch_indices", strayed)
    r = _small(run_mod, cell)
    assert r["checks"]["cohort_faults"]["value"] >= 1
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("cell", SERVER)
def test_bf16_pool_is_not_correct(run_mod, cell, monkeypatch):
    """The consolidated pool kept in bfloat16: every later step reads
    rounded activations."""
    import jax.numpy as jnp

    from repro.core import splitting

    fwd = splitting.device_forward

    def rounded(*args, **kwargs):
        out = fwd(*args, **kwargs)
        return out.astype(jnp.bfloat16).astype(out.dtype)

    monkeypatch.setattr(splitting, "device_forward", rounded)
    r = _small(run_mod, cell)
    assert r["checks"]["feed_gap"]["value"] > r["checks"]["feed_gap"]["limit"]
    assert not r["correct"], r["checks"]
