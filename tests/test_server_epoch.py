"""Device-resident server phase + fused xent backward + feeding pipeline.

Covers the PR's new paths:
* fused single-pass xent backward vs the materializing oracle (fp32,
  softcap, padded T/V tails, oversized block_t clamp);
* jitted whole-epoch server training: loss trajectory equivalent to the
  seed per-batch host loop under a fixed seed (bitwise on the LM smoke
  config — the roofline-bearing path; the vision conv path is compiled
  inside lax.scan and may differ in the last ulp, checked to 1e-5);
* the run_server_phase epoch loop performs zero per-step host syncs
  (no ``float(`` call inside the loop body — source-level check);
* DevicePrefetcher ordering;
* streaming store: one guaranteed full epoch over the COMPLETE pool
  after finish(), including late-arriving shards.
"""

import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.configs.base import FedConfig, OptimConfig, RunConfig, replace
from repro.core import steps
from repro.core.uit import AmpereTrainer
from repro.data import (ActivationStore, DevicePrefetcher, federate,
                        make_dataset_for_model)
from repro.kernels.xent.kernel import (clamp_block_t, fused_xent_pallas,
                                       xent_bwd, xent_fwd)
from repro.kernels.xent.ref import cross_entropy_ref
from repro.models import build_model

rng = np.random.default_rng(7)


# ---------------------------------------------------------------------------
# fused single-pass xent backward
# ---------------------------------------------------------------------------

BWD_CASES = [
    # T, D, V, softcap, block_t, block_v
    (24, 32, 100, 0.0, None, None),     # divisible T
    (16, 64, 53, 30.0, None, None),     # softcap + padded V tail
    (33, 48, 257, 0.0, None, None),     # padded T and V tails
    (20, 16, 130, 10.0, 256, 64),       # oversized bt clamps toward T
    (64, 16, 1000, 0.0, 8, 128),        # many tiles both axes
    (8, 32, 17, 10.0, 8, 16),           # single token tile
    (7, 8, 9, 0.0, None, None),         # sub-tile T with padding
]


@pytest.mark.parametrize("case", BWD_CASES)
def test_fused_backward_matches_ref(case):
    T, D, V, cap, bt, bv = case
    h = jnp.asarray(rng.normal(0, 1, (T, D)), jnp.float32)
    w = jnp.asarray(rng.normal(0, 1, (D, V)) / np.sqrt(D), jnp.float32)
    lab = jnp.asarray(rng.integers(0, V, (T,)), jnp.int32)

    dh_ref, dw_ref = jax.grad(
        lambda h, w: cross_entropy_ref(h, w, lab, softcap=cap)[0],
        argnums=(0, 1))(h, w)
    _, lse = xent_fwd(h, w, lab, softcap=cap, block_t=bt, block_v=bv)
    g = jnp.full((T,), 1.0 / T, jnp.float32)
    dh, dw = xent_bwd(h, w, lab, lse, g, softcap=cap, block_t=bt, block_v=bv)
    np.testing.assert_allclose(np.asarray(dh), np.asarray(dh_ref),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(dw), np.asarray(dw_ref),
                               atol=1e-4, rtol=1e-4)

    # and through the custom-vjp public entry
    dh2, dw2 = jax.grad(
        lambda h, w: jnp.mean(fused_xent_pallas(h, w, lab, cap)),
        argnums=(0, 1))(h, w)
    np.testing.assert_allclose(np.asarray(dh2), np.asarray(dh_ref),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(dw2), np.asarray(dw_ref),
                               atol=1e-4, rtol=1e-4)


def test_backward_is_single_pallas_call():
    """The fused backward lowers to exactly one pallas_call."""
    h = jnp.zeros((16, 8), jnp.float32)
    w = jnp.zeros((8, 40), jnp.float32)
    lab = jnp.zeros((16,), jnp.int32)
    lse = jnp.zeros((16,), jnp.float32)
    g = jnp.ones((16,), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda *a: xent_bwd(*a, block_t=8, block_v=16))(h, w, lab, lse, g)
    n_calls = str(jaxpr).count("pallas_call")
    assert n_calls == 1, f"expected 1 pallas_call in backward, got {n_calls}"


def test_alias_strategy_plumbing():
    """The TPU dH strategy can't produce correct dH under the interpreter
    (output flushes don't feed aliased input re-reads), but its dW path
    is scratch-accumulated and identical — run it to pin shapes, specs
    and the dW numerics of the alias variant."""
    T, D, V, cap = 33, 16, 100, 10.0
    h = jnp.asarray(rng.normal(0, 1, (T, D)), jnp.float32)
    w = jnp.asarray(rng.normal(0, 1, (D, V)) / np.sqrt(D), jnp.float32)
    lab = jnp.asarray(rng.integers(0, V, (T,)), jnp.int32)
    _, dw_ref = jax.grad(
        lambda h, w: cross_entropy_ref(h, w, lab, softcap=cap)[0],
        argnums=(0, 1))(h, w)
    _, lse = xent_fwd(h, w, lab, softcap=cap, block_t=8, block_v=32)
    g = jnp.full((T,), 1.0 / T, jnp.float32)
    dh, dw = xent_bwd(h, w, lab, lse, g, softcap=cap, block_t=8,
                      block_v=32, dh_strategy="alias")
    assert dh.shape == (T, D)
    np.testing.assert_allclose(np.asarray(dw), np.asarray(dw_ref),
                               atol=1e-4, rtol=1e-4)


def test_block_clamp_short_sequences():
    # bt=256 with T=20 must clamp to the 8-aligned cover of T, not pad 12x
    assert clamp_block_t(256, 20) == 24
    assert clamp_block_t(256, 256) == 256
    assert clamp_block_t(8, 100) == 8
    assert clamp_block_t(256, 3) == 8
    # fwd result unaffected by an oversized requested block
    T, D, V = 20, 16, 64
    h = jnp.asarray(rng.normal(0, 1, (T, D)), jnp.float32)
    w = jnp.asarray(rng.normal(0, 1, (D, V)), jnp.float32)
    lab = jnp.asarray(rng.integers(0, V, (T,)), jnp.int32)
    _, ref = cross_entropy_ref(h, w, lab)
    loss, _ = xent_fwd(h, w, lab, block_t=256, block_v=32)
    np.testing.assert_allclose(np.asarray(loss), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# jitted server epoch ≡ seed per-batch loop
# ---------------------------------------------------------------------------


def _setup(arch, n_train=96, n_eval=48, seq=32):
    cfg = registry.get_smoke_config(arch)
    m = build_model(cfg)
    kw = dict(seq_len=seq) if m.kind == "lm" else {}
    train = make_dataset_for_model(m, n_train, seed=0, **kw)
    test = make_dataset_for_model(m, n_eval, seed=1, **kw)
    clients = federate(train, 4, 0.5, seed=0)
    run = RunConfig(fed=FedConfig(num_clients=4, clients_per_round=2,
                                  local_steps=2, device_batch_size=4,
                                  server_batch_size=8),
                    optim=OptimConfig(name="momentum", lr=0.1,
                                      schedule="inverse_time",
                                      decay_gamma=0.01))
    return m, run, clients, test


def _filled_stores(tr, dev_state):
    """Two identically-seeded stores with identical shard order."""
    sa = ActivationStore(seed=0)
    tr.generate_activations(dev_state, sa)
    sb = ActivationStore(seed=0)
    for cid in sa.clients():
        for shard in sa._mem[cid]:
            sb.add(cid, shard)
    return sa, sb


def _seed_loop_epochs(m, run, srv, store, epochs):
    """The pre-PR server loop, verbatim semantics: host shuffle + per-batch
    upload + per-step float() sync."""
    step = jax.jit(steps.make_server_train_step(m, run))
    st = steps.init_server_state(m, run, srv)
    out = []
    for _ in range(epochs):
        ls = []
        for batch in store.batches(run.fed.server_batch_size, epochs=1):
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            st, mm = step(st, batch)
            ls.append(float(mm["loss"]))
        out.append(np.asarray(ls))
    return out


def _jitted_epochs(m, run, srv, store, epochs):
    epoch_fn = jax.jit(steps.make_server_epoch_fn(m, run),
                       donate_argnums=(0,))
    pool = {k: jnp.asarray(v) for k, v in store.pool(dequantize=False).items()}
    st = jax.tree.map(lambda a: jnp.array(a),
                      steps.init_server_state(m, run, srv))
    out = []
    for _ in range(epochs):
        idx = jnp.asarray(store.epoch_indices(run.fed.server_batch_size))
        st, losses = epoch_fn(st, pool, idx)
        out.append(np.asarray(losses, np.float64))
    return out


@pytest.mark.slow
def test_jitted_epoch_bitwise_lm():
    m, run, clients, test = _setup("qwen3-1.7b")
    tr = AmpereTrainer(m, run, clients, test, patience=50)
    dev, srv, aux = tr._init_states(jax.random.PRNGKey(0))
    sa, sb = _filled_stores(tr, {"device": dev, "aux": aux})
    ref = _seed_loop_epochs(m, run, srv, sa, 2)
    new = _jitted_epochs(m, run, srv, sb, 2)
    for ep, (a, b) in enumerate(zip(ref, new)):
        assert a.shape == b.shape
        np.testing.assert_array_equal(a, b, err_msg=f"epoch {ep}")


@pytest.mark.slow
def test_jitted_epoch_close_vision():
    m, run, clients, test = _setup("mobilenet-l", n_train=128)
    tr = AmpereTrainer(m, run, clients, test, patience=50)
    dev, srv, aux = tr._init_states(jax.random.PRNGKey(0))
    sa, sb = _filled_stores(tr, {"device": dev, "aux": aux})
    ref = _seed_loop_epochs(m, run, srv, sa, 2)
    new = _jitted_epochs(m, run, srv, sb, 2)
    for a, b in zip(ref, new):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


@pytest.mark.slow
def test_run_server_phase_uses_resident_path_and_no_step_syncs():
    m, run, clients, test = _setup("mobilenet-l", n_train=128)
    tr = AmpereTrainer(m, run, clients, test, patience=50)
    dev, srv, aux = tr._init_states(jax.random.PRNGKey(0))
    dev_state = {"device": dev, "aux": aux}
    store = ActivationStore(seed=0)
    tr.generate_activations(dev_state, store)
    st = tr.run_server_phase(dev_state, srv, store, max_epochs=2)
    assert len(tr.history["server"]) == 2
    assert np.isfinite(tr.history["server"][-1]["loss"])
    assert int(st["step"]) == 2 * (store.num_samples()
                                   // run.fed.server_batch_size)
    # the resident epoch loop must not sync per step: no float() between
    # the epoch-fn call and the per-epoch np.asarray landing
    src = inspect.getsource(AmpereTrainer.run_server_phase)
    resident_branch = src.split("if resident:")[2].split("else:")[0]
    assert "float(" not in resident_branch
    assert "self._server_epoch" in resident_branch


@pytest.mark.slow
def test_run_server_phase_streaming_fallback_budget():
    m, run, clients, test = _setup("mobilenet-l", n_train=128)
    run = replace(run, device_pool_budget_mb=0)   # force the fallback
    tr = AmpereTrainer(m, run, clients, test, patience=50)
    dev, srv, aux = tr._init_states(jax.random.PRNGKey(0))
    dev_state = {"device": dev, "aux": aux}
    store = ActivationStore(seed=0)
    tr.generate_activations(dev_state, store)
    tr.run_server_phase(dev_state, srv, store, max_epochs=1)
    assert len(tr.history["server"]) == 1
    assert np.isfinite(tr.history["server"][-1]["loss"])


@pytest.mark.parametrize("path", ["streamed", "resident"])
def test_run_server_phase_pool_concat_bytes(path, monkeypatch):
    """A streamed phase gathers its batches from the shards and never
    concatenates the pool; a resident one concatenates it once, to
    upload it.  ``server.phase`` carries the count."""
    from repro.observability import Observability

    m, run, clients, test = _setup("vit-s")
    if path == "streamed":
        run = replace(run, device_pool_budget_mb=0)
    obs = Observability()
    tr = AmpereTrainer(m, run, clients, test, patience=50, obs=obs)
    dev, srv, aux = tr._init_states(jax.random.PRNGKey(0))
    dev_state = {"device": dev, "aux": aux}
    store = ActivationStore(seed=0)
    tr.generate_activations(dev_state, store)
    if path == "streamed":
        def no_pool(*a, **k):
            raise AssertionError("a streamed epoch concatenated the pool")
        monkeypatch.setattr(store, "_pool", no_pool)
    tr.run_server_phase(dev_state, srv, store, max_epochs=2)
    assert len(tr.history["server"]) == 2
    want = 0 if path == "streamed" else store.pool_nbytes()
    assert store.pool_concat_bytes == want
    phase = [e for e in obs.tracer.events if e.name == "server.phase"]
    assert len(phase) == 1
    assert phase[0].attrs["pool_concat_bytes"] == want


# ---------------------------------------------------------------------------
# feeding pipeline
# ---------------------------------------------------------------------------


def test_device_prefetcher_order_and_transfer():
    items = [((i, "meta"), {"x": np.full((4,), i, np.float32)})
             for i in range(17)]
    got = list(DevicePrefetcher(iter(items), depth=3))
    assert [m for m, _ in got] == [m for m, _ in items]
    for i, (_, tree) in enumerate(got):
        assert isinstance(tree["x"], jax.Array)
        np.testing.assert_array_equal(np.asarray(tree["x"]),
                                      np.full((4,), i, np.float32))


def test_device_prefetcher_propagates_errors():
    def gen():
        yield (0, {"x": np.zeros(2, np.float32)})
        raise ValueError("boom")

    it = iter(DevicePrefetcher(gen()))
    next(it)
    with pytest.raises(ValueError, match="boom"):
        list(it)


def test_streaming_final_epoch_covers_late_shards():
    st = ActivationStore(consolidated=True, seed=0)
    st.add(0, {"acts": np.zeros((8, 4), np.float32),
               "labels": np.zeros((8,), np.int32)})
    gen = st.streaming_batches(4)
    # consume at least one full mid-stream epoch over the early pool
    first = [next(gen), next(gen)]
    assert all((b["labels"] == 0).all() for b in first)
    # a late shard lands, then the producer closes
    st.add(1, {"acts": np.ones((8, 4), np.float32),
               "labels": np.ones((8,), np.int32)})
    st.finish()
    rest = list(gen)
    # the final full epoch covers the COMPLETE pool: every late sample
    # appears at least once after close
    late = sum(int((b["labels"] == 1).sum()) for b in rest)
    assert late >= 8, "late-arriving shard missed by the final epoch"
    # the final epoch is exactly one full pass at the tail: the last 4
    # batches (16 samples) contain each client's 8 samples exactly once
    tail = rest[-4:]
    lab_tail = np.concatenate([b["labels"] for b in tail])
    assert len(lab_tail) == 16
    assert (lab_tail == 0).sum() == 8 and (lab_tail == 1).sum() == 8


def test_streaming_closed_before_iteration_single_epoch():
    st = ActivationStore(consolidated=True, seed=0)
    st.add(0, {"acts": np.arange(32, dtype=np.float32).reshape(8, 4),
               "labels": np.arange(8, dtype=np.int32)})
    st.finish()
    batches = list(st.streaming_batches(4))
    assert len(batches) == 2  # exactly one full epoch, then stop
    seen = np.sort(np.concatenate([b["labels"] for b in batches]))
    np.testing.assert_array_equal(seen, np.arange(8))


def test_epoch_indices_match_batches_draw():
    st1 = ActivationStore(seed=3)
    st2 = ActivationStore(seed=3)
    data = {"acts": rng.normal(0, 1, (20, 4)).astype(np.float32),
            "labels": np.arange(20, dtype=np.int32)}
    st1.add(0, data)
    st2.add(0, data)
    via_batches = [b["labels"] for b in st1.batches(8, epochs=1)]
    idx = st2.epoch_indices(8)
    assert idx.shape == (2, 8)
    for got, b in zip(idx, via_batches):
        np.testing.assert_array_equal(data["labels"][got], b)


def test_server_batch_larger_than_pool_fails_loudly():
    """A server batch the consolidated pool cannot fill would run no
    server step and report a NaN loss; the phase must refuse instead."""
    m, run, clients, test = _setup("vit-s")
    run = replace(run, fed=replace(run.fed, server_batch_size=128))
    tr = AmpereTrainer(m, run, clients, test, patience=50)
    dev, srv, aux = tr._init_states(jax.random.PRNGKey(0))
    dev_state = {"device": dev, "aux": aux}
    store = ActivationStore(seed=0)
    tr.generate_activations(dev_state, store)
    assert store.num_samples() < 128
    with pytest.raises(ValueError, match="server batch 128 exceeds"):
        tr.run_server_phase(dev_state, srv, store, max_epochs=1)
