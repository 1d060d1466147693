"""Jittable training / serving steps.

These are the functions the launchers ``jax.jit(...).lower().compile()``
for the production meshes — the multi-pod dry-run and the roofline both
read from here.

Ampere decomposes training into two steps (never active simultaneously —
that is the point of UIT):

* :func:`make_device_round_step` — one federated round of the device phase:
  every participating client runs H local-SGD iterations on
  (device block + auxiliary network) starting from the global params, then
  the round ends with weighted FedAvg across the client axis (Eq. 9+10).
  Clients are vmapped over a leading axis that the launcher shards across
  the DP mesh axes, so per-client local SGD is embarrassingly parallel and
  the aggregation is one weighted psum — communication-wise this is
  *exactly* local SGD with period H.  :func:`make_device_round_pool_step`
  is the device-resident variant (batches gathered on device from a
  (K, H, b) index matrix into a flat sample pool uploaded once; state
  donated); :func:`make_client_round_fn` exposes the single-client round
  both variants vmap over.

* :func:`make_server_train_step` — one step of the centralized server phase
  over consolidated activations (Eq. 11+12): a standard DP x TP training
  step; >95% of total FLOPs live here for p=1, so this is the
  roofline-bearing graph.

Baselines / serving:

* :func:`make_e2e_train_step`    — end-to-end step (FL / SplitFed-V2
  semantics under immediate aggregation; also the non-split reference).
* :func:`make_prefill_step` / :func:`make_decode_step` — serving graphs
  for the decode_* input shapes.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import aggregation, auxiliary, losses, splitting
from repro.models import layers as L
from repro.models import transformer as T
from repro.optim import make_optimizer, make_schedule, clip_by_global_norm
from repro.sharding import shard


def named(name: str, fn):
    """``fn`` under ``jax.named_scope(name)``: a stable name for a jitted
    step's ops in a profile, whatever builds the step.  Each call enters
    a scope of its own (one ``named_scope`` used as a decorator keeps its
    state on itself, which nested or concurrent traces would share)."""
    @functools.wraps(fn)
    def scoped(*args, **kwargs):
        with jax.named_scope(name):
            return fn(*args, **kwargs)
    return scoped


def _device_batch_slice(batch, idx):
    return jax.tree.map(lambda a: a[idx], batch)


# ---------------------------------------------------------------------------
# Ampere device phase
# ---------------------------------------------------------------------------


def make_client_round_fn(model, run_cfg, *, impl="xla", xent_impl="xla"):
    """H local SGD iterations on ONE client (Eq. 9).

    ``client_round(device_params, aux_params, client_batches, lr)`` with
    batch leaves shaped (H, b, ...).  This is the unit the vectorized round
    steps vmap over a leading client axis; exported on its own so the
    fleet engine's sequential reference path and the equivalence tests run
    the *same* jitted math as the vmapped cohort round.
    """
    split_cfg = run_cfg.split
    p = split_cfg.split_point
    H = run_cfg.fed.local_steps

    def local_loss(par, batch):
        device_params, aux_params = par
        if model.kind == "lm":
            acts = splitting.device_forward(model, device_params,
                                            batch["tokens"], p, impl=impl)
        else:
            acts = splitting.device_forward(model, device_params,
                                            batch["images"], p, impl=impl)
        loss, m = auxiliary.aux_loss(model, aux_params, device_params, acts,
                                     batch, split_cfg, impl=impl,
                                     xent_impl=xent_impl)
        return loss

    def client_round(device_params, aux_params, client_batches, lr):
        def one_step(par, batch):
            loss, grads = jax.value_and_grad(local_loss)(par, batch)
            new_par = jax.tree.map(
                lambda q, g: (q.astype(jnp.float32)
                              - lr * g.astype(jnp.float32)).astype(q.dtype),
                par, grads)
            return new_par, loss

        from repro.analysis import scan_unroll
        (device_params, aux_params), losses_h = jax.lax.scan(
            one_step, (device_params, aux_params), client_batches, length=H,
            unroll=scan_unroll(H))
        return device_params, aux_params, jnp.mean(losses_h)

    return client_round


def _round_from_batches(client_round, state, batches, weights, lr):
    """vmap ``client_round`` over the leading client axis + weighted FedAvg."""
    dev_k, aux_k, loss_k = jax.vmap(
        client_round, in_axes=(None, None, 0, None))(
            state["device"], state["aux"], batches, lr)
    new_device = aggregation.fedavg_stacked(dev_k, weights)
    new_aux = aggregation.fedavg_stacked(aux_k, weights)
    w = aggregation.normalize_weights(weights)
    metrics = {"loss": jnp.sum(loss_k * w)}
    return {"device": new_device, "aux": new_aux}, metrics


def make_device_round_step(model, run_cfg, *, impl="xla", xent_impl="xla"):
    client_round = make_client_round_fn(model, run_cfg, impl=impl,
                                        xent_impl=xent_impl)

    def device_round_step(state, batches, weights, lr):
        """state: {"device":..., "aux":...}; batches leaves (K, H, b, ...);
        weights: (K,) aggregation weights (zeros = dropped client).

        Intended jit: ``jax.jit(device_round_step, donate_argnums=(0,))``
        — the round state threads through every round, so donating it
        keeps one resident copy instead of two live copies per round.
        """
        return _round_from_batches(client_round, state, batches, weights, lr)

    return device_round_step


def make_device_round_pool_step(model, run_cfg, *, impl="xla",
                                xent_impl="xla"):
    """Pool-fed federated round: the cohort's batches are *gathered on
    device* from a resident flat sample pool instead of being re-uploaded
    as a (K, H, b, ...) stack every round.

    ``pool_round_step(state, pool, idx, weights, lr)`` where ``pool``
    leaves are (N_total, ...) device-resident sample arrays (uploaded once
    for the whole run), and ``idx`` is a (K, H, b) int32 matrix of global
    sample indices — the only per-round host->device transfer besides the
    scalar lr and (K,) weights.  Intended jit:
    ``jax.jit(pool_round_step, donate_argnums=(0,))`` (donate the state,
    NEVER the pool — it must survive across rounds).
    """
    client_round = make_client_round_fn(model, run_cfg, impl=impl,
                                        xent_impl=xent_impl)

    def pool_round_step(state, pool, idx, weights, lr):
        batches = jax.tree.map(lambda a: jnp.take(a, idx, axis=0), pool)
        return _round_from_batches(client_round, state, batches, weights, lr)

    return pool_round_step


def _buffered_from_batches(client_round, state, snapshots, batches,
                           weights, lr):
    """vmap ``client_round`` with PER-CLIENT init params + FedBuff agg.

    Unlike :func:`_round_from_batches` (every client starts from the one
    shared state), each buffered client trains from its own stale
    snapshot of the global model (leading client axis on ``snapshots``
    leaves), and the weighted *deltas* are folded into the current
    global ``state`` (:func:`repro.core.aggregation.fedbuff_stacked`).
    """
    dev_k, aux_k, loss_k = jax.vmap(
        client_round, in_axes=(0, 0, 0, None))(
            snapshots["device"], snapshots["aux"], batches, lr)
    new_device = aggregation.fedbuff_stacked(state["device"], dev_k,
                                             snapshots["device"], weights)
    new_aux = aggregation.fedbuff_stacked(state["aux"], aux_k,
                                          snapshots["aux"], weights)
    w = aggregation.normalize_weights(weights)
    metrics = {"loss": jnp.sum(loss_k * w)}
    return {"device": new_device, "aux": new_aux}, metrics


def make_buffered_round_step(model, run_cfg, *, impl="xla",
                             xent_impl="xla"):
    """Buffered (FedBuff-style) federated round from uploaded batches.

    ``buffered_round_step(state, snapshots, batches, weights, lr)`` —
    ``state`` is the current global {"device", "aux"} (NOT donated: past
    versions stay live as snapshots for still-in-flight clients),
    ``snapshots`` stacks each buffered client's init params over a
    leading K axis, batch leaves are (K, H, b, ...).
    """
    client_round = make_client_round_fn(model, run_cfg, impl=impl,
                                        xent_impl=xent_impl)

    def buffered_round_step(state, snapshots, batches, weights, lr):
        return _buffered_from_batches(client_round, state, snapshots,
                                      batches, weights, lr)

    return buffered_round_step


def make_buffered_round_pool_step(model, run_cfg, *, impl="xla",
                                  xent_impl="xla"):
    """Pool-fed buffered round: like :func:`make_device_round_pool_step`
    but with per-client init snapshots and FedBuff delta aggregation.

    Intended jit: NO donation — ``state`` remains a live entry of the
    trainer's version ring (stale in-flight clients still reference it),
    the pool must survive across rounds, and the (K, ...) snapshot stack
    cannot alias the un-stacked output.
    """
    client_round = make_client_round_fn(model, run_cfg, impl=impl,
                                        xent_impl=xent_impl)

    def buffered_pool_round_step(state, snapshots, pool, idx, weights, lr):
        batches = jax.tree.map(lambda a: jnp.take(a, idx, axis=0), pool)
        return _buffered_from_batches(client_round, state, snapshots,
                                      batches, weights, lr)

    return buffered_pool_round_step


# ---------------------------------------------------------------------------
# Ampere server phase
# ---------------------------------------------------------------------------


def make_server_train_step(model, run_cfg, *, impl="xla", xent_impl="xla",
                           grad_shardings=None, entry=None):
    """``grad_shardings``: optional NamedSharding tree matching the server
    params; constraining the gradients to the parameter sharding right at
    the grad boundary makes SPMD materialize them as a reduce-scatter in
    the backward dtype instead of a full-precision all-reduce deferred to
    the optimizer use-site (measured 2-4x collective reduction on ZeRO
    configs).

    ``entry``: static cut depth this step's activations were produced at
    (heterogeneous-cut consolidation trains one server block with
    per-bucket entry points); ``None`` = the split point itself."""
    cfg = model.cfg
    p = run_cfg.split.split_point
    opt = make_optimizer(run_cfg.optim)
    sched = make_schedule(run_cfg.optim)
    scan = run_cfg.sharding.scan_layers
    remat = run_cfg.sharding.remat

    def loss_fn(server_params, batch):
        acts = batch["acts"]
        if "acts_scale" in batch:   # int8 payload stayed quantized until here
            from repro.runtime import compression
            acts = compression.dequantize_int8(acts, batch["acts_scale"])
        out = splitting.server_forward(model, server_params, acts, p,
                                       impl=impl, scan=scan, remat=remat,
                                       entry=entry)
        if model.kind == "lm":
            head_w = splitting.server_head_weight(server_params)
            loss, m = losses.lm_loss_from_hidden(
                out["hidden"], head_w, batch["tokens"],
                softcap=cfg.final_softcap, impl=xent_impl,
                loss_mask=batch.get("loss_mask"))
        else:
            loss, m = losses.classification_loss(out["logits"],
                                                 batch["labels"])
        return loss + out["aux"], m

    def server_train_step(state, batch):
        (loss, m), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state["server"], batch)
        if run_cfg.optim.grad_dtype:
            gd = jnp.dtype(run_cfg.optim.grad_dtype)
            grads = jax.tree.map(lambda g: g.astype(gd), grads)
        if grad_shardings is not None:
            grads = jax.lax.with_sharding_constraint(grads, grad_shardings)
        if run_cfg.optim.grad_clip:
            grads, _ = clip_by_global_norm(grads, run_cfg.optim.grad_clip)
        lr = sched(state["step"])
        new_params, new_opt = opt.update(grads, state["opt"],
                                         state["server"], lr)
        new_state = {"server": new_params, "opt": new_opt,
                     "step": state["step"] + 1}
        m = dict(m, lr=lr)
        return new_state, m

    return server_train_step


def init_server_state(model, run_cfg, server_params):
    opt = make_optimizer(run_cfg.optim)
    return {"server": server_params, "opt": opt.init(server_params),
            "step": jnp.zeros((), jnp.int32)}


def make_server_epoch_fn(model, run_cfg, *, impl="xla", xent_impl="xla",
                         grad_shardings=None, entry=None):
    """One FULL server epoch as a single jittable function.

    ``epoch_fn(state, pool, idx)`` scans :func:`make_server_train_step`
    over ``idx`` — an (nb, batch) int32 matrix of gathered sample indices
    into the device-resident consolidated ``pool`` (int8 payloads stay
    quantized in HBM; the step dequantizes per batch).  Per-batch losses
    come back as one (nb,) device array, so the host syncs once per
    epoch instead of once per step.  Intended use:
    ``jax.jit(make_server_epoch_fn(...), donate_argnums=(0,))``.
    """
    step = make_server_train_step(model, run_cfg, impl=impl,
                                  xent_impl=xent_impl,
                                  grad_shardings=grad_shardings, entry=entry)

    def epoch_fn(state, pool, idx):
        def body(state, idx_b):
            batch = jax.tree.map(lambda a: jnp.take(a, idx_b, axis=0), pool)
            state, m = step(state, batch)
            return state, m["loss"]

        return jax.lax.scan(body, state, idx)

    return epoch_fn


# ---------------------------------------------------------------------------
# End-to-end baseline step (FL / SplitFed-V2-like)
# ---------------------------------------------------------------------------


def _lm_hidden_and_loss(cfg, params, tokens, *, impl, xent_impl, scan, remat,
                        loss_mask=None):
    out = T.forward(cfg, params, tokens, impl=impl, scan=scan, remat=remat,
                    return_logits=False)
    h = L.rmsnorm(params["final_norm"], out["hidden"], cfg.norm_eps, cfg.dtype)
    head_w = T.head_weight(cfg, params)
    loss, m = losses.lm_loss_from_hidden(h, head_w, tokens,
                                         softcap=cfg.final_softcap,
                                         impl=xent_impl, loss_mask=loss_mask)
    return loss + out["aux"], m


def make_e2e_train_step(model, run_cfg, *, impl="xla", xent_impl="xla"):
    cfg = model.cfg
    opt = make_optimizer(run_cfg.optim)
    sched = make_schedule(run_cfg.optim)
    scan = run_cfg.sharding.scan_layers
    remat = run_cfg.sharding.remat

    def loss_fn(params, batch):
        if model.kind == "lm":
            return _lm_hidden_and_loss(cfg, params, batch["tokens"],
                                       impl=impl, xent_impl=xent_impl,
                                       scan=scan, remat=remat,
                                       loss_mask=batch.get("loss_mask"))
        out = model.apply(params, batch["images"], remat=remat)
        loss, m = losses.classification_loss(out["logits"], batch["labels"])
        return loss + out["aux"], m

    def e2e_train_step(state, batch):
        (loss, m), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state["params"], batch)
        if run_cfg.optim.grad_clip:
            grads, _ = clip_by_global_norm(grads, run_cfg.optim.grad_clip)
        lr = sched(state["step"])
        new_params, new_opt = opt.update(grads, state["opt"],
                                         state["params"], lr)
        return ({"params": new_params, "opt": new_opt,
                 "step": state["step"] + 1}, dict(m, lr=lr))

    return e2e_train_step


def init_e2e_state(model, run_cfg, params):
    opt = make_optimizer(run_cfg.optim)
    return {"params": params, "opt": opt.init(params),
            "step": jnp.zeros((), jnp.int32)}


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------


def make_prefill_step(model, run_cfg, *, impl="xla"):
    cfg = model.cfg
    scan = run_cfg.sharding.scan_layers

    def prefill_step(params, tokens, caches):
        """Fill the KV caches for the prompt; return last-position logits.

        Logits are computed for the LAST position only — materializing
        (B, S, V) for a 32k prompt would be hundreds of GB."""
        out = T.forward(cfg, params, tokens, caches=caches, cache_index=0,
                        impl=impl, scan=scan, remat="none",
                        return_logits=False)
        h = L.rmsnorm(params["final_norm"], out["hidden"][:, -1:],
                      cfg.norm_eps, cfg.dtype)
        if cfg.tie_embeddings:
            logits = L.unembed(params["embed"], h, cfg.dtype)
        else:
            logits = L.dense(params["head"], h, cfg.dtype)
        logits = L.softcap(logits, cfg.final_softcap)
        return logits[:, 0], out["caches"]

    return prefill_step


def make_decode_step(model, run_cfg, *, impl="xla", scan: bool = False):
    cfg = model.cfg

    def decode_step(params, caches, token, index):
        """One decode step: token (B, 1) at position ``index``."""
        out = T.forward(cfg, params, token, caches=caches, cache_index=index,
                        impl=impl, scan=scan, remat="none")
        next_token = jnp.argmax(out["logits"][:, -1], axis=-1)
        return next_token, out["logits"][:, -1], out["caches"]

    return decode_step
