"""Fused (never-materialize-the-logits) cross entropy over huge vocabularies.

The memory hot spot of every large-vocab LM loss: (B*S, V) logits at fp32
are multiple GB for V in [150k, 256k].  This op computes the softmax
cross-entropy *blockwise over the vocabulary*, carrying only the online
(max, sumexp, correct-logit) statistics:

* ``impl="xla"``   — lax.scan over vocab tiles, each step rematerialized
  (jax.checkpoint) so autodiff recomputes the tile logits in the backward
  pass instead of saving them.  This is the path the dry-run lowers.
* ``impl="pallas"``— the TPU Pallas kernel (kernel.py), VMEM-tiled with a
  custom VJP.
* ``impl="ref"``   — the materializing oracle (test scale only).

All paths support gemma2's final-logit softcap.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.xent.ref import cross_entropy_ref


def _blockwise_stats(hidden, w, labels, softcap: float, block: int):
    """Online (m, l, correct) over vocab tiles.  hidden: (T, D), w: (D, V)."""
    T, D = hidden.shape
    V = w.shape[1]
    pad = (-V) % block
    if pad:
        w = jnp.pad(w, ((0, 0), (0, pad)))
    nb = w.shape[1] // block
    wb = w.reshape(D, nb, block)

    hf = hidden.astype(jnp.float32)

    def step(carry, inp):
        m, l, corr = carry
        w_blk, j = inp
        logits = hf @ w_blk.astype(jnp.float32)          # (T, block)
        if softcap:
            logits = jnp.tanh(logits / softcap) * softcap
        base = j * block
        ids = base + jnp.arange(block)
        valid = ids < V
        logits = jnp.where(valid[None, :], logits, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        l_new = l * jnp.exp(m - m_new) + jnp.sum(
            jnp.exp(logits - m_new[:, None]), axis=-1)
        is_here = (labels >= base) & (labels < base + block)
        local = jnp.clip(labels - base, 0, block - 1)
        got = jnp.take_along_axis(logits, local[:, None], axis=1)[:, 0]
        corr_new = jnp.where(is_here, got, corr)
        return (m_new, l_new, corr_new), None

    m0 = jnp.full((T,), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((T,), jnp.float32)
    c0 = jnp.zeros((T,), jnp.float32)
    wb_seq = jnp.moveaxis(wb, 1, 0)                       # (nb, D, block)
    from repro.analysis import scan_unroll
    (m, l, corr), _ = jax.lax.scan(
        jax.checkpoint(step), (m0, l0, c0), (wb_seq, jnp.arange(nb)),
        unroll=scan_unroll(nb))
    return m, l, corr


def _vocab_shards() -> int:
    """Size of the mesh axes bound to the logical "vocab" axis (1 when no
    mesh context is active)."""
    from repro.sharding.annotations import current_mesh, logical_to_spec
    mesh = current_mesh()
    if mesh is None:
        return 1
    spec = logical_to_spec("vocab")[0]
    if spec is None:
        return 1
    axes = (spec,) if isinstance(spec, str) else spec
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n = 1
    for a in axes:
        n *= sizes[a]
    return n


def _sharded_per_token(hidden, w, labels, softcap: float):
    """SPMD-native CE: materialize logits *sharded* over (batch x vocab)
    and reduce with collectives — under TP this is one matmul + tiny
    psums, no weight resharding.  jax.checkpoint makes the backward
    recompute the logits tile instead of saving (T, V) fp32.

    Vocabularies that do not divide the vocab-shard count are padded up to
    a multiple (otherwise GSPMD replicates the logits — a multi-GB fp32
    regression observed for the 49155/50280 vocab archs)."""
    from repro.sharding import shard

    V = w.shape[1]
    n = _vocab_shards()
    pad = (-V) % n
    if pad:
        w = jnp.pad(w, ((0, 0), (0, pad)))

    def f(h, wv):
        logits = jnp.einsum("td,dv->tv", h.astype(jnp.float32),
                            wv.astype(jnp.float32))
        if softcap:
            logits = jnp.tanh(logits / softcap) * softcap
        logits = shard(logits, "batch", "vocab")
        ids = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        if pad:
            logits = jnp.where(ids < V, logits, -1e30)
        lse = jax.nn.logsumexp(logits, axis=-1)
        corr = jnp.sum(jnp.where(ids == labels[:, None], logits, 0.0),
                       axis=-1)
        return lse - corr

    return jax.checkpoint(f)(hidden, w)


def cross_entropy(hidden, w, labels, mask=None, *, softcap: float = 0.0,
                  impl: str = "xla", block: int = 2048):
    """Mean cross-entropy; hidden (T, D), w (D, V), labels (T,).

    Returns (loss, per_token_loss).  Differentiable wrt hidden and w in all
    impls: "ref" (materializing oracle), "xla" (blockwise scan — fused
    memory behaviour on one device), "sharded" (SPMD-native, used by the
    production mesh), "pallas" (TPU kernel).
    """
    if impl == "ref":
        return cross_entropy_ref(hidden, w, labels, mask, softcap)
    if impl == "pallas":
        from repro.kernels.xent.kernel import fused_xent_pallas
        with jax.named_scope("fused_xent_pallas"):
            per_token = fused_xent_pallas(hidden, w, labels,
                                          softcap=softcap)
    elif impl == "sharded":
        per_token = _sharded_per_token(hidden, w, labels, softcap)
    else:
        m, l, corr = _blockwise_stats(hidden, w, labels, softcap, block)
        per_token = (jnp.log(l) + m) - corr
    if mask is None:
        mask = jnp.ones_like(per_token)
    mask = mask.astype(jnp.float32)
    loss = jnp.sum(per_token * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return loss, per_token
