"""jit'd public wrapper for the Pallas flash attention with a custom VJP.

Public layout matches the model code: q (B, S, Hkv, G, hd); k, v
(B, Skv, Hkv, hd).  Handles padding to block multiples and the layout
reshape to the kernel's (BH, S, hd) / (BKV, Skv, hd) views.

Output dtype matches the input dtype (fp32 accumulation stays internal to
the kernels) — bf16 models no longer get a silent fp32 upcast after every
attention layer.

The VJP residuals carry the *padded kernel-layout* q/k/v/o/lse produced by
the forward, so the backward never re-transposes or re-pads them; ``do``
is cast to fp32 and laid out once, feeding both the delta reduction and
the kernel.  ``bwd_strategy`` selects the backward kernel schedule:

* ``"fused"`` (default) — :func:`~.kernel.flash_bwd_fused`, a single
  pallas_call recomputing each P tile once for dQ/dK/dV;
* ``"split"`` — the legacy two-sweep :func:`~.kernel.flash_bwd_dq` +
  :func:`~.kernel.flash_bwd_dkv` pair (kept for A/B and TPU validation).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import kernel as K
from repro.observability.profiling import annotate


def _pad_to(x, axis, mult):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _sublane(dtype) -> int:
    """Minimum TPU tile rows for a dtype: 8 for 4-byte, 16 for 2-byte,
    32 for 1-byte element types."""
    return {4: 8, 2: 16, 1: 32}.get(jnp.dtype(dtype).itemsize, 8)


def _block_sizes(S, Skv, block_q, block_k, dtype=jnp.float32):
    """Clamp blocks toward the (possibly short) sequence, rounded up to the
    dtype's sublane tile (8 rows fp32, 16 rows bf16) so odd shapes (e.g.
    S=20) never produce a misaligned block — ``_pad_to`` absorbs the
    remainder."""
    sub = _sublane(dtype)
    bq = min(block_q, max(sub, S))
    bk = min(block_k, max(sub, Skv))
    return -(-bq // sub) * sub, -(-bk // sub) * sub


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def flash_attention(q, k, v, causal=True, window=0, softcap=0.0, scale=1.0,
                    block_q=128, block_k=128, bwd_strategy="fused"):
    """Returns (B, S, Hkv, G, hd) attention output in the input dtype."""
    if bwd_strategy not in ("fused", "split"):   # fail at trace, not in vjp
        raise ValueError(f"unknown bwd_strategy: {bwd_strategy!r}")
    o, _ = _fwd(q, k, v, causal, window, softcap, scale, block_q, block_k)
    return o


def _fwd(q, k, v, causal, window, softcap, scale, block_q, block_k):
    """Runs the forward kernel; returns the public-layout output plus the
    padded kernel-layout residuals the backward consumes as-is."""
    B, S, Hkv, G, hd = q.shape
    Skv = k.shape[1]
    qk = jnp.transpose(q, (0, 2, 3, 1, 4)).reshape(B * Hkv * G, S, hd)
    kk = jnp.transpose(k, (0, 2, 1, 3)).reshape(B * Hkv, Skv, hd)
    vk = jnp.transpose(v, (0, 2, 1, 3)).reshape(B * Hkv, Skv, hd)
    bq, bk = _block_sizes(S, Skv, block_q, block_k, q.dtype)
    qp = _pad_to(qk, 1, bq)
    kp = _pad_to(kk, 1, bk)
    vp = _pad_to(vk, 1, bk)
    with annotate("flash_fwd"):      # host dispatch/trace time (--profile)
        op, lsep = K.flash_fwd(qp, kp, vp, group=G, causal=causal,
                               window=window, softcap=softcap, scale=scale,
                               kv_len=Skv, block_q=bq, block_k=bk)
    o = (op[:, :S].reshape(B, Hkv, G, S, hd).transpose(0, 3, 1, 2, 4)
         .astype(q.dtype))
    # zero-size proto: carries the static Skv (residual tracers expose
    # static shapes) without retaining the unpadded k/v
    kv_proto = jnp.zeros((Skv, 0), k.dtype)
    return o, (qp, kp, vp, op, lsep, kv_proto)


def _vjp_fwd(q, k, v, causal, window, softcap, scale, block_q, block_k,
             bwd_strategy):
    return _fwd(q, k, v, causal, window, softcap, scale, block_q, block_k)


def _vjp_bwd(causal, window, softcap, scale, block_q, block_k, bwd_strategy,
             res, do):
    qp, kp, vp, op, lsep, kv_proto = res
    B, S, Hkv, G, hd = do.shape
    Skv = kv_proto.shape[0]
    bq, bk = _block_sizes(S, Skv, block_q, block_k, qp.dtype)

    # one fp32 cast + layout pass over do; padded rows are zero, so delta
    # (and every gradient contribution) vanishes there
    dok = _pad_to(
        jnp.transpose(do, (0, 2, 3, 1, 4))
        .reshape(B * Hkv * G, S, hd).astype(jnp.float32), 1, bq)
    delta = jnp.sum(dok * op, axis=-1)[:, None, :]       # (BH, 1, Sq_padded)

    common = dict(group=G, causal=causal, window=window, softcap=softcap,
                  scale=scale, kv_len=Skv, block_q=bq, block_k=bk)
    bwds = {"fused": K.flash_bwd_fused, "split": K.flash_bwd_dq_dkv}
    if bwd_strategy not in bwds:
        raise ValueError(f"unknown bwd_strategy: {bwd_strategy!r}")
    with annotate(f"flash_bwd_{bwd_strategy}"):
        dq, dk, dv = bwds[bwd_strategy](qp, kp, vp, dok, lsep, delta,
                                        **common)

    dq = dq[:, :S].reshape(B, Hkv, G, S, hd).transpose(0, 3, 1, 2, 4)
    dk = dk[:, :Skv].reshape(B, Hkv, Skv, hd).transpose(0, 2, 1, 3)
    dv = dv[:, :Skv].reshape(B, Hkv, Skv, hd).transpose(0, 2, 1, 3)
    return (dq.astype(qp.dtype), dk.astype(kp.dtype), dv.astype(vp.dtype))


flash_attention.defvjp(_vjp_fwd, _vjp_bwd)
