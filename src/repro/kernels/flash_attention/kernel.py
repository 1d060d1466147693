"""Pallas TPU flash attention (FA-2 schedule), forward + backward.

TPU adaptation notes (vs the CUDA algorithm):
* tiles are MXU-aligned (block_q x block_k multiples of 128; head_dim is
  kept whole per tile — 64..256 fits VMEM comfortably);
* the kv-block loop is the *innermost grid dimension* — TPU grids execute
  sequentially per core, so the (m, l, acc) running statistics live in VMEM
  scratch that persists across grid steps (the Pallas-TPU idiom replacing
  FA's per-CTA shared-memory loop);
* GQA never materializes repeated K/V: the kv BlockSpec index_map folds the
  q-head -> kv-head mapping (bh // group) so each kv tile is fetched once
  per group from HBM;
* causal/sliding-window masks are computed from block-relative iota and
  applied in-register; softcap (gemma2) is fused into the score tile.

The backward (:func:`flash_bwd_fused`) is a SINGLE grid sweep: each
(q-tile, kv-tile) probability tile is recomputed exactly once and feeds
all three gradients in the same kernel invocation — the two-sweep design
(:func:`flash_bwd_dq` + :func:`flash_bwd_dkv`, kept for A/B behind the
ops-level ``bwd_strategy`` knob) recomputes every P tile twice and pays a
second full Q/K/V/dO HBM sweep.  The fused grid is (BKV, nk, G, nq) — the
dK/dV tile stays resident in VMEM scratch while all group members and
q-blocks accumulate into it; the dQ tile is revisited ``G * nq`` grid
steps apart and accumulates via one of two strategies (``dq_strategy``):
"alias" threads the running sum through an input/output-aliased HBM
buffer (TPU; zero extra footprint — mirrors the xent backward's
``dh_strategy="alias"``), "partials" stages per-kv-tile partials reduced
outside the kernel (interpreter-safe; nk x the dQ footprint, test scale
only).  ``G * nq == 1`` would make the aliased window's index constant
across revisits (no flush/refetch), so that case accumulates in VMEM
scratch instead.

Layouts:  q, o: (BH, S, hd) with BH = B * Hkv * G (kv-major: bh // G is the
kv head); k, v: (BKV, Skv, hd) with BKV = B * Hkv; the per-row statistics
lse and delta: (BH, 1, S), the sequence on lanes, so their (1, 1, bq)
blocks meet the TPU's (8, 128) tiling (a (1, bq) block over (BH, S) is
refused by the compiler).  Kernels transpose a (1, bq) row to the (bq, 1)
column the score tile broadcasts against.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.platform import pallas_interpret

NEG_INF = -1e30


def _mask(iq, ik, bq, bk, *, causal, window, kv_len):
    q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    m = k_pos < kv_len
    if causal:
        m &= q_pos >= k_pos
    if window:
        m &= (q_pos - k_pos) < window
    return m


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_sc, l_sc, acc_sc, *,
                causal, window, softcap, scale, kv_len, nk):
    iq, ik = pl.program_id(1), pl.program_id(2)
    bq, hd = q_ref.shape[1], q_ref.shape[2]
    bk = k_ref.shape[1]

    @pl.when(ik == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    q = q_ref[0].astype(jnp.float32) * scale          # (bq, hd)
    k = k_ref[0].astype(jnp.float32)                  # (bk, hd)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    mask = _mask(iq, ik, bq, bk, causal=causal, window=window, kv_len=kv_len)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_sc[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_sc[...] = l_sc[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    v = v_ref[0].astype(jnp.float32)
    acc_sc[...] = acc_sc[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_sc[...] = m_new

    @pl.when(ik == nk - 1)
    def _final():
        l = jnp.maximum(l_sc[...], 1e-30)
        o_ref[0] = (acc_sc[...] / l).astype(o_ref.dtype)
        lse_ref[0] = (m_sc[...] + jnp.log(l)).T


def flash_fwd(q, k, v, *, group: int, causal: bool, window: int,
              softcap: float, scale: float, kv_len: int,
              block_q: int = 128, block_k: int = 128, interpret=None):
    """q: (BH, Sq, hd); k, v: (BKV, Skv, hd).  Sq, Skv padded to blocks.
    Returns o (BH, Sq, hd) fp32 and lse (BH, 1, Sq)."""
    BH, Sq, hd = q.shape
    BKV, Skv = k.shape[0], k.shape[1]
    bq, bk = min(block_q, Sq), min(block_k, Skv)
    assert Sq % bq == 0 and Skv % bk == 0
    nq, nk = Sq // bq, Skv // bk
    if interpret is None:
        interpret = pallas_interpret()

    kern = functools.partial(_fwd_kernel, causal=causal, window=window,
                             softcap=softcap, scale=scale, kv_len=kv_len,
                             nk=nk)
    o, lse = pl.pallas_call(
        kern,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, bk, hd),
                         lambda bh, iq, ik, g=group: (bh // g, ik, 0)),
            pl.BlockSpec((1, bk, hd),
                         lambda bh, iq, ik, g=group: (bh // g, ik, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, hd), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, 1, bq), lambda bh, iq, ik: (bh, 0, iq)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Sq, hd), jnp.float32),
            jax.ShapeDtypeStruct((BH, 1, Sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return o, lse


# ---------------------------------------------------------------------------
# Backward: dq  (grid: bh, iq, ik — kv innermost, dq accumulates in scratch)
# ---------------------------------------------------------------------------


def _recompute_p(q, k, iq, ik, bq, bk, *, causal, window, softcap, scale,
                 kv_len, lse):
    """Recompute the probability tile and the softcap chain factor."""
    s_raw = jax.lax.dot_general(q * scale, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
    if softcap:
        s = jnp.tanh(s_raw / softcap) * softcap
        dchain = 1.0 - jnp.square(s / softcap)     # d softcap / d s_raw
    else:
        s = s_raw
        dchain = jnp.ones_like(s)
    mask = _mask(iq, ik, bq, bk, causal=causal, window=window, kv_len=kv_len)
    s = jnp.where(mask, s, NEG_INF)
    p = jnp.exp(s - lse)
    p = jnp.where(mask, p, 0.0)
    return p, dchain


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_sc, *, causal, window, softcap, scale, kv_len, nk):
    iq, ik = pl.program_id(1), pl.program_id(2)
    bq, hd = q_ref.shape[1], q_ref.shape[2]
    bk = k_ref.shape[1]

    @pl.when(ik == 0)
    def _init():
        dq_sc[...] = jnp.zeros_like(dq_sc)

    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0].T                                # (bq, 1)
    delta = delta_ref[0].T

    p, dchain = _recompute_p(q, k, iq, ik, bq, bk, causal=causal,
                             window=window, softcap=softcap, scale=scale,
                             kv_len=kv_len, lse=lse)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta) * dchain * scale
    dq_sc[...] += jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _final():
        dq_ref[0] = dq_sc[...].astype(dq_ref.dtype)


def flash_bwd_dq(q, k, v, do, lse, delta, *, group, causal, window, softcap,
                 scale, kv_len, block_q=128, block_k=128, interpret=None):
    BH, Sq, hd = q.shape
    Skv = k.shape[1]
    bq, bk = min(block_q, Sq), min(block_k, Skv)
    nq, nk = Sq // bq, Skv // bk
    if interpret is None:
        interpret = pallas_interpret()
    kern = functools.partial(_dq_kernel, causal=causal, window=window,
                             softcap=softcap, scale=scale, kv_len=kv_len,
                             nk=nk)
    return pl.pallas_call(
        kern,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, bk, hd), lambda bh, iq, ik, g=group: (bh // g, ik, 0)),
            pl.BlockSpec((1, bk, hd), lambda bh, iq, ik, g=group: (bh // g, ik, 0)),
            pl.BlockSpec((1, bq, hd), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, 1, bq), lambda bh, iq, ik: (bh, 0, iq)),
            pl.BlockSpec((1, 1, bq), lambda bh, iq, ik: (bh, 0, iq)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, hd), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bq, hd), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse, delta)


# ---------------------------------------------------------------------------
# Backward: dk, dv  (grid: bkv, ik, g, iq — dk/dv tiles stay resident while
# all group members and q blocks accumulate into them)
# ---------------------------------------------------------------------------


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_sc, dv_sc, *, causal, window, softcap,
                scale, kv_len, group, nq):
    _bwd_kv_tile(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                 dk_ref, dv_ref, dk_sc, dv_sc, causal=causal, window=window,
                 softcap=softcap, scale=scale, kv_len=kv_len, group=group,
                 nq=nq, with_dq=False)


def flash_bwd_dq_dkv(q, k, v, do, lse, delta, *, group, causal, window,
                     softcap, scale, kv_len, block_q=128, block_k=128,
                     interpret=None):
    """Legacy two-sweep backward: two pallas_calls, each recomputing P."""
    common = dict(group=group, causal=causal, window=window, softcap=softcap,
                  scale=scale, kv_len=kv_len, block_q=block_q,
                  block_k=block_k, interpret=interpret)
    dq = flash_bwd_dq(q, k, v, do, lse, delta, **common)
    dk, dv = flash_bwd_dkv(q, k, v, do, lse, delta, **common)
    return dq, dk, dv


def flash_bwd_dkv(q, k, v, do, lse, delta, *, group, causal, window, softcap,
                  scale, kv_len, block_q=128, block_k=128, interpret=None):
    BH, Sq, hd = q.shape
    BKV, Skv = k.shape[0], k.shape[1]
    bq, bk = min(block_q, Sq), min(block_k, Skv)
    nq, nk = Sq // bq, Skv // bk
    if interpret is None:
        interpret = pallas_interpret()
    kern = functools.partial(_dkv_kernel, causal=causal, window=window,
                             softcap=softcap, scale=scale, kv_len=kv_len,
                             group=group, nq=nq)
    g = group
    return pl.pallas_call(
        kern,
        grid=(BKV, nk, g, nq),
        in_specs=[
            pl.BlockSpec((1, bq, hd),
                         lambda bkv, ik, gg, iq, g=g: (bkv * g + gg, iq, 0)),
            pl.BlockSpec((1, bk, hd), lambda bkv, ik, gg, iq: (bkv, ik, 0)),
            pl.BlockSpec((1, bk, hd), lambda bkv, ik, gg, iq: (bkv, ik, 0)),
            pl.BlockSpec((1, bq, hd),
                         lambda bkv, ik, gg, iq, g=g: (bkv * g + gg, iq, 0)),
            pl.BlockSpec((1, 1, bq),
                         lambda bkv, ik, gg, iq, g=g: (bkv * g + gg, 0, iq)),
            pl.BlockSpec((1, 1, bq),
                         lambda bkv, ik, gg, iq, g=g: (bkv * g + gg, 0, iq)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, hd), lambda bkv, ik, gg, iq: (bkv, ik, 0)),
            pl.BlockSpec((1, bk, hd), lambda bkv, ik, gg, iq: (bkv, ik, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BKV, Skv, hd), jnp.float32),
            jax.ShapeDtypeStruct((BKV, Skv, hd), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, hd), jnp.float32),
            pltpu.VMEM((bk, hd), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, do, lse, delta)


# ---------------------------------------------------------------------------
# Backward: fused dq+dk+dv  (grid: bkv, ik, g, iq — one P recompute per
# (q-tile, kv-tile) feeds all three gradients; dk/dv tiles stay resident in
# VMEM scratch, dq accumulates across kv revisits per dq_strategy)
# ---------------------------------------------------------------------------


def _bwd_kv_tile(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                 dk_ref, dv_ref, dk_sc, dv_sc, *, causal, window, softcap,
                 scale, kv_len, group, nq, with_dq=True):
    """Shared (bkv, ik, g, iq)-grid tile work — the legacy dkv sweep and
    both fused dq strategies run this body: recompute the P tile ONCE,
    accumulate dK/dV into the resident VMEM scratch (flushed at the last
    (g, iq) visit of this kv tile), and — when ``with_dq`` — return the
    tile's dQ contribution."""
    ik, gg, iq = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    bq, hd = q_ref.shape[1], q_ref.shape[2]
    bk = k_ref.shape[1]

    @pl.when(jnp.logical_and(gg == 0, iq == 0))
    def _init_kv():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0].T                                # (bq, 1)
    delta = delta_ref[0].T

    p, dchain = _recompute_p(q, k, iq, ik, bq, bk, causal=causal,
                             window=window, softcap=softcap, scale=scale,
                             kv_len=kv_len, lse=lse)
    # dv += p^T @ do
    dv_sc[...] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta) * dchain * scale
    # dk += ds^T @ q
    dk_sc[...] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_and(gg == group - 1, iq == nq - 1))
    def _final_kv():
        dk_ref[0] = dk_sc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[...].astype(dv_ref.dtype)

    if not with_dq:
        return None
    # dq contribution of this kv tile: ds @ k
    return jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _fused_bwd_kernel_partials(q_ref, k_ref, v_ref, do_ref, lse_ref,
                               delta_ref, dq_ref, dk_ref, dv_ref, dk_sc,
                               dv_sc, *, causal, window, softcap, scale,
                               kv_len, group, nq):
    """Interpreter-safe variant: dQ emitted as per-kv-tile partials —
    block (ik, bh, iq) is written exactly once (no revisit semantics
    needed) and reduced over nk by the caller.  The (nk, BH, Sq, hd)
    staging array is acceptable only at interpret/test scale; the TPU
    variant below accumulates in-place instead."""
    dq_part = _bwd_kv_tile(q_ref, k_ref, v_ref, do_ref, lse_ref,
                              delta_ref, dk_ref, dv_ref, dk_sc, dv_sc,
                              causal=causal, window=window, softcap=softcap,
                              scale=scale, kv_len=kv_len, group=group, nq=nq)
    dq_ref[0, 0] = dq_part


def _fused_bwd_kernel_alias(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                            dqin_ref, dq_ref, dk_ref, dv_ref, *scratch,
                            causal, window, softcap, scale, kv_len, group,
                            nq, nk):
    """TPU variant: dQ accumulates through the HBM buffer aliased between
    ``dqin`` and the dQ output — block (bh, iq) is flushed every step (the
    block index changes each step since iq is innermost) and re-fetched
    ``group * nq`` steps later on the next kv revisit, so the running sum
    lives in HBM at no extra footprint.  group * nq == 1 would make the
    window index constant across revisits (the input window is not
    re-fetched when its index does not change), so that case accumulates
    in VMEM scratch over the kv sweep instead."""
    ik = pl.program_id(1)
    dk_sc, dv_sc = scratch[-2], scratch[-1]
    dq_sc = scratch[0] if group * nq == 1 else None

    if dq_sc is not None:
        @pl.when(ik == 0)
        def _init_dq():
            dq_sc[...] = jnp.zeros_like(dq_sc)

    dq_part = _bwd_kv_tile(q_ref, k_ref, v_ref, do_ref, lse_ref,
                              delta_ref, dk_ref, dv_ref, dk_sc, dv_sc,
                              causal=causal, window=window, softcap=softcap,
                              scale=scale, kv_len=kv_len, group=group, nq=nq)
    if dq_sc is not None:
        dq_sc[...] += dq_part

        @pl.when(ik == nk - 1)
        def _final_dq():
            dq_ref[0] = dq_sc[...].astype(dq_ref.dtype)
    else:
        dq_ref[0] = dqin_ref[0] + dq_part


def flash_bwd_fused(q, k, v, do, lse, delta, *, group, causal, window,
                    softcap, scale, kv_len, block_q=128, block_k=128,
                    interpret=None, dq_strategy=None):
    """Single-pallas_call backward: one P recompute per (q-tile, kv-tile)
    feeds dQ, dK and dV (5 matmuls per tile — P, dP, dV, dK, dQ — instead
    of the 7 the two-sweep backward pays with P and dP each computed
    twice, and one Q/K/V/dO HBM sweep instead of two).

    ``dq_strategy``: "partials" (any backend; stages (nk, BH, Sq, hd) in
    HBM — test scale only) or "alias" (in-place HBM accumulation; relies
    on TPU window revisit semantics, numerically wrong under the
    interpreter when group * nq > 1).  Default: partials when
    interpreting, alias on TPU.
    """
    BH, Sq, hd = q.shape
    BKV, Skv = k.shape[0], k.shape[1]
    bq, bk = min(block_q, Sq), min(block_k, Skv)
    assert Sq % bq == 0 and Skv % bk == 0
    nq, nk = Sq // bq, Skv // bk
    if interpret is None:
        interpret = pallas_interpret()
    if dq_strategy is None:
        dq_strategy = "partials" if interpret else "alias"
    if dq_strategy not in ("partials", "alias"):
        raise ValueError(f"unknown dq_strategy: {dq_strategy!r}")

    g = group
    in_specs = [
        pl.BlockSpec((1, bq, hd),
                     lambda bkv, ik, gg, iq, g=g: (bkv * g + gg, iq, 0)),
        pl.BlockSpec((1, bk, hd), lambda bkv, ik, gg, iq: (bkv, ik, 0)),
        pl.BlockSpec((1, bk, hd), lambda bkv, ik, gg, iq: (bkv, ik, 0)),
        pl.BlockSpec((1, bq, hd),
                     lambda bkv, ik, gg, iq, g=g: (bkv * g + gg, iq, 0)),
        pl.BlockSpec((1, 1, bq),
                     lambda bkv, ik, gg, iq, g=g: (bkv * g + gg, 0, iq)),
        pl.BlockSpec((1, 1, bq),
                     lambda bkv, ik, gg, iq, g=g: (bkv * g + gg, 0, iq)),
    ]
    dq_block = pl.BlockSpec((1, bq, hd),
                            lambda bkv, ik, gg, iq, g=g: (bkv * g + gg, iq, 0))
    dkv_specs = [
        pl.BlockSpec((1, bk, hd), lambda bkv, ik, gg, iq: (bkv, ik, 0)),
        pl.BlockSpec((1, bk, hd), lambda bkv, ik, gg, iq: (bkv, ik, 0)),
    ]
    dkv_shapes = [
        jax.ShapeDtypeStruct((BKV, Skv, hd), jnp.float32),
        jax.ShapeDtypeStruct((BKV, Skv, hd), jnp.float32),
    ]
    kv_scratch = [
        pltpu.VMEM((bk, hd), jnp.float32),
        pltpu.VMEM((bk, hd), jnp.float32),
    ]
    common = dict(causal=causal, window=window, softcap=softcap, scale=scale,
                  kv_len=kv_len, group=group, nq=nq)

    if dq_strategy == "partials":
        dq_parts, dk, dv = pl.pallas_call(
            functools.partial(_fused_bwd_kernel_partials, **common),
            grid=(BKV, nk, g, nq),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, 1, bq, hd),
                             lambda bkv, ik, gg, iq, g=g:
                             (ik, bkv * g + gg, iq, 0)),
            ] + dkv_specs,
            out_shape=[
                jax.ShapeDtypeStruct((nk, BH, Sq, hd), jnp.float32),
            ] + dkv_shapes,
            scratch_shapes=kv_scratch,
            interpret=interpret,
        )(q, k, v, do, lse, delta)
        dq = jnp.sum(dq_parts, axis=0)
    else:
        dq, dk, dv = pl.pallas_call(
            functools.partial(_fused_bwd_kernel_alias, **common, nk=nk),
            grid=(BKV, nk, g, nq),
            in_specs=in_specs + [dq_block],
            out_specs=[dq_block] + dkv_specs,
            out_shape=[jax.ShapeDtypeStruct((BH, Sq, hd), jnp.float32)]
            + dkv_shapes,
            scratch_shapes=(
                ([pltpu.VMEM((bq, hd), jnp.float32)] if g * nq == 1 else [])
                + kv_scratch),
            input_output_aliases={6: 0},
            interpret=interpret,
        )(q, k, v, do, lse, delta, jnp.zeros((BH, Sq, hd), jnp.float32))
    return dq, dk, dv
