"""Pallas TPU fused cross-entropy over huge vocabularies.

Never materializes the (T, V) logit matrix in HBM: the vocabulary is tiled
(grid dim v innermost); a VMEM scratch carries the online (max, sumexp,
correct-logit) statistics per token tile, exactly like flash attention's
row statistics.  Backward recomputes each logit tile from (h, W, lse) — a
remat-in-kernel scheme — and accumulates dH (grid t, v) and dW (grid v, t)
into resident VMEM tiles.

VMEM budget: tiles are (bt, D) for hidden and (D, bv) for the weight —
``pick_blocks`` chooses bt/bv so a grid step's windows and scratch fit
under the TPU's 16 MiB default scoped VMEM (the backward also holds fp32
dH and dW tiles, so it gets smaller blocks); supports gemma2's
final-logit softcap with the exact tanh chain rule.

The backward is a SINGLE grid sweep: each (bt, bv) logits tile is
recomputed exactly once and contributes to both dH and dW in the same
kernel invocation (3 matmuls per tile instead of the 4 a two-kernel
backward pays, and one H/W HBM sweep instead of two).  dW lives in a
resident VMEM tile accumulated over the innermost token axis.  dH has
two strategies (``xent_bwd(dh_strategy=...)``): on TPU the running sum
lives in HBM through an input/output-aliased buffer re-fetched on each
vocab revisit (zero extra footprint); under the interpreter — whose
pipeline does not thread output flushes back into aliased input reads —
dH is staged as per-vocab-tile partials and reduced outside the kernel
(test scale only).

Per-token operands (labels, loss, lse, g) are (Tp, 1) columns with
(bt, 1) blocks: 1-D (bt,) blocks are refused by the TPU compiler (XLA's
1-D tiling does not match Mosaic's), and a column is what the (bt, bv)
logits tile broadcasts against, so the kernels need no relayout.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.platform import pallas_interpret

NEG_INF = -1e30


def pick_blocks(D: int, itemsize: int = 4, *, backward: bool = False,
                vmem_budget: int = 14 * 2 ** 20):
    """Largest (bt, bv) whose per-step VMEM fits ``vmem_budget``: the
    double-buffered h (bt, D) and w (D, bv) windows of ``itemsize``
    bytes, plus for the backward the fp32 dH in/out windows and the dW
    output window and scratch.  The budget leaves room under the 16 MiB
    scoped limit for the (bt, bv) fp32 logits tiles."""
    for bt, bv in ((256, 512), (128, 256), (64, 128), (32, 128), (16, 128),
                   (8, 128)):
        need = 2 * (bt * D + D * bv) * itemsize
        if backward:
            need += (4 * bt * D + 3 * D * bv) * 4
        if need <= vmem_budget:
            return bt, bv
    return 8, 128


def clamp_block_t(bt: int, T: int, dtype=jnp.float32) -> int:
    """Clamp the token block toward T (rounded up to the dtype's sublane
    tile: 8 rows fp32, 16 rows bf16) so short sequences don't pad to a
    huge block — bt=256 with T=20 would otherwise pad 12x."""
    sub = {4: 8, 2: 16, 1: 32}.get(jnp.dtype(dtype).itemsize, 8)
    return max(sub, min(-(-bt // sub) * sub, -(-T // sub) * sub))


def _column(x, pad):
    """(T,) per-token vector -> zero-padded (T + pad, 1) kernel column."""
    return (jnp.pad(x, (0, pad)) if pad else x)[:, None]


def _logits_tile(h, w, labels, iv, bv, V, softcap):
    """Returns (capped logits, dchain, onehot, valid) for one (bt,bv) tile."""
    z = jax.lax.dot_general(h, w, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if softcap:
        s = jnp.tanh(z / softcap) * softcap
        dchain = 1.0 - jnp.square(s / softcap)
    else:
        s, dchain = z, None
    ids = iv * bv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    valid = ids < V
    onehot = (ids == labels).astype(jnp.float32)      # labels: (bt, 1)
    s = jnp.where(valid, s, NEG_INF)
    return s, dchain, onehot, valid


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _fwd_kernel(h_ref, w_ref, lab_ref, loss_ref, lse_ref,
                m_sc, l_sc, c_sc, *, V, softcap, nv):
    iv = pl.program_id(1)
    bv = w_ref.shape[1]

    @pl.when(iv == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        c_sc[...] = jnp.zeros_like(c_sc)

    h = h_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    labels = lab_ref[...]
    s, _, onehot, _ = _logits_tile(h, w, labels, iv, bv, V, softcap)

    m_prev = m_sc[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    l_sc[...] = l_sc[...] * jnp.exp(m_prev - m_new) + jnp.sum(
        jnp.exp(s - m_new), axis=1, keepdims=True)
    c_sc[...] += jnp.sum(jnp.where(onehot > 0, s, 0.0), axis=1, keepdims=True)
    m_sc[...] = m_new

    @pl.when(iv == nv - 1)
    def _final():
        lse = m_sc[...] + jnp.log(jnp.maximum(l_sc[...], 1e-30))
        loss_ref[...] = lse - c_sc[...]
        lse_ref[...] = lse


def xent_fwd(h, w, labels, *, softcap=0.0, block_t=None, block_v=None,
             interpret=None):
    T, D = h.shape
    V = w.shape[1]
    bt0, bv0 = pick_blocks(D, h.dtype.itemsize)
    bt = block_t or bt0
    bv = block_v or bv0
    bt = clamp_block_t(bt, T, h.dtype)
    padT = (-T) % bt
    padV = (-V) % bv
    hp = jnp.pad(h, ((0, padT), (0, 0))) if padT else h
    labp = _column(labels, padT)
    wp = jnp.pad(w, ((0, 0), (0, padV))) if padV else w
    Tp, Vp = T + padT, V + padV
    nt, nv = Tp // bt, Vp // bv
    if interpret is None:
        interpret = pallas_interpret()

    kern = functools.partial(_fwd_kernel, V=V, softcap=softcap, nv=nv)
    loss, lse = pl.pallas_call(
        kern,
        grid=(nt, nv),
        in_specs=[
            pl.BlockSpec((bt, D), lambda it, iv: (it, 0)),
            pl.BlockSpec((D, bv), lambda it, iv: (0, iv)),
            pl.BlockSpec((bt, 1), lambda it, iv: (it, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bt, 1), lambda it, iv: (it, 0)),
            pl.BlockSpec((bt, 1), lambda it, iv: (it, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Tp, 1), jnp.float32),
            jax.ShapeDtypeStruct((Tp, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bt, 1), jnp.float32),
            pltpu.VMEM((bt, 1), jnp.float32),
            pltpu.VMEM((bt, 1), jnp.float32),
        ],
        interpret=interpret,
    )(hp, wp, labp)
    return loss[:T, 0], lse[:T, 0]


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------


def _bwd_dlog(h_ref, w_ref, lab_ref, lse_ref, g_ref, iv, *, V, softcap):
    """Shared tile work: recompute the (bt, bv) logits tile ONCE and form
    (h, w, dlog) — both gradient contractions read from it."""
    bv = w_ref.shape[1]
    h = h_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    s, dchain, onehot, valid = _logits_tile(h, w, lab_ref[...], iv, bv, V,
                                            softcap)
    p = jnp.exp(s - lse_ref[...])
    p = jnp.where(valid, p, 0.0)
    dlog = (p - onehot) * g_ref[...]
    if dchain is not None:
        dlog = dlog * dchain
    return h, w, dlog


def _dh_part(dlog, w):
    return jax.lax.dot_general(dlog, w, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _accum_dw(dw_sc, dw_ref, h, dlog, it, nt):
    dw_sc[...] += jax.lax.dot_general(h, dlog, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)

    @pl.when(it == nt - 1)
    def _final_dw():
        dw_ref[...] = dw_sc[...].astype(dw_ref.dtype)


def _bwd_kernel_partials(h_ref, w_ref, lab_ref, lse_ref, g_ref,
                         dh_ref, dw_ref, dw_sc, *, V, softcap, nt):
    """Interpret-mode variant: dH emitted as per-vocab-tile partials —
    block (iv, it) is written exactly once (no revisit semantics needed)
    and reduced over nv by the caller.  The (nv, Tp, D) staging array is
    D/bv times the logits matrix, acceptable only at interpret/test
    scale; the TPU variant below accumulates in-place instead."""
    iv, it = pl.program_id(0), pl.program_id(1)

    @pl.when(it == 0)
    def _init_dw():
        dw_sc[...] = jnp.zeros_like(dw_sc)

    h, w, dlog = _bwd_dlog(h_ref, w_ref, lab_ref, lse_ref, g_ref, iv,
                           V=V, softcap=softcap)
    dh_ref[0] = _dh_part(dlog, w)
    _accum_dw(dw_sc, dw_ref, h, dlog, it, nt)


def _bwd_kernel_alias(h_ref, w_ref, lab_ref, lse_ref, g_ref, dhin_ref,
                      dh_ref, dw_ref, *scratch, V, softcap, nt, nv):
    """TPU variant: dH accumulates through the HBM buffer aliased between
    ``dhin`` and the dH output — block (it) is flushed every step (the
    block index changes each step since it is innermost) and re-fetched
    nt steps later on the next vocab revisit, so the running sum lives in
    HBM at no extra footprint.  nt == 1 would make the revisits
    consecutive (the input window is not re-fetched when its index does
    not change), so that case accumulates in VMEM scratch over the whole
    grid instead."""
    iv, it = pl.program_id(0), pl.program_id(1)
    dw_sc = scratch[-1]
    dh_sc = scratch[0] if nt == 1 else None  # allocated only for nt == 1

    @pl.when(it == 0)
    def _init_dw():
        dw_sc[...] = jnp.zeros_like(dw_sc)

    if nt == 1:
        @pl.when(iv == 0)
        def _init_dh():
            dh_sc[...] = jnp.zeros_like(dh_sc)

    h, w, dlog = _bwd_dlog(h_ref, w_ref, lab_ref, lse_ref, g_ref, iv,
                           V=V, softcap=softcap)
    if nt == 1:
        dh_sc[...] += _dh_part(dlog, w)

        @pl.when(iv == nv - 1)
        def _final_dh():
            dh_ref[...] = dh_sc[...].astype(dh_ref.dtype)
    else:
        dh_ref[...] = dhin_ref[...] + _dh_part(dlog, w)
    _accum_dw(dw_sc, dw_ref, h, dlog, it, nt)


def xent_bwd(h, w, labels, lse, g, *, softcap=0.0, block_t=None,
             block_v=None, interpret=None, dh_strategy=None):
    """Fused single-sweep backward.  ``dh_strategy``: "partials" (any
    backend; stages (nv, Tp, D) in HBM — test scale only) or "alias"
    (in-place HBM accumulation; relies on TPU window revisit semantics,
    numerically wrong under the interpreter).  Default: partials when
    interpreting, alias on TPU."""
    T, D = h.shape
    V = w.shape[1]
    bt0, bv0 = pick_blocks(D, h.dtype.itemsize, backward=True)
    bt = block_t or bt0
    bv = block_v or bv0
    bt = clamp_block_t(bt, T, h.dtype)
    padT = (-T) % bt
    padV = (-V) % bv
    hp = jnp.pad(h, ((0, padT), (0, 0))) if padT else h
    labp, lsep, gp = (_column(a, padT) for a in (labels, lse, g))
    wp = jnp.pad(w, ((0, 0), (0, padV))) if padV else w
    Tp, Vp = T + padT, V + padV
    nt, nv = Tp // bt, Vp // bv
    if interpret is None:
        interpret = pallas_interpret()
    if dh_strategy is None:
        dh_strategy = "partials" if interpret else "alias"

    in_specs = [
        pl.BlockSpec((bt, D), lambda iv, it: (it, 0)),
        pl.BlockSpec((D, bv), lambda iv, it: (0, iv)),
        pl.BlockSpec((bt, 1), lambda iv, it: (it, 0)),
        pl.BlockSpec((bt, 1), lambda iv, it: (it, 0)),
        pl.BlockSpec((bt, 1), lambda iv, it: (it, 0)),
    ]
    dw_spec = pl.BlockSpec((D, bv), lambda iv, it: (0, iv))
    dw_shape = jax.ShapeDtypeStruct((D, Vp), jnp.float32)
    dh_block = pl.BlockSpec((bt, D), lambda iv, it: (it, 0))

    if dh_strategy == "partials":
        dh_parts, dw = pl.pallas_call(
            functools.partial(_bwd_kernel_partials, V=V, softcap=softcap,
                              nt=nt),
            grid=(nv, nt),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, bt, D), lambda iv, it: (iv, it, 0)),
                dw_spec,
            ],
            out_shape=[
                jax.ShapeDtypeStruct((nv, Tp, D), jnp.float32),
                dw_shape,
            ],
            scratch_shapes=[pltpu.VMEM((D, bv), jnp.float32)],
            interpret=interpret,
        )(hp, wp, labp, lsep, gp)
        dh = jnp.sum(dh_parts, axis=0)
    else:
        dh, dw = pl.pallas_call(
            functools.partial(_bwd_kernel_alias, V=V, softcap=softcap,
                              nt=nt, nv=nv),
            grid=(nv, nt),
            in_specs=in_specs + [dh_block],
            out_specs=[dh_block, dw_spec],
            out_shape=[
                jax.ShapeDtypeStruct((Tp, D), jnp.float32),
                dw_shape,
            ],
            scratch_shapes=(
                ([pltpu.VMEM((bt, D), jnp.float32)] if nt == 1 else [])
                + [pltpu.VMEM((D, bv), jnp.float32)]),
            input_output_aliases={5: 0},
            interpret=interpret,
        )(hp, wp, labp, lsep, gp, jnp.zeros((Tp, D), jnp.float32))
    return dh[:T], dw[:, :V]


# ---------------------------------------------------------------------------
# custom-vjp public entry
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def fused_xent_pallas(h, w, labels, softcap=0.0):
    loss, _ = xent_fwd(h, w, labels, softcap=softcap)
    return loss


def _f(h, w, labels, softcap):
    loss, lse = xent_fwd(h, w, labels, softcap=softcap)
    return loss, (h, w, labels, lse)


def _b(softcap, res, g):
    h, w, labels, lse = res
    dh, dw = xent_bwd(h, w, labels, lse, g, softcap=softcap)
    return dh.astype(h.dtype), dw.astype(w.dtype), None


fused_xent_pallas.defvjp(lambda h, w, l, softcap=0.0: _f(h, w, l, softcap), _b)
