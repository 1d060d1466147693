"""Pallas kernel validation: shape/dtype sweeps against the pure-jnp
oracles, forward AND backward, in interpret mode (CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.ssd_chunk.ops import ssd_intra
from repro.kernels.ssd_chunk.ref import ssd_intra_ref
from repro.kernels.xent import ops as xent_ops
from repro.kernels.xent.kernel import fused_xent_pallas
from repro.kernels.xent.ref import cross_entropy_ref

rng = np.random.default_rng(0)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

FA_CASES = [
    # B, S, Skv, Hkv, G, hd, causal, window, softcap, dtype
    (2, 32, 32, 2, 2, 16, True, 0, 0.0, jnp.float32),
    (1, 48, 48, 2, 1, 32, True, 0, 0.0, jnp.float32),    # MHA
    (2, 32, 32, 1, 4, 16, True, 16, 0.0, jnp.float32),   # MQA + window
    (2, 32, 32, 2, 2, 16, True, 0, 30.0, jnp.float32),   # softcap
    (1, 40, 40, 2, 2, 16, True, 8, 50.0, jnp.float32),   # padding + both
    (2, 32, 32, 2, 2, 16, False, 0, 0.0, jnp.float32),   # bidirectional
    (2, 32, 32, 2, 2, 16, True, 0, 0.0, jnp.bfloat16),   # low precision
    (2, 20, 20, 2, 2, 16, True, 8, 30.0, jnp.float32),   # odd S + both
    (1, 24, 40, 2, 2, 16, True, 12, 25.0, jnp.float32),  # Skv != S + both
    (1, 40, 24, 2, 1, 16, True, 0, 40.0, jnp.float32),   # Skv < S + softcap
]


def _fa_inputs(case):
    B, S, Skv, Hkv, G, hd, causal, window, softcap, dtype = case
    q = jnp.asarray(rng.normal(0, 1, (B, S, Hkv, G, hd)), dtype)
    k = jnp.asarray(rng.normal(0, 1, (B, Skv, Hkv, hd)), dtype)
    v = jnp.asarray(rng.normal(0, 1, (B, Skv, Hkv, hd)), dtype)
    return q, k, v, 1.0 / np.sqrt(hd)


@pytest.mark.parametrize("bwd_strategy", ["fused", "split"])
@pytest.mark.parametrize("case", FA_CASES)
def test_flash_attention_fwd_bwd(case, bwd_strategy):
    _, _, _, _, _, _, causal, window, softcap, dtype = case
    q, k, v, scale = _fa_inputs(case)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5

    if bwd_strategy == "fused":   # forward is strategy-independent
        o = fa_ops.flash_attention(q, k, v, causal, window, softcap, scale,
                                   16, 16)
        assert o.dtype == dtype     # output keeps the input dtype
        o_ref, _ = attention_ref(q, k, v, causal=causal, window=window,
                                 softcap=softcap, scale=scale)
        np.testing.assert_allclose(np.asarray(o, np.float32),
                                   np.asarray(o_ref, np.float32),
                                   rtol=tol, atol=tol)

    def f(q, k, v):
        return jnp.sum(jnp.sin(fa_ops.flash_attention(
            q, k, v, causal, window, softcap, scale, 16, 16,
            bwd_strategy).astype(jnp.float32)))

    def f_ref(q, k, v):
        return jnp.sum(jnp.sin(attention_ref(
            q, k, v, causal=causal, window=window, softcap=softcap,
            scale=scale)[0]))

    g = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=max(tol, 1e-4), atol=max(tol, 1e-4))


def test_flash_attention_fused_matches_split():
    """The fused single-recompute backward and the legacy two-sweep
    backward are the same math over different schedules — bitwise-close."""
    case = (1, 40, 40, 2, 2, 16, True, 8, 50.0, jnp.float32)
    q, k, v, scale = _fa_inputs(case)

    def loss(strategy):
        return lambda q, k, v: jnp.sum(jnp.sin(fa_ops.flash_attention(
            q, k, v, True, 8, 50.0, scale, 16, 16, strategy)))

    g_fused = jax.grad(loss("fused"), argnums=(0, 1, 2))(q, k, v)
    g_split = jax.grad(loss("split"), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_fused, g_split):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)


def test_flash_attention_odd_shape_default_blocks():
    """S=20 with the default block_q=128 exercises the 8-aligned block
    clamp (bq rounds 20 -> 24); forward and grads must still match."""
    case = (2, 20, 20, 2, 2, 16, True, 0, 0.0, jnp.float32)
    q, k, v, scale = _fa_inputs(case)
    o = fa_ops.flash_attention(q, k, v, True, 0, 0.0, scale)
    o_ref, _ = attention_ref(q, k, v, causal=True, scale=scale)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               rtol=2e-5, atol=2e-5)
    g = jax.grad(lambda q, k, v: jnp.sum(jnp.sin(fa_ops.flash_attention(
        q, k, v, True, 0, 0.0, scale))), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(lambda q, k, v: jnp.sum(jnp.sin(attention_ref(
        q, k, v, causal=True, scale=scale)[0])), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_flash_attention_rejects_unknown_strategies():
    """Typos must fail loudly, not silently pick a (possibly
    interpreter-wrong) schedule."""
    from repro.kernels.flash_attention import kernel as K

    case = (1, 16, 16, 2, 1, 16, True, 0, 0.0, jnp.float32)
    q, k, v, scale = _fa_inputs(case)
    with pytest.raises(ValueError, match="bwd_strategy"):
        fa_ops.flash_attention(q, k, v, True, 0, 0.0, scale, 16, 16,
                               "fuzed")
    with pytest.raises(ValueError, match="bwd_strategy"):
        jax.grad(lambda q: jnp.sum(fa_ops.flash_attention(
            q, k, v, True, 0, 0.0, scale, 16, 16, "partial")))(q)
    qk = jnp.zeros((2, 16, 16), jnp.float32)
    kv = jnp.zeros((2, 16, 16), jnp.float32)
    row = jnp.zeros((2, 1, 16), jnp.float32)
    with pytest.raises(ValueError, match="dq_strategy"):
        K.flash_bwd_fused(qk, kv, kv, qk, row, row, group=1, causal=True,
                          window=0, softcap=0.0, scale=1.0, kv_len=16,
                          block_q=16, block_k=16, dq_strategy="aliased")


def test_flash_attention_fused_alias_scratch_case():
    """dq_strategy="alias" with G * nq == 1 accumulates dQ in VMEM scratch
    (the aliased window's index would not change between kv revisits) —
    the one alias configuration the interpreter executes correctly; the
    G * nq > 1 alias path is TPU-only to validate (see README/ROADMAP)."""
    from repro.kernels.flash_attention import kernel as K

    B, S, Hkv, G, hd = 1, 16, 2, 1, 16
    bq, bk = 16, 8                       # nq=1, nk=2; G*nq == 1
    q = jnp.asarray(rng.normal(0, 1, (B, S, Hkv, G, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(0, 1, (B, S, Hkv, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(0, 1, (B, S, Hkv, hd)), jnp.float32)
    _, (qp, kp, vp, op, lsep, _) = fa_ops._fwd(q, k, v, True, 0, 0.0, 0.25,
                                               bq, bk)
    do = jnp.asarray(rng.normal(0, 1, op.shape), jnp.float32)
    delta = jnp.sum(do * op, axis=-1)[:, None, :]
    common = dict(group=G, causal=True, window=0, softcap=0.0, scale=0.25,
                  kv_len=S, block_q=bq, block_k=bk)
    alias = K.flash_bwd_fused(qp, kp, vp, do, lsep, delta,
                              dq_strategy="alias", **common)
    parts = K.flash_bwd_fused(qp, kp, vp, do, lsep, delta,
                              dq_strategy="partials", **common)
    for a, b in zip(alias, parts):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)


def test_flash_attention_block_size_invariance():
    B, S, Hkv, G, hd = 1, 64, 2, 2, 16
    q = jnp.asarray(rng.normal(0, 1, (B, S, Hkv, G, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(0, 1, (B, S, Hkv, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(0, 1, (B, S, Hkv, hd)), jnp.float32)
    outs = [fa_ops.flash_attention(q, k, v, True, 0, 0.0, 0.25, bq, bk)
            for bq, bk in ((8, 8), (16, 32), (32, 16), (64, 64))]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o),
                                   rtol=1e-5, atol=1e-5)


def test_flash_attention_grad_block_size_invariance():
    """Backward mirror of the forward invariance test: dQ/dK/dV must not
    depend on the (block_q, block_k) tiling."""
    B, S, Hkv, G, hd = 1, 64, 2, 2, 16
    q = jnp.asarray(rng.normal(0, 1, (B, S, Hkv, G, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(0, 1, (B, S, Hkv, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(0, 1, (B, S, Hkv, hd)), jnp.float32)

    def grads(bq, bk):
        return jax.grad(lambda q, k, v: jnp.sum(jnp.sin(
            fa_ops.flash_attention(q, k, v, True, 16, 20.0, 0.25, bq, bk))),
            argnums=(0, 1, 2))(q, k, v)

    base = grads(8, 8)
    for bq, bk in ((16, 32), (32, 16), (64, 64)):
        for a, b in zip(base, grads(bq, bk)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# fused cross-entropy
# ---------------------------------------------------------------------------

XENT_CASES = [
    (24, 32, 100, 0.0), (16, 64, 53, 30.0), (33, 48, 257, 0.0),
    (8, 32, 17, 10.0), (64, 16, 1000, 0.0),
]


@pytest.mark.parametrize("case", XENT_CASES)
@pytest.mark.parametrize("impl", ["pallas", "xla", "sharded"])
def test_xent_all_impls_match_ref(case, impl):
    T, D, V, cap = case
    h = jnp.asarray(rng.normal(0, 1, (T, D)), jnp.float32)
    w = jnp.asarray(rng.normal(0, 1, (D, V)) / np.sqrt(D), jnp.float32)
    lab = jnp.asarray(rng.integers(0, V, (T,)), jnp.int32)

    _, ref = cross_entropy_ref(h, w, lab, softcap=cap)
    _, got = xent_ops.cross_entropy(h, w, lab, softcap=cap, impl=impl,
                                    block=16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)

    gf = jax.grad(lambda h, w: xent_ops.cross_entropy(
        h, w, lab, softcap=cap, impl=impl, block=16)[0], argnums=(0, 1))
    gr = jax.grad(lambda h, w: cross_entropy_ref(
        h, w, lab, softcap=cap)[0], argnums=(0, 1))
    for a, b in zip(gf(h, w), gr(h, w)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_xent_mask():
    T, D, V = 16, 8, 40
    h = jnp.asarray(rng.normal(0, 1, (T, D)), jnp.float32)
    w = jnp.asarray(rng.normal(0, 1, (D, V)), jnp.float32)
    lab = jnp.asarray(rng.integers(0, V, (T,)), jnp.int32)
    mask = jnp.asarray(rng.integers(0, 2, (T,)), jnp.float32)
    l_ref, _ = cross_entropy_ref(h, w, lab, mask)
    l_got, _ = xent_ops.cross_entropy(h, w, lab, mask, impl="xla", block=8)
    np.testing.assert_allclose(float(l_got), float(l_ref), rtol=1e-5)


# ---------------------------------------------------------------------------
# SSD chunk kernel
# ---------------------------------------------------------------------------

SSD_CASES = [
    (2, 3, 16, 4, 8, 16),
    (1, 2, 8, 2, 16, 8),
    (2, 1, 32, 8, 8, 32),
]


@pytest.mark.parametrize("case", SSD_CASES)
def test_ssd_intra_matches_ref(case):
    B, nc, Q, H, P, N = case
    xf = jnp.asarray(rng.normal(0, 1, (B, nc, Q, H, P)), jnp.float32)
    dtf = jnp.asarray(np.abs(rng.normal(0, 0.1, (B, nc, Q, H))), jnp.float32)
    A = -jnp.asarray(np.abs(rng.normal(1, 0.3, (H,))), jnp.float32)
    a_cum = jnp.cumsum(dtf * A, axis=2)
    Bf = jnp.asarray(rng.normal(0, 1, (B, nc, Q, N)), jnp.float32)
    Cf = jnp.asarray(rng.normal(0, 1, (B, nc, Q, N)), jnp.float32)

    y_p, s_p = ssd_intra(xf, dtf, a_cum, Bf, Cf)
    y_r, s_r = ssd_intra_ref(xf, dtf, a_cum, Bf, Cf)
    np.testing.assert_allclose(np.asarray(y_p), np.asarray(y_r),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s_p), np.asarray(s_r),
                               rtol=1e-5, atol=1e-5)

    def loss(fn):
        return lambda *a: (jnp.sum(jnp.sin(fn(*a)[0]))
                           + jnp.sum(fn(*a)[1] ** 2))

    g = jax.grad(loss(ssd_intra), argnums=(0, 1, 2, 3, 4))(
        xf, dtf, a_cum, Bf, Cf)
    g_ref = jax.grad(loss(ssd_intra_ref), argnums=(0, 1, 2, 3, 4))(
        xf, dtf, a_cum, Bf, Cf)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_ssd_chunked_matches_sequential_decode():
    """Chunked SSD == step-by-step recurrence (the duality itself)."""
    from repro.models.mamba import ssd_chunked, ssd_decode_step
    B, S, H, P, N = 2, 20, 2, 4, 8
    xh = jnp.asarray(rng.normal(0, 1, (B, S, H, P)), jnp.float32)
    dt = jnp.asarray(np.abs(rng.normal(0, 0.2, (B, S, H))), jnp.float32)
    A = -jnp.asarray(np.abs(rng.normal(1, 0.3, (H,))), jnp.float32)
    Bm = jnp.asarray(rng.normal(0, 1, (B, S, N)), jnp.float32)
    Cm = jnp.asarray(rng.normal(0, 1, (B, S, N)), jnp.float32)

    y_chunk, h_final = ssd_chunked(xh, dt, A, Bm, Cm, chunk=8)
    h = jnp.zeros((B, H, P, N), jnp.float32)
    ys = []
    for t in range(S):
        y_t, h = ssd_decode_step(xh[:, t], dt[:, t], A, Bm[:, t], Cm[:, t], h)
        ys.append(y_t)
    y_seq = jnp.stack(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_chunk), np.asarray(y_seq),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h_final), np.asarray(h),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("backend,interpret", [("tpu", False), ("cpu", True),
                                               ("gpu", None)])
def test_pallas_interpret_follows_the_backend(monkeypatch, backend,
                                              interpret):
    """Compiled on a TPU, interpreted on the CPU, and no silent
    interpreter fallback on any other backend."""
    from repro.platform import pallas_interpret

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if interpret is None:
        with pytest.raises(RuntimeError, match="'gpu'"):
            pallas_interpret()
    else:
        assert pallas_interpret() is interpret
