"""The representative config matrix the kernel analyzer traces.

Each config names one registered kernel entry point with one concrete
shape/strategy combination, covering every pallas_call the repo can
emit: both xent backward strategies (and the nt==1 scratch fallback),
both flash-attention backward schedules (fused alias / fused partials /
legacy split, and the G*nq==1 fallback), bf16 and short-sequence block
clamping, and the SSD intra-chunk kernel.  Tracing is abstract
(``jax.ShapeDtypeStruct`` arguments — no FLOPs, no device buffers), so
shapes are chosen for schedule coverage, not realism: every aliased
accumulator must actually revisit (nt > 1, G*nq > 1) and every fallback
must actually degenerate (nt == 1, G*nq == 1).

``expect`` documents hand-derived geometry (from the kernel READMEs);
``tests/test_staticcheck.py`` asserts the analyzer reproduces it.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Tuple

XENT_PATH = "src/repro/kernels/xent/kernel.py"
FA_PATH = "src/repro/kernels/flash_attention/kernel.py"
SSD_PATH = "src/repro/kernels/ssd_chunk/kernel.py"


@dataclasses.dataclass(frozen=True)
class KernelConfig:
    name: str
    path: str                      # repo-relative file findings point at
    hash_modules: Tuple[str, ...]  # sources hashed into the cache key
    build: Callable                # () -> (traceable fn, abstract args)
    expect: dict = dataclasses.field(default_factory=dict)


def _sds(shape, dtype):
    import jax
    import jax.numpy as jnp
    return jax.ShapeDtypeStruct(shape, getattr(jnp, dtype))


# --- xent ------------------------------------------------------------------


def _xent_fwd_args(T, D, V, dtype="float32"):
    return [_sds((T, D), dtype), _sds((D, V), dtype), _sds((T,), "int32")]


def _xent_bwd_args(T, D, V, dtype="float32"):
    return _xent_fwd_args(T, D, V, dtype) + [_sds((T,), "float32"),
                                             _sds((T,), "float32")]


def _build_xent_fwd(T=64, D=32, V=512, bt=16, bv=128, softcap=0.0,
                    dtype="float32"):
    from repro.kernels.xent import kernel as XK

    def fn(h, w, lab):
        return XK.xent_fwd(h, w, lab, softcap=softcap, block_t=bt,
                           block_v=bv, interpret=True)
    return fn, _xent_fwd_args(T, D, V, dtype)


def _build_xent_bwd(T=64, D=32, V=512, bt=16, bv=128, softcap=0.0,
                    dtype="float32", dh_strategy="alias"):
    from repro.kernels.xent import kernel as XK

    def fn(h, w, lab, lse, g):
        return XK.xent_bwd(h, w, lab, lse, g, softcap=softcap, block_t=bt,
                           block_v=bv, interpret=True,
                           dh_strategy=dh_strategy)
    return fn, _xent_bwd_args(T, D, V, dtype)


# --- flash attention -------------------------------------------------------


def _fa_fwd_args(BH, BKV, Sq, Skv, hd, dtype="float32"):
    return [_sds((BH, Sq, hd), dtype), _sds((BKV, Skv, hd), dtype),
            _sds((BKV, Skv, hd), dtype)]


def _fa_bwd_args(BH, BKV, Sq, Skv, hd, dtype="float32"):
    return _fa_fwd_args(BH, BKV, Sq, Skv, hd, dtype) + [
        _sds((BH, Sq, hd), "float32"), _sds((BH, 1, Sq), "float32"),
        _sds((BH, 1, Sq), "float32")]


def _build_flash_fwd(BKV=2, G=2, Sq=256, Skv=256, hd=64, bq=128, bk=128,
                     dtype="float32"):
    from repro.kernels.flash_attention import kernel as K

    def fn(q, k, v):
        return K.flash_fwd(q, k, v, group=G, causal=True, window=0,
                           softcap=0.0, scale=0.125, kv_len=Skv,
                           block_q=bq, block_k=bk, interpret=True)
    return fn, _fa_fwd_args(BKV * G, BKV, Sq, Skv, hd, dtype)


def _build_flash_fwd_short(dtype="float32"):
    """S=20 through the public block clamping (the PR 5 regression
    shape): ``ops._block_sizes`` must round the block to the dtype's
    sublane tile, and the analyzer confirms the result is aligned."""
    from repro.kernels.flash_attention import kernel as K
    from repro.kernels.flash_attention import ops
    import jax.numpy as jnp

    S = Skv = 20
    bq, bk = ops._block_sizes(S, Skv, 128, 128, getattr(jnp, dtype))
    Sp, Skvp = -(-S // bq) * bq, -(-Skv // bk) * bk

    def fn(q, k, v):
        return K.flash_fwd(q, k, v, group=1, causal=True, window=0,
                           softcap=0.0, scale=1.0, kv_len=Skv,
                           block_q=bq, block_k=bk, interpret=True)
    return fn, _fa_fwd_args(2, 2, Sp, Skvp, 64, dtype)


def _build_flash_bwd_fused(BKV=2, G=2, Sq=256, Skv=256, hd=64, bq=128,
                           bk=128, dtype="float32", dq_strategy="alias"):
    from repro.kernels.flash_attention import kernel as K

    def fn(q, k, v, do, lse, delta):
        return K.flash_bwd_fused(q, k, v, do, lse, delta, group=G,
                                 causal=True, window=0, softcap=0.0,
                                 scale=0.125, kv_len=Skv, block_q=bq,
                                 block_k=bk, interpret=True,
                                 dq_strategy=dq_strategy)
    return fn, _fa_bwd_args(BKV * G, BKV, Sq, Skv, hd, dtype)


def _build_flash_bwd_split(BKV=2, G=2, Sq=256, Skv=256, hd=64, bq=128,
                           bk=128, dtype="float32"):
    from repro.kernels.flash_attention import kernel as K

    def fn(q, k, v, do, lse, delta):
        return K.flash_bwd_dq_dkv(q, k, v, do, lse, delta, group=G,
                                  causal=True, window=0, softcap=0.0,
                                  scale=0.125, kv_len=Skv, block_q=bq,
                                  block_k=bk, interpret=True)
    return fn, _fa_bwd_args(BKV * G, BKV, Sq, Skv, hd, dtype)


# --- ssd -------------------------------------------------------------------


def _build_ssd(B=1, nc=2, Q=128, H=2, P=64, N=128):
    from repro.kernels.ssd_chunk import kernel as SK

    def fn(xf, dtf, ac, bf, cf):
        return SK.ssd_intra_pallas(xf, dtf, ac, bf, cf, interpret=True)
    args = [_sds((B, nc, Q, H, P), "float32"),
            _sds((B, nc, Q, H), "float32"),
            _sds((B, nc, Q, H), "float32"),
            _sds((B, nc, Q, N), "float32"),
            _sds((B, nc, Q, N), "float32")]
    return fn, args


# --- the matrix ------------------------------------------------------------

_XENT_MODS = ("repro.kernels.xent.kernel", "repro.staticcheck.kernel_configs")
_FA_MODS = ("repro.kernels.flash_attention.kernel",
            "repro.kernels.flash_attention.ops",
            "repro.staticcheck.kernel_configs")
_SSD_MODS = ("repro.kernels.ssd_chunk.kernel",
             "repro.staticcheck.kernel_configs")

KERNEL_CONFIGS = (
    # xent: T=64/bt=16 -> nt=4 token tiles, V=512/bv=128 -> nv=4
    KernelConfig("xent_fwd", XENT_PATH, _XENT_MODS,
                 lambda: _build_xent_fwd(),
                 expect={"grid": (4, 4)}),
    KernelConfig("xent_fwd_softcap", XENT_PATH, _XENT_MODS,
                 lambda: _build_xent_fwd(softcap=30.0),
                 expect={"grid": (4, 4)}),
    KernelConfig("xent_fwd_bf16_short", XENT_PATH, _XENT_MODS,
                 # T=20 bf16: clamp_block_t must round to the 16-row tile
                 lambda: _build_xent_fwd(T=20, bt=256, dtype="bfloat16"),
                 expect={"grid": (1, 4)}),
    KernelConfig("xent_bwd_alias", XENT_PATH, _XENT_MODS,
                 lambda: _build_xent_bwd(dh_strategy="alias"),
                 # README: dH window revisited nt grid steps apart
                 expect={"grid": (4, 4), "dh_revisit": 4,
                         "aliases": ((5, 0),)}),
    KernelConfig("xent_bwd_alias_nt1", XENT_PATH, _XENT_MODS,
                 # T=16=bt -> nt=1: VMEM-scratch fallback, the aliased
                 # input is never read and revisit semantics are unused
                 lambda: _build_xent_bwd(T=16, dh_strategy="alias"),
                 expect={"grid": (4, 1), "dh_revisit": None}),
    KernelConfig("xent_bwd_partials", XENT_PATH, _XENT_MODS,
                 lambda: _build_xent_bwd(dh_strategy="partials"),
                 expect={"grid": (4, 4), "aliases": ()}),
    # FA: BKV=2 kv heads, G=2 group, S=256/bq=128 -> nq=nk=2
    KernelConfig("flash_fwd", FA_PATH, _FA_MODS,
                 lambda: _build_flash_fwd(),
                 expect={"grid": (4, 2, 2)}),
    KernelConfig("flash_fwd_bf16", FA_PATH, _FA_MODS,
                 lambda: _build_flash_fwd(dtype="bfloat16"),
                 expect={"grid": (4, 2, 2)}),
    KernelConfig("flash_fwd_short_s20", FA_PATH, _FA_MODS,
                 # the PR 5 regression shape: S=20 must clamp to an
                 # aligned block (24 for fp32), never bq=20
                 lambda: _build_flash_fwd_short(),
                 expect={"grid": (2, 1, 1)}),
    KernelConfig("flash_fwd_short_s20_bf16", FA_PATH, _FA_MODS,
                 # same shape in bf16: the block must round to 32 rows
                 lambda: _build_flash_fwd_short(dtype="bfloat16"),
                 expect={"grid": (2, 1, 1)}),
    KernelConfig("flash_bwd_fused_alias", FA_PATH, _FA_MODS,
                 lambda: _build_flash_bwd_fused(dq_strategy="alias"),
                 # README: dQ window revisited G*nq grid steps apart
                 expect={"grid": (2, 2, 2, 2), "dq_revisit": 4,
                         "aliases": ((6, 0),)}),
    KernelConfig("flash_bwd_fused_alias_gnq1", FA_PATH, _FA_MODS,
                 # G=1, Sq=128=bq -> G*nq=1: VMEM-scratch fallback
                 lambda: _build_flash_bwd_fused(G=1, Sq=128,
                                                dq_strategy="alias"),
                 expect={"grid": (2, 2, 1, 1), "dq_revisit": None}),
    KernelConfig("flash_bwd_fused_partials", FA_PATH, _FA_MODS,
                 lambda: _build_flash_bwd_fused(dq_strategy="partials"),
                 expect={"grid": (2, 2, 2, 2), "aliases": ()}),
    KernelConfig("flash_bwd_split", FA_PATH, _FA_MODS,
                 lambda: _build_flash_bwd_split(),
                 expect={"n_calls": 2}),
    KernelConfig("ssd_intra", SSD_PATH, _SSD_MODS,
                 lambda: _build_ssd(),
                 expect={"grid": (2, 2), "aliases": ()}),
)


def get_config(name: str) -> KernelConfig:
    for cfg in KERNEL_CONFIGS:
        if cfg.name == name:
            return cfg
    raise KeyError(name)
