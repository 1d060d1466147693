"""CNN classifiers for the paper-faithful reproduction path:
MobileNetV3-Large-style inverted-residual CNN and VGG-11.

Layer-indexed API (layer 0 = stem, 1..n = blocks, head applied at the end)
so Ampere's split point / auxiliary generation work identically to the LM
path.  Normalization uses GroupNorm instead of BatchNorm (deterministic,
no cross-device batch statistics — adaptation noted in DESIGN.md).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import layers as L


# ---------------------------------------------------------------------------
# Primitives
# ---------------------------------------------------------------------------


def init_conv(key, kh, kw, cin, cout, param_dtype="float32"):
    fan_in = kh * kw * cin
    std = math.sqrt(2.0 / fan_in)
    return {"w": (jax.random.normal(key, (kh, kw, cin, cout)) * std
                  ).astype(L.dt(param_dtype)),
            "b": L.zeros_init((cout,), param_dtype)}


def conv2d(p, x, stride=1, groups=1, compute_dtype="float32"):
    w = L.cast(p["w"], compute_dtype)
    y = jax.lax.conv_general_dilated(
        L.cast(x, compute_dtype), w,
        window_strides=(stride, stride), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups)
    return y + L.cast(p["b"], compute_dtype)


def init_groupnorm(ch, param_dtype="float32"):
    return {"scale": L.ones_init((ch,), param_dtype),
            "bias": L.zeros_init((ch,), param_dtype)}


def groupnorm(p, x, groups=8, eps=1e-5):
    B, H, W, C = x.shape
    g = math.gcd(groups, C)
    xf = x.astype(jnp.float32).reshape(B, H, W, g, C // g)
    mu = jnp.mean(xf, axis=(1, 2, 4), keepdims=True)
    var = jnp.var(xf, axis=(1, 2, 4), keepdims=True)
    y = ((xf - mu) * jax.lax.rsqrt(var + eps)).reshape(B, H, W, C)
    return (y * p["scale"] + p["bias"]).astype(x.dtype)


def hardswish(x):
    return x * jax.nn.relu6(x + 3.0) / 6.0


# ---------------------------------------------------------------------------
# MobileNet-style inverted residual
# ---------------------------------------------------------------------------


def init_ir_block(key, cin, cout, stride, expand_ratio, use_se,
                  param_dtype="float32"):
    mid = cin * expand_ratio
    ks = jax.random.split(key, 5)
    p = {
        "expand": init_conv(ks[0], 1, 1, cin, mid, param_dtype),
        "expand_norm": init_groupnorm(mid, param_dtype),
        "dw": init_conv(ks[1], 3, 3, 1, mid, param_dtype),  # depthwise: I=1
        "dw_norm": init_groupnorm(mid, param_dtype),
        "project": init_conv(ks[2], 1, 1, mid, cout, param_dtype),
        "project_norm": init_groupnorm(cout, param_dtype),
    }
    if use_se:
        se_mid = max(8, mid // 4)
        p["se_reduce"] = L.init_dense(ks[3], mid, se_mid, bias=True,
                                      param_dtype=param_dtype)
        p["se_expand"] = L.init_dense(ks[4], se_mid, mid, bias=True,
                                      param_dtype=param_dtype)
    return p


def ir_block(p, x, stride, compute_dtype="float32"):
    cin = x.shape[-1]
    h = conv2d(p["expand"], x, 1, compute_dtype=compute_dtype)
    h = hardswish(groupnorm(p["expand_norm"], h))
    mid = h.shape[-1]
    h = conv2d(p["dw"], h, stride, groups=mid, compute_dtype=compute_dtype)
    h = hardswish(groupnorm(p["dw_norm"], h))
    if "se_reduce" in p:
        s = jnp.mean(h, axis=(1, 2))
        s = jax.nn.relu(L.dense(p["se_reduce"], s, compute_dtype))
        s = jax.nn.sigmoid(L.dense(p["se_expand"], s, compute_dtype))
        h = h * s[:, None, None, :]
    h = groupnorm(p["project_norm"],
                  conv2d(p["project"], h, 1, compute_dtype=compute_dtype))
    if stride == 1 and h.shape[-1] == cin:
        h = h + x
    return h


# ---------------------------------------------------------------------------
# VGG block
# ---------------------------------------------------------------------------


def init_vgg_block(key, cin, cout, param_dtype="float32"):
    return {"conv": init_conv(key, 3, 3, cin, cout, param_dtype),
            "norm": init_groupnorm(cout, param_dtype)}


def vgg_block(p, x, stride, compute_dtype="float32"):
    h = conv2d(p["conv"], x, 1, compute_dtype=compute_dtype)
    h = jax.nn.relu(groupnorm(p["norm"], h))
    if stride == 2:
        h = jax.lax.reduce_window(h, -jnp.inf, jax.lax.max,
                                  (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
    return h


# ---------------------------------------------------------------------------
# Layer-indexed model API
# ---------------------------------------------------------------------------


def cnn_channels(cfg, layer_idx: int, width_scale: float = 1.0):
    """Output channels of layer ``layer_idx`` (0 = stem)."""
    if layer_idx == 0:
        ch = cfg.stem_channels if cfg.family == "cnn" else cfg.block_channels[0]
    else:
        ch = cfg.block_channels[layer_idx - 1]
    return max(4, int(round(ch * width_scale)))


def init_vision_layer(key, cfg, layer_idx: int, in_ch: Optional[int] = None,
                      width_scale: float = 1.0):
    """Init CNN/VGG layer ``layer_idx``; ``width_scale`` supports Ampere's
    auxiliary-network generation (halved dimensions)."""
    pd = cfg.param_dtype
    cout = cnn_channels(cfg, layer_idx, width_scale)
    if layer_idx == 0:
        cin = in_ch if in_ch is not None else cfg.in_channels
        if cfg.family == "cnn":
            return {"conv": init_conv(key, 3, 3, cin, cout, pd),
                    "norm": init_groupnorm(cout, pd)}
        return init_vgg_block(key, cin, cout, pd)
    cin = in_ch if in_ch is not None else cnn_channels(cfg, layer_idx - 1)
    if cfg.family == "cnn":
        return init_ir_block(key, cin, cout,
                             cfg.block_strides[layer_idx - 1],
                             cfg.expand_ratio, cfg.use_se, pd)
    return init_vgg_block(key, cin, cout, pd)


def apply_vision_layer(cfg, p, x, layer_idx: int):
    cd = cfg.dtype
    if layer_idx == 0:
        if cfg.family == "cnn":
            return hardswish(groupnorm(p["norm"],
                                       conv2d(p["conv"], x, cfg.stem_stride,
                                              compute_dtype=cd)))
        return vgg_block(p, x, cfg.block_strides[0] if cfg.block_strides else 1,
                         compute_dtype=cd)
    stride = cfg.block_strides[layer_idx - 1]
    if cfg.family == "cnn":
        return ir_block(p, x, stride, compute_dtype=cd)
    return vgg_block(p, x, stride, compute_dtype=cd)


def init_head(key, cfg, in_ch: int):
    head = {"fc": L.init_dense(key, in_ch, cfg.num_classes, bias=True,
                               param_dtype=cfg.param_dtype)}
    if cfg.family in ("vit", "swin"):
        # the transformer's final LayerNorm: the pre-LN residual stream
        # grows with depth, and at full ViT-S width it reached the
        # classifier at std ~3.4 (init loss 4.3 on 10 classes)
        head["norm"] = L.init_layernorm(in_ch, cfg.param_dtype)
    return head


def apply_head(cfg, p, x):
    if "norm" in p:
        x = L.layernorm(p["norm"], x, cfg.norm_eps, cfg.dtype)
    feat = jnp.mean(x, axis=(1, 2)) if x.ndim == 4 else jnp.mean(x, axis=1)
    return L.dense(p["fc"], feat, cfg.dtype)
