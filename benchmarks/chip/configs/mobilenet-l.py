"""Plain reference of the repo's MobileNetV3-L-style CNN split at layer 1,
and its FLOPs.

Written from the layer equations, with no import of the program: a 3x3
stride-2 stem (the device block at ``split_point=1``), then fifteen
inverted-residual stages (1x1 expansion, 3x3 depthwise, squeeze-and-
excitation, 1x1 projection, residual where the shape allows), GroupNorm
of 8 groups after every convolution, hard-swish, global average pooling
and a linear classifier.  The auxiliary net is the first server stage at
``aux_ratio`` of its output width plus its own classifier.

``mode``: ``"f32"`` is the configuration's stated precision, float32
weights and activations with every convolution and matrix product at
JAX's default precision (on the TPU its operands are rounded to
bfloat16, its sums kept in float32); ``"bf16"``, the lower-precision
control, casts weights and activations to bfloat16.

Departures from the published MobileNetV3-Large (Howard et al. 2019,
Table 1), as the repo's model has them: expansion 4 in every stage,
3x3 depthwise kernels everywhere, squeeze-and-excitation in every stage,
hard-swish everywhere, GroupNorm for BatchNorm, and no final 960-wide
1x1 convolution or 1280-wide layer before the classifier.

``init`` draws the weights from the seed's key in the order the repo's
model draws them.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

GROUPS = 8


MODES = ("f32", "bf16")


def _dt(mode):
    if mode not in MODES:
        raise ValueError(f"no arithmetic {mode!r}; one of {MODES}")
    return jnp.float32 if mode == "f32" else jnp.bfloat16


def _c(tree, mode):
    return jax.tree.map(lambda a: a.astype(_dt(mode)), tree)


def _ch(m, i, scale=1.0):
    ch = m["stem_channels"] if i == 0 else m["block_channels"][i - 1]
    return max(4, int(round(ch * scale)))


# ---------------------------------------------------------------------------
# weights from the seed
# ---------------------------------------------------------------------------


def _conv(key, kh, kw, cin, cout):
    std = math.sqrt(2.0 / (kh * kw * cin))
    return {"w": jax.random.normal(key, (kh, kw, cin, cout)) * std,
            "b": jnp.zeros((cout,), jnp.float32)}


def _gn(c):
    return {"scale": jnp.ones((c,), jnp.float32),
            "bias": jnp.zeros((c,), jnp.float32)}


def _dense(key, din, dout):
    return {"w": jax.random.normal(key, (din, dout)) * (1.0 / math.sqrt(din)),
            "b": jnp.zeros((dout,), jnp.float32)}


def _stage(key, cin, cout, expand, se):
    mid = cin * expand
    ks = jax.random.split(key, 5)
    p = {"expand": _conv(ks[0], 1, 1, cin, mid), "expand_norm": _gn(mid),
         "dw": _conv(ks[1], 3, 3, 1, mid), "dw_norm": _gn(mid),
         "project": _conv(ks[2], 1, 1, mid, cout), "project_norm": _gn(cout)}
    if se:
        p["se_reduce"] = _dense(ks[3], mid, max(8, mid // 4))
        p["se_expand"] = _dense(ks[4], max(8, mid // 4), mid)
    return p


def init(key, m, split):
    """(device, server, aux) weight trees for a split at layer 1, drawn
    in one jitted call as the program draws its own: an eager draw
    rounds a third or more of the weights one float32 ulp apart from a
    jitted one, and the odd weight on a bfloat16 rounding midpoint then
    rounds one bfloat16 ulp apart in every default-precision product."""
    return jax.jit(lambda k: _draw(k, m, split))(key)


def _draw(key, m, split):
    assert split["split_point"] == 1
    n_layers = len(m["block_channels"]) + 1
    keys = jax.random.split(key, n_layers + 1)
    stem = {"conv": _conv(keys[0], 3, 3, m["in_channels"], _ch(m, 0)),
            "norm": _gn(_ch(m, 0))}
    stages = [_stage(keys[i], _ch(m, i - 1), _ch(m, i), m["expand_ratio"],
                     m["use_se"]) for i in range(1, n_layers)]
    server = {"layers": stages,
              "head": {"fc": _dense(keys[-1], _ch(m, n_layers - 1),
                                    m["num_classes"])}}
    k1, k2 = jax.random.split(jax.random.fold_in(key, 7))
    a_out = _ch(m, 1, split["aux_ratio"])
    aux = {"block": _stage(k1, _ch(m, 0), a_out, m["expand_ratio"],
                           m["use_se"]),
           "head": {"fc": _dense(k2, a_out, m["num_classes"])}}
    return {"layers": [stem]}, server, aux


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _conv2d(p, x, stride, groups):
    y = jax.lax.conv_general_dilated(
        x, p["w"], (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups)
    return y + p["b"]


def _groupnorm(p, x, eps=1e-5):
    B, H, W, C = x.shape
    g = math.gcd(GROUPS, C)
    xf = x.astype(jnp.float32).reshape(B, H, W, g, C // g)
    mu = xf.mean(axis=(1, 2, 4), keepdims=True)
    var = ((xf - mu) ** 2).mean(axis=(1, 2, 4), keepdims=True)
    y = ((xf - mu) / jnp.sqrt(var + eps)).reshape(B, H, W, C)
    return (y * p["scale"].astype(jnp.float32)
            + p["bias"].astype(jnp.float32)).astype(x.dtype)


def _hswish(x):
    return x * jnp.clip(x + 3.0, 0.0, 6.0) / 6.0


def _lin(p, x):
    return jnp.einsum("bi,io->bo", x, p["w"]) + p["b"]


def stage(p, x, stride, mode):
    p = _c(p, mode)
    cin = x.shape[-1]
    h = _hswish(_groupnorm(p["expand_norm"],
                           _conv2d(p["expand"], x, 1, 1)))
    mid = h.shape[-1]
    h = _hswish(_groupnorm(p["dw_norm"],
                           _conv2d(p["dw"], h, stride, mid)))
    if "se_reduce" in p:
        s = h.mean(axis=(1, 2))
        s = jax.nn.relu(_lin(p["se_reduce"], s))
        s = jax.nn.sigmoid(_lin(p["se_expand"], s))
        h = h * s[:, None, None, :]
    h = _groupnorm(p["project_norm"], _conv2d(p["project"], h, 1, 1))
    return h + x if (stride == 1 and h.shape[-1] == cin) else h


def _logits(p, x, mode):
    return _lin(_c(p, mode)["fc"], x.mean(axis=(1, 2)))


def _xent(logits, labels, half):
    if half:        # the half-batch fault: the mean over the first half
        n = logits.shape[0] // 2
        logits, labels = logits[:n], labels[:n]
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    return jnp.mean(lse - jnp.take_along_axis(lf, labels[:, None], -1)[:, 0])


def device_forward(device, images, m, mode):
    p = _c(device["layers"][0], mode)
    x = _conv2d(p["conv"], images.astype(_dt(mode)), m["stem_stride"], 1)
    return _hswish(_groupnorm(p["norm"], x))


def server_loss(server, acts, labels, m, mode, half=False):
    x = acts.astype(_dt(mode))
    for p, s in zip(server["layers"], m["block_strides"]):
        x = stage(p, x, s, mode)
    return _xent(_logits(server["head"], x, mode), labels, half)


def aux_loss(params, images, labels, m, split, mode, half=False):
    """``params = {"device": ..., "aux": ...}``: the stem, then the
    auxiliary stage and classifier, against the labels."""
    x = device_forward(params["device"], images, m, mode)
    x = stage(params["aux"]["block"], x, m["block_strides"][0], mode)
    return _xent(_logits(params["aux"]["head"], x, mode), labels, half)


# ---------------------------------------------------------------------------
# FLOPs per sample, conv by conv (multiply-add = 2)
# ---------------------------------------------------------------------------


def _taps(hw, k, stride):
    """(output position, kernel tap) pairs of a SAME convolution over an
    ``hw`` x ``hw`` input that read the input, not its zero padding."""
    out = -(-hw // stride)
    pad = max((out - 1) * stride + k - hw, 0) // 2
    per_dim = sum(1 for o in range(out) for d in range(k)
                  if 0 <= o * stride + d - pad < hw)
    return per_dim * per_dim


def _stage_flops(hw_in, cin, cout, stride, expand, se):
    mid = cin * expand
    hw_out = -(-hw_in // stride)
    f = 2 * hw_in * hw_in * cin * mid                 # 1x1 expansion
    f += 2 * _taps(hw_in, 3, stride) * mid            # 3x3 depthwise
    if se:
        f += 2 * 2 * mid * max(8, mid // 4)          # squeeze, excite
    f += 2 * hw_out * hw_out * mid * cout             # 1x1 projection
    return f, hw_out


def flops_per_sample(m, split, part):
    """Model FLOPs of one sample in convolutions and dense layers; a
    convolution's taps on the zero padding are not counted.
    ``part``: ``"server_train"``, ``"device_train"`` (device block plus
    auxiliary net) or ``"device_forward"``; training is three times the
    forward.  Norms and activations are not counted."""
    hw = -(-m["img_size"] // m["stem_stride"])
    stem = 2 * _taps(m["img_size"], 3, m["stem_stride"]) \
        * m["in_channels"] * _ch(m, 0)
    if part == "device_forward":
        return stem
    if part == "device_train":
        a_out = _ch(m, 1, split["aux_ratio"])
        f, hw1 = _stage_flops(hw, _ch(m, 0), a_out, m["block_strides"][0],
                              m["expand_ratio"], m["use_se"])
        return 3 * (stem + f + 2 * a_out * m["num_classes"])
    if part == "server_train":
        total = 0
        for i, s in enumerate(m["block_strides"], start=1):
            f, hw = _stage_flops(hw, _ch(m, i - 1), _ch(m, i), s,
                                 m["expand_ratio"], m["use_se"])
            total += f
        total += 2 * _ch(m, len(m["block_strides"])) * m["num_classes"]
        return 3 * total
    raise KeyError(part)
