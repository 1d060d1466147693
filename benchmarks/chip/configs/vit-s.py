"""Plain reference of ViT-S split at layer 1, and its FLOP count.

Written from the layer equations, with no import of the program: patch
embedding (the device block at ``split_point=1``), pre-LN encoder blocks
(multi-head self-attention, GELU MLP), a final LayerNorm, mean pooling
over patches and a linear classifier; the auxiliary net is one encoder
block at ``aux_ratio`` of the width (heads and MLP) plus its own head.

``mode`` picks the arithmetic: ``"f32"`` is the configuration's stated
precision, float32 weights and activations with every matrix product at
JAX's default precision (on the TPU its operands are rounded to
bfloat16, its sums kept in float32), so the program and the reference
differ by the order of their float32 sums alone; ``"bf16"``, the
lower-precision control, casts weights and activations to bfloat16.
Departures from the published ViT, each as the repo's model has it: no
class token (mean pooling), a LayerNorm before pooling, GELU in its
tanh form.

``init`` draws the weights from the seed's key in the order the repo's
model draws them, so both start from the same numbers without the
reference taking any array from the program.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

MODES = ("f32", "bf16")


def _dt(mode):
    if mode not in MODES:
        raise ValueError(f"no arithmetic {mode!r}; one of {MODES}")
    return jnp.float32 if mode == "f32" else jnp.bfloat16


def _c(tree, mode):
    return jax.tree.map(lambda a: a.astype(_dt(mode)), tree)


# ---------------------------------------------------------------------------
# weights from the seed
# ---------------------------------------------------------------------------


def _dense(key, din, dout):
    return {"w": jax.random.normal(key, (din, dout)) * (1.0 / math.sqrt(din)),
            "b": jnp.zeros((dout,), jnp.float32)}


def _ln(d):
    return {"scale": jnp.ones((d,), jnp.float32),
            "bias": jnp.zeros((d,), jnp.float32)}


def _block(key, din, d, f):
    ks = jax.random.split(key, 8)
    return {"norm1": _ln(din), "wq": _dense(ks[0], din, d),
            "wk": _dense(ks[1], din, d), "wv": _dense(ks[2], din, d),
            "wo": _dense(ks[3], d, din), "norm2": _ln(din),
            "wi": _dense(ks[4], din, f), "wom": _dense(ks[5], f, din)}


def _head(key, d, classes):
    return {"fc": _dense(key, d, classes), "norm": _ln(d)}


def aux_dims(m, ratio):
    d = max(8, int(round(m["d_model"] * ratio)))
    h = max(1, int(round(m["num_heads"] * ratio)))
    while d % h:
        h -= 1
    return d, h, int(d * m["mlp_ratio"])


def init(key, m, split):
    """(device, server, aux) weight trees for a split at layer 1, drawn
    in one jitted call as the program draws its own: an eager draw
    rounds a third or more of the weights one float32 ulp apart from a
    jitted one, and the odd weight on a bfloat16 rounding midpoint then
    rounds one bfloat16 ulp apart in every default-precision product."""
    return jax.jit(lambda k: _draw(k, m, split))(key)


def _draw(key, m, split):
    assert split["split_point"] == 1
    D = m["d_model"]
    F = int(D * m["mlp_ratio"])
    n_layers = m["depth"] + 1
    keys = jax.random.split(key, n_layers + 1)
    ks0 = jax.random.split(keys[0], 8)
    patch_dim = m["patch_size"] ** 2 * m["in_channels"]
    n_patch = (m["img_size"] // m["patch_size"]) ** 2
    embed = {"proj": _dense(ks0[0], patch_dim, D),
             "pos": jax.random.normal(ks0[1], (n_patch, D)) * 0.02}
    server = {"layers": [_block(keys[i], D, D, F)
                         for i in range(1, n_layers)],
              "head": _head(keys[-1], D, m["num_classes"])}
    k1, k2 = jax.random.split(jax.random.fold_in(key, 7))
    ad, _, af = aux_dims(m, split["aux_ratio"])
    aux = {"block": _block(k1, D, ad, af),
           "head": _head(k2, D, m["num_classes"])}
    return {"layers": [embed]}, server, aux


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _lin(p, x):
    return jnp.einsum("...i,io->...o", x, p["w"]) + p["b"]


def _layernorm(p, x, eps):
    xf = x.astype(jnp.float32)
    mu = xf.mean(-1, keepdims=True)
    var = ((xf - mu) ** 2).mean(-1, keepdims=True)
    y = (xf - mu) / jnp.sqrt(var + eps) * p["scale"].astype(jnp.float32) \
        + p["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def patch_embed(p, images, m, mode):
    B = images.shape[0]
    P, g = m["patch_size"], m["img_size"] // m["patch_size"]
    x = images.astype(_dt(mode)).reshape(B, g, P, g, P, -1)
    x = x.transpose(0, 1, 3, 2, 4, 5).reshape(B, g * g, -1)
    p = _c(p, mode)
    return _lin(p["proj"], x) + p["pos"]


def encoder_block(p, x, heads, m, mode):
    p = _c(p, mode)
    B, N, _ = x.shape
    h = _layernorm(p["norm1"], x, m["norm_eps"])
    q, k, v = (_lin(p[n], h) for n in ("wq", "wk", "wv"))
    D = q.shape[-1]
    hd = D // heads
    q, k, v = (t.reshape(B, N, heads, hd) for t in (q, k, v))
    s = jnp.einsum("bnhd,bmhd->bhnm", q, k) / math.sqrt(hd)
    a = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(s.dtype)
    o = jnp.einsum("bhnm,bmhd->bnhd", a, v).reshape(B, N, D)
    x = x + _lin(p["wo"], o)
    h = _layernorm(p["norm2"], x, m["norm_eps"])
    return x + _lin(p["wom"], _gelu(_lin(p["wi"], h)))


def head_logits(p, x, m, mode):
    p = _c(p, mode)
    x = _layernorm(p["norm"], x, m["norm_eps"])
    return _lin(p["fc"], x.mean(axis=1))


def _xent(logits, labels, half):
    if half:        # the half-batch fault: the mean over the first half
        n = logits.shape[0] // 2
        logits, labels = logits[:n], labels[:n]
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    return jnp.mean(lse - jnp.take_along_axis(lf, labels[:, None], -1)[:, 0])


def device_forward(device, images, m, mode):
    return patch_embed(device["layers"][0], images, m, mode)


def server_loss(server, acts, labels, m, mode, half=False):
    x = acts.astype(_dt(mode))
    for p in server["layers"]:
        x = encoder_block(p, x, m["num_heads"], m, mode)
    return _xent(head_logits(server["head"], x, m, mode), labels, half)


def aux_loss(params, images, labels, m, split, mode, half=False):
    """``params = {"device": ..., "aux": ...}``: the device block, then
    the auxiliary block and head, against the labels."""
    x = device_forward(params["device"], images, m, mode)
    _, heads, _ = aux_dims(m, split["aux_ratio"])
    x = encoder_block(params["aux"]["block"], x, heads, m, mode)
    return _xent(head_logits(params["aux"]["head"], x, m, mode), labels,
                 half)


# ---------------------------------------------------------------------------
# FLOPs per sample, counted from the shapes (multiply-add = 2)
# ---------------------------------------------------------------------------


def _block_fwd_flops(n, din, d, f):
    proj = 2 * n * (3 * din * d + d * din)
    attn = 2 * 2 * n * n * d
    mlp = 2 * n * 2 * din * f
    return proj + attn + mlp


def flops_per_sample(m, split, part):
    """Model FLOPs of one sample.  ``part``: ``"server_train"`` (forward
    and backward of the server block, three times its forward),
    ``"device_train"`` (the same for device block plus auxiliary net) or
    ``"device_forward"``.  Recomputation is not counted."""
    D = m["d_model"]
    F = int(D * m["mlp_ratio"])
    n = (m["img_size"] // m["patch_size"]) ** 2
    head = 2 * D * m["num_classes"]
    embed = 2 * n * m["patch_size"] ** 2 * m["in_channels"] * D
    if part == "server_train":
        return 3 * (m["depth"] * _block_fwd_flops(n, D, D, F) + head)
    if part == "device_forward":
        return embed
    if part == "device_train":
        ad, _, af = aux_dims(m, split["aux_ratio"])
        return 3 * (embed + _block_fwd_flops(n, D, ad, af) + head)
    raise KeyError(part)
