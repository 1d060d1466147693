"""The numbers that decide ``correct``, computed from per-leaf norms.

A training check compares, leaf by leaf, the norm the program reaches
with the norm the reference reaches, never the norm of their difference:
the gap is ``|prog - ref|`` over the larger of the reference leaf's norm
and the median leaf's norm, so a leaf whose gradient is all but zero
does not blow the ratio up.  The worst leaf is the number compared.
"""

from __future__ import annotations

import math

import numpy as np

# leaves whose reference gradient is below this share of the median
# leaf's move by round-off alone (a key bias under softmax); they are
# left out of the change comparison by this rule, never by name
NEGLIGIBLE_GRAD = 1e-3
# the median is taken over the leaves whose reference gradient is at
# least this share of the largest one's: where most leaves cannot reach
# the loss at all (mobilenet-l's auxiliary net), their round-off would
# otherwise be the median, and every gap a ratio of two round-offs
REACH = 1e-3


def leaf_norms(tree):
    """{path: float32 L2 norm} of a pytree of arrays, read to the host."""
    import jax
    import jax.numpy as jnp

    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    norms = jax.device_get([jnp.sqrt(jnp.sum(jnp.square(
        jnp.asarray(x, jnp.float32)))) for _, x in flat])
    return {jax.tree_util.keystr(p): float(v) for (p, _), v in
            zip(flat, norms)}


def diff_norms(a, b, scale=1.0):
    """{path: ||(a - b) * scale||} for two trees of one structure."""
    import jax
    import jax.numpy as jnp

    d = jax.tree.map(lambda x, y: (jnp.asarray(x, jnp.float32)
                                   - jnp.asarray(y, jnp.float32)) * scale,
                     a, b)
    return leaf_norms(d)


def worst_leaf_gap(prog, ref, keep, scale):
    """max over the ``keep`` leaves of |prog - ref| / max(ref leaf,
    ``scale``), with ``scale`` the median leaf's reference norm.

    ``prog``/``ref``: {path: norm}.  A path missing on either side is a
    fault: inf."""
    paths = sorted(keep)
    if set(prog) != set(ref):
        return math.inf, "structure"
    med = scale
    worst, where = 0.0, ""
    for p in paths:
        if not (math.isfinite(prog[p]) and math.isfinite(ref[p])):
            return math.inf, p
        g = abs(prog[p] - ref[p]) / max(ref[p], med, 1e-30)
        if g > worst:
            worst, where = g, p
    return worst, where


def median_leaf(norms, ref_grad):
    """The median of ``norms`` over the leaves whose reference gradient
    is at least REACH of the largest."""
    top = max(ref_grad.values())
    live = [norms[p] for p, g in ref_grad.items() if g >= REACH * top]
    return float(np.median(live))


def moving_leaves(ref_grad):
    """Paths whose reference gradient is at least NEGLIGIBLE_GRAD of the
    median leaf's."""
    med = median_leaf(ref_grad, ref_grad)
    return [p for p, v in ref_grad.items() if v >= NEGLIGIBLE_GRAD * med]


def loss_gap(prog, ref):
    """max over steps of |prog - ref| / |ref|."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    if prog.shape != ref.shape or not np.all(np.isfinite(prog)):
        return math.inf
    return float(np.max(np.abs(prog - ref) / np.maximum(np.abs(ref), 1e-30)))


def worst_row_gap(got, want):
    """max over rows of ||got - want|| / ||want||: ``got``/``want`` are
    sequences of arrays of one shape, a row each (``None`` where the
    program gave none).  A row missing, misshapen or not finite: inf."""
    worst = 0.0
    for g, w in zip(got, want):
        if g is None or np.shape(g) != np.shape(w) or not np.all(
                np.isfinite(g)):
            return math.inf
        w = np.asarray(w, np.float64)
        worst = max(worst, float(np.linalg.norm(g - w)
                                 / max(float(np.linalg.norm(w)), 1e-30)))
    return worst


def training_numbers(prog, ref):
    """The numbers of a training check; a cell compares those its
    ``limits`` name.

    ``prog``/``ref`` hold ``losses`` (the first steps' losses),
    ``grad`` ({path: norm of the first update over its learning rate,
    i.e. the gradient as the optimizer got it}) and ``change`` ({path:
    norm of the parameters' change after the steps}).  The reference's
    gradient decides which leaves the change comparison keeps."""
    g = ref["grad"]
    nums = {"loss_gap": loss_gap(prog["losses"], ref["losses"]),
            "first_loss_gap": loss_gap(prog["losses"][:1], ref["losses"][:1])}
    where = {}
    nums["grad_gap"], where["grad_gap"] = worst_leaf_gap(
        prog["grad"], g, list(g), median_leaf(g, g))
    nums["change_gap"], where["change_gap"] = worst_leaf_gap(
        prog["change"], ref["change"], moving_leaves(g),
        median_leaf(ref["change"], g))
    return nums, where
