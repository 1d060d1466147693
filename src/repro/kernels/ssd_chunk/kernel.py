"""Pallas TPU kernel for the Mamba-2 SSD intra-chunk computation.

The SSD dual form processes each (chunk, head) tile independently:

    y[q] = sum_{j<=q} (C_q . B_j) * exp(acum_q - acum_j) * dt_j * x_j
    S    = sum_j exp(acum_Q - acum_j) * dt_j * (B_j (x) x_j)

Grid: (B*nc, H) — one VMEM-resident tile per (chunk, head): the (Q, Q)
decay matrix, the (Q, N) B/C projections (shared across heads, fetched per
head via index_map), and the (Q, P) inputs.  Q=chunk (128-256), N=d_state
(128), P=head_dim (64) — everything MXU-aligned and comfortably in VMEM
(Q=256: ~1 MB/tile).

The cross-chunk linear recurrence (nc sequential steps over tiny (H, P, N)
states) stays in XLA — it is latency-, not compute-bound, and fusing it
would serialize the grid.

dt and a_cum enter as (B*nc*H, 1, Q) rows with (1, 1, Q) blocks — the
chunk on lanes; a (1, Q) block over (B*nc*H, Q) is refused by the TPU
compiler.  The kernel transposes a row where it needs the column.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.platform import pallas_interpret

NEG_INF = -1e30


def _ssd_kernel(x_ref, dt_ref, ac_ref, b_ref, c_ref, y_ref, s_ref):
    Q = x_ref.shape[1]
    x = x_ref[0].astype(jnp.float32)        # (Q, P)
    dt = dt_ref[0].astype(jnp.float32)      # (1, Q)
    ac = ac_ref[0].astype(jnp.float32)      # (1, Q)
    b = b_ref[0].astype(jnp.float32)        # (Q, N)
    c = c_ref[0].astype(jnp.float32)        # (Q, N)

    cb = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (Q, Q)
    seg = ac.T - ac                         # (Q, Q): ac_q - ac_j
    qi = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    kj = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    Ldec = jnp.exp(jnp.where(qi >= kj, seg, NEG_INF))
    att = cb * Ldec * dt
    y_ref[0] = jax.lax.dot_general(att, x, (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    wj = jnp.exp(ac[:, Q - 1:] - ac) * dt   # (1, Q)
    bw = b * wj.T                           # (Q, N)
    # S = x^T @ bw -> (P, N)
    s_ref[0] = jax.lax.dot_general(x, bw, (((0,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)


def ssd_intra_pallas(xf, dtf, a_cum, Bf, Cf, *, interpret=None):
    """Layouts as in ref.py; returns (y_intra, S_chunk)."""
    B, nc, Q, H, P = xf.shape
    N = Bf.shape[-1]
    BC = B * nc
    if interpret is None:
        interpret = pallas_interpret()

    # kernel layouts: head-major so each (bc, h) tile is contiguous
    xk = xf.transpose(0, 1, 3, 2, 4).reshape(BC * H, Q, P)
    dtk = dtf.transpose(0, 1, 3, 2).reshape(BC * H, 1, Q)
    ack = a_cum.transpose(0, 1, 3, 2).reshape(BC * H, 1, Q)
    bk = Bf.reshape(BC, Q, N)
    ck = Cf.reshape(BC, Q, N)

    y, s = pl.pallas_call(
        _ssd_kernel,
        grid=(BC, H),
        in_specs=[
            pl.BlockSpec((1, Q, P), lambda bc, h, H=H: (bc * H + h, 0, 0)),
            pl.BlockSpec((1, 1, Q), lambda bc, h, H=H: (bc * H + h, 0, 0)),
            pl.BlockSpec((1, 1, Q), lambda bc, h, H=H: (bc * H + h, 0, 0)),
            pl.BlockSpec((1, Q, N), lambda bc, h: (bc, 0, 0)),
            pl.BlockSpec((1, Q, N), lambda bc, h: (bc, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, Q, P), lambda bc, h, H=H: (bc * H + h, 0, 0)),
            pl.BlockSpec((1, P, N), lambda bc, h, H=H: (bc * H + h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BC * H, Q, P), jnp.float32),
            jax.ShapeDtypeStruct((BC * H, P, N), jnp.float32),
        ],
        interpret=interpret,
    )(xk, dtk, ack, bk, ck)

    y = y.reshape(B, nc, H, Q, P).transpose(0, 1, 3, 2, 4)
    s = s.reshape(B, nc, H, P, N)
    return y, s
