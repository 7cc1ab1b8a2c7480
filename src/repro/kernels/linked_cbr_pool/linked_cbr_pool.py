"""Linked CBR-AvgPool kernel — the paper's ``x.cbra`` op (Figure 4).

Conv1x1 (+folded BN/bias) + ReLU + AvgPool2x2 in ONE pallas_call.  Each grid
step loads a (2-row, W, C) strip of the input feature map, computes the
1x1 conv as four (W/2, C)@(C, OC) matmuls on the MXU (one per row and
column parity of the 2x2 squares), applies bias+ReLU, and reduces every
2x2 square to its average *while the conv output is still in VMEM* — the
paper's zigzag write order.  The pre-pool feature map never exists in HBM,
which is exactly the locality win Figure 4 illustrates.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, w_ref, b_ref, o_ref):
    C = w_ref.shape[0]
    w = w_ref[...]
    b = b_ref[...].astype(jnp.float32)          # (1, OC)

    def cbr(r, parity):
        # one column parity of input row r: the lane half of the paired
        # view, read in the pool's order so the conv output is never
        # re-sliced
        x = x_ref[0, r, :, pl.ds(parity * C, C)]           # (W/2, C)
        return jax.nn.relu(
            jnp.dot(x, w, preferred_element_type=jnp.float32) + b)

    # avg over each 2x2 square: rows first, then column pairs (zigzag order)
    pooled = ((cbr(0, 0) + cbr(1, 0)) * 0.5
              + (cbr(0, 1) + cbr(1, 1)) * 0.5) * 0.5       # (W/2, OC)
    o_ref[0, 0] = pooled.astype(o_ref.dtype)


def cbr_avgpool(x: jax.Array, w: jax.Array, b: jax.Array, *,
                interpret: bool = True) -> jax.Array:
    """x: (N, H, W, C) with H, W even; w: (C, OC); b: (OC,).
    Returns relu(x @ w + b) avg-pooled 2x2 -> (N, H/2, W/2, OC).

    The input is read through the free ``(N, H, W/2, 2C)`` view, which
    puts each horizontal pixel pair in one row.  Every block spans its
    array's last two dims, so any even shape tiles a TPU.  VMEM per step
    (double-buffered): 2*(2*W*C + C*OC + OC + (W/2)*OC) elements plus
    four (W/2, OC) f32 conv blocks — 1.1 MiB in f32 at 224x224x24 -> 224
    and 8.2 MiB at 8x8x1024 -> 1024, inside the 16 MiB scoped-VMEM
    default of a v5e.
    """
    N, H, W, C = x.shape
    OC = w.shape[1]
    assert H % 2 == 0 and W % 2 == 0, (H, W)
    return pl.pallas_call(
        _kernel,
        grid=(N, H // 2),
        in_specs=[
            pl.BlockSpec((1, 2, W // 2, 2 * C), lambda n, i: (n, i, 0, 0)),
            pl.BlockSpec((C, OC), lambda n, i: (0, 0)),
            pl.BlockSpec((1, OC), lambda n, i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, W // 2, OC), lambda n, i: (n, i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((N, H // 2, W // 2, OC), x.dtype),
        interpret=interpret,
    )(x.reshape(N, H, W // 2, 2 * C), w, b.reshape(1, OC))
