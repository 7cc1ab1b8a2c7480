"""DOS parameter-split matmul (paper §4.2.2, Equation 1).

``y = x @ W + b`` with W too large for private memory: W is split into
(block_k, block_n) chunks, each sized to VMEM.  The N split is the paper's
preferred K-dimension (output-channel) split — partial results concatenate
for free (separate output blocks).  The K split is the deprioritized
inC split — it needs the extra reduction the paper warns about, realized
here as sequential accumulation over the innermost grid dim.

VMEM per step (double-buffered): 2*(bm*bk + bk*bn + bn + bm*bn)
elements plus the (bm, bn) f32 partial product — 4.5 MiB in f32 at the
256/512/512 default blocks, inside the 16 MiB scoped-VMEM default of a
v5e.  TPU tiling: bm a multiple of 8, bk and bn multiples of 128 (or the
whole dimension).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, w_ref, b_ref, o_ref):
    k = pl.program_id(2)
    part = jnp.dot(x_ref[...], w_ref[...], preferred_element_type=jnp.float32)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = (part + b_ref[...].astype(jnp.float32)).astype(o_ref.dtype)

    @pl.when(k > 0)
    def _acc():
        o_ref[...] += part.astype(o_ref.dtype)


def split_matmul(x: jax.Array, w: jax.Array, b: jax.Array, *,
                 block_m: int = 256, block_n: int = 512, block_k: int = 512,
                 interpret: bool = True) -> jax.Array:
    """x: (M, K); w: (K, N); b: (N,) -> (M, N)."""
    M, K = x.shape
    N = w.shape[1]
    bm, bn, bk = min(block_m, M), min(block_n, N), min(block_k, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0
    grid = (M // bm, N // bn, K // bk)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        interpret=interpret,
    )(x, w, b.reshape(1, N))
