"""Linked Matmul->Matmul kernel (paper Table 1, ``MatmulX -> MatmulY``).

The SwiGLU MLP chain  y = (silu(x@W_g) * (x@W_u)) @ W_d  executed as ONE
pallas_call: the hidden activation h (the paper's "intermediate feature
map") is produced and consumed inside VMEM in the same (m, ff)-block —
the producer's write order IS the consumer's read order by construction,
and h never round-trips through HBM.

Tiling: grid (M/bm, FF/bff).  The ff axis is the innermost (sequential)
grid dim so the partial y(bm, d) accumulates in the output block across ff
steps.  VMEM per step: the double-buffered blocks bm*d (x) + 2*d*bff
(W_g, W_u) + bff*d (W_d) + bm*d (y), plus the f32 temporaries bm*bff (h)
and bm*d (partial y).  At d=2048 with the 256/512 default blocks that is
19.4 MB in bf16 and 36.2 MB in f32: more than the 16 MiB scoped-VMEM
default of a v5e (128 MiB of VMEM in all), so the call sets
``vmem_limit_bytes`` from this budget (:func:`vmem_budget`).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, wg_ref, wu_ref, wd_ref, o_ref):
    j = pl.program_id(1)
    x = x_ref[...]
    h = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
    h = jax.nn.silu(h) * jnp.dot(x, wu_ref[...],
                                 preferred_element_type=jnp.float32)
    part = jnp.dot(h.astype(x.dtype), wd_ref[...],
                   preferred_element_type=jnp.float32)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = part.astype(o_ref.dtype)

    @pl.when(j > 0)
    def _acc():
        o_ref[...] += part.astype(o_ref.dtype)


def vmem_budget(bm: int, d: int, bff: int, itemsize: int) -> int:
    """VMEM bytes one grid step holds (see the module docstring)."""
    blocks = 2 * (bm * d + 2 * d * bff + bff * d + bm * d) * itemsize
    return blocks + 4 * (bm * bff + bm * d)


def linked_mlp(x: jax.Array, wg: jax.Array, wu: jax.Array, wd: jax.Array,
               *, block_m: int = 256, block_ff: int = 512,
               interpret: bool = True) -> jax.Array:
    """x: (M, d); wg/wu: (d, ff); wd: (ff, d) -> (M, d)."""
    M, d = x.shape
    ff = wg.shape[1]
    bm = min(block_m, M)
    bff = min(block_ff, ff)
    assert M % bm == 0 and ff % bff == 0, (M, bm, ff, bff)
    grid = (M // bm, ff // bff)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, d), lambda i, j: (i, 0)),
            pl.BlockSpec((d, bff), lambda i, j: (0, j)),
            pl.BlockSpec((d, bff), lambda i, j: (0, j)),
            pl.BlockSpec((bff, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bm, d), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((M, d), x.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=max(
            16 << 20, vmem_budget(bm, d, bff, x.dtype.itemsize) * 5 // 4)),
        interpret=interpret,
    )(x, wg, wu, wd)
