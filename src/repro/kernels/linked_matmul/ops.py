"""Jit'd public wrapper for the linked MLP kernel."""
from __future__ import annotations

from functools import partial

import jax

from .. import interpret_mode
from .linked_matmul import linked_mlp as _kernel_impl
from .ref import linked_mlp_ref


@partial(jax.jit, static_argnames=("block_m", "block_ff"))
def linked_mlp(x, wg, wu, wd, *, block_m: int = 256, block_ff: int = 512):
    """Ragged blocks run the reference in interpret mode and raise when
    compiled for a TPU."""
    M, d = x.shape
    ff = wg.shape[1]
    if M % min(block_m, M) or ff % min(block_ff, ff):
        if not interpret_mode():
            raise ValueError(
                f"linked_mlp cannot tile a TPU: blocks {block_m}x{block_ff} "
                f"do not divide M={M}, ff={ff}")
        return linked_mlp_ref(x, wg, wu, wd)
    return _kernel_impl(x, wg, wu, wd, block_m=block_m, block_ff=block_ff,
                        interpret=interpret_mode())
