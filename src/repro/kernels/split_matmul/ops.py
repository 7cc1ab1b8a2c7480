from __future__ import annotations

from functools import partial

import jax

from .. import interpret_mode
from .ref import split_matmul_ref
from .split_matmul import split_matmul as _kernel_impl


@partial(jax.jit, static_argnames=("block_m", "block_n", "block_k"))
def split_matmul(x, w, b, *, block_m: int = 256, block_n: int = 512,
                 block_k: int = 512):
    """Ragged blocks run the reference in interpret mode and raise when
    compiled for a TPU."""
    M, K = x.shape
    N = w.shape[1]
    if M % min(block_m, M) or N % min(block_n, N) or K % min(block_k, K):
        if not interpret_mode():
            raise ValueError(
                f"split_matmul cannot tile a TPU: blocks "
                f"{block_m}x{block_n}x{block_k} do not divide "
                f"M={M}, N={N}, K={K}")
        return split_matmul_ref(x, w, b)
    return _kernel_impl(x, w, b, block_m=block_m, block_n=block_n,
                        block_k=block_k, interpret=interpret_mode())
