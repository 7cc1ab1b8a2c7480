from __future__ import annotations

from functools import partial

import jax

from .. import interpret_mode
from .decode_attention import gqa_decode as _kernel_impl
from .decode_attention import gqa_decode_paged as _paged_impl
from .decode_attention import tpu_tiling_error
from .ref import gqa_decode_ref


@partial(jax.jit, static_argnames=("block_w",))
def gqa_decode(q, k_cache, v_cache, valid, *, block_w: int = 1024):
    """Dense flash-decode.  In interpret mode a window that ``block_w``
    does not divide runs the reference; compiled, a shape the TPU cannot
    tile raises."""
    W, D = k_cache.shape[1], k_cache.shape[3]
    bw = min(block_w, W)
    if not interpret_mode():
        err = tpu_tiling_error(D, W, bw)
        if err:
            raise ValueError(f"gqa_decode cannot tile a TPU: {err}")
    elif W % bw:
        return gqa_decode_ref(q, k_cache, v_cache, valid)
    return _kernel_impl(q, k_cache, v_cache, valid, block_w=block_w,
                        interpret=interpret_mode())


@jax.jit
def gqa_decode_paged(q, k_pool, v_pool, block_tables, lengths):
    """Paged flash-decode: the block table is scalar-prefetched so each
    grid step DMAs one physical pool block (no dense gather).  Compiled,
    a pool the TPU cannot tile raises."""
    if not interpret_mode():
        err = tpu_tiling_error(k_pool.shape[3])
        if err:
            raise ValueError(f"gqa_decode_paged cannot tile a TPU: {err}")
    return _paged_impl(q, k_pool, v_pool, block_tables, lengths,
                       interpret=interpret_mode())
