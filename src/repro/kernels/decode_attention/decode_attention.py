"""GQA flash-decode kernel: one query token vs. a (ring-buffer) KV cache.

Grid: (batch, kv_head, cache_blocks).  The cache-block axis is innermost
(sequential), carrying the online-softmax running state (max, denominator,
weighted accumulator) in VMEM scratch — the standard flash-decoding
decomposition, which is operator linking applied to
QK^T -> mask -> softmax -> PV: the score block never leaves VMEM.

TPU tiling: the caches are read through a free ``(..., K, D) ->
(..., K*D)`` view, so one kv head is the lane block ``[k*D, (k+1)*D)`` and
a cache block is ``(rows, D)`` — legal for the Pallas TPU lowering when
``D`` is a multiple of 128 and ``rows`` a multiple of 8 (the last two block
dims must tile ``(8, 128)`` or span the array).  Queries and outputs use
``(1, 1, G, D)`` blocks of the ``(B, K, G, D)`` view, which span their last
two dims.  The dense mask rides as an int32 ``(B, 1, W)`` row; the paged
kernel derives its mask from scalar-prefetched lengths.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import LANE

NEG_INF = -1e30


def tpu_tiling_error(head_dim: int, window: int = 0,
                     block_w: int = 0) -> str | None:
    """Why the kernels cannot tile a TPU at this shape, or None.

    Paged blocks span the pool's block axis, so only the head dim matters
    there.  A dense block of ``block_w`` rows out of ``window`` also holds
    a ``block_w``-lane mask row."""
    if head_dim % LANE:
        return f"head_dim {head_dim} is not a multiple of {LANE}"
    if window and window % block_w:
        return f"window {window} is not a multiple of block_w {block_w}"
    if window and block_w != window and block_w % LANE:
        return f"block_w {block_w} is not a multiple of {LANE}"
    return None


def _online_step(q, k, v, ok, acc_ref, m_ref, l_ref):
    """Fold one ``(n, D)`` KV block into the running softmax state of the
    ``(G, D)`` queries; ``ok`` is the ``(1, n)`` live-position mask."""
    q = q.astype(jnp.float32)
    k = k.astype(jnp.float32)
    v = v.astype(jnp.float32)
    D = q.shape[-1]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) / np.sqrt(D)
    s = jnp.where(ok, s, NEG_INF)               # (G, n)
    m_prev = m_ref[...]                         # (G, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def _init(acc_ref, m_ref, l_ref):
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)


def _finish(o_ref, acc_ref, l_ref):
    o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                   ).astype(o_ref.dtype)


def _scratch(G: int, D: int) -> list:
    return [pltpu.VMEM((G, D), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32)]


def _kernel(q_ref, k_ref, v_ref, valid_ref, o_ref, acc_ref, m_ref, l_ref):
    w = pl.program_id(2)

    @pl.when(w == 0)
    def _():
        _init(acc_ref, m_ref, l_ref)

    _online_step(q_ref[0, 0], k_ref[0], v_ref[0], valid_ref[0] != 0,
                 acc_ref, m_ref, l_ref)

    @pl.when(w == pl.num_programs(2) - 1)
    def _():
        _finish(o_ref, acc_ref, l_ref)


def _paged_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                  acc_ref, m_ref, l_ref):
    """Same online-softmax body as `_kernel`, but the (innermost) grid axis
    walks the request's *block table*: `bt_ref` is scalar-prefetched, so the
    BlockSpec index maps below DMA the right physical pool block per step.
    One pool block is one cache block — the paged gather never materializes
    a per-request dense cache."""
    b = pl.program_id(0)
    m = pl.program_id(2)
    bs = k_ref.shape[1]

    @pl.when(m == 0)
    def _():
        _init(acc_ref, m_ref, l_ref)

    pos = m * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
    _online_step(q_ref[0, 0], k_ref[0], v_ref[0], pos < len_ref[b],
                 acc_ref, m_ref, l_ref)

    @pl.when(m == pl.num_programs(2) - 1)
    def _():
        _finish(o_ref, acc_ref, l_ref)


def gqa_decode_paged(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                     block_tables: jax.Array, lengths: jax.Array, *,
                     interpret: bool = True) -> jax.Array:
    """Flash-decode over a block-paged KV pool.

    q: (B, H, D); pools: (P, bs, K, D); block_tables: (B, M) int32 physical
    block ids in logical order (-1 = unassigned); lengths: (B,) valid
    context tokens.  Grid: (batch, kv_head, table_blocks) with the block
    axis innermost carrying the online-softmax state; the scalar-prefetched
    block table turns the grid step into the page gather.

    VMEM per step (double-buffered inputs): 4*bs*D*itemsize (K and V
    blocks) + 4*G*D*itemsize (q, out) + 4*G*D + 8*G bytes of f32 scratch,
    plus f32 copies of the K/V block — about 70 KiB at bs=32, D=128, G=2
    in bf16, far inside the 16 MiB scoped-VMEM default of a v5e.
    """
    B, H, D = q.shape
    P, bs, K, _ = k_pool.shape
    M = block_tables.shape[1]
    G = H // K
    qg = q.reshape(B, K, G, D)
    # unassigned entries gather block 0; masked off by the length
    bt = jnp.maximum(block_tables, 0).astype(jnp.int32).reshape(B * M)
    kv_spec = pl.BlockSpec((1, bs, D),
                           lambda b, k, m, bt, ln: (bt[b * M + m], 0, k))
    qo_spec = pl.BlockSpec((1, 1, G, D), lambda b, k, m, bt, ln: (b, k, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, K, M),
        in_specs=[qo_spec, kv_spec, kv_spec],
        out_specs=qo_spec,
        scratch_shapes=_scratch(G, D),
    )
    out = pl.pallas_call(
        _paged_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, G, D), q.dtype),
        interpret=interpret,
    )(bt, lengths.astype(jnp.int32), qg,
      k_pool.reshape(P, bs, K * D), v_pool.reshape(P, bs, K * D))
    return out.reshape(B, H, D)


def gqa_decode(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
               valid: jax.Array, *, block_w: int = 1024,
               interpret: bool = True) -> jax.Array:
    """q: (B, H, D); k/v_cache: (B, W, K, D); valid: (B, W) bool.
    Returns (B, H, D).

    VMEM per step (double-buffered inputs): 4*bw*D*itemsize (K and V
    blocks) + 8*bw (int32 mask row) + 4*G*D*itemsize (q, out) + f32
    scratch and f32 copies of the K/V block — about 2.1 MiB at bw=1024,
    D=128, G=2 in bf16, inside the 16 MiB scoped-VMEM default of a v5e.
    """
    B, H, D = q.shape
    W, K = k_cache.shape[1], k_cache.shape[2]
    G = H // K
    bw = min(block_w, W)
    assert W % bw == 0, (W, bw)
    qg = q.reshape(B, K, G, D)
    kv_spec = pl.BlockSpec((1, bw, D), lambda b, k, w: (b, w, k))
    qo_spec = pl.BlockSpec((1, 1, G, D), lambda b, k, w: (b, k, 0, 0))
    out = pl.pallas_call(
        _kernel,
        grid=(B, K, W // bw),
        in_specs=[qo_spec, kv_spec, kv_spec,
                  pl.BlockSpec((1, 1, bw), lambda b, k, w: (b, 0, w))],
        out_specs=qo_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, G, D), q.dtype),
        scratch_shapes=_scratch(G, D),
        interpret=interpret,
    )(qg, k_cache.reshape(B, W, K * D), v_cache.reshape(B, W, K * D),
      valid.astype(jnp.int32).reshape(B, 1, W))
    return out.reshape(B, H, D)
