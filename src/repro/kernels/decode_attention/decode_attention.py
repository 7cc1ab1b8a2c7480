"""GQA flash-decode kernels: one query token vs. a dense (ring-buffer) or a
block-paged KV cache.

Both fold KV blocks into an online-softmax running state (max,
denominator, weighted accumulator) in VMEM scratch — the standard
flash-decoding decomposition, which is operator linking applied to
QK^T -> mask -> softmax -> PV: the score block never leaves VMEM.

Dense (`gqa_decode`), grid (batch, kv_head, cache_blocks), the block axis
innermost and sequential.  The caches are read through a ``(..., K, D)
-> (..., K*D)`` view, so one kv head is the lane block ``[k*D,
(k+1)*D)`` and a cache block is ``(rows, D)`` — legal for the Pallas TPU
lowering when ``D`` is a multiple of 128 and ``rows`` a multiple of 8 (the
last two block dims must tile ``(8, 128)`` or span the array).  Queries
and outputs use ``(1, 1, G, D)`` blocks of the ``(B, K, G, D)`` view; the
mask rides as an int32 ``(B, 1, W)`` row.

Paged (`gqa_decode_paged`), grid (batch,): one program per row, whatever
the table width or the kv-head count.  The block table and lengths are
scalar-prefetched; the pools stay in HBM, read through a ``(P, bs*K, D)``
view — the same bytes as the TPU's ``(P, bs, K, D)`` layout, tiled over
``(K, D)``, where a ``(P, bs, K*D)`` view would be a relayout copy of the
whole pool on every call — so a page, every kv head of ``bs`` tokens, is
one contiguous slab.  A program copies only its row's ``ceil(len / bs)``
live pages, ``C`` whole pages per chunk into a double-buffered ``(2, C,
bs*K, D)`` VMEM scratch by manual DMA, chunk ``c + 1`` in flight while
chunk ``c`` is folded into the state of all ``H`` query rows by one
``(H, C*bs*K)`` score matmul, each row keeping its own head's columns.
``C`` follows from the shapes: as many pages as fit one
``PAGE_BUFFER_BYTES`` buffer, so the four page buffers take at most 4 MiB
of the 16 MiB scoped-VMEM default of a v5e — 16 pages, 512 tokens, at
``bs`` 32, K 8, D 128 in bf16; a concat-TP shard holding ``K/shards``
heads gets proportionally more pages per chunk.
"""
from __future__ import annotations

import functools

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

    The paged kernel copies whole pages, so only the head dim matters
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
    ``(G, D)`` queries; ``ok`` is the ``(1, n)`` live-position mask.  The
    scores accumulate ``q @ k.T`` in f32 from the dtype the caller gives;
    the f32 probabilities meet ``v`` in f32."""
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

    f32 = jnp.float32
    _online_step(q_ref[0, 0].astype(f32), k_ref[0].astype(f32),
                 v_ref[0].astype(f32), valid_ref[0] != 0,
                 acc_ref, m_ref, l_ref)

    @pl.when(w == pl.num_programs(2) - 1)
    def _():
        _finish(o_ref, acc_ref, l_ref)


#: VMEM for one of the paged kernel's four page buffers (K and V, two
#: slots each): the chunk is as many whole pages as fit
PAGE_BUFFER_BYTES = 1 << 20


def pages_per_chunk(block_size: int, row_bytes: int, max_blocks: int) -> int:
    """Whole pages the paged kernel copies per chunk: as many as fit one
    ``PAGE_BUFFER_BYTES`` buffer (a page is ``block_size`` rows of
    ``row_bytes``), at least one and no more than a table holds."""
    return max(1, min(max_blocks,
                      PAGE_BUFFER_BYTES // (block_size * row_bytes)))


def _paged_kernel(bt_ref, len_ref, q_ref, k_hbm, v_hbm, o_ref,
                  k_buf, v_buf, sem, acc_ref, m_ref, l_ref, *, kv_heads):
    """One request row: walk its live pages, ``C`` whole pages a chunk.

    The pools stay in HBM; chunk ``c + 1``'s pages are copied into the
    other slot of the double-buffered ``(2, C, bs*K, D)`` scratch while
    chunk ``c`` is folded into the online-softmax state.  A page's rows
    are its tokens' kv heads in turn (row ``t*K + k``), so a chunk is one
    ``(C*bs*K, D)`` key block that all ``H = K*G`` query rows score at
    once; a query row keeps only its own head's columns.  Pages at or
    past ``ceil(len / bs)`` are never copied, and every position at or
    past ``len`` is masked out of both the scores and the values: a page
    buffer may hold an older chunk's pages or uninitialized memory there,
    and a score mask alone lets ``0 * NaN`` through ``P @ V``."""
    b = pl.program_id(0)
    M = bt_ref.shape[0] // len_ref.shape[0]
    C, rows = k_buf.shape[1], k_buf.shape[2]
    H, D = q_ref.shape[1], q_ref.shape[2]
    K = kv_heads
    bs, G = rows // K, H // K
    length = len_ref[b]
    n_pages = jnp.minimum(pl.cdiv(length, bs), M)
    n_chunks = pl.cdiv(n_pages, C)

    def copies(chunk, slot, start):
        """Start, or wait on, chunk ``chunk``'s live pages into ``slot``."""
        for j in range(C):
            page = chunk * C + j

            @pl.when(page < n_pages)
            def _():
                blk = bt_ref[b * M + page]
                for i, (hbm, buf) in enumerate(((k_hbm, k_buf),
                                                (v_hbm, v_buf))):
                    dma = pltpu.make_async_copy(hbm.at[blk], buf.at[slot, j],
                                                sem.at[i, slot])
                    dma.start() if start else dma.wait()

    _init(acc_ref, m_ref, l_ref)

    @pl.when(n_chunks > 0)
    def _():
        copies(0, 0, start=True)

    n = C * rows                                 # key rows in a chunk
    iota = jax.lax.broadcasted_iota
    col = iota(jnp.int32, (H, n), 1)
    own_head = col % K == iota(jnp.int32, (H, n), 0) // G
    col_token = col // K
    row_token = iota(jnp.int32, (n, 1), 0) // K
    dt = jnp.promote_types(q_ref.dtype, k_buf.dtype)

    def chunk_step(c, carry):
        slot = c % 2

        @pl.when(c + 1 < n_chunks)
        def _():
            copies(c + 1, 1 - slot, start=True)

        copies(c, slot, start=False)
        live = length - c * C * bs               # tokens left from here
        kk = k_buf[slot].reshape(n, D).astype(dt)
        vv = v_buf[slot].reshape(n, D).astype(jnp.float32)
        _online_step(q_ref[0].astype(dt), kk,
                     jnp.where(row_token < live, vv, 0.0),
                     own_head & (col_token < live), acc_ref, m_ref, l_ref)
        return carry

    jax.lax.fori_loop(0, n_chunks, chunk_step, 0)
    o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                ).astype(o_ref.dtype)


def gqa_decode_paged(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                     block_tables: jax.Array, lengths: jax.Array, *,
                     interpret: bool = True) -> jax.Array:
    """Flash-decode over a block-paged KV pool, walking each row's live
    pages only.

    q: (B, H, D); pools: (P, bs, K, D); block_tables: (B, M) int32 physical
    block ids in logical order (-1 = unassigned, never read); lengths:
    (B,) valid context tokens.  A row of length 0 reads nothing and
    returns zeros.  Grid: (B,), one program per row; the block table and
    lengths are scalar-prefetched, the pools stay in HBM and each program
    copies its ``ceil(len / bs)`` live pages, ``C`` at a time
    (:func:`pages_per_chunk`).  The kernel reads the pools as ``(P, bs*K,
    D)``, the same bytes as the TPU's tiled ``(P, bs, K, D)`` layout, so
    the view is free and a page, every kv head of ``bs`` tokens, is one
    contiguous slab.

    VMEM: four page buffers of ``C * bs * K * D * itemsize`` bytes, at most
    ``PAGE_BUFFER_BYTES`` each (4 MiB: C = 16 pages of 32 tokens at K = 8,
    D = 128 in bf16), plus per chunk an f32 copy of the ``(C*bs*K, D)``
    values, the ``(H, C*bs*K)`` f32 scores and ``(H, D)`` f32 softmax
    state — inside the 16 MiB scoped-VMEM default of a v5e.
    """
    B, H, D = q.shape
    P, bs, K, _ = k_pool.shape
    M = block_tables.shape[1]
    C = pages_per_chunk(bs, K * D * k_pool.dtype.itemsize, M)
    # unassigned entries past a row's live pages are never read; any
    # inside them would copy block 0 rather than leave the pool
    bt = jnp.maximum(block_tables, 0).astype(jnp.int32).reshape(B * M)
    qo_spec = pl.BlockSpec((1, H, D), lambda b, bt, ln: (b, 0, 0))
    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[qo_spec, hbm, hbm],
        out_specs=qo_spec,
        scratch_shapes=[
            pltpu.VMEM((2, C, bs * K, D), k_pool.dtype),
            pltpu.VMEM((2, C, bs * K, D), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((H, D), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_paged_kernel, kv_heads=K),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        interpret=pltpu.InterpretParams() if interpret else False,
    )(bt, lengths.astype(jnp.int32), q,
      k_pool.reshape(P, bs * K, D), v_pool.reshape(P, bs * K, D))


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
