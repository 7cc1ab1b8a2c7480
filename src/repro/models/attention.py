"""Attention: GQA + RoPE (full/partial) + qk-norm + sliding window + caches.

Three execution paths:
  * ``full_attention`` — materialized scores, used for short sequences and as
    the oracle in tests;
  * ``chunked_attention`` — flash-style double-scan (online softmax) in pure
    JAX; the train/prefill path for long sequences.  This is operator linking
    applied to attention: QK^T -> softmax -> PV execute per-block with the
    block intermediate held in VMEM, never materializing (S, S);
  * ``decode_attention`` — one query position against a (ring-buffer) cache;
    the serve_step hot loop (Pallas version in repro.kernels.decode_attention).
"""
from __future__ import annotations

import dataclasses
from functools import partial

from jax import lax
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .layers import ParamSpec, rms_norm

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, fraction: float, theta: float) -> jax.Array:
    """Inverse frequencies for the rotary dims (fraction<1 => partial RoPE,
    the chatglm 2d convention: only the first fraction*head_dim dims rotate)."""
    rot = int(head_dim * fraction)
    rot -= rot % 2
    return 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))


def apply_rope(x: jax.Array, positions: jax.Array, inv_freq: jax.Array) -> jax.Array:
    """x: (..., S, H, D); positions: broadcastable to (..., S)."""
    rot = inv_freq.shape[0] * 2
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # (..., S, rot/2)
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x_rot[..., 0::2], x_rot[..., 1::2]
    r1 = (x1.astype(jnp.float32) * cos - x2.astype(jnp.float32) * sin).astype(x.dtype)
    r2 = (x1.astype(jnp.float32) * sin + x2.astype(jnp.float32) * cos).astype(x.dtype)
    out = jnp.stack([r1, r2], axis=-1).reshape(x_rot.shape)
    return jnp.concatenate([out, x_pass], axis=-1) if x_pass.shape[-1] else out


# ---------------------------------------------------------------------------
# Parameter specs
# ---------------------------------------------------------------------------

def attention_specs(d: int, n_heads: int, n_kv: int, head_dim: int,
                    qk_norm: bool, cross: bool = False) -> dict[str, ParamSpec]:
    specs = {
        "wq": ParamSpec((d, n_heads, head_dim), ("embed", "heads", None)),
        "wk": ParamSpec((d, n_kv, head_dim), ("embed", "kv_heads", None)),
        "wv": ParamSpec((d, n_kv, head_dim), ("embed", "kv_heads", None)),
        "wo": ParamSpec((n_heads, head_dim, d), ("heads", None, "embed")),
    }
    if qk_norm:
        specs["q_norm"] = ParamSpec((head_dim,), (None,), init="ones")
        specs["k_norm"] = ParamSpec((head_dim,), (None,), init="ones")
    return specs


# ---------------------------------------------------------------------------
# Core attention math
# ---------------------------------------------------------------------------

def _gqa_scores(q: jax.Array, k: jax.Array) -> jax.Array:
    """q: (B,S,K,G,D) k: (B,T,K,D) -> scores (B,K,G,S,T)."""
    return jnp.einsum("bskgd,btkd->bkgst", q, k)


def full_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                   causal: bool = True, window: int = 0,
                   q_offset: int = 0) -> jax.Array:
    """q: (B,S,H,D), k/v: (B,T,K,D).  Returns (B,S,H,D)."""
    B, S, H, D = q.shape
    T, K = k.shape[1], k.shape[2]
    G = H // K
    qg = q.reshape(B, S, K, G, D)
    scores = _gqa_scores(qg, k).astype(jnp.float32) / np.sqrt(D)
    q_pos = jnp.arange(S) + q_offset
    k_pos = jnp.arange(T)
    mask = jnp.ones((S, T), bool)
    if causal:
        mask &= k_pos[None, :] <= q_pos[:, None]
    if window:
        mask &= k_pos[None, :] > q_pos[:, None] - window
    scores = jnp.where(mask, scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", w, v)
    return out.reshape(B, S, H, D)


def chunked_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      causal: bool = True, window: int = 0,
                      q_chunk: int = 512, kv_chunk: int = 1024) -> jax.Array:
    """Flash-style online-softmax attention; never materializes (S, T).

    Pure-JAX double scan: the (q_chunk, kv_chunk) score block is the only
    quadratic intermediate.  Matches full_attention to float tolerance
    (property-tested).
    """
    B, S, H, D = q.shape
    T, K = k.shape[1], k.shape[2]
    G = H // K
    if S % q_chunk or T % kv_chunk:
        return full_attention(q, k, v, causal=causal, window=window)
    nq, nk = S // q_chunk, T // kv_chunk
    qg = q.reshape(B, nq, q_chunk, K, G, D)
    kc = k.reshape(B, nk, kv_chunk, K, D)
    vc = v.reshape(B, nk, kv_chunk, K, D)
    scale = 1.0 / np.sqrt(D)

    # banded iteration for sliding windows (beyond-paper, EXPERIMENTS §Perf):
    # a q block only overlaps ceil((qc+window)/kvc)+1 kv blocks, so SWA
    # archs skip the fully-masked tail instead of computing and masking it
    # (flops AND score-block HBM traffic drop by ~T/(window+qc)).
    banded = bool(window) and causal
    nk_needed = min(nk, -(-(q_chunk + window) // kv_chunk) + 1) if banded else nk

    def q_block(_, qi):
        qb, qidx = qi  # (B, qc, K, G, D), scalar
        q_pos = qidx * q_chunk + jnp.arange(q_chunk)
        hi_block = (qidx * q_chunk + q_chunk - 1) // kv_chunk

        def kv_block(carry, rel):
            m, l, acc = carry
            if banded:
                kidx = hi_block - rel
                block_ok = kidx >= 0
                kb = lax.dynamic_index_in_dim(
                    kc, jnp.maximum(kidx, 0), axis=1, keepdims=False)
                vb = lax.dynamic_index_in_dim(
                    vc, jnp.maximum(kidx, 0), axis=1, keepdims=False)
            else:
                kidx = rel
                block_ok = jnp.bool_(True)
                kb = lax.dynamic_index_in_dim(kc, kidx, axis=1, keepdims=False)
                vb = lax.dynamic_index_in_dim(vc, kidx, axis=1, keepdims=False)
            k_pos = kidx * kv_chunk + jnp.arange(kv_chunk)
            s = jnp.einsum("bqkgd,btkd->bkgqt", qb, kb).astype(jnp.float32) * scale
            mask = jnp.full((q_chunk, kv_chunk), block_ok)
            if causal:
                mask &= k_pos[None, :] <= q_pos[:, None]
            if window:
                mask &= k_pos[None, :] > q_pos[:, None] - window
            s = jnp.where(mask, s, NEG_INF)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bkgqt,btkd->bkgqd", p.astype(vb.dtype), vb).astype(jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, K, G, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, K, G, q_chunk), jnp.float32)
        a0 = jnp.zeros((B, K, G, q_chunk, D), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_block, (m0, l0, a0), jnp.arange(nk_needed))
        out = acc / jnp.maximum(l, 1e-30)[..., None]       # (B,K,G,qc,D)
        return None, out.astype(q.dtype)

    _, blocks = jax.lax.scan(q_block, None,
                             (qg.swapaxes(0, 1), jnp.arange(nq)))
    # blocks: (nq, B, K, G, qc, D) -> (B, S, H, D)
    out = blocks.transpose(1, 0, 4, 2, 3, 5).reshape(B, S, H, D)
    return out


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     valid: jax.Array, backend: str = "xla") -> jax.Array:
    """One-token attention over a cache.

    q: (B,H,D); caches: (B,W,K,D); valid: (B,W) bool mask of live slots.
    ``backend`` is the ``decode_dense`` site of a ``KernelPlan``:
    ``"xla"`` (einsum + softmax) or ``"pallas"`` (flash-decode kernel).
    """
    if backend == "pallas":
        from repro.kernels.decode_attention import ops as dec_ops
        return dec_ops.gqa_decode(q, k_cache, v_cache, valid)
    if backend != "xla":
        raise ValueError(f"unknown decode_dense backend {backend!r}")
    B, H, D = q.shape
    K = k_cache.shape[2]
    G = H // K
    qg = q.reshape(B, K, G, D)
    s = jnp.einsum("bkgd,bwkd->bkgw", qg, k_cache).astype(jnp.float32) / np.sqrt(D)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1).astype(v_cache.dtype)
    out = jnp.einsum("bkgw,bwkd->bkgd", w, v_cache)
    return out.reshape(B, H, D)


def decode_attention_paged(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, block_tables: jax.Array,
                           lengths: jax.Array,
                           backend: str = "gather") -> jax.Array:
    """One-token attention over a block-paged cache.

    q: (B,H,D); pools: (P,bs,K,D); block_tables: (B,M) int32 physical block
    ids in logical order (-1 = unassigned); lengths: (B,) context tokens.
    The logical axis is ``M*bs`` wide with position ``p`` at index ``p`` —
    the same layout (and therefore the same masked reductions) as the dense
    ring buffer, which is what keeps paged and dense decode bit-identical.

    ``backend`` is the ``decode_paged`` site of a ``KernelPlan``:
    ``"gather"`` materializes the dense per-request K/V view through the
    block table; ``"fold"`` replaces the dynamic-index K gather with a
    one-hot contraction XLA fuses into the scores
    (:func:`_paged_fold_attention`, bit-identical to gather); ``"pallas"``
    is the scalar-prefetched flash-decode kernel.
    """
    if backend == "pallas":
        from repro.kernels.decode_attention import ops as dec_ops
        return dec_ops.gqa_decode_paged(q, k_pool, v_pool, block_tables,
                                        lengths)
    if backend == "fold":
        return _paged_fold_attention(q, k_pool, v_pool, block_tables,
                                     lengths)
    if backend != "gather":
        raise ValueError(f"unknown decode_paged backend {backend!r}")
    k, v = paged_kv_view(k_pool, v_pool, block_tables)
    W = k.shape[1]
    valid = jnp.arange(W)[None, :] < lengths[:, None]
    return decode_attention(q, k, v, valid)


def _paged_fold_attention(q: jax.Array, k_pool: jax.Array,
                          v_pool: jax.Array, block_tables: jax.Array,
                          lengths: jax.Array) -> jax.Array:
    """Paged decode with the block-table K gather folded into a contraction.

    The gather path dispatches a dynamic-index ``take`` per pool to build
    the (B, M*bs, K, D) view — on CPU that scalarized copy is the paged
    layout's main overhead over dense.  Here the K view is instead
    *computed* as a one-hot contraction over the physical-block axis, a
    dense matmul XLA fuses into the decode step: each output row sums
    exactly one pool row and P-1 true float zeros, which is bit-exact
    under any reduction order (``x + 0.0 == x``; a ``-0.0`` element may
    flip to ``+0.0``, which no downstream reduction can distinguish —
    scores at worst flip zero sign, and softmax maps both to the same
    weight).  Every contraction after the select uses the *same einsum
    shapes* as :func:`decode_attention`'s XLA path, so the reduction
    bracketing — and therefore the output bits — match the gather path
    exactly, keeping fold inside the paged==dense bitwise oracle.  (A
    "true" two-level fold that scores the query against all pool blocks
    and selects afterwards reduces over D in a different operand shape;
    XLA brackets that reduction differently and the scores drift by an
    ulp, so it cannot sit behind the bitwise-equivalence guarantee.)

    V is still take-gathered: the PV contraction needs it row-major and
    its gather sits on the same op as the gather path, so the folded
    variant halves the dynamic-index traffic rather than doubling the
    select matmuls.  Unassigned table entries (-1) select nothing: their
    K rows are exact zeros, then masked by ``lengths`` exactly like the
    gather path masks its garbage block-0 rows.
    """
    B, H, D = q.shape
    P, bs, K, _ = k_pool.shape
    M = block_tables.shape[1]
    W = M * bs
    onehot = ((block_tables[:, :, None] == jnp.arange(P)[None, None, :])
              & (block_tables >= 0)[:, :, None]).astype(k_pool.dtype)
    k = jnp.einsum("bmp,pskd->bmskd", onehot,
                   k_pool).reshape(B, W, K, D)   # exact one-hot select
    bt = jnp.maximum(block_tables, 0)
    v = v_pool[bt].reshape(B, W, *v_pool.shape[2:])
    valid = jnp.arange(W)[None, :] < lengths[:, None]
    return decode_attention(q, k, v, valid)


def paged_kv_view(k_pool: jax.Array, v_pool: jax.Array,
                  block_tables: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Gather a request-major dense view (B, M*bs, K, D) out of the pools.
    Unassigned table entries (-1) gather block 0; callers mask by length."""
    B, M = block_tables.shape
    bs = k_pool.shape[1]
    bt = jnp.maximum(block_tables, 0)
    k = k_pool[bt].reshape(B, M * bs, *k_pool.shape[2:])
    v = v_pool[bt].reshape(B, M * bs, *v_pool.shape[2:])
    return k, v


# ---------------------------------------------------------------------------
# The attention block (projections + rope + cache handling)
# ---------------------------------------------------------------------------

class KVCache(NamedTuple):
    """Ring-buffer KV cache.  ``window == cache width`` (full seq_len for
    full attention, sliding window for SWA archs)."""
    k: jax.Array          # (B, W, K, D)
    v: jax.Array          # (B, W, K, D)
    positions: jax.Array  # (B, W) int32, absolute position per slot, -1 = empty
    length: jax.Array     # (B,) int32 tokens seen so far


def init_kv_cache(batch: int, width: int, n_kv: int, head_dim: int,
                  dtype=jnp.bfloat16) -> KVCache:
    return KVCache(
        k=jnp.zeros((batch, width, n_kv, head_dim), dtype),
        v=jnp.zeros((batch, width, n_kv, head_dim), dtype),
        positions=jnp.full((batch, width), -1, jnp.int32),
        length=jnp.zeros((batch,), jnp.int32),
    )


class PagedKVCache(NamedTuple):
    """Block-paged KV cache: physical blocks + per-slot block tables.

    The pool (``repro.serving.kv_pool.KVBlockPool``) owns the *allocation*
    of blocks host-side; this pytree owns the *arrays*.  Position ``p`` of
    slot ``b`` lives at ``(block_tables[b, p // bs], p % bs)``; block
    tables are logical-order, so the gathered view reproduces the dense
    cache's axis layout exactly (full attention only — a paged ring for
    sliding windows is future work).
    """
    k: jax.Array             # (P, bs, K, D) physical pool
    v: jax.Array             # (P, bs, K, D)
    block_tables: jax.Array  # (B, M) int32, -1 = unassigned
    length: jax.Array        # (B,) int32 context tokens cached


def init_paged_kv_cache(batch: int, pool_blocks: int, block_size: int,
                        max_blocks: int, n_kv: int, head_dim: int,
                        dtype=jnp.bfloat16) -> PagedKVCache:
    return PagedKVCache(
        k=jnp.zeros((pool_blocks, block_size, n_kv, head_dim), dtype),
        v=jnp.zeros((pool_blocks, block_size, n_kv, head_dim), dtype),
        block_tables=jnp.full((batch, max_blocks), -1, jnp.int32),
        length=jnp.zeros((batch,), jnp.int32),
    )


class PagedRingKVCache(NamedTuple):
    """Wraparound-aware paged ring for sliding-window attention.

    The block table is *window-sized*: ``M = W // bs`` blocks cover ring
    slots, not logical positions — position ``p`` lives at ring slot
    ``p % W``, i.e. ``(block_tables[b, (p % W) // bs], (p % W) % bs)``.
    As the window slides, new tokens overwrite the slots of tokens that
    just fell out of the window, so a request holds O(window) pool
    blocks forever regardless of sequence length.

    ``positions`` mirrors the dense ring's per-slot metadata (absolute
    position, -1 = empty): the gathered ``(B, W, K, D)`` view is in
    *ring-slot order*, exactly the dense :class:`KVCache` layout, so the
    dense decode/chunk attends — and their window masks — apply
    verbatim.  That layout identity is what keeps the ring engine
    bit-identical to the dense sliding-window oracle.
    """
    k: jax.Array             # (P, bs, K, D) physical pool
    v: jax.Array             # (P, bs, K, D)
    block_tables: jax.Array  # (B, M) int32 ring-slot-order, -1 = unassigned
    positions: jax.Array     # (B, W) int32 absolute position per slot, -1 empty
    length: jax.Array        # (B,) int32 tokens seen so far


def init_paged_ring_kv_cache(batch: int, pool_blocks: int, block_size: int,
                             max_blocks: int, n_kv: int, head_dim: int,
                             dtype=jnp.bfloat16) -> PagedRingKVCache:
    return PagedRingKVCache(
        k=jnp.zeros((pool_blocks, block_size, n_kv, head_dim), dtype),
        v=jnp.zeros((pool_blocks, block_size, n_kv, head_dim), dtype),
        block_tables=jnp.full((batch, max_blocks), -1, jnp.int32),
        positions=jnp.full((batch, max_blocks * block_size), -1, jnp.int32),
        length=jnp.zeros((batch,), jnp.int32),
    )


def rollback_kv_cache(cache: KVCache, keep_len: jax.Array,
                      rows: jax.Array) -> KVCache:
    """Rewind slot rows ((B,) bool) to ``keep_len`` ((B,) int) context
    tokens: ring entries at absolute positions >= keep_len are invalidated
    and the write pointer moves back, exactly undoing the rejected-suffix
    writes of a speculative verify.  Stale K/V payloads are dead once no
    position points at them (same contract as ``reset_cache_rows``).
    Leaves may carry a leading layer axis — shapes broadcast."""
    m = rows[:, None] & (cache.positions >= keep_len[:, None])
    return cache._replace(
        positions=jnp.where(m, -1, cache.positions),
        length=jnp.where(rows, keep_len, cache.length).astype(jnp.int32))


def rollback_paged_kv_cache(cache: PagedKVCache, keep_len: jax.Array,
                            rows: jax.Array) -> PagedKVCache:
    """Paged rewind is pure metadata: truncate ``length`` and the rejected
    positions cease to exist — attention masks by length, the block table
    keeps its (logical-order) layout, and the host-side pool may then free
    strandable tail blocks (``KVBlockPool.truncate``)."""
    return cache._replace(
        length=jnp.where(rows, keep_len, cache.length).astype(jnp.int32))


def _project(p, x, name):
    w = p[name].astype(x.dtype)
    return jnp.einsum("bsd,dhk->bshk", x, w)


def _gather_heads(out: jax.Array, shard_axis: str | None,
                  axis: int) -> jax.Array:
    """Reassemble head-sharded attention output under concat-TP serving.

    Each shard attends over its local heads (a contiguous head slice —
    wq/wk/wv are column-split, so shard ``i`` computes exactly heads
    ``[i*H_loc, (i+1)*H_loc)`` of the unsharded op, bit for bit); the tiled
    all_gather concatenates the slices back to full width with no
    arithmetic.  The ``wo`` projection that follows is replicated, so its
    contraction sees identical full-width inputs on every shard — this is
    the no-cross-shard-reduction rule of ``repro.distributed.tp``."""
    if shard_axis is None:
        return out
    return jax.lax.all_gather(out, shard_axis, axis=axis, tiled=True)


def attention_block(p: dict[str, jax.Array], x: jax.Array, *,
                    cfg, causal: bool = True, positions: jax.Array | None = None,
                    kv: tuple[jax.Array, jax.Array] | None = None,
                    use_chunked: bool | None = None,
                    window: int | None = None,
                    rope_theta: float | None = None) -> jax.Array:
    """Training/prefill attention over a whole sequence.

    x: (B,S,d).  ``kv`` overrides K/V inputs (cross-attention).
    ``window``/``rope_theta`` override the config's stack-wide values for
    one layer of a heterogeneous (layer-pattern) stack; None keeps the
    homogeneous behavior.  Both are static Python values — the masks
    branch on them at trace time.
    """
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    if window is None:
        window = cfg.sliding_window
    if rope_theta is None:
        rope_theta = cfg.rope_theta
    q = _project(p, x, "wq")
    if kv is None:
        k = _project(p, x, "wk")
        v = _project(p, x, "wv")
    else:
        k, v = kv
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"]) if kv is None else k
    if positions is None:
        positions = jnp.arange(S)[None, :]
    if kv is None and cfg.rope_fraction > 0:
        inv = rope_frequencies(hd, cfg.rope_fraction, rope_theta)
        q = apply_rope(q, positions, inv)
        k = apply_rope(k, positions, inv)
    if use_chunked is None:
        use_chunked = S > 2048
    if use_chunked and kv is None:
        out = chunked_attention(q, k, v, causal=causal,
                                window=window)
    else:
        out = full_attention(q, k, v, causal=causal and kv is None,
                             window=window if kv is None else 0)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))


def attention_decode_block(p: dict[str, jax.Array], x: jax.Array,
                           cache: KVCache, *, cfg,
                           cross_kv: tuple[jax.Array, jax.Array] | None = None,
                           dense_backend: str = "xla",
                           paged_backend: str = "gather",
                           ring_backend: str = "gather",
                           live: jax.Array | None = None,
                           shard_axis: str | None = None,
                           window: int | None = None,
                           rope_theta: float | None = None
                           ) -> tuple[jax.Array, KVCache]:
    """One decode step.  x: (B, 1, d).  Updates the ring-buffer (or paged)
    cache.

    ``shard_axis`` (concat-TP serving): params arrive head-column-sharded
    and the cache kv-head-sharded; attention runs over the local heads and
    :func:`_gather_heads` concatenates before the replicated ``wo``.

    RoPE is applied at *write* time (k cached post-rotation, standard decode
    practice): absolute-position rotation of both q and k preserves the
    relative property, so the ring buffer never needs re-rotation.

    ``dense_backend`` / ``paged_backend`` are the ``decode_dense`` /
    ``decode_paged`` sites of a ``KernelPlan`` — whichever matches the
    cache type dispatches; cross-attention always decodes dense.

    ``live`` ((B,) bool) only matters for a :class:`PagedKVCache`: dead
    rows' pool writes are dropped and their lengths frozen (the dense path
    lets the caller restore old rows wholesale instead — a paged pool is
    shared across rows, so the mask must act at the scatter).

    ``window``/``rope_theta`` override the config for one layer of a
    heterogeneous stack (static trace-time values); None keeps the
    stack-wide ``cfg.sliding_window``/``cfg.rope_theta``.
    """
    B, _, _ = x.shape
    hd = cfg.resolved_head_dim
    if window is None:
        window = cfg.sliding_window
    if rope_theta is None:
        rope_theta = cfg.rope_theta
    pos = cache.length  # (B,) position of the new token

    q = _project(p, x, "wq")[:, 0]            # (B, H, D)
    if cross_kv is not None:
        # cross-attention: cache holds the (static) encoder K/V — no update
        if cfg.qk_norm:
            q = rms_norm(q, p["q_norm"])
        k_c, v_c = cross_kv
        valid = jnp.ones(k_c.shape[:2], bool)
        out = decode_attention(q, k_c, v_c, valid, dense_backend)
        out = _gather_heads(out, shard_axis, axis=1)
        return jnp.einsum("bhk,hkd->bd", out, p["wo"].astype(x.dtype))[:, None], cache

    k_new = _project(p, x, "wk")[:, 0]         # (B, K, D)
    v_new = _project(p, x, "wv")[:, 0]
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"])
        k_new = rms_norm(k_new, p["k_norm"])
    if cfg.rope_fraction > 0:
        inv = rope_frequencies(hd, cfg.rope_fraction, rope_theta)
        q = apply_rope(q[:, None], pos[:, None], inv)[:, 0]
        k_new = apply_rope(k_new[:, None], pos[:, None], inv)[:, 0]

    if isinstance(cache, PagedKVCache):
        y, new_cache = _paged_decode_write_attend(
            q, k_new, v_new, cache, live=live, backend=paged_backend)
        y = _gather_heads(y, shard_axis, axis=1)
        return jnp.einsum("bhk,hkd->bd", y,
                          p["wo"].astype(x.dtype))[:, None], new_cache

    if isinstance(cache, PagedRingKVCache):
        y, new_cache = _ring_decode_write_attend(
            q, k_new, v_new, cache, window=window, live=live,
            dense_backend=dense_backend, backend=ring_backend)
        y = _gather_heads(y, shard_axis, axis=1)
        return jnp.einsum("bhk,hkd->bd", y,
                          p["wo"].astype(x.dtype))[:, None], new_cache

    W = cache.k.shape[1]
    slot = (pos % W).astype(jnp.int32)         # ring-buffer write index
    bidx = jnp.arange(B)
    k_cache = cache.k.at[bidx, slot].set(k_new.astype(cache.k.dtype))
    v_cache = cache.v.at[bidx, slot].set(v_new.astype(cache.v.dtype))
    positions = cache.positions.at[bidx, slot].set(pos)
    # valid slots: written, and within the sliding window if one is set
    valid = positions >= 0
    if window:
        valid &= positions > (pos[:, None] - window)
    out = decode_attention(q, k_cache, v_cache, valid, dense_backend)
    out = _gather_heads(out, shard_axis, axis=1)
    new_cache = KVCache(k=k_cache, v=v_cache, positions=positions,
                        length=cache.length + 1)
    y = jnp.einsum("bhk,hkd->bd", out, p["wo"].astype(x.dtype))
    return y[:, None], new_cache


def _paged_decode_write_attend(q: jax.Array, k_new: jax.Array,
                               v_new: jax.Array, cache: PagedKVCache, *,
                               live: jax.Array | None,
                               backend: str = "gather"
                               ) -> tuple[jax.Array, PagedKVCache]:
    """Scatter one token's K/V into the pool and attend over the pages.

    Live rows write at ``(block_tables[b, pos//bs], pos % bs)``; dead rows
    route to an out-of-bounds block index and the scatter drops them
    (``mode="drop"``), so bystanders never touch shared physical blocks.
    """
    B = q.shape[0]
    P, bs = cache.k.shape[0], cache.k.shape[1]
    M = cache.block_tables.shape[1]
    pos = cache.length
    if live is None:
        live = jnp.ones((B,), bool)
    bidx = jnp.arange(B)
    blk = cache.block_tables[bidx, jnp.clip(pos // bs, 0, M - 1)]
    ok = live & (blk >= 0) & (pos < M * bs)
    safe_blk = jnp.where(ok, blk, P)           # P = out of bounds -> dropped
    off = (pos % bs).astype(jnp.int32)
    k_pool = cache.k.at[safe_blk, off].set(
        k_new.astype(cache.k.dtype), mode="drop")
    v_pool = cache.v.at[safe_blk, off].set(
        v_new.astype(cache.v.dtype), mode="drop")
    new_len = jnp.where(ok, pos + 1, pos).astype(jnp.int32)
    # the kernel walks each row's live pages: a row left out of this step
    # walks none (its output is discarded); gather/fold keep their bits
    attend_len = jnp.where(live, new_len, 0) if backend == "pallas" \
        else new_len
    out = decode_attention_paged(q, k_pool, v_pool, cache.block_tables,
                                 attend_len, backend)
    return out, PagedKVCache(k=k_pool, v=v_pool,
                             block_tables=cache.block_tables, length=new_len)


def _ring_decode_write_attend(q: jax.Array, k_new: jax.Array,
                              v_new: jax.Array, cache: PagedRingKVCache, *,
                              window: int, live: jax.Array | None,
                              dense_backend: str = "xla",
                              backend: str = "gather"
                              ) -> tuple[jax.Array, PagedRingKVCache]:
    """Scatter one token into the ring pool and attend over the window.

    The write lands at ring slot ``pos % W`` — past the window, that slot
    belongs to the token ``W`` positions back, which just slid out: the
    overwrite *is* the "oldest block frees as the window slides" step, at
    token granularity within the request's fixed block lease.  Dead rows
    (and rows with no lease yet) scatter out of bounds and drop, same as
    the classic paged pool.  The attend mask is the dense ring's
    (written ``&`` inside the window), over the gathered ring-slot-order
    view, so outputs match the dense sliding-window engine bit for bit.
    """
    if backend != "gather":
        raise ValueError(f"unknown decode_ring backend {backend!r}")
    B = q.shape[0]
    P, bs = cache.k.shape[0], cache.k.shape[1]
    M = cache.block_tables.shape[1]
    W = M * bs
    pos = cache.length
    if live is None:
        live = jnp.ones((B,), bool)
    bidx = jnp.arange(B)
    slot = (pos % W).astype(jnp.int32)
    blk = cache.block_tables[bidx, slot // bs]
    ok = live & (blk >= 0)                     # the ring wraps by design
    safe_blk = jnp.where(ok, blk, P)           # P = out of bounds -> dropped
    off = (slot % bs).astype(jnp.int32)
    k_pool = cache.k.at[safe_blk, off].set(
        k_new.astype(cache.k.dtype), mode="drop")
    v_pool = cache.v.at[safe_blk, off].set(
        v_new.astype(cache.v.dtype), mode="drop")
    positions = cache.positions.at[bidx, slot].set(
        jnp.where(ok, pos, cache.positions[bidx, slot]))
    new_len = jnp.where(ok, pos + 1, pos).astype(jnp.int32)
    k_cache, v_cache = paged_kv_view(k_pool, v_pool, cache.block_tables)
    valid = positions >= 0
    if window:
        valid &= positions > (pos[:, None] - window)
    out = decode_attention(q, k_cache, v_cache, valid, dense_backend)
    return out, PagedRingKVCache(k=k_pool, v=v_pool,
                                 block_tables=cache.block_tables,
                                 positions=positions, length=new_len)


def prefill_into_cache(p: dict[str, jax.Array], x: jax.Array, cache: KVCache,
                       *, cfg, lengths: jax.Array | None = None,
                       window: int | None = None,
                       rope_theta: float | None = None
                       ) -> tuple[jax.Array, KVCache]:
    """Prefill: run full-sequence attention AND populate the cache.

    Used by prefill_32k.  For a sliding-window cache (W < S) only the last W
    positions land in the ring buffer.

    ``lengths`` (B,) enables a right-padded multi-sequence batch: positions
    at or beyond a row's length are recorded as empty (-1) and the cache
    length is per-row, so each slot decodes from its own prompt end.  Padded
    keys sit *after* every valid query position, so causal masking already
    keeps them out of the prefill attention itself.
    """
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    W = cache.k.shape[1]
    if window is None:
        window = cfg.sliding_window
    if rope_theta is None:
        rope_theta = cfg.rope_theta
    q = _project(p, x, "wq")
    k = _project(p, x, "wk")
    v = _project(p, x, "wv")
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    positions = jnp.arange(S)[None, :]
    if cfg.rope_fraction > 0:
        inv = rope_frequencies(hd, cfg.rope_fraction, rope_theta)
        q = apply_rope(q, positions, inv)
        k = apply_rope(k, positions, inv)
    out = (chunked_attention if S > 2048 else full_attention)(
        q, k, v, causal=True, window=window)
    # write the last min(W, S) positions into the ring buffer at their slots
    take = min(W, S)
    tail_pos = jnp.arange(S - take, S)
    slots = tail_pos % W
    k_cache = cache.k.at[:, slots].set(k[:, S - take:].astype(cache.k.dtype))
    v_cache = cache.v.at[:, slots].set(v[:, S - take:].astype(cache.v.dtype))
    written = jnp.broadcast_to(tail_pos, (B, take))
    if lengths is not None:
        written = jnp.where(written < lengths[:, None], written, -1)
    positions_c = cache.positions.at[:, slots].set(written)
    length = (jnp.full((B,), S, jnp.int32) if lengths is None
              else lengths.astype(jnp.int32))
    new_cache = KVCache(k=k_cache, v=v_cache, positions=positions_c,
                        length=length)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return y, new_cache


def _chunk_qkv(p: dict[str, jax.Array], x: jax.Array, *, cfg,
               offsets: jax.Array, rope_theta: float | None = None):
    """Shared chunk-prefill front half: q/k/v projections, qk-norm and
    RoPE at the rows' absolute positions.  One body for the ring-buffer
    and paged variants — the K/V bits a chunk writes must not depend on
    which cache layout receives them."""
    B, C, _ = x.shape
    hd = cfg.resolved_head_dim
    if rope_theta is None:
        rope_theta = cfg.rope_theta
    q = _project(p, x, "wq")                    # (B, C, H, D)
    k_new = _project(p, x, "wk")                # (B, C, K, D)
    v_new = _project(p, x, "wv")
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"])
        k_new = rms_norm(k_new, p["k_norm"])
    pos = offsets[:, None] + jnp.arange(C)[None, :]          # (B, C)
    if cfg.rope_fraction > 0:
        inv = rope_frequencies(hd, cfg.rope_fraction, rope_theta)
        q = apply_rope(q, pos, inv)
        k_new = apply_rope(k_new, pos, inv)
    return q, k_new, v_new, pos


def _chunk_attend(p: dict[str, jax.Array], q: jax.Array, k_cache: jax.Array,
                  v_cache: jax.Array, attend: jax.Array,
                  dtype, shard_axis: str | None = None) -> jax.Array:
    """Shared chunk-prefill back half: chunk queries over the whole
    (just-updated) cache view, masked per row by ``attend`` (B, C, W),
    then the output projection."""
    B, C, H, hd = q.shape
    K = k_cache.shape[2]
    G = H // K
    qg = q.reshape(B, C, K, G, hd)
    s = jnp.einsum("bckgd,bwkd->bkgcw", qg, k_cache).astype(jnp.float32) \
        / np.sqrt(hd)
    s = jnp.where(attend[:, None, None, :, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1).astype(v_cache.dtype)
    out = jnp.einsum("bkgcw,bwkd->bckgd", w, v_cache).reshape(B, C, H, hd)
    out = _gather_heads(out, shard_axis, axis=2)
    return jnp.einsum("bchk,hkd->bcd", out, p["wo"].astype(dtype))


def prefill_chunk_into_cache(p: dict[str, jax.Array], x: jax.Array,
                             cache: KVCache, *, cfg, offsets: jax.Array,
                             n_new: jax.Array,
                             shard_axis: str | None = None,
                             window: int | None = None,
                             rope_theta: float | None = None
                             ) -> tuple[jax.Array, KVCache]:
    """Chunked prefill: extend the cache by up to C prompt tokens per row.

    x: (B, C, d) — the next prompt chunk per row, right-padded.
    offsets: (B,) int32 — tokens already in each row's cache (its length).
    n_new: (B,) int32 in [0, C] — valid tokens this chunk; rows with 0 are
    bystanders (mid-decode or idle slots) and their cache is untouched.

    Chunk queries attend to everything the row has cached so far *plus* the
    chunk itself (written first), with per-slot position masking — the same
    ring-buffer discipline as decode, vectorized over C query positions.
    This is what lets a long prompt interleave with decode steps instead of
    stalling the whole batch behind a monolithic prefill.
    """
    B, C, _ = x.shape
    W = cache.k.shape[1]
    if window is None:
        window = cfg.sliding_window
    q, k_new, v_new, pos = _chunk_qkv(p, x, cfg=cfg, offsets=offsets,
                                      rope_theta=rope_theta)

    # masked ring-buffer write: padded/bystander entries write back the old
    # value, so the scatter is a no-op exactly where n_new says it must be
    valid_new = jnp.arange(C)[None, :] < n_new[:, None]      # (B, C)
    slot = (pos % W).astype(jnp.int32)
    bidx = jnp.arange(B)[:, None]
    old_k = cache.k[bidx, slot]
    old_v = cache.v[bidx, slot]
    sel = valid_new[..., None, None]
    k_cache = cache.k.at[bidx, slot].set(
        jnp.where(sel, k_new.astype(cache.k.dtype), old_k))
    v_cache = cache.v.at[bidx, slot].set(
        jnp.where(sel, v_new.astype(cache.v.dtype), old_v))
    positions = cache.positions.at[bidx, slot].set(
        jnp.where(valid_new, pos, cache.positions[bidx, slot]))
    length = jnp.where(n_new > 0, offsets + n_new, cache.length) \
        .astype(jnp.int32)

    attend = (positions[:, None, :] >= 0) \
        & (positions[:, None, :] <= pos[:, :, None])         # (B, C, W)
    if window:
        attend &= positions[:, None, :] > pos[:, :, None] - window
    y = _chunk_attend(p, q, k_cache, v_cache, attend, x.dtype, shard_axis)
    new_cache = KVCache(k=k_cache, v=v_cache, positions=positions,
                        length=length)
    return y, new_cache


def prefill_chunk_into_paged_cache(p: dict[str, jax.Array], x: jax.Array,
                                   cache: PagedKVCache, *, cfg,
                                   offsets: jax.Array, n_new: jax.Array,
                                   shard_axis: str | None = None,
                                   window: int | None = None,
                                   rope_theta: float | None = None
                                   ) -> tuple[jax.Array, PagedKVCache]:
    """Chunked prefill against a block-paged cache.

    Same contract as :func:`prefill_chunk_into_cache` — x: (B, C, d)
    right-padded chunk per row, ``offsets`` tokens already cached,
    ``n_new`` valid tokens (0 = bystander, untouched) — but K/V land in
    pool blocks through the row's block table instead of a private ring
    row.  The chunk only ever writes *private* blocks: shared prefix
    blocks sit below ``offsets`` by construction (the engine starts the
    prefill at the shared-prefix boundary), and padded/bystander positions
    scatter out of bounds and are dropped.  Masks reproduce the dense
    function's exactly (position ``p`` at axis index ``p``), keeping the
    paged engine bit-identical to the dense oracle.
    """
    B, C, _ = x.shape
    P, bs = cache.k.shape[0], cache.k.shape[1]
    M = cache.block_tables.shape[1]
    if window:
        raise ValueError("classic paged chunks attend the full context; "
                         "sliding layers take the ring variant")
    q, k_new, v_new, pos = _chunk_qkv(p, x, cfg=cfg, offsets=offsets,
                                      rope_theta=rope_theta)

    # block-table scatter: (row, chunk position) -> (physical block, offset)
    valid_new = jnp.arange(C)[None, :] < n_new[:, None]      # (B, C)
    blk = jnp.take_along_axis(cache.block_tables,
                              jnp.clip(pos // bs, 0, M - 1), axis=1)
    ok = valid_new & (blk >= 0) & (pos < M * bs)
    safe_blk = jnp.where(ok, blk, P)           # P = out of bounds -> dropped
    off = (pos % bs).astype(jnp.int32)
    k_pool = cache.k.at[safe_blk, off].set(
        k_new.astype(cache.k.dtype), mode="drop")
    v_pool = cache.v.at[safe_blk, off].set(
        v_new.astype(cache.v.dtype), mode="drop")
    length = jnp.where(n_new > 0, offsets + n_new, cache.length) \
        .astype(jnp.int32)

    # chunk queries over the gathered page view, masked like the dense
    # path: position k is attendable iff written (< the row's new length)
    # and causally visible (<= the query's position)
    k_cache, v_cache = paged_kv_view(k_pool, v_pool, cache.block_tables)
    pos_k = jnp.arange(k_cache.shape[1])[None, None, :]      # (1, 1, W)
    attend = (pos_k < length[:, None, None]) \
        & (pos_k <= pos[:, :, None])                         # (B, C, W)
    y = _chunk_attend(p, q, k_cache, v_cache, attend, x.dtype, shard_axis)
    new_cache = PagedKVCache(k=k_pool, v=v_pool,
                             block_tables=cache.block_tables, length=length)
    return y, new_cache


def prefill_chunk_into_ring_cache(p: dict[str, jax.Array], x: jax.Array,
                                  cache: PagedRingKVCache, *, cfg,
                                  offsets: jax.Array, n_new: jax.Array,
                                  shard_axis: str | None = None,
                                  window: int | None = None,
                                  rope_theta: float | None = None
                                  ) -> tuple[jax.Array, PagedRingKVCache]:
    """Chunked prefill against the wraparound ring pool.

    Same contract as :func:`prefill_chunk_into_cache`; K/V land at ring
    slot ``pos % W`` through the window-sized block table.  A prompt
    longer than the window simply laps the ring — earlier slots are
    overwritten by the positions that displace them, and the per-slot
    ``positions`` metadata plus the dense window mask keep exactly the
    last ``window`` tokens attendable, matching the dense sliding ring
    bit for bit.
    """
    B, C, _ = x.shape
    P, bs = cache.k.shape[0], cache.k.shape[1]
    M = cache.block_tables.shape[1]
    W = M * bs
    if window is None:
        window = cfg.sliding_window
    q, k_new, v_new, pos = _chunk_qkv(p, x, cfg=cfg, offsets=offsets,
                                      rope_theta=rope_theta)

    valid_new = jnp.arange(C)[None, :] < n_new[:, None]      # (B, C)
    slot = (pos % W).astype(jnp.int32)
    blk = jnp.take_along_axis(cache.block_tables, slot // bs, axis=1)
    ok = valid_new & (blk >= 0)
    safe_blk = jnp.where(ok, blk, P)           # P = out of bounds -> dropped
    off = (slot % bs).astype(jnp.int32)
    k_pool = cache.k.at[safe_blk, off].set(
        k_new.astype(cache.k.dtype), mode="drop")
    v_pool = cache.v.at[safe_blk, off].set(
        v_new.astype(cache.v.dtype), mode="drop")
    bidx = jnp.arange(B)[:, None]
    positions = cache.positions.at[bidx, slot].set(
        jnp.where(ok, pos, cache.positions[bidx, slot]))
    length = jnp.where(n_new > 0, offsets + n_new, cache.length) \
        .astype(jnp.int32)

    # dense-ring attend mask over the ring-slot-order view: written,
    # causally visible, and inside the sliding window
    k_cache, v_cache = paged_kv_view(k_pool, v_pool, cache.block_tables)
    attend = (positions[:, None, :] >= 0) \
        & (positions[:, None, :] <= pos[:, :, None])         # (B, C, W)
    if window:
        attend &= positions[:, None, :] > pos[:, :, None] - window
    y = _chunk_attend(p, q, k_cache, v_cache, attend, x.dtype, shard_axis)
    new_cache = PagedRingKVCache(k=k_pool, v=v_pool,
                                 block_tables=cache.block_tables,
                                 positions=positions, length=length)
    return y, new_cache
