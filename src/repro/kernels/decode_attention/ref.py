"""Pure-jnp oracle for GQA decode attention (mirrors models.attention)."""
import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30


def gqa_decode_ref(q, k_cache, v_cache, valid):
    B, H, D = q.shape
    K = k_cache.shape[2]
    G = H // K
    qg = q.reshape(B, K, G, D)
    s = jnp.einsum("bkgd,bwkd->bkgw", qg, k_cache).astype(jnp.float32) / np.sqrt(D)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1).astype(v_cache.dtype)
    out = jnp.einsum("bkgw,bwkd->bkgd", w, v_cache)
    return out.reshape(B, H, D).astype(q.dtype)


def gqa_decode_paged_ref(q, k_pool, v_pool, block_tables, lengths):
    """Paged oracle: gather the pages into a dense per-request view, then
    run the dense oracle with a length mask.  A row of length 0 attends to
    nothing and reads zeros, as the kernel does."""
    B, M = block_tables.shape
    bs = k_pool.shape[1]
    bt = jnp.maximum(block_tables, 0)
    k = k_pool[bt].reshape(B, M * bs, *k_pool.shape[2:])
    v = v_pool[bt].reshape(B, M * bs, *v_pool.shape[2:])
    valid = jnp.arange(M * bs)[None, :] < lengths[:, None]
    out = gqa_decode_ref(q, k, v, valid)
    return jnp.where(lengths[:, None, None] > 0, out, 0).astype(out.dtype)
