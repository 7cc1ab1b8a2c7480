"""Paged-vs-dense attention parity at the model level.

The serving-equivalence fuzz harness (test_serving_fuzz.py) proves the
*engines* agree; these tests pin the property it rests on — the paged
gather produces **bit-identical** logits to the dense ring buffer on the
same dispatch shapes — across GQA group counts, partial-RoPE and qk-norm
configs, for both chunked prefill and decode, including the Pallas kernel
path in interpret mode."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.models import attention as A
from repro.models.model import Model

BS, M = 8, 4                    # block size, table width
MAX_LEN = BS * M


def _cfg(n_heads=4, n_kv=2, rope_fraction=1.0, qk_norm=False):
    return ModelConfig(
        name=f"paged-tiny-h{n_heads}k{n_kv}r{rope_fraction}q{int(qk_norm)}",
        family="dense", n_layers=2, d_model=64, vocab=96, n_heads=n_heads,
        n_kv_heads=n_kv, d_ff=128, rope_fraction=rope_fraction,
        qk_norm=qk_norm, dtype="float32", param_dtype="float32")


def _paged_with_tables(m, slots, tables):
    caches = m.init_paged_caches(slots, pool_blocks=slots * M + 2,
                                 block_size=BS, max_blocks=M)
    bt = jnp.broadcast_to(jnp.asarray(tables, jnp.int32),
                          (m.cfg.n_layers, slots, M))
    return caches._replace(kv=caches.kv._replace(block_tables=bt))


@pytest.mark.parametrize("n_heads,n_kv", [(4, 1), (4, 2), (8, 8)])
@pytest.mark.parametrize("rope_fraction,qk_norm",
                         [(1.0, False), (0.5, True)])
def test_paged_matches_dense_bitwise(n_heads, n_kv, rope_fraction, qk_norm):
    """Chunked prefill + decode through the full model: identical bits
    from the paged and dense cache layouts, with shuffled block tables and
    a bystander slot riding along."""
    cfg = _cfg(n_heads, n_kv, rope_fraction, qk_norm)
    m = Model(cfg)
    params = m.init(jax.random.key(0))
    rng = np.random.default_rng(3)
    slots = 2
    prompt = rng.integers(0, cfg.vocab, 11).astype(np.int32)

    dense = m.init_caches(slots, MAX_LEN)
    tables = np.full((slots, M), -1, np.int32)
    tables[0] = rng.permutation(slots * M + 2)[:M]  # shuffled physical ids
    paged = _paged_with_tables(m, slots, tables)

    C, off = 4, 0
    logits_d = logits_p = None
    for start in range(0, len(prompt), C):
        n = min(C, len(prompt) - start)
        chunk = np.zeros((slots, C), np.int32)
        chunk[0, :n] = prompt[start:start + n]
        nn = np.zeros((slots,), np.int32)
        nn[0] = n
        offs = np.asarray([off, 0], np.int32)
        logits_d, dense = m.prefill_chunk(
            params, dense, jnp.asarray(chunk), jnp.asarray(offs),
            jnp.asarray(nn))
        logits_p, paged = m.prefill_chunk(
            params, paged, jnp.asarray(chunk), jnp.asarray(offs),
            jnp.asarray(nn))
        off += n
    np.testing.assert_array_equal(np.asarray(logits_d[0]),
                                  np.asarray(logits_p[0]))

    live = jnp.asarray([True, False])
    t = int(jnp.argmax(logits_d[0, :cfg.vocab]))
    for _ in range(6):
        toks = jnp.asarray([[t], [0]], jnp.int32)
        logits_d, dense = m.serve_step(params, dense, toks, live=live)
        logits_p, paged = m.serve_step(params, paged, toks, live=live)
        np.testing.assert_array_equal(np.asarray(logits_d[0]),
                                      np.asarray(logits_p[0]))
        t = int(jnp.argmax(logits_d[0, :cfg.vocab]))
    # bystander slot untouched: no length advance, no block writes
    assert int(paged.kv.length[0, 1]) == 0


@pytest.mark.parametrize("n_heads,n_kv", [(4, 2), (8, 2)])
@pytest.mark.parametrize("rope_fraction", [1.0, 0.5])
def test_paged_decode_block_pallas_interpret(n_heads, n_kv, rope_fraction):
    """attention_decode_block over a PagedKVCache with the pallas paged
    backend (interpret mode on CPU) matches the pure-jnp gather path."""
    cfg = _cfg(n_heads, n_kv, rope_fraction)
    hd = cfg.resolved_head_dim
    rng = np.random.default_rng(5)
    B = 2
    p = {k: jnp.asarray(rng.normal(size=s.shape) * 0.2, jnp.float32)
         for k, s in A.attention_specs(cfg.d_model, n_heads, n_kv, hd,
                                       False).items()}
    lengths = np.asarray([13, 5], np.int32)
    tables = np.stack([rng.permutation(2 * M)[:M] for _ in range(B)])
    kv = A.PagedKVCache(
        k=jnp.asarray(rng.normal(size=(2 * M, BS, n_kv, hd)), jnp.float32),
        v=jnp.asarray(rng.normal(size=(2 * M, BS, n_kv, hd)), jnp.float32),
        block_tables=jnp.asarray(tables, jnp.int32),
        length=jnp.asarray(lengths))
    x = jnp.asarray(rng.normal(size=(B, 1, cfg.d_model)), jnp.float32)
    y_ref, kv_ref = A.attention_decode_block(p, x, kv, cfg=cfg,
                                             paged_backend="gather")
    y_pl, kv_pl = A.attention_decode_block(p, x, kv, cfg=cfg,
                                           paged_backend="pallas")
    np.testing.assert_allclose(np.asarray(y_pl), np.asarray(y_ref),
                               rtol=3e-5, atol=3e-5)
    np.testing.assert_array_equal(np.asarray(kv_pl.length),
                                  np.asarray(kv_ref.length))
    np.testing.assert_array_equal(np.asarray(kv_pl.k), np.asarray(kv_ref.k))


def test_paged_rejects_unsupported_families():
    from repro.configs.base import all_configs
    ssm = Model(all_configs()["mamba2-370m"].reduced())
    with pytest.raises(NotImplementedError, match="attention-only"):
        ssm.init_paged_caches(2, pool_blocks=8, block_size=8, max_blocks=4)
    # sliding-window stacks are no longer rejected: they get the
    # wraparound ring pool (window-sized block tables) instead of the
    # classic logical-order pool
    swa = Model(dataclasses.replace(_cfg(), sliding_window=16))
    caches = swa.init_paged_caches(2, pool_blocks=8, block_size=8,
                                   max_blocks=4)
    assert isinstance(caches.kv, A.PagedRingKVCache)
    assert caches.kv.block_tables.shape == (swa.cfg.n_layers, 2, 4)


def test_paged_pallas_walks_dead_rows_as_empty():
    """``_paged_decode_write_attend`` hands the Pallas kernel length 0 for
    a row left out of the step: that row reads nothing (zeros), and the
    live rows' outputs are the same bits as with every row live."""
    K, hd, B = 2, 16, 3
    rng = np.random.default_rng(9)
    kv = A.PagedKVCache(
        k=jnp.asarray(rng.normal(size=(B * M, BS, K, hd)), jnp.float32),
        v=jnp.asarray(rng.normal(size=(B * M, BS, K, hd)), jnp.float32),
        block_tables=jnp.asarray(rng.permutation(B * M).reshape(B, M),
                                 jnp.int32),
        length=jnp.asarray([13, 20, 5], jnp.int32))
    q = jnp.asarray(rng.normal(size=(B, 2 * K, hd)), jnp.float32)
    k_new, v_new = (jnp.asarray(rng.normal(size=(B, K, hd)), jnp.float32)
                    for _ in range(2))

    def attend(live, backend):
        out, _ = A._paged_decode_write_attend(
            q, k_new, v_new, kv, live=jnp.asarray(live), backend=backend)
        return np.asarray(out)

    some = attend([True, False, True], "pallas")
    every = attend([True, True, True], "pallas")
    np.testing.assert_array_equal(some[[0, 2]], every[[0, 2]])
    assert not some[1].any()
    np.testing.assert_allclose(some[[0, 2]],
                               attend([True, False, True], "gather")[[0, 2]],
                               rtol=3e-5, atol=3e-5)


def test_paged_engine_pallas_tokens_match_gather():
    """A paged engine whose rows come and go (prompts of 3-17 tokens in
    two slots, so rows prefill, decode and sit empty on different ticks)
    emits the same greedy tokens under the interpreted Pallas kernel as
    under the gather path."""
    from repro.core.pipeline import KernelPlan
    from repro.serving import Request, ServingEngine
    cfg = _cfg(4, 2)
    m = Model(cfg)
    params = m.init(jax.random.key(1))
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, cfg.vocab, n).astype(np.int32)
               for n in (17, 3, 9)]

    def serve(backend):
        eng = ServingEngine(m, params, slots=2, max_len=MAX_LEN, chunk=4,
                            prefill_mode="chunked", kv="paged",
                            kv_block_size=BS, kv_pool_blocks=2 * M,
                            kernel_plan=KernelPlan(decode_paged=backend))
        reqs = [Request(rid=i, prompt=p.copy(), max_new_tokens=6)
                for i, p in enumerate(prompts)]
        for r in reqs:
            eng.submit(r)
        for _ in range(200):
            if not eng.scheduler.pending():
                break
            eng.step()
        assert all(r.done for r in reqs)
        assert eng.stats()["kernel_plan"]["decode_paged"] == backend
        return [list(r.generated) for r in reqs]

    assert serve("pallas") == serve("gather")
