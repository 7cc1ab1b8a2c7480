"""Per-kernel allclose vs. the pure-jnp oracles: shape/dtype sweeps +
hypothesis property tests (interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip(
    "hypothesis", reason="property tests need the optional hypothesis extra")
from hypothesis import given, settings, strategies as st

from repro.kernels.decode_attention import ops as da_ops, ref as da_ref
from repro.kernels.linked_cbr_pool import ops as cb_ops, ref as cb_ref
from repro.kernels.linked_matmul import ops as lm_ops, ref as lm_ref
from repro.kernels.split_matmul import ops as sm_ops, ref as sm_ref

RNG = np.random.default_rng(42)


def _arr(shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(RNG.normal(size=shape) * scale, dtype)


TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


@pytest.mark.parametrize("M,d,ff", [(128, 128, 256), (256, 64, 512),
                                    (512, 256, 1024), (64, 32, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_linked_matmul_sweep(M, d, ff, dtype):
    x = _arr((M, d), dtype)
    wg, wu = _arr((d, ff), dtype, 0.05), _arr((d, ff), dtype, 0.05)
    wd = _arr((ff, d), dtype, 0.05)
    out = lm_ops.linked_mlp(x, wg, wu, wd, block_m=64, block_ff=128)
    ref = lm_ref.linked_mlp_ref(x, wg, wu, wd)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **TOL[dtype])


@pytest.mark.parametrize("M,K,N,bm,bn,bk", [
    (128, 256, 512, 64, 128, 128), (64, 64, 64, 64, 64, 64),
    (256, 1024, 256, 128, 256, 256)])
def test_split_matmul_sweep(M, K, N, bm, bn, bk):
    x, w, b = _arr((M, K)), _arr((K, N), scale=0.05), _arr((N,))
    out = sm_ops.split_matmul(x, w, b, block_m=bm, block_n=bn, block_k=bk)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(sm_ref.split_matmul_ref(x, w, b)),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("N,H,W,C,OC", [(1, 8, 8, 16, 32), (2, 16, 16, 32, 64),
                                        (1, 4, 32, 8, 8)])
def test_cbr_avgpool_sweep(N, H, W, C, OC):
    x, w, b = _arr((N, H, W, C)), _arr((C, OC), scale=0.1), _arr((OC,))
    out = cb_ops.cbr_avgpool(x, w, b)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(cb_ref.cbr_avgpool_ref(x, w, b)),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("B,H,K,D,W,bw", [
    (1, 4, 1, 64, 256, 128), (2, 8, 2, 64, 1024, 256),
    (2, 8, 8, 128, 512, 512), (1, 16, 4, 32, 2048, 1024)])
def test_gqa_decode_sweep(B, H, K, D, W, bw):
    q = _arr((B, H, D))
    kc, vc = _arr((B, W, K, D)), _arr((B, W, K, D))
    valid = jnp.asarray(RNG.random((B, W)) < 0.7)
    valid = valid.at[:, 0].set(True)  # at least one live slot
    out = da_ops.gqa_decode(q, kc, vc, valid, block_w=bw)
    ref = da_ref.gqa_decode_ref(q, kc, vc, valid)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-5, atol=3e-5)


@given(m=st.sampled_from([64, 128, 192]), ff=st.sampled_from([128, 256]),
       d=st.sampled_from([32, 64]), seed=st.integers(0, 2**16))
@settings(max_examples=12, deadline=None)
def test_linked_matmul_property(m, ff, d, seed):
    r = np.random.default_rng(seed)
    x = jnp.asarray(r.normal(size=(m, d)), jnp.float32)
    wg = jnp.asarray(r.normal(size=(d, ff)) * 0.1, jnp.float32)
    wu = jnp.asarray(r.normal(size=(d, ff)) * 0.1, jnp.float32)
    wd = jnp.asarray(r.normal(size=(ff, d)) * 0.1, jnp.float32)
    out = lm_ops.linked_mlp(x, wg, wu, wd, block_m=64, block_ff=128)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(lm_ref.linked_mlp_ref(x, wg, wu, wd)),
                               rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("B,H,K,D,bs,M", [
    (1, 4, 1, 64, 16, 4), (2, 8, 2, 64, 8, 8),
    (2, 8, 8, 128, 32, 2), (3, 16, 4, 32, 8, 4)])
def test_gqa_decode_paged_sweep(B, H, K, D, bs, M):
    """Paged flash-decode (scalar-prefetched block tables) vs the gather
    oracle, across GQA group counts H/K and page geometries."""
    P = B * M + 3
    q = _arr((B, H, D))
    kp, vp = _arr((P, bs, K, D)), _arr((P, bs, K, D))
    perm = RNG.permutation(P)
    bt = np.full((B, M), -1, np.int32)
    lengths = np.asarray(
        [int(RNG.integers(1, M * bs + 1)) for _ in range(B)], np.int32)
    idx = 0
    for b in range(B):
        for m in range(-(-int(lengths[b]) // bs)):
            bt[b, m] = perm[idx]
            idx += 1
    out = da_ops.gqa_decode_paged(q, kp, vp, jnp.asarray(bt),
                                  jnp.asarray(lengths))
    ref = da_ref.gqa_decode_paged_ref(q, kp, vp, jnp.asarray(bt),
                                      jnp.asarray(lengths))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-5, atol=3e-5)


#: (lengths, K, D, bs, M, pages per chunk or None for the kernel's own,
#: dtype): empty rows, ends mid-page, on a page, on a chunk and at the
#: full table, at toy widths and at qwen3-1.7b's (K 8, D 128, bs 32)
PAGED_EDGES = {
    "edges_f32": ([0, 5, 8, 16, 27, 48], 2, 32, 8, 6, 2, jnp.float32),
    "one_chunk_f32": ([0, 13, 64], 2, 64, 8, 8, None, jnp.float32),
    "qwen3_bf16": ([0, 31, 64, 128], 8, 128, 32, 4, 2, jnp.bfloat16),
    "qwen3_own_chunk_bf16": ([1, 100, 0], 8, 128, 32, 4, None,
                             jnp.bfloat16),
}


@pytest.mark.parametrize("case", sorted(PAGED_EDGES))
def test_gqa_decode_paged_walks_live_pages_only(monkeypatch, case):
    """Row lengths at every page and chunk edge, ``-1`` table entries past
    the live pages, and every pool position outside the live context —
    whole blocks no row owns and the tail of each row's last page — set
    to NaN in K and Inf in V: the kernel matches the oracle on clean
    pools, so nothing past a row's length reaches its output."""
    from repro.kernels.decode_attention import decode_attention as DA
    lengths, K, D, bs, M, C, dtype = PAGED_EDGES[case]
    if C is not None:
        itemsize = jnp.dtype(dtype).itemsize
        monkeypatch.setattr(DA, "PAGE_BUFFER_BYTES",
                            C * bs * K * D * itemsize)
        assert DA.pages_per_chunk(bs, K * D * itemsize, M) == C
    B, H = len(lengths), 2 * K
    P = B * M + 2
    r = np.random.default_rng(11)
    kp, vp = r.normal(size=(2, P, bs, K, D))
    bt = np.full((B, M), -1, np.int32)
    live = np.zeros((P, bs), bool)
    blocks = iter(r.permutation(P))
    for b, n in enumerate(lengths):
        for m in range(-(-n // bs)):
            bt[b, m] = next(blocks)
            live[bt[b, m], :min(bs, n - m * bs)] = True
    clean_k, clean_v = (jnp.asarray(np.where(live[..., None, None], x, 0.0),
                                    dtype) for x in (kp, vp))
    kp[~live], vp[~live] = np.nan, np.inf
    q = jnp.asarray(r.normal(size=(B, H, D)), dtype)
    tables, n = jnp.asarray(bt), jnp.asarray(lengths, jnp.int32)
    out = DA.gqa_decode_paged(q, jnp.asarray(kp, dtype),
                              jnp.asarray(vp, dtype), tables, n,
                              interpret=True)
    ref = da_ref.gqa_decode_paged_ref(q, clean_k, clean_v, tables, n)
    out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
    np.testing.assert_allclose(out, ref, **TOL[dtype])
    assert not out[np.asarray(lengths) == 0].any()


@given(bs=st.sampled_from([8, 16]), m=st.sampled_from([2, 4]),
       seed=st.integers(0, 2**16))
@settings(max_examples=10, deadline=None)
def test_gqa_decode_paged_property_vs_dense_gather(bs, m, seed):
    """For any block table and length, the paged kernel must equal the
    *dense* kernel run on the gathered cache with a length mask — the
    page indirection cannot change the math."""
    r = np.random.default_rng(seed)
    B, H, K, D = 2, 4, 2, 32
    P = B * m + 2
    q = jnp.asarray(r.normal(size=(B, H, D)), jnp.float32)
    kp = jnp.asarray(r.normal(size=(P, bs, K, D)), jnp.float32)
    vp = jnp.asarray(r.normal(size=(P, bs, K, D)), jnp.float32)
    perm = r.permutation(P)
    bt = perm[:B * m].reshape(B, m).astype(np.int32)
    lengths = r.integers(1, m * bs + 1, size=(B,)).astype(np.int32)
    out = da_ops.gqa_decode_paged(q, kp, vp, jnp.asarray(bt),
                                  jnp.asarray(lengths))
    gathered_k = kp[bt].reshape(B, m * bs, K, D)
    gathered_v = vp[bt].reshape(B, m * bs, K, D)
    valid = jnp.arange(m * bs)[None, :] < jnp.asarray(lengths)[:, None]
    dense = da_ops.gqa_decode(q, gathered_k, gathered_v, valid, block_w=bs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                               rtol=3e-5, atol=3e-5)


@given(w=st.sampled_from([128, 256, 512]), frac=st.floats(0.05, 1.0),
       seed=st.integers(0, 2**16))
@settings(max_examples=10, deadline=None)
def test_gqa_decode_property_masking(w, frac, seed):
    """Output must equal the oracle for any validity mask (ring-buffer
    holes, sliding windows)."""
    r = np.random.default_rng(seed)
    B, H, K, D = 2, 4, 2, 32
    q = jnp.asarray(r.normal(size=(B, H, D)), jnp.float32)
    kc = jnp.asarray(r.normal(size=(B, w, K, D)), jnp.float32)
    vc = jnp.asarray(r.normal(size=(B, w, K, D)), jnp.float32)
    valid = jnp.asarray(r.random((B, w)) < frac).at[:, 0].set(True)
    out = da_ops.gqa_decode(q, kc, vc, valid, block_w=128)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(da_ref.gqa_decode_ref(q, kc, vc, valid)),
        rtol=3e-5, atol=3e-5)


def test_engine_pallas_path_matches():
    """Engine under a pallas linked_matmul plan (cbra via kernel) == the
    pure-jnp seed-plan engine."""
    from repro.core import Graph, execute, init_params, optimize
    from repro.core import graph as G
    g = Graph("cbra_net")
    x = g.add_input("x", (1, 8, 8, 16))
    y = G.conv2d(g, x, 32, 1)
    y = G.bn(g, y)
    y = G.relu(g, y)
    y = G.pool(g, y, "avg", 2)
    g.mark_output(y)
    opt = optimize(g)
    assert any(n.op_type == "cbra" for n in opt.nodes)
    params = init_params(g)
    inputs = {"x": RNG.normal(size=(1, 8, 8, 16)).astype("float32")}
    from repro.core.pipeline import KernelPlan
    a = execute(opt, params, inputs, mode="xenos")
    b = execute(opt, params, inputs, mode="xenos",
                plan=KernelPlan(linked_matmul="pallas"))
    np.testing.assert_allclose(np.asarray(a[0]), np.asarray(b[0]),
                               rtol=2e-5, atol=2e-5)
