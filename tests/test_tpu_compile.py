"""The main-path Pallas kernels and the serving step, compiled for a
described TPU v5e at published widths.

Interpret-mode parity (``test_kernels.py`` and friends) cannot see what
the TPU lowering refuses: blocks whose last two dims do not tile
``(8, 128)``, scoped-VMEM overuse, programs that do not fit.  These tests
ask the chip's own compiler, which is installed with jax and compiles for
a chip that is described and not attached.  Nothing runs, so they say
nothing about results or times.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every pytest worker imports
this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_config

#: qwen3-1.7b decode geometry: 8 slots of 1024 tokens, bf16 KV
B, H, K, D, W = 8, 16, 8, 128, 1024
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    # no skip: a host that cannot describe the chip fails these tests
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def shape(one_chip):
    def make(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    return make


def compile_for_chip(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("block_w", [W, 256])
def test_gqa_decode_compiles(shape, block_w):
    from repro.kernels.decode_attention.decode_attention import gqa_decode
    compile_for_chip(
        lambda q, k, v, m: gqa_decode(q, k, v, m, block_w=block_w,
                                      interpret=False),
        shape((B, H, D), BF16), shape((B, W, K, D), BF16),
        shape((B, W, K, D), BF16), shape((B, W), jnp.bool_))


@pytest.mark.parametrize("block_size", [8, 16, 32])
def test_gqa_decode_paged_compiles(shape, block_size):
    from repro.kernels.decode_attention.decode_attention import (
        gqa_decode_paged)
    M = W // block_size
    pool = shape((B * M, block_size, K, D), BF16)
    compile_for_chip(
        lambda q, k, v, t, n: gqa_decode_paged(q, k, v, t, n,
                                               interpret=False),
        shape((B, H, D), BF16), pool, pool, shape((B, M), jnp.int32),
        shape((B,), jnp.int32))


def test_fused_mask_compiles_at_qwen3_vocab(shape):
    from repro.kernels.fused_sampler.fused_sampler import fused_mask
    V = get_config("qwen3-1.7b").vocab
    compile_for_chip(
        lambda r, t, k, p: fused_mask(r, t, k, p, interpret=False),
        shape((B, V)), shape((B,)), shape((B,), jnp.int32), shape((B,)))


@pytest.mark.parametrize("nhwc_oc", [(1, 224, 224, 24, 224),
                                     (1, 8, 8, 1024, 1024)],
                         ids=["table4_cbra", "deep_1x1"])
def test_cbr_avgpool_compiles(shape, nhwc_oc):
    from repro.kernels.linked_cbr_pool.linked_cbr_pool import cbr_avgpool
    N, Hh, Ww, C, OC = nhwc_oc
    compile_for_chip(
        lambda x, w, b: cbr_avgpool(x, w, b, interpret=False),
        shape((N, Hh, Ww, C)), shape((C, OC)), shape((OC,)))


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Steer the kernel wrappers to their compiled path (the CPU backend
    would interpret them), with no trace cached on either side."""
    import repro.kernels.decode_attention.ops as decode_ops
    import repro.kernels.fused_sampler.ops as sampler_ops
    jax.clear_caches()
    monkeypatch.setattr(decode_ops, "interpret_mode", lambda: False)
    monkeypatch.setattr(sampler_ops, "interpret_mode", lambda: False)
    yield
    jax.clear_caches()


@pytest.mark.parametrize("kv", ["dense", "paged"])
def test_serve_sample_compiles_under_tpu_plan(one_chip, shape,
                                              compiled_kernels, kv):
    """The engine's fused decode+sample dispatch for qwen3-1.7b at its
    registered widths, under the plan ``kernel_select`` picks for a TPU."""
    from repro.core.pipeline import select_kernel_plan
    from repro.models.model import Model
    from repro.serving.engine import _serving_jits
    cfg = get_config("qwen3-1.7b")
    model = Model(cfg)
    plan, detail = select_kernel_plan({
        "accelerator": "tpu", "slots": B, "max_len": W,
        "q_heads": cfg.n_heads, "kv_heads": cfg.n_kv_heads,
        "head_dim": cfg.resolved_head_dim, "vocab": cfg.vocab})
    assert "pallas_untiled" not in detail
    for site in ("decode_dense", "decode_paged", "sampler"):
        assert getattr(plan, site) == "pallas", site

    def on_chip(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(model.init, jax.random.key(0)))
    bs = 16
    caches = on_chip(jax.eval_shape(
        lambda: model.init_caches(B, W) if kv == "dense" else
        model.init_paged_caches(B, pool_blocks=B * W // bs, block_size=bs,
                                max_blocks=W // bs)))
    step = _serving_jits(model, W, plan, caches=caches)["serve_sample"]
    compiled = step.lower(
        params, caches, shape((B, 1), jnp.int32), shape((B,), jnp.bool_),
        shape((B,), jnp.uint32), shape((B,), jnp.int32), shape((B,)),
        shape((B,), jnp.int32), shape((B,))).compile()
    text = compiled.as_text()
    # one decode-attention kernel in the scanned layer body, one sampler
    assert text.count("tpu_custom_call") >= 2
    mem = compiled.memory_analysis()
    live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert live < 16e9, f"{live / 1e9:.2f} GB does not fit a 16 GB v5e"


def test_concat_tp_entries_compile_over_four_chips(topo, compiled_kernels):
    """Every hot-path entry of a 4-way concat-TP engine under the TPU plan
    (each Pallas call must sit inside the engine's shard_map: the TPU
    lowering cannot partition a kernel on its own)."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P
    from repro.core.pipeline import select_kernel_plan
    from repro.distributed import tp
    from repro.models.model import Model
    from repro.serving.engine import _serving_jits
    cfg = get_config("qwen3-1.7b")
    model = Model(cfg)
    mesh = Mesh(np.array(topo.devices[:4]), (tp.SERVING_AXIS,))
    plan, _ = select_kernel_plan({
        "accelerator": "tpu", "slots": B, "max_len": W, "mesh_shards": 4,
        "q_heads": cfg.n_heads, "kv_heads": cfg.n_kv_heads,
        "head_dim": cfg.resolved_head_dim, "vocab": cfg.vocab})
    assert plan.decode_paged == plan.sampler == "pallas"

    def placed(tree, specs):
        return jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=NamedSharding(mesh, s)),
            tree, specs, is_leaf=lambda x: isinstance(x, P))

    bs = 16
    caches = jax.eval_shape(lambda: model.init_paged_caches(
        B, pool_blocks=B * W // bs, block_size=bs, max_blocks=W // bs))
    params = placed(jax.eval_shape(model.init, jax.random.key(0)),
                    tp.serving_param_specs(model.param_specs()))
    caches = placed(caches, tp.serving_cache_specs(caches))

    def rep(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype,
                                    sharding=NamedSharding(mesh, P()))

    jits = _serving_jits(model, W, plan, mesh=mesh, caches=caches)
    policy = (rep((B,), jnp.uint32), rep((B,), jnp.int32), rep((B,)),
              rep((B,), jnp.int32), rep((B,)))
    entries = {
        "serve_sample": (params, caches, rep((B, 1), jnp.int32),
                         rep((B,), jnp.bool_)) + policy,
        "chunk": (params, caches, rep((B, 32), jnp.int32),
                  rep((B,), jnp.int32), rep((B,), jnp.int32)),
        "sample": (rep((B, cfg.vocab)),) + policy,
    }
    for name, args in entries.items():
        compiled = jax.jit(jits[name]).lower(*args).compile()
        if name != "chunk":
            assert "tpu_custom_call" in compiled.as_text(), name
