"""No hidden fallback off the device: the interpreter only on the CPU,
kernel wrappers that refuse an untiled shape when compiled, an explicit
sampler backend, a kernel plan that says why a TPU site stays on XLA, and
benchmark children whose failure surfaces."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.kernels
from repro.kernels.decode_attention import ops as decode_ops
from repro.kernels.fused_sampler import ops as sampler_ops
from repro.kernels.linked_cbr_pool import ops as cbra_ops
from repro.kernels.linked_matmul import ops as mlp_ops
from repro.kernels.split_matmul import ops as split_ops


@pytest.mark.parametrize("backend,interpreted", [("cpu", True),
                                                 ("tpu", False)])
def test_interpret_mode_per_backend(monkeypatch, backend, interpreted):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert repro.kernels.interpret_mode() is interpreted


@pytest.mark.parametrize("backend", ["gpu", "rocm"])
def test_interpret_mode_refuses_other_backends(monkeypatch, backend):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    with pytest.raises(RuntimeError, match=repr(backend)):
        repro.kernels.interpret_mode()


def _decode_args(B=2, H=4, K=2, D=64, W=48):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, W, K, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, W, K, D)), jnp.float32)
    valid = jnp.arange(W)[None, :] < jnp.asarray([[W // 2], [W]])
    return q, k, v, valid


def _paged_args(D=64, bs=8, M=4):
    q, _, _, _ = _decode_args(D=D)
    rng = np.random.default_rng(1)
    pool = jnp.asarray(rng.normal(size=(8, bs, 2, D)), jnp.float32)
    tables = jnp.asarray(np.arange(8).reshape(2, M), jnp.int32)
    lengths = jnp.asarray([5, 3 * bs], jnp.int32)
    return q, pool, pool, tables, lengths


def _untiled_calls():
    """Each wrapper at a shape the TPU lowering cannot tile."""
    x = jnp.ones((1, 5, 6, 8), jnp.float32)              # odd map
    m = jnp.ones((12, 16), jnp.float32)                  # 12 rows, block 8
    return {
        "gqa_decode_head_dim": lambda: decode_ops.gqa_decode(
            *_decode_args(D=64, W=256)),
        "gqa_decode_block_w": lambda: decode_ops.gqa_decode(
            *_decode_args(D=128, W=384), block_w=256),
        "gqa_decode_paged": lambda: decode_ops.gqa_decode_paged(
            *_paged_args(D=64)),
        "cbr_avgpool": lambda: cbra_ops.cbr_avgpool(
            x, jnp.ones((8, 4)), jnp.zeros((4,))),
        "linked_mlp": lambda: mlp_ops.linked_mlp(
            m, jnp.ones((16, 32)), jnp.ones((16, 32)), jnp.ones((32, 16)),
            block_m=8),
        "split_matmul": lambda: split_ops.split_matmul(
            m, jnp.ones((16, 24)), jnp.zeros((24,)), block_m=8),
    }


@pytest.fixture
def compiled_wrappers(monkeypatch):
    """Steer every kernel wrapper to its compiled (TPU) branch, with no
    trace cached from, or left to, the interpreted branch."""
    jax.clear_caches()
    for mod in (decode_ops, cbra_ops, mlp_ops, split_ops, sampler_ops):
        monkeypatch.setattr(mod, "interpret_mode", lambda: False)
    yield
    jax.clear_caches()


@pytest.mark.parametrize("name", sorted(_untiled_calls()))
def test_compiled_wrappers_refuse_untiled_shapes(compiled_wrappers, name):
    with pytest.raises(ValueError, match="cannot tile a TPU"):
        _untiled_calls()[name]()


@pytest.mark.parametrize("name", sorted(_untiled_calls()))
def test_interpreted_wrappers_still_run(name):
    out = _untiled_calls()[name]()
    assert np.all(np.isfinite(np.asarray(out)))


def test_fused_mask_refuses_unaligned_vocab(compiled_wrappers):
    B, V = 2, 96
    args = (jnp.zeros((B, V)), jnp.zeros((B,), jnp.uint32),
            jnp.zeros((B,), jnp.int32), jnp.ones((B,)),
            jnp.full((B,), 5, jnp.int32), jnp.full((B,), 0.9))
    with pytest.raises(ValueError, match="multiple of 128"):
        sampler_ops.fused_sample(*args, vocab=V, backend="pallas")


def test_fused_sample_refuses_unknown_backend():
    B, V = 2, 128
    args = (jnp.zeros((B, V)), jnp.zeros((B,), jnp.uint32),
            jnp.zeros((B,), jnp.int32), jnp.ones((B,)),
            jnp.full((B,), 5, jnp.int32), jnp.full((B,), 0.9))
    with pytest.raises(ValueError, match="unknown sampler backend"):
        sampler_ops.fused_sample(*args, vocab=V, backend="auto")


@pytest.mark.parametrize("head_dim,vocab,untiled", [
    (128, 151936, set()),
    (64, 151936, {"decode_dense", "decode_paged"}),
    (128, 1000, {"sampler"}),
])
def test_tpu_plan_names_every_site_left_on_xla(head_dim, vocab, untiled):
    from repro.core.pipeline import select_kernel_plan
    plan, detail = select_kernel_plan({
        "accelerator": "tpu", "slots": 8, "max_len": 1024, "q_heads": 16,
        "kv_heads": 8, "head_dim": head_dim, "vocab": vocab})
    assert set(detail.get("pallas_untiled", {})) == untiled
    for site in ("decode_dense", "decode_paged", "sampler"):
        assert (getattr(plan, site) == "pallas") == (site not in untiled)
    assert plan.linked_matmul == "pallas"


def test_host_device_child_failure_raises():
    from benchmarks.common import run_on_host_devices
    with pytest.raises(RuntimeError, match="exit 3"):
        run_on_host_devices("import sys; sys.exit(3)", 2, timeout=120)


def test_host_device_children_are_cpu_only(monkeypatch):
    from benchmarks import common
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="CPU backend only"):
        common.run_on_host_devices("print(1)", 2)


def test_fig11_surfaces_a_failed_sync_child(monkeypatch):
    from benchmarks import common, fig11_dxenos
    monkeypatch.setattr(
        fig11_dxenos, "run_on_host_devices",
        lambda code, n: common.run_on_host_devices(
            "raise SystemExit('sync child failed')", n, timeout=120))
    with pytest.raises(RuntimeError, match="sync child failed"):
        fig11_dxenos.run()
