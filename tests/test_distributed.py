"""Multi-device behaviour (subprocesses with forced host device counts):
collective schedules, sharded MoE == oracle, sharded train step, dry-run.

All snippets build meshes through ``repro.distributed.compat`` (re-exported
by ``repro.launch.mesh``), whose ``make_mesh`` refuses a mesh larger than
the visible device set."""
import json

import jax
import pytest

from conftest import run_multidevice


def test_ring_allreduce_and_ps_equal_psum():
    out = run_multidevice("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.distributed import ring_allreduce, ps_sync
from repro.distributed.compat import make_mesh
mesh = make_mesh((8,), ("x",))
x = jnp.arange(8*33, dtype=jnp.float32).reshape(8, 33)
def f(kind):
    def inner(xs):
        if kind == "ring": return ring_allreduce(xs[0], "x")
        if kind == "ps": return ps_sync(xs[0], "x")
        return jax.lax.psum(xs[0], "x")
    return jax.jit(jax.shard_map(inner, mesh=mesh, in_specs=P("x", None),
                                 out_specs=P(), check_vma=False))
want = np.asarray(f("psum")(x))
for kind in ("ring", "ps"):
    got = np.asarray(f(kind)(x))
    np.testing.assert_allclose(got, want, rtol=1e-6)
print("COLLECTIVES_OK")
""")
    assert "COLLECTIVES_OK" in out


def test_sharded_moe_matches_reference():
    out = run_multidevice("""
import dataclasses, jax, jax.numpy as jnp, numpy as np
from repro.configs.base import all_configs
from repro.distributed.compat import make_mesh
from repro.models import moe as M
mesh = make_mesh((2, 4), ("data", "model"))
cfg = dataclasses.replace(all_configs()["olmoe-1b-7b"].reduced(),
                          n_experts=8, top_k=2, capacity_factor=8.0)
rng = np.random.default_rng(0)
d, ff = cfg.d_model, cfg.d_ff
p = {"router": jnp.asarray(rng.normal(size=(d, 8)), jnp.float32),
     "gate": jnp.asarray(rng.normal(size=(8, d, ff))*0.05, jnp.float32),
     "up": jnp.asarray(rng.normal(size=(8, d, ff))*0.05, jnp.float32),
     "down": jnp.asarray(rng.normal(size=(8, ff, d))*0.05, jnp.float32)}
x = jnp.asarray(rng.normal(size=(4, 8, d)), jnp.float32)
with jax.set_mesh(mesh):
    out, aux = jax.jit(lambda p, x: M.moe_block(p, x, cfg=cfg, mesh=mesh,
                                                batch_axes=("data",)))(p, x)
ref = M.moe_reference(p, x, cfg=cfg)
np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-5)
# gradient path through shard_map dispatch
g = jax.jit(jax.grad(lambda pp: M.moe_block(pp, x, cfg=cfg, mesh=mesh,
                                            batch_axes=("data",))[0].sum()))(p)
assert all(float(jnp.sum(jnp.abs(v))) > 0 for v in g.values())
print("MOE_SHARDED_OK")
""")
    assert "MOE_SHARDED_OK" in out


def test_sharded_train_matches_single_device():
    """The sharded train step must be numerically equivalent to the
    single-device step (GSPMD is semantics-preserving; our shard_map MoE
    must be too)."""
    out = run_multidevice("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs.base import all_configs
from repro.models.model import Model
from repro.launch import mesh as mesh_lib
cfg = all_configs()["qwen3-1.7b"].reduced()
mesh = mesh_lib.make_debug_mesh(8)
rng = np.random.default_rng(1)
toks = jnp.asarray(rng.integers(0, cfg.vocab, (8, 16)), jnp.int32)
batch = {"tokens": toks, "labels": toks}

m1 = Model(cfg)          # no mesh
s1 = m1.init_train_state(jax.random.key(0))
_, met1 = jax.jit(lambda s, b: m1.train_step(s, b))(s1, batch)

m2 = Model(cfg, mesh=mesh)
s2 = m2.init_train_state(jax.random.key(0))
with jax.set_mesh(mesh):
    _, met2 = jax.jit(lambda s, b: m2.train_step(s, b, batch_axes=("data",)))(s2, batch)
np.testing.assert_allclose(float(met1["loss"]), float(met2["loss"]), rtol=2e-4)
print("TRAIN_SHARDED_OK", float(met1["loss"]), float(met2["loss"]))
""")
    assert "TRAIN_SHARDED_OK" in out


@pytest.mark.parametrize("arch,shape", [
    ("qwen3-1.7b", "train_4k"),
    ("olmoe-1b-7b", "decode_32k"),
    ("mamba2-370m", "long_500k"),
])
def test_dryrun_smoke(arch, shape):
    """dryrun lower+compile must succeed on a debug mesh (the full 512-way
    run is benchmarks/roofline territory)."""
    out = run_multidevice(f"""
import os
os.environ["REPRO_DRYRUN_DEVICES"] = "8"
from repro.launch import dryrun
rec = dryrun.run_one("{arch}", "{shape}", "single", verbose=False)
assert "error" not in rec, rec
print("DRYRUN_OK", rec["dominant"], rec["flops_per_device"] > 0)
""")
    assert "DRYRUN_OK" in out


def test_sharding_rules_divisibility():
    """Every assigned arch must produce even argument shardings on the
    production mesh axes (the DOS fallback ladder must catch 56/25/5-head
    cases) — checked structurally, no compile."""
    out = run_multidevice("""
import jax
from repro.configs.base import all_configs
from repro.models.model import Model
from repro.launch import mesh as mesh_lib
from jax.sharding import PartitionSpec as P
mesh = mesh_lib.make_debug_mesh(8)   # data=4, model=2
sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
for name, cfg in all_configs().items():
    m = Model(cfg, mesh=mesh)
    specs = m.partition_specs()
    abst = m.abstract()
    flat_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    flat_a = jax.tree.leaves(abst)
    assert len(flat_s) == len(flat_a)
    for spec, arr in zip(flat_s, flat_a):
        for dim, entry in enumerate(spec):
            if entry is None: continue
            names = entry if isinstance(entry, tuple) else (entry,)
            n = 1
            for nm in names: n *= sizes[nm]
            assert arr.shape[dim] % n == 0, (name, arr.shape, spec)
print("RULES_OK")
""")
    assert "RULES_OK" in out


# -- mesh construction guards (run in the normal 1-device process) ------------

def test_make_mesh_oversubscription_raises_with_hint():
    """Asking for more devices than the host has must fail loudly — with
    the XLA_FLAGS relaunch hint — never fall back to fewer devices."""
    from repro.distributed.compat import device_count, make_mesh
    have = device_count()
    with pytest.raises(ValueError, match="xla_force_host_platform"):
        make_mesh((have + 1,), ("x",))


def test_make_serving_mesh_guards():
    """The serving mesh helper inherits the same no-silent-fallback rule
    and rejects nonsensical shard counts."""
    from repro.launch.mesh import make_serving_mesh
    with pytest.raises(ValueError):
        make_serving_mesh(0)
    with pytest.raises(ValueError, match="xla_force_host_platform"):
        make_serving_mesh(len(jax.devices()) + 1)


def test_device_count_matches_jax():
    from repro.distributed.compat import device_count
    assert device_count() == len(jax.devices())
