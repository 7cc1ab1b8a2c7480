"""Fig. 11 reproduction: d-Xenos — PS vs ring sync, partition schemes.

Two parts:
  1. wall-clocks our explicit ring all-reduce vs. the PS emulation on a
     parameter-sync workload (and checks both equal psum): over 8 forced
     host devices in a CPU-pinned child on the CPU, over the process's
     own devices on an accelerator;
  2. the d-Xenos planner (Algorithm 1 + the Figure-6 scheme set) scores
     inH / inW / outC / mixed partitions with the roofline model for
     MobileNet/ResNet/Bert on 4 devices — reproducing the takeaways: ring
     beats PS (PS can be worse than single-device), and the per-operator
     Ring-Mix wins.
"""
from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs import cnn_zoo
from repro.core import pipeline, planner
from repro.distributed import ps_sync, ring_allreduce
from repro.distributed.compat import make_mesh

from .common import emit, run_on_host_devices

#: forced host devices of the CPU sync child
CPU_SYNC_DEVICES = 8


def sync_times(n: int) -> dict[str, float]:
    """Seconds per ring / PS / psum parameter sync over ``n`` devices of
    this process, after checking that ring and PS both equal psum."""
    mesh = make_mesh((n,), ("x",))
    x = jnp.ones((n, 1 << 20), jnp.float32)

    def make(kind):
        def inner(xs):
            v = xs[0]
            if kind == "ring":
                return ring_allreduce(v, "x")
            if kind == "ps":
                return ps_sync(v, "x")
            return jax.lax.psum(v, "x")
        # check_vma=False: the replication of the hand-built ring/PS
        # schedules cannot be statically inferred from ppermute
        return jax.jit(jax.shard_map(inner, mesh=mesh, in_specs=P("x", None),
                                 out_specs=P(), check_vma=False))

    want = np.asarray(make("psum")(x))
    times = {}
    for kind in ("ring", "ps", "psum"):
        f = make(kind)
        np.testing.assert_allclose(np.asarray(f(x)), want, rtol=1e-6)
        f(x).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(5):
            f(x).block_until_ready()
        times[kind] = (time.perf_counter() - t0) / 5
    return times


def _sync_times_here() -> dict[str, float]:
    """On the CPU the sync runs in a child with forced host devices; on an
    accelerator it runs here over the real devices."""
    if jax.default_backend() != "cpu":
        return sync_times(len(jax.devices()))
    out = run_on_host_devices(
        "import json\n"
        "from benchmarks.fig11_dxenos import sync_times\n"
        f"print('SYNC_JSON ' + json.dumps(sync_times({CPU_SYNC_DEVICES})))\n",
        CPU_SYNC_DEVICES)
    line = next(l for l in out.splitlines() if l.startswith("SYNC_JSON "))
    return json.loads(line[len("SYNC_JSON "):])


def run() -> None:
    # part 1: explicit collective schedules (a failed child raises)
    times = _sync_times_here()
    for kind, s in times.items():
        emit(f"fig11.sync.{kind}", s, "allclose_vs_psum=True")
    emit("fig11.sync.ring_vs_ps", 0.0,
         f"ring_speedup={times['ps'] / times['ring']:.2f}x")

    # part 2: planner scheme comparison (modeled per Alg. 1's cost oracle)
    for name in ("mobilenet", "resnet18", "bert_s"):
        g = cnn_zoo.build(name)
        single = planner.model_scheme_time(
            g, planner.Scheme(()), 1, sync="ring").serial_s
        rows = {}
        for dim in ("inH", "inW", "outC"):
            for sync in ("ring", "ps"):
                t = planner.model_scheme_time(
                    g, planner.Scheme.single(dim, 4), 4, sync=sync).serial_s
                rows[f"{sync}-{dim}"] = t
        # the planner runs as the pipeline's opt-in dxenos_plan pass
        # (annotate=False: only the whole-graph scheme is needed here)
        _, rep = pipeline.optimize(
            g, passes=("dxenos_plan",),
            options={"n_devices": 4, "sync": "ring", "annotate": False})
        summ = rep.passes[0].summary
        best, best_t = summ["best_scheme"], summ["best_modeled_s"]
        rows["ring-mix"] = best_t
        for k, t in sorted(rows.items(), key=lambda kv: kv[1]):
            emit(f"fig11.{name}.{k}", t,
                 f"speedup_vs_single={single/t:.2f}x")
        worst_ps = max(t for k, t in rows.items() if k.startswith("ps-"))
        emit(f"fig11.{name}.takeaways", 0.0,
             f"ring_mix_best={best_t <= min(rows.values()) + 1e-12};"
             f"ps_can_lose_to_single={worst_ps > single};"
             f"best_scheme={best}")


if __name__ == "__main__":
    run()
