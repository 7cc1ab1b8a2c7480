import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any jax import: jax locks the device
# count on first backend init.  REPRO_DRYRUN_DEVICES overrides for CI.
if os.environ.get("REPRO_DRYRUN_DEVICES"):
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                               + os.environ["REPRO_DRYRUN_DEVICES"])

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) and emit
memory/cost/collective analysis — deliverable (e), feeding §Roofline (g).

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-1.7b \
        --shape train_4k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all --out dryrun.jsonl
"""
import argparse
import json
import sys
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import INPUT_SHAPES, all_configs, get_config
from repro.core import costmodel as cm
from repro.core.pipeline import StageTimer
from repro.distributed import sharding as SH
from repro.distributed import state_sharding as SS
from repro.launch import mesh as mesh_lib
from repro.models.model import Model, TrainState
from repro.optim import adamw_init

SKIPS: dict[tuple[str, str], str] = {
    ("seamless-m4t-large-v2", "long_500k"):
        "enc-dec full attention; no faithful sub-quadratic variant (DESIGN.md §4)",
}


def build_mesh(multi_pod: bool):
    n = jax.device_count()
    if n == 512:
        return mesh_lib.make_production_mesh(multi_pod=multi_pod)
    return mesh_lib.make_debug_mesh(n, multi_pod=multi_pod)


def config_for(arch: str, shape_name: str):
    cfg = get_config(arch)
    if shape_name == "long_500k" and not cfg.sub_quadratic:
        cfg = cfg.long_context_variant()
    return cfg


def lower_one(arch: str, shape_name: str, mesh, rules=None, cfg=None,
              seq_shard=None):
    """Lower+compile the right step for (arch, shape) on mesh.

    ``seq_shard`` forces context-parallel KV-cache sharding (decode shapes).
    Returns (lowered, compiled, model, batch_axes).
    """
    shape = INPUT_SHAPES[shape_name]
    cfg = cfg or config_for(arch, shape_name)
    model = Model(cfg, mesh=mesh, rules=rules)
    baxes = SH.batch_axes_for(mesh, shape.global_batch)
    pspecs = model.partition_specs()
    pshard = SS.to_shardings(pspecs, mesh)
    inputs = model.input_specs(shape)
    repl = NamedSharding(mesh, P())
    bspec = SH.activation_spec(baxes, 2)

    if shape.kind == "train":
        opt_abs = jax.eval_shape(lambda p: adamw_init(p, model.opt_cfg),
                                 model.abstract())
        opt_specs = SS.opt_partition_specs(opt_abs, pspecs, mesh)
        state_shardings = TrainState(
            params=pshard, opt=SS.to_shardings(opt_specs, mesh),
            step=repl)
        state_abs = TrainState(params=model.abstract(), opt=opt_abs,
                               step=jax.ShapeDtypeStruct((), jnp.int32))
        batch_shardings = {k: NamedSharding(mesh, bspec if v.ndim == 2
                                            else SH.activation_spec(baxes, v.ndim))
                           for k, v in inputs.items()}

        def step(state, batch):
            return model.train_step(state, batch, batch_axes=baxes)

        # explicit out_shardings: without them XLA may materialize the new
        # TrainState replicated (observed: arctic-480b outputs at 905 GiB/dev)
        jitted = jax.jit(step, in_shardings=(state_shardings, batch_shardings),
                         out_shardings=(state_shardings, repl),
                         donate_argnums=(0,))
        with jax.set_mesh(mesh):
            lowered = jitted.lower(state_abs, inputs)

    elif shape.kind == "prefill":
        batch_shardings = {k: NamedSharding(mesh, bspec if v.ndim == 2
                                            else SH.activation_spec(baxes, v.ndim))
                           for k, v in inputs.items()}

        def step(params, batch):
            return model.prefill_step(params, batch, batch_axes=baxes)

        s_tok = shape.seq_len // 2 if cfg.is_encoder_decoder else shape.seq_len
        cache_abs = jax.eval_shape(
            lambda: model.init_caches(shape.global_batch, s_tok,
                                      shape.seq_len // 2
                                      if cfg.is_encoder_decoder else 0))
        cache_specs = SS.cache_partition_specs(
            cache_abs, mesh, global_batch=shape.global_batch)
        logits_spec = NamedSharding(mesh, SH.activation_spec(baxes, 2, "model"))
        jitted = jax.jit(step, in_shardings=(pshard, batch_shardings),
                         out_shardings=(logits_spec,
                                        SS.to_shardings(cache_specs, mesh)))
        with jax.set_mesh(mesh):
            lowered = jitted.lower(model.abstract(), inputs)

    else:  # decode
        caches_abs = inputs["caches"]
        kv_axis = (rules or {}).get("kv_heads", "model")
        cache_specs = SS.cache_partition_specs(
            caches_abs, mesh, global_batch=shape.global_batch,
            seq_shard=seq_shard, kv_axis=kv_axis)
        cache_shardings = SS.to_shardings(cache_specs, mesh)
        tok_shard = NamedSharding(mesh, bspec)

        def step(params, caches, tokens):
            return model.serve_step(params, caches, tokens, batch_axes=baxes)

        logits_spec = NamedSharding(mesh, SH.activation_spec(baxes, 2, "model"))
        jitted = jax.jit(step, in_shardings=(pshard, cache_shardings, tok_shard),
                         out_shardings=(logits_spec, cache_shardings),
                         donate_argnums=(1,))
        with jax.set_mesh(mesh):
            lowered = jitted.lower(model.abstract(), caches_abs,
                                   inputs["tokens"])

    compiled = lowered.compile()
    return lowered, compiled, model, baxes


def _cost_analysis(compiled) -> dict:
    """``compiled.cost_analysis()`` returns a dict on new jax, a one-element
    list of dicts on older releases; normalize to a dict."""
    ca = compiled.cost_analysis() or {}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return ca


def analyze(arch: str, shape_name: str, mesh_name: str, lowered, compiled,
            model) -> dict:
    """Per-device roofline record (cost_analysis is per-device SPMD)."""
    ca = _cost_analysis(compiled)
    ma = compiled.memory_analysis()
    hlo = compiled.as_text()
    coll = cm.collective_bytes_from_hlo(hlo)
    flops = float(ca.get("flops", 0.0))
    bytes_acc = float(ca.get("bytes accessed", 0.0))
    terms = cm.roofline(flops, bytes_acc, coll.get("total", 0.0), chips=1)
    n = model.param_count()
    shape = INPUT_SHAPES[shape_name]
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        if model.cfg.is_encoder_decoder:
            tokens = shape.global_batch * shape.seq_len  # src+tgt halves
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
    else:
        tokens = shape.global_batch
    n_active = _active_params(model.cfg)
    mult = 6.0 if shape.kind == "train" else 2.0
    model_flops_global = mult * n_active * tokens
    model_flops_per_dev = model_flops_global / mesh_size(mesh_name)
    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "params": n, "active_params": n_active,
        "flops_per_device": flops,
        "bytes_per_device": bytes_acc,
        "collective_bytes_per_device": coll.get("total", 0.0),
        "collectives": {k: v for k, v in coll.items() if k != "total"},
        "compute_s": terms.compute_s, "memory_s": terms.memory_s,
        "collective_s": terms.collective_s, "dominant": terms.dominant,
        "bound_s": terms.bound_s,
        "model_flops_per_device": model_flops_per_dev,
        "useful_flops_ratio": (model_flops_per_dev / flops) if flops else 0.0,
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "peak_estimate": ma.argument_size_in_bytes + ma.output_size_in_bytes
                             + ma.temp_size_in_bytes - ma.alias_size_in_bytes,
        },
        "fits_hbm": (ma.argument_size_in_bytes + ma.output_size_in_bytes
                     + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
                    < cm.HBM_BYTES,
    }
    return rec


def mesh_size(mesh_name: str) -> int:
    n = jax.device_count()
    return n if mesh_name == "multi" else (256 if n == 512 else n)


def _active_params(cfg) -> int:
    """6*N_active*D for MoE counts only routed+shared experts."""
    if not cfg.n_experts:
        return cfg.param_count()
    full = cfg.param_count()
    expert_params = cfg.n_layers * cfg.n_experts * 3 * cfg.d_model * cfg.d_ff
    active_expert = cfg.n_layers * cfg.top_k * 3 * cfg.d_model * cfg.d_ff
    return full - expert_params + active_expert


CAL_POINTS = (2, 4)


def calibrate_depth(arch: str, shape_name: str, mesh, rules=None,
                    cfg=None, seq_shard=None) -> dict:
    """XLA's cost_analysis counts a scanned (while-loop) body ONCE
    regardless of trip count, so depth is invisible in loop form.  The
    calibration compiles UNROLLED depth-2 and depth-4 variants
    (scan_layers=False, microbatch off) and recovers the per-layer slope:

        X(L) = X(2) + (X(4) - X(2)) / 2 * (L - 2)

    for flops, bytes and collective bytes.  Microbatch accumulation is a
    pure reorganization of the same math (its extra parameter re-reads and
    ZeRO re-gathers are §Perf territory, analyzed with unroll_microbatch)."""
    import dataclasses as _dc
    cfg = cfg or config_for(arch, shape_name)
    pts = {}
    for L in CAL_POINTS:
        c = _dc.replace(cfg, n_layers=L,
                        encoder_layers=L if cfg.encoder_layers else 0,
                        microbatch=0, scan_layers=False)
        _, comp, _, _ = lower_one(arch, shape_name, mesh, rules, cfg=c,
                                  seq_shard=seq_shard)
        ca = _cost_analysis(comp)
        coll = cm.collective_bytes_from_hlo(comp.as_text())
        pts[L] = (float(ca.get("flops", 0.0)),
                  float(ca.get("bytes accessed", 0.0)),
                  coll.get("total", 0.0))
    lo, hi = CAL_POINTS
    L = cfg.n_layers
    out = {}
    for i, key in enumerate(("flops", "bytes", "collective_bytes")):
        x_lo, x_hi = pts[lo][i], pts[hi][i]
        slope = (x_hi - x_lo) / (hi - lo)
        out[key] = max(x_lo + slope * (L - lo), 0.0)
    return out


def serve_plan_for(cfg, shape) -> dict:
    """serve_schedule plan for a decode shape (slots = the decode batch)."""
    from repro.core import pipeline
    from repro.serving.scheduler import serve_plan_graph

    g = serve_plan_graph(cfg.name, shape.global_batch, cfg.d_model,
                         cfg.d_ff or cfg.d_model, cfg.vocab)
    _, report = pipeline.optimize(
        g, passes=("serve_schedule",),
        options={"slots": shape.global_batch, "max_len": shape.seq_len})
    plan = dict(report.passes[-1].summary)
    plan["cache_hit"] = report.cache_hit
    return plan


def run_one(arch: str, shape_name: str, mesh_name: str, out=None,
            rules=None, verbose: bool = True, calibrate: bool = True) -> dict:
    if (arch, shape_name) in SKIPS:
        rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
               "skipped": SKIPS[(arch, shape_name)]}
        if verbose:
            print(f"SKIP {arch} x {shape_name}: {rec['skipped']}")
        return rec
    t0 = time.time()
    timer = StageTimer()  # same stage instrumentation the pass manager uses
    with timer.stage("lower_compile"):
        mesh = build_mesh(multi_pod=(mesh_name == "multi"))
        lowered, compiled, model, _ = lower_one(arch, shape_name, mesh, rules)
    with timer.stage("analyze"):
        rec = analyze(arch, shape_name, mesh_name, lowered, compiled, model)
    if calibrate and mesh_name == "single":  # roofline table is single-pod
        with timer.stage("calibrate_depth"):
            cal = calibrate_depth(arch, shape_name, mesh, rules)
        terms = cm.roofline(cal["flops"], cal["bytes"],
                            cal["collective_bytes"], chips=1)
        rec["calibrated"] = {
            **cal, "compute_s": terms.compute_s, "memory_s": terms.memory_s,
            "collective_s": terms.collective_s, "dominant": terms.dominant,
            "bound_s": terms.bound_s,
            "useful_flops_ratio": (rec["model_flops_per_device"] / cal["flops"])
                                  if cal["flops"] else 0.0,
        }
    if INPUT_SHAPES[shape_name].kind == "decode":
        # decode shapes are serving shapes: record what the serve_schedule
        # pass would plan for this (slots, max_len) — the same code path the
        # ServingEngine's scheduler replans through at runtime.
        with timer.stage("serve_plan"):
            rec["serve_plan"] = serve_plan_for(model.cfg,
                                               INPUT_SHAPES[shape_name])
    rec["stages"] = timer.as_dict()
    rec["compile_s"] = round(time.time() - t0, 1)
    if verbose:
        print(f"OK {arch:24s} {shape_name:12s} {mesh_name:6s} "
              f"flops/dev {rec['flops_per_device']:.3e} "
              f"dominant {rec['dominant']:10s} bound {rec['bound_s']*1e3:8.2f} ms "
              f"peak {rec['memory']['peak_estimate']/2**30:6.2f} GiB "
              f"fits {rec['fits_hbm']} ({rec['compile_s']}s)")
        print(f"   memory_analysis: {compiled.memory_analysis()}")
        ca = _cost_analysis(compiled)
        print(f"   cost_analysis: flops={ca.get('flops')} "
              f"bytes={ca.get('bytes accessed')}")
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=[*INPUT_SHAPES, None])
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    archs = sorted(all_configs()) if (args.all or not args.arch) else [args.arch]
    shapes = list(INPUT_SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    out_f = open(args.out, "a") if args.out else None
    failures = []
    for mesh_name in meshes:
        for arch in archs:
            for shape_name in shapes:
                try:
                    rec = run_one(arch, shape_name, mesh_name)
                except Exception as e:  # noqa: BLE001 - report & continue
                    traceback.print_exc()
                    rec = {"arch": arch, "shape": shape_name,
                           "mesh": mesh_name, "error": f"{type(e).__name__}: {e}"}
                    failures.append(rec)
                if out_f:
                    out_f.write(json.dumps(rec) + "\n")
                    out_f.flush()
    if out_f:
        out_f.close()
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print(" ", f["arch"], f["shape"], f["mesh"], f["error"])
        sys.exit(1)
    print("\nall dry-runs passed")


if __name__ == "__main__":
    main()
