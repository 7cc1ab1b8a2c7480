"""Quickstart: the Xenos workflow end to end in under a minute on CPU.

1. build a computation graph (MobileNet-style CNN),
2. run the automatic dataflow optimization (fusion -> linking -> DOS),
3. execute vanilla vs optimized and compare,
4. then the transformer side: a reduced assigned architecture through one
   train step and a few decode steps.

    PYTHONPATH=src python examples/quickstart.py
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import cnn_zoo
from repro.configs.base import get_config
from repro.core import DeviceSpec, Engine, init_params, pipeline
from repro.core.linking import link_groups
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import Model


def cnn_side():
    print("== Xenos graph optimization (the paper's CNN path) ==")
    g = cnn_zoo.build("mobilenet")
    # one entry point: the pass pipeline (fuse -> link -> DOS split), with
    # per-pass timing and verification built in
    opt, report = pipeline.optimize(g, DeviceSpec.tms320c6678())
    print(f"model={g.name}: {g.num_ops()} ops -> {opt.num_ops()} ops "
          f"in {report.total_s * 1e3:.1f} ms (Table-2 analogue)")
    linked = [n.op_type for n in opt.nodes if n.op_type in ("cbr", "cbra", "cbrm")]
    print(f"fused/linked ops: {linked}")
    print(f"link groups: {len(link_groups(opt))}")
    print(report.format())

    params = init_params(g)
    x = jnp.asarray(np.random.default_rng(0).normal(
        size=g.tensors[g.inputs[0]].shape), jnp.float32)

    # reuse the pipeline's output for xenos mode; vanilla runs the raw graph
    # (build_engine(g, mode) bundles both steps when no report is needed)
    for mode, graph in (("vanilla", g), ("xenos", opt)):
        eng = Engine(graph, mode)
        eng(params, x)  # compile
        t0 = time.perf_counter()
        out = eng(params, x)
        dt = time.perf_counter() - t0
        print(f"  {mode:8s}: {dt * 1e3:7.2f} ms  out[0,:3]="
              f"{np.asarray(out[0]).ravel()[:3].round(4)}")


def transformer_side():
    print("\n== Assigned architecture (reduced) through the same framework ==")
    cfg = get_config("qwen3-1.7b").reduced()
    model = Model(cfg)
    print(f"arch={cfg.name} family={cfg.family} params={model.param_count():,}")
    state = model.init_train_state(jax.random.key(0))
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (4, 32)), jnp.int32)
    state, metrics = jax.jit(lambda s, b: model.train_step(s, b))(
        state, {"tokens": toks, "labels": toks})
    print(f"one train step: loss={float(metrics['loss']):.4f}")

    logits, caches = model.prefill_step(state.params,
                                        {"tokens": toks[:1, :16]}, max_len=64)
    out = []
    tok = jnp.argmax(logits[:, :cfg.vocab], -1)[:, None].astype(jnp.int32)
    for _ in range(8):
        logits, caches = model.serve_step(state.params, caches, tok)
        tok = jnp.argmax(logits[:, :cfg.vocab], -1)[:, None].astype(jnp.int32)
        out.append(int(tok[0, 0]))
    print(f"greedy decode after prefill: {out}")


if __name__ == "__main__":
    enable_compile_cache()
    cnn_side()
    transformer_side()
    print("\nquickstart OK")
