"""Smoke run of the main paths on TPU chips, through the normal entry points.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # the paths that span four chips

One chip, in one process:

  a. device check: the first JAX device must be a TPU and the Pallas
     kernels must compile (no interpret mode);
  b. serving: qwen3-1.7b at its registered widths (random weights from
     ``--seed``) through ``ServingEngine`` with the default
     ``kernel_select`` plan, which must route ``decode_dense``,
     ``decode_paged`` and ``sampler`` to Pallas — 8 requests of 256 prompt
     tokens and 16 new tokens on 8 slots of 1024 tokens, one of them
     sampled with a seed, with paged and with dense KV; every request must
     finish;
  c. reference: on each engine, once every prompt is prefilled, the logits
     of the first decode step under the routed plan against the XLA plan
     (``kernel_plan="off"``) on the same weights and caches;
  d. graph engine: the zoo ResNet18 and the paper's Table-4 CBRA operator
     (conv1x1 + BN + ReLU + avgpool2x2 at 224x224x24 -> 224) through
     ``build_engine(g, "vanilla")`` and ``build_engine(g, "xenos",
     plan=<kernel_select plan for this backend>)``; the CBRA graph must
     take the Pallas ``cbra`` kernel, and the outputs must agree;
  e. compile seconds per phase and peak device memory.

With ``--four-chips`` it runs only what spans chips, each against its
one-chip reference: concat-TP serving over ``make_serving_mesh(4)`` against
the one-device engine on the same weights, and four one-chip replicas
behind ``ReplicaRouter``, each placed on its own device, against a solo
engine.

Any failed check exits nonzero.  The last line of standard output is one
JSON object naming the device.  On a host without a TPU, or outside a
checkout of the repository, the script exits nonzero and prints no result.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

ARCH = "qwen3-1.7b"
SLOTS = 8
MAX_LEN = 1024
PROMPT_LEN = 256
MAX_NEW = 16
N_REQUESTS = 8
SAMPLED_RID = 3          # the one request sampled with its own seed

#: routed (Pallas) vs XLA first-decode logits, max |diff| / max |XLA|.  The
#: XLA decode rounds the softmax weights to bf16 before PV while the
#: flash-decode kernel keeps them in f32, and activations are bf16 through
#: 28 layers, so the two agree to a few bf16 ulps of the logit scale.
LOGITS_RTOL = 3e-2
#: vanilla vs xenos graph outputs, max |diff| / max |vanilla| (f32 graphs;
#: the two engines contract in different orders, and the Pallas CBRA
#: kernel's matmul precision is its own).
GRAPH_RTOL = 1e-2
#: the paper's Table-4 CBRA operator shape: (H, W, C) -> OC
CBRA_SHAPE = (224, 224, 24, 224)


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, read per phase."""

    def __init__(self):
        import jax
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, seconds: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            self.total += seconds


def max_rel_diff(a, b) -> tuple[float, float]:
    """(max |a - b|, that over max |b|) in float32."""
    import numpy as np
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    diff = float(np.max(np.abs(a - b)))
    return diff, diff / max(float(np.max(np.abs(b))), 1e-30)


def smoke_config():
    from repro.configs.base import get_config
    return get_config(ARCH)


def make_requests(vocab: int, seed: int):
    import numpy as np
    from repro.serving import Request, SamplingParams
    rng = np.random.default_rng(seed)
    return [Request(
        rid=i, prompt=rng.integers(0, vocab, PROMPT_LEN).astype(np.int32),
        max_new_tokens=MAX_NEW,
        sampling=SamplingParams(temperature=0.8, top_k=50, top_p=0.95,
                                seed=seed + i) if i == SAMPLED_RID else None)
        for i in range(N_REQUESTS)]


def prefill_all(engine, reqs) -> None:
    """Submit ``reqs`` and step until every prompt is prefilled and no
    decode step has run yet."""
    for r in reqs:
        engine.submit(r)
    for _ in range(10_000):
        if all(r.generated for r in reqs):
            break
        engine.step()
    check(all(len(r.generated) == 1 for r in reqs),
          "the engine decoded before every prompt was prefilled: raise "
          "the slot count to the request count")


def routed_vs_xla(engine, label: str) -> dict:
    """Phase c on one engine: first-decode logits, routed plan vs XLA."""
    from repro.core.pipeline import KernelPlan
    routed = engine.decode_logits()
    xla = engine.decode_logits(KernelPlan())
    diff, rel = max_rel_diff(routed, xla)
    print(f"[c] {label}: routed vs XLA first-decode logits: max abs diff "
          f"{diff:.6g}, relative {rel:.6g} (tolerance {LOGITS_RTOL})")
    check(rel <= LOGITS_RTOL,
          f"{label}: routed logits differ from XLA by {rel:.3g} > "
          f"{LOGITS_RTOL}")
    return {"max_abs": diff, "rel": rel}


def finish(engine, reqs, vocab: int, label: str) -> dict:
    t0 = time.perf_counter()
    engine.run()
    wall = time.perf_counter() - t0
    done = sum(r.done and len(r.generated) == r.max_new_tokens
               for r in reqs)
    print(f"[b] {label}: {done}/{len(reqs)} requests done, "
          f"{sum(len(r.generated) for r in reqs)} tokens, run wall "
          f"{wall:.3f} s (host clock, compile included)")
    check(done == len(reqs), f"{label}: only {done}/{len(reqs)} requests "
          "finished")
    check(all(0 <= t < vocab for r in reqs for t in r.generated),
          f"{label}: a token is outside the vocabulary")
    return {"done": done, "tokens": [list(r.generated) for r in reqs]}


def serve_phase(model, params, kv: str, seed: int) -> dict:
    """Phases b and c for one KV layout, with the default kernel plan."""
    import jax
    from repro.serving import ServingEngine
    engine = ServingEngine(model, params, slots=SLOTS, max_len=MAX_LEN,
                           kv=kv)
    plan = engine.kernel_plan.as_dict()
    print(f"[b] kv={kv} kernel plan: {plan}")
    if jax.default_backend() == "tpu":
        for site in ("decode_dense", "decode_paged", "sampler"):
            check(plan[site] == "pallas",
                  f"kernel_select left {site} on {plan[site]!r}, not pallas")
    reqs = make_requests(model.cfg.vocab, seed)
    prefill_all(engine, reqs)
    out = {"plan": plan, "logits": routed_vs_xla(engine, f"kv={kv}")}
    out.update(finish(engine, reqs, model.cfg.vocab, f"kv={kv}"))
    return out


def _graph_pair(g, plan, seed: int) -> tuple[float, float, list, bool]:
    """Worst (max abs, relative) difference of xenos against vanilla, the
    xenos op types, and whether the xenos program holds a Pallas call."""
    import jax
    import numpy as np
    from repro.core import build_engine, init_params
    params = init_params(g, seed)
    rng = np.random.default_rng(seed)
    inputs = [np.asarray(rng.normal(size=g.tensors[t].shape), np.float32)
              for t in g.inputs]
    vanilla, _ = build_engine(g, "vanilla")
    xenos, _ = build_engine(g, "xenos", plan=plan)
    a = vanilla(params, *inputs)
    b = xenos(params, *inputs)
    worst = (0.0, 0.0)
    for x, y in zip(b, a):
        check(np.all(np.isfinite(np.asarray(x))), f"{g.name}: xenos output "
              "is not finite")
        worst = max(worst, max_rel_diff(x, y), key=lambda d: d[1])
    kernel = "pallas_call" in str(jax.make_jaxpr(xenos)(params, *inputs))
    return (*worst, [n.op_type for n in xenos.graph.nodes], kernel)


def cbra_graph():
    from repro.core import Graph
    from repro.core import graph as G
    H, W, C, OC = CBRA_SHAPE
    g = Graph("table4_cbra")
    x = g.add_input("x", (1, H, W, C))
    y = G.relu(g, G.bn(g, G.conv2d(g, x, OC, 1)))
    g.mark_output(G.pool(g, y, "avg", 2))
    return g


def graph_phase(seed: int) -> dict:
    """Phase d: vanilla vs xenos on the zoo ResNet18 and the CBRA op."""
    import jax
    from repro.configs import cnn_zoo
    from repro.core.pipeline import select_kernel_plan
    plan, _ = select_kernel_plan({"accelerator": jax.default_backend()})
    print(f"[d] graph kernel plan: linked_matmul={plan.linked_matmul}")
    out = {}
    with jax.default_matmul_precision("highest"):
        for g in (cnn_zoo.build("resnet18"), cbra_graph()):
            diff, rel, ops, kernel = _graph_pair(g, plan, seed)
            print(f"[d] {g.name}: vanilla vs xenos max abs diff {diff:.6g}, "
                  f"relative {rel:.6g} (tolerance {GRAPH_RTOL}); xenos ops "
                  f"{sorted(set(ops))}; Pallas kernel in use: {kernel}")
            check(rel <= GRAPH_RTOL, f"{g.name}: vanilla and xenos differ "
                  f"by {rel:.3g} > {GRAPH_RTOL}")
            out[g.name] = {"max_abs": diff, "rel": rel}
    check("cbra" in ops, "the CBRA graph did not link into a cbra op")
    if jax.default_backend() == "tpu":
        check(plan.linked_matmul == "pallas" and kernel,
              "the cbra op did not run the Pallas kernel")
    return out


def random_weights(model, seed: int):
    """Random weights from ``seed`` in one jitted program (initialised leaf
    by leaf, each leaf shape would compile a program of its own)."""
    import jax
    return jax.block_until_ready(jax.jit(model.init)(jax.random.key(seed)))


def peak_bytes() -> int | None:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def on_device(tree, device) -> bool:
    import jax
    return all(leaf.devices() == {device} for leaf in jax.tree.leaves(tree))


def one_chip(args, clock) -> dict:
    import jax
    from repro.models.model import Model
    result = {}
    cfg = smoke_config()
    model = Model(cfg)
    c0, t0 = clock.total, time.perf_counter()
    params = random_weights(model, args.seed)
    print(f"[b] {cfg.name}: {model.param_count() / 1e9:.3f} B params "
          f"({cfg.param_dtype}), init {time.perf_counter() - t0:.3f} s")
    for kv in ("paged", "dense"):
        c0 = clock.total
        out = serve_phase(model, params, kv, args.seed)
        gc.collect()
        out["compile_s"] = clock.total - c0
        out["peak_bytes"] = peak_bytes()
        print(f"[e] kv={kv}: compile {out['compile_s']:.3f} s, peak HBM "
              f"{out['peak_bytes']} bytes")
        del out["tokens"]
        result[f"serve_{kv}"] = out
    del params
    gc.collect()
    c0 = clock.total
    result["graph"] = graph_phase(args.seed)
    result["graph"]["compile_s"] = clock.total - c0
    print(f"[e] graph: compile {result['graph']['compile_s']:.3f} s, peak "
          f"HBM {peak_bytes()} bytes")
    return result


def four_chips(args, clock) -> dict:
    """Concat-TP over four chips and four one-chip router replicas, each
    against a one-device engine on the same weights."""
    import jax
    from repro.launch.mesh import make_serving_mesh
    from repro.models.model import Model
    from repro.serving import ReplicaRouter, ServingEngine
    devices = jax.devices()
    check(len(devices) >= 4, f"--four-chips needs 4 devices, found "
          f"{len(devices)}")
    devices = devices[:4]
    cfg = smoke_config()
    model = Model(cfg)
    params = random_weights(model, args.seed)
    kw = dict(slots=SLOTS, max_len=MAX_LEN, kv="paged")
    result = {}

    c0 = clock.total
    solo = ServingEngine(model, params, device=devices[0], **kw)
    reqs = make_requests(cfg.vocab, args.seed)
    prefill_all(solo, reqs)
    solo_logits = solo.decode_logits()
    solo_out = finish(solo, reqs, cfg.vocab, "solo")
    del solo
    gc.collect()

    tp = ServingEngine(model, params, mesh=make_serving_mesh(4), **kw)
    print(f"[tp] kernel plan: {tp.kernel_plan.as_dict()}")
    tp_reqs = make_requests(cfg.vocab, args.seed)
    prefill_all(tp, tp_reqs)
    diff, rel = max_rel_diff(tp.decode_logits(), solo_logits)
    print(f"[tp] 4-way concat-TP vs one device, first-decode logits: max "
          f"abs diff {diff:.6g}, relative {rel:.6g} (tolerance "
          f"{LOGITS_RTOL})")
    check(rel <= LOGITS_RTOL, f"concat-TP logits differ by {rel:.3g}")
    tp_out = finish(tp, tp_reqs, cfg.vocab, "concat-TP")
    same = sum(a == b for a, b in zip(tp_out["tokens"], solo_out["tokens"]))
    print(f"[tp] {same}/{len(reqs)} token streams equal the one-device "
          "engine's")
    result["tp"] = {"max_abs": diff, "rel": rel, "same_streams": same,
                    "compile_s": clock.total - c0}
    del tp
    gc.collect()

    c0 = clock.total
    engines = [ServingEngine(model, params, device=d, **kw)
               for d in devices]
    for i, (e, d) in enumerate(zip(engines, devices)):
        check(on_device(e.params, d) and on_device(e.caches, d),
              f"replica {i} is not wholly on {d}")
    router = ReplicaRouter(engines)
    r_reqs = make_requests(cfg.vocab, args.seed)
    for r in r_reqs:
        router.submit(r)
    router.run()
    for i, (e, d) in enumerate(zip(engines, devices)):
        check(on_device(e.caches, d), f"replica {i}'s caches left {d}")
    per = [s["tokens_out"] for s in router.stats()["per_replica"]]
    print(f"[router] replicas on {[str(d) for d in devices]}; tokens per "
          f"replica {per}")
    check(all(t > 0 for t in per), "a replica served no request")
    same = sum(a.generated == b for a, b in zip(r_reqs, solo_out["tokens"]))
    print(f"[router] {same}/{len(r_reqs)} token streams equal the solo "
          "engine's")
    check(all(r.done for r in r_reqs), "a routed request did not finish")
    check(same == len(r_reqs), "router streams differ from the solo engine")
    result["router"] = {"tokens_per_replica": per, "same_streams": same,
                        "compile_s": clock.total - c0}
    for i, d in enumerate(devices):
        stats = d.memory_stats() or {}
        print(f"[e] device {i}: peak HBM {stats.get('peak_bytes_in_use')} "
              "bytes")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip paths (concat-TP, router "
                         "replicas) against their one-chip references")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: FAIL: {SRC / 'repro'} is missing; run this "
              "script from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: FAIL: needs a TPU; JAX's first device is on "
              f"platform {dev.platform!r}", file=sys.stderr)
        return 3
    from repro.kernels import interpret_mode
    print(f"[a] jax {jax.__version__}; device_kind {dev.device_kind!r}; "
          f"{len(jax.devices())} devices; compile cache {cache_dir}")
    if interpret_mode():
        print("chip_smoke: FAIL: the Pallas kernels would run interpreted",
              file=sys.stderr)
        return 3
    clock = CompileClock()
    t0 = time.perf_counter()
    try:
        result = (four_chips if args.four_chips else one_chip)(args, clock)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    print(f"[e] total wall {time.perf_counter() - t0:.3f} s, compile "
          f"{clock.total:.3f} s; summary {json.dumps(result)}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
