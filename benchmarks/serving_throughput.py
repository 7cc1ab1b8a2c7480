"""Serving-throughput benchmark: scheduler-planned continuous batching vs
the one-at-a-time admission path, plus the per-request policy columns.

Same workload (N requests, fixed prompt length, fixed decode budget, same
params), five engine policies through one code path — only the scheduler
config and the per-request generation policy change:

  * ``serial``  — one request admitted and prefilled (B=1) per tick: the
    pre-scheduler engine's behaviour, kept as the baseline;
  * ``batched`` — all free slots admitted in one tick, one padded
    multi-sequence prefill call;
  * ``chunked`` — batched admission + chunked prefill interleaved with
    decode (the default serving configuration);
  * ``sampled`` — chunked, but every request samples with its own
    temperature/top-p/seed.  The auto kernel plan routes this through
    the fused sampler, whose ``serve_sample`` jit folds decode step and
    sampling into ONE dispatch per tick — the sampled column should sit
    within a few percent of ``chunked``;
  * ``sampled_ref`` — same workload with ``kernel_plan="off"``: the
    seed path's reference two-sort sampler as a second dispatch per
    tick.  The gap between ``sampled_ref`` and ``sampled`` is the fused
    sampler's win;
  * ``mixed``   — chunked, but a quarter of the requests arrive
    high-priority *after* the batch has settled into decode, so the
    scheduler's priority admission + preemption + restore machinery is
    actually on the clock (up-front mixed priorities would only be
    sorted, never preempt);
  * ``paged``   — chunked, but the KV lives in a block pool
    (``kv="paged"``): per-request block tables instead of dense
    ``max_len`` rows, admission gated on free blocks;
  * ``chunked_shared`` / ``paged_shared`` — the shared-prefix workload:
    every request's prompt starts with the same 16 tokens.  The paged
    column reports ``prefill_tokens_saved`` (> 0: later admissions map
    the shared prefix to already-filled blocks and skip those chunks);
    the dense engine re-prefills the prefix every time.

Emits end-to-end tokens/s per policy, the chunked-vs-serial speedup — the
request-level analogue of Fig. 7's dataflow-restructuring claim — the
sampling/priority overheads vs plain chunked, and the paged engine's
prefill-token saving on the shared-prefix workload.

**Speculative columns** (``spec_*``): the decode-loop restructure.  These
run on a *briefly trained* tiny model, not the random-init reduced arch —
speculation's win depends on the model's own continuations being
predictable, and a random-init model's greedy decode never falls into a
repeatable pattern (verified across seeds), so the n-gram proposer would
sit idle and the column would measure nothing.  Training memorizes a
small fixed bank of periodic patterns (loss ~0.4 in a few hundred steps),
the honest analogue of real models decoding templated/repetitive text.
Four columns, spec ``k`` left to the ``serve_schedule`` planner
(``SpecParams(k=None)``):

  * off/ngram on a **repetitive** workload (prompts drawn from the
    memorized bank): acceptance lands near 1, the planner keeps a long
    draft, and the fused verify amortizes dispatches — speculation must
    *win* here;
  * off/ngram on a **random** workload: drafts rarely survive, the
    observed acceptance rate goes to the next replan, and the planner
    prices speculation with ``core.pipeline.SPEC_VERIFY_OVERHEAD`` extra
    decode-step cost per scored position and turns it **off**
    (``spec_k=0``) — so the only cost is the pre-replan window and the
    column is bounded near 1.0x rather than paying verify overhead all
    run.

Both spec columns also re-assert bit-identical streams vs spec=off.
Timing runs ``SPEC_TRIALS`` alternating off/on pairs and reports the
**median per-pair ratio**: adjacent runs share whatever ambient machine
load exists, so the ratio of a pair is far more stable than any absolute
tokens/s number on a shared box.
"""
from __future__ import annotations

import dataclasses
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, get_config
from repro.models import cache_family as CF
from repro.models.model import Model
from repro.serving import (ReplicaRouter, Request, SamplingParams,
                           ServingEngine, SpecParams, settle_ticks)

from .common import emit, run_on_host_devices

ARCH = "qwen3-1.7b"
REQUESTS = 8
SLOTS = 4
PROMPT_LEN = 24
SHARED_PREFIX = 16
MAX_NEW = 8
MAX_LEN = 64
CHUNK = 8
KV_BLOCK = 8

#: policy name -> (prefill_mode, per-request sampling?, priority mix?,
#:                 kv layout, shared-prefix workload?, kernel plan mode)
POLICIES: dict[str, tuple[str, bool, bool, str, bool, str]] = {
    "serial": ("serial", False, False, "dense", False, "auto"),
    "batched": ("batched", False, False, "dense", False, "auto"),
    "chunked": ("chunked", False, False, "dense", False, "auto"),
    "sampled": ("chunked", True, False, "dense", False, "auto"),
    "sampled_ref": ("chunked", True, False, "dense", False, "off"),
    "mixed": ("chunked", False, True, "dense", False, "auto"),
    "paged": ("chunked", False, False, "paged", False, "auto"),
    "chunked_shared": ("chunked", False, False, "dense", True, "auto"),
    "paged_shared": ("chunked", False, False, "paged", True, "auto"),
}


def _serve(model, params, policy: str, cfg) -> tuple[float, dict]:
    mode, sampled, mixed, kv, shared, planmode = POLICIES[policy]
    engine = ServingEngine(model, params, slots=SLOTS, max_len=MAX_LEN,
                           prefill_mode=mode, chunk=CHUNK, kv=kv,
                           kv_block_size=KV_BLOCK if kv == "paged" else None,
                           kernel_plan="off" if planmode == "off" else None)
    rng = np.random.default_rng(0)
    prefix = rng.integers(0, cfg.vocab, SHARED_PREFIX).astype(np.int32) \
        if shared else None
    reqs = [Request(
        rid=rid,
        prompt=np.concatenate(
            [prefix,
             rng.integers(0, cfg.vocab,
                          PROMPT_LEN - SHARED_PREFIX).astype(np.int32)])
        if shared else
        rng.integers(0, cfg.vocab, PROMPT_LEN).astype(np.int32),
        max_new_tokens=MAX_NEW,
        sampling=SamplingParams(temperature=0.8, top_p=0.95, seed=rid)
        if sampled else None,
        priority=1 if mixed and rid >= REQUESTS - REQUESTS // 4 else 0)
        for rid in range(REQUESTS)]
    late = [r for r in reqs if r.priority > 0]  # empty except under mixed
    t0 = time.perf_counter()
    for r in reqs:
        if r.priority == 0:
            engine.submit(r)
    if late:
        # let the batch settle into decode, then inject the VIPs so they
        # preempt their way in instead of just sorting to the queue front
        for _ in range(settle_ticks(PROMPT_LEN, CHUNK)):
            engine.step()
        for r in late:
            engine.submit(r)
    engine.run()
    dt = time.perf_counter() - t0
    return dt, engine.stats()


# -- speculative columns ------------------------------------------------------

SPEC_CFG = ModelConfig(name="spec-bench-tiny", family="dense", n_layers=2,
                       d_model=64, vocab=64, n_heads=4, n_kv_heads=2,
                       d_ff=128, dtype="float32", param_dtype="float32")
SPEC_TRAIN_STEPS = 300
SPEC_PATTERNS = 8      # fixed pattern bank the training memorizes
SPEC_TRIALS = 5        # alternating off/on timing pairs per workload
SPEC_REQUESTS = 12
SPEC_SLOTS = 2         # narrow decode batch: the off-engine pays per-tick
                       # dispatch on every token, which is the overhead
                       # speculation amortizes k+1-fold
SPEC_MAX_NEW = 160     # long decodes keep the run decode-dominated —
SPEC_MAX_LEN = 192     # prefill and engine setup dilute the spec signal
SPEC_CHUNK = 16


def _spec_pattern_bank():
    rng = np.random.default_rng(0)
    return [rng.integers(2, SPEC_CFG.vocab, int(rng.integers(2, 5)))
            for _ in range(SPEC_PATTERNS)], rng


def _train_spec_model():
    """A tiny model trained to memorize the fixed pattern bank, so its
    greedy continuations on bank prompts are predictable by prompt lookup
    (see module docstring — random-init weights never are).  Training
    sequences span the full serving horizon (``SPEC_MAX_LEN``): a model
    trained only on short windows drifts off-pattern at the RoPE
    positions it never saw, and every drift costs a rejected draft."""
    model = Model(SPEC_CFG)
    state = model.init_train_state(jax.random.key(0))
    step = jax.jit(lambda s, b: model.train_step(s, b))
    patterns, rng = _spec_pattern_bank()

    def batch(B=16, S=SPEC_MAX_LEN + 1):
        toks = np.zeros((B, S), np.int32)
        for b in range(B):
            pat = patterns[int(rng.integers(0, len(patterns)))]
            off = int(rng.integers(0, len(pat)))   # phase augmentation
            toks[b] = np.tile(pat, S // len(pat) + 2)[off:off + S]
        return {"tokens": jnp.asarray(toks[:, :-1]),
                "labels": jnp.asarray(toks[:, 1:])}

    for _ in range(SPEC_TRAIN_STEPS):
        state, _ = step(state, batch())
    return model, state.params, patterns


def _spec_requests(rng, patterns, repetitive: bool) -> list[Request]:
    reqs = []
    for rid in range(SPEC_REQUESTS):
        if repetitive:
            pat = patterns[rid % len(patterns)]
            prompt = np.tile(pat, 12)[:int(rng.integers(10, 20))]
        else:
            prompt = rng.integers(2, SPEC_CFG.vocab,
                                  int(rng.integers(10, 20)))
        reqs.append(Request(rid=rid, prompt=prompt.astype(np.int32),
                            max_new_tokens=SPEC_MAX_NEW))
    return reqs


def _spec_serve(model, params, reqs, spec: SpecParams | None
                ) -> tuple[float, object, list[list[int]]]:
    kw = dict(spec=spec) if spec is not None else {}
    # replan_every=8: the spec-k planner adapts after one short window —
    # on random text it zeroes the draft length there, bounding the
    # regression to the first few ticks' verify tax
    engine = ServingEngine(model, params, slots=SPEC_SLOTS,
                           max_len=SPEC_MAX_LEN, chunk=SPEC_CHUNK,
                           replan_every=8, **kw)
    rs = [Request(rid=r.rid, prompt=r.prompt.copy(),
                  max_new_tokens=r.max_new_tokens) for r in reqs]
    t0 = time.perf_counter()
    for r in rs:
        engine.submit(r)
    engine.run()
    dt = time.perf_counter() - t0
    return dt, engine, [list(r.generated) for r in rs]


def run_spec() -> dict[str, float]:
    model, params, patterns = _train_spec_model()
    rng = np.random.default_rng(1)
    workloads = {"repetitive": _spec_requests(rng, patterns, True),
                 "random": _spec_requests(rng, patterns, False)}
    spec = SpecParams(mode="ngram")     # k=None: serve_schedule plans it
    tps: dict[str, float] = {}
    for wname, reqs in workloads.items():
        # warmup passes put compilation off the clock for both engines;
        # several are needed because replans adopt chunk budgets from
        # *observed* (noisy) timings — each new budget is a fresh trace
        for _ in range(3):
            _spec_serve(model, params, reqs, None)
            _spec_serve(model, params, reqs, spec)
        dt_off = dt_on = float("inf")
        ratios = []
        for _ in range(SPEC_TRIALS):
            d_off, _, out_off = _spec_serve(model, params, reqs, None)
            d_on, engine, out_on = _spec_serve(model, params, reqs, spec)
            assert out_on == out_off, \
                f"spec changed the {wname} streams — equivalence broken"
            ratios.append(d_off / d_on)     # >1: speculation was faster
            dt_off, dt_on = min(dt_off, d_off), min(dt_on, d_on)
        ratio = float(np.median(ratios))
        toks = sum(len(o) for o in out_off)
        tps[f"off_{wname}"] = toks / dt_off
        tps[f"ngram_{wname}"] = tps[f"off_{wname}"] * ratio
        sp = engine.stats()["spec"]
        emit(f"serving.spec.{wname}.off", dt_off / toks,
             f"tokens_per_s={toks / dt_off:.1f}")
        emit(f"serving.spec.{wname}.ngram", dt_on / toks,
             f"tokens_per_s={toks / dt_on:.1f};"
             f"median_pair_ratio={ratio:.2f};"
             f"accept_rate={sp['accept_rate']:.3f};"
             f"planned_k={engine.scheduler.cfg.spec_k};"
             f"drafts_proposed={sp['drafts_proposed']};"
             f"verify_calls={sp['verify_calls']};"
             f"spec_tokens={sp['spec_tokens']}")
    return tps


# -- device-count scaling (mesh shards + engine replicas) ---------------------
#
# Two orthogonal axes, recorded in ``BENCH_serving.json``:
#
#   * **mesh shards** (1/2/4 devices): on the CPU each shard count runs
#     in a CPU-pinned *subprocess* with
#     ``--xla_force_host_platform_device_count`` (the device count is
#     locked at first backend init); on an accelerator it runs in this
#     process over the real devices, up to as many as there are.  On one
#     physical core the forced devices timeshare it, so wall-clock does
#     not improve — the honest scaling signal reported is the per-shard
#     KV footprint (bytes/device drop 1/n, which is exactly what concat-TP
#     buys an edge deployment) plus the measured tok/s for the record;
#   * **replicas** (1/2/4 routed engines): weak scaling — the workload
#     grows with the fleet so every replica decodes full batches.  The
#     aggregate is the sum of per-replica busy-time decode rates: the
#     fleet throughput when replicas own their devices (d-Xenos), the
#     capacity projection when they timeshare one host.  Monotonic growth
#     with replica count is the acceptance bar.

SCALE_SHARDS = (1, 2, 4)
SCALE_REPLICAS = (1, 2, 4)
SCALE_REQS_PER_REPLICA = 2 * SLOTS   # two full admission waves per replica
BENCH_JSON = Path(__file__).resolve().parent.parent / "BENCH_serving.json"

#: every dim divisible by 4 shards (kv heads are the binding axis)
SHARD_CFG = ModelConfig(name="scale-tiny", family="dense", n_layers=2,
                        d_model=128, vocab=96, n_heads=8, n_kv_heads=4,
                        d_ff=256, dtype="float32", param_dtype="float32")


def shard_row(shards: int) -> dict:
    """One concat-TP serving run over ``shards`` of this process's devices:
    wall-clock, decode rate and the KV bytes each device holds per block."""
    from repro.launch.mesh import make_serving_mesh
    cfg = SHARD_CFG
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    mesh = make_serving_mesh(shards) if shards > 1 else None

    def serve():
        eng = ServingEngine(model, params, slots=2, max_len=64, chunk=8,
                            prefill_mode="chunked", replan_every=10_000,
                            kv="paged", kv_block_size=8, mesh=mesh)
        rng = np.random.default_rng(0)
        reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab, 16)
                        .astype(np.int32), max_new_tokens=8)
                for i in range(4)]
        t0 = time.perf_counter()
        for r in reqs:
            eng.submit(r)
        eng.run()
        return time.perf_counter() - t0, eng.stats()

    serve()                       # compile off the clock
    dt, stats = serve()
    kp = stats["kv_pool"]
    per_block = kp.get("per_shard", {}).get("block_bytes")
    if per_block is None:         # unsharded: dense per-block payload
        per_block = (2 * kp["block_size"] * cfg.n_kv_heads
                     * cfg.resolved_head_dim
                     * jnp.dtype(cfg.dtype).itemsize)
    return {"shards": shards, "devices": len(jax.devices()), "wall_s": dt,
            "decode_tokens_per_s": stats.get("decode_tokens_per_s", 0.0),
            "kv_bytes_per_block_per_device": int(per_block)}


def _bench_shards(shards: int) -> dict:
    """A shard row on the CPU runs in a child with ``shards`` forced host
    devices (the count is locked at backend init); on an accelerator it
    runs here, on this process's own devices."""
    if jax.default_backend() != "cpu":
        return shard_row(shards)
    out = run_on_host_devices(
        "import json\n"
        "from benchmarks.serving_throughput import shard_row\n"
        f"print('SCALE_JSON ' + json.dumps(shard_row({shards})))\n",
        shards)
    line = next(l for l in out.splitlines() if l.startswith("SCALE_JSON "))
    return json.loads(line[len("SCALE_JSON "):])


def _bench_replicas(model, params, cfg, replicas: int) -> dict:
    devices = jax.devices()

    def build(i):
        return ServingEngine(model, params, slots=SLOTS, max_len=MAX_LEN,
                             chunk=CHUNK, prefill_mode="chunked",
                             replan_every=10_000, kv="paged",
                             kv_block_size=KV_BLOCK,
                             device=devices[i % len(devices)])
    router = ReplicaRouter([build(i) for i in range(replicas)])
    rng = np.random.default_rng(0)
    n_req = SCALE_REQS_PER_REPLICA * replicas
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab, PROMPT_LEN)
                    .astype(np.int32),
                    max_new_tokens=MAX_NEW) for i in range(n_req)]
    t0 = time.perf_counter()
    for r in reqs:
        router.submit(r)
    router.run()
    dt = time.perf_counter() - t0
    s = router.stats()
    toks = sum(len(r.generated) for r in reqs)
    return {"replicas": replicas, "requests": n_req, "tokens": toks,
            "wall_s": dt, "overall_tokens_per_s": toks / dt,
            "aggregate_decode_tokens_per_s":
                s["aggregate_decode_tokens_per_s"],
            "dispatched": s["dispatched"]}


def run_scaling() -> None:
    # shard rows first: on the CPU their children start before this
    # process holds any array
    shard_rows = [_bench_shards(s) for s in SCALE_SHARDS
                  if jax.default_backend() == "cpu"
                  or s <= len(jax.devices())]
    cfg = get_config(ARCH).reduced()
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    _bench_replicas(model, params, cfg, 1)    # compile off the clock

    replica_rows = [_bench_replicas(model, params, cfg, r)
                    for r in SCALE_REPLICAS]

    record = {
        "generated_by": "benchmarks/serving_throughput.py run_scaling",
        "host": {"physical_devices": len(jax.devices()),
                 "note": "forced CPU devices timeshare the host; shard "
                         "wall-clock is not a speedup claim, the per-"
                         "device KV byte column is the scaling signal; "
                         "replica aggregate is the fleet capacity "
                         "projection (sum of busy-time decode rates)"},
        "mesh_shards": shard_rows,
        "replicas": replica_rows,
    }
    _merge_bench_json(record)

    for row in shard_rows:
        emit(f"serving.scale.shards{row['shards']}", row["wall_s"],
             f"decode_tokens_per_s={row['decode_tokens_per_s']:.1f};"
             f"kv_bytes_per_block_per_device="
             f"{row['kv_bytes_per_block_per_device']}")
    for row in replica_rows:
        emit(f"serving.scale.replicas{row['replicas']}", row["wall_s"],
             f"aggregate_decode_tokens_per_s="
             f"{row['aggregate_decode_tokens_per_s']:.1f};"
             f"overall_tokens_per_s={row['overall_tokens_per_s']:.1f};"
             f"requests={row['requests']}")
    aggs = [r["aggregate_decode_tokens_per_s"] for r in replica_rows]
    mono = all(b > a for a, b in zip(aggs, aggs[1:]))
    emit("serving.scale.takeaways", 0.0,
         f"replica_aggregate_monotonic={mono};"
         f"aggregate_1_to_{SCALE_REPLICAS[-1]}="
         f"{aggs[-1] / aggs[0]:.2f}x;"
         f"per_device_kv_1_to_{SCALE_SHARDS[-1]}="
         f"{shard_rows[0]['kv_bytes_per_block_per_device'] / shard_rows[-1]['kv_bytes_per_block_per_device']:.1f}x")


def _merge_bench_json(record: dict) -> None:
    """Update ``BENCH_serving.json`` in place: ``run_scaling`` and
    ``run_families`` each own their keys, neither clobbers the other."""
    existing = {}
    if BENCH_JSON.exists():
        try:
            existing = json.loads(BENCH_JSON.read_text())
        except json.JSONDecodeError:
            existing = {}
    existing.update(record)
    BENCH_JSON.write_text(json.dumps(existing, indent=2) + "\n")


# -- cache-family rows: the long-chat KV-footprint column ---------------------
#
# The long-chat workload: prompts past the sliding window, so by the time
# a request decodes its ring has already wrapped.  One row per dataflow
# shape — ``full`` (classic paged pool, KV grows to the horizon),
# ``sliding`` (ring-paged, the same arch with a window: the lease is
# window-sized *forever*), ``ssm`` and ``hybrid`` (constant recurrent
# state) — reporting tokens/s and the KV bytes a live request actually
# holds mid-decode.  The sliding-vs-full byte ratio is the O(window) vs
# O(seq) claim, measured from the pool's own accounting rather than
# asserted; the ssm row's bytes don't change with context length at all
# (``kv_growth="constant"`` in the serve_schedule plan).

FAMILY_WINDOW = 32           # tokens; 4 ring blocks of KV_BLOCK=8
FAMILY_PROMPT = 48           # > window: the ring wraps during prefill
FAMILY_MAX_NEW = 8
FAMILY_MAX_LEN = 128
FAMILY_SLOTS = 2
FAMILY_REQUESTS = 4

FAMILY_ROWS = ("full", "sliding", "mixed", "ssm", "hybrid")


def _family_setup(row: str):
    if row in ("full", "sliding", "mixed"):
        cfg = get_config(ARCH).reduced()
        if row == "sliding":
            cfg = dataclasses.replace(cfg, name=cfg.name + "-swa",
                                      sliding_window=FAMILY_WINDOW)
        elif row == "mixed":
            # the heterogeneous stack: same arch, alternating sliding and
            # global layers (gemma3-style) — its long-chat KV must land
            # strictly between the all-sliding and all-full rows
            cfg = dataclasses.replace(cfg, name=cfg.name + "-mixed",
                                      sliding_window=FAMILY_WINDOW,
                                      layer_pattern="SG")
        kw = dict(kv="paged", kv_block_size=KV_BLOCK)
    elif row == "ssm":
        cfg = get_config("mamba2-370m").reduced()
        kw = dict(kv="dense")
    else:
        cfg = get_config("hymba-1.5b").reduced()
        kw = dict(kv="dense")
    model = Model(cfg)
    return cfg, model, model.init(jax.random.key(0)), kw


def _state_bytes(cfg) -> int:
    """Constant recurrent footprint per request: SSD state + conv tail."""
    conv_dim = cfg.ssm_inner + 2 * cfg.ssm_groups * cfg.ssm_state
    per_layer = (cfg.ssm_heads * cfg.ssm_head_dim * cfg.ssm_state
                 + (cfg.ssm_conv - 1) * conv_dim)
    return per_layer * 4 * cfg.n_layers


def _family_serve(cfg, model, params, kw) -> tuple[float, dict, int]:
    eng = ServingEngine(model, params, slots=FAMILY_SLOTS,
                        max_len=FAMILY_MAX_LEN, chunk=CHUNK,
                        prefill_mode="chunked", replan_every=10_000, **kw)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab, FAMILY_PROMPT)
                    .astype(np.int32),
                    max_new_tokens=FAMILY_MAX_NEW)
            for i in range(FAMILY_REQUESTS)]
    t0 = time.perf_counter()
    for r in reqs:
        eng.submit(r)
    # drive the first admission wave into decode, then snapshot the KV a
    # live request holds — past the window for the sliding row, so the
    # ring has wrapped and the lease is still window-sized
    kv_bytes = 0
    for _ in range(3000):
        eng.step()
        decoding = [r for r in reqs if len(r.generated) >= 2 and not r.done]
        if decoding:
            if eng.pool is not None:
                ps = eng.pool.stats()
                live = max(ps["live_requests"], 1)
                per_block_layer = (2 * eng.pool.cfg.block_size
                                   * cfg.n_kv_heads
                                   * cfg.resolved_head_dim * 4)
                if ps.get("kind") == "mixed":
                    # per-kind accounting: the classic lease backs only
                    # the global layers, the ring lease only the sliding
                    # ones — multiplying either count by n_layers would
                    # double-book the other kind's layers
                    fams = CF.layer_cache_families(cfg)
                    n_slide = sum(f.kv == "sliding" for f in fams)
                    kv_bytes = (ps["classic"]["blocks_in_use"]
                                * (len(fams) - n_slide)
                                + ps["ring"]["blocks_in_use"] * n_slide) \
                        * per_block_layer // live
                else:
                    kv_bytes = (ps["blocks_in_use"] * per_block_layer
                                * cfg.n_layers // live)
            else:
                kv_bytes = _state_bytes(cfg)
                if cfg.family == "hybrid":
                    # dense per-slot attention rows: the whole horizon
                    kv_bytes += (2 * FAMILY_MAX_LEN * cfg.n_kv_heads
                                 * cfg.resolved_head_dim * 4 * cfg.n_layers)
            break
    eng.run()
    dt = time.perf_counter() - t0
    toks = sum(len(r.generated) for r in reqs)
    assert all(r.done for r in reqs) and toks > 0
    return dt, eng.stats(), kv_bytes


def run_families() -> None:
    rows = []
    for row in FAMILY_ROWS:
        cfg, model, params, kw = _family_setup(row)
        _family_serve(cfg, model, params, kw)      # compile off the clock
        dt, stats, kv_bytes = _family_serve(cfg, model, params, kw)
        toks = FAMILY_REQUESTS * FAMILY_MAX_NEW
        rec = {"row": row, "arch": cfg.name, "family": cfg.family,
               "sliding_window": cfg.sliding_window, "kv": kw["kv"],
               "prompt_len": FAMILY_PROMPT, "max_new": FAMILY_MAX_NEW,
               "wall_s": dt, "tokens_per_s": toks / dt,
               "decode_tokens_per_s":
                   stats.get("decode_tokens_per_s", 0.0),
               "kv_bytes_held_per_request": int(kv_bytes),
               "kv_growth": stats["plan"].get("kv_growth", "linear")}
        rows.append(rec)
        emit(f"serving.family.{row}", dt / toks,
             f"tokens_per_s={rec['tokens_per_s']:.1f};"
             f"decode_tokens_per_s={rec['decode_tokens_per_s']:.1f};"
             f"kv_bytes_held_per_request={rec['kv_bytes_held_per_request']};"
             f"kv_growth={rec['kv_growth']}")
    by = {r["row"]: r for r in rows}
    ratio = (by["full"]["kv_bytes_held_per_request"]
             / max(by["sliding"]["kv_bytes_held_per_request"], 1))
    # the heterogeneous stack's claim, measured not asserted-by-hand: a
    # mixed lease (full-horizon classic blocks on the global layers, a
    # window-sized ring on the sliding ones) holds strictly less KV than
    # the all-full stack and strictly more than the all-sliding one
    assert (by["sliding"]["kv_bytes_held_per_request"]
            < by["mixed"]["kv_bytes_held_per_request"]
            < by["full"]["kv_bytes_held_per_request"]), (
        "mixed-stack KV footprint did not land between sliding and full: "
        f"{by['sliding']['kv_bytes_held_per_request']} vs "
        f"{by['mixed']['kv_bytes_held_per_request']} vs "
        f"{by['full']['kv_bytes_held_per_request']}")
    emit("serving.family.takeaways", 0.0,
         f"sliding_kv_saving_vs_full={ratio:.2f}x;"
         f"mixed_kv_between_sliding_and_full="
         f"{by['sliding']['kv_bytes_held_per_request']}<"
         f"{by['mixed']['kv_bytes_held_per_request']}<"
         f"{by['full']['kv_bytes_held_per_request']};"
         f"window={FAMILY_WINDOW};prompt={FAMILY_PROMPT};"
         f"ssm_kv_growth={by['ssm']['kv_growth']};"
         f"hybrid_kv_growth={by['hybrid']['kv_growth']}")
    _merge_bench_json({"families": {
        "workload": {"prompt_len": FAMILY_PROMPT, "max_new": FAMILY_MAX_NEW,
                     "window": FAMILY_WINDOW, "max_len": FAMILY_MAX_LEN,
                     "note": "kv_bytes_held_per_request is snapshotted "
                             "mid-decode from the pool's own accounting "
                             "(ring leases stay window-sized after the "
                             "ring wraps) or the constant-state shapes"},
        "rows": rows}})


def run() -> None:
    cfg = get_config(ARCH).reduced()
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    total_tokens = REQUESTS * MAX_NEW

    # one throwaway pass per policy so jit compilation is off the clock
    for policy in POLICIES:
        _serve(model, params, policy, cfg)

    times = {}
    saved = {}
    for policy in POLICIES:
        dt, stats = _serve(model, params, policy, cfg)
        times[policy] = dt
        saved[policy] = stats.get("prefill_tokens_saved", 0)
        kplan = ",".join(f"{k}:{v}"
                         for k, v in sorted(stats["kernel_plan"].items()))
        emit(f"serving.{ARCH}.{policy}", dt / total_tokens,
             f"tokens_per_s={total_tokens / dt:.1f};"
             f"decode_tokens_per_s={stats.get('decode_tokens_per_s', 0):.1f};"
             f"chunk={stats['plan']['chunk']};"
             f"preempted={stats['scheduler']['preempted']};"
             f"prefill_tokens_saved={saved[policy]};"
             f"kernel_plan={kplan}")
    emit(f"serving.{ARCH}.takeaways", 0.0,
         f"batched_speedup_vs_serial={times['serial'] / times['batched']:.2f}x;"
         f"chunked_speedup_vs_serial={times['serial'] / times['chunked']:.2f}x;"
         f"sampling_overhead_vs_chunked={times['sampled'] / times['chunked']:.2f}x;"
         f"sampling_overhead_reference={times['sampled_ref'] / times['chunked']:.2f}x;"
         f"priority_overhead_vs_chunked={times['mixed'] / times['chunked']:.2f}x;"
         f"paged_overhead_vs_chunked={times['paged'] / times['chunked']:.2f}x;"
         f"paged_shared_prefill_tokens_saved={saved['paged_shared']};"
         f"paged_shared_speedup_vs_dense_shared="
         f"{times['chunked_shared'] / times['paged_shared']:.2f}x")

    tps = run_spec()
    emit("serving.spec.takeaways", 0.0,
         f"spec_speedup_repetitive="
         f"{tps['ngram_repetitive'] / tps['off_repetitive']:.2f}x;"
         f"spec_ratio_random="
         f"{tps['ngram_random'] / tps['off_random']:.2f}x")

    run_families()
    run_scaling()


if __name__ == "__main__":
    run()
