"""End-to-end serving driver: scheduler-planned continuous batching.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b --reduced \
        --requests 8 --prompt-len 16 --max-new 12 \
        --temperature 0.8 --top-p 0.95 --eos-id 7 --priority-mix 0,1

Requests go through the scheduler subsystem (``repro.serving.scheduler``):
priority-then-FIFO batched admission (with bounded preemption), chunked
prefill interleaved with decode, and the ``serve_schedule`` pass
re-planning the chunk budget / prefill mode from observed stage stats.
Each request carries its own SamplingParams (``--temperature 0`` is exact
greedy; every request gets its own PRNG stream, seeded ``--seed + rid``).
Throughput is computed from the tokens requests *actually* emitted — with
``--eos-id`` set, a request may retire well before ``--max-new``, and with
``--spec`` the verify forward scores draft positions the target may
reject: scored-but-rejected positions are **never** counted as emissions
(they appear separately in the spec report as drafts/sec and the
accepted-per-draft ratio).  Exits nonzero when the batched decode loop
produced no throughput — CI runs this as the serving smoke check.
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
import time

import jax
import numpy as np

from repro.configs.base import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_serving_mesh
from repro.models.model import Model
from repro.serving import (ReplicaRouter, Request, SamplingParams,
                           ServingEngine, SpecParams, settle_ticks)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--sliding-window", type=int, default=None,
                    help="serve the arch with this sliding-attention "
                         "window (tokens): per-request KV stays O(window) "
                         "— with --kv paged the pool runs window-sized "
                         "ring block tables; logits are identical to full "
                         "attention while context <= window")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--chunk", type=int, default=32)
    ap.add_argument("--prefill-mode", default=None,
                    choices=[None, "chunked", "batched", "serial"],
                    help="default: auto (chunked for attention archs, "
                         "batched for recurrent ones, then re-chosen by "
                         "serve_schedule from observed stats); serial is "
                         "the pre-scheduler one-at-a-time baseline")
    ap.add_argument("--replan-every", type=int, default=32)
    ap.add_argument("--kv", default="dense", choices=["dense", "paged"],
                    help="KV cache layout: 'dense' pre-allocates max-len "
                         "per slot; 'paged' allocates fixed-size blocks "
                         "per request from a pool, with shared prompt "
                         "prefixes mapped to the same blocks (requires "
                         "chunked prefill on an attention arch; a "
                         "sliding-window arch pages a wraparound ring "
                         "sized to the window)")
    ap.add_argument("--kv-block-size", type=int, default=None,
                    help="tokens per KV block (default: planned by the "
                         "serve_schedule pass)")
    ap.add_argument("--kv-pool-blocks", type=int, default=None,
                    help="physical blocks in the pool (default: planned; "
                         "smaller pools gate admission on free blocks)")
    ap.add_argument("--spec", default="off",
                    choices=["off", "ngram", "draft"],
                    help="speculative decoding: 'ngram' self-drafts via "
                         "prompt lookup over each request's own context; "
                         "'draft' runs the arch's reduced config as a "
                         "draft model (own params, greedy proposals); "
                         "either way committed streams are bit-identical "
                         "to spec=off")
    ap.add_argument("--spec-k", type=int, default=None,
                    help="draft tokens per verify step (default: planned "
                         "by serve_schedule from the observed acceptance "
                         "rate; 0 disables drafting)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy argmax (the default policy)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="keep the k most likely tokens (0 disables)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus sampling mass (1.0 disables)")
    ap.add_argument("--eos-id", type=int, default=-1,
                    help="token id that retires a request early (<0 = none)")
    ap.add_argument("--priority-mix", default="0",
                    help="comma-separated priorities assigned round-robin "
                         "to requests; higher admits first and may preempt "
                         "lower DECODE slots (e.g. '0,0,0,1')")
    ap.add_argument("--mesh-shards", type=int, default=1,
                    help="shard each engine's decode/prefill hot path over "
                         "this many mesh devices (concat tensor "
                         "parallelism: per-shard KV pools, bit-identical "
                         "outputs); exits nonzero if the host has fewer "
                         "devices — no silent single-device fallback")
    ap.add_argument("--replicas", type=int, default=1,
                    help="independent engine replicas behind one router "
                         "(least-loaded + prefix-affinity dispatch); "
                         "composes with --mesh-shards")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.sliding_window is not None:
        if args.sliding_window <= 0:
            raise SystemExit("--sliding-window must be positive")
        cfg = dataclasses.replace(
            cfg, name=f"{cfg.name}-swa{args.sliding_window}",
            sliding_window=args.sliding_window)
    if cfg.is_encoder_decoder:
        raise SystemExit("serve.py drives decoder-only archs; for seamless "
                         "see examples/translate_audio.py")
    priorities = [int(x) for x in args.priority_mix.split(",")]
    model = Model(cfg)
    params = model.init(jax.random.key(args.seed))
    prefill_mode = args.prefill_mode
    if args.kv == "paged" and prefill_mode is None:
        prefill_mode = "chunked"  # the only mode a block pool can execute
    mesh = None
    if args.mesh_shards > 1:
        try:
            mesh = make_serving_mesh(args.mesh_shards)
        except ValueError as e:
            # no silent fallback: a sharded deployment that quietly runs
            # on one device reports throughput that does not exist
            print(f"FAIL: {e}", file=sys.stderr)
            return 2
        if prefill_mode is None:
            prefill_mode = "chunked"  # the only shard-threaded prefill
    spec_kw = {}
    if args.spec != "off":
        spec_kw["spec"] = SpecParams(mode=args.spec, k=args.spec_k)
        if args.spec == "draft":
            draft_cfg = cfg.reduced()
            draft = Model(draft_cfg)
            spec_kw["draft_model"] = draft
            spec_kw["draft_params"] = draft.init(
                jax.random.key(args.seed + 1))
    def build_engine(device=None):
        return ServingEngine(model, params, slots=args.slots,
                             max_len=args.max_len, chunk=args.chunk,
                             eos_id=args.eos_id,
                             prefill_mode=prefill_mode,
                             replan_every=args.replan_every,
                             kv=args.kv, kv_block_size=args.kv_block_size,
                             kv_pool_blocks=args.kv_pool_blocks,
                             mesh=mesh, device=device, **spec_kw)

    router = None
    if args.replicas > 1:
        # one-device replicas spread round-robin over the host's devices;
        # mesh replicas share the mesh
        devices = jax.devices()
        router = ReplicaRouter([
            build_engine(None if mesh is not None
                         else devices[i % len(devices)])
            for i in range(args.replicas)])
        engine = router.engines[0]
        submit, step, run_all = router.submit, router.step, router.run
    else:
        engine = build_engine()
        submit, step, run_all = engine.submit, engine.step, engine.run
    rng = np.random.default_rng(args.seed)
    reqs = []
    for rid in range(args.requests):
        reqs.append(Request(
            rid=rid,
            prompt=rng.integers(0, cfg.vocab, size=args.prompt_len).astype(np.int32),
            max_new_tokens=args.max_new,
            sampling=SamplingParams(temperature=args.temperature,
                                    top_k=args.top_k, top_p=args.top_p,
                                    seed=args.seed + rid),
            priority=priorities[rid % len(priorities)]))
    # above-baseline priorities arrive *after* the batch settles into
    # decode — submitted up-front they would merely sort to the queue
    # head, and the preemption path the flag advertises would never run
    base = min(priorities)
    vips = [r for r in reqs if r.priority > base]
    t0 = time.time()
    for r in reqs:
        if r.priority == base:
            submit(r)
    if vips:
        for _ in range(settle_ticks(args.prompt_len, args.chunk)):
            step()
        for r in vips:
            submit(r)
    run_all()
    dt = time.time() - t0
    stats = engine.stats()
    # actual emission, not requests * max_new: EOS retires requests early
    total_tokens = sum(len(r.generated) for r in reqs)
    eos_stopped = sum(1 for r in reqs
                      if args.eos_id >= 0 and r.generated
                      and r.generated[-1] == args.eos_id)
    decode_tps = stats.get("decode_tokens_per_s", 0.0)
    if router is not None:
        rstats = router.stats()
        decode_tps = rstats.get("aggregate_decode_tokens_per_s", 0.0)
    print(f"served {args.requests} requests, {total_tokens} tokens "
          f"in {dt:.2f}s ({total_tokens / dt:.1f} tok/s overall, "
          f"{decode_tps:.1f} tok/s batched decode)")
    if router is not None:
        print(f"router: {rstats['replicas']} replicas, "
              f"{rstats['dispatched']} dispatched, "
              f"{rstats['affinity_hits']} affinity hits, aggregate decode "
              f"capacity {decode_tps:.1f} tok/s")
        for i, per in enumerate(rstats["per_replica"]):
            print(f"  replica {i}: {per['tokens_out']} tokens out, "
                  f"{per.get('decode_tokens_per_s', 0.0):.1f} tok/s decode")
    if "mesh_shards" in stats:
        print(f"mesh: {stats['mesh_shards']}-way concat-TP "
              f"({len(jax.devices())} devices visible)")
    print(f"policy: temperature={args.temperature} top_k={args.top_k} "
          f"top_p={args.top_p} eos_id={args.eos_id} "
          f"priorities={priorities}; {eos_stopped} requests stopped at EOS, "
          f"{stats['scheduler']['preempted']} preemptions")
    print(f"plan: {stats['plan']} (prefill_mode={stats['prefill_mode']}, "
          f"kv={stats['kv']})")
    if "spec" in stats:
        sp = stats["spec"]
        # emissions vs draft traffic are different currencies: the verify
        # forward scores draft positions, the target keeps only the
        # accepted prefix — report them side by side, never summed
        print(f"spec: mode={sp['mode']} k={sp['k']} — "
              f"{total_tokens} tokens emitted, "
              f"{sp['drafts_proposed']} drafts proposed "
              f"({sp['drafts_proposed'] / dt:.1f} drafts/s), "
              f"{sp['drafts_accepted']} accepted "
              f"(accept ratio {sp['accept_rate']:.2f}), "
              f"{sp['spec_tokens']} tokens via {sp['verify_calls']} "
              f"verify dispatches")
    if "kv_pool" in stats:
        kp = stats["kv_pool"]
        print(f"kv pool: {kp['pool_blocks']} x {kp['block_size']}-token "
              f"blocks, {kp['registered_prefixes']} cached prefixes, "
              f"{kp['prefill_tokens_saved']} prefill tokens saved, "
              f"{kp['gated_requests']} requests block-gated")
        if "per_shard" in kp:
            ps = kp["per_shard"]
            print(f"  per shard: {ps['kv_heads']} kv heads, "
                  f"{ps['block_bytes']} B/block, "
                  f"{ps['pool_bytes'] / 1e6:.2f} MB pool payload")
    for stage, s in stats["stages"].items():
        print(f"  stage {stage}: {s['calls']} calls, "
              f"mean {s['mean_s'] * 1e3:.2f} ms")
    if "plan_cache_hit" in stats:
        print(f"  serve_schedule replan cache_hit={stats['plan_cache_hit']}")
    if not all(r.done for r in reqs):
        print("FAIL: not every request completed", file=sys.stderr)
        return 1
    if not decode_tps > 0:
        print("FAIL: batched decode produced no throughput", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    enable_compile_cache()
    sys.exit(main())
