"""Batched serving example: continuous batching over 12 requests on a
reduced assigned architecture (including an SSM to show O(1)-state decode),
with per-request sampling policies and a late high-priority request that
preempts its way past the decode batch.

    PYTHONPATH=src python examples/serve_batch.py --arch mamba2-370m
"""
import argparse
import time

import jax
import numpy as np

from repro.configs.base import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import Model
from repro.serving import (Request, SamplingParams, ServingEngine,
                           settle_ticks)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=10)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch).reduced()
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    engine = ServingEngine(model, params, slots=args.slots, max_len=96)
    rng = np.random.default_rng(0)
    # even rids decode greedily, odd rids sample their own seeded stream
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab, 8 + (i % 5)).astype(np.int32),
                    max_new_tokens=args.max_new,
                    sampling=None if i % 2 == 0 else
                    SamplingParams(temperature=0.8, top_p=0.95, seed=i))
            for i in range(args.requests)]
    for r in reqs:
        engine.submit(r)
    t0 = time.time()
    # let the batch settle into decode, then submit a high-priority request:
    # it preempts the lowest-priority DECODE slot instead of queueing
    for _ in range(settle_ticks(12, engine.scheduler.cfg.chunk)):
        engine.step()
    vip = Request(rid=args.requests, prompt=reqs[0].prompt.copy(),
                  max_new_tokens=args.max_new, priority=5)
    engine.submit(vip)
    reqs.append(vip)
    engine.run()
    dt = time.time() - t0
    for r in reqs[:4]:
        print(f"req {r.rid}: prompt_len={len(r.prompt)} -> {r.generated}")
    done = sum(r.done for r in reqs)
    toks = sum(len(r.generated) for r in reqs)
    finish_order = [s.req.rid for s in engine.scheduler.retired]
    print(f"{done}/{len(reqs)} done, {toks} tokens in {dt:.2f}s "
          f"({toks / dt:.1f} tok/s, {args.slots} slots)")
    stats = engine.stats()
    print(f"vip (rid={vip.rid}, priority=5) finished "
          f"#{finish_order.index(vip.rid) + 1} of {len(reqs)}; "
          f"{stats['scheduler']['preempted']} preemptions")
    print(f"scheduler plan: {stats['plan']}")
    for stage, s in stats["stages"].items():
        print(f"  stage {stage}: {s['calls']} calls, "
              f"mean {s['mean_s'] * 1e3:.2f} ms")
    assert done == len(reqs)
    if len(reqs) > args.slots + 1:
        # only meaningful oversubscribed: with every request already in a
        # slot there is no queue tail for the VIP to overtake
        assert finish_order.index(vip.rid) < len(reqs) - 1, \
            "high-priority request should overtake the tail of the queue"
    print("serve_batch OK")


if __name__ == "__main__":
    enable_compile_cache()
    main()
