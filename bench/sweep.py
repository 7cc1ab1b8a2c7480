"""Find an open-loop cell's knee: the highest arrival rate it sustains.

    python3 bench/sweep.py --workload <cell> --rates 0.2,0.25,0.3 \
        --seeds 11,12 --seconds 51 --prime 32

Runs the cell once per seed and rate in one process (its traffic file's
rate, and with ``--prime`` the number of requests in flight when the warm
phase opens, are replaced; nothing else) and prints one JSON line each.

A request holds its slot for its prompt in chunks and then one engine
tick per output token: about two minutes in a chat mix, longer than a
run's warm phase and window together.  So a run cannot show a backlog
building: requests that leave soon after the run starts free slots for
the window's, and the queue drains at any rate near the knee.  What a run
does measure is the tick rate with every slot busy (``--prime`` = slots
opens it so).  The knee follows by Little's law: ``capacity_rps`` = slots
× ticks per second over the mean ticks a request of the mix needs, taken
over 100,000 draws from the mix's own length distributions.  A cell is
run at about four fifths of the least ``capacity_rps`` over every seed and
rate, written into its traffic file.  Each line also gives the queue of
requests waiting for a slot at the window's ends and the first-token
latency of its first and last quarter, which say whether a rate already
queues within the run.
"""
from __future__ import annotations

import argparse
import gc
import json
import statistics
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import run, stats, traffic  # noqa: E402

#: seconds at each end of the window over which the queue is averaged
EDGE_S = 5.0


def queue(ticks, lo: float, hi: float) -> float:
    q = [t.waiting for t in ticks if lo <= t.t1 <= hi]
    return statistics.fmean(q) if q else 0.0


def mean_need(mix: dict, chunk: int, n: int = 100_000) -> float:
    """Mean engine ticks a request of ``mix`` holds a slot: its prompt in
    chunks, then one tick per output token."""
    rng = np.random.default_rng(int(mix["shape_seed"]))
    prompt = traffic.lengths(mix["prompt"], n, rng)
    output = traffic.lengths(mix["output"], n, rng)
    return float(np.mean(-(-prompt // chunk) + output))


def reading(keep: dict, engine_cfg: dict, mix: dict) -> dict:
    lo, hi = keep["window"]
    ticks = [t for t in keep["ticks"] if lo <= t.t1 <= hi]
    counted = keep["counted"]
    need = mean_need(mix, engine_cfg["chunk"])
    ticks_s = len(ticks) / (hi - lo)
    q = (hi - lo) / 4
    first = stats.ttfts(counted, (lo, lo + q))
    last = stats.ttfts(counted, (hi - q, hi))
    return {
        "queue_open": queue(ticks, lo, lo + EDGE_S),
        "queue_close": queue(ticks, hi - EDGE_S, hi),
        "queue_max": max((t.waiting for t in ticks), default=0),
        "active_mean": statistics.fmean(t.active for t in ticks)
        if ticks else 0.0,
        "ticks_per_s": ticks_s,
        "ticks_per_request": need,
        "capacity_rps": engine_cfg["slots"] * ticks_s / need,
        "finished_in_window": sum(
            1 for r in keep["requests"]
            if r.done and r.token_times and lo <= r.token_times[-1] <= hi),
        "first_quarter_p50_s": stats.percentile(first, 0.5)
        if first else None,
        "last_quarter_p50_s": stats.percentile(last, 0.5) if last else None,
        "drain_s": max((r.token_times[0] for r in counted if r.token_times),
                       default=hi) - hi,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--prime", type=int)
    a = ap.parse_args(argv)
    for seed in (int(s) for s in a.seeds.split(",")):
        for rate in (float(r) for r in a.rates.split(",")):
            engine_cfg, mix = {}, {}

            def at_rate(ctx, rate=rate):
                ctx["traffic"]["rate_rps"] = rate
                if a.prime is not None:
                    ctx["traffic"]["prime"] = a.prime
                engine_cfg.update(ctx["config"]["engine"])
                mix.update(ctx["traffic"])
            keep: dict = {}
            args = argparse.Namespace(workload=a.workload, seed=seed,
                                      seconds=a.seconds, trace=0)
            out = run.run(args, override=at_rate, keep=keep)
            print(json.dumps({
                "seed": seed, "rate_rps": rate, "prime": a.prime,
                "attempted": out["attempted"], "failed": out["failed"],
                **reading(keep, engine_cfg, mix), "metrics": out["metrics"],
                "correct": out["correct"]}), flush=True)
            gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
