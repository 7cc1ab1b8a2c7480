"""Run one benchmark cell once and print its result as one JSON line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration (``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``); its correctness limits are in
``bench/checks/<cell>.json``.  A run:

1. checks for the accelerator: no TPU, a ``device_kind`` missing from
   ``bench/peaks.py`` or fewer chips than the cell asks for exit nonzero
   and print no result;
2. set-up: makes the weights from the seed on the device, builds the
   engine, compiles every program shape the traffic can reach (every
   prefill-chunk width the replanner may pick, both sampling paths) and
   runs the traffic's own warm phase;
3. measures for ``--seconds`` with the profiler off (``--trace 0``, the
   end-to-end metrics) or on (``--trace 1``, the per-layer metrics);
4. drains the window's requests, reads the peak device memory, frees the
   engine, and compares a sample of the served tokens, greedy and sampled,
   with the plain float32 reference (``bench/check.py``).

The last line of standard output is the result; the last lines of
standard error are the numbers compared, each beside its limit.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

import jax  # noqa: E402
import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from bench import (check, flops, loop, manifest, peaks, stats,  # noqa: E402
                   system, trace_reduce, traffic)
from bench import weights as W  # noqa: E402

#: the persistent compilation cache, at a fixed path inside the checkout
CACHE_DIR = ROOT / ".jax_cache"
#: reported when more than the percentile's share of requests never got a
#: first token: they count as infinitely late (milliseconds)
NEVER_MS = 1e12


class Refused(Exception):
    """The run cannot measure here: exit nonzero, print no result."""

    def __init__(self, code: int, msg: str):
        super().__init__(msg)
        self.code = code


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def load_cell(name: str) -> dict:
    """Everything a run needs, found by name; missing files refuse."""
    try:
        man = manifest.load()
        cell = manifest.cell(man, name)
        return {"manifest": man, "cell": cell,
                "config": manifest.config(man, cell["config"]),
                "traffic": manifest.traffic(cell["traffic"]),
                "limits": manifest.limits(cell["name"])}
    except (manifest.ManifestError, KeyError, ValueError) as e:
        raise Refused(2, str(e)) from None


def use_program() -> None:
    """Put the system under test on the path; refuse without it."""
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        raise Refused(2, f"the system under test ({src / 'repro'}) is not "
                         "in this checkout")
    sys.path.insert(0, str(src))


def accelerator(chips: int, require_tpu: bool = True):
    """The first ``chips`` devices; refuse anything but a known TPU.
    ``require_tpu=False`` is for the tests of the harness on the CPU; its
    peaks are placeholders and nothing it measures is a device number."""
    devices = jax.devices()
    dev = devices[0]
    if not require_tpu:
        return devices[:chips], {"bf16_flops_s": 1e12, "hbm_bytes_s": 1e11}
    if dev.platform != "tpu":
        raise Refused(3, f"no TPU: JAX's first device is on platform "
                         f"{dev.platform!r}")
    try:
        peak = peaks.peak_for(dev.device_kind)
    except peaks.UnknownDevice as e:
        raise Refused(3, str(e)) from None
    if len(devices) < chips:
        raise Refused(3, f"the cell asks for {chips} chips; JAX finds "
                         f"{len(devices)}")
    return devices[:chips], peak


class CompileCount:
    """Programs compiled or loaded from the cache, counted from JAX's
    monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, seconds: float, **_) -> None:
        if event == self.EVENT:
            self.n += 1


def warm_shapes(engine, vocab: int, chunk: int) -> None:
    """Compile every program the traffic can reach: each prefill-chunk
    width the replanner may switch to, the sampled and the all-greedy
    first-token paths, and the fused decode-and-sample step."""
    rng = np.random.default_rng(0)
    rid = 10**9
    for width in system.chunk_sizes():
        engine.scheduler.cfg.chunk = width
        for greedy in (False, True):
            req = traffic.Req(
                rid=rid, prompt=rng.integers(0, vocab, 2 * width + 1)
                .astype(np.int32), max_new=3, greedy=greedy, phase="warm",
                sampling=None if greedy else {
                    "temperature": 0.8, "top_k": 50, "top_p": 0.95,
                    "seed": rid})
            engine.submit(system.program_request(req))
            rid += 1
            while engine.scheduler.pending():
                engine.step()
    engine.scheduler.cfg.chunk = chunk


def counters(engine) -> dict:
    s = engine.stats()
    return {"tokens_out": s["tokens_out"],
            "prefill_tokens": s["prefill_tokens"],
            "prefill_tokens_saved": s.get("prefill_tokens_saved", 0)}


def seq_bound(mix: dict) -> tuple[int, int]:
    """Longest sequence (prompt and output) and output the mix can make,
    the sequence rounded up to the reference's query block."""
    out = mix["output"]["max"]
    return -(-(mix["prompt"]["max"] + out) // 256) * 256, out


def run(args, *, require_tpu: bool = True, override=None,
        fault=None, control: str | None = None,
        keep: dict | None = None) -> dict:
    """One run of a cell.  The tests of the harness pass
    ``require_tpu=False``, an ``override(ctx)`` that shrinks the cell to a
    size the CPU holds, and a ``fault(engine)`` that breaks the timed path
    underneath; ``bench/calibrate.py`` passes ``control`` to judge the
    control beside the program (and a ``fault``), and ``bench/sweep.py`` a
    ``keep`` dict that receives the run's requests and ticks.  A benchmark
    run passes none."""
    ctx = load_cell(args.workload)
    if override is not None:
        override(ctx)
    cell, cfg_file, mix = ctx["cell"], ctx["config"], ctx["traffic"]
    use_program()
    if require_tpu and not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices, peak = accelerator(int(cell["chips"]), require_tpu)
    compiles = CompileCount()

    model = system.build_model(cfg_file)
    params = W.make(model.abstract(), args.seed, devices[0])
    engine = system.build_engine(model, params, cfg_file["engine"])
    if fault is not None:
        fault(engine)
    log(f"weights and engine ready at {time.perf_counter() - T_START:.3f} "
        f"s; kernel plan {engine.kernel_plan.as_dict()}")
    warm_shapes(engine, model.cfg.vocab, cfg_file["engine"]["chunk"])
    log(f"shapes warm at {time.perf_counter() - T_START:.3f} s")

    tr = traffic.Traffic(mix, args.seed, args.seconds, model.cfg.vocab)
    lp = loop.Loop(engine)
    marks: dict = {}
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if args.trace \
        else None

    def on_open():
        marks["open"] = time.perf_counter()
        marks["compiles"] = compiles.n
        marks["c0"] = counters(engine)
        if trace_dir:
            # a span's clock starts when it is made, so it is made here
            marks["span"] = jax.profiler.TraceAnnotation("bench.window")
            marks["span"].__enter__()

    def on_close():
        if "close" in marks:
            return
        if trace_dir:
            marks["span"].__exit__(None, None, None)
        marks["close"] = time.perf_counter()
        marks["compiles"] = compiles.n - marks["compiles"]
        marks["c1"] = counters(engine)

    if trace_dir:
        # the trace opens with the warm phase, so that its own start-up
        # stall falls outside the window; the reduction keeps only what
        # lies inside the ``bench.window`` span
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    t0 = time.perf_counter() + float(mix.get("warm_s", 0.0))
    loop.run_open(lp, tr.requests, t0, args.seconds,
                  float(mix["drain_cap_s"]), on_open, on_close)
    requests = tr.requests
    counted = [r for r in requests if t0 <= r.due_at < t0 + args.seconds]
    if trace_dir:
        jax.profiler.stop_trace()
    window = (t0, t0 + args.seconds)
    setup_s = marks["open"] - T_START
    if keep is not None:
        keep.update(requests=requests, counted=counted, window=window,
                    ticks=lp.ticks)
    in_window = [t for t in lp.ticks if t0 <= t.t1 <= window[1]]
    log(f"{len(in_window)} engine ticks in the window, mean "
        f"{1e3 * sum(t.t1 - t.t0 for t in in_window) / max(len(in_window), 1):.1f}"
        f" ms; {sum(r.done for r in requests)} requests finished")
    log(f"window {args.seconds} s closed; {len(counted)} requests counted, "
        f"{sum(not r.token_times for r in counted)} without a first token; "
        f"{marks['compiles']} compiles inside the window")

    memory_peak = max(int((d.memory_stats() or {})
                          .get("peak_bytes_in_use", 0)) for d in devices)
    for r in counted:
        # refused, or no first token when the drain cap ended the run
        r.failed = r.failed or not r.token_times
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": memory_peak}

    reduced = None
    if trace_dir:
        reduced = trace_reduce.load(trace_dir, n_devices=len(devices))
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s

    # the program's state goes before the reference runs
    ticks = lp.ticks
    del engine, lp
    gc.collect()

    lim = ctx["limits"]
    # every request the run finished went through the window's engine and
    # programs, the warm phase's alongside the window's
    sample = check.pick(requests, lim["sample_requests"], args.seed)
    seq_len, max_out = seq_bound(mix)
    t_ref = time.perf_counter()
    result = check.compare(params, cfg_file, sample, seq_len, max_out,
                           control=control)
    correct, checks = check.verdict(result, lim)
    log(f"reference compared {result['tokens_compared']} tokens of "
        f"{result['requests_compared']} requests in "
        f"{time.perf_counter() - t_ref:.3f} s")

    failed = sum(1 for r in counted if r.failed)
    out = {"correct": correct, "attempted": len(counted), "failed": failed}
    man = ctx["manifest"]
    if not args.trace:
        metrics = {}
        for m in manifest.end_to_end(man, cell):
            value = end_to_end(m["name"], counted, requests, window, setup_s)
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        out["metrics"] = metrics
    else:
        record = SimpleNamespace(
            cell=cell, window=window, window_s=args.seconds,
            traced=(marks["open"], marks["close"]),
            requests=requests, counted=counted, ticks=ticks,
            counters=(marks["c0"], marks["c1"]),
            compiles_in_window=marks["compiles"], trace=reduced, peak=peak,
            chips=len(devices), shape=flops.model_shape(cfg_file),
            engine=cfg_file["engine"])
        metrics = {}
        for m in manifest.per_layer(man, cell):
            value = manifest.metric_reader(m["name"])(record)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        out["metrics"] = metrics
        out["breakdown"] = reduced.breakdown()
    out["device"] = device
    if control is not None:
        ok, c_checks = check.verdict(result["control"], lim)
        out["control"] = {"correct": ok, **{k: v["value"]
                                            for k, v in c_checks.items()}}
    out["checks"] = {k: {"value": v["value"], "limit": v["limit"]}
                     for k, v in checks.items()}
    return out


def end_to_end(name: str, counted, requests, window, setup_s) -> float:
    if name == "setup_s":
        return setup_s
    if name == "ttft_p90_ms":
        v = stats.percentile(stats.ttfts(counted, window), 0.90)
        return v * 1e3 if math.isfinite(v) else NEVER_MS
    if name == "itl_p95_ms":
        return stats.percentile(stats.itl_gaps(requests, window), 0.95) * 1e3
    raise KeyError(f"no end-to-end metric {name!r} in bench/run.py")


def main(argv=None) -> int:
    args = parse(argv)
    try:
        out = run(args)
    except Refused as e:
        print(f"bench: {e}", file=sys.stderr)
        return e.code
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(f"correct: {out['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
