"""The program's own spans and counters reach the metric readers.

A run's record carries the engine's ``stats()`` numbers at the window's
open and close and each request's ``queued_s``; the trace keeps the
engine's ``serving.*`` spans beside the harness's ``bench.*``.  Each
reader that reads them gives its number where they are there and
nothing where they are not.
"""
import glob
import os
from types import SimpleNamespace as NS

import jax
import pytest

import tiny
from bench import manifest, run
from bench import trace_reduce as T


def test_a_run_carries_the_engine_stats_and_queue_waits():
    keep = {}
    run.run(tiny.args("qwen3-chat"), require_tpu=False,
            override=tiny.override(), keep=keep)
    s0, s1 = keep["engine_stats"]
    assert all(isinstance(v, (int, float)) and not isinstance(v, bool)
               for v in {**s0, **s1}.values())
    assert "prefill_tokens_saved" in s1
    for key in ("tokens_out", "kv_pool.leased_block_ticks",
                "kv_pool.written_block_ticks", "stages.step.calls"):
        assert s1[key] > s0[key], key
    assert s1["kv_pool.written_block_ticks"] <= \
        s1["kv_pool.leased_block_ticks"]
    waits = [r.queued_s for r in keep["counted"]]
    assert waits and all(w is not None and w >= 0 for w in waits)


def test_the_trace_keeps_the_programs_spans(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        for name in ("serving.step", "bench.engine_step", "other.span"):
            with jax.profiler.TraceAnnotation(name):
                jax.block_until_ready(jax.numpy.ones(4) + 1)
    path, = glob.glob(os.path.join(tmp_path, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    names = {e.name for e in T.events_from_xplane(path) if e.kind == "span"}
    assert names == {"serving.step", "bench.engine_step"}


def _span(name, start, dur):
    return T.Event("span", -1, name, start, dur)


def _trace(spans):
    """A trace of one window [0, 1] with the given host spans."""
    return T.Trace([_span("bench.window", 0.0, 1.0),
                    T.Event("op", 0, "fusion", 0.0, 1.0)] + spans)


def test_host_tick_is_the_step_less_its_waits():
    read = manifest.metric_reader("host_tick_ms")
    spans = [
        _span("serving.step", -0.05, 0.1),              # cut by the window
        _span("serving.step", 0.1, 0.2),                # 200 ms, 150 waited
        _span("serving.prefill_chunk", 0.11, 0.1),
        _span("serving.prefill_chunk.wait", 0.12, 0.08),
        _span("serving.decode.wait", 0.23, 0.07),
        _span("serving.step", 0.4, 0.1),                # 100 ms, 10 waited
        _span("serving.decode.wait", 0.45, 0.01),
        _span("serving.step", 0.6, 0.3),                # 300 ms, none
        _span("bench.engine_step", 0.6, 0.31),
    ]
    assert read(NS(trace=_trace(spans))) == pytest.approx(90.0)
    assert read(NS(trace=_trace([_span("bench.engine_step", 0.1, 0.2)]))) \
        is None


def test_an_idle_gap_is_named_by_the_innermost_span():
    tr = T.Trace([_span("bench.engine_step", 0.0, 1.0),
                  _span("serving.step", 0.0, 1.0),
                  _span("serving.decode.wait", 0.2, 0.5),
                  T.Event("op", 0, "fusion", 0.0, 0.2),
                  T.Event("op", 0, "fusion", 0.7, 0.3)])
    assert tr.idle_gaps() == [(pytest.approx(0.5), "serving.decode.wait")]


def test_queue_wait_p90():
    read = manifest.metric_reader("queue_wait_p90_ms")
    counted = [NS(queued_s=0.001 * i) for i in range(1, 11)]
    counted.append(NS(queued_s=None))                 # never admitted
    assert read(NS(counted=counted)) == pytest.approx(9.0)
    assert read(NS(counted=[NS(queued_s=None)])) is None


def test_kv_used_is_written_over_leased_in_the_window():
    read = manifest.metric_reader("kv_used_pct")
    s0 = {"kv_pool.leased_block_ticks": 100,
          "kv_pool.written_block_ticks": 40}
    s1 = {"kv_pool.leased_block_ticks": 300,
          "kv_pool.written_block_ticks": 190}
    assert read(NS(engine_stats=(s0, s1))) == pytest.approx(75.0)
    dense = ({"tokens_out": 1}, {"tokens_out": 9})
    assert read(NS(engine_stats=dense)) is None
    assert read(NS(engine_stats=(s0, s0))) is None


def test_prefix_hits_read_the_window_delta_of_the_pool():
    read = manifest.metric_reader("prefix_hit_pct")
    ticks = [NS(t1=0.5, admitted=100), NS(t1=1.5, admitted=300),
             NS(t1=9.0, admitted=50)]
    s0 = {"prefill_tokens_saved": 64}
    s1 = {"prefill_tokens_saved": 160}
    run_ = NS(window=(1.0, 5.0), ticks=ticks, engine_stats=(s0, s1))
    assert read(run_) == pytest.approx(32.0)
    dense = NS(window=(1.0, 5.0), ticks=ticks, engine_stats=({}, {}))
    assert read(dense) == 0.0
    assert read(NS(window=(2.0, 5.0), ticks=ticks,
                   engine_stats=(s0, s1))) is None
