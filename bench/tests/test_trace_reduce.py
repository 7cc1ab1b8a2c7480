from pathlib import Path

import numpy as np
import pytest

from bench import trace_reduce as T

FIXTURE = Path(__file__).resolve().parents[1] / "fixtures" / \
    "qwen3_decode_3ticks.json.gz"


def test_synthetic_union_idle_and_gap_attribution():
    ev = [T.Event("span", -1, "bench.engine_step", 0.0, 1.0),
          T.Event("span", -1, "bench.gen_sleep", 1.0, 1.0),
          T.Event("module", 0, "jit_step", 0.1, 0.5),
          T.Event("op", 0, "while", 0.1, 0.5),
          T.Event("op", 0, "gqa_decode_paged", 0.1, 0.2),
          T.Event("op", 0, "gqa_decode_paged", 0.3, 0.2),
          T.Event("op", 0, "fusion", 0.65, 0.05),
          T.Event("op", 0, "fusion", 1.9, 0.1)]
    tr = T.Trace(ev)
    assert tr.window_s == pytest.approx(2.0)
    # union: [0.1, 0.6] + [0.65, 0.7] + [1.9, 2.0]
    assert tr.busy_s == pytest.approx(0.65)
    assert tr.ops("gqa_decode_paged") == (2, pytest.approx(0.4))
    assert tr.modules("jit_step") == (1, pytest.approx(0.5))
    gaps = tr.idle_gaps()
    assert gaps[0] == (pytest.approx(1.2), "bench.gen_sleep")
    assert [g for g, _ in gaps] == pytest.approx([1.2, 0.1, 0.05])
    assert gaps[1][1] == "bench.engine_step"
    bd = tr.breakdown()
    # the layer loop (a container) is not listed; its body is
    assert bd["device_ops"][0] == ["jit_step/gqa_decode_paged",
                                   pytest.approx(0.4)]
    assert all("while" not in k for k, _ in bd["device_ops"])


def test_op_labels_name_kernels_and_kinds():
    assert T.op_label("%gqa_decode_paged.6 = bf16[32,8,2,128] custom-call("
                      "s32[4096] %copy-done.3)") == "gqa_decode_paged"
    assert T.op_label("%fused_sample.1 = f32[32,1187,128] custom-call(") \
        == "fused_sample"
    assert T.op_label("%copy-done = bf16[1536,32,1024] copy-done(") \
        == "copy-done"
    assert T.module_label("jit_serve_sample_body(1605409808705169656)") \
        == "jit_serve_sample_body"


def test_recorded_decode_trace():
    """Three decode ticks of qwen3-1.7b at 32 slots on one TPU v5 lite."""
    tr = T.Trace(T.load_events(str(FIXTURE)))
    n, t = tr.ops("gqa_decode_paged")
    assert n == 3 * 28                    # once per layer per tick
    assert tr.modules("jit_serve_sample_body")[0] == 3
    assert tr.ops("fused_sample")[0] == 3
    assert 0 < tr.busy_s < tr.window_s
    assert t < tr.busy_s
    gaps = tr.idle_gaps()
    assert sum(g for g, _ in gaps) == pytest.approx(tr.window_s - tr.busy_s)
    assert {name for _, name in gaps} <= {"bench.engine_step",
                                          "outside the benchmark's spans"}
    bd = tr.breakdown()
    assert bd["device_ops"][0][0] == "jit_serve_sample_body/gqa_decode_paged"
    assert len(bd["device_ops"]) == 10 and len(bd["idle_gaps"]) == 10


def test_window_span_clips_the_trace():
    ev = [T.Event("span", -1, "bench.engine_step", 0.0, 3.0),
          T.Event("span", -1, "bench.window", 1.0, 1.5),
          T.Event("op", 0, "fusion", 0.5, 1.0),     # half inside
          T.Event("op", 0, "fusion", 1.8, 0.4),     # inside
          T.Event("op", 0, "fusion", 2.6, 0.2)]     # outside
    tr = T.Trace(ev)
    assert (tr.t0, tr.t1, tr.window_s) == (1.0, 2.5, 1.5)
    assert tr.busy_s == pytest.approx(0.5 + 0.4)
    assert tr.ops("fusion") == (2, pytest.approx(0.9))
    assert [g for g, _ in tr.idle_gaps()] == pytest.approx([0.3, 0.3])


def test_gap_names_are_the_shortest_open_span():
    """The walk back over nested spans names what a search of every span
    for the shortest one open at the time would name."""
    rng = np.random.default_rng(5)
    ev, t = [], 0.0
    for tick in range(200):
        length = rng.uniform(0.05, 0.3)
        ev.append(T.Event("span", -1, "bench.engine_step", t, length))
        ev.append(T.Event("span", -1, "serving.step", t + 0.001,
                          length - 0.002))
        a = t + 0.001
        for stage in ("plan", "prefill_chunk", "decode"):
            d = rng.uniform(0.0, (t + length - 0.001 - a) / 2)
            ev.append(T.Event("span", -1, f"serving.{stage}", a, d))
            if d > 0.01:
                ev.append(T.Event("span", -1, f"serving.{stage}.wait",
                                  a + d / 2, d / 3))
            a += d
        t += length + rng.uniform(0.0, 0.02)
        ev.append(T.Event("span", -1, "bench.gen_sleep", t, 0.005))
        t += 0.01
    ev.append(T.Event("op", 0, "fusion", 0.0, t))
    tr = T.Trace(ev)
    spans = [e for e in ev if e.kind == "span"]
    for x in rng.uniform(0.0, t, 2000):
        open_ = [s for s in spans if s.start <= x <= s.start + s.dur]
        want = min(open_, key=lambda s: s.dur).name if open_ \
            else "outside the benchmark's spans"
        assert tr.span_at(x) == want, x
