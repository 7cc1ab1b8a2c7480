"""Engine tracing: the ``serving.*`` stage spans in a profiler session, the
stage totals the replanner reads, queue wait across a preemption, and the
KV reservation-use totals."""
import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs.base import all_configs
from repro.core import pipeline
from repro.models.model import Model
from repro.serving import Request, Scheduler, SchedulerConfig, ServingEngine


@pytest.fixture(scope="module")
def dense_model():
    cfg = all_configs()["qwen3-1.7b"].reduced()
    m = Model(cfg)
    return cfg, m, m.init(jax.random.key(0))


def _submit(eng, cfg, n, seed, prompt_len=12, max_new=3):
    rng = np.random.default_rng(seed)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab, prompt_len)
                    .astype(np.int32), max_new_tokens=max_new)
            for i in range(n)]
    for r in reqs:
        eng.submit(r)
    return reqs


def _paged(m, params, **kw):
    return ServingEngine(m, params, slots=2, max_len=64, chunk=8,
                         kv="paged", kv_block_size=8, **kw)


def _host_spans(trace_dir: str) -> list[tuple[str, int, int]]:
    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("serving."):
                        out.append((e.name, e.start_ns,
                                    e.start_ns + e.duration_ns))
    return out


def test_engine_spans_reach_the_profiler_trace(dense_model, tmp_path):
    cfg, m, params = dense_model
    eng = _paged(m, params)
    _submit(eng, cfg, 3, seed=0)
    eng.step()  # compile outside the trace
    steps0 = eng.timer.counts["step"]
    with jax.profiler.trace(str(tmp_path)):
        eng.run()
    spans = _host_spans(str(tmp_path))
    by = {}
    for name, a, b in spans:
        by.setdefault(name, []).append((a, b))

    def inside(name, parent):
        return all(any(pa <= a and b <= pb for pa, pb in by[parent])
                   for a, b in by[name])

    assert len(by["serving.step"]) == eng.timer.counts["step"] - steps0
    for name in ("serving.plan", "serving.prefill_chunk.emit",
                 "serving.decode.wait"):
        assert by.get(name), name
        assert inside(name, "serving.step"), name
    assert inside("serving.decode.wait", "serving.decode")
    assert inside("serving.prefill_chunk.emit", "serving.prefill_chunk")


def test_stage_timer_names_children_by_the_open_stage():
    t = pipeline.StageTimer(prefix="x.")
    with t.stage("a"):
        with t.stage(".b"):
            pass
        with t.stage(".b"):
            pass
    with t.stage("c"):
        with t.stage(".b"):
            pass
    assert t.counts == {"a": 1, "a.b": 2, "c": 1, "c.b": 1}
    assert t.totals["a"] >= t.totals["a.b"] >= 0
    assert t.as_dict()["a.b"]["calls"] == 2


def test_stage_totals_feed_the_replanner_on_replan_ticks_only(dense_model):
    cfg, m, params = dense_model
    eng = ServingEngine(m, params, slots=2, max_len=64, chunk=8,
                        replan_every=3)
    seen = []
    replan = eng.scheduler.maybe_replan

    def spy(**kw):
        timer = eng.timer
        # the replanner reads the whole decode stage, not its children
        assert kw["decode_step_s"] == pytest.approx(
            timer.totals.get("decode", 0.0)
            / max(timer.counts.get("decode", 0), 1))
        plan = replan(**kw)
        seen.append(plan is not None)
        return plan

    eng.scheduler.maybe_replan = spy
    _submit(eng, cfg, 4, seed=6, prompt_len=10, max_new=6)
    steps = 0
    while eng.scheduler.pending():
        eng.step()
        steps += 1
    c, tot = eng.timer.counts, eng.timer.totals
    assert c["step"] == c["plan"] == steps
    assert c["replan"] == len(seen) >= 1 and all(seen)
    assert c["decode.wait"] >= c["decode"] and c["decode.emit"] == c["decode"]
    children = sum(v for k, v in tot.items() if k.startswith("decode."))
    assert children <= tot["decode"] <= tot["step"]


def test_queue_wait_sums_over_preemption_and_readmission():
    now = [0.0]
    sched = Scheduler(SchedulerConfig(slots=2, chunk=32, preempt=1),
                      clock=lambda: now[0])
    first, low = (Request(rid=i, prompt=np.zeros((4,), np.int32),
                          max_new_tokens=8) for i in range(2))
    sched.submit(first)
    sched.submit(low)
    assert low.queued_s is None
    now[0] = 1.0
    plan = sched.plan_tick()
    assert first.queued_s == low.queued_s == pytest.approx(1.0)
    for a in plan.prefill:
        sched.note_prefilled(a.sreq, a.n_new, first_token=0)  # decoding
    now[0] = 2.0
    vip = Request(rid=2, prompt=np.zeros((4,), np.int32), max_new_tokens=1,
                  priority=5)
    sched.submit(vip)
    now[0] = 2.5
    plan = sched.plan_tick()         # the VIP preempts the newest, `low`
    assert [s.req.rid for s in plan.admissions] == [2]
    assert sched.preempted == 1 and sched.waiting[0].req is low
    assert vip.queued_s == pytest.approx(0.5)
    assert low.queued_s == pytest.approx(1.0)
    (a,) = plan.prefill
    sched.note_prefilled(a.sreq, a.n_new, first_token=0)     # VIP retires
    now[0] = 4.0
    plan = sched.plan_tick()         # `low` is admitted again
    assert [s.req.rid for s in plan.admissions] == [1]
    assert low.queued_s == pytest.approx(1.0 + 1.5)
    assert first.queued_s == pytest.approx(1.0)


def test_engine_requests_carry_queue_wait(dense_model):
    cfg, m, params = dense_model
    eng = _paged(m, params)
    reqs = _submit(eng, cfg, 3, seed=1)
    eng.run()
    assert all(r.queued_s is not None and r.queued_s >= 0 for r in reqs)
    # two slots: the third request waits for the first to retire
    assert reqs[2].queued_s > max(reqs[0].queued_s, reqs[1].queued_s)


def test_kv_reservation_use_per_tick(dense_model):
    cfg, m, params = dense_model
    eng = _paged(m, params)
    _submit(eng, cfg, 3, seed=2, prompt_len=13, max_new=6)
    pool, bs = eng.pool, eng.pool.cfg.block_size
    leased = written = 0
    while eng.scheduler.pending():
        eng.step()
        pool.check_invariants()
        # context the device caches hold per slot, an independent count
        length = np.asarray(eng.caches.kv.length)[0]
        for s in eng.scheduler.active:
            if s is not None:
                n = len(pool.leases[s.req.rid].blocks)
                leased += n
                written += min(-(-int(length[s.slot]) // bs), n)
        st = eng.stats()["kv_pool"]
        assert st["leased_block_ticks"] == leased
        assert st["written_block_ticks"] == written
    assert 0 < written < leased
