"""Engine-replica router: dispatch policy, failure requeue, and the
solo-equivalence oracle.

The router never touches model numerics — a request lives wholly inside
one replica — so the load-bearing property is the same one the serving
fuzz harness enforces for batch composition: **where** a request runs must
never change **what** it generates.  Every routed request must emit the
stream a solo single-request engine emits, greedy and seeded-sampled
alike, through least-loaded spreading, prefix-affinity stickiness,
overload spill, and replica failure with at-least-once requeue.
"""
import json

import numpy as np
import pytest

import jax

from repro.models.model import Model
from repro.serving import Request, SamplingParams, ServingEngine
from repro.serving.router import ReplicaRouter, prefix_key

from test_serving_fuzz import BLOCK, CFG, CHUNK, MAX_LEN, SLOTS


@pytest.fixture(scope="module")
def router_model():
    m = Model(CFG)
    return m, m.init(jax.random.key(0))


def make_engine(model, params, kv="paged"):
    return ServingEngine(model, params, slots=SLOTS, max_len=MAX_LEN,
                         chunk=CHUNK, prefill_mode="chunked",
                         replan_every=10_000, kv=kv,
                         kv_block_size=BLOCK if kv == "paged" else None,
                         kv_pool_blocks=SLOTS * MAX_LEN // BLOCK
                         if kv == "paged" else None)


def make_router(model, params, n=2, kv="paged"):
    return ReplicaRouter([make_engine(model, params, kv) for _ in range(n)])


def solo_reference(model, params, req_proto: Request) -> list:
    """What this request generates alone on a fresh engine — the oracle."""
    eng = make_engine(model, params)
    req = Request(rid=req_proto.rid, prompt=req_proto.prompt.copy(),
                  max_new_tokens=req_proto.max_new_tokens,
                  sampling=req_proto.sampling)
    eng.submit(req)
    eng.run()
    return list(req.generated)


def distinct_prompt(rng, n=None):
    """A prompt shorter than one block: no block-aligned prefix, so the
    router can never take the affinity path for it."""
    return rng.integers(0, CFG.vocab, n or int(rng.integers(3, BLOCK))) \
        .astype(np.int32)


# -- dispatch policy ----------------------------------------------------------

def test_prefix_key_granularity():
    rng = np.random.default_rng(0)
    short = rng.integers(0, CFG.vocab, BLOCK - 1).astype(np.int32)
    assert prefix_key(short, BLOCK) is None
    base = rng.integers(0, CFG.vocab, BLOCK).astype(np.int32)
    tail_a = np.concatenate([base, np.array([1, 2], np.int32)])
    tail_b = np.concatenate([base, np.array([3], np.int32)])
    # same block-aligned prefix -> same key; the unshared tail is ignored
    assert prefix_key(tail_a, BLOCK) == prefix_key(tail_b, BLOCK)
    other = np.concatenate([base[:-1], np.array([0, 0], np.int32)])
    assert prefix_key(other, BLOCK) != prefix_key(tail_a, BLOCK)


def test_least_loaded_dispatch_alternates(router_model):
    """Distinct sub-block prompts (no affinity) spread round-robin via
    the load counter, ties broken by replica index."""
    model, params = router_model
    router = make_router(model, params, n=2)
    rng = np.random.default_rng(1)
    reqs = [Request(rid=i, prompt=distinct_prompt(rng), max_new_tokens=2)
            for i in range(4)]
    for r in reqs:
        router.submit(r)
    router._dispatch()
    assert [router.placements[i].replica for i in range(4)] == [0, 1, 0, 1]
    router.run()
    assert all(r.done for r in reqs)


def test_prefix_affinity_sticks_then_spills(router_model):
    """Shared-prefix requests stick to the first replica that prefilled
    the prefix — until its load exceeds the least-loaded replica by the
    slack window, at which point the router spills and re-pins."""
    model, params = router_model
    router = make_router(model, params, n=2)
    rng = np.random.default_rng(2)
    base = rng.integers(0, CFG.vocab, 2 * BLOCK).astype(np.int32)
    reqs = [Request(rid=i,
                    prompt=np.concatenate(
                        [base, rng.integers(0, CFG.vocab, i + 1)
                         .astype(np.int32)]),
                    max_new_tokens=2)
            for i in range(4)]
    for r in reqs:
        router.submit(r)
    router._dispatch()
    # slack is one slot-width (SLOTS=2): three stick to replica 0, the
    # fourth sees load 3 > 0 + 2 and spills to replica 1 (re-pinning it)
    assert [router.placements[i].replica for i in range(4)] == [0, 0, 0, 1]
    assert router.affinity_hits == 2
    router.run()
    assert all(r.done for r in reqs)
    # the stickiness paid off: replica 0's pool served the shared prefix
    # from cache for the later arrivals
    assert router.engines[0].pool.tokens_saved >= 2 * BLOCK


def test_routed_outputs_equal_solo_runs(router_model):
    """The oracle: greedy and seeded-sampled requests routed across two
    replicas generate exactly what each generates alone."""
    model, params = router_model
    router = make_router(model, params, n=2)
    rng = np.random.default_rng(3)
    reqs = []
    for i in range(6):
        sampling = None
        if i % 2:
            sampling = SamplingParams(temperature=0.8, top_k=12,
                                      seed=100 + i)
        reqs.append(Request(rid=i,
                            prompt=rng.integers(0, CFG.vocab,
                                                int(rng.integers(3, 20)))
                            .astype(np.int32),
                            max_new_tokens=int(rng.integers(1, 6)),
                            sampling=sampling))
    for r in reqs:
        router.submit(r)
    router.run()
    assert all(r.done for r in reqs)
    assert {router.placements.get(i) for i in range(6)} == {None}
    for r in reqs:
        assert list(r.generated) == solo_reference(model, params, r), \
            f"request {r.rid} diverged from its solo run"


# -- failure handling ---------------------------------------------------------

def test_replica_failure_requeues_and_replays(router_model):
    """Killing a replica mid-generation re-queues its unfinished requests
    from scratch on the survivor; final outputs still equal solo runs
    (at-least-once + deterministic replay)."""
    model, params = router_model
    router = make_router(model, params, n=2)
    rng = np.random.default_rng(4)
    reqs = [Request(rid=i, prompt=distinct_prompt(rng, 6), max_new_tokens=6)
            for i in range(4)]
    for r in reqs:
        router.submit(r)
    for _ in range(3):   # partial progress on both replicas
        router.step()
    victims = [i for i in range(4)
               if i in router.placements
               and router.placements[i].replica == 0]
    assert victims, "replica 0 should still hold unfinished requests"
    moved = router.fail_replica(0)
    assert moved == len(victims) and router.requeued == moved
    for i in victims:  # partial generations were discarded
        assert reqs[i].generated == [] and not reqs[i].done
    router.run()
    assert router.stats()["live_replicas"] == 1
    assert all(r.done for r in reqs)
    for i in victims:  # every re-run landed on the survivor
        assert i not in router.placements
    for r in reqs:
        assert list(r.generated) == solo_reference(model, params, r), \
            f"request {r.rid} diverged after failover"


def test_failing_last_replica_raises(router_model):
    model, params = router_model
    router = make_router(model, params, n=1)
    rng = np.random.default_rng(5)
    router.submit(Request(rid=0, prompt=distinct_prompt(rng),
                          max_new_tokens=2))
    router.fail_replica(0)
    with pytest.raises(RuntimeError, match="no live replicas"):
        router.step()


# -- stats --------------------------------------------------------------------

def test_router_stats_shape(router_model):
    model, params = router_model
    router = make_router(model, params, n=2)
    rng = np.random.default_rng(6)
    reqs = [Request(rid=i, prompt=distinct_prompt(rng, 10),
                    max_new_tokens=4) for i in range(4)]
    for r in reqs:
        router.submit(r)
    router.run()
    s = router.stats()
    assert s["replicas"] == 2 and s["live_replicas"] == 2
    assert s["dispatched"] == 4 and s["queued"] == 0
    assert len(s["per_replica"]) == 2
    assert s["aggregate_decode_tokens_per_s"] > 0
    # the aggregate is the sum of per-replica busy-time rates
    per = sum(p["decode_tokens_per_s"] for p in s["per_replica"]
              if p and p.get("decode_tokens_per_s"))
    assert s["aggregate_decode_tokens_per_s"] == pytest.approx(per)


# -- placement ------------------------------------------------------------------

def test_engine_refuses_both_device_and_mesh(router_model):
    model, params = router_model
    with pytest.raises(ValueError, match="one device or on a mesh"):
        ServingEngine(model, params, device=jax.devices()[0], mesh=object())


def test_replicas_live_on_their_own_devices():
    """Replicas built with ``device=`` hold their params and caches on that
    device through a whole routed run, and still generate what a solo
    engine on the first device generates."""
    from conftest import run_multidevice
    out = run_multidevice(r"""
import json
import jax, numpy as np
from repro.configs.base import get_config
from repro.models.model import Model
from repro.serving import Request, ServingEngine
from repro.serving.router import ReplicaRouter

cfg = get_config("qwen3-1.7b").reduced()
model = Model(cfg)
params = model.init(jax.random.key(0))
devices = jax.devices()

def engine(d):
    return ServingEngine(model, params, slots=2, max_len=64, chunk=8,
                         prefill_mode="chunked", kv="paged",
                         kv_block_size=8, device=d)

def on(tree, d):
    return all(x.devices() == {d} for x in jax.tree.leaves(tree))

def requests():
    rng = np.random.default_rng(0)
    return [Request(rid=i, prompt=rng.integers(0, cfg.vocab, 12)
                    .astype(np.int32), max_new_tokens=4) for i in range(4)]

solo = engine(devices[0])
want = requests()
for r in want:
    solo.submit(r)
solo.run()
router = ReplicaRouter([engine(d) for d in devices])
got = requests()
for r in got:
    router.submit(r)
router.run()
for e, d in zip(router.engines, devices):
    assert on(e.params, d) and on(e.caches, d), d
    assert e.stats()["device"] == str(d)
assert all(r.done for r in got)
assert [r.generated for r in got] == [r.generated for r in want]
print("PLACED " + json.dumps(
    [s["tokens_out"] for s in router.stats()["per_replica"]]))
""", n_devices=2)
    per = json.loads(out.split("PLACED ", 1)[1])
    assert len(per) == 2 and all(n > 0 for n in per)
