import numpy as np
import pytest

from bench import manifest
from bench.traffic import Traffic

SEEDS = (3, 2**31 + 11, 9_000_000_001)


def _open_mix():
    return {"kind": "open_poisson", "rate_rps": 2.0, "warm_s": 3.0,
            "drain_cap_s": 10.0, "shape_seed": 7,
            "prompt": {"dist": "lognormal", "median": 512, "sigma": 1.0,
                       "min": 32, "max": 3072},
            "output": {"dist": "lognormal", "median": 128, "sigma": 0.8,
                       "min": 16, "max": 1024},
            "sampling": {"temperature": 0.8, "top_k": 50, "top_p": 0.95},
            "greedy_every": 4}


def _sig(reqs):
    return [(r.rid, r.due, r.max_new, r.greedy, r.prompt.tobytes(),
             r.sampling and r.sampling["seed"]) for r in reqs]


@pytest.mark.parametrize("seed", SEEDS)
def test_same_seed_same_requests(seed):
    a = Traffic(_open_mix(), seed, 20, 1000)
    b = Traffic(_open_mix(), seed, 20, 1000)
    assert _sig(a.requests) == _sig(b.requests)


def test_seeds_share_the_work_in_another_order():
    a = Traffic(_open_mix(), 1, 20, 1000)
    b = Traffic(_open_mix(), 2, 20, 1000)
    wa = [r for r in a.requests if r.phase == "window"]
    wb = [r for r in b.requests if r.phase == "window"]
    assert sorted((len(r.prompt), r.max_new, r.greedy) for r in wa) == \
        sorted((len(r.prompt), r.max_new, r.greedy) for r in wb)
    assert [len(r.prompt) for r in wa] != [len(r.prompt) for r in wb]
    # the gaps are one multiset, in another order (each window leaves out
    # the one gap that would follow its last request)
    ga = {round(x, 9) for x in np.diff([r.due for r in wa])}
    gb = {round(x, 9) for x in np.diff([r.due for r in wb])}
    assert len(ga & gb) >= len(ga) - 1


def test_fixed_order_varies_only_the_tokens():
    mix = _open_mix()
    mix["fixed_order"] = True
    a, b = (Traffic(mix, seed, 20, 1000).requests for seed in (1, 2))
    assert [(r.phase, r.due, len(r.prompt), r.max_new, r.greedy)
            for r in a] == [(r.phase, r.due, len(r.prompt), r.max_new,
                             r.greedy) for r in b]
    assert [r.prompt.tobytes() for r in a] != [r.prompt.tobytes() for r in b]
    free = [r for r in Traffic(_open_mix(), 1, 20, 1000).requests
            if r.phase == "window"]
    fixed = [r for r in a if r.phase == "window"]
    assert sorted((len(r.prompt), r.max_new) for r in fixed) == \
        sorted((len(r.prompt), r.max_new) for r in free)


def test_window_holds_rate_times_seconds():
    t = Traffic(_open_mix(), 5, 20, 1000)
    w = [r for r in t.requests if 0.0 <= r.due < 20]
    assert len(w) == 40
    assert all(r.phase == "window" for r in w)
    assert min(r.due for r in t.requests) == pytest.approx(-3.0)


def test_lengths_have_the_stated_medians_and_clips():
    mix = _open_mix()
    mix["rate_rps"] = 50.0
    t = Traffic(mix, 1, 100, 1000)
    p = np.array([len(r.prompt) for r in t.requests])
    o = np.array([r.max_new for r in t.requests])
    assert 32 <= p.min() and p.max() <= 3072
    assert 16 <= o.min() and o.max() <= 1024
    assert abs(np.median(p) / 512 - 1) < 0.1
    assert abs(np.median(o) / 128 - 1) < 0.1
    assert np.mean([r.greedy for r in t.requests]) == pytest.approx(0.25,
                                                                     abs=0.01)
    assert all((r.sampling is None) == r.greedy for r in t.requests)


@pytest.mark.parametrize("name", sorted(
    {w["traffic"] for w in manifest.load()["workloads"]}))
def test_every_mix_file_builds(name):
    mix = manifest.traffic(name)
    t = Traffic(mix, 2**31 + 5, 10, 151936)
    reqs = t.requests
    assert reqs
    assert all(len(r.prompt) + r.max_new <= 4096 for r in reqs)
