"""The allowed set of a served token, and the sample that is compared."""
from types import SimpleNamespace as NS

import jax.numpy as jnp
import numpy as np
import pytest

from bench import check


def _floor_by_hand(row, t, k, p):
    z = np.sort(row / t)[::-1][:k]
    e = np.exp(z - z.max())
    probs = e / e.sum()
    keep = (np.cumsum(probs) - probs) < p
    return z[max(keep.sum(), 1) - 1] * t


@pytest.mark.parametrize("t,k,p", [(0.8, 50, 0.95), (1.0, 1, 1.0),
                                   (0.5, 7, 0.3), (2.0, 20, 0.999)])
def test_allowed_floor_is_the_published_rule(t, k, p):
    rows = np.random.default_rng(3).normal(0, 2, (5, 300)).astype(np.float32)
    got = np.asarray(check._allowed_floor(jnp.asarray(rows), t, k, p))
    want = [_floor_by_hand(r, t, k, p) for r in rows]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_greedy_floor_is_the_best_logit():
    rows = np.random.default_rng(4).normal(0, 1, (3, 40)).astype(np.float32)
    got = check._allowed_floor(jnp.asarray(rows), 1.0, 1, 1.0)
    np.testing.assert_allclose(np.asarray(got), rows.max(-1))


def _req(rid, n, greedy, done=True):
    return NS(rid=rid, tokens=[1] * n, greedy=greedy, done=done,
              failed=False)


def test_pick_keeps_the_longest_of_each_kind():
    reqs = [_req(0, 5, True), _req(1, 50, True), _req(2, 9, False),
            _req(3, 30, False), _req(4, 99, True, done=False),
            _req(5, 3, True), _req(6, 4, False)]
    got = check.pick(reqs, 4, seed=2**31 + 1)
    assert [r.rid for r in got[:2]] == [1, 3]
    assert len(got) == 4 and len({r.rid for r in got}) == 4
    assert all(r.done for r in got)
    assert check.pick(reqs, 4, seed=2**31 + 1) == got


def test_verdict_compares_each_number_with_its_limit():
    lim = {"max_logit_gap": 0.2, "sampled_set_gap": 0.1, "min_tokens": 10}
    ok, c = check.verdict({"max_logit_gap": 0.05, "sampled_set_gap": 0.0,
                           "tokens_compared": 10}, lim)
    assert ok and c["sampled_set_gap"]["limit"] == 0.1
    assert not check.verdict({"max_logit_gap": 0.05, "sampled_set_gap": 0.2,
                              "tokens_compared": 10}, lim)[0]
    assert not check.verdict({"max_logit_gap": float("inf"),
                              "sampled_set_gap": 0.0,
                              "tokens_compared": 10}, lim)[0]
    assert not check.verdict({"max_logit_gap": 0.0, "sampled_set_gap": 0.0,
                              "tokens_compared": 9}, lim)[0]
