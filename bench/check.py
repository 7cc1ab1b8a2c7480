"""Whether what the timed path served is correct.

Once the window has closed and the program's state is freed, a sample of
the requests the run finished (drawn from the seed, the greedy and the
sampled request with the most output tokens always in it) goes through
the plain float32 reference (``bench/reference``), each prompt with its
served tokens.  At every served position the reference gives the logits
of the next token, and from them the set of tokens the request allows
there: the best one for a greedy request; for a sampled one the tokens
that survive its temperature, top-k and top-p filter, as the published
sampling rule defines it (keep the ``k`` highest tempered logits, then the
shortest run of them, from the top, whose renormalised mass reaches
``p``).  The gap of a served token is how far its logit lies below the
lowest logit of that set (0 inside it; infinite for a token outside the
vocabulary).  Two numbers are compared, each the widest gap of its kind:
``max_logit_gap`` over greedy tokens and ``sampled_set_gap`` over sampled
ones.

``compare(..., control=...)`` also reads the control: the reference
computed one precision step lower (float8 e4m3 operands for every linear
layer) put in the program's place.  At the same positions the token the
control puts first is read against the same sets, and ``verdict`` judges
that reading as it judges the program's; ``bench/calibrate.py`` reports
both.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .reference import model as ref

#: the numbers compared with a limit, by kind of request
GAP_KEYS = {True: "max_logit_gap", False: "sampled_set_gap"}


def pick(requests, n: int, seed: int) -> list:
    """Up to ``n`` finished requests: the longest greedy and the longest
    sampled one, and the rest drawn from ``seed``."""
    pool = [r for r in requests if r.done and not r.failed and r.tokens]
    pool.sort(key=lambda r: (-len(r.tokens), r.rid))
    first = []
    for greedy in (True, False):
        longest = next((r for r in pool if r.greedy == greedy), None)
        if longest is not None and len(first) < n:
            first.append(longest)
    taken = {r.rid for r in first}
    rest = [r for r in pool if r.rid not in taken]
    rng = np.random.default_rng(int(seed) + 1)
    k = min(n - len(first), len(rest))
    chosen = [rest[i] for i in sorted(rng.choice(len(rest), k,
                                                 replace=False))] if k else []
    return first + chosen


def _allowed_floor(logits, temperature, top_k: int, top_p):
    """Per position, the lowest untempered logit of the allowed set."""
    top = jax.lax.top_k(logits / temperature, top_k)[0]     # descending
    probs = jax.nn.softmax(top, axis=-1)
    keep = (jnp.cumsum(probs, -1) - probs) < top_p
    last = jnp.maximum(keep.sum(-1), 1) - 1
    return jnp.take_along_axis(top, last[:, None], -1)[:, 0] * temperature


@functools.partial(jax.jit, static_argnames=("rc", "top_k", "control"))
def _gaps(weights, tokens, positions, served, temperature, top_p, *, rc,
          top_k, control):
    pub = ref.published_layout(weights, rc.head_dim)
    logits = ref.logits_at(pub, tokens, positions, rc)
    floor = _allowed_floor(logits, temperature, top_k, top_p)

    def gap(tok):
        ok = (tok >= 0) & (tok < rc.vocab)
        got = jnp.take_along_axis(logits, jnp.clip(tok, 0, rc.vocab - 1)
                                  [:, None], -1)[:, 0]
        return jnp.where(ok, jnp.maximum(floor - got, 0.0), jnp.inf)

    if control is None:
        return gap(served), gap(served)
    low = ref.logits_at(pub, tokens, positions, rc, control)
    return gap(served), gap(jnp.argmax(low, -1))


def compare(weights, cfg_file: dict, reqs: list, seq_len: int,
            max_out: int, control: str | None = None) -> dict:
    """The widest gap of each kind over ``reqs``, and the same read for
    the control under ``"control"`` when ``control`` is given.  Every
    request is padded to one ``seq_len`` and ``max_out`` so that one
    program serves each kind."""
    rc = ref.RefConfig.from_file(cfg_file)
    widest = {k: 0.0 for k in GAP_KEYS.values()}
    low_widest = dict(widest)
    n_tokens = 0
    for r in reqs:
        toks = np.asarray(r.tokens, np.int64)
        P, n = len(r.prompt), len(toks)
        seq = np.zeros(seq_len, np.int32)
        seq[:P] = r.prompt
        seq[P:P + n - 1] = toks[:-1]
        pos = np.full(max_out, P - 1, np.int32)
        pos[:n] = P - 1 + np.arange(n)
        served = np.zeros(max_out, np.int32)
        served[:n] = np.clip(toks, -1, 2**31 - 1)
        s = r.sampling or {"temperature": 1.0, "top_k": 1, "top_p": 1.0}
        gaps, low = _gaps(weights, jnp.asarray(seq), jnp.asarray(pos),
                          jnp.asarray(served),
                          jnp.float32(s["temperature"]),
                          jnp.float32(s["top_p"]), rc=rc,
                          top_k=int(s["top_k"]), control=control)
        key = GAP_KEYS[r.sampling is None]
        widest[key] = max(widest[key], float(np.max(np.asarray(gaps)[:n])))
        low_widest[key] = max(low_widest[key],
                              float(np.max(np.asarray(low)[:n])))
        n_tokens += n
    out = {**widest, "tokens_compared": n_tokens,
           "requests_compared": len(reqs)}
    if control is not None:
        out["control"] = {**low_widest, "tokens_compared": n_tokens}
    return out


def verdict(result: dict, limits: dict) -> tuple[bool, dict]:
    """``correct`` and each number compared beside its limit."""
    checks = {}
    for key in GAP_KEYS.values():
        gap = result[key]
        checks[key] = {"value": gap, "limit": limits[key],
                       "ok": math.isfinite(gap) and gap <= limits[key]}
    n = result["tokens_compared"]
    checks["tokens_compared"] = {"value": n, "limit": limits["min_tokens"],
                                 "ok": n >= limits["min_tokens"]}
    return all(c["ok"] for c in checks.values()), checks
