"""One general traffic generator, driven by a mix's data file.

A mix (``bench/traffic/<name>.json``) gives the arrival process and the
length distributions.  The *sizes* of the work come from the mix's own
``shape_seed``: every run seed gets the same multiset of prompt lengths,
output lengths, greedy/sampled requests and inter-arrival gaps, in another
order.  The run seed chooses that order and every token.  So two seeds
offer the same work, and the spread between runs is the system's, not the
generator's.  With ``fixed_order`` the order is the mix's own too, and the
run seed chooses only the tokens and the sampling seeds: where requests
live about as long as the window, any reorder moves work across its
edges.

Kind ``open_poisson``: independent users.  Requests are due on a schedule
that does not wait for the server: exponential gaps at ``rate_rps``.  The
schedule has three phases, each a fixed multiset of its own: ``warm``
(``warm_s`` seconds before the window, part of set-up), ``window``
(exactly ``round(rate * seconds)`` requests whose gaps sum to the window)
and ``drain`` (the process keeps running after the window until every
request due in it has its first token, at most ``drain_cap_s``).  With
``prime`` the warm phase opens with that many requests already in flight,
their output budgets cut to fractions spread evenly over them, so the
window opens on a server that holds a steady mix of request ages instead
of an empty one.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

KINDS = ("open_poisson",)


@dataclasses.dataclass
class Req:
    rid: int
    prompt: np.ndarray            # int32 token ids
    max_new: int
    greedy: bool
    sampling: dict | None         # temperature/top_k/top_p/seed, or None
    phase: str                    # warm | window | drain
    due: float | None = None      # seconds from the window's start
    # filled in by the run, on the run's clock
    due_at: float | None = None
    submitted: float | None = None
    token_times: list = dataclasses.field(default_factory=list)
    tokens: list | None = None
    failed: bool = False
    done: bool = False


def lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` lengths from ``spec``: lognormal around ``median`` with shape
    ``sigma``, clipped to ``[min, max]``."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    x = rng.lognormal(math.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


@dataclasses.dataclass
class _Shape:
    """A fixed multiset of request sizes (one row per request)."""
    prompt: np.ndarray
    output: np.ndarray
    greedy: np.ndarray


def _shapes(mix: dict, n: int, rng: np.random.Generator) -> _Shape:
    output = lengths(mix["output"], n, rng)
    prompt = lengths(mix["prompt"], n, rng)
    every = int(mix.get("greedy_every", 1))
    greedy = (np.arange(n) % every) == 0
    return _Shape(prompt, output, greedy)


def _gaps(rate: float, n: int, total: float | None,
          rng: np.random.Generator) -> np.ndarray:
    """``n`` exponential gaps at ``rate``; scaled to sum to ``total``."""
    g = rng.exponential(1.0 / rate, n)
    if total is not None:
        g *= total / g.sum()
    return g


class Traffic:
    """The requests of one run, from a mix, the run seed and its length."""

    def __init__(self, mix: dict, seed: int, seconds: float, vocab: int):
        if mix["kind"] not in KINDS:
            raise ValueError(f"unknown traffic kind {mix['kind']!r}")
        self.mix = mix
        self.kind = mix["kind"]
        self.seconds = float(seconds)
        self.vocab = int(vocab)
        self._shape_rng = np.random.default_rng(int(mix["shape_seed"]))
        self._rng = np.random.default_rng(int(seed))
        self._rid = 0
        self.requests = self._open()

    # -- building blocks ------------------------------------------------------
    def _tokens(self, n: int) -> np.ndarray:
        return self._rng.integers(0, self.vocab, int(n)).astype(np.int32)

    def _make(self, shapes: _Shape, order: np.ndarray,
              phase: str) -> list[Req]:
        samp = self.mix.get("sampling", {})
        out = []
        for i in order:
            prompt = self._tokens(shapes.prompt[i])
            greedy = bool(shapes.greedy[i])
            sampling = None if greedy else {
                "temperature": float(samp["temperature"]),
                "top_k": int(samp["top_k"]), "top_p": float(samp["top_p"]),
                "seed": int(self._rng.integers(0, 2**31 - 1))}
            out.append(Req(rid=self._rid, prompt=prompt,
                           max_new=int(shapes.output[i]), greedy=greedy,
                           sampling=sampling, phase=phase))
            self._rid += 1
        return out

    # -- open loop ------------------------------------------------------------
    def _open(self) -> list[Req]:
        mix, rate = self.mix, float(self.mix["rate_rps"])
        phases = (("warm", float(mix.get("warm_s", 0.0))),
                  ("window", self.seconds),
                  ("drain", float(mix["drain_cap_s"])))
        reqs: list[Req] = []
        t = -phases[0][1]
        fixed = bool(mix.get("fixed_order", False))
        n_prime = int(mix.get("prime", 0))
        if n_prime:
            shapes = _shapes(mix, n_prime, self._shape_rng)
            for i, r in enumerate(self._make(shapes, np.arange(n_prime),
                                             "warm")):
                r.due = t
                r.max_new = max(1, int(r.max_new * (i + 0.5) / n_prime))
                reqs.append(r)
        for phase, length in phases:
            n = int(round(rate * length))
            if n == 0:
                continue
            shapes = _shapes(mix, n, self._shape_rng)
            gaps = _gaps(rate, n, length, self._shape_rng)
            if not fixed:
                order = self._rng.permutation(n)
                gaps = gaps[self._rng.permutation(n)]
            else:
                order = np.arange(n)
            # the first request of a phase is due at its start, so the
            # window holds exactly n requests due in [0, seconds)
            dues = t + np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
            for r, due in zip(self._make(shapes, order, phase), dues):
                r.due = float(due)
                reqs.append(r)
            t += length
        return reqs
