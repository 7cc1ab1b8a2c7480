"""Cells and configurations shrunk to a size the CPU holds, for the tests
that drive the harness without the chip.  Every key not listed here is the
benchmark's own."""
from __future__ import annotations

import argparse
import copy

from bench import manifest

TINY_MODEL = {"hidden_size": 256, "intermediate_size": 512,
              "num_hidden_layers": 2, "num_attention_heads": 4,
              "num_key_value_heads": 2, "head_dim": 64, "vocab_size": 512}
TINY_ENGINE = {"slots": 4, "max_len": 256, "kv": "paged",
               "kv_pool_blocks": 32, "chunk": 32, "replan_every": 32}


def shrunk(config: str) -> dict:
    """A configuration file at the tiny widths."""
    cfg = copy.deepcopy(manifest.config(manifest.load(), config))
    cfg["config"].update(TINY_MODEL)
    return cfg


def override(limits: dict | None = None):
    """An ``override(ctx)`` for ``bench.run.run`` that shrinks the cell."""
    def shrink(ctx):
        ctx["config"]["config"].update(TINY_MODEL)
        ctx["config"]["engine"] = dict(TINY_ENGINE)
        mix = ctx["traffic"]
        mix.update(warm_s=0.5, drain_cap_s=20.0, rate_rps=4.0, prime=2)
        mix["prompt"] = {"dist": "lognormal", "median": 40, "sigma": 0.5,
                         "min": 8, "max": 120}
        mix["output"] = {"dist": "lognormal", "median": 12, "sigma": 0.5,
                         "min": 4, "max": 40}
        ctx["limits"].update(limits or {"max_logit_gap": 1e9,
                                        "sampled_set_gap": 1e9,
                                        "min_tokens": 1,
                                        "sample_requests": 3})
    return shrink


def args(cell: str, seed: int = 2**31 + 3, seconds: float = 2.0,
         trace: int = 0):
    return argparse.Namespace(workload=cell, seed=seed, seconds=seconds,
                              trace=trace)
