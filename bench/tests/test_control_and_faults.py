"""The comparison that decides ``correct`` can fail.

At a size the CPU holds, a whole run of the harness (traffic, engine,
window, drain, reference) with the chip check skipped:

* the control, the reference computed one precision step lower (float8
  operands) in the program's place, is judged not correct by the same
  limits, and reads well above the program;
* each fault a serving cell can have (``bench/faults.py``), planted in the
  timed path, turns ``correct`` false: a decode step that returns its
  state (the KV cache) unchanged, and a token altered where it is
  produced.

The limits here are set for this size as the cells' limits are set for
theirs: between the sound runs' widest gaps and the control's smallest.
"""
import pytest

import tiny
from bench import faults, manifest, run

SEEDS = (2**31 + 3, 77)
CELLS = [w["name"] for w in manifest.load()["workloads"]]
#: at this size sound runs read 0.0016 (greedy) and 0.006 (sampled) at
#: most, the control 0.069 at least (greedy)
TINY_LIMITS = {"max_logit_gap": 0.03, "sampled_set_gap": 0.03,
               "min_tokens": 20, "sample_requests": 4}


def _run(cell, seed, **kw):
    return run.run(tiny.args(cell, seed=seed), require_tpu=False,
                   override=tiny.override(TINY_LIMITS), **kw)


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("seed", SEEDS)
def test_sound_run_is_correct_and_control_is_not(cell, seed):
    out = _run(cell, seed, control="fp8")
    assert out["correct"], out["checks"]
    assert out["control"]["correct"] is False, out["control"]
    assert out["control"]["max_logit_gap"] >= \
        3 * out["checks"]["max_logit_gap"]["value"]
    assert list(out)[-1] == "checks"
    assert set(out["checks"]) == {"max_logit_gap", "sampled_set_gap",
                                  "tokens_compared"}


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
@pytest.mark.parametrize("cell", CELLS)
def test_planted_fault_is_not_correct(cell, fault):
    out = _run(cell, SEEDS[0], fault=faults.FAULTS[fault])
    assert not out["correct"], out["checks"]
    if fault == "token_altered":
        # the sampled tokens alone catch it too
        c = out["checks"]["sampled_set_gap"]
        assert c["value"] > c["limit"], out["checks"]
