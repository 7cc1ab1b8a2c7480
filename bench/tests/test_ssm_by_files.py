"""A state-space architecture is added by files alone, and its own weight
draw lets the check see a state lost between prefill chunks.

In a copy of ``bench/`` and ``BENCHMARK.json`` the test adds only files
and entries, as ``test_arch_by_files.py`` does for the MoE: a fixture
Mamba2 (``fixtures/fixture_ssm/``: a reference with its ``draw``, a
mapping onto the program's registered ``family="ssm"`` path, a
configuration and a checks file) and one ``configs`` and one
``workloads`` entry.  Whole runs of the new cell at CPU size, every
prompt three full chunks long, so that the last chunk boundary lies 32
tokens before the first served token:

=========================  ===========  ===========
run                        weight draw  ``correct``
=========================  ===========  ===========
sound                      plug-in      true
fp8 control                plug-in      false
``ssm_state_dropped``      plug-in      false
``ssm_state_dropped``      default      true
=========================  ===========  ===========

The last row is the blindness the plug-in's draw removes: under the
default draw every head's state decays by about half a token, so a state
zeroed 32 tokens back has faded before the first served token.
"""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]
FIXTURE = Path(__file__).resolve().parent / "fixtures" / "fixture_ssm"
CELL = "fixture-ssm-chat"
SEED = 2**31 + 3

CHILD = r"""
import json, sys
sys.path[:0] = [sys.argv[1] + p for p in ("", "/src", "/bench/tests")]
import numpy as np
from bench import faults, manifest, run, system, weights
import tiny

cell, seed = sys.argv[2], int(sys.argv[3])


def shrink(ctx):
    mix = ctx["traffic"]
    mix.update(warm_s=0.5, drain_cap_s=20.0, rate_rps=4.0, prime=2)
    mix["prompt"] = {"dist": "lognormal", "median": 96, "sigma": 0.0,
                     "min": 96, "max": 96}
    mix["output"] = {"dist": "lognormal", "median": 12, "sigma": 0.5,
                     "min": 4, "max": 40}


def one(**kw):
    out = run.run(tiny.args(cell, seed=seed), require_tpu=False,
                  override=shrink, **kw)
    return {"correct": out["correct"], "control": out.get("control"),
            **{k: c["value"] for k, c in out["checks"].items()}}


dropped = faults.STATE_SPACE_FAULTS["ssm_state_dropped"]
cfg = manifest.config(manifest.load(), "fixture-ssm")
arch = manifest.architecture(manifest.model_type(cfg))
w = weights.make(system.build_model(cfg).abstract(), seed, arch=arch)
leaves = {k: np.asarray(v).tolist() for k, v in w["layers"]["ssm"].items()
          if k in ("A_log", "dt_bias", "D", "conv_b")}
out = {"sound": one(control="fp8"), "dropped": one(fault=dropped),
       "leaves": leaves, "module": manifest.__file__}
del arch.draw                       # the default rule for every leaf
out["dropped_default"] = one(fault=dropped)
print(json.dumps(out))
"""


def _install(tmp: Path) -> Path:
    """A checkout with the fixture architecture and its cell added."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp / "src").symlink_to(ROOT / "src")
    bench = tmp / "bench"
    shutil.copy(FIXTURE / "reference.py", bench / "reference" /
                "fixture_ssm.py")
    shutil.copy(FIXTURE / "mapping.py", bench / "mapping" / "fixture_ssm.py")
    shutil.copy(FIXTURE / "config.json", bench / "configs" /
                "fixture-ssm.json")
    shutil.copy(FIXTURE / "checks.json", bench / "checks" / f"{CELL}.json")
    man = json.loads((tmp / "BENCHMARK.json").read_text())
    man["configs"].append({
        "name": "fixture-ssm", "source": "bench/tests/fixtures/fixture_ssm",
        "file": "bench/configs/fixture-ssm.json", "reduced": [],
        "why": "a tiny Mamba2 fixture"})
    man["workloads"].append({
        "name": CELL, "config": "fixture-ssm", "traffic": "chat",
        "chips": 1, "why": "the fixture served with the chat mix"})
    (tmp / "BENCHMARK.json").write_text(json.dumps(man, indent=1))
    return tmp


def test_a_state_space_architecture_draws_its_own_leaves(tmp_path):
    tmp = _install(tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", CHILD, str(tmp), CELL,
                        str(SEED)],
                       cwd=tmp, env=env, capture_output=True, text=True,
                       timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert Path(out["module"]).is_relative_to(tmp)
    lim = json.loads((FIXTURE / "checks.json").read_text())

    sound = out["sound"]
    assert sound["correct"], sound
    assert sound["tokens_compared"] >= lim["min_tokens"]
    assert sound["control"]["correct"] is False, sound["control"]
    dropped = out["dropped"]
    assert not dropped["correct"], dropped
    assert max(dropped["max_logit_gap"], dropped["sampled_set_gap"]) > \
        3 * lim["max_logit_gap"], dropped
    # the blindness this draw removes: the same fault, the default draw
    blind = out["dropped_default"]
    assert blind["correct"], blind

    leaves = {k: np.asarray(v) for k, v in out["leaves"].items()}
    A = np.exp(leaves["A_log"])
    assert A.min() >= 1.0 and A.max() <= 16.0 and np.ptp(A) > 5.0
    dt = np.log1p(np.exp(leaves["dt_bias"]))          # softplus
    assert dt.min() >= 0.99e-3 and dt.max() <= 1.01e-1
    assert np.ptp(np.log10(dt)) > 1.0
    assert (leaves["D"] == 1.0).all() and (leaves["conv_b"] == 0.0).all()
