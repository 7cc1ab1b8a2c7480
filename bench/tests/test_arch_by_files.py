"""An architecture is added by files alone.

In a copy of ``bench/`` and ``BENCHMARK.json`` the test adds only files
and entries: a fixture architecture (a reference and a mapping, from
``fixtures/fixture_moe/``), a configuration file, a checks file, one
``configs`` and one ``workloads`` entry.  The fixture is a tiny
expert-routed model on the program's registered ``family="moe"`` path;
its reference runs every expert on every token.  A whole run of the new
cell at CPU size comes out correct, a planted fault does not, and its
expert leaves draw at ``N(0, 1) / sqrt(fan_in)``.  The run goes in a
child process, so that the copy's harness is the one imported.

The fixture is served in float32, where its gaps are rounding.  This
shows that the harness takes an architecture from files; it does not
show that the check separates a sound expert-routed run in bfloat16 from
its control, where router near-ties can flip an expert.
"""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
FIXTURE = Path(__file__).resolve().parent / "fixtures" / "fixture_moe"
CELL = "fixture-moe-chat"

CHILD = r"""
import json, sys
sys.path[:0] = [sys.argv[1], sys.argv[1] + "/bench/tests"]
import numpy as np
from bench import faults, manifest, run, system, weights
import tiny

lim = manifest.limits(sys.argv[2])
sound = run.run(tiny.args(sys.argv[2]), require_tpu=False,
                override=tiny.override(lim))
fault = run.run(tiny.args(sys.argv[2]), require_tpu=False,
                override=tiny.override(lim),
                fault=faults.FAULTS["token_altered"])
cfg = manifest.config(manifest.load(), "fixture-moe")
arch = manifest.architecture(manifest.model_type(cfg))
model = system.build_model(cfg)
w = weights.make(model.abstract(), 2**31 + 5, arch=arch)
std = {k: float(np.std(np.asarray(v, np.float32)))
       for k, v in w["layers"]["moe"].items()}
print(json.dumps({"sound": sound, "fault": fault, "std": std,
                  "module": manifest.__file__}))
"""


def _install(tmp: Path) -> Path:
    """A checkout with the fixture architecture and its cell added."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp / "src").symlink_to(ROOT / "src")
    bench = tmp / "bench"
    shutil.copy(FIXTURE / "reference.py", bench / "reference" /
                "fixture_moe.py")
    shutil.copy(FIXTURE / "mapping.py", bench / "mapping" / "fixture_moe.py")
    shutil.copy(FIXTURE / "config.json", bench / "configs" /
                "fixture-moe.json")
    shutil.copy(FIXTURE / "checks.json", bench / "checks" / f"{CELL}.json")
    man = json.loads((tmp / "BENCHMARK.json").read_text())
    man["configs"].append({
        "name": "fixture-moe", "source": "bench/tests/fixtures/fixture_moe",
        "file": "bench/configs/fixture-moe.json", "reduced": [],
        "why": "a tiny expert-routed fixture"})
    man["workloads"].append({
        "name": CELL, "config": "fixture-moe", "traffic": "chat",
        "chips": 1, "why": "the fixture served with the chat mix"})
    (tmp / "BENCHMARK.json").write_text(json.dumps(man, indent=1))
    return tmp


def test_an_architecture_is_added_by_files_alone(tmp_path):
    tmp = _install(tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", CHILD, str(tmp), CELL],
                       cwd=tmp, env=env, capture_output=True, text=True,
                       timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert Path(out["module"]).is_relative_to(tmp)
    sound, fault = out["sound"], out["fault"]
    assert sound["correct"], sound["checks"]
    assert sound["checks"]["tokens_compared"]["value"] >= 20
    assert not fault["correct"], fault["checks"]
    cfg = json.loads((FIXTURE / "config.json").read_text())["config"]
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    want = {"gate": d ** -0.5, "up": d ** -0.5, "down": ff ** -0.5,
            "router": d ** -0.5}
    for name, std in out["std"].items():
        assert abs(std / want[name] - 1) < 0.05, (name, std, want[name])


def test_the_generic_harness_names_no_architecture():
    """Only the plug-in files know an architecture: the rest of the harness
    looks it up by ``model_type`` and never branches on it."""
    bench = ROOT / "bench"
    files = [bench / f for f in ("check.py", "system.py", "flops.py",
                                 "weights.py", "run.py")]
    files += sorted((bench / "metrics").glob("*.py"))
    for f in files:
        text = f.read_text()
        assert "qwen" not in text.lower(), f
        assert not re.search(r"model_type\W*(==|!=|\bin\b)", text), f
