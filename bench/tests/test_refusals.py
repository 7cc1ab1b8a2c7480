"""``bench/run.py`` measures only on a TPU it knows: elsewhere it exits
nonzero and prints no result; so does a configuration whose
``model_type`` has no reference or no mapping file."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
ARGS = ["--workload", "qwen3-chat", "--seed", str(2**31 + 9),
        "--seconds", "1", "--trace", "0"]


def _run(cwd: Path, script: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, str(script), *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_no_tpu_exits_nonzero_without_a_result():
    p = _run(ROOT, ROOT / "bench" / "run.py")
    assert p.returncode == 3, p.stderr[-2000:]
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_checkout_without_the_program_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, tmp_path / "bench" / "run.py")
    assert p.returncode == 2, p.stderr[-2000:]
    assert p.stdout.strip() == ""


def test_unknown_cell_exits_nonzero():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(ROOT / "bench" / "run.py"),
                        "--workload", "no-such-cell", "--seed", "1",
                        "--seconds", "1"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 2 and p.stdout.strip() == ""


@pytest.mark.parametrize("missing", ["both", "reference", "mapping"])
def test_model_type_without_its_files_exits_2(tmp_path, missing):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "src").symlink_to(ROOT / "src")
    cfg_path = tmp_path / "bench" / "configs" / "qwen3-1.7b.json"
    cfg = json.loads(cfg_path.read_text())
    if missing == "both":
        cfg["config"]["model_type"] = "no_such_architecture"
        cfg_path.write_text(json.dumps(cfg))
    else:
        (tmp_path / "bench" / missing / "qwen3.py").unlink()
    p = _run(tmp_path, tmp_path / "bench" / "run.py")
    assert p.returncode == 2, p.stderr[-2000:]
    assert p.stdout.strip() == ""
    assert "model_type" in p.stderr
