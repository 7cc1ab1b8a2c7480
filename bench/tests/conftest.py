"""The benchmark's own tests run on the CPU:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

They import the harness as the package ``bench`` and the program from
``src``; neither is installed."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
