"""Shared fixtures.  NOTE: no XLA_FLAGS here — smoke tests and benches must
see the real (single) CPU device; multi-device tests spawn subprocesses."""
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parent.parent
SRC = REPO / "src"
for p in (str(REPO), str(SRC)):
    if p not in sys.path:
        sys.path.insert(0, p)  # `pytest tests/` from anywhere finds repro + benchmarks


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


def run_multidevice(code: str, n_devices: int = 8, timeout: int = 900) -> str:
    """Run a python snippet in a CPU-pinned subprocess with N host-platform
    devices; a failed child raises with its stderr."""
    from benchmarks.common import run_on_host_devices
    return run_on_host_devices(code, n_devices, timeout)
