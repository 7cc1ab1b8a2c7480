"""Shared benchmark utilities."""
from __future__ import annotations

import os
import subprocess
import sys
import time
from pathlib import Path

import jax

REPO = Path(__file__).resolve().parent.parent


def timeit(fn, *args, warmup: int = 2, iters: int = 5) -> float:
    """Median wall-clock seconds per call (blocking)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def emit(name: str, seconds: float, derived: str = "") -> None:
    print(f"{name},{seconds * 1e6:.1f},{derived}")


def run_on_host_devices(code: str, n_devices: int,
                        timeout: int = 900) -> str:
    """Run python ``code`` in a child with ``n_devices`` forced CPU devices
    and return its stdout; a failed child raises with its stderr.

    Only a CPU process spawns such a child, and the child is pinned to the
    CPU: on an accelerator the parent holds the chip, and a child reaching
    for it would fail or hang."""
    if jax.default_backend() != "cpu":
        raise RuntimeError(
            "forced-host-device children are for the CPU backend only; "
            f"this process runs on {jax.default_backend()!r}")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={n_devices}",
               PYTHONPATH=os.pathsep.join([str(REPO), str(REPO / "src")]))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=timeout)
    if out.returncode != 0:
        raise RuntimeError(f"{n_devices}-device child failed (exit "
                           f"{out.returncode}):\n{out.stderr[-4000:]}")
    return out.stdout
