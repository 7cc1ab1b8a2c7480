"""Production meshes, built through ``repro.distributed.compat``.

Defined as FUNCTIONS so importing this module never touches jax device
state (jax locks the device count on first backend init).
``make_mesh`` is ``jax.make_mesh`` with all-Auto axes plus the refusal
of a mesh larger than the visible devices; install a mesh with
``jax.set_mesh``.
"""
from __future__ import annotations

from repro.distributed.compat import device_count, make_mesh

__all__ = ["device_count", "make_mesh", "make_production_mesh",
           "make_debug_mesh", "make_serving_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(n_devices: int, *, multi_pod: bool = False):
    """Small-device-count analogue for CI/tests (same axis names)."""
    if multi_pod:
        assert n_devices % 2 == 0
        per_pod = n_devices // 2
        d = _split(per_pod)
        return make_mesh((2,) + d, ("pod", "data", "model"))
    return make_mesh(_split(n_devices), ("data", "model"))


def make_serving_mesh(shards: int):
    """1-D tensor-parallel mesh for the sharded serving hot path.

    ``("model",)`` only: serving shards the head/mlp axes of one replica
    (concat-TP, see ``repro.distributed.tp``); data parallelism at serving
    scale is the engine-replica router (``repro.serving.router``), not a
    mesh axis.  Raises ``ValueError`` when ``shards`` exceeds the visible
    device count — callers must surface that, never shrink the mesh
    silently."""
    if shards < 1:
        raise ValueError(f"serving mesh needs >= 1 shard, got {shards}")
    return make_mesh((shards,), ("model",))


def _split(n: int) -> tuple[int, int]:
    a = 1
    for c in range(int(n ** 0.5), 0, -1):
        if n % c == 0:
            a = c
            break
    return (n // a, a)
