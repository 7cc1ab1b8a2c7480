"""The mesh constructor every multi-device path goes through.

Written against the installed jax (0.9), whose ``jax.sharding.AxisType``,
``jax.set_mesh`` and ``jax.shard_map(..., check_vma=...)`` callers use
directly.  ``make_mesh`` adds one thing to ``jax.make_mesh``: it refuses a
mesh larger than the visible device set with an error that says how to
get more devices, instead of jax's backend-specific one.
"""
from __future__ import annotations

import jax
import numpy as np


def device_count() -> int:
    """Devices visible to this process.

    On CPU this is 1 unless the process was started with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (jax locks the
    count at first backend init, so setting the flag after importing jax
    has no effect — tests spawn a subprocess instead, see
    ``tests/conftest.run_multidevice``)."""
    return len(jax.devices())


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """``jax.make_mesh`` with all-Auto axes (the only type this repo
    uses).

    Raises ``ValueError`` (not jax's backend-specific error) when the
    requested mesh is larger than the visible device set, with the
    forced-host-device escape hatch spelled out — callers like
    ``launch/serve.py --mesh-shards`` turn this into a nonzero exit
    instead of silently falling back to fewer devices.
    """
    need = int(np.prod(shape))
    have = device_count()
    if need > have:
        raise ValueError(
            f"mesh {dict(zip(axes, shape))} needs {need} devices but only "
            f"{have} are visible; on CPU relaunch the process with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={need} "
            "(the count is locked at first jax backend init)")
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
