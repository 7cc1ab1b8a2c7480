"""Seeded random weights, made on the device in one jitted call.

The benchmark makes the weights; neither the program under test nor the
reference does.  The tree has the layout the served model takes (its
abstract shapes), and every leaf is drawn here, from the seed, in the
dtype it is served in:

* RMSNorm scales: ``1 + 0.1 * N(0, 1)``, so that a norm scale that the
  served path dropped would show in the comparison with the reference;
* the embedding: ``0.02 * N(0, 1)``;
* every projection: ``N(0, 1) / sqrt(fan_in)``, the fan-in as the
  architecture's ``fan_in(leaf_name, shape)`` gives it
  (``bench/reference/<model_type>.py``), or by :func:`default_fan_in`
  where it defines none.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

#: projections whose input spans two leading dims after the layer axis
_TWO_INPUT_DIMS = ("wo",)


def _leaf_name(path) -> str:
    return str(getattr(path[-1], "key", path[-1]))


def default_fan_in(name: str, shape: tuple) -> int:
    """Stacked layer leaves: (layers, in..., out...)."""
    if name in _TWO_INPUT_DIMS:
        return shape[1] * shape[2]
    return shape[1]


def run_key(seed: int) -> jax.Array:
    """A PRNG key from any whole-number seed, 64 bits and beyond."""
    seed = int(seed)
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _draw(abstract, fan_in, key):
    leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    keys = jax.random.split(key, len(leaves))
    out = []
    for (path, leaf), k in zip(leaves, keys):
        name = _leaf_name(path)
        z = jax.random.normal(k, leaf.shape, jnp.float32)
        if "norm" in name:
            x = 1.0 + 0.1 * z
        elif name == "tokens":
            x = 0.02 * z
        else:
            x = z / math.sqrt(fan_in(name, leaf.shape))
        out.append(x.astype(leaf.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def make(abstract, seed: int, device=None, arch=None):
    """The weight tree for ``abstract`` (a tree of ShapeDtypeStruct) from
    ``seed``, in one jitted program on ``device`` (default device if
    None), with the fan-in rule of ``arch`` (the architecture's reference
    module) where it has one."""
    fan_in = getattr(arch, "fan_in", default_fan_in)
    draw = functools.partial(_draw, abstract, fan_in)
    fn = jax.jit(draw)
    if device is not None:
        fn = jax.jit(draw,
                     out_shardings=jax.sharding.SingleDeviceSharding(device))
    return jax.block_until_ready(fn(run_key(seed)))
