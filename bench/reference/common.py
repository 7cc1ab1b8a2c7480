"""The float32 pieces every plain reference shares.

An architecture is a plug-in of the benchmark, found by the published
``config.model_type`` of a configuration file (``bench/manifest.py``):
``bench/reference/<model_type>.py`` holds its float32 reference and the
counts of its work, ``bench/mapping/<model_type>.py`` maps its published
keys onto the program's configuration.  A reference module imports
nothing of the program and exposes:

* ``RefConfig.from_file(cfg_file)``: a frozen, hashable dataclass of the
  sizes the reference needs (a static argument of the jitted check);
* ``published_layout(tree, rc)``: the served weight tree mapped to the
  published names and layout;
* ``logits_at(pub, tokens, positions, rc, control=None)``: float32 logits
  of one sequence at the given positions; ``control="fp8"`` is the same
  pass one precision step lower (:func:`_linear`);
* ``shape(cfg_file)``: the model-shape dict the counts and the metric
  readers take; it has at least ``heads``, ``kv_heads``, ``head_dim``,
  ``kv_bytes`` and ``vocab``, which the generic kernel counts of
  ``bench/flops.py`` read;
* ``linear_flops_per_token(m)``, ``attention_flops(m, context)`` and
  ``head_flops(m)``: the operations of one token's projections and
  feed-forward (routed experts only), of one query over ``context`` keys
  (a windowed layer counts at most its window), and of one logits row;
* ``paged_decode_layers(m)``: ``(layers, kv_heads)`` pairs, how many
  layers call the paged decode-attention kernel in one decode step and
  with how many kv heads;
* optionally ``fan_in(leaf_name, shape)``: the fan-in of a stacked weight
  leaf for ``bench/weights.py``, which otherwise takes ``shape[1]``
  (``shape[1] * shape[2]`` for ``wo``);
* optionally ``draw(leaf_name, shape, key)``: the leaf drawn as the
  architecture publishes its initialisation, a float32 array of
  ``shape`` from the leaf's own PRNG ``key``, or None to leave the leaf
  to ``bench/weights.py``'s rule.  It is traced inside the one jitted
  draw, so it uses ``jax.random`` and ``jax.numpy`` only.  A state-space
  or gated linear-attention architecture must draw its decay and step
  leaves as published (Mamba2: ``A`` uniform in [1, 16], the step
  log-uniform in [1e-3, 1e-1]): under the generic rule every head forgets
  within a token or two, and a state lost between prefill chunks cannot
  show in the served tokens the check compares.

Every matrix product here is float32 at ``HIGHEST`` precision.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0


def rotary_halves(head_dim: int) -> np.ndarray:
    """The column permutation from the served rotary layout (the two
    halves of a pair next to each other, dims ``2i, 2i+1``) to the
    published one (``D/2`` apart, dims ``i, i + D/2``)."""
    return np.concatenate([np.arange(0, head_dim, 2),
                           np.arange(1, head_dim, 2)])


def _fp8(x: jax.Array, axis) -> jax.Array:
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, FP8_MAX / amax, 1.0)
    return (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale


def _linear(x: jax.Array, w: jax.Array, spec: str, n_in: int,
            control: str | None) -> jax.Array:
    """``einsum(spec, x, w)``; ``n_in`` leading dims of ``w`` are inputs.
    ``control="fp8"`` rounds both operands to float8 e4m3 first (weights
    per output channel, activations per row) with a scale that maps the
    largest magnitude to 448, then multiplies in float32."""
    if control == "fp8":
        x = _fp8(x, axis=-1)
        w = _fp8(w, axis=tuple(range(n_in)))
    elif control is not None:
        raise ValueError(f"unknown control precision {control!r}")
    return jnp.einsum(spec, x, w, precision=HIGHEST)


def _rms(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding by halves; x: (S, heads, D)."""
    S, _, D = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    half = D // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


def _attention(q, k, v, q_block: int) -> jax.Array:
    """Causal GQA attention; q: (S, H, D), k/v: (S, K, D) -> (S, H, D).
    Queries go in blocks of ``q_block`` rows so that the score block is
    ``H * q_block * S`` floats."""
    S, H, D = q.shape
    K = k.shape[1]
    G = H // K
    kpos = jnp.arange(S)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * q_block, q_block, 0)
        qb = qb.reshape(q_block, K, G, D)
        s = jnp.einsum("qkgd,tkd->kgqt", qb, k,
                       precision=HIGHEST) / np.sqrt(D)
        qpos = i * q_block + jnp.arange(q_block)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("kgqt,tkd->qkgd", w, v, precision=HIGHEST)
        return o.reshape(q_block, H, D)

    out = jax.lax.map(block, jnp.arange(S // q_block))
    return out.reshape(S, H, D)
