"""Fused top-k/top-p support kernel: one pass, no vocab-size sorts.

Grid: (batch,).  Each step filters one logits row entirely in VMEM.
The reference sampler sorts the row twice (once for the k-th value,
once for the nucleus prefix); this kernel replaces both sorts
with 32-step binary searches over the *monotone uint32 key space* of
the scaled logits — for finite IEEE floats, ``sign-flip(bitcast(x))``
is an order-preserving injection into uint32, so value thresholds can
be found MSB-first without ever ordering the row:

* **top-k** — the largest key ``t`` with ``count(key >= t) >= k`` is
  exactly the key of the k-th largest scaled logit; ties at the
  threshold all survive, matching the reference's value-threshold rule.
* **top-p** — the largest key ``c`` with ``mass(keys > c) >= p`` puts
  the nucleus boundary between attained values: a surviving token is
  one whose strictly-greater mass is still ``< p``, i.e. ``key > c`` —
  the same support the reference derives from its descending cumsum
  (the most likely token always survives).

Per-row scalars (temperature / k / p) are scalar-prefetched into SMEM.
The keyed categorical draw stays *outside* the kernel (``ops.py``), so
the serving PRNG contract — ``fold_in(key(seed), emitted-step)`` per
request — is untouched by the backend choice.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import LANE


def _monotone_key(x):
    """Order-preserving uint32 key for finite float32 values."""
    u = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(u >> 31 > 0, ~u, u | jnp.uint32(0x80000000))


def _kernel(t_ref, k_ref, p_ref, x_ref, o_ref):
    b = pl.program_id(0)
    V = x_ref.shape[1] * x_ref.shape[2]
    row = x_ref[0].astype(jnp.float32)              # (V/128, 128)
    temperature = t_ref[b]
    top_k = k_ref[b]
    top_p = p_ref[b]

    safe_t = jnp.where(temperature > 0, temperature, 1.0)
    x = row / safe_t
    key = _monotone_key(x)

    # top-k: greedily build the largest threshold with >= k keys above it
    k_eff = jnp.clip(top_k, 1, V)

    def topk_bit(i, res):
        cand = res | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        cnt = jnp.sum((key >= cand).astype(jnp.int32))
        return jnp.where(cnt >= k_eff, cand, res)

    tk = jax.lax.fori_loop(0, 32, topk_bit, jnp.uint32(0))
    keep_k = (top_k <= 0) | (key >= tk)

    # nucleus mass over the top-k survivors
    xk = jnp.where(keep_k, x, -jnp.inf)
    m = jnp.max(xk)
    e = jnp.where(keep_k, jnp.exp(xk - m), 0.0)
    denom = jnp.sum(e)
    p_eff = jnp.maximum(top_p, 1e-6)
    kk = jnp.where(keep_k, key, jnp.uint32(0))

    # top-p: largest boundary with strictly-greater mass still >= p
    def topp_bit(i, res):
        cand = res | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        mass = jnp.sum(jnp.where(kk > cand, e, 0.0)) / denom
        return jnp.where(mass >= p_eff, cand, res)

    tp = jax.lax.fori_loop(0, 32, topp_bit, jnp.uint32(0))
    o_ref[0] = jnp.where(keep_k & (key > tp), x, -jnp.inf)


def fused_mask(rows: jax.Array, temperature: jax.Array, top_k: jax.Array,
               top_p: jax.Array, *, interpret: bool = True) -> jax.Array:
    """rows: (B, V) float32 with ``V`` a multiple of 128; temperature /
    top_p: (B,) float32; top_k: (B,) int32.  Returns the (B, V) masked
    scaled logits (surviving support keeps ``row / max(T, eps)``,
    everything else is ``-inf``).

    Each row is filtered as a ``(V/128, 128)`` block, which spans its
    array's last two dims and so tiles a TPU.  VMEM per step: 16*V bytes
    for the double-buffered f32 in and out blocks plus about six
    row-sized f32/u32 temporaries (24*V) — about 5.8 MiB at V=151936,
    inside the 16 MiB scoped-VMEM default of a v5e.
    """
    B, V = rows.shape
    if V % LANE:
        raise ValueError(f"fused_mask needs a vocab that is a multiple of "
                         f"{LANE}, got {V}")
    R = V // LANE
    row_spec = pl.BlockSpec((1, R, LANE), lambda b, t, k, p: (b, 0, 0))
    out = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(B,),
            in_specs=[row_spec], out_specs=row_spec),
        out_shape=jax.ShapeDtypeStruct((B, R, LANE), jnp.float32),
        interpret=interpret,
    )(temperature.astype(jnp.float32), top_k.astype(jnp.int32),
      top_p.astype(jnp.float32), rows.reshape(B, R, LANE))
    return out.reshape(B, V)
