"""Mixture-of-Experts with expert-parallel dispatch.

TPU adaptation of the paper's DOS (§4.2): the expert dimension is the purest
``outC`` split — expert weights *distribute* across the model axis (no
reduction over them), exactly like the paper distributing kernel parameters
across DSP units' L2 memories.  Implementation:

  * tokens are sharded over the data axis and *replicated* over the model
    axis, so no all-to-all is needed for dispatch: each model shard selects
    the tokens routed to ITS local experts;
  * dispatch is sort-based dropless-up-to-capacity: assignments are sorted
    by local expert id, truncated to a static capacity ``K_max =
    cf * T * k * E_local / E``, and computed with grouped matmuls
    (``jax.lax.ragged_dot``), giving per-shard compute ≈ T*k/E_shards;
  * partial outputs combine with one psum over the model axis (same
    collective as tensor-parallel FFN).

For very large expert weights (arctic-480b) the stored layout additionally
shards the expert ``d_model`` dim over the data axis (ZeRO-3 style); the
shard_map boundary all-gathers one layer's experts transiently (DESIGN.md §2,
a hillclimb target in EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from .layers import ParamSpec


def moe_specs(d: int, ff: int, n_experts: int) -> dict[str, ParamSpec]:
    return {
        "router": ParamSpec((d, n_experts), ("embed", "experts")),
        "gate": ParamSpec((n_experts, d, ff), ("experts", "embed", "expert_mlp")),
        "up": ParamSpec((n_experts, d, ff), ("experts", "embed", "expert_mlp")),
        "down": ParamSpec((n_experts, ff, d), ("experts", "expert_mlp", "embed")),
    }


def _ragged_ffn(xs: jax.Array, gate: jax.Array, up: jax.Array, down: jax.Array,
                gs: jax.Array) -> jax.Array:
    """Grouped SwiGLU over sorted rows.  A trailing all-zero 'trash expert'
    absorbs rows that belong to remote shards or overflow capacity."""
    zpad = lambda w: jnp.concatenate([w, jnp.zeros_like(w[:1])], axis=0)
    h = jax.nn.silu(lax.ragged_dot(xs, zpad(gate), gs)) \
        * lax.ragged_dot(xs, zpad(up), gs)
    return lax.ragged_dot(h, zpad(down), gs)


def _moe_local(x: jax.Array, router: jax.Array, gate: jax.Array, up: jax.Array,
               down: jax.Array, *, n_experts: int, top_k: int, e_local: int,
               lo: jax.Array, k_max: int) -> jax.Array:
    """Dispatch + grouped FFN for the experts in [lo, lo+e_local).

    x: (T, d) local tokens.  Returns the partial output (T, d).
    """
    T, d = x.shape
    logits = (x.astype(jnp.float32) @ router.astype(jnp.float32))
    top_v, top_i = lax.top_k(logits, top_k)                   # (T, k)
    weights = jax.nn.softmax(top_v, axis=-1)                  # renormalized
    flat_e = top_i.reshape(-1)                                # (T*k,)
    flat_w = weights.reshape(-1)
    local_e = flat_e - lo
    is_local = (local_e >= 0) & (local_e < e_local)
    sort_key = jnp.where(is_local, local_e, e_local)          # e_local = trash
    order = jnp.argsort(sort_key, stable=True)
    sel = order[:k_max]                                       # static capacity
    tok = sel // top_k
    xs = jnp.take(x, tok, axis=0)                             # (k_max, d)
    key_sorted = jnp.take(sort_key, sel)
    gs = jnp.bincount(key_sorted, length=e_local + 1)         # trash group last
    y = _ragged_ffn(xs, gate, up, down, gs)                   # (k_max, d)
    y = y * jnp.take(flat_w, sel)[:, None].astype(y.dtype)
    out = jnp.zeros((T, d), y.dtype).at[tok].add(y)
    return out


def load_balance_loss(x: jax.Array, router: jax.Array, *, n_experts: int,
                      top_k: int) -> jax.Array:
    """Switch-style auxiliary loss: n_e * sum_e f_e * p_e."""
    logits = (x.astype(jnp.float32) @ router.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    _, top_i = lax.top_k(logits, top_k)
    assigned = jax.nn.one_hot(top_i, n_experts, dtype=jnp.float32).sum(axis=-2)
    f = assigned.mean(axis=tuple(range(assigned.ndim - 1))) / top_k
    p = probs.mean(axis=tuple(range(probs.ndim - 1)))
    return n_experts * jnp.sum(f * p)


def moe_block(p: dict[str, jax.Array], x: jax.Array, *, cfg, mesh=None,
              batch_axes: tuple = ("data",)) -> tuple[jax.Array, jax.Array]:
    """MoE FFN.  x: (B, S, d) -> (out (B, S, d), aux_loss scalar).

    With a >1-way 'model' axis, runs expert-parallel inside shard_map;
    otherwise runs the identical local math on all experts (the oracle path
    tests compare against).
    """
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    xf = x.reshape(B * S, d)
    aux = load_balance_loss(xf, p["router"], n_experts=E, top_k=k)

    model_size = 1
    if mesh is not None and "model" in mesh.axis_names:
        model_size = mesh.shape["model"]

    if model_size == 1:
        t = B * S
        k_max = _round8(int(math.ceil(cfg.capacity_factor * t * k)))
        out = _moe_local(xf, p["router"], p["gate"], p["up"], p["down"],
                         n_experts=E, top_k=k, e_local=E,
                         lo=jnp.int32(0), k_max=k_max)
        return out.reshape(B, S, d).astype(x.dtype), aux

    e_local = E // model_size
    assert e_local * model_size == E, (E, model_size)
    n_batch_shards = 1
    for a in batch_axes:
        n_batch_shards *= mesh.shape[a]
    if (B * S) % max(n_batch_shards, 1):
        batch_axes = ()          # tiny decode batches: replicate tokens
        n_batch_shards = 1
    t_local = (B * S) // n_batch_shards
    k_max = _round8(int(math.ceil(cfg.capacity_factor * t_local * k * e_local / E)))
    bspec = tuple(batch_axes) if batch_axes else None

    def inner(xf_l, router, gate, up, down):
        rank = lax.axis_index("model")
        lo = (rank * e_local).astype(jnp.int32)
        out = _moe_local(xf_l, router, gate, up, down, n_experts=E, top_k=k,
                         e_local=e_local, lo=lo, k_max=k_max)
        return lax.psum(out, "model")

    fn = jax.shard_map(
        inner, mesh=mesh,
        in_specs=(P(bspec, None), P(None, None),
                  P("model", None, None), P("model", None, None),
                  P("model", None, None)),
        out_specs=P(bspec, None))
    out = fn(xf, p["router"], p["gate"], p["up"], p["down"])
    return out.reshape(B, S, d).astype(x.dtype), aux


def moe_reference(p: dict[str, jax.Array], x: jax.Array, *, cfg) -> jax.Array:
    """Dense oracle: every expert on every token, exact top-k combine.
    O(E/k) overcompute — tests only."""
    B, S, d = x.shape
    xf = x.reshape(-1, d)
    logits = xf.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    top_v, top_i = lax.top_k(logits, cfg.top_k)
    w = jax.nn.softmax(top_v, axis=-1)
    # (E, T, ff) dense compute
    h = jax.nn.silu(jnp.einsum("td,edf->etf", xf, p["gate"])) \
        * jnp.einsum("td,edf->etf", xf, p["up"])
    y_all = jnp.einsum("etf,efd->etd", h, p["down"])          # (E, T, d)
    gathered = jnp.take_along_axis(
        y_all.transpose(1, 0, 2), top_i[..., None], axis=1)   # (T, k, d)
    out = jnp.sum(gathered * w[..., None].astype(gathered.dtype), axis=1)
    return out.reshape(B, S, d).astype(x.dtype)


def _round8(n: int) -> int:
    return max(8, -(-n // 8) * 8)
