"""Plain float32 forward pass of a decoder-only GQA transformer.

Written from the published descriptions of Qwen3 (``Qwen3ForCausalLM``:
pre-norm RMSNorm, GQA, per-head RMSNorm on queries and keys, rotary
embedding by halves, SwiGLU, tied embedding).  It imports
nothing of the program under test: no kernels, no cache, no batching, every
matrix product in float32 at ``HIGHEST`` precision.

Weights come in the tree layout the served program takes (the benchmark
makes them, see ``bench/weights.py``) and are read here by name, layer by
layer, each layer cast to float32 inside the scan so that one layer at a
time is held in float32.

The one layout fact taken over from the served tree is where each rotary
pair sits: the served tree keeps the two halves of a rotary pair next to
each other (dims ``2i, 2i+1``), the published layout ``D/2`` apart (dims
``i, i + D/2``).  :func:`published_layout` permutes the query and key
columns and their norm scales from the one to the other, the permutation
a checkpoint converter applies; attention scores do not change under it.

``control`` selects the precision step below the configuration's bf16:
``"fp8"`` rounds both operands of every linear layer (weights per output
channel, activations per row) to float8 e4m3 with a scale that maps the
largest magnitude to 448, then multiplies in float32.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class RefConfig:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    rms_norm_eps: float

    @classmethod
    def from_file(cls, file: dict) -> "RefConfig":
        """From a benchmark configuration file (published key names)."""
        c = file["config"]
        return cls(
            n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
            n_heads=c["num_attention_heads"],
            n_kv_heads=c["num_key_value_heads"],
            head_dim=c.get("head_dim") or
            c["hidden_size"] // c["num_attention_heads"],
            d_ff=c["intermediate_size"], vocab=c["vocab_size"],
            rope_theta=float(c["rope_theta"]),
            rms_norm_eps=float(c["rms_norm_eps"]))


def published_layout(tree: dict, head_dim: int) -> dict:
    """Map the served tree to the published names and rotary layout."""
    perm = np.concatenate([np.arange(0, head_dim, 2),
                           np.arange(1, head_dim, 2)])
    lay = tree["layers"]
    attn = lay["attn"]
    out = {
        "embed": tree["embed"]["tokens"],
        "final_norm": tree["final_norm"],
        "layers": {
            "input_layernorm": lay["norm1"],
            "post_attention_layernorm": lay["norm2"],
            "q_proj": attn["wq"][..., perm],      # (L, d, H, D)
            "k_proj": attn["wk"][..., perm],      # (L, d, K, D)
            "v_proj": attn["wv"],
            "o_proj": attn["wo"],                 # (L, H, D, d)
            "gate_proj": lay["mlp"]["gate"],
            "up_proj": lay["mlp"]["up"],
            "down_proj": lay["mlp"]["down"],
            "q_norm": attn["q_norm"][..., perm],
            "k_norm": attn["k_norm"][..., perm],
        },
    }
    return out


FP8_MAX = 448.0


def _fp8(x: jax.Array, axis) -> jax.Array:
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, FP8_MAX / amax, 1.0)
    return (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale


def _linear(x: jax.Array, w: jax.Array, spec: str, n_in: int,
            control: str | None) -> jax.Array:
    """``einsum(spec, x, w)``; ``n_in`` leading dims of ``w`` are inputs."""
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


def hidden_states(weights: dict, tokens: jax.Array, cfg: RefConfig,
                  control: str | None = None,
                  q_block: int = 256) -> jax.Array:
    """Final-normed hidden states (S, d) of one sequence of token ids."""
    S = tokens.shape[0]
    if S % q_block:
        raise ValueError(f"sequence length {S} is not a multiple of "
                         f"{q_block}; pad it")
    f32 = lambda a: a.astype(jnp.float32)
    h = f32(weights["embed"])[tokens]
    eps = cfg.rms_norm_eps

    def layer(h, lw):
        lw = jax.tree.map(f32, lw)
        x = _rms(h, lw["input_layernorm"], eps)
        q = _linear(x, lw["q_proj"], "sd,dhk->shk", 1, control)
        k = _linear(x, lw["k_proj"], "sd,dhk->shk", 1, control)
        v = _linear(x, lw["v_proj"], "sd,dhk->shk", 1, control)
        q = _rms(q, lw["q_norm"], eps)
        k = _rms(k, lw["k_norm"], eps)
        q = _rope(q, cfg.rope_theta)
        k = _rope(k, cfg.rope_theta)
        a = _attention(q, k, v, q_block)
        h = h + _linear(a, lw["o_proj"], "shk,hkd->sd", 2, control)
        x = _rms(h, lw["post_attention_layernorm"], eps)
        g = _linear(x, lw["gate_proj"], "sd,df->sf", 1, control)
        u = _linear(x, lw["up_proj"], "sd,df->sf", 1, control)
        h = h + _linear(jax.nn.silu(g) * u, lw["down_proj"], "sf,fd->sd",
                        1, control)
        return h, None

    h, _ = jax.lax.scan(layer, h, weights["layers"])
    return _rms(h, f32(weights["final_norm"]), eps)


def logits_at(weights: dict, tokens: jax.Array, positions: jax.Array,
              cfg: RefConfig, control: str | None = None) -> jax.Array:
    """Logits (len(positions), vocab) for one sequence: the tied embedding
    applied at the given positions only."""
    h = hidden_states(weights, tokens, cfg, control)[positions]
    emb = weights["embed"][:cfg.vocab].astype(jnp.float32)
    return _linear(h, emb.T, "sd,dv->sv", 1, control)
