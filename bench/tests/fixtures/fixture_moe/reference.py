"""A test fixture, not a published model: a tiny expert-routed decoder.

Pre-norm RMSNorm, GQA with rotary embedding by halves and no qk-norm, a
routed SwiGLU feed-forward (a linear router over every expert, the top
``k`` of them combined with the softmax of their router logits, no shared
expert), tied embedding.  The plain float32 reference runs every expert
on every token and takes the top-k combination afterwards; every matrix
product is float32 at ``HIGHEST`` precision (``common.py``).  It imports
nothing of the program.

Copied in as ``bench/reference/fixture_moe.py`` by the test that an
architecture is added by files alone.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from bench.reference.common import (_attention, _linear, _rms, _rope,
                                    rotary_halves)

#: expert leaves stacked as (layers, experts, in, out)
_EXPERT_LEAVES = ("gate", "up", "down")


@dataclasses.dataclass(frozen=True)
class RefConfig:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    n_experts: int
    top_k: int
    vocab: int
    rope_theta: float
    rms_norm_eps: float

    @classmethod
    def from_file(cls, file: dict) -> "RefConfig":
        c = file["config"]
        return cls(
            n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
            n_heads=c["num_attention_heads"],
            n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
            d_ff=c["intermediate_size"], n_experts=c["num_experts"],
            top_k=c["num_experts_per_tok"], vocab=c["vocab_size"],
            rope_theta=float(c["rope_theta"]),
            rms_norm_eps=float(c["rms_norm_eps"]))


def published_layout(tree: dict, rc: RefConfig) -> dict:
    perm = rotary_halves(rc.head_dim)
    lay = tree["layers"]
    attn, moe = lay["attn"], lay["moe"]
    return {
        "embed": tree["embed"]["tokens"],
        "final_norm": tree["final_norm"],
        "layers": {
            "input_layernorm": lay["norm1"],
            "post_attention_layernorm": lay["norm2"],
            "q_proj": attn["wq"][..., perm],      # (L, d, H, D)
            "k_proj": attn["wk"][..., perm],      # (L, d, K, D)
            "v_proj": attn["wv"],
            "o_proj": attn["wo"],                 # (L, H, D, d)
            "router": moe["router"],              # (L, d, E)
            "gate_proj": moe["gate"],             # (L, E, d, F)
            "up_proj": moe["up"],
            "down_proj": moe["down"],             # (L, E, F, d)
        },
    }


def _experts(x, lw, rc: RefConfig, control):
    """Every expert on every token, then the top-k softmax combination."""
    logits = _linear(x, lw["router"], "sd,de->se", 1, control)
    top_v, top_i = jax.lax.top_k(logits, rc.top_k)
    w = jax.nn.softmax(top_v, axis=-1)

    def expert(gate, up, down):
        g = _linear(x, gate, "sd,df->sf", 1, control)
        u = _linear(x, up, "sd,df->sf", 1, control)
        return _linear(jax.nn.silu(g) * u, down, "sf,fd->sd", 1, control)

    y = jax.vmap(expert, out_axes=1)(lw["gate_proj"], lw["up_proj"],
                                     lw["down_proj"])       # (S, E, d)
    picked = jnp.take_along_axis(y, top_i[..., None], 1)    # (S, k, d)
    return jnp.sum(picked * w[..., None], 1)


def hidden_states(weights: dict, tokens: jax.Array, cfg: RefConfig,
                  control: str | None = None,
                  q_block: int = 256) -> jax.Array:
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
        q = _rope(_linear(x, lw["q_proj"], "sd,dhk->shk", 1, control),
                  cfg.rope_theta)
        k = _rope(_linear(x, lw["k_proj"], "sd,dhk->shk", 1, control),
                  cfg.rope_theta)
        v = _linear(x, lw["v_proj"], "sd,dhk->shk", 1, control)
        a = _attention(q, k, v, q_block)
        h = h + _linear(a, lw["o_proj"], "shk,hkd->sd", 2, control)
        x = _rms(h, lw["post_attention_layernorm"], eps)
        return h + _experts(x, lw, cfg, control), None

    h, _ = jax.lax.scan(layer, h, weights["layers"])
    return _rms(h, f32(weights["final_norm"]), eps)


def logits_at(weights: dict, tokens: jax.Array, positions: jax.Array,
              cfg: RefConfig, control: str | None = None) -> jax.Array:
    h = hidden_states(weights, tokens, cfg, control)[positions]
    emb = weights["embed"][:cfg.vocab].astype(jnp.float32)
    return _linear(h, emb.T, "sd,dv->sv", 1, control)


def fan_in(name: str, shape: tuple) -> int:
    """Stacked leaves are (layers, in..., out...); an expert leaf is
    (layers, experts, in, out)."""
    if name in _EXPERT_LEAVES and len(shape) == 4:
        return shape[2]
    if name == "wo":
        return shape[1] * shape[2]
    return shape[1]


def shape(cfg_file: dict) -> dict:
    c = cfg_file["config"]
    return {
        "layers": c["num_hidden_layers"], "d": c["hidden_size"],
        "heads": c["num_attention_heads"],
        "kv_heads": c["num_key_value_heads"], "head_dim": c["head_dim"],
        "d_ff": c["intermediate_size"], "experts": c["num_experts"],
        "top_k": c["num_experts_per_tok"], "vocab": c["vocab_size"],
        "kv_bytes": 2 if c["torch_dtype"] in ("bfloat16", "float16") else 4,
    }


def linear_flops_per_token(m: dict) -> float:
    """Projections, the router, and the ``top_k`` experts a token is
    routed to, every layer."""
    d, H, K, D = m["d"], m["heads"], m["kv_heads"], m["head_dim"]
    per_layer = 2 * (d * (H + 2 * K) * D + H * D * d + d * m["experts"]
                     + m["top_k"] * 3 * d * m["d_ff"])
    return m["layers"] * per_layer


def attention_flops(m: dict, context: int) -> float:
    return m["layers"] * 4 * m["heads"] * m["head_dim"] * context


def head_flops(m: dict) -> float:
    return 2 * m["d"] * m["vocab"]


def paged_decode_layers(m: dict) -> list[tuple[int, int]]:
    return [(m["layers"], m["kv_heads"])]
