"""Qwen3: plain float32 forward pass and the counts of its work.

Written from the published descriptions of Qwen3 (``Qwen3ForCausalLM``:
pre-norm RMSNorm, GQA, per-head RMSNorm on queries and keys, rotary
embedding by halves, SwiGLU, tied embedding).  It imports
nothing of the program under test: no kernels, no cache, no batching, every
matrix product in float32 at ``HIGHEST`` precision (``common.py``).

Weights come in the tree layout the served program takes (the benchmark
makes them, see ``bench/weights.py``) and are read here by name, layer by
layer, each layer cast to float32 inside the scan so that one layer at a
time is held in float32.

The one layout fact taken over from the served tree is where each rotary
pair sits (:func:`common.rotary_halves`): :func:`published_layout` permutes
the query and key columns and their norm scales from the served layout to
the published one, the permutation a checkpoint converter applies;
attention scores do not change under it.

``control="fp8"`` selects the precision step below the configuration's
bf16 (:func:`common._linear`).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from bench.reference.common import (_attention, _linear, _rms, _rope,
                                    rotary_halves)


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


def published_layout(tree: dict, rc: RefConfig) -> dict:
    """Map the served tree to the published names and rotary layout."""
    perm = rotary_halves(rc.head_dim)
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


# ---- counts of the work (``bench/flops.py``); a multiply-add is two ----

def shape(cfg_file: dict) -> dict:
    """The shapes the counts need, from a benchmark configuration file."""
    c = cfg_file["config"]
    heads = c["num_attention_heads"]
    return {
        "layers": c["num_hidden_layers"], "d": c["hidden_size"],
        "heads": heads, "kv_heads": c["num_key_value_heads"],
        "head_dim": c.get("head_dim") or c["hidden_size"] // heads,
        "d_ff": c["intermediate_size"], "vocab": c["vocab_size"],
        "kv_bytes": 2 if c["torch_dtype"] in ("bfloat16", "float16") else 4,
    }


def linear_flops_per_token(m: dict) -> float:
    """Projections and MLP of every layer, for one token (no attention
    scores, no embedding, no head)."""
    d, H, K, D, F = m["d"], m["heads"], m["kv_heads"], m["head_dim"], \
        m["d_ff"]
    per_layer = 2 * (d * (H + 2 * K) * D + H * D * d + 3 * d * F)
    return m["layers"] * per_layer


def attention_flops(m: dict, context: int) -> float:
    """Scores and weighted values of one query over ``context`` keys, all
    layers (every layer attends to the whole context)."""
    return m["layers"] * 4 * m["heads"] * m["head_dim"] * context


def head_flops(m: dict) -> float:
    """The tied embedding as the output head, for one logits row."""
    return 2 * m["d"] * m["vocab"]


def paged_decode_layers(m: dict) -> list[tuple[int, int]]:
    """Every layer calls the paged decode kernel, with all its kv heads."""
    return [(m["layers"], m["kv_heads"])]
