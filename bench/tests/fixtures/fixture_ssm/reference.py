"""A test fixture, not a published model: a tiny Mamba2 decoder.

Written from the published description of Mamba2 (``Mamba2ForCausalLM``,
arXiv:2405.21060): every layer is a pre-norm residual Mamba2 mixer.  The
mixer projects the normed input to a gate ``z``, the convolution input
``xBC`` and a step ``dt`` per head; ``xBC`` goes through a depthwise
causal convolution and SiLU and splits into ``x``, ``B`` and ``C``; the
state of each head advances token by token as

    h <- exp(dt * A) * h + dt * x (outer) B,     y = h . C + D * x

with ``dt = softplus(dt + dt_bias)`` and ``A = -exp(A_log)``; ``y`` is
gated by ``SiLU(z)`` before an RMSNorm and the output projection.  Tied
embedding.  The plain float32 reference runs the recurrence one token at
a time (the program runs it chunked); every projection is float32 at
``HIGHEST`` precision (``common.py``).  It imports nothing of the
program.

:func:`draw` gives the decay and step leaves their published
initialisation, so that some heads remember hundreds of tokens and a
state lost between prefill chunks shows in the served tokens.

Copied in as ``bench/reference/fixture_ssm.py`` by the test that a
state-space architecture is added by files alone.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from bench.reference.common import HIGHEST, _linear, _rms


@dataclasses.dataclass(frozen=True)
class RefConfig:
    n_layers: int
    d_model: int
    d_inner: int
    n_heads: int
    head_dim: int
    n_groups: int
    state: int
    conv: int
    vocab: int
    eps: float

    @classmethod
    def from_file(cls, file: dict) -> "RefConfig":
        c = file["config"]
        return cls(
            n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
            d_inner=c["expand"] * c["hidden_size"], n_heads=c["num_heads"],
            head_dim=c["head_dim"], n_groups=c["n_groups"],
            state=c["state_size"], conv=c["conv_kernel"],
            vocab=c["vocab_size"], eps=float(c["layer_norm_epsilon"]))


def published_layout(tree: dict, rc: RefConfig) -> dict:
    """The served leaves under Mamba2's published names: one input
    projection ``[z, x, B, C, dt]``, the convolution weight as
    ``(channels, kernel)``."""
    lay = tree["layers"]
    ssm = lay["ssm"]
    return {
        "embed": tree["embed"]["tokens"],
        "norm_f": tree["final_norm"],
        "layers": {
            "norm": lay["norm1"],
            "in_proj": jnp.concatenate([ssm["w_zx"], ssm["w_bc"],
                                        ssm["w_dt"]], -1),   # (L, d, E)
            "conv1d_weight": jnp.swapaxes(ssm["conv_w"], 1, 2),
            "conv1d_bias": ssm["conv_b"],
            "dt_bias": ssm["dt_bias"],
            "A_log": ssm["A_log"],
            "D": ssm["D"],
            "gated_norm": ssm["norm"],
            "out_proj": ssm["out"],                          # (L, di, d)
        },
    }


def _conv(x, w, b):
    """Depthwise causal convolution; x: (S, C), w: (C, K)."""
    K = w.shape[1]
    S = x.shape[0]
    pad = jnp.pad(x, ((K - 1, 0), (0, 0)))
    return sum(pad[k:k + S] * w[:, k] for k in range(K)) + b


def _mixer(x, lw, rc: RefConfig, control):
    di, g, n, H, P = rc.d_inner, rc.n_groups, rc.state, rc.n_heads, \
        rc.head_dim
    S = x.shape[0]
    proj = _linear(x, lw["in_proj"], "sd,de->se", 1, control)
    z, xbc, dt = proj[:, :di], proj[:, di:2 * di + 2 * g * n], \
        proj[:, 2 * di + 2 * g * n:]
    xbc = jax.nn.silu(_conv(xbc, lw["conv1d_weight"], lw["conv1d_bias"]))
    xs = xbc[:, :di].reshape(S, H, P)
    B = jnp.repeat(xbc[:, di:di + g * n].reshape(S, g, n), H // g, 1)
    C = jnp.repeat(xbc[:, di + g * n:].reshape(S, g, n), H // g, 1)
    dt = jax.nn.softplus(dt + lw["dt_bias"])                 # (S, H)
    A = -jnp.exp(lw["A_log"])

    def token(h, inp):
        dt_t, x_t, b_t, c_t = inp
        h = h * jnp.exp(dt_t * A)[:, None, None] \
            + (dt_t[:, None] * x_t)[..., None] * b_t[:, None, :]
        return h, jnp.einsum("hpn,hn->hp", h, c_t, precision=HIGHEST)

    _, y = jax.lax.scan(token, jnp.zeros((H, P, n), jnp.float32),
                        (dt, xs, B, C))
    y = (y + xs * lw["D"][:, None]).reshape(S, di)
    y = _rms(y * jax.nn.silu(z), lw["gated_norm"], rc.eps)
    return _linear(y, lw["out_proj"], "se,ed->sd", 1, control)


def hidden_states(weights: dict, tokens: jax.Array, cfg: RefConfig,
                  control: str | None = None) -> jax.Array:
    f32 = lambda a: a.astype(jnp.float32)
    h = f32(weights["embed"])[tokens]

    def layer(h, lw):
        lw = jax.tree.map(f32, lw)
        return h + _mixer(_rms(h, lw["norm"], cfg.eps), lw, cfg,
                          control), None

    h, _ = jax.lax.scan(layer, h, weights["layers"])
    return _rms(h, f32(weights["norm_f"]), cfg.eps)


def logits_at(weights: dict, tokens: jax.Array, positions: jax.Array,
              cfg: RefConfig, control: str | None = None) -> jax.Array:
    h = hidden_states(weights, tokens, cfg, control)[positions]
    emb = weights["embed"][:cfg.vocab].astype(jnp.float32)
    return _linear(h, emb.T, "sd,dv->sv", 1, control)


def draw(name: str, shape: tuple, key):
    """Mamba2's published initialisation of the decay and step leaves:
    ``A`` uniform in [1, 16], the step ``softplus(dt_bias)`` log-uniform
    in [1e-3, 1e-1], ``D`` one and the convolution bias zero.  Every
    other leaf takes ``bench/weights.py``'s rule."""
    if name == "A_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0,
                                          16.0))
    if name == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))      # softplus^-1
    if name == "D":
        return jnp.ones(shape, jnp.float32)
    if name == "conv_b":
        return jnp.zeros(shape, jnp.float32)
    return None


# ---- counts of the work (``bench/flops.py``); a multiply-add is two ----

def shape(cfg_file: dict) -> dict:
    c = cfg_file["config"]
    return {
        "layers": c["num_hidden_layers"], "d": c["hidden_size"],
        "d_inner": c["expand"] * c["hidden_size"],
        "ssm_heads": c["num_heads"], "ssm_head_dim": c["head_dim"],
        "groups": c["n_groups"], "state": c["state_size"],
        "conv": c["conv_kernel"], "vocab": c["vocab_size"],
        "heads": 0, "kv_heads": 0, "head_dim": 0,
        "kv_bytes": 2 if c["torch_dtype"] in ("bfloat16", "float16") else 4,
    }


def linear_flops_per_token(m: dict) -> float:
    """The projections, the convolution and one step of the recurrence
    (state update and read-out) of every layer, for one token."""
    d, di, H = m["d"], m["d_inner"], m["ssm_heads"]
    gn = m["groups"] * m["state"]
    proj = d * (2 * di + 2 * gn + H) + di * d
    conv = m["conv"] * (di + 2 * gn)
    scan = 2 * di * m["state"]
    return m["layers"] * 2 * (proj + conv + scan)


def attention_flops(m: dict, context: int) -> float:
    return 0.0


def head_flops(m: dict) -> float:
    return 2 * m["d"] * m["vocab"]


def paged_decode_layers(m: dict) -> list[tuple[int, int]]:
    """No layer keeps a KV cache."""
    return []
