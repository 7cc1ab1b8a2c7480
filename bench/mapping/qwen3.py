"""Qwen3's published keys onto the program's ``ModelConfig``.

Every published key that the program can be told maps to a field of the
registered configuration; what the program cannot be told, it must
already do, and a configuration that asks for anything else is refused.
"""
from __future__ import annotations

import dataclasses

from bench.system import ConfigMismatch

#: published key -> field of the program's ModelConfig
PUBLISHED_TO_PROGRAM = {
    "num_hidden_layers": "n_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab",
    "rope_theta": "rope_theta",
}


def program_config(c: dict, registered):
    """The registered ModelConfig with the published sizes of ``c`` (the
    ``config`` group of a configuration file) and per-head qk-norm."""
    fields = {f: c[k] for k, f in PUBLISHED_TO_PROGRAM.items() if k in c}
    fields["rope_theta"] = float(fields["rope_theta"])
    mc = dataclasses.replace(registered, qk_norm=True, **fields)
    if not c.get("tie_word_embeddings", False):
        raise ConfigMismatch("the served model ties its embedding and head; "
                             "an untied configuration cannot be run")
    if c["hidden_act"] != "silu" or c.get("attention_bias", False) \
            or c.get("mlp_bias", False):
        raise ConfigMismatch("the served dense family is SwiGLU without "
                             "attention or MLP biases")
    if float(c["rms_norm_eps"]) != 1e-6:
        raise ConfigMismatch("the served RMSNorm has epsilon 1e-6")
    if mc.family != "dense" or mc.sliding_window or mc.layer_pattern:
        raise ConfigMismatch(f"{mc.name} is not a dense full-attention "
                             "model")
    return mc
