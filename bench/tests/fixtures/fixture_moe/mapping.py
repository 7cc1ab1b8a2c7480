"""A test fixture's published keys onto the program's registered
expert-routed family (``family="moe"``).  Copied in as
``bench/mapping/fixture_moe.py`` by the test that an architecture is
added by files alone."""
from __future__ import annotations

import dataclasses

from bench.system import ConfigMismatch

PUBLISHED_TO_PROGRAM = {
    "num_hidden_layers": "n_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim",
    "intermediate_size": "d_ff",
    "num_experts": "n_experts",
    "num_experts_per_tok": "top_k",
    "vocab_size": "vocab",
    "rope_theta": "rope_theta",
}


def program_config(c: dict, registered):
    fields = {f: c[k] for k, f in PUBLISHED_TO_PROGRAM.items()}
    fields["rope_theta"] = float(fields["rope_theta"])
    mc = dataclasses.replace(registered, qk_norm=False,
                             moe_dense_residual=False, **fields)
    if mc.family != "moe" or mc.sliding_window or mc.layer_pattern:
        raise ConfigMismatch(f"{mc.name} is not an expert-routed "
                             "full-attention model")
    if not c["tie_word_embeddings"] or c["hidden_act"] != "silu" \
            or float(c["rms_norm_eps"]) != 1e-6:
        raise ConfigMismatch("the served family ties its head, uses SwiGLU "
                             "experts and RMSNorm epsilon 1e-6")
    return mc
