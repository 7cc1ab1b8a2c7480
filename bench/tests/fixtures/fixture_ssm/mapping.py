"""A test fixture's published Mamba2 keys onto the program's registered
state-space family (``family="ssm"``).  Copied in as
``bench/mapping/fixture_ssm.py`` by the test that a state-space
architecture is added by files alone."""
from __future__ import annotations

import dataclasses

from bench.system import ConfigMismatch

PUBLISHED_TO_PROGRAM = {
    "num_hidden_layers": "n_layers",
    "hidden_size": "d_model",
    "vocab_size": "vocab",
    "state_size": "ssm_state",
    "expand": "ssm_expand",
    "head_dim": "ssm_head_dim",
    "conv_kernel": "ssm_conv",
    "chunk_size": "ssm_chunk",
    "n_groups": "ssm_groups",
}


def program_config(c: dict, registered):
    fields = {f: c[k] for k, f in PUBLISHED_TO_PROGRAM.items()}
    mc = dataclasses.replace(registered, **fields)
    if mc.family != "ssm":
        raise ConfigMismatch(f"{mc.name} is not a state-space model")
    if c["num_heads"] != mc.ssm_heads:
        raise ConfigMismatch(f"num_heads {c['num_heads']} is not expand * "
                             f"hidden_size / head_dim = {mc.ssm_heads}")
    if not c["tie_word_embeddings"] or c["hidden_act"] != "silu" \
            or c["use_bias"] or not c["use_conv_bias"] \
            or float(c["layer_norm_epsilon"]) != 1e-6:
        raise ConfigMismatch("the served family ties its head, gates with "
                             "SiLU, has a convolution bias and no "
                             "projection bias, and RMSNorm epsilon 1e-6")
    return mc
