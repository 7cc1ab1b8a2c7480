"""The system under test, built from a configuration file.

This is the only module that imports the program (``src/repro``): the
registered model key, ``Model``, ``ServingEngine`` and its request types.
The configuration file states the model as it is run, with the published
key names; every published key maps to a field of the program's
configuration, so the served model is exactly what the file says.
"""
from __future__ import annotations

import dataclasses

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
#: weight and activation dtypes the served model can take
DTYPES = ("bfloat16", "float32")


class ConfigMismatch(RuntimeError):
    pass


def model_config(cfg_file: dict):
    """The program's ModelConfig for a benchmark configuration file."""
    from repro.configs.base import get_config
    c = cfg_file["config"]
    fields = {f: c[k] for k, f in PUBLISHED_TO_PROGRAM.items() if k in c}
    fields["rope_theta"] = float(fields["rope_theta"])
    if c["torch_dtype"] not in DTYPES:
        raise ConfigMismatch(f"torch_dtype {c['torch_dtype']!r} is not one "
                             f"of {DTYPES}")
    fields["param_dtype"] = fields["dtype"] = c["torch_dtype"]
    fields["qk_norm"] = c["model_type"] == "qwen3"
    mc = dataclasses.replace(get_config(cfg_file["model"]), **fields)
    # what the program cannot be told, it must already do
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
        raise ConfigMismatch(f"{cfg_file['model']} is not a dense "
                             "full-attention model")
    return mc


def build_model(cfg_file: dict):
    from repro.models.model import Model
    return Model(model_config(cfg_file))


def build_engine(model, params, engine_cfg: dict, device=None):
    from repro.serving import ServingEngine
    e = engine_cfg
    return ServingEngine(
        model, params, slots=e["slots"], max_len=e["max_len"], kv=e["kv"],
        kv_block_size=e.get("kv_block_size"),
        kv_pool_blocks=e.get("kv_pool_blocks"), chunk=e["chunk"],
        replan_every=e["replan_every"], device=device)


def chunk_sizes() -> tuple[int, ...]:
    """Every prefill-chunk width the engine's replanner may choose."""
    from repro.core.pipeline import SERVE_CHUNK_SIZES
    return tuple(SERVE_CHUNK_SIZES)


def program_request(req):
    """The program's Request for a generated one."""
    from repro.serving import Request, SamplingParams
    sampling = None
    if req.sampling is not None:
        s = req.sampling
        sampling = SamplingParams(temperature=s["temperature"],
                                  top_k=s["top_k"], top_p=s["top_p"],
                                  seed=s["seed"])
    return Request(rid=req.rid, prompt=req.prompt,
                   max_new_tokens=req.max_new, sampling=sampling)
