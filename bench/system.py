"""The system under test, built from a configuration file.

This is the only module that imports the program (``src/repro``): the
registered model key, ``Model``, ``ServingEngine`` and its request types.
The configuration file states the model as it is run, with the published
key names; its architecture's mapping (``bench/mapping/<model_type>.py``,
found by ``config.model_type``) turns them into the program's
configuration and refuses what the program cannot be told, so the served
model is exactly what the file says.
"""
from __future__ import annotations

import dataclasses

from bench import manifest

#: weight and activation dtypes the served model can take
DTYPES = ("bfloat16", "float32")


class ConfigMismatch(RuntimeError):
    pass


def model_config(cfg_file: dict):
    """The program's ModelConfig for a benchmark configuration file."""
    from repro.configs.base import get_config
    c = cfg_file["config"]
    if c["torch_dtype"] not in DTYPES:
        raise ConfigMismatch(f"torch_dtype {c['torch_dtype']!r} is not one "
                             f"of {DTYPES}")
    mapping = manifest.mapping(manifest.model_type(cfg_file))
    mc = mapping.program_config(c, get_config(cfg_file["model"]))
    return dataclasses.replace(mc, param_dtype=c["torch_dtype"],
                               dtype=c["torch_dtype"])


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
