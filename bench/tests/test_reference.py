"""The plain float32 reference is the served model: at a reduced size on
the CPU it agrees with the program's own full-sequence forward pass."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import manifest, system, weights
from tiny import shrunk


@pytest.mark.parametrize("config", [c["name"] for c in
                                    manifest.load()["configs"]])
def test_reference_matches_the_program_forward(config):
    from repro.models.model import Model
    cfg_file = shrunk(config)
    ref = manifest.architecture(manifest.model_type(cfg_file))
    mc = dataclasses.replace(system.model_config(cfg_file), dtype="float32",
                             param_dtype="float32")
    model = Model(mc)
    w = weights.make(model.abstract(), 2**31 + 21, arch=ref)
    toks = np.random.default_rng(0).integers(0, mc.vocab, 256)
    toks = jnp.asarray(toks, jnp.int32)
    with jax.default_matmul_precision("highest"):
        want, _ = model.forward(w, {"tokens": toks[None]})
    rc = ref.RefConfig.from_file(cfg_file)
    got = jax.jit(lambda w, t: ref.logits_at(
        ref.published_layout(w, rc), t, jnp.arange(256), rc))(w, toks)
    want = np.asarray(want[0, :, :mc.vocab])
    scale = np.abs(want).max()
    assert np.abs(np.asarray(got) - want).max() <= 1e-5 * scale + 1e-6


def test_fp8_control_departs_from_the_reference():
    cfg_file = shrunk("qwen3-1.7b")
    ref = manifest.architecture("qwen3")
    from repro.models.model import Model
    model = Model(system.model_config(cfg_file))
    w = weights.make(model.abstract(), 5)
    rc = ref.RefConfig.from_file(cfg_file)
    toks = jnp.asarray(np.random.default_rng(1).integers(0, rc.vocab, 256),
                       jnp.int32)
    pub = ref.published_layout(w, rc)
    hi = ref.logits_at(pub, toks, jnp.arange(256), rc)
    lo = ref.logits_at(pub, toks, jnp.arange(256), rc, control="fp8")
    rel = float(jnp.abs(hi - lo).max() / jnp.abs(hi).max())
    assert 1e-3 < rel < 0.5
