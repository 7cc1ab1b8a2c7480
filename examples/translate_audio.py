"""Encoder-decoder (seamless-m4t) example: audio-frames -> text decode.

The audio frontend is the assignment's stub carve-out: precomputed frame
embeddings stand in for the mel+conformer feature extractor.  The decoder
prefills the target BOS prompt with cross-attention over the encoder
output, then greedy-decodes with self- and cross-KV caches.

    PYTHONPATH=src python examples/translate_audio.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_config
from repro.data import audio_batch_stub
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import Model


def main():
    cfg = get_config("seamless-m4t-large-v2").reduced()
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    print(f"arch={cfg.name} enc_layers={cfg.encoder_layers} "
          f"dec_layers={cfg.n_layers} params={model.param_count():,}")

    B, src_len = 2, 24
    stub = audio_batch_stub(B, src_len, 4, cfg.d_model, cfg.vocab, seed=0)
    batch = {"src": jnp.asarray(stub["src"]),
             "tokens": jnp.asarray(stub["tokens"][:, :4])}

    logits, caches = model.prefill_step(params, batch, max_len=32)
    tok = jnp.argmax(logits[:, :cfg.vocab], -1)[:, None].astype(jnp.int32)
    hyps = [tok]
    step = jax.jit(lambda p, c, t: model.serve_step(p, c, t))
    for _ in range(10):
        logits, caches = step(params, caches, tok)
        tok = jnp.argmax(logits[:, :cfg.vocab], -1)[:, None].astype(jnp.int32)
        hyps.append(tok)
    out = jnp.concatenate(hyps, axis=1)
    for b in range(B):
        print(f"utterance {b}: src_frames={src_len} -> tokens {np.asarray(out[b])}")
    assert bool(jnp.all(out >= 0)) and bool(jnp.all(out < cfg.vocab))
    print("translate_audio OK")


if __name__ == "__main__":
    enable_compile_cache()
    main()
