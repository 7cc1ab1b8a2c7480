"""Public fused-sampler wrapper: one-sort support filter + keyed draw.

Two backends behind one call:

* ``jnp`` — the host/XLA fast path.  One ``lax.sort`` co-sorting the
  scaled logits with their indices replaces the reference's two
  full-vocab sorts, and the result is **bit-identical** to the
  reference filter: the co-sort yields the same descending value
  sequence (so the same k-th threshold) *and* the permutation, and
  because softmax is weakly monotone, gathering the masked
  probabilities through that permutation reproduces the reference's
  ``sort(probs)[::-1]`` value sequence exactly — same cumsum, same
  nucleus threshold, same support, same token.
* ``pallas`` — the TPU kernel (``fused_sampler.py``): sort-free
  single-pass threshold reduction, VMEM-resident row.

The categorical draw is shared and identical to the reference
(``fold_in(key(seed), step)`` then ``jax.random.categorical``), so the
backend choice never touches the PRNG contract.  The caller names the
backend — the ``sampler`` site of the ``kernel_select`` pass's plan —
and the kernel raises on a vocab it cannot tile rather than falling
back.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .. import interpret_mode
from .fused_sampler import fused_mask as _kernel_impl


def _mask_one(row, temperature, top_k, top_p):
    """One-sort filter for one ``(vocab,)`` row -> masked scaled logits."""
    vocab = row.shape[-1]
    safe_t = jnp.where(temperature > 0, temperature, 1.0)
    x = row / safe_t
    # one ascending co-sort gives both the descending values (top-k
    # threshold) and the argsort permutation (descending-prob gather)
    sx, perm = jax.lax.sort(
        (x, jnp.arange(vocab, dtype=jnp.int32)), num_keys=1)
    sx, perm = sx[::-1], perm[::-1]
    kth = sx[jnp.clip(top_k - 1, 0, vocab - 1)]
    x = jnp.where((top_k <= 0) | (x >= kth), x, -jnp.inf)
    probs = jax.nn.softmax(x)
    sp = probs[perm]             # == sort(probs)[::-1], bit for bit
    keep = (jnp.cumsum(sp) - sp) < jnp.maximum(top_p, 1e-6)
    thresh = jnp.min(jnp.where(keep, sp, jnp.inf))
    return jnp.where(probs >= thresh, x, -jnp.inf)


def _draw_one(row, masked, seed, step, temperature):
    key = jax.random.fold_in(jax.random.key(seed), step)
    sampled = jax.random.categorical(key, masked)
    return jnp.where(temperature <= 0, jnp.argmax(row),
                     sampled).astype(jnp.int32)


@partial(jax.jit, static_argnames=("vocab", "backend"))
def fused_sample(logits, seeds, steps, temperature, top_k, top_p, *,
                 vocab: int, backend: str = "jnp"):
    """Batched fused sampling: ``(B, V) -> (B,)`` int32 tokens.

    Same signature and PRNG contract as
    ``serving.sampling.sample_tokens`` — and token-identical to it for
    the same keyed draw (proven by ``tests/test_fused_sampler.py``).
    """
    rows = logits[..., :vocab].astype(jnp.float32)
    if backend == "pallas":
        masked = _kernel_impl(rows, temperature, top_k, top_p,
                              interpret=interpret_mode())
    elif backend == "jnp":
        masked = jax.vmap(_mask_one)(rows, temperature, top_k, top_p)
    else:
        raise ValueError(f"unknown sampler backend {backend!r}; "
                         "have jnp|pallas")
    return jax.vmap(_draw_one)(rows, masked, seeds, steps, temperature)


@partial(jax.jit, static_argnames=("vocab", "backend"))
def fused_sample_grid(logits, seeds, steps, temperature, top_k, top_p, *,
                      vocab: int, backend: str = "jnp"):
    """Speculative-verify sampling: ``(B, K1, V) -> (B, K1)`` tokens,
    keyed ``(seeds[b], steps[b] + i)`` per position exactly like
    ``serving.sampling.sample_token_grid``."""
    B, K1 = logits.shape[0], logits.shape[1]
    grid_steps = (steps[:, None] +
                  jnp.arange(K1, dtype=steps.dtype)[None, :])
    toks = fused_sample(
        logits.reshape(B * K1, logits.shape[2]),
        jnp.repeat(seeds, K1), grid_steps.reshape(-1),
        jnp.repeat(temperature, K1), jnp.repeat(top_k, K1),
        jnp.repeat(top_p, K1), vocab=vocab, backend=backend)
    return toks.reshape(B, K1)
