"""Operations and bytes that the work needs, computed from shapes.

These are the numerators of every roofline share and of ``step_mfu``.
They count what the algorithm needs, not what a kernel happens to do: the
context a decode row attends to, not the block-padded table it walks; the
prompt tokens of a prefill chunk, not its padding.  A multiply-add is two
operations.  ``arch`` is the reference module of the configuration's
architecture (``bench/reference/<model_type>.py``), which counts one
token's terms; ``m`` is the model-shape dict of its ``shape(cfg_file)``.
The kernel counts below need only the keys every such dict has.
"""
from __future__ import annotations

from typing import Iterable


def decode_step_flops(arch, m: dict, contexts: Iterable[int]) -> float:
    """One decode step of the rows that decode, each attending to its
    context (the new token included)."""
    total = 0.0
    for c in contexts:
        total += arch.linear_flops_per_token(m) + \
            arch.attention_flops(m, c) + arch.head_flops(m)
    return total


def prefill_flops(arch, m: dict, start: int, stop: int, last: bool) -> float:
    """Prompt positions ``[start, stop)`` of one request, position ``p``
    attending to the ``p + 1`` positions up to itself; ``last`` adds the
    logits row that gives the first token."""
    n = stop - start
    if n <= 0:
        return 0.0
    attn = sum(arch.attention_flops(m, p + 1) for p in range(start, stop))
    total = n * arch.linear_flops_per_token(m) + attn
    return total + (arch.head_flops(m) if last else 0.0)


def decode_paged_call(m: dict, contexts: Iterable[int]) -> tuple[float, float]:
    """(operations, bytes) of one call of the paged decode-attention kernel
    (one layer): each decoding row reads the keys and values of its
    context once and its query and output once."""
    H, K, D, kvb = m["heads"], m["kv_heads"], m["head_dim"], m["kv_bytes"]
    flops = 0.0
    nbytes = 0.0
    for c in contexts:
        flops += 4 * H * D * c
        nbytes += 2 * K * D * c * kvb + 2 * H * D * kvb
    return flops, nbytes


def sampler_call(m: dict, rows: int) -> tuple[float, float]:
    """(operations, bytes) of one call of the fused top-k/top-p filter:
    ``rows`` float32 vocabulary rows read once and written once; a handful
    of operations per entry (scale, key, exponent, compare, select)."""
    V = m["vocab"]
    return 5.0 * rows * V, 2.0 * 4 * rows * V


def least_time(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the operations
    over the bf16 peak and the bytes over the HBM bandwidth."""
    return max(flops / peak["bf16_flops_s"], nbytes / peak["hbm_bytes_s"])
