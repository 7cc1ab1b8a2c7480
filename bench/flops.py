"""Operations and bytes that the work needs, computed from shapes.

These are the numerators of every roofline share and of ``step_mfu``.
They count what the algorithm needs, not what a kernel happens to do: the
context a decode row attends to, not the block-padded table it walks; the
prompt tokens of a prefill chunk, not its padding.  A multiply-add is two
operations.  ``m`` is a model-shape dict with the keys of
:func:`model_shape`.
"""
from __future__ import annotations

from typing import Iterable


def model_shape(cfg_file: dict) -> dict:
    """The shapes these counts need, from a benchmark configuration file."""
    c = cfg_file["config"]
    heads = c["num_attention_heads"]
    return {
        "layers": c["num_hidden_layers"], "d": c["hidden_size"],
        "heads": heads, "kv_heads": c["num_key_value_heads"],
        "head_dim": c.get("head_dim") or c["hidden_size"] // heads,
        "d_ff": c["intermediate_size"], "vocab": c["vocab_size"],
        "kv_bytes": 2 if c["torch_dtype"] in ("bfloat16", "float16") else 4,
    }


def linear_flops_per_token(m: dict) -> float:
    """Projections and MLP of every layer, for one token (no attention
    scores, no embedding, no head)."""
    d, H, K, D, F = m["d"], m["heads"], m["kv_heads"], m["head_dim"], \
        m["d_ff"]
    per_layer = 2 * (d * (H + 2 * K) * D + H * D * d + 3 * d * F)
    return m["layers"] * per_layer


def attention_flops(m: dict, context: int) -> float:
    """Scores and weighted values of one query over ``context`` keys, all
    layers."""
    return m["layers"] * 4 * m["heads"] * m["head_dim"] * context


def head_flops(m: dict) -> float:
    """The tied embedding as the output head, for one logits row."""
    return 2 * m["d"] * m["vocab"]


def decode_step_flops(m: dict, contexts: Iterable[int]) -> float:
    """One decode step of the rows that decode, each attending to its
    context (the new token included)."""
    total = 0.0
    for c in contexts:
        total += linear_flops_per_token(m) + attention_flops(m, c) \
            + head_flops(m)
    return total


def prefill_flops(m: dict, start: int, stop: int, last: bool) -> float:
    """Prompt positions ``[start, stop)`` of one request, each attending to
    every position up to itself; ``last`` adds the logits row that gives
    the first token."""
    n = stop - start
    if n <= 0:
        return 0.0
    # sum over p in [start, stop) of (p + 1) keys
    keys = (stop * (stop + 1) - start * (start + 1)) // 2
    total = n * linear_flops_per_token(m) + attention_flops(m, 1) * keys
    return total + (head_flops(m) if last else 0.0)


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
