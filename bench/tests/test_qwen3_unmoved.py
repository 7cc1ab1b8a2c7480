"""Qwen3 reads through the architecture plug-in exactly what it read when
the harness served it alone: the same weights from the same seed, the same
counts on the real configuration and in the metric readers, and the same
comparison with the reference.  The pinned values were recorded with the
harness before the plug-in, on the CPU."""
import hashlib
from types import SimpleNamespace as NS

import jax
import numpy as np
import pytest

from bench import check, flops, manifest, system, weights
from tiny import shrunk

Q = manifest.architecture("qwen3")
SEED = 2**31 + 11
#: sha256 (first 16 hex digits) of every leaf's bytes, tiny qwen3, SEED
LEAVES = {
    "['embed']['tokens']": "c09136f8700f499c",
    "['final_norm']": "74047c77c79e21d2",
    "['layers']['attn']['k_norm']": "93661ce12657c804",
    "['layers']['attn']['q_norm']": "db566070e16de764",
    "['layers']['attn']['wk']": "4852f49b0905174e",
    "['layers']['attn']['wo']": "8211a88248277c2f",
    "['layers']['attn']['wq']": "180942e93ec26a0c",
    "['layers']['attn']['wv']": "e4f525d33e1caf16",
    "['layers']['mlp']['down']": "d5c446c0261702f2",
    "['layers']['mlp']['gate']": "d756463865456952",
    "['layers']['mlp']['up']": "21581a9577ea736a",
    "['layers']['norm1']": "26bf7a8f54614a8d",
    "['layers']['norm2']": "baf7710ad37c8694",
}
#: check.compare on the requests of _requests(), program and fp8 control
COMPARED = {"max_logit_gap": 1.4705965518951416,
            "sampled_set_gap": 1.3794150352478027,
            "tokens_compared": 87, "requests_compared": 4}
CONTROL = {"max_logit_gap": 0.07560175657272339,
           "sampled_set_gap": 0.05016136169433594, "tokens_compared": 87}


@pytest.fixture(scope="module")
def tiny_weights():
    cfg = shrunk("qwen3-1.7b")
    model = system.build_model(cfg)
    return cfg, weights.make(model.abstract(), SEED, arch=Q)


def test_weights_are_the_same_bits(tiny_weights):
    _, w = tiny_weights
    got = {jax.tree_util.keystr(p):
           hashlib.sha256(np.asarray(leaf).tobytes()).hexdigest()[:16]
           for p, leaf in jax.tree_util.tree_flatten_with_path(w)[0]}
    assert got == LEAVES


def _requests(vocab: int) -> list:
    """Fixed synthetic requests: prompt length, served tokens, sampling."""
    rng = np.random.default_rng(7)
    sizes = [(40, 12, None),
             (90, 30, {"temperature": 0.8, "top_k": 50, "top_p": 0.95}),
             (17, 5, None),
             (120, 40, {"temperature": 1.0, "top_k": 7, "top_p": 0.5})]
    return [NS(rid=i, prompt=rng.integers(0, vocab, P).astype(np.int32),
               tokens=list(rng.integers(0, vocab, n)), sampling=s)
            for i, (P, n, s) in enumerate(sizes)]


def test_compare_is_bit_identical(tiny_weights):
    cfg, w = tiny_weights
    reqs = _requests(cfg["config"]["vocab_size"])
    assert check.compare(w, cfg, reqs, 256, 40) == COMPARED
    got = check.compare(w, cfg, reqs, 256, 40, control="fp8")
    assert got == {**COMPARED, "control": CONTROL}


def test_counts_on_the_real_configuration():
    m = Q.shape(manifest.config(manifest.load(), "qwen3-1.7b"))
    assert m == {"layers": 28, "d": 2048, "heads": 16, "kv_heads": 8,
                 "head_dim": 128, "d_ff": 6144, "vocab": 151936,
                 "kv_bytes": 2}
    assert Q.linear_flops_per_token(m) == 2818572288
    assert Q.attention_flops(m, 1) == 229376
    assert Q.head_flops(m) == 622329856
    assert flops.decode_step_flops(Q, m, [517, 1000, 3072]) == \
        11375312896.0
    assert flops.prefill_flops(Q, m, 480, 512, True) == 94460968960
    assert flops.prefill_flops(Q, m, 0, 32, False) == 90315423744.0
    assert flops.decode_paged_call(m, [517, 1000, 3072]) == \
        (37593088.0, 18821120.0)
    assert flops.sampler_call(m, 32) == (24309760.0, 38895616.0)


class _Trace:
    """Calls and device seconds of each kernel, as the trace gives them."""

    def ops(self, name):
        return {"gqa_decode_paged": (40, 0.0021),
                "fused_sample": (3, 0.00041)}[name]


def test_readers_read_the_same_numbers():
    cfg = manifest.config(manifest.load(), "qwen3-1.7b")
    tick = lambda t0, t1, decode, prefill: NS(
        t0=t0, t1=t1, decode=decode, prefill=prefill)
    ticks = [tick(1.0, 1.08, [517, 1000, 3072], []),
             tick(1.08, 1.3, [518, 1001], [(480, 512, True), (0, 32, False)]),
             tick(1.3, 1.35, [519, 1002, 40, 2000, 7], [(32, 64, False)]),
             tick(5.0, 5.1, [9], [])]
    run = NS(window=(0.5, 4.0), traced=(0.5, 4.0), ticks=ticks,
             trace=_Trace(), chips=1,
             peak={"bf16_flops_s": 197e12, "hbm_bytes_s": 819e9},
             arch=Q, shape=Q.shape(cfg), engine=cfg["engine"])
    want = {"step_mfu": 0.45243678185351693,
            "decode_paged_roofline": 64.65578510378512,
            "sampler_roofline": 34.74994728848388}
    assert {m: manifest.metric_reader(m)(run) for m in want} == want
