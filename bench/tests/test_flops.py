import pytest

from bench import flops

M = {"layers": 2, "d": 8, "heads": 4, "kv_heads": 2, "head_dim": 2,
     "d_ff": 16, "vocab": 10, "kv_bytes": 2}
PEAK = {"bf16_flops_s": 100.0, "hbm_bytes_s": 10.0}


def test_linear_flops_by_hand():
    # per layer: q 8x4x2, k/v 8x2x2 each, o 4x2x8, mlp 3x8x16
    per_layer = 2 * (64 + 32 + 32 + 64 + 384)
    assert flops.linear_flops_per_token(M) == 2 * per_layer


def test_attention_and_head():
    assert flops.attention_flops(M, 5) == 2 * 4 * 4 * 2 * 5
    assert flops.head_flops(M) == 2 * 8 * 10


def test_decode_step_sums_rows():
    one = flops.linear_flops_per_token(M) + flops.head_flops(M)
    assert flops.decode_step_flops(M, [3, 7]) == \
        2 * one + flops.attention_flops(M, 10)


def test_prefill_counts_every_position_once():
    lin = flops.linear_flops_per_token(M)
    # positions 2, 3, 4 attend to 3, 4, 5 keys
    want = 3 * lin + flops.attention_flops(M, 3 + 4 + 5)
    assert flops.prefill_flops(M, 2, 5, last=False) == want
    assert flops.prefill_flops(M, 2, 5, last=True) == \
        want + flops.head_flops(M)
    assert flops.prefill_flops(M, 4, 4, last=True) == 0.0
    whole = flops.prefill_flops(M, 0, 6, False)
    assert flops.prefill_flops(M, 0, 3, False) + \
        flops.prefill_flops(M, 3, 6, False) == whole


def test_paged_decode_call_by_hand():
    f, b = flops.decode_paged_call(M, [4, 6])
    assert f == 4 * 4 * 2 * 10
    # keys and values of 10 positions, 2 kv heads x 2 dims, bf16, plus
    # each row's query and output (4 heads x 2 dims, bf16)
    assert b == 2 * 2 * 2 * 10 * 2 + 2 * (2 * 4 * 2 * 2)


def test_sampler_call_reads_and_writes_f32_rows():
    f, b = flops.sampler_call(M, 3)
    assert b == 2 * 4 * 3 * 10
    assert f == 5 * 3 * 10


def test_least_time_takes_the_binding_bound():
    assert flops.least_time(100.0, 5.0, PEAK) == pytest.approx(1.0)
    assert flops.least_time(1.0, 10.0, PEAK) == pytest.approx(1.0)
    assert flops.least_time(1.0, 20.0, PEAK) == pytest.approx(2.0)


def test_model_shape_from_a_config_file():
    from bench import manifest
    man = manifest.load()
    m = flops.model_shape(manifest.config(man, "qwen3-1.7b"))
    assert (m["layers"], m["d"], m["heads"], m["kv_heads"], m["head_dim"],
            m["d_ff"], m["vocab"]) == (28, 2048, 16, 8, 128, 6144, 151936)
    # 1.41e9 non-embedding parameters -> ~2.8 GFLOP per token
    assert flops.linear_flops_per_token(m) == pytest.approx(2.82e9, rel=0.01)
