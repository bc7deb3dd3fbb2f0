"""The operation and byte counts behind the roofline and MFU metrics,
against hand calculations."""
import pytest

from bench import work

NEMO = {"attention": "gqa", "hidden_size": 5120, "num_attention_heads": 32,
        "num_key_value_heads": 8, "head_dim": 128,
        "intermediate_size": 14336, "vocab_size": 131072,
        "num_hidden_layers": 8, "dtype": "bfloat16"}


def test_decode_attention_bytes_of_two_ragged_rows():
    # rows with 100 and 3000 valid positions: K and V of 8 heads x 128,
    # bf16, plus q and the output (32 heads x 128) of each row
    flops, bytes_ = work.decode_attn_work(NEMO, [100, 3000])
    kv = (100 + 3000) * 2 * 8 * 128 * 2
    qo = 2 * (2 * 32 * 128 * 2)
    assert bytes_ == kv + qo
    assert flops == 4 * 32 * 128 * (100 + 3000)


def test_decode_attention_count_ignores_arena_length():
    # the count is the work the rows need, not the arena the kernel walks
    assert work.decode_attn_work(NEMO, [7]) == work.decode_attn_work(
        dict(NEMO), [7])
    f1, b1 = work.decode_attn_work(NEMO, [1000])
    f2, b2 = work.decode_attn_work(NEMO, [2000])
    assert f2 == 2 * f1 and b2 - b1 == 1000 * 2 * 8 * 128 * 2


def test_layer_params_match_published_shapes():
    # Mistral-Nemo layer: q 5120x4096, k and v 5120x1024, o 4096x5120,
    # SwiGLU 3 x 5120 x 14336 = 272.6 M
    assert work.layer_matmul_params(NEMO) == 272_629_760


def test_decode_step_flops_per_row():
    per_row = 2 * (8 * 272_629_760 + 5120 * 131072)
    attn = 8 * 4 * 32 * 128 * 512
    assert work.decode_step_flops(NEMO, [512]) == per_row + attn
    assert work.decode_step_flops(NEMO, [512, 512]) == 2 * (per_row + attn)
    assert work.attention_flops(NEMO, 512) == 4 * 32 * 128 * 512


def test_roofline_picks_the_binding_bound():
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert work.roofline_seconds(197e12, 1.0, peak) == pytest.approx(1.0)
    assert work.roofline_seconds(1.0, 819e9, peak) == pytest.approx(1.0)
