"""FLOP and byte counts against hand counts at tiny shapes."""
import pytest

from bench import flops

CFG = dict(hidden_size=8, num_attention_heads=2, num_key_value_heads=1,
           head_dim=4, intermediate_size=16, vocab_size=10,
           num_hidden_layers=3)
PEAKS = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}


def test_matmul_params_by_hand():
    # per layer: wq 8x8 + wk 8x4 + wv 8x4 + wo 8x8 + 3 x 8x16; head 8x10
    layer = 64 + 32 + 32 + 64 + 3 * 128
    assert flops.matmul_params(CFG) == 3 * layer + 80


@pytest.mark.parametrize("context", [1, 5, 100])
def test_token_flops_by_hand(context):
    # 2 per weight; QK^T and PV: 2 x 2 x heads x head_dim per position
    want = 2 * flops.matmul_params(CFG) + 3 * 4 * 2 * 4 * context
    assert flops.token_flops(CFG, context) == want


def test_prefill_is_the_sum_of_its_tokens():
    assert flops.prefill_flops(CFG, 7) == sum(
        flops.token_flops(CFG, p + 1) for p in range(7))


def test_decode_attention_by_hand():
    f, b = flops.decode_attention(CFG, [3, 5])
    assert f == 4 * 2 * 4 * (3 + 5)
    # q + out: 2 x heads x head_dim; K + V: 2 x kv_heads x head_dim x len
    assert b == 2 * ((2 * 8 + 2 * 4 * 3) + (2 * 8 + 2 * 4 * 5))


@pytest.mark.parametrize("f,b,want", [(1000, 1, 10.0), (1, 1000, 100.0)])
def test_least_time_takes_the_larger_bound(f, b, want):
    assert flops.least_time(f, b, PEAKS) == want
