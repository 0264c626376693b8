"""FLOP and byte counts against shapes worked by hand."""
import pytest

import flops

TOY = {"hidden_size": 4, "num_attention_heads": 2, "num_key_value_heads": 1,
       "head_dim": 2, "intermediate_size": 8, "vocab_size": 16}
PEAK = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}


def test_dense_flops_per_token():
    # q 4x4, k 4x2, v 4x2, o 4x4, gate/up 4x8, down 8x4: 144 MACs a layer,
    # and the 4x16 tied head: 64
    assert flops.dense_flops_per_token(TOY, 1) == 2 * (144 + 64)
    assert flops.dense_flops_per_token(TOY, 3) == 2 * (3 * 144 + 64)


def test_attention_and_prompt_flops():
    assert flops.attn_flops(TOY, 1, 10) == 4 * 2 * 2 * 10
    assert flops.token_flops(TOY, 1, 10) == 416 + 160
    # 3 tokens see 1, 2 and 3 keys: 6 keys in all
    assert flops.prompt_flops(TOY, 1, 3) == 3 * 416 + 4 * 2 * 2 * 6


def test_paged_attention_work():
    f, b = flops.paged_attn_work(TOY, 1, 10)
    assert f == 4 * 2 * 2 * 11              # 10 cached keys and the new one
    # cached K and V rows (10 x 1 head x 2 x 2 bytes each), the new K and
    # V rows, q in and the output out (2 heads x 2 x 2 bytes each)
    assert b == 2 * 10 * 2 * 2 + 2 * 2 * 2 + 2 * 2 * 2 * 2
    f2, b2 = flops.paged_attn_work(TOY, 4, 10)
    assert (f2, b2) == (4 * f, 4 * b)


def test_roofline_takes_the_binding_bound():
    assert flops.roofline_seconds(1000.0, 10.0, PEAK) == 10.0    # compute
    assert flops.roofline_seconds(10.0, 1000.0, PEAK) == 100.0   # memory


def test_yi_step_matches_its_parameter_count():
    yi = {"hidden_size": 7168, "num_attention_heads": 56,
          "num_key_value_heads": 8, "intermediate_size": 20480,
          "vocab_size": 64000}
    params = 4 * (2 * 7168 * 7168 + 2 * 7168 * 1024 + 3 * 7168 * 20480) \
        + 64000 * 7168
    assert flops.dense_flops_per_token(yi, 4) == pytest.approx(2 * params)
    # decode at 1000 cached rows: 4 layers x 2 x 1000 x 8 x 128 x 2 bytes
    _, b = flops.paged_attn_work(yi, 4, 1000)
    assert b == pytest.approx(4 * (2 * 1000 * 8 * 128 * 2
                                   + 2 * 8 * 128 * 2 + 2 * 56 * 128 * 2))
