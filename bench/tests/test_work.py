"""Operations and bytes of a step against hand-worked numbers for a
one-layer model."""

import pytest

import work

CFG = {"hidden_size": 8, "num_attention_heads": 2, "num_key_value_heads": 1,
       "head_dim": 4, "intermediate_size": 16, "num_hidden_layers": 1,
       "vocab_size": 10, "model_type": "qwen3", "torch_dtype": "bfloat16"}
PEAKS = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}


def test_layer_sizes():
    # q 8x8, k 8x4, v 8x4, o 8x8; gate, up 8x16, down 16x8
    assert work.layer_params(CFG) == 64 + 32 + 32 + 64 + 3 * 128
    assert work.head_params(CFG) == 80
    # attn_norm 8 + mlp_norm 8 + q_norm 4 + k_norm 4, final norm 8
    assert work.norm_params(CFG) == 32
    # K and V, one kv head of 4, bf16
    assert work.kv_bytes_per_token(CFG) == 16


def test_decode_step():
    # one request decoding its 6th token: 6 keys
    flops, nbytes = work.step_work(CFG, [6], [])
    assert flops == 2 * 576 + 4 * 6 * 2 * 4 + 2 * 80
    weights = (576 + 80 + 32) * 2
    assert nbytes == weights + 8 * 2 + 6 * 16 + 16


def test_prefill_segment():
    # rows 3..5 of a 6-token prompt, which the segment completes:
    # rows attend to 4, 5 and 6 keys
    flops, nbytes = work.step_work(CFG, [], [(3, 3, True)])
    assert flops == 3 * 2 * 576 + 4 * (4 + 5 + 6) * 2 * 4 + 2 * 80
    weights = (576 + 80 + 32) * 2
    assert nbytes == weights + 3 * 8 * 2 + 6 * 16 + 3 * 16
    # a segment that does not end its prompt needs no output head
    assert work.step_work(CFG, [], [(3, 3, False)])[0] == flops - 160


def test_least_time():
    t, bound = work.least_time_s(1000.0, 50.0, PEAKS, 1)
    assert (t, bound) == (pytest.approx(10.0), "compute")
    t, bound = work.least_time_s(100.0, 50.0, PEAKS, 2)
    assert (t, bound) == (pytest.approx(2.5), "memory")
