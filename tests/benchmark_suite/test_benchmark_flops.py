"""The FLOP and byte functions against values worked by hand."""

import pytest

from benchmark import flops, manifest

MISTRAL = dict(vocab_size=32768, d_model=4096, n_layers=2, n_heads=32,
               n_kv_heads=8, head_dim=128, d_ff=14336)
INTERNLM2 = dict(vocab_size=92544, d_model=2048, n_layers=24, n_heads=16,
                 n_kv_heads=8, head_dim=128, d_ff=8192)


def test_mistral_layer_is_218_1_m_parameters():
    # q 4096*4096, k and v 4096*1024 each, o 4096*4096, SwiGLU 3*4096*14336
    by_hand = 16_777_216 + 2 * 4_194_304 + 16_777_216 + 176_160_768
    assert flops.layer_matmul_params(MISTRAL) == by_hand == 218_103_808


def test_mistral_two_layers_hold_704_6_m_and_multiply_by_570_4_m():
    assert flops.matmul_params(MISTRAL) == 2 * 218_103_808 + 134_217_728
    # + embedding 134.2 M, two norms a layer and the final norm
    assert flops.total_params(MISTRAL) == 570_425_344 + 134_217_728 + 5 * 4096
    assert round(flops.total_params(MISTRAL) / 1e6, 1) == 704.7


def test_mistral_eight_layers_are_2_013_b():
    dims = dict(MISTRAL, n_layers=8)
    assert round(flops.total_params(dims) / 1e9, 3) == 2.013


def test_internlm2_whole_is_1_889_b():
    assert round(flops.total_params(INTERNLM2) / 1e9, 3) == 1.889


def test_attention_forward_counts_the_causal_half():
    # one head, 4 positions, head_dim 2: QK^T is 4*4*2 multiply-adds,
    # PV the same; 2 operations each; the mask needs half
    assert flops.attention_fwd_flops(1, 4, 1, 2) == 2 * (2 * 4 * 4 * 2) / 2
    assert flops.causal_matmul_flops(2, 4096, 32, 128) == (
        2 * 2 * 32 * 4096 * 4096 * 128 / 2)


def test_train_flops_per_token_of_the_one_chip_cell_is_3_62_g():
    per_token = flops.train_flops_per_token(MISTRAL, 4096)
    matmul = 6 * 570_425_344
    attention = 3 * 2 * (2 * 32 * 128 * 4096 * 4096) / 4096
    assert per_token == pytest.approx(matmul + attention)
    assert round(per_token / 1e9, 2) == 3.62
    assert round(flops.train_flops_per_token(
        dict(MISTRAL, n_layers=8), 4096) / 1e9, 1) == 12.1


def test_mfu_arithmetic():
    # 16,000 tokens/s at 3.62 GFLOP a token on one 197 TFLOP/s chip
    assert flops.mfu_pct(16_000, 3.62e9, 1, 197e12) == pytest.approx(29.4,
                                                                     abs=0.01)
    assert flops.mfu_pct(16_000, 3.62e9, 4, 197e12) == pytest.approx(
        29.4 / 4, abs=0.01)


def test_flash_calls_flops_and_bytes():
    one = flops.causal_matmul_flops(1, 4096, 32, 128)
    assert flops.flash_call_flops("fwd", 1, 4096, 32, 128) == 2 * one
    assert flops.flash_call_flops("dq", 1, 4096, 32, 128) == 3 * one
    assert flops.flash_call_flops("dkv", 1, 4096, 32, 128) == 4 * one
    tensor = 4096 * 32 * 128 * 2      # one bf16 [1, 4096, 32, 128]
    rows = 4096 * 32 * 4
    assert flops.flash_call_bytes("fwd", 1, 4096, 32, 128) == (
        4 * tensor + 2 * rows)
    assert flops.flash_call_bytes("dkv", 1, 4096, 32, 128) == (
        7 * tensor + 2 * rows)


def test_flash_least_time_says_which_bound_applies():
    long_ = flops.flash_min_seconds("fwd", 1, 4096, 32, 128, 197e12, 819e9)
    assert long_["bound"] == "flops"
    assert long_["seconds"] == pytest.approx(
        2 * (2 * 32 * 4096 * 4096 * 128 / 2) / 197e12)
    short = flops.flash_min_seconds("fwd", 1, 128, 32, 128, 197e12, 819e9)
    assert short["bound"] == "bytes"


def test_dims_of_the_real_configurations_match_the_hand_values():
    m = manifest.Manifest(manifest.ROOT)
    cell = m.cell("mistral7b-train-4k")
    dims = manifest.model_dims(cell.config, "train", 1)
    assert {k: dims[k] for k in MISTRAL} == MISTRAL
