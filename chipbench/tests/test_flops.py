"""Model FLOPs and the flash forward's work, counted by hand."""
import pytest

from chipbench import flops
from chipbench.reference import Model

M720 = Model(n_layers=28, d_model=1152, n_heads=16, head_dim=72, d_ff=4608,
             in_dim=64)


def test_720m_train_step_at_16x256_is_11_2_tflop():
    # per token, forward: 28 blocks x 2 x (4 x 1152^2 + 2 x 1152 x 4608)
    # = 891.8M, plus embed and head 0.3M; attention adds 17.5M a token;
    # x3 for training = 2.73 GFLOP a token, x 4096 tokens
    f = flops.train_step_flops(M720, 1, 16, 256)
    assert f == pytest.approx(11.18e12, rel=2e-3)
    assert f / 4096 == pytest.approx(2.73e9, rel=2e-3)


def test_adaln_counts_once_per_sample():
    # doubling the tokens of one sample doubles all but the per-sample
    # work (timestep projection and adaLN) and the attention
    per_sample = 2 * 1152 * 1152 + 28 * 2 * 1152 * 6 * 1152
    attn = lambda t, s: 14 * (flops.attention_flops(t, s, 16, 72)
                              + flops.attention_flops(s, t, 16, 72))
    got = (flops.forward_flops(M720, 1, 16, 512)
           - 2 * flops.forward_flops(M720, 1, 16, 256))
    assert got == -per_sample + attn(16, 512) - 2 * attn(16, 256)


def test_flash_forward_cost_is_two_products_and_one_pass_over_qkvo():
    f, b = flops.flash_forward_cost(16, 256, 16, 72, 2)
    assert f == 4 * 16 * 16 * 256 * 256 * 72
    assert b == 4 * 16 * 16 * 256 * 72 * 2


def test_peaks_known_kind_and_unknown_is_an_error():
    p = flops.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        flops.peaks("TPU v9 imaginary")
