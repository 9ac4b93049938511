"""FLOP and byte counts against hand counts at Mixtral-8x7B widths, and the
peaks table."""

import pytest

from bench import flops, peaks

MIXTRAL = {"hidden_size": 4096, "intermediate_size": 14336,
           "num_hidden_layers": 4, "num_attention_heads": 32,
           "num_key_value_heads": 8, "num_local_experts": 8,
           "num_experts_per_tok": 2, "vocab_size": 32000,
           "sliding_window": 4096}


def test_layer_flops_count_routed_experts_only():
    # q, o: 4096x4096 each; k, v: 4096x1024 each; 2 FLOPs per MAC
    proj = 2 * 4096 * (4096 + 1024 + 1024 + 4096)
    scores = 4 * 32 * 128 * 10           # QK^T and PV over 10 keys
    router = 2 * 4096 * 8
    experts = 2 * (3 * 2 * 4096 * 14336)  # top-2, SwiGLU's three matmuls
    assert flops.layer_flops_per_token(MIXTRAL, 10) == pytest.approx(
        proj + scores + router + experts, rel=0, abs=0)


def test_window_caps_the_context():
    short = flops.layer_flops_per_token(MIXTRAL, 4096)
    assert flops.layer_flops_per_token(MIXTRAL, 10_000) == short


def test_prefill_flops_sum_per_position_and_head_once():
    want = sum(flops.layer_flops_per_token(MIXTRAL, p + 1)
               for p in range(256, 512)) * 4 + 2 * 4096 * 32000
    assert flops.prefill_flops(MIXTRAL, 256, 512, logits=1) == \
        pytest.approx(want, rel=1e-12)
    # past the window every position sees exactly 4096 keys
    win = flops.prefill_flops(MIXTRAL, 4096, 4100)
    assert win == pytest.approx(
        4 * 4 * flops.layer_flops_per_token(MIXTRAL, 4096), rel=1e-12)


def test_weight_bytes_at_mixtral_widths():
    per_layer = (4096 * (4096 + 1024 + 1024 + 4096)   # attention
                 + 8 * 3 * 4096 * 14336                 # experts
                 + 4096 * 8 + 2 * 4096)                 # router, norms
    want = 2 * (4 * per_layer + 4096 * 32000 + 4096)    # + head, final norm
    assert flops.weight_bytes(MIXTRAL) == want
    # the decode floor of the 4-layer cut: about 11.9 GB at 819 GB/s
    assert 14.0e-3 < want / peaks.peaks_for("TPU v5 lite").hbm_bytes_per_s \
        < 15.0e-3
    assert flops.weight_bytes(MIXTRAL, experts_read=2) < want


def test_v5e_peaks_and_unknown_kind():
    p = peaks.peaks_for("TPU v5 lite")
    assert (p.flops_bf16, p.hbm_bytes_per_s) == (197e12, 819e9)
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v9 imaginary")
