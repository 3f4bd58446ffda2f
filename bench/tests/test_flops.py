"""FLOP counts from shapes against hand counts at a tiny size, and the
peaks table."""
import pytest

from bench import flops, harness

QWEN = {"hidden_size": 8, "intermediate_size": 16, "num_hidden_layers": 2,
        "num_attention_heads": 2, "num_key_value_heads": 1,
        "vocab_size": 128}
MAMBA = {"d_model": 4, "n_layer": 3, "vocab_size": 100,
         "mamba2_layer": {"d_state": 2, "expand": 2, "headdim": 4,
                          "ngroups": 1, "chunk_size": 4, "d_conv": 4}}


def test_attention_train_step_by_hand():
    # per layer per token: q 2*8*8, k and v 2*8*4 each, o 2*8*8,
    # mlp 3*2*8*16 = 128 + 64 + 64 + 128 + 768 = 1152; scores 2*2*2*4*ctx
    # (QK^T and PV over 2 heads of size 4) = 32*ctx; head 2*8*128 = 2048
    B, S = 2, 4
    ctx = (S + 1) / 2
    fwd = B * S * (2 * (1152 + 32 * ctx) + 2048)
    assert flops.train_step(QWEN, B, S) == pytest.approx(3 * fwd)


def test_serve_tokens_by_hand():
    # positions 5, 6, 7 attend over 6, 7, 8 positions; one head read-out
    want = 2 * (3 * 1152 + 32 * (6 + 7 + 8)) + 1 * 2048
    assert flops.serve_tokens(QWEN, 5, 3, with_head=1) == pytest.approx(want)
    assert flops.serve_tokens(QWEN, 0, 1, 0) == pytest.approx(
        2 * (1152 + 32))


def test_ssd_train_step_by_hand():
    # d 4, d_in 8, heads 2 of 4, state 2, one group, chunk 4:
    # in-projections 2*4*(2*8 + 2*2 + 2) = 176, out 2*8*4 = 64;
    # core (causal half of the chunk) 2*2*1*2 + 2*2*2*4 + 2*2*2*2*4 = 104
    per_layer = 176 + 64 + 104
    head = 2 * 4 * 128                          # vocab padded to 128
    B, S = 3, 8
    assert flops.train_step(MAMBA, B, S) == pytest.approx(
        3 * B * S * (3 * per_layer + head))


def test_peaks_refuse_unknown_and_cpu():
    assert harness.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    for kind in ("cpu", "TPU v4", ""):
        with pytest.raises(KeyError):
            harness.peaks(kind)


def test_share_fails_loudly_above_the_peak():
    assert harness.share(1.0, 4.0, "x") == pytest.approx(25.0)
    with pytest.raises(ValueError, match="miscounted"):
        harness.share(1.01, 1.0, "mfu.train")
    with pytest.raises(ValueError):
        harness.share(-1.0, 1.0, "x")
