"""``flops.py`` against counts made by hand."""

import pytest

from benchmark import flops


def test_resnet50_forward_is_4_09_gmac_at_224():
    # stage by stage, by hand (MACs): stem 7x7x3x64 at 112^2 = 118.0M;
    # stage 1 (56^2): first block 64*64 + 9*64*64 + 64*256 + proj 64*256
    # per pixel, the two others 256*64 + 9*64*64 + 64*256
    stem = 112 * 112 * 64 * 147
    s1 = 56 * 56 * ((64 * 64 + 9 * 64 * 64 + 64 * 256 + 64 * 256)
                    + 2 * (256 * 64 + 9 * 64 * 64 + 64 * 256))
    # stage 2: first block's 1x1 still at 56^2 (v1.5), the rest at 28^2
    s2 = (56 * 56 * 256 * 128
          + 28 * 28 * (9 * 128 * 128 + 128 * 512 + 256 * 512)
          + 3 * 28 * 28 * (512 * 128 + 9 * 128 * 128 + 128 * 512))
    s3 = (28 * 28 * 512 * 256
          + 14 * 14 * (9 * 256 * 256 + 256 * 1024 + 512 * 1024)
          + 5 * 14 * 14 * (1024 * 256 + 9 * 256 * 256 + 256 * 1024))
    s4 = (14 * 14 * 1024 * 512
          + 7 * 7 * (9 * 512 * 512 + 512 * 2048 + 1024 * 2048)
          + 2 * 7 * 7 * (2048 * 512 + 9 * 512 * 512 + 512 * 2048))
    by_hand = stem + s1 + s2 + s3 + s4 + 2048 * 1000
    assert flops.resnet50_forward_macs(224) == by_hand
    assert by_hand == pytest.approx(4.09e9, rel=2e-3)
    assert flops.resnet50_train_flops_per_image(224) == 6 * by_hand


MISTRAL_L2 = dict(dim=4096, n_layers=2, n_heads=32, n_kv_heads=8,
                  head_dim=128, ffn_dim=14336, vocab=32000)


def test_decoder_counts_at_mistral_widths():
    per_layer = (2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336)
    assert per_layer == 218_103_808
    assert flops.decoder_matmul_params(**MISTRAL_L2) == (
        2 * per_layer + 4096 * 32000
    )
    # causal attention forward per token: 2 products x 2 x T x D / 2
    attn = 2 * 2 * 4096 * 4096 * 2 / 2
    assert flops.attention_flops_per_token(
        seq_len=4096, n_layers=2, n_heads=32, head_dim=128) == attn
    total = flops.decoder_train_flops_per_token(seq_len=4096, **MISTRAL_L2)
    assert total == 3 * (2 * (2 * per_layer + 4096 * 32000) + attn)
    assert total == pytest.approx(3.6e9, rel=5e-3)
    # full depth: 7.11B matrix parameters (7.24B with the embedding)
    full = dict(MISTRAL_L2, n_layers=32)
    assert flops.decoder_matmul_params(**full) == pytest.approx(7.11e9, rel=2e-3)


def test_flash_call_need_and_roofline():
    shape = dict(batch=2, n_heads=32, seq_len=4096, head_dim=128)
    ops, nbytes = flops.flash_call_need("fwd", **shape)
    assert ops == 2 * 2 * 2 * 32 * 4096 * 4096 * 128 / 2
    assert nbytes == 4 * 2 * 32 * 4096 * 128 * 2
    dkv, _ = flops.flash_call_need("dkv", **shape)
    dq, _ = flops.flash_call_need("dq", **shape)
    assert dkv + dq == 2 * ops          # backward needs twice the forward
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    t, bound = flops.least_seconds(ops, nbytes, peaks)
    assert bound == "compute" and t == ops / 197e12
    t, bound = flops.least_seconds(1e6, 819e9, peaks)
    assert bound == "memory" and t == 1.0
