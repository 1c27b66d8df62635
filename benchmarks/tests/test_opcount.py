import pytest

from benchmarks.lib import harness, opcount, weights


def cfg(name):
    return harness.load_json("configs", name + ".json")


def test_parameter_counts_are_the_published_ones():
    # 124.4 M and 1.316 B with the tied head counted once
    assert weights.gpt_param_count(cfg("gpt2-small")) == 124_439_808
    assert weights.gpt_param_count(cfg("cerebras-gpt-1.3b")) == \
        1_315_723_264


def test_peaks_are_keyed_by_exact_device_kind():
    assert opcount.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    assert opcount.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks recorded"):
        opcount.peaks("TPU v5")


def test_train_flops_by_hand():
    # gpt2-small, 16 x 1024: per token 12 * (8*768^2 + 4*1024*768
    # + 4*768*3072) + 2*768*50257 = 284,812,800; x3 x 16384 tokens
    f = opcount.transformer_train_flops(16, 1024, 12, 768, 3072, 50257)
    assert f == 3.0 * 16384 * 284_812_800
    # cerebras 1.3B, 8 x 2048: 24 * (8*2048^2 + 4*2048*2048 + 4*2048*8192)
    # + 2*2048*50257 = 24 * 117,440,512 + 205,852,672
    f = opcount.transformer_train_flops(8, 2048, 24, 2048, 8192, 50257)
    assert f == 3.0 * 16384 * (24 * 117_440_512 + 205_852_672)


def test_flash_by_hand():
    # one layer of gpt2-small at 16 x 1024, 12 heads of 64, causal:
    # a product is 2*16*12*1024*1024*64 / 2 = 12,884,901,888 FLOPs
    f, b = opcount.flash_attention(16, 1024, 12, 64)
    assert f == 7 * 12_884_901_888
    assert b == 12 * 16 * 1024 * 12 * 64 * 2
    f1, _ = opcount.flash_attention(2, 2048, 16, 128)
    assert f1 == 7 * (2.0 * 2 * 16 * 2048 * 2048 * 128 / 2)


def test_ragged_by_hand():
    # a decode row over 100 keys and a 3-row chunk ending at key 10
    # (rows attend 8, 9, 10): 127 keys; 16 heads of 128
    f, b = opcount.ragged_paged_attention([(1, 100), (3, 10)], 16, 128)
    assert f == 4 * 127 * 16 * 128
    assert b == (2 * 100 + 2 * 1 + 2 * 10 + 2 * 3) * 16 * 128 * 2
    t, side = opcount.roofline_s(f, b, "TPU v5 lite")
    assert side == "memory" and t == b / 819e9
