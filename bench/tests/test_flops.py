"""The operation and byte counts against hand arithmetic for both
configurations, and the peak table."""

from __future__ import annotations

import pytest

from bench.lib import flops, peaks
from bench.tests import smoke

H2O = smoke.load("bench", "configs", "h2o-danube-1.8b.json")["model"]
YI = smoke.load("bench", "configs", "yi-6b-pp4.json")["model"]


def test_matmul_params():
    # h2o: per layer q 2560*2560 + k,v 2*2560*640 + o 2560*2560 + ffn 3*2560*6912
    per_layer = 6_553_600 + 3_276_800 + 6_553_600 + 53_084_160
    assert flops.matmul_params(H2O) == 24 * per_layer + 32_000 * 2560 == 1_749_155_840
    # yi, 8 layers: q 4096*4096 + k,v 2*4096*512 + o 4096*4096 + ffn 3*4096*11008
    per_layer = 16_777_216 + 4_194_304 + 16_777_216 + 135_266_304
    assert flops.matmul_params(YI) == 8 * per_layer + 64_000 * 4096 == 1_646_264_320


def test_weight_bytes_match_the_served_float32_weights():
    # matmul parameters + embedding + norm scales, 4 bytes each
    h2o = flops.matmul_params(H2O) + 32_000 * 2560 + (2 * 24 + 1) * 2560
    assert 4 * h2o == 7_324_805_120  # the program's weight multicast counted this
    yi = flops.matmul_params(YI) + 64_000 * 4096 + (2 * 8 + 1) * 4096
    assert 4 * yi == 7_633_911_808


def test_kv_bytes_per_position():
    assert flops.kv_bytes_per_position(H2O) == 24 * 2 * 8 * 80 * 2 == 61_440
    assert flops.kv_bytes_per_position(YI) == 8 * 2 * 4 * 128 * 2 == 16_384


def test_train_flops_per_token():
    # forward of 512 causal tokens: 2 N per token plus 4 L H Dh per key,
    # sum of keys = 512 * 513 / 2 = 131_328 (the 4096 window is wider)
    fwd = 2 * 1_749_155_840 * 512 + 4 * 24 * 32 * 80 * 131_328
    assert flops.train_flops_per_token(H2O, 512) == pytest.approx(3 * fwd / 512)
    assert flops.train_flops_per_token(H2O, 512) * 2048 == pytest.approx(2.188e13, rel=1e-3)


def test_attention_window():
    # window 4: keys per query 1, 2, 3, 4, 4, 4
    assert flops.attended_sum(0, 6, 4) == 18
    assert flops.attended_sum(2, 6, 4) == 15
    assert flops.attended_sum(0, 6, None) == 21
    assert flops.attended(9, 4) == 4 and flops.attended(9, None) == 10


def test_decode_step_flops_and_cache_bytes():
    per_key = 4 * 8 * 32 * 128
    assert flops.decode_step_flops(YI, [99, 1999]) == 2 * (2 * 1_646_264_320) + per_key * (100 + 2000)
    # h2o's 4096-token window: a row at position 5000 attends 4096 keys
    assert flops.decode_step_flops(H2O, [5000]) == 2 * 1_749_155_840 + 4 * 24 * 32 * 80 * 4096
    # 16 slots x 4608 positions x 16384 B is the whole yi cache the step reads
    assert 16 * 4608 * flops.kv_bytes_per_position(YI) == 1_207_959_552


def test_prefix_suffix_flops():
    # a 40-token suffix after a 1024-token cached prefix
    got = flops.forward_flops(H2O, 1024, 1064)
    keys = sum(p + 1 for p in range(1024, 1064))
    assert got == 2 * 1_749_155_840 * 40 + 4 * 24 * 32 * 80 * keys


def test_peak_table():
    v5e = peaks.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")


def test_param_count():
    assert 4 * flops.param_count(H2O) == 7_324_805_120
    assert 4 * flops.param_count(YI) == 7_633_911_808
    assert flops.param_count(dict(H2O, tie_embeddings=True)) == flops.param_count(H2O) - 32_000 * 2560


@pytest.mark.parametrize("model,per_position", [(H2O, 61_440), (YI, 16_384)])
def test_decode_step_bytes(model, per_position):
    # two rows at positions 99 and 1999: the weights once, less the
    # embedding table but for the two rows' entries, and 99 + 1999 cached
    # positions of keys and values (each row's own is computed)
    d, V = model["d_model"], model["vocab_size"]
    weights = flops.param_count(model) - V * d + 2 * d
    got = flops.decode_step_bytes(model, [99, 1999], 4)
    assert got == 4 * weights + (99 + 1999) * per_position
    # bfloat16 weights halve the weights' part only
    assert flops.decode_step_bytes(model, [99, 1999], 2) == got - 2 * weights


def test_decode_step_bytes_in_the_window():
    # h2o's 4096-token window: a row at position 5000 reads 4095 cached positions
    assert flops.decode_step_bytes(H2O, [5000], 0) == 4095 * 61_440
