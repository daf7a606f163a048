"""The FLOP and byte functions against hand-worked values, and the peaks."""

import pytest

from benchmark import costs, peaks


def test_one_inception_convolution_by_hand():
    # inception_3a/3x3: 96 -> 128 channels, 3x3, on 28x28, batch 256
    layer = {"cin": 96, "cout": 128, "kh": 3, "kw": 3,
             "hin": 28, "win": 28, "hout": 28, "wout": 28}
    c = costs.conv_pass_costs(layer, 256)
    macs = 256 * 128 * 28 * 28 * 96 * 9            # 22,196,256,768
    assert c["flops"] == 2 * macs == 44_392_513_536
    assert c["bytes"] == 2 * (256 * 96 * 784 + 128 * 96 * 9
                              + 256 * 128 * 784) == 90_136_576
    floor, bound = peaks.roofline_floor_s(c["flops"], c["bytes"],
                                          peaks.lookup("TPU v5 lite"))
    assert bound == "compute"
    assert floor == pytest.approx(44_392_513_536 / 197e12)


def test_train_passes_skip_the_image_gradient():
    layers = [{"cin": 3, "cout": 64, "kh": 7, "kw": 7, "hin": 224,
               "win": 224, "hout": 112, "wout": 112},
              {"cin": 64, "cout": 64, "kh": 1, "kw": 1, "hin": 56,
               "win": 56, "hout": 56, "wout": 56}]
    f0 = 2 * 64 * 112 * 112 * 3 * 49
    f1 = 2 * 64 * 56 * 56 * 64
    assert costs.train_passes(layers) == [2, 3]
    assert costs.train_flops_per_sample(layers) == 2 * f0 + 3 * f1


def test_one_paged_attention_decode_call_by_hand():
    # two rows holding 100 and 33 tokens, one query each, width 1600,
    # pages of 16: after the call 101 and 34 valid keys -> 7 and 3 pages
    c = costs.paged_attention_costs([100, 33], 1, 1600, 16)
    assert c["flops"] == 4 * (101 + 34) * 1600 == 864_000
    kv = 2 * (7 + 3) * 16 * 1600 * 2
    qo = 2 * 2 * 1 * 1600 * 2
    assert c["bytes"] == kv + qo == 1_036_800
    floor, bound = peaks.roofline_floor_s(c["flops"], c["bytes"],
                                          peaks.lookup("TPU v5 lite"))
    assert bound == "memory" and floor == pytest.approx(1_036_800 / 819e9)


def test_one_paged_attention_prefill_call_by_hand():
    # 200 new tokens on an empty cache: query i sees i + 1 keys
    c = costs.paged_attention_costs([0], 200, 1600, 16)
    assert c["flops"] == 4 * (200 * 201 // 2) * 1600
    assert c["bytes"] == 2 * 13 * 16 * 1600 * 2 + 2 * 200 * 1600 * 2
    # behind a cached prefix of 64 every query sees 64 more
    d = costs.paged_attention_costs([64], 136, 1600, 16)
    assert d["flops"] == 4 * (136 * 64 + 136 * 137 // 2) * 1600


def test_an_unknown_device_is_an_error_not_a_default():
    assert peaks.lookup("TPU v5 lite")["bf16_flops"] == 197e12
    assert peaks.lookup("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.lookup("cpu")
