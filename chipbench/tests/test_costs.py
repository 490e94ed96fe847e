"""The FLOP and byte functions against hand counts, and the peaks table."""

import pytest

from chipbench import costs
from chipbench.reference import resnet50, se_resnext50

CONV = dict(cin=64, cout=64, k=3, stride=1, groups=1, h_out=56, w_out=56,
            first=False)


def test_one_convolution_by_hand():
    # 32 images, 56x56 outputs, 64 filters of 64x3x3: 2 ops a multiply-add
    assert costs.conv_flops(CONV, 32) == 2 * 32 * 56 * 56 * 64 * 64 * 9
    assert costs.conv_flops(CONV, 32) == 7398752256
    # input 32x56x56x64, filter 64x64x3x3, output 32x56x56x64, bf16
    by_hand = (32 * 56 * 56 * 64 + 64 * 64 * 9 + 32 * 56 * 56 * 64) * 2
    assert costs.conv_bytes(CONV, 32) == by_hand == 25763840
    # the compiler's own count for this very convolution, read from the
    # recorded v5e trace: 25763840 bytes, 7249330176 flops (it leaves out
    # the padded border)


def test_grouped_convolution_and_passes():
    g = dict(CONV, cin=128, cout=128, groups=32)
    assert costs.conv_flops(g, 1) == 2 * 56 * 56 * 128 * 4 * 9
    assert costs.conv_passes(CONV, train=False) == 1
    assert costs.conv_passes(CONV, train=True) == 3
    assert costs.conv_passes(dict(CONV, first=True), train=True) == 2


def test_least_time_says_which_bound_binds():
    peaks = costs.peaks_for("TPU v5 lite")
    t, tf, tb = costs.step_least_seconds([CONV], 128, False, peaks)
    assert tf == pytest.approx(costs.conv_flops(CONV, 128) / 197e12)
    assert tb == pytest.approx(costs.conv_bytes(CONV, 128) / 819e9)
    assert t == max(tf, tb) and tf > tb         # a 3x3 conv is FLOP-bound
    one = dict(CONV, k=1, cin=64, cout=64)
    t1, tf1, tb1 = costs.step_least_seconds([one], 128, False, peaks)
    assert tb1 > tf1 and t1 == tb1              # a thin 1x1 is byte-bound


def test_model_flops_match_the_published_counts():
    import json
    import os

    here = os.path.join(os.path.dirname(__file__), "..", "configs")
    r = json.load(open(os.path.join(here, "resnet50.json")))
    s = json.load(open(os.path.join(here, "se_resnext50.json")))
    fwd_r = costs.step_flops(resnet50.layer_plan(r), 1, train=False)
    fwd_s = costs.step_flops(se_resnext50.layer_plan(s), 1, train=False)
    # He et al. Table 1: 3.8e9 multiply-adds; Hu et al. Table 1: 4.25e9
    # (this ResNet-50 runs its stages at 55 px, not 56: slightly under)
    assert fwd_r / 2 == pytest.approx(3.8e9, rel=0.06)
    assert fwd_s / 2 == pytest.approx(4.25e9, rel=0.03)
    assert costs.step_flops(resnet50.layer_plan(r), 1, True) \
        == pytest.approx(3 * fwd_r, rel=0.03)


def test_an_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        costs.peaks_for("TPU v9 imaginary")
    p = costs.peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
