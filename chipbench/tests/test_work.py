"""Operation and byte counts against the hand numbers, and the peaks
table."""

import json
from pathlib import Path

import pytest

from chipbench import peaks, work

ROOT = Path(__file__).resolve().parents[2]


def _config(name):
    return json.loads((ROOT / "chipbench" / "configs" / name).read_text())


def test_vgg_stage2_ops_per_image():
    cfg = _config("vgg16-s2-int8.json")
    layers = [work.conv3x3_layer(1, cfg["img_h"], cfg["img_w"],
                                 s["in_channels"], s["out_channels"],
                                 s["data_bits"], s["coeff_bits"])
              for s in cfg["layers"]]
    # 2·112·112·128·64·9 + 2·112·112·128·128·9
    assert [w.ops for w in layers] == [1849688064.0, 3699376128.0]
    assert sum(w.ops for w in layers) == pytest.approx(5.55e9, rel=1e-3)
    # int8 in and out: 112·112·(64+128) and 112·112·(128+128) bytes,
    # plus the weights once
    assert layers[0].bytes == 112 * 112 * 192 + 128 * 64 * 9
    assert layers[1].bytes == 112 * 112 * 256 + 128 * 128 * 9


def test_conv_bytes_scale_with_bits():
    w8 = work.conv3x3_layer(4, 8, 8, 16, 16, 8, 8)
    w4 = work.conv3x3_layer(4, 8, 8, 16, 16, 4, 4)
    assert w4.bytes == w8.bytes / 2 and w4.ops == w8.ops


def test_qwen3_moe_dispatch_counts():
    cfg = _config("qwen3-moe-30b-a3b-2L.json")
    args = (cfg["hidden_size"], cfg["num_experts"],
            cfg["num_experts_per_tok"], cfg["moe_intermediate_size"])
    one_token = [work.moe_layer(1, *args, 4, 4) for _ in range(2)]
    # router 2·2048·128 + 8 experts · 3 matrices · 2·2048·768, per layer
    assert sum(w.ops for w in one_token) == 2 * (524288 + 75497472)
    assert sum(w.ops for w in one_token) == pytest.approx(151e6, rel=0.01)
    # 16 blocks of 32 tokens: every expert weight once at 4 bits is
    # 604 MB for the two layers; the float32 router and the 4-bit
    # activations in and out come on top
    dispatch = [work.moe_layer(16 * 32, *args, 4, 4) for _ in range(2)]
    experts = 2 * 128 * 3 * 2048 * 768 * 0.5
    assert experts == pytest.approx(604e6, rel=1e-3)
    router = 2 * 2048 * 128 * 4
    acts = 2 * 2 * 16 * 32 * 2048 * 0.5
    assert sum(w.bytes for w in dispatch) == experts + router + acts


def test_least_time_takes_the_larger_bound():
    w = work.Work(ops=393e12, bytes=819e9 / 2)
    assert w.least_time(393e12, 819e9) == 1.0
    assert work.Work(1.0, 819e9).least_time(393e12, 819e9) == 1.0


def test_peaks_by_device_kind():
    p = peaks.peaks("TPU v5 lite")
    assert p["int8_ops_per_s"] == 393e12
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert peaks.ops_peak("TPU v5 lite", 4) == 393e12
    assert peaks.ops_peak("TPU v5 lite", 8) == 393e12
    with pytest.raises(ValueError, match="16-bit"):
        peaks.ops_peak("TPU v5 lite", 16)


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v6 lite", ""])
def test_unknown_device_kind_raises(kind):
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks(kind)
