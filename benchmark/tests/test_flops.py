"""``flops.py`` against counts made by hand."""

import json
import os

from benchmark import flops

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs")


def test_one_gpt2_medium_layer_by_hand():
    s, d, h, ff = 1024, 1024, 16, 4096
    qkv = 2 * s * d * (3 * d)
    out = 2 * s * d * d
    mlp = 2 * (2 * s * d * ff)
    # Two attention matmuls per head over the s(s+1)/2 visible pairs.
    attention = h * 2 * (2 * (s * (s + 1) // 2) * (d // h))
    assert flops.gpt2_layer_forward_ops(s, d, h, ff) == \
        qkv + out + mlp + attention
    assert attention == 2 * 2 * 524800 * 1024
    assert qkv + out + mlp == 2 * 1024 * 12 * 1024 * 1024


def test_gpt2_medium_step_per_token():
    sizes = dict(vocab=50257, d_model=1024, n_head=16, d_ff=4096, n_layer=24)
    at_1024 = flops.gpt2_step_ops(4, 1024, **sizes) / 4096
    at_4096 = flops.gpt2_step_ops(1, 4096, **sizes) / 4096
    # 6 x 353.4M matmul parameters + 6 * S * d * L of causal attention.
    assert abs(at_1024 - 2.272e9) < 2e6
    assert abs(at_4096 - 2.725e9) < 2e6


def test_flash_kernel_work_by_hand():
    work = flops.flash_kernel_work(1, 4096, 16, 64)
    per_matmul = 16 * 2 * (4096 * 4097 // 2) * 64
    assert work["fwd"][0] == 2 * per_matmul
    assert work["dkv"][0] == 4 * per_matmul
    assert work["dq"][0] == 3 * per_matmul
    panel, row = 16 * 4096 * 64 * 2, 16 * 4096 * 4
    assert work["fwd"][1] == 4 * panel + row
    assert work["dkv"][1] == 6 * panel + 2 * row


def test_resnet50_first_stage_by_hand():
    convs = flops.resnet_convs([3, 4, 6, 3], 64, 224, 3, 4)
    assert len(convs) == 1 + 16 * 3 + 4
    assert convs[0] == ("conv_init", 7, 3, 64, 112)
    stage1 = [c for c in convs if c[0].startswith("stage1.")]
    macs = sum(flops.conv_forward_ops(*c[1:]) for c in stage1) // 2
    # 56 x 56 positions. Block 0: 64->64, 3x3 64->64, 64->256, projection
    # 64->256; blocks 1 and 2: 256->64, 3x3 64->64, 64->256.
    by_hand = 56 * 56 * ((64 * 64 + 9 * 64 * 64 + 64 * 256 + 64 * 256)
                         + 2 * (256 * 64 + 9 * 64 * 64 + 64 * 256))
    assert macs == by_hand
    # v1.5's stride-2 3x3 runs at the reduced size.
    assert [c for c in convs if c[0] == "stage2.block0.conv3x3"] == \
        [("stage2.block0.conv3x3", 3, 128, 128, 28)]


def test_resnet50_step_counts_a_mac_as_two():
    with open(os.path.join(CONFIGS, "resnet50.json")) as f:
        cfg = json.load(f)
    per_image = flops.resnet_step_ops(
        1, stage_sizes=cfg["stage_sizes"], num_filters=cfg["num_filters"],
        image_size=cfg["image_size"], channels=cfg["image_channels"],
        expansion=cfg["bottleneck_expansion"],
        num_classes=cfg["num_classes"])
    # 4.09 GMAC forward (v1.5), two operations a MAC, three passes, less
    # the first convolution's unneeded input gradient: XLA's
    # cost_analysis() of the whole step says 2.409e10 an image.
    assert 2.40e10 < per_image < 2.47e10
    assert per_image > 1.9 * 3 * 4.09e9


def test_roofline_names_the_binding_roof():
    peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    assert flops.roofline_seconds(197e12, 1, peak) == (1.0, "compute")
    assert flops.roofline_seconds(1, 819e9, peak) == (1.0, "memory")
