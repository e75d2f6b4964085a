"""The benchmark's operation and byte counts against hand-worked vgg16
numbers."""
import json
from pathlib import Path

import pytest

from chipbench import flops

with open(Path(__file__).resolve().parents[2] / "chipbench" / "configs"
          / "vgg16.json") as f:
    VGG16 = json.load(f)


def test_vgg16_224_flops_per_image():
    # 2*K*C*9*H*W per conv: conv1 173,408,256; six 3,699,376,128 convs
    # (64-64, 128-128, 256-256 x2, 512-512 at 28 x2); three 1,849,688,064
    # channel-doubling convs; three 924,844,032 convs at 14x14
    want = (173_408_256 + 6 * 3_699_376_128 + 3 * 1_849_688_064
            + 3 * 924_844_032)
    assert want == 30_693_261_312
    assert flops.vgg_flops_per_image(VGG16, 224, 224) == want


def test_vgg16_720p_scales_with_area():
    # every group's H*W is 224's times 921600 / 50176 exactly
    assert flops.vgg_flops_per_image(VGG16, 720, 1280) == 563_753_779_200


def test_vgg16_conv_shapes_at_720p():
    convs = flops.vgg_convs(VGG16, 720, 1280)
    assert len(convs) == 13
    assert convs[0] == (3, 64, 3, 3, 720, 1280)
    assert convs[-1] == (512, 512, 3, 3, 45, 80)


def test_conv_min_bytes_by_hand():
    # conv1 at batch 1: input 3*224*224 + output 64*224*224 + weights
    # 64*3*9, two bytes each
    assert flops.conv_min_bytes(3, 64, 3, 3, 224, 224, 1) == \
        2 * (150_528 + 3_211_264 + 1_728)


def test_roofline_takes_the_larger_bound_per_conv():
    cfg = {"convs": [[512, 512, 3, 3], [3, 64, 3, 3]], "pools_after": []}
    peak_flops, peak_bw = 197e12, 819e9
    deep = max(8 * 2 * 512 * 512 * 9 * 196 / peak_flops,
               2 * (8 * 2 * 512 * 196 + 512 * 512 * 9) / peak_bw)
    first = max(8 * 2 * 64 * 3 * 9 * 196 / peak_flops,
                2 * (8 * 67 * 196 + 64 * 3 * 9) / peak_bw)
    assert deep == 8 * 2 * 512 * 512 * 9 * 196 / peak_flops   # compute
    assert first > 8 * 2 * 64 * 3 * 9 * 196 / peak_flops      # bandwidth
    assert flops.vgg_roofline_s(cfg, 14, 14, 8, peak_flops, peak_bw) == \
        pytest.approx(deep + first, rel=1e-12)
