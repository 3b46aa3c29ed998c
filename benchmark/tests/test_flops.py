"""benchmark/flops.py against counts made by hand."""

import json
import os

import pytest

from benchmark import flops
from benchmark.reference import resnet18, vit

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(__file__)), "configs")


def _model(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)["model"]


def test_resnet18_forward_by_hand():
    # torchvision resnet18 at 128 px, multiply-adds layer by layer:
    stem = 64 * 64 * 49 * 3 * 64
    stage1 = 4 * (32 * 32 * 9 * 64 * 64)
    stage2 = 16 * 16 * (9 * 64 * 128 + 3 * 9 * 128 * 128 + 64 * 128)
    stage3 = 8 * 8 * (9 * 128 * 256 + 3 * 9 * 256 * 256 + 128 * 256)
    stage4 = 4 * 4 * (9 * 256 * 512 + 3 * 9 * 512 * 512 + 256 * 512)
    head = 512 * 64500
    by_hand = 2 * (stem + stage1 + stage2 + stage3 + stage4 + head)
    got = resnet18.forward_flops(_model("resnet18-herbarium128"))
    assert got == by_hand
    assert got == pytest.approx(1.25e9, rel=0.01)  # ISSUE 22's figure


def test_vit_b16_forward_by_hand():
    s, d, mlp = 196, 768, 3072
    layer = 4 * s * d * d + 2 * s * s * d + 2 * s * d * mlp
    by_hand = 2 * (s * 768 * d + 12 * layer + d * 64500)
    got = vit.forward_flops(_model("vit_b16-herbarium224"))
    assert got == by_hand
    assert got == pytest.approx(35e9, rel=0.01)  # ISSUE 22's figure


def test_training_is_three_forwards():
    model = _model("resnet18-herbarium128")
    assert flops.train_flops_per_image("resnet18", model) == 3 * resnet18.forward_flops(model)


def test_stem_kernel_bytes_by_hand():
    # B=2048 at 128 px: y is [2048, 64, 64, 64] bf16 = 1 073 741 824 bytes.
    cost = flops.stem_kernel_cost({"image_size": 128}, 2048)
    y = 2048 * 64 * 64 * 64 * 2
    assert cost["fwd"]["bytes"] == y + 2 * y // 4  # read y; write pooled, index
    assert cost["bwd"]["bytes"] == 3 * y // 4 + 2 * y  # read g, idx, pooled, y; write dy
    seconds, bound = flops.roofline_seconds(cost["fwd"], "TPU v5 lite")
    assert bound == "bytes"
    assert seconds == pytest.approx(1.61e9 / 819e9, rel=0.01)  # the kernel's 2.0 ms byte bound


def test_unknown_device_kind_is_an_error():
    assert flops.peaks("TPU v5 lite") == (197e12, 819e9)
    with pytest.raises(KeyError, match="TPU v9"):
        flops.peaks("TPU v9")
