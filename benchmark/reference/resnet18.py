"""Plain reference: ResNet-18 (arXiv:1512.03385, torchvision layout) forward
and cross-entropy in float32 ``jax.numpy``/``lax``, no kernels, consuming the
system's variable tree (``{"params", "batch_stats"}`` of
``mpi_pytorch_tpu.models.resnet``). Inference mode normalizes with the stored
BatchNorm statistics, train mode with the batch's own (mean and biased
variance over N, H, W), which is what the gradient is taken through.

Follows the published description: 7x7/2 convolution (pad 3), BatchNorm,
ReLU, 3x3/2 max-pool (pad 1); four stages of two BasicBlocks at widths 64,
128, 256, 512, stride 2 and a 1x1 projection shortcut entering stages 2-4;
global average pool; dense head. BatchNorm eps 1e-5 (torch default).
Departures: none in the mathematics — NHWC layout and HWIO kernels are the
system's storage order, not a different function.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference import common
from benchmark.reference.common import cross_entropy, f32  # noqa: F401 (re-export)

BN_EPS = 1e-5


def _conv(x, kernel, stride: int, pad: int):
    return lax.conv_general_dilated(
        x, kernel, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


def _bn(x, p, s, train: bool):
    mean, var = (x.mean(axis=(0, 1, 2)), x.var(axis=(0, 1, 2))) if train else (s["mean"], s["var"])
    return (x - mean) / jnp.sqrt(var + BN_EPS) * p["scale"] + p["bias"]


def _max_pool_3x3_s2(x):
    return lax.reduce_window(
        x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
        [(0, 0), (1, 1), (1, 1), (0, 0)],
    )


def _block(x, p, s, stride: int, train: bool):
    y = jax.nn.relu(_bn(_conv(x, p["conv1"]["kernel"], stride, 1), p["bn1"], s["bn1"], train))
    y = _bn(_conv(y, p["conv2"]["kernel"], 1, 1), p["bn2"], s["bn2"], train)
    if "downsample_conv" in p:
        x = _bn(
            _conv(x, p["downsample_conv"]["kernel"], stride, 0),
            p["downsample_bn"], s["downsample_bn"], train,
        )
    return jax.nn.relu(y + x)


def forward(variables, images, train: bool = False, stage_sizes=(2, 2, 2, 2)):
    """float32 logits [B, classes] for normalized NHWC ``images``."""
    with jax.default_matmul_precision("highest"):
        p, s = f32(variables["params"]), f32(variables["batch_stats"])
        x = _conv(images.astype(jnp.float32), p["conv1"]["kernel"], 2, 3)
        x = _max_pool_3x3_s2(jax.nn.relu(_bn(x, p["bn1"], s["bn1"], train)))
        for stage, n_blocks in enumerate(stage_sizes):
            for block in range(n_blocks):
                name = f"layer{stage + 1}_{block}"
                stride = 2 if stage > 0 and block == 0 else 1
                x = _block(x, p[name], s[name], stride, train)
        x = x.mean(axis=(1, 2))
        return x @ p["head"]["kernel"] + p["head"]["bias"]


def loss_and_grads(variables, images, labels):
    return common.loss_and_grads(forward, variables, images, labels)


def forward_flops(model: dict) -> int:
    """FLOPs (2 per multiply-add) one image's forward pass requires: 7x7/2
    stem, four stages of ``stage_sizes`` BasicBlocks at widths 64..512
    (stride 2 and a 1x1 projection entering stages 2-4), dense head."""

    def conv(h_out: int, k: int, c_in: int, c_out: int) -> int:
        return h_out * h_out * k * k * c_in * c_out

    h = model["image_size"] // 2  # 7x7 stride 2, pad 3
    macs = conv(h, 7, 3, 64)
    h //= 2  # max-pool
    c_in = 64
    for stage, n_blocks in enumerate(model["stage_sizes"]):
        c = 64 * 2**stage
        for block in range(n_blocks):
            if stage > 0 and block == 0:
                h //= 2
                macs += conv(h, 1, c_in, c)  # projection shortcut
            macs += conv(h, 3, c_in, c) + conv(h, 3, c, c)
            c_in = c
    macs += c_in * model["num_classes"]
    return 2 * macs
