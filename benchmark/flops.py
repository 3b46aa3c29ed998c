"""Operations and bytes from shapes, and the table of peaks.

The yardstick's arithmetic: nothing here reads the program. Model FLOPs are
the multiply-adds the architecture REQUIRES (2 FLOPs each) for one image's
forward pass, counted by ``forward_flops`` beside each plain reference
(``benchmark/reference/<arch>.py``); training counts forward + backward = 3x
forward, recomputed operations never counted (the configurations run without
remat). Elementwise work (norms, activations, softmax, the optimizer) is
left out, as is usual for model-FLOPs utilization: it rides the VPU, not the
MXU whose peak the ratio is taken against.
"""

from __future__ import annotations

import importlib

# Per chip: (peak bf16 FLOP/s, peak HBM bytes/s), keyed by the EXACT
# ``device_kind`` JAX reports. Source: Google Cloud documentation, "TPU v5e"
# system architecture (197 TFLOP/s bf16, 819 GB/s HBM per chip); the kind
# string is what the chip machine reported in PR 21 (PERF.md section 6).
# The benchmark's own copy of mpi_pytorch_tpu/utils/hardware._PEAKS.
PEAKS = {
    "TPU v5 lite": (197e12, 819e9),
}


def peaks(device_kind: str) -> tuple[float, float]:
    """(FLOP/s, bytes/s) of one chip. A kind that is not in the table is an
    error, never a default."""
    if device_kind not in PEAKS:
        raise KeyError(
            f"no peak recorded for device_kind {device_kind!r}; add a row, "
            "with its source, to benchmark/flops.py PEAKS"
        )
    return PEAKS[device_kind]


def train_flops_per_image(reference: str, model: dict) -> int:
    """Forward + backward (= 2x forward: one matmul for the input gradient,
    one for the weight gradient) of one image. The forward count is the
    architecture's own, kept beside its plain reference and found by the
    configuration's ``reference`` name: ``benchmark/reference/<name>.py``
    ``forward_flops(model)``."""
    arch = importlib.import_module("benchmark.reference." + reference)
    return 3 * arch.forward_flops(model)


def stem_kernel_cost(model: dict, batch: int) -> dict:
    """Bytes and operations the fused stem's two Mosaic calls need for one
    train step on ``batch`` images of one chip (bn-affine + relu + 3x3/2
    max-pool forward with the window index; index-unpool backward with the
    two BN reduces). Activations are 2-byte (bf16); y is the stem
    convolution's output [batch, size/2, size/2, 64].

    forward : read y; write pooled and index (a quarter of y each).
    backward: read upstream gradient, index, pooled (a quarter each) and y;
              write dy.
    Operations are elementwise (VPU), a few per element: negligible against
    the MXU peak, so bytes bound both calls — returned all the same so the
    roofline says which side binds."""
    h = model["image_size"] // 2
    elems = batch * h * h * 64
    quarter = elems // 4
    return {
        "fwd": {"bytes": 2 * elems + 2 * 2 * quarter, "ops": 3 * elems + 9 * quarter},
        "bwd": {"bytes": 3 * 2 * quarter + 2 * 2 * elems, "ops": 6 * elems},
    }


def roofline_seconds(cost: dict, device_kind: str) -> tuple[float, str]:
    """Least seconds the chip could take for ``cost`` and which peak binds."""
    flops, bandwidth = peaks(device_kind)
    by_ops, by_bytes = cost["ops"] / flops, cost["bytes"] / bandwidth
    return (by_ops, "ops") if by_ops > by_bytes else (by_bytes, "bytes")
