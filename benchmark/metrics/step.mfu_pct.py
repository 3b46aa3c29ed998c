"""Model FLOPs utilization of the step: the FLOPs forward and backward
REQUIRE for one chip's share of the batch (benchmark/flops.py and the
configuration's reference, from shapes, no recompute) / (``step.device_ms``
x the chip's peak)."""

from benchmark import flops
from benchmark.metrics import load_reader


def read(obs, trace):
    device_ms = load_reader("step.device_ms")(obs, trace)
    if device_ms is None:
        return None
    per_image = flops.train_flops_per_image(obs["reference"], obs["model"])
    needed = per_image * obs["global_batch"] / obs["chips"]
    peak, _ = flops.peaks(obs["device_kind"])
    return 100.0 * needed / (device_ms / 1e3 * peak)
