"""Share of their roofline the flash-attention backward kernels reach: the
least time the chip could take for the backward's operations and bytes
(``benchmark/costs_flash_bwd.py``, from shapes: five causal matmuls, the
kernels' second recompute not counted, so the MXU binds at 8k tokens) /
``kernel.flash_bwd_ms``."""

from benchmark import costs_flash_bwd, flops
from benchmark.metrics import load_reader


def read(obs, trace):
    measured_ms = load_reader("kernel.flash_bwd_ms")(obs, trace)
    if measured_ms is None or "seq_len" not in obs["model"]:
        return None
    cost = costs_flash_bwd.flash_bwd_cost(obs["model"], obs["global_batch"] // obs["chips"])
    least, _ = flops.roofline_seconds(cost, obs["device_kind"])
    return 100.0 * least * 1e3 / measured_ms
