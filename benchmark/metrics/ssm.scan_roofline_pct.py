"""Share of its roofline the state-space scan reaches: the least time the chip
could take for the scans' REQUIRED operations and bytes
(``benchmark/costs_ssd.py``, from shapes: forward + backward = 3 x forward,
recompute never counted, the causal half of the two intra-chunk products) /
``ssm.scan_ms``, which holds the recompute too."""

from benchmark import costs_ssd, flops
from benchmark.metrics import load_reader


def read(obs, trace):
    measured_ms = load_reader("ssm.scan_ms")(obs, trace)
    if measured_ms is None or "mamba_n_heads" not in obs["model"]:
        return None
    cost = costs_ssd.scan_cost(obs["model"], obs["global_batch"] // obs["chips"])
    least, _ = flops.roofline_seconds(cost, obs["device_kind"])
    return 100.0 * least * 1e3 / measured_ms
