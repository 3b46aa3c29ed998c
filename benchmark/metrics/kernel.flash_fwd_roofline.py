"""Share of its roofline the flash-attention forward kernel reaches: the
least time the chip could take for the calls' operations and bytes
(``benchmark/costs_lfm2.py``, from shapes; causal work counted as half of
S x S, so the MXU binds at 8k tokens) / ``kernel.flash_fwd_ms``."""

from benchmark import costs_lfm2, flops
from benchmark.metrics import load_reader


def read(obs, trace):
    measured_ms = load_reader("kernel.flash_fwd_ms")(obs, trace)
    if measured_ms is None or "seq_len" not in obs["model"]:
        return None
    cost = costs_lfm2.flash_fwd_cost(obs["model"], obs["global_batch"] // obs["chips"])
    least, _ = flops.roofline_seconds(cost, obs["device_kind"])
    return 100.0 * least * 1e3 / measured_ms
