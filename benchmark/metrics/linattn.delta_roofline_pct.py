"""Share of its roofline the gated delta rule reaches: the least time the chip
could take for the rules' REQUIRED operations and bytes
(``benchmark/costs_gated_delta.py``, from shapes: forward + backward = 3 x
forward, recompute never counted, the causal half of a chunk's Q x Q, the
triangular inverse once a chunk) / ``linattn.delta_ms``, which holds the
recompute too."""

from benchmark import costs_gated_delta, flops
from benchmark.metrics import load_reader


def read(obs, trace):
    measured_ms = load_reader("linattn.delta_ms")(obs, trace)
    if measured_ms is None or "linear_num_key_heads" not in obs["model"]:
        return None
    cost = costs_gated_delta.delta_cost(obs["model"], obs["global_batch"] // obs["chips"])
    least, _ = flops.roofline_seconds(cost, obs["device_kind"])
    return 100.0 * least * 1e3 / measured_ms
