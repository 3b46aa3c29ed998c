"""Share of its roofline the stem kernel pair reaches: the least time the
chip could take for the two calls' bytes and operations (benchmark/flops.py,
from the call's shapes; bytes bound both) / ``kernel.stem_ms``."""

from benchmark import flops
from benchmark.metrics import load_reader


def read(obs, trace):
    measured_ms = load_reader("kernel.stem_ms")(obs, trace)
    if measured_ms is None:
        return None
    cost = flops.stem_kernel_cost(obs["model"], obs["global_batch"] // obs["chips"])
    least = sum(flops.roofline_seconds(c, obs["device_kind"])[0] for c in cost.values())
    return 100.0 * least * 1e3 / measured_ms
