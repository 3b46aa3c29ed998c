"""Device trace: milliseconds per optimizer step in the expert layers, forward
and backward: every operation with ``moe`` in its scope path (route, dispatch,
experts, combine) and XLA's grouped-matmul custom calls, which carry the
compiler's own name (``benchmark/costs_lfm2.py``)."""

from benchmark import costs_lfm2
from benchmark.trace import scopes


def read(obs, trace):
    ms = scopes.picked_ms(obs, trace, costs_lfm2.in_moe)
    return ms if ms else None
