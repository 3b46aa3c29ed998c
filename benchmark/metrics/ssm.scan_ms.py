"""Device trace: milliseconds per optimizer step in the state-space scan
(scope ``mamba/scan``: the softplus, decays, cumulative sums, the four chunk
products, the recurrence over chunk states and the ``D x`` term), forward,
recompute and backward."""

from benchmark.trace import scopes


def read(obs, trace):
    return scopes.scope_ms(obs, trace, "mamba/scan")
