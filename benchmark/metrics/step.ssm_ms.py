"""Device trace: milliseconds per optimizer step in the Mamba-2 state-space
mixers (scope ``mamba``: in_proj, the convolution, the scan, the gated norm,
out_proj), forward, recompute and backward."""

from benchmark.trace import scopes


def read(obs, trace):
    return scopes.scope_ms(obs, trace, "mamba")
