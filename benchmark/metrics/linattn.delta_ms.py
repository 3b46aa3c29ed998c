"""Device trace: milliseconds per optimizer step in the gated delta rule
(scope ``linear_attn/delta``: the L2 norms of q and k, beta, the decays, their
running sums, the chunk's triangular solve and products, the scan over chunk
states), forward, recompute and backward."""

from benchmark.trace import scopes


def read(obs, trace):
    return scopes.scope_ms(obs, trace, "linear_attn/delta")
