"""Device trace: milliseconds per optimizer step in the gated-delta-rule
linear-attention mixers (scope ``linear_attn``: the q, k, v, g, a, b and out
projections, the convolutions, the rule, the gated norm), forward, recompute
and backward."""

from benchmark.trace import scopes


def read(obs, trace):
    return scopes.scope_ms(obs, trace, "linear_attn")
