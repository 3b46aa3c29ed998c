"""Device trace: milliseconds per optimizer step in the dense feed-forward
(scope ``mlp``: the SwiGLU's three matmuls and its gate), forward, recompute
and backward."""

from benchmark.trace import scopes


def read(obs, trace):
    return scopes.scope_ms(obs, trace, "mlp")
