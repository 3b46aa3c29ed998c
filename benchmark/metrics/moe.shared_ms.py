"""Device trace: milliseconds per optimizer step in the shared expert every
token visits (scope ``moe/shared``: two matmuls at the hidden width around a
squared ReLU), forward, recompute and backward; a program without the scope
reads nothing."""

from benchmark.trace import scopes


def read(obs, trace):
    return scopes.scope_ms(obs, trace, "moe/shared")
