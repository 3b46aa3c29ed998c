"""Device trace: milliseconds per optimizer step in operations with
``attention`` in their scope path, forward and backward: the attention
dispatch of models/vit.py, projections outside it."""

from benchmark.trace import scopes


def read(obs, trace):
    return scopes.scope_ms(obs, trace, "attention")
