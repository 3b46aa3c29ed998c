"""Device trace: milliseconds per optimizer step in the forward pass — the
scope path holds ``forward`` or ``loss`` (JAX writes ``jvp(forward)`` under
``value_and_grad``) and no ``transpose(``."""

from benchmark.trace import scopes


def read(obs, trace):
    return scopes.phase_ms(obs, trace, "fwd")
