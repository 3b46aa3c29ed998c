"""Device trace: milliseconds per optimizer step in the backward pass — the
scope path holds ``transpose(jvp(`` (what JAX derives from the ``forward``
and ``loss`` scopes) or, under ``--remat full``, the recomputed forward."""

from benchmark.trace import scopes


def read(obs, trace):
    return scopes.phase_ms(obs, trace, "bwd")
