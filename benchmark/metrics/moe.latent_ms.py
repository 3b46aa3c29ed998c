"""Device trace: milliseconds per optimizer step in the two latent projections
of the expert layers (scope ``moe/latent``: hidden -> latent before the
dispatch, latent -> hidden after the combine), forward, recompute and
backward; a program without the scope (no latent experts) reads nothing."""

from benchmark.trace import scopes


def read(obs, trace):
    return scopes.scope_ms(obs, trace, "moe/latent")
