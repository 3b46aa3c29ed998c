"""Device trace: milliseconds per optimizer step in the fused stem's
backward Mosaic call, found by its scope ``kernel/stem_bwd``."""

from benchmark.trace import scopes


def read(obs, trace):
    return scopes.scope_ms(obs, trace, "kernel/stem_bwd")
