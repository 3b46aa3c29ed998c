"""Device trace: milliseconds per optimizer step in the fused stem's forward
Mosaic call, found by its scope ``kernel/stem_fwd`` (``kernel.stem_ms`` tells
the pair by result shape)."""

from benchmark.trace import scopes


def read(obs, trace):
    return scopes.scope_ms(obs, trace, "kernel/stem_fwd")
