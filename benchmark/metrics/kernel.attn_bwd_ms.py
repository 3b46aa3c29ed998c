"""Device trace: milliseconds per optimizer step in dense attention's backward
Mosaic call (dq, dk, dv in one pass, probabilities recomputed), found by its
scope ``kernel/attn_small_bwd``; left out where attention runs in XLA."""

from benchmark.trace import scopes


def read(obs, trace):
    return scopes.scope_ms(obs, trace, "kernel/attn_small_bwd")
