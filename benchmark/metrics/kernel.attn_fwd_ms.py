"""Device trace: milliseconds per optimizer step in dense attention's forward
Mosaic call (ops/fused_attention_small.py), found by its scope
``kernel/attn_small_fwd`` inside ``attention``; a program whose attention runs
in XLA has no such scope and the metric is left out."""

from benchmark.trace import scopes


def read(obs, trace):
    return scopes.scope_ms(obs, trace, "kernel/attn_small_fwd")
