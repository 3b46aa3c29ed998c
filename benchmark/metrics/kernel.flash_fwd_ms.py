"""Device trace: milliseconds per optimizer step in the flash-attention
forward Mosaic call (ops/flash_attention.py), found by its scope
``kernel/flash_attn_fwd``; a program without the call has no such scope and
the metric is left out."""

from benchmark.trace import scopes


def read(obs, trace):
    return scopes.scope_ms(obs, trace, "kernel/flash_attn_fwd")
