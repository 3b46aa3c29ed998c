"""Device trace: milliseconds per optimizer step in the flash-attention
backward's Mosaic calls (ops/flash_attention.py: ``flash_attn_bwd_dkv`` and
``flash_attn_bwd_dq``), found by their one scope ``kernel/flash_attn_bwd``; a
program whose backward is XLA's (the parent of PR 28) has no such scope and
the metric is left out."""

from benchmark.trace import scopes


def read(obs, trace):
    return scopes.scope_ms(obs, trace, "kernel/flash_attn_bwd")
