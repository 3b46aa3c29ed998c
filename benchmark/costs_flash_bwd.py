"""Operations and bytes, from shapes, of the flash-attention backward kernels
(``flash_attn_bwd_dkv`` + ``flash_attn_bwd_dq``, one scope), under the
conventions of ``benchmark/costs_lfm2.py``: nothing here reads the program,
the peaks are ``benchmark/flops.py``'s, causal work is HALF of S x S whatever
the kernels skip or pad, and recomputed work never counts.
"""

from __future__ import annotations


def flash_bwd_cost(model: dict, batch: int) -> dict:
    """One train step's backward of causal flash attention on ``batch``
    sequences of one chip, one an attention layer. Operations: FIVE matmuls
    of ``2 x head_dim`` FLOPs a (query, key) pair — the recomputed scores,
    dP = dOut vT, dV = pT dOut, dQ = dS k, dK = dST q; the second recompute
    of scores and dP that a dk/dv + dq pair of kernels pays is the kernels'
    own cost, not the algorithm's. Bytes (2-byte activations): q, the
    output, dOut and dq once a QUERY head, k, v, dk and dv once a KEY-VALUE
    head, the float32 logsumexp and delta a row."""
    s, d = model["seq_len"], model["hidden_size"]
    h, hkv = model["num_attention_heads"], model["num_key_value_heads"]
    dh = d // h
    layers = sum(kind == "full_attention" for kind in model["layer_types"])
    return {
        "ops": layers * batch * h * 5 * 2 * dh * (s * s // 2),
        "bytes": layers * batch * (4 * h * s * dh * 2 + 4 * hkv * s * dh * 2 + 2 * h * s * 4),
    }
