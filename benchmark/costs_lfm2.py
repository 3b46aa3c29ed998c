"""Operations and bytes, from shapes, of what the ``lfm2_moe`` configurations
add to the program: the flash-attention forward kernel and the expert
layers' grouped matmuls. Nothing here reads the program; the peaks are
``benchmark/flops.py``'s.

Also what the device trace calls the grouped matmuls. ``jax.lax.ragged_dot``
becomes, on a TPU, two custom calls the compiler names itself —
``ragged-dot-metadata`` (group boundaries to tiles) and ``ragged-dot-none``
(the grouped matmul) — and they carry THAT as their ``op_name``, not the
scope they were written under (``moe/experts``): readers that want the
expert layer's time add them by that name (``RAGGED_DOT``).
"""

from __future__ import annotations

RAGGED_DOT = "ragged-dot"  # the op_name of XLA's grouped-matmul custom calls starts so


def flash_fwd_cost(model: dict, batch: int) -> dict:
    """One train step's calls of the ``flash_attn_fwd`` kernel on ``batch``
    sequences of one chip: one call an attention layer. Causal work is HALF
    of S x S, whatever the kernel skips or pads. Operations: scores and
    weighted values, 2 FLOPs a multiply-add. Bytes (2-byte activations): q and
    the output once a query head, k and v once a KEY-VALUE head (a grouped
    head's k/v are read once at least), the float32 logsumexp a row."""
    s, d = model["seq_len"], model["hidden_size"]
    h, hkv = model["num_attention_heads"], model["num_key_value_heads"]
    dh = d // h
    layers = sum(kind == "full_attention" for kind in model["layer_types"])
    return {
        "ops": layers * batch * h * 2 * 2 * dh * (s * s // 2),
        "bytes": layers * batch * (2 * h * s * dh * 2 + 2 * hkv * s * dh * 2 + h * s * 4),
    }


def expert_pair_flops(model: dict) -> int:
    """Matmul FLOPs of one routed (token, expert) pair through a SwiGLU
    expert, forward + backward (3 x forward; recomputed work never counts):
    three matmuls of ``hidden x moe_intermediate``."""
    return 3 * 3 * 2 * model["hidden_size"] * model["moe_intermediate_size"]


def moe_layers(model: dict) -> int:
    return len(model["layer_types"]) - model["num_dense_layers"]


def window_epochs(obs: dict) -> list[dict]:
    """The ``kind="epoch"`` records after warm-up that carry the expert
    layers' counters; empty for a program that writes none (the parent)."""
    return [
        rec for _, rec in obs["epoch_marks"][obs["warmup_epochs"]:] if "moe_pairs_held" in rec
    ]


def in_moe(path: str | None) -> bool:
    """A device operation of an expert layer: anything under ``moe``, or one
    of XLA's own grouped-matmul calls."""
    from benchmark.trace import scopes

    return scopes.holds(path, "moe") or (path or "").startswith(RAGGED_DOT)


def in_experts(path: str | None) -> bool:
    """A device operation of the expert FFNs: under the scope ``moe/experts``
    or one of XLA's own grouped-matmul calls."""
    from benchmark.trace import scopes

    return scopes.holds(path, "moe/experts") or (path or "").startswith(RAGGED_DOT)
