"""Operations, from shapes, of what the ``nemotron_h`` configurations add to
the program, under the conventions of ``benchmark/costs_lfm2.py`` and
``benchmark/costs_ssd.py``: nothing here reads the program, the peaks are
``benchmark/flops.py``'s, causal work is HALF of a chunk's Q x Q whatever an
implementation masks or pads, and recomputed work never counts. The model's
keys are the source's (``hybrid_override_pattern``, ``mamba_num_heads``, ...),
``n_routed_experts`` the experts HELD here and ``n_routed_experts_published``
the router's width.
"""

from __future__ import annotations

from benchmark import costs_ssd


def moe_layers(model: dict) -> int:
    """The ``E`` letters of the pattern."""
    return model["hybrid_override_pattern"].count("E")


def expert_pair_flops(model: dict) -> int:
    """Matmul FLOPs of one routed (token, expert) pair through a latent
    squared-ReLU expert, forward + backward (3 x forward; recomputed work never
    counts): two matmuls of ``moe_latent_size x moe_intermediate_size``."""
    return 3 * 2 * 2 * model["moe_latent_size"] * model["moe_intermediate_size"]


def scan_forward_macs(model: dict) -> int:
    """Multiply-adds of ONE state-space layer's scan, forward, one sequence:
    ``benchmark/costs_ssd.py``'s four products, asked in this source's keys."""
    return costs_ssd.scan_forward_macs({
        "seq_len": model["seq_len"], "mamba_chunk_size": model["chunk_size"],
        "mamba_n_heads": model["mamba_num_heads"], "mamba_d_head": model["mamba_head_dim"],
        "mamba_n_groups": model["n_groups"], "mamba_d_state": model["ssm_state_size"],
    })
