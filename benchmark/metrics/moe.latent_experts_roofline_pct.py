"""Share of the MXU's peak the latent experts reach: the FLOPs the pairs
computed here REQUIRE (the epoch records' ``moe_pairs_held`` a step x two
``latent x moe_intermediate`` matmuls, forward + backward, recompute never
counted; ``benchmark/costs_nemotron_h.py``) / (the time of the operations under
``moe/experts`` and of XLA's grouped-matmul calls x the chip's peak), as
``moe.experts_roofline_pct`` does it for SwiGLU experts at the hidden width.
A model without a latent width reads nothing."""

from benchmark import costs_lfm2, costs_nemotron_h, flops
from benchmark.trace import scopes


def read(obs, trace):
    if "moe_latent_size" not in obs["model"]:
        return None
    epochs = costs_lfm2.window_epochs(obs)
    ms = scopes.picked_ms(obs, trace, costs_lfm2.in_experts)
    if not epochs or not ms:
        return None
    pairs = sum(rec["moe_pairs_held"] for rec in epochs) / (len(epochs) * obs["steps_per_epoch"])
    peak, _ = flops.peaks(obs["device_kind"])
    return 100.0 * pairs * costs_nemotron_h.expert_pair_flops(obs["model"]) / (ms / 1e3 * peak)
