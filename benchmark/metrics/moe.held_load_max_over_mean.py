"""Program counters: how uneven routing is over the experts held here — the
largest count one expert of one layer took in one step of an epoch
(``moe_load_max``) over the mean count an expert, an ``E`` layer, a step
(``moe_pairs_held`` / (steps x E layers x experts held),
``benchmark/costs_nemotron_h.py``); the median over the window's epochs. 1 is
perfectly even. ``moe.load_max_over_mean`` is the same reading from
``lfm2_moe``'s keys; a model without this source's pattern reads nothing."""

import statistics

from benchmark import costs_lfm2, costs_nemotron_h


def read(obs, trace):
    epochs = costs_lfm2.window_epochs(obs)
    if not epochs or "hybrid_override_pattern" not in obs["model"]:
        return None
    model = obs["model"]
    slots = obs["steps_per_epoch"] * costs_nemotron_h.moe_layers(model) * model["n_routed_experts"]
    return statistics.median(
        rec["moe_load_max"] / (rec["moe_pairs_held"] / slots) for rec in epochs
    )
