"""Program counters: how uneven routing is over the experts held here — the
largest count one expert of one layer took in one step of an epoch
(``moe_load_max``) over the mean count an expert, a layer, a step
(``moe_pairs_held`` / (steps x expert layers x experts held)); the median over
the window's epochs. 1 is perfectly even."""

import statistics

from benchmark import costs_lfm2


def read(obs, trace):
    epochs = costs_lfm2.window_epochs(obs)
    if not epochs:
        return None
    slots = obs["steps_per_epoch"] * costs_lfm2.moe_layers(obs["model"]) * obs["model"]["num_experts"]
    return statistics.median(
        rec["moe_load_max"] / (rec["moe_pairs_held"] / slots) for rec in epochs
    )
