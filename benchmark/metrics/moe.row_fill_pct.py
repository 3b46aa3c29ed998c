"""Program counters: how full the expert layers' row buffers run — the pairs
computed here (``moe_pairs_held``) over the buffer rows the layers ran over
(``moe_rows_computed``), both summed over the window's epoch records, in
percent. A program that sizes its buffers for the worst case reads the share
of the routed pairs that land here; one whose records lack
``moe_rows_computed`` (the parent of the PR that added it) reads nothing."""

from benchmark import costs_lfm2


def read(obs, trace):
    epochs = [rec for rec in costs_lfm2.window_epochs(obs) if "moe_rows_computed" in rec]
    rows = sum(rec["moe_rows_computed"] for rec in epochs)
    if not rows:
        return None
    return 100.0 * sum(rec["moe_pairs_held"] for rec in epochs) / rows
