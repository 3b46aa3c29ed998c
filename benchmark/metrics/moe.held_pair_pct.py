"""Program counters: the share of the routed (token, expert) pairs that land
on the experts held here — ``moe_pairs_held`` / (``moe_pairs_held`` +
``moe_pairs_absent``), both summed over the window's epoch records, in
percent. Under uniform routing it reads held / routed (8 of 512: 1.5625); a
router that collapses onto the held experts shows here first, so this is the
cell's steadiness witness (the first and the last epoch's shares are printed
beside it). A program whose records carry no counters reads nothing."""

from benchmark import costs_lfm2


def read(obs, trace):
    epochs = costs_lfm2.window_epochs(obs)
    share = lambda recs: 100.0 * sum(r["moe_pairs_held"] for r in recs) / sum(
        r["moe_pairs_held"] + r["moe_pairs_absent"] for r in recs
    )
    if not epochs:
        return None
    print(f"benchmark: held pair share, first / last epoch of the window: "
          f"{share(epochs[:1]):.4f} / {share(epochs[-1:]):.4f} %", flush=True)
    return share(epochs)
