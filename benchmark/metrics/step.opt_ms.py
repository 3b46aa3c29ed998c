"""Device trace: milliseconds per optimizer step under ``optimizer`` (the
update, the bad-step select), ``grad_sync`` (the gradient collectives where
the step issues them itself) and ``metrics`` (the step's own numbers: the
gradient norm reads every gradient once more, beside the update)."""

from benchmark.trace import scopes


def read(obs, trace):
    return scopes.phase_ms(obs, trace, "opt")
