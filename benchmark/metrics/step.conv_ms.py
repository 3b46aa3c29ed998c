"""Device trace: milliseconds per optimizer step in the gated short
convolutions (scope ``shortconv``: in_proj, the gates, the depthwise taps,
out_proj), forward and backward."""

from benchmark.trace import scopes


def read(obs, trace):
    return scopes.scope_ms(obs, trace, "shortconv")
