"""Device trace: milliseconds per optimizer step the expert layers spend
AROUND their matmuls: scores and top-k (``moe/route``), the sort of the routed
pairs and the gather of their rows (``moe/dispatch``), the un-sort and the
weighted sum (``moe/combine``), forward and backward."""

from benchmark.trace import scopes

PARTS = ("moe/route", "moe/dispatch", "moe/combine")


def read(obs, trace):
    ms = scopes.picked_ms(obs, trace, lambda path: any(scopes.holds(path, p) for p in PARTS))
    return ms if ms else None
