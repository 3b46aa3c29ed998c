"""Program spans: seconds in ``lower`` — tracing the Python step into
StableHLO, once per ``.lower(...)``, the per-step program lowered only for
its ``cost_analysis()`` included."""


def read(obs, trace):
    spans = [e["dur"] for e in obs["spans"] if e["name"] == "lower"]
    return sum(spans) / 1e6 if spans else None
