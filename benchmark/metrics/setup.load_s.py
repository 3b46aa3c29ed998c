"""Program spans: seconds in ``load_or_compile`` — each ``.compile(...)``,
served by the persistent cache (``cache_hit`` in the span's args) or by the
compiler."""


def read(obs, trace):
    spans = [e["dur"] for e in obs["spans"] if e["name"] == "load_or_compile"]
    return sum(spans) / 1e6 if spans else None
