"""Seconds in the trainer's ``build`` and ``cache_build`` spans: model and
state, loaders, and the dataset's way into HBM."""


def read(obs, trace):
    spans = [e["dur"] for e in obs["spans"] if e["name"] in ("build", "cache_build")]
    return sum(spans) / 1e6 if spans else None
