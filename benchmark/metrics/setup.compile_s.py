"""Seconds the trainer spent compiling (or loading from the cache) its train
program: the ``kind="compile"`` records."""


def read(obs, trace):
    seconds = [r["seconds"] for r in obs["records"] if r["kind"] == "compile"]
    return sum(seconds) if seconds else None
