"""One small reader per metric, found by the metric's name: ``<name>.py``
with ``read(obs, trace) -> float | None`` (None: nothing to read, the harness
leaves the metric out), or ``<name>.json`` ``{"reader": "<other name>"}`` to
report another metric's reading under this name. The names carry dots, so the
files are loaded by path, not imported."""

from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def load_reader(name: str):
    path = os.path.join(HERE, name)
    if not os.path.exists(path + ".py"):
        with open(path + ".json") as f:
            return load_reader(json.load(f)["reader"])
    spec = importlib.util.spec_from_file_location("bench_metric_" + name.replace(".", "_"), path + ".py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read
