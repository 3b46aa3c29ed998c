"""The flash-attention backward's cost and its two readers (PR 28): by hand
at the token cell's shapes, on a hand-made observation, and on the recorded
resnet18 trace, where there is nothing to read and they must say so (the
parent commit runs these readers too)."""

import json
import os

import pytest

from benchmark import costs_flash_bwd, flops
from benchmark.metrics import load_reader
from benchmark.trace import xplane

HERE = os.path.dirname(__file__)
ROOT = os.path.dirname(os.path.dirname(HERE))
READERS = ["kernel.flash_bwd_ms", "kernel.flash_bwd_roofline"]


def _model():
    with open(os.path.join(ROOT, "benchmark", "configs", "lfm2-24b-a2b-ep8.json")) as f:
        return json.load(f)["model"]


def test_flash_bwd_cost_by_hand():
    cost = costs_flash_bwd.flash_bwd_cost(_model(), 2)
    # batch x query heads x five causal matmuls of 2 x 64 FLOPs a pair
    assert cost["ops"] == 2 * 32 * 5 * (2 * 64 * 8192 * 8192 // 2)
    rows = 8192 * 64 * 2  # one head's [S, Dh] in bf16
    assert cost["bytes"] == 2 * (4 * 32 * rows + 4 * 8 * rows + 2 * 32 * 8192 * 4)
    least, binds = flops.roofline_seconds(cost, "TPU v5 lite")
    assert binds == "ops" and least * 1e3 == pytest.approx(6.98, abs=0.005)  # ISSUE 28's 7.0 ms
    assert cost["bytes"] / 819e9 * 1e3 < 0.5


def test_the_roofline_divides_the_least_time_by_the_measured(monkeypatch):
    from benchmark.trace import scopes

    obs = {"model": _model(), "global_batch": 2, "chips": 1, "device_kind": "TPU v5 lite"}
    seen = []
    monkeypatch.setattr(scopes, "scope_ms", lambda obs, trace, scope: seen.append(scope) or 50.0)
    assert load_reader("kernel.flash_bwd_ms")(obs, None) == 50.0
    assert load_reader("kernel.flash_bwd_roofline")(obs, None) == pytest.approx(100 * 6.977 / 50.0, rel=1e-3)
    assert set(seen) == {"kernel/flash_attn_bwd"}
    # an image model's observation has no sequence: the share is left out
    assert load_reader("kernel.flash_bwd_roofline")(dict(obs, model={"image_size": 128}), None) is None


@pytest.mark.parametrize("name", READERS)
def test_a_trace_without_the_scope_reads_as_nothing(name):
    recorded = os.path.join(HERE, "r18_train_hbm.scoped.xplane.pb")
    with open(os.path.join(HERE, "r18_train_hbm.scoped.spans.json")) as f:
        spans = json.load(f)["traceEvents"]
    obs = {
        "xplane": recorded, "spans": spans, "steps_per_program": 19, "steps_per_epoch": 19,
        "model": {"image_size": 128}, "global_batch": 2048, "chips": 1, "warmup_epochs": 1,
        "device_kind": "TPU v5 lite", "epoch_marks": [(0.0, {"kind": "epoch"})] * 3,
    }
    trace = xplane.read(recorded, {e["name"] for e in spans})
    assert load_reader(name)(obs, trace) is None
    assert load_reader(name)(obs, None) is None


def test_both_metrics_are_declared_for_the_token_cell_alone():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        declared = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name, unit in zip(READERS, ("ms", "%")):
        entry = declared[name]
        assert (entry["unit"], entry["layer"], entry["moves"], entry["source"], entry["workloads"]) == (
            unit, "kernels", "img_per_s_chip", "device_trace", ["lfm2_train_hbm_8k"]
        )
