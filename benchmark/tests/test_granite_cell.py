"""The state-space cell's own files (PR 31): the configuration against the
published model, the cell and its traffic as ISSUE 31 states them, the scan's
costs and its roofline reader — on hand-made observations, and on the recorded
resnet18 trace, where there is nothing for the new readers to read and they
must say so (the parent commit runs these readers too) — and the cell
rehearsed on the CPU from the new files alone."""

import json
import os

import pytest

from benchmark import costs_ssd, tasks
from benchmark.metrics import load_reader
from benchmark.trace import scopes, xplane

HERE = os.path.dirname(__file__)
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "granite4h_train_hbm_8k"
NEW_READERS = ["step.ssm_ms", "ssm.scan_ms", "ssm.scan_roofline_pct", "step.mlp_ms"]


def _load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def _config():
    return _load("benchmark", "configs", "granite-4.0-h-micro-vp8.json")


def test_the_configuration_keeps_every_published_width():
    config, bench = _config(), _load("BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == config["name"])
    published = {
        "hidden_size": 2048, "shared_intermediate_size": 8192, "intermediate_size": 8192,
        "num_attention_heads": 32, "num_key_value_heads": 8, "mamba_n_heads": 64, "mamba_d_head": 64,
        "mamba_d_state": 128, "mamba_n_groups": 1, "mamba_d_conv": 4, "mamba_expand": 2,
        "mamba_chunk_size": 256, "embedding_multiplier": 12, "residual_multiplier": 0.22,
        "attention_multiplier": 0.015625, "logits_scaling": 8, "rms_norm_eps": 1e-5,
        "num_local_experts": 0, "position_embedding_type": "nope", "tie_word_embeddings": True,
        "max_position_embeddings": 131072,
    }
    for key, value in published.items():
        assert config[key] == value and config["model"][key] == value, key
    cut = {"num_hidden_layers": 10, "vocab_size": 12544}
    for key, value in cut.items():
        assert config[key] == value and key in entry["reduced"] and key in config["published"], key
    assert entry["reduced"] == config["reduced"] == ["num_hidden_layers", "layer_types", "vocab_size"]
    assert config["layer_types"] == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4  # source layers 0-9
    assert config["model"]["seq_len"] == 8192 and config["batch_per_chip"] == 1
    assert config["flags"]["remat"] == "blocks" and config["flags"]["attn-impl"] == "flash"
    for key in ("source", "published", "reduced", "changed", "assumed", "deployment"):
        assert config[key], key
    assert config["tolerance"]["why"]


def test_the_cell_and_its_traffic_are_as_the_issue_states_them():
    bench = _load("BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("granite-4.0-h-micro-vp8", "train_tokens_8k_x4", 1)
    traffic = _load("benchmark", "traffic", "train_tokens_8k_x4.json")
    like = _load("benchmark", "traffic", "train_tokens_8k.json")
    assert traffic["dataset"] == dict(like["dataset"], sequences=4)  # a quarter of the sequences
    assert traffic["flags"] == like["flags"] and traffic["driver"] == "train"
    assert (traffic["warmup_epochs"], traffic["trace_epochs"]) == (1, 1)
    mine = {m["name"] for g in ("end_to_end", "per_layer") for m in bench[g] if CELL in m.get("workloads", [])}
    assert mine == {
        "img_per_s_chip", "step.device_ms", "step.mfu_pct", "device.idle_pct", "step.input_ms",
        "step.fwd_ms", "step.bwd_ms", "step.opt_ms", "step.attn_ms", "epoch.boundary_ms",
        "kernel.flash_fwd_ms", "kernel.flash_bwd_ms", *NEW_READERS,
    }
    for name in NEW_READERS:
        metric = next(m for m in bench["per_layer"] if m["name"] == name)
        assert metric["workloads"] == [CELL] and metric["moves"] == "img_per_s_chip"
    task = tasks.load(_config())
    assert task.train_samples(traffic["dataset"]) == 4
    assert tasks.check_sizes(task, _config()) == {"forward_samples": 1, "train_samples": 1}


def test_the_scans_cost_by_hand():
    model = _config()["model"]
    s = 8192
    assert costs_ssd.mamba_layers(model) == 9
    # C B^T once a group and (L o C B^T) X once a head over the causal half of
    # a 256-wide chunk; the chunk state and its read-out over all of N x P.
    assert costs_ssd.scan_forward_macs(model) == s * 128 * 128 + 64 * s * 128 * 64 + 2 * 64 * s * 128 * 64
    cost = costs_ssd.scan_cost(model, 1)
    assert cost["ops"] == 9 * 3 * 2 * costs_ssd.scan_forward_macs(model)
    assert cost["bytes"] == 9 * 2 * s * (3 * (4096 + 2 * 128 + 64) + 2 * 4096)
    assert costs_ssd.scan_cost(model, 2) == {k: 2 * v for k, v in cost.items()}
    short = dict(model, seq_len=64)  # a sequence below the chunk is one chunk
    assert costs_ssd.scan_forward_macs(short) == 64 * 32 * 128 + 64 * 64 * 32 * 64 + 2 * 64 * 64 * 128 * 64


def _obs(**more):
    return {
        "epoch_marks": [(0.0, {"kind": "epoch"})] * 3, "warmup_epochs": 1, "steps_per_epoch": 4,
        "steps_per_program": 4, "model": _config()["model"], "global_batch": 1, "chips": 1,
        "device_kind": "TPU v5 lite", "xplane": None, **more,
    }


def test_the_scans_roofline_divides_the_least_time_by_the_measured(monkeypatch):
    monkeypatch.setattr(scopes, "scope_ms", lambda obs, trace, scope: {"mamba/scan": 100.0}.get(scope))
    # 3.16 GB of required bytes: 3.860 ms at 819 GB/s (the 0.70 TFLOP take 3.569 ms).
    assert load_reader("ssm.scan_roofline_pct")(_obs(), None) == pytest.approx(3.860, rel=1e-3)
    assert load_reader("ssm.scan_ms")(_obs(), None) == 100.0
    assert load_reader("step.ssm_ms")(_obs(), None) is None  # this fake knows one scope
    # a model without state-space layers has no such cost
    assert load_reader("ssm.scan_roofline_pct")(_obs(model={"seq_len": 8192}), None) is None


def test_the_scopes_nest_as_the_readers_expect():
    path = "jit(epoch_fn)/while/body/transpose(jvp(forward))/layer3/mamba/mamba/mamba/scan/dot_general"
    assert scopes.holds(path, "mamba") and scopes.holds(path, "mamba/scan")
    assert not scopes.holds(path, "mamba/conv") and not scopes.holds(path, "mlp")
    remat = "jit(epoch_fn)/while/body/transpose(jvp(forward))/layer3/checkpoint/rematted_computation/mlp/mlp/dot_general"
    assert scopes.holds(remat, "mlp") and scopes.phase(remat) == "bwd" and not scopes.holds(remat, "mamba")


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_trace_without_the_new_scopes_reads_as_nothing(name):
    """The recorded resnet18 trace holds no state-space mixer and no ``mlp``
    scope: every new reader returns None and none raises (what the parent
    commit gives under these files)."""
    recorded = os.path.join(HERE, "r18_train_hbm.scoped.xplane.pb")
    spans = _load("benchmark", "tests", "r18_train_hbm.scoped.spans.json")["traceEvents"]
    obs = {
        "xplane": recorded, "spans": spans, "steps_per_program": 19, "steps_per_epoch": 19,
        "model": {"image_size": 128}, "global_batch": 2048, "chips": 1, "warmup_epochs": 1,
        "device_kind": "TPU v5 lite", "epoch_marks": [(0.0, {"kind": "epoch"})] * 3,
    }
    trace = xplane.read(recorded, {e["name"] for e in spans})
    assert load_reader(name)(obs, trace) is None
    assert load_reader(name)(obs, None) is None


def test_the_cell_rehearses_on_the_cpu_from_new_files_alone():
    """``run.py --workload granite4h_train_hbm_8k --trace 1 --rehearse``: the
    harness's own flow at the configuration's tiny preset, the flash kernels
    interpreted, the per-position reference beside the chunked scan; a CPU
    finds the span metrics and none of the device's."""
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="--xla_force_host_platform_device_count=1")
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "2147483659", "--seconds", "3", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] and line["correct"] and line["failed"] == 0
    assert line["metrics_found"] == ["setup.build_s", "setup.compile_s", "setup.load_s", "setup.lower_s"]
    spans = _load("benchmark", "out", CELL, "spans.json")["traceEvents"]
    shapes = [e["args"] for e in spans if e["name"] == "ssm/dispatch"]
    assert len(shapes) == len({tuple(sorted(a.items())) for a in shapes}) >= 2  # one a distinct shape
    assert all(a["path"] == "xla_chunked" and a["chunk"] == 16 for a in shapes)
