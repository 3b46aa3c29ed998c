"""The linear-attention cell's own files (PR 37): the configuration against
the published model, the cell and its traffic as ISSUE 37 states them, the
delta rule's costs and its roofline reader — on hand-made observations, and on
the recorded resnet18 trace, where there is nothing for the new readers to
read and they must say so (the parent commit runs these readers too) — and
the cell rehearsed on the CPU from the new files alone."""

import json
import os

import pytest

from benchmark import costs_gated_delta, tasks
from benchmark.metrics import load_reader
from benchmark.trace import scopes, xplane

HERE = os.path.dirname(__file__)
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "olmohybrid_train_hbm_8k"
NEW_READERS = ["step.linear_attn_ms", "linattn.delta_ms", "linattn.delta_roofline_pct"]


def _load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def _config():
    return _load("benchmark", "configs", "olmo-hybrid-7b-tp5.json")


def _catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        return next(r for r in map(json.loads, f) if r["name"] == "Olmo-Hybrid-7B")


def test_the_configuration_keeps_every_published_width():
    config, bench = _config(), _load("BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == config["name"])
    published = {
        "hidden_size": 3840, "intermediate_size": 11008, "num_attention_heads": 30, "num_key_value_heads": 30,
        "linear_key_head_dim": 96, "linear_value_head_dim": 192, "linear_conv_kernel_dim": 4,
        "linear_allow_neg_eigval": True, "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
        "attention_bias": False, "hidden_act": "silu", "max_position_embeddings": 65536,
        "rope_parameters": {"rope_theta": None},
    }
    for key, value in published.items():
        assert config[key] == value and config["model"][key] == value, key
    cut = {"num_hidden_layers": 4, "linear_num_key_heads": 6, "linear_num_value_heads": 6, "vocab_size": 12544}
    for key, value in cut.items():
        assert config[key] == value and key in entry["reduced"] and key in config["published"], key
    assert entry["reduced"] == config["reduced"] == [
        "num_hidden_layers", "layer_types", "linear_num_key_heads", "linear_num_value_heads", "vocab_size",
    ]
    assert config["layer_types"] == ["linear_attention"] * 3 + ["full_attention"]  # source layers 0-3
    assert config["model"]["seq_len"] == 8192 and config["batch_per_chip"] == 1
    assert config["model"]["linear_chunk_size"] == 64
    assert config["flags"]["remat"] == "blocks" and config["flags"]["attn-impl"] == "flash"
    for key in ("source", "published", "reduced", "changed", "assumed", "deployment"):
        assert config[key], key
    assert config["tolerance"]["why"]


def test_every_number_of_the_catalog_row_is_in_the_file_or_reduced():
    row, config = _catalog_row(), _config()
    entry = next(c for c in _load("BENCHMARK.json")["configs"] if c["name"] == config["name"])
    assert entry["source"] == config["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert key in config, key
        assert config[key] == value or key in entry["reduced"], key


def test_the_cell_and_its_traffic_are_as_the_issue_states_them():
    bench = _load("BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("olmo-hybrid-7b-tp5", "train_tokens_8k_x4", 1)
    traffic = _load("benchmark", "traffic", "train_tokens_8k_x4.json")
    assert traffic["dataset"] == {
        "recipe": "tokens", "sequences": 4, "seq_len": 8192, "doc_len_median": 1024, "doc_len_sigma": 1.0,
        "doc_len_min": 64, "doc_len_max": 8192, "zipf_exponent": 1.1, "eod_id": 0,
    }
    mine = {m["name"] for g in ("end_to_end", "per_layer") for m in bench[g] if CELL in m.get("workloads", [])}
    assert mine == {
        "img_per_s_chip", "step.device_ms", "step.mfu_pct", "step.attn_ms", "step.mlp_ms",
        "kernel.flash_fwd_ms", "kernel.flash_bwd_ms", "kernel.flash_fwd_roofline", "kernel.flash_bwd_roofline",
        # the trainer's phase, idle and boundary readers, as every token cell has them
        "device.idle_pct", "step.input_ms", "step.fwd_ms", "step.bwd_ms", "step.opt_ms", "epoch.boundary_ms",
        *NEW_READERS,
    }
    for name in NEW_READERS:
        metric = next(m for m in bench["per_layer"] if m["name"] == name)
        assert metric["workloads"] == [CELL] and metric["moves"] == "img_per_s_chip"
    task = tasks.load(_config())
    assert task.train_samples(traffic["dataset"]) == 4
    assert tasks.check_sizes(task, _config()) == {"forward_samples": 1, "train_samples": 1}


def test_the_rules_cost_by_hand():
    model = _config()["model"]
    s, h = 8192, 6
    assert costs_gated_delta.linear_layers(model) == 3
    # K K^T and Q K^T over the causal half of a 64-wide chunk; the inverse,
    # 64^3/6 a chunk; W and U over the triangle; the chunk's writes read out;
    # the entering state read out, corrected and updated.
    macs = costs_gated_delta.delta_forward_macs(model)
    assert macs == (2 * h * s * 32 * 96 + h * 128 * 43690 + h * s * 32 * 288 + h * s * 32 * 192
                    + 3 * h * s * 96 * 192)
    cost = costs_gated_delta.delta_cost(model, 1)
    assert cost["ops"] == 3 * 3 * 2 * macs
    # bf16 q, k, v and o, float32 beta and g: read, written, read again with o's cotangent
    assert cost["bytes"] == 3 * s * h * (3 * (2 * 384 + 8) + 2 * 2 * 192)
    assert costs_gated_delta.delta_cost(model, 2) == {k: 2 * v for k, v in cost.items()}
    # the flash rooflines' cost files read this configuration's keys as they stand: head size 128, 30 / 30 heads
    from benchmark import costs_flash_bwd, costs_lfm2

    fwd = costs_lfm2.flash_fwd_cost(model, 1)
    assert fwd["ops"] == 30 * 2 * 2 * 128 * (s * s // 2)
    assert costs_flash_bwd.flash_bwd_cost(model, 1)["ops"] == 30 * 5 * 2 * 128 * (s * s // 2)


def _obs(**more):
    return {
        "epoch_marks": [(0.0, {"kind": "epoch"})] * 3, "warmup_epochs": 1, "steps_per_epoch": 4,
        "steps_per_program": 4, "model": _config()["model"], "global_batch": 1, "chips": 1,
        "device_kind": "TPU v5 lite", "xplane": None, **more,
    }


def test_the_rules_roofline_divides_the_least_time_by_the_measured(monkeypatch):
    monkeypatch.setattr(scopes, "scope_ms", lambda obs, trace, scope: {"linear_attn/delta": 100.0}.get(scope))
    cost = costs_gated_delta.delta_cost(_config()["model"], 1)
    least_ms = max(cost["ops"] / 197e12, cost["bytes"] / 819e9) * 1e3  # bytes bind: 0.56 ms
    assert cost["bytes"] / 819e9 > cost["ops"] / 197e12
    assert load_reader("linattn.delta_roofline_pct")(_obs(), None) == pytest.approx(least_ms, rel=1e-9)
    assert load_reader("linattn.delta_ms")(_obs(), None) == 100.0
    assert load_reader("step.linear_attn_ms")(_obs(), None) is None  # this fake knows one scope
    # a model without linear-attention layers has no such cost
    assert load_reader("linattn.delta_roofline_pct")(_obs(model={"seq_len": 8192}), None) is None


def test_the_scopes_nest_as_the_readers_expect():
    path = "jit(epoch_fn)/while/body/transpose(jvp(forward))/layer1/linear_attn/linear_attn/linear_attn/delta/dot_general"
    assert scopes.holds(path, "linear_attn") and scopes.holds(path, "linear_attn/delta")
    assert not scopes.holds(path, "attention") and not scopes.holds(path, "mlp")
    full = "jit(epoch_fn)/while/body/forward/layer3/attn/attention/kernel/flash_attn_fwd/pallas_call"
    assert scopes.holds(full, "attention") and not scopes.holds(full, "linear_attn")


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_trace_without_the_new_scopes_reads_as_nothing(name):
    """The recorded resnet18 trace holds no linear-attention mixer: every new
    reader returns None and none raises (what the parent commit gives under
    these files)."""
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
    """``run.py --workload olmohybrid_train_hbm_8k --trace 1 --rehearse``: the
    harness's own flow at the configuration's tiny preset, the flash kernels
    interpreted, the per-position reference beside the chunked rule; a CPU
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
    shapes = [e["args"] for e in spans if e["name"] == "linear_attn/dispatch"]
    assert len(shapes) == len({tuple(sorted(a.items())) for a in shapes}) >= 2  # one a distinct shape
    assert all(a["path"] == "xla_chunked" and a["chunk"] == 16 for a in shapes)
