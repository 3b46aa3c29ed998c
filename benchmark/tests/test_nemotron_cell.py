"""The latent-expert cell's own files (PR 33): the configuration against the
catalog's published model, the cell and its traffic as ISSUE 33 states them,
the cost file's numbers by hand, the five new readers — on hand-made
observations, and on the recorded resnet18 trace, where there is nothing for
them to read and they must say so (the parent commit runs these readers too)
— and the cell rehearsed on the CPU from the new files alone, both traces."""

import json
import os

import pytest

from benchmark import costs_lfm2, costs_nemotron_h, tasks
from benchmark.metrics import load_reader
from benchmark.trace import scopes, xplane

HERE = os.path.dirname(__file__)
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "nemotron3s_train_hbm_8k"
CONFIG = "nemotron-3-super-120b-a12b-tp8ep64"
NEW_READERS = [
    "moe.latent_ms", "moe.shared_ms", "moe.latent_experts_roofline_pct", "moe.held_pair_pct",
    "moe.held_load_max_over_mean",
]
REDUCED = {
    "num_hidden_layers": (88, 11), "n_routed_experts": (512, 8), "mamba_num_heads": (128, 16),
    "n_groups": (8, 1), "num_attention_heads": (32, 4), "num_key_value_heads": (2, 1),
    "vocab_size": (131072, 16384), "num_nextn_predict_layers": (1, 0),
}


def _load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def _config():
    return _load("benchmark", "configs", CONFIG + ".json")


def test_the_configuration_keeps_every_published_width():
    config, bench = _config(), _load("BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    widths = {
        "hidden_size": 4096, "head_dim": 128, "mamba_head_dim": 64, "ssm_state_size": 128, "expand": 2,
        "conv_kernel": 4, "chunk_size": 128, "moe_latent_size": 1024, "moe_intermediate_size": 2688,
        "moe_shared_expert_intermediate_size": 5376, "intermediate_size": 2688, "num_experts_per_tok": 22,
        "routed_scaling_factor": 5, "n_shared_experts": 1, "n_group": 1, "topk_group": 1,
        "layer_norm_epsilon": 1e-5, "mlp_hidden_act": "relu2", "tie_word_embeddings": False,
        "model_type": "nemotron_h", "mtp_hybrid_override_pattern": "*E",
    }
    for key, value in widths.items():
        assert config[key] == value and config["model"][key] == value, key
        assert key not in entry["reduced"], key
    for key, (published, cut) in REDUCED.items():
        assert config[key] == config["model"][key] == cut and config["published"][key] == published, key
    assert config["hybrid_override_pattern"] == config["published"]["hybrid_override_pattern"][:11] == "MEMEMEM*EME"
    assert entry["reduced"] == config["reduced"] and set(entry["reduced"]) == set(REDUCED) | {"hybrid_override_pattern"}
    assert (config["model"]["n_routed_experts_published"], config["model"]["expert_offset"]) == (512, 0)
    assert config["model"]["seq_len"] == 8192
    assert config["flags"]["remat"] == "blocks" and config["flags"]["attn-impl"] == "flash"
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json" and entry["source"] == config["source"]
    for key in ("source", "published", "reduced", "changed", "assumed", "deployment"):
        assert config[key], key
    assert config["tolerance"]["why"] and "700.9 M" in config["deployment"]


def test_the_cell_and_its_traffic_are_as_the_issue_states_them():
    bench = _load("BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "train_tokens_8k_x8", 1)
    traffic = _load("benchmark", "traffic", "train_tokens_8k_x8.json")
    like = _load("benchmark", "traffic", "train_tokens_8k.json")
    assert traffic["dataset"] == dict(like["dataset"], sequences=8)  # half the sequences
    assert traffic["flags"] == like["flags"] and traffic["driver"] == "train"
    assert (traffic["warmup_epochs"], traffic["trace_epochs"]) == (1, 1)
    assert traffic["rehearse"] == _load("benchmark", "traffic", "train_tokens_8k_x4.json")["rehearse"]
    mine = {m["name"] for g in ("end_to_end", "per_layer") for m in bench[g] if CELL in m.get("workloads", [])}
    assert mine == {
        "img_per_s_chip", "step.device_ms", "step.mfu_pct", "device.idle_pct", "step.input_ms",
        "step.fwd_ms", "step.bwd_ms", "step.opt_ms", "epoch.boundary_ms", "step.attn_ms",
        "kernel.flash_fwd_ms", "kernel.flash_bwd_ms", "step.moe_ms", "moe.dispatch_ms",
        "moe.row_fill_pct", "step.ssm_ms", "ssm.scan_ms", *NEW_READERS,
    }
    for name in NEW_READERS:
        metric = next(m for m in bench["per_layer"] if m["name"] == name)
        assert metric["workloads"] == [CELL] and metric["moves"] == "img_per_s_chip"
        assert metric["layer"] == "expert layer"
    assert len(bench["workloads"]) == 7 and sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    task = tasks.load(_config())
    assert task.train_samples(traffic["dataset"]) == 8
    assert tasks.check_sizes(task, _config()) == {"forward_samples": 1, "train_samples": 1}


def test_the_cost_files_numbers_by_hand():
    model = _config()["model"]
    assert costs_nemotron_h.moe_layers(model) == 5
    # two matmuls of 1 024 x 2 688, 2 FLOPs a multiply-add, forward + backward = 3 x
    assert costs_nemotron_h.expert_pair_flops(model) == 33_030_144
    s = 8192
    assert costs_nemotron_h.scan_forward_macs(model) == s * 64 * 128 + 16 * s * 64 * 64 + 2 * 16 * s * 128 * 64
    short = dict(model, seq_len=64)  # a sequence below the chunk is one chunk
    assert costs_nemotron_h.scan_forward_macs(short) == 64 * 32 * 128 + 16 * 64 * 32 * 64 + 2 * 16 * 64 * 128 * 64


def _obs(records, **more):
    return {
        "epoch_marks": [(0.0, {"kind": "epoch"})] + [(0.0, dict(r, kind="epoch")) for r in records],
        "warmup_epochs": 1, "steps_per_epoch": 4, "steps_per_program": 4, "model": _config()["model"],
        "global_batch": 2, "chips": 1, "device_kind": "TPU v5 lite", "xplane": None, **more,
    }


EPOCHS = [
    # 4 steps x 5 E layers x 360 448 routed pairs = 7 208 960 an epoch
    {"moe_pairs_held": 112_640, "moe_pairs_absent": 7_096_320, "moe_load_max": 1_408, "moe_rows_computed": 225_280},
    {"moe_pairs_held": 225_280, "moe_pairs_absent": 6_983_680, "moe_load_max": 2_816, "moe_rows_computed": 225_280},
]


def test_the_counter_readers_on_hand_made_epoch_records(capsys):
    obs = _obs(EPOCHS)
    # (112 640 + 225 280) of 2 x 7 208 960 routed pairs
    assert load_reader("moe.held_pair_pct")(obs, None) == pytest.approx(100 * 337_920 / 14_417_920)
    assert "first / last epoch of the window: 1.5625 / 3.1250 %" in capsys.readouterr().out
    # the mean count an expert, a layer, a step: 112 640 / (4 x 5 x 8) = 704, twice that in the second epoch
    assert load_reader("moe.held_load_max_over_mean")(obs, None) == pytest.approx(2.0)
    for name in ("moe.held_pair_pct", "moe.held_load_max_over_mean", "moe.latent_experts_roofline_pct"):
        assert load_reader(name)(_obs([]), None) is None  # a program that writes no counters
    # lfm2's records under these readers: a share, yes; this source's pattern, no
    lfm2 = _obs(EPOCHS, model=_load("benchmark", "configs", "lfm2-24b-a2b-ep8.json")["model"])
    assert load_reader("moe.held_load_max_over_mean")(lfm2, None) is None
    assert load_reader("moe.latent_experts_roofline_pct")(lfm2, None) is None


def test_the_latent_experts_roofline_divides_required_flops_by_the_measured_time(monkeypatch):
    asked = []

    def picked_ms(obs, trace, want):
        asked.append(want)
        return 10.0

    monkeypatch.setattr(scopes, "picked_ms", picked_ms)
    got = load_reader("moe.latent_experts_roofline_pct")(_obs(EPOCHS), None)
    pairs_a_step = (112_640 + 225_280) / (2 * 4)
    assert got == pytest.approx(100 * pairs_a_step * 33_030_144 / (10e-3 * 197e12))
    assert asked == [costs_lfm2.in_experts]  # under moe/experts, or XLA's own grouped-matmul calls
    monkeypatch.setattr(scopes, "picked_ms", lambda *a: None)
    assert load_reader("moe.latent_experts_roofline_pct")(_obs(EPOCHS), None) is None


def test_the_scopes_nest_as_the_readers_expect():
    path = "jit(epoch_fn)/while/body/transpose(jvp(forward))/layer1/checkpoint/rematted_computation/moe/moe/moe/latent/dot_general"
    assert scopes.holds(path, "moe") and scopes.holds(path, "moe/latent") and scopes.phase(path) == "bwd"
    assert not scopes.holds(path, "moe/shared") and not scopes.holds(path, "moe/experts")
    assert costs_lfm2.in_moe(path) and not costs_lfm2.in_experts(path)
    shared = "jit(epoch_fn)/while/body/jvp(forward)/layer1/moe/moe/moe/shared/shared/dot_general"
    assert scopes.holds(shared, "moe/shared") and not scopes.holds(shared, "moe/latent")
    # neither is dispatch: moe.dispatch_ms keeps its three parts
    parts = ("moe/route", "moe/dispatch", "moe/combine")
    assert not any(scopes.holds(p, part) for p in (path, shared) for part in parts)


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_trace_without_the_new_scopes_reads_as_nothing(name):
    """The recorded resnet18 trace holds no expert layer and its records no
    counters: every new reader returns None and none raises (what the parent
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


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses_on_the_cpu_from_new_files_alone(trace):
    """``run.py --workload nemotron3s_train_hbm_8k --rehearse``: the harness's
    own flow at the configuration's tiny preset (state 128 and top-22 as the
    reference's constants have them), the flash kernels interpreted, the
    per-position reference and the dense expert loop beside the system; a CPU
    finds the span and counter metrics and none of the device's."""
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="--xla_force_host_platform_device_count=1")
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "2147483659", "--seconds", "3", "--trace", str(trace), "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] and line["correct"] and line["failed"] == 0
    assert line["metrics_found"] == [
        "moe.held_load_max_over_mean", "moe.held_pair_pct", "moe.row_fill_pct", "setup.build_s",
        "setup.compile_s", "setup.load_s", "setup.lower_s",
    ] if trace else ["img_per_s_chip", "setup_s"]
    spans = _load("benchmark", "out", CELL, "spans.json")["traceEvents"]
    shapes = [e["args"] for e in spans if e["name"] == "moe/dispatch"]
    # one a distinct shape (init's dummy sequence and the step's batch are both 128 tokens here)
    assert len(shapes) == len({tuple(sorted(a.items())) for a in shapes}) >= 1
    assert all((a["experts"], a["held"], a["top_k"], a["latent"]) == (32, 8, 22, 32) for a in shapes)
    assert {e["args"]["groups"] for e in spans if e["name"] == "ssm/dispatch"} == {2}
    records = [json.loads(l) for l in open(os.path.join(ROOT, "benchmark", "out", CELL, "metrics.jsonl"))]
    epochs = [r for r in records if r["kind"] == "epoch"]
    assert epochs and all(
        r["moe_pairs_held"] + r["moe_pairs_absent"] == r["tokens"] * 22 * 2 for r in epochs
    )
