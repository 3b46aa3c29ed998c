"""The token cell's own files (PR 27): the ``tokens`` task and recipe, the
LFM2 reference's FLOPs against a hand count, the kernel costs, and the new
metric readers — on hand-made observations, and on the recorded resnet18
trace, where there is nothing for them to read and they must say so (the
parent commit runs these readers too)."""

import json
import os

import numpy as np
import pytest

from benchmark import costs_lfm2, tasks
from benchmark.metrics import load_reader
from benchmark.reference import lfm2_moe
from benchmark.trace import xplane

HERE = os.path.dirname(__file__)
ROOT = os.path.dirname(os.path.dirname(HERE))
NEW_READERS = [
    "step.moe_ms", "moe.dispatch_ms", "moe.experts_roofline_pct", "moe.load_max_over_mean",
    "step.conv_ms", "kernel.flash_fwd_ms", "kernel.flash_fwd_roofline",
]


def _config():
    with open(os.path.join(ROOT, "benchmark", "configs", "lfm2-24b-a2b-ep8.json")) as f:
        return json.load(f)


def test_the_configuration_keeps_every_published_width():
    config = _config()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == config["name"])
    published = {
        "conv_L_cache": 3, "hidden_size": 2048, "intermediate_size": 11776,
        "moe_intermediate_size": 1536, "num_attention_heads": 32, "num_key_value_heads": 8,
        "num_experts_per_tok": 4, "norm_eps": 1e-5, "routed_scaling_factor": 1,
        "max_position_embeddings": 128000,
    }
    for key, value in published.items():
        assert config[key] == value and config["model"][key] == value, key
    assert config["model"]["num_experts_routed"] == 64  # the router's width, as published
    cut = {"num_hidden_layers": 5, "num_dense_layers": 1, "num_experts": 8, "vocab_size": 8192}
    for key, value in cut.items():
        assert config[key] == value and key in entry["reduced"] and key in config["published"], key
    assert entry["reduced"] == config["reduced"]
    assert config["layer_types"] == ["conv", "full_attention", "conv", "conv", "conv"]


def test_the_tokens_task_answers_the_harness(tmp_path):
    import jax
    from jax.sharding import Mesh

    config = _config()
    task = tasks.load(config)
    model = dict(config["model"], seq_len=32, vocab_size=64)
    flags = task.model_flags(model)
    assert list(flags) == ["model-config"] and json.loads(flags["model-config"]) == model
    recipe = {"recipe": "tokens", "sequences": 4, "seq_len": 32, "doc_len_median": 8,
              "doc_len_sigma": 1.0, "doc_len_min": 2, "doc_len_max": 32,
              "zipf_exponent": 1.1, "eod_id": 0}
    assert task.train_samples(recipe) == 4
    got = task.ensure(recipe, model, seed=2147483659, data_root=str(tmp_path))
    assert got["synthetic-data"] is False and got["debug"] is False
    pack = np.load(os.path.join(got["packed-dir"], "train.tokens.npy"))
    assert pack.shape == (4, 33) and pack.dtype == np.int32 and pack.max() < 64
    again = task.ensure(recipe, model, seed=2147483659, data_root=str(tmp_path))
    other = task.ensure(recipe, model, seed=5, data_root=str(tmp_path))
    assert again == got and other["packed-dir"] != got["packed-dir"]
    with pytest.raises(ValueError, match="seq_len"):
        task.ensure(dict(recipe, seq_len=64), model, seed=1, data_root=str(tmp_path))

    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    inputs, targets = task.seeded_batch(model, mesh, jax.random.PRNGKey(3), 2)
    assert inputs.shape == targets.shape == (2, 32) and inputs.dtype == np.int32
    np.testing.assert_array_equal(np.asarray(inputs)[:, 1:], np.asarray(targets)[:, :-1])
    assert int(inputs.max()) < 64
    shapes = task.batch_shapes(model, 2, None, None)
    assert [(s.shape, s.dtype) for s in shapes] == [((2, 32), np.int32)] * 2
    dataset, labels = task.cache_shapes(model, 4, 4, "uint8", None, None)
    assert (dataset.shape, dataset.dtype, labels.shape) == ((4, 33), np.int32, (4,))
    assert tasks.check_sizes(task, config) == {"forward_samples": 1, "train_samples": 1}
    assert task.epoch_samples({"images_per_sec": 16 / 2.9, "time_s": 2.9}) == 16


def test_lfm2_forward_flops_by_hand():
    model = _config()["model"]
    s, d = 8192, 2048
    conv = s * (3 * d * d + d * d + 3 * d)  # in_proj, out_proj, three taps
    attn = s * d * 64 * (2 * 32 + 2 * 8) + 2 * 32 * 64 * (s * s // 2)  # projections; causal half
    dense = 3 * s * d * 11776
    pairs = s * 4 * 8 // 64  # uniform routing: an eighth of the pairs land here
    moe = s * d * 64 + 3 * pairs * d * 1536
    by_hand = 2 * ((conv + dense) + (attn + moe) + 3 * (conv + moe) + s * d * 8192)
    assert lfm2_moe.forward_flops(model) == by_hand
    assert by_hand / s == pytest.approx(405.8e6, rel=1e-3)  # ISSUE 27's 405 MFLOP a token


def test_kernel_costs_by_hand():
    model = _config()["model"]
    cost = costs_lfm2.flash_fwd_cost(model, 2)
    assert cost["ops"] == 2 * 32 * 2 * (2 * 64 * 8192 * 8192 // 2)  # batch x heads x two causal matmuls
    assert cost["bytes"] == 2 * (2 * 32 * 8192 * 64 * 2 + 2 * 8 * 8192 * 64 * 2 + 32 * 8192 * 4)
    assert costs_lfm2.expert_pair_flops(model) == 18 * 2048 * 1536
    assert costs_lfm2.moe_layers(model) == 4


def _obs(records, **more):
    return {
        "epoch_marks": [(float(i), rec) for i, rec in enumerate(records)], "warmup_epochs": 1,
        "steps_per_epoch": 8, "steps_per_program": 8, "model": _config()["model"], "global_batch": 2,
        "chips": 1, "device_kind": "TPU v5 lite", "xplane": None, **more,
    }


def test_load_max_over_mean_reads_the_epoch_records_counters():
    rec = lambda held, load: {"kind": "epoch", "moe_pairs_held": held, "moe_pairs_absent": 0,
                              "moe_load_max": load}
    # 8 steps x 4 expert layers x 8 experts = 256 slots: mean 1 000 a slot.
    obs = _obs([rec(1, 1), rec(256_000, 2_500), rec(256_000, 3_500), rec(256_000, 3_000)])
    assert load_reader("moe.load_max_over_mean")(obs, None) == pytest.approx(3.0)
    assert load_reader("moe.load_max_over_mean")(_obs([{"kind": "epoch"}] * 3), None) is None


def test_rooflines_divide_the_least_time_by_the_measured(monkeypatch):
    from benchmark.trace import scopes

    obs = _obs([{}, {"moe_pairs_held": 8 * 32_768, "moe_pairs_absent": 0, "moe_load_max": 1}])
    monkeypatch.setattr(scopes, "picked_ms", lambda obs, trace, want: 20.0)
    # 32 768 pairs a step x 18 x 2048 x 1536 FLOPs in 20 ms of a 197 TFLOP/s chip.
    want = 100 * 32_768 * 18 * 2048 * 1536 / (0.020 * 197e12)
    assert load_reader("moe.experts_roofline_pct")(obs, None) == pytest.approx(want)
    monkeypatch.setattr(scopes, "scope_ms", lambda obs, trace, scope: 8.0)
    # 0.55 TFLOP of causal attention: 2.79 ms at the MXU's peak (the bytes take 0.21 ms).
    assert load_reader("kernel.flash_fwd_roofline")(obs, None) == pytest.approx(100 * 2.7906 / 8.0, rel=1e-3)


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_trace_without_the_new_scopes_reads_as_nothing(name):
    """The recorded resnet18 trace holds no expert layer, convolution operator
    or flash call, and its records no counters: every new reader returns None
    and none raises (what the parent commit gives under these files)."""
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


def test_in_experts_takes_the_scope_and_the_compilers_own_name():
    path = "jit(epoch_fn)/while/body/jvp(forward)/Lfm2Moe/layer2/moe/moe/checkpoint/moe/experts/mul"
    assert costs_lfm2.in_experts(path)
    assert costs_lfm2.in_experts("ragged-dot-none") and costs_lfm2.in_experts("ragged-dot-metadata")
    assert not costs_lfm2.in_experts(path.replace("moe/experts", "moe/dispatch"))
    assert not costs_lfm2.in_experts(None)


def test_the_cell_rehearses_on_the_cpu_from_new_files_alone():
    """``run.py --workload lfm2_train_hbm_8k --trace 1 --rehearse``: the
    harness's own flow at the configuration's tiny preset, the flash kernel
    interpreted; the counters' metric is found on any backend."""
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="--xla_force_host_platform_device_count=1")
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "lfm2_train_hbm_8k", "--seed",
         "2147483659", "--seconds", "3", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] and line["correct"] and line["failed"] == 0
    assert line["metrics_found"] == [
        "moe.load_max_over_mean", "setup.build_s", "setup.compile_s", "setup.load_s", "setup.lower_s",
    ]
