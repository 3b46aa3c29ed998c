"""Run-telemetry subsystem (mpi_pytorch_tpu/obs/): span tracer output
format and nesting, per-step health record schema, the NaN-sentinel abort
path, straggler flagging with a faked slow host, the report tool against
both a live dryrun and the committed artifacts, and the grad-norm metric
every train-step flavor now carries."""

import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_pytorch_tpu.obs import (
    Heartbeat,
    NonFiniteLossError,
    StepHealth,
    Tracer,
    flag_stragglers,
)
from mpi_pytorch_tpu.obs.schema import validate_jsonl, validate_record
from mpi_pytorch_tpu.utils.logging import MetricsWriter

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

import report_run  # noqa: E402


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------


def test_tracer_span_nesting_and_chrome_format(tmp_path):
    """Spans emit Chrome 'X' (complete) events whose ts/dur nest correctly,
    args round-trip, and close() writes one valid JSON object."""
    path = str(tmp_path / "trace.json")
    tracer = Tracer(path)
    with tracer.span("outer"):
        with tracer.span("inner", args={"step": 3}):
            pass
    tracer.instant("marker", args={"why": "test"})
    out = tracer.close()
    assert out == path

    data = json.load(open(path))
    events = {e["name"]: e for e in data["traceEvents"]}
    assert set(events) == {"outer", "inner", "marker"}
    outer, inner = events["outer"], events["inner"]
    assert outer["ph"] == inner["ph"] == "X"
    assert events["marker"]["ph"] == "i"
    # inner completes first (events append at span END), and sits inside
    # outer's [ts, ts+dur) window — the property Chrome renders as nesting.
    assert inner["ts"] >= outer["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-6
    assert inner["args"] == {"step": 3}
    assert outer["pid"] == 0  # single-process test env


def test_tracer_disabled_is_inert(tmp_path):
    tracer = Tracer("")
    with tracer.span("anything"):
        pass
    assert tracer.close() is None
    assert list(tmp_path.iterdir()) == []


def test_tracer_close_idempotent_and_creates_dirs(tmp_path):
    path = str(tmp_path / "deep" / "dir" / "t.json")
    tracer = Tracer(path)
    with tracer.span("s"):
        pass
    assert tracer.close() == path
    assert tracer.close() is None  # second close: no rewrite
    assert json.load(open(path))["traceEvents"]


def test_trace_path_per_process_suffix():
    from mpi_pytorch_tpu.obs.trace import trace_path

    assert trace_path("run.json", 0, 1) == "run.json"
    assert trace_path("run.json", 2, 4) == "run.p2.json"
    assert trace_path("run", 1, 2) == "run.p1.json"


def test_tracer_writes_its_origin_down(tmp_path):
    """``otherData`` holds the span clock's origin on ``perf_counter`` and on
    the wall clock, read back to back: what lays the file on any other
    timeline of the run (ISSUE 24 part C)."""
    import time

    before = (time.perf_counter(), time.time_ns())
    tracer = Tracer(str(tmp_path / "t.json"))
    after = (time.perf_counter(), time.time_ns())
    with tracer.span("s"):
        pass
    other = json.load(open(tracer.close()))["otherData"]
    assert before[0] <= other["t0_perf_counter_s"] <= after[0]
    assert before[1] <= other["t0_unix_ns"] <= after[1]


def test_tracer_end_returns_the_duration_it_measured(tmp_path):
    ticks = iter([10.0, 10.5, 12.0])  # origin, begin, end

    tracer = Tracer(str(tmp_path / "t.json"), clock=lambda: next(ticks))
    token = tracer.begin("timed")
    assert tracer.end(token, args={"k": 1}) == pytest.approx(1.5)
    (event,) = json.load(open(tracer.close()))["traceEvents"]
    assert event["ts"] == pytest.approx(0.5e6) and event["dur"] == pytest.approx(1.5e6)
    # Disabled: nothing recorded, the same seconds — one clock pair a region
    # on either path (the trainer's data_wait_ms / step_ms are span lengths).
    ticks = iter([0.0, 1.0, 1.25, 2.0, 2.5])
    off = Tracer("", clock=lambda: next(ticks))
    assert off.end(off.begin("x")) == pytest.approx(0.25)
    with off.span("y") as timed:
        pass
    assert timed.seconds == pytest.approx(0.5) and off._events == []


def test_span_args_are_read_when_the_span_closes(tmp_path):
    tracer = Tracer(str(tmp_path / "t.json"))
    args = {"batch": 0}
    with tracer.span("h2d", args=args):
        args["bytes"] = 123
    (event,) = json.load(open(tracer.close()))["traceEvents"]
    assert event["args"] == {"batch": 0, "bytes": 123}


def test_current_tracer_is_inert_until_a_driver_installs_one(tmp_path):
    from mpi_pytorch_tpu.obs import trace as obs_trace

    assert not obs_trace.current().enabled
    tracer = Tracer(str(tmp_path / "t.json"))
    with obs_trace.use(tracer):
        assert obs_trace.current() is tracer
        with obs_trace.current().span("below"):
            pass
    assert not obs_trace.current().enabled
    assert [e["name"] for e in json.load(open(tracer.close()))["traceEvents"]] == ["below"]


# ---------------------------------------------------------------------------
# per-step health records + NaN sentinel
# ---------------------------------------------------------------------------


def test_step_health_record_matches_schema(tmp_path):
    path = str(tmp_path / "m.jsonl")
    writer = MetricsWriter(path)
    health = StepHealth(writer, step_metrics=True)
    health.start_epoch()
    health.on_step(0, 0, {"loss": 1.5, "grad_norm": 2.25}, 0.012, 0.345)
    writer.close()

    assert validate_jsonl(path) == []
    (rec,) = [json.loads(line) for line in open(path)]
    assert rec["kind"] == "step"
    assert rec["loss"] == 1.5 and rec["grad_norm"] == 2.25
    assert rec["data_wait_ms"] == 12.0 and rec["step_ms"] == 345.0
    assert isinstance(rec["recompiles"], int)
    assert rec["hbm_bytes"] is None  # CPU test env has no memory_stats


def test_step_health_disabled_never_syncs(tmp_path):
    """With step_metrics off, on_step must not touch the metrics values at
    all (reading them would force a per-step device sync in real runs)."""
    writer = MetricsWriter(str(tmp_path / "m.jsonl"))

    class Exploding:
        def __getitem__(self, key):  # pragma: no cover - must not be hit
            raise AssertionError("on_step read a metric while disabled")

        def __contains__(self, key):
            raise AssertionError("on_step probed a metric while disabled")

    health = StepHealth(writer, step_metrics=False)
    health.on_step(0, 0, Exploding(), 0.0, 0.0)  # must be a silent no-op
    writer.close()


def test_nan_sentinel_writes_diagnostic_and_aborts(tmp_path):
    path = str(tmp_path / "m.jsonl")
    writer = MetricsWriter(path)
    health = StepHealth(writer, step_metrics=True)
    with pytest.raises(NonFiniteLossError, match="epoch 1 step 4"):
        health.on_step(1, 4, {"loss": float("nan"), "grad_norm": 7.0}, 0.0, 0.1)
    writer.close()

    records = [json.loads(line) for line in open(path)]
    # The poisoned step record lands first, then the diagnostic.
    assert [r["kind"] for r in records] == ["step", "anomaly"]
    anomaly = records[-1]
    assert anomaly["reason"] == "nonfinite_loss"
    assert (anomaly["epoch"], anomaly["step"]) == (1, 4)
    assert math.isnan(anomaly["loss"]) and anomaly["grad_norm"] == 7.0
    assert validate_jsonl(path) == []


def test_nan_sentinel_epoch_check_and_opt_out(tmp_path):
    writer = MetricsWriter(str(tmp_path / "m.jsonl"))
    health = StepHealth(writer, step_metrics=False)  # default run shape
    health.check_epoch(2, 1.25)  # finite: fine
    with pytest.raises(NonFiniteLossError):
        health.check_epoch(2, float("inf"))
    writer.close()

    off = StepHealth(MetricsWriter(None), step_metrics=False, nan_sentinel=False)
    off.check_epoch(0, float("nan"))  # explicitly disabled: keep going


def test_scan_epoch_records_and_sentinel(tmp_path):
    path = str(tmp_path / "m.jsonl")
    writer = MetricsWriter(path)
    health = StepHealth(writer, step_metrics=True)
    m = {"loss": np.asarray([1.0, 2.0]), "grad_norm": np.asarray([3.0, 4.0])}
    health.on_scan_epoch(0, m)
    poisoned = {"loss": np.asarray([1.0, float("nan")])}
    with pytest.raises(NonFiniteLossError):
        health.on_scan_epoch(1, poisoned)
    writer.close()

    records = [json.loads(line) for line in open(path)]
    steps = [r for r in records if r["kind"] == "step"]
    # 2 clean + 2 poisoned-epoch records (the NaN step IS recorded), 1 anomaly.
    assert len(steps) == 4 and records[-1]["kind"] == "anomaly"
    assert steps[0]["step_ms"] is None  # scan mode: no per-step host timing
    assert steps[1]["grad_norm"] == 4.0
    assert validate_jsonl(path) == []


# ---------------------------------------------------------------------------
# heartbeat / straggler flagging
# ---------------------------------------------------------------------------


def test_flag_stragglers_policy():
    assert flag_stragglers([100.0, 101.0, 99.0, 400.0], 1.5) == [3]
    assert flag_stragglers([100.0, 100.0, 100.0, 100.0], 1.5) == []
    assert flag_stragglers([100.0], 1.5) == []  # one host: no baseline
    # Two slow hosts don't hide each other (median, not mean).
    assert flag_stragglers([100.0, 104.0, 98.0, 101.0, 300.0, 280.0], 1.5) == [4, 5]


def test_heartbeat_flags_faked_slow_host(tmp_path):
    """A 4-host heartbeat with one faked 4x-slower process: the record
    carries per-host rows, the straggler index, and the schema holds."""
    path = str(tmp_path / "m.jsonl")
    writer = MetricsWriter(path)
    calls = []

    def fake_gather(local):  # process 3 is wedged on a slow disk
        calls.append(np.asarray(local))
        return np.asarray([[100.0], [102.0], [98.0], [400.0]], np.float32)

    hb = Heartbeat(
        writer, every_steps=2, threshold=1.5, batch_images=128,
        gather=fake_gather,
    )
    hb.on_step(0, 0, 0.1)
    assert calls == []  # not at the beat boundary yet
    hb.on_step(0, 1, 0.1)
    assert len(calls) == 1
    np.testing.assert_allclose(calls[0], [100.0])  # local mean, ms
    writer.close()

    (rec,) = [json.loads(line) for line in open(path)]
    assert rec["kind"] == "heartbeat"
    assert rec["step_ms"] == [100.0, 102.0, 98.0, 400.0]
    assert rec["stragglers"] == [3]
    assert rec["median_step_ms"] == 101.0
    # Steps are collective: the slowest host sets the global pace.
    assert rec["images_per_sec"] == pytest.approx(128 / 0.4, rel=1e-6)
    assert validate_record(rec) == []


def test_heartbeat_uniform_hosts_flag_nothing(tmp_path):
    path = str(tmp_path / "m.jsonl")
    writer = MetricsWriter(path)
    hb = Heartbeat(
        writer, every_steps=1, threshold=1.5,
        gather=lambda v: np.asarray([[100.0], [101.0]], np.float32),
    )
    hb.on_step(0, 0, 0.1)
    writer.close()
    (rec,) = [json.loads(line) for line in open(path)]
    assert rec["stragglers"] == []


def test_host_allgather_single_process_identity():
    from mpi_pytorch_tpu.parallel.collectives import host_allgather

    out = host_allgather(np.asarray([1.5, 2.5], np.float32))
    assert out.shape == (1, 2)
    np.testing.assert_allclose(out[0], [1.5, 2.5])


# ---------------------------------------------------------------------------
# grad-norm metric in the train steps
# ---------------------------------------------------------------------------


def test_train_step_metrics_include_global_grad_norm():
    """Every step flavor now reports the global gradient L2 norm — checked
    here on the streaming auto step against an explicit value_and_grad."""
    import flax.linen as nn
    import optax

    from mpi_pytorch_tpu.config import MeshConfig
    from mpi_pytorch_tpu.ops.losses import classification_loss
    from mpi_pytorch_tpu.parallel.mesh import create_mesh, shard_batch
    from mpi_pytorch_tpu.train.state import TrainState, make_optimizer
    from mpi_pytorch_tpu.train.step import make_train_step, place_state_on_mesh

    class MLP(nn.Module):
        @nn.compact
        def __call__(self, x, train=False):
            x = x.reshape((x.shape[0], -1))
            return nn.Dense(11)(nn.relu(nn.Dense(16)(x)))

    model = MLP()
    variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, 8, 3)))
    state = TrainState.create(
        apply_fn=model.apply, variables=variables, tx=make_optimizer(1e-3),
        rng=jax.random.PRNGKey(1),
    )
    mesh = create_mesh(MeshConfig())
    state = place_state_on_mesh(state, mesh)
    rng = np.random.default_rng(0)
    images = rng.standard_normal((16, 8, 8, 3)).astype(np.float32)
    labels = rng.integers(0, 11, size=(16,)).astype(np.int32)

    params_before = jax.device_get(state.params)  # the step donates `state`
    step = make_train_step(jnp.float32)
    _, m = step(state, shard_batch((images, labels), mesh))
    got = float(m["grad_norm"])
    assert math.isfinite(got) and got > 0

    def loss_fn(params):
        return classification_loss(
            model.apply({"params": params}, jnp.asarray(images), train=False),
            jnp.asarray(labels),
        )

    grads = jax.grad(loss_fn)(params_before)
    np.testing.assert_allclose(got, float(optax.global_norm(grads)), rtol=1e-4)


def test_step_sync_fields_schema_and_render(tmp_path, capsys):
    """Schema v2 (grad-sync levers): step records MAY carry sync_ms /
    overlap_frac — v1 records without them stay valid, mistyped values fail
    validation, and report_run renders the grad-sync phase row + overlap
    line only when the fields are present (satellite: backward-compatible
    rendering)."""
    v2 = {"ts": 2.0, "kind": "step", "epoch": 0, "step": 1, "loss": 0.9,
          "sync_ms": 3.2, "overlap_frac": 0.87}
    v1 = {"ts": 1.0, "kind": "step", "epoch": 0, "step": 0, "loss": 1.0}
    assert validate_record(v1) == [] and validate_record(v2) == []
    assert validate_record({**v2, "overlap_frac": "high"}) != []
    assert validate_record({**v2, "sync_ms": True}) != []

    both = tmp_path / "levers_metrics.jsonl"
    both.write_text(json.dumps(v1) + "\n" + json.dumps(v2) + "\n")
    assert validate_jsonl(str(both)) == []
    assert report_run.main([str(both)]) == 0
    out = capsys.readouterr().out
    assert "grad-sync" in out and "overlap-eligible" in out

    old = tmp_path / "old_metrics.jsonl"
    old.write_text(json.dumps(v1) + "\n")
    assert report_run.main([str(old)]) == 0
    assert "grad-sync" not in capsys.readouterr().out


def test_report_run_renders_committed_levers_artifact(capsys):
    """The committed §4e dryrun artifact (spmd --zero-opt-state
    --grad-sync-buckets, 8-device CPU mesh) renders with the overlap line
    and zero recompiles — the artifact CI schema-checks via
    check_results_artifacts."""
    path = os.path.join(REPO, "docs", "levers_dryrun_metrics.jsonl")
    assert report_run.main([path]) == 0
    out = capsys.readouterr().out
    assert "overlap-eligible" in out
    assert "recompiles (max per record): 0" in out


# ---------------------------------------------------------------------------
# end-to-end: telemetry-enabled dryrun + the report tool
# ---------------------------------------------------------------------------


def _telemetry_cfg(tmpdir, **kw):
    from mpi_pytorch_tpu.config import Config

    cfg = Config()
    cfg.debug = True
    cfg.debug_sample_size = 48
    cfg.train_csv = os.path.join(REPO, "data", "train_sample.csv")
    cfg.test_csv = os.path.join(REPO, "data", "test_sample.csv")
    cfg.synthetic_data = True
    cfg.model_name = "resnet18"
    cfg.num_classes = 200
    cfg.batch_size = 16
    cfg.width = cfg.height = 16
    cfg.num_epochs = 2
    cfg.compute_dtype = "float32"
    cfg.checkpoint_dir = os.path.join(tmpdir, "ckpt")
    cfg.log_file = os.path.join(tmpdir, "training.log")
    cfg.metrics_file = os.path.join(tmpdir, "metrics.jsonl")
    cfg.trace_file = os.path.join(tmpdir, "trace.json")
    cfg.validate = False
    cfg.loader_workers = 2
    cfg.log_every_steps = 0
    cfg.step_metrics = True
    cfg.heartbeat_every_steps = 2
    for k, v in kw.items():
        setattr(cfg, k, v)
    cfg.validate_config()
    return cfg


def test_dryrun_telemetry_end_to_end(tmp_path, capsys):
    """THE acceptance path: a CPU dryrun with telemetry on produces a valid
    Chrome-trace JSON plus per-step records (data-wait, grad-norm,
    recompile count) that report_run.py accepts."""
    from mpi_pytorch_tpu.train.trainer import train

    cfg = _telemetry_cfg(str(tmp_path))
    summary = train(cfg)
    assert summary.epochs_run == 2

    # Chrome trace: valid JSON, the documented span names, nested step spans.
    trace = json.load(open(cfg.trace_file))
    names = {e["name"] for e in trace["traceEvents"]}
    assert {"build", "compile", "ingest", "step", "checkpoint"} <= names
    assert all("ts" in e and "pid" in e for e in trace["traceEvents"])

    # Metrics stream: schema-clean; step records carry the health fields.
    assert validate_jsonl(cfg.metrics_file) == []
    records = [json.loads(line) for line in open(cfg.metrics_file)]
    kinds = {r["kind"] for r in records}
    assert {"epoch", "step", "heartbeat"} <= kinds
    steps = [r for r in records if r["kind"] == "step"]
    # 48 sampled images -> 38-image train split -> 2 steps/epoch x 2 epochs.
    assert len(steps) == 4
    for rec in steps:
        assert math.isfinite(rec["loss"]) and rec["grad_norm"] > 0
        assert rec["data_wait_ms"] >= 0 and rec["step_ms"] > 0
        assert rec["recompiles"] == 0  # AOT step: no silent recompiles
    beats = [r for r in records if r["kind"] == "heartbeat"]
    assert beats and all(b["stragglers"] == [] for b in beats)
    # One clock pair a region: a record's times ARE its spans' lengths.
    spans = sorted(
        (e for e in trace["traceEvents"] if e["name"] in ("ingest", "step")),
        key=lambda e: e["ts"],
    )
    step_ms = [e["dur"] / 1e3 for e in spans if e["name"] == "step"]
    assert [r["step_ms"] for r in steps] == pytest.approx(step_ms, abs=1e-3)
    # (Every epoch ends on one more ``ingest``, the one that finds no batch.)
    waits = [e["dur"] / 1e3 for e in spans if e["name"] == "ingest"]
    assert [r["data_wait_ms"] for r in steps] == pytest.approx(
        waits[0:2] + waits[3:5], abs=1e-3
    )

    # The report tool renders it (exit 0) with the phase breakdown.
    assert report_run.main([cfg.metrics_file]) == 0
    out = capsys.readouterr().out
    assert "data-wait" in out and "grad norm" in out and "heartbeats" in out


EPOCH_SPANS = ("epoch/control", "epoch/prepare", "epoch/wait", "epoch/account", "epoch/record")


@pytest.mark.parametrize("path", ["stream", "cached", "scan"])
def test_trainer_names_the_epoch_boundary_and_the_compile(tmp_path, path):
    """``trainer.main``-level run with ``--trace-file``: each ``epoch/*`` span
    once per epoch on the per-step paths and on the scanned one, the loader's
    and ``h2d`` spans per batch where batches stream, and the compile span's
    children inside it (ISSUE 24 part B)."""
    from mpi_pytorch_tpu.train.trainer import train

    kw = {
        "stream": {},
        "cached": {"device_cache": True},
        "scan": {"device_cache": True, "scan_epoch": True},
    }[path]
    cfg = _telemetry_cfg(
        str(tmp_path), step_metrics=False, heartbeat_every_steps=0,
        checkpoint_every_epochs=0, **kw,
    )
    assert train(cfg).epochs_run == 2
    data = json.load(open(cfg.trace_file))
    assert set(data["otherData"]) == {"t0_perf_counter_s", "t0_unix_ns"}
    events = [e for e in data["traceEvents"] if e["ph"] == "X"]

    def named(name):
        return sorted((e for e in events if e["name"] == name), key=lambda e: e["ts"])

    for name in EPOCH_SPANS:
        spans = [e for e in named(name) if not e["args"].get("stop")]
        assert [e["args"]["epoch"] for e in spans] == [0, 1], name
    assert [e["args"]["steps"] for e in named("epoch/account")] == [2, 2]
    # In order inside an epoch, and none inside another.
    first = [named(name)[0] for name in EPOCH_SPANS]
    assert all(a["ts"] + a["dur"] <= b["ts"] for a, b in zip(first, first[1:]))
    assert len(named("step")) == (2 if path == "scan" else 4)

    (compile_span,) = named("compile")
    programs = [e["args"]["program"] for e in named("lower")]
    assert programs == {"stream": ["step"], "cached": ["step"], "scan": ["step", "epoch"]}[path]
    (load,) = named("load_or_compile")
    assert isinstance(load["args"]["cache_hit"], bool)
    for child in named("lower") + [load] + named("cost_analysis"):
        assert compile_span["ts"] <= child["ts"]
        assert child["ts"] + child["dur"] <= compile_span["ts"] + compile_span["dur"]

    if path == "stream":
        batches = [(e["args"]["epoch"], e["args"]["batch"]) for e in named("h2d")]
        assert batches == [(0, 0), (0, 1), (1, 0), (1, 1)]
        assert all(e["args"]["bytes"] == 16 * 16 * 16 * 3 * 4 + 16 * 4 for e in named("h2d"))
        assert len(named("loader/decode")) == 4 and len(named("loader/epoch")) == 2
        assert all(e["args"]["source"] == "synthetic" for e in named("loader/decode"))
        # h2d sits inside the trainer's ingest, which stays.
        ingests = named("ingest")
        for e in named("h2d"):
            assert any(
                i["ts"] <= e["ts"] and e["ts"] + e["dur"] <= i["ts"] + i["dur"] for i in ingests
            )
    else:
        # The one pass of the loader is the device cache's build.
        (build,), (walk,) = named("cache_build"), named("loader/epoch")
        assert build["ts"] <= walk["ts"] <= build["ts"] + build["dur"]
        assert not named("h2d")


def test_epoch_time_covers_a_scanned_epochs_step_records(tmp_path, monkeypatch):
    """``time_s`` of a scanned epoch's record (and the rate from it) holds
    the top of the epoch to the end of ``epoch/wait`` plus the per-step
    records ``epoch/record`` writes — a slow metrics sink shows in it."""
    import time

    from mpi_pytorch_tpu.obs.health import StepHealth
    from mpi_pytorch_tpu.train.trainer import train

    real = StepHealth.on_scan_epoch

    def slow(self, *args, **kw):
        time.sleep(0.2)
        return real(self, *args, **kw)

    monkeypatch.setattr(StepHealth, "on_scan_epoch", slow)
    cfg = _telemetry_cfg(
        str(tmp_path), heartbeat_every_steps=0, checkpoint_every_epochs=0,
        device_cache=True, scan_epoch=True,
    )
    assert train(cfg).epochs_run == 2
    events = json.load(open(cfg.trace_file))["traceEvents"]
    records = [json.loads(line) for line in open(cfg.metrics_file)]
    epochs = [r for r in records if r["kind"] == "epoch"]
    assert len(epochs) == 2 and len([r for r in records if r["kind"] == "step"]) == 4

    def span(name, epoch):
        (e,) = [
            e for e in events
            if e["name"] == name and e.get("args", {}).get("epoch") == epoch
        ]
        return e["ts"] / 1e6, (e["ts"] + e["dur"]) / 1e6

    for rec in epochs:
        control, wait, record = (
            span(name, rec["epoch"]) for name in ("epoch/control", "epoch/wait", "epoch/record")
        )
        assert wait[1] - control[1] + 0.2 <= rec["time_s"] <= record[1] - control[0]
        assert rec["images_per_sec"] == pytest.approx(32 / rec["time_s"])


@pytest.mark.parametrize("scan", [False, True])
def test_profile_dir_traces_steady_state_only(tmp_path, scan):
    """``--profile-dir`` starts once the first execution of the step program
    has been awaited — never the compile — and stops two epoch boundaries
    later, with the program's spans on the host plane: a trace
    ``benchmark/trace/`` can read."""
    import glob
    from collections import Counter

    from jax.profiler import ProfileData

    from mpi_pytorch_tpu.train.trainer import train

    cfg = _telemetry_cfg(
        str(tmp_path), step_metrics=False, heartbeat_every_steps=0,
        checkpoint_every_epochs=0, num_epochs=5, device_cache=scan, scan_epoch=scan,
        profile_dir=str(tmp_path / "profile"),
    )
    assert train(cfg).epochs_run == 5
    (path,) = glob.glob(os.path.join(cfg.profile_dir, "plugins", "profile", "*", "*.xplane.pb"))
    spans = {e["name"] for e in json.load(open(cfg.trace_file))["traceEvents"]}
    seen = Counter(
        event.name
        for plane in ProfileData.from_file(path).planes if plane.name == "/host:CPU"
        for line in plane.lines for event in line.events if event.name in spans
    )
    assert not {"build", "compile", "lower", "load_or_compile"} & set(seen)
    # From inside epoch 0 to the awaited end of epoch 2: three waits, two
    # whole boundaries, and not the epochs after them.
    assert seen["epoch/wait"] == 3 and seen["epoch/record"] == 2 and seen["epoch/prepare"] == 2


def test_poisoned_loss_aborts_cleanly(tmp_path):
    """THE sentinel acceptance: a diverging run (lr=1e38 NaNs the loss
    within two steps) aborts with NonFiniteLossError, writes the anomaly
    diagnostic, and still flushes the trace on the failure path."""
    from mpi_pytorch_tpu.train.trainer import train

    cfg = _telemetry_cfg(str(tmp_path), learning_rate=1e38, num_epochs=3)
    with pytest.raises(NonFiniteLossError):
        train(cfg)

    records = [json.loads(line) for line in open(cfg.metrics_file)]
    anomalies = [r for r in records if r["kind"] == "anomaly"]
    assert len(anomalies) == 1
    assert anomalies[0]["reason"] == "nonfinite_loss"
    assert not math.isfinite(anomalies[0]["loss"])
    assert validate_jsonl(cfg.metrics_file) == []
    # Failure path still writes the trace the diagnostics need.
    assert {"build", "step"} <= {
        e["name"] for e in json.load(open(cfg.trace_file))["traceEvents"]
    }


def test_report_run_renders_committed_artifact(capsys):
    """Acceptance: the committed chip artifact renders into a summary."""
    path = os.path.join(REPO, "docs", "chip_train_metrics.jsonl")
    assert report_run.main([path]) == 0
    out = capsys.readouterr().out
    assert "epochs:" in out and "throughput" in out
    assert "MFU" in out


def test_report_run_json_mode(capsys):
    path = os.path.join(REPO, "docs", "decode_metrics.jsonl")
    assert report_run.main([path, "--json"]) == 0
    summary = json.loads(capsys.readouterr().out)
    assert summary["kinds"] == {"epoch": 10, "eval": 1, "val": 10}
    assert summary["val"]["best_accuracy"] == 1.0


def test_report_run_rejects_malformed(tmp_path, capsys):
    bad = tmp_path / "bad_metrics.jsonl"
    bad.write_text(
        '{"ts": 1.0, "kind": "epoch", "epoch": 0}\n'  # missing required fields
        '{"ts": 1.0, "kind": "bogus"}\n'  # unknown kind
        "not json\n"
    )
    assert report_run.main([str(bad)]) == 1
    out = capsys.readouterr().out
    assert "schema violation" in out and "bogus" in out


def test_schema_rejects_wrong_types():
    assert validate_record(
        {"ts": 1.0, "kind": "epoch", "epoch": "zero", "loss": 1.0,
         "time_s": 1.0, "images_per_sec": 1.0}
    ) != []
    assert validate_record({"kind": "val", "epoch": 0, "accuracy": 0.5,
                            "loss": 1.0}) != []  # missing ts
    assert validate_record(
        {"ts": 1.0, "kind": "step", "epoch": 0, "step": 0, "loss": 1.0,
         "grad_norm": None, "hbm_bytes": None}
    ) == []  # optional fields may be null


def test_heartbeat_window_resets_at_epoch_boundary(tmp_path):
    """Leftover step samples (n_steps % every != 0) must not leak into the
    next epoch's first beat — beats never average across epoch boundaries."""
    locals_sent = []

    def gather(v):
        locals_sent.append(round(float(np.asarray(v)[0]), 3))
        return np.asarray(v, np.float32)[None]

    writer = MetricsWriter(str(tmp_path / "m.jsonl"))
    hb = Heartbeat(writer, every_steps=2, gather=gather)
    hb.start_epoch()
    hb.on_step(0, 0, 1.0)
    hb.on_step(0, 1, 1.0)      # beat: mean 1000 ms
    hb.on_step(0, 2, 9.0)      # tail sample, no beat — must be dropped
    hb.start_epoch()
    hb.on_step(1, 0, 0.1)
    hb.on_step(1, 1, 0.1)      # beat: mean 100 ms, NOT polluted by the 9 s tail
    writer.close()
    assert locals_sent == [1000.0, 100.0]


# ---------------------------------------------------------------------------
# live telemetry: registry + SLO monitor + flight recorder, end to end
# ---------------------------------------------------------------------------


def test_slo_straggler_alert_preempts_run(tmp_path, monkeypatch):
    """ISSUE 8's acceptance chain, in-process: a fake straggler appears
    mid-run (MPT_FAULT_DELAY_STEP_MS after MPT_FAULT_DELAY_AFTER_STEP
    clean steps), the drift SLO rule fires ONE kind="alert" record, its
    preempt action writes the sentinel, the watchdog observes it
    (kind="fault" reason=preempt_file) and stops the run cleanly, the
    flight recorder dumps schema-clean evidence, and periodic
    kind="metrics" snapshots land in the stream."""
    from mpi_pytorch_tpu.train.trainer import train

    sentinel = str(tmp_path / "preempt.sentinel")
    # The delay must dominate the noisy natural CPU step time so the 2x
    # drift ratio is unambiguous — the run preempts ~2 delayed steps in,
    # so the extra wall cost stays at a few seconds. Natural steps on a
    # loaded single-core box reach ~2 s, which put 1500 ms under the 2x
    # ratio; 6 s keeps the ratio >= 3-4x on any hardware.
    monkeypatch.setenv("MPT_FAULT_DELAY_STEP_MS", "6000")
    monkeypatch.setenv("MPT_FAULT_DELAY_AFTER_STEP", "4")
    cfg = _telemetry_cfg(
        str(tmp_path),
        num_epochs=8,
        heartbeat_every_steps=0,
        slo_rules=(
            "drift:train/step_ms_last > 2.0 warmup=3 "
            "action=log,metric,preempt name=straggler_step_drift"
        ),
        metrics_every_steps=2,
        flight_dir=str(tmp_path / "flight"),
        preempt_file=sentinel,
    )
    summary = train(cfg)
    assert summary.preempted, "the SLO breach never stopped the run"
    assert os.path.exists(sentinel)

    assert validate_jsonl(cfg.metrics_file) == []
    records = [json.loads(line) for line in open(cfg.metrics_file)]
    alerts = [r for r in records if r["kind"] == "alert"]
    assert [a["rule"] for a in alerts] == ["straggler_step_drift"]
    assert alerts[0]["value"] > 2.0 and alerts[0]["action"] == "log,metric,preempt"
    faults = [r for r in records if r["kind"] == "fault"]
    assert any(f["reason"] == "preempt_file" for f in faults), faults
    snaps = [r for r in records if r["kind"] == "metrics"]
    assert snaps, "no kind='metrics' snapshots on the cadence"
    last = snaps[-1]
    assert last["counters"]["obs/alerts_fired"] == 1.0
    assert last["histograms"]["train/step_ms"]["count"] > 0
    assert last["gauges"]["train/step_ms_last"] > 0

    dumps = sorted(os.listdir(cfg.flight_dir))
    alert_dumps = [d for d in dumps if "alert_straggler_step_drift" in d]
    assert alert_dumps, dumps
    dumped = json.load(open(os.path.join(cfg.flight_dir, alert_dumps[0])))
    assert dumped["records"][-1]["kind"] == "alert"
    from mpi_pytorch_tpu.obs.schema import validate_record as _vr
    for rec in dumped["records"]:
        assert _vr(rec) == [], rec

    # The report tool renders the new kinds.
    assert report_run.main([cfg.metrics_file]) == 0


def test_registry_snapshots_without_rules(tmp_path):
    """--metrics-every-steps alone (no SLO rules) still publishes the
    registry cadence: step-time histograms/gauges with no alert machinery,
    and the stream stays schema-clean."""
    from mpi_pytorch_tpu.train.trainer import train

    cfg = _telemetry_cfg(
        str(tmp_path), metrics_every_steps=2, heartbeat_every_steps=0,
    )
    summary = train(cfg)
    assert summary.epochs_run == 2
    assert validate_jsonl(cfg.metrics_file) == []
    records = [json.loads(line) for line in open(cfg.metrics_file)]
    snaps = [r for r in records if r["kind"] == "metrics"]
    # 2 steps/epoch x 2 epochs at every-2 cadence = 2 periodic + 1 final.
    assert len(snaps) == 3
    for s in snaps:
        assert set(s["histograms"]) >= {"train/step_ms", "train/data_wait_ms"}
    assert snaps[-1]["gauges"]["train/images_per_sec"] > 0
    assert not [r for r in records if r["kind"] == "alert"]
