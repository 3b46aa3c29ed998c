"""Elastic training (ISSUE 7 / ROADMAP item 4): topology-manifest stamping,
cross-mesh ZeRO checkpoint round-trips (8→4, 8→1, 4→8), bounded-HBM
redistribution, corrupt-checkpoint fallback, the preemption watchdog
(sentinel file / health streaks), resume-side retry+backoff, and the
fault-injection harness — all on the 8-virtual-device CPU mesh.

Cross-mesh tolerance: the spmd gradient is the mean of P per-shard means
over the SAME global batch, so a P=8 and a P=4 run see identical math up
to reduction order — trajectories must agree to float32 reduction noise
(atol 1e-5), the documented checkpoint tolerance for exact (non-bf16-
moment) saves. BN models are excluded by design: spmd-mode LOCAL batch
statistics legitimately depend on P (reference per-rank semantics,
docs/MULTIHOST.md)."""

import json
import os
import threading
import time

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from mpi_pytorch_tpu import checkpoint as ckpt
from mpi_pytorch_tpu.config import Config, MeshConfig
from mpi_pytorch_tpu.parallel.mesh import create_mesh, mesh_topology
from mpi_pytorch_tpu.train import elastic
from mpi_pytorch_tpu.train.state import (
    TrainState,
    make_optimizer,
    zero_shard_opt_state,
    zero_unshard_opt_state,
)
from mpi_pytorch_tpu.train.step import make_spmd_train_step, place_state_on_mesh
from mpi_pytorch_tpu.parallel.mesh import shard_batch
from mpi_pytorch_tpu.utils.env import FAULT_GATES, fault_countdown, reset_fault_counters

NUM_CLASSES = 8


class MLP(nn.Module):
    @nn.compact
    def __call__(self, x, train=False):
        x = x.reshape(x.shape[0], -1)
        x = nn.relu(nn.Dense(13, name="body")(x))  # 13: uneven → ZeRO padding
        return nn.Dense(NUM_CLASSES, name="head")(x)


def _mlp_state(seed=0):
    model = MLP()
    variables = model.init(jax.random.PRNGKey(seed), jnp.zeros((1, 8, 8, 3)), train=True)
    return TrainState.create(
        apply_fn=model.apply, variables=variables,
        tx=make_optimizer(1e-2), rng=jax.random.PRNGKey(seed + 1),
    )


def _mesh_of(n: int) -> Mesh:
    return Mesh(np.asarray(jax.devices()[:n]).reshape(n, 1), ("data", "model"))


def _batch(n=16):
    rng = np.random.default_rng(0)
    images = rng.normal(size=(n, 8, 8, 3)).astype(np.float32)
    labels = (np.arange(n) % NUM_CLASSES).astype(np.int32)
    return images, labels


class FakeMetrics:
    def __init__(self):
        self.records = []

    def write(self, record):
        self.records.append(dict(record))


def _zero_steps(state, mesh, batch, n, bounded_bytes=None):
    """Run ``n`` spmd+ZeRO steps from a HOST state: place, shard, step;
    returns (state, [loss], [grad_norm])."""
    state = place_state_on_mesh(state, mesh)
    state = state.replace(
        opt_state=zero_shard_opt_state(state.opt_state, mesh, bounded_bytes=bounded_bytes)
    )
    step = make_spmd_train_step(mesh, jnp.float32, zero_opt_state=True)
    losses, norms = [], []
    for _ in range(n):
        state, m = step(state, shard_batch(batch, mesh))
        losses.append(float(m["loss"]))
        norms.append(float(m["grad_norm"]))
    return state, losses, norms


def _save_zero(state, mesh, tmp_path, epoch=0, loss=0.5):
    """Gather-on-save a ZeRO-sharded state with its topology manifest."""
    template = jax.eval_shape(state.tx.init, state.params)
    saveable = state.replace(opt_state=zero_unshard_opt_state(state.opt_state, template))
    manifest = elastic.topology_manifest(
        mesh, zero_opt_state=True, spmd_mode=True, opt_template=template
    )
    return ckpt.save_checkpoint(
        str(tmp_path), epoch=epoch, state=saveable, loss=loss, manifest=manifest
    )


# ---------------------------------------------------------------------------
# topology manifest
# ---------------------------------------------------------------------------


def test_manifest_written_read_and_retired(tmp_path):
    mesh = _mesh_of(8)
    batch = _batch()
    state, _, _ = _zero_steps(_mlp_state(), mesh, batch, 1)
    path = _save_zero(state, mesh, tmp_path, epoch=0)

    manifest = ckpt.read_manifest(path)
    assert manifest["manifest_version"] == elastic.MANIFEST_VERSION
    assert manifest["payload_schema"] == ckpt.PAYLOAD_SCHEMA
    assert manifest["device_count"] == 8
    assert manifest["mesh_shape"] == {"data": 8, "model": 1}
    assert manifest["zero_opt_state"] is True and manifest["zero_shards"] == 8
    # Per-leaf [chunk, padded] layout: the 13-unit body bias is the uneven
    # leaf — ceil(13/8)=2 rows of chunk, padded to 16.
    layout = manifest["zero_shard_layout"]
    bias_keys = [k for k in layout if "body" in k and "bias" in k]
    assert bias_keys and layout[bias_keys[0]] == [2, 16]

    # Retention retires the manifest sidecar with its payload.
    for epoch in (1, 2, 3):
        _save_zero(state, mesh, tmp_path, epoch=epoch)
    assert not os.path.exists(path)
    assert not os.path.exists(path + ".manifest.json")

    # Legacy (manifest-less) checkpoints read as None.
    bare = ckpt.save_checkpoint(str(tmp_path / "bare"), epoch=0, state=_mlp_state(), loss=0.0)
    assert ckpt.read_manifest(bare) is None


# ---------------------------------------------------------------------------
# cross-mesh ZeRO round-trips (the satellite: 8→4, 8→1, 4→8)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("p_from,p_to", [(8, 4), (8, 1), (4, 8)])
def test_cross_mesh_zero_resume_matches_same_mesh(tmp_path, p_from, p_to):
    """A checkpoint written with --zero-opt-state on a P_from-device mesh
    resumes on a P_to mesh with the SAME post-resume loss/grad-norm
    trajectory as the same-mesh resume (float32 reduction noise only):
    the opt-state leaves are re-flattened/re-padded/re-chunked for the new
    P, including the P→1 degenerate case."""
    batch = _batch()
    mesh_from = _mesh_of(p_from)
    state, _, _ = _zero_steps(_mlp_state(), mesh_from, batch, 2)
    path = _save_zero(state, mesh_from, tmp_path, epoch=0)

    def resume_on(p):
        mesh = _mesh_of(p)
        metrics = FakeMetrics()
        res = elastic.restore_latest(
            str(tmp_path), _mlp_state(seed=7), mesh, metrics=metrics,
            zero_shards_to=p,
        )
        assert res is not None
        restored, epoch, loss, info = res
        assert (epoch, loss) == (0, 0.5)
        assert info["manifest"]["zero_shards"] == p_from
        record = [r for r in metrics.records if r["kind"] == "resume"][0]
        assert record["from_devices"] == p_from and record["to_devices"] == p
        assert record["zero_shards_from"] == p_from and record["zero_shards_to"] == p
        _, losses, norms = _zero_steps(restored, mesh, batch, 3)
        return losses, norms

    same_losses, same_norms = resume_on(p_from)
    cross_losses, cross_norms = resume_on(p_to)
    np.testing.assert_allclose(cross_losses, same_losses, rtol=2e-5, atol=1e-5)
    np.testing.assert_allclose(cross_norms, same_norms, rtol=2e-5, atol=1e-5)


def test_bounded_redistribution_matches_jitted_path():
    """The chunked per-row device redistribution (bounded_bytes=0 forces
    EVERY host leaf through it) lands bit-identical [P, chunk] shards to
    the jitted-reshape path, with each device holding exactly its 1/P row."""
    mesh = _mesh_of(8)
    state = _mlp_state()
    host_opt = jax.device_get(state.opt_state)

    jitted = zero_shard_opt_state(host_opt, mesh)
    bounded = zero_shard_opt_state(host_opt, mesh, bounded_bytes=0)
    for a, b in zip(jax.tree_util.tree_leaves(jitted), jax.tree_util.tree_leaves(bounded)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        if hasattr(b, "addressable_shards") and b.ndim > 0:
            assert b.sharding.spec == jax.sharding.PartitionSpec("data")
            assert b.addressable_shards[0].data.shape[0] == 1  # one row/device

    template = jax.eval_shape(state.tx.init, state.params)
    back = zero_unshard_opt_state(bounded, template)
    for a, b in zip(jax.tree_util.tree_leaves(host_opt), jax.tree_util.tree_leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# corrupt-checkpoint fallback (satellite 1, pinned by the fault harness)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["truncate", "garbage", "empty"])
def test_corrupt_newest_falls_back_to_previous(tmp_path, mode):
    from tools.inject_faults import corrupt_latest

    mesh = _mesh_of(8)
    batch = _batch()
    state, _, _ = _zero_steps(_mlp_state(), mesh, batch, 1)
    _save_zero(state, mesh, tmp_path, epoch=0, loss=0.1)
    state2, _, _ = _zero_steps(_mlp_state(seed=3), mesh, batch, 1)
    _save_zero(state2, mesh, tmp_path, epoch=1, loss=0.2)

    newest = corrupt_latest(str(tmp_path), mode=mode)
    assert ckpt.checkpoint_epoch(newest) == 1
    with pytest.raises(ckpt.CheckpointCorruptError):
        ckpt.load_checkpoint(newest, _mlp_state())

    metrics = FakeMetrics()
    res = elastic.restore_latest(str(tmp_path), _mlp_state(seed=9), mesh, metrics=metrics)
    assert res is not None
    _, epoch, loss, info = res
    assert (epoch, loss) == (0, pytest.approx(0.1)) and info["corrupt_skipped"] == 1
    anomalies = [r for r in metrics.records if r["kind"] == "anomaly"]
    assert anomalies and anomalies[0]["reason"] == "corrupt_checkpoint"
    assert anomalies[0]["epoch"] == 1
    resume = [r for r in metrics.records if r["kind"] == "resume"][0]
    assert resume["corrupt_skipped"] == 1


def test_every_checkpoint_corrupt_aborts_instead_of_fresh_start(tmp_path):
    """Checkpoints existed but NONE restored: refuse to fresh-start (which
    would exit 0 and let retention delete the files) — every file failing
    identically is the template-mismatch signature, not bit rot. An EMPTY
    dir still means a legitimate fresh start (None)."""
    from tools.inject_faults import corrupt_latest

    assert elastic.restore_latest(str(tmp_path / "nothing"), _mlp_state(), _mesh_of(8)) is None

    _save_zero(*_state_on_mesh8(), tmp_path, epoch=0)
    corrupt_latest(str(tmp_path), mode="empty")
    metrics = FakeMetrics()
    with pytest.raises(ckpt.CheckpointCorruptError, match="refusing to fresh-start"):
        elastic.restore_latest(str(tmp_path), _mlp_state(), _mesh_of(8), metrics=metrics)
    assert [r["kind"] for r in metrics.records] == ["anomaly"]


def _state_on_mesh8():
    mesh = _mesh_of(8)
    state, _, _ = _zero_steps(_mlp_state(), mesh, _batch(), 1)
    return state, mesh


# ---------------------------------------------------------------------------
# trainer integration: sentinel preemption, retries, fault gates
# ---------------------------------------------------------------------------


def _train_cfg(tmp_path, **kw) -> Config:
    c = Config()
    c.debug = True
    c.debug_sample_size = 48
    c.train_csv = os.path.join(os.path.dirname(__file__), "..", "data", "train_sample.csv")
    c.test_csv = os.path.join(os.path.dirname(__file__), "..", "data", "test_sample.csv")
    c.synthetic_data = True
    c.model_name = "resnet18"
    c.num_classes = 200
    c.batch_size = 16
    c.width = c.height = 16
    c.num_epochs = 2
    c.compute_dtype = "float32"
    c.checkpoint_dir = os.path.join(str(tmp_path), "ckpt")
    c.log_file = os.path.join(str(tmp_path), "training.log")
    c.metrics_file = os.path.join(str(tmp_path), "metrics.jsonl")
    c.validate = False
    c.loader_workers = 2
    c.log_every_steps = 0
    c.spmd_mode = True
    c.zero_opt_state = True
    c.resume_backoff_s = 0.0  # tests never sleep through backoff
    for k, v in kw.items():
        setattr(c, k, v)
    c.validate_config()
    return c


def _records(cfg) -> list[dict]:
    return [json.loads(line) for line in open(cfg.metrics_file) if line.strip()]


@pytest.fixture
def clean_gates():
    """Fault-gate hygiene: counters latch env values at first use, so every
    gate test resets before AND after (a leaked countdown would fire inside
    an unrelated test's create_mesh)."""
    reset_fault_counters()
    yield
    for name in FAULT_GATES:
        os.environ.pop(name, None)
    reset_fault_counters()


def test_preexisting_sentinel_stops_before_epoch_zero(tmp_path):
    from mpi_pytorch_tpu.train.trainer import train

    sentinel = tmp_path / "preempt.now"
    sentinel.write_text("")
    cfg = _train_cfg(tmp_path, preempt_file=str(sentinel), num_epochs=5)
    summary = train(cfg)
    assert summary.preempted and summary.epochs_run == 0
    faults = [r for r in _records(cfg) if r["kind"] == "fault"]
    assert faults and faults[0]["reason"] == "preempt_file"
    from mpi_pytorch_tpu.obs.schema import validate_jsonl

    assert validate_jsonl(cfg.metrics_file) == []


def test_midrun_sentinel_preempts_saves_and_resumes(tmp_path):
    """The sentinel appears MID-run (the scheduler's preemption notice):
    the run stops at a safe boundary, saves, reports preempted; dropping
    the sentinel lets auto-resume finish the remaining epochs."""
    from mpi_pytorch_tpu.train.trainer import train

    sentinel = tmp_path / "preempt.now"
    cfg = _train_cfg(tmp_path, preempt_file=str(sentinel), num_epochs=30)
    out = {}

    def run():
        out["summary"] = train(cfg)

    t = threading.Thread(target=run)
    t.start()
    deadline = time.time() + 240
    while time.time() < deadline:
        if os.path.exists(cfg.metrics_file) and any(
            r["kind"] == "epoch" for r in _records(cfg)
        ):
            break
        time.sleep(0.1)
    else:
        pytest.fail("epoch 0 never completed")
    sentinel.write_text("")
    t.join(timeout=240)
    assert not t.is_alive()
    assert out["summary"].preempted
    assert ckpt.latest_checkpoint(cfg.checkpoint_dir) is not None
    assert any(
        r["kind"] == "fault" and r["reason"] == "preempt_file" for r in _records(cfg)
    )

    sentinel.unlink()
    done = train(_train_cfg(tmp_path, preempt_file=str(sentinel),
                            num_epochs=out["summary"].epochs_run + 2,
                            from_checkpoint=True))
    assert not done.preempted and done.epochs_run >= 1
    resumes = [r for r in _records(cfg) if r["kind"] == "resume"]
    assert resumes and resumes[-1]["to_devices"] == 8


def test_backend_wedge_absorbed_by_resume_retries(tmp_path, clean_gates):
    from mpi_pytorch_tpu.train.trainer import train

    # Seed a checkpoint, then resume through a backend that wedges twice.
    train(_train_cfg(tmp_path, num_epochs=1))
    os.environ["MPT_FAULT_BACKEND_WEDGE_N"] = "2"
    reset_fault_counters()
    summary = train(_train_cfg(tmp_path, num_epochs=2, from_checkpoint=True))
    assert summary.epochs_run == 1
    log = open(_train_cfg(tmp_path).log_file).read()
    assert "backend init (mesh build) failed" in log and "retrying" in log


def test_backend_wedge_beyond_retries_raises(tmp_path, clean_gates):
    from mpi_pytorch_tpu.train.trainer import train

    train(_train_cfg(tmp_path, num_epochs=1))
    os.environ["MPT_FAULT_BACKEND_WEDGE_N"] = "10"
    reset_fault_counters()
    with pytest.raises(RuntimeError, match="backend init wedged"):
        train(_train_cfg(tmp_path, num_epochs=2, from_checkpoint=True, resume_retries=2))


def test_device_put_fault_absorbed_on_resume(tmp_path, clean_gates):
    from mpi_pytorch_tpu.train.trainer import train

    train(_train_cfg(tmp_path, num_epochs=1))
    os.environ["MPT_FAULT_DEVICE_PUT_N"] = "1"
    reset_fault_counters()
    summary = train(_train_cfg(tmp_path, num_epochs=2, from_checkpoint=True))
    assert summary.epochs_run == 1
    log = open(_train_cfg(tmp_path).log_file).read()
    assert "state placement (device_put) failed" in log


def test_fault_injector_kill_gate(monkeypatch, clean_gates):
    from mpi_pytorch_tpu.train.elastic import FaultInjector

    killed = []
    monkeypatch.setattr(os, "kill", lambda pid, sig: killed.append((pid, sig)))
    os.environ["MPT_FAULT_KILL_AT_STEP"] = "2"
    metrics = FakeMetrics()
    injector = FaultInjector(metrics=metrics)
    assert injector.active
    injector.after_step(0, 0)
    assert not killed
    injector.after_step(0, 1)
    assert killed == [(os.getpid(), 9)]
    assert metrics.records[-1] == {
        "kind": "fault", "reason": "injected_kill", "epoch": 0, "step": 1,
        "detail": "MPT_FAULT_KILL_AT_STEP=2",
    }


def test_fault_countdown_is_registered_and_bounded(clean_gates):
    os.environ["MPT_FAULT_BACKEND_WEDGE_N"] = "2"
    reset_fault_counters()
    assert fault_countdown("MPT_FAULT_BACKEND_WEDGE_N")
    assert fault_countdown("MPT_FAULT_BACKEND_WEDGE_N")
    assert not fault_countdown("MPT_FAULT_BACKEND_WEDGE_N")  # exhausted
    with pytest.raises(KeyError):
        fault_countdown("MPT_FAULT_TYPO")


def test_watchdog_streak_triggers():
    from mpi_pytorch_tpu.train.elastic import PreemptionWatchdog

    class Beat:
        straggler_streak = 0

    class Health:
        nonfinite_grad_streak = 0

    beat, health, metrics = Beat(), Health(), FakeMetrics()
    dog = PreemptionWatchdog(
        None, straggler_beats=3, nonfinite_steps=2,
        heartbeat=beat, health=health, metrics=metrics,
    )
    assert not dog.should_stop(epoch=0, step=0)
    beat.straggler_streak = 3
    assert dog.should_stop(epoch=1, step=4)
    assert dog.should_stop()  # latched
    assert len(metrics.records) == 1  # one record, not one per poll
    rec = metrics.records[0]
    assert rec["reason"] == "straggler_streak" and rec["streak"] == 3
    assert (rec["epoch"], rec["step"]) == (1, 4)

    dog2 = PreemptionWatchdog(None, nonfinite_steps=2, health=health, metrics=metrics)
    health.nonfinite_grad_streak = 2
    assert dog2.should_stop(epoch=0)
    assert metrics.records[-1]["reason"] == "nonfinite_grads"


def test_heartbeat_and_health_streak_counters():
    from mpi_pytorch_tpu.obs.health import StepHealth
    from mpi_pytorch_tpu.obs.heartbeat import Heartbeat

    metrics = FakeMetrics()
    hb = Heartbeat(
        metrics, every_steps=1, threshold=1.5,
        gather=lambda v: np.asarray([[100.0], [500.0]], np.float32),
    )
    hb.on_step(0, 0, 0.1)
    hb.on_step(0, 1, 0.1)
    assert hb.straggler_streak == 2
    hb._gather = lambda v: np.asarray([[100.0], [100.0]], np.float32)
    hb.on_step(0, 2, 0.1)
    assert hb.straggler_streak == 0  # a clean beat resets

    sh = StepHealth(metrics, step_metrics=True, nan_sentinel=False)
    m = {"loss": 1.0, "grad_norm": float("inf")}
    sh.on_step(0, 0, m)
    sh.on_step(0, 1, m)
    assert sh.nonfinite_grad_streak == 2
    sh.on_step(0, 2, {"loss": 1.0, "grad_norm": 0.5})
    assert sh.nonfinite_grad_streak == 0


def test_every_fault_gate_in_source_is_registered():
    """The check_results_artifacts-style hygiene rule: every MPT_FAULT_* /
    MPT_PREEMPT_* token anywhere in the package and tools must be a
    registered FAULT_GATES entry — a renamed or typo'd gate must fail here,
    not silently never fire inside a chaos test."""
    import re

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    pat = re.compile(r"MPT_(?:FAULT|PREEMPT)_[A-Z_]*[A-Z]")
    found = set()
    for root in ("mpi_pytorch_tpu", "tools", "tests", "__graft_entry__.py"):
        full = os.path.join(repo, root)
        files = [full] if full.endswith(".py") else [
            os.path.join(d, f)
            for d, _, names in os.walk(full) for f in names if f.endswith(".py")
        ]
        for path in files:
            found |= set(pat.findall(open(path).read()))
    found.discard("MPT_FAULT_TYPO")  # this file's negative-case fixture
    assert found, "the scan found no gates — the pattern broke"
    assert found <= set(FAULT_GATES), found - set(FAULT_GATES)


def test_report_run_renders_resume_and_fault_records(tmp_path, capsys):
    from tools import report_run

    path = tmp_path / "m.jsonl"
    records = [
        {"ts": 1.0, "kind": "fault", "reason": "preempt_file",
         "detail": "sentinel exists", "epoch": 2},
        {"ts": 2.0, "kind": "resume", "epoch": 2, "to_devices": 4,
         "from_devices": 8, "from_mesh": "data=8,model=1",
         "to_mesh": "data=4,model=1", "zero_shards_from": 8,
         "zero_shards_to": 4, "corrupt_skipped": 1, "strategy": "host-reshard"},
    ]
    path.write_text("".join(json.dumps(r) + "\n" for r in records))
    assert report_run.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert "RESUME: epoch 2 — data=8,model=1 → data=4,model=1" in out
    assert "ZeRO P 8 → 4" in out and "1 corrupt checkpoint(s) skipped" in out
    assert "FAULT: preempt_file at epoch 2 — sentinel exists" in out


def test_config_validates_elastic_knobs():
    with pytest.raises(ValueError, match="resume_retries"):
        Config(resume_retries=-1).validate_config()
    with pytest.raises(ValueError, match="resume_backoff_s"):
        Config(resume_backoff_s=-0.1).validate_config()
    with pytest.raises(ValueError, match="preempt_straggler_beats"):
        Config(preempt_straggler_beats=2).validate_config()  # no heartbeat
    with pytest.raises(ValueError, match="preempt_nonfinite_steps"):
        Config(preempt_nonfinite_steps=2).validate_config()  # no step metrics
    Config(
        preempt_straggler_beats=2, heartbeat_every_steps=5,
        preempt_nonfinite_steps=2, step_metrics=True,
    ).validate_config()


# ---------------------------------------------------------------------------
# THE chaos drive (acceptance): SIGKILL mid-step on 8 devices + corrupt the
# newest file, auto-resume on 4 — recovery via fallback + reshard-on-load.
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_chaos_kill_corrupt_and_cross_mesh_resume(tmp_path):
    import subprocess
    import sys

    from tools.inject_faults import corrupt_latest, fault_env

    REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    args = [
        sys.executable, "-m", "mpi_pytorch_tpu.train",
        "--debug", "true", "--debug-sample-size", "64", "--num-classes", "200",
        "--batch-size", "16", "--width", "16", "--height", "16",
        "--synthetic-data", "true", "--validate", "false",
        "--compute-dtype", "float32", "--loader-workers", "2",
        "--log-every-steps", "0", "--spmd-mode", "true",
        "--zero-opt-state", "true", "--step-metrics", "true",
        "--num-epochs", "6", "--checkpoint-every-epochs", "1",
        "--checkpoint-dir", str(tmp_path / "ckpt"),
        "--log-file", str(tmp_path / "training.log"),
        "--metrics-file", str(tmp_path / "metrics.jsonl"),
    ]

    def env_for(n, **faults):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        flags = [
            f for f in env.get("XLA_FLAGS", "").split()
            if "xla_force_host_platform_device_count" not in f
        ]
        env["XLA_FLAGS"] = " ".join(
            flags + [f"--xla_force_host_platform_device_count={n}"]
        )
        return fault_env(base=env, **faults)

    # Kill mid-epoch 3 (4 steps/epoch, step 14 = epoch 3 step 1) on 8 devices.
    rc = subprocess.run(
        args, env=env_for(8, kill_at_step=14), cwd=REPO,
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    ).returncode
    assert rc != 0
    assert len(ckpt.checkpoint_paths(str(tmp_path / "ckpt"))) >= 2

    # Corrupt whatever the crash left newest: recovery must fall back.
    corrupt_latest(str(tmp_path / "ckpt"), mode="garbage")

    # Auto-resume on HALF the mesh, through a backend that wedges once.
    subprocess.run(
        args + ["--from-checkpoint", "true"],
        env=env_for(4, backend_wedge=1), cwd=REPO, check=True,
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )

    records = [
        json.loads(line) for line in open(tmp_path / "metrics.jsonl") if line.strip()
    ]
    kinds = {r["kind"] for r in records}
    assert {"fault", "anomaly", "resume", "epoch", "step"} <= kinds
    resume = [r for r in records if r["kind"] == "resume"][-1]
    assert resume["from_devices"] == 8 and resume["to_devices"] == 4
    assert resume["corrupt_skipped"] >= 1
    # Every epoch completed across the kill+corrupt+reshard.
    assert {r["epoch"] for r in records if r["kind"] == "epoch"} == set(range(6))
    # Zero steady-state recompiles after the cross-mesh resume.
    post = [r for r in records if r["kind"] == "step" and r["ts"] >= resume["ts"]]
    assert post and all(r["recompiles"] == 0 for r in post)
    from mpi_pytorch_tpu.obs.schema import validate_jsonl

    assert validate_jsonl(str(tmp_path / "metrics.jsonl")) == []
