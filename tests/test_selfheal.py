"""Self-healing training (ISSUE 10): exact-step mid-epoch resume (the data
cursor in the checkpoint's topology sidecar + loader fast-forward) and the
bad-step policies (--bad-step-policy skip|rollback), plus the decode-failure
quarantine path in data/pipeline.py — all on the 8-virtual-device CPU mesh.

The tentpole pin: a run preempted MID-epoch (deterministically, via the
MPT_FAULT_PREEMPT_AT_STEP gate) saves a dirty checkpoint whose cursor lets
auto-resume continue at step N+1 with ZERO replayed optimizer steps — the
resumed run's final parameters equal the uninterrupted run's bit-for-bit
(the save is exact f32 and the walk is deterministic)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_pytorch_tpu import checkpoint as ckpt
from mpi_pytorch_tpu.config import Config
from mpi_pytorch_tpu.data.manifest import Manifest, manifest_fingerprint
from mpi_pytorch_tpu.data.pipeline import BadSampleLimitError, DataLoader
from mpi_pytorch_tpu.train import elastic
from mpi_pytorch_tpu.utils.env import FAULT_GATES, reset_fault_counters


class FakeMetrics:
    def __init__(self):
        self.records = []

    def write(self, record):
        self.records.append(dict(record))


@pytest.fixture
def clean_gates():
    reset_fault_counters()
    yield
    for name in FAULT_GATES:
        os.environ.pop(name, None)
    reset_fault_counters()


def _synthetic_manifest(n=20):
    return Manifest(
        filenames=tuple(f"f{i}.jpg" for i in range(n)),
        labels=(np.arange(n) % 7).astype(np.int32),
        category_ids=np.arange(n),
        img_dir="unused",
    )


# ---------------------------------------------------------------------------
# cursor fast-forward on all three data paths
# ---------------------------------------------------------------------------


def _loader_batches(dl, epoch, start):
    return [(i.copy(), l.copy()) for i, l in dl.epoch(epoch, start_batch=start)]


@pytest.mark.parametrize("start", [0, 1, 3])
def test_fastforward_streaming_matches_full_tail(start):
    m = _synthetic_manifest(20)
    kw = dict(batch_size=4, image_size=(8, 8), shuffle=True, seed=3,
              synthetic=True, num_workers=2)
    full = _loader_batches(DataLoader(m, **kw), 1, 0)
    ff = _loader_batches(DataLoader(m, **kw), 1, start)
    assert len(ff) == len(full) - start
    for (fi, fl), (gi, gl) in zip(full[start:], ff):
        np.testing.assert_array_equal(fi, gi)
        np.testing.assert_array_equal(fl, gl)


def test_fastforward_ram_cache_and_filling_epoch():
    m = _synthetic_manifest(16)
    kw = dict(batch_size=4, image_size=(8, 8), shuffle=True, seed=0,
              synthetic=True, host_cache=True, num_workers=2)
    ref = DataLoader(m, **kw)
    full0 = _loader_batches(ref, 0, 0)
    # Filling epoch with a fast-forward start: the skipped prefix is
    # backfilled, and the yielded tail matches the full walk's tail.
    dl = DataLoader(m, **kw)
    ff0 = _loader_batches(dl, 0, 2)
    for (fi, fl), (gi, gl) in zip(full0[2:], ff0):
        np.testing.assert_array_equal(fi, gi)
        np.testing.assert_array_equal(fl, gl)
    assert dl.wait_cache_complete()
    # Cached epoch (the fast slice path) honors start_batch too.
    full1 = _loader_batches(ref, 1, 0)
    ff1 = _loader_batches(dl, 1, 3)
    for (fi, fl), (gi, gl) in zip(full1[3:], ff1):
        np.testing.assert_array_equal(fi, gi)
        np.testing.assert_array_equal(fl, gl)


def test_fastforward_packed_mmap(tmp_path):
    from mpi_pytorch_tpu.data.packed import write_pack

    m = _synthetic_manifest(16)
    packed_dir = str(tmp_path / "packed")
    write_pack(m, (8, 8), f"{packed_dir}/train_8x8", synthetic=True,
               num_workers=2)
    kw = dict(batch_size=4, image_size=(8, 8), shuffle=True, seed=1,
              synthetic=True, packed_dir=packed_dir, num_workers=2)
    full = _loader_batches(DataLoader(m, **kw), 2, 0)
    ff = _loader_batches(DataLoader(m, **kw), 2, 2)
    for (fi, fl), (gi, gl) in zip(full[2:], ff):
        np.testing.assert_array_equal(fi, gi)
        np.testing.assert_array_equal(fl, gl)


def test_cached_index_batches_fastforward():
    from mpi_pytorch_tpu.train.trainer import cached_index_batches

    cfg = Config(seed=5)
    full = list(cached_index_batches(cfg, 40, 8, epoch=2, n_steps=5))
    ff = list(cached_index_batches(cfg, 40, 8, epoch=2, n_steps=5, start_step=3))
    assert len(ff) == 2
    for (fi, fv), (gi, gv) in zip(full[3:], ff):
        np.testing.assert_array_equal(fi, gi)
        np.testing.assert_array_equal(fv, gv)


# ---------------------------------------------------------------------------
# the data cursor itself
# ---------------------------------------------------------------------------


def test_cursor_roundtrip_and_validation():
    from mpi_pytorch_tpu.train.trainer import data_cursor, validate_cursor

    cfg = Config()
    m = _synthetic_manifest(20)
    fp = manifest_fingerprint(m)
    cur = data_cursor(cfg, fp, 10, next_epoch=3, step_in_epoch=4)
    step, why = validate_cursor(
        cur, cfg=cfg, fingerprint=fp, n_steps=10, start_epoch=3
    )
    assert (step, why) == (4, None)
    # Every invalidation falls back with a reason, never misaligns.
    bad_fp, _ = validate_cursor(
        cur, cfg=cfg, fingerprint="deadbeef", n_steps=10, start_epoch=3
    )[0], None
    assert bad_fp == 0
    assert validate_cursor(
        cur, cfg=cfg, fingerprint=fp, n_steps=10, start_epoch=2
    ) == (0, "cursor epoch=3 != current 2")
    cfg2 = Config(batch_size=64)
    step2, why2 = validate_cursor(
        cur, cfg=cfg2, fingerprint=fp, n_steps=10, start_epoch=3
    )
    assert step2 == 0 and "global_batch" in why2
    assert validate_cursor(None, cfg=cfg, fingerprint=fp, n_steps=10,
                           start_epoch=3)[0] == 0


def test_manifest_fingerprint_is_order_sensitive():
    m = _synthetic_manifest(10)
    same = manifest_fingerprint(_synthetic_manifest(10))
    assert manifest_fingerprint(m) == same
    reordered = m.select(np.arange(9, -1, -1))
    assert manifest_fingerprint(reordered) != same


# ---------------------------------------------------------------------------
# trainer integration: exact-step resume (THE tentpole pin)
# ---------------------------------------------------------------------------


def _train_cfg(tmp_path, **kw) -> Config:
    c = Config()
    c.debug = True
    c.debug_sample_size = 64  # 51 train rows -> 3 steps/epoch at batch 16
    c.train_csv = os.path.join(os.path.dirname(__file__), "..", "data", "train_sample.csv")
    c.test_csv = os.path.join(os.path.dirname(__file__), "..", "data", "test_sample.csv")
    c.synthetic_data = True
    c.model_name = "resnet18"
    c.num_classes = 200
    c.batch_size = 16
    c.width = c.height = 16
    c.num_epochs = 3
    c.compute_dtype = "float32"
    c.checkpoint_dir = os.path.join(str(tmp_path), "ckpt")
    c.log_file = os.path.join(str(tmp_path), "training.log")
    c.metrics_file = os.path.join(str(tmp_path), "metrics.jsonl")
    c.validate = False
    c.loader_workers = 2
    c.log_every_steps = 0
    c.step_metrics = True
    c.resume_backoff_s = 0.0
    for k, v in kw.items():
        setattr(c, k, v)
    c.validate_config()
    return c


def _records(cfg):
    return [json.loads(line) for line in open(cfg.metrics_file) if line.strip()]


def _final_params(ckpt_dir):
    from mpi_pytorch_tpu.train.trainer import build_training

    cfg = Config()  # only used as a template container below
    path = ckpt.latest_checkpoint(ckpt_dir)
    assert path is not None
    from flax import serialization

    with open(path, "rb") as f:
        raw = serialization.msgpack_restore(f.read())
    return path, raw["params"]


def _flat(tree):
    return jax.tree_util.tree_leaves(tree)


def test_exact_step_resume_matches_uninterrupted(tmp_path, clean_gates):
    """Preempt mid-epoch (after step 4 = epoch 1 step 0) → dirty save with
    cursor (1, 1) → resume runs epoch 1 steps 1..2 and epoch 2 — final
    params equal the uninterrupted run's, and NO (epoch, step) pair is
    replayed across the two sessions."""
    from mpi_pytorch_tpu.train.trainer import train

    # Uninterrupted reference.
    ref_cfg = _train_cfg(tmp_path / "ref")
    train(ref_cfg)
    _, ref_params = _final_params(ref_cfg.checkpoint_dir)

    # Interrupted: stop right after the 4th completed step (epoch 1 step 0).
    cfg = _train_cfg(tmp_path / "run")
    os.environ["MPT_FAULT_PREEMPT_AT_STEP"] = "4"
    summary = train(cfg)
    assert summary.preempted
    latest = ckpt.latest_checkpoint(cfg.checkpoint_dir)
    assert os.path.exists(latest + ".dirty")
    manifest = ckpt.read_manifest(latest)
    assert manifest["data_cursor"]["epoch"] == 1
    assert manifest["data_cursor"]["step_in_epoch"] == 1

    os.environ.pop("MPT_FAULT_PREEMPT_AT_STEP")
    done = train(_train_cfg(tmp_path / "run", from_checkpoint=True))
    assert not done.preempted

    log = open(cfg.log_file).read()
    assert "exact-step resume: continuing epoch 1 at step 1" in log

    _, got_params = _final_params(cfg.checkpoint_dir)
    for a, b in zip(_flat(ref_params), _flat(got_params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # Zero replayed steps: across both sessions every (epoch, step) pair
    # appears exactly once, and the resume record carries the cursor.
    records = _records(cfg)
    pairs = [(r["epoch"], r["step"]) for r in records if r["kind"] == "step"]
    assert len(pairs) == len(set(pairs)) == 9, sorted(pairs)
    resume = [r for r in records if r["kind"] == "resume"][-1]
    assert resume["cursor_epoch"] == 1 and resume["cursor_step"] == 1
    from mpi_pytorch_tpu.obs.schema import validate_jsonl

    assert validate_jsonl(cfg.metrics_file) == []


def test_cursor_mismatch_falls_back_to_replay(tmp_path, clean_gates):
    """A tampered fingerprint invalidates the cursor: resume warns (typed
    kind='anomaly' reason='cursor_mismatch'), replays the interrupted epoch
    from step 0, and still completes."""
    from mpi_pytorch_tpu.train.trainer import train

    cfg = _train_cfg(tmp_path)
    os.environ["MPT_FAULT_PREEMPT_AT_STEP"] = "4"
    assert train(cfg).preempted
    os.environ.pop("MPT_FAULT_PREEMPT_AT_STEP")

    latest = ckpt.latest_checkpoint(cfg.checkpoint_dir)
    manifest = ckpt.read_manifest(latest)
    manifest["data_cursor"]["manifest_fingerprint"] = "0" * 16
    ckpt.write_manifest(latest, manifest)

    done = train(_train_cfg(tmp_path, from_checkpoint=True))
    assert not done.preempted
    log = open(cfg.log_file).read()
    assert "exact-step resume unavailable" in log
    mismatches = [
        r for r in _records(cfg)
        if r["kind"] == "anomaly" and r["reason"] == "cursor_mismatch"
    ]
    assert mismatches and "manifest_fingerprint" in mismatches[0]["detail"]
    # The interrupted epoch was REPLAYED: epoch 1 step 0 appears twice.
    pairs = [(r["epoch"], r["step"]) for r in _records(cfg) if r["kind"] == "step"]
    assert pairs.count((1, 0)) == 2


# ---------------------------------------------------------------------------
# bad-step policy: skip
# ---------------------------------------------------------------------------


def _spmd_state_and_step(bad_step_skip):
    import flax.linen as nn
    import optax
    from jax.sharding import Mesh

    from mpi_pytorch_tpu.train.state import TrainState, make_optimizer
    from mpi_pytorch_tpu.train.step import make_spmd_train_step, place_state_on_mesh

    class MLP(nn.Module):
        @nn.compact
        def __call__(self, x, train=False):
            x = x.reshape(x.shape[0], -1)
            return nn.Dense(8, name="head")(nn.relu(nn.Dense(13)(x)))

    model = MLP()
    variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, 8, 3)), train=True)
    state = TrainState.create(
        apply_fn=model.apply, variables=variables,
        tx=make_optimizer(1e-2), rng=jax.random.PRNGKey(1),
    )
    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(8, 1), ("data", "model"))
    state = place_state_on_mesh(state, mesh)
    step = make_spmd_train_step(mesh, jnp.float32, bad_step_skip=bad_step_skip)
    return state, step, mesh


def test_skip_guard_keeps_params_bit_identical():
    from mpi_pytorch_tpu.parallel.mesh import shard_batch

    state, step, mesh = _spmd_state_and_step(bad_step_skip=True)
    rng = np.random.default_rng(0)
    clean = (rng.normal(size=(16, 8, 8, 3)).astype(np.float32),
             (np.arange(16) % 8).astype(np.int32))
    poisoned = (np.full((16, 8, 8, 3), np.nan, np.float32), clean[1])

    before = [np.asarray(x) for x in _flat(jax.device_get(state.params))]
    before_opt = [np.asarray(x) for x in _flat(jax.device_get(state.opt_state))]
    state, m = step(state, shard_batch(poisoned, mesh))
    assert int(m["skipped"]) == 1
    assert not np.isfinite(float(m["loss"]))
    after = [np.asarray(x) for x in _flat(jax.device_get(state.params))]
    after_opt = [np.asarray(x) for x in _flat(jax.device_get(state.opt_state))]
    for a, b in zip(before, after):
        np.testing.assert_array_equal(a, b)  # bit-identical
    for a, b in zip(before_opt, after_opt):
        np.testing.assert_array_equal(a, b)
    assert int(jax.device_get(state.step)) == 0  # the update never happened

    # Training continues: the next clean step commits normally.
    state, m = step(state, shard_batch(clean, mesh))
    assert int(m["skipped"]) == 0
    assert np.isfinite(float(m["loss"]))
    assert int(jax.device_get(state.step)) == 1
    changed = any(
        not np.array_equal(a, np.asarray(b))
        for a, b in zip(before, _flat(jax.device_get(state.params)))
    )
    assert changed


def test_skip_guard_inside_scanned_epoch():
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import flax.linen as nn

    from mpi_pytorch_tpu.train.state import TrainState, make_optimizer
    from mpi_pytorch_tpu.train.step import make_scanned_epoch, place_state_on_mesh

    class MLP(nn.Module):
        @nn.compact
        def __call__(self, x, train=False):
            return nn.Dense(8)(x.reshape(x.shape[0], -1))

    model = MLP()
    variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4, 4, 3)), train=True)
    state = TrainState.create(
        apply_fn=model.apply, variables=variables,
        tx=make_optimizer(1e-2), rng=jax.random.PRNGKey(1),
    )
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    state = place_state_on_mesh(state, mesh)
    rep = NamedSharding(mesh, P())
    rng = np.random.default_rng(0)
    dataset = rng.normal(size=(24, 4, 4, 3)).astype(np.float32)
    dataset[8:16] = np.nan  # the middle scan step gathers only NaN rows
    dataset = jax.device_put(dataset, rep)
    labels = jax.device_put((np.arange(24) % 8).astype(np.int32), rep)
    idx_all = np.arange(24, dtype=np.int32).reshape(3, 8)
    valid_all = np.ones((3, 8), bool)
    epoch_fn = make_scanned_epoch(mesh, jnp.float32, bad_step_skip=True)
    state, m = epoch_fn(state, dataset, labels, idx_all, valid_all)
    np.testing.assert_array_equal(np.asarray(m["skipped"]), [0, 1, 0])
    # The scan carried the pre-step state through the bad step: params stay
    # finite and two updates committed.
    assert int(jax.device_get(state.step)) == 2
    for leaf in _flat(jax.device_get(state.params)):
        assert np.isfinite(np.asarray(leaf)).all()


def test_skip_policy_trainer_survives_injected_nonfinite(tmp_path, clean_gates):
    from mpi_pytorch_tpu.train.trainer import train

    cfg = _train_cfg(tmp_path, bad_step_policy="skip", num_epochs=2)
    os.environ["MPT_FAULT_NONFINITE_AT_STEP"] = "2"
    summary = train(cfg)
    assert summary.epochs_run == 2
    records = _records(cfg)
    skipped = [r for r in records if r["kind"] == "step" and r.get("skipped")]
    assert len(skipped) == 1 and skipped[0]["steps_skipped"] == 1
    assert (skipped[0]["epoch"], skipped[0]["step"]) == (0, 1)
    faults = [r for r in records if r["kind"] == "fault"]
    assert any(f["reason"] == "injected_nonfinite" for f in faults)
    # The injection is announced BEFORE the poisoned step's record.
    fault_ts = [f["ts"] for f in faults if f["reason"] == "injected_nonfinite"][0]
    assert fault_ts <= skipped[0]["ts"]
    # Epoch accounting masked the skipped step: the epoch loss is finite.
    epoch0 = [r for r in records if r["kind"] == "epoch" and r["epoch"] == 0][0]
    assert np.isfinite(epoch0["loss"])
    from mpi_pytorch_tpu.obs.schema import validate_jsonl

    assert validate_jsonl(cfg.metrics_file) == []


def test_skip_policy_aborts_at_limit(tmp_path, clean_gates):
    from mpi_pytorch_tpu.obs.health import NonFiniteLossError
    from mpi_pytorch_tpu.train.trainer import train

    cfg = _train_cfg(
        tmp_path, bad_step_policy="skip", max_skipped_steps=1, num_epochs=2
    )
    os.environ["MPT_FAULT_NONFINITE_AT_STEP"] = "2"
    with pytest.raises(NonFiniteLossError, match="max-skipped-steps"):
        train(cfg)
    assert any(
        r["kind"] == "anomaly" and r["reason"] == "skip_limit"
        for r in _records(cfg)
    )


# ---------------------------------------------------------------------------
# bad-step policy: rollback
# ---------------------------------------------------------------------------


def test_rollback_policy_observe_streak_and_drift():
    p = elastic.RollbackPolicy(nonfinite_steps=2, loss_drift=3.0, drift_warmup=2)
    assert p.observe(1.0, 1.0) is None  # warmup 1
    assert p.observe(1.0, 1.0) is None  # warmup 2 (baseline = 1.0)
    assert p.observe(float("nan"), 1.0) is None  # streak 1 of 2
    assert p.observe(2.0, float("inf")) == "nonfinite_streak"  # streak 2
    p.after_rollback()
    assert p.nonfinite_streak == 0
    assert p.observe(2.9, 1.0) is None  # 2.9x baseline: under 3.0
    assert p.observe(3.5, 1.0) == "loss_drift"


def test_rollback_trainer_restores_in_process(tmp_path, clean_gates):
    """NaN injected mid-epoch 1 under rollback policy: ONE kind='rollback'
    record, the run restores epoch 0's checkpoint WITHOUT exiting, re-runs
    epoch 1 cleanly, and completes all epochs — spmd+ZeRO, so the restore
    exercises the unsharded-template path."""
    from mpi_pytorch_tpu.train.trainer import train

    cfg = _train_cfg(
        tmp_path, bad_step_policy="rollback", rollback_nonfinite_steps=1,
        num_epochs=3, spmd_mode=True, zero_opt_state=True,
    )
    os.environ["MPT_FAULT_NONFINITE_AT_STEP"] = "5"  # epoch 1 step 1
    summary = train(cfg)
    assert summary.epochs_run >= 3  # epoch 1 ran twice; all epochs completed
    records = _records(cfg)
    rollbacks = [r for r in records if r["kind"] == "rollback"]
    assert len(rollbacks) == 1, rollbacks
    rb = rollbacks[0]
    assert rb["reason"] == "nonfinite_streak"
    assert (rb["epoch"], rb["step"]) == (1, 1)
    assert rb["restored_epoch"] == 0 and rb["rollbacks"] == 1
    # The in-process restore wrote a resume record; the run never exited.
    assert any(r["kind"] == "resume" for r in records)
    epochs = {r["epoch"] for r in records if r["kind"] == "epoch"}
    assert epochs == {0, 1, 2}
    from mpi_pytorch_tpu.obs.schema import validate_jsonl

    assert validate_jsonl(cfg.metrics_file) == []


def test_rollback_without_checkpoint_aborts(tmp_path, clean_gates):
    from mpi_pytorch_tpu.train.trainer import train

    cfg = _train_cfg(
        tmp_path, bad_step_policy="rollback", rollback_nonfinite_steps=1,
    )
    os.environ["MPT_FAULT_NONFINITE_AT_STEP"] = "1"  # before any checkpoint
    with pytest.raises(elastic.RollbackLimitError, match="no checkpoint"):
        train(cfg)


def test_rollback_lr_backoff_scales_and_records(tmp_path, clean_gates):
    from mpi_pytorch_tpu.train.trainer import train

    cfg = _train_cfg(
        tmp_path, bad_step_policy="rollback", rollback_nonfinite_steps=1,
        rollback_lr_backoff=0.5, num_epochs=3,
    )
    os.environ["MPT_FAULT_NONFINITE_AT_STEP"] = "5"
    summary = train(cfg)
    assert summary.epochs_run >= 3
    rb = [r for r in _records(cfg) if r["kind"] == "rollback"][0]
    assert rb["lr_scale"] == 0.5


# ---------------------------------------------------------------------------
# decode-failure quarantine (data/pipeline.py satellite)
# ---------------------------------------------------------------------------


def test_decode_failure_retries_then_quarantines(tmp_path, clean_gates):
    m = _synthetic_manifest(12)
    dl = DataLoader(
        m, batch_size=4, image_size=(8, 8), shuffle=False, synthetic=True,
        num_workers=2, decode_retries=2, decode_retry_backoff_s=0.0,
        quarantine_file=str(tmp_path / "quarantine.txt"),
    )
    dl.metrics = FakeMetrics()
    # One poisoned sample: every attempt (1 original + 2 retries) fails,
    # so exactly ONE sample exhausts its retries and is quarantined.
    os.environ["MPT_FAULT_DECODE_N"] = "1"
    reset_fault_counters()
    batches = list(dl.epoch(0))
    assert dl.bad_samples == 1
    labels = np.concatenate([l for _, l in batches])
    assert (labels == -1).sum() == 1
    anomalies = [r for r in dl.metrics.records if r["kind"] == "anomaly"]
    assert len(anomalies) == 1
    assert anomalies[0]["reason"] == "bad_sample"
    assert "injected decode failure" in anomalies[0]["detail"]
    quarantine = open(tmp_path / "quarantine.txt").read()
    assert anomalies[0]["path"] in quarantine
    # Later epochs keep the row masked (one -1 label per epoch).
    labels1 = np.concatenate([l for _, l in dl.epoch(1)])
    assert (labels1 == -1).sum() == 1


def test_decode_failure_budget_aborts_loudly(tmp_path, clean_gates):
    m = _synthetic_manifest(12)
    dl = DataLoader(
        m, batch_size=4, image_size=(8, 8), shuffle=False, synthetic=True,
        num_workers=1, decode_retries=0, decode_retry_backoff_s=0.0,
        max_bad_samples=1,
    )
    os.environ["MPT_FAULT_DECODE_N"] = "2"  # two poisoned samples -> budget blown
    reset_fault_counters()
    with pytest.raises(BadSampleLimitError, match="max_bad_samples"):
        for _ in dl.epoch(0):
            pass


def test_trainer_quarantine_writes_anomaly_records(tmp_path, clean_gates):
    from mpi_pytorch_tpu.train.trainer import train

    cfg = _train_cfg(tmp_path, num_epochs=1,
                     quarantine_file=str(tmp_path / "q.txt"))
    os.environ["MPT_FAULT_DECODE_N"] = "1"  # one poisoned sample -> 1 quarantine
    summary = train(cfg)
    assert summary.epochs_run == 1
    bad = [
        r for r in _records(cfg)
        if r["kind"] == "anomaly" and r["reason"] == "bad_sample"
    ]
    assert len(bad) == 1 and bad[0]["path"]
    assert os.path.exists(tmp_path / "q.txt")
    from mpi_pytorch_tpu.obs.schema import validate_jsonl

    assert validate_jsonl(cfg.metrics_file) == []


# ---------------------------------------------------------------------------
# gates, config, rendering
# ---------------------------------------------------------------------------


def test_new_gates_registered_and_in_fault_env():
    from tools.inject_faults import fault_env

    for gate in (
        "MPT_FAULT_NONFINITE_AT_STEP",
        "MPT_FAULT_DECODE_N",
        "MPT_FAULT_PREEMPT_AT_STEP",
    ):
        assert gate in FAULT_GATES
    env = fault_env(nonfinite_at_step=3, decode_fail=2, preempt_at_step=7)
    assert env["MPT_FAULT_NONFINITE_AT_STEP"] == "3"
    assert env["MPT_FAULT_DECODE_N"] == "2"
    assert env["MPT_FAULT_PREEMPT_AT_STEP"] == "7"


def test_config_validates_selfheal_knobs():
    with pytest.raises(ValueError, match="bad_step_policy"):
        Config(bad_step_policy="retry").validate_config()
    with pytest.raises(ValueError, match="max_skipped_steps"):
        Config(max_skipped_steps=0).validate_config()
    with pytest.raises(ValueError, match="rollback_loss_drift"):
        Config(rollback_loss_drift=0.5).validate_config()
    with pytest.raises(ValueError, match="rollback_lr_backoff"):
        Config(rollback_lr_backoff=0.0).validate_config()
    with pytest.raises(ValueError, match="scan_epoch"):
        Config(
            bad_step_policy="rollback", device_cache=True, scan_epoch=True
        ).validate_config()
    with pytest.raises(ValueError, match="max_bad_samples"):
        Config(max_bad_samples=-1).validate_config()
    Config(
        bad_step_policy="rollback", rollback_loss_drift=2.0,
        rollback_lr_backoff=0.5,
    ).validate_config()
    Config(bad_step_policy="skip", max_skipped_steps=3).validate_config()


def test_schema_v6_records_validate():
    from mpi_pytorch_tpu.obs.schema import validate_record

    assert validate_record({
        "ts": 1.0, "kind": "rollback", "epoch": 2, "reason": "nonfinite_streak",
        "step": 3, "restored_epoch": 1, "rollbacks": 1, "lr_scale": 0.5,
        "path": "ckpt/ckpt_00001.msgpack",
    }) == []
    assert validate_record({
        "ts": 1.0, "kind": "step", "epoch": 0, "step": 1, "loss": float("nan"),
        "skipped": 1, "steps_skipped": 4,
    }) == []
    assert validate_record({
        "ts": 1.0, "kind": "resume", "epoch": 1, "to_devices": 8,
        "cursor_epoch": 2, "cursor_step": 3,
    }) == []
    assert validate_record({
        "ts": 1.0, "kind": "anomaly", "reason": "bad_sample", "epoch": 0,
        "path": "img/x.jpg", "detail": "truncated",
    }) == []
    assert validate_record({"ts": 1.0, "kind": "rollback", "epoch": 1}) != []


def test_report_run_renders_rollback_and_skips(tmp_path, capsys):
    from tools import report_run

    path = tmp_path / "m.jsonl"
    records = [
        {"ts": 1.0, "kind": "step", "epoch": 0, "step": 0, "loss": 1.0,
         "skipped": 0, "steps_skipped": 0},
        {"ts": 2.0, "kind": "step", "epoch": 0, "step": 1,
         "loss": float("nan"), "skipped": 1, "steps_skipped": 1},
        {"ts": 3.0, "kind": "rollback", "epoch": 2, "step": 1,
         "reason": "loss_drift", "restored_epoch": 1, "rollbacks": 1,
         "lr_scale": 0.5, "path": "ckpt/ckpt_00001.msgpack"},
    ]
    path.write_text("".join(json.dumps(r) + "\n" for r in records))
    assert report_run.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert "skipped steps (bad-step policy): 1 discarded, longest streak 1" in out
    assert "ROLLBACK: #1 — loss_drift at epoch 2 step 1 → restored epoch 1" in out
    assert "LR scaled to 0.5x" in out


# ---------------------------------------------------------------------------
# cross-mesh exact-step continuity (8 -> 4 devices; subprocess, slow)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_cross_mesh_exact_step_resume(tmp_path):
    """Mid-epoch preempt on an 8-device mesh, resume on 4: the cursor lives
    in global-sample space, so the fast-forward continues at the same
    global step with the same batches — no replayed (epoch, step) pairs."""
    import subprocess
    import sys

    from tools.inject_faults import fault_env

    REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    args = [
        sys.executable, "-m", "mpi_pytorch_tpu.train",
        "--debug", "true", "--debug-sample-size", "64", "--num-classes", "200",
        "--batch-size", "16", "--width", "16", "--height", "16",
        "--synthetic-data", "true", "--validate", "false",
        "--compute-dtype", "float32", "--loader-workers", "2",
        "--log-every-steps", "0", "--step-metrics", "true",
        "--num-epochs", "3", "--checkpoint-every-epochs", "1",
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

    subprocess.run(
        args, env=env_for(8, preempt_at_step=4), cwd=REPO, check=True,
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )
    subprocess.run(
        args + ["--from-checkpoint", "true"], env=env_for(4), cwd=REPO,
        check=True, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )
    records = [
        json.loads(line) for line in open(tmp_path / "metrics.jsonl")
        if line.strip()
    ]
    pairs = [(r["epoch"], r["step"]) for r in records if r["kind"] == "step"]
    assert len(pairs) == len(set(pairs)) == 9, sorted(pairs)
    resume = [r for r in records if r["kind"] == "resume"][-1]
    assert resume["from_devices"] == 8 and resume["to_devices"] == 4
    assert resume["cursor_epoch"] == 1 and resume["cursor_step"] == 1
    assert {r["epoch"] for r in records if r["kind"] == "epoch"} == {0, 1, 2}
