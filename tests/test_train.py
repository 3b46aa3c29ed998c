"""Integration tests (SURVEY §4 item 3): tiny synthetic run — loss decreases,
checkpoint round-trips, resume continues, eval matches a plain forward."""

import os

import jax
import numpy as np
import pytest

from mpi_pytorch_tpu.config import Config
from mpi_pytorch_tpu.train.trainer import train
from mpi_pytorch_tpu.evaluate import evaluate


def _tiny_cfg(tmpdir, **kw) -> Config:
    cfg = Config()
    cfg.debug = True
    cfg.debug_sample_size = 128
    cfg.test_csv = "/root/repo/data/test_sample.csv"
    cfg.train_csv = "/root/repo/data/train_sample.csv"
    cfg.synthetic_data = True
    cfg.model_name = "resnet18"
    cfg.num_classes = 64500  # raw category_id labels, reference head size
    cfg.batch_size = 32
    cfg.width = cfg.height = 32
    cfg.num_epochs = 2
    cfg.compute_dtype = "float32"
    cfg.checkpoint_dir = os.path.join(tmpdir, "ckpt")
    cfg.log_file = os.path.join(tmpdir, "training.log")
    cfg.validate = False
    cfg.loader_workers = 2
    cfg.log_every_steps = 0
    for k, v in kw.items():
        setattr(cfg, k, v)
    cfg.validate_config()
    return cfg


@pytest.mark.slow
@pytest.mark.parametrize("spmd", [False, True])
def test_loss_decreases(tmp_path, spmd):
    cfg = _tiny_cfg(str(tmp_path), num_epochs=3, spmd_mode=spmd,
                    learning_rate=1e-3, num_classes=200)
    summary = train(cfg)
    assert summary.epochs_run == 3
    assert summary.epoch_losses[-1] < summary.epoch_losses[0]
    assert os.path.exists(cfg.log_file)


@pytest.mark.slow
def test_checkpoint_resume(tmp_path):
    # num_classes=200 (not the full 64500) keeps the XLA CPU compile cheap;
    # raw-category-id label handling is covered by test_data.test_labels_fit_head.
    cfg = _tiny_cfg(str(tmp_path), num_epochs=1, num_classes=200)
    s1 = train(cfg)
    assert s1.checkpoint_path and os.path.exists(s1.checkpoint_path)

    # resume: epoch counter continues (helpers.py:10-15 semantics)
    cfg2 = _tiny_cfg(str(tmp_path), num_epochs=2, from_checkpoint=True, num_classes=200)
    s2 = train(cfg2)
    assert s2.epochs_run == 1  # only epoch 1 remains
    assert "00001" in s2.checkpoint_path


@pytest.mark.slow
def test_validation_runs_on_train_split(tmp_path):
    cfg = _tiny_cfg(str(tmp_path), num_epochs=1, validate=True, num_classes=150,
                    debug_sample_size=96)
    summary = train(cfg)
    assert summary.val_accuracy is not None
    assert 0.0 <= summary.val_accuracy <= 1.0


@pytest.mark.slow
def test_eval_pipeline_matches_direct_forward(tmp_path):
    """The collapsed 4-stage pipeline reports the same accuracy a direct
    batched forward gives (SURVEY §4 item 3 'eval pipeline produces the same
    accuracy as a plain batched forward'): one un-sharded, un-padded
    ``model.apply`` over the whole test manifest, accuracy in plain numpy."""
    import jax.numpy as jnp

    from mpi_pytorch_tpu import checkpoint as ckpt
    from mpi_pytorch_tpu.data import DataLoader
    from mpi_pytorch_tpu.evaluate import build_inference

    cfg = _tiny_cfg(str(tmp_path), num_epochs=1, num_classes=200, debug_sample_size=160)
    train(cfg)
    res = evaluate(cfg)
    assert res.num_images == 32  # 20% of 160

    mesh, bundle, state, test_manifest = build_inference(cfg)
    latest = ckpt.latest_checkpoint(cfg.checkpoint_dir)
    assert latest is not None
    state, _, _ = ckpt.load_for_eval(latest, state)
    loader = DataLoader(
        test_manifest, batch_size=len(test_manifest), image_size=cfg.image_size,
        shuffle=False, drop_remainder=False, synthetic=True, num_workers=2,
    )
    images, labels = next(iter(loader.epoch(0)))
    logits = state.apply_fn(state.variables, jnp.asarray(images), train=False)
    direct_acc = float(np.mean(np.argmax(np.asarray(logits), axis=-1) == labels))
    assert res.accuracy == pytest.approx(direct_acc, abs=1e-9)


def test_async_checkpointer_roundtrip(tmp_path):
    """AsyncCheckpointer: snapshot-then-background-write lands an atomic,
    loadable checkpoint; the snapshot is decoupled from the live state (the
    train loop donates those buffers into the next step)."""
    import jax.numpy as jnp
    import optax

    from flax import linen as nn

    from mpi_pytorch_tpu import checkpoint as ckpt
    from mpi_pytorch_tpu.train.state import TrainState

    class M(nn.Module):
        @nn.compact
        def __call__(self, x, train=False):
            return nn.Dense(4)(x)

    model = M()
    variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8)))
    state = TrainState.create(
        apply_fn=model.apply, variables=variables, tx=optax.adam(1e-3),
        rng=jax.random.PRNGKey(1),
    )
    cp = ckpt.AsyncCheckpointer()
    path = cp.save(str(tmp_path), epoch=3, state=state, loss=1.5, keep=2)
    cp.wait()
    assert path and os.path.exists(path)
    assert ckpt.latest_checkpoint(str(tmp_path)) == path

    template = TrainState.create(
        apply_fn=model.apply,
        variables=model.init(jax.random.PRNGKey(9), jnp.zeros((1, 8))),
        tx=optax.adam(1e-3), rng=jax.random.PRNGKey(2),
    )
    restored, epoch, loss = ckpt.load_checkpoint(path, template)
    assert (epoch, loss) == (3, 1.5)
    for a, b in zip(
        jax.tree_util.tree_leaves(state.params),
        jax.tree_util.tree_leaves(restored.params),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_bf16_moments_checkpoint_roundtrip(tmp_path):
    """moments_bf16 snapshots: params restore EXACTLY, big moment tensors
    restore as f32 values quantized to bf16, small/integer optimizer leaves
    (Adam count) stay exact, and the file actually shrinks."""
    import jax.numpy as jnp
    import optax
    from flax import linen as nn

    from mpi_pytorch_tpu import checkpoint as ckpt
    from mpi_pytorch_tpu.train.state import TrainState

    class M(nn.Module):
        @nn.compact
        def __call__(self, x, train=False):
            return nn.Dense(4)(nn.Dense(2048)(x))

    model = M()

    def fresh(seed):
        return TrainState.create(
            apply_fn=model.apply,
            variables=model.init(jax.random.PRNGKey(seed), jnp.zeros((1, 8))),
            tx=optax.adam(1e-3), rng=jax.random.PRNGKey(seed + 1),
        )

    state = fresh(0)
    # Take one real optimizer step so the moments are non-zero (a zero
    # moment would trivially be bf16-exact and prove nothing).
    grads = jax.tree_util.tree_map(
        lambda p: jnp.asarray(np.random.default_rng(0).normal(size=p.shape), p.dtype),
        state.params,
    )

    def step(st, grads):
        updates, opt_state = st.tx.update(grads, st.opt_state, st.params)
        return st.replace(
            step=st.step + 1,
            params=optax.apply_updates(st.params, updates),
            opt_state=opt_state,
        )

    state = step(state, grads)

    cp = ckpt.AsyncCheckpointer()
    exact = cp.save(str(tmp_path / "exact"), epoch=0, state=state, loss=1.0)
    cp.wait()
    lossy = cp.save(
        str(tmp_path / "bf16"), epoch=0, state=state, loss=1.0, moments_bf16=True
    )
    cp.wait()
    assert os.path.getsize(lossy) < 0.75 * os.path.getsize(exact)

    restored, _, _ = ckpt.load_checkpoint(lossy, fresh(9))
    for a, b in zip(
        jax.tree_util.tree_leaves(state.params),
        jax.tree_util.tree_leaves(restored.params),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # Restored moments: f32 dtype (the optimizer's), values == bf16(quantized).
    for a, b in zip(
        jax.tree_util.tree_leaves(state.opt_state),
        jax.tree_util.tree_leaves(restored.opt_state),
    ):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype, (a.dtype, b.dtype)
        if a.dtype == np.float32 and a.size >= 4096:
            np.testing.assert_array_equal(
                a.astype(jnp.bfloat16).astype(np.float32), b
            )
        else:  # count / small leaves: exact
            np.testing.assert_array_equal(a, b)
    # The restored state steps (dtype-clean for the optimizer).
    step(restored, grads)


def test_dirty_checkpoint_marker_and_resume_warning(tmp_path):
    """A mid-epoch preemption save is marked dirty (sidecar): resume warns
    that the replayed epoch double-applies the partial epoch's updates, a
    clean overwrite of the same epoch clears the marker, and last-k cleanup
    removes markers with their checkpoints."""
    import logging

    import jax.numpy as jnp
    import optax
    from flax import linen as nn

    from mpi_pytorch_tpu import checkpoint as ckpt
    from mpi_pytorch_tpu.train.state import TrainState
    from mpi_pytorch_tpu.utils.logging import run_logger

    class M(nn.Module):
        @nn.compact
        def __call__(self, x, train=False):
            return nn.Dense(4)(x)

    model = M()
    state = TrainState.create(
        apply_fn=model.apply,
        variables=model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8))),
        tx=optax.adam(1e-3), rng=jax.random.PRNGKey(1),
    )
    cp = ckpt.AsyncCheckpointer()
    path = cp.save(str(tmp_path), epoch=5, state=state, loss=1.0, dirty=True)
    cp.wait()
    assert os.path.exists(path + ".dirty")

    # Capture from the rank-tagged run logger itself: it is the logger the
    # trainer configures (propagate=False), so the warning must land THERE
    # to be visible in real runs' stream/file handlers.
    records = []
    handler = logging.Handler()
    handler.emit = records.append
    logger = run_logger()
    logger.addHandler(handler)
    try:
        ckpt.load_checkpoint(path, state)
        assert any("DIRTY" in r.getMessage() for r in records)

        # A clean save of the same epoch (the resumed run re-finishing it)
        # clears the marker, and a clean load stays silent.
        cp.save(str(tmp_path), epoch=5, state=state, loss=0.9)
        cp.wait()
        assert not os.path.exists(path + ".dirty")
        records.clear()
        ckpt.load_checkpoint(path, state)
        assert not records
    finally:
        logger.removeHandler(handler)

    # Markers ride last-k retention: evicting the checkpoint evicts its
    # sidecar too.
    p6 = ckpt.save_checkpoint(str(tmp_path), epoch=6, state=state, loss=0.8,
                              dirty=True)
    assert os.path.exists(p6 + ".dirty")
    ckpt.save_checkpoint(str(tmp_path), epoch=7, state=state, loss=0.7, keep=1)
    assert not os.path.exists(p6) and not os.path.exists(p6 + ".dirty")


@pytest.mark.slow
def test_device_cache_matches_streaming(tmp_path):
    """device_cache=True (HBM-resident dataset, on-device index gather) walks
    the data in the same order as the streaming loader and must produce the
    same loss trajectory — including a padded tail step (102 images, batch 32
    → 6-row tail)."""
    cfg_a = _tiny_cfg(
        os.path.join(str(tmp_path), "a"), num_epochs=2, num_classes=200,
        debug_sample_size=128, drop_remainder=False,
    )
    sa = train(cfg_a)
    cfg_b = _tiny_cfg(
        os.path.join(str(tmp_path), "b"), num_epochs=2, num_classes=200,
        debug_sample_size=128, drop_remainder=False, device_cache=True,
    )
    sb = train(cfg_b)
    np.testing.assert_allclose(sa.epoch_losses, sb.epoch_losses, rtol=1e-4)


@pytest.mark.slow
def test_device_cache_rows_sharded_not_replicated(tmp_path):
    """The device cache shards rows over the data axis: each of the 8
    devices holds ceil(N/8) rows — per-device HBM ≈ dataset/n, not a full
    replica per chip — and the padded tail rows sit past the real count."""
    from mpi_pytorch_tpu.train.trainer import build_device_cache, build_training

    cfg = _tiny_cfg(str(tmp_path), num_classes=200, debug_sample_size=102,
                    device_cache=True)
    mesh, _, _, (train_manifest, _, loader) = build_training(cfg)
    dataset, labels = build_device_cache(cfg, train_manifest, loader, mesh)
    n = len(train_manifest)
    per_dev = -(-n // 8)
    assert dataset.shape[0] == per_dev * 8  # padded to divisibility
    assert int(labels.shape[0]) == n  # labels stay real-length (and replicated)
    for shard in dataset.addressable_shards:
        assert shard.data.shape[0] == per_dev, shard.data.shape
    # Distinct rows per device (sharded), not 8 copies of everything.
    assert len({shard.index[0].start for shard in dataset.addressable_shards}) == 8


@pytest.mark.slow
def test_host_cache_matches_streaming(tmp_path):
    """host_cache=True (decode the shard once into host RAM, slice epochs)
    must reproduce the streaming loss trajectory and validation accuracy —
    same (seed, epoch) walk, same padding semantics."""
    kw = dict(num_epochs=2, num_classes=200, debug_sample_size=128,
              drop_remainder=False, validate=True)
    sa = train(_tiny_cfg(os.path.join(str(tmp_path), "a"), **kw))
    sb = train(_tiny_cfg(os.path.join(str(tmp_path), "b"), **kw, host_cache=True))
    np.testing.assert_allclose(sa.epoch_losses, sb.epoch_losses, rtol=1e-4)
    assert sa.val_accuracy == sb.val_accuracy


def test_host_and_device_cache_exclusive():
    with pytest.raises(ValueError, match="host_cache and device_cache"):
        Config(host_cache=True, device_cache=True).validate_config()


@pytest.mark.slow
def test_scan_epoch_matches_per_step_cache(tmp_path):
    """scan_epoch=True (the whole epoch as ONE compiled lax.scan over the
    device cache) must reproduce the per-step cached trajectory — same
    (seed, epoch) batch order, same padded tail handling, one dispatch."""
    cfg_a = _tiny_cfg(
        os.path.join(str(tmp_path), "a"), num_epochs=2, num_classes=200,
        debug_sample_size=96, drop_remainder=False, device_cache=True,
    )
    sa = train(cfg_a)
    cfg_b = _tiny_cfg(
        os.path.join(str(tmp_path), "b"), num_epochs=2, num_classes=200,
        debug_sample_size=96, drop_remainder=False, device_cache=True,
        scan_epoch=True,
    )
    sb = train(cfg_b)
    # The scan body is compiled (and fused) separately from the unrolled
    # step, so f32 reassociation drifts the trajectory slightly as updates
    # compound across an epoch: first epoch agrees to ~1e-5 relative, later
    # epochs to ~1e-3. Assert trajectory-level equivalence.
    np.testing.assert_allclose(sa.epoch_losses[:1], sb.epoch_losses[:1], rtol=1e-4)
    np.testing.assert_allclose(sa.epoch_losses, sb.epoch_losses, rtol=5e-3)


def test_step_metrics_names_what_the_step_itself_reports():
    """``STEP_METRICS`` is the set the trainer folds into loss and counts
    itself; every other key a step reports joins the epoch record under its
    own name — so the set must be exactly what the step's own code emits."""
    import jax.numpy as jnp

    from mpi_pytorch_tpu.train.step import STEP_METRICS, _step_metrics, _with_skip_flag

    grads = {"w": jnp.ones(3)}
    images = _step_metrics(jnp.float32(1.0), jnp.zeros((4, 3)), jnp.array([0, 1, 2, -1]), grads)
    assert set(_with_skip_flag(images, jnp.bool_(True))) == set(STEP_METRICS)
    tokens = _step_metrics(
        jnp.float32(1.0), jnp.zeros((2, 5, 3)), jnp.array([[0, 1, 2, -1, -1]] * 2), grads,
        {"moe_load_max": jnp.int32(3)},
    )
    assert set(tokens) - set(STEP_METRICS) == {"tokens", "moe_load_max"}
    assert int(tokens["tokens"]) == 6 and int(tokens["count"]) == 2


def test_scan_epoch_requires_device_cache():
    with pytest.raises(ValueError, match="scan_epoch"):
        Config(scan_epoch=True).validate_config()


def _mlp_state(rng_seed=0, num_classes=11, image=8):
    """A BN-free, dropout-free model so accumulation/remat equivalence can be
    asserted exactly (no per-microbatch stats, no rng-shape dependence)."""
    import flax.linen as nn
    import jax.numpy as jnp
    from mpi_pytorch_tpu.train.state import TrainState, make_optimizer

    class MLP(nn.Module):
        @nn.compact
        def __call__(self, x, train=False):
            x = x.reshape((x.shape[0], -1))
            x = nn.relu(nn.Dense(32)(x))
            return nn.Dense(num_classes)(x)

    model = MLP()
    variables = model.init(jax.random.PRNGKey(rng_seed), jnp.zeros((1, image, image, 3)))
    return TrainState.create(
        apply_fn=model.apply, variables=variables, tx=make_optimizer(1e-3),
        rng=jax.random.PRNGKey(rng_seed + 1),
    )


def test_grad_accumulation_matches_full_batch():
    """accum_steps=k (count-weighted microbatch grads, one optimizer update)
    must equal the unsplit big-batch step — including when padded (-1) rows
    land unevenly across microbatches."""
    import jax.numpy as jnp
    from mpi_pytorch_tpu.config import MeshConfig
    from mpi_pytorch_tpu.parallel.mesh import create_mesh, shard_batch
    from mpi_pytorch_tpu.train.step import make_train_step, place_state_on_mesh

    mesh = create_mesh(MeshConfig())
    rng = np.random.default_rng(0)
    batch = 32
    images = rng.standard_normal((batch, 8, 8, 3)).astype(np.float32)
    labels = rng.integers(0, 11, size=(batch,)).astype(np.int32)
    labels[5:11] = -1  # padding rows, unevenly placed across 4 microbatches

    outs = {}
    for k in (1, 4):
        state = place_state_on_mesh(_mlp_state(), mesh)
        step = make_train_step(jnp.float32, accum_steps=k, mesh=mesh)
        new_state, m = step(state, shard_batch((images, labels), mesh))
        outs[k] = (new_state.params, m)
    p1, m1 = outs[1]
    p4, m4 = outs[4]
    assert int(m1["count"]) == int(m4["count"]) == batch - 6
    assert int(m1["correct"]) == int(m4["correct"])
    np.testing.assert_allclose(float(m1["loss"]), float(m4["loss"]), rtol=1e-6)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=2e-6, atol=2e-7), p1, p4
    )


@pytest.mark.slow
@pytest.mark.parametrize("strategy", ["full", "blocks"])
def test_remat_matches_plain_step(tmp_path, strategy):
    """Rematerialization (whole-forward jax.checkpoint, or per-residual-block
    nn.remat) only changes WHEN activations are computed, not what — the loss
    trajectory must match the plain step."""
    cfg_a = _tiny_cfg(os.path.join(str(tmp_path), "a"), num_epochs=2, num_classes=200)
    sa = train(cfg_a)
    cfg_b = _tiny_cfg(
        os.path.join(str(tmp_path), "b"), num_epochs=2, num_classes=200, remat=strategy
    )
    sb = train(cfg_b)
    np.testing.assert_allclose(sa.epoch_losses, sb.epoch_losses, rtol=1e-4)


@pytest.mark.slow
def test_remat_blocks_param_tree_unchanged():
    """nn.remat must not change parameter paths — checkpoints and the
    torchvision converter depend on them."""
    from mpi_pytorch_tpu.models import create_model_bundle

    _, plain = create_model_bundle("resnet18", 10, image_size=32)
    _, blocks = create_model_bundle("resnet18", 10, image_size=32, remat_blocks=True)
    assert jax.tree_util.tree_structure(plain) == jax.tree_util.tree_structure(blocks)


@pytest.mark.slow
def test_cached_eval_matches_streaming_eval(tmp_path):
    """evaluate_cached (HBM-resident val set) must agree with
    evaluate_manifest (streaming decode) — same masking, same accounting."""
    from mpi_pytorch_tpu.train.trainer import (
        build_device_cache,
        build_training,
        evaluate_cached,
        evaluate_manifest,
    )
    from mpi_pytorch_tpu.train.step import place_state_on_mesh

    cfg = _tiny_cfg(str(tmp_path), num_classes=200, debug_sample_size=96, batch_size=32)
    mesh, bundle, state, (train_manifest, _, loader) = build_training(cfg)
    state = place_state_on_mesh(state, mesh)
    dataset, labels = build_device_cache(cfg, train_manifest, loader, mesh)
    acc_c, loss_c = evaluate_cached(cfg, state, mesh, dataset, labels)
    acc_s, loss_s = evaluate_manifest(cfg, state, mesh, train_manifest)
    # The two paths compile different HLO; allow one argmax tie-flip of slack
    # (the loss check concedes the same numeric divergence via rtol).
    assert abs(acc_c - acc_s) <= 1.0 / len(train_manifest) + 1e-9
    np.testing.assert_allclose(loss_c, loss_s, rtol=1e-5)


def test_remat_blocks_rejects_non_resnet():
    with pytest.raises(ValueError, match="remat_blocks=True does not apply to model 'alexnet'"):
        Config(remat="blocks", model_name="alexnet").validate_config()


@pytest.mark.slow
def test_remat_blocks_densenet_tree_and_forward():
    """densenet block remat: unchanged param tree, same forward output."""
    import jax.numpy as jnp
    from mpi_pytorch_tpu.models import create_model_bundle

    b_plain, v_plain = create_model_bundle("densenet121", 10, image_size=32)
    b_remat, v_remat = create_model_bundle("densenet121", 10, image_size=32, remat_blocks=True)
    assert jax.tree_util.tree_structure(v_plain) == jax.tree_util.tree_structure(v_remat)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 32, 32, 3))
    out_plain = b_plain.model.apply(v_plain, x, train=False)
    out_remat = b_remat.model.apply(v_plain, x, train=False)
    np.testing.assert_allclose(np.asarray(out_plain), np.asarray(out_remat), atol=1e-5)


def test_accum_config_validation():
    with pytest.raises(ValueError, match="accum_steps"):
        Config(accum_steps=3, batch_size=128).validate_config()
    with pytest.raises(ValueError, match="accum_steps"):
        Config(accum_steps=2, device_cache=True).validate_config()
    with pytest.raises(ValueError, match="accum_steps"):
        Config(accum_steps=0).validate_config()


@pytest.mark.slow
def test_feature_extract_freezes_backbone(tmp_path):
    from mpi_pytorch_tpu.train.trainer import build_training
    from mpi_pytorch_tpu.parallel.mesh import shard_batch
    from mpi_pytorch_tpu.train.step import make_train_step, place_state_on_mesh
    import jax.numpy as jnp

    cfg = _tiny_cfg(str(tmp_path), feature_extract=True, num_classes=200)
    mesh, bundle, state, (_, _, loader) = build_training(cfg)
    state = place_state_on_mesh(state, mesh)
    before = jax.device_get(state.params)
    step = make_train_step(jnp.float32)
    batch = next(iter(loader.epoch(0)))
    state2, _ = step(state, shard_batch(batch, mesh))
    after = jax.device_get(state2.params)

    # backbone unchanged, head moved
    np.testing.assert_array_equal(before["conv1"]["kernel"], after["conv1"]["kernel"])
    assert not np.array_equal(before["head"]["kernel"], after["head"]["kernel"])


def test_make_optimizer_variants_and_schedules():
    """adam|sgd|adamw x constant|cosine|warmup_cosine: each produces finite
    updates, cosine's update magnitude shrinks toward the end of the run,
    and bad names raise."""
    import jax.numpy as jnp

    from mpi_pytorch_tpu.train.state import make_optimizer

    params = {"w": jnp.ones((4, 4)), "b": jnp.zeros((4,))}
    grads = jax.tree_util.tree_map(jnp.ones_like, params)

    for opt in ("adam", "sgd", "adamw"):
        tx = make_optimizer(1e-2, optimizer=opt)
        st = tx.init(params)
        upd, _ = tx.update(grads, st, params)
        assert all(
            np.all(np.isfinite(np.asarray(u))) for u in jax.tree_util.tree_leaves(upd)
        )

    # Cosine: step-100 update is much smaller than step-0 update (lr -> 0).
    tx = make_optimizer(1e-2, optimizer="sgd", lr_schedule="cosine", total_steps=100)
    st = tx.init(params)
    upd0, st = tx.update(grads, st, params)
    for _ in range(98):
        _, st = tx.update(grads, st, params)
    upd_last, _ = tx.update(grads, st, params)
    assert abs(float(upd_last["w"][0, 0])) < 0.05 * abs(float(upd0["w"][0, 0]))

    # Warmup: the first update is (near) zero, the peak is reached later.
    tx = make_optimizer(
        1e-2, optimizer="sgd", lr_schedule="warmup_cosine",
        warmup_steps=10, total_steps=100,
    )
    st = tx.init(params)
    upd0, _ = tx.update(grads, st, params)
    assert abs(float(upd0["w"][0, 0])) < 1e-4

    with pytest.raises(ValueError, match="total_steps"):
        make_optimizer(1e-2, lr_schedule="cosine")
    with pytest.raises(ValueError, match="optimizer"):
        make_optimizer(1e-2, optimizer="rmsprop")
    with pytest.raises(ValueError, match="lr_schedule"):
        make_optimizer(1e-2, lr_schedule="linear")


def test_config_rejects_bad_optimizer_fields():
    from mpi_pytorch_tpu.config import Config

    with pytest.raises(ValueError, match="optimizer"):
        Config(optimizer="rmsprop").validate_config()
    with pytest.raises(ValueError, match="lr_schedule"):
        Config(lr_schedule="linear").validate_config()


def test_config_rejects_ignored_optimizer_combos():
    from mpi_pytorch_tpu.config import Config
    from mpi_pytorch_tpu.train.state import make_optimizer

    with pytest.raises(ValueError, match="weight_decay"):
        Config(weight_decay=0.01).validate_config()  # adam ignores it
    with pytest.raises(ValueError, match="warmup_steps"):
        Config(warmup_steps=10, lr_schedule="cosine").validate_config()
    with pytest.raises(ValueError, match="must be <"):
        make_optimizer(
            1e-2, lr_schedule="warmup_cosine", warmup_steps=200, total_steps=100
        )


@pytest.mark.slow
def test_uint8_input_matches_float_input(tmp_path):
    """--input-dtype uint8 (raw pixels to device, normalize on chip) must
    reproduce the float-input loss trajectory on a real-JPEG dataset — the
    pixels are uint8 at the source, so the two paths see identical data."""
    from mpi_pytorch_tpu.data.create_dataset import main as create_main

    out = str(tmp_path / "data")
    create_main(["--synthetic", "96", "--num-classes", "8", "--image-size", "48",
                 "--out", out])
    common = dict(
        debug=True, debug_sample_size=64, synthetic_data=False, num_classes=8,
        validate=True, val_on_train=True,
    )
    cfg_a = _tiny_cfg(os.path.join(str(tmp_path), "a"), **common)
    cfg_b = _tiny_cfg(
        os.path.join(str(tmp_path), "b"), **common, input_dtype="uint8"
    )
    for c in (cfg_a, cfg_b):
        c.train_csv = f"{out}/train_sample.csv"
        c.test_csv = f"{out}/test_sample.csv"
        c.train_img_dir = f"{out}/img/train"
        c.test_img_dir = f"{out}/img/test"
    sa = train(cfg_a)
    sb = train(cfg_b)
    np.testing.assert_allclose(sa.epoch_losses, sb.epoch_losses, rtol=1e-4)
    assert sa.val_accuracy == sb.val_accuracy


@pytest.mark.slow
def test_uint8_device_cache_matches_uint8_streaming(tmp_path):
    """input_dtype='uint8' composed with device_cache: the HBM-resident
    dataset is stored as raw uint8 (4x smaller) and normalized on device
    after the index gather — trajectory must match uint8 streaming."""
    kw = dict(num_epochs=2, num_classes=200, debug_sample_size=96,
              drop_remainder=False, input_dtype="uint8")
    sa = train(_tiny_cfg(os.path.join(str(tmp_path), "a"), **kw))
    sb = train(_tiny_cfg(os.path.join(str(tmp_path), "b"), **kw, device_cache=True))
    np.testing.assert_allclose(sa.epoch_losses, sb.epoch_losses, rtol=1e-4)


@pytest.mark.slow
def test_track_best_pins_checkpoint_and_eval_uses_it(tmp_path):
    """--track-best: best.json points at the best-validation epoch, retention
    (keep=1) never deletes that file even as newer checkpoints churn past it,
    a resumed run won't demote the stored best, and evaluate --use-best loads
    exactly the marked checkpoint."""
    from mpi_pytorch_tpu import checkpoint as ckpt

    cfg = _tiny_cfg(
        str(tmp_path), num_epochs=4, num_classes=200, validate=True,
        track_best=True, keep_checkpoints=1, learning_rate=1e-3,
    )
    summary = train(cfg)
    marker = ckpt.best_marker(cfg.checkpoint_dir)
    assert marker is not None
    assert marker["accuracy"] == summary.best_accuracy
    best_path = os.path.join(cfg.checkpoint_dir, marker["checkpoint"])
    assert os.path.exists(best_path), "retention must pin the best checkpoint"

    # The marker is the max over epochs: at least as good as the final
    # epoch's accuracy (equality when the last epoch is the best).
    assert summary.best_accuracy >= summary.val_accuracy
    assert marker["epoch"] <= 3

    # A resumed run starting from the stored best must not demote it.
    cfg2 = _tiny_cfg(
        str(tmp_path), num_epochs=5, num_classes=200, validate=True,
        track_best=True, keep_checkpoints=1, from_checkpoint=True,
    )
    train(cfg2)
    marker2 = ckpt.best_marker(cfg.checkpoint_dir)
    assert marker2["accuracy"] >= marker["accuracy"]

    # evaluate --use-best loads the marked file (log records the epoch).
    cfg3 = _tiny_cfg(str(tmp_path), num_classes=200, use_best=True)
    res = evaluate(cfg3)
    assert 0.0 <= res.accuracy <= 1.0


def test_track_best_requires_validation():
    with pytest.raises(ValueError, match="track_best"):
        Config(track_best=True, validate=False).validate_config()


@pytest.mark.slow
def test_full_fast_path_stack_matches_streaming(tmp_path):
    """The whole TPU-first ingest stack composed — offline pack, raw-uint8
    feeding, HBM-resident device cache, one-scan-per-epoch — must reproduce
    the plain f32 streaming trajectory on a real-JPEG dataset (uint8 source,
    so every path sees identical pixels)."""
    from mpi_pytorch_tpu.data.create_dataset import main as create_main
    from mpi_pytorch_tpu.data.packed import main as pack_main

    out = str(tmp_path / "data")
    create_main(["--synthetic", "96", "--num-classes", "8", "--image-size", "48",
                 "--out", out])
    data_args = dict(
        debug=True, debug_sample_size=64, synthetic_data=False, num_classes=8,
    )

    def with_dataset(cfg):
        cfg.train_csv = f"{out}/train_sample.csv"
        cfg.test_csv = f"{out}/test_sample.csv"
        cfg.train_img_dir = f"{out}/img/train"
        cfg.test_img_dir = f"{out}/img/test"
        return cfg

    packed_dir = str(tmp_path / "packed")
    pack_main([
        "--packed-dir", packed_dir, "--debug", "true", "--debug-sample-size", "64",
        "--test-csv", f"{out}/test_sample.csv", "--train-csv", f"{out}/train_sample.csv",
        "--train-img-dir", f"{out}/img/train", "--test-img-dir", f"{out}/img/test",
        "--synthetic-data", "false", "--num-classes", "8",
        "--image-size", "32", "--loader-workers", "2",
    ])

    sa = train(with_dataset(_tiny_cfg(os.path.join(str(tmp_path), "a"), **data_args)))
    sb = train(with_dataset(_tiny_cfg(
        os.path.join(str(tmp_path), "b"), **data_args,
        packed_dir=packed_dir, input_dtype="uint8",
        device_cache=True, scan_epoch=True,
    )))
    np.testing.assert_allclose(sa.epoch_losses, sb.epoch_losses, rtol=1e-4)


@pytest.mark.slow
def test_predictions_file_matches_reported_accuracy(tmp_path):
    """evaluate --predictions-file writes one row per test image in manifest
    order; the fraction of rows whose predicted_category_id equals the true
    category reproduces the reported accuracy exactly — the submission-file
    capability the reference's predictor ranks compute per-image but never
    persist (evaluation_pipeline.py:149-158)."""
    cfg = _tiny_cfg(str(tmp_path), num_epochs=2, num_classes=200,
                    debug_sample_size=160, learning_rate=1e-3)
    train(cfg)
    pred_path = os.path.join(str(tmp_path), "predictions.csv")
    cfg.predictions_file = pred_path
    res = evaluate(cfg)

    from mpi_pytorch_tpu.data import load_manifests

    _, test_m = load_manifests(cfg)
    rows = open(pred_path).read().strip().splitlines()
    assert rows[0] == "file_name,predicted_label,predicted_category_id"
    body = [r.split(",") for r in rows[1:]]
    assert [b[0] for b in body] == list(test_m.filenames)  # manifest order
    correct = sum(
        int(b[2]) == int(c) for b, c in zip(body, test_m.category_ids)
    )
    assert correct / len(body) == pytest.approx(res.accuracy, abs=1e-9)
