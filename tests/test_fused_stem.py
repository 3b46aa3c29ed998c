"""Pin the fused stem kernel (ops/fused_stem.py) to the unfused XLA
composition it replaces — values AND gradients, via the Pallas interpreter
on CPU (the same kernel code path the TPU compiles).

Reference semantics: ``max_pool3x3s2p1(relu(y·a + b))`` with f32 math
(≙ the torchvision resnet stem tail, reference ``models.py:30-45``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_pytorch_tpu.ops.fused_stem import (
    _reference_impl,
    stem_affine_relu_pool,
)

B, H, W, C = 4, 16, 16, 64


def _inputs(rng, tie_heavy=False, dtype=jnp.float32):
    y = rng.standard_normal((B, H, W, C)).astype(np.float32)
    if tie_heavy:
        # Quantize hard so pool windows tie constantly (and relu produces
        # exact-zero plateaus) — the select-and-scatter tie-break regime.
        y = np.round(y * 2) / 2
    a = (0.5 + rng.random(C)).astype(np.float32)
    b = rng.standard_normal(C).astype(np.float32) * 0.1
    return jnp.asarray(y, dtype), jnp.asarray(a), jnp.asarray(b)


@pytest.mark.parametrize("tie_heavy", [False, True])
def test_forward_matches_reference(rng, tie_heavy):
    y, a, b = _inputs(rng, tie_heavy)
    got = stem_affine_relu_pool(y, a, b, interpret=True)
    want = _reference_impl(y, a, b)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("tie_heavy", [False, True])
def test_gradients_match_reference(rng, tie_heavy):
    y, a, b = _inputs(rng, tie_heavy)
    co = jnp.asarray(rng.standard_normal((B, H // 2, W // 2, C)), jnp.float32)

    def loss(fn):
        return lambda y, a, b: jnp.sum(fn(y, a, b) * co)

    gy, ga, gb = jax.grad(
        loss(lambda y, a, b: stem_affine_relu_pool(y, a, b, interpret=True)),
        argnums=(0, 1, 2),
    )(y, a, b)
    ry, ra, rb = jax.grad(loss(_reference_impl), argnums=(0, 1, 2))(y, a, b)
    np.testing.assert_allclose(gy, ry, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(ga, ra, rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(gb, rb, rtol=1e-5, atol=1e-4)


def test_bf16_storage_roundtrip(rng):
    """Production dtype: bf16 in/out, f32 compute inside the kernel."""
    y, a, b = _inputs(rng, dtype=jnp.bfloat16)
    got = stem_affine_relu_pool(y, a, b, interpret=True)
    assert got.dtype == jnp.bfloat16
    want = _reference_impl(y, a, b)
    np.testing.assert_allclose(
        got.astype(np.float32), want.astype(np.float32), rtol=2e-2, atol=2e-2
    )


_LEVERS = [
    # (env, value, reference): the §4d byte-bound lever gates — exact
    # re-tilings, pinned against the f32 reference.
    ("MPT_STEM_LANES", "256", _reference_impl),
    ("MPT_STEM_IDX_INT8", "1", _reference_impl),
    ("MPT_STEM_C_BLOCK", "16", _reference_impl),
]


@pytest.mark.parametrize("env,val,reference", _LEVERS)
@pytest.mark.parametrize("tie_heavy", [False, True])
def test_levers_match_reference(rng, monkeypatch, env, val, reference, tie_heavy):
    """Each §4d byte-bound lever (docs/RESULTS.md) preserves its reference
    semantics — values AND all three gradients — through the real kernel
    code path. The lever config is read from the env at trace time, so the
    monkeypatched env drives the actual gated kernel variant. B=256 so the
    256-lane lever genuinely widens the batch block."""
    monkeypatch.setenv(env, val)
    y = rng.standard_normal((256, 8, 8, C)).astype(np.float32)
    if tie_heavy:
        y = np.round(y * 2) / 2
    y = jnp.asarray(y)
    # Power-of-two scales make y·a EXACT, so a+b is the affine's only f32
    # rounding and FMA ≡ mul+add — otherwise the kernel's and the XLA
    # reference's 1-ulp f32 contraction differences land on bf16 rounding
    # boundaries.
    a = jnp.asarray(2.0 ** rng.integers(-1, 2, C).astype(np.float32))
    b = jnp.asarray(
        (rng.standard_normal(C).astype(np.float32) * 0.1)
        .astype(jnp.bfloat16)
        .astype(np.float32)
    )
    got = stem_affine_relu_pool(y, a, b, interpret=True)
    want = reference(y, a, b)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)

    # Cotangent on the bf16 grid: the bf16 reference's VJP rounds the
    # cotangent through its cast (the kernel back-propagates full f32), so
    # a bf16-exact cotangent makes the comparison rounding-free.
    co = (
        jnp.asarray(rng.standard_normal((256, 4, 4, C)), jnp.float32)
        .astype(jnp.bfloat16)
        .astype(jnp.float32)
    )

    def loss(fn):
        return lambda y, a, b: jnp.sum(fn(y, a, b) * co)

    g = jax.grad(
        loss(lambda y, a, b: stem_affine_relu_pool(y, a, b, interpret=True)),
        argnums=(0, 1, 2),
    )(y, a, b)
    r = jax.grad(loss(reference), argnums=(0, 1, 2))(y, a, b)
    for u, v in zip(g, r):
        np.testing.assert_allclose(u, v, rtol=1e-5, atol=1e-4)


def test_idx_int8_lever_changes_residual_dtype(rng, monkeypatch):
    """The int8-argmax lever must actually store int8 (the HBM-traffic
    halving is the point) — pinned on the fwd-with-idx output directly."""
    from mpi_pytorch_tpu.ops.fused_stem import _fwd_impl

    y, a, b = _inputs(rng)
    yt = jnp.transpose(y, (1, 2, 3, 0))
    _, idx = _fwd_impl(
        yt, a, b, want_idx=True, interpret=True
    )
    assert idx.dtype == jnp.bfloat16  # default storage
    monkeypatch.setenv("MPT_STEM_IDX_INT8", "1")
    _, idx8 = _fwd_impl(yt, a, b, want_idx=True, interpret=True)
    assert idx8.dtype == jnp.int8
    np.testing.assert_array_equal(
        np.asarray(idx, np.int32), np.asarray(idx8, np.int32)
    )


def test_shard_map_multi_device_matches_single_call(rng):
    """dp_mesh partitions the kernel over the 8-device data axis: values
    and all three gradients equal the reference (the da/db cotangents are
    psum-reduced across shards by shard_map's transpose)."""
    from jax.sharding import Mesh

    n = len(jax.devices())
    assert n == 8  # conftest virtual-CPU mesh
    mesh = Mesh(np.array(jax.devices()).reshape(n, 1), ("data", "model"))
    y = jnp.asarray(rng.standard_normal((2 * n, H, W, C)), jnp.float32)
    a = jnp.asarray((0.5 + rng.random(C)).astype(np.float32))
    b = jnp.asarray(rng.standard_normal(C).astype(np.float32) * 0.1)
    got = stem_affine_relu_pool(y, a, b, interpret=True, dp_mesh=mesh)
    want = _reference_impl(y, a, b)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)

    co = jnp.asarray(rng.standard_normal((2 * n, H // 2, W // 2, C)), jnp.float32)

    def loss(fn):
        return lambda y, a, b: jnp.sum(fn(y, a, b) * co)

    g = jax.grad(
        loss(lambda y, a, b: stem_affine_relu_pool(
            y, a, b, interpret=True, dp_mesh=mesh
        )),
        argnums=(0, 1, 2),
    )(y, a, b)
    r = jax.grad(loss(_reference_impl), argnums=(0, 1, 2))(y, a, b)
    np.testing.assert_allclose(g[0], r[0], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(g[1], r[1], rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(g[2], r[2], rtol=1e-5, atol=1e-4)

    # An indivisible batch must take the XLA path (never replicate the
    # Mosaic call), still producing reference values.
    y_odd = y[: 2 * n - 1]
    got_odd = stem_affine_relu_pool(y_odd, a, b, interpret=True, dp_mesh=mesh)
    np.testing.assert_allclose(
        got_odd, _reference_impl(y_odd, a, b), rtol=1e-6, atol=1e-6
    )


def test_shape_guards(rng):
    y, a, b = _inputs(rng)
    with pytest.raises(ValueError):
        stem_affine_relu_pool(y[:, :15], a, b, interpret=True)
    with pytest.raises(ValueError):
        stem_affine_relu_pool(y, a[:3], b, interpret=True)


def test_module_runs_kernel_under_env_gate(rng, monkeypatch):
    """MPT_STEM_INTERPRET routes the module through the REAL kernel code
    path (Pallas interpreter) instead of the XLA fallback — the gate the
    whole-model CPU tests rely on."""
    monkeypatch.setenv("MPT_STEM_INTERPRET", "1")
    from mpi_pytorch_tpu.models.common import FusedStemBNReluPool

    y = jnp.asarray(rng.standard_normal((B, H, W, C)), jnp.float32)
    m = FusedStemBNReluPool()
    v = m.init(jax.random.PRNGKey(0), y, True)
    out, _ = m.apply(v, y, False, mutable=["batch_stats"])
    monkeypatch.delenv("MPT_STEM_INTERPRET")
    want = m.apply(v, y, False, mutable=["batch_stats"])[0]
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)


def test_fused_stem_training_matches_unfused(rng, monkeypatch, tmp_path):
    """TWO full sharded training epochs through the REAL kernel code path
    (Pallas interpreter) equal the unfused stem's epochs — the end-to-end
    integration pin: custom-VJP grads, BN stat updates, optimizer steps,
    checkpointing, all through the trainer."""
    import os

    from mpi_pytorch_tpu.config import Config
    from mpi_pytorch_tpu.train.trainer import train

    def cfg(fused, sub):
        c = Config(
            model_name="resnet18", num_classes=200, batch_size=16,
            num_epochs=2, debug=True, debug_sample_size=64,
            synthetic_data=True, compute_dtype="float32",
            width=32, height=32, fused_stem=fused, validate=False,
            loader_workers=2, log_every_steps=0, metrics_file="",
            checkpoint_dir=os.path.join(str(tmp_path), sub),
            log_file=os.path.join(str(tmp_path), sub + ".log"),
        )
        c.validate_config()
        return c

    monkeypatch.setenv("MPT_STEM_INTERPRET", "1")
    fused = train(cfg(True, "f"))
    monkeypatch.delenv("MPT_STEM_INTERPRET")
    plain = train(cfg(False, "p"))
    # Same data, same init, same seeds. Epoch 1 agrees to float tolerance;
    # later epochs drift at the usual chaotic-amplification rate of
    # correct-but-not-bit-identical op orderings (measured: 1e-6 after
    # epoch 1, 1e-3 after epoch 2) — gradient EXACTNESS is pinned tightly
    # in test_gradients_match_reference; this test pins the integration.
    np.testing.assert_allclose(
        fused.epoch_losses[:1], plain.epoch_losses[:1], rtol=2e-4, atol=2e-4
    )
    np.testing.assert_allclose(
        fused.epoch_losses, plain.epoch_losses, rtol=1e-2, atol=1e-2
    )


def test_spmd_fused_stem_training_matches_unfused(rng, monkeypatch, tmp_path):
    """The multi-chip recipe, pinned (VERDICT r5 #3): ``--spmd-mode`` +
    ``--fused-stem`` on the 8-device CPU mesh, REAL kernel code path
    (Pallas interpreter), epoch losses ≡ the unfused spmd run. In spmd
    mode the step itself is a shard_map handing the kernel PER-SHARD
    batches (the trainer passes no dp_mesh), so this drives exactly the
    partitioned regime the kernel sees on a multi-chip pod.

    Batch 64 → 8 images per shard: at per-shard batch 2 the folded affine's
    float rounding near relu boundaries, amplified by noisy 2-image local-BN
    variances, drifts the trajectories ~1e-2 (measured; same equivalence
    class the auto-mode test tolerates at later epochs) — 8/shard is both
    the realistic regime and numerically tight."""
    import os

    from mpi_pytorch_tpu.config import Config
    from mpi_pytorch_tpu.train.trainer import train

    def cfg(fused, sub):
        c = Config(
            model_name="resnet18", num_classes=200, batch_size=64,
            num_epochs=2, debug=True, debug_sample_size=128,
            synthetic_data=True, compute_dtype="float32",
            width=32, height=32, fused_stem=fused, spmd_mode=True,
            validate=False, loader_workers=2, log_every_steps=0,
            metrics_file="",
            checkpoint_dir=os.path.join(str(tmp_path), sub),
            log_file=os.path.join(str(tmp_path), sub + ".log"),
        )
        c.validate_config()
        return c

    monkeypatch.setenv("MPT_STEM_INTERPRET", "1")
    fused = train(cfg(True, "sf"))
    monkeypatch.delenv("MPT_STEM_INTERPRET")
    plain = train(cfg(False, "sp"))
    np.testing.assert_allclose(
        fused.epoch_losses[:1], plain.epoch_losses[:1], rtol=2e-4, atol=2e-4
    )
    np.testing.assert_allclose(
        fused.epoch_losses, plain.epoch_losses, rtol=1e-2, atol=1e-2
    )


def test_module_matches_unfused_stem(rng):
    """FusedStemBNReluPool ≡ batch_norm → relu → max_pool(3,2,1): same
    output, same batch_stats update, same eval-mode behavior, and the
    SAME variable tree (checkpoints interchange)."""
    from flax import linen as nn

    from mpi_pytorch_tpu.models.common import (
        FusedStemBNReluPool,
        batch_norm,
        max_pool,
    )

    class Unfused(nn.Module):
        @nn.compact
        def __call__(self, y, use_running_average):
            z = batch_norm("bn1")(y, use_running_average=use_running_average)
            return max_pool(nn.relu(z), 3, 2, padding=1)

    class Fused(nn.Module):
        @nn.compact
        def __call__(self, y, use_running_average):
            return FusedStemBNReluPool(name="bn1")(y, use_running_average)

    y = jnp.asarray(rng.standard_normal((B, H, W, C)), jnp.float32)
    uf, fu = Unfused(), Fused()
    vu = uf.init(jax.random.PRNGKey(0), y, True)
    vf = fu.init(jax.random.PRNGKey(0), y, True)
    assert jax.tree.structure(vu) == jax.tree.structure(vf)

    # Train mode: same output, same running-stat update (from shared params).
    ou, su = uf.apply(vu, y, False, mutable=["batch_stats"])
    of, sf = fu.apply(vu, y, False, mutable=["batch_stats"])
    np.testing.assert_allclose(ou, of, rtol=1e-5, atol=1e-5)
    jax.tree.map(
        lambda x, z: np.testing.assert_allclose(x, z, rtol=1e-5, atol=1e-6),
        su["batch_stats"], sf["batch_stats"],
    )

    # Eval mode: running stats drive both identically.
    eu = uf.apply(vu, y, True)
    ef = fu.apply(vu, y, True)
    np.testing.assert_allclose(eu, ef, rtol=1e-5, atol=1e-5)

    # Gradients through the module (params + input) agree.
    def tloss(m):
        def f(params, y):
            out, _ = m.apply(
                {"params": params, "batch_stats": vu["batch_stats"]},
                y, False, mutable=["batch_stats"],
            )
            return jnp.sum(out * out)
        return f

    gu = jax.grad(tloss(uf), argnums=(0, 1))(vu["params"], y)
    gf = jax.grad(tloss(fu), argnums=(0, 1))(vu["params"], y)
    jax.tree.map(
        lambda x, z: np.testing.assert_allclose(x, z, rtol=1e-4, atol=1e-4),
        gu, gf,
    )


def test_densenet_fused_stem_matches_unfused(rng, monkeypatch):
    """densenet121's stem (features.conv0..pool0) is geometrically the
    resnet stem, so the fused kernel applies (verdict r5 #7): a whole
    DenseNet forward with fused_stem=True — real kernel code path via the
    interpreter — equals the unfused model on the SAME variables (the
    variable trees are identical, so checkpoints interchange), and the
    param gradients agree."""
    from mpi_pytorch_tpu.models.densenet import DenseNet

    kw = dict(block_config=(1, 1), num_classes=5, growth_rate=8,
              num_init_features=64)
    unfused = DenseNet(**kw)
    fused = DenseNet(fused_stem=True, **kw)
    x = jnp.asarray(rng.standard_normal((2, 32, 32, 3)), jnp.float32)

    monkeypatch.setenv("MPT_STEM_INTERPRET", "1")
    vu = unfused.init({"params": jax.random.PRNGKey(0)}, x, train=True)
    vf = fused.init({"params": jax.random.PRNGKey(0)}, x, train=True)
    assert jax.tree.structure(vu) == jax.tree.structure(vf)

    ou, su = unfused.apply(vu, x, train=True, mutable=["batch_stats"])
    of, sf = fused.apply(vu, x, train=True, mutable=["batch_stats"])
    np.testing.assert_allclose(ou, of, rtol=1e-5, atol=1e-5)
    jax.tree.map(
        lambda p, q: np.testing.assert_allclose(p, q, rtol=1e-5, atol=1e-6),
        su["batch_stats"], sf["batch_stats"],
    )

    def tloss(m):
        def f(params):
            out, _ = m.apply(
                {"params": params, "batch_stats": vu["batch_stats"]},
                x, train=True, mutable=["batch_stats"],
            )
            return jnp.sum(out * out)
        return f

    gu = jax.grad(tloss(unfused))(vu["params"])
    gf = jax.grad(tloss(fused))(vu["params"])
    jax.tree.map(
        lambda p, q: np.testing.assert_allclose(p, q, rtol=1e-4, atol=1e-4),
        gu, gf,
    )


def test_densenet_fused_stem_registry_and_default():
    """densenet121 is fused-stem CAPABLE (--fused-stem builds it) but NOT a
    bench default until its chip A/B lands (docs/RESULTS.md §4: stem tail
    ≈3% of its roofline bound — the fused-head discipline)."""
    from mpi_pytorch_tpu.models.registry import initialize_model, model_spec

    spec = model_spec("densenet121")
    assert spec.accepts("fused_stem", True) and not spec.fused_stem_measured
    assert model_spec("resnet18").fused_stem_measured
    model, _ = initialize_model("densenet121", 5, fused_stem=True)
    assert model.fused_stem
    # fused_stem_default is platform-gated (TPU); on the CPU test mesh it
    # must be False for every model regardless of ``fused_stem_measured``.
    from mpi_pytorch_tpu.models.registry import fused_stem_default

    assert not fused_stem_default("densenet121")
    assert not fused_stem_default("resnet18")

    from mpi_pytorch_tpu.config import parse_config

    cfg = parse_config(["--model-name", "densenet121", "--fused-stem", "1"])
    assert cfg.fused_stem
