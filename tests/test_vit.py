"""ViT family: registry integration, SP-strategy numerics (full ≡ ring ≡
Ulysses inside the model), remat agreement, the train step end-to-end, and
the sp_strategy guard for sequence-free architectures.

The load-bearing property: a ViT built with ``sp_strategy='ring'`` or
``'ulysses'`` computes the SAME function as the plain model — sequence
parallelism is an execution layout, not a different network.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from mpi_pytorch_tpu.models import create_model_bundle, initialize_model
from mpi_pytorch_tpu.models.vit import VisionTransformer

# Tiny config: 32px / patch 4 → 64 tokens (divisible by 8 shards); 8 heads
# (divisible by 8 for Ulysses).
TINY = dict(
    num_classes=10, patch_size=4, hidden=64, depth=2, num_heads=8, mlp_dim=128
)


@pytest.fixture(scope="module")
def sp_mesh():
    dev = np.asarray(jax.devices()[:8]).reshape(8, 1)
    return Mesh(dev, ("seq", "unused"))


@pytest.fixture(scope="module")
def tiny_vit():
    model = VisionTransformer(**TINY)
    x = jnp.asarray(
        np.random.default_rng(0).standard_normal((4, 32, 32, 3)), jnp.float32
    )
    variables = model.init({"params": jax.random.PRNGKey(0)}, x, train=False)
    return model, variables, x


def test_vit_forward_shape_and_params(tiny_vit):
    model, variables, x = tiny_vit
    logits = model.apply(variables, x, train=False)
    assert logits.shape == (4, 10)
    # Exact param count: patch embed + pos + 2 blocks + final LN + head.
    h, mlp, heads, p = TINY["hidden"], TINY["mlp_dim"], TINY["num_heads"], TINY["patch_size"]
    tokens = (32 // p) ** 2
    patch = 3 * p * p * h + h
    pos = tokens * h
    per_block = (
        4 * (h * h + h)          # q, k, v, out projections
        + (h * mlp + mlp) + (mlp * h + h)  # MLP
        + 2 * 2 * h              # two LayerNorms
    )
    total = patch + pos + TINY["depth"] * per_block + 2 * h + (h * 10 + 10)
    got = sum(x.size for x in jax.tree_util.tree_leaves(variables["params"]))
    assert got == total


@pytest.mark.parametrize("strategy", ["ring", "ulysses"])
def test_vit_sp_matches_plain(tiny_vit, sp_mesh, strategy):
    model, variables, x = tiny_vit
    sp_model = VisionTransformer(**TINY, sp_strategy=strategy, sp_mesh=sp_mesh)
    got = sp_model.apply(variables, x, train=False)
    want = model.apply(variables, x, train=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


@pytest.mark.slow
@pytest.mark.parametrize("strategy", ["ring", "ulysses"])
def test_vit_sp_grads_match_plain(tiny_vit, sp_mesh, strategy):
    model, variables, x = tiny_vit
    sp_model = VisionTransformer(**TINY, sp_strategy=strategy, sp_mesh=sp_mesh)

    def loss(m, params):
        out = m.apply({"params": params}, x, train=False)
        return jnp.sum(out * out)

    g_sp = jax.grad(lambda p: loss(sp_model, p))(variables["params"])
    g_pl = jax.grad(lambda p: loss(model, p))(variables["params"])
    for a, b in zip(jax.tree_util.tree_leaves(g_sp), jax.tree_util.tree_leaves(g_pl)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4)


def test_vit_remat_blocks_matches_plain(tiny_vit):
    model, variables, x = tiny_vit
    remat_model = VisionTransformer(**TINY, remat_blocks=True)

    def loss(m, params):
        return jnp.sum(m.apply({"params": params}, x, train=False) ** 2)

    np.testing.assert_allclose(
        float(loss(remat_model, variables["params"])),
        float(loss(model, variables["params"])),
        rtol=1e-6,
    )
    g_r = jax.grad(lambda p: loss(remat_model, p))(variables["params"])
    g_p = jax.grad(lambda p: loss(model, p))(variables["params"])
    for a, b in zip(jax.tree_util.tree_leaves(g_r), jax.tree_util.tree_leaves(g_p)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-6)


@pytest.mark.slow
def test_vit_trains_through_standard_step():
    """The family plugs into the same train step as the CNN zoo."""
    from mpi_pytorch_tpu.train.state import TrainState, make_optimizer
    from mpi_pytorch_tpu.train.step import make_train_step

    bundle, variables = create_model_bundle(
        "vit_s16", 10, rng=jax.random.PRNGKey(0), image_size=32
    )
    assert bundle.has_aux_logits is False
    state = TrainState.create(
        apply_fn=bundle.model.apply, variables=variables,
        tx=make_optimizer(1e-3), rng=jax.random.PRNGKey(1),
    )
    rng = np.random.default_rng(2)
    images = jnp.asarray(rng.standard_normal((8, 32, 32, 3)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, 10, 8), jnp.int32)
    step = make_train_step(jnp.float32)
    losses = []
    for _ in range(3):
        state, metrics = step(state, (images, labels))
        losses.append(float(metrics["loss"]))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]


@pytest.mark.slow
def test_trainer_config_wires_sp_and_ep(tmp_path):
    """--sp-strategy / --expert-parallel reach the model through
    build_training: the bundle's model carries the strategy and a
    seq/expert mesh over the training mesh's devices (numerics of those
    paths are covered by the module-level SP/EP equality tests)."""
    from mpi_pytorch_tpu.config import Config
    from mpi_pytorch_tpu.train.trainer import build_training

    cfg = Config(
        model_name="vit_s16", num_classes=1000, batch_size=8,
        width=64, height=64, synthetic_data=True, sp_strategy="ring",
        checkpoint_dir=str(tmp_path), validate=False,
    )
    _, bundle, _, _ = build_training(cfg)
    assert bundle.model.sp_strategy == "ring"
    assert bundle.model.sp_mesh.axis_names[0] == "seq"

    cfg2 = Config(
        model_name="vit_moe_s16", num_classes=1000, batch_size=8,
        width=64, height=64, synthetic_data=True, expert_parallel=True,
        checkpoint_dir=str(tmp_path), validate=False,
    )
    _, bundle2, _, _ = build_training(cfg2)
    assert bundle2.model.ep_mesh.axis_names[0] == "expert"
    assert bundle2.model.moe_every == 2


@pytest.mark.slow
def test_inference_config_wires_sp_and_ep(tmp_path):
    """The eval driver mirrors the trainer's SP/EP model wiring."""
    from mpi_pytorch_tpu.config import Config
    from mpi_pytorch_tpu.evaluate import build_inference

    cfg = Config(
        model_name="vit_moe_s16", num_classes=1000, batch_size=8,
        width=64, height=64, synthetic_data=True, expert_parallel=True,
        checkpoint_dir=str(tmp_path), validate=False,
    )
    _, bundle, _, _ = build_inference(cfg)
    assert bundle.model.ep_mesh.axis_names[0] == "expert"


def test_config_rejects_bad_sp_strategy():
    from mpi_pytorch_tpu.config import Config

    with pytest.raises(ValueError, match="sp_strategy"):
        Config(sp_strategy="rings").validate_config()


def test_registry_rejects_sp_on_cnn():
    with pytest.raises(ValueError, match="vit"):
        initialize_model("resnet18", 10, sp_strategy="ring")


def test_vit_rejects_bad_patch_grid():
    model = VisionTransformer(**TINY)
    with pytest.raises(ValueError, match="divisible"):
        model.init(
            {"params": jax.random.PRNGKey(0)},
            jnp.zeros((1, 30, 30, 3)), train=False,
        )


def test_qkv_fused_parity():
    """--qkv-fused: identical param tree, bit-identical INIT values (the
    _ProjParams kernel init replicates DenseGeneral's flatten-then-reshape
    fan-in), equal forward and gradients — the checkpoint-interchange
    claim, pinned (it depends on flax DenseGeneral internals)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mpi_pytorch_tpu.models.vit import VisionTransformer

    kw = dict(num_classes=10, patch_size=8, hidden=32, depth=2,
              num_heads=4, mlp_dim=64)
    vu = VisionTransformer(**kw)
    vf = VisionTransformer(**kw, qkv_fused=True)
    x = jnp.asarray(
        np.random.default_rng(0).normal(size=(2, 16, 16, 3)), jnp.float32
    )
    p1 = vu.init({"params": jax.random.PRNGKey(0)}, x, train=False)
    p2 = vf.init({"params": jax.random.PRNGKey(0)}, x, train=False)
    assert jax.tree.structure(p1) == jax.tree.structure(p2)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    o1 = vu.apply(p1, x, train=False)
    o2 = vf.apply(p1, x, train=False)  # SAME params through both layouts
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=2e-5, atol=2e-5)
    g1 = jax.grad(lambda p: jnp.sum(vu.apply(p, x, train=False) ** 2))(p1)
    g2 = jax.grad(lambda p: jnp.sum(vf.apply(p, x, train=False) ** 2))(p1)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)


def test_vit_b16_full_takes_the_kernel_and_agrees_with_full_attention(monkeypatch):
    """``attn_impl="full"`` through ``vit_b16`` at 224 px (196 tokens, 12
    heads of 64 — inside the single-pass kernel's envelope, so the dispatch
    takes it, interpreted here) computes what XLA's ``full_attention`` does:
    logits and every parameter's gradient. Two blocks: the attention shapes
    are the model's, the depth is the test's."""
    from mpi_pytorch_tpu.models.vit import vit_b16
    from mpi_pytorch_tpu.obs import trace as obs_trace

    model = vit_b16(10, depth=2)
    assert model.attn_impl == "full"
    x = jnp.asarray(
        np.random.default_rng(3).standard_normal((2, 224, 224, 3)), jnp.float32
    )
    variables = model.init({"params": jax.random.PRNGKey(0)}, x[:1], train=False)

    def logits_and_grads():
        loss = lambda v: jnp.sum(model.apply(v, x, train=False) ** 2)
        return model.apply(variables, x, train=False), jax.grad(loss)(variables)

    want, want_grads = logits_and_grads()  # the CPU backend: full_attention
    monkeypatch.setenv("MPT_ATTN_INTERPRET", "1")
    # The kernel's path keeps q, k, v and the output [B, S, H·Dh] (plain
    # matmuls over the SAME parameters): one tree, one initialization.
    again = model.init({"params": jax.random.PRNGKey(0)}, x[:1], train=False)
    assert jax.tree.structure(again) == jax.tree.structure(variables)
    for a, b in zip(jax.tree.leaves(again), jax.tree.leaves(variables)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    tracer = obs_trace.Tracer("unwritten.json")
    with obs_trace.use(tracer):
        got, got_grads = logits_and_grads()
    assert [e["args"] for e in tracer._events if e["name"] == "attn/dispatch"] == [
        {"path": "kernel", "S": 196, "Dh": 64, "batch": 2}
    ]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)
    # Per parameter, against its own length — but no shorter than a thousandth
    # of the tree's: the key bias's gradient is zero in exact arithmetic
    # (softmax ignores a shift of every score of a row) and rounding on both
    # sides.
    norm = lambda t: float(np.linalg.norm(np.asarray(t, np.float64)))
    floor = 1e-3 * norm(jnp.concatenate([g.ravel() for g in jax.tree.leaves(want_grads)]))
    for a, b in zip(jax.tree.leaves(got_grads), jax.tree.leaves(want_grads)):
        assert norm(a - b) <= 1e-4 * max(norm(b), floor)
