"""MoE-ViT (`vit_moe_s16`): the EP training-path model. Asserts the aux loss
flows through the standard train step, expert-parallel execution equals the
dense evaluation of the same network, and the registry guards.

The load-bearing property (mirroring the SP tests): sharding the experts
over a mesh is an execution layout — the EP-built model computes the same
function as the dense one.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from mpi_pytorch_tpu.models import create_model_bundle, initialize_model
from mpi_pytorch_tpu.models.vit import VisionTransformer

# 32px / patch 4 → 64 tokens; batch 4 → 256 tokens, divisible by 8 shards.
TINY = dict(
    num_classes=10, patch_size=4, hidden=64, depth=2, num_heads=4, mlp_dim=128,
    moe_every=2, num_experts=8, moe_capacity=256,  # no-drop capacity: EP ≡ dense
)


@pytest.fixture(scope="module")
def ep_mesh():
    dev = np.asarray(jax.devices()[:8]).reshape(8, 1)
    return Mesh(dev, ("expert", "unused"))


@pytest.fixture(scope="module")
def tiny_moe_vit():
    model = VisionTransformer(**TINY)
    x = jnp.asarray(
        np.random.default_rng(0).standard_normal((4, 32, 32, 3)), jnp.float32
    )
    variables = model.init({"params": jax.random.PRNGKey(0)}, x, train=False)
    variables.pop("losses", None)
    return model, variables, x


def test_moe_vit_has_experts_in_odd_blocks_only(tiny_moe_vit):
    _, variables, _ = tiny_moe_vit
    params = variables["params"]
    assert "moe" in params["block1"] and "w1" in params["block1"]["moe"]
    assert "moe" not in params["block0"] and "mlp1" in params["block0"]
    assert params["block1"]["moe"]["w1"].shape == (8, 64, 128)


def test_moe_vit_sows_aux_loss(tiny_moe_vit):
    model, variables, x = tiny_moe_vit
    logits, updated = model.apply(variables, x, train=False, mutable=["losses"])
    assert logits.shape == (4, 10)
    leaves = jax.tree_util.tree_leaves(updated["losses"])
    assert len(leaves) == 1  # one MoE block at depth 2
    aux = float(sum(jnp.sum(v) for v in leaves))
    assert np.isfinite(aux) and aux > 0.0


def test_moe_vit_ep_matches_dense(tiny_moe_vit, ep_mesh):
    model, variables, x = tiny_moe_vit
    ep_model = VisionTransformer(**TINY, ep_mesh=ep_mesh)
    got = ep_model.apply(variables, x, train=False)
    want = model.apply(variables, x, train=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


@pytest.mark.slow
def test_moe_vit_ep_grads_match_dense(tiny_moe_vit, ep_mesh):
    model, variables, x = tiny_moe_vit
    ep_model = VisionTransformer(**TINY, ep_mesh=ep_mesh)

    # Task-path grads only: the aux term is EXPECTED to differ between the
    # two layouts (EP computes load-balance per shard and pmeans — average of
    # per-shard frac·p̄ products ≠ the dense global product; the per-shard
    # semantics themselves are asserted in test_moe.py).
    def loss(m, params):
        out = m.apply({"params": params}, x, train=False)
        return jnp.sum(out * out)

    g_ep = jax.grad(lambda p: loss(ep_model, p))(variables["params"])
    g_de = jax.grad(lambda p: loss(model, p))(variables["params"])
    # f32 accumulation-order noise: the all_to_all regroups the expert einsum
    # into per-shard blocks, so backward sums run in a different order than
    # the dense single-einsum (measured ≤6e-5 abs on 0.05% of elements).
    for a, b in zip(jax.tree_util.tree_leaves(g_ep), jax.tree_util.tree_leaves(g_de)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=3e-3, atol=1e-4)


@pytest.mark.slow
def test_moe_vit_trains_through_standard_step():
    """The aux loss reaches the optimizer via the train step's "losses"
    collection — total loss stays finite and decreases."""
    from mpi_pytorch_tpu.train.state import TrainState, make_optimizer
    from mpi_pytorch_tpu.train.step import make_train_step

    bundle, variables = create_model_bundle(
        "vit_moe_s16", 10, rng=jax.random.PRNGKey(0), image_size=32
    )
    assert "losses" not in variables
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


def test_moe_vit_composes_sp_and_ep(tiny_moe_vit, ep_mesh):
    """SP attention and EP experts in the SAME blocks: ring-sharded
    attention + all_to_all-sharded experts compute the same function as the
    plain dense model (both are execution layouts over one set of params).
    Requires heads and sequence divisible by the shard count: TINY has 4
    heads, so ring (no head constraint) is the strategy under test."""
    model, variables, x = tiny_moe_vit
    sp_mesh = Mesh(
        np.asarray(jax.devices()[:8]).reshape(8, 1), ("seq", "unused")
    )
    both = VisionTransformer(
        **TINY, sp_strategy="ring", sp_mesh=sp_mesh, ep_mesh=ep_mesh
    )
    got = both.apply(variables, x, train=False)
    want = model.apply(variables, x, train=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_moe_vit_handles_awkward_token_counts():
    """Token counts that are not multiples of the default routing group
    (e.g. 20px/patch4 → 25 tokens/image, batch 8 → 200 tokens) pick the
    largest dividing group instead of crashing."""
    model = VisionTransformer(
        num_classes=10, patch_size=4, hidden=64, depth=2, num_heads=4,
        mlp_dim=128, moe_every=2, num_experts=8,
    )
    x = jnp.asarray(
        np.random.default_rng(5).standard_normal((8, 20, 20, 3)), jnp.float32
    )
    variables = model.init({"params": jax.random.PRNGKey(0)}, x, train=False)
    variables.pop("losses", None)
    logits = model.apply(variables, x, train=False)
    assert logits.shape == (8, 10)
    assert np.all(np.isfinite(np.asarray(logits)))


def test_registry_rejects_ep_on_dense_model(ep_mesh):
    with pytest.raises(ValueError, match="ep_mesh does not apply to model 'vit_s16'.*vit_moe_s16"):
        initialize_model("vit_s16", 10, ep_mesh=ep_mesh)
