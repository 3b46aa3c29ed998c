"""The plain references against the system at a tiny size on the CPU, in the
system's float32 (where they must agree to rounding, forward and gradient)
and its bfloat16 (where the configuration's tolerance must hold and a
coarser precision must not)."""

import jax
import jax.numpy as jnp
import pytest

from benchmark.reference import resnet18, vit
from mpi_pytorch_tpu.models import create_model_bundle

CASES = {
    "resnet18": (resnet18, dict(fused_stem=False)),
    "vit_b16": (vit, dict(attn_impl="full")),
}


def _system(name, dtype, size=32, classes=50):
    reference, kw = CASES[name]
    bundle, variables = create_model_bundle(
        name, classes, rng=jax.random.PRNGKey(0), image_size=size, dtype=dtype, **kw
    )
    if "batch_stats" in variables:
        # Not the fresh mean 0 / variance 1, so that the statistics matter.
        keys = iter(jax.random.split(jax.random.PRNGKey(1), 100))
        variables = dict(
            variables,
            batch_stats=jax.tree_util.tree_map(
                lambda x: x + 0.3 * jax.random.uniform(next(keys), x.shape),
                variables["batch_stats"],
            ),
        )
    images = jax.random.normal(jax.random.PRNGKey(2), (4, size, size, 3))
    labels = jnp.arange(4) % classes
    got = bundle.model.apply(variables, images.astype(dtype), train=False).astype(jnp.float32)
    want = reference.forward(variables, images)
    return reference, got, want, labels


def _rel(got, want):
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


@pytest.mark.parametrize("name", sorted(CASES))
def test_float32_agrees_to_rounding(name):
    reference, got, want, labels = _system(name, jnp.float32)
    assert _rel(got, want) < 2e-5  # float32 sums in another order
    assert float(reference.cross_entropy(got, labels)) == pytest.approx(
        float(reference.cross_entropy(want, labels)), abs=1e-5
    )


@pytest.mark.parametrize("name,tolerance", [("resnet18", 0.02), ("vit_b16", 0.02)])
def test_bfloat16_within_the_configurations_tolerance(name, tolerance):
    _, got, want, _ = _system(name, jnp.bfloat16)
    assert 1e-4 < _rel(got, want) < tolerance


@pytest.mark.parametrize("name,tolerance", [("resnet18", 0.02), ("vit_b16", 0.02)])
def test_a_coarser_precision_fails_the_tolerance(name, tolerance):
    """Weights rounded to an 8-bit float (e4m3: 3 mantissa bits) — what
    computing below the stated precision would look like — land outside."""
    reference, kw = CASES[name]
    bundle, variables = create_model_bundle(
        name, 50, rng=jax.random.PRNGKey(0), image_size=32, dtype=jnp.bfloat16, **kw
    )
    coarse = dict(
        variables,
        params=jax.tree_util.tree_map(
            lambda x: x.astype(jnp.float8_e4m3fn).astype(x.dtype), variables["params"]
        ),
    )
    images = jax.random.normal(jax.random.PRNGKey(2), (4, 32, 32, 3))
    got = bundle.model.apply(coarse, images.astype(jnp.bfloat16), train=False)
    assert _rel(got.astype(jnp.float32), reference.forward(variables, images)) > tolerance


@pytest.mark.parametrize("name", sorted(CASES))
def test_float32_train_mode_loss_and_gradient_agree_to_rounding(name):
    """Train mode (BatchNorm on the batch's own statistics) and the gradient
    ``correct.train_step_agreement`` compares the system's train step with."""
    reference, kw = CASES[name]
    bundle, variables = create_model_bundle(
        name, 50, rng=jax.random.PRNGKey(0), image_size=32, dtype=jnp.float32, **kw
    )
    images = jax.random.normal(jax.random.PRNGKey(2), (8, 32, 32, 3))
    labels = jnp.arange(8) % 50
    rest = {k: v for k, v in variables.items() if k != "params"}

    def loss_fn(params):
        logits, _ = bundle.model.apply(
            {"params": params, **rest}, images, train=True, mutable=["batch_stats"]
        )
        return reference.cross_entropy(logits, labels)

    got_loss, got = jax.value_and_grad(loss_fn)(variables["params"])
    want_loss, want = reference.loss_and_grads(variables, images, labels)
    assert float(got_loss) == pytest.approx(float(want_loss), abs=1e-5)
    flat = lambda tree: jnp.concatenate([x.ravel() for x in jax.tree_util.tree_leaves(tree)])
    assert _rel(flat(got), flat(want)) < 2e-4


def test_first_moment_is_found_however_optax_wraps_it():
    import optax

    from benchmark import correct

    params = {"a": jnp.ones(3), "b": {"c": jnp.ones(2)}}
    plain = optax.adam(1e-3).init(params)
    masked = optax.multi_transform(
        {"train": optax.adamw(1e-3), "freeze": optax.set_to_zero()},
        {"a": "train", "b": {"c": "train"}},
    ).init(params)
    for state in (plain, masked):
        mu = correct._first_moment(state)
        assert jax.tree_util.tree_structure(mu) == jax.tree_util.tree_structure(params)
    assert correct._first_moment(optax.sgd(1e-3).init(params)) is None
