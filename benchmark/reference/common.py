"""What the references share: float32 casting, the loss and its gradient."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def f32(tree):
    return jax.tree_util.tree_map(lambda x: jnp.asarray(x, jnp.float32), tree)


def cross_entropy(logits, labels):
    """Mean softmax cross-entropy with integer labels."""
    logits = logits.astype(jnp.float32)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - picked)


def loss_and_grads(forward, variables, images, labels):
    """The train step's loss (train-mode forward, mean cross-entropy) and its
    gradient for the parameters, float32 throughout."""

    def loss_fn(params):
        logits = forward(dict(variables, params=params), images, train=True)
        return cross_entropy(logits, labels)

    return jax.value_and_grad(loss_fn)(f32(variables["params"]))
