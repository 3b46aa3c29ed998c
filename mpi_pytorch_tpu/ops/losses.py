"""Loss and metric ops.

The reference uses ``nn.CrossEntropyLoss`` (``main.py:56``, applied
``main.py:150``). With a 64 500-class head, materializing one-hot targets
(128×64500 floats per step) would waste HBM bandwidth, so the loss is the
fused integer-label softmax cross-entropy (SURVEY §7 hard-parts). Computed in
float32 regardless of compute dtype — softmax over 64 500 logits is exactly
where bfloat16 accumulates error.
"""

from __future__ import annotations

import jax.numpy as jnp
import optax

# Standard weight for the Inception-v3 auxiliary classifier loss — the
# behavior the reference *intends* but gets wrong by never unpacking the
# (logits, aux) train output (``main.py:149-150``; SURVEY §3 quirks).
AUX_LOSS_WEIGHT = 0.4


def cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Mean fused softmax CE with integer labels (≙ nn.CrossEntropyLoss).

    Labels < 0 mark padding rows (tail batches are padded to a static shape so
    XLA never recompiles); they contribute nothing to the mean.
    """
    logits = logits.astype(jnp.float32)
    valid = labels >= 0
    per_example = optax.softmax_cross_entropy_with_integer_labels(
        logits, jnp.maximum(labels, 0)
    )
    return jnp.sum(per_example * valid) / jnp.maximum(jnp.sum(valid), 1)


def classification_loss(outputs, labels: jnp.ndarray) -> jnp.ndarray:
    """Total training loss: plain CE, or CE + 0.4·aux-CE for inception's
    train-mode ``(logits, aux_logits)`` output."""
    if isinstance(outputs, tuple):
        logits, aux = outputs
        return cross_entropy(logits, labels) + AUX_LOSS_WEIGHT * cross_entropy(aux, labels)
    return cross_entropy(outputs, labels)


def accuracy_count(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Number of correct top-1 predictions (≙ reference ``main.py:179-182``).
    Padding rows (label < 0) never count as correct."""
    return jnp.sum((jnp.argmax(logits, axis=-1) == labels) & (labels >= 0))


def valid_count(labels: jnp.ndarray) -> jnp.ndarray:
    """Number of non-padding rows in a batch; a row of a token batch
    (``labels [B, S]``) counts when any of its positions is valid."""
    valid = labels >= 0
    if labels.ndim > 1:
        valid = jnp.any(valid.reshape(labels.shape[0], -1), axis=-1)
    return jnp.sum(valid.astype(jnp.int32))
