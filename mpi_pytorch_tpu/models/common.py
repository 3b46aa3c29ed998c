"""Shared building blocks for the Flax CNN zoo.

All models are NHWC (TPU-native layout: channels last keeps the lane dimension
dense for the VPU/MXU), take a ``train`` flag for BatchNorm/Dropout mode, and
thread ``dtype`` (compute, bfloat16 by default on TPU) separately from
``param_dtype`` (float32 master params).
"""

from __future__ import annotations

from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
from flax import linen as nn

Dtype = Any

# torch BatchNorm defaults: eps=1e-5, momentum=0.1 (flax momentum = 1-0.1).
BN_MOMENTUM = 0.9
BN_EPS = 1e-5


def batch_norm(
    name: str | None = None,
    *,
    dtype: Dtype = jnp.float32,
    axis_name: str | None = None,
    eps: float = BN_EPS,
) -> nn.BatchNorm:
    """BatchNorm matching torch defaults. ``axis_name=None`` keeps per-replica
    local batch statistics — the reference's data-parallel semantics (only
    grads are synced, ``mpi_tools.py:30-37``; SURVEY §7 'BatchNorm under DP').
    Pass the mesh data axis name to opt into sync-BN. ``eps`` for families
    that deviate from torch's 1e-5 default (efficientnet uses 1e-3)."""
    return nn.BatchNorm(
        use_running_average=None,  # caller passes via __call__
        momentum=BN_MOMENTUM,
        epsilon=eps,
        dtype=dtype,
        axis_name=axis_name,
        name=name,
    )


class FusedStemBNReluPool(nn.Module):
    """BatchNorm + ReLU + 3×3/s2/p1 max-pool as ONE fused op — the resnet
    stem tail (reference ``models.py:30-45`` → torchvision ``bn1``/``relu``/
    ``maxpool``), executed by the ``ops/fused_stem.py`` Pallas kernel pair
    on TPU (docs/RESULTS.md §4d: removes the 1 GB intermediate activation
    and the select-and-scatter backward from the HBM budget).

    Variable layout is IDENTICAL to ``batch_norm(name)`` + separate pool:
    params ``{scale, bias}``, batch_stats ``{mean, var}`` (biased batch
    variance, torch/flax momentum convention) — checkpoints move freely
    between the fused and unfused stem. Stats are computed in f32 from the
    conv output (XLA fuses that reduce into the conv epilogue, as it does
    for the unfused path); the kernel receives the folded affine
    a = γ·rsqrt(var+ε), b = β − μ·a. Sync-BN (``axis_name``) is not
    supported here — the fused stem exists for the reference's local-BN
    data-parallel semantics (``mpi_tools.py:30-37``)."""

    momentum: float = BN_MOMENTUM
    eps: float = BN_EPS
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32
    # Multi-chip: mesh whose leading (data) axis partitions the Mosaic call
    # via shard_map (ops/fused_stem.py, Multi-chip). The BN statistics above
    # the kernel stay GLOBAL-batch reductions either way (GSPMD lowers them
    # to cross-device means under auto-jit — identical to the unfused stem).
    dp_mesh: Any = None

    @nn.compact
    def __call__(self, y: jnp.ndarray, use_running_average: bool) -> jnp.ndarray:
        from mpi_pytorch_tpu.ops.fused_stem import stem_affine_relu_pool

        c = y.shape[-1]
        scale = self.param("scale", nn.initializers.ones, (c,), self.param_dtype)
        bias = self.param("bias", nn.initializers.zeros, (c,), self.param_dtype)
        ra_mean = self.variable(
            "batch_stats", "mean", lambda: jnp.zeros((c,), jnp.float32)
        )
        ra_var = self.variable(
            "batch_stats", "var", lambda: jnp.ones((c,), jnp.float32)
        )
        if use_running_average:
            mean, var = ra_mean.value, ra_var.value
        else:
            yf = y.astype(jnp.float32)
            mean = yf.mean(axis=(0, 1, 2))
            var = jnp.square(yf).mean(axis=(0, 1, 2)) - jnp.square(mean)
            if not self.is_initializing():
                ra_mean.value = (
                    self.momentum * ra_mean.value + (1 - self.momentum) * mean
                )
                ra_var.value = (
                    self.momentum * ra_var.value + (1 - self.momentum) * var
                )
        a = scale.astype(jnp.float32) * jax.lax.rsqrt(var + self.eps)
        b = bias.astype(jnp.float32) - mean * a
        # flax init traces ONE dummy image for shapes: nothing to split over
        # the data axis (found on four chips, PR 21 — a batch of 1 does not
        # divide 4, which the kernel refuses on a TPU), so init runs the
        # single un-partitioned call.
        dp_mesh = None if self.is_initializing() else self.dp_mesh
        # Output in the module's compute dtype, matching what the unfused
        # batch_norm(dtype=...) -> relu -> pool composition produces.
        return stem_affine_relu_pool(y, a, b, dp_mesh=dp_mesh).astype(self.dtype)


def max_pool(x: jnp.ndarray, window: int, stride: int, padding: Any = "VALID") -> jnp.ndarray:
    """XLA reduce_window max pool (select-and-scatter backward).

    An XLA-level index-based alternative (round 4's ``ops/pooling.py``)
    measured WORSE as a general drop-in — XLA materializes the scatter's
    dilated pads (or the phase-interleave copies) instead of fusing them,
    regressing the resnet18 roofline bound 62.4→79.5 ms — and was deleted
    once ``ops/fused_stem.py`` landed the same byte win properly in VMEM
    (docs/RESULTS.md §4d records both; git history has the code)."""
    if isinstance(padding, int):
        padding = [(padding, padding), (padding, padding)]
    return nn.max_pool(x, (window, window), strides=(stride, stride), padding=padding)



def adaptive_avg_pool(x: jnp.ndarray, out_hw: tuple[int, int]) -> jnp.ndarray:
    """torch AdaptiveAvgPool2d for static input shapes.

    Output cell (i, j) averages rows [floor(i*H/th), ceil((i+1)*H/th)) — the
    exact torch window algorithm. Shapes are static under jit, so the window
    arithmetic unrolls at trace time into th+tw strided slices; XLA fuses the
    means. Separable because the window bounds factor by axis.
    """
    th, tw = out_hw
    h, w = x.shape[1], x.shape[2]
    if h == th and w == tw:
        return x
    if h % th == 0 and w % tw == 0:
        # Fast path: equal windows → single reshape-mean (the common case).
        x = x.reshape(x.shape[0], th, h // th, tw, w // tw, x.shape[3])
        return x.mean(axis=(2, 4))
    rows = [
        x[:, (i * h) // th : -(-((i + 1) * h) // th), :, :].mean(axis=1, keepdims=True)
        for i in range(th)
    ]
    x = jnp.concatenate(rows, axis=1)
    cols = [
        x[:, :, (j * w) // tw : -(-((j + 1) * w) // tw), :].mean(axis=2, keepdims=True)
        for j in range(tw)
    ]
    return jnp.concatenate(cols, axis=2)


def global_avg_pool(x: jnp.ndarray) -> jnp.ndarray:
    return x.mean(axis=(1, 2))


class Classifier(nn.Module):
    """Final dense head. Kept as its own module so (a) `feature_extract`
    freezing can target the `head` subtree by name across every architecture
    (parity: the reference swaps/unfreezes exactly this layer,
    ``models.py:36,44,53,62,80``), and (b) tensor-parallel sharding rules can
    match the 64 500-wide kernel by path (`.../head/kernel`)."""

    num_classes: int
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        return nn.Dense(
            self.num_classes, dtype=self.dtype, param_dtype=self.param_dtype, name="head"
        )(x)


def head_filter(path: Sequence[str]) -> bool:
    """True for params belonging to a classification head — the subtree that
    stays trainable under feature_extract (reference ``models.py:5-13`` +
    head swap)."""
    return any(p in ("head", "aux_head") for p in path)
