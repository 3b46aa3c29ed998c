"""Vision Transformer in Flax (NHWC patches, TPU-native) — the zoo's
sequence-model family.

The reference zoo is seven CNNs (``models.py:16-101``); it has no attention
anywhere (SURVEY §2c). This family goes beyond parity to make the
framework's long-context machinery part of the *training path* rather than
standalone ops: the encoder's attention dispatches, per config, to plain
full attention, ring attention (``ops/ring_attention.py``), or Ulysses
all-to-all (``ops/ulysses.py``) — the same exact-numerics SP strategies,
now inside a trainable classifier that plugs into the standard
``initialize_model``/trainer/checkpoint stack like any CNN.

Architecture: patch-embed conv → learned position embeddings → pre-LN
encoder blocks (MHA + GELU MLP, residual) → final LN → global average pool
→ ``head`` Dense. GAP instead of a class token keeps the token count equal
to the patch count, so the sequence axis divides evenly over an SP mesh
axis (a class token would make S = P+1, coprime with any ring size).
All blocks are homogeneous [B, S, hidden] → [B, S, hidden] maps — exactly
the stage shape ``parallel/pipeline.py`` pipelines.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax import nn as jnn

from mpi_pytorch_tpu.models.common import Dtype


class _ProjParams(nn.Module):
    """Parameter-only twin of an ``nn.DenseGeneral``: declares the SAME
    variable tree (``<name>/kernel`` of ``kernel_shape``, lecun-normal over
    its first ``n_in`` axes as fan-in; ``<name>/bias`` over the rest, zeros —
    flax folds the init RNG by module path, so even the initial values
    match), without computing anything. Lets a caller own the matmul (the
    fused-QKV path; the rows path, whose matmuls leave ``[B, S, H·Dh]`` for
    the attention kernel) while checkpoints remain interchangeable with the
    DenseGeneral layout. ``(in, H, Dh)`` with ``n_in=1`` is
    ``DenseGeneral((H, Dh))``; ``(H, Dh, out)`` with ``n_in=2`` is
    ``DenseGeneral(out, axis=(-2, -1))``."""

    kernel_shape: tuple[int, ...]
    n_in: int = 1
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self):
        import numpy as np

        fan_in = int(np.prod(self.kernel_shape[: self.n_in]))
        features = self.kernel_shape[self.n_in :]

        def kernel_init(rng, shape, dtype):
            # DenseGeneral initializes the kernel in FLATTENED 2-D form
            # (fan-in = the contracted axes, fan-out = prod(features)) and
            # then reshapes — calling lecun-normal on the full shape directly
            # would compute fan-in from the wrong axis.
            flat = nn.linear.default_kernel_init(
                rng, (fan_in, int(np.prod(features))), dtype
            )
            return flat.reshape(shape)

        kernel = self.param("kernel", kernel_init, self.kernel_shape, self.param_dtype)
        bias = self.param(
            "bias", nn.initializers.zeros_init(), features, self.param_dtype
        )
        return kernel, bias


class MultiHeadAttention(nn.Module):
    """MHA whose core attention is pluggable: ``sp_strategy`` of ``none``
    (single-device attention — exact dense ``full``, the Pallas ``flash``
    kernel, or the single-pass kernel by name ``fused-small``, ``attn_impl``),
    ``ring``, or ``ulysses`` (both SP strategies shard the sequence over
    ``sp_mesh``'s first axis)."""

    num_heads: int
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32
    sp_strategy: str = "none"
    sp_mesh: Any = None
    # "full" is exact dense attention, executed by shape: on a TPU the
    # single-pass kernel (scores and probabilities stay in VMEM) wherever a
    # head's score tile fits it, XLA's materialized [B,H,S,S] otherwise and
    # on other backends (ops/fused_attention_small.dense_attention);
    # "fused-small" is that kernel or an error naming the shape; "flash"
    # streams k/v blocks through VMEM with an online softmax
    # (ops/flash_attention.py). Same function all three ways.
    attn_impl: str = "full"
    # Mesh whose leading (data) axis the single-pass kernel's Mosaic call
    # shard_maps over (ops/fused_attention_small.py, Multi-chip). None =
    # single call (single chip, or an spmd-mode step whose shard_map already
    # hands the kernel per-shard batches). Read by 'full' and 'fused-small'.
    dp_mesh: Any = None
    # One [D, 3·H·Dh] projection matmul instead of three [D, H·Dh] ones:
    # x is read once, one MXU dispatch, same param tree (docs/RESULTS.md
    # §4 vit_s16 row). Identical math — the concatenated matmul computes
    # each output column independently.
    qkv_fused: bool = False

    def _dp_mesh(self):
        # init traces one dummy image: nothing to split over the data axis
        # (see models/common.FusedStemBNReluPool).
        return None if self.is_initializing() else self.dp_mesh

    def _attend(self, q, k, v) -> jnp.ndarray:
        from mpi_pytorch_tpu.ops.flash_attention import flash_attention
        from mpi_pytorch_tpu.ops.fused_attention_small import (
            dense_attention,
            fused_attention_small,
        )
        from mpi_pytorch_tpu.ops.ring_attention import ring_self_attention
        from mpi_pytorch_tpu.ops.ulysses import ulysses_self_attention

        if self.sp_strategy == "none":
            if self.attn_impl == "flash":
                return flash_attention(q, k, v)
            elif self.attn_impl == "fused-small":
                return fused_attention_small(q, k, v, dp_mesh=self._dp_mesh())
            elif self.attn_impl == "full":
                return dense_attention(
                    q, k, v, dp_mesh=self._dp_mesh(), num_heads=self.num_heads
                )
            else:
                raise ValueError(f"unknown attn_impl {self.attn_impl!r}")
        elif self.sp_strategy == "ring":
            return ring_self_attention(q, k, v, self.sp_mesh)
        elif self.sp_strategy == "ulysses":
            return ulysses_self_attention(q, k, v, self.sp_mesh)
        raise ValueError(f"unknown sp_strategy {self.sp_strategy!r}")

    def _rows(self, x, head_dim: int) -> bool:
        """Whether dense attention will take the single-pass kernel for this
        input: the projections then stay plain ``[B, S, H·Dh]`` matmuls —
        the layout the kernel blocks as it lies — and no ``[B, S, H, Dh]``
        array (whose tiled layout costs a copy each way, 84 a step in
        ViT-B/16: PERF.md section 6, PR 25) exists around it. Same
        parameters, same products either way."""
        from mpi_pytorch_tpu.ops.fused_attention_small import (
            dense_attention_takes_kernel,
        )

        return (
            self.sp_strategy == "none"
            and self.attn_impl == "full"
            and dense_attention_takes_kernel(
                x.shape[0], x.shape[1], self.num_heads, head_dim, self.dtype,
                self._dp_mesh(),
            )
        )

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        hidden = x.shape[-1]
        if hidden % self.num_heads:
            raise ValueError(f"hidden {hidden} not divisible by {self.num_heads} heads")
        head_dim = hidden // self.num_heads
        heads = (self.num_heads, head_dim)
        rows = self._rows(x, head_dim)
        if self.qkv_fused or rows:
            ws, bs = zip(*(
                (w.reshape(hidden, -1), b.reshape(-1))
                for w, b in (
                    _ProjParams((hidden,) + heads, 1, self.param_dtype, name=name)()
                    for name in ("q", "k", "v")
                )
            ))
            x = x.astype(self.dtype)
            if self.qkv_fused:
                wqkv = jnp.concatenate(ws, axis=1).astype(self.dtype)
                fused = x @ wqkv + jnp.concatenate(bs).astype(self.dtype)
                q, k, v = jnp.split(fused, 3, axis=-1)  # of [B, S, 3·H·Dh]
            else:
                q, k, v = (
                    x @ w.astype(self.dtype) + b.astype(self.dtype)
                    for w, b in zip(ws, bs)
                )
            if not rows:
                q, k, v = (part.reshape(x.shape[:-1] + heads) for part in (q, k, v))
        else:
            proj = lambda name: nn.DenseGeneral(
                heads, dtype=self.dtype, param_dtype=self.param_dtype, name=name,
            )
            q, k, v = proj("q")(x), proj("k")(x), proj("v")(x)
        # The dispatch alone is the ``attention`` scope (its backward shows as
        # ``transpose(jvp(…attention))``); the projections stay outside it.
        with jax.named_scope("attention"):
            out = self._attend(q, k, v)
        if rows:
            w, b = _ProjParams(heads + (hidden,), 2, self.param_dtype, name="out")()
            return out @ w.reshape(-1, hidden).astype(self.dtype) + b.astype(self.dtype)
        return nn.DenseGeneral(
            hidden, axis=(-2, -1), dtype=self.dtype,
            param_dtype=self.param_dtype, name="out",
        )(out)


class MoEMlp(nn.Module):
    """MoE replacement for the encoder MLP: top-k routed expert FFNs over
    the tokens of the whole batch ([B, S, d] flattened to [B·S, d]).

    Routing is group-wise (``group_size`` tokens per group, ``capacity``
    slots per expert PER GROUP — see ``ops/moe.py`` ``_grouped_routing`` for
    why that is the scalable dispatch). With ``ep_mesh`` set, experts are
    sharded over the mesh's first axis and tokens travel by ``all_to_all``;
    without it, the dense evaluation of the same grouped routing runs. The
    group clamps to the per-shard token count under EP, so the two layouts
    compute the same function whenever ``group_size`` ≤ tokens/shard (and
    the no-drop tests assert it). The load-balance aux loss is sown into the
    ``losses`` collection, which the train step sums into the total loss
    (``train/step.py``)."""

    num_experts: int
    mlp_dim: int
    k: int = 2
    capacity: int | None = None  # per routing group; None → 2x balanced load
    group_size: int = 64  # tokens per routing group (see ops/moe.py grouping)
    aux_weight: float = 0.01
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32
    ep_mesh: Any = None

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        from mpi_pytorch_tpu.ops.moe import dense_moe, moe_forward, pick_group_size

        b, s, d = x.shape
        e, h = self.num_experts, self.mlp_dim
        init = nn.initializers.normal
        params = {
            "gate": self.param("gate", init(d**-0.5), (d, e), self.param_dtype),
            "w1": self.param("w1", init((2.0 / d) ** 0.5), (e, d, h), self.param_dtype),
            "b1": self.param("b1", nn.initializers.zeros, (e, h), self.param_dtype),
            "w2": self.param("w2", init((2.0 / h) ** 0.5), (e, h, d), self.param_dtype),
            "b2": self.param("b2", nn.initializers.zeros, (e, d), self.param_dtype),
        }
        params = {k_: v.astype(self.dtype) for k_, v in params.items()}
        tokens = x.reshape(b * s, d)
        # Tokens route in fixed-size groups (ops/moe.py _grouped_routing):
        # the [G, g, E, C] dispatch stays linear in token count. The group
        # is the largest divisor of the (per-shard) token count that fits
        # group_size; default capacity is 2x the perfectly-balanced
        # per-group load (the standard capacity_factor=2 headroom) —
        # overflow tokens in a group are dropped from that expert (combine
        # weight 0) like production MoEs.
        n = (
            self.ep_mesh.shape[self.ep_mesh.axis_names[0]]
            if self.ep_mesh is not None
            else 1
        )
        g = pick_group_size(b * s // n, self.group_size)
        cap = (
            self.capacity
            if self.capacity is not None
            else max(1, (2 * self.k * g) // e)
        )
        if self.ep_mesh is not None:
            y, aux = moe_forward(
                params, tokens, self.ep_mesh, k=self.k, capacity=cap,
                group_size=g,
            )
        else:
            y, aux = dense_moe(
                params, tokens, k=self.k, capacity=cap, group_size=g
            )
        self.sow(
            "losses", "moe_aux", self.aux_weight * aux,
            reduce_fn=lambda a, b_: a + b_, init_fn=lambda: jnp.zeros((), jnp.float32),
        )
        return y.reshape(b, s, d)


class EncoderBlock(nn.Module):
    """Pre-LN transformer block: x + MHA(LN(x)); x + MLP(LN(x)). The MLP is
    dense by default, or an expert-parallel MoE when ``num_experts > 0``."""

    num_heads: int
    mlp_dim: int
    dropout: float = 0.0
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32
    sp_strategy: str = "none"
    sp_mesh: Any = None
    attn_impl: str = "full"
    dp_mesh: Any = None  # the attention kernel's shard_map mesh (see MHA)
    qkv_fused: bool = False
    num_experts: int = 0
    moe_k: int = 2
    moe_capacity: int | None = None
    moe_group_size: int = 64
    ep_mesh: Any = None

    @nn.compact
    def __call__(self, x: jnp.ndarray, train: bool) -> jnp.ndarray:
        ln = lambda name: nn.LayerNorm(
            dtype=self.dtype, param_dtype=self.param_dtype, name=name
        )
        y = MultiHeadAttention(
            num_heads=self.num_heads, dtype=self.dtype,
            param_dtype=self.param_dtype, sp_strategy=self.sp_strategy,
            sp_mesh=self.sp_mesh, attn_impl=self.attn_impl,
            dp_mesh=self.dp_mesh, qkv_fused=self.qkv_fused, name="attn",
        )(ln("ln1")(x))
        y = nn.Dropout(self.dropout, deterministic=not train)(y)
        x = x + y

        z = ln("ln2")(x)
        if self.num_experts > 0:
            z = MoEMlp(
                num_experts=self.num_experts, mlp_dim=self.mlp_dim,
                k=self.moe_k, capacity=self.moe_capacity,
                group_size=self.moe_group_size,
                dtype=self.dtype, param_dtype=self.param_dtype,
                ep_mesh=self.ep_mesh, name="moe",
            )(z)
        else:
            z = nn.Dense(
                self.mlp_dim, dtype=self.dtype, param_dtype=self.param_dtype,
                name="mlp1",
            )(z)
            z = jnn.gelu(z)
            z = nn.Dense(
                x.shape[-1], dtype=self.dtype, param_dtype=self.param_dtype,
                name="mlp2",
            )(z)
        z = nn.Dropout(self.dropout, deterministic=not train)(z)
        return x + z


class VisionTransformer(nn.Module):
    num_classes: int
    patch_size: int = 16
    hidden: int = 384
    depth: int = 12
    num_heads: int = 6
    mlp_dim: int = 1536
    dropout: float = 0.0
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32
    # Checkpoint each encoder block (nn.remat), same lever as the resnets'
    # remat_blocks: backward recomputes one homogeneous block at a time.
    remat_blocks: bool = False
    sp_strategy: str = "none"
    sp_mesh: Any = None
    attn_impl: str = "full"
    dp_mesh: Any = None  # the attention kernel's shard_map mesh (see MHA)
    qkv_fused: bool = False
    # MoE: every `moe_every`-th block (0-indexed blocks moe_every-1,
    # 2·moe_every-1, ...; =2 → the odd blocks) swaps its dense MLP for a
    # `num_experts`-expert MoE. 0 disables.
    moe_every: int = 0
    num_experts: int = 8
    moe_k: int = 2
    moe_capacity: int | None = None
    moe_group_size: int = 64
    ep_mesh: Any = None

    @nn.compact
    def __call__(self, x: jnp.ndarray, train: bool = False) -> jnp.ndarray:
        p = self.patch_size
        if x.shape[1] % p or x.shape[2] % p:
            raise ValueError(f"image {x.shape[1]}x{x.shape[2]} not divisible by patch {p}")
        x = nn.Conv(
            self.hidden, (p, p), strides=(p, p), padding="VALID",
            dtype=self.dtype, param_dtype=self.param_dtype, name="patch_embed",
        )(x)
        b, gh, gw, c = x.shape
        x = x.reshape(b, gh * gw, c)
        pos = self.param(
            "pos_embed",
            nn.initializers.normal(stddev=0.02),
            (1, gh * gw, c),
            self.param_dtype,
        )
        x = x + pos.astype(x.dtype)
        x = nn.Dropout(self.dropout, deterministic=not train)(x)

        block_cls = (
            nn.remat(EncoderBlock, static_argnums=(2,))  # (self, x, train)
            if self.remat_blocks
            else EncoderBlock
        )
        for i in range(self.depth):
            is_moe = self.moe_every > 0 and i % self.moe_every == self.moe_every - 1
            x = block_cls(
                num_heads=self.num_heads, mlp_dim=self.mlp_dim,
                dropout=self.dropout, dtype=self.dtype,
                param_dtype=self.param_dtype, sp_strategy=self.sp_strategy,
                sp_mesh=self.sp_mesh, attn_impl=self.attn_impl,
                dp_mesh=self.dp_mesh, qkv_fused=self.qkv_fused,
                num_experts=self.num_experts if is_moe else 0,
                moe_k=self.moe_k, moe_capacity=self.moe_capacity,
                moe_group_size=self.moe_group_size,
                ep_mesh=self.ep_mesh, name=f"block{i}",
            )(x, train)
        x = nn.LayerNorm(dtype=self.dtype, param_dtype=self.param_dtype, name="ln")(x)
        x = x.mean(axis=1)  # GAP over tokens (see module docstring)
        return nn.Dense(
            self.num_classes, dtype=self.dtype, param_dtype=self.param_dtype,
            name="head",
        )(x)


def vit_s16(num_classes: int, **kw: Any) -> VisionTransformer:
    """ViT-Small/16: 384 hidden, 12 blocks, 6 heads."""
    return VisionTransformer(num_classes=num_classes, **kw)


def vit_b16(num_classes: int, **kw: Any) -> VisionTransformer:
    """ViT-Base/16: 768 hidden, 12 blocks, 12 heads."""
    return VisionTransformer(
        num_classes=num_classes, hidden=768, num_heads=12, mlp_dim=3072, **kw
    )


def vit_moe_s16(num_classes: int, **kw: Any) -> VisionTransformer:
    """ViT-Small/16 with 8-expert top-2 MoE MLPs in every other block —
    the EP training-path model (dense routing until ``ep_mesh`` is set)."""
    kw.setdefault("moe_every", 2)
    kw.setdefault("num_experts", 8)
    return VisionTransformer(num_classes=num_classes, **kw)
