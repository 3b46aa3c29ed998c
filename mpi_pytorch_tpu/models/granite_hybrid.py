"""Granite 4.0-H decoder (IBM ``model_type: granitemoehybrid``, dense members:
e.g. granite-4.0-h-micro): a token model — ``int32 [B, S]`` ids in,
``[B, S, V]`` next-token logits out — whose mixers are Mamba-2 state-space
layers with an attention layer every few.

The layer equations are the source's ``modeling_granitemoehybrid``:

- ``h = embed[tokens] * embedding_multiplier``; block: ``h += residual_multiplier
  * mixer(RMSNorm(h)); h += residual_multiplier * mlp(RMSNorm(h))``; ``logits =
  RMSNorm(h) embed^T / logits_scaling`` — the head IS the embedding
  (``tie_word_embeddings``): one leaf, used twice;
- ``mixer`` by ``layer_types[i]``: ``attention`` — q, k, v, out projections
  without bias, ``num_key_value_heads`` serving ``num_attention_heads`` query
  heads, NO positional embedding (``position_embedding_type: nope``), causal
  softmax of ``q k^T * attention_multiplier`` — or ``mamba`` — ``[z, xBC, dt] =
  x W_in``; ``xBC = silu(causal depthwise conv(xBC) + bias)`` (``mamba_d_conv``
  taps); ``x, B, C = split(xBC)``; ``dt = softplus(dt + dt_bias)``; the
  selective state-space recurrence (``ops/ssd.py``, chunks of
  ``mamba_chunk_size``); ``y = RMSNorm(y * silu(z)) * w`` (the gate before the
  norm; the mean square over each of ``mamba_n_groups`` groups of channels
  apart — this model has one group, all channels); ``out = y W_out``;
- ``mlp``: SwiGLU of ``shared_intermediate_size`` (the source fuses gate and
  up into one ``input_linear``; here they are the two matrices ``w1``, ``w3``).

The architecture arrives one way, ``--model-config`` (a JSON object, inline or
a file's path) in the source's own key names; ``GraniteHybridConfig`` reads it.
A sliced vocabulary is a smaller ``vocab_size``.

``Mamba2`` and ``Attention`` take SIZES (``Mamba2Sizes``; heads, head size,
scale), not this family's configuration: ``models/nemotron_h.py`` builds its
mixers from the same two definitions.

Device scopes: ``mamba`` around the whole state-space mixer, inside it
``mamba/conv`` (convolution, bias, SiLU), ``mamba/scan`` (softplus, decays,
cumulative sums, the four chunk products, the state recurrence, ``D x``),
``mamba/gate_norm``; ``attention`` (the attention call; the projections stay
outside); ``mlp`` around the feed-forward; ``head``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from mpi_pytorch_tpu.models.lfm2 import (
    RMSNorm, SwiGLU, _init, causal_attention, causal_depthwise_conv1d, model_config_json, rms_norm,
)

Dtype = Any

_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig:
    """The source's ``config.json`` keys this module reads (defaults:
    granite-4.0-h-micro's published values)."""

    hidden_size: int = 2048
    shared_intermediate_size: int = 8192
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    layer_types: tuple = _PERIOD * 4
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_chunk_size: int = 256
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0
    rms_norm_eps: float = 1e-5
    vocab_size: int = 100352

    @property
    def mamba_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def mamba(self) -> "Mamba2Sizes":
        return Mamba2Sizes(
            self.mamba_n_heads, self.mamba_d_head, self.mamba_d_state, self.mamba_n_groups,
            self.mamba_d_conv, self.mamba_chunk_size, self.rms_norm_eps,
        )

    @classmethod
    def parse(cls, text: str) -> "GraniteHybridConfig":
        """From ``--model-config``: a JSON object, or the path of a file that
        holds one. Keys this module does not read (``model_type``,
        ``max_position_embeddings``, ``rope_theta``, ...) pass; a key whose
        value this module cannot honour is an error that names it."""
        if not text:
            return cls()
        raw = model_config_json(text)
        only = (
            ("num_local_experts", 0), ("num_experts_per_tok", 0), ("position_embedding_type", "nope"),
            ("attention_bias", False), ("mamba_conv_bias", True), ("mamba_proj_bias", False),
            ("tie_word_embeddings", True), ("hidden_act", "silu"), ("normalization_function", "rmsnorm"),
        )
        for key, want in only:
            if raw.get(key, want) != want:
                raise ValueError(f"model-config: {key}={raw[key]!r} is not implemented (only {want!r})")
        known = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in raw.items() if k in known}
        if "layer_types" in kw:
            kw["layer_types"] = tuple(kw["layer_types"])
        cfg = cls(**kw)
        depth = raw.get("num_hidden_layers", len(cfg.layer_types))
        if depth != len(cfg.layer_types):
            raise ValueError(
                f"model-config: num_hidden_layers {depth} but {len(cfg.layer_types)} layer_types"
            )
        unknown = sorted(set(cfg.layer_types) - {"mamba", "attention"})
        if unknown:
            raise ValueError(f"model-config: layer type {unknown[0]!r} is not implemented")
        if cfg.num_attention_heads % cfg.num_key_value_heads:
            raise ValueError("model-config: num_key_value_heads must divide num_attention_heads")
        if cfg.mamba_n_heads % cfg.mamba_n_groups:
            raise ValueError(
                f"model-config: mamba_n_groups {cfg.mamba_n_groups} must divide mamba_n_heads {cfg.mamba_n_heads}"
            )
        if cfg.mamba_inner != cfg.mamba_expand * cfg.hidden_size:
            raise ValueError(
                f"model-config: mamba_n_heads x mamba_d_head = {cfg.mamba_inner} is not "
                f"mamba_expand x hidden_size = {cfg.mamba_expand * cfg.hidden_size}"
            )
        return cfg


def _a_log_init(key, shape, dtype=jnp.float32):
    """``A = -uniform(1, 16)`` a head (the Mamba-2 paper's initialisation)."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """The inverse softplus of a step log-uniform in 1e-3 .. 1e-1."""
    dt = jnp.exp(jax.random.uniform(key, shape, dtype, math.log(1e-3), math.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))


@dataclasses.dataclass(frozen=True)
class Mamba2Sizes:
    """What a Mamba-2 mixer is, whoever's configuration says it: ``heads`` of
    ``head_dim`` channels, ``groups`` of B and C with ``state`` entries each
    (a group serves ``heads / groups`` heads and is one group of the gated
    norm), ``conv`` taps, chunks of ``chunk`` positions."""

    heads: int
    head_dim: int
    state: int
    groups: int
    conv: int
    chunk: int
    eps: float

    @property
    def inner(self) -> int:
        return self.heads * self.head_dim


def grouped_rms_norm(x, scale, eps, groups: int):
    """``rms_norm`` with the mean square taken over each of ``groups`` equal
    runs of the last axis apart; one group is ``rms_norm``."""
    if groups == 1:
        return rms_norm(x, scale, eps)
    split = x.reshape(x.shape[:-1] + (groups, x.shape[-1] // groups))
    return rms_norm(split, 1.0, eps).reshape(x.shape) * scale


class Mamba2(nn.Module):
    sizes: Mamba2Sizes
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        from mpi_pytorch_tpu.ops.ssd import ssd

        m = self.sizes
        d, inner, heads = x.shape[-1], m.inner, m.heads
        bc = m.groups * m.state
        w_in = self.param("in_proj", _init(), (d, 2 * inner + 2 * bc + heads), self.param_dtype)
        conv_w = self.param("conv_w", _init(m.conv**-0.5), (m.conv, inner + 2 * bc), self.param_dtype)
        conv_b = self.param("conv_b", nn.initializers.zeros, (inner + 2 * bc,), self.param_dtype)
        dt_bias = self.param("dt_bias", _dt_bias_init, (heads,), jnp.float32)
        a_log = self.param("A_log", _a_log_init, (heads,), jnp.float32)
        skip = self.param("D", nn.initializers.ones, (heads,), jnp.float32)
        norm_w = self.param("norm", nn.initializers.ones, (inner,), self.param_dtype)
        w_out = self.param("out_proj", _init(), (inner, d), self.param_dtype)
        with jax.named_scope("mamba"):
            z, xbc, dt = jnp.split(x @ w_in.astype(self.dtype), [inner, 2 * inner + 2 * bc], axis=-1)
            with jax.named_scope("mamba/conv"):
                xbc = jax.nn.silu(
                    causal_depthwise_conv1d(xbc, conv_w.astype(self.dtype)) + conv_b.astype(self.dtype)
                )
            u, b, c = jnp.split(xbc, [inner, inner + bc], axis=-1)
            with jax.named_scope("mamba/scan"):
                step = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)
            y = ssd(
                u.reshape(u.shape[:2] + (heads, m.head_dim)), step, a_log,
                b.reshape(b.shape[:2] + (m.groups, m.state)),
                c.reshape(c.shape[:2] + (m.groups, m.state)),
                skip, chunk=m.chunk,
            ).reshape(u.shape)
            with jax.named_scope("mamba/gate_norm"):
                y = grouped_rms_norm(y * jax.nn.silu(z), norm_w, m.eps, m.groups).astype(self.dtype)
            return y @ w_out.astype(self.dtype)


class Attention(nn.Module):
    """Causal grouped-query attention WITHOUT a positional embedding:
    ``heads`` query heads of ``head_dim`` read ``kv_heads`` key-value heads;
    softmax of ``q k^T * scale`` (None: ``head_dim ** -0.5``)."""

    heads: int
    kv_heads: int
    head_dim: int
    scale: float | None = None
    attn_impl: str = "full"
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        d, h, hkv, dh = x.shape[-1], self.heads, self.kv_heads, self.head_dim
        proj = lambda name, heads: self.param(name, _init(), (d, heads, dh), self.param_dtype)
        wq, wk, wv = proj("q", h), proj("k", hkv), proj("v", hkv)
        wo = self.param("out", _init(), (h, dh, d), self.param_dtype)
        q = jnp.einsum("bsd,dhk->bshk", x, wq.astype(self.dtype))
        k = jnp.einsum("bsd,dhk->bshk", x, wk.astype(self.dtype))
        v = jnp.einsum("bsd,dhk->bshk", x, wv.astype(self.dtype))
        with jax.named_scope("attention"):
            out = causal_attention(self, q, k, v, self.attn_impl, scale=self.scale)
        return jnp.einsum("bshk,hkd->bsd", out, wo.astype(self.dtype))


class Block(nn.Module):
    cfg: GraniteHybridConfig
    index: int
    attn_impl: str = "full"
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        cfg, kw = self.cfg, dict(dtype=self.dtype, param_dtype=self.param_dtype)
        scale = jnp.asarray(cfg.residual_multiplier, self.dtype)
        h = RMSNorm(cfg.rms_norm_eps, name="mixer_norm", **kw)(x)
        if cfg.layer_types[self.index] == "attention":
            heads = cfg.num_attention_heads
            x = x + scale * Attention(
                heads, cfg.num_key_value_heads, cfg.hidden_size // heads, cfg.attention_multiplier,
                self.attn_impl, name="attn", **kw,
            )(h)
        else:
            x = x + scale * Mamba2(cfg.mamba, name="mamba", **kw)(h)
        h = RMSNorm(cfg.rms_norm_eps, name="mlp_norm", **kw)(x)
        with jax.named_scope("mlp"):
            return x + scale * SwiGLU(cfg.shared_intermediate_size, name="mlp", **kw)(h)


class GraniteHybrid(nn.Module):
    cfg: GraniteHybridConfig
    attn_impl: str = "full"
    remat_blocks: bool = False
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    def setup(self):
        cfg, kw = self.cfg, dict(dtype=self.dtype, param_dtype=self.param_dtype)
        self.embed = nn.Embed(cfg.vocab_size, cfg.hidden_size, embedding_init=_init(), **kw)
        block = nn.remat(Block) if self.remat_blocks else Block
        for i in range(len(cfg.layer_types)):
            setattr(self, f"layer{i}", block(cfg, i, self.attn_impl, **kw))
        self.norm = RMSNorm(cfg.rms_norm_eps, **kw)

    def hidden(self, tokens):
        """``[B, S, D]`` after the last block, before the final norm: what
        every chip that shares the layers computes alike."""
        x = self.embed(tokens) * jnp.asarray(self.cfg.embedding_multiplier, self.dtype)
        for i in range(len(self.cfg.layer_types)):
            x = getattr(self, f"layer{i}")(x)
        return x

    def head(self, x):
        """The final norm and the tied head over the embedding rows held here."""
        x = self.norm(x)
        with jax.named_scope("head"):
            return self.embed.attend(x) / jnp.asarray(self.cfg.logits_scaling, self.dtype)

    def __call__(self, tokens, train: bool = False):
        return self.head(self.hidden(tokens))


def granitemoehybrid(num_classes: int, *, model_config: str = "", **kw: Any) -> GraniteHybrid:
    """``num_classes`` is the image models' head size and is not read: the
    vocabulary is the configuration's."""
    del num_classes
    return GraniteHybrid(cfg=GraniteHybridConfig.parse(model_config), **kw)


def granite_vocab(model_config: str) -> int:
    """The vocabulary ``granitemoehybrid`` would be built with (``ModelSpec.vocab``)."""
    return GraniteHybridConfig.parse(model_config).vocab_size
