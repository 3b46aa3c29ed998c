"""LFM2-MoE decoder (LiquidAI ``model_type: lfm2_moe``, e.g. LFM2-24B-A2B):
a token model — ``int32 [B, S]`` ids in, ``[B, S, V]`` next-token logits out.

The layer equations are the source's ``modeling_lfm2_moe``:

- block: ``x += op(RMSNorm(x)); x += ffn(RMSNorm(x))``; a final RMSNorm
  before the (untied) head;
- ``op`` by ``layer_types[i]``: ``conv`` — the gated short convolution
  ``B, C, u = split3(W_in h); y = W_out (C * causal_depthwise_conv1d(B * u))``
  (``conv_L_cache`` taps, no bias) — or ``full_attention`` — q, k, v, out
  projections without bias, RMSNorm over each head's dims of q and k, RoPE
  (rotate-half, all head dims), causal, scale ``head_dim ** -0.5``,
  ``num_key_value_heads`` serving ``num_attention_heads`` query heads;
- ``ffn``: the first ``num_dense_layers`` layers a SwiGLU of
  ``intermediate_size``; the others a mixture of experts
  (``ops/moe.dropless_moe``): sigmoid scores over all routed experts in
  float32, top-``num_experts_per_tok`` of score + ``expert_bias`` (a buffer:
  it steers the selection and takes no gradient), normalised weights times
  ``routed_scaling_factor``, SwiGLU experts of ``moe_intermediate_size``. No
  capacity, no dropped token, no auxiliary loss.

The architecture arrives one way, ``--model-config`` (a JSON object, inline or
a file's path) in the source's own key names; ``Lfm2Config`` reads it. Three
keys are this system's, for one expert-parallel rank's share: ``num_experts``
counts the experts HELD here, ``num_experts_routed`` the router's width
(absent: every expert is held), ``expert_offset`` the first held id. What the
absent experts would add to a token is left out; the pairs routed to them are
counted (``moe_pairs_absent``). A sliced vocabulary is a smaller
``vocab_size``.

Device scopes: ``shortconv``, ``attention`` (q/k norms, RoPE and the
attention call; the projections stay outside), ``moe`` (with ``moe/route``,
``moe/dispatch``, ``moe/experts``, ``moe/combine`` beneath), ``head``. The
expert layers sow ``moe_pairs_held`` / ``moe_pairs_absent`` / ``moe_load_max`` /
``moe_rows_computed`` into the ``counters`` collection, which the train step
sums (``_max``: takes the largest of) into its step metrics.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax import lax

Dtype = Any

INIT_STD = 0.02  # the source's ``initializer_range``


def model_config_json(text: str) -> dict:
    """``--model-config`` as a dict: a JSON object, or the path of a file that
    holds one (what every configured token model's ``parse`` starts from)."""
    if not text.lstrip().startswith("{"):
        with open(text) as f:
            text = f.read()
    return json.loads(text)


@dataclasses.dataclass(frozen=True)
class Lfm2Config:
    """The source's ``config.json`` keys this module reads (defaults:
    LFM2-24B-A2B's published values), plus the share keys above."""

    hidden_size: int = 2048
    intermediate_size: int = 11776
    moe_intermediate_size: int = 1536
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    layer_types: tuple = ("conv", "conv", "full_attention") + ("conv", "conv", "conv", "full_attention") * 9 + ("conv",)
    num_dense_layers: int = 2
    num_experts: int = 64
    num_experts_routed: int | None = None
    expert_offset: int = 0
    num_experts_per_tok: int = 4
    norm_eps: float = 1e-5
    routed_scaling_factor: float = 1.0
    conv_L_cache: int = 3
    vocab_size: int = 65536
    rope_theta: float = 1e6

    @property
    def routed(self) -> int:
        return self.num_experts_routed or self.num_experts

    @classmethod
    def parse(cls, text: str) -> "Lfm2Config":
        """From ``--model-config``: a JSON object, or the path of a file that
        holds one. Keys this module does not read (``model_type``,
        ``max_position_embeddings``, ...) pass; a key whose value this module
        cannot honour is an error that names it."""
        if not text:
            return cls()
        raw = model_config_json(text)
        for key, want in (("conv_bias", False), ("norm_topk_prob", True), ("use_expert_bias", True)):
            if raw.get(key, want) != want:
                raise ValueError(f"model-config: {key}={raw[key]!r} is not implemented (only {want})")
        known = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in raw.items() if k in known}
        if "rope_parameters" in raw:
            kw["rope_theta"] = float(raw["rope_parameters"]["rope_theta"])
        if "layer_types" in kw:
            kw["layer_types"] = tuple(kw["layer_types"])
        cfg = cls(**kw)
        depth = raw.get("num_hidden_layers", len(cfg.layer_types))
        if depth != len(cfg.layer_types):
            raise ValueError(
                f"model-config: num_hidden_layers {depth} but {len(cfg.layer_types)} layer_types"
            )
        unknown = sorted(set(cfg.layer_types) - {"conv", "full_attention"})
        if unknown:
            raise ValueError(f"model-config: layer type {unknown[0]!r} is not implemented")
        if cfg.num_attention_heads % cfg.num_key_value_heads:
            raise ValueError("model-config: num_key_value_heads must divide num_attention_heads")
        if not 0 <= cfg.expert_offset <= cfg.routed - cfg.num_experts:
            raise ValueError(
                f"model-config: experts {cfg.expert_offset}..{cfg.expert_offset + cfg.num_experts} "
                f"are not among the {cfg.routed} routed"
            )
        return cfg


class RMSNorm(nn.Module):
    eps: float
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],), self.param_dtype)
        return rms_norm(x, scale, self.eps).astype(self.dtype)


def rms_norm(x, scale, eps):
    """float32 statistics whatever the compute dtype."""
    xf = x.astype(jnp.float32)
    return xf * lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps) * scale


def _init(std: float = INIT_STD):
    return nn.initializers.normal(stddev=std)


def causal_depthwise_conv1d(x, taps):
    """``[B, S, D]`` against ``taps [K, D]``: output t is ``sum_j taps[j] *
    x[t - (K-1) + j]``, zeros before the sequence's start."""
    k, s = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(padded[:, j : j + s] * taps[j] for j in range(k))


class ShortConv(nn.Module):
    cfg: Lfm2Config
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        d = self.cfg.hidden_size
        taps = self.cfg.conv_L_cache
        w_in = self.param("in_proj", _init(), (d, 3 * d), self.param_dtype)
        conv = self.param("conv", _init(taps**-0.5), (taps, d), self.param_dtype)
        w_out = self.param("out_proj", _init(), (d, d), self.param_dtype)
        with jax.named_scope("shortconv"):
            b_gate, c_gate, u = jnp.split(x @ w_in.astype(self.dtype), 3, axis=-1)
            y = c_gate * causal_depthwise_conv1d(b_gate * u, conv.astype(self.dtype))
            return y @ w_out.astype(self.dtype)


def rope(x, theta: float):
    """``[B, S, H, Dh]`` rotated by position (rotate-half, float32 angles)."""
    s, dh = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)[None, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return x.astype(jnp.float32) * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def causal_attention(module: nn.Module, q, k, v, attn_impl: str, scale: float | None = None):
    """Causal grouped-query attention of ``q [B, S, H, Dh]`` over ``k``, ``v``
    ``[B, S, Hkv, Dh]`` by ``attn_impl`` (``flash``: the Pallas kernels;
    ``full``: XLA's materialized scores); ``scale`` None is ``Dh ** -0.5``."""
    from mpi_pytorch_tpu.ops.flash_attention import flash_attention
    from mpi_pytorch_tpu.ops.ring_attention import full_attention

    # init traces one short dummy sequence for the parameters' shapes:
    # XLA's composition will do, whatever the backend.
    impl = "full" if module.is_initializing() else attn_impl
    if impl == "flash":
        # The kernel sizes its own tiles from the shape it sees (ops/flash_attention.py).
        return flash_attention(q, k, v, causal=True, scale=scale)
    if impl == "full":
        # XLA's materialized scores have one head layout: k and v repeat.
        k, v = (jnp.repeat(t, q.shape[2] // k.shape[2], axis=2) for t in (k, v))
        return full_attention(q, k, v, causal=True, scale=scale)
    raise ValueError(f"attn_impl {attn_impl!r} is not implemented for a token model (full|flash)")


class Attention(nn.Module):
    cfg: Lfm2Config
    attn_impl: str = "full"
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        d, h, hkv = cfg.hidden_size, cfg.num_attention_heads, cfg.num_key_value_heads
        dh = d // h
        proj = lambda name, heads: self.param(name, _init(), (d, heads, dh), self.param_dtype)
        wq, wk, wv = proj("q", h), proj("k", hkv), proj("v", hkv)
        wo = self.param("out", _init(), (h, dh, d), self.param_dtype)
        q_scale = self.param("q_norm", nn.initializers.ones, (dh,), self.param_dtype)
        k_scale = self.param("k_norm", nn.initializers.ones, (dh,), self.param_dtype)
        q = jnp.einsum("bsd,dhk->bshk", x, wq.astype(self.dtype))
        k = jnp.einsum("bsd,dhk->bshk", x, wk.astype(self.dtype))
        v = jnp.einsum("bsd,dhk->bshk", x, wv.astype(self.dtype))
        with jax.named_scope("attention"):
            q = rope(rms_norm(q, q_scale, cfg.norm_eps), cfg.rope_theta).astype(self.dtype)
            k = rope(rms_norm(k, k_scale, cfg.norm_eps), cfg.rope_theta).astype(self.dtype)
            out = causal_attention(self, q, k, v, self.attn_impl)
        return jnp.einsum("bshk,hkd->bsd", out, wo.astype(self.dtype))


class SwiGLU(nn.Module):
    width: int
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        d = x.shape[-1]
        w1 = self.param("w1", _init(), (d, self.width), self.param_dtype)
        w3 = self.param("w3", _init(), (d, self.width), self.param_dtype)
        w2 = self.param("w2", _init(), (self.width, d), self.param_dtype)
        gate = jax.nn.silu(x @ w1.astype(self.dtype)) * (x @ w3.astype(self.dtype))
        return gate @ w2.astype(self.dtype)


class MoE(nn.Module):
    """The expert layer, told which experts it holds (``num_experts`` of
    ``num_experts_routed``, from ``expert_offset``)."""

    cfg: Lfm2Config
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        from mpi_pytorch_tpu.ops.moe import dropless_moe

        cfg = self.cfg
        d, f, held = cfg.hidden_size, cfg.moe_intermediate_size, cfg.num_experts
        gate = self.param("gate", _init(), (d, cfg.routed), self.param_dtype)
        # A buffer in the source (no update rule is published): it is drawn
        # once, steers the selection, and ``dropless_moe`` stops its gradient,
        # so the optimizer's update of it is exactly zero.
        bias = self.param("expert_bias", _init(0.01), (cfg.routed,), jnp.float32)
        w1 = self.param("w1", _init(), (held, d, f), self.param_dtype)
        w3 = self.param("w3", _init(), (held, d, f), self.param_dtype)
        w2 = self.param("w2", _init(), (held, f, d), self.param_dtype)
        with jax.named_scope("moe"):
            y, counters, selected = dropless_moe(
                x.reshape(-1, d).astype(self.dtype), gate, bias, w1, w3, w2,
                top_k=cfg.num_experts_per_tok, expert_offset=cfg.expert_offset,
                scaling=cfg.routed_scaling_factor,
            )
        for name, value in counters.items():
            self.sow("counters", name, value)
        # For a check of the routing (apply with mutable=["intermediates"]).
        self.sow("intermediates", "selected_experts", selected)
        return y.reshape(x.shape)


class Block(nn.Module):
    cfg: Lfm2Config
    index: int
    attn_impl: str = "full"
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        cfg, kw = self.cfg, dict(dtype=self.dtype, param_dtype=self.param_dtype)
        h = RMSNorm(cfg.norm_eps, name="operator_norm", **kw)(x)
        if cfg.layer_types[self.index] == "full_attention":
            x = x + Attention(cfg, self.attn_impl, name="attn", **kw)(h)
        else:
            x = x + ShortConv(cfg, name="conv", **kw)(h)
        h = RMSNorm(cfg.norm_eps, name="ffn_norm", **kw)(x)
        if self.index < cfg.num_dense_layers:
            return x + SwiGLU(cfg.intermediate_size, name="mlp", **kw)(h)
        return x + MoE(cfg, name="moe", **kw)(h)


class Lfm2Moe(nn.Module):
    cfg: Lfm2Config
    attn_impl: str = "full"
    remat_blocks: bool = False
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        cfg, kw = self.cfg, dict(dtype=self.dtype, param_dtype=self.param_dtype)
        x = nn.Embed(
            cfg.vocab_size, cfg.hidden_size, embedding_init=_init(), name="embed", **kw
        )(tokens)
        block = nn.remat(Block) if self.remat_blocks else Block
        for i in range(len(cfg.layer_types)):
            x = block(cfg, i, self.attn_impl, name=f"layer{i}", **kw)(x)
        x = RMSNorm(cfg.norm_eps, name="norm", **kw)(x)
        with jax.named_scope("head"):
            return nn.Dense(
                cfg.vocab_size, use_bias=False, kernel_init=_init(), name="head", **kw
            )(x)


def lfm2_moe(num_classes: int, *, model_config: str = "", **kw: Any) -> Lfm2Moe:
    """``num_classes`` is the image models' head size and is not read: the
    vocabulary is the configuration's."""
    del num_classes
    return Lfm2Moe(cfg=Lfm2Config.parse(model_config), **kw)


def lfm2_vocab(model_config: str) -> int:
    """The vocabulary ``lfm2_moe`` would be built with (``ModelSpec.vocab``)."""
    return Lfm2Config.parse(model_config).vocab_size
