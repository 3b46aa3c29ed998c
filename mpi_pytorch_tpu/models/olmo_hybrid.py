"""Olmo Hybrid decoder (AllenAI ``model_type: olmo_hybrid``, e.g.
Olmo-Hybrid-7B): a token model — ``int32 [B, S]`` ids in, ``[B, S, V]``
next-token logits out — whose mixers are gated-delta-rule linear attention
with a full-attention layer every few.

The layer equations (what the source's ``config.json`` does not fix is stated
as assumed in ``benchmark/configs/olmo-hybrid-7b-tp5.json``):

- ``h = embed[tokens]``; block (post-norm, the Olmo 2/3 "reordered norm"):
  ``h += RMSNorm(mixer(h)); h += RMSNorm(SwiGLU(h))``; ``logits = RMSNorm(h)
  W_head`` (the head is NOT tied to the embedding);
- ``mixer`` by ``layer_types[i]``: ``linear_attention`` — the gated delta rule
  of Gated DeltaNet (arXiv:2412.06464; the key names are flash-linear-attention's
  ``GatedDeltaNet``): ``q, k, v = silu(causal_conv(x W_q | x W_k | x W_v))``
  (depthwise, ``linear_conv_kernel_dim`` taps, no bias); ``q``, ``k``
  L2-normalised a head, ``q`` scaled by ``Dk ** -0.5``; ``beta = 2 sigmoid(x
  W_b)`` (``linear_allow_neg_eigval``; ``sigmoid`` without it); ``g = -exp(A_log)
  softplus(x W_a + dt_bias)``; the recurrence (``ops/gated_delta.py``, chunks
  of ``linear_chunk_size``); ``y = RMSNorm_Dv(o) w * silu(x W_g)`` a head; ``y
  W_o`` — or ``full_attention`` — causal softmax attention of ``num_attention_heads``
  heads of ``hidden_size / num_attention_heads`` over ``num_key_value_heads``,
  ``q = RMSNorm(x W_q)`` and ``k = RMSNorm(x W_k)`` over the WHOLE projection,
  NO positional embedding (``rope_theta`` is null);
- the SwiGLU of ``intermediate_size`` in every layer.

The architecture arrives one way, ``--model-config`` (a JSON object, inline or
a file's path) in the source's own key names; ``OlmoHybridConfig`` reads it.
A tensor-parallel rank's share of the linear-attention heads is a smaller
``linear_num_key_heads`` / ``linear_num_value_heads`` (the heads HELD here, of
the same size); ``W_o``'s output is then this rank's partial sum and goes on to
the post-norm without the exchange. A sliced vocabulary is a smaller
``vocab_size``. One key is this system's: ``linear_chunk_size`` (64).

Device scopes: ``linear_attn`` around the whole linear-attention mixer, inside
it ``linear_attn/conv`` (the three convolutions and their SiLU),
``linear_attn/delta`` (the L2 norms, beta, the decays and the chunked rule),
``linear_attn/gate_norm``; ``attention`` (the q/k norms and the attention
call; the projections stay outside); ``mlp`` around the SwiGLU; ``head``.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax import lax

from mpi_pytorch_tpu.models.granite_hybrid import _a_log_init, _dt_bias_init
from mpi_pytorch_tpu.models.lfm2 import (
    RMSNorm, SwiGLU, _init, causal_attention, causal_depthwise_conv1d, model_config_json, rms_norm,
)

Dtype = Any

_PERIOD = ("linear_attention",) * 3 + ("full_attention",)
L2_EPS = 1e-6  # the L2 norm of q and k (flash-linear-attention's ``l2norm``)


@dataclasses.dataclass(frozen=True)
class OlmoHybridConfig:
    """The source's ``config.json`` keys this module reads (defaults:
    Olmo-Hybrid-7B's published values), plus ``linear_chunk_size``."""

    hidden_size: int = 3840
    intermediate_size: int = 11008
    num_attention_heads: int = 30
    num_key_value_heads: int = 30
    layer_types: tuple = _PERIOD * 8
    linear_num_key_heads: int = 30
    linear_num_value_heads: int = 30
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True
    linear_chunk_size: int = 64
    rms_norm_eps: float = 1e-6
    vocab_size: int = 100352

    @classmethod
    def parse(cls, text: str) -> "OlmoHybridConfig":
        """From ``--model-config``: a JSON object, or the path of a file that
        holds one. Keys this module does not read (``model_type``,
        ``max_position_embeddings``, ...) pass; a key whose value this module
        cannot honour is an error that names it."""
        if not text:
            return cls()
        raw = model_config_json(text)
        only = (("tie_word_embeddings", False), ("attention_bias", False), ("hidden_act", "silu"))
        for key, want in only:
            if raw.get(key, want) != want:
                raise ValueError(f"model-config: {key}={raw[key]!r} is not implemented (only {want!r})")
        theta = raw.get("rope_theta", (raw.get("rope_parameters") or {}).get("rope_theta"))
        if theta is not None:
            raise ValueError(f"model-config: rope_theta={theta!r} is not implemented (only null: no positions)")
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
        unknown = sorted(set(cfg.layer_types) - set(_PERIOD))
        if unknown:
            raise ValueError(f"model-config: layer type {unknown[0]!r} is not implemented")
        if cfg.linear_num_value_heads != cfg.linear_num_key_heads:
            raise ValueError(
                f"model-config: linear_num_value_heads {cfg.linear_num_value_heads} != "
                f"linear_num_key_heads {cfg.linear_num_key_heads} is not implemented"
            )
        if cfg.num_attention_heads % cfg.num_key_value_heads:
            raise ValueError("model-config: num_key_value_heads must divide num_attention_heads")
        if cfg.hidden_size % cfg.num_attention_heads:
            raise ValueError("model-config: num_attention_heads must divide hidden_size")
        return cfg


def l2_norm(x):
    """``x / |x|`` over the last axis, in float32."""
    xf = x.astype(jnp.float32)
    return xf * lax.rsqrt(jnp.sum(jnp.square(xf), axis=-1, keepdims=True) + L2_EPS)


class LinearAttention(nn.Module):
    cfg: OlmoHybridConfig
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        from mpi_pytorch_tpu.ops.gated_delta import gated_delta

        m, dt = self.cfg, self.dtype
        d, h = x.shape[-1], m.linear_num_key_heads
        dk, dv, kk = m.linear_key_head_dim, m.linear_value_head_dim, m.linear_conv_kernel_dim
        proj = lambda name, *width: self.param(name, _init(), (d, h, *width), self.param_dtype)
        wq, wk, wv, wg, wa, wb = proj("q", dk), proj("k", dk), proj("v", dv), proj("g", dv), proj("a"), proj("b")
        taps = lambda name, width: self.param(name, _init(kk**-0.5), (kk, h, width), self.param_dtype)
        cq, ck, cv = taps("conv_q", dk), taps("conv_k", dk), taps("conv_v", dv)
        a_log = self.param("A_log", _a_log_init, (h,), jnp.float32)
        dt_bias = self.param("dt_bias", _dt_bias_init, (h,), jnp.float32)
        norm_w = self.param("norm", nn.initializers.ones, (dv,), self.param_dtype)
        wo = self.param("out", _init(), (h, dv, d), self.param_dtype)
        with jax.named_scope("linear_attn"):
            heads = lambda w: jnp.einsum("bsd,dhk->bshk", x, w.astype(dt))
            q, k, v, gate = heads(wq), heads(wk), heads(wv), heads(wg)
            a, b = (jnp.einsum("bsd,dh->bsh", x, w.astype(dt)) for w in (wa, wb))
            with jax.named_scope("linear_attn/conv"):
                def conv(t, w):
                    flat = causal_depthwise_conv1d(t.reshape(t.shape[:2] + (-1,)), w.reshape(kk, -1).astype(dt))
                    return jax.nn.silu(flat).reshape(t.shape)

                q, k, v = conv(q, cq), conv(k, ck), conv(v, cv)
            with jax.named_scope("linear_attn/delta"):
                q = (l2_norm(q) * dk**-0.5).astype(dt)
                k = l2_norm(k).astype(dt)
                beta = jax.nn.sigmoid(b.astype(jnp.float32)) * (2.0 if m.linear_allow_neg_eigval else 1.0)
                g = -jnp.exp(a_log) * jax.nn.softplus(a.astype(jnp.float32) + dt_bias)
            o = gated_delta(q, k, v, beta, g, chunk=m.linear_chunk_size)
            with jax.named_scope("linear_attn/gate_norm"):
                o = (rms_norm(o, norm_w, m.rms_norm_eps) * jax.nn.silu(gate.astype(jnp.float32))).astype(dt)
            return jnp.einsum("bshv,hvd->bsd", o, wo.astype(dt))


class FullAttention(nn.Module):
    """Causal multi-head attention with RMSNorm over the WHOLE q and k
    projections and no positional embedding."""

    heads: int
    kv_heads: int
    eps: float
    attn_impl: str = "full"
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        d, h, hkv = x.shape[-1], self.heads, self.kv_heads
        dh = d // h
        proj = lambda name, heads: self.param(name, _init(), (d, heads, dh), self.param_dtype)
        wq, wk, wv = proj("q", h), proj("k", hkv), proj("v", hkv)
        wo = self.param("out", _init(), (h, dh, d), self.param_dtype)
        q_scale = self.param("q_norm", nn.initializers.ones, (h * dh,), self.param_dtype)
        k_scale = self.param("k_norm", nn.initializers.ones, (hkv * dh,), self.param_dtype)
        q = jnp.einsum("bsd,dhk->bshk", x, wq.astype(self.dtype))
        k = jnp.einsum("bsd,dhk->bshk", x, wk.astype(self.dtype))
        v = jnp.einsum("bsd,dhk->bshk", x, wv.astype(self.dtype))
        with jax.named_scope("attention"):
            whole = lambda t, scale: rms_norm(t.reshape(t.shape[:2] + (-1,)), scale, self.eps).reshape(t.shape)
            q, k = whole(q, q_scale).astype(self.dtype), whole(k, k_scale).astype(self.dtype)
            out = causal_attention(self, q, k, v, self.attn_impl)
        return jnp.einsum("bshk,hkd->bsd", out, wo.astype(self.dtype))


class Block(nn.Module):
    cfg: OlmoHybridConfig
    index: int
    attn_impl: str = "full"
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        cfg, kw = self.cfg, dict(dtype=self.dtype, param_dtype=self.param_dtype)
        if cfg.layer_types[self.index] == "full_attention":
            y = FullAttention(
                cfg.num_attention_heads, cfg.num_key_value_heads, cfg.rms_norm_eps, self.attn_impl,
                name="attn", **kw,
            )(x)
        else:
            y = LinearAttention(cfg, name="linear_attn", **kw)(x)
        x = x + RMSNorm(cfg.rms_norm_eps, name="post_mixer_norm", **kw)(y)
        with jax.named_scope("mlp"):
            y = SwiGLU(cfg.intermediate_size, name="mlp", **kw)(x)
        return x + RMSNorm(cfg.rms_norm_eps, name="post_mlp_norm", **kw)(y)


class OlmoHybrid(nn.Module):
    cfg: OlmoHybridConfig
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
        self.head = nn.Dense(cfg.vocab_size, use_bias=False, kernel_init=_init(), **kw)

    def hidden(self, tokens):
        """``[B, S, D]`` after the last block, before the final norm: what
        every chip that shares the layers computes alike."""
        x = self.embed(tokens)
        for i in range(len(self.cfg.layer_types)):
            x = getattr(self, f"layer{i}")(x)
        return x

    def logits(self, x):
        """The final norm and the head over the vocabulary rows held here."""
        x = self.norm(x)
        with jax.named_scope("head"):
            return self.head(x)

    def __call__(self, tokens, train: bool = False):
        return self.logits(self.hidden(tokens))


def olmo_hybrid(num_classes: int, *, model_config: str = "", **kw: Any) -> OlmoHybrid:
    """``num_classes`` is the image models' head size and is not read: the
    vocabulary is the configuration's."""
    del num_classes
    return OlmoHybrid(cfg=OlmoHybridConfig.parse(model_config), **kw)


def olmo_vocab(model_config: str) -> int:
    """The vocabulary ``olmo_hybrid`` would be built with (``ModelSpec.vocab``)."""
    return OlmoHybridConfig.parse(model_config).vocab_size
