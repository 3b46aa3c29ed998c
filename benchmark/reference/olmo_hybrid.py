"""Plain reference: Olmo Hybrid (allenai/Olmo-Hybrid-7B, ``model_type:
olmo_hybrid``) forward, loss and gradients in float32 ``jax.numpy``, no
kernels, consuming the system's parameter tree
(``mpi_pytorch_tpu.models.olmo_hybrid``) and importing nothing of the system.

``h = embed[tokens]``; post-norm blocks ``h += RMSNorm(mixer(h)); h +=
RMSNorm(SwiGLU(h))`` (the Olmo 2/3 placement, arXiv:2501.00656: the config does
not state it); ``logits = RMSNorm(h) W_head``, the head untied. The mixer, by
what a layer's subtree holds:

- ``linear_attn``: the gated delta rule (Gated DeltaNet, arXiv:2412.06464).
  ``q, k, v = silu(causal depthwise conv(x W_q | x W_k | x W_v))`` without bias;
  ``q, k`` L2-normalised a head (eps 1e-6), ``q`` scaled by ``Dk ** -0.5``;
  ``beta = 2 sigmoid(x W_b)`` (``allow_neg_eigval``); ``g = -exp(A_log)
  softplus(x W_a + dt_bias)``; per head, one position at a time,

      S_t = exp(g_t) S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T      o_t = S_t q_t

  with ``S [Dv, Dk]`` float32 from zero — the PER-POSITION recurrence, not the
  chunked algebra the system runs (``ops/gated_delta.py``), so that the
  system's chunking and its triangular solve are tested against something
  independent; ``y = RMSNorm_Dv(o) w * silu(x W_g)`` a head, ``y W_o``;
- ``attn``: causal softmax attention without a positional embedding, scale
  ``head_dim ** -0.5``, after RMSNorm over the WHOLE q and k projections (the
  Olmo 2/3 QK-norm), head by head.

The architecture is read off the parameter tree (heads and head sizes from the
shapes of ``q``, ``k``, ``v``; the vocabulary from the embedding: a slice is a
smaller vocabulary; a tensor-parallel rank's heads are fewer heads, and ``W_o``
gives their partial sum, as in the system). What the tree cannot say are the
source's constants, defaults below: ``rms_norm_eps`` 1e-6, the L2 norm's 1e-6,
``linear_allow_neg_eigval`` true.

Departures from the published description: none in the arithmetic; the
state, the convolution and attention run across document boundaries (no reset,
no mask), as the configuration assumes.

Memory at the timed size (one sequence of 8 192 tokens): every layer under
``jax.checkpoint``; the recurrence in blocks of ``POS_BLOCK`` positions, each
under ``jax.checkpoint``, so a gradient keeps one ``[H, Dv, Dk]`` state a block
(8 192 / 64 x 0.74 MB for ten heads) and one a position inside the block being
pulled back (per-position states would be 6 GB); attention one head at a time;
the feed-forward in blocks of ``ROW_BLOCK`` rows.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmark import costs_gated_delta
from benchmark.reference.common import f32
from benchmark.reference.lfm2_moe import cross_entropy  # noqa: F401  (mean next-token loss)

NORM_EPS = 1e-6
L2_EPS = 1e-6
NEG_EIGVAL = True
POS_BLOCK = 64  # positions per checkpointed block of the recurrence
ROW_BLOCK = 2048  # rows per checkpointed block of the feed-forward


def _rms(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def _l2(x):
    return x * lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + L2_EPS)


def _swiglu(x, w1, w3, w2):
    rows = x.reshape(-1, x.shape[-1])
    size = math.gcd(rows.shape[0], ROW_BLOCK)
    block = jax.checkpoint(lambda r: (jax.nn.silu(r @ w1) * (r @ w3)) @ w2)
    return lax.map(block, rows.reshape(-1, size, x.shape[-1])).reshape(x.shape)


def delta_rule(q, k, v, beta, g):
    """``q``, ``k [B, S, H, Dk]``, ``v [B, S, H, Dv]``, ``beta``, ``g [B, S, H]``
    -> ``o [B, S, H, Dv]``, one position at a time."""
    batch, s, h, dk = q.shape
    dv = v.shape[-1]

    def position(state, at):
        q_t, k_t, v_t, b_t, g_t = at  # [B, H, Dk], [B, H, Dk], [B, H, Dv], [B, H], [B, H]
        alpha = jnp.exp(g_t)[..., None, None]
        kept = alpha * state  # alpha S
        written = b_t[..., None] * (v_t - jnp.einsum("bhvd,bhd->bhv", kept, k_t))
        state = kept + written[..., :, None] * k_t[..., None, :]
        return state, jnp.einsum("bhvd,bhd->bhv", state, q_t)

    @jax.checkpoint
    def block(state, ats):
        return lax.scan(position, state, ats)

    size = math.gcd(s, POS_BLOCK)
    blocked = lambda t: jnp.moveaxis(t, 1, 0).reshape((s // size, size) + t.shape[:1] + t.shape[2:])
    state = jnp.zeros((batch, h, dv, dk), jnp.float32)
    _, o = lax.scan(block, state, tuple(map(blocked, (q, k, v, beta, g))))
    return jnp.moveaxis(o.reshape((s,) + o.shape[2:]), 0, 1)


def linear_attention(x, p, *, eps=NORM_EPS, neg_eigval=NEG_EIGVAL):
    """q, k ``[D, H, Dk]``, v, g ``[D, H, Dv]``, a, b ``[D, H]``, conv_q / conv_k
    / conv_v ``[K, H, width]`` (tap j multiplies the input K-1-j steps back),
    A_log, dt_bias ``[H]``, norm ``[Dv]``, out ``[H, Dv, D]``."""
    dk = p["q"].shape[-1]
    heads = lambda w: jnp.einsum("bsd,dhk->bshk", x, w)
    s = x.shape[1]

    def conv(t, taps):
        kk = taps.shape[0]
        padded = jnp.pad(t, ((0, 0), (kk - 1, 0), (0, 0), (0, 0)))
        return jax.nn.silu(sum(padded[:, j : j + s] * taps[j] for j in range(kk)))

    q = _l2(conv(heads(p["q"]), p["conv_q"])) * dk**-0.5
    k = _l2(conv(heads(p["k"]), p["conv_k"]))
    v = conv(heads(p["v"]), p["conv_v"])
    beta = jax.nn.sigmoid(jnp.einsum("bsd,dh->bsh", x, p["b"])) * (2.0 if neg_eigval else 1.0)
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(jnp.einsum("bsd,dh->bsh", x, p["a"]) + p["dt_bias"])
    o = delta_rule(q, k, v, beta, g)
    y = _rms(o, p["norm"], eps) * jax.nn.silu(heads(p["g"]))
    return jnp.einsum("bshv,hvd->bsd", y, p["out"])


def attention(x, p, *, eps=NORM_EPS):
    """q ``[D, H, Dh]``, k and v ``[D, Hkv, Dh]``, out ``[H, Dh, D]``, q_norm
    ``[H Dh]``, k_norm ``[Hkv Dh]``; query head h reads key-value head ``h //
    (H / Hkv)``; no positional embedding; one head at a time."""
    b, s, _ = x.shape
    whole = lambda w, scale: _rms(
        jnp.einsum("bsd,dhk->bshk", x, w).reshape(b, s, -1), scale, eps
    ).reshape(b, s, w.shape[1], w.shape[2])
    q, k = whole(p["q"], p["q_norm"]), whole(p["k"], p["k_norm"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["v"])
    h, hkv, dh = q.shape[2], k.shape[2], q.shape[3]
    causal = jnp.tril(jnp.ones((s, s), bool))

    @jax.checkpoint
    def one(head):
        kv = head // (h // hkv)
        qh, kh, vh = q[:, :, head], k[:, :, kv], v[:, :, kv]
        scores = jnp.where(causal, jnp.einsum("bqk,btk->bqt", qh, kh) * dh**-0.5, -jnp.inf)
        return jnp.einsum("bqt,btk->bqk", jax.nn.softmax(scores, axis=-1), vh)

    out = jnp.moveaxis(lax.map(one, jnp.arange(h)), 0, 2)  # [B, S, H, Dh]
    return jnp.einsum("bshk,hkd->bsd", out, p["out"])


def _layer(x, lp, kw):
    eps = kw.get("eps", NORM_EPS)
    if "attn" in lp:
        y = attention(x, lp["attn"], eps=eps)
    else:
        y = linear_attention(x, lp["linear_attn"], eps=eps, neg_eigval=kw.get("neg_eigval", NEG_EIGVAL))
    x = x + _rms(y, lp["post_mixer_norm"]["scale"], eps)
    y = _swiglu(x, lp["mlp"]["w1"], lp["mlp"]["w3"], lp["mlp"]["w2"])
    return x + _rms(y, lp["post_mlp_norm"]["scale"], eps)


def hidden(params, tokens, **kw):
    """The final-norm output ``[B, S, D]`` (float32 ``params``): what every
    vocabulary slice's head reads."""
    x = params["embed"]["embedding"][tokens]
    depth = sum(1 for name in params if name.startswith("layer"))
    for i in range(depth):
        # a gradient recomputes each layer from its input
        x = jax.checkpoint(lambda x, lp: _layer(x, lp, kw))(x, params[f"layer{i}"])
    return _rms(x, params["norm"]["scale"], kw.get("eps", NORM_EPS))


def forward(variables, tokens, train: bool = False, **kw):
    """float32 logits ``[B, S, V]`` for int32 ``tokens [B, S]``. Train mode is
    the same function: no dropout, no auxiliary loss."""
    with jax.default_matmul_precision("highest"):
        p = f32(variables["params"])
        return hidden(p, tokens, **kw) @ p["head"]["kernel"]


def loss_and_grads(variables, tokens, targets, **kw):
    def loss_fn(params):
        return cross_entropy(forward({"params": params}, tokens, train=True, **kw), targets)

    return jax.value_and_grad(loss_fn)(f32(variables["params"]))


def forward_flops(model: dict) -> int:
    """Matmul FLOPs (2 per multiply-add) one SEQUENCE's forward pass requires,
    from shapes: per layer the mixer (``linear_attention``: the q, k, v, g, a, b
    and out projections, the K taps over q, k and v, and the chunked rule's
    products at the chunk size, ``benchmark/costs_gated_delta.py``;
    ``full_attention``: q, k, v, out projections, scores and weighted values
    over the CAUSAL half of S x S) and the SwiGLU; the untied head. The
    embedding is a lookup. ``model``: the source's keys (the linear heads are
    the heads HELD), ``seq_len`` the tokens."""
    s, d = model["seq_len"], model["hidden_size"]
    h, hkv = model["num_attention_heads"], model["num_key_value_heads"]
    dh = d // h
    lh, dk, dv = model["linear_num_key_heads"], model["linear_key_head_dim"], model["linear_value_head_dim"]
    macs = 0
    for kind in model["layer_types"]:
        if kind == "full_attention":
            macs += s * d * dh * (2 * h + 2 * hkv)  # q, out; k, v
            macs += 2 * h * dh * (s * s // 2)  # scores, weighted values: causal half
        else:
            macs += s * d * lh * (2 * dk + 3 * dv + 2)  # q, k; v, g, out; a, b
            macs += s * model["linear_conv_kernel_dim"] * lh * (2 * dk + dv)
            macs += costs_gated_delta.delta_forward_macs(model)
        macs += 3 * s * d * model["intermediate_size"]
    macs += s * d * model["vocab_size"]
    return 2 * macs
