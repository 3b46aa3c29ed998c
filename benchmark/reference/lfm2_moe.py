"""Plain reference: LFM2-MoE (LiquidAI/LFM2-24B-A2B, ``model_type: lfm2_moe``)
forward, loss and gradients in float32 ``jax.numpy``, no kernels, consuming
the system's parameter tree (``mpi_pytorch_tpu.models.lfm2``) and importing
nothing of it.

Follows the source's ``modeling_lfm2_moe``: pre-RMSNorm blocks ``x +=
op(norm(x)); x += ffn(norm(x))``; the operator is a gated short convolution
(``B, C, u = split3(W_in h)``, ``y = W_out (C * causal_depthwise_conv(B * u))``,
kernel 3, no bias) or grouped-query attention (RMSNorm over each head's q and
k, RoPE in the rotate-half convention over all head dims, causal, scale
``head_dim ** -0.5``); the feed-forward is a dense SwiGLU or a mixture of
experts (``s = sigmoid(W_g h)`` over ALL routed experts, top-k of ``s + b``,
weights ``s[sel] / (sum s[sel] + 1e-6) * routed_scaling_factor``, each expert
a SwiGLU). A final RMSNorm, an untied head.

The architecture is read off the parameter tree (``conv`` or ``attn``,
``mlp`` or ``moe`` in a layer; heads from the projections' shapes). What the
tree cannot say are the source's constants, defaults below: ``top_k`` 4,
``norm_eps`` 1e-5, ``rope_theta`` 1e6, ``routed_scaling_factor`` 1.

The chip's share. A layer's ``moe`` holds ``E_held`` experts of the router's
``E`` (ids ``expert_offset .. expert_offset + E_held``): routing is over all
``E``; the output is the sum over the selected experts HELD here; what the
absent experts would add is left out, as in the system. The vocabulary is
whatever the embedding and the head hold (a slice is a smaller vocabulary).

Memory at the timed size (one sequence of 8 192 tokens): attention runs in
query blocks of ``Q_BLOCK`` rows, the experts one at a time, every block under
``jax.checkpoint``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference.common import f32

TOP_K = 4
NORM_EPS = 1e-5
ROPE_THETA = 1e6
ROUTED_SCALING = 1.0
Q_BLOCK = 512  # query rows per attention block


def _rms(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def _swiglu(x, w1, w3, w2):
    return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2


def short_conv(x, p):
    """``[B, S, D]``: in_proj ``[D, 3D]``, conv ``[K, D]`` (tap j multiplies
    the input K-1-j steps back), out_proj ``[D, D]``."""
    b_gate, c_gate, u = jnp.split(x @ p["in_proj"], 3, axis=-1)
    bu = b_gate * u
    taps = p["conv"].shape[0]
    padded = jnp.pad(bu, ((0, 0), (taps - 1, 0), (0, 0)))
    s = x.shape[1]
    conv = sum(padded[:, j : j + s] * p["conv"][j] for j in range(taps))
    return (c_gate * conv) @ p["out_proj"]


def _rope(x, theta):
    """``[B, S, H, Dh]`` rotated by position, rotate-half convention."""
    s, dh = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]  # [S, Dh/2]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def attention(x, p, *, eps=NORM_EPS, theta=ROPE_THETA):
    """q ``[D, H, Dh]``, k and v ``[D, Hkv, Dh]``, out ``[H, Dh, D]``, q_norm
    and k_norm ``[Dh]``; query head h reads key-value head ``h // (H / Hkv)``."""
    b, s, _ = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, p["q"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["k"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["v"])
    q = _rope(_rms(q, p["q_norm"], eps), theta)
    k = _rope(_rms(k, p["k_norm"], eps), theta)
    h, hkv, dh = q.shape[2], k.shape[2], q.shape[3]
    q = q.reshape(b, s, hkv, h // hkv, dh)
    blk = min(Q_BLOCK, s)
    assert s % blk == 0, (s, blk)

    @jax.checkpoint
    def rows(start):
        qb = lax.dynamic_slice_in_dim(q, start, blk, axis=1)
        scores = jnp.einsum("bqgrk,btgk->bgrqt", qb, k) * dh**-0.5
        q_pos = start + jnp.arange(blk)[:, None]
        scores = jnp.where(jnp.arange(s)[None, :] <= q_pos, scores, -jnp.inf)
        return jnp.einsum("bgrqt,btgk->bqgrk", jax.nn.softmax(scores, axis=-1), v)

    out = lax.map(rows, jnp.arange(0, s, blk))  # [S/blk, B, blk, Hkv, G, Dh]
    out = jnp.moveaxis(out, 0, 1).reshape(b, s, h, dh)
    return jnp.einsum("bshk,hkd->bsd", out, p["out"])


def route(x, p, top_k=TOP_K, scaling=ROUTED_SCALING):
    """(selected ids ``[T, k]``, their weights ``[T, k]``) over ALL routed
    experts: selection by ``s + b``, weights from ``s`` alone."""
    s = jax.nn.sigmoid(x @ p["gate"])  # [T, E]
    _, sel = lax.top_k(s + p["expert_bias"], top_k)
    w = jnp.take_along_axis(s, sel, axis=-1)
    return sel, w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6) * scaling


def moe(x, p, *, top_k=TOP_K, expert_offset=0, scaling=ROUTED_SCALING):
    """``[T, D]`` tokens through the experts held here: w1, w3 ``[E_held, D,
    F]``, w2 ``[E_held, F, D]``, gate ``[D, E]``, expert_bias ``[E]``."""
    sel, w = route(x, p, top_k, scaling)
    held = p["w1"].shape[0]
    # Dense combine weight of every (token, held expert): 0 where not selected.
    ids = expert_offset + jnp.arange(held)
    combine = jnp.sum(w[:, :, None] * (sel[:, :, None] == ids[None, None, :]), axis=1)

    @jax.checkpoint
    def one(y, e):
        w1, w3, w2, c = e
        return y + c[:, None] * _swiglu(x, w1, w3, w2), None

    y, _ = lax.scan(one, jnp.zeros_like(x), (p["w1"], p["w3"], p["w2"], combine.T))
    return y


def selected_experts(variables, tokens, **kw):
    """``[layers with experts, B*S, k]`` ids the reference's routing selects,
    sorted within a token: what the check's routing agreement compares."""
    _, sel = _forward(variables, tokens, want_selection=True, **kw)
    return sel


def _layer(x, lp, kw, selection):
    eps = kw.get("eps", NORM_EPS)
    h = _rms(x, lp["operator_norm"]["scale"], eps)
    if "attn" in lp:
        x = x + attention(h, lp["attn"], eps=eps, theta=kw.get("theta", ROPE_THETA))
    else:
        x = x + short_conv(h, lp["conv"])
    h = _rms(x, lp["ffn_norm"]["scale"], eps)
    if "moe" in lp:
        flat = h.reshape(-1, h.shape[-1])
        moe_kw = {k: kw[k] for k in ("top_k", "expert_offset", "scaling") if k in kw}
        if selection is not None:
            sel, _ = route(flat, lp["moe"], moe_kw.get("top_k", TOP_K))
            selection.append(jnp.sort(sel, axis=-1))
        return x + moe(flat, lp["moe"], **moe_kw).reshape(h.shape)
    return x + _swiglu(h, lp["mlp"]["w1"], lp["mlp"]["w3"], lp["mlp"]["w2"])


def _forward(variables, tokens, want_selection=False, **kw):
    with jax.default_matmul_precision("highest"):
        p = f32(variables["params"])
        x = p["embed"]["embedding"][tokens]
        selection = [] if want_selection else None
        depth = sum(1 for name in p if name.startswith("layer"))
        for i in range(depth):
            layer = lambda x, lp: _layer(x, lp, kw, selection)
            if not want_selection:  # a gradient recomputes each layer from its input
                layer = jax.checkpoint(layer)
            x = layer(x, p[f"layer{i}"])
        x = _rms(x, p["norm"]["scale"], kw.get("eps", NORM_EPS))
        logits = x @ p["head"]["kernel"]
        return logits, (jnp.stack(selection) if selection else None)


def forward(variables, tokens, train: bool = False, **kw):
    """float32 logits ``[B, S, V]`` for int32 ``tokens [B, S]``. Train mode is
    the same function: no dropout, no auxiliary loss."""
    return _forward(variables, tokens, **kw)[0]


def cross_entropy(logits, targets):
    """Mean next-token cross-entropy over every position, ``targets [B, S]``."""
    logits = logits.astype(jnp.float32)
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - picked)


def loss_and_grads(variables, tokens, targets, **kw):
    def loss_fn(params):
        return cross_entropy(forward({"params": params}, tokens, train=True, **kw), targets)

    return jax.value_and_grad(loss_fn)(f32(variables["params"]))


def forward_flops(model: dict) -> int:
    """Matmul FLOPs (2 per multiply-add) one SEQUENCE's forward pass requires,
    from shapes: per layer the operator (conv: in_proj, out_proj and the K
    taps; attention: q, k, v, out projections, scores and weighted values over
    the CAUSAL half of S x S) and the feed-forward (dense SwiGLU, or the router
    plus the expert pairs computed HERE — ``S * top_k * held / routed`` of them,
    uniform routing assumed: the share of the pairs a chip holding ``held`` of
    ``routed`` experts computes on average); the head. The embedding is a
    lookup. ``model``: the source's keys, ``num_experts`` the experts held,
    ``num_experts_routed`` the router's width, ``seq_len`` the tokens."""
    s, d = model["seq_len"], model["hidden_size"]
    h, hkv = model["num_attention_heads"], model["num_key_value_heads"]
    dh = d // h
    macs = 0
    for i, kind in enumerate(model["layer_types"]):
        if kind == "full_attention":
            macs += s * d * dh * (2 * h + 2 * hkv)  # q, out; k, v
            macs += 2 * h * dh * (s * s // 2)  # scores, weighted values: causal half
        else:
            macs += s * (3 * d * d + d * d + model["conv_L_cache"] * d)
        if i < model["num_dense_layers"]:
            macs += 3 * s * d * model["intermediate_size"]
        else:
            routed = model.get("num_experts_routed", model["num_experts"])
            pairs = s * model["num_experts_per_tok"] * model["num_experts"] // routed
            macs += s * d * routed + 3 * pairs * d * model["moe_intermediate_size"]
    macs += s * d * model["vocab_size"]
    return 2 * macs
