"""Plain reference: Granite 4.0-H (ibm-granite/granite-4.0-h-micro,
``model_type: granitemoehybrid``, dense: ``num_local_experts`` 0) forward, loss
and gradients in float32 ``jax.numpy``, no kernels, consuming the system's
parameter tree (``mpi_pytorch_tpu.models.granite_hybrid``) and importing
nothing of the system.

Follows the source's ``modeling_granitemoehybrid``: ``h = embed[tokens] *
embedding_multiplier``; pre-RMSNorm blocks ``h += residual_multiplier *
mixer(norm(h)); h += residual_multiplier * mlp(norm(h))``; the mixer is
grouped-query attention WITHOUT a positional embedding (causal softmax of ``q
k^T * attention_multiplier``) or a Mamba-2 layer — ``[z, xBC, dt] = x W_in``,
``xBC = silu(causal depthwise conv + bias)``, ``dt = softplus(dt + dt_bias)``,
the selective state-space recurrence, ``y = RMSNorm(y * silu(z)) * w``, ``y
W_out`` —; the feed-forward a SwiGLU; ``logits = RMSNorm(h) embed^T /
logits_scaling`` with the head tied to the embedding.

The state-space layer is the PER-POSITION recurrence, one ``lax.scan`` step a
position with the ``[H, P, N]`` state as its carry

    H_t = exp(dt_t A) H_{t-1} + dt_t x_t (x) B_t        y_t = H_t C_t + D x_t

and not the chunked algebra the system runs (``ops/ssd.py``), so that the
system's chunking is tested against something independent.

The architecture is read off the parameter tree (``mamba`` or ``attn`` in a
layer; heads from the shapes of ``q``/``k`` and of ``A_log``, the state from
the convolution's width). What the tree cannot say are the source's
constants, defaults below: the four multipliers, ``rms_norm_eps`` 1e-5,
``mamba_n_groups`` 1. The vocabulary is whatever the embedding holds (a slice
is a smaller vocabulary).

Memory at the timed size (one sequence of 8 192 tokens): every layer under
``jax.checkpoint``, attention in query blocks of ``Q_BLOCK`` rows, the
feed-forward in blocks of ``ROW_BLOCK`` rows, the recurrence in blocks of ``POS_BLOCK`` positions each under
``jax.checkpoint`` — a gradient keeps the ``[H, P, N]`` state once a block
(8 192 / 64 x 2 MB) and once a position inside the block being pulled back.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmark import costs_ssd
from benchmark.reference.common import f32
from benchmark.reference.lfm2_moe import cross_entropy  # noqa: F401  (mean next-token loss)

EMBEDDING_MULTIPLIER = 12.0
RESIDUAL_MULTIPLIER = 0.22
ATTENTION_MULTIPLIER = 0.015625
LOGITS_SCALING = 8.0
NORM_EPS = 1e-5
GROUPS = 1
Q_BLOCK = 512  # query rows per attention block
POS_BLOCK = 64  # positions per checkpointed block of the recurrence
ROW_BLOCK = 2048  # rows per checkpointed block of the feed-forward


def _rms(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def _swiglu(x, w1, w3, w2):
    """Rows in blocks of ``ROW_BLOCK``, each under ``jax.checkpoint``: the
    ``[rows, 8 192]`` float32 intermediates of one block at a time."""
    rows = x.reshape(-1, x.shape[-1])
    size = math.gcd(rows.shape[0], ROW_BLOCK)
    block = jax.checkpoint(lambda r: (jax.nn.silu(r @ w1) * (r @ w3)) @ w2)
    return lax.map(block, rows.reshape(-1, size, x.shape[-1])).reshape(x.shape)


def ssm_scan(x, dt, a_log, b, c, d):
    """``x [B, S, H, P]``, ``dt [B, S, H]`` (after the softplus), ``a_log
    [H]``, ``b`` and ``c [B, S, G, N]``, ``d [H]`` -> ``y [B, S, H, P]``, one
    position at a time."""
    batch, s, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    a = -jnp.exp(a_log)
    per_head = lambda t: jnp.repeat(t, h // g, axis=-2)  # [.., G, N] -> [.., H, N]

    def position(state, at):
        x_t, dt_t, b_t, c_t = at  # [B, H, P], [B, H], [B, G, N], [B, G, N]
        decay = jnp.exp(dt_t * a)[..., None, None]
        update = (dt_t[..., None] * x_t)[..., None] * per_head(b_t)[..., None, :]
        state = decay * state + update  # [B, H, P, N]
        y_t = jnp.sum(state * per_head(c_t)[..., None, :], axis=-1) + d[:, None] * x_t
        return state, y_t

    @jax.checkpoint
    def block(state, ats):
        return lax.scan(position, state, ats)

    size = math.gcd(s, POS_BLOCK)
    blocked = lambda t: jnp.moveaxis(t, 1, 0).reshape((s // size, size) + t.shape[:1] + t.shape[2:])
    state = jnp.zeros((batch, h, p, n), jnp.float32)
    _, y = lax.scan(block, state, tuple(map(blocked, (x, dt, b, c))))
    return jnp.moveaxis(y.reshape((s,) + y.shape[2:]), 0, 1)


def mamba(x, p, *, eps=NORM_EPS, groups=GROUPS):
    """in_proj ``[D, 2I + 2GN + H]``, conv_w ``[K, I + 2GN]`` (tap j multiplies
    the input K-1-j steps back), conv_b, dt_bias / A_log / D ``[H]``, norm
    ``[I]``, out_proj ``[I, D]``."""
    heads, inner = p["A_log"].shape[0], p["norm"].shape[0]
    bc = (p["conv_w"].shape[1] - inner) // 2
    z, xbc, dt = jnp.split(x @ p["in_proj"], [inner, 2 * inner + 2 * bc], axis=-1)
    taps, s = p["conv_w"].shape[0], x.shape[1]
    padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(padded[:, j : j + s] * p["conv_w"][j] for j in range(taps)) + p["conv_b"])
    u, b, c = jnp.split(xbc, [inner, inner + bc], axis=-1)
    heads_of = lambda t, k: t.reshape(t.shape[:2] + (k, t.shape[-1] // k))
    y = ssm_scan(
        heads_of(u, heads), jax.nn.softplus(dt + p["dt_bias"]), p["A_log"],
        heads_of(b, groups), heads_of(c, groups), p["D"],
    ).reshape(u.shape)
    return _rms(y * jax.nn.silu(z), p["norm"], eps) @ p["out_proj"]


def attention(x, p, *, scale=ATTENTION_MULTIPLIER):
    """q ``[D, H, Dh]``, k and v ``[D, Hkv, Dh]``, out ``[H, Dh, D]``; query
    head h reads key-value head ``h // (H / Hkv)``; no positional embedding."""
    b, s, _ = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, p["q"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["k"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["v"])
    h, hkv, dh = q.shape[2], k.shape[2], q.shape[3]
    q = q.reshape(b, s, hkv, h // hkv, dh)
    blk = min(Q_BLOCK, s)
    assert s % blk == 0, (s, blk)

    @jax.checkpoint
    def rows(start):
        qb = lax.dynamic_slice_in_dim(q, start, blk, axis=1)
        scores = jnp.einsum("bqgrk,btgk->bgrqt", qb, k) * scale
        q_pos = start + jnp.arange(blk)[:, None]
        scores = jnp.where(jnp.arange(s)[None, :] <= q_pos, scores, -jnp.inf)
        return jnp.einsum("bgrqt,btgk->bqgrk", jax.nn.softmax(scores, axis=-1), v)

    out = lax.map(rows, jnp.arange(0, s, blk))  # [S/blk, B, blk, Hkv, G, Dh]
    out = jnp.moveaxis(out, 0, 1).reshape(b, s, h, dh)
    return jnp.einsum("bshk,hkd->bsd", out, p["out"])


def _layer(x, lp, kw):
    eps, res = kw.get("eps", NORM_EPS), kw.get("residual_multiplier", RESIDUAL_MULTIPLIER)
    h = _rms(x, lp["mixer_norm"]["scale"], eps)
    if "attn" in lp:
        x = x + res * attention(h, lp["attn"], scale=kw.get("attention_multiplier", ATTENTION_MULTIPLIER))
    else:
        x = x + res * mamba(h, lp["mamba"], eps=eps, groups=kw.get("groups", GROUPS))
    h = _rms(x, lp["mlp_norm"]["scale"], eps)
    return x + res * _swiglu(h, lp["mlp"]["w1"], lp["mlp"]["w3"], lp["mlp"]["w2"])


def hidden(params, tokens, **kw):
    """The final-norm output ``[B, S, D]`` (float32 ``params``): what every
    vocabulary slice's head reads."""
    x = params["embed"]["embedding"][tokens] * kw.get("embedding_multiplier", EMBEDDING_MULTIPLIER)
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
        return hidden(p, tokens, **kw) @ p["embed"]["embedding"].T / kw.get("logits_scaling", LOGITS_SCALING)


def loss_and_grads(variables, tokens, targets, **kw):
    def loss_fn(params):
        return cross_entropy(forward({"params": params}, tokens, train=True, **kw), targets)

    return jax.value_and_grad(loss_fn)(f32(variables["params"]))


def forward_flops(model: dict) -> int:
    """Matmul FLOPs (2 per multiply-add) one SEQUENCE's forward pass requires,
    from shapes: per layer the mixer (mamba: in_proj, out_proj, the K taps and
    the scan's four products at the chunk size with the causal half of the two
    intra-chunk ones, ``benchmark/costs_ssd.py``; attention: q, k, v, out
    projections, scores and weighted values over the CAUSAL half of S x S) and
    the SwiGLU; the tied head. The embedding is a lookup. ``model``: the
    source's keys, ``seq_len`` the tokens."""
    s, d = model["seq_len"], model["hidden_size"]
    h, hkv = model["num_attention_heads"], model["num_key_value_heads"]
    dh = d // h
    inner = model["mamba_n_heads"] * model["mamba_d_head"]
    bc = model["mamba_n_groups"] * model["mamba_d_state"]
    macs = 0
    for kind in model["layer_types"]:
        if kind == "attention":
            macs += s * d * dh * (2 * h + 2 * hkv)  # q, out; k, v
            macs += 2 * h * dh * (s * s // 2)  # scores, weighted values: causal half
        else:
            macs += s * d * (2 * inner + 2 * bc + model["mamba_n_heads"]) + s * inner * d
            macs += s * model["mamba_d_conv"] * (inner + 2 * bc)
            macs += costs_ssd.scan_forward_macs(model)
        macs += 3 * s * d * model["shared_intermediate_size"]
    macs += s * d * model["vocab_size"]
    return 2 * macs
