"""Plain reference: Nemotron-H with latent sparse experts
(nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16, ``model_type: nemotron_h``)
forward, loss and gradients in float32 ``jax.numpy``, no kernels, consuming the
system's parameter tree (``mpi_pytorch_tpu.models.nemotron_h``) and importing
nothing of the system.

``h = embed[tokens]``; every layer is ONE pre-RMSNorm branch, ``h = h +
mixer(norm(h))``, by what its subtree holds:

- ``mamba``: Mamba-2 — ``[z, xBC, dt] = x W_in``, ``xBC = silu(causal depthwise
  conv + bias)``, ``dt = softplus(dt + dt_bias)``, the selective state-space
  recurrence with ``G`` groups of B and C (head ``h`` reads group ``h // (H /
  G)``), ``y = GroupRMSNorm_G(y * silu(z)) * w`` with the mean square over each
  group's channels apart, ``y W_out``. The recurrence is the PER-POSITION one
  (``ssm_scan``, shared with ``reference/granitemoehybrid.py``), not the
  chunked algebra the system runs;
- ``attn``: grouped-query attention without bias and WITHOUT a positional
  embedding, causal softmax of ``q k^T / sqrt(head_dim)``;
- ``moe``: the latent expert layer. ``s = sigmoid(x W_r)`` over ALL routed
  experts, the top-k of ``s + b`` selected, weights ``s[sel] / (sum s[sel] +
  1e-20) * 5``; ``u = x W_down``; the routed part ``(sum_j w_j relu(u W1_e)^2
  W2_e) W_up`` over the selected experts HELD here (a dense loop over the held
  experts with a ``[T, held]`` combine weight); the shared expert ``relu(x
  V1)^2 V2`` on the full hidden state; the two added.

``logits = RMSNorm(h) W_head``, the head untied.

The architecture is read off the parameter tree: which branch a layer has;
heads and head size from the shapes of ``q``/``k`` and of ``A_log``; the
experts held, the latent and the expert widths from ``w1``; the router's width
from ``gate``; the vocabulary from the embedding (a slice is a smaller
vocabulary). What the tree cannot say are the source's constants, defaults
below: the state 128 (``STATE``: with it the groups follow from the
convolution's width), top-k 22, scaling 5, the two epsilons.

The chip's share. A ``moe`` holds ``E_held`` experts of the router's ``E``
(ids ``expert_offset ..``): routing is over all ``E``, the sum over the
selected experts held here, what absent experts would add is left out, as in
the system; so with the other seven tensor-parallel ranks' heads.

Memory at the timed size (one sequence of 8 192 tokens): every layer under
``jax.checkpoint``, attention in query blocks of ``Q_BLOCK`` rows, the
feed-forwards in blocks of ``ROW_BLOCK`` rows, the recurrence in blocks of
``POS_BLOCK`` positions (``granitemoehybrid.ssm_scan``), the experts one at a
time, each block under ``jax.checkpoint``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference.common import f32
from benchmark.reference.granitemoehybrid import attention, ssm_scan
from benchmark.reference.lfm2_moe import cross_entropy  # noqa: F401  (mean next-token loss)

STATE = 128  # ssm_state_size
TOP_K = 22
ROUTED_SCALING = 5.0
ROUTE_EPS = 1e-20
NORM_EPS = 1e-5
ROW_BLOCK = 2048  # rows per checkpointed block of a feed-forward


def _rms(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def _in_row_blocks(fn, rows):
    """``fn`` over ``rows [T, D]`` in blocks of ``ROW_BLOCK`` rows, each under
    ``jax.checkpoint``: one block's intermediates at a time."""
    size = math.gcd(rows.shape[0], ROW_BLOCK)
    out = lax.map(jax.checkpoint(fn), rows.reshape(-1, size, rows.shape[-1]))
    return out.reshape(rows.shape[0], -1)


def _relu2(x, w1, w2):
    return jnp.square(jax.nn.relu(x @ w1)) @ w2


def mamba(x, p, *, eps=NORM_EPS, state=STATE):
    """in_proj ``[D, 2I + 2GN + H]``, conv_w ``[K, I + 2GN]`` (tap j multiplies
    the input K-1-j steps back), conv_b, dt_bias / A_log / D ``[H]``, norm
    ``[I]``, out_proj ``[I, D]``; ``G`` from the convolution's width and ``N``."""
    heads, inner = p["A_log"].shape[0], p["norm"].shape[0]
    bc = (p["conv_w"].shape[1] - inner) // 2
    groups = bc // state
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
    gated = heads_of(y * jax.nn.silu(z), groups)  # [B, S, G, I / G]: a norm a group
    normed = gated * lax.rsqrt(jnp.mean(jnp.square(gated), axis=-1, keepdims=True) + eps)
    return (normed.reshape(u.shape) * p["norm"]) @ p["out_proj"]


def route(x, p, top_k=TOP_K, scaling=ROUTED_SCALING):
    """(selected ids ``[T, k]``, their weights ``[T, k]``) over ALL routed
    experts: selection by ``s + b``, weights from ``s`` alone."""
    s = jax.nn.sigmoid(x @ p["gate"])  # [T, E]
    _, sel = lax.top_k(s + p["e_score_correction_bias"], top_k)
    w = jnp.take_along_axis(s, sel, axis=-1)
    return sel, w / (jnp.sum(w, axis=-1, keepdims=True) + ROUTE_EPS) * scaling


def routed_experts(x, p, *, top_k=TOP_K, expert_offset=0, scaling=ROUTED_SCALING):
    """``[T, D]`` tokens through the experts held here: down ``[D, L]``, w1
    ``[E_held, L, F]``, w2 ``[E_held, F, L]``, up ``[L, D]``, gate ``[D, E]``."""
    sel, w = route(x, p, top_k, scaling)
    held = p["w1"].shape[0]
    # Dense combine weight of every (token, held expert): 0 where not selected.
    ids = expert_offset + jnp.arange(held)
    combine = jnp.sum(w[:, :, None] * (sel[:, :, None] == ids[None, None, :]), axis=1)
    u = x @ p["down"]

    @jax.checkpoint
    def one(y, e):
        w1, w2, c = e
        return y + c[:, None] * _relu2(u, w1, w2), None

    y, _ = lax.scan(one, jnp.zeros_like(u), (p["w1"], p["w2"], combine.T))
    return y @ p["up"]


def shared_expert(x, p):
    """``relu(x V1)^2 V2`` on the full hidden state, ``[T, D]``."""
    return _in_row_blocks(lambda r: _relu2(r, p["w1"], p["w2"]), x)


def moe(x, p, **kw):
    """The whole ``E`` branch on ``[T, D]``: routed part + shared expert."""
    return routed_experts(x, p, **kw) + shared_expert(x, p["shared"])


def _layer(x, lp, kw, selection):
    eps = kw.get("eps", NORM_EPS)
    h = _rms(x, lp["norm"]["scale"], eps)
    if "mamba" in lp:
        return x + mamba(h, lp["mamba"], eps=eps, state=kw.get("state", STATE))
    if "attn" in lp:
        return x + attention(h, lp["attn"], scale=lp["attn"]["q"].shape[-1] ** -0.5)
    flat = h.reshape(-1, h.shape[-1])
    moe_kw = {k: kw[k] for k in ("top_k", "expert_offset", "scaling") if k in kw}
    if selection is not None:
        sel, _ = route(flat, lp["moe"], moe_kw.get("top_k", TOP_K))
        selection.append(jnp.sort(sel, axis=-1))
    return x + moe(flat, lp["moe"], **moe_kw).reshape(h.shape)


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
        return x @ p["head"]["kernel"], (jnp.stack(selection) if selection else None)


def forward(variables, tokens, train: bool = False, **kw):
    """float32 logits ``[B, S, V]`` for int32 ``tokens [B, S]``. Train mode is
    the same function: no dropout, no auxiliary loss."""
    return _forward(variables, tokens, **kw)[0]


def selected_experts(variables, tokens, **kw):
    """``[E layers, B*S, k]`` ids the reference's routing selects, sorted
    within a token: what a check of the routing agreement compares."""
    return _forward(variables, tokens, want_selection=True, **kw)[1]


def loss_and_grads(variables, tokens, targets, **kw):
    def loss_fn(params):
        return cross_entropy(forward({"params": params}, tokens, train=True, **kw), targets)

    return jax.value_and_grad(loss_fn)(f32(variables["params"]))


def forward_flops(model: dict) -> int:
    """Matmul FLOPs (2 per multiply-add) one SEQUENCE's forward pass requires,
    from shapes, by the letters of ``hybrid_override_pattern``: ``M`` in_proj,
    out_proj, the K taps and the scan's four products at the chunk size with
    the causal half of the two intra-chunk ones (``benchmark/costs_nemotron_h.py``);
    ``*`` q, k, v, out projections, scores and weighted values over the CAUSAL
    half of S x S; ``E`` the router, both latent projections, the shared expert
    and the expert pairs computed HERE — ``S * top_k * held / routed`` of them,
    uniform routing assumed — through two ``latent x moe_intermediate``
    matmuls; the head. The embedding is a lookup. ``model``: the source's keys,
    ``n_routed_experts`` the experts held, ``n_routed_experts_published`` the
    router's width, ``seq_len`` the tokens."""
    from benchmark import costs_nemotron_h as costs

    s, d = model["seq_len"], model["hidden_size"]
    h, hkv, dh = model["num_attention_heads"], model["num_key_value_heads"], model["head_dim"]
    inner = model["mamba_num_heads"] * model["mamba_head_dim"]
    bc = model["n_groups"] * model["ssm_state_size"]
    latent = model["moe_latent_size"]
    routed = model.get("n_routed_experts_published", model["n_routed_experts"])
    macs = 0
    for kind in model["hybrid_override_pattern"]:
        if kind == "M":
            macs += s * d * (2 * inner + 2 * bc + model["mamba_num_heads"]) + s * inner * d
            macs += s * model["conv_kernel"] * (inner + 2 * bc)
            macs += costs.scan_forward_macs(model)
        elif kind == "*":
            macs += s * d * dh * (2 * h + 2 * hkv)  # q, out; k, v
            macs += 2 * h * dh * (s * s // 2)  # scores, weighted values: causal half
        else:
            macs += s * d * (routed + 2 * latent + 2 * model["moe_shared_expert_intermediate_size"])
            pairs = s * model["num_experts_per_tok"] * model["n_routed_experts"] // routed
            macs += pairs * 2 * latent * model["moe_intermediate_size"]
    macs += s * d * model["vocab_size"]
    return 2 * macs
