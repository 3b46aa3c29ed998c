"""Mixture-of-Experts FFN with expert parallelism (EP) over a mesh axis.

The reference has no MoE (its seven CNNs are dense, ``models.py:16-101``;
SURVEY §2c lists EP as absent), but a complete TPU-native parallelism matrix
needs the strategy: experts are sharded over an ``expert`` mesh axis and
tokens travel to their experts over the ICI via ``lax.all_to_all`` — the
canonical TPU MoE dataflow (dispatch → all-to-all → local expert FFNs →
all-to-all back → combine).

Routing is Mesh-TensorFlow-style static-capacity top-k:

- gate logits over all ``E`` experts, softmax, top-k choice per token;
- each expert accepts at most ``capacity`` tokens *per shard* (XLA needs
  static shapes — overflow tokens are dropped from that expert's
  contribution, exactly like production TPU MoEs; their combine weight is 0
  so the token simply passes less signal through);
- dispatch/combine are one-hot tensors ``[T, E, C]``, so dispatch is an
  einsum (MXU work, not scatter).

The auxiliary load-balance loss (Shazeer et al.: ``E · Σ_e f_e · p̄_e``)
is returned alongside the output; add it to the task loss with a small
coefficient to keep routing uniform.

tests/test_moe.py asserts the 8-shard EP result equals a dense single-device
evaluation of the same routing, values and gradients.

A second routing lives below the first (``dropless_moe``, for
``models/lfm2.py``): sigmoid scores, a selection bias, top-k over ALL routed
experts with NO capacity and NO dropped token, computed for the experts this
chip HOLDS by grouped matmuls over the routed pairs sorted by expert. The two
share nothing yet; ROADMAP's Design queue says which cell would settle them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from mpi_pytorch_tpu.parallel.compat import shard_map


def init_moe_params(rng, d_model: int, d_hidden: int, num_experts: int) -> dict:
    """Gate + per-expert two-layer FFN params. Expert-axis-leading leaves
    (``w1 [E, d, h]`` etc.) so EP sharding is a leading-axis PartitionSpec."""
    kg, k1, k2 = jax.random.split(rng, 3)
    scale1 = (2.0 / d_model) ** 0.5
    scale2 = (2.0 / d_hidden) ** 0.5
    return {
        "gate": jax.random.normal(kg, (d_model, num_experts), jnp.float32)
        * (1.0 / d_model**0.5),
        "w1": jax.random.normal(k1, (num_experts, d_model, d_hidden), jnp.float32)
        * scale1,
        "b1": jnp.zeros((num_experts, d_hidden), jnp.float32),
        "w2": jax.random.normal(k2, (num_experts, d_hidden, d_model), jnp.float32)
        * scale2,
        "b2": jnp.zeros((num_experts, d_model), jnp.float32),
    }


def _routing(gate_logits, k: int, capacity: int):
    """Top-k static-capacity routing → (dispatch [T,E,C], combine [T,E,C],
    aux load-balance loss). Pure function of the gate logits; shared by the
    EP path and the dense reference so the two can never disagree."""
    t, e = gate_logits.shape
    probs = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)

    dispatch = jnp.zeros((t, e, capacity), jnp.float32)
    combine = jnp.zeros((t, e, capacity), jnp.float32)
    # Fill per-expert capacity slots choice-by-choice: the j-th choices of
    # all tokens are assigned after every (j-1)-th choice, tokens in order —
    # a deterministic, priority-respecting slotting (standard MTF semantics).
    taken = jnp.zeros((e,), jnp.int32)  # slots already used per expert
    masked = probs
    for _ in range(k):
        choice = jnp.argmax(masked, axis=-1)  # [T]
        gatew = jnp.take_along_axis(probs, choice[:, None], axis=-1)[:, 0]
        onehot = jax.nn.one_hot(choice, e, dtype=jnp.int32)  # [T, E]
        # Position of each token within its chosen expert's buffer.
        pos = taken[choice] + (jnp.cumsum(onehot, axis=0) - onehot)[
            jnp.arange(t), choice
        ]
        keep = pos < capacity
        oh = (
            jax.nn.one_hot(choice, e, dtype=jnp.float32)[:, :, None]
            * jax.nn.one_hot(jnp.clip(pos, 0, capacity - 1), capacity)[:, None, :]
            * keep[:, None, None]
        )
        dispatch = dispatch + oh
        combine = combine + oh * gatew[:, None, None]
        taken = taken + jnp.sum(onehot, axis=0)
        masked = jnp.where(jax.nn.one_hot(choice, e, dtype=bool), -jnp.inf, masked)

    # Load-balance aux (Shazeer): fraction of token-routings landing on e
    # (all k choices, normalized by k) × mean gate prob for e, summed, ×E.
    frac = jnp.mean(dispatch.sum(-1), axis=0)  # [E] tokens-per-expert / T
    aux = e * jnp.sum(frac / max(k, 1) * jnp.mean(probs, axis=0))
    return dispatch, combine, aux


def pick_group_size(tokens: int, group_size: int | None) -> int:
    """Largest divisor of ``tokens`` that is <= ``group_size`` (all-tokens
    when None). Grouped routing needs the token count to split into equal
    groups; blind clamping to min(group_size, tokens) crashes on token
    counts that are not multiples of the requested group."""
    if group_size is None or group_size >= tokens:
        return tokens
    g = max(1, group_size)
    while tokens % g:
        g -= 1
    return g


def _grouped_routing(gate_logits, k: int, capacity: int, group_size: int):
    """Group-wise routing: tokens are routed in independent groups of
    ``group_size``, each with its own ``capacity`` slots per expert. This is
    what makes the one-hot dispatch scale: per-group dispatch is [g, E, C]
    with C ∝ g, so the total [G, g, E, C] tensor is LINEAR in token count
    (ungrouped [T, E, C] with C ∝ T is quadratic — unusable at training
    batch sizes). Returns dispatch/combine [G, g, E, C] and the aux loss
    averaged over groups."""
    t = gate_logits.shape[0]
    if t % group_size:
        raise ValueError(f"tokens {t} not divisible by group_size {group_size}")
    grouped = gate_logits.reshape(t // group_size, group_size, -1)
    dispatch, combine, aux = jax.vmap(
        lambda gl: _routing(gl, k, capacity)
    )(grouped)
    return dispatch, combine, jnp.mean(aux)


def dense_moe(
    params: dict,
    x,
    *,
    k: int = 2,
    capacity: int | None = None,
    group_size: int | None = None,
):
    """Single-device reference MoE (also the EP-free fallback): same routing,
    experts applied by einsum over the full expert axis. Returns (y, aux).

    ``group_size`` routes tokens in independent fixed-size groups; capacity
    is then PER GROUP. Defaults: one group of all tokens, capacity =
    group size (no drops). See ``_grouped_routing`` for why grouping is the
    scalable form."""
    t, d = x.shape
    g = group_size if group_size is not None else t
    capacity = capacity if capacity is not None else g
    dispatch, combine, aux = _grouped_routing(x @ params["gate"], k, capacity, g)
    xg = x.reshape(t // g, g, d)
    xin = jnp.einsum("gtec,gtd->gecd", dispatch, xg)
    h = jax.nn.gelu(
        jnp.einsum("gecd,edh->gech", xin, params["w1"]) + params["b1"][None, :, None]
    )
    out = jnp.einsum("gech,ehd->gecd", h, params["w2"]) + params["b2"][None, :, None]
    y = jnp.einsum("gecd,gtec->gtd", out, combine)
    return y.reshape(t, d).astype(x.dtype), aux


def moe_ffn(
    params: dict,
    x,
    *,
    axis_name: str,
    k: int = 2,
    capacity: int,
    group_size: int | None = None,
):
    """Per-shard expert-parallel MoE. Must run inside an SPMD context binding
    ``axis_name`` (size n): ``x [t_local, d]`` is the shard's tokens;
    ``params['w1']/['b1']/['w2']/['b2']`` hold only the shard's ``E/n`` local
    experts (leading axis sharded); ``params['gate']`` is replicated.

    Dataflow per shard: route against ALL ``E`` experts (group-wise, capacity
    per group — see ``_grouped_routing``) → buffers ``[E, G·C, d]`` → tiled
    ``all_to_all`` regroups to ``[E/n, n·G·C, d]`` (my experts, every shard's
    slots) → local expert FFNs → inverse ``all_to_all`` → weighted combine.
    Returns ``(y [t_local, d], aux)`` with ``aux`` pmean'd across shards.
    """
    t, d = x.shape
    e = params["gate"].shape[1]
    g = group_size if group_size is not None else t
    dispatch, combine, aux = _grouped_routing(x @ params["gate"], k, capacity, g)

    xg = x.reshape(t // g, g, d)
    xin = jnp.einsum("gtec,gtd->gecd", dispatch, xg)  # [G, E, C, d]
    # Fold groups into the slot axis so the all_to_all sees one [E, G*C, d]
    # buffer (expert compute is position-agnostic along slots).
    n_groups, _, cap = xin.shape[0], xin.shape[1], xin.shape[2]
    xin = xin.transpose(1, 0, 2, 3).reshape(e, n_groups * cap, d)
    # → [E/n, n*G*C, d]: shard i keeps rows for ITS experts from every shard.
    xin = lax.all_to_all(xin, axis_name, split_axis=0, concat_axis=1, tiled=True)
    h = jax.nn.gelu(
        jnp.einsum("ecd,edh->ech", xin, params["w1"]) + params["b1"][:, None]
    )
    out = jnp.einsum("ech,ehd->ecd", h, params["w2"]) + params["b2"][:, None]
    # Inverse regroup: back to [E, G*C, d] rows for MY tokens.
    out = lax.all_to_all(out, axis_name, split_axis=1, concat_axis=0, tiled=True)
    out = out.reshape(e, n_groups, cap, d).transpose(1, 0, 2, 3)
    y = jnp.einsum("gecd,gtec->gtd", out, combine).reshape(t, d).astype(x.dtype)
    return y, lax.pmean(aux, axis_name)


@functools.lru_cache(maxsize=None)
def _moe_jit(mesh, axis, k, capacity, group_size):
    pspec = {
        "gate": P(),
        "w1": P(axis),
        "b1": P(axis),
        "w2": P(axis),
        "b2": P(axis),
    }
    fn = shard_map(
        functools.partial(
            moe_ffn, axis_name=axis, k=k, capacity=capacity, group_size=group_size
        ),
        mesh=mesh,
        in_specs=(pspec, P(axis)),
        out_specs=(P(axis), P()),
        check_vma=False,
    )
    return jax.jit(fn)


def moe_forward(
    params: dict,
    x,
    mesh: Mesh,
    *,
    expert_axis: str | None = None,
    k: int = 2,
    capacity: int | None = None,
    group_size: int | None = None,
):
    """Driver-facing wrapper: tokens ``[T, d]`` sharded over ``expert_axis``
    (EP=DP layout — each shard routes its own tokens), experts sharded over
    the same axis. ``group_size`` (clamped to the per-shard token count)
    routes in independent groups; ``capacity`` is PER GROUP and defaults to
    the group size (no drops when routing is balanced within 1×). Returns
    ``(y [T, d], aux_loss)``."""
    expert_axis = expert_axis or mesh.axis_names[0]
    n = mesh.shape[expert_axis]
    t = x.shape[0]
    e = params["gate"].shape[1]
    if t % n or e % n:
        raise ValueError(
            f"'{expert_axis}' axis size {n} must divide both "
            f"tokens ({t}) and experts ({e})"
        )
    g = pick_group_size(t // n, group_size)
    capacity = capacity if capacity is not None else g
    return _moe_jit(mesh, expert_axis, k, capacity, g)(params, x)


# ---------------------------------------------------------------------------
# Dropless routing over the experts held here (models/lfm2.py)
# ---------------------------------------------------------------------------


def sigmoid_topk_route(x, gate, expert_bias, top_k: int, scaling: float = 1.0):
    """Scores ``s = sigmoid(x W_g)`` in float32 over ALL routed experts; the
    top-k of ``s + b`` are SELECTED (``b`` steers the choice and nothing
    else); the weights are the selected ``s`` over their sum (+ 1e-6), times
    ``scaling``. Returns (ids ``[T, k]`` int32, weights ``[T, k]`` float32).
    The scores' matmul asks for HIGHEST precision: a TPU's default would round
    its float32 operands to bf16, and top-k over 64 close scores flips on less."""
    s = jax.nn.sigmoid(
        jnp.dot(x.astype(jnp.float32), gate.astype(jnp.float32), precision=lax.Precision.HIGHEST)
    )
    _, sel = lax.top_k(s + lax.stop_gradient(expert_bias.astype(jnp.float32)), top_k)
    w = jnp.take_along_axis(s, sel, axis=-1)
    return sel, w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6) * scaling


def grouped_matmul(rows, weights, group_sizes):
    """``rows [R, K]`` sorted by group against ``weights [G, K, N]``: rows of
    group g meet ``weights[g]``; float32 accumulation, the rows' dtype out.
    ``jax.lax.ragged_dot``: on a TPU one Mosaic grouped-matmul call whose work
    follows the rows the groups cover (rows past them are not visited)."""
    return lax.ragged_dot(rows, weights, group_sizes, preferred_element_type=rows.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _pair_rows(x, perm, inv_perm, top_k: int):
    """Row r of the result is the token of routed pair ``perm[r]``. Pairs are
    numbered CHOICE-major — pair p is choice ``p // T`` of token ``p % T`` —
    so that ``[k * T, D]`` pair rows split into ``[k, T, D]`` along the major
    dimension alone (token-major pairs would put ``k`` beside ``D`` in the
    tiled layout, a padded copy each way: 3 ms a layer at 65 536 pairs).
    ``inv_perm`` is handed in so that the cotangent is a gather too
    (``ct[inv_perm]``, summed over a token's choices): the transpose of a
    gather is a scatter-add that cannot know its indices are a permutation."""
    return jnp.take(x, perm % x.shape[0], axis=0)


def _pair_rows_fwd(x, perm, inv_perm, top_k):
    return _pair_rows(x, perm, inv_perm, top_k), inv_perm


def _pair_rows_bwd(top_k, inv_perm, ct):
    by_choice = jnp.take(ct, inv_perm, axis=0).reshape(top_k, -1, ct.shape[-1])
    return jnp.sum(by_choice.astype(jnp.float32), axis=0).astype(ct.dtype), None, None


_pair_rows.defvjp(_pair_rows_fwd, _pair_rows_bwd)


@jax.custom_vjp
def _unsort(rows, perm, inv_perm):
    """``rows[inv_perm]``: sorted rows back in pair order; cotangent ``ct[perm]``."""
    return jnp.take(rows, inv_perm, axis=0)


_unsort.defvjp(
    lambda rows, perm, inv_perm: (jnp.take(rows, inv_perm, axis=0), perm),
    lambda perm, ct: (jnp.take(ct, perm, axis=0), None, None),
)


def dropless_moe(
    x, gate, expert_bias, w1, w3, w2, *, top_k: int, expert_offset: int = 0,
    scaling: float = 1.0,
):
    """The expert layer of one expert-parallel rank, without its exchange.

    ``x [T, D]`` tokens; ``gate [D, E]`` and ``expert_bias [E]`` span ALL
    ``E`` routed experts; ``w1``/``w3 [H, D, F]`` and ``w2 [H, F, D]`` are the
    ``H`` experts held here, ids ``expert_offset .. expert_offset + H``. Every
    token routes over all ``E`` (``sigmoid_topk_route``); the result is the
    weighted sum over its selected experts HELD HERE of ``W2 (silu(W1 h) *
    W3 h)``; what absent experts would add is left out (their pairs are
    counted, below). No capacity: the ``T * k`` routed pairs are sorted by
    expert — absent ones last — and the held ones run through three grouped
    matmuls whatever their split over the experts, so adversarial routing
    (every token to one expert) drops nothing.

    The row buffers are sized for the worst case (``T * k`` rows); the grouped
    matmuls visit only the rows the held pairs fill. The expert FFN is
    recomputed in the backward pass (``jax.checkpoint``): its ``[T * k, F]``
    intermediates would otherwise be kept for every layer at the worst-case
    size, an eighth of them used.

    Returns ``(y [T, D], counters, selected [T, k])``: ``moe_pairs_held``
    (pairs computed here), ``moe_pairs_absent`` (pairs routed to experts not
    held), ``moe_load_max`` (largest per-expert count), int32 scalars; and the
    ids every token selected, for whoever compares routings.
    """
    from mpi_pytorch_tpu.obs import trace as obs_trace

    t, d = x.shape
    held = w1.shape[0]
    obs_trace.current().instant(
        "moe/dispatch",
        {"experts": int(gate.shape[1]), "held": int(held), "top_k": top_k,
         "tokens": int(t), "path": "ragged_dot"},
        once=True,
    )
    with jax.named_scope("moe/route"):
        sel, weight = sigmoid_topk_route(x, gate, expert_bias, top_k, scaling)
    with jax.named_scope("moe/dispatch"):
        # [k*T] pair -> held index; pair p = choice p // T of token p % T
        local = sel.T.reshape(-1) - expert_offset
        here = (local >= 0) & (local < held)
        key = jnp.where(here, local, held)  # absent pairs sort last
        perm = jnp.argsort(key, stable=True).astype(jnp.int32)
        inv_perm = jnp.zeros_like(perm).at[perm].set(jnp.arange(t * top_k, dtype=jnp.int32))
        group_sizes = jnp.bincount(key, length=held + 1)[:held].astype(jnp.int32)
        n_held = jnp.sum(group_sizes)
        filled = (jnp.arange(t * top_k) < n_held)[:, None]  # rows a held pair fills

    @jax.checkpoint
    def experts(x, w1, w3, w2):
        with jax.named_scope("moe/dispatch"):
            rows = jnp.where(filled, _pair_rows(x, perm, inv_perm, top_k), 0)
        with jax.named_scope("moe/experts"):
            hidden = jax.nn.silu(grouped_matmul(rows, w1, group_sizes)) * grouped_matmul(
                rows, w3, group_sizes
            )
            out = grouped_matmul(hidden, w2, group_sizes)
        with jax.named_scope("moe/combine"):
            # Rows no group covers hold whatever the buffer held.
            return _unsort(jnp.where(filled, out, 0), perm, inv_perm)

    out = experts(x, w1.astype(x.dtype), w3.astype(x.dtype), w2.astype(x.dtype))
    with jax.named_scope("moe/combine"):
        share = jnp.where(here.reshape(top_k, t), weight.T, 0.0).astype(jnp.float32)
        y = jnp.sum(out.reshape(top_k, t, d) * share[..., None], axis=0)
    counters = {
        "moe_pairs_held": n_held.astype(jnp.int32),
        "moe_pairs_absent": (t * top_k - n_held).astype(jnp.int32),
        "moe_load_max": jnp.max(group_sizes).astype(jnp.int32),
    }
    return y.astype(x.dtype), counters, sel
