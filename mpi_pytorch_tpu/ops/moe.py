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

A second routing lives below the first, for the token models: sigmoid scores,
a selection bias, top-k over ALL routed experts with NO capacity and NO
dropped token (``sigmoid_topk_route``), and — apart from it — the pass over
the experts this chip HOLDS (``held_experts``): grouped matmuls over the routed
pairs sorted by expert, for whatever rows the caller dispatches and whatever
expert it hands in as a function of (rows, group sizes, its weights).
``models/lfm2.py`` routes and dispatches the same hidden state through SwiGLU
experts (``dropless_moe``, the two composed); ``models/nemotron_h.py`` routes
on the hidden state and dispatches rows of a narrower latent space through
squared-ReLU experts. The two routings share nothing yet; ROADMAP's Design
queue says which cell would settle them.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

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


def sigmoid_topk_route(x, gate, expert_bias, top_k: int, scaling: float = 1.0, eps: float = 1e-6):
    """Scores ``s = sigmoid(x W_g)`` in float32 over ALL routed experts; the
    top-k of ``s + b`` are SELECTED (``b`` steers the choice and nothing
    else); the weights are the selected ``s`` over their sum (+ ``eps``: the
    source's own constant), times ``scaling``. Returns (ids ``[T, k]`` int32,
    weights ``[T, k]`` float32).
    The scores' matmul asks for HIGHEST precision: a TPU's default would round
    its float32 operands to bf16, and top-k over 64 close scores flips on less."""
    s = jax.nn.sigmoid(
        jnp.dot(x.astype(jnp.float32), gate.astype(jnp.float32), precision=lax.Precision.HIGHEST)
    )
    _, sel = lax.top_k(s + lax.stop_gradient(expert_bias.astype(jnp.float32)), top_k)
    w = jnp.take_along_axis(s, sel, axis=-1)
    return sel, w / (jnp.sum(w, axis=-1, keepdims=True) + eps) * scaling


def grouped_matmul(rows, weights, group_sizes):
    """``rows [R, K]`` sorted by group against ``weights [G, K, N]``: rows of
    group g meet ``weights[g]``; float32 accumulation, the rows' dtype out.
    ``jax.lax.ragged_dot``: on a TPU one Mosaic grouped-matmul call whose work
    follows the rows the groups cover (rows past them are not visited)."""
    return lax.ragged_dot(rows, weights, group_sizes, preferred_element_type=rows.dtype)


# A row bound is a whole number of these: the grouped matmul tiles its rows.
ROW_TILE = 128


def row_bound(pairs: int, held: int, routed: int, slack: int = 2) -> int:
    """``C``, the rows of the expert layer's buffers: ``slack`` times the
    share of the ``pairs`` (tokens x choices) that ``held`` of ``routed``
    experts expect, in whole row tiles, and never more than all of them —
    which it is where every routed expert is held. Twice by default, because
    a pass costs by its rows whether filled or not: while lfm2's router is
    sound every layer of every step holds under 1.1 times the share, and four
    times the share cost its layer 4.4 ms of 14.7 more (PERF.md section 6,
    PR 30). A caller whose routing swings further than that asks for more
    (``models/nemotron_h.py``: PERF.md section 6, PR 33). The bound also sets
    how a pass returns to tokens (``BY_ROW_FROM``)."""
    expected = -(-slack * pairs * held // routed)
    return min(pairs, -(-expected // ROW_TILE) * ROW_TILE)


class _Sorted(NamedTuple):
    """The routed pairs sorted by held expert, absent ones last. Pairs are
    numbered CHOICE-major — pair p is choice ``p // T`` of token ``p % T`` —
    so that ``[k * T]`` splits into ``[k, T]`` along the major dimension."""

    order: jax.Array  # [chunks * C] int32: the pair at each sorted position (padded)
    place: jax.Array  # [k, T] int32: the sorted position of each pair
    ends: jax.Array  # [H] int32: where each held expert's run of positions ends
    n_held: jax.Array  # int32: positions below it hold a pair of a held expert


def _chunk(pairs: _Sorted, start, bound: int, tokens: int):
    """Sorted positions ``start .. start + bound``: (their pairs, their
    tokens, how many of them each held expert has, how many are held)."""
    ids = lax.dynamic_slice_in_dim(pairs.order, start, bound)
    sizes = jnp.diff(jnp.clip(pairs.ends - start, 0, bound), prepend=0)
    return ids, ids % tokens, sizes, jnp.clip(pairs.n_held - start, 0, bound)


def swiglu_expert(rows, sizes, w1, w3, w2):
    """``W2 (silu(W1 r) * W3 r)``: ``w1``, ``w3 [H, D, F]``, ``w2 [H, F, D]``."""
    hidden = jax.nn.silu(grouped_matmul(rows, w1, sizes)) * grouped_matmul(rows, w3, sizes)
    return grouped_matmul(hidden, w2, sizes)


def relu2_expert(rows, sizes, w1, w2):
    """``W2 relu(W1 r)^2``, no gate: ``w1 [H, D, F]``, ``w2 [H, F, D]``."""
    return grouped_matmul(jnp.square(jax.nn.relu(grouped_matmul(rows, w1, sizes))), w2, sizes)


# Routed pairs a buffer row (``k * T / C``) from which a pass returns to tokens
# BY ROW. The per-pair read costs by ``k * T`` rows and the sum by row by ``C``,
# so the two cross in that ratio. One expert layer, forward + backward, on a
# v5e, ms per pair / by row: 2 048-wide rows 13.6 / 16.7 at 4 pairs a row,
# 15.4 / 16.5 at 8, 17.8 / 16.7 at 12, 20.0 / 17.0 at 16; 1 024-wide rows
# 14.8 / 15.7 at 6, 13.2 / 12.9 at 8, 24.6 / 13.1 at 16 (PERF.md section 6,
# PR 34).
BY_ROW_FROM = 10


def _returns_by_row(pairs: int, bound: int) -> bool:
    """Whether a pass of ``bound`` buffer rows over ``pairs`` routed pairs goes
    back to tokens by row (``_sum_by_token``) or once a pair (``_by_token``):
    from the two SHAPES alone."""
    return pairs >= BY_ROW_FROM * bound


def _by_token(buffer, at, n_live):
    """``buffer [C, ...]`` read once a pair: ``[k, T, ...]``, entry ``(j, t)``
    the row at ``at[j, t]`` where that lies in ``0 .. n_live``, else 0. The
    way from sorted rows back to tokens as a gather of ``k * T`` rows, which
    the caller sums over ``k``: the cheaper way while a buffer row stands for
    few routed pairs (at lfm2's 4 this read and its sum take 1.0 ms for 16 384
    rows of 2 048 where ``_sum_by_token`` takes 1.9), the dearer from
    ``BY_ROW_FROM`` on. Every pass is read on its own: a gather costs by its
    operand, 0.41 ms from lfm2's 67 MB and 2.39 from one ``[T * k, D]`` buffer
    all passes would land in. Rows past ``n_live`` hold whatever the buffer
    held, so they are masked, never multiplied by zero."""
    live = (at >= 0) & (at < n_live)
    rows = jnp.take(buffer, at.reshape(-1), axis=0, mode="clip").reshape(at.shape + buffer.shape[1:])
    return jnp.where(live.reshape(at.shape + (1,) * (buffer.ndim - 1)), rows, 0)


def _sum_by_token(rows, token, n_live, tokens: int):
    """``rows [C, D]`` float32 summed into ``[tokens, D]`` by ``token [C]``:
    the transpose of ``_by_token`` and its sum, a scatter-add of ``C`` rows,
    through a ``[C, D / 128, 128]`` view in which a row is whole lane tiles.
    The plain two-dimensional scatter is 1.3 ms a layer faster on a v5e and
    ~1.5 MB of generated code an instance, forty instances a step of
    ``nemotron_h``: its program then no longer fits the compile cache beside
    the others of its run and every start compiles it again (PERF.md section
    6, PR 34). The TPU compiler sorts the indices itself (sorting the rows by
    token first bought nothing). Rows past ``n_live`` are masked, never
    multiplied by zero."""
    live = (jnp.arange(rows.shape[0]) < n_live)[:, None]
    tiles = jnp.where(live, rows, 0).reshape(rows.shape[0], -1, math.gcd(rows.shape[1], 128))
    return jax.ops.segment_sum(tiles, token, num_segments=tokens).reshape(tokens, -1)


def _chunk_forward(bound, expert, start, x, weights, share, pairs):
    """What the held pairs at sorted positions ``start .. start + bound`` add
    to ``y [T, D]`` (float32)."""
    ids, token, sizes, n_live = _chunk(pairs, start, bound, x.shape[0])
    with jax.named_scope("moe/dispatch"):
        rows = jnp.take(x, token, axis=0, mode="clip")
    with jax.named_scope("moe/experts"):
        out = expert(rows, sizes, *weights)
    with jax.named_scope("moe/combine"):
        if _returns_by_row(share.size, bound):
            weight = jnp.take(share.reshape(-1), ids, mode="clip")
            return _sum_by_token(out.astype(jnp.float32) * weight[:, None], token, n_live, x.shape[0])
        mine = _by_token(out, pairs.place - start, n_live).astype(jnp.float32)
        return jnp.sum(mine * share[..., None], axis=0)


def _chunk_backward(bound, expert, start, x, weights, share, pairs, ct_y):
    """The same chunk recomputed and pulled back: its part of the cotangents
    of ``x`` (float32), the expert's ``weights`` and ``share``."""
    ids, token, sizes, n_live = _chunk(pairs, start, bound, x.shape[0])
    by_row = _returns_by_row(share.size, bound)
    at = None if by_row else pairs.place - start
    live = (jnp.arange(bound) < n_live)[:, None]
    with jax.named_scope("moe/dispatch"):
        rows = jnp.take(x, token, axis=0, mode="clip")
    with jax.named_scope("moe/experts"):
        out, pull = jax.vjp(lambda rows, *w: expert(rows, sizes, *w), rows, *weights)
    with jax.named_scope("moe/combine"):
        weight = jnp.take(share.reshape(-1), ids, mode="clip")
        ct_y_rows = jnp.take(ct_y, token, axis=0, mode="clip").astype(jnp.float32)
        ct_out = jnp.where(live, ct_y_rows * weight[:, None], 0).astype(out.dtype)
        ct_weight = jnp.sum(out.astype(jnp.float32) * ct_y_rows, axis=-1)
        if by_row:
            # ``C`` scalars to their pairs: the live rows' ids are distinct.
            ct_share = jnp.zeros(share.size, jnp.float32).at[ids].add(jnp.where(live[:, 0], ct_weight, 0))
            ct_share = ct_share.reshape(share.shape)
        else:
            ct_share = _by_token(ct_weight, at, n_live)
    with jax.named_scope("moe/experts"):
        ct_rows, *ct_weights = pull(ct_out)
    with jax.named_scope("moe/dispatch"):
        if by_row:
            ct_x = _sum_by_token(ct_rows.astype(jnp.float32), token, n_live, x.shape[0])
        else:
            ct_x = jnp.sum(_by_token(ct_rows, at, n_live).astype(jnp.float32), axis=0)
    return ct_x, tuple(ct_weights), ct_share


def _live_chunks(bound: int, pairs: _Sorted, chunk):
    """The sum of ``chunk(start)`` over the chunks of ``bound`` sorted
    positions that hold a held pair — the first whatever it holds, the others
    in a loop on the device that ends at ``n_held``: as many passes as the
    routing demands, one where the held pairs fit ``bound``. Returns (the
    rows the passes ran over, the sum)."""
    # The first pass is not the loop's, though that compiles the body twice:
    # a sum onto zeros in a loop's carry is two [T, D] float32 adds and three
    # of the experts' weights a layer, 2.9 ms of the cell's 14.7.
    first = (jnp.int32(bound), chunk(0))
    if pairs.order.shape[0] == bound:
        return first

    def another(carry):
        start, total = carry
        return start + bound, jax.tree.map(jnp.add, total, chunk(start))

    return lax.while_loop(lambda carry: carry[0] < pairs.n_held, another, first)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _held_ffn(bound: int, expert, x, weights, share, pairs: _Sorted):
    """``y[t] = sum_j share[j, t] * expert_e(j, t)(x[t])`` over the held
    pairs, in buffers of ``bound`` rows, and the buffer rows that took.
    ``expert(rows, sizes, *weights)`` is the held experts' function of rows
    sorted by expert. Nothing is kept for the backward pass but the
    arguments: it computes each chunk's experts again."""
    rows_run, y = _live_chunks(
        bound, pairs, lambda start: _chunk_forward(bound, expert, start, x, weights, share, pairs)
    )
    return y.astype(x.dtype), rows_run


def _held_ffn_fwd(bound, expert, x, weights, share, pairs):
    return _held_ffn(bound, expert, x, weights, share, pairs), (x, weights, share, pairs)


def _held_ffn_bwd(bound, expert, saved, cotangents):
    # What ``jax.checkpoint`` does for its recomputation: without the barrier
    # XLA finds the first chunk's FFN in the forward pass and keeps it (2.0 GB
    # of the cell's memory for 3 ms of its 257 ms step).
    saved, ct_y = lax.optimization_barrier((saved, cotangents[0]))
    x, *_, pairs = saved
    _, (ct_x, ct_weights, ct_share) = _live_chunks(
        bound, pairs, lambda start: _chunk_backward(bound, expert, start, *saved, ct_y)
    )
    return ct_x.astype(x.dtype), ct_weights, ct_share, None


_held_ffn.defvjp(_held_ffn_fwd, _held_ffn_bwd)


def held_experts(
    x, sel, weight, expert, weights, *, routed: int, expert_offset: int = 0, slack: int = 2
):
    """The pass over the experts held here, for one expert-parallel rank
    without its exchange.

    ``x [T, D]`` the rows to dispatch (the hidden state, or a latent
    projection of it); ``sel``, ``weight [T, k]`` every token's selection
    over ALL ``routed`` experts and its weights (``sigmoid_topk_route``);
    ``weights`` a tuple of arrays with the ``H`` held experts leading, ids
    ``expert_offset .. expert_offset + H``, and ``expert(rows, sizes,
    *weights)`` their function of rows sorted by expert (``swiglu_expert``,
    ``relu2_expert``). The result is, for every token, the weighted sum of
    its selected experts HELD HERE; what absent experts would add is left out
    (their pairs are counted, below). No capacity: the ``T * k`` routed pairs
    are sorted by expert — absent ones last — and the held ones run through
    the grouped matmuls whatever their split over the experts, so adversarial
    routing (every token to one expert) drops nothing.

    The row buffers follow the pairs held. They have ``C = row_bound(T * k,
    H, E, slack)`` rows, ``slack`` times what ``H`` of ``E`` experts expect, and the sorted
    pairs go through them ``C`` at a time for as long as held pairs remain
    (``_live_chunks``, from ``n_held`` on the device): one pass where the
    routing is anywhere near even, ``T * k / C`` where every pair lands here.
    A pass goes back from its ``C`` rows to the ``T`` tokens in one of two
    forms, chosen from ``T * k / C`` alone (``BY_ROW_FROM``): under it by a
    read of the buffer once a routed pair (``_by_token``: the pass costs by
    its ``T * k`` reads besides its ``C`` rows), from it on by a sum of the
    ``C`` rows by token (``_sum_by_token``: the pass costs by ``C`` alone);
    the same float32 products and sums either way, in another order.
    A pass past the first costs about a fifth more than the same rows cost
    in full-size buffers (each repeats the return to tokens and the weights'
    sums), so from three passes on this is the slower way: a router that has
    collapsed onto the experts held here, not one that is learning.
    A rank that holds every expert has ``C = T * k``, one pass and no loop.
    The experts are recomputed in the backward pass (``_held_ffn``): their
    ``[C, F]`` intermediates would otherwise be kept for every layer.

    Returns ``(y [T, D], counters)``: ``moe_pairs_held`` (pairs computed
    here), ``moe_pairs_absent`` (pairs routed to experts not held),
    ``moe_load_max`` (largest per-expert count), ``moe_rows_computed``
    (buffer rows the passes ran over: ``C`` a pass), int32 scalars.
    """
    from mpi_pytorch_tpu.obs import trace as obs_trace

    t, d = x.shape
    top_k, held = sel.shape[1], weights[0].shape[0]
    bound = row_bound(t * top_k, held, routed, slack)
    chunks = -(-t * top_k // bound)
    obs_trace.current().instant(
        "moe/dispatch",
        {"experts": int(routed), "held": int(held), "top_k": top_k, "tokens": int(t),
         "latent": int(d), "path": "ragged_dot", "rows_bound": bound,
         "combine": "by_row" if _returns_by_row(t * top_k, bound) else "per_pair",
         "pairs_per_row": round(t * top_k / bound, 2)},
        once=True,
    )
    with jax.named_scope("moe/dispatch"):
        # [k*T] pair -> held index
        local = sel.T.reshape(-1) - expert_offset
        here = (local >= 0) & (local < held)
        key = jnp.where(here, local, held)  # absent pairs sort last
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        # A second sort inverts the first in 0.05 ms at 65 536 pairs; a scatter
        # takes 0.30, and a bincount's scatter-add 0.57 for a compare and sum's 0.01.
        place = jnp.argsort(order).astype(jnp.int32)
        group_sizes = jnp.sum(key[:, None] == jnp.arange(held), axis=0, dtype=jnp.int32)
        n_held = jnp.sum(group_sizes)
        pairs = _Sorted(
            jnp.pad(order, (0, chunks * bound - t * top_k)), place.reshape(top_k, t),
            jnp.cumsum(group_sizes), n_held,
        )
    with jax.named_scope("moe/combine"):
        share = jnp.where(here.reshape(top_k, t), weight.T, 0.0).astype(jnp.float32)
    y, rows_run = _held_ffn(
        bound, expert, x, tuple(w.astype(x.dtype) for w in weights), share, pairs
    )
    counters = {
        "moe_pairs_held": n_held,
        "moe_pairs_absent": t * top_k - n_held,
        "moe_load_max": jnp.max(group_sizes),
        "moe_rows_computed": rows_run,
    }
    return y, {name: value.astype(jnp.int32) for name, value in counters.items()}


def dropless_moe(
    x, gate, expert_bias, w1, w3, w2, *, top_k: int, expert_offset: int = 0,
    scaling: float = 1.0,
):
    """``models/lfm2.py``'s expert layer: every token of ``x [T, D]`` routes
    over all ``E`` experts of ``gate [D, E]`` (``sigmoid_topk_route``) and the
    SAME rows go through the ``H`` SwiGLU experts held here (``held_experts``
    with ``w1``/``w3 [H, D, F]``, ``w2 [H, F, D]``). Returns ``(y, counters,
    selected [T, k])``: the ids every token selected, for whoever compares
    routings."""
    with jax.named_scope("moe/route"):
        sel, weight = sigmoid_topk_route(x, gate, expert_bias, top_k, scaling)
    y, counters = held_experts(
        x, sel, weight, swiglu_expert, (w1, w3, w2),
        routed=gate.shape[1], expert_offset=expert_offset,
    )
    return y, counters, sel
