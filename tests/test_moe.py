"""Expert-parallel MoE vs the dense single-device evaluation on the 8-device
CPU mesh — values, gradients, aux-loss agreement, capacity drops, and guards.

The correctness property: sharding experts over the mesh and moving tokens
via all_to_all computes exactly the dense per-shard routing result (each
shard routes its own tokens with its own capacity budget — the documented
EP semantics), for both top-1 and top-2 routing.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from mpi_pytorch_tpu.ops.moe import (
    dense_moe,
    init_moe_params,
    moe_forward,
)

N_SHARDS = 8
E = 16  # 2 experts per shard
D = 8
H = 32
T = 64  # 8 tokens per shard


@pytest.fixture(scope="module")
def mesh():
    dev = np.asarray(jax.devices()[:N_SHARDS]).reshape(N_SHARDS, 1)
    return Mesh(dev, ("expert", "unused"))


@pytest.fixture(scope="module")
def params():
    return init_moe_params(jax.random.PRNGKey(0), D, H, E)


def _x(seed=1):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal((T, D)), jnp.float32)


def dense_per_shard(params, x, *, k, capacity):
    """Reference: run each shard's token block through the dense MoE with the
    shard's capacity budget — exactly the EP semantics, no collectives."""
    blocks, auxes = [], []
    for x_blk in jnp.split(x, N_SHARDS):
        y, aux = dense_moe(params, x_blk, k=k, capacity=capacity)
        blocks.append(y)
        auxes.append(aux)
    return jnp.concatenate(blocks), jnp.mean(jnp.asarray(auxes))


@pytest.mark.slow
@pytest.mark.parametrize("k", [1, 2])
def test_moe_matches_dense(mesh, params, k):
    x = _x()
    cap = T // N_SHARDS  # default capacity in moe_forward
    got, aux = moe_forward(params, x, mesh, expert_axis="expert", k=k)
    want, aux_want = dense_per_shard(params, x, k=k, capacity=cap)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(float(aux), float(aux_want), rtol=2e-5)


@pytest.mark.slow
def test_moe_grads_match_dense(mesh, params):
    x = _x(seed=2)
    cap = T // N_SHARDS

    def loss_ep(p, x_):
        y, aux = moe_forward(p, x_, mesh, expert_axis="expert", k=2)
        return jnp.sum(y * y) + 0.01 * aux

    def loss_dense(p, x_):
        y, aux = dense_per_shard(p, x_, k=2, capacity=cap)
        return jnp.sum(y * y) + 0.01 * aux

    ge, gxe = jax.grad(loss_ep, argnums=(0, 1))(params, x)
    gd, gxd = jax.grad(loss_dense, argnums=(0, 1))(params, x)
    for a, b in zip(jax.tree_util.tree_leaves(ge), jax.tree_util.tree_leaves(gd)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-5, atol=5e-5)
    np.testing.assert_allclose(np.asarray(gxe), np.asarray(gxd), rtol=5e-5, atol=5e-5)


def test_moe_capacity_drops_tokens(params):
    """With capacity 1, an expert chosen by several tokens serves only the
    first; dropped tokens contribute zero through that expert (combine=0)."""
    x = jnp.tile(_x(seed=3)[:1], (4, 1))  # 4 identical tokens → same expert
    y_tight, _ = dense_moe(params, x, k=1, capacity=1)
    y_loose, _ = dense_moe(params, x, k=1, capacity=4)
    # first token is served either way
    np.testing.assert_allclose(
        np.asarray(y_tight[0]), np.asarray(y_loose[0]), rtol=1e-5, atol=1e-6
    )
    # overflow tokens got dropped → zero output, unlike the loose run
    assert np.allclose(np.asarray(y_tight[1:]), 0.0)
    assert not np.allclose(np.asarray(y_loose[1:]), 0.0)


def test_moe_aux_penalizes_imbalance(params):
    """Routing everything to one expert yields a higher aux loss than the
    measured (roughly balanced) routing — the property the loss exists for."""
    x = _x(seed=4)
    _, aux_real = dense_moe(params, x, k=1)
    hot = {**params, "gate": jnp.zeros_like(params["gate"]).at[:, 0].set(10.0)}
    _, aux_hot = dense_moe(hot, x, k=1)
    assert float(aux_hot) > float(aux_real)


def test_moe_grouped_matches_ungrouped_when_no_drops(params):
    """Grouped routing with per-group no-drop capacity equals global routing
    with no drops: grouping only changes capacity competition scope, and
    with no overflow each token meets its top-k experts either way."""
    x = _x(seed=5)
    y_g, _ = dense_moe(params, x, k=2, capacity=16, group_size=16)
    y_u, _ = dense_moe(params, x, k=2, capacity=T)
    np.testing.assert_allclose(np.asarray(y_g), np.asarray(y_u), rtol=2e-5, atol=2e-5)


def test_moe_grouped_ep_matches_grouped_dense(mesh, params):
    """The grouped EP dataflow (fold groups into slots → all_to_all → unfold)
    equals the per-shard dense evaluation with the same groups."""
    x = _x(seed=6)
    got, _ = moe_forward(
        params, x, mesh, expert_axis="expert", k=2, capacity=4, group_size=4
    )
    blocks = [
        dense_moe(params, x_blk, k=2, capacity=4, group_size=4)[0]
        for x_blk in jnp.split(x, N_SHARDS)
    ]
    want = jnp.concatenate(blocks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_pick_group_size_always_divides():
    from mpi_pytorch_tpu.ops.moe import pick_group_size

    assert pick_group_size(64, None) == 64
    assert pick_group_size(64, 64) == 64
    assert pick_group_size(200, 64) == 50  # largest divisor <= 64
    assert pick_group_size(1936, 64) == 44
    assert pick_group_size(7, 4) == 1  # prime: one token per group
    for t, g in [(200, 64), (1936, 64), (7, 4), (30, 8)]:
        assert t % pick_group_size(t, g) == 0


def test_moe_rejects_indivisible(mesh, params):
    with pytest.raises(ValueError, match="divide"):
        moe_forward(params, _x()[:63], mesh, expert_axis="expert")


# -- the held experts' pass: back from buffer rows to tokens, in both forms ----


def _plain_held(x, sel, weight, kind, weights, offset):
    """Every held expert on every token, then each token's weighted choices
    among them: the reference ``held_experts`` is compared with."""
    if kind == "swiglu":
        w1, w3, w2 = weights
        out = jnp.einsum("htf,hfd->htd", jax.nn.silu(jnp.einsum("td,hdf->htf", x, w1))
                         * jnp.einsum("td,hdf->htf", x, w3), w2)
    else:
        w1, w2 = weights
        out = jnp.einsum("htf,hfd->htd", jnp.square(jax.nn.relu(jnp.einsum("td,hdf->htf", x, w1))), w2)
    chosen = (sel - offset)[..., None] == jnp.arange(out.shape[0])  # [T, k, H]
    return jnp.einsum("th,htd->td", jnp.sum(weight[..., None] * chosen, axis=1), out)


def _selection_with_held_pairs(n, tokens, top_k, held, routed, offset, rng):
    """``sel [T, k]`` of distinct experts a token under which exactly ``n``
    pairs land on experts ``offset .. offset + held``: tokens in order take
    ``min(k, held)`` held choices each (several a token) until ``n`` are
    given, at choice positions drawn at random."""
    absent = np.setdiff1d(np.arange(routed), np.arange(offset, offset + held))
    sel = np.empty((tokens, top_k), np.int64)
    for t in range(tokens):
        mine = min(top_k, held, max(0, n - t * min(top_k, held)))
        ids = np.concatenate([offset + rng.permutation(held)[:mine], rng.permutation(absent)[: top_k - mine]])
        sel[t] = ids[rng.permutation(top_k)]
    assert int(np.sum((sel >= offset) & (sel < offset + held))) == n
    return jnp.asarray(sel, jnp.int32)


@pytest.mark.parametrize("form", ["by_row", "per_pair"])
@pytest.mark.parametrize("kind", ["swiglu", "relu2"])
@pytest.mark.parametrize("top_k", [2, 4, 6])  # under, at and over the 4 experts held
@pytest.mark.parametrize("fill", ["below", "full", "one_over", "three_passes", "every_pair", "none"])
def test_held_experts_returns_to_tokens_the_same_in_both_forms(fill, top_k, kind, form, monkeypatch):
    """4 of 32 experts held (ids 8..12), buffers of C = 128 rows for ~512
    pairs: whatever share of the pairs lands here — under C, C, C + 1, three
    passes' worth, every choice a token can hold here, none — and whichever
    way a pass goes back to tokens, the output and the gradients of the rows,
    of each expert weight and of the routing weights are the plain
    reference's, the pairs are conserved and nothing is dropped."""
    from mpi_pytorch_tpu.ops import moe

    monkeypatch.setattr(moe, "BY_ROW_FROM", 1 if form == "by_row" else float("inf"))
    held, routed, offset, d, f = 4, 32, 8, 24, 40
    tokens = 512 // top_k
    pairs = tokens * top_k
    bound = moe.row_bound(pairs, held, routed)
    assert bound == 128 < pairs and moe._returns_by_row(pairs, bound) == (form == "by_row")
    most = tokens * min(top_k, held)
    n = {"below": bound - 7, "full": bound, "one_over": bound + 1, "three_passes": 2 * bound + 5,
         "every_pair": most, "none": 0}[fill]
    rng = np.random.default_rng(n + top_k)
    sel = _selection_with_held_pairs(n, tokens, top_k, held, routed, offset, rng)
    normal = lambda *shape, std=0.3: jnp.asarray(rng.normal(size=shape) * std, jnp.float32)
    x, weight, mix = normal(tokens, d, std=1.0), jnp.abs(normal(tokens, top_k)) + 0.1, normal(tokens, d, std=1.0)
    shapes = [(held, d, f), (held, d, f), (held, f, d)] if kind == "swiglu" else [(held, d, f), (held, f, d)]
    weights = tuple(normal(*shape) for shape in shapes)
    expert = moe.swiglu_expert if kind == "swiglu" else moe.relu2_expert

    def mine(x, weight, weights):
        y, counters = moe.held_experts(x, sel, weight, expert, weights, routed=routed, expert_offset=offset)
        return jnp.sum(y * mix), (y, counters)

    def plain(x, weight, weights):
        y = _plain_held(x, sel, weight, kind, weights, offset)
        return jnp.sum(y * mix), y

    (_, (y, c)), got = jax.value_and_grad(mine, argnums=(0, 1, 2), has_aux=True)(x, weight, weights)
    (_, want_y), want = jax.value_and_grad(plain, argnums=(0, 1, 2), has_aux=True)(x, weight, weights)
    assert (int(c["moe_pairs_held"]), int(c["moe_pairs_absent"])) == (n, pairs - n)
    assert int(c["moe_rows_computed"]) == bound * max(1, -(-n // bound))
    compared = {"y": (y, want_y), "x": (got[0], want[0]), "weight": (got[1], want[1])}
    compared.update({f"w{i}": pair for i, pair in enumerate(zip(got[2], want[2]))})
    for name, (g, w) in compared.items():
        assert g.shape == w.shape and g.dtype == w.dtype, name
        assert float(jnp.max(jnp.abs(g - w))) < 2e-5 * (float(jnp.max(jnp.abs(w))) + 1.0), name


@pytest.mark.parametrize(
    "tokens,top_k,held,routed,slack,rows,form",
    [
        (16_384, 4, 8, 64, 2, 16_384, "per_pair"),  # lfm2_train_hbm_8k's expert layer: 4 pairs a row
        (16_384, 22, 8, 512, 4, 22_528, "by_row"),  # nemotron3s_train_hbm_8k's: 16 pairs a row
        (16_384, 22, 8, 512, 2, 11_264, "by_row"),  # the same at the default slack: 32
        (16_384, 4, 64, 64, 2, 65_536, "per_pair"),  # a rank that holds every expert: one pair a row
    ],
)
def test_the_return_to_tokens_is_chosen_by_the_pairs_a_buffer_row(tokens, top_k, held, routed, slack, rows, form):
    from mpi_pytorch_tpu.ops.moe import _returns_by_row, row_bound

    bound = row_bound(tokens * top_k, held, routed, slack)
    assert bound == rows
    assert _returns_by_row(tokens * top_k, bound) == (form == "by_row")
