"""LFM2-MoE on the training path (models/lfm2.py, ops/moe.dropless_moe,
grouped-head flash attention, data/tokens.py) against its plain reference
(benchmark/reference/lfm2_moe.py), at small sizes on the CPU with seeded
float32 weights."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import lfm2_moe as ref
from mpi_pytorch_tpu.models.lfm2 import Block, Lfm2Config, ShortConv, lfm2_moe
from mpi_pytorch_tpu.ops.moe import dropless_moe, row_bound, sigmoid_topk_route

TINY = {
    "hidden_size": 64, "intermediate_size": 160, "moe_intermediate_size": 48,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "layer_types": ["conv", "full_attention", "conv"], "num_hidden_layers": 3,
    "num_dense_layers": 1, "num_experts": 4, "num_experts_routed": 16, "expert_offset": 4,
    "num_experts_per_tok": 4, "vocab_size": 128,
}


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


def _tokens(seed, batch=2, seq=64, vocab=128):
    rows = jax.random.randint(jax.random.PRNGKey(seed), (batch, seq + 1), 0, vocab)
    return rows[:, :-1], rows[:, 1:]


@pytest.mark.parametrize("attn_impl", ["full", "flash"])
def test_model_matches_the_reference_logits_loss_and_every_gradient_leaf(attn_impl, monkeypatch):
    monkeypatch.setenv("MPT_FLASH_INTERPRET", "1")  # the real kernel, interpreted
    model = lfm2_moe(0, model_config=json.dumps(TINY), attn_impl=attn_impl)
    x, y = _tokens(1)
    variables = {"params": model.init(jax.random.PRNGKey(0), x)["params"]}
    assert _rel(model.apply(variables, x), ref.forward(variables, x, expert_offset=4)) < 1e-5

    def loss(params):
        return ref.cross_entropy(model.apply({"params": params}, x), y)

    got_loss, got = jax.value_and_grad(loss)(variables["params"])
    want_loss, want = ref.loss_and_grads(variables, x, y, expert_offset=4)
    assert abs(float(got_loss) - float(want_loss)) < 1e-5
    got_leaves = jax.tree_util.tree_leaves_with_path(got)
    want_leaves = jax.tree_util.tree_leaves_with_path(want)
    assert [p for p, _ in got_leaves] == [p for p, _ in want_leaves]
    for (path, g), (_, w) in zip(got_leaves, want_leaves):
        name = jax.tree_util.keystr(path)
        if name.endswith("['expert_bias']"):
            assert not np.any(np.asarray(g)) and not np.any(np.asarray(w)), name  # a buffer
        else:
            assert _rel(g, w) < 1e-4, name


def test_every_gradient_leaf_through_many_blocks_of_the_flash_kernels(monkeypatch):
    """The model's 64 tokens in 16-wide forward and 32 x 16 backward blocks:
    forward, dk/dv and dq kernels each walk several blocks a side, skip those
    above the diagonal and sum dk and dv over the 2 query heads of a group."""
    from mpi_pytorch_tpu.ops import flash_attention

    monkeypatch.setenv("MPT_FLASH_INTERPRET", "1")
    monkeypatch.setattr(flash_attention, "FWD_TILE", (32, 16))  # 16 rows a head of the group
    monkeypatch.setattr(flash_attention, "BWD_BLOCKS", (32, 16))
    model = lfm2_moe(0, model_config=json.dumps(TINY), attn_impl="flash")
    x, y = _tokens(2)
    variables = {"params": model.init(jax.random.PRNGKey(0), x)["params"]}
    got = jax.grad(lambda p: ref.cross_entropy(model.apply({"params": p}, x), y))(variables["params"])
    _, want = ref.loss_and_grads(variables, x, y, expert_offset=4)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree_util.tree_leaves(want)):
        if not jax.tree_util.keystr(path).endswith("['expert_bias']"):
            assert _rel(g, w) < 1e-4, jax.tree_util.keystr(path)


def test_remat_blocks_is_the_same_function():
    x, y = _tokens(2)
    plain = lfm2_moe(0, model_config=json.dumps(TINY))
    remat = lfm2_moe(0, model_config=json.dumps(TINY), remat_blocks=True)
    params = plain.init(jax.random.PRNGKey(0), x)["params"]
    grads = [
        jax.grad(lambda p, m=m: ref.cross_entropy(m.apply({"params": p}, x), y))(params)
        for m in (plain, remat)
    ]
    for a, b in zip(*map(jax.tree_util.tree_leaves, grads)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7)


def _moe_params(seed, d=16, f=8, experts=64):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    normal = lambda k, shape, std: jax.random.normal(k, shape, jnp.float32) * std
    return {
        "gate": normal(ks[0], (d, experts), 0.5), "expert_bias": normal(ks[1], (experts,), 0.05),
        "w1": normal(ks[2], (experts, d, f), 0.3), "w3": normal(ks[3], (experts, d, f), 0.3),
        "w2": normal(ks[4], (experts, f, d), 0.3),
    }


def _share(p, lo, hi):
    return dict(p, **{k: p[k][lo:hi] for k in ("w1", "w3", "w2")})


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """A whole layer (conv operator, norms, residuals, 64 experts, top-4):
    each of eight ranks holds 8 experts and computes the operator and the
    residual alike; those counted once, the shares' expert parts sum to the
    uncut reference's layer."""
    d, tokens = 16, 24
    cfg = lambda held, offset: Lfm2Config(
        hidden_size=d, moe_intermediate_size=8, layer_types=("conv",), num_dense_layers=0,
        num_experts=held, num_experts_routed=64, expert_offset=offset,
    )
    x = jax.random.normal(jax.random.PRNGKey(3), (1, tokens, d), jnp.float32)
    whole = Block(cfg(64, 0), 0)
    params = whole.init(jax.random.PRNGKey(4), x)["params"]
    params["moe"] = _moe_params(5, d=d)
    want = ref._layer(x, params, {}, None)  # the reference holds all 64
    assert _rel(whole.apply({"params": params}, x), want) < 1e-5

    after_op = x + ref.short_conv(
        ref._rms(x, params["operator_norm"]["scale"], 1e-5), params["conv"]
    )
    total = after_op
    for rank in range(8):
        mine = dict(params, moe=_share(params["moe"], 8 * rank, 8 * rank + 8))
        out = Block(cfg(8, 8 * rank), 0).apply({"params": mine}, x)
        assert _rel(out, ref._layer(x, mine, {"expert_offset": 8 * rank}, None)) < 1e-5
        total = total + (out - after_op)
    assert _rel(total, want) < 1e-5


@pytest.mark.parametrize("top_k,favoured", [(1, [2]), (4, [0, 1, 2, 3])])
def test_dropless_under_adversarial_routing(top_k, favoured):
    """Every token to the same held expert(s): nothing dropped, the pairs
    conserved, the output the reference's."""
    d, tokens, held = 16, 40, 4
    p = _share(_moe_params(6, d=d, experts=16), 0, held)
    p["expert_bias"] = jnp.zeros(16).at[jnp.asarray(favoured)].set(100.0)
    x = jax.random.normal(jax.random.PRNGKey(7), (tokens, d), jnp.float32)
    y, counters, _ = dropless_moe(
        x, p["gate"], p["expert_bias"], p["w1"], p["w3"], p["w2"], top_k=top_k
    )
    assert int(counters["moe_pairs_held"]) == tokens * top_k
    assert int(counters["moe_pairs_absent"]) == 0
    assert int(counters["moe_load_max"]) == tokens  # one expert took every token
    assert _rel(y, ref.moe(x, p, top_k=top_k)) < 1e-5


def _routing_with_held_pairs(n, tokens, top_k, held, routed, d=24):
    """Tokens and a router under which exactly ``n`` of the ``tokens * top_k``
    pairs land on the first ``held`` experts: the first ``n // top_k`` tokens
    send every choice there, one more the remainder, the others none. The
    first ``top_k + 1`` features say which kind a token is and the router's
    rows for them set its choices 3 against -3; the other features and router
    rows are noise around that."""
    rng = np.random.default_rng(n)
    to_held = np.zeros(tokens, int)
    to_held[: n // top_k] = top_k
    if n % top_k:
        to_held[n // top_k] = n % top_k
    x = rng.normal(size=(tokens, d)).astype(np.float32) * 0.5
    x[:, : top_k + 1] = 0
    x[np.arange(tokens), to_held] = 1.0
    gate = rng.normal(size=(d, routed)).astype(np.float32) * 0.1
    for kind in range(top_k + 1):
        gate[kind] = -3.0 + rng.normal(size=routed) * 0.1
        gate[kind, :kind] += 6.0
        gate[kind, held : held + top_k - kind] += 6.0
    return jnp.asarray(x), jnp.asarray(gate)


@pytest.mark.parametrize("top_k,tokens", [(1, 512), (4, 128)])
@pytest.mark.parametrize("fill", ["below", "full", "one_over", "every_pair", "none"])
def test_row_buffers_follow_the_pairs_held(fill, top_k, tokens):
    """4 of 32 experts held, so the row buffers have C = 128 of the 512
    pairs' rows: whatever share of the pairs lands here — under C, C, C + 1,
    all, none — the output and every gradient are the reference's, the pairs
    are conserved, and the passes ran over as many rows as that share demands
    (one pass of C at the least)."""
    held, routed, pairs = 4, 32, tokens * top_k
    bound = row_bound(pairs, held, routed)
    assert bound == 128 < pairs
    n = {"below": bound - 7, "full": bound, "one_over": bound + 1, "every_pair": pairs, "none": 0}[fill]
    x, gate = _routing_with_held_pairs(n, tokens, top_k, held, routed)
    p = dict(_share(_moe_params(10, d=x.shape[1], experts=routed), 0, held), gate=gate)
    mix = jax.random.normal(jax.random.PRNGKey(11), x.shape, jnp.float32)

    def mine(x, p):
        y, counters, _ = dropless_moe(
            x, p["gate"], p["expert_bias"], p["w1"], p["w3"], p["w2"], top_k=top_k
        )
        return jnp.sum(y * mix), (y, counters)

    (_, (y, c)), got = jax.value_and_grad(mine, argnums=(0, 1), has_aux=True)(x, p)
    want_y = ref.moe(x, p, top_k=top_k)
    want = jax.grad(lambda x, p: jnp.sum(ref.moe(x, p, top_k=top_k) * mix), argnums=(0, 1))(x, p)
    assert (int(c["moe_pairs_held"]), int(c["moe_pairs_absent"])) == (n, pairs - n)
    assert int(c["moe_rows_computed"]) == bound * max(1, -(-n // bound))
    pairs_of = {"y": (y, want_y), "x": (got[0], want[0])}
    pairs_of.update({k: (got[1][k], want[1][k]) for k in ("gate", "w1", "w3", "w2")})
    for name, (g, w) in pairs_of.items():
        assert float(jnp.max(jnp.abs(g - w))) < 2e-5 * (float(jnp.max(jnp.abs(w))) + 1.0), name


def test_pairs_routed_to_absent_experts_are_counted_and_left_out():
    d, tokens, top_k = 16, 32, 4
    p = _share(_moe_params(8, d=d, experts=16), 4, 8)
    x = jax.random.normal(jax.random.PRNGKey(9), (tokens, d), jnp.float32)
    y, c, mine = dropless_moe(x, p["gate"], p["expert_bias"], p["w1"], p["w3"], p["w2"],
                              top_k=top_k, expert_offset=4)
    sel, _ = ref.route(x, p, top_k)
    np.testing.assert_array_equal(np.sort(np.asarray(mine), -1), np.sort(np.asarray(sel), -1))
    here = int(jnp.sum((sel >= 4) & (sel < 8)))
    assert 0 < here < tokens * top_k
    assert (int(c["moe_pairs_held"]), int(c["moe_pairs_absent"])) == (here, tokens * top_k - here)
    assert _rel(y, ref.moe(x, p, top_k=top_k, expert_offset=4)) < 1e-5


def test_selection_uses_score_plus_bias_and_weights_use_the_score():
    gate = jnp.eye(4, 6, dtype=jnp.float32)  # token i's logit for expert j is x[i, j]
    x = jnp.array([[2.0, 1.0, 0.0, -1.0]])  # scores fall from expert 0 to 3; 4 and 5 score 0.5
    bias = jnp.array([-10.0, 0.0, 0.0, 0.0, 0.0, 10.0])
    sel, w = sigmoid_topk_route(x, gate, bias, top_k=2)
    s = np.asarray(jax.nn.sigmoid(x @ gate))[0]
    assert sorted(np.asarray(sel)[0].tolist()) == [1, 5]  # by s + b: not expert 0, the best score
    order = np.asarray(sel)[0]
    np.testing.assert_allclose(np.asarray(w)[0], s[order] / (s[order].sum() + 1e-6), rtol=1e-6)
    # The bias takes no gradient; the scores do.
    g = jax.grad(lambda b: jnp.sum(sigmoid_topk_route(x, gate, b, 2)[1] ** 2))(bias)
    assert not np.any(np.asarray(g))


def test_short_conv_is_causal_and_matches_the_reference():
    cfg = Lfm2Config(hidden_size=16)
    x = jax.random.normal(jax.random.PRNGKey(10), (2, 12, 16), jnp.float32)
    module = ShortConv(cfg)
    params = module.init(jax.random.PRNGKey(11), x)["params"]
    out = module.apply({"params": params}, x)
    assert _rel(out, ref.short_conv(x, params)) < 1e-6
    later = module.apply({"params": params}, x.at[:, 7:].add(1.0))
    np.testing.assert_array_equal(np.asarray(out[:, :7]), np.asarray(later[:, :7]))
    assert np.all(np.abs(np.asarray(out[:, 7:] - later[:, 7:])).max(axis=(0, 2)) > 0)


@pytest.mark.parametrize("block_q,block_k", [(16, 16), (32, 16), (16, 32)])
def test_flash_attention_with_grouped_heads_matches_full_attention(block_q, block_k):
    """8 key-value heads for 32 query heads, causal, forward and VJP, the
    kernel interpreted; the reference repeats k and v."""
    from mpi_pytorch_tpu.ops.flash_attention import flash_attention
    from mpi_pytorch_tpu.ops.ring_attention import full_attention

    b, s, h, hkv, d = 2, 64, 32, 8, 8
    ks = jax.random.split(jax.random.PRNGKey(12), 4)
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32)
    k, v = (jax.random.normal(key, (b, s, hkv, d), jnp.float32) for key in ks[1:3])
    co = jax.random.normal(ks[3], (b, s, h, d), jnp.float32)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=block_q, block_k=block_k,
                               interpret=True)

    def full(q, k, v):
        return full_attention(q, jnp.repeat(k, h // hkv, 2), jnp.repeat(v, h // hkv, 2), causal=True)

    got, got_vjp = jax.vjp(flash, q, k, v)
    want, want_vjp = jax.vjp(full, q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)
    for name, a, w in zip("qkv", got_vjp(co), want_vjp(co)):
        assert a.shape == w.shape, name
        np.testing.assert_allclose(np.asarray(a), np.asarray(w), rtol=1e-4, atol=1e-4, err_msg=name)


def test_flash_attention_refuses_heads_that_do_not_group():
    from mpi_pytorch_tpu.ops.flash_attention import flash_attention

    q = jnp.zeros((1, 16, 6, 8))
    kv = jnp.zeros((1, 16, 4, 8))
    with pytest.raises(ValueError, match="must divide"):
        flash_attention(q, kv, kv, interpret=True)


def test_the_token_recipe_is_a_pure_function_of_the_seed():
    from benchmark.recipes import tokens as recipe

    with open("benchmark/traffic/train_tokens_8k.json") as f:
        spec = dict(json.load(f)["dataset"], sequences=4, seq_len=512)
    a, b = recipe.sequences(spec, 8192, 2147483659), recipe.sequences(spec, 8192, 2147483659)
    other = recipe.sequences(spec, 8192, 7)
    assert a.shape == (4, 513) and a.dtype == np.int32
    np.testing.assert_array_equal(a, b)
    assert np.mean(a == other) < 0.2
    assert a.min() >= 0 and a.max() < 8192  # inside the vocabulary slice
    # No padding: documents run end to end, so every end-of-document id is
    # followed by a token (of the next document) within the flat stream, and
    # no run of end-of-document ids fills a row's tail.
    flat = a.reshape(-1)
    ends = np.flatnonzero(flat == spec["eod_id"])
    assert 0 < len(ends) < len(flat) // spec["doc_len_min"] + 1
    assert np.all(np.diff(ends) >= spec["doc_len_min"])
    # Zipf: low ids are the frequent ones.
    counts = np.bincount(flat, minlength=8192)
    assert counts[1] > counts[10] > counts[1000]


def _train_flags(tmp_path, **more):
    flags = {
        "model-name": "lfm2_moe", "model-config": json.dumps(TINY), "device-cache": "true",
        "scan-epoch": "true", "validate": "false", "debug-sample-size": "16", "image-size": "64",
        "batch-size": "8", "num-epochs": "2", "checkpoint-every-epochs": "0",
        "learning-rate": "0.003", "compute-dtype": "float32",
        "metrics-file": str(tmp_path / "metrics.jsonl"), "log-file": str(tmp_path / "train.log"),
        "checkpoint-dir": str(tmp_path / "ckpt"), "trace-file": str(tmp_path / "spans.json"),
        **more,
    }
    return [part for k, v in flags.items() for part in (f"--{k}", v)]


def test_trainer_main_trains_tokens_from_the_device_cache_in_scanned_epochs(tmp_path):
    from mpi_pytorch_tpu.obs.schema import validate_jsonl
    from mpi_pytorch_tpu.train import trainer

    summary = trainer.main(_train_flags(tmp_path))
    assert summary.epochs_run == 2
    assert summary.epoch_losses[1] < summary.epoch_losses[0]
    assert not validate_jsonl(str(tmp_path / "metrics.jsonl"))
    with open(tmp_path / "metrics.jsonl") as f:
        epochs = [r for r in map(json.loads, f) if r["kind"] == "epoch"]
    assert len(epochs) == 2
    moe_layers = 2
    for rec in epochs:
        assert rec["tokens"] == 16 * 64  # 2 scanned steps of 8 sequences of 64 (8 CPU devices)
        assert round(rec["images_per_sec"] * rec["time_s"]) == 16  # samples are sequences
        assert rec["moe_pairs_held"] + rec["moe_pairs_absent"] == rec["tokens"] * 4 * moe_layers
        assert 0 < rec["moe_load_max"] <= 8 * 64
        # 4 of 16 held: buffers of half the pairs' rows, one pass or two a layer a step
        passes, rest = divmod(rec["moe_rows_computed"], 2 * 8 * 64)
        assert rest == 0 and 2 * moe_layers <= passes <= 4 * moe_layers
        assert rec["moe_rows_computed"] >= rec["moe_pairs_held"]
    with open(tmp_path / "spans.json") as f:
        instants = [e for e in json.load(f)["traceEvents"] if e["name"] == "moe/dispatch"]
    # One a distinct shape: init's dummy sequence, then the step's batch.
    assert [e["args"] for e in instants] == [
        {"experts": 16, "held": 4, "top_k": 4, "tokens": tokens, "latent": 64,
         "path": "ragged_dot", "rows_bound": 2 * tokens, "combine": "per_pair", "pairs_per_row": 2.0}
        for tokens in (64, 8 * 64)
    ]


def test_trainer_reads_a_token_pack_and_refuses_ids_beyond_the_vocabulary(tmp_path):
    from mpi_pytorch_tpu.data.tokens import synthetic_tokens, write_token_pack
    from mpi_pytorch_tpu.train import trainer

    write_token_pack(str(tmp_path / "pack"), synthetic_tokens(8, 32, 128, seed=1))
    summary = trainer.main(_train_flags(
        tmp_path, **{"packed-dir": str(tmp_path / "pack"), "synthetic-data": "false",
                     "num-epochs": "1", "trace-file": ""}))
    assert summary.epochs_run == 1
    write_token_pack(str(tmp_path / "wide"), synthetic_tokens(8, 32, 500, seed=1))
    with pytest.raises(ValueError, match="vocabulary is 128"):
        trainer.main(_train_flags(tmp_path, **{"packed-dir": str(tmp_path / "wide")}))
    with pytest.raises(FileNotFoundError, match="train.tokens.npy"):
        trainer.main(_train_flags(tmp_path, **{"packed-dir": str(tmp_path / "none")}))


@pytest.mark.parametrize(
    "flags,message",
    [
        ({"model-name": "resnet18", "model-config": "{}"}, "model_config=.* does not apply to model 'resnet18'"),
        ({"device-cache": "false", "scan-epoch": "false"}, "device_cache=True"),
        ({"validate": "true"}, "validation is an image path"),
        ({"attn-impl": "fused-small"}, "attn_impl='fused-small' does not apply to model 'lfm2_moe'"),
    ],
)
def test_config_refuses_what_a_token_model_cannot_do(tmp_path, flags, message):
    from mpi_pytorch_tpu.config import parse_config

    with pytest.raises(ValueError, match=message):
        parse_config(_train_flags(tmp_path, **flags))


def test_model_config_defaults_are_the_published_model_and_errors_name_the_key(tmp_path):
    cfg = Lfm2Config.parse("")
    assert len(cfg.layer_types) == 40 and cfg.layer_types.count("full_attention") == 10
    assert [i for i, t in enumerate(cfg.layer_types) if t == "full_attention"][:3] == [2, 6, 10]
    assert (cfg.routed, cfg.num_experts, cfg.num_experts_per_tok) == (64, 64, 4)
    path = tmp_path / "model.json"
    path.write_text(json.dumps(TINY))
    assert Lfm2Config.parse(str(path)) == Lfm2Config.parse(json.dumps(TINY))
    for bad, key in [
        ({"layer_types": ["conv", "mamba"], "num_hidden_layers": 2}, "mamba"),
        ({"num_hidden_layers": 7}, "num_hidden_layers"),
        ({"conv_bias": True}, "conv_bias"),
        ({"num_experts": 8, "num_experts_routed": 64, "expert_offset": 60}, "routed"),
    ]:
        with pytest.raises(ValueError, match=key):
            Lfm2Config.parse(json.dumps(bad))


def test_the_cached_batch_of_a_token_model_is_inputs_and_shifted_targets():
    from jax.sharding import Mesh

    from mpi_pytorch_tpu.ops.losses import valid_count
    from mpi_pytorch_tpu.train.step import _gather_batch

    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    dataset = jnp.arange(5 * 9, dtype=jnp.int32).reshape(5, 9)
    idx, valid = jnp.array([3, 1, 1]), jnp.array([True, True, False])
    inputs, targets = _gather_batch(mesh, jnp.bfloat16, dataset, jnp.zeros(5, jnp.int32), idx, valid)
    np.testing.assert_array_equal(np.asarray(inputs), np.asarray(dataset)[[3, 1, 1], :-1])
    np.testing.assert_array_equal(np.asarray(targets[:2]), np.asarray(dataset)[[3, 1], 1:])
    assert inputs.dtype == jnp.int32 and np.all(np.asarray(targets[2]) == -1)
    assert int(valid_count(targets)) == 2  # samples, not positions
