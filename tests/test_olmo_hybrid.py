"""Olmo Hybrid on the training path (models/olmo_hybrid.py, ops/gated_delta.py,
flash attention at 30 heads with group size 1 in the cell, the untied head)
against its plain reference (benchmark/reference/olmo_hybrid.py: the
PER-POSITION delta rule), at small sizes on the CPU with seeded float32
weights."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import olmo_hybrid as ref
from mpi_pytorch_tpu.models.olmo_hybrid import (
    LinearAttention, OlmoHybrid, OlmoHybridConfig, olmo_hybrid,
)
from mpi_pytorch_tpu.ops.gated_delta import gated_delta, unit_lower_inverse

TINY = {
    "hidden_size": 64, "intermediate_size": 96, "num_attention_heads": 4, "num_key_value_heads": 4,
    "layer_types": ["linear_attention", "full_attention", "linear_attention"], "num_hidden_layers": 3,
    "linear_num_key_heads": 6, "linear_num_value_heads": 6, "linear_key_head_dim": 16,
    "linear_value_head_dim": 32, "linear_chunk_size": 16, "vocab_size": 128,
}


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


def _tokens(seed, batch=2, seq=64, vocab=128):
    rows = jax.random.randint(jax.random.PRNGKey(seed), (batch, seq + 1), 0, vocab)
    return rows[:, :-1], rows[:, 1:]


def _model(**kw):
    return olmo_hybrid(0, model_config=json.dumps(dict(TINY, **kw.pop("config", {}))), **kw)


def _init(model, x, seed=0):
    return {"params": model.init(jax.random.PRNGKey(seed), x)["params"]}


def _loss(model, x, y):
    return lambda params: ref.cross_entropy(model.apply({"params": params}, x), y)


@pytest.mark.parametrize("attn_impl", ["full", "flash"])
def test_model_matches_the_reference_logits_loss_and_every_gradient_leaf(attn_impl, monkeypatch):
    monkeypatch.setenv("MPT_FLASH_INTERPRET", "1")  # the real kernels, interpreted
    model = _model(attn_impl=attn_impl)
    x, y = _tokens(1)
    variables = _init(model, x)
    assert _rel(model.apply(variables, x), ref.forward(variables, x)) < 1e-5
    got_loss, got = jax.value_and_grad(_loss(model, x, y))(variables["params"])
    want_loss, want = ref.loss_and_grads(variables, x, y)
    assert abs(float(got_loss) - float(want_loss)) < 1e-5
    got_leaves = jax.tree_util.tree_leaves_with_path(got)
    want_leaves = jax.tree_util.tree_leaves_with_path(want)
    assert [p for p, _ in got_leaves] == [p for p, _ in want_leaves]
    # embedding, head, final norm; two linear blocks of 13 + 5; one attention block of 6 + 5
    assert len(got_leaves) == 3 + 2 * (13 + 5) + (6 + 5)
    for (path, g), (_, w) in zip(got_leaves, want_leaves):
        assert _rel(g, w) < 1e-4, jax.tree_util.keystr(path)


def _rule_inputs(seed, batch, seq, heads, dk, dv, *, beta_max=2.0, decay="mid", aligned=0.0):
    """L2-normalised q and k (keys that point alike when ``aligned`` > 0),
    beta in (0, beta_max), log-decays near 0 (``slow``), around -0.5 or large."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    normal = lambda k, *shape: jax.random.normal(k, shape, jnp.float32)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)
    q = unit(normal(ks[0], batch, seq, heads, dk)) * dk**-0.5
    k = unit(normal(ks[1], batch, seq, heads, dk) + aligned)
    beta = beta_max * jax.nn.sigmoid(normal(ks[3], batch, seq, heads) + 1.0)
    raw = normal(ks[4], batch, seq, heads)
    g = {
        "slow": -1e-3 * jax.nn.softplus(raw),  # decay near 1
        "mid": -0.5 * jax.nn.softplus(raw),
        "fast": -8.0 * jax.nn.softplus(raw + 2.0),  # decay near 0
    }[decay]
    return q, k, normal(ks[2], batch, seq, heads, dv), beta, g


_KINDS = {
    "beta2_decay_near_1": (2.0, "slow", 0.0), "beta2_decay_near_0": (2.0, "fast", 0.0),
    "beta2_aligned_keys": (2.0, "slow", 1.5), "beta1": (1.0, "mid", 0.0),
}
_SHAPES = {
    "below_a_chunk": (8, 16), "one_chunk": (16, 16), "three_chunks": (48, 16), "chunk_8": (64, 8),
    "chunk_32": (64, 32), "chunk_4": (32, 4),
}
# Every input kind at three chunks of 16, every chunking with one kind in turn.
_CASES = [("three_chunks", kind) for kind in _KINDS] + [
    (shape, list(_KINDS)[i % len(_KINDS)]) for i, shape in enumerate(_SHAPES) if shape != "three_chunks"
]


@pytest.mark.parametrize("shape,kind", _CASES, ids=[f"{s}-{k}" for s, k in _CASES])
def test_the_chunked_rule_is_the_per_position_recurrence(shape, kind):
    """Values and all five cotangents (q, k, v, beta, g) of
    ``ops/gated_delta.gated_delta`` against one ``lax.scan`` step a position."""
    (seq, chunk), (beta_max, decay, aligned) = _SHAPES[shape], _KINDS[kind]
    args = _rule_inputs(seq + chunk, 2, seq, 3, 8, 16, beta_max=beta_max, decay=decay, aligned=aligned)
    weight = jax.random.normal(jax.random.PRNGKey(9), (2, seq, 3, 16), jnp.float32)
    chunked = lambda *a: gated_delta(*a, chunk=chunk)
    with jax.default_matmul_precision("highest"):
        assert _rel(chunked(*args), ref.delta_rule(*args)) < 1e-5
        grads = [
            jax.grad(lambda *a, f=f: jnp.sum(f(*a) * weight), argnums=tuple(range(5)))(*args)
            for f in (chunked, ref.delta_rule)
        ]
    for name, g, w in zip(("q", "k", "v", "beta", "g"), *grads):
        assert g.shape == w.shape and _rel(g, w) < 1e-4, name


def test_the_chunk_inverse_stays_exact_where_the_nilpotent_product_does_not():
    """``(I + A)^-1`` by doubling diagonal blocks, against the identity, for
    beta 1.9 and keys of mean cosine ~0.9: the product ``(I - A)(I + A^2)...``
    of the same matrix is off by more than 1e10 there."""
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)
    k = unit(jax.random.normal(jax.random.PRNGKey(1), (64, 96)) + 3.0)
    a = jnp.tril(1.9 * k @ k.T, -1)
    eye = jnp.eye(64)
    with jax.default_matmul_precision("highest"):
        inverse = unit_lower_inverse(a)
        product, power = eye - a, a
        for _ in range(5):
            power = power @ power
            product = product @ (eye + power)
        assert float(jnp.max(jnp.abs(inverse @ (eye + a) - eye))) < 1e-4
        assert float(jnp.max(jnp.abs(product @ (eye + a) - eye))) > 1e10


@pytest.mark.parametrize("seq,chunk", [(40, 40), (24, 64)], ids=["chunk_of_40", "sequence_of_24_below_a_chunk"])
def test_a_chunk_that_is_no_power_of_two_is_refused_with_the_numbers(seq, chunk):
    """The doubling inverse needs a power of two, and nothing pads."""
    args = _rule_inputs(0, 1, seq, 2, 8, 16)
    with pytest.raises(ValueError, match=rf"gated_delta: a chunk of {seq} positions \(a sequence of {seq}, "
                                         rf"linear_chunk_size {chunk}\) is no power of two"):
        gated_delta(*args, chunk=chunk)


def test_a_sequence_that_is_no_whole_number_of_chunks_is_refused_with_the_numbers():
    args = _rule_inputs(0, 1, 40, 2, 8, 16)
    with pytest.raises(ValueError, match=r"gated_delta: a sequence of 40 positions .* chunks of 16 \(linear_chunk_size\)"):
        gated_delta(*args, chunk=16)
    model = _model()
    x, _ = _tokens(0, seq=64)
    variables = _init(model, x)
    with pytest.raises(ValueError, match="72 positions"):
        model.apply(variables, _tokens(0, seq=72)[0])


def test_the_linear_attention_mixer_is_causal():
    """A change at position t moves nothing before t, across chunk borders."""
    mixer = LinearAttention(OlmoHybridConfig.parse(json.dumps(TINY)))
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 64, 64), jnp.float32)
    params = mixer.init(jax.random.PRNGKey(1), x)
    base = mixer.apply(params, x)
    for t in (0, 15, 16, 37):
        moved = mixer.apply(params, x.at[:, t].add(1.0))
        np.testing.assert_array_equal(np.asarray(moved[:, :t]), np.asarray(base[:, :t]))
        assert float(jnp.max(jnp.abs(moved[:, t:] - base[:, t:]))) > 1e-4
    with jax.default_matmul_precision("highest"):
        assert _rel(base, ref.linear_attention(x, params["params"])) < 1e-5


@pytest.mark.parametrize("neg_eigval", [True, False])
def test_allow_neg_eigval_is_honoured_both_ways(neg_eigval):
    """beta = 2 sigmoid with the key set, sigmoid without it: the system
    agrees with the reference told the same, and not with the other."""
    cfg = OlmoHybridConfig.parse(json.dumps(dict(TINY, linear_allow_neg_eigval=neg_eigval)))
    assert cfg.linear_allow_neg_eigval is neg_eigval
    mixer = LinearAttention(cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 32, 64), jnp.float32)
    params = mixer.init(jax.random.PRNGKey(3), x)
    got = mixer.apply(params, x)
    with jax.default_matmul_precision("highest"):
        assert _rel(got, ref.linear_attention(x, params["params"], neg_eigval=neg_eigval)) < 1e-5
        assert _rel(got, ref.linear_attention(x, params["params"], neg_eigval=not neg_eigval)) > 1e-3


@pytest.mark.parametrize("ranks", [3, 5], ids=["three_ranks_of_10_heads", "five_ranks_of_6_heads"])
def test_the_ranks_head_shares_sum_to_the_uncut_thirty_head_mixer(ranks):
    """The share test. Tensor-parallel ranks each hold ``30 / ranks`` of the
    30 heads (their q/k/v/g columns, a/b rows, conv channels, A_log / dt_bias
    and W_o rows; the gated norm's weight is per value channel and every rank
    holds it): their ``W_o`` outputs, the partial sums that go into the
    post-norm without the exchange, add up to the uncut mixer's output. Three
    ranks of 10 is the issue's cut, five of 6 the benchmark's (its fallback)."""
    held = 30 // ranks
    sizes = dict(TINY, linear_key_head_dim=8, linear_value_head_dim=16)
    mixer = lambda heads: LinearAttention(OlmoHybridConfig.parse(json.dumps(
        dict(sizes, linear_num_key_heads=heads, linear_num_value_heads=heads))))
    whole, part = mixer(30), mixer(held)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 32, 64), jnp.float32)
    params = whole.init(jax.random.PRNGKey(5), x)["params"]
    head_axis = {"q": 1, "k": 1, "v": 1, "g": 1, "a": 1, "b": 1, "conv_q": 1, "conv_k": 1, "conv_v": 1,
                 "A_log": 0, "dt_bias": 0, "out": 0}
    assert set(head_axis) | {"norm"} == set(params)

    def share(rank):
        cut = lambda name, w: w if name == "norm" else jax.lax.slice_in_dim(
            w, held * rank, held * (rank + 1), axis=head_axis[name])
        return {"params": {name: cut(name, w) for name, w in params.items()}}

    with jax.default_matmul_precision("highest"):
        want = whole.apply({"params": params}, x)
        parts = [part.apply(share(r), x) for r in range(ranks)]
        assert _rel(sum(parts), want) < 1e-5
        assert min(_rel(p, want) for p in parts) > 0.1  # no rank alone is the layer
        # and the reference, given one rank's share, computes that rank's part
        assert _rel(ref.linear_attention(x, share(1)["params"]), parts[1]) < 1e-5


def test_the_eight_vocabulary_slices_side_by_side_are_the_uncut_head():
    """Eight chips split the embedding and the untied head by rows and compute
    the layers alike (counted once: ``hidden``). Each rank's SYSTEM head over
    its rows, side by side, is the uncut reference's logits; and a rank whose
    traffic is drawn from its slice computes, whole and alone, the reference's
    logits over that slice's columns and its loss and gradients over the slice."""
    vocab, ranks = 128, 8
    rows = vocab // ranks
    whole, part = _model(), _model(config={"vocab_size": rows})
    x, _ = _tokens(5)
    variables = _init(whole, x)
    params = variables["params"]
    want = ref.forward(variables, x)  # [B, S, 128]
    alike = whole.apply(variables, x, method=OlmoHybrid.hidden)

    def share(r):
        held = slice(r * rows, (r + 1) * rows)
        return {"params": dict(params, embed={"embedding": params["embed"]["embedding"][held]},
                               head={"kernel": params["head"]["kernel"][:, held]})}

    side_by_side = [part.apply(share(r), alike, method=OlmoHybrid.logits) for r in range(ranks)]
    assert all(got.shape[-1] == rows for got in side_by_side)
    assert _rel(jnp.concatenate(side_by_side, axis=-1), want) < 1e-5

    for rank in (0, 3, 7):
        local, targets = _tokens(6 + rank, vocab=rows)  # ids inside the slice, as the cell's traffic
        got = part.apply(share(rank), local)
        uncut = ref.forward(variables, local + rank * rows)[..., rank * rows:(rank + 1) * rows]
        assert _rel(got, uncut) < 1e-5
        assert abs(float(ref.cross_entropy(got, targets)) - float(ref.cross_entropy(uncut, targets))) < 1e-5
        got_loss, got_grads = jax.value_and_grad(_loss(part, local, targets))(share(rank)["params"])
        want_loss, want_grads = ref.loss_and_grads(share(rank), local, targets)
        assert abs(float(got_loss) - float(want_loss)) < 1e-5
        for leaf in ("embed", "head"):
            assert _rel(*(jax.tree_util.tree_leaves(g[leaf])[0] for g in (got_grads, want_grads))) < 1e-4


def test_remat_blocks_is_the_same_function():
    x, y = _tokens(2)
    plain, remat = _model(), _model(remat_blocks=True)
    params = _init(plain, x)["params"]
    grads = [jax.grad(_loss(m, x, y))(params) for m in (plain, remat)]
    for a, b in zip(*map(jax.tree_util.tree_leaves, grads)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7)


def _train_flags(tmp_path, **more):
    flags = {
        "model-name": "olmo_hybrid", "model-config": json.dumps(TINY), "device-cache": "true",
        "scan-epoch": "true", "validate": "false", "debug-sample-size": "16", "image-size": "64",
        "batch-size": "8", "num-epochs": "2", "checkpoint-every-epochs": "0", "remat": "blocks",
        "learning-rate": "0.003", "compute-dtype": "float32",
        "metrics-file": str(tmp_path / "metrics.jsonl"), "log-file": str(tmp_path / "train.log"),
        "checkpoint-dir": str(tmp_path / "ckpt"), "trace-file": str(tmp_path / "spans.json"),
        **more,
    }
    return [part for k, v in flags.items() for part in (f"--{k}", v)]


def test_trainer_main_trains_the_model_from_the_device_cache_in_scanned_epochs(tmp_path):
    from mpi_pytorch_tpu.obs.schema import validate_jsonl
    from mpi_pytorch_tpu.train import trainer

    summary = trainer.main(_train_flags(tmp_path))
    assert summary.epochs_run == 2
    assert summary.epoch_losses[1] < summary.epoch_losses[0]
    assert not validate_jsonl(str(tmp_path / "metrics.jsonl"))
    with open(tmp_path / "metrics.jsonl") as f:
        epochs = [r for r in map(json.loads, f) if r["kind"] == "epoch"]
    assert len(epochs) == 2
    for rec in epochs:
        assert rec["tokens"] == 16 * 64  # 2 scanned steps of 8 sequences of 64 (8 CPU devices)
        assert round(rec["images_per_sec"] * rec["time_s"]) == 16  # samples are sequences
        assert not any(key.startswith("moe_") for key in rec)  # no router, no counters
    with open(tmp_path / "spans.json") as f:
        instants = [e for e in json.load(f)["traceEvents"] if e["name"] == "linear_attn/dispatch"]
    # One a distinct shape: init's dummy sequence, then the step's batch.
    assert [e["args"] for e in instants] == [
        {"heads": 6, "key_dim": 16, "value_dim": 32, "chunk": 16, "chunks": 4,
         "tokens": tokens, "path": "xla_chunked"}
        for tokens in (64, 8 * 64)
    ]


@pytest.mark.parametrize(
    "flags,message",
    [
        ({"device-cache": "false", "scan-epoch": "false"}, "device_cache=True"),
        ({"attn-impl": "fused-small"}, "attn_impl='fused-small' does not apply to model 'olmo_hybrid'"),
        ({"fused-stem": "true"}, "fused_stem=True does not apply to model 'olmo_hybrid'"),
    ],
)
def test_the_registry_entry_alone_covers_what_a_token_model_cannot_do(tmp_path, flags, message):
    from mpi_pytorch_tpu.config import parse_config
    from mpi_pytorch_tpu.models.registry import model_spec

    spec = model_spec("olmo_hybrid")
    assert spec.sample == "tokens" and spec.attn_impls == ("full", "flash")
    assert spec.flags == frozenset({"remat_blocks", "model_config"})
    assert spec.vocab(json.dumps(TINY)) == 128 and spec.vocab("") == 100352
    parse_config(_train_flags(tmp_path))
    with pytest.raises(ValueError, match=message):
        parse_config(_train_flags(tmp_path, **flags))


def test_model_config_defaults_are_the_published_model(tmp_path):
    cfg = OlmoHybridConfig.parse("")
    assert len(cfg.layer_types) == 32
    assert [i for i, t in enumerate(cfg.layer_types) if t == "full_attention"] == list(range(3, 32, 4))
    assert (cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size, cfg.rms_norm_eps) == (3840, 11008, 100352, 1e-6)
    assert (cfg.num_attention_heads, cfg.num_key_value_heads) == (30, 30)
    assert (cfg.linear_num_key_heads, cfg.linear_key_head_dim, cfg.linear_value_head_dim,
            cfg.linear_conv_kernel_dim, cfg.linear_chunk_size) == (30, 96, 192, 4, 64)
    assert cfg.linear_allow_neg_eigval is True
    # the benchmark's configuration file is the published model but for its
    # reduced keys, and parses from its path
    with open("benchmark/configs/olmo-hybrid-7b-tp5.json") as f:
        stated = json.load(f)
    path = tmp_path / "model.json"
    path.write_text(json.dumps(stated["model"]))
    cut = OlmoHybridConfig.parse(str(path))
    assert cut == OlmoHybridConfig.parse(json.dumps(stated["model"]))
    assert cut.layer_types == cfg.layer_types[:4] and cut.vocab_size == 12544 == 100352 // 8
    assert cut.linear_num_key_heads == 6 == cfg.linear_num_key_heads // 5  # the issue's fallback cut
    assert {f for f in cfg.__dataclass_fields__ if getattr(cut, f) != getattr(cfg, f)} == {
        "layer_types", "vocab_size", "linear_num_key_heads", "linear_num_value_heads",
    }
    assert stated["reduced"] == [
        "num_hidden_layers", "layer_types", "linear_num_key_heads", "linear_num_value_heads", "vocab_size",
    ]


@pytest.mark.parametrize(
    "bad,key",
    [
        ({"tie_word_embeddings": True}, "tie_word_embeddings"),
        ({"attention_bias": True}, "attention_bias"),
        ({"rope_parameters": {"rope_theta": 10000.0}}, "rope_theta"),
        ({"rope_theta": 500000}, "rope_theta"),
        ({"hidden_act": "gelu"}, "hidden_act"),
        ({"num_hidden_layers": 7}, "num_hidden_layers"),
        ({"layer_types": ["linear_attention", "sliding_attention"], "num_hidden_layers": 2}, "sliding_attention"),
        ({"linear_num_value_heads": 60}, "linear_num_value_heads"),
        ({"num_key_value_heads": 7}, "num_key_value_heads"),
    ],
)
def test_a_model_config_this_module_cannot_honour_is_refused_by_its_key(bad, key):
    with pytest.raises(ValueError, match=key):
        OlmoHybridConfig.parse(json.dumps(bad))


def test_the_flops_count_is_the_published_layers_arithmetic():
    """``forward_flops`` and the rule's cost from shapes, against the numbers
    worked by hand for the benchmark's configuration: 1.40 GFLOP a token at 6
    heads, and ISSUE 37's ~1.48 at the 10 heads it worked them for."""
    from benchmark import costs_gated_delta

    with open("benchmark/configs/olmo-hybrid-7b-tp5.json") as f:
        model = json.load(f)["model"]
    s, d, h = 8192, 3840, 6
    rule = costs_gated_delta.delta_forward_macs(model)
    assert rule == (2 * h * s * 32 * 96 + h * 128 * (64**3 // 6) + h * s * 32 * (96 + 192)
                    + h * s * 32 * 192 + 3 * h * s * 96 * 192)
    linear = s * d * h * (2 * 96 + 3 * 192 + 2) + s * 4 * h * 384 + rule
    attention = s * d * 128 * (4 * 30) + 2 * 30 * 128 * (s * s // 2)
    mlp = 3 * s * d * 11008
    flops = ref.forward_flops(model)
    assert flops == 2 * (3 * linear + attention + 4 * mlp + s * d * 12544)
    assert 1.40e9 < flops / s < 1.41e9
    ten = dict(model, linear_num_key_heads=10, linear_num_value_heads=10)
    assert 1.47e9 < ref.forward_flops(ten) / s < 1.49e9
    cost = costs_gated_delta.delta_cost(model, 1)
    assert cost["ops"] == 3 * 3 * 2 * rule
    assert cost["bytes"] == 3 * s * h * (3 * (2 * (2 * 96 + 192) + 8) + 2 * 2 * 192)
