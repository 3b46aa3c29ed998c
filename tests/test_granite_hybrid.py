"""Granite 4.0-H on the training path (models/granite_hybrid.py, ops/ssd.py,
flash attention with its own softmax scale, the tied head) against its plain
reference (benchmark/reference/granitemoehybrid.py: the PER-POSITION
state-space recurrence), at small sizes on the CPU with seeded float32
weights."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import granitemoehybrid as ref
from mpi_pytorch_tpu.models.granite_hybrid import (
    GraniteHybridConfig, Mamba2, granitemoehybrid,
)
from mpi_pytorch_tpu.ops.ssd import ssd

TINY = {
    "hidden_size": 64, "shared_intermediate_size": 96, "num_attention_heads": 4,
    "num_key_value_heads": 2, "layer_types": ["mamba", "attention", "mamba"],
    "num_hidden_layers": 3, "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 16,
    "mamba_n_groups": 1, "mamba_chunk_size": 16, "vocab_size": 128,
}


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


def _tokens(seed, batch=2, seq=64, vocab=128):
    rows = jax.random.randint(jax.random.PRNGKey(seed), (batch, seq + 1), 0, vocab)
    return rows[:, :-1], rows[:, 1:]


def _model(**kw):
    return granitemoehybrid(0, model_config=json.dumps(dict(TINY, **kw.pop("config", {}))), **kw)


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
    assert len(got_leaves) == 1 + 2 * 13 + 9 + 1  # embedding, 2 mamba blocks, 1 attention block, norm
    for (path, g), (_, w) in zip(got_leaves, want_leaves):
        assert _rel(g, w) < 1e-4, jax.tree_util.keystr(path)


def _scan_inputs(seed, batch, seq, heads, head_dim, state, groups):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    normal = lambda k, *shape: jax.random.normal(k, shape, jnp.float32)
    return (
        normal(ks[0], batch, seq, heads, head_dim),
        jax.nn.softplus(normal(ks[1], batch, seq, heads) - 1.0),  # dt, positive
        jnp.log(jax.random.uniform(ks[2], (heads,), jnp.float32, 1.0, 16.0)),  # A_log
        normal(ks[3], batch, seq, groups, state),
        normal(ks[4], batch, seq, groups, state),
        normal(ks[5], heads),  # D
    )


@pytest.mark.parametrize("seq", [8, 16, 48], ids=["below_a_chunk", "one_chunk", "three_chunks"])
@pytest.mark.parametrize(
    "heads,head_dim,state,groups", [(4, 8, 16, 1), (4, 16, 8, 2), (6, 4, 8, 3), (2, 8, 4, 2)]
)
def test_the_chunked_scan_is_the_per_position_recurrence(seq, heads, head_dim, state, groups):
    """Values and all six cotangents (x, dt, A_log, B, C, D) of ``ops/ssd.ssd``
    at chunk 16 against one ``lax.scan`` step a position."""
    args = _scan_inputs(seq + heads, 2, seq, heads, head_dim, state, groups)
    weight = jax.random.normal(jax.random.PRNGKey(9), (2, seq, heads, head_dim), jnp.float32)
    chunked = lambda *a: ssd(*a, chunk=16)
    assert _rel(chunked(*args), ref.ssm_scan(*args)) < 1e-5
    grads = [
        jax.grad(lambda *a, f=f: jnp.sum(f(*a) * weight), argnums=tuple(range(6)))(*args)
        for f in (chunked, ref.ssm_scan)
    ]
    for name, g, w in zip(("x", "dt", "A_log", "B", "C", "D"), *grads):
        assert g.shape == w.shape and _rel(g, w) < 1e-4, name


def test_a_sequence_that_is_no_whole_number_of_chunks_is_refused_with_the_numbers():
    args = _scan_inputs(0, 1, 40, 4, 8, 16, 1)
    with pytest.raises(ValueError, match=r"40 positions .* chunks of 16 \(mamba_chunk_size\)"):
        ssd(*args, chunk=16)
    with pytest.raises(ValueError, match="groups must divide the heads"):
        ssd(*_scan_inputs(0, 1, 16, 4, 8, 16, 3), chunk=16)
    model = _model()
    x, _ = _tokens(0, seq=64)
    variables = _init(model, x)
    with pytest.raises(ValueError, match="72 positions"):
        model.apply(variables, _tokens(0, seq=72)[0])


def test_the_state_space_mixer_is_causal():
    """A change at position t moves nothing before t, across chunk borders."""
    cfg = GraniteHybridConfig.parse(json.dumps(TINY))
    mixer = Mamba2(cfg.mamba)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 64, 64), jnp.float32)
    params = mixer.init(jax.random.PRNGKey(1), x)
    base = mixer.apply(params, x)
    for t in (0, 15, 16, 37):
        moved = mixer.apply(params, x.at[:, t].add(1.0))
        np.testing.assert_array_equal(np.asarray(moved[:, :t]), np.asarray(base[:, :t]))
        assert float(jnp.max(jnp.abs(moved[:, t:] - base[:, t:]))) > 1e-4
    assert _rel(base, ref.mamba(x, params["params"])) < 1e-5


@pytest.mark.parametrize("block_q,block_k", [(16, 16), (32, 16)])
def test_flash_attention_takes_a_softmax_scale_forward_and_backward(block_q, block_k, monkeypatch):
    """``scale=1/64`` at head size 16 (not ``16 ** -0.5``) through the forward
    kernel and both backward kernels, grouped heads, several blocks a side."""
    from mpi_pytorch_tpu.ops import flash_attention as fa
    from mpi_pytorch_tpu.ops.ring_attention import full_attention

    monkeypatch.setattr(fa, "BWD_BLOCKS", (32, 16))
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    q = jax.random.normal(ks[0], (2, 64, 4, 16), jnp.float32) * 4
    k, v = (jax.random.normal(kk, (2, 64, 2, 16), jnp.float32) * 4 for kk in ks[1:3])
    weight = jax.random.normal(ks[3], q.shape, jnp.float32)

    def flash(q, k, v):
        return fa.flash_attention(q, k, v, causal=True, block_q=block_q, block_k=block_k,
                                  interpret=True, scale=1 / 64)

    def full(q, k, v, scale=1 / 64):
        return full_attention(q, jnp.repeat(k, 2, 2), jnp.repeat(v, 2, 2), causal=True, scale=scale)

    assert _rel(flash(q, k, v), full(q, k, v)) < 1e-5
    assert _rel(full(q, k, v), full(q, k, v, None)) > 0.05  # the scale is not the default
    grads = [
        jax.grad(lambda *a, f=f: jnp.sum(f(*a) * weight), argnums=(0, 1, 2))(q, k, v)
        for f in (flash, full)
    ]
    for name, g, w in zip("qkv", *grads):
        assert _rel(g, w) < 1e-4, name


def test_no_scale_lowers_flash_attention_to_what_the_default_scale_lowers_to():
    """``scale=None`` is ``D ** -0.5``: the same jaxpr (kernel bodies with their
    constants included) and the same text lowered for a TPU, so ``lfm2_moe``
    and the ViTs, which pass none, keep their programs; another scale is
    another program."""
    from mpi_pytorch_tpu.ops.flash_attention import flash_attention
    from mpi_pytorch_tpu.ops.ring_attention import full_attention

    q = jax.ShapeDtypeStruct((1, 256, 4, 64), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 256, 2, 64), jnp.bfloat16)

    def program(scale):
        def pair(q, k, v, do):
            out, pull = jax.vjp(
                lambda *a: flash_attention(*a, causal=True, interpret=False, scale=scale), q, k, v
            )
            return out, pull(do)

        traced = jax.jit(pair).trace(q, kv, kv, q)
        return str(traced.jaxpr), traced.lower(lowering_platforms=("tpu",)).as_text()

    # one call site: a lowered module carries its callers' lines AND columns
    none, default, other = [program(scale) for scale in (None, 64**-0.5, 1 / 64)]
    assert none == default
    assert none[0] != other[0] and none[1] != other[1]
    dense = lambda scale: str(jax.make_jaxpr(lambda q: full_attention(q, q, q, scale=scale))(q))
    assert dense(None) == dense(64**-0.5) != dense(1 / 64)


def test_the_tied_leafs_gradient_is_the_sum_of_its_two_uses():
    """The embedding is read by the lookup and by the head: with the head's
    use of it cut off from the gradient, and then the lookup's, the two parts
    add up to the leaf's gradient, and neither is zero."""
    x, y = _tokens(4)
    model = _model()
    params = _init(model, x)["params"]
    table = params["embed"]["embedding"]

    def loss(lookup_table, head_table):
        p = dict(params, embed={"embedding": lookup_table})
        h = ref.hidden(p, x)
        return ref.cross_entropy(h @ head_table.T / ref.LOGITS_SCALING, y)

    by_lookup, by_head = jax.grad(loss, argnums=(0, 1))(table, table)
    tied = jax.grad(_loss(model, x, y))(params)["embed"]["embedding"]
    assert float(jnp.linalg.norm(by_lookup)) > 0 and float(jnp.linalg.norm(by_head)) > 0
    assert _rel(tied, by_lookup + by_head) < 1e-4
    assert _rel(tied, by_head) > 1e-2


def test_the_eight_vocabulary_slices_side_by_side_are_the_uncut_model():
    """The share test. Eight chips split the tied embedding/head by rows and
    compute the layers alike (counted once: ``hidden``). Each rank's SYSTEM
    head over its rows, side by side, is the uncut reference's logits; and a
    rank whose traffic is drawn from its slice computes, whole and alone, the
    reference's logits over that slice's columns and its loss over the slice
    (the softmax over the slice's columns, the targets inside it)."""
    from mpi_pytorch_tpu.models.granite_hybrid import GraniteHybrid

    vocab, ranks = 128, 8
    rows = vocab // ranks
    whole, part = _model(), _model(config={"vocab_size": rows})
    x, _ = _tokens(5)
    variables = _init(whole, x)
    params = variables["params"]
    want = ref.forward(variables, x)  # [B, S, 128]
    alike = whole.apply(variables, x, method=GraniteHybrid.hidden)
    share = lambda r: {"params": dict(params, embed={"embedding": params["embed"]["embedding"][r * rows:(r + 1) * rows]})}
    side_by_side = [part.apply(share(r), alike, method=GraniteHybrid.head) for r in range(ranks)]
    assert all(got.shape[-1] == rows for got in side_by_side)
    assert _rel(jnp.concatenate(side_by_side, axis=-1), want) < 1e-5

    for rank in (0, 3, 7):
        local, targets = _tokens(6 + rank, vocab=rows)  # ids inside the slice, as the cell's traffic
        got = part.apply(share(rank), local)
        uncut = ref.forward(variables, local + rank * rows)[..., rank * rows:(rank + 1) * rows]
        assert _rel(got, uncut) < 1e-5
        assert abs(float(ref.cross_entropy(got, targets)) - float(ref.cross_entropy(uncut, targets))) < 1e-5
        # and the reference, given the same share, says the same: loss and gradient
        got_loss, got_grads = jax.value_and_grad(_loss(part, local, targets))(share(rank)["params"])
        want_loss, want_grads = ref.loss_and_grads(share(rank), local, targets)
        assert abs(float(got_loss) - float(want_loss)) < 1e-5
        assert _rel(got_grads["embed"]["embedding"], want_grads["embed"]["embedding"]) < 1e-4


def test_remat_blocks_is_the_same_function():
    x, y = _tokens(2)
    plain, remat = _model(), _model(remat_blocks=True)
    params = _init(plain, x)["params"]
    grads = [jax.grad(_loss(m, x, y))(params) for m in (plain, remat)]
    for a, b in zip(*map(jax.tree_util.tree_leaves, grads)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7)


def _train_flags(tmp_path, **more):
    flags = {
        "model-name": "granitemoehybrid", "model-config": json.dumps(TINY), "device-cache": "true",
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
        assert rec["tokens_per_sec"] > 0
        assert round(rec["images_per_sec"] * rec["time_s"]) == 16  # samples are sequences
        assert not any(key.startswith("moe_") for key in rec)  # no router, no counters
    with open(tmp_path / "spans.json") as f:
        instants = [e for e in json.load(f)["traceEvents"] if e["name"] == "ssm/dispatch"]
    # One a distinct shape: init's dummy sequence, then the step's batch.
    assert [e["args"] for e in instants] == [
        {"heads": 8, "head_dim": 16, "state": 16, "groups": 1, "chunk": 16, "chunks": 4,
         "tokens": tokens, "path": "xla_chunked"}
        for tokens in (64, 8 * 64)
    ]


@pytest.mark.parametrize(
    "flags,message",
    [
        ({"device-cache": "false", "scan-epoch": "false"}, "device_cache=True"),
        ({"validate": "true"}, "validation is an image path"),
        ({"attn-impl": "fused-small"}, "attn_impl='fused-small' does not apply to model 'granitemoehybrid'"),
        ({"fused-stem": "true"}, "fused_stem=True does not apply to model 'granitemoehybrid'"),
    ],
)
def test_the_registry_entry_alone_covers_what_a_token_model_cannot_do(tmp_path, flags, message):
    """``config.py`` refuses by ``ModelSpec.sample`` and ``ModelSpec.flags``:
    no list of names anywhere knows this model."""
    from mpi_pytorch_tpu.config import parse_config
    from mpi_pytorch_tpu.models.registry import model_spec

    spec = model_spec("granitemoehybrid")
    assert spec.sample == "tokens" and spec.attn_impls == ("full", "flash")
    assert spec.flags == frozenset({"remat_blocks", "model_config"})
    assert spec.vocab(json.dumps(TINY)) == 128 and spec.vocab("") == 100352
    parse_config(_train_flags(tmp_path))  # the flags as the test above runs them pass
    with pytest.raises(ValueError, match=message):
        parse_config(_train_flags(tmp_path, **flags))


def test_model_config_defaults_are_the_published_model(tmp_path):
    cfg = GraniteHybridConfig.parse("")
    assert len(cfg.layer_types) == 40
    assert [i for i, t in enumerate(cfg.layer_types) if t == "attention"] == [5, 15, 25, 35]
    assert set(cfg.layer_types) == {"mamba", "attention"}
    assert (cfg.hidden_size, cfg.shared_intermediate_size, cfg.vocab_size) == (2048, 8192, 100352)
    assert (cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state, cfg.mamba_n_groups) == (64, 64, 128, 1)
    assert (cfg.mamba_d_conv, cfg.mamba_chunk_size, cfg.mamba_inner) == (4, 256, 4096)
    assert (cfg.num_attention_heads, cfg.num_key_value_heads) == (32, 8)
    assert (cfg.embedding_multiplier, cfg.residual_multiplier) == (12.0, 0.22)
    assert (cfg.attention_multiplier, cfg.logits_scaling, cfg.rms_norm_eps) == (1 / 64, 8.0, 1e-5)
    # the benchmark's configuration file is the published model but for its
    # three reduced keys, and parses from its path
    with open("benchmark/configs/granite-4.0-h-micro-vp8.json") as f:
        stated = json.load(f)
    path = tmp_path / "model.json"
    path.write_text(json.dumps(stated["model"]))
    cut = GraniteHybridConfig.parse(str(path))
    assert cut == GraniteHybridConfig.parse(json.dumps(stated["model"]))
    assert cut.layer_types == cfg.layer_types[:10] and cut.vocab_size == 12544 == 100352 // 8
    assert {
        f for f in cfg.__dataclass_fields__ if getattr(cut, f) != getattr(cfg, f)
    } == {"layer_types", "vocab_size"} and stated["reduced"] == ["num_hidden_layers", "layer_types", "vocab_size"]


@pytest.mark.parametrize(
    "bad,key",
    [
        ({"num_local_experts": 8}, "num_local_experts"),
        ({"position_embedding_type": "rope"}, "position_embedding_type"),
        ({"mamba_n_groups": 3}, "mamba_n_groups"),
        ({"layer_types": ["mamba", "conv"], "num_hidden_layers": 2}, "conv"),
        ({"num_hidden_layers": 7}, "num_hidden_layers"),
        ({"tie_word_embeddings": False}, "tie_word_embeddings"),
        ({"mamba_conv_bias": False}, "mamba_conv_bias"),
        ({"mamba_d_head": 48}, "mamba_expand"),
        ({"num_key_value_heads": 5}, "num_key_value_heads"),
    ],
)
def test_a_model_config_this_module_cannot_honour_is_refused_by_its_key(bad, key):
    with pytest.raises(ValueError, match=key):
        GraniteHybridConfig.parse(json.dumps(bad))


def test_the_flops_count_is_the_published_layers_arithmetic():
    """``forward_flops`` and the scan's cost from shapes, against the numbers
    worked by hand for the benchmark's configuration (ISSUE 31)."""
    from benchmark import costs_ssd

    with open("benchmark/configs/granite-4.0-h-micro-vp8.json") as f:
        model = json.load(f)["model"]
    s = 8192
    scan = costs_ssd.scan_forward_macs(model)
    assert scan == s * 128 * 128 + 64 * s * 128 * 64 + 2 * 64 * s * 128 * 64
    mamba = s * 2048 * 8512 + s * 4096 * 2048 + s * 4 * 4352 + scan
    attention = s * 2048 * 64 * (2 * 32 + 2 * 8) + 2 * 32 * 64 * (s * s // 2)
    mlp = 3 * s * 2048 * 8192
    assert ref.forward_flops(model) == 2 * (9 * mamba + attention + 10 * mlp + s * 2048 * 12544)
    cost = costs_ssd.scan_cost(model, 1)
    assert cost["ops"] == 9 * 3 * 2 * scan
    assert cost["bytes"] == 9 * 2 * s * (3 * (4096 + 256 + 64) + 2 * 4096)
