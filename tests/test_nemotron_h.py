"""Nemotron-H with latent sparse experts on the training path
(models/nemotron_h.py; the shared ``Mamba2`` / ``Attention`` of
models/granite_hybrid.py; ``ops/moe.sigmoid_topk_route`` and ``held_experts``
with squared-ReLU experts on latent rows) against its plain reference
(benchmark/reference/nemotron_h.py: the per-position recurrence, a dense loop
over the held experts), at small sizes on the CPU with seeded float32 weights."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import nemotron_h as ref
from mpi_pytorch_tpu.models.granite_hybrid import (
    Attention, Mamba2, Mamba2Sizes, grouped_rms_norm,
)
from mpi_pytorch_tpu.models.lfm2 import rms_norm
from mpi_pytorch_tpu.models.nemotron_h import LatentMoE, NemotronHConfig, nemotron_h
from mpi_pytorch_tpu.ops.moe import held_experts, relu2_expert, row_bound, sigmoid_topk_route

TINY = {
    "hidden_size": 64, "hybrid_override_pattern": "ME*E", "num_hidden_layers": 4,
    "mamba_num_heads": 8, "mamba_head_dim": 8, "ssm_state_size": 16, "n_groups": 2, "chunk_size": 16,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "n_routed_experts": 4, "n_routed_experts_published": 16, "expert_offset": 4,
    "num_experts_per_tok": 6, "moe_latent_size": 32, "moe_intermediate_size": 48,
    "moe_shared_expert_intermediate_size": 80, "num_nextn_predict_layers": 0, "vocab_size": 128,
}
# what the reference cannot read off the tree at this size
REF_KW = dict(state=16, top_k=6, expert_offset=4)
CONFIG_FILE = "benchmark/configs/nemotron-3-super-120b-a12b-tp8ep64.json"


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


def _tokens(seed, batch=2, seq=64, vocab=128):
    rows = jax.random.randint(jax.random.PRNGKey(seed), (batch, seq + 1), 0, vocab)
    return rows[:, :-1], rows[:, 1:]


def _model(**kw):
    return nemotron_h(0, model_config=json.dumps(dict(TINY, **kw.pop("config", {}))), **kw)


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
    assert _rel(model.apply(variables, x), ref.forward(variables, x, **REF_KW)) < 1e-5
    got_loss, got = jax.value_and_grad(_loss(model, x, y))(variables["params"])
    want_loss, want = ref.loss_and_grads(variables, x, y, **REF_KW)
    assert abs(float(got_loss) - float(want_loss)) < 1e-5
    got_leaves = jax.tree_util.tree_leaves_with_path(got)
    want_leaves = jax.tree_util.tree_leaves_with_path(want)
    assert [p for p, _ in got_leaves] == [p for p, _ in want_leaves]
    # embedding, head, final norm; M 8 + norm; * 4 + norm; two E of 8 + norm
    assert len(got_leaves) == 3 + 9 + 5 + 2 * 9
    for (path, g), (_, w) in zip(got_leaves, want_leaves):
        name = jax.tree_util.keystr(path)
        if "e_score_correction_bias" in name:  # steers the selection, takes no gradient
            assert not np.any(np.asarray(g)) and not np.any(np.asarray(w)), name
        else:
            assert float(jnp.linalg.norm(w)) > 0 and _rel(g, w) < 1e-4, name


# The two heaviest tests (eight CPU devices, whole-model compiles) come first:
# the file's tail is then light, which is what runs beside other files'
# timing-sensitive tests under the driver's six workers.

def test_remat_blocks_is_the_same_function():
    x, y = _tokens(2)
    plain, remat = _model(), _model(remat_blocks=True)
    params = _init(plain, x)["params"]
    grads = [jax.grad(_loss(m, x, y))(params) for m in (plain, remat)]
    for a, b in zip(*map(jax.tree_util.tree_leaves, grads)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7)


# -- the trainer -----------------------------------------------------------

def _train_flags(tmp_path, **more):
    flags = {
        "model-name": "nemotron_h", "model-config": json.dumps(TINY), "device-cache": "true",
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
    e_layers = 2
    for rec in epochs:
        assert rec["tokens"] == 16 * 64  # 2 scanned steps of 8 sequences of 64 (8 CPU devices)
        assert rec["tokens_per_sec"] > 0
        assert round(rec["images_per_sec"] * rec["time_s"]) == 16  # samples are sequences
        assert rec["moe_pairs_held"] + rec["moe_pairs_absent"] == rec["tokens"] * 6 * e_layers
        assert 0 < rec["moe_load_max"] <= 8 * 64
        # 4 of 16 held at the model's ROW_SLACK of 4: buffers of all 3 072 pairs' rows, one pass a layer a step
        assert rec["moe_rows_computed"] == 2 * e_layers * row_bound(8 * 64 * 6, 4, 16, 4) == 2 * e_layers * 3072
        assert rec["moe_rows_computed"] >= rec["moe_pairs_held"]
    with open(tmp_path / "spans.json") as f:
        events = json.load(f)["traceEvents"]
    # One a distinct shape: init's dummy sequence, then the step's batch. The
    # dispatched rows are the LATENT ones (32 wide, not the hidden 64). Buffers
    # of every pair's rows (one pair a row) go back to tokens once a pair.
    assert [e["args"] for e in events if e["name"] == "moe/dispatch"] == [
        {"experts": 16, "held": 4, "top_k": 6, "tokens": tokens, "latent": 32,
         "path": "ragged_dot", "rows_bound": tokens * 6, "combine": "per_pair", "pairs_per_row": 1.0}
        for tokens in (64, 8 * 64)
    ]
    assert [e["args"]["groups"] for e in events if e["name"] == "ssm/dispatch"] == [2, 2]


def test_the_program_selects_the_experts_the_reference_selects():
    model = _model()
    x, _ = _tokens(3)
    variables = _init(model, x)
    _, sown = model.apply(variables, x, mutable=["intermediates", "counters"])
    mine = [sown["intermediates"][f"layer{i}"]["moe"]["selected_experts"][0] for i in (1, 3)]
    want = ref.selected_experts(variables, x, **REF_KW)
    assert want.shape == (2, 2 * 64, 6)
    for got, w in zip(mine, want):
        np.testing.assert_array_equal(np.sort(np.asarray(got), -1), np.asarray(w))
    for i in (1, 3):  # every routed pair is held or absent
        c = {k: int(v[0]) for k, v in sown["counters"][f"layer{i}"]["moe"].items()}
        assert c["moe_pairs_held"] + c["moe_pairs_absent"] == 2 * 64 * 6
        assert 0 < c["moe_pairs_held"] < 2 * 64 * 6


# -- the shares add up to the uncut layer, one test a layer kind -----------

def test_the_tensor_parallel_shares_of_a_mamba_layer_add_up_to_the_uncut_layer():
    """``G`` ranks each hold ONE group of B and C with its ``H / G`` heads: the
    columns of in_proj and of the convolution for those heads' z, x and dt and
    that group's B and C, the heads' dt_bias / A_log / D, their slice of the
    gated norm's weight (the norm is per group, so a rank normalises exactly
    what the uncut layer normalises there) and their rows of out_proj. Summed
    after out_proj the shares are the uncut reference's layer."""
    d, heads, p, n, groups = 32, 12, 8, 16, 3
    inner, r = heads * p, heads // groups
    whole = Mamba2(Mamba2Sizes(heads, p, n, groups, 4, 16, 1e-5))
    share = Mamba2(Mamba2Sizes(r, p, n, 1, 4, 16, 1e-5))
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 48, d), jnp.float32)
    params = whole.init(jax.random.PRNGKey(1), x)["params"]
    params["conv_b"] = jax.random.normal(jax.random.PRNGKey(2), params["conv_b"].shape) * 0.1
    params["norm"] = 1.0 + jax.random.normal(jax.random.PRNGKey(3), params["norm"].shape) * 0.1
    want = ref.mamba(x, params, state=n)
    assert _rel(whole.apply({"params": params}, x), want) < 1e-5

    def columns(rank):
        """This rank's columns of [z | x | B | C | dt] (in_proj) and of [x | B | C] (the convolution)."""
        ch = np.arange(rank * r * p, (rank + 1) * r * p)
        st = np.arange(rank * n, (rank + 1) * n)
        hd = np.arange(rank * r, (rank + 1) * r)
        bc = groups * n
        conv = np.concatenate([ch, inner + st, inner + bc + st])
        return np.concatenate([ch, inner + conv, 2 * inner + 2 * bc + hd]), conv, ch, hd

    total = 0.0
    for rank in range(groups):
        proj, conv, ch, hd = columns(rank)
        mine = {
            "in_proj": params["in_proj"][:, proj], "conv_w": params["conv_w"][:, conv],
            "conv_b": params["conv_b"][conv], "dt_bias": params["dt_bias"][hd],
            "A_log": params["A_log"][hd], "D": params["D"][hd], "norm": params["norm"][ch],
            "out_proj": params["out_proj"][ch],
        }
        out = share.apply({"params": mine}, x)
        assert _rel(out, ref.mamba(x, mine, state=n)) < 1e-5  # the reference, given the same share
        total = total + out
    assert _rel(total, want) < 1e-5
    # one group over all channels would NOT be the layer: the norm is per group
    one_group = Mamba2(Mamba2Sizes(heads, p, groups * n, 1, 4, 16, 1e-5))
    assert _rel(one_group.apply({"params": params}, x), want) > 1e-2


def test_the_query_head_shares_of_an_attention_layer_add_up_to_the_uncut_layer():
    """A rank holds the ``H / Hkv`` query heads of one key-value head and that
    head: q, k, v columns and out rows. Summed after the out projection the
    shares are the uncut reference's layer."""
    d, h, hkv, dh = 32, 6, 3, 16
    whole, share = Attention(h, hkv, dh), Attention(h // hkv, 1, dh)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, d), jnp.float32)
    params = whole.init(jax.random.PRNGKey(1), x)["params"]
    want = ref.attention(x, params, scale=dh**-0.5)
    assert _rel(whole.apply({"params": params}, x), want) < 1e-5
    g = h // hkv
    total = 0.0
    for rank in range(hkv):
        mine = {
            "q": params["q"][:, rank * g:(rank + 1) * g], "k": params["k"][:, rank:rank + 1],
            "v": params["v"][:, rank:rank + 1], "out": params["out"][rank * g:(rank + 1) * g],
        }
        total = total + share.apply({"params": mine}, x)
    assert _rel(total, want) < 1e-5


def test_the_expert_shares_add_up_to_the_uncut_layer_with_the_shared_expert_counted_once():
    """16 routed experts over four ranks of 4: every rank routes over all 16,
    computes the latent projections and the shared expert alike and its own
    experts' part. With the shared expert counted ONCE the routed parts add
    up to the uncut reference's layer (which holds all 16)."""
    tokens = 40
    cfg = lambda held, offset: NemotronHConfig.parse(json.dumps(dict(
        TINY, n_routed_experts=held, n_routed_experts_published=16, expert_offset=offset)))
    x = jax.random.normal(jax.random.PRNGKey(0), (1, tokens, 64), jnp.float32)
    whole = LatentMoE(cfg(16, 0))
    params = whole.init(jax.random.PRNGKey(1), x)["params"]
    params["e_score_correction_bias"] = jax.random.normal(jax.random.PRNGKey(2), (16,)) * 0.05
    # at 0.02 the routed part (four matrices deep, squared) is 0.2 % of the layer: make it count
    params.update(down=params["down"] * 4, w1=params["w1"] * 4)
    flat = x.reshape(-1, 64)
    want = ref.moe(flat, params, top_k=6)
    assert _rel(whole.apply({"params": params}, x).reshape(-1, 64), want) < 1e-5
    shared = ref.shared_expert(flat, params["shared"])
    total = shared
    for rank in range(4):
        mine = dict(params, w1=params["w1"][4 * rank:4 * rank + 4], w2=params["w2"][4 * rank:4 * rank + 4])
        out = LatentMoE(cfg(4, 4 * rank)).apply({"params": mine}, x).reshape(-1, 64)
        assert _rel(out, ref.moe(flat, mine, top_k=6, expert_offset=4 * rank)) < 1e-5
        total = total + (out - shared)
    assert _rel(total, want) < 1e-5
    assert _rel(shared, want) > 0.1  # the routed part is not nothing


# -- the pieces two models share -------------------------------------------

def test_the_grouped_gated_norm_with_one_group_is_granitemoehybrids_norm():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 24), jnp.float32) * 3
    w = 1.0 + jax.random.normal(jax.random.PRNGKey(1), (24,), jnp.float32) * 0.1
    np.testing.assert_array_equal(
        np.asarray(grouped_rms_norm(x, w, 1e-5, 1)), np.asarray(rms_norm(x, w, 1e-5))
    )
    same = lambda f: str(jax.make_jaxpr(f)(x, w))
    assert same(lambda x, w: grouped_rms_norm(x, w, 1e-5, 1)) == same(lambda x, w: rms_norm(x, w, 1e-5))
    by_group = jnp.concatenate(
        [rms_norm(x[..., i:i + 8], w[i:i + 8], 1e-5) for i in (0, 8, 16)], axis=-1
    )
    assert _rel(grouped_rms_norm(x, w, 1e-5, 3), by_group) < 1e-6
    assert _rel(grouped_rms_norm(x, w, 1e-5, 3), rms_norm(x, w, 1e-5)) > 1e-2


def _latent_experts(seed, latent=16, f=24, experts=64, d=32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    normal = lambda k, shape, std: jax.random.normal(k, shape, jnp.float32) * std
    return {
        "gate": normal(ks[0], (d, experts), 0.5), "e_score_correction_bias": normal(ks[1], (experts,), 0.05),
        "down": normal(ks[2], (d, latent), 0.3), "up": normal(ks[3], (latent, d), 0.3),
        "w1": normal(ks[4], (experts, latent, f), 0.3), "w2": normal(ks[5], (experts, f, latent), 0.3),
    }


def _routed_part(x, p, top_k, expert_offset=0):
    """The system's routed part of an E layer from a bare parameter dict."""
    sel, weight = sigmoid_topk_route(x, p["gate"], p["e_score_correction_bias"], top_k, 5.0, 1e-20)
    y, counters = held_experts(
        x @ p["down"], sel, weight, relu2_expert, (p["w1"], p["w2"]),
        routed=p["gate"].shape[1], expert_offset=expert_offset,
    )
    return y @ p["up"], counters


def test_top_22_under_adversarial_routing_drops_nothing():
    """Every token sends 8 of its 22 choices to the 8 held experts (and the
    same held expert takes every token): 8 T held pairs for row buffers of
    ``C = row_bound(22 T, 8, 64)`` < 8 T rows, so the passes loop; nothing is
    dropped, the pairs are conserved, values and gradients are the reference's."""
    tokens, top_k, held, routed = 96, 22, 8, 64
    p = _latent_experts(4, experts=routed)
    p.update(w1=p["w1"][:held], w2=p["w2"][:held])
    p["e_score_correction_bias"] = jnp.zeros(routed).at[jnp.arange(top_k)].set(100.0)  # ids 0..21
    x = jax.random.normal(jax.random.PRNGKey(5), (tokens, 32), jnp.float32)
    bound = row_bound(tokens * top_k, held, routed)
    assert bound == 640 < tokens * held
    mix = jax.random.normal(jax.random.PRNGKey(6), x.shape, jnp.float32)
    (_, (y, c)), got = jax.value_and_grad(
        lambda x, p: (lambda y, c: (jnp.sum(y * mix), (y, c)))(*_routed_part(x, p, top_k)),
        argnums=(0, 1), has_aux=True,
    )(x, p)
    assert int(c["moe_pairs_held"]) == tokens * held
    assert int(c["moe_pairs_absent"]) == tokens * (top_k - held)
    assert int(c["moe_load_max"]) == tokens  # one expert took every token
    assert int(c["moe_rows_computed"]) == 2 * bound  # 768 held pairs in buffers of 640 rows
    want_y = ref.routed_experts(x, p, top_k=top_k)
    want = jax.grad(lambda x, p: jnp.sum(ref.routed_experts(x, p, top_k=top_k) * mix), argnums=(0, 1))(x, p)
    assert _rel(y, want_y) < 1e-5
    assert _rel(got[0], want[0]) < 1e-4
    for name in ("gate", "down", "up", "w1", "w2"):
        assert _rel(got[1][name], want[1][name]) < 1e-4, name


def test_the_normaliser_is_the_sources_epsilon():
    """``sigmoid_topk_route(eps=)``: scores that all underflow towards 0 keep
    their ratios under 1e-20 and lose them under lfm2's 1e-6."""
    gate = jnp.eye(3, dtype=jnp.float32)
    x = jnp.array([[-16.0, -17.0, -18.0]])  # sigmoid ~ 1e-7, 4e-8, 1.5e-8
    bias = jnp.zeros(3)
    _, tight = sigmoid_topk_route(x, gate, bias, 2, 5.0, 1e-20)
    _, loose = sigmoid_topk_route(x, gate, bias, 2, 5.0)
    assert abs(float(jnp.sum(tight)) - 5.0) < 1e-4
    assert float(jnp.sum(loose)) < 4.0


# -- what a token model cannot do, by its registry entry alone ----------------

@pytest.mark.parametrize(
    "flags,message",
    [
        ({"device-cache": "false", "scan-epoch": "false"}, "device_cache=True"),
        ({"attn-impl": "fused-small"}, "attn_impl='fused-small' does not apply to model 'nemotron_h'"),
        ({"fused-stem": "true"}, "fused_stem=True does not apply to model 'nemotron_h'"),
    ],
)
def test_the_registry_entry_alone_covers_what_a_token_model_cannot_do(tmp_path, flags, message):
    from mpi_pytorch_tpu.config import parse_config
    from mpi_pytorch_tpu.models.registry import model_spec

    spec = model_spec("nemotron_h")
    assert spec.sample == "tokens" and spec.attn_impls == ("full", "flash")
    assert spec.flags == frozenset({"remat_blocks", "model_config"})
    assert spec.vocab(json.dumps(TINY)) == 128 and spec.vocab("") == 131072
    parse_config(_train_flags(tmp_path))
    with pytest.raises(ValueError, match=message):
        parse_config(_train_flags(tmp_path, **flags))


# -- the configuration -----------------------------------------------------

def test_model_config_defaults_are_the_published_model(tmp_path):
    cfg = NemotronHConfig.parse("")
    pattern = cfg.hybrid_override_pattern
    assert len(pattern) == 88 and (pattern.count("M"), pattern.count("E"), pattern.count("*")) == (40, 40, 8)
    # cut in eight runs of eleven, every run holds the published 5 : 5 : 1
    assert all(sorted(pattern[i:i + 11]) == sorted("MMMMMEEEEE*") for i in range(0, 88, 11))
    assert (cfg.hidden_size, cfg.vocab_size, cfg.layer_norm_epsilon) == (4096, 131072, 1e-5)
    assert (cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.ssm_state_size, cfg.n_groups) == (128, 64, 128, 8)
    assert (cfg.conv_kernel, cfg.chunk_size, cfg.mamba.inner) == (4, 128, 8192)
    assert (cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim) == (32, 2, 128)
    assert (cfg.n_routed_experts, cfg.routed, cfg.num_experts_per_tok, cfg.routed_scaling_factor) == (512, 512, 22, 5.0)
    assert (cfg.moe_latent_size, cfg.moe_intermediate_size, cfg.moe_shared_expert_intermediate_size) == (1024, 2688, 5376)
    # the benchmark's configuration file is the published model but for its
    # reduced keys, and parses from its path
    with open(CONFIG_FILE) as f:
        stated = json.load(f)
    path = tmp_path / "model.json"
    path.write_text(json.dumps(stated["model"]))
    cut = NemotronHConfig.parse(str(path))
    assert cut == NemotronHConfig.parse(json.dumps(stated["model"]))
    assert cut.hybrid_override_pattern == pattern[:11] == "MEMEMEM*EME"
    assert (cut.n_routed_experts, cut.routed, cut.expert_offset) == (8, 512, 0)
    assert (cut.mamba_num_heads, cut.n_groups) == (128 // 8, 8 // 8)
    assert (cut.num_attention_heads, cut.num_key_value_heads, cut.vocab_size) == (32 // 8, 1, 131072 // 8)
    differing = {f for f in cfg.__dataclass_fields__ if getattr(cut, f) != getattr(cfg, f)}
    assert differing == {
        "hybrid_override_pattern", "n_routed_experts", "n_routed_experts_published", "mamba_num_heads",
        "n_groups", "num_attention_heads", "num_key_value_heads", "vocab_size",
    }
    assert set(stated["reduced"]) == (differing - {"n_routed_experts_published"}) | {
        "num_hidden_layers", "num_nextn_predict_layers"}
    assert all(stated[key] == stated["model"][key] for key in stated["reduced"])


def test_the_cut_configuration_holds_700_9_million_parameters():
    """Counted by layer from the shapes the model would be built with
    (``jax.eval_shape``: nothing is allocated)."""
    with open(CONFIG_FILE) as f:
        model = nemotron_h(0, model_config=json.dumps(json.load(f)["model"]))
    shapes = jax.eval_shape(
        lambda key, x: model.init(key, x), jax.ShapeDtypeStruct((2,), jnp.uint32),
        jax.ShapeDtypeStruct((1, 128), jnp.int32),
    )["params"]
    count = lambda tree: sum(int(np.prod(leaf.shape)) for leaf in jax.tree_util.tree_leaves(tree))
    by_kind = {kind: count(shapes[f"layer{i}"]) for i, kind in enumerate("MEMEMEM*EME")}
    assert by_kind["M"] == 4096 * 2320 + 1024 * 4096 + 4 * 1280 + 1280 + 3 * 16 + 1024 + 4096
    assert by_kind["*"] == 2 * 4096 * 4 * 128 + 2 * 4096 * 128 + 4096
    assert by_kind["E"] == 4096 * 512 + 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376 + 8 * 2 * 1024 * 2688 + 4096
    assert count(shapes["embed"]) + count(shapes["head"]) == 2 * 16384 * 4096
    assert round(count(shapes) / 1e6, 1) == 700.9


@pytest.mark.parametrize(
    "bad,key",
    [
        ({"n_group": 2}, "n_group"),
        ({"topk_group": 2}, "topk_group"),
        ({"mlp_hidden_act": "silu"}, "mlp_hidden_act"),
        ({"hybrid_override_pattern": "ME-*", "num_hidden_layers": 4}, "'-'"),
        ({"num_nextn_predict_layers": 1}, "num_nextn_predict_layers"),
        ({"tie_word_embeddings": True}, "tie_word_embeddings"),
        ({"n_groups": 3}, "n_groups"),
        ({"num_key_value_heads": 3}, "num_key_value_heads"),
        ({"num_hidden_layers": 7}, "num_hidden_layers"),
        ({"expert_offset": 14}, "expert_offset"),
        ({"use_conv_bias": False}, "use_conv_bias"),
    ],
)
def test_a_model_config_this_module_cannot_honour_is_refused_by_its_key(bad, key):
    with pytest.raises(ValueError, match=key):
        NemotronHConfig.parse(json.dumps(dict(TINY, **bad)))


def test_the_flops_count_is_the_cuts_arithmetic():
    """``forward_flops`` and the cost file from shapes, against the numbers
    worked by hand for the benchmark's configuration (ISSUE 33): 857.6 MFLOP a
    token forward, 42.2 TFLOP a 16 384-token step."""
    from benchmark import costs_nemotron_h as costs

    with open(CONFIG_FILE) as f:
        model = json.load(f)["model"]
    s = 8192
    scan = costs.scan_forward_macs(model)
    assert scan == s * 64 * 128 + 16 * s * 64 * 64 + 2 * 16 * s * 128 * 64
    mamba = s * 4096 * 2320 + s * 1024 * 4096 + s * 4 * 1280 + scan
    attention = s * 4096 * 128 * (2 * 4 + 2 * 1) + 2 * 4 * 128 * (s * s // 2)
    pairs = s * 22 * 8 // 512
    experts = s * 4096 * (512 + 2 * 1024 + 2 * 5376) + pairs * 2 * 1024 * 2688
    total = 2 * (5 * mamba + attention + 5 * experts + s * 4096 * 16384)
    assert ref.forward_flops(model) == total
    assert round(total / s / 1e6, 1) == 857.7 and round(3 * 2 * total / 1e12, 1) == 42.2
    assert costs.expert_pair_flops(model) == 3 * 2 * 2 * 1024 * 2688
    assert costs.moe_layers(model) == 5
