"""``models/registry.ModelSpec``: one entry says what an architecture is, and
config validation, the model factory, the trainer and the serving zoo all ask
it. Pinned here: (a) every model's accepted build flags are what its module
really takes — and exactly the table the name lists held before PR 29; (b) a
model registered in a test is honoured everywhere with no other edit; (c) the
loaders' device-cache row contract (``cache_row`` / ``fill_cache_rows``)."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn
from jax.sharding import Mesh

from mpi_pytorch_tpu.config import Config
from mpi_pytorch_tpu.models import registry
from mpi_pytorch_tpu.models.registry import (
    ModelSpec,
    available_models,
    check_build_flags,
    create_model_bundle,
    initialize_model,
    model_spec,
)

TINY_LFM2 = json.dumps({
    "hidden_size": 64, "intermediate_size": 160, "moe_intermediate_size": 48,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "layer_types": ["conv", "full_attention"], "num_dense_layers": 1,
    "num_experts": 4, "num_experts_per_tok": 2, "vocab_size": 128,
})

TINY_GRANITE = json.dumps({
    "hidden_size": 64, "shared_intermediate_size": 96, "num_attention_heads": 4,
    "num_key_value_heads": 2, "layer_types": ["mamba", "attention"], "mamba_n_heads": 8,
    "mamba_d_head": 16, "mamba_d_state": 16, "mamba_chunk_size": 16, "vocab_size": 128,
})
TINY_NEMOTRON = json.dumps({
    "hidden_size": 64, "hybrid_override_pattern": "ME*", "mamba_num_heads": 8, "mamba_head_dim": 8,
    "ssm_state_size": 16, "n_groups": 2, "chunk_size": 16, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "n_routed_experts": 4, "num_experts_per_tok": 2,
    "moe_latent_size": 32, "moe_intermediate_size": 48, "moe_shared_expert_intermediate_size": 80,
    "num_nextn_predict_layers": 0, "vocab_size": 128,
})
# A configured model's own tiny ``--model-config`` (each reads its source's keys).
TINY_CONFIGS = {"lfm2_moe": TINY_LFM2, "granitemoehybrid": TINY_GRANITE, "nemotron_h": TINY_NEMOTRON}

# The (flag, value) -> models table of the parent commit's eleven name lists
# (ATTN_IMPL_MODELS, SP_MODELS, MOE_MODELS, REMAT_BLOCKS_MODELS, S2D_MODELS,
# FUSED_STEM_MODELS, CONFIGURED_MODELS, PP_MODELS and the token model's
# fused-small refusal): what is accepted; every other pair is refused.
_VITS = {"vit_s16", "vit_b16", "vit_moe_s16"}
ACCEPTED = {
    ("attn_impl", "flash"): _VITS | {"lfm2_moe", "granitemoehybrid", "nemotron_h"},
    ("attn_impl", "fused-small"): _VITS,
    ("sp_strategy", "ring"): _VITS,
    ("qkv_fused", True): _VITS,
    ("ep_mesh", "mesh"): {"vit_moe_s16"},
    ("remat_blocks", True): {
        "resnet18", "resnet34", "densenet121", "vit_s16", "vit_b16", "lfm2_moe",
        "granitemoehybrid", "nemotron_h",
    },
    ("stem_s2d", True): {"resnet18", "resnet34"},
    ("fused_stem", True): {"resnet18", "resnet34", "densenet121"},
    ("model_config", TINY_LFM2): set(TINY_CONFIGS),
    ("pp_stages", 2): {"vit_s16", "vit_b16"},
}


def _mesh(axis):
    return Mesh(np.array(jax.devices()[:2]), (axis,))


def _init_shapes(name, model):
    spec = model_spec(name)
    if spec.sample == "tokens":
        dummy = jax.ShapeDtypeStruct((2, 32), jnp.int32)
    else:
        size = spec.required_size or 64
        dummy = jax.ShapeDtypeStruct((2, size, size, 3), jnp.float32)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    return jax.eval_shape(
        lambda r, x: model.init(r, x, train=True), {"params": key, "dropout": key}, dummy
    )


@pytest.mark.parametrize("name", available_models())
def test_accepted_flags_are_what_the_module_takes_and_the_rest_is_refused(name):
    spec = model_spec(name)
    base = {"model_config": TINY_CONFIGS[name]} if "model_config" in spec.flags else {}
    for (flag, value), takers in ACCEPTED.items():
        if flag == "ep_mesh":
            value = _mesh("expert")
        if flag == "model_config":
            value = TINY_CONFIGS.get(name, value)
        assert spec.accepts(flag, value) == (name in takers), (name, flag)
        if name not in takers:
            with pytest.raises(ValueError, match=f"{flag}.* does not apply to model '{name}'"):
                check_build_flags(name, **{flag: value})
            if flag != "pp_stages":  # an execution strategy, not a factory keyword
                with pytest.raises(ValueError, match=f"{flag}.* does not apply to model"):
                    initialize_model(name, 10, **{flag: value})
            continue
        check_build_flags(name, **{flag: value})
        if flag == "pp_stages":
            continue
        kw = {**base, flag: value}
        if flag == "sp_strategy":
            kw["sp_mesh"] = _mesh("seq")
        model, _ = initialize_model(name, 10, **kw)
        if flag != "model_config":  # the factory parses that one into ``cfg``
            assert getattr(model, flag) is value or getattr(model, flag) == value
        assert "params" in _init_shapes(name, model)
    # The two capabilities that are handed over rather than asked for.
    model, _ = initialize_model(name, 10, **base)
    fields = type(model).__dataclass_fields__
    assert spec.batchnorm == ("bn_axis_name" in fields)
    assert ("dp_mesh" in spec.flags) == ("dp_mesh" in fields)
    assert set(spec.flags) <= {flag for flag, _ in ACCEPTED} | {"dp_mesh"}


class _TinyTok(nn.Module):
    """A throw-away token model: embed, project back to the vocabulary."""

    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        return nn.Dense(32, name="head", **kw)(nn.Embed(32, 8, name="embed", **kw)(tokens))


def test_a_model_registered_here_is_honoured_everywhere_with_no_other_edit(
    monkeypatch, tmp_path
):
    from mpi_pytorch_tpu.serve.zoo.registry import parse_model_specs
    from mpi_pytorch_tpu.train import trainer

    monkeypatch.setitem(
        registry._REGISTRY, "tiny_tok",
        ModelSpec(
            lambda num_classes, **kw: _TinyTok(**kw), 16, sample="tokens",
            vocab=lambda model_config: 32, batchnorm=False,
        ),
    )
    ok = dict(model_name="tiny_tok", device_cache=True, validate=False)
    Config(**ok).validate_config()
    for bad, message in [
        ({"validate": True}, "validation is an image path"),
        ({"device_cache": False}, "device_cache=True"),
        ({"remat": "blocks"}, "remat_blocks=True does not apply to model 'tiny_tok'"),
        ({"attn_impl": "flash"}, "attn_impl='flash' does not apply to model 'tiny_tok'"),
    ]:
        with pytest.raises(ValueError, match=message):
            Config(**{**ok, **bad}).validate_config()

    bundle, variables = create_model_bundle("tiny_tok", 10, image_size=16)
    assert not bundle.has_aux_logits and bundle.input_size == 16
    assert variables["params"]["embed"]["embedding"].shape == (32, 8)
    with pytest.raises(ValueError, match="token model"):
        parse_model_specs("tiny_tok")

    # The trainer: manifests, loader, device cache and epoch record follow
    # from ``sample``; ``tokens`` is the step's own count of a [B, S] batch.
    summary = trainer.main([
        "--model-name", "tiny_tok", "--device-cache", "true", "--scan-epoch", "true",
        "--validate", "false", "--debug-sample-size", "16", "--image-size", "16",
        "--batch-size", "8", "--num-epochs", "1", "--checkpoint-every-epochs", "0",
        "--compute-dtype", "float32", "--metrics-file", str(tmp_path / "metrics.jsonl"),
        "--log-file", str(tmp_path / "train.log"), "--checkpoint-dir", str(tmp_path / "ckpt"),
    ])
    assert summary.epochs_run == 1
    with open(tmp_path / "metrics.jsonl") as f:
        (epoch,) = [r for r in map(json.loads, f) if r["kind"] == "epoch"]
    assert epoch["tokens"] == 16 * 16 and epoch["tokens_per_sec"] > 0
    assert not [k for k in epoch if k.startswith("moe_")]


def _parent_cache_rows(kind, manifest, loader, lo, hi):
    """What ``build_device_cache`` put in rows ``[lo, hi)`` before PR 29: the
    pack's rows as they lie, or one ordered decode pass over the selection."""
    if kind == "tokens":
        return np.asarray(manifest.tokens[lo:hi])
    from mpi_pytorch_tpu.data import DataLoader

    ordered = DataLoader(
        manifest.select(np.arange(lo, hi)), batch_size=loader.batch_size,
        image_size=loader.image_size, shuffle=False, drop_remainder=False,
        synthetic=True, image_dtype=str(np.dtype(loader.image_dtype)),
    )
    return np.concatenate([images for images, _ in ordered.epoch(0)])


@pytest.mark.parametrize("kind", ["images", "tokens"])
def test_loader_cache_row_contract(kind, tmp_path):
    from mpi_pytorch_tpu.parallel.mesh import create_mesh
    from mpi_pytorch_tpu.train.trainer import build_device_cache, build_training

    if kind == "tokens":
        cfg = Config(
            model_name="lfm2_moe", model_config=TINY_LFM2, device_cache=True,
            validate=False, debug_sample_size=10, width=24, height=24, batch_size=8,
        )
        row, dtype = (25,), np.int32
    else:
        cfg = Config(
            debug=True, debug_sample_size=10, width=16, height=16, batch_size=8,
            num_classes=1000, input_dtype="bfloat16", device_cache=True,
        )
        import ml_dtypes

        row, dtype = (16, 16, 3), ml_dtypes.bfloat16
    cfg.validate_config()
    mesh = create_mesh(cfg.mesh)
    _, _, _, (manifest, _, loader) = build_training(cfg, mesh)
    assert loader.cache_row == (row, np.dtype(dtype))

    out = np.full((6, *row), 7, dtype)
    assert loader.fill_cache_rows(manifest, 3, 8, out) == set()
    np.testing.assert_array_equal(out[:5], _parent_cache_rows(kind, manifest, loader, 3, 8))
    assert (out[5] == 7).all()  # rows past hi - lo are the caller's
    assert loader.fill_cache_rows(manifest, 4, 4, out) == set()  # an empty range fills nothing
    assert (out[5] == 7).all()

    dataset, labels = build_device_cache(cfg, manifest, loader, mesh)
    assert dataset.dtype == np.dtype(dtype) and dataset.shape[1:] == row
    np.testing.assert_array_equal(
        np.asarray(dataset)[: len(manifest)],
        _parent_cache_rows(kind, manifest, loader, 0, len(manifest)),
    )
    np.testing.assert_array_equal(np.asarray(labels), manifest.labels.astype(np.int32))
