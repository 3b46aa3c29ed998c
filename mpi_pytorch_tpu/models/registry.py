"""Model factory — parity with the reference's ``initialize_model``
(``models.py:16-101``): dispatch on an architecture name, build the network
with a ``num_classes`` head, optionally freeze everything but the head
(``feature_extract``), optionally load pretrained weights; return
``(model, input_size)``.

Differences by design:
- invalid names raise ``ValueError`` instead of ``exit()`` (``models.py:97-99``);
- ``use_pretrained`` loads converted-from-torchvision weights from disk when
  available (tools/convert_torchvision.py) instead of downloading — this
  environment has no torchvision and no egress;
- ``feature_extract`` returns a *trainable-parameter mask* (params are
  immutable pytrees here; freezing is an optimizer property — see
  ``train/step.py`` optax masking — not a mutable ``requires_grad`` flag).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
from flax import linen as nn

from mpi_pytorch_tpu.models.alexnet import alexnet
from mpi_pytorch_tpu.models.common import head_filter
from mpi_pytorch_tpu.models.densenet import densenet121
from mpi_pytorch_tpu.models.efficientnet import efficientnet_b0
from mpi_pytorch_tpu.models.inception import inception_v3
from mpi_pytorch_tpu.models.lfm2 import lfm2_moe
from mpi_pytorch_tpu.models.mobilenet import mobilenet_v2
from mpi_pytorch_tpu.models.resnet import resnet18, resnet34
from mpi_pytorch_tpu.models.squeezenet import squeezenet1_0
from mpi_pytorch_tpu.models.vgg import vgg11_bn
from mpi_pytorch_tpu.models.vit import vit_b16, vit_moe_s16, vit_s16

# name → (factory, canonical input size). Input sizes mirror models.py
# (:37,:45,:54,:63,:72,:81,:95); as in the reference they are advisory — the
# config's resize wins (main.py:64) — except inception which truly needs 299.
# The vit_* family is beyond reference parity (the reference has no
# attention): its encoder can run the SP strategies inside training.
_REGISTRY: dict[str, tuple[Callable[..., nn.Module], int]] = {
    "resnet18": (resnet18, 224),
    "resnet34": (resnet34, 128),
    "alexnet": (alexnet, 224),
    "vgg11_bn": (vgg11_bn, 224),
    "squeezenet1_0": (squeezenet1_0, 224),
    "densenet121": (densenet121, 224),
    "inception_v3": (inception_v3, 299),
    "mobilenet_v2": (mobilenet_v2, 224),
    "efficientnet_b0": (efficientnet_b0, 224),
    "vit_s16": (vit_s16, 224),
    "vit_b16": (vit_b16, 224),
    "vit_moe_s16": (vit_moe_s16, 224),
    # A token model: its "input size" is a sequence length (TOKEN_MODELS).
    "lfm2_moe": (lfm2_moe, 128),
}

# Architectures with no BatchNorm (their factories take no bn_axis_name).
BN_FREE_MODELS = ("alexnet", "squeezenet1_0", "vit_s16", "vit_b16", "vit_moe_s16", "lfm2_moe")

# What a model consumes is the registry's to say, not a flag's: these take
# ``int32 [B, S]`` token ids and return ``[B, S, vocab]`` next-token logits; a
# sample is a packed sequence of S + 1 ids (inputs ``[:-1]``, targets
# ``[1:]``; data/tokens.py, train/step.py::_gather_batch). Every other model
# takes ``[B, H, W, 3]`` images and returns ``[B, classes]``.
TOKEN_MODELS = ("lfm2_moe",)

# Architectures whose factories read ``--model-config`` (a JSON object in
# their source's own key names) instead of one flag per key.
CONFIGURED_MODELS = ("lfm2_moe",)

# Architectures with an ``attn_impl`` choice (the vit family's three; a
# token model's causal ``full`` | ``flash``).
ATTN_IMPL_MODELS = ("vit_s16", "vit_b16", "vit_moe_s16", "lfm2_moe")

# Architectures whose factories accept sp_strategy/sp_mesh (sequence models
# that can run the SP attention strategies inside training).
SP_MODELS = ("vit_s16", "vit_b16", "vit_moe_s16")

# Architectures with MoE MLPs (their factories accept ep_mesh for expert
# parallelism; their train loss includes the sown load-balance aux term).
MOE_MODELS = ("vit_moe_s16",)

# Architectures whose trunk is a stack of depth-homogeneous blocks that
# pipeline parallelism can split into stages (parallel/pp_vit.py). The MoE
# variant is excluded: its sown aux-loss collection cannot cross the
# pipeline's shard_map boundary, and its alternating block structure breaks
# the stacked-stage layout.
PP_MODELS = ("vit_s16", "vit_b16")


@dataclasses.dataclass(frozen=True)
class ModelBundle:
    """Everything the training/eval drivers need to know about a model."""

    model: nn.Module
    input_size: int
    name: str
    has_aux_logits: bool
    trainable_mask: Any | None  # pytree of bools over params; None = all trainable


def token_vocab(model_name: str, model_config: str) -> int:
    """The vocabulary a token model is built with, without building it (the
    trainer checks its sequences against it)."""
    from mpi_pytorch_tpu.models.lfm2 import Lfm2Config

    assert model_name in TOKEN_MODELS, model_name
    return Lfm2Config.parse(model_config).vocab_size


def available_models() -> tuple[str, ...]:
    return tuple(_REGISTRY)


# Architectures whose factories accept remat_blocks (per-block nn.remat).
# THE owner of this capability — config validation and error messages defer here.
REMAT_BLOCKS_MODELS = ("resnet18", "resnet34", "densenet121", "vit_s16", "vit_b16", "lfm2_moe")


def supports_remat_blocks(model_name: str) -> bool:
    return model_name in REMAT_BLOCKS_MODELS


# Architectures whose factories accept stem_s2d (space-to-depth stem — the
# exact re-expression of the 7×7/s2 3-channel stem conv as a 4×4/s1
# 12-channel conv; models/resnet.py s2d_stem_input/s2d_stem_kernel).
S2D_MODELS = ("resnet18", "resnet34")

# Architectures whose factories accept fused_stem (the bn1+relu+maxpool
# Pallas kernel pair, ops/fused_stem.py — the identical 7×7/s2/p3 + BN +
# relu + 3×3/s2/p1-pool stem family; the fused module mirrors flax
# BatchNorm's variable tree so checkpoints interchange). densenet121's
# torchvision stem (features.conv0..pool0) is geometrically the same stem,
# so the kernel applies — see MEASURED_FUSED_STEM_MODELS for why its bench
# default differs.
FUSED_STEM_MODELS = ("resnet18", "resnet34", "densenet121")

# The subset whose fused stem is a MEASURED chip win (docs/RESULTS.md §4d:
# resnet18 24.7k → 26.1k img/s). densenet121 is capability-enabled but
# default-off: its stem tail is only ≈3% of its roofline bound and the
# step already runs at 1.11× bound (docs/RESULTS.md §4), so it ships
# behind --fused-stem until its own A/B row lands — the fused-head
# discipline (measure first, default only wins).
MEASURED_FUSED_STEM_MODELS = ("resnet18", "resnet34")


def fused_stem_default(model_name: str) -> bool:
    """The benchmark harnesses' shared gate: fused stem ON for the
    measured-win members on TPU unless MPT_FUSED_STEM is set falsy — any
    case of '0'/'false'/'no'/'off' (``utils/env.py`` is the one definition;
    advisor r5: 'False'/'no' used to silently mean ON). The trainer/eval
    CLIs stay explicit via ``--fused-stem``."""
    import jax

    from mpi_pytorch_tpu.utils.env import env_flag

    return (
        model_name in MEASURED_FUSED_STEM_MODELS
        and env_flag("MPT_FUSED_STEM", default=True)
        and jax.default_backend() == "tpu"
    )


def initialize_model(
    model_name: str,
    num_classes: int,
    feature_extract: bool = False,
    use_pretrained: bool = False,
    *,
    dtype: Any = jnp.float32,
    param_dtype: Any = jnp.float32,
    bn_axis_name: str | None = None,
    pretrained_dir: str = "pretrained",
    remat_blocks: bool = False,
    sp_strategy: str = "none",
    sp_mesh: Any = None,
    ep_mesh: Any = None,
    attn_impl: str = "full",
    stem_s2d: bool = False,
    fused_stem: bool = False,
    dp_mesh: Any = None,
    qkv_fused: bool = False,
    model_config: str = "",
) -> tuple[nn.Module, int]:
    """Reference-parity signature (``models.py:16``): returns (model, input_size)."""
    if model_name not in _REGISTRY:
        raise ValueError(
            f"unsupported model {model_name!r}; expected one of {tuple(_REGISTRY)}"
        )
    factory, input_size = _REGISTRY[model_name]
    kw: dict[str, Any] = dict(dtype=dtype, param_dtype=param_dtype)
    if model_name not in BN_FREE_MODELS:
        kw["bn_axis_name"] = bn_axis_name
    if attn_impl != "full":
        if model_name not in ATTN_IMPL_MODELS:
            raise ValueError(
                f"attn_impl={attn_impl!r} applies only to the attention "
                f"family ({', '.join(ATTN_IMPL_MODELS)}); {model_name!r} has no "
                "attention"
            )
        kw["attn_impl"] = attn_impl
    if model_config:  # config.validate_config has refused it for any other model
        kw["model_config"] = model_config
    if model_name in SP_MODELS and attn_impl != "flash" and dp_mesh is not None:
        # Multi-chip: the attention module shard_maps its Mosaic call over
        # this mesh's data axis (ops/fused_attention_small.py, Multi-chip) —
        # the same contract as the fused stem below. 'full' needs it as
        # 'fused-small' does: it takes the same kernel wherever the shape
        # allows.
        kw["dp_mesh"] = dp_mesh
    if qkv_fused:
        if model_name not in SP_MODELS:
            raise ValueError(
                f"qkv_fused applies only to the attention family "
                f"({', '.join(SP_MODELS)}); {model_name!r} has no attention"
            )
        kw["qkv_fused"] = True
    if sp_strategy != "none":
        if model_name not in SP_MODELS:
            raise ValueError(
                f"sp_strategy={sp_strategy!r} applies only to sequence models "
                f"({', '.join(SP_MODELS)}); {model_name!r} has no sequence axis"
            )
        if sp_mesh is None:
            raise ValueError(
                f"sp_strategy={sp_strategy!r} requires sp_mesh (the mesh whose "
                "first axis shards the sequence)"
            )
        kw["sp_strategy"] = sp_strategy
        kw["sp_mesh"] = sp_mesh
    if ep_mesh is not None:
        if model_name not in MOE_MODELS:
            raise ValueError(
                f"ep_mesh applies only to MoE models ({', '.join(MOE_MODELS)}); "
                f"{model_name!r} has no experts to shard"
            )
        kw["ep_mesh"] = ep_mesh
    if remat_blocks:
        if not supports_remat_blocks(model_name):
            raise ValueError(
                f"remat='blocks' is not implemented for {model_name!r} "
                f"(supported: {', '.join(REMAT_BLOCKS_MODELS)}); "
                "use remat='full' or 'none'"
            )
        kw["remat_blocks"] = True
    if stem_s2d:
        if model_name not in S2D_MODELS:
            raise ValueError(
                f"stem_s2d is only implemented for the 7×7-stem family "
                f"({', '.join(S2D_MODELS)}); {model_name!r} has no such stem"
            )
        kw["stem_s2d"] = True
    if fused_stem:
        if model_name not in FUSED_STEM_MODELS:
            raise ValueError(
                f"fused_stem is only implemented for the 7×7-stem family "
                f"({', '.join(FUSED_STEM_MODELS)}); {model_name!r} has no such stem"
            )
        if bn_axis_name is not None:
            raise ValueError("fused_stem does not support sync-BN (bn_axis_name)")
        kw["fused_stem"] = True
        if dp_mesh is not None:
            # Multi-chip: the stem module shard_maps its Mosaic call over
            # this mesh's data axis (ops/fused_stem.py, Multi-chip). Only
            # meaningful with fused_stem — silently ignored otherwise.
            kw["dp_mesh"] = dp_mesh
    model = factory(num_classes, **kw)
    return model, input_size


def init_variables(
    model: nn.Module, input_size: int, rng: jax.Array, batch_size: int = 1,
    tokens: bool = False,
) -> dict:
    """Initialize params + batch_stats. Uses train=True so architectures with
    train-only submodules (inception aux head) create their full param set.
    ``tokens``: the model is one of ``TOKEN_MODELS`` and is traced on
    ``int32 [batch, input_size]`` ids.

    Jitted so XLA dead-code-eliminates the traced forward pass — only the
    parameter initializers actually run (orders of magnitude faster than
    eager init for the deep architectures, especially on CPU test meshes)."""
    if tokens:
        dummy = jnp.zeros((batch_size, input_size), jnp.int32)
    else:
        dummy = jnp.zeros((batch_size, input_size, input_size, 3), jnp.float32)
    p_rng, d_rng = jax.random.split(rng)
    init_fn = jax.jit(lambda rngs, x: model.init(rngs, x, train=True))
    variables = jax.device_get(init_fn({"params": p_rng, "dropout": d_rng}, dummy))
    # MoE models sow their load-balance aux into a "losses" collection even
    # at init; it is a per-apply output, not model state — drop it.
    variables.pop("losses", None)
    variables.pop("counters", None)  # per-apply too (models/lfm2.py MoE.sow)
    return variables


def create_model_bundle(
    model_name: str,
    num_classes: int,
    feature_extract: bool = False,
    use_pretrained: bool = False,
    *,
    rng: jax.Array | None = None,
    image_size: int | None = None,
    dtype: Any = jnp.float32,
    param_dtype: Any = jnp.float32,
    bn_axis_name: str | None = None,
    pretrained_dir: str = "pretrained",
    remat_blocks: bool = False,
    sp_strategy: str = "none",
    sp_mesh: Any = None,
    ep_mesh: Any = None,
    attn_impl: str = "full",
    stem_s2d: bool = False,
    fused_stem: bool = False,
    dp_mesh: Any = None,
    qkv_fused: bool = False,
    model_config: str = "",
) -> tuple[ModelBundle, dict]:
    """Full-fat factory: returns the bundle plus initialized variables."""
    model, canonical = initialize_model(
        model_name, num_classes, feature_extract, use_pretrained,
        dtype=dtype, param_dtype=param_dtype, bn_axis_name=bn_axis_name,
        remat_blocks=remat_blocks, sp_strategy=sp_strategy, sp_mesh=sp_mesh,
        ep_mesh=ep_mesh, attn_impl=attn_impl, stem_s2d=stem_s2d,
        fused_stem=fused_stem, dp_mesh=dp_mesh, qkv_fused=qkv_fused,
        model_config=model_config,
    )
    size = image_size or (299 if model_name == "inception_v3" else 128)
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    variables = init_variables(model, size, rng, tokens=model_name in TOKEN_MODELS)

    if use_pretrained:
        from mpi_pytorch_tpu.models.pretrained import load_pretrained

        variables = load_pretrained(
            model_name, variables, pretrained_dir, stem_s2d=stem_s2d
        )

    mask = None
    if feature_extract:
        mask = jax.tree_util.tree_map_with_path(
            lambda path, _: head_filter([getattr(k, "key", str(k)) for k in path]),
            variables["params"],
        )
    bundle = ModelBundle(
        model=model,
        input_size=size,
        name=model_name,
        has_aux_logits=(model_name == "inception_v3"),
        trainable_mask=mask,
    )
    return bundle, variables
