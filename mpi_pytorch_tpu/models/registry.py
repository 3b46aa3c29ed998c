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
from mpi_pytorch_tpu.models.granite_hybrid import granite_vocab, granitemoehybrid
from mpi_pytorch_tpu.models.inception import inception_v3
from mpi_pytorch_tpu.models.lfm2 import lfm2_moe, lfm2_vocab
from mpi_pytorch_tpu.models.mobilenet import mobilenet_v2
from mpi_pytorch_tpu.models.nemotron_h import nemotron_h, nemotron_vocab
from mpi_pytorch_tpu.models.resnet import resnet18, resnet34
from mpi_pytorch_tpu.models.squeezenet import squeezenet1_0
from mpi_pytorch_tpu.models.vgg import vgg11_bn
from mpi_pytorch_tpu.models.vit import vit_b16, vit_moe_s16, vit_s16

# What the optional build flags are when nobody asked for them: a flag is
# "set" when its value differs. ``check_build_flags`` reads this, and the
# drivers never repeat it.
_FLAG_OFF = {
    "attn_impl": "full",
    "sp_strategy": "none",
    "ep_mesh": None,
    "qkv_fused": False,
    "remat_blocks": False,
    "stem_s2d": False,
    "fused_stem": False,
    "model_config": "",
    "pp_stages": 1,
}


@dataclasses.dataclass(frozen=True)
class ModelSpec:
    """What one architecture IS: everything config validation, the trainer
    and the serving zoo may ask about a model by name. Adding a model is its
    own file plus one entry of ``_REGISTRY``."""

    factory: Callable[..., nn.Module]
    # Canonical input size, mirroring models.py (:37,:45,:54,:63,:72,:81,:95).
    # Advisory as in the reference — the config's resize wins (main.py:64) —
    # unless ``required_size`` says otherwise. A token model's "size" is a
    # sequence length.
    input_size: int
    # The optional build flags this model accepts, of ``_FLAG_OFF``'s names
    # (``attn_impl`` apart: see ``attn_impls``) and ``dp_mesh``:
    #   sp_strategy   (with sp_mesh) the SP attention strategies in training
    #   qkv_fused     one [D, 3D] projection matmul (models/vit.py)
    #   ep_mesh       MoE MLPs, shardable over experts; the train loss then
    #                 includes the sown load-balance term
    #   remat_blocks  per-block nn.remat
    #   stem_s2d      the 7×7/s2 stem conv re-expressed exactly as a 4×4/s1
    #                 12-channel conv (models/resnet.py s2d_stem_*)
    #   fused_stem    the bn1+relu+maxpool Pallas pair (ops/fused_stem.py): the
    #                 7×7/s2/p3 + BN + relu + 3×3/s2/p1-pool stem family
    #   model_config  the factory reads ``--model-config``, one JSON object in
    #                 its source's own key names
    #   pp_stages     the trunk is a stack of depth-homogeneous blocks that
    #                 parallel/pp_vit.py can split into stages (not the MoE
    #                 variant: its sown aux loss cannot cross the pipeline's
    #                 shard_map, and alternating blocks break the stacking)
    #   dp_mesh       the module shard_maps a Mosaic call over the mesh's data
    #                 axis; never refused, handed over only where a kernel
    #                 uses it (``initialize_model``)
    flags: frozenset = frozenset()
    # The ``attn_impl`` values it takes; () = no attention, no such flag.
    attn_impls: tuple[str, ...] = ()
    # What a sample is. "images": ``[B, H, W, 3]`` in, ``[B, classes]`` out.
    # "tokens": ``int32 [B, S]`` ids in, ``[B, S, vocab]`` next-token logits
    # out; a sample is a packed sequence of S + 1 ids (inputs ``[:-1]``,
    # targets ``[1:]``; data/tokens.py, train/step.py::_gather_batch).
    sample: str = "images"
    # vocab(model_config) -> int for a token model, without building it (the
    # trainer checks its sequences against it). Lives beside the model.
    vocab: Callable[[str], int] | None = None
    batchnorm: bool = True  # the factory takes bn_axis_name
    required_size: int | None = None  # inception truly needs 299
    aux_logits: bool = False  # a second head in training (inception)
    # The fused stem is a MEASURED chip win here (docs/RESULTS.md §4d), so
    # ``fused_stem_default`` turns it on; densenet121 has the capability but
    # ships behind --fused-stem until its own A/B row lands.
    fused_stem_measured: bool = False

    def accepts(self, flag: str, value: Any) -> bool:
        if flag == "attn_impl":
            return value in self.attn_impls
        return flag in self.flags


_RESNET = dict(
    flags=frozenset({"remat_blocks", "stem_s2d", "fused_stem", "dp_mesh"}),
    fused_stem_measured=True,
)


def _vit(*more: str) -> dict:
    """What every vit_* entry shares, plus the flags only some take."""
    return dict(
        flags=frozenset({"sp_strategy", "qkv_fused", "dp_mesh", *more}),
        attn_impls=("full", "flash", "fused-small"),
        batchnorm=False,
    )


# The vit_* family is beyond reference parity (the reference has no
# attention); lfm2_moe, granitemoehybrid and nemotron_h are the token models.
_REGISTRY: dict[str, ModelSpec] = {
    "resnet18": ModelSpec(resnet18, 224, **_RESNET),
    "resnet34": ModelSpec(resnet34, 128, **_RESNET),
    "alexnet": ModelSpec(alexnet, 224, batchnorm=False),
    "vgg11_bn": ModelSpec(vgg11_bn, 224),
    "squeezenet1_0": ModelSpec(squeezenet1_0, 224, batchnorm=False),
    "densenet121": ModelSpec(
        densenet121, 224, flags=frozenset({"remat_blocks", "fused_stem", "dp_mesh"})
    ),
    "inception_v3": ModelSpec(inception_v3, 299, required_size=299, aux_logits=True),
    "mobilenet_v2": ModelSpec(mobilenet_v2, 224),
    "efficientnet_b0": ModelSpec(efficientnet_b0, 224),
    "vit_s16": ModelSpec(vit_s16, 224, **_vit("remat_blocks", "pp_stages")),
    "vit_b16": ModelSpec(vit_b16, 224, **_vit("remat_blocks", "pp_stages")),
    "vit_moe_s16": ModelSpec(vit_moe_s16, 224, **_vit("ep_mesh")),
    "lfm2_moe": ModelSpec(
        lfm2_moe, 128, flags=frozenset({"remat_blocks", "model_config"}),
        # causal, grouped heads: the single-pass kernel has no such form
        attn_impls=("full", "flash"), sample="tokens", vocab=lfm2_vocab,
        batchnorm=False,
    ),
    "granitemoehybrid": ModelSpec(
        granitemoehybrid, 256, flags=frozenset({"remat_blocks", "model_config"}),
        attn_impls=("full", "flash"), sample="tokens", vocab=granite_vocab,
        batchnorm=False,
    ),
    "nemotron_h": ModelSpec(
        nemotron_h, 128, flags=frozenset({"remat_blocks", "model_config"}),
        attn_impls=("full", "flash"), sample="tokens", vocab=nemotron_vocab,
        batchnorm=False,
    ),
}


@dataclasses.dataclass(frozen=True)
class ModelBundle:
    """Everything the training/eval drivers need to know about a model."""

    model: nn.Module
    input_size: int
    name: str
    has_aux_logits: bool
    trainable_mask: Any | None  # pytree of bools over params; None = all trainable


def available_models() -> tuple[str, ...]:
    return tuple(_REGISTRY)


def model_spec(model_name: str) -> ModelSpec:
    if model_name not in _REGISTRY:
        raise ValueError(
            f"unsupported model {model_name!r}; expected one of {tuple(_REGISTRY)}"
            " (parity with reference models.py:97-99, but raising instead of exit())"
        )
    return _REGISTRY[model_name]


def check_build_flags(model_name: str, **flags: Any) -> None:
    """THE owner of every "flag X does not apply to model Y" refusal:
    ``Config.validate_config`` and ``initialize_model`` both call it, with
    whichever of ``_FLAG_OFF``'s flags they hold. A flag left at its off
    value is never refused."""
    spec = model_spec(model_name)
    for flag, value in flags.items():
        if value == _FLAG_OFF[flag] or spec.accepts(flag, value):
            continue
        takers = [n for n, s in _REGISTRY.items() if s.accepts(flag, value)]
        shown = f"{flag}={value!r}" if isinstance(value, (str, bool, int)) else flag
        raise ValueError(
            f"{shown} does not apply to model {model_name!r}; the models that "
            f"accept it: {', '.join(takers) or 'none'}"
        )


def fused_stem_default(model_name: str) -> bool:
    """The benchmark harnesses' shared gate: fused stem ON for the
    measured-win members on TPU unless MPT_FUSED_STEM is set falsy — any
    case of '0'/'false'/'no'/'off' (``utils/env.py`` is the one definition;
    advisor r5: 'False'/'no' used to silently mean ON). The trainer/eval
    CLIs stay explicit via ``--fused-stem``."""
    from mpi_pytorch_tpu.utils.env import env_flag

    return (
        model_spec(model_name).fused_stem_measured
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
    spec = model_spec(model_name)
    given = dict(
        attn_impl=attn_impl, sp_strategy=sp_strategy, ep_mesh=ep_mesh,
        qkv_fused=qkv_fused, remat_blocks=remat_blocks, stem_s2d=stem_s2d,
        fused_stem=fused_stem, model_config=model_config,
    )
    check_build_flags(model_name, **given)
    if sp_strategy != "none" and sp_mesh is None:
        raise ValueError(
            f"sp_strategy={sp_strategy!r} requires sp_mesh (the mesh whose "
            "first axis shards the sequence)"
        )
    if fused_stem and bn_axis_name is not None:
        raise ValueError("fused_stem does not support sync-BN (bn_axis_name)")
    kw: dict[str, Any] = dict(dtype=dtype, param_dtype=param_dtype)
    kw.update({f: v for f, v in given.items() if v != _FLAG_OFF[f]})
    if spec.batchnorm:
        kw["bn_axis_name"] = bn_axis_name
    if sp_strategy != "none":
        kw["sp_mesh"] = sp_mesh
    if (
        dp_mesh is not None
        and "dp_mesh" in spec.flags
        and (fused_stem or (spec.attn_impls and attn_impl != "flash"))
    ):
        # Multi-chip: the module shard_maps its Mosaic call over this mesh's
        # data axis (ops/fused_stem.py, ops/fused_attention_small.py,
        # Multi-chip) — so it goes only where such a call exists: the fused
        # stem, or dense attention ('full' takes the same kernel as
        # 'fused-small' wherever the shape allows; 'flash' takes no mesh).
        kw["dp_mesh"] = dp_mesh
    return spec.factory(num_classes, **kw), spec.input_size


def init_variables(
    model: nn.Module, input_size: int, rng: jax.Array, batch_size: int = 1,
    tokens: bool = False,
) -> dict:
    """Initialize params + batch_stats. Uses train=True so architectures with
    train-only submodules (inception aux head) create their full param set.
    ``tokens``: the model's ``ModelSpec.sample`` is "tokens" and it is traced
    on ``int32 [batch, input_size]`` ids.

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
    spec = model_spec(model_name)
    size = image_size or spec.required_size or 128
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    variables = init_variables(model, size, rng, tokens=spec.sample == "tokens")

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
        has_aux_logits=spec.aux_logits,
        trainable_mask=mask,
    )
    return bundle, variables
