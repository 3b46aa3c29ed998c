"""Post-training int8 quantization for the serve path (ISSUE 11).

The eval head over 64 500 classes is byte-bound (``docs/roofline_*.json``;
RESULTS §4): the serve path's raw-speed ceiling is set by how many weight/
activation bytes move through the MXU, not by FLOPs. The bf16 fused head
already halved the f32 bytes; this module halves them AGAIN with
post-training int8 — the single biggest remaining lever for the serving
half, and it compounds multiplicatively with the fleet (N hosts × int8
throughput).

Three layers, smallest trusted base first:

1. **Per-channel weight quantization** (``quantize_per_channel``): every
   conv/dense kernel leaf becomes int8 values + a per-OUTPUT-channel f32
   dequant scale (``scale = max|w|/127`` over the channel's fan-in).
   Symmetric, no zero points — the MXU's signed-int8 contract.
2. **A quantized params tree** (``quantize_state``): the trained
   ``TrainState``'s kernels are replaced by int8 leaves; the state's
   ``apply_fn`` is wrapped so the forward dequantizes on the fly
   (``q.astype(f32) * scale`` fuses into each consumer under jit — the
   HBM-resident weights are int8, the dequant is a register-level cast).
   With ``keep_head_int8=True`` the classifier-head Dense kernel is NOT
   dequantized: it stays int8 for the fused kernel below, whose input
   activations are quantized with a scale **calibrated from a small
   sample batch** (``calibrate_head_act_scale``).
3. **The fused int8 head-predict kernel** (``head_predict_int8``): the
   sibling of ``ops/fused_head_ce.head_predict`` — int8 feats × int8 W
   on the MXU with int32 accumulation, dequantized per vocab block
   (``acc * (act_scale · w_scale[col]) + bias``) and fed through the SAME
   online softmax/argmax accumulator (``online_predict_update``), so the
   [B, V] logits never exist and the streamed weight bytes halve again
   vs the bf16 kernel. ``MPT_QHEAD_INTERPRET`` (or the existing
   ``MPT_HEAD_INTERPRET``) drives the real kernel through the Pallas
   interpreter on CPU; non-TPU backends without the gate fall back to
   ``head_predict_int8_reference`` — the exact-integer XLA computation
   the kernel is validated against (``tests/test_quantize.py``).

Int8 tiling note (TPU Mosaic): int8 operands tile at (32, 128) minimum —
the kernel keeps the whole [B, D] feats block and [D, 2048] weight blocks
resident, both well-shaped for the int8 MXU path. The compiled-TPU cells
are staged per the artifact discipline (ROADMAP item 6); this round
validates interpret-mode semantics only.

Accuracy is a measured contract, not an assumption: ``parity_probe`` runs
the SAME fixed sample through the bf16 and int8 predict paths and reports
top-1/top-5 agreement + max logit drift — the oracle behind
``evaluate --quantize-eval``, the serve-side startup parity stamp, and
the top-1 gates in the ``_dryrun_quant`` CI leg.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl

from mpi_pytorch_tpu.ops.fused_head_ce import (
    _BLOCK_V,
    _no_tiling,
    _predict_row_block,
    predict_compiler_params,
    online_predict_update,
)
from mpi_pytorch_tpu.ops.kernel_call import kernel_call

# ---------------------------------------------------------------------------
# per-channel weight quantization
# ---------------------------------------------------------------------------


def quantize_per_channel(w, axis: int = -1):
    """``w`` → (int8 values, f32 per-channel scale) with symmetric range
    [-127, 127] per OUTPUT channel (``axis``; the last dim for both Dense
    [in, out] and conv [kh, kw, in, out] kernels). ``dequantize`` inverts
    to within scale/2 per element — the round-trip bound the tests pin."""
    w = jnp.asarray(w, jnp.float32)
    axis = axis % w.ndim
    reduce_axes = tuple(i for i in range(w.ndim) if i != axis)
    amax = jnp.max(jnp.abs(w), axis=reduce_axes)
    # All-zero channels get scale 1/127 (quantize to exact zeros) instead
    # of a divide-by-zero; 1e-8 floors denormal channels.
    scale = jnp.maximum(amax, 1e-8) / 127.0
    shape = [1] * w.ndim
    shape[axis] = -1
    q = jnp.clip(jnp.round(w / scale.reshape(shape)), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize(q, scale, axis: int = -1, dtype=jnp.float32):
    """int8 values + per-channel scale → float tensor."""
    axis = axis % q.ndim
    shape = [1] * q.ndim
    shape[axis] = -1
    return q.astype(dtype) * scale.reshape(shape).astype(dtype)


def quantize_activations(x, act_scale):
    """Symmetric per-tensor int8 activation quantization with a CALIBRATED
    scale (``calibrate_head_act_scale``) — the other operand of the int8
    MXU matmul. Out-of-range activations saturate at ±127 (the calibration
    batch sets the clip point; saturation error shows up honestly in the
    parity probe, never as wraparound)."""
    return jnp.clip(
        jnp.round(x.astype(jnp.float32) / act_scale), -127, 127
    ).astype(jnp.int8)


# ---------------------------------------------------------------------------
# quantized params tree + dequantizing apply
# ---------------------------------------------------------------------------


def _path_str(path) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in path)


def _should_quantize(path, leaf) -> bool:
    # Conv/Dense kernels only: ndim >= 2 float leaves named 'kernel'.
    # Biases, BN scale/bias, and batch_stats stay f32 — they are a
    # rounding error of the byte budget and carry the calibration-free
    # precision the head's dequant chain leans on.
    keys = [str(getattr(k, "key", k)) for k in path]
    return (
        bool(keys)
        and keys[-1] == "kernel"
        and hasattr(leaf, "ndim")
        and leaf.ndim >= 2
        and jnp.issubdtype(jnp.asarray(leaf).dtype, jnp.floating)
    )


def quantize_params(params):
    """params tree → (same-structured tree with int8 kernels, {path:
    per-channel scale}). Non-kernel leaves pass through untouched."""
    scales: dict[str, jnp.ndarray] = {}

    def qleaf(path, leaf):
        if not _should_quantize(path, leaf):
            return leaf
        q, s = quantize_per_channel(leaf)
        scales[_path_str(path)] = s
        return q

    qtree = jax.tree_util.tree_map_with_path(qleaf, params)
    return qtree, scales


def head_kernel_key(scales: dict, qtree=None) -> str | None:
    """The quantized classifier-head DENSE kernel's scale key, or None.
    Matches the fused-head interceptor's filter (a module NAMED 'head';
    ``evaluate._make_predict_step``): segment 'head' + leaf 'kernel'.
    Conv heads (squeezenet) are ndim-4 kernels — the fused int8 path does
    not apply to them, so with ``qtree`` given they are excluded (and
    dequantize normally; the interceptor would never fire on them)."""
    for key in scales:
        seg = key.split("/")
        if seg[-1] == "kernel" and "head" in seg[:-1]:
            if qtree is not None:
                leaf = qtree
                for s in seg:
                    leaf = leaf[s]
                if leaf.ndim != 2:
                    continue
            return key
    return None


def dequantize_params(qtree, scales: dict, skip=frozenset(), dtype=jnp.float32):
    """Invert ``quantize_params`` inside the traced forward — per leaf a
    cast+multiply that XLA fuses into the consumer, so the weights resident
    in HBM are the int8 tree. ``skip``: scale keys left int8 (the fused
    head's kernel, consumed directly by ``head_predict_int8``)."""

    def dleaf(path, leaf):
        key = _path_str(path)
        if key in scales and key not in skip:
            return dequantize(leaf, scales[key], dtype=dtype)
        return leaf

    return jax.tree_util.tree_map_with_path(dleaf, qtree)


def quantize_state(state, *, keep_head_int8: bool = False, act_scale: float = 1.0):
    """A trained ``TrainState`` → its post-training-int8 twin.

    ``state.params`` becomes ``{"q": <int8-kernel tree>, "scale": {path:
    per-channel scale}, "act_scale": <f32 scalar>}`` and ``apply_fn`` is
    wrapped to dequantize on the fly, so EVERY existing consumer —
    ``eval_logits``, the predict steps, ``place_state_on_mesh``, AOT
    ``jit(...).lower(state, ...)`` — works on the quantized state
    unchanged: the quantized params are ordinary executable inputs, which
    is what lets a serve host hold a bf16 and an int8 executable set over
    the same predict function and switch between them without compiling.

    ``keep_head_int8``: leave the classifier-head Dense kernel int8 (the
    fused ``head_predict_int8`` path consumes it raw, with ``act_scale``
    quantizing its input features). Conv heads have no fused path and
    dequantize normally regardless.
    """
    qtree, scales = quantize_params(state.params)
    skip = frozenset()
    if keep_head_int8:
        hk = head_kernel_key(scales, qtree)
        if hk is not None:
            skip = frozenset({hk})
    orig_apply = state.apply_fn

    def quantized_apply(variables, *args, **kwargs):
        v = dict(variables)
        packed = v["params"]
        v["params"] = dequantize_params(packed["q"], packed["scale"], skip=skip)
        return orig_apply(v, *args, **kwargs)

    packed = {
        "q": qtree,
        "scale": scales,
        "act_scale": jnp.asarray(act_scale, jnp.float32),
    }
    return state.replace(params=packed, apply_fn=quantized_apply)


def fused_head_gate(cfg) -> bool:
    """ONE definition of "does this config serve/probe through the fused
    head kernels": the ``--fused-head-eval`` flag AND a backend that can
    run them (TPU, or the interpret test gates). Shared by the serve
    executables and the ``--quantize-eval`` oracle so the probe can never
    measure a different contract than the server actually runs."""
    from mpi_pytorch_tpu.utils.env import env_flag
    from mpi_pytorch_tpu.utils.hardware import tpu_backend

    return bool(
        cfg.fused_head_eval and (
            tpu_backend() or env_flag("MPT_HEAD_INTERPRET")
            or env_flag("MPT_QHEAD_INTERPRET")
        )
    )


def calibration_batch(cfg) -> np.ndarray:
    """THE fixed calibration/parity sample: ``--quantize-calib`` seeded
    raw-pixel images (``--seed``). One definition so the offline oracle
    and every serve host calibrate on the identical batch — their act
    scales (and therefore the probed contract) can never drift apart."""
    h, w = cfg.image_size
    rng = np.random.default_rng(cfg.seed)
    return rng.integers(
        0, 256, size=(cfg.quantize_calib, h, w, 3)
    ).astype(np.uint8)


def calibrate_head_act_scale(state, images, compute_dtype) -> float:
    """The int8 activation scale for the head's input features, measured
    on a small sample batch through the FLOAT model: ``max|feats| / 127``
    (symmetric per-tensor). Returns 1.0 when no Dense named 'head' fires
    (conv-head models — the fused int8 path does not apply there)."""
    from flax import linen as flax_nn

    from mpi_pytorch_tpu.train.step import ingest_images

    box = {}

    def grab(next_fn, args, kwargs, context):
        m = context.module
        if m.name == "head" and isinstance(m, flax_nn.Dense):
            box["feats"] = args[0]
            return jnp.zeros(args[0].shape[:-1] + (m.features,), jnp.float32)
        return next_fn(*args, **kwargs)

    with flax_nn.intercept_methods(grab):
        state.apply_fn(
            state.variables, ingest_images(jnp.asarray(images), compute_dtype),
            train=False,
        )
    if "feats" not in box:
        return 1.0
    amax = float(jnp.max(jnp.abs(box["feats"].astype(jnp.float32))))
    return max(amax, 1e-6) / 127.0


# ---------------------------------------------------------------------------
# the fused int8 head-predict kernel (sibling of fused_head_ce.head_predict)
# ---------------------------------------------------------------------------


def _predict_int8_kernel(
    labels_ref, feats_ref, w_ref, s_ref, b_ref,
    loss_ref, pred_ref, m_ref, l_ref, picked_ref, arg_ref,
):
    """Per (row block, vocab block): int8×int8 matmul on the MXU with
    int32 accumulation, per-channel dequant (``acc * scale + bias``), then
    the SAME online softmax/argmax update as the bf16 predict kernel —
    one shared definition (``online_predict_update``), two matmul dtypes."""
    j = pl.program_id(1)
    feats = feats_ref[...]  # [B, D] int8
    w = w_ref[...]  # [D, BV] int8
    acc = lax.dot_general(
        feats, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )  # exact: |acc| <= D * 127^2 << 2^31
    logits = acc.astype(jnp.float32) * s_ref[...] + b_ref[...]  # [B, BV] f32
    online_predict_update(
        j, pl.num_programs(1), logits, labels_ref,
        loss_ref, pred_ref, m_ref, l_ref, picked_ref, arg_ref,
    )


def _pad_int8(w_q, b, scale, block: int):
    """Pad the vocab dim to the block size: zero int8 columns, -inf bias
    (padded logits are ``0*scale + (-inf)`` — never the argmax, add
    ``exp(-inf)=0`` to l), unit scales."""
    v = w_q.shape[1]
    pad = (-v) % block
    if pad:
        w_q = jnp.pad(w_q, ((0, 0), (0, pad)))
        b = jnp.pad(b, (0, pad), constant_values=-jnp.inf)
        scale = jnp.pad(scale, (0, pad), constant_values=1.0)
    return w_q, b, scale, v


def head_predict_int8_reference(feats, w_q, b, labels, w_scale, act_scale):
    """Plain-XLA int8 reference/fallback: the exact integer matmul the
    kernel computes (int32 accumulate), explicit logits, CE + argmax.
    Shares ``quantize_activations`` and the combined-scale expression with
    the kernel, so in interpret mode the two paths agree BITWISE on the
    logits (and therefore exactly on the argmax)."""
    import optax

    q = quantize_activations(feats, act_scale)
    scale_v = (jnp.asarray(w_scale, jnp.float32) * act_scale).astype(jnp.float32)
    acc = lax.dot_general(
        q.astype(jnp.int32), w_q.astype(jnp.int32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32,
    )
    logits = acc.astype(jnp.float32) * scale_v + b.astype(jnp.float32)
    preds = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    valid = labels >= 0
    per = optax.softmax_cross_entropy_with_integer_labels(
        logits, jnp.maximum(labels, 0)
    )
    return jnp.where(valid, per, 0.0), preds


def _predict_int8_call(labels, feats_q, wp, sp, bp, *, block_r: int, interpret: bool):
    """One (per-shard) row-tiled kernel invocation over pre-padded
    W/scale/bias (the ``_predict_call`` shape with one extra operand)."""
    bsz, d = feats_q.shape
    row_spec = pl.BlockSpec((block_r, 1), lambda i, j: (i, 0))
    loss, pred, *_ = kernel_call(
        "head_predict_int8",
        _predict_int8_kernel,
        grid=(bsz // block_r, wp.shape[1] // _BLOCK_V),
        in_specs=[
            row_spec,  # labels
            pl.BlockSpec((block_r, d), lambda i, j: (i, 0)),  # int8 feat rows
            pl.BlockSpec((d, _BLOCK_V), lambda i, j: (0, j)),  # int8 W block
            pl.BlockSpec((1, _BLOCK_V), lambda i, j: (0, j)),  # scale block
            pl.BlockSpec((1, _BLOCK_V), lambda i, j: (0, j)),  # bias block
        ],
        out_specs=[row_spec] * 6,
        out_shape=[jax.ShapeDtypeStruct((bsz, 1), jnp.float32)] * 6,
        interpret=interpret,
        compiler_params=predict_compiler_params(interpret),
    )(labels.reshape(bsz, 1), feats_q, wp, sp.reshape(1, -1), bp.reshape(1, -1))
    return loss[:, 0], pred[:, 0].astype(jnp.int32)


def head_predict_int8(
    feats: jnp.ndarray,
    w_q: jnp.ndarray,
    b: jnp.ndarray,
    labels: jnp.ndarray,
    w_scale: jnp.ndarray,
    act_scale,
    interpret: bool | None = None,
    dp_mesh=None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(per-example CE [B] f32, argmax predictions [B] int32) of the
    int8-quantized head ``softmax(dequant(q(feats) @ w_q) + b)`` without
    materializing [B, V] — ``head_predict``'s int8 sibling, streaming the
    weight blocks through VMEM at HALF the bf16 kernel's bytes.

    ``feats`` is float (the model tower's output); its int8 quantization
    (calibrated ``act_scale``) happens here so the caller never handles
    int8 activations. ``w_q``/``w_scale`` come from
    ``quantize_per_channel`` (kept raw by ``quantize_state(...,
    keep_head_int8=True)``). ``interpret=None`` auto-selects: the Pallas
    interpreter under ``MPT_QHEAD_INTERPRET``/``MPT_HEAD_INTERPRET``
    (the CPU test gates), the compiled kernel on TPU, the XLA int8
    reference elsewhere. ``dp_mesh`` shard_maps the call over the data
    axis exactly like ``head_predict`` (W/scales/bias replicated)."""
    if interpret is None:
        from mpi_pytorch_tpu.utils.env import env_flag
        from mpi_pytorch_tpu.utils.hardware import tpu_backend

        if env_flag("MPT_QHEAD_INTERPRET") or env_flag("MPT_HEAD_INTERPRET"):
            interpret = True
        elif not tpu_backend():
            return head_predict_int8_reference(
                feats, w_q, b, labels, w_scale, act_scale
            )
        else:
            interpret = False
    n_data = 1
    if dp_mesh is not None:
        from mpi_pytorch_tpu.parallel.compat import axis_is_manual

        if not axis_is_manual(dp_mesh.axis_names[0]):
            n_data = dp_mesh.shape[dp_mesh.axis_names[0]]
    rows = feats.shape[0]
    if rows % n_data:
        _no_tiling(
            "head_predict_int8",
            f"batch rows {rows} not divisible by the data axis ({n_data})",
        )
        return head_predict_int8_reference(
            feats, w_q, b, labels, w_scale, act_scale
        )
    block_r = _predict_row_block(rows // n_data)
    if block_r is None:
        _no_tiling(
            "head_predict_int8",
            f"no power-of-two row tiling divides {rows // n_data} per-shard "
            "rows within the VMEM envelope",
        )
        return head_predict_int8_reference(
            feats, w_q, b, labels, w_scale, act_scale
        )
    labels = labels.astype(jnp.int32)
    feats_q = quantize_activations(feats, act_scale)
    scale_v = (jnp.asarray(w_scale, jnp.float32) * act_scale).astype(jnp.float32)
    wp, bp, sp, _ = _pad_int8(
        w_q, b.astype(jnp.float32), scale_v, _BLOCK_V
    )
    call = functools.partial(
        _predict_int8_call, block_r=block_r, interpret=interpret
    )
    if n_data > 1:
        from jax.sharding import PartitionSpec as P

        from mpi_pytorch_tpu.parallel.compat import shard_map

        axis = dp_mesh.axis_names[0]
        return shard_map(
            call,
            mesh=dp_mesh,
            in_specs=(P(axis), P(axis), P(), P(), P()),
            out_specs=(P(axis), P(axis)),
            check_vma=False,
        )(labels, feats_q, wp, sp, bp)
    return call(labels, feats_q, wp, sp, bp)


# ---------------------------------------------------------------------------
# the parity oracle (evaluate --quantize-eval + the serve startup stamp)
# ---------------------------------------------------------------------------


def parity_probe(
    state, qstate, mesh, compute_dtype, images, *,
    topk: int = 5, fused_head: bool = False,
) -> dict:
    """Run the SAME fixed sample through the bf16 and int8 predict paths
    and measure agreement — the reusable oracle behind ``evaluate
    --quantize-eval`` and the serve-side parity gates.

    Returns ``{"samples", "top1_agree", "top5_agree"}``: top-1 is the
    fraction of rows where both paths pick the same class; top-5 (None
    when topk < 5) the fraction where the bf16 argmax appears in the int8
    path's top 5. Metrics compare the SERVED contract (the fused paths
    when ``fused_head``), not an idealized one."""
    from mpi_pytorch_tpu.evaluate import _make_predict_step

    images = jnp.asarray(images)
    n = images.shape[0]
    labels = jnp.full((n,), -1, jnp.int32)
    batch = (images, labels)
    predict_ref = _make_predict_step(
        mesh, compute_dtype, fused_head=fused_head, topk=topk
    )
    predict_q = _make_predict_step(
        mesh, compute_dtype, fused_head=fused_head, topk=topk,
        int8_head=fused_head,
    )
    _, p_ref = predict_ref(state, batch)
    _, p_q = predict_q(qstate, batch)
    p_ref = np.asarray(jax.device_get(p_ref)).reshape(n, -1)
    p_q = np.asarray(jax.device_get(p_q)).reshape(n, -1)
    top1 = float(np.mean(p_ref[:, 0] == p_q[:, 0]))
    top5 = None
    if p_ref.shape[1] >= 5 and p_q.shape[1] >= 5:
        top5 = float(
            np.mean([p_ref[i, 0] in p_q[i, :5] for i in range(n)])
        )
    return {"samples": int(n), "top1_agree": round(top1, 4),
            "top5_agree": None if top5 is None else round(top5, 4)}


def max_logit_drift(state, qstate_plain, images, compute_dtype) -> float:
    """max |bf16-path logit − int8-path logit| over the sample — the
    scalar that turns "quantization error" into a number next to the
    agreement rate. ``qstate_plain`` must be a FULLY-dequantizing
    quantized state (``keep_head_int8=False``): with the head kept int8
    the plain forward has no comparable logits."""
    from mpi_pytorch_tpu.train.step import eval_logits

    images = jnp.asarray(images)
    l_ref = eval_logits(state, images, compute_dtype)
    l_q = eval_logits(qstate_plain, images, compute_dtype)
    return float(jnp.max(jnp.abs(l_ref - l_q)))
