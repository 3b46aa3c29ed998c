"""Pallas TPU kernel: classifier-head matmul fused into softmax cross-entropy.

The reference computes ``logits = fc(features)`` then ``CrossEntropyLoss``
(``models.py:36`` + ``main.py:150``) over a 64 500-class head (``utils.py:
39``). Unfused, the [B, V] logits tensor round-trips HBM several times: at
B=512 that is 512×64500 f32 ≈ 132 MB written by the matmul, re-read by the
softmax, and the [B, V] gradient written and re-read on the way back — and
this repo's zoo computes the head in float32, so none of it rides the bf16
MXU path. Measured cost on one v5e chip: 2.84 ms of a 24.5 ms resnet18 step
(the head's 101 GFLOP would take 0.51 ms at peak — ~18% efficiency).

This kernel streams the head weights through VMEM in vocab blocks and never
materializes [B, V] anywhere:

- forward: per vocab block, ``logits_blk = feats @ W_blk + b_blk`` on the
  MXU (bf16 in, f32 accumulate), online-softmax update of running (m, l)
  and the picked label logit; loss = log(l) + m - picked.
- backward: recomputes each ``logits_blk`` (one extra B·D·V matmul — FLOPs
  are cheap here, HBM is not), forms the block softmax from the saved
  (m, l), and produces all three grads in the same pass: ``dW_blk =
  featsᵀ @ dlog_blk``, ``db_blk = Σ_B dlog_blk``, and ``dfeats +=
  dlog_blk @ W_blkᵀ`` accumulated across the sequential TPU grid.

Rows with label < 0 (batch padding, trainer.pad_batch) get loss 0 and zero
gradient. Non-TPU backends fall back to the plain XLA computation, which is
also the reference the Pallas path is validated against in
tests/test_fused_head_ce.py (interpret mode).

**Measured verdict (v5e, B=512, D=512, V=64500, fwd+bwd per iter):**

    XLA f32 head + optax CE:   2.96 ms   (the zoo's former default)
    XLA bf16 head + optax CE:  2.38 ms   ← production path (models/*.py)
    this Pallas kernel:        3.39 ms   (fwd 1.72 / bwd 1.67)

XLA's producer-consumer fusion plus its own online softmax already keep the
unfused path bandwidth-efficient, and at D=512 the matmuls are small enough
that Mosaic's sequential accumulator grid cannot beat them ("don't
hand-schedule what the compiler already does"). The production win extracted
from this investigation was switching the head matmul to the compute dtype —
bf16 on the MXU, −0.58 ms/step — which is wired into every zoo model. The
kernel stays as the validated template for genuinely XLA-infeasible fusions
(grads match XLA to 7e-6; variants measured and rejected: f32 W streaming
0.80×, shared-residual bf16 W 0.86×, unpadded grad outputs → Mosaic
mis-executes partial final blocks, fwd block 4096 → scoped-VMEM OOM).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from mpi_pytorch_tpu.ops.kernel_call import kernel_call

_BLOCK_V = 2048  # fwd vocab tile; [B, BV] f32 = 4 MB at B=512 (4096 OOMs scoped VMEM)
# The backward pass holds ~5 live [B, BV] f32 temporaries (logits, softmax,
# onehot, dlog, dW) plus feats/dfeats — 2048 blows the 16 MB scoped-VMEM
# limit at B=512 (measured: 23.4 MB), so it tiles half as wide.
_BLOCK_V_BWD = 1024
# head_predict's per-ROW-BLOCK VMEM envelope: beyond this many rows the
# [rows, _BLOCK_V] f32 logits block exceeds scoped VMEM (measured at 4096).
# Larger batches are ROW-TILED: the wrapper runs a (row-block, vocab-block)
# grid with ≤ this many rows resident per step, so B=4096+ streams through
# the kernel instead of compile-rejecting (a batch with no usable row
# tiling raises on a TPU; off-TPU the XLA reference is the path anyway).
PREDICT_MAX_ROWS = 1024
# Scoped VMEM for the predict kernels. A full 1 024-row block needs 17.2 MiB
# on the row-tiled grid — Mosaic (libtpu 0.0.34, v5e) refused rows=4096
# under the 16 MiB default, 1.21 MiB short — so the call asks for headroom
# instead of shrinking the block (v5e has 128 MiB physical VMEM).
PREDICT_VMEM_LIMIT = 32 * 1024 * 1024


def predict_compiler_params(interpret: bool):
    """``compiler_params`` for the predict kernels' ``pallas_call``s (bf16
    here, int8 in ops/quantize.py): the VMEM headroom above, nothing under
    the interpreter."""
    if interpret:
        return None
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(vmem_limit_bytes=PREDICT_VMEM_LIMIT)


def _fwd_kernel(labels_ref, feats_ref, w_ref, b_ref, loss_ref, m_ref, l_ref, picked_ref):
    """Grid: (num_v_blocks,). m/l/picked outputs alias one block across the
    sequential grid, acting as accumulators."""
    j = pl.program_id(0)
    feats = feats_ref[...]  # [B, D] bf16
    w = w_ref[...]  # [D, BV] bf16
    logits = lax.dot_general(
        feats, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) + b_ref[...].astype(jnp.float32)  # [B, BV] f32
    b_rows, bv = logits.shape

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        picked_ref[...] = jnp.zeros_like(picked_ref)

    m_prev = m_ref[...]  # [B, 1]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=1, keepdims=True))
    l_ref[...] = l_ref[...] * jnp.exp(m_prev - m_new) + jnp.sum(
        jnp.exp(logits - m_new), axis=1, keepdims=True
    )
    m_ref[...] = m_new

    labels = labels_ref[...]  # [B, 1] int32
    local = labels - j * bv
    cols = lax.broadcasted_iota(jnp.int32, (b_rows, bv), 1)
    hit = cols == local  # all-false when the label is outside this block
    picked_ref[...] += jnp.sum(jnp.where(hit, logits, 0.0), axis=1, keepdims=True)

    @pl.when(j == pl.num_programs(0) - 1)
    def _finish():
        valid = labels >= 0
        loss = jnp.log(l_ref[...]) + m_ref[...] - picked_ref[...]
        loss_ref[...] = jnp.where(valid, loss, 0.0)


def _bwd_kernel(
    labels_ref, feats_ref, w_ref, b_ref, m_ref, l_ref, g_ref,
    dfeats_ref, dw_ref, db_ref,
):
    j = pl.program_id(0)
    feats = feats_ref[...]  # [B, D]
    w = w_ref[...]  # [D, BV] bf16
    logits = lax.dot_general(
        feats, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) + b_ref[...].astype(jnp.float32)
    b_rows, bv = logits.shape

    labels = labels_ref[...]  # [B, 1]
    valid = labels >= 0
    softmax = jnp.exp(logits - m_ref[...]) / l_ref[...]
    local = labels - j * bv
    cols = lax.broadcasted_iota(jnp.int32, (b_rows, bv), 1)
    onehot = (cols == local).astype(jnp.float32)
    g = jnp.where(valid, g_ref[...], 0.0)  # [B, 1]
    dlog = (softmax - onehot) * g  # [B, BV] f32

    # dW_blk = featsᵀ @ dlog  → [D, BV] (bf16 operands, f32 accumulate —
    # the standard mixed-precision gradient matmul)
    dw_ref[...] = lax.dot_general(
        feats, dlog.astype(feats.dtype), (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(dw_ref.dtype)
    db_ref[...] = jnp.sum(dlog, axis=0, keepdims=True).astype(db_ref.dtype)

    # dfeats += dlog @ W_blkᵀ → [B, D], accumulated over the sequential grid
    contrib = lax.dot_general(
        dlog.astype(feats.dtype), w, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(j == 0)
    def _init():
        dfeats_ref[...] = jnp.zeros_like(dfeats_ref)

    dfeats_ref[...] += contrib


def _pad_wb(
    w: jnp.ndarray, b: jnp.ndarray, block: int, dtype=jnp.bfloat16
) -> tuple[jnp.ndarray, jnp.ndarray, int]:
    """Pad the vocab dim to the block size and cast W to the kernel compute
    dtype (bf16 for the production head: streaming W through VMEM at half
    the bytes is where the fusion's bandwidth win comes from — W is the one
    large operand; f32 when the caller runs an f32-compute model)."""
    v = w.shape[1]
    pad = (-v) % block
    if pad:
        # zero W columns + -inf bias → padded logits are -inf: they add
        # exp(-inf)=0 to l and can never be a label or receive gradient.
        w = jnp.pad(w, ((0, 0), (0, pad)))
        b = jnp.pad(b, (0, pad), constant_values=-jnp.inf)
    return w.astype(dtype), b, v


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _fused_head_ce(feats, w, b, labels, interpret=False):
    return _fused_head_ce_impl(feats, w, b, labels, interpret)


def _fwd_impl(feats, w, b, labels, interpret):
    # Pad to the fwd block multiple (2048); the bwd block (1024) divides it,
    # so the SAME padded/cast W is reused by the backward pass via residuals
    # — one f32→bf16 cast of the 132 MB weight matrix per step, not two.
    wp, bp, v = _pad_wb(w, b, _BLOCK_V)
    bsz, d = feats.shape
    grid = wp.shape[1] // _BLOCK_V
    out = kernel_call(
        "head_ce_fwd",
        _fwd_kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((bsz, 1), lambda j: (0, 0)),  # labels
            pl.BlockSpec((bsz, d), lambda j: (0, 0)),  # feats (resident)
            pl.BlockSpec((d, _BLOCK_V), lambda j: (0, j)),  # W block
            pl.BlockSpec((1, _BLOCK_V), lambda j: (0, j)),  # bias block
        ],
        out_specs=[
            pl.BlockSpec((bsz, 1), lambda j: (0, 0)),  # loss
            pl.BlockSpec((bsz, 1), lambda j: (0, 0)),  # m
            pl.BlockSpec((bsz, 1), lambda j: (0, 0)),  # l
            pl.BlockSpec((bsz, 1), lambda j: (0, 0)),  # picked
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, 1), jnp.float32),
            jax.ShapeDtypeStruct((bsz, 1), jnp.float32),
            jax.ShapeDtypeStruct((bsz, 1), jnp.float32),
            jax.ShapeDtypeStruct((bsz, 1), jnp.float32),
        ],
        interpret=interpret,
    )(labels.reshape(bsz, 1), feats, wp, bp.reshape(1, -1))
    return out[0][:, 0], out[1], out[2], wp, bp, v


def _fused_head_ce_impl(feats, w, b, labels, interpret):
    loss, _, _, _, _, _ = _fwd_impl(feats, w, b, labels, interpret)
    return loss


def _fwd_rule(feats, w, b, labels, interpret):
    loss, m, l, wp, bp, v = _fwd_impl(feats, w, b, labels, interpret)
    return loss, (feats, wp, bp, labels, m, l, v)


def _bwd_rule(interpret, residuals, g):
    feats, wp, bp, labels, m, l, v = residuals
    bsz, d = feats.shape
    grid = wp.shape[1] // _BLOCK_V_BWD
    dfeats, dw, db = kernel_call(
        "head_ce_bwd",
        _bwd_kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((bsz, 1), lambda j: (0, 0)),  # labels
            pl.BlockSpec((bsz, d), lambda j: (0, 0)),  # feats
            pl.BlockSpec((d, _BLOCK_V_BWD), lambda j: (0, j)),  # W block
            pl.BlockSpec((1, _BLOCK_V_BWD), lambda j: (0, j)),  # bias block
            pl.BlockSpec((bsz, 1), lambda j: (0, 0)),  # m
            pl.BlockSpec((bsz, 1), lambda j: (0, 0)),  # l
            pl.BlockSpec((bsz, 1), lambda j: (0, 0)),  # g
        ],
        out_specs=[
            pl.BlockSpec((bsz, d), lambda j: (0, 0)),  # dfeats (accumulator)
            pl.BlockSpec((d, _BLOCK_V_BWD), lambda j: (0, j)),  # dW
            pl.BlockSpec((1, _BLOCK_V_BWD), lambda j: (0, j)),  # db
        ],
        # Cotangents must match the primal avals: the public wrapper casts
        # w/b to f32 before the custom_vjp boundary, so grads are f32.
        # (Unpadded [·, v] out_shapes were tried to skip the slice-copy of
        # the padded gradient; Pallas mis-executes the partial final block
        # here — TPU abort — so the outputs stay block-aligned.)
        out_shape=[
            jax.ShapeDtypeStruct((bsz, d), jnp.float32),
            jax.ShapeDtypeStruct((d, wp.shape[1]), jnp.float32),
            jax.ShapeDtypeStruct((1, wp.shape[1]), jnp.float32),
        ],
        interpret=interpret,
    )(
        labels.reshape(bsz, 1), feats, wp, bp.reshape(1, -1), m, l,
        g.reshape(bsz, 1).astype(jnp.float32),
    )
    return dfeats.astype(feats.dtype), dw[:, :v], db[0, :v], None


_fused_head_ce.defvjp(_fwd_rule, _bwd_rule)


def online_predict_update(
    j, n_programs, logits, labels_ref,
    loss_ref, pred_ref, m_ref, l_ref, picked_ref, arg_ref,
):
    """The shared per-vocab-block accumulator update of the predict
    kernels: online softmax (m, l), running argmax, and the picked label
    logit, finalized into (loss, pred) on the last block. ``logits`` is
    this block's [B, BV] f32 tile; how it was produced is the kernel's
    business — the bf16 MXU matmul in ``_predict_kernel`` below, or the
    int8×int8→int32 dequantized matmul in ``ops/quantize.py``'s sibling.
    One definition so the two kernels cannot drift on the subtle parts
    (tie convention, padding-row zeroing, the f32 index trick)."""
    b_rows, bv = logits.shape

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        picked_ref[...] = jnp.zeros_like(picked_ref)
        arg_ref[...] = jnp.zeros_like(arg_ref)

    block_max = jnp.max(logits, axis=1, keepdims=True)  # [B, 1]
    # First column attaining the block max — jnp.argmax's tie convention.
    # All-f32 arithmetic: an int32 min-reduce in this kernel crashes the
    # TPU compile helper; vocab indices are exact in f32 up to 2^24.
    cols_f = lax.broadcasted_iota(jnp.int32, (b_rows, bv), 1).astype(jnp.float32)
    first_hit = jnp.min(
        jnp.where(logits == block_max, cols_f, float(bv)), axis=1, keepdims=True
    )
    m_prev = m_ref[...]
    # Strict >: on a cross-block tie the EARLIER block keeps the argmax,
    # matching argmax over the concatenated vocab.
    better = block_max > m_prev
    arg_ref[...] = jnp.where(better, j * bv + first_hit, arg_ref[...])
    m_new = jnp.maximum(m_prev, block_max)
    l_ref[...] = l_ref[...] * jnp.exp(m_prev - m_new) + jnp.sum(
        jnp.exp(logits - m_new), axis=1, keepdims=True
    )
    m_ref[...] = m_new

    labels = labels_ref[...]  # [B, 1] int32
    local = labels - j * bv
    cols = lax.broadcasted_iota(jnp.int32, (b_rows, bv), 1)  # label hit only
    hit = cols == local
    picked_ref[...] += jnp.sum(jnp.where(hit, logits, 0.0), axis=1, keepdims=True)

    @pl.when(j == n_programs - 1)
    def _finish():
        valid = labels >= 0
        loss = jnp.log(l_ref[...]) + m_ref[...] - picked_ref[...]
        loss_ref[...] = jnp.where(valid, loss, 0.0)
        pred_ref[...] = arg_ref[...]


def _predict_kernel(
    labels_ref, feats_ref, w_ref, b_ref,
    loss_ref, pred_ref, m_ref, l_ref, picked_ref, arg_ref,
):
    """Inference sibling of ``_fwd_kernel``: same online softmax, plus a
    running ARGMAX (the predictions-pass output) — so eval accuracy, loss,
    and per-image predictions all come out of one pass that never
    materializes [B, V]. Grid: (num_row_blocks, num_v_blocks) — the vocab
    axis is the MINOR (fastest) grid dim, so for each row block the
    m/l/picked/arg outputs alias one block across the sequential vocab
    sweep as accumulators, then the grid advances to the next row block
    (the B=4096+ row tiling; the single-block case is grid (1, n_v))."""
    j = pl.program_id(1)
    feats = feats_ref[...]  # [B, D] bf16
    w = w_ref[...]  # [D, BV] bf16
    logits = lax.dot_general(
        feats, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) + b_ref[...].astype(jnp.float32)  # [B, BV] f32
    online_predict_update(
        j, pl.num_programs(1), logits, labels_ref,
        loss_ref, pred_ref, m_ref, l_ref, picked_ref, arg_ref,
    )


def _no_tiling(kernel: str, reason: str) -> None:
    """A shape the predict kernels cannot tile. On a TPU the caller asked
    for the kernel and must not quietly get the XLA reference (logits
    materialized) instead — raise and name the shape; elsewhere the
    reference is the path anyway and the caller falls through to it."""
    from mpi_pytorch_tpu.utils.hardware import tpu_backend

    if tpu_backend():
        raise ValueError(f"{kernel}: {reason}")


def head_predict_reference(feats, w, b, labels):
    """Plain-XLA reference/fallback: explicit logits, CE + argmax."""
    logits = (feats.astype(jnp.float32) @ w.astype(jnp.float32)) + b.astype(jnp.float32)
    preds = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return head_ce_reference(feats, w, b, labels), preds


def _predict_row_block(rows: int) -> int | None:
    """Rows resident per grid step: the whole batch when it fits the
    measured per-block envelope, else the largest power-of-two divisor
    ≤ PREDICT_MAX_ROWS (None = no usable tiling)."""
    if rows <= PREDICT_MAX_ROWS:
        return rows
    for rb in (1024, 512, 256, 128, 64, 32, 16, 8):
        if rb <= PREDICT_MAX_ROWS and rows % rb == 0:
            return rb
    return None


def _predict_call(labels, feats, wp, bp, *, block_r: int, interpret: bool):
    """One (per-shard) row-tiled kernel invocation over pre-padded W/bias."""
    bsz, d = feats.shape
    row_spec = pl.BlockSpec((block_r, 1), lambda i, j: (i, 0))
    loss, pred, *_ = kernel_call(
        "head_predict",
        _predict_kernel,
        grid=(bsz // block_r, wp.shape[1] // _BLOCK_V),
        in_specs=[
            row_spec,  # labels
            pl.BlockSpec((block_r, d), lambda i, j: (i, 0)),  # feats rows
            pl.BlockSpec((d, _BLOCK_V), lambda i, j: (0, j)),  # W block
            pl.BlockSpec((1, _BLOCK_V), lambda i, j: (0, j)),  # bias block
        ],
        # loss/pred/m/l/picked/arg: per-row-block accumulators (the vocab
        # grid dim is minor, so each aliases one block across the v sweep).
        out_specs=[row_spec] * 6,
        out_shape=[jax.ShapeDtypeStruct((bsz, 1), jnp.float32)] * 6,
        interpret=interpret,
        compiler_params=predict_compiler_params(interpret),
    )(labels.reshape(bsz, 1), feats, wp, bp.reshape(1, -1))
    return loss[:, 0], pred[:, 0].astype(jnp.int32)


def head_predict(
    feats: jnp.ndarray,
    w: jnp.ndarray,
    b: jnp.ndarray,
    labels: jnp.ndarray,
    interpret: bool | None = None,
    dp_mesh=None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(per-example CE [B] f32, argmax predictions [B] int32) of
    ``softmax(feats @ w + b)`` without materializing [B, V] — the
    inference pass of the reference's predictor ranks
    (``evaluation_pipeline.py:149-158``) as one VMEM-streaming kernel.
    Forward-only (no VJP): the predictions path never backpropagates.

    Batches beyond PREDICT_MAX_ROWS are ROW-TILED (a (rows, vocab) grid
    with the vocab sweep minor), so B=4096 streams through the kernel —
    the former compile-rejection envelope is now an internal loop.

    ``dp_mesh``: the eval mesh. When its leading (data) axis has >1
    device, the call is ``shard_map``-partitioned over that axis — each
    chip runs the Mosaic call on its own row shard (a Mosaic custom call
    has no GSPMD partitioning rule; unwrapped, XLA would all-gather the
    features and instantiate the kernel at the global batch). W/b stay
    replicated inside the wrapper (a TP-sharded head is gathered once —
    correctness over speed for that corner).

    Argmax/compute-dtype note: the kernel matmuls in the FEATURE dtype —
    bf16×bf16→f32 for the production bf16 head; an f32-compute model keeps
    exact f32 semantics (no silent bf16 downcast). Under bf16, near-ties
    within rounding can pick a different index than an f32-matmul argmax
    would — same caveat as the XLA bf16 head (models/resnet.py head dtype
    note).
    """
    if interpret is None:
        from mpi_pytorch_tpu.utils.env import env_flag
        from mpi_pytorch_tpu.utils.hardware import tpu_backend

        # MPT_HEAD_INTERPRET=1 drives the REAL kernel through the Pallas
        # interpreter on CPU (mirrors MPT_STEM_INTERPRET — how the driver-
        # level tests exercise the kernel + shard_map path without a TPU).
        if env_flag("MPT_HEAD_INTERPRET"):
            interpret = True
        elif not tpu_backend():
            return head_predict_reference(feats, w, b, labels)
        else:
            interpret = False
    n_data = 1
    if dp_mesh is not None:
        from mpi_pytorch_tpu.parallel.compat import axis_is_manual

        # Already inside a shard_map over the data axis → the rows are
        # per-shard and nesting over the same axis is an error.
        if not axis_is_manual(dp_mesh.axis_names[0]):
            n_data = dp_mesh.shape[dp_mesh.axis_names[0]]
    rows = feats.shape[0]
    if rows % n_data:
        _no_tiling(
            "head_predict",
            f"batch rows {rows} not divisible by the data axis ({n_data})",
        )
        return head_predict_reference(feats, w, b, labels)
    block_r = _predict_row_block(rows // n_data)
    if block_r is None:
        _no_tiling(
            "head_predict",
            f"no power-of-two row tiling divides {rows // n_data} per-shard "
            f"rows within the {PREDICT_MAX_ROWS}-row VMEM envelope",
        )
        return head_predict_reference(feats, w, b, labels)
    labels = labels.astype(jnp.int32)
    # Compute dtype = the feature dtype: bf16 halves W's VMEM stream (the
    # bandwidth win) for the production bf16 head; f32 models stay f32.
    kdtype = jnp.bfloat16 if feats.dtype == jnp.bfloat16 else jnp.float32
    wp, bp, v = _pad_wb(w, b, _BLOCK_V, dtype=kdtype)
    feats = feats.astype(kdtype)
    call = functools.partial(_predict_call, block_r=block_r, interpret=interpret)
    if n_data > 1:
        from jax.sharding import PartitionSpec as P

        from mpi_pytorch_tpu.parallel.compat import shard_map

        axis = dp_mesh.axis_names[0]
        return shard_map(
            call,
            mesh=dp_mesh,
            in_specs=(P(axis), P(axis), P(), P()),
            out_specs=(P(axis), P(axis)),
            check_vma=False,
        )(labels, feats, wp, bp)
    return call(labels, feats, wp, bp)


def head_ce_reference(feats, w, b, labels) -> jnp.ndarray:
    """Plain-XLA reference/fallback: explicit logits + fused-by-XLA CE."""
    import optax

    logits = (feats.astype(jnp.float32) @ w.astype(jnp.float32)) + b.astype(jnp.float32)
    valid = labels >= 0
    per = optax.softmax_cross_entropy_with_integer_labels(
        logits, jnp.maximum(labels, 0)
    )
    return jnp.where(valid, per, 0.0)


def fused_head_ce(
    feats: jnp.ndarray,
    w: jnp.ndarray,
    b: jnp.ndarray,
    labels: jnp.ndarray,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Per-example CE of ``softmax(feats @ w + b)`` [B], without ever
    materializing [B, V]. Pallas on TPU; XLA fallback elsewhere.

    ``interpret=True`` forces the Pallas interpreter (CPU tests);
    ``interpret=None`` auto-selects the compiled Pallas kernel on TPU
    backends and the XLA fallback otherwise.
    """
    if interpret is None:
        from mpi_pytorch_tpu.utils.hardware import tpu_backend

        if not tpu_backend():
            return head_ce_reference(feats, w, b, labels)
        interpret = False
    # f32 w/b at the custom_vjp boundary keeps the cotangent dtypes f32 (the
    # kernel casts W to bf16 internally, once, shared by fwd and bwd).
    return _fused_head_ce(
        feats.astype(jnp.bfloat16),
        w.astype(jnp.float32),
        b.astype(jnp.float32),
        labels.astype(jnp.int32),
        interpret,
    )
