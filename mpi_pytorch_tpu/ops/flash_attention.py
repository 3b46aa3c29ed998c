"""Pallas TPU kernel: flash (block-tiled online-softmax) attention.

The ViT family's ``sp_strategy='none'`` path (``models/vit.py
MultiHeadAttention``) computes vanilla attention, which materializes the
[B, H, S, S] score tensor in HBM — at long sequence lengths that tensor,
not the matmuls, is the memory and bandwidth cost (S=8192, H=6, B=8 is
12.9 GB in f32). The SP strategies already solve the CROSS-chip version of
this with a ppermute ring (``ops/ring_attention.py``); this kernel is the
WITHIN-chip counterpart: q is processed in VMEM-resident blocks, k/v stream
through VMEM block by block on the MXU, and the softmax is computed online
(running max ``m``, running sum ``l``) so nothing of size S×S ever exists.
Same math as ``full_attention`` — the online-softmax recurrence is exactly
the one ``ring_attention`` uses across shards, applied across k-blocks.

Design notes:
- Layout [B, S, H, D] (the repo's attention convention), internally
  [B·H, S, D]; f32 accumulation regardless of input dtype.
- Forward is the Pallas kernel: grid (B·H, S/BQ, S/BK), k innermost; the
  (m, l, acc) state lives in VMEM scratch and persists across the k
  iterations (TPU grids iterate sequentially); the last k block finalizes
  ``acc / l`` and also writes the logsumexp per row.
- Backward is BLOCKED XLA, not a second kernel: with the forward's saved
  logsumexp, each k-block's probabilities are recomputed inside a
  ``lax.scan`` (one extra q@kᵀ per block — FLOPs are cheap, HBM is not),
  so backward memory is O(S·BK) too. XLA fuses the per-block chain well,
  and the scan keeps this correctness-critical code in plain jnp.
- Grouped key-value heads (``k``/``v`` with fewer heads than ``q``): query
  head h reads key-value head ``h // (H / Hkv)`` through the kernel's index
  map — k and v are never repeated in HBM; the backward folds a group's
  query heads into one einsum, so dk and dv come out summed over the group.
- Causal: a k block wholly above the diagonal is neither computed (the body
  is skipped) nor fetched (its index clamps to the last block the q block
  needs, which the pipeline already holds).
- The two dots run in the operands' dtype with float32 accumulation (bf16
  operands take the MXU's bf16 rate; float32 operands stay float32).
- Sequences that don't divide the block sizes are zero-padded and masked
  (padded KEYS get -1e30 before the softmax; padded q rows are sliced off).
- Non-TPU backends fall back to ``full_attention`` (identical math, the
  reference this kernel is validated against in
  tests/test_flash_attention.py via interpret mode) — mirroring
  ``ops/fused_head_ce.py``'s gating.

Trainer integration: ``--attn-impl flash`` on the vit family swaps this in
for the dense-attention path (models/vit.py); composes with everything else
because it is numerically the same function.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from mpi_pytorch_tpu.ops.kernel_call import kernel_call

_NEG = -1e30  # finite mask value: keeps the online-softmax recurrence NaN-free
DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
# The blocked backward keeps [B·Hkv, G, S, BK] float32 temporaries: its own,
# smaller k block whatever the forward's.
BWD_BLOCK_K = 128


def _attn_fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
    *, scale: float, causal: bool, seq_len: int, block_q: int, block_k: int,
    n_k: int,
):
    iq, ik = pl.program_id(1), pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _block():
        scores = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [BQ, BK]

        k_pos = ik * block_k + lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        valid = k_pos < seq_len  # padded keys contribute nothing
        if causal:
            q_pos = iq * block_q + lax.broadcasted_iota(jnp.int32, scores.shape, 0)
            valid = valid & (k_pos <= q_pos)
        scores = jnp.where(valid, scores, _NEG)

        m_prev = m_scr[:, :1]  # [BQ, 1]
        l_prev = l_scr[:, :1]
        m_cur = jnp.max(scores, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new)  # masked entries: exp(_NEG - m) == 0
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    if causal:
        # Blocks wholly above the diagonal hold no valid key for this q block.
        pl.when(ik * block_k <= iq * block_q + block_q - 1)(_block)
    else:
        _block()

    @pl.when(ik == n_k - 1)
    def _finalize():
        l = l_scr[:, :1]
        safe_l = jnp.where(l > 0, l, 1.0)  # fully-padded q rows (sliced later)
        o_ref[0] = (acc_scr[:] / safe_l).astype(o_ref.dtype)
        # lse rides a 128-wide lane dim (TPU block shapes need the minor-most
        # two dims (8, 128)-tileable or full; a [BQ] vector is neither) —
        # broadcast across lanes here, lane 0 is read back after the call.
        lse_ref[0] = jnp.broadcast_to(
            m_scr[:, :1] + jnp.log(safe_l), lse_ref[0].shape
        )


def _pad_to(x, axis, mult):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _fwd_impl(q3, k3, v3, *, causal, block_q, block_k, interpret):
    """[BH, S, D] flash forward → (out [BH, S, D], lse [BH, S_pad]); ``k3``
    and ``v3`` are [BHkv, S, D], row ``b // (BH / BHkv)`` serving q row b."""
    bh, s, d = q3.shape
    group = bh // k3.shape[0]
    scale = d**-0.5
    qp = _pad_to(q3, 1, block_q)
    kp = _pad_to(k3, 1, block_k)
    vp = _pad_to(v3, 1, block_k)
    sq, sk = qp.shape[1], kp.shape[1]
    n_q, n_k = sq // block_q, sk // block_k

    kernel = functools.partial(
        _attn_fwd_kernel, scale=scale, causal=causal, seq_len=s,
        block_q=block_q, block_k=block_k, n_k=n_k,
    )
    from jax.experimental.pallas import tpu as pltpu

    if causal:
        # The last k block a q block needs; later grid steps name it again,
        # so nothing is fetched for the blocks the body skips.
        def kv_block(b, iq, ik):
            return (b // group, jnp.minimum(ik, (iq * block_q + block_q - 1) // block_k), 0)
    else:
        def kv_block(b, iq, ik):
            return (b // group, ik, 0)

    out, lse = kernel_call(
        "flash_attn_fwd",
        kernel,
        grid=(bh, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, iq, ik: (b, iq, 0)),
            pl.BlockSpec((1, block_k, d), kv_block),
            pl.BlockSpec((1, block_k, d), kv_block),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, iq, ik: (b, iq, 0)),
            pl.BlockSpec((1, block_q, 128), lambda b, iq, ik: (b, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q3.dtype),
            jax.ShapeDtypeStruct((bh, sq, 128), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),  # running max m
            pltpu.VMEM((block_q, 128), jnp.float32),  # running sum l
            pltpu.VMEM((block_q, d), jnp.float32),  # output accumulator
        ],
        interpret=interpret,
    )(qp, kp, vp)
    return out[:, :s], lse[:, :, 0]


def _bwd_blocked(q3, k3, v3, out, lse, do, *, causal, block_k):
    """Blocked XLA backward from the saved logsumexp: scan over k blocks,
    recomputing each block's probabilities — O(S·BK) memory, never S×S. The
    ``G = BH / BHkv`` query heads that share a key-value head ride one einsum
    axis, so dk and dv are summed over the group where they are made."""
    bh, s, d = q3.shape
    bkv = k3.shape[0]
    g = bh // bkv
    scale = d**-0.5
    qf = q3.astype(jnp.float32).reshape(bkv, g, s, d)
    dof = do.astype(jnp.float32).reshape(bkv, g, s, d)
    # D_i = Σ_d dOut · Out — the softmax-jacobian diagonal term.
    delta = jnp.sum(
        dof * out.astype(jnp.float32).reshape(bkv, g, s, d), axis=-1, keepdims=True
    )  # [BHkv, G, S, 1]
    lse_r = lse[:, :s].reshape(bkv, g, s, 1)

    kp = _pad_to(k3.astype(jnp.float32), 1, block_k)
    vp = _pad_to(v3.astype(jnp.float32), 1, block_k)
    n_k = kp.shape[1] // block_k
    k_blocks = kp.reshape(bkv, n_k, block_k, d).transpose(1, 0, 2, 3)
    v_blocks = vp.reshape(bkv, n_k, block_k, d).transpose(1, 0, 2, 3)
    q_pos = lax.broadcasted_iota(jnp.int32, (s, block_k), 0)

    def one_block(dq_acc, xs):
        ib, k_blk, v_blk = xs
        scores = jnp.einsum("bgqd,bkd->bgqk", qf * scale, k_blk)
        k_pos = ib * block_k + lax.broadcasted_iota(jnp.int32, (s, block_k), 1)
        valid = k_pos < s
        if causal:
            valid = valid & (k_pos <= q_pos)
        p = jnp.where(valid, jnp.exp(scores - lse_r), 0.0)  # [BHkv, G, S, BK]
        dv_blk = jnp.einsum("bgqk,bgqd->bkd", p, dof)
        dp = jnp.einsum("bgqd,bkd->bgqk", dof, v_blk)
        ds = p * (dp - delta)
        dq_acc = dq_acc + jnp.einsum("bgqk,bkd->bgqd", ds, k_blk) * scale
        dk_blk = jnp.einsum("bgqk,bgqd->bkd", ds, qf) * scale
        return dq_acc, (dk_blk, dv_blk)

    dq, (dk_b, dv_b) = lax.scan(
        one_block,
        jnp.zeros_like(qf),
        (jnp.arange(n_k), k_blocks, v_blocks),
    )
    dk = dk_b.transpose(1, 0, 2, 3).reshape(bkv, n_k * block_k, d)[:, :s]
    dv = dv_b.transpose(1, 0, 2, 3).reshape(bkv, n_k * block_k, d)[:, :s]
    return dq.reshape(bh, s, d).astype(q3.dtype), dk.astype(k3.dtype), dv.astype(v3.dtype)


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6)
)
def _flash3(q3, k3, v3, causal, block_q, block_k, interpret):
    out, _ = _fwd_impl(
        q3, k3, v3, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret,
    )
    return out


def _flash3_fwd(q3, k3, v3, causal, block_q, block_k, interpret):
    out, lse = _fwd_impl(
        q3, k3, v3, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret,
    )
    return out, (q3, k3, v3, out, lse)


def _flash3_bwd(causal, block_q, block_k, interpret, residuals, do):
    q3, k3, v3, out, lse = residuals
    return _bwd_blocked(
        q3, k3, v3, out, lse, do, causal=causal, block_k=min(block_k, BWD_BLOCK_K)
    )


_flash3.defvjp(_flash3_fwd, _flash3_bwd)


def flash_attention(
    q, k, v, *, causal: bool = False,
    block_q: int = DEFAULT_BLOCK_Q, block_k: int = DEFAULT_BLOCK_K,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Flash attention over [B, S, H, D] inputs (the repo layout); ``k`` and
    ``v`` may carry fewer heads, [B, S, Hkv, D] with ``H % Hkv == 0``: query
    head h then reads key-value head ``h // (H / Hkv)`` (grouped-query
    attention), by index and without a repeated copy.

    ``interpret``: None = Pallas on TPU, ``full_attention`` fallback
    elsewhere (or the Pallas interpreter when ``MPT_FLASH_INTERPRET`` is
    set — how tests drive the real kernel path through a whole model on
    CPU); True forces the interpreter; False forces the compiled kernel."""
    from mpi_pytorch_tpu.ops.ring_attention import full_attention
    from mpi_pytorch_tpu.utils.env import env_flag
    from mpi_pytorch_tpu.utils.hardware import tpu_backend

    if interpret is None:
        if env_flag("MPT_FLASH_INTERPRET"):
            interpret = True
        elif not tpu_backend():
            group = q.shape[2] // k.shape[2]
            if group > 1:  # the XLA composition has one head layout
                k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
            return full_attention(q, k, v, causal=causal)
        else:
            interpret = False

    b, s, h, d = q.shape
    if h % k.shape[2] or k.shape != v.shape:
        raise ValueError(
            f"flash_attention: {h} query heads over k {k.shape} / v {v.shape}: "
            "the key-value heads must divide the query heads"
        )
    bq = min(block_q, max(8, s))
    bk = min(block_k, max(8, s))

    def to3(x):
        return x.transpose(0, 2, 1, 3).reshape(b * x.shape[2], x.shape[1], d)

    out3 = _flash3(to3(q), to3(k), to3(v), causal, bq, bk, interpret)
    return out3.reshape(b, h, s, d).transpose(0, 2, 1, 3)
