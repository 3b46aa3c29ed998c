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
- Forward is the Pallas kernel: grid (B·Hkv, S/BQ, S/BK), k innermost.
  One step holds a [G·BQ, BK] tile: the BQ-row query blocks of the G query
  heads that share a key-value head, stacked along the rows against the one
  key block they all read (the tile is tall without the causal diagonal's
  waste growing with it, and K and V are fetched once a group). The
  (m, l, acc) state lives in VMEM scratch and persists across the k
  iterations (TPU grids iterate sequentially); the last k block finalizes
  ``acc / l`` and also writes the logsumexp per row.
- The online softmax's state is lane-dense: ``m`` [rows, 128] holds the
  row's running max in every lane, ``l`` [rows, 128] holds in lane j the
  running sum over the keys j mod 128. A step then costs a row ONE
  cross-lane reduction (the max); subtracting the max, rescaling ``l`` and
  ``acc`` are plain vreg-by-vreg operations with no column broadcast, and
  the 128 partial sums meet once a query block, when it finalizes. With
  [rows, 1] columns (two reductions and four broadcasts a row and step) the
  kernel's time went as 1 / BK and the elementwise work hardly showed
  (PERF.md section 6, PR 32).
- Every computed tile carries the per-element mask. Leaving it out of the
  tiles the diagonal and the padded end do not cross gives the same bits
  and, at this tile, no time: the tile is MXU-bound at Dh = 64 and the mask
  rides in spare VPU slots (PERF.md section 6, PR 32).
- The forward's tile is this module's constant ``FWD_TILE``, from a sweep on
  the chip, fitted to the head's width and clipped to the sequence
  (``_fwd_blocks``); no caller chooses it. The trace file's
  ``flash/dispatch`` instant says what a shape got.
- Backward is two more Pallas kernels under one scope
  (``kernel/flash_attn_bwd``), from the forward's residuals (q, k, v, out,
  logsumexp) and ``delta = rowsum(dOut · out)``: each (q block, k block)
  tile's probabilities are recomputed as ``exp(q kᵀ · scale − lse)`` and
  scores, probabilities and dS live in VMEM only. ``flash_attn_bwd_dkv``
  walks key blocks outermost and, innermost, the query blocks of every
  query head of the key-value group, accumulating dk and dv in float32
  scratch and writing each once; its tiles are [BK, BQ], so the per-query
  ``lse`` and ``delta`` are [1, BQ] rows of narrow [B·H, 1, S] arrays.
  ``flash_attn_bwd_dq`` runs on a grid (B·H, S/BQ, S/BK), key blocks
  innermost, dq in float32 scratch, with [BQ, BK] tiles, the two rows turned
  into columns once a query block. Each kernel recomputes the scores: seven
  matmuls a tile pair for the algorithm's five. Their block sizes are this
  module's constant ``BWD_BLOCKS``, from a sweep of their own.
- Grouped key-value heads (``k``/``v`` with fewer heads than ``q``): query
  head h reads key-value head ``h // (H / Hkv)`` through the kernel's index
  map — k and v are never repeated in HBM; the dk/dv kernel streams a
  group's query heads past one key block, so dk and dv come out summed over
  the group.
- Causal, all three kernels: a tile wholly above the diagonal is neither
  computed (the body is skipped) nor fetched (its index clamps to a block
  the pipeline already holds: the last k block a q block needs, forward and
  dq; the first q block a k block reaches, dk/dv). Every tile they compute
  carries the per-element mask (``_mask_as_forward``: padded keys and,
  causal, keys after the query).
- The softmax scale defaults to ``D ** -0.5`` (the ViT family and
  ``lfm2_moe`` pass none); a model whose scale is its own constant passes
  ``scale=`` (``granitemoehybrid``: ``attention_multiplier``), and the forward
  kernel and both backward kernels take it.
- Every dot runs in the operands' dtype with float32 accumulation (bf16
  operands take the MXU's bf16 rate; float32 operands stay float32): p and
  dS are cast to it for the MXU; exp, the mask, dS and every accumulator are
  float32.
- Sequences that don't divide the block sizes are zero-padded and masked
  (padded KEYS get -1e30 before the softmax; padded q rows are sliced off,
  and carry dOut = 0 in the backward).
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
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from mpi_pytorch_tpu.ops.kernel_call import kernel_call

_NEG = -1e30  # finite mask value: keeps the online-softmax recurrence NaN-free
# The forward's tile, (query rows, keys): the rows are shared among the query
# heads of a key-value group, whose blocks one grid step stacks. The fastest of
# a sweep over 512..4096 rows (one head's, or a group of four's) by 256..2048
# keys on a v5e at S=8192, Dh=64, bf16, causal (PERF.md section 6, PR 32): the
# [2048, 512] float32 score tile and its probabilities fit the 16 MiB of VMEM
# a kernel gets; 4096 rows read 1 % faster and do not.
FWD_TILE = (2048, 512)
# The bytes a row of q may have (Dh x itemsize) for FWD_TILE's rows to fit
# those 16 MiB, by compiling for a v5e: Dh=128 in float32 and Dh=256 in bf16
# fit at 2048 rows, Dh=256 in float32 does not and fits at 1024.
_ROW_BYTES = 512
# The backward kernels' (query, key) block sizes, whatever the forward's: the
# fastest of a sweep over 128..2048 a side on a v5e at S=8192, Dh=64, for the
# dk/dv kernel and for the dq kernel alike (PERF.md section 6, PR 28); three
# [1024, 1024] float32 tiles fit the 16 MiB of VMEM a kernel gets, 2048 x 1024
# does not.
BWD_BLOCKS = (1024, 1024)


def _mask_as_forward(scores, *, causal, seq_len, q0, k0, q_axis, block_q=None):
    """``scores`` at -1e30 wherever the forward masks: padded keys and,
    causal, keys after the query. ``q_axis`` is the tile's query axis (0 in a
    [BQ, BK] tile, 1 in a [BK, BQ] one). ``block_q``: the query axis stacks
    several heads' blocks of that many rows, each starting at ``q0``."""
    k_pos = k0 + lax.broadcasted_iota(jnp.int32, scores.shape, 1 - q_axis)
    valid = k_pos < seq_len
    if causal:
        row = lax.broadcasted_iota(jnp.int32, scores.shape, q_axis)
        if block_q is not None and block_q != scores.shape[q_axis]:
            row = row % block_q
        valid = valid & (k_pos <= q0 + row)
    return jnp.where(valid, scores, _NEG)


def _attn_fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
    *, scale: float, causal: bool, seq_len: int, block_q: int, block_k: int,
    n_k: int,
):
    """One [heads * BQ, BK] tile: the ``heads`` query heads of the step share
    the key block, their query blocks stacked along the rows."""
    iq, ik = pl.program_id(1), pl.program_id(2)
    heads, _, d = q_ref.shape[1:]
    rows = heads * block_q

    @pl.when(ik == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _block():
        scores = jax.lax.dot_general(
            q_ref[0].reshape(rows, d), k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [rows, BK]
        scores = _mask_as_forward(
            scores, causal=causal, seq_len=seq_len,
            q0=iq * block_q, k0=ik * block_k, q_axis=0, block_q=block_q,
        )

        # Running max and sum ride ``w`` lanes: every lane of ``m`` holds the
        # row's max, lane j of ``l`` the sum over the keys j mod w — so a step
        # costs a row ONE cross-lane reduction (the max) and no broadcast of
        # a column; the sum's lanes meet once a query block, in ``_finalize``.
        w = m_scr.shape[1]
        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - _lanes(m_new, block_k))  # masked entries: exp(_NEG - m) == 0
        l_scr[:] = alpha * l_scr[:] + sum(
            p[:, c * w:(c + 1) * w] for c in range(block_k // w)
        )
        acc_scr[:] = acc_scr[:] * _lanes(alpha, d) + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[:] = m_new

    if causal:
        # Key blocks wholly after the query block: none of their keys is seen.
        pl.when(ik * block_k <= iq * block_q + block_q - 1)(_block)
    else:
        _block()

    @pl.when(ik == n_k - 1)
    def _finalize():
        l = jnp.sum(l_scr[:], axis=-1, keepdims=True)
        safe_l = jnp.where(l > 0, l, 1.0)  # fully-padded q rows (sliced later)
        o_ref[0] = (acc_scr[:] / safe_l).astype(o_ref.dtype).reshape(o_ref.shape[1:])
        # lse rides a 128-wide lane dim (TPU block shapes need the minor-most
        # two dims (8, 128)-tileable or full; a [BQ] vector is neither) —
        # broadcast across lanes here, lane 0 is read back after the call.
        lse_ref[0] = jnp.broadcast_to(
            m_scr[:, :1] + jnp.log(safe_l), (rows, 128)
        ).reshape(lse_ref.shape[1:])


def _lanes(x, n: int):
    """``x [rows, w]`` whose lanes are equal in every row, as ``[rows, n]``."""
    w = x.shape[1]
    if n <= w:
        return x[:, :n]
    if n % w == 0:
        return jnp.tile(x, (1, n // w))
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _pad_to(x, axis, mult):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _fwd_impl(q3, k3, v3, *, causal, block_q, block_k, interpret, scale=None):
    """[BH, S, D] flash forward → (out [BH, S, D], lse [BH, S_pad]); ``k3``
    and ``v3`` are [BHkv, S, D], row ``b // (BH / BHkv)`` serving q row b."""
    bh, s, d = q3.shape
    bkv = k3.shape[0]
    group = bh // bkv
    scale = d**-0.5 if scale is None else scale
    qp = _pad_to(q3, 1, block_q)
    kp = _pad_to(k3, 1, block_k)
    vp = _pad_to(v3, 1, block_k)
    sq, sk = qp.shape[1], kp.shape[1]
    n_q, n_k = sq // block_q, sk // block_k
    qp = qp.reshape(bkv, group, sq, d)
    lanes = math.gcd(block_k, 128)  # of the running max and sum: whole vregs at real sizes

    kernel = functools.partial(
        _attn_fwd_kernel, scale=scale, causal=causal, seq_len=s,
        block_q=block_q, block_k=block_k, n_k=n_k,
    )
    from jax.experimental.pallas import tpu as pltpu

    if causal:
        # The last k block a q block needs; later grid steps name it again,
        # so nothing is fetched for the blocks the body skips.
        def kv_block(b, iq, ik):
            return (b, jnp.minimum(ik, (iq * block_q + block_q - 1) // block_k), 0)
    else:
        def kv_block(b, iq, ik):
            return (b, ik, 0)

    def q_block(b, iq, ik):
        return (b, 0, iq, 0)

    out, lse = kernel_call(
        "flash_attn_fwd",
        kernel,
        grid=(bkv, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, group, block_q, d), q_block),
            pl.BlockSpec((1, block_k, d), kv_block),
            pl.BlockSpec((1, block_k, d), kv_block),
        ],
        out_specs=[
            pl.BlockSpec((1, group, block_q, d), q_block),
            pl.BlockSpec((1, group, block_q, 128), q_block),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bkv, group, sq, d), q3.dtype),
            jax.ShapeDtypeStruct((bkv, group, sq, 128), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((group * block_q, lanes), jnp.float32),  # running max m
            pltpu.VMEM((group * block_q, lanes), jnp.float32),  # running sum l
            pltpu.VMEM((group * block_q, d), jnp.float32),  # output accumulator
        ],
        interpret=interpret,
    )(qp, kp, vp)
    # Every lane holds the row's value: a max over them reads the kernel's
    # output as it lies (``lse[:, :, 0]`` made XLA copy all of it into
    # another layout first, 0.8 ms at [64, 8192, 128]).
    return out.reshape(bh, sq, d)[:, :s], jnp.max(lse, axis=-1).reshape(bh, sq)


def _attn_bwd_dkv_kernel(
    q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref, dk_ref, dv_ref, dk_scr, dv_scr,
    *, scale: float, causal: bool, seq_len: int, block_q: int, block_k: int,
    n_q: int, group: int,
):
    """dk and dv of one key block: the query blocks of the ``group`` query
    heads that share the key-value head stream past it, innermost. Tiles are
    [BK, BQ] (keys on sublanes), so the per-query ``lse`` and ``delta`` are
    [1, BQ] rows and both accumulating matmuls are plain ``a @ b``."""
    ik, ig, iq = pl.program_id(1), pl.program_id(2), pl.program_id(3)

    @pl.when((ig == 0) & (iq == 0))
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _block():
        q, do = q_ref[0], do_ref[0]
        scores = jax.lax.dot_general(
            k_ref[0], q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [BK, BQ]
        scores = _mask_as_forward(
            scores, causal=causal, seq_len=seq_len,
            q0=iq * block_q, k0=ik * block_k, q_axis=1,
        )
        p = jnp.exp(scores - lse_ref[0])  # masked entries: exp(_NEG - lse) == 0
        dp = jax.lax.dot_general(
            v_ref[0], do, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_ref[0])
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        # Query blocks wholly before the key block see none of its keys.
        pl.when(iq * block_q + block_q - 1 >= ik * block_k)(_block)
    else:
        _block()

    @pl.when((ig == group - 1) & (iq == n_q - 1))
    def _finalize():
        dk_ref[0] = (dk_scr[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _attn_bwd_dq_kernel(
    q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref, dq_ref, dq_scr, lse_scr, delta_scr,
    *, scale: float, causal: bool, seq_len: int, block_q: int, block_k: int,
    n_k: int,
):
    """dq of one query block of one query head, key blocks innermost. Tiles
    are [BQ, BK] as in the forward, so ``dq += ds @ k`` is plain; the
    [1, BQ] rows of ``lse`` and ``delta`` are turned into columns once a
    query block."""
    iq, ik = pl.program_id(1), pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)
        lanes = lse_scr.shape[::-1]
        lse_scr[:] = jnp.broadcast_to(lse_ref[0], lanes).T
        delta_scr[:] = jnp.broadcast_to(delta_ref[0], lanes).T

    def _block():
        k = k_ref[0]
        scores = jax.lax.dot_general(
            q_ref[0], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [BQ, BK]
        scores = _mask_as_forward(
            scores, causal=causal, seq_len=seq_len,
            q0=iq * block_q, k0=ik * block_k, q_axis=0,
        )
        p = jnp.exp(scores - lse_scr[:, :1])
        dp = jax.lax.dot_general(
            do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_scr[:, :1])
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        pl.when(ik * block_k <= iq * block_q + block_q - 1)(_block)
    else:
        _block()

    @pl.when(ik == n_k - 1)
    def _finalize():
        dq_ref[0] = (dq_scr[:] * scale).astype(dq_ref.dtype)


def _bwd_blocks(s: int) -> tuple[int, int]:
    """The backward kernels' (query, key) block sizes for a sequence of ``s``:
    the module's constants, clipped to the sequence in whole 128-lane tiles
    (each is the lane dimension of one kernel's score tiles)."""
    whole = -(-s // 128) * 128
    return min(BWD_BLOCKS[0], whole), min(BWD_BLOCKS[1], whole)


def _bwd_impl(q3, k3, v3, out, lse, do, *, causal, interpret, scale=None):
    """dq, dk, dv from the forward's residuals: two Pallas kernels under one
    scope, probabilities recomputed from the saved logsumexp in VMEM, blocks
    wholly above the causal diagonal neither computed nor fetched. ``k3`` /
    ``v3`` are [BHkv, S, D]; dk and dv come out summed over each group."""
    from jax.experimental.pallas import tpu as pltpu

    bh, s, d = q3.shape
    bkv = k3.shape[0]
    group = bh // bkv
    scale = d**-0.5 if scale is None else scale
    # D_i = Σ_d dOut · Out — the softmax-jacobian diagonal term.
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    lse = lse[:, :s]

    bq, bk = _bwd_blocks(s)
    # Padded query rows carry dOut = 0 (and lse = delta = 0): they add nothing
    # to dk or dv, and their dq rows are sliced off.
    rows = lambda x: _pad_to(x, 1, bq)[:, None, :]  # [BH, 1, S_pad]: narrow in HBM
    args = (
        _pad_to(q3, 1, bq), _pad_to(do, 1, bq), rows(lse), rows(delta),
        _pad_to(k3, 1, bk), _pad_to(v3, 1, bk),
    )
    n_q, n_k = args[0].shape[1] // bq, args[4].shape[1] // bk

    def q_block(ik, iq):
        # Causal: the first query block a key block reaches; earlier grid steps
        # name it too, so nothing is fetched for the blocks the body skips.
        return jnp.maximum(iq, (ik * bk) // bq) if causal else iq

    q_spec = pl.BlockSpec((1, bq, d), lambda b, ik, ig, iq: (b * group + ig, q_block(ik, iq), 0))
    row_spec = pl.BlockSpec((1, 1, bq), lambda b, ik, ig, iq: (b * group + ig, 0, q_block(ik, iq)))
    kv_spec = pl.BlockSpec((1, bk, d), lambda b, ik, ig, iq: (b, ik, 0))
    dk, dv = kernel_call(
        "flash_attn_bwd",
        functools.partial(
            _attn_bwd_dkv_kernel, scale=scale, causal=causal, seq_len=s,
            block_q=bq, block_k=bk, n_q=n_q, group=group,
        ),
        name="flash_attn_bwd_dkv",
        grid=(bkv, n_k, group, n_q),
        in_specs=[q_spec, q_spec, row_spec, row_spec, kv_spec, kv_spec],
        out_specs=[kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct(args[4].shape, k3.dtype)] * 2,
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32)] * 2,
        interpret=interpret,
    )(*args)

    def k_block(iq, ik):  # causal: the last key block a query block needs, as the forward
        return jnp.minimum(ik, (iq * bq + bq - 1) // bk) if causal else ik

    q_spec = pl.BlockSpec((1, bq, d), lambda b, iq, ik: (b, iq, 0))
    row_spec = pl.BlockSpec((1, 1, bq), lambda b, iq, ik: (b, 0, iq))
    kv_spec = pl.BlockSpec((1, bk, d), lambda b, iq, ik: (b // group, k_block(iq, ik), 0))
    dq = kernel_call(
        "flash_attn_bwd",
        functools.partial(
            _attn_bwd_dq_kernel, scale=scale, causal=causal, seq_len=s,
            block_q=bq, block_k=bk, n_k=n_k,
        ),
        name="flash_attn_bwd_dq",
        grid=(bh, n_q, n_k),
        in_specs=[q_spec, q_spec, row_spec, row_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(args[0].shape, q3.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),  # dq accumulator
            pltpu.VMEM((bq, 128), jnp.float32),  # lse, a column
            pltpu.VMEM((bq, 128), jnp.float32),  # delta, a column
        ],
        interpret=interpret,
    )(*args)
    return dq[:, :s], dk[:, :s], dv[:, :s]


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7)
)
def _flash3(q3, k3, v3, causal, block_q, block_k, interpret, scale):
    out, _ = _fwd_impl(
        q3, k3, v3, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret, scale=scale,
    )
    return out


def _flash3_fwd(q3, k3, v3, causal, block_q, block_k, interpret, scale):
    out, lse = _fwd_impl(
        q3, k3, v3, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret, scale=scale,
    )
    return out, (q3, k3, v3, out, lse)


def _flash3_bwd(causal, block_q, block_k, interpret, scale, residuals, do):
    q3, k3, v3, out, lse = residuals
    return _bwd_impl(q3, k3, v3, out, lse, do, causal=causal, interpret=interpret, scale=scale)


_flash3.defvjp(_flash3_fwd, _flash3_bwd)


def _fwd_blocks(s: int, group: int, row_bytes: int) -> tuple[int, int]:
    """The forward's (query rows a head, keys) a tile for a sequence of ``s``
    whose key-value heads each serve ``group`` query heads of ``row_bytes``
    (Dh x itemsize) a row: the module's constant, its rows fewer as a row of q
    and out outgrows ``_ROW_BYTES``, shared among the group in whole 16-row
    sublane tiles (bf16 packs 16 rows a vreg), and clipped to the sequence —
    in whole 128-lane tiles, or for a sequence inside one, in whole sublane
    tiles (so the ViTs' S <= 196 is one block a side)."""
    rows = FWD_TILE[0] * _ROW_BYTES // max(_ROW_BYTES, row_bytes)
    whole = -(-s // 128) * 128 if s > 128 else -(-s // 16) * 16
    return min(max(rows // group // 16 * 16, 16), whole), min(FWD_TILE[1], whole)


def _tile_counts(s: int, *, causal: bool, block_q: int, block_k: int) -> dict:
    """How many of a head's tiles the forward skips and computes."""
    n_q, n_k = -(-s // block_q), -(-s // block_k)
    computed = sum(
        min(n_k, (iq * block_q + block_q - 1) // block_k + 1) if causal else n_k
        for iq in range(n_q)
    )
    return {"tiles_skipped": n_q * n_k - computed, "tiles_computed": computed}


def flash_attention(
    q, k, v, *, causal: bool = False,
    block_q: int | None = None, block_k: int | None = None,
    interpret: bool | None = None, scale: float | None = None,
) -> jnp.ndarray:
    """Flash attention over [B, S, H, D] inputs (the repo layout); ``k`` and
    ``v`` may carry fewer heads, [B, S, Hkv, D] with ``H % Hkv == 0``: query
    head h then reads key-value head ``h // (H / Hkv)`` (grouped-query
    attention), by index and without a repeated copy.

    ``scale`` multiplies ``q kᵀ`` before the softmax; None is ``D ** -0.5``.

    ``block_q`` / ``block_k``: the forward's tile, for a test that wants
    several blocks a side of a short sequence; None is what the shape chooses
    (``_fwd_blocks``). The trace file's ``flash/dispatch`` instant says which.

    ``interpret``: None = Pallas on TPU, ``full_attention`` fallback
    elsewhere (or the Pallas interpreter when ``MPT_FLASH_INTERPRET`` is
    set — how tests drive the real kernel path through a whole model on
    CPU); True forces the interpreter; False forces the compiled kernel."""
    from mpi_pytorch_tpu.obs import trace as obs_trace
    from mpi_pytorch_tpu.ops.ring_attention import full_attention
    from mpi_pytorch_tpu.utils.env import env_flag
    from mpi_pytorch_tpu.utils.hardware import tpu_backend

    group = q.shape[2] // k.shape[2]
    if interpret is None:
        if env_flag("MPT_FLASH_INTERPRET"):
            interpret = True
        elif not tpu_backend():
            if group > 1:  # the XLA composition has one head layout
                k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
            return full_attention(q, k, v, causal=causal, scale=scale)
        else:
            interpret = False

    b, s, h, d = q.shape
    if h % k.shape[2] or k.shape != v.shape:
        raise ValueError(
            f"flash_attention: {h} query heads over k {k.shape} / v {v.shape}: "
            "the key-value heads must divide the query heads"
        )
    bq, bk = _fwd_blocks(s, group, d * jnp.dtype(q.dtype).itemsize)
    bq = bq if block_q is None else min(block_q, max(8, s))
    bk = bk if block_k is None else min(block_k, max(8, s))
    obs_trace.current().instant(
        "flash/dispatch",
        {"S": s, "Dh": d, "heads": h, "kv_heads": k.shape[2], "causal": causal,
         "block_q": bq, "block_k": bk,
         **_tile_counts(s, causal=causal, block_q=bq, block_k=bk)},
        once=True,
    )

    def to3(x):
        return x.transpose(0, 2, 1, 3).reshape(b * x.shape[2], x.shape[1], d)

    out3 = _flash3(to3(q), to3(k), to3(v), causal, bq, bk, interpret, scale)
    return out3.reshape(b, h, s, d).transpose(0, 2, 1, 3)
