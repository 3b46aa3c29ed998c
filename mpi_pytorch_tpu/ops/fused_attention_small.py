"""Pallas TPU kernel: fused tiny-S attention — scores + softmax + AV in one
VMEM pass per (batch·head) group; fully-fused recompute backward.

Why this op exists (docs/RESULTS.md §4): vit_s16 is the zoo's worst
performer relative to its own roofline — 28.0% MFU against a 44.4% ceiling,
a 1.59× measured/bound gap that the HLO's own cost model localizes to the
per-layer attention block: **31% of modeled time in the softmax chain**
(the [2048, 6, 64, 64] f32 score tensor is 201 MB and the chain touches
several of them per block) and **35% in the score/AV batched matmuls**
(12 288 tiny 64×64×64 matmuls per direction, each filling a quarter of the
128×128 MXU in M×N). The flash kernel (``ops/flash_attention.py``) cannot
help here — measured and rejected at this S in round 3 (4 942 vs 5 722
img/s, ``docs/zoo_flash.json``): its block-tiled online softmax exists to
avoid materializing an S×S tensor that at S=64 is trivially VMEM-sized,
so its per-block state machinery is pure overhead.

This kernel is the flash kernel's tiny-S sibling, purpose-built for the
regime flash loses in (S ≤ 128, Dh ≤ 128 — every per-head score matrix
fits in VMEM whole):

- **Forward**: one grid step per group of ``G`` (batch, head) pairs; q/k/v
  tiles live entirely in VMEM, scores are computed in f32 on the MXU, the
  softmax is a plain (not online) max/exp/sum over the full row, and AV
  lands in the same pass. Nothing between the q/k/v reads and the output
  write ever touches HBM — the 201 MB score tensor and the entire softmax
  chain disappear from the HBM budget.
- **bh-grouping (the MXU-fill lever)**: ``G`` (batch, head) pairs are
  stacked into one [G·S, D] tile and the scores computed as ONE
  [G·S, G·S] matmul with the off-diagonal (cross-head) blocks masked to
  −1e30 before the softmax. Masked probabilities are exactly zero, so the
  AV matmul over the stacked tile is exact with no unstacking. At S=64,
  G=2 turns two quarter-filled 64×64 MXU outputs into one full 128×128
  output (and gives every VPU softmax row 128 full lanes) at the price of
  computing the masked half — the lever the chip A/B decides
  (``MPT_ATTN_BH_BLOCK``; ``tools/bench_attention.py --fused-small``).
- **Backward**: a second single-pass Pallas kernel that RECOMPUTES the
  probabilities in VMEM (one extra q·kᵀ + softmax — tiny-S FLOPs are
  cheap, HBM bytes are not) and emits dq/dk/dv in the same pass:
  dv = pᵀ·do, Δ = Σ_d do·o with o = p·v recomputed in-kernel,
  ds = p·(do·vᵀ − Δ), dq = ds·k·scale, dk = dsᵀ·q·scale. No logsumexp,
  no saved output: the residuals are just the primal q/k/v. The blocked
  XLA backward the flash kernel uses would re-materialize [B·H, S, S]
  probability and ds tensors in HBM — exactly the bytes this kernel
  exists to remove.
- **Masking**: padding (S not a sublane multiple) and the cross-head
  blocks share one precomputed [G·S_pad, G·S_pad] additive f32 bias
  (0 / −1e30), built ONCE in XLA outside the kernel from static shape
  parameters and re-read by every grid step (≤64 KB — VMEM-trivial).
  This keeps every Mosaic-fragile integer div/mod off the kernel body;
  in-kernel there is only dot/exp/max/sum/where, all probed ops. Padded
  q rows softmax over their head's valid keys (l > 0 always) and are
  sliced off by the wrapper; their cotangents are zero because the
  padded ``do`` rows are zero.

Non-TPU backends fall back to ``full_attention`` (identical math — the
reference this kernel is pinned against in
tests/test_fused_attention_small.py via interpret mode), mirroring
``ops/flash_attention.py``'s gating; ``MPT_ATTN_INTERPRET=1`` drives the
real kernel through the Pallas interpreter on CPU (how the tests run it).
Sequences outside the tiny-S envelope (S > 128, or Dh > 128) raise on a
TPU and take ``full_attention`` elsewhere — this kernel's domain is
exactly the regime where flash was measured to lose.

Multi-chip: pass ``dp_mesh`` (the training/eval mesh) and the public
wrapper ``shard_map``s the kernel over the mesh's leading (data) axis —
each chip runs the Mosaic call on its own batch shard, identical to the
fused stem / fused eval head contract (ops/fused_stem.py "Multi-chip").
All operands are batch-sharded (no replicated params), so shard_map's
transpose needs no psum and gradients equal the single-call gradients
exactly. Inside an ALREADY shard_map'd context over the same axis (the
``--spmd-mode`` train step) the wrapper detects the bound axis
(``compat.axis_is_manual``) and runs the per-shard call directly.

Trainer integration: ``--attn-impl fused-small`` on the vit family
(models/vit.py) — same function as ``full``/``flash``, different
execution. The measured ship-or-reject A/B is staged in docs/RESULTS.md
§4 (chip window pending), exactly like the §4d stem levers.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from mpi_pytorch_tpu.ops.kernel_call import kernel_call

_NEG = -1e30  # finite mask value — exp(_NEG - m) underflows to exactly 0

# The tiny-S envelope: one (G·S_pad)² f32 score tile must fit comfortably
# in VMEM and the regime must be the one flash LOSES in (docs/RESULTS.md
# §4: flash wins from S≈2048 up; the crossover is far above this).
MAX_SEQ = 128
MAX_HEAD_DIM = 128


def _bh_block(bh: int, s_pad: int, override: int | None = None) -> int:
    """(batch·head) pairs per grid step. Default fills the 128-lane /
    128×128-MXU tile: G = 128 // S_pad (≥1), reduced until it divides the
    (per-shard) B·H count. ``override`` (the ``bh_block`` kwarg) beats the
    ``MPT_ATTN_BH_BLOCK`` env gate beats the default
    (tools/bench_attention.py --fused-small sweeps them)."""
    raw = os.environ.get("MPT_ATTN_BH_BLOCK")
    if override is not None:
        g = override
    elif raw:
        g = int(raw)
    else:
        g = max(1, 128 // s_pad)
    # VMEM envelope: the kernel holds (G·S_pad)² f32 score/probability
    # tiles; cap G·S_pad at 512 (≤1 MB per tile) so an aggressive override
    # degrades to a buildable grouping instead of a Mosaic compile failure
    # mid-run.
    g = max(1, min(g, bh, max(1, 512 // s_pad)))
    while bh % g:
        g -= 1
    return g


def _mask_bias(g: int, s_pad: int, seq_len: int, causal: bool) -> jnp.ndarray:
    """[G·S_pad, G·S_pad] additive f32 bias: 0 on (same-head, valid-key
    [, causal]) entries, −1e30 elsewhere. Built in XLA from static ints —
    no integer div/mod ever reaches the Mosaic kernel body."""
    r = g * s_pad
    rows = lax.broadcasted_iota(jnp.int32, (r, r), 0)
    cols = lax.broadcasted_iota(jnp.int32, (r, r), 1)
    valid = (rows // s_pad == cols // s_pad) & (cols % s_pad < seq_len)
    if causal:
        valid &= cols % s_pad <= rows % s_pad
    return jnp.where(valid, 0.0, _NEG).astype(jnp.float32)


def _fwd_kernel(q_ref, k_ref, v_ref, bias_ref, o_ref, *, scale):
    q = q_ref[0].astype(jnp.float32) * scale  # [R, D]
    k = k_ref[0].astype(jnp.float32)
    s = lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) + bias_ref[...]  # [R, R]
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)  # masked entries: exp(_NEG - m) == 0
    l = jnp.sum(p, axis=-1, keepdims=True)  # ≥ 1 valid key per row ⇒ l > 0
    o = lax.dot_general(
        p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    o_ref[0] = (o / l).astype(o_ref.dtype)


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, bias_ref,
                dq_ref, dk_ref, dv_ref, *, scale):
    q = q_ref[0].astype(jnp.float32)  # [R, D]
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    s = lax.dot_general(
        q * scale, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) + bias_ref[...]
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    p = p / jnp.sum(p, axis=-1, keepdims=True)  # normalized probs [R, R]
    o = lax.dot_general(  # recomputed output — cheaper than an HBM residual
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    delta = jnp.sum(do * o, axis=-1, keepdims=True)  # [R, 1]
    dp = lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # do·vᵀ [R, R]
    ds = p * (dp - delta)
    dq_ref[0] = (lax.dot_general(
        ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale).astype(dq_ref.dtype)
    dk_ref[0] = (lax.dot_general(
        ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale).astype(dk_ref.dtype)
    dv_ref[0] = lax.dot_general(
        p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    ).astype(dv_ref.dtype)


def _tile_specs(n: int, r: int, d: int):
    """(in_specs for [N, R, D] operands + the shared [R, R] bias, grid)."""
    tile = pl.BlockSpec((1, r, d), lambda i: (i, 0, 0))
    bias = pl.BlockSpec((r, r), lambda i: (0, 0))
    return tile, bias, (n,)


def _fwd_impl(qg, kg, vg, *, seq_len, s_pad, g, causal, interpret):
    n, r, d = qg.shape
    bias = _mask_bias(g, s_pad, seq_len, causal)
    tile, bspec, grid = _tile_specs(n, r, d)
    return kernel_call(
        "attn_small_fwd",
        functools.partial(_fwd_kernel, scale=d**-0.5),
        grid=grid,
        in_specs=[tile, tile, tile, bspec],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((n, r, d), qg.dtype),
        interpret=interpret,
    )(qg, kg, vg, bias)


def _bwd_impl(qg, kg, vg, dog, *, seq_len, s_pad, g, causal, interpret):
    n, r, d = qg.shape
    bias = _mask_bias(g, s_pad, seq_len, causal)
    tile, bspec, grid = _tile_specs(n, r, d)
    return kernel_call(
        "attn_small_bwd",
        functools.partial(_bwd_kernel, scale=d**-0.5),
        grid=grid,
        in_specs=[tile, tile, tile, tile, bspec],
        out_specs=[tile, tile, tile],
        out_shape=[
            jax.ShapeDtypeStruct((n, r, d), qg.dtype),
            jax.ShapeDtypeStruct((n, r, d), kg.dtype),
            jax.ShapeDtypeStruct((n, r, d), vg.dtype),
        ],
        interpret=interpret,
    )(qg, kg, vg, dog, bias)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _attn_grouped(qg, kg, vg, seq_len, s_pad, g, causal, interpret):
    """[N, G·S_pad, D] grouped attention, N = B·H // G."""
    return _fwd_impl(
        qg, kg, vg, seq_len=seq_len, s_pad=s_pad, g=g, causal=causal,
        interpret=interpret,
    )


def _attn_grouped_fwd(qg, kg, vg, seq_len, s_pad, g, causal, interpret):
    out = _fwd_impl(
        qg, kg, vg, seq_len=seq_len, s_pad=s_pad, g=g, causal=causal,
        interpret=interpret,
    )
    return out, (qg, kg, vg)  # probabilities are recomputed, not saved


def _attn_grouped_bwd(seq_len, s_pad, g, causal, interpret, res, dog):
    qg, kg, vg = res
    return _bwd_impl(
        qg, kg, vg, dog, seq_len=seq_len, s_pad=s_pad, g=g, causal=causal,
        interpret=interpret,
    )


_attn_grouped.defvjp(_attn_grouped_fwd, _attn_grouped_bwd)


def _attn_call(q, k, v, *, causal, bh_block, interpret):
    """One (per-shard) kernel invocation over [B, S, H, D] operands."""
    b, s, h, d = q.shape
    # Pad S to the operand dtype's sublane tile: the (1, G·S_pad, D) block's
    # second-minor dim must tile (8, 128) for 4-byte and (16, 128) for
    # 2-byte dtypes — bf16 is the production dtype, and a 56-row bf16 block
    # is exactly the class of chip-only block-spec bug the flash kernel's
    # lse output hit on hardware (docs/RESULTS.md §4c).
    tile = 16 if jnp.dtype(q.dtype).itemsize < 4 else 8
    s_pad = -(-s // tile) * tile
    g = _bh_block(b * h, s_pad, bh_block)

    def to_grouped(x):
        x3 = x.transpose(0, 2, 1, 3).reshape(b * h, s, d)
        if s_pad != s:
            x3 = jnp.pad(x3, ((0, 0), (0, s_pad - s), (0, 0)))
        return x3.reshape(b * h // g, g * s_pad, d)

    outg = _attn_grouped(
        to_grouped(q), to_grouped(k), to_grouped(v), s, s_pad, g, causal,
        interpret,
    )
    out3 = outg.reshape(b * h, s_pad, d)[:, :s]
    return out3.reshape(b, h, s, d).transpose(0, 2, 1, 3)


def fused_attention_small(
    q, k, v, *, causal: bool = False, bh_block: int | None = None,
    interpret: bool | None = None, dp_mesh=None,
) -> jnp.ndarray:
    """Fused tiny-S attention over [B, S, H, D] inputs (the repo layout).

    Domain: S ≤ 128, head dim ≤ 128 — the regime where the flash kernel's
    block machinery was measured to LOSE to plain XLA (docs/RESULTS.md §4,
    round 3) and the [B, H, S, S] softmax chain is the byte cost. Outside
    the envelope the call raises on a TPU (``full_attention`` elsewhere).

    ``bh_block``: (batch·head) pairs fused per grid step (None = auto /
    ``MPT_ATTN_BH_BLOCK`` — see module docstring, bh-grouping).

    ``interpret``: None = Pallas on TPU, ``full_attention`` fallback
    elsewhere (or the Pallas interpreter when ``MPT_ATTN_INTERPRET`` is
    set — how tests drive the real kernel path on CPU); True forces the
    interpreter; False forces the compiled kernel.

    ``dp_mesh``: training/eval mesh. With >1 device on its leading (data)
    axis the call is ``shard_map``-partitioned over that axis — each
    device runs the Mosaic call on its batch shard (a Mosaic custom call
    has no GSPMD partitioning rule of its own). If the axis is ALREADY
    bound (the spmd-mode step's shard_map), the per-shard call runs
    directly — no nesting."""
    from mpi_pytorch_tpu.ops.ring_attention import full_attention
    from mpi_pytorch_tpu.utils.env import env_flag
    from mpi_pytorch_tpu.utils.hardware import tpu_backend

    b, s, h, d = q.shape
    n_data = 1
    if dp_mesh is not None:
        from mpi_pytorch_tpu.parallel.compat import axis_is_manual

        axis = dp_mesh.axis_names[0]
        if not axis_is_manual(axis):
            n_data = dp_mesh.shape[axis]
    if s > MAX_SEQ or d > MAX_HEAD_DIM or (n_data > 1 and b % n_data):
        # Outside the tiny-S envelope (flash/full own that regime), or a
        # batch that does not tile the data axis. On a TPU the caller asked
        # for this kernel and gets it or an error naming the shape.
        if tpu_backend():
            raise ValueError(
                f"fused-small attention: q {q.shape} is outside the kernel's "
                f"domain (S <= {MAX_SEQ}, head dim <= {MAX_HEAD_DIM}, batch "
                f"divisible by the {n_data}-device data axis); use "
                "--attn-impl full or flash for this shape"
            )
        return full_attention(q, k, v, causal=causal)
    if interpret is None:
        if env_flag("MPT_ATTN_INTERPRET"):
            interpret = True
        elif not tpu_backend():
            return full_attention(q, k, v, causal=causal)
        else:
            interpret = False

    call = functools.partial(
        _attn_call, causal=causal, bh_block=bh_block, interpret=interpret
    )
    if n_data > 1:
        from jax.sharding import PartitionSpec as P

        from mpi_pytorch_tpu.parallel.compat import shard_map

        axis = dp_mesh.axis_names[0]
        return shard_map(
            call,
            mesh=dp_mesh,
            in_specs=(P(axis), P(axis), P(axis)),
            out_specs=P(axis),
            check_vma=False,
        )(q, k, v)
    return call(q, k, v)
