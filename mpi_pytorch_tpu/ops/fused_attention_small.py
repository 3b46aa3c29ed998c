"""Pallas TPU kernels: dense attention in ONE VMEM pass — scores, softmax
and AV per head without the [B, H, S, S] tensors ever reaching HBM; a second
single-pass kernel recomputes the probabilities for dq/dk/dv.

Why (PERF.md sections 5 and 6, PR 25): with XLA's ``full_attention`` the
``attention`` scope of ViT-B/16 (S=196) was 47 % of the train step for ~4 %
of its FLOPs — float32 softmax fusions over 236 MB score tensors, ~100 layout
copies of ``[128,196,12,64]`` around the matmuls, and the probabilities of
all 12 layers kept for the backward. At these sizes one head's score tile is
VMEM-sized (196² float32 = 150 KB), so a plain (not online) softmax over whole
rows does the job; the flash kernel (``ops/flash_attention.py``) exists for
the sequences where it is not.

**Envelope** (``in_envelope``): the sequence padded to the dtype's sublane
tile is at most ``MAX_SEQ_PAD`` = 512 (one float32 ``[S_pad, S_pad]`` tile ≤
1 MB), the head dim at most ``MAX_HEAD_DIM`` = 128, and the heads tile the
128 lanes (Dh divides 128 and H·Dh is a multiple of 128, or H·Dh ≤ 128:
every ViT of the zoo). Inside it
``dense_attention`` — what ``attn_impl="full"`` executes — takes the kernel
on a TPU, chosen from the operands' shape alone; outside it, and on every
other backend, XLA's ``full_attention`` runs unchanged. ``fused_attention_small``
(``attn_impl="fused-small"``) is this kernel or a ``ValueError`` naming the
shape.

**Layout.** The projections' matmuls leave ``[B, S, H·Dh]``;
the kernels block THAT array — ``(bb, S, w)`` blocks on a ``(B/bb, H·Dh/w)``
grid, a block dim equal to the array's whole S needs no padding — and write
``o``/``dq``/``dk``/``dv`` in the same layout: no transpose or pad of an
operand exists outside the kernel. ``models/vit.py`` asks
``dense_attention_takes_kernel`` first and then never forms a
``[B, S, H, Dh]`` array: XLA tiles that one over (H, Dh), and each reshape
to or from rows was a copy (84 a step in ViT-B/16, 5.9 ms; PERF.md section
6). Callers with ``[B, S, H, Dh]`` operands are served too. Inside a
block the heads of one 128-lane group (two heads of 64) are told apart by a
lane mask on ONE operand of each matmul (the other head's products are exact
zeros), so every matmul contracts over aligned 128-lane tiles and no lane is
ever shifted; a model narrower than one tile (H·Dh ≤ 128) is one group.

- **Forward**: per head ``s = q·kᵀ·scale`` (float32 accumulation),
  ``p = exp(s − max)``, ``o = (p·v) / Σp`` — max, exp, sum and the
  normalisation in float32.
- **Backward**: recomputes ``p`` in VMEM (FLOPs are cheap, HBM bytes are
  not; the residuals are the primal q/k/v), then ``dp = do·vᵀ``,
  ``Δ = Σ_k p·dp``, ``ds = p·(dp − Δ)``, ``dq = ds·k·scale``,
  ``dk = dsᵀ·q·scale``, ``dv = pᵀ·do``.
- **Precision**: MXU operands carry the INPUT dtype with float32
  accumulation (``preferred_element_type``): bf16 inputs are bf16 values
  already, so these are the products XLA's float32 casts give, and ``p`` /
  ``ds`` are rounded to bf16 exactly where XLA's default-precision dot rounds
  them; float32 inputs keep float32 operands throughout.
- Twelve layers call the same two kernels at the same shape: each is one
  jitted function per shape, lowered once and called from every site.

Non-TPU backends take ``full_attention`` (identical math — the reference
these kernels are pinned against in tests/test_fused_attention_small.py via
interpret mode); ``MPT_ATTN_INTERPRET=1`` drives the real kernels through the
Pallas interpreter on CPU (how the tests run them).

Multi-chip: pass ``dp_mesh`` (the training/eval mesh) and the public
wrappers ``shard_map`` the kernel over the mesh's leading (data) axis —
each chip runs the Mosaic call on its own batch shard, identical to the
fused stem / fused eval head contract (ops/fused_stem.py "Multi-chip").
All operands are batch-sharded (no replicated params), so shard_map's
transpose needs no psum and gradients equal the single-call gradients
exactly. Inside an ALREADY shard_map'd context over the same axis (the
``--spmd-mode`` train step) the wrapper detects the bound axis
(``compat.axis_is_manual``) and runs the per-shard call directly.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from mpi_pytorch_tpu.ops.kernel_call import kernel_call

_NEG = -1e30  # finite mask value — exp(_NEG - m) underflows to exactly 0

# The envelope: one [S_pad, S_pad] float32 score tile is at most 1 MB, so
# scores, probabilities and their cotangents of a head sit in VMEM together.
MAX_SEQ_PAD = 512
MAX_HEAD_DIM = 128
_LANES = 128


def _seq_pad(s: int, dtype) -> int:
    """S rounded up to the dtype's sublane tile (8 rows of 4 bytes, 16 of 2)."""
    tile = 16 if jnp.dtype(dtype).itemsize < 4 else 8
    return -(-s // tile) * tile


def _lane_group(h: int, d: int) -> int | None:
    """Lanes one in-kernel slice takes: a 128-lane tile where the heads tile
    it (Dh divides 128 and H·Dh is a multiple of 128), all of H·Dh where that
    is no more than one tile, else None — heads that straddle lane tiles are
    not this kernel's."""
    if _LANES % d == 0 and (h * d) % _LANES == 0:
        return _LANES
    return h * d if h * d <= _LANES else None


def in_envelope(s: int, h: int, d: int, dtype) -> bool:
    """Whether a head's whole score tile fits VMEM and the heads tile the
    lanes: the one question the dispatch asks of a shape."""
    return (
        _seq_pad(s, dtype) <= MAX_SEQ_PAD
        and d <= MAX_HEAD_DIM
        and _lane_group(h, d) is not None
    )


# ---------------------------------------------------------------------------
# Rows layout: [B, S, H·Dh] blocks, heads told apart by lane masks.
# ---------------------------------------------------------------------------


def _rows_block(b: int, hd: int, gw: int) -> tuple[int, int]:
    """(images, lanes) of one grid step: ``_ROWS_UNROLL`` heads, as whole
    lane groups of one image first, then more images."""
    heads_per_group = 2  # the common case (Dh=64); a unit of body size only
    groups = _largest_divisor(hd // gw, max(1, _ROWS_UNROLL // heads_per_group))
    images = _largest_divisor(b, max(1, _ROWS_UNROLL // (heads_per_group * groups)))
    return images, gw * groups


def _largest_divisor(n: int, cap: int) -> int:
    return next(g for g in range(min(n, cap), 0, -1) if n % g == 0)


# Heads unrolled into one grid step's body: enough that the ~0.35 µs a grid
# step costs is small against its work, few enough that the body stays a
# few thousand instructions (measured on v5e: PERF.md section 6, PR 25).
_ROWS_UNROLL = 12


def _head_masks(gw: int, d: int):
    """One [1, gw] lane mask per head of a group (None: the group IS a head)."""
    if gw == d:
        return [None]
    lane = lax.broadcasted_iota(jnp.int32, (1, gw), 1)
    return [(lane >= i * d) & (lane < (i + 1) * d) for i in range(gw // d)]


def _only(mask, x):
    """``x`` with every lane outside ``mask`` zeroed: a matmul that contracts
    over the group's lanes then sees this head alone."""
    return x if mask is None else jnp.where(mask, x, jnp.zeros_like(x))


def _causal_mask(s: int, causal: bool):
    if not causal:
        return None
    rows = lax.broadcasted_iota(jnp.int32, (s, s), 0)
    return lax.broadcasted_iota(jnp.int32, (s, s), 1) <= rows


def _exp_scores(q, k_head, scale, tri):
    """exp(s − rowmax) and its row sums for one head, float32: ``k_head`` is
    k with the other heads' lanes zeroed."""
    s = lax.dot_general(
        q, k_head, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # [S, S]
    if tri is not None:
        s = jnp.where(tri, s, _NEG)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    return p, jnp.sum(p, axis=-1, keepdims=True)  # ≥ 1 visible key: l > 0


def _rows_fwd_kernel(q_ref, k_ref, v_ref, o_ref, *, scale, d, gw, causal):
    bb, s, w = q_ref.shape
    masks, tri = _head_masks(gw, d), _causal_mask(s, causal)
    for i in range(bb):
        for j in range(w // gw):
            lanes = slice(j * gw, (j + 1) * gw)
            q, k, v = q_ref[i, :, lanes], k_ref[i, :, lanes], v_ref[i, :, lanes]
            out = None
            for mask in masks:
                p, l = _exp_scores(q, _only(mask, k), scale, tri)
                pv = lax.dot_general(
                    p.astype(v.dtype), _only(mask, v), (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ) * (1.0 / l)  # this head's lanes; exact zeros in the others
                out = pv if out is None else out + pv
            o_ref[i, :, lanes] = out.astype(o_ref.dtype)


def _rows_bwd_kernel(q_ref, k_ref, v_ref, do_ref, dq_ref, dk_ref, dv_ref,
                     *, scale, d, gw, causal):
    bb, s, w = q_ref.shape
    masks, tri = _head_masks(gw, d), _causal_mask(s, causal)
    for i in range(bb):
        for j in range(w // gw):
            lanes = slice(j * gw, (j + 1) * gw)
            q, k, v = q_ref[i, :, lanes], k_ref[i, :, lanes], v_ref[i, :, lanes]
            do = do_ref[i, :, lanes]
            dq = dk = dv = None
            for mask in masks:
                k_head = _only(mask, k)
                p, l = _exp_scores(q, k_head, scale, tri)
                p = p * (1.0 / l)  # normalized probabilities [S, S]
                dp = lax.dot_general(
                    do, _only(mask, v), (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )  # do·vᵀ
                delta = jnp.sum(p * dp, axis=-1, keepdims=True)  # = Σ_d do·o
                ds = (p * (dp - delta)).astype(q.dtype)
                dq_h = lax.dot_general(
                    ds, k_head, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                dk_h = lax.dot_general(
                    ds, _only(mask, q), (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                dv_h = lax.dot_general(
                    p.astype(do.dtype), _only(mask, do), (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                dq = dq_h if dq is None else dq + dq_h
                dk = dk_h if dk is None else dk + dk_h
                dv = dv_h if dv is None else dv + dv_h
            dq_ref[i, :, lanes] = (dq * scale).astype(dq_ref.dtype)
            dk_ref[i, :, lanes] = (dk * scale).astype(dk_ref.dtype)
            dv_ref[i, :, lanes] = dv.astype(dv_ref.dtype)


def _rows_pallas(name, kernel, operands, n_out, *, d, causal, interpret):
    """One Pallas call over [B, S, H·Dh] operands, blocked as they lie."""
    b, s, hd = operands[0].shape
    gw = _lane_group(hd // d, d)
    bb, w = _rows_block(b, hd, gw)
    block = pl.BlockSpec((bb, s, w), lambda i, j: (i, 0, j))
    out = jax.ShapeDtypeStruct((b, s, hd), operands[0].dtype)
    return kernel_call(
        name,
        functools.partial(kernel, scale=d**-0.5, d=d, gw=gw, causal=causal),
        grid=(b // bb, hd // w),
        in_specs=[block] * len(operands),
        out_specs=block if n_out == 1 else [block] * n_out,
        out_shape=out if n_out == 1 else [out] * n_out,
        interpret=interpret,
    )(*operands)


# jitted: the call sites of one shape (a model's layers) share one lowered body.
@functools.partial(jax.jit, static_argnames=("d", "causal", "interpret"))
def _rows_fwd(q, k, v, *, d, causal, interpret):
    return _rows_pallas(
        "attn_small_fwd", _rows_fwd_kernel, (q, k, v), 1,
        d=d, causal=causal, interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("d", "causal", "interpret"))
def _rows_bwd(q, k, v, do, *, d, causal, interpret):
    return _rows_pallas(
        "attn_small_bwd", _rows_bwd_kernel, (q, k, v, do), 3,
        d=d, causal=causal, interpret=interpret,
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _attn_rows(q, k, v, d, causal, interpret):
    """[B, S, H·Dh] attention over heads of ``d`` lanes."""
    return _rows_fwd(q, k, v, d=d, causal=causal, interpret=interpret)


def _attn_rows_fwd(q, k, v, d, causal, interpret):
    out = _rows_fwd(q, k, v, d=d, causal=causal, interpret=interpret)
    return out, (q, k, v)  # probabilities are recomputed, not saved


def _attn_rows_bwd(d, causal, interpret, res, do):
    return _rows_bwd(*res, do, d=d, causal=causal, interpret=interpret)


_attn_rows.defvjp(_attn_rows_fwd, _attn_rows_bwd)


# ---------------------------------------------------------------------------
# Public entry points.
# ---------------------------------------------------------------------------


def _data_axis_size(dp_mesh) -> int:
    """Devices the wrapper must split the batch over: the mesh's leading
    (data) axis, or 1 with no mesh or inside a shard_map that already did."""
    if dp_mesh is None:
        return 1
    from mpi_pytorch_tpu.parallel.compat import axis_is_manual

    axis = dp_mesh.axis_names[0]
    return 1 if axis_is_manual(axis) else dp_mesh.shape[axis]


def _kernel(q, k, v, *, h, d, causal, interpret, dp_mesh, n_data):
    """The kernel over ``h`` heads of ``d`` lanes — operands [B, S, H, D] or
    [B, S, H·D], read as rows either way (free where they are rows already),
    the result in the operands' form — split over the data axis where there
    is one."""

    def call(q, k, v):
        return _attn_rows(q, k, v, d, causal, interpret)

    if n_data > 1:
        from jax.sharding import PartitionSpec as P

        from mpi_pytorch_tpu.parallel.compat import shard_map

        axis = dp_mesh.axis_names[0]
        call = shard_map(
            call,
            mesh=dp_mesh,
            in_specs=(P(axis), P(axis), P(axis)),
            out_specs=P(axis),
            check_vma=False,
        )
    rows = q.shape[:2] + (h * d,)
    return call(q.reshape(rows), k.reshape(rows), v.reshape(rows)).reshape(q.shape)


def _interpret_mode() -> bool | None:
    """False: the compiled kernel (a TPU); True: the Pallas interpreter
    (``MPT_ATTN_INTERPRET``, how the tests drive the kernel on the CPU);
    None: neither — XLA's ``full_attention`` is the path."""
    from mpi_pytorch_tpu.utils.env import env_flag
    from mpi_pytorch_tpu.utils.hardware import tpu_backend

    if env_flag("MPT_ATTN_INTERPRET"):
        return True
    return False if tpu_backend() else None


def _dispatch(b, s, h, d, dtype, dp_mesh):
    """(why not the kernel — "" where it runs, interpret, data-axis size) for
    dense attention of this shape; each distinct choice leaves one
    ``attn/dispatch`` instant in the run's trace."""
    from mpi_pytorch_tpu.obs import trace as obs_trace

    n_data = _data_axis_size(dp_mesh)
    interpret = _interpret_mode()
    if interpret is None:
        why = "backend"
    elif not in_envelope(s, h, d, dtype):
        why = "outside_envelope"
    elif b % n_data:
        why = "batch_not_divisible"
    else:
        why = ""
    args = {"path": "xla" if why else "kernel", "S": s, "Dh": d, "batch": b}
    if why:
        args["why"] = why
    obs_trace.current().instant("attn/dispatch", args, once=True)
    return why, interpret, n_data


def dense_attention_takes_kernel(b, s, h, d, dtype, dp_mesh=None) -> bool:
    """Whether ``dense_attention`` runs the kernel for this shape: a caller
    that can then hands it ``[B, S, H·D]`` operands, as its projections'
    matmuls leave them (models/vit.py), and no layout copy is left between
    the matmuls and the kernel."""
    return not _dispatch(b, s, h, d, dtype, dp_mesh)[0]


def dense_attention(
    q, k, v, *, causal: bool = False, dp_mesh=None, num_heads: int | None = None
) -> jnp.ndarray:
    """Exact dense attention — what ``attn_impl="full"`` executes — over
    [B, S, H, D] operands, or over [B, S, H·D] ones of ``num_heads`` heads
    (the result has the operands' form). The path is chosen from the
    operands' shape: the single-pass kernel on a TPU for every shape inside
    the envelope whose batch tiles the data axis, XLA's ``full_attention``
    otherwise and on every other backend. Each distinct choice leaves one
    ``attn/dispatch`` instant in the run's trace (``path`` ``kernel`` |
    ``xla``, and ``why`` not)."""
    from mpi_pytorch_tpu.ops.ring_attention import full_attention

    shape = q.shape
    b, s = shape[:2]
    h = num_heads if q.ndim == 3 else shape[2]
    d = shape[-1] // h if q.ndim == 3 else shape[3]
    why, interpret, n_data = _dispatch(b, s, h, d, q.dtype, dp_mesh)
    if not why:
        return _kernel(
            q, k, v, h=h, d=d, causal=causal, interpret=interpret,
            dp_mesh=dp_mesh, n_data=n_data,
        )
    heads = (b, s, h, d)
    return full_attention(
        q.reshape(heads), k.reshape(heads), v.reshape(heads), causal=causal
    ).reshape(shape)


def fused_attention_small(
    q, k, v, *, causal: bool = False, interpret: bool | None = None,
    dp_mesh=None,
) -> jnp.ndarray:
    """The single-pass kernel over [B, S, H, D] inputs, asked for by name
    (``attn_impl="fused-small"``, the A/B tools): on a TPU it is this kernel
    or a ``ValueError`` naming the shape; elsewhere ``full_attention``.

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
    from mpi_pytorch_tpu.utils.hardware import tpu_backend

    b, s, h, d = q.shape
    n_data = _data_axis_size(dp_mesh)
    if not in_envelope(s, h, d, q.dtype) or b % n_data:
        # Outside the envelope (flash owns that regime), or a batch that
        # does not tile the data axis. On a TPU the caller asked for this
        # kernel and gets it or an error naming the shape.
        if tpu_backend():
            raise ValueError(
                f"fused-small attention: q {q.shape} is outside the kernel's "
                f"domain (S padded to the sublane tile <= {MAX_SEQ_PAD}, head "
                f"dim <= {MAX_HEAD_DIM} with heads that tile {_LANES}-lane "
                f"groups, batch divisible by the {n_data}-device data axis); "
                "use --attn-impl full or flash for this shape"
            )
        return full_attention(q, k, v, causal=causal)
    if interpret is None:
        interpret = _interpret_mode()
        if interpret is None:
            return full_attention(q, k, v, causal=causal)
    return _kernel(
        q, k, v, h=h, d=d, causal=causal, interpret=interpret,
        dp_mesh=dp_mesh, n_data=n_data,
    )
