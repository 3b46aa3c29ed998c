"""Pallas TPU kernel: fused stem tail — BN-affine + ReLU + 3×3/s2/p1
max-pool (+ window argmax) in one VMEM pass, index-unpool backward.

Why this op exists (docs/RESULTS.md §4d): the resnet18 headline HLO's five
largest byte rows are ALL the stem tail around ``jvp(ResNet)/bn1..max_pool``
(named in ``docs/hlo_resnet18_r5.txt``; B=2048, 128px ⇒ conv1 out
[2048,64,64,64] bf16 = 1 073 MB):

=========================  ========  ==========================================
instruction                bytes/MB  role
=========================  ========  ==========================================
``fusion.29``                 2 147  BN-apply + relu fwd (read conv, write act)
``fusion.765``                1 342  reduce_window max fwd (read act, write 268)
``select_and_scatter.9``      2 416  maxpool bwd (re-reads the FULL activation
                                     to re-discover the winner it knew at fwd)
``fusion.1``                  2 147  bn1 bwd reduces (read grad + activation)
``fusion.11``                 2 348  conv1 wgrad (+ inline BN-dx)
=========================  ========  ==========================================

≈10.4 GB — 12.7 ms of the 62.3 ms bandwidth bound — and XLA's own cost
model prices the fusions well ABOVE those bounds (``estimated_cycles``
⇒ ~3.6–5.8 ms each at ~1.67 GHz, vs 1.3–2.9 ms bounds), with
select-and-scatter's windowed scan worse still.

This kernel pair removes the intermediate activation tensor entirely:

- forward: read conv1 output y once, apply the FOLDED batchnorm affine
  (a = γ·rsqrt(var+ε), b = β − μ·a) in f32, relu, 3×3/s2/p1 max-pool with
  a first-match window argmax, all in VMEM; write the pooled [B,32,32,64]
  activation + a window-offset index. ≈1.6 GB, replacing fusion.29 +
  fusion.765's 3.5 GB.
- backward: the pool+relu gradient is a static phase-GATHER through the
  saved index (each input position is covered by ≤4 windows; offset
  parity decides which — the in-VMEM version of round 4's XLA-level
  phase decomposition (the since-deleted ``ops/pooling.py``), which LOST
  as an XLA graph because the interleave copies would not fuse but costs
  nothing inside one kernel).
  The relu mask is ``pooled > 0`` (the window max is post-relu: max > 0
  ⟺ the winner was a live activation). The same pass accumulates the
  BN reduces Σdu and Σdu·y across the sequential TPU grid, replacing
  select-and-scatter + fusion.1's 4.6 GB with ≈2.8 GB and NO
  select-and-scatter.

LAYOUT IS THE WHOLE GAME (three measured failures preceded this design):

1. Natural [B,H,W,C] per-image blocks: C=64 half-fills every 128-lane
   vreg and the 9-candidate phase build needs sublane reshapes — the
   kernel ran 10× over its byte bound and the headline step LOST 50%.
2. W-pair lane packing ([B,H,W/2,128]): full vregs, kernel ≈ parity with
   the XLA chain it replaces — but the custom call's required row-major
   {3,2,1,0} operand/result layouts FIGHT the backbone's batch-minor
   {0,3,2,1} preference, so XLA wrapped the call in ~3 ms layout copies
   at EVERY residual conv (measured: step 85 → 140 ms despite the
   kernel itself winning its microbench).
3. This version: the kernel operates on logically TRANSPOSED arrays
   [H, W, C, B] — whose row-major layout is physically IDENTICAL to the
   batch-minor layout XLA already prefers for every conv activation
   ("all batch in lanes"). The wrapper's transposes are layout bitcasts,
   the backbone keeps its layouts, and in-kernel the batch rides the
   lanes (128/block), channels the sublanes (8/block), and both spatial
   dims are outer vector axes where shifts, subsampling (reshape-split +
   unit slice + squeeze — the one 2× pattern that passes Mosaic
   verification; strided vector slices and N-D gathers both fail), and
   the backward interleave (stack+reshape) are all cheap probed ops.

The pooling itself is a SEPARABLE column-then-row pass; column-first
preserves select-and-scatter's row-major first-match tie semantics
exactly (the row fold picks minimal dh among value-maxima, and within
that dh the column fold already picked minimal dw — lexicographic
(dh, dw), pinned on tie-heavy inputs in tests/test_fused_stem.py).

Reference parity: this fuses the torch stem sequence
``bn1 → relu → maxpool(3,2,1)`` of the reference's resnet family
(``/root/reference/models.py:30-45`` via torchvision resnet18/34);
semantics pinned against the unfused XLA composition in
tests/test_fused_stem.py (values AND gradients).

Non-TPU backends fall back to the identical-math XLA composition
(``_reference_impl``), mirroring ``ops/flash_attention.py``'s gating;
``MPT_STEM_INTERPRET=1`` drives the real kernel through the Pallas
interpreter on CPU (how the tests run it).

Multi-chip: pass ``dp_mesh`` (the training mesh) and the public wrapper
``shard_map``s the kernel over the mesh's leading (data) axis — each chip
runs the Mosaic call on its own batch shard, which is exactly the shape
regime the kernel was tuned for, instead of XLA replicating the call's
operands behind an activation all-gather (a Mosaic custom call has no
GSPMD partitioning rule of its own). The BN affine (a, b) stays replicated
(``P()``), and shard_map's transpose psums the per-shard da/db cotangents,
so gradients equal the single-call gradients exactly. Inside an ALREADY
shard_map'd context over the same axis (the ``--spmd-mode`` train step),
the wrapper detects the bound axis (``compat.axis_is_manual``) and runs
the single per-shard call directly — so the mesh can be threaded
unconditionally and spmd-mode VALIDATION (a plain-jit eval step over the
same model) still gets the partitioned call instead of a global-batch
replicated one.

Byte-bound levers (docs/RESULTS.md §4d; the fwd runs 4.25 ms vs a 2.0 ms
byte bound, the bwd ~6.1 vs 3.6): three candidates are implemented behind
env gates, each microbenched by ``tools/bench_stem.py --levers`` and
recorded as a ship-or-rejection row in §4d —

- ``MPT_STEM_LANES=256``    — 256-image batch block (two full vregs per op);
- ``MPT_STEM_IDX_INT8=1``   — int8 window-argmax storage (k ∈ [0, 8] needs
  4 bits; halves the idx tensor's HBM traffic vs bf16);
- ``MPT_STEM_C_BLOCK=16``   — 16-channel sublane block (half the grid
  steps at the same per-step tile bytes).

All three are exact re-tilings that preserve the reference semantics, pinned
per-lever (values and all three gradients) in tests/test_fused_stem.py.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from mpi_pytorch_tpu.ops.kernel_call import kernel_call

_NEG = float("-inf")

# Pool geometry is fixed: the torchvision stem (3×3, stride 2, pad 1).
_WIN, _STRIDE, _PAD = 3, 2, 1

# Channels per grid step (sublane dim: 8 = one full f32 sublane tile;
# MPT_STEM_C_BLOCK=16 is the measured-lever override — see module docstring).
_C_BLOCK = 8


def _levers() -> dict:
    """The §4d byte-bound lever configuration, read from the env at trace
    time (defaults = the shipped round-5 kernel)."""
    from mpi_pytorch_tpu.utils.env import env_flag

    return {
        "c_block": int(os.environ.get("MPT_STEM_C_BLOCK", str(_C_BLOCK))),
        "lanes": int(os.environ.get("MPT_STEM_LANES", "128")),
        "idx_int8": env_flag("MPT_STEM_IDX_INT8"),
    }

# Mosaic's stack allocation for the fold's temporaries exceeds the 16 MB
# default scoped-vmem budget at useful block sizes; v5e has 128 MB
# physical VMEM, so grant headroom instead of shrinking blocks. 120 MiB:
# the two levers that double the tile (MPT_STEM_LANES=256,
# MPT_STEM_C_BLOCK=16) need 111.8 MiB and were refused under a 100 MiB
# limit (Mosaic, libtpu 0.0.34, v5e — PR 21).
_VMEM_LIMIT = 120 * 1024 * 1024


def _tpu_params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT)


def _reference_impl(y, a, b):
    """Unfused XLA composition — the semantics this kernel is pinned to."""
    z = jax.nn.relu(y.astype(jnp.float32) * a + b)
    pooled = nn_max_pool_f32(z)
    return pooled.astype(y.dtype)


def nn_max_pool_f32(z):
    return lax.reduce_window(
        z, _NEG, lax.max,
        (1, _WIN, _WIN, 1), (1, _STRIDE, _STRIDE, 1),
        ((0, 0), (_PAD, _PAD), (_PAD, _PAD), (0, 0)),
    )


# --- in-kernel building blocks (T-space: [H, W, C_blk, B_blk]) -----------
# All operate on the two OUTER vector axes (H=0, W=1); the minor (sublane,
# lane) dims are never restructured.


def _shift(x, axis, by, fill):
    """t[i] = x[i + by] along an outer axis, ``fill`` off the edge —
    static pad + unit-offset slice."""
    n = x.shape[axis]
    pad = [(0, 0)] * x.ndim
    pad[axis] = (max(0, -by), max(0, by))
    sl = [slice(None)] * x.ndim
    sl[axis] = slice(max(0, by), max(0, by) + n)
    return jnp.pad(x, pad, constant_values=fill)[tuple(sl)]


def _even_odd(x, axis):
    """(x[0::2], x[1::2]) along an outer axis via reshape-SPLIT + unit
    slice + squeeze — the one 2× subsampling pattern that passes Mosaic
    verification (strided vector slices and N-D gathers both fail)."""
    n = x.shape[axis]
    shape = x.shape[:axis] + (n // 2, 2) + x.shape[axis + 1 :]
    x5 = x.reshape(shape)

    def take(o):
        starts = (0,) * len(shape)
        limits = list(shape)
        limits[axis + 1] = o + 1
        starts = list(starts)
        starts[axis + 1] = o
        sl = lax.slice(x5, tuple(starts), tuple(limits))
        return sl.reshape(x.shape[: axis] + (n // 2,) + x.shape[axis + 1 :])

    return take(0), take(1)


def _interleave(e, o, axis):
    """Inverse of ``_even_odd``: t[2i]=e[i], t[2i+1]=o[i]."""
    st = jnp.stack([e, o], axis=axis + 1)
    n = e.shape[axis]
    return st.reshape(e.shape[:axis] + (2 * n,) + e.shape[axis + 1 :])


def _pool_argmax_t(z):
    """3×3/s2/p1 max-pool + first-match argmax of ``z`` [H, W, C, B]
    (T-space). Returns (pooled [H/2, W/2, C, B], k [same], k = dh·3+dw).
    Runs in ``z.dtype`` (float32 from ``_fwd_kernel``)."""
    neg = jnp.asarray(_NEG, z.dtype)
    # --- column pass at every row: fold over dw ∈ {0,1,2} -------------
    cm = _shift(z, 1, -1, neg)  # z[w-1]  (dw=0 candidate)
    cp = _shift(z, 1, +1, neg)  # z[w+1]  (dw=2)
    v = cm
    dw = jnp.zeros_like(z)
    better = z > v  # strict: the FIRST max keeps the window
    v = jnp.maximum(v, z)  # NaN-propagating, like reduce_window's lax.max
    dw = jnp.where(better, 1.0, dw)
    better = cp > v
    v = jnp.maximum(v, cp)
    dw = jnp.where(better, 2.0, dw)
    # keep even columns (the window centers, w = 2·ow)
    v, _ = _even_odd(v, 1)
    dw, _ = _even_odd(dw, 1)
    # --- row pass: fold over dh ∈ {0,1,2}, carrying (value, dw) -------
    ev, od = _even_odd(v, 0)        # rows 2h' (dh=1), 2h'+1 (dh=2)
    edw, odw = _even_odd(dw, 0)
    bv = _shift(od, 0, -1, neg)     # rows 2h'-1 (dh=0)
    bdw = _shift(odw, 0, -1, 0.0)
    bdh = jnp.zeros_like(bv)
    better = ev > bv
    bv = jnp.maximum(bv, ev)
    bdh = jnp.where(better, 1.0, bdh)
    bdw = jnp.where(better, edw, bdw)
    better = od > bv
    bv = jnp.maximum(bv, od)
    bdh = jnp.where(better, 2.0, bdh)
    bdw = jnp.where(better, odw, bdw)
    return bv, bdh * 3.0 + bdw


def _fwd_kernel(yt_ref, a_ref, b_ref, out_ref, idx_ref):
    yt = yt_ref[...].astype(jnp.float32)  # [H, W, C_blk, B_blk]
    a = a_ref[...].reshape(1, 1, a_ref.shape[0], 1)
    b = b_ref[...].reshape(1, 1, b_ref.shape[0], 1)
    z = jax.nn.relu(yt * a + b)
    best, bestk = _pool_argmax_t(z)
    out_ref[...] = best.astype(out_ref.dtype)
    if idx_ref is not None:
        idx_ref[...] = bestk.astype(idx_ref.dtype)


def _primal_kernel(yt_ref, a_ref, b_ref, out_ref):
    _fwd_kernel(yt_ref, a_ref, b_ref, out_ref, None)


def _bwd_kernel(g_ref, idx_ref, pooled_ref, yt_ref, a_ref,
                dy_ref, da_ref, db_ref, da_scr, db_scr, *, n_c, n_b, nc):
    jc, ib = pl.program_id(0), pl.program_id(1)

    @pl.when((jc == 0) & (ib == 0))
    def _init():
        da_scr[:] = jnp.zeros_like(da_scr)
        db_scr[:] = jnp.zeros_like(db_scr)

    g = g_ref[...].astype(jnp.float32)  # [H2, W2, C_blk, B_blk]
    idx = idx_ref[...].astype(jnp.float32)
    live = pooled_ref[...].astype(jnp.float32) > 0  # window max post-relu
    gm = jnp.where(live, g, 0.0)

    def d(k):
        return jnp.where(idx == float(k), gm, 0.0)

    # Input parity phases: position (2m+i, 2n+j) is covered by ≤4 windows;
    # offset parity decides which — a static gather over the masked pooled
    # gradient, assembled by outer-axis interleaves.
    ee = d(4)
    eo = d(5) + _shift(d(3), 1, +1, 0.0)
    oe = d(7) + _shift(d(1), 0, +1, 0.0)
    oo = (d(8) + _shift(d(6), 1, +1, 0.0) + _shift(d(2), 0, +1, 0.0)
          + _shift(_shift(d(0), 0, +1, 0.0), 1, +1, 0.0))
    even_rows = _interleave(ee, eo, 1)  # [H2, W, C_blk, B_blk]
    odd_rows = _interleave(oe, oo, 1)
    du = _interleave(even_rows, odd_rows, 0)  # [H, W, C_blk, B_blk]

    yt = yt_ref[...].astype(jnp.float32)
    a = a_ref[...].reshape(1, 1, a_ref.shape[0], 1)
    dy_ref[...] = (du * a).astype(dy_ref.dtype)
    red_a = jnp.sum(du * yt, axis=(0, 1, 3))  # [C_blk]
    red_b = jnp.sum(du, axis=(0, 1, 3))
    # Accumulate into lane jc via a one-hot mask: a dynamic lane index in
    # a scratch store is not provably 128-aligned for Mosaic.
    onehot = (
        lax.broadcasted_iota(jnp.int32, (nc, 128), 1) == jc
    ).astype(jnp.float32)
    da_scr[:, :] += red_a[:, None] * onehot
    db_scr[:, :] += red_b[:, None] * onehot

    @pl.when((jc == n_c - 1) & (ib == n_b - 1))
    def _emit():
        da_ref[:] = da_scr[:]
        db_ref[:] = db_scr[:]


def _lane_block(bsz: int, max_lanes: int = 128) -> int:
    """Batch images per grid step (the lane dim): a full 128-lane tile
    when the batch allows it — or two (MPT_STEM_LANES=256, the §4d lever:
    every vector op then covers two full vregs per sublane row)."""
    for nb in (256, 128, 64, 32, 16, 8, 4, 2):
        if nb <= max_lanes and bsz % nb == 0:
            return nb
    return 1


def _check_shapes(y, a, b):
    bsz, h, w, c = y.shape
    if h % 2 or w % 2:
        raise ValueError(f"fused stem needs even spatial dims, got {h}x{w}")
    if a.shape != (c,) or b.shape != (c,):
        raise ValueError(f"affine shape mismatch: {a.shape}/{b.shape} vs C={c}")


def _fwd_impl(yt, a, b, *, want_idx, interpret):
    lev = _levers()
    h, w, c, bsz = yt.shape
    nb, nc = _lane_block(bsz, lev["lanes"]), lev["c_block"]
    a2 = a.astype(jnp.float32).reshape(c, 1)
    b2 = b.astype(jnp.float32).reshape(c, 1)
    h2, w2 = h // 2, w // 2
    in_specs = [
        pl.BlockSpec((h, w, nc, nb), lambda j, i: (0, 0, j, i)),
        pl.BlockSpec((nc, 1), lambda j, i: (j, 0)),
        pl.BlockSpec((nc, 1), lambda j, i: (j, 0)),
    ]
    out_spec = pl.BlockSpec((h2, w2, nc, nb), lambda j, i: (0, 0, j, i))
    grid = (c // nc, bsz // nb)
    idx_dtype = jnp.int8 if lev["idx_int8"] else jnp.bfloat16
    if want_idx:
        return kernel_call(
            "stem_fwd",
            _fwd_kernel,
            grid=grid,
            in_specs=in_specs,
            out_specs=[out_spec, out_spec],
            out_shape=[
                jax.ShapeDtypeStruct((h2, w2, c, bsz), yt.dtype),
                jax.ShapeDtypeStruct((h2, w2, c, bsz), idx_dtype),
            ],
            interpret=interpret,
            compiler_params=_tpu_params() if not interpret else None,
        )(yt, a2, b2)
    return kernel_call(
        "stem_fwd",  # one kernel to a trace reader, with or without idx
        _primal_kernel,
        name="stem_fwd_primal",
        grid=grid,
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((h2, w2, c, bsz), yt.dtype),
        interpret=interpret,
        compiler_params=_tpu_params() if not interpret else None,
    )(yt, a2, b2)


def _bwd_impl(gt, idxt, pooledt, yt, a, *, interpret):
    from jax.experimental.pallas import tpu as pltpu

    lev = _levers()
    h, w, c, bsz = yt.shape
    nb, nc = _lane_block(bsz, lev["lanes"]), lev["c_block"]
    h2, w2 = h // 2, w // 2
    a2 = a.astype(jnp.float32).reshape(c, 1)
    small = pl.BlockSpec((h2, w2, nc, nb), lambda j, i: (0, 0, j, i))
    big = pl.BlockSpec((h, w, nc, nb), lambda j, i: (0, 0, j, i))
    dyt, da8, db8 = kernel_call(
        "stem_bwd",
        functools.partial(_bwd_kernel, n_c=c // nc, n_b=bsz // nb, nc=nc),
        grid=(c // nc, bsz // nb),
        in_specs=[
            small,  # g
            small,  # idx
            small,  # pooled
            big,    # yt
            pl.BlockSpec((nc, 1), lambda j, i: (j, 0)),
        ],
        out_specs=[
            big,
            pl.BlockSpec((nc, 128), lambda j, i: (0, 0)),
            pl.BlockSpec((nc, 128), lambda j, i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((h, w, c, bsz), yt.dtype),
            jax.ShapeDtypeStruct((nc, 128), jnp.float32),
            jax.ShapeDtypeStruct((nc, 128), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((nc, 128), jnp.float32),
            pltpu.VMEM((nc, 128), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=_tpu_params() if not interpret else None,
    )(gt, idxt, pooledt, yt, a2)
    # scr[s, j] = grad for channel j*nc + s.
    n_c = c // nc
    da = jnp.transpose(da8[:, :n_c]).reshape(c)
    db = jnp.transpose(db8[:, :n_c]).reshape(c)
    return dyt, da, db


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _stem_pool_t(yt, a, b, interpret):
    return _fwd_impl(yt, a, b, want_idx=False, interpret=interpret)


def _stem_pool_t_fwd(yt, a, b, interpret):
    pooled, idx = _fwd_impl(yt, a, b, want_idx=True, interpret=interpret)
    return pooled, (yt, a, pooled, idx)


def _stem_pool_t_bwd(interpret, res, gt):
    yt, a, pooledt, idxt = res
    dyt, da, db = _bwd_impl(gt, idxt, pooledt, yt, a, interpret=interpret)
    return dyt, da.astype(a.dtype), db.astype(a.dtype)


_stem_pool_t.defvjp(_stem_pool_t_fwd, _stem_pool_t_bwd)


def _stem_call(y, a, b, interpret):
    """One (per-shard) kernel invocation: T-space transpose wrappers around
    the custom-vjp Pallas pair."""
    yt = jnp.transpose(y, (1, 2, 3, 0))
    outt = _stem_pool_t(yt, a, b, interpret)
    return jnp.transpose(outt, (3, 0, 1, 2))


def stem_affine_relu_pool(y, a, b, *, interpret: bool | None = None, dp_mesh=None):
    """``max_pool3x3s2p1(relu(y·a + b))`` fused in VMEM, differentiable.

    ``y``: [B, H, W, C] (H, W even), any float dtype (bf16 in
    production). ``a``/``b``: f32 [C] — the FOLDED batchnorm affine.
    Returns [B, H/2, W/2, C] in ``y.dtype``.

    Internally the kernels run in T-space [H, W, C, B]: the surrounding
    transposes are layout BITCASTS on TPU because T-space row-major ==
    the batch-minor physical layout XLA already prefers for conv
    activations (see module docstring, failure #2).

    ``interpret``: None = Pallas kernel on TPU, XLA composition elsewhere
    (or the Pallas interpreter when ``MPT_STEM_INTERPRET`` is set); True
    forces the interpreter; False forces the compiled kernel.

    ``dp_mesh``: the training/eval mesh. When its leading (data) axis has
    >1 device, the kernel call is ``shard_map``-partitioned over that axis
    — each device runs the Mosaic call on its batch shard (see module
    docstring, Multi-chip). The batch must divide the axis (the trainer
    validates this; on a TPU an indivisible batch raises rather than
    silently replicating the call or becoming the XLA composition). If the
    axis is ALREADY
    bound (calling from inside the spmd-mode step's shard_map), the
    per-shard call runs directly — no nesting."""
    from mpi_pytorch_tpu.utils.hardware import tpu_backend

    _check_shapes(y, a, b)
    n_data = 1
    if dp_mesh is not None:
        from mpi_pytorch_tpu.parallel.compat import axis_is_manual

        axis = dp_mesh.axis_names[0]
        # Inside a shard_map over the data axis (the spmd-mode train step)
        # the operands are already per-shard and a nested wrap over the
        # same axis would be an error — run the single call directly.
        if not axis_is_manual(axis):
            n_data = dp_mesh.shape[axis]
    if y.shape[-1] % _levers()["c_block"] or (n_data > 1 and y.shape[0] % n_data):
        # Channel count must tile the sublane block (every 7×7 stem in the
        # zoo has C=64) and the batch must tile the data axis. On a TPU the
        # caller asked for the kernel and gets it or an error; elsewhere
        # the XLA composition is the path anyway.
        if tpu_backend():
            raise ValueError(
                f"fused stem: input {y.shape} does not tile the kernel "
                f"(channels must divide by {_levers()['c_block']}, batch by "
                f"the {n_data}-device data axis); pass --fused-stem false "
                "for this shape"
            )
        return _reference_impl(y, a, b)
    if interpret is None:
        from mpi_pytorch_tpu.utils.env import env_flag

        if env_flag("MPT_STEM_INTERPRET"):
            interpret = True
        elif not tpu_backend():
            return _reference_impl(y, a, b)
        else:
            interpret = False
    a32, b32 = a.astype(jnp.float32), b.astype(jnp.float32)
    if n_data > 1:
        from jax.sharding import PartitionSpec as P

        from mpi_pytorch_tpu.parallel.compat import shard_map

        axis = dp_mesh.axis_names[0]
        return shard_map(
            functools.partial(_stem_call, interpret=interpret),
            mesh=dp_mesh,
            in_specs=(P(axis), P(), P()),
            out_specs=P(axis),
            check_vma=False,
        )(y, a32, b32)
    return _stem_call(y, a32, b32, interpret)
