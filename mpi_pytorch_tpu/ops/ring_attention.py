"""Ring attention: sequence-parallel exact attention over a mesh axis.

The reference has no attention anywhere (it is a CNN trainer — SURVEY §2c),
but this framework treats long-context scale as first-class: sequences too
long for one chip's HBM are sharded over a mesh axis, and attention runs as
a ring — each device computes blockwise attention against the K/V block it
currently holds while ``lax.ppermute`` rotates K/V blocks around the ring,
overlapping ICI transfer with compute. Numerics are the online-softmax
(flash) recurrence, so results are exact (not approximated) regardless of
ring size: running max ``m``, normalizer ``l``, and unnormalized accumulator
``o`` are carried across ring steps and renormalized once at the end.

Layout: [batch, seq, heads, head_dim] ("BSHD"), sequence axis sharded.
``ring_attention`` is the per-shard SPMD function (call inside ``shard_map``
with the sequence axis bound); ``ring_self_attention`` wraps it for direct
use from un-sharded code. Causal masking uses *global* positions, so the
sharded result matches single-device causal attention exactly
(tests/test_ring_attention.py asserts both).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from mpi_pytorch_tpu.parallel.compat import shard_map


def full_attention(q, k, v, *, causal: bool = False, scale: float | None = None) -> jnp.ndarray:
    """Single-device reference attention ([B,S,H,D], f32 accumulation);
    ``scale`` multiplies the scores, None is ``D ** -0.5``."""
    scale = q.shape[-1] ** -0.5 if scale is None else scale
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.float32) * scale, k.astype(jnp.float32)
    )
    if causal:
        sq, sk = scores.shape[-2], scores.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        scores = jnp.where(mask, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32)).astype(q.dtype)


def ring_attention(q, k, v, *, axis_name: str, causal: bool = False) -> jnp.ndarray:
    """Per-shard ring attention. Must run inside an SPMD context binding
    ``axis_name``; each shard holds the local sequence block of q/k/v."""
    n = lax.psum(1, axis_name)
    me = lax.axis_index(axis_name)
    b, sq, h, d = q.shape
    sk = k.shape[1]
    scale = d**-0.5

    qf = q.astype(jnp.float32) * scale
    q_pos = me * sq + lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
    k_iota = lax.broadcasted_iota(jnp.int32, (sq, sk), 1)

    perm = [(i, (i + 1) % n) for i in range(n)]

    def accumulate(t, k_blk, v_blk, m, l, o):
        # after t rotations this shard holds the block that originated at
        # ring position (me - t) mod n
        src = (me - t) % n
        scores = jnp.einsum("bqhd,bkhd->bhqk", qf, k_blk.astype(jnp.float32))
        if causal:
            k_pos = src * sk + k_iota
            scores = jnp.where((k_pos > q_pos)[None, None], -jnp.inf, scores)

        m_new = jnp.maximum(m, jnp.max(scores, axis=-1))
        # exp(-inf - -inf) guard: rows with no visible keys yet keep m=-inf
        p = jnp.exp(scores - jnp.where(jnp.isinf(m_new), 0.0, m_new)[..., None])
        p = jnp.where(jnp.isinf(scores), 0.0, p)
        corr = jnp.exp(m - m_new)
        corr = jnp.where(jnp.isinf(m) & jnp.isinf(m_new), 0.0, corr)
        l = l * corr + jnp.sum(p, axis=-1)
        o = o * corr.transpose(0, 2, 1)[..., None] + jnp.einsum(
            "bhqk,bkhd->bqhd", p, v_blk.astype(jnp.float32)
        )
        return m_new, l, o

    def body(t, carry):
        k_blk, v_blk, m, l, o = carry
        m, l, o = accumulate(t, k_blk, v_blk, m, l, o)
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        return k_blk, v_blk, m, l, o

    m0 = jnp.full((b, h, sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    o0 = jnp.zeros((b, sq, h, d), jnp.float32)
    # n-1 rotate-and-accumulate steps, then a final accumulate without the
    # wasted last rotation (its result would be discarded).
    k_blk, v_blk, m, l, o = lax.fori_loop(0, n - 1, body, (k, v, m0, l0, o0))
    _, l, o = accumulate(n - 1, k_blk, v_blk, m, l, o)
    denom = jnp.maximum(l, 1e-20).transpose(0, 2, 1)[..., None]
    return (o / denom).astype(q.dtype)


@functools.lru_cache(maxsize=None)
def _sp_jit(mesh, causal, seq_axis, per_shard_fn):
    """Shared scaffolding for both SP strategies (ring here, Ulysses in
    ops/ulysses.py): shard q/k/v's sequence axis over ``seq_axis`` and jit
    the given per-shard attention function under shard_map."""
    spec = P(None, seq_axis, None, None)
    fn = shard_map(
        functools.partial(per_shard_fn, axis_name=seq_axis, causal=causal),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return jax.jit(fn)


def sp_self_attention(
    per_shard_fn, q, k, v, mesh: Mesh, *, seq_axis: str | None = None,
    causal: bool = False,
) -> jnp.ndarray:
    """Driver-facing wrapper shared by the SP strategies: shards [B,S,H,D]
    tensors over ``seq_axis`` of ``mesh`` and runs ``per_shard_fn``. S must
    divide evenly by the axis size."""
    seq_axis = seq_axis or mesh.axis_names[0]
    if q.shape[1] % mesh.shape[seq_axis] != 0:
        raise ValueError(
            f"sequence length {q.shape[1]} not divisible by mesh axis "
            f"'{seq_axis}' of size {mesh.shape[seq_axis]}"
        )
    return _sp_jit(mesh, causal, seq_axis, per_shard_fn)(q, k, v)


def ring_self_attention(
    q, k, v, mesh: Mesh, *, seq_axis: str | None = None, causal: bool = False
) -> jnp.ndarray:
    """Ring attention over ``seq_axis``-sharded [B,S,H,D] tensors."""
    return sp_self_attention(
        ring_attention, q, k, v, mesh, seq_axis=seq_axis, causal=causal
    )
