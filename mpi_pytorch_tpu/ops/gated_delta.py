"""The gated delta rule (Gated DeltaNet, arXiv:2412.06464) in its chunked
form: XLA einsums over chunks plus one ``lax.scan`` over the chunk states, as
``ops/ssd.py`` is. No kernel; autodiff goes through the chunked form, so
neither pass walks the sequence position by position.

Per head, with state ``S_t [Dv, Dk]`` (float32), decay ``alpha_t = exp(g_t)``
(``g_t <= 0``) and write strength ``beta_t`` (up to 2 where the model allows
negative eigenvalues):

    S_t = alpha_t S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T       o_t = S_t q_t

Each position writes a rank-one CORRECTION, so unlike Mamba-2's decayed outer
products a chunk needs a triangular solve (the WY/UT transform). In chunks of
``Q`` positions, with ``G_i`` the running sum of ``g`` inside a chunk and
``S_0`` the state entering it:

1. inside a chunk   ``A = strict_tril(diag(beta) K K^T o exp(G_i - G_j))``,
                    ``T = (I + A)^-1``, ``W = T diag(beta exp(G)) K``,
                    ``U = T diag(beta) V`` (the pseudo-values are ``U - W S_0^T``);
2. a chunk's map    ``S_end = S_0 M + N`` with ``M = exp(G_Q) I - W^T diag(d) K``
                    and ``N = U^T diag(d) K``, ``d_j = exp(G_Q - G_j)``;
3. across chunks    the scan: ``S / Q`` steps of ``S <- S M + N`` on ``[H, Dv, Dk]``;
4. the output       ``O = exp(G) Q S_0^T + tril(Q K^T o exp(G_i - G_j)) (U - W S_0^T)``.

``T`` is the inverse of a unit lower-triangular matrix, computed by doubling
its diagonal blocks — 1, 2, 4, ... 64 rows: ``[[X1, 0], [-X2 L21 X1, X2]]``,
two matmuls a level, six levels — which is forward substitution in blocks.
Not the nilpotent product ``(I - A)(I + A^2)(I + A^4)...``: with ``beta`` near 2
and keys that point alike the powers of ``A`` grow as binomials (``A^32`` by
~1e27) and the product is a difference of such numbers; every block the
doubling forms is the true inverse of a run of positions, which stays bounded.

Precision: the decays, their running sums, ``A`` and its inverse are
``DECAY_DTYPE`` (float32: the configuration's statement; the benchmark's
precision control lowers it), the inverse's matmuls at ``HIGHEST``, and the
scan's state update in float32 at ``HIGHEST``; every other product takes
``q.dtype`` operands (bf16 on the training path) and accumulates in float32.
The decay matrices are masked BEFORE the exponential (``exp(-inf)`` is 0 with a
zero gradient).

The sequence must be a whole number of chunks (a sequence shorter than one
chunk is one chunk of its own length), and the chunk a power of two, as the
doubling needs: anything else is refused with the numbers (``ops/ssd.chunk_of``),
never padded.

Device scope: ``linear_attn/delta`` (the caller's ``linear_attn`` encloses it).
An instant ``linear_attn/dispatch`` (``heads``, ``key_dim``, ``value_dim``,
``chunk``, ``chunks``, ``tokens``, ``path``) marks each distinct shape once at
trace time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from mpi_pytorch_tpu.ops.ssd import chunk_of

# What the decays, their running sums, A and its inverse are computed in.
# float32 is the configuration's statement; the benchmark's control lowers it.
DECAY_DTYPE = jnp.float32
_EXACT = lax.Precision.HIGHEST


def unit_lower_inverse(a):
    """``(I + a)^-1`` for ``a [..., Q, Q]`` strictly lower-triangular, ``Q`` a
    power of two, in ``a.dtype``: the inverses of the diagonal blocks doubled
    level by level."""
    q, lead = a.shape[-1], a.shape[:-2]
    x = jnp.ones(lead + (q, 1, 1), a.dtype)  # the unit diagonal's 1 x 1 blocks
    b = 1
    while b < q:
        pairs = q // (2 * b)
        blocks = a.reshape(lead + (pairs, 2 * b, pairs, 2 * b))
        # the diagonal blocks by a masked sum (exact: the rest adds zeros);
        # ``jnp.diagonal`` is a gather, which XLA runs as a loop on a TPU
        on_diagonal = jnp.eye(pairs, dtype=bool)[:, None, :, None]
        lower = jnp.sum(jnp.where(on_diagonal, blocks, 0), axis=-2)[..., b:, :b]  # [..., pairs, b, b]
        x1, x2 = x[..., 0::2, :, :], x[..., 1::2, :, :]
        corner = -jnp.matmul(x2, jnp.matmul(lower, x1, precision=_EXACT), precision=_EXACT)
        x = jnp.concatenate(
            [jnp.concatenate([x1, jnp.zeros_like(x1)], -1), jnp.concatenate([corner, x2], -1)], -2
        )
        b *= 2
    return x[..., 0, :, :]


def gated_delta(q, k, v, beta, g, *, chunk: int):
    """``q``, ``k [B, S, H, Dk]`` (normalised and scaled by the caller), ``v
    [B, S, H, Dv]``, ``beta [B, S, H]``, ``g [B, S, H]`` (log-decays, <= 0) ->
    ``o [B, S, H, Dv]`` in ``q.dtype``, from a zero state."""
    from mpi_pytorch_tpu.obs import trace as obs_trace

    batch, s, h, dk = q.shape
    dv = v.shape[-1]
    size = chunk_of(s, chunk, "gated_delta", "linear_chunk_size")
    if size & (size - 1):
        raise ValueError(
            f"gated_delta: a chunk of {size} positions (a sequence of {s}, linear_chunk_size "
            f"{chunk}) is no power of two, which the chunk's inverse needs"
        )
    n = s // size
    obs_trace.current().instant(
        "linear_attn/dispatch",
        {"heads": h, "key_dim": dk, "value_dim": dv, "chunk": size, "chunks": n,
         "tokens": batch * s, "path": "xla_chunked"},
        once=True,
    )
    with jax.named_scope("linear_attn/delta"):
        dtype, f32 = q.dtype, DECAY_DTYPE

        def per_chunk(t):  # [B, S, H, ...] -> [B, n, H, Q, ...]
            return jnp.moveaxis(t.reshape((batch, n, size, h) + t.shape[3:]), 3, 2)

        qc, kc, vc = per_chunk(q), per_chunk(k), per_chunk(v)
        bc = per_chunk(beta).astype(f32)  # [B, n, H, Q]
        cum = jnp.cumsum(per_chunk(g).astype(f32), axis=-1)
        span = cum[..., :, None] - cum[..., None, :]  # G_i - G_j
        causal = jnp.tril(jnp.ones((size, size), bool))
        decay = jnp.exp(jnp.where(causal, span, -jnp.inf))
        dot = lambda spec, x, y: jnp.einsum(spec, x, y, preferred_element_type=jnp.float32)

        # 1. inside a chunk: the triangular solve
        kk = dot("bzhid,bzhjd->bzhij", kc, kc)
        strict = jnp.tril(jnp.ones((size, size), bool), -1)
        a = jnp.where(strict, kk * decay * bc[..., :, None], 0).astype(f32)
        t = unit_lower_inverse(a).astype(dtype)
        scaled = lambda x, by: (x.astype(jnp.float32) * by[..., None]).astype(dtype)
        w = dot("bzhij,bzhjd->bzhid", t, scaled(kc, bc * jnp.exp(cum)))
        u = dot("bzhij,bzhjv->bzhiv", t, scaled(vc, bc))

        # 2. a chunk's map of the state entering it to the state leaving it
        to_end = scaled(kc, jnp.exp(cum[..., -1:] - cum))  # d_j k_j
        keep = jnp.exp(cum[..., -1]).astype(jnp.float32)  # [B, n, H]
        m = keep[..., None, None] * jnp.eye(dk, dtype=jnp.float32) - dot(
            "bzhid,bzhie->bzhde", w.astype(dtype), to_end
        )
        add = dot("bzhiv,bzhid->bzhvd", u.astype(dtype), to_end)

        # 3. across chunks: the state ENTERING each chunk
        def carry_on(state, chunk_in):
            m_c, add_c = chunk_in
            return jnp.einsum("bhvd,bhde->bhve", state, m_c, precision=_EXACT) + add_c, state

        moved = lambda x: jnp.moveaxis(x, 1, 0)
        _, entering = lax.scan(
            carry_on, jnp.zeros((batch, h, dv, dk), jnp.float32), (moved(m), moved(add))
        )
        entering = moved(entering).astype(dtype)  # [B, n, H, Dv, Dk]

        # 4. the output: the entering state read out, and the chunk's own writes
        carried = dot("bzhid,bzhvd->bzhiv", qc, entering) * jnp.exp(cum)[..., None].astype(jnp.float32)
        values = u - dot("bzhid,bzhvd->bzhiv", w.astype(dtype), entering)
        mixed = (dot("bzhid,bzhjd->bzhij", qc, kc) * decay).astype(dtype)
        o = carried + dot("bzhij,bzhjv->bzhiv", mixed, values.astype(dtype))
        return jnp.moveaxis(o, 2, 3).reshape(batch, s, h, dv).astype(dtype)
