"""The selective state-space recurrence of Mamba-2 ("SSD", arXiv:2405.21060)
in its chunked form: XLA einsums over chunks plus one ``lax.scan`` over the
chunk states. No kernel; autodiff goes through the chunked form, so neither
pass walks the sequence position by position.

Per head, with state ``H_t [P, N]`` (``P`` the head's channels, ``N`` the
state size), decay ``a_t = exp(dt_t * A)``, ``A = -exp(A_log)``:

    H_t = a_t H_{t-1} + dt_t x_t (x) B_t        y_t = H_t C_t + D x_t

``B_t`` and ``C_t`` ``[N]`` are shared by the ``H / G`` heads of a group. In
chunks of ``Q`` positions, with ``cum_i`` the running sum of ``dt * A`` inside
a chunk (all of it float32: decays multiply over thousands of positions):

1. inside a chunk    ``Y = (L o C B^T)(dt . X)``, ``L[i, j] = exp(cum_i - cum_j)``
                     for ``i >= j`` and 0 above the diagonal;
2. a chunk's state   ``S = sum_j exp(cum_end - cum_j) dt_j x_j (x) B_j``;
3. across chunks     ``H_c = exp(cum_end) H_{c-1} + S_c`` (the scan: ``S / Q``
                     steps on ``[H, P, N]`` float32);
4. the carried state ``Y += exp(cum_i) (H_{c-1} C_i)``.

The four products take ``x.dtype`` operands (bf16 on the training path) and
accumulate in float32; ``L`` is masked BEFORE the exponential (``exp(-inf)``
is 0 with a zero gradient; masking after it would multiply an overflowed
``exp`` by 0).

The sequence must be a whole number of chunks (a sequence shorter than one
chunk is one chunk of its own length): anything else is refused with the
numbers, never padded.

Device scope: ``mamba/scan`` (the caller's ``mamba`` encloses it). An instant
``ssm/dispatch`` (``heads``, ``head_dim``, ``state``, ``groups``, ``chunk``,
``chunks``, ``tokens``, ``path``) marks each distinct shape once at trace time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

# What the decays and their running sums are computed in. float32 is the
# configuration's statement; the benchmark's precision control lowers it.
DECAY_DTYPE = jnp.float32


def chunk_of(seq_len: int, chunk: int, op: str = "ssd", key: str = "mamba_chunk_size") -> int:
    """The chunk a sequence of ``seq_len`` runs in, or a refusal that names
    both numbers (and the operation and configuration key that set them)."""
    size = min(chunk, seq_len)
    if seq_len % size:
        raise ValueError(
            f"{op}: a sequence of {seq_len} positions is not a whole number of "
            f"chunks of {chunk} ({key}); it is not padded"
        )
    return size


def ssd(x, dt, a_log, b, c, d, *, chunk: int):
    """``x [B, S, H, P]``, ``dt [B, S, H]`` (positive: after the softplus),
    ``a_log [H]``, ``b`` and ``c [B, S, G, N]`` with ``H % G == 0``, ``d [H]``
    -> ``y [B, S, H, P]`` in ``x.dtype``."""
    from mpi_pytorch_tpu.obs import trace as obs_trace

    batch, s, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    if h % g or b.shape != c.shape:
        raise ValueError(f"ssd: {h} heads over b {b.shape} / c {c.shape}: the groups must divide the heads")
    q = chunk_of(s, chunk)
    chunks, r = s // q, h // g
    obs_trace.current().instant(
        "ssm/dispatch",
        {"heads": h, "head_dim": p, "state": n, "groups": g, "chunk": q,
         "chunks": chunks, "tokens": batch * s, "path": "xla_chunked"},
        once=True,
    )
    with jax.named_scope("mamba/scan"):
        dtype, f32 = x.dtype, DECAY_DTYPE
        xc = x.reshape(batch, chunks, q, g, r, p)
        bc = b.reshape(batch, chunks, q, g, n)
        cc = c.reshape(batch, chunks, q, g, n)
        dtc = dt.astype(f32).reshape(batch, chunks, q, g, r)
        a = -jnp.exp(a_log.astype(f32)).reshape(g, r)
        cum = jnp.cumsum(dtc * a, axis=2)  # [B, c, Q, G, R]
        total = cum[:, :, -1]  # [B, c, G, R]

        # 1. inside a chunk
        scores = jnp.einsum("bzlgn,bzsgn->bzgls", cc, bc, preferred_element_type=jnp.float32)
        rows = jnp.moveaxis(cum, 2, -1)  # [B, c, G, R, Q]
        span = rows[..., :, None] - rows[..., None, :]  # cum_l - cum_s
        causal = jnp.tril(jnp.ones((q, q), bool))
        decay = jnp.exp(jnp.where(causal, span, -jnp.inf))
        mixed = (scores[:, :, :, None] * decay).astype(dtype)  # [B, c, G, R, l, s]
        x_dt = (xc.astype(jnp.float32) * dtc[..., None]).astype(dtype)
        y = jnp.einsum("bzgrls,bzsgrp->bzlgrp", mixed, x_dt, preferred_element_type=jnp.float32)

        # 2. what a chunk adds to the state
        to_end = jnp.exp(total[:, :, None] - cum)  # [B, c, Q, G, R]
        x_end = (xc.astype(jnp.float32) * (dtc * to_end)[..., None]).astype(dtype)
        added = jnp.einsum("bzsgn,bzsgrp->zbgrpn", bc, x_end, preferred_element_type=jnp.float32)

        # 3. across chunks: the state ENTERING each chunk
        def carry_on(state, chunk_in):
            keep, new = chunk_in
            return keep[..., None, None] * state + new, state

        keep = jnp.moveaxis(jnp.exp(total), 1, 0).astype(jnp.float32)  # [c, B, G, R]
        _, entering = lax.scan(carry_on, jnp.zeros_like(added[0]), (keep, added))

        # 4. the carried state read out
        carried = jnp.einsum(
            "bzlgn,zbgrpn->bzlgrp", cc, entering.astype(dtype), preferred_element_type=jnp.float32
        )
        y = y + carried * jnp.exp(cum)[..., None].astype(jnp.float32)
        y = y + xc.astype(jnp.float32) * d.astype(jnp.float32).reshape(g, r, 1)
        return y.reshape(batch, s, h, p).astype(dtype)
