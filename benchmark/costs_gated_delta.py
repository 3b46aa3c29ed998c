"""Operations and bytes, from shapes, of the gated delta rule (the
``linear_attn/delta`` scope: the chunked rule between the convolutions and the
gated norm), under the conventions of ``benchmark/costs_ssd.py``: nothing here
reads the program, the peaks are ``benchmark/flops.py``'s, causal work is HALF
of a chunk's Q x Q whatever an implementation masks or pads, the triangular
inverse is counted once a chunk, and recomputed work never counts.

The chunked algorithm (arXiv:2412.06464, chunk ``Q`` = ``linear_chunk_size``)
a layer, per sequence of ``S`` positions, ``H`` heads, key and value head
sizes ``Dk``, ``Dv``, in multiply-adds:

1. ``K K^T`` and ``Q K^T`` inside a chunk: ``2 * H * S * Q/2 * Dk``;
2. the inverse of the unit lower-triangular ``I + A``: ``Q^3 / 6`` a chunk and
   head, ``H * (S/Q) * Q^3 // 6``;
3. ``W = T (beta exp(G) K)`` and ``U = T (beta V)`` over the triangle:
   ``H * S * Q/2 * (Dk + Dv)``;
4. the chunk's own writes read out, ``tril(Q K^T o decay)(U - W S^T)``:
   ``H * S * Q/2 * Dv``;
5. the entering state read out ``Q S^T``, its correction ``W S^T``, and the
   state update ``(U - W S^T)^T (d K)``: ``3 * H * S * Dk * Dv``.
"""

from __future__ import annotations


def linear_layers(model: dict) -> int:
    return sum(kind == "linear_attention" for kind in model["layer_types"])


def _shape(model: dict):
    s = model["seq_len"]
    q = min(model.get("linear_chunk_size", 64), s)
    return s, q, model["linear_num_key_heads"], model["linear_key_head_dim"], model["linear_value_head_dim"]


def delta_forward_macs(model: dict) -> int:
    """Multiply-adds of ONE linear-attention layer's rule, forward, one sequence."""
    s, q, h, dk, dv = _shape(model)
    half = q // 2
    return (
        2 * h * s * half * dk
        + h * (s // q) * (q**3 // 6)
        + h * s * half * (dk + dv)
        + h * s * half * dv
        + 3 * h * s * dk * dv
    )


def delta_cost(model: dict, batch: int) -> dict:
    """One train step's rules on ``batch`` sequences of one chip, forward +
    backward = 3 x forward. Bytes: forward reads ``q``, ``k`` ``[S, H, Dk]`` and
    ``v`` ``[S, H, Dv]`` (2-byte activations) and ``beta``, ``g`` ``[S, H]``
    (float32) and writes ``o`` ``[S, H, Dv]`` (2 bytes); backward reads them and
    ``o``'s cotangent again and writes the five cotangents."""
    s, _, h, dk, dv = _shape(model)
    inputs = s * h * (2 * (2 * dk + dv) + 2 * 4)
    output = 2 * s * h * dv
    forward, backward = inputs + output, inputs + output + inputs
    layers = linear_layers(model)
    return {
        "ops": layers * batch * 3 * 2 * delta_forward_macs(model),
        "bytes": layers * batch * (forward + backward),
    }
