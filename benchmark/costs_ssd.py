"""Operations and bytes, from shapes, of the Mamba-2 state-space scan (the
``mamba/scan`` scope: everything between the split of ``xBC`` and ``y``), under
the conventions of ``benchmark/costs_lfm2.py``: nothing here reads the
program, the peaks are ``benchmark/flops.py``'s, causal work is HALF of a
chunk's Q x Q whatever an implementation masks or pads, and recomputed work
never counts.

The chunked algorithm (arXiv:2405.21060, chunk ``Q`` = ``mamba_chunk_size``)
has four products a layer, per sequence of ``S`` positions, ``H`` heads of
``P`` channels, ``G`` groups, state ``N``:

1. ``C B^T`` inside a chunk, once a GROUP: ``G * S * Q/2 * N`` multiply-adds;
2. ``(L o C B^T)(dt X)`` inside a chunk, once a head: ``H * S * Q/2 * P``;
3. a chunk's contribution to the state ``B^T (decay dt X)``: ``H * S * N * P``;
4. the carried state read through ``C``: ``H * S * N * P``.
"""

from __future__ import annotations


def mamba_layers(model: dict) -> int:
    return sum(kind == "mamba" for kind in model["layer_types"])


def scan_forward_macs(model: dict) -> int:
    """Multiply-adds of ONE state-space layer's scan, forward, one sequence."""
    s, q = model["seq_len"], min(model["mamba_chunk_size"], model["seq_len"])
    h, p = model["mamba_n_heads"], model["mamba_d_head"]
    g, n = model["mamba_n_groups"], model["mamba_d_state"]
    return g * s * (q // 2) * n + h * s * (q // 2) * p + 2 * h * s * n * p


def scan_cost(model: dict, batch: int) -> dict:
    """One train step's scans on ``batch`` sequences of one chip, forward +
    backward = 3 x forward. Bytes (2-byte activations): forward reads ``x``
    ``[S, H, P]``, ``B`` and ``C`` ``[S, G, N]`` and ``dt`` ``[S, H]`` and
    writes ``y`` ``[S, H, P]``; backward reads them and ``y``'s cotangent
    again and writes the four cotangents."""
    s = model["seq_len"]
    h, p = model["mamba_n_heads"], model["mamba_d_head"]
    g, n = model["mamba_n_groups"], model["mamba_d_state"]
    inputs = 2 * s * (h * p + 2 * g * n + h)
    output = 2 * s * h * p
    forward, backward = inputs + output, inputs + output + inputs
    layers = mamba_layers(model)
    return {
        "ops": layers * batch * 3 * 2 * scan_forward_macs(model),
        "bytes": layers * batch * (forward + backward),
    }
