"""Plain reference: ViT (arXiv:2010.11929) forward and cross-entropy in
float32 ``jax.numpy``, no kernels, consuming the system's parameter tree
(``mpi_pytorch_tpu.models.vit``).

Follows the published description: non-overlapping patches through one
linear map, learned position embeddings, pre-LayerNorm blocks of multi-head
self-attention (scale 1/sqrt(head size)) and a two-layer GELU MLP, a final
LayerNorm, a dense head. Departures, each forced by the system's parameter
tree or stated by its module, and shared by both sides of the comparison:

- no class token: the head reads the MEAN of the final tokens (196 tokens at
  224 px, not 197);
- GELU in its tanh approximation (``jax.nn.gelu`` default) where the paper
  and the published checkpoint use the exact erf form;
- LayerNorm eps 1e-6 (flax default) where the published config says 1e-12.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference import common
from benchmark.reference.common import cross_entropy, f32  # noqa: F401 (re-export)

LN_EPS = 1e-6


def _ln(x, p):
    mean = x.mean(axis=-1, keepdims=True)
    var = jnp.square(x - mean).mean(axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LN_EPS) * p["scale"] + p["bias"]


def _attention(x, p):
    """q, k, v kernels are [D, heads, head]; out is [heads, head, D]."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["q"]["kernel"]) + p["q"]["bias"]
    k = jnp.einsum("bsd,dhk->bshk", x, p["k"]["kernel"]) + p["k"]["bias"]
    v = jnp.einsum("bsd,dhk->bshk", x, p["v"]["kernel"]) + p["v"]["bias"]
    scores = jnp.einsum("bqhk,bthk->bhqt", q, k) / jnp.sqrt(q.shape[-1])
    out = jnp.einsum("bhqt,bthk->bqhk", jax.nn.softmax(scores, axis=-1), v)
    return jnp.einsum("bqhk,hkd->bqd", out, p["out"]["kernel"]) + p["out"]["bias"]


def _block(x, blk):
    x = x + _attention(_ln(x, blk["ln1"]), blk["attn"])
    z = _ln(x, blk["ln2"])
    z = jax.nn.gelu(z @ blk["mlp1"]["kernel"] + blk["mlp1"]["bias"], approximate=True)
    return x + z @ blk["mlp2"]["kernel"] + blk["mlp2"]["bias"]


def forward(variables, images, train: bool = False, patch: int = 16):
    """float32 logits [B, classes] for normalized NHWC ``images``. Train mode
    is the same function: the configuration trains without dropout."""
    with jax.default_matmul_precision("highest"):
        p = f32(variables["params"])
        b, h, w, c = images.shape
        x = images.astype(jnp.float32).reshape(b, h // patch, patch, w // patch, patch, c)
        x = x.transpose(0, 1, 3, 2, 4, 5).reshape(b, (h // patch) * (w // patch), -1)
        kernel = p["patch_embed"]["kernel"]  # [patch, patch, 3, D]
        x = x @ kernel.reshape(-1, kernel.shape[-1]) + p["patch_embed"]["bias"]
        x = x + p["pos_embed"]
        depth = sum(1 for name in p if name.startswith("block"))
        # One block's code run ``depth`` times over the stacked parameters:
        # the same arithmetic as a Python loop in a twelfth of the program
        # (the compile cache a run may keep is small, PERF.md finding 9). A
        # gradient recomputes each block from its input instead of keeping
        # every block's float32 intermediates on the chip.
        blocks = jax.tree_util.tree_map(
            lambda *leaves: jnp.stack(leaves), *(p[f"block{i}"] for i in range(depth))
        )
        x, _ = lax.scan(lambda x, blk: (jax.checkpoint(_block)(x, blk), None), x, blocks)
        x = _ln(x, p["ln"]).mean(axis=1)
        return x @ p["head"]["kernel"] + p["head"]["bias"]


def loss_and_grads(variables, images, labels):
    return common.loss_and_grads(forward, variables, images, labels)


def forward_flops(model: dict) -> int:
    """FLOPs (2 per multiply-add) one image's forward pass requires: patch
    embedding, ``num_hidden_layers`` blocks (q, k, v, out projections; scores
    and weighted values; two MLP matmuls), dense head, over the tokens the
    module attends to (196 at 224 px: no class token)."""
    d, mlp = model["hidden_size"], model["intermediate_size"]
    s = (model["image_size"] // model["patch_size"]) ** 2
    macs = s * (model["patch_size"] ** 2 * 3) * d
    macs += model["num_hidden_layers"] * (4 * s * d * d + 2 * s * s * d + 2 * s * d * mlp)
    macs += d * model["num_classes"]
    return 2 * macs
