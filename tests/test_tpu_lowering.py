"""Every Pallas entry point lowers for ``tpu`` at its production shape.

Runs on the CPU in seconds: ``lowering_platforms=("tpu",)`` takes each kernel
through Pallas' Mosaic lowering (block specs, scratch shapes, compiler
params, the kernel body's jaxpr → MLIR) without a chip, so Pallas API drift
after a JAX upgrade fails HERE, before a chip is asked. What it cannot see is
whether the Mosaic COMPILER accepts the module — that is
``python tools/chip_kernels.py`` on the chip, over the same ``CASES``.
"""

import importlib.util
import os
import re
from unittest import mock

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_spec = importlib.util.spec_from_file_location(
    "chip_kernels", os.path.join(REPO, "tools", "chip_kernels.py")
)
chip_kernels = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(chip_kernels)

# Mosaic custom calls each case's program must carry (forward, and backward
# where the backward is its own kernel; flash's backward is two: dk/dv and dq).
EXPECTED_CALLS = {
    "stem": 2, "flash_attention": 3, "fused_attention_small": 2,
    "fused_head_ce": 2, "head_predict": 1, "head_predict_int8": 1,
}


def _lower(case):
    args = jax.eval_shape(case.make_args)  # shapes only: nothing is allocated
    with mock.patch.dict(os.environ, case.env):  # levers are read at trace time
        return jax.jit(case.fn).trace(*args).lower(lowering_platforms=("tpu",))


@pytest.mark.parametrize("case", chip_kernels.CASES, ids=lambda c: c.name)
def test_kernel_lowers_for_tpu_with_its_mosaic_calls(case):
    from mpi_pytorch_tpu.utils.hardware import mosaic_call_count

    assert mosaic_call_count(_lower(case)) == EXPECTED_CALLS[case.name.split("[")[0]]


def test_stem_levers_change_the_lowered_kernel():
    """The four MPT_STEM_* levers are read at trace time: each must produce
    a module different from the default's (a lever that silently lowered to
    the default kernel would make its chip A/B a no-op)."""
    by_name = {c.name: c for c in chip_kernels.CASES}
    default = _lower(by_name["stem"]).as_text()
    for name, case in by_name.items():
        if name.startswith("stem["):
            assert _lower(case).as_text() != default, name


def test_untileable_shapes_raise_on_a_tpu_and_give_way_only_off_it(monkeypatch):
    """On a TPU backend a kernel the caller asked for runs or RAISES, naming
    the shape; it never quietly becomes its XLA reference. Off-TPU the
    reference is the path anyway (how tier-1 runs)."""
    from mpi_pytorch_tpu.utils import hardware

    for name, call in chip_kernels.untileable_calls():
        jax.eval_shape(call)  # CPU backend: the reference, no error
    monkeypatch.setattr(hardware, "tpu_backend", lambda: True)
    rows = chip_kernels.check_raises()
    assert [r["status"] for r in rows] == ["raises"] * len(rows), rows
    assert "(8, 4, 4, 60)" in rows[0]["error"]  # the message names the shape
    assert "(2, 1024, 6, 64)" in rows[1]["error"]


def test_model_init_does_not_partition_the_kernels(monkeypatch):
    """flax init traces ONE dummy image: nothing to split over a multi-device
    data axis. On four real chips (PR 21) the fused stem refused that batch
    of 1 — init must run the single un-partitioned call, whatever the mesh."""
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from mpi_pytorch_tpu.models import create_model_bundle
    from mpi_pytorch_tpu.utils import hardware

    monkeypatch.setattr(hardware, "tpu_backend", lambda: True)
    monkeypatch.setenv("MPT_STEM_INTERPRET", "1")
    monkeypatch.setenv("MPT_ATTN_INTERPRET", "1")
    mesh = Mesh(np.array(jax.devices()).reshape(-1, 1), ("data", "model"))
    assert mesh.shape["data"] > 1
    create_model_bundle(
        "resnet18", 10, rng=jax.random.PRNGKey(0), image_size=32,
        dtype=jnp.float32, fused_stem=True, dp_mesh=mesh,
    )
    for attn_impl in ("fused-small", "full"):  # 'full' takes the same kernel
        create_model_bundle(
            "vit_s16", 10, rng=jax.random.PRNGKey(0), image_size=32,
            dtype=jnp.float32, attn_impl=attn_impl, dp_mesh=mesh,
        )


@pytest.mark.parametrize("shape,tpu,calls", [
    ((128, 196, 12, 64), True, 2),  # ViT-B/16 at 224 px, the benchmark's batch
    ((256, 64, 6, 64), True, 2),  # vit_s16 at 128 px
    ((2, 1024, 12, 64), True, 0),  # vit_b16-hires: outside the envelope
    ((128, 196, 12, 64), False, 0),  # any shape off a TPU
])
def test_dense_attention_lowers_to_the_kernel_by_shape(monkeypatch, shape, tpu, calls):
    """``attn_impl="full"`` (``dense_attention``): inside the envelope on a
    TPU the program carries the kernel pair, forward and backward; outside
    it, and on any other backend, it is ``full_attention``'s program to the
    letter."""
    import jax.numpy as jnp

    from mpi_pytorch_tpu.ops.fused_attention_small import dense_attention
    from mpi_pytorch_tpu.ops.ring_attention import full_attention
    from mpi_pytorch_tpu.utils import hardware

    monkeypatch.setattr(hardware, "tpu_backend", lambda: tpu)
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)

    def lowered(attend):
        def pair(q, k, v, do):
            out, vjp = jax.vjp(attend, q, k, v)
            return out, vjp(do)

        return jax.jit(pair).trace(x, x, x, x).lower(lowering_platforms=("tpu",))

    got = lowered(lambda q, k, v: dense_attention(q, k, v))
    assert hardware.mosaic_call_count(got) == calls
    if not calls:
        assert got.as_text() == lowered(lambda q, k, v: full_attention(q, k, v)).as_text()


@pytest.fixture(scope="module")
def one_v5e():
    """One chip of a described (not attached) ``v5e:2x2``: the TPU's compiler
    is installed here and compiles for it. Made inside the fixture, never at
    import: only the worker that runs this file may load the TPU's library."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever keeps the compiler away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("shape", [(128, 196, 12, 64), (256, 64, 6, 64)],
                         ids=["vit_b16", "vit_s16"])
def test_attention_kernels_compile_for_v5e(one_v5e, shape):
    """What lowering cannot see: Mosaic ACCEPTS the single-pass pair at the
    models' real shapes (whole-S blocks of 196 rows, 128-lane slices, the
    transposed-LHS dk/dv matmuls). Nothing runs; no time comes out of this."""
    import jax.numpy as jnp

    from mpi_pytorch_tpu.ops.fused_attention_small import fused_attention_small
    from mpi_pytorch_tpu.utils.hardware import mosaic_call_count

    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_v5e)

    def pair(q, k, v, do):
        out, vjp = jax.vjp(
            lambda *a: fused_attention_small(*a, interpret=False), q, k, v
        )
        return out, vjp(do)

    compiled = jax.jit(pair).lower(x, x, x, x).compile()
    assert mosaic_call_count(compiled) == 2


def test_flash_kernels_compile_for_v5e_at_the_benchmark_cells_shape(one_v5e):
    """Mosaic ACCEPTS the flash forward and both backward kernels at
    ``lfm2_train_hbm_8k``'s attention layer (2 sequences of 8 192 tokens, 32
    query / 8 key-value heads of 64, causal, the tiles the shape chooses):
    the forward's stacked query heads and the VMEM it asks for, the [1, BQ]
    rows of lse and delta, their turn into columns, VMEM for three float32
    score tiles. Nothing runs; no time comes out of this."""
    import jax.numpy as jnp

    from mpi_pytorch_tpu.ops.flash_attention import flash_attention
    from mpi_pytorch_tpu.utils.hardware import mosaic_call_count

    q = jax.ShapeDtypeStruct((2, 8192, 32, 64), jnp.bfloat16, sharding=one_v5e)
    kv = jax.ShapeDtypeStruct((2, 8192, 8, 64), jnp.bfloat16, sharding=one_v5e)

    def pair(q, k, v, do):
        out, vjp = jax.vjp(
            lambda *a: flash_attention(*a, causal=True, interpret=False), q, k, v,
        )
        return out, vjp(do)

    compiled = jax.jit(pair).lower(q, kv, kv, q).compile()
    assert mosaic_call_count(compiled) == 3


@pytest.mark.parametrize("heads,kv_heads,d,dtype", [
    (6, 2, 64, "bfloat16"),  # a group of 3: 2 048 rows do not divide by it
    (5, 1, 64, "bfloat16"),
    (7, 1, 64, "float32"),
    (8, 2, 128, "float32"),  # the widest head that keeps every row of the tile
    (4, 1, 256, "float32"),  # twice its bytes a row: half the rows
], ids=["group3", "group5", "group7-f32", "dh128-f32", "dh256-f32"])
def test_flash_forward_compiles_for_v5e_whatever_the_group_and_the_head(
    one_v5e, heads, kv_heads, d, dtype
):
    """Mosaic ACCEPTS the forward's tile at S = 8 192 for key-value groups
    that do not divide ``FWD_TILE``'s rows (the rows a head are whole sublane
    tiles) and for heads wider than the cells' 64 x bf16 (fewer rows: the tile
    stays inside the 16 MiB of VMEM a kernel gets). The forward alone: the
    backward's blocks are not the shape's to choose. Nothing runs."""
    import jax.numpy as jnp

    from mpi_pytorch_tpu.ops.flash_attention import flash_attention
    from mpi_pytorch_tpu.utils.hardware import mosaic_call_count

    q = jax.ShapeDtypeStruct((1, 8192, heads, d), jnp.dtype(dtype), sharding=one_v5e)
    kv = jax.ShapeDtypeStruct((1, 8192, kv_heads, d), jnp.dtype(dtype), sharding=one_v5e)
    compiled = jax.jit(
        lambda *a: flash_attention(*a, causal=True, interpret=False)
    ).lower(q, kv, kv).compile()
    assert mosaic_call_count(compiled) == 1


def test_expert_layer_compiles_for_v5e_at_the_benchmark_cells_shape(one_v5e):
    """``dropless_moe`` and its gradients at ``lfm2_train_hbm_8k``'s expert
    layer (16 384 tokens of 2 048, 8 of 64 experts of 1 536 held, top-4,
    bf16): row buffers of C = 16 384 rows, the first chunk of the sorted pairs
    inline and a loop on the device over the others, forward and backward,
    with the grouped matmuls inside both. No buffer of the FFN's width has
    all 65 536 pairs' rows. Nothing runs; no time comes out of this."""
    import jax.numpy as jnp

    from mpi_pytorch_tpu.ops.moe import dropless_moe, row_bound

    tokens, d, f, held, routed, top_k = 16_384, 2_048, 1_536, 8, 64, 4
    assert row_bound(tokens * top_k, held, routed) == 16_384
    shape = lambda *dims, dtype=jnp.float32: jax.ShapeDtypeStruct(dims, dtype, sharding=one_v5e)

    def loss(x, gate, bias, w1, w3, w2):
        y, _, _ = dropless_moe(x, gate, bias, w1, w3, w2, top_k=top_k)
        return jnp.sum(y.astype(jnp.float32))

    grad = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 3, 4, 5)))
    args = (
        shape(tokens, d, dtype=jnp.bfloat16), shape(d, routed), shape(routed),
        shape(held, d, f), shape(held, d, f), shape(held, f, d),
    )
    text = grad.lower(*args).compile().as_text()
    assert text.count(" while(") == 2  # the chunks past the first, forward and backward
    assert f"[{tokens * top_k},{f}]" not in text
    assert f"[{tokens},{f}]" in text
    # Back to tokens ONCE A PAIR (4 routed pairs a buffer row): the [4, 16 384, 2 048] read, and no sum of
    # 2 048-wide rows by token (the router's scatters move scalars).
    lowered = grad.trace(*args).lower(lowering_platforms=("tpu",)).as_text()
    assert f"tensor<{top_k}x{tokens}x{d}xbf16>" in lowered
    assert not re.search(rf"xi32>, tensor<\d+x{d // 128}x128xf32>\) -> tensor<{tokens}x{d // 128}x128xf32>", lowered)


def test_state_space_scan_compiles_for_v5e_at_the_benchmark_cells_shape(one_v5e):
    """``ops/ssd.ssd`` and its six gradients at ``granite4h_train_hbm_8k``'s
    state-space layer (one sequence of 8 192 positions, 64 heads of 64, one
    group, state 128, chunks of 256, bf16 operands, float32 decays): the
    chunked form fits a chip as XLA einsums — the ``[64, 32, 256, 256]``
    float32 decay tensor is 537 MB — with the one loop over the 32 chunk
    states, forward and backward, and no loop over positions. Nothing runs;
    no time comes out of this."""
    import jax.numpy as jnp

    from mpi_pytorch_tpu.ops.ssd import ssd

    s, h, p, g, n = 8_192, 64, 64, 1, 128
    shape = lambda *dims, dtype=jnp.float32: jax.ShapeDtypeStruct(dims, dtype, sharding=one_v5e)

    def loss(x, dt, a_log, b, c, d):
        return jnp.sum(ssd(x, dt, a_log, b, c, d, chunk=256).astype(jnp.float32))

    compiled = jax.jit(jax.value_and_grad(loss, argnums=tuple(range(6)))).lower(
        shape(1, s, h, p, dtype=jnp.bfloat16), shape(1, s, h), shape(h),
        shape(1, s, g, n, dtype=jnp.bfloat16), shape(1, s, g, n, dtype=jnp.bfloat16), shape(h),
    ).compile()
    text = compiled.as_text()
    assert text.count(" while(") == 2  # the 32 chunk states, forward and backward
    assert "f32[32,64,256,256]" in text  # the decays, float32, every chunk at once
    assert compiled.memory_analysis().temp_size_in_bytes < 2**30  # 461 MB when written


def test_nemotron_h_step_lowers_for_a_tpu_at_the_benchmark_cells_shapes(monkeypatch):
    """Loss and gradients of ``nemotron3s_train_hbm_8k``'s model — the
    configuration file's cut, 16 384 tokens, bf16, flash attention, per-block
    recompute — lowered for ``tpu`` from shapes alone: flash attention at head
    size 128 with four query heads on one key-value head (forward, the
    recompute's forward, dk/dv, dq), the expert layers' grouped matmuls over
    row buffers of C = 22 528 rows of the 1 024-wide LATENT (never the 360 448
    routed pairs' rows), the sum of those rows by token. Nothing is
    allocated; whether Mosaic compiles it is ``rehearse/compile_v5e.py``'s."""
    import json

    import jax.numpy as jnp

    from mpi_pytorch_tpu.models.nemotron_h import nemotron_h
    from mpi_pytorch_tpu.ops.losses import cross_entropy
    from mpi_pytorch_tpu.ops.moe import row_bound
    from mpi_pytorch_tpu.utils import hardware

    monkeypatch.setattr(hardware, "tpu_backend", lambda: True)  # the kernels, not their XLA compositions
    with open(os.path.join(REPO, "benchmark/configs/nemotron-3-super-120b-a12b-tp8ep64.json")) as f:
        stated = json.load(f)
    tokens = stated["batch_per_chip"] * stated["model"]["seq_len"]
    assert row_bound(tokens * 22, 8, 512, 4) == 22_528  # the model's ROW_SLACK; 11 264 at the default
    model = nemotron_h(
        0, model_config=json.dumps(stated["model"]), attn_impl="flash", remat_blocks=True,
        dtype=jnp.bfloat16,
    )
    params = jax.eval_shape(
        lambda key, x: model.init(key, x), jax.ShapeDtypeStruct((2,), jnp.uint32),
        jax.ShapeDtypeStruct((1, 128), jnp.int32),
    )["params"]
    ids = jax.ShapeDtypeStruct((stated["batch_per_chip"], stated["model"]["seq_len"]), jnp.int32)

    def loss(params, x, y):
        logits, _ = model.apply({"params": params}, x, mutable=["counters"])
        return cross_entropy(logits, y)

    lowered = jax.jit(jax.value_and_grad(loss)).trace(params, ids, ids).lower(lowering_platforms=("tpu",))
    assert hardware.mosaic_call_count(lowered) == 4
    text = lowered.as_text()
    assert "ragged_dot" in text and "22528x2688xbf16" in text and "22528x1024xbf16" in text
    # Back to tokens BY ROW (16 routed pairs a buffer row): a sum of the 22 528 rows by token, forward and
    # backward, and nothing of a row's width once a routed pair.
    assert f"xi32>, tensor<22528x8x128xf32>) -> tensor<{tokens}x8x128xf32>" in text
    assert f"22x{tokens}x1024" not in text and f"{tokens * 22}x1024" not in text
    assert f"{tokens * 22}x2688" not in text  # no buffer of the experts' width has every pair's row
    assert f"{tokens}x5376xbf16" in text  # the shared expert sees every token at the hidden width
