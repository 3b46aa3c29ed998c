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
# where the backward is its own kernel; flash's backward is blocked XLA).
EXPECTED_CALLS = {
    "stem": 2, "flash_attention": 1, "fused_attention_small": 2,
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
    create_model_bundle(
        "vit_s16", 10, rng=jax.random.PRNGKey(0), image_size=32,
        dtype=jnp.float32, attn_impl="fused-small", dp_mesh=mesh,
    )
