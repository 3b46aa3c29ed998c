"""The names the program puts on its device work (ISSUE 24 part A): every
step factory lowers with operations under ``input`` / ``forward`` / the
derived ``transpose(jvp(forward))`` / ``optimizer`` (and ``attention``,
``kernel/stem_fwd`` / ``kernel/stem_bwd`` where that code runs), and the
scopes are metadata only — the StableHLO is the same with and without them.
Lowering alone, on abstract shapes: nothing compiles or runs here."""

import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_pytorch_tpu.config import MeshConfig
from mpi_pytorch_tpu.models import initialize_model
from mpi_pytorch_tpu.models.vit import VisionTransformer
from mpi_pytorch_tpu.parallel.mesh import create_mesh
from mpi_pytorch_tpu.train.state import TrainState, make_optimizer
from mpi_pytorch_tpu.train.step import (
    make_cached_train_step,
    make_scanned_epoch,
    make_spmd_train_step,
    make_train_step,
)

BATCH, CLASSES, SIZE, ROWS = 16, 8, 32, 64


def _model(arch: str, mesh):
    if arch == "vit":
        return VisionTransformer(
            num_classes=CLASSES, patch_size=8, hidden=32, depth=1, num_heads=2, mlp_dim=64
        )
    # The stem's Mosaic calls, interpreted (the env var is set by the test).
    return initialize_model("resnet18", CLASSES, fused_stem=(arch == "r18_stem"), dp_mesh=mesh)[0]


def _abstract_state(model):
    """The TrainState's shapes, without initialising or compiling anything."""

    def build():
        variables = model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, SIZE, SIZE, 3), jnp.float32), train=False
        )
        return TrainState.create(
            apply_fn=model.apply, variables=variables, tx=make_optimizer(1e-3),
            rng=jax.random.PRNGKey(1),
        )

    return jax.eval_shape(build)


def _lower(factory: str, arch: str, debug_info: bool = True) -> str:
    """The StableHLO of one step factory, with or without its locations."""
    mesh = create_mesh(MeshConfig())
    state = _abstract_state(_model(arch, None if factory == "spmd" else mesh))
    s = jax.ShapeDtypeStruct
    batch = (s((BATCH, SIZE, SIZE, 3), jnp.uint8), s((BATCH,), jnp.int32))  # raw pixels
    cache = (s((ROWS, SIZE, SIZE, 3), jnp.uint8), s((ROWS,), jnp.int32))
    if factory == "auto":
        lowered = make_train_step(jnp.float32).lower(state, batch)
    elif factory == "spmd":
        lowered = make_spmd_train_step(mesh, jnp.float32).lower(state, batch)
    elif factory == "cached":
        lowered = make_cached_train_step(mesh, jnp.float32).lower(
            state, *cache, s((BATCH,), jnp.int32), s((BATCH,), jnp.bool_)
        )
    else:
        lowered = make_scanned_epoch(mesh, jnp.float32).lower(
            state, *cache, s((3, BATCH), jnp.int32), s((3, BATCH), jnp.bool_)
        )
    return lowered.as_text(debug_info=debug_info)


def _paths(text: str) -> set[str]:
    return set(re.findall(r'loc\("([^"]+)"', text))


CASES = [
    ("auto", "r18"), ("spmd", "r18"), ("cached", "r18"), ("scan", "r18"),
    ("auto", "vit"), ("spmd", "vit"), ("cached", "vit"), ("scan", "vit"),
    ("auto", "r18_stem"), ("scan", "r18_stem"),
]


@pytest.mark.parametrize("factory,arch", CASES)
def test_step_lowers_with_the_scopes(factory, arch, monkeypatch):
    monkeypatch.setenv("MPT_STEM_INTERPRET", "1")
    paths = _paths(_lower(factory, arch))

    def under(*scopes):
        return any(all(s in p for s in scopes) for p in paths)

    assert under("input/"), "the ingest (and the cache take) is under `input`"
    assert under("jvp(forward)/")
    assert under("transpose(jvp(forward))/"), "the backward pass derives its name"
    assert under("jvp(loss)/") and under("optimizer/") and under("metrics/")
    if factory == "spmd":
        assert under("grad_sync/")
    if arch == "vit":
        assert under("jvp(forward)/", "/attention/")
        assert under("transpose(jvp(forward))/", "/attention/")
    if arch == "r18_stem":
        # Inside the stem's own shard_map the name stack starts afresh in
        # the lowering (XLA prefixes the caller's on inlining), so the
        # kernels are asserted by their own scope alone.
        assert under("kernel/stem_fwd/") and under("kernel/stem_bwd/")
        assert under("jvp(forward)/", "bn1/shard_map")


@pytest.mark.parametrize("factory,arch", [("scan", "r18_stem"), ("spmd", "r18"), ("auto", "vit")])
def test_scopes_are_metadata_only(factory, arch, monkeypatch):
    monkeypatch.setenv("MPT_STEM_INTERPRET", "1")
    makers = (make_train_step, make_cached_train_step, make_scanned_epoch)
    named = _lower(factory, arch)
    with_scopes = _lower(factory, arch, debug_info=False)
    for make in makers:
        make.cache_clear()  # the memoized jitted steps hold their traces
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    try:
        unnamed = _lower(factory, arch)
        without = _lower(factory, arch, debug_info=False)
    finally:
        for make in makers:
            make.cache_clear()
    assert "jvp(forward)" in named and "jvp(forward)" not in unnamed
    # Without its locations (what JAX's compile-cache key is taken from)
    # the program is the same, letter for letter.
    assert "loc(" not in with_scopes and with_scopes == without


def test_every_kernel_is_called_by_name():
    """``grep -rn "pallas_call(" mpi_pytorch_tpu/ops`` shows one call: the
    helper that names it. ``kernel_call`` gives the Mosaic kernel its name
    and runs it under ``kernel/<scope>``."""
    import glob
    import os

    from mpi_pytorch_tpu.ops import kernel_call as kc

    ops = os.path.dirname(kc.__file__)
    calls = [
        (os.path.basename(path), line.strip())
        for path in glob.glob(os.path.join(ops, "*.py"))
        for line in open(path)
        if "pallas_call(" in line and not line.lstrip().startswith(("#", '"', "`"))
    ]
    calls = [c for c in calls if "``" not in c[1]]
    assert [c[0] for c in calls] == ["kernel_call.py"] and "name=" in calls[0][1]

    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    def f(x):
        return kc.kernel_call(
            "twice", kernel, name="double",
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype), interpret=True,
        )(x)

    text = jax.jit(f).lower(np.ones((8, 128), np.float32)).as_text(debug_info=True)
    assert any("kernel/twice/double" in p for p in _paths(text))
    np.testing.assert_array_equal(f(np.ones((8, 128), np.float32)), 2.0)
