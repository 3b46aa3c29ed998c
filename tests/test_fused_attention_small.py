"""The single-pass dense attention kernels (Pallas, interpret mode on CPU) vs
the plain ``full_attention`` reference — values, grads, bf16, S=64 (vit_s16)
and S=196 (ViT-B/16), head geometries, the shape dispatch under ``attn_impl="full"``
(``dense_attention``), the multi-chip shard_map path, and the spmd
(bound-axis) path. The kernels compute the SAME function as full attention,
so every check is an exact-to-tolerance comparison."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from mpi_pytorch_tpu.obs import trace as obs_trace
from mpi_pytorch_tpu.ops.fused_attention_small import (
    dense_attention,
    fused_attention_small,
)
from mpi_pytorch_tpu.ops.ring_attention import full_attention

B, S, H, D = 2, 64, 2, 64  # the vit_s16 attention geometry (S=64, Dh=64)


def _qkv(seed, b=B, s=S, h=H, d=D, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.standard_normal((b, s, h, d)), dtype)
    return mk(), mk(), mk()


def _mesh():
    return Mesh(np.array(jax.devices()).reshape(-1, 1), ("data", "model"))


def _all_grads(fn, q, k, v):
    f = lambda q_, k_, v_: jnp.sum(fn(q_, k_, v_).astype(jnp.float32) ** 2)
    return jax.grad(f, argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("s", [64, 65, 50, 128, 196])
def test_values_match_full_attention(s):
    """S=64 (the vit_s16 regime), odd S=65 (class-token variant), S=50 (off
    the sublane tile: a block of the array's whole S needs no padding),
    S=128 and S=196 (ViT-B/16 at 224 px)."""
    q, k, v = _qkv(0, s=s)
    got = fused_attention_small(q, k, v, interpret=True)
    want = full_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("s", [64, 50, 196])
def test_grads_match_full_attention(s):
    q, k, v = _qkv(1, s=s)
    g_fused = _all_grads(
        lambda *a: fused_attention_small(*a, interpret=True), q, k, v
    )
    g_full = _all_grads(full_attention, q, k, v)
    for a, b in zip(g_fused, g_full):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-5, atol=5e-5)
        assert np.isfinite(np.asarray(a)).all()


@pytest.mark.parametrize("s", [64, 196])
def test_causal_matches_full_attention(s):
    """Values and all three gradients under the causal mask."""
    q, k, v = _qkv(2, s=s)
    fused = lambda *a: fused_attention_small(*a, causal=True, interpret=True)
    full = lambda *a: full_attention(*a, causal=True)
    np.testing.assert_allclose(np.asarray(fused(q, k, v)),
                               np.asarray(full(q, k, v)), rtol=2e-5, atol=2e-5)
    for a, b in zip(_all_grads(fused, q, k, v), _all_grads(full, q, k, v)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-5, atol=5e-5)


@pytest.mark.parametrize("h,d", [(1, 128), (4, 32), (2, 48), (2, 8)])
def test_head_geometries_match_full_attention(h, d):
    """The kernel tells heads apart by lane masks inside 128-lane groups
    (one head of 128: no mask; four of 32), and inside one group of all
    H·Dh lanes in a model narrower than a tile (2x48 = 96 lanes, 2x8 = 16):
    values and gradients either way."""
    q, k, v = _qkv(12, s=24, h=h, d=d)
    fused = lambda *a: fused_attention_small(*a, interpret=True)
    np.testing.assert_allclose(np.asarray(fused(q, k, v)),
                               np.asarray(full_attention(q, k, v)),
                               rtol=2e-5, atol=2e-5)
    for a, b in zip(_all_grads(fused, q, k, v),
                    _all_grads(full_attention, q, k, v)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-5, atol=5e-5)


@pytest.mark.parametrize("s", [64, 196])
def test_bf16_values_and_grads(s):
    q, k, v = _qkv(3, s=s, dtype=jnp.bfloat16)
    fused_attention = functools.partial(fused_attention_small, interpret=True)
    got = fused_attention(q, k, v)
    want = full_attention(q, k, v)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=2e-2, atol=2e-2,  # bf16 quantization on in/out
    )

    def grads(fn):
        f = lambda q_: jnp.sum(fn(q_, k, v).astype(jnp.float32) ** 2)
        return jax.grad(f)(q)

    g_fused = grads(fused_attention)
    g_full = grads(full_attention)
    np.testing.assert_allclose(
        np.asarray(g_fused, np.float32), np.asarray(g_full, np.float32),
        rtol=5e-2, atol=5e-1,
    )


def test_cpu_fallback_and_envelope():
    """interpret=None off-TPU routes to full_attention exactly; so does a
    sequence outside the envelope (S_pad > 512) even with interpret."""
    q, k, v = _qkv(6)
    np.testing.assert_array_equal(
        np.asarray(fused_attention_small(q, k, v)),
        np.asarray(full_attention(q, k, v)),
    )
    q, k, v = _qkv(6, b=1, s=520)  # a 520x520 float32 score tile is over 1 MB
    np.testing.assert_array_equal(
        np.asarray(fused_attention_small(q, k, v, interpret=True)),
        np.asarray(full_attention(q, k, v)),
    )


def _dispatch_instants(tmp_path, call):
    """The ``attn/dispatch`` instants ``call`` leaves in a run's trace file."""
    import json

    tracer = obs_trace.Tracer(str(tmp_path / "trace.json"))
    with obs_trace.use(tracer):
        call()
    with open(tracer.close()) as f:
        events = json.load(f)["traceEvents"]
    return [e["args"] for e in events if e["name"] == "attn/dispatch"]


@pytest.mark.parametrize("shape,kernel_env,mesh_axis,want", [
    # inside the envelope: the kernel, at ViT-B/16's S=196 and vit_s16's S=64
    ((2, 196, 2, 64), True, 0, {"path": "kernel"}),
    ((2, 64, 2, 64), True, 0, {"path": "kernel"}),
    ((1, 512, 1, 128), True, 0, {"path": "kernel"}),  # the envelope's corner
    ((16, 64, 2, 64), True, 8, {"path": "kernel"}),  # 2 images a device
    # outside it: XLA's full_attention, and the instant says why
    ((1, 1024, 2, 64), True, 0, {"path": "xla", "why": "outside_envelope"}),
    ((1, 513, 2, 64), True, 0, {"path": "xla", "why": "outside_envelope"}),
    ((2, 16, 2, 256), True, 0, {"path": "xla", "why": "outside_envelope"}),
    # heads that straddle 128-lane tiles (3x64 = 192 lanes, 16x80 = 1 280)
    ((2, 24, 3, 64), True, 0, {"path": "xla", "why": "outside_envelope"}),
    ((2, 24, 16, 80), True, 0, {"path": "xla", "why": "outside_envelope"}),
    ((2, 196, 2, 64), False, 0, {"path": "xla", "why": "backend"}),
    ((9, 64, 2, 64), True, 8, {"path": "xla", "why": "batch_not_divisible"}),
])
def test_dense_attention_dispatches_by_shape(
    tmp_path, monkeypatch, shape, kernel_env, mesh_axis, want
):
    """What ``attn_impl="full"`` executes is chosen from the operands' shape
    (and the backend): one ``attn/dispatch`` instant per distinct choice says
    which path a shape took and why, and the XLA path is ``full_attention``
    bit for bit."""
    if kernel_env:  # stands in for the TPU backend: the interpreted kernel
        monkeypatch.setenv("MPT_ATTN_INTERPRET", "1")
    b, s, h, d = shape
    q, k, v = _qkv(13, b=b, s=s, h=h, d=d)
    mesh = _mesh() if mesh_axis else None
    call = lambda: dense_attention(q, k, v, dp_mesh=mesh)
    got = []
    instants = _dispatch_instants(
        tmp_path, lambda: got.extend([call(), call()])  # twice: one instant
    )
    assert instants == [{**want, "S": s, "Dh": d, "batch": b}]
    check = np.testing.assert_array_equal if want["path"] == "xla" else (
        functools.partial(np.testing.assert_allclose, rtol=2e-5, atol=2e-5)
    )
    check(np.asarray(got[0]), np.asarray(full_attention(q, k, v)))


def test_dense_attention_grads_match_full_attention(monkeypatch):
    """The dispatch is differentiable through the kernel path (S=196, causal
    too) exactly as ``full_attention`` is."""
    monkeypatch.setenv("MPT_ATTN_INTERPRET", "1")
    q, k, v = _qkv(14, s=196)
    for causal in (False, True):
        dense = functools.partial(dense_attention, causal=causal)
        full = functools.partial(full_attention, causal=causal)
        for a, b in zip(_all_grads(dense, q, k, v), _all_grads(full, q, k, v)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-5, atol=5e-5)


@pytest.mark.parametrize("attn_impl,qkv_fused", [
    ("fused-small", False), ("full", False), ("full", True),
])
def test_vit_fused_small_matches_full_through_model(monkeypatch, attn_impl, qkv_fused):
    """A whole ViT forward and its parameter gradients through the REAL
    Pallas kernel (via MPT_ATTN_INTERPRET) — asked for by name
    (``fused-small``) or taken by ``full``'s shape dispatch, whose module then
    keeps q, k, v and the output [B, S, H·Dh] (fused QKV or three matmuls) —
    equal plain XLA attention on the same params: the flag and the dispatch
    change execution, never the function."""
    from mpi_pytorch_tpu.models.vit import VisionTransformer

    kw = dict(num_classes=7, patch_size=4, hidden=16, depth=2, num_heads=2,
              mlp_dim=32, dtype=jnp.float32, param_dtype=jnp.float32)
    plain = VisionTransformer(**kw)
    kernel = VisionTransformer(attn_impl=attn_impl, qkv_fused=qkv_fused, **kw)
    x = jnp.asarray(
        np.random.default_rng(7).standard_normal((2, 16, 16, 3)), jnp.float32
    )
    variables = plain.init({"params": jax.random.PRNGKey(0)}, x, train=False)
    loss = lambda model: lambda v: jnp.sum(model.apply(v, x, train=False) ** 2)
    want, want_grads = plain.apply(variables, x, train=False), jax.grad(loss(plain))(variables)

    monkeypatch.setenv("MPT_ATTN_INTERPRET", "1")
    got = kernel.apply(variables, x, train=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    for a, b in zip(jax.tree.leaves(jax.grad(loss(kernel))(variables)),
                    jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dtype,s", [
    (jnp.float32, 64), (jnp.float32, 50),
    (jnp.bfloat16, 64), (jnp.bfloat16, 50),
])
def test_shard_map_multi_device_matches_single_call(monkeypatch, dtype, s):
    """dp_mesh with an 8-device data axis: the wrapper shard_maps the kernel
    call; values AND all three grads must equal the single-call path — for
    f32 and bf16, at S=64 and padded S (the acceptance shapes)."""
    monkeypatch.setenv("MPT_ATTN_INTERPRET", "1")
    mesh = _mesh()
    n = mesh.shape["data"]
    q, k, v = _qkv(8, b=2 * n, s=s, dtype=dtype)
    vtol = dict(rtol=2e-5, atol=2e-5) if dtype == jnp.float32 else dict(
        rtol=2e-2, atol=2e-2)
    gtol = dict(rtol=5e-5, atol=5e-5) if dtype == jnp.float32 else dict(
        rtol=5e-2, atol=5e-1)

    got = fused_attention_small(q, k, v, dp_mesh=mesh)
    assert got.dtype == dtype
    want = fused_attention_small(q, k, v, interpret=True)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(full_attention(q, k, v), np.float32),
                               **vtol)

    def grads(fn):
        f = lambda q_, k_, v_: jnp.sum(fn(q_, k_, v_).astype(jnp.float32) ** 2)
        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    g_sharded = grads(lambda *a: fused_attention_small(*a, dp_mesh=mesh))
    g_full = grads(full_attention)
    for a, b in zip(g_sharded, g_full):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), **gtol)


def test_indivisible_batch_falls_back(monkeypatch):
    """A batch that does not tile the data axis must take the XLA path
    (exactly full attention), not replicate the Mosaic call."""
    monkeypatch.setenv("MPT_ATTN_INTERPRET", "1")
    mesh = _mesh()
    q, k, v = _qkv(9, b=mesh.shape["data"] + 1)
    got = fused_attention_small(q, k, v, dp_mesh=mesh)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(full_attention(q, k, v)))


def test_spmd_bound_axis_runs_per_shard_call(monkeypatch):
    """Inside a shard_map over the data axis (the spmd-mode step), the
    wrapper must detect the bound axis and run the per-shard call directly
    — no nested shard_map — and still match full attention."""
    from mpi_pytorch_tpu.parallel.compat import shard_map

    monkeypatch.setenv("MPT_ATTN_INTERPRET", "1")
    mesh = _mesh()
    n = mesh.shape["data"]
    q, k, v = _qkv(10, b=2 * n)

    inner = functools.partial(fused_attention_small, dp_mesh=mesh)
    got = shard_map(
        lambda q_, k_, v_: inner(q_, k_, v_),
        mesh=mesh,
        in_specs=(P("data"), P("data"), P("data")),
        out_specs=P("data"),
        check_vma=False,
    )(q, k, v)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(full_attention(q, k, v)),
                               rtol=2e-5, atol=2e-5)


def test_spmd_training_step_with_fused_small(monkeypatch):
    """One spmd-mode (explicit-collective shard_map) training step over a
    ViT with attn_impl='fused-small' and the mesh threaded — the trainer's
    --spmd-mode --attn-impl fused-small recipe, real kernel code path."""
    from mpi_pytorch_tpu.models.vit import VisionTransformer
    from mpi_pytorch_tpu.parallel.mesh import shard_batch
    from mpi_pytorch_tpu.train.state import TrainState, make_optimizer
    from mpi_pytorch_tpu.train.step import (
        make_spmd_train_step,
        make_train_step,
        place_state_on_mesh,
    )

    monkeypatch.setenv("MPT_ATTN_INTERPRET", "1")
    mesh = _mesh()
    n = mesh.shape["data"]
    model = VisionTransformer(
        num_classes=5, patch_size=8, hidden=16, depth=1, num_heads=2,
        mlp_dim=32, attn_impl="fused-small", dp_mesh=mesh,
    )
    rng = np.random.default_rng(11)
    images = rng.standard_normal((2 * n, 16, 16, 3)).astype(np.float32)
    labels = rng.integers(0, 5, size=(2 * n,)).astype(np.int32)
    def one_step(step_factory):
        # Fresh init per leg: the donated step deletes buffers that
        # place_state_on_mesh may alias with the init arrays.
        variables = model.init(
            {"params": jax.random.PRNGKey(0)}, jnp.asarray(images[:2]),
            train=False,
        )
        state = place_state_on_mesh(
            TrainState.create(
                apply_fn=model.apply, variables=variables,
                tx=make_optimizer(1e-3), rng=jax.random.PRNGKey(1),
            ),
            mesh,
        )
        _, metrics = step_factory(state, shard_batch((images, labels), mesh))
        return float(metrics["loss"])

    spmd_loss = one_step(make_spmd_train_step(mesh, jnp.float32))
    auto_loss = one_step(make_train_step(jnp.float32))
    # Same model, same batch: the spmd (bound-axis direct call) and auto
    # (self-shard_mapping) paths compute the same step loss.
    assert np.isfinite(spmd_loss) and np.isfinite(auto_loss)
    np.testing.assert_allclose(spmd_loss, auto_loss, rtol=1e-5, atol=1e-5)


def test_attn_impl_config_validation():
    from mpi_pytorch_tpu.config import parse_config

    ok = parse_config(["--model-name", "vit_s16", "--attn-impl", "fused-small"])
    assert ok.attn_impl == "fused-small"
    with pytest.raises(ValueError, match="attn_impl='fused-small' does not apply to model 'resnet18'"):
        parse_config(["--attn-impl", "fused-small"])  # default resnet18
    with pytest.raises(ValueError, match="choose one"):
        parse_config(["--model-name", "vit_s16", "--attn-impl", "fused-small",
                      "--sp-strategy", "ring"])
