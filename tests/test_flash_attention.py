"""Flash attention (Pallas, interpret mode on CPU) vs the plain
``full_attention`` reference — values, grads, causal masking, non-divisible
sequence padding, and bf16 inputs. The kernel computes the SAME function, so
every check is an exact-to-tolerance comparison."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_pytorch_tpu.ops.flash_attention import flash_attention
from mpi_pytorch_tpu.ops.ring_attention import full_attention

B, S, H, D = 2, 32, 2, 8


def _qkv(seed, s=S, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.standard_normal((B, s, H, D)), dtype)
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_full_attention(causal):
    q, k, v = _qkv(0)
    got = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16,
                          interpret=True)
    want = full_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_grads_match_full_attention(causal):
    q, k, v = _qkv(1)
    y = jnp.asarray(np.random.default_rng(2).standard_normal((B, S, H, D)),
                    jnp.float32)

    def loss(fn):
        def f(q_, k_, v_):
            return jnp.mean((fn(q_, k_, v_) - y) ** 2)

        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    g_flash = loss(lambda *a: flash_attention(
        *a, causal=causal, block_q=16, block_k=16, interpret=True))
    g_full = loss(lambda *a: full_attention(*a, causal=causal))
    for a, b in zip(g_flash, g_full):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-5, atol=5e-5)


def test_flash_pads_non_divisible_sequence():
    """S=24 with 16-wide blocks: padded keys must contribute nothing and the
    output slice must equal the unpadded reference (values AND grads)."""
    q, k, v = _qkv(3, s=24)
    got = flash_attention(q, k, v, block_q=16, block_k=16, interpret=True)
    want = full_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)

    g_flash = jax.grad(
        lambda q_: jnp.sum(flash_attention(q_, k, v, block_q=16, block_k=16,
                                           interpret=True) ** 2)
    )(q)
    g_full = jax.grad(lambda q_: jnp.sum(full_attention(q_, k, v) ** 2))(q)
    np.testing.assert_allclose(np.asarray(g_flash), np.asarray(g_full),
                               rtol=5e-5, atol=5e-5)
    assert np.isfinite(np.asarray(g_flash)).all()


def test_flash_bf16_inputs():
    q, k, v = _qkv(4, dtype=jnp.bfloat16)
    got = flash_attention(q, k, v, block_q=16, block_k=16, interpret=True)
    want = full_attention(q, k, v)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=2e-2, atol=2e-2,  # bf16 quantization on in/out
    )


@pytest.mark.parametrize("s", [64, 50])
def test_flash_tiny_s_values_and_grads(s):
    """Tiny-S pins at the vit_s16 geometry (S=64 / padded S=50, Dh=64):
    the flash kernel is the measured baseline the fused tiny-S kernel
    (ops/fused_attention_small.py) is A/B'd against, so its own parity at
    these shapes is pinned here — values AND all three grads vs full
    attention, through the real kernel path (interpret mode)."""
    rng = np.random.default_rng(20 + s)
    mk = lambda: jnp.asarray(rng.standard_normal((2, s, 2, 64)), jnp.float32)
    q, k, v = mk(), mk(), mk()
    got = flash_attention(q, k, v, interpret=True)
    want = full_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)

    def grads(fn):
        f = lambda q_, k_, v_: jnp.sum(fn(q_, k_, v_) ** 2)
        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    for a, b in zip(grads(lambda *x: flash_attention(*x, interpret=True)),
                    grads(full_attention)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-5, atol=5e-5)
        assert np.isfinite(np.asarray(a)).all()


def test_flash_tiny_s_bf16():
    """bf16 at S=64/Dh=64 — the production dtype of the tiny-S regime."""
    rng = np.random.default_rng(30)
    mk = lambda: jnp.asarray(rng.standard_normal((2, 64, 2, 64)), jnp.bfloat16)
    q, k, v = mk(), mk(), mk()
    got = flash_attention(q, k, v, interpret=True)
    want = full_attention(q, k, v)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=2e-2, atol=2e-2,
    )


def _bwd_case(name, *, causal, group, s, dtype, blocks):
    return pytest.param(causal, group, s, dtype, blocks, id=name)


# (query, key) blocks of the backward kernels: never square, so a swapped
# index or block size shows.
BWD_CASES = [
    _bwd_case(
        f"{'causal' if causal else 'dense'}-g{group}-s{s}-{np.dtype(dtype).name}-{bq}x{bk}",
        causal=causal, group=group, s=s, dtype=dtype, blocks=(bq, bk),
    )
    for causal in (False, True)
    for group in (1, 4)
    for s in (64, 50)  # divides the blocks; padded
    for dtype in (jnp.float32, jnp.bfloat16)
    for bq, bk in ((16, 32), (32, 16))
] + [
    # S <= block: one block a side, padded keys and the diagonal in the same tile.
    _bwd_case("one-block-a-side", causal=True, group=4, s=50, dtype=jnp.float32, blocks=(1024, 1024)),
    # Square blocks: the first query block sees exactly one key block and the
    # last key block is seen by exactly one query block (the clamps' edges).
    _bwd_case("clamp-edge", causal=True, group=4, s=64, dtype=jnp.float32, blocks=(16, 16)),
]


@pytest.mark.parametrize("causal,group,s,dtype,blocks", BWD_CASES)
def test_flash_backward_kernels_match_grad_of_full_attention(
    monkeypatch, causal, group, s, dtype, blocks
):
    """dq, dk, dv of the two backward kernels (interpreted) against
    ``jax.grad`` of ``full_attention`` with k and v repeated over the group:
    float32 tight; bf16 against the float32 gradient at the same bf16 inputs,
    as loose as the operands' rounding of p and ds."""
    from mpi_pytorch_tpu.ops import flash_attention as module

    monkeypatch.setattr(module, "BWD_BLOCKS", blocks)
    b, hkv, d = 2, 2, 8
    h = hkv * group
    ks = jax.random.split(jax.random.PRNGKey(40 + s + group), 4)
    q, co = (jax.random.normal(key, (b, s, h, d), jnp.float32).astype(dtype) for key in ks[::3])
    k, v = (jax.random.normal(key, (b, s, hkv, d), jnp.float32).astype(dtype) for key in ks[1:3])

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=causal, block_q=16, block_k=32, interpret=True)

    def full(q, k, v):
        return full_attention(q, jnp.repeat(k, group, 2), jnp.repeat(v, group, 2), causal=causal)

    got = jax.vjp(flash, q, k, v)[1](co)
    f32 = lambda *xs: (x.astype(jnp.float32) for x in xs)
    want = jax.vjp(full, *f32(q, k, v))[1](*f32(co))
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    for name, a, w in zip(("dq", "dk", "dv"), got, want):
        assert a.shape == w.shape and a.dtype == dtype, name
        a = np.asarray(a, np.float32)
        assert np.isfinite(a).all(), name
        rel = np.linalg.norm(a - np.asarray(w)) / np.linalg.norm(np.asarray(w))
        assert rel < tol, (name, rel)


def test_flash_cpu_fallback_is_full_attention():
    """interpret=None off-TPU must route to full_attention (identical
    output, no Pallas involved) — the production CPU/GPU gating."""
    q, k, v = _qkv(5)
    got = flash_attention(q, k, v)  # auto: CPU → fallback
    want = full_attention(q, k, v)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_vit_flash_matches_full_through_model(monkeypatch):
    """A whole ViT forward with attn_impl='flash' — routed through the REAL
    Pallas kernel via MPT_FLASH_INTERPRET — equals attn_impl='full' on the
    same params: the trainer flag changes execution, never the function."""
    from mpi_pytorch_tpu.models.vit import VisionTransformer

    kw = dict(num_classes=7, patch_size=4, hidden=16, depth=2, num_heads=2,
              mlp_dim=32, dtype=jnp.float32, param_dtype=jnp.float32)
    full = VisionTransformer(**kw)
    flash = VisionTransformer(attn_impl="flash", **kw)
    x = jnp.asarray(
        np.random.default_rng(6).standard_normal((2, 16, 16, 3)), jnp.float32
    )
    variables = full.init({"params": jax.random.PRNGKey(0)}, x, train=False)

    monkeypatch.setenv("MPT_FLASH_INTERPRET", "1")
    got = flash.apply(variables, x, train=False)
    want = full.apply(variables, x, train=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_attn_impl_config_validation():
    from mpi_pytorch_tpu.config import parse_config

    ok = parse_config(["--model-name", "vit_s16", "--attn-impl", "flash"])
    assert ok.attn_impl == "flash"
    with pytest.raises(ValueError, match="attn_impl='flash' does not apply to model 'resnet18'"):
        parse_config(["--attn-impl", "flash"])  # default resnet18
    with pytest.raises(ValueError, match="choose one"):
        parse_config(["--model-name", "vit_s16", "--attn-impl", "flash",
                      "--sp-strategy", "ring"])
    with pytest.raises(ValueError, match="full|flash"):
        parse_config(["--model-name", "vit_s16", "--attn-impl", "typo"])
