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


def _fwd_case(name, *, causal, group, s, dtype, blocks):
    return pytest.param(causal, group, s, dtype, blocks, id=name)


# (query, key) blocks of the forward kernel, never square in the product; None:
# what the shape chooses.
FWD_CASES = [
    _fwd_case(
        f"{'causal' if causal else 'dense'}-g{group}-s{s}-{np.dtype(dtype).name}-{bq}x{bk}",
        causal=causal, group=group, s=s, dtype=dtype, blocks=(bq, bk),
    )
    for causal in (False, True)
    for group in (1, 4)
    for s in (64, 50)  # divides the blocks; padded
    for dtype in (jnp.float32, jnp.bfloat16)
    for bq, bk in ((16, 32), (32, 16))
] + [
    # S inside one block of the shape's choice, not filling it: padded keys
    # and the diagonal in the same tile.
    _fwd_case("one-block-a-side", causal=True, group=4, s=200, dtype=jnp.float32, blocks=None),
    # Square blocks: the first query block sees exactly one key block and the
    # last key block is seen by exactly one query block (the clamp's edges).
    _fwd_case("clamp-edge", causal=True, group=4, s=64, dtype=jnp.float32, blocks=(16, 16)),
    # 48 x 16: the diagonal crosses a tile in some of its rows only (the tile
    # of queries 48..95 and keys 64..79 is all-true below row 80), and other
    # tiles of the same query block lie wholly below it.
    _fwd_case("diagonal-in-part-of-the-rows", causal=True, group=4, s=96, dtype=jnp.float32, blocks=(48, 16)),
    # 16 x 48, padded: the last key block holds the diagonal AND padded keys.
    _fwd_case("wide-keys-padded", causal=True, group=1, s=88, dtype=jnp.float32, blocks=(16, 48)),
    # A group of 3 in 48-row blocks: queries padded to 144 over keys padded to
    # 128, so the last query block's clamp points past the last key block.
    _fwd_case("group3-rows-past-the-keys", causal=True, group=3, s=100, dtype=jnp.bfloat16, blocks=(48, 32)),
]


def _fwd_operands(causal, group, s, dtype):
    b, hkv, d = 2, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(60 + s + group), 3)
    q = jax.random.normal(ks[0], (b * hkv * group, s, d), jnp.float32).astype(dtype)
    k, v = (jax.random.normal(key, (b * hkv, s, d), jnp.float32).astype(dtype) for key in ks[1:])
    return q, k, v


@pytest.mark.parametrize("causal,group,s,dtype,blocks", FWD_CASES)
def test_flash_forward_kernel_matches_materialized_scores(causal, group, s, dtype, blocks):
    """The forward kernel's output AND the logsumexp it leaves for the
    backward (interpreted), against the materialized scores' softmax and
    ``logsumexp`` in float32 at the same operands."""
    from mpi_pytorch_tpu.ops import flash_attention as module

    q, k, v = _fwd_operands(causal, group, s, dtype)
    bq, bk = blocks or module._fwd_blocks(s, group, q.shape[-1] * q.dtype.itemsize)
    if blocks is None:
        assert (-(-s // bq), -(-s // bk)) == (1, 1) and s % bk
    out, lse = module._fwd_impl(q, k, v, causal=causal, block_q=bq, block_k=bk, interpret=True)

    qf, kf, vf = (x.astype(jnp.float32) for x in (q, jnp.repeat(k, group, 0), jnp.repeat(v, group, 0)))
    scores = jnp.einsum("hqd,hkd->hqk", qf, kf) * q.shape[-1] ** -0.5
    if causal:
        scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    want = jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(scores, axis=-1), vf)
    want_lse = jax.nn.logsumexp(scores, axis=-1)

    assert out.shape == q.shape and out.dtype == dtype and lse.dtype == jnp.float32
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(want), rtol=tol, atol=tol)
    # The scores are float32 whatever the operands: only p's rounding into
    # the MXU differs, and the logsumexp does not pass through it.
    np.testing.assert_allclose(np.asarray(lse[:, :s]), np.asarray(want_lse), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("s,causal,blocks,want", [
    (8192, True, (512, 512), (120, 136)),  # the parent's blocks at the token cells' sequence
    (8192, True, None, None),  # one head's own tile, 2 048 x 512: counted element by element below
    (8192, False, (512, 512), (0, 256)),  # no diagonal: every tile
    (200, True, (128, 128), (1, 3)),  # padded: the tiles are the padded sequence's
    (96, True, (48, 16), (3, 9)),  # tall tiles: a query block reaches the key blocks under its LAST row
    (88, True, (16, 48), (3, 9)),  # wide tiles: a query block inside a key block computes it
])
def test_tile_counts_skipped_and_computed(s, causal, blocks, want):
    from mpi_pytorch_tpu.ops import flash_attention as module

    bq, bk = blocks or module._fwd_blocks(s, 1, 128)
    counts = module._tile_counts(s, causal=causal, block_q=bq, block_k=bk)
    if want is None:  # a tile is computed where some key of it is at or before some query
        q_pos, k_pos = np.arange(s)[:, None], np.arange(s)[None, :]
        seen = (k_pos <= q_pos).reshape(s // bq, bq, s // bk, bk).any(axis=(1, 3))
        want = ((~seen).sum(), seen.sum())
    assert (counts["tiles_skipped"], counts["tiles_computed"]) == want


@pytest.mark.parametrize("s,group,d,dtype,want", [
    (8192, 4, 64, jnp.bfloat16, (512, 512)),  # both token cells: FWD_TILE's 2 048 rows over four heads
    (8192, 1, 64, jnp.bfloat16, (2048, 512)),
    (8192, 3, 64, jnp.bfloat16, (672, 512)),  # 2 048 / 3 = 682: down to whole sublane tiles
    (8192, 5, 64, jnp.bfloat16, (400, 512)),
    (8192, 7, 64, jnp.float32, (288, 512)),
    (8192, 256, 64, jnp.bfloat16, (16, 512)),  # never under one sublane tile
    (8192, 4, 128, jnp.float32, (512, 512)),  # 512 bytes a row of q: the widest head at every row
    (8192, 4, 256, jnp.float32, (256, 512)),  # twice that: half the rows
    (8192, 1, 192, jnp.float32, (1360, 512)),
    (1024, 1, 64, jnp.bfloat16, (1024, 512)),  # clipped to the sequence
    (196, 1, 64, jnp.bfloat16, (256, 256)),  # ViT-B/16: one padded block a side
    (65, 1, 64, jnp.float32, (80, 80)),  # inside one lane tile: whole sublane tiles
])
def test_forward_blocks_follow_the_shape(s, group, d, dtype, want):
    """``_fwd_blocks``: rows a head in whole 16-row sublane tiles whatever the
    group (Mosaic refuses a block whose second-minor dimension is neither a
    multiple of 8 nor the array's), fewer rows for a wider head."""
    from mpi_pytorch_tpu.ops import flash_attention as module

    bq, bk = module._fwd_blocks(s, group, d * np.dtype(dtype).itemsize)
    assert (bq, bk) == want and bq % 16 == 0 and bk % 16 == 0


def test_flash_dispatch_instant_once_a_distinct_shape():
    """``flash/dispatch`` says, once per distinct shape at trace time, which
    tiles the forward chose and how many of a head's it skips and computes
    (nothing runs here: the token cells' shape is only traced)."""
    from mpi_pytorch_tpu.obs import trace as obs_trace

    q = jax.ShapeDtypeStruct((2, 8192, 32, 64), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((2, 8192, 8, 64), jnp.bfloat16)
    small = jax.ShapeDtypeStruct((2, 64, 2, 64), jnp.float32)
    causal = lambda *a, **kw: flash_attention(*a, causal=True, interpret=True, **kw)
    tracer = obs_trace.Tracer("unwritten.json")
    with obs_trace.use(tracer):
        for _ in range(2):  # twice: one instant a shape
            jax.eval_shape(causal, q, kv, kv)
            jax.eval_shape(lambda *a: flash_attention(*a, interpret=True), small, small, small)
        jax.eval_shape(lambda *a: causal(*a, block_q=1024, block_k=1024), q, kv, kv)
    shape = {"S": 8192, "Dh": 64, "heads": 32, "kv_heads": 8, "causal": True}
    assert [e["args"] for e in tracer._events if e["name"] == "flash/dispatch"] == [
        {**shape, "block_q": 512, "block_k": 512,
         "tiles_skipped": 120, "tiles_computed": 136},
        {"S": 64, "Dh": 64, "heads": 2, "kv_heads": 2, "causal": False, "block_q": 64, "block_k": 64,
         "tiles_skipped": 0, "tiles_computed": 1},
        {**shape, "block_q": 1024, "block_k": 1024,
         "tiles_skipped": 28, "tiles_computed": 36},
    ]


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
