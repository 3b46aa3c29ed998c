"""Microbench: fused stem kernel vs XLA composition, headline shape, on chip.

Default mode: the fused-vs-reference A/B (fwd and fwd+bwd) that produced
the §4d round-5 numbers.

``--levers``: one JSON row per §4d byte-bound lever configuration
(docs/RESULTS.md §4d, round 6) — r5-default, lanes-256, idx-int8,
c-block-16, and all-three — each correctness-checked against the
XLA reference on chip before timing, so every lever lands in the table as
a measured ship-or-rejection row, never a silent drop. Lever gates are
read from the env at TRACE time (ops/fused_stem.py:_levers), so each
config builds fresh jitted callables.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import jax, jax.numpy as jnp
import numpy as np

from mpi_pytorch_tpu.ops.fused_stem import stem_affine_relu_pool, _reference_impl

B, H, W, C = 2048, 64, 64, 64

# (label, env) — the §4d lever matrix. Values mirror the MPT_STEM_* gates.
LEVER_CONFIGS = [
    ("r5-default", {}),
    ("lanes-256", {"MPT_STEM_LANES": "256"}),
    ("idx-int8", {"MPT_STEM_IDX_INT8": "1"}),
    ("c-block-16", {"MPT_STEM_C_BLOCK": "16"}),
    (
        "all-three",
        {
            "MPT_STEM_LANES": "256",
            "MPT_STEM_IDX_INT8": "1",
            "MPT_STEM_C_BLOCK": "16",
        },
    ),
]


def _data():
    key = jax.random.PRNGKey(0)
    y = jax.random.normal(key, (B, H, W, C), jnp.bfloat16)
    a = jnp.abs(jax.random.normal(key, (C,), jnp.float32)) + 0.5
    b = jax.random.normal(key, (C,), jnp.float32) * 0.1
    co = jax.random.normal(key, (B, H // 2, W // 2, C), jnp.bfloat16)
    return y, a, b, co


def make(fn):
    @jax.jit
    def fwd(y, a, b):
        return fn(y, a, b)

    @jax.jit
    def fwdbwd(y, a, b, co):
        l, grads = jax.value_and_grad(
            lambda y, a, b: jnp.sum((fn(y, a, b) * co).astype(jnp.float32)),
            argnums=(0, 1, 2))(y, a, b)
        return l, grads

    return fwd, fwdbwd


def timeit(f, *args, n=30):
    r = f(*args)
    jax.block_until_ready(r)
    # value-fetch barrier: the fetched value is the end of the dependency chain
    t0 = time.perf_counter()
    for _ in range(n):
        r = f(*args)
    leaf = jax.tree.leaves(r)[0]
    _ = float(jnp.sum(leaf.astype(jnp.float32)))
    return (time.perf_counter() - t0) / n * 1000


def check(fus_fwd, fus_fb, ref_fwd, ref_fb, y, a, b, co):
    """On-chip correctness gate before any timing ships. bf16 storage
    tolerances (2e-2 values / 3e-1 grad atol) — identical to the round-5
    A/B gate."""
    rf = ref_fwd(y, a, b)
    ff = fus_fwd(y, a, b)
    np.testing.assert_allclose(
        np.asarray(rf, np.float32), np.asarray(ff, np.float32), rtol=2e-2, atol=2e-2
    )
    _, gr = ref_fb(y, a, b, co)
    _, gf = fus_fb(y, a, b, co)
    for u, v in zip(gr, gf):
        np.testing.assert_allclose(
            np.asarray(u, np.float32), np.asarray(v, np.float32), rtol=3e-2, atol=3e-1
        )


def bench_default(n: int) -> None:
    y, a, b, co = _data()
    ref_fwd, ref_fb = make(lambda y, a, b: _reference_impl(y, a, b))
    fus_fwd, fus_fb = make(lambda y, a, b: stem_affine_relu_pool(y, a, b))
    check(fus_fwd, fus_fb, ref_fwd, ref_fb, y, a, b, co)
    print("on-chip correctness OK")
    print(f"ref  fwd: {timeit(ref_fwd, y, a, b, n=n):8.3f} ms")
    print(f"fused fwd: {timeit(fus_fwd, y, a, b, n=n):8.3f} ms")
    print(f"ref  fwd+bwd: {timeit(ref_fb, y, a, b, co, n=n):8.3f} ms")
    print(f"fused fwd+bwd: {timeit(fus_fb, y, a, b, co, n=n):8.3f} ms")


def bench_levers(n: int) -> None:
    y, a, b, co = _data()
    ref_fwd, ref_fb = make(lambda y, a, b: _reference_impl(y, a, b))
    jax.block_until_ready(ref_fwd(y, a, b))
    # Each row must measure EXACTLY its config: ambient MPT_STEM_* vars
    # (e.g. a lever the operator exported while experimenting) would
    # otherwise contaminate every row including the r5-default baseline.
    # Snapshot them, clear before each config, restore when done.
    gate_keys = sorted({k for _, env in LEVER_CONFIGS for k in env})
    ambient = {k: os.environ.get(k) for k in gate_keys}
    try:
        for label, env in LEVER_CONFIGS:
            for k in gate_keys:
                os.environ.pop(k, None)
            os.environ.update(env)
            try:
                fus_fwd, fus_fb = make(lambda y, a, b: stem_affine_relu_pool(y, a, b))
                check(fus_fwd, fus_fb, ref_fwd, ref_fb, y, a, b, co)
                row = {
                    "metric": f"fused stem ms (B={B}, {H}x{W}x{C}, bf16)",
                    "label": label,
                    "env": env,
                    "fwd_ms": round(timeit(fus_fwd, y, a, b, n=n), 3),
                    "fwdbwd_ms": round(timeit(fus_fb, y, a, b, co, n=n), 3),
                }
            except Exception as e:  # a rejected lever is still a table row
                row = {
                    "metric": f"fused stem ms (B={B}, {H}x{W}x{C}, bf16)",
                    "label": label,
                    "env": env,
                    "error": f"{type(e).__name__}: {e}"[:300],
                }
            print(json.dumps(row), flush=True)
    finally:
        for k, v in ambient.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--levers", action="store_true",
                    help="one JSON row per §4d byte-bound lever config "
                    "(correctness-gated A/B vs the r5-default kernel)")
    ap.add_argument("--steps", type=int, default=30)
    args = ap.parse_args()
    if args.levers:
        bench_levers(args.steps)
    else:
        bench_default(args.steps)
