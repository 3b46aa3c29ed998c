"""Attention microbench: full (materialized S×S) vs flash (Pallas) vs the
single-pass kernel, on chip.

Default mode sweeps long sequences — the flash kernel's domain:

    python tools/bench_attention.py [--seqs 512,1024,2048,4096] [--out f]

``--fused-small`` is the single-pass kernel's A/B (PERF.md section 6, PR 25
has its chip rows): S ∈ {64, 65, 50, 128, 196} at a (batch·head) count big
enough to fill the grid (``--heads 12 --batch 128 --seqs 196`` is ViT-B/16's
attention), one JSON row per (impl, S) — each fused row
CORRECTNESS-GATED against full attention on chip before any timing ships,
and the ambient ``MPT_ATTN_*`` environment snapshotted/cleared/restored
around the sweep so an operator's exported lever cannot contaminate a row
(the same env-hygiene guard as ``bench_stem --levers``). A rejected
config still lands as an error row, never a silent drop.

On non-TPU backends the flash and fused-small paths fall back to full
attention (their module gating), so chip runs are the meaningful ones;
the battery stages this after the zoo sweep.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

H, D = 6, 64  # vit_s16-shaped heads
DEFAULT_BATCH = 4          # long-S mode: S×S dominates, tiny B suffices
FUSED_SMALL_BATCH = 256    # tiny-S mode: enough (b·h) tiles to fill the grid

# (label, env) — "auto" is the kernel as the models call it; a lever under
# test adds its row here.
FUSED_SMALL_CONFIGS = [
    ("auto", {}),
]


def _impl_fn(impl: str):
    from mpi_pytorch_tpu.ops.flash_attention import flash_attention
    from mpi_pytorch_tpu.ops.fused_attention_small import fused_attention_small
    from mpi_pytorch_tpu.ops.ring_attention import full_attention

    return {
        "full": lambda q, k, v: full_attention(q, k, v),
        "flash": lambda q, k, v: flash_attention(q, k, v),
        "fused-small": lambda q, k, v: fused_attention_small(q, k, v),
    }[impl]


def _check_vs_full(fn, q, k, v):
    """On-chip correctness gate before any timing ships (the bench_stem
    --levers discipline): values AND all three gradients — the timed row
    is fwd+bwd, and the fused kernel's recompute backward is its own
    Mosaic program, so a chip-only backward miscompile (the class of bug
    the flash lse block spec hit on hardware, docs/RESULTS.md §4c) must
    fail the gate, not ship inside a timing row. bf16 storage tolerances —
    identical math, bf16 quantization on in/out."""
    from mpi_pytorch_tpu.ops.ring_attention import full_attention

    got = jax.jit(fn)(q, k, v)
    want = jax.jit(lambda q, k, v: full_attention(q, k, v))(q, k, v)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=2e-2, atol=2e-2,
    )

    def grads(f):
        loss = lambda q_, k_, v_: jnp.sum(f(q_, k_, v_).astype(jnp.float32) ** 2)
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)

    for g_got, g_want in zip(grads(fn),
                             grads(lambda q, k, v: full_attention(q, k, v))):
        np.testing.assert_allclose(
            np.asarray(g_got, np.float32), np.asarray(g_want, np.float32),
            rtol=5e-2, atol=5e-1,
        )


def bench_one(impl: str, seq: int, steps: int, warmup: int, batch: int,
              check: bool = False, label: str | None = None,
              env: dict | None = None, heads: int = H) -> dict:
    fn = _impl_fn(impl)

    rng = np.random.default_rng(0)
    mk = lambda: jnp.asarray(
        rng.standard_normal((batch, seq, heads, D)), jnp.bfloat16
    )
    q, k, v = mk(), mk(), mk()
    if check:
        _check_vs_full(fn, q, k, v)

    # The inputs are DONATED and each step consumes the previous step's
    # outputs (a true dependency chain), and the timing barrier is a VALUE
    # FETCH of a scalar computed from the final state: a fetched value
    # cannot exist before the work that produces it, so the timed region
    # ends when the chain does; its one round-trip is amortized over
    # ``steps``.
    import functools

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(q, k, v):
        def loss(q_, k_, v_):
            return jnp.sum(fn(q_, k_, v_).astype(jnp.float32) ** 2)

        _, grads = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
        eps = jnp.asarray(1e-3, q.dtype)  # tiny axpy: negligible vs attention
        return q - eps * grads[0], k - eps * grads[1], v - eps * grads[2]

    compiled = step.lower(q, k, v).compile()
    mem = None
    try:
        ma = compiled.memory_analysis()
        mem = int(getattr(ma, "temp_size_in_bytes", 0))
    except Exception:
        pass

    def sync(x):  # true execution barrier (see note above)
        return float(jnp.sum(x.astype(jnp.float32)))

    for _ in range(warmup):
        q, k, v = compiled(q, k, v)
    sync(q)
    t0 = time.perf_counter()
    for _ in range(steps):
        q, k, v = compiled(q, k, v)
    sync(q)
    dt = (time.perf_counter() - t0) / steps

    rec = {
        "impl": impl, "seq": seq, "batch": batch, "heads": heads, "head_dim": D,
        "fwd_bwd_ms": round(dt * 1e3, 3),
    }
    if label is not None:
        rec["label"] = label
    if env:
        rec["env"] = env
    if mem is not None:
        rec["temp_hbm_mb"] = round(mem / 1e6, 1)
    return rec


def sweep_long(args) -> list[dict]:
    records = []
    for seq in (int(s) for s in args.seqs.split(",") if s):
        for impl in ("full", "flash"):
            try:
                rec = bench_one(impl, seq, args.steps, args.warmup, args.batch,
                                heads=args.heads)
            except Exception as e:
                rec = {"impl": impl, "seq": seq,
                       "error": f"{type(e).__name__}: {e}"[:300]}
            records.append(rec)
            print(json.dumps(rec), flush=True)
    return records


def sweep_fused_small(args) -> list[dict]:
    """The tiny-S staged A/B: full / flash baselines + the fused kernel per
    bh-grouping lever, correctness-gated, env-hygienic."""
    records = []
    # Every row must measure EXACTLY its config: ambient MPT_ATTN_* vars
    # (e.g. a lever the operator exported while experimenting) would
    # otherwise contaminate every row including the baselines. Snapshot
    # them, clear before each config, restore when done (the bench_stem
    # --levers guard).
    gate_keys = sorted(
        {k for _, env in FUSED_SMALL_CONFIGS for k in env}
        | {k for k in os.environ if k.startswith("MPT_ATTN_")}
    )
    ambient = {k: os.environ.get(k) for k in gate_keys}
    try:
        for seq in (int(s) for s in args.seqs.split(",") if s):
            for impl, label, env in (
                [("full", None, {}), ("flash", None, {})]
                + [("fused-small", lbl, env) for lbl, env in FUSED_SMALL_CONFIGS]
            ):
                for k in gate_keys:
                    os.environ.pop(k, None)
                os.environ.update(env)
                try:
                    rec = bench_one(
                        impl, seq, args.steps, args.warmup, args.batch,
                        check=(impl == "fused-small"), label=label, env=env,
                        heads=args.heads,
                    )
                except Exception as e:  # a rejected config is still a row
                    rec = {"impl": impl, "seq": seq, "label": label,
                           "env": env,
                           "error": f"{type(e).__name__}: {e}"[:300]}
                records.append(rec)
                print(json.dumps(rec), flush=True)
    finally:
        for k, v in ambient.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return records


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seqs", default=None,
                    help="comma-separated sequence lengths "
                    "(default 512,1024,2048,4096; 64,65,50,128,196 with "
                    "--fused-small)")
    ap.add_argument("--batch", type=int, default=None,
                    help=f"batch size (default {DEFAULT_BATCH}; "
                    f"{FUSED_SMALL_BATCH} with --fused-small)")
    ap.add_argument("--heads", type=int, default=H,
                    help=f"heads of {D} (default {H}: vit_s16; 12: ViT-B/16)")
    ap.add_argument("--fused-small", action="store_true",
                    help="the single-pass kernel's A/B: full/flash vs the "
                    "kernel (correctness-gated, ambient MPT_ATTN_* cleared "
                    "per row)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--warmup", type=int, default=3)
    ap.add_argument("--out", default="")
    args = ap.parse_args()

    if args.seqs is None:
        # 64 = the vit_s16 token count (GAP head, S == patch count); 65 =
        # the class-token variant (odd S); 50 = off the sublane tile; 128 =
        # one full lane tile of keys; 196 = ViT-B/16 at 224 px.
        args.seqs = "64,65,50,128,196" if args.fused_small else "512,1024,2048,4096"
    if args.batch is None:
        args.batch = FUSED_SMALL_BATCH if args.fused_small else DEFAULT_BATCH

    records = sweep_fused_small(args) if args.fused_small else sweep_long(args)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(records, f, indent=1)


if __name__ == "__main__":
    main()
