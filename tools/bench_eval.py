"""Inference/evaluation throughput: batched sharded forward, images/sec/chip.

The reference's second driver is a 4-stage MPI inference pipeline whose
predict stage runs ONE image per forward per rank
(``evaluation_pipeline.py:132-159``). This framework collapses it into one
jitted batched forward over all chips (``evaluate.py``); this tool measures
that forward with the harness shared with ``tools/bench_zoo.py`` and prints
one JSON line per batch size.

Timing note: the eval step outputs only scalars. The timed loop chains
every step's metrics into one on-device accumulator and blocks on that —
the final value depends on every step, so it cannot be ready before the
work is.

Run: ``python tools/bench_eval.py [--model resnet18] [--batches 256,1024,4096]``
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

from bench_zoo import NUM_CLASSES, build_state_and_batch  # noqa: E402


def bench_eval(model_name: str, batch_per_chip: int, image: int, steps: int, warmup: int):
    from mpi_pytorch_tpu.train.step import make_eval_step
    from mpi_pytorch_tpu.utils.hardware import peak_bf16_tflops, step_flops

    mesh, state, device_batch, n_chips, batch = build_state_and_batch(
        model_name, batch_per_chip, image, optimizer=False
    )
    eval_step = make_eval_step(jnp.bfloat16)
    compiled = eval_step.lower(state, device_batch).compile()
    flops = step_flops(compiled)

    add = jax.jit(lambda acc, m: acc + m["loss"] + m["count"])

    acc = jnp.zeros((), jnp.float32)
    for _ in range(warmup + 1):  # +1 so the accumulator add is compiled too
        acc = add(acc, compiled(state, device_batch))
    jax.block_until_ready(acc)

    acc = jnp.zeros((), jnp.float32)
    t0 = time.perf_counter()
    for _ in range(steps):
        acc = add(acc, compiled(state, device_batch))
    jax.block_until_ready(acc)  # depends on every step above
    dt = time.perf_counter() - t0

    ips = steps * batch / dt
    tflops_per_chip = flops * steps / dt / 1e12  # cost analysis is per-device
    peak = peak_bf16_tflops(jax.devices()[0])
    rec = {
        "metric": f"eval images/sec/chip (bf16, {NUM_CLASSES} classes, {image}px)",
        "model": model_name,
        "batch_per_chip": batch_per_chip,
        "chips": n_chips,
        "images_per_sec_per_chip": round(ips / n_chips, 1),
        "step_ms": round(dt / steps * 1e3, 2),
        "tflops_per_chip": round(tflops_per_chip, 2),
    }
    if peak and flops > 0:
        rec["mfu_pct"] = round(100.0 * tflops_per_chip / peak, 1)
    return rec


def bench_head(batch: int, d: int, steps: int, warmup: int):
    """A/B of the PREDICTIONS-PASS head stage in isolation (the eval path's
    [B, 64 500] logits question — VERDICT r4 item 5): the XLA composition
    (bf16 matmul → pinned-f32 logits → CE + argmax, what
    evaluate._make_predict_step runs today) vs ``ops.fused_head_ce.
    head_predict`` (one VMEM-streaming kernel, no [B, V] tensor). Chained
    on-device accumulator barrier, same as bench_eval."""
    import numpy as np

    from mpi_pytorch_tpu.ops.fused_head_ce import head_predict
    from mpi_pytorch_tpu.train.step import metrics_from_logits

    rng = np.random.default_rng(0)
    feats = jnp.asarray(rng.normal(size=(batch, d)), jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(d, NUM_CLASSES)) * 0.05, jnp.float32)
    b = jnp.asarray(rng.normal(size=(NUM_CLASSES,)) * 0.1, jnp.float32)
    labels = jnp.asarray(rng.integers(0, NUM_CLASSES, size=(batch,)), jnp.int32)

    from jax import lax

    # w/b travel as ARGUMENTS: a 132 MB closure constant would be baked
    # into the compiled program.
    @jax.jit
    def xla_head(feats, labels, w, b):
        logits = feats @ w.astype(jnp.bfloat16) + b.astype(jnp.bfloat16)
        logits = lax.optimization_barrier(logits.astype(jnp.float32))
        m = metrics_from_logits(logits, labels)
        preds = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return m["loss"] + jnp.sum(preds)

    @jax.jit
    def fused_head(feats, labels, w, b):
        loss, preds = head_predict(feats, w, b, labels)
        return jnp.sum(loss) + jnp.sum(preds)

    out = []
    from mpi_pytorch_tpu.ops.fused_head_ce import PREDICT_MAX_ROWS, _predict_row_block

    # Row tiling (round 6): beyond PREDICT_MAX_ROWS the kernel streams
    # ≤1024-row blocks through a (rows, vocab) grid instead of falling
    # back — so B=4096 is now a real fused measurement, labeled with its
    # row-block size.
    rb = _predict_row_block(batch)
    fused_label = (
        "fused" if batch <= PREDICT_MAX_ROWS
        else (f"fused(row-tiled rb={rb})" if rb else "fused(untileable: xla fallback)")
    )
    for label, fn in (("xla", xla_head), (fused_label, fused_head)):
        add = jax.jit(lambda acc, v: acc + v)
        acc = jnp.zeros((), jnp.float32)
        for _ in range(warmup + 1):
            acc = add(acc, fn(feats, labels, w, b))
        float(acc)  # value fetch: block_until_ready lies here (§4c)
        acc = jnp.zeros((), jnp.float32)
        t0 = time.perf_counter()
        for _ in range(steps):
            acc = add(acc, fn(feats, labels, w, b))
        float(acc)  # a fetched value cannot be fabricated
        dt = time.perf_counter() - t0
        out.append({
            "metric": f"predictions head ms (B={batch}, D={d}, V={NUM_CLASSES})",
            "head": label,
            "step_ms": round(dt / steps * 1e3, 3),
        })
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="resnet18")
    ap.add_argument("--image", type=int, default=128)
    ap.add_argument("--batches", default="256,1024,4096")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--warmup", type=int, default=5)
    ap.add_argument("--head", action="store_true",
                    help="A/B the isolated predictions-pass head stage "
                    "(XLA vs ops.fused_head_ce.head_predict) per batch size")
    args = ap.parse_args()
    for b in (x.strip() for x in args.batches.split(",") if x.strip()):
        try:
            if args.head:
                for rec in bench_head(int(b), 512, args.steps, args.warmup):
                    print(json.dumps(rec), flush=True)
                continue
            rec = bench_eval(args.model, int(b), args.image, args.steps, args.warmup)
        except Exception as e:
            rec = {"model": args.model, "batch_per_chip": int(b),
                   "error": f"{type(e).__name__}: {e}"[:300]}
        print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()
