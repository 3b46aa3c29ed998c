"""One-command step profiler: XLA trace + memory/FLOPs summary for any zoo
model's train step.

SURVEY §5 tracing row: the reference's only instrumentation is MPI.Wtime
epoch pairs (``main.py:145,158``). The trainer already embeds jax.profiler
tracing (``--profile-dir``); this tool profiles ONE step in isolation so a
kernel investigation doesn't need a training run:

    python tools/profile_step.py --model resnet18 --batch 2048 \
        [--trace-dir /tmp/trace] [--accum 1] [--remat none|full|blocks] \
        [--spmd] [--zero-opt-state] [--grad-sync-buckets MB]

``--spmd`` profiles the shard_map step instead of the auto-jit step, and
composes with the two training-half levers (ISSUE 6 / ROADMAP item 2):
``--zero-opt-state`` (ZeRO moment sharding — the summary then reports the
actually-resident optimizer MB/chip) and ``--grad-sync-buckets`` (bucketed
grad sync — the summary reports the plan's bucket count and static
overlap_frac, and with --trace-dir the XLA trace shows whether the bucket
collectives really hide under the backward).

Prints a JSON summary (step ms, img/s/chip, per-chip TFLOP/s, MFU, HBM
argument/output/temp sizes from XLA's memory analysis) and, with
--trace-dir, writes a TensorBoard-viewable XLA trace of the timed steps.
Setup and timing discipline are shared with tools/bench_zoo.py.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from bench_zoo import build_state_and_batch, timed_train_steps  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="resnet18")
    ap.add_argument("--batch", type=int, default=2048, help="per chip")
    ap.add_argument("--image", type=int, default=128)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--warmup", type=int, default=3)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--remat", default="none", choices=["none", "full", "blocks"])
    ap.add_argument("--trace-dir", default="", help="write a jax.profiler trace here")
    ap.add_argument("--spmd", action="store_true",
                    help="profile the spmd shard_map step (explicit collectives)")
    ap.add_argument("--zero-opt-state", action="store_true",
                    help="spmd: ZeRO-shard the optimizer state over the data axis")
    ap.add_argument("--grad-sync-buckets", type=float, default=0.0, metavar="MB",
                    help="spmd: bucketed grad-sync collectives (MiB per bucket)")
    ap.add_argument("--mesh-pods", type=int, default=1,
                    help="spmd: nest the data axis into this many pods — the "
                         "two-level ICI/DCN hierarchical sync (ISSUE 15); the "
                         "summary gains per-axis bytes + dcn_overlap_frac, and "
                         "with --trace-dir the XLA trace shows whether each "
                         "bucket's cross-pod phase hides under the backward")
    args = ap.parse_args()

    from mpi_pytorch_tpu.models.registry import check_build_flags
    from mpi_pytorch_tpu.train.step import (
        bucket_overlap_frac,
        grad_bucket_plan,
        make_spmd_train_step,
        make_train_step,
    )
    from mpi_pytorch_tpu.utils.hardware import peak_bf16_tflops, step_flops

    try:
        check_build_flags(args.model, remat_blocks=args.remat == "blocks")
    except ValueError as e:
        ap.error(str(e))
    if (args.zero_opt_state or args.grad_sync_buckets) and not args.spmd:
        ap.error("--zero-opt-state / --grad-sync-buckets are spmd-step levers; add --spmd")
    if args.mesh_pods > 1 and not args.spmd:
        ap.error("--mesh-pods nests the spmd step's data axis; add --spmd")
    if args.spmd and args.accum > 1:
        ap.error("--accum applies to the auto-jit step only")

    mesh, state, device_batch, n_chips, batch = build_state_and_batch(
        args.model, args.batch, args.image, remat_blocks=(args.remat == "blocks"),
        mesh_pods=args.mesh_pods,
    )
    lever_info = {}
    if args.mesh_pods > 1:
        lever_info["mesh"] = f"p{args.mesh_pods}xi{jax.device_count() // args.mesh_pods}"
    if args.spmd:
        if args.zero_opt_state:
            from mpi_pytorch_tpu.train.state import zero_shard_opt_state

            state = state.replace(
                opt_state=zero_shard_opt_state(state.opt_state, mesh)
            )
            lever_info["opt_state_mb_per_chip"] = round(
                sum(
                    leaf.addressable_shards[0].data.nbytes
                    for leaf in jax.tree_util.tree_leaves(state.opt_state)
                    if hasattr(leaf, "addressable_shards") and leaf.ndim > 0
                ) / 1e6, 1,
            )
        if args.grad_sync_buckets > 0:
            plan = grad_bucket_plan(state.params, args.grad_sync_buckets)
            lever_info["buckets"] = len(plan)
            lever_info["overlap_frac"] = bucket_overlap_frac(state.params, plan)
            if args.mesh_pods > 1:
                from mpi_pytorch_tpu.train.step import hier_dcn_overlap_frac

                lever_info["dcn_overlap_frac"] = hier_dcn_overlap_frac(
                    state.params, plan
                )
        step = make_spmd_train_step(
            mesh, jnp.bfloat16, remat=(args.remat == "full"),
            zero_opt_state=args.zero_opt_state,
            grad_bucket_mb=args.grad_sync_buckets,
        )
    else:
        step = make_train_step(
            jnp.bfloat16, remat=(args.remat == "full"), accum_steps=args.accum, mesh=mesh
        )
    from mpi_pytorch_tpu.parallel.collectives import LEDGER

    LEDGER.reset()  # trace-time per-axis byte accounting (one lower = one step)
    compiled = step.lower(state, device_batch).compile()
    if args.spmd:
        traffic = LEDGER.snapshot()
        lever_info["ici_bytes_per_step"] = traffic["ici"]["bytes"]
        lever_info["dcn_bytes_per_step"] = traffic["dcn"]["bytes"]
    mem = compiled.memory_analysis()
    flops = step_flops(compiled)

    dt, state = timed_train_steps(
        compiled, state, device_batch, args.steps, args.warmup, trace_dir=args.trace_dir
    )

    peak = peak_bf16_tflops(jax.devices()[0])
    tflops_per_chip = flops * args.steps / dt / 1e12
    summary = {
        "model": args.model,
        "batch_per_chip": args.batch,
        "accum_steps": args.accum,
        "remat": args.remat,
        "mode": "spmd" if args.spmd else "auto",
        **lever_info,
        "chips": n_chips,
        "step_ms": round(dt / args.steps * 1e3, 2),
        "images_per_sec_per_chip": round(args.steps * batch / dt / n_chips, 1),
        "tflops_per_chip": round(tflops_per_chip, 2),
        "hbm_args_gb": round(getattr(mem, "argument_size_in_bytes", 0) / 1e9, 2),
        "hbm_output_gb": round(getattr(mem, "output_size_in_bytes", 0) / 1e9, 2),
        "hbm_temp_gb": round(getattr(mem, "temp_size_in_bytes", 0) / 1e9, 2),
    }
    if peak and flops > 0:
        summary["mfu_pct"] = round(100.0 * tflops_per_chip / peak, 1)
    if args.trace_dir:
        summary["trace_dir"] = args.trace_dir
    print(json.dumps(summary))


if __name__ == "__main__":
    main()
