"""Per-architecture training throughput across the full model zoo.

The headline ``bench.py`` measures the reference's north-star workload
(resnet18); this sweeps all seven architectures of the zoo
(≙ ``models.py:16-101``) through the same jitted DP train step on whatever
chips are present, and prints one JSON line per architecture:

    {"model": ..., "images_per_sec_per_chip": N, "mfu_pct": N, ...}

Run: ``python tools/bench_zoo.py [--steps 20] [--out docs/zoo_bench.json]``

Per-arch batch sizes are throughput-reasonable single-chip defaults, scaled
down where activation memory is the binding constraint (vgg11_bn's big
early feature maps; inception's 299px input — the size the reference would
have needed for inception to work at all, SURVEY §3 quirks).
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

REFERENCE_IMG_PER_SEC_PER_WORKER = 4.4  # BASELINE.md, training.log:1268-1275
NUM_CLASSES = 64500  # utils.py:39

# (batch per chip, image size). 128px mirrors utils.py:33-34 except
# inception_v3, which genuinely requires 299 (models.py:95, SURVEY §3).
ZOO = {
    "resnet18": (2048, 128),
    "resnet34": (2048, 128),
    "alexnet": (2048, 128),
    "vgg11_bn": (512, 128),
    # squeezenet's classifier is a 1x1 conv applied BEFORE global pooling
    # (≙ models.py:70), so its head activation is [B, 8, 8, 64500] — 64x the
    # other archs' logits per example. Batch 2048 blows compile memory.
    "squeezenet1_0": (512, 128),
    "densenet121": (1024, 128),
    "inception_v3": (256, 299),
    "mobilenet_v2": (1024, 128),
    "efficientnet_b0": (1024, 128),
    # vit at 128px/patch16 = 64 tokens; large batches keep the MXU fed.
    "vit_s16": (2048, 128),
    "vit_b16": (1024, 128),
    "vit_moe_s16": (1024, 128),
}


def build_state_and_batch(
    model_name: str, batch_per_chip: int, image: int, optimizer: bool = True,
    remat_blocks: bool = False, attn_impl: str = "full", stem_s2d: bool = False,
    fused_stem: bool | None = None, qkv_fused: bool = False, mesh_pods: int = 1,
):
    """Shared harness setup (also used by tools/bench_eval.py and
    tools/profile_step.py): mesh, placed train state, and a random sharded
    device batch. ``optimizer=False`` skips the Adam moment trees (~2x params
    of f32 HBM) for forward-only benches. ``mesh_pods > 1`` nests the data
    axis (pod, ici) for the hierarchical-sync profiles (ISSUE 15)."""
    import optax

    from mpi_pytorch_tpu.config import MeshConfig
    from mpi_pytorch_tpu.models import create_model_bundle
    from mpi_pytorch_tpu.parallel.mesh import create_mesh, shard_batch
    from mpi_pytorch_tpu.train.state import TrainState, make_optimizer
    from mpi_pytorch_tpu.train.step import place_state_on_mesh

    n_chips = jax.device_count()
    batch = batch_per_chip * n_chips
    mesh = create_mesh(MeshConfig(pods=mesh_pods))
    if fused_stem is None:
        # Same contract as bench.py: the fused stem is the headline resnet
        # configuration on TPU; MPT_FUSED_STEM=0 reverts for A/B.
        from mpi_pytorch_tpu.models.registry import fused_stem_default

        fused_stem = fused_stem_default(model_name)
    bundle, variables = create_model_bundle(
        model_name, NUM_CLASSES, rng=jax.random.PRNGKey(0), image_size=image,
        dtype=jnp.bfloat16, param_dtype=jnp.float32, remat_blocks=remat_blocks,
        attn_impl=attn_impl, stem_s2d=stem_s2d, fused_stem=fused_stem,
        # Multi-chip: the kernels (stem, dense attention) shard_map
        # themselves over the data axis (ops/fused_stem.py /
        # ops/fused_attention_small.py, Multi-chip) instead of degrading to
        # an activation all-gather around a replicated Mosaic call; a model
        # with neither ignores the mesh.
        dp_mesh=mesh,
        qkv_fused=qkv_fused,
    )
    state = TrainState.create(
        apply_fn=bundle.model.apply, variables=variables,
        tx=make_optimizer(4e-4) if optimizer else optax.identity(),
        rng=jax.random.PRNGKey(1),
    )
    state = place_state_on_mesh(state, mesh)
    rng = np.random.default_rng(0)
    device_batch = shard_batch(
        (rng.standard_normal((batch, image, image, 3), np.float32),
         rng.integers(0, NUM_CLASSES, size=(batch,)).astype(np.int32)),
        mesh,
    )
    return mesh, state, device_batch, n_chips, batch


def timed_train_steps(compiled, state, device_batch, steps, warmup, trace_dir=""):
    """Warmup then time ``steps`` calls of a compiled train step, blocking on
    the DONATED STATE, not a metrics scalar: the state is the end of the
    dependency chain, so the timed region ends when the work does.
    Optionally wraps the timed steps in a jax.profiler trace."""
    for _ in range(warmup):
        state, _ = compiled(state, device_batch)
    jax.block_until_ready(state.params)

    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    t0 = time.perf_counter()
    for _ in range(steps):
        state, _ = compiled(state, device_batch)
    jax.block_until_ready(state.params)
    dt = time.perf_counter() - t0
    if trace_dir:
        jax.profiler.stop_trace()
    return dt, state


def bench_one(model_name: str, batch_per_chip: int, image: int, steps: int,
              warmup: int, attn_impl: str = "full", stem_s2d: bool = False,
              qkv_fused: bool = False):
    from mpi_pytorch_tpu.train.step import make_train_step
    from mpi_pytorch_tpu.utils.hardware import peak_bf16_tflops, step_flops

    from mpi_pytorch_tpu.models.registry import fused_stem_default

    fused_stem = fused_stem_default(model_name)  # what the harness resolves
    mesh, state, device_batch, n_chips, batch = build_state_and_batch(
        model_name, batch_per_chip, image, attn_impl=attn_impl,
        stem_s2d=stem_s2d, qkv_fused=qkv_fused, fused_stem=fused_stem,
    )
    step = make_train_step(jnp.bfloat16)

    # Same channel and contract as bench.py: a set MPT_COMPILER_OPTIONS
    # (JSON dict) is applied verbatim as per-compile options (jaxlib aborts
    # on an xla_tpu_* entry in XLA_FLAGS; those flags live in libtpu). The
    # zoo applies NO default options so cross-model rows stay comparable.
    options = json.loads(os.environ.get("MPT_COMPILER_OPTIONS", "null"))
    compiled = step.lower(state, device_batch).compile(
        compiler_options=options or None
    )
    flops_per_step = step_flops(compiled)
    dt, state = timed_train_steps(compiled, state, device_batch, steps, warmup)

    ips = steps * batch / dt
    tflops_per_chip = flops_per_step * steps / dt / 1e12  # cost analysis is per-device
    peak = peak_bf16_tflops(jax.devices()[0])
    rec = {
        "model": model_name,
        "batch_per_chip": batch_per_chip,
        "image_size": image,
        "chips": n_chips,
        "images_per_sec_per_chip": round(ips / n_chips, 1),
        "vs_baseline": round(ips / n_chips / REFERENCE_IMG_PER_SEC_PER_WORKER, 1),
        "step_ms": round(dt / steps * 1e3, 2),
        "tflops_per_chip": round(tflops_per_chip, 2),
    }
    if attn_impl != "full":
        rec["attn_impl"] = attn_impl
    if stem_s2d:
        rec["stem_s2d"] = True
    if qkv_fused:
        rec["qkv_fused"] = True
    if fused_stem:
        rec["fused_stem"] = True
    if peak and flops_per_step > 0:
        rec["mfu_pct"] = round(100.0 * tflops_per_chip / peak, 1)
    return rec


def bench_one_in_child(name: str, steps: int, warmup: int, timeout_s: int,
                       attn_impl: str = "full", stem_s2d: bool = False,
                       qkv_fused: bool = False) -> dict:
    """Run one model's bench in a fresh child interpreter with a hard
    timeout: each model starts from an empty device (no HBM held over from
    the previous one), and a model that fails or hangs costs its own row,
    not the sweep. The parent never initialises a backend — a chip belongs
    to one process, and the children need it."""
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cmd = [
        sys.executable, os.path.abspath(__file__), "--in-process",
        "--models", name, "--steps", str(steps), "--warmup", str(warmup),
        "--attn-impl", attn_impl,
    ] + (["--stem-s2d"] if stem_s2d else []) + (
        ["--qkv-fused"] if qkv_fused else [])
    try:
        proc = subprocess.run(
            cmd, cwd=repo, capture_output=True, text=True, timeout=timeout_s
        )
    except subprocess.TimeoutExpired:
        return {"model": name, "error": f"child exceeded {timeout_s}s"}
    for line in (proc.stdout or "").splitlines()[::-1]:
        if line.startswith("{"):
            return json.loads(line)
    tail = (proc.stderr or "").strip().splitlines()[-3:]
    return {"model": name, "error": f"no JSON (rc={proc.returncode}): " + " | ".join(tail)}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--warmup", type=int, default=3)
    ap.add_argument("--attn-impl", default="full",
                    choices=["full", "flash", "fused-small"],
                    help="vit family only: dense-attention implementation "
                    "(fused-small = the tiny-S Pallas kernel, "
                    "ops/fused_attention_small.py — the vit_s16 A/B row)")
    ap.add_argument("--models", default=",".join(ZOO), help="comma-separated subset")
    ap.add_argument("--qkv-fused", action="store_true",
                    help="fuse q/k/v projections into one matmul (vit family)")
    ap.add_argument("--stem-s2d", action="store_true",
                    help="resnet family only: space-to-depth stem conv")
    ap.add_argument("--out", default="", help="also write a JSON array to this path")
    ap.add_argument(
        "--in-process", action="store_true",
        help="bench in this process (no per-model watchdog child); the "
        "default isolates each model in a child with --model-timeout",
    )
    ap.add_argument("--model-timeout", type=int, default=1200)
    args = ap.parse_args()

    records = []
    for name in (m.strip() for m in args.models.split(",") if m.strip()):
        try:
            batch, image = ZOO[name]  # inside try: a typo'd name must not
            if args.in_process:  # kill the sweep or discard --out
                rec = bench_one(name, batch, image, args.steps, args.warmup,
                                attn_impl=args.attn_impl, stem_s2d=args.stem_s2d,
                                qkv_fused=args.qkv_fused)
            else:
                rec = bench_one_in_child(
                    name, args.steps, args.warmup, args.model_timeout,
                    attn_impl=args.attn_impl, stem_s2d=args.stem_s2d,
                    qkv_fused=args.qkv_fused,
                )
        except Exception as e:
            rec = {"model": name, "error": f"{type(e).__name__}: {e}"[:300]}
        records.append(rec)
        print(json.dumps(rec), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(records, f, indent=1)
    failed = [r["model"] for r in records if "error" in r]
    if failed:
        raise SystemExit(f"bench_zoo: {len(failed)} model(s) failed: {failed}")


if __name__ == "__main__":
    main()
