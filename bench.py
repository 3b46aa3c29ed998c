"""Headline benchmark: resnet18 training throughput, images/sec/chip.

Mirrors the reference's north-star workload (``main.py``: resnet18, 64 500
classes, Adam 4e-4, 128×128 inputs) as one jitted DP train step over all
available chips, bfloat16 compute. One process, no children: it starts at
``import jax``, runs on whatever backend JAX selects (``JAX_PLATFORMS`` is
the one switch), and any failure — backend init included — is JAX's own
error and a non-zero exit. Prints ONE JSON line:

    {"metric": ..., "value": N, "unit": "images/sec/chip", "vs_baseline": N,
     "platform": ..., "device_kind": ..., "device_count": N,
     "fused_stem": bool, "compiler_options": {...}, ...}

``vs_baseline`` is value ÷ the reference's best *per-worker* throughput
(≈4.4 img/s/worker — 800 imgs / 45.4 s over 4 MPI ranks, derived from
``training.log:1268-1275``; see BASELINE.md). ``mfu_pct`` is computed from
the XLA cost analysis of the compiled step against the chip's peak bf16
FLOP/s (``utils/hardware.py``; an unknown TPU kind raises). A row whose
``platform`` is not ``tpu`` is not a speed.

Timing notes: the state is donated through the step, so the timed region
ends in a block on the final state — that is what guarantees every queued
step actually finished.
"""

from __future__ import annotations

import json
import os
import time

import numpy as np

REFERENCE_IMG_PER_SEC_PER_WORKER = 4.4  # BASELINE.md, training.log:1268-1275

# ---------------------------------------------------------------------------
# Partial bench rows: a multi-cell sweep (tools/bench_modes.py) appends every
# completed row to a ``*.partial.json`` (cell key → row, atomic rename) the
# moment it lands, and ``--resume-from`` skips cells that file already holds
# — a sweep that dies on one cell costs a retry of the REMAINING cells.
# ---------------------------------------------------------------------------


def load_partial(path: str) -> dict:
    """Rows already measured in a partial file ({cell key: row}). A missing,
    unreadable, or non-dict file is an empty dict — resume must never be
    the thing that fails a retry."""
    if not path or not os.path.isfile(path):
        return {}
    try:
        with open(path) as f:
            data = json.load(f)
    except (ValueError, OSError):
        return {}
    return data if isinstance(data, dict) else {}


def append_partial_row(path: str, key: str, row: dict) -> None:
    """Durably record one completed bench cell (read-modify-write, tmp +
    atomic rename)."""
    rows = load_partial(path)
    rows[key] = row
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(rows, f, indent=1)
    os.replace(tmp, path)


MODEL = "resnet18"
NUM_CLASSES = 64500   # utils.py:39
IMAGE = 128           # utils.py:33-34
BATCH_PER_CHIP = 2048  # largest power of two the builder's earlier batch
#                        sweep favoured (docs/RESULTS.md); not re-measured
#                        on this installation yet.
WARMUP_STEPS = 5
MEASURE_STEPS = 30


def main() -> None:
    import jax
    import jax.numpy as jnp

    from mpi_pytorch_tpu.config import enable_compilation_cache

    # Before the first compile: JAX keeps the cache it opens there.
    cache_dir = enable_compilation_cache()

    from mpi_pytorch_tpu.config import Config
    from mpi_pytorch_tpu.models import create_model_bundle
    from mpi_pytorch_tpu.obs import Tracer
    from mpi_pytorch_tpu.parallel.mesh import create_mesh, shard_batch
    from mpi_pytorch_tpu.train.state import TrainState, make_optimizer
    from mpi_pytorch_tpu.train.step import make_train_step, place_state_on_mesh
    from mpi_pytorch_tpu.utils.hardware import peak_bf16_tflops, step_flops

    # MPT_TRACE_FILE=path → host-side Chrome-trace spans for the bench's
    # phases (compile/warmup/measure — obs/trace.py), so a slow bench run
    # is attributable without re-running under a profiler.
    tracer = Tracer(os.environ.get("MPT_TRACE_FILE", ""))

    n_chips = jax.device_count()
    batch = BATCH_PER_CHIP * n_chips

    mesh = create_mesh(Config().mesh)
    # Fused bn1+relu+maxpool stem (ops/fused_stem.py), on by default on a
    # TPU. MPT_FUSED_STEM=0 reverts to the unfused XLA stem for A/B.
    from mpi_pytorch_tpu.models.registry import fused_stem_default

    _fused = fused_stem_default(MODEL)
    bundle, variables = create_model_bundle(
        MODEL, NUM_CLASSES, rng=jax.random.PRNGKey(0), image_size=IMAGE,
        dtype=jnp.bfloat16, param_dtype=jnp.float32,
        fused_stem=_fused,
        # Multi-chip: the stem kernel shard_maps itself over the data axis
        # (ops/fused_stem.py, Multi-chip).
        dp_mesh=mesh if _fused else None,
    )
    state = TrainState.create(
        apply_fn=bundle.model.apply, variables=variables,
        tx=make_optimizer(4e-4), rng=jax.random.PRNGKey(1),
    )
    state = place_state_on_mesh(state, mesh)
    step = make_train_step(jnp.bfloat16)

    rng = np.random.default_rng(0)
    images = rng.standard_normal((batch, IMAGE, IMAGE, 3), np.float32)
    labels = rng.integers(0, NUM_CLASSES, size=(batch,)).astype(np.int32)
    device_batch = shard_batch((images, labels), mesh)

    # TPU compiler options. Default: 64 MiB scoped VMEM (the value the
    # tools/bench_flags.py sweep settled on for this workload). A set
    # MPT_COMPILER_OPTIONS (JSON dict) REPLACES the default entirely — so
    # bench_flags.py's baseline="{}" row really is the no-options baseline —
    # and must hold PER-COMPILE options, not XLA_FLAGS: jaxlib aborts at
    # start-up on an XLA_FLAGS entry it does not know, and the xla_tpu_*
    # flags live in libtpu.
    env_options = os.environ.get("MPT_COMPILER_OPTIONS")
    if env_options is not None:
        options = json.loads(env_options)
    elif jax.default_backend() == "tpu":
        options = {"xla_tpu_scoped_vmem_limit_kib": 65536}
    else:
        options = {}
    device = jax.devices()[0]
    print(
        f"bench: platform={device.platform} device_kind={device.device_kind} "
        f"devices={n_chips} model={MODEL} fused_stem={_fused} "
        f"compiler_options={json.dumps(options)} compile_cache={cache_dir}",
        flush=True,
    )
    # finally-close: an aborted bench is exactly the run whose trace is
    # needed to see which phase it died in.
    try:
        with tracer.span("compile"):
            compiled = step.lower(state, device_batch).compile(
                compiler_options=options or None
            )
        flops_per_step = step_flops(compiled)

        with tracer.span("warmup", args={"steps": WARMUP_STEPS}):
            for _ in range(WARMUP_STEPS):
                state, metrics = compiled(state, device_batch)
            jax.block_until_ready(state.params)

        t0 = time.perf_counter()
        with tracer.span("measure", args={"steps": MEASURE_STEPS}):
            for _ in range(MEASURE_STEPS):
                state, metrics = compiled(state, device_batch)
            jax.block_until_ready(state.params)
        dt = time.perf_counter() - t0
    finally:
        tracer.close()

    ips = MEASURE_STEPS * batch / dt
    # cost_analysis() FLOPs are PER-DEVICE under SPMD partitioning, so this
    # is already per-chip achieved TFLOP/s — no further division by n_chips.
    tflops_per_chip = flops_per_step * MEASURE_STEPS / dt / 1e12
    peak = peak_bf16_tflops(device)
    record = {
        "metric": (
            f"{MODEL} train images/sec/chip (bf16, {NUM_CLASSES} classes, "
            f"{IMAGE}px, batch {BATCH_PER_CHIP}/chip, {n_chips} chip(s))"
        ),
        "value": round(ips / n_chips, 2),
        "unit": "images/sec/chip",
        "vs_baseline": round(ips / n_chips / REFERENCE_IMG_PER_SEC_PER_WORKER, 2),
        "tflops_per_chip": round(tflops_per_chip, 2),
        "platform": device.platform,
        "device_kind": device.device_kind,
        "device_count": n_chips,
        "fused_stem": _fused,
        "compiler_options": options,
    }
    if peak and flops_per_step > 0:
        record["mfu_pct"] = round(100.0 * tflops_per_chip / peak, 1)
    print(json.dumps(record))


if __name__ == "__main__":
    main()
