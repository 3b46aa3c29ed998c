"""Training driver — the TPU-native ``main.py``.

Structural parity with the reference driver (``main.py:49-189``), stage by
stage:

| reference (main.py)                         | here                           |
|---------------------------------------------|--------------------------------|
| MPI world setup (``:16-18``)                | mesh over all chips            |
| rank-0 CSV read + scatter (``:73-91``)      | ``load_manifests`` + per-host shard |
| DataLoader(batch, shuffle) (``:99-102``)    | ``DataLoader`` (prefetching)   |
| model/opt init (``:121-125``)               | ``create_model_bundle`` + optax|
| FROM_CHECKPOINT resume (``:127-129``)       | ``latest_checkpoint`` restore  |
| ``sync_params`` broadcast (``:131``)        | ``place_state_on_mesh``        |
| epoch loop + ``mpi_avg_grads`` (``:142-160``)| jitted DP step over the mesh  |
| rank-0 checkpoint (``:162-171``)            | process-0 ``save_checkpoint``  |
| rank-0 validation (``:173-185``)            | sharded batched eval           |
"""

from __future__ import annotations

import os
import signal
import threading
import time
from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from mpi_pytorch_tpu import checkpoint as ckpt
from mpi_pytorch_tpu.config import Config
from mpi_pytorch_tpu.data import DataLoader, load_manifests, manifest_fingerprint
from mpi_pytorch_tpu.data.tokens import TokenLoader, load_token_manifests
from mpi_pytorch_tpu.models import create_model_bundle
from mpi_pytorch_tpu.models.registry import model_spec
from mpi_pytorch_tpu.obs import (
    FlightRecorder,
    Heartbeat,
    MetricsRegistry,
    SLOMonitor,
    StepHealth,
    Tracer,
    parse_rules,
)
from mpi_pytorch_tpu.obs import trace as obs_trace
from mpi_pytorch_tpu.parallel.collectives import LEDGER
from mpi_pytorch_tpu.parallel.mesh import (
    create_mesh,
    data_axis_names,
    data_axis_size,
    flat_mesh,
    is_hierarchical,
    pod_shape,
    shard_batch,
    zero_shard_axis,
)
from mpi_pytorch_tpu.train import elastic
from mpi_pytorch_tpu.train.state import (
    TrainState,
    make_optimizer,
    zero_shard_opt_state,
    zero_unshard_opt_state,
)
from mpi_pytorch_tpu.train.step import (
    STEP_METRICS,
    bucket_overlap_frac,
    grad_bucket_plan,
    hier_dcn_overlap_frac,
    make_cached_eval_step,
    make_cached_train_step,
    make_eval_step,
    make_scanned_epoch,
    make_spmd_train_step,
    make_train_step,
    place_state_on_mesh,
)
from mpi_pytorch_tpu.utils import hardware as hw
from mpi_pytorch_tpu.utils.logging import MetricsWriter, init_logger, run_logger


@dataclass
class TrainSummary:
    epochs_run: int = 0
    final_loss: float = float("nan")
    val_accuracy: float | None = None
    epoch_times: list = field(default_factory=list)
    images_per_sec: float = 0.0
    checkpoint_path: str | None = None
    epoch_losses: list = field(default_factory=list)
    preempted: bool = False
    best_accuracy: float | None = None  # track_best: best val acc this run


class PreemptionGuard:
    """Graceful SIGTERM/SIGINT handling (SURVEY §5 failure-detection row).

    Cluster schedulers and TPU maintenance events deliver SIGTERM with a
    grace window; the reference's fail-stop MPI world dies mid-step and
    relies on a manual ``FROM_CHECKPOINT`` restart. Here the FIRST signal
    only sets a flag that the train loop polls — the run stops at the next
    safe boundary, saves any unsaved completed-epoch progress, drains the
    in-flight async checkpoint write, and returns normally with
    ``summary.preempted=True`` (exit code 0, auto-resume picks up the saved
    epoch). A SECOND signal restores the previous handler and re-raises it —
    the escape hatch if the graceful drain itself wedges.

    Installed only from the main thread (Python restricts ``signal.signal``
    to it); elsewhere the guard is inert and the signals keep their prior
    behavior."""

    SIGNALS = (signal.SIGTERM, signal.SIGINT)

    def __init__(self) -> None:
        self.triggered = False
        self._previous: dict[int, Any] = {}

    def __enter__(self) -> "PreemptionGuard":
        if threading.current_thread() is threading.main_thread():
            for sig in self.SIGNALS:
                self._previous[sig] = signal.signal(sig, self._handle)
        return self

    def __exit__(self, *exc) -> None:
        for sig, prev in self._previous.items():
            signal.signal(sig, prev)
        self._previous.clear()

    def _handle(self, signum, frame) -> None:
        if self.triggered:  # second signal: defer to the original behavior
            prev = self._previous.get(signum)
            signal.signal(signum, prev if prev is not None else signal.SIG_DFL)
            signal.raise_signal(signum)
            return
        self.triggered = True


def _global_max(value: float, mesh) -> float:
    """Tiny all-reduce: the max of every process's ``value`` over the whole
    mesh (single-process: identity). The one collective that decisions read
    off the host side go through — anything that gates entering a collective
    (stop flags, best-accuracy init) must agree across processes."""
    if jax.process_count() == 1:
        return value
    from jax.sharding import NamedSharding, PartitionSpec as P

    local = np.full((jax.local_device_count(),), value, np.float32)
    sharding = NamedSharding(mesh, P(tuple(mesh.axis_names)))  # 1-D over all devices
    vals = jax.make_array_from_process_local_data(sharding, local)
    return float(jnp.max(vals))


def _stop_agreed(stop: bool, mesh) -> bool:
    """Epoch-boundary stop decision: EITHER all processes break before the
    next epoch or none do — a host stopping unilaterally would leave the
    others blocked in the next collective step. ``stop`` is this process's
    local verdict (the watchdog's poll of SIGTERM/sentinel/health streaks)."""
    return _global_max(1.0 if stop else 0.0, mesh) > 0.0


def _p0_scalar(value: float, mesh) -> float:
    """Process 0's ``value`` on every process: non-0 processes contribute
    -inf to the global max. Used where a value read from process 0's
    filesystem (e.g. the best.json marker) feeds a decision that gates a
    collective — every process must start from the same number even when
    the checkpoint dir is not a shared filesystem."""
    return _global_max(value if jax.process_index() == 0 else float("-inf"), mesh)


def _dtype(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[name]


def build_training(cfg: Config, mesh=None):
    """Construct (mesh, bundle, state, loaders, step fns) for cfg — shared by
    the trainer, the eval pipeline, and the graft entry points."""
    mesh = mesh or create_mesh(cfg.mesh)
    compute_dtype = _dtype(cfg.compute_dtype)

    if cfg.batch_size % jax.process_count() != 0:
        raise ValueError(
            f"global batch {cfg.batch_size} not divisible by {jax.process_count()} hosts"
        )
    data_size = data_axis_size(mesh)
    if cfg.batch_size % data_size != 0:
        raise ValueError(
            f"global batch {cfg.batch_size} not divisible by data-parallel size "
            f"{data_size}; sharding the batch over the "
            f"'{'×'.join(data_axis_names(mesh))}' ax{'es' if is_hierarchical(mesh) else 'is'} "
            "requires even division"
        )
    host_batch = cfg.batch_size // jax.process_count()
    if cfg.accum_steps > 1 and (cfg.batch_size // cfg.accum_steps) % data_size != 0:
        raise ValueError(
            f"microbatch {cfg.batch_size}/{cfg.accum_steps} not divisible by "
            f"data-parallel size {data_size}"
        )

    # What a sample is, is the model's to say (registry ModelSpec.sample);
    # manifests and loader follow from it here and nowhere else. Per-host
    # sharding ≙ rank-0 scatter (main.py:84-91): host p reads only its own
    # shard; no coordinator, no pickled dataframes over the wire.
    spec = model_spec(cfg.model_name)
    if spec.sample == "tokens":
        # Packed sequences, checked against the model's vocabulary (data/tokens.py).
        train_manifest, test_manifest = load_token_manifests(
            cfg, spec.vocab(cfg.model_config)
        )
        train_loader = TokenLoader(
            train_manifest.shard(jax.process_count(), jax.process_index()), host_batch
        )
    else:
        train_manifest, test_manifest = load_manifests(cfg)
        train_loader = DataLoader(
            train_manifest.shard(jax.process_count(), jax.process_index()),
            batch_size=host_batch,
            image_size=cfg.image_size,
            shuffle=cfg.shuffle,
            seed=cfg.seed,
            drop_remainder=cfg.drop_remainder,
            synthetic=cfg.synthetic_data,
            num_workers=cfg.loader_workers,
            prefetch=cfg.prefetch_batches,
            image_dtype=cfg.input_dtype,
            native_decode=cfg.native_decode,
            decode_prescale=cfg.decode_prescale,
            host_cache=cfg.host_cache,
            packed_dir=cfg.packed_dir,
            max_bad_samples=cfg.max_bad_samples,
            quarantine_file=cfg.quarantine_file,
        )

    bundle, variables = create_model_bundle(
        cfg.model_name,
        cfg.num_classes,
        feature_extract=cfg.feature_extract,
        use_pretrained=cfg.use_pretrained,
        rng=jax.random.PRNGKey(cfg.seed),
        image_size=cfg.image_size[0],
        dtype=compute_dtype,
        param_dtype=jnp.float32,
        # Sync-BN: in spmd mode the axis name must be bound inside shard_map;
        # in auto mode BN already normalizes over the logical global batch
        # (the compiler inserts the cross-device mean), so no axis is needed.
        # Nested meshes sync over both data factors (flax forwards the
        # tuple to lax.pmean unchanged).
        bn_axis_name=(
            (data_axis_names(mesh) if is_hierarchical(mesh) else mesh.axis_names[0])
            if (cfg.sync_batchnorm and cfg.spmd_mode) else None
        ),
        pretrained_dir=cfg.pretrained_dir,
        remat_blocks=(cfg.remat == "blocks"),
        sp_strategy=cfg.sp_strategy,
        sp_mesh=flat_mesh(mesh, "seq") if cfg.sp_strategy != "none" else None,
        ep_mesh=flat_mesh(mesh, "expert") if cfg.expert_parallel else None,
        attn_impl=cfg.attn_impl,
        qkv_fused=cfg.qkv_fused,
        stem_s2d=cfg.stem_s2d,
        fused_stem=cfg.fused_stem,
        # Multi-chip kernels: the model shard_maps its Mosaic calls (fused
        # stem, dense attention's single-pass kernel) over the mesh's data
        # axis (ops/fused_stem.py / ops/fused_attention_small.py,
        # Multi-chip); a model with neither ignores the mesh. Threaded in
        # spmd mode too: inside the spmd step's shard_map the wrappers
        # detect the bound axis and run the per-shard call directly, while
        # spmd-mode VALIDATION (plain-jit eval over the same model) still
        # gets the partitioned call.
        dp_mesh=mesh,
        model_config=cfg.model_config,
    )
    # Total optimizer steps for cosine-style schedules: the globally-computed
    # per-epoch step count (identical on every host) x epochs.
    total_steps = (
        global_step_count(len(train_manifest), host_batch, cfg.drop_remainder)
        * cfg.num_epochs
    )
    tx = make_optimizer(
        cfg.learning_rate,
        bundle.trainable_mask,
        optimizer=cfg.optimizer,
        lr_schedule=cfg.lr_schedule,
        warmup_steps=cfg.warmup_steps,
        total_steps=total_steps,
        weight_decay=cfg.weight_decay,
    )
    state = TrainState.create(
        apply_fn=bundle.model.apply,
        variables=variables,
        tx=tx,
        rng=jax.random.PRNGKey(cfg.seed + 1),
    )
    if cfg.pp_stages > 1:
        # PP is an execution strategy, not a different model: swap the
        # apply_fn for the pipelined forward over the SAME param tree
        # (parallel/pp_vit.py), and every step flavor keyed on
        # state.apply_fn — streaming, cached, scanned-epoch, eval —
        # pipelines from here on.
        from mpi_pytorch_tpu.parallel.pp_vit import pp_apply_from_config

        state = state.replace(
            apply_fn=pp_apply_from_config(
                cfg, bundle.model, mesh, remat=(cfg.remat == "blocks")
            )
        )
    return mesh, bundle, state, (train_manifest, test_manifest, train_loader)


def pad_batch(images: np.ndarray, labels: np.ndarray, target: int):
    """Pad a tail batch to the static ``target`` rows; label -1 marks padding,
    which the loss/accuracy ops mask out (ops/losses.py). Static shapes mean
    XLA never recompiles, and no images are dropped (the reference's
    DataLoader keeps tail batches too, ``main.py:99-102``).

    Padding rows repeat real rows (cyclically) rather than injecting zero
    images: the loss masks them either way, but during training BatchNorm
    batch statistics span the whole padded batch, and repeated real rows keep
    those stats unbiased in expectation where zero rows would skew them
    (the reference instead trains on the smaller real tail batch)."""
    pad = target - images.shape[0]
    if pad <= 0:
        return images, labels
    images = np.concatenate([images, _cyclic_fill(images, pad)])
    labels = np.concatenate([labels, np.full(pad, -1, labels.dtype)])
    return images, labels


def _cyclic_fill(images: np.ndarray, n: int) -> np.ndarray:
    """``n`` rows of real image content, repeating ``images`` cyclically
    (zeros only when there are no real rows at all) — the shared fill
    strategy of ``pad_batch`` and ``synchronized_batches``."""
    if images.shape[0] == 0:
        return np.zeros((n, *images.shape[1:]), images.dtype)
    return images[np.resize(np.arange(images.shape[0]), n)]


def global_step_count(total_examples: int, host_batch: int, drop_remainder: bool) -> int:
    """Number of steps EVERY host must run per epoch, computed from global
    quantities so it is identical on all hosts.

    Per-host shards come from ``np.array_split`` semantics (manifest.shard),
    so shard sizes differ by up to 1 across hosts. Each step is a global SPMD
    program: a host running one extra (or one fewer) step than its peers
    deadlocks the collective. With drop_remainder the count is what the
    *smallest* shard yields (larger shards truncate); without, it is what the
    *largest* shard yields (exhausted shards feed all-padding batches)."""
    procs = jax.process_count()
    if drop_remainder:
        return (total_examples // procs) // host_batch
    largest = -(-total_examples // procs)
    return -(-largest // host_batch)


def data_cursor(
    cfg: Config, fingerprint: str, n_steps: int, next_epoch: int, step_in_epoch: int
) -> dict:
    """The exact-step resume cursor stamped into every checkpoint's topology
    sidecar (ISSUE 10): WHERE the run continues — ``(epoch, step_in_epoch)``
    in the deterministic global walk — plus everything that must still hold
    for that offset to mean the same samples: the shuffle discipline
    (seed/shuffle), the global batch and per-epoch step count (steps ×
    global batch is the topology-invariant sample count), the host count
    (per-host shards derive from it on the streaming path), and the global
    train manifest's fingerprint. ``validate_cursor`` checks each field and
    falls back to epoch replay on any mismatch — the cursor can be ignored,
    never silently misaligned."""
    return {
        "epoch": int(next_epoch),
        "step_in_epoch": int(step_in_epoch),
        "seed": int(cfg.seed),
        "shuffle": bool(cfg.shuffle),
        "global_batch": int(cfg.batch_size),
        "drop_remainder": bool(cfg.drop_remainder),
        "processes": int(jax.process_count()),
        "steps_per_epoch": int(n_steps),
        "manifest_fingerprint": fingerprint,
    }


def validate_cursor(
    cursor, *, cfg: Config, fingerprint: str, n_steps: int, start_epoch: int
) -> tuple[int, str | None]:
    """``(start_step, None)`` when ``cursor`` still describes this run's
    data walk, else ``(0, why)`` — the caller logs the typed warning and
    replays the epoch (today's behavior), never silently misaligning."""
    if not isinstance(cursor, dict):
        return 0, "no data cursor in the checkpoint's topology manifest"
    expected = {
        "epoch": start_epoch,
        "seed": int(cfg.seed),
        "shuffle": bool(cfg.shuffle),
        "global_batch": int(cfg.batch_size),
        "drop_remainder": bool(cfg.drop_remainder),
        "processes": int(jax.process_count()),
        "steps_per_epoch": int(n_steps),
        "manifest_fingerprint": fingerprint,
    }
    for key, want in expected.items():
        got = cursor.get(key)
        if got != want:
            return 0, f"cursor {key}={got!r} != current {want!r}"
    step = int(cursor.get("step_in_epoch", 0))
    if not 0 <= step < max(n_steps, 1):
        return 0, f"cursor step_in_epoch={step} outside 0..{n_steps - 1}"
    if step and cfg.scan_epoch:
        # A partial scanned epoch would need a differently-shaped scan
        # (one extra compile for a state the scan path can never itself
        # produce — scans never stop mid-epoch). Replay instead.
        return 0, "mid-epoch cursor with scan_epoch=True (scan is all-or-nothing)"
    return step, None


def _abort_skip_limit(metrics, epoch: int, streak: int, limit: int) -> None:
    """``--bad-step-policy skip`` ran out of patience: N consecutive
    non-finite updates were discarded, so the divergence is systematic, not
    transient — record it and abort (the same typed error the sentinel
    raises, so callers handle both abort paths uniformly)."""
    from mpi_pytorch_tpu.obs.health import NonFiniteLossError

    metrics.write(
        {
            "kind": "anomaly", "reason": "skip_limit", "epoch": epoch,
            "detail": f"{streak} consecutive skipped steps hit "
                      f"max_skipped_steps={limit}",
        }
    )
    raise NonFiniteLossError(
        f"{streak} consecutive non-finite steps were skipped (epoch {epoch}) "
        f"— hit --max-skipped-steps={limit}; the divergence is systematic, "
        "aborting instead of discarding updates forever"
    )


def synchronized_batches(
    loader: DataLoader, epoch: int, n_steps: int, start_step: int = 0
):
    """Yield exactly ``n_steps - start_step`` (images, labels) host-batches
    from ``loader`` — steps ``start_step..n_steps-1`` of the epoch — padding
    with all-padding batches (every label -1) once the local shard is
    exhausted and truncating any surplus, so every host issues the same
    number of collective steps (see ``global_step_count``). ``start_step``
    is the exact-step resume fast-forward: the loader skips the consumed
    prefix of its deterministic ``(seed, epoch)`` order without decoding it.

    Filler batches repeat the images of the last REAL batch (labels all -1):
    the loss masks them either way, but BatchNorm batch statistics span
    whatever images the step sees, so filler must be real image content, not
    zeros — the same reasoning as ``pad_batch``."""
    it = iter(loader.epoch(epoch, start_batch=start_step))
    all_pad = np.full((loader.batch_size,), -1, np.int32)
    last_images = None
    try:
        for _ in range(start_step, n_steps):
            batch = next(it, None)
            if batch is not None:
                last_images = batch[0]
                yield batch
            else:
                if last_images is None:  # empty local shard: no real rows exist
                    last_images = np.zeros(
                        (0, *loader.image_size, 3), loader.image_dtype
                    )
                yield (_cyclic_fill(last_images, loader.batch_size), all_pad)
    finally:
        if hasattr(it, "close"):
            it.close()  # stops the producer thread on early exit / truncation


def cached_index_batches(
    cfg: Config, n: int, host_batch: int, epoch: int, n_steps: int,
    shuffle: bool | None = None, start_step: int = 0,
):
    """Per-epoch (idx [B] int32, valid [B] bool) batches for the
    device-cache path. The permutation uses the same ``(seed, epoch)`` rng
    discipline as ``DataLoader.epoch``, so a cached run and a streaming run
    walk the data in the same order; tail indices repeat real rows
    (the ``_cyclic_fill`` policy) with ``valid=False``. ``shuffle=False``
    gives the ordered walk the cached eval path uses; ``start_step`` is the
    exact-step resume fast-forward (the consumed prefix of the permutation
    is simply not yielded)."""
    from mpi_pytorch_tpu.data.pipeline import epoch_order

    order = epoch_order(cfg.seed, epoch, n, cfg.shuffle if shuffle is None else shuffle)
    for step_i in range(start_step, n_steps):
        idx = order[step_i * host_batch : (step_i + 1) * host_batch]
        valid = np.ones(len(idx), bool)
        pad = host_batch - len(idx)
        if pad > 0:
            fill = np.resize(idx, pad) if len(idx) else np.zeros(pad, order.dtype)
            idx = np.concatenate([idx, fill])
            valid = np.concatenate([valid, np.zeros(pad, bool)])
        yield idx.astype(np.int32), valid


def _state_shardings(state):
    """The placed state's shardings, used to PIN the train step's output
    state layout to its input layout. Without this the AOT executable's
    output shardings are compiler-chosen, and with ZeRO-sharded moments XLA
    happily emits data-sharded *params* — which the next call then rejects,
    since AOT executables do not auto-reshard their inputs."""
    return jax.tree_util.tree_map(lambda x: x.sharding, state)


def device_prefetch(
    batches, mesh, host_batch: int, depth: int = 2, *, epoch: int = 0,
    start_step: int = 0,
):
    """Double-buffered host→device transfer: pad + ``shard_batch`` each
    host batch ``depth`` steps ahead of the consumer. ``device_put`` is
    asynchronous, so the H2D copy for batch N+1 overlaps the compute of
    batch N — the overlap the reference's 4-stage MPI pipeline bought with
    dedicated ranks (``evaluation_pipeline.py:53-129``), at zero process
    cost.

    Each batch's pad + ``shard_batch`` is one ``h2d`` span on the run's
    tracer (args ``bytes``, ``epoch``, ``batch`` — the last two only name
    the batch, which is what lets a device trace be joined to it): the
    host's share of the transfer. A finished batch then waits in ``buf``
    until ``depth`` more are behind it; on a traced run the instant
    ``prefetch/yield`` marks the moment it is handed to the step loop (args
    the ``h2d`` span's ``epoch`` and ``batch``, ``held_ms`` since that span
    closed, ``behind``: the batches still in ``buf``). With two batches an
    epoch and ``depth`` 2 the first is held for the whole decode of the
    second."""
    from collections import deque

    tracer = obs_trace.current()
    buf = deque()  # (batch index, its h2d span's Timed, the sharded batch)

    def hand_over():
        batch_i, h2d, batch = buf.popleft()
        if tracer.enabled:
            tracer.instant(
                "prefetch/yield",
                args={
                    "epoch": epoch, "batch": batch_i,
                    "held_ms": tracer.ms_since(h2d), "behind": len(buf),
                },
            )
        return batch

    for batch_i, (images, labels) in enumerate(batches, start=start_step):
        args = {"epoch": epoch, "batch": batch_i}
        with tracer.span("h2d", args=args) as h2d:
            images, labels = pad_batch(images, labels, host_batch)
            args["bytes"] = int(images.nbytes + labels.nbytes)
            buf.append((batch_i, h2d, shard_batch((images, labels), mesh)))
        if len(buf) > depth:
            yield hand_over()
    while buf:
        yield hand_over()


def build_device_cache(cfg: Config, manifest, loader, mesh):
    """Materialize the train split as a device-resident dataset with rows
    SHARDED over the data axis — per-device HBM is ``dataset/n_data``, not a
    full replica per chip — plus replicated (tiny) labels. One decode pass
    in manifest order; the per-epoch shuffle happens on indices instead, and
    the step gathers batch rows across shards (``step._sharded_cache_take``).

    ``manifest`` is the GLOBAL train manifest: global row i is dataset row i
    on every host, so the identical seeded index permutation each host draws
    refers to the same images. Each host decodes exactly the contiguous row
    range its local devices hold (data is the mesh's major axis), which is
    what makes the cache build itself scale with the host count. Rows are
    padded up to a multiple of the data-axis size; padding rows sit past the
    real row count and are never indexed."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    data_axis = mesh.axis_names[0]
    n_data = mesh.shape[data_axis]
    n = len(manifest)
    padded = -(-n // n_data) * n_data
    sharding = NamedSharding(mesh, P(data_axis))
    # What a row is, is the loader's to say (``cache_row``): a decoded image,
    # or a token model's packed sequence — whose label column is the
    # manifest's row checksums, which the step does not read.
    row, dtype = loader.cache_row
    shape = (padded, *row)

    # This host's addressable slice of the sharded rows: contiguous because
    # ``data`` is the leading (process-major) mesh axis.
    imap = sharding.addressable_devices_indices_map(shape)
    lo = min((s[0].start or 0) for s in imap.values())
    hi = max((s[0].stop if s[0].stop is not None else padded) for s in imap.values())
    real_hi = min(hi, n)

    # Preallocate and fill in place: np.concatenate over a parts list would
    # transiently hold the slice twice, at exactly the scale (GBs) this
    # feature targets. Zeros beyond real_hi are the never-indexed padding.
    local = np.zeros((hi - lo, *row), dtype)
    labels_np = manifest.labels.astype(np.int32)
    quarantined = loader.fill_cache_rows(manifest, lo, real_hi, local)
    if quarantined:
        if jax.process_count() > 1:
            # Each host decodes only its own row range, so a per-host
            # label mask would make the REPLICATED labels array differ
            # across hosts — silent divergence inside every collective
            # step. Abort loudly instead (the quarantine trail names
            # the files); multi-host runs must fix the data or take
            # the streaming/host-cache path, whose masking is local.
            from mpi_pytorch_tpu.data.pipeline import BadSampleLimitError

            raise BadSampleLimitError(
                f"{len(quarantined)} sample(s) quarantined "
                "while building the multi-host device cache — per-host "
                "label masking cannot stay consistent across hosts; "
                "repair/remove the corrupt files (see the quarantine "
                "log) or drop --device-cache"
            )
        # Quarantined rows hold substitute pixels — mask their labels.
        labels_np = labels_np.copy()
        labels_np[lo + np.fromiter(quarantined, int)] = -1

    rep = NamedSharding(mesh, P())
    if jax.process_count() == 1:
        dataset = jax.device_put(local, sharding)
        labels = jax.device_put(labels_np, rep)
    else:
        dataset = jax.make_array_from_process_local_data(sharding, local)
        labels = jax.make_array_from_process_local_data(rep, labels_np)
    jax.block_until_ready(dataset)
    return dataset, labels


def make_eval_loader(cfg: Config, manifest, host_cache: bool = False) -> DataLoader:
    """The eval/validation DataLoader over this host's shard of ``manifest``.
    ``host_cache`` defaults OFF: a one-shot evaluation streams through the
    data once, so pre-decoding a full shard-sized cache would cost strictly
    more. Per-epoch validation passes ``cfg.host_cache`` and reuses ONE
    loader across epochs (the loader owns the cache) — and under
    ``val_on_train`` it adopts the train loader's cache outright."""
    return DataLoader(
        manifest.shard(jax.process_count(), jax.process_index()),
        batch_size=cfg.batch_size // jax.process_count(),
        image_size=cfg.image_size,
        shuffle=False,
        drop_remainder=False,
        synthetic=cfg.synthetic_data,
        num_workers=cfg.loader_workers,
        prefetch=cfg.prefetch_batches,
        image_dtype=cfg.input_dtype,
        native_decode=cfg.native_decode,
        decode_prescale=cfg.decode_prescale,
        host_cache=host_cache,
        packed_dir=cfg.packed_dir,
        max_bad_samples=cfg.max_bad_samples,
        quarantine_file=cfg.quarantine_file,
    )


def evaluate_manifest(
    cfg: Config, state: TrainState, mesh, manifest, loader: DataLoader | None = None
) -> tuple[float, float]:
    """Batched sharded eval over a manifest → (accuracy, mean_loss).
    ≙ the rank-0 validation loop (``main.py:173-185``), but using every chip.
    Pass a ``make_eval_loader`` instance to reuse its host cache across calls."""
    eval_step = make_eval_step(_dtype(cfg.compute_dtype))
    host_batch = cfg.batch_size // jax.process_count()
    if loader is None:
        loader = make_eval_loader(cfg, manifest)
    n_steps = global_step_count(len(manifest), host_batch, drop_remainder=False)
    return _accumulate_eval(
        eval_step(state, shard_batch(pad_batch(images, labels, host_batch), mesh))
        for images, labels in synchronized_batches(loader, 0, n_steps)
    )


def _accumulate_eval(metric_batches) -> tuple[float, float]:
    """Fold per-batch eval metrics into (accuracy, mean_loss) — the one
    accounting shared by the streaming and cached eval paths."""
    correct = total = 0
    loss_sum = 0.0
    for m in metric_batches:
        correct += int(m["correct"])
        total += int(m["count"])
        loss_sum += float(m["loss"])
    if total == 0:
        return 0.0, float("nan")
    return correct / total, loss_sum / total


def evaluate_cached(cfg: Config, state: TrainState, mesh, dataset, labels) -> tuple[float, float]:
    """Batched eval over a DEVICE-RESIDENT dataset → (accuracy, mean_loss).
    Same semantics as ``evaluate_manifest`` but zero host decode / H2D per
    call — per-epoch validation over an HBM-cached val set (with
    ``val_on_train=True``, the reference's default, the val set IS the
    already-cached train set)."""
    eval_step = make_cached_eval_step(mesh, _dtype(cfg.compute_dtype))
    # Real row count from the labels: the sharded dataset's row dim carries
    # divisibility padding past it (build_device_cache) that must not be
    # evaluated. Index batches are global and identical on every host.
    n = int(labels.shape[0])
    n_steps = -(-n // cfg.batch_size)
    return _accumulate_eval(
        eval_step(state, dataset, labels, idx, valid)
        for idx, valid in cached_index_batches(
            cfg, n, cfg.batch_size, epoch=0, n_steps=n_steps, shuffle=False
        )
    )


def train(cfg: Config) -> TrainSummary:
    from mpi_pytorch_tpu.parallel.distributed import maybe_initialize_distributed

    from mpi_pytorch_tpu.config import apply_runtime_flags

    maybe_initialize_distributed()
    apply_runtime_flags(cfg)
    logger = init_logger("MPT", cfg.log_file)
    metrics = MetricsWriter(cfg.metrics_file)
    # Run telemetry (obs/): host-side trace spans, per-step health records,
    # the NaN sentinel, and the multi-host straggler heartbeat. All inert
    # unless their knobs are set (the sentinel's epoch check is free).
    tracer = Tracer(cfg.trace_file)
    # Anomaly flight recorder (obs/flight.py): tap the metrics writer so
    # every record on EVERY process enters the ring (only process 0's
    # writer persists the stream), and any fault/alert record dumps it.
    flight = None
    if cfg.flight_dir:
        flight = FlightRecorder(
            cfg.flight_dir, capacity=cfg.flight_records,
            profile_window_s=cfg.flight_profile_window_s,
        )
        metrics = flight.tap(metrics)
    # Live metrics registry + SLO monitor (obs/metrics.py, obs/monitor.py):
    # built only when a live consumer is configured — the default hot path
    # never touches either.
    registry = monitor = None
    if cfg.slo_rules or cfg.metrics_every_steps:
        registry = MetricsRegistry()
    if cfg.slo_rules:
        monitor = SLOMonitor(
            registry, parse_rules(cfg.slo_rules), metrics=metrics,
            preempt_path=cfg.preempt_file, tracer=tracer, logger=logger,
        )
    # With a bad-step POLICY armed (skip/rollback) the sentinel's hard
    # abort is replaced by the policy: the non-finite step is the event the
    # policy handles, not a reason to crash (the policy's own bounds —
    # max_skipped_steps / max_rollbacks — are the new aborts).
    health = StepHealth(
        metrics, step_metrics=cfg.step_metrics,
        nan_sentinel=cfg.nan_sentinel and cfg.bad_step_policy == "abort",
        tracer=tracer, registry=registry,
    )
    heartbeat = Heartbeat(
        metrics, every_steps=cfg.heartbeat_every_steps,
        threshold=cfg.straggler_threshold, batch_images=cfg.batch_size,
        tracer=tracer, registry=registry,
    )
    if heartbeat.enabled and cfg.device_cache and cfg.scan_epoch:
        # The scan runs the whole epoch on device — there are no per-step
        # host returns to beat on. Surface it instead of silently recording
        # nothing (the fused-head-eval lesson, advisor r5).
        run_logger().warning(
            "heartbeat_every_steps=%d has no effect with scan_epoch=True "
            "(the epoch is one device-side scan; no per-step host "
            "boundaries to exchange step times at)",
            cfg.heartbeat_every_steps,
        )
        heartbeat.enabled = False
    if registry is not None and cfg.metrics_every_steps and cfg.device_cache and cfg.scan_epoch:
        # Same silent-degrade class: the scan path has no per-step host
        # boundaries, so the snapshot cadence never advances — only the
        # run-end snapshot lands. (slo_rules + scan_epoch is already a
        # config ERROR; a reduced snapshot cadence merely degrades.)
        run_logger().warning(
            "metrics_every_steps=%d has no per-step cadence with "
            "scan_epoch=True (the epoch is one device-side scan); only "
            "the final kind='metrics' snapshot will be written",
            cfg.metrics_every_steps,
        )
    # Per-step telemetry must observe step COMPLETION, not dispatch: block
    # on the step's metrics before timestamping (documented cost of
    # step_metrics/heartbeat; registry step-time gauges/histograms must be
    # completion times too, so a live registry also syncs; the default
    # loop stays fully async). A bad-step policy also syncs: the host must
    # observe every step's loss/grad-norm verdict to count skips or
    # trigger a rollback.
    telemetry_sync = (
        health.enabled or heartbeat.enabled or registry is not None
        or cfg.bad_step_policy != "abort"
    )
    try:
        # The run's tracer is also the process-wide current one for its
        # length: the layers below (data/pipeline.py, device_prefetch) open
        # their spans on it without a flag of their own.
        with obs_trace.use(tracer):
            return _train_impl(
                cfg, logger, metrics, tracer, health, heartbeat, telemetry_sync,
                registry, monitor, flight,
            )
    except BaseException:
        # A failure anywhere — including build/cache/compile, BEFORE the
        # epoch loop's own handler exists — must still flush the buffered
        # spans: the aborted run is exactly the one whose trace is needed.
        # The flight recorder dumps its last-moments ring the same way.
        try:
            tracer.close()
        except BaseException as terr:
            logger.warning("trace write also failed: %s", terr)
        if flight is not None:
            try:
                flight.dump("crash")
                flight.close()
            except BaseException as ferr:
                logger.warning("flight-recorder dump also failed: %s", ferr)
        raise


def _train_impl(
    cfg: Config, logger, metrics, tracer, health, heartbeat, telemetry_sync,
    registry=None, monitor=None, flight=None,
) -> TrainSummary:
    with tracer.span("build"):
        mesh = None
        if cfg.from_checkpoint:
            # Resume side: backend init retries with bounded backoff — a
            # transiently wedged backend must cost attempts, not the
            # auto-resume (train/elastic.py).
            mesh = elastic.with_retries(
                lambda: create_mesh(cfg.mesh),
                what="backend init (mesh build)",
                retries=cfg.resume_retries, backoff_s=cfg.resume_backoff_s,
                logger=logger,
            )
        mesh, bundle, state, (train_manifest, test_manifest, loader) = build_training(
            cfg, mesh=mesh
        )
    logger.info(
        "world: %d process(es), %d device(s), mesh %s",
        jax.process_count(), jax.device_count(), dict(mesh.shape),
    )
    logger.info(
        "model %s | %d classes | global batch %d | shard %d images (≙ scatter, main.py:84-91)",
        cfg.model_name, cfg.num_classes, cfg.batch_size, len(loader.manifest),
    )

    # The exact-step resume cursor is defined over the GLOBAL train
    # manifest (global-sample space — topology-invariant); the loader gets
    # the metrics writer so decode quarantines land in the stream.
    fingerprint = manifest_fingerprint(train_manifest)
    loader.metrics = metrics

    start_epoch = 0
    resumed = False
    resume_manifest = None
    resume_was_dirty = False
    # ZeRO shard count: the WITHIN-POD (ici) size on a nested mesh — shards
    # place inside a pod so the param all_gather never crosses the DCN
    # (train/state.py zero_shard_opt_state); the whole data axis when flat.
    zero_shards_to = (
        zero_shard_axis(mesh)[1] if (cfg.spmd_mode and cfg.zero_opt_state) else 0
    )
    if cfg.from_checkpoint:
        # Elastic restore (train/elastic.py): newest LOADABLE checkpoint
        # (corrupt files log a kind="anomaly" record and fall back to the
        # previous one), topology manifest compared against the current
        # mesh, kind="resume" record written — the self-healing form of
        # the reference's manual FROM_CHECKPOINT restart (main.py:127-130).
        res = elastic.restore_latest(
            cfg.checkpoint_dir, state, mesh, metrics=metrics, logger=logger,
            zero_shards_to=zero_shards_to,
        )
        if res is not None:
            state, start_epoch, last_loss, _resume = res
            resumed = True
            resume_manifest = _resume.get("manifest")
            resume_was_dirty = os.path.exists(_resume["path"] + ".dirty")
            start_epoch += 1
            logger.info(
                "resumed from %s (epoch %d, loss %.4f)",
                _resume["path"], start_epoch, last_loss,
            )
        else:
            logger.info("from_checkpoint=True but no checkpoint found; fresh start")

    # With ZeRO opt-state sharding the optimizer tree must NOT go through
    # the replicated placement below: that would device_put the full
    # unsharded 2×params moments onto every device — exactly the transient
    # HBM spike the sharding exists to avoid — before the [P, chunk]
    # reshard even runs. Detach it here and hand the raw (host, on resume)
    # tree straight to zero_shard_opt_state, whose bounded per-row path
    # then never sees more than one chunk per device.
    defer_zero_opt = cfg.spmd_mode and cfg.zero_opt_state
    raw_opt_state = state.opt_state
    if defer_zero_opt:
        state = state.replace(opt_state=())
    if resumed:
        # Reshard-on-load placement, retried: the restored host state is
        # re-placed onto THIS mesh (whatever its shape), with device_put
        # wrapped in the same bounded retry+backoff as backend init.
        state = elastic.with_retries(
            lambda: elastic.checked_place(
                state, mesh, zero_optimizer=cfg.zero_optimizer, fsdp=cfg.fsdp
            ),
            what="state placement (device_put)",
            retries=cfg.resume_retries, backoff_s=cfg.resume_backoff_s,
            logger=logger,
        )
    else:
        state = place_state_on_mesh(
            state, mesh, zero_optimizer=cfg.zero_optimizer, fsdp=cfg.fsdp
        )
    if defer_zero_opt:
        state = state.replace(opt_state=raw_opt_state)
    # ZeRO opt-state sharding (spmd mode): capture the UNSHARDED optimizer
    # layout first (eval_shape: shapes only, zero device memory) — it is the
    # gather-on-save template that keeps the on-disk checkpoint format
    # identical to an unsharded run's — then repartition every moment leaf
    # [P, chunk] over the data axis (train/state.py zero_shard_spec).
    opt_template = None
    if cfg.spmd_mode and cfg.zero_opt_state:
        opt_template = jax.eval_shape(state.tx.init, state.params)
        state = state.replace(opt_state=zero_shard_opt_state(state.opt_state, mesh))
        zero_axis_name, n_zero = zero_shard_axis(mesh)
        moment_bytes = sum(
            s.data.nbytes
            for leaf in jax.tree_util.tree_leaves(state.opt_state)
            if hasattr(leaf, "addressable_shards") and leaf.ndim > 0
            for s in leaf.addressable_shards[:1]
        )
        logger.info(
            "ZeRO opt-state sharding: moments partitioned 1/%d over '%s'%s "
            "(%.1f MB/device)",
            n_zero, zero_axis_name,
            " (within-pod: the param all_gather never crosses the DCN)"
            if is_hierarchical(mesh) else "",
            moment_bytes / 1e6,
        )

    def _saveable(st: TrainState) -> TrainState:
        """The checkpoint view of the state: with ZeRO-sharded optimizer
        state, gather-on-save to the unsharded host layout (one leaf at a
        time) so the file format never depends on the run's sharding."""
        if opt_template is None:
            return st
        return st.replace(
            opt_state=zero_unshard_opt_state(st.opt_state, opt_template)
        )

    # Topology manifest stamped onto every checkpoint this run writes
    # (JSON sidecar, checkpoint.write_manifest): the world shape + ZeRO
    # shard layout an elastic restore reshards FROM (train/elastic.py).
    topology = elastic.topology_manifest(
        mesh,
        zero_opt_state=bool(zero_shards_to),
        spmd_mode=cfg.spmd_mode,
        opt_template=opt_template,
    )

    host_batch = cfg.batch_size // jax.process_count()

    # AOT-compile the step on the static batch shape: one compile serves the
    # whole run, and the executable's cost analysis gives exact FLOPs/step for
    # MFU logging (SURVEY §5 — the reference has only wall-clock timers).
    n_steps = global_step_count(len(train_manifest), host_batch, cfg.drop_remainder)
    dataset = labels_all = None
    val_loader = None  # built lazily, then reused so its host cache persists
    # Cached-mode index batches are GLOBAL (every host draws the identical
    # seeded permutation over the global manifest): one [B] index array per
    # step on all hosts, stepping over global rows.
    cache_batch = cfg.batch_size
    n_cache = len(train_manifest)
    # --bad-step-policy skip: the jitted step itself discards a non-finite
    # update (train/step.py _guard_bad_step); the host side only counts.
    bad_step_skip = cfg.bad_step_policy == "skip"
    if cfg.device_cache:
        # Step count over the GLOBAL walk (the streaming count derives from
        # per-host array_split shards and can differ by rounding off it).
        n_steps = (
            n_cache // cache_batch if cfg.drop_remainder else -(-n_cache // cache_batch)
        )
        with tracer.span("cache_build"):
            dataset, labels_all = build_device_cache(cfg, train_manifest, loader, mesh)
        n_data = mesh.shape[cfg.mesh.data_axis]
        logger.info(
            "device cache: %d images, rows sharded over %d device(s) "
            "(%.1f MB/device %s)",
            n_cache, n_data, dataset.nbytes / n_data / 1e6, dataset.dtype,
        )

    # Where the ``compile`` span's seconds go, as child spans: one ``lower``
    # per ``.lower(...)`` (tracing the Python step into StableHLO), one
    # ``load_or_compile`` per ``.compile(...)`` (``cache_hit``: the
    # persistent cache served it), one ``cost_analysis``.
    def _lower(program: str, jitted, *args):
        with tracer.span("lower", args={"program": program}):
            return jitted.lower(*args)

    def _load_or_compile(lowered):
        hits: list[str] = []

        def on_event(event: str, **_kw) -> None:
            if event == "/jax/compilation_cache/cache_hits":
                hits.append(event)

        args: dict = {}
        jax.monitoring.register_event_listener(on_event)
        try:
            with tracer.span("load_or_compile", args=args):
                compiled = lowered.compile(
                    compiler_options=cfg.parsed_compiler_options()
                )
                args["cache_hit"] = bool(hits)
        finally:
            jax.monitoring.unregister_event_listener(on_event)
        return compiled

    def _with_flops(compiled):
        with tracer.span("cost_analysis"):
            return compiled, hw.step_flops(compiled)

    def build_compiled(st: TrainState):
        """AOT-compile the train step (scan-epoch mode: the whole-epoch
        scan) against ``st``'s placed layout → ``(compiled_step,
        flops_per_step)``. Factored out of the straight-line setup so a
        bad-step ROLLBACK that rebuilt the optimizer (--rollback-lr-backoff
        embeds a new LR in the step program) can recompile against the
        restored state; the default run calls it exactly once. The compile
        span opens here, AFTER the device-cache build — a span that
        swallowed the dataset decode would misattribute ingest time to XLA,
        the exact confusion the tracer exists to prevent."""
        span = tracer.begin("compile")
        try:
            if cfg.device_cache:
                # The per-step program is the FLOPs reference either way;
                # the scan mode reuses the Lowered (cost analysis needs no
                # backend compile) because XLA counts a scan body once
                # regardless of trip count.
                lowered_step = _lower(
                    "step",
                    jax.jit(
                        make_cached_train_step(
                            mesh, _dtype(cfg.compute_dtype), remat=(cfg.remat == "full"),
                            bad_step_skip=bad_step_skip,
                        ),
                        donate_argnums=(0,), out_shardings=(_state_shardings(st), None),
                    ),
                    st, dataset, labels_all,
                    np.zeros((cache_batch,), np.int32), np.ones((cache_batch,), bool),
                )
                if cfg.scan_epoch:
                    epoch_fn = make_scanned_epoch(
                        mesh, _dtype(cfg.compute_dtype), remat=(cfg.remat == "full"),
                        bad_step_skip=bad_step_skip,
                    )
                    compiled = _load_or_compile(
                        _lower(
                            "epoch",
                            jax.jit(
                                epoch_fn, donate_argnums=(0,),
                                out_shardings=(_state_shardings(st), None),
                            ),
                            st, dataset, labels_all,
                            np.zeros((n_steps, cache_batch), np.int32),
                            np.ones((n_steps, cache_batch), bool),
                        )
                    )
                    # Per-step FLOPs for the scan mode, without compiling a
                    # throwaway per-step executable. Two wrinkles: (a)
                    # Lowered.cost_analysis() runs BEFORE SPMD partitioning,
                    # so the per-step lowering gives WHOLE-program FLOPs
                    # (÷ device_count approximates per-device); (b) whether
                    # the compiled scan's cost analysis counts the body once
                    # or trip-count times is an XLA implementation detail
                    # (observed: once). Use the compiled scan's number,
                    # disambiguated against the lowered estimate.
                    with tracer.span("cost_analysis"):
                        est = hw.step_flops(lowered_step) / max(1, jax.device_count())
                        cand = hw.step_flops(compiled)
                    if cand > 0 and est > 0 and n_steps > 1:
                        flops = (
                            cand if abs(cand - est) <= abs(cand / n_steps - est)
                            else cand / n_steps
                        )
                    else:
                        flops = cand if cand > 0 else est
                    return compiled, flops
                return _with_flops(_load_or_compile(lowered_step))
            step_fn = (
                make_spmd_train_step(
                    mesh, _dtype(cfg.compute_dtype), remat=(cfg.remat == "full"),
                    zero_opt_state=cfg.zero_opt_state,
                    grad_bucket_mb=cfg.grad_sync_buckets,
                    bad_step_skip=bad_step_skip,
                )
                if cfg.spmd_mode
                else jax.jit(
                    make_train_step(
                        _dtype(cfg.compute_dtype), remat=(cfg.remat == "full"),
                        accum_steps=cfg.accum_steps, mesh=mesh,
                        bad_step_skip=bad_step_skip,
                    ),
                    donate_argnums=(0,), out_shardings=(_state_shardings(st), None),
                )
            )
            # The sample must match the loader's batch dtype exactly — the
            # AOT executable is specialized on input avals.
            sample = shard_batch(
                (np.zeros((host_batch, *cfg.image_size, 3), loader.image_dtype),
                 np.zeros((host_batch,), np.int32)),
                mesh,
            )
            return _with_flops(_load_or_compile(_lower("step", step_fn, st, sample)))
        finally:
            tracer.end(span)

    # Per-axis collective-traffic ledger (ISSUE 15): bytes are booked at
    # TRACE time (shapes are static), so one reset + one lower = exactly
    # one step's ICI-vs-DCN traffic, attributable per collective op.
    LEDGER.reset()
    compile_t0 = time.perf_counter()
    compiled_step, flops_per_step = build_compiled(state)
    metrics.write(
        hw.compile_record(
            "train_step", compiled_step, time.perf_counter() - compile_t0
        )
    )
    traffic = LEDGER.snapshot() if cfg.spmd_mode else None
    if traffic is not None and (traffic["ici"]["ops"] or traffic["dcn"]["ops"]):
        tracer.instant(
            "collective_traffic",
            args={
                "ici_bytes_per_step": traffic["ici"]["bytes"],
                "dcn_bytes_per_step": traffic["dcn"]["bytes"],
                "dcn_by_op": traffic["dcn"]["by_op"],
            },
        )
        if registry is not None:
            registry.gauge("train/ici_bytes_per_step").set(traffic["ici"]["bytes"])
            registry.gauge("train/dcn_bytes_per_step").set(traffic["dcn"]["bytes"])
        if is_hierarchical(mesh):
            pods, ici = pod_shape(mesh)
            logger.info(
                "hierarchical sync (%d pod(s) × %d ici): %.2f MB/step ICI, "
                "%.3f MB/step DCN per device (cross-pod payload 1/%d of the "
                "gradient)",
                pods, ici, traffic["ici"]["bytes"] / 1e6,
                traffic["dcn"]["bytes"] / 1e6, ici,
            )

    # Exact-step resume (ISSUE 10): validate the restored checkpoint's data
    # cursor against THIS run's walk. A match fast-forwards the first
    # post-resume epoch past the consumed batches (zero replayed optimizer
    # steps); any mismatch writes a typed kind="anomaly" record and falls
    # back to today's epoch replay — the cursor can be ignored, never
    # silently misaligned.
    start_step = 0
    if resumed:
        cursor = (resume_manifest or {}).get("data_cursor")
        start_step, cursor_why = validate_cursor(
            cursor, cfg=cfg, fingerprint=fingerprint, n_steps=n_steps,
            start_epoch=start_epoch,
        )
        if cursor_why is not None and (cursor is not None or resume_was_dirty):
            metrics.write(
                {
                    "kind": "anomaly", "reason": "cursor_mismatch",
                    "epoch": start_epoch, "detail": cursor_why,
                }
            )
            logger.warning(
                "exact-step resume unavailable (%s) — replaying epoch %d "
                "from step 0%s", cursor_why, start_epoch,
                " (DIRTY checkpoint: the replay double-applies the partial "
                "epoch's updates)" if resume_was_dirty else "",
            )
        elif start_step:
            logger.info(
                "exact-step resume: continuing epoch %d at step %d "
                "(fast-forwarding %d consumed batch(es) without decoding)",
                start_epoch, start_step, start_step,
            )

    # Grad-sync bucket-plan telemetry (spmd + --grad-sync-buckets): one
    # instant span per bucket (bytes/leaves, in reverse-topo issue order)
    # and the static overlap_frac estimate stamped onto every step health
    # record — the plan the chip A/B (tools/bench_modes.py --levers)
    # measures against.
    _hier = is_hierarchical(mesh)
    if cfg.spmd_mode and cfg.grad_sync_buckets > 0:
        _plan = grad_bucket_plan(state.params, cfg.grad_sync_buckets)
        _overlap = bucket_overlap_frac(state.params, _plan)
        _dcn_overlap = hier_dcn_overlap_frac(state.params, _plan) if _hier else None
        _leaves = jax.tree_util.tree_leaves(state.params)
        _, _ici_size = pod_shape(mesh)
        for _order, _bucket in enumerate(_plan):
            _bytes = int(
                sum(_leaves[i].size * _leaves[i].dtype.itemsize for i in _bucket)
            )
            tracer.instant(
                "grad_bucket",
                args={"order": _order, "leaves": len(_bucket), "bytes": _bytes},
            )
            if _hier:
                # The bucket's CROSS-POD phase: issued the moment its
                # within-pod reduce-scatter lands, carrying 1/ici of the
                # bucket's bytes over the DCN — one instant per bucket so
                # a chip trace can line the phases up against backward.
                tracer.instant(
                    "dcn",
                    args={
                        "order": _order,
                        "bytes": _bytes // _ici_size,
                        "of_bucket_bytes": _bytes,
                    },
                )
        health.set_sync(overlap_frac=_overlap, dcn_overlap_frac=_dcn_overlap)
        if registry is not None:
            registry.gauge("train/overlap_frac").set(_overlap)
            if _dcn_overlap is not None:
                registry.gauge("train/dcn_overlap_frac").set(_dcn_overlap)
        logger.info(
            "grad-sync buckets: %d × ~%.0f MiB (reverse-topo issue order), "
            "%.0f%% of sync bytes overlap-eligible%s%s",
            len(_plan), cfg.grad_sync_buckets, 100.0 * _overlap,
            ", reduce-scatter (ZeRO slices)" if cfg.zero_opt_state else "",
            ", two-phase ICI/DCN (per-bucket cross-pod stage overlapped)"
            if _hier else "",
        )
    elif cfg.spmd_mode and _hier:
        # Hierarchical without buckets: the whole-tree sync is still
        # two-phase (DCN carries 1/ici of the payload), but its cross-pod
        # stage waits for the full backward — nothing to overlap, which
        # the stamped 0.0 makes visible rather than implicit.
        health.set_sync(dcn_overlap_frac=0.0)
    peak = hw.peak_bf16_tflops(jax.devices()[0])
    if heartbeat.enabled and heartbeat.every > n_steps:
        # Beats never span epoch boundaries (the window resets per epoch),
        # so an interval longer than the epoch would silently never fire —
        # the same silent-degrade class as the scan_epoch case above.
        run_logger().warning(
            "heartbeat_every_steps=%d exceeds the %d step(s) per epoch — no "
            "heartbeat will ever fire (beats never span epoch boundaries); "
            "lower it to at most the per-epoch step count",
            heartbeat.every, n_steps,
        )

    # Live-registry step instrumentation, pre-bound so the loop body does
    # no registry lookups; snapshot cadence counts STEPS (not wall time)
    # because the multi-host merge inside snapshot_record is a collective
    # every process must reach at the same step.
    h_step_ms = h_wait_ms = g_step_last = None
    if registry is not None:
        h_step_ms = registry.histogram("train/step_ms")
        h_wait_ms = registry.histogram("train/data_wait_ms")
        g_step_last = registry.gauge("train/step_ms_last")
    snapshot_merge = jax.process_count() > 1
    steps_since_snapshot = 0

    summary = TrainSummary()
    checkpointer = ckpt.AsyncCheckpointer()
    total_images = 0
    train_t0 = time.perf_counter()
    epoch_loss = float("nan")

    # SURVEY §5 observability: step-level XLA traces, viewable in TensorBoard
    # (the reference only has MPI.Wtime wall-clock pairs, main.py:145,158).
    # --profile-dir traces STEADY STATE, so that benchmark/trace/ can reduce
    # what an operator records: it starts once the first execution of the
    # step program has been awaited (never the compile), stops two epoch
    # boundaries later or at the end of the run, and records the host at
    # the level the benchmark harness uses (this program's spans, nothing
    # of Python's, none of the runtime's transfer threads).
    profile_from_epoch = None  # epoch in which the profiler was started
    profile_done = not cfg.profile_dir

    def _profile_start(at_epoch: int, first_out) -> None:
        """After the run's first dispatch of the step program: await it,
        then start the trace. A no-op on every later call."""
        nonlocal profile_from_epoch
        if profile_done or profile_from_epoch is not None:
            return
        jax.block_until_ready(first_out)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(cfg.profile_dir, profiler_options=options)
        profile_from_epoch = at_epoch

    def _profile_stop(at_epoch: int | None = None) -> None:
        """Stop a running trace once ``at_epoch`` is two epochs past its
        start (None: the run is over)."""
        nonlocal profile_done
        if profile_done or profile_from_epoch is None:
            return
        if at_epoch is not None and at_epoch < profile_from_epoch + 2:
            return
        jax.profiler.stop_trace()
        profile_done = True
        logger.info("profiler trace written to %s", cfg.profile_dir)

    # The guard stays installed through the preemption save and the final
    # checkpoint drain below: a FIRST signal arriving mid-drain is absorbed
    # (the run is already finishing), and only a SECOND signal falls through
    # to the previous handler — the escape hatch if the drain itself wedges.
    guard = PreemptionGuard()
    # Deterministic chaos, armed only via the MPT_FAULT_* env gates
    # (utils/env.py FAULT_GATES; driven by tools/inject_faults.py).
    faults = elastic.FaultInjector(metrics=metrics)
    if faults.active:
        logger.warning(
            "fault injection armed: kill_at_step=%d delay_step_ms=%d "
            "dcn_delay_ms=%d nonfinite_at_step=%d preempt_at_step=%d "
            "(MPT_FAULT_* gates)",
            faults.kill_at_step, faults.delay_ms, faults.dcn_delay_ms,
            faults.nonfinite_at_step, faults.preempt_at_step,
        )
    if faults.nonfinite_at_step and (
        cfg.device_cache or loader.image_dtype == np.dtype(np.uint8)
    ):
        logger.warning(
            "MPT_FAULT_NONFINITE_AT_STEP has no effect on this run: the "
            "gate NaN-poisons streaming float batches, and this run feeds "
            "%s", "device-cache indices" if cfg.device_cache else "uint8 pixels",
        )
    if faults.dcn_delay_ms and not _hier:
        logger.warning(
            "MPT_FAULT_DCN_DELAY_MS has no effect on this run: a flat mesh "
            "has no cross-pod phase to slow down (set --mesh-pods > 1)"
        )
    # The watchdog unifies every stop signal behind one poll: the guard's
    # SIGTERM flag, the MPT_PREEMPT_FILE sentinel, repeated health signals
    # (straggler beats / non-finite grad norms), and the injected-preempt
    # gate — each firing writes a kind="fault" record and stops the run at
    # the same safe boundary a SIGTERM would (train/elastic.py).
    watchdog = elastic.PreemptionWatchdog(
        guard,
        preempt_file=cfg.preempt_file,
        straggler_beats=cfg.preempt_straggler_beats,
        nonfinite_steps=cfg.preempt_nonfinite_steps,
        heartbeat=heartbeat, health=health, metrics=metrics, logger=logger,
        injector=faults,
    )
    # --- bad-step-policy state (ISSUE 10) ---------------------------------
    # skip: the step discards on device; the host counts the consecutive
    # streak (every host reads the same psum'd verdict, so the abort below
    # is agreed without a collective). rollback: the governor watches the
    # same host-read values and the trainer restores in-process.
    if cfg.bad_step_policy != "abort":
        logger.info(
            "bad-step policy '%s': the NaN sentinel's hard abort is "
            "replaced by the policy (per-step host sync enabled to observe "
            "loss/grad norm)", cfg.bad_step_policy,
        )
    skip_streak = 0
    steps_skipped_total = 0
    if registry is not None and bad_step_skip:
        registry.counter("train/steps_skipped")  # registered up front
    rollback_policy = (
        elastic.RollbackPolicy(
            nonfinite_steps=cfg.rollback_nonfinite_steps,
            loss_drift=cfg.rollback_loss_drift,
            drift_warmup=cfg.rollback_drift_warmup,
        )
        if cfg.bad_step_policy == "rollback"
        else None
    )
    rollbacks_done = 0
    lr_scale = 1.0
    last_saved_epoch = -1
    stopped_mid_epoch = False
    # Recomputed the way build_training computes schedule lengths, for the
    # rollback LR-backoff optimizer rebuild.
    total_steps = (
        global_step_count(len(train_manifest), host_batch, cfg.drop_remainder)
        * cfg.num_epochs
    )

    def _rollback_restore(at_epoch: int, at_step: int, reason: str):
        """--bad-step-policy rollback, the restore half: drain the async
        writer, restore the newest loadable checkpoint IN-PROCESS (the
        same elastic.restore_latest + placement dataflow as a process
        restart — minus the process death), optionally back off the LR,
        and return ``(next_epoch, next_start_step)`` from the restored
        cursor. Deterministic across hosts: the trigger reads globally-
        reduced values, so every process calls this at the same step."""
        nonlocal rollbacks_done, lr_scale, state, compiled_step, flops_per_step
        nonlocal last_saved_epoch, last_completed_epoch
        checkpointer.wait()
        if rollbacks_done >= cfg.max_rollbacks:
            metrics.write(
                {
                    "kind": "anomaly", "reason": "rollback_limit",
                    "epoch": at_epoch, "step": at_step,
                    "detail": f"{rollbacks_done} rollbacks hit "
                              f"max_rollbacks={cfg.max_rollbacks}",
                }
            )
            raise elastic.RollbackLimitError(
                f"bad-step rollback requested ({reason} at epoch {at_epoch} "
                f"step {at_step}) but {rollbacks_done} rollback(s) already "
                f"hit --max-rollbacks={cfg.max_rollbacks}; aborting — see "
                "the kind='rollback' trail in the metrics stream"
            )
        rollbacks_done += 1
        # Restore template with the UNSHARDED optimizer layout: a ZeRO
        # run's live [P, chunk] opt-state does not match the on-disk
        # gathered payload the checkpoint loader deserializes against.
        tmpl = state
        if opt_template is not None:
            tmpl = state.replace(
                opt_state=jax.tree_util.tree_map(
                    lambda s: np.zeros(s.shape, s.dtype), opt_template
                )
            )
        res = elastic.restore_latest(
            cfg.checkpoint_dir, tmpl, mesh, metrics=metrics, logger=logger,
            zero_shards_to=zero_shards_to,
        )
        if res is None:
            raise elastic.RollbackLimitError(
                f"bad-step rollback requested ({reason} at epoch {at_epoch} "
                f"step {at_step}) but no checkpoint exists in "
                f"{cfg.checkpoint_dir} to restore"
            )
        restored, ckpt_epoch, _ckpt_loss, info = res
        tx_changed = False
        if cfg.rollback_lr_backoff != 1.0:
            lr_scale *= cfg.rollback_lr_backoff
            restored = restored.replace(
                tx=make_optimizer(
                    cfg.learning_rate * lr_scale,
                    bundle.trainable_mask,
                    optimizer=cfg.optimizer,
                    lr_schedule=cfg.lr_schedule,
                    warmup_steps=cfg.warmup_steps,
                    total_steps=total_steps,
                    weight_decay=cfg.weight_decay,
                )
            )
            tx_changed = True
        # Re-place onto the mesh — the resume path's dataflow, including
        # the ZeRO detach (never device_put the full unsharded moments).
        raw_opt = restored.opt_state
        if defer_zero_opt:
            restored = restored.replace(opt_state=())
        placed = elastic.with_retries(
            lambda: elastic.checked_place(
                restored, mesh, zero_optimizer=cfg.zero_optimizer, fsdp=cfg.fsdp
            ),
            what="rollback state placement (device_put)",
            retries=cfg.resume_retries, backoff_s=cfg.resume_backoff_s,
            logger=logger,
        )
        if defer_zero_opt:
            placed = placed.replace(opt_state=zero_shard_opt_state(raw_opt, mesh))
        state = placed
        if tx_changed:
            # The LR lives inside the compiled step program: rebuild it
            # (one compile per backed-off rollback, documented cost).
            compiled_step, flops_per_step = build_compiled(state)
        rollback_policy.after_rollback()
        next_epoch = ckpt_epoch + 1
        # Epoch bookkeeping rewinds WITH the state: a later preemption save
        # must file under what the RESTORED state has completed, not what
        # the abandoned timeline had.
        last_completed_epoch = ckpt_epoch
        rb_cursor = (info.get("manifest") or {}).get("data_cursor")
        next_step, rb_why = validate_cursor(
            rb_cursor, cfg=cfg, fingerprint=fingerprint, n_steps=n_steps,
            start_epoch=next_epoch,
        )
        if rb_why is not None and (
            rb_cursor is not None or os.path.exists(info["path"] + ".dirty")
        ):
            # Same typed fallback contract as the resume path: ANY cursor
            # mismatch is recorded, never silently misaligned.
            metrics.write(
                {
                    "kind": "anomaly", "reason": "cursor_mismatch",
                    "epoch": next_epoch, "detail": rb_why,
                }
            )
            logger.warning(
                "rollback cursor unavailable (%s) — replaying epoch %d "
                "from step 0", rb_why, next_epoch,
            )
        metrics.write(
            {
                "kind": "rollback", "epoch": at_epoch, "step": at_step,
                "reason": reason, "restored_epoch": ckpt_epoch,
                "rollbacks": rollbacks_done, "lr_scale": round(lr_scale, 6),
                "path": info["path"],
            }
        )
        last_saved_epoch = ckpt_epoch
        logger.warning(
            "bad-step rollback #%d/%d (%s at epoch %d step %d): restored "
            "%s in-process, continuing at epoch %d step %d%s",
            rollbacks_done, cfg.max_rollbacks, reason, at_epoch, at_step,
            info["path"], next_epoch, next_step,
            f", LR scaled to {lr_scale:g}x" if tx_changed else "",
        )
        return next_epoch, next_step
    # A resumed run must not demote a better historical best (best.json
    # survives restarts; missing marker → any first accuracy wins). Only
    # process 0 reads the marker: on multi-host WITHOUT a shared checkpoint
    # dir the other processes would see no file and start from -inf, and a
    # diverged improvement decision gates a collective (checkpointer.save)
    # — a hang. Process 0's value is broadcast instead.
    best_accuracy = float("-inf")
    if cfg.track_best:
        _marker = (
            ckpt.best_marker(cfg.checkpoint_dir) if jax.process_index() == 0 else None
        )
        best_accuracy = _p0_scalar(
            _marker["accuracy"] if _marker else float("-inf"), mesh
        )
    # Epoch loop as an explicit cursor (epoch, next_start_step) rather than
    # a range: exact-step resume starts the first epoch mid-way, and a
    # bad-step rollback jumps BACKWARD to the restored checkpoint's cursor.
    epoch = start_epoch
    next_start_step = start_step
    last_completed_epoch = start_epoch - 1
    interrupted = None  # (epoch, next_step, steps_run_this_session) on mid-epoch stop
    with guard:
      try:
        while epoch < cfg.num_epochs:
            # The epoch boundary, named piece by piece (epoch/control →
            # epoch/prepare → step → epoch/wait → epoch/account →
            # epoch/record), so that a device trace's idle time between two
            # epochs falls under the host work that caused it.
            control = tracer.begin("epoch/control")
            if _stop_agreed(watchdog.should_stop(epoch=epoch), mesh):
                tracer.end(control, args={"epoch": epoch, "stop": True})
                summary.preempted = True
                logger.info(
                    "preemption signal: stopping before epoch %d "
                    "(progress saved; auto-resume continues from the latest "
                    "checkpoint)", epoch,
                )
                break
            start_step_this, next_start_step = next_start_step, 0
            t0 = time.perf_counter()  # ≙ MPI.Wtime() (main.py:145)
            health.start_epoch()  # re-arm the recompile counter per epoch
            heartbeat.start_epoch()  # beats never span epoch boundaries
            losses, counts = [], []
            extras: dict[str, list] = {}  # what the steps counted beside STEP_METRICS
            loss_v = count_v = None  # [steps] device arrays, set below
            rollback_trigger = None  # (reason, step) breaking the step loop
            tracer.end(control, args={"epoch": epoch})
            scan_inputs = None  # (idx [steps, B], valid [steps, B]) of a scanned epoch
            with tracer.span("epoch/prepare", args={"epoch": epoch}):
                if cfg.device_cache and cfg.scan_epoch:
                    # One dispatch for the whole epoch: stack the per-step
                    # index batches and let the compiled lax.scan run every
                    # step back-to-back on device. (start_step_this is always
                    # 0 here: validate_cursor replays rather than reshaping
                    # the compiled scan.)
                    idx_steps = list(
                        cached_index_batches(cfg, n_cache, cache_batch, epoch, n_steps)
                    )
                    if idx_steps:  # zero-step epochs (tiny shard + drop_remainder) no-op
                        scan_inputs = (
                            np.stack([i for i, _ in idx_steps]),
                            np.stack([v for _, v in idx_steps]),
                        )
                    step_args = ()
                elif cfg.device_cache:
                    # Same (seed, epoch) shuffle discipline as DataLoader.epoch, so
                    # cached and streaming runs see identical batch compositions.
                    step_args = (
                        (dataset, labels_all, idx, valid)
                        for idx, valid in cached_index_batches(
                            cfg, n_cache, cache_batch, epoch, n_steps,
                            start_step=start_step_this,
                        )
                    )
                else:
                    # Tail batches (drop_remainder=False) are padded to the static
                    # shape with masked rows, so training keeps every image without
                    # triggering an XLA recompile; device_prefetch keeps the H2D
                    # copies a couple of steps ahead of compute. (Generators:
                    # the loader's producer starts at the first ``ingest``.)
                    batches = synchronized_batches(
                        loader, epoch, n_steps, start_step=start_step_this
                    )
                    if faults.nonfinite_at_step:
                        batches = faults.poison_batches(batches, epoch)
                    step_args = (
                        (dev_batch,)
                        for dev_batch in device_prefetch(
                            batches, mesh, host_batch, cfg.prefetch_device_batches,
                            epoch=epoch, start_step=start_step_this,
                        )
                    )
            if scan_inputs is not None:
                # metrics come back as [n_steps] arrays — used as-is, never
                # split into per-step scalars.
                idx_all, valid_all = scan_inputs
                with tracer.span("step", args={"epoch": epoch, "mode": "scan"}):
                    state, m = compiled_step(state, dataset, labels_all, idx_all, valid_all)
                    if telemetry_sync:
                        jax.block_until_ready(m["loss"])
                _profile_start(epoch, m["loss"])
                loss_v, count_v = m["loss"], m["count"]
                extras = {k: [v] for k, v in m.items() if k not in STEP_METRICS}
                skipped_before_epoch = steps_skipped_total
                if bad_step_skip and "skipped" in m:
                    # Mask skipped steps out of the epoch accounting (a
                    # discarded update contributes no samples, and its
                    # observed NaN loss must not poison the mean), and
                    # enforce the consecutive-skip budget post-hoc.
                    skip_v = np.asarray(m["skipped"], np.int64)
                    steps_skipped_total += int(skip_v.sum())
                    if registry is not None and skip_v.sum():
                        registry.counter("train/steps_skipped").inc(
                            int(skip_v.sum())
                        )
                    keep = jnp.asarray(1 - skip_v)
                    loss_v = jnp.where(keep == 1, loss_v, 0.0)
                    count_v = count_v * keep.astype(count_v.dtype)
                    # Seed from the previous epoch's trailing streak so
                    # a run of skips spanning the epoch boundary still
                    # trips the limit (the scan has no per-step host
                    # boundary to count at).
                    longest, run = 0, skip_streak
                    for flag in skip_v:
                        run = run + 1 if flag else 0
                        longest = max(longest, run)
                    skip_streak = run  # carries into the next epoch
                    if longest >= cfg.max_skipped_steps:
                        _abort_skip_limit(
                            metrics, epoch, int(longest), cfg.max_skipped_steps
                        )
            stopped_mid_epoch = False
            step_iter = iter(step_args)
            step_i = start_step_this - 1
            while True:
                # Ingest span = time the consumer WAITS for the next batch:
                # decode + H2D dispatch not yet hidden by prefetch — the
                # host-side half of the data-wait vs device-compute split
                # the per-step records carry.
                with tracer.span("ingest") as ingest:
                    args = next(step_iter, None)
                if args is None:
                    break
                data_wait_s = ingest.seconds
                step_i += 1
                # Single-process: stop promptly at a step boundary, dropping
                # the partial epoch (its updates stay in `state` but aren't
                # reported or saved as a completed epoch). Multi-host stops
                # only at the agreed epoch boundary above — a unilateral
                # mid-epoch break would strand the other hosts' collectives.
                if watchdog.should_stop(epoch=epoch, step=step_i) and jax.process_count() == 1:
                    stopped_mid_epoch = True
                    break
                with tracer.span("step", args={"epoch": epoch, "step": step_i}) as stepped:
                    state, m = compiled_step(state, *args)
                    if telemetry_sync:
                        jax.block_until_ready(m["loss"])
                    # Inside the timed region so a faked straggler delay
                    # lands in the step time the heartbeat exchanges.
                    faults.maybe_delay()
                    # Slow-DCN-link fake (ISSUE 15): stretches only
                    # hierarchical steps — a flat mesh has no cross-pod
                    # phase to slow down.
                    faults.maybe_dcn_delay(_hier)
                step_s = stepped.seconds
                _profile_start(epoch, m["loss"])
                was_skipped = None
                if bad_step_skip:
                    # The device already discarded the bad update; count the
                    # streak (the verdict is a psum'd value, so every host
                    # agrees) and mask the step out of the epoch accounting.
                    was_skipped = int(m["skipped"])
                    if was_skipped:
                        skip_streak += 1
                        steps_skipped_total += 1
                        if registry is not None:
                            registry.counter("train/steps_skipped").inc()
                        logger.warning(
                            "bad step skipped (non-finite update) at epoch "
                            "%d step %d — params unchanged, %d consecutive "
                            "(%d total)", epoch, step_i, skip_streak,
                            steps_skipped_total,
                        )
                        losses.append(jnp.zeros_like(m["loss"]))
                        counts.append(jnp.zeros_like(m["count"]))
                    else:
                        skip_streak = 0
                        losses.append(m["loss"])
                        counts.append(m["count"])
                else:
                    losses.append(m["loss"])
                    counts.append(m["count"])
                for k, v in m.items():
                    if k not in STEP_METRICS:
                        extras.setdefault(k, []).append(v)
                health.on_step(
                    epoch, step_i, m, data_wait_s, step_s,
                    skipped=was_skipped,
                    steps_skipped=steps_skipped_total if bad_step_skip else None,
                )
                heartbeat.on_step(epoch, step_i, step_s)
                if bad_step_skip and skip_streak >= cfg.max_skipped_steps:
                    _abort_skip_limit(
                        metrics, epoch, skip_streak, cfg.max_skipped_steps
                    )
                if rollback_policy is not None:
                    reason = rollback_policy.observe(
                        float(m["loss"]),
                        float(m["grad_norm"]) if "grad_norm" in m else None,
                    )
                    if reason is not None:
                        rollback_trigger = (reason, step_i)
                        break
                if registry is not None:
                    h_wait_ms.observe(data_wait_s * 1e3)
                    h_step_ms.observe(step_s * 1e3)
                    g_step_last.set(step_s * 1e3)
                if monitor is not None:
                    monitor.evaluate(epoch=epoch, step=step_i)
                if registry is not None and cfg.metrics_every_steps:
                    steps_since_snapshot += 1
                    if steps_since_snapshot % cfg.metrics_every_steps == 0:
                        metrics.write(
                            registry.snapshot_record(merge=snapshot_merge)
                        )
                faults.after_step(epoch, step_i)
                if cfg.log_every_steps and (step_i + 1) % cfg.log_every_steps == 0:
                    logger.info(
                        "epoch %d step %d loss %.4f", epoch, step_i + 1, float(m["loss"])
                    )
            if rollback_trigger is not None:
                # Bad-step rollback: restore the last good checkpoint
                # in-process and jump the epoch cursor back to it. The
                # partial epoch's bookkeeping (losses/counts) is discarded
                # with the poisoned state.
                reason, at_step = rollback_trigger
                epoch, next_start_step = _rollback_restore(epoch, at_step, reason)
                continue
            if stopped_mid_epoch:
                summary.preempted = True
                interrupted = (epoch, step_i, step_i - start_step_this, start_step_this)
                logger.info(
                    "preemption signal: stopping mid-epoch %d at step "
                    "boundary %d (partial-epoch state — saved dirty with an "
                    "exact-step data cursor; resume continues at step %d "
                    "when the cursor validates, replaying zero optimizer "
                    "steps)", epoch, step_i, step_i,
                )
                break
            # Device sync so the timer measures compute, not dispatch.
            with tracer.span("epoch/wait", args={"epoch": epoch}):
                jax.block_until_ready(state.params)
            _profile_stop(epoch)
            dt = time.perf_counter() - t0
            account_args = {"epoch": epoch}
            with tracer.span("epoch/account", args=account_args):
                # From the step metrics on the device to host floats: three
                # tiny device programs and their read-back.
                if losses:  # per-step paths collected python lists
                    loss_v = jnp.stack(losses)
                    count_v = jnp.stack(counts)
                steps_run = int(loss_v.shape[0]) if loss_v is not None else 0
                account_args["steps"] = steps_run
                if steps_run:
                    # Per-sample accounting: weight each step's mean loss by its
                    # global valid-row count, so padded tail steps aren't over-weighted
                    # (matches the reference's per-sample loss bookkeeping) and
                    # throughput never counts padding rows. One device sync per epoch.
                    count_f = count_v.astype(jnp.float32)
                    n_valid = float(jnp.sum(count_f))
                    epoch_loss = (
                        float(jnp.sum(loss_v * count_f) / n_valid) if n_valid else float("nan")
                    )
                else:
                    n_valid = 0.0
                    epoch_loss = float("nan")
                # What the steps counted beside the loss (a token model's
                # tokens, the expert layers' routed pairs): the epoch's sum,
                # or its largest where the name ends in ``_max``.
                epoch_extras = {
                    k: int(getattr(np, "max" if k.endswith("_max") else "sum")(
                        np.concatenate([np.ravel(v) for v in jax.device_get(vals)])
                    ))
                    for k, vals in extras.items()
                }
            with tracer.span("epoch/record", args={"epoch": epoch}):
                if scan_inputs is not None:
                    # Per-step records post-hoc from the [n_steps] arrays
                    # (host timing is null — the scan never returns to the
                    # host between steps); sentinel checks every step.
                    # Writing them is part of what a scanned epoch costs:
                    # their time joins ``dt`` (the record's ``time_s``).
                    t_steps = time.perf_counter()
                    health.on_scan_epoch(
                        epoch, m, steps_skipped_base=skipped_before_epoch
                    )
                    if cfg.log_every_steps:
                        for logged_i in range(
                            cfg.log_every_steps - 1, steps_run, cfg.log_every_steps
                        ):
                            logger.info(
                                "epoch %d step %d loss %.4f",
                                epoch, logged_i + 1, float(loss_v[logged_i]),
                            )
                    dt += time.perf_counter() - t_steps
                total_images += int(n_valid)
                ips = n_valid / dt if dt > 0 else 0.0
                # cost_analysis() FLOPs are PER-DEVICE under SPMD partitioning.
                per_chip_tflops = flops_per_step * steps_run / dt / 1e12 if dt > 0 else 0.0
                tflops = per_chip_tflops * jax.device_count()
                # mfu None (omitted) when either peak or FLOPs are unknown — a
                # confident "0.0%" would be indistinguishable from a stalled chip.
                mfu = 100.0 * per_chip_tflops / peak if (peak and flops_per_step > 0) else None
                # ≙ reference epoch log line (main.py:158-160), plus throughput/MFU
                logger.info(
                    "Epoch: %d, Loss: %.6f, Time: %.2f s, %.1f img/s%s",
                    epoch, epoch_loss, dt, ips,
                    f", MFU {mfu:.1f}%" if mfu is not None else "",
                )
                if "tokens" in epoch_extras:
                    epoch_extras["tokens_per_sec"] = epoch_extras["tokens"] / dt if dt > 0 else 0.0
                metrics.write(
                    {"kind": "epoch", "epoch": epoch, "loss": epoch_loss, "time_s": dt,
                     "images_per_sec": ips, "tflops": tflops, "mfu_pct": mfu,
                     **epoch_extras}
                )
                if registry is not None:
                    # The MFU-estimate / throughput gauges a fleet controller
                    # (ROADMAP item 1) reads live instead of tailing the stream.
                    # No monitor.evaluate here: rules are defined in per-step
                    # evaluation units (for=/warmup/rate deltas), and a second
                    # pass over the same last-step state would double-count a
                    # single breach; the next epoch's first step evaluates
                    # these gauges instead.
                    registry.gauge("train/images_per_sec").set(ips)
                    if mfu is not None:
                        registry.gauge("train/mfu_pct").set(mfu)
                if steps_run and n_valid:
                    # Free epoch-granularity sentinel (the loss is already a
                    # host float); zero-valid-row epochs are legitimately NaN.
                    health.check_epoch(epoch, epoch_loss)
            summary.epoch_times.append(dt)
            summary.epoch_losses.append(epoch_loss)
            summary.epochs_run += 1

            if cfg.checkpoint_every_epochs and (epoch + 1) % cfg.checkpoint_every_epochs == 0:
                # Async: an on-device snapshot (~ms) releases the epoch loop
                # immediately; device_get + write happen on a background thread
                # (a synchronous save stalls the epoch loop for the whole
                # D2H + write). ≙ rank-0 save (main.py:162-171), without stopping the
                # world. The topology sidecar carries the exact-step data
                # cursor: a clean epoch-E save resumes at (E+1, step 0).
                ckpt_t0 = time.perf_counter()
                with tracer.span("checkpoint", args={"epoch": epoch}):
                    path = checkpointer.save(
                        cfg.checkpoint_dir, epoch=epoch, state=_saveable(state),
                        loss=epoch_loss,
                        keep=cfg.keep_checkpoints,
                        moments_bf16=cfg.ckpt_bf16_moments,
                        manifest=dict(
                            topology,
                            data_cursor=data_cursor(
                                cfg, fingerprint, n_steps, epoch + 1, 0
                            ),
                        ),
                    )
                last_saved_epoch = epoch
                if path:
                    summary.checkpoint_path = path
                    logger.info(
                        "checkpoint dispatched: %s (%.2f s stall; ≙ main.py:162-171)",
                        path, time.perf_counter() - ckpt_t0,
                    )

            if cfg.validate:
                _val_span = tracer.begin("validate")
                try:
                    # Reference quirk preserved behind a flag: validation runs over the
                    # TRAIN manifest (main.py:104-112; SURVEY §3); val_on_train=False
                    # gives the honest test-split validation.
                    val_manifest = train_manifest if cfg.val_on_train else test_manifest
                    if cfg.device_cache and cfg.val_on_train:
                        # The cached train set IS the val set (main.py:104-112
                        # semantics): validate straight out of HBM.
                        acc, vloss = evaluate_cached(cfg, state, mesh, dataset, labels_all)
                    else:
                        if val_loader is None:
                            val_loader = make_eval_loader(
                                cfg, val_manifest, host_cache=cfg.host_cache
                            )
                        if (
                            cfg.host_cache
                            and cfg.val_on_train
                            and not val_loader._cache_complete
                        ):
                            # Same shard, same decode params: share the train
                            # loader's cache instead of decoding a second copy.
                            # Join the train loader's background backfill first —
                            # it finishes in bounded time, and adopting beats
                            # starting a duplicate full-shard decode.
                            loader.wait_cache_complete()
                            val_loader.adopt_cache(loader)
                        acc, vloss = evaluate_manifest(
                            cfg, state, mesh, val_manifest, loader=val_loader
                        )
                finally:
                    # finally: a crashed validation must still appear in the
                    # flushed trace as the span the run died in.
                    tracer.end(_val_span, args={"epoch": epoch})
                summary.val_accuracy = acc
                logger.info("Accuracy of the network: %.4f (val_on_train=%s)", acc, cfg.val_on_train)
                metrics.write({"kind": "val", "epoch": epoch, "accuracy": acc, "loss": vloss})

                if cfg.track_best and acc > best_accuracy:
                    # acc is globally reduced, so every process agrees on the
                    # improvement; any save below is a global snapshot every
                    # process must run (only process 0 writes files/markers).
                    # The marker is published strictly AFTER the checkpoint
                    # file is durable — a crash mid-write must never leave
                    # best.json naming a file that doesn't exist.
                    best_accuracy = acc
                    summary.best_accuracy = acc

                    def _mark_best(ckpt_path, *, _epoch=epoch, _acc=acc):
                        ckpt.write_best_marker(
                            cfg.checkpoint_dir, epoch=_epoch, accuracy=_acc,
                            ckpt_path=ckpt_path,
                        )

                    if last_saved_epoch == epoch:
                        # This epoch's periodic save is already in flight:
                        # join it (bounded — validation usually outlasts the
                        # write anyway), then mark. `path` is that save's
                        # return (this epoch's file on process 0).
                        checkpointer.wait()
                        _mark_best(path)
                    else:
                        best_path = checkpointer.save(
                            cfg.checkpoint_dir, epoch=epoch, state=_saveable(state),
                            loss=epoch_loss, keep=cfg.keep_checkpoints,
                            on_durable=_mark_best,
                            moments_bf16=cfg.ckpt_bf16_moments,
                            manifest=dict(
                                topology,
                                data_cursor=data_cursor(
                                    cfg, fingerprint, n_steps, epoch + 1, 0
                                ),
                            ),
                        )
                        last_saved_epoch = epoch
                        if best_path:
                            summary.checkpoint_path = best_path
                    logger.info("new best: val acc %.4f at epoch %d", acc, epoch)

            last_completed_epoch = epoch
            epoch += 1

      except BaseException:
        # Drain the in-flight write on the failure path too, but never let a
        # secondary writer error replace the primary exception the user
        # needs to see. (The trace flush on failure lives in train()'s
        # outer handler, which also covers build/compile-time crashes.)
        try:
            checkpointer.wait()
        except BaseException as werr:
            logger.warning("background checkpoint write also failed: %s", werr)
        raise
      if summary.preempted and cfg.checkpoint_every_epochs:
        # Preserve whatever the preemption would otherwise lose. Two cases:
        #
        # - Stopped MID-epoch with steps run this session: save the state
        #   (which carries the partial epoch's updates) DIRTY under the
        #   last completed epoch, with the exact-step data cursor in the
        #   topology sidecar — resume continues at step N+1, replaying
        #   ZERO optimizer steps (ISSUE 10). A run that stopped before
        #   running any new step saves nothing new (the on-disk checkpoint
        #   already describes this state); mid-epoch-0 stops with no
        #   completed epoch still have no epoch to file under, so the
        #   partial steps are dropped exactly as before.
        # - Stopped at an epoch boundary: save completed-but-unsaved
        #   epochs (checkpoint_every_epochs > 1 leaves up to k-1 unsaved).
        completed = last_completed_epoch
        # Never rewrite the best-pinned checkpoint with partial-epoch state:
        # best.json claims that file holds the accuracy it measured, and the
        # dirty save below files under `completed` — the same name as that
        # epoch's clean save. Integrity of the pinned file outranks keeping
        # the partial steps (they are dropped, exactly the old behavior).
        _best = ckpt.best_marker(cfg.checkpoint_dir) if cfg.track_best else None
        _best_is_target = bool(
            _best
            and completed >= 0
            and _best.get("checkpoint")
            == os.path.basename(ckpt._ckpt_path(cfg.checkpoint_dir, completed))
        )
        if (
            interrupted is not None and interrupted[2] > 0 and completed >= 0
            and not _best_is_target
        ):
            int_epoch, int_step, _steps, _start = interrupted
            path = checkpointer.save(
                cfg.checkpoint_dir, epoch=completed, state=_saveable(state),
                loss=epoch_loss,
                keep=cfg.keep_checkpoints, dirty=True,
                moments_bf16=cfg.ckpt_bf16_moments,
                manifest=dict(
                    topology,
                    data_cursor=data_cursor(
                        cfg, fingerprint, n_steps, int_epoch, int_step
                    ),
                ),
            )
            last_saved_epoch = completed
            if path:
                summary.checkpoint_path = path
                logger.info(
                    "preemption checkpoint dispatched: %s (dirty; cursor "
                    "epoch %d step %d)", path, int_epoch, int_step,
                )
        elif (
            completed >= start_epoch
            and completed != last_saved_epoch
            # A stop with ZERO new steps in the interrupted epoch is a clean
            # boundary state ONLY if the epoch wasn't entered mid-way (a
            # resumed-then-immediately-stopped run's state is the on-disk
            # dirty checkpoint, already saved).
            and (interrupted is None or interrupted[3] == 0)
        ):
            path = checkpointer.save(
                cfg.checkpoint_dir, epoch=completed, state=_saveable(state),
                loss=epoch_loss,
                keep=cfg.keep_checkpoints,
                moments_bf16=cfg.ckpt_bf16_moments,
                manifest=dict(
                    topology,
                    data_cursor=data_cursor(
                        cfg, fingerprint, n_steps, completed + 1, 0
                    ),
                ),
            )
            if path:
                summary.checkpoint_path = path
                logger.info("preemption checkpoint dispatched: %s", path)

      # Clean path: the last dispatched write must land before callers read
      # the file (resume, evaluate), and a writer error must fail the run
      # loudly. Still under the guard: see the note at `with guard:` above.
      checkpointer.wait()

    _profile_stop()  # the run ended inside the traced window

    wall = time.perf_counter() - train_t0
    summary.final_loss = epoch_loss
    summary.images_per_sec = total_images / wall if wall > 0 else 0.0
    trace_out = tracer.close()
    if trace_out:
        logger.info("host trace spans written to %s (chrome://tracing)", trace_out)
    if registry is not None:
        # Final snapshot so even a run below the step cadence leaves one
        # kind="metrics" record (all processes reach here together — the
        # epoch loop breaks by agreement — so the merge collective is safe).
        metrics.write(registry.snapshot_record(merge=snapshot_merge))
    metrics.close()
    if flight is not None:
        flight.close()
    return summary


def main(argv=None) -> TrainSummary:
    from mpi_pytorch_tpu.config import parse_config

    cfg = parse_config(argv)
    return train(cfg)


if __name__ == "__main__":
    main()
