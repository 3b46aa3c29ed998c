"""Inference/evaluation driver — the TPU-native ``evaluation_pipeline.py``.

The reference runs inference as a 4-stage MPI pipeline (``evaluation_pipeline
.py:162-199``): rank 0 reads images and streams them to rank 1 (resize), then
rank 2 (normalize), then a randomly-assigned predictor rank ≥3 runs a
single-image forward (``:149-158``), and a final ``comm.reduce`` sums
per-predictor accuracies (``:196``).

Here the same four capabilities collapse into a batched dataflow (the
BASELINE.json north star):

| reference stage (rank)            | here                                    |
|-----------------------------------|-----------------------------------------|
| read_images (rank 0, ``:53-71``)  | DataLoader worker threads (PIL decode)  |
| resize_images (rank 1, ``:74-96``)| same workers — decode+resize fused      |
| preprocess_image (rank 2,``:99-129``)| same workers — normalize fused       |
| predict (ranks ≥3, ``:132-159``)  | one jitted batched forward over all chips|
| reduce(acc, SUM) (``:196``)       | on-device sum via the sharded eval step |

The stage *overlap* the MPI pipeline bought with dedicated ranks is provided
by the loader's thread pool + prefetch queue; the random image→predictor
routing (``:178``) is just batch sharding over the ``data`` mesh axis; the
per-image ``model(image[None])`` forward becomes a full-batch MXU matmul.
"""

from __future__ import annotations

import functools
import os
import time
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import optax

from mpi_pytorch_tpu import checkpoint as ckpt
from mpi_pytorch_tpu.config import Config, parse_config
from mpi_pytorch_tpu.data import load_manifests
from mpi_pytorch_tpu.models import create_model_bundle
from mpi_pytorch_tpu.obs import Tracer
from mpi_pytorch_tpu.parallel.mesh import create_mesh, flat_mesh
from mpi_pytorch_tpu.train.state import TrainState
from mpi_pytorch_tpu.train.trainer import evaluate_manifest
from mpi_pytorch_tpu.utils.logging import MetricsWriter, init_logger, run_logger

# One warning per (process, reason): --fused-head-eval silently degrading to
# the plain step was an advisor r5 finding — the user could not tell the
# flag did nothing. Kept module-level so repeated evaluate() calls in one
# process (tests, notebooks) don't spam.
_fused_head_warned: set[str] = set()


def _warn_fused_head_fallback(reason: str) -> None:
    if reason in _fused_head_warned:
        return
    _fused_head_warned.add(reason)
    run_logger().warning(
        "--fused-head-eval requested but falling back to the plain XLA "
        "predict step: %s", reason,
    )


@dataclass
class EvalSummary:
    accuracy: float
    mean_loss: float
    num_images: int
    wall_s: float
    images_per_sec: float


def build_inference(cfg: Config, mesh=None, manifests=None):
    """Inference-only construction: model + params, no optimizer moments, no
    train-split loader — the predictor-rank setup (``evaluation_pipeline.py:
    132-144``) without the training baggage ``build_training`` carries.
    ``manifests``: pre-loaded (train, test) pair, so callers that need both
    splits (the predictions pass's label map) parse the CSVs only once."""
    mesh = mesh or create_mesh(cfg.mesh)
    _, test_manifest = manifests or load_manifests(cfg)
    compute_dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[cfg.compute_dtype]
    bundle, variables = create_model_bundle(
        cfg.model_name,
        cfg.num_classes,
        use_pretrained=cfg.use_pretrained,
        rng=jax.random.PRNGKey(cfg.seed),
        image_size=cfg.image_size[0],
        dtype=compute_dtype,
        param_dtype=jnp.float32,
        pretrained_dir=cfg.pretrained_dir,
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
        # Multi-chip); a model with neither ignores the mesh.
        dp_mesh=mesh,
    )
    state = TrainState.create(
        apply_fn=bundle.model.apply,
        variables=variables,
        tx=optax.identity(),
        rng=jax.random.PRNGKey(cfg.seed),
    )
    if cfg.pp_stages > 1:
        # Same seam as build_training: PP is an execution strategy keyed on
        # state.apply_fn, so --pp-stages pipelines inference too (identical
        # params and numerics; the eval batch streams through the stages).
        from mpi_pytorch_tpu.parallel.pp_vit import pp_apply_from_config

        state = state.replace(
            apply_fn=pp_apply_from_config(cfg, bundle.model, mesh)
        )
    return mesh, bundle, state, test_manifest


def evaluate(cfg: Config) -> EvalSummary:
    from mpi_pytorch_tpu.parallel.distributed import maybe_initialize_distributed

    from mpi_pytorch_tpu.config import apply_runtime_flags

    maybe_initialize_distributed()
    apply_runtime_flags(cfg)
    logger = init_logger("MPT_EVAL", cfg.eval_log_file)
    tracer = Tracer(cfg.trace_file)
    writer = MetricsWriter(cfg.metrics_file)
    # finally-close: a failed evaluation (bad checkpoint, OOM) is exactly
    # the run whose trace is needed — the buffered spans and records must
    # reach disk on the failure path too.
    try:
        with tracer.span("build"):
            manifests = load_manifests(cfg)
            mesh, bundle, state, test_manifest = build_inference(cfg, manifests=manifests)

        latest = ckpt.latest_checkpoint(cfg.checkpoint_dir)
        if cfg.use_best:
            # Best-validation checkpoint (train --track-best), not merely the
            # newest — the reference's intended is_best machinery (helpers.py:4-7).
            marker = ckpt.best_marker(cfg.checkpoint_dir)
            if marker is None:
                raise FileNotFoundError(
                    f"use_best=True but no best.json in {cfg.checkpoint_dir} "
                    "(train with --track-best true --validate true)"
                )
            latest = os.path.join(cfg.checkpoint_dir, marker["checkpoint"])
            logger.info(
                "best checkpoint: epoch %d, val acc %.4f", marker["epoch"], marker["accuracy"]
            )
        if latest:
            # ≙ predictor ranks loading the trained checkpoint
            # (evaluation_pipeline.py:142-144); params/batch_stats only.
            with tracer.span("checkpoint_load"):
                state, epoch, loss = ckpt.load_for_eval(latest, state)
            logger.info("loaded checkpoint %s (epoch %d)", latest, epoch)
        else:
            logger.info("no checkpoint in %s — evaluating fresh init", cfg.checkpoint_dir)

        from mpi_pytorch_tpu.train.step import place_state_on_mesh

        state = place_state_on_mesh(state, mesh)

        t0 = time.perf_counter()
        if cfg.predictions_file:
            # One pass produces both the metrics and the submission CSV.
            with tracer.span("eval", args={"pass": "predictions"}):
                acc, mean_loss = evaluate_with_predictions(
                    cfg, state, mesh, manifests[0], test_manifest, logger,
                    writer,
                )
        else:
            if cfg.fused_head_eval:
                # The metrics-only pass runs the shared eval step — the fused
                # head lives in the predictions step. Surface it instead of
                # letting the flag silently do nothing (advisor r5).
                _warn_fused_head_fallback(
                    "metrics-only evaluation uses the shared eval step; the "
                    "fused head applies to the predictions pass "
                    "(add --predictions-file)"
                )
            with tracer.span("eval", args={"pass": "metrics"}):
                acc, mean_loss = evaluate_manifest(cfg, state, mesh, test_manifest)
        wall = time.perf_counter() - t0
        n = len(test_manifest)
        # ≙ rank-0 final accuracy log (evaluation_pipeline.py:198-199)
        logger.info("Accuracy of the network: %.4f (%d images, %.2f s)", acc, n, wall)
        writer.write(
            {"kind": "eval", "accuracy": acc, "loss": mean_loss, "images": n, "time_s": wall}
        )
    finally:
        writer.close()
        trace_out = tracer.close()
        if trace_out:
            logger.info("host trace spans written to %s (chrome://tracing)", trace_out)
    return EvalSummary(
        accuracy=acc,
        mean_loss=mean_loss,
        num_images=n,
        wall_s=wall,
        images_per_sec=n / wall if wall > 0 else 0.0,
    )


def _make_predict_step(
    mesh, compute_dtype, fused_head: bool = False, topk: int = 1,
    int8_head: bool = False,
):
    # Canonicalize to positional args: lru_cache keys keyword and
    # positional calls separately, which would double-compile the step.
    if fused_head and topk > 1:
        raise ValueError(
            "the fused head (head_predict) streams argmax only; top-k needs "
            "the plain predict path (serve forces topk=1 under "
            "--fused-head-eval, with a warning)"
        )
    if int8_head and not fused_head:
        raise ValueError(
            "int8_head selects the fused int8 kernel variant and requires "
            "fused_head=True; the plain int8 path is just the plain predict "
            "step over a quantized state (ops/quantize.quantize_state)"
        )
    return _make_predict_step_impl(
        mesh, compute_dtype, bool(fused_head), int(topk), bool(int8_head)
    )


def _row_sharding(mesh, batch: int):
    """The argmax/top-k pin: ``P(data)`` when the batch divides the data
    axis (the eval paths — required for ``_host_rows`` on multi-host),
    replicated otherwise (the serve buckets smaller than the device count,
    where a forced uneven shard would buy nothing)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from mpi_pytorch_tpu.parallel.mesh import data_axis_names, data_axis_size

    axes = data_axis_names(mesh)  # ("pod", "ici") on a nested mesh
    spec = P(axes) if batch % data_axis_size(mesh) == 0 else P()
    return NamedSharding(mesh, spec)


@functools.lru_cache(maxsize=None)
def _make_predict_step_impl(
    mesh, compute_dtype, fused_head: bool, topk: int, int8_head: bool = False,
):
    """ONE batched forward yielding both the eval metrics and the per-image
    argmax — predictions and accuracy come from the same pass (the
    reference's predictor ranks compute the per-image argmax and discard it,
    ``evaluation_pipeline.py:149-158``).

    The argmax is PINNED to ``P(data)``: on multi-host the global array
    spans non-addressable devices, and the caller reads back exactly its own
    host's rows from the addressable shards — a compiler-chosen layout
    (e.g. replicated) would silently hand every host all rows. (For batches
    that don't divide the data axis — the small serve buckets — the pin
    degrades to replicated, see ``_row_sharding``; the eval paths always
    divide.)

    ``topk`` (plain path only): > 1 returns [B, k] top-k class indices per
    row instead of the [B] argmax — the serving contract (a request wants
    candidates, not just the winner). Column 0 IS the argmax, which the
    parity test pins against ``head_predict``.

    ``fused_head`` (``--fused-head-eval``, TPU): the [B, 64 500] logits
    tensor never reaches HBM — a flax method interceptor captures the
    ``head`` Dense's INPUT features during the same traced forward, and
    ``ops.fused_head_ce.head_predict`` streams the head weights through
    VMEM computing per-example loss + argmax online (measured 2.31 vs
    2.74 ms per 1024-image batch against the XLA head — bench_eval
    --head). On a multi-device data axis the kernel call is shard_map-
    partitioned over the mesh inside ``head_predict`` (each chip streams
    its own row shard), and batches beyond the per-block VMEM envelope
    are row-tiled inside the kernel wrapper — no silent fallback on
    either axis. The metrics are loss-sum/correct/count over the SAME
    quantities ``metrics_from_logits`` computes, so accuracy is identical
    up to the bf16-matmul argmax caveat in ``head_predict``'s docstring."""
    from flax import linen as flax_nn

    from mpi_pytorch_tpu.train.step import (
        eval_logits,
        ingest_images,
        metrics_from_logits,
    )

    if not fused_head:

        @jax.jit
        def predict(state, batch):
            images, labels = batch
            logits = eval_logits(state, images, compute_dtype)
            row_sharding = _row_sharding(mesh, images.shape[0])
            if topk > 1:
                # lax.top_k's indices come back best-first, so [:, 0] is
                # exactly the argmax the k=1 path returns.
                _, idx = jax.lax.top_k(logits, topk)
                preds = idx.astype(jnp.int32)
            else:
                preds = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            preds = jax.lax.with_sharding_constraint(preds, row_sharding)
            return metrics_from_logits(logits, labels), preds

        return predict

    from mpi_pytorch_tpu.ops.fused_head_ce import head_predict

    def _intercepted_forward(state, images):
        """Run the forward with the 'head' Dense intercepted: its INPUT
        features/kernel/bias land in the returned box, its dummy output IS
        the model output (the head is every zoo model's last layer that
        fires this filter) — shared by the bf16 and int8 fused steps."""
        box = {}

        def grab_head_input(next_fn, args, kwargs, context):
            m = context.module
            if m.name == "head" and isinstance(m, flax_nn.Dense):
                box["feats"] = args[0]
                box["w"] = m.variables["params"]["kernel"]
                box["b"] = m.variables["params"].get(
                    "bias", jnp.zeros((m.features,), jnp.float32)
                )
                # The dummy return is discarded below; XLA dead-code-
                # eliminates it.
                return jnp.zeros(args[0].shape[:-1] + (m.features,), jnp.float32)
            return next_fn(*args, **kwargs)

        with flax_nn.intercept_methods(grab_head_input):
            out = state.apply_fn(
                state.variables, ingest_images(images, compute_dtype), train=False
            )
        return out, box

    def _plain_from_logits(out, labels, batch_rows):
        """The no-head-match fallback (conv-classifier models): ``out`` is
        the model's REAL logits — plain metrics + pinned argmax."""
        logits = jax.lax.optimization_barrier(out.astype(jnp.float32))
        preds = jax.lax.with_sharding_constraint(
            jnp.argmax(logits, axis=-1).astype(jnp.int32),
            _row_sharding(mesh, batch_rows),
        )
        return metrics_from_logits(logits, labels), preds

    def _fused_metrics(loss, preds, labels):
        valid = labels >= 0
        return {
            "loss": jnp.sum(loss),  # the kernels zero padding rows
            "correct": jnp.sum((preds == labels) & valid),
            "count": jnp.sum(valid.astype(jnp.int32)),
        }

    if int8_head:
        from mpi_pytorch_tpu.ops.quantize import head_kernel_key, head_predict_int8

        @jax.jit
        def predict_fused_int8(state, batch):
            """The int8 twin of ``predict_fused`` over a quantized state
            (``quantize_state(..., keep_head_int8=True)``): the head Dense
            kernel the interceptor captures is the RAW int8 tensor (the
            dequantizing apply wrapper skips it), and the Pallas int8
            kernel consumes it with the packed tree's per-channel scales
            and the calibrated activation scale."""
            images, labels = batch
            packed = state.params  # {"q", "scale", "act_scale"}
            out, box = _intercepted_forward(state, images)
            hk = head_kernel_key(packed["scale"], packed["q"])  # static
            if "feats" not in box or hk is None:
                # No int8-kept Dense head (conv classifiers): everything
                # was dequantized by the apply wrapper and ``out`` is the
                # real (weight-quantized) logits.
                _warn_fused_head_fallback(
                    "the model has no int8-kept Dense 'head' for the int8 "
                    "kernel to replace; the logits are materialized"
                )
                return _plain_from_logits(out, labels, images.shape[0])
            assert out.shape == box["feats"].shape[:-1] + (box["w"].shape[1],), (
                "intercepted 'head' output shape does not match the model "
                f"output: {out.shape} vs "
                f"{box['feats'].shape[:-1] + (box['w'].shape[1],)}"
            )
            loss, preds = head_predict_int8(
                box["feats"], box["w"], box["b"], labels,
                w_scale=packed["scale"][hk],
                act_scale=packed["act_scale"],
                dp_mesh=mesh,
            )
            preds = jax.lax.with_sharding_constraint(
                preds, _row_sharding(mesh, images.shape[0])
            )
            return _fused_metrics(loss, preds, labels), preds

        return predict_fused_int8

    @jax.jit
    def predict_fused(state, batch):
        images, labels = batch
        out, box = _intercepted_forward(state, images)
        if "feats" not in box:
            # Head never matched (e.g. squeezenet's Conv classifier, which
            # is also not the final op): ``out`` is then the model's REAL
            # logits — take the plain path, and say so.
            _warn_fused_head_fallback(
                "the model has no Dense layer named 'head' for the kernel "
                "to replace; the [B, num_classes] logits are materialized"
            )
            return _plain_from_logits(out, labels, images.shape[0])
        # The interceptor's dummy return must BE the model output — if an
        # architecture ever routes more layers after its 'head' Dense, the
        # captured features would not be the logits' features and the fused
        # metrics would be silently wrong. Shapes are static under jit, so
        # this costs nothing at runtime.
        assert out.shape == box["feats"].shape[:-1] + (box["w"].shape[1],), (
            "intercepted 'head' output shape does not match the model "
            f"output: {out.shape} vs {box['feats'].shape[:-1] + (box['w'].shape[1],)}"
        )
        # head_predict shard_maps itself over the mesh's data axis (each
        # chip streams its own row shard) and row-tiles beyond its
        # per-block VMEM envelope.
        loss, preds = head_predict(
            box["feats"], box["w"], box["b"], labels, dp_mesh=mesh
        )
        preds = jax.lax.with_sharding_constraint(
            preds, _row_sharding(mesh, images.shape[0])
        )
        return _fused_metrics(loss, preds, labels), preds

    return predict_fused


def _host_rows(p, host_batch: int):
    """This host's rows of a ``P(data)``-sharded [B] array, in global row
    order, read from the addressable shards only (``np.asarray`` on the
    global array raises on multi-host). Shards replicated across a model/
    pipe axis carry duplicate row blocks — deduped by start index."""
    import numpy as np

    by_start = {}
    for s in p.addressable_shards:
        start = s.index[0].start or 0
        by_start.setdefault(start, np.asarray(s.data))
    rows = np.concatenate([by_start[k] for k in sorted(by_start)])
    assert rows.shape[0] == host_batch, (rows.shape, host_batch)
    return rows


def evaluate_with_predictions(
    cfg: Config, state, mesh, train_manifest, test_manifest, logger,
    metrics,
) -> tuple[float, float]:
    """One pass over the test manifest: accuracy/loss AND a predictions CSV
    (file_name, predicted_label, predicted_category_id) in manifest order —
    the submission file the Herbarium task actually wants. The filename key
    mirrors ``GetData`` returning ``(tensor, fname)`` for the test split
    (``data_loader.py:36-39``). Returns (accuracy, mean_loss).

    Multi-host: every host walks its manifest shard through the same
    synchronized global steps as ``evaluate_manifest`` (so the sharded
    forward uses every chip of the pod), slices its own rows out of each
    step's global argmax, and the per-host predictions — tiny int32 rows,
    not images — are all-gathered so process 0 writes the single CSV in
    global manifest order. No shared filesystem is required."""
    import numpy as np

    from mpi_pytorch_tpu.parallel.mesh import shard_batch
    from mpi_pytorch_tpu.train.trainer import (
        global_step_count,
        make_eval_loader,
        pad_batch,
        synchronized_batches,
    )

    n_proc, pid = jax.process_count(), jax.process_index()
    host_batch = cfg.batch_size // n_proc
    loader = make_eval_loader(cfg, test_manifest)  # this host's shard
    local_n = len(loader.manifest)
    compute_dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[cfg.compute_dtype]
    from mpi_pytorch_tpu.utils.env import env_flag
    from mpi_pytorch_tpu.utils.hardware import tpu_backend

    # MPT_HEAD_INTERPRET=1 drives the real kernel through the Pallas
    # interpreter on CPU (the driver-level test path), so it passes the gate.
    fused_head = cfg.fused_head_eval and (
        tpu_backend() or env_flag("MPT_HEAD_INTERPRET")
    )
    if cfg.fused_head_eval and not fused_head:
        _warn_fused_head_fallback(
            "backend is not TPU (the Mosaic kernel has no CPU/GPU build); "
            "metrics are identical, but the [B, num_classes] logits are "
            "materialized"
        )
    predict = _make_predict_step(mesh, compute_dtype, fused_head=fused_head)
    compiled = None
    preds: list = []
    loss_sum = correct = count = 0.0
    n_steps = global_step_count(len(test_manifest), host_batch, drop_remainder=False)
    for images, labels in synchronized_batches(loader, 0, n_steps):
        batch = shard_batch(pad_batch(images, labels, host_batch), mesh)
        if compiled is None:
            # Every batch is padded to one static shape: compile it once,
            # ahead of time, and record what the executable carries
            # (kind="compile": Mosaic calls, input placement).
            from mpi_pytorch_tpu.utils.hardware import compile_record

            t_compile = time.perf_counter()
            compiled = predict.lower(state, batch).compile()
            metrics.write(
                compile_record(
                    "predict", compiled, time.perf_counter() - t_compile
                )
            )
        m, p = compiled(state, batch)
        # Global batch rows [pid*hb, (pid+1)*hb) are THIS host's images
        # (shard_batch assembles the global array host-major), and the
        # P(data)-pinned argmax keeps them on this host's devices.
        preds.append(_host_rows(p, host_batch))
        loss_sum += float(m["loss"])
        correct += int(m["correct"])
        count += int(m["count"])
    local_preds = np.concatenate(preds)[:local_n]  # drop tail/filler padding

    if n_proc > 1:
        from jax.experimental import multihost_utils

        # array_split shard sizes are deterministic — every host computes the
        # same layout, pads its rows to the max, and the gather is one tiny
        # [P, max] int32 exchange.
        sizes = [
            len(part)
            for part in np.array_split(np.arange(len(test_manifest)), n_proc)
        ]
        buf = np.full((max(sizes),), -1, np.int32)
        buf[:local_n] = local_preds
        gathered = np.asarray(multihost_utils.process_allgather(buf))
        labels_pred = np.concatenate(
            [gathered[p, : sizes[p]] for p in range(n_proc)]
        )
    else:
        labels_pred = local_preds
    assert len(labels_pred) == len(test_manifest), (
        len(labels_pred), len(test_manifest),
    )

    if pid == 0:
        # Contiguous label -> raw Herbarium category_id, from BOTH splits (the
        # label map was built over both, data/manifest.py build_label_map).
        label_to_cat: dict[int, int] = {}
        for m in (train_manifest, test_manifest):
            label_to_cat.update(zip(m.labels.tolist(), m.category_ids.tolist()))

        tmp = cfg.predictions_file + ".tmp"
        with open(tmp, "w") as f:
            f.write("file_name,predicted_label,predicted_category_id\n")
            for fname, p in zip(test_manifest.filenames, labels_pred.tolist()):
                f.write(f"{fname},{p},{label_to_cat.get(p, -1)}\n")
        os.replace(tmp, cfg.predictions_file)
        logger.info(
            "predictions written: %s (%d rows)", cfg.predictions_file, len(labels_pred)
        )
    acc = correct / count if count else 0.0
    return acc, (loss_sum / count if count else float("nan"))


def quantize_eval_report(cfg: Config) -> dict:
    """``--quantize-eval``: the offline int8-vs-bf16 parity report — the
    reusable oracle the serve-side parity gates lean on (``ops/quantize.
    parity_probe``), run against the checkpoint the server would load.

    A fixed seeded sample (``--quantize-calib`` images, ``--seed``) goes
    through the trained model on both paths — the served contract (fused
    int8 kernel when the ``--fused-head-eval`` gate is active, otherwise
    the plain predict over the weight-quantized state) — and the report
    carries top-1/top-5 agreement plus the max full-model logit drift.
    Written as a ``kind="quant_parity"`` record (schema v7) and returned.
    """
    from mpi_pytorch_tpu.config import apply_runtime_flags
    from mpi_pytorch_tpu.ops import quantize as qz
    from mpi_pytorch_tpu.parallel.distributed import maybe_initialize_distributed
    from mpi_pytorch_tpu.train.step import place_state_on_mesh

    maybe_initialize_distributed()
    apply_runtime_flags(cfg)
    logger = init_logger("MPT_EVAL", cfg.eval_log_file)
    # Serving has the request as data: the report needs no manifest either.
    mesh, _, state, _ = build_inference(cfg, manifests=(None, None))
    latest = ckpt.latest_checkpoint(cfg.checkpoint_dir)
    if cfg.use_best:
        marker = ckpt.best_marker(cfg.checkpoint_dir)
        if marker is None:
            raise FileNotFoundError(
                f"use_best=True but no best.json in {cfg.checkpoint_dir}"
            )
        latest = os.path.join(cfg.checkpoint_dir, marker["checkpoint"])
    if latest:
        state, epoch, _ = ckpt.load_for_eval(latest, state)
        logger.info("quantize-eval: checkpoint %s (epoch %d)", latest, epoch)
    else:
        logger.info(
            "quantize-eval: no checkpoint in %s — probing fresh init",
            cfg.checkpoint_dir,
        )
    state = place_state_on_mesh(state, mesh)
    compute_dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[
        cfg.compute_dtype
    ]
    # The SAME gate and calibration batch the serve executables use
    # (ops/quantize.fused_head_gate / calibration_batch): the oracle
    # measures the contract the server would actually run, by
    # construction rather than by textual coincidence.
    fused = qz.fused_head_gate(cfg)
    images = qz.calibration_batch(cfg)
    act_scale = qz.calibrate_head_act_scale(state, images, compute_dtype)
    q_plain = qz.quantize_state(state, keep_head_int8=False, act_scale=act_scale)
    drift = qz.max_logit_drift(state, q_plain, images, compute_dtype)
    if fused:
        qstate = qz.quantize_state(
            state, keep_head_int8=True, act_scale=act_scale
        )
        topk = 1  # the fused kernels stream argmax only (both precisions)
    else:
        qstate, topk = q_plain, min(cfg.serve_topk, cfg.num_classes)
    probe = qz.parity_probe(
        state, qstate, mesh, compute_dtype, images,
        topk=topk, fused_head=fused,
    )
    report = {
        "kind": "quant_parity",
        "precision": "int8",
        "model": cfg.model_name,
        "max_logit_drift": round(drift, 6),
        **probe,
    }
    logger.info(
        "quantize-eval parity: top1 %.4f, top5 %s, max logit drift %.4g "
        "over %d samples (%s path)",
        report["top1_agree"],
        "-" if report["top5_agree"] is None else f"{report['top5_agree']:.4f}",
        drift, report["samples"], "fused int8" if fused else "plain int8",
    )
    writer = MetricsWriter(cfg.metrics_file)
    writer.write(dict(report))
    writer.close()
    return report


def main(argv=None):
    cfg = parse_config(argv)
    if cfg.quantize_eval:
        return quantize_eval_report(cfg)
    return evaluate(cfg)


if __name__ == "__main__":
    main()
