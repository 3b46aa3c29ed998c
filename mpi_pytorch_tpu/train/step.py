"""The jitted train/eval steps — the heart of the framework.

This single compiled function subsumes reference components #1 (hot loop,
``main.py:146-155``), #2 (the entire ``mpi_tools.py`` gradient-sync stack),
and the predict stage of #7 (SURVEY §2a). Two interchangeable SPMD styles:

- **auto** (default): one ``jit`` over the mesh; batch sharded on ``data``,
  params replicated except the classifier head, which is column-sharded over
  ``model`` (vocab-parallel, for the 64 500-class head). XLA's partitioner
  inserts the gradient all-reduce — the compiler-native equivalent of
  ``mpi_avg_grads`` (``mpi_tools.py:30-37``). BatchNorm sees the global
  batch (sync-BN semantics).

- **spmd** (reference-parity): ``shard_map`` over the ``data`` axis with
  *explicit* collectives from ``parallel/collectives.py`` — per-shard forward
  with **local** BN statistics (exactly the reference's per-rank BN, SURVEY
  §7 'BatchNorm under DP'), then one fused ``pmean`` over grads. This is the
  direct structural descendant of ``mpiexec`` + ``mpi_avg_grads``. Two
  composable levers ride it (ROADMAP item 2): ``zero_opt_state`` shards the
  optimizer state 1/P over the data axis (update-on-slice + params
  allgather, arXiv 2004.13336) and ``grad_bucket_mb`` buckets the gradient
  sync so collectives overlap the remaining backward (arXiv 1810.11112);
  with both on, the buckets become reduce-scatters and grad comms halve.

Both satisfy: N-shard step == 1-device step on the concatenated batch (up to
BN-stats bookkeeping); tests/test_parallel.py asserts it.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from mpi_pytorch_tpu.parallel.compat import shard_map

from mpi_pytorch_tpu.config import IMAGENET_MEAN, IMAGENET_STD
from mpi_pytorch_tpu.ops.losses import accuracy_count, classification_loss, valid_count
from mpi_pytorch_tpu.parallel import collectives
from mpi_pytorch_tpu.parallel.mesh import (
    data_axis_names,
    data_axis_size,
    is_hierarchical,
    named_shardings,
    param_specs,
    pod_shape,
    shard_first_divisible,
    zero_shard_axis,
)
from mpi_pytorch_tpu.train.state import TrainState


def ingest_images(images, compute_dtype):
    """Device-side image ingest, keyed on the TRACED dtype (static under jit,
    so no extra step-factory parameter or cache key is needed):

    - uint8 batches are raw pixels (``input_dtype='uint8'`` — 4x less
      host→device traffic than f32, 2x less than bf16, and a 4x smaller
      device/host cache): the ImageNet normalize runs ON DEVICE in f32 with
      the exact op order of ``pipeline.normalize_image``, where XLA fuses it
      into the first convolution for free;
    - float batches were normalized on the host and just cast;
    - int32 batches are a token model's ids (``[B, S]``) and pass as they
      are: the model's embedding reads them."""
    with jax.named_scope("input"):
        if images.dtype == jnp.int32:
            return images
        if images.dtype == jnp.uint8:
            x = images.astype(jnp.float32) / 255.0
            x = (x - jnp.asarray(IMAGENET_MEAN, jnp.float32)) / jnp.asarray(
                IMAGENET_STD, jnp.float32
            )
            return x.astype(compute_dtype)
        return images.astype(compute_dtype)


def _loss_and_updates(state: TrainState, images, labels, rng, remat: bool = False):
    """Shared core: forward (train mode), loss, logits, new batch_stats,
    gradients, and what the model counted in this apply (``{}`` for a model
    that counts nothing).

    ``remat`` wraps the forward in ``jax.checkpoint``: activations are
    recomputed during the backward pass instead of being saved — the
    canonical HBM-for-FLOPs trade that lets batch sizes (or 299px inception
    inputs) exceed what activation memory would otherwise allow."""

    def loss_fn(params):
        variables = {"params": params}
        # "losses" collects model-internal auxiliary losses (MoE load-balance
        # terms, models/vit.py MoEMlp.sow); empty for every other model.
        # "counters" collects what a layer counts per apply (the expert
        # layers' routed pairs, models/lfm2.py MoE.sow); it joins the step's
        # metrics and takes no part in the loss.
        mutable = ["losses", "counters"]
        if state.batch_stats is not None:
            variables["batch_stats"] = state.batch_stats
            mutable.append("batch_stats")
        # The backward pass needs no scope of its own: JAX names what it
        # derives from these two ``transpose(jvp(forward))`` / ``…(loss)``.
        with jax.named_scope("forward"):
            out, updated = state.apply_fn(
                variables, images, train=True, rngs={"dropout": rng}, mutable=mutable
            )
        new_bs = updated["batch_stats"] if state.batch_stats is not None else None
        with jax.named_scope("loss"):
            aux = sum(
                jnp.sum(v) for v in jax.tree_util.tree_leaves(updated.get("losses", {}))
            )
            loss = classification_loss(out, labels) + aux
        logits = out[0] if isinstance(out, tuple) else out
        return loss, (new_bs, logits, _sum_counters(updated.get("counters", {})))

    if remat:
        loss_fn = jax.checkpoint(loss_fn)
    (loss, (new_bs, logits, counters)), grads = jax.value_and_grad(loss_fn, has_aux=True)(
        state.params
    )
    return loss, logits, new_bs, grads, counters


def _sum_counters(collection) -> dict:
    """One number a name over every layer that sowed it: the sum, or the
    largest where the name ends in ``_max``."""
    out: dict = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(collection):
        name = next(k.key for k in reversed(path) if hasattr(k, "key"))
        if name in out:
            out[name] = jnp.maximum(out[name], leaf) if name.endswith("_max") else out[name] + leaf
        else:
            out[name] = leaf
    return out


def _apply_updates(state: TrainState, grads, new_bs) -> TrainState:
    with jax.named_scope("optimizer"):
        updates, new_opt = state.tx.update(grads, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)
        return state.replace(
            step=state.step + 1,
            params=new_params,
            batch_stats=new_bs if state.batch_stats is not None else None,
            opt_state=new_opt,
            rng=jax.random.fold_in(state.rng, 1),
        )


# The step's own metrics (``_step_metrics``, ``_with_skip_flag``), which the
# trainer folds into an epoch's loss and counts itself. Whatever else a step
# reports — a token batch's ``tokens``, the counters a model sows — joins the
# epoch record under its own name: summed, or maxed where the name ends in
# ``_max`` (obs/schema.py owns which keys a record may carry).
STEP_METRICS = ("loss", "correct", "count", "grad_norm", "skipped")


def _step_metrics(loss, logits, labels, grads, counters=None) -> dict:
    """The step's own numbers, in every compiler-partitioned step flavor.
    ``counters``: what the model counted in this apply (``_loss_and_updates``;
    the expert layers' ``moe_pairs_held`` / ``moe_pairs_absent`` /
    ``moe_load_max`` / ``moe_rows_computed``), under its own names; a token batch (labels ``[B, S]``)
    also reports ``tokens``, its valid positions — ``count`` stays samples.
    grad_norm: the global (all-parameter) L2 norm — the training-health
    signal the obs layer records per step (obs/health.py). A scalar
    reduction XLA fuses into the backward; negligible next to the matmuls,
    and present in every step flavor so telemetry can't depend on which
    mode a run uses."""
    with jax.named_scope("metrics"):
        metrics = {
            "loss": loss,
            "correct": accuracy_count(logits, labels),
            "count": valid_count(labels),
            "grad_norm": optax.global_norm(grads).astype(jnp.float32),
            **(counters or {}),
        }
        if labels.ndim > 1:
            metrics["tokens"] = jnp.sum((labels >= 0).astype(jnp.int32))
        return metrics


def _step_ok(metrics) -> jax.Array:
    """Whether this step's update is SAFE to commit: finite loss AND finite
    global grad norm. Both are globally-reduced quantities (the loss is the
    count-weighted global mean, the norm spans every parameter), so under
    SPMD every shard/host computes the identical verdict — the property
    that lets the skip policy branch without a collective."""
    with jax.named_scope("metrics"):
        return jnp.isfinite(metrics["loss"]) & jnp.isfinite(metrics["grad_norm"])


def _guard_bad_step(ok, new_tree, old_tree):
    """``--bad-step-policy skip``, the device half: select the OLD value of
    every state leaf when ``ok`` is False — the non-finite update is
    discarded and the state (params, moments, BN stats, step counter, rng)
    is bit-identical to pre-step, so training simply retries on the next
    batch. A whole-tree select instead of ``lax.cond`` because it stays
    trivially correct inside shard_map/scan and costs one fused elementwise
    pass only on runs that opted into the policy."""
    with jax.named_scope("optimizer"):
        return jax.tree_util.tree_map(
            lambda n, o: jnp.where(ok, n, o), new_tree, old_tree
        )


def _with_skip_flag(metrics, ok):
    """Stamp the step's verdict into the metrics (``skipped`` ∈ {0, 1}) —
    the host side of the policy (streak counting, telemetry) reads this."""
    with jax.named_scope("metrics"):
        return dict(metrics, skipped=(~ok).astype(jnp.int32))


# ---------------------------------------------------------------------------
# auto mode: compiler-partitioned jit
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def make_train_step(
    compute_dtype=jnp.bfloat16, remat: bool = False, accum_steps: int = 1, mesh=None,
    bad_step_skip: bool = False,
) -> Callable:
    """Auto-sharded train step: ``jit(step)`` with donated state. Sharding
    comes from the input arrays' placements (state placed by
    ``place_state_on_mesh``, batch by ``mesh.shard_batch``).

    ``accum_steps`` > 1 splits the batch into that many microbatches and
    accumulates gradients over a ``lax.scan`` before the single optimizer
    update — same global-batch gradient (each microbatch's mean-grad is
    weighted by its valid-row count), a fraction of the activation memory.
    BatchNorm statistics are updated per microbatch (sequentially), the one
    semantic difference from the unsplit step; requires ``mesh`` so each
    microbatch stays sharded over the data axis through the reshape.

    Memoized so repeated ``train()`` calls in one process (resume, tests)
    reuse the same jitted function and its XLA compilation cache."""

    if accum_steps <= 1:

        @functools.partial(jax.jit, donate_argnums=(0,))
        def train_step(state: TrainState, batch):
            images, labels = batch
            images = ingest_images(images, compute_dtype)
            rng = jax.random.fold_in(state.rng, state.step)
            loss, logits, new_bs, grads, counters = _loss_and_updates(
                state, images, labels, rng, remat=remat
            )
            new_state = _apply_updates(state, grads, new_bs)
            metrics = _step_metrics(loss, logits, labels, grads, counters)
            if bad_step_skip:
                ok = _step_ok(metrics)
                new_state = _guard_bad_step(ok, new_state, state)
                metrics = _with_skip_flag(metrics, ok)
            return new_state, metrics

        return train_step

    if mesh is None:
        raise ValueError("accum_steps > 1 requires the mesh (microbatch sharding)")
    data_axis = mesh.axis_names[0]

    n_data = mesh.shape[data_axis]

    def local_microbatches(x):
        # DEVICE-LOCAL split: each device scans its own k chunks, so no batch
        # data crosses the ICI. A contiguous reshape([k, B/k]) would instead
        # reshard essentially the whole batch every step (device d holds rows
        # [d*B/n, (d+1)*B/n) but contiguous microbatch j needs different
        # rows). Which rows share a microbatch is semantically irrelevant —
        # the final gradient/metrics are count-weighted sums over ALL rows —
        # except for per-microbatch BN stats, the already-documented
        # difference of accumulation.
        b = x.shape[0]
        mpd = b // (n_data * accum_steps)  # rows per device per microbatch
        x = lax.with_sharding_constraint(
            x.reshape(n_data, accum_steps, mpd, *x.shape[1:]),
            NamedSharding(mesh, P(data_axis)),
        )
        x = jnp.swapaxes(x, 0, 1)  # device-local transpose
        x = lax.with_sharding_constraint(x, NamedSharding(mesh, P(None, data_axis)))
        return lax.with_sharding_constraint(
            x.reshape(accum_steps, n_data * mpd, *x.shape[3:]),
            NamedSharding(mesh, P(None, data_axis)),
        )

    @functools.partial(jax.jit, donate_argnums=(0,))
    def accum_train_step(state: TrainState, batch):
        images, labels = batch
        images = ingest_images(images, compute_dtype)
        if images.shape[0] % (n_data * accum_steps):
            raise ValueError(
                f"batch {images.shape[0]} not divisible by data size {n_data} "
                f"x accum_steps {accum_steps}"
            )
        im = local_microbatches(images)
        lb = local_microbatches(labels)
        base_rng = jax.random.fold_in(state.rng, state.step)
        zero_grads = jax.tree_util.tree_map(jnp.zeros_like, state.params)

        def body(carry, xs):
            grad_sum, bs, loss_sum, correct, count, i = carry
            mimg, mlab = xs
            st = state.replace(batch_stats=bs) if bs is not None else state
            loss, logits, new_bs, grads, _ = _loss_and_updates(
                st, mimg, mlab, jax.random.fold_in(base_rng, i), remat=remat
            )
            # Weight each microbatch's mean-grad/mean-loss by its valid-row
            # count so the accumulated step equals the unsplit big-batch step
            # even when padded tail rows land unevenly across microbatches.
            cnt = valid_count(mlab)
            w = cnt.astype(loss.dtype)
            grad_sum = jax.tree_util.tree_map(
                lambda a, g: a + g * w.astype(g.dtype), grad_sum, grads
            )
            return (
                grad_sum,
                new_bs if bs is not None else None,
                loss_sum + loss * w,
                correct + accuracy_count(logits, mlab),
                count + cnt,
                i + 1,
            ), None

        init = (
            zero_grads,
            state.batch_stats,
            jnp.zeros((), jnp.float32),
            jnp.zeros((), jnp.int32),
            jnp.zeros((), jnp.int32),
            jnp.zeros((), jnp.int32),
        )
        (grad_sum, new_bs, loss_sum, correct, count, _), _ = lax.scan(
            body, init, (im, lb)
        )
        denom = jnp.maximum(count.astype(jnp.float32), 1.0)
        grads = jax.tree_util.tree_map(
            lambda g: g / denom.astype(g.dtype), grad_sum
        )
        new_state = _apply_updates(state, grads, new_bs)
        with jax.named_scope("metrics"):
            metrics = {
                "loss": loss_sum / denom,
                "correct": correct,
                "count": count,
                # Norm of the ACCUMULATED (count-weighted mean) gradient — the
                # same quantity the unsplit step reports.
                "grad_norm": optax.global_norm(grads).astype(jnp.float32),
            }
        if bad_step_skip:
            ok = _step_ok(metrics)
            new_state = _guard_bad_step(ok, new_state, state)
            metrics = _with_skip_flag(metrics, ok)
        return new_state, metrics

    return accum_train_step


@functools.lru_cache(maxsize=None)
def make_cached_train_step(
    mesh, compute_dtype=jnp.bfloat16, remat: bool = False,
    bad_step_skip: bool = False,
) -> Callable:
    """Train step over a DEVICE-RESIDENT dataset (cfg.device_cache): the
    normalized image set lives in HBM (replicated), and each step gathers its
    batch rows by index inside the compiled program — the host sends only
    ``[B]`` int32 indices + a ``[B]`` valid mask per step instead of the
    ``[B,H,W,3]`` pixels. The gather output is shard-constrained onto the
    ``data`` axis, so each device materializes only its own batch shard and
    the rest of the step is identical to ``make_train_step``.

    This is the end state of the reference's data-feeding problem (its MPI
    pipeline existed to hide per-image host cost, ``evaluation_pipeline.py:
    53-129``): for datasets that fit HBM there is nothing left to hide."""

    @functools.partial(jax.jit, donate_argnums=(0,))
    def cached_step(state: TrainState, dataset, labels_all, idx, valid):
        return _cached_batch_step(
            mesh, compute_dtype, state, dataset, labels_all, idx, valid,
            remat=remat, bad_step_skip=bad_step_skip,
        )

    return cached_step


def _sharded_cache_take(mesh, dataset, idx):
    """Batch-row gather from a dataset whose rows are SHARDED over the data
    axis (``trainer.build_device_cache``): each shard gathers the indices
    that fall in its row range (masked to zero otherwise) and a ``psum``
    combines them — exact, because every global row lives on exactly one
    shard (masked uint8 sums cannot overflow: all other contributions are
    literal zeros). The replicated output is immediately shard-constrained
    back onto ``data`` by the caller, which XLA folds into a
    reduce-scatter — per-step cross-shard traffic of about one batch, the
    price of holding 1/n of the dataset per device instead of a full
    replica."""
    data_axis = mesh.axis_names[0]
    per = dataset.shape[0] // mesh.shape[data_axis]

    def local(ds_local, idx_g):
        li = idx_g - lax.axis_index(data_axis) * per
        inb = (li >= 0) & (li < per)
        rows = jnp.take(ds_local, jnp.clip(li, 0, per - 1), axis=0)
        mask = inb.reshape((-1,) + (1,) * (rows.ndim - 1))
        rows = jnp.where(mask, rows, jnp.zeros((), rows.dtype))
        return lax.psum(rows, data_axis)

    return shard_map(
        local, mesh=mesh, in_specs=(P(data_axis), P()), out_specs=P(),
        check_vma=False,
    )(dataset, idx)


def _gather_batch(mesh, compute_dtype, dataset, labels_all, idx, valid):
    """Index-gather a batch from the HBM-resident dataset, shard-constrained
    onto the data axis — THE shared ingest of the cached train, scanned-epoch,
    and cached eval steps, so none can drift from the others. The dataset's
    rows are sharded over ``data`` whenever that axis has >1 device
    (``build_device_cache``), so the gather goes through the cross-shard
    path; a 1-device data axis holds the whole dataset locally."""
    with jax.named_scope("input"):
        if mesh.shape[mesh.axis_names[0]] > 1:
            raw = _sharded_cache_take(mesh, dataset, idx)
        else:
            raw = jnp.take(dataset, idx, axis=0)
        if raw.ndim == 2:
            # Packed token sequences ``int32 [B, S + 1]`` (data/tokens.py),
            # keyed on the traced rank like ``ingest_images`` on the dtype:
            # inputs are all but the last id, targets all but the first; a
            # padding row's targets are all -1. ``labels_all`` is not read.
            rows = NamedSharding(mesh, P(mesh.axis_names[0]))
            tokens = lax.with_sharding_constraint(raw[:, :-1], rows)
            return tokens, jnp.where(valid[:, None], raw[:, 1:], -1)
    images = ingest_images(raw, compute_dtype)  # opens ``input`` itself
    with jax.named_scope("input"):
        images = lax.with_sharding_constraint(
            images, NamedSharding(mesh, P(mesh.axis_names[0]))
        )
        labels = jnp.where(valid, jnp.take(labels_all, idx), -1)
    return images, labels


def _cached_batch_step(
    mesh, compute_dtype, state, dataset, labels_all, idx, valid,
    remat: bool = False, bad_step_skip: bool = False,
):
    """One gather-from-HBM train step — THE shared body of the per-step
    cached mode and the scanned-epoch mode, so the two can never drift
    numerically (the trainer's FLOPs accounting and the scan≡cached test
    both rely on the per-step program equalling the scan body)."""
    images, labels = _gather_batch(mesh, compute_dtype, dataset, labels_all, idx, valid)
    rng = jax.random.fold_in(state.rng, state.step)
    loss, logits, new_bs, grads, counters = _loss_and_updates(
        state, images, labels, rng, remat=remat
    )
    new_state = _apply_updates(state, grads, new_bs)
    metrics = _step_metrics(loss, logits, labels, grads, counters)
    if bad_step_skip:
        # Inside the scanned epoch this guards EVERY scan iteration: a
        # non-finite step mid-scan is discarded on device and the scan
        # simply carries the pre-step state forward.
        ok = _step_ok(metrics)
        new_state = _guard_bad_step(ok, new_state, state)
        metrics = _with_skip_flag(metrics, ok)
    return new_state, metrics


@functools.lru_cache(maxsize=None)
def make_scanned_epoch(
    mesh, compute_dtype=jnp.bfloat16, remat: bool = False,
    bad_step_skip: bool = False,
) -> Callable:
    """An ENTIRE epoch as one compiled program (cfg.scan_epoch): ``lax.scan``
    over the per-step index batches, gathering each batch from the
    HBM-resident dataset exactly like ``make_cached_train_step``.

    Why: with the dataset cached on device, the remaining end-to-end cost is
    per-step Python dispatch (one host→device round-trip per step).
    Scanning moves the epoch loop into XLA: one
    dispatch per EPOCH, zero host involvement between steps. This is the
    idiomatic-TPU endpoint of the reference's data-feeding problem — where
    its MPI pipeline overlapped host stages (``evaluation_pipeline.py:
    53-129``), here the host isn't on the path at all.

    Returns ``(state, metrics)`` where each metrics leaf is ``[n_steps]``
    (per-step loss / correct / count), so the trainer's per-sample epoch
    accounting is unchanged."""

    @functools.partial(jax.jit, donate_argnums=(0,))
    def epoch_fn(state: TrainState, dataset, labels_all, idx_all, valid_all):
        def body(state, step_batch):
            idx, valid = step_batch
            return _cached_batch_step(
                mesh, compute_dtype, state, dataset, labels_all, idx, valid,
                remat=remat, bad_step_skip=bad_step_skip,
            )

        return lax.scan(body, state, (idx_all, valid_all))

    return epoch_fn


@functools.lru_cache(maxsize=None)
def make_cached_eval_step(mesh, compute_dtype=jnp.bfloat16) -> Callable:
    """Eval forward over the DEVICE-RESIDENT dataset: gather the batch by
    index like ``make_cached_train_step``, then the ``make_eval_step`` math.
    With ``val_on_train=True`` (the reference's default validation semantics,
    ``main.py:104-112``) the cached train set is reused as-is, so per-epoch
    validation costs zero host decode and zero H2D traffic."""

    @jax.jit
    def cached_eval_step(state: TrainState, dataset, labels_all, idx, valid):
        images, labels = _gather_batch(mesh, compute_dtype, dataset, labels_all, idx, valid)
        return _eval_metrics(state, images, labels, compute_dtype)

    return cached_eval_step


def eval_logits(state: TrainState, images, compute_dtype):
    """Eval forward with the pinned f32 boundary.

    The barrier pins a real f32 boundary: without it XLA fuses the upcast
    into the softmax chain and evaluates logsumexp at bf16 precision, which
    yields per-example CE errors of ±3e-3 — enough to report (impossible)
    negative eval losses on a converged model (measured: batch loss-sums off
    by ±0.4 vs the eager computation)."""
    logits = state.apply_fn(state.variables, ingest_images(images, compute_dtype), train=False)
    return lax.optimization_barrier(logits.astype(jnp.float32))


def metrics_from_logits(logits, labels):
    """loss-sum / correct / count from f32 logits (labels < 0 = padding) —
    shared by the eval steps and the evaluate-driver predictions pass."""
    valid = labels >= 0
    safe_labels = jnp.maximum(labels, 0)
    per_ex = optax.softmax_cross_entropy_with_integer_labels(logits, safe_labels)
    return {
        "loss": jnp.sum(per_ex * valid),
        "correct": jnp.sum((jnp.argmax(logits, axis=-1) == labels) & valid),
        "count": jnp.sum(valid.astype(jnp.int32)),
    }


def _eval_metrics(state: TrainState, images, labels, compute_dtype):
    """Shared eval math of the streaming and cached eval steps."""
    return metrics_from_logits(eval_logits(state, images, compute_dtype), labels)


@functools.lru_cache(maxsize=None)
def make_eval_step(compute_dtype=jnp.bfloat16) -> Callable:
    """Batched eval forward (≙ validation loop body ``main.py:173-182`` and
    the predict stage ``evaluation_pipeline.py:149-158``, batched).

    Memoized so per-epoch validation reuses one jitted function (and its XLA
    cache) instead of recompiling the forward every epoch."""

    @jax.jit
    def eval_step(state: TrainState, batch):
        images, labels = batch
        # labels < 0 mark padding rows (tail batches padded to a static
        # shape so XLA never recompiles; see trainer.evaluate_manifest).
        return _eval_metrics(state, images, labels, compute_dtype)

    return eval_step


def place_state_on_mesh(
    state: TrainState, mesh, zero_optimizer: bool = False, fsdp: bool = False
) -> TrainState:
    """Device-put the state with DP/TP shardings: head column-sharded over
    ``model``, everything else replicated. Opt-state mirrors param shardings
    (Adam moments have the params' tree structure).

    ``zero_optimizer`` (beyond reference parity — SURVEY §2c's 'natural pjit
    extension'): Adam moments of replicated params are sharded over the
    ``data`` axis instead of replicated (ZeRO-1 style). The compiler then
    partitions the elementwise optimizer update along the moment sharding
    and gathers the param updates — per-device optimizer memory drops from
    2×params to 2×params/n with no change to the step function.

    ``fsdp`` (ZeRO-3 style): the params THEMSELVES are sharded over the
    ``data`` axis at rest (``param_specs(..., fsdp=True)``), and the Adam
    moments follow their params' shardings automatically. XLA all-gathers
    each layer's weights at use and reduce-scatters its gradient; per-device
    params+optimizer memory drops from 3×params to 3×params/n. The step
    function is unchanged — sharding is entirely a placement decision."""
    specs = param_specs(state.params, mesh, fsdp=fsdp)
    p_shard = named_shardings(specs, mesh)
    rep = NamedSharding(mesh, P())
    data_axis, data_size = mesh.axis_names[0], mesh.shape[mesh.axis_names[0]]

    new_params = jax.tree_util.tree_map(jax.device_put, state.params, p_shard)

    def put_opt_tree(opt_state):
        # optax states (adam mu/nu) contain params-shaped subtrees plus
        # scalars; match shardings by (shape, dtype), replicate the rest.
        shape_map = {}
        for pl, ps in zip(
            jax.tree_util.tree_leaves(state.params), jax.tree_util.tree_leaves(p_shard)
        ):
            shape_map.setdefault((pl.shape, str(pl.dtype)), ps)

        def zero_spec(shape) -> NamedSharding | None:
            # Same shard-selection rule as FSDP param placement; None → no
            # axis shards evenly, replicate.
            spec = shard_first_divisible(shape, data_axis, data_size)
            return None if spec == P() else NamedSharding(mesh, spec)

        def put(leaf):
            if not hasattr(leaf, "shape"):
                return leaf
            sharding = shape_map.get((leaf.shape, str(leaf.dtype)), rep)
            if (
                zero_optimizer
                and data_size > 1
                and leaf.ndim > 0
                and sharding.spec == P()  # don't override TP-head moment shardings
            ):
                sharding = zero_spec(leaf.shape) or rep
            return jax.device_put(leaf, sharding)

        return jax.tree_util.tree_map(put, opt_state)

    return state.replace(
        params=new_params,
        batch_stats=jax.device_put(state.batch_stats, rep)
        if state.batch_stats is not None
        else None,
        opt_state=put_opt_tree(state.opt_state),
        step=jax.device_put(state.step, rep),
        rng=jax.device_put(state.rng, rep),
    )


# ---------------------------------------------------------------------------
# spmd mode: shard_map with explicit collectives (reference-parity semantics)
# + the two training-half levers (ROADMAP item 2): ZeRO optimizer-state
# sharding (arXiv 2004.13336) and bucketed gradient-sync overlap
# (arXiv 1810.11112).
# ---------------------------------------------------------------------------


def _zero_chunk(size: int, n_shards: int) -> int:
    """Rows per shard of a flatten-pad-reshaped leaf (``state.zero_shard_spec``)."""
    return -(-size // n_shards)


def grad_bucket_plan(params, bucket_mb: float) -> list[list[int]]:
    """Partition the param tree's flat-leaf indices into ~``bucket_mb``-MiB
    buckets in REVERSE flatten order — the reverse-topological approximation
    (backward produces the later layers' gradients first, so the first
    bucket to fill is the first whose collective can be issued while the
    backward for earlier layers is still running; arXiv 1810.11112
    characterizes exactly this allreduce/compute overlap). Leaves of
    different dtypes never share a bucket (each bucket is one fused
    collective over a concatenated flat vector); a single leaf larger than
    the cap gets a bucket of its own. Works on concrete arrays AND on
    tracers (the step calls it at trace time; the trainer calls it on the
    placed params for telemetry — same plan, one source of truth)."""
    import numpy as np

    leaves = jax.tree_util.tree_leaves(params)
    cap = max(1, int(bucket_mb * (1 << 20)))
    buckets: list[list[int]] = []
    cur: list[int] = []
    cur_bytes, cur_dtype = 0, None
    for i in reversed(range(len(leaves))):
        leaf = leaves[i]
        dtype = np.dtype(leaf.dtype)
        nbytes = leaf.size * dtype.itemsize
        if cur and (cur_bytes + nbytes > cap or dtype != cur_dtype):
            buckets.append(cur)
            cur, cur_bytes = [], 0
        cur.append(i)
        cur_bytes += nbytes
        cur_dtype = dtype
    if cur:
        buckets.append(cur)
    return buckets


def bucket_overlap_frac(params, buckets: list[list[int]]) -> float:
    """Static dataflow estimate of the overlap opportunity: the fraction of
    gradient-sync bytes whose collective is issued BEFORE the final bucket.
    The final bucket holds the earliest layers' gradients, which only exist
    once the backward itself completes — its collective can never hide under
    remaining backward compute; every earlier bucket's can. A plan-derived
    upper bound, not a measurement (one bucket ≡ the fused baseline → 0.0);
    the measured per-bucket timings are a chip-profile question
    (``tools/bench_modes.py --levers``)."""
    import numpy as np

    leaves = jax.tree_util.tree_leaves(params)

    def bucket_bytes(bucket):
        return sum(
            leaves[i].size * np.dtype(leaves[i].dtype).itemsize for i in bucket
        )

    total = sum(bucket_bytes(b) for b in buckets)
    if total == 0 or len(buckets) <= 1:
        return 0.0
    return round(1.0 - bucket_bytes(buckets[-1]) / total, 4)


def hier_dcn_overlap_frac(params, buckets: list[list[int]]) -> float:
    """Static estimate of the cross-pod (DCN) overlap opportunity on a
    hierarchical bucket plan: the fraction of DCN sync bytes whose
    cross-pod phase is issued before the FINAL bucket's within-pod phase
    completes. Each bucket's DCN payload is proportional to its byte size
    (bucket_bytes / ici per pod pair), so the fraction is structurally the
    same number as ``bucket_overlap_frac`` — exposed under its own name
    because the claim it backs is different: DCN latency (the slow link)
    hides under remaining backward compute + later buckets' ICI phases,
    which is the whole point of the two-level sync (arXiv 1810.11112)."""
    return bucket_overlap_frac(params, buckets)


def _slice_tree(tree, data_axis: str, n_shards: int):
    """Shard k's OWNED 1/P slice of every leaf (the ``zero_shard_spec``
    flatten-pad partition), taken with one dynamic_slice per leaf at
    ``lax.axis_index`` — must run inside a shard_map binding ``data_axis``.
    On a nested mesh ``data_axis`` is the ``ici`` axis: the slice index is
    the within-pod position, identical across pods."""
    idx = lax.axis_index(data_axis)

    def slc(x):
        chunk = _zero_chunk(x.size, n_shards)
        flat = jnp.pad(x.reshape(-1), (0, chunk * n_shards - x.size))
        return lax.dynamic_slice(flat, (idx * chunk,), (chunk,))

    return jax.tree_util.tree_map(slc, tree)


def _bucketed_pmean(grads, buckets, data_axis: str):
    """Replace the one whole-tree fused ``pmean`` with one pmean per bucket,
    issued in reverse-topo order. Each bucket's collective depends ONLY on
    its own leaves' gradients, so the XLA scheduler is free to start it on
    the ICI while the backward is still producing earlier layers' grads —
    the dataflow form of allreduce/compute overlap (arXiv 1810.11112)."""
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    out: list = [None] * len(leaves)
    for bucket in buckets:
        flat = jnp.concatenate([leaves[i].reshape(-1) for i in bucket])
        collectives._account(
            "all_reduce", data_axis, flat.size * jnp.dtype(flat.dtype).itemsize
        )
        mean = lax.pmean(flat, data_axis)
        off = 0
        for i in bucket:
            n = leaves[i].size
            out[i] = mean[off : off + n].reshape(leaves[i].shape)
            off += n
    return jax.tree_util.tree_unflatten(treedef, out)


def _bucketed_reduce_scatter(grads, buckets, data_axis: str, n_shards: int):
    """The (a)+(b) composition: each bucket is ONE ``psum_scatter`` over its
    leaves stacked ``[P, chunk_i]`` and concatenated along the chunk axis —
    shard k receives exactly row k, its OWNED slice of every leaf in the
    ``zero_shard_spec`` layout, at half an allreduce's egress bytes (the
    grad-comms halving of arXiv 2004.13336 §weight-update sharding).
    Returns the tree of ``[chunk]`` mean-gradient slices."""
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    out: list = [None] * len(leaves)
    for bucket in buckets:
        stacked = []
        for i in bucket:
            chunk = _zero_chunk(leaves[i].size, n_shards)
            flat = jnp.pad(
                leaves[i].reshape(-1), (0, chunk * n_shards - leaves[i].size)
            )
            stacked.append(flat.reshape(n_shards, chunk))
        cat = jnp.concatenate(stacked, axis=1)
        collectives._account(
            "reduce_scatter", data_axis, cat.size * jnp.dtype(cat.dtype).itemsize
        )
        sl = (
            lax.psum_scatter(cat, data_axis, scatter_dimension=0, tiled=True)
            / n_shards
        ).reshape(-1)
        off = 0
        for i in bucket:
            chunk = _zero_chunk(leaves[i].size, n_shards)
            out[i] = sl[off : off + chunk]
            off += chunk
    return jax.tree_util.tree_unflatten(treedef, out)


def _hier_bucketed_mean(grads, buckets, ici_axis: str, pod_axis: str):
    """The hierarchical twin of ``_bucketed_pmean``: each reverse-topo
    bucket is ONE three-phase collective — ICI reduce-scatter of the
    concatenated bucket, DCN psum of the 1/ici slice (the only bytes that
    leave the pod), ICI all-gather back to full shape. Each bucket's DCN
    phase depends only on its OWN within-pod result, so the scheduler
    issues it the moment phase 1 completes — cross-pod latency hides under
    the remaining backward AND the later buckets' ICI phases."""
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    out: list = [None] * len(leaves)
    for bucket in buckets:
        flat = jnp.concatenate([leaves[i].reshape(-1) for i in bucket])
        mean = collectives.hier_pmean(flat, ici_axis, pod_axis)
        off = 0
        for i in bucket:
            n = leaves[i].size
            out[i] = mean[off : off + n].reshape(leaves[i].shape)
            off += n
    return jax.tree_util.tree_unflatten(treedef, out)


def _hier_bucketed_reduce_scatter(
    grads, buckets, ici_axis: str, pod_axis: str, n_shards: int, n_pods: int
):
    """The ZeRO composition on the nested mesh: one ICI ``psum_scatter``
    per bucket over the ``zero_shard_spec``-stacked leaves (shard i of
    every pod receives slice i of the POD-LOCAL mean), then one DCN psum of
    just that slice — cross-pod grad bytes per bucket are
    ``bucket_bytes / ici``, the ~1/ici_size shrink the byte ledger pins.
    Returns the tree of ``[chunk]`` GLOBAL-mean gradient slices, identical
    (up to reduction order) to slicing ``_bucketed_reduce_scatter`` of a
    flat mesh of the same total size."""
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    out: list = [None] * len(leaves)
    for bucket in buckets:
        stacked = []
        for i in bucket:
            chunk = _zero_chunk(leaves[i].size, n_shards)
            flat = jnp.pad(
                leaves[i].reshape(-1), (0, chunk * n_shards - leaves[i].size)
            )
            stacked.append(flat.reshape(n_shards, chunk))
        cat = jnp.concatenate(stacked, axis=1)
        collectives._account(
            "reduce_scatter", ici_axis, cat.size * jnp.dtype(cat.dtype).itemsize
        )
        sl = lax.psum_scatter(
            cat, ici_axis, scatter_dimension=0, tiled=True
        ).reshape(-1)
        collectives._account(
            "all_reduce", pod_axis, sl.size * jnp.dtype(sl.dtype).itemsize
        )
        sl = lax.psum(sl, pod_axis) / (n_shards * n_pods)
        off = 0
        for i in bucket:
            chunk = _zero_chunk(leaves[i].size, n_shards)
            out[i] = sl[off : off + chunk]
            off += chunk
    return jax.tree_util.tree_unflatten(treedef, out)


def make_spmd_train_step(
    mesh,
    compute_dtype=jnp.bfloat16,
    remat: bool = False,
    zero_opt_state: bool = False,
    grad_bucket_mb: float = 0.0,
    bad_step_skip: bool = False,
) -> Callable:
    """Reference-parity DP step: shard_map over ``data``; local BN stats;
    explicit ``avg_grads`` pmean — the literal TPU translation of one
    training iteration of ``mpiexec -n N python -m mpi4py main.py``.

    Two composable levers on top (ROADMAP item 2; both default OFF, in which
    case the step is byte-identical to the reference-parity baseline):

    - ``zero_opt_state`` (``--zero-opt-state``): the optimizer state arrives
      in the ``zero_shard_spec`` layout (``state.zero_shard_opt_state``:
      every array leaf ``[P, chunk]``, sharded over ``data``). Each shard
      slices out ITS 1/P of the params and mean gradients, applies the
      optimizer update to that slice only, and one tiled ``all_gather``
      (collectives.py) reassembles full params for the next forward —
      per-device optimizer HBM drops 2×params → 2×params/P with the same
      update math (arXiv 2004.13336). The sliced update is exact because
      adam/adamw/sgd-momentum (and ``multi_transform`` freezing) are
      elementwise per leaf and the flatten-pad slicing preserves the optax
      tree structure.

    - ``grad_bucket_mb`` > 0 (``--grad-sync-buckets``): the one fused
      post-backward ``pmean`` becomes one collective per ~N-MiB bucket of
      param leaves in reverse-topo order (``grad_bucket_plan``) — each
      bucket's collective depends only on its own grads, so it can overlap
      the remaining backward (arXiv 1810.11112). With ``zero_opt_state``
      the buckets become ``reduce_scatter``s: each shard receives only its
      owned slice and grad comms halve.

    On a NESTED ``(pod, ici)`` mesh (``--mesh-pods``, ISSUE 15) the same
    step becomes the two-level hierarchical sync of ROADMAP item 5: every
    gradient collective decomposes into an ICI phase (within-pod
    reduce-scatter) and a DCN phase (cross-pod psum of the 1/ici-sized
    partial), each bucket's DCN phase issued the moment its ICI phase
    completes so cross-pod latency hides under remaining backward compute;
    ZeRO shards place WITHIN the pod (slice index = ici position), so the
    param all_gather never crosses the DCN. Numerics are parity-pinned
    against the flat step (tests/test_hierarchical.py).

    The self-partitioning Mosaic kernels (``ops/fused_stem.py``,
    ``ops/fused_head_ce.py``, ``ops/fused_attention_small.py``) compose
    with this step without special-casing: their wrappers detect the
    already-bound ``data`` axis (``compat.axis_is_manual``) and run the
    per-shard kernel call directly instead of nesting a second shard_map
    over the same axis."""
    hier = is_hierarchical(mesh)
    data_axes = data_axis_names(mesh)
    # Hierarchical (pods > 1): the data axis is the nested (pod, ici) pair.
    # Scalar reductions span both axes in one psum; the GRADIENT sync is
    # explicitly two-phase so the DCN carries only 1/ici of the payload.
    pod_axis, ici_axis = (data_axes if hier else (None, data_axes[0]))
    red_axes = data_axes if hier else data_axes[0]
    n_pods, ici_size = pod_shape(mesh)
    # The ZeRO partition axis: within-pod (ici) on a nested mesh, so slice
    # ownership — and the param all_gather — never crosses the DCN.
    zero_axis, n_shards = zero_shard_axis(mesh)
    batch_spec = P(data_axes if hier else data_axes[0])

    def _forward_backward(state: TrainState, batch):
        images, labels = batch
        images = ingest_images(images, compute_dtype)
        # Per-shard rng ≙ each MPI rank's independent dropout stream. The
        # nested index folds pod-major, which equals the flat shard index
        # for the same device — hierarchical runs draw the identical
        # per-shard streams a flat run would (parity-pinned).
        shard_idx = (
            lax.axis_index(pod_axis) * ici_size + lax.axis_index(ici_axis)
            if hier
            else lax.axis_index(ici_axis)
        )
        rng = jax.random.fold_in(
            jax.random.fold_in(state.rng, state.step), shard_idx
        )
        loss, logits, new_bs, grads, _ = _loss_and_updates(
            state, images, labels, rng, remat=remat
        )
        # Running BN stats: normalization above used LOCAL batch stats
        # (reference per-rank semantics); the stored running averages are
        # pmean'd so the replicated state stays consistent across shards
        # (the reference instead checkpoints rank 0's stats, main.py:162-171).
        if new_bs is not None:
            with jax.named_scope("grad_sync"):
                new_bs = (
                    collectives.hier_pmean(new_bs, ici_axis, pod_axis)
                    if hier
                    else collectives.all_reduce(new_bs, "mean", axis=ici_axis)
                )
        return loss, logits, new_bs, grads, labels

    def _metrics(loss, logits, labels, grad_norm):
        # Reported loss is the GLOBAL per-sample mean (each shard's mean loss
        # weighted by its valid-row count), so padded tail steps with uneven
        # shard occupancy stay exact — the *gradient* keeps the reference's
        # unweighted per-rank average (mpi_avg_grads divides by world size
        # regardless of local batch size, mpi_tools.py:36). These are scalar
        # psums (a few bytes), spanning both nested axes in one collective —
        # not worth a two-phase decomposition or a ledger entry.
        with jax.named_scope("metrics"):
            local_count = valid_count(labels)
            global_count = lax.psum(local_count, red_axes)
            return {
                "loss": lax.psum(loss * local_count.astype(loss.dtype), red_axes)
                / jnp.maximum(global_count.astype(loss.dtype), 1),
                "correct": lax.psum(accuracy_count(logits, labels), red_axes),
                "count": global_count,
                "grad_norm": grad_norm.astype(jnp.float32),
            }

    if not zero_opt_state:

        def per_shard(state: TrainState, batch):
            loss, logits, new_bs, grads, labels = _forward_backward(state, batch)
            with jax.named_scope("grad_sync"):
                if grad_bucket_mb > 0:
                    plan = grad_bucket_plan(grads, grad_bucket_mb)
                    grads = (
                        _hier_bucketed_mean(grads, plan, ici_axis, pod_axis)
                        if hier
                        else _bucketed_pmean(grads, plan, ici_axis)
                    )
                elif hier:
                    # Three-phase hierarchical allreduce: the DCN sees 1/ici of
                    # the gradient bytes a flat pmean would push across it.
                    grads = collectives.hier_pmean(grads, ici_axis, pod_axis)
                else:
                    # THE line (≙ the entire mpi_avg_grads stack, mpi_tools.py:30-37):
                    grads = collectives.avg_grads(grads, axis=ici_axis)
            new_state = _apply_updates(state, grads, new_bs)
            # grads were just averaged: every shard computes the identical
            # global-gradient norm, so no further collective is needed.
            with jax.named_scope("metrics"):
                grad_norm = optax.global_norm(grads)
            metrics = _metrics(loss, logits, labels, grad_norm)
            if bad_step_skip:
                # The verdict reads the ALREADY-psum'd loss and the
                # averaged-grads norm, so every shard takes the same branch
                # with no extra collective (the skip-policy contract).
                ok = _step_ok(metrics)
                new_state = _guard_bad_step(ok, new_state, state)
                metrics = _with_skip_flag(metrics, ok)
            return new_state, metrics

        sharded = shard_map(
            per_shard,
            mesh=mesh,
            in_specs=(P(), (batch_spec, batch_spec)),
            out_specs=(P(), P()),
            check_vma=False,
        )
        return jax.jit(sharded, donate_argnums=(0,))

    # --- zero_opt_state: ZeRO-sharded weight update ------------------------
    # The optimizer state's array leaves travel through shard_map as a FLAT
    # TUPLE with per-leaf specs (P(data) for [P, chunk] leaves, P() for
    # scalars) — the rest of the TrainState stays one replicated P() prefix.
    # The treedef is closed over per trace, so jit recompiles only if the
    # optimizer structure itself changes (it never does mid-run: zero
    # steady-state compiles, asserted by the dryrun leg).

    def per_shard_zero(opt_treedef, state: TrainState, flat_opt, batch):
        loss, logits, new_bs, grads, labels = _forward_backward(state, batch)

        with jax.named_scope("grad_sync"):
            if grad_bucket_mb > 0:
                plan = grad_bucket_plan(grads, grad_bucket_mb)
                grad_slices = (
                    _hier_bucketed_reduce_scatter(
                        grads, plan, ici_axis, pod_axis, n_shards, n_pods
                    )
                    if hier
                    else _bucketed_reduce_scatter(grads, plan, ici_axis, n_shards)
                )
            elif hier:
                # Phases 1+2 only: each ici shard keeps its global-mean slice
                # (pod-replicated) — the slice IS what the sharded optimizer
                # update consumes, so no gather of gradients ever happens.
                grad_slices = collectives.hier_reduce_scatter_mean(
                    grads, ici_axis, pod_axis
                )
            else:
                grads = collectives.avg_grads(grads, axis=ici_axis)
                grad_slices = _slice_tree(grads, ici_axis, n_shards)
        # Global grad norm from the owned slices: the slices tile the mean
        # gradient exactly (padding contributes zeros), so psum of per-slice
        # squared sums is the global squared norm — same number every other
        # step flavor reports, one scalar collective. Over the ZeRO axis
        # only: on a nested mesh the slices are pod-replicated, so an
        # all-axis psum would count each slice pods times.
        with jax.named_scope("metrics"):
            sq = sum(
                jnp.sum(jnp.square(g.astype(jnp.float32)))
                for g in jax.tree_util.tree_leaves(grad_slices)
            )
            grad_norm = jnp.sqrt(lax.psum(sq, zero_axis))

        with jax.named_scope("optimizer"):
            param_slices = _slice_tree(state.params, zero_axis, n_shards)
            opt_local = jax.tree_util.tree_unflatten(
                opt_treedef,
                [
                    leaf.reshape(leaf.shape[1:]) if getattr(leaf, "ndim", 0) else leaf
                    for leaf in flat_opt
                ],
            )
            # The sliced trees preserve the params' TREE structure, so the optax
            # chain (schedules off the replicated count scalar, multi_transform
            # labels, adamw decay against the sliced params) applies unchanged.
            updates, new_opt = state.tx.update(grad_slices, opt_local, param_slices)
            new_param_slices = optax.apply_updates(param_slices, updates)
            # Reassemble full params for the next forward: ONE tiled allgather
            # per leaf, then strip the zero_shard_spec padding. On a nested
            # mesh this gathers over ``ici`` ONLY — every pod holds the full
            # slice set, so reassembling params costs zero DCN bytes (the
            # within-pod ZeRO placement rule).
            gathered = (
                collectives.hier_all_gather(new_param_slices, ici_axis)
                if hier
                else collectives.all_gather(new_param_slices, axis=ici_axis)
            )
            new_params = jax.tree_util.tree_map(
                lambda full, orig: full[: orig.size].reshape(orig.shape),
                gathered,
                state.params,
            )
            new_state = state.replace(
                step=state.step + 1,
                params=new_params,
                batch_stats=new_bs if state.batch_stats is not None else None,
                rng=jax.random.fold_in(state.rng, 1),
            )
            new_flat = tuple(
                leaf[None] if getattr(leaf, "ndim", 0) else leaf
                for leaf in jax.tree_util.tree_leaves(new_opt)
            )
        metrics = _metrics(loss, logits, labels, grad_norm)
        if bad_step_skip:
            # Same contract as the non-ZeRO shard: the psum'd loss/norm
            # give every shard the identical verdict, and the guard covers
            # BOTH the replicated state and this shard's opt-state slices.
            ok = _step_ok(metrics)
            new_state = _guard_bad_step(ok, new_state, state)
            new_flat = _guard_bad_step(ok, new_flat, tuple(flat_opt))
            metrics = _with_skip_flag(metrics, ok)
        return new_state, new_flat, metrics

    def step(state: TrainState, batch):
        flat_opt, opt_treedef = jax.tree_util.tree_flatten(state.opt_state)
        # Array leaves arrive [n_shards, chunk] sharded over the ZeRO axis
        # (the ici axis on a nested mesh — pod-replicated by construction).
        opt_specs = tuple(
            P(zero_axis) if getattr(leaf, "ndim", 0) else P() for leaf in flat_opt
        )
        core = shard_map(
            functools.partial(per_shard_zero, opt_treedef),
            mesh=mesh,
            in_specs=(P(), opt_specs, (batch_spec, batch_spec)),
            out_specs=(P(), opt_specs, P()),
            check_vma=False,
        )
        new_state, new_flat, metrics = core(
            state.replace(opt_state=()), tuple(flat_opt), batch
        )
        return (
            new_state.replace(
                opt_state=jax.tree_util.tree_unflatten(opt_treedef, list(new_flat))
            ),
            metrics,
        )

    return jax.jit(step, donate_argnums=(0,))
