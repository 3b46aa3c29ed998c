"""Elastic training: mesh-shape-change resume, preemption watchdog, and the
in-process halves of the fault-injection harness (ISSUE 7 / ROADMAP item 4).

Production fleets lose and gain chips, and backends wedge. The reference handles
every failure the same way: a human restarts ``main.py`` with
``FROM_CHECKPOINT=True`` onto the SAME MPI world (``main.py:127-130``).
This module generalizes that into a self-healing loop:

- **Topology manifest** — every checkpoint is stamped with the writer's
  world shape (device/process counts, dp×mp mesh shape, the ZeRO
  ``[P, chunk]`` shard layout per optimizer leaf, payload schema version)
  as a JSON sidecar (``checkpoint.write_manifest``), so a restore knows
  what it is resharding FROM without trusting the payload.

- **Reshard-on-load** (``restore_latest``) — a checkpoint written on mesh
  shape A restores onto mesh shape B. The on-disk payload is always the
  gathered (unsharded) host layout (``zero_unshard_opt_state``
  gather-on-save), so resharding is a placement problem: replicated leaves
  are re-placed, sharded leaves re-split for the new axis sizes, and ZeRO
  opt-state leaves re-flattened/re-padded/re-chunked for the new P
  (``zero_shard_opt_state`` — including the P→1 and 1→P degenerate cases).
  Small leaves batch through one jitted reshape; leaves past the bounded-
  HBM cap take the chunked per-row redistribution
  (``state._row_redistribute``) so no device ever transiently holds a full
  unsharded moment tensor. A corrupt/truncated newest checkpoint logs a
  ``kind="anomaly"`` record and falls back to the previous one.

- **Preemption watchdog** (``PreemptionWatchdog``) — generalizes the
  SIGTERM-only ``PreemptionGuard``: a sentinel file (``MPT_PREEMPT_FILE``,
  the cluster-scheduler preemption-notice pattern) or repeated health
  signals (straggler-beat / non-finite-grad streaks from ``obs/``) trigger
  the same safe-boundary save + clean exit, each writing a ``kind="fault"``
  record naming the reason.

- **Bounded retry+backoff** (``with_retries``) — the resume side retries
  backend init and state placement a bounded number of times with
  deterministic exponential backoff, absorbing transient wedges instead of
  dying on the first one.

- **Fault injection** (``FaultInjector`` + the ``MPT_FAULT_*`` gates in
  ``utils/env.py``, driven by ``tools/inject_faults.py``) — deterministic
  mid-step kills and fake stragglers, so the recovery paths above are
  testable end to end on a CPU mesh.
"""

from __future__ import annotations

import os
import signal
import time
from typing import Any

import jax

from mpi_pytorch_tpu import checkpoint as ckpt
from mpi_pytorch_tpu.parallel.mesh import (
    describe_topology,
    mesh_topology,
    zero_shard_axis,
)
from mpi_pytorch_tpu.train.state import _BOUNDED_LEAF_BYTES, zero_shard_spec
from mpi_pytorch_tpu.train.step import place_state_on_mesh
from mpi_pytorch_tpu.utils.env import env_int, fault_countdown
from mpi_pytorch_tpu.utils.logging import process_index, run_logger

MANIFEST_VERSION = 1


# ---------------------------------------------------------------------------
# Topology manifest
# ---------------------------------------------------------------------------


def zero_shard_layout(opt_template: Any, n_shards: int) -> dict:
    """Per-leaf ZeRO partition table for the manifest: key-path →
    ``[chunk, padded]`` (``zero_shard_spec``), or None for replicated
    scalars. ``opt_template`` is the unsharded optimizer layout
    (``jax.eval_shape(tx.init, params)``)."""
    layout = {}
    flat, _ = jax.tree_util.tree_flatten_with_path(opt_template)
    for path, leaf in flat:
        if not hasattr(leaf, "shape"):
            continue
        layout[jax.tree_util.keystr(path)] = zero_shard_spec(tuple(leaf.shape), n_shards)
    return layout


def topology_manifest(
    mesh,
    *,
    zero_opt_state: bool = False,
    spmd_mode: bool = False,
    opt_template: Any = None,
) -> dict:
    """The JSON-able topology stamp every checkpoint of this run carries
    (``checkpoint.write_manifest`` sidecar): world shape, payload schema,
    and — for ZeRO runs — the writer's per-leaf shard layout, so a restore
    can state exactly what it resharded from P_old to P_new."""
    manifest = {
        "manifest_version": MANIFEST_VERSION,
        "payload_schema": ckpt.PAYLOAD_SCHEMA,
        **mesh_topology(mesh),
        "zero_opt_state": bool(zero_opt_state),
        "spmd_mode": bool(spmd_mode),
    }
    if zero_opt_state:
        # The ZeRO partition axis: within-pod (ici) on a nested mesh —
        # matches what zero_shard_opt_state actually chunked to, so a
        # restore states the true P_old (parallel/mesh.zero_shard_axis).
        _, n_shards = zero_shard_axis(mesh)
        manifest["zero_shards"] = n_shards
        if opt_template is not None:
            manifest["zero_shard_layout"] = zero_shard_layout(opt_template, n_shards)
    return manifest


# ---------------------------------------------------------------------------
# Reshard-on-load restore with corruption fallback
# ---------------------------------------------------------------------------


def restore_latest(
    ckpt_dir: str,
    state: Any,
    mesh,
    *,
    metrics=None,
    logger=None,
    zero_shards_to: int = 0,
):
    """Restore the newest LOADABLE checkpoint in ``ckpt_dir`` against
    ``state``'s templates, walking back past corrupt files, and write the
    ``kind="resume"`` record describing the topology change.

    Returns ``(state, epoch, loss, info)`` or None when no loadable
    checkpoint exists (fresh start). ``info`` carries the path, the
    writer's manifest (None for legacy files), and how many corrupt
    checkpoints were skipped. The caller still places the returned host
    state onto ``mesh`` (``checked_place`` + ``zero_shard_opt_state``) —
    this function only decides WHAT to restore and records the topology
    delta; ``zero_shards_to`` is the data-axis size the caller will
    reshard the ZeRO opt-state to (0 = replicated, no ZeRO)."""
    log = logger or run_logger()
    corrupt = 0
    paths = ckpt.checkpoint_paths(ckpt_dir)
    for path in reversed(paths):
        try:
            restored, epoch, loss = ckpt.load_checkpoint(path, state)
        except ckpt.CheckpointCorruptError as e:
            corrupt += 1
            log.error(
                "corrupt checkpoint %s (%s) — falling back to the previous one",
                path, e,
            )
            if metrics is not None:
                file_epoch = ckpt.checkpoint_epoch(path)
                metrics.write(
                    {
                        "kind": "anomaly",
                        "reason": "corrupt_checkpoint",
                        "epoch": file_epoch if file_epoch is not None else -1,
                        "path": path,
                    }
                )
            continue
        manifest = ckpt.read_manifest(path)
        _write_resume_record(
            metrics, epoch, path, manifest, mesh, zero_shards_to, corrupt, restored
        )
        if manifest is not None and manifest.get("payload_schema", 1) > ckpt.PAYLOAD_SCHEMA:
            log.warning(
                "checkpoint %s was written by a NEWER payload schema (%s > %s); "
                "restore proceeded but fields beyond this build's schema are lost",
                path, manifest.get("payload_schema"), ckpt.PAYLOAD_SCHEMA,
            )
        from_topo = describe_topology(manifest)
        to_topo = describe_topology(mesh_topology(mesh))
        if manifest is None or manifest.get("mesh_shape") != mesh_topology(mesh)["mesh_shape"]:
            log.info(
                "elastic resume: checkpoint topology %s → current %s "
                "(reshard-on-load%s)",
                from_topo, to_topo,
                f"; ZeRO opt-state re-chunked to P={zero_shards_to}"
                if zero_shards_to else "",
            )
        return restored, epoch, loss, {
            "path": path, "manifest": manifest, "corrupt_skipped": corrupt,
        }
    if corrupt:
        # Checkpoints existed but NONE restored. Real on-disk corruption
        # hits one file; every file failing the same way is the signature
        # of a template mismatch (changed model/optimizer config on
        # resume). Silently fresh-starting here would exit 0 AND let
        # retention delete the — probably fine — checkpoints as new epochs
        # save: abort loudly instead, and let the operator fix the config
        # or clear the dir deliberately.
        raise ckpt.CheckpointCorruptError(
            f"all {len(paths)} checkpoint(s) in {ckpt_dir} failed to "
            "restore — refusing to fresh-start over them (a changed "
            "model/optimizer config on resume fails exactly like this; "
            "fix the config, or clear the checkpoint dir / drop "
            "--from-checkpoint to deliberately start over)"
        )
    return None


def _write_resume_record(
    metrics, epoch: int, path: str, manifest: dict | None, mesh,
    zero_shards_to: int, corrupt: int, restored: Any,
) -> None:
    if metrics is None:
        return
    topo = mesh_topology(mesh)
    record: dict = {
        "kind": "resume",
        "epoch": epoch,
        "to_devices": topo["device_count"],
        "to_mesh": ",".join(f"{a}={s}" for a, s in topo["mesh_shape"].items()),
        "path": path,
        "corrupt_skipped": corrupt,
        "strategy": _reshard_strategy(restored, zero_shards_to),
    }
    if manifest is not None:
        record["from_devices"] = int(manifest.get("device_count", 0))
        record["from_mesh"] = ",".join(
            f"{a}={s}" for a, s in manifest.get("mesh_shape", {}).items()
        )
        record["zero_shards_from"] = int(manifest.get("zero_shards", 0))
        cursor = manifest.get("data_cursor")
        if isinstance(cursor, dict):
            # Schema v6: the exact-step data cursor the writer stamped —
            # where the run continues if the trainer validates it
            # (train/trainer.py; a mismatch falls back to epoch replay).
            record["cursor_epoch"] = int(cursor.get("epoch", 0))
            record["cursor_step"] = int(cursor.get("step_in_epoch", 0))
    if zero_shards_to:
        record["zero_shards_to"] = int(zero_shards_to)
    metrics.write(record)


def _reshard_strategy(restored: Any, zero_shards_to: int) -> str:
    """Which placement path the restored opt-state will take: replicate
    (no ZeRO), one jitted host reshard, or the chunked per-row
    redistribution once any leaf exceeds the bounded-HBM cap."""
    if not zero_shards_to:
        return "replicate"
    big = any(
        getattr(leaf, "nbytes", 0) > _BOUNDED_LEAF_BYTES
        for leaf in jax.tree_util.tree_leaves(restored.opt_state)
    )
    return "chunked-redistribute" if big else "host-reshard"


# ---------------------------------------------------------------------------
# Bounded retry + backoff (resume side)
# ---------------------------------------------------------------------------


def with_retries(fn, *, what: str, retries: int = 3, backoff_s: float = 0.5, logger=None):
    """Run ``fn`` with up to ``retries`` retries on Exception, sleeping a
    deterministic exponential backoff (``backoff_s * 2^attempt``) between
    attempts — the resume-side absorber for transiently wedged backend init
    and device placement. The final failure re-raises unchanged."""
    log = logger or run_logger()
    for attempt in range(retries + 1):
        try:
            return fn()
        except Exception as e:
            if attempt >= retries:
                raise
            delay = backoff_s * (2 ** attempt)
            log.warning(
                "%s failed (attempt %d/%d: %s) — retrying in %.1f s",
                what, attempt + 1, retries + 1, e, delay,
            )
            time.sleep(delay)


def checked_place(state: Any, mesh, *, zero_optimizer: bool = False, fsdp: bool = False):
    """``place_state_on_mesh`` behind the ``MPT_FAULT_DEVICE_PUT_N`` gate —
    the injectable placement the resume path retries through
    ``with_retries`` (placement is idempotent: a retried device_put simply
    re-places the same host arrays)."""
    if fault_countdown("MPT_FAULT_DEVICE_PUT_N"):
        raise RuntimeError("injected fault: device_put failed (MPT_FAULT_DEVICE_PUT_N)")
    return place_state_on_mesh(state, mesh, zero_optimizer=zero_optimizer, fsdp=fsdp)


# ---------------------------------------------------------------------------
# Preemption watchdog
# ---------------------------------------------------------------------------


class PreemptionWatchdog:
    """The trainer's unified stop-signal poll: SIGTERM/SIGINT (via the
    ``PreemptionGuard``), the ``MPT_PREEMPT_FILE`` sentinel, and repeated
    health signals from ``obs/`` (straggler-beat streaks, non-finite-grad
    streaks). The first observed reason writes ONE ``kind="fault"`` record
    and latches — the trainer then stops at the next safe boundary exactly
    like a SIGTERM preemption (save, clean exit, auto-resume).

    Streak thresholds of 0 disable that trigger (the loss sentinel already
    aborts hard on a NaN loss; opting a run into preempt-on-streak is a
    fleet-policy decision, not a default)."""

    def __init__(
        self,
        guard,
        *,
        preempt_file: str = "",
        straggler_beats: int = 0,
        nonfinite_steps: int = 0,
        heartbeat=None,
        health=None,
        metrics=None,
        logger=None,
        injector=None,
    ):
        self.guard = guard
        self.preempt_file = preempt_file or os.environ.get("MPT_PREEMPT_FILE", "")
        self.straggler_beats = int(straggler_beats)
        self.nonfinite_steps = int(nonfinite_steps)
        self.heartbeat = heartbeat
        self.health = health
        self.metrics = metrics
        self.log = logger or run_logger()
        self.injector = injector  # FaultInjector (MPT_FAULT_PREEMPT_AT_STEP)
        self.fired_reason: str | None = None
        self.fired_detail: str = ""
        self.fired_streak: int | None = None

    def _poll(self) -> tuple[str, str, int | None] | None:
        if self.guard is not None and self.guard.triggered:
            return "sigterm", "preemption signal received", None
        if self.preempt_file and os.path.exists(self.preempt_file):
            return "preempt_file", f"sentinel {self.preempt_file} exists", None
        if self.injector is not None and self.injector.preempt_fired:
            return (
                "injected_preempt",
                f"MPT_FAULT_PREEMPT_AT_STEP={self.injector.preempt_at_step}",
                None,
            )
        if (
            self.straggler_beats > 0
            and self.heartbeat is not None
            and getattr(self.heartbeat, "straggler_streak", 0) >= self.straggler_beats
        ):
            return (
                "straggler_streak",
                f"{self.heartbeat.straggler_streak} consecutive straggler beats",
                self.heartbeat.straggler_streak,
            )
        if (
            self.nonfinite_steps > 0
            and self.health is not None
            and getattr(self.health, "nonfinite_grad_streak", 0) >= self.nonfinite_steps
        ):
            return (
                "nonfinite_grads",
                f"{self.health.nonfinite_grad_streak} consecutive non-finite grad norms",
                self.health.nonfinite_grad_streak,
            )
        return None

    def should_stop(self, epoch: int | None = None, step: int | None = None) -> bool:
        """Poll every trigger; latch, record, and warn on the first firing.
        Cheap when nothing fires: a flag read plus (with a sentinel
        configured) one stat()."""
        if self.fired_reason is not None:
            return True
        hit = self._poll()
        if hit is None:
            return False
        self.fired_reason, self.fired_detail, self.fired_streak = hit
        record: dict = {"kind": "fault", "reason": self.fired_reason, "detail": self.fired_detail}
        if epoch is not None:
            record["epoch"] = epoch
        if step is not None:
            record["step"] = step
        if self.fired_streak is not None:
            record["streak"] = self.fired_streak
        if self.metrics is not None:
            self.metrics.write(record)
        self.log.warning(
            "preemption watchdog: %s (%s) — stopping at the next safe "
            "boundary, saving, and exiting cleanly for auto-resume",
            self.fired_reason, self.fired_detail,
        )
        return True


# ---------------------------------------------------------------------------
# Bad-step rollback policy (ISSUE 10: --bad-step-policy rollback)
# ---------------------------------------------------------------------------


class RollbackLimitError(RuntimeError):
    """More in-process rollbacks than ``--max-rollbacks`` allows — the run
    is not converging past the bad region, so it aborts loudly with the
    full ``kind="rollback"`` trail in the metrics stream."""


class RollbackPolicy:
    """Host-side governor deciding WHEN ``--bad-step-policy rollback``
    restores the last good checkpoint (the trainer does the restoring,
    in-process, via ``restore_latest`` — no process death).

    Two triggers, both computed from globally-reduced per-step values
    (the count-weighted global loss and the all-parameter grad norm), so
    every host reaches the identical verdict at the identical step:

    - ``nonfinite_steps`` CONSECUTIVE steps with a non-finite loss/grad
      norm (a diverged update poisons the params, so every later step
      stays non-finite — the streak is the detection delay, not a retry);
    - ``loss_drift`` > 0: the loss exceeds ``loss_drift`` × the run's own
      warmup baseline (the mean of the first ``drift_warmup`` finite
      losses) — the same warmup-baseline semantics as the SLO monitor's
      ``drift:`` rules (obs/monitor.py), catching a spike that never goes
      NaN but has clearly left the run's normal.
    """

    def __init__(
        self,
        *,
        nonfinite_steps: int = 2,
        loss_drift: float = 0.0,
        drift_warmup: int = 5,
    ):
        self.nonfinite_steps = max(1, int(nonfinite_steps))
        self.loss_drift = float(loss_drift)
        self.drift_warmup = max(1, int(drift_warmup))
        self.nonfinite_streak = 0
        self.baseline: list[float] = []

    def observe(self, loss: float, grad_norm: float | None) -> str | None:
        """Feed one step's host-read metrics; returns the trigger reason
        (``"nonfinite_streak"`` / ``"loss_drift"``) or None."""
        import math

        finite = math.isfinite(loss) and (
            grad_norm is None or math.isfinite(grad_norm)
        )
        if not finite:
            self.nonfinite_streak += 1
            if self.nonfinite_streak >= self.nonfinite_steps:
                return "nonfinite_streak"
            return None
        self.nonfinite_streak = 0
        if self.loss_drift > 0:
            if len(self.baseline) < self.drift_warmup:
                # The first observations ARE the baseline (SLO drift
                # semantics): the policy only judges once the run has
                # defined "normal".
                self.baseline.append(loss)
                return None
            base = sum(self.baseline) / len(self.baseline)
            if base > 0 and loss / base > self.loss_drift:
                return "loss_drift"
        return None

    def after_rollback(self) -> None:
        """Re-arm after a restore: the streak resets (the restored state
        is good); the warmup baseline is KEPT — it describes the run's
        normal, which a rollback does not change."""
        self.nonfinite_streak = 0


# ---------------------------------------------------------------------------
# In-process fault injection (the trainer-side half of tools/inject_faults.py)
# ---------------------------------------------------------------------------


class FaultInjector:
    """Deterministic in-process chaos, armed by the ``MPT_FAULT_*`` env
    gates (``utils/env.py FAULT_GATES``), inert otherwise:

    - ``MPT_FAULT_KILL_AT_STEP=n``: SIGKILL this process right after its
      n-th completed train step — a hard crash with the async checkpoint
      writer possibly mid-write, exactly what the atomic tmp+rename
      discipline must survive;
    - ``MPT_FAULT_DELAY_STEP_MS=m`` (+ ``MPT_FAULT_DELAY_PROCESS=k``,
      ``MPT_FAULT_DELAY_AFTER_STEP=j``): sleep m ms inside every timed
      step (on process k only, if set; only after the first j clean steps,
      if set) — a fake straggler the heartbeat/watchdog stack must flag,
      appearing mid-run when j > 0 so the SLO monitor's warmup-baseline
      drift rules (obs/monitor.py) see a clean "normal" first.
    - ``MPT_FAULT_NONFINITE_AT_STEP=n``: poison the n-th train batch
      (1-based, counted across epochs) with NaN pixels so that step's
      loss/grad norm go non-finite — announced with a ``kind="fault"``
      record BEFORE the step runs, so the ``--bad-step-policy``
      skip/rollback paths are testable without a hand-tuned poisoned
      learning rate. Streaming float-input path only (uint8 batches
      cannot carry a NaN; the device-cache path feeds indices).
    - ``MPT_FAULT_PREEMPT_AT_STEP=n``: behave as if a preemption notice
      arrived right after the n-th completed train step — a deterministic
      mid-epoch stop (the watchdog polls ``preempt_fired``) exercising
      the dirty-save + exact-step-resume path without racing a signal.
    """

    def __init__(self, metrics=None):
        self.kill_at_step = env_int("MPT_FAULT_KILL_AT_STEP", 0)
        self.delay_ms = env_int("MPT_FAULT_DELAY_STEP_MS", 0)
        self.delay_process = env_int("MPT_FAULT_DELAY_PROCESS", -1)
        self.delay_after = env_int("MPT_FAULT_DELAY_AFTER_STEP", 0)
        self.dcn_delay_ms = env_int("MPT_FAULT_DCN_DELAY_MS", 0)
        self.nonfinite_at_step = env_int("MPT_FAULT_NONFINITE_AT_STEP", 0)
        self.preempt_at_step = env_int("MPT_FAULT_PREEMPT_AT_STEP", 0)
        self.preempt_fired = False
        self.metrics = metrics
        self._steps = 0
        self._delay_calls = 0
        self._batches = 0

    @property
    def active(self) -> bool:
        return bool(
            self.kill_at_step or self.delay_ms or self.dcn_delay_ms
            or self.nonfinite_at_step or self.preempt_at_step
        )

    def poison_batches(self, batches, epoch: int | None = None):
        """Wrap a host-batch iterator, NaN-poisoning the images of the
        armed batch (1-based, counted across epochs — the injector
        instance carries the count between epochs). The fault record is
        written BEFORE the poisoned batch is yielded, so the stream always
        shows the injection ahead of its non-finite step records."""
        import numpy as np

        for images, labels in batches:
            self._batches += 1
            if self._batches == self.nonfinite_at_step:
                if self.metrics is not None:
                    self.metrics.write(
                        {
                            "kind": "fault",
                            "reason": "injected_nonfinite",
                            "detail": (
                                f"MPT_FAULT_NONFINITE_AT_STEP="
                                f"{self.nonfinite_at_step}"
                            ),
                            **({"epoch": epoch} if epoch is not None else {}),
                        }
                    )
                run_logger().warning(
                    "fault injection: NaN-poisoning train batch %d "
                    "(MPT_FAULT_NONFINITE_AT_STEP)", self._batches,
                )
                images = np.full_like(images, np.nan)
            yield images, labels

    def after_step(self, epoch: int, step: int) -> None:
        """Count completed steps; fire whichever step-count gate is armed.
        The kill gate announces itself (the metrics stream is
        line-buffered, so the record lands) and SIGKILLs — no cleanup, no
        drain: this is the crash, not a shutdown. The preempt gate only
        latches a flag the watchdog polls at the next step boundary."""
        if not (self.kill_at_step or self.preempt_at_step):
            return
        self._steps += 1
        if (
            self.preempt_at_step
            and not self.preempt_fired
            and self._steps >= self.preempt_at_step
        ):
            self.preempt_fired = True
            run_logger().warning(
                "fault injection: simulated preemption notice after train "
                "step %d (epoch %d step %d)", self._steps, epoch, step,
            )
        if not self.kill_at_step or self._steps < self.kill_at_step:
            return
        if self.metrics is not None:
            self.metrics.write(
                {
                    "kind": "fault",
                    "reason": "injected_kill",
                    "epoch": epoch,
                    "step": step,
                    "detail": f"MPT_FAULT_KILL_AT_STEP={self.kill_at_step}",
                }
            )
        run_logger().warning(
            "fault injection: SIGKILL at train step %d (epoch %d step %d)",
            self._steps, epoch, step,
        )
        os.kill(os.getpid(), signal.SIGKILL)

    def maybe_delay(self) -> None:
        """The straggler fake — called inside the step's timed region so
        heartbeats attribute the delay to this host's step time. With
        ``MPT_FAULT_DELAY_AFTER_STEP`` the first j steps stay clean."""
        if self.delay_ms <= 0:
            return
        self._delay_calls += 1
        if self._delay_calls <= self.delay_after:
            return
        if self.delay_process < 0 or process_index() == self.delay_process:
            time.sleep(self.delay_ms / 1e3)

    def maybe_dcn_delay(self, hierarchical: bool) -> None:
        """``MPT_FAULT_DCN_DELAY_MS`` — the slow-DCN-link fake (ISSUE 15):
        stretch every step by the injected cross-pod latency, but ONLY on
        hierarchical (pods > 1) runs — a flat mesh has no DCN phase, so
        the gate correctly does nothing there (the property the overlap
        chaos test pins). Host-side stand-in: the device step is one fused
        program, so the delay lands in the timed region like a real slow
        second-stage reduction would, and heartbeats/step records carry
        it."""
        if self.dcn_delay_ms <= 0 or not hierarchical:
            return
        time.sleep(self.dcn_delay_ms / 1e3)
