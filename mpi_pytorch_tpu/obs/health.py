"""Per-step training-health metrics + the non-finite-loss sentinel (obs
tentpole part 2).

The per-epoch record answers "how fast was the epoch"; these records answer
"what is the step doing RIGHT NOW": data-wait vs device-compute ms, loss,
global gradient norm, live HBM bytes, and a recompile counter — the per-phase
instrumentation that turns "it's slow" into an actionable bottleneck
(Awan et al., arXiv:1810.11112, and SURVEY §5).

Costs are explicit: per-step records require one host sync per step (the
loss must be read back), so ``step_metrics`` defaults off and benchmarks
leave it off. The NaN/Inf sentinel defaults ON — it piggybacks on values
the trainer already reads (the epoch loss; the per-step loss only when step
telemetry is on), and training on a NaN'd loss is never the right outcome:
it writes a ``kind="anomaly"`` diagnostic record and aborts cleanly
(``NonFiniteLossError``) instead of burning an epoch on garbage.
"""

from __future__ import annotations

import math
from typing import Any, Mapping

# Backend compiles observed process-wide since the listener was installed.
# jax.monitoring has no unregister, so ONE module-level listener increments
# this global forever and StepHealth instances read deltas against their
# epoch baseline — repeated train() calls in one process can't stack hooks.
_compile_count = 0
_listener_installed = False

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

# Registry gauges StepHealth publishes — ONLY when step telemetry is on.
# config.validate_config imports this set to reject SLO rules over these
# names without --step-metrics (the rule would silently never evaluate);
# keeping the set next to the registrations means a new gauge cannot
# escape that check.
STEP_GAUGES = (
    "train/loss",
    "train/grad_norm",
    "train/recompiles",
    "train/nonfinite_grad_streak",
    "train/sync_ms",
)


def ensure_compile_listener() -> None:
    """Arm the process-wide backend-compile counter (idempotent). Callers
    that assert zero steady-state compiles — serving after warmup, tests —
    arm it first, record ``compile_count()`` as a baseline, and read the
    delta later; the listener itself is installed at most once."""
    global _listener_installed
    if _listener_installed:
        return
    import jax

    def _on_event(name: str, _secs: float, **_kw) -> None:
        global _compile_count
        if name == _COMPILE_EVENT:
            _compile_count += 1

    jax.monitoring.register_event_duration_secs_listener(_on_event)
    _listener_installed = True


# Backwards-compatible private alias (pre-serve callers).
_ensure_compile_listener = ensure_compile_listener


def compile_count() -> int:
    """Backend compiles observed so far (0 until the listener is armed)."""
    return _compile_count


def device_bytes_in_use() -> int | None:
    """Live HBM bytes on this process's FULLEST local device (the one that
    runs out first — not just device 0, which says nothing about its
    siblings), or None where the backend has no ``memory_stats`` (CPU) —
    the record carries null rather than a confident fake zero."""
    import jax

    stats = [d.memory_stats() for d in jax.local_devices()]
    if not all(stats):
        return None
    return max(int(s["bytes_in_use"]) for s in stats)


class NonFiniteLossError(RuntimeError):
    """Raised by the sentinel AFTER the diagnostic record is written."""


class StepHealth:
    """Per-step health records (``kind="step"``) + the non-finite sentinel.

    ``on_step`` is a no-op unless ``step_metrics`` is on — the default train
    loop keeps its async dispatch; ``check_epoch`` runs regardless (the
    epoch loss is already a host float there, so the sentinel is free)."""

    def __init__(
        self,
        metrics,
        *,
        step_metrics: bool = False,
        nan_sentinel: bool = True,
        tracer=None,
        registry=None,
    ):
        self.metrics = metrics
        self.enabled = bool(step_metrics)
        self.nan_sentinel = bool(nan_sentinel)
        self.tracer = tracer
        # Live-telemetry publication (obs/metrics.MetricsRegistry): per-step
        # loss/grad-norm/recompile/streak gauges the SLO monitor reads.
        # Only advances when step telemetry is on — same gate as the
        # records, so registry publication never adds a host sync. Gauges
        # pre-bound (the registry's own hot-path guidance), and up front
        # rather than on first use: the cross-host metrics merge flattens
        # by name set, so registration must not depend on what a given
        # host happened to observe.
        self.registry = registry
        if registry is not None:
            self._g_loss = registry.gauge("train/loss")
            self._g_grad_norm = registry.gauge("train/grad_norm")
            self._g_recompiles = registry.gauge("train/recompiles")
            self._g_nonfinite = registry.gauge("train/nonfinite_grad_streak")
        self._baseline = 0
        # Gradient-sync telemetry (schema v2, optional): set by the trainer
        # when --grad-sync-buckets is on. overlap_frac is the static
        # bucket-plan estimate (train/step.py bucket_overlap_frac) stamped
        # onto every step record; sync_ms is a per-step measured value where
        # a caller has one (host code cannot decompose a fused device step,
        # so the trainer leaves it unset — records carry it only from
        # tooling that measures it by A/B).
        self.overlap_frac: float | None = None
        # Schema v11 (ISSUE 15): the cross-pod (DCN) overlap estimate of a
        # hierarchical bucket plan — stamped only on --mesh-pods > 1 runs,
        # so flat-mesh records stay byte-identical to prior generations.
        self.dcn_overlap_frac: float | None = None
        # Consecutive steps whose GRADIENT norm was non-finite while the
        # loss stayed finite — the slow-corruption signal the preemption
        # watchdog (train/elastic.py) can act on before the loss itself
        # goes NaN and the sentinel aborts. Only advances when step
        # telemetry is on (the norm is a host float there anyway).
        self.nonfinite_grad_streak = 0
        if self.enabled:
            _ensure_compile_listener()
            self._baseline = _compile_count

    def set_sync(
        self,
        *,
        overlap_frac: float | None = None,
        dcn_overlap_frac: float | None = None,
    ) -> None:
        """Arm the grad-sync fields on subsequent step records (trainer,
        after the bucket plan is known). ``dcn_overlap_frac`` is the
        hierarchical (--mesh-pods) twin: what fraction of cross-pod sync
        bytes are issued before the final bucket (train/step.py
        hier_dcn_overlap_frac)."""
        self.overlap_frac = overlap_frac
        self.dcn_overlap_frac = dcn_overlap_frac

    def start_epoch(self) -> None:
        """Re-arm the recompile counter: compiles BETWEEN epochs (first-call
        validation/eval jits) are expected, so each epoch's records count
        compiles since the epoch began — any nonzero value mid-epoch is the
        silent-recompile smell this field exists to surface."""
        if self.enabled:
            self._baseline = _compile_count

    def on_step(
        self,
        epoch: int,
        step: int,
        m: Mapping[str, Any],
        data_wait_s: float | None = None,
        step_s: float | None = None,
        sync_ms: float | None = None,
        skipped: int | None = None,
        steps_skipped: int | None = None,
    ) -> None:
        if not self.enabled:
            return
        loss = float(m["loss"])
        grad_norm = float(m["grad_norm"]) if "grad_norm" in m else None
        record = {
            "kind": "step",
            "epoch": epoch,
            "step": step,
            "loss": loss,
            "grad_norm": grad_norm,
            "data_wait_ms": None if data_wait_s is None else round(data_wait_s * 1e3, 3),
            "step_ms": None if step_s is None else round(step_s * 1e3, 3),
            "recompiles": _compile_count - self._baseline,
            "hbm_bytes": device_bytes_in_use(),
        }
        # Schema-v2 grad-sync fields only on runs that configured them —
        # records from lever-less runs stay byte-identical to v1.
        if self.overlap_frac is not None:
            record["overlap_frac"] = self.overlap_frac
        # v11: hierarchical runs only (same absent-when-off discipline).
        if self.dcn_overlap_frac is not None:
            record["dcn_overlap_frac"] = self.dcn_overlap_frac
        if sync_ms is not None:
            record["sync_ms"] = round(sync_ms, 3)
        # Schema-v6 bad-step-policy fields (--bad-step-policy skip only):
        # the trainer passes them when the policy is armed.
        if skipped is not None:
            record["skipped"] = int(skipped)
        if steps_skipped is not None:
            record["steps_skipped"] = int(steps_skipped)
        self.metrics.write(record)
        if grad_norm is not None:
            self.nonfinite_grad_streak = (
                0 if math.isfinite(grad_norm) else self.nonfinite_grad_streak + 1
            )
        if self.registry is not None:
            self._g_loss.set(loss)
            if grad_norm is not None:
                self._g_grad_norm.set(grad_norm)
            self._g_recompiles.set(record["recompiles"])
            self._g_nonfinite.set(self.nonfinite_grad_streak)
            if sync_ms is not None:
                # train/sync_ms intentionally NOT pre-registered: no
                # trainer path passes sync_ms today (schema-v2 note), so
                # the name would be a permanently-null gauge; any future
                # caller passes it from step 0 on every host alike.
                self.registry.gauge("train/sync_ms").set(sync_ms)
        self._sentinel(epoch, step, loss, grad_norm)

    def on_scan_epoch(
        self, epoch: int, m: Mapping[str, Any], steps_skipped_base: int = 0
    ) -> None:
        """Per-step records for the scan-epoch mode, post-hoc from the
        ``[n_steps]`` metric arrays (the scan ran entirely on device, so
        there is no per-step host timing to report — those fields are
        null; loss/grad-norm/recompiles are real). ``steps_skipped_base``
        is the run's skip total BEFORE this epoch, so scan-mode records
        carry the same run-cumulative ``steps_skipped`` the per-step path
        reports (the schema's contract)."""
        if not self.enabled:
            return
        import numpy as np

        loss_v = np.asarray(m["loss"], np.float64)
        norm_v = (
            np.asarray(m["grad_norm"], np.float64) if "grad_norm" in m else None
        )
        skip_v = (
            np.asarray(m["skipped"], np.int64) if "skipped" in m else None
        )
        skipped_total = int(steps_skipped_base)
        for step in range(loss_v.shape[0]):
            record = {
                "kind": "step",
                "epoch": epoch,
                "step": step,
                "loss": float(loss_v[step]),
                "grad_norm": None if norm_v is None else float(norm_v[step]),
                "data_wait_ms": None,
                "step_ms": None,
                "recompiles": _compile_count - self._baseline,
                "hbm_bytes": device_bytes_in_use(),
            }
            if skip_v is not None:
                skipped_total += int(skip_v[step])
                record["skipped"] = int(skip_v[step])
                record["steps_skipped"] = skipped_total
            self.metrics.write(record)
            self._sentinel(
                epoch, step, float(loss_v[step]),
                None if norm_v is None else float(norm_v[step]),
            )

    def check_epoch(self, epoch: int, loss: float) -> None:
        """Epoch-granularity sentinel — the check every run gets for free."""
        self._sentinel(epoch, None, float(loss), None)

    def _sentinel(
        self, epoch: int, step: int | None, loss: float, grad_norm: float | None
    ) -> None:
        if not self.nan_sentinel or math.isfinite(loss):
            return
        record = {
            "kind": "anomaly",
            "reason": "nonfinite_loss",
            "epoch": epoch,
            "step": step,
            "loss": loss,
            "grad_norm": grad_norm,
        }
        self.metrics.write(record)
        if self.tracer is not None:
            self.tracer.instant("nonfinite_loss", args={"epoch": epoch, "step": step})
        from mpi_pytorch_tpu.utils.logging import run_logger

        where = f"epoch {epoch}" + ("" if step is None else f" step {step}")
        run_logger().error(
            "non-finite loss (%s) at %s — aborting instead of training on "
            "garbage (diagnostic kind='anomaly' record written; disable via "
            "--nan-sentinel false)", loss, where,
        )
        raise NonFiniteLossError(
            f"non-finite loss {loss} at {where}; see the kind='anomaly' "
            "metrics record for diagnostics"
        )
