"""Declarative SLO rules over the live metrics registry (ISSUE 8).

Failures want IN-RUN detection: a wedged backend discovered post-hoc has
already cost the run, and a serve p99 regression is otherwise only visible
after ``report_run.py`` renders the stream. The monitor
closes that loop: rules are evaluated against ``MetricsRegistry``
snapshots on the driver's own cadence (per step in the trainer, per flush
in the serve completion loop — no extra thread, no extra sync), and a
breach emits a ``kind="alert"`` record (schema v4) plus pluggable actions.

Rule syntax (``--slo-rules``; rules separated by ``;``, options by
whitespace)::

    [rate:|drift:]METRIC OP THRESHOLD [for=N] [warmup=K] [name=ID]
                                      [severity=warn|critical]
                                      [action=log,metric,preempt]

- ``METRIC`` — a registry name, with ``:p50/:p95/:p99/:mean/:count``
  selecting a histogram statistic (``obs/metrics.resolve_metric``).
- ``OP`` — one of ``> >= < <=`` against ``THRESHOLD`` (a float).
- ``rate:`` — evaluate the metric's per-second DELTA between evaluations
  (queue-reject rate over a counter).
- ``drift:`` — evaluate the metric's RATIO to a warmup baseline: the mean
  of its first ``warmup`` (default 5) non-None evaluations. The
  step-time-drift SLO: ``drift:train/step_ms_last>2.0`` fires when steps
  run 2x slower than the run's own warmup.
- ``for=N`` — require N CONSECUTIVE breaching evaluations (default 1);
  transient spikes don't page.
- ``action`` — any of ``log`` (rank-tagged warning, default), ``metric``
  (increment the ``obs/alerts_fired`` counter — alerts become telemetry
  too), ``preempt`` (write the preemption sentinel file, so the trainer's
  watchdog [train/elastic.py] stops at the next safe boundary: an SLO
  breach feeds the SAME save-and-exit path a scheduler notice does).

A fired rule latches until its condition recovers (one evaluation below
threshold re-arms it) — a sustained breach is one alert, not one per step.

Examples (the SLOs named in docs/OBSERVABILITY.md):

    serve/flush_ms:p99 > 250 for=3 name=serve_p99
    rate:serve/rejected > 5 name=reject_rate severity=critical
    train/recompiles > 0 name=steady_state_compiles
    train/straggler_streak >= 3 name=straggler action=log,preempt
    drift:train/step_ms_last > 2.0 for=2 warmup=5 name=step_drift

Dependency-free (stdlib only): the rules parse in ``config.validate`` and
in tools without a backend.
"""

from __future__ import annotations

import os
import time
from dataclasses import dataclass, field

from mpi_pytorch_tpu.obs.metrics import resolve_metric

_OPS = {
    ">=": lambda v, t: v >= t,
    "<=": lambda v, t: v <= t,
    ">": lambda v, t: v > t,
    "<": lambda v, t: v < t,
}
_SEVERITIES = ("warn", "critical")
_ACTIONS = ("log", "metric", "preempt")
_MODES = ("value", "rate", "drift")


@dataclass
class SLORule:
    """One parsed rule (see the module docstring for the syntax)."""

    name: str
    metric: str
    op: str
    threshold: float
    mode: str = "value"  # value | rate | drift
    for_count: int = 1
    warmup: int = 5  # drift mode: evaluations forming the baseline
    severity: str = "warn"
    actions: tuple = ("log",)

    # --- evaluation state (per-run, owned by the monitor) ---
    streak: int = field(default=0, compare=False)
    fired: bool = field(default=False, compare=False)
    baseline: list = field(default_factory=list, compare=False)
    prev_value: float | None = field(default=None, compare=False)
    prev_t: float | None = field(default=None, compare=False)


def parse_rules(spec: str) -> list[SLORule]:
    """Parse a ``--slo-rules`` string; raises ValueError with the offending
    rule text on any malformed entry (config validation surfaces it)."""
    rules = []
    for chunk in spec.split(";"):
        chunk = chunk.strip()
        if not chunk:
            continue
        rules.append(_parse_rule(chunk))
    names = [r.name for r in rules]
    dupes = {n for n in names if names.count(n) > 1}
    if dupes:
        raise ValueError(f"duplicate SLO rule name(s): {sorted(dupes)}")
    return rules


def _parse_rule(text: str) -> SLORule:
    tokens = text.split()
    if not tokens:
        raise ValueError(f"empty SLO rule in {text!r}")
    # The comparison may arrive as one token ("m>5") or three ("m > 5"):
    # rejoin, then split on the longest matching operator.
    opts = [t for t in tokens if "=" in t and not any(o in t for o in _OPS)]
    expr = "".join(t for t in tokens if t not in opts)
    op = None
    for cand in ("<=", ">=", "<", ">"):  # two-char ops first
        if cand in expr:
            op = cand
            break
    if op is None:
        raise ValueError(
            f"SLO rule {text!r} has no comparison (expected one of "
            f"{sorted(_OPS)})"
        )
    metric, _, thr_text = expr.partition(op)
    metric = metric.strip()
    mode = "value"
    for m in ("rate", "drift"):
        if metric.startswith(m + ":"):
            mode = m
            metric = metric[len(m) + 1 :]
    if not metric:
        raise ValueError(f"SLO rule {text!r} names no metric")
    try:
        threshold = float(thr_text)
    except ValueError:
        raise ValueError(
            f"SLO rule {text!r}: threshold {thr_text!r} is not a number"
        ) from None
    rule = SLORule(name=metric, metric=metric, op=op, threshold=threshold, mode=mode)
    for opt in opts:
        key, _, val = opt.partition("=")
        if key == "for":
            rule.for_count = _positive_int(text, key, val)
        elif key == "warmup":
            rule.warmup = _positive_int(text, key, val)
        elif key == "name":
            rule.name = val
        elif key == "severity":
            if val not in _SEVERITIES:
                raise ValueError(
                    f"SLO rule {text!r}: severity must be one of "
                    f"{_SEVERITIES}, got {val!r}"
                )
            rule.severity = val
        elif key == "action":
            actions = tuple(a for a in val.split(",") if a)
            bad = [a for a in actions if a not in _ACTIONS]
            if bad or not actions:
                raise ValueError(
                    f"SLO rule {text!r}: actions must be from {_ACTIONS}, "
                    f"got {val!r}"
                )
            rule.actions = actions
        else:
            raise ValueError(f"SLO rule {text!r}: unknown option {key!r}")
    if rule.mode == "rate" and rule.op in ("<", "<="):
        # A below-rate rule would fire forever on an idle system — reject
        # the footgun loudly instead of paging on silence.
        raise ValueError(
            f"SLO rule {text!r}: rate: rules must use > or >= (an idle "
            "system has rate 0 and would breach a < rule forever)"
        )
    return rule


def _positive_int(text: str, key: str, val: str) -> int:
    try:
        n = int(val)
    except ValueError:
        n = 0
    if n < 1:
        raise ValueError(f"SLO rule {text!r}: {key}= takes a positive int")
    return n


class SLOMonitor:
    """Evaluate rules against the registry; emit alerts + run actions.

    Driver-cadence, zero threads: the trainer calls ``evaluate()`` per
    step (only when ``--slo-rules`` is set), serve per completed flush.
    Evaluation cost is one ``snapshot()`` plus a handful of float
    compares — host-side, never a device sync.
    """

    def __init__(
        self,
        registry,
        rules: list[SLORule],
        *,
        metrics=None,
        preempt_path: str = "",
        tracer=None,
        logger=None,
        clock=time.monotonic,
        labels: dict | None = None,
    ):
        self.registry = registry
        self.rules = rules
        self.metrics = metrics
        # Static labels merged into every alert record (schema v10):
        # a zoo tenant's monitor passes {"model": <tenant>} so its SLO
        # breaches are attributable per tenant (ISSUE 14). Only
        # schema-known keys should be passed.
        self.labels = dict(labels or {})
        self.preempt_path = preempt_path or os.environ.get("MPT_PREEMPT_FILE", "")
        self.tracer = tracer
        self._logger = logger
        self._clock = clock
        self.alerts_fired = 0
        for rule in rules:
            if rule.mode == "rate":
                # Baseline rate rules at CONSTRUCTION (counter = 0), not
                # at their first evaluation: a burst landing before the
                # first eval (a flood of rejects while the first flush is
                # still in flight) must count as rate, not vanish into
                # the baseline sample.
                rule.prev_value = 0.0
                rule.prev_t = clock()
        if any("metric" in r.actions for r in rules):
            # Register the alert counter UP FRONT, not lazily at first
            # fire: the registry's cross-host merge flattens by metric
            # name set, and a per-host alert (one straggler breaching a
            # drift rule) registering a new metric on that host alone
            # would diverge the exchanged vector widths mid-run.
            self.registry.counter("obs/alerts_fired")

    def _log(self):
        if self._logger is None:
            from mpi_pytorch_tpu.utils.logging import run_logger

            self._logger = run_logger()
        return self._logger

    def evaluate(self, epoch: int | None = None, step: int | None = None) -> list[str]:
        """One evaluation pass; returns the names of rules that FIRED this
        pass (most passes: [])."""
        snap = self.registry.snapshot()
        now = self._clock()
        fired = []
        for rule in self.rules:
            value = self._value(rule, snap, now)
            if value is None:
                continue
            if _OPS[rule.op](value, rule.threshold):
                rule.streak += 1
            else:
                rule.streak = 0
                rule.fired = False  # recovery re-arms the rule
                continue
            if rule.streak >= rule.for_count and not rule.fired:
                rule.fired = True
                self._fire(rule, value, epoch, step)
                fired.append(rule.name)
        return fired

    def _value(self, rule: SLORule, snap, now: float) -> float | None:
        raw = resolve_metric(snap, rule.metric)
        if raw is None:
            return None
        if rule.mode == "value":
            return raw
        if rule.mode == "rate":
            prev_v, prev_t = rule.prev_value, rule.prev_t
            rule.prev_value, rule.prev_t = raw, now
            if prev_v is None or now <= prev_t:
                return None
            return (raw - prev_v) / (now - prev_t)
        # drift: the first `warmup` observations ARE the baseline — the
        # rule only starts judging once the run has defined "normal".
        if len(rule.baseline) < rule.warmup:
            rule.baseline.append(raw)
            return None
        base = sum(rule.baseline) / len(rule.baseline)
        if base <= 0:
            return None
        return raw / base

    def _fire(self, rule: SLORule, value: float, epoch, step) -> None:
        self.alerts_fired += 1
        record = {
            "kind": "alert",
            "rule": rule.name,
            "severity": rule.severity,
            "metric": ("" if rule.mode == "value" else rule.mode + ":") + rule.metric,
            "value": round(float(value), 6),
            "threshold": rule.threshold,
            "streak": rule.streak,
            "action": ",".join(rule.actions),
            **self.labels,
        }
        if epoch is not None:
            record["epoch"] = epoch
        if step is not None:
            record["step"] = step
        if self.metrics is not None:
            self.metrics.write(record)
        if self.tracer is not None:
            self.tracer.instant(
                "alert", args={"rule": rule.name, "value": record["value"]}
            )
        if "metric" in rule.actions:
            self.registry.counter("obs/alerts_fired").inc()
        if "log" in rule.actions or rule.actions == ():
            self._log().warning(
                "SLO alert [%s] %s: %s = %.6g breaches %s %s (streak %d; "
                "actions: %s)",
                rule.severity, rule.name, record["metric"], value, rule.op,
                rule.threshold, rule.streak, record["action"],
            )
        if "preempt" in rule.actions:
            self._preempt(rule, value)

    def _preempt(self, rule: SLORule, value: float) -> None:
        """Write the preemption sentinel: the watchdog's MPT_PREEMPT_FILE
        poll (train/elastic.py) then stops the run at the next safe
        boundary — an SLO breach becomes a clean save-and-exit, not a
        post-mortem."""
        if not self.preempt_path:
            self._log().warning(
                "SLO rule %s requests action=preempt but no preemption "
                "sentinel path is configured (--preempt-file / "
                "MPT_PREEMPT_FILE) — alert recorded, preemption skipped",
                rule.name,
            )
            return
        os.makedirs(os.path.dirname(self.preempt_path) or ".", exist_ok=True)
        with open(self.preempt_path, "w") as f:
            f.write(
                f"slo:{rule.name} value={value:.6g} threshold="
                f"{rule.op}{rule.threshold}\n"
            )
        self._log().warning(
            "SLO rule %s wrote preemption sentinel %s — the watchdog will "
            "stop at the next safe boundary", rule.name, self.preempt_path,
        )
