"""Host-side trace spans in Chrome trace-event format (obs tentpole part 1).

The repo already had two timing surfaces: per-epoch wall-clock (≙ the
reference's ``MPI.Wtime`` pairs, ``main.py:145,158``) and the XLA device
trace (``--profile-dir``). Neither shows WHERE host time goes inside a
step — decode wait vs dispatch vs checkpoint stall. ``Tracer`` fills that
gap: the drivers wrap their phases in ``span("ingest")`` / ``span("step")``
/ ``span("checkpoint")`` / …, and the run writes one Chrome-trace JSON per
process, loadable in ``chrome://tracing`` or Perfetto.

Each span also enters ``jax.profiler.TraceAnnotation(name)``, so when an
XLA trace is being captured at the same time (``--profile-dir``) the host
spans appear on the profiler's host timeline with the SAME names — the
overlay recipe in ``docs/OBSERVABILITY.md``.

One clock under both. Span times are ``time.perf_counter()`` minus an origin
read at construction, and ``close()`` writes that origin down as
``"otherData": {"t0_perf_counter_s", "t0_unix_ns"}`` (both read back to
back): ``ts / 1e6 + t0_perf_counter_s`` puts a span on the clock of anything
else in the process that reads ``perf_counter`` (a benchmark harness's
window), and ``ts * 1e3 + t0_unix_ns`` on the wall clock, which is the
profiler's — so the file lies on an XLA trace without an anchor event.

Layers below the drivers reach the run's tracer through ``current()``: the
trainer installs its tracer with ``use(tracer)`` for the length of the run,
and the input pipeline (``data/pipeline.py``, ``trainer.device_prefetch``)
opens its spans on whatever is installed. Nothing installed, or a tracer
without a path, is the same inert object: it records nothing, opens no
profiler annotation and ``close`` writes nothing. What it still does is read
the clock at both ends of a span, because a span is also how a caller times
a region: ``end`` returns the seconds it measured and ``span`` yields an
object whose ``seconds`` holds them once the block has closed, on either
path — the trainer's ``data_wait_ms`` / ``step_ms`` are the ``ingest`` and
``step`` spans' own durations, not a second clock pair around them.
"""

from __future__ import annotations

import json
import os
import threading
import time
from contextlib import contextmanager
from typing import Any, Mapping


def _trace_annotation(name: str):
    """A ``jax.profiler.TraceAnnotation`` for ``name``, or None when jax (or
    its profiler) is unavailable — the tracer itself never requires jax."""
    try:
        import jax

        return jax.profiler.TraceAnnotation(name)
    except Exception:
        return None


def trace_path(path: str, process: int, process_count: int) -> str:
    """Per-process trace file: the given path verbatim for a single-process
    run, ``name.pN.json``-style otherwise (every process writes its own
    events; merge by concatenating ``traceEvents`` — pids differ)."""
    if process_count <= 1:
        return path
    root, ext = os.path.splitext(path)
    return f"{root}.p{process}{ext or '.json'}"


class Timed:
    """What ``Tracer.span`` yields: ``seconds`` is how long the block took and
    ``ended_us`` when it closed (the tracer's clock, as an event's ``ts``),
    both set when it closes."""

    __slots__ = ("seconds", "ended_us")

    def __init__(self):
        self.seconds = 0.0
        self.ended_us = 0.0


class Tracer:
    """Chrome-trace-event span recorder. Thread-safe (the async checkpointer
    and loader threads may span concurrently); events buffer in memory and
    ``close()`` writes one valid JSON object — the trace of an aborted run
    is whatever ``close()`` was reached with (the drivers close on their
    failure paths too)."""

    def __init__(self, path: str | None, clock=time.perf_counter):
        self.path = path or None
        self._clock = clock
        # The origin on both clocks, read back to back: ``close`` writes them
        # down so the spans can be laid on any other timeline of the run.
        self._t0 = clock()
        self._t0_unix_ns = time.time_ns()
        self._events: list[dict] = []
        self._once: set[tuple] = set()  # instants already written with once=True
        self._lock = threading.Lock()
        self._pid: int | None = None
        self._closed = False

    @property
    def enabled(self) -> bool:
        return self.path is not None and not self._closed

    def _process_index(self) -> int:
        if self._pid is None:
            from mpi_pytorch_tpu.utils.logging import process_index

            self._pid = process_index()
        return self._pid

    def _now_us(self) -> float:
        return (self._clock() - self._t0) * 1e6

    def begin(self, name: str, cat: str = "host"):
        """Open a span manually — for regions that span control-flow a
        ``with`` block can't wrap cleanly (the trainer's compile branches).
        Returns a token for ``end``. Disabled, the token carries the clock
        reading alone: nothing is recorded and no annotation opens."""
        ann = _trace_annotation(name) if self.enabled else None
        if ann is not None:
            ann.__enter__()
        return (name, cat, self._now_us(), ann)

    def end(self, token, args: Mapping[str, Any] | None = None) -> float:
        """Close the span ``begin`` opened and return the seconds it lasted
        (enabled or not), so a caller that wants the number does not time
        the region a second time."""
        name, cat, ts, ann = token
        dur = self._now_us() - ts
        # Balance the TraceAnnotation even when the tracer was closed
        # mid-span (failure-path flush) — the event is dropped, the
        # profiler's host annotation stack must not be.
        if ann is not None:
            ann.__exit__(None, None, None)
        if self.enabled:
            event = {
                "name": name,
                "cat": cat,
                "ph": "X",  # complete event: ts+dur; nesting renders from overlap
                "ts": round(ts, 3),  # Chrome trace timestamps are microseconds
                "dur": round(dur, 3),
                "pid": self._process_index(),
                "tid": threading.get_ident() % 2**31,
            }
            if args:
                event["args"] = dict(args)
            with self._lock:
                self._events.append(event)
        return dur / 1e6

    @contextmanager
    def span(self, name: str, cat: str = "host", args: Mapping[str, Any] | None = None):
        """``with tracer.span("ingest") as timed: ...`` — the primary API.
        ``timed.seconds`` is the span's length once the block has closed.
        ``args`` is read when the span closes, so the block may fill a dict
        it passed in with what it learned (a batch's byte count, a decoder's
        counters)."""
        timed = Timed()
        token = self.begin(name, cat)
        try:
            yield timed
        finally:
            timed.seconds = self.end(token, args)
            timed.ended_us = token[2] + timed.seconds * 1e6

    def ms_since(self, timed: Timed) -> float:
        """Milliseconds from the close of the span that yielded ``timed`` to
        now: what a finished piece of work then waited for."""
        return (self._now_us() - timed.ended_us) / 1e3

    def instant(
        self, name: str, args: Mapping[str, Any] | None = None, *, once: bool = False
    ) -> None:
        """A zero-duration marker (anomalies, heartbeats) on the timeline.
        ``once``: a marker with this name and these args is written the first
        time only (a decision made at every trace of one shape)."""
        if not self.enabled:
            return
        if once:
            key = (name, tuple(sorted((args or {}).items())))
            with self._lock:
                if key in self._once:
                    return
                self._once.add(key)
        event = {
            "name": name,
            "cat": "marker",
            "ph": "i",
            "s": "p",  # process-scoped marker line
            "ts": round(self._now_us(), 3),
            "pid": self._process_index(),
            "tid": threading.get_ident() % 2**31,
        }
        if args:
            event["args"] = dict(args)
        with self._lock:
            self._events.append(event)

    def close(self) -> str | None:
        """Write the trace JSON (idempotent); returns the written path."""
        if self.path is None or self._closed:
            return None
        self._closed = True
        try:
            import jax

            procs, pid = jax.process_count(), jax.process_index()
        except Exception:
            procs, pid = 1, 0
        out = trace_path(self.path, pid, procs)
        os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
        with self._lock, open(out, "w") as f:
            json.dump(
                {
                    "traceEvents": self._events,
                    "displayTimeUnit": "ms",
                    "otherData": {
                        "t0_perf_counter_s": self._t0,
                        "t0_unix_ns": self._t0_unix_ns,
                    },
                },
                f,
                separators=(",", ":"),
            )
        return out


# The process-wide current tracer: inert until a driver installs its own.
_INERT = Tracer(None)
_current: Tracer = _INERT


def current() -> Tracer:
    """The tracer of the run in progress — the inert one when no driver
    installed any, so a layer below the drivers opens its spans
    unconditionally and pays nothing outside a traced run."""
    return _current


@contextmanager
def use(tracer: Tracer):
    """Install ``tracer`` as ``current()`` for the length of the block (one
    run of a driver); the previous one is restored on the way out."""
    global _current
    previous, _current = _current, tracer
    try:
        yield tracer
    finally:
        _current = previous
