"""One clock under the program's spans and the device trace.

The program writes every span to ``spans.json`` on its own clock and the
origin of that clock beside them (``otherData``: ``t0_perf_counter_s``,
``t0_unix_ns``; obs/trace.py). ``perf_counter`` is the harness's clock too
(same process), so ``in_window`` cuts the spans to the measured window. The
device trace counts from its own ``profile_start_time``, a wall-clock
nanosecond it carries, so ``offset_ns`` — what to add to a ``spans.json``
time to land on the trace's clock — is the difference of the two origins
written down, with no event matched to any other: on the chip it agreed with
the program's own annotations in the trace's host plane to 4 us in five
traces (PR 24; benchmark/tests/test_scoped_trace.py holds one of them to
it). ``spans.json`` holds every span of the run and cannot overflow, which
the profiler's host buffer does in the streaming cell.
"""

from __future__ import annotations

import functools
import json

from benchmark.trace import wire


@functools.lru_cache(maxsize=2)
def origin(spans_path: str) -> dict | None:
    """``otherData`` of the program's span file: its clock's origin on
    ``perf_counter`` (seconds) and on the wall clock (nanoseconds). None for
    a program that does not write it down."""
    with open(spans_path) as f:
        other = json.load(f).get("otherData") or {}
    return other if "t0_perf_counter_s" in other else None


def window(obs: dict) -> tuple[float, float, float] | None:
    """``(start, end, origin)`` of the measured window on ``perf_counter``:
    from ``window_start`` to the end of the program's last span, or
    ``t_end`` if that comes first. (``t_end`` alone is late in a traced run:
    the harness reads it after the trace is written out, minutes after the
    trainer returned in the streaming cell — PR 24.) None where the span
    file gives no origin."""
    other = origin(obs["flags"]["trace-file"])
    if other is None:
        return None
    t0 = other["t0_perf_counter_s"]
    last = max((e["ts"] + e.get("dur", 0.0) for e in obs["spans"]), default=0.0)
    return obs["window_start"], min(obs["t_end"], t0 + last / 1e6), t0


def in_window(obs: dict, name: str) -> list[dict] | None:
    """The ``name`` spans clipped to ``window``, as ``{"dur_s", "whole_s",
    "args"}`` (clipped and whole duration). None where the window cannot be
    found."""
    found = window(obs)
    if found is None:
        return None
    lo, hi, t0 = found
    out = []
    for e in obs["spans"]:
        if e["name"] != name or e.get("ph") != "X":
            continue
        start = t0 + e["ts"] / 1e6
        a, b = max(start, lo), min(start + e["dur"] / 1e6, hi)
        if b > a:
            out.append({"dur_s": b - a, "whole_s": e["dur"] / 1e6, "args": e.get("args", {})})
    return out


def window_pct(obs: dict, name: str) -> float | None:
    """Share of the window's wall time under ``name`` spans (one thread's
    spans of one name do not overlap). None where the program has no such
    span."""
    spans = in_window(obs, name)
    if not spans:
        return None
    lo, hi, _ = window(obs)
    return 100.0 * sum(s["dur_s"] for s in spans) / (hi - lo)


@functools.lru_cache(maxsize=2)
def profile_start_ns(xplane_path: str) -> int | None:
    """The wall-clock nanosecond at which the trace's own clock starts: the
    ``Task Environment`` plane's ``profile_start_time``. None where the
    trace does not say."""
    for name, stats, _ in wire.planes(xplane_path):
        if name == "Task Environment" and stats.get("profile_start_time") is not None:
            return int(stats["profile_start_time"])
    return None


def offset_ns(obs: dict, say=print) -> int | None:
    """What to add to a ``spans.json`` time (ns) to place it on the device
    trace's clock: ``t0_unix_ns`` (the program's span file) minus
    ``profile_start_time`` (the trace). None, and a line saying why, where
    either origin is not written down."""
    other = origin(obs["flags"]["trace-file"])
    started = profile_start_ns(obs["xplane"]) if obs.get("xplane") else None
    if other is None or started is None:
        say("benchmark: hostclock: no offset: "
            + ("the span file gives no origin" if other is None
               else "the trace does not say when it began"), flush=True)
        return None
    return other["t0_unix_ns"] - started
