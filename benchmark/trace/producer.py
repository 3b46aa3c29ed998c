"""What the input pipeline's producer wrote on its own spans, cut to the
measured window (PR 35): the C decoder's seconds by stage on ``loader/decode``
(``stage_s``, and ``jpeg_scan_s`` for the one stage split once more), the
whole process's CPU seconds and the host's cores on ``loader/epoch``
(``cpu_s``, ``host_cpus``). Shared by the ``input.decode_*_ms``
readers and by the two CPU shares. A span the window cuts counts by its part
inside, as ``input.decode_util_pct`` counts; a program that writes no such
argument reads None everywhere here, never zero."""

from __future__ import annotations

from benchmark.trace import hostclock


def _cut(obs: dict, name: str, arg: str) -> list[tuple[dict, float, float]]:
    """``(args, seconds inside the window, share of the span inside it)`` of
    the window's ``name`` spans that carry ``arg``."""
    return [
        (s["args"], s["dur_s"], s["dur_s"] / s["whole_s"])
        for s in hostclock.in_window(obs, name) or []
        if arg in s["args"] and s["whole_s"] > 0
    ]


def _per_image_ms(obs: dict, arg: str, seconds) -> float | None:
    """Sum of ``seconds(args)`` / sum of ``images``, in milliseconds, over
    the window's ``loader/decode`` spans that carry ``arg``."""
    spans = _cut(obs, "loader/decode", arg)
    images = sum(args["images"] * share for args, _, share in spans)
    if not images:
        return None
    return 1e3 * sum(seconds(args) * share for args, _, share in spans) / images


def stage_ms(obs: dict, stage: str) -> float | None:
    """Milliseconds of a decode worker an image spends in ``stage``
    (``stage_s[stage]``)."""
    return _per_image_ms(obs, "stage_s", lambda args: args["stage_s"][stage])


def jpeg_scan_ms(obs: dict) -> float | None:
    """Milliseconds of a decode worker an image spends in libjpeg's scanline
    loop (``jpeg_scan_s``, a part of ``stage_s["jpeg"]``)."""
    return _per_image_ms(obs, "jpeg_scan_s", lambda args: args["jpeg_scan_s"])


def busy_s(obs: dict) -> float:
    """Seconds the decode workers were inside a decode, over the window."""
    spans = _cut(obs, "loader/decode", "thread_busy_s")
    return sum(args["thread_busy_s"] * share for args, _, share in spans)


def cpu(obs: dict) -> tuple[float, float, float] | None:
    """``(CPU seconds of the process, core-seconds the host offered, seconds
    of producer life)`` over the window's ``loader/epoch`` spans."""
    spans = _cut(obs, "loader/epoch", "cpu_s")
    if not spans:
        return None
    return (
        sum(args["cpu_s"] * share for args, _, share in spans),
        sum(args["host_cpus"] * inside for args, inside, _ in spans),
        sum(inside for _, inside, _ in spans),
    )
