"""Program counter: how much of its workers' time the decode used — seconds
the decode threads spent inside a decode (``thread_busy_s``, from the
counters native/decode.cpp and the PIL pool keep where they decode) / (worker
threads x seconds of ``loader/decode``), over the window's batches."""

from benchmark.trace import hostclock


def read(obs, trace):
    spans = hostclock.in_window(obs, "loader/decode")
    spans = [s for s in spans or [] if "thread_busy_s" in s["args"]]
    if not spans:
        return None
    # A span the window cut counts by the part inside it.
    busy = sum(s["args"]["thread_busy_s"] * s["dur_s"] / s["whole_s"] for s in spans)
    offered = sum(
        s["args"].get("threads", obs["flags"].get("loader-workers", 1)) * s["dur_s"]
        for s in spans
    )
    return 100.0 * busy / offered
