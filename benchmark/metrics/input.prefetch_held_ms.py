"""Program counter: mean milliseconds a finished batch waits in
``device_prefetch``'s buffer — ``held_ms`` of the window's ``prefetch/yield``
instants (from the close of the batch's ``h2d`` span to the moment it goes to
the step loop). The means by ``batch`` index are printed: with two batches an
epoch and depth 2 the first waits for the whole decode of the second and the
second for one step, and the mean of the two kinds is neither."""

import statistics

from benchmark.trace import hostclock


def read(obs, trace):
    found = hostclock.window(obs)
    if found is None:
        return None
    lo, hi, t0 = found
    held: dict[int, list[float]] = {}
    for e in obs["spans"]:
        if e["name"] == "prefetch/yield" and lo <= t0 + e["ts"] / 1e6 <= hi:
            held.setdefault(e["args"]["batch"], []).append(e["args"]["held_ms"])
    if not held:
        return None
    by_batch = {b: round(statistics.fmean(ms), 3) for b, ms in sorted(held.items())}
    print(f"benchmark: prefetch held_ms by batch (mean, n): "
          f"{ {b: (ms, len(held[b])) for b, ms in by_batch.items()} }", flush=True)
    return statistics.fmean(ms for values in held.values() for ms in values)
