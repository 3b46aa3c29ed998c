"""Program spans: share of the window's wall time with no producer thread
alive — 100 minus the share under ``loader/epoch`` (a new thread every epoch:
what lies between two of them is the consumer finishing the old epoch and
starting the new). Read from the spans that carry the producer's ``cpu_s``,
as the two CPU shares are, so a program from before them reads nothing."""

from benchmark.trace import hostclock, producer


def read(obs, trace):
    found = producer.cpu(obs)
    if found is None:
        return None
    _, _, alive_s = found
    lo, hi, _ = hostclock.window(obs)
    return 100.0 - 100.0 * alive_s / (hi - lo)
