"""Program spans: share of the window's wall time the loader's producer
thread spent in ``loader/cast`` (the batch's conversion to the input dtype,
on that one thread)."""

from benchmark.trace import hostclock


def read(obs, trace):
    return hostclock.window_pct(obs, "loader/cast")
