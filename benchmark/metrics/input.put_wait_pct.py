"""Program spans: share of the window's wall time the loader's producer
thread spent in ``loader/put``, blocked on a full queue: the consumer is the
slower side."""

from benchmark.trace import hostclock


def read(obs, trace):
    return hostclock.window_pct(obs, "loader/put")
