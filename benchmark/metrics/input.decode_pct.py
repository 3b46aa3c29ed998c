"""Program spans: share of the window's wall time the loader's producer
thread spent in ``loader/decode`` (``_load_batch``: the native call on its
worker threads, or the PIL pool)."""

from benchmark.trace import hostclock


def read(obs, trace):
    return hostclock.window_pct(obs, "loader/decode")
