"""Program spans: share of the window's wall time the consumer's thread
spent in ``h2d`` (``device_prefetch``: pad + ``shard_batch``, the host's
share of the transfer; the copy itself is asynchronous)."""

from benchmark.trace import hostclock


def read(obs, trace):
    return hostclock.window_pct(obs, "h2d")
