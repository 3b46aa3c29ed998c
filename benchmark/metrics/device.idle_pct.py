"""Device trace: share of the traced steady window in which no operation
ran, averaged over the chips."""

from benchmark.trace import reduce


def read(obs, trace):
    if trace is None or not trace.devices:
        return None
    busy_s, window_s = reduce.busy_and_window_s(trace)
    return 100.0 * (1.0 - busy_s / window_s)
