"""Program counters: the share of the process's CPU seconds that is NOT the
decode workers' — (``cpu_s`` of the window's ``loader/epoch`` spans minus
``thread_busy_s`` of the ``loader/decode`` spans in the same window) /
``cpu_s``: the cast, the step loop's Python, and whatever the runtime's
transfer threads burn beside the workers. ``thread_busy_s`` is wall time
inside a decode, so workers that wait for a core read as decode here and a
host out of cores can read below zero."""

from benchmark.trace import producer


def read(obs, trace):
    found = producer.cpu(obs)
    if found is None or not found[0]:
        return None
    cpu_s = found[0]
    return 100.0 * (cpu_s - producer.busy_s(obs)) / cpu_s
