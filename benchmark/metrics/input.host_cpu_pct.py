"""Program counter: how much of the host the job takes while a producer is
alive — CPU seconds of the WHOLE process (``cpu_s``: ``time.process_time()``
over the producer's life, so decode workers, the cast, the step loop and the
runtime's transfer threads alike) / (``host_cpus`` x those seconds), over the
window's ``loader/epoch`` spans. A witness, not a lever: near 100 the host is
out of cores and more workers cannot help; ``better: lower`` only says that
the same images for less CPU is the cheaper job. The run prints the core
count and the CPU seconds a second beside it."""

from benchmark.trace import producer


def read(obs, trace):
    found = producer.cpu(obs)
    if found is None:
        return None
    cpu_s, offered, alive_s = found
    print(f"benchmark: host cpus {offered / alive_s:g}, process cpu "
          f"{cpu_s / alive_s:.3f} s a second of producer life", flush=True)
    return 100.0 * cpu_s / offered
