"""Device trace: milliseconds per optimizer step chip 0 spent under the
program's ``input`` scope — the device-cache row take (on several chips the
cross-chip batch gather) and ``ingest_images``. The first of the four phase
metrics also prints what they leave of ``step.device_ms``: the operations
under none of the program's scopes."""

from benchmark.trace import scopes


def read(obs, trace):
    rest = scopes.unscoped_ms(obs, trace)
    if rest is not None:
        print(f"benchmark: scopes: {rest:.4f} ms a step under none of the program's scopes",
              flush=True)
    return scopes.phase_ms(obs, trace, "input")
