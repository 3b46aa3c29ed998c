"""Device trace: idle milliseconds of chip 0 between two consecutive whole
executions of the step program (a scanned epoch), median per boundary — what
the trainer's epoch loop costs the device."""

from benchmark.trace import scopes


def read(obs, trace):
    return scopes.boundary_ms(trace)
