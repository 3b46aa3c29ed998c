"""Which part of the program a device operation belongs to: raw trace ->
``{instruction name: scope path}``, and the split of a step by it.

The program puts JAX's name stack on its device work (``jax.named_scope``:
``input``, ``forward``, ``loss``, ``grad_sync``, ``optimizer``, ``metrics``,
``attention``, ``kernel/<name>``; PERF.md section 3), XLA carries it on every
instruction as ``op_name``, and the chip's trace keeps it as the stat
``tf_op`` of the event's METADATA, beside the ``program_id`` of the
executable the instruction belongs to (PR 24, TPU v5 lite; neither is in the
event's name or in what ``ProfileData`` shows of an event). ``xplane.label``
keeps an event's opcode, instruction name, shape and target, so this file
reads the raw trace a second time (``wire.py``) for the one thing it adds,
and the readers join the two by the instruction name, the label's second
word.

A fusion belongs to the scope of the instruction the chip names it by, its
root; a fusion that straddles two scopes (a weight gradient with Adam fused
in) goes whole to one of them, so the split is exact in its sum and
approximate at those seams.

A trace of a program without scopes (the parent of the PR that added them,
or an executable a compile cache kept from such a tree: JAX's cache key
ignores locations) yields paths that name no ``forward``; every reader here
then returns None and the harness leaves the metric out.
"""

from __future__ import annotations

import functools
import re
import statistics

from benchmark.trace import reduce, wire
from benchmark.trace.xplane import DEVICE_PLANE, Trace

_INSTRUCTION = re.compile(r"^%?(\S+) = ")
_PROGRAM_ID = re.compile(r"\((\d+)\)$")  # ``jit_epoch_fn(11610552608033325028)``

# One scope of a path: ``jit(epoch_fn)/while/body/transpose(jvp(forward))/
# attn/attention/dot_general`` holds ``forward`` and ``attention``.
_WORD = re.compile(r"[A-Za-z0-9_.\-]+")
PHASES = ("input", "fwd", "bwd", "opt")
_PHASE_SCOPES = {
    "input": {"input"},
    "fwd": {"forward", "loss"},
    "opt": {"optimizer", "grad_sync", "metrics"},
}
# The backward pass needs no scope of its own: JAX derives its names from
# the forward's. Under ``--remat full`` the recomputed forward
# (``transpose(jvp(jvp()))/checkpoint/rematted_computation/forward/…``) is
# backward-pass time too.
_BACKWARD = ("transpose(jvp(", "rematted_computation")
_SAID: set[str] = set()  # traces already reported as carrying no scope


def instruction_of(name: str) -> str:
    """``bn1.21`` from the chip's ``%bn1.21 = (...) custom-call(...)``, from
    the label ``custom-call bn1.21 bf16[...]`` (a cut fixture), or from the
    bare name."""
    found = _INSTRUCTION.match(name)
    if found:
        return found.group(1)
    words = name.split(" ", 2)
    return words[1] if len(words) > 1 else name


def from_metadata(planes) -> dict[int, dict[tuple[str, str], str]]:
    """Per device ordinal, ``{(program id, instruction): scope path}`` from
    ``wire.planes``: the ``tf_op`` of every event metadata that has one
    (``op_name:`` and, on some builds, an op type after the colon)."""
    out: dict[int, dict[tuple[str, str], str]] = {}
    for name, _, events in planes:
        device = DEVICE_PLANE.match(name)
        if not device:
            continue
        paths = out.setdefault(int(device.group(1)), {})
        for event_name, stats in events:
            path = stats.get("tf_op")
            if path:
                key = (str(stats.get("program_id", "")), instruction_of(event_name))
                paths[key] = path.rsplit(":", 1)[0]
    return out


@functools.lru_cache(maxsize=2)
def read(path: str) -> dict[int, dict[tuple[str, str], str]]:
    """``from_metadata`` of the ``.xplane.pb`` at ``path``; read once a run,
    whichever metric asks first."""
    return from_metadata(wire.planes(path))


def table(raw: dict[tuple[str, str], str], runs) -> dict[str, str]:
    """``{instruction: scope path}`` of the step program (``runs``:
    ``reduce.step_program``, whose name ends in the program's id). Two
    programs of one trace can both hold a ``fusion.3``: only the step
    program's count."""
    found = _PROGRAM_ID.search(runs[0][0]) if runs else None
    program = found.group(1) if found else ""
    return {instr: path for (pid, instr), path in raw.items() if pid == program}


def scopes_of(path: str) -> set[str]:
    """The names in a scope path, transforms opened: ``transpose(jvp(
    forward))/attn/attention`` -> ``{transpose, jvp, forward, attn,
    attention}``."""
    return set(_WORD.findall(path))


def phase(path: str | None) -> str | None:
    """``input`` | ``fwd`` | ``bwd`` | ``opt`` for a scope path, None for an
    operation outside the program's scopes (the scan's carry copies)."""
    if not path:
        return None
    if any(mark in path for mark in _BACKWARD):
        return "bwd"
    names = scopes_of(path)
    for name in ("input", "opt", "fwd"):
        if names & _PHASE_SCOPES[name]:
            return name
    return None


def holds(path: str | None, scope: str) -> bool:
    """Whether ``scope`` (``attention``, ``kernel/stem_bwd``) is in the
    path, as whole names."""
    if not path:
        return False
    if "/" in scope:
        return f"/{scope}/" in f"/{path}/"
    return scope in scopes_of(path)


def for_run(obs: dict, trace: Trace | None, device: int = 0):
    """``(table, runs)`` of this run's trace for ``device``; None where
    there is no device trace, no whole execution of the step program, or no
    operation under any of the program's scopes."""
    if trace is None or device not in trace.devices or not obs.get("xplane"):
        return None
    runs = reduce.step_program(trace, device)
    raw = read(obs["xplane"]).get(device)
    if not runs or not raw:
        return None
    paths = table(raw, runs)
    # ``transpose(jvp(`` is JAX's own and in any differentiated program;
    # ``forward`` is this program's.
    if not any(phase(p) == "fwd" for p in paths.values()):
        if obs["xplane"] not in _SAID:
            _SAID.add(obs["xplane"])  # once a trace, not once a metric
            print("benchmark: the trace names no operation under the program's scopes "
                  "(a program without them, or an executable cached from one)", flush=True)
        return None
    return paths, runs


def picked_ms(obs: dict, trace: Trace | None, want) -> float | None:
    """Milliseconds per optimizer step chip 0 spent in operations whose
    scope path ``want(path)`` selects, as ``reduce.per_step`` counts them."""
    got = for_run(obs, trace)
    if got is None:
        return None
    paths, _ = got
    wanted = {instruction for instruction, path in paths.items() if want(path)}
    unnamed = bool(want(None))  # an operation the table does not hold

    def pick(label: str) -> bool:
        words = label.split(" ", 2)
        return len(words) > 1 and (words[1] in wanted if words[1] in paths else unnamed)

    per_step = reduce.per_step(trace, 0, obs["steps_per_program"], pick=pick)
    return None if per_step is None else per_step[0] / 1e6


def phase_ms(obs: dict, trace: Trace | None, name: str) -> float | None:
    """``step.input_ms`` / ``fwd`` / ``bwd`` / ``opt``. Operations can
    overlap on the chip's one operation line only by nesting, and control
    flow does not count, so the four and ``unscoped_ms`` sum to
    ``step.device_ms``."""
    return picked_ms(obs, trace, lambda path: phase(path) == name)


def unscoped_ms(obs: dict, trace: Trace | None) -> float | None:
    """What the four phases leave of ``step.device_ms``."""
    return picked_ms(obs, trace, lambda path: phase(path) is None)


def scope_ms(obs: dict, trace: Trace | None, scope: str) -> float | None:
    """Per step, every operation with ``scope`` in its path, forward and
    backward. None where the trace holds none (a cell without that code)."""
    ms = picked_ms(obs, trace, lambda path: holds(path, scope))
    return ms if ms else None


def boundary_ms(trace: Trace | None, device: int = 0) -> float | None:
    """Median idle milliseconds of ``device`` between consecutive
    executions of the step program: the gap from one's end to the next's
    start, less what ran in it (the epoch accounting's tiny programs). Every
    gap whose two edges lie in the trace counts, whole neighbours or not: an
    execution the trace's START cut short still ends where it ended, and one
    its END cut short began where it began."""
    if trace is None or device not in trace.devices:
        return None
    runs = reduce.program_runs(trace, device)
    gaps = [(a[1] + a[2], b[1]) for a, b in zip(runs, runs[1:]) if b[1] > a[1] + a[2]]
    if not gaps:
        return None
    idle = [hi - lo - reduce.busy(trace, device, lo, hi) for lo, hi in gaps]
    return statistics.median(idle) / 1e6
