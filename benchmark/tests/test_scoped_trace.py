"""The scope table, the phase split, the kernels by name and the joined
clocks on the recorded trace beside this file: one whole scanned epoch of
``r18_train_hbm`` on the v5e with the program's scopes on it (PR 24), cut by
``benchmark/trace/cut_scoped.py``, and the spans of the same run that lie
inside the cut. Numbers checked by hand against the uncut trace."""

import json
import os
import statistics
from collections import defaultdict

import pytest

from benchmark.metrics import load_reader
from benchmark.trace import hostclock, reduce, scopes, wire, xplane

HERE = os.path.dirname(__file__)
RECORDED = os.path.join(HERE, "r18_train_hbm.scoped.xplane.pb")
SPANS = os.path.join(HERE, "r18_train_hbm.scoped.spans.json")


@pytest.fixture(scope="module")
def run():
    with open(SPANS) as f:
        spans = json.load(f)["traceEvents"]
    obs = {
        "xplane": RECORDED, "spans": spans, "flags": {"trace-file": SPANS},
        "steps_per_program": 19, "model": {"image_size": 128}, "global_batch": 2048,
        "chips": 1,
    }
    return obs, xplane.read(RECORDED, {e["name"] for e in spans})


def test_the_chip_keeps_the_scope_path_on_the_events_metadata():
    (ordinal, raw), = scopes.read(RECORDED).items()
    assert ordinal == 0 and len(raw) == 1037
    (program,) = {pid for pid, _ in raw}
    assert program == "11610552608033325028"  # jit_epoch_fn(...)'s
    assert raw[program, "stem_fwd.10"] == (
        "jit(epoch_fn)/jit(epoch_fn)/while/body/closed_call/jvp(forward)/ResNet/bn1/"
        "kernel/stem_fwd/stem_fwd/pallas_call"
    )
    # What ProfileData shows of an event holds none of it.
    planes = dict((name, stats) for name, stats, _ in wire.planes(RECORDED))
    assert planes["Task Environment"]["profile_start_time"] == 1790622647165503668


def test_the_four_phases_sum_to_the_step_within_two_percent(run):
    obs, trace = run
    device_ms = load_reader("step.device_ms")(obs, trace)
    assert device_ms == pytest.approx(76.2094, abs=1e-3)
    got = {name: load_reader(f"step.{name}_ms")(obs, trace) for name in scopes.PHASES}
    assert got == pytest.approx(
        {"input": 1.58365, "fwd": 23.68489, "bwd": 50.30213, "opt": 0.39167}, abs=1e-4
    )
    rest = scopes.unscoped_ms(obs, trace)  # the compiler's own copies, under `while`
    assert rest == pytest.approx(0.24702, abs=1e-4)
    assert sum(got.values()) + rest == pytest.approx(device_ms, rel=1e-9)
    assert sum(got.values()) == pytest.approx(device_ms, rel=0.02)
    assert load_reader("step.attn_ms")(obs, trace) is None  # no attention in resnet18


def test_the_stem_kernels_are_found_by_name(run):
    obs, trace = run
    fwd = load_reader("kernel.stem_fwd_ms")(obs, trace)
    bwd = load_reader("kernel.stem_bwd_ms")(obs, trace)
    assert (fwd, bwd) == pytest.approx((2.52270, 4.38121), abs=1e-4)
    # The same two calls the older reader tells by their result shapes.
    assert fwd + bwd == pytest.approx(load_reader("kernel.stem_ms")(obs, trace), rel=1e-9)


def test_a_seam_goes_whole_to_its_roots_scope(run):
    """conv1's weight gradient with Adam fused in is one fusion, rooted in
    the backward pass: the split is exact in its sum, approximate there."""
    obs, trace = run
    paths, _ = scopes.for_run(obs, trace)
    assert scopes.phase(paths["multiply_add_fusion.327"]) == "bwd"
    assert scopes.phase(paths["copy-done.4"]) is None  # jit(epoch_fn)/while


def test_the_span_file_lies_on_the_trace_by_its_written_origin(run):
    """``t0_unix_ns - profile_start_time`` against the program's own
    annotations in the trace's host plane, matched per name in order: the
    arithmetic alone is right to 4 us, and the matches agree with one
    another to under 1 us (quartile distance)."""
    obs, trace = run
    shift = hostclock.offset_ns(obs)
    assert shift == -121_040_988_949  # 1790622526124514719 - 1790622647165503668
    written = defaultdict(list)
    for e in obs["spans"]:
        written[e["name"]].append(e["ts"] * 1e3)
    noted = defaultdict(list)
    for name, start, _ in trace.host:
        noted[name].append(start)
    diffs = [
        at - ts
        for name in noted if len(noted[name]) == len(written[name])
        for at, ts in zip(sorted(noted[name]), sorted(written[name]))
    ]
    assert len(diffs) >= 12
    quartiles = statistics.quantiles(diffs, n=4)
    assert quartiles[2] - quartiles[0] < 1_000
    assert abs(statistics.median(diffs) - shift) < 5_000


def test_the_boundary_reads_on_a_cut_with_one_whole_execution(run):
    """The fixture holds one whole scanned epoch between the ragged ends of
    its neighbours: two boundaries (7.747 and 7.588 ms idle, 2.4 us of tiny
    programs in each) and no two whole executions, so the reader of PRs 24-25
    returned None here; the uncut trace's own reading was 7.67 (PERF.md)."""
    obs, trace = run
    assert len(reduce.step_program(trace, 0)) == 1 and len(reduce.program_runs(trace, 0)) == 3
    assert load_reader("epoch.boundary_ms")(obs, trace) == pytest.approx(7.66746, abs=1e-4)


def test_the_boundarys_idle_has_names(run):
    _, trace = run
    gaps = dict(reduce.idle_gaps(trace, 0))
    assert gaps["none"] < 0.25 * sum(gaps.values())
    assert max(gaps, key=gaps.get) in ("epoch/account", "epoch/wait")
    assert {"epoch/prepare", "epoch/record", "epoch/control"} <= set(gaps)
