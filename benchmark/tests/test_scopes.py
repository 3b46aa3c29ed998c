"""The scope table, the phase split, the boundary and the joined clocks on
hand-made events (exact answers); the recorded trace is in
test_scoped_trace.py."""

import json
import pytest

from benchmark.metrics import load_reader
from benchmark.trace import hostclock, reduce, scopes, xplane

HLO = "%fusion.7 = bf16[8,8]{1,0} fusion(bf16[8,8]{1,0} %p), kind=kLoop, calls=%fused"
STEP, TINY = "111", "222"  # program ids


def test_instruction_names_and_the_metadata_table():
    assert scopes.instruction_of(HLO) == "fusion.7"
    assert scopes.instruction_of("fusion fusion.7 bf16[8,8]") == "fusion.7"  # a label
    assert scopes.instruction_of("fusion.7") == "fusion.7"
    planes = [
        ("/device:TPU:1", {}, [
            (HLO, {"tf_op": "jit(f)/jvp(forward)/conv:", "program_id": 111, "flops": 3}),
            ("%copy.3 = f32[2]{0} copy(f32[2]{0} %x)", {"program_id": 111}),  # no op_name
            ("%sub.1 = f32[] subtract()", {"tf_op": "jit(f)/optimizer/sub:Sub", "program_id": 111}),
        ]),
        ("/host:CPU", {}, [("step", {"tf_op": "not a device"})]),
    ]
    assert scopes.from_metadata(planes) == {
        1: {(STEP, "fusion.7"): "jit(f)/jvp(forward)/conv", (STEP, "sub.1"): "jit(f)/optimizer/sub"}
    }


@pytest.mark.parametrize(
    "path,phase",
    [
        ("jit(epoch_fn)/while/body/closed_call/input/take", "input"),
        ("jit(f)/jvp(forward)/ResNet/conv1/conv_general_dilated", "fwd"),
        ("jit(f)/jvp(loss)/reduce_sum", "fwd"),
        ("jit(f)/transpose(jvp(forward))/ResNet/conv1/conv_general_dilated", "bwd"),
        ("jit(f)/transpose(jvp(loss))/mul", "bwd"),
        ("jit(f)/transpose(jvp(jvp()))/checkpoint/rematted_computation/forward/sin", "bwd"),
        ("jit(f)/optimizer/add", "opt"),
        ("jit(f)/shard_map/grad_sync/psum", "opt"),
        ("jit(f)/metrics/sqrt", "opt"),
        ("jit(epoch_fn)/while/body/dynamic_update_slice", None),
        ("jit(f)/jvp(forward_pass)/inputs/x", None),  # whole names only
        ("", None),
        (None, None),
    ],
)
def test_phase_of_a_scope_path(path, phase):
    assert scopes.phase(path) == phase


def test_scope_membership_is_by_whole_names():
    path = "jit(f)/transpose(jvp(forward))/encoder_0/attn/attention/dot_general"
    assert scopes.holds(path, "attention") and not scopes.holds(path, "attn/attentio")
    assert not scopes.holds("jit(f)/jvp(forward)/MultiHeadAttention_0/dot", "attention")
    kernel = "jit(f)/jvp(forward)/ResNet/bn1/shard_map/kernel/stem_fwd/stem_fwd/pallas_call"
    assert scopes.holds(kernel, "kernel/stem_fwd") and not scopes.holds(kernel, "kernel/stem")
    assert not scopes.holds(None, "attention")


def _planes():
    """Chip 0, as ``wire.planes`` yields it: a tiny program whose fusion.1
    is under nothing, and the step program with a fusion.1 of its own."""
    def op(program, instr, path):
        return f"%{instr} = f32[4]{{0}} fusion()", {"tf_op": path + ":", "program_id": int(program)}

    body = "jit(epoch_fn)/while/body/closed_call/"
    return [
        ("/device:TPU:0", {}, [
            op(TINY, "fusion.1", "jit(convert_element_type)/convert_element_type"),
            op(STEP, "take.2", body + "input/take"),
            op(STEP, "fusion.1", body + "jvp(forward)/conv1/conv"),
            op(STEP, "fusion.5", body + "transpose(jvp(forward))/conv1/conv"),
            op(STEP, "fusion.6", body + "optimizer/add"),
            op(STEP, "copy.9", "jit(epoch_fn)/while/body/dynamic_update_slice"),
        ]),
        ("/host:CPU", {}, []),
    ]


def _trace():
    trace = xplane.Trace()
    trace.devices[0] = xplane.Device(
        ops=[
            ("fusion fusion.1 f32[4]", 100.0, 50.0),
            ("while while.1", 1000.0, 10000.0),
            ("gather take.2 f32[4]", 1000.0, 1000.0),
            ("fusion fusion.1 f32[4]", 2000.0, 3000.0),
            ("fusion fusion.5 f32[4]", 5000.0, 4000.0),
            ("fusion fusion.6 f32[4]", 9000.0, 1000.0),
            ("copy copy.9 f32[4]", 10000.0, 500.0),
            # A second whole execution, 1500 ns later, 300 of them busy.
            ("fusion fusion.1 f32[4]", 11700.0, 300.0),
            ("fusion fusion.5 f32[4]", 12500.0, 10000.0),
        ],
        modules=[
            ("jit_convert_element_type(222)", 100.0, 50.0),
            ("jit_epoch_fn(111)", 1000.0, 10000.0),
            ("jit_convert_element_type(222)", 11700.0, 300.0),
            ("jit_epoch_fn(111)", 12500.0, 10000.0),
        ],
    )
    return trace


def test_table_resolves_a_name_two_programs_share():
    raw = scopes.from_metadata(_planes())[0]
    assert (TINY, "fusion.1") in raw and (STEP, "fusion.1") in raw
    paths = scopes.table(raw, [("jit_epoch_fn(111)", 1000.0, 10000.0)])
    assert len(paths) == 5 and paths["fusion.1"].endswith("jvp(forward)/conv1/conv")
    assert scopes.phase(paths["copy.9"]) is None
    assert scopes.table(raw, []) == {}


def test_the_four_phases_and_the_rest_sum_to_the_step(monkeypatch):
    monkeypatch.setattr(scopes, "read", lambda path: scopes.from_metadata(_planes()))
    obs = {"xplane": "hand-made", "steps_per_program": 2}
    trace = _trace()
    got = {name: scopes.phase_ms(obs, trace, name) for name in scopes.PHASES}
    # Two executions, two steps each: (1000 + 0) / 4 ns of input, and so on.
    assert got == pytest.approx(
        {"input": 250e-6, "fwd": 750e-6, "bwd": 3500e-6, "opt": 250e-6}
    )
    rest = scopes.unscoped_ms(obs, trace)
    assert rest == pytest.approx(125e-6)
    device_ms = load_reader("step.device_ms")(obs, trace)
    assert sum(got.values()) + rest == pytest.approx(device_ms)
    for name in scopes.PHASES:
        assert load_reader(f"step.{name}_ms")(obs, trace) == got[name]
    # No operation under `attention` or a stem kernel: left out, not zero.
    assert load_reader("step.attn_ms")(obs, trace) is None
    assert load_reader("kernel.stem_bwd_ms")(obs, trace) is None


def test_boundary_is_the_idle_between_two_whole_executions():
    # 11000 -> 12500 with jit_tiny busy for 300 of the 1500.
    assert scopes.boundary_ms(_trace()) == pytest.approx(1200e-6)
    assert load_reader("epoch.boundary_ms")({}, _trace()) == pytest.approx(1200e-6)
    assert scopes.boundary_ms(None) is None


def test_boundary_counts_a_gap_beside_an_execution_the_traces_start_cut_short():
    """The profiler's start takes the head off the first traced execution
    (~40 ms on the chip: over 2 % of ViT-B/16's 1.69 s epoch since PR 25, so
    it no longer counts as whole); where it ENDS is still in the trace. The
    reader of PRs 24-25 asked for two whole executions and returned None."""
    trace = _trace()
    modules = trace.devices[0].modules
    modules[1] = ("jit_epoch_fn(111)", 1500.0, 9500.0)  # 5 % short, the same end
    assert len(reduce.step_program(trace, 0)) == 1  # one whole execution: no pair of them
    assert [e[1] for e in reduce.program_runs(trace, 0)] == [1500.0, 12500.0]
    assert scopes.boundary_ms(trace) == pytest.approx(1200e-6)
    assert load_reader("epoch.boundary_ms")({}, trace) == pytest.approx(1200e-6)
    del modules[3]  # one execution in the trace: no boundary, nothing to read
    assert scopes.boundary_ms(trace) is None


def test_a_program_without_scopes_yields_nothing(monkeypatch, capsys):
    planes = _planes()
    for _, stats in planes[0][2]:  # what the parent of PR 24 names its operations
        stats["tf_op"] = stats["tf_op"].replace("input/", "").replace(
            "jvp(forward)", "jvp(ResNet)").replace("optimizer/", "")
    monkeypatch.setattr(scopes, "read", lambda path: scopes.from_metadata(planes))
    obs = {"xplane": "hand-made", "steps_per_program": 2}
    assert load_reader("step.fwd_ms")(obs, _trace()) is None
    assert "no operation under the program's scopes" in capsys.readouterr().out
    assert load_reader("step.fwd_ms")(obs, None) is None
    assert load_reader("step.fwd_ms")({"xplane": None}, _trace()) is None


# ---------------------------------------------------------------------------
# one clock
# ---------------------------------------------------------------------------

T0_PERF, T0_UNIX = 1000.0, 1_790_000_000_000_000_000


def _span(name, ts_us, dur_us, **args):
    return {"name": name, "ph": "X", "ts": ts_us, "dur": dur_us, "args": args}


def _obs(tmp_path, spans, with_origin=True):
    path = tmp_path / "spans.json"
    data = {"traceEvents": spans}
    if with_origin:
        data["otherData"] = {"t0_perf_counter_s": T0_PERF, "t0_unix_ns": T0_UNIX}
    path.write_text(json.dumps(data))
    return {
        "spans": spans, "flags": {"trace-file": str(path), "loader-workers": 8},
        "window_start": T0_PERF + 10.0, "t_end": T0_PERF + 30.0,
    }


def test_span_shares_of_the_window(tmp_path):
    spans = [
        _span("loader/decode", 5e6, 10e6, thread_busy_s=40.0, threads=8),  # half inside
        _span("loader/decode", 20e6, 4e6, thread_busy_s=16.0, threads=8),
        _span("loader/decode", 29e6, 5e6, thread_busy_s=40.0, threads=8),  # 1 s inside
        _span("loader/cast", 24e6, 1e6),
        _span("loader/put", 40e6, 1e6),  # after the window
        _span("lower", 1e6, 2e6, program="step"), _span("lower", 3e6, 1e6, program="epoch"),
        _span("load_or_compile", 4e6, 0.5e6, cache_hit=True),
        {"name": "marker", "ph": "i", "ts": 12e6},
    ]
    obs = _obs(tmp_path, spans)
    assert hostclock.window(obs) == (T0_PERF + 10.0, T0_PERF + 30.0, T0_PERF)
    assert load_reader("input.decode_pct")(obs, None) == pytest.approx(100 * 10 / 20)
    assert load_reader("input.cast_pct")(obs, None) == pytest.approx(5.0)
    assert load_reader("input.put_wait_pct")(obs, None) is None  # none in the window
    assert load_reader("input.h2d_pct")(obs, None) is None
    # busy: 20 + 16 + 8 of (8 x 10) offered.
    assert load_reader("input.decode_util_pct")(obs, None) == pytest.approx(100 * 44 / 80)
    assert load_reader("setup.lower_s")(obs, None) == pytest.approx(3.0)
    assert load_reader("setup.load_s")(obs, None) == pytest.approx(0.5)
    # A traced run reads t_end late (the trace is written out first): the
    # window ends with the program's last span.
    late = dict(obs, t_end=T0_PERF + 500.0)
    assert hostclock.window(late)[1] == T0_PERF + 41.0
    assert load_reader("input.cast_pct")(late, None) == pytest.approx(100 * 1 / 31)
    # A program that writes no origin: the window cannot be found.
    hostclock.origin.cache_clear()
    blind = _obs(tmp_path, spans, with_origin=False)
    assert load_reader("input.decode_pct")(blind, None) is None
    assert load_reader("input.decode_util_pct")(blind, None) is None
    assert load_reader("setup.lower_s")({"spans": []}, None) is None
    hostclock.origin.cache_clear()


STARTED = T0_UNIX - 77_000  # the trace's clock starts 77 us before the program's


def _joined(tmp_path):
    """Six ``step`` spans and their batches' ``h2d`` in the span file, and a
    trace whose ``Task Environment`` plane says when its clock began."""
    from benchmark.trace import cut_scoped
    from benchmark.trace.encode import _bytes

    durs = [700.0, 900.0, 1100.0, 1300.0, 1500.0, 1700.0]
    spans = [_span("step", 1e6 * k, d, epoch=k // 2, step=k % 2) for k, d in enumerate(durs)]
    spans += [_span("h2d", 1e6 * k - 3e5, 100.0, epoch=k // 2, batch=k % 2, bytes=1)
              for k in range(6)]
    obs = _obs(tmp_path, spans)
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(_bytes(1, cut_scoped._plane(
        1, "Task Environment", {}, 0.0,
        plane_stats=_bytes(6, cut_scoped._stat(cut_scoped.PROFILE_START, STARTED)),
    )))
    return dict(obs, xplane=str(path))


def test_spans_are_placed_on_the_traces_clock_by_the_two_origins(tmp_path):
    obs = _joined(tmp_path)
    said = []
    assert hostclock.offset_ns(obs, say=said.append) == 77_000 and not said
    # A trace that does not say when it began, a program that wrote no
    # origin: no offset, and a line saying which.
    hostclock.origin.cache_clear()
    say = lambda line, **k: said.append(line)  # noqa: E731
    assert hostclock.offset_ns(dict(obs, xplane=None), say=say) is None
    assert "the trace does not say" in said[-1]
    blind = dict(_obs(tmp_path, obs["spans"], with_origin=False), xplane=obs["xplane"])
    hostclock.origin.cache_clear()
    assert hostclock.offset_ns(blind, say=say) is None
    assert "no origin" in said[-1]
    hostclock.origin.cache_clear()


@pytest.mark.parametrize("late_ns", [0, 100_000, -100_000])
def test_handoff_from_a_batchs_h2d_to_its_first_device_operation(tmp_path, late_ns):
    """An execution belongs to the step span it ran under, by overlap: the
    trace's device timeline may sit ``late_ns`` off the host's (0.1 ms here;
    a first operation read 0.38 ms before its span opened on the chip), more
    than the 50 us from a span's opening to its first operation."""
    obs = _joined(tmp_path)
    # Executions of the step program for spans 2, 3 and 4, 600 us each: the
    # first operation starts 50 us after the step span began (dispatch).
    runs = [77_000 + 1e9 * k + 50_000 + late_ns for k in (2, 3, 4)]
    trace = xplane.Trace()
    trace.devices[0] = xplane.Device(
        ops=[("fusion fusion.1", at + 1_000, 5_000.0) for at in runs],
        modules=[("jit_train_step(1)", at, 600_000.0) for at in runs],
    )
    # h2d began 0.3 s before its step span: 300 000 + 50 + 1 us.
    got = load_reader("input.handoff_ms")(obs, trace)
    assert got == pytest.approx(300.051 + late_ns / 1e6, abs=1e-6)
    assert load_reader("input.handoff_ms")(obs, None) is None
    # The profiler's host plane plays no part: the streaming cell's overflows.
    assert trace.host == []
    hostclock.origin.cache_clear()
    assert load_reader("input.handoff_ms")(dict(obs, xplane=None), trace) is None  # no origin
    hostclock.origin.cache_clear()


def test_wire_reads_metadata_stats_by_value_and_by_reference(tmp_path):
    """A string stat is stored in place (``str_value``) or as the NAME of
    another stat metadata (``ref_value``); an id as ``uint64_value``."""
    from benchmark.trace import wire
    from benchmark.trace.encode import _bytes, _int

    def stat_name(key, name):
        return _bytes(5, _int(1, key) + _bytes(2, _int(1, key) + _bytes(2, name.encode())))

    def metadata(key, name, *stats):
        message = _int(1, key) + _bytes(2, name.encode()) + b"".join(_bytes(5, s) for s in stats)
        return _bytes(4, _int(1, key) + _bytes(2, message))

    plane = (
        _int(1, 1) + _bytes(2, b"/device:TPU:0")
        + stat_name(1, "tf_op") + stat_name(2, "program_id") + stat_name(9, "jit(f)/optimizer/add:")
        + metadata(1, "%add.1 = f32[] add()", _int(1, 1) + _int(7, 9), _int(1, 2) + _int(3, 2**63 + 5))
        + metadata(2, "%mul.2 = f32[] multiply()", _int(1, 1) + _bytes(5, b"jit(f)/jvp(forward)/mul:"))
        + _bytes(6, _int(1, 2) + _int(3, 7))
    )
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(_bytes(1, plane))
    ((name, plane_stats, events),) = wire.planes(str(path))
    assert name == "/device:TPU:0" and plane_stats == {"program_id": 7}
    assert events == [
        ("%add.1 = f32[] add()", {"tf_op": "jit(f)/optimizer/add:", "program_id": 2**63 + 5}),
        ("%mul.2 = f32[] multiply()", {"tf_op": "jit(f)/jvp(forward)/mul:"}),
    ]
    assert scopes.read(str(path)) == {
        0: {(str(2**63 + 5), "add.1"): "jit(f)/optimizer/add", ("", "mul.2"): "jit(f)/jvp(forward)/mul"}
    }
