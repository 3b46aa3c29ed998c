"""The trace reduction on hand-made events (exact answers) and on the
recorded trace beside this file (one whole scanned epoch of r18_train_hbm on
the v5e, cut by benchmark/trace/cut.py)."""

import os

import pytest

from benchmark.trace import encode, reduce, xplane

RECORDED = os.path.join(os.path.dirname(__file__), "r18_train_hbm.cut.xplane.pb")


def _hand_made() -> xplane.Trace:
    """One device, one program of two steps, in nanoseconds:

    1000 ......................................... 11000  while.1 (container)
    1000-3000 fusion.1 | 3000-3100 all-reduce-start.1 | 3100-5100 fusion.2
    5100-6000 all-reduce-done.1 | 6000-7000 idle | 7000-10000 custom-call.3
    10000-11000 idle (inside the program, after its last operation)
    """
    trace = xplane.Trace()
    trace.devices[0] = xplane.Device(
        ops=[
            ("while while.1", 1000.0, 10000.0),
            ("fusion fusion.1", 1000.0, 2000.0),
            ("all-reduce-start all-reduce-start.1", 3000.0, 100.0),
            ("fusion fusion.2", 3100.0, 2000.0),
            ("all-reduce-done all-reduce-done.1", 5100.0, 900.0),
            ("custom-call bn1.3 tpu_custom_call", 7000.0, 3000.0),
            ("fusion fusion.9", 12000.0, 500.0),  # the next program, cut off
        ],
        # What the chip's async line shows of the same all-reduce in flight.
        async_ops=[("all-reduce-start all-reduce-start.1", 3000.0, 3000.0)],
        modules=[("jit_epoch_fn(1)", 1000.0, 10000.0), ("jit_epoch_fn(1)", 12000.0, 500.0)],
    )
    trace.host = [("step", 6200.0, 500.0), ("ingest", 6300.0, 100.0), ("other", 6000.0, 1000.0)]
    return trace


def test_interval_arithmetic():
    assert reduce.merge([(5, 7), (1, 3), (2, 4), (7, 8)]) == [(1, 4), (5, 8)]
    assert reduce.length([(1, 4), (5, 8)]) == 6
    assert reduce.clip([(1, 4), (5, 8)], 3, 6) == [(3, 4), (5, 6)]
    assert reduce.overlap([(0, 10)], [(2, 3), (9, 12)]) == 2


def test_control_flow_and_markers_do_not_count_as_work():
    ops = _hand_made().devices[0].ops + [("fusion marker.1", 1500.0, 0.0)]
    names = [e[0] for e in reduce.work(ops)]
    assert "while while.1" not in names and "fusion marker.1" not in names and len(names) == 6
    # A zero-length marker inside fusion.1 takes nothing from it.
    own = dict(reduce.self_times(sorted(ops, key=lambda e: (e[1], -e[2]))))
    assert own["fusion fusion.1"] == 2000.0 and own["while while.1"] == 2000.0


def test_labels_from_the_chips_hlo_text():
    hlo = (
        '%bn1.21 = (bf16[64,64,64,2048]{3,2,1,0:T(8,128)(2,1)}, f32[8,128]{1,0:T(8,128)S(1)}) '
        'custom-call(bf16[32,32,64,2048]{3,2,1,0:T(8,128)(2,1)} %fusion.816, f32[64,1]{1,0} %copy.2), '
        'custom_call_target="tpu_custom_call", operand_layout_constraints={}'
    )
    assert xplane.label(hlo) == "custom-call bn1.21 bf16[64,64,64,2048] tpu_custom_call"
    assert reduce.MOSAIC.match(xplane.label(hlo))
    start = "%all-reduce-start.3 = f32[512,64500]{1,0} all-reduce-start(f32[512,64500]{1,0} %x), channel_id=1"
    assert xplane.label(start) == "all-reduce-start all-reduce-start.3 f32[512,64500]"
    assert reduce.collective(xplane.label(start)) == ("all-reduce", "-start")
    assert reduce.collective("fusion all-reduce-scatter.3 f32[64]") == ("all-reduce", None)
    assert reduce.collective("fusion fusion.3 f32[64]") is None
    assert xplane.label("fusion.12") == "fusion fusion.12"  # a bare name, off the chip
    assert xplane.label("fusion fusion.12") == "fusion fusion.12"  # a label passes


def test_hand_made_trace_round_trips_and_reduces(tmp_path):
    path = str(tmp_path / "hand.xplane.pb")
    encode.write(_hand_made(), path)
    trace = xplane.read(path, {"step", "ingest"})  # "other" is not the program's
    assert trace.devices[0].ops == _hand_made().devices[0].ops
    assert [e[0] for e in trace.host] == ["step", "ingest"]
    assert reduce.window(trace) == (1000.0, 12500.0)
    busy_s, window_s = reduce.busy_and_window_s(trace)
    assert busy_s == pytest.approx(8.5e-6) and window_s == pytest.approx(11.5e-6)
    # One whole program of two steps; the second is cut off by the window.
    assert reduce.per_step(trace, 0, steps_per_program=2) == (4000.0, 2)
    mosaic = reduce.per_step(trace, 0, 2, pick=reduce.MOSAIC.match)
    assert mosaic == (1500.0, 2)
    # all-reduce from 3000 to 6000; fusion.2 hides 2000 of it.
    assert reduce.collectives(trace, 0, 2) == (1500.0, 500.0)
    assert reduce.top_ops(trace, 0, 2) == [
        ["custom-call bn1.3 tpu_custom_call", 3e-6], ["fusion fusion.1", 2e-6],
    ]
    # Idle: 6000-7000 (host: 100 ingest, 400 step around it, 500 none) and
    # 10000-12000 (none).
    gaps = dict(reduce.idle_gaps(trace, 0))
    assert gaps == pytest.approx({"none": 2.5e-6, "step": 4e-7, "ingest": 1e-7})


def test_recorded_trace():
    trace = xplane.read(RECORDED, {"step", "ingest"})
    assert sorted(trace.devices) == [0]
    runs = reduce.step_program(trace, 0)
    assert len(runs) == 1  # the cut keeps one whole scanned epoch
    ns, steps = reduce.per_step(trace, 0, steps_per_program=19)
    assert steps == 19
    busy_s, window_s = reduce.busy_and_window_s(trace)
    assert 0 < busy_s <= window_s
    # The device was busy for nearly all of the whole epoch inside the cut.
    assert ns * 19 == pytest.approx(runs[0][2], rel=0.05)
    assert reduce.collectives(trace, 0, 19) == (0.0, 0.0)  # one chip
    assert any(reduce.MOSAIC.match(name) for name, _ in reduce.top_ops(trace, 0, 50))
