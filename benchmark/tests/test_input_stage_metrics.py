"""The nine readers of what PR 35's input pipeline writes on its spans —
``stage_s`` on ``loader/decode``, ``cpu_s`` / ``host_cpus`` on
``loader/epoch``, the instant ``prefetch/yield`` — on a hand-built ``obs``:
a span the window cuts counts by its part inside, a program without an
argument reads None for exactly the metrics that need it, and the four
stages are the busy time. Then the cell's CPU rehearsal finds all nine."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.metrics import load_reader
from benchmark.trace import hostclock

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
T0_PERF = 1000.0
STAGES = ["input.decode_file_ms", "input.decode_jpeg_ms", "input.decode_resize_ms",
          "input.decode_normalize_ms"]
PART = ["input.decode_jpeg_scan_ms"]  # of the jpeg stage: not a fifth stage
CPU = ["input.host_cpu_pct", "input.cpu_outside_decode_pct", "input.no_producer_pct"]
HELD = ["input.prefetch_held_ms"]


def _span(name, ts_s, dur_s, **args):
    return {"name": name, "ph": "X", "ts": ts_s * 1e6, "dur": dur_s * 1e6, "args": args}


def _yield(ts_s, batch, held_ms):
    return {"name": "prefetch/yield", "ph": "i", "ts": ts_s * 1e6,
            "args": {"epoch": 0, "batch": batch, "held_ms": held_ms, "behind": 1 - batch}}


def _stage_s(scale=1.0):
    return {"file": 0.1 * scale, "jpeg": 4.0 * scale, "resize": 3.5 * scale,
            "normalize": 0.4 * scale}


def _spans():
    """A window of 20 s ([10, 30] on the program's clock). Two producers:
    one the window's start cuts in half, one whole; 2 s with none alive."""
    return [
        # Alive 4..16: half of its 12 s, and of its 48 CPU seconds, inside.
        _span("loader/epoch", 4, 12, epoch=0, batches=2, cpu_s=48.0, host_cpus=16),
        _span("loader/epoch", 18, 12, epoch=1, batches=2, cpu_s=60.0, host_cpus=16),
        # Cut in half by the window's start: 500 of its 1 000 images count.
        _span("loader/decode", 8, 4, images=1000, threads=8, thread_busy_s=16.0,
              stage_s=_stage_s(2.0), jpeg_scan_s=7.6),
        _span("loader/decode", 20, 2, images=1000, threads=8, thread_busy_s=8.0,
              stage_s=_stage_s(), jpeg_scan_s=3.8),
        _span("h2d", 12, 0.01, epoch=0, batch=0, bytes=1),
        _yield(9.0, 0, 5000.0),  # before the window
        _yield(13.2, 0, 1200.0), _yield(13.3, 1, 80.0),
        _yield(23.2, 0, 1000.0), _yield(23.3, 1, 120.0),
    ]


def _obs(tmp_path, spans):
    path = tmp_path / "spans.json"
    path.write_text(json.dumps(
        {"traceEvents": spans, "otherData": {"t0_perf_counter_s": T0_PERF, "t0_unix_ns": 0}}
    ))
    hostclock.origin.cache_clear()
    return {
        "spans": spans, "flags": {"trace-file": str(path), "loader-workers": 8},
        "window_start": T0_PERF + 10.0, "t_end": T0_PERF + 30.0,
    }


def _read(obs, names):
    return {name: load_reader(name)(obs, None) for name in names}


def test_the_readers_on_a_window_that_cuts_a_span_in_half(tmp_path, capsys):
    obs = _obs(tmp_path, _spans())
    got = _read(obs, STAGES + PART + CPU + HELD)
    # 1 500 images in the window; the cut span gives half of its doubled stages.
    assert got["input.decode_file_ms"] == pytest.approx(1e3 * (0.1 + 0.1) / 1500)
    assert got["input.decode_jpeg_ms"] == pytest.approx(1e3 * (4.0 + 4.0) / 1500)
    assert got["input.decode_resize_ms"] == pytest.approx(1e3 * (3.5 + 3.5) / 1500)
    assert got["input.decode_normalize_ms"] == pytest.approx(1e3 * (0.4 + 0.4) / 1500)
    assert got["input.decode_jpeg_scan_ms"] == pytest.approx(1e3 * (3.8 + 3.8) / 1500)
    # The four stages are the workers' busy time of the same window, an image.
    busy_s = 16.0 / 2 + 8.0
    assert sum(got[name] for name in STAGES) == pytest.approx(1e3 * busy_s / 1500)
    # CPU: 24 of the first producer's 48 s + 60, on 16 cores x (6 + 12) s alive.
    assert got["input.host_cpu_pct"] == pytest.approx(100 * 84 / (16 * 18))
    assert got["input.cpu_outside_decode_pct"] == pytest.approx(100 * (84 - busy_s) / 84)
    assert got["input.no_producer_pct"] == pytest.approx(100 - 100 * 18 / 20)
    assert got["input.no_producer_pct"] == pytest.approx(
        100 - hostclock.window_pct(obs, "loader/epoch")
    )
    # The mean of the window's four hand-overs, and the two kinds apart in print.
    assert got["input.prefetch_held_ms"] == pytest.approx((1200 + 80 + 1000 + 120) / 4)
    said = capsys.readouterr().out
    assert "prefetch held_ms by batch (mean, n): {0: (1100.0, 2), 1: (100.0, 2)}" in said
    assert "host cpus 16, process cpu 4.667 s a second of producer life" in said


def _without(spans, name=None, arg=None):
    out = []
    for e in spans:
        if e["name"] == name and arg is None:
            continue
        if e["name"] == name:
            e = dict(e, args={k: v for k, v in e["args"].items() if k != arg})
        out.append(e)
    return out


@pytest.mark.parametrize(
    "name,arg,silent",
    [
        ("loader/decode", "stage_s", STAGES),
        ("loader/decode", "jpeg_scan_s", PART),
        ("loader/epoch", "cpu_s", CPU),
        ("prefetch/yield", None, HELD),
        ("loader/decode", None, STAGES + PART),  # e.g. a run whose window holds no decode
    ],
)
def test_a_program_without_an_argument_silences_the_metrics_that_need_it(
    tmp_path, name, arg, silent
):
    got = _read(_obs(tmp_path, _without(_spans(), name, arg)), STAGES + PART + CPU + HELD)
    assert sorted(k for k, v in got.items() if v is None) == sorted(silent)
    assert all(v is not None and v == v for k, v in got.items() if k not in silent)


def test_the_parents_program_reads_none_of_the_nine(tmp_path):
    """What the parent commit writes: the spans without the new arguments and
    no instant. None, not zero, for all nine; the older readers as before."""
    spans = _spans()
    for name, arg in [("loader/decode", "stage_s"), ("loader/decode", "jpeg_scan_s"),
                      ("loader/epoch", "cpu_s"), ("prefetch/yield", None)]:
        spans = _without(spans, name, arg)
    obs = _obs(tmp_path, spans)
    everything = STAGES + PART + CPU + HELD
    assert _read(obs, everything) == dict.fromkeys(everything)
    assert load_reader("input.decode_util_pct")(obs, None) == pytest.approx(100 * 16 / (8 * 4))
    # No origin in the span file (older still): nothing raises, nothing is read.
    blind = dict(_obs(tmp_path, _spans()))
    (tmp_path / "spans.json").write_text(json.dumps({"traceEvents": blind["spans"]}))
    hostclock.origin.cache_clear()
    assert _read(blind, everything) == dict.fromkeys(everything)
    hostclock.origin.cache_clear()


def test_the_nine_are_declared_for_the_streaming_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entries = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in STAGES + PART + CPU + HELD:
        entry = entries[name]
        assert entry["layer"] == "input pipeline" and entry["moves"] == "fed_img_per_s_chip"
        assert "r18_train_stream" in entry["workloads"] and entry["better"] == "lower"
        assert entry["unit"] == ("ms" if name.endswith("_ms") else "%")


def test_the_streaming_cells_rehearsal_finds_the_nine():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "r18_train_stream", "--seed",
         "2147483659", "--seconds", "3", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] and line["correct"] and line["failed"] == 0
    assert set(STAGES + PART + CPU + HELD) <= set(line["metrics_found"])
