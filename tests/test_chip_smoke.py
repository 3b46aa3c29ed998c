"""``chip_smoke.py`` from the outside, as the driver runs it: a child process,
its exit code, and the last line of its standard output.

The chip run itself cannot happen here; what can is the script's own control
flow (``--rehearse``: the same train → checkpoint → evaluate → checks
sequence on the CPU at a tiny size, kernels under the Pallas interpreter)
and its refusal to pass without an accelerator."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from mpi_pytorch_tpu.utils.hardware import local_tpu_chips

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(args, cwd=REPO, script=SMOKE):
    return subprocess.run(
        [sys.executable, script, *args], cwd=cwd, env=dict(os.environ),
        capture_output=True, text=True, timeout=900,
    )


def test_rehearsal_runs_the_whole_sequence_and_never_prints_the_pass_marker():
    proc = _run(["--rehearse"])
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-4000:]
    lines = proc.stdout.strip().splitlines()
    # The last line is the one the driver parses: on the chip exactly
    # {"ok", "device"}; a CPU rehearsal must never carry the pass marker.
    last = json.loads(lines[-1])
    assert set(last) == {"rehearsal", "platform", "device"}
    assert last["rehearsal"] is True and last["platform"] == "cpu"
    assert set(last["device"]) == {"platform", "kind", "count"}
    assert last["device"]["platform"] == "cpu"
    assert isinstance(last["device"]["kind"], str)
    assert isinstance(last["device"]["count"], int)
    assert '"ok"' not in proc.stdout
    # What the run found: the line before it, and the same object on disk.
    tag = "chip_smoke: report "
    assert lines[-2].startswith(tag)
    report = json.loads(lines[-2][len(tag):])
    with open(os.path.join(REPO, "chiprun_out", "chip_smoke", "report.json")) as f:
        assert json.load(f) == report
    assert report["rehearsal"] is True and report["device"] == last["device"]
    assert report["device"]["count"] == len(report["shard_devices"])
    assert report["steps"] == 8 and report["recompiles_epoch1"] == 0
    assert report["prediction_rows"] == 16
    assert report["compile_cache"]["dir"] == os.environ["JAX_COMPILATION_CACHE_DIR"]
    assert report["compile_cache"]["entries_after"] > 0
    assert set(report["setup_seconds"]) >= {
        "train_build", "train_compile", "train_epochs", "checkpoint_dispatch",
        "validate", "predict_compile", "evaluate_total", "total",
    }
    assert report["claim"] is None
    # Set-up seconds and losses only: the smoke states no speed.
    assert not any("per_sec" in k or "mfu" in k for k in report)
    # The checkpoints are not left behind (0.4 GB each at flagship size).
    assert not os.path.exists(
        os.path.join(REPO, "chiprun_out", "chip_smoke", "checkpoints")
    )


def test_the_pass_line_has_exactly_the_keys_the_driver_parses():
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    assert json.loads(chip_smoke.last_line(False, device)) == {"ok": True, "device": device}


@pytest.mark.skipif(local_tpu_chips() > 0, reason="this machine has a TPU")
def test_plain_command_fails_on_a_machine_without_a_chip():
    """No flag = the TPU and nothing else: on this CPU-only machine the
    script must exit non-zero before training anything, with no result."""
    proc = _run([])
    assert proc.returncode != 0
    assert proc.stdout.strip() == "", proc.stdout[-500:]
    assert "Unable to initialize backend 'tpu'" in proc.stderr


def test_fails_in_a_directory_that_holds_nothing_else_of_the_repo(tmp_path):
    lonely = shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    proc = _run(["--rehearse"], cwd=str(tmp_path), script=str(lonely))
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "No module named 'mpi_pytorch_tpu'" in proc.stderr
