"""Fault injection (SURVEY §5 failure-detection row): SIGKILL a live training
process mid-run and verify the atomic-checkpoint discipline (tmp+rename) left
only loadable checkpoints with auto-resume continuing the epoch count; SIGTERM
one and verify graceful preemption (stop at a safe boundary, save, exit 0) —
the crash-recovery story the reference handles by manual restart with
FROM_CHECKPOINT=True (``main.py:127-130``)."""

import os
import signal
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _trainer_args(tmp_path, **overrides) -> list[str]:
    """The shared CLI recipe for a small CPU-mesh training subprocess."""
    defaults = {
        "--debug": "true", "--debug-sample-size": "128", "--num-classes": "200",
        "--batch-size": "32", "--width": "32", "--height": "32",
        "--num-epochs": "50", "--synthetic-data": "true", "--validate": "false",
        "--compute-dtype": "float32", "--loader-workers": "2",
        "--log-every-steps": "0",
        "--checkpoint-dir": str(tmp_path / "ckpt"),
        "--log-file": str(tmp_path / "training.log"),
        "--metrics-file": "",
    }
    defaults.update(overrides)
    return [tok for pair in defaults.items() for tok in pair]


def _launch_training(args: list[str], device_count: int = 8) -> subprocess.Popen:
    """Spawn the CLI trainer on a ``device_count``-virtual-device CPU world."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = [
        f for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f
    ]
    env["XLA_FLAGS"] = " ".join(
        flags + [f"--xla_force_host_platform_device_count={device_count}"]
    )
    return subprocess.Popen(
        [sys.executable, "-m", "mpi_pytorch_tpu.train", *args],
        env=env, cwd=REPO, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )


def _await(proc: subprocess.Popen, condition, what: str, deadline_s: float = 300):
    deadline = time.time() + deadline_s
    while time.time() < deadline:
        if condition():
            return
        if proc.poll() is not None:
            pytest.fail(f"training exited early with rc={proc.returncode}")
        time.sleep(0.2)
    pytest.fail(f"{what} within the deadline")


@pytest.mark.slow
def test_sigkill_mid_training_then_resume(tmp_path):
    args = _trainer_args(tmp_path)
    ckpt_dir = str(tmp_path / "ckpt")
    proc = _launch_training(args)
    try:
        # Wait until at least two checkpoints exist, then SIGKILL with the
        # run (and possibly an async write) in flight.
        _await(
            proc,
            lambda: os.path.isdir(ckpt_dir)
            and sum(n.endswith(".msgpack") for n in os.listdir(ckpt_dir)) >= 2,
            "no checkpoints appeared",
        )
        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()

    from mpi_pytorch_tpu import checkpoint as ckpt
    from mpi_pytorch_tpu.config import parse_config
    from mpi_pytorch_tpu.train.trainer import train

    latest = ckpt.latest_checkpoint(ckpt_dir)
    assert latest is not None and latest.endswith(".msgpack")
    killed_epoch = int(ckpt._CKPT_RE.search(os.path.basename(latest)).group(1))

    # Auto-resume from whatever the crash left behind and run to completion.
    cfg = parse_config(
        args + ["--from-checkpoint", "true", "--num-epochs", str(killed_epoch + 3)]
    )
    summary = train(cfg)
    assert summary.epochs_run == 2  # epochs killed+1 .. killed+2
    assert summary.checkpoint_path and os.path.exists(summary.checkpoint_path)
    resumed_epoch = int(
        ckpt._CKPT_RE.search(os.path.basename(summary.checkpoint_path)).group(1)
    )
    assert resumed_epoch == killed_epoch + 2


def test_preemption_guard_flag_and_restore():
    """First signal sets the flag without raising; handlers are restored on
    exit."""
    from mpi_pytorch_tpu.train.trainer import PreemptionGuard

    before = signal.getsignal(signal.SIGTERM)
    with PreemptionGuard() as guard:
        assert not guard.triggered
        signal.raise_signal(signal.SIGTERM)
        assert guard.triggered  # first signal: flag only, no exception
    assert signal.getsignal(signal.SIGTERM) is before


def test_preemption_guard_second_signal_escape_hatch():
    """A second signal defers to the prior handler — for SIGINT, Python's
    default handler, which raises KeyboardInterrupt (the escape hatch when
    the graceful drain itself wedges)."""
    from mpi_pytorch_tpu.train.trainer import PreemptionGuard

    before = signal.getsignal(signal.SIGINT)
    with pytest.raises(KeyboardInterrupt):
        with PreemptionGuard() as guard:
            signal.raise_signal(signal.SIGINT)
            assert guard.triggered
            signal.raise_signal(signal.SIGINT)  # second: prior handler raises
            pytest.fail("second SIGINT must re-raise through the prior handler")
    assert signal.getsignal(signal.SIGINT) is before


@pytest.mark.slow
def test_sigterm_graceful_preemption_then_resume(tmp_path):
    """SIGTERM mid-run → the trainer stops at a safe boundary, saves the last
    COMPLETED epoch even though the periodic save (every 3 epochs) isn't due,
    exits 0, and auto-resume continues from exactly that epoch."""
    args = _trainer_args(
        tmp_path,
        **{
            "--debug-sample-size": "512", "--num-classes": "600",
            "--num-epochs": "500", "--checkpoint-every-epochs": "3",
        },
    )
    ckpt_dir = str(tmp_path / "ckpt")
    log_file = str(tmp_path / "training.log")
    proc = _launch_training(args)
    try:
        _await(
            proc,
            lambda: os.path.exists(log_file) and "Epoch: 1," in open(log_file).read(),
            "epoch 1 never completed",
        )
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=120)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()

    assert rc == 0, f"graceful preemption must exit 0, got {rc}"
    log = open(log_file).read()
    assert "preemption signal" in log
    completed = max(
        int(line.split("Epoch: ")[1].split(",")[0])
        for line in log.splitlines()
        if "Epoch: " in line
    )

    from mpi_pytorch_tpu import checkpoint as ckpt
    from mpi_pytorch_tpu.config import parse_config
    from mpi_pytorch_tpu.train.trainer import train

    latest = ckpt.latest_checkpoint(ckpt_dir)
    assert latest is not None, "preemption must leave a checkpoint"
    saved_epoch = int(ckpt._CKPT_RE.search(os.path.basename(latest)).group(1))
    assert saved_epoch == completed  # the preemption save, not just every-3rd

    cfg = parse_config(
        args + ["--from-checkpoint", "true", "--num-epochs", str(saved_epoch + 3)]
    )
    summary = train(cfg)
    assert summary.epochs_run == 2 and not summary.preempted


@pytest.mark.slow
def test_resume_on_different_world_size(tmp_path):
    """Checkpoints are world-size independent: a run on 8 devices (ZeRO-
    sharded moments included) resumes cleanly on a 4-device world — the
    shrunk-fleet restart a preemptible environment needs. The snapshot
    gather stores replicated arrays, and restore re-shards onto whatever
    mesh exists."""
    args = _trainer_args(
        tmp_path, **{"--num-epochs": "2", "--zero-optimizer": "true"}
    )
    log_file = str(tmp_path / "training.log")
    proc = _launch_training(args, device_count=8)
    assert proc.wait(timeout=300) == 0

    proc = _launch_training(
        args + ["--from-checkpoint", "true", "--num-epochs", "4"],
        device_count=4,
    )
    assert proc.wait(timeout=300) == 0
    log = open(log_file).read()
    assert "resumed from" in log
    assert "8 device(s)" in log and "4 device(s)" in log
    completed = [
        int(line.split("Epoch: ")[1].split(",")[0])
        for line in log.splitlines()
        if "Epoch: " in line
    ]
    assert completed == [0, 1, 2, 3]  # epochs 2-3 ran on the 4-device world
