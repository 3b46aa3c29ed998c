"""Two-process ``jax.distributed`` smoke test (SURVEY §5 comm-backend row;
≙ the reference's multi-node ``mpiexec`` launch, ``README.md:30-38``).

Spawns 2 real OS processes, each with 4 virtual CPU devices, rendezvousing
through a local coordinator — the only way to exercise
``maybe_initialize_distributed`` + the ``make_array_from_process_local_data``
branch of ``shard_batch`` without a TPU pod."""

import os
import socket
import subprocess
import sys

import pytest


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.mark.slow
def test_two_process_distributed_train_step(tmp_path):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    port = _free_port()
    procs = []
    for pid in range(2):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        flags = [
            f for f in env.get("XLA_FLAGS", "").split()
            if "xla_force_host_platform_device_count" not in f
        ]
        env["XLA_FLAGS"] = " ".join(flags + ["--xla_force_host_platform_device_count=4"])
        env["JAX_COORDINATOR_ADDRESS"] = f"127.0.0.1:{port}"
        env["JAX_NUM_PROCESSES"] = "2"
        env["JAX_PROCESS_ID"] = str(pid)
        env["MPT_MULTIHOST"] = "1"
        env["MPT_TEST_SCRATCH"] = str(tmp_path)
        procs.append(
            subprocess.Popen(
                [sys.executable, os.path.join(repo, "tests", "distributed_child.py")],
                env=env, cwd=repo,
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            )
        )
    outs = []
    try:
        for p in procs:
            # Generous: two children × (DP step + two full trainer runs +
            # predictions pass + preemption leg) on one starved CPU core.
            out, _ = p.communicate(timeout=1800)
            outs.append(out)
    finally:  # a hung rendezvous must not leak children holding the port
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
    for p, out in zip(procs, outs):
        assert p.returncode == 0, f"child failed:\n{out}"
    losses = [
        line.split()[1]
        for out in outs
        for line in out.splitlines()
        if line.startswith("DIST_OK")
    ]
    assert len(losses) == 2, outs
    # both processes saw different local data; the all-reduce made them agree
    assert losses[0] == losses[1]
    # The full multi-host trainer run (host_cache, uneven shards, early-close
    # backfill, cached-val adoption): both processes must complete and agree
    # on the globally-reduced per-epoch losses and validation accuracy.
    train_lines = [
        line
        for out in outs
        for line in out.splitlines()
        if line.startswith("TRAIN_OK")
    ]
    assert len(train_lines) == 2, outs
    assert train_lines[0] == train_lines[1], train_lines
    # Sharded device cache across processes: both must complete the
    # scan-epoch cached run and agree on per-epoch losses and accuracy.
    devcache_lines = [
        line
        for out in outs
        for line in out.splitlines()
        if line.startswith("DEVCACHE_OK")
    ]
    assert len(devcache_lines) == 2, outs
    assert devcache_lines[0] == devcache_lines[1], devcache_lines
    # Pipeline parallelism across processes: both ran one PP x DP step on
    # different local data and agree on the all-reduced loss.
    pp_lines = [
        line
        for out in outs
        for line in out.splitlines()
        if line.startswith("PP_OK")
    ]
    assert len(pp_lines) == 2, outs
    assert pp_lines[0] == pp_lines[1], pp_lines
    # Multi-host predictions: both processes ran the sharded predictions
    # pass and agree on its accuracy; process 0 wrote the single CSV.
    pred_lines = [
        line
        for out in outs
        for line in out.splitlines()
        if line.startswith("PRED_OK")
    ]
    assert len(pred_lines) == 2, outs
    assert pred_lines[0] == pred_lines[1], pred_lines
    assert os.path.exists(os.path.join(str(tmp_path), "preds.csv"))
    # Agreed preemption: only process 1 was signaled; process 0 stopped via
    # the epoch-boundary all-reduce, and both agree on the epoch count.
    preempt_lines = [
        line
        for out in outs
        for line in out.splitlines()
        if line.startswith("PREEMPT_OK")
    ]
    assert len(preempt_lines) == 2, outs
    assert preempt_lines[0] == preempt_lines[1], preempt_lines
