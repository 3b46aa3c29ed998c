"""CPU rehearsal of every cell with ``--trace 1 --rehearse`` (PERF.md
section 7): the harness runs end to end against the program, and the
metrics a CPU can read — spans and counters — are found under their names,
the older ones as before. A device metric is never found here: a CPU run
holds no device plane. One process per cell; ~30 s each."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
SETUP = ["setup.build_s", "setup.compile_s", "setup.load_s", "setup.lower_s"]
STREAM = [
    "input.cast_pct", "input.data_wait_pct", "input.decode_pct", "input.decode_util_pct",
    "input.h2d_pct", "input.put_wait_pct",
]


@pytest.mark.parametrize(
    "cell,chips,found",
    [
        ("r18_train_hbm", 1, SETUP),
        ("vitb16_train_hbm", 1, SETUP),
        ("r18_train_dp4", 4, SETUP),
        ("r18_train_stream", 1, sorted(STREAM + SETUP)),
    ],
)
def test_rehearsal_finds_the_span_and_counter_metrics(cell, chips, found):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={chips}")
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed", "2147483659",
         "--seconds", "3", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] and line["correct"] and line["failed"] == 0
    assert line["metrics_found"] == found
