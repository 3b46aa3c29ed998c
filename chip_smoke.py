"""Chip smoke: train and evaluate the flagship end to end on the TPU.

The quickest proof that the system still starts on the chip. ONE process
drives the two scripts a user runs — ``python -m mpi_pytorch_tpu.train`` and
``python -m mpi_pytorch_tpu.evaluate`` — through their ``main(argv)`` entry
points at the published widths of the flagship (resnet18, 64 500 classes,
128 px, bf16, fused stem, global batch 1 024, two epochs of seven steps on
seeded synthetic images), then checks what came out by the repo's own
records and exits non-zero, with the reason, on the first miss:

- every step loss finite, the expected number of steps, zero recompiles in
  epoch 1 (``kind="step"`` records);
- the Mosaic kernels are IN the executables that ran — stem forward and
  backward in the train step, stem and ``head_predict`` in the predict step
  (``kind="compile"`` records) — and no give-way warning was logged;
- the state and the batch have a shard on every local device, and every
  device's allocator saw real bytes;
- the evaluator loaded the checkpoint the trainer just wrote, and wrote one
  prediction row per evaluated image.

What the run found goes out as one ``chip_smoke: report {...}`` line (and to
``chiprun_out/chip_smoke/report.json``): the device as JAX reports it,
package versions, the compile-cache directory with entry counts and
persistent-cache hits/misses, per-phase SET-UP seconds, first and final loss,
accuracies. It states no rate and no utilization: this script proves the
path, ``bench.py`` measures it. The LAST stdout line is the pass marker and
nothing else, ``{"ok": true, "device": {"platform", "kind", "count"}}`` —
exactly those keys, which is what the driver parses.

Without an accelerator the script fails before training anything (it asks
JAX for ``tpu`` and nothing else). ``--rehearse`` runs the same sequence on
the CPU at a tiny size with the kernels under their ``MPT_*_INTERPRET``
gates — a check of this script's own control flow — and ends with
``{"rehearsal": true, "platform": "cpu", ...}`` instead of the pass marker.
"""

from __future__ import annotations

import argparse
import csv
import importlib.metadata
import json
import math
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(ROOT, "chiprun_out", "chip_smoke")

# The flagship at its published widths (ISSUE 21). The global batch is 1 024
# whatever the device count, so the one-chip and four-chip runs are the same
# command and their losses compare.
FLAGSHIP = dict(num_classes=64500, image_size=128, batch_size=1024, sample=10000)
# --rehearse: same sequence, sized for a CPU and the Pallas interpreter.
REHEARSAL = dict(num_classes=1000, image_size=32, batch_size=16, sample=80)
EPOCHS = 2


class SmokeFailure(SystemExit):
    """A failed check: the reason on stderr, exit code 1, no result line."""

    def __init__(self, reason: str):
        print(f"chip_smoke: FAILED: {reason}", file=sys.stderr, flush=True)
        super().__init__(1)


def _records(path: str) -> list[dict]:
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def _span_seconds(trace_path: str, name: str) -> float:
    with open(trace_path) as f:
        events = json.load(f)["traceEvents"]
    return round(sum(e["dur"] for e in events if e["name"] == name and e["ph"] == "X") / 1e6, 3)


def _cache_entries(cache_dir: str) -> int:
    return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0


def _check_compile(rec: dict, *, min_mosaic: int, device_ids: list[int]) -> None:
    name = rec["executable"]
    if rec["mosaic_calls"] < min_mosaic:
        raise SmokeFailure(
            f"{name}: {rec['mosaic_calls']} Mosaic custom call(s) in the "
            f"compiled program, expected at least {min_mosaic} — a requested "
            "Pallas kernel was replaced by its XLA composition"
        )
    if rec["devices"] != device_ids:
        raise SmokeFailure(
            f"{name}: inputs have shards on devices {rec['devices']}, "
            f"expected every local device {device_ids}"
        )
    if len(device_ids) > 1 and rec["sharded_inputs"] < 2:
        raise SmokeFailure(
            f"{name}: {rec['sharded_inputs']} input(s) split over "
            f"{len(device_ids)} devices — the batch is not sharded"
        )


def last_line(rehearse: bool, device: dict) -> str:
    """The line the driver parses: ``ok`` and ``device`` and no other key.
    The pass marker belongs to a run on the chip; a rehearsal proves only
    this script's control flow and never prints it."""
    marker = {"rehearsal": True, "platform": "cpu"} if rehearse else {"ok": True}
    return json.dumps({**marker, "device": device})


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--rehearse", action="store_true",
        help="CPU, tiny size, interpreted kernels; never prints the pass marker",
    )
    rehearse = ap.parse_args(argv).rehearse
    try:
        _smoke(rehearse)
    finally:
        # ~0.4 GB a checkpoint at flagship size; the logs, records and
        # predictions beside them are what a failed run is debugged from,
        # and the chip tool brings back 64 MiB.
        shutil.rmtree(os.path.join(OUT, "checkpoints"), ignore_errors=True)


def _smoke(rehearse: bool) -> None:
    t_start = time.perf_counter()
    os.chdir(ROOT)  # the drivers' default data paths are repo-relative
    sys.path.insert(0, ROOT)

    import jax

    # Before anything is printed or the chip is touched: without the repo
    # beside it this script has nothing to prove.
    from mpi_pytorch_tpu import evaluate as evaluator
    from mpi_pytorch_tpu.config import enable_compilation_cache
    from mpi_pytorch_tpu.train import trainer

    # The TPU and nothing else: a machine without a chip raises at the first
    # device use instead of handing back a CPU.
    jax.config.update("jax_platforms", "cpu" if rehearse else "tpu")
    if rehearse:
        os.environ["MPT_STEM_INTERPRET"] = "1"
        os.environ["MPT_HEAD_INTERPRET"] = "1"
    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    versions = {p: importlib.metadata.version(p) for p in ("jax", "jaxlib", "libtpu", "flax")}
    print(f"chip_smoke: device {json.dumps(device)} versions {json.dumps(versions)}", flush=True)
    device_ids = sorted(d.id for d in jax.local_devices())

    cache = {"hits": 0, "misses": 0}

    def on_event(name: str, **_kw) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            cache["hits"] += 1
        elif name == "/jax/compilation_cache/cache_misses":
            cache["misses"] += 1

    jax.monitoring.register_event_listener(on_event)

    # The drivers make this same call; made here first only to learn the
    # directory and count what it holds before the run.
    cache_dir = enable_compilation_cache()
    entries_before = _cache_entries(cache_dir)

    # A stale output directory travels to the chip machine with the tree,
    # and evaluate would load a stale checkpoint from it: start empty.
    shutil.rmtree(OUT, ignore_errors=True)
    os.makedirs(OUT)
    size = REHEARSAL if rehearse else FLAGSHIP
    model_flags = [
        "--model-name", "resnet18",
        "--num-classes", str(size["num_classes"]),
        "--image-size", str(size["image_size"]),
        "--compute-dtype", "bfloat16",
        "--fused-stem", "true",
        "--synthetic-data", "true",
        "--debug", "true",
        "--debug-sample-size", str(size["sample"]),
        "--batch-size", str(size["batch_size"]),
        "--checkpoint-dir", os.path.join(OUT, "checkpoints"),
    ]
    n_train = int(size["sample"] * 0.8)  # data/manifest.py: the debug 80/20 split
    n_test = size["sample"] - n_train
    steps_per_epoch = n_train // size["batch_size"]  # drop_remainder

    # ---- train -----------------------------------------------------------
    train_metrics = os.path.join(OUT, "train_metrics.jsonl")
    train_trace = os.path.join(OUT, "train_trace.json")
    train_log = os.path.join(OUT, "training.log")
    t0 = time.perf_counter()
    summary = trainer.main(
        model_flags + [
            "--compiler-options", "" if rehearse else "xla_tpu_scoped_vmem_limit_kib=65536",
            "--step-metrics", "true",
            "--num-epochs", str(EPOCHS),
            "--log-file", train_log,
            "--metrics-file", train_metrics,
            "--trace-file", train_trace,
        ]
    )
    train_s = time.perf_counter() - t0

    recs = _records(train_metrics)
    steps = [r for r in recs if r["kind"] == "step"]
    if len(steps) != EPOCHS * steps_per_epoch:
        raise SmokeFailure(
            f"{len(steps)} train steps recorded, expected "
            f"{EPOCHS} epochs x {steps_per_epoch}"
        )
    bad = [(r["epoch"], r["step"], r["loss"]) for r in steps if not math.isfinite(r["loss"])]
    if bad:
        raise SmokeFailure(f"non-finite step loss at (epoch, step, loss) {bad[0]}")
    recompiled = [(r["step"], r["recompiles"]) for r in steps if r["epoch"] == 1 and r["recompiles"]]
    if recompiled:
        raise SmokeFailure(f"recompiles in epoch 1 at (step, count) {recompiled}")
    (train_compile,) = [r for r in recs if r["kind"] == "compile"]
    # Stem forward + stem backward.
    _check_compile(train_compile, min_mosaic=0 if rehearse else 2, device_ids=device_ids)
    if summary.checkpoint_path is None or not os.path.isfile(summary.checkpoint_path):
        raise SmokeFailure(f"trainer left no checkpoint file ({summary.checkpoint_path})")
    if summary.val_accuracy is None or not 0.0 <= summary.val_accuracy <= 1.0:
        raise SmokeFailure(f"validation accuracy {summary.val_accuracy} is not a fraction")
    memory = None
    if not rehearse:
        memory = {d.id: d.memory_stats()["peak_bytes_in_use"] for d in jax.local_devices()}
        # Params + Adam moments alone are ~0.5 GB replicated on every chip.
        idle = {i: b for i, b in memory.items() if b < 256 * 2**20}
        if idle:
            raise SmokeFailure(f"devices that never held the state (peak bytes): {idle}")

    # ---- evaluate ----------------------------------------------------------
    eval_metrics = os.path.join(OUT, "eval_metrics.jsonl")
    eval_trace = os.path.join(OUT, "eval_trace.json")
    eval_log = os.path.join(OUT, "evaluation.log")
    predictions = os.path.join(OUT, "predictions.csv")
    t0 = time.perf_counter()
    result = evaluator.main(
        model_flags + [
            "--fused-head-eval", "true",
            "--predictions-file", predictions,
            "--eval-log-file", eval_log,
            "--metrics-file", eval_metrics,
            "--trace-file", eval_trace,
        ]
    )
    eval_s = time.perf_counter() - t0

    with open(eval_log) as f:
        log_text = f.read()
    if f"loaded checkpoint {summary.checkpoint_path} " not in log_text:
        raise SmokeFailure(
            f"evaluator did not load the trainer's checkpoint "
            f"{summary.checkpoint_path} (see {eval_log})"
        )
    with open(train_log) as f:
        log_text += f.read()
    if not rehearse and "falling back" in log_text:
        raise SmokeFailure("a kernel give-way warning was logged (see the logs in " + OUT + ")")
    (predict_compile,) = [r for r in _records(eval_metrics) if r["kind"] == "compile"]
    # Stem (inference form) + head_predict.
    _check_compile(predict_compile, min_mosaic=0 if rehearse else 2, device_ids=device_ids)
    with open(predictions) as f:
        rows = list(csv.DictReader(f))
    if len(rows) != n_test or result.num_images != n_test:
        raise SmokeFailure(
            f"{len(rows)} prediction rows / {result.num_images} images "
            f"evaluated, expected {n_test}"
        )
    if not (math.isfinite(result.mean_loss) and 0.0 <= result.accuracy <= 1.0):
        raise SmokeFailure(f"evaluation loss {result.mean_loss} / accuracy {result.accuracy}")

    report = {
        "rehearsal": rehearse,
        "device": device,
        "versions": versions,
        "steps": len(steps),
        "recompiles_epoch1": sum(r["recompiles"] for r in steps if r["epoch"] == 1),
        "mosaic_calls": {
            "train_step": train_compile["mosaic_calls"],
            "predict": predict_compile["mosaic_calls"],
        },
        "shard_devices": train_compile["devices"],
        "peak_bytes_in_use": memory,
        "first_step_loss": steps[0]["loss"],
        "final_loss": summary.final_loss,
        "val_accuracy": summary.val_accuracy,
        "eval_accuracy": result.accuracy,
        "eval_loss": result.mean_loss,
        "prediction_rows": len(rows),
        "compile_cache": {
            "dir": cache_dir,
            "entries_before": entries_before,
            "entries_after": _cache_entries(cache_dir),
            "hits": cache["hits"],
            "misses": cache["misses"],
        },
        # Set-up time, not speed: wall seconds of each phase of ONE run,
        # compilation included.
        "setup_seconds": {
            "train_build": _span_seconds(train_trace, "build"),
            "train_compile": train_compile["seconds"],
            "train_epochs": [round(r["time_s"], 3) for r in recs if r["kind"] == "epoch"],
            "checkpoint_dispatch": _span_seconds(train_trace, "checkpoint"),
            "validate": _span_seconds(train_trace, "validate"),
            "train_total": round(train_s, 3),
            "predict_compile": predict_compile["seconds"],
            "evaluate_total": round(eval_s, 3),
            "total": round(time.perf_counter() - t_start, 3),
        },
        "claim": None,
    }
    with open(os.path.join(OUT, "report.json"), "w") as f:
        json.dump(report, f, indent=1)
    print(f"chip_smoke: report {json.dumps(report)}", flush=True)
    print(last_line(rehearse, device), flush=True)


if __name__ == "__main__":
    main()
