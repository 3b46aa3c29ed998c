"""The benchmark's one command (BENCHMARK.json ``command``):

    python3 benchmark/run.py --workload NAME --seed N --seconds S --trace 0|1

One run is one process: it resolves the cell by name, generates the inputs
from the seed, lets the cell's driver load, warm up and measure the system
for ``--seconds``, checks the outputs, and prints ONE JSON object as the
last line of stdout. ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics from a profiler trace of a steady
window, with a ``breakdown``.

Everything that belongs to one cell is data or a file of its own, found by
the name in BENCHMARK.json: ``configs/<config>.json`` (the entry's
``file``) with its plain reference and FLOPs count ``reference/<arch>.py``
(the file's ``reference``) and what its samples are ``tasks/<task>.py`` (the
file's ``task``; absent: ``images``), ``traffic/<traffic>.json``,
``drivers/<kind>.py`` (the traffic file's ``driver``), ``recipes/<recipe>.py``
(its dataset's ``recipe``), ``metrics/<metric>.py`` (or ``.json`` naming
another metric's reader). Adding a cell, a configuration, a metric or a task
whose samples are not images (token sequences, say) adds files and
BENCHMARK.json entries and edits nothing here: the harness never looks inside
a ``model`` or a recipe, the task does.

A run that finds no TPU, or another number of chips than the cell asks for,
exits non-zero and prints no result. ``--rehearse`` is the exception made
for this sandbox: the same control flow on the CPU at the tiny sizes the
files give under ``"rehearse"``, Pallas kernels interpreted; it prints
counts and never a result line.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # before the heavy imports: set-up starts here

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def cell_metrics(bench: dict, cell: str, group: str) -> list[dict]:
    """The ``group`` metrics this cell reports: those that list it under
    ``workloads`` or list nothing; per-layer ones only beside the end-to-end
    metric they move."""
    mine = [m for m in bench[group] if cell in m.get("workloads", [cell])]
    if group == "per_layer":
        moved = {m["name"] for m in cell_metrics(bench, cell, "end_to_end")}
        mine = [m for m in mine if m["moves"] in moved]
    return mine


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    bench = _load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = _load_json(os.path.join(ROOT, entry["file"]))
    traffic = _load_json(os.path.join(HERE, "traffic", cell["traffic"] + ".json"))

    os.chdir(ROOT)
    sys.path.insert(0, ROOT)
    from benchmark import tasks

    # A check size that comes out below 1 fails here, not after the window.
    tasks.check_sizes(tasks.load(config), config, args.rehearse)
    import jax

    # The TPU and nothing else: without a chip the first device use raises.
    jax.config.update("jax_platforms", "cpu" if args.rehearse else "tpu")
    devices = jax.devices()
    if len(devices) != cell["chips"]:
        print(
            f"benchmark: {args.workload} asks for {cell['chips']} chip(s), "
            f"JAX finds {len(devices)}", file=sys.stderr,
        )
        return 3

    out_dir = os.path.join(HERE, "out", args.workload)
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    driver = importlib.import_module("benchmark.drivers." + traffic["driver"])
    obs = driver.run(
        {
            "config": config, "traffic": traffic, "chips": cell["chips"],
            "seed": args.seed, "seconds": args.seconds, "trace": bool(args.trace),
            "rehearse": args.rehearse, "out_dir": out_dir,
            "data_root": os.path.join(HERE, "data"), "t_start": T_START,
        }
    )
    obs["device_kind"] = devices[0].device_kind

    trace = None
    if args.trace and obs["xplane"]:
        from benchmark.trace import xplane

        trace = xplane.read(obs["xplane"], {e["name"] for e in obs["spans"]})
        if not trace.devices:
            trace = None  # a CPU rehearsal: host events only
    from benchmark.metrics import load_reader

    group = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for metric in cell_metrics(bench, args.workload, group):
        value = load_reader(metric["name"])(obs, trace)
        if value is not None:
            metrics[metric["name"]] = {"value": value, "unit": metric["unit"]}

    from benchmark import correct

    why_not = correct.check(obs, config, args.seed, rehearse=args.rehearse)
    for reason in why_not:
        print(f"benchmark: NOT CORRECT: {reason}", flush=True)
    # The window's optimizer steps, by the program's own span counter: one
    # span a step when it streams, one an epoch when the epoch is scanned.
    spans = [
        e for e in obs["spans"]
        if e["name"] == "step" and e.get("args", {}).get("epoch", -1) >= obs["warmup_epochs"]
    ]
    attempted = len(spans) * obs["steps_per_program"]
    # Failed: steps the program skipped (where it runs with its skip policy)
    # and every step of an epoch whose loss is not finite.
    failed = sum(
        int(r.get("skipped") or 0) for r in obs["records"]
        if r["kind"] == "step" and r["epoch"] >= obs["warmup_epochs"]
    ) + obs["steps_per_epoch"] * sum(
        not math.isfinite(rec["loss"]) for _, rec in obs["epoch_marks"][obs["warmup_epochs"]:]
    )

    if args.rehearse:
        print(json.dumps({
            "rehearsal": True, "platform": devices[0].platform, "workload": args.workload,
            "correct": not why_not, "attempted": attempted, "failed": failed,
            "metrics_found": sorted(metrics), "epochs": len(obs["epoch_marks"]),
        }))
        return 0

    device = {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices), "memory_peak_bytes": obs["peak_bytes"],
    }
    result = {
        "correct": not why_not, "attempted": attempted, "failed": failed,
        "metrics": metrics, "device": device,
    }
    if trace is not None:
        from benchmark.trace import reduce

        device["busy_s"], device["window_s"] = reduce.busy_and_window_s(trace)
        result["breakdown"] = {
            "device_ops": reduce.top_ops(trace, 0),
            "idle_gaps": reduce.idle_gaps(trace, 0),
        }
    sys.stdout.flush()
    for reason in why_not:  # last on standard error too: what a record of the run keeps
        print(f"benchmark: NOT CORRECT: {reason}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
