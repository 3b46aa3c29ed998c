"""TPU-compiler-option sweep for the headline benchmark (MFU lever hunting).

Runs ``bench.py`` in a fresh child interpreter per option set, parses each
run's one-line JSON, and prints a ranked table. Options travel as
PER-COMPILE ``compiler_options`` (via the ``MPT_COMPILER_OPTIONS`` env JSON
that bench.py/bench_zoo.py read at ``.compile()`` time) — NOT ``XLA_FLAGS``:
jaxlib parses ``XLA_FLAGS`` at start-up and aborts on the ``xla_tpu_*``
flags, which are defined in libtpu (``Unknown flag in XLA_FLAGS``, confirmed
on the v5e machine, PR 21); per-compile options are the channel that
reaches the TPU compiler. The sets below are the standard TPU levers worth
checking for a conv workload; add more on the command line:

    python tools/bench_flags.py                       # sweep the builtin sets
    python tools/bench_flags.py --flags "xla_tpu_scoped_vmem_limit_kib=65536"

The parent never initialises a backend (a chip belongs to one process, and
each child needs it); a child that fails or outlives its timeout is an
error row.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# (label, compiler_options dict). Baseline first; each candidate is one lever.
SWEEP: list[tuple[str, dict]] = [
    ("baseline", {}),
    # Latency-hiding scheduler: overlaps async copies/collectives with
    # compute; mostly a multi-chip lever but can reorder HBM prefetches.
    ("latency-hiding", {"xla_tpu_enable_latency_hiding_scheduler": True}),
    # More VMEM for fusion scratch: lets XLA form larger fusions before
    # spilling to HBM (default is model-dependent).
    ("vmem-64M", {"xla_tpu_scoped_vmem_limit_kib": 65536}),
    ("vmem-128M", {"xla_tpu_scoped_vmem_limit_kib": 131072}),
    # Aggressive while-loop/all-reduce fusion knobs.
    ("fusion-aggr", {"xla_tpu_enable_aggressive_loop_fusion": True}),
]


def _parse_flag_set(text: str) -> dict:
    """CLI "k=v k2=v2" → compiler_options dict — the shared parser behind
    the trainer's --compiler-options (single source of truth for the
    bool/int coercion XLA's option setter requires)."""
    sys.path.insert(0, REPO)
    from mpi_pytorch_tpu.config import parse_compiler_options

    return parse_compiler_options(text) or {}


def run_one(label: str, options: dict, model: str = "") -> dict:
    env = dict(os.environ)
    env["MPT_COMPILER_OPTIONS"] = json.dumps(options)
    # Default: the headline bench.py (resnet18). --model X instead sweeps the
    # flags over any zoo member via a single-model bench_zoo child — the
    # instrument for attacking the bandwidth-bound members (densenet121
    # 16.3%, squeezenet 30.7% MFU, docs/RESULTS.md §3b).
    cmd = (
        [sys.executable, os.path.join(REPO, "bench.py")]
        if not model
        else [
            sys.executable, os.path.join(REPO, "tools", "bench_zoo.py"),
            "--in-process", "--models", model,
        ]
    )
    try:
        proc = subprocess.run(
            cmd, env=env, cwd=REPO, capture_output=True, text=True, timeout=1800,
        )
    except subprocess.TimeoutExpired:
        # One hung flag set must not discard the completed results.
        return {
            "value": 0.0, "error": "child exceeded 1800s",
            "label": label, "flags": options,
        }
    line = ""
    for out_line in (proc.stdout or "").splitlines()[::-1]:
        if out_line.startswith("{"):
            line = out_line
            break
    try:
        rec = json.loads(line)
    except (json.JSONDecodeError, ValueError):
        stderr_tail = (proc.stderr or "").strip().splitlines()[-3:]
        rec = {
            "value": 0.0,
            "error": f"no JSON (rc={proc.returncode}): " + " | ".join(stderr_tail),
        }
    if model and "value" not in rec:
        # bench_zoo rows key throughput differently from bench.py's one-liner.
        rec["value"] = rec.get("images_per_sec_per_chip", 0.0)
    rec["label"] = label
    rec["flags"] = options
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--flags", action="append", default=[],
        help="extra flag set to sweep (repeatable); label = the flags string",
    )
    ap.add_argument(
        "--sets", default=None,
        help="comma-separated subset of builtin set labels to run",
    )
    ap.add_argument(
        "--model", default="",
        help="sweep this zoo model (bench_zoo child) instead of bench.py",
    )
    args = ap.parse_args()
    # --sets filters only the BUILTIN sets; explicit --flags always run.
    sweep = SWEEP
    if args.sets is not None:
        wanted = set(args.sets.split(","))
        known = {s[0] for s in SWEEP}
        unknown = wanted - known
        if unknown:
            ap.error(
                f"unknown --sets label(s) {sorted(unknown)}; "
                f"builtin sets: {sorted(known)}"
            )
        sweep = [s for s in sweep if s[0] in wanted]
    sweep = sweep + [(f, _parse_flag_set(f)) for f in args.flags]

    results = []
    for label, flags in sweep:
        print(f"== {label}: {flags or '(none)'}", file=sys.stderr, flush=True)
        results.append(run_one(label, flags, model=args.model))
        r = results[-1]
        print(
            f"   -> {r.get('value', 0.0):.0f} img/s  mfu={r.get('mfu_pct', '?')}%"
            + (f"  ERROR: {r['error']}" if "error" in r else ""),
            file=sys.stderr, flush=True,
        )

    results.sort(key=lambda r: -float(r.get("value", 0.0)))
    print(json.dumps(results, indent=2))


if __name__ == "__main__":
    main()
