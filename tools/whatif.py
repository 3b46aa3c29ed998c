"""Offline what-if planner (ISSUE 18): search fleet configs against a
RECORDED workload, ranked by the fitted per-phase latency model, with the
winner validated by actually replaying it.

The closed loop ROADMAP item 4b asks for, first cut:

1. Extract the workload from a fleet-trace JSONL (``obs/replay.py``) and
   fit the per-(model, bucket, precision, residency) device-time +
   queueing model from the same spans (``obs/model.py``).
2. Enumerate candidates over (bucket sets x precision x host count x
   pack budget x max_wait x residency — incl. ``pipe:K``) and rank them
   by model-predicted total p99 (ties break toward fewer hosts — the
   cheaper fleet). Unpriceable residencies are reported, never dropped.
3. ``--validate``: stamp the model's calibration error by replaying on a
   holdout window (the second half of the workload), then replay the
   WINNER on the full workload and check its prediction lands within the
   stamped error. The plan is only as good as that number says it is.

Output is an ``explain()``-style plan (the zoo packing planner's idiom)
plus one ``kind="whatif"`` JSONL record (schema v14). Promoting the
winning plan to the live fleet (ROADMAP 4c) is out of scope here.

Run:  python tools/whatif.py --trace /tmp/fleet_trace.jsonl --smoke \
          --hosts 1,2 --max-wait-ms 2,8 [--validate] [--out whatif.json]
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def rank_candidates(model, workload, *, bucket_sets, precisions, hosts,
                    waits, budgets, residencies=("replicated",)):
    """Every candidate config scored by the fitted model; returns the
    ranked list (best first). Saturated candidates carry the end-of-burst
    backlog-drain queue term, so they still rank against each other
    (more hosts -> smaller backlog) instead of tying on a sentinel.
    ``residencies`` is the ISSUE 20 axis: "replicated"/"tp:K"/"fsdp:K"/
    "pipe:K" candidates price against their OWN fitted trend (pipe keys
    fit from per-stage spans) — one the model never saw is reported
    unpriceable, never silently dropped."""
    from mpi_pytorch_tpu.obs.model import ModelError

    ranked = []
    for bs, prec, h, wait, budget, res in itertools.product(
            bucket_sets, precisions, hosts, waits, budgets, residencies):
        config = {
            "buckets": [int(b) for b in bs.split(",") if b.strip()],
            "max_wait_ms": wait,
            "hosts": h,
            "precision": prec,
            "pack_budget_mb": budget,
        }
        if res and res != "replicated":
            config["residency"] = res
        try:
            pred = model.predict(config, workload)
        except ModelError as e:
            # A candidate the model cannot price (nothing fitted for its
            # precision or residency, say) is reported, not silently
            # dropped.
            ranked.append({"config": config, "error": str(e)})
            continue
        ranked.append({"config": config, "predicted": pred})
    ranked.sort(key=lambda c: (
        c.get("predicted", {}).get("p99_ms", float("inf")),
        c["config"]["hosts"],
        max(c["config"]["buckets"]),
    ))
    for i, c in enumerate(ranked, start=1):
        c["rank"] = i
    return ranked


def explain_plan(ranked, workload, model) -> list:
    """The human-readable plan, one line per candidate (best first)."""
    calib = model.calibration_error_pct
    lines = [
        f"what-if plan [workload {workload.fingerprint}]: "
        f"{len(workload.requests)} arrivals over "
        f"{workload.duration_s:.2f}s ({workload.offered_rps} rps), "
        f"{len(ranked)} candidate(s), calibration "
        + (f"±{calib:.1f}%" if calib is not None else "UNSTAMPED")
    ]
    for c in ranked:
        cfg = c["config"]
        base = (f"  #{c['rank']} buckets={','.join(map(str, cfg['buckets']))}"
                f" precision={cfg['precision'] or '-'} hosts={cfg['hosts']}"
                f" wait={cfg['max_wait_ms']:g}ms"
                + (f" budget={cfg['pack_budget_mb']:g}MB"
                   if cfg.get("pack_budget_mb") else "")
                + (f" residency={cfg['residency']}"
                   if cfg.get("residency") else ""))
        if "error" in c:
            lines.append(base + f" -> UNPRICEABLE ({c['error']})")
            continue
        p = c["predicted"]
        ph = p["per_phase"]
        lines.append(
            base + f" -> p99 {p['p99_ms']:.1f}ms "
            f"(queue {ph['serve/queue']:.1f} + prep "
            f"{ph['serve/preprocess']:.1f} + device "
            f"{ph['serve/device']:.1f}) rho={p['rho']:.2f}"
            + (" SATURATED" if p["saturated"] else ""))
        for note in p.get("notes", []):
            lines.append(f"       note: {note}")
    return lines


def _build_server(cfg_args, config):
    """A real fleet for a candidate config (validation replays only).
    Always a FleetServer — even at one host — because the replayed
    per-phase stats come from its collector, and the trace context is
    minted at the router front door."""
    from mpi_pytorch_tpu.config import Config
    from mpi_pytorch_tpu.serve import FleetServer

    cfg = Config(
        model_name=cfg_args.model, num_classes=cfg_args.num_classes,
        width=cfg_args.image, height=cfg_args.image, synthetic_data=True,
        compute_dtype=cfg_args.compute_dtype,
        serve_buckets=",".join(str(b) for b in config["buckets"]),
        serve_max_wait_ms=config["max_wait_ms"],
        serve_queue_depth=cfg_args.queue_depth,
        serve_topk=cfg_args.topk,
        serve_fleet_hosts=max(1, config["hosts"]),
        trace_sample_rate=1.0,
        serve_collect_interval_s=0.1,
        metrics_file="", log_file="", eval_log_file="",
    )
    cfg.validate_config()
    return FleetServer(cfg, load_checkpoint=False)


def _replay_against(server, workload, args):
    """Replay ``workload`` and return its per-phase stats + total p99."""
    import numpy as np

    from mpi_pytorch_tpu.obs.replay import replay_workload

    rng = np.random.default_rng(args.seed)
    pool = [rng.integers(0, 256, size=(args.image, args.image, 3))
            .astype(np.uint8) for _ in range(32)]
    res = replay_workload(
        lambda i, req: server.submit(pool[i % len(pool)]),
        workload, timeout_s=args.timeout_s)
    collector = getattr(server, "collector", None)
    per_phase = None
    if collector is not None:
        collector.tick()
        per_phase = collector.drain_phase_stats()
    return res, per_phase


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--trace", required=True,
                    help="fleet-trace JSONL to plan against (both the "
                    "workload and the model are fitted from it)")
    ap.add_argument("--bucket-sets", default="1,4;1,8",
                    help="semicolon-separated candidate bucket sets")
    ap.add_argument("--precisions", default="",
                    help="comma list of candidate precisions (default: "
                    "whatever the recorded trace used)")
    ap.add_argument("--hosts", default="1,2,3",
                    help="comma list of candidate host counts")
    ap.add_argument("--max-wait-ms", default="2,8",
                    help="comma list of candidate batching windows")
    ap.add_argument("--residencies", default="",
                    help="comma list of candidate weight residencies "
                    "(replicated, tp:K, fsdp:K, pipe:K; default: every "
                    "residency the fitted trace carries)")
    ap.add_argument("--pack-budgets", default="0",
                    help="comma list of candidate per-host packing budgets "
                    "in MB (0 = unbounded)")
    ap.add_argument("--top", type=int, default=0,
                    help="print only the top N candidates (0 = all)")
    ap.add_argument("--validate", action="store_true",
                    help="stamp the calibration error on a holdout window, "
                    "then replay the WINNER and check its prediction lands "
                    "within the stamped error (exit 1 if it does not)")
    ap.add_argument("--calib-floor-pct", type=float, default=10.0,
                    help="floor on the stamped calibration error — a "
                    "single noisy holdout must not stamp an impossibly "
                    "tight bound (CPU smoke boxes need a generous floor)")
    ap.add_argument("--smoke", action="store_true",
                    help="CPU validation shapes: tiny resnet18, 32px, 64 "
                    "classes (matches bench_serve --smoke)")
    ap.add_argument("--model", default="resnet18")
    ap.add_argument("--image", type=int, default=128)
    ap.add_argument("--num-classes", type=int, default=64500)
    ap.add_argument("--topk", type=int, default=5)
    ap.add_argument("--compute-dtype", default="bfloat16")
    ap.add_argument("--queue-depth", type=int, default=1024)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--timeout-s", type=float, default=120.0)
    ap.add_argument("--out", default="",
                    help="also write the kind='whatif' record to this "
                    "JSONL file (overwritten)")
    args = ap.parse_args()
    if args.smoke:
        args.model, args.image, args.num_classes = "resnet18", 32, 64
        args.topk, args.compute_dtype = 3, "float32"

    if args.validate and args.smoke:
        # Validation builds real servers; --smoke is DEFINED as the CPU
        # mode (set before jax is imported, as in bench_serve).
        os.environ["JAX_PLATFORMS"] = "cpu"

    from mpi_pytorch_tpu.obs.model import ModelError, PhaseLatencyModel
    from mpi_pytorch_tpu.obs.replay import WorkloadError, extract_workload

    try:
        workload = extract_workload(args.trace)
        model = PhaseLatencyModel()
        model.fit_trace(args.trace)
    except (OSError, WorkloadError, ModelError) as e:
        print(f"whatif: {e}", file=sys.stderr)
        return 2

    bucket_sets = [b for b in args.bucket_sets.split(";") if b.strip()]
    if args.precisions:
        precisions = [p.strip() or None
                      for p in args.precisions.split(",")]
    else:
        precisions = sorted(
            {k.precision for k in model.keys}, key=str) or [None]
    hosts = [int(h) for h in args.hosts.split(",") if h.strip()]
    waits = [float(w) for w in args.max_wait_ms.split(",") if w.strip()]
    budgets = [float(b) for b in args.pack_budgets.split(",") if b.strip()]
    if args.residencies:
        residencies = [r.strip() or "replicated"
                       for r in args.residencies.split(",")]
    else:
        residencies = sorted(
            {k.residency for k in model.keys}) or ["replicated"]

    record = {"kind": "whatif", "ts": time.time(),
              "workload": workload.fingerprint}
    ok = True
    if args.validate:
        # Calibration FIRST, on a holdout window (the second half of the
        # workload) replayed under the RECORDED shape — so the error the
        # plan is stamped with predates, and is independent of, the
        # winner comparison below.
        holdout = workload.trim(workload.duration_s / 2.0)
        # Calibrate against a config shaped like the RECORDING: the
        # buckets that actually served it and the host count its
        # serve-side spans came from.
        rec_hosts = set()
        with open(args.trace) as fh:
            for line in fh:
                if '"serve/request"' in line:
                    rec_hosts.add(json.loads(line).get("host"))
        rec_config = {
            "buckets": sorted({r.bucket for r in workload.requests
                               if r.bucket is not None}) or [1],
            "max_wait_ms": waits[0], "hosts": max(1, len(rec_hosts)),
            "precision": precisions[0],
        }
        pred_hold = model.predict(rec_config, holdout)
        server = _build_server(args, rec_config)
        try:
            _, per_phase_hold = _replay_against(server, holdout, args)
        finally:
            server.close()
        if not per_phase_hold:
            print("whatif: holdout replay produced no per-phase stats "
                  "(single-host validation has no collector) — cannot "
                  "stamp calibration", file=sys.stderr)
            return 2
        measured = model.calibrate(pred_hold, per_phase_hold,
                                   window="holdout")
        model.calibration_error_pct = max(measured, args.calib_floor_pct)
        print(f"calibration: measured ±{measured:.1f}% on the "
              f"holdout window (stamped "
              f"±{model.calibration_error_pct:.1f}% with the "
              f"{args.calib_floor_pct:g}% floor)", file=sys.stderr)

    ranked = rank_candidates(
        model, workload, bucket_sets=bucket_sets, precisions=precisions,
        hosts=hosts, waits=waits, budgets=budgets, residencies=residencies)
    shown = ranked[:args.top] if args.top else ranked
    for line in explain_plan(shown, workload, model):
        print(line)
    for line in model.explain():
        print(line)

    priced = [c for c in ranked if "predicted" in c]
    record["ranked"] = [
        {"rank": c["rank"], "config": c["config"],
         **({"p99_ms": c["predicted"]["p99_ms"],
             "per_phase": c["predicted"]["per_phase"],
             "rho": c["predicted"]["rho"],
             "saturated": c["predicted"]["saturated"]}
            if "predicted" in c else {"error": c["error"]})}
        for c in ranked
    ]
    record["candidates"] = len(ranked)
    record["model"] = model.to_record()
    if priced:
        record["winner"] = record["ranked"][priced[0]["rank"] - 1]

    if args.validate and priced:
        winner = priced[0]
        pred = model.predict(winner["config"], workload)
        server = _build_server(args, winner["config"])
        try:
            res, per_phase = _replay_against(server, workload, args)
            compiles = server.stats().get("compiles_after_warmup", 0)
        finally:
            server.close()
        replayed_p99 = res.get("p99_ms")
        if replayed_p99 is None:
            print("whatif: winner replay completed no requests",
                  file=sys.stderr)
            return 1
        err_pct = (100.0 * abs(pred["p99_ms"] - replayed_p99)
                   / max(replayed_p99, 1e-9))
        within = err_pct <= model.calibration_error_pct
        record["validated_p99_ms"] = replayed_p99
        record["within_calibration"] = int(within)
        record["calibration_error_pct"] = model.calibration_error_pct
        print(f"validated winner: predicted p99 {pred['p99_ms']:.1f}ms vs "
              f"replayed {replayed_p99:.1f}ms "
              f"({err_pct:.1f}% off, stamped bound "
              f"±{model.calibration_error_pct:.1f}%) — "
              f"{'WITHIN' if within else 'OUTSIDE'} calibration; "
              f"compiles_after_warmup={compiles}")
        ok = within and compiles == 0

    print(json.dumps(record), flush=True)
    if args.out:
        with open(args.out, "w") as fh:
            fh.write(json.dumps(record) + "\n")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
