"""Load-drive the online inference server: latency percentiles vs load.

Two canonical load shapes, both per (bucket set, max_wait_ms) sweep point:

- **closed loop**: N client threads in a submit→wait→repeat cycle — the
  saturation throughput shape (offered load adapts to service rate).
- **open loop**: seeded-Poisson arrivals at a fixed offered RPS — the SLO
  shape (latency vs offered load, with typed rejections counted instead
  of silently queueing unbounded). Open-loop numbers are the honest ones
  for "can it hold X req/s at Y ms p99" (closed-loop coordinated omission
  hides queueing).

Each run prints ONE ``kind="serve_bench"`` JSONL row (p50/p95/p99 latency,
images/sec, mean batch fill, rejected count, compiles-after-warmup — which
must be 0, the serve subsystem's defining invariant). Rows validate
against ``mpi_pytorch_tpu/obs/schema.py``; the committed artifact is
``docs/serve_bench.json`` (``tools/summarize_benches.py`` renders it).

``--smoke`` is the CPU tier-1 mode: tiny model, two bucket sets, closed +
open loop, seconds not minutes — the shape of the measurement, not a
number worth quoting. Chip rows are staged per the artifact discipline
(docs/RESULTS.md staleness ledger) until a driver-confirmed TPU battery
refreshes them.

``--fleet N`` drives the same sweeps against a local N-host FLEET
(threads on the CPU/host mesh, one shared executable set) through the
load-aware router (``serve/fleet/``): rejections are the front door's
admission control, and each row gains ``fleet_hosts`` plus a ``per_host``
fill/latency breakdown from the hosts' registry snapshots — how evenly
the router actually spread the load.

``--precision bf16,int8`` sweeps the serving precision (ISSUE 11): both
values build ONE server holding both startup-compiled executable sets
and switch live between them (``set_precision`` — the same no-compile
lever the fleet controller retunes), so the bf16 and int8 points share
params, warmup, and load shape. Rows carry ``precision``, and int8 rows
carry ``parity_top1`` — the startup int8-vs-bf16 top-1 agreement the
throughput claim is conditioned on.

``--models resnet18,mobilenet_v2`` turns the sweep multi-tenant
(ISSUE 14): ONE zoo server/fleet holds every tenant's executable sets,
the load driver interleaves per-tenant traffic from a seeded assignment
sequence (``--hot-model X`` skews 80% onto one tenant — the starvation
drill), and every sweep point yields one row PER TENANT (model-keyed
p99/fill/rejected columns, ``load_shape`` stamped). ``model`` +
``load_shape`` key into ``check_regression``'s serve trend-line
identity, so tenant rows never compare cross-model or cross-shape.

``--replay <trace>`` (ISSUE 18) swaps the synthetic load for a RECORDED
one: the fleet trace's ``route/request`` roots are extracted into a
fingerprinted workload artifact (``obs/replay.py``) and their exact
arrival process is re-driven against the candidate config, over any
transport. Rows stamp ``mode="replay"``, the workload fingerprint (its
own regression trend line — never compared against synthetic Poisson),
and ``replay_diff`` — the recorded-vs-replayed per-phase differential
report. Record with ``--fleet N --trace-sample-rate 1.0
--fleet-trace-file t.jsonl``; replay with ``--replay t.jsonl``
(``--speed``/``--replay-window`` warp and trim, changing the
fingerprint).

Run: ``python tools/bench_serve.py --smoke [--out docs/serve_bench.json]``
     ``python tools/bench_serve.py --bucket-sets "1,8,32,128;1,32,512" \
        --max-wait-ms 2,5,10 --requests 2000 --rps 0,500,2000``
     ``python tools/bench_serve.py --smoke --fleet 3``
     ``python tools/bench_serve.py --smoke --precision bf16,int8``
     ``python tools/bench_serve.py --smoke --fleet 2 \
        --models resnet18,mobilenet_v2 [--hot-model resnet18]``
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _percentiles(lat_ms: list[float]) -> dict:
    if not lat_ms:
        # A fully-rejected sweep point (offered load >> capacity with a
        # small queue) is a VALID result — the row must report rejected=N,
        # not crash the sweep on an empty percentile.
        return {"p50_ms": 0.0, "p95_ms": 0.0, "p99_ms": 0.0}
    arr = np.asarray(lat_ms, np.float64)
    return {
        "p50_ms": round(float(np.percentile(arr, 50)), 3),
        "p95_ms": round(float(np.percentile(arr, 95)), 3),
        "p99_ms": round(float(np.percentile(arr, 99)), 3),
    }


def _image_pool(n: int, size: tuple[int, int], seed: int) -> list[np.ndarray]:
    """Distinct uint8 request images (raw pixels, so the server's
    preprocess pool does real normalize work per request)."""
    rng = np.random.default_rng(seed)
    return [
        rng.integers(0, 256, size=(*size, 3)).astype(np.uint8) for _ in range(n)
    ]


def closed_loop(server, pool, requests: int, concurrency: int, timeout_s: float):
    """N clients in submit→wait→repeat; returns (latencies_ms, wall_s, rejected)."""
    lat_ms: list[float] = []
    rejected = [0]
    lock = threading.Lock()
    counter = [0]

    from mpi_pytorch_tpu.serve import QueueFullError

    def client() -> None:
        while True:
            with lock:
                i = counter[0]
                if i >= requests:
                    return
                counter[0] += 1
            t0 = time.monotonic()
            try:
                server.submit(pool[i % len(pool)]).result(timeout=timeout_s)
            except QueueFullError:
                with lock:
                    rejected[0] += 1
                continue
            dt = 1e3 * (time.monotonic() - t0)
            with lock:
                lat_ms.append(dt)

    t0 = time.monotonic()
    threads = [threading.Thread(target=client) for _ in range(concurrency)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return lat_ms, time.monotonic() - t0, rejected[0]


def open_loop(server, pool, requests: int, rps: float, seed: int, timeout_s: float):
    """Seeded-Poisson arrivals at ``rps``; latency measured per request
    from its (intended) submit; full-queue submissions count as rejected.

    The client HONORS the rejection's ``retry_after_ms`` hint (ISSUE 12
    satellite): after a hinted 429/QueueFullError, no submission goes out
    before the hint expires — arrivals due inside the backoff window are
    deferred to its edge (still counted at their deferred submit time),
    instead of hammering a host that just said "not before T". A
    saturated sweep point therefore measures the BACKPRESSURE PROTOCOL's
    throughput, not a retry storm's."""
    from mpi_pytorch_tpu.serve import QueueFullError

    rng = np.random.default_rng(seed)
    gaps = rng.exponential(1.0 / rps, size=requests)
    lat_ms: list[float] = []
    lock = threading.Lock()
    futures = []
    rejected = 0
    backoff_until = 0.0
    t0 = time.monotonic()
    next_t = t0
    for i in range(requests):
        next_t += gaps[i]
        if next_t < backoff_until:
            next_t = backoff_until  # defer to the hint's edge, don't hammer
        delay = next_t - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        t_submit = time.monotonic()
        try:
            fut = server.submit(pool[i % len(pool)])
        except QueueFullError as e:
            rejected += 1
            if e.retry_after_ms:
                backoff_until = max(
                    backoff_until, time.monotonic() + e.retry_after_ms / 1e3
                )
            continue

        def _done(f, t_submit=t_submit):
            dt = 1e3 * (time.monotonic() - t_submit)
            with lock:
                lat_ms.append(dt)

        fut.add_done_callback(_done)
        futures.append(fut)
    for f in futures:
        f.result(timeout=timeout_s)
    return lat_ms, time.monotonic() - t0, rejected


def _delta_mean(snap1, snap0, hist_name):
    """Mean of a registry histogram over THIS sweep point only: the
    sketches are cumulative across a server's life, so per-point means
    come from (sum, count) deltas (percentiles cannot be delta'd from
    summaries — the per-point tail is the top-level row's job)."""
    h1 = snap1.get("histograms", {}).get(hist_name) or {}
    h0 = snap0.get("histograms", {}).get(hist_name) or {}
    n = h1.get("count", 0) - h0.get("count", 0)
    if n <= 0:
        return None
    return round((h1.get("sum", 0.0) - h0.get("sum", 0.0)) / n, 3)


def _per_host_breakdown(snaps0, snaps1, stats0, stats1) -> dict:
    """The --fleet rows' per-host fill/latency table — all values are
    deltas over this sweep point (a host promoted mid-point, e.g. the
    spare after a failover, diffs against empty)."""
    out = {}
    for name, snap in sorted(snaps1.items()):
        snap0 = snaps0.get(name, {})
        served0 = stats0["hosts"].get(name, {}).get("served", 0)
        served1 = stats1["hosts"].get(name, {}).get("served", 0)
        out[name] = {
            "requests": served1 - served0,
            "fill_pct": _delta_mean(snap, snap0, "serve/fill_pct"),
            "mean_ms": _delta_mean(
                snap, snap0, "serve/request_latency_ms"
            ),
        }
    return out


def run_point_tenants(server, pool, models, weights, *, mode, requests,
                      concurrency, rps, seed, timeout_s, fleet_hosts=0,
                      load_shape="uniform"):
    """Multi-tenant sweep point (ISSUE 14): one seeded tenant-assignment
    sequence drives interleaved traffic across ``models`` (weighted —
    the hot-tenant skewed shape), latencies/rejections tally PER TENANT,
    and the point yields one ``serve_bench`` row per tenant (p99 / fill /
    rejected columns each under its ``model`` key).

    Open-loop arrivals for a tenant inside its own ``retry_after_ms``
    backoff window are SHED client-side (counted rejected) — per-tenant
    backpressure must not distort the other tenants' arrival process."""
    from mpi_pytorch_tpu.serve import QueueFullError

    rng = np.random.default_rng(seed)
    assign = rng.choice(len(models), size=requests, p=weights)
    stats0 = server.tenant_stats()
    lat = {m: [] for m in models}
    rejected = {m: 0 for m in models}
    lock = threading.Lock()

    if mode == "open":
        gaps = rng.exponential(1.0 / rps, size=requests)
        backoff_until = {m: 0.0 for m in models}
        futures = []
        t0 = time.monotonic()
        next_t = t0
        for i in range(requests):
            model = models[int(assign[i])]
            next_t += gaps[i]
            delay = next_t - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            if time.monotonic() < backoff_until[model]:
                rejected[model] += 1  # shed: the tenant said "not yet"
                continue
            t_submit = time.monotonic()
            try:
                fut = server.submit(pool[i % len(pool)], model=model)
            except QueueFullError as e:
                rejected[model] += 1
                if e.retry_after_ms:
                    backoff_until[model] = max(
                        backoff_until[model],
                        time.monotonic() + e.retry_after_ms / 1e3,
                    )
                continue

            def _done(f, m=model, t_submit=t_submit):
                dt = 1e3 * (time.monotonic() - t_submit)
                with lock:
                    lat[m].append(dt)

            fut.add_done_callback(_done)
            futures.append(fut)
        for f in futures:
            f.result(timeout=timeout_s)
        wall = time.monotonic() - t0
    else:
        counter = [0]

        def client() -> None:
            while True:
                with lock:
                    i = counter[0]
                    if i >= requests:
                        return
                    counter[0] += 1
                model = models[int(assign[i])]
                t_submit = time.monotonic()
                try:
                    server.submit(
                        pool[i % len(pool)], model=model
                    ).result(timeout=timeout_s)
                except QueueFullError:
                    with lock:
                        rejected[model] += 1
                    continue
                dt = 1e3 * (time.monotonic() - t_submit)
                with lock:
                    lat[model].append(dt)

        t0 = time.monotonic()
        threads = [threading.Thread(target=client) for _ in range(concurrency)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.monotonic() - t0

    stats1 = server.tenant_stats()
    compiles = server.stats()["compiles_after_warmup"]
    rows = []
    for m in models:
        s0, s1 = stats0.get(m, {}), stats1.get(m, {})
        served = s1.get("served", 0) - s0.get("served", 0)
        padded = s1.get("padded_rows", 0) - s0.get("padded_rows", 0)
        fill = served / (served + padded) if served + padded else 0.0
        share = float(weights[models.index(m)])
        rows.append({
            "kind": "serve_bench",
            "ts": time.time(),
            "mode": mode,
            "model": m,
            "load_shape": load_shape,
            "requests": len(lat[m]),
            "rejected": rejected[m],
            "offered_rps": round(rps * share, 1) if mode == "open" else None,
            "images_per_sec": (
                round(len(lat[m]) / wall, 1) if wall > 0 else 0.0
            ),
            "mean_fill_ratio": round(fill, 4),
            "compiles_after_warmup": compiles,
            **_percentiles(lat[m]),
        })
        if fleet_hosts:
            rows[-1]["fleet_hosts"] = fleet_hosts
    return rows


def _sum_host_stat(stats: dict, key: str) -> int:
    """Sum ``key`` across a fleet's per-host stats (or read it straight
    off a single server's stats)."""
    if "hosts" in stats:
        return sum(s.get(key, 0) for s in stats["hosts"].values())
    return stats.get(key, 0)


def run_point(server, pool, *, mode, requests, concurrency, rps, seed, timeout_s,
              fleet_hosts=0):
    stats0 = server.stats()
    snaps0 = server.host_snapshots() if fleet_hosts else None
    if mode == "open":
        lat_ms, wall, rejected = open_loop(
            server, pool, requests, rps, seed, timeout_s
        )
    else:
        lat_ms, wall, rejected = closed_loop(
            server, pool, requests, concurrency, timeout_s
        )
    stats1 = server.stats()
    served = stats1["served"] - stats0["served"]
    padded = stats1["padded_rows"] - stats0["padded_rows"]
    fill = served / (served + padded) if served + padded else 0.0
    row = {
        "kind": "serve_bench",
        "ts": time.time(),
        "mode": mode,
        "requests": len(lat_ms),
        "rejected": rejected,
        "offered_rps": round(rps, 1) if mode == "open" else None,
        "images_per_sec": round(len(lat_ms) / wall, 1) if wall > 0 else 0.0,
        "mean_fill_ratio": round(fill, 4),
        "compiles_after_warmup": stats1["compiles_after_warmup"],
        **_percentiles(lat_ms),
    }
    # Zero-copy assertion (ISSUE 16): input bytes touched exactly once
    # between the transport and device_put — the ledger-checked number.
    copies = _sum_host_stat(stats1, "input_copies") - _sum_host_stat(
        stats0, "input_copies"
    )
    if served > 0 and copies > 0:
        row["copies_per_request"] = round(copies / served, 6)
    hedges1 = stats1.get("router", {}).get("hedges")
    if hedges1 is not None:
        row["hedged"] = hedges1 - (stats0.get("router", {}).get("hedges") or 0)
    if fleet_hosts:
        row["fleet_hosts"] = fleet_hosts
        row["per_host"] = _per_host_breakdown(
            snaps0, server.host_snapshots(), stats0, stats1
        )
    return row


def run_point_replay(server, pool, workload, *, timeout_s, fleet_hosts=0,
                     use_models=False):
    """One trace-replay sweep point (ISSUE 18): re-drive the workload's
    RECORDED arrival process against the candidate server. Latency is
    measured from each intended arrival instant (open_loop semantics).
    Admission rejections are SHED, never deferred — a deferral would
    distort the recorded arrival process the row claims to have replayed,
    so the reject count is the candidate config's honest admission answer
    to this exact load shape."""
    from mpi_pytorch_tpu.obs.replay import replay_workload

    stats0 = server.stats()
    snaps0 = server.host_snapshots() if fleet_hosts else None

    def submit(i, req):
        if use_models and req.model is not None:
            return server.submit(pool[i % len(pool)], model=req.model)
        return server.submit(pool[i % len(pool)])

    res = replay_workload(submit, workload, timeout_s=timeout_s)
    stats1 = server.stats()
    served = stats1["served"] - stats0["served"]
    padded = stats1["padded_rows"] - stats0["padded_rows"]
    fill = served / (served + padded) if served + padded else 0.0
    if res["failed"]:
        print(f"WARNING: {res['failed']} replayed request(s) FAILED "
              "(not admission rejects) — the row undercounts them",
              file=sys.stderr)
    row = {
        "kind": "serve_bench",
        "ts": time.time(),
        "mode": "replay",
        "requests": res["accepted"],
        "rejected": res["rejected"],
        "offered_rps": workload.offered_rps,
        "images_per_sec": res["images_per_sec"],
        "mean_fill_ratio": round(fill, 4),
        "compiles_after_warmup": stats1["compiles_after_warmup"],
        **_percentiles(res["lat_ms"]),
    }
    copies = _sum_host_stat(stats1, "input_copies") - _sum_host_stat(
        stats0, "input_copies"
    )
    if served > 0 and copies > 0:
        row["copies_per_request"] = round(copies / served, 6)
    hedges1 = stats1.get("router", {}).get("hedges")
    if hedges1 is not None:
        row["hedged"] = hedges1 - (stats0.get("router", {}).get("hedges") or 0)
    if fleet_hosts:
        row["fleet_hosts"] = fleet_hosts
        row["per_host"] = _per_host_breakdown(
            snaps0, server.host_snapshots(), stats0, stats1
        )
    return row


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--model", default="resnet18")
    ap.add_argument("--image", type=int, default=128)
    ap.add_argument("--num-classes", type=int, default=64500)
    ap.add_argument("--topk", type=int, default=5)
    ap.add_argument("--compute-dtype", default="bfloat16")
    ap.add_argument("--bucket-sets", default="1,8,32,128;1,32,512",
                    help="semicolon-separated bucket SETS; one server build "
                    "(and one warmup compile set) per entry")
    ap.add_argument("--max-wait-ms", default="5",
                    help="comma list; swept live per server (no recompile)")
    ap.add_argument("--requests", type=int, default=2000)
    ap.add_argument("--concurrency", type=int, default=32,
                    help="closed-loop client threads")
    ap.add_argument("--rps", default="0",
                    help="comma list of offered open-loop rates; 0 = closed "
                    "loop only for that sweep point")
    ap.add_argument("--queue-depth", type=int, default=1024)
    ap.add_argument("--fleet", type=int, default=0,
                    help="N > 0: drive a local N-host fleet (threads, one "
                    "shared executable set) through the load-aware router "
                    "instead of a single server; rows gain fleet_hosts + "
                    "the per_host fill/latency breakdown")
    ap.add_argument("--transport", default="local",
                    choices=("local", "remote", "framed"),
                    help="remote (needs --fleet N): each host is a REAL "
                    "python -m mpi_pytorch_tpu.serve.host subprocess and "
                    "requests cross the wire (serve/fleet/remote.py); rows "
                    "gain transport='http' so check_regression never "
                    "compares them against in-process baselines. framed "
                    "(ISSUE 16): same subprocess fleet, but the data plane "
                    "is the binary framed wire (serve/wire.py — persistent "
                    "pooled connections, pipelining, CANCEL); rows stamp "
                    "transport='framed' (its own trend line)")
    ap.add_argument("--hedge", action="store_true",
                    help="with --transport framed and --fleet >= 2: hedge "
                    "tail requests to the second-best host after a per-host "
                    "p99-derived deadline, first completion wins, loser "
                    "CANCELled over the wire; rows stamp "
                    "transport='framed+hedge' and the hedged count")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--timeout-s", type=float, default=120.0)
    ap.add_argument("--fused-head", action="store_true",
                    help="serve through ops.fused_head_ce.head_predict "
                    "(TPU; forces topk=1)")
    ap.add_argument("--precision", default="bf16",
                    help="comma list over {bf16,int8}; both values build "
                    "ONE server holding both startup-compiled executable "
                    "sets and sweep by switching live (no recompile); "
                    "int8 rows carry the startup parity_top1 stamp")
    ap.add_argument("--models", default="",
                    help="comma list of tenant specs (ISSUE 14, e.g. "
                    "'resnet18,mobilenet_v2'): ONE multi-tenant server/"
                    "fleet serves the whole zoo, sweeps drive interleaved "
                    "per-tenant traffic, and each sweep point yields one "
                    "row PER TENANT (model-keyed p99/fill/rejected "
                    "columns; check_regression keys model + load_shape "
                    "into the trend-line identity)")
    ap.add_argument("--hot-model", default="",
                    help="with --models: skew the offered load onto this "
                    "tenant (80%% hot / 20%% split over the rest) — the "
                    "hot-tenant starvation shape; rows stamp "
                    "load_shape='hot:<model>'")
    ap.add_argument("--pack-budget-mb", type=float, default=0.0,
                    help="with --models: the per-host packing budget "
                    "(serve_pack_budget_mb; 0 = unbounded)")
    ap.add_argument("--trace-sample-rate", type=float, default=0.0,
                    help="> 0 (needs --fleet N): distributed tracing at "
                    "the router front door + the FleetCollector, and each "
                    "row gains per_phase — the collector-derived "
                    "queue/preprocess/device/wire p50/p99 breakdown for "
                    "that sweep point (ISSUE 13)")
    ap.add_argument("--replay", default="",
                    help="path to a fleet-trace JSONL (or a saved workload "
                    "artifact) to REPLAY (ISSUE 18): re-drive the recorded "
                    "arrival process — not Poisson — against the candidate "
                    "config over either transport. --rps is ignored; each "
                    "(bucket set, precision, wait) point yields one "
                    "mode='replay' row stamped with the workload "
                    "fingerprint and the recorded-vs-replayed differential "
                    "report (replay_diff)")
    ap.add_argument("--speed", type=float, default=1.0,
                    help="with --replay: time-warp factor (2.0 = replay "
                    "twice as fast). Warping changes the workload "
                    "fingerprint — a warped replay is its own trend line; "
                    "rows also stamp speed")
    ap.add_argument("--replay-window", default="",
                    help="with --replay: 'START,END' arrival-offset window "
                    "in seconds — trim the workload to arrivals in "
                    "[START, END) before replaying")
    ap.add_argument("--fleet-trace-file", default="",
                    help="with --fleet N and --trace-sample-rate > 0: write "
                    "the kept traces to this JSONL — the RECORD half of "
                    "the record-and-replay recipe (record at sample rate "
                    "1.0 for an exact workload)")
    ap.add_argument("--canary-probes", type=int, default=0,
                    help="arm the golden-set quality canary (ISSUE 19): N "
                         "shadow probes per tenant per swept point through "
                         "the fleet front door; rows gain agreement_top1 "
                         "(needs a local --fleet N)")
    ap.add_argument("--drift-window", type=int, default=0,
                    help="arm prediction-drift detection: per-tenant top-1 "
                         "histograms over windows of N real requests "
                         "(needs a local --fleet N)")
    ap.add_argument("--serve-shard-degree", type=int, default=1,
                    help="> 1: single-model MODEL-parallel serving — "
                    "params fsdp:K-sharded over the model axis of a "
                    "nested (data, model) serve mesh (ISSUE 17); rows "
                    "gain shard_degree and key a separate trend line")
    ap.add_argument("--serve-pipe-stages", type=int, default=1,
                    help="> 1: single-model PIPELINE-parallel serving — "
                    "the model stage-split over K chip groups of a nested "
                    "(data, pipe) serve mesh, flushes streamed through as "
                    "micro-batches (ISSUE 20); rows gain pipe_stages + "
                    "bubble_frac and key a separate trend line")
    ap.add_argument("--out", default="",
                    help="also write rows to this JSONL file (overwritten)")
    ap.add_argument("--smoke", action="store_true",
                    help="CPU tier-1 mode: tiny model, two bucket sets, "
                    "closed+open loop, seconds not minutes")
    args = ap.parse_args()

    if args.smoke:
        args.model, args.image, args.num_classes = "resnet18", 32, 64
        args.topk, args.compute_dtype = 3, "float32"
        # Fleet smoke: one bucket set (the hosts share its executables,
        # but each SET is a fresh fleet build — keep tier-1 cheap).
        args.bucket_sets = "1,4" if (args.fleet or args.models) else "1,4;1,8"
        args.max_wait_ms, args.requests, args.concurrency = "2", 48, 8
        args.rps = "0,400"

    if args.smoke:
        # --smoke is DEFINED as the CPU mode: it must never take a chip,
        # and neither may the host processes a remote transport spawns
        # (they inherit the environment). Set before jax is imported.
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    from mpi_pytorch_tpu.config import Config
    from mpi_pytorch_tpu.serve import FleetServer, InferenceServer, RemoteFleet

    if args.transport in ("remote", "framed") and args.fleet <= 0:
        print(f"--transport {args.transport} needs --fleet N (N >= 1)",
              file=sys.stderr)
        return 2
    if args.hedge and (args.transport != "framed" or args.fleet < 2):
        print("--hedge needs --transport framed and --fleet >= 2 (a hedge "
              "needs a second host and a CANCEL-capable wire)",
              file=sys.stderr)
        return 2
    if args.trace_sample_rate > 0 and args.fleet <= 0:
        # The trace id is minted at the FRONT DOOR, which is the fleet
        # router — a single bare server has no front door to mint at.
        print("--trace-sample-rate needs --fleet N (the router is the "
              "minting front door)", file=sys.stderr)
        return 2
    if args.serve_shard_degree > 1 and (args.fleet > 0 or args.models):
        # The single-model knob: a fleet's hosts each own the full mesh,
        # and zoo tenants pick residency per-spec (shard=K) or from the
        # packing planner instead.
        print("--serve-shard-degree needs a bare single-model server "
              "(no --fleet/--models)", file=sys.stderr)
        return 2
    if args.serve_pipe_stages > 1 and (
            args.fleet > 0 or args.models or args.serve_shard_degree > 1):
        # Same single-model scoping as the shard knob, and pipe/fsdp are
        # rival layouts of the same chips (config.validate_config agrees).
        print("--serve-pipe-stages needs a bare single-model server "
              "(no --fleet/--models/--serve-shard-degree)", file=sys.stderr)
        return 2
    if (args.canary_probes or args.drift_window) and (
            args.fleet <= 0 or args.transport != "local"):
        # The gate/prober live in FleetServer; remote hosts are separate
        # processes whose fleet object is theirs, not ours.
        print("--canary-probes/--drift-window need a local --fleet N "
              "(the canary gate and prober are FleetServer wiring)",
              file=sys.stderr)
        return 2
    workload = None
    if args.replay:
        from mpi_pytorch_tpu.obs.replay import WorkloadError, load_workload

        try:
            workload = load_workload(args.replay)
            if args.replay_window:
                try:
                    start_s, end_s = (
                        float(x) for x in args.replay_window.split(","))
                except ValueError:
                    print("--replay-window wants 'START,END' seconds",
                          file=sys.stderr)
                    return 2
                workload = workload.trim(start_s, end_s)
            if args.speed != 1.0:
                # Warp HERE so the fingerprint stamped on rows identifies
                # the arrival process actually replayed.
                workload = workload.warp(args.speed)
        except (OSError, WorkloadError) as e:
            print(f"--replay: {e}", file=sys.stderr)
            return 2
        if workload.defaults_applied:
            print(f"note: {workload.defaults_applied} recorded request(s) "
                  "predate schema v14 root attrs — replayed with documented "
                  "defaults (model=None, rows=1)", file=sys.stderr)
        print(f"replaying workload {workload.fingerprint}: "
              f"{len(workload.requests)} arrivals over "
              f"{workload.duration_s:.2f}s ({workload.offered_rps} rps)",
              file=sys.stderr)

    out_rows = []
    pool = _image_pool(32, (args.image, args.image), args.seed)
    waits = [float(w) for w in args.max_wait_ms.split(",") if w.strip()]
    rates = [float(r) for r in args.rps.split(",") if r.strip()]
    if workload is not None:
        rates = [0.0]  # one replay point per (set, precision, wait)
    precisions = [p.strip() for p in args.precision.split(",") if p.strip()]
    bad_prec = sorted(set(precisions) - {"bf16", "int8"})
    if not precisions or bad_prec:
        print(f"unknown --precision value(s): {bad_prec}", file=sys.stderr)
        return 2
    # Any int8 point needs the bf16 set too — it is the parity REFERENCE:
    # an int8 row without its parity_top1 stamp is half a row (the v7
    # schema contract), so an int8-only sweep still builds both sets and
    # just doesn't drive the bf16 one. A bf16-only sweep builds one set.
    serve_precision = "both" if "int8" in precisions else "bf16"
    # Stamp rows only when the precision axis is LIVE (non-bf16 involved):
    # a default pure-bf16 run keeps v6-identical rows, so its trend lines
    # keep pairing with pre-v7 baselines (the serve-record rule).
    stamp_precision = "int8" in precisions
    tenant_models: list[str] = []
    tenant_weights: list[float] = []
    load_shape = "uniform"
    if args.models:
        from mpi_pytorch_tpu.serve.zoo import parse_model_specs

        tenant_models = [s.model for s in parse_model_specs(args.models)]
        if args.hot_model:
            if args.hot_model not in tenant_models:
                print(f"--hot-model {args.hot_model!r} is not in --models",
                      file=sys.stderr)
                return 2
            if len(tenant_models) < 2:
                print("--hot-model needs >= 2 tenants", file=sys.stderr)
                return 2
            # The hot-tenant skewed shape: 80% of offered load on the
            # hot tenant, the rest split evenly — the starvation drill.
            cold_share = 0.2 / (len(tenant_models) - 1)
            tenant_weights = [
                0.8 if m == args.hot_model else cold_share
                for m in tenant_models
            ]
            load_shape = f"hot:{args.hot_model}"
        else:
            tenant_weights = [1.0 / len(tenant_models)] * len(tenant_models)
    elif args.hot_model or args.pack_budget_mb:
        print("--hot-model/--pack-budget-mb need --models", file=sys.stderr)
        return 2

    for bucket_set in [b for b in args.bucket_sets.split(";") if b.strip()]:
        cfg = Config(
            model_name=args.model, num_classes=args.num_classes,
            width=args.image, height=args.image, synthetic_data=True,
            compute_dtype=args.compute_dtype, serve_buckets=bucket_set,
            serve_max_wait_ms=waits[0], serve_queue_depth=args.queue_depth,
            serve_topk=args.topk, fused_head_eval=args.fused_head,
            serve_fleet_hosts=max(0, args.fleet),
            serve_precision=serve_precision,
            serve_models=args.models,
            serve_pack_budget_mb=args.pack_budget_mb,
            serve_shard_degree=max(1, args.serve_shard_degree),
            serve_pipe_stages=max(1, args.serve_pipe_stages),
            serve_transport="framed" if args.transport == "framed"
            else "http",
            serve_hedge=args.hedge,
            trace_sample_rate=args.trace_sample_rate,
            fleet_trace_file=args.fleet_trace_file,
            # The collector is what derives the per-phase breakdown; a
            # tight scrape keeps the sweep point's spans inside the point.
            serve_collect_interval_s=0.1 if args.trace_sample_rate > 0
            else 0.0,
            serve_canary_probes=max(0, args.canary_probes),
            serve_drift_window=max(0, args.drift_window),
            metrics_file="", log_file="", eval_log_file="",
        )
        cfg.validate_config()
        if args.transport in ("remote", "framed"):
            server = RemoteFleet(cfg)
        elif args.fleet > 0:
            server = FleetServer(cfg, load_checkpoint=False)
        elif args.models:
            from mpi_pytorch_tpu.serve.zoo import ZooServer

            server = ZooServer(cfg, load_checkpoint=False)
        else:
            server = InferenceServer(cfg, load_checkpoint=False)
        # The parent of a remote fleet stays OFF the device — a chip belongs
        # to one process, and the hosts need it: the chip count is what
        # each host reported at readiness.
        chips = (
            sum(h.chips or 0 for h in server.router.active_hosts())
            if args.transport in ("remote", "framed")
            else jax.device_count()
        )
        if args.canary_probes and getattr(server, "prober", None) is not None:
            # Pin the healthy references BEFORE the sweep, with the
            # quality-fault gate disarmed: the bench's references are
            # ground truth by construction, so a drill fault (the
            # logit-noise gate pair below) must surface as sweep-row
            # disagreement — never silently poison the baseline the
            # sweep is scored against.
            _noise_gates = {
                k: os.environ.pop(k)
                for k in ("MPT_FAULT_LOGIT_NOISE_PCT",
                          "MPT_FAULT_LOGIT_NOISE_MODEL")
                if k in os.environ
            }
            try:
                server.prober.probe_once()
            finally:
                os.environ.update(_noise_gates)
        try:
            for precision in precisions:
                if server.precision != precision:
                    server.set_precision(precision)
                for wait_ms in waits:
                    server.set_max_wait_ms(wait_ms)
                    for rps in rates:
                        mode = "open" if rps > 0 else "closed"
                        if workload is not None:
                            row = run_point_replay(
                                server, pool, workload,
                                timeout_s=args.timeout_s,
                                fleet_hosts=max(0, args.fleet),
                                use_models=bool(tenant_models),
                            )
                            if not tenant_models:
                                row["model"] = args.model
                            rows = [row]
                        elif tenant_models:
                            rows = run_point_tenants(
                                server, pool, tenant_models, tenant_weights,
                                mode=mode, requests=args.requests,
                                concurrency=args.concurrency, rps=rps,
                                seed=args.seed, timeout_s=args.timeout_s,
                                fleet_hosts=max(0, args.fleet),
                                load_shape=load_shape,
                            )
                        else:
                            row = run_point(
                                server, pool, mode=mode,
                                requests=args.requests,
                                concurrency=args.concurrency, rps=rps,
                                seed=args.seed, timeout_s=args.timeout_s,
                                fleet_hosts=max(0, args.fleet),
                            )
                            row["model"] = args.model
                            rows = [row]
                        canary_scores = None
                        if (args.canary_probes
                                and getattr(server, "prober", None)
                                is not None):
                            # One probe cycle per swept point: the row's
                            # quality stamp measures THIS point's config
                            # (precision/wait/buckets), not a stale one.
                            canary_scores = server.prober.probe_once()
                        collector = getattr(server, "collector", None)
                        per_phase = None
                        if collector is not None:
                            # One forced scrape so the point's spans are
                            # all in, then the per-phase p50/p99 deltas
                            # since the previous point (ISSUE 13
                            # satellite: the attribution columns).
                            collector.tick()
                            per_phase = collector.drain_phase_stats()
                        for row in rows:
                            row.update(
                                buckets=bucket_set, max_wait_ms=wait_ms,
                                chips=chips,
                            )
                            if args.transport == "remote":
                                row["transport"] = "http"
                            elif args.transport == "framed":
                                row["transport"] = (
                                    "framed+hedge" if args.hedge
                                    else "framed"
                                )
                            if per_phase and not tenant_models:
                                # Per-phase spans are not tenant-split:
                                # attach only to single-model rows.
                                row["per_phase"] = per_phase
                            if workload is not None:
                                from mpi_pytorch_tpu.obs.replay import (
                                    differential_report,
                                    render_diff,
                                )

                                row["workload"] = workload.fingerprint
                                if args.speed != 1.0:
                                    row["speed"] = args.speed
                                diff = differential_report(
                                    workload,
                                    {"submitted": (row["requests"]
                                                   + row["rejected"]),
                                     "rejected": row["rejected"],
                                     "images_per_sec":
                                         row["images_per_sec"]},
                                    per_phase,
                                )
                                row["replay_diff"] = diff
                                for line in render_diff(diff):
                                    print(line, file=sys.stderr)
                            if args.serve_shard_degree > 1:
                                # Schema-v13: the model-parallel axis is
                                # its own trend-line identity — a sharded
                                # row must never pair with a replicated
                                # baseline.
                                row["shard_degree"] = args.serve_shard_degree
                            if args.serve_pipe_stages > 1:
                                # Schema-v16: the pipeline axis — its own
                                # trend line, with the last flush's
                                # measured fill/drain bubble as evidence.
                                row["pipe_stages"] = args.serve_pipe_stages
                                exe = getattr(server, "_exe", None)
                                lf = (
                                    exe.last_flush()
                                    if hasattr(exe, "last_flush") else None
                                )
                                if lf:
                                    row["bubble_frac"] = round(
                                        float(lf["bubble_frac"]), 4
                                    )
                            if stamp_precision:
                                row["precision"] = precision
                            if (precision == "int8"
                                    and server.parity_top1 is not None):
                                row["parity_top1"] = server.parity_top1
                            if canary_scores:
                                # Schema-v15 quality axis: the canary's
                                # live top-1 agreement for this row's
                                # tenant (check_regression fails a >2-pt
                                # absolute drop vs baseline).
                                sc = canary_scores.get(
                                    row.get("model") or ""
                                )
                                if sc and "agreement_top1" in sc:
                                    row["agreement_top1"] = (
                                        sc["agreement_top1"]
                                    )
                            print(json.dumps(row), flush=True)
                            out_rows.append(row)
        finally:
            server.close()

    bad = [r for r in out_rows if r["compiles_after_warmup"] != 0]
    if bad:
        print(
            f"WARNING: {len(bad)} row(s) observed steady-state compiles — "
            "the zero-compile invariant is broken; rows are tainted",
            file=sys.stderr,
        )
    if args.out:
        with open(args.out, "w") as f:
            for row in out_rows:
                f.write(json.dumps(row) + "\n")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
