"""THE metrics-record schema — one definition, three consumers.

``MetricsWriter`` streams are consumed by ``tools/report_run.py`` (render),
``tools/check_results_artifacts.py`` (CI lint over the committed
``docs/*_metrics.jsonl`` artifacts), and ad-hoc analysis; all three validate
through here so the record shapes cannot drift between writer and readers.

Deliberately dependency-free (no jax, no numpy): the tools import this
module without initializing a backend.

Record kinds (every record also carries ``ts``, the epoch-seconds stamp
``MetricsWriter`` adds, and ``kind``):

| kind      | required                                            | optional |
|-----------|-----------------------------------------------------|----------|
| epoch     | epoch, loss, time_s, images_per_sec                 | tflops, mfu_pct, tokens, tokens_per_sec, moe_pairs_held, moe_pairs_absent, moe_load_max, moe_rows_computed |
| val       | epoch, accuracy, loss                               |          |
| eval      | accuracy, loss, images, time_s                      |          |
| step      | epoch, step, loss                                   | grad_norm, data_wait_ms, step_ms, recompiles, hbm_bytes, sync_ms, overlap_frac, dcn_overlap_frac, skipped, steps_skipped |
| heartbeat | epoch, step, step_ms, median_step_ms, stragglers, threshold | images_per_sec |
| anomaly   | reason, epoch                                       | step, loss, grad_norm, path, detail |
| serve     | bucket, requests, queue_depth, fill_ratio, queue_wait_ms, device_ms | preprocess_ms, total_ms, precision, model |
| serve_bench | mode, buckets, max_wait_ms, requests, p50_ms, p95_ms, p99_ms, images_per_sec | model, offered_rps, rejected, mean_fill_ratio, compiles_after_warmup, chips, precision, parity_top1, load_shape |
| quant_parity | precision, top1_agree, samples                   | top5_agree, max_logit_drift, model |
| resume    | epoch, to_devices                                   | from_devices, from_mesh, to_mesh, path, zero_shards_from, zero_shards_to, corrupt_skipped, strategy, cursor_epoch, cursor_step |
| fault     | reason                                              | epoch, step, detail, streak |
| rollback  | epoch, reason                                       | step, restored_epoch, rollbacks, lr_scale, path, detail |
| metrics   | counters, gauges, histograms                        | merged_hosts |
| alert     | rule, severity                                      | metric, value, threshold, streak, action, detail, epoch, step |
| route     | host, requests                                      | share, score, queue_depth, inflight, window_s, transport, trace_ids, models |
| fleet     | event                                               | host, detail, redispatched, spare, max_wait_ms_from/to, buckets_from/to, p99_ms, target_p99_ms, compiles_after_warmup, hosts_from/to, reason, reject_rate, queue_depth, restarts, transport, model, resident, plan |
| timeline  | host, metric, points                                | window_s, clock_offset_ms, resets |
| hedge     | winner, loser                                       | cancelled, deadline_ms, trace_id |
| canary    | model, event                                        | agreement_top1, agreement_topk, rank_drift, probes, verdict, mutation, reason, detail |
| compile   | executable, seconds, mosaic_calls, devices, sharded_inputs |   |

``serve`` is the per-flush record the online inference server writes
(serve/server.py: one coalesced batch dispatched to a bucket executable);
``serve_bench`` is a latency/throughput summary row from the load driver
(tools/bench_serve.py — the committed ``docs/serve_bench.json`` rows).
``resume`` is written once per elastic restore (train/elastic.py): the
checkpoint's topology-manifest shape vs the mesh actually resumed onto;
``fault`` is written when a preemption/fault signal is observed (the
watchdog's SIGTERM / sentinel-file / streak triggers, and the
fault-injection gates of ``tools/inject_faults.py`` announcing themselves
before they strike).

Optional fields may be ``null`` (unknown on this backend — e.g. HBM bytes
on CPU, per-step host timing in scan-epoch mode); required fields may not.
Unknown EXTRA keys are allowed (forward compatibility); unknown KINDS are
not (a typo'd kind is exactly the malformed record this schema exists to
catch).
"""

from __future__ import annotations

import json
from typing import Any, Mapping

# Schema generations (additive only — readers accept every prior version's
# records, and optional fields never become required):
#   1: epoch/val/eval/step/heartbeat/anomaly (+serve, serve_bench in PR 4)
#   2: step records may carry the grad-sync fields ``sync_ms`` (measured
#      per-step gradient-sync milliseconds, where a tool measured one) and
#      ``overlap_frac`` (the static bucket-plan overlap estimate the
#      spmd --grad-sync-buckets trainer stamps; train/step.py
#      bucket_overlap_frac) — ISSUE 6 / ROADMAP item 2.
#   3: the elastic-training kinds ``resume`` (topology of an elastic
#      restore) and ``fault`` (an observed preemption/fault signal), plus
#      the ``serve`` record's optional ``preprocess_failures`` /
#      ``worker_respawns`` counts — ISSUE 7 / ROADMAP item 4.
#   4: the live-telemetry kinds ``metrics`` (a point-in-time snapshot of
#      the in-process metrics registry, ``obs/metrics.py`` — counters,
#      gauges, and histogram summaries with sketch-derived p50/p95/p99)
#      and ``alert`` (one SLO-rule breach from the monitor,
#      ``obs/monitor.py``: the rule that fired, the observed value vs its
#      threshold, and the action(s) taken) — ISSUE 8.
#   5: the fleet-serving kinds ``route`` (one per-host routing window from
#      the fleet router, ``serve/fleet/router.py``: requests dispatched to
#      that host in the window, its EWMA load score, queue depth) and
#      ``fleet`` (one fleet lifecycle event: a failover — host drained,
#      in-flight requests re-dispatched, warm spare promoted — or a
#      controller retune of ``max_wait_ms`` / the active bucket set,
#      ``serve/fleet/controller.py``), plus the ``serve_bench`` row's
#      optional ``fleet_hosts`` / ``per_host`` breakdown
#      (``tools/bench_serve.py --fleet N``) — ISSUE 9 / ROADMAP item 1.
#   6: the self-healing-training fields (ISSUE 10): the ``rollback`` kind
#      (one in-process bad-step rollback — the trigger, the checkpoint
#      restored, the rollback count and LR scale), the ``step`` record's
#      optional ``skipped``/``steps_skipped`` fields (--bad-step-policy
#      skip: this step's update was discarded / cumulative discards), the
#      ``resume`` record's optional ``cursor_epoch``/``cursor_step``
#      (the exact-step data cursor stamped in the checkpoint's topology
#      sidecar), and the ``anomaly`` record's optional ``path``/``detail``
#      (``reason=bad_sample`` quarantines name the undecodable file).
#   7: the quantized-serving fields (ISSUE 11): ``precision`` on ``serve``
#      flushes (which startup-compiled executable set ran the batch —
#      stamped when a server holds multiple sets or serves non-bf16) and
#      on ``serve_bench`` rows (plus ``parity_top1``, the int8-vs-bf16
#      startup agreement, on int8 rows); ``precision_from``/
#      ``precision_to`` + ``parity_top1`` on ``fleet`` retune records
#      (the controller's precision axis, with the measured top-1 parity
#      delta on the record); and the ``quant_parity`` kind — one offline
#      int8-vs-bf16 parity report from ``evaluate --quantize-eval``
#      (top-1/top-5 agreement + max logit drift on a fixed sample).
#   8: the remote-fleet generation (ISSUE 12): ``fleet`` records grow the
#      autoscaler/supervisor events ``scale_up``/``scale_down``/
#      ``restart`` with their evidence fields (``hosts_from``/``hosts_to``
#      host counts, ``reason``, the front-door ``reject_rate`` rejects/s,
#      the summed ``queue_depth``, the supervisor's cumulative
#      ``restarts``); and ``route``/``fleet``/``serve_bench`` records may
#      carry ``transport`` ("http" when the row came from real serving
#      processes over the wire — stamped only when the axis is live, so
#      in-process streams stay byte-identical to prior generations, and
#      ``check_regression`` keys it into the serve trend-line identity).
#   9: the distributed-tracing generation (ISSUE 13): the ``timeline``
#      kind — one per-(host, metric) time-series window from the fleet
#      collector (``obs/collector.py``: gauge samples / counter RATES as
#      ``points`` [[ts, value], ...], the host's probe-RTT clock-offset
#      estimate, and how many counter RESETS — host restarts — the
#      collector absorbed instead of booking negative rates); optional
#      ``trace_ids`` on ``serve`` flushes and ``route`` windows (the
#      W3C-traceparent-style trace ids of the TRACED requests they
#      carried — absent on untraced traffic, so tracing-off streams stay
#      byte-identical to v8); optional ``trace_id`` on ``fault`` records
#      (a fault gate firing inside a traced request names its victim
#      trace, so chaos evidence joins the exact waterfall it disrupted);
#      and optional ``per_phase`` on ``serve_bench`` rows (the
#      collector-derived queue/preprocess/device/wire p50/p99 breakdown
#      per sweep point).
#  10: the multi-model-tenancy generation (ISSUE 14): ``serve`` flushes
#      may carry ``model`` (the tenant the single-tenant-by-construction
#      flush served), ``route`` windows may carry ``models`` (per-tenant
#      dispatch counts of the window), ``fleet`` records grow the zoo
#      lifecycle events ``swap_in``/``evict`` (the cold-model swap-in /
#      LRU-or-operator eviction, with ``model``, the ``resident`` tenant
#      list after the change, and — on swap-ins — the explainable
#      packing ``plan`` the decision rested on), controller ``retune``
#      and autoscaler ``scale_up``/``scale_down`` records may carry
#      ``model`` (the tenant retuned / the pressured tenant), ``alert``
#      records may carry ``model`` (the SLO monitor's tenant label), and
#      ``serve_bench`` rows may carry ``load_shape`` (the multi-tenant
#      sweep's traffic shape, e.g. "uniform" / "hot:resnet18"). All
#      absent on untenanted serving — streams stay byte-identical to v9.
#  11: the cross-pod hierarchical-training generation (ISSUE 15 / ROADMAP
#      item 5): ``step`` records may carry ``dcn_overlap_frac`` (the
#      static estimate of how much of the two-level grad sync's CROSS-POD
#      (DCN) traffic is issued before the final reverse-topo bucket —
#      stamped only on ``--mesh-pods > 1`` runs, so flat-mesh streams stay
#      byte-identical to v10; the within-pod twin is v2's
#      ``overlap_frac``). The checkpoint topology manifest and ``resume``
#      records carry the pod factoring implicitly via their mesh-shape
#      strings (``pod=2,ici=4,model=1``) — no new fields.
#  12: the tail-at-scale data-plane generation (ISSUE 16): the ``hedge``
#      kind — one per hedged request that raced (router-level request
#      hedging over the framed wire, ``serve/fleet/router.py``: which
#      host won, which lost, whether the loser was revoked in flight,
#      and the p99-derived deadline that fired the hedge; ``trace_id``
#      when the request was traced); ``serve_bench`` rows may carry
#      ``hedged`` (how many requests of the sweep point hedged) and
#      ``copies_per_request`` (the zero-copy dispatch assertion: input
#      bytes touched exactly once between wire and ``device_put``);
#      ``transport`` values grow "framed" / "framed+hedge" (the binary
#      framed wire of ``serve/wire.py`` — check_regression already keys
#      transport into the serve trend-line identity). All absent on
#      HTTP/in-process serving — streams stay byte-identical to v11.
#  13: the model-parallel-residency generation (ISSUE 17): ``serve``
#      flushes and ``serve_bench`` rows may carry ``shard_degree`` (how
#      many chips one copy of the serving params spans — absent on
#      replicated tenants, so pre-sharding streams stay byte-identical
#      to v12); ``fleet`` swap_in/retune records may carry ``residency``
#      (the tenant's weight layout after the event — "replicated" /
#      "tp:K" / "fsdp:K"), ``reshard_bytes`` (total bytes the bounded
#      per-leaf cross-topology reshard moved), and ``shard_degree``.
#  14: the trace-replay generation (ISSUE 18): fleet-trace ROOT spans
#      (``route/request``) carry ``model``/``bucket``/``rows``/
#      ``precision`` attrs (joined from the winning ``serve/request``
#      span at collector finalize — trace files are spans, not metrics
#      records, so this is documented here rather than type-checked;
#      pre-v14 traces replay with documented defaults). ``serve_bench``
#      rows may carry ``workload`` (the 16-hex content fingerprint of
#      the replayed workload artifact — check_regression keys it so a
#      replay row never compares against a synthetic-Poisson baseline),
#      ``speed`` (the replay time-warp factor, absent at 1.0), and
#      ``replay_diff`` (the recorded-vs-replayed differential report:
#      per-phase p50/p99 both sides + reject-rate/throughput deltas).
#      New ``whatif`` kind — one offline planner run (tools/whatif.py):
#      the workload fingerprint, the ranked candidate plan, and the
#      model's stamped calibration error. All absent on non-replay
#      serving — streams stay byte-identical to v13.
#  15: the quality-observability generation (ISSUE 19): the ``canary``
#      kind — one golden-set canary event per tenant (``obs/canary.py``:
#      ``event`` is "pin" — references pinned from the healthy tenant's
#      answers, "probe" — one shadow probe cycle scored against them
#      (top-1/top-k agreement, ``rank_drift`` — the max-logit-drift
#      stand-in for an index-only prediction contract), or "blocked" —
#      a fleet mutation refused on a FAIL verdict, naming the mutation);
#      ``alert`` records may carry ``source`` ("drift" = a
#      baseline-relative breach from ``obs/drift.py``, with its
#      ``psi``/``chi2`` evidence, window/baseline sizes, and — for
#      CUSUM change-points over collector rings — the ``host``);
#      ``fleet`` swap_in/retune records may carry ``canary_verdict``
#      (the gate's verdict stamped on every ALLOWED mutation);
#      ``serve`` flushes may carry ``shadow_requests`` (how many of the
#      flush's requests were tagged canary probes — excluded from the
#      served/requests counters, so billing stays honest); and
#      ``serve_bench`` rows may carry ``agreement_top1`` (the canary
#      agreement measured during the sweep point — trends like img/s in
#      check_regression, a >2-point absolute drop fails) and
#      ``residency`` (keyed into the trend-line identity alongside
#      precision). All absent when the canary/drift knobs are off —
#      streams stay byte-identical to v14.
# v16: pipeline-parallel serving (serve/pipeline.py, ISSUE 20 — additive):
#      ``serve`` flushes on a ``pipe:K`` tenant carry ``pipe_stages``,
#      ``bubble_frac`` (the MEASURED fill/drain bubble of that flush's
#      micro-batch schedule), and ``interstage_bytes`` (the ledger-booked
#      inter-stage activation traffic the flush moved); ``serve_bench``
#      rows from ``--serve-pipe-stages`` sweeps carry ``pipe_stages``
#      (keyed into the trend-line identity) and ``bubble_frac``;
#      ``fleet`` retune records for conversions TO pipe carry
#      ``pipe_stages`` + ``interstage_bytes``. Traced pipe requests gain
#      per-stage ``serve/stage{i}`` child spans under ``serve/device``.
#      All absent off the pipe path — streams stay byte-identical to v15.
# v17: the ``compile`` kind (ISSUE 21) — one record per AOT-compiled driver
#      executable (the trainer's step, the evaluator's predict step;
#      ``utils/hardware.compile_record``): compile wall seconds, how many
#      Mosaic custom calls the optimized HLO carries, which local devices
#      every input has an addressable shard on, and how many inputs are
#      split rather than replicated. ``chip_smoke.py`` reads it to prove a
#      requested Pallas kernel is in the program that ran and that every
#      chip holds its shard.
SCHEMA_VERSION = 17

_NUM = (int, float)
_INT = (int,)

# kind -> {field: allowed types}. bool is an int subclass in Python; it is
# never a valid metrics value, so the checker rejects it explicitly.
REQUIRED: dict[str, dict[str, tuple]] = {
    "epoch": {
        "epoch": _INT, "loss": _NUM, "time_s": _NUM, "images_per_sec": _NUM,
    },
    "val": {"epoch": _INT, "accuracy": _NUM, "loss": _NUM},
    "eval": {"accuracy": _NUM, "loss": _NUM, "images": _INT, "time_s": _NUM},
    "step": {"epoch": _INT, "step": _INT, "loss": _NUM},
    "heartbeat": {
        "epoch": _INT, "step": _INT, "step_ms": (list,),
        "median_step_ms": _NUM, "stragglers": (list,), "threshold": _NUM,
    },
    "anomaly": {"reason": (str,), "epoch": _INT},
    "serve": {
        "bucket": _INT, "requests": _INT, "queue_depth": _INT,
        "fill_ratio": _NUM, "queue_wait_ms": _NUM, "device_ms": _NUM,
    },
    "serve_bench": {
        "mode": (str,), "buckets": (str,), "max_wait_ms": _NUM,
        "requests": _INT, "p50_ms": _NUM, "p95_ms": _NUM, "p99_ms": _NUM,
        "images_per_sec": _NUM,
    },
    "resume": {"epoch": _INT, "to_devices": _INT},
    "fault": {"reason": (str,)},
    # v4: live-telemetry snapshot (the three registry sections; each a
    # name → value/summary object) and SLO alerts.
    "metrics": {"counters": (dict,), "gauges": (dict,), "histograms": (dict,)},
    "alert": {"rule": (str,), "severity": (str,)},
    # v5: fleet serving — one routing window per host (router) and one
    # lifecycle event (failover/retune/…) per occurrence.
    "route": {"host": (str,), "requests": _INT},
    "fleet": {"event": (str,)},
    # v6: one in-process bad-step rollback (train/trainer.py,
    # --bad-step-policy rollback): where it triggered and why.
    "rollback": {"epoch": _INT, "reason": (str,)},
    # v7: one offline int8-vs-bf16 parity report (evaluate --quantize-eval
    # — the serve-side parity gates' reusable oracle).
    "quant_parity": {
        "precision": (str,), "top1_agree": _NUM, "samples": _INT,
    },
    # v9: one per-(host, metric) time-series window from the fleet
    # collector (obs/collector.py) — points are [[wall_ts, value], ...].
    "timeline": {"host": (str,), "metric": (str,), "points": (list,)},
    # v12: one hedged-request race (serve/fleet/router.py): the host
    # whose completion won and the host whose attempt was revoked.
    "hedge": {"winner": (str,), "loser": (str,)},
    # v14: one offline what-if planner run (tools/whatif.py): which
    # workload it planned against and the ranked candidate list.
    "whatif": {"workload": (str,), "ranked": (list,)},
    # v15: one golden-set canary event per tenant (obs/canary.py):
    # references pinned, a probe cycle scored, or a mutation blocked.
    "canary": {"model": (str,), "event": (str,)},
    # v17: one AOT-compiled driver executable (utils/hardware.compile_record).
    "compile": {
        "executable": (str,), "seconds": _NUM, "mosaic_calls": _INT,
        "devices": (list,), "sharded_inputs": _INT,
    },
}

OPTIONAL: dict[str, dict[str, tuple]] = {
    "epoch": {
        "tflops": _NUM, "mfu_pct": _NUM,
        # A token model's epoch (images_per_sec then counts sequences), and
        # its expert layers' counters: the epoch's sums, the largest load.
        "tokens": _INT, "tokens_per_sec": _NUM,
        "moe_pairs_held": _INT, "moe_pairs_absent": _INT, "moe_load_max": _INT,
        "moe_rows_computed": _INT,
    },
    "val": {},
    "eval": {},
    "step": {
        "grad_norm": _NUM, "data_wait_ms": _NUM, "step_ms": _NUM,
        "recompiles": _INT, "hbm_bytes": _INT,
        # v2 grad-sync fields (spmd --grad-sync-buckets; absent on v1
        # records and on lever-less runs):
        "sync_ms": _NUM, "overlap_frac": _NUM,
        # v11: hierarchical (--mesh-pods > 1) runs only — the cross-pod
        # (DCN) overlap estimate of the two-level bucket plan.
        "dcn_overlap_frac": _NUM,
        # v6 bad-step-policy fields (--bad-step-policy skip only): whether
        # THIS step's update was discarded on a non-finite grad norm
        # (0/1), and the run's cumulative discard count.
        "skipped": _INT, "steps_skipped": _INT,
    },
    "heartbeat": {"images_per_sec": _NUM},
    # v6: bad_sample quarantines (data/pipeline.py) carry the undecodable
    # file's path and the decode error; cursor_mismatch fallbacks carry
    # the mismatch reason in detail.
    "anomaly": {
        "step": _INT, "loss": _NUM, "grad_norm": _NUM,
        "path": (str,), "detail": (str,),
    },
    "serve": {
        "preprocess_ms": _NUM, "total_ms": _NUM,
        # v3: requests of this flush dropped at preprocess (typed
        # PreprocessError to their callers) and cumulative worker-pool
        # respawns — absent on clean flushes.
        "preprocess_failures": _INT, "worker_respawns": _INT,
        # v7: which startup-compiled executable set ran this flush —
        # stamped when the server holds multiple precision sets or serves
        # non-bf16 (pure-bf16 servers keep v6-identical records).
        "precision": (str,),
        # v9: the trace ids of the TRACED requests this flush carried —
        # absent on untraced traffic (tracing-off streams stay
        # byte-identical to v8; the no-hot-path-cost invariant's record
        # half).
        "trace_ids": (list,),
        # v10: the tenant this flush served (flushes are single-tenant
        # by construction — serve/zoo/) — absent on untenanted servers.
        "model": (str,),
        # v13: chips one copy of the params spans (model-parallel
        # tenants only — absent on replicated serving).
        "shard_degree": _INT,
        # v15: how many of the flush's requests were tagged canary
        # shadow probes (obs/canary.py) — they ride the batch but are
        # excluded from the served/requests counters; absent on flushes
        # that carried none, so canary-off streams stay byte-identical.
        "shadow_requests": _INT,
        # v16: pipeline flush facts (pipe:K tenants only): stage count,
        # the measured fill/drain bubble fraction of the micro-batch
        # schedule, and the ledger-booked inter-stage activation bytes
        # moved. Absent on non-pipeline serving.
        "pipe_stages": _INT, "bubble_frac": _NUM, "interstage_bytes": _INT,
    },
    "serve_bench": {
        "model": (str,), "offered_rps": _NUM, "rejected": _INT,
        "mean_fill_ratio": _NUM, "compiles_after_warmup": _INT, "chips": _INT,
        # v5: rows from the --fleet N mode — how many serving hosts the
        # router spread the sweep over, and the per-host breakdown (host
        # name → {requests, fill_pct, mean_ms}, all deltas over THIS
        # sweep point; per-point tail percentiles live on the row itself).
        "fleet_hosts": _INT, "per_host": (dict,),
        # v7: the --precision sweep axis; int8 rows also carry the
        # startup int8-vs-bf16 top-1 agreement the accuracy claim rests
        # on (a throughput row without its parity stamp is half a row).
        "precision": (str,), "parity_top1": _NUM,
        # v8: which transport served the row ("http" = real serving
        # processes over the wire) — a remote row is a different trend
        # line than an in-process one (check_regression keys it).
        "transport": (str,),
        # v9: the collector-derived per-phase latency breakdown for this
        # sweep point (span name → {count, p50_ms, p99_ms} — the
        # queue/preprocess/device/wire attribution; absent without a
        # collector, so pre-v9 rows compare unchanged).
        "per_phase": (dict,),
        # v10: the multi-tenant sweep's traffic shape ("uniform" /
        # "hot:<model>") — keyed into the regression trend-line identity
        # alongside model, so a skewed-load row never compares against a
        # uniform baseline.
        "load_shape": (str,),
        # v12: how many requests of this sweep point hedged (framed wire
        # with --hedge only), and the zero-copy dispatch assertion —
        # input copies per served request (1.0 = bytes touched exactly
        # once between the wire and device_put). Absent elsewhere.
        "hedged": _INT, "copies_per_request": _NUM,
        # v13: the --serve-shard-degree axis — a sharded row is a
        # different trend line than a replicated one
        # (check_regression keys it).
        "shard_degree": _INT,
        # v14: trace-replay rows (bench_serve --replay): the workload
        # artifact's content fingerprint (keyed into the regression
        # trend-line identity — replayed load never compares against
        # synthetic Poisson), the time-warp factor (absent at 1.0), and
        # the recorded-vs-replayed differential report. Absent on
        # synthetic-load rows — streams stay byte-identical to v13.
        "workload": (str,), "speed": _NUM, "replay_diff": (dict,),
        # v15: the quality axes — the canary top-1 agreement measured
        # during this sweep point (trends like img/s: a >2-point
        # absolute drop fails check_regression), and the tenant's weight
        # residency, keyed into the trend-line identity so a sharded/
        # int8 row never compares against a replicated/bf16 baseline.
        "agreement_top1": _NUM, "residency": (str,),
        # v16: the --serve-pipe-stages axis — a pipelined row is its own
        # trend line (check_regression keys pipe_stages) and carries the
        # mean measured bubble fraction over the sweep point.
        "pipe_stages": _INT, "bubble_frac": _NUM,
    },
    "resume": {
        "from_devices": _INT, "from_mesh": (str,), "to_mesh": (str,),
        "path": (str,), "zero_shards_from": _INT, "zero_shards_to": _INT,
        "corrupt_skipped": _INT, "strategy": (str,),
        # v6: the exact-step data cursor stamped in the restored
        # checkpoint's topology sidecar (train/trainer.py): the epoch and
        # step-in-epoch the run continues at when the cursor validates.
        "cursor_epoch": _INT, "cursor_step": _INT,
    },
    # v9 trace_id: a fault gate that fired INSIDE a traced request (the
    # router's kill gate striking a traced dispatch, a preprocess crash
    # taking a traced flush) stamps the victim's trace id, so the chaos
    # evidence links to the exact waterfall it disrupted.
    "fault": {
        "epoch": _INT, "step": _INT, "detail": (str,), "streak": _INT,
        "trace_id": (str,),
    },
    # v5: fleet routing/lifecycle fields. ``route`` is a per-host window:
    # requests dispatched there since the last record, the router's
    # smoothed load score and the host's queue/in-flight state when the
    # window closed. ``fleet`` events: "failover" carries the drained
    # host, how many in-flight requests were re-dispatched, and the
    # promoted spare; "retune" carries the controller's max_wait/bucket
    # change and the p99-vs-target evidence it acted on.
    "route": {
        "share": _NUM, "score": _NUM, "queue_depth": _INT, "inflight": _INT,
        "window_s": _NUM,
        # v8: the host's transport ("http" = a real serving process over
        # the wire; absent = in-process LocalHost, streams unchanged).
        "transport": (str,),
        # v9: the traced requests dispatched to this host in the window
        # (bounded; absent when tracing is off — streams unchanged).
        "trace_ids": (list,),
        # v10: per-tenant dispatch counts of this window (multi-model
        # fleets only — absent otherwise, streams unchanged).
        "models": (dict,),
    },
    "fleet": {
        "host": (str,), "detail": (str,), "redispatched": _INT,
        "spare": (str,), "max_wait_ms_from": _NUM, "max_wait_ms_to": _NUM,
        "buckets_from": (str,), "buckets_to": (str,), "p99_ms": _NUM,
        "target_p99_ms": _NUM, "compiles_after_warmup": _INT,
        # v10: the multi-model axis — the tenant a retune/scale acted on
        # (or the swap_in/evict subject), the resident set after a zoo
        # residency change, and the packing plan a swap-in rested on.
        "model": (str,), "resident": (list,), "plan": (dict,),
        # v7: the controller's precision retune axis — which executable
        # set the host left/entered, and the measured int8-vs-bf16 top-1
        # agreement stamped as the retune's accuracy evidence.
        "precision_from": (str,), "precision_to": (str,),
        "parity_top1": _NUM,
        # v8: the autoscaler/supervisor events (scale_up / scale_down /
        # restart): host counts before/after, the policy's reason, the
        # front-door reject rate and summed queue depth it acted on, the
        # supervisor's cumulative restart count, and the transport.
        "hosts_from": _INT, "hosts_to": _INT, "reason": (str,),
        "reject_rate": _NUM, "queue_depth": _INT, "restarts": _INT,
        "transport": (str,),
        # v13: the model-parallel residency axis — the tenant's weight
        # layout after a swap_in/retune ("replicated"/"tp:K"/"fsdp:K"),
        # the bytes the bounded cross-topology reshard moved getting
        # there, and the chip span (absent on replicated events).
        "residency": (str,), "reshard_bytes": _INT, "shard_degree": _INT,
        # v15: the canary gate's verdict stamped on every ALLOWED
        # mutation (swap_in / retune / conversion) when a gate is
        # present — "pass", or "none" for a tenant never probed. Absent
        # on canary-off fleets (streams stay byte-identical to v14);
        # refused mutations write kind="canary" event="blocked" instead.
        "canary_verdict": (str,),
        # v16: a retune converting a tenant TO pipe:K says how it was cut
        # and the per-flush inter-stage traffic price (absent elsewhere).
        "pipe_stages": _INT, "interstage_bytes": _INT,
    },
    # v6: which step the rollback triggered at, what it restored (the
    # checkpoint's filed epoch + path), how many rollbacks this run has
    # taken, and the cumulative --rollback-lr-backoff scale in effect.
    "rollback": {
        "step": _INT, "restored_epoch": _INT, "rollbacks": _INT,
        "lr_scale": _NUM, "path": (str,), "detail": (str,),
    },
    "metrics": {
        # How many hosts' registries were merged into this snapshot
        # (absent on single-host runs — the local registry IS the merge).
        "merged_hosts": _INT,
    },
    "alert": {
        "metric": (str,), "value": _NUM, "threshold": _NUM, "streak": _INT,
        "action": (str,), "detail": (str,), "epoch": _INT, "step": _INT,
        # v10: the SLO monitor's tenant label (a zoo tenant's rules fire
        # with its model stamped) — absent on untenanted monitors.
        "model": (str,),
        # v15: baseline-relative drift alerts (obs/drift.py) carry
        # source="drift" (the collector pins in-flight traces on them),
        # the PSI / reduced-chi2 evidence with window/baseline sizes,
        # and — for CUSUM change-points over collector rings — which
        # host's series moved. Absent on threshold-DSL SLO alerts.
        "source": (str,), "psi": _NUM, "chi2": _NUM,
        "window_n": _INT, "baseline_n": _INT, "host": (str,),
    },
    # v7: top5_agree is null for fused (argmax-only) contracts.
    "quant_parity": {
        "top5_agree": _NUM, "max_logit_drift": _NUM, "model": (str,),
    },
    # v9: window span of the points, the host's probe-RTT clock-offset
    # estimate (ms — what skew-corrects its span timestamps), and how
    # many counter resets (host restarts) the collector absorbed.
    "timeline": {
        "window_s": _NUM, "clock_offset_ms": _NUM, "resets": _INT,
    },
    # v12: whether the loser was revoked while still in flight (a CANCEL
    # frame / Future.cancel() landed before it resolved), the deadline
    # that fired the hedge, and the traced request's id.
    "hedge": {
        "cancelled": _INT, "deadline_ms": _NUM, "trace_id": (str,),
    },
    # v14: the winning candidate config (first ranked entry, repeated for
    # direct access), the fitted model summary with its stamped
    # calibration error, and — when --validate replayed the winner — the
    # validated row's p99 and whether prediction landed inside the
    # calibration bound.
    "whatif": {
        "winner": (dict,), "model": (dict,), "candidates": _INT,
        "validated_p99_ms": _NUM, "within_calibration": _INT,
        "calibration_error_pct": _NUM,
    },
    # v15: probe-cycle scores (event="probe"), the pinned set size
    # (event="pin"), the latched verdict, and — on event="blocked" —
    # which mutation the FAIL verdict refused and why. rank_drift is the
    # mean displacement of the reference top-1 within the probed top-k
    # (the logit-drift stand-in for an index-only serve contract).
    "canary": {
        "agreement_top1": _NUM, "agreement_topk": _NUM, "rank_drift": _NUM,
        "probes": _INT, "verdict": (str,), "mutation": (str,),
        "reason": (str,), "detail": (str,),
    },
    "compile": {},
}


def _type_ok(value: Any, types: tuple) -> bool:
    return isinstance(value, types) and not isinstance(value, bool)


def validate_record(rec: Any) -> list[str]:
    """Problems with one parsed record ([] = valid)."""
    if not isinstance(rec, Mapping):
        return [f"record is {type(rec).__name__}, not an object"]
    problems = []
    kind = rec.get("kind")
    if not isinstance(kind, str) or kind not in REQUIRED:
        return [f"unknown kind {kind!r} (expected one of {sorted(REQUIRED)})"]
    if not _type_ok(rec.get("ts"), _NUM):
        problems.append("missing/non-numeric 'ts'")
    for field, types in REQUIRED[kind].items():
        if field not in rec:
            problems.append(f"{kind}: missing required field {field!r}")
        elif not _type_ok(rec[field], types):
            problems.append(
                f"{kind}: field {field!r} has type "
                f"{type(rec[field]).__name__}, expected "
                f"{'/'.join(t.__name__ for t in types)}"
            )
    for field, types in OPTIONAL[kind].items():
        if field in rec and rec[field] is not None and not _type_ok(rec[field], types):
            problems.append(
                f"{kind}: optional field {field!r} has type "
                f"{type(rec[field]).__name__}, expected "
                f"{'/'.join(t.__name__ for t in types)} or null"
            )
    return problems


def validate_jsonl(path: str) -> list[str]:
    """Problems across a metrics JSONL file, tagged ``line N:`` ([] = valid)."""
    problems = []
    with open(path) as f:
        for lineno, line in enumerate(f, 1):
            if not line.strip():
                continue
            try:
                rec = json.loads(line)
            except ValueError as e:
                problems.append(f"line {lineno}: not JSON ({e})")
                continue
            problems.extend(f"line {lineno}: {p}" for p in validate_record(rec))
    return problems


def load_records(path: str) -> list[dict]:
    """Parse a metrics JSONL (no validation — pair with ``validate_jsonl``)."""
    records = []
    with open(path) as f:
        for line in f:
            if line.strip():
                records.append(json.loads(line))
    return records
