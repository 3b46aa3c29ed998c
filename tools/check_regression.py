"""Perf regression gate over the committed bench history (ISSUE 8).

Two artifact families carry the repo's trend lines:

- ``BENCH_r*.json`` (repo root) — the driver's headline train cell per
  round: ``{"rc": ..., "parsed": {"metric": ..., "value": img/s, ...}}``.
- ``docs/serve_bench.json`` — the serve load driver's
  ``kind="serve_bench"`` rows (p50/p95/p99, img/s per sweep point).

This gate fails (exit 1) when the NEWEST comparable cell regressed more
than ``--tolerance-pct`` against its predecessor:

- train: ``value`` (img/s) dropped — compared only between rounds whose
  ``metric`` string AND mesh topology (``parsed["mesh"]``, the pods×ici
  factoring of hierarchical rounds — ISSUE 15) are IDENTICAL (the config
  is baked into the string, so a batch-size change — or a flat↔nested
  mesh change — is a new trend line, not a regression);
- serve: ``p99_ms`` rose or ``images_per_sec`` dropped for the same sweep
  point (mode × buckets × max_wait × offered_rps × model), compared
  against a committed baseline snapshot (``--serve-baseline``); the
  QUALITY axis (ISSUE 19) — canary ``agreement_top1`` on rows that carry
  it — trends the same way but on an absolute scale: a drop of more than
  2 points (0.02) fails regardless of ``--tolerance-pct``, keyed by
  (model, precision, residency) so int8/sharded rows never compare
  against bf16/replicated baselines.

Tolerances for history that CANNOT be compared, by design:

- rounds with ``rc != 0`` (a round whose backend never came up) are skipped;
- ``parsed``/``value`` null (staged or failed cells) are skipped;
- no prior round with the same metric string → no pair → pass;
- a missing serve baseline file → empty history → pass, announced loudly
  ("serve gate skipped") so the inert half is visible, not silent. The
  baseline is captured by committing the previous round's snapshot:
  ``cp docs/serve_bench.json docs/serve_bench_prev.json`` before a round
  refreshes ``serve_bench.json`` (the BENCH_r* history pattern, one file
  deep).

Tier-1 wrapper: ``tests/test_regression_gate.py`` (the
``check_results_artifacts.py`` pattern) — a regression lands as a CI
failure in the same PR that caused it, not in the next round's postmortem.

Run: ``python tools/check_regression.py [--tolerance-pct 10]``
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_ROUND = re.compile(r"BENCH_r(\d+)\.json$")


def bench_cells(root: str) -> list[tuple[int, str, str | None, float]]:
    """Comparable (round, metric, mesh, value) cells from ``BENCH_r*.json``,
    round-ordered; rounds with rc != 0 or null parsed/value are dropped
    (a wedged backend is a lost round, not a zero). ``mesh`` is the
    training mesh topology stamped by hierarchical rounds
    (``parsed["mesh"]``, e.g. ``"p2xi4"`` for 2 pods × 4 ici — the
    ``tools/bench_modes.py`` cell convention); flat/legacy rounds carry
    None, so prior history keys exactly as before."""
    cells = []
    for path in glob.glob(os.path.join(root, "BENCH_r*.json")):
        m = _ROUND.search(os.path.basename(path))
        if not m:
            continue
        try:
            data = json.load(open(path))
        except ValueError:
            continue  # a truncated bench artifact is the artifacts linter's job
        if data.get("rc") != 0:
            continue
        parsed = data.get("parsed")
        if not isinstance(parsed, dict):
            continue
        metric, value = parsed.get("metric"), parsed.get("value")
        if not isinstance(metric, str) or not isinstance(value, (int, float)):
            continue
        mesh = parsed.get("mesh")
        if not isinstance(mesh, str):
            mesh = None
        cells.append((int(m.group(1)), metric, mesh, float(value)))
    return sorted(cells, key=lambda c: (c[0], c[1], c[2] or ""))


def check_bench(root: str, tol_pct: float) -> list[str]:
    """NEWEST-vs-predecessor comparison per (metric, mesh-topology) trend
    line — only the last pair of each line is judged: the gate protects
    the current PR's claim, and a historical dip that later recovered must
    not fail CI forever (the history is immutable). Mesh topology
    (pods×ici, ISSUE 15) is part of the identity: a hierarchical cell pays
    a DCN hop per step by construction, so it must never be read as a
    regression of — or an alibi for — the flat-mesh trend line."""
    violations = []
    by_metric: dict[tuple, list[tuple[int, float]]] = {}
    for rnd, metric, mesh, value in bench_cells(root):
        by_metric.setdefault((metric, mesh), []).append((rnd, value))
    for (metric, mesh), cells in by_metric.items():
        if len(cells) < 2:
            continue
        (prev_rnd, prev), (rnd, value) = cells[-2], cells[-1]
        if value < prev * (1 - tol_pct / 100.0):
            line = metric if mesh is None else f"{metric} [mesh {mesh}]"
            violations.append(
                f"BENCH r{rnd:02d}: {line!r} regressed "
                f"{value:,.1f} vs r{prev_rnd:02d}'s {prev:,.1f} "
                f"(-{100.0 * (1 - value / prev):.1f}% > {tol_pct}% tolerance)"
            )
    return violations


def _serve_key(row: dict) -> tuple:
    # fleet_hosts joined the sweep-point identity in schema v5, precision
    # in v7, transport in v8, load_shape in v10: an N-host fleet row — or
    # an int8 row, a remote-transport row, or a multi-tenant row under a
    # skewed load shape — is a different trend line than a
    # single-server/bf16/in-process/uniform row at the same
    # (mode, buckets, wait, rps), so none of them can ever be "a
    # regression" against the other's baseline. ``model`` has keyed the
    # identity since v4 — tenant rows never compare cross-model. Old rows
    # (no field) key as None on both sides, so prior-generation baselines
    # keep comparing unchanged. shard_degree joined in v13: a
    # model-parallel row (params sharded over K chips) is a different
    # machine shape than the replicated row at the same sweep point.
    # workload joined in v14: a trace-replay row carries the replayed
    # workload's content fingerprint, so replayed-load trend lines never
    # compare against synthetic-Poisson baselines (and two replays only
    # compare when they re-drove the IDENTICAL arrival process);
    # pre-v14 rows key None on both sides, unchanged. residency joined
    # in v15 alongside shard_degree: a tp/fsdp-resident tenant is a
    # different machine shape than the replicated one, and the QUALITY
    # axis (agreement_top1) must never read "int8 agrees less than
    # bf16" or "fsdp differs from replicated" as a regression — those
    # are different trend lines by construction. pipe_stages joined in
    # v16: a pipeline-split row pays a fill/drain bubble by design, so it
    # must never read as a regression against the unsplit row at the same
    # sweep point (pre-v16 rows key None on both sides, unchanged).
    return (
        row.get("mode"), row.get("buckets"), row.get("max_wait_ms"),
        row.get("offered_rps"), row.get("model"), row.get("fleet_hosts"),
        row.get("precision"), row.get("transport"), row.get("load_shape"),
        row.get("shard_degree"), row.get("workload"), row.get("residency"),
        row.get("pipe_stages"),
    )


def serve_rows(path: str) -> dict[tuple, dict]:
    """Sweep-point → newest row for that point (a file may append rows
    across reruns; the last one is the current claim)."""
    rows: dict[tuple, dict] = {}
    with open(path) as f:
        for line in f:
            if not line.strip():
                continue
            row = json.loads(line)
            if row.get("kind") == "serve_bench":
                rows[_serve_key(row)] = row
    return rows


def check_serve(new_path: str, baseline_path: str, tol_pct: float) -> list[str]:
    """p99 rise / img/s drop per sweep point vs the committed baseline.
    Either file missing = empty history = nothing to compare; null cells
    (staged chip rows) skip that comparison only."""
    if not (os.path.isfile(new_path) and os.path.isfile(baseline_path)):
        return []
    violations = []
    base = serve_rows(baseline_path)
    for key, row in serve_rows(new_path).items():
        prev = base.get(key)
        if prev is None:
            continue
        point = " ".join(str(k) for k in key if k is not None)
        p99, p99_0 = row.get("p99_ms"), prev.get("p99_ms")
        if (
            isinstance(p99, (int, float)) and isinstance(p99_0, (int, float))
            and p99_0 > 0 and p99 > p99_0 * (1 + tol_pct / 100.0)
        ):
            violations.append(
                f"serve [{point}]: p99 {p99:.1f} ms vs baseline {p99_0:.1f} ms "
                f"(+{100.0 * (p99 / p99_0 - 1):.1f}% > {tol_pct}% tolerance)"
            )
        ips, ips_0 = row.get("images_per_sec"), prev.get("images_per_sec")
        if (
            isinstance(ips, (int, float)) and isinstance(ips_0, (int, float))
            and ips_0 > 0 and ips < ips_0 * (1 - tol_pct / 100.0)
        ):
            violations.append(
                f"serve [{point}]: {ips:,.1f} img/s vs baseline {ips_0:,.1f} "
                f"(-{100.0 * (1 - ips / ips_0):.1f}% > {tol_pct}% tolerance)"
            )
        # Schema-v15 quality axis: the canary top-1 agreement trends
        # like img/s, but on an ABSOLUTE scale — agreement is a
        # fraction of probes, so "10% relative" would let a 0.99
        # baseline drift to 0.89 (ten misclassified probes in a
        # hundred) without failing. A drop of more than 2 absolute
        # points (0.02) fails; keyed by (model, precision, residency)
        # via _serve_key, so int8/sharded rows only ever compare
        # against their own baselines. Pre-v15 rows (no field) skip.
        agree, agree_0 = row.get("agreement_top1"), prev.get("agreement_top1")
        if (
            isinstance(agree, (int, float)) and isinstance(agree_0, (int, float))
            and agree < agree_0 - 0.02
        ):
            violations.append(
                f"serve [{point}]: canary agreement_top1 {agree:.4f} vs "
                f"baseline {agree_0:.4f} "
                f"(-{100.0 * (agree_0 - agree):.1f} points > 2-point "
                "absolute tolerance)"
            )
        # Schema-v9 per-phase attribution (the collector-derived
        # queue/preprocess/device/wire breakdown): compared only when
        # BOTH sides carry the phase — pre-v9 rows (no per_phase) and
        # newly-instrumented phases skip, so old baselines keep working.
        pp, pp_0 = row.get("per_phase"), prev.get("per_phase")
        if isinstance(pp, dict) and isinstance(pp_0, dict):
            for phase in sorted(set(pp) & set(pp_0)):
                p99, p99_0 = (
                    (pp[phase] or {}).get("p99_ms"),
                    (pp_0[phase] or {}).get("p99_ms"),
                )
                if (
                    isinstance(p99, (int, float))
                    and isinstance(p99_0, (int, float))
                    and p99_0 > 0 and p99 > p99_0 * (1 + tol_pct / 100.0)
                ):
                    violations.append(
                        f"serve [{point}] phase {phase}: p99 {p99:.1f} ms "
                        f"vs baseline {p99_0:.1f} ms "
                        f"(+{100.0 * (p99 / p99_0 - 1):.1f}% > {tol_pct}% "
                        "tolerance)"
                    )
    return violations


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--root", default=REPO, help="repo root (BENCH_r*.json)")
    ap.add_argument(
        "--tolerance-pct", type=float, default=10.0,
        help="allowed regression before failing (run-to-run noise floor)",
    )
    ap.add_argument(
        "--serve", default=os.path.join(REPO, "docs", "serve_bench.json")
    )
    ap.add_argument(
        "--serve-baseline",
        default=os.path.join(REPO, "docs", "serve_bench_prev.json"),
        help="prior round's serve snapshot; absent = empty history = pass",
    )
    args = ap.parse_args(argv)
    violations = check_bench(args.root, args.tolerance_pct)
    violations += check_serve(args.serve, args.serve_baseline, args.tolerance_pct)
    if violations:
        print(f"{len(violations)} perf regression(s) beyond "
              f"{args.tolerance_pct}% tolerance:")
        for v in violations:
            print(" -", v)
        return 1
    cells = bench_cells(args.root)
    if os.path.isfile(args.serve_baseline):
        serve_note = " and the serve baseline pairs"
    else:
        # Inert halves must be VISIBLE: a silently-skipped serve gate
        # reads as "serve is covered" when it is not.
        serve_note = ""
        print(
            f"note: serve baseline {args.serve_baseline} absent — serve "
            "p99/img-s gate skipped (capture one with "
            "`cp docs/serve_bench.json docs/serve_bench_prev.json` before "
            "refreshing the snapshot)"
        )
    print(
        f"ok: no perf regression beyond {args.tolerance_pct}% across "
        f"{len(cells)} comparable BENCH cell(s)" + serve_note
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
