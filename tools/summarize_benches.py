"""Render the bench tools' JSON artifacts as RESULTS-ready markdown.

The bench tools (``bench.py``, ``tools/bench_{zoo,modes,attention,eval}.py``,
``tools/bench_flags.py``, ``tools/roofline.py``) write
docs/{bench_latest,zoo_bench,zoo_flash,modes_bench,attention_bench,
eval_bench}.json plus the flag-sweep and roofline text files when given
``--out``. This prints the markdown tables those artifacts support, so
folding a set of chip runs into docs/RESULTS.md is one command:

    python tools/summarize_benches.py [docs]
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from mpi_pytorch_tpu.obs.replay import render_diff  # noqa: E402


def _load(path):
    if not os.path.exists(path):
        return None
    with open(path) as f:
        try:
            return json.load(f)
        except json.JSONDecodeError:
            # corrupt != absent: a killed run can truncate an artifact
            # mid-write, and that stage must not silently vanish.
            print(f"WARNING: {path} exists but is not valid JSON "
                  "(truncated by a killed run?)", file=sys.stderr)
            return None


def _load_jsonl(path):
    """One JSON object per line (tools/bench_eval.py output)."""
    if not os.path.exists(path):
        return None
    rows = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line.startswith("{"):
                try:
                    rows.append(json.loads(line))
                except json.JSONDecodeError:
                    print(f"WARNING: bad JSONL line in {path}",
                          file=sys.stderr)
    return rows or None


def _cell(text) -> str:
    """Escape markdown-table separators in interpolated text (bench_zoo
    error strings contain literal | separators)."""
    return str(text).replace("|", "\\|")


def main() -> None:
    out = sys.argv[1] if len(sys.argv) > 1 else "docs"

    headline = _load(os.path.join(out, "bench_latest.json"))
    if headline:
        print("## headline\n")
        print("```json")
        print(json.dumps(headline))
        print("```\n")

    zoo = _load(os.path.join(out, "zoo_bench.json"))
    if zoo:
        print("## zoo (§3b)\n")
        print("| model | batch/chip | img/s/chip | step ms | TFLOP/s | MFU |")
        print("|---|---|---|---|---|---|")
        for r in zoo:
            if "error" in r:
                print(f"| {r['model']} | — | ERROR: {_cell(r['error'][:60])} | | | |")
                continue
            print(
                f"| {r['model']} | {r['batch_per_chip']} | "
                f"{r['images_per_sec_per_chip']:,.0f} | {r['step_ms']} | "
                f"{r['tflops_per_chip']} | {r.get('mfu_pct', '?')}% |"
            )
        print()

    flash = _load(os.path.join(out, "zoo_flash.json"))
    if flash:
        print("## vit flash vs full (zoo rows above are full)\n")
        for r in flash:
            print(json.dumps(r))
        print()

    s2d = _load(os.path.join(out, "zoo_s2d.json"))
    if s2d:
        print("## resnet space-to-depth stem vs standard (zoo rows above "
              "are the standard stem)\n")
        for r in s2d:
            print(json.dumps(r))
        print()

    vmem = _load(os.path.join(out, "flags_vmem_sweep.json"))
    if vmem:
        print("## scoped-VMEM compiler-option sweep (headline)\n")
        print("| set | img/s/chip | MFU |")
        print("|---|---|---|")
        for r in vmem:
            print(f"| {_cell(r.get('label'))} | {r.get('value', 0):,.0f} | "
                  f"{r.get('mfu_pct', '?')}% |")
        print()

    modes = _load(os.path.join(out, "modes_bench.json"))
    if modes:
        print("## input/execution modes (§4c)\n")
        print("| mode | img/s/chip | vs baseline |")
        print("|---|---|---|")
        for r in modes:
            if "error" in r:
                print(f"| {r['mode']} | ERROR: {_cell(r['error'][:60])} | |")
                continue
            print(
                f"| {r['mode']} | {r['images_per_sec_per_chip']:,.0f} | "
                f"{r['vs_baseline']:,.0f}× |"
            )
        print()

    attn = _load(os.path.join(out, "attention_bench.json"))
    if attn:
        print("## attention microbench (flash vs full)\n")
        print("| S | full ms | flash ms | speedup | full temp MB | flash temp MB |")
        print("|---|---|---|---|---|---|")
        by_seq: dict[int, dict] = {}
        for r in attn:
            by_seq.setdefault(r["seq"], {})[r["impl"]] = r
        for seq in sorted(by_seq):
            f_, fl = by_seq[seq].get("full", {}), by_seq[seq].get("flash", {})
            if "error" in f_ or "error" in fl or not f_ or not fl:
                # keep whichever side succeeded, name the one that failed
                def fmt(r, impl):
                    if not r:
                        return f"{impl}: missing"
                    if "error" in r:
                        return f"{impl}: {_cell(r['error'][:50])}"
                    return f"{r['fwd_bwd_ms']} ms"
                print(f"| {seq} | {fmt(f_, 'full')} | {fmt(fl, 'flash')} | | | |")
                continue
            sp = f_["fwd_bwd_ms"] / fl["fwd_bwd_ms"] if fl["fwd_bwd_ms"] else 0
            print(
                f"| {seq} | {f_['fwd_bwd_ms']} | {fl['fwd_bwd_ms']} | "
                f"{sp:.2f}× | {f_.get('temp_hbm_mb', '?')} | "
                f"{fl.get('temp_hbm_mb', '?')} |"
            )
        print()

    ev = _load_jsonl(os.path.join(out, "eval_bench.json"))
    if ev:
        print("## inference bench\n")
        for r in ev:
            print(json.dumps(r))
        print()

    sb = _load_jsonl(os.path.join(out, "serve_bench.json"))
    if sb:
        print("## serving latency vs load (tools/bench_serve.py)\n")
        # The v10 tenant columns: only rendered when some row carries a
        # load_shape (a multi-tenant sweep) — single-model artifacts
        # print the same table as before.
        tenants = any(r.get("load_shape") for r in sb)
        tenant_head = "model | shape | " if tenants else ""
        # The v14 workload column: only rendered when some row replayed a
        # fingerprinted workload — pre-v14 artifacts print the same table.
        replays = any(r.get("workload") for r in sb)
        workload_head = "workload | " if replays else ""
        print(f"| mode | buckets | wait ms | offered rps | {tenant_head}"
              f"{workload_head}"
              "prec | fleet | p50 ms | p95 ms | p99 ms | img/s | fill | "
              "rejected | compiles |")
        print("|---" * (13 + (2 if tenants else 0) + (1 if replays else 0))
              + "|")
        for r in sb:
            rps = r.get("offered_rps")
            tenant_cells = (
                f"{r.get('model') or '—'} | {r.get('load_shape') or '—'} | "
                if tenants else ""
            )
            workload_cells = (
                f"{r.get('workload') or '—'} | " if replays else ""
            )
            print(
                f"| {r['mode']} | {_cell(r['buckets'])} | {r['max_wait_ms']} | "
                f"{'—' if rps is None else rps} | "
                f"{tenant_cells}"
                f"{workload_cells}"
                f"{r.get('precision') or 'bf16'} | "
                f"{r.get('fleet_hosts') or '—'} | {r['p50_ms']} | "
                f"{r['p95_ms']} | {r['p99_ms']} | {r['images_per_sec']:,.0f} | "
                f"{r.get('mean_fill_ratio', '?')} | {r.get('rejected', '?')} | "
                f"{r.get('compiles_after_warmup', '?')} |"
            )
        parities = {
            r["parity_top1"] for r in sb if r.get("parity_top1") is not None
        }
        if parities:
            print(
                "\nint8 rows: startup top-1 parity vs bf16 = "
                + ", ".join(str(p) for p in sorted(parities))
                + " (ops/quantize.py; offline oracle: evaluate --quantize-eval)"
            )
        # The v9 per-phase columns (collector-derived attribution): only
        # rendered when some row carries per_phase, so pre-v9 artifacts
        # print the same tables as before.
        pp_rows = [r for r in sb if r.get("per_phase")]
        if pp_rows:
            print("\n### per-phase p99 attribution (tools/trace_report.py "
                  "renders the waterfalls)\n")
            phases = sorted({
                p for r in pp_rows for p in r["per_phase"]
            })
            print("| mode | buckets | wait ms | " +
                  " | ".join(f"{p} p99" for p in phases) + " |")
            print("|---" * (3 + len(phases)) + "|")
            for r in pp_rows:
                cells = [
                    str((r["per_phase"].get(p) or {}).get("p99_ms", "—"))
                    for p in phases
                ]
                print(f"| {r['mode']} | {_cell(r['buckets'])} | "
                      f"{r['max_wait_ms']} | " + " | ".join(cells) + " |")
        # The v14 replay differential: recorded vs replayed per-phase p99
        # for rows that re-drove a fingerprinted workload (cite the
        # fingerprint when quoting these numbers — SERVING.md).
        diff_rows = [r for r in sb if isinstance(r.get("replay_diff"), dict)]
        if diff_rows:
            print("\n### trace-replay differential "
                  "(tools/bench_serve.py --replay)\n")
            print("```")
            for r in diff_rows:
                for ln in render_diff(r["replay_diff"]):
                    print(ln)
            print("```")
        print()

    for name in ("roofline_resnet18.txt", "roofline_densenet121.txt",
                 "flags_sweep.txt", "flags_densenet.txt",
                 "flags_squeezenet.txt"):
        p = os.path.join(out, name)
        if os.path.exists(p):
            print(f"## {name}\n")
            with open(p) as f:
                print(f.read().strip()[:4000])
            print()


if __name__ == "__main__":
    main()
