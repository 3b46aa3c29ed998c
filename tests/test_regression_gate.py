"""Perf regression gate (tools/check_regression.py) — the tier-1 wrapper
(the check_results_artifacts pattern) plus unit coverage: regression
detection, tolerance, metric-string isolation, wedged-round (rc!=0) and
null-cell tolerance, empty histories, and serve p99/img-s baseline pairs."""

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

import check_regression  # noqa: E402


def _bench(path, rnd, value, metric="m train img/s", rc=0, parsed=True):
    cell = {"metric": metric, "value": value} if parsed else None
    with open(os.path.join(path, f"BENCH_r{rnd:02d}.json"), "w") as f:
        json.dump({"n": rnd, "rc": rc, "parsed": cell}, f)


def test_committed_history_passes():
    """THE gate: the repo's own bench trajectory must be regression-free
    (an empty ``BENCH_r*`` history — the state after PR 21 withdrew the old
    installation's records — is a pass, not an error)."""
    assert check_regression.main([]) == 0


def test_detects_throughput_regression(tmp_path, capsys):
    _bench(tmp_path, 1, 1000.0)
    _bench(tmp_path, 2, 850.0)  # -15%
    rc = check_regression.main(["--root", str(tmp_path), "--tolerance-pct", "10"])
    assert rc == 1
    out = capsys.readouterr().out
    assert "regressed" in out and "r01" in out and "15.0%" in out


def test_tolerance_and_improvements_pass(tmp_path):
    _bench(tmp_path, 1, 1000.0)
    _bench(tmp_path, 2, 950.0)  # -5%: inside the 10% noise floor
    _bench(tmp_path, 3, 1200.0)  # improvement
    assert check_regression.main(["--root", str(tmp_path)]) == 0


def test_only_the_newest_pair_is_judged(tmp_path):
    """A historical dip that later recovered must not fail CI forever —
    the artifacts are immutable, so the gate protects only the CURRENT
    claim (newest cell vs its predecessor)."""
    _bench(tmp_path, 1, 1000.0)
    _bench(tmp_path, 2, 700.0)  # a real historical dip...
    _bench(tmp_path, 3, 1050.0)  # ...since recovered
    assert check_regression.main(["--root", str(tmp_path)]) == 0
    _bench(tmp_path, 4, 700.0)  # the NEWEST cell regressing still fails
    assert check_regression.main(["--root", str(tmp_path)]) == 1


def test_compares_latest_against_most_recent_comparable(tmp_path):
    """A wedged round between two good ones must not break the pairing:
    r03 compares against r01, the most recent round with the same metric."""
    _bench(tmp_path, 1, 1000.0)
    _bench(tmp_path, 2, 0.0, rc=3)  # lost to a wedged backend
    _bench(tmp_path, 3, 600.0)
    assert check_regression.main(["--root", str(tmp_path)]) == 1


def test_different_metric_strings_are_separate_trends(tmp_path):
    """A config change (batch size in the metric string) starts a NEW trend
    line — a smaller absolute number is not a regression."""
    _bench(tmp_path, 1, 1000.0, metric="m (batch 512)")
    _bench(tmp_path, 2, 400.0, metric="m (batch 2048)")
    assert check_regression.main(["--root", str(tmp_path)]) == 0


def test_tolerates_empty_and_null_history(tmp_path):
    assert check_regression.main(["--root", str(tmp_path)]) == 0  # no files
    _bench(tmp_path, 1, 0.0, rc=3, parsed=False)  # null cell
    _bench(tmp_path, 2, 500.0)  # first good round: no pair yet
    assert check_regression.main(["--root", str(tmp_path)]) == 0


def _serve_row(mode="closed", p99=40.0, ips=300.0, **kw):
    return {
        "kind": "serve_bench", "ts": 1.0, "mode": mode, "buckets": "1,8",
        "max_wait_ms": 2.0, "offered_rps": None, "requests": 48,
        "p50_ms": 10.0, "p95_ms": 30.0, "p99_ms": p99,
        "images_per_sec": ips, **kw,
    }


def _write_rows(path, rows):
    with open(path, "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")


def test_serve_p99_and_throughput_regressions(tmp_path, capsys):
    base, new = str(tmp_path / "base.json"), str(tmp_path / "new.json")
    _write_rows(base, [_serve_row(), _serve_row(mode="open", offered_rps=400.0)])
    _write_rows(new, [
        _serve_row(p99=60.0),  # +50% p99
        _serve_row(mode="open", offered_rps=400.0, ips=200.0),  # -33% img/s
    ])
    rc = check_regression.main([
        "--root", str(tmp_path), "--serve", new, "--serve-baseline", base,
    ])
    assert rc == 1
    out = capsys.readouterr().out
    assert "p99" in out and "img/s" in out


def test_serve_empty_history_and_null_cells_pass(tmp_path):
    new = str(tmp_path / "new.json")
    _write_rows(new, [_serve_row()])
    # No baseline file: the empty-history case of the current trajectory.
    assert check_regression.main([
        "--root", str(tmp_path), "--serve", new,
        "--serve-baseline", str(tmp_path / "missing.json"),
    ]) == 0
    # Staged/null chip cells skip the comparison, not the run.
    base = str(tmp_path / "base.json")
    _write_rows(base, [_serve_row(p99=None, ips=None)])
    assert check_regression.main([
        "--root", str(tmp_path), "--serve", new, "--serve-baseline", base,
    ]) == 0


def test_serve_within_tolerance_passes(tmp_path):
    base, new = str(tmp_path / "base.json"), str(tmp_path / "new.json")
    _write_rows(base, [_serve_row(p99=40.0, ips=300.0)])
    _write_rows(new, [_serve_row(p99=42.0, ips=290.0)])  # +5% / -3%
    assert check_regression.main([
        "--root", str(tmp_path), "--serve", new, "--serve-baseline", base,
    ]) == 0
