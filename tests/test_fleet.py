"""Tests for the fleet-serving subsystem (mpi_pytorch_tpu/serve/fleet/).

The ISSUE 9 acceptance surface: load-aware dispatch picks the shorter
queue under a fake-slow host (MPT_FAULT_DELAY_PROCESS), kill-one-host
failover re-dispatches every in-flight request exactly once with the
warm spare promoted (the in-process twin of the ``_dryrun_fleet`` CI
leg), admission control rejects at the FRONT DOOR before any per-host
queue overflows, controller retunes change ``max_wait_ms`` / the active
bucket set with ``compiles_after_warmup == 0`` throughout, continuous
batching keeps responses correctly routed across overlapping flushes,
the ``retry_after_ms`` backpressure hint, the ``--fleet N`` bench mode,
schema-v5 ``route``/``fleet`` records, and the report/regression-gate
tooling over them.
"""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cpu_env(**extra):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra)
    return env


def _images(n, size=32, seed=0):
    rng = np.random.default_rng(seed)
    return [
        rng.integers(0, 256, size=(size, size, 3)).astype(np.uint8)
        for _ in range(n)
    ]


# ----------------------------------------------------- shared fleet fixtures


@pytest.fixture(scope="module")
def fleet_cfg():
    from mpi_pytorch_tpu.config import Config

    cfg = Config(
        model_name="resnet18", num_classes=16, width=32, height=32,
        synthetic_data=True, compute_dtype="float32",
        serve_buckets="1,4", serve_max_wait_ms=2.0, serve_topk=3,
        serve_queue_depth=64, loader_workers=4,
        serve_fleet_hosts=2, serve_probe_interval_ms=50.0,
        metrics_file="", log_file="", eval_log_file="",
    )
    cfg.validate_config()
    return cfg


@pytest.fixture(scope="module")
def shared_exe(fleet_cfg):
    """ONE warmed executable set for the whole module — every FleetServer
    below shares it, so tests pay the warmup compiles once."""
    import jax
    from jax.sharding import Mesh

    from mpi_pytorch_tpu.evaluate import build_inference
    from mpi_pytorch_tpu.serve.executables import BucketExecutables
    from mpi_pytorch_tpu.train.step import place_state_on_mesh

    mesh = Mesh(np.array(jax.devices()).reshape(-1, 1), ("data", "model"))
    _, _, state, _ = build_inference(
        fleet_cfg, mesh=mesh, manifests=(None, None)
    )
    state = place_state_on_mesh(state, mesh)
    exe = BucketExecutables(fleet_cfg, state, mesh)
    exe.warmup()
    return exe


def _make_fleet(fleet_cfg, shared_exe, **overrides):
    import dataclasses

    from mpi_pytorch_tpu.serve.fleet import FleetServer

    cfg = dataclasses.replace(fleet_cfg, **overrides)
    cfg.validate_config()
    return FleetServer(cfg, executables=shared_exe)


# ------------------------------------------------------------ schema (v5)


def test_route_and_fleet_record_schema():
    from mpi_pytorch_tpu.obs.schema import validate_record

    good_route = {
        "kind": "route", "ts": 1.0, "host": "h0", "requests": 12,
        "share": 0.5, "score": 3.2, "queue_depth": 4, "inflight": 2,
        "window_s": 1.0,
    }
    assert validate_record(good_route) == []
    assert validate_record({"kind": "route", "ts": 1.0, "host": "h0"})
    good_fleet = {
        "kind": "fleet", "ts": 1.0, "event": "failover", "host": "h0",
        "redispatched": 3, "spare": "h2",
    }
    assert validate_record(good_fleet) == []
    retune = {
        "kind": "fleet", "ts": 1.0, "event": "retune", "host": "h1",
        "max_wait_ms_from": 2.0, "max_wait_ms_to": 1.0,
        "buckets_from": "1,4", "buckets_to": "1", "p99_ms": 9.0,
        "target_p99_ms": 5.0, "compiles_after_warmup": 0,
    }
    assert validate_record(retune) == []
    assert validate_record({"kind": "fleet", "ts": 1.0})  # event required


def test_serve_bench_fleet_fields_schema():
    from mpi_pytorch_tpu.obs.schema import validate_record

    row = {
        "kind": "serve_bench", "ts": 1.0, "mode": "open", "buckets": "1,4",
        "max_wait_ms": 2.0, "requests": 10, "p50_ms": 1.0, "p95_ms": 2.0,
        "p99_ms": 3.0, "images_per_sec": 100.0, "fleet_hosts": 3,
        "per_host": {"h0": {"requests": 4}},
    }
    assert validate_record(row) == []


def test_config_fleet_knob_validation():
    from mpi_pytorch_tpu.config import Config

    Config(serve_fleet_hosts=3, serve_fleet_spare=True).validate_config()
    with pytest.raises(ValueError):
        Config(serve_fleet_hosts=-1).validate_config()
    # Fleet-only knobs without a fleet would be silently ignored → error.
    with pytest.raises(ValueError):
        Config(serve_fleet_spare=True).validate_config()
    with pytest.raises(ValueError):
        Config(serve_target_p99_ms=50.0).validate_config()
    with pytest.raises(ValueError):
        Config(serve_admission_tokens=8).validate_config()
    with pytest.raises(ValueError):
        Config(serve_fleet_hosts=2, serve_fail_probes=0).validate_config()
    with pytest.raises(ValueError):
        Config(
            serve_fleet_hosts=2, serve_probe_interval_ms=0
        ).validate_config()


# ------------------------------------------------- retry_after_ms satellite


def test_queue_full_carries_retry_after_hint():
    """ISSUE 9 bugfix satellite: the typed rejection now tells the client
    HOW LONG to back off, derived from the observed drain rate."""
    from mpi_pytorch_tpu.serve import (
        DynamicBatcher,
        PendingRequest,
        QueueFullError,
    )

    b = DynamicBatcher(buckets=(4,), max_wait_s=0.05, max_queue=2)
    b.submit(PendingRequest(payload=0, future=None))
    b.submit(PendingRequest(payload=1, future=None))
    with pytest.raises(QueueFullError) as exc:
        b.submit(PendingRequest(payload=2, future=None))
    # Cold server: the fallback hint (2× the flush deadline), never None
    # on a batcher-level rejection.
    assert exc.value.retry_after_ms and exc.value.retry_after_ms > 0

    # With an observed drain rate the hint tracks backlog/rate.
    b2 = DynamicBatcher(buckets=(2,), max_wait_s=0.0, max_queue=4)
    for i in range(4):
        b2.submit(PendingRequest(payload=i, future=None))
    assert len(b2.next_flush()) == 2
    time.sleep(0.01)
    assert len(b2.next_flush()) == 2  # two timed drains → a rate estimate
    with pytest.raises(QueueFullError) as exc2:
        for i in range(9):
            b2.submit(PendingRequest(payload=i, future=None))
    assert exc2.value.retry_after_ms > 0
    assert b2.retry_after_ms() > 0


# ------------------------------------------- batcher: active buckets, top-up


def test_batcher_active_buckets_and_drain_ready():
    from mpi_pytorch_tpu.serve import DynamicBatcher, PendingRequest

    b = DynamicBatcher(buckets=(1, 4, 8), max_wait_s=10.0, max_queue=32)
    assert b.active_buckets == (1, 4, 8)
    b.set_active_buckets((1, 4))
    assert b.active_buckets == (1, 4)
    with pytest.raises(ValueError):
        b.set_active_buckets((1, 16))  # 16 was never compiled
    with pytest.raises(ValueError):
        b.set_active_buckets(())
    # The flush-full threshold follows the ACTIVE largest bucket: 4
    # queued requests flush immediately even though 8 is compiled.
    for i in range(4):
        b.submit(PendingRequest(payload=i, future=None))
    t0 = time.monotonic()
    assert len(b.next_flush()) == 4
    assert time.monotonic() - t0 < 1.0

    # drain_ready: already-queued requests come back instantly, bounded.
    for i in range(3):
        b.submit(PendingRequest(payload=i, future=None))
    got = b.drain_ready(2)
    assert [r.payload for r in got] == [0, 1]
    assert [r.payload for r in b.drain_ready(8)] == [2]
    assert b.drain_ready(8) == []


def test_batcher_shrink_mid_wait_caps_flush_and_carries():
    """Review fix pinned: a retune that SHRINKS the active set while
    requests sit out the deadline must not hand the server more rows
    than any active executable's shape — the flush caps at the new
    largest bucket and the excess leads the next flush."""
    from mpi_pytorch_tpu.serve import DynamicBatcher, PendingRequest

    b = DynamicBatcher(buckets=(1, 4, 8), max_wait_s=0.4, max_queue=32)
    for i in range(6):
        b.submit(PendingRequest(payload=i, future=None))
    out = []
    t = threading.Thread(target=lambda: out.append(b.next_flush()))
    t.start()  # 6 < 8 and the deadline is 400 ms away: it waits
    time.sleep(0.1)
    b.set_active_buckets((1, 4))  # the controller's emergency shrink
    t.join(timeout=10)
    assert [r.payload for r in out[0]] == [0, 1, 2, 3]  # capped at 4
    # The displaced requests lead the NEXT flush, oldest-first.
    assert [r.payload for r in b.next_flush()] == [4, 5]


def test_continuous_batching_tops_up_inflight_flush(fleet_cfg, shared_exe):
    """The continuous-batching seam, deterministically: requests that
    arrive while a flush is stuck in preprocess ride THAT flush (topped
    up to the active bucket), instead of waiting out another deadline.
    Without the top-up this scenario dispatches a 1-request flush."""
    import dataclasses

    from mpi_pytorch_tpu.serve import InferenceServer

    cfg = dataclasses.replace(
        fleet_cfg, serve_fleet_hosts=0, serve_max_wait_ms=0.0,
    )
    cfg.validate_config()
    server = InferenceServer(cfg, executables=shared_exe)
    try:
        release = threading.Event()
        real_preprocess = server._preprocess

        def gated_preprocess(image):
            if isinstance(image, np.ndarray) and image[0, 0, 0] == 255:
                release.wait(timeout=30)
            return real_preprocess(image)

        server._preprocess = gated_preprocess
        slow = np.full((32, 32, 3), 255, np.uint8)
        fast = _images(3, seed=1)
        for im in fast:
            im[0, 0, 0] = 0
        futs = [server.submit(slow)]
        time.sleep(0.2)  # the 1-request flush is now blocked in preprocess
        futs += [server.submit(im) for im in fast]
        time.sleep(0.2)  # the late arrivals are queued behind it
        release.set()
        for f in futs:
            assert f.result(timeout=120).shape == (3,)
        stats = server.stats()
        # One topped-up flush of all 4 — not a flush of 1 then one of 3.
        assert stats["batches"] == 1, stats
        assert stats["by_bucket"][4] == 1, stats
        assert stats["compiles_after_warmup"] == 0
    finally:
        server._preprocess = real_preprocess
        server.close()


def test_continuous_batching_routes_responses_across_overlapping_flushes(
    fleet_cfg, shared_exe
):
    """Responses stay correctly routed while flush n+1 is admitted and
    dispatched behind on-device flush n: every request's top-k equals
    the prediction the same image gets in isolation."""
    import dataclasses

    from mpi_pytorch_tpu.serve import InferenceServer

    cfg = dataclasses.replace(fleet_cfg, serve_fleet_hosts=0)
    cfg.validate_config()
    server = InferenceServer(cfg, executables=shared_exe)
    try:
        images = _images(12, seed=3)
        # Isolated references, one at a time (each its own flush).
        ref = [server.predict_batch([im], timeout=120)[0] for im in images]
        # Now a rapid-fire wave: flushes overlap (dispatch n+1 while n is
        # on-device) and requests top up in-flight flushes.
        futs = [server.submit(im) for im in images]
        for f, expect in zip(futs, ref):
            np.testing.assert_array_equal(f.result(timeout=120), expect)
        assert server.stats()["compiles_after_warmup"] == 0
    finally:
        server.close()


# ------------------------------------------------------ load-aware dispatch


def test_load_aware_dispatch_avoids_slow_host(
    fleet_cfg, shared_exe, monkeypatch
):
    """A fake-slow host (MPT_FAULT_DELAY_PROCESS targets fleet-host 0,
    MPT_FAULT_DELAY_STEP_MS delays its every dispatch) builds queue
    depth; the router's EWMA scores must observe it via the registry
    snapshots and route the bulk of the traffic to the healthy host.

    The injected delay must DOMINATE the real step time and the arrival
    rate must be one the healthy host can actually drain — on a slow
    single-core box, 250 ms/step against a 100 req/s wave saturated BOTH
    hosts equally (lockstep scores, ~50/50 split) and the premise
    collapsed. 1 s/step at 25 req/s keeps h1's queue near-empty while
    h0 visibly wedges, on any hardware."""
    monkeypatch.setenv("MPT_FAULT_DELAY_STEP_MS", "1000")
    monkeypatch.setenv("MPT_FAULT_DELAY_PROCESS", "0")
    fleet = _make_fleet(fleet_cfg, shared_exe)
    try:
        images = _images(8)
        futs = []
        for i in range(40):
            futs.append(fleet.submit(images[i % 8]))
            time.sleep(0.04)
        for f in futs:
            assert f.result(timeout=120).shape == (3,)
        by_host = fleet.router.stats()["dispatched_by_host"]
        assert by_host["h0"] + by_host["h1"] == 40
        # The healthy host must carry the clear majority.
        assert by_host["h1"] > by_host["h0"], by_host
        assert by_host["h1"] >= 24, by_host
    finally:
        fleet.close()


def test_stale_snapshots_fall_back_to_power_of_two(fleet_cfg, shared_exe):
    """With the probe thread effectively off (huge interval → every
    snapshot stale), picking degrades to po2 over the router's own
    outstanding counts — it must still spread load, not wedge."""
    fleet = _make_fleet(
        fleet_cfg, shared_exe, serve_probe_interval_ms=60_000.0
    )
    try:
        preds = fleet.predict_batch(_images(16, seed=5), timeout=120)
        assert preds.shape == (16, 3)
        by_host = fleet.router.stats()["dispatched_by_host"]
        assert sum(by_host.values()) == 16
        assert all(v > 0 for v in by_host.values()), by_host  # both used
    finally:
        fleet.close()


# ------------------------------------------------------- admission control


def test_admission_rejects_at_front_door_before_host_overflow(
    fleet_cfg, shared_exe, monkeypatch
):
    """The global token budget rejects at the ROUTER with a typed,
    hint-carrying QueueFullError; no per-host queue ever overflows (the
    hosts' own rejected counters stay 0)."""
    from mpi_pytorch_tpu.serve import QueueFullError

    monkeypatch.setenv("MPT_FAULT_DELAY_STEP_MS", "150")  # both hosts slow
    fleet = _make_fleet(fleet_cfg, shared_exe, serve_admission_tokens=6)
    try:
        assert fleet.router.budget == 6
        images = _images(4, seed=7)
        futs, rejections = [], []
        for i in range(30):
            try:
                futs.append(fleet.submit(images[i % 4]))
            except QueueFullError as e:
                rejections.append(e)
        assert rejections, "the front door never engaged"
        assert all(
            e.retry_after_ms and e.retry_after_ms > 0 for e in rejections
        )
        for f in futs:
            assert f.result(timeout=120).shape == (3,)
        stats = fleet.stats()
        assert stats["router"]["front_door_rejections"] == len(rejections)
        # The point of the budget: hosts never saw their queues overflow.
        for name, s in stats["hosts"].items():
            assert s["rejected"] == 0, (name, s)
    finally:
        fleet.close()


# ------------------------------------------------------------- failover


def test_kill_one_host_failover_redispatches_exactly_once(
    fleet_cfg, shared_exe, monkeypatch, tmp_path
):
    """The in-process twin of the ``_dryrun_fleet`` CI leg: host h0 is
    hard-killed mid-traffic via the registered serve fault gates; every
    accepted request still resolves (zero lost), each re-dispatched
    in-flight request is re-dispatched EXACTLY once, the spare is
    promoted, and one kind="fleet" failover record lands in the stream
    with the surviving hosts at zero steady-state compiles."""
    from mpi_pytorch_tpu.obs.schema import load_records, validate_jsonl

    monkeypatch.setenv("MPT_FAULT_SERVE_KILL_HOST", "0")
    monkeypatch.setenv("MPT_FAULT_SERVE_KILL_AFTER", "5")
    # Slow flushes so the kill lands with requests genuinely in flight.
    monkeypatch.setenv("MPT_FAULT_DELAY_STEP_MS", "50")
    metrics_file = str(tmp_path / "fleet.jsonl")
    fleet = _make_fleet(
        fleet_cfg, shared_exe, serve_fleet_spare=True,
        metrics_file=metrics_file,
    )
    try:
        images = _images(8, seed=9)
        futs = []
        for i in range(40):
            futs.append(fleet.submit(images[i % 8]))
            time.sleep(0.005)
        for f in futs:
            assert f.result(timeout=120).shape == (3,)  # ZERO lost
        deadline = time.monotonic() + 10
        while not fleet.router.failovers and time.monotonic() < deadline:
            time.sleep(0.05)
        stats = fleet.stats()
        assert stats["router"]["failovers"] == ["h0"], stats["router"]
        assert "h2" in stats["hosts"], stats["hosts"].keys()  # spare in
        assert stats["router"]["spare"] is None  # ... and consumed
        # Exactly once: no flight id appears twice in the redispatch log.
        log = fleet.router.redispatch_log
        assert len(log) == len(set(log)), log
        assert stats["router"]["redispatched"] == len(log)
        for name, s in stats["hosts"].items():
            assert s["compiles_after_warmup"] == 0, (name, s)
    finally:
        fleet.close()
    assert validate_jsonl(metrics_file) == []
    records = load_records(metrics_file)
    failovers = [
        r for r in records
        if r["kind"] == "fleet" and r["event"] == "failover"
    ]
    assert len(failovers) == 1, failovers
    assert failovers[0]["host"] == "h0"
    assert failovers[0]["spare"] == "h2"
    assert any(
        r["kind"] == "fault" and r["reason"] == "injected_host_kill"
        for r in records
    ), "the kill gate must announce itself before striking"
    assert any(r["kind"] == "route" for r in records)


# ------------------------------------------------------------- controller


def test_controller_retunes_wait_then_buckets_with_zero_compiles(
    fleet_cfg, shared_exe, tmp_path
):
    """Breaching p99 halves max_wait_ms down to the floor, then deactivates
    the largest active bucket — every retune only activates pre-compiled
    executables and the compile counter stays 0 throughout."""
    import dataclasses

    from mpi_pytorch_tpu.serve import InferenceServer
    from mpi_pytorch_tpu.serve.fleet import FleetController, LocalHost
    from mpi_pytorch_tpu.utils.logging import MetricsWriter

    cfg = dataclasses.replace(fleet_cfg, serve_fleet_hosts=0)
    cfg.validate_config()
    server = InferenceServer(cfg, executables=shared_exe, host_index=0)
    host = LocalHost(server)
    writer = MetricsWriter(str(tmp_path / "ctl.jsonl"))
    ctl = FleetController(
        lambda: [host], target_p99_ms=0.001, metrics=writer,
    )
    try:
        images = _images(6, seed=11)
        assert host.max_wait_ms == 2.0
        server.predict_batch(images, timeout=120)
        assert ctl.tick() == 1  # real traffic breaches the absurd target
        assert host.max_wait_ms == 1.0
        # Each tick needs NEW observations — an idle fleet is not retuned.
        assert ctl.tick() == 0
        for _ in range(8):
            server.predict_batch(images, timeout=120)
            ctl.tick()
        # Wait pinned to the floor, then the largest bucket deactivated.
        assert host.max_wait_ms == 0.0
        assert host.active_buckets == (1,)
        assert set(host.active_buckets) <= set(host.buckets)
        assert host.compiles_after_warmup() == 0
        assert ctl.retunes >= 3
    finally:
        server.close()
        writer.close()
    from mpi_pytorch_tpu.obs.schema import load_records, validate_jsonl

    path = str(tmp_path / "ctl.jsonl")
    assert validate_jsonl(path) == []
    retunes = [
        r for r in load_records(path)
        if r["kind"] == "fleet" and r["event"] == "retune"
    ]
    assert retunes and all(
        r["compiles_after_warmup"] == 0 for r in retunes
    )
    assert retunes[0]["max_wait_ms_from"] == 2.0
    assert retunes[0]["max_wait_ms_to"] == 1.0
    assert any(r["buckets_to"] == "1" for r in retunes)


def test_controller_recovers_headroom(fleet_cfg, shared_exe):
    """With p99 far under target and poor fill, the controller restores
    deactivated buckets first, then grows the wait."""
    import dataclasses

    from mpi_pytorch_tpu.serve import InferenceServer
    from mpi_pytorch_tpu.serve.fleet import FleetController, LocalHost

    cfg = dataclasses.replace(fleet_cfg, serve_fleet_hosts=0)
    cfg.validate_config()
    server = InferenceServer(cfg, executables=shared_exe, host_index=0)
    host = LocalHost(server)
    # fill_low_pct above 100: the wait-growth branch triggers on any fill
    # (this test pins the mechanism; thresholds are policy).
    ctl = FleetController(
        lambda: [host], target_p99_ms=1e9, fill_low_pct=200.0
    )
    try:
        host.set_active_buckets((1,))
        host.set_max_wait_ms(1.0)
        images = _images(3, seed=13)
        server.predict_batch(images, timeout=120)  # batch-1 flushes: low fill
        assert ctl.tick() == 1
        assert host.active_buckets == (1, 4)  # bucket restored first
        server.predict_batch(images, timeout=120)
        assert ctl.tick() == 1
        assert host.max_wait_ms == 1.5  # then the wait grows
        assert host.compiles_after_warmup() == 0
    finally:
        server.close()


def test_set_active_buckets_rejects_uncompiled(fleet_cfg, shared_exe):
    import dataclasses

    from mpi_pytorch_tpu.serve import InferenceServer, ServeError

    cfg = dataclasses.replace(fleet_cfg, serve_fleet_hosts=0)
    cfg.validate_config()
    server = InferenceServer(cfg, executables=shared_exe)
    try:
        with pytest.raises(ServeError):
            server.set_active_buckets((1, 32))
        server.set_active_buckets((4,))
        assert server.active_buckets == (4,)
    finally:
        server.close()


# ----------------------------------------------------------- bench / tools


def test_bench_serve_fleet_smoke(tmp_path):
    """``--fleet 2 --smoke``: rows carry fleet_hosts + the per-host
    fill/latency breakdown, schema-valid, zero steady-state compiles."""
    from mpi_pytorch_tpu.obs.schema import validate_record

    out = tmp_path / "fleet_bench.json"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "bench_serve.py"),
         "--smoke", "--fleet", "2", "--out", str(out)],
        cwd=REPO, env=_cpu_env(), capture_output=True, text=True,
        timeout=900,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    rows = [json.loads(l) for l in out.read_text().splitlines() if l.strip()]
    assert rows and {r["mode"] for r in rows} == {"closed", "open"}
    for r in rows:
        assert not validate_record(r), validate_record(r)
        assert r["fleet_hosts"] == 2
        assert set(r["per_host"]) == {"h0", "h1"}
        assert r["compiles_after_warmup"] == 0
        assert sum(h["requests"] for h in r["per_host"].values()) > 0


def test_report_run_renders_fleet_sections(tmp_path, capsys):
    from tools import report_run

    path = tmp_path / "m.jsonl"
    records = [
        {"kind": "route", "ts": 1.0, "host": "h0", "requests": 30,
         "share": 0.75, "score": 2.1, "queue_depth": 1, "inflight": 0,
         "window_s": 1.0},
        {"kind": "route", "ts": 1.0, "host": "h1", "requests": 10,
         "share": 0.25, "score": 9.0, "queue_depth": 7, "inflight": 2,
         "window_s": 1.0},
        {"kind": "fleet", "ts": 2.0, "event": "failover", "host": "h1",
         "detail": "health-probe failures", "redispatched": 4,
         "spare": "h2"},
        {"kind": "fleet", "ts": 3.0, "event": "retune", "host": "h0",
         "max_wait_ms_from": 5.0, "max_wait_ms_to": 2.5,
         "buckets_from": "1,8,32", "buckets_to": "1,8", "p99_ms": 80.0,
         "target_p99_ms": 50.0, "compiles_after_warmup": 0},
    ]
    with open(path, "w") as f:
        for r in records:
            f.write(json.dumps(r) + "\n")
    assert report_run.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert "fleet routing: 40 request(s) over 2 host(s)" in out
    assert "75.0" in out  # h0's share
    assert "FLEET failover: host h1 drained" in out
    assert "4 in-flight re-dispatched, spare h2 promoted" in out
    assert "FLEET retune: host h0" in out
    assert "1,8,32 → 1,8" in out
    # And the JSON mode carries the same structure.
    assert report_run.main([str(path), "--json"]) == 0
    js = json.loads(capsys.readouterr().out)
    assert js["fleet_routing"]["hosts"]["h0"]["share_pct"] == 75.0
    assert js["fleet_events"][0]["event"] == "failover"


def test_check_regression_keys_fleet_rows_separately(tmp_path):
    """A fleet row and a single-host row at the same sweep point are
    different trend lines; and the gate still catches a fleet p99
    regression against a fleet baseline."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "check_regression", os.path.join(REPO, "tools", "check_regression.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    base_row = {
        "kind": "serve_bench", "ts": 1.0, "mode": "open", "buckets": "1,4",
        "max_wait_ms": 2.0, "offered_rps": 400.0, "model": "resnet18",
        "requests": 100, "p50_ms": 5.0, "p95_ms": 8.0, "p99_ms": 10.0,
        "images_per_sec": 1000.0,
    }
    fleet_row = dict(base_row, fleet_hosts=3, p99_ms=30.0)
    baseline = tmp_path / "prev.json"
    new = tmp_path / "new.json"
    with open(baseline, "w") as f:
        f.write(json.dumps(base_row) + "\n")
        f.write(json.dumps(fleet_row) + "\n")
    # The single-host point is unchanged; the FLEET point regressed 2x.
    with open(new, "w") as f:
        f.write(json.dumps(base_row) + "\n")
        f.write(json.dumps(dict(fleet_row, p99_ms=60.0)) + "\n")
    violations = mod.check_serve(str(new), str(baseline), 10.0)
    assert len(violations) == 1, violations
    assert "p99" in violations[0]
    # Distinct keys: a fleet row never pairs with a single-host row.
    assert mod._serve_key(base_row) != mod._serve_key(fleet_row)


def test_fleet_server_local_autoscale_spawns_from_shared_executables(
    fleet_cfg, shared_exe, tmp_path
):
    """ISSUE 12: the in-process twin of the remote autoscaler wiring — a
    local scale-up is a new InferenceServer over the SHARED warmed
    executable set (zero compiles by construction), admitted into the
    router with the admission budget growing to match."""
    from mpi_pytorch_tpu.obs.schema import load_records, validate_jsonl

    metrics_file = str(tmp_path / "autoscale.jsonl")
    fleet = _make_fleet(
        fleet_cfg, shared_exe, serve_autoscale=True,
        serve_fleet_min_hosts=1, serve_fleet_max_hosts=3,
        serve_scale_cooldown_s=0.0, serve_scale_reject_rate=0.5,
        serve_retune_interval_s=3600.0,  # drive tick() manually
        metrics_file=metrics_file,
    )
    try:
        assert fleet.autoscaler is not None
        budget_before = fleet.router.budget
        fleet.autoscaler.tick()  # baseline the signal deltas
        time.sleep(0.02)
        fleet.router.front_door_rejections += 100  # reject pressure
        assert fleet.autoscaler.tick() == "scale_up"
        hosts = fleet.hosts()
        assert len(hosts) == 3, [h.name for h in hosts]
        assert fleet.router.budget == budget_before + (
            fleet.cfg.serve_queue_depth
        )
        # The scaled-up host serves real traffic with zero compiles.
        preds = fleet.predict_batch(_images(8, seed=21), timeout=120)
        assert preds.shape == (8, 3)
        for name, s in fleet.stats()["hosts"].items():
            assert s["compiles_after_warmup"] == 0, (name, s)
    finally:
        fleet.close()
    assert validate_jsonl(metrics_file) == []
    ups = [
        r for r in load_records(metrics_file)
        if r["kind"] == "fleet" and r["event"] == "scale_up"
    ]
    assert len(ups) == 1
    assert ups[0]["hosts_from"] == 2 and ups[0]["hosts_to"] == 3
    assert ups[0]["compiles_after_warmup"] == 0


def test_fleet_rejects_shared_fixed_metrics_port(fleet_cfg):
    import dataclasses

    from mpi_pytorch_tpu.serve import ServeError
    from mpi_pytorch_tpu.serve.fleet import FleetServer

    cfg = dataclasses.replace(fleet_cfg, serve_metrics_port=8080)
    cfg.validate_config()
    with pytest.raises(ServeError, match="cannot be shared"):
        FleetServer(cfg, executables=object())
