"""Tests for the remote fleet transport (ISSUE 12):
``serve/host.py`` (the serving-host process wire surface),
``serve/fleet/remote.py`` (RemoteHost / HostSupervisor / RemoteFleet),
``serve/fleet/autoscaler.py`` (FleetAutoscaler), the hardened
``ObsHTTPServer``, the generalized kill gate + ``kill-serve-host`` drill,
the retry_after_ms wire round trip honored by bench_serve's open-loop
client, schema v8, and the transport-keyed regression gate.

Most tests drive the REAL wire path (ServingHost over ObsHTTPServer ↔
RemoteHost over urllib) against a jax-free fake inference server, so the
transport/retry/timeout/taxonomy machinery is pinned in milliseconds;
one end-to-end test spawns a real ``python -m mpi_pytorch_tpu.serve.host``
subprocess, and the 3-host subprocess chaos drive (the
``_dryrun_remote_fleet`` twin) is slow-marked.
"""

import json
import os
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from concurrent.futures import Future

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cpu_env(**extra):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra)
    return env


# ------------------------------------------------------------ fakes / helpers


class FakeInferenceServer:
    """Duck-typed server for the wire-path tests: no jax, deterministic
    answers, scriptable failure modes."""

    name = "h0"

    def __init__(self, topk=3):
        from mpi_pytorch_tpu.serve.batcher import (
            PreprocessError,
            QueueFullError,
            ServerClosedError,
        )

        self._QueueFullError = QueueFullError
        self._ServerClosedError = ServerClosedError
        self._PreprocessError = PreprocessError
        self.topk = topk
        self.mode = "ok"  # ok | reject | closed | reqfault | hostfault | pending
        self.retry_after_ms = 123.0
        self.submits = 0
        self.max_wait_ms = 2.0
        self.active = (1, 4)
        self.closed = False

    def submit(self, image):
        self.submits += 1
        if self.mode == "reject":
            raise self._QueueFullError(
                "queue full", retry_after_ms=self.retry_after_ms
            )
        if self.mode == "closed":
            raise self._ServerClosedError("server is shut down")
        fut = Future()
        if self.mode == "reqfault":
            fut.set_exception(self._PreprocessError("poison payload"))
        elif self.mode == "hostfault":
            fut.set_exception(RuntimeError("device exploded"))
        elif self.mode == "pending":
            pass  # never resolves
        else:
            arr = np.asarray(image)
            fut.set_result(
                np.full((self.topk,), int(arr.reshape(-1)[0]), np.int32)
            )
        return fut

    def set_max_wait_ms(self, v):
        self.max_wait_ms = float(v)

    def set_active_buckets(self, buckets):
        from mpi_pytorch_tpu.serve.batcher import ServeError

        if not set(buckets) <= {1, 4}:
            raise ServeError("bucket was never compiled")
        self.active = tuple(buckets)

    def set_precision(self, precision):
        from mpi_pytorch_tpu.serve.batcher import ServeError

        if precision != "bf16":
            raise ServeError("precision was never compiled")

    def stats(self):
        return {"served": self.submits, "rejected": 0, "padded_rows": 0,
                "compiles_after_warmup": 0, "by_bucket": {1: self.submits}}

    def _healthz(self):
        return {
            "status": "closing" if self.closed else "ok",
            "queue_depth": 0, "compiles_after_warmup": 0,
            "served": self.submits, "rejected": 0, "buckets": [1, 4],
            "precision": "bf16", "queue_capacity": 8,
            "max_wait_ms": self.max_wait_ms,
            "active_buckets": list(self.active),
            "precisions": ["bf16"], "parity_top1": None,
            "topk": self.topk, "host_index": 0, "pid": None,
        }

    def close(self, drain=True):
        self.closed = True


@pytest.fixture()
def wire():
    """A live (ServingHost over a fake server, RemoteHost) pair."""
    from mpi_pytorch_tpu.serve.fleet.remote import RemoteHost
    from mpi_pytorch_tpu.serve.host import ServingHost

    server = FakeInferenceServer()
    host = ServingHost(server, port=0)
    remote = RemoteHost(
        f"http://127.0.0.1:{host.port}", name="h0", index=0,
        poll_slice_s=0.2, result_timeout_s=5.0, probe_retries=1,
    )
    yield server, host, remote
    remote._pool.shutdown(wait=False, cancel_futures=True)
    host.close()


class FakeHost:
    """In-memory HostHandle for router/autoscaler/supervisor units."""

    transport = "local"

    def __init__(self, name, index, queue_capacity=8):
        self.name = name
        self.index = index
        self.queue_capacity = queue_capacity
        self.buckets = (1, 4)
        self.active_buckets = (1, 4)
        self.max_wait_ms = 2.0
        self.precision = "bf16"
        self.precisions = ("bf16",)
        self.parity_top1 = None
        self.fail_mode = None  # None | "future" | "raise"
        self.submitted = 0
        self.closed = False
        self.hist = {}  # histograms served via snapshot()
        self.queue_depth = 0

    def submit(self, payload):
        from mpi_pytorch_tpu.serve.batcher import HostUnavailableError

        if self.fail_mode == "raise":
            raise HostUnavailableError(f"{self.name} unreachable")
        self.submitted += 1
        fut = Future()
        if self.fail_mode == "future":
            fut.set_exception(
                HostUnavailableError(f"{self.name} died mid-flight")
            )
        else:
            fut.set_result(np.full((3,), self.index, np.int32))
        return fut

    def snapshot(self):
        return {
            "counters": {},
            "gauges": {"serve/queue_depth": self.queue_depth},
            "histograms": dict(self.hist),
        }

    def alive(self):
        return not self.closed

    def qsize(self):
        return self.queue_depth

    def stats(self):
        return {"served": self.submitted, "rejected": 0, "padded_rows": 0,
                "compiles_after_warmup": 0}

    def compiles_after_warmup(self):
        return 0

    def set_max_wait_ms(self, v):
        self.max_wait_ms = float(v)

    def close(self, drain=True):
        self.closed = True

    def kill(self):
        self.closed = True


def _make_router(hosts, spare=None, **kw):
    from mpi_pytorch_tpu.serve.fleet import FleetRouter

    kw.setdefault("probe_interval_s", 10.0)  # probes quiet in units
    return FleetRouter(hosts, spare, **kw)


# ----------------------------------------------------------- schema (v8)


def test_schema_v8_scale_and_restart_records():
    from mpi_pytorch_tpu.obs.schema import SCHEMA_VERSION, validate_record

    assert SCHEMA_VERSION >= 8
    up = {
        "kind": "fleet", "ts": 1.0, "event": "scale_up", "host": "h4",
        "hosts_from": 3, "hosts_to": 4, "reason": "admission rejects",
        "reject_rate": 2.5, "queue_depth": 17, "p99_ms": 80.0,
        "target_p99_ms": 50.0, "compiles_after_warmup": 0,
        "transport": "http",
    }
    assert validate_record(up) == []
    down = {
        "kind": "fleet", "ts": 1.0, "event": "scale_down", "host": "h1",
        "hosts_from": 4, "hosts_to": 3, "reason": "idle", "reject_rate": 0.0,
        "queue_depth": 0,
    }
    assert validate_record(down) == []
    restart = {
        "kind": "fleet", "ts": 1.0, "event": "restart", "host": "h1",
        "detail": "supervisor restart #1", "restarts": 1,
        "compiles_after_warmup": 0, "transport": "http",
    }
    assert validate_record(restart) == []
    # transport on route records; typed wrong → rejected.
    route = {
        "kind": "route", "ts": 1.0, "host": "h0", "requests": 3,
        "transport": "http",
    }
    assert validate_record(route) == []
    assert validate_record(dict(route, transport=1))
    bench = {
        "kind": "serve_bench", "ts": 1.0, "mode": "open", "buckets": "1,4",
        "max_wait_ms": 2.0, "requests": 10, "p50_ms": 1.0, "p95_ms": 2.0,
        "p99_ms": 3.0, "images_per_sec": 100.0, "transport": "http",
    }
    assert validate_record(bench) == []


def test_config_remote_and_autoscale_knob_validation():
    from mpi_pytorch_tpu.config import Config

    Config(
        serve_fleet_hosts=2, serve_autoscale=True, serve_fleet_min_hosts=1,
        serve_fleet_max_hosts=4, serve_scale_cooldown_s=5.0,
    ).validate_config()
    # Autoscale is a fleet knob: silently-ignored combinations error.
    with pytest.raises(ValueError):
        Config(serve_autoscale=True).validate_config()
    with pytest.raises(ValueError):
        Config(serve_fleet_hosts=2, serve_fleet_max_hosts=3).validate_config()
    with pytest.raises(ValueError):
        Config(
            serve_fleet_hosts=2, serve_autoscale=True,
            serve_fleet_min_hosts=5, serve_fleet_max_hosts=3,
        ).validate_config()
    with pytest.raises(ValueError):
        Config(serve_connect_timeout_s=0).validate_config()
    with pytest.raises(ValueError):
        Config(serve_probe_retries=-1).validate_config()
    with pytest.raises(ValueError):
        Config(serve_port=-2).validate_config()


# ------------------------------------------------- hardened ObsHTTPServer


class _Reg:
    def prometheus_text(self):
        return "x 1\n"

    def snapshot(self):
        return {"counters": {}, "gauges": {}, "histograms": {}}


def test_http_server_bounds_request_bodies():
    from mpi_pytorch_tpu.serve.http import ObsHTTPServer

    srv = ObsHTTPServer(
        _Reg(), port=0, max_body_bytes=1024,
        post_routes={"/echo": lambda p, q, b: (
            200, "application/octet-stream", b, {}
        )},
    )
    try:
        url = srv.url("/echo")
        # In-bound body round-trips.
        with urllib.request.urlopen(
            urllib.request.Request(url, data=b"ok", method="POST"), timeout=5
        ) as resp:
            assert resp.read() == b"ok"
        # Over the bound → 413 before any handler runs.
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(
                urllib.request.Request(url, data=b"x" * 2048, method="POST"),
                timeout=5,
            )
        assert exc.value.code == 413
        # No Content-Length → 411 (raw socket; urllib always sends one).
        with socket.create_connection(("127.0.0.1", srv.port)) as s:
            s.sendall(b"POST /echo HTTP/1.1\r\nHost: x\r\n\r\n")
            assert b"411" in s.recv(1024).split(b"\r\n", 1)[0]
    finally:
        srv.close()


def test_http_server_cuts_hung_client_and_survives():
    """A client that never finishes its request is cut at the read
    timeout instead of pinning a handler thread — and close() is not
    hostage to it."""
    from mpi_pytorch_tpu.serve.http import ObsHTTPServer

    srv = ObsHTTPServer(_Reg(), port=0, read_timeout_s=0.3)
    try:
        s = socket.create_connection(("127.0.0.1", srv.port))
        s.sendall(b"GET /metricsz HTT")  # never completed
        s.settimeout(5)
        assert s.recv(1024) == b""  # server closed the connection
        s.close()
        with urllib.request.urlopen(srv.url("/healthz"), timeout=5) as resp:
            assert resp.status == 200  # still serving
    finally:
        t0 = time.monotonic()
        srv.close()
        assert time.monotonic() - t0 < 5.0


def test_http_server_graceful_close_drains_inflight():
    from mpi_pytorch_tpu.serve.http import ObsHTTPServer

    started = threading.Event()

    def slow(path, query, body):
        started.set()
        time.sleep(0.5)
        return (200, "text/plain", b"slow-done", {})

    srv = ObsHTTPServer(_Reg(), port=0, get_routes={"/slow": slow})
    out = {}

    def client():
        with urllib.request.urlopen(srv.url("/slow"), timeout=10) as resp:
            out["body"] = resp.read()

    t = threading.Thread(target=client)
    t.start()
    assert started.wait(5)
    srv.close()  # stops accepting FIRST, then waits for the handler
    t.join(timeout=10)
    assert out["body"] == b"slow-done"
    with pytest.raises(OSError):
        socket.create_connection(("127.0.0.1", srv.port), timeout=1)


# ------------------------------------------------- ServingHost wire surface


def test_serving_host_submit_result_roundtrip_idempotent(wire):
    from mpi_pytorch_tpu.serve.host import _npy_bytes

    server, host, remote = wire
    url = f"http://127.0.0.1:{host.port}"
    body = _npy_bytes(np.full((2, 2, 3), 9, np.uint8))
    with urllib.request.urlopen(
        urllib.request.Request(f"{url}/submit", data=body, method="POST"),
        timeout=5,
    ) as resp:
        assert resp.status == 202
        rid = json.loads(resp.read())["req_id"]
    for _ in range(2):  # delivery is idempotent until the reaper expires it
        with urllib.request.urlopen(
            f"{url}/result/{rid}?timeout_s=5", timeout=10
        ) as resp:
            preds = np.load(__import__("io").BytesIO(resp.read()))
        np.testing.assert_array_equal(preds, np.full((3,), 9, np.int32))
    # Unknown id → 404 (a restarted process forgot its predecessor's ids).
    with pytest.raises(urllib.error.HTTPError) as exc:
        urllib.request.urlopen(f"{url}/result/99999?timeout_s=0", timeout=5)
    assert exc.value.code == 404
    # Malformed body → 400 tagged as a request fault.
    with pytest.raises(urllib.error.HTTPError) as exc:
        urllib.request.urlopen(
            urllib.request.Request(
                f"{url}/submit", data=b"not-npy", method="POST"
            ),
            timeout=5,
        )
    assert exc.value.code == 400
    assert json.loads(exc.value.read())["taxonomy"] == "request"


def test_retry_after_ms_crosses_the_wire(wire):
    """The tentpole satellite: HTTP 429 carries retry_after_ms (body +
    Retry-After header) and RemoteHost re-raises a faithful typed
    QueueFullError."""
    from mpi_pytorch_tpu.serve.batcher import QueueFullError
    from mpi_pytorch_tpu.serve.host import _npy_bytes

    server, host, remote = wire
    server.mode = "reject"
    server.retry_after_ms = 456.5
    body = _npy_bytes(np.zeros((2, 2, 3), np.uint8))
    with pytest.raises(urllib.error.HTTPError) as exc:
        urllib.request.urlopen(
            urllib.request.Request(
                f"http://127.0.0.1:{host.port}/submit", data=body,
                method="POST",
            ),
            timeout=5,
        )
    assert exc.value.code == 429
    assert exc.value.headers["Retry-After"] == "1"
    assert json.loads(exc.value.read())["retry_after_ms"] == 456.5
    with pytest.raises(QueueFullError) as typed:
        remote.submit(np.zeros((2, 2, 3), np.uint8))
    assert typed.value.retry_after_ms == 456.5


def test_remote_host_error_taxonomy(wire):
    """Request faults propagate typed; host faults classify into
    HostUnavailableError (the router's re-dispatch branch); a closing
    server classifies ServerClosedError."""
    from mpi_pytorch_tpu.serve.batcher import (
        HostUnavailableError,
        ServeError,
        ServerClosedError,
    )

    server, host, remote = wire
    img = np.zeros((2, 2, 3), np.uint8)
    server.mode = "reqfault"
    with pytest.raises(ServeError) as exc:
        remote.submit(img).result(timeout=10)
    assert not isinstance(
        exc.value, (HostUnavailableError, ServerClosedError)
    )
    server.mode = "hostfault"
    with pytest.raises(HostUnavailableError):
        remote.submit(img).result(timeout=10)
    server.mode = "closed"
    with pytest.raises(ServerClosedError):
        remote.submit(img)
    # Result long-poll that never resolves → host-shaped after the
    # bounded result timeout (re-polled, not hung forever).
    server.mode = "pending"
    with pytest.raises(HostUnavailableError):
        remote.submit(img).result(timeout=30)


def test_remote_host_control_and_probe_surface(wire):
    from mpi_pytorch_tpu.serve.batcher import ServeError

    server, host, remote = wire
    assert remote.queue_capacity == 8
    assert remote.buckets == (1, 4)
    assert remote.alive()
    remote.set_max_wait_ms(0.5)
    assert server.max_wait_ms == 0.5
    assert remote.max_wait_ms == 0.5  # control invalidates the facts cache
    remote.set_active_buckets((1,))
    assert server.active == (1,)
    with pytest.raises(ServeError):
        remote.set_active_buckets((1, 32))  # typed 400 crosses back
    snap = remote.snapshot()
    assert set(snap) == {"counters", "gauges", "histograms"}
    assert remote.stats()["served"] == server.submits
    assert remote.compiles_after_warmup() == 0


def test_remote_host_probe_retries_but_never_submit_retries():
    """Probes (idempotent) get bounded jittered retries through a flaky
    wire; submit gets exactly ONE attempt — a retry could double-enqueue
    and exactly-once re-dispatch belongs to the router."""
    from mpi_pytorch_tpu.serve.batcher import HostUnavailableError
    from mpi_pytorch_tpu.serve.fleet.remote import RemoteHost
    from mpi_pytorch_tpu.serve.http import ObsHTTPServer

    calls = {"metricsz": 0, "submit": 0}
    healthz = {
        "status": "ok", "queue_capacity": 8, "buckets": [1],
        "queue_depth": 0, "compiles_after_warmup": 0, "topk": 1,
        "host_index": 0, "pid": None,
    }

    def flaky_metricsz():
        calls["metricsz"] += 1
        if calls["metricsz"] <= 2:
            raise RuntimeError("transient scrape failure")  # → 500
        return {"counters": {}, "gauges": {}, "histograms": {}}

    def failing_submit(path, query, body):
        calls["submit"] += 1
        return (500, "application/json",
                json.dumps({"error": "internal"}).encode(), {})

    srv = ObsHTTPServer(
        _Reg(), healthz=lambda: healthz, port=0, metricsz=flaky_metricsz,
        post_routes={"/submit": failing_submit},
    )
    try:
        remote = RemoteHost(
            f"http://127.0.0.1:{srv.port}", name="h0", index=0,
            probe_retries=2,
        )
        snap = remote.snapshot()  # two 500s absorbed by the retry budget
        assert calls["metricsz"] == 3
        assert set(snap) == {"counters", "gauges", "histograms"}
        with pytest.raises(HostUnavailableError):
            remote.submit(np.zeros((2, 2, 3), np.uint8))
        assert calls["submit"] == 1, "submit must never be retried"
        remote._pool.shutdown(wait=False, cancel_futures=True)
    finally:
        srv.close()


def test_remote_host_dead_endpoint_is_loud():
    from mpi_pytorch_tpu.serve.batcher import HostUnavailableError
    from mpi_pytorch_tpu.serve.fleet.remote import RemoteHost

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        dead_port = s.getsockname()[1]
    with pytest.raises(HostUnavailableError):
        RemoteHost(
            f"http://127.0.0.1:{dead_port}", name="hx", index=0,
            probe_retries=0,
        )


# ----------------------------------------- router: taxonomy + membership


def test_router_redispatches_host_unavailable_futures():
    """A future failing HostUnavailableError (the remote transport's
    mid-flight death) re-dispatches exactly once — never propagates to
    the caller as a request fault."""
    a, b = FakeHost("h0", 0), FakeHost("h1", 1)
    a.fail_mode = "future"
    router = _make_router([a, b], fail_probes=1)
    try:
        futs = [router.submit(i) for i in range(8)]
        preds = [f.result(timeout=30) for f in futs]
        assert all(p[0] == 1 for p in preds)  # every answer came from h1
        if a.submitted:  # h0 was hit before its first failure drained it
            log = router.redispatch_log
            assert log and len(log) == len(set(log))
            assert router.failovers == ["h0"]
    finally:
        router.close()


def test_router_add_and_retire_host():
    a, b = FakeHost("h0", 0), FakeHost("h1", 1)
    router = _make_router([a, b])
    try:
        assert router.budget == 16  # auto budget: sum of capacities
        c = FakeHost("h2", 2)
        router.add_host(c)
        assert {h.name for h in router.active_hosts()} == {"h0", "h1", "h2"}
        assert router.budget == 24  # auto budget grew with the host
        # Graceful retire: out of rotation, closed, nothing re-dispatched,
        # nothing marked dead.
        retired = router.retire_host("h2", wait_s=5.0)
        assert retired is c and c.closed
        assert {h.name for h in router.active_hosts()} == {"h0", "h1"}
        assert router.budget == 16
        assert router.redispatch_log == [] and router.failovers == []
        assert router.retire_host("h2") is None  # idempotent-ish
    finally:
        router.close()


def test_router_readmission_clears_dead_state():
    """The supervisor's re-admission path: a drained (dead) host name
    re-enters rotation with fresh state."""
    a, b = FakeHost("h0", 0), FakeHost("h1", 1)
    a.fail_mode = "raise"
    router = _make_router([a, b], fail_probes=1)
    try:
        assert router.budget == 16
        futs = [router.submit(i) for i in range(6)]
        for f in futs:
            f.result(timeout=30)
        deadline = time.monotonic() + 10
        while "h0" not in router.failovers and time.monotonic() < deadline:
            time.sleep(0.02)
        assert router.failovers == ["h0"]
        # Spare-less drain under an auto budget: the dead host's share
        # leaves the front door with it...
        assert router.budget == 8
        a2 = FakeHost("h0", 0)  # the restarted process, same identity
        router.add_host(a2)
        assert "h0" in {h.name for h in router.active_hosts()}
        assert "h0" not in router.stats()["dead"]
        # ...and re-admission restores it EXACTLY once — kill+restart
        # cycles must not inflate the budget.
        assert router.budget == 16
        futs = [router.submit(i) for i in range(20)]
        for f in futs:
            f.result(timeout=30)
        assert a2.submitted > 0  # traffic flows to the re-admitted host
    finally:
        router.close()


def test_router_restarted_spare_replaces_its_dead_handle():
    """A supervised spare that died and restarted re-enters as the SPARE
    (replacing the dead handle a failover would otherwise promote), not
    as an extra rotation host."""
    a = FakeHost("h0", 0)
    spare = FakeHost("h1", 1)
    router = _make_router([a], spare)
    try:
        assert router.budget == 8  # spare capacity is not admission budget
        spare2 = FakeHost("h1", 1)
        router.add_host(spare2, spare=True)
        assert router.spare_host() is spare2
        assert {h.name for h in router.active_hosts()} == {"h0"}
        assert router.budget == 8
    finally:
        router.close()


# ----------------------------------------------------------- autoscaler


class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _scaler(router, clock, tmp_path=None, writer=None, **kw):
    from mpi_pytorch_tpu.serve.fleet import FleetAutoscaler

    spawned = []

    def spawn():
        h = FakeHost(f"h{10 + len(spawned)}", 10 + len(spawned))
        spawned.append(h)
        return h

    retired = []
    kw.setdefault("cooldown_s", 10.0)
    kw.setdefault("idle_ticks", 2)
    scaler = FleetAutoscaler(
        router, spawn_fn=spawn, retire_fn=retired.append,
        metrics=writer, clock=clock, **kw,
    )
    return scaler, spawned, retired


def test_autoscaler_scales_up_on_reject_rate(tmp_path):
    from mpi_pytorch_tpu.obs.schema import load_records, validate_jsonl
    from mpi_pytorch_tpu.utils.logging import MetricsWriter

    router = _make_router([FakeHost("h0", 0)])
    path = str(tmp_path / "scale.jsonl")
    writer = MetricsWriter(path)
    clock = _FakeClock()
    scaler, spawned, _ = _scaler(
        router, clock, writer=writer, max_hosts=2, reject_rate_up=0.5,
        transport="http",
    )
    try:
        assert scaler.tick() is None  # first tick only baselines signals
        clock.t += 1.0
        router.front_door_rejections += 10  # 10 rejects/s — pressure
        assert scaler.tick() == "scale_up"
        assert spawned and len(router.active_hosts()) == 2
        # At max_hosts the bound holds even under continuing pressure.
        clock.t += 100.0
        router.front_door_rejections += 1000
        assert scaler.tick() is None
        assert len(router.active_hosts()) == 2
    finally:
        scaler.stop()
        router.close()
        writer.close()
    assert validate_jsonl(path) == []
    ups = [r for r in load_records(path) if r["event"] == "scale_up"]
    assert len(ups) == 1
    assert ups[0]["hosts_from"] == 1 and ups[0]["hosts_to"] == 2
    assert ups[0]["reject_rate"] > 0.5
    assert ups[0]["transport"] == "http"
    assert "reason" in ups[0]


def test_autoscaler_scales_up_on_p99_with_rising_queue():
    hosts = [FakeHost("h0", 0), FakeHost("h1", 1)]
    router = _make_router(hosts)
    clock = _FakeClock()
    scaler, spawned, _ = _scaler(
        router, clock, target_p99_ms=50.0, max_hosts=3, trend_window=2,
    )
    try:
        hosts[0].hist["serve/request_latency_ms"] = {"count": 5, "p99": 200.0}
        hosts[0].queue_depth = 2
        assert scaler.tick() is None  # trend not yet established
        clock.t += 1.0
        hosts[0].queue_depth = 9  # rising
        assert scaler.tick() == "scale_up"
        assert len(router.active_hosts()) == 3
    finally:
        scaler.stop()
        router.close()


def test_autoscaler_scales_down_at_idle_with_cooldown_and_min_bound(tmp_path):
    from mpi_pytorch_tpu.obs.schema import load_records
    from mpi_pytorch_tpu.utils.logging import MetricsWriter

    hosts = [FakeHost("h0", 0), FakeHost("h1", 1), FakeHost("h2", 2)]
    router = _make_router(hosts)
    path = str(tmp_path / "down.jsonl")
    writer = MetricsWriter(path)
    clock = _FakeClock()
    scaler, _, retired = _scaler(
        router, clock, writer=writer, min_hosts=2, cooldown_s=10.0,
    )
    try:
        # Make h2 the coldest (others carry traffic history).
        for h in hosts[:2]:
            for _ in range(4):
                router.submit(0).result(timeout=30)
        assert scaler.tick() is None  # idle streak 1
        clock.t += 1.0
        assert scaler.tick() == "scale_down"  # idle streak 2 → act
        assert len(router.active_hosts()) == 2
        assert retired and retired[0].closed
        # Cooldown: still idle, but no flap inside the window...
        clock.t += 1.0
        assert scaler.tick() is None
        # ...and past it, the min bound holds.
        clock.t += 20.0
        for _ in range(5):
            clock.t += 1.0
            assert scaler.tick() is None
        assert len(router.active_hosts()) == 2
    finally:
        scaler.stop()
        router.close()
        writer.close()
    downs = [r for r in load_records(path) if r["event"] == "scale_down"]
    assert len(downs) == 1
    assert downs[0]["hosts_from"] == 3 and downs[0]["hosts_to"] == 2


def test_autoscaler_rolling_restart_records():
    from mpi_pytorch_tpu.serve.fleet import FleetAutoscaler

    hosts = [FakeHost("h0", 0), FakeHost("h1", 1)]
    router = _make_router(hosts)
    cycled = []
    scaler = FleetAutoscaler(
        router, spawn_fn=lambda: None, restart_fn=cycled.append,
        cooldown_s=0.0,
    )
    try:
        assert scaler.rolling_restart() == 2
        assert [h.name for h in cycled] == ["h0", "h1"]
        assert scaler.actions == ["restart", "restart"]
    finally:
        scaler.stop()
        router.close()


# ----------------------------------------------------------- supervisor


class FakeProc:
    def __init__(self):
        self.rc = None

    def poll(self):
        return self.rc

    def terminate(self):
        self.rc = -15

    def kill(self):
        self.rc = -9

    def wait(self, timeout=None):
        return self.rc


class FakeRemoteHost(FakeHost):
    transport = "http"

    def __init__(self, name, index, compiles=0, healthy=True):
        super().__init__(name, index)
        self._compiles = compiles
        self._healthy = healthy

    def _healthz(self):
        return {
            "status": "ok" if self._healthy else "closing",
            "compiles_after_warmup": self._compiles,
        }


def test_supervisor_restart_backoff_and_warm_readmission(tmp_path):
    from mpi_pytorch_tpu.obs.schema import load_records, validate_jsonl
    from mpi_pytorch_tpu.serve.fleet.remote import HostSupervisor
    from mpi_pytorch_tpu.utils.logging import MetricsWriter

    router = _make_router([FakeHost("h9", 9)])  # placeholder rotation
    path = str(tmp_path / "sup.jsonl")
    writer = MetricsWriter(path)
    clock = _FakeClock()
    spawn_times = []
    spawn_fail = {"n": 0}

    def spawn(index):
        spawn_times.append(clock.t)
        if spawn_fail["n"] > 0:
            spawn_fail["n"] -= 1
            raise RuntimeError("spawn wedged")
        return FakeProc(), FakeRemoteHost(f"h{index}", index)

    sup = HostSupervisor(
        spawn, router=router, metrics=writer,
        backoff_base_s=0.5, backoff_max_s=8.0, clock=clock,
    )
    try:
        proc = FakeProc()
        sup.manage(0, proc, FakeRemoteHost("h0", 0))
        proc.rc = -9  # SIGKILL'd
        assert sup.tick() == 0  # death noticed, restart scheduled at +0.5
        clock.t = 0.4
        assert sup.tick() == 0  # backoff not elapsed
        clock.t = 0.6
        spawn_fail["n"] = 1  # first restart attempt fails → backoff doubles
        assert sup.tick() == 0
        entry = sup.entry(0)
        assert entry.state == "dead"
        # Failed attempt at 0.6 with restarts=1 → next at 0.6 + 1.0.
        clock.t = 1.2
        assert sup.tick() == 0
        clock.t = 1.7
        assert sup.tick() == 1  # restart + warm probe + re-admission
        assert spawn_times == [0.6, 1.7]  # exponential schedule, not a spin
        assert "h0" in {h.name for h in router.active_hosts()}
        assert sup.restarts_total == 1
        # Stability window forgives history.
        clock.t = 1.7 + 120.0
        sup.tick()
        assert sup.entry(0).restarts == 0
    finally:
        sup.stop()
        router.close()
        writer.close()
    assert validate_jsonl(path) == []
    restarts = [r for r in load_records(path) if r.get("event") == "restart"]
    assert len(restarts) == 1
    assert restarts[0]["host"] == "h0"
    assert restarts[0]["compiles_after_warmup"] == 0
    assert restarts[0]["transport"] == "http"


def test_supervisor_warm_probe_rejects_compiling_host():
    """A restarted host that would compile under traffic must NOT rejoin
    rotation — the warm-start invariant is checked, not assumed."""
    from mpi_pytorch_tpu.serve.fleet.remote import HostSupervisor

    router = _make_router([FakeHost("h9", 9)])
    clock = _FakeClock()

    def spawn(index):
        return FakeProc(), FakeRemoteHost(f"h{index}", index, compiles=2)

    sup = HostSupervisor(spawn, router=router, clock=clock)
    try:
        proc = FakeProc()
        sup.manage(0, proc, FakeRemoteHost("h0", 0))
        proc.rc = 1
        sup.tick()
        clock.t = 10.0
        assert sup.tick() == 0  # spawned but failed the warm probe
        assert sup.entry(0).state == "dead"
        assert "h0" not in {h.name for h in router.active_hosts()}
    finally:
        sup.stop()
        router.close()


# ------------------------------------------- one chip per child process


def test_chip_slots_give_each_child_its_own_chip(monkeypatch):
    """On a TPU machine every serving-host child must get a chip of its own
    through its environment — handed the parent's environment unchanged,
    every child asks for every chip and the first wins (shown working on
    the four-chip v5e host, PR 21)."""
    from mpi_pytorch_tpu.serve.fleet.remote import NoFreeChipError, _ChipSlots
    from mpi_pytorch_tpu.utils import hardware

    monkeypatch.setattr(hardware, "local_tpu_chips", lambda: 4)
    slots = _ChipSlots({"JAX_PLATFORMS": "tpu,cpu"})
    envs = [slots.env(i) for i in range(4)]
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
    assert all(e["TPU_PROCESS_BOUNDS"] == "1,1,1" for e in envs)
    assert all(e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1" for e in envs)
    assert len({e["TPU_MESH_CONTROLLER_PORT"] for e in envs}) == 4
    # A supervisor restart re-spawns the SAME index: same chip, not a new one.
    assert slots.env(2) == envs[2]
    # One child more than there are chips: typed, up front — not a hang.
    with pytest.raises(NoFreeChipError, match="no free TPU chip for host 7"):
        slots.env(7)
    # A retired host's chip goes to the next scale-up.
    slots.release(1)
    assert slots.env(7)["TPU_VISIBLE_CHIPS"] == "1"


def test_chip_slots_are_inert_without_a_tpu_or_on_cpu_children(monkeypatch):
    from mpi_pytorch_tpu.serve.fleet.remote import _ChipSlots
    from mpi_pytorch_tpu.utils import hardware

    assert _ChipSlots({}).env(0) == {}  # this sandbox: no chip to hand out
    monkeypatch.setattr(hardware, "local_tpu_chips", lambda: 4)
    # Children pinned to the CPU never touch the chips: any number may run.
    slots = _ChipSlots({"JAX_PLATFORMS": "cpu"})
    assert [slots.env(i) for i in range(9)] == [{}] * 9


def test_logging_reads_the_process_index_without_starting_a_backend():
    """The parent of a remote fleet logs and writes metrics; if either
    started a backend (``jax.process_index()`` does) it would take every
    chip its children need. Checked in a child: this process's backend is
    long since up."""
    code = (
        "from mpi_pytorch_tpu.utils.logging import MetricsWriter, run_logger\n"
        "run_logger().info('x'); MetricsWriter('').close()\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge._backends, list(xla_bridge._backends)\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, env=_cpu_env(),
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]


# ----------------------------------------------- chaos drill tooling


def test_kill_serve_host_finds_announces_and_strikes(tmp_path):
    from mpi_pytorch_tpu.obs.schema import load_records, validate_jsonl
    from tools import inject_faults

    # A decoy process whose argv mimics a serving host with ANOTHER index
    # plus the real target: the finder must hit index 7 only.
    argv_extra = ["mpi_pytorch_tpu.serve.host", "--serve-host-index"]
    sleeper = "import time; time.sleep(300)"
    decoy = subprocess.Popen([sys.executable, "-c", sleeper, *argv_extra, "5"])
    target = subprocess.Popen([sys.executable, "-c", sleeper, *argv_extra, "7"])
    metrics = str(tmp_path / "kill.jsonl")
    try:
        # Between fork and exec a child's /proc cmdline still shows the
        # PARENT's argv (no marker) — on a busy single-core box the scan
        # can win that race. Wait until both children have exec'd.
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            if inject_faults.find_serve_host_pids(5) == [decoy.pid] and \
                    inject_faults.find_serve_host_pids(7) == [target.pid]:
                break
            time.sleep(0.05)
        pids = inject_faults.find_serve_host_pids(7)
        assert pids == [target.pid]
        assert inject_faults.main(
            ["kill-serve-host", "--host-index", "7",
             "--metrics-file", metrics]
        ) == 0
        assert target.wait(timeout=10) == -9
        assert decoy.poll() is None  # the decoy lives
        with pytest.raises(ProcessLookupError):
            inject_faults.kill_serve_host(7)
    finally:
        for p in (decoy, target):
            if p.poll() is None:
                p.kill()
                p.wait()
    assert validate_jsonl(metrics) == []
    recs = load_records(metrics)
    assert len(recs) == 1 and recs[0]["reason"] == "injected_host_kill"
    assert "--serve-host-index" not in recs[0]["detail"]
    assert "index 7" in recs[0]["detail"]


def test_list_gates_documents_generalized_kill(capsys):
    from tools import inject_faults

    assert inject_faults.main(["list-gates"]) == 0
    out = capsys.readouterr().out
    assert "MPT_FAULT_SERVE_KILL_HOST" in out
    assert "SIGKILL" in out and "SUBPROCESS" in out


def test_open_loop_honors_retry_after_hint():
    """bench_serve's open-loop client backs off by the hint instead of
    hammering a saturated host (the end-to-end half of the wire
    round-trip satellite)."""
    import importlib.util

    from mpi_pytorch_tpu.serve.batcher import QueueFullError

    spec = importlib.util.spec_from_file_location(
        "bench_serve", os.path.join(REPO, "tools", "bench_serve.py")
    )
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)

    submit_times = []
    state = {"n": 0}

    class HintingServer:
        def submit(self, image):
            submit_times.append(time.monotonic())
            state["n"] += 1
            if state["n"] == 1:
                raise QueueFullError("full", retry_after_ms=300.0)
            fut = Future()
            fut.set_result(np.int32([1]))
            return fut

    lat, wall, rejected = bench.open_loop(
        HintingServer(), pool=[np.zeros((2, 2, 3), np.uint8)],
        requests=5, rps=1000.0, seed=0, timeout_s=10.0,
    )
    assert rejected == 1
    assert len(lat) == 4
    # The submission after the hinted rejection waited out the hint
    # (Poisson gaps at 1000 rps are ~1 ms — without the backoff the gap
    # would be three orders of magnitude smaller).
    assert submit_times[1] - submit_times[0] >= 0.25


def test_check_regression_keys_transport_separately(tmp_path):
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
        "images_per_sec": 1000.0, "fleet_hosts": 3,
    }
    remote_row = dict(base_row, transport="http", p99_ms=40.0)
    assert mod._serve_key(base_row) != mod._serve_key(remote_row)
    baseline, new = tmp_path / "prev.json", tmp_path / "new.json"
    with open(baseline, "w") as f:
        f.write(json.dumps(base_row) + "\n")
        f.write(json.dumps(remote_row) + "\n")
    # The remote point regressed 2×; the in-process one is unchanged —
    # exactly one violation, on the remote trend line.
    with open(new, "w") as f:
        f.write(json.dumps(base_row) + "\n")
        f.write(json.dumps(dict(remote_row, p99_ms=80.0)) + "\n")
    violations = mod.check_serve(str(new), str(baseline), 10.0)
    assert len(violations) == 1 and "http" in violations[0]


def test_report_run_renders_scale_and_restart_events(tmp_path, capsys):
    from tools import report_run

    path = tmp_path / "m.jsonl"
    records = [
        {"kind": "fleet", "ts": 1.0, "event": "scale_up", "host": "h3",
         "hosts_from": 2, "hosts_to": 3,
         "reason": "admission rejects at 2.10/s", "reject_rate": 2.1,
         "queue_depth": 14, "transport": "http"},
        {"kind": "fleet", "ts": 2.0, "event": "restart", "host": "h1",
         "detail": "supervisor restart #1", "restarts": 1,
         "compiles_after_warmup": 0, "transport": "http"},
        {"kind": "fleet", "ts": 3.0, "event": "scale_down", "host": "h0",
         "hosts_from": 3, "hosts_to": 2, "reason": "idle for 2 tick(s)",
         "reject_rate": 0.0, "queue_depth": 0},
    ]
    with open(path, "w") as f:
        for r in records:
            f.write(json.dumps(r) + "\n")
    assert report_run.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert "FLEET scale_up: 2 → 3 host(s) (h3)" in out
    assert "admission rejects" in out
    assert "FLEET restart: host h1 re-admitted" in out
    assert "FLEET scale_down: 3 → 2 host(s)" in out


# ----------------------------------------- end-to-end: a real host process


def _host_argv(tmp, port_file, **over):
    flags = {
        "--model-name": "resnet18", "--num-classes": "16", "--width": "32",
        "--height": "32", "--synthetic-data": "true",
        "--compute-dtype": "float32", "--serve-buckets": "1,4",
        "--serve-max-wait-ms": "2", "--serve-topk": "3",
        "--serve-queue-depth": "64", "--loader-workers": "2",
        "--serve-host-index": "0", "--serve-port-file": port_file,
        "--metrics-file": f"{tmp}/host.jsonl", "--log-file": "",
        "--eval-log-file": "",
    }
    flags.update(over)
    argv = [sys.executable, "-m", "mpi_pytorch_tpu.serve.host"]
    for k, v in flags.items():
        argv += [k, v]
    return argv


def test_live_host_process_probe_submit_429_and_drain(tmp_path):
    """The non-slow end-to-end: spawn ONE real serving-host process,
    drive probe + submit over the wire, force deterministic 429s via the
    registered slow-flush gate, and shut it down gracefully."""
    from mpi_pytorch_tpu.obs.schema import validate_jsonl
    from mpi_pytorch_tpu.serve.batcher import QueueFullError
    from mpi_pytorch_tpu.serve.fleet.remote import RemoteHost
    from mpi_pytorch_tpu.serve.http import wait_port_file

    tmp = str(tmp_path)
    port_file = f"{tmp}/port.json"
    # Every flush on this fleet-host sleeps 250 ms (the registered fake
    # slow-host gate) → a tight submit loop overflows the bounded queue
    # deterministically, and the 429s carry drain-rate-derived hints.
    env = _cpu_env(
        MPT_FAULT_DELAY_STEP_MS="250", MPT_FAULT_DELAY_PROCESS="0",
    )
    proc = subprocess.Popen(
        _host_argv(tmp, port_file, **{"--serve-queue-depth": "4"}),
        env=env, cwd=REPO, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True,
    )
    try:
        ready = wait_port_file(port_file, 240, proc)
        assert ready["host_index"] == 0 and ready["pid"] == proc.pid
        remote = RemoteHost(
            f"http://127.0.0.1:{ready['port']}", name="h0", index=0,
            pid=ready["pid"],
        )
        assert remote.alive()
        assert remote.buckets == (1, 4)
        assert remote.queue_capacity == 4
        rng = np.random.default_rng(0)
        images = [
            rng.integers(0, 256, size=(32, 32, 3)).astype(np.uint8)
            for _ in range(8)
        ]
        futs, rejections = [], []
        for i in range(30):
            try:
                futs.append(remote.submit(images[i % 8]))
            except QueueFullError as e:
                rejections.append(e)
        assert rejections, "the bounded queue never pushed back"
        assert all(
            e.retry_after_ms and e.retry_after_ms > 0 for e in rejections
        ), "429s must carry the retry_after_ms hint over the wire"
        for f in futs:
            assert f.result(timeout=120).shape == (3,)
        assert remote.compiles_after_warmup() == 0
        snap = remote.snapshot()
        assert snap["counters"]["serve/served"] >= len(futs)
        remote.close(drain=True)
        assert proc.wait(timeout=60) == 0  # graceful wire shutdown
        assert validate_jsonl(f"{tmp}/host.jsonl") == []
    finally:
        if proc.poll() is None:
            proc.kill()
            print(proc.communicate()[0][-3000:])
            raise AssertionError("host process had to be killed")


@pytest.mark.slow
def test_remote_fleet_subprocess_chaos_drive():
    """The 3-host subprocess chaos drive — the in-tree twin of the
    ``_dryrun_remote_fleet`` CI leg (SIGKILL mid-traffic → zero lost,
    failover, supervisor re-admission, bounded autoscale, schema-clean)."""
    child = (
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "import sys\n"
        f"sys.path.insert(0, {REPO!r})\n"
        "from __graft_entry__ import _dryrun_remote_fleet_child\n"
        "_dryrun_remote_fleet_child()\n"
    )
    env = _cpu_env(
        MPT_FAULT_SERVE_KILL_HOST="1", MPT_FAULT_SERVE_KILL_AFTER="8",
    )
    env["XLA_FLAGS"] = " ".join(
        f for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f
    )
    out = subprocess.run(
        [sys.executable, "-c", child], env=env, cwd=REPO,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        timeout=1200,
    )
    assert out.returncode == 0 and "REMOTE_FLEET_OK" in out.stdout, out.stdout
