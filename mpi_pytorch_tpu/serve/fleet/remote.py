"""Remote fleet transport (ISSUE 12 / ROADMAP item 2): drive REAL serving
processes over HTTP, with the failure machinery exercised across a real
process boundary.

PR 9's fleet was in-process: ``LocalHost`` wraps an ``InferenceServer``
in threads, so "kill a host" never meant killing a process and the
drain → exactly-once-redispatch → spare-promotion state machine had only
ever seen simulated death. This module is the ``/metricsz``-shaped twin
that surface was deliberately built for:

- **``RemoteHost``** — the ``HostHandle`` over HTTP. ``submit`` POSTs the
  request bytes (``.npy`` on the wire) and long-polls the result on a
  bounded poller pool; probes (``/metricsz``, ``/healthz``) get bounded
  JITTERED retries because they are idempotent — ``submit`` gets NONE,
  because a submit retry could double-enqueue and the router's
  K-consecutive-failure drain streak is the designed response to submit
  failure (exactly-once re-dispatch stays with the router, where the
  claim ledger lives). Connection-refused, connect/read timeouts, and
  5xx all classify into ``HostUnavailableError`` — the same
  dispatch-failure taxonomy the router already scores — while a wire 429
  re-raises a faithful ``QueueFullError`` (``retry_after_ms`` intact) and
  a 400 re-raises the request-fault ``ServeError`` that must propagate to
  the caller, not re-dispatch.
- **``HostSupervisor``** — process lifecycle. Watches each serving
  subprocess; on death, restarts it with exponential backoff and
  re-admits it into the router only after warm-probe success (the
  ``/healthz`` handshake: process ready, executables warmed, zero
  steady-state compiles) — drain → restart → warm → re-admit, the
  weight-rollout drain machinery's failure-path twin. Warm start rides
  the persistent compilation cache (``--compilation-cache-dir``): a
  restarted host's warmup compiles are cache hits, so recovery costs
  placement + warmup execution, not XLA.
- **``RemoteFleet``** — the N-process harness: spawns
  ``python -m mpi_pytorch_tpu.serve.host`` per host (+ optional warm
  spare), fronts them with the unchanged ``FleetRouter``/
  ``FleetController``, wires the supervisor and (``--serve-autoscale``)
  the ``FleetAutoscaler``. The router never knows the transport — that
  was the point of the handle.

Chaos: ``MPT_FAULT_SERVE_KILL_HOST``/``_AFTER`` generalize — the router's
kill gate now lands on ``RemoteHost.kill()``, which SIGKILLs the serving
SUBPROCESS mid-traffic (``tools/inject_faults.py kill-serve-host`` is the
by-hand drill). The ``_dryrun_remote_fleet`` CI leg and
``tests/test_remote_fleet.py`` assert zero lost accepted requests, one
failover record, supervisor re-admission, and zero steady-state compiles
through real process death.
"""

from __future__ import annotations

import dataclasses
import http.client
import io
import json
import os
import random
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.parse
import urllib.request
from concurrent.futures import Future, ThreadPoolExecutor

import numpy as np

from mpi_pytorch_tpu.serve.batcher import (
    HostUnavailableError,
    QueueFullError,
    ServeError,
    ServerClosedError,
)


class _PendingResult(Exception):
    """Internal: the result long-poll sliced out (HTTP 408) — re-poll."""


class _StaleConnection(Exception):
    """Internal: a REUSED keep-alive connection died on first touch —
    the server reaped it while idle. Not a host verdict: retry exactly
    once on a fresh connection (reconnect-on-stale), and only THEN let
    a failure classify host-shaped."""


def _classify_status(status: int, body: bytes,
                     fallback_detail: str = "") -> Exception:
    """Wire status + JSON body → the typed in-process exception it
    stands for (the PR 12 taxonomy, transport-independent)."""
    try:
        payload = json.loads(body.decode())
    except Exception:  # noqa: BLE001 — a broken body is still a status
        payload = {}
    detail = (payload.get("detail") or payload.get("error")
              or fallback_detail or f"HTTP {status}")
    if status == 429:
        return QueueFullError(
            detail, retry_after_ms=payload.get("retry_after_ms"),
            model=payload.get("model"),
        )
    if status == 503:
        return ServerClosedError(detail)
    if status == 408:
        return _PendingResult()
    if status == 404:
        # /result for an id this process never issued: a RESTARTED host
        # forgot its predecessor's requests — host-shaped, re-dispatch.
        err = HostUnavailableError(f"unknown on host (restarted?): {detail}")
        err.status = status
        return err
    if 400 <= status < 500:
        err = ServeError(detail)
        err.status = status
        return err
    err = HostUnavailableError(f"HTTP {status}: {detail}")
    err.status = status
    return err


def _classify_http_error(e: urllib.error.HTTPError) -> Exception:
    """Back-compat shim over ``_classify_status`` for urllib call sites."""
    try:
        body = e.read()
    except Exception:  # noqa: BLE001
        body = b""
    return _classify_status(e.code, body, str(e))


class RemoteHost:
    """``HostHandle`` twin over HTTP — what the router drives when each
    serving host is its own process (or machine)."""

    transport = "http"

    def __init__(
        self,
        base_url: str,
        *,
        name: str,
        index: int,
        pid: int | None = None,
        connect_timeout_s: float = 2.0,
        read_timeout_s: float = 30.0,
        probe_retries: int = 2,
        poll_slice_s: float = 5.0,
        result_timeout_s: float = 120.0,
        pollers: int = 8,
        facts_ttl_s: float = 0.2,
        seed: int = 0,
        logger=None,
        spans=None,
    ):
        from mpi_pytorch_tpu.utils.logging import run_logger

        self.base_url = base_url.rstrip("/")
        self.name = name
        self.index = index
        self._logger = logger or run_logger()
        self.connect_timeout_s = float(connect_timeout_s)
        self.read_timeout_s = float(read_timeout_s)
        self.probe_retries = int(probe_retries)
        self.poll_slice_s = float(poll_slice_s)
        self.result_timeout_s = float(result_timeout_s)
        self._facts_ttl_s = float(facts_ttl_s)
        self._rng = random.Random(seed)
        self._closed = False
        # Local chip count the host process reported at readiness
        # (RemoteFleet._spawn fills it; None when unknown).
        self.chips: int | None = None
        # Keep-alive connection pool (ISSUE 16 satellite): the server
        # side has always spoken HTTP/1.1 with Content-Length, so the
        # only reason every call paid a TCP handshake was the client's
        # one-shot urlopen. Connections are checked out per call and
        # returned after a clean response; a stale one (reaped by the
        # peer while idle) is replaced via reconnect-on-stale. Bounded
        # RETENTION (creation is demand-driven — the poller pool is the
        # real concurrency cap).
        self._conns: list[http.client.HTTPConnection] = []
        self._conns_lock = threading.Lock()
        self._conns_cap = max(4, pollers)
        self._netloc = urllib.parse.urlsplit(self.base_url).netloc
        # Router-process span ring for the WIRE halves of a traced
        # request (wire/submit POST, wire/result long-poll) — None keeps
        # the transport fully inert for tracing (ISSUE 13).
        self._spans = spans
        self._pool = ThreadPoolExecutor(
            max_workers=max(1, pollers),
            thread_name_prefix=f"remote-{name}",
        )
        self._facts_lock = threading.Lock()
        self._facts_cache: dict | None = None
        self._facts_t = -1.0
        # Facts generation (ISSUE 14 satellite): zoo hosts bump this
        # counter on every resident-model change (swap-in/evict), and it
        # rides BOTH /healthz and /metricsz — so the probe loop's
        # snapshot invalidates a stale facts cache the moment the
        # resident set changes, and the router never dispatches a tenant
        # to a host that just evicted it.
        self._facts_gen: int | None = None
        # First probe pins the static facts (capacity, compiled buckets,
        # pid) — constructing a RemoteHost against a dead endpoint is a
        # loud typed failure, not a handle that fails later.
        facts = self._healthz(retries=self.probe_retries)
        self.pid = pid if pid is not None else facts.get("pid")
        self.queue_capacity = int(facts.get("queue_capacity") or 0)
        self.buckets = tuple(facts.get("buckets") or ())
        self.topk = facts.get("topk")

    # --------------------------------------------------------- wire plumbing

    def _checkout_conn(self, timeout: float):
        """(conn, reused): a pooled keep-alive connection, or a fresh one
        when the pool is dry."""
        with self._conns_lock:
            conn = self._conns.pop() if self._conns else None
        if conn is not None:
            if conn.sock is not None:
                conn.sock.settimeout(timeout)
            return conn, True
        return http.client.HTTPConnection(self._netloc, timeout=timeout), False

    def _checkin_conn(self, conn, keep: bool) -> None:
        if keep and not self._closed:
            with self._conns_lock:
                if len(self._conns) < self._conns_cap:
                    self._conns.append(conn)
                    return
        conn.close()

    def _drop_conns(self) -> None:
        with self._conns_lock:
            conns, self._conns = list(self._conns), []
        for c in conns:
            c.close()

    def _request_once(
        self, method: str, path: str, body: bytes | None,
        timeout: float, ctype: str, headers: dict | None,
        idempotent: bool = True,
    ) -> bytes:
        """One wire call on a (pooled) persistent connection. Raises
        ``_StaleConnection`` when a REUSED connection died on first
        touch — the keep-alive race, retried fresh by the caller —
        but ONLY for idempotent calls: a broken reused connection may
        have died AFTER the server accepted the request, so a silent
        retry of ``POST /submit`` would dispatch a duplicate inference.
        Non-idempotent calls surface the break as
        ``HostUnavailableError`` and let the router decide."""
        url = self.base_url + path
        conn, reused = self._checkout_conn(timeout)
        try:
            hdrs = dict(headers or {})
            if body is not None:
                hdrs["Content-Type"] = ctype
            try:
                conn.request(method, path, body=body, headers=hdrs)
                resp = conn.getresponse()
                data = resp.read()
            except (http.client.BadStatusLine,
                    http.client.CannotSendRequest,
                    BrokenPipeError, ConnectionResetError,
                    ConnectionAbortedError) as e:
                conn.close()
                if reused and idempotent:
                    # The peer reaped this idle keep-alive connection as
                    # we touched it — reconnect-on-stale, not a verdict.
                    raise _StaleConnection() from None
                raise HostUnavailableError(
                    f"{self.name} unreachable at {url}: {e}"
                ) from None
            except (urllib.error.URLError, ConnectionError, socket.timeout,
                    TimeoutError, OSError, http.client.HTTPException) as e:
                conn.close()
                reason = getattr(e, "reason", e)
                raise HostUnavailableError(
                    f"{self.name} unreachable at {url}: {reason}"
                ) from None
        except BaseException:
            # conn already closed on the paths above; belt-and-braces for
            # anything that escaped before checkin.
            conn.close()
            raise
        self._checkin_conn(conn, keep=not resp.will_close)
        if 200 <= resp.status < 300:
            return data
        raise _classify_status(resp.status, data)

    def _request(
        self, method: str, path: str, body: bytes | None = None, *,
        timeout: float, retries: int = 0, ctype: str = "application/json",
        headers: dict | None = None, idempotent: bool = True,
    ) -> bytes:
        """One wire call with bounded jittered retries on TRANSPORT
        failures only (the idempotent-probe discipline — callers pass
        ``retries=0`` for submit). Typed statuses raise immediately.
        A stale pooled connection costs one silent fresh-connection
        retry, never a retry-budget charge or a host-shaped verdict —
        unless ``idempotent=False`` (submit), where even THAT retry is
        forbidden: the break is ambiguous about server-side acceptance."""
        last: Exception | None = None
        for attempt in range(retries + 1):
            try:
                try:
                    return self._request_once(
                        method, path, body, timeout, ctype, headers,
                        idempotent,
                    )
                except _StaleConnection:
                    # Purge the pool first: its siblings idled just as
                    # long, so the retry must dial fresh, not pop the
                    # next corpse (a fresh connection never raises
                    # _StaleConnection).
                    self._drop_conns()
                    return self._request_once(
                        method, path, body, timeout, ctype, headers,
                        idempotent,
                    )
            except HostUnavailableError as e:
                last = e
                if attempt >= retries:
                    raise
            time.sleep(
                0.05 * (2 ** attempt) * (0.5 + self._rng.random())
            )
        raise last  # pragma: no cover — loop always raises or returns

    def _request_json(self, method, path, payload=None, *, timeout,
                      retries=0) -> dict:
        body = None if payload is None else json.dumps(payload).encode()
        data = self._request(method, path, body, timeout=timeout,
                             retries=retries)
        return json.loads(data.decode()) if data else {}

    def _healthz(self, retries: int | None = None) -> dict:
        facts = self._request_json(
            "GET", "/healthz", timeout=self.connect_timeout_s,
            retries=self.probe_retries if retries is None else retries,
        )
        with self._facts_lock:
            self._facts_cache = facts
            self._facts_t = time.monotonic()
            gen = facts.get("facts_generation")
            if gen is not None:
                self._facts_gen = int(gen)
        return facts

    def _note_generation(self, gen) -> None:
        """A sighting of the host's facts generation from ANY payload
        (the /metricsz probe, mainly): a change means the resident model
        set moved — the cached facts are stale NOW, TTL notwithstanding."""
        if gen is None:
            return
        with self._facts_lock:
            if self._facts_gen is not None and int(gen) != self._facts_gen:
                self._facts_t = -1.0
            self._facts_gen = int(gen)

    def _facts(self) -> dict:
        """The last /healthz payload, refreshed when stale — the cheap
        read behind the property surface (a controller tick reads several
        properties; one probe serves them all)."""
        with self._facts_lock:
            fresh = (
                self._facts_cache is not None
                and time.monotonic() - self._facts_t <= self._facts_ttl_s
            )
            if fresh:
                return self._facts_cache
        return self._healthz()

    # ------------------------------------------------------------- requests

    def submit(self, image, trace=None, model=None) -> Future:
        """POST the request bytes; the future resolves from the result
        long-poll. NO wire retries: a submit is not idempotent, and a
        failed submit is exactly the signal the router's drain streak
        and re-dispatch machinery exist to consume.

        ``model`` (ISSUE 14) names the tenant on a multi-model host —
        it rides the wire as the ``?model=`` query of ``POST /submit``.

        ``trace`` (optional ``obs.TraceContext``) rides the wire as a
        W3C-style ``Traceparent`` header — the serving process parents
        its queue/preprocess/device spans under it — and the wire halves
        (this POST, the result long-poll) land as spans in the router
        process's ring (ISSUE 13)."""
        if self._closed:
            raise ServerClosedError(f"remote host {self.name} is closed")
        buf = io.BytesIO()
        np.save(buf, np.asarray(image), allow_pickle=False)
        headers = None
        t_wire = 0.0
        if trace is not None:
            from mpi_pytorch_tpu.obs.context import format_traceparent

            headers = {"Traceparent": format_traceparent(trace)}
            t_wire = time.time()
        path = "/submit"
        if model is not None:
            import urllib.parse

            path += "?model=" + urllib.parse.quote(str(model))
        resp = json.loads(self._request(
            "POST", path, buf.getvalue(),
            timeout=self.connect_timeout_s, retries=0,
            ctype="application/octet-stream", headers=headers,
            idempotent=False,
        ).decode())
        rid = resp["req_id"]
        if trace is not None and self._spans is not None:
            self._spans.add(
                name="wire/submit", trace=trace.trace_id,
                parent=trace.span_id, t0=t_wire, t1=time.time(),
                host="router", attrs={"host": self.name, "req_id": rid},
            )
        fut: Future = Future()
        try:
            self._pool.submit(self._poll_result, rid, fut, headers, trace)
        except RuntimeError as e:  # pool shut down under us (kill/close)
            raise HostUnavailableError(
                f"remote host {self.name} poller is shut down: {e}"
            ) from None
        return fut

    def _poll_result(self, rid: int, fut: Future, headers=None,
                     trace=None) -> None:
        deadline = time.monotonic() + self.result_timeout_s
        transport_strikes = 0
        t_wire = time.time() if trace is not None else 0.0
        while True:
            try:
                data = self._request(
                    "GET", f"/result/{rid}?timeout_s={self.poll_slice_s}",
                    timeout=self.poll_slice_s + self.read_timeout_s,
                    retries=0, headers=headers,
                )
                if trace is not None and self._spans is not None:
                    # The delivery half of the wire phase: first poll →
                    # result bytes in hand.
                    self._spans.add(
                        name="wire/result", trace=trace.trace_id,
                        parent=trace.span_id, t0=t_wire, t1=time.time(),
                        host="router",
                        attrs={"host": self.name, "req_id": rid},
                    )
                fut.set_result(np.load(io.BytesIO(data), allow_pickle=False))
                return
            except _PendingResult:
                transport_strikes = 0
                if time.monotonic() > deadline:
                    fut.set_exception(HostUnavailableError(
                        f"{self.name}: no result for req {rid} within "
                        f"{self.result_timeout_s}s"
                    ))
                    return
            except HostUnavailableError as e:
                # The poll is idempotent → bounded retries before the
                # host-shaped verdict reaches the router.
                transport_strikes += 1
                if (
                    transport_strikes > self.probe_retries
                    or time.monotonic() > deadline
                    or self._closed
                ):
                    fut.set_exception(e)
                    return
                time.sleep(0.05 * (2 ** transport_strikes)
                           * (0.5 + self._rng.random()))
            except Exception as e:  # noqa: BLE001 — typed request faults et al
                fut.set_exception(e)
                return

    def predict_batch(self, images, timeout: float | None = None):
        futs = [self.submit(im) for im in images]
        return np.stack([f.result(timeout=timeout) for f in futs])

    # ----------------------------------------------------- telemetry / control

    def snapshot(self) -> dict:
        snap = self._request_json(
            "GET", "/metricsz", timeout=self.connect_timeout_s,
            retries=self.probe_retries,
        )
        self._note_generation(snap.get("facts_generation"))
        return snap

    def alive(self) -> bool:
        try:
            return self._healthz().get("status") == "ok"
        except ServeError:
            return False

    def qsize(self) -> int:
        try:
            return int(self._facts().get("queue_depth") or 0)
        except ServeError:
            return 0

    def stats(self) -> dict:
        return self._request_json(
            "GET", "/statsz", timeout=self.connect_timeout_s,
            retries=self.probe_retries,
        )

    def traces(self, since: int = 0) -> dict:
        """Drain the host's span-export ring from ``since`` — the
        collector's /tracez scrape (idempotent read → probe retries)."""
        return self._request_json(
            "GET", f"/tracez?since={int(since)}",
            timeout=self.connect_timeout_s, retries=self.probe_retries,
        )

    def clock_probe(self) -> tuple:
        """(rtt_s, offset_s): the host's wall-clock offset estimated from
        the probe's RTT midpoint — a fresh ``/healthz`` read (never the
        facts cache: a cached ``time`` would book the cache age as clock
        skew). Offset error is bounded by rtt/2, which is why the
        collector keeps the tightest recent probe."""
        t0 = time.time()
        facts = self._request_json(
            "GET", "/healthz", timeout=self.connect_timeout_s, retries=0,
        )
        t1 = time.time()
        host_time = facts.get("time")
        if host_time is None:
            return (t1 - t0, 0.0)
        return (t1 - t0, float(host_time) - (t0 + t1) / 2.0)

    def compiles_after_warmup(self) -> int:
        return int(self._facts().get("compiles_after_warmup") or 0)

    @property
    def active_buckets(self) -> tuple:
        return tuple(self._facts().get("active_buckets") or self.buckets)

    @property
    def max_wait_ms(self) -> float:
        return float(self._facts().get("max_wait_ms") or 0.0)

    @property
    def precision(self) -> str:
        return self._facts().get("precision") or "bf16"

    @property
    def precisions(self) -> tuple:
        return tuple(self._facts().get("precisions") or (self.precision,))

    @property
    def parity_top1(self):
        return self._facts().get("parity_top1")

    # -- multi-model tenancy (ISSUE 14) --------------------------------
    def models(self):
        """The host's RESIDENT tenant set from its /healthz facts — the
        router's dispatch filter. None = an untenanted (single-model)
        host: the key is simply absent from its facts. The facts cache
        serves this read; the generation counter keeps it coherent
        through swap-ins/evictions."""
        try:
            models = self._facts().get("models")
        except ServeError:
            return ()
        return None if models is None else tuple(models)

    @property
    def facts_generation(self):
        return self._facts().get("facts_generation")

    def ensure_model(self, model: str) -> None:
        """The router's cold-load spill, over the wire. NOT idempotent-
        retried (a retry would queue a second build behind the first),
        and on the READ timeout: the control call holds the wire for
        the whole load + warm-probe."""
        self._control(
            "ensure_model", str(model), retries=0,
            timeout=max(self.read_timeout_s, self.result_timeout_s),
        )

    def evict_model(self, model: str) -> None:
        self._control("evict_model", str(model), retries=0)

    def _control(self, op: str, value=None, retries: int | None = None,
                 timeout: float | None = None) -> None:
        payload = {"op": op}
        if value is not None:
            payload["value"] = value
        # Control sets are idempotent → the probe retry budget applies
        # (callers override for the non-idempotent zoo swap-in, which
        # also holds the wire for the whole build — read timeout).
        self._request_json(
            "POST", "/control", payload,
            timeout=self.connect_timeout_s if timeout is None else timeout,
            retries=self.probe_retries if retries is None else retries,
        )
        with self._facts_lock:
            # A knob just moved: the next property read must not serve
            # the pre-retune healthz from the facts cache.
            self._facts_t = -1.0

    def set_max_wait_ms(self, v: float) -> None:
        self._control("set_max_wait_ms", float(v))

    def set_active_buckets(self, buckets) -> None:
        self._control("set_active_buckets", [int(b) for b in buckets])

    def set_precision(self, precision: str) -> None:
        self._control("set_precision", str(precision))

    # ------------------------------------------------------------ lifecycle

    def kill(self) -> None:
        """The hard-death path, generalized to a real process: SIGKILL the
        serving subprocess (the ``MPT_FAULT_SERVE_KILL_HOST`` gate's
        strike lands here). Falls back to a no-drain wire shutdown when
        the pid is unknown (a true remote machine)."""
        self._closed = True
        try:
            if self.pid:
                os.kill(int(self.pid), signal.SIGKILL)
            else:
                self._request_json(
                    "POST", "/control", {"op": "shutdown", "drain": False},
                    timeout=self.connect_timeout_s, retries=0,
                )
        except (OSError, ServeError):
            pass  # already dead — which is the goal
        self._pool.shutdown(wait=False, cancel_futures=True)
        self._drop_conns()

    def close(self, drain: bool = True) -> None:
        if self._closed:
            return
        self._closed = True
        try:
            self._request_json(
                "POST", "/control", {"op": "shutdown", "drain": bool(drain)},
                timeout=self.connect_timeout_s, retries=0,
            )
        except ServeError as e:
            self._logger.warning(
                "remote host %s shutdown call failed: %s", self.name, e
            )
        # Give in-flight result polls a moment to deliver the drain's
        # resolutions, then cut the poller pool.
        self._pool.shutdown(wait=drain, cancel_futures=not drain)
        self._drop_conns()


# ---------------------------------------------------------------------------
# Supervisor: restart dead serving processes with backoff, re-admit warm.
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class _Supervised:
    index: int
    proc: object  # subprocess.Popen-shaped (poll/terminate/kill) or None
    host: RemoteHost
    spare: bool = False  # re-admission preserves the host's role
    restarts: int = 0
    state: str = "live"  # live | dead | restarting
    next_restart_t: float = 0.0
    last_start_t: float = 0.0


class HostSupervisor:
    """Watch serving subprocesses; restart with exponential backoff and
    re-admit after warm-probe success (drain → restart → warm → re-admit).

    The router handles the SERVING side of a death on its own (probe/
    dispatch failures → drain → re-dispatch → spare promotion); this loop
    owns the PROCESS side: bring the corpse back, verify it is warm
    (``/healthz`` ok + zero steady-state compiles — the persistent
    compilation cache is what makes that fast), then hand it back to the
    router as a fresh active host. Every re-admission writes a
    ``kind="fleet"`` ``event="restart"`` record (schema v8).
    """

    def __init__(
        self,
        spawn_fn,
        *,
        router,
        metrics=None,
        backoff_base_s: float = 0.5,
        backoff_max_s: float = 30.0,
        reset_after_s: float = 60.0,
        interval_s: float = 0.5,
        logger=None,
        clock=time.monotonic,
    ):
        from mpi_pytorch_tpu.utils.logging import run_logger

        self._spawn_fn = spawn_fn  # (index) -> (proc, RemoteHost), warm
        self._router = router
        self._metrics = metrics
        self._backoff_base_s = float(backoff_base_s)
        self._backoff_max_s = float(backoff_max_s)
        self._reset_after_s = float(reset_after_s)
        self._interval_s = float(interval_s)
        self._logger = logger or run_logger()
        self._clock = clock
        self._entries: dict[int, _Supervised] = {}
        self._lock = threading.Lock()
        self.restarts_total = 0
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None

    def manage(self, index: int, proc, host: RemoteHost,
               spare: bool = False) -> None:
        with self._lock:
            self._entries[index] = _Supervised(
                index=index, proc=proc, host=host, spare=spare,
                last_start_t=self._clock(),
            )

    def unmanage(self, index: int):
        with self._lock:
            return self._entries.pop(index, None)

    def entry(self, index: int) -> _Supervised | None:
        with self._lock:
            return self._entries.get(index)

    def procs(self) -> list:
        with self._lock:
            return [e.proc for e in self._entries.values()
                    if e.proc is not None]

    def _backoff(self, restarts: int) -> float:
        return min(
            self._backoff_base_s * (2 ** restarts), self._backoff_max_s
        )

    def tick(self) -> int:
        """One supervision pass; returns how many hosts were re-admitted.
        Drivable directly (tests, fake clocks) or via start()/stop().
        State transitions happen under the supervisor lock, so a
        concurrent ``restart_host`` (the rolling-restart path) and the
        background loop can never both restart one entry."""
        readmitted = 0
        with self._lock:
            entries = list(self._entries.values())
        now = self._clock()
        for e in entries:
            claimed = False
            with self._lock:
                if e.state == "live":
                    if e.proc is not None and e.proc.poll() is not None:
                        backoff = self._backoff(e.restarts)
                        e.state = "dead"
                        e.next_restart_t = now + backoff
                        self._logger.warning(
                            "supervisor: host %s process died (rc=%s) — "
                            "restart #%d in %.2fs",
                            e.host.name, e.proc.poll(), e.restarts + 1,
                            backoff,
                        )
                    elif (
                        e.restarts
                        and now - e.last_start_t > self._reset_after_s
                    ):
                        e.restarts = 0  # stable long enough: forgive history
                elif e.state == "dead" and now >= e.next_restart_t:
                    e.state = "restarting"  # claim, then work off-lock
                    claimed = True
            if claimed:
                readmitted += self._restart(e)
        return readmitted

    def _restart(self, e: _Supervised, detail: str | None = None) -> int:
        """Spawn + warm-probe + re-admit one CLAIMED entry (``e.state``
        must already be "restarting" — tick()/restart_host own the
        claim)."""
        e.restarts += 1
        proc = host = None
        try:
            proc, host = self._spawn_fn(e.index)
            # Warm probe: the handshake already implies warmup ran; what
            # re-admission additionally demands is ZERO steady-state
            # compiles (the persistent-cache warm start made the warmup
            # cheap; a host that would compile under traffic must not
            # rejoin rotation).
            facts = host._healthz()
            if facts.get("status") != "ok":
                raise HostUnavailableError(
                    f"restarted host {host.name} unhealthy: {facts}"
                )
            compiles = int(facts.get("compiles_after_warmup") or 0)
            if compiles != 0:
                raise HostUnavailableError(
                    f"restarted host {host.name} shows {compiles} "
                    "steady-state compile(s) at warm probe"
                )
        except Exception as err:  # noqa: BLE001 — schedule the next attempt
            # A spawned-but-unfit process must not outlive the failed
            # attempt: it is healthy enough to hold devices/memory, and
            # nothing else tracks it.
            if host is not None:
                try:
                    host.kill()
                except Exception:  # noqa: BLE001 — it is being discarded
                    pass
            if proc is not None:
                _terminate(proc)
            backoff = self._backoff(e.restarts)
            with self._lock:
                e.state = "dead"
                e.next_restart_t = self._clock() + backoff
            self._logger.warning(
                "supervisor: restart of host index %d failed (%s) — "
                "next attempt in %.2fs", e.index, err, backoff,
            )
            return 0
        with self._lock:
            e.proc, e.host = proc, host
            e.last_start_t = self._clock()
            e.state = "live"
        self.restarts_total += 1
        self._router.add_host(host, spare=e.spare)
        self._logger.info(
            "supervisor: host %s restarted (attempt %d) and re-admitted "
            "after warm probe", host.name, e.restarts,
        )
        if self._metrics is not None:
            self._metrics.write({
                "kind": "fleet", "event": "restart", "host": host.name,
                "detail": detail or f"supervisor restart #{e.restarts}",
                "restarts": e.restarts, "compiles_after_warmup": 0,
                "transport": host.transport,
            })
        return 1

    def restart_host(self, index: int, *, reason: str = "rolling",
                     drain_wait_s: float = 30.0) -> None:
        """Rolling-restart one LIVE host: drain → terminate → spawn →
        warm → re-admit (the autoscaler's rolling-restart unit). The
        entry is claimed ("restarting") BEFORE the old process is
        touched, so the background loop cannot race a second restart of
        the same index while the drain/terminate window is open."""
        with self._lock:
            e = self._entries.get(index)
            if e is None:
                raise KeyError(f"no supervised host with index {index}")
            if e.state != "live":
                raise HostUnavailableError(
                    f"host index {index} is {e.state}; a rolling restart "
                    "needs a live host (the supervisor already owns its "
                    "recovery)"
                )
            e.state = "restarting"
        old = e.host
        self._router.retire_host(old.name, wait_s=drain_wait_s)
        if e.proc is not None:
            _terminate(e.proc)
        if not self._restart(e, detail=f"rolling restart ({reason})"):
            raise HostUnavailableError(
                f"rolling restart of host index {index} failed ({reason})"
            )

    # ---------------------------------------------------------- background

    def start(self) -> None:
        if self._thread is not None:
            return
        self._thread = threading.Thread(
            target=self._run, name="fleet-supervisor", daemon=True
        )
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=10)
            self._thread = None

    def _run(self) -> None:
        while not self._stop.wait(self._interval_s):
            try:
                self.tick()
            except Exception as e:  # noqa: BLE001 — supervision must not die
                self._logger.warning("supervisor tick failed: %s", e)


def _terminate(proc, grace_s: float = 10.0) -> None:
    """TERM, wait, KILL — the polite process reap."""
    if proc.poll() is not None:
        return
    try:
        proc.terminate()
        proc.wait(timeout=grace_s)
    except Exception:  # noqa: BLE001 — escalate
        try:
            proc.kill()
            proc.wait(timeout=grace_s)
        except Exception:  # noqa: BLE001 — nothing left to do
            pass


# ---------------------------------------------------------------------------
# RemoteFleet: N serving PROCESSES behind the unchanged router.
# ---------------------------------------------------------------------------

# Config fields that must NOT flow to a serving-host child: fleet-side
# knobs (the child is one host, not a fleet — they would fail its
# validation), per-process outputs the fleet assigns itself, and the
# wire/port identity the spawner owns.
_CHILD_EXCLUDE = frozenset({
    "serve_fleet_hosts", "serve_fleet_spare", "serve_admission_tokens",
    "serve_target_p99_ms", "serve_retune_interval_s",
    "serve_probe_interval_ms", "serve_fail_probes",
    "serve_autoscale", "serve_fleet_min_hosts", "serve_fleet_max_hosts",
    "serve_scale_cooldown_s", "serve_scale_reject_rate",
    "metrics_file", "log_file", "eval_log_file", "trace_file",
    "serve_port", "serve_port_file", "serve_host_index",
    "serve_metrics_port", "flight_dir",
    # Tracing/collector knobs are fleet-front-door-only (ISSUE 13): a
    # serving child follows incoming Traceparent headers and exports its
    # span ring over /tracez — it mints nothing and collects nothing.
    "trace_sample_rate", "trace_slow_ms", "serve_collect_interval_s",
    "fleet_trace_file",
    # Hedging is a ROUTER decision (ISSUE 16): the child host only ever
    # sees the duplicate submit + the CANCEL frame; the knobs would fail
    # its single-host validation. serve_transport DOES flow — it is what
    # makes the child mount its framed listener.
    "serve_hedge", "serve_hedge_factor", "serve_hedge_floor_ms",
})


def child_host_args(cfg, index: int, port_file: str,
                    metrics_file: str) -> list[str]:
    """CLI argv for one ``python -m mpi_pytorch_tpu.serve.host`` child:
    the cfg's diff against defaults (so children and fleet agree on the
    model/bucket/precision world) plus the per-process identity."""
    from mpi_pytorch_tpu.config import Config

    default = Config()
    args: list[str] = []

    def _emit(flag_name: str, value, ftype) -> None:
        flag = f"--{flag_name.replace('_', '-')}"
        if ftype in (bool, "bool"):
            args.extend([flag, "true" if value else "false"])
        else:
            args.extend([flag, str(value)])

    for f in dataclasses.fields(Config):
        if f.name in _CHILD_EXCLUDE:
            continue
        value = getattr(cfg, f.name)
        if dataclasses.is_dataclass(value):
            sub_default = getattr(default, f.name)
            for sf in dataclasses.fields(value):
                sv = getattr(value, sf.name)
                if sv != getattr(sub_default, sf.name):
                    _emit(f"{f.name}.{sf.name}", sv, sf.type)
            continue
        if f.type not in (bool, "bool", int, "int", float, "float",
                          str, "str"):
            continue  # non-CLI fields (tuples) — parse_config skips them too
        if value != getattr(default, f.name):
            _emit(f.name, value, f.type)
    args.extend([
        "--serve-host-index", str(index),
        "--serve-port", "0",
        "--serve-port-file", port_file,
        "--metrics-file", metrics_file,
        "--log-file", "",
        "--eval-log-file", "",
    ])
    return args


class NoFreeChipError(ServeError):
    """A serving-host process was asked for on a TPU host whose chips are
    all taken by its siblings. A chip belongs to one process at a time; a
    child started without one of its own would fail or hang at backend
    init."""


class _ChipSlots:
    """One TPU chip per serving-host child, handed out through the child's
    environment (libtpu's ``TPU_VISIBLE_CHIPS`` and process-bounds
    variables — shown on the four-chip v5e host in PR 21: four concurrent
    children each came up on their own single device). Without this every
    child inherits the parent's environment, asks for every chip, and the
    first wins.

    A child keeps its slot across supervisor restarts (same index, same
    chip); ``release`` returns it when the autoscaler retires the host.
    On a machine with no TPU, or with the children pinned to the CPU
    (``JAX_PLATFORMS=cpu``), there is nothing to hand out and ``env`` is
    empty."""

    def __init__(self, child_env: dict):
        from mpi_pytorch_tpu.utils.hardware import local_tpu_chips

        on_cpu = (
            child_env.get("JAX_PLATFORMS", "").split(",")[0].strip().lower()
            == "cpu"
        )
        self.chips = 0 if on_cpu else local_tpu_chips()
        self._slot_of: dict[int, int] = {}
        self._lock = threading.Lock()  # _spawn runs on a thread pool

    def env(self, index: int) -> dict:
        if not self.chips:
            return {}
        with self._lock:
            if index not in self._slot_of:
                free = sorted(
                    set(range(self.chips)) - set(self._slot_of.values())
                )
                if not free:
                    raise NoFreeChipError(
                        f"remote fleet: no free TPU chip for host {index} — "
                        f"this machine's {self.chips} chip(s) each belong "
                        f"to one of hosts {sorted(self._slot_of)}"
                    )
                self._slot_of[index] = free[0]
            slot = self._slot_of[index]
        port = 8476 + slot  # libtpu's default controller port, one per chip
        return {
            "TPU_VISIBLE_CHIPS": str(slot),
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": "1,1,1",
            "TPU_MESH_CONTROLLER_ADDRESS": f"localhost:{port}",
            "TPU_MESH_CONTROLLER_PORT": str(port),
        }

    def release(self, index: int) -> None:
        with self._lock:
            self._slot_of.pop(index, None)


class RemoteFleet:
    """N ``serve.host`` subprocesses (+ optional warm spare) behind the
    transport-agnostic ``FleetRouter`` — one handle, same surface as the
    in-process ``FleetServer``, but every host is a real process whose
    death the supervisor survives."""

    def __init__(
        self,
        cfg,
        *,
        n_hosts: int | None = None,
        spare: bool | None = None,
        workdir: str | None = None,
        env: dict | None = None,
        python: str = sys.executable,
        spawn_timeout_s: float = 300.0,
        logger=None,
    ):
        import tempfile

        from mpi_pytorch_tpu.serve.fleet.autoscaler import FleetAutoscaler
        from mpi_pytorch_tpu.serve.fleet.controller import FleetController
        from mpi_pytorch_tpu.serve.fleet.router import FleetRouter
        from mpi_pytorch_tpu.utils.logging import MetricsWriter, run_logger

        n = int(n_hosts if n_hosts is not None else cfg.serve_fleet_hosts)
        if n < 1:
            raise ServeError(
                f"a remote fleet needs at least one host, got n_hosts={n}"
            )
        self.cfg = cfg
        self._logger = logger or run_logger()
        self._python = python
        self._spawn_timeout_s = float(spawn_timeout_s)
        self.workdir = workdir or tempfile.mkdtemp(prefix="mpt_remote_fleet_")
        os.makedirs(self.workdir, exist_ok=True)
        self._env = dict(os.environ)
        if env:
            self._env.update(env)
        self._repo = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))
        )))
        self._raw_metrics = MetricsWriter(cfg.metrics_file)
        # Fleet-wide tracing + collector (ISSUE 13): the router process
        # owns the front-door span ring (router spans + the RemoteHosts'
        # wire spans); the collector scrapes it alongside every child's
        # /metricsz + /tracez, and fleet/fault records passing through
        # the tapped stream pin their in-flight traces. (flight_dir is
        # child-excluded — children keep their own recorders.)
        from mpi_pytorch_tpu.obs.collector import wire_fleet_obs

        (self.spans, self.collector, self._fleet_flight,
         self._metrics) = wire_fleet_obs(
            cfg, self._raw_metrics,
            lambda: self.router.active_hosts(), logger=self._logger,
        )
        self._next_index = 0
        self._closed = False

        want_spare = bool(cfg.serve_fleet_spare if spare is None else spare)
        total = n + (1 if want_spare else 0)
        indices = list(range(total))
        self._next_index = total
        self._chip_slots = _ChipSlots(self._env)
        spawned: dict[int, tuple] = {}
        try:
            # Warm-start ordering: the FIRST host pays the cold compiles
            # and populates the persistent compilation cache the children
            # share (config.enable_compilation_cache); every later spawn
            # (including failover restarts and scale-ups) warms from it in
            # parallel.
            spawned[indices[0]] = self._spawn(indices[0])
            rest = indices[1:]
            if rest:
                with ThreadPoolExecutor(max_workers=len(rest)) as pool:
                    futs = {i: pool.submit(self._spawn, i) for i in rest}
                    for i, fut in futs.items():
                        spawned[i] = fut.result()
        except BaseException:
            for proc, host in spawned.values():
                try:
                    host.kill()
                except Exception:  # noqa: BLE001
                    pass
                _terminate(proc)
            self._raw_metrics.close()
            raise

        hosts = [spawned[i][1] for i in indices[:n]]
        spare_host = spawned[indices[n]][1] if want_spare else None
        # Per-tenant front-door budgets (ISSUE 14): the zoo children
        # advertise their tenants over /healthz; the router enforces the
        # same isolation as the in-process fleet.
        tenant_budgets = None
        if cfg.serve_models:
            from mpi_pytorch_tpu.serve.zoo import ModelRegistry

            fleet_budget = cfg.serve_admission_tokens or sum(
                h.queue_capacity for h in hosts
            )
            tenant_budgets = ModelRegistry.from_config(cfg).tenant_budgets(
                fleet_budget
            )
        warmup_payload = np.zeros((*cfg.image_size, 3), np.uint8)
        self.router = FleetRouter(
            hosts, spare_host,
            metrics=self._metrics,
            admission_tokens=cfg.serve_admission_tokens,
            probe_interval_s=cfg.serve_probe_interval_ms / 1e3,
            fail_probes=cfg.serve_fail_probes,
            warmup_payload=warmup_payload,
            logger=self._logger,
            trace_sample_rate=cfg.trace_sample_rate,
            spans=self.spans,
            tenant_budgets=tenant_budgets,
            hedge=cfg.serve_hedge,
            hedge_factor=cfg.serve_hedge_factor,
            hedge_floor_ms=cfg.serve_hedge_floor_ms,
        )
        if self.collector is not None:
            self.collector.start()
        self.supervisor = HostSupervisor(
            self._spawn, router=self.router, metrics=self._metrics,
            logger=self._logger,
        )
        for i in indices:
            self.supervisor.manage(
                i, *spawned[i], spare=(want_spare and i == indices[n]),
            )
        self.supervisor.start()
        self.controller = None
        if cfg.serve_target_p99_ms > 0:
            self.controller = FleetController(
                self.router.active_hosts,
                target_p99_ms=cfg.serve_target_p99_ms,
                metrics=self._metrics,
                interval_s=cfg.serve_retune_interval_s,
                max_wait_ms_cap=max(
                    cfg.serve_max_wait_ms * 4.0, cfg.serve_max_wait_ms + 1.0
                ),
                logger=self._logger,
            )
            self.controller.start()
        self.autoscaler = None
        if cfg.serve_autoscale:
            self.autoscaler = FleetAutoscaler(
                self.router,
                spawn_fn=self._scale_spawn,
                retire_fn=self._scale_retire,
                target_p99_ms=cfg.serve_target_p99_ms,
                min_hosts=cfg.serve_fleet_min_hosts,
                max_hosts=cfg.serve_fleet_max_hosts,
                cooldown_s=cfg.serve_scale_cooldown_s,
                reject_rate_up=cfg.serve_scale_reject_rate,
                interval_s=cfg.serve_retune_interval_s,
                metrics=self._metrics,
                transport=cfg.serve_transport,
                logger=self._logger,
            )
            self.autoscaler.start()
        self._logger.info(
            "remote fleet: %d subprocess host(s)%s behind the router "
            "(budget %d, workdir %s)",
            n, " + warm spare" if want_spare else "", self.router.budget,
            self.workdir,
        )

    # -------------------------------------------------------------- spawning

    def _spawn(self, index: int):
        """One serving-host subprocess: spawn, wait for the readiness
        handshake (port file), return (proc, RemoteHost)."""
        from mpi_pytorch_tpu.serve.http import wait_port_file

        port_file = os.path.join(self.workdir, f"host{index}.port.json")
        try:
            os.remove(port_file)
        except FileNotFoundError:
            pass
        metrics_file = os.path.join(self.workdir, f"host{index}.jsonl")
        log_path = os.path.join(self.workdir, f"host{index}.log")
        argv = [self._python, "-m", "mpi_pytorch_tpu.serve.host"]
        argv += child_host_args(self.cfg, index, port_file, metrics_file)
        log_fh = open(log_path, "ab")
        try:
            proc = subprocess.Popen(
                argv, env={**self._env, **self._chip_slots.env(index)},
                cwd=self._repo, stdout=log_fh, stderr=subprocess.STDOUT,
            )
        finally:
            log_fh.close()
        try:
            ready = wait_port_file(port_file, self._spawn_timeout_s, proc)
            kwargs = dict(
                name=f"h{index}", index=index, pid=ready["pid"],
                connect_timeout_s=self.cfg.serve_connect_timeout_s,
                read_timeout_s=self.cfg.serve_read_timeout_s,
                probe_retries=self.cfg.serve_probe_retries,
                logger=self._logger,
                spans=self.spans,
            )
            if self.cfg.serve_transport == "framed":
                # The framed data plane (ISSUE 16): the child advertised
                # its wire port in the readiness payload; control/probes
                # stay on HTTP via the WireHost's RemoteHost half.
                from mpi_pytorch_tpu.serve.client import WireHost

                host = WireHost(
                    f"http://127.0.0.1:{ready['port']}",
                    wire_port=ready.get("wire_port"), **kwargs,
                )
            else:
                host = RemoteHost(
                    f"http://127.0.0.1:{ready['port']}", **kwargs,
                )
            host.chips = ready.get("chips")
        except BaseException:
            _terminate(proc)
            tail = ""
            try:
                with open(log_path, "rb") as f:
                    tail = f.read()[-2048:].decode(errors="replace")
            except OSError:
                pass
            self._logger.error(
                "remote fleet: host %d failed to come up; log tail:\n%s",
                index, tail,
            )
            raise
        return proc, host

    def _scale_spawn(self):
        index = self._next_index
        self._next_index += 1
        proc, host = self._spawn(index)
        self.supervisor.manage(index, proc, host)
        return host

    def _scale_retire(self, host) -> None:
        """Autoscaler detach hook — runs BEFORE the router's drain, so
        the supervisor stops watching the process before its deliberate
        exit could read as a death. The reap happens in the background
        (the child only exits once the drain's wire shutdown lands)."""
        entry = self.supervisor.unmanage(host.index)
        if entry is None or entry.proc is None:
            return

        def _reap() -> None:
            try:
                entry.proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                _terminate(entry.proc)
            # Only a dead process has let go of its chip.
            self._chip_slots.release(host.index)

        threading.Thread(
            target=_reap, name="fleet-scale-reap", daemon=True
        ).start()

    # -------------------------------------------------------------- requests

    def submit(self, image, model: str | None = None):
        return self.router.submit(image, model=model)

    def predict_batch(self, images, timeout: float | None = None,
                      model: str | None = None):
        return self.router.predict_batch(images, timeout=timeout, model=model)

    # ------------------------------------------------------------- inspection

    def hosts(self) -> list:
        return self.router.active_hosts()

    def host_snapshots(self) -> dict:
        return {h.name: h.snapshot() for h in self.router.active_hosts()}

    def set_max_wait_ms(self, max_wait_ms: float) -> None:
        for h in self.router.active_hosts():
            h.set_max_wait_ms(max_wait_ms)
        spare = self.router.spare_host()
        if spare is not None:
            spare.set_max_wait_ms(max_wait_ms)

    @property
    def precision(self) -> str:
        hosts = self.router.active_hosts()
        return hosts[0].precision if hosts else "bf16"

    @property
    def parity_top1(self):
        hosts = self.router.active_hosts()
        return hosts[0].parity_top1 if hosts else None

    def set_precision(self, precision: str) -> None:
        for h in self.router.active_hosts():
            h.set_precision(precision)
        spare = self.router.spare_host()
        if spare is not None:
            spare.set_precision(precision)

    def tenant_stats(self) -> dict:
        """model → fleet-wide per-tenant counters (the in-process
        FleetServer surface, over the wire /statsz 'models' sections;
        a host dying mid-inspection contributes nothing, not an error)."""
        from mpi_pytorch_tpu.serve.fleet.router import aggregate_tenant_stats

        host_stats = []
        for h in self.router.active_hosts():
            try:
                host_stats.append(h.stats())
            except ServeError:
                continue
        return aggregate_tenant_stats(
            host_stats, self.router.rejections_by_model
        )

    def stats(self) -> dict:
        hosts = {}
        for h in self.router.active_hosts():
            try:
                hosts[h.name] = h.stats()
            except ServeError:
                continue  # a host dying mid-inspection is not an error here
        return {
            "hosts": hosts,
            "router": self.router.stats(),
            "served": sum(s.get("served", 0) for s in hosts.values()),
            "rejected": sum(s.get("rejected", 0) for s in hosts.values()),
            "padded_rows": sum(
                s.get("padded_rows", 0) for s in hosts.values()
            ),
            "compiles_after_warmup": max(
                (s.get("compiles_after_warmup", 0) for s in hosts.values()),
                default=0,
            ),
        }

    # ------------------------------------------------------------- lifecycle

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        if self.autoscaler is not None:
            self.autoscaler.stop()
        if self.controller is not None:
            self.controller.stop()
        self.supervisor.stop()
        # Collector stops BEFORE the router closes the children: the
        # final scrape drains their /tracez rings over the wire, forces
        # every open trace through the tail decision, and flushes the
        # timelines.
        if self.collector is not None:
            self.collector.stop(final=True)
        if self._fleet_flight is not None:
            self._fleet_flight.close()
        # Router close drains every host handle (wire shutdown → children
        # exit); then reap whatever lingers.
        self.router.close()
        for proc in self.supervisor.procs():
            try:
                proc.wait(timeout=15)
            except subprocess.TimeoutExpired:
                _terminate(proc)
        self._raw_metrics.close()

    def __enter__(self) -> "RemoteFleet":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
