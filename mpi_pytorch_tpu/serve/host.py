"""One serving host as a PROCESS: ``python -m mpi_pytorch_tpu.serve.host``.

The remote half of the fleet transport (ISSUE 12 / ROADMAP item 2): one
``InferenceServer`` stood up behind an extended ``ObsHTTPServer``, so the
fleet router can drive it over the wire exactly like it drives a
``LocalHost`` in-process. PR 9's fleet fixed the routing topology but
not the blast radius — every "host" shared one process; this entrypoint
is what makes "kill a host" mean killing a process.

Wire protocol (all bodies bounded, all reads timed — ``serve/http.py``):

- ``POST /submit`` — one request image as ``.npy`` bytes (the
  self-describing numpy wire format: shape + dtype + raw pixels). Replies
  ``202 {"req_id": N}``; the id keys the result long-poll. Admission
  backpressure surfaces as **HTTP 429** with a ``retry_after_ms`` JSON
  body (and a ``Retry-After`` header) mapped from the server's typed
  ``QueueFullError`` — the hint crosses the wire intact. A closing server
  replies 503; a request-fault (bad shape, undecodable payload) replies
  400 and is NEVER retried by a sane client — it would fail anywhere.
- ``GET /result/<req_id>?timeout_s=S`` — long-poll for the prediction:
  200 with ``.npy`` top-k bytes when done, **408** when still pending
  after the slice (re-poll), 404 for an unknown id (a RESTARTED process
  does not know its predecessor's ids — the client classifies that as a
  host failure and the router re-dispatches). Delivery is idempotent: a
  delivered result stays fetchable until the reaper expires it, so a
  response lost on the wire costs a re-poll, not the answer.
- ``POST /control`` — ``{"op": "set_max_wait_ms"|"set_active_buckets"|
  "set_precision"|"shutdown", ...}``: the retune/lifecycle surface the
  fleet controller and supervisor drive (each op maps 1:1 onto the
  ``HostHandle`` method of the same name; invalid retunes are the same
  typed 400 the in-process call would raise).
- ``GET /statsz`` / ``/metricsz`` / ``/metrics`` / ``/healthz`` — the
  probe surface (``/healthz`` carries the static host facts: queue
  capacity, compiled buckets, precisions, pid — plus ``time``, the
  collector's clock-probe read). ``/metricsz`` snapshots carry a
  monotonic ``seq`` + process ``start_ts`` so a scraper can tell a
  counter reset (restart) from a negative delta (ISSUE 13).
- ``GET /tracez?since=N`` — the bounded span-export ring: finished
  host-side spans (queue/preprocess/device per traced request), exported
  incrementally by cursor to the fleet collector. A ``Traceparent``
  header on ``POST /submit`` / ``GET /result`` threads the front door's
  trace id through this host's spans (W3C-style; ``obs/context.py``).

Readiness: after warmup the process atomically writes ``--serve-port-file``
(JSON ``{"port", "pid", "host_index"}``) and prints a ``SERVE_HOST_READY``
line — the supervisor's spawn handshake. SIGTERM/SIGINT drain gracefully:
the batcher flushes queued requests, waiting long-polls deliver, then the
HTTP listener closes. Warm-start recipe: point ``--compilation-cache-dir``
at a shared directory and a (re)started host's warmup compiles become
cache hits — the startup cost of failover/scale-up is placement + warmup
execution, not XLA compilation (``compiles_after_warmup`` stays 0 either
way; the cache is what makes the WALL CLOCK of "spawn a host" cheap).
"""

from __future__ import annotations

import io
import itertools
import json
import math
import os
import signal
import threading
import time
from concurrent.futures import TimeoutError as FutureTimeoutError

import numpy as np

from mpi_pytorch_tpu.serve.batcher import (
    QueueFullError,
    ServeError,
    ServerClosedError,
)
from mpi_pytorch_tpu.serve.http import ObsHTTPServer


def _npy_bytes(arr: np.ndarray) -> bytes:
    buf = io.BytesIO()
    np.save(buf, arr, allow_pickle=False)
    return buf.getvalue()


def load_npy_bytes(body: bytes) -> np.ndarray:
    """The wire decode (shared with the client side): strict, no pickle."""
    return np.load(io.BytesIO(body), allow_pickle=False)


class _NullRegistry:
    """Registry stand-in for duck-typed servers without one (tests)."""

    def prometheus_text(self) -> str:
        return ""

    def snapshot(self) -> dict:
        return {"counters": {}, "gauges": {}, "histograms": {}}


class ServingHost:
    """HTTP front over one (duck-typed) ``InferenceServer``.

    Owns the wire surface only: request ids, the result table with its
    idempotent-delivery reaper, and the typed-error → status mapping.
    The server underneath is anything with ``submit(image) -> Future``
    (plus the stats/retune surface when mounted on the real thing) —
    which is what lets the transport tests drive the full wire path
    without a jax backend behind it.
    """

    def __init__(
        self,
        server,
        *,
        port: int = 0,
        read_timeout_s: float = 10.0,
        max_body_bytes: int = 64 << 20,
        poll_slice_s: float = 10.0,
        result_ttl_s: float = 60.0,
        result_hard_ttl_s: float = 600.0,
        wire: bool = False,
        wire_port: int = 0,
        logger=None,
    ):
        from mpi_pytorch_tpu.utils.logging import run_logger

        self.server = server
        self._logger = logger or run_logger()
        self._poll_slice_s = float(poll_slice_s)
        self._result_ttl_s = float(result_ttl_s)
        self._result_hard_ttl_s = float(result_hard_ttl_s)
        # req_id -> [future, t_created, t_delivered|None]; delivered
        # results stay until the reaper expires them (idempotent /result).
        self._results: dict[int, list] = {}
        self._results_lock = threading.Lock()
        self._ids = itertools.count()
        self._closed = False
        self.closed_event = threading.Event()
        # Framed data plane (ISSUE 16): a WireListener mounted NEXT TO the
        # HTTP surface — submit/result move to persistent binary-framed
        # connections, while probes/control/facts stay on HTTP (cold
        # paths; one wire protocol per temperature). The port rides
        # /healthz (and the readiness file) as ``wire_port``.
        self.wire = None
        if wire:
            from mpi_pytorch_tpu.serve.wire import WireListener

            host_index = getattr(server, "host_index", None)
            self.wire = WireListener(
                self._wire_submit,
                host_index=-1 if host_index is None else host_index,
                port=wire_port,
                logger=self._logger,
            )
        healthz_fn = getattr(server, "_healthz", None)
        if self.wire is not None and healthz_fn is not None:
            base_healthz, wire_listener = healthz_fn, self.wire

            def healthz_fn():
                return dict(base_healthz(), wire_port=wire_listener.port)

        registry = getattr(server, "_registry", None) or _NullRegistry()
        metricsz = getattr(server, "registry_snapshot", None)
        self.http = ObsHTTPServer(
            registry,
            healthz=healthz_fn,
            port=port,
            metricsz=metricsz,
            get_routes={"/result/": self._handle_result,
                        "/statsz": self._handle_statsz,
                        "/tracez": self._handle_tracez},
            post_routes={"/submit": self._handle_submit,
                         "/control": self._handle_control},
            read_timeout_s=read_timeout_s,
            max_body_bytes=max_body_bytes,
        )
        self.port = self.http.port
        self._reaper_stop = threading.Event()
        self._reaper = threading.Thread(
            target=self._reap_loop, name="serve-host-reaper", daemon=True
        )
        self._reaper.start()

    @property
    def wire_port(self) -> int | None:
        """The framed listener's port (None on an HTTP-only host)."""
        return self.wire.port if self.wire is not None else None

    def _wire_submit(self, image, model, traceparent):
        """The WireListener's coupling into the request path: same typed
        semantics as POST /submit, minus the HTTP wrapping — typed
        ServeErrors propagate (the listener maps them to ERROR frames
        with the taxonomy intact)."""
        from mpi_pytorch_tpu.obs.context import parse_traceparent

        kwargs = {}
        ctx = parse_traceparent(traceparent)
        if ctx is not None:
            kwargs["trace"] = ctx
        if model is not None:
            kwargs["model"] = model
        try:
            return self.server.submit(image, **kwargs)
        except TypeError:
            if model is None:
                raise
            raise ServeError(
                f"host is not multi-tenant (model={model!r})"
            ) from None

    # ------------------------------------------------------------- routes

    @staticmethod
    def _json(status: int, payload: dict, headers=None):
        return (status, "application/json",
                json.dumps(payload).encode(), headers or {})

    def _handle_submit(self, path, query, body):
        try:
            image = load_npy_bytes(body)
        except Exception as e:  # noqa: BLE001 — malformed wire payload
            return self._json(400, {
                "error": "bad_request", "taxonomy": "request",
                "detail": f"request body is not .npy bytes ({e})",
            })
        # Tenant routing over the wire (ISSUE 14): POST /submit?model=m
        # names the tenant on a multi-model (zoo) host. Naming one on an
        # untenanted host is a request fault (400), never host-shaped.
        model = None
        for part in query.split("&"):
            if part.startswith("model="):
                import urllib.parse

                model = urllib.parse.unquote(part[6:])
        # The trace thread crossing the wire (ISSUE 13): a traceparent
        # header minted at the fleet front door parents this host's
        # queue/preprocess/device spans; a malformed or absent header is
        # an untraced request, never an error.
        from mpi_pytorch_tpu.obs.context import parse_traceparent

        ctx = parse_traceparent(self.http.request_headers().get("Traceparent"))
        try:
            kwargs = {}
            if ctx is not None:
                kwargs["trace"] = ctx
            if model is not None:
                kwargs["model"] = model
            try:
                fut = self.server.submit(image, **kwargs)
            except TypeError:
                if model is None:
                    raise
                return self._json(400, {
                    "error": "serve_error", "taxonomy": "request",
                    "detail": f"host is not multi-tenant (model={model!r})",
                })
        except QueueFullError as e:
            hint = e.retry_after_ms
            headers = {}
            if hint is not None:
                headers["Retry-After"] = max(1, math.ceil(hint / 1e3))
            return self._json(429, {
                "error": "queue_full", "detail": str(e),
                "retry_after_ms": hint,
                # ISSUE 14: the rejection names its tenant so a client
                # (or the router) backs off the right budget.
                "model": getattr(e, "model", None),
            }, headers)
        except ServerClosedError as e:
            return self._json(503, {"error": "closed", "detail": str(e)})
        except ServeError as e:
            return self._json(400, {
                "error": "serve_error", "taxonomy": "request",
                "detail": str(e),
            })
        rid = next(self._ids)
        with self._results_lock:
            self._results[rid] = [fut, time.monotonic(), None]
        return self._json(202, {"req_id": rid})

    def _handle_result(self, path, query, body):
        try:
            rid = int(path.rsplit("/", 1)[1])
        except ValueError:
            return self._json(400, {"error": "bad_request",
                                    "taxonomy": "request",
                                    "detail": "non-integer req_id"})
        timeout = self._poll_slice_s
        for part in query.split("&"):
            if part.startswith("timeout_s="):
                try:
                    timeout = min(max(float(part[10:]), 0.0), 30.0)
                except ValueError:
                    pass
        with self._results_lock:
            entry = self._results.get(rid)
        if entry is None:
            return self._json(404, {"error": "unknown_req_id"})
        fut = entry[0]
        try:
            preds = fut.result(timeout=timeout)
        except FutureTimeoutError:
            return self._json(408, {"error": "pending"})
        except QueueFullError as e:
            # Cannot happen post-admission today; mapped for completeness.
            return self._json(429, {"error": "queue_full", "detail": str(e),
                                    "retry_after_ms": e.retry_after_ms})
        except ServerClosedError as e:
            return self._json(503, {"error": "closed", "detail": str(e)})
        except ServeError as e:
            # The REQUEST's own fault (preprocess crash on its payload,
            # bad shape): the client must propagate, never re-dispatch.
            return self._json(400, {"error": "serve_error",
                                    "taxonomy": "request",
                                    "detail": str(e)})
        except Exception as e:  # noqa: BLE001 — host-shaped failure
            return self._json(500, {"error": "internal", "taxonomy": "host",
                                    "detail": f"{type(e).__name__}: {e}"})
        with self._results_lock:
            if rid in self._results:
                self._results[rid][2] = time.monotonic()  # delivered
        return (200, "application/octet-stream",
                _npy_bytes(np.asarray(preds)), {})

    def _handle_tracez(self, path, query, body):
        """The bounded span-export ring (ISSUE 13): incremental by
        ``?since=<cursor>``; the payload's ``start_ts`` is the recorder
        generation, so a collector whose cursor outlived this process's
        predecessor knows to rewind."""
        since = 0
        for part in query.split("&"):
            if part.startswith("since="):
                try:
                    since = int(part[6:])
                except ValueError:
                    pass
        traces_fn = getattr(self.server, "traces", None)
        if traces_fn is None:
            return self._json(200, {"spans": [], "next_seq": 0,
                                    "dropped": 0, "start_ts": None})
        return self._json(200, traces_fn(since))

    def _handle_statsz(self, path, query, body):
        stats_fn = getattr(self.server, "stats", None)
        stats = stats_fn() if stats_fn else {}
        # by_bucket keys are ints — JSON objects stringify them; the
        # remote consumers read the flat counters, so stringified is fine.
        if "by_bucket" in stats:
            stats = dict(stats, by_bucket={
                str(k): v for k, v in stats["by_bucket"].items()
            })
        return self._json(200, stats)

    def _handle_control(self, path, query, body):
        try:
            req = json.loads(body.decode())
            op = req["op"]
        except (ValueError, KeyError, UnicodeDecodeError) as e:
            return self._json(400, {"error": "bad_request",
                                    "taxonomy": "request",
                                    "detail": f"malformed control body ({e})"})
        try:
            if op == "set_max_wait_ms":
                self.server.set_max_wait_ms(float(req["value"]))
            elif op == "set_active_buckets":
                self.server.set_active_buckets(
                    tuple(int(b) for b in req["value"])
                )
            elif op == "set_precision":
                self.server.set_precision(str(req["value"]))
            elif op in ("ensure_model", "evict_model"):
                # The zoo residency surface (ISSUE 14): the router's
                # cold-load spill and the operator's evict, over the wire.
                fn = getattr(self.server, op, None)
                if fn is None:
                    return self._json(400, {
                        "error": "serve_error", "taxonomy": "request",
                        "detail": f"host is not multi-tenant ({op})",
                    })
                fn(str(req["value"]))
            elif op == "shutdown":
                self.shutdown_async(drain=bool(req.get("drain", True)))
            else:
                return self._json(400, {"error": "unknown_op", "op": op})
        except ServeError as e:
            return self._json(400, {"error": "serve_error",
                                    "taxonomy": "request",
                                    "detail": str(e)})
        except (KeyError, TypeError, ValueError) as e:
            return self._json(400, {"error": "bad_request",
                                    "detail": f"{type(e).__name__}: {e}"})
        return self._json(200, {"ok": True, "op": op})

    # ---------------------------------------------------------- lifecycle

    def _reap_loop(self) -> None:
        while not self._reaper_stop.wait(5.0):
            now = time.monotonic()
            with self._results_lock:
                stale = [
                    rid for rid, (fut, t0, t_done) in self._results.items()
                    if (t_done is not None
                        and now - t_done > self._result_ttl_s)
                    or now - t0 > self._result_hard_ttl_s
                ]
                for rid in stale:
                    del self._results[rid]

    def shutdown_async(self, drain: bool = True) -> None:
        """The /control shutdown: run the (slow, thread-joining) close off
        the handler thread so the control reply goes out first."""
        threading.Thread(
            target=self.close, kwargs={"drain": drain},
            name="serve-host-shutdown", daemon=True,
        ).start()

    def close(self, drain: bool = True) -> None:
        with self._results_lock:
            if self._closed:
                return
            self._closed = True
        # Server first: a graceful drain resolves the outstanding futures
        # WHILE the HTTP surface is still up, so waiting long-polls
        # deliver their results instead of dying with the listener.
        try:
            self.server.close(drain=drain)
        except TypeError:  # duck-typed servers without the drain kwarg
            self.server.close()
        self._reaper_stop.set()
        self.http.close()
        # Wire listener LAST: a graceful drain resolves in-flight futures
        # above, and their done-callbacks must still find live
        # connections to write RESULT frames into.
        if self.wire is not None:
            self.wire.close()
        self.closed_event.set()


def main(argv=None) -> int:
    """Entrypoint: stand up one serving-host process and serve until a
    signal (or a /control shutdown) takes it down."""
    from mpi_pytorch_tpu.config import parse_config
    from mpi_pytorch_tpu.serve.server import InferenceServer
    from mpi_pytorch_tpu.utils.logging import run_logger

    cfg = parse_config(argv)
    logger = run_logger()
    host_index = cfg.serve_host_index if cfg.serve_host_index >= 0 else None
    if cfg.serve_models:
        # Multi-model tenancy (ISSUE 14): this process serves the whole
        # zoo spec — per-tenant pipelines behind the same wire surface
        # (requests carry ?model=, /healthz advertises the resident set).
        from mpi_pytorch_tpu.serve.zoo import ZooServer

        server = ZooServer(cfg, host_index=host_index)
    else:
        server = InferenceServer(cfg, host_index=host_index)
    host = ServingHost(
        server,
        port=max(0, cfg.serve_port),
        read_timeout_s=cfg.serve_read_timeout_s,
        wire=cfg.serve_transport == "framed",
        logger=logger,
    )
    import jax

    payload = {
        "port": host.port, "pid": os.getpid(),
        "host_index": -1 if host_index is None else host_index,
        # Where this host actually runs: the parent of a remote fleet
        # stays off the device and learns the chip count from here.
        "platform": jax.default_backend(),
        "chips": jax.local_device_count(),
    }
    if host.wire_port is not None:
        # ISSUE 16: the framed data-plane port, for WireHost's dial
        # (absent on http-transport hosts — old readers are unaffected).
        payload["wire_port"] = host.wire_port
    if cfg.serve_port_file:
        # Atomic: the supervisor polls for this file, and a torn read of
        # a half-written JSON must be impossible, not just unlikely.
        tmp = f"{cfg.serve_port_file}.tmp.{os.getpid()}"
        os.makedirs(os.path.dirname(cfg.serve_port_file) or ".", exist_ok=True)
        with open(tmp, "w") as f:
            json.dump(payload, f)
        os.replace(tmp, cfg.serve_port_file)
    print(
        f"SERVE_HOST_READY host=127.0.0.1 port={host.port} "
        f"pid={os.getpid()} index={payload['host_index']}",
        flush=True,
    )
    logger.info(
        "serve host %s: listening on 127.0.0.1:%d (pid %d)",
        server.name, host.port, os.getpid(),
    )

    def _graceful(signum, frame):
        logger.info("serve host: signal %d — draining", signum)
        host.shutdown_async(drain=True)

    signal.signal(signal.SIGTERM, _graceful)
    signal.signal(signal.SIGINT, _graceful)
    host.closed_event.wait()
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
