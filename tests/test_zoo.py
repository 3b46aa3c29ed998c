"""Tests for the multi-model tenancy subsystem (mpi_pytorch_tpu/serve/zoo/,
ISSUE 14).

The acceptance surface: packing-plan invariants (an over-budget spec is
rejected loudly), per-tenant front-door admission (a flooding tenant is
rejected while the others keep serving), model-aware routing with the
cold-load spill, the model-labelled controller retune with
``compiles == 0``, cold-swap warm-probe gating, LRU eviction under the
packing budget, single-tenant flush discipline, the ``RemoteHost`` facts
generation invalidation satellite, schema-v10 shapes, and the
model/load_shape-keyed regression gate.

Fast tests drive fakes (no jax compute); one module-scoped REAL 2-tenant
fleet on the 8-device CPU mesh pins the end-to-end behavior (the
``_dryrun_zoo`` CI leg's in-process twin).
"""

import json
import os
import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _images(n, size=32, seed=0):
    rng = np.random.default_rng(seed)
    return [
        rng.integers(0, 256, size=(size, size, 3)).astype(np.uint8)
        for _ in range(n)
    ]


# ------------------------------------------------------------- spec parsing


def test_parse_model_specs_syntax():
    from mpi_pytorch_tpu.serve.zoo import parse_model_specs

    specs = parse_model_specs(
        "hot=resnet18:admission=8,mobilenet_v2:cold,"
        "b=resnet18:precision=int8:buckets=1|8:ckpt=/ck"
    )
    by_name = {s.model: s for s in specs}
    assert set(by_name) == {"hot", "mobilenet_v2", "b"}
    assert by_name["hot"].arch == "resnet18"
    assert by_name["hot"].admission == 8
    assert by_name["mobilenet_v2"].cold
    assert by_name["b"].precision == "int8"
    assert by_name["b"].buckets == "1,8"
    assert by_name["b"].checkpoint_dir == "/ck"

    with pytest.raises(ValueError, match="duplicate"):
        parse_model_specs("resnet18,resnet18")
    with pytest.raises(ValueError, match="unsupported architecture"):
        parse_model_specs("not_a_model")
    with pytest.raises(ValueError, match="token model"):
        parse_model_specs("lfm2_moe")  # serving takes image requests only
    with pytest.raises(ValueError, match="unknown spec key"):
        parse_model_specs("resnet18:bogus=1")
    with pytest.raises(ValueError, match="precision"):
        parse_model_specs("resnet18:precision=fp64")
    with pytest.raises(ValueError, match="zero tenants"):
        parse_model_specs(" , ")


def test_config_validates_zoo_knobs():
    from mpi_pytorch_tpu.config import Config

    Config(serve_models="resnet18,mobilenet_v2").validate_config()
    with pytest.raises(ValueError, match="cold"):
        Config(serve_models="resnet18:cold").validate_config()
    with pytest.raises(ValueError, match="duplicate"):
        Config(serve_models="resnet18,resnet18").validate_config()
    with pytest.raises(ValueError, match="serve_pack_budget_mb"):
        Config(serve_pack_budget_mb=64.0).validate_config()
    with pytest.raises(ValueError):
        Config(serve_models="resnet18", serve_pack_budget_mb=-1).validate_config()


# ------------------------------------------------------------- packing plan


def _registry_with_estimates(cfg, estimates_mb):
    """A real ModelRegistry whose byte estimates are injected (no
    eval_shape) — the planner logic under test, not the model zoo."""
    from mpi_pytorch_tpu.serve.zoo import ModelRegistry

    reg = ModelRegistry.from_config(cfg)
    mb = 1024 * 1024
    reg._estimates = {
        m: {
            "params_bytes": int(v * mb),
            "per_bucket_bytes": {1: 0},
            "total_bytes": int(v * mb),
        }
        for m, v in estimates_mb.items()
    }
    return reg


def test_packing_plan_rejects_single_over_budget_spec_loudly():
    from mpi_pytorch_tpu.config import Config
    from mpi_pytorch_tpu.serve.zoo import PackingError

    cfg = Config(serve_models="a=resnet18,b=resnet18")
    reg = _registry_with_estimates(cfg, {"a": 100.0, "b": 10.0})
    with pytest.raises(PackingError) as ei:
        reg.plan_packing(["a", "b"], budget_bytes=50 * 1024 * 1024)
    # The loud rejection carries the plan's arithmetic.
    assert "alone exceeds" in str(ei.value)
    assert "100.0 MB" in str(ei.value)


def test_packing_plan_fits_explain_and_record():
    from mpi_pytorch_tpu.config import Config

    cfg = Config(serve_models="a=resnet18,b=resnet18")
    reg = _registry_with_estimates(cfg, {"a": 30.0, "b": 30.0})
    plan = reg.plan_packing(["a", "b"], budget_bytes=100 * 1024 * 1024)
    assert plan.fits and plan.total_bytes == 60 * 1024 * 1024
    assert "FITS" in plan.explain()
    rec = plan.to_record()
    assert rec["fits"] == 1 and rec["tenants"] == {"a": 30.0, "b": 30.0}
    # Two tenants that fit alone but not together: fits=False (the
    # eviction path's decision input), never a silent truncation.
    tight = reg.plan_packing(["a", "b"], budget_bytes=40 * 1024 * 1024)
    assert not tight.fits
    assert "OVER BUDGET" in tight.explain()
    # measured overrides the estimate where available
    measured = reg.plan_packing(
        ["a"], budget_bytes=None, measured={"a": 5 * 1024 * 1024}
    )
    assert measured.entries[0].total_bytes == 5 * 1024 * 1024
    assert measured.entries[0].measured


def test_tenant_budgets_explicit_and_equal_share():
    from mpi_pytorch_tpu.config import Config
    from mpi_pytorch_tpu.serve.zoo import ModelRegistry

    cfg = Config(serve_models="hot=resnet18:admission=8,b=resnet18")
    reg = ModelRegistry.from_config(cfg)
    budgets = reg.tenant_budgets(100)
    assert budgets == {"hot": 8, "b": 50}


# ------------------------------------------------- cold-swap warm-probe gate


class _FakeExe:
    """BucketExecutables-shaped fake: scriptable compile counter so the
    warm-probe gate is testable in milliseconds."""

    def __init__(self, state_bytes=4, probe_compiles=0):
        self._state = np.zeros(max(1, state_bytes // 4), np.float32)
        self.buckets = (1,)
        self._image_hw = (4, 4)
        self.image_dtype = np.dtype(np.uint8)
        self.warm = False
        self.precision = "bf16"
        self._probe_compiles = probe_compiles
        self._compiles = 0

    def warmup(self):
        self.warm = True

    def rebaseline(self):
        self._compiles = 0

    def place(self, images, labels):
        return (images, labels)

    def __call__(self, bucket, batch):
        # Simulate a steady-state compile on execution when scripted.
        self._compiles += self._probe_compiles
        return np.zeros((bucket, 1), np.int32)

    def compiles_since_warmup(self):
        return self._compiles


def test_cold_swap_warm_probe_gates_activation():
    from mpi_pytorch_tpu.config import Config
    from mpi_pytorch_tpu.serve.zoo import ZooExecutablePool
    from mpi_pytorch_tpu.serve.zoo.pool import ColdSwapError

    cfg = Config(serve_models="a=resnet18,b=resnet18")
    reg = _registry_with_estimates(cfg, {"a": 1.0, "b": 1.0})
    built = []

    def build_fn(tenant_cfg, mesh):
        bad = tenant_cfg.model_name == "resnet18" and len(built) == 1
        built.append(tenant_cfg.model_name)
        return {"bf16": _FakeExe(probe_compiles=1 if bad else 0)}

    pool = ZooExecutablePool(cfg, reg, mesh=object(), build_fn=build_fn)
    sets = pool.ensure("a")  # clean probe → activates
    assert pool.resident() == ("a",)
    assert sets["bf16"].warm
    # The second build compiles ON THE PROBE → the gate refuses to
    # activate it, and the pool stays without the tenant.
    with pytest.raises(ColdSwapError, match="warm probe"):
        pool.ensure("b")
    assert pool.resident() == ("a",)


def test_pool_refcounts_and_release():
    from mpi_pytorch_tpu.config import Config
    from mpi_pytorch_tpu.serve.zoo import ZooExecutablePool

    cfg = Config(serve_models="a=resnet18")
    reg = _registry_with_estimates(cfg, {"a": 1.0})
    pool = ZooExecutablePool(
        cfg, reg, mesh=object(),
        build_fn=lambda c, m: {"bf16": _FakeExe(state_bytes=2048)},
    )
    pool.ensure("a")
    pool.ensure("a")  # second host holds it too
    assert pool.measured_bytes() == {"a": 2048}
    pool.release("a")
    assert pool.resident() == ("a",)  # one ref left
    pool.release("a")
    assert pool.resident() == ()  # last ref dropped the sets
    # measured bytes stay cached for the next plan
    assert pool.measured_bytes() == {"a": 2048}


# --------------------------------------------- router: admission + routing


class _FakeZooHost:
    """Router-facing fake with the zoo surface: resident models,
    scriptable ensure_model, futures resolved by the test."""

    def __init__(self, name, models=(), queue_capacity=64):
        self.name = name
        self.index = int(name[1:])
        self._models = list(models)
        self.queue_capacity = queue_capacity
        self.submits = []  # (model, future)
        self.ensured = []

    def models(self):
        return tuple(self._models)

    def ensure_model(self, model):
        self.ensured.append(model)
        self._models.append(model)

    def submit(self, image, trace=None, model=None):
        fut = Future()
        self.submits.append((model, fut))
        return fut

    def snapshot(self):
        return {"counters": {}, "gauges": {}, "histograms": {}}

    def alive(self):
        return True

    def qsize(self):
        return 0

    def close(self, drain=True):
        pass

    def kill(self):
        pass


def _make_router(hosts, **kw):
    from mpi_pytorch_tpu.serve.fleet.router import FleetRouter

    kw.setdefault("probe_interval_s", 3600.0)  # no background probes
    return FleetRouter(hosts, **kw)


def test_per_tenant_admission_isolates_hot_tenant():
    from mpi_pytorch_tpu.serve import QueueFullError

    h0 = _FakeZooHost("h0", models=("a", "b"))
    router = _make_router([h0], tenant_budgets={"a": 2, "b": 4})
    try:
        futs = [router.submit(0, model="a") for _ in range(2)]
        # Tenant a's budget is exhausted — rejected AT THE FRONT DOOR,
        # and the typed error names the tenant.
        with pytest.raises(QueueFullError) as ei:
            router.submit(0, model="a")
        assert ei.value.model == "a"
        assert "tenant 'a'" in str(ei.value)
        # Tenant b keeps admitting through a's flood.
        fb = router.submit(0, model="b")
        assert router.rejections_by_model == {"a": 1, "b": 0}
        # Completion returns the tenant token: a admits again.
        h0.submits[0][1].set_result(np.zeros(3, np.int32))
        futs[0].result(timeout=5)
        deadline = time.monotonic() + 2
        while time.monotonic() < deadline:
            try:
                futs.append(router.submit(0, model="a"))
                break
            except QueueFullError:
                time.sleep(0.01)
        else:
            pytest.fail("tenant token never returned")
        for _, fut in h0.submits:
            if not fut.done():
                fut.set_result(np.zeros(3, np.int32))
        fb.result(timeout=5)
        stats = router.stats()
        assert stats["tenant_budgets"] == {"a": 2, "b": 4}
    finally:
        router.close()


def test_router_prefers_resident_host_and_cold_loads_on_spill():
    h0 = _FakeZooHost("h0", models=("a",))
    h1 = _FakeZooHost("h1", models=("b",))
    router = _make_router([h0, h1])
    try:
        router.submit(0, model="a")
        router.submit(0, model="b")
        assert [m for m, _ in h0.submits] == ["a"]
        assert [m for m, _ in h1.submits] == ["b"]
        # Tenant c is resident nowhere: the router cold-loads it on one
        # host (ensure_model) before the hand-over.
        router.submit(0, model="c")
        ensured = h0.ensured + h1.ensured
        assert ensured == ["c"]
        loaded = h0 if h0.ensured else h1
        assert loaded.submits[-1][0] == "c"
        for h in (h0, h1):
            for _, fut in h.submits:
                fut.set_result(np.zeros(3, np.int32))
    finally:
        router.close()


def test_router_routes_by_per_tenant_queue_depth():
    """Per-(host, model) scoring: a host whose TENANT queue is deep
    loses the tenant's traffic even when its host-level score ties."""
    h0 = _FakeZooHost("h0", models=("a",))
    h1 = _FakeZooHost("h1", models=("a",))
    router = _make_router([h0, h1])
    try:
        # Feed fresh snapshots by hand: equal host scores, but h0's
        # tenant-a queue is deep.
        snap_busy = {
            "counters": {}, "gauges": {"serve/queue_depth": 0},
            "histograms": {},
            "models": {"a": {"gauges": {"serve/queue_depth": 10}}},
        }
        snap_idle = {
            "counters": {}, "gauges": {"serve/queue_depth": 0},
            "histograms": {},
            "models": {"a": {"gauges": {"serve/queue_depth": 0}}},
        }
        router._score_from_snapshot(h0, snap_busy)
        router._score_from_snapshot(h1, snap_idle)
        router.submit(0, model="a")
        assert len(h1.submits) == 1 and not h0.submits
        h1.submits[0][1].set_result(np.zeros(3, np.int32))
    finally:
        router.close()


def test_unknown_model_is_request_shaped_never_a_host_strike():
    """A typo'd model name must propagate to the caller as
    UnknownModelError — NOT count as dispatch failures that drain every
    healthy host fleet-wide (review finding on the cold-load spill)."""
    from mpi_pytorch_tpu.serve.batcher import UnknownModelError

    class _StrictHost(_FakeZooHost):
        def ensure_model(self, model):
            if model not in ("a", "b"):
                raise UnknownModelError(f"unknown model {model!r}")
            super().ensure_model(model)

    h0 = _StrictHost("h0", models=("a",))
    h1 = _StrictHost("h1", models=("b",))
    router = _make_router([h0, h1], fail_probes=1)
    try:
        for _ in range(5):  # well past fail_probes
            with pytest.raises(UnknownModelError):
                router.submit(0, model="typo")
        stats = router.stats()
        assert stats["dead"] == [], stats
        assert set(stats["hosts"]) == {"h0", "h1"}
        # A real tenant still routes fine afterwards.
        router.submit(0, model="a")
        h0.submits[0][1].set_result(np.zeros(3, np.int32))
    finally:
        router.close()


def test_failed_swap_in_rebaselines_resident_sets():
    """A swap-in that FAILS its warm probe must still re-baseline the
    already-resident sets: its cold compiles landed on their
    process-global counters, and a refused tenant must not leave
    phantom compiles on healthy ones (review finding)."""
    from mpi_pytorch_tpu.config import Config
    from mpi_pytorch_tpu.serve.zoo import ZooExecutablePool
    from mpi_pytorch_tpu.serve.zoo.pool import ColdSwapError

    cfg = Config(serve_models="a=resnet18,b=resnet18")
    reg = _registry_with_estimates(cfg, {"a": 1.0, "b": 1.0})
    exes = {}

    def build_fn(tenant_cfg, mesh):
        exe = _FakeExe(probe_compiles=1 if exes else 0)
        exes[tenant_cfg.model_name + str(len(exes))] = exe
        return {"bf16": exe}

    pool = ZooExecutablePool(cfg, reg, mesh=object(), build_fn=build_fn)
    a_exe = pool.ensure("a")["bf16"]
    # Simulate b's cold-load compiles landing on a's process-global
    # counter, then the swap-in failing its probe.
    a_exe._compiles = 3
    with pytest.raises(ColdSwapError):
        pool.ensure("b")
    assert a_exe.compiles_since_warmup() == 0, (
        "failed swap-in left phantom compiles on a resident set"
    )


# --------------------------------------------- controller: model labelling


class _FakeTenantUnit:
    def __init__(self, host_name, model, p99):
        self.host_name = host_name
        self.model = model
        self.name = f"{host_name}/{model}"
        self.max_wait_ms = 8.0
        self.buckets = (1, 4)
        self.active_buckets = (1, 4)
        self.precision = "bf16"
        self.precisions = ("bf16",)
        self.parity_top1 = None
        self._p99 = p99
        self._count = 10

    def snapshot(self):
        return {"histograms": {
            "serve/request_latency_ms": {
                "count": self._count, "sum": 1.0, "p99": self._p99,
            },
            "serve/fill_pct": {"count": 1, "sum": 80.0},
        }}

    def set_max_wait_ms(self, v):
        self.max_wait_ms = v

    def set_active_buckets(self, b):
        self.active_buckets = tuple(b)

    def set_precision(self, p):
        self.precision = p

    def compiles_after_warmup(self):
        return 0


class _FakeZooControllerHost:
    name = "h0"

    def __init__(self, units):
        self._units = units

    def tenants(self):
        return list(self._units)


class _ListWriter:
    def __init__(self):
        self.records = []

    def write(self, rec):
        self.records.append(dict(rec))


def test_controller_retunes_per_tenant_with_model_label():
    from mpi_pytorch_tpu.serve.fleet.controller import FleetController

    hot = _FakeTenantUnit("h0", "a", p99=50.0)  # breaches
    cold = _FakeTenantUnit("h0", "b", p99=1.0)  # deep headroom
    writer = _ListWriter()
    ctl = FleetController(
        lambda: [_FakeZooControllerHost([hot, cold])],
        target_p99_ms=10.0, metrics=writer,
    )
    retuned = ctl.tick()
    assert retuned == 1  # only the breaching tenant moved
    assert hot.max_wait_ms == 4.0  # halved
    assert cold.max_wait_ms == 8.0  # untouched — isolation
    recs = [r for r in writer.records if r.get("event") == "retune"]
    assert len(recs) == 1
    assert recs[0]["model"] == "a"
    assert recs[0]["host"] == "h0"
    assert recs[0]["compiles_after_warmup"] == 0
    from mpi_pytorch_tpu.obs.schema import validate_record

    recs[0]["ts"] = 1.0
    assert validate_record(recs[0]) == []


# -------------------------------------- RemoteHost facts generation satellite


class _FakeZooWireServer:
    """Duck-typed multi-tenant server behind the REAL wire stack
    (ServingHost + ObsHTTPServer): scriptable resident set + facts
    generation, no jax."""

    name = "h0"

    def __init__(self):
        self.resident = ["a", "b"]
        self.generation = 1
        self.submits = []

    def submit(self, image, model=None, trace=None):
        self.submits.append(model)
        fut = Future()
        fut.set_result(np.zeros(3, np.int32))
        return fut

    def ensure_model(self, model):
        if model not in self.resident:
            self.resident.append(model)
            self.generation += 1

    def evict_model(self, model):
        self.resident.remove(model)
        self.generation += 1

    def registry_snapshot(self):
        return {"counters": {}, "gauges": {}, "histograms": {},
                "models": {m: {} for m in self.resident},
                "facts_generation": self.generation,
                "seq": 0, "start_ts": 123.0}

    def stats(self):
        return {"served": len(self.submits), "models": {}}

    def _healthz(self):
        return {
            "status": "ok", "queue_depth": 0, "compiles_after_warmup": 0,
            "served": 0, "rejected": 0, "buckets": [1, 4],
            "precision": "bf16", "queue_capacity": 64,
            "max_wait_ms": 2.0, "active_buckets": [1, 4],
            "precisions": ["bf16"], "parity_top1": None, "topk": 3,
            "host_index": 0, "pid": None, "time": time.time(),
            "start_ts": 123.0,
            "models": list(self.resident),
            "registered_models": ["a", "b", "c"],
            "facts_generation": self.generation,
        }

    def set_max_wait_ms(self, v):
        pass

    def close(self, drain=True):
        pass


def test_remote_facts_cache_invalidates_on_generation_change():
    """ISSUE 14 satellite: the RemoteHost facts cache (static /healthz
    facts + TTL) must refresh the moment the resident model set changes
    — the /metricsz probe carries the generation counter, so the router
    never dispatches a tenant to a host that just evicted it."""
    from mpi_pytorch_tpu.serve.fleet.remote import RemoteHost
    from mpi_pytorch_tpu.serve.host import ServingHost

    server = _FakeZooWireServer()
    wire = ServingHost(server, port=0)
    try:
        host = RemoteHost(
            f"http://127.0.0.1:{wire.port}", name="h0", index=0,
            facts_ttl_s=3600.0,  # TTL alone would NEVER refresh in-test
        )
        assert host.models() == ("a", "b")
        # The host evicts b; the facts cache is still warm (huge TTL).
        server.evict_model("b")
        assert host.models() == ("a", "b")  # stale — cache, by design
        # The probe loop's snapshot carries the new generation → the
        # facts cache invalidates → the next models() read is fresh.
        host.snapshot()
        assert host.models() == ("a",)
        # Wire submit carries the tenant; the zoo control ops cross too.
        host.submit(np.zeros((4, 4, 3), np.uint8), model="a").result(5)
        assert server.submits[-1] == "a"
        host.ensure_model("c")
        assert "c" in server.resident
        assert host.models() == ("a", "c")  # control invalidated facts
        host.close(drain=False)
    finally:
        wire.close(drain=False)


# ------------------------------------------------------------- schema v10


def test_schema_v10_shapes():
    from mpi_pytorch_tpu.obs.schema import SCHEMA_VERSION, validate_record

    assert SCHEMA_VERSION >= 10
    serve = {
        "kind": "serve", "ts": 1.0, "bucket": 4, "requests": 3,
        "queue_depth": 0, "fill_ratio": 0.75, "queue_wait_ms": 1.0,
        "device_ms": 2.0, "model": "resnet18",
    }
    assert validate_record(serve) == []
    route = {
        "kind": "route", "ts": 1.0, "host": "h0", "requests": 5,
        "models": {"resnet18": 3, "mobilenet_v2": 2},
    }
    assert validate_record(route) == []
    swap = {
        "kind": "fleet", "ts": 1.0, "event": "swap_in", "host": "h0",
        "model": "mobilenet_v2", "resident": ["mobilenet_v2", "resnet18"],
        "compiles_after_warmup": 0,
        "plan": {"budget_mb": 100.0, "total_mb": 52.0, "fits": 1,
                 "tenants": {"resnet18": 43.0, "mobilenet_v2": 9.0}},
    }
    assert validate_record(swap) == []
    evict = {
        "kind": "fleet", "ts": 1.0, "event": "evict", "host": "h0",
        "model": "resnet18", "resident": [], "detail": "lru",
    }
    assert validate_record(evict) == []
    alert = {
        "kind": "alert", "ts": 1.0, "rule": "p99", "severity": "warn",
        "model": "resnet18",
    }
    assert validate_record(alert) == []
    bench = {
        "kind": "serve_bench", "ts": 1.0, "mode": "open", "buckets": "1,4",
        "max_wait_ms": 2.0, "requests": 10, "p50_ms": 1.0, "p95_ms": 2.0,
        "p99_ms": 3.0, "images_per_sec": 50.0, "model": "resnet18",
        "load_shape": "hot:resnet18",
    }
    assert validate_record(bench) == []
    # Wrong types still rejected.
    assert validate_record(dict(serve, model=3))
    assert validate_record(dict(route, models=[1]))


def test_monitor_labels_stamp_alert_records():
    from mpi_pytorch_tpu.obs.metrics import MetricsRegistry
    from mpi_pytorch_tpu.obs.monitor import SLOMonitor, parse_rules

    registry = MetricsRegistry()
    registry.counter("serve/rejected").inc(100)
    writer = _ListWriter()
    mon = SLOMonitor(
        registry, parse_rules("serve/rejected > 1 name=rej"),
        metrics=writer, labels={"model": "resnet18"},
    )
    mon.evaluate()
    assert writer.records and writer.records[0]["model"] == "resnet18"


# -------------------------------------------------------- regression keying


def test_check_regression_keys_model_and_load_shape(tmp_path):
    import sys

    sys.path.insert(0, os.path.join(REPO, "tools"))
    import check_regression

    def row(model, load_shape, p99):
        return {
            "kind": "serve_bench", "ts": 1.0, "mode": "open",
            "buckets": "1,4", "max_wait_ms": 2.0, "requests": 10,
            "p50_ms": 1.0, "p95_ms": 2.0, "p99_ms": p99,
            "images_per_sec": 100.0, "model": model,
            "load_shape": load_shape,
        }

    base = tmp_path / "base.json"
    new = tmp_path / "new.json"
    # Baseline: tenant a fast. New: tenant b slow at the SAME sweep
    # point — a DIFFERENT trend line, never compared.
    base.write_text(json.dumps(row("a", "uniform", 10.0)) + "\n")
    new.write_text(json.dumps(row("b", "uniform", 100.0)) + "\n")
    assert check_regression.check_serve(str(new), str(base), 10.0) == []
    # Same tenant, different load shape: also never compared.
    new.write_text(json.dumps(row("a", "hot:a", 100.0)) + "\n")
    assert check_regression.check_serve(str(new), str(base), 10.0) == []
    # Same tenant, same shape, regressed p99: caught.
    new.write_text(json.dumps(row("a", "uniform", 100.0)) + "\n")
    violations = check_regression.check_serve(str(new), str(base), 10.0)
    assert len(violations) == 1 and "p99" in violations[0]


# ------------------------------------------------ real 2-tenant fleet (jax)


@pytest.fixture(scope="module")
def zoo_fleet(tmp_path_factory):
    """The module's one REAL fleet: 2 hosts × 2 resnet18-arch tenants
    (one cold) on the CPU mesh — every expensive end-to-end assertion
    shares its build."""
    from mpi_pytorch_tpu.config import Config
    from mpi_pytorch_tpu.serve.fleet import FleetServer

    tmp = tmp_path_factory.mktemp("zoo_fleet")
    cfg = Config(
        model_name="resnet18", num_classes=16, width=32, height=32,
        synthetic_data=True, compute_dtype="float32",
        serve_buckets="1,4", serve_max_wait_ms=2.0, serve_topk=3,
        serve_queue_depth=64, loader_workers=4,
        serve_fleet_hosts=2, serve_probe_interval_ms=50.0,
        serve_models="hot=resnet18:admission=4,b=resnet18:cold",
        metrics_file=str(tmp / "metrics.jsonl"),
        log_file="", eval_log_file="",
    )
    cfg.validate_config()
    fleet = FleetServer(cfg, load_checkpoint=False)
    yield fleet, cfg
    fleet.close()


def test_zoo_fleet_end_to_end(zoo_fleet):
    """The _dryrun_zoo twin: cold swap-in via the router, per-tenant
    admission isolation under a hot-tenant flood, single-tenant flushes,
    zero steady-state compiles, schema-clean v10 stream."""
    from mpi_pytorch_tpu.serve import QueueFullError

    fleet, cfg = zoo_fleet
    images = _images(8)

    # --- cold swap-in: tenant b is resident nowhere; the first request
    # spills to a cold-load (ensure_model) and still answers.
    preds = fleet.submit(images[0], model="b").result(timeout=120)
    assert preds.shape == (3,)
    resident = [set(h.models()) for h in fleet.router.active_hosts()]
    assert any("b" in r for r in resident)

    # --- hot-tenant flood: admission=4 binds at the front door; the
    # cold tenant keeps serving with rejected == 0.
    futs, rejected = [], 0
    for i in range(40):
        try:
            futs.append(fleet.submit(images[i % 8], model="hot"))
        except QueueFullError as e:
            assert e.model == "hot"
            rejected += 1
    for i in range(4):
        futs.append(fleet.submit(images[i], model="b"))
    for f in futs:
        f.result(timeout=120)
    assert rejected > 0
    assert fleet.router.rejections_by_model["b"] == 0
    ts = fleet.tenant_stats()
    assert ts["b"]["rejected"] == 0 and ts["b"]["front_door_rejections"] == 0
    assert ts["hot"]["front_door_rejections"] == rejected
    assert ts["b"]["served"] >= 5

    # --- zero steady-state compiles across every tenant set, through
    # the swap-in and the flood.
    assert fleet.stats()["compiles_after_warmup"] == 0


def test_zoo_fleet_controller_retunes_tenant_with_model_label(zoo_fleet):
    from mpi_pytorch_tpu.serve.fleet.controller import FleetController

    fleet, cfg = zoo_fleet
    writer = _ListWriter()
    # An impossible target: every tenant with observations breaches →
    # the retune halves its wait, per tenant, with compiles == 0.
    ctl = FleetController(
        fleet.router.active_hosts, target_p99_ms=0.001, metrics=writer,
    )
    retuned = ctl.tick()
    assert retuned >= 1
    recs = [r for r in writer.records if r.get("event") == "retune"]
    assert recs and all(r["compiles_after_warmup"] == 0 for r in recs)
    assert all(r.get("model") in ("hot", "b") for r in recs)


def test_zoo_fleet_single_tenant_flushes_and_stream(zoo_fleet):
    """Every serve record names exactly one tenant (flushes are
    single-tenant by construction), route windows carry per-tenant
    counts, the swap-in record carries its packing plan, and the whole
    stream validates as schema v10."""
    from mpi_pytorch_tpu.obs.schema import load_records, validate_jsonl

    fleet, cfg = zoo_fleet
    # Flush the router's open windows so route records land.
    fleet.router._write_route_records(force=True)
    assert validate_jsonl(cfg.metrics_file) == []
    recs = load_records(cfg.metrics_file)
    serves = [r for r in recs if r["kind"] == "serve"]
    assert serves
    assert all(r.get("model") in ("hot", "b") for r in serves)
    swaps = [
        r for r in recs
        if r["kind"] == "fleet" and r.get("event") == "swap_in"
    ]
    assert len(swaps) >= 1
    assert swaps[0]["model"] == "b"
    assert swaps[0]["compiles_after_warmup"] == 0
    assert "b" in swaps[0]["resident"]
    assert swaps[0]["plan"]["fits"] == 1
    routes = [r for r in recs if r["kind"] == "route" and r.get("models")]
    assert routes, "no route window carried per-tenant counts"


def test_zoo_lru_eviction_under_budget(zoo_fleet):
    """Shrinking the packing budget below the resident set forces the
    next swap-in to evict the LRU tenant — and the facts generation
    moves so routing facts stay coherent."""
    fleet, cfg = zoo_fleet
    # The cold-load spill picked ONE host for tenant b — use that one.
    host = next(
        h for h in fleet.router.active_hosts() if "b" in h.models()
    )
    server = host.server
    assert set(server.models()) == {"hot", "b"}
    gen0 = server.facts_generation
    # Touch "b" so "hot" is the LRU victim, then make the budget only
    # fit one tenant + the incoming one.
    server.submit(_images(1)[0], model="b").result(timeout=60)
    measured = server.pool.measured_bytes()
    one_tenant = max(measured.values())
    server._budget_bytes = int(one_tenant * 2.2)
    # Evict + re-ensure: evict hot manually is NOT the point — ask for
    # an eviction via the budget by re-activating a previously evicted
    # tenant. Simplest deterministic route: evict b, then re-ensure b
    # under the tightened budget — hot (LRU) must be evicted to fit.
    server.evict_model("b")
    server._last_used["hot"] = 0.0  # pin hot as least-recently-used
    server.ensure_model("b")
    after = set(server.models())
    assert "b" in after
    assert server.facts_generation > gen0
    # restore for other tests
    server._budget_bytes = None
    server.ensure_model("hot")
    assert set(server.models()) == {"hot", "b"}
