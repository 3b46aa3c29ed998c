"""Tests for the online inference subsystem (mpi_pytorch_tpu/serve/).

Covers the full acceptance surface: batcher semantics (buckets, deadline,
backpressure, drain), the end-to-end server with ZERO steady-state
compiles across a multi-bucket request mix (asserted via the obs
backend-compile counter), top-k parity between the plain predict path and
the fused ``head_predict`` argmax, the ``kind="serve"`` record schema, the
``tools/bench_serve.py --smoke`` CPU bench, the persistent compilation
cache satellite, and (slow) 2-process replicated serving.
"""

import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cpu_env(**extra):
    """Subprocess env pinned to a CPU world."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra)
    return env


# ---------------------------------------------------------------- batcher


def test_parse_buckets_and_pick_bucket():
    from mpi_pytorch_tpu.serve import parse_buckets, pick_bucket

    assert parse_buckets([32, 1, 8, 8]) == (1, 8, 32)
    with pytest.raises(ValueError):
        parse_buckets([])
    with pytest.raises(ValueError):
        parse_buckets([0, 4])
    buckets = (1, 8, 32)
    assert pick_bucket(1, buckets) == 1
    assert pick_bucket(2, buckets) == 8
    assert pick_bucket(8, buckets) == 8
    assert pick_bucket(9, buckets) == 32
    assert pick_bucket(1000, buckets) == 32  # flushes cap at the largest


def test_config_serve_knobs_validate():
    from mpi_pytorch_tpu.config import Config

    cfg = Config(serve_buckets="8,1,32")
    assert cfg.parsed_serve_buckets() == (1, 8, 32)
    with pytest.raises(ValueError):
        Config(serve_buckets="").validate_config()
    with pytest.raises(ValueError):
        Config(serve_buckets="1,frog").validate_config()
    with pytest.raises(ValueError):
        Config(serve_topk=0).validate_config()
    with pytest.raises(ValueError):
        Config(serve_topk=6).validate_config()
    with pytest.raises(ValueError):
        Config(serve_max_wait_ms=-1).validate_config()
    with pytest.raises(ValueError):
        Config(serve_queue_depth=0).validate_config()
    with pytest.raises(ValueError):
        Config(serve_topk=5, num_classes=3).validate_config()


def test_batcher_deadline_flush_and_drain():
    from mpi_pytorch_tpu.serve import DynamicBatcher, PendingRequest

    b = DynamicBatcher(buckets=(8,), max_wait_s=0.05, max_queue=16)
    t0 = time.monotonic()
    for i in range(3):
        b.submit(PendingRequest(payload=i, future=None))
    flush = b.next_flush()
    waited = time.monotonic() - t0
    assert [r.payload for r in flush] == [0, 1, 2]
    # Flushed by the deadline (3 < bucket 8), not instantly and not never.
    assert 0.03 <= waited < 2.0, waited

    # A full bucket flushes immediately, without sitting out the deadline.
    b2 = DynamicBatcher(buckets=(1, 4), max_wait_s=10.0, max_queue=16)
    for i in range(4):
        b2.submit(PendingRequest(payload=i, future=None))
    t0 = time.monotonic()
    assert len(b2.next_flush()) == 4
    assert time.monotonic() - t0 < 1.0

    # close() drains: queued requests still flush, then None forever.
    b2.submit(PendingRequest(payload=9, future=None))
    b2.close()
    assert [r.payload for r in b2.next_flush()] == [9]
    assert b2.next_flush() is None


def test_batcher_backlog_coalesces_full_buckets():
    """Regression (caught by a live flood drive): requests that sat in the
    queue past their deadline must still coalesce into the LARGEST bucket —
    the pre-fix behavior flushed one overdue request per batch, i.e. the
    batch-1 regime bucketing exists to avoid."""
    from mpi_pytorch_tpu.serve import DynamicBatcher, PendingRequest

    b = DynamicBatcher(buckets=(1, 8), max_wait_s=0.0, max_queue=64)
    for i in range(20):
        b.submit(PendingRequest(payload=i, future=None))
    time.sleep(0.01)  # everything queued is long past the 0 ms deadline
    sizes = [len(b.next_flush()) for _ in range(3)]
    assert sizes == [8, 8, 4], sizes


def test_batcher_backpressure_and_closed():
    from mpi_pytorch_tpu.serve import (
        DynamicBatcher,
        PendingRequest,
        QueueFullError,
        ServerClosedError,
    )

    b = DynamicBatcher(buckets=(4,), max_wait_s=1.0, max_queue=2)
    b.submit(PendingRequest(payload=0, future=None))
    b.submit(PendingRequest(payload=1, future=None))
    with pytest.raises(QueueFullError):
        b.submit(PendingRequest(payload=2, future=None))
    b.close()
    with pytest.raises(ServerClosedError):
        b.submit(PendingRequest(payload=3, future=None))


# ------------------------------------------------------------------ server


@pytest.fixture(scope="module")
def serve_cfg(tmp_path_factory):
    from mpi_pytorch_tpu.config import Config

    scratch = tmp_path_factory.mktemp("serve")
    cfg = Config(
        model_name="resnet18", num_classes=32, width=32, height=32,
        synthetic_data=True, compute_dtype="float32",
        serve_buckets="1,8", serve_max_wait_ms=5.0, serve_topk=3,
        serve_queue_depth=64, loader_workers=4,
        metrics_file=str(scratch / "serve_metrics.jsonl"),
        log_file="", eval_log_file="",
    )
    cfg.validate_config()
    return cfg


@pytest.fixture(scope="module")
def server(serve_cfg):
    from mpi_pytorch_tpu.serve import InferenceServer

    srv = InferenceServer(serve_cfg, load_checkpoint=False)
    yield srv
    srv.close()


def test_server_zero_compiles_across_bucket_mix(server):
    """The acceptance invariant: after warmup, a request mix that lands in
    BOTH buckets (1 and 8; replicated and data-sharded executables)
    performs zero XLA compiles — measured by the backend-compile
    listener, not assumed."""
    rng = np.random.default_rng(0)
    images = [
        rng.integers(0, 256, size=(32, 32, 3)).astype(np.uint8)
        for _ in range(13)
    ]
    preds = server.predict_batch(images, timeout=120)
    assert preds.shape == (13, 3)
    assert preds.dtype == np.int32
    assert (preds >= 0).all() and (preds < 32).all()
    # Each row's top-k indices are distinct classes.
    assert all(len(set(row.tolist())) == 3 for row in preds)

    # A second wave, single + bulk, post-warmup: still zero compiles.
    one = server.predict_batch(images[:1], timeout=120)
    again = server.predict_batch(images, timeout=120)
    stats = server.stats()
    assert stats["compiles_after_warmup"] == 0, stats
    assert set(stats["buckets"]) == {1, 8}
    assert sum(stats["by_bucket"].values()) == stats["batches"]
    assert stats["served"] >= 27
    # Determinism: the same image yields the same top-k every time.
    np.testing.assert_array_equal(one[0], preds[0])
    np.testing.assert_array_equal(again, preds)


def test_server_preprocess_contract_and_bad_request(server):
    """Float requests pass through as already-normalized; a wrong-shape
    request fails ITS OWN future (typed), never the batch or the server."""
    from mpi_pytorch_tpu.serve import ServeError

    rng = np.random.default_rng(1)
    raw = rng.integers(0, 256, size=(32, 32, 3)).astype(np.uint8)
    from mpi_pytorch_tpu.data.pipeline import normalize_image

    normalized = normalize_image(raw.astype(np.float32) / 255.0)
    p_raw = server.predict_batch([raw], timeout=120)
    p_norm = server.predict_batch([normalized], timeout=120)
    np.testing.assert_array_equal(p_raw, p_norm)

    bad = server.submit(np.zeros((4, 4, 3), np.uint8))
    good = server.submit(raw)
    with pytest.raises(ServeError):
        bad.result(timeout=120)
    np.testing.assert_array_equal(good.result(timeout=120), p_raw[0])


def test_server_path_request_decodes(server, tmp_path):
    """A path request goes through the real decode→resize→normalize stage
    (native → PIL fallback) and predicts identically to submitting the
    same pixels directly (PNG = lossless, so the arrays match exactly)."""
    from PIL import Image

    rng = np.random.default_rng(3)
    raw = rng.integers(0, 256, size=(32, 32, 3)).astype(np.uint8)
    path = tmp_path / "req.png"
    Image.fromarray(raw).save(path)
    from_path = server.predict_batch([str(path)], timeout=120)
    from_array = server.predict_batch([raw], timeout=120)
    np.testing.assert_array_equal(from_path, from_array)


def test_server_metrics_records_schema(serve_cfg, server):
    """The per-flush kind="serve" records validate against the shared obs
    schema — the same contract report_run/check_results_artifacts read."""
    from mpi_pytorch_tpu.obs.schema import load_records, validate_jsonl

    # server fixture work has already run; records are on disk (line-buffered).
    problems = validate_jsonl(serve_cfg.metrics_file)
    assert not problems, problems
    records = load_records(serve_cfg.metrics_file)
    serves = [r for r in records if r["kind"] == "serve"]
    assert serves, "no serve records written"
    assert {r["bucket"] for r in serves} <= {1, 8}
    for r in serves:
        assert 0.0 < r["fill_ratio"] <= 1.0
        assert r["requests"] <= r["bucket"]


def test_preprocess_worker_crash_typed_counted_and_batch_survives(server):
    """ISSUE 7 satellite: a preprocess-WORKER crash (a non-ServeError from
    inside the pool, injected via the MPT_FAULT_PREPROCESS_N gate) fails
    only ITS request, with the typed PreprocessError — not a silent loss,
    not a misleading ServerClosedError — while the rest of the flush
    serves; the failure is counted in stats and on the flush's
    kind=\"serve\" record (preprocess_failures)."""
    from mpi_pytorch_tpu.serve import PreprocessError
    from mpi_pytorch_tpu.utils.env import reset_fault_counters

    rng = np.random.default_rng(7)
    raw = [
        rng.integers(0, 256, size=(32, 32, 3)).astype(np.uint8) for _ in range(3)
    ]
    before = server.stats()
    os.environ["MPT_FAULT_PREPROCESS_N"] = "1"
    reset_fault_counters()
    try:
        # The first payload entering the pool crashes; submit the whole
        # wave quickly so survivors coalesce around the casualty.
        futs = [server.submit(im) for im in raw]
        results, crashes = [], []
        for f in futs:
            try:
                results.append(f.result(timeout=120))
            except PreprocessError as e:
                crashes.append(e)
        assert len(crashes) == 1 and "worker crash" in str(crashes[0])
        assert len(results) == 2  # the batch went on without the casualty
    finally:
        os.environ.pop("MPT_FAULT_PREPROCESS_N", None)
        reset_fault_counters()
    stats = server.stats()
    assert stats["preprocess_failures"] == before["preprocess_failures"] + 1
    # The flush that saw the casualty carries the count on its record (the
    # completion loop writes it just after resolving the futures — poll).
    from mpi_pytorch_tpu.obs.schema import load_records, validate_jsonl

    flagged = []
    deadline = time.monotonic() + 30
    while not flagged and time.monotonic() < deadline:
        flagged = [
            r for r in load_records(server.cfg.metrics_file)
            if r["kind"] == "serve" and r.get("preprocess_failures")
        ]
        time.sleep(0.05)
    assert validate_jsonl(server.cfg.metrics_file) == []
    assert flagged and flagged[-1]["preprocess_failures"] >= 1
    assert "worker_respawns" in flagged[-1]


def test_preprocess_all_failed_flush_emits_fault_record(server):
    """A flush in which EVERY request fails preprocess dispatches no batch
    (no kind=\"serve\" record) — the failure must surface as a
    kind=\"fault\" reason=preprocess_all_failed record instead of
    vanishing from the stream."""
    from mpi_pytorch_tpu.obs.schema import load_records, validate_jsonl
    from mpi_pytorch_tpu.serve import PreprocessError
    from mpi_pytorch_tpu.utils.env import reset_fault_counters

    rng = np.random.default_rng(13)
    raw = rng.integers(0, 256, size=(32, 32, 3)).astype(np.uint8)
    os.environ["MPT_FAULT_PREPROCESS_N"] = "1"
    reset_fault_counters()
    try:
        with pytest.raises(PreprocessError):
            server.predict_batch([raw], timeout=120)  # lone request = whole flush
    finally:
        os.environ.pop("MPT_FAULT_PREPROCESS_N", None)
        reset_fault_counters()
    faults = []
    deadline = time.monotonic() + 30
    while not faults and time.monotonic() < deadline:
        faults = [
            r for r in load_records(server.cfg.metrics_file)
            if r["kind"] == "fault" and r["reason"] == "preprocess_all_failed"
        ]
        time.sleep(0.05)
    assert faults and "1 request(s)" in faults[-1]["detail"]
    assert validate_jsonl(server.cfg.metrics_file) == []


def test_preprocess_pool_death_respawns_and_serves(server):
    """A DEAD worker pool (simulated by shutting it down under the live
    server — the BrokenThreadPool/errant-shutdown scenario) used to turn
    every subsequent request into a bogus 'server is shut down'; now the
    pool respawns once, the request retries on the fresh pool, and the
    respawn is counted."""
    rng = np.random.default_rng(11)
    raw = rng.integers(0, 256, size=(32, 32, 3)).astype(np.uint8)
    baseline = server.predict_batch([raw], timeout=120)

    before = server.stats()["worker_respawns"]
    server._pool.shutdown(wait=True)  # the pool dies; the server is live
    after_death = server.predict_batch([raw], timeout=120)
    np.testing.assert_array_equal(after_death, baseline)
    assert server.stats()["worker_respawns"] == before + 1


def test_server_rejects_after_close(serve_cfg):
    from mpi_pytorch_tpu.serve import InferenceServer, ServerClosedError

    # A second tiny server would recompile; reuse the executables via the
    # lru-cached predict step — construction is the cheap part. Use a
    # single-bucket config to keep it light.
    import dataclasses

    cfg = dataclasses.replace(serve_cfg, serve_buckets="8", metrics_file="")
    cfg.validate_config()
    srv = InferenceServer(cfg, load_checkpoint=False)
    img = np.zeros((32, 32, 3), np.uint8)
    fut = srv.submit(img)
    assert fut.result(timeout=120).shape == (3,)
    srv.close()  # graceful drain
    with pytest.raises(ServerClosedError):
        srv.submit(img)


# ---------------------------------------------------------- top-k parity


def test_topk_top1_matches_fused_head_argmax(monkeypatch):
    """Satellite: the plain predict path's top-k column 0 IS the argmax the
    fused head_predict computes — pinned through a real zoo model with the
    real kernel (Pallas interpreter) on the 8-device mesh."""
    import optax
    from jax.sharding import Mesh

    from mpi_pytorch_tpu.evaluate import _make_predict_step, _make_predict_step_impl
    from mpi_pytorch_tpu.models import create_model_bundle
    from mpi_pytorch_tpu.train.state import TrainState

    bundle, variables = create_model_bundle(
        "resnet18", 200, rng=jax.random.PRNGKey(0), image_size=32
    )
    state = TrainState.create(
        apply_fn=bundle.model.apply, variables=variables,
        tx=optax.identity(), rng=jax.random.PRNGKey(1),
    )
    mesh = Mesh(np.array(jax.devices()).reshape(-1, 1), ("data", "model"))
    images = np.random.default_rng(0).normal(size=(8, 32, 32, 3)).astype(np.float32)
    labels = np.asarray([3, 5, -1, 9, 0, 1, -1, 7], np.int32)
    batch = (jnp.asarray(images), jnp.asarray(labels))

    monkeypatch.setenv("MPT_HEAD_INTERPRET", "1")
    _make_predict_step_impl.cache_clear()
    try:
        topk = _make_predict_step(mesh, jnp.float32, topk=5)
        fused = _make_predict_step(mesh, jnp.float32, fused_head=True)
        mk, pk = topk(state, batch)
        mf, pf = fused(state, batch)
    finally:
        monkeypatch.delenv("MPT_HEAD_INTERPRET")
        _make_predict_step_impl.cache_clear()
    pk, pf = np.asarray(pk), np.asarray(pf)
    assert pk.shape == (8, 5)
    np.testing.assert_array_equal(pk[:, 0], pf)  # top-1 == fused argmax
    # Metrics agree too (same logits, same masking).
    for k in ("loss", "correct", "count"):
        np.testing.assert_allclose(float(mk[k]), float(mf[k]), rtol=1e-4, atol=1e-4)
    # topk>1 with the fused head is a contract violation, not a silent k=1.
    with pytest.raises(ValueError):
        _make_predict_step(mesh, jnp.float32, fused_head=True, topk=3)


def test_topk1_path_unchanged(monkeypatch):
    """topk=1 keeps the original [B] argmax contract (the predictions-CSV
    path depends on it)."""
    import optax
    from jax.sharding import Mesh

    from mpi_pytorch_tpu.evaluate import _make_predict_step
    from mpi_pytorch_tpu.models import create_model_bundle
    from mpi_pytorch_tpu.train.state import TrainState

    bundle, variables = create_model_bundle(
        "resnet18", 50, rng=jax.random.PRNGKey(0), image_size=32
    )
    state = TrainState.create(
        apply_fn=bundle.model.apply, variables=variables,
        tx=optax.identity(), rng=jax.random.PRNGKey(1),
    )
    mesh = Mesh(np.array(jax.devices()).reshape(-1, 1), ("data", "model"))
    images = np.random.default_rng(2).normal(size=(8, 32, 32, 3)).astype(np.float32)
    labels = np.arange(8, dtype=np.int32)
    plain = _make_predict_step(mesh, jnp.float32)
    _, p = plain(state, (jnp.asarray(images), jnp.asarray(labels)))
    assert np.asarray(p).shape == (8,)


# ----------------------------------------------------------- bench (smoke)


def test_bench_serve_smoke(tmp_path):
    """Acceptance: the CPU smoke bench emits schema-valid p50/p95/p99 +
    throughput rows for at least two bucket sets, in both load shapes,
    with zero steady-state compiles."""
    from mpi_pytorch_tpu.obs.schema import validate_record

    out = tmp_path / "serve_bench.json"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "bench_serve.py"),
         "--smoke", "--out", str(out)],
        cwd=REPO, env=_cpu_env(), capture_output=True, text=True, timeout=900,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    rows = [json.loads(l) for l in out.read_text().splitlines() if l.strip()]
    assert len(rows) >= 4, rows
    for r in rows:
        assert not validate_record(r), validate_record(r)
        assert r["p50_ms"] <= r["p95_ms"] <= r["p99_ms"]
        assert r["images_per_sec"] > 0
        assert r["compiles_after_warmup"] == 0
        assert 0.0 < r["mean_fill_ratio"] <= 1.0
    assert len({r["buckets"] for r in rows}) >= 2  # two bucket sets
    assert {r["mode"] for r in rows} == {"closed", "open"}
    open_rows = [r for r in rows if r["mode"] == "open"]
    assert all(r["offered_rps"] for r in open_rows)


def test_committed_serve_bench_artifact_validates():
    """The committed docs/serve_bench.json rows pass the same lint CI
    applies (check_results_artifacts covers it via the metrics sweep)."""
    from mpi_pytorch_tpu.obs.schema import validate_jsonl

    path = os.path.join(REPO, "docs", "serve_bench.json")
    assert os.path.isfile(path), "docs/serve_bench.json missing"
    assert not validate_jsonl(path)


# ------------------------------------------------- compilation cache (sat)


_CACHE_CHILD = """
import sys
import jax
hits = [0]
def on_event(name, **kw):
    if name == "/jax/compilation_cache/cache_hits":
        hits[0] += 1
jax.monitoring.register_event_listener(on_event)
sys.path.insert(0, {repo!r})
from mpi_pytorch_tpu.config import Config, apply_runtime_flags
apply_runtime_flags(Config())   # the real wiring under test
print("CACHE_DIR", jax.config.jax_compilation_cache_dir)
if "--compile" in sys.argv:
    import jax.numpy as jnp
    jax.jit(lambda x: (x * 2 + 1).sum())(jnp.arange(64.0)).block_until_ready()
    print("CACHE_HITS", hits[0])
"""


def _cache_child(tmp_path, env, *args):
    script = tmp_path / "cache_child.py"
    script.write_text(_CACHE_CHILD.format(repo=REPO))
    proc = subprocess.run(
        [sys.executable, str(script), *args],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    return dict(
        line.split(" ", 1) for line in proc.stdout.splitlines()
        if line.startswith("CACHE_")
    )


def test_bench_serve_percentiles_survive_total_rejection():
    """A fully-rejected sweep point (overload regime) must yield a row, not
    an empty-array percentile crash."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_serve", os.path.join(REPO, "tools", "bench_serve.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod._percentiles([]) == {"p50_ms": 0.0, "p95_ms": 0.0, "p99_ms": 0.0}
    out = mod._percentiles([1.0, 2.0, 3.0])
    assert out["p50_ms"] <= out["p95_ms"] <= out["p99_ms"]


def test_compilation_cache_default_is_one_fixed_path_in_the_checkout(tmp_path):
    """With ``JAX_COMPILATION_CACHE_DIR`` unset the cache's home is ONE fixed
    path — ``<repo>/.jax_cache``, gitignored — never a mkdtemp, a pid or a
    timestamp: a cache only hits at the path it was written under. (No
    compile in the child: the checkout's cache stays untouched.)"""
    from mpi_pytorch_tpu.config import DEFAULT_COMPILATION_CACHE_DIR

    assert DEFAULT_COMPILATION_CACHE_DIR == os.path.join(REPO, ".jax_cache")
    env = _cpu_env()
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    first = _cache_child(tmp_path, env)["CACHE_DIR"]
    assert first == DEFAULT_COMPILATION_CACHE_DIR
    assert _cache_child(tmp_path, env)["CACHE_DIR"] == first  # same path twice
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_compilation_cache_goes_where_the_environment_says(tmp_path):
    """With ``JAX_COMPILATION_CACHE_DIR`` set the code sets no directory at
    all: whoever placed the variable (the chip driver, a parent giving its
    children a shared cache) wins, the entries land there, and a second
    FRESH process is served from them."""
    cache_dir = tmp_path / "jax_cache"
    env = _cpu_env(JAX_COMPILATION_CACHE_DIR=str(cache_dir))

    cold = _cache_child(tmp_path, env, "--compile")
    assert cold["CACHE_DIR"] == str(cache_dir)
    assert int(cold["CACHE_HITS"]) == 0  # cold: populated, no hits
    assert len(list(cache_dir.iterdir())) > 0, "cache dir not populated"
    # fresh process: served from the populated cache
    assert int(_cache_child(tmp_path, env, "--compile")["CACHE_HITS"]) >= 1


# ------------------------------------------------ multi-process replicas


@pytest.mark.slow
def test_two_process_serve_replicas(tmp_path):
    """Satellite: replicated-server predictions match single-process. Two
    real processes rendezvous through jax.distributed, each serving over
    its LOCAL 4-device replica mesh; a third, plain single process runs
    the identical workload. All three top-k streams must be identical."""
    import socket

    def _free_port() -> int:
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    def _flags(env):
        flags = [
            f for f in env.get("XLA_FLAGS", "").split()
            if "xla_force_host_platform_device_count" not in f
        ]
        return " ".join(flags + ["--xla_force_host_platform_device_count=4"])

    child = os.path.join(REPO, "tests", "serve_child.py")
    port = _free_port()
    procs = []
    for pid in range(2):
        env = _cpu_env(
            JAX_COORDINATOR_ADDRESS=f"127.0.0.1:{port}",
            JAX_NUM_PROCESSES="2", JAX_PROCESS_ID=str(pid), MPT_MULTIHOST="1",
        )
        env["XLA_FLAGS"] = _flags(env)
        procs.append(subprocess.Popen(
            [sys.executable, child], env=env, cwd=REPO,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        ))
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=900)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
    for p, out in zip(procs, outs):
        assert p.returncode == 0, f"child failed:\n{out}"

    env = _cpu_env()
    env["XLA_FLAGS"] = _flags(env)
    single = subprocess.run(
        [sys.executable, child], env=env, cwd=REPO,
        capture_output=True, text=True, timeout=900,
    )
    assert single.returncode == 0, single.stdout + single.stderr

    lines = [
        line
        for out in outs + [single.stdout]
        for line in out.splitlines()
        if line.startswith("SERVE_OK")
    ]
    assert len(lines) == 3, (outs, single.stdout)
    assert lines[0] == lines[1] == lines[2], lines


# ------------------------------------------------- live telemetry (ISSUE 8)


def test_server_obs_endpoints_request_ids_and_idempotent_close(tmp_path):
    """The serve live-telemetry surface in one server life: /metrics
    (parseable Prometheus text), /metricsz (JSON snapshot whose flush p99
    matches the kind="serve" record stream), /healthz, per-request trace
    ids threaded enqueue→preprocess→dispatch→fetch, the final registry
    snapshot record, and idempotent close (the satellite fix)."""
    import dataclasses
    import re
    import urllib.request

    from mpi_pytorch_tpu.config import Config
    from mpi_pytorch_tpu.obs.schema import load_records, validate_jsonl
    from mpi_pytorch_tpu.serve import InferenceServer

    cfg = Config(
        model_name="resnet18", num_classes=32, width=32, height=32,
        synthetic_data=True, compute_dtype="float32",
        serve_buckets="1,4", serve_max_wait_ms=2.0, serve_topk=3,
        metrics_file=str(tmp_path / "m.jsonl"),
        trace_file=str(tmp_path / "trace.json"),
        log_file="", eval_log_file="", serve_metrics_port=-1,
    )
    cfg.validate_config()
    server = InferenceServer(cfg, load_checkpoint=False)
    try:
        rng = np.random.default_rng(0)
        images = [
            rng.integers(0, 256, size=(32, 32, 3)).astype(np.uint8)
            for _ in range(16)
        ]
        server.predict_batch(images, timeout=120)

        port = server.metrics_port
        assert port and port > 0
        text = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10
        ).read().decode()
        line_re = re.compile(
            r'^(# (TYPE|HELP) .*|'
            r'[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? -?[0-9+.][^ ]*)$'
        )
        for line in text.strip().splitlines():
            assert line_re.match(line), repr(line)
        assert "mpt_serve_requests_total 16" in text
        assert 'mpt_serve_flush_ms_bucket{le="+Inf"}' in text
        snap = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metricsz", timeout=10
        ).read().decode())
        health = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=10
        ).read().decode())
        assert health["status"] == "ok"
        assert health["compiles_after_warmup"] == 0
    finally:
        server.close()
    server.close()  # idempotent: a second close is a no-op, not a crash

    assert validate_jsonl(cfg.metrics_file) == []
    records = load_records(cfg.metrics_file)
    serves = [r for r in records if r["kind"] == "serve"]
    finals = [r for r in records if r["kind"] == "metrics"]
    assert serves and len(finals) == 1  # the close-time registry snapshot
    # The scraped histogram saw exactly the flush stream: same count, and
    # p99 within the sketch's bucket error of the exact stream p99.
    flush_ms = sorted(r["total_ms"] for r in serves)
    exact_p99 = flush_ms[max(0, -(-99 * len(flush_ms) // 100) - 1)]
    scraped = snap["histograms"]["serve/flush_ms"]
    assert scraped["count"] == len(serves)
    assert abs(scraped["p99"] - exact_p99) <= 0.10 * max(exact_p99, 1e-9)
    assert snap["counters"]["serve/requests"] == 16.0
    assert finals[0]["counters"]["serve/served"] == 16.0

    # Request-id threading across the pipeline phases.
    trace = json.load(open(cfg.trace_file))
    events = trace["traceEvents"]
    enqueued = {e["args"]["req"] for e in events if e["name"] == "serve/enqueue"}
    assert enqueued == set(range(16))
    for phase in ("serve/preprocess", "serve/dispatch", "serve/fetch"):
        seen = {
            rid for e in events if e["name"] == phase
            for rid in e.get("args", {}).get("req_ids", [])
        }
        assert seen == enqueued, (phase, sorted(seen))


def test_close_flushes_sinks_even_when_drain_path_raises(tmp_path):
    """THE satellite fix pinned: close() used to flush sinks only after a
    clean drain — a failure mid-shutdown lost the per-process trace and
    the final snapshot. Now the sink flush is on the finally path."""
    from mpi_pytorch_tpu.config import Config
    from mpi_pytorch_tpu.obs.schema import load_records
    from mpi_pytorch_tpu.serve import InferenceServer

    cfg = Config(
        model_name="resnet18", num_classes=32, width=32, height=32,
        synthetic_data=True, compute_dtype="float32",
        serve_buckets="4", serve_max_wait_ms=1.0, serve_topk=1,
        metrics_file=str(tmp_path / "m.jsonl"),
        trace_file=str(tmp_path / "trace.json"),
        log_file="", eval_log_file="",
    )
    cfg.validate_config()
    server = InferenceServer(cfg, load_checkpoint=False)

    def exploding_shutdown(wait=True):
        raise RuntimeError("injected: worker pool wedged mid-drain")

    server._pool.shutdown = exploding_shutdown
    with pytest.raises(RuntimeError, match="wedged mid-drain"):
        server.close()
    # The failure still flushed every obs sink: trace on disk, final
    # registry snapshot in the stream, and a repeat close() is a no-op.
    assert json.load(open(cfg.trace_file))["traceEvents"] is not None
    assert any(
        r["kind"] == "metrics" for r in load_records(cfg.metrics_file)
    )
    server.close()


def test_init_failure_flushes_sinks(tmp_path, monkeypatch):
    """A warmup/build crash inside __init__ must leave the trace and the
    metrics stream flushed — the aborted startup is exactly the run whose
    evidence is needed (the trainer failure-path discipline)."""
    import mpi_pytorch_tpu.serve.server as server_mod
    from mpi_pytorch_tpu.config import Config
    from mpi_pytorch_tpu.serve import InferenceServer

    def exploding_exe(*a, **kw):
        raise RuntimeError("injected: warmup compile died")

    monkeypatch.setattr(server_mod, "BucketExecutables", exploding_exe)
    cfg = Config(
        model_name="resnet18", num_classes=32, width=32, height=32,
        synthetic_data=True, compute_dtype="float32", serve_buckets="4",
        metrics_file=str(tmp_path / "m.jsonl"),
        trace_file=str(tmp_path / "trace.json"),
        log_file="", eval_log_file="",
    )
    cfg.validate_config()
    with pytest.raises(RuntimeError, match="warmup compile died"):
        InferenceServer(cfg, load_checkpoint=False)
    assert (tmp_path / "trace.json").exists()  # tracer flushed on the way out


def test_serve_slo_rule_fires_on_latency_breach(tmp_path):
    """A serve-side SLO rule over the live registry: an absurdly low p99
    threshold breaches on real traffic, writing a kind="alert" record into
    the serve stream and dumping the flight ring."""
    import os as _os

    from mpi_pytorch_tpu.config import Config
    from mpi_pytorch_tpu.obs.schema import load_records, validate_jsonl
    from mpi_pytorch_tpu.serve import InferenceServer

    cfg = Config(
        model_name="resnet18", num_classes=32, width=32, height=32,
        synthetic_data=True, compute_dtype="float32",
        serve_buckets="1,4", serve_max_wait_ms=1.0, serve_topk=1,
        metrics_file=str(tmp_path / "m.jsonl"),
        log_file="", eval_log_file="",
        slo_rules="serve/flush_ms:p99 > 0.001 name=serve_p99 action=log,metric",
        flight_dir=str(tmp_path / "flight"),
    )
    cfg.validate_config()
    with InferenceServer(cfg, load_checkpoint=False) as server:
        rng = np.random.default_rng(0)
        server.predict_batch(
            [rng.integers(0, 256, size=(32, 32, 3)).astype(np.uint8)
             for _ in range(6)],
            timeout=120,
        )
    assert validate_jsonl(cfg.metrics_file) == []
    records = load_records(cfg.metrics_file)
    alerts = [r for r in records if r["kind"] == "alert"]
    assert len(alerts) == 1  # latched: one alert, not one per flush
    assert alerts[0]["rule"] == "serve_p99"
    finals = [r for r in records if r["kind"] == "metrics"]
    assert finals and finals[-1]["counters"]["obs/alerts_fired"] == 1.0
    dumps = _os.listdir(cfg.flight_dir)
    assert any("alert_serve_p99" in d for d in dumps), dumps


def test_slo_evaluation_driven_from_submit_path(tmp_path):
    """An outage in which no flush ever completes must still evaluate the
    SLO rules: the submit path drives a throttled evaluation, so a
    reject-rate rule can fire while the pipeline is wedged."""
    import types

    from mpi_pytorch_tpu.config import Config
    from mpi_pytorch_tpu.serve import InferenceServer

    cfg = Config(
        model_name="resnet18", num_classes=32, width=32, height=32,
        synthetic_data=True, compute_dtype="float32", serve_buckets="8",
        serve_max_wait_ms=50.0, serve_topk=1, serve_queue_depth=2,
        metrics_file="", log_file="", eval_log_file="",
    )
    cfg.validate_config()
    server = InferenceServer(cfg, load_checkpoint=False)
    try:
        calls = []
        server._monitor = types.SimpleNamespace(
            evaluate=lambda **kw: calls.append(1)
        )
        server._slo_eval_interval = 0.0  # un-throttle for the test
        img = np.zeros((32, 32, 3), np.uint8)
        futs = []
        for _ in range(6):  # queue_depth 2 + long max_wait: some reject
            try:
                futs.append(server.submit(img))
            except Exception:  # noqa: BLE001 — QueueFullError is the point
                pass
        assert calls, "submit path never evaluated the SLO rules"
        for f in futs:
            f.result(timeout=120)
    finally:
        server._monitor = None
        server.close()


def test_init_failure_does_not_orphan_pipeline_threads(tmp_path, monkeypatch):
    """A construction failure AFTER the worker threads start (an HTTP port
    bind, here simulated) must tear the pipeline down — a retry loop
    around a failing bind must not accumulate live serve-batch threads."""
    import threading

    import mpi_pytorch_tpu.serve.server as server_mod
    from mpi_pytorch_tpu.config import Config
    from mpi_pytorch_tpu.serve import InferenceServer

    def exploding_http(*a, **kw):
        raise OSError("injected: port already in use")

    monkeypatch.setattr(server_mod, "ObsHTTPServer", exploding_http, raising=False)
    # The import inside __init__ resolves via the module; patch there too.
    import mpi_pytorch_tpu.serve.http as http_mod

    monkeypatch.setattr(http_mod, "ObsHTTPServer", exploding_http)
    cfg = Config(
        model_name="resnet18", num_classes=32, width=32, height=32,
        synthetic_data=True, compute_dtype="float32", serve_buckets="4",
        serve_topk=1, serve_metrics_port=-1,
        metrics_file="", log_file="", eval_log_file="",
        trace_file=str(tmp_path / "trace.json"),
    )
    cfg.validate_config()
    before = {t.name for t in threading.enumerate() if t.name.startswith("serve-")}
    with pytest.raises(OSError, match="port already in use"):
        InferenceServer(cfg, load_checkpoint=False)
    leaked = [
        t for t in threading.enumerate()
        if t.name.startswith("serve-") and t.name not in before and t.is_alive()
    ]
    assert not leaked, leaked
    assert (tmp_path / "trace.json").exists()  # sinks still flushed
