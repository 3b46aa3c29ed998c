"""Cross-pod hierarchical training suite (ISSUE 15 / ROADMAP item 5): the
nested ``(pod, ici)`` data axis, the two-phase ICI/DCN collectives, within-pod
ZeRO placement, the per-axis byte ledger, pod-count-change elastic resume,
and the slow-DCN fault gate — all on the 8-device CPU mesh nested as 2×4
"pods" (the CPU twin of a real multi-pod DCN world).

Parity discipline matches tests/test_grad_sync.py: the hierarchical step
reduces the SAME elements as the flat step in a different order, so params
and metrics agree to float32 tolerance across optimizers × {ZeRO, buckets}.
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
from jax import lax
from jax.sharding import PartitionSpec as P

from mpi_pytorch_tpu.config import Config, MeshConfig, parse_config
from mpi_pytorch_tpu.parallel import collectives
from mpi_pytorch_tpu.parallel.collectives import LEDGER, axis_kind
from mpi_pytorch_tpu.parallel.compat import shard_map
from mpi_pytorch_tpu.parallel.mesh import (
    create_mesh,
    data_axis_names,
    data_axis_size,
    is_hierarchical,
    model_axis_name,
    pod_shape,
    shard_batch,
    zero_shard_axis,
)
from mpi_pytorch_tpu.train.state import (
    TrainState,
    make_optimizer,
    zero_shard_opt_state,
)
from mpi_pytorch_tpu.train.step import (
    grad_bucket_plan,
    hier_dcn_overlap_frac,
    make_spmd_train_step,
    place_state_on_mesh,
)

BATCH = 16
NUM_CLASSES = 7  # not divisible by anything relevant: every leaf pads


def _mlp_state(optimizer="adam", seed=0):
    """BN-free MLP with UNEVEN leaf sizes (13, 7) so every leaf exercises
    the flatten-pad-slice path of both the flat and the nested layouts."""
    import flax.linen as nn

    class MLP(nn.Module):
        @nn.compact
        def __call__(self, x, train=False):
            x = x.reshape(x.shape[0], -1)
            x = nn.relu(nn.Dense(13, name="body")(x))
            return nn.Dense(NUM_CLASSES, name="head")(x)

    model = MLP()
    variables = model.init(
        jax.random.PRNGKey(seed), jnp.zeros((1, 8, 8, 3)), train=True
    )
    tx = make_optimizer(
        1e-2, optimizer=optimizer,
        weight_decay=0.01 if optimizer == "adamw" else 0.0,
    )
    return TrainState.create(
        apply_fn=model.apply, variables=variables, tx=tx,
        rng=jax.random.PRNGKey(seed + 1),
    )


def _batch():
    rng = np.random.default_rng(0)
    images = rng.normal(size=(BATCH, 8, 8, 3)).astype(np.float32)
    labels = (np.arange(BATCH) % NUM_CLASSES).astype(np.int32)
    return images, labels


def _run(mesh, batch, *, optimizer="adam", zero=False, bucket_mb=0.0, steps=3):
    state = place_state_on_mesh(_mlp_state(optimizer), mesh)
    if zero:
        state = state.replace(opt_state=zero_shard_opt_state(state.opt_state, mesh))
    step = make_spmd_train_step(
        mesh, jnp.float32, zero_opt_state=zero, grad_bucket_mb=bucket_mb
    )
    metrics = []
    for _ in range(steps):
        state, m = step(state, shard_batch(batch, mesh))
        metrics.append(
            {k: float(v) for k, v in m.items() if k in ("loss", "grad_norm")}
        )
    return state, metrics


def _assert_trees_close(a, b, atol):
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=atol)


# ---------------------------------------------------------------------------
# Nested-mesh factoring invariants
# ---------------------------------------------------------------------------


def test_nested_mesh_factoring():
    """pods=2 on 8 devices → (pod=2, ici=4, model=1), pod-MAJOR packing
    (device (p, i) is flat device p*ici+i, so an ici group is contiguous
    and never straddles a pod boundary), and the helper vocabulary agrees."""
    mesh = create_mesh(MeshConfig(pods=2))
    assert mesh.axis_names == ("pod", "ici", "model")
    assert dict(mesh.shape) == {"pod": 2, "ici": 4, "model": 1}
    assert is_hierarchical(mesh)
    assert data_axis_names(mesh) == ("pod", "ici")
    assert data_axis_size(mesh) == 8
    assert pod_shape(mesh) == (2, 4)
    assert zero_shard_axis(mesh) == ("ici", 4)
    assert model_axis_name(mesh) == "model"
    devices = jax.devices()
    for p in range(2):
        for i in range(4):
            assert mesh.devices[p, i, 0] == devices[p * 4 + i]


def test_flat_mesh_unchanged_when_pods_1():
    mesh = create_mesh(MeshConfig(pods=1))
    assert mesh.axis_names == ("data", "model")
    assert not is_hierarchical(mesh)
    assert data_axis_names(mesh) == ("data",)
    assert pod_shape(mesh) == (1, 8)
    assert zero_shard_axis(mesh) == ("data", 8)
    assert model_axis_name(mesh) == "model"


def test_nested_mesh_rejects_bad_factorings():
    with pytest.raises(ValueError, match="not divisible by pods"):
        create_mesh(MeshConfig(pods=3))
    with pytest.raises(ValueError, match="pipe"):
        create_mesh(MeshConfig(pods=2, pipe_parallel=2))


# ---------------------------------------------------------------------------
# Two-phase ≡ single-phase collective parity on raw arrays
# ---------------------------------------------------------------------------


def test_hier_collectives_match_fused_on_raw_arrays():
    """hier_psum / hier_pmean ≡ one fused psum/pmean over both axes, and
    hier_reduce_scatter_mean + hier_all_gather reassemble the exact global
    mean — on an odd-sized leaf (13) that forces ici padding."""
    mesh = create_mesh(MeshConfig(pods=2))

    def body(batch):
        g = batch.mean(0)  # per-shard value, differs per shard
        fused_sum = lax.psum(g, ("pod", "ici"))
        fused_mean = lax.pmean(g, ("pod", "ici"))
        h_sum = collectives.hier_psum(g)
        h_mean = collectives.hier_pmean(g)
        sl = collectives.hier_reduce_scatter_mean(g)
        rs_ag = collectives.hier_all_gather(sl)[: g.size].reshape(g.shape)
        return fused_sum, fused_mean, h_sum, h_mean, rs_ag

    data = np.arange(16 * 13, dtype=np.float32).reshape(16, 13)
    out = jax.jit(
        shard_map(
            body, mesh=mesh, in_specs=P(("pod", "ici")),
            out_specs=(P(), P(), P(), P(), P()), check_vma=False,
        )
    )(data)
    fused_sum, fused_mean, h_sum, h_mean, rs_ag = map(np.asarray, out)
    np.testing.assert_allclose(h_sum, fused_sum, rtol=1e-6)
    np.testing.assert_allclose(h_mean, fused_mean, rtol=1e-6)
    np.testing.assert_allclose(rs_ag, fused_mean, rtol=1e-6)


# ---------------------------------------------------------------------------
# Full-step parity: hierarchical ≡ flat across optimizers × {ZeRO, buckets}
# ---------------------------------------------------------------------------

LEVERS = {
    "fused": dict(zero=False, bucket_mb=0.0),
    "zero": dict(zero=True, bucket_mb=0.0),
    "buckets": dict(zero=False, bucket_mb=0.0001),  # tiny cap → many buckets
    "both": dict(zero=True, bucket_mb=0.0001),
}


@pytest.mark.parametrize("optimizer", ["adam", "adamw", "sgd"])
@pytest.mark.parametrize("lever", sorted(LEVERS))
def test_hierarchical_matches_flat_step(optimizer, lever):
    """The acceptance parity: the 2×4 nested step ≡ the flat 8-shard fused
    baseline after 3 steps — params, loss, grad_norm — for every optimizer
    and every lever combination (the hierarchical sync only reorders the
    same reductions)."""
    flat = create_mesh(MeshConfig())
    nested = create_mesh(MeshConfig(pods=2))
    batch = _batch()
    base, base_m = _run(flat, batch, optimizer=optimizer)
    hier, hier_m = _run(nested, batch, optimizer=optimizer, **LEVERS[lever])
    _assert_trees_close(base.params, hier.params, atol=1e-5)
    for m0, m1 in zip(base_m, hier_m):
        np.testing.assert_allclose(m0["loss"], m1["loss"], rtol=1e-5)
        np.testing.assert_allclose(m0["grad_norm"], m1["grad_norm"], rtol=1e-4)


def test_zero_shards_place_within_pod():
    """The ZeRO placement rule on the nested mesh: [ici, chunk] leaves
    sharded over ``ici`` and REPLICATED across pods — devices at the same
    ici index in different pods hold bit-identical slice data (that pod
    symmetry is what makes the param all_gather DCN-free)."""
    mesh = create_mesh(MeshConfig(pods=2))
    state = place_state_on_mesh(_mlp_state(), mesh)
    sharded = zero_shard_opt_state(state.opt_state, mesh)
    checked = 0
    for leaf in jax.tree_util.tree_leaves(sharded):
        if not (hasattr(leaf, "ndim") and leaf.ndim > 0):
            continue
        assert leaf.shape[0] == 4  # ici size, NOT the 8-way data size
        by_index: dict[int, list] = {}
        for s in leaf.addressable_shards:
            row = s.index[0].start or 0
            by_index.setdefault(row, []).append(np.asarray(s.data))
        assert len(by_index) == 4
        for row, copies in by_index.items():
            assert len(copies) == 2  # one per pod
            np.testing.assert_array_equal(copies[0], copies[1])
        checked += 1
    assert checked  # moments existed to check


# ---------------------------------------------------------------------------
# Per-axis byte ledger
# ---------------------------------------------------------------------------


def test_ledger_axis_kinds_and_snapshot():
    assert axis_kind("ici") == "ici"
    assert axis_kind("data") == "ici"  # a flat mesh is one pod
    assert axis_kind("pod") == "dcn"
    assert axis_kind(("pod", "ici")) == "dcn"
    ledger = collectives.TrafficLedger()
    ledger.add("ici", "all_gather", 100)
    ledger.add("dcn", "all_reduce", 10)
    ledger.add("dcn", "all_reduce", 5)
    snap = ledger.snapshot()
    assert snap["ici"] == {"bytes": 100, "ops": 1, "by_op": {"all_gather": 100}}
    assert snap["dcn"]["bytes"] == 15 and snap["dcn"]["ops"] == 2
    ledger.reset()
    assert ledger.snapshot()["dcn"]["bytes"] == 0


def test_cross_pod_grad_bytes_shrink_one_over_ici():
    """THE acceptance accounting: per-device cross-pod (DCN) gradient bytes
    on the nested 2×4 mesh ≤ 1/ici_size of what the flat fused allreduce
    moves — for every lever combination — and a flat mesh books ZERO DCN
    bytes. Recorded at trace time, so one lower() is exactly one step."""
    flat = create_mesh(MeshConfig())
    nested = create_mesh(MeshConfig(pods=2))
    batch = _batch()
    _, ici = pod_shape(nested)

    def step_bytes(mesh, zero, bucket_mb):
        state = place_state_on_mesh(_mlp_state(), mesh)
        if zero:
            state = state.replace(
                opt_state=zero_shard_opt_state(state.opt_state, mesh)
            )
        step = make_spmd_train_step(
            mesh, jnp.float32, zero_opt_state=zero, grad_bucket_mb=bucket_mb
        )
        LEDGER.reset()
        step.lower(state, shard_batch(batch, mesh))
        return LEDGER.snapshot()

    flat_traffic = step_bytes(flat, zero=False, bucket_mb=0.0)
    assert flat_traffic["dcn"]["bytes"] == 0  # a flat mesh never hits DCN
    flat_grad_bytes = flat_traffic["ici"]["by_op"]["all_reduce"]
    assert flat_grad_bytes > 0

    for name, lever in sorted(LEVERS.items()):
        traffic = step_bytes(nested, **lever)
        dcn = traffic["dcn"]["bytes"]
        assert 0 < dcn <= flat_grad_bytes / ici, (name, dcn, flat_grad_bytes)
        # The cross-pod phase is the ONLY thing on the DCN: params gather
        # within-pod (all_gather never appears in the dcn bucket).
        assert set(traffic["dcn"]["by_op"]) == {"all_reduce"}, name
        assert traffic["ici"]["bytes"] > 0, name


def test_dcn_overlap_frac_estimate():
    params = {"a": np.zeros((4096,), np.float32), "b": np.zeros((64,), np.float32)}
    plan = grad_bucket_plan(params, 0.001)
    assert len(plan) > 1
    frac = hier_dcn_overlap_frac(params, plan)
    assert 0.0 < frac < 1.0
    # one fat bucket = nothing issued early = no DCN overlap
    all_leaves = list(range(len(jax.tree_util.tree_leaves(params))))
    assert hier_dcn_overlap_frac(params, [all_leaves]) == 0.0


# ---------------------------------------------------------------------------
# Config validation + CLI
# ---------------------------------------------------------------------------


def test_config_rejects_pods_outside_spmd():
    with pytest.raises(ValueError, match="spmd_mode"):
        Config(mesh=MeshConfig(pods=2)).validate_config()
    with pytest.raises(ValueError, match="pods"):
        Config(spmd_mode=True, mesh=MeshConfig(pods=0)).validate_config()
    # the supported composition
    Config(
        spmd_mode=True, zero_opt_state=True, grad_sync_buckets=25.0,
        mesh=MeshConfig(pods=2),
    ).validate_config()


def test_mesh_pods_cli_alias():
    cfg = parse_config(["--mesh-pods", "2", "--spmd-mode", "true"])
    assert cfg.mesh.pods == 2
    cfg = parse_config(["--mesh.pods", "2", "--spmd-mode", "true"])
    assert cfg.mesh.pods == 2


# ---------------------------------------------------------------------------
# Slow-DCN fault gate
# ---------------------------------------------------------------------------


def test_dcn_delay_gate_bites_only_hierarchical(monkeypatch):
    from mpi_pytorch_tpu.train.elastic import FaultInjector
    from mpi_pytorch_tpu.utils.env import FAULT_GATES

    assert "MPT_FAULT_DCN_DELAY_MS" in FAULT_GATES  # registered (hygiene)
    monkeypatch.setenv("MPT_FAULT_DCN_DELAY_MS", "120")
    injector = FaultInjector()
    assert injector.active
    t0 = time.perf_counter()
    injector.maybe_dcn_delay(hierarchical=False)  # flat mesh: no DCN phase
    assert time.perf_counter() - t0 < 0.05
    t0 = time.perf_counter()
    injector.maybe_dcn_delay(hierarchical=True)
    assert time.perf_counter() - t0 >= 0.1
    monkeypatch.delenv("MPT_FAULT_DCN_DELAY_MS")
    assert not FaultInjector().active


# ---------------------------------------------------------------------------
# Regression-gate trend-line identity (satellite: pods×ici keys the line)
# ---------------------------------------------------------------------------


def test_check_regression_keys_mesh_topology(tmp_path):
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
    try:
        import check_regression
    finally:
        sys.path.pop(0)

    def cell(rnd, value, mesh=None):
        parsed = {"metric": "resnet18 train img/s", "value": value}
        if mesh is not None:
            parsed["mesh"] = mesh
        (tmp_path / f"BENCH_r{rnd:02d}.json").write_text(
            json.dumps({"rc": 0, "parsed": parsed})
        )

    # A hierarchical cell at half the flat throughput is a NEW trend line,
    # never a regression of the flat baseline...
    cell(1, 100.0)
    cell(2, 50.0, mesh="p2xi4")
    assert check_regression.check_bench(str(tmp_path), 10.0) == []
    # ...but a drop WITHIN the hierarchical line still fails the gate.
    cell(3, 30.0, mesh="p2xi4")
    violations = check_regression.check_bench(str(tmp_path), 10.0)
    assert len(violations) == 1 and "p2xi4" in violations[0]
    # And the flat line keeps judging itself: a flat recovery is clean.
    cell(4, 99.0)
    violations = check_regression.check_bench(str(tmp_path), 10.0)
    assert len(violations) == 1  # still only the hierarchical drop


# ---------------------------------------------------------------------------
# The tier-1 dryrun leg: full trainer on the nested CPU mesh + pod-count-
# change elastic resume (2×4 → flat)
# ---------------------------------------------------------------------------


def _dryrun_cfg(tmp_path, **kw):
    c = Config()
    c.debug = True
    c.debug_sample_size = 48
    c.train_csv = os.path.join(os.path.dirname(__file__), "..", "data", "train_sample.csv")
    c.test_csv = os.path.join(os.path.dirname(__file__), "..", "data", "test_sample.csv")
    c.synthetic_data = True
    c.model_name = "resnet18"
    c.num_classes = 200
    c.batch_size = 16
    c.width = c.height = 16
    c.num_epochs = 2
    c.compute_dtype = "float32"
    c.checkpoint_dir = os.path.join(str(tmp_path), "ckpt")
    c.log_file = os.path.join(str(tmp_path), "training.log")
    c.metrics_file = os.path.join(str(tmp_path), "metrics.jsonl")
    c.trace_file = os.path.join(str(tmp_path), "trace.json")
    c.validate = False
    c.loader_workers = 2
    c.log_every_steps = 0
    c.step_metrics = True
    c.spmd_mode = True
    c.zero_opt_state = True
    c.grad_sync_buckets = 0.05
    c.mesh.pods = 2
    for k, v in kw.items():
        if k == "pods":
            c.mesh.pods = v
        else:
            setattr(c, k, v)
    c.validate_config()
    return c


def test_hierarchical_dryrun_end_to_end(tmp_path):
    """THE tier-1 dryrun leg (acceptance): the full trainer on the 8-device
    CPU mesh nested 2×4 with ZeRO + buckets — zero steady-state recompiles,
    ``dcn_overlap_frac`` stamped on every step record, per-bucket
    ``grad_bucket``/``dcn`` tracer spans + the collective-traffic instant,
    schema-clean stream — then a POD-COUNT-CHANGE elastic resume (2×4 →
    flat 8) that re-chunks the ZeRO layout and recompiles nothing
    steady-state."""
    from mpi_pytorch_tpu.obs.schema import validate_jsonl
    from mpi_pytorch_tpu.train.trainer import train

    summary = train(_dryrun_cfg(tmp_path))
    assert summary.epochs_run == 2

    cfg = _dryrun_cfg(tmp_path)
    records = [json.loads(line) for line in open(cfg.metrics_file)]
    steps = [r for r in records if r["kind"] == "step"]
    assert steps
    for rec in steps:
        assert rec["recompiles"] == 0  # zero steady-state compiles
        assert 0.0 < rec["overlap_frac"] < 1.0
        assert 0.0 < rec["dcn_overlap_frac"] < 1.0
    assert validate_jsonl(cfg.metrics_file) == []

    trace = json.load(open(cfg.trace_file))
    names = [e["name"] for e in trace["traceEvents"]]
    assert "grad_bucket" in names and "dcn" in names
    traffic = [e for e in trace["traceEvents"] if e["name"] == "collective_traffic"]
    assert traffic and traffic[0]["args"]["dcn_bytes_per_step"] > 0
    assert (
        traffic[0]["args"]["dcn_bytes_per_step"]
        < traffic[0]["args"]["ici_bytes_per_step"]
    )

    # Pod-count change: resume the 2×4 checkpoint on the FLAT 8-device mesh
    # (ZeRO re-chunks 4 → 8 through the gathered-on-save payload).
    resumed = train(
        _dryrun_cfg(tmp_path, pods=1, from_checkpoint=True, num_epochs=3)
    )
    assert resumed.epochs_run == 1
    records = [json.loads(line) for line in open(cfg.metrics_file)]
    resumes = [r for r in records if r["kind"] == "resume"]
    assert resumes
    assert resumes[-1]["from_mesh"].count("pod=2")
    assert resumes[-1]["to_mesh"] == "data=8,model=1"
    assert resumes[-1]["zero_shards_from"] == 4  # the WITHIN-POD ici size
    assert resumes[-1]["zero_shards_to"] == 8
    post = [
        r for r in records
        if r["kind"] == "step" and r["ts"] >= resumes[-1]["ts"]
    ]
    assert post and all(r["recompiles"] == 0 for r in post)
    assert validate_jsonl(cfg.metrics_file) == []


@pytest.mark.slow
def test_pod_count_change_resume_2x4_to_1x4(tmp_path):
    """The satellite's exact scenario on REAL world-size change: train on
    the 8-device mesh nested 2×4, then resume in a SUBPROCESS forced to 4
    CPU devices as the flat 1×4 world. The ici size is 4 on both sides, so
    the ZeRO shard layout is PINNED across the pod-count change (the resume
    record states 4 → 4: no re-chunk, pure re-placement)."""
    train_cfg = _dryrun_cfg(tmp_path)
    from mpi_pytorch_tpu.train.trainer import train

    assert train(train_cfg).epochs_run == 2

    env = dict(os.environ)
    flags = [
        f for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f
    ]
    env["XLA_FLAGS"] = " ".join(flags + ["--xla_force_host_platform_device_count=4"])
    env["JAX_PLATFORMS"] = "cpu"
    repo = os.path.join(os.path.dirname(__file__), "..")
    subprocess.run(
        [
            sys.executable, "-m", "mpi_pytorch_tpu.train",
            "--debug", "true", "--debug-sample-size", "48",
            "--num-classes", "200", "--batch-size", "16",
            "--width", "16", "--height", "16", "--synthetic-data", "true",
            "--validate", "false", "--compute-dtype", "float32",
            "--loader-workers", "2", "--log-every-steps", "0",
            "--spmd-mode", "true", "--zero-opt-state", "true",
            "--grad-sync-buckets", "0.05", "--step-metrics", "true",
            "--num-epochs", "3", "--from-checkpoint", "true",
            "--checkpoint-dir", train_cfg.checkpoint_dir,
            "--log-file", train_cfg.log_file,
            "--metrics-file", train_cfg.metrics_file,
            "--train-csv", train_cfg.train_csv,
            "--test-csv", train_cfg.test_csv,
        ],
        env=env, cwd=repo, check=True,
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )
    records = [json.loads(line) for line in open(train_cfg.metrics_file)]
    resumes = [r for r in records if r["kind"] == "resume"]
    assert resumes and resumes[-1]["from_devices"] == 8
    assert resumes[-1]["to_devices"] == 4
    # ZeRO shards pinned: within-pod ici=4 before, flat data=4 after.
    assert resumes[-1]["zero_shards_from"] == 4
    assert resumes[-1]["zero_shards_to"] == 4
    post = [
        r for r in records
        if r["kind"] == "step" and r["ts"] >= resumes[-1]["ts"]
    ]
    assert post and all(r["recompiles"] == 0 for r in post)
