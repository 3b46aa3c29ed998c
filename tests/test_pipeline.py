"""GPipe pipeline parallelism vs the un-pipelined stacked forward on the
8-device CPU mesh — values, gradients, remat agreement, and the shape guards.

The correctness property: streaming M microbatches through S ppermute-linked
stages computes exactly ``stage_S(...stage_1(x))`` per example, and grads
through the schedule equal grads of the plain composition.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from mpi_pytorch_tpu.parallel.pipeline import (
    pipeline_forward,
    stack_stage_params,
)

N_STAGES = 8
D = 16


@pytest.fixture(scope="module")
def mesh():
    dev = np.asarray(jax.devices()[:N_STAGES]).reshape(N_STAGES, 1)
    return Mesh(dev, ("pipe", "unused"))


def residual_mlp_stage(params, x):
    """One homogeneous stage: residual two-layer MLP, [mb, D] → [mb, D]."""
    h = jax.nn.gelu(x @ params["w1"] + params["b1"])
    return x + h @ params["w2"] + params["b2"]


def _stage_params(seed):
    rng = np.random.default_rng(seed)
    return {
        "w1": jnp.asarray(rng.standard_normal((D, 4 * D)) * 0.1, jnp.float32),
        "b1": jnp.zeros((4 * D,), jnp.float32),
        "w2": jnp.asarray(rng.standard_normal((4 * D, D)) * 0.1, jnp.float32),
        "b2": jnp.zeros((D,), jnp.float32),
    }


@pytest.fixture(scope="module")
def stacked():
    return stack_stage_params([_stage_params(s) for s in range(N_STAGES)])


def stacked_reference(stacked_params, x):
    """Un-pipelined composition of all stages on one device."""
    for s in range(N_STAGES):
        params_s = jax.tree_util.tree_map(lambda p: p[s], stacked_params)
        x = residual_mlp_stage(params_s, x)
    return x


def _x(b=32, seed=1):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal((b, D)), jnp.float32)


@pytest.mark.parametrize("num_micro", [4, 8])
def test_pipeline_matches_stacked_forward(mesh, stacked, num_micro):
    x = _x()
    got = pipeline_forward(
        stacked, x, mesh, stage_fn=residual_mlp_stage, num_microbatches=num_micro
    )
    want = stacked_reference(stacked, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


@pytest.mark.slow
def test_pipeline_grads_match_stacked(mesh, stacked):
    x = _x(seed=2)
    y = jnp.asarray(np.random.default_rng(3).standard_normal(x.shape), jnp.float32)

    def loss_pp(params, x_):
        out = pipeline_forward(
            params, x_, mesh, stage_fn=residual_mlp_stage, num_microbatches=8
        )
        return jnp.mean((out - y) ** 2)

    def loss_ref(params, x_):
        return jnp.mean((stacked_reference(params, x_) - y) ** 2)

    gp, gxp = jax.grad(loss_pp, argnums=(0, 1))(stacked, x)
    gr, gxr = jax.grad(loss_ref, argnums=(0, 1))(stacked, x)
    for a, b in zip(jax.tree_util.tree_leaves(gp), jax.tree_util.tree_leaves(gr)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-5, atol=5e-5)
    np.testing.assert_allclose(np.asarray(gxp), np.asarray(gxr), rtol=5e-5, atol=5e-5)


def test_pipeline_remat_matches_plain(mesh, stacked):
    """remat=True re-derives stage internals in the backward; same numbers."""
    x = _x(seed=4)

    def loss(params, remat):
        out = pipeline_forward(
            params, x, mesh, stage_fn=residual_mlp_stage,
            num_microbatches=8, remat=remat,
        )
        return jnp.sum(out * out)

    g_plain = jax.grad(functools.partial(loss, remat=False))(stacked)
    g_remat = jax.grad(functools.partial(loss, remat=True))(stacked)
    for a, b in zip(
        jax.tree_util.tree_leaves(g_plain), jax.tree_util.tree_leaves(g_remat)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-6)


def test_pipeline_composes_with_dp():
    """PP×DP on a 4-stage × 2-data mesh: values AND grads equal the
    un-pipelined single-device composition (shard_map's transpose supplies
    the gradient psum over the data axis for the pipe-sharded params)."""
    dev = np.asarray(jax.devices()[:8]).reshape(4, 2)
    mesh2d = Mesh(dev, ("pipe", "data"))
    stacked4 = stack_stage_params([_stage_params(s) for s in range(4)])

    def ref4(params, x):
        for s in range(4):
            x = residual_mlp_stage(
                jax.tree_util.tree_map(lambda p: p[s], params), x
            )
        return x

    x = _x(b=32, seed=9)
    got = pipeline_forward(
        stacked4, x, mesh2d, stage_fn=residual_mlp_stage,
        num_microbatches=8, data_axis="data",
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref4(stacked4, x)), rtol=2e-5, atol=2e-5
    )

    y = jnp.asarray(np.random.default_rng(10).standard_normal(x.shape), jnp.float32)

    def loss_pp(params):
        out = pipeline_forward(
            params, x, mesh2d, stage_fn=residual_mlp_stage,
            num_microbatches=8, data_axis="data",
        )
        return jnp.mean((out - y) ** 2)

    g_pp = jax.grad(loss_pp)(stacked4)
    g_rf = jax.grad(lambda p: jnp.mean((ref4(p, x) - y) ** 2))(stacked4)
    for a, b in zip(jax.tree_util.tree_leaves(g_pp), jax.tree_util.tree_leaves(g_rf)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-5, atol=5e-5)


# --- real-model stages: the ViT encoder block as a pipeline stage ---------

VIT_BLOCK = dict(num_heads=4, mlp_dim=32)
VIT_HIDDEN = 16


def vit_block_stage(params, x):
    """One ViT EncoderBlock as a pipeline stage: [mb, S, hidden] →
    [mb, S, hidden] (the homogeneous-stage property models/vit.py documents)."""
    from mpi_pytorch_tpu.models.vit import EncoderBlock

    return EncoderBlock(**VIT_BLOCK).apply({"params": params}, x, train=False)


@pytest.mark.slow
def test_pipeline_runs_vit_encoder_blocks(mesh):
    """An 8-deep ViT encoder split one-block-per-stage over the pipe axis
    equals running the blocks sequentially on one device."""
    from mpi_pytorch_tpu.models.vit import EncoderBlock

    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal((16, 8, VIT_HIDDEN)), jnp.float32)
    block = EncoderBlock(**VIT_BLOCK)
    per_stage = [
        block.init({"params": jax.random.PRNGKey(s)}, x[:2], train=False)["params"]
        for s in range(N_STAGES)
    ]
    stacked_blocks = stack_stage_params(per_stage)

    got = pipeline_forward(
        stacked_blocks, x, mesh, stage_fn=vit_block_stage, num_microbatches=8
    )
    want = x
    for params in per_stage:
        want = block.apply({"params": params}, want, train=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


# --- PP as a trainer capability (--pp-stages): parallel/pp_vit.py ---------


def _tiny_vit(num_classes=7, depth=4, **kw):
    from mpi_pytorch_tpu.models.vit import VisionTransformer

    return VisionTransformer(
        num_classes=num_classes, patch_size=4, hidden=16, depth=depth,
        num_heads=2, mlp_dim=32, dtype=jnp.float32, param_dtype=jnp.float32,
        **kw,
    )


def _pp_mesh(stages=4):
    from mpi_pytorch_tpu.config import MeshConfig
    from mpi_pytorch_tpu.parallel.mesh import create_mesh

    return create_mesh(MeshConfig(pipe_parallel=stages))


@pytest.mark.slow
def test_pp_apply_matches_model_apply():
    """make_pp_apply over the UNCHANGED param tree reproduces model.apply
    exactly: logits and per-param grads — pipelining is an execution
    strategy, not a different model."""
    from mpi_pytorch_tpu.parallel.pp_vit import make_pp_apply

    model = _tiny_vit()
    x = jnp.asarray(
        np.random.default_rng(0).standard_normal((16, 16, 16, 3)), jnp.float32
    )
    variables = model.init({"params": jax.random.PRNGKey(0)}, x[:2], train=False)
    mesh = _pp_mesh(4)
    pp_apply = make_pp_apply(model, mesh, num_microbatches=8)

    got = pp_apply(variables, x, train=False)
    want = model.apply(variables, x, train=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)

    labels = jnp.asarray(np.random.default_rng(1).integers(0, 7, 16), jnp.int32)

    def ce(apply_fn):
        def loss(params):
            logits = apply_fn({"params": params}, x, train=False)
            import optax

            return jnp.mean(
                optax.softmax_cross_entropy_with_integer_labels(logits, labels)
            )

        return jax.grad(loss)(variables["params"])

    g_pp, g_ref = ce(pp_apply), ce(model.apply)
    assert jax.tree_util.tree_structure(g_pp) == jax.tree_util.tree_structure(g_ref)
    for a, b in zip(jax.tree_util.tree_leaves(g_pp), jax.tree_util.tree_leaves(g_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4)


@pytest.mark.slow
def test_pp_train_step_matches_unpipelined():
    """The FULL jitted train step (loss, grads, Adam update) with the PP
    apply_fn produces the same updated params as the unpipelined step —
    the --pp-stages ≡ unpipelined trajectory property, two steps deep."""
    import optax

    from mpi_pytorch_tpu.parallel.mesh import shard_batch
    from mpi_pytorch_tpu.parallel.pp_vit import make_pp_apply
    from mpi_pytorch_tpu.train.state import TrainState
    from mpi_pytorch_tpu.train.step import make_train_step

    model = _tiny_vit()
    mesh = _pp_mesh(4)
    rng = np.random.default_rng(2)
    x = np.asarray(rng.standard_normal((16, 16, 16, 3)), np.float32)
    labels = np.asarray(rng.integers(0, 7, 16), np.int32)
    variables = model.init(
        {"params": jax.random.PRNGKey(3)}, jnp.asarray(x[:2]), train=False
    )

    def run(apply_fn):
        # Fresh buffers per run: the jitted step donates the state, so the
        # two runs must not share the init arrays. SGD, not Adam: Adam's
        # m/sqrt(v) normalization amplifies noise-level grad differences on
        # zero-grad params into O(lr) update differences, which would force
        # a vacuous tolerance — SGD keeps the comparison linear in grads.
        fresh = jax.tree_util.tree_map(jnp.array, variables)
        state = TrainState.create(
            apply_fn=apply_fn, variables=fresh, tx=optax.sgd(1e-2),
            rng=jax.random.PRNGKey(4),
        )
        step = make_train_step(compute_dtype=jnp.float32)
        batch = shard_batch((jnp.asarray(x), jnp.asarray(labels)), mesh)
        metrics = None
        for _ in range(2):
            state, metrics = step(state, batch)
        return state, metrics

    s_pp, m_pp = run(make_pp_apply(model, mesh, num_microbatches=8))
    s_ref, m_ref = run(model.apply)
    np.testing.assert_allclose(float(m_pp["loss"]), float(m_ref["loss"]), rtol=1e-5)
    for a, b in zip(
        jax.tree_util.tree_leaves(s_pp.params), jax.tree_util.tree_leaves(s_ref.params)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)


def test_pp_apply_guards():
    """make_pp_apply rejects the configurations whose semantics would
    silently differ: MoE blocks, SP attention, dropout, indivisible depth."""
    from mpi_pytorch_tpu.parallel.pp_vit import make_pp_apply

    mesh = _pp_mesh(4)
    with pytest.raises(ValueError, match="dense encoder blocks"):
        make_pp_apply(_tiny_vit(moe_every=2), mesh, num_microbatches=8)
    with pytest.raises(ValueError, match="dropout"):
        make_pp_apply(_tiny_vit(dropout=0.1), mesh, num_microbatches=8)
    with pytest.raises(ValueError, match="not divisible"):
        make_pp_apply(_tiny_vit(depth=6), mesh, num_microbatches=8)


def test_build_inference_wires_pp(tmp_path):
    """--pp-stages reaches the EVAL driver through the same apply_fn seam as
    the trainer (no silently-ignored flag)."""
    from mpi_pytorch_tpu.config import parse_config
    from mpi_pytorch_tpu.evaluate import build_inference

    cfg = parse_config([
        "--model-name", "vit_s16", "--pp-stages", "4", "--image-size", "32",
        "--num-classes", "1000", "--synthetic-data", "true",
    ])
    mesh, bundle, state, _ = build_inference(cfg)
    assert mesh.shape.get("pipe") == 4
    assert state.apply_fn is not bundle.model.apply  # the PP swap happened


@pytest.mark.slow
def test_pp_stages_config_trains_vit(tmp_path):
    """--pp-stages 4 end to end through parse_config/build_training/train on
    the 8-device mesh (pipe=4 × data=2): the PIPELINED multi-epoch loss
    trajectory matches the unpipelined trainer's on the identical config
    (SURVEY §2c's PP "Done =" criterion), and the checkpoint it writes
    restores into an UNPIPELINED run (PP-degree-independent checkpoints)."""
    from mpi_pytorch_tpu.config import parse_config
    from mpi_pytorch_tpu.train.trainer import train

    common = [
        "--debug", "true", "--debug-sample-size", "64",
        "--image-size", "32", "--batch-size", "16", "--num-classes", "1000",
        "--num-epochs", "2", "--synthetic-data", "true", "--validate", "false",
        "--compute-dtype", "float32",  # tight trajectory comparison
        "--log-file", str(tmp_path / "training.log"),
        "--metrics-file", str(tmp_path / "metrics.jsonl"),
    ]
    args = ["--model-name", "vit_s16", "--pp-stages", "4",
            "--checkpoint-dir", str(tmp_path / "ckpt")] + common
    cfg = parse_config(args)
    assert cfg.mesh.pipe_parallel == 4
    summary = train(cfg)
    assert summary.epochs_run == 2
    assert np.isfinite(summary.final_loss)

    # Same config WITHOUT pipelining: the per-epoch losses must match —
    # PP is an execution strategy, not a different trajectory.
    cfg_ref = parse_config(
        ["--model-name", "vit_s16",
         "--checkpoint-dir", str(tmp_path / "ckpt_ref")] + common
    )
    summary_ref = train(cfg_ref)
    np.testing.assert_allclose(
        summary.epoch_losses, summary_ref.epoch_losses, rtol=1e-4
    )

    # Resume the PP checkpoint WITHOUT pipelining: same param tree.
    cfg2 = parse_config(
        ["--model-name", "vit_s16",
         "--checkpoint-dir", str(tmp_path / "ckpt"),
         "--from-checkpoint", "true"] + common + ["--num-epochs", "3"]
    )
    assert cfg2.pp_stages == 1
    summary2 = train(cfg2)
    assert summary2.epochs_run == 1
    assert np.isfinite(summary2.final_loss)


def test_pipeline_rejects_bad_shapes(mesh, stacked):
    with pytest.raises(ValueError, match="not divisible"):
        pipeline_forward(
            stacked, _x(b=30), mesh,
            stage_fn=residual_mlp_stage, num_microbatches=7,
        )
    short = jax.tree_util.tree_map(lambda p: p[:4], stacked)
    with pytest.raises(ValueError, match="stage axis"):
        pipeline_forward(
            short, _x(), mesh, stage_fn=residual_mlp_stage, num_microbatches=4
        )


# ==========================================================================
# Serving: the pipe:K residency — stage-split per-bucket AOT executables
# with micro-batched inter-stage handoff (serve/pipeline.py, ISSUE 20).
# ==========================================================================


def _serve_cfg(num_classes=64, buckets="1,4"):
    from mpi_pytorch_tpu.config import Config

    cfg = Config(
        model_name="resnet18", num_classes=num_classes, width=32, height=32,
        synthetic_data=True, compute_dtype="float32",
        serve_buckets=buckets, serve_topk=3,
        metrics_file="", log_file="", eval_log_file="",
    )
    cfg.validate_config()
    return cfg


@pytest.fixture(scope="module")
def pipe_serving():
    """The module's one expensive build: pipe:2 stage-split executables on
    the nested (data, pipe) CPU mesh, plus the single-chip oracle over the
    SAME state, plus deterministic inputs and the oracle's predictions at
    every bucket. The compile listener is process-global, so the pipe set
    is rebaselined AFTER the oracle's warmup."""
    from mpi_pytorch_tpu.parallel.collectives import LEDGER
    from mpi_pytorch_tpu.parallel.mesh import create_pipe_serve_mesh
    from mpi_pytorch_tpu.serve.executables import BucketExecutables
    from mpi_pytorch_tpu.serve.pipeline import PipelineExecutables
    from mpi_pytorch_tpu.serve.server import InferenceServer

    cfg = _serve_cfg()
    state = InferenceServer._build_state(cfg, None, False)
    booked_before = LEDGER.snapshot()["ici"]["by_op"].get("pipe_handoff", 0)
    exe = PipelineExecutables(
        cfg, state, create_pipe_serve_mesh(2), microbatches=4
    )
    booked = (
        LEDGER.snapshot()["ici"]["by_op"].get("pipe_handoff", 0)
        - booked_before
    )
    exe.warmup()
    oracle_mesh = Mesh(
        np.asarray(jax.devices()[:1]).reshape(1, 1), ("data", "model")
    )
    oracle = BucketExecutables(cfg, state, oracle_mesh)
    oracle.warmup()
    exe.rebaseline()

    rng = np.random.default_rng(11)
    inputs, want = {}, {}
    for bucket in (1, 4):
        imgs = rng.normal(size=(bucket, 32, 32, 3)).astype(np.float32)
        inputs[bucket] = imgs
        rows = oracle.host_rows(bucket)
        oi = np.zeros((rows, 32, 32, 3), np.float32)
        oi[:bucket] = imgs
        ol = np.full((rows,), -1, np.int32)
        preds = np.asarray(jax.device_get(oracle(bucket, oracle.place(oi, ol))))
        want[bucket] = preds[:bucket]
    return {
        "cfg": cfg, "state": state, "exe": exe, "booked": booked,
        "inputs": inputs, "want": want,
    }


def _pipe_flush(exe, imgs):
    bucket = imgs.shape[0]
    labels = np.full((bucket,), -1, np.int32)
    return np.asarray(jax.device_get(exe(bucket, exe.place(imgs, labels))))


def test_pipe_cut_points_every_zoo_arch():
    """The generic cut derivation holds for EVERY servable architecture:
    the traced top-level chain is once-called and ends in "head", and
    plan_stages covers it contiguously in order with the head alone on the
    last stage — no per-arch table needed (PIPE_CUT_OVERRIDES stays empty,
    and this test is what turns a future non-linear arch into a loud
    failure instead of a wrong generic cut)."""
    from mpi_pytorch_tpu.models import initialize_model
    from mpi_pytorch_tpu.models.registry import available_models, model_spec
    from mpi_pytorch_tpu.serve.pipeline import (
        PIPE_CUT_OVERRIDES, plan_stages, trace_units,
    )

    assert PIPE_CUT_OVERRIDES == {}
    # Servable = takes images: the zoo refuses a model whose samples are tokens.
    for arch in (a for a in available_models() if model_spec(a).sample == "images"):
        size = model_spec(arch).required_size or 32
        model, _ = initialize_model(arch, 10)
        dummy = jax.ShapeDtypeStruct((1, size, size, 3), jnp.float32)
        rngs = {
            "params": jax.ShapeDtypeStruct((2,), jnp.uint32),
            "dropout": jax.ShapeDtypeStruct((2,), jnp.uint32),
        }
        shapes = jax.eval_shape(
            lambda r, x, m=model: m.init(r, x, train=True), rngs, dummy
        )
        units = trace_units(model.apply, shapes, dummy)
        names = [n for n, _ in units]
        assert names[-1] == "head", (arch, names[-3:])
        assert len(set(names)) == len(names), (arch, names)
        unit_bytes = {n: 1 for n in names}
        for k in (2, 3):
            if len(names) - 1 < k - 1:
                continue
            plan = plan_stages(names, unit_bytes, k, arch=arch)
            assert len(plan) == k, (arch, k, plan)
            assert [u for g in plan for u in g] == names, (arch, plan)
            assert plan[-1] == ["head"], (arch, plan)
            assert all(g for g in plan), (arch, plan)


def test_pipe_parity_with_single_chip_oracle(pipe_serving):
    """The tentpole's correctness core: the stage-split flush reproduces
    the unsplit single-chip forward bit-exactly at EVERY bucket, with zero
    compiles after warmup (per-bucket AOT — no steady-state tracing)."""
    exe = pipe_serving["exe"]
    for bucket in (1, 4):
        got = _pipe_flush(exe, pipe_serving["inputs"][bucket])
        assert np.array_equal(got, pipe_serving["want"][bucket]), bucket
    assert exe.compiles_since_warmup() == 0


def test_pipe_flush_stamp_and_bubble(pipe_serving):
    """Every flush stamps the measured pipeline facts: S/M as built (M_eff
    is the largest divisor of the bucket ≤ configured M — bucket 1
    degenerates to sequential M=1), bubble_frac in [0, 1), interstage
    bytes = Σ hop bytes × M, and monotonic per-stage wall windows in
    schedule order."""
    exe = pipe_serving["exe"]
    for bucket, m_want in ((1, 1), (4, 4)):
        _pipe_flush(exe, pipe_serving["inputs"][bucket])
        lf = exe.last_flush()
        assert lf["pipe_stages"] == 2
        assert lf["microbatches"] == m_want
        assert 0.0 <= lf["bubble_frac"] < 1.0
        plan = exe._plans[bucket]
        assert lf["interstage_bytes"] == sum(plan.hop_bytes) * m_want
        assert len(lf["stage_ms"]) == 2
        windows = lf["stage_windows"]
        assert len(windows) == 2
        for t0, t1 in windows:
            assert t0 <= t1
        # stage 1 cannot START before stage 0 dispatched its first micro.
        assert windows[1][0] >= windows[0][0]


def test_pipe_bubble_fraction_arithmetic():
    """The GPipe fill/drain arithmetic: (S−1)/(M+S−1), with the M=1 fully
    sequential and M→∞ amortized limits, and loud rejection of degenerate
    S/M."""
    from mpi_pytorch_tpu.serve.pipeline import pipeline_bubble_fraction

    assert pipeline_bubble_fraction(2, 4) == pytest.approx(0.2)
    assert pipeline_bubble_fraction(2, 1) == pytest.approx(0.5)
    assert pipeline_bubble_fraction(4, 1) == pytest.approx(0.75)
    assert pipeline_bubble_fraction(1, 8) == 0.0
    assert pipeline_bubble_fraction(4, 1000) < 0.003
    with pytest.raises(ValueError, match="stages >= 1"):
        pipeline_bubble_fraction(0, 4)
    with pytest.raises(ValueError, match="stages >= 1"):
        pipeline_bubble_fraction(2, 0)


def test_pipe_ledger_books_handoff_at_build(pipe_serving):
    """Inter-stage handoff is booked in the traffic LEDGER at build time
    (book-at-trace, PR 15): one micro-batch's boundary bytes per hop per
    bucket — and the flush-time ``interstage_bytes_per_flush`` quote is
    the max-bucket flow (Σ hop bytes × its M)."""
    exe = pipe_serving["exe"]
    per_hop = {
        b: sum(exe._plans[b].hop_bytes) for b in (1, 4)
    }
    assert all(v > 0 for v in per_hop.values())
    assert pipe_serving["booked"] == sum(per_hop.values())
    assert exe.interstage_bytes_per_flush() == max(
        per_hop[b] * exe._plans[b].m_eff for b in (1, 4)
    )


def test_pipe_microbatch_sweep_parity(pipe_serving):
    """M is a throughput knob, never a numerics knob: M=1 (fully
    sequential) and M=3 (non-divisor → M_eff=2) reproduce the oracle
    exactly at bucket 4, and the non-divisor request visibly degrades to
    the largest divisor in the flush stamp."""
    from mpi_pytorch_tpu.parallel.mesh import create_pipe_serve_mesh
    from mpi_pytorch_tpu.serve.pipeline import PipelineExecutables

    cfg = _serve_cfg(buckets="4")
    for m, m_eff in ((1, 1), (3, 2)):
        exe = PipelineExecutables(
            cfg, pipe_serving["state"], create_pipe_serve_mesh(2),
            microbatches=m,
        )
        exe.warmup()
        exe.rebaseline()
        got = _pipe_flush(exe, pipe_serving["inputs"][4])
        assert np.array_equal(got, pipe_serving["want"][4]), m
        lf = exe.last_flush()
        assert lf["microbatches"] == m_eff, (m, lf)
        assert exe.compiles_since_warmup() == 0
    # These builds moved the process-global compile counter past the
    # shared set's baseline — restore its zero-compile invariant.
    pipe_serving["exe"].rebaseline()


def test_pipe_slow_stage_gate_inflates_measured_bubble(pipe_serving):
    """The slow-stage drill: MPT_FAULT_STAGE_DELAY_MS stalls the target
    stage's dispatch window, the MEASURED bubble rises above the healthy
    flush's at the same bucket, the announce-once kind="fault" record is
    written exactly once, and numerics stay bit-identical.

    Dispatch walls on a shared CPU are noisy (one healthy flush can read
    0.34 and the next 0.48 of a possible 0.5), so the healthy side is the
    smallest of several flushes and the stalled side the larger of its two;
    what the delay MUST do — at least 30 ms inside stage 0's own window, in
    every stalled flush — is asserted as such."""
    import os

    exe = pipe_serving["exe"]
    healthy = []
    for _ in range(5):
        _pipe_flush(exe, pipe_serving["inputs"][4])
        healthy.append(exe.last_flush()["bubble_frac"])

    written = []

    class _Sink:
        def write(self, record):
            written.append(record)

    exe.set_obs(metrics=_Sink())
    os.environ["MPT_FAULT_STAGE_DELAY_MS"] = "30"
    os.environ["MPT_FAULT_STAGE_DELAY_STAGE"] = "0"
    stalled = []
    try:
        for _ in range(2):
            got = _pipe_flush(exe, pipe_serving["inputs"][4])
            stalled.append(exe.last_flush())
            assert np.array_equal(got, pipe_serving["want"][4])
    finally:
        del os.environ["MPT_FAULT_STAGE_DELAY_MS"]
        del os.environ["MPT_FAULT_STAGE_DELAY_STAGE"]
    for flush in stalled:
        start, end = flush["stage_windows"][0]
        assert flush["stage_ms"][0] >= 30.0 and end - start >= 0.030, flush
    assert max(f["bubble_frac"] for f in stalled) > min(healthy), (healthy, stalled)
    faults = [r for r in written if r.get("kind") == "fault"]
    assert len(faults) == 1, written  # announce-once, two stalled flushes
    assert faults[0]["reason"] == "injected_stage_delay"


def test_pipe_zoo_live_conversion_round_trip(tmp_path):
    """convert_residency replicated → pipe:2 → replicated on a live
    tenant: predictions bit-identical at both buckets through BOTH
    conversions, zero steady-state compiles, and each retune record labels
    its residency — the pipe one additionally carrying pipe_stages and
    the flush's interstage-byte price (schema v16)."""
    from mpi_pytorch_tpu.config import Config
    from mpi_pytorch_tpu.obs.schema import load_records, validate_jsonl
    from mpi_pytorch_tpu.serve.zoo import ZooServer

    cfg = Config(
        model_name="resnet18", num_classes=16, width=32, height=32,
        synthetic_data=True, compute_dtype="float32",
        serve_buckets="1,4", serve_max_wait_ms=2.0, serve_topk=3,
        serve_models="alpha=resnet18",
        metrics_file=str(tmp_path / "metrics.jsonl"),
        log_file="", eval_log_file="",
    )
    cfg.validate_config()
    zoo = ZooServer(cfg, load_checkpoint=False)
    rng = np.random.default_rng(3)
    images = [rng.random((32, 32, 3)).astype(np.float32) for _ in range(4)]
    base4 = np.asarray(zoo.predict_batch(images, model="alpha"))
    base1 = np.asarray(zoo.predict_batch(images[:1], model="alpha"))

    zoo.convert_residency("alpha", "pipe:2", reason="test")
    assert zoo.pool.residency("alpha") == "pipe:2"
    assert np.array_equal(
        np.asarray(zoo.predict_batch(images, model="alpha")), base4
    )
    assert np.array_equal(
        np.asarray(zoo.predict_batch(images[:1], model="alpha")), base1
    )
    zoo.convert_residency("alpha", "replicated", reason="test")
    assert zoo.pool.residency("alpha") == "replicated"
    assert np.array_equal(
        np.asarray(zoo.predict_batch(images, model="alpha")), base4
    )
    assert zoo.compiles_after_warmup() == 0
    zoo.close()

    assert validate_jsonl(cfg.metrics_file) == []
    retunes = [
        r for r in load_records(cfg.metrics_file)
        if r["kind"] == "fleet" and r.get("event") == "retune"
        and r.get("residency")
    ]
    assert [r["residency"] for r in retunes] == ["pipe:2", "replicated"]
    pipe_rec = retunes[0]
    assert pipe_rec["pipe_stages"] == 2
    assert pipe_rec["interstage_bytes"] > 0
    assert pipe_rec["reshard_bytes"] > 0
    assert pipe_rec["compiles_after_warmup"] == 0
    assert "pipe_stages" not in retunes[1]


def test_pipe_planner_prices_fourth_residency():
    """estimate_model_bytes under pipe:K: per-chip bytes = the BOTTLENECK
    stage (params + activation high-water), the 64.5k-class logits slab
    lands ONLY on the head stage, and the pipe estimate undercuts the
    replicated one — the planner's reason to ever pick the fourth
    option."""
    from mpi_pytorch_tpu.serve.sharding import parse_residency
    from mpi_pytorch_tpu.serve.zoo.registry import estimate_model_bytes

    est = estimate_model_bytes(
        "resnet18", 64500, 32, (1, 4), "bf16",
        residency=parse_residency("pipe:2"), n_devices=8,
    )
    assert est["residency"] == "pipe:2"
    assert est["pipe_stages"] == 2
    assert est["data_degree"] == 4
    stage_params = est["stage_params_bytes"]
    assert len(stage_params) == 2
    # At 64.5k classes the head stage (logits slab) dominates the trunk.
    assert stage_params[1] > stage_params[0]
    assert est["params_bytes"] == max(stage_params)
    assert est["total_bytes"] == est["params_bytes"] + max(
        est["per_bucket_bytes"].values()
    )
    assert est["total_bytes"] < est["replicated_total_bytes"]
    # Indivisible chip counts are a loud error, not a silent round-down.
    with pytest.raises(ValueError, match="does not divide"):
        estimate_model_bytes(
            "resnet18", 64500, 32, (1, 4), "bf16",
            residency=parse_residency("pipe:3"), n_devices=8,
        )


def test_pipe_config_and_mesh_validation():
    """The pipe knobs fail loudly: degenerate stage/micro counts, the
    zoo/shard mutual exclusions, the reserved "pipe" axis name, the
    indivisible serve mesh, and the no-PartitionSpec rule for pipe
    residency."""
    from mpi_pytorch_tpu.config import Config, MeshConfig
    from mpi_pytorch_tpu.parallel.mesh import create_pipe_serve_mesh
    from mpi_pytorch_tpu.serve.sharding import (
        parse_residency, serve_param_specs,
    )

    with pytest.raises(ValueError, match="serve_pipe_stages must be >= 1"):
        Config(serve_pipe_stages=0).validate_config()
    with pytest.raises(ValueError, match="serve_pipe_microbatches"):
        Config(serve_pipe_microbatches=0).validate_config()
    with pytest.raises(ValueError, match="single-model pipeline knob"):
        Config(
            serve_pipe_stages=2, serve_models="a=resnet18"
        ).validate_config()
    with pytest.raises(ValueError, match="mutually"):
        Config(
            serve_pipe_stages=2, serve_shard_degree=2
        ).validate_config()
    with pytest.raises(ValueError, match="reserved for the pipeline-stage"):
        MeshConfig(data_axis="pipe").validate()
    with pytest.raises(ValueError, match="not divisible by pipe stage"):
        create_pipe_serve_mesh(3)  # 8 CPU devices
    with pytest.raises(ValueError, match=">= 2 stages"):
        create_pipe_serve_mesh(1)

    res = parse_residency("pipe:2")
    assert (res.kind, res.degree, str(res)) == ("pipe", 2, "pipe:2")
    with pytest.raises(ValueError, match="degree >= 2"):
        parse_residency("pipe:1")
    with pytest.raises(ValueError, match="PipelineExecutables instead"):
        serve_param_specs({}, None, res)
