"""Typed configuration for the TPU-native framework.

Capability parity with the reference's constants module (``utils.py:4-45`` in
erick093/MPI_Pytorch): every knob the reference exposes as a module-level
constant is a field here with the same default, plus CLI/env overrides and
validation — which the reference lacks entirely (hand-edited constants,
``README.md:24-29``).
"""

from __future__ import annotations

import argparse
import dataclasses
import os
from dataclasses import dataclass, field
from typing import Any, Sequence

# ImageNet normalization constants (reference ``main.py:62-65``).
IMAGENET_MEAN = (0.485, 0.456, 0.406)
IMAGENET_STD = (0.229, 0.224, 0.225)


@dataclass
class MeshConfig:
    """Parallelism layout over the TPU device mesh.

    The reference's only axis of parallelism is MPI ranks doing data
    parallelism (``mpi_tools.py:30-37``). Here the mesh is explicit, and a
    ``model`` axis is available for tensor-parallel sharding of the
    64 500-class classifier head — a config change, not a rewrite.
    """

    data_axis: str = "data"
    model_axis: str = "model"
    pipe_axis: str = "pipe"
    # Nested data-axis names (pods > 1). FIXED strings, not configurable:
    # the collectives ledger classifies ICI-vs-DCN traffic by the "pod"
    # name (parallel/mesh.POD_AXIS), and a renamed axis would silently
    # misattribute cross-pod bytes.
    pod_axis: str = "pod"
    ici_axis: str = "ici"
    # -1 means "all remaining devices" on that axis.
    data_parallel: int = -1
    model_parallel: int = 1
    # Pipeline stages (driven by --pp-stages; the mesh gains a third axis
    # only when > 1, so existing 2-axis layouts are untouched).
    pipe_parallel: int = 1
    # Cross-pod hierarchical training (--mesh-pods, ISSUE 15 / ROADMAP
    # item 5): factor the data axis into the nested ("pod", "ici") pair —
    # gradient sync becomes two-phase (reduce-scatter within the pod over
    # fast ICI, cross-pod reduction over DCN with 1/ici the bytes,
    # overlapped with backward), and ZeRO shards place within-pod so the
    # param all_gather never crosses the DCN. 1 = flat mesh, unchanged.
    pods: int = 1

    def validate(self) -> None:
        if self.model_parallel < 1:
            raise ValueError(f"model_parallel must be >= 1, got {self.model_parallel}")
        if self.pipe_parallel < 1:
            raise ValueError(f"pipe_parallel must be >= 1, got {self.pipe_parallel}")
        if self.pods < 1:
            raise ValueError(f"mesh pods must be >= 1, got {self.pods}")
        # The nested-axis names really are fixed (see the field comment):
        # is_hierarchical()/axis_kind() match the literal strings, so a
        # renamed axis would make the step sync over only one data factor.
        if self.pod_axis != "pod" or self.ici_axis != "ici":
            raise ValueError(
                "mesh pod_axis/ici_axis are fixed at 'pod'/'ici' (the "
                "traffic ledger and the hierarchical step key on the "
                f"literal names), got {self.pod_axis!r}/{self.ici_axis!r}"
            )
        # ...and the configurable axes may not claim the reserved names: a
        # flat mesh named ('pod', 'ici') would read as hierarchical to
        # is_hierarchical()/axis_kind() and sync over the wrong axes.
        for field in ("data_axis", "model_axis", "pipe_axis"):
            if getattr(self, field) in ("pod", "ici"):
                raise ValueError(
                    f"mesh {field} may not be named 'pod' or 'ici' — those "
                    "names are reserved for the nested hierarchical data "
                    f"axes, got {getattr(self, field)!r}"
                )
        # "pipe" is likewise reserved FOR the pipeline axis (ISSUE 20: the
        # nested (data, pipe) serve mesh and the stage planner key on the
        # literal name) — the data/model axes may not claim it.
        for field in ("data_axis", "model_axis"):
            if getattr(self, field) == "pipe":
                raise ValueError(
                    f"mesh {field} may not be named 'pipe' — that name is "
                    "reserved for the pipeline-stage axis (serve pipe mesh "
                    "and --pp-stages layouts key on the literal name)"
                )


@dataclass
class Config:
    """All framework knobs. Defaults mirror reference ``utils.py:4-45``."""

    # --- model (utils.py:4, :39-45) ---
    model_name: str = "resnet18"
    # The architecture of a model that is configured, not named (the ones
    # whose registry ModelSpec accepts ``model_config``): ONE JSON object in
    # the source's own config.json key names, inline or the path of a file
    # that holds it (models/lfm2.py Lfm2Config). Empty: the source's published values.
    model_config: str = ""
    num_classes: int = 64500
    feature_extract: bool = False
    use_pretrained: bool = False  # reference default True needs torchvision weights;
    # here pretrained means "load converted weights from pretrained_dir" (tools/convert_torchvision.py)
    pretrained_dir: str = "pretrained"

    # --- run mode (utils.py:5-6, :13) ---
    from_checkpoint: bool = False
    validate: bool = True
    debug: bool = True
    n_images: int = 50000  # utils.py:14 (create_dataset sampling)
    debug_sample_size: int = 1000  # main.py:78 samples 1000 rows seed=0 in DEBUG

    # --- data (utils.py:22-27, :33-34) ---
    data_dir: str = "data"
    train_csv: str = "data/train_sample.csv"
    test_csv: str = "data/test_sample.csv"
    train_img_dir: str = "data/img/train"
    test_img_dir: str = "data/img/test"
    checkpoint_dir: str = "checkpoints"
    width: int = 128
    height: int = 128
    synthetic_data: bool = True  # images are not shipped with the repo (.gitignore:2-4)

    # --- optimization (utils.py:40-42) ---
    batch_size: int = 128  # GLOBAL batch size (split across data-parallel devices)
    learning_rate: float = 4e-4
    num_epochs: int = 10
    # Beyond reference parity (it hard-codes Adam at a fixed rate,
    # main.py:125): optimizer adam|sgd|adamw, schedule constant|cosine|
    # warmup_cosine (cosine decays to 0 over the run's total step count,
    # computed by the trainer).
    optimizer: str = "adam"
    lr_schedule: str = "constant"
    warmup_steps: int = 0
    weight_decay: float = 0.0

    # --- precision / TPU ---
    compute_dtype: str = "bfloat16"  # MXU-native; params stay float32
    param_dtype: str = "float32"
    # host batch dtype: bfloat16 halves host→device transfer (the step casts
    # to compute_dtype anyway); float32 preserves exact reference numerics;
    # uint8 ships RAW pixels (4x less H2D than f32, 4x smaller host/device
    # caches, zero host float work on the packed path) and normalizes ON
    # DEVICE (train/step.py ingest_images), where XLA fuses it into the
    # first conv. uint8 disables the fused native C++ decode (PIL path).
    input_dtype: str = "float32"
    sync_batchnorm: bool = False  # reference keeps per-rank local BN stats (SURVEY §7)
    # spmd_mode=True uses the shard_map step with explicit collectives and
    # per-shard local BN — exact reference DP semantics; default is the
    # compiler-partitioned jit step (global-batch BN, supports TP head).
    spmd_mode: bool = False
    # ZeRO-1-style optimizer sharding (beyond reference parity): Adam moments
    # sharded over the data axis instead of replicated — per-device optimizer
    # memory 2×params → 2×params/n. Auto (jit) mode only.
    zero_optimizer: bool = False
    # ZeRO-style optimizer-state sharding for the SPMD (shard_map) step
    # (ROADMAP item 2a, arXiv 2004.13336): every optimizer-state leaf is
    # flatten-pad-partitioned 1/P over the data axis (train/state.py
    # zero_shard_spec); each shard updates only its owned slice and an
    # allgather reassembles full params for the next forward. Per-device
    # optimizer HBM 2×params → 2×params/P; checkpoints gather-on-save, so
    # the on-disk format is unchanged and legacy checkpoints load into
    # either layout. spmd_mode only (the auto-jit twin is zero_optimizer).
    zero_opt_state: bool = False
    # Bucketed gradient sync for the SPMD step (ROADMAP item 2b, arXiv
    # 1810.11112): replace the one fused post-backward pmean with one
    # collective per ~N-MiB bucket of param leaves in reverse-topo order,
    # so earlier buckets' collectives overlap the remaining backward
    # compute; with zero_opt_state the buckets become reduce_scatters and
    # grad comms halve. Value is the bucket size in MiB (~25 is the
    # conventional sweet spot); 0 = the fused single-pmean baseline.
    # spmd_mode only.
    grad_sync_buckets: float = 0.0
    # ZeRO-3/FSDP-style parameter sharding (beyond reference parity): params
    # AND their Adam moments sharded over the data axis at rest; XLA
    # all-gathers each layer's weights at use and reduce-scatters its
    # gradient — per-device params+optimizer memory 3×params → 3×params/n.
    # Auto (jit) mode only.
    fsdp: bool = False
    # Rematerialization strategy: "none" | "full" | "blocks".
    # "full" wraps the whole forward in jax.checkpoint (measured NOT to pay
    # for these CNNs — docs/RESULTS.md §4b); "blocks" checkpoints each
    # residual block / dense layer / encoder block (the models whose
    # registry ModelSpec accepts ``remat_blocks``), recomputing
    # one block at a time during backward — the placement that can actually
    # cut activation memory.
    remat: str = "none"
    # Gradient accumulation: split each batch into this many microbatches,
    # accumulate count-weighted gradients over a lax.scan, apply ONE
    # optimizer update — the same global-batch gradient at 1/accum_steps the
    # activation memory. (BN stats update per microbatch.) Streaming auto
    # mode only.
    accum_steps: int = 1
    # Sequence parallelism inside the vit_* family's encoder attention:
    # "none" | "ring" | "ulysses". Builds a ("seq", "_") mesh over all
    # devices and shards every attention call's sequence axis over it
    # (ops/ring_attention.py, ops/ulysses.py). vit models only.
    sp_strategy: str = "none"
    # Attention of the vit_* family when sp_strategy is "none". "full" is
    # exact dense attention and needs no setting: on a TPU the single-pass
    # Pallas kernel runs (scores and probabilities stay in VMEM) wherever a
    # head's score tile fits it — S padded to the sublane tile <= 512, head
    # dim <= 128, chosen from the operands' shape — and XLA's materialized
    # [B,H,S,S] otherwise and on other backends
    # (ops/fused_attention_small.py). "flash" is the block-tiled
    # online-softmax kernel for long sequences (ops/flash_attention.py);
    # "fused-small" names the single-pass kernel for A/B tools: that kernel
    # or an error naming the shape. Identical math all three ways.
    attn_impl: str = "full"
    # Fuse the q/k/v projections into one [D, 3·H·Dh] matmul (vit family;
    # same param tree, exactly the same math — models/vit.py qkv_fused).
    qkv_fused: bool = False
    # Predictions pass: stream the head weights through VMEM computing
    # loss+argmax online instead of materializing [B, num_classes] logits
    # (ops/fused_head_ce.head_predict; TPU only, XLA path elsewhere). The
    # kernel matmuls in the FEATURE dtype: bf16 compute gets the VMEM-stream
    # bandwidth win, while an f32-compute model keeps exact f32 head
    # semantics — no silent bf16 downcast of the argmax (advisor r5).
    # Applies to the predictions pass (--predictions-file); a silent
    # fallback to the plain step logs a one-time warning (evaluate.py).
    fused_head_eval: bool = False
    # Expert parallelism for MoE models (vit_moe_s16): shard the experts
    # over all devices on an ("expert", "_") mesh; tokens travel by
    # all_to_all (ops/moe.py). MoE models only.
    expert_parallel: bool = False
    # Pipeline parallelism over the vit_* encoder trunk (parallel/pp_vit.py):
    # > 1 adds a "pipe" mesh axis of that size, splits the depth-homogeneous
    # encoder blocks into pp_stages equal stages, and streams microbatches
    # through them GPipe-style (parallel/pipeline.py) — composed with DP over
    # the remaining devices. Same param tree, same checkpoints: PP is purely
    # an execution strategy (the apply_fn is swapped, nothing else). Dense
    # ViT models only (ModelSpec flag ``pp_stages``); auto mode only.
    pp_stages: int = 1
    # Microbatches streamed through the pipeline per step; 0 → 2*pp_stages.
    # The GPipe bubble fraction is (S-1)/(M+S-1): raise M to amortize it.
    pp_microbatches: int = 0
    # Space-to-depth stem for the resnet family (ModelSpec flag
    # ``stem_s2d``): the 7×7/stride-2 conv on 3 input channels becomes an
    # exactly-equivalent 4×4/stride-1 conv on 12 channels (MLPerf conv0 trick) — keeps the
    # MXU's contracting dimension filled at the stem. Checkpoints carry the
    # (4,4,12,64) kernel; pretrained 7×7 weights load through the exact
    # transform (models/resnet.py s2d_stem_kernel). Requires even image size.
    stem_s2d: bool = False
    # Fused stem for the identical-7×7-stem family (ModelSpec flag
    # ``fused_stem``: resnet18/34 — the measured winners — plus
    # densenet121, whose torchvision stem features.conv0..pool0 is the same
    # geometry; capability-enabled, A/B staged — docs/RESULTS.md §4):
    # BN+relu+maxpool(3,2,1) as one Pallas kernel pair (ops/fused_stem.py) —
    # the stem-conv activation never round-trips HBM between BN and the pool,
    # and the pool backward is an index gather instead of select-and-scatter
    # (docs/RESULTS.md §4d). Same variable tree as the unfused stem, so
    # checkpoints interchange. TPU only (XLA composition elsewhere); requires
    # even post-conv spatial dims (any even image size) and local BN.
    fused_stem: bool = False

    # --- input pipeline ---
    shuffle: bool = True
    seed: int = 0  # reference uses seed 0 for sampling (main.py:78)
    loader_workers: int = 8
    prefetch_batches: int = 2
    # Native (C++) batched JPEG ingest (mpi_pytorch_tpu/native): decode a whole
    # batch per ctypes call on C threads with the GIL released — the TPU-host
    # equivalent of the reference's DataLoader worker processes / MPI
    # preprocessing ranks. Auto-falls back to PIL when the toolchain is absent.
    native_decode: bool = True
    # libjpeg DCT prescale for large sources: 0 = full decode (PIL bit-parity),
    # 1 = fastest, 2 = 2x-margin scaled decode (default; ~1/255 mean deviation
    # from PIL, measured in tests/test_native_decode.py).
    decode_prescale: int = 2
    # Decode each host's shard once into HOST RAM (epoch 0), then serve later
    # epochs by slicing — zero decode after the first epoch, multi-host safe,
    # and sized by host memory instead of HBM (40k images at 128px = 7.9 GB
    # f32 / 3.9 GB bf16). The middle ground between streaming and device_cache.
    host_cache: bool = False
    # Directory of OFFLINE-packed datasets (data/packed.py): uint8 image
    # tensors decoded+resized once, mmap'd at run time — per-epoch decode
    # cost removed entirely (vs hidden, the reference's approach), page
    # cache shared across processes on a host. Build with
    # `python -m mpi_pytorch_tpu.data.packed --packed-dir DIR [flags]`;
    # loaders resolve their shard against the packs by filename.
    packed_dir: str = ""
    drop_remainder: bool = True  # static shapes for XLA; see trainer for semantics
    # Keep the whole (decoded, normalized) training set resident in HBM and
    # have each jitted step gather its batch by index on device — zero
    # per-step host↔device traffic. The TPU-idiomatic answer for datasets
    # that fit (DEBUG's 800 images ≈ 157 MB f32; the full 40 000-image
    # manifest ≈ 3.7 GB bf16): the host feeds the chip once per run instead
    # of once per step. Single-process only (multi-host keeps streaming).
    device_cache: bool = False
    # With device_cache: run each epoch as ONE compiled lax.scan over all its
    # steps (one dispatch per epoch instead of per step), removing the
    # remaining host↔device round-trips from the training path entirely.
    scan_epoch: bool = False
    # Streaming path: batches transferred to device this many steps ahead of
    # compute (device_put is async), hiding host→device latency — the
    # overlap the reference's 4-stage MPI pipeline existed to provide.
    prefetch_device_batches: int = 2

    # --- online serving (mpi_pytorch_tpu/serve/) ---
    # Batch buckets for the dynamic batcher: every coalesced request batch is
    # padded up to one of these sizes, and ONE predict executable per bucket
    # is AOT-compiled at server start — steady-state serving never compiles.
    # More buckets = tighter padding waste but more warmup compiles; sizes
    # divisible by the data-axis device count shard over the chips, smaller
    # ones run replicated (docs/SERVING.md, tuning).
    serve_buckets: str = "1,8,32,128,512"
    # Deadline (ms) from the OLDEST queued request to a forced flush: the
    # latency/throughput lever — 0 flushes every request immediately
    # (lowest latency, worst fill), larger values coalesce fuller batches.
    serve_max_wait_ms: float = 5.0
    # Bounded request queue: submissions beyond this depth are REJECTED with
    # a typed QueueFullError (backpressure — shed load instead of building
    # an unbounded latency backlog).
    serve_queue_depth: int = 1024
    # Top-k class predictions returned per request (k<=5; the plain predict
    # path computes lax.top_k online). --fused-head-eval streams argmax only,
    # so the fused server serves k=1 (warned, not silent).
    serve_topk: int = 5
    # Serving numeric precision (ISSUE 11): which predict-executable set(s)
    # are AOT-compiled and warmed at startup.
    #   bf16 — the compute-dtype path (today's default);
    #   int8 — post-training int8 (ops/quantize.py): per-channel int8
    #          conv/dense weights dequantized on the fly (half the resident
    #          weight bytes — the head is byte-bound, docs/roofline_*.json),
    #          and under the --fused-head-eval gate the fused int8 head
    #          kernel (int8×int8 MXU, int32 accumulate);
    #   both — compile BOTH sets and start serving bf16: the fleet
    #          controller's precision retune axis (bf16 under SLO headroom,
    #          int8 under p99 pressure) switches only ever between these
    #          startup-compiled sets, parity stamped on retune records.
    serve_precision: str = "bf16"
    # evaluate --quantize-eval: offline int8-vs-bf16 parity report (top-1/
    # top-5 agreement + max logit drift on a fixed seeded sample) — the
    # reusable oracle behind the serve-side parity gates.
    quantize_eval: bool = False
    # Sample-batch size for int8 calibration (the head activation scale),
    # the serve startup parity stamp, and the --quantize-eval probe.
    quantize_calib: int = 64

    # --- multi-model tenancy (mpi_pytorch_tpu/serve/zoo/, ISSUE 14) ---
    # Non-empty turns the serving stack multi-tenant: comma-separated
    # tenant specs "[alias=]arch[:key=val]*" (keys: ckpt, precision,
    # buckets (|-separated), admission, cold — serve/zoo/registry.py).
    # Each tenant gets its own per-(model, bucket[, precision]) AOT
    # executable sets, its own batcher/queue (flushes are single-tenant
    # by construction), a per-tenant front-door admission budget, and a
    # model-labelled controller/SLO axis; requests carry model=. "" =
    # single-model serving, byte-identical to the pre-zoo behavior.
    serve_models: str = ""
    # Packing budget (MB) for the resident tenant set on one host —
    # params + largest-bucket activations per tenant, PR 6's leaf-size
    # accounting (serve/zoo/registry.plan_packing; the plan is stamped
    # on swap-in records). A cold swap-in evicts LRU-idle tenants until
    # the plan fits; a single over-budget tenant is rejected loudly.
    # 0 = unbounded (the plan is still computed and explained).
    serve_pack_budget_mb: float = 0.0
    # --- model-parallel residency (serve/sharding.py, ISSUE 17) ---
    # K > 1 serves this host MODEL-PARALLEL over a nested (data, model)
    # mesh: params FSDP-shard over K chips (the serve residency fsdp:K),
    # batch rows shard over the remaining data-slices, and buckets smaller
    # than the data degree pad to it. 1 = replicated, byte-identical to
    # before. Zoo tenants pick residency per-spec (shard=K / shard=tp:K)
    # or get it from the packing planner instead; this knob is the
    # single-model and bench_serve surface.
    serve_shard_degree: int = 1
    # --- pipeline-parallel residency (serve/pipeline.py, ISSUE 20) ---
    # K > 1 serves this host PIPELINE-PARALLEL over a nested (data, pipe)
    # mesh: the model splits at registry cut points into K stages (stem /
    # trunk / fused head), each stage its own per-bucket AOT executable on
    # a disjoint chip group, and a flush streams serve_pipe_microbatches
    # micro-batches through the stages. 1 = no pipelining. Zoo tenants
    # pick it per-spec (shard=pipe:K) or via the planner; this knob is the
    # single-model and bench_serve surface.
    serve_pipe_stages: int = 1
    # Micro-batches per flush (M). Steady state overlaps stages; the
    # fill/drain bubble fraction is (K-1)/(M+K-1) under equal stage times,
    # so more micro-batches amortize the bubble. M is clamped down to the
    # largest divisor of each bucket size (M=1 degenerates to sequential).
    serve_pipe_microbatches: int = 4

    # --- fleet serving (mpi_pytorch_tpu/serve/fleet/, ISSUE 9) ---
    # N > 0 builds an in-process N-host fleet (FleetServer: N InferenceServer
    # replicas sharing one warmed executable set, fronted by the load-aware
    # router) — the bench/CI harness shape. 0 = plain single-host serving.
    # In production each host is its own process; the router talks the same
    # HostHandle surface either way.
    serve_fleet_hosts: int = 0
    # Also build one warm STANDBY host: it receives warmup traffic only and
    # is promoted into rotation when a live host is drained (failover).
    serve_fleet_spare: bool = False
    # Router health/score probe cadence: each tick snapshots every host's
    # live metrics registry (the EWMA dispatch score) and probes liveness;
    # a host failing serve_fail_probes CONSECUTIVE probes (or dispatches)
    # is drained and its in-flight requests re-dispatched.
    serve_probe_interval_ms: float = 200.0
    serve_fail_probes: int = 3
    # Cross-host admission budget: fleet-wide in-flight requests beyond
    # this are rejected AT THE FRONT DOOR with a typed QueueFullError
    # carrying a retry_after_ms hint. 0 = auto (the sum of every active
    # host's serve_queue_depth).
    serve_admission_tokens: int = 0
    # > 0 starts the live autotuning controller against this p99 target
    # (ms): per host, max_wait_ms halves while p99 breaches (then the
    # largest active bucket deactivates), and recovers when there is
    # latency headroom and fill is poor. Retunes only ever activate
    # pre-compiled executables. 0 = controller off.
    serve_target_p99_ms: float = 0.0
    serve_retune_interval_s: float = 2.0

    # --- remote fleet transport + autoscaler (serve/fleet/remote.py,
    # serve/fleet/autoscaler.py, serve/host.py — ISSUE 12) ---
    # The serving-host PROCESS entrypoint (python -m mpi_pytorch_tpu.serve.host)
    # binds its wire surface (POST /submit, GET /result/<id>, /control,
    # /metricsz, /healthz) on this port; 0 = ephemeral (read it back from
    # serve_port_file).
    serve_port: int = 0
    # Readiness handshake: after warmup the host process atomically writes
    # this JSON file ({"port", "pid", "host_index"}) — the supervisor's
    # spawn handshake. "" = no file (the SERVE_HOST_READY stdout line and
    # --serve-port remain).
    serve_port_file: str = ""
    # This process's fleet-host identity (the hN name, the kill-gate /
    # inject_faults target). -1 = standalone serving (no fleet identity).
    serve_host_index: int = -1
    # RemoteHost wire discipline: connect-ish timeout for submit/probe/
    # control calls, read timeout for result long-polls, and the bounded
    # jittered retry budget for IDEMPOTENT probes (submit is never
    # retried — a failed submit feeds the router's drain streak, which is
    # what preserves exactly-once re-dispatch).
    serve_connect_timeout_s: float = 2.0
    serve_read_timeout_s: float = 30.0
    serve_probe_retries: int = 2

    # --- tail-at-scale data plane (serve/wire.py, serve/client.py —
    # ISSUE 16) ---
    # Fleet data-plane transport: "http" = the .npy-over-HTTP legacy path
    # (now with per-host keep-alive connection reuse); "framed" = the
    # length-prefixed binary MPTW wire — persistent pooled connections,
    # pipelining, out-of-order completion by req_id, no JSON/base64 on
    # the hot path. Control/probe traffic (healthz, statsz, control ops)
    # stays on HTTP either way; only submit/result moves. Flows to
    # spawned host processes, which bind a WireListener next to the HTTP
    # surface and advertise it as wire_port in the readiness file.
    serve_transport: str = "http"
    # Hedged requests (the 1810.11112 tail-tolerance move): when a
    # dispatched request outlives a deadline derived from the TARGET
    # host's live p99 (p99 × serve_hedge_factor, floor-clamped), the
    # router re-issues it to the second-best host; the claim ledger
    # resolves duplicate completions first-wins exactly-once and the
    # loser is revoked with a CANCEL frame so it never occupies a batch
    # slot after the winner lands. Needs >= 2 fleet hosts to ever have a
    # second-best host.
    serve_hedge: bool = False
    serve_hedge_factor: float = 3.0
    serve_hedge_floor_ms: float = 20.0
    # True starts the FleetAutoscaler: grow/shrink the host set from
    # registry metrics (admission-reject rate, p99 vs --serve-target-p99-ms,
    # queue-depth trend), bounded by the min/max host counts and the
    # cooldown below so it can't flap; every action a kind="fleet"
    # scale_up/scale_down/restart record (schema v8).
    serve_autoscale: bool = False
    serve_fleet_min_hosts: int = 1
    serve_fleet_max_hosts: int = 8
    serve_scale_cooldown_s: float = 30.0
    # Front-door rejects/s that trigger a scale-up.
    serve_scale_reject_rate: float = 0.5
    # --- quality observability (ISSUE 19) ---
    # serve_canary_probes > 0 arms the golden-set quality canary: that
    # many seeded probe images per tenant go through the REAL front door
    # as shadow requests (excluded from SLO/admission/billing counters),
    # scored against references pinned on the first cycle; the latched
    # per-tenant verdict gates EVERY fleet mutation (zoo swap-in /
    # set_precision / convert_residency, controller retunes) — a FAIL
    # verdict blocks the mutation until the canary recovers. 0 = off.
    serve_canary_probes: int = 0
    # Probe-cycle period for the background prober; 0 keeps the canary
    # armed but passive (drive fleet.prober.probe_once() yourself — the
    # tests/CI mode).
    serve_canary_interval_s: float = 0.0
    # Top-1 agreement below this fails a probe cycle; fail_after
    # consecutive failing cycles trip the verdict to FAIL, pass_after
    # passing cycles recover it (hysteresis — one noisy cycle is not an
    # incident, one good cycle is not a recovery).
    serve_canary_min_top1: float = 0.95
    serve_canary_fail_after: int = 2
    serve_canary_pass_after: int = 2
    # serve_drift_window > 0 arms prediction-drift detection: per-tenant
    # top-1 class histograms over windows of this many REAL requests,
    # compared against a rolling clean baseline by PSI + chi-squared;
    # breaches write kind="alert" source="drift" records (which pin
    # traces and auto-dump the flight recorder). The prober's heartbeat
    # also runs a CUSUM change-point scan over the collector's
    # per-(host, metric) rings with threshold serve_drift_cusum_h (in
    # sigma units of the learned reference). 0 = off.
    serve_drift_window: int = 0
    serve_drift_psi: float = 0.25
    serve_drift_chi2: float = 10.0
    serve_drift_cusum_h: float = 8.0

    # --- validation semantics (main.py:104-112 validates on the TRAIN split) ---
    val_on_train: bool = True

    # --- checkpoint ---
    keep_checkpoints: int = 3
    checkpoint_every_epochs: int = 1
    # Cast the large f32 Adam-moment tensors to bf16 in the snapshot:
    # halves the moment D2H bytes and the file (~540 MB → ~270 MB at
    # headline scale). Lossy for the moments only (params stay exact);
    # restore casts back to f32, so resume continues with bf16-quantized
    # moments — a trajectory perturbation within optimizer noise.
    ckpt_bf16_moments: bool = False
    # Track the best-validation checkpoint: on a val-accuracy improvement the
    # epoch's checkpoint is dispatched (even when the periodic save isn't
    # due) and best.json points at it; retention never deletes it; evaluate
    # --use-best consumes it. This is the reference's accepted-and-ignored
    # is_best/best_model_dir surface (helpers.py:4-7), implemented.
    track_best: bool = False
    # Evaluation: load the best.json checkpoint instead of the latest.
    use_best: bool = False
    # --- elastic resume / preemption (ISSUE 7, ROADMAP item 4) ---
    # Bounded retry+backoff around the RESUME side's backend init and state
    # placement (train/elastic.with_retries): a transiently wedged backend
    # costs retries, not the run. Backoff doubles
    # per attempt from resume_backoff_s; retries bounds the attempts.
    resume_retries: int = 3
    resume_backoff_s: float = 0.5
    # Preemption sentinel file: when this path exists, the watchdog stops
    # the run at the next safe boundary, saves, and exits 0 for auto-resume
    # (the cluster-scheduler preemption-notice pattern). "" reads the
    # MPT_PREEMPT_FILE env gate instead.
    preempt_file: str = ""
    # Preempt (save + clean exit) after this many CONSECUTIVE heartbeat
    # beats that flagged a straggler / steps with a non-finite grad norm —
    # the self-healing escalation of the obs signals. 0 disables (default:
    # the NaN-loss sentinel still aborts hard; preempt-on-streak is a
    # fleet policy, opted into per run).
    preempt_straggler_beats: int = 0
    preempt_nonfinite_steps: int = 0
    # --- self-healing training (ISSUE 10) ---
    # What a BAD step (non-finite loss / global grad norm) costs the run:
    #   abort    — today's behavior: the NaN sentinel writes a diagnostic
    #              record and raises (obs/health.py).
    #   skip     — discard the update ON DEVICE (the jitted step selects the
    #              pre-step params/opt-state when the psum'd grad norm is
    #              non-finite — every host takes the same branch) and keep
    #              training; aborts after --max-skipped-steps CONSECUTIVE
    #              skips. Params across a skipped step are bit-identical.
    #   rollback — restore the last good checkpoint IN-PROCESS
    #              (elastic.restore_latest — no process death) when a
    #              non-finite streak or a loss-spike drift fires
    #              (train/elastic.RollbackPolicy), optionally backing off
    #              the LR; bounded by --max-rollbacks, each writing a
    #              kind="rollback" record (schema v6).
    # skip/rollback read the step's loss/grad norm on the host, costing one
    # device sync per step (the --step-metrics cost) — a recovery-policy
    # run is telemetry-priced by construction. Both disable the NaN
    # sentinel's hard abort (the policy IS the response).
    bad_step_policy: str = "abort"
    # skip: consecutive discarded steps before aborting anyway (something
    # is systematically wrong, not transient).
    max_skipped_steps: int = 10
    # rollback triggers: consecutive non-finite steps, and (0 = off) a
    # loss-spike ratio vs the run's own warmup baseline — the mean of the
    # first rollback_drift_warmup finite losses, the SLO monitor's drift:
    # semantics (obs/monitor.py).
    rollback_nonfinite_steps: int = 2
    rollback_loss_drift: float = 0.0
    rollback_drift_warmup: int = 5
    # rollback bounds: total in-process restores before aborting, and an
    # LR scale applied on EACH rollback (1.0 = keep the LR; 0.5 halves it
    # per rollback — note a scale != 1.0 rebuilds the optimizer and
    # recompiles the step once per rollback).
    max_rollbacks: int = 3
    rollback_lr_backoff: float = 1.0
    # --- input-pipeline robustness (ISSUE 10 satellite) ---
    # An unreadable/corrupt image is retried with bounded backoff, then
    # QUARANTINED: its batch row becomes a masked (label -1) copy of a good
    # row, its path lands in quarantine_file ("" = no file) and a
    # kind="anomaly" reason="bad_sample" record is written. More than
    # max_bad_samples quarantines abort the run loudly (0 = abort on the
    # first one past zero tolerance).
    max_bad_samples: int = 16
    quarantine_file: str = ""
    # Evaluation: also write per-image predictions as CSV
    # (file_name, predicted_label, predicted_category_id) — the Herbarium
    # task's actual deliverable (a submission file), which the reference's
    # pipeline computes per-image but never persists
    # (evaluation_pipeline.py:149-158). "" disables. Single-process.
    predictions_file: str = ""

    # --- observability ---
    log_file: str = "training.log"
    eval_log_file: str = "evaluation.log"
    metrics_file: str = "metrics.jsonl"  # structured JSONL metrics; "" disables
    profile_dir: str = ""  # non-empty → jax.profiler traces written here
    log_every_steps: int = 10
    # Host-side trace spans (obs/trace.py): non-empty → Chrome-trace-event
    # JSON written here at run end (one file per process on multi-host),
    # loadable in chrome://tracing / Perfetto. Spans (ingest/step/checkpoint/
    # validate/…) also enter jax.profiler.TraceAnnotation, so they line up
    # with an XLA trace captured via --profile-dir (docs/OBSERVABILITY.md).
    trace_file: str = ""
    # Per-step health records (kind="step" in metrics_file): data-wait vs
    # device-step ms, loss, global grad norm, live HBM bytes, recompile
    # counter (obs/health.py). Costs ONE host sync per step — telemetry
    # mode, not benchmark mode; default off.
    step_metrics: bool = False
    # NaN/Inf-loss sentinel (obs/health.py): a non-finite loss writes a
    # kind="anomaly" diagnostic record and aborts cleanly instead of
    # training on garbage. Checked per step when step_metrics is on, per
    # epoch always (the epoch loss is a host float anyway — free).
    nan_sentinel: bool = True
    # Multi-host heartbeat (obs/heartbeat.py): every N steps all processes
    # exchange mean step time (parallel/collectives.host_allgather) and the
    # metrics stream gains kind="heartbeat" records with per-host rows;
    # hosts slower than straggler_threshold x median are flagged. 0 = off.
    heartbeat_every_steps: int = 0
    straggler_threshold: float = 1.5
    # --- live telemetry (obs/metrics.py, obs/monitor.py, obs/flight.py) ---
    # Declarative SLO rules over the live metrics registry ("" = off).
    # Rules separated by ";", e.g.
    #   "serve/flush_ms:p99 > 250 for=3 name=serve_p99;
    #    drift:train/step_ms_last > 2.0 for=2 action=log,preempt"
    # Evaluated per step (trainer) / per flush (serve); a breach writes a
    # kind="alert" record and runs its actions (log | metric | preempt —
    # the last writes the preemption sentinel so the watchdog stops the
    # run at a safe boundary). Syntax: obs/monitor.py / OBSERVABILITY.md.
    slo_rules: str = ""
    # Periodic kind="metrics" registry snapshots every N steps (0 = off).
    # Step-count cadence (not wall time) because the multi-host merge is a
    # collective: every process must snapshot at the same step.
    metrics_every_steps: int = 0
    # Anomaly flight recorder ("" = off): every record this process emits
    # enters a bounded ring, and any kind="fault"/"alert" record dumps the
    # ring as a JSON evidence file in this directory (obs/flight.py).
    flight_dir: str = ""
    flight_records: int = 256
    # > 0: a flight dump also opens a jax.profiler trace for the next S
    # seconds (closed on a later record), capturing the device-side
    # aftermath of the incident next to the host evidence.
    flight_profile_window_s: float = 0.0
    # Serve-only: HTTP exposition thread (serve/http.py). 0 = off; > 0
    # binds that port; -1 binds an ephemeral port (tests/smokes — read it
    # back from InferenceServer.metrics_port). Serves /metrics (Prometheus
    # text), /metricsz (JSON registry snapshot), /healthz.
    serve_metrics_port: int = 0
    # --- fleet-wide distributed tracing + collector (ISSUE 13) ---
    # > 0 turns on cross-process tracing at the fleet front door: every
    # admitted request is minted a W3C-traceparent-style trace id that
    # threads router → wire → host queue/preprocess/device → result, and
    # the value is the HEAD-sample keep fraction for ordinary traces —
    # tail sampling keeps every slow/failed/rejected/re-dispatched trace
    # regardless. 0 (default) = tracing fully off: serve records and
    # hot-path behavior are byte-identical to the untraced build.
    trace_sample_rate: float = 0.0
    # Tail-sampling slow threshold (ms): a trace whose end-to-end root
    # exceeds this is kept in full. 0 = no slow criterion.
    trace_slow_ms: float = 0.0
    # > 0 runs the FleetCollector (obs/collector.py) on this cadence:
    # scrape every host's /metricsz + /tracez, estimate per-host clock
    # offsets from probe-RTT midpoints, detect counter resets across
    # restarts, and emit schema-v9 kind="timeline" records. 0 = off.
    serve_collect_interval_s: float = 0.0
    # Where the collector appends KEPT trace spans (JSONL, one span per
    # line) — the input of tools/trace_report.py. "" = don't persist
    # spans (phase stats and timelines still collect).
    fleet_trace_file: str = ""
    # Sanitizer (SURVEY §5 race-detection row): XLA collectives are
    # deterministic by construction, so the debug surface that remains is
    # numerics — this flag turns every NaN-producing op into an immediate
    # error with a traceback (jax_debug_nans).
    debug_nans: bool = False
    # Extra TPU compiler options for the AOT-compiled step executables, as
    # "key=value key2=value2" (bool/int values coerced; leading "--"
    # tolerated). These are PER-COMPILE PJRT options: they reach only the
    # executables this repo AOT-compiles, and a CPU process never has to
    # parse a TPU-only flag (XLA_FLAGS is read by every backend at start-up
    # and aborts the process on a flag it does not know). Example:
    # "xla_tpu_scoped_vmem_limit_kib=65536" (64 MiB scoped VMEM).
    compiler_options: str = ""

    mesh: MeshConfig = field(default_factory=MeshConfig)

    def validate_config(self) -> None:
        # What the model is, is the registry's to say (models/registry.py
        # ModelSpec); an unknown name is refused there.
        from mpi_pytorch_tpu.models.registry import check_build_flags, model_spec

        spec = model_spec(self.model_name)
        if self.num_classes < 2:
            raise ValueError(f"num_classes must be >= 2, got {self.num_classes}")
        if self.batch_size < 1:
            raise ValueError(f"batch_size must be >= 1, got {self.batch_size}")
        if self.learning_rate <= 0:
            raise ValueError(f"learning_rate must be > 0, got {self.learning_rate}")
        if self.compute_dtype not in ("float32", "bfloat16"):
            raise ValueError(f"compute_dtype must be float32|bfloat16, got {self.compute_dtype}")
        if self.input_dtype not in ("float32", "bfloat16", "uint8"):
            raise ValueError(
                f"input_dtype must be float32|bfloat16|uint8, got {self.input_dtype}"
            )
        if self.zero_optimizer and self.spmd_mode:
            raise ValueError(
                "zero_optimizer shards Adam moments via the auto-partitioned "
                "jit step; the spmd_mode shard_map step replicates its state "
                "specs, so the two do not compose"
            )
        if self.zero_opt_state and not self.spmd_mode:
            raise ValueError(
                "zero_opt_state shards the optimizer state inside the "
                "spmd_mode shard_map step (explicit slice-update + params "
                "allgather); for the auto-partitioned jit step use "
                "zero_optimizer instead"
            )
        if self.grad_sync_buckets < 0:
            raise ValueError(
                f"grad_sync_buckets is a bucket size in MiB (0 disables), "
                f"got {self.grad_sync_buckets}"
            )
        if self.grad_sync_buckets > 0 and not self.spmd_mode:
            raise ValueError(
                "grad_sync_buckets stages explicit per-bucket collectives "
                "inside the spmd_mode shard_map step; the auto-partitioned "
                "jit step has no explicit gradient collective to bucket "
                "(XLA inserts and schedules its own)"
            )
        if self.track_best and not self.validate:
            raise ValueError(
                "track_best needs validation accuracy to rank checkpoints "
                "(set validate=True, or drop track_best)"
            )
        if self.fsdp and self.spmd_mode:
            raise ValueError(
                "fsdp shards params via the auto-partitioned jit step; the "
                "spmd_mode shard_map step replicates its state specs, so the "
                "two do not compose"
            )
        if self.device_cache and self.spmd_mode:
            raise ValueError(
                "device_cache uses the auto-partitioned gather step; it does "
                "not compose with the reference-parity spmd_mode shard_map step"
            )
        if self.host_cache and self.device_cache:
            raise ValueError(
                "host_cache and device_cache are alternatives (host-RAM vs "
                "HBM residency); enable at most one"
            )
        if self.scan_epoch and not self.device_cache:
            raise ValueError(
                "scan_epoch runs the epoch as one compiled scan over the "
                "device-resident dataset; it requires device_cache=True"
            )
        if self.remat not in ("none", "full", "blocks"):
            raise ValueError(f"remat must be none|full|blocks, got {self.remat!r}")
        if self.sp_strategy not in ("none", "ring", "ulysses"):
            raise ValueError(
                f"sp_strategy must be none|ring|ulysses, got {self.sp_strategy!r}"
            )
        if self.attn_impl not in ("full", "flash", "fused-small"):
            raise ValueError(
                f"attn_impl must be full|flash|fused-small, got {self.attn_impl!r}"
            )
        # Every "flag X does not apply to model Y" rule is the registry's
        # (pp_stages joins below, once its own range is checked).
        check_build_flags(
            self.model_name,
            model_config=self.model_config,
            attn_impl=self.attn_impl,
            sp_strategy=self.sp_strategy,
            ep_mesh=self.expert_parallel or None,
            qkv_fused=self.qkv_fused,
            remat_blocks=self.remat == "blocks",
            stem_s2d=self.stem_s2d,
            fused_stem=self.fused_stem,
        )
        if spec.sample == "tokens":
            # A token model's samples are packed sequences (data/tokens.py):
            # they train from the device cache; the image loader, validation
            # and the spmd step are image paths.
            if not self.device_cache:
                raise ValueError(
                    f"{self.model_name!r} trains on token sequences held in HBM: "
                    "set device_cache=True (streaming tokens from the host is "
                    "not implemented)"
                )
            if self.validate:
                raise ValueError(
                    f"{self.model_name!r}: validation is an image path; set "
                    "validate=False"
                )
        if self.attn_impl != "full" and self.sp_strategy != "none":
            raise ValueError(
                f"attn_impl={self.attn_impl!r} is the dense-attention "
                "path (data-parallel over chips); the SP strategies "
                "(--sp-strategy) already compute attention blockwise "
                "across chips — choose one"
            )
        if self.optimizer not in ("adam", "sgd", "adamw"):
            raise ValueError(f"optimizer must be adam|sgd|adamw, got {self.optimizer!r}")
        if self.lr_schedule not in ("constant", "cosine", "warmup_cosine"):
            raise ValueError(
                "lr_schedule must be constant|cosine|warmup_cosine, "
                f"got {self.lr_schedule!r}"
            )
        # Reject silently-ignored combinations: training quietly without the
        # decay/warmup the user asked for is worse than an error.
        if self.weight_decay != 0.0 and self.optimizer != "adamw":
            raise ValueError(
                f"weight_decay={self.weight_decay} only applies to "
                f"optimizer='adamw' (got {self.optimizer!r})"
            )
        if self.warmup_steps != 0 and self.lr_schedule != "warmup_cosine":
            raise ValueError(
                f"warmup_steps={self.warmup_steps} only applies to "
                f"lr_schedule='warmup_cosine' (got {self.lr_schedule!r})"
            )
        if self.warmup_steps < 0:
            raise ValueError(f"warmup_steps must be >= 0, got {self.warmup_steps}")
        self.parsed_serve_buckets()  # raises on a malformed bucket list
        if not 1 <= self.serve_topk <= 5:
            raise ValueError(
                f"serve_topk must be in 1..5, got {self.serve_topk} (the "
                "serving contract is a handful of candidates, not a ranking "
                "of all classes)"
            )
        if self.serve_topk > self.num_classes:
            raise ValueError(
                f"serve_topk={self.serve_topk} exceeds num_classes="
                f"{self.num_classes}"
            )
        if self.serve_precision not in ("bf16", "int8", "both"):
            raise ValueError(
                f"serve_precision must be bf16|int8|both, got "
                f"{self.serve_precision!r}"
            )
        if self.serve_precision != "bf16" and self.fused_head_eval and self.serve_topk > 1:
            raise ValueError(
                f"serve_precision={self.serve_precision!r} with "
                "--fused-head-eval serves through the fused int8 head "
                "kernel, which streams argmax only — and a precision-"
                "switchable server must keep ONE response shape across its "
                f"executable sets. Set serve_topk=1 (got {self.serve_topk}) "
                "or drop --fused-head-eval for top-k int8 serving"
            )
        if self.quantize_calib < 1:
            raise ValueError(
                f"quantize_calib must be >= 1 (the int8 calibration/parity "
                f"sample batch), got {self.quantize_calib}"
            )
        if self.serve_max_wait_ms < 0:
            raise ValueError(
                f"serve_max_wait_ms must be >= 0, got {self.serve_max_wait_ms}"
            )
        if self.serve_models:
            # Parse now so a malformed tenant spec fails at config time,
            # not at the first cold swap-in (serve/zoo/registry.py).
            from mpi_pytorch_tpu.serve.zoo.registry import parse_model_specs

            specs = parse_model_specs(self.serve_models)
            if all(s.cold for s in specs):
                raise ValueError(
                    "serve_models marks every tenant :cold — a zoo host "
                    "would start serving nothing"
                )
        if self.serve_pack_budget_mb < 0:
            raise ValueError(
                f"serve_pack_budget_mb must be >= 0 (0 = unbounded), "
                f"got {self.serve_pack_budget_mb}"
            )
        if self.serve_pack_budget_mb and not self.serve_models:
            raise ValueError(
                "serve_pack_budget_mb bounds the multi-tenant packing "
                "plan and needs serve_models (single-model serving has "
                "no packing axis)"
            )
        if self.serve_queue_depth < 1:
            raise ValueError(
                f"serve_queue_depth must be >= 1, got {self.serve_queue_depth}"
            )
        if self.serve_shard_degree < 1:
            raise ValueError(
                f"serve_shard_degree must be >= 1 (1 = replicated), "
                f"got {self.serve_shard_degree}"
            )
        if self.serve_shard_degree > 1 and self.serve_models:
            raise ValueError(
                "serve_shard_degree is the single-model model-parallel "
                "knob; zoo tenants pick residency per-spec (shard=K) or "
                "from the packing planner"
            )
        if self.serve_pipe_stages < 1:
            raise ValueError(
                f"serve_pipe_stages must be >= 1 (1 = no pipelining), "
                f"got {self.serve_pipe_stages}"
            )
        if self.serve_pipe_microbatches < 1:
            raise ValueError(
                f"serve_pipe_microbatches must be >= 1, "
                f"got {self.serve_pipe_microbatches}"
            )
        if self.serve_pipe_stages > 1 and self.serve_models:
            raise ValueError(
                "serve_pipe_stages is the single-model pipeline knob; zoo "
                "tenants pick residency per-spec (shard=pipe:K) or from "
                "the packing planner"
            )
        if self.serve_pipe_stages > 1 and self.serve_shard_degree > 1:
            raise ValueError(
                "serve_pipe_stages and serve_shard_degree are mutually "
                "exclusive residencies — a host serves pipeline-parallel "
                "OR model-parallel, not both"
            )
        if self.serve_fleet_hosts < 0:
            raise ValueError(
                f"serve_fleet_hosts must be >= 0 (0 = single-host serving), "
                f"got {self.serve_fleet_hosts}"
            )
        # The silently-ignored-combination rule: every fleet knob below is
        # only read by FleetServer, so setting one without a fleet would
        # quietly do nothing.
        if self.serve_fleet_hosts == 0:
            for knob in (
                "serve_fleet_spare", "serve_target_p99_ms",
                "serve_admission_tokens", "serve_autoscale",
                "trace_sample_rate", "trace_slow_ms",
                "serve_collect_interval_s", "fleet_trace_file",
            ):
                if getattr(self, knob):
                    raise ValueError(
                        f"{knob} configures the serve fleet and needs "
                        "serve_fleet_hosts > 0 (it is read by the fleet "
                        "harness only — without a fleet it would be "
                        "silently ignored)"
                    )
        if not 0.0 <= self.trace_sample_rate <= 1.0:
            raise ValueError(
                f"trace_sample_rate must be in [0, 1] (the head-sample "
                f"keep fraction), got {self.trace_sample_rate}"
            )
        if self.trace_slow_ms < 0:
            raise ValueError(
                f"trace_slow_ms must be >= 0 (0 = no slow criterion), "
                f"got {self.trace_slow_ms}"
            )
        if self.serve_collect_interval_s < 0:
            raise ValueError(
                f"serve_collect_interval_s must be >= 0 (0 = collector "
                f"off), got {self.serve_collect_interval_s}"
            )
        if self.fleet_trace_file and self.serve_collect_interval_s <= 0:
            raise ValueError(
                "fleet_trace_file is written by the FleetCollector — set "
                "serve_collect_interval_s > 0 (without the collector the "
                "file would silently stay empty)"
            )
        if self.serve_probe_interval_ms <= 0:
            raise ValueError(
                f"serve_probe_interval_ms must be > 0, "
                f"got {self.serve_probe_interval_ms}"
            )
        if self.serve_fail_probes < 1:
            raise ValueError(
                f"serve_fail_probes must be >= 1, got {self.serve_fail_probes}"
            )
        if self.serve_admission_tokens < 0:
            raise ValueError(
                f"serve_admission_tokens must be >= 0 (0 = auto), "
                f"got {self.serve_admission_tokens}"
            )
        if self.serve_target_p99_ms < 0:
            raise ValueError(
                f"serve_target_p99_ms must be >= 0 (0 = controller off), "
                f"got {self.serve_target_p99_ms}"
            )
        if self.serve_retune_interval_s <= 0:
            raise ValueError(
                f"serve_retune_interval_s must be > 0, "
                f"got {self.serve_retune_interval_s}"
            )
        # --- remote transport / autoscaler (ISSUE 12) ---
        if self.serve_port < 0:
            raise ValueError(
                f"serve_port must be >= 0 (0 = ephemeral), got "
                f"{self.serve_port}"
            )
        if self.serve_connect_timeout_s <= 0 or self.serve_read_timeout_s <= 0:
            raise ValueError(
                "serve_connect_timeout_s and serve_read_timeout_s must be "
                f"> 0, got {self.serve_connect_timeout_s}/"
                f"{self.serve_read_timeout_s}"
            )
        if self.serve_probe_retries < 0:
            raise ValueError(
                f"serve_probe_retries must be >= 0 (0 = single attempt), "
                f"got {self.serve_probe_retries}"
            )
        # --- tail-at-scale data plane (ISSUE 16) ---
        if self.serve_transport not in ("http", "framed"):
            raise ValueError(
                f"serve_transport must be http|framed, "
                f"got {self.serve_transport!r}"
            )
        if self.serve_hedge_factor <= 1.0:
            raise ValueError(
                "serve_hedge_factor must be > 1.0 (a hedge at or below "
                f"p99 duplicates the median request), "
                f"got {self.serve_hedge_factor}"
            )
        if self.serve_hedge_floor_ms <= 0:
            raise ValueError(
                f"serve_hedge_floor_ms must be > 0, "
                f"got {self.serve_hedge_floor_ms}"
            )
        if not self.serve_hedge:
            # The silently-ignored rule: the hedge policy knobs are only
            # read by the router's hedge timer.
            if (self.serve_hedge_factor != 3.0
                    or self.serve_hedge_floor_ms != 20.0):
                raise ValueError(
                    "serve_hedge_factor/serve_hedge_floor_ms configure "
                    "request hedging and need --serve-hedge true (without "
                    "it they would be silently ignored)"
                )
        elif self.serve_fleet_hosts < 2:
            raise ValueError(
                "serve_hedge needs >= 2 fleet hosts (--serve-fleet-hosts) "
                "— with one host there is never a second-best host to "
                "hedge to, and the knob would be silently ignored"
            )
        if not self.serve_autoscale:
            # The silently-ignored rule again: the scaler bounds are only
            # read by FleetAutoscaler.
            defaults = {
                "serve_fleet_min_hosts": 1, "serve_fleet_max_hosts": 8,
                "serve_scale_cooldown_s": 30.0,
                "serve_scale_reject_rate": 0.5,
            }
            for knob, default in defaults.items():
                if getattr(self, knob) != default:
                    raise ValueError(
                        f"{knob} configures the fleet autoscaler and needs "
                        "--serve-autoscale true (without it the knob would "
                        "be silently ignored)"
                    )
        else:
            if self.serve_fleet_min_hosts < 1:
                raise ValueError(
                    f"serve_fleet_min_hosts must be >= 1, got "
                    f"{self.serve_fleet_min_hosts}"
                )
            if self.serve_fleet_max_hosts < self.serve_fleet_min_hosts:
                raise ValueError(
                    f"serve_fleet_max_hosts ({self.serve_fleet_max_hosts}) "
                    f"must be >= serve_fleet_min_hosts "
                    f"({self.serve_fleet_min_hosts})"
                )
            if self.serve_scale_cooldown_s < 0:
                raise ValueError(
                    f"serve_scale_cooldown_s must be >= 0, got "
                    f"{self.serve_scale_cooldown_s}"
                )
            if self.serve_scale_reject_rate < 0:
                raise ValueError(
                    f"serve_scale_reject_rate must be >= 0, got "
                    f"{self.serve_scale_reject_rate}"
                )
        if self.serve_canary_probes < 0:
            raise ValueError(
                f"serve_canary_probes must be >= 0 (0 disables the quality "
                f"canary), got {self.serve_canary_probes}"
            )
        if not self.serve_canary_probes:
            # The silently-ignored rule: the canary policy knobs are only
            # read by CanaryGate/CanaryProber.
            defaults = {
                "serve_canary_interval_s": 0.0,
                "serve_canary_min_top1": 0.95,
                "serve_canary_fail_after": 2, "serve_canary_pass_after": 2,
            }
            for knob, default in defaults.items():
                if getattr(self, knob) != default:
                    raise ValueError(
                        f"{knob} configures the quality canary and needs "
                        "--serve-canary-probes > 0 (without it the knob "
                        "would be silently ignored)"
                    )
        else:
            if self.serve_canary_interval_s < 0:
                raise ValueError(
                    f"serve_canary_interval_s must be >= 0 (0 = passive, "
                    f"drive probe_once), got {self.serve_canary_interval_s}"
                )
            if not 0.0 < self.serve_canary_min_top1 <= 1.0:
                raise ValueError(
                    f"serve_canary_min_top1 must be in (0, 1], got "
                    f"{self.serve_canary_min_top1}"
                )
            if self.serve_canary_fail_after < 1:
                raise ValueError(
                    f"serve_canary_fail_after must be >= 1, got "
                    f"{self.serve_canary_fail_after}"
                )
            if self.serve_canary_pass_after < 1:
                raise ValueError(
                    f"serve_canary_pass_after must be >= 1, got "
                    f"{self.serve_canary_pass_after}"
                )
        if self.serve_drift_window < 0:
            raise ValueError(
                f"serve_drift_window must be >= 0 (0 disables drift "
                f"detection), got {self.serve_drift_window}"
            )
        if not self.serve_drift_window:
            # Same rule for the drift thresholds: only DriftMonitor reads
            # them.
            defaults = {
                "serve_drift_psi": 0.25, "serve_drift_chi2": 10.0,
                "serve_drift_cusum_h": 8.0,
            }
            for knob, default in defaults.items():
                if getattr(self, knob) != default:
                    raise ValueError(
                        f"{knob} configures drift detection and needs "
                        "--serve-drift-window > 0 (without it the knob "
                        "would be silently ignored)"
                    )
        else:
            if self.serve_drift_window < 8:
                raise ValueError(
                    f"serve_drift_window must be >= 8 for a meaningful "
                    f"histogram compare, got {self.serve_drift_window}"
                )
            if self.serve_drift_psi <= 0:
                raise ValueError(
                    f"serve_drift_psi must be > 0, got {self.serve_drift_psi}"
                )
            if self.serve_drift_chi2 <= 0:
                raise ValueError(
                    f"serve_drift_chi2 must be > 0, "
                    f"got {self.serve_drift_chi2}"
                )
            if self.serve_drift_cusum_h <= 0:
                raise ValueError(
                    f"serve_drift_cusum_h must be > 0, "
                    f"got {self.serve_drift_cusum_h}"
                )
        if self.resume_retries < 0:
            raise ValueError(
                f"resume_retries must be >= 0 (0 = one attempt, no retry), "
                f"got {self.resume_retries}"
            )
        if self.resume_backoff_s < 0:
            raise ValueError(
                f"resume_backoff_s must be >= 0, got {self.resume_backoff_s}"
            )
        if self.preempt_straggler_beats < 0:
            raise ValueError(
                f"preempt_straggler_beats must be >= 0 (0 disables), "
                f"got {self.preempt_straggler_beats}"
            )
        if self.preempt_nonfinite_steps < 0:
            raise ValueError(
                f"preempt_nonfinite_steps must be >= 0 (0 disables), "
                f"got {self.preempt_nonfinite_steps}"
            )
        if self.preempt_straggler_beats > 0 and self.heartbeat_every_steps <= 0:
            raise ValueError(
                "preempt_straggler_beats counts heartbeat beats; it needs "
                "--heartbeat-every-steps > 0 to ever observe one"
            )
        if self.preempt_nonfinite_steps > 0 and not self.step_metrics:
            raise ValueError(
                "preempt_nonfinite_steps counts per-step grad norms; it "
                "needs --step-metrics true to ever observe one"
            )
        if self.bad_step_policy not in ("abort", "skip", "rollback"):
            raise ValueError(
                f"bad_step_policy must be abort|skip|rollback, "
                f"got {self.bad_step_policy!r}"
            )
        if self.max_skipped_steps < 1:
            raise ValueError(
                f"max_skipped_steps must be >= 1, got {self.max_skipped_steps}"
            )
        if self.rollback_nonfinite_steps < 1:
            raise ValueError(
                f"rollback_nonfinite_steps must be >= 1, "
                f"got {self.rollback_nonfinite_steps}"
            )
        if self.rollback_loss_drift != 0.0 and self.rollback_loss_drift <= 1.0:
            raise ValueError(
                "rollback_loss_drift is a ratio vs the warmup-baseline loss "
                f"and must be > 1.0 (0 disables), got {self.rollback_loss_drift}"
            )
        if self.rollback_drift_warmup < 1:
            raise ValueError(
                f"rollback_drift_warmup must be >= 1, "
                f"got {self.rollback_drift_warmup}"
            )
        if self.max_rollbacks < 1:
            raise ValueError(
                f"max_rollbacks must be >= 1, got {self.max_rollbacks}"
            )
        if not 0.0 < self.rollback_lr_backoff <= 1.0:
            raise ValueError(
                "rollback_lr_backoff is a per-rollback LR scale in (0, 1] "
                f"(1.0 = no backoff), got {self.rollback_lr_backoff}"
            )
        if self.bad_step_policy == "rollback":
            if self.scan_epoch:
                raise ValueError(
                    "bad_step_policy='rollback' watches per-step host "
                    "values; scan_epoch runs the whole epoch as one "
                    "device-side scan with no step boundaries — use "
                    "bad_step_policy='skip' (guarded inside the scan) or "
                    "drop scan_epoch"
                )
            if self.checkpoint_every_epochs < 1:
                raise ValueError(
                    "bad_step_policy='rollback' restores the last good "
                    "checkpoint; it needs checkpoint_every_epochs >= 1 to "
                    "ever have one"
                )
        if self.max_bad_samples < 0:
            raise ValueError(
                f"max_bad_samples must be >= 0, got {self.max_bad_samples}"
            )
        if self.heartbeat_every_steps < 0:
            raise ValueError(
                f"heartbeat_every_steps must be >= 0 (0 disables), "
                f"got {self.heartbeat_every_steps}"
            )
        if self.metrics_every_steps < 0:
            raise ValueError(
                f"metrics_every_steps must be >= 0 (0 disables), "
                f"got {self.metrics_every_steps}"
            )
        if self.flight_records < 1:
            raise ValueError(
                f"flight_records must be >= 1, got {self.flight_records}"
            )
        if self.flight_profile_window_s < 0:
            raise ValueError(
                f"flight_profile_window_s must be >= 0, "
                f"got {self.flight_profile_window_s}"
            )
        if self.serve_metrics_port < -1:
            raise ValueError(
                "serve_metrics_port must be -1 (ephemeral), 0 (off), or a "
                f"port number, got {self.serve_metrics_port}"
            )
        if self.slo_rules and self.scan_epoch:
            raise ValueError(
                "slo_rules are evaluated at per-step host boundaries; "
                "scan_epoch runs the whole epoch as one device-side scan "
                "with no step boundaries, so the rules would silently "
                "never evaluate — drop one of the two"
            )
        if self.slo_rules:
            # Parse now so a malformed rule fails the run at config time,
            # not silently mid-training; dependency-free import.
            from mpi_pytorch_tpu.obs.monitor import parse_rules

            rules = parse_rules(self.slo_rules)
            if any("preempt" in r.actions for r in rules) and not (
                self.preempt_file or os.environ.get("MPT_PREEMPT_FILE")
            ):
                raise ValueError(
                    "an SLO rule requests action=preempt but no preemption "
                    "sentinel path is configured — set --preempt-file or "
                    "MPT_PREEMPT_FILE so the watchdog has a file to watch"
                )
            # A rule over a metric whose publisher is off would silently
            # never evaluate — the same silently-ignored-combination class
            # validate_config rejects elsewhere (preempt_nonfinite_steps
            # needs --step-metrics; fused-head silent degrade, advisor r5).
            # The name sets live NEXT TO their registrations so a new
            # gauge cannot silently escape this check.
            from mpi_pytorch_tpu.obs.health import STEP_GAUGES
            from mpi_pytorch_tpu.obs.heartbeat import BEAT_GAUGES

            step_only = set(STEP_GAUGES)
            beat_only = set(BEAT_GAUGES)
            for r in rules:
                base = r.metric.split(":")[0]
                if base in step_only and not self.step_metrics:
                    raise ValueError(
                        f"SLO rule {r.name!r} reads {base!r}, which is only "
                        "published with --step-metrics true (obs/health.py)"
                    )
                if base in beat_only and self.heartbeat_every_steps <= 0:
                    raise ValueError(
                        f"SLO rule {r.name!r} reads {base!r}, which is only "
                        "published with --heartbeat-every-steps > 0 "
                        "(obs/heartbeat.py)"
                    )
        if self.straggler_threshold <= 1.0:
            raise ValueError(
                "straggler_threshold is a multiple of the median step time "
                f"and must be > 1.0, got {self.straggler_threshold}"
            )
        if self.stem_s2d and (self.width % 2 or self.height % 2):
            raise ValueError(
                "stem_s2d folds 2×2 spatial patches into channels and "
                f"requires even image dims, got {self.width}x{self.height}"
            )
        if self.fused_stem:
            # conv1 output dim: 7×7/s2/p3 → (N-1)//2 + 1; with stem_s2d
            # the equivalent 4×4/s1 conv gives N/2 (even N already required).
            def post_conv(n: int) -> int:
                return n // 2 if self.stem_s2d else (n - 1) // 2 + 1

            if post_conv(self.width) % 2 or post_conv(self.height) % 2:
                raise ValueError(
                    "fused_stem needs even post-conv spatial dims; "
                    f"{self.width}x{self.height} gives "
                    f"{post_conv(self.width)}x{post_conv(self.height)}"
                )
        if self.accum_steps < 1:
            raise ValueError(f"accum_steps must be >= 1, got {self.accum_steps}")
        if self.accum_steps > 1 and (self.spmd_mode or self.device_cache):
            raise ValueError(
                "accum_steps > 1 is implemented for the streaming auto-"
                "partitioned step only (not spmd_mode / device_cache)"
            )
        if self.accum_steps > 1 and self.batch_size % self.accum_steps != 0:
            raise ValueError(
                f"batch_size {self.batch_size} not divisible by "
                f"accum_steps {self.accum_steps}"
            )
        if spec.required_size and (self.width, self.height) not in (
            (128, 128),  # the untouched default: image_size upgrades it
            (spec.required_size,) * 2,
        ):
            raise ValueError(
                f"{self.model_name} requires {spec.required_size}x"
                f"{spec.required_size} inputs (aux-logits pooling); "
                f"an explicit --width/--height/--image-size of "
                f"{self.width}x{self.height} would be silently overridden — "
                f"drop the flag or pass {spec.required_size}"
            )
        if self.spmd_mode and self.mesh.model_parallel > 1:
            raise ValueError(
                "spmd_mode is pure data-parallel (reference-parity shard_map step); "
                "its replicated in/out specs would silently gather the TP-sharded "
                "head. Use the default auto mode for mesh.model_parallel > 1."
            )
        if self.mesh.pods > 1:
            # Cross-pod hierarchical training (ISSUE 15): the two-phase
            # ICI/DCN collectives live in the spmd shard_map step — the
            # auto-partitioned jit step has no explicit collective to
            # decompose (XLA schedules its own), so a nested mesh there
            # would change nothing but the axis names.
            if not self.spmd_mode:
                raise ValueError(
                    "mesh pods > 1 (hierarchical ICI/DCN gradient sync) "
                    "requires spmd_mode: the two-phase collectives are "
                    "explicit shard_map collectives (train/step.py)"
                )
            if self.pp_stages > 1:
                raise ValueError(
                    "mesh pods > 1 does not compose with pp_stages (the "
                    "nested data axis and the pipe axis claim the same "
                    "mesh reshape)"
                )
        if self.pp_stages < 1:
            raise ValueError(f"pp_stages must be >= 1, got {self.pp_stages}")
        if self.pp_microbatches < 0:
            raise ValueError(
                f"pp_microbatches must be >= 0 (0 = default), got {self.pp_microbatches}"
            )
        if self.pp_microbatches and self.pp_stages <= 1:
            raise ValueError("pp_microbatches only applies with pp_stages > 1")
        if self.pp_stages > 1:
            check_build_flags(self.model_name, pp_stages=self.pp_stages)
            if self.spmd_mode:
                raise ValueError(
                    "pp_stages > 1 requires the auto-partitioned step "
                    "(spmd_mode is pure reference-parity data parallelism)"
                )
            if self.sp_strategy != "none":
                raise ValueError(
                    "pp_stages > 1 cannot nest the SP attention strategies "
                    "inside pipeline stages (both shard the same devices); "
                    "choose one of --pp-stages / --sp-strategy"
                )
            if self.accum_steps > 1:
                raise ValueError(
                    "pp_stages > 1 already microbatches the step (GPipe); "
                    "combine with --pp-microbatches instead of --accum-steps"
                )
            if self.remat == "full":
                raise ValueError(
                    "pp_stages > 1 supports remat='blocks' (per-stage "
                    "rematerialization inside the pipeline) or 'none', "
                    "not 'full'"
                )
            if self.fsdp or self.zero_optimizer:
                raise ValueError(
                    "pp_stages > 1 with fsdp/zero_optimizer would re-gather "
                    "the data-axis-sharded trunk params into the pipeline's "
                    "P(pipe) layout every step — the full unsharded stack per "
                    "device, defeating exactly the memory saving the sharding "
                    "buys. The pipeline already splits trunk memory S ways; "
                    "choose one of --pp-stages / --fsdp / --zero-optimizer"
                )
            # Normalize the default HERE, once: the trainer, the eval driver,
            # and this validation all read the resolved value afterwards.
            self.pp_microbatches = self.pp_microbatches or 2 * self.pp_stages
            if self.batch_size % self.pp_microbatches:
                raise ValueError(
                    f"batch_size {self.batch_size} not divisible by "
                    f"pp_microbatches {self.pp_microbatches}"
                )
            # pp_stages drives the mesh layout: one stage per device along
            # the pipe axis (DP fills the remaining devices).
            self.mesh.pipe_parallel = self.pp_stages
        self.mesh.validate()

    @property
    def image_size(self) -> tuple[int, int]:
        """Resize target. The reference always resizes to WIDTH×HEIGHT=128×128
        regardless of each architecture's canonical input (``main.py:64`` vs
        ``models.py:37,54,95``) — except inception_v3, which *requires* >=299
        and is latently broken in the reference (SURVEY §3 quirks). We keep
        128×128 for the six and use 299×299 for inception so it actually works.
        """
        from mpi_pytorch_tpu.models.registry import model_spec

        required = model_spec(self.model_name).required_size
        return (required, required) if required else (self.height, self.width)

    def parsed_compiler_options(self) -> dict[str, Any] | None:
        """``compiler_options`` as the dict jax's ``Lowered.compile`` takes,
        or None when unset."""
        return parse_compiler_options(self.compiler_options)

    def parsed_serve_buckets(self) -> tuple[int, ...]:
        """``serve_buckets`` as a sorted deduped tuple of positive ints —
        the bucket set the server AOT-compiles one executable per entry of.
        Raises on an empty or non-positive list."""
        try:
            buckets = sorted(
                {int(b) for b in self.serve_buckets.replace(";", ",").split(",") if b.strip()}
            )
        except ValueError:
            raise ValueError(
                f"serve_buckets must be comma-separated ints, got "
                f"{self.serve_buckets!r}"
            ) from None
        if not buckets or buckets[0] < 1:
            raise ValueError(
                f"serve_buckets needs at least one positive size, got "
                f"{self.serve_buckets!r}"
            )
        return tuple(buckets)

    def parsed_serve_precisions(self) -> tuple[str, ...]:
        """``serve_precision`` as the tuple of executable sets to compile
        at startup — ONE definition of the bf16|int8|both mapping, shared
        by InferenceServer and FleetServer (``validate_config`` rejects
        anything else first)."""
        return {
            "bf16": ("bf16",), "int8": ("int8",),
            "both": ("bf16", "int8"),
        }[self.serve_precision]


def parse_compiler_options(text: str) -> dict[str, Any] | None:
    """"k=v k2=v2" (comma- or space-separated; leading "--" tolerated) →
    the dict jax's ``Lowered.compile(compiler_options=...)`` takes, or None
    for an empty string. XLA's option setter wants REAL types — a "true"
    string raises "'true' is not a valid bool value", observed live — so
    values are coerced: true/false/bare → bool, digits → int, rest → str.
    Single source of truth for the trainer's --compiler-options and
    tools/bench_flags.py --flags."""
    if not text.strip():
        return None
    opts: dict[str, Any] = {}
    for item in text.replace(",", " ").split():
        k, _, v = item.partition("=")
        if v.lower() in ("", "true", "false"):
            val: Any = v.lower() != "false"
        else:
            try:
                val = int(v)
            except ValueError:
                val = v
        opts[k.lstrip("-")] = val
    return opts


def apply_runtime_flags(cfg: Config) -> None:
    """Apply config knobs that live in the JAX runtime rather than in our own
    code. Called by the train/eval drivers (and the serve startup) before
    any compilation."""
    import jax

    # Unconditional so a later run in the same process with the flag off
    # isn't stuck with the previous run's setting.
    jax.config.update("jax_debug_nans", cfg.debug_nans)
    enable_compilation_cache()


# The persistent compile cache's home when JAX_COMPILATION_CACHE_DIR is not
# set: ONE fixed path inside the checkout (gitignored). Fixed because the
# cache only ever hits at the path it was written under — a temp dir, pid or
# timestamp in the name is a cache that never hits.
DEFAULT_COMPILATION_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)


def enable_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache for this process and
    return its directory. THE one owner of the cache's placement, called
    before the first compile of every entry point (``apply_runtime_flags``,
    ``bench.py``, the serve start-up):

    - ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it; no directory
      is set in code, so whoever placed the variable (the chip driver, a
      parent giving its children a shared or an isolated cache) wins.
    - unset: ``DEFAULT_COMPILATION_CACHE_DIR``.

    JAX opens the cache at the first compile that finds a directory
    configured and keeps it for the life of the process, so this must run
    BEFORE any probe computation — a later call cannot move the cache.

    The thresholds are zeroed deliberately: this repo's repeat-run cost is
    many medium compiles (one per serve bucket, per eval shape, per bench
    leg), each individually below jax's default 1 s floor — with the
    defaults a populated cache would still recompile everything."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_COMPILATION_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax.config.jax_compilation_cache_dir


def _add_dataclass_args(parser: argparse.ArgumentParser, cls: type, prefix: str = "") -> None:
    for f in dataclasses.fields(cls):
        name = f"--{prefix}{f.name.replace('_', '-')}"
        if dataclasses.is_dataclass(f.type) or dataclasses.is_dataclass(getattr(f, "default_factory", None)):
            _add_dataclass_args(parser, f.default_factory, prefix=f"{f.name}.")  # type: ignore[arg-type]
            continue
        if f.type in (bool, "bool"):
            parser.add_argument(name, type=_str2bool, default=None, metavar="BOOL")
        elif f.type in (int, "int"):
            parser.add_argument(name, type=int, default=None)
        elif f.type in (float, "float"):
            parser.add_argument(name, type=float, default=None)
        elif f.type in (str, "str"):
            parser.add_argument(name, type=str, default=None)
        # tuples/other types are not CLI-exposed


def _str2bool(v: str) -> bool:
    if v.lower() in ("1", "true", "yes", "on"):
        return True
    if v.lower() in ("0", "false", "no", "off"):
        return False
    raise argparse.ArgumentTypeError(f"expected boolean, got {v!r}")


def parse_config(argv: Sequence[str] | None = None, **overrides: Any) -> Config:
    """Build a Config from defaults < env (MPT_*) < CLI flags < explicit overrides."""
    cfg = Config()

    # env overrides: MPT_BATCH_SIZE=64 etc.
    casters = {bool: _str2bool, "bool": _str2bool, int: int, "int": int,
               float: float, "float": float, str: str, "str": str}
    for f in dataclasses.fields(Config):
        env_key = f"MPT_{f.name.upper()}"
        if env_key in os.environ and f.type in casters:
            setattr(cfg, f.name, casters[f.type](os.environ[env_key]))
    # Env counterpart of the --image-size alias. Like the CLI, the per-dim
    # form wins: MPT_WIDTH/MPT_HEIGHT each beat MPT_IMAGE_SIZE for their dim.
    if "MPT_IMAGE_SIZE" in os.environ:
        size = int(os.environ["MPT_IMAGE_SIZE"])
        if "MPT_WIDTH" not in os.environ:
            cfg.width = size
        if "MPT_HEIGHT" not in os.environ:
            cfg.height = size

    parser = argparse.ArgumentParser(description="mpi_pytorch_tpu")
    _add_dataclass_args(parser, Config)
    # Convenience alias: one flag for square inputs (sets width AND height).
    parser.add_argument("--image-size", type=int, default=None, dest="image_size_alias")
    # Alias for the nested-mesh pod count (ISSUE 15's documented spelling;
    # equivalent to --mesh.pods).
    parser.add_argument("--mesh-pods", type=int, default=None, dest="mesh_pods_alias")
    # STRICT parsing: an unknown flag must error, not be silently dropped —
    # a typo'd --batchsize otherwise trains with the default and no warning.
    args = parser.parse_args(argv)
    ns = vars(args)
    alias = ns.pop("image_size_alias", None)
    if alias is not None:
        cfg.width = cfg.height = alias
    pods_alias = ns.pop("mesh_pods_alias", None)
    if pods_alias is not None:
        cfg.mesh.pods = pods_alias
    for key, val in ns.items():
        if val is None:
            continue
        if "." in key:
            scope, leaf = key.split(".", 1)
            setattr(getattr(cfg, scope), leaf, val)
        else:
            setattr(cfg, key, val)

    for key, val in overrides.items():
        if "." in key:
            scope, leaf = key.split(".", 1)
            setattr(getattr(cfg, scope), leaf, val)
        else:
            setattr(cfg, key, val)

    # Explicit-dimension check that validate_config cannot do (the dataclass
    # can't tell an explicit 128 from the untouched default): any explicitly
    # requested size other than the one a model requires (inception_v3's
    # 299) errors — including 128, which the image_size property would
    # otherwise silently upgrade.
    dims_explicit = (
        alias is not None
        or ns.get("width") is not None
        or ns.get("height") is not None
        or any(k in os.environ for k in ("MPT_IMAGE_SIZE", "MPT_WIDTH", "MPT_HEIGHT"))
    )
    from mpi_pytorch_tpu.models.registry import model_spec

    required = model_spec(cfg.model_name).required_size
    if required and dims_explicit and (cfg.width, cfg.height) != (required, required):
        raise ValueError(
            f"{cfg.model_name} requires {required}x{required} inputs "
            f"(aux-logits pooling); the requested {cfg.width}x{cfg.height} "
            f"would be silently overridden — drop the size flags or pass {required}"
        )

    cfg.validate_config()
    return cfg
