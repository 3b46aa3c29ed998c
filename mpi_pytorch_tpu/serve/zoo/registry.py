"""The model-zoo registry: tenant specs + the VMEM/HBM-aware packing plan.

The reference ships seven torchvision CNNs (``models.py``) but its
inference pipeline — and ours, until ISSUE 14 — serves exactly one
checkpoint per deployment. This module makes *model identity* a
first-class serving dimension: a ``ModelSpec`` names one TENANT (a model
the fleet serves — architecture, checkpoint, precision, bucket set,
admission budget), the ``ModelRegistry`` holds the zoo, and
``plan_packing`` decides which (model, bucket) executable sets fit
together on one host under an explicit byte budget — the same leaf-size
accounting discipline PR 6 used for the ZeRO optimizer-state HBM math,
applied to the serving side.

The plan is EXPLAINABLE and stamped on records: every cold-model swap-in
(``zoo/server.py``) carries ``plan.to_record()`` — which tenants are
resident, what each costs, what the budget was — so "why did tenant X
get evicted" is answerable from the metrics stream, not from a debugger.

Spec syntax (the ``--serve-models`` / ``bench_serve --models`` string) —
comma-separated tenants, each ``[alias=]arch[:key=value]*``::

    resnet18,mobilenet_v2
    hot=resnet18:admission=8,mobilenet_v2:precision=int8:cold
    resnet18:ckpt=/ckpts/resnet18:buckets=1|8|32

Keys: ``ckpt`` (checkpoint dir), ``precision`` (bf16|int8|both),
``buckets`` (``|``-separated sizes — ``,`` is the tenant separator),
``admission`` (per-tenant front-door token budget; 0 = an equal share of
the fleet budget), ``cold`` (don't build at startup; the first routed
request cold-swaps the model in from the persistent compilation cache),
``shard`` (model-parallel residency, ISSUE 17/20: ``K``/``fsdpK`` = FSDP
over K chips, ``tpK`` = head-only tensor parallelism, ``pipeK`` =
pipeline stages over K chip groups — ``:`` can't appear inside an
option, so the spec syntax is ``shard=fsdp4``, not ``shard=fsdp:4``).
An alias lets two tenants share an architecture (A/B checkpoints).

The planner itself holds a THIRD residency option beyond
resident-replicated and evicted: when the resident set is over budget,
``plan_packing`` tries converting the largest replicated tenants to
``fsdp:K`` (per-chip bytes ≈ params/K) before the caller reaches for
eviction — and ``plan.explain()`` shows the per-chip arithmetic that
made sharding win.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field

from mpi_pytorch_tpu.serve.batcher import ServeError, UnknownModelError

__all__ = [
    "ModelRegistry", "ModelSpec", "PackingError", "PackingPlan",
    "PlanEntry", "UnknownModelError", "estimate_model_bytes",
    "parse_model_specs",
]


class PackingError(ServeError):
    """A tenant spec cannot fit the packing budget even alone (or the
    resident set cannot be made to fit by evicting idle tenants) — the
    loud rejection the planner owes the operator, with the plan's
    arithmetic in the message."""


@dataclass(frozen=True)
class ModelSpec:
    """One serving tenant: the unit of routing, admission, and retuning."""

    model: str  # tenant name (the routing key; defaults to the arch)
    arch: str  # architecture (models/registry.available_models)
    checkpoint_dir: str = ""  # "" = serve fresh init (smoke/CI) or cfg's
    precision: str = ""  # "" = the fleet cfg's serve_precision
    buckets: str = ""  # "" = the fleet cfg's serve_buckets
    admission: int = 0  # per-tenant front-door tokens; 0 = equal share
    cold: bool = False  # True = not built at startup; swap-in on demand
    shard: str = ""  # "" = replicated; else "tp:K"/"fsdp:K"/"pipe:K"


def parse_model_specs(text: str) -> tuple[ModelSpec, ...]:
    """``--serve-models`` string → validated specs (see module docstring
    for the syntax). Raises ``ValueError`` on malformed entries, unknown
    architectures, or duplicate tenant names."""
    from mpi_pytorch_tpu.models.registry import available_models, model_spec

    specs: list[ModelSpec] = []
    for entry in (e.strip() for e in text.split(",") if e.strip()):
        head, *opts = entry.split(":")
        alias, _, arch = head.rpartition("=")
        arch = arch.strip()
        name = alias.strip() or arch
        kwargs: dict = {}
        for opt in opts:
            key, _, value = opt.partition("=")
            key = key.strip()
            if key == "cold" and not value:
                kwargs["cold"] = True
            elif key == "ckpt":
                kwargs["checkpoint_dir"] = value
            elif key == "precision":
                if value not in ("bf16", "int8", "both"):
                    raise ValueError(
                        f"tenant {name!r}: precision must be "
                        f"bf16|int8|both, got {value!r}"
                    )
                kwargs["precision"] = value
            elif key == "buckets":
                kwargs["buckets"] = value.replace("|", ",")
            elif key == "admission":
                kwargs["admission"] = int(value)
            elif key == "shard":
                import re

                m = re.fullmatch(r"(tp|fsdp|pipe)?(\d+)", value.strip().lower())
                if not m or int(m.group(2)) < 2:
                    raise ValueError(
                        f"tenant {name!r}: shard must be K, tpK, fsdpK or "
                        f"pipeK with K >= 2 (got {value!r}); ':' can't "
                        "appear inside a spec option, so shard=fsdp4 means "
                        "fsdp:4"
                    )
                kwargs["shard"] = f"{m.group(1) or 'fsdp'}:{m.group(2)}"
            else:
                raise ValueError(
                    f"tenant {name!r}: unknown spec key {key!r} (expected "
                    "ckpt|precision|buckets|admission|cold|shard)"
                )
        if arch not in available_models():
            raise ValueError(
                f"tenant {name!r}: unsupported architecture {arch!r}; "
                f"expected one of {available_models()}"
            )
        if model_spec(arch).sample == "tokens":
            raise ValueError(
                f"tenant {name!r}: {arch!r} is a token model; serving takes "
                "image requests only"
            )
        if kwargs.get("admission", 0) < 0:
            raise ValueError(
                f"tenant {name!r}: admission must be >= 0 (0 = equal "
                f"share), got {kwargs['admission']}"
            )
        specs.append(ModelSpec(model=name, arch=arch, **kwargs))
    if not specs:
        raise ValueError("serve_models parsed to zero tenants")
    names = [s.model for s in specs]
    dupes = sorted({n for n in names if names.count(n) > 1})
    if dupes:
        raise ValueError(
            f"duplicate tenant name(s) {dupes} — alias them "
            "(e.g. 'a=resnet18,b=resnet18')"
        )
    return tuple(specs)


# --------------------------------------------------------------- byte math


def _spec_param_bytes(shapes, precision: str) -> int:
    """Leaf-size accounting over an abstract variables tree (PR 6's HBM
    discipline): f32 resident params, except int8 tenants whose >=2-D
    kernels quantize to 1 byte/element + a 4-byte scale per output
    channel (``ops/quantize.py``'s layout)."""
    import jax

    total = 0
    for leaf in jax.tree_util.tree_leaves(shapes):
        n = 1
        for d in leaf.shape:
            n *= int(d)
        if precision == "int8" and len(leaf.shape) >= 2:
            total += n + 4 * int(leaf.shape[-1])  # int8 kernel + scales
        else:
            total += n * 4  # f32 resident
    return total


def _sharded_param_bytes(shapes, precision: str, residency) -> tuple[int, int]:
    """Per-CHIP ``(param_bytes, scale_overhead_bytes)`` under a sharded
    residency: leaves the residency divides cost 1/K per chip, per-channel
    int8 scales stay whole on every chip (they ride each shard's dequant),
    non-divisible leaves stay replicated. TP divides only the head
    (``is_head_kernel`` — the trainer's rule), FSDP any K-divisible dim."""
    import jax

    from mpi_pytorch_tpu.parallel.mesh import is_head_kernel

    k = residency.degree
    total = scales = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        shape = tuple(int(d) for d in leaf.shape)
        n = 1
        for d in shape:
            n *= d
        if residency.kind == "fsdp":
            divides = any(d > 0 and d % k == 0 for d in shape)
        else:  # tp: the head only
            is_head, is_kernel = is_head_kernel(path)
            divides = is_head and (
                (is_kernel and len(shape) >= 2 and shape[-1] % k == 0)
                or (len(shape) == 1 and shape[0] % k == 0)
            )
        if precision == "int8" and len(shape) >= 2:
            sc = 4 * shape[-1]  # per-channel f32 scales, replicated
            total += (n // k if divides else n) + sc
            scales += sc
        else:
            b = n * 4
            total += b // k if divides else b
    return total, scales


def estimate_model_bytes(
    arch: str, num_classes: int, image_size: int, buckets, precision: str,
    *, residency=None, n_devices: int = 0,
) -> dict:
    """Resident-byte estimate for one tenant's executable sets, from
    abstract shapes only (``jax.eval_shape`` — no device memory, no
    compute): params via leaf accounting, plus per-bucket activation
    high-water (the input batch and the [bucket, num_classes] logits —
    at the 64.5k-class head the logits ARE the spike). An estimate for
    the PLANNER; the pool re-measures from the built state.

    A sharded ``residency`` makes every number PER CHIP (ISSUE 17):
    params/K + the per-channel scale overhead, and activations at
    ``ceil(bucket / data_degree)`` rows — batch rows (and the 64.5k-class
    logits spike) divide over ``data``, not ``model``, so the activation
    term shrinks with the OTHER mesh factor. A tenant whose sharded
    footprint fits must never be rejected by the replicated estimate."""
    import jax
    import jax.numpy as jnp

    from mpi_pytorch_tpu.models import initialize_model

    model, _ = initialize_model(arch, num_classes)
    dummy = jax.ShapeDtypeStruct((1, image_size, image_size, 3), jnp.float32)
    rngs = {
        "params": jax.ShapeDtypeStruct((2,), jnp.uint32),
        "dropout": jax.ShapeDtypeStruct((2,), jnp.uint32),
    }
    shapes = jax.eval_shape(
        lambda r, x: model.init(r, x, train=True), rngs, dummy
    )
    precisions = ("bf16", "int8") if precision == "both" else (precision,)
    params_repl = sum(_spec_param_bytes(shapes, p) for p in precisions)
    row_bytes = image_size * image_size * 3 * 4 + num_classes * 4
    per_bucket_repl = {int(b): int(b) * row_bytes for b in buckets}
    out = {
        "params_bytes": int(params_repl),
        "per_bucket_bytes": per_bucket_repl,
        "total_bytes": int(params_repl) + max(per_bucket_repl.values(), default=0),
    }
    if residency is None or not residency.sharded:
        return out
    k = residency.degree
    if n_devices and (n_devices % k or k > n_devices):
        raise ValueError(
            f"residency {residency} does not divide {n_devices} device(s)"
        )
    data_degree = max(1, (n_devices or k) // k)
    if residency.kind == "pipe":
        # Fourth residency option (ISSUE 20): per-chip bytes under the
        # stage split = the BOTTLENECK stage's params + its activation
        # high-water (stage input + output rows), priced from the same
        # traced cut the builder uses. The 64.5k-class logits slab only
        # ever lands on the head stage's chips — a pipe split makes a
        # head-heavy tenant fit where fsdp's all-gather working set won't.
        from mpi_pytorch_tpu.serve.pipeline import (
            _key_name, plan_stages, trace_units,
        )

        units = trace_units(model.apply, shapes, dummy)
        unit_names = [n for n, _ in units]
        unit_avals = dict(units)
        unit_set = set(unit_names)

        def leaf_bytes(shape, p):
            n = 1
            for d in shape:
                n *= int(d)
            if p == "int8" and len(shape) >= 2:
                return n + 4 * int(shape[-1])
            return n * 4

        # Leaf → stage partition, the builder's rule: a leaf under a
        # traced unit's subtree belongs to that unit; a DIRECT top-level
        # param leaf replicates on every stage group (its reading stage
        # is not statically knowable); an uncalled subtree (eval-dead,
        # e.g. inception's AuxLogits) parks on stage 0.
        unit_bytes = {u: 0 for u in unit_names}
        every_stage = stage0_extra = 0
        for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]:
            names = [n for n in (_key_name(e) for e in path) if n]
            b = sum(leaf_bytes(tuple(leaf.shape), p) for p in precisions)
            if len(names) >= 2 and names[1] in unit_set:
                unit_bytes[names[1]] += b
            elif len(names) == 2:
                every_stage += b
            else:
                stage0_extra += b
        stage_units = plan_stages(unit_names, unit_bytes, k, arch=arch)
        stage_params = [
            sum(unit_bytes[u] for u in g) + every_stage for g in stage_units
        ]
        stage_params[0] += stage0_extra

        def row_act(s: int) -> int:
            # One row's stage input + output bytes (f32-traced avals).
            def unit_row(u):
                a = unit_avals[u]
                n = 1
                for d in a.shape[1:]:
                    n *= int(d)
                return n * 4

            inb = (
                image_size * image_size * 3 * 4 if s == 0
                else unit_row(stage_units[s - 1][-1])
            )
            outb = (
                num_classes * 4 if s == k - 1
                else unit_row(stage_units[s][-1])
            )
            return inb + outb

        def act(s: int, b: int) -> int:
            return (-(-int(b) // data_degree)) * row_act(s)

        max_b = max((int(b) for b in buckets), default=1)
        bottleneck = max(
            range(k), key=lambda s: stage_params[s] + act(s, max_b)
        )
        per_bucket = {int(b): act(bottleneck, b) for b in buckets}
        out.update(
            replicated_total_bytes=out["total_bytes"],
            params_bytes=int(stage_params[bottleneck]),
            per_bucket_bytes=per_bucket,
            total_bytes=int(stage_params[bottleneck])
            + max(per_bucket.values(), default=0),
            residency=str(residency),
            data_degree=data_degree,
            pipe_stages=k,
            stage_params_bytes=[int(x) for x in stage_params],
        )
        return out
    params = scale_overhead = 0
    for p in precisions:
        pb, sb = _sharded_param_bytes(shapes, p, residency)
        params += pb
        scale_overhead += sb
    per_bucket = {
        int(b): (-(-int(b) // data_degree)) * row_bytes for b in buckets
    }
    out.update(
        replicated_total_bytes=out["total_bytes"],
        params_bytes=int(params),
        scale_overhead_bytes=int(scale_overhead),
        per_bucket_bytes=per_bucket,
        total_bytes=int(params) + max(per_bucket.values(), default=0),
        residency=str(residency),
        data_degree=data_degree,
    )
    return out


@dataclass
class PlanEntry:
    model: str
    params_bytes: int  # per chip when sharded
    bucket_bytes: dict  # bucket -> bytes (per chip when sharded)
    total_bytes: int  # per chip when sharded
    measured: bool = False  # True when sized from the BUILT state
    residency: str = "replicated"  # "tp:K"/"fsdp:K" = model-parallel
    replicated_bytes: int = 0  # the estimate sharding beat (sharded only)
    scale_bytes: int = 0  # per-channel int8 scale overhead (sharded only)


@dataclass
class PackingPlan:
    """Which tenants fit together on one host, and the arithmetic."""

    budget_bytes: int | None  # None = unbounded (plan still explains)
    entries: list[PlanEntry] = field(default_factory=list)

    @property
    def total_bytes(self) -> int:
        return sum(e.total_bytes for e in self.entries)

    @property
    def fits(self) -> bool:
        return self.budget_bytes is None or self.total_bytes <= self.budget_bytes

    def explain(self) -> str:
        mb = 1024 * 1024
        lines = [
            f"packing plan: {len(self.entries)} tenant(s), "
            f"{self.total_bytes / mb:.1f} MB of "
            + ("unbounded budget" if self.budget_bytes is None
               else f"{self.budget_bytes / mb:.1f} MB budget")
            + (" — FITS" if self.fits else " — OVER BUDGET"),
        ]
        for e in sorted(self.entries, key=lambda e: -e.total_bytes):
            worst = max(e.bucket_bytes.values(), default=0)
            if e.residency != "replicated":
                # The per-chip arithmetic that made sharding win over
                # eviction: params/K (+ whole per-channel scales) + the
                # data-degree-divided activation high-water.
                k = int(e.residency.rsplit(":", 1)[-1])
                scales = (
                    f" (incl {e.scale_bytes / mb:.1f} MB scales)"
                    if e.scale_bytes else ""
                )
                lines.append(
                    f"  {e.model} [{e.residency}]: params/{k} "
                    f"{e.params_bytes / mb:.1f} MB/chip{scales} + "
                    f"largest-bucket activations {worst / mb:.1f} MB/chip "
                    f"= {e.total_bytes / mb:.1f} MB/chip — replicated "
                    f"would be {e.replicated_bytes / mb:.1f} MB"
                    f" ({'measured' if e.measured else 'estimated'})"
                )
            else:
                lines.append(
                    f"  {e.model}: params {e.params_bytes / mb:.1f} MB + "
                    f"largest-bucket activations {worst / mb:.1f} MB = "
                    f"{e.total_bytes / mb:.1f} MB"
                    f" ({'measured' if e.measured else 'estimated'})"
                )
        return "\n".join(lines)

    def entry(self, model: str) -> PlanEntry | None:
        return next((e for e in self.entries if e.model == model), None)

    def to_record(self) -> dict:
        """The stamp swap-in/evict records carry (MB, JSON-clean)."""
        mb = 1024 * 1024
        out = {
            "budget_mb": (
                None if self.budget_bytes is None
                else round(self.budget_bytes / mb, 1)
            ),
            "total_mb": round(self.total_bytes / mb, 1),
            "fits": 1 if self.fits else 0,
            "tenants": {
                e.model: round(e.total_bytes / mb, 1) for e in self.entries
            },
        }
        sharded = {
            e.model: e.residency for e in self.entries
            if e.residency != "replicated"
        }
        if sharded:
            out["residency"] = sharded
        return out


class ModelRegistry:
    """The zoo: tenant name → spec, per-tenant derived configs, byte
    estimates, and the packing planner."""

    def __init__(self, cfg, specs):
        self.cfg = cfg
        self._specs = {s.model: s for s in specs}
        self._estimates: dict[str, dict] = {}

    @classmethod
    def from_config(cls, cfg) -> "ModelRegistry":
        if not cfg.serve_models:
            raise ValueError(
                "ModelRegistry.from_config needs cfg.serve_models (the "
                "tenant spec string)"
            )
        return cls(cfg, parse_model_specs(cfg.serve_models))

    def models(self) -> tuple[str, ...]:
        return tuple(self._specs)

    def specs(self) -> tuple[ModelSpec, ...]:
        return tuple(self._specs.values())

    def spec(self, model: str) -> ModelSpec:
        try:
            return self._specs[model]
        except KeyError:
            raise UnknownModelError(
                f"unknown model {model!r} (registry holds "
                f"{sorted(self._specs)})"
            ) from None

    def tenant_cfg(self, model: str):
        """The per-tenant ``Config`` a tenant's state/executables build
        from: the fleet cfg with the spec's arch/checkpoint/precision/
        buckets swapped in (everything else — image size, topk, queue
        depth, wait — is host policy and stays shared)."""
        spec = self.spec(model)
        overrides: dict = {"model_name": spec.arch}
        if spec.checkpoint_dir:
            overrides["checkpoint_dir"] = spec.checkpoint_dir
        if spec.precision:
            overrides["serve_precision"] = spec.precision
        if spec.buckets:
            overrides["serve_buckets"] = spec.buckets
        cfg = dataclasses.replace(self.cfg, **overrides)
        return cfg

    def tenant_budgets(self, total_budget: int) -> dict[str, int]:
        """Per-tenant front-door admission tokens: the spec's explicit
        ``admission`` when set, else an equal share of the fleet budget —
        the isolation guarantee that one hot tenant cannot consume
        another tenant's admission capacity (ISSUE 14 tentpole (4))."""
        share = max(1, total_budget // max(1, len(self._specs)))
        return {
            s.model: (s.admission or share) for s in self._specs.values()
        }

    def estimate_bytes(
        self, model: str, residency=None, n_devices: int = 0
    ) -> dict:
        """Cached abstract-shape estimate for one tenant (planner input;
        the pool overrides with measured bytes once the state is built).
        ``residency`` (``serve/sharding.Residency``) makes the estimate
        per-chip; None = the spec's own residency."""
        from mpi_pytorch_tpu.serve.sharding import parse_residency

        spec = self.spec(model)
        if residency is None:
            residency = parse_residency(spec.shard)
        if not residency.sharded and model in self._estimates:
            # Bare-name entries are the pre-v13 cache shape AND the test
            # seam (tests inject replicated estimates by model name).
            return self._estimates[model]
        key = (model, str(residency), int(n_devices) if residency.sharded else 0)
        if key not in self._estimates:
            cfg = self.tenant_cfg(model)
            self._estimates[key] = estimate_model_bytes(
                spec.arch, cfg.num_classes, cfg.image_size[0],
                cfg.parsed_serve_buckets(),
                spec.precision or cfg.serve_precision,
                residency=residency, n_devices=n_devices,
            )
        return self._estimates[key]

    def _plan_entry(
        self, model: str, residency, n_devices: int,
        measured: dict[str, int], residencies: dict[str, str],
    ) -> PlanEntry:
        est = self.estimate_bytes(model, residency=residency, n_devices=n_devices)
        res_str = est.get("residency", "replicated")
        # A measured (built-state) size only describes the residency it
        # was measured AT — a proposed conversion falls back to the
        # estimate until the pool re-measures the resharded state.
        use_measured = (
            model in measured
            and residencies.get(model, "replicated") == res_str
        )
        total = measured[model] if use_measured else est["total_bytes"]
        return PlanEntry(
            model=model,
            params_bytes=est["params_bytes"],
            bucket_bytes=est["per_bucket_bytes"],
            total_bytes=int(total),
            measured=use_measured,
            residency=res_str,
            replicated_bytes=int(est.get("replicated_total_bytes", 0)),
            scale_bytes=int(est.get("scale_overhead_bytes", 0)),
        )

    def plan_packing(
        self, models, budget_bytes: int | None,
        measured: dict[str, int] | None = None,
        *, n_devices: int = 0, residencies: dict[str, str] | None = None,
    ) -> PackingPlan:
        """The packing plan for ``models`` co-resident on one host.
        ``measured`` (model → bytes, from the pool's built states)
        overrides the estimate where available; ``residencies`` names the
        layout each measurement was taken at.

        Third residency option (ISSUE 17): when the replicated set is over
        budget and the host has chips to shard over (``n_devices``), the
        planner converts the largest replicated tenants to ``fsdp:K`` —
        smallest K first, so a tenant never spans more chips than the
        budget requires — BEFORE the caller reaches for eviction. A single
        tenant exceeding the budget even at the deepest shard degree is a
        spec error and raises ``PackingError`` loudly."""
        from mpi_pytorch_tpu.serve.sharding import Residency, parse_residency

        plan = PackingPlan(budget_bytes=budget_bytes)
        measured = measured or {}
        residencies = residencies or {}
        degrees = [
            k for k in range(2, max(2, n_devices) + 1)
            if n_devices and n_devices % k == 0
        ]
        for model in models:
            spec_res = parse_residency(
                residencies.get(model) or self.spec(model).shard
            )
            entry = self._plan_entry(
                model, spec_res, n_devices, measured, residencies
            )
            if budget_bytes is not None and entry.total_bytes > budget_bytes:
                # Too big even alone at its declared residency: shard
                # deeper before rejecting — the whole point of the third
                # residency option is that "doesn't fit replicated" no
                # longer means "can't be served".
                for k in degrees:
                    if k <= spec_res.degree:
                        continue
                    cand = self._plan_entry(
                        model, Residency("fsdp", k), n_devices,
                        measured, residencies,
                    )
                    if cand.total_bytes <= budget_bytes:
                        entry = cand
                        break
                else:
                    single = PackingPlan(
                        budget_bytes=budget_bytes, entries=[entry]
                    )
                    raise PackingError(
                        f"tenant {model!r} alone exceeds the packing budget "
                        "at every shard degree — no eviction can make it "
                        "fit. " + single.explain()
                    )
            plan.entries.append(entry)
        if budget_bytes is not None and not plan.fits and degrees:
            # Over budget together: convert the largest replicated tenants
            # to fsdp:K (smallest K that helps) until the plan fits — the
            # explain() lines show the per-chip arithmetic of each win.
            for entry in sorted(plan.entries, key=lambda e: -e.total_bytes):
                if plan.fits:
                    break
                if entry.residency != "replicated":
                    continue
                others = plan.total_bytes - entry.total_bytes
                for k in degrees:
                    cand = self._plan_entry(
                        entry.model, Residency("fsdp", k), n_devices,
                        measured, residencies,
                    )
                    if others + cand.total_bytes <= budget_bytes:
                        plan.entries[plan.entries.index(entry)] = cand
                        break
                else:
                    # No single degree closes the gap alone: take the
                    # deepest shard anyway if it helps, and keep
                    # converting the next-largest tenant.
                    cand = self._plan_entry(
                        entry.model, Residency("fsdp", degrees[-1]),
                        n_devices, measured, residencies,
                    )
                    if cand.total_bytes < entry.total_bytes:
                        plan.entries[plan.entries.index(entry)] = cand
        return plan
