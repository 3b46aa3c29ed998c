"""Every Pallas kernel in the repo, once, at the shape its model uses:
compiled by Mosaic (``interpret=False``) and compared with its in-repo
reference.

Two readers share ``CASES``:

- ``python tools/chip_kernels.py`` on the chip compiles and runs each case
  and its reference, prints one JSON row per kernel (compiled / the
  compiler's message, relative L2 error per output) and exits non-zero if
  any kernel was refused or disagrees. It times nothing.
- ``tests/test_tpu_lowering.py`` on the CPU lowers each case for ``tpu``
  (``lowering_platforms=("tpu",)``) and finds its ``tpu_custom_call`` —
  Pallas API drift caught in seconds, before a chip is asked.

A kernel Mosaic refuses is a failing row here until it is repaired or
deleted: it never quietly becomes its reference.
"""

from __future__ import annotations

import json
import os
import sys
import traceback
from typing import Callable, NamedTuple
from unittest import mock

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from mpi_pytorch_tpu.utils.hardware import mosaic_call_count

# Flagship head (resnet18: 512 features -> 64 500 classes) and stem
# (128 px input -> conv1 output 64x64x64); ViT-S/16 attention at 128 px
# (64 tokens, 6 heads of 64), ViT-B/16 attention at 224 px (196 tokens, 12
# heads of 64, at the benchmark's three batches) plus one long sequence for
# flash.
D_HEAD, V_HEAD = 512, 64500
STEM_B, STEM_HW, STEM_C = 512, 64, 64
ATTN_B, ATTN_S, ATTN_H, ATTN_D = 64, 64, 6, 64
VITB_S, VITB_H, VITB_BATCHES = 196, 12, (16, 128, 256)


class Case(NamedTuple):
    name: str
    fn: Callable  # the Pallas path, interpret=False
    ref: Callable  # the in-repo reference, same signature and outputs
    make_args: Callable[[], tuple]  # seeded inputs (jax.random: eval_shape-able)
    env: dict = {}  # MPT_* levers, read at trace time (never mutated)
    tol: float = 2e-2  # relative L2, bf16 storage on inputs/outputs


def _with_grads(f: Callable, n_diff: int) -> Callable:
    """``f(*diff_args, cotangent)`` -> (out, grads wrt the first ``n_diff``
    args) under a fixed random cotangent: forward AND backward kernels."""

    def run(*args):
        *xs, co = args

        def loss(*diff):
            out = f(*diff, *xs[n_diff:])
            return jnp.sum(out.astype(jnp.float32) * co.astype(jnp.float32)), out

        (_, out), grads = jax.value_and_grad(
            loss, argnums=tuple(range(n_diff)), has_aux=True
        )(*xs[:n_diff])
        return out, grads

    return run


def _stem_args():
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(0), 4)
    y = jax.random.normal(k1, (STEM_B, STEM_HW, STEM_HW, STEM_C), jnp.bfloat16)
    a = jnp.abs(jax.random.normal(k2, (STEM_C,), jnp.float32)) + 0.5
    b = jax.random.normal(k3, (STEM_C,), jnp.float32) * 0.1
    co = jax.random.normal(k4, (STEM_B, STEM_HW // 2, STEM_HW // 2, STEM_C), jnp.bfloat16)
    return y, a, b, co


def _stem_case(name: str, env: dict) -> Case:
    from mpi_pytorch_tpu.ops.fused_stem import _reference_impl, stem_affine_relu_pool

    return Case(
        name,
        _with_grads(lambda y, a, b: stem_affine_relu_pool(y, a, b, interpret=False), 3),
        _with_grads(_reference_impl, 3),
        _stem_args,
        env=env,
    )


def _attn_args(b, s, h=ATTN_H):
    def make():
        ks = jax.random.split(jax.random.PRNGKey(1), 4)
        shape = (b, s, h, ATTN_D)
        return tuple(jax.random.normal(k, shape, jnp.bfloat16) for k in ks)

    return make


def _gqa_args(b, s, h, hkv):
    """q [b, s, h, D] with k, v [b, s, hkv, D] and a cotangent of q's shape."""
    def make():
        ks = jax.random.split(jax.random.PRNGKey(3), 4)
        shapes = [(b, s, h, ATTN_D), (b, s, hkv, ATTN_D), (b, s, hkv, ATTN_D), (b, s, h, ATTN_D)]
        return tuple(jax.random.normal(k, shape, jnp.bfloat16) for k, shape in zip(ks, shapes))

    return make


def _gqa_flash_case(b, s, h, hkv) -> Case:
    """Causal flash attention with grouped heads at the tiles the shape
    chooses, forward and gradients; the reference repeats k and v."""
    from mpi_pytorch_tpu.ops.flash_attention import flash_attention
    from mpi_pytorch_tpu.ops.ring_attention import full_attention

    group = h // hkv
    return Case(
        f"flash_attention[S={s},H={h}/{hkv},causal]",
        _with_grads(
            lambda q, k, v: flash_attention(q, k, v, causal=True, interpret=False), 3,
        ),
        _with_grads(
            lambda q, k, v: full_attention(
                q, jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2), causal=True
            ), 3,
        ),
        _gqa_args(b, s, h, hkv),
        tol=5e-2,
    )


def _head_args(rows, grads):
    def make():
        k1, k2, k3, k4, k5 = jax.random.split(jax.random.PRNGKey(2), 5)
        feats = jax.random.normal(k1, (rows, D_HEAD), jnp.bfloat16)
        w = jax.random.normal(k2, (D_HEAD, V_HEAD), jnp.float32) * 0.05
        b = jax.random.normal(k3, (V_HEAD,), jnp.float32) * 0.1
        labels = jax.random.randint(k4, (rows,), 0, V_HEAD, jnp.int32)
        labels = labels.at[-3:].set(-1)  # padding rows (trainer.pad_batch)
        if grads:
            return feats, w, b, labels, jax.random.normal(k5, (rows,), jnp.float32)
        return feats, w, b, labels

    return make


def _int8_args(rows):
    def make():
        from mpi_pytorch_tpu.ops.quantize import quantize_per_channel

        feats, w, b, labels = _head_args(rows, grads=False)()
        w_q, w_scale = quantize_per_channel(w)
        act_scale = jnp.max(jnp.abs(feats.astype(jnp.float32))) / 127.0
        return feats, w_q, b, labels, w_scale, act_scale

    return make


def _cases() -> list[Case]:
    from mpi_pytorch_tpu.ops.flash_attention import flash_attention
    from mpi_pytorch_tpu.ops.fused_attention_small import fused_attention_small
    from mpi_pytorch_tpu.ops.fused_head_ce import (
        fused_head_ce,
        head_ce_reference,
        head_predict,
        head_predict_reference,
    )
    from mpi_pytorch_tpu.ops.quantize import (
        head_predict_int8,
        head_predict_int8_reference,
    )
    from mpi_pytorch_tpu.ops.ring_attention import full_attention

    cases = [
        _stem_case("stem", {}),
        _stem_case("stem[MPT_STEM_LANES=256]", {"MPT_STEM_LANES": "256"}),
        _stem_case("stem[MPT_STEM_IDX_INT8=1]", {"MPT_STEM_IDX_INT8": "1"}),
        _stem_case("stem[MPT_STEM_C_BLOCK=16]", {"MPT_STEM_C_BLOCK": "16"}),
        Case(
            "flash_attention[S=64]",
            _with_grads(lambda q, k, v: flash_attention(q, k, v, interpret=False), 3),
            _with_grads(full_attention, 3),
            _attn_args(ATTN_B, ATTN_S),
            tol=5e-2,
        ),
        Case(
            "flash_attention[S=2048]",
            _with_grads(lambda q, k, v: flash_attention(q, k, v, interpret=False), 3),
            _with_grads(full_attention, 3),
            _attn_args(2, 2048),
            tol=5e-2,
        ),
        # LFM2's attention layer: 8 key-value heads for 32 query heads, causal,
        # the tiles the kernel chooses for the shape; then the
        # benchmark cell's sequence (lfm2_train_hbm_8k) for one key-value group
        # of one sequence: the float32 reference's scores are 1 GB.
        _gqa_flash_case(1, 2048, 32, 8),
        _gqa_flash_case(1, 8192, 4, 1),
        # A group of 3: the forward's 2 048 rows do not divide by it, and the
        # sequence is no whole number of the 672-row blocks a head then gets.
        _gqa_flash_case(1, 8192, 3, 1),
        Case(
            "fused_attention_small",
            _with_grads(lambda q, k, v: fused_attention_small(q, k, v, interpret=False), 3),
            _with_grads(full_attention, 3),
            _attn_args(ATTN_B, ATTN_S),
            tol=5e-2,
        ),
        Case(
            "fused_head_ce",
            _with_grads(lambda *a: fused_head_ce(*a, interpret=False), 3),
            _with_grads(head_ce_reference, 3),
            _head_args(512, grads=True),
        ),
    ]
    for batch in VITB_BATCHES:
        cases.append(
            Case(
                f"fused_attention_small[S=196,B={batch}]",
                _with_grads(lambda q, k, v: fused_attention_small(q, k, v, interpret=False), 3),
                _with_grads(full_attention, 3),
                _attn_args(batch, VITB_S, VITB_H),
                tol=5e-2,
            )
        )
    for rows in (256, 1024, 4096):
        cases.append(
            Case(
                f"head_predict[rows={rows}]",
                lambda *a: head_predict(*a, interpret=False),
                head_predict_reference,
                _head_args(rows, grads=False),
            )
        )
    for rows in (1024, 4096):
        cases.append(
            Case(
                f"head_predict_int8[rows={rows}]",
                lambda *a: head_predict_int8(*a, interpret=False),
                head_predict_int8_reference,
                _int8_args(rows),
            )
        )
    return cases


CASES = _cases()


def _compare(got, want, tol: float) -> tuple[dict, bool]:
    """Per-output relative L2 error (integer outputs — argmax predictions —
    as a mismatch fraction: bf16 near-ties may pick a different index)."""
    errors, ok = {}, True
    flat_got, _ = jax.tree_util.tree_flatten(got)
    flat_want, _ = jax.tree_util.tree_flatten(want)
    for i, (g, w) in enumerate(zip(flat_got, flat_want)):
        g, w = np.asarray(g), np.asarray(w)
        if g.shape != w.shape:
            errors[f"out{i}"] = f"shape {g.shape} vs {w.shape}"
            ok = False
        elif np.issubdtype(w.dtype, np.integer):
            frac = float(np.mean(g != w))
            errors[f"out{i}"] = f"mismatch {frac:.4f}"
            ok &= frac <= 0.01
        else:
            g, w = g.astype(np.float64), w.astype(np.float64)
            rel = float(np.linalg.norm(g - w) / max(np.linalg.norm(w), 1e-12))
            errors[f"out{i}"] = round(rel, 6)
            ok &= bool(np.isfinite(g).all()) and rel <= tol
    return errors, ok


def run_case(case: Case) -> dict:
    row = {"kernel": case.name, "env": case.env}
    args = case.make_args()
    try:
        with mock.patch.dict(os.environ, case.env):  # levers are read at trace time
            compiled = jax.jit(case.fn).lower(*args).compile()
        row["mosaic_calls"] = mosaic_call_count(compiled)
        got = jax.block_until_ready(compiled(*args))
    except Exception as e:  # noqa: BLE001 — the report boundary: every kernel gets a row
        traceback.print_exc()
        row.update(status="refused", error=f"{type(e).__name__}: {e}"[:2000])
        return row
    want = jax.block_until_ready(jax.jit(case.ref)(*args))
    row["rel_l2"], ok = _compare(got, want, case.tol)
    row["status"] = "compiled" if ok and row["mosaic_calls"] else "wrong"
    return row


def untileable_calls() -> list[tuple[str, Callable]]:
    """One call per kernel with a shape it cannot tile. On a TPU each must
    RAISE (a ValueError naming the shape) — none may quietly return its XLA
    reference. Trace-time errors: nothing here compiles."""
    from mpi_pytorch_tpu.ops.fused_attention_small import fused_attention_small
    from mpi_pytorch_tpu.ops.fused_head_ce import head_predict
    from mpi_pytorch_tpu.ops.fused_stem import stem_affine_relu_pool
    from mpi_pytorch_tpu.ops.quantize import head_predict_int8

    z = jnp.zeros
    rows = 1028  # > the 1 024-row envelope, and 4 x 257: no power-of-two tiling
    head = (z((rows, 8), jnp.bfloat16), z((8, 16)), z((16,)), z((rows,), jnp.int32))
    return [
        ("stem[C=60]", lambda: stem_affine_relu_pool(
            z((8, 4, 4, 60), jnp.bfloat16), z((60,)), z((60,)))),
        ("fused_attention_small[S=1024]", lambda: fused_attention_small(
            *(z((2, 1024, 6, 64), jnp.bfloat16),) * 3)),
        ("head_predict[rows=1028]", lambda: head_predict(*head)),
        ("head_predict_int8[rows=1028]", lambda: head_predict_int8(
            head[0], z((8, 16), jnp.int8), head[2], head[3], z((16,)) + 1.0, 1.0)),
    ]


def check_raises() -> list[dict]:
    rows = []
    for name, call in untileable_calls():
        try:
            jax.eval_shape(call)
        except ValueError as e:
            rows.append({"kernel": name, "status": "raises", "error": str(e)})
        else:
            rows.append({"kernel": name, "status": "wrong",
                         "error": "returned a result for a shape it cannot tile"})
    return rows


def check_attention_precision() -> dict:
    """Forward and VJP of the attention pair alone on a seeded
    ``[16,196,12,64]`` bf16 block, the kernel and XLA's ``full_attention``
    each against a ``Precision.HIGHEST`` float32 ``jnp`` reference: the
    kernel's relative L2 error (out, dq, dk, dv) may be no larger than the
    XLA path's, to a hundredth of it (both round the same bf16 products; the
    difference is where p and ds are rounded)."""
    from mpi_pytorch_tpu.ops.fused_attention_small import fused_attention_small
    from mpi_pytorch_tpu.ops.ring_attention import full_attention

    args = _attn_args(VITB_BATCHES[0], VITB_S, VITB_H)()

    def highest(q, k, v):
        with jax.default_matmul_precision("highest"):
            return full_attention(q, k, v)

    flat = lambda tree: [np.asarray(x, np.float64) for x in jax.tree_util.tree_leaves(tree)]
    want = flat(jax.jit(_with_grads(highest, 3))(*(x.astype(jnp.float32) for x in args)))
    errors = {}
    for name, fn in (
        ("kernel", lambda q, k, v: fused_attention_small(q, k, v, interpret=False)),
        ("xla", full_attention),
    ):
        got = flat(jax.jit(_with_grads(fn, 3))(*args))
        errors[name] = [float(np.linalg.norm(g - w) / np.linalg.norm(w)) for g, w in zip(got, want)]
    ok = all(k <= 1.01 * x for k, x in zip(errors["kernel"], errors["xla"]))
    return {"kernel": "attention_precision[16x196x12x64]", "status": "compiled" if ok else "wrong",
            "rel_l2_vs_highest": errors}


def check_compiler_options() -> dict:
    """Per-compile TPU options reach the TPU compiler: a known option is
    accepted, an unknown one is an error (not silently dropped)."""
    lowered = jax.jit(lambda x: x @ x).lower(jnp.zeros((128, 128), jnp.bfloat16))
    lowered.compile(compiler_options={"xla_tpu_scoped_vmem_limit_kib": 65536})
    try:
        lowered.compile(compiler_options={"xla_tpu_no_such_option": 1})
    except Exception as e:  # noqa: BLE001 — whatever type XLA raises, it is the evidence
        return {"kernel": "compiler_options", "status": "compiled",
                "error": f"unknown option rejected: {type(e).__name__}: {e}"[:300]}
    return {"kernel": "compiler_options", "status": "wrong",
            "error": "an unknown per-compile option was accepted"}


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--only", default="",
                    help="run only the cases whose name contains this text")
    only = ap.parse_args().only
    if jax.default_backend() != "tpu":
        raise SystemExit(
            f"chip_kernels: backend is {jax.default_backend()!r}; Mosaic "
            "compiles only on a TPU (the CPU check is tests/test_tpu_lowering.py)"
        )
    device = jax.devices()[0]
    print(f"chip_kernels: {device.platform} {device.device_kind} x{jax.device_count()}", flush=True)
    rows = check_raises() + [check_compiler_options()]
    if only in "attention_precision":
        rows.append(check_attention_precision())
    for row in rows:
        print(json.dumps(row), flush=True)
    for case in CASES:
        if only in case.name:
            rows.append(run_case(case))
            print(json.dumps(rows[-1]), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/chip_kernels.json", "w") as f:
        json.dump(rows, f, indent=1)
    passing = ("compiled", "raises")
    bad = [r["kernel"] for r in rows if r["status"] not in passing]
    if bad:
        raise SystemExit(f"chip_kernels: {len(bad)} kernel(s) refused or wrong: {bad}")


if __name__ == "__main__":
    main()
