"""Test env: 8 virtual CPU devices so the real sharded code paths run without
TPU hardware — the TPU-native analogue of testing MPI code without a cluster
(SURVEY §4). Everything here lands in ``os.environ`` BEFORE jax is imported,
so the test process and every child it spawns see the same world."""

import os
import tempfile

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"
# The suite makes thousands of small CPU compiles, and the drivers under test
# turn the persistent cache on with its thresholds at zero
# (config.enable_compilation_cache). Its default home is inside the
# checkout, and a checkout swollen by a CPU cache is copied to the chip
# machine whole — so unless the caller placed the cache, keep it at one fixed
# path OUTSIDE the checkout.
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.join(tempfile.gettempdir(), "mpt_jax_cache_tests"),
)

import jax  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


# Initializing every convertible architecture is the most expensive fixture
# in the suite (XLA compiles on a single CPU core) — session-scoped and
# shared by test_models and test_torch_mapping. The list IS
# CONVERTIBLE_MODELS, so a new weight mapping is automatically covered.
TEST_NUM_CLASSES = 10


@pytest.fixture(scope="session")
def bundles():
    from mpi_pytorch_tpu.models import create_model_bundle
    from mpi_pytorch_tpu.models.pretrained import CONVERTIBLE_MODELS

    out = {}
    for name in CONVERTIBLE_MODELS:
        # small sizes for test speed; inception needs its real 299 spatial
        # dims for the aux-logits pooling path
        size = 299 if name == "inception_v3" else 64
        bundle, variables = create_model_bundle(
            name, TEST_NUM_CLASSES, rng=jax.random.PRNGKey(0), image_size=size
        )
        out[name] = (bundle, variables)
    return out


@pytest.fixture
def spans_of(tmp_path):
    """``spans_of(run, epochs=0)``: events by name (sorted by start) of what
    ``run()`` did with a tracer installed as the run's current one, once
    ``epochs`` loader producers have closed their ``loader/epoch`` span (a
    producer closes just after its sentinel is taken; the wait has a
    deadline of its own)."""
    import json
    import time

    from mpi_pytorch_tpu.obs import trace as obs_trace

    def spans_of(run, epochs: int = 0) -> dict:
        tracer = obs_trace.Tracer(str(tmp_path / "spans.json"))
        with obs_trace.use(tracer):
            run()
        deadline = time.monotonic() + 10
        while sum(e["name"] == "loader/epoch" for e in list(tracer._events)) < epochs:
            assert time.monotonic() < deadline, "a producer never closed loader/epoch"
            time.sleep(0.01)
        by_name: dict = {}
        with open(tracer.close()) as f:
            for e in sorted(json.load(f)["traceEvents"], key=lambda e: e["ts"]):
                by_name.setdefault(e["name"], []).append(e)
        return by_name

    return spans_of
