"""Native (C++) batched JPEG ingest: build, parity vs the PIL path, fallback.

The native library (mpi_pytorch_tpu/native/decode.cpp) is the TPU-host
equivalent of the reference's parallel-ingest machinery (torch DataLoader
workers, ``data_loader.py:29-39``; MPI preprocessing ranks,
``evaluation_pipeline.py:53-129``). These tests pin its contract:

- decode parity: same libjpeg, so exact-size decode is bit-identical to PIL
- resize parity: the separable triangle filter matches PIL's BILINEAR within
  fixed-point rounding (<1.5/255 per pixel)
- DCT prescale modes trade PIL-exactness for IDCT work, with bounded deviation
- corrupt / non-JPEG items fall back to PIL one at a time
"""

from __future__ import annotations

import os

import numpy as np
import pytest
from PIL import Image

from mpi_pytorch_tpu import native
from mpi_pytorch_tpu.config import IMAGENET_MEAN, IMAGENET_STD
from mpi_pytorch_tpu.data.manifest import Manifest
from mpi_pytorch_tpu.data.pipeline import (
    DataLoader,
    decode_image,
    normalize_image,
    synthetic_image,
)

MEAN = np.asarray(IMAGENET_MEAN, dtype=np.float32)
STD = np.asarray(IMAGENET_STD, dtype=np.float32)

pytestmark = pytest.mark.skipif(
    not native.available(), reason=f"native decode unavailable: {native.build_error()}"
)


def _write_jpeg(path, img_u8, quality=95):
    Image.fromarray(img_u8).save(path, quality=quality)


def _pil(path, size=(128, 128)):
    return normalize_image(decode_image(str(path), size))


def _pixel_diff(a, b):
    """Max |a-b| in uint8 pixel units (undo the ImageNet normalization)."""
    return float((np.abs(a - b) * STD).max() * 255)


def test_exact_size_decode_is_bit_parity_with_pil(tmp_path):
    img = (synthetic_image(3, (128, 128)) * 255).astype(np.uint8)
    p = tmp_path / "a.jpg"
    _write_jpeg(p, img)
    out = native.decode_batch([str(p)], (128, 128), MEAN, STD)
    assert _pixel_diff(out[0], _pil(p)) < 0.01  # same libjpeg: f32 rounding only


def test_resize_matches_pil_bilinear(tmp_path):
    # 140->128 stays below any prescale threshold: pure resize comparison.
    img = (synthetic_image(3, (140, 140)) * 255).astype(np.uint8)
    p = tmp_path / "a.jpg"
    _write_jpeg(p, img)
    out = native.decode_batch([str(p)], (128, 128), MEAN, STD, prescale_margin=0)
    # PIL computes the same triangle filter in 8.22 fixed point; we use f32.
    assert _pixel_diff(out[0], _pil(p)) < 1.5


def test_upscale_matches_pil(tmp_path):
    img = (synthetic_image(5, (100, 90)) * 255).astype(np.uint8)
    p = tmp_path / "a.jpg"
    _write_jpeg(p, img)
    out = native.decode_batch([str(p)], (128, 128), MEAN, STD)
    assert _pixel_diff(out[0], _pil(p)) < 1.5


def test_prescale_margin0_full_parity_on_large_source(tmp_path):
    img = (synthetic_image(7, (1000, 800)) * 255).astype(np.uint8)
    p = tmp_path / "big.jpg"
    _write_jpeg(p, img)
    out = native.decode_batch([str(p)], (128, 128), MEAN, STD, prescale_margin=0)
    assert _pixel_diff(out[0], _pil(p)) < 1.5


def test_prescale_deviation_is_bounded(tmp_path):
    # Scaled IDCT is a different low-pass than full-decode+resize; the default
    # 2x-margin mode must stay close to PIL in the mean (documented contract).
    img = (synthetic_image(7, (1000, 800)) * 255).astype(np.uint8)
    p = tmp_path / "big.jpg"
    _write_jpeg(p, img)
    ref = _pil(p)
    for margin, mean_tol in ((2, 3.0), (1, 6.0)):
        out = native.decode_batch([str(p)], (128, 128), MEAN, STD, prescale_margin=margin)
        mean_diff = float((np.abs(out[0] - ref) * STD).mean() * 255)
        assert mean_diff < mean_tol, (margin, mean_diff)


def test_grayscale_jpeg_expands_to_rgb(tmp_path):
    gray = (synthetic_image(2, (150, 150))[:, :, 0] * 255).astype(np.uint8)
    p = tmp_path / "gray.jpg"
    Image.fromarray(gray, mode="L").save(p, quality=95)
    out = native.decode_batch([str(p)], (128, 128), MEAN, STD, prescale_margin=0)
    assert out.shape == (1, 128, 128, 3)
    # PIL path applies .convert("RGB") — the grayscale fix the reference lacks.
    assert _pixel_diff(out[0], _pil(p)) < 1.5


def test_corrupt_item_falls_back_per_item(tmp_path):
    good = tmp_path / "good.jpg"
    _write_jpeg(good, (synthetic_image(1, (128, 128)) * 255).astype(np.uint8))
    bad = tmp_path / "bad.jpg"
    bad.write_bytes(b"this is not a jpeg")
    calls = []

    def fallback(path):
        calls.append(path)
        return np.zeros((128, 128, 3), np.float32)

    out = native.decode_batch(
        [str(good), str(bad)], (128, 128), MEAN, STD, fallback=fallback
    )
    assert calls == [str(bad)]
    assert np.all(out[1] == 0)
    assert _pixel_diff(out[0], _pil(good)) < 0.01


def test_missing_file_raises_without_fallback(tmp_path):
    with pytest.raises(RuntimeError, match="native decode failed"):
        native.decode_batch([str(tmp_path / "nope.jpg")], (128, 128), MEAN, STD)


def _jpeg_manifest(tmp_path, n=12):
    img_dir = tmp_path / "img"
    img_dir.mkdir()
    names, labels = [], []
    for i in range(n):
        name = f"im_{i}.jpg"
        _write_jpeg(img_dir / name, (synthetic_image(i % 3, (160, 140)) * 255).astype(np.uint8))
        names.append(name)
        labels.append(i % 3)
    return Manifest(
        filenames=tuple(names),
        labels=np.array(labels, np.int32),
        category_ids=np.array(labels, np.int64),
        img_dir=str(img_dir),
    )


def test_loader_native_path_matches_pil_path(tmp_path):
    m = _jpeg_manifest(tmp_path)
    kw = dict(batch_size=4, image_size=(128, 128), shuffle=False, drop_remainder=False)
    native_batches = list(
        DataLoader(m, **kw, native_decode=True, decode_prescale=0).epoch(0)
    )
    pil_batches = list(DataLoader(m, **kw, native_decode=False).epoch(0))
    assert len(native_batches) == len(pil_batches) == 3
    for (ni, nl), (pi, pl) in zip(native_batches, pil_batches):
        np.testing.assert_array_equal(nl, pl)
        assert _pixel_diff(ni, pi) < 1.5


def test_loader_host_cache_matches_direct_decode(tmp_path):
    """host_cache composed with native decode: identical batches to direct
    per-epoch decode, and repeat epochs serve from the cache byte-for-byte."""
    m = _jpeg_manifest(tmp_path)
    kw = dict(batch_size=4, image_size=(128, 128), shuffle=True, seed=3,
              drop_remainder=False, native_decode=True, decode_prescale=0)
    direct = list(DataLoader(m, **kw).epoch(1))
    cached_loader = DataLoader(m, **kw, host_cache=True)
    first = list(cached_loader.epoch(1))
    again = list(cached_loader.epoch(1))
    assert len(direct) == len(first) == len(again) == 3
    for (di, dl), (fi, fl), (ai, al) in zip(direct, first, again):
        np.testing.assert_array_equal(dl, fl)
        np.testing.assert_array_equal(di, fi)
        np.testing.assert_array_equal(fi, ai)
        np.testing.assert_array_equal(fl, al)


def test_env_kill_switch():
    # The switch is latched at first load(), and this process has already
    # loaded the library — exercise it in a fresh interpreter.
    import subprocess
    import sys

    probe = (
        "from mpi_pytorch_tpu import native; "
        "assert native.load() is None, 'kill switch ignored'; "
        "assert not native.available(); print('disabled-ok')"
    )
    out = subprocess.run(
        [sys.executable, "-c", probe],
        env={**os.environ, "MPT_DISABLE_NATIVE": "1", "JAX_PLATFORMS": "cpu"},
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert out.returncode == 0, out.stderr
    assert "disabled-ok" in out.stdout


def test_library_name_is_keyed_on_the_source_hash(tmp_path, monkeypatch):
    """A binary built from any other decode.cpp must be unloadable BY NAME:
    a copied tree (the chip machine's, a checkout's) does not preserve the
    mtimes an older freshness check compared, so a stale ``.so`` that
    travelled with it used to load."""
    current = native._lib_name()
    assert current.startswith("_mptnative_") and current.endswith(".so")
    assert all(
        os.path.basename(p) == current for p in native._candidate_paths(current)
    )
    edited = tmp_path / "decode.cpp"
    with open(native._SRC, "rb") as f:
        edited.write_bytes(f.read() + b"\n// one more line\n")
    monkeypatch.setattr(native, "_SRC", str(edited))
    assert native._lib_name() != current


# ---------------------------------------------------------------------------
# the counter where the decode happens, and the loader's spans (ISSUE 24)
# ---------------------------------------------------------------------------


def test_native_counter_counts_refusals_and_busy_time(tmp_path):
    """Two process-wide atomics in decode.cpp: one refusal per image the C
    decoder left to the fallback, none for a clean image, and the workers'
    nanoseconds inside a decode positive and at most threads x the call's
    wall time."""
    import time

    m = _jpeg_manifest(tmp_path, n=8)
    paths = [os.path.join(m.img_dir, f) for f in m.filenames]
    bad = tmp_path / "bad.jpg"
    bad.write_bytes(b"this is not a jpeg")
    before = native.counters()
    t0 = time.perf_counter()
    native.decode_batch(
        paths + [str(bad)], (64, 64), MEAN, STD, threads=4,
        fallback=lambda p: np.zeros((64, 64, 3), np.float32),
    )
    wall_ns = (time.perf_counter() - t0) * 1e9
    refused, busy_ns = (a - b for a, b in zip(native.counters(), before))
    assert refused == 1
    assert 0 < busy_ns <= 4 * wall_ns


def _traced_epoch(spans_of, loader):
    batches = []
    spans = spans_of(lambda: batches.extend(loader.epoch(0)), epochs=1)
    return batches, spans


@pytest.mark.parametrize(
    "source,kw",
    [
        ("native", dict(native_decode=True, image_dtype="bfloat16")),
        ("pil", dict(native_decode=False, image_dtype="bfloat16")),
        ("pil", dict(native_decode=False, image_dtype="float32")),
    ],
)
def test_loader_spans_per_batch_with_their_args(tmp_path, spans_of, source, kw):
    """``loader/decode|cast|put|get`` per batch, ``loader/epoch`` around the
    producer's life; the decode span's args say what was decoded, by whom,
    and how busy the workers were."""
    m = _jpeg_manifest(tmp_path, n=12)
    loader = DataLoader(
        m, batch_size=4, image_size=(64, 64), shuffle=False, num_workers=3, **kw
    )
    batches, spans = _traced_epoch(spans_of, loader)
    assert len(batches) == 3
    (life,) = spans["loader/epoch"]
    assert life["args"] == {"epoch": 0, "batches": 3}
    decodes = spans["loader/decode"]
    assert len(decodes) == 3
    for e in decodes:
        args = e["args"]
        assert args["images"] == 4 and args["source"] == source and args["threads"] == 3
        assert args["fallbacks"] == 0 and args["quarantined"] == 0
        assert 0 < args["thread_busy_s"] <= 3 * e["dur"] / 1e6 * 1.05
        assert life["ts"] <= e["ts"] and e["ts"] + e["dur"] <= life["ts"] + life["dur"]
    # The Python paths time themselves as decode.cpp does; the C path does
    # not touch the Python counter.
    assert (loader._py_busy_ns > 0) == (source == "pil")
    # The cast runs only where the decode's float32 is not the batch dtype.
    assert len(spans.get("loader/cast", [])) == (3 if kw["image_dtype"] == "bfloat16" else 0)
    assert len(spans["loader/put"]) == 4  # three batches and the sentinel
    assert len(spans["loader/get"]) == 4
    # Producer and consumer run on two threads.
    assert {e["tid"] for e in spans["loader/put"]} != {e["tid"] for e in spans["loader/get"]}


def test_loader_decode_span_counts_fallbacks_and_quarantines(tmp_path, spans_of):
    m = _jpeg_manifest(tmp_path, n=4)
    with open(os.path.join(m.img_dir, m.filenames[1]), "wb") as f:
        f.write(b"this is not a jpeg")
    loader = DataLoader(
        m, batch_size=4, image_size=(64, 64), shuffle=False, native_decode=True,
        decode_retries=0,
    )
    (batch,), spans = _traced_epoch(spans_of, loader)
    (decode,) = spans["loader/decode"]
    # The C decoder refused one file; PIL could not read it either.
    assert decode["args"]["fallbacks"] == 1 and decode["args"]["quarantined"] == 1
    assert list(batch[1]).count(-1) == 1
