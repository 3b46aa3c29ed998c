"""Native (C++) batched JPEG ingest: build, parity vs the PIL path, fallback.

The native library (mpi_pytorch_tpu/native/decode.cpp) is the TPU-host
equivalent of the reference's parallel-ingest machinery (torch DataLoader
workers, ``data_loader.py:29-39``; MPI preprocessing ranks,
``evaluation_pipeline.py:53-129``). These tests pin its contract:

- decode parity: same libjpeg, so exact-size decode is bit-identical to PIL
- resize parity: the separable triangle filter matches PIL's BILINEAR within
  fixed-point rounding (<1.5/255 per pixel); its vertical pass runs first,
  so its float32 sums run in another order than a horizontal pass first
  would give them, and stay within 1e-3 of the same filter in float64
- DCT prescale modes trade PIL-exactness for IDCT work, with bounded deviation
- corrupt / non-JPEG items fall back to PIL one at a time
- the output dtype is the caller's: a bfloat16 batch has the bits of the
  float32 batch converted afterwards (round to nearest, ties to even)
"""

from __future__ import annotations

import ctypes
import os

import ml_dtypes
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
BF16 = np.dtype(ml_dtypes.bfloat16)

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


def _triangle_weights(in_size, out_size):
    """[out_size, in_size] float64 weights of Pillow's BILINEAR: a triangle
    of support max(1, in/out) around each output's centre, cut at the edges
    and normalized to sum 1."""
    scale = in_size / out_size
    support = max(scale, 1.0)
    centre = (np.arange(out_size) + 0.5) * scale
    lo = np.maximum((centre - support + 0.5).astype(np.int64), 0)
    hi = np.minimum((centre + support + 0.5).astype(np.int64), in_size)
    src = np.arange(in_size)
    arg = np.abs((src[None, :] - centre[:, None] + 0.5) / support)
    inside = (src[None, :] >= lo[:, None]) & (src[None, :] < hi[:, None])
    w = np.where(inside, np.maximum(1.0 - arg, 0.0), 0.0)
    return w / w.sum(axis=1, keepdims=True)


def _decoded_pixels(path):
    """The uint8 pixels the native decoder resizes: the full decode, through
    its equal-size branch, with the normalization undone."""
    with Image.open(path) as im:
        w, h = im.size
    out = native.decode_batch([path], (h, w), MEAN, STD, prescale_margin=0)[0]
    pixels = (out.astype(np.float64) * STD + MEAN) * 255.0
    assert np.abs(pixels - np.round(pixels)).max() < 1e-3
    return np.round(pixels)


@pytest.mark.parametrize(
    "src_hw,out_hw",
    [
        ((500, 334), (128, 128)),  # the streaming cell's prescaled portrait
        ((334, 500), (128, 128)),  # landscape
        ((257, 131), (128, 64)),  # the last columns' taps run past the row
        ((9, 5), (4, 2)),  # a row narrower than a column's padded taps
        ((100, 90), (128, 128)),  # upscale: three taps
    ],
    ids=["portrait", "landscape", "right_edge", "narrow", "upscale"],
)
def test_resize_matches_the_triangle_filter_in_float64(tmp_path, src_hw, out_hw):
    """The C resize against the same separable filter computed in float64:
    only the order of float32 sums may differ."""
    p = str(tmp_path / "a.jpg")
    _write_jpeg(p, (synthetic_image(6, src_hw) * 255).astype(np.uint8))
    pixels = _decoded_pixels(p)
    wv = _triangle_weights(src_hw[0], out_hw[0])
    wh = _triangle_weights(src_hw[1], out_hw[1])
    ref = np.einsum("yi,ijc,xj->yxc", wv, pixels, wh)
    out = native.decode_batch([p], out_hw, MEAN, STD, prescale_margin=0)[0]
    got = (out.astype(np.float64) * STD + MEAN) * 255.0
    assert np.abs(got - ref).max() < 1e-3


@pytest.mark.parametrize("threads", [1, 2])
def test_a_batch_of_mixed_source_sizes_is_each_image_decoded_alone(tmp_path, threads):
    """A worker that meets one source size after another resizes each as if
    it were the only one: nothing of one image's kernels or scratch leaks
    into the next."""
    paths = []
    for i, hw in enumerate([(500, 334), (334, 500), (140, 140)]):
        paths.append(str(tmp_path / f"{i}.jpg"))
        _write_jpeg(paths[-1], (synthetic_image(i, hw) * 255).astype(np.uint8))
    order = [0, 1, 0, 2, 1, 2, 0]
    batch = native.decode_batch(
        [paths[i] for i in order], (128, 128), MEAN, STD, threads=threads
    )
    alone = [native.decode_batch([p], (128, 128), MEAN, STD, threads=1)[0] for p in paths]
    for row, i in zip(batch, order):
        np.testing.assert_array_equal(row, alone[i])


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


def _jpeg_manifest(tmp_path, n=12, dirs=0):
    """``n`` JPEGs under ``img_dir``: at its top, or spread over ``dirs``
    directories ``images/NNN/`` as the benchmark's dataset lays them out."""
    img_dir = tmp_path / "img"
    img_dir.mkdir()
    names, labels = [], []
    for i in range(n):
        name = f"images/{i % dirs:03d}/im_{i}.jpg" if dirs else f"im_{i}.jpg"
        (img_dir / name).parent.mkdir(parents=True, exist_ok=True)
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


# ---------------------------------------------------------------------------
# the output dtype is the caller's (ISSUE 36)
# ---------------------------------------------------------------------------


def _float_ptr(a):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_float))


def _source_jpeg(tmp_path, kind):
    """A JPEG that takes the decoder's ``kind`` of way to 64 x 64: ``resized``
    (400 x 300: each prescale margin picks another IDCT scale), ``exact``
    (already 64 x 64: the no-resize branch) or ``gray`` (one component)."""
    p = tmp_path / f"{kind}.jpg"
    if kind == "gray":
        gray = (synthetic_image(2, (150, 150))[:, :, 0] * 255).astype(np.uint8)
        Image.fromarray(gray, mode="L").save(p, quality=95)
    else:
        size = (300, 400) if kind == "resized" else (64, 64)
        _write_jpeg(p, (synthetic_image(4, size) * 255).astype(np.uint8))
    return str(p)


@pytest.mark.parametrize("threads", [1, 3])
@pytest.mark.parametrize("kind", ["resized", "exact", "gray"])
@pytest.mark.parametrize("prescale_margin", [0, 1, 2])
def test_bfloat16_batch_is_the_float32_batch_converted_bit_for_bit(
    tmp_path, prescale_margin, kind, threads
):
    """The workers' store rounds as ``astype`` does: what the step receives
    is what it received when the producer thread converted the batch."""
    paths = [_source_jpeg(tmp_path, kind)] * 4
    kw = dict(threads=threads, prescale_margin=prescale_margin)
    f32 = native.decode_batch(paths, (64, 64), MEAN, STD, **kw)
    bf16 = native.decode_batch(paths, (64, 64), MEAN, STD, dtype=BF16, **kw)
    assert f32.dtype == np.float32 and bf16.dtype == BF16 and bf16.shape == f32.shape
    assert np.abs(f32).max() > 0.5  # a picture, not a row of zeros
    np.testing.assert_array_equal(bf16.view(np.uint16), f32.astype(BF16).view(np.uint16))


# float32 bit patterns around bfloat16's rounding points, and the bfloat16
# bits nearest-even gives: exact ties go to the even neighbour, whichever side.
_ROUNDING = [
    (0x3F808000, 0x3F80),  # tie, lower neighbour even: down
    (0x3F818000, 0x3F82),  # tie, lower neighbour odd: up
    (0x3F808001, 0x3F81),  # just over the tie: up
    (0x3F817FFF, 0x3F81),  # just under the tie: down
    (0xBF808000, 0xBF80),  # the same ties, negative
    (0xBF818000, 0xBF82),
    (0x3F7F8000, 0x3F80),  # a tie that carries into the exponent
    (0x40490FDB, 0x4049),  # pi
    (0x00000000, 0x0000),
]


@pytest.mark.parametrize("elem,dtype", [(0, np.float32), (1, BF16)])
def test_the_store_rounds_to_nearest_ties_to_even(elem, dtype):
    """``mpt_normalize_store`` is the decoder's own normalize pass; with mean
    0 and std 1/255 its arithmetic is ``x * 1 + -0``, so hand-picked float32
    bits reach the store unchanged."""
    src = np.array([b for b, _ in _ROUNDING], np.uint32).view(np.float32)
    assert len(src) % 3 == 0
    mean = np.zeros(3, np.float32)
    std = np.full(3, np.float32(1) / np.float32(255), np.float32)
    dst = np.full(len(src), 1, dtype)
    rc = native.load().mpt_normalize_store(
        _float_ptr(src), len(src) // 3, _float_ptr(mean), _float_ptr(std),
        dst.ctypes.data_as(ctypes.c_void_p), elem,
    )
    assert rc == 0
    if dtype == np.float32:
        np.testing.assert_array_equal(dst.view(np.uint32), src.view(np.uint32))
    else:
        assert [hex(b) for b in dst.view(np.uint16)] == [hex(b) for _, b in _ROUNDING]
        np.testing.assert_array_equal(dst.view(np.uint16), src.astype(BF16).view(np.uint16))


def test_an_element_type_the_decoder_does_not_write_is_refused(tmp_path):
    path = _source_jpeg(tmp_path, "exact")
    with pytest.raises(ValueError, match="float32 or bfloat16"):
        native.decode_batch([path], (64, 64), MEAN, STD, dtype=np.float16)
    lib = native.load()
    out = np.zeros(3, np.float32)
    assert lib.mpt_normalize_store(
        _float_ptr(out), 1, _float_ptr(MEAN), _float_ptr(STD),
        out.ctypes.data_as(ctypes.c_void_p), 7,
    ) == -1


@pytest.mark.parametrize("dtype", [np.dtype(np.float32), BF16], ids=str)
def test_corrupt_item_in_a_batch_of_either_dtype(tmp_path, dtype):
    """The fallback's float32 row lands converted in the batch's dtype; and
    the C entry point, with no fallback after it, leaves the failed item's
    row zeroed in the dtype's width — its neighbours untouched."""
    good = _source_jpeg(tmp_path, "exact")
    bad = tmp_path / "bad.jpg"
    bad.write_bytes(b"this is not a jpeg")
    row = normalize_image(synthetic_image(9, (64, 64)))
    paths = [good, str(bad), good]
    out = native.decode_batch(
        paths, (64, 64), MEAN, STD, fallback=lambda p: row, dtype=dtype
    )
    assert out.dtype == dtype
    np.testing.assert_array_equal(out[1], row.astype(dtype))
    np.testing.assert_array_equal(out[0], out[2])
    with pytest.raises(RuntimeError, match="native decode failed for 1 item"):
        native.decode_batch(paths, (64, 64), MEAN, STD, dtype=dtype)
    # The same call as decode_batch makes, on a buffer that starts as ones.
    raw = np.ones((3, 64, 64, 3), dtype)
    statuses = np.zeros(3, np.int32)
    c_paths = (ctypes.c_char_p * 3)(*[os.fsencode(p) for p in paths])
    failures = native.load().mpt_decode_batch(
        None, c_paths, 3, 64, 64, _float_ptr(MEAN), _float_ptr(STD),
        raw.ctypes.data_as(ctypes.c_void_p), native._elem(dtype), 2, 2,
        statuses.ctypes.data_as(ctypes.POINTER(ctypes.c_int)),
    )
    assert failures == 1 and list(statuses != 0) == [False, True, False]
    assert not raw[1].view(np.uint8).any()
    np.testing.assert_array_equal(raw[0], out[0])
    np.testing.assert_array_equal(raw[2], out[2])


@pytest.mark.parametrize("host_cache", [False, True])
def test_bfloat16_loader_yields_the_bits_the_producers_cast_gave(tmp_path, host_cache):
    """A bfloat16 epoch from the native source, whose workers store the
    dtype, is bit for bit the float32 decode converted afterwards (what the
    producer thread did before ISSUE 36), and the PIL source's epoch up to
    the decoders' known pixel difference plus one bfloat16 rounding."""
    m = _jpeg_manifest(tmp_path)
    kw = dict(batch_size=4, image_size=(128, 128), shuffle=False, drop_remainder=False,
              image_dtype="bfloat16", decode_prescale=0)
    loader = DataLoader(m, **kw, native_decode=True, host_cache=host_cache)
    native_batches = list(loader.epoch(0))
    pil_batches = list(DataLoader(m, **kw, native_decode=False).epoch(0))
    assert len(native_batches) == len(pil_batches) == 3
    paths = [os.path.join(m.img_dir, f) for f in m.filenames]
    before = native.decode_batch(paths, (128, 128), MEAN, STD, prescale_margin=0).astype(BF16)
    for b, ((ni, nl), (pi, pl)) in enumerate(zip(native_batches, pil_batches)):
        assert ni.dtype == pi.dtype == BF16
        np.testing.assert_array_equal(nl, pl)
        np.testing.assert_array_equal(
            ni.view(np.uint16), before[4 * b : 4 * b + 4].view(np.uint16)
        )
        # bfloat16 near 2.7 steps by 2**-7: under half a pixel at std 0.229.
        assert _pixel_diff(ni.astype(np.float32), pi.astype(np.float32)) < 1.5 + 0.5
    if host_cache:  # filled without a conversion, served as it was filled
        for (ni, _), (ci, _) in zip(native_batches, loader.epoch(0)):
            np.testing.assert_array_equal(ni.view(np.uint16), ci.view(np.uint16))


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
# names relative to a root: opened from the directories the call holds
# ---------------------------------------------------------------------------


def _tree(tmp_path):
    """``(root, names)``: a root with two directories that several names
    share (one two levels down), one that holds a single name, and a name
    at the top — each kind of open a rooted call makes."""
    root = tmp_path / "root"
    names = ["a/0.jpg", "b/c/1.jpg", "a/2.jpg", "d/3.jpg", "b/c/4.jpg", "a/5.jpg", "6.jpg"]
    for k, name in enumerate(names):
        (root / name).parent.mkdir(parents=True, exist_ok=True)
        _write_jpeg(root / name, (synthetic_image(k, (90 + 7 * k, 140)) * 255).astype(np.uint8))
    return str(root), names


def _open_fds():
    return len(os.listdir("/proc/self/fd"))


@pytest.mark.parametrize("dtype", [np.dtype(np.float32), BF16], ids=str)
def test_root_and_relative_names_decode_the_bits_of_whole_paths(tmp_path, dtype):
    root, names = _tree(tmp_path)
    paths = [os.path.join(root, name) for name in names]
    whole = native.decode_batch(paths, (64, 64), MEAN, STD, threads=3, dtype=dtype)
    rooted = native.decode_batch(names, (64, 64), MEAN, STD, root=root, threads=3, dtype=dtype)
    assert rooted.dtype == dtype and np.abs(whole.astype(np.float32)).max() > 0.5
    view = np.uint32 if dtype == np.float32 else np.uint16
    np.testing.assert_array_equal(rooted.view(view), whole.view(view))


@pytest.mark.parametrize("threads", [1, 3])
def test_opened_counts_follow_the_batch_directories(tmp_path, threads):
    """Names in a directory another name of the batch shares are opened
    from it (``dir``), a lone one and a name at the top from the root
    (``root``); without a root every name is a whole path (``path``). The
    counts sum to the images decoded: a refused one is not counted."""
    root, names = _tree(tmp_path)
    (tmp_path / "root" / "a" / "bad.jpg").write_bytes(b"this is not a jpeg")
    names = names + ["a/bad.jpg"]

    def opened(**kw):
        before = native.counters()[3]
        native.decode_batch(
            names if "root" in kw else [os.path.join(root, n) for n in names], (32, 32),
            MEAN, STD, threads=threads, fallback=lambda p: np.zeros((32, 32, 3), np.float32),
            **kw,
        )
        return {k: v - before[k] for k, v in native.counters()[3].items()}

    assert native.OPENED == ("dir", "root", "path")
    assert opened(root=root) == {"dir": 5, "root": 2, "path": 0}
    assert opened() == {"dir": 0, "root": 0, "path": 7}


def test_an_absolute_name_in_a_rooted_call_decodes_as_itself(tmp_path):
    root, names = _tree(tmp_path)
    elsewhere = _source_jpeg(tmp_path, "exact")
    assert os.path.isabs(elsewhere) and not elsewhere.startswith(root)
    before = native.counters()[3]
    out = native.decode_batch([names[0], elsewhere, names[2]], (64, 64), MEAN, STD, root=root)
    took = {k: v - before[k] for k, v in native.counters()[3].items()}
    np.testing.assert_array_equal(out[1], native.decode_batch([elsewhere], (64, 64), MEAN, STD)[0])
    assert took == {"dir": 2, "root": 0, "path": 1}


@pytest.mark.parametrize(
    "case", ["missing_name", "missing_shared_dir", "missing_root", "root_is_a_file"]
)
def test_what_a_rooted_call_cannot_open_falls_back_by_its_joined_path(tmp_path, case):
    """An item whose file cannot be opened goes to the fallback with
    ``os.path.join(root, name)``, whatever could not be opened: the name,
    the directory it shares with another name (the root then tries it), or
    the root itself (every relative name fails). The rest decode as whole
    paths do, and the call leaves no descriptor open."""
    root, names = _tree(tmp_path)
    if case == "missing_name":
        names = names + ["a/nope.jpg"]
    elif case == "missing_shared_dir":
        names = names + ["nodir/x.jpg", "nodir/y.jpg"]
    elif case == "missing_root":
        root = str(tmp_path / "no-such-root")
    else:
        root = os.path.join(root, "6.jpg")
    expected = native.decode_batch(
        [os.path.join(str(tmp_path / "root"), n) for n in names], (32, 32), MEAN, STD,
        fallback=lambda p: np.full((32, 32, 3), -1.0, np.float32),
    )
    calls = []

    def fallback(path):
        calls.append(path)
        return np.full((32, 32, 3), -1.0, np.float32)

    fds = _open_fds()
    out = native.decode_batch(names, (32, 32), MEAN, STD, root=root, threads=3, fallback=fallback)
    assert _open_fds() == fds
    failed = {
        "missing_name": ["a/nope.jpg"],
        "missing_shared_dir": ["nodir/x.jpg", "nodir/y.jpg"],
    }.get(case, names)
    assert sorted(calls) == sorted(os.path.join(root, n) for n in failed)
    if case in ("missing_name", "missing_shared_dir"):
        np.testing.assert_array_equal(out, expected)
    else:
        assert np.all(out == -1.0)


def test_the_stage_and_open_counters_stay_apart(tmp_path):
    """The open counts are images, not nanoseconds: they sit beside the
    stages, and a rooted batch's four stages still sum to its busy time."""
    root, names = _tree(tmp_path)
    fds = _open_fds()
    _, busy_ns, stage_ns, _ = _counters_around(
        lambda: native.decode_batch(names, (32, 32), MEAN, STD, root=root, threads=2)
    )
    assert _open_fds() == fds
    assert list(stage_ns) == list(native.STAGES)
    assert abs(sum(stage_ns.values()) - busy_ns) <= 0.01 * busy_ns + 5_000 * len(names)


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
    refused, busy_ns = (a - b for a, b in zip(native.counters()[:2], before[:2]))
    assert refused == 1
    assert 0 < busy_ns <= 4 * wall_ns


def _counters_around(call):
    """``(refused, busy_ns, {stage: ns}, jpeg_scan ns)`` that ``call()``
    added."""
    refused, busy_ns, stage_ns, _ = native.counters()
    call()
    refused_now, busy_ns_now, stage_ns_now, _ = native.counters()
    assert native.STAGES == ("file", "jpeg", "resize", "normalize")
    assert list(stage_ns_now) == [*native.STAGES, "jpeg_scan"]
    took = {k: stage_ns_now[k] - stage_ns[k] for k in stage_ns_now}
    # The scanline loop is a part of ``jpeg``, not a fifth stage.
    scan_ns = took.pop("jpeg_scan")
    assert 0 <= scan_ns <= took["jpeg"]
    return refused_now - refused, busy_ns_now - busy_ns, took, scan_ns


def test_native_stage_counters_split_the_busy_time(tmp_path):
    """Beside the busy time, its four stages (ISSUE 35): every one grows for
    a JPEG that needs a resize, none ever falls, and together they are the
    busy time — the loop's own bookkeeping is all that may be left over
    (1 % and a few microseconds an image)."""
    m = _jpeg_manifest(tmp_path, n=8)  # 200 x 150 sources
    paths = [os.path.join(m.img_dir, f) for f in m.filenames]
    decode = lambda: native.decode_batch(paths, (64, 64), MEAN, STD, threads=3)
    _, busy_ns, stage_ns, scan_ns = _counters_around(decode)
    assert all(ns > 0 for ns in stage_ns.values()), stage_ns
    assert abs(sum(stage_ns.values()) - busy_ns) <= 0.01 * busy_ns + 5_000 * len(paths)
    # The loop decodes every pixel; the header and the start are what is left.
    assert 0.5 * stage_ns["jpeg"] < scan_ns < stage_ns["jpeg"]
    # Monotone: a second batch adds to each, and a batch of nothing adds nothing.
    _, _, again, scan_again = _counters_around(decode)
    assert all(ns > 0 for ns in again.values()) and scan_again > 0
    assert _counters_around(lambda: None) == (0, 0, dict.fromkeys(native.STAGES, 0), 0)


def test_refused_image_leaves_its_time_on_the_stage_it_stopped_in(tmp_path):
    """A CMYK JPEG is read whole, refused after libjpeg's header and handed
    to the fallback: ``file`` and ``jpeg`` grow, the stages after the refusal
    do not, and ``refused`` counts it."""
    cmyk = tmp_path / "cmyk.jpg"
    Image.new("CMYK", (80, 60), (10, 200, 30, 40)).save(cmyk, quality=90)
    refused, busy_ns, stage_ns, scan_ns = _counters_around(
        lambda: native.decode_batch(
            [str(cmyk)], (32, 32), MEAN, STD, threads=1,
            fallback=lambda p: np.zeros((32, 32, 3), np.float32),
        )
    )
    assert refused == 1
    assert stage_ns["file"] > 0 and stage_ns["jpeg"] > 0
    assert stage_ns["resize"] == 0 and stage_ns["normalize"] == 0 and scan_ns == 0
    assert sum(stage_ns.values()) == busy_ns


def test_library_of_the_old_abi_is_rebuilt_once(tmp_path, monkeypatch):
    """A cached library that loads but answers another ABI version (3: two
    counters where this source writes ten; 5: seven, and no root before the
    names) is unmapped, deleted and rebuilt from the source — never skipped,
    and never answered by the stale mapping dlopen keeps for a path it has
    already loaded."""
    import subprocess

    with open(native._SRC) as f:
        src = f.read()
    current = f"int mpt_abi_version() {{ return {native._ABI_VERSION}; }}"
    assert current in src
    for old in (3, 5):
        old_src = tmp_path / f"old{old}.cpp"
        old_src.write_text(src.replace(current, f"int mpt_abi_version() {{ return {old}; }}"))
        os.makedirs(tmp_path / str(old))
        cached = str(tmp_path / str(old) / native._lib_name())
        subprocess.run(
            ["g++", "-O0", "-shared", "-fPIC", "-std=c++17", str(old_src), "-o", cached,
             "-ljpeg", "-pthread"],
            check=True, capture_output=True, timeout=120,
        )
        monkeypatch.setattr(native, "_candidate_paths", lambda name: [cached])
        monkeypatch.setattr(native, "_build_error", None)
        lib = native._try_load()
        assert lib is not None, native._build_error
        assert native._abi_version(lib) == native._ABI_VERSION == 6
        out = (native.ctypes.c_longlong * 10)()
        lib.mpt_decode_counters(out)  # ten values, not two or seven: no write past the end
        assert list(out) == [0] * 10


def _traced_epoch(spans_of, loader):
    batches = []
    spans = spans_of(lambda: batches.extend(loader.epoch(0)), epochs=1)
    return batches, spans


@pytest.mark.parametrize(
    "source,kw",
    [
        ("native", dict(native_decode=True, image_dtype="bfloat16")),
        ("native", dict(native_decode=True, image_dtype="float32")),
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
    # Beside what it produced: the whole process's CPU seconds over the
    # producer's life (every thread's, so it may pass the span's own length)
    # and the cores the process may run on (ISSUE 35).
    assert {k: life["args"][k] for k in ("epoch", "batches")} == {"epoch": 0, "batches": 3}
    assert set(life["args"]) == {"epoch", "batches", "cpu_s", "host_cpus"}
    assert life["args"]["cpu_s"] > 0
    assert 1 <= life["args"]["host_cpus"] == len(os.sched_getaffinity(0))
    decodes = spans["loader/decode"]
    assert len(decodes) == 3
    for e in decodes:
        args = e["args"]
        assert args["images"] == 4 and args["source"] == source and args["threads"] == 3
        # The dtype the source produced: the C decoder's workers store the
        # batch dtype, the PIL pool float32 (ISSUE 36).
        assert args["wrote"] == (kw["image_dtype"] if source == "native" else "float32")
        assert args["fallbacks"] == 0 and args["quarantined"] == 0
        assert 0 < args["thread_busy_s"] <= 3 * e["dur"] / 1e6 * 1.05
        assert life["ts"] <= e["ts"] and e["ts"] + e["dur"] <= life["ts"] + life["dur"]
        # The C decoder's seconds by stage, which are its busy seconds; the
        # Python paths have no stages and say nothing.
        if source == "native":
            assert list(args["stage_s"]) == ["file", "jpeg", "resize", "normalize"]
            assert all(v > 0 for v in args["stage_s"].values())
            assert sum(args["stage_s"].values()) == pytest.approx(
                args["thread_busy_s"], rel=0.01, abs=5e-6 * args["images"]
            )
            assert 0 < args["jpeg_scan_s"] < args["stage_s"]["jpeg"]
            # How the files were reached: every name sits at img_dir's top.
            assert args["opened"] == {"dir": 0, "root": 4, "path": 0}
        else:
            assert "stage_s" not in args and "jpeg_scan_s" not in args
            assert "opened" not in args
    # The Python paths time themselves as decode.cpp does; the C path does
    # not touch the Python counter.
    assert (loader._py_busy_ns > 0) == (source == "pil")
    # ``loader/cast`` a batch where the batch dtype is not float32, with the
    # rows the producer thread converted itself: none on the native path.
    casts = spans.get("loader/cast", [])
    assert len(casts) == (3 if kw["image_dtype"] == "bfloat16" else 0)
    assert {e["args"]["converted"] for e in casts} <= {0 if source == "native" else 4}
    assert all(b[0].dtype == loader.image_dtype for b in batches)
    assert len(spans["loader/put"]) == 4  # three batches and the sentinel
    assert len(spans["loader/get"]) == 4
    # Producer and consumer run on two threads.
    assert {e["tid"] for e in spans["loader/put"]} != {e["tid"] for e in spans["loader/get"]}


def test_loader_opens_a_batch_from_the_directories_its_names_share(tmp_path, spans_of):
    """The loader hands the C decoder ``img_dir`` and the manifest's names
    as they are: a batch whose names share their directories is opened from
    them (``opened.dir`` = its images), gives the bits the whole paths gave,
    and ``stage_s`` keeps exactly the four stages."""
    m = _jpeg_manifest(tmp_path, n=12, dirs=2)
    loader = DataLoader(
        m, batch_size=4, image_size=(64, 64), shuffle=False, num_workers=3,
        native_decode=True, image_dtype="bfloat16",
    )
    batches, spans = _traced_epoch(spans_of, loader)
    paths = [os.path.join(m.img_dir, f) for f in m.filenames]
    whole = native.decode_batch(paths, (64, 64), MEAN, STD, dtype=BF16)
    for b, (images, _) in enumerate(batches):
        np.testing.assert_array_equal(
            images.view(np.uint16), whole[4 * b : 4 * b + 4].view(np.uint16)
        )
    decodes = spans["loader/decode"]
    assert len(decodes) == 3
    for e in decodes:
        assert e["args"]["opened"] == {"dir": 4, "root": 0, "path": 0}
        assert list(e["args"]["stage_s"]) == ["file", "jpeg", "resize", "normalize"]


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
