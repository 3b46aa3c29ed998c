"""Native (C++) host-ingest library: batched JPEG decode→resize→normalize,
stored in the dtype the caller asks for (float32 or bfloat16).

The reference's ingest parallelism is native code wearing Python clothes —
torch DataLoader worker processes (``data_loader.py:29-39``) and three
dedicated MPI preprocessing ranks (``evaluation_pipeline.py:53-129``). This
module is the TPU-host equivalent: ``decode.cpp`` decodes a whole batch on
C++ threads in ONE ctypes call (GIL released for its duration), so host
decode scales with cores instead of fighting the interpreter lock.

Build-on-demand: the shared library is compiled with g++ the first time it's
needed and cached next to the source (falling back to a per-user cache dir if
the package is read-only), under a name that carries a hash of ``decode.cpp``
— a binary built from any other source (a stale one that travelled with a
copied tree, whose mtimes a copy does not preserve) has another name and can
never load. Every entry point degrades gracefully: if the
toolchain, libjpeg, or the build is unavailable, ``load()`` returns ``None``
and callers keep using the pure-PIL path; if an individual file fails to
decode (corrupt, non-JPEG, CMYK), only that item falls back to PIL.
"""

from __future__ import annotations

import _ctypes
import ctypes
import glob
import hashlib
import os
import subprocess
import tempfile
import threading
from typing import Sequence

import numpy as np

_SRC = os.path.join(os.path.dirname(__file__), "decode.cpp")
_LIB_PREFIX = "_mptnative_"

# What decode.cpp's ``mpt_abi_version`` must answer, and the names of the
# nanoseconds ``mpt_decode_counters`` writes after its first two values: the
# four stages of an image, which are its busy time, then ``jpeg_scan``, the
# scanline loop inside ``jpeg``.
_ABI_VERSION = 5
STAGES = ("file", "jpeg", "resize", "normalize")
_COUNTER_NAMES = STAGES + ("jpeg_scan",)


def _elem(dtype) -> int:
    """decode.cpp's ``Elem`` for an output dtype: 0 float32, 1 bfloat16."""
    dtype = np.dtype(dtype)
    if dtype == np.float32:
        return 0
    if dtype.name == "bfloat16":
        return 1
    raise ValueError(f"native decode writes float32 or bfloat16, not {dtype}")


_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_load_attempted = False
_build_error: str | None = None

# Per-item status: 0 = OK; nonzero values are decode.cpp's Status enum
# (unreadable file / corrupt JPEG / refused colorspace) — the wrapper only
# distinguishes zero from nonzero and routes failures to the PIL fallback.


def _lib_name() -> str:
    """``_mptnative_<sha256 of decode.cpp, 12 hex>.so``."""
    with open(_SRC, "rb") as f:
        return f"{_LIB_PREFIX}{hashlib.sha256(f.read()).hexdigest()[:12]}.so"


def _candidate_paths(lib_name: str) -> list[str]:
    cache = os.path.join(
        os.environ.get("XDG_CACHE_HOME", os.path.expanduser("~/.cache")), "mpi_pytorch_tpu"
    )
    return [os.path.join(os.path.dirname(__file__), lib_name), os.path.join(cache, lib_name)]


def _build(out_path: str) -> None:
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    # Atomic: build to a temp name then rename, so a concurrent process never
    # dlopens a half-written library.
    fd, tmp = tempfile.mkstemp(dir=os.path.dirname(out_path), suffix=".so")
    os.close(fd)
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", _SRC, "-o", tmp, "-ljpeg", "-pthread"]
    try:
        subprocess.run(cmd, check=True, capture_output=True, text=True, timeout=120)
        os.replace(tmp, out_path)
    except BaseException:
        if os.path.exists(tmp):
            os.unlink(tmp)
        raise
    # Builds of older sources can never load again: drop them.
    for stale in glob.glob(os.path.join(os.path.dirname(out_path), "_mptnative*.so")):
        if stale != out_path:
            try:
                os.unlink(stale)
            except OSError:
                pass


def _abi_version(lib: ctypes.CDLL) -> int:
    """The library's ABI version; -1 for a library without the symbol (a
    foreign or pre-versioning build) — any failure here must mean 'stale',
    never an exception, so the caller can rebuild or fall back to PIL."""
    try:
        return int(lib.mpt_abi_version())
    except (AttributeError, OSError):
        return -1


def _try_load() -> ctypes.CDLL | None:
    global _build_error
    try:
        lib_name = _lib_name()
    except OSError as e:  # source not shipped (trimmed install): PIL path
        _build_error = f"native source unavailable: {e}"
        return None
    last_err: str | None = None
    for path in _candidate_paths(lib_name):
        # Two attempts per candidate: a cached library that loads but has the
        # wrong ABI is deleted and rebuilt once, not skipped (a skip would
        # silently run the whole job on the slower PIL path).
        lib = None
        for _ in range(2):
            try:
                if not os.path.exists(path):
                    _build(path)
                lib = ctypes.CDLL(path)
            except (OSError, subprocess.SubprocessError) as e:
                out = getattr(e, "stderr", "")
                last_err = f"{type(e).__name__}: {e} {out}"
                lib = None
                break  # build/load failure: move to the next candidate dir
            if _abi_version(lib) == _ABI_VERSION:
                break
            last_err = f"stale native library (wrong ABI) at {path}"
            # Unmap it: dlopen answers a path it has already loaded with that
            # mapping, so the rebuilt file would never be looked at.
            _ctypes.dlclose(lib._handle)
            lib = None
            try:
                os.unlink(path)  # next attempt rebuilds from source
            except OSError as e:
                last_err = f"stale native library at {path}, unlink failed: {e}"
                break
        if lib is None:
            continue
        lib.mpt_decode_batch.restype = ctypes.c_int
        lib.mpt_decode_batch.argtypes = [
            ctypes.POINTER(ctypes.c_char_p),
            ctypes.c_int,
            ctypes.c_int,
            ctypes.c_int,
            ctypes.POINTER(ctypes.c_float),
            ctypes.POINTER(ctypes.c_float),
            ctypes.c_void_p,
            ctypes.c_int,
            ctypes.c_int,
            ctypes.c_int,
            ctypes.POINTER(ctypes.c_int),
        ]
        lib.mpt_normalize_store.restype = ctypes.c_int
        lib.mpt_normalize_store.argtypes = [
            ctypes.POINTER(ctypes.c_float),
            ctypes.c_int,
            ctypes.POINTER(ctypes.c_float),
            ctypes.POINTER(ctypes.c_float),
            ctypes.c_void_p,
            ctypes.c_int,
        ]
        lib.mpt_decode_counters.restype = None
        lib.mpt_decode_counters.argtypes = [ctypes.POINTER(ctypes.c_longlong)]
        # (decode.cpp also exports mpt_decode_one for ad-hoc C consumers and
        # microbenchmarks; the framework only uses the batch entry point, and
        # the tests mpt_normalize_store for the store's rounding.)
        return lib
    _build_error = last_err
    return None


def load() -> ctypes.CDLL | None:
    """The loaded native library, building it if needed; None if unavailable."""
    global _lib, _load_attempted
    if _lib is not None or _load_attempted:
        return _lib
    with _lock:
        if not _load_attempted:
            from mpi_pytorch_tpu.config import _str2bool  # same MPT_* semantics

            disable = os.environ.get("MPT_DISABLE_NATIVE", "")
            if disable and _str2bool(disable):
                global _build_error
                _build_error = "disabled via MPT_DISABLE_NATIVE"
                _lib = None
            else:
                _lib = _try_load()
            _load_attempted = True
    return _lib


def available() -> bool:
    return load() is not None


def build_error() -> str | None:
    """Why the native library failed to load (for log lines), if it did."""
    load()
    return _build_error


def counters() -> tuple[int, int, dict[str, int]]:
    """``(images refused, nanoseconds the worker threads spent inside a
    decode, those nanoseconds by name)`` over every ``decode_batch`` call of
    this process so far — seven values kept where the decode happens (atomics
    in decode.cpp). The names are ``STAGES`` (read the file, libjpeg, the
    resize, the normalize pass), which partition the busy time — a refused or
    failed image leaves what it spent on the stage it stopped in — and then
    ``jpeg_scan``, the scanline loop's share of ``jpeg``. A caller reads
    before and after a call and takes the differences; zeros when the
    library is unavailable."""
    lib = load()
    if lib is None:
        return 0, 0, dict.fromkeys(_COUNTER_NAMES, 0)
    out = (ctypes.c_longlong * (2 + len(_COUNTER_NAMES)))()
    lib.mpt_decode_counters(out)
    return int(out[0]), int(out[1]), dict(zip(_COUNTER_NAMES, map(int, out[2:])))


def decode_batch(
    paths: Sequence[str],
    image_size: tuple[int, int],
    mean: np.ndarray,
    std: np.ndarray,
    *,
    threads: int = 8,
    prescale_margin: int = 2,
    fallback=None,
    dtype=np.float32,
) -> np.ndarray:
    """Decode+resize+normalize a batch of JPEG files → ``dtype`` [N,H,W,3].

    One C call on ``threads`` native threads with the GIL released. ``dtype``
    is float32 or ``ml_dtypes.bfloat16``: the worker threads store it in
    their normalize pass (bfloat16 rounded to nearest, ties to even — the
    bits ``astype`` would give), so the caller converts nothing afterwards.
    Items the native path refuses (corrupt file, CMYK, ...) are retried
    through ``fallback(path) -> normalized HWC f32`` (e.g. the PIL path;
    numpy converts that one row) so odd files degrade one at a time instead
    of failing the batch.

    ``prescale_margin`` controls libjpeg DCT prescaling for large sources:
    0 = full-resolution decode (PIL bit-parity, slowest), 1 = decode just past
    the target (fastest), 2 = keep a 2x margin so everything the final
    antialias filter passes survives the scaled IDCT (default).
    """
    lib = load()
    if lib is None:
        raise RuntimeError(f"native decode unavailable: {_build_error}")
    n = len(paths)
    h, w = image_size
    elem = _elem(dtype)
    out = np.empty((n, h, w, 3), dtype=dtype)
    statuses = np.zeros(n, dtype=np.int32)
    mean32 = np.ascontiguousarray(mean, dtype=np.float32)
    std32 = np.ascontiguousarray(std, dtype=np.float32)
    encoded = [os.fsencode(p) for p in paths]
    c_paths = (ctypes.c_char_p * n)(*encoded)
    failures = lib.mpt_decode_batch(
        c_paths,
        n,
        h,
        w,
        mean32.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        std32.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        out.ctypes.data_as(ctypes.c_void_p),
        elem,
        threads,
        prescale_margin,
        statuses.ctypes.data_as(ctypes.POINTER(ctypes.c_int)),
    )
    if failures:
        bad = np.nonzero(statuses)[0]
        if fallback is None:
            raise RuntimeError(
                f"native decode failed for {len(bad)} item(s), e.g. {paths[bad[0]]!r} "
                f"(status {statuses[bad[0]]})"
            )
        for i in bad:
            out[i] = fallback(paths[i])
    return out
