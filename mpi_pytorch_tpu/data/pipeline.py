"""Host-side input pipeline: decode → RGB → resize → normalize → batch → prefetch.

This collapses two reference components into one idiomatic pipeline:

- ``data_loader.py:6-39`` (``GetData`` Dataset: per-item PIL open + transform)
- the first three stages of the 4-stage MPI inference pipeline
  (``evaluation_pipeline.py:53-129``: rank 0 reads, rank 1 resizes, rank 2
  normalizes, streaming pickled PIL images between ranks over MPI send/recv).

TPU-first design: the pipeline overlap the MPI stages bought with dedicated
ranks is had for free with a thread pool + a bounded prefetch queue on each
host; the device only ever sees fixed-shape normalized float batches, so the
jitted step never recompiles. Transform math matches the reference
(``main.py:62-65``): ToTensor (scale to [0,1]) → Resize(H,W) → Normalize
(ImageNet mean/std), with the grayscale fix (`.convert('RGB')`) the reference
is missing (SURVEY §3 quirks).

Where a streaming epoch's time goes is written on the run's tracer
(``obs.trace.current()``, inert unless a driver installed one): on the
producer thread ``loader/epoch`` (its whole life; wall time outside it is
time with no producer alive; args ``cpu_s``, the CPU seconds of the whole
process over that life, and ``host_cpus``, the cores it may run on),
``loader/decode`` per batch (args ``images``,
``source``, ``threads``, ``fallbacks``, ``quarantined``, ``thread_busy_s``
— the seconds the decode workers were inside a decode —, ``wrote``, the
dtype the source produced, and, for the native
source, ``stage_s``: those seconds by stage of the C decoder, with
``jpeg_scan_s``, the scanline loop's part of its ``jpeg`` stage; all from
counters kept where the decode happens and read only while a tracer records),
``loader/cast`` (in a loader whose batch dtype is not float32: what the
producer thread converts itself — the PIL and pack sources' float32 batches,
arg ``converted`` the rows it converted; ~0 and ``converted`` 0 on the native
path, whose workers store the batch dtype) and ``loader/put`` (blocked on a
full queue: the consumer is
the slower side); on the consumer's thread ``loader/get`` (blocked on an
empty queue: the producer is). docs/OBSERVABILITY.md "Trace spans".
"""

from __future__ import annotations

import os
import queue
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Iterator

import numpy as np

from mpi_pytorch_tpu.config import IMAGENET_MEAN, IMAGENET_STD
from mpi_pytorch_tpu.data.manifest import Manifest
from mpi_pytorch_tpu.obs import trace as obs_trace
from mpi_pytorch_tpu.utils.env import fault_countdown


class BadSampleLimitError(RuntimeError):
    """More samples failed to decode than ``max_bad_samples`` tolerates.
    Raised AFTER the final sample was quarantined and recorded, so the
    abort carries a full quarantine trail — a dataset rotting past the
    budget must fail the run loudly, not train on substitute rows."""

_MEAN = np.asarray(IMAGENET_MEAN, dtype=np.float32)
_STD = np.asarray(IMAGENET_STD, dtype=np.float32)

# Normalized synthetic images by (label, size), capped by BYTES so image
# size doesn't change the memory footprint. First-come insertion: covers
# small-vocabulary runs (e.g. the DEBUG sample's 964 classes) completely;
# full-64500-class runs fall back to regeneration for uncached labels.
_SYNTH_CACHE: dict = {}
_SYNTH_CACHE_BUDGET = 256 * 1024 * 1024
_synth_cache_bytes = 0
# Guards the check-then-insert (loader worker threads share the cache); the
# lock-free read in _load_one is safe under the GIL.
_SYNTH_CACHE_LOCK = threading.Lock()


def epoch_order(seed: int, epoch: int, n: int, shuffle: bool) -> np.ndarray:
    """THE per-epoch visit order, shared by the streaming loader and the
    device-cache index path so both walk the data identically: deterministic
    per ``(seed, epoch)`` — the shuffle discipline the reference lacks
    (``main.py:102``; SURVEY §3 quirks)."""
    if shuffle:
        return np.random.default_rng((seed, epoch)).permutation(n)
    return np.arange(n)


def normalize_image(img: np.ndarray) -> np.ndarray:
    """[0,1] float32 HWC → ImageNet-normalized (parity: transforms.Normalize,
    ``main.py:65``)."""
    return (img - _MEAN) / _STD


def decode_image(path: str, image_size: tuple[int, int]) -> np.ndarray:
    """PIL decode → RGB → resize → [0,1] float32 HWC.

    Matches the reference transform order ToTensor→Resize (``main.py:62-64``)
    numerically: PIL bilinear on the uint8 image differs from torch's resize
    of the float tensor only by rounding; both produce [0,1] floats at (H,W).
    """
    from PIL import Image

    with Image.open(path) as im:
        im = im.convert("RGB").resize((image_size[1], image_size[0]), Image.BILINEAR)
        return np.asarray(im, dtype=np.float32) / 255.0


def synthetic_image(seed: int, image_size: tuple[int, int]) -> np.ndarray:
    """Deterministic synthetic image for environments without the Herbarium
    images (they are gitignored in the reference too, ``.gitignore:2-4``).

    Class-conditioned structure (low-frequency pattern keyed by the seed) so a
    model can actually learn from synthetic data in integration tests.
    """
    rng = np.random.default_rng(seed)
    h, w = image_size
    yy, xx = np.mgrid[0:h, 0:w].astype(np.float32)
    freq = rng.uniform(0.02, 0.3, size=(3,))
    phase = rng.uniform(0, 2 * np.pi, size=(3,))
    img = 0.5 + 0.5 * np.sin(freq[None, None, :] * (yy + xx)[:, :, None] + phase[None, None, :])
    noise = rng.normal(0, 0.05, size=(h, w, 3)).astype(np.float32)
    return np.clip(img + noise, 0.0, 1.0).astype(np.float32)


class DataLoader:
    """Sharded, shuffled, prefetching batch loader.

    Parity mapping:
    - shard-per-process       ≙ rank-0 scatter (``main.py:84-91``)
    - seeded epoch shuffle    ≙ DataLoader(shuffle=True) (``main.py:102``) but
      deterministic per (seed, epoch) — a discipline the reference lacks
      (SURVEY §3 quirks).
    - worker thread pool      ≙ per-item loading inside torch DataLoader
    - prefetch queue          ≙ the overlap the MPI pipeline stages provided
    Batches are (images [B,H,W,3] normalized in ``image_dtype`` — float32 by
    default, bfloat16 to halve host→device transfer — labels [B] int32).
    """

    def __init__(
        self,
        manifest: Manifest,
        batch_size: int,
        image_size: tuple[int, int],
        *,
        shuffle: bool = True,
        seed: int = 0,
        drop_remainder: bool = True,
        synthetic: bool = False,
        num_workers: int = 8,
        prefetch: int = 2,
        image_dtype: str = "float32",
        native_decode: bool = True,
        decode_prescale: int = 2,
        host_cache: bool = False,
        packed_dir: str = "",
        max_bad_samples: int = 16,
        quarantine_file: str = "",
        decode_retries: int = 2,
        decode_retry_backoff_s: float = 0.05,
    ):
        self.manifest = manifest
        self.batch_size = batch_size
        self.image_size = image_size
        self.shuffle = shuffle
        self.seed = seed
        self.drop_remainder = drop_remainder
        self.synthetic = synthetic
        self.num_workers = max(1, num_workers)
        self.prefetch = max(1, prefetch)
        self.decode_prescale = decode_prescale
        # Decode-failure robustness: a sample that still fails after
        # ``decode_retries`` bounded-backoff retries is QUARANTINED — its
        # batch row becomes a copy of a good row with label -1 (masked by
        # the loss exactly like padding), its path is appended to
        # ``quarantine_file`` ("" = no file) and a kind="anomaly"
        # reason="bad_sample" record is written when a metrics writer is
        # attached (``self.metrics``, set by the trainer). More than
        # ``max_bad_samples`` quarantines abort the run loudly
        # (BadSampleLimitError).
        self.max_bad_samples = max_bad_samples
        self.quarantine_file = quarantine_file
        self.decode_retries = max(0, decode_retries)
        self.decode_retry_backoff_s = decode_retry_backoff_s
        self.metrics = None  # optional MetricsWriter, attached post-build
        self.bad_samples = 0
        self._quarantined: set[int] = set()  # manifest row indices
        self._poisoned_decode: set[int] = set()  # MPT_FAULT_DECODE_N victims
        self._bad_lock = threading.Lock()
        # Nanoseconds the Python decode paths (PIL pool, synthetic, the
        # native path's per-item fallback) spent inside a decode while a
        # tracer was recording — the counterpart of decode.cpp's busy time.
        self._py_busy_ns = 0
        self._cur_epoch = 0
        # Decode the whole shard ONCE into host RAM (first epoch), then serve
        # every later epoch by slicing — zero decode cost after epoch 0, at
        # the price of n_images × H × W × 3 × dtype host memory. Works
        # per-host (multi-host safe) and for datasets bigger than HBM —
        # the middle ground between streaming and the device cache.
        self.host_cache = host_cache
        self._cache_images: np.ndarray | None = None
        self._cache_filled: np.ndarray | None = None  # [n] bool, rows decoded
        self._cache_complete = False
        self._fill_thread: threading.Thread | None = None  # in-flight filler
        self._cache_fill_error: BaseException | None = None  # undelivered
        # Offline-packed uint8 dataset (data/packed.py): batches become mmap
        # row slices + a vectorized normalize — no decode at run time at all.
        # Resolution is strict: a set packed_dir with no covering pack raises.
        self.packed_dir = packed_dir
        self._pack = None
        if packed_dir:
            from mpi_pytorch_tpu.data.packed import find_pack

            self._pack = find_pack(packed_dir, manifest, image_size, synthetic)
        # image_dtype 'uint8' = RAW-pixel batches (train/step.py ingest_images
        # normalizes on device): 4x less H2D than f32, 4x smaller host cache;
        # packed batches become plain mmap slices with no host float work.
        self.raw_uint8 = image_dtype == "uint8"
        # Native C++ batched ingest (mpi_pytorch_tpu/native): one GIL-released
        # call decodes the whole batch on C threads. Auto-falls back to the
        # PIL thread pool when the toolchain/libjpeg is unavailable. (Its
        # fused output is normalized floats, so raw-uint8 mode uses PIL.)
        self.native_decode = False
        if native_decode and not synthetic and self._pack is None and not self.raw_uint8:
            from mpi_pytorch_tpu import native as _native

            self.native_decode = _native.available()
        # bfloat16 batches halve host→device transfer (the step computes in
        # bf16 anyway); decode/normalize still run in float32 on the host,
        # and the native decoder's workers store the batch dtype themselves.
        if image_dtype == "bfloat16":
            import ml_dtypes

            self.image_dtype = np.dtype(ml_dtypes.bfloat16)
        else:
            self.image_dtype = np.dtype(image_dtype)

    def __len__(self) -> int:
        n = len(self.manifest)
        return n // self.batch_size if self.drop_remainder else -(-n // self.batch_size)

    @property
    def cache_row(self) -> tuple[tuple[int, ...], np.dtype]:
        """(shape, dtype) of one row of the device cache
        (``trainer.build_device_cache``): a decoded image."""
        return (*self.image_size, 3), self.image_dtype

    def fill_cache_rows(self, manifest, lo: int, hi: int, out: np.ndarray) -> set[int]:
        """Rows ``[lo, hi)`` of ``manifest`` into ``out[: hi - lo]``, in
        place: one ordered decode pass with this loader's settings and its
        metrics writer. Returns the offsets (from ``lo``) of quarantined
        rows, which hold substitute pixels."""
        if hi <= lo:
            return set()
        ordered = DataLoader(
            manifest.select(np.arange(lo, hi)),
            batch_size=self.batch_size,
            image_size=self.image_size,
            shuffle=False,
            drop_remainder=False,
            synthetic=self.synthetic,
            num_workers=self.num_workers,
            prefetch=self.prefetch,
            image_dtype=str(np.dtype(self.image_dtype)),
            native_decode=self.native_decode,
            decode_prescale=self.decode_prescale,
            packed_dir=self.packed_dir,
            max_bad_samples=self.max_bad_samples,
            quarantine_file=self.quarantine_file,
        )
        ordered.metrics = self.metrics
        row = 0
        for batch_images, _ in ordered.epoch(0):
            out[row : row + batch_images.shape[0]] = batch_images
            row += batch_images.shape[0]
        assert row == hi - lo, (row, lo, hi)
        return ordered._quarantined

    def _sample_name(self, i: int) -> str:
        if self.synthetic:
            return f"synthetic:{int(self.manifest.labels[i])}@{i}"
        return os.path.join(self.manifest.img_dir, self.manifest.filenames[i])

    def _quarantine(self, i: int, err: BaseException) -> None:
        """Record one undecodable sample: remember its row (labels mask to
        -1 from now on, including cached epochs), log it, append the path to
        the quarantine file, write the anomaly record — then abort loudly
        once the budget is blown. Runs on worker threads."""
        from mpi_pytorch_tpu.utils.logging import run_logger

        name = self._sample_name(i)
        with self._bad_lock:
            already = i in self._quarantined
            self._quarantined.add(i)
            if not already:
                self.bad_samples += 1
            count = self.bad_samples
        if already:
            return
        run_logger().warning(
            "quarantined undecodable sample %s (%d/%d bad allowed): %s",
            name, count, self.max_bad_samples, err,
        )
        if self.quarantine_file:
            with self._bad_lock:
                with open(self.quarantine_file, "a") as f:
                    f.write(f"{name}\t{type(err).__name__}: {err}\n")
        if self.metrics is not None:
            self.metrics.write(
                {
                    "kind": "anomaly", "reason": "bad_sample",
                    "epoch": self._cur_epoch, "path": name,
                    "detail": f"{type(err).__name__}: {err}",
                }
            )
        if count > self.max_bad_samples:
            raise BadSampleLimitError(
                f"{count} undecodable samples exceed max_bad_samples="
                f"{self.max_bad_samples} (latest: {name}: {err}); see the "
                f"quarantine trail"
            ) from err

    def _decode_with_retries(self, i: int) -> np.ndarray | None:
        """``_load_one`` behind bounded-backoff retries; None = quarantined
        (the caller substitutes a good row and masks the label). Timed on
        the thread that runs it, while a tracer records (``_py_busy_ns``)."""
        if not obs_trace.current().enabled:
            return self._retrying_load(i)
        t0 = time.perf_counter_ns()
        try:
            return self._retrying_load(i)
        finally:
            busy = time.perf_counter_ns() - t0
            with self._bad_lock:
                self._py_busy_ns += busy

    def _retrying_load(self, i: int) -> np.ndarray | None:
        delay = self.decode_retry_backoff_s
        err: BaseException | None = None
        for attempt in range(self.decode_retries + 1):
            try:
                return self._load_one(i)
            except BadSampleLimitError:
                raise
            except Exception as e:
                err = e
                if attempt < self.decode_retries and delay > 0:
                    time.sleep(delay)
                    delay *= 2
        self._quarantine(i, err)
        return None

    def _decode_counters(self) -> tuple[int, int, dict[str, int]]:
        """``(images the C decoder refused, nanoseconds inside a decode, the
        C decoder's nanoseconds by stage)`` so far: the native library's
        process-wide counters (another loader decoding at the same time shows
        in them) plus this loader's Python decodes, which have no stages (an
        empty dict where the pixels do not come from the C decoder). A
        batch's share is the difference around its ``_load_batch``."""
        refused = busy_ns = 0
        stage_ns: dict[str, int] = {}
        if self.native_decode:
            from mpi_pytorch_tpu import native

            refused, busy_ns, stage_ns = native.counters()
            if self._pack is not None:
                stage_ns = {}  # a pack's rows are read, not decoded
        with self._bad_lock:
            return refused, busy_ns + self._py_busy_ns, stage_ns

    @property
    def decode_source(self) -> str:
        """Where ``_load_batch`` gets its pixels: ``pack`` | ``native`` |
        ``synthetic`` | ``pil`` (its order of preference)."""
        if self._pack is not None:
            return "pack"
        if self.native_decode:
            return "native"
        return "synthetic" if self.synthetic else "pil"

    def _masked_labels(self, idx: np.ndarray) -> np.ndarray:
        """Batch labels with quarantined rows masked to -1 (the padding
        label the loss already ignores) — THE label source of every batch
        path, so a row quarantined in epoch 0 stays masked when later
        epochs serve it from the host cache."""
        labels = np.asarray(self.manifest.labels[idx])
        if self._quarantined:
            bad = np.fromiter(
                (int(j) in self._quarantined for j in idx), bool, len(idx)
            )
            if bad.any():
                labels = np.where(bad, np.int32(-1), labels).astype(labels.dtype)
        return labels

    def _load_one(self, i: int) -> np.ndarray:
        # MPT_FAULT_DECODE_N poisons N DISTINCT samples permanently (one
        # countdown shot per sample on first draw, then every retry of that
        # sample fails too) — deterministic regardless of worker-thread
        # interleaving, so N=1 always quarantines exactly one sample.
        if int(i) in self._poisoned_decode:
            raise RuntimeError(
                f"injected decode failure (MPT_FAULT_DECODE_N) for "
                f"{self._sample_name(i)}"
            )
        if fault_countdown("MPT_FAULT_DECODE_N"):
            self._poisoned_decode.add(int(i))
            raise RuntimeError(
                f"injected decode failure (MPT_FAULT_DECODE_N) for "
                f"{self._sample_name(i)}"
            )
        if self.synthetic:
            # Key the pattern by label so classes are separable. The pattern
            # is a pure function of (label, size, dtype), so a bounded cache
            # removes the host-side generation bottleneck (1 CPU core feeding
            # a TPU). raw-uint8 mode caches the quantized pixels instead.
            key = (int(self.manifest.labels[i]), self.image_size, self.raw_uint8)
            img = _SYNTH_CACHE.get(key)
            if img is None:
                global _synth_cache_bytes
                if self.raw_uint8:
                    from mpi_pytorch_tpu.data.packed import _synthetic_uint8

                    img = _synthetic_uint8(key[0], self.image_size)
                else:
                    img = normalize_image(synthetic_image(key[0], self.image_size))
                with _SYNTH_CACHE_LOCK:
                    if key not in _SYNTH_CACHE and (
                        _synth_cache_bytes + img.nbytes <= _SYNTH_CACHE_BUDGET
                    ):
                        _SYNTH_CACHE[key] = img
                        _synth_cache_bytes += img.nbytes
            return img
        path = os.path.join(self.manifest.img_dir, self.manifest.filenames[i])
        if self.raw_uint8:
            # Shared with the pack writer — the single point of truth that
            # keeps pack ≡ streaming bit-identity for raw-uint8 batches.
            from mpi_pytorch_tpu.data.packed import _decode_uint8

            return _decode_uint8(path, self.image_size)
        return normalize_image(decode_image(path, self.image_size))

    def _load_batch(self, idx: np.ndarray, pool: ThreadPoolExecutor) -> np.ndarray:
        """Load a batch of images [B,H,W,3]: normalized f32 (the native
        source: normalized ``image_dtype``, stored by its workers), or RAW
        uint8 pixels in ``raw_uint8`` mode (normalization then happens on
        device, train/step.py ``ingest_images``). Sources in order: packed
        mmap rows when a pack is resolved, else one GIL-released native call
        when available, else the PIL thread pool."""
        if self._pack is not None:
            if self.raw_uint8:
                # The whole host pipeline collapses to an mmap row gather;
                # normalize happens on device (step.ingest_images).
                return self._pack.images[self._pack.rows[idx]]
            # uint8 rows / 255 reproduce decode_image's floats bit-for-bit
            # (the pack stores PIL's resize output pre-float-conversion), and
            # the in-place chain keeps the exact op order of normalize_image
            # (same bits) with one allocation instead of four — this IS the
            # packed path's hot loop, there's no decode to hide behind.
            out = self._pack.images[self._pack.rows[idx]].astype(np.float32)
            out /= 255.0
            out -= _MEAN
            out /= _STD
            return out
        if self.native_decode:
            from mpi_pytorch_tpu import native

            paths = [
                os.path.join(self.manifest.img_dir, self.manifest.filenames[i]) for i in idx
            ]
            # Items the C decoder refuses fall back per path; the fallback
            # rides the same retry/quarantine discipline as the PIL pool
            # (a quarantined item returns a zero image — its label is
            # masked by _masked_labels, so the content never trains).
            row_of = {}
            for k, p in enumerate(paths):
                row_of.setdefault(p, int(idx[k]))

            def robust_fallback(p):
                img = self._decode_with_retries(row_of[p])
                if img is None:
                    return np.zeros((*self.image_size, 3), np.float32)
                return img

            return native.decode_batch(
                paths,
                self.image_size,
                _MEAN,
                _STD,
                threads=self.num_workers,
                prescale_margin=self.decode_prescale,
                fallback=robust_fallback,
                dtype=self.image_dtype,
            )
        rows = list(pool.map(self._decode_with_retries, idx))
        bad = [k for k, r in enumerate(rows) if r is None]
        if bad:
            # Substitute quarantined rows with real decoded content (the
            # _cyclic_fill rationale: BN statistics span the whole batch,
            # so substitutes should be real pixels, not zeros) — zeros only
            # when the entire batch failed. Labels mask either way.
            good = [k for k, r in enumerate(rows) if r is not None]
            fill_dtype = np.uint8 if self.raw_uint8 else np.float32
            for n, k in enumerate(bad):
                rows[k] = (
                    rows[good[n % len(good)]]
                    if good
                    else np.zeros((*self.image_size, 3), fill_dtype)
                )
        return np.stack(rows)

    def wait_cache_complete(self) -> bool:
        """Join any in-flight cache-filling thread (the backfill keeps
        running after an early consumer close), then surface a decode error
        the closed consumer never saw. True when the cache is complete."""
        t = self._fill_thread
        if t is not None and t.is_alive():
            t.join()
        if self._cache_fill_error is not None:
            err, self._cache_fill_error = self._cache_fill_error, None
            raise err
        return self._cache_complete

    def adopt_cache(self, other: "DataLoader") -> bool:
        """Share ``other``'s completed host cache (by reference) when the two
        loaders walk the same data the same way — e.g. the validation loader
        adopting the train loader's cache under ``val_on_train`` semantics,
        instead of decoding a second full copy of the identical shard."""
        if (
            other._cache_images is not None
            and other._cache_complete
            and len(other.manifest) == len(self.manifest)
            and other.manifest.filenames == self.manifest.filenames
            and other.manifest.img_dir == self.manifest.img_dir
            and other.image_size == self.image_size
            and other.image_dtype == self.image_dtype
            and other.synthetic == self.synthetic
            and other.native_decode == self.native_decode
            and other.decode_prescale == self.decode_prescale
            and (other._pack.stem if other._pack else None)
            == (self._pack.stem if self._pack else None)
        ):
            self._cache_images = other._cache_images
            self._cache_complete = True
            # Rows the source loader quarantined while filling stay masked
            # here too — the cache holds their substitute pixels.
            self._quarantined |= other._quarantined
            return True
        return False

    def epoch(
        self, epoch: int = 0, start_batch: int = 0
    ) -> Iterator[tuple[np.ndarray, np.ndarray]]:
        """Iterate one epoch of batches, prefetched in the background.

        ``start_batch`` fast-forwards past the first k batches WITHOUT
        decoding them: the ``(seed, epoch)`` visit order is deterministic,
        so the consumed prefix is just an offset into ``epoch_order`` — the
        exact-step mid-epoch resume dataflow (train/trainer.py). Applies
        identically to the streaming, RAM-cache, and packed-mmap paths
        (all three walk the same order)."""
        n = len(self.manifest)
        order = epoch_order(self.seed, epoch, n, self.shuffle)
        nb = len(self)
        self._cur_epoch = epoch
        start_batch = max(0, min(start_batch, nb))
        if nb - start_batch == 0:
            return iter(())

        if self.host_cache:
            # Serialize with an in-flight filling epoch: two producers over
            # the same cache arrays would double-decode the shard (and the
            # join is exactly the remaining decode work either way).
            self.wait_cache_complete()

        if self.host_cache and self._cache_complete:
            # Slicing RAM is not worth a producer thread; the (seed, epoch)
            # order is identical to the streaming walk, so trajectories match.
            cache = self._cache_images

            def cached_gen() -> Iterator[tuple[np.ndarray, np.ndarray]]:
                for b in range(start_batch, nb):
                    idx = order[b * self.batch_size : (b + 1) * self.batch_size]
                    yield cache[idx], self._masked_labels(idx)

            return cached_gen()

        # Cache-as-you-stream: the filling epoch IS a normal streaming epoch
        # (decode overlapped with the consumer via the producer thread), with
        # each decoded batch additionally scattered into the cache array and
        # marked in a filled mask. Whatever the epoch never visits — tail
        # rows under drop_remainder, whole batches when the consumer stops
        # early (multi-host globally-truncated step counts close the iterator
        # after n_steps) — is backfilled at the end, in the background if the
        # consumer is already gone, so the cache ALWAYS completes.
        fill_cache = self.host_cache
        if fill_cache and self._cache_images is None:
            self._cache_images = np.empty(
                (n, *self.image_size, 3), self.image_dtype
            )
            self._cache_filled = np.zeros(n, bool)

        q: queue.Queue = queue.Queue(maxsize=self.prefetch)
        stop = threading.Event()

        # The run's tracer, read on the consumer's thread (the driver's) and
        # used by both threads; inert outside a traced run.
        tracer = obs_trace.current()

        def put_or_abandon(item) -> bool:
            # Bounded put that gives up once the consumer is gone — never
            # blocks forever on a full queue. Returns whether it enqueued.
            with tracer.span("loader/put"):
                while not stop.is_set():
                    try:
                        q.put(item, timeout=0.5)
                        return True
                    except queue.Full:
                        continue
                return False

        def load_batch(idx, pool):
            # ``_load_batch`` under a ``loader/decode`` span that says what
            # the decode workers did; nothing is counted outside a traced run.
            if not tracer.enabled:
                return self._load_batch(idx, pool)
            args = {
                "images": len(idx), "source": self.decode_source,
                "threads": self.num_workers,
            }
            with tracer.span("loader/decode", args=args):
                refused, busy_ns, stage_ns = self._decode_counters()
                quarantined = self.bad_samples
                t0 = time.perf_counter()
                stacked = self._load_batch(idx, pool)
                call_s = time.perf_counter() - t0
                args["wrote"] = str(stacked.dtype)
                refused_now, busy_ns_now, stage_ns_now = self._decode_counters()
                args["fallbacks"] = refused_now - refused
                args["quarantined"] = self.bad_samples - quarantined
                # A pack is read by this one thread: its busy time is the
                # call's; every other source counts where it decodes.
                args["thread_busy_s"] = (
                    call_s if self._pack is not None else (busy_ns_now - busy_ns) / 1e9
                )
                if stage_ns_now:
                    # Which stage of the C decoder the workers' seconds went
                    # to (a per-item PIL fallback is busy time outside them),
                    # and how much of ``jpeg`` was its scanline loop.
                    took = {k: (ns - stage_ns[k]) / 1e9 for k, ns in stage_ns_now.items()}
                    args["jpeg_scan_s"] = took.pop("jpeg_scan")
                    args["stage_s"] = took
            return stacked

        # ``loader/cast`` in every batch of a loader whose floats are not
        # float32, around what this thread converts itself: a float32 batch
        # of the PIL pool or a pack. The native source's workers stored
        # ``image_dtype``, and the span closes in microseconds.
        may_convert = self.image_dtype != np.float32 and not self.raw_uint8

        def decode_one_batch(idx, pool):
            stacked = load_batch(idx, pool)
            if may_convert:
                args = {"converted": 0}
                with tracer.span("loader/cast", args=args):
                    if stacked.dtype != self.image_dtype:
                        args["converted"] = len(stacked)
                        stacked = stacked.astype(self.image_dtype)
            if fill_cache:
                self._cache_images[idx] = stacked
                self._cache_filled[idx] = True
            return stacked

        def producer() -> None:
            # The producer's whole life, thread start to sentinel: the wall
            # time of a run not under a ``loader/epoch`` had no producer alive.
            # Traced, the span also says what the WHOLE process burnt over
            # that life — decode workers, the cast, the step loop and the
            # runtime's transfer threads alike — beside the cores it had: a
            # host out of cores reads cpu_s / (host_cpus x seconds) near 1.
            args = {"epoch": epoch, "batches": nb - start_batch}
            with tracer.span("loader/epoch", args=args):
                cpu0 = time.process_time() if tracer.enabled else None
                produce()
                if cpu0 is not None:
                    args["cpu_s"] = time.process_time() - cpu0
                    args["host_cpus"] = len(os.sched_getaffinity(0))

        def produce() -> None:
            error = None
            try:
                with ThreadPoolExecutor(max_workers=self.num_workers) as pool:
                    for b in range(start_batch, nb):
                        if stop.is_set():
                            break  # consumer gone; still backfill the cache below
                        idx = order[b * self.batch_size : (b + 1) * self.batch_size]
                        stacked = decode_one_batch(idx, pool)
                        # Labels AFTER decode: a row quarantined by this
                        # very batch must already be masked.
                        put_or_abandon((stacked, self._masked_labels(idx)))
                    if fill_cache and not self._cache_complete:
                        # Backfill whatever this epoch didn't decode. With a
                        # live consumer this is at most the drop_remainder
                        # tail (sub-batch, done before the sentinel); after an
                        # early close it runs in the background — the stopped
                        # consumer isn't waiting on the queue.
                        missing = np.nonzero(~self._cache_filled)[0]
                        for s in range(0, len(missing), self.batch_size):
                            decode_one_batch(missing[s : s + self.batch_size], pool)
                        self._cache_complete = True
            except BaseException as e:  # surface decode errors to the consumer
                error = e
            finally:
                # None sentinel, or the exception to re-raise. If the
                # consumer is already gone (early close), park the error for
                # wait_cache_complete() so a backfill failure is never silent.
                if not put_or_abandon(error) and error is not None:
                    self._cache_fill_error = error

        t = threading.Thread(target=producer, daemon=True)
        if fill_cache:
            self._fill_thread = t
        t.start()

        def gen() -> Iterator[tuple[np.ndarray, np.ndarray]]:
            try:
                while True:
                    with tracer.span("loader/get"):
                        item = q.get()
                    if item is None:
                        break
                    if isinstance(item, BaseException):
                        raise item
                    yield item
            finally:
                stop.set()

        return gen()
