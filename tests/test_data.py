import numpy as np
import pytest

from mpi_pytorch_tpu.config import Config
from mpi_pytorch_tpu.data import DataLoader, load_manifests, normalize_image, synthetic_image
from mpi_pytorch_tpu.data.manifest import Manifest


@pytest.fixture(scope="module")
def cfg():
    c = Config()
    c.test_csv = "/root/repo/data/test_sample.csv"
    c.train_csv = "/root/repo/data/train_sample.csv"
    c.debug = True
    return c


@pytest.fixture(scope="module")
def manifests(cfg):
    return load_manifests(cfg)


def test_debug_sampling_semantics(manifests):
    # main.py:77-79: 1000-row sample seed 0, 80/20 split
    train, test = manifests
    assert len(train) == 800
    assert len(test) == 200


def test_sharding_matches_array_split(manifests):
    train, _ = manifests
    shards = [train.shard(3, i) for i in range(3)]
    sizes = [len(s) for s in shards]
    expected = [len(a) for a in np.array_split(np.arange(len(train)), 3)]
    assert sizes == expected
    # shards partition the manifest without overlap
    all_files = [f for s in shards for f in s.filenames]
    assert all_files == list(train.filenames)


def test_labels_fit_head(manifests):
    train, test = manifests
    assert train.labels.max() < 64500  # utils.py:39 head size
    assert train.labels.min() >= 0


def test_normalize_matches_torch_semantics():
    # transforms.Normalize((0.485,...),(0.229,...)) — main.py:65
    img = np.full((4, 4, 3), 0.5, dtype=np.float32)
    out = normalize_image(img)
    expected = (0.5 - np.array([0.485, 0.456, 0.406])) / np.array([0.229, 0.224, 0.225])
    np.testing.assert_allclose(out[0, 0], expected, rtol=1e-5)


def test_synthetic_deterministic():
    a = synthetic_image(7, (16, 16))
    b = synthetic_image(7, (16, 16))
    c = synthetic_image(8, (16, 16))
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)
    assert a.shape == (16, 16, 3)
    assert a.min() >= 0.0 and a.max() <= 1.0


def _tiny_manifest(n=20, classes=4):
    labels = np.arange(n, dtype=np.int32) % classes
    return Manifest(
        filenames=tuple(f"img_{i}.jpg" for i in range(n)),
        labels=labels,
        category_ids=labels.astype(np.int64),
        img_dir="unused",
    )


def test_loader_shapes_and_determinism():
    m = _tiny_manifest()
    dl = DataLoader(m, batch_size=8, image_size=(32, 32), synthetic=True, seed=3)
    batches = list(dl.epoch(0))
    assert len(batches) == 2  # drop_remainder: 20 // 8
    imgs, labels = batches[0]
    assert imgs.shape == (8, 32, 32, 3) and imgs.dtype == np.float32
    assert labels.shape == (8,) and labels.dtype == np.int32
    # same (seed, epoch) → same order; different epoch → different order
    again = list(dl.epoch(0))
    np.testing.assert_array_equal(batches[0][1], again[0][1])
    other = list(dl.epoch(1))
    assert not all(np.array_equal(b[1], o[1]) for b, o in zip(batches, other))


def test_loader_no_drop_remainder():
    m = _tiny_manifest(n=10)
    dl = DataLoader(m, batch_size=8, image_size=(8, 8), synthetic=True, drop_remainder=False,
                    shuffle=False)
    batches = list(dl.epoch(0))
    assert [b[0].shape[0] for b in batches] == [8, 2]


def test_create_dataset_metadata_join(tmp_path):
    """read→join→sample→split→write parity with reference create_dataset.py."""
    import json

    from mpi_pytorch_tpu.data.create_dataset import read_metadata, sample_and_split, write_split

    meta = {
        "images": [
            {"id": i, "file_name": f"f{i}.jpg", "height": 100, "width": 80, "license": 1}
            for i in range(50)
        ],
        "annotations": [
            {"image_id": i, "category_id": i % 7, "id": 1000 + i} for i in range(50)
        ],
    }
    mpath = tmp_path / "metadata.json"
    mpath.write_text(json.dumps(meta))

    df = read_metadata(str(mpath))
    assert len(df) == 50
    assert set(["file_name", "category_id"]).issubset(df.columns)

    train_df, test_df = sample_and_split(df, 40, seed=0)
    assert len(train_df) == 32 and len(test_df) == 8  # 80/20 of 40

    train_csv, test_csv = write_split(train_df, test_df, str(tmp_path / "out"), copy_images=False)
    import pandas as pd

    assert len(pd.read_csv(train_csv)) == 32
    # deterministic: seed 0 resample gives the same rows
    t2, _ = sample_and_split(df, 40, seed=0)
    assert list(t2["file_name"]) == list(train_df["file_name"])


@pytest.mark.slow
def test_synthetic_jpeg_dataset_trains_via_decode_path(tmp_path):
    """--synthetic generates real JPEGs; training with synthetic_data=False
    exercises the actual PIL decode→resize→normalize path end to end."""
    from mpi_pytorch_tpu.data.create_dataset import main as create_main
    from mpi_pytorch_tpu.train.trainer import train

    out = str(tmp_path / "data")
    create_main(["--synthetic", "96", "--num-classes", "8", "--image-size", "48",
                 "--out", out])

    cfg = Config()
    cfg.debug = True
    cfg.debug_sample_size = 64
    cfg.train_csv = f"{out}/train_sample.csv"
    cfg.test_csv = f"{out}/test_sample.csv"
    cfg.train_img_dir = f"{out}/img/train"
    cfg.test_img_dir = f"{out}/img/test"
    cfg.synthetic_data = False  # decode the JPEGs for real
    cfg.num_classes = 8
    cfg.batch_size = 16
    cfg.width = cfg.height = 32
    cfg.num_epochs = 1
    cfg.compute_dtype = "float32"
    cfg.validate = False
    cfg.checkpoint_dir = str(tmp_path / "ckpt")
    cfg.log_file = str(tmp_path / "training.log")
    cfg.loader_workers = 2
    cfg.log_every_steps = 0
    cfg.validate_config()

    summary = train(cfg)
    assert summary.epochs_run == 1
    assert np.isfinite(summary.final_loss)


def test_loader_bfloat16_batches():
    import ml_dtypes

    m = _tiny_manifest(n=16)
    dl = DataLoader(m, batch_size=8, image_size=(16, 16), synthetic=True,
                    shuffle=False, image_dtype="bfloat16")
    imgs, labels = next(iter(dl.epoch(0)))
    assert imgs.dtype == np.dtype(ml_dtypes.bfloat16)
    assert labels.dtype == np.int32
    # values match the float32 pipeline to bf16 precision
    dl32 = DataLoader(m, batch_size=8, image_size=(16, 16), synthetic=True, shuffle=False)
    imgs32, _ = next(iter(dl32.epoch(0)))
    np.testing.assert_allclose(imgs.astype(np.float32), imgs32, atol=0.02, rtol=0.02)


def test_host_cache_adoption():
    """adopt_cache shares a completed cache by reference only when the two
    loaders walk identical data; mismatches refuse."""
    from mpi_pytorch_tpu.data.manifest import Manifest
    from mpi_pytorch_tpu.data.pipeline import DataLoader

    m = Manifest(
        filenames=tuple(f"f{i}" for i in range(8)),
        labels=np.arange(8, dtype=np.int32),
        category_ids=np.arange(8),
        img_dir="unused",
    )
    a = DataLoader(m, batch_size=4, image_size=(16, 16), shuffle=False,
                   synthetic=True, host_cache=True)
    for _ in a.epoch(0):
        pass
    assert a._cache_complete

    b = DataLoader(m, batch_size=4, image_size=(16, 16), shuffle=False,
                   synthetic=True, host_cache=True)
    assert b.adopt_cache(a)
    assert b._cache_images is a._cache_images

    c = DataLoader(m, batch_size=4, image_size=(8, 8), shuffle=False,
                   synthetic=True, host_cache=True)
    assert not c.adopt_cache(a)  # different image size: refuse


def test_host_cache_completes_after_early_close():
    """The multi-host globally-truncated step count closes the epoch iterator
    before the loader is exhausted; the cache must still complete (in the
    background) so 'decode once' holds on the default drop_remainder path."""
    import time

    from mpi_pytorch_tpu.data.manifest import Manifest
    from mpi_pytorch_tpu.data.pipeline import DataLoader

    m = Manifest(
        filenames=tuple(f"f{i}" for i in range(10)),
        labels=np.arange(10, dtype=np.int32),
        category_ids=np.arange(10),
        img_dir="unused",
    )
    dl = DataLoader(m, batch_size=4, image_size=(16, 16), shuffle=False,
                    drop_remainder=True, synthetic=True, host_cache=True)
    it = dl.epoch(0)
    next(it)       # consume ONE of the two full batches
    it.close()     # early close, as synchronized_batches does after n_steps
    deadline = time.monotonic() + 30
    while not dl._cache_complete and time.monotonic() < deadline:
        time.sleep(0.02)
    assert dl._cache_complete
    assert dl._cache_filled.all()
    # next epoch serves from the cache (fast slice path)
    batches = list(dl.epoch(1))
    assert len(batches) == 2


def test_host_cache_backfill_error_surfaces(tmp_path):
    """A decode failure in the post-close backfill must not be silent: a
    failure past the quarantine budget surfaces through
    wait_cache_complete (within-budget failures quarantine instead —
    tests/test_selfheal.py)."""
    from mpi_pytorch_tpu.data.manifest import Manifest
    from mpi_pytorch_tpu.data.pipeline import BadSampleLimitError, DataLoader

    img_dir = tmp_path / "img"
    img_dir.mkdir()
    from PIL import Image

    names = []
    for i in range(10):
        name = f"f{i}.jpg"
        if i < 8:  # the last two (the drop_remainder tail) stay missing
            Image.new("RGB", (32, 32)).save(img_dir / name)
        names.append(name)
    m = Manifest(
        filenames=tuple(names), labels=np.arange(10, dtype=np.int32),
        category_ids=np.arange(10), img_dir=str(img_dir),
    )
    dl = DataLoader(m, batch_size=4, image_size=(16, 16), shuffle=False,
                    drop_remainder=True, synthetic=False, host_cache=True,
                    max_bad_samples=1, decode_retries=0)
    it = dl.epoch(0)
    next(it)
    next(it)  # both full batches decode fine (files 0-7)
    it.close()  # backfill of the missing tail files now fails in background
    with pytest.raises(BadSampleLimitError):
        dl.wait_cache_complete()
    assert not dl._cache_complete


def test_host_cache_next_epoch_waits_for_backfill():
    """epoch(N+1) must not race the still-running backfill of epoch N: it
    joins the filler and then serves from the completed cache."""
    from mpi_pytorch_tpu.data.manifest import Manifest
    from mpi_pytorch_tpu.data.pipeline import DataLoader

    m = Manifest(
        filenames=tuple(f"f{i}" for i in range(10)),
        labels=np.arange(10, dtype=np.int32),
        category_ids=np.arange(10),
        img_dir="unused",
    )
    dl = DataLoader(m, batch_size=4, image_size=(16, 16), shuffle=False,
                    drop_remainder=True, synthetic=True, host_cache=True)
    it = dl.epoch(0)
    next(it)
    it.close()  # backfill continues in the background
    batches = list(dl.epoch(1))  # joins the filler, then slices the cache
    assert dl._cache_complete
    assert dl._fill_thread is None or not dl._fill_thread.is_alive()
    assert len(batches) == 2


def _jpeg_dataset(tmp_path, n=96, classes=8, size=48):
    """Synthetic-JPEG dataset on disk + its (train, test) manifests."""
    from mpi_pytorch_tpu.data.create_dataset import main as create_main

    out = str(tmp_path / "data")
    create_main(["--synthetic", str(n), "--num-classes", str(classes),
                 "--image-size", str(size), "--out", out])
    c = Config()
    c.debug = False
    c.train_csv = f"{out}/train_sample.csv"
    c.test_csv = f"{out}/test_sample.csv"
    c.train_img_dir = f"{out}/img/train"
    c.test_img_dir = f"{out}/img/test"
    c.synthetic_data = False
    c.num_classes = classes
    return c, load_manifests(c)


def test_packed_dataset_matches_streaming_exactly(tmp_path):
    """Packed batches must be BIT-identical to the streaming PIL decode path
    (the pack stores PIL's resize output pre-float), including when a shard
    resolves against the full-split pack by filename."""
    from mpi_pytorch_tpu.data.packed import write_pack

    _, (train_m, _) = _jpeg_dataset(tmp_path)
    packed_dir = str(tmp_path / "packed")
    write_pack(train_m, (32, 32), f"{packed_dir}/train_32x32", num_workers=2)

    kw = dict(batch_size=8, image_size=(32, 32), shuffle=True, seed=7,
              native_decode=False, num_workers=2)
    streamed = list(DataLoader(train_m, **kw).epoch(0))
    packed = list(DataLoader(train_m, packed_dir=packed_dir, **kw).epoch(0))
    assert len(streamed) == len(packed) > 0
    for (si, sl), (pi, pl) in zip(streamed, packed):
        np.testing.assert_array_equal(sl, pl)
        np.testing.assert_array_equal(si, pi)  # bit-for-bit, not allclose

    shard = train_m.shard(2, 1)
    s_shard = list(DataLoader(shard, **kw).epoch(0))
    p_shard = list(DataLoader(shard, packed_dir=packed_dir, **kw).epoch(0))
    for (si, _), (pi, _) in zip(s_shard, p_shard):
        np.testing.assert_array_equal(si, pi)


def test_packed_resolution_is_strict(tmp_path):
    """A configured packed_dir with no covering pack must raise (silent
    fallback to per-epoch decode would hide the cost the format removes)."""
    from mpi_pytorch_tpu.data.packed import write_pack

    _, (train_m, _) = _jpeg_dataset(tmp_path, n=48)
    packed_dir = str(tmp_path / "packed")
    write_pack(train_m, (32, 32), f"{packed_dir}/train_32x32", num_workers=2)
    with pytest.raises(FileNotFoundError, match="image_size"):
        DataLoader(train_m, batch_size=8, image_size=(16, 16),
                   packed_dir=packed_dir)


def test_packed_accepts_relative_img_dir_spelling(tmp_path, monkeypatch):
    """A pack recorded with a relative spelling of the manifest's img_dir is
    the SAME pack: find_pack compares realpaths, so the strict no-fallback
    policy doesn't turn a path-spelling difference into a hard error."""
    import json
    import os

    from mpi_pytorch_tpu.data.packed import find_pack, write_pack

    _, (train_m, _) = _jpeg_dataset(tmp_path, n=48)
    packed_dir = str(tmp_path / "packed")
    write_pack(train_m, (32, 32), f"{packed_dir}/train_32x32", num_workers=2)

    meta_path = f"{packed_dir}/train_32x32.meta.json"
    with open(meta_path) as f:
        meta = json.load(f)
    monkeypatch.chdir(tmp_path)
    meta["img_dir"] = os.path.relpath(meta["img_dir"], str(tmp_path))
    assert meta["img_dir"] != train_m.img_dir  # genuinely different spellings
    with open(meta_path, "w") as f:
        json.dump(meta, f)

    handle = find_pack(packed_dir, train_m, (32, 32), synthetic=False)
    assert handle.rows.shape[0] == len(train_m.filenames)


@pytest.mark.slow
def test_packed_cli_then_train(tmp_path):
    """The pack CLI writes both splits; the trainer consumes them through
    --packed-dir end to end."""
    import os

    from mpi_pytorch_tpu.data.packed import main as pack_main
    from mpi_pytorch_tpu.train.trainer import train

    c, _ = _jpeg_dataset(tmp_path, n=64, classes=4)
    packed_dir = str(tmp_path / "packed")
    pack_main([
        "--packed-dir", packed_dir, "--debug", "false",
        "--train-csv", c.train_csv, "--test-csv", c.test_csv,
        "--train-img-dir", c.train_img_dir, "--test-img-dir", c.test_img_dir,
        "--synthetic-data", "false", "--num-classes", "4",
        "--image-size", "32", "--loader-workers", "2",
    ])
    assert sorted(n for n in os.listdir(packed_dir) if n.endswith(".meta.json")) == [
        "test_32x32.meta.json", "train_32x32.meta.json"
    ]

    c.packed_dir = packed_dir
    c.batch_size = 16
    c.width = c.height = 32
    c.num_epochs = 1
    c.compute_dtype = "float32"
    c.validate = True
    c.val_on_train = False  # resolves the test-split pack for validation
    c.checkpoint_dir = str(tmp_path / "ckpt")
    c.log_file = str(tmp_path / "training.log")
    c.loader_workers = 2
    c.log_every_steps = 0
    c.validate_config()
    summary = train(c)
    assert summary.epochs_run == 1 and np.isfinite(summary.final_loss)
    assert summary.val_accuracy is not None


def test_packed_synthetic_label_mismatch_rejected(tmp_path):
    """Synthetic images are functions of their labels, so a synthetic pack
    whose stored labels disagree with the manifest must be rejected — it
    would silently serve images of the wrong classes."""
    from mpi_pytorch_tpu.data.packed import write_pack

    m = _tiny_manifest(n=12, classes=3)
    packed_dir = str(tmp_path / "packed")
    write_pack(m, (16, 16), f"{packed_dir}/train_16x16", synthetic=True,
               num_workers=2)
    # Same filenames, shifted labels ≙ a regenerated dataset.
    shifted = Manifest(
        filenames=m.filenames,
        labels=(m.labels + 1) % 3,
        category_ids=m.category_ids,
        img_dir=m.img_dir,
    )
    with pytest.raises(FileNotFoundError, match="labels disagree"):
        DataLoader(shifted, batch_size=4, image_size=(16, 16), synthetic=True,
                   packed_dir=packed_dir)
    # The matching manifest still resolves.
    dl = DataLoader(m, batch_size=4, image_size=(16, 16), synthetic=True,
                    packed_dir=packed_dir)
    assert dl._pack is not None


def test_uint8_ingest_matches_host_normalize(tmp_path):
    """input_dtype='uint8' batches + on-device normalize (step.ingest_images)
    must equal the host-normalized float path exactly: same uint8 source,
    same op order, f32 both ways."""
    import jax.numpy as jnp

    from mpi_pytorch_tpu.train.step import ingest_images

    _, (train_m, _) = _jpeg_dataset(tmp_path, n=48)
    kw = dict(batch_size=8, image_size=(32, 32), shuffle=False,
              native_decode=False, num_workers=2)
    f32_batches = list(DataLoader(train_m, **kw).epoch(0))
    u8_batches = list(DataLoader(train_m, image_dtype="uint8", **kw).epoch(0))
    assert u8_batches[0][0].dtype == np.uint8
    for (fi, fl), (ui, ul) in zip(f32_batches, u8_batches):
        np.testing.assert_array_equal(fl, ul)
        on_device = np.asarray(ingest_images(jnp.asarray(ui), jnp.float32))
        np.testing.assert_allclose(on_device, fi, rtol=0, atol=1e-6)


# ---------------------------------------------------------------------------
# where a streaming epoch's time goes: the loader's spans (ISSUE 24)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("source", ["synthetic", "pack", "pil", "native"])
def test_loader_decode_span_names_its_source(tmp_path, spans_of, source):
    if source == "synthetic":
        loader = DataLoader(
            _tiny_manifest(), batch_size=8, image_size=(16, 16), synthetic=True, num_workers=2
        )
    else:
        _, (manifest, _) = _jpeg_dataset(tmp_path, n=40)
        kw = {}
        if source == "pack":
            from mpi_pytorch_tpu.data.packed import write_pack

            kw["packed_dir"] = str(tmp_path / "packed")
            write_pack(manifest, (16, 16), f"{kw['packed_dir']}/train_16x16", num_workers=2)
        elif source == "native":
            from mpi_pytorch_tpu import native

            if not native.available():
                pytest.skip(f"native decode unavailable: {native.build_error()}")
        loader = DataLoader(
            manifest, batch_size=8, image_size=(16, 16), num_workers=2,
            native_decode=source == "native", **kw
        )

    def run():
        assert len(list(loader.epoch(0))) == len(loader)

    spans = spans_of(run, epochs=1)
    assert len(spans["loader/decode"]) == len(loader)
    for e in spans["loader/decode"]:
        args = e["args"]
        assert args["source"] == source and args["images"] == 8
        assert 0 < args["thread_busy_s"] <= 2 * e["dur"] / 1e6 * 1.05
        # Only the C decoder has stages to name (ISSUE 35); they are its busy
        # time, to the loop's own bookkeeping.
        if source == "native":
            assert set(args["stage_s"]) == {"file", "jpeg", "resize", "normalize"}
            assert sum(args["stage_s"].values()) == pytest.approx(
                args["thread_busy_s"], rel=0.01, abs=5e-6 * args["images"]
            )
            assert 0 < args["jpeg_scan_s"] <= args["stage_s"]["jpeg"]
        else:
            assert "stage_s" not in args and "jpeg_scan_s" not in args
    assert "loader/cast" not in spans  # float32 batches: nothing to convert
    (life,) = spans["loader/epoch"]
    assert life["args"]["batches"] == len(loader)
    # The process's CPU over the producer's life, and the cores it had.
    assert life["args"]["cpu_s"] > 0 and life["args"]["host_cpus"] >= 1


def test_untraced_loader_opens_no_span(tmp_path, monkeypatch):
    """Outside a traced run the current tracer is the inert one: the loader
    records nothing and counts nothing (no clock read or lock per image, no
    counter read per batch, no process clock or affinity call per epoch)."""
    from mpi_pytorch_tpu.data import pipeline
    from mpi_pytorch_tpu.obs import trace as obs_trace

    dl = DataLoader(_tiny_manifest(), batch_size=8, image_size=(8, 8), synthetic=True)
    dl._decode_counters = None  # a call would raise

    def forbidden(*_a, **_k):
        raise AssertionError("read outside a traced run")

    monkeypatch.setattr(pipeline.time, "process_time", forbidden)
    monkeypatch.setattr(pipeline.os, "sched_getaffinity", forbidden)
    assert len(list(dl.epoch(0))) == 2
    assert not obs_trace.current().enabled and obs_trace.current()._events == []
    assert dl._py_busy_ns == 0


@pytest.mark.parametrize("depth", [1, 2])
def test_device_prefetch_h2d_span_per_batch(spans_of, depth):
    """``h2d`` around pad + shard_batch of every batch, named by the epoch
    and batch it carries (what joins a device trace to it), with the padded
    batch's bytes."""
    from mpi_pytorch_tpu.config import MeshConfig
    from mpi_pytorch_tpu.parallel.mesh import create_mesh
    from mpi_pytorch_tpu.train.trainer import device_prefetch

    mesh = create_mesh(MeshConfig())
    images = np.zeros((6, 8, 8, 3), np.float32)  # padded to the host batch of 8
    labels = np.zeros((6,), np.int32)

    def run():
        out = list(
            device_prefetch(
                iter([(images, labels)] * 3), mesh, 8, depth, epoch=5, start_step=1
            )
        )
        assert len(out) == 3 and out[0][0].shape == (8, 8, 8, 3)

    spans = spans_of(run)["h2d"]
    assert [e["args"] for e in spans] == [
        {"epoch": 5, "batch": b, "bytes": 8 * 8 * 8 * 3 * 4 + 8 * 4} for b in (1, 2, 3)
    ]


def _slow_batches(n, gap_s):
    """``n`` host batches, each ``gap_s`` in the making (a decode)."""
    import time

    for _ in range(n):
        time.sleep(gap_s)
        yield np.zeros((8, 4, 4, 3), np.float32), np.zeros((8,), np.int32)


@pytest.mark.parametrize("depth,behind", [(1, [1, 1, 0]), (2, [2, 1, 0])])
def test_device_prefetch_marks_each_hand_over(spans_of, depth, behind):
    """One ``prefetch/yield`` instant a batch, in order, at the moment the
    batch goes to the step loop: named as its ``h2d`` span, ``held_ms`` since
    that span closed and ``behind`` as ``buf`` was after the pop. The first
    batch waits for ``depth`` more to be made; the last waits for nothing."""
    from mpi_pytorch_tpu.config import MeshConfig
    from mpi_pytorch_tpu.parallel.mesh import create_mesh
    from mpi_pytorch_tpu.train.trainer import device_prefetch

    mesh = create_mesh(MeshConfig())
    gap_s = 0.05

    def run():
        assert len(list(device_prefetch(_slow_batches(3, gap_s), mesh, 8, depth, epoch=2))) == 3

    events = spans_of(run)
    marks = events["prefetch/yield"]
    assert [e["ph"] for e in marks] == ["i"] * 3
    assert [(e["args"]["epoch"], e["args"]["batch"]) for e in marks] == [(2, 0), (2, 1), (2, 2)]
    assert [e["args"]["behind"] for e in marks] == behind
    held = [e["args"]["held_ms"] for e in marks]
    assert all(ms >= 0 for ms in held)
    # Batch 0 sat through the making of ``depth`` more batches.
    assert held[0] >= depth * gap_s * 1e3 * 0.9 and held[0] >= held[-1]
    # ``held_ms`` counts from the close of the batch's own ``h2d`` span.
    for mark, h2d in zip(marks, events["h2d"]):
        assert mark["ts"] - (h2d["ts"] + h2d["dur"]) == pytest.approx(
            mark["args"]["held_ms"] * 1e3, abs=10_000
        )


def test_device_prefetch_untraced_writes_nothing():
    """Under the inert tracer the hand-over costs one ``enabled`` test: no
    instant is built and no clock is read for it."""
    from mpi_pytorch_tpu.config import MeshConfig
    from mpi_pytorch_tpu.obs import trace as obs_trace
    from mpi_pytorch_tpu.parallel.mesh import create_mesh
    from mpi_pytorch_tpu.train.trainer import device_prefetch

    tracer = obs_trace.current()
    assert not tracer.enabled
    tracer.ms_since = None  # a call would raise
    try:
        out = list(device_prefetch(_slow_batches(3, 0.0), create_mesh(MeshConfig()), 8, 2))
    finally:
        del tracer.ms_since
    assert len(out) == 3 and out[0][0].shape == (8, 4, 4, 3)
    assert tracer._events == []
