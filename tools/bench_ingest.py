"""Cold-start ingest at reference scale — the capacity-planning row.

The reference's ingest story starts from a directory of JPEGs
(``create_dataset.py`` + the scatter/feeding problem, ``main.py:84-91``);
this framework's answers are the streaming C++/PIL decode pipeline, the
host cache, and the offline pack (``data/packed.py``). What was never
measured (VERDICT r4 item 7) is the COLD-START cost at the reference's
scale: 40 000 on-disk images, empty OS page cache.

This tool generates the 40 000-image synthetic JPEG dataset once
(``data/create_dataset.py --synthetic``), then measures:

- ``pack_build_s``  — offline pack wall time (decode+resize every image
  into the mmap-able uint8 tensor file), i.e. how long before the
  ``--packed-dir`` fast path exists at all;
- ``cold_stream``   — first-epoch streaming-decode throughput with a
  dropped page cache (`/proc/sys/vm/drop_caches`), the true first-epoch
  experience of a fresh host;
- ``warm_stream``   — the same epoch with the files page-cached;
- ``cold_packed``   — packed-loader first epoch, page cache dropped
  (mmap faults stream the tensor file back from disk);
- ``warm_packed``   — packed steady state.

One JSON line per row. Run (≈5–10 min on this 1-core host):

    python tools/bench_ingest.py [--n 40000] [--workdir /tmp/mpt_ingest]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _drop_page_cache() -> bool:
    try:
        subprocess.run(["sync"], check=True, timeout=120)
        with open("/proc/sys/vm/drop_caches", "w") as f:
            f.write("3\n")
        return True
    except (OSError, subprocess.SubprocessError):
        return False  # not privileged: rows are then warm-ish, say so


def _epoch_throughput(loader, epoch: int) -> tuple[float, int]:
    n = 0
    t0 = time.perf_counter()
    for images, _labels in loader.epoch(epoch):
        n += images.shape[0]
    return time.perf_counter() - t0, n


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=40000)
    ap.add_argument("--workdir", default="/tmp/mpt_ingest")
    ap.add_argument("--batch-size", type=int, default=512)
    ap.add_argument("--image-size", type=int, default=128)
    ap.add_argument("--num-classes", type=int, default=100)
    args = ap.parse_args()

    from mpi_pytorch_tpu.config import Config
    from mpi_pytorch_tpu.data.manifest import load_manifests
    from mpi_pytorch_tpu.data.pipeline import DataLoader

    os.makedirs(args.workdir, exist_ok=True)
    train_csv = os.path.join(args.workdir, "train_sample.csv")

    # --- one-time dataset generation (not the measured quantity) ---------
    if not os.path.exists(train_csv):
        from mpi_pytorch_tpu.data import create_dataset

        t0 = time.perf_counter()
        create_dataset.main([
            "--synthetic", str(args.n), "--out", args.workdir,
            "--num-classes", str(args.num_classes),
            "--image-size", str(args.image_size),
        ])
        print(json.dumps({
            "row": "generate_jpegs", "images": args.n,
            "wall_s": round(time.perf_counter() - t0, 1),
        }), flush=True)

    cfg = Config(
        debug=False, synthetic_data=False, num_classes=args.num_classes,
        train_csv=train_csv,
        test_csv=os.path.join(args.workdir, "test_sample.csv"),
        train_img_dir=os.path.join(args.workdir, "img", "train"),
        test_img_dir=os.path.join(args.workdir, "img", "test"),
        width=args.image_size, height=args.image_size,
    )
    train_manifest, _ = load_manifests(cfg)

    def make_loader(**kw):
        return DataLoader(
            train_manifest, args.batch_size, (args.image_size, args.image_size),
            shuffle=False, drop_remainder=False, synthetic=False,
            num_workers=8, **kw,
        )

    # --- pack build ------------------------------------------------------
    packed_dir = os.path.join(args.workdir, "packed")
    pack_build_s = None
    pack_ok = True
    if not os.path.isdir(packed_dir) or not os.listdir(packed_dir):
        import shutil

        t0 = time.perf_counter()
        err = ""
        try:
            proc = subprocess.run(
                [sys.executable, "-m", "mpi_pytorch_tpu.data.packed",
                 "--packed-dir", packed_dir,
                 "--debug", "false", "--synthetic-data", "false",
                 "--num-classes", str(args.num_classes),
                 "--train-csv", cfg.train_csv, "--test-csv", cfg.test_csv,
                 "--train-img-dir", cfg.train_img_dir,
                 "--test-img-dir", cfg.test_img_dir,
                 "--width", str(args.image_size), "--height", str(args.image_size)],
                cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                capture_output=True, text=True, timeout=3600,
                env=dict(os.environ, JAX_PLATFORMS="cpu"),
            )
            pack_ok = proc.returncode == 0
            if not pack_ok:
                err = (proc.stderr or "")[-300:]
        except subprocess.TimeoutExpired:
            pack_ok, err = False, "pack build exceeded 3600s"
        pack_build_s = round(time.perf_counter() - t0, 1)
        print(json.dumps({
            "row": "pack_build", "images": len(train_manifest),
            "wall_s": pack_build_s, "ok": pack_ok,
            **({} if pack_ok else {"err": err}),
        }), flush=True)
        if not pack_ok:
            # A partial pack must not masquerade as complete on reruns —
            # covers crash, nonzero exit, AND timeout.
            shutil.rmtree(packed_dir, ignore_errors=True)

    # --- streaming decode: cold then warm --------------------------------
    dropped = _drop_page_cache()
    wall, n = _epoch_throughput(make_loader(), 0)
    print(json.dumps({
        "row": "cold_stream", "page_cache_dropped": dropped, "images": n,
        "wall_s": round(wall, 1), "images_per_sec": round(n / wall, 1),
    }), flush=True)
    wall, n = _epoch_throughput(make_loader(), 1)
    print(json.dumps({
        "row": "warm_stream", "images": n,
        "wall_s": round(wall, 1), "images_per_sec": round(n / wall, 1),
    }), flush=True)

    # --- packed mmap: cold then warm --------------------------------------
    if not pack_ok:
        print(json.dumps({"row": "cold_packed", "skipped": "pack build failed"}),
              flush=True)
        return
    dropped = _drop_page_cache()
    wall, n = _epoch_throughput(make_loader(packed_dir=packed_dir), 0)
    print(json.dumps({
        "row": "cold_packed", "page_cache_dropped": dropped, "images": n,
        "wall_s": round(wall, 1), "images_per_sec": round(n / wall, 1),
    }), flush=True)
    wall, n = _epoch_throughput(make_loader(packed_dir=packed_dir), 1)
    print(json.dumps({
        "row": "warm_packed", "images": n,
        "wall_s": round(wall, 1), "images_per_sec": round(n / wall, 1),
    }), flush=True)


if __name__ == "__main__":
    main()
