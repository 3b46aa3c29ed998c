"""Process-tagged logging, shared by the train and eval drivers.

The reference duplicates an ``init_logger()`` in both entry points
(``main.py:22-41``, ``evaluation_pipeline.py:19-38``): a rank-tagged Python
logger with dual stream+file handlers. This is the single shared equivalent,
tagged with ``jax.process_index()`` instead of an MPI rank, plus a structured
JSONL metrics writer the reference lacks.
"""

from __future__ import annotations

import json
import logging
import os
import sys
import time
from typing import Any, Mapping


def process_index() -> int:
    """This process's index in the multi-process world (0 single-process).
    Imported lazily so this module stays importable without jax, and read
    WITHOUT starting a backend: a logger or metrics writer must never be
    what takes the chips (the parent of a remote serving fleet logs, and
    its children need them)."""
    from mpi_pytorch_tpu.parallel.compat import process_index as _index

    return _index()


def init_logger(name: str = "MPT", log_file: str | None = "training.log",
                level: int = logging.INFO) -> logging.Logger:
    """Rank-tagged logger with stream+file handlers (parity: ``main.py:22-41``)."""
    rank = process_index()
    logger = logging.getLogger(f"{name}_R{rank}")
    logger.setLevel(level)
    logger.propagate = False

    fmt = logging.Formatter(
        "%(asctime)s %(name)s %(levelname)s: %(message)s", datefmt="%Y-%m-%d %H:%M:%S"
    )
    if not any(isinstance(h, logging.StreamHandler) and not isinstance(h, logging.FileHandler)
               for h in logger.handlers):
        sh = logging.StreamHandler(sys.stdout)
        sh.setFormatter(fmt)
        logger.addHandler(sh)
    if log_file:
        target = os.path.abspath(log_file)
        file_handlers = [h for h in logger.handlers if isinstance(h, logging.FileHandler)]
        if not any(h.baseFilename == target for h in file_handlers):
            # Re-init with a different path (new run/config): swap file handlers.
            for h in file_handlers:
                logger.removeHandler(h)
                h.close()
            os.makedirs(os.path.dirname(log_file) or ".", exist_ok=True)
            fh = logging.FileHandler(log_file)
            fh.setFormatter(fmt)
            logger.addHandler(fh)
            logger.info("Logger Initialized (process %d)", rank)
    return logger


def run_logger() -> logging.Logger:
    """The rank-tagged run logger — the SAME logger ``init_logger`` configures
    (stream + file handlers, ``propagate=False``). Library modules that need
    to surface messages outside the trainer (e.g. checkpoint restore
    warnings) must log here, not to a module-named logger: the run logger
    doesn't propagate, and an unconfigured module logger would fall to the
    bare stderr last-resort handler and never reach ``training.log``."""
    return logging.getLogger(f"MPT_R{process_index()}")


class MetricsWriter:
    """Structured JSONL metrics (throughput, loss, MFU) — SURVEY §5 observability.

    Only process 0 writes, mirroring the reference's rank-0-only result
    reporting (``main.py:173-185``).
    """

    def __init__(self, path: str | None):
        self._fh = None
        if path and process_index() == 0:
            os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
            self._fh = open(path, "a", buffering=1)

    def write(self, record: Mapping[str, Any]) -> None:
        if self._fh is None:
            return
        rec = {"ts": time.time(), **record}
        self._fh.write(json.dumps(rec) + "\n")

    def close(self) -> None:
        if self._fh is not None:
            self._fh.close()
            self._fh = None
