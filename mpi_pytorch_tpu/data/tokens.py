"""Token datasets for the token models (``ModelSpec.sample == "tokens"``).

A sample is one PACKED sequence of ``S + 1`` int32 token ids: documents laid
end to end, no padding; the step reads inputs ``[:, :-1]`` and targets
``[:, 1:]`` (``train/step.py::_gather_batch``). A split is one array
``[N, S + 1]``:

- a pack on disk, ``<packed-dir>/train.tokens.npy`` (``write_token_pack``;
  the benchmark's recipe and the tests write theirs), named by the same
  ``--packed-dir`` the image packs use;
- or, with ``--synthetic-data true`` and no pack, ``--debug-sample-size``
  sequences of ``--image-size`` tokens drawn uniformly from ``--seed`` (a
  token model's "size" is its sequence length).

``TokenManifest`` stands where the trainer holds a ``Manifest``: a length, a
row selection, and — for the resume cursor's fingerprint — names and one
number a row (its checksum; nothing trains on it).
"""

from __future__ import annotations

import dataclasses
import os

import numpy as np

PACK_NAME = "train.tokens.npy"


@dataclasses.dataclass(frozen=True)
class TokenManifest:
    tokens: np.ndarray  # int32 [N, S + 1]

    def __len__(self) -> int:
        return self.tokens.shape[0]

    @property
    def filenames(self) -> tuple[str, ...]:
        return tuple(f"sequence-{i}" for i in range(len(self)))

    @property
    def labels(self) -> np.ndarray:
        """A checksum a row: what ``manifest_fingerprint`` hashes, and the
        device cache's (unread) label column."""
        return (self.tokens.astype(np.int64).sum(axis=1) % (2**31 - 1)).astype(np.int32)

    def shard(self, num_shards: int, shard_index: int) -> "TokenManifest":
        idx = np.array_split(np.arange(len(self)), num_shards)[shard_index]
        return TokenManifest(self.tokens[idx])

    def select(self, idx) -> "TokenManifest":
        return TokenManifest(self.tokens[np.asarray(idx)])


@dataclasses.dataclass
class TokenLoader:
    """What the trainer holds where an image run holds its ``DataLoader``:
    this host's shard, the metrics writer and the device cache's row
    contract. Token models train from the device cache
    (``config.validate_config``), so nothing iterates it."""

    manifest: TokenManifest
    batch_size: int
    metrics: object = None

    @property
    def cache_row(self) -> tuple[tuple[int, ...], np.dtype]:
        """(shape, dtype) of one row of the device cache: a packed sequence
        ``int32 [S + 1]`` as it lies in the pack."""
        return self.manifest.tokens.shape[1:], np.dtype(np.int32)

    def fill_cache_rows(self, manifest, lo: int, hi: int, out: np.ndarray) -> set[int]:
        """Rows ``[lo, hi)`` of ``manifest`` into ``out[: hi - lo]``, in
        place; nothing is decoded, so nothing is ever quarantined."""
        out[: max(hi - lo, 0)] = manifest.tokens[lo:hi]
        return set()


def synthetic_tokens(n: int, seq_len: int, vocab: int, seed: int) -> np.ndarray:
    return np.random.default_rng([seed, 29]).integers(
        0, vocab, size=(n, seq_len + 1), dtype=np.int32
    )


def write_token_pack(packed_dir: str, tokens: np.ndarray) -> str:
    os.makedirs(packed_dir, exist_ok=True)
    path = os.path.join(packed_dir, PACK_NAME)
    np.save(path, np.ascontiguousarray(tokens, np.int32))
    return path


def load_token_manifests(cfg, vocab: int) -> tuple[TokenManifest, TokenManifest]:
    """(train, test) for a token model: the pack under ``cfg.packed_dir``, or
    synthetic sequences. Ids outside ``[0, vocab)`` are an error here, not a
    clamped gather on the device. There is no test split yet: the train
    manifest stands in (validation is off for token models)."""
    if cfg.packed_dir:
        path = os.path.join(cfg.packed_dir, PACK_NAME)
        if not os.path.exists(path):
            raise FileNotFoundError(
                f"{cfg.model_name!r} reads its sequences from {path} "
                "(data/tokens.write_token_pack writes one)"
            )
        tokens = np.load(path, mmap_mode="r")
    elif cfg.synthetic_data:
        tokens = synthetic_tokens(cfg.debug_sample_size, cfg.image_size[0], vocab, cfg.seed)
    else:
        raise ValueError(
            f"{cfg.model_name!r}: name a token pack with --packed-dir or set "
            "--synthetic-data true"
        )
    if tokens.ndim != 2 or tokens.dtype != np.int32 or tokens.shape[1] < 2:
        raise ValueError(f"token pack {tokens.shape} {tokens.dtype}: expected int32 [N, S + 1]")
    lo, hi = int(tokens.min()), int(tokens.max())
    if lo < 0 or hi >= vocab:
        raise ValueError(f"token ids span [{lo}, {hi}], the model's vocabulary is {vocab}")
    manifest = TokenManifest(tokens)
    return manifest, manifest
