"""Recipe ``tokens``: ``sequences`` packed sequences of ``seq_len + 1`` ids
in the program's token pack (``data/tokens.py``: ``train.tokens.npy``, int32
``[N, seq_len + 1]``). Documents have log-normal lengths (median
``doc_len_median``, sigma ``doc_len_sigma`` of the log, clipped to
``doc_len_min`` .. ``doc_len_max``), are laid end to end with ``eod_id``
closing each, and fill every row to its last position: no padding. Ids are
Zipf-distributed (probability of rank r proportional to ``r ** -zipf_exponent``)
over the vocabulary without ``eod_id``, the rank being the id, so that low ids
are frequent and routing over experts is uneven. A pure function of the recipe,
the vocabulary and ``--seed``."""

from __future__ import annotations

import hashlib
import json
import os

import numpy as np

PACK = "train.tokens.npy"


def sequences(recipe: dict, vocab: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng([seed, 31])
    n, width = recipe["sequences"], recipe["seq_len"] + 1
    total = n * width
    # Enough documents to fill the rows, whatever their lengths come out as.
    lengths = np.empty(0, np.int64)
    while lengths.sum() < total:
        draw = rng.lognormal(np.log(recipe["doc_len_median"]), recipe["doc_len_sigma"], size=64)
        lengths = np.concatenate([
            lengths, np.clip(np.rint(draw), recipe["doc_len_min"], recipe["doc_len_max"]).astype(np.int64)
        ])
    ends = np.cumsum(lengths)  # a document's last position holds eod_id
    ids = np.array([i for i in range(vocab) if i != recipe["eod_id"]])
    weights = np.arange(1, len(ids) + 1, dtype=np.float64) ** -recipe["zipf_exponent"]
    cdf = np.cumsum(weights / weights.sum())
    flat = ids[np.minimum(np.searchsorted(cdf, rng.random(total)), len(ids) - 1)]
    flat[ends[ends <= total] - 1] = recipe["eod_id"]
    return flat.reshape(n, width).astype(np.int32)


def ensure(recipe: dict, *, vocab: int, seed: int, data_root: str) -> str:
    """The directory that holds this (recipe, vocabulary, seed)'s pack, built
    under a temporary name and renamed: a complete pack or none."""
    key = hashlib.sha1(
        json.dumps({"recipe": recipe, "vocab": vocab, "seed": seed}, sort_keys=True).encode()
    ).hexdigest()[:12]
    root = os.path.join(data_root, f"tokens-{key}")
    if not os.path.isdir(root):
        tmp = f"{root}.tmp{os.getpid()}"
        os.makedirs(tmp)
        np.save(os.path.join(tmp, PACK), sequences(recipe, vocab, seed))
        os.rename(tmp, root)
    return root


def flags(root: str) -> dict:
    return {"packed-dir": root}
