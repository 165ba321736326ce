"""The benchmark's token rows: row ``i`` is a fixed function of (seed, i),
made on the host by a splitmix-style hash, so the program and the reference
read the same rows from the same seed and sample ids. Token ids are uniform
over the configuration's vocabulary (its slice, where the vocabulary is
cut). The trainer reads them through the dataset interface of its data
pipeline: ``n_samples``, ``read(start, count)`` and ``read_ids(ids)``."""
from __future__ import annotations

import numpy as np

_M = (1 << 64) - 1


class TokenRows:
    def __init__(self, n_samples: int, seq_len: int, vocab: int, seed: int):
        self.n_samples = n_samples
        self.seq_len = seq_len
        self.vocab = vocab
        self.seed = np.uint64(int(seed) & _M)

    def read(self, start: int, count: int) -> dict:
        return self.read_ids(np.arange(start, start + count, dtype=np.int64))

    def read_ids(self, ids) -> dict:
        idx = np.asarray(ids, dtype=np.uint64)
        pos = np.arange(self.seq_len + 1, dtype=np.uint64)
        with np.errstate(over="ignore"):
            h = (self.seed * np.uint64(0x9E3779B97F4A7C15)
                 + idx[:, None] * np.uint64(0xBF58476D1CE4E5B9)
                 + pos[None, :] * np.uint64(0x94D049BB133111EB))
            h ^= h >> np.uint64(30)
            h *= np.uint64(0xBF58476D1CE4E5B9)
            h ^= h >> np.uint64(27)
            h *= np.uint64(0x94D049BB133111EB)
            h ^= h >> np.uint64(31)
        toks = (h % np.uint64(self.vocab)).astype(np.int32)
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:],
                "sample_ids": idx.astype(np.int64)}
