"""The token source and loader of the sequence families: ``(N, S)`` int32
ids behind the same ``DeviceStager`` and fit loop as the image loaders.

A token source is an ``(n, S)`` array of ids, every row one full sequence
(concatenated text cut into rows; no reset at document boundaries).  It
comes from a caller (``train_net(roidb=...)`` takes it where the detectors
take a roidb), from a file (dataset ``tokens``:
``<dataset_path>/<image_set>.npy``, one- or two-dimensional) or from the
seed (dataset ``synthetic_tokens``: uniform ids, download-free).
"""

from __future__ import annotations

import os
from typing import Iterator

import numpy as np

from mx_rcnn_tpu.core.train import TokenBatch

SYNTHETIC_ROWS = 256


def load_token_source(cfg, seed: int = 0) -> np.ndarray:
    """(n, S) int32 ids of ``cfg.dataset`` cut to ``cfg.train.seq_len``."""
    ds, s, vocab = cfg.dataset, cfg.train.seq_len, cfg.network.vocab_size
    if ds.name == "synthetic_tokens":
        rng = np.random.RandomState(seed % (2 ** 32))
        return rng.randint(0, vocab, (SYNTHETIC_ROWS, s)).astype(np.int32)
    if ds.name != "tokens":
        raise ValueError(f"dataset {ds.name!r} is no token source")
    path = os.path.join(ds.dataset_path, f"{ds.image_set}.npy")
    ids = np.load(path, mmap_mode="r")
    if ids.ndim == 1:
        ids = ids[:ids.size // s * s].reshape(-1, s)
    if ids.ndim != 2 or ids.shape[1] != s or not len(ids):
        raise ValueError(f"{path}: {ids.shape} holds no rows of {s} ids")
    if int(ids.max()) >= vocab or int(ids.min()) < 0:
        raise ValueError(f"{path}: ids outside the {vocab} rows held")
    return ids


class TokenLoader:
    """Batches of ``batch_images`` rows of a token source, in the source's
    order, or in a per-epoch permutation of the rows with ``shuffle``
    (deterministic from ``seed`` and the epoch, so a resume replays it).
    A trailing partial batch is dropped."""

    def __init__(self, source, cfg, batch_images: int, shuffle: bool = True,
                 seed: int = 0):
        source = np.asarray(source)
        if source.ndim != 2 or source.shape[1] != cfg.train.seq_len:
            raise ValueError(
                f"token source {source.shape} holds no rows of "
                f"train.seq_len={cfg.train.seq_len} ids")
        if len(source) < batch_images:
            raise ValueError(f"{len(source)} sequences cannot fill a batch "
                             f"of {batch_images}")
        self.source, self.batch_images = source, int(batch_images)
        self.shuffle, self.seed = shuffle, seed
        self._epoch, self._skip = 0, 0

    def __len__(self) -> int:
        return len(self.source) // self.batch_images

    def set_epoch(self, epoch: int) -> None:
        self._epoch = int(epoch)

    def skip_next_batches(self, n: int) -> None:
        """The next iteration starts ``n`` batches into the epoch."""
        self._skip = int(n)

    def __iter__(self) -> Iterator[TokenBatch]:
        rows = np.arange(len(self.source))
        if self.shuffle:
            rows = np.random.RandomState(
                (self.seed * 1_000_003 + self._epoch) % (2 ** 32)
            ).permutation(rows)
        first, self._skip = self._skip, 0
        n = self.batch_images
        for i in range(first, len(self)):
            ids = self.source[rows[i * n:(i + 1) * n]]
            yield TokenBatch(ids=np.ascontiguousarray(ids, dtype=np.int32))
