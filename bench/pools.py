"""Candidate pools, made from the seed.

`classification_pool` copies the program's synthetic task generator
(repro.data.tasks.make_classification_task, pool side only) so that the
traffic lives with the benchmark: unlabeled, class-imbalanced pools
whose classes peak on their own slice of the vocabulary, with a slab of
near-duplicate majority examples (SelectFormer section 2.1). The labels are
returned for the record; the scoring traffic sends tokens only.
"""
from __future__ import annotations

import numpy as np


def classification_pool(seed: int, *, n_pool: int, seq: int, vocab: int,
                        n_classes: int, imbalance: float, signal: float,
                        redundancy: float) -> tuple[np.ndarray, np.ndarray]:
    rng = np.random.default_rng(seed)
    toks_per_class = vocab // (n_classes + 1)
    class_tokens = [np.arange(c * toks_per_class, (c + 1) * toks_per_class)
                    for c in range(n_classes)]
    noise_tokens = np.arange(n_classes * toks_per_class, vocab)

    def sample(label: int, n: int) -> np.ndarray:
        informative = rng.choice(class_tokens[label], size=(n, seq))
        noise = rng.choice(noise_tokens, size=(n, seq))
        take = rng.random((n, seq)) < signal
        return np.where(take, informative, noise).astype(np.int32)

    w = imbalance ** (-np.arange(n_classes) / max(n_classes - 1, 1))
    labels = rng.choice(n_classes, size=n_pool, p=w / w.sum())
    tokens = np.concatenate([sample(int(y), 1) for y in labels])
    n_red = int(redundancy * n_pool)
    if n_red:
        proto = sample(0, 1)[0]
        dup = np.tile(proto, (n_red, 1))
        flip = rng.random(dup.shape) < 0.05
        dup = np.where(flip, rng.integers(0, vocab, dup.shape), dup)
        idx = rng.choice(n_pool, size=n_red, replace=False)
        tokens[idx] = dup
        labels[idx] = 0
    return tokens, labels.astype(np.int32)

