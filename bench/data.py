"""Inputs made from the seed: the SAE's synthetic table and LM token rows.

``make_classification`` is a copy of the generator the paper's Table 1
uses (scikit-learn's hypercube mode), kept here so the benchmark's inputs
do not change when the program's copy does. ``token_rows`` draws
zipf-like next-token rows with a copied second half, so a model has
something to learn.
"""
from __future__ import annotations

import numpy as np


def make_classification(n_samples, n_features, n_informative, n_classes,
                        class_sep, flip_y, seed):
    """(X float32 (n, d), y int64 (n,)) in scikit-learn's hypercube mode,
    one cluster per class."""
    rng = np.random.default_rng(seed)
    centroids = rng.integers(0, 2, size=(n_classes, n_informative))
    centroids = centroids.astype(np.float64) * 2 * class_sep - class_sep
    counts = np.full(n_classes, n_samples // n_classes)
    counts[: n_samples % n_classes] += 1
    X_inf = np.empty((n_samples, n_informative))
    y = np.empty(n_samples, dtype=np.int64)
    pos = 0
    for c in range(n_classes):
        k = counts[c]
        block = rng.normal(size=(k, n_informative))
        A = rng.uniform(-1, 1, size=(n_informative, n_informative))
        X_inf[pos:pos + k] = block @ A * 0.5 + centroids[c]
        y[pos:pos + k] = c
        pos += k
    X = rng.normal(size=(n_samples, n_features))
    informative = rng.choice(n_features, size=n_informative, replace=False)
    X[:, informative] = X_inf
    flip = rng.uniform(size=n_samples) < flip_y
    y[flip] = rng.integers(0, n_classes, size=flip.sum())
    perm = rng.permutation(n_samples)
    return X[perm].astype(np.float32), y[perm]


def sae_table(cfg: dict, seed: int):
    """Standardised train/test split of the configuration's table:
    (X_train, y_train, X_test, y_test)."""
    X, y = make_classification(cfg["n_samples"], cfg["n_features"],
                               cfg["n_informative"], cfg["n_classes"],
                               cfg["class_sep"], cfg["flip_y"], seed)
    X = ((X - X.mean(0)) / (X.std(0) + 1e-6)).astype(np.float32)
    n_test = int(round(cfg["test_frac"] * len(X)))
    perm = np.random.default_rng(seed + 1).permutation(len(X))
    te, tr = perm[:n_test], perm[n_test:]
    return X[tr], y[tr], X[te], y[te]


def token_rows(seed: int, step: int, batch: int, seq: int, vocab: int,
               zipf_a: float) -> np.ndarray:
    """(batch, seq + 1) int32 tokens for one step, a function of
    (seed, step) alone, so any step can be made again."""
    rng = np.random.default_rng([seed, step])
    tok = (rng.zipf(zipf_a, size=(batch, seq + 1))
           + rng.integers(0, 7, size=(batch, seq + 1))) % vocab
    half = (seq + 1) // 2
    tok[:, half:2 * half] = tok[:, :half]
    return tok.astype(np.int32)
