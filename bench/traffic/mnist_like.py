"""The paper's problem as data: an ``mnist_like`` binary classification set,
split over ``m`` nodes, and the l1-regularised logistic loss (Eq. 26).

A copy owned by the benchmark, so that no later change to the program can
move what the paper cells feed it.  The generator follows the geometry of
the paper's Table I (n = 60,000 rows of d = 784 features): a sparse teacher,
rows normalised to a fixed norm, labels from the teacher's margin plus
noise.  Everything is a pure function of the seed.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def make_classification(n: int, d: int, seed: int, *, margin: float,
                        noise: float, sparsity: float, row_norm: float):
    """-> features (n, d) float32, labels (n,) float32 in {0, 1}."""
    rng = np.random.default_rng(seed)
    w_true = rng.normal(size=d)
    mask = rng.random(d) < sparsity
    w_true = w_true * np.maximum(mask, 1e-12)
    x = rng.normal(size=(n, d))
    x *= row_norm / np.maximum(np.linalg.norm(x, axis=1, keepdims=True), 1e-9)
    raw = x @ w_true
    raw *= margin * 3.0 / max(np.std(raw), 1e-9)
    logits = raw + noise * rng.normal(size=n)
    y = (logits > 0).astype(np.float32)
    return x.astype(np.float32), y


def partition_per_node(features, labels, m: int, seed: int):
    """IID split into ``m`` equal shards -> (m, n // m, d), (m, n // m)."""
    rng = np.random.default_rng(seed)
    n = (features.shape[0] // m) * m
    idx = rng.permutation(n)
    idx = idx[rng.permutation(n)]
    d = features.shape[1]
    return {"features": features[idx].reshape(m, n // m, d),
            "labels": labels[idx].reshape(m, n // m)}


def make_problem_data(problem: dict, m: int, seed: int) -> dict:
    """The per-node data set a paper configuration describes."""
    feats, labels = make_classification(
        problem["rows"], problem["features"], seed,
        margin=problem["margin"], noise=problem["noise"],
        sparsity=problem["teacher_active"] / problem["features"],
        row_norm=problem["row_norm"])
    return partition_per_node(feats, labels, m, seed + 1)


def logreg_loss(w, batch):
    """Eq. 26, per node: mean over rows of -y z + log(1 + e^z), z = a . w.
    The dot runs at HIGHEST, the precision the configuration states."""
    logits = jnp.dot(batch["features"], w,
                     precision=jax.lax.Precision.HIGHEST)
    y = batch["labels"]
    return jnp.mean(-y * logits + jnp.log1p(jnp.exp(logits)))
