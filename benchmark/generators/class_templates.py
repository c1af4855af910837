"""The one data generator: a labelled federation from a seed and a mix file.

Every sample is ``(strength * template[label] + noise) / sqrt(1 + strength^2)``
with a seeded template per class, so the data is learnable (the runner checks
that training beats the prior) and roughly unit variance. A template is a
coarse ``template_grid x template_grid`` unit-normal image blown up to the
input's size: low spatial frequencies, which a convolutional net with global
pooling picks up within tens of rounds (per-pixel noise templates it does
not: PERF.md, PR 24).
Labels are uniform, so every client is IID in its labels; what a mix varies
is how many samples each client holds (``counts``):

- ``{"law": "equal", "per_client": n}``
- ``{"law": "lognormal", "mu": m, "sigma": s, "min": k, "seed": c}`` - the
  sizes come from the mix's OWN seed ``c``, not from ``--seed``: the sampler
  is ``RandomState(round)``, so every seed then meets the same cohort shapes
  round for round, and ``--seed`` changes the samples, the labels and the
  model's initial weights, never the amount of work.

(The seeded-federation idea is bench.py's ``_synthetic_cifar_fed`` /
``_synthetic_femnist_store``; the templates are new: those were pure noise
with random labels, on which no loss can fall.)
"""

from __future__ import annotations

import numpy as np


def client_counts(law: dict, clients: int) -> np.ndarray:
    if law["law"] == "equal":
        return np.full(clients, int(law["per_client"]), np.int64)
    if law["law"] == "lognormal":
        sizes = np.random.RandomState(int(law["seed"])).lognormal(
            law["mu"], law["sigma"], clients).astype(np.int64)
        return np.maximum(int(law.get("min", 1)), sizes)
    raise ValueError(f"unknown counts law {law['law']!r}")


def generate(mix: dict, config: dict, seed: int):
    """``(x [N, *input_shape] float32, y [N] int32, parts {client: rows},
    counts [clients])`` for this mix and configuration."""
    clients = int(mix["clients"])
    counts = client_counts(mix["counts"], clients)
    total = int(counts.sum())
    shape = tuple(config["input_shape"])
    classes = int(config["classes"])
    s = float(mix["template_strength"])
    rng = np.random.default_rng(seed)
    grid = int(mix["template_grid"])
    coarse = rng.standard_normal((classes, grid, grid, shape[-1]),
                                 dtype=np.float32)
    reps = (-(-shape[0] // grid), -(-shape[1] // grid))
    templates = np.repeat(np.repeat(coarse, reps[0], axis=1), reps[1],
                          axis=2)[:, :shape[0], :shape[1]]
    y = rng.integers(0, classes, total, dtype=np.int32)
    x = rng.standard_normal((total,) + shape, dtype=np.float32)
    x += s * templates[y]
    x *= np.float32(1.0 / np.sqrt(1.0 + s * s))
    edges = np.concatenate([[0], np.cumsum(counts)])
    parts = {c: np.arange(edges[c], edges[c + 1]) for c in range(clients)}
    return x, y, parts, counts
