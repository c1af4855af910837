"""``class_templates``' samples, split over the clients by label with the
reference's LDA (``fedml_tpu.data.partition.partition_dirichlet``: a
Dirichlet draw a class, with its size balancing and minimum-size retries).

The labels (``samples_per_class`` of each class, shuffled) and the split come
from the mix's OWN seed (``lda.seed``), not from ``--seed``: the sampler is
``RandomState(round)``, so every ``--seed`` meets the same client sizes, the
same step count and the same padding round for round. ``--seed`` makes the
class templates and the noise, by ``class_templates``' rule: a sample is
``(strength * template[label] + noise) / sqrt(1 + strength^2)`` with a coarse
``template_grid x template_grid`` unit-normal template a class.
"""

from __future__ import annotations

import numpy as np


def generate(mix: dict, config: dict, seed: int):
    """``(x [N, *input_shape] float32, y [N] int32, parts {client: rows},
    counts [clients])``."""
    from fedml_tpu.data.partition import partition_dirichlet

    clients, classes = int(mix["clients"]), int(config["classes"])
    lda = mix["lda"]
    own = np.random.RandomState(int(lda["seed"]))
    y = own.permutation(np.repeat(
        np.arange(classes, dtype=np.int32), int(lda["samples_per_class"])))
    parts = partition_dirichlet(y, clients, float(lda["alpha"]),
                                seed=int(lda["seed"]))
    parts = {c: np.sort(np.asarray(parts[c])) for c in range(clients)}
    counts = np.array([len(parts[c]) for c in range(clients)], np.int64)

    shape = tuple(config["input_shape"])
    s = float(mix["template_strength"])
    grid = int(mix["template_grid"])
    rng = np.random.default_rng(seed)
    coarse = rng.standard_normal((classes, grid, grid, shape[-1]),
                                 dtype=np.float32)
    reps = (-(-shape[0] // grid), -(-shape[1] // grid))
    templates = np.repeat(np.repeat(coarse, reps[0], axis=1), reps[1],
                          axis=2)[:, :shape[0], :shape[1]]
    x = rng.standard_normal((len(y),) + shape, dtype=np.float32)
    x += s * templates[y]
    x *= np.float32(1.0 / np.sqrt(1.0 + s * s))
    return x, y, parts, counts
