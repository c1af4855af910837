"""Plain FedAvg on multinomial logistic regression - the reference the
benchmark holds ``FedAvgAPI`` to before every window.

Written from the algorithm (McMahan et al. 2017, Algorithm 1) and the
reference implementation's sampler (``np.random.seed(round)``, then
``np.random.choice(range(N), k, replace=False)``), in ``jax.numpy`` and
float32 at "highest" matmul precision. It imports nothing from the system
under test. One local epoch of ONE full-batch SGD step per client, so the
order of a client's samples cannot matter; the server takes the mean of the
client models weighted by their sample counts.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def sample_cohort(round_idx: int, n_clients: int, k: int) -> np.ndarray:
    if k >= n_clients:
        return np.arange(n_clients)
    return np.random.RandomState(round_idx).choice(n_clients, k, replace=False)


def _loss(params, x, y, mask):
    w, b = params
    logits = x @ w + b
    logp = logits - jax.scipy.special.logsumexp(logits, axis=-1, keepdims=True)
    nll = -jnp.take_along_axis(logp, y[:, None], axis=-1)[:, 0]
    return jnp.sum(nll * mask) / jnp.sum(mask)


_grad = jax.jit(jax.grad(_loss))


def fedavg_rounds(w, b, client_data, n_clients, cohort, rounds, lr):
    """``rounds`` is the list of round indices; ``client_data(c)`` gives
    client ``c``'s ``(x [n, d], y [n])``. Clients are padded (masked) to one
    length so the gradient compiles once. Returns the final ``(w, b)``."""
    w = jnp.asarray(w, jnp.float32)
    b = jnp.asarray(b, jnp.float32)
    cohorts = [[client_data(int(c))
                for c in sample_cohort(r, n_clients, cohort)] for r in rounds]
    cap = max(len(y) for clients in cohorts for _, y in clients)
    with jax.default_matmul_precision("highest"):
        for clients in cohorts:
            acc_w, acc_b, total = jnp.zeros_like(w), jnp.zeros_like(b), 0
            for x, y in clients:
                n = len(y)
                xp = np.zeros((cap, x.shape[1]), np.float32)
                yp = np.zeros((cap,), np.int32)
                mp = np.zeros((cap,), np.float32)
                xp[:n], yp[:n], mp[:n] = x, y, 1.0
                gw, gb = _grad((w, b), xp, yp, mp)
                acc_w = acc_w + n * (w - lr * gw)
                acc_b = acc_b + n * (b - lr * gb)
                total += n
            w, b = acc_w / total, acc_b / total
    return np.asarray(w), np.asarray(b)
