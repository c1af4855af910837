"""Pipeline parallelism: GPipe-style microbatch schedule over a ``pp`` axis.

New TPU capability (absent from the reference, SURVEY.md §2.10 — though its
SplitNN is conceptually a 2-stage pipeline across processes;
split_nn/client_manager.py:35-65): each device on the ``pp`` mesh axis holds
ONE stage's parameters; microbatches flow device-to-device via
``lax.ppermute``. With S stages and M microbatches the schedule runs
S+M−1 ticks; at tick t, stage s processes microbatch t−s (bubble fraction
(S−1)/(S+M−1), the GPipe bound). The last stage accumulates its outputs,
replicated to every device with one ``psum`` — results are bit-equal to
applying the stages sequentially (tested).

Differentiable end-to-end (ppermute has a transpose rule), so pipeline
training works by wrapping the whole thing in ``jax.grad``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P


def make_pipeline(stage_fn, mesh, axis: str = "pp"):
    """``pipe(stage_params, x) -> y``.

    ``stage_params``: pytree with a leading stage axis [S, ...], sharded
    over ``mesh[axis]`` (one stage per device). ``stage_fn(params, x)`` maps
    one microbatch through one stage; every stage must preserve the
    microbatch shape (equal widths — pad stages if not). ``x``: [M, B, d]
    microbatches, replicated; returns [M, B, d], replicated.
    """

    n_stages = int(mesh.shape[axis])
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def validated(stage_params, x):
        s = jax.tree.leaves(stage_params)[0].shape[0]
        if s != n_stages:
            raise ValueError(
                f"stage_params has {s} stages but the '{axis}' mesh axis has "
                f"{n_stages} devices; this schedule runs one stage per "
                "device (a mismatch would silently drop stages)")
        return _pipe(stage_params, x)

    @partial(shard_map, mesh=mesh, in_specs=(P(axis), P()), out_specs=P(),
             check_vma=False)
    def _pipe(stage_params, x):
        params_local = jax.tree.map(lambda a: a[0], stage_params)
        s = jax.lax.axis_index(axis)
        m, b, d = x.shape

        def tick(t, carry):
            prev_out, acc = carry
            # Receive the upstream stage's last output.
            recv = jax.lax.ppermute(prev_out, axis, perm)
            mb = t - s
            active = (mb >= 0) & (mb < m)
            x_in = jnp.where(s == 0, x[jnp.clip(t, 0, m - 1)], recv)
            out = stage_fn(params_local, x_in)
            out = jnp.where(active, out, jnp.zeros_like(out))
            is_last = s == n_stages - 1
            acc = acc.at[jnp.clip(mb, 0, m - 1)].add(
                jnp.where(active & is_last, out, jnp.zeros_like(out)))
            return out, acc

        out0 = jnp.zeros((b, d), x.dtype)
        acc0 = jnp.zeros_like(x)
        _, acc = jax.lax.fori_loop(0, n_stages + m - 1, tick, (out0, acc0))
        # Only the last stage wrote anything; replicate its buffer.
        return jax.lax.psum(acc, axis)

    return validated


def stack_stage_params(per_stage_params):
    """[pytree, pytree, ...] (equal structures) → pytree with leading stage
    axis, ready for :func:`make_pipeline`."""
    return jax.tree.map(lambda *leaves: jnp.stack(leaves), *per_stage_params)


def sequential_reference(stage_fn, per_stage_params, x):
    """Oracle: run the stages one after another on all microbatches."""

    def apply_all(xmb):
        for p in per_stage_params:
            xmb = stage_fn(p, xmb)
        return xmb

    return jax.vmap(apply_all)(x)
