"""Expert parallelism: top-1 mixture-of-experts with all_to_all dispatch.

New TPU capability (nothing comparable exists in the reference, SURVEY.md
§2.10): E experts' MLPs live one-per-device on an ``ep`` mesh axis; tokens
are sharded over the same axis. Each device routes its tokens (top-1 +
softmax gate), packs them into per-expert capacity buffers, and a single
``lax.all_to_all`` ships every buffer to its expert's device — the
canonical MoE dispatch that rides ICI. Expert compute is one batched MLP;
a second all_to_all returns outputs, which are unpacked and gate-weighted.

Tokens over capacity are dropped (output 0 — standard Switch-style
behavior); with ``capacity >= tokens_per_device`` no token can drop and the
sharded result equals the dense oracle exactly (tested).
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P


class MoEParams(NamedTuple):
    w_router: jax.Array  # [d, E]
    w_in: jax.Array      # [E, d, h]
    b_in: jax.Array      # [E, h]
    w_out: jax.Array     # [E, h, d]
    b_out: jax.Array     # [E, d]


def init_moe(rng, d: int, hidden: int, n_experts: int) -> MoEParams:
    k1, k2, k3 = jax.random.split(rng, 3)
    s_in = 1.0 / jnp.sqrt(d)
    s_out = 1.0 / jnp.sqrt(hidden)
    return MoEParams(
        w_router=jax.random.normal(k1, (d, n_experts)) * s_in,
        w_in=jax.random.normal(k2, (n_experts, d, hidden)) * s_in,
        b_in=jnp.zeros((n_experts, hidden)),
        w_out=jax.random.normal(k3, (n_experts, hidden, d)) * s_out,
        b_out=jnp.zeros((n_experts, d)),
    )


def _expert_mlp(x, w_in, b_in, w_out, b_out):
    return jax.nn.gelu(x @ w_in + b_in) @ w_out + b_out


def moe_reference(params: MoEParams, x: jnp.ndarray) -> jnp.ndarray:
    """Dense oracle: every expert runs on every token, outputs masked by the
    top-1 routing decision and weighted by the softmax gate. [N, d] → [N, d]."""
    logits = x @ params.w_router  # [N, E]
    idx = jnp.argmax(logits, axis=-1)
    gate = jnp.take_along_axis(jax.nn.softmax(logits, -1), idx[:, None], -1)[:, 0]
    all_out = jax.vmap(
        lambda w_in, b_in, w_out, b_out: _expert_mlp(x, w_in, b_in, w_out, b_out)
    )(params.w_in, params.b_in, params.w_out, params.b_out)  # [E, N, d]
    sel = jnp.take_along_axis(
        all_out, idx[None, :, None], axis=0)[0]  # [N, d]
    return sel * gate[:, None]


def make_moe_ep(mesh, axis: str = "ep", capacity: int | None = None):
    """``moe(params, x) -> y`` with tokens AND experts sharded over
    ``mesh[axis]``; one expert per device (E == mesh size). ``capacity`` =
    max tokens each (source device → expert) pair can carry per call;
    defaults to tokens_per_device (lossless)."""
    n_dev = int(mesh.shape[axis])

    def validated(params, x):
        e = params.w_in.shape[0]
        if e != n_dev:
            raise ValueError(
                f"MoE has {e} experts but the '{axis}' mesh axis has "
                f"{n_dev} devices; this layout runs one expert per device "
                f"(a mismatch would silently drop tokens routed to experts "
                f">= {n_dev})")
        return _moe(params, x)

    @partial(shard_map, mesh=mesh,
             in_specs=(
                 MoEParams(P(), P(axis), P(axis), P(axis), P(axis)),
                 P(axis),
             ),
             out_specs=P(axis), check_vma=False)
    def _moe(params, x):
        n_local, d = x.shape
        cap = capacity or n_local
        # Local routing over the FULL router (replicated) --------------
        logits = x @ params.w_router  # [n_local, E]
        idx = jnp.argmax(logits, axis=-1)
        gate = jnp.take_along_axis(
            jax.nn.softmax(logits, -1), idx[:, None], -1)[:, 0]
        # Pack per-expert capacity buffers -----------------------------
        onehot = jax.nn.one_hot(idx, n_dev, dtype=jnp.int32)  # [n, E]
        pos = jnp.cumsum(onehot, axis=0) * onehot - 1  # slot per token, -1 if other expert
        pos = jnp.max(pos, axis=1)  # [n]
        keep = pos < cap
        dispatch = (
            jax.nn.one_hot(idx, n_dev, dtype=x.dtype)[:, :, None]
            * jax.nn.one_hot(jnp.where(keep, pos, cap), cap + 1,
                             dtype=x.dtype)[:, None, :]
        )[:, :, :cap]  # [n, E, cap] (overflow slot truncated)
        buf = jnp.einsum("nec,nd->ecd", dispatch, x)  # [E, cap, d]
        # Ship buffers to their expert's device ------------------------
        recv = jax.lax.all_to_all(buf, axis, split_axis=0, concat_axis=0,
                                  tiled=True)  # [n_dev*cap, d] for MY expert
        # Expert compute (device-local expert 0 of the sharded stack) --
        y = _expert_mlp(recv, params.w_in[0], params.b_in[0],
                        params.w_out[0], params.b_out[0])
        # Return outputs to the token owners ---------------------------
        back = jax.lax.all_to_all(
            y.reshape(n_dev, cap, d), axis, split_axis=0, concat_axis=0,
            tiled=True).reshape(n_dev, cap, d)  # [E, cap, d] from each expert
        out = jnp.einsum("nec,ecd->nd", dispatch, back)
        return out * (gate * keep.astype(x.dtype))[:, None]

    return validated
