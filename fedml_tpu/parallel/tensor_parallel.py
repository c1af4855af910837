"""Tensor parallelism for the transformer (Megatron-style, shard_map).

New TPU capability (the reference's models are small CNNs/LSTMs — no TP
exists there, SURVEY.md §2.10): the transformer block's two big matmul
pairs are sharded over a ``tp`` mesh axis —

- MLP: W_in column-sharded → per-device hidden shard → W_out row-sharded →
  ``psum`` (one collective per MLP);
- Attention: heads split across devices (QKV column-sharded, output proj
  row-sharded → ``psum``).

Implemented as a functional transform over a ``TransformerLM``'s params:
``shard_tp_params`` splits the replicated parameter pytree into per-device
shards, and ``make_tp_forward`` runs the block-parallel forward inside
``shard_map`` — activations replicated, parameters device-local, exactly
matching the unsharded model's math (tested to 1e-5).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P


def _split(arr, n, axis):
    return jnp.stack(jnp.split(arr, n, axis=axis))


def _split_qkv(kernel, n):
    """Fused QKV kernel [d, 3d]: device i must get (Q_i, K_i, V_i) — its
    heads' columns from EACH of the three projections, not a contiguous
    3d/n column chunk (which would hand device 0 a slice of Q only)."""
    q, k, v = jnp.split(kernel, 3, axis=1)
    w = q.shape[1] // n
    return jnp.stack([
        jnp.concatenate([p[:, i * w:(i + 1) * w] for p in (q, k, v)], axis=1)
        for i in range(n)
    ])


def shard_tp_params(params: Dict[str, Any], n_dev: int) -> Dict[str, Any]:
    """Split a TransformerLM param tree for tp: per-layer QKV/W_in sharded on
    the OUTPUT dim, out-proj/W_out on the INPUT dim; everything else
    replicated (stacked n_dev times on a new leading axis so the whole tree
    has a uniform [n_dev, ...] layout for shard_map)."""

    def visit(path, leaf):
        keys = [getattr(k, "key", str(k)) for k in path]
        name = "/".join(keys)
        if "Dense_0" in name and "MHA_" in name and keys[-1] == "kernel":
            return _split_qkv(leaf, n_dev)  # QKV fused: per-head column shard
        if "Dense_1" in name and "MHA_" in name and keys[-1] == "kernel":
            return _split(leaf, n_dev, axis=0)  # out proj: row shard
        if "Dense_0" in name and "Block_" in name and "MHA_" not in name and keys[-1] == "kernel":
            return _split(leaf, n_dev, axis=1)  # MLP in: column shard
        if "Dense_0" in name and "Block_" in name and "MHA_" not in name and keys[-1] == "bias":
            return _split(leaf, n_dev, axis=0)
        if "Dense_1" in name and "Block_" in name and "MHA_" not in name and keys[-1] == "kernel":
            return _split(leaf, n_dev, axis=0)  # MLP out: row shard
        return jnp.broadcast_to(leaf[None], (n_dev,) + leaf.shape)

    return jax.tree_util.tree_map_with_path(visit, params)


def make_tp_forward(model, mesh, axis: str = "tp"):
    """``fwd(sharded_params, tokens) -> logits`` running the TP math inside
    shard_map. ``model`` is a TransformerLM (used for static shape config:
    layers, heads, dims). The tp size must divide the head count."""
    n_dev = int(mesh.shape[axis])
    if model.n_heads % n_dev:
        raise ValueError(
            f"tp={n_dev} must divide n_heads={model.n_heads} "
            "(attention heads are split across the tp axis)")
    d_model = model.d_model
    n_layers = model.n_layers
    heads_local = model.n_heads // n_dev
    d_head = d_model // model.n_heads
    causal = model.causal

    def block(x, p, prefix):
        # --- attention (heads sharded) ---------------------------------
        h = _layernorm(x, p[f"{prefix}/LayerNorm_0"])
        qkv = h @ p[f"{prefix}/MHA_0/Dense_0"]["kernel"]  # [B,T,3*dm/n]
        b, t, _ = qkv.shape
        q, k, v = jnp.split(qkv, 3, axis=-1)
        shp = (b, t, heads_local, d_head)
        from fedml_tpu.parallel.ring_attention import reference_attention

        o = reference_attention(q.reshape(shp), k.reshape(shp), v.reshape(shp),
                                causal=causal)
        o = o.reshape(b, t, heads_local * d_head)
        attn = jax.lax.psum(o @ p[f"{prefix}/MHA_0/Dense_1"]["kernel"], axis)
        x = x + attn
        # --- MLP (hidden sharded) --------------------------------------
        h = _layernorm(x, p[f"{prefix}/LayerNorm_1"])
        mid = jax.nn.gelu(h @ p[f"{prefix}/Dense_0"]["kernel"]
                          + p[f"{prefix}/Dense_0"]["bias"])
        out = jax.lax.psum(mid @ p[f"{prefix}/Dense_1"]["kernel"], axis)
        # W_out bias is replicated — add once (outside the psum).
        out = out + p[f"{prefix}/Dense_1"]["bias"]
        return x + out

    def _layernorm(x, p):
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.var(x, axis=-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + 1e-6) * p["scale"] + p["bias"]

    def flat(params):
        """dict keyed by 'a/b/c' path → leaf (built per call; cheap)."""
        out = {}

        def visit(path, leaf):
            keys = [getattr(kk, "key", str(kk)) for kk in path]
            out["/".join(keys[:-1])] = out.get("/".join(keys[:-1]), {})
            out["/".join(keys[:-1])][keys[-1]] = leaf

        jax.tree_util.tree_map_with_path(visit, params)
        return out

    @partial(shard_map, mesh=mesh, in_specs=(P(axis), P()), out_specs=P(),
             check_vma=False)
    def fwd(sharded_params, tokens):
        p = flat(jax.tree.map(lambda a: a[0], sharded_params))
        x = p["Embed_0"]["embedding"][tokens]
        pos = p["Embed_1"]["embedding"][: tokens.shape[1]]
        x = x + pos[None]
        for i in range(n_layers):
            x = block(x, p, f"Block_{i}")
        x = _layernorm(x, p["LayerNorm_0"])
        return x @ p["Dense_0"]["kernel"]

    return fwd
