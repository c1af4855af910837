"""Qwen3-Next: gated DeltaNet, gated attention, and one shard of a wide
mixture of experts, as a flax module on the federated round's normal path.

Source: https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/
config.json (the field names below are its keys). A period of
``full_attention_interval`` layers is gated-DeltaNet linear attention
(``ops/gated_delta.py``) with a gated softmax-attention layer last
(``ops/flash_attention.py``); every layer ends in a mixture of ``num_experts``
experts, top ``num_experts_per_tok``, plus one shared expert behind a
sigmoid gate. This module is ONE SHARD of an expert-parallel deployment
(``parallel/expert_parallel.route_held``): it routes over all
``num_experts``, holds ``num_experts_held`` of them from
``first_expert_held`` on, and computes their part; a token none of whose
experts is held gets the shared expert only.

The equations, written out, are ``benchmark/reference_qwen3_next.py``'s, which
imports nothing from here; the tests hold the two together. No biases.
Norms, gates, decays, routing and the logits are float32; the matrix
products take ``dtype`` inputs (``parallel/layout.step_dtype_model`` clones
the module to bfloat16 for the client step) and accumulate in float32; the
residual stream is float32. This repo's own choices, not the source's: the
order of channels inside ``in_proj_qkvz`` (``q | k | v | z``) and ``q_proj``
(per head ``query | gate``), gate and up side by side in one matrix, the
initial values (``assumed`` in the benchmark's configuration file). Left
out: the multi-token-prediction module and the router's auxiliary loss.

Device scopes (``jax.named_scope``, read by ``benchmark/reduce_scopes.py``):
``fed.model.gdn`` (``.scan`` around the chunked rule), ``fed.model.attn``
(``.core`` around the softmax attention), ``fed.model.moe`` (``.route``,
``.experts``, ``.shared``), ``fed.model.head`` (embedding, final norm, head;
``token_ce`` puts the loss there too), ``fed.model.norm`` (a layer's input
norm, the one piece of the residual stream outside the mixers' scopes; read
by ``benchmark/reduce_booked.py``, which lists no scope). Counters, a layer: the ``counters``
collection's running totals ``expert_tokens [H]``, ``unrouted_tokens``,
``uncomputed_tokens`` (held assignments the layer did not compute: dropped
tokens, 0 or the layer is wrong) and ``dense_arm_calls`` (calls in which the
grouped product gave way to its dense arm). A collection and not sown
``intermediates``, because the round's program carries a model's
collections from step to step and back to the caller
(``SparseMoE._count``): what the benchmark reads was counted by the timed
rounds themselves.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from fedml_tpu.models.registry import register_model
from fedml_tpu.ops.gated_delta import gated_delta_rule
from fedml_tpu.parallel.expert_parallel import (
    gated_mlp,
    held_layout,
    held_expert_products,
    route_held,
)
from fedml_tpu.trainer.local import seq_softmax_ce

F32 = jnp.float32


def _normal(std: float = 0.02):
    return nn.initializers.normal(std)


def rms_norm(x, weight, eps: float):
    """Zero-centred RMSNorm, float32: ``x rsqrt(mean x^2 + eps) (1 + w)``."""
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (
        1.0 + weight.astype(F32))


def _mm(x, w, spec: str):
    """``x`` times ``w`` cast to ``x``'s dtype, float32 accumulation."""
    return jnp.einsum(spec, x, w.astype(x.dtype), preferred_element_type=F32)


def token_ce(logits, labels, pad_id: int = 0):
    """``trainer.local.seq_softmax_ce`` under the model's head scope: mean
    next-token cross-entropy over the non-pad positions of each sequence."""
    with jax.named_scope("fed.model.head"):
        return seq_softmax_ce(logits, labels, pad_id=pad_id)


class GatedDeltaNet(nn.Module):
    cfg: "Qwen3NextShapes"
    dtype: Any

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        hk, hv = c.linear_num_key_heads, c.linear_num_value_heads
        dk, dv = c.linear_key_head_dim, c.linear_value_head_dim
        kd, vd, d = hk * dk, hv * dv, c.hidden_size
        conv_dim = 2 * kd + vd
        taps = c.linear_conv_kernel_dim
        w_qkvz = self.param("in_proj_qkvz", _normal(), (d, conv_dim + vd))
        w_ba = self.param("in_proj_ba", _normal(), (d, 2 * hv))
        w_conv = self.param(
            "conv_weight", lambda k, s: jax.random.uniform(
                k, s, F32, -taps ** -0.5, taps ** -0.5), (taps, conv_dim))
        a_log = self.param(
            "A_log", lambda k, s: jnp.log(jax.random.uniform(
                k, s, F32, 1.0, 16.0)), (hv,))
        dt_bias = self.param("dt_bias", nn.initializers.ones, (hv,))
        w_norm = self.param("norm_weight", nn.initializers.ones, (dv,))
        w_out = self.param("out_proj", _normal(), (vd, d))

        b, t, _ = x.shape
        qkvz = _mm(x, w_qkvz, "btd,de->bte")
        ba = _mm(x, w_ba, "btd,de->bte")                   # float32 gates
        qkv, z = qkvz[..., :conv_dim], qkvz[..., conv_dim:]
        # causal depthwise convolution: y_t = sum_j w_j x_{t - taps + 1 + j}
        padded = jnp.pad(qkv, ((0, 0), (taps - 1, 0), (0, 0)))
        qkv = sum(padded[:, j:j + t] * w_conv[j].astype(F32)
                  for j in range(taps))
        qkv = jax.nn.silu(qkv)
        q = qkv[..., :kd].reshape(b, t, hk, dk)
        k = qkv[..., kd:2 * kd].reshape(b, t, hk, dk)
        v = qkv[..., 2 * kd:].reshape(b, t, hv, dv)
        q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) \
            * dk ** -0.5
        k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
        # each key head serves hv / hk value heads
        q, k = (jnp.repeat(a, hv // hk, axis=2) for a in (q, k))
        beta = jax.nn.sigmoid(ba[..., :hv])
        g = -jnp.exp(a_log.astype(F32)) * jax.nn.softplus(
            ba[..., hv:] + dt_bias.astype(F32))
        with jax.named_scope("fed.model.gdn.scan"):
            o = gated_delta_rule(
                q.astype(x.dtype), k.astype(x.dtype), v.astype(x.dtype), g,
                beta, chunk=c.linear_chunk_size).astype(F32)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                              + c.rms_norm_eps) * w_norm.astype(F32)
        o = o.reshape(b, t, vd) * jax.nn.silu(z)
        return _mm(o.astype(x.dtype), w_out, "bte,ed->btd")


def _rotary(x, theta: float, rot: int):
    """Half-rotation rotary positions on the first ``rot`` of the head's
    dimensions; ``x [B, T, H, D]`` float32."""
    t = x.shape[1]
    inv = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=F32) / rot)
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]    # [T, rot/2]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
    xr, rest = x[..., :rot], x[..., rot:]
    half = jnp.concatenate([-xr[..., rot // 2:], xr[..., :rot // 2]], -1)
    return jnp.concatenate([xr * cos + half * sin, rest], -1)


class GatedAttention(nn.Module):
    cfg: "Qwen3NextShapes"
    dtype: Any

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        hq, hkv, hd, d = (c.num_attention_heads, c.num_key_value_heads,
                          c.head_dim, c.hidden_size)
        w_q = self.param("q_proj", _normal(), (d, hq * 2 * hd))
        w_k = self.param("k_proj", _normal(), (d, hkv * hd))
        w_v = self.param("v_proj", _normal(), (d, hkv * hd))
        q_norm = self.param("q_norm", nn.initializers.zeros, (hd,))
        k_norm = self.param("k_norm", nn.initializers.zeros, (hd,))
        w_o = self.param("o_proj", _normal(), (hq * hd, d))

        b, t, _ = x.shape
        qg = _mm(x, w_q, "btd,de->bte").reshape(b, t, hq, 2 * hd)
        q, gate = qg[..., :hd], qg[..., hd:]
        k = _mm(x, w_k, "btd,de->bte").reshape(b, t, hkv, hd)
        v = _mm(x, w_v, "btd,de->bte").reshape(b, t, hkv, hd)
        rot = int(hd * c.partial_rotary_factor)
        q = _rotary(rms_norm(q, q_norm, c.rms_norm_eps), c.rope_theta, rot)
        k = _rotary(rms_norm(k, k_norm, c.rms_norm_eps), c.rope_theta, rot)
        # every key-value head serves hq / hkv query heads
        k, v = (jnp.repeat(a, hq // hkv, axis=2) for a in (k, v))
        q, k, v = (a.astype(x.dtype) for a in (q, k, v))
        with jax.named_scope("fed.model.attn.core"):
            if c.attention == "flash":
                from fedml_tpu.ops.flash_attention import flash_attention

                o = flash_attention(q, k, v, causal=True)
            else:
                s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                               preferred_element_type=F32) * hd ** -0.5
                s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -1e30)
                p = jax.nn.softmax(s, axis=-1).astype(x.dtype)
                o = jnp.einsum("bhqk,bkhd->bqhd", p, v,
                               preferred_element_type=F32)
        o = o.astype(F32) * jax.nn.sigmoid(gate)
        return _mm(o.reshape(b, t, hq * hd).astype(x.dtype), w_o,
                   "bte,ed->btd")


class SparseMoE(nn.Module):
    cfg: "Qwen3NextShapes"
    dtype: Any

    def _count(self, **counts):
        """Running totals in the ``counters`` collection: float32 (exact to
        2^24), zero at init, added to by every call that may change them.
        The round carries and averages every collection but ``params`` as
        it does batch statistics (``trainer.local.model_fns``), so a round's
        model holds the totals so far plus the cohort's weighted mean of
        what each client's local steps counted."""
        if not self.is_mutable_collection("counters"):
            return
        for name, value in counts.items():
            total = self.variable(
                "counters", name,
                lambda v=value: jnp.zeros(jnp.shape(v), F32))
            if not self.is_initializing():
                total.value = total.value + value.astype(F32)

    @nn.compact
    def __call__(self, x32):
        """``x32 [B, T, d]`` float32 (the norm's output): the router reads
        it as it is, the experts read it in the compute dtype."""
        c = self.cfg
        d, f, fs = (c.hidden_size, c.moe_intermediate_size,
                    c.shared_expert_intermediate_size)
        held = c.num_experts_held
        w_router = self.param("router", _normal(), (d, c.num_experts))
        w_gate_up = self.param("experts_gate_up", _normal(), (held, d, 2 * f))
        w_down = self.param("experts_down", _normal(), (held, f, d))
        ws_gate_up = self.param("shared_gate_up", _normal(), (d, 2 * fs))
        ws_down = self.param("shared_down", _normal(), (fs, d))
        ws_gate = self.param("shared_gate", _normal(), (d, 1))

        b, t, _ = x32.shape
        dt = self.dtype
        flat32 = x32.reshape(b * t, d)
        flat = flat32.astype(dt)
        tile, n_tiles = held_layout(
            b * t, c.num_experts_per_tok, c.num_experts, held)
        with jax.named_scope("fed.model.moe.route"):
            routing = route_held(
                flat32, w_router, held, c.first_expert_held,
                c.num_experts_per_tok, tile, n_tiles,
                renormalise=c.norm_topk_prob)
        # assignments the layer computes: the filled slots of the grouped
        # product, or all of them in its dense arm
        computed = jnp.where(
            routing.overflow, jnp.sum(routing.counts),
            jnp.sum((routing.slot_weight > 0).astype(jnp.int32)))
        self._count(expert_tokens=routing.counts,
                    unrouted_tokens=routing.unrouted,
                    uncomputed_tokens=jnp.sum(routing.counts) - computed,
                    dense_arm_calls=routing.overflow)
        with jax.named_scope("fed.model.moe.experts"):
            y = held_expert_products(
                flat, routing, w_gate_up.astype(dt), w_down.astype(dt),
                c.first_expert_held)
        with jax.named_scope("fed.model.moe.shared"):
            shared = gated_mlp(flat, ws_gate_up.astype(dt), ws_down.astype(dt),
                          "nd,df->nf", "nf,fd->nd")
            y = y + jax.nn.sigmoid(_mm(flat, ws_gate, "nd,de->ne")) * shared
        return y.reshape(b, t, d)


class Qwen3NextLayer(nn.Module):
    cfg: "Qwen3NextShapes"
    dtype: Any
    full_attention: bool

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        w_in = self.param("input_norm", nn.initializers.zeros,
                          (c.hidden_size,))
        w_post = self.param("post_norm", nn.initializers.zeros,
                            (c.hidden_size,))
        with jax.named_scope("fed.model.norm"):
            h = rms_norm(x, w_in, c.rms_norm_eps).astype(self.dtype)
        if self.full_attention:
            with jax.named_scope("fed.model.attn"):
                x = x + GatedAttention(c, self.dtype, name="mixer")(h)
        else:
            with jax.named_scope("fed.model.gdn"):
                x = x + GatedDeltaNet(c, self.dtype, name="mixer")(h)
        with jax.named_scope("fed.model.moe"):
            return x + SparseMoE(c, self.dtype, name="moe")(
                rms_norm(x, w_post, c.rms_norm_eps))


@dataclasses.dataclass(frozen=True)
class Qwen3NextShapes:
    """The source's ``config.json`` keys (its values as defaults), this
    shard's experts, and how the layers are computed."""

    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    full_attention_interval: int = 4
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    num_experts: int = 512
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    # this shard of the expert-parallel layer
    num_experts_held: int = 512
    first_expert_held: int = 0
    # how it is computed
    linear_chunk_size: int = 64
    attention: str = "flash"            # or "dense": masked softmax in XLA

    def __post_init__(self):
        if self.attention not in ("flash", "dense"):
            raise ValueError(f"attention={self.attention!r}: 'flash' or "
                             "'dense'")
        if not 0 < self.num_experts_held <= (
                self.num_experts - self.first_expert_held):
            raise ValueError(
                f"held experts {self.first_expert_held}.."
                f"{self.first_expert_held + self.num_experts_held - 1} are "
                f"not among the {self.num_experts} that exist")


class Qwen3Next(nn.Module):
    """``ids [B, T] int32 -> logits [B, T, vocab_size]`` float32."""

    cfg: Qwen3NextShapes = Qwen3NextShapes()
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, ids, train: bool = False):
        c = self.cfg
        if self.is_initializing():
            # no parameter's shape depends on the sequence's length
            ids = ids[:, :c.linear_chunk_size]
        with jax.named_scope("fed.model.head"):
            embedding = self.param("embed", _normal(),
                                   (c.vocab_size, c.hidden_size))
            x = jnp.take(embedding, ids, axis=0).astype(F32)
        # a layer's activations are computed again in the backward pass:
        # what is kept is the residual stream between layers
        layer = nn.remat(Qwen3NextLayer)
        for i in range(c.num_hidden_layers):
            x = layer(c, self.dtype,
                      (i + 1) % c.full_attention_interval == 0,
                      name=f"layer_{i}")(x)
        with jax.named_scope("fed.model.head"):
            w_norm = self.param("final_norm", nn.initializers.zeros,
                                (c.hidden_size,))
            w_head = self.param("lm_head", _normal(),
                                (c.hidden_size, c.vocab_size))
            h = rms_norm(x, w_norm, c.rms_norm_eps).astype(self.dtype)
            return _mm(h, w_head, "btd,dv->btv")


#: keys of the source's config.json that say nothing about a shape this
#: module computes (``intermediate_size`` is the width of dense layers, of
#: which ``mlp_only_layers: []`` leaves none)
IGNORED_SOURCE_KEYS = frozenset({
    "decoder_sparse_step", "hidden_act", "intermediate_size",
    "max_position_embeddings", "mlp_only_layers", "model_type",
    "rope_scaling", "tie_word_embeddings", "use_sliding_window"})


@register_model("qwen3_next")
def qwen3_next(dtype="float32", num_classes=None, **kwargs) -> Qwen3Next:
    """``Qwen3Next`` from the source's ``config.json`` keys plus this
    shard's (``num_experts_held``, ``first_expert_held``) and the compute
    choices (:class:`Qwen3NextShapes`). ``IGNORED_SOURCE_KEYS`` are accepted
    and dropped, so a configuration file can hold the source's dictionary
    as it is; ``num_classes`` (``create_model``'s argument) is the
    vocabulary where ``vocab_size`` is not given."""
    fields = {f.name for f in dataclasses.fields(Qwen3NextShapes)}
    unknown = sorted(set(kwargs) - fields - IGNORED_SOURCE_KEYS)
    if unknown:
        raise TypeError(f"qwen3_next: unknown keys {unknown}")
    kept = {k: v for k, v in kwargs.items() if k in fields}
    if num_classes is not None:
        kept.setdefault("vocab_size", int(num_classes))
    return Qwen3Next(Qwen3NextShapes(**kept), jnp.dtype(dtype))
