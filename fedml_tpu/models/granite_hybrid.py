"""Granite 4.0-H (``granitemoehybrid``): Mamba-2 layers with a no-position
grouped-query attention layer every so often, a shared gated MLP after every
mixer, and a low-rank (LoRA) pair beside every linear projection, as a flax
module for the federated adapter round (``algos/fedadapter.py``).

Source: https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/
config.json (the field names below are its keys). ``layer_types`` says which
mixer a layer has; the layers repeat with a period (Granite 4.0-H Micro: 4
periods of 5 Mamba-2, 1 attention, 4 Mamba-2), so the stack is ONE
``nn.scan`` over the periods' stacked parameters with a period's layers
written out inside it: the program traces 10 layers, not 40, and every
layer's activations are computed again in the backward pass (``nn.remat``).
``num_local_experts`` is 0: no router, only the shared MLP.

The equations, written out, are ``benchmark/reference_granite_hybrid.py``'s,
which imports nothing from here; the tests hold the two together. The base
parameters are created in ``base_dtype`` (bfloat16: 6.4 GB for the 3.19 G of
Granite 4.0-H Micro) and are what ``models/adapter.split_frozen`` freezes;
the ``lora_*`` pairs are float32 and are the federated net. The residual
stream, norms, ``Delta``, decays, the scan's state and the logits are
float32; products take ``dtype`` operands and accumulate in float32. This
repo's own choices: the initial values (``assumed`` in the benchmark's
configuration file) and ``adapter_b_std`` (LoRA's ``B`` starts at zero unless
a caller asks for a seeded small one, with which the first step's gradient of
``A`` is not zero).

Device scopes (``jax.named_scope``, read by the benchmark's reducers):
``fed.model.ssm`` (``.conv``, ``.scan``), ``fed.model.attn`` (``.core``),
``fed.model.mlp``, ``fed.model.lora`` (every low-rank pair's two products),
``fed.model.head`` (embedding, final norm, tied head; ``token_ce`` puts the
loss there too), ``fed.model.norm`` (a layer's input norm, the mixer's
residual add and the post norm: the residual stream between the branches)
and ``fed.model.stack`` around the scan over periods (the stacked weights'
slices and the loop's carry; a layer's operations keep their innermost
scope), both read by ``benchmark/reduce_booked.py``, which lists no scope.
Where a projection's shapes take ``ops/lora_linear.py``'s
kernel (Granite 4.0-H Micro at 1,024 tokens a client: ``input_linear``) the
forward's frozen product, the pair's second product, the sum, the gate and
the cast are ONE Mosaic call, booked under the layer it stands in
(``fed.model.mlp``): it is the frozen product with an epilogue. What stays
under ``fed.model.lora`` there is ``x A`` and the backward pass's products
with ``A`` and ``B``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from fedml_tpu.models.qwen3_next import token_ce  # noqa: F401  (the head-scoped loss)
from fedml_tpu.models.registry import register_model
from fedml_tpu.ops.lora_linear import gated, lora_linear
from fedml_tpu.ops.ssd import ssd_scan

F32 = jnp.float32


def rms_norm(x, weight, eps: float):
    """``w x rsqrt(mean x^2 + eps)``, float32."""
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * weight.astype(F32)


def group_rms_norm(x, weight, eps: float, groups: int = 1):
    """``w x rsqrt(mean x^2 + eps)`` with the mean taken over each of
    ``groups`` equal runs of the last axis (Mamba-2's gated norm: a norm a
    B/C group of heads), float32; :func:`rms_norm` at one group."""
    if groups == 1:
        return rms_norm(x, weight, eps)
    x = x.astype(F32).reshape(x.shape[:-1] + (groups, -1))
    x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return x.reshape(x.shape[:-2] + (-1,)) * weight.astype(F32)


def _mm(x, w, spec: str = "...d,de->...e"):
    """``x`` times ``w`` cast to ``x``'s dtype, float32 accumulation."""
    return jnp.einsum(spec, x, w.astype(x.dtype), preferred_element_type=F32)


def _narrowed(init):
    """``init`` drawn in float32 and then cast to the parameter's dtype. A
    draw made in bfloat16 has 8-bit uniforms behind it: ``normal(0.02)``
    then takes some 250 values between -2.9 and +2.5 sigma with a mean of
    -0.01 sigma, a common component in every matrix that the stated law
    (``assumed`` in the benchmark's configuration file) does not have."""
    return lambda key, shape, dtype: init(key, shape, F32).astype(dtype)


class _Layer(nn.Module):
    """What the mixers and the MLP share: base parameters in the base's
    dtype, and a linear projection with its low-rank pair."""

    cfg: "GraniteHybridShapes"
    dtype: Any

    def base(self, name, init, shape):
        return self.param(name, _narrowed(init), shape, self.cfg.base_dtype)

    def linear(self, name: str, x, out_dim: int, gate: bool = False):
        """``x W + (alpha / r) (x A) B`` in float32: ``W`` frozen, ``A`` and
        ``B`` the federated net (``lora_<name>_a``, ``lora_<name>_b``). With
        ``gate`` the result is ``silu(first half) * second half`` in ``x``'s
        dtype. One pass over the output where the shapes take the kernel
        (``ops/lora_linear.py``)."""
        c = self.cfg
        w = self.base(name, nn.initializers.normal(0.02),
                      (x.shape[-1], out_dim))
        if not c.adapter_rank:
            y = _mm(x, w)
            return gated(y).astype(x.dtype) if gate else y
        b_init = (nn.initializers.normal(c.adapter_b_std) if c.adapter_b_std
                  else nn.initializers.zeros)
        a = self.param(f"lora_{name}_a", nn.initializers.normal(0.02),
                       (x.shape[-1], c.adapter_rank))
        b = self.param(f"lora_{name}_b", b_init, (c.adapter_rank, out_dim))
        return lora_linear(x, w, a, b, c.adapter_alpha / c.adapter_rank,
                           gate=gate, out_dtype=x.dtype if gate else F32)


class Mamba2Mixer(_Layer):
    @nn.compact
    def __call__(self, x):
        c = self.cfg
        h, p, n, g = (c.mamba_n_heads, c.mamba_d_head, c.mamba_d_state,
                      c.mamba_n_groups)
        inner, taps = h * p, c.mamba_d_conv
        conv_dim = inner + 2 * g * n
        zxbcdt = self.linear("in_proj", x, inner + conv_dim + h)
        z, xbc, dt = (zxbcdt[..., :inner], zxbcdt[..., inner:inner + conv_dim],
                      zxbcdt[..., inner + conv_dim:])
        w_conv = self.base(
            "conv_weight", lambda k, s, d: jax.random.uniform(
                k, s, d, -taps ** -0.5, taps ** -0.5), (taps, conv_dim))
        b_conv = self.base("conv_bias", lambda k, s, d: jax.random.uniform(
            k, s, d, -taps ** -0.5, taps ** -0.5), (conv_dim,))
        a_log = self.base("A_log", lambda k, s, d: jnp.log(jax.random.uniform(
            k, s, d, 1.0, 16.0)), (h,))
        # softplus(dt_bias) is log-uniform in [0.001, 0.1]
        dt_bias = self.base(
            "dt_bias", lambda k, s, d: _inverse_softplus(jnp.exp(
                jax.random.uniform(k, s, d, jnp.log(1e-3), jnp.log(1e-1)))),
            (h,))
        skip = self.base("D", nn.initializers.ones, (h,))
        w_norm = self.base("norm_weight", nn.initializers.ones, (inner,))

        bsz, t, _ = x.shape
        with jax.named_scope("fed.model.ssm.conv"):
            # causal, depthwise: y_t = b + sum_j w_j x_{t - taps + 1 + j}
            padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
            xbc = jax.nn.silu(b_conv.astype(F32) + sum(
                padded[:, j:j + t] * w_conv[j].astype(F32)
                for j in range(taps)))
        xs = xbc[..., :inner].reshape(bsz, t, h, p)
        b = xbc[..., inner:inner + g * n].reshape(bsz, t, g, n)
        cc = xbc[..., inner + g * n:].reshape(bsz, t, g, n)
        delta = jax.nn.softplus(dt + dt_bias.astype(F32))
        with jax.named_scope("fed.model.ssm.scan"):
            y = ssd_scan(xs.astype(x.dtype), delta, -jnp.exp(a_log.astype(F32)),
                         b.astype(x.dtype), cc.astype(x.dtype),
                         chunk=c.mamba_chunk_size)
        y = y + skip.astype(F32)[:, None] * xs
        # gate first, then a norm over the inner channels of each B/C group
        # (Granite's one group: over all of them)
        y = group_rms_norm(y.reshape(bsz, t, inner) * jax.nn.silu(z), w_norm,
                           c.rms_norm_eps, g)
        return self.linear("out_proj", y.astype(x.dtype), c.hidden_size)


def _inverse_softplus(y):
    return y + jnp.log(-jnp.expm1(-y))


class Attention(_Layer):
    @nn.compact
    def __call__(self, x):
        c = self.cfg
        hq, hkv, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        bsz, t, _ = x.shape
        q = self.linear("q_proj", x, hq * hd).reshape(bsz, t, hq, hd)
        k = self.linear("k_proj", x, hkv * hd).reshape(bsz, t, hkv, hd)
        v = self.linear("v_proj", x, hkv * hd).reshape(bsz, t, hkv, hd)
        # no positions of any kind; a key-value head serves hq / hkv queries
        k, v = (jnp.repeat(a, hq // hkv, axis=2) for a in (k, v))
        q, k, v = (a.astype(x.dtype) for a in (q, k, v))
        with jax.named_scope("fed.model.attn.core"):
            if c.attention == "flash":
                from fedml_tpu.ops.flash_attention import flash_attention

                o = flash_attention(q, k, v, causal=True,
                                    scale=c.attention_multiplier)
            else:
                s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                               preferred_element_type=F32) \
                    * c.attention_multiplier
                s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -1e30)
                o = jnp.einsum("bhqk,bkhd->bqhd",
                               jax.nn.softmax(s, axis=-1).astype(x.dtype), v,
                               preferred_element_type=F32)
        return self.linear("o_proj", o.reshape(bsz, t, hq * hd).astype(
            x.dtype), c.hidden_size)


class SharedMLP(_Layer):
    @nn.compact
    def __call__(self, x):
        f = self.cfg.shared_intermediate_size
        hidden = self.linear("input_linear", x, 2 * f, gate=True)
        return self.linear("output_linear", hidden, self.cfg.hidden_size)


class GraniteHybridLayer(_Layer):
    kind: str = "mamba"

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        w_in = self.base("input_norm", nn.initializers.ones, (c.hidden_size,))
        w_post = self.base("post_norm", nn.initializers.ones, (c.hidden_size,))
        with jax.named_scope("fed.model.norm"):
            h = rms_norm(x, w_in, c.rms_norm_eps).astype(self.dtype)
        if self.kind == "attention":
            with jax.named_scope("fed.model.attn"):
                mixed = Attention(c, self.dtype, name="mixer")(h)
        else:
            with jax.named_scope("fed.model.ssm"):
                mixed = Mamba2Mixer(c, self.dtype, name="mixer")(h)
        with jax.named_scope("fed.model.norm"):
            x = x + c.residual_multiplier * mixed
            h = rms_norm(x, w_post, c.rms_norm_eps).astype(self.dtype)
        with jax.named_scope("fed.model.mlp"):
            return x + c.residual_multiplier * SharedMLP(
                c, self.dtype, name="mlp")(h)


class _Period(_Layer):
    """One period of the layer pattern: the body of the scan over periods."""

    @nn.compact
    def __call__(self, x, _):
        layer = nn.remat(GraniteHybridLayer)
        for j, kind in enumerate(self.cfg.period):
            x = layer(self.cfg, self.dtype, kind, name=f"layer_{j}")(x)
        return x, None


@dataclasses.dataclass(frozen=True)
class GraniteHybridShapes:
    """The source's ``config.json`` keys (Granite 4.0-H Micro's values as
    defaults), the adapters, and how the layers are computed."""

    vocab_size: int = 100352
    hidden_size: int = 2048
    num_hidden_layers: int = 40
    layer_types: Tuple[str, ...] = (
        ("mamba",) * 5 + ("attention",) + ("mamba",) * 4) * 4
    shared_intermediate_size: int = 8192
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_chunk_size: int = 256
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    attention_multiplier: float = 0.015625
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    logits_scaling: float = 8.0
    rms_norm_eps: float = 1e-5
    # the adapters: a pair beside every linear projection
    adapter_rank: int = 16
    adapter_alpha: float = 32.0
    adapter_b_std: float = 0.0
    # how it is held and computed
    base_dtype: Any = jnp.bfloat16
    attention: str = "flash"            # or "dense": masked softmax in XLA

    def __post_init__(self):
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(
                f"layer_types names {len(self.layer_types)} layers, "
                f"num_hidden_layers is {self.num_hidden_layers}")
        unknown = set(self.layer_types) - {"mamba", "attention"}
        if unknown:
            raise ValueError(f"layer_types: unknown kinds {sorted(unknown)}")
        if self.mamba_n_heads * self.mamba_d_head != (
                self.mamba_expand * self.hidden_size):
            raise ValueError(
                f"{self.mamba_n_heads} heads of {self.mamba_d_head} are not "
                f"mamba_expand {self.mamba_expand} x hidden_size "
                f"{self.hidden_size}")
        if self.attention not in ("flash", "dense"):
            raise ValueError(f"attention={self.attention!r}: 'flash' or "
                             "'dense'")

    @property
    def head_dim(self) -> int:
        """Granite's config has no key for it: the stream over the heads."""
        return self.hidden_size // self.num_attention_heads

    @property
    def period(self) -> Tuple[str, ...]:
        """The shortest prefix of ``layer_types`` that, repeated, is all of
        it."""
        kinds = self.layer_types
        for p in range(1, len(kinds) + 1):
            if len(kinds) % p == 0 and kinds == kinds[:p] * (len(kinds) // p):
                return kinds[:p]
        return kinds


class GraniteHybrid(nn.Module):
    """``ids [B, T] int32 -> logits [B, T, vocab_size]`` float32."""

    cfg: GraniteHybridShapes = GraniteHybridShapes()
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, ids, train: bool = False):
        c = self.cfg
        if self.is_initializing():
            # no parameter's shape depends on the sequence's length
            ids = ids[:, :8]
        with jax.named_scope("fed.model.head"):
            embedding = self.param(
                "embed", _narrowed(nn.initializers.normal(0.02)),
                (c.vocab_size, c.hidden_size), c.base_dtype)
            x = c.embedding_multiplier * jnp.take(
                embedding, ids, axis=0).astype(F32)
        periods = c.num_hidden_layers // len(c.period)
        # the period loop's own work: the stacked weights' slices, its carry
        # and what of its body names no scope (the residual stream's
        # gradient sums); a layer's operations keep their innermost scope
        with jax.named_scope("fed.model.stack"):
            x, _ = nn.scan(
                _Period, variable_axes={"params": 0},
                split_rngs={"params": True},
                length=periods)(c, self.dtype, name="periods")(x, None)
        with jax.named_scope("fed.model.head"):
            w_norm = self.param("final_norm", nn.initializers.ones,
                                (c.hidden_size,), c.base_dtype)
            h = rms_norm(x, w_norm, c.rms_norm_eps).astype(self.dtype)
            # tie_word_embeddings: the head is the embedding
            return _mm(h, embedding, "btd,vd->btv") / c.logits_scaling


#: keys of the source's config.json that say nothing about a shape this
#: module computes, or whose one supported value ``granite_hybrid`` checks
IGNORED_SOURCE_KEYS = frozenset({
    "hidden_act", "intermediate_size", "max_position_embeddings",
    "model_type", "normalization_function", "rope_scaling", "rope_theta"})
_REQUIRED = {"attention_bias": False, "mamba_conv_bias": True,
             "mamba_proj_bias": False, "num_experts_per_tok": 0,
             "num_local_experts": 0, "position_embedding_type": "nope",
             "tie_word_embeddings": True}


@register_model("granite_hybrid")
def granite_hybrid(dtype="float32", base_dtype="bfloat16", num_classes=None,
                   **kwargs) -> GraniteHybrid:
    """``GraniteHybrid`` from the source's ``config.json`` keys plus the
    adapters' (``adapter_rank``, ``adapter_alpha``, ``adapter_b_std``) and
    the compute choices (:class:`GraniteHybridShapes`). ``IGNORED_SOURCE_KEYS``
    are accepted and dropped, so a configuration file can hold the source's
    dictionary as it is; a key whose published value is the only one this
    module computes (no routed experts, no positions, a tied head, the
    biases) is refused at any other; ``num_classes`` (``create_model``'s
    argument) is the vocabulary where ``vocab_size`` is not given."""
    for key, only in _REQUIRED.items():
        if kwargs.pop(key, only) != only:
            raise NotImplementedError(
                f"granite_hybrid computes {key}={only!r} only")
    fields = {f.name for f in dataclasses.fields(GraniteHybridShapes)}
    unknown = sorted(set(kwargs) - fields - IGNORED_SOURCE_KEYS)
    if unknown:
        raise TypeError(f"granite_hybrid: unknown keys {unknown}")
    kept = {k: v for k, v in kwargs.items() if k in fields}
    if "layer_types" in kept:
        kept["layer_types"] = tuple(kept["layer_types"])
    if num_classes is not None:
        kept.setdefault("vocab_size", int(num_classes))
    return GraniteHybrid(
        GraniteHybridShapes(base_dtype=jnp.dtype(base_dtype), **kept),
        jnp.dtype(dtype))
