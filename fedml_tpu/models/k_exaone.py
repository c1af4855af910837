"""K-EXAONE (``exaone_moe``): window-128 and full causal attention mixed
(``LLLG``), a dense gated MLP in the first layer and, in every other, a
128-wide sigmoid router over gated experts of which THIS shard holds a
contiguous range, plus a shared expert; a low-rank (LoRA) pair beside every
linear projection, each held expert's three matrices included, as a flax
module for the federated adapter round (``algos/fedadapter.py``).

Source: https://huggingface.co/LGAI-EXAONE/K-EXAONE-236B-A23B/blob/main/
config.json (the field names below are its keys). What the config has no key
for is EXAONE 4.0's published block (arXiv:2507.11407; ``assumed`` in the
benchmark's configuration file): an RMSNorm on each branch's OUTPUT and none
on its input (``h = x + RMS(Attn(x))``, ``y = h + RMS(FFN(h))``), an RMSNorm
a head on ``q`` and ``k``, rotary positions on the window layers and NONE on
the full ones. The multi-token-prediction module is left out.

The equations, written out, are ``benchmark/reference_k_exaone.py``'s, which
imports nothing from here; the tests hold the two together. The base
parameters are created in float32 and narrowed to ``base_dtype`` (bfloat16)
and are what ``models/adapter.split_frozen`` freezes; the ``lora_*`` pairs are
float32 and are the federated net. The residual stream, norms, the router and
the logits are float32; products take ``dtype`` operands and accumulate in
float32. The layers differ (window or full, dense or sparse), so they are
written out, each under ``nn.remat``: what is kept for the backward pass is
the residual stream between layers.

This shard of the expert-parallel layer: ``num_experts`` is the router's
width, ``num_experts_held`` experts from ``first_expert_held`` are here; the
others' part of the sum is absent (``parallel/expert_parallel.py``). The
collection ``counters`` keeps, a sparse layer, the running totals
``expert_tokens [held]``, ``unrouted_tokens``, ``uncomputed_tokens``,
``further_passes`` (chunks of the held experts' grouped product after the
first) and ``grouped_rows`` (the rows of the chunks taken: ``expert_tokens``
over it is the product's fill), which the round carries and averages over the
cohort like batch statistics.

Device scopes (``jax.named_scope``, read by the benchmark's reducers):
``fed.model.attn.window`` and ``fed.model.attn.full`` (``.core`` around the
kernel alone), ``fed.model.mlp`` (the dense layer's), ``fed.model.moe``
(``.route``, ``.experts``, ``.shared``), ``fed.model.lora`` (every pair's two
products but the held experts', which are grouped by the experts' own
assignment and are part of ``fed.model.moe.experts``), ``fed.model.head``
(embedding, final norm, head; ``token_ce`` puts the loss there too),
``fed.model.norm`` (the second branch's output norm and its residual add;
the first branch's are the attention's; read by
``benchmark/reduce_booked.py``, which lists no scope).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from fedml_tpu.models.granite_hybrid import _mm, _narrowed, rms_norm
from fedml_tpu.models.qwen3_next import SparseMoE as _CountingMoE
from fedml_tpu.models.qwen3_next import token_ce  # noqa: F401  (the head-scoped loss)
from fedml_tpu.models.registry import register_model
from fedml_tpu.ops import lora_linear as ll
from fedml_tpu.parallel.expert_parallel import (
    ExpertPairs, chunk_rows, held_lora_products, route_sigmoid, sort_held)

F32 = jnp.float32
_NORMAL = nn.initializers.normal(0.02)
#: this repo's initial laws where normal(0, 0.02) and 1 would make a drawn
#: model unlike any trained one (``assumed`` in the benchmark's configuration
#: file; measured, PERF.md section 6, PR 34). At random weights a query
#: averages its window, so every token's attention output is nearly the same
#: vector; normalised to unit scale beside an embedding of scale 0.02 it IS
#: the residual stream, and a drawn router sends every token of a sequence to
#: the same few experts. So: the embedding at unit scale and the norms on the
#: branches' outputs at 0.05 (a token's own row leads the stream, as in a
#: trained model), and small first logits (the first loss is the prior's).
EMBED_STD, HEAD_STD, BRANCH_NORM = 1.0, 0.002, 0.05
#: the router's selection bias as drawn (the benchmark then balances it)
ROUTER_BIAS_STD = 0.05


class _Layer(nn.Module):
    """Base parameters in the base's dtype, and a linear projection with its
    low-rank pair."""

    cfg: "KExaoneShapes"
    dtype: Any

    def base(self, name, init, shape):
        return self.param(name, _narrowed(init), shape, self.cfg.base_dtype)

    def pair(self, name: str, shape_a, shape_b):
        c = self.cfg
        b_init = (nn.initializers.normal(c.adapter_b_std) if c.adapter_b_std
                  else nn.initializers.zeros)
        return (self.param(f"lora_{name}_a", _NORMAL, shape_a),
                self.param(f"lora_{name}_b", b_init, shape_b))

    def linear(self, name: str, x, out_dim: int, out_dtype=F32):
        """``x W + (alpha / r) (x A) B``: ``W`` frozen, ``A`` and ``B`` the
        federated net (``ops/lora_linear.py``)."""
        c = self.cfg
        w = self.base(name, _NORMAL, (x.shape[-1], out_dim))
        if not c.adapter_rank:
            return _mm(x, w).astype(out_dtype)
        a, b = self.pair(name, (x.shape[-1], c.adapter_rank),
                         (c.adapter_rank, out_dim))
        return ll.lora_linear(x, w, a, b, c.adapter_alpha / c.adapter_rank,
                              out_dtype=out_dtype)

    def gated_mlp(self, x, width: int):
        """``W_down (silu(W_gate x) * W_up x)``, a pair beside each."""
        gate = self.linear("gate_proj", x, width, x.dtype).astype(F32)
        up = self.linear("up_proj", x, width, x.dtype).astype(F32)
        return self.linear("down_proj", (jax.nn.silu(gate) * up).astype(
            x.dtype), self.cfg.hidden_size)


def rotary(x, theta: float):
    """Rotary positions ``0 .. T - 1`` on the whole head of ``x [B, T, H,
    D]`` (float32), the halves paired as the source's ``rotate_half``."""
    t, d = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    angle = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]      # [T, D / 2]
    cos, sin = (jnp.concatenate([f(angle)] * 2, -1)[None, :, None, :]
                for f in (jnp.cos, jnp.sin))
    turned = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + turned * sin


class Attention(_Layer):
    window: int = 0         # 0: full causal attention, no positions

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        hq, hkv, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        bsz, t, _ = x.shape
        q = self.linear("q_proj", x, hq * hd).reshape(bsz, t, hq, hd)
        k = self.linear("k_proj", x, hkv * hd).reshape(bsz, t, hkv, hd)
        v = self.linear("v_proj", x, hkv * hd).reshape(bsz, t, hkv, hd)
        q = rms_norm(q, self.base("q_norm", nn.initializers.ones, (hd,)),
                     c.rms_norm_eps)
        k = rms_norm(k, self.base("k_norm", nn.initializers.ones, (hd,)),
                     c.rms_norm_eps)
        if self.window:
            q, k = rotary(q, c.rope_theta), rotary(k, c.rope_theta)
        # a key-value head serves hq / hkv queries. The window layers' flash
        # path hands the band kernel k and v with their hkv heads; the
        # full-attention layer's streaming kernel reads a head a grid row and
        # the einsum arm contracts head by head: those two repeat them
        if not (self.window and c.attention == "flash"):
            k, v = (jnp.repeat(a, hq // hkv, axis=2) for a in (k, v))
        q, k, v = (a.astype(x.dtype) for a in (q, k, v))
        with jax.named_scope(self.scope_name + ".core"):
            if c.attention == "flash":
                from fedml_tpu.ops.flash_attention import flash_attention

                o = flash_attention(q, k, v, causal=True,
                                    window=self.window or None)
            else:
                s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                               preferred_element_type=F32) * hd ** -0.5
                back = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
                seen = (back >= 0) & (back < (self.window or t))
                o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(
                    jnp.where(seen, s, -1e30), axis=-1).astype(x.dtype), v,
                    preferred_element_type=F32)
        return self.linear("o_proj", o.reshape(bsz, t, hq * hd).astype(
            x.dtype), c.hidden_size)

    @property
    def scope_name(self) -> str:
        return "fed.model.attn." + ("window" if self.window else "full")


class GatedMLP(_Layer):
    """The dense layer's MLP, or a shared expert."""

    width: int = 0

    @nn.compact
    def __call__(self, x):
        return self.gated_mlp(x, self.width)


class SparseMoE(_Layer):
    """This shard's part of the routed layer, and the shared expert."""

    #: running float32 totals in the ``counters`` collection, one rule for
    #: both expert layers
    _count = _CountingMoE._count

    @nn.compact
    def __call__(self, x32):
        """``x32 [B, T, d]`` float32 (the residual stream): the router reads
        it as it is, the experts read it in the compute dtype."""
        c = self.cfg
        d, f, held, r = (c.hidden_size, c.moe_intermediate_size,
                         c.num_experts_held, c.adapter_rank)
        w_router = self.base("router", _NORMAL, (d, c.num_experts))
        bias = self.base("router_bias", nn.initializers.normal(
            ROUTER_BIAS_STD), (c.num_experts,))
        w_gate_up = self.base("experts_gate_up", _NORMAL, (held, d, 2 * f))
        w_down = self.base("experts_down", _NORMAL, (held, f, d))
        shapes = {"gate": (d, f), "up": (d, f), "down": (f, d)}
        if r:
            pairs = ExpertPairs(*(m for name, (i, o) in shapes.items()
                                  for m in self.pair(f"experts_{name}",
                                                     (held, i, r),
                                                     (held, r, o))))
        else:       # no adapters: pairs of rank 1 that add nothing
            pairs = ExpertPairs(*(jnp.zeros(s, F32)
                                  for i, o in shapes.values()
                                  for s in ((held, i, 1), (held, 1, o))))
        b, t, _ = x32.shape
        flat32 = x32.reshape(b * t, d)
        flat = flat32.astype(self.dtype)
        with jax.named_scope("fed.model.moe.route"):
            idx, weight = route_sigmoid(
                flat32, w_router, bias, c.num_experts_per_tok,
                c.routed_scaling_factor, c.norm_topk_prob)
            assigned = sort_held(idx, held, c.first_expert_held)
        rows = chunk_rows(b * t, c.num_experts_per_tok, c.num_experts, held)
        if r:       # pairs that the grouped product computes itself
            for i, o in shapes.values():
                ll.note(rows, i, o, r, False, experts=held)
        with jax.named_scope("fed.model.moe.experts"):
            y, computed, further = held_lora_products(
                flat, weight, assigned, w_gate_up, w_down, pairs,
                c.adapter_alpha / max(r, 1), rows)
        self._count(expert_tokens=assigned.counts,
                    unrouted_tokens=assigned.unrouted,
                    uncomputed_tokens=jnp.sum(assigned.counts) - computed,
                    further_passes=further, grouped_rows=rows * (1 + further))
        with jax.named_scope("fed.model.moe.shared"):
            y = y + GatedMLP(c, self.dtype, c.moe_intermediate_size,
                             name="shared")(flat)
        return y.reshape(b, t, d)


class KExaoneLayer(_Layer):
    window: int = 0
    sparse: bool = True

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        small = nn.initializers.constant(BRANCH_NORM)
        w_attn = self.base("post_attn_norm", small, (c.hidden_size,))
        w_ffn = self.base("post_ffn_norm", small, (c.hidden_size,))
        attn = Attention(c, self.dtype, self.window, name="attn")
        with jax.named_scope(attn.scope_name):
            x = x + rms_norm(attn(x.astype(self.dtype)), w_attn,
                             c.rms_norm_eps)
        if self.sparse:
            with jax.named_scope("fed.model.moe"):
                branch = SparseMoE(c, self.dtype, name="moe")(x)
        else:
            with jax.named_scope("fed.model.mlp"):
                branch = GatedMLP(c, self.dtype, c.intermediate_size,
                                  name="mlp")(x.astype(self.dtype))
        with jax.named_scope("fed.model.norm"):
            return x + rms_norm(branch, w_ffn, c.rms_norm_eps)


@dataclasses.dataclass(frozen=True)
class KExaoneShapes:
    """The source's ``config.json`` keys (K-EXAONE-236B-A23B's values as
    defaults), this shard's experts, the adapters, and how the layers are
    computed. ``layer_types``, ``mlp_layer_types`` may be longer than
    ``num_hidden_layers`` (the source's lists, a cut stack): the first
    ``num_hidden_layers`` entries are the layers."""

    vocab_size: int = 153600
    hidden_size: int = 6144
    num_hidden_layers: int = 48
    layer_types: Tuple[str, ...] = (
        ("sliding_attention",) * 3 + ("full_attention",)) * 12
    mlp_layer_types: Tuple[str, ...] = ("dense",) + ("sparse",) * 47
    sliding_window: int = 128
    num_attention_heads: int = 64
    num_key_value_heads: int = 8
    head_dim: int = 128
    rope_theta: float = 1e6
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_experts: int = 128
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    rms_norm_eps: float = 1e-5
    # this shard of the expert-parallel layer
    num_experts_held: int = 128
    first_expert_held: int = 0
    # the adapters: a pair beside every linear projection
    adapter_rank: int = 16
    adapter_alpha: float = 32.0
    adapter_b_std: float = 0.0
    # how it is held and computed
    base_dtype: Any = jnp.bfloat16
    attention: str = "flash"            # or "dense": masked softmax in XLA

    def __post_init__(self):
        n = self.num_hidden_layers
        for name, known in (("layer_types", {"sliding_attention",
                                             "full_attention"}),
                            ("mlp_layer_types", {"dense", "sparse"})):
            kinds = getattr(self, name)
            if len(kinds) < n:
                raise ValueError(f"{name} names {len(kinds)} layers, "
                                 f"num_hidden_layers is {n}")
            if set(kinds) - known:
                raise ValueError(
                    f"{name}: unknown kinds {sorted(set(kinds) - known)}")
        if self.attention not in ("flash", "dense"):
            raise ValueError(f"attention={self.attention!r}: 'flash' or "
                             "'dense'")
        if not 0 < self.num_experts_held <= (
                self.num_experts - self.first_expert_held):
            raise ValueError(
                f"held experts {self.first_expert_held}.."
                f"{self.first_expert_held + self.num_experts_held - 1} are "
                f"not among the {self.num_experts} that exist")


class KExaone(nn.Module):
    """``ids [B, T] int32 -> logits [B, T, vocab_size]`` float32."""

    cfg: KExaoneShapes = KExaoneShapes()
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, ids, train: bool = False):
        c = self.cfg
        if self.is_initializing():
            # no parameter's shape depends on the sequence's length
            ids = ids[:, :8]
        with jax.named_scope("fed.model.head"):
            embedding = self.param(
                "embed", _narrowed(nn.initializers.normal(EMBED_STD)),
                (c.vocab_size, c.hidden_size), c.base_dtype)
            x = jnp.take(embedding, ids, axis=0).astype(F32)
        layer = nn.remat(KExaoneLayer)
        for i in range(c.num_hidden_layers):
            window = (c.sliding_window
                      if c.layer_types[i] == "sliding_attention" else 0)
            x = layer(c, self.dtype, window,
                      c.mlp_layer_types[i] == "sparse", name=f"layer_{i}")(x)
        with jax.named_scope("fed.model.head"):
            w_norm = self.param("final_norm", nn.initializers.ones,
                                (c.hidden_size,), c.base_dtype)
            w_head = self.param(
                "lm_head", _narrowed(nn.initializers.normal(HEAD_STD)),
                (c.hidden_size, c.vocab_size), c.base_dtype)
            h = rms_norm(x, w_norm, c.rms_norm_eps).astype(self.dtype)
            return _mm(h, w_head, "btd,dv->btv")


#: keys of the source's config.json that say nothing about a shape this
#: module computes, or that another key this module reads repeats
#: (``sliding_windows`` and ``sliding_window_pattern`` repeat ``layer_types``
#: and ``sliding_window``; ``first_k_dense_replace`` repeats
#: ``mlp_layer_types``; the ``mtp_*`` keys size the left-out module)
IGNORED_SOURCE_KEYS = frozenset({
    "first_k_dense_replace", "max_position_embeddings", "model_type",
    "mtp_layer_types", "mtp_sliding_windows", "num_nextn_predict_layers",
    "sliding_window_pattern", "sliding_windows"})
_REQUIRED = {"hidden_act": "silu", "n_group": 1, "topk_group": 1,
             "num_shared_experts": 1, "scoring_func": "sigmoid",
             "tie_word_embeddings": False}


@register_model("k_exaone")
def k_exaone(dtype="float32", base_dtype="bfloat16", num_classes=None,
             **kwargs) -> KExaone:
    """``KExaone`` from the source's ``config.json`` keys plus this shard's
    (``num_experts_held``, ``first_expert_held``), the adapters'
    (``adapter_rank``, ``adapter_alpha``, ``adapter_b_std``) and the compute
    choices (:class:`KExaoneShapes`). ``IGNORED_SOURCE_KEYS`` are accepted and
    dropped, so a configuration file can hold the source's dictionary as it
    is; a key whose published value is the only one this module computes (a
    sigmoid router with one group, one shared expert, an untied head, SiLU)
    is refused at any other; ``rope_parameters`` gives ``rope_theta`` (the
    default rotary type only); ``num_classes`` (``create_model``'s argument)
    is the vocabulary where ``vocab_size`` is not given."""
    for key, only in _REQUIRED.items():
        if kwargs.pop(key, only) != only:
            raise NotImplementedError(f"k_exaone computes {key}={only!r} only")
    rope = kwargs.pop("rope_parameters", None)
    if rope is not None:
        if rope.get("rope_type", "default") != "default":
            raise NotImplementedError(
                "k_exaone computes the default rotary type only")
        kwargs.setdefault("rope_theta", float(rope["rope_theta"]))
    fields = {f.name for f in dataclasses.fields(KExaoneShapes)}
    unknown = sorted(set(kwargs) - fields - IGNORED_SOURCE_KEYS)
    if unknown:
        raise TypeError(f"k_exaone: unknown keys {unknown}")
    kept = {k: v for k, v in kwargs.items() if k in fields}
    for name in ("layer_types", "mlp_layer_types"):
        if name in kept:
            kept[name] = tuple(kept[name])
    if num_classes is not None:
        kept.setdefault("vocab_size", int(num_classes))
    kept.setdefault("num_experts_held", kept.get(
        "num_experts", KExaoneShapes.num_experts) - kept.get(
            "first_expert_held", 0))
    return KExaone(KExaoneShapes(base_dtype=jnp.dtype(base_dtype), **kept),
                   jnp.dtype(dtype))
