"""MiniMax-M3: learned block-sparse attention (MSA) in every layer, a dense
SwiGLU MLP with gpt-oss's clamps in the leading layers and, in the others, a
128-wide sigmoid router over clamped-SwiGLU experts of which THIS shard holds
a contiguous range, plus a shared expert; a low-rank (LoRA) pair beside every
linear projection but the indexer's, each held expert's three matrices
included, as a flax module for the federated adapter round
(``algos/fedadapter.py``).

Source: https://huggingface.co/MiniMaxAI/MiniMax-M3/blob/main/config.json
(the field names below are its keys). The layer, for ``x [T, d]``, with
``n(z) = z / sqrt(mean z^2 + eps) (1 + w)`` (``use_gemma_norm``):

- pre-norm blocks: ``h = x + Attn(n(x))``, ``y = h + FFN(n(h))``; a final
  norm before the untied head;
- attention, ``a = n(x)``: ``q = N_q(a W_q)`` (64 heads of 128), ``k = N_k(a
  W_k)`` (4 groups of 128), ``v = a W_v``; ``N_q``, ``N_k`` a Gemma norm over
  each head's dims, one weight for q and one for k (``qk_norm_type:
  per_head``); rotate-half rotary positions on each head's first
  ``rotary_dim`` dims, ``rope_theta``;
- the indexer, a group ``g`` and its ``msa_index_heads`` heads ``i``: ``u =
  (a W_I)[g, i]`` (``W_I`` frozen, no pair), the pooled block keys ``kbar[b,
  g] = max`` over block ``b``'s ``msa_block_size`` tokens of ``k[., g]``
  elementwise (normed, before rotary), the score ``sum_i relu(u . kbar)``;
- the selection: with ``c`` the token's own block, block 0, block ``c`` and
  the ``msa_topk`` best of blocks ``1 .. c - 1`` (all of them where there are
  no more; a tie goes to the lower block). An integer choice: no gradient
  flows through it. Scores and top-k are float32 from the base as it is held
  (the router's rule), so a choice that differs from a float32 reference is
  a near tie;
- the core: query head ``n`` belongs to group ``n // (64 / 4)`` and attends
  causally to the keys of its group's chosen blocks
  (``ops/block_sparse_attention.py``); ``o W_o``;
- FFN: ``swiglu_oai(z W_gate, z W_up) W_down`` in the dense MLP, the shared
  expert and the routed experts (``parallel.expert_parallel.swiglu_oai``);
- router: ``p = sigmoid(n(h) W_r)`` over all ``num_local_experts``, the
  ``num_experts_per_tok`` best of ``p + bias``, weights ``routed_scaling_factor
  p / sum p`` over the chosen; the shared expert added unweighted.

What the config has no key for is ``assumed`` in the benchmark's
configuration file: the block placement, the indexer's form and widths
(``msa_*`` below), which dims rotate, the router's normalisation.
The multi-token-prediction modules and the vision tower are left out.

The equations, written out, are ``benchmark/reference_minimax_m3.py``'s, which
imports nothing from here; the tests hold the two together. The base is
created in float32 and narrowed to ``base_dtype`` (bfloat16); the ``lora_*``
pairs are float32 and are the federated net. The residual stream, norms,
the indexer's and the router's scores and the logits are float32; products
take ``dtype`` operands and accumulate in float32. Each layer is under
``nn.remat``.

This shard of the expert-parallel layer: ``num_local_experts`` is the
router's width, ``num_experts_held`` experts from ``first_expert_held`` are
here (``parallel/expert_parallel.py``). The collection ``counters`` keeps, a
layer, the running totals ``msa_pairs_selected``, ``msa_pairs_computed``,
``msa_blocks_fetched`` (``ops/block_sparse_attention.counts``) and, an
expert layer, ``expert_tokens [held]``, ``unrouted_tokens``,
``uncomputed_tokens``, ``further_passes`` and ``grouped_rows``, which the
round carries and averages over the cohort like batch statistics. Where the
caller makes ``intermediates`` mutable, a layer sows its choices: the
attention's blocks (``chosen``) and the router's experts (``experts``).

Device scopes (``jax.named_scope``): ``fed.model.attn.msa`` (the input norm,
projections, q/k norms, rotary, the indexer, the selection and the core),
``.select`` (the indexer's projection, pooling, scores, top-k and the
counters) and ``.core`` (the kernels and their visiting lists);
``fed.model.mlp`` (the dense layer's), ``fed.model.moe`` (``.route``,
``.experts``, ``.shared``), ``fed.model.norm`` (the FFN branch's input norm
and its residual add), ``fed.model.lora`` (every pair's two products but
the held experts'), ``fed.model.head`` (embedding, final norm, head, loss).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from fedml_tpu.models.granite_hybrid import _mm, _narrowed, rms_norm
from fedml_tpu.models.k_exaone import _Layer
from fedml_tpu.models.qwen3_next import SparseMoE as _CountingMoE
from fedml_tpu.models.registry import register_model
from fedml_tpu.ops import block_sparse_attention as bsa
from fedml_tpu.ops import lora_linear as ll
from fedml_tpu.parallel.expert_parallel import (
    OAI_ALPHA, OAI_LIMIT, ExpertPairs, chunk_rows, held_lora_products,
    route_sigmoid, sort_held, swiglu_oai)

F32 = jnp.float32
_NORMAL = nn.initializers.normal(0.02)
#: the embedding at unit scale and small first logits (the first loss is the
#: prior's): the benchmark's base law (``benchmark/reference_minimax_m3.
#: init_base``), which draws every tensor itself
EMBED_STD, HEAD_STD = 1.0, 0.002
#: the router's selection bias as drawn (the benchmark then balances it)
ROUTER_BIAS_STD = 0.05
_HIGHEST = jax.lax.Precision.HIGHEST


def gemma_norm(x, w, eps: float):
    """``x / sqrt(mean x^2 + eps) (1 + w)``, float32."""
    return rms_norm(x, 1.0 + w.astype(F32), eps)


def partial_rotary(x, theta: float, dims: int):
    """Rotary positions ``0 .. T - 1`` on the first ``dims`` channels of each
    head of ``x [B, T, H, D]`` (float32), the halves of those paired as the
    source's ``rotate_half``; the other channels pass unturned."""
    t = x.shape[1]
    inv = theta ** (-jnp.arange(0, dims, 2, dtype=F32) / dims)
    angle = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]   # [T, dims/2]
    cos, sin = (jnp.concatenate([f(angle)] * 2, -1)[None, :, None, :]
                for f in (jnp.cos, jnp.sin))
    turn, rest = x[..., :dims], x[..., dims:]
    half = dims // 2
    turned = jnp.concatenate([-turn[..., half:], turn[..., :half]], -1)
    return jnp.concatenate([turn * cos + turned * sin, rest], -1)


def select_blocks(u, k, block: int, topk: int):
    """``chosen [B, T, G, 2 + topk] int32`` (``-1`` in an empty slot): block
    0, the token's own block (empty in block 0) and the ``topk`` best of
    blocks ``1 .. own - 1`` by ``sum_i relu(u[t, g, i] . kbar[b, g])``.
    ``u [B, T, G, I, D]`` the indexer's queries and ``k [B, T, G, D]`` the
    normed keys, float32; ``kbar`` their elementwise maximum a block."""
    b, t, g, _ = k.shape
    n = t // block
    kbar = jnp.max(k.reshape(b, n, block, g, -1), axis=2)        # [B, n, G, D]
    score = jnp.sum(jax.nn.relu(jnp.einsum(
        "btgid,bngd->btgin", u, kbar, precision=_HIGHEST)), axis=3)
    own = jnp.arange(t, dtype=jnp.int32) // block
    before = ((jnp.arange(n)[None, :] >= 1)
              & (jnp.arange(n)[None, :] < own[:, None]))         # [T, n]
    # every score is a sum of relus, at least 0: -1 marks a block that is
    # no candidate, and top_k takes the lower block of a tie
    candidates = jnp.where(before[None, :, None, :], score, -1.0)
    if n < topk:    # fewer blocks than choices: the rest are none
        candidates = jnp.pad(candidates, ((0, 0),) * 3 + ((0, topk - n),),
                             constant_values=-1.0)
    value, best = jax.lax.top_k(candidates, topk)
    best = jnp.where(value >= 0, best, -1).astype(jnp.int32)
    fixed = jnp.stack([jnp.zeros_like(own), jnp.where(own > 0, own, -1)], -1)
    return jnp.concatenate(
        [jnp.broadcast_to(fixed[None, :, None, :], (b, t, g, 2)), best], -1)


class _OaiLayer(_Layer):
    def oai_mlp(self, x, width: int):
        """``W_down swiglu_oai(W_gate x, W_up x)``, a pair beside each."""
        gate = self.linear("gate_proj", x, width, x.dtype).astype(F32)
        up = self.linear("up_proj", x, width, x.dtype).astype(F32)
        return self.linear("down_proj", swiglu_oai(gate, up).astype(x.dtype),
                           self.cfg.hidden_size)


class OaiMLP(_OaiLayer):
    """The dense layer's MLP, or a shared expert."""

    width: int = 0

    @nn.compact
    def __call__(self, x):
        return self.oai_mlp(x, self.width)


class MSAttention(_OaiLayer):
    """Attention over the blocks an indexer chose, token by token."""

    _count = _CountingMoE._count

    @nn.compact
    def __call__(self, a32):
        """``a32 [B, T, d]`` float32: the block's input norm's output."""
        c = self.cfg
        hq, hkv, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        bsz, t, d = a32.shape
        a = a32.astype(self.dtype)
        q = self.linear("q_proj", a, hq * hd).reshape(bsz, t, hq, hd)
        w_k = self.base("k_proj", _NORMAL, (d, hkv * hd))
        k_pair = (self.pair("k_proj", (d, c.adapter_rank),
                            (c.adapter_rank, hkv * hd))
                  if c.adapter_rank else None)
        k = (ll.lora_linear(a, w_k, *k_pair, c.adapter_alpha / c.adapter_rank,
                            out_dtype=F32) if k_pair
             else _mm(a, w_k)).reshape(bsz, t, hkv, hd)
        v = self.linear("v_proj", a, hkv * hd).reshape(bsz, t, hkv, hd)
        w_qn = self.base("q_norm", nn.initializers.zeros, (hd,))
        w_kn = self.base("k_norm", nn.initializers.zeros, (hd,))
        q = gemma_norm(q, w_qn, c.rms_norm_eps)
        k = gemma_norm(k, w_kn, c.rms_norm_eps)
        w_index = self.base("index_proj", _NORMAL,
                            (d, hkv * c.msa_index_heads * hd))
        with jax.named_scope("fed.model.attn.msa.select"):
            # the indexer reads its queries AND the keys it pools in float32
            # at the highest precision from the base as it is held (the
            # router's rule): where it chooses otherwise than a float32
            # reference, the two scores were within the stream's rounding
            exact = jax.lax.stop_gradient(jnp.dot(
                a32, w_k.astype(F32), precision=_HIGHEST) + (jnp.dot(
                    jnp.dot(a32, k_pair[0], precision=_HIGHEST), k_pair[1],
                    precision=_HIGHEST) * (c.adapter_alpha / c.adapter_rank)
                    if k_pair else 0.0))
            u = jnp.dot(a32, w_index.astype(F32), precision=_HIGHEST)
            chosen = select_blocks(
                u.reshape(bsz, t, hkv, c.msa_index_heads, hd),
                gemma_norm(exact.reshape(bsz, t, hkv, hd), w_kn,
                           c.rms_norm_eps), c.msa_block_size, c.msa_topk)
            self._count(**bsa.counts(jnp.swapaxes(chosen, 1, 2),
                                     c.msa_block_size, hq // hkv))
            self.sow("intermediates", "chosen", chosen)
        q = partial_rotary(q, c.rope_theta, c.rotary_dim)
        k = partial_rotary(k, c.rope_theta, c.rotary_dim)
        q, k, v = (x.astype(self.dtype) for x in (q, k, v))
        with jax.named_scope("fed.model.attn.msa.core"):
            o = bsa.block_sparse_attention(q, k, v, chosen, c.msa_block_size)
        return self.linear("o_proj", o.reshape(bsz, t, hq * hd).astype(
            self.dtype), d)


class SparseMoE(_OaiLayer):
    """This shard's part of the routed layer, and the shared expert."""

    _count = _CountingMoE._count

    @nn.compact
    def __call__(self, x32):
        """``x32 [B, T, d]`` float32 (the norm's output): the router reads it
        as it is, the experts read it in the compute dtype."""
        c = self.cfg
        d, f, held, r = (c.hidden_size, c.intermediate_size,
                         c.num_experts_held, c.adapter_rank)
        experts = c.num_local_experts
        w_router = self.base("router", _NORMAL, (d, experts))
        bias = self.base("router_bias", nn.initializers.normal(
            ROUTER_BIAS_STD), (experts,))
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
                c.routed_scaling_factor)
            assigned = sort_held(idx, held, c.first_expert_held)
            self.sow("intermediates", "experts", idx)
        rows = chunk_rows(b * t, c.num_experts_per_tok, experts, held)
        if r:       # pairs that the grouped product computes itself
            for i, o in shapes.values():
                ll.note(rows, i, o, r, False, experts=held)
        with jax.named_scope("fed.model.moe.experts"):
            y, computed, further = held_lora_products(
                flat, weight, assigned, w_gate_up, w_down, pairs,
                c.adapter_alpha / max(r, 1), rows, form="swigluoai")
        self._count(expert_tokens=assigned.counts,
                    unrouted_tokens=assigned.unrouted,
                    uncomputed_tokens=jnp.sum(assigned.counts) - computed,
                    further_passes=further, grouped_rows=rows * (1 + further))
        with jax.named_scope("fed.model.moe.shared"):
            y = y + OaiMLP(c, self.dtype, c.shared_intermediate_size,
                           name="shared")(flat)
        return y.reshape(b, t, d)


class MiniMaxM3Layer(_OaiLayer):
    sparse: bool = True

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        zeros = nn.initializers.zeros
        with jax.named_scope("fed.model.attn.msa"):
            a = gemma_norm(x, self.base("input_norm", zeros,
                                        (c.hidden_size,)), c.rms_norm_eps)
            x = x + MSAttention(c, self.dtype, name="attn")(a)
        with jax.named_scope("fed.model.norm"):
            a = gemma_norm(x, self.base("ffn_norm", zeros, (c.hidden_size,)),
                           c.rms_norm_eps)
        if self.sparse:
            with jax.named_scope("fed.model.moe"):
                branch = SparseMoE(c, self.dtype, name="moe")(a)
        else:
            with jax.named_scope("fed.model.mlp"):
                branch = OaiMLP(c, self.dtype, c.dense_intermediate_size,
                                name="mlp")(a.astype(self.dtype))
        with jax.named_scope("fed.model.norm"):
            return x + branch


@dataclasses.dataclass(frozen=True)
class MiniMaxM3Shapes:
    """The source's ``config.json`` keys (MiniMax-M3's values as defaults),
    the indexer's assumed sizes, this shard's experts, the adapters, and the
    base's dtype. ``moe_layer_freq`` may be longer than
    ``num_hidden_layers``: its first ``num_hidden_layers`` entries are the
    layers (0 dense, 1 routed experts)."""

    vocab_size: int = 200064
    hidden_size: int = 6144
    num_hidden_layers: int = 60
    moe_layer_freq: Tuple[int, ...] = (0,) * 3 + (1,) * 57
    num_attention_heads: int = 64
    num_key_value_heads: int = 4
    head_dim: int = 128
    rms_norm_eps: float = 1e-6
    rope_theta: float = 5e6
    rotary_dim: int = 64
    intermediate_size: int = 3072
    dense_intermediate_size: int = 12288
    shared_intermediate_size: int = 3072
    num_local_experts: int = 128
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 2.0
    # the indexer and the selection (assumed: the config has no key for them)
    msa_index_heads: int = 4
    msa_block_size: int = 128
    msa_topk: int = 16
    # this shard of the expert-parallel layer
    num_experts_held: int = 128
    first_expert_held: int = 0
    # the adapters: a pair beside every linear projection but the indexer's
    adapter_rank: int = 16
    adapter_alpha: float = 32.0
    adapter_b_std: float = 0.0
    # how it is held
    base_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        n = self.num_hidden_layers
        kinds = self.moe_layer_freq
        if len(kinds) < n or set(kinds[:n]) - {0, 1}:
            raise ValueError(f"moe_layer_freq {kinds!r}: 0 or 1 for each of "
                             f"the {n} layers")
        if self.rotary_dim % 2 or not 0 < self.rotary_dim <= self.head_dim:
            raise ValueError(f"rotary_dim {self.rotary_dim} of a head of "
                             f"{self.head_dim}")
        if not 0 < self.num_experts_held <= (
                self.num_local_experts - self.first_expert_held):
            raise ValueError(
                f"held experts {self.first_expert_held}.."
                f"{self.first_expert_held + self.num_experts_held - 1} are "
                f"not among the {self.num_local_experts} that exist")


class MiniMaxM3(nn.Module):
    """``ids [B, T] int32 -> logits [B, T, vocab_size]`` float32; ``T`` in
    whole blocks of ``msa_block_size``."""

    cfg: MiniMaxM3Shapes = MiniMaxM3Shapes()
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, ids, train: bool = False):
        c = self.cfg
        if self.is_initializing():
            # no parameter's shape depends on the sequence's length
            ids = ids[:, :c.msa_block_size]
        with jax.named_scope("fed.model.head"):
            embedding = self.param(
                "embed", _narrowed(nn.initializers.normal(EMBED_STD)),
                (c.vocab_size, c.hidden_size), c.base_dtype)
            x = jnp.take(embedding, ids, axis=0).astype(F32)
        layer = nn.remat(MiniMaxM3Layer)
        for i in range(c.num_hidden_layers):
            # the stream between layers is what the backward pass keeps: the
            # barrier makes XLA write it once, where it would otherwise fuse
            # the residual add into each of its readers and keep the add's
            # three [T, d] float32 operands instead (a client step at 16,384
            # tokens: 13.7 GB with it, 16.9 GB without, by the TPU
            # compiler's memory analysis for a v5e)
            x = jax.lax.optimization_barrier(layer(
                c, self.dtype, c.moe_layer_freq[i] == 1, name=f"layer_{i}")(x))
        with jax.named_scope("fed.model.head"):
            w_norm = self.param("final_norm", nn.initializers.zeros,
                                (c.hidden_size,), c.base_dtype)
            w_head = self.param(
                "lm_head", _narrowed(nn.initializers.normal(HEAD_STD)),
                (c.hidden_size, c.vocab_size), c.base_dtype)
            h = gemma_norm(x, w_norm, c.rms_norm_eps).astype(self.dtype)
            return _mm(h, w_head, "btd,dv->btv")


@jax.custom_vjp
def _token_ce(logits, labels):
    """``logsumexp(z) - z[label]`` a token, float32."""
    return (jax.scipy.special.logsumexp(logits, axis=-1)
            - jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0])


def _token_ce_fwd(logits, labels):
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return lse - picked, (logits, labels, lse)


def _token_ce_bwd(saved, g):
    logits, labels, lse = saved
    hot = jnp.arange(logits.shape[-1]) == labels[..., None]
    return (jnp.exp(logits - lse[..., None]) - hot) * g[..., None], None


_token_ce.defvjp(_token_ce_fwd, _token_ce_bwd)


def token_ce(logits, labels, pad_id: int = 0):
    """Mean next-token cross-entropy over the non-pad positions of each
    sequence (``trainer.local.seq_softmax_ce``'s), under the head's scope. Its
    backward keeps the logits and their log-sum-exp only and writes the
    gradient in one pass: at 16,384 tokens and 25,008 ids a float32 copy of
    the logits is 1.64 GB, and ``optax``'s rule keeps their exponential
    besides."""
    with jax.named_scope("fed.model.head"):
        per_tok = _token_ce(logits.astype(F32), labels)
        mask = (labels != pad_id).astype(F32)
        return (per_tok * mask).sum(-1) / jnp.maximum(mask.sum(-1), 1.0)


#: keys of the source's config.json that say nothing about a shape this
#: module computes (the ``mtp`` keys size the left-out modules)
IGNORED_SOURCE_KEYS = frozenset({
    "max_position_embeddings", "model_type", "num_mtp_modules",
    "num_nextn_predict_layers"})
_REQUIRED = {"hidden_act": "swigluoai", "scoring_func": "sigmoid",
             "use_routing_bias": True, "tie_word_embeddings": False,
             "use_gemma_norm": True, "use_qk_norm": True,
             "qk_norm_type": "per_head", "attention_output_gate": False,
             "n_shared_experts": 1, "swiglu_alpha": OAI_ALPHA,
             "swiglu_limit": OAI_LIMIT}


@register_model("minimax_m3")
def minimax_m3(dtype="float32", base_dtype="bfloat16", num_classes=None,
               **kwargs) -> MiniMaxM3:
    """``MiniMaxM3`` from the source's ``config.json`` keys plus the
    indexer's (``msa_*``), this shard's (``num_experts_held``,
    ``first_expert_held``), the adapters' (``adapter_rank``,
    ``adapter_alpha``, ``adapter_b_std``) and the base's dtype
    (:class:`MiniMaxM3Shapes`): ``IGNORED_SOURCE_KEYS`` are accepted and
    dropped; a key whose published value is the only one this module computes
    (the clamped SwiGLU at its constants, a biased sigmoid router, one shared
    expert, Gemma norms, a per-head q/k norm, no output gate, an untied head)
    is refused at any other, and ``partial_rotary_factor`` must be
    ``rotary_dim / head_dim``; ``num_classes`` (``create_model``'s argument)
    is the vocabulary where ``vocab_size`` is not given."""
    for key, only in _REQUIRED.items():
        if kwargs.pop(key, only) != only:
            raise NotImplementedError(
                f"minimax_m3 computes {key}={only!r} only")
    fields = {f.name for f in dataclasses.fields(MiniMaxM3Shapes)}
    factor = kwargs.pop("partial_rotary_factor", None)
    unknown = sorted(set(kwargs) - fields - IGNORED_SOURCE_KEYS)
    if unknown:
        raise TypeError(f"minimax_m3: unknown keys {unknown}")
    kept = {k: v for k, v in kwargs.items() if k in fields}
    if "moe_layer_freq" in kept:
        kept["moe_layer_freq"] = tuple(int(v) for v in kept["moe_layer_freq"])
    if num_classes is not None:
        kept.setdefault("vocab_size", int(num_classes))
    kept.setdefault("num_experts_held", kept.get(
        "num_local_experts", MiniMaxM3Shapes.num_local_experts) - kept.get(
            "first_expert_held", 0))
    shapes = MiniMaxM3Shapes(base_dtype=jnp.dtype(base_dtype), **kept)
    if factor is not None and factor * shapes.head_dim != shapes.rotary_dim:
        raise ValueError(f"partial_rotary_factor {factor} of a head of "
                         f"{shapes.head_dim} is not rotary_dim "
                         f"{shapes.rotary_dim}")
    return MiniMaxM3(shapes, jnp.dtype(dtype))
