"""Nemotron-H (``nemotron_h``): a stack of SINGLE-MIXER blocks read from
``hybrid_override_pattern``: ``M`` a Mamba-2 mixer whose ``B`` and ``C``
belong to ``n_groups`` groups of heads, ``*`` grouped-query attention with no
positions, ``E`` a 128-wide sigmoid router over NON-GATED ``relu^2`` experts of
which THIS shard holds a contiguous range, plus a shared expert. Every block
is ``x + Mixer(RMSNorm(x))`` and nothing else: no MLP follows a mixer. A
low-rank (LoRA) pair stands beside every linear projection, each held expert's
two matrices included, as a flax module for the federated adapter round
(``algos/fedadapter.py``).

Source: https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/
blob/main/config.json (the field names below are its keys). What the config
has no key for is listed under ``assumed`` in the benchmark's configuration
file: attention applies no positional encoding (Nemotron-H, arXiv:2504.03624,
although the config carries ``rope_theta``), the gated norm's group is
``mamba_num_heads * mamba_head_dim / n_groups`` channels.

The equations, written out, are ``benchmark/reference_nemotron_h.py``'s, which
imports nothing from here; the tests hold the two together. The Mamba-2 mixer
and the attention are ``models/granite_hybrid.py``'s modules, which read their
sizes from the shapes below under that file's names (the properties at the end
of :class:`NemotronHShapes`); the held experts are
``parallel/expert_parallel.held_lora_products`` in its ``"relu2"`` form. The
base parameters are created in float32 and narrowed to ``base_dtype``
(bfloat16) and are what ``models/adapter.split_frozen`` freezes; the ``lora_*``
pairs are float32 and are the federated net. The residual stream, norms, the
router and the logits are float32; products take ``dtype`` operands and
accumulate in float32. Each block is under ``nn.remat``: what is kept for the
backward pass is the residual stream between blocks.

This shard of the expert-parallel layer: ``n_routed_experts`` is the router's
width, ``num_experts_held`` experts from ``first_expert_held`` are here; the
others' part of the sum is absent. The collection ``counters`` keeps, a sparse
block, the running totals ``expert_tokens [held]``, ``unrouted_tokens``,
``uncomputed_tokens``, ``further_passes`` and ``grouped_rows``
(``models/k_exaone.py`` has their meaning), which the round carries and
averages over the cohort like batch statistics.

Device scopes (``jax.named_scope``, read by the benchmark's reducers):
``fed.model.ssm`` (``.conv``, ``.scan``), ``fed.model.attn`` (``.core`` around
the kernel alone), ``fed.model.moe`` (``.route``, ``.experts``, ``.shared``),
``fed.model.norm`` (a block's input norm), ``fed.model.lora`` (every pair's
two products but the held experts', which are part of
``fed.model.moe.experts``), ``fed.model.head`` (embedding, final norm, head;
``token_ce`` puts the loss there too).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from fedml_tpu.models.granite_hybrid import (
    Attention, Mamba2Mixer, _mm, _narrowed, rms_norm)
# base parameters in the base's dtype, a pair, a linear map with its pair
from fedml_tpu.models.k_exaone import _Layer
from fedml_tpu.models.qwen3_next import SparseMoE as _CountingMoE
from fedml_tpu.models.qwen3_next import token_ce  # noqa: F401  (the head-scoped loss)
from fedml_tpu.models.registry import register_model
from fedml_tpu.ops import lora_linear as ll
from fedml_tpu.parallel.expert_parallel import (
    ExpertPairs, chunk_rows, held_lora_products, route_sigmoid, sort_held)

F32 = jnp.float32
_NORMAL = nn.initializers.normal(0.02)
#: the head's initial law: small first logits, so that the first loss is the
#: prior's. Every other matrix is drawn normal(0, 0.02) here; the benchmark
#: draws its base itself (``benchmark/reference_nemotron_h.init_base``), with
#: a unit embedding and small output maps, for the reasons that file gives
HEAD_STD = 0.002
#: the router's selection bias as drawn (the benchmark then balances it)
ROUTER_BIAS_STD = 0.05
#: block kinds of ``hybrid_override_pattern``
KINDS = {"M": "mamba", "*": "attn", "E": "moe"}


def relu2(x):
    return jnp.square(jax.nn.relu(x))


class Relu2MLP(_Layer):
    """``W_down relu(W_up x)^2``, a pair beside each: the shared expert."""

    width: int = 0

    @nn.compact
    def __call__(self, x):
        up = self.linear("up_proj", x, self.width, x.dtype).astype(F32)
        return self.linear("down_proj", relu2(up).astype(x.dtype),
                           self.cfg.hidden_size)


class SparseMoE(_Layer):
    """This shard's part of the routed layer, and the shared expert."""

    #: running float32 totals in the ``counters`` collection, one rule for
    #: every expert layer of the repo
    _count = _CountingMoE._count

    @nn.compact
    def __call__(self, x32):
        """``x32 [B, T, d]`` float32 (the block's normed input): the router
        reads it as it is, the experts read it in the compute dtype."""
        c = self.cfg
        d, f, held, r = (c.hidden_size, c.moe_intermediate_size,
                         c.num_experts_held, c.adapter_rank)
        w_router = self.base("router", _NORMAL, (d, c.n_routed_experts))
        bias = self.base("router_bias", nn.initializers.normal(
            ROUTER_BIAS_STD), (c.n_routed_experts,))
        # a hidden unit a row, as the source's linear maps lie: the last axis
        # is the stream's width, on the lane grid (``expert_parallel.FORMS``)
        w_up = self.base("experts_up", _NORMAL, (held, f, d))
        w_down = self.base("experts_down", _NORMAL, (held, f, d))
        shapes = {"up": (d, f), "down": (f, d)}
        if r:
            pairs = ExpertPairs(None, None, *(
                m for name, (i, o) in shapes.items() for m in self.pair(
                    f"experts_{name}", (held, i, r), (held, r, o))))
        else:       # no adapters: pairs of rank 1 that add nothing
            pairs = ExpertPairs(None, None, *(
                jnp.zeros(s, F32) for i, o in shapes.values()
                for s in ((held, i, 1), (held, 1, o))))
        b, t, _ = x32.shape
        flat32 = x32.reshape(b * t, d)
        flat = flat32.astype(self.dtype)
        with jax.named_scope("fed.model.moe.route"):
            idx, weight = route_sigmoid(
                flat32, w_router, bias, c.num_experts_per_tok,
                c.routed_scaling_factor, c.norm_topk_prob)
            assigned = sort_held(idx, held, c.first_expert_held)
        rows = chunk_rows(b * t, c.num_experts_per_tok, c.n_routed_experts,
                          held)
        if r:       # pairs that the grouped product computes itself
            for i, o in shapes.values():
                ll.note(rows, i, o, r, False, experts=held)
        with jax.named_scope("fed.model.moe.experts"):
            y, computed, further = held_lora_products(
                flat, weight, assigned, w_up, w_down, pairs,
                c.adapter_alpha / max(r, 1), rows, form="relu2")
        self._count(expert_tokens=assigned.counts,
                    unrouted_tokens=assigned.unrouted,
                    uncomputed_tokens=jnp.sum(assigned.counts) - computed,
                    further_passes=further, grouped_rows=rows * (1 + further))
        with jax.named_scope("fed.model.moe.shared"):
            y = y + Relu2MLP(c, self.dtype,
                             c.moe_shared_expert_intermediate_size,
                             name="shared")(flat)
        return y.reshape(b, t, d)


class NemotronHBlock(_Layer):
    """``x + Mixer(RMSNorm(x))``: one mixer of ``kind`` and nothing else."""

    kind: str = "mamba"

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        w_norm = self.base("norm", nn.initializers.ones, (c.hidden_size,))
        with jax.named_scope("fed.model.norm"):
            h = rms_norm(x, w_norm, c.layer_norm_epsilon)
        if self.kind == "mamba":
            with jax.named_scope("fed.model.ssm"):
                return x + Mamba2Mixer(c, self.dtype, name="mamba")(
                    h.astype(self.dtype))
        if self.kind == "attn":
            with jax.named_scope("fed.model.attn"):
                return x + Attention(c, self.dtype, name="attn")(
                    h.astype(self.dtype))
        with jax.named_scope("fed.model.moe"):
            return x + SparseMoE(c, self.dtype, name="moe")(h)


@dataclasses.dataclass(frozen=True)
class NemotronHShapes:
    """The source's ``config.json`` keys (Nemotron-3-Nano-30B-A3B's values as
    defaults), this shard's experts, the adapters, and how the blocks are
    computed. ``hybrid_override_pattern`` may be longer than
    ``num_hidden_layers`` (the source's string, a cut stack): its first
    ``num_hidden_layers`` characters are the blocks."""

    vocab_size: int = 131072
    hidden_size: int = 2688
    num_hidden_layers: int = 52
    hybrid_override_pattern: str = (
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME")
    layer_norm_epsilon: float = 1e-5
    # Mamba-2
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    n_groups: int = 8
    conv_kernel: int = 4
    chunk_size: int = 128
    # attention: no positions, scale head_dim^-1/2
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    # experts
    n_routed_experts: int = 128
    num_experts_per_tok: int = 6
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
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
        n, pattern = self.num_hidden_layers, self.hybrid_override_pattern
        if len(pattern) < n:
            raise ValueError(f"hybrid_override_pattern names {len(pattern)} "
                             f"blocks, num_hidden_layers is {n}")
        if set(pattern) - set(KINDS):
            raise ValueError("hybrid_override_pattern: unknown kinds "
                             f"{sorted(set(pattern) - set(KINDS))}")
        if self.mamba_num_heads % self.n_groups:
            raise ValueError(f"{self.mamba_num_heads} Mamba-2 heads do not "
                             f"divide into {self.n_groups} groups")
        if self.attention not in ("flash", "dense"):
            raise ValueError(f"attention={self.attention!r}: 'flash' or "
                             "'dense'")
        if not 0 < self.num_experts_held <= (
                self.n_routed_experts - self.first_expert_held):
            raise ValueError(
                f"held experts {self.first_expert_held}.."
                f"{self.first_expert_held + self.num_experts_held - 1} are "
                f"not among the {self.n_routed_experts} that exist")

    @property
    def kinds(self) -> tuple:
        """The blocks' kinds: ``"mamba"``, ``"attn"`` or ``"moe"`` each."""
        return tuple(KINDS[ch] for ch in self.hybrid_override_pattern[
            :self.num_hidden_layers])

    # what ``models/granite_hybrid``'s mixers read, under that file's names
    mamba_n_heads = property(lambda self: self.mamba_num_heads)
    mamba_d_head = property(lambda self: self.mamba_head_dim)
    mamba_d_state = property(lambda self: self.ssm_state_size)
    mamba_n_groups = property(lambda self: self.n_groups)
    mamba_d_conv = property(lambda self: self.conv_kernel)
    mamba_chunk_size = property(lambda self: self.chunk_size)
    rms_norm_eps = property(lambda self: self.layer_norm_epsilon)
    attention_multiplier = property(lambda self: self.head_dim ** -0.5)


class NemotronH(nn.Module):
    """``ids [B, T] int32 -> logits [B, T, vocab_size]`` float32."""

    cfg: NemotronHShapes = NemotronHShapes()
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, ids, train: bool = False):
        c = self.cfg
        if self.is_initializing():
            # no parameter's shape depends on the sequence's length
            ids = ids[:, :8]
        with jax.named_scope("fed.model.head"):
            embedding = self.param(
                "embed", _narrowed(_NORMAL),
                (c.vocab_size, c.hidden_size), c.base_dtype)
            x = jnp.take(embedding, ids, axis=0).astype(F32)
        block = nn.remat(NemotronHBlock)
        for i, kind in enumerate(c.kinds):
            x = block(c, self.dtype, kind, name=f"layer_{i}")(x)
        with jax.named_scope("fed.model.head"):
            w_norm = self.param("final_norm", nn.initializers.ones,
                                (c.hidden_size,), c.base_dtype)
            w_head = self.param(
                "lm_head", _narrowed(nn.initializers.normal(HEAD_STD)),
                (c.hidden_size, c.vocab_size), c.base_dtype)
            h = rms_norm(x, w_norm, c.layer_norm_epsilon).astype(self.dtype)
            return _mm(h, w_head, "btd,dv->btv")


#: keys of the source's config.json that say nothing about a shape this
#: module computes, or that another key this module reads repeats
#: (``intermediate_size`` repeats ``moe_intermediate_size`` in a model with no
#: dense MLP block; ``norm_eps`` repeats ``layer_norm_epsilon``;
#: ``rope_theta`` and ``partial_rotary_factor`` belong to positions this
#: attention does not apply; ``expand`` is not what sizes this mixer: its
#: inner width is ``mamba_num_heads * mamba_head_dim`` = 4,096, not ``expand *
#: hidden_size`` = 5,376, as in the source's own ``nemotron_h`` code)
IGNORED_SOURCE_KEYS = frozenset({
    "expand", "intermediate_size", "max_position_embeddings", "model_type", "norm_eps",
    "num_logits_to_keep", "partial_rotary_factor", "rescale_prenorm_residual",
    "residual_in_fp32", "rope_theta", "sliding_window", "time_step_floor",
    "use_mamba_kernels"})
_REQUIRED = {"attention_bias": False, "mamba_proj_bias": False,
             "mlp_bias": False, "use_bias": False, "use_conv_bias": True,
             "mamba_hidden_act": "silu", "mlp_hidden_act": "relu2",
             "n_group": 1, "topk_group": 1, "n_shared_experts": 1,
             "tie_word_embeddings": False,
             # the step's bias as ``Mamba2Mixer`` draws it
             "time_step_min": 0.001, "time_step_max": 0.1}


@register_model("nemotron_h")
def nemotron_h(dtype="float32", base_dtype="bfloat16", num_classes=None,
               **kwargs) -> NemotronH:
    """``NemotronH`` from the source's ``config.json`` keys plus this shard's
    (``num_experts_held``, ``first_expert_held``), the adapters'
    (``adapter_rank``, ``adapter_alpha``, ``adapter_b_std``) and the compute
    choices (:class:`NemotronHShapes`). ``IGNORED_SOURCE_KEYS`` are accepted
    and dropped, so a configuration file can hold the source's dictionary as
    it is; a key whose published value is the only one this module computes
    (no bias on a linear map and one on the convolution, SiLU in the mixer,
    ``relu^2`` experts, a router with one group, one shared expert, an untied
    head) is refused at any other; ``num_classes`` (``create_model``'s
    argument) is the vocabulary where ``vocab_size`` is not given."""
    for key, only in _REQUIRED.items():
        if kwargs.pop(key, only) != only:
            raise NotImplementedError(
                f"nemotron_h computes {key}={only!r} only")
    fields = {f.name for f in dataclasses.fields(NemotronHShapes)}
    unknown = sorted(set(kwargs) - fields - IGNORED_SOURCE_KEYS)
    if unknown:
        raise TypeError(f"nemotron_h: unknown keys {unknown}")
    kept = {k: v for k, v in kwargs.items() if k in fields}
    if num_classes is not None:
        kept.setdefault("vocab_size", int(num_classes))
    kept.setdefault("num_experts_held", kept.get(
        "n_routed_experts", NemotronHShapes.n_routed_experts) - kept.get(
            "first_expert_held", 0))
    return NemotronH(NemotronHShapes(base_dtype=jnp.dtype(base_dtype),
                                     **kept), jnp.dtype(dtype))
