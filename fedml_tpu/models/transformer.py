"""Decoder-only transformer LM (flax) with pluggable attention.

New TPU-era capability (the reference's NLP ceiling is an 80-char LSTM,
model/nlp/rnn.py:4): a causal LM whose attention implementation is injected
— dense single-chip attention by default, ring attention over a mesh
``sp`` axis for long-context training (fedml_tpu.parallel.ring_attention).
Pre-LN blocks, learned positional embeddings, bf16-friendly.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import flax.linen as nn
import jax.numpy as jnp

from fedml_tpu.models.registry import register_model
from fedml_tpu.parallel.ring_attention import reference_attention

#: The adapter scopes the factory accepts: which dense projections get a
#: low-rank (LoRA) pair injected NEXT TO them. Base param paths are
#: UNCHANGED by injection (the adapters are extra ``lora_*`` params in
#: the same module), so a dense-trained checkpoint loads straight into
#: the adapter model's frozen base (models/adapter.py splits by name).
ADAPTER_SCOPES = ("attn", "mlp", "all")


def lora_delta(a, b, x, *, alpha: float, rank: int):
    """The low-rank residual ``(alpha/rank) * (x @ A) @ B`` (Hu et al.
    2021; FedPara/LoRA-style low-rank updates, arXiv:2108.06098) for ONE
    adapter pair — the single expression both the training-time module
    injection (:func:`_lora_delta`) and the serving plane's KV-decode
    path (fedml_tpu.serve.forward) evaluate, so the two can never
    diverge numerically."""
    return (alpha / rank) * ((x @ a) @ b)


def lora_delta_batched(a, b, x, *, alpha: float, rank: int):
    """Batched-B twin of :func:`lora_delta`: ``B`` per-row adapter pairs
    ``a [B, d, r]`` / ``b [B, r, o]`` applied to ``x [B, ..., d]`` inside
    ONE dispatch — the multi-tenant serving move (fedml_tpu.serve): B
    different personalized models share a single batched forward instead
    of B per-request dispatches. The contraction order matches
    :func:`lora_delta` exactly (x·A then ·B, scale last), so the B=1
    slice is bitwise-equal to the per-request path (test-pinned)."""
    xa = jnp.einsum("b...d,bdr->b...r", x, a)
    return (alpha / rank) * jnp.einsum("b...r,bro->b...o", xa, b)


def _lora_delta(mod: nn.Module, name: str, x, out_dim: int, rank: int,
                alpha: float, dtype):
    """Module-side injection of :func:`lora_delta`: creates the pair next
    to a dense projection. ``A`` is small-normal, ``B`` zero — the
    injected model is exactly the base model at init. Param names carry
    the ``lora_`` prefix :mod:`fedml_tpu.models.adapter` splits on."""
    a = mod.param(f"lora_{name}_a", nn.initializers.normal(0.02),
                  (x.shape[-1], rank))
    b = mod.param(f"lora_{name}_b", nn.initializers.zeros, (rank, out_dim))
    if dtype is not None:
        a, b = a.astype(dtype), b.astype(dtype)
    return lora_delta(a, b, x, alpha=alpha, rank=rank)


class MHA(nn.Module):
    n_heads: int
    d_model: int
    attn_fn: Optional[Callable] = None  # (q,k,v[,causal]) -> o, else dense
    causal: bool = True
    dtype: Any = None  # compute dtype (params stay float32)
    adapter_rank: int = 0  # 0 = no adapters: param tree identical to pre-LoRA
    adapter_alpha: float = 16.0

    @nn.compact
    def __call__(self, x):
        b, t, _ = x.shape
        d_head = self.d_model // self.n_heads
        qkv = nn.Dense(3 * self.d_model, use_bias=False, dtype=self.dtype)(x)
        if self.adapter_rank:
            qkv = qkv + _lora_delta(self, "qkv", x, 3 * self.d_model,
                                    self.adapter_rank, self.adapter_alpha,
                                    self.dtype)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        shp = (b, t, self.n_heads, d_head)
        q, k, v = q.reshape(shp), k.reshape(shp), v.reshape(shp)
        if self.attn_fn is not None:
            # Forward causal when the injected attention accepts it (e.g.
            # flash_attention defaults to causal=False — silently building a
            # non-causal decoder would make training look great and
            # generation garbage). Pre-bound callables (ring attention from
            # make_ring_attention, partials, lambdas) are used as-is.
            import inspect

            try:
                accepts_causal = "causal" in inspect.signature(self.attn_fn).parameters
            except (TypeError, ValueError):
                accepts_causal = False
            if accepts_causal:
                o = self.attn_fn(q, k, v, causal=self.causal)
            else:
                o = self.attn_fn(q, k, v)
        else:
            o = reference_attention(q, k, v, causal=self.causal)
        o = o.reshape(b, t, self.d_model)
        out = nn.Dense(self.d_model, use_bias=False, dtype=self.dtype)(o)
        if self.adapter_rank:
            out = out + _lora_delta(self, "out", o, self.d_model,
                                    self.adapter_rank, self.adapter_alpha,
                                    self.dtype)
        return out


class Block(nn.Module):
    n_heads: int
    d_model: int
    mlp_ratio: int = 4
    attn_fn: Optional[Callable] = None
    causal: bool = True
    dtype: Any = None
    adapter_rank: int = 0
    adapter_scope: str = "attn"  # which projections get LoRA pairs
    adapter_alpha: float = 16.0

    @nn.compact
    def __call__(self, x, train: bool = False):
        r = self.adapter_rank
        attn_r = r if self.adapter_scope in ("attn", "all") else 0
        mlp_r = r if self.adapter_scope in ("mlp", "all") else 0
        h = nn.LayerNorm(dtype=self.dtype)(x)
        x = x + MHA(self.n_heads, self.d_model, self.attn_fn, self.causal,
                    dtype=self.dtype, adapter_rank=attn_r,
                    adapter_alpha=self.adapter_alpha)(h)
        h = nn.LayerNorm(dtype=self.dtype)(x)
        up = nn.Dense(self.mlp_ratio * self.d_model, dtype=self.dtype)(h)
        if mlp_r:
            up = up + _lora_delta(self, "mlp_in", h,
                                  self.mlp_ratio * self.d_model, mlp_r,
                                  self.adapter_alpha, self.dtype)
        up = nn.gelu(up)
        down = nn.Dense(self.d_model, dtype=self.dtype)(up)
        if mlp_r:
            down = down + _lora_delta(self, "mlp_out", up, self.d_model,
                                      mlp_r, self.adapter_alpha, self.dtype)
        return x + down


class TransformerLM(nn.Module):
    vocab_size: int
    d_model: int = 128
    n_heads: int = 4
    n_layers: int = 2
    max_len: int = 2048
    attn_fn: Optional[Callable] = None
    causal: bool = True
    dtype: Any = None  # compute dtype; jnp.bfloat16 = mixed precision
    #: LoRA adapter injection (models/adapter.py): rank 0 leaves the
    #: param tree byte-identical to the pre-adapter model; rank > 0 adds
    #: ``lora_*`` pairs next to the scoped projections. Embeddings and
    #: the logits head stay base-only (frozen in adapter finetuning).
    adapter_rank: int = 0
    adapter_scope: str = "attn"
    adapter_alpha: float = 16.0

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        b, t = tokens.shape
        x = nn.Embed(self.vocab_size, self.d_model, dtype=self.dtype)(tokens)
        pos = nn.Embed(self.max_len, self.d_model,
                       dtype=self.dtype)(jnp.arange(t))
        x = x + pos[None]
        for _ in range(self.n_layers):
            x = Block(self.n_heads, self.d_model, attn_fn=self.attn_fn,
                      causal=self.causal, dtype=self.dtype,
                      adapter_rank=self.adapter_rank,
                      adapter_scope=self.adapter_scope,
                      adapter_alpha=self.adapter_alpha)(x, train)
        x = nn.LayerNorm(dtype=self.dtype)(x)
        # Logits in f32: softmax-CE over a 10k vocab is the one place bf16
        # rounding visibly hurts the loss.
        return nn.Dense(self.vocab_size, use_bias=False)(x).astype(jnp.float32)


@register_model("transformer_lm")
def transformer_lm(vocab_size: int = 90, d_model: int = 128, n_heads: int = 4,
                   n_layers: int = 2, max_len: int = 2048,
                   attn_fn: Optional[Callable] = None, causal: bool = True,
                   attn: str = "dense", dtype=None, adapter_rank: int = 0,
                   adapter_scope: str = "attn", adapter_alpha: float = 16.0,
                   **_):
    """``attn="flash"`` swaps in the pallas fused kernel
    (fedml_tpu.ops.flash_attention) — O(T) memory, faster than dense on
    TPU from T≈2k with bf16 activations (a crossover read through the
    retired attachment). ``attn_fn`` (a callable) overrides both.

    ``adapter_rank > 0`` injects LoRA pairs (scope ``attn`` | ``mlp`` |
    ``all``) for parameter-efficient federated finetuning — see
    fedml_tpu.models.adapter / fedml_tpu.algos.fedadapter."""
    if attn_fn is None and attn == "flash":
        from fedml_tpu.ops.flash_attention import flash_attention
        attn_fn = flash_attention  # MHA forwards causal= (it inspects)
    elif attn_fn is None and attn != "dense":
        raise ValueError(f"unknown attn {attn!r}: expected dense|flash")
    if adapter_rank and adapter_scope not in ADAPTER_SCOPES:
        raise ValueError(
            f"unknown adapter_scope {adapter_scope!r}: expected one of "
            f"{ADAPTER_SCOPES}")
    if adapter_rank < 0:
        raise ValueError(f"adapter_rank must be >= 0, got {adapter_rank}")
    from fedml_tpu.models.registry import resolve_dtype
    return TransformerLM(vocab_size=vocab_size, d_model=d_model,
                         n_heads=n_heads, n_layers=n_layers, max_len=max_len,
                         attn_fn=attn_fn, causal=causal,
                         dtype=resolve_dtype(dtype),
                         adapter_rank=int(adapter_rank),
                         adapter_scope=adapter_scope,
                         adapter_alpha=float(adapter_alpha))
