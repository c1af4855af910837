"""Batched multi-adapter inference over ONE frozen-base dispatch.

The serving problem at FedML scale: every request belongs to a
*different* personalized model (a per-client LoRA adapter row in
:class:`~fedml_tpu.models.adapter.PersonalAdapterStore`), but the frozen
transformer base — 99%+ of the FLOPs — is shared by all of them. Serving
per request would pay one dispatch + one unbatched forward per user;
here ``B`` requests ride a single jitted program: the base enters as
jit-captured device constants (the ``adapter_model_fns`` holder), the
``B`` adapter rows enter as stacked ``[B, ...]`` leaves, and ``vmap``
lifts the shared-base matmuls to batched matmuls against one weight
while the per-row LoRA pairs contract per row
(:func:`~fedml_tpu.models.transformer.lora_delta_batched`).

Identity contracts (test-pinned, the PR 15 identity invariant moved onto
the read path; byte-exact unless noted):

- the batched forward at ``B=1`` equals the per-request jitted forward
  bit-for-bit;
- a row whose adapter vector is all-zero (rank-0 / never-personalized
  under a zero global) reproduces the DENSE model (to the last ulp since
  jaxlib 0.9.0: another program, other fusions);
- right-padding the token row and zero-padding the batch change no real
  row's logits (causal attention + row-independent vmap; the token
  padding to the last ulp since jaxlib 0.9.0, whose CPU dots pick their
  accumulation order from the operand shape), so the plane can pad every
  micro-batch to one compiled ``[max_batch, seq_len]`` shape.

For tokens/s the module also carries :class:`AdapterDecoder`, a
KV-cached prefill + per-step decode over the SAME merged params —
single-token steps never recompute the prompt. Attention for the
full-sequence path follows the flash-attention sweep: causal flash above
the crossover (``T >= 2048``, :data:`FLASH_CROSSOVER_T`),
dense below it (:func:`pick_attention`).
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from fedml_tpu.models.transformer import lora_delta_batched
from fedml_tpu.trainer.local import NetState

#: Flash-vs-dense crossover (v5e through the retired attachment,
#: 2026-07-31; not re-measured on this benchmark): the pallas fused
#: kernel wins from T≈2048 with bf16 activations, dense wins below.
FLASH_CROSSOVER_T = 2048


def pick_attention(seq_len: int, crossover: int = FLASH_CROSSOVER_T) -> str:
    """``attn=`` spec for a serving model at this sequence length: causal
    flash (fedml_tpu.ops.flash_attention) where the sweep says it wins,
    dense fallback below the crossover."""
    return "flash" if int(seq_len) >= int(crossover) else "dense"


def stacked_tree_of(vecs, spec):
    """``[B, D]`` flat adapter vectors → adapter tree with ``[B, ...]``
    leaves (the batched twin of ``comm.codec.vector_to_tree_np``): per
    leaf one reshape of the row slice, no per-row Python loop."""
    vecs = np.asarray(vecs, np.float32)
    if vecs.ndim != 2:
        raise ValueError(f"expected [B, D] adapter vectors, got {vecs.shape}")
    b = vecs.shape[0]
    total = int(sum(spec.sizes))
    if vecs.shape[1] != total:
        raise ValueError(
            f"adapter vectors have dim {vecs.shape[1]} but the spec "
            f"declares {total}")
    leaves, off = [], 0
    for shape, dtype, size in zip(spec.shapes, spec.dtypes, spec.sizes):
        leaves.append(vecs[:, off:off + size]
                      .reshape((b,) + tuple(shape)).astype(np.dtype(dtype)))
        off += size
    return jax.tree_util.tree_unflatten(spec.treedef, leaves)


class ServeForward:
    """The jitted batched multi-adapter forward over one frozen base.

    ``fns`` is the :class:`~fedml_tpu.models.adapter.AdapterFns` whose
    holder already carries the frozen base; ``template_adapters`` fixes
    the adapter tree structure (and hence the flat dim the store rows
    must match). ``batched(stacked, tokens)`` is the serving path;
    ``sequential(adapters, tokens_row)`` is the per-request baseline the
    B=1 bitwise pin (and the bench A/B) runs against.
    """

    def __init__(self, fns, template_adapters):
        from fedml_tpu.core.compression import tree_spec

        self.fns = fns
        self.spec = tree_spec(template_adapters)
        self.dim = int(sum(self.spec.sizes))

        def row(adapters, toks):
            logits, _ = fns.apply(NetState(adapters, {}), toks[None],
                                  train=False)
            return logits[0]

        #: [B,...]-stacked adapters + [B, T] tokens -> [B, T, V] logits;
        #: ONE dispatch for B personalized models.
        self.batched = jax.jit(jax.vmap(row))
        #: one adapter tree + [T] tokens -> [T, V]; the per-request path.
        self.sequential = jax.jit(row)

    def stacked_tree(self, vecs):
        """``[B, D]`` store rows → the batched forward's adapter input."""
        return stacked_tree_of(vecs, self.spec)

    def prefill(self, vecs, tokens):
        """Serve ``B`` requests in one dispatch: gathered ``[B, D]`` rows
        + ``[B, T]`` int32 tokens → ``[B, T, V]`` float32 logits."""
        return self.batched(self.stacked_tree(vecs),
                            jnp.asarray(tokens, jnp.int32))

    def prefill_sequential(self, vecs, tokens):
        """The one-adapter-at-a-time baseline: same inputs, one dispatch
        PER ROW (what serving without this plane would pay). Bench A/B
        arm and bitwise oracle for the B=1 pin."""
        tokens = np.asarray(tokens, np.int32)
        out = []
        for i in range(tokens.shape[0]):
            tree = self._row_tree(vecs, i)
            out.append(self.sequential(tree, jnp.asarray(tokens[i])))
        return jnp.stack(out)

    def _row_tree(self, vecs, i):
        from fedml_tpu.comm.codec import vector_to_tree_np

        return vector_to_tree_np(np.asarray(vecs[i], np.float32), self.spec)


class _DecodeCache(NamedTuple):
    """Per-layer KV cache: ``k``/``v`` are ``[L, B, T_max, H, Dh]``;
    ``pos`` is the PER-ROW ``[B]`` count of filled positions — rows with
    different true prompt lengths decode from their own last token, so
    the plane's right-padding stays inert through decode."""

    k: Any
    v: Any
    pos: Any


def _layer_norm(x, p):
    """flax ``nn.LayerNorm`` twin (eps 1e-6, scale + bias)."""
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + 1e-6) * p["scale"] + p["bias"]


class AdapterDecoder:
    """KV-cached greedy decode over the merged (base + per-row adapter)
    params — the tokens/s path: ``prefill`` runs the prompt once and
    fills the cache; each ``step`` pays a single-position forward whose
    attention reads the cache instead of recomputing the prompt.

    The stack is evaluated functionally from the param tree the flax
    module owns (same names, same math: pre-LN blocks, causal attention
    at ``1/sqrt(d_head)``, gelu MLP, f32 logits head), with the per-row
    LoRA residuals applied through the SAME
    :func:`~fedml_tpu.models.transformer.lora_delta_batched` expression
    as the batched forward. Decode logits are pinned against the full
    forward (tests/test_serve.py) — the cache is an optimization, not a
    different model.
    """

    def __init__(self, model, fns, template_adapters, *,
                 max_len: Optional[int] = None):
        from fedml_tpu.core.compression import tree_spec

        self.model = model
        self.fns = fns
        self.spec = tree_spec(template_adapters)
        self.n_heads = int(model.n_heads)
        self.n_layers = int(model.n_layers)
        self.d_model = int(model.d_model)
        self.alpha = float(model.adapter_alpha)
        self.max_len = int(max_len or model.max_len)
        # One jitted program per static step count: the prompt length(s)
        # and steps=1 for decode — the cache shape keys the rest.
        self._jit_run = jax.jit(self._run, static_argnames=("steps",))

    # -- merged functional stack ---------------------------------------

    def _delta(self, ad, site, x):
        a = ad.get(f"lora_{site}_a")
        if a is None:
            return None
        b = ad[f"lora_{site}_b"]
        return lora_delta_batched(a, b, x, alpha=self.alpha,
                                  rank=int(a.shape[-1]))

    def _block(self, base, ad, x, ck, cv, pos):
        """One pre-LN block over ``x [B, S, d]`` with the KV cache
        (``pos [B]`` per-row write offsets); returns updated
        ``(x, ck, cv)`` (``ck``/``cv`` ``[B, T, H, Dh]``)."""
        h = _layer_norm(x, base["LayerNorm_0"])
        mha, mad = base["MHA_0"], (ad or {}).get("MHA_0", {})
        qkv = h @ mha["Dense_0"]["kernel"]
        d = self._delta(mad, "qkv", h)
        if d is not None:
            qkv = qkv + d
        q, k, v = jnp.split(qkv, 3, axis=-1)
        bsz, s, _ = q.shape
        hd = self.d_model // self.n_heads
        shp = (bsz, s, self.n_heads, hd)
        q, k, v = q.reshape(shp), k.reshape(shp), v.reshape(shp)
        upd = jax.vmap(lambda c, new, p: jax.lax.dynamic_update_slice(
            c, new, (p, 0, 0)))
        ck = upd(ck, k, pos)
        cv = upd(cv, v, pos)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, ck) / jnp.sqrt(
            jnp.asarray(hd, q.dtype))
        # Causal over ABSOLUTE per-row positions: row b's query i sits at
        # pos[b]+i, key j is valid iff j <= pos[b]+i (unfilled cache
        # slots — and a short row's stale prompt-pad slots — live beyond
        # pos[b]+S-1, so the same inequality masks them).
        qpos = pos[:, None] + jnp.arange(s)[None, :]
        keep = jnp.arange(ck.shape[1])[None, None, :] <= qpos[:, :, None]
        scores = jnp.where(keep[:, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", probs, cv).reshape(bsz, s,
                                                            self.d_model)
        out = o @ mha["Dense_1"]["kernel"]
        d = self._delta(mad, "out", o)
        if d is not None:
            out = out + d
        x = x + out
        h = _layer_norm(x, base["LayerNorm_1"])
        up = h @ base["Dense_0"]["kernel"] + base["Dense_0"]["bias"]
        d = self._delta(ad or {}, "mlp_in", h)
        if d is not None:
            up = up + d
        up = jax.nn.gelu(up)
        down = up @ base["Dense_1"]["kernel"] + base["Dense_1"]["bias"]
        d = self._delta(ad or {}, "mlp_out", up)
        if d is not None:
            down = down + d
        return x + down, ck, cv

    def _run(self, stacked, tokens, cache, *, steps: int):
        """``steps`` positions starting at the per-row ``cache.pos``:
        prompt prefill (``steps = T0``, empty cache) and single-token
        decode (``steps = 1``) are the same traced program at different
        static shapes. Returns ``(logits [B, steps, V], cache')``."""
        base = self.fns.holder["base"]
        pos = cache.pos
        x = (base["Embed_0"]["embedding"][tokens]
             + base["Embed_1"]["embedding"][pos[:, None]
                                            + jnp.arange(steps)[None]])
        ks, vs = [], []
        for li in range(self.n_layers):
            name = f"Block_{li}"
            x, ck, cv = self._block(base[name], stacked.get(name), x,
                                    cache.k[li], cache.v[li], pos)
            ks.append(ck)
            vs.append(cv)
        x = _layer_norm(x, base["LayerNorm_0"])
        logits = (x @ base["Dense_0"]["kernel"]).astype(jnp.float32)
        return logits, _DecodeCache(jnp.stack(ks), jnp.stack(vs),
                                    pos + steps)

    # -- public surface -------------------------------------------------

    def empty_cache(self, batch: int, max_len: Optional[int] = None):
        t = int(max_len or self.max_len)
        hd = self.d_model // self.n_heads
        shape = (self.n_layers, batch, t, self.n_heads, hd)
        return _DecodeCache(jnp.zeros(shape, jnp.float32),
                            jnp.zeros(shape, jnp.float32),
                            jnp.zeros(batch, jnp.int32))

    def prefill(self, stacked, tokens, lens=None,
                max_len: Optional[int] = None):
        """Prompt pass: ``[B, T0]`` tokens → TRUE-last-position logits
        ``[B, V]`` + the filled cache. ``lens [B]`` gives per-row true
        prompt lengths for right-padded batches: the returned logits are
        gathered at ``lens-1`` (never a pad position) and the cache's
        per-row write offsets rewind to ``lens``, so decode overwrites a
        short row's pad slots before its causal mask can reach them.
        ``lens=None`` means every row is full length."""
        tokens = jnp.asarray(tokens, jnp.int32)
        cache = self.empty_cache(tokens.shape[0], max_len)
        logits, cache = self._jit_run(stacked, tokens, cache,
                                      steps=int(tokens.shape[1]))
        if lens is None:
            return logits[:, -1], cache
        lens = jnp.asarray(lens, jnp.int32)
        last = jnp.take_along_axis(logits, (lens - 1)[:, None, None],
                                   axis=1)[:, 0]
        return last, cache._replace(pos=lens)

    def step(self, stacked, token, cache):
        """One decode position: ``[B]`` tokens → ``[B, V]`` logits."""
        logits, cache = self._jit_run(stacked, token[:, None], cache,
                                      steps=1)
        return logits[:, 0], cache

    def generate(self, stacked, tokens, n_new: int, lens=None):
        """Greedy decode ``n_new`` tokens per row (``lens`` as in
        :meth:`prefill` — right-padded rows continue from their true
        last token). Returns ``[B, n_new]`` int32 — the tokens/s
        workload (one cached step per token)."""
        logits, cache = self.prefill(stacked, tokens, lens=lens)
        out = []
        for _ in range(int(n_new)):
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            out.append(nxt)
            logits, cache = self.step(stacked, nxt, cache)
        return jnp.stack(out, axis=1)
