"""Ring attention: exact attention over sequences sharded across a mesh
axis (sequence/context parallelism).

The reference has NO long-context machinery (SURVEY.md §2.10 — its largest
sequence is an 80-char LSTM window); this is the TPU-native capability axis
the task mandates. Design follows the blockwise/ring formulation (Liu &
Abbeel; Ring Attention with Blockwise Transformers): each device holds a
sequence shard of Q, K, V; K/V blocks rotate around the ring via
``lax.ppermute`` over ICI while every device accumulates its Q-shard's
attention with a streaming (online) softmax — running max ``m``, normalizer
``l``, and unnormalized output ``o`` — so the result is bit-for-bit exact
attention, never materializing the full [T, T] score matrix.

Collectives ride the mesh axis (ICI when the axis maps to ICI), overlapping
the permute of block ``i+1`` with compute of block ``i`` is left to XLA's
latency-hiding scheduler.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P


def _block_attn(q, k, v, scale, mask):
    """One (Q-shard × KV-block) partial: returns scores-softmax pieces.

    q: [B, Tq, H, D], k/v: [B, Tk, H, D], mask: [Tq, Tk] bool (True=keep).
    Returns (m, l, o) block stats: m [B,H,Tq], l [B,H,Tq], o [B,Tq,H,D].
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    s = jnp.where(mask[None, None], s, -jnp.inf)
    m = jnp.max(s, axis=-1)  # [B,H,Tq]
    # exp(-inf - -inf) guards: fully-masked rows get m=-inf; make exp 0.
    p = jnp.exp(s - jnp.where(jnp.isfinite(m), m, 0.0)[..., None])
    p = jnp.where(mask[None, None], p, 0.0)
    l = jnp.sum(p, axis=-1)  # [B,H,Tq]
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    return m, l, o


def ring_attention_sharded(q, k, v, axis_name: str, causal: bool = False):
    """Body to run INSIDE shard_map: q/k/v are the local shards
    [B, T_local, H, D]; returns the local attention output shard.

    Streaming-softmax accumulation across ring steps; the K/V pair rotates
    ``n`` times so every Q shard sees every KV block exactly once.
    """
    n = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    b, t, h, d = q.shape
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, q.dtype))
    perm = [(j, (j + 1) % n) for j in range(n)]

    q_pos = my * t + jnp.arange(t)  # global positions of the local Q rows

    def accumulate(i, o, m, l, k_cur, v_cur):
        src = (my - i) % n  # whose KV block we hold at step i
        k_pos = src * t + jnp.arange(t)
        if causal:
            mask = q_pos[:, None] >= k_pos[None, :]
        else:
            mask = jnp.ones((t, t), bool)
        bm, bl, bo = _block_attn(q, k_cur, v_cur, scale, mask)
        m_new = jnp.maximum(m, bm)
        # Correction factors; exp(-inf - -inf)=nan guard via where.
        c_old = jnp.where(jnp.isfinite(m), jnp.exp(m - m_new), 0.0)
        c_blk = jnp.where(jnp.isfinite(bm), jnp.exp(bm - m_new), 0.0)
        l = l * c_old + bl * c_blk
        o = (o * c_old.transpose(0, 2, 1)[..., None]
             + bo * c_blk.transpose(0, 2, 1)[..., None])
        return o, m_new, l

    def step(i, carry):
        o, m, l, k_cur, v_cur = carry
        o, m, l = accumulate(i, o, m, l, k_cur, v_cur)
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return o, m, l, k_nxt, v_nxt

    o0 = jnp.zeros_like(q)
    m0 = jnp.full((b, h, t), -jnp.inf, q.dtype)
    l0 = jnp.zeros((b, h, t), q.dtype)
    # n-1 rotating steps, then the final block WITHOUT the trailing
    # ppermute pair (its result would be discarded — dead ICI traffic).
    o, m, l, k_last, v_last = jax.lax.fori_loop(0, n - 1, step, (o0, m0, l0, k, v))
    o, m, l = accumulate(n - 1, o, m, l, k_last, v_last)
    denom = jnp.where(l > 0, l, 1.0).transpose(0, 2, 1)[..., None]
    return o / denom


# ---------------------------------------------------------------------------
# Ring attention with the pallas flash kernels as the per-shard computation
# (r3): each (Q-shard x KV-block) partial runs the fused MXU kernel instead
# of dense einsums; per-block (o, lse) pairs merge with log-sum-exp algebra.
# Backward is its OWN ring pass (Liu & Abbeel §3.2) reusing the block-level
# FlashAttention-2 kernels: the dk/dv accumulators rotate WITH their K/V
# blocks so every gradient lands home after n permutes, and dq accumulates
# locally — wired through jax.custom_vjp, so AD never needs to transpose a
# ppermute.
# ---------------------------------------------------------------------------


def _to3(x):
    b, t, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)


def _from3(x3, b, h):
    bh, t, d = x3.shape
    return x3.reshape(b, h, t, d).transpose(0, 2, 1, 3)


def _ring_cases(my, src, causal):
    """0 = full block (src strictly before my), 1 = diagonal (causal
    within the block), 2 = skip (entirely above the causal diagonal)."""
    if not causal:
        return jnp.int32(0)
    return jnp.where(src == my, 1, jnp.where(src < my, 0, 2)).astype(jnp.int32)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def ring_flash_attention_sharded(q, k, v, axis_name: str, causal: bool = False):
    """Flash-kernel ring attention body (run INSIDE shard_map): local
    shards [B, T_local, H, D] → local output shard. Exact attention —
    matches :func:`ring_attention_sharded` / dense to numerical
    precision, at flash-kernel speed and O(T_local) memory per step."""
    o3, _ = _ring_flash_fwd_core(q, k, v, axis_name, causal)
    return _from3(o3, q.shape[0], q.shape[2])


def _ring_flash_fwd_core(q, k, v, axis_name, causal):
    from fedml_tpu.ops.flash_attention import _SUB, NEG_INF, _auto_blk, _fwd

    n = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    b, t, h, d = q.shape
    scale = 1.0 / (d ** 0.5)
    # Divisor-aligned blocks: the pallas grid is t//blk, so a non-divisor
    # block (e.g. T_local=384 with a clamped 256) would silently drop the
    # tail rows of the shard. _auto_blk mirrors flash_attention's guard.
    bq, bk = _auto_blk(t, 256), _auto_blk(t, 512)
    perm = [(j, (j + 1) % n) for j in range(n)]
    q3 = _to3(q)
    bh = b * h

    def block(kind, k3, v3):
        def full(_):
            return _fwd(q3, k3, v3, scale, False, bq, bk)

        def diag(_):
            return _fwd(q3, k3, v3, scale, True, bq, bk)

        def skip(_):
            return (jnp.zeros_like(q3),
                    jnp.full((bh, _SUB, t), NEG_INF, jnp.float32))

        return jax.lax.switch(kind, (full, diag, skip), None)

    def accumulate(i, o_acc, lse_acc, k_cur, v_cur):
        src = (my - i) % n
        o_b3, lse_b = block(_ring_cases(my, src, causal),
                            _to3(k_cur), _to3(v_cur))
        lse_b = lse_b[:, 0, :]  # [bh, t]
        lse_new = jnp.logaddexp(lse_acc, lse_b)
        w_a = jnp.exp(lse_acc - lse_new)[..., None]
        w_b = jnp.exp(lse_b - lse_new)[..., None]
        # f32 rescale-and-add: with bf16 inputs the per-step rounding
        # would otherwise compound across ring steps (the backward's
        # accumulators are f32 for the same reason).
        return (o_acc * w_a + o_b3.astype(jnp.float32) * w_b), lse_new

    def step(i, carry):
        o_acc, lse_acc, k_cur, v_cur = carry
        o_acc, lse_acc = accumulate(i, o_acc, lse_acc, k_cur, v_cur)
        return (o_acc, lse_acc,
                jax.lax.ppermute(k_cur, axis_name, perm),
                jax.lax.ppermute(v_cur, axis_name, perm))

    o0 = jnp.zeros_like(q3, jnp.float32)
    lse0 = jnp.full((bh, t), NEG_INF, jnp.float32)
    # n-1 rotating steps + the final block without the dead trailing permute.
    o_acc, lse_acc, k_last, v_last = jax.lax.fori_loop(
        0, n - 1, step, (o0, lse0, k, v))
    o_acc, lse_acc = accumulate(n - 1, o_acc, lse_acc, k_last, v_last)
    return o_acc.astype(q.dtype), lse_acc


def _ring_flash_vjp_fwd(q, k, v, axis_name, causal):
    o3, lse = _ring_flash_fwd_core(q, k, v, axis_name, causal)
    return (_from3(o3, q.shape[0], q.shape[2]),
            (q, k, v, o3, lse))


def _ring_flash_vjp_bwd(axis_name, causal, res, do):
    """Backward ring pass: (k, v, dk_acc, dv_acc) rotate together — after
    n permutes every dk/dv accumulator is back on its owner with every
    Q-shard's contribution; dq accumulates locally."""
    from fedml_tpu.ops.flash_attention import _SUB, _auto_blk, _bwd

    q, k, v, o3, lse = res
    n = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    b, t, h, d = q.shape
    scale = 1.0 / (d ** 0.5)
    bq, bk = _auto_blk(t, 256), _auto_blk(t, 512)  # divisor-aligned (see fwd)
    perm = [(j, (j + 1) % n) for j in range(n)]
    q3, do3 = _to3(q), _to3(do)
    lse_sub = jnp.broadcast_to(lse[:, None, :], (lse.shape[0], _SUB,
                                                 lse.shape[1]))

    def block_bwd(kind, k3, v3):
        def run(causal_flag):
            return lambda _: _bwd(q3, k3, v3, o3, lse_sub, do3, scale,
                                  causal_flag, bq, bk)

        def skip(_):
            return (jnp.zeros_like(q3), jnp.zeros_like(k3),
                    jnp.zeros_like(v3))

        return jax.lax.switch(kind, (run(False), run(True), skip), None)

    def step(i, carry):
        dq_acc, k_cur, v_cur, dk_cur, dv_cur = carry
        src = (my - i) % n
        dq_c, dk_c, dv_c = block_bwd(_ring_cases(my, src, causal),
                                     _to3(k_cur), _to3(v_cur))
        dq_acc = dq_acc + dq_c.astype(dq_acc.dtype)
        dk_cur = dk_cur + _from3(dk_c, b, h).astype(dk_cur.dtype)
        dv_cur = dv_cur + _from3(dv_c, b, h).astype(dv_cur.dtype)
        return (dq_acc,
                jax.lax.ppermute(k_cur, axis_name, perm),
                jax.lax.ppermute(v_cur, axis_name, perm),
                jax.lax.ppermute(dk_cur, axis_name, perm),
                jax.lax.ppermute(dv_cur, axis_name, perm))

    dq0 = jnp.zeros_like(q3, jnp.float32)
    carry = (dq0, k, v, jnp.zeros_like(k, jnp.float32),
             jnp.zeros_like(v, jnp.float32))
    # Full n steps each ending in a permute: the dk/dv accumulators make a
    # complete loop and land back on their owners.
    dq_acc, _, _, dk_home, dv_home = jax.lax.fori_loop(0, n, step, carry)
    return (_from3(dq_acc, b, h).astype(q.dtype),
            dk_home.astype(k.dtype), dv_home.astype(v.dtype))


ring_flash_attention_sharded.defvjp(_ring_flash_vjp_fwd, _ring_flash_vjp_bwd)


def make_ring_flash_attention(mesh, axis_name: str = "sp",
                              causal: bool = False):
    """[B, T, H, D] full arrays → exact attention with the pallas flash
    kernels per shard; sequence axis sharded over ``mesh[axis_name]``.
    Drop-in for :func:`make_ring_attention` (same pluggable attn_fn
    contract), differentiable."""

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(None, axis_name), P(None, axis_name), P(None, axis_name)),
        out_specs=P(None, axis_name),
        check_vma=False,
    )
    def attn(q, k, v):
        return ring_flash_attention_sharded(q, k, v, axis_name,
                                            causal=causal)

    return attn


def make_ring_attention(mesh, axis_name: str = "sp", causal: bool = False):
    """[B, T, H, D] full arrays → exact attention, sequence axis sharded
    over ``mesh[axis_name]``; output replicates the input sharding."""

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(None, axis_name), P(None, axis_name), P(None, axis_name)),
        out_specs=P(None, axis_name),
        check_vma=False,
    )
    def attn(q, k, v):
        return ring_attention_sharded(q, k, v, axis_name, causal=causal)

    return attn


def reference_attention(q, k, v, causal: bool = False):
    """Naive full-matrix attention (test oracle)."""
    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.asarray(d, q.dtype))
    if causal:
        tq, tk = s.shape[-2], s.shape[-1]
        mask = jnp.arange(tq)[:, None] >= jnp.arange(tk)[None, :]
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)
