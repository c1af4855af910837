"""Flash attention as pallas TPU kernels (single-chip hot path).

Two algorithms, chosen by what the call can see (no option):

**Streaming softmax** (full and causal attention). Fused blockwise attention:
the [T, T] score matrix is never materialized and VMEM usage is block-sized
regardless of sequence length. Forward stores only the output and row
log-sum-exp; backward recomputes probabilities blockwise (FlashAttention-2
style: dP = dO·Vᵀ, dS = P∘(dP − δ), δ = rowsum(dO∘O)) in three kernels (fwd,
dq, dkv) wired through ``jax.custom_vjp``. Kernel structure (the
TPU-idiomatic pattern): 3-D grid with the contraction block dim INNERMOST —
TPU grids iterate sequentially over the last dimension, so VMEM scratch
accumulators carry across it; the kernel initializes scratch on the first
inner step and writes the output block on the last. K/V stream through VMEM
one block per step (HBM→VMEM pipelined by pallas), which is what keeps
T=64k+ within the 16 MB VMEM budget. Layout: [B, T, H, D] public API
(matching fedml_tpu.parallel.ring_attention), flattened to [B*H, T, D]; the
log-sum-exp / delta vectors are stored [B*H, 8, T] (8 identical sublanes) to
satisfy the TPU (8, 128) tiling rule for 1-D-per-row outputs.

**The grouped band** (``window=``: causal sliding-window attention, PR 37).
A band no wider than a sub-block is all the keys a query has, so nothing
streams: the softmax is one pass (no running maximum, no rescale, no scratch)
and every grid step stands alone. The three kernels (``window_band_fwd``,
``window_band_dq``, ``window_band_dkv``) read q ``[B, T, Hq*D]`` and k, v
``[B, T, Hkv*D]`` where the projections left them (a free reshape of the
public layout: no transpose, no repeat of a key-value head over its group)
and write o where the output projection reads it. Grid ``(B, Hkv, T /
block)``: a step holds one key-value head's rows of a block, the sub-block
before them (a halo: a second ``BlockSpec``, masked off at the sequence's
start) and the whole group of ``Hq / Hkv`` query heads that share them;
inside, a static loop over the group's heads and the block's sub-blocks
multiplies each sub-block of queries by its own keys and the sub-block
before only. ``dk`` and ``dv`` take a key block, the queries that see it (its
own and the halo AFTER it) and sum the group in VMEM, so they leave with
``Hkv`` heads. The row log-sum-exp and delta are ``[B, Hkv, G, T]``: the
sublanes are the group's heads; delta is made by the dq kernel from ``do``
and o as they lie (XLA's pass over them cost a float32 relayout).

On the CPU backend the kernels run in interpreter mode so the same code path
is testable on the CPU mesh (ops/platform.py; any other non-TPU backend
raises); the streaming kernels compose under ring attention as the per-shard
computation.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fedml_tpu.ops.platform import pallas_interpret

NEG_INF = -1e30
_SUB = 8  # sublane replication for per-row vectors
#: sub-blocks of queries a grid step of the band kernels holds. A sub-block is
#: the window rounded up to whole lanes (128 at a window of 128) and computes
#: against two sub-blocks of keys, so a step of 4 does the arithmetic of 128 x
#: 256 blocks in the step count of 512-row blocks. What that answers (a v5e,
#: 2 clients x 4,096 tokens, 64 heads of 128, window 128; PERF.md section 6,
#: PR 34: forward / forward + backward ms of the streaming kernels' window
#: grids, which PR 37 removed): 128 x 128 blocks 8.1 / 18.8, 256 x 256 7.0 /
#: 16.4, 512 x 512 6.2 / 14.6: a quarter of the scores LOST to four times the
#: grid steps, so the saving has to come from geometry inside a large step.
#: The band at 1 client x 4,096 tokens, 64 query over 8 key-value heads (my
#: chip run, PR 37; the 512 x 512 grids over repeated keys there: 2.69 /
#: 6.85): 1 sub-block a step 0.76 / 2.10, 2 0.69 / 1.96, 4 0.66 / 1.90, 8
#: 0.65 / 1.88, layout copies of the 4-D operands included.
BAND_SUB_BLOCKS = 4

# Grid = (batch·heads, outer block dim, contraction block dim). Only the
# innermost (contraction) dim is sequential — scratch accumulators carry
# across it; telling Mosaic the outer two are parallel frees its scheduler.
# (A vmap over clients prepends a grid dim; the lowering adds its entry.)
_DIMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _blk(t: int, want: int = 128) -> int:
    return min(want, t)


def _auto_blk(t: int, want: int) -> int:
    """Largest divisor of ``t`` that is ≤ ``want`` and sublane-aligned
    (multiple of 8) — default block sizes must accept every T the old
    fixed-128 defaults accepted (e.g. T=384 → 192, not a ValueError)."""
    if t <= want:
        return t
    for b in range(want, 7, -1):
        if t % b == 0 and b % 8 == 0:
            return b
    return t  # no aligned divisor ≤ want: single block


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32)


def _visible(s, qi, ki, blk_q, blk_k):
    """``s`` with the keys after a query at ``NEG_INF``."""
    q_pos = qi * blk_q + jax.lax.broadcasted_iota(jnp.int32, (blk_q, 1), 0)
    k_pos = ki * blk_k + jax.lax.broadcasted_iota(jnp.int32, (1, blk_k), 1)
    return jnp.where(q_pos >= k_pos, s, NEG_INF)


# ---------------------------------------------------------------------------
# Forward: grid (bh, n_q, n_k), scratch carries (acc, m, l) across n_k
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_s, l_s, *,
                scale, causal, blk_q, blk_k):
    qi, ki = pl.program_id(1), pl.program_id(2)
    n_k = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)

    # Causal: blocks entirely above the diagonal contribute nothing.
    diag_ok = (qi + 1) * blk_q > ki * blk_k if causal else True

    @pl.when(diag_ok)
    def _compute():
        # Dots run in the INPUT dtype (bf16 stays bf16 on the MXU — ~4x the
        # fp32 matmul rate) with f32 accumulation via preferred_element_type;
        # softmax statistics stay f32.
        q, k, v = q_ref[0], k_ref[0], v_ref[0]
        s = _dot(q, k, (((1,), (1,)))) * scale  # [blk_q, blk_k] f32
        if causal:
            s = _visible(s, qi, ki, blk_q, blk_k)
        m_prev, l_prev = m_s[...], l_s[...]
        m_blk = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_blk)
        p = jnp.exp(s - m_new)
        c = jnp.exp(m_prev - m_new)
        m_s[...] = m_new
        l_s[...] = l_prev * c + jnp.sum(p, axis=1, keepdims=True)
        acc[...] = acc[...] * c + _dot(p.astype(v.dtype), v, ((1,), (0,)))

    @pl.when(ki == n_k - 1)
    def _finalize():
        l = l_s[...]
        l_safe = jnp.where(l > 0, l, 1.0)
        o_ref[0] = (acc[...] / l_safe).astype(o_ref.dtype)
        lse = (m_s[...] + jnp.log(l_safe))[:, 0]
        lse_ref[0] = jnp.broadcast_to(lse[None, :], (_SUB, blk_q))


def _fwd(q3, k3, v3, scale, causal, blk_q, blk_k):
    bh, t, d = q3.shape
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               blk_q=blk_q, blk_k=blk_k)
    return pl.pallas_call(
        kernel,
        grid=(bh, t // blk_q, t // blk_k),
        in_specs=[
            pl.BlockSpec((1, blk_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, blk_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, blk_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, blk_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, _SUB, blk_q), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), q3.dtype),
            jax.ShapeDtypeStruct((bh, _SUB, t), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((blk_q, d), jnp.float32),
            pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, 1), jnp.float32),
        ],
        compiler_params=_DIMS,
        interpret=pallas_interpret(),
    )(q3, k3, v3)


# ---------------------------------------------------------------------------
# Backward dq: grid (bh, n_q, n_k), dq accumulates across n_k
# ---------------------------------------------------------------------------

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, acc, *,
               scale, causal, blk_q, blk_k):
    qi, ki = pl.program_id(1), pl.program_id(2)
    n_k = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    diag_ok = (qi + 1) * blk_q > ki * blk_k if causal else True

    @pl.when(diag_ok)
    def _compute():
        q, do = q_ref[0], do_ref[0]
        lse = lse_ref[0, 0][:, None]
        delta = delta_ref[0, 0][:, None]
        k, v = k_ref[0], v_ref[0]
        s = _dot(q, k, ((1,), (1,))) * scale
        if causal:
            s = _visible(s, qi, ki, blk_q, blk_k)
        p = jnp.exp(s - lse)
        dp = _dot(do, v, ((1,), (1,)))
        ds = (p * (dp - delta)).astype(q.dtype)
        acc[...] += _dot(ds, k, ((1,), (0,))) * scale

    @pl.when(ki == n_k - 1)
    def _finalize():
        dq_ref[0] = acc[...].astype(dq_ref.dtype)


# ---------------------------------------------------------------------------
# Backward dk/dv: grid (bh, n_k, n_q), dk/dv accumulate across n_q
# ---------------------------------------------------------------------------

def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *,
                scale, causal, blk_q, blk_k):
    ki, qi = pl.program_id(1), pl.program_id(2)
    n_q = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    diag_ok = (qi + 1) * blk_q > ki * blk_k if causal else True

    @pl.when(diag_ok)
    def _compute():
        k, v = k_ref[0], v_ref[0]
        q, do = q_ref[0], do_ref[0]
        lse = lse_ref[0, 0][:, None]
        delta = delta_ref[0, 0][:, None]
        s = _dot(q, k, ((1,), (1,))) * scale
        if causal:
            s = _visible(s, qi, ki, blk_q, blk_k)
        p = jnp.exp(s - lse)  # [blk_q, blk_k] f32
        dv_acc[...] += _dot(p.astype(do.dtype), do, ((0,), (0,)))
        dp = _dot(do, v, ((1,), (1,)))
        ds = (p * (dp - delta)).astype(q.dtype)
        dk_acc[...] += _dot(ds, q, ((0,), (0,))) * scale

    @pl.when(qi == n_q - 1)
    def _finalize():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd(q3, k3, v3, o3, lse, do3, scale, causal, blk_q, blk_k):
    bh, t, d = q3.shape
    delta = jnp.sum(do3.astype(jnp.float32) * o3.astype(jnp.float32), axis=-1)
    delta = jnp.broadcast_to(delta[:, None, :], (bh, _SUB, t))
    static = dict(scale=scale, causal=causal, blk_q=blk_q, blk_k=blk_k)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **static),
        grid=(bh, t // blk_q, t // blk_k),
        in_specs=[
            pl.BlockSpec((1, blk_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, blk_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, blk_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, blk_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, _SUB, blk_q), lambda b, i, j: (b, 0, i)),
            pl.BlockSpec((1, _SUB, blk_q), lambda b, i, j: (b, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, blk_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, t, d), q3.dtype),
        scratch_shapes=[pltpu.VMEM((blk_q, d), jnp.float32)],
        compiler_params=_DIMS,
        interpret=pallas_interpret(),
    )(q3, k3, v3, do3, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, **static),
        grid=(bh, t // blk_k, t // blk_q),
        in_specs=[
            pl.BlockSpec((1, blk_q, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, blk_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, blk_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, blk_q, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, _SUB, blk_q), lambda b, j, i: (b, 0, i)),
            pl.BlockSpec((1, _SUB, blk_q), lambda b, j, i: (b, 0, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, blk_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, blk_k, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), k3.dtype),
            jax.ShapeDtypeStruct((bh, t, d), v3.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((blk_k, d), jnp.float32),
            pltpu.VMEM((blk_k, d), jnp.float32),
        ],
        compiler_params=_DIMS,
        interpret=pallas_interpret(),
    )(q3, k3, v3, do3, lse, delta)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q3, k3, v3, causal, blocks, scale):
    blk_q, blk_k = blocks[:2]
    o, _ = _fwd(q3, k3, v3, scale, causal, blk_q, blk_k)
    return o


def _flash_fwd(q3, k3, v3, causal, blocks, scale):
    blk_q, blk_k = blocks[:2]
    o, lse = _fwd(q3, k3, v3, scale, causal, blk_q, blk_k)
    return o, (q3, k3, v3, o, lse)


def _flash_bwd(causal, blocks, scale, res, do3):
    q3, k3, v3, o3, lse = res
    bwd_blk_q, bwd_blk_k = blocks[2:]
    return _bwd(q3, k3, v3, o3, lse, do3, scale, causal,
                bwd_blk_q, bwd_blk_k)


_flash.defvjp(_flash_fwd, _flash_bwd)


# ---------------------------------------------------------------------------
# The grouped band: causal sliding-window attention where the window is no
# wider than a sub-block of ``sub`` rows. Query sub-block ``n`` then sees key
# sub-blocks ``n - 1`` and ``n`` and nothing else, so its softmax is one pass.
# Arrays stay as the projections wrote them: q, o, do, dq ``[B, T, Hq*D]``,
# k, v, dk, dv ``[B, T, Hkv*D]``; a grid step ``(b, h, i)`` takes key-value
# head ``h``'s column block of ``D`` and its group's column block of ``G*D``.
# ---------------------------------------------------------------------------

_BAND_DIMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel"))


def _band_bias(shape, q_dim, q_first, window):
    """0 where a query sees a key and ``NEG_INF`` where not, over a tile
    whose ``q_dim`` axis counts queries from position ``q_first`` and whose
    other axis counts keys from position 0: ``0 <= q - k < window``."""
    back = (q_first + jax.lax.broadcasted_iota(jnp.int32, shape, q_dim)
            - jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_dim))
    return jnp.where((back >= 0) & (back < window), 0.0, NEG_INF)


def _band_keys(k_ref, kh_ref, v_ref, vh_ref, sub, window):
    """For the kernels that walk query sub-blocks: the block's keys and values
    with the sub-block before them on top (query sub-block ``j`` sees rows
    ``[j * sub, (j + 2) * sub)`` of these), the band's bias over such a pair,
    and the bias of the block's first sub-block: at the sequence's start
    there is nothing before it (the halo is the clamped block 0 again)."""
    k = jnp.concatenate([kh_ref[0], k_ref[0]], axis=0)
    v = jnp.concatenate([vh_ref[0], v_ref[0]], axis=0)
    bias = _band_bias((sub, 2 * sub), 0, sub, window)
    own = jax.lax.broadcasted_iota(jnp.int32, (sub, 2 * sub), 1) >= sub
    return k, v, bias, jnp.where((pl.program_id(2) > 0) | own, bias, NEG_INF)


def _band_fwd_kernel(q_ref, k_ref, kh_ref, v_ref, vh_ref, o_ref, lse_ref, *,
                     scale, window, sub, n_sub, group, d):
    k, v, bias, first = _band_keys(k_ref, kh_ref, v_ref, vh_ref, sub, window)
    for j in range(n_sub):
        rows = slice(j * sub, (j + 1) * sub)
        kj, vj = k[j * sub:(j + 2) * sub], v[j * sub:(j + 2) * sub]
        seen = bias if j else first
        lse = []
        for g in range(group):
            q = q_ref[0, rows, g * d:(g + 1) * d]
            s = _dot(q, kj, ((1,), (1,))) * scale + seen    # [sub, 2 sub] f32
            m = jnp.max(s, axis=1, keepdims=True)
            p = jnp.exp(s - m)
            l = jnp.sum(p, axis=1, keepdims=True)   # >= 1: a query sees itself
            o = _dot(p.astype(vj.dtype), vj, ((1,), (0,)))
            o_ref[0, rows, g * d:(g + 1) * d] = (o / l).astype(o_ref.dtype)
            lse.append((m + jnp.log(l))[:, 0][None, :])
        lse_ref[0, 0, :, rows] = jnp.concatenate(lse, axis=0)


def _band_dq_kernel(q_ref, k_ref, kh_ref, v_ref, vh_ref, do_ref, o_ref, lse_ref,
                    dq_ref, delta_ref, *, scale, window, sub, n_sub, group, d):
    # delta = rowsum(dO o O) is made here, a column as this kernel needs it,
    # and leaves as a row vector beside the log-sum-exp for the dkv kernel
    k, v, bias, first = _band_keys(k_ref, kh_ref, v_ref, vh_ref, sub, window)
    for j in range(n_sub):
        rows = slice(j * sub, (j + 1) * sub)
        kj, vj = k[j * sub:(j + 2) * sub], v[j * sub:(j + 2) * sub]
        seen = bias if j else first
        deltas = []
        for g in range(group):
            cols = slice(g * d, (g + 1) * d)
            q, do = q_ref[0, rows, cols], do_ref[0, rows, cols]
            lse = lse_ref[0, 0, g, rows][:, None]
            delta = jnp.sum(do.astype(jnp.float32) * o_ref[0, rows, cols].astype(
                jnp.float32), axis=1, keepdims=True)
            s = _dot(q, kj, ((1,), (1,))) * scale + seen
            p = jnp.exp(s - lse)
            dp = _dot(do, vj, ((1,), (1,)))
            ds = (p * (dp - delta)).astype(q.dtype)
            dq_ref[0, rows, cols] = (
                _dot(ds, kj, ((1,), (0,))) * scale).astype(dq_ref.dtype)
            deltas.append(delta[:, 0][None, :])
        delta_ref[0, 0, :, rows] = jnp.concatenate(deltas, axis=0)


def _band_dkv_kernel(q_ref, qh_ref, k_ref, v_ref, do_ref, doh_ref, lse_ref,
                     lseh_ref, delta_ref, deltah_ref, dk_ref, dv_ref, *,
                     scale, window, sub, n_sub, group, d):
    # transposed scores, keys down and queries across: the row vectors lse
    # and delta broadcast down the sublanes as they lie, and both products
    # into dk, dv contract the lanes. Key sub-block j is seen by query
    # sub-blocks j and j + 1; the last one's second is the halo after the
    # block, which is past the sequence's end in the last block: masked off.
    bias = _band_bias((sub, 2 * sub), 1, 0, window)
    inside = jax.lax.broadcasted_iota(jnp.int32, (sub, 2 * sub), 1) < sub
    last = jnp.where((pl.program_id(2) < pl.num_programs(2) - 1) | inside,
                     bias, NEG_INF)

    def pair(ref, halo_ref, j, axis, at):
        """Query sub-blocks ``j`` and ``j + 1`` along ``axis`` of ``ref[at]``."""
        if j < n_sub - 1:
            return ref[at(slice(j * sub, (j + 2) * sub))]
        return jnp.concatenate([ref[at(slice(j * sub, (j + 1) * sub))],
                                halo_ref[at(slice(None))]], axis=axis)

    for j in range(n_sub):
        rows = slice(j * sub, (j + 1) * sub)
        seen = bias if j < n_sub - 1 else last
        k, v = k_ref[0, rows, :], v_ref[0, rows, :]
        dk = jnp.zeros((sub, d), jnp.float32)
        dv = jnp.zeros((sub, d), jnp.float32)
        for g in range(group):
            heads = lambda r, g=g: (0, r, slice(g * d, (g + 1) * d))  # noqa: E731
            vector = lambda r, g=g: (0, 0, slice(g, g + 1), r)  # noqa: E731
            q, do = pair(q_ref, qh_ref, j, 0, heads), pair(
                do_ref, doh_ref, j, 0, heads)
            lse, delta = pair(lse_ref, lseh_ref, j, 1, vector), pair(
                delta_ref, deltah_ref, j, 1, vector)
            s = _dot(k, q, ((1,), (1,))) * scale + seen     # [sub, 2 sub] f32
            p = jnp.exp(s - lse)
            dv += _dot(p.astype(do.dtype), do, ((1,), (0,)))
            dp = _dot(v, do, ((1,), (1,)))
            ds = (p * (dp - delta)).astype(q.dtype)
            dk += _dot(ds, q, ((1,), (0,)))
        dk_ref[0, rows, :] = (dk * scale).astype(dk_ref.dtype)
        dv_ref[0, rows, :] = dv.astype(dv_ref.dtype)


class _Band(NamedTuple):
    """What a band call is compiled for (hashable: one static argument)."""

    hq: int
    hkv: int
    scale: float
    window: int
    sub: int    # rows of a sub-block, at least the window
    blk: int    # rows of a grid step's block, whole sub-blocks


def _band_call(kernel, name, band: _Band, q, k):
    """``pallas_call`` partly applied for one of the three band kernels, the
    block specs they share on the grid ``(b, h, i)``, and the shape of the
    row vectors. Specs: a block of rows of the group's heads (``heads``) and
    of the key-value head (``head``), the key-value head's sub-block before
    the block (clamped at the start), the group's sub-block after it (clamped
    at the end), and blocks of the ``[B, Hkv, G, T]`` row vectors likewise."""
    b, t, _ = q.shape
    group, d = band.hq // band.hkv, k.shape[2] // band.hkv
    sub, blk, n_sub = band.sub, band.blk, band.blk // band.sub
    last = t // sub - 1

    def after(i):
        return jnp.minimum((i + 1) * n_sub, last)

    spec = dict(
        heads=pl.BlockSpec((1, blk, group * d), lambda b, h, i: (b, i, h)),
        head=pl.BlockSpec((1, blk, d), lambda b, h, i: (b, i, h)),
        head_before=pl.BlockSpec((1, sub, d), lambda b, h, i: (
            b, jnp.maximum(i * n_sub - 1, 0), h)),
        heads_after=pl.BlockSpec((1, sub, group * d), lambda b, h, i: (
            b, after(i), h)),
        rows=pl.BlockSpec((1, 1, group, blk), lambda b, h, i: (b, h, 0, i)),
        rows_after=pl.BlockSpec((1, 1, group, sub), lambda b, h, i: (
            b, h, 0, after(i))),
    )
    call = functools.partial(
        pl.pallas_call,
        functools.partial(kernel, scale=band.scale, window=band.window,
                          sub=sub, n_sub=n_sub, group=group, d=d),
        grid=(b, band.hkv, t // blk), compiler_params=_BAND_DIMS,
        interpret=pallas_interpret(), name=name)
    return call, spec, (b, band.hkv, group, t)


@functools.partial(jax.jit, static_argnames="band")
def _band_fwd(q, k, v, band):
    """``(o, lse)``."""
    call, spec, rows = _band_call(_band_fwd_kernel, "window_band_fwd", band,
                                  q, k)
    return call(
        in_specs=[spec["heads"], spec["head"], spec["head_before"],
                  spec["head"], spec["head_before"]],
        out_specs=[spec["heads"], spec["rows"]],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(rows, jnp.float32)],
    )(q, k, k, v, v)


@functools.partial(jax.jit, static_argnames="band")
def _band_dq(q, k, v, do, o, lse, band):
    """``(dq, delta)``."""
    call, spec, rows = _band_call(_band_dq_kernel, "window_band_dq", band,
                                  q, k)
    return call(
        in_specs=[spec["heads"], spec["head"], spec["head_before"],
                  spec["head"], spec["head_before"], spec["heads"],
                  spec["heads"], spec["rows"]],
        out_specs=[spec["heads"], spec["rows"]],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(rows, jnp.float32)],
    )(q, k, k, v, v, do, o, lse)


@functools.partial(jax.jit, static_argnames="band")
def _band_dkv(q, k, v, do, lse, delta, band):
    """``(dk, dv)``."""
    call, spec, _ = _band_call(_band_dkv_kernel, "window_band_dkv", band,
                               q, k)
    return call(
        in_specs=[spec["heads"], spec["heads_after"], spec["head"],
                  spec["head"], spec["heads"], spec["heads_after"],
                  spec["rows"], spec["rows_after"],
                  spec["rows"], spec["rows_after"]],
        out_specs=[spec["head"], spec["head"]],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
    )(q, q, k, v, do, do, lse, lse, delta, delta)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _band(q, k, v, band):
    return _band_fwd(q, k, v, band)[0]


def _band_vjp_fwd(q, k, v, band):
    o, lse = _band_fwd(q, k, v, band)
    return o, (q, k, v, o, lse)


def _band_vjp_bwd(band, res, do):
    q, k, v, o, lse = res
    dq, delta = _band_dq(q, k, v, do, o, lse, band)
    dk, dv = _band_dkv(q, k, v, do, lse, delta, band)
    return dq, dk, dv


_band.defvjp(_band_vjp_fwd, _band_vjp_bwd)


def _band_blocks(t: int, window: int, sub: int | None, blk: int | None):
    """Rows of a sub-block and of a grid step's block for a window shorter
    than the sequence: the window rounded up to whole lanes, and the most
    sub-blocks up to ``BAND_SUB_BLOCKS`` that divide the sequence."""
    sub = min(sub or -(-window // 128) * 128, t)
    if sub < window or t % sub:
        raise ValueError(
            f"a window of {window} over {t} tokens needs sub-blocks of at "
            f"least the window that divide the sequence, not {sub}; pad the "
            "sequence")
    if blk is None:
        blk = next(n * sub for n in range(BAND_SUB_BLOCKS, 0, -1)
                   if t % (n * sub) == 0)
    blk = min(blk, t)
    if blk % sub or t % blk:
        raise ValueError(
            f"a block of {blk} rows must hold whole sub-blocks of {sub} and "
            f"divide the sequence length {t}")
    return sub, blk


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------

def flash_attention(q, k, v, causal: bool = False, block_q: int | None = None,
                    block_k: int | None = None,
                    bwd_block_q: int | None = None,
                    bwd_block_k: int | None = None,
                    scale: float | None = None, window: int | None = None):
    """Fused attention: q [B, T, H, D], k/v [B, T, Hkv, D] → o [B, T, H, D].

    ``scale`` multiplies ``q k^T`` before the softmax: ``1/sqrt(D)`` unless
    the caller's model states another (Granite's ``attention_multiplier``).

    ``window`` (with ``causal``): query ``i`` sees key ``j`` iff ``0 <= i - j
    < window``. A window shorter than the sequence takes the grouped band
    kernels (module docstring): k and v may have ``Hkv`` heads, any divisor
    of ``H``, head ``n`` of q reading head ``n // (H / Hkv)`` of k and v as
    ``jnp.repeat`` over the head axis would lay them; nothing is transposed
    or repeated, and ``dk``, ``dv`` come back with ``Hkv`` heads. There
    ``block_k`` is the sub-block (default: the window rounded up to a multiple
    of 128; it must hold the window and divide T) and ``block_q`` the rows of
    a grid step (default: up to ``BAND_SUB_BLOCKS`` sub-blocks); the backward
    kernels use the same two. Compiled for the TPU the band wants D a
    multiple of 128 (a head is a column block of ``[B, T, H*D]``). A window
    no shorter than the sequence is causal attention and takes the streaming
    kernels below (grouped k, v are repeated for them: they read a head a
    grid row).

    Without a window Hkv = H, and T must be a multiple of the (clamped) block
    sizes; pad upstream if not.
    Differentiable (custom VJP, FlashAttention-2-style backward).

    Default blocks are (512, 1024) at every T (clamped to divisors of
    T): the r4 re-sweep with floor-calibrated timing (v5e through
    the retired attachment, 2026-07-31; not re-measured on this
    benchmark) measured (512, 1024) ahead of the r3-era (256, 512)
    default at EVERY point — fwd +39% @ T=2048, +81% @ 4096; training
    +27% / +42% — the r3 "small blocks win at short T" conclusion was an
    artifact of dispatch-polluted timing (each r3 call carried ~0.1 s of
    fixed per-call dispatch cost in a ~0.15 s measurement). The three backward
    kernels take their own block sizes (``bwd_block_q/k``, defaulting to
    the forward pair — best-of-sweep for training at T ∈ {4096, 8192});
    pass explicit blocks to override. For the MXU rate, feed bf16
    q/k/v: the kernel dots run in the input dtype (f32 accumulation),
    and bf16 is ~4x the fp32 matmul rate.
    """
    b, t, h, d = q.shape
    hkv = k.shape[2]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    if window is not None:
        if not causal or window < 1:
            raise ValueError(
                f"window={window} needs causal=True and at least one key")
        if h % hkv or v.shape[2] != hkv:
            raise ValueError(
                f"{h} query heads over {hkv} key and {v.shape[2]} value heads")
        if window < t:
            sub, blk = _band_blocks(t, window, block_k, block_q)
            # [B, T, H, D] and [B, T, H*D] are tiled differently in HBM, so
            # this reshape is a copy. Behind the barrier it is one copy of
            # what the caller made (bf16 in the round); without it XLA moves
            # the reshape up through the caller's elementwise float32 passes
            # and copies each of their operands (PERF.md section 6, PR 37)
            q, k, v = jax.lax.optimization_barrier((q, k, v))
            o = _band(q.reshape(b, t, h * d), k.reshape(b, t, hkv * d),
                      v.reshape(b, t, hkv * d),
                      _Band(h, hkv, float(scale), window, sub, blk))
            return o.reshape(b, t, h, d)
        # every earlier key is inside it
        k, v = (jnp.repeat(a, h // hkv, axis=2) if hkv != h else a
                for a in (k, v))
    if block_q is None:
        block_q = _auto_blk(t, 512)
    if block_k is None:
        block_k = _auto_blk(t, 1024)
    blk_q = _blk(t, block_q)
    blk_k = _blk(t, block_k)
    bwd_q = _blk(t, bwd_block_q) if bwd_block_q else blk_q
    bwd_k = _blk(t, bwd_block_k) if bwd_block_k else blk_k
    for bq, bk in ((blk_q, blk_k), (bwd_q, bwd_k)):
        if t % bq or t % bk:
            raise ValueError(
                f"sequence length {t} must be a multiple of block sizes "
                f"({bq}, {bk}); pad the sequence")

    def to3(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)

    o3 = _flash(to3(q), to3(k), to3(v), causal, (blk_q, blk_k, bwd_q, bwd_k),
                float(scale))
    return o3.reshape(b, h, t, d).transpose(0, 2, 1, 3)
