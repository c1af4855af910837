"""Flash attention as pallas TPU kernels (single-chip hot path).

Fused blockwise attention with streaming softmax: the [T, T] score matrix
is never materialized and VMEM usage is block-sized regardless of sequence
length. Forward stores only the output and row log-sum-exp; backward
recomputes probabilities blockwise (FlashAttention-2 style: dP = dO·Vᵀ,
dS = P∘(dP − δ), δ = rowsum(dO∘O)) in three kernels (fwd, dq, dkv) wired
through ``jax.custom_vjp``.

Kernel structure (the TPU-idiomatic pattern): 3-D grid with the
contraction block dim INNERMOST — TPU grids iterate sequentially over the
last dimension, so VMEM scratch accumulators carry across it; the kernel
initializes scratch on the first inner step and writes the output block on
the last. K/V stream through VMEM one block per step (HBM→VMEM pipelined
by pallas), which is what keeps T=64k+ within the 16 MB VMEM budget.

Layout: [B, T, H, D] public API (matching
fedml_tpu.parallel.ring_attention), flattened to [B*H, T, D]; the
log-sum-exp / delta vectors are stored [B*H, 8, T] (8 identical sublanes)
to satisfy the TPU (8, 128) tiling rule for 1-D-per-row outputs. On the
CPU backend the kernels run in interpreter mode so the same code path is
testable on the CPU mesh (ops/platform.py; any other non-TPU backend
raises); composes under ring attention as the per-shard computation.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fedml_tpu.ops.platform import pallas_interpret

NEG_INF = -1e30
_SUB = 8  # sublane replication for per-row vectors
#: rows and keys of a block under a sliding window no wider than it (wider
#: windows take the next power of two): the band of a query block is then two
#: key blocks. Swept on a v5e at 2 clients x 4,096 tokens, 64 heads of 128,
#: window 128 (PERF.md section 6, PR 34; forward / forward + backward ms):
#: 128 x 128 8.1 / 18.8, 256 x 256 7.0 / 16.4, 512 x 256 7.8 / 18.4, 512 x
#: 512 6.2 / 14.6: fewer, larger grid steps win although more of their keys
#: are masked (the causal kernel over all keys: 9.5 / 27.5).
WINDOW_BLOCK = 512

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


# ---------------------------------------------------------------------------
# A sliding window: query ``i`` sees key ``j`` iff ``0 <= i - j < window``.
# The kernels below then visit only the blocks that hold a visible key: the
# grid's innermost axis counts the blocks of ONE outer block's band (the same
# number for every outer block: the widest band's), the index maps and the
# kernels add the band's first block, and a step past the band's end (the
# first rows' bands are shorter) is skipped. ``window=None`` is the causal or
# full kernel as it was: same grid, same index maps, same text.
# ---------------------------------------------------------------------------

def _first_k(qi, blk_q, blk_k, window):
    """The first key block that query block ``qi`` sees."""
    return jnp.maximum(qi * blk_q - (window - 1), 0) // blk_k


def _first_q(ki, blk_q, blk_k):
    """The first query block that sees key block ``ki``."""
    return (ki * blk_k) // blk_q


def _band_k(t, blk_q, blk_k, window) -> int:
    """Key blocks in the widest band of a query block."""
    return max(((i + 1) * blk_q - 1) // blk_k
               - max(i * blk_q - (window - 1), 0) // blk_k + 1
               for i in range(t // blk_q))


def _band_q(t, blk_q, blk_k, window) -> int:
    """Query blocks in the widest band of a key block."""
    return max(min(((j + 1) * blk_k - 1 + window - 1) // blk_q,
                   t // blk_q - 1) - (j * blk_k) // blk_q + 1
               for j in range(t // blk_k))


def _visible(s, qi, ki, blk_q, blk_k, window):
    """``s`` with the keys a query does not see at ``NEG_INF``."""
    q_pos = qi * blk_q + jax.lax.broadcasted_iota(jnp.int32, (blk_q, 1), 0)
    k_pos = ki * blk_k + jax.lax.broadcasted_iota(jnp.int32, (1, blk_k), 1)
    seen = q_pos >= k_pos
    if window is not None:
        seen = seen & (q_pos - k_pos < window)
    return jnp.where(seen, s, NEG_INF)


# ---------------------------------------------------------------------------
# Forward: grid (bh, n_q, n_k), scratch carries (acc, m, l) across n_k
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_s, l_s, *,
                scale, causal, blk_q, blk_k, window=None):
    qi, step = pl.program_id(1), pl.program_id(2)
    n_k = pl.num_programs(2)
    ki = step if window is None else step + _first_k(qi, blk_q, blk_k, window)

    @pl.when(step == 0)
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
            s = _visible(s, qi, ki, blk_q, blk_k, window)
        m_prev, l_prev = m_s[...], l_s[...]
        m_blk = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_blk)
        p = jnp.exp(s - m_new)
        c = jnp.exp(m_prev - m_new)
        m_s[...] = m_new
        l_s[...] = l_prev * c + jnp.sum(p, axis=1, keepdims=True)
        acc[...] = acc[...] * c + _dot(p.astype(v.dtype), v, ((1,), (0,)))

    @pl.when(step == n_k - 1)
    def _finalize():
        l = l_s[...]
        l_safe = jnp.where(l > 0, l, 1.0)
        o_ref[0] = (acc[...] / l_safe).astype(o_ref.dtype)
        lse = (m_s[...] + jnp.log(l_safe))[:, 0]
        lse_ref[0] = jnp.broadcast_to(lse[None, :], (_SUB, blk_q))


def _key_map(t, blk_q, blk_k, window):
    """The index map of a key or value block on a grid ``(bh, n_q, keys)``:
    with a window, block ``j`` of query block ``i``'s band (clamped to the
    sequence: the kernel skips a step past the band's end)."""
    if window is None:
        return lambda b, i, j: (b, j, 0)
    last = t // blk_k - 1
    return lambda b, i, j: (b, jnp.minimum(
        _first_k(i, blk_q, blk_k, window) + j, last), 0)


def _fwd(q3, k3, v3, scale, causal, blk_q, blk_k, window=None):
    bh, t, d = q3.shape
    if window is None:
        kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                                   blk_q=blk_q, blk_k=blk_k)
        grid = (bh, t // blk_q, t // blk_k)
    else:
        kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                                   blk_q=blk_q, blk_k=blk_k, window=window)
        grid = (bh, t // blk_q, _band_k(t, blk_q, blk_k, window))
    keys = _key_map(t, blk_q, blk_k, window)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, blk_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, blk_k, d), keys),
            pl.BlockSpec((1, blk_k, d), keys),
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
               scale, causal, blk_q, blk_k, window=None):
    qi, step = pl.program_id(1), pl.program_id(2)
    n_k = pl.num_programs(2)
    ki = step if window is None else step + _first_k(qi, blk_q, blk_k, window)

    @pl.when(step == 0)
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
            s = _visible(s, qi, ki, blk_q, blk_k, window)
        p = jnp.exp(s - lse)
        dp = _dot(do, v, ((1,), (1,)))
        ds = (p * (dp - delta)).astype(q.dtype)
        acc[...] += _dot(ds, k, ((1,), (0,))) * scale

    @pl.when(step == n_k - 1)
    def _finalize():
        dq_ref[0] = acc[...].astype(dq_ref.dtype)


# ---------------------------------------------------------------------------
# Backward dk/dv: grid (bh, n_k, n_q), dk/dv accumulate across n_q
# ---------------------------------------------------------------------------

def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *,
                scale, causal, blk_q, blk_k, window=None, n_q_blocks=None):
    ki, step = pl.program_id(1), pl.program_id(2)
    n_q = pl.num_programs(2)
    qi = step if window is None else step + _first_q(ki, blk_q, blk_k)

    @pl.when(step == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    diag_ok = (qi + 1) * blk_q > ki * blk_k if causal else True
    if window is not None:
        # past the band's end: the last query that sees this block's last key
        diag_ok = (qi * blk_q <= (ki + 1) * blk_k + window - 2) & (
            qi < n_q_blocks)

    @pl.when(diag_ok)
    def _compute():
        k, v = k_ref[0], v_ref[0]
        q, do = q_ref[0], do_ref[0]
        lse = lse_ref[0, 0][:, None]
        delta = delta_ref[0, 0][:, None]
        s = _dot(q, k, ((1,), (1,))) * scale
        if causal:
            s = _visible(s, qi, ki, blk_q, blk_k, window)
        p = jnp.exp(s - lse)  # [blk_q, blk_k] f32
        dv_acc[...] += _dot(p.astype(do.dtype), do, ((0,), (0,)))
        dp = _dot(do, v, ((1,), (1,)))
        ds = (p * (dp - delta)).astype(q.dtype)
        dk_acc[...] += _dot(ds, q, ((0,), (0,))) * scale

    @pl.when(step == n_q - 1)
    def _finalize():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd(q3, k3, v3, o3, lse, do3, scale, causal, blk_q, blk_k, window=None):
    bh, t, d = q3.shape
    delta = jnp.sum(do3.astype(jnp.float32) * o3.astype(jnp.float32), axis=-1)
    delta = jnp.broadcast_to(delta[:, None, :], (bh, _SUB, t))
    static = dict(scale=scale, causal=causal, blk_q=blk_q, blk_k=blk_k)
    if window is None:
        n_k, n_q = t // blk_k, t // blk_q
        queries, rows = (lambda b, j, i: (b, i, 0)), (
            lambda b, j, i: (b, 0, i))
        dkv_static = static
    else:
        n_k = _band_k(t, blk_q, blk_k, window)
        n_q = _band_q(t, blk_q, blk_k, window)
        last = t // blk_q - 1
        queries = lambda b, j, i: (b, jnp.minimum(
            _first_q(j, blk_q, blk_k) + i, last), 0)
        rows = lambda b, j, i: (b, 0, jnp.minimum(
            _first_q(j, blk_q, blk_k) + i, last))
        static = dict(static, window=window)
        dkv_static = dict(static, n_q_blocks=t // blk_q)
    keys = _key_map(t, blk_q, blk_k, window)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **static),
        grid=(bh, t // blk_q, n_k),
        in_specs=[
            pl.BlockSpec((1, blk_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, blk_k, d), keys),
            pl.BlockSpec((1, blk_k, d), keys),
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
        functools.partial(_dkv_kernel, **dkv_static),
        grid=(bh, t // blk_k, n_q),
        in_specs=[
            pl.BlockSpec((1, blk_q, d), queries),
            pl.BlockSpec((1, blk_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, blk_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, blk_q, d), queries),
            pl.BlockSpec((1, _SUB, blk_q), rows),
            pl.BlockSpec((1, _SUB, blk_q), rows),
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


# ---------------------------------------------------------------------------
# Public API with custom VJP
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q3, k3, v3, causal, blocks, scale, window=None):
    blk_q, blk_k = blocks[:2]
    o, _ = _fwd(q3, k3, v3, scale, causal, blk_q, blk_k, window)
    return o


def _flash_fwd(q3, k3, v3, causal, blocks, scale, window):
    blk_q, blk_k = blocks[:2]
    o, lse = _fwd(q3, k3, v3, scale, causal, blk_q, blk_k, window)
    return o, (q3, k3, v3, o, lse)


def _flash_bwd(causal, blocks, scale, window, res, do3):
    q3, k3, v3, o3, lse = res
    bwd_blk_q, bwd_blk_k = blocks[2:]
    return _bwd(q3, k3, v3, o3, lse, do3, scale, causal,
                bwd_blk_q, bwd_blk_k, window)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, causal: bool = False, block_q: int | None = None,
                    block_k: int | None = None,
                    bwd_block_q: int | None = None,
                    bwd_block_k: int | None = None,
                    scale: float | None = None, window: int | None = None):
    """Fused attention: q/k/v [B, T, H, D] → o [B, T, H, D].

    ``scale`` multiplies ``q k^T`` before the softmax: ``1/sqrt(D)`` unless
    the caller's model states another (Granite's ``attention_multiplier``).

    ``window`` (with ``causal``): query ``i`` sees key ``j`` iff ``0 <= i - j
    < window``. The three kernels' grids then hold only the blocks of each
    band (at 4,096 tokens and a window of 128, 2 key blocks of ``WINDOW_BLOCK``
    a query block where the causal kernel visits all 4 of its own 1,024-key
    blocks and computes up to 3), masked inside
    the edge blocks; the default blocks are ``WINDOW_BLOCK`` square.

    T must be a multiple of the (clamped) block sizes; pad upstream if not.
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
    if window is not None:
        if not causal or window < 1:
            raise ValueError(
                f"window={window} needs causal=True and at least one key")
        if window >= t:
            window = None       # every earlier key is inside it
        else:
            most = max(WINDOW_BLOCK, 1 << (window - 1).bit_length())
            block_q = block_q or _auto_blk(t, most)
            block_k = block_k or _auto_blk(t, most)
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

    if scale is None:
        scale = 1.0 / (d ** 0.5)
    o3 = _flash(to3(q), to3(k), to3(v), causal, (blk_q, blk_k, bwd_q, bwd_k),
                float(scale), window)
    return o3.reshape(b, h, t, d).transpose(0, 2, 1, 3)
