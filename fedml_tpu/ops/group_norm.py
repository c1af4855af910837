"""Fused GroupNorm as pallas TPU kernels (fwd + custom VJP).

Why it exists: a round-time attribution (v5e through the retired
attachment, 2026-07-31; its script was removed in PR 30) put GroupNorm's
MARGINAL cost at ~38% of the full federated round, so a fused
one-VMEM-pass kernel (stats + normalize + affine; backward recomputes
instead of saving temporaries) was the round's designated lever.

Measured OUTCOME — a dead end at CIFAR-ResNet shapes (same attachment,
not re-measured on this benchmark): the fused-GN round ran 98.2 ms vs
44.1 ms for XLA's lowering (same config, same params). The ablation's 38% is the
marginal cost of GN *fused into the surrounding conv chains* — XLA
folds the normalize/affine into conv epilogues, so swapping in an
opaque pallas call severs those fusions and forces extra HBM
round-trips per layer that the kernel's own efficiency cannot buy
back. The op stays available (``models.resnet.Norm(kind="gn_fused")``,
param-compatible with ``"gn"``); models default to ``"gn"``.

The reserved use case was MEASURED, not hypothetical (same attachment,
2026-07-31, script removed in PR 30; random cotangent —
an all-ones cotangent lets XLA simplify the mean-subtracted backward
and was rejected as an unfair workload): standalone wide-channel GN
TRAINING steps (fwd+bwd) run 0.67-0.73x of flax's time at C=2048-4096
([64,128,2048]: 165 vs 225 us; [32,128,4096]: 143 vs 214 us) — the
backward's recompute-in-VMEM strategy beats XLA's saved-temporaries
autodiff, which drops to ~150 GB/s. Forward-only, XLA wins everywhere
(1.56-1.92x, sustaining 640-825 GB/s). Boundary: at C=8192 the bwd
kernel's [N-block, S, C] tile exceeds the 16 MB scoped VMEM and fails
to compile — use ``"gn"`` past ~4k channels.

Layout: public API [..., S, C] with ``groups`` dividing C (the caller
flattens spatial dims; models.resnet.Norm does the NHWC reshape).
Internally [N, S, C]: grid over N-blocks, each block resident in VMEM.
Stats are f32 regardless of input dtype (same numerics as flax
``nn.GroupNorm``: normalize in f32, cast on output). Backward is a
single kernel producing dx and accumulating dscale/dbias across the
sequential grid in VMEM scratch (written on the last step) — the TPU
idiom for cross-block reductions.

On the CPU backend the kernels run in interpreter mode (CPU-mesh
testable; ops/platform.py); equivalence vs ``nn.GroupNorm`` is pinned in
tests/test_group_norm.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fedml_tpu.ops.platform import pallas_interpret


def _block_n(n: int, s: int, c: int, budget_bytes: int = 1 << 19) -> int:
    """Largest divisor of n whose [bn, S, C] f32 block fits the VMEM
    budget. The budget is PER BUFFER: the kernels hold ~6-8 f32-sized
    live temporaries (x cast, x², xhat, dxhat, products, output), so
    512 KB/buffer keeps the scoped-vmem stack a few MB under the 16 MB
    limit (measured: a 4 MB/buffer budget OOM'd at 31 MB on v5e)."""
    per = s * c * 4
    want = max(1, budget_bytes // max(per, 1))
    for bn in range(min(want, n), 0, -1):
        if n % bn == 0:
            return bn
    return 1


def _group_mats(c, groups):
    """[C, G] 0/1 indicator and its transpose, built with iota — group
    reductions become matmuls (MXU) instead of lane-splitting reshapes,
    which Mosaic lowers badly (observed: compile stall on v5e for the
    [bn, S, G, C/G] reshape formulation)."""
    ci = jax.lax.broadcasted_iota(jnp.int32, (c, groups), 0)
    gi = jax.lax.broadcasted_iota(jnp.int32, (c, groups), 1)
    return (ci // (c // groups) == gi).astype(jnp.float32)


def _stats_per_channel(x32, groups):
    """Per-(sample, channel) group mean/var broadcast back to channels:
    ([bn, C], [bn, C]) f32 — each channel carries ITS group's stats."""
    bn, s, c = x32.shape
    m = _group_mats(c, groups)          # [C, G]
    denom = s * (c // groups)
    sum_c = jnp.sum(x32, axis=1)        # [bn, C]
    sumsq_c = jnp.sum(x32 * x32, axis=1)
    mu = ((sum_c @ m) @ m.T) / denom    # [bn, C], group-pooled
    ex2 = ((sumsq_c @ m) @ m.T) / denom
    # Clamp like flax's _compute_stats: E[x^2] - mu^2 can cancel below
    # zero for near-constant inputs, and rsqrt(var + eps) would NaN.
    return mu, jnp.maximum(ex2 - mu * mu, 0.0)


def _fwd_kernel(x_ref, g_ref, b_ref, y_ref, *, groups, eps):
    # g_ref/b_ref are [1, C]: TPU block shapes must have their last two
    # dims (8,128)-divisible OR equal to the array dims — a bare [C] with
    # C<128 becomes an illegal (1, C) block once vmap batching inserts a
    # leading grid dim (observed on v5e; interpreter mode does not check).
    x = x_ref[...].astype(jnp.float32)
    mu, var = _stats_per_channel(x, groups)
    rstd = jax.lax.rsqrt(var + eps)
    y = (x - mu[:, None, :]) * rstd[:, None, :]
    y = y * g_ref[0].astype(jnp.float32) + b_ref[0].astype(jnp.float32)
    y_ref[...] = y.astype(y_ref.dtype)


def _bwd_kernel(x_ref, dy_ref, g_ref, dx_ref, dg_ref, db_ref,
                dg_acc, db_acc, *, groups, eps):
    i, n_i = pl.program_id(0), pl.num_programs(0)

    @pl.when(i == 0)
    def _init():
        dg_acc[...] = jnp.zeros_like(dg_acc)
        db_acc[...] = jnp.zeros_like(db_acc)

    x = x_ref[...].astype(jnp.float32)
    dy = dy_ref[...].astype(jnp.float32)
    gamma = g_ref[0].astype(jnp.float32)  # [C]
    bn, s, c = x.shape
    mu, var = _stats_per_channel(x, groups)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = (x - mu[:, None, :]) * rstd[:, None, :]      # [bn, S, C]

    db_acc[...] += jnp.sum(dy, axis=(0, 1))[None]
    dg_acc[...] += jnp.sum(dy * xhat, axis=(0, 1))[None]

    dxhat = dy * gamma[None, None, :]
    mm = _group_mats(c, groups)
    denom = s * (c // groups)
    # group means of dxhat and dxhat*xhat, broadcast back per channel
    mean_dxhat = ((jnp.sum(dxhat, axis=1) @ mm) @ mm.T) / denom
    mean_dxhat_xhat = ((jnp.sum(dxhat * xhat, axis=1) @ mm) @ mm.T) / denom
    dx = rstd[:, None, :] * (dxhat
                             - mean_dxhat[:, None, :]
                             - xhat * mean_dxhat_xhat[:, None, :])
    dx_ref[...] = dx.astype(dx_ref.dtype)

    @pl.when(i == n_i - 1)
    def _finalize():
        dg_ref[...] = dg_acc[...]
        db_ref[...] = db_acc[...]


def _fwd(x3, gamma, beta, groups, eps):
    n, s, c = x3.shape
    bn = _block_n(n, s, c)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, groups=groups, eps=eps),
        grid=(n // bn,),
        in_specs=[
            pl.BlockSpec((bn, s, c), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, c), lambda i: (0, 0)),
            pl.BlockSpec((1, c), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bn, s, c), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, s, c), x3.dtype),
        interpret=pallas_interpret(),
    )(x3, gamma.reshape(1, c), beta.reshape(1, c))


def _bwd(x3, dy3, gamma, groups, eps):
    n, s, c = x3.shape
    bn = _block_n(n, s, c)
    dims = pltpu.CompilerParams(dimension_semantics=("arbitrary",))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, groups=groups, eps=eps),
        grid=(n // bn,),
        in_specs=[
            pl.BlockSpec((bn, s, c), lambda i: (i, 0, 0)),
            pl.BlockSpec((bn, s, c), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, c), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn, s, c), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, c), lambda i: (0, 0)),
            pl.BlockSpec((1, c), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, s, c), x3.dtype),
            jax.ShapeDtypeStruct((1, c), jnp.float32),
            jax.ShapeDtypeStruct((1, c), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, c), jnp.float32),
            pltpu.VMEM((1, c), jnp.float32),
        ],
        compiler_params=dims,
        interpret=pallas_interpret(),
    )(x3, dy3, gamma.reshape(1, c))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _gn(x3, gamma, beta, groups, eps):
    return _fwd(x3, gamma, beta, groups, eps)


def _gn_fwd(x3, gamma, beta, groups, eps):
    return _fwd(x3, gamma, beta, groups, eps), (x3, gamma)


def _gn_bwd(groups, eps, res, dy3):
    x3, gamma = res
    dx, dg, db = _bwd(x3, dy3, gamma, groups, eps)
    return (dx, dg.reshape(gamma.shape).astype(gamma.dtype),
            db.reshape(gamma.shape).astype(gamma.dtype))


_gn.defvjp(_gn_fwd, _gn_bwd)


def group_norm(x, gamma, beta, groups: int, eps: float = 1e-6):
    """Fused GroupNorm: x [..., C] → same shape; gamma/beta [C].

    All leading dims are flattened to [N, S, C] with S the second-to-last
    dim (callers pass [N, H*W, C] or [N*H*W, 1, C]-style layouts; the
    models flatten NHWC spatial dims). ``groups`` must divide C. Stats
    and normalization are f32 (flax ``nn.GroupNorm`` numerics); output in
    x's dtype. Differentiable via a fused backward kernel.
    """
    c = x.shape[-1]
    if c % groups:
        raise ValueError(f"groups {groups} must divide channels {c}")
    orig = x.shape
    if x.ndim == 1:
        x3 = x.reshape(1, 1, c)
    elif x.ndim == 2:
        x3 = x[:, None, :]  # per-sample over channel groups only
    else:
        # normalization is per leading-sample over ALL non-channel dims:
        # [N, prod(middle), C]
        x3 = x.reshape(orig[0], -1, c)
    out = _gn(x3, gamma, beta, groups, eps)
    return out.reshape(orig)
