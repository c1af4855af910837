"""Lever A/B (r3): vmapped per-client-filter conv vs ONE grouped conv.

A federated round vmaps local training over clients, so convs carry a
per-client filter stack. The same math can be phrased as a single conv
with feature_group_count=C on a channel-stacked input:
    x_g[b, h, w, c*ch + j] = x[c, b, h, w, j]
Times ITERS chained iterations inside one jit (single dispatch + one
host fetch) — per-call timing measures a ~100 ms fixed per-call
dispatch cost, not the kernel.
"""
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

ITERS = 32
C = 8  # clients in the vmap (bench: 8/round)


def timed(f, *args, reps=3):
    float(f(*args))  # warm + sync
    vals = []
    for _ in range(reps):
        t0 = time.perf_counter()
        float(f(*args))
        vals.append(time.perf_counter() - t0)
    return statistics.median(vals)


def chain_fwd(conv_fn):
    """y feeds the next x (shapes match: ch_in == ch_out, SAME)."""
    def run(x, w):
        out = jax.lax.fori_loop(
            0, ITERS, lambda i, acc: conv_fn(acc, w), x)
        return jnp.sum(out.astype(jnp.float32))
    return jax.jit(run)


def chain_bwd(conv_fn):
    """Chained on the WEIGHTS (w -= eps * grad): fwd+bwd per step."""
    g = jax.grad(lambda w, x: jnp.sum(conv_fn(x, w).astype(jnp.float32) ** 2))

    def run(x, w):
        out = jax.lax.fori_loop(
            0, ITERS, lambda i, wi: wi - 1e-6 * g(wi, x).astype(wi.dtype), w)
        return jnp.sum(out.astype(jnp.float32))
    return jax.jit(run)


print("backend:", jax.default_backend(), flush=True)
for ch, hw, B in [(16, 32, 32), (32, 16, 32), (64, 8, 32), (16, 32, 128)]:
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(C, B, hw, hw, ch), jnp.bfloat16)
    w = jnp.asarray(rng.randn(C, 3, 3, ch, ch) * 0.05, jnp.bfloat16)

    def conv(xi, wi):
        return jax.lax.conv_general_dilated(
            xi, wi, (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))

    def vmapped(x, w):
        return jax.vmap(conv)(x, w)

    def grouped(x, w, hw=hw, ch=ch, B=B):
        xg = jnp.transpose(x, (1, 2, 3, 0, 4)).reshape(B, hw, hw, C * ch)
        wg = jnp.transpose(w, (1, 2, 3, 0, 4)).reshape(3, 3, ch, C * ch)
        yg = jax.lax.conv_general_dilated(
            xg, wg, (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=C)
        return jnp.transpose(
            yg.reshape(B, hw, hw, C, ch), (3, 0, 1, 2, 4))

    # grouped-conv math == vmap math
    ref = np.asarray(jax.jit(vmapped)(x, w), np.float32)
    got = np.asarray(jax.jit(grouped)(x, w), np.float32)
    np.testing.assert_allclose(got, ref, rtol=2e-2, atol=2e-1)

    gflop = 2 * C * B * hw * hw * 9 * ch * ch * ITERS / 1e9
    tv, tg = timed(chain_fwd(vmapped), x, w), timed(chain_fwd(grouped), x, w)
    tvb, tgb = timed(chain_bwd(vmapped), x, w), timed(chain_bwd(grouped), x, w)
    print(f"ch={ch} hw={hw} B={B}: fwd vmap={gflop/tv:.0f} "
          f"grouped={gflop/tg:.0f} GFLOP/s (g/v={tv/tg:.2f}x) | "
          f"fwd+bwd vmap={3*gflop/tvb:.0f} grouped={3*gflop/tgb:.0f} GFLOP/s "
          f"(g/v={tvb/tgb:.2f}x)", flush=True)
