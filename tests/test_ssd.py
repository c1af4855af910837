"""``ops/ssd.py``: Mamba-2's chunked scan against the recurrence it states,
token by token, forward and every gradient. CPU, float32 at the highest
matmul precision unless a case says bf16."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.ops.ssd import ssd_recurrence, ssd_scan

B, H, P, N = 2, 4, 16, 16


def _operands(t, groups=1, seed=0, dtype=jnp.float32):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(k[0], (B, t, H, P)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(k[1], (B, t, H)) - 2.0)
    a = -jnp.exp(jax.random.uniform(k[2], (H,), minval=0.0, maxval=2.7))
    b = jax.random.normal(k[3], (B, t, groups, N)).astype(dtype)
    c = jax.random.normal(k[4], (B, t, groups, N)).astype(dtype)
    return x, dt, a, b, c


# sequence lengths that are and are not multiples of the chunk (8), one
# shorter than a chunk, and two groups of heads
CASES = [(32, 1), (29, 1), (8, 1), (5, 1), (24, 2), (19, 2)]


@pytest.mark.parametrize("t, groups", CASES)
def test_chunks_equal_the_recurrence(t, groups):
    args = _operands(t, groups)
    with jax.default_matmul_precision("highest"):
        got = ssd_scan(*args, chunk=8)
        want = ssd_recurrence(*args)
    assert got.shape == (B, t, H, P) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("t, groups", [(32, 1), (29, 1), (5, 1), (19, 2)])
def test_every_gradient_equals_the_recurrences(t, groups):
    args = _operands(t, groups, seed=1)

    def through(fn):
        return jax.grad(lambda *a: jnp.sum(jnp.sin(fn(*a))),
                        argnums=(0, 1, 2, 3, 4))

    with jax.default_matmul_precision("highest"):
        got = through(lambda *a: ssd_scan(*a, chunk=8))(*args)
        want = through(ssd_recurrence)(*args)
    for name, g, w in zip(("x", "dt", "a", "b", "c"), got, want):
        assert np.isfinite(np.asarray(g)).all(), name
        np.testing.assert_allclose(
            g, w, rtol=1e-4, atol=1e-5 * float(jnp.abs(w).max()),
            err_msg=name)


def test_the_chunk_size_does_not_change_the_result():
    args = _operands(48)
    with jax.default_matmul_precision("highest"):
        outs = [ssd_scan(*args, chunk=q) for q in (4, 16, 48, 256)]
    for other in outs[1:]:
        np.testing.assert_allclose(outs[0], other, rtol=1e-5, atol=1e-5)


def test_bf16_operands_accumulate_in_float32():
    """The products take bf16 operands; decays and state stay float32: the
    result is float32 and within bf16's rounding of the float32 rule."""
    args = _operands(40, dtype=jnp.bfloat16)
    got = ssd_scan(*args, chunk=8)
    want = ssd_recurrence(*args)
    assert got.dtype == jnp.float32
    err = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    assert err < 2e-2, err


def test_vmap_over_clients_is_the_loop_over_clients():
    args = [_operands(16, seed=s) for s in (3, 4, 5)]
    stacked = [jnp.stack(v) for v in zip(*args)]
    with jax.default_matmul_precision("highest"):
        got = jax.vmap(lambda *a: ssd_scan(*a, chunk=8))(*stacked)
        for i, one in enumerate(args):
            np.testing.assert_allclose(got[i], ssd_scan(*one, chunk=8),
                                       rtol=1e-5, atol=1e-6)


def test_heads_must_divide_into_the_groups():
    x, dt, a, b, c = _operands(8, groups=1)
    with pytest.raises(ValueError, match="groups"):
        ssd_scan(x, dt, a, jnp.tile(b, (1, 1, 3, 1)), jnp.tile(c, (1, 1, 3, 1)))


def test_eight_groups_in_chunks_of_128_equal_the_recurrence():
    """Nemotron-H's shape of the scan at a small size: 16 heads in 8 B/C
    groups (two heads a group), chunks of 128 over 300 tokens (two whole
    chunks and a padded third), forward and the gradients of x, B and C."""
    k = jax.random.split(jax.random.PRNGKey(8), 5)
    t, h, g, p, n = 300, 16, 8, 8, 16
    x = jax.random.normal(k[0], (1, t, h, p))
    dt = jax.nn.softplus(jax.random.normal(k[1], (1, t, h)) - 2.0)
    a = -jnp.exp(jax.random.uniform(k[2], (h,), minval=0.0, maxval=2.7))
    b, c = (jax.random.normal(key, (1, t, g, n)) for key in k[3:])

    def through(fn):
        return jax.value_and_grad(
            lambda x, b, c: jnp.sum(jnp.sin(fn(x, dt, a, b, c))),
            argnums=(0, 1, 2))

    with jax.default_matmul_precision("highest"):
        got = ssd_scan(x, dt, a, b, c, chunk=128)
        want = ssd_recurrence(x, dt, a, b, c)
        (_, grads), (_, wanted) = (through(fn)(x, b, c) for fn in (
            lambda *v: ssd_scan(*v, chunk=128), ssd_recurrence))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5 * float(
        jnp.abs(want).max()))
    # a group's B and C serve its own two heads and no other's
    other = ssd_recurrence(x, dt, a, jnp.roll(b, 1, axis=2), c)
    assert float(jnp.abs(other - want).max()) > 1e-2
    for name, got_g, want_g in zip(("x", "b", "c"), grads, wanted):
        np.testing.assert_allclose(
            got_g, want_g, rtol=1e-4, atol=1e-5 * float(jnp.abs(want_g).max()),
            err_msg=name)
