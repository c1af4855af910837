"""Fused pallas GroupNorm ≡ flax nn.GroupNorm (fwd + grads).

The kernel exists because GN read ~38% marginal cost of the s2d
federated round under XLA's lowering (v5e through the retired
attachment, 2026-07-31; script removed in PR 30, not re-measured on this
benchmark). Equivalence here is what licenses swapping it into
models via ``Norm(kind="gn_fused")``.
Runs in pallas interpreter mode on the CPU mesh.
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.ops.group_norm import group_norm


@pytest.mark.parametrize("shape,groups", [
    ((6, 8, 8, 32), 32),   # s2d stage-1: group size 1 (instance-norm-like)
    ((4, 4, 4, 64), 32),   # group size 2
    ((3, 2, 2, 128), 32),  # group size 4
    ((5, 7, 48), 8),       # non-square spatial, 3-d input
    ((9, 16), 4),          # 2-d input: per-sample channel groups
])
def test_matches_flax_groupnorm_fwd(shape, groups):
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(*shape), jnp.float32)
    c = shape[-1]
    gamma = jnp.asarray(rng.rand(c) + 0.5, jnp.float32)
    beta = jnp.asarray(rng.randn(c), jnp.float32)

    ref_mod = nn.GroupNorm(num_groups=groups, epsilon=1e-6)
    ref = ref_mod.apply(
        {"params": {"scale": gamma, "bias": beta}}, x)
    got = group_norm(x, gamma, beta, groups)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_matches_flax_groupnorm_grads():
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(4, 6, 6, 32), jnp.float32)
    gamma = jnp.asarray(rng.rand(32) + 0.5, jnp.float32)
    beta = jnp.asarray(rng.randn(32), jnp.float32)
    ref_mod = nn.GroupNorm(num_groups=32, epsilon=1e-6)

    def loss_ref(x, g, b):
        y = ref_mod.apply({"params": {"scale": g, "bias": b}}, x)
        return jnp.sum(jnp.sin(y))  # non-trivial cotangent

    def loss_fused(x, g, b):
        return jnp.sum(jnp.sin(group_norm(x, g, b, 32)))

    ref_grads = jax.grad(loss_ref, argnums=(0, 1, 2))(x, gamma, beta)
    got_grads = jax.grad(loss_fused, argnums=(0, 1, 2))(x, gamma, beta)
    for a, b in zip(got_grads, ref_grads):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-5, atol=5e-5)


def test_bf16_output_dtype_and_f32_stats():
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(2, 4, 4, 32), jnp.bfloat16)
    gamma = jnp.ones((32,), jnp.float32)
    beta = jnp.zeros((32,), jnp.float32)
    y = group_norm(x, gamma, beta, 32)
    assert y.dtype == jnp.bfloat16
    ref = nn.GroupNorm(num_groups=32, epsilon=1e-6, dtype=jnp.bfloat16).apply(
        {"params": {"scale": gamma, "bias": beta}}, x)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_norm_module_gn_fused_param_compat():
    """resnet.Norm(kind="gn_fused") produces the same param tree as
    kind="gn" (scale/bias under GroupNorm's names) and the same outputs,
    so checkpoints are interchangeable."""
    from fedml_tpu.models.resnet import Norm

    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(2, 8, 8, 64), jnp.float32)
    v_ref = Norm(kind="gn").init(jax.random.PRNGKey(0), x)
    v_fused = Norm(kind="gn_fused").init(jax.random.PRNGKey(0), x)
    ref_leaves = {(jax.tree_util.keystr(k), tuple(l.shape))
                  for k, l in jax.tree_util.tree_leaves_with_path(v_ref)}
    fused_leaves = {(jax.tree_util.keystr(k), tuple(l.shape))
                    for k, l in jax.tree_util.tree_leaves_with_path(v_fused)}
    assert fused_leaves == ref_leaves and len(ref_leaves) == 2
    y_ref = Norm(kind="gn").apply(v_ref, x)
    y_fused = Norm(kind="gn_fused").apply(v_ref, x)  # REF params, fused op
    np.testing.assert_allclose(np.asarray(y_fused), np.asarray(y_ref),
                               rtol=2e-5, atol=2e-5)


def test_vmap_composes():
    """Per-client GN under vmap (the federated round's shape): pallas
    batching must give the same result as a python loop."""
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(3, 2, 4, 4, 32), jnp.float32)  # [C, B, H, W, c]
    gamma = jnp.asarray(rng.rand(3, 32) + 0.5, jnp.float32)
    beta = jnp.asarray(rng.randn(3, 32), jnp.float32)
    got = jax.vmap(lambda xx, g, b: group_norm(xx, g, b, 32))(x, gamma, beta)
    for i in range(3):
        np.testing.assert_allclose(
            np.asarray(got[i]),
            np.asarray(group_norm(x[i], gamma[i], beta[i], 32)),
            rtol=2e-5, atol=2e-5)


def test_rejects_bad_groups():
    with pytest.raises(ValueError, match="divide"):
        group_norm(jnp.zeros((2, 3, 30)), jnp.ones(30), jnp.zeros(30), 4)
