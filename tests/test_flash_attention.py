"""Pallas flash attention vs dense oracle (interpret mode on the CPU mesh;
the same kernels compile to MXU code on real TPU)."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.ops import flash_attention
from fedml_tpu.parallel.ring_attention import reference_attention


def _qkv(b=2, t=64, h=2, d=16, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(b, t, h, d), jnp.float32)
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("t,blk", [(64, 16), (64, 64), (128, 32)])
def test_flash_matches_dense_forward(causal, t, blk):
    q, k, v = _qkv(t=t)
    got = flash_attention(q, k, v, causal=causal, block_q=blk, block_k=blk)
    want = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_grads_match_dense(causal):
    q, k, v = _qkv(t=32, d=8)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal,
                                       block_q=16, block_k=16) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal=causal) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_flash_rejects_ragged_seq():
    q, k, v = _qkv(t=48)
    with pytest.raises(ValueError):
        flash_attention(q, k, v, block_q=32, block_k=32)


def test_flash_default_blocks_accept_any_128_multiple():
    """Default (auto) block sizes must not regress on sequence lengths
    the old fixed-128 defaults accepted: T=384 is not a multiple of the
    tuned 256/512 targets, so the auto-pick falls back to a divisor."""
    q, k, v = _qkv(t=384)
    got = flash_attention(q, k, v, causal=True)
    want = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_transformer_lm_with_flash_attention():
    """LM forward with flash attention == dense attention logits."""
    from fedml_tpu.models import create_model
    from fedml_tpu.trainer.local import model_fns

    t, vocab = 32, 19
    flash = lambda q, k, v: flash_attention(q, k, v, causal=True,
                                            block_q=16, block_k=16)
    dense = create_model("transformer_lm", vocab_size=vocab, d_model=32,
                         n_heads=2, n_layers=1, max_len=t)
    flashm = create_model("transformer_lm", vocab_size=vocab, d_model=32,
                          n_heads=2, n_layers=1, max_len=t, attn_fn=flash)
    toks = jnp.asarray(np.random.RandomState(0).randint(0, vocab, (2, t)))
    fns_d, fns_f = model_fns(dense), model_fns(flashm)
    net = fns_d.init(jax.random.PRNGKey(0), toks)
    ld, _ = fns_d.apply(net, toks)
    lf, _ = fns_f.apply(net, toks)
    np.testing.assert_allclose(np.asarray(ld), np.asarray(lf),
                               rtol=2e-5, atol=2e-5)


# --- a sliding window (PR 34) -------------------------------------------------

def _windowed(q, k, v, window):
    """Masked plain softmax: query ``i`` sees key ``j`` iff ``0 <= i - j <
    window``."""
    t = q.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    back = np.arange(t)[:, None] - np.arange(t)[None, :]
    s = jnp.where((back >= 0) & (back < window), s, -1e30)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)


@pytest.mark.parametrize("t,window,blk_q,blk_k", [
    (64, 10, 16, 16),       # not a multiple of the block
    (64, 16, 16, 16),       # exactly a block
    (128, 24, 32, 16),      # a band of three key blocks
    (128, 24, 16, 32),      # keys wider than queries
    (64, 5, 8, 16),
    (96, 40, 32, 32),       # wider than a block
    (64, 1, 16, 16),        # a token sees itself only
])
def test_windowed_flash_matches_masked_plain_attention(t, window, blk_q,
                                                       blk_k):
    """Forward and the three gradients with a window, the grid holding only
    each band's blocks, against the masked plain softmax."""
    q, k, v = _qkv(t=t, d=8)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, window=window,
                               block_q=blk_q, block_k=blk_k)

    np.testing.assert_allclose(flash(q, k, v), _windowed(q, k, v, window),
                               rtol=2e-5, atol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(flash(*a) ** 2), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(_windowed(*a, window) ** 2),
                    (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


def test_a_windows_grid_holds_its_bands_blocks_only():
    """The forward and dq grids' innermost axis is the widest band's key
    blocks, the dkv grid's its query blocks: 2 of 8 at a window of one
    block, under ``vmap`` over clients too."""
    import importlib

    fa = importlib.import_module("fedml_tpu.ops.flash_attention")
    assert fa._band_k(128, 16, 16, 16) == 2 == fa._band_q(128, 16, 16, 16)
    assert fa._band_k(128, 16, 16, 17) == 2 and fa._band_k(128, 16, 16, 18) == 3
    assert fa._band_k(4096, 256, 256, 128) == 2     # the benchmark's cell
    assert fa._band_q(4096, 256, 256, 128) == 2
    q, k, v = (jnp.stack([a, a + 1]) for a in _qkv(t=128, d=8))
    step = jax.vmap(jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, causal=True, window=16, block_q=16, block_k=16)), (0, 1, 2)))
    grids = re.findall(r"grid=\((\d+), (\d+), (\d+), (\d+)\)",
                       str(jax.make_jaxpr(step)(q, k, v)))
    assert grids and all(g == ("2", "4", "8", "2") for g in grids), grids
    got = step(q, k, v)
    want = jax.vmap(jax.grad(lambda q, k, v: jnp.sum(
        _windowed(q, k, v, 16)), (0, 1, 2)))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


def test_no_window_is_the_kernel_as_it_was():
    """``window=None`` and a window no shorter than the sequence trace the
    causal kernel's program, text for text; a window needs ``causal``."""
    q, k, v = _qkv(t=64)
    text = lambda **kw: str(jax.make_jaxpr(lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, causal=True, block_q=16, block_k=16, **kw))(q, k, v))
    assert text() == text(window=None) == text(window=64) == text(window=900)
    assert text(window=63) != text()
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, window=8)
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, k, v, causal=True, window=0)
