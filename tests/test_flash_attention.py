"""Pallas flash attention vs dense oracle (interpret mode on the CPU mesh;
the same kernels compile to MXU code on real TPU)."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.ops import flash_attention
from fedml_tpu.parallel.ring_attention import reference_attention


def _grouped_qkv(t, hq, hkv, d=8, seed=0, lead=(2,)):
    """q with ``hq`` heads, k and v with ``hkv``, ``lead`` axes before T."""
    rng = np.random.RandomState(seed)
    mk = lambda h: jnp.asarray(rng.randn(*lead, t, h, d), jnp.float32)  # noqa: E731
    return mk(hq), mk(hkv), mk(hkv)


def _qkv(b=2, t=64, h=2, d=16, seed=0):
    return _grouped_qkv(t, h, h, d, seed, (b,))


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


# --- a sliding window: the grouped band kernels (PR 37) -----------------------

def _windowed(q, k, v, window):
    """Masked plain softmax, query ``i`` sees key ``j`` iff ``0 <= i - j <
    window``; k and v may have fewer heads, each serving a group of q's."""
    t, group = q.shape[1], q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(a, group, axis=2) for a in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    back = np.arange(t)[:, None] - np.arange(t)[None, :]
    s = jnp.where((back >= 0) & (back < window), s, -1e30)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)


# t, window, query heads, key-value heads, sub-block (block_k), block (block_q)
BAND_CASES = {
    # PR 34's shapes of the old window grids, now the band's
    "window_no_multiple_of_the_sub_block": (64, 10, 2, 2, 16, 16),
    "window_exactly_a_sub_block": (64, 16, 2, 2, 16, 16),
    "two_sub_blocks_a_block": (128, 24, 2, 2, 32, 64),
    "one_block_no_halo_group_4": (128, 24, 4, 1, 32, 128),
    "narrow_window_wide_block": (64, 5, 2, 2, 8, 32),
    "window_under_an_odd_sub_block": (96, 40, 2, 2, 48, 96),
    "a_token_sees_itself_only": (64, 1, 2, 2, 16, 16),
    "block_of_one_sub_block_group_2": (64, 16, 4, 2, 16, 16),
    # the default blocks: the window rounded up to 128, 4 sub-blocks a step
    "window_128_one_block_group_8": (512, 128, 8, 1, None, None),
    "window_128_two_blocks_group_4": (1024, 128, 4, 1, None, None),
    "window_128_three_blocks_of_one_group_1": (384, 128, 1, 1, None, None),
    "window_under_128_group_8": (512, 100, 8, 2, None, None),
    "window_no_multiple_of_128_group_4": (1024, 200, 4, 1, None, 256),
    "window_longer_than_t_over_2": (256, 200, 2, 1, None, None),
}


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5), ("bfloat16", 4e-2)])
@pytest.mark.parametrize("case", BAND_CASES)
def test_band_matches_masked_plain_attention(case, dtype, tol):
    """Forward and the three gradients of the grouped band kernels against
    the masked plain softmax over repeated k, v: k, v, ``dk``, ``dv`` with
    their own heads, nothing repeated (bf16: the operands rounded, the
    reference in float32 on the rounded operands)."""
    t, window, hq, hkv, sub, blk = BAND_CASES[case]
    q, k, v = (a.astype(dtype) for a in _grouped_qkv(t, hq, hkv))

    def band(q, k, v):
        return flash_attention(q, k, v, causal=True, window=window,
                               block_k=sub, block_q=blk)

    wide = lambda f: lambda *a: f(*a).astype(jnp.float32)  # noqa: E731
    exact = [a.astype(jnp.float32) for a in (q, k, v)]
    np.testing.assert_allclose(wide(band)(q, k, v), _windowed(*exact, window),
                               rtol=tol, atol=tol)
    got = jax.grad(lambda *a: jnp.sum(wide(band)(*a) ** 2), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(_windowed(*a, window) ** 2),
                    (0, 1, 2))(*exact)
    assert [a.shape for a in got] == [q.shape, k.shape, v.shape]
    for a, b in zip(got, want):
        np.testing.assert_allclose(a.astype(jnp.float32), b,
                                   rtol=10 * tol, atol=10 * tol)


@pytest.mark.parametrize("hq,hkv", [(8, 1), (4, 1), (4, 2), (2, 2)])
def test_band_under_vmap_sums_the_group_as_the_repeat_would(hq, hkv):
    """Under ``jax.vmap`` over a leading client axis, as the round calls it:
    the grids are ``(clients, B, Hkv, T / block)``, the three calls keep
    their names, and ``dk``, ``dv`` are the repeated kernel's summed over
    each key-value head's group."""
    t, window, d = 128, 16, 8
    q, k, v = _grouped_qkv(t, hq, hkv, d, lead=(2, 1))

    def band(q, k, v):
        return flash_attention(q, k, v, causal=True, window=window,
                               block_k=16, block_q=32)

    step = jax.vmap(jax.grad(lambda *a: jnp.sum(band(*a) ** 2), (0, 1, 2)))
    text = str(jax.make_jaxpr(step)(q, k, v))
    grids = re.findall(r"grid=\((\d+), (\d+), (\d+), (\d+)\)", text)
    assert grids and all(g == ("2", "1", str(hkv), "4") for g in grids), grids
    assert {"window_band_fwd", "window_band_dq", "window_band_dkv"} <= set(
        re.findall(r"window_band_\w+", text))
    got = step(q, k, v)
    want = jax.vmap(jax.grad(lambda *a: jnp.sum(_windowed(*a, window) ** 2),
                             (0, 1, 2)))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)
    repeated = step(q, *(jnp.repeat(a, hq // hkv, axis=3) for a in (k, v)))
    for a, b in zip(got[1:], repeated[1:]):
        np.testing.assert_allclose(
            a, b.reshape(2, 1, t, hkv, hq // hkv, d).sum(4),
            rtol=1e-5, atol=1e-5)


def test_band_blocks_from_what_the_call_can_see():
    """Sub-block = the window rounded up to a multiple of 128, block = up to
    4 sub-blocks that divide T; a sub-block narrower than the window, or a
    ragged sequence, is refused; grouped k, v under a window no shorter than
    the sequence are repeated for the causal kernel."""
    import importlib

    fa = importlib.import_module("fedml_tpu.ops.flash_attention")
    assert fa._band_blocks(4096, 128, None, None) == (128, 512)  # the cell
    assert fa._band_blocks(4096, 100, None, None) == (128, 512)
    assert fa._band_blocks(4096, 129, None, None) == (256, 1024)
    assert fa._band_blocks(640, 128, None, None) == (128, 128)
    assert fa._band_blocks(768, 128, None, None) == (128, 384)
    assert fa._band_blocks(24, 6, None, None) == (24, 24)
    assert fa._band_blocks(64, 10, 16, 32) == (16, 32)
    with pytest.raises(ValueError, match="sub-blocks"):
        fa._band_blocks(64, 17, 16, None)
    with pytest.raises(ValueError, match="sub-blocks"):
        fa._band_blocks(200, 128, None, None)
    with pytest.raises(ValueError, match="whole sub-blocks"):
        fa._band_blocks(128, 16, 16, 48)
    q, k, v = _grouped_qkv(32, 4, 2)
    np.testing.assert_allclose(
        flash_attention(q, k, v, causal=True, window=32, block_q=16,
                        block_k=16),
        _windowed(q, k, v, 32), rtol=2e-5, atol=2e-5)
    with pytest.raises(ValueError, match="heads"):
        flash_attention(q, k[:, :, :1], v, causal=True, window=8)
    with pytest.raises(ValueError, match="heads"):
        flash_attention(*_grouped_qkv(32, 3, 2), causal=True, window=8)


def test_no_window_is_the_kernel_as_it_was():
    """``window=None`` and a window no shorter than the sequence trace the
    causal kernel's program, text for text; a window needs ``causal``."""
    q, k, v = _qkv(t=64)
    text = lambda **kw: str(jax.make_jaxpr(lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, causal=True, block_q=16, block_k=16, **kw))(q, k, v))
    assert text() == text(window=None) == text(window=64) == text(window=900)
    assert "window_band" not in text()
    banded = str(jax.make_jaxpr(lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=63))(q, k, v))
    assert "window_band_fwd" in banded
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, window=8)
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, k, v, causal=True, window=0)
