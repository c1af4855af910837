"""Mamba-2's selective state-space scan (state-space duality), in chunks.

Per head, with a state ``S`` in ``R^{P x N}`` and ``S_0 = 0``::

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T      (A < 0, dt_t > 0)
    y_t = S_t C_t

``B`` and ``C`` belong to a group of heads (``mamba_n_groups``; Granite
4.0-H has one). It is the gated delta rule of ``ops/gated_delta.py`` without
the ``(I - beta k k^T)`` term, so the chunk algebra needs no inverse (Dao and
Gu, "Transformers are SSMs", arXiv:2405.21060, section 6). With
``c_i = sum_{s<=i} dt_s A`` inside a chunk of ``Q`` tokens:

    Y    = tril((C B^T) exp(c_i - c_j)) (dt X)  +  exp(c_i) (S_in C_i)
    S_Q  = exp(c_Q) S_in + sum_j exp(c_Q - c_j) dt_j x_j B_j^T

Everything that does not depend on ``S_in`` is computed for all chunks at
once as batched products; the hand-over from chunk to chunk is a
``lax.scan`` whose step is one multiply-add on the states: at Granite's
1,024 tokens in chunks of 256 it runs 4 steps a sequence, where the delta
rule's hand-over at 4,096 tokens in chunks of 64 ran 64 steps of three
products each and earned the kernels of PR 31. Those kernels tile heads of
128 x 128; this state is 64 x 128 and its hand-over has no product to fuse,
so it stays a scan (PERF.md section 6, PR 32). The backward pass is JAX's
own through the batched products and the scan.

Precision: ``dt``, the decays and the state are float32; the products with
``x``, ``B``, ``C`` take operands in ``x``'s dtype and accumulate in float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _product(spec, a, b, dtype):
    return jnp.einsum(spec, a.astype(dtype), b.astype(dtype),
                      preferred_element_type=F32)


def ssd_scan(x, dt, a, b, c, chunk: int = 256):
    """``x [B, T, H, P]``, ``dt [B, T, H]`` (after its softplus), ``a [H]``
    (negative), ``b, c [B, T, G, N]`` with ``H`` a multiple of ``G`` ->
    ``y [B, T, H, P]`` float32. Any ``T``: the last chunk is padded with
    tokens of ``dt = 0``, which leave the state as it is."""
    bsz, t, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    if h % g:
        raise ValueError(f"{h} heads do not divide into {g} groups")
    q = min(chunk, t)
    pad = -t % q
    if pad:
        x, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
                       for v in (x, dt, b, c))
    nc = (t + pad) // q
    dtype = x.dtype
    dt = dt.astype(F32)
    x = x.reshape(bsz, nc, q, g, h // g, p)
    dt = dt.reshape(bsz, nc, q, g, h // g)
    b = b.reshape(bsz, nc, q, g, n)
    c = c.reshape(bsz, nc, q, g, n)
    # c_i: the log of the decay from the chunk's start to token i, inclusive
    cum = jnp.cumsum(dt * a.astype(F32).reshape(g, h // g), axis=2)
    xdt = x.astype(F32) * dt[..., None]
    # within a chunk: tril((C B^T) exp(c_i - c_j)) (dt X)
    gram = _product("zcign,zcjgn->zcgij", c, b, dtype)
    by_head = cum.transpose(0, 1, 3, 4, 2)                  # [B, nc, G, Hg, Q]
    # exp(c_i - c_j) for j <= i, 0 above: the masked exponent is never positive
    decay = jnp.exp(jnp.where(
        jnp.tril(jnp.ones((q, q), bool)),
        by_head[..., :, None] - by_head[..., None, :], -jnp.inf))
    y = _product("zcghij,zcjghp->zcighp", gram[:, :, :, None] * decay, xdt,
                 dtype)
    # a chunk's own state at its end, from a zero start
    to_end = jnp.exp(cum[:, :, -1:] - cum)                  # [B, nc, Q, G, Hg]
    own = _product("zcjghp,zcjgn->zcghpn", xdt * to_end[..., None], b, dtype)
    whole = jnp.exp(cum[:, :, -1])                          # [B, nc, G, Hg]

    def hand_over(state, chunk_terms):
        own_c, whole_c = chunk_terms
        return whole_c[..., None, None] * state + own_c, state

    _, entering = jax.lax.scan(
        hand_over, jnp.zeros((bsz, g, h // g, p, n), F32),
        (own.swapaxes(0, 1), whole.swapaxes(0, 1)))
    entering = entering.swapaxes(0, 1)                      # [B, nc, G, Hg, P, N]
    read = _product("zcghpn,zcign->zcighp", entering, c, dtype)
    y = y + jnp.exp(cum)[..., None] * read
    return y.reshape(bsz, nc * q, h, p)[:, :t]


def ssd_recurrence(x, dt, a, b, c):
    """The same sums token by token (``lax.scan`` over ``t``), float32: the
    definition :func:`ssd_scan` is tested against."""
    bsz, t, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    x, dt, b, c = (v.astype(F32) for v in (x, dt, b, c))
    b, c = (jnp.repeat(v, h // g, axis=2) for v in (b, c))  # [B, T, H, N]

    def token(state, inputs):
        x_t, dt_t, b_t, c_t = inputs
        state = (jnp.exp(dt_t * a.astype(F32))[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * b_t[..., None, :])
        return state, jnp.einsum("zhpn,zhn->zhp", state, c_t)

    tokens = tuple(v.swapaxes(0, 1) for v in (x, dt, b, c))
    y = jax.lax.scan(token, jnp.zeros((bsz, h, p, n), F32), tokens)[1]
    return y.swapaxes(0, 1)
