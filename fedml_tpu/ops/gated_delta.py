"""The gated delta rule of gated-DeltaNet linear attention, in chunks.

Per head, with a state ``S`` in ``R^{dk x dv}`` and ``S_0 = 0``::

    S'  = alpha_t S_{t-1}            (alpha_t = exp(g_t), g_t <= 0)
    u_t = beta_t (v_t - S'^T k_t)
    S_t = S' + k_t u_t^T
    o_t = S_t^T q_t

``gated_delta_rule`` computes it ``chunk`` tokens at a time (Yang et al.,
"Gated Delta Networks", arXiv:2412.06464, section 3.3; the WY form of
arXiv:2406.06484). With ``gamma_i = prod_{s<=i} alpha_s`` inside a chunk and
``A_ij = beta_i (k_i . k_j) gamma_i / gamma_j`` for ``j < i`` (0 elsewhere),
the chunk's ``u`` rows solve ``(I + A) U = beta V - (beta gamma K) S_0``, so

    T = (I + A)^{-1},  U = T (beta V) - T (beta gamma K) S_0
    O = (gamma Q) S_0 + tril(Q K^T gamma_i / gamma_j) U
    S_C = gamma_C S_0 + (K gamma_C / gamma)^T U

Everything that does not depend on ``S_0`` is computed for all chunks at
once, as batched matrix products; a ``lax.scan`` over the chunks carries the
state and does three products a chunk: the one sequential hand-over. ``A`` is
strictly lower triangular, so it is nilpotent and
``(I + A)^{-1} = (I - A)(I + A^2)(I + A^4)...`` ends after ``log2(chunk)``
factors: products again, no triangular solve, and an exact inverse in exact
arithmetic. Those small products run at the highest precision; the products
with ``q``, ``k``, ``v`` run in their dtype with float32 accumulation; gates,
decays and the state are float32.

The backward pass is JAX's, through the batched products and the scan (whose
per-chunk states it saves: ``T / chunk`` states of ``dk x dv`` a head).
``gated_delta_rule_recurrent`` is the token-by-token rule, the oracle of the
tests; the model's reference (``benchmark/reference_qwen3_next.py``) has its own
copy and imports nothing from here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _inverse_unit_lower(a):
    """``(I + a)^{-1}`` for strictly lower triangular ``a [..., C, C]``:
    ``prod_k (I + (-a)^(2^k))``, which ends because ``a^C = 0``."""
    c = a.shape[-1]
    eye = jnp.eye(c, dtype=a.dtype)
    power = -a
    inv = eye + power
    span = 2
    while span < c:
        power = jnp.matmul(power, power, precision=HIGHEST)
        inv = jnp.matmul(inv, eye + power, precision=HIGHEST)
        span *= 2
    return inv


def gated_delta_rule(q, k, v, g, beta, chunk: int = 64):
    """``q, k [B, T, H, dk]``, ``v [B, T, H, dv]``, log-decay ``g [B, T, H]``
    (``<= 0``) and ``beta [B, T, H]`` (float32) -> ``o [B, T, H, dv]`` in
    ``v``'s dtype. ``T`` need not be a multiple of ``chunk``: the tail is
    padded with tokens that leave the state alone (``beta = 0``, ``g = 0``).
    """
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    pad = -t % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (q, k, v, g, beta))
    n = (t + pad) // chunk
    f32 = jnp.float32

    def chunks(a):      # [B, T, H, ...] -> [B, H, N, C, ...]
        a = a.reshape((b, n, chunk) + a.shape[2:])
        return jnp.moveaxis(a, 3, 1)

    q, k, v = chunks(q), chunks(k), chunks(v)
    g, beta = chunks(g.astype(f32)), chunks(beta.astype(f32))
    gc = jnp.cumsum(g, axis=-1)                     # log gamma_i
    # gamma_i / gamma_j for j <= i (the other entries are masked below;
    # their exponent is clamped so that nothing overflows on the way)
    ratio = jnp.exp(jnp.minimum(gc[..., :, None] - gc[..., None, :], 0.0))
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)

    def mm(x, y, spec):
        return jnp.einsum(spec, x, y, preferred_element_type=f32)

    kk = mm(k, k, "bhnid,bhnjd->bhnij")
    a = jnp.where(strict, kk * ratio * beta[..., :, None], 0.0)
    t_inv = _inverse_unit_lower(a)                  # [B, H, N, C, C] f32
    dt = v.dtype
    bv = (v.astype(f32) * beta[..., None]).astype(dt)
    bgk = (k.astype(f32) * (beta * jnp.exp(gc))[..., None]).astype(dt)
    u0 = mm(t_inv.astype(dt), bv, "bhnij,bhnjd->bhnid")     # T (beta V)
    w = mm(t_inv.astype(dt), bgk, "bhnij,bhnjd->bhnid")     # T (beta gamma K)
    qk = jnp.where(lower, mm(q, k, "bhnid,bhnjd->bhnij") * ratio, 0.0)
    gq = (q.astype(f32) * jnp.exp(gc)[..., None]).astype(dt)
    g_last = gc[..., -1]                            # log gamma_C
    k_out = (k.astype(f32)
             * jnp.exp(g_last[..., None] - gc)[..., None]).astype(dt)

    def hand_over(state, chunk_in):
        u0_c, w_c, qk_c, gq_c, k_out_c, g_last_c = chunk_in
        s = state.astype(dt)
        u = u0_c - mm(w_c.astype(dt), s, "bhik,bhkd->bhid")
        o = (mm(gq_c, s, "bhik,bhkd->bhid")
             + mm(qk_c.astype(dt), u.astype(dt), "bhij,bhjd->bhid"))
        state = (state * jnp.exp(g_last_c)[..., None, None]
                 + mm(k_out_c, u.astype(dt), "bhik,bhid->bhkd"))
        return state, o

    per_chunk = tuple(jnp.moveaxis(x, 2, 0)
                      for x in (u0, w, qk, gq, k_out, g_last))
    _, o = jax.lax.scan(hand_over, jnp.zeros((b, h, dk, dv), f32), per_chunk)
    o = jnp.moveaxis(o, 0, 2)                       # [B, H, N, C, dv]
    o = jnp.moveaxis(o, 1, 3).reshape(b, n * chunk, h, dv)
    return o[:, :t].astype(dt)


def gated_delta_rule_recurrent(q, k, v, g, beta):
    """The rule token by token, float32, highest precision: the oracle."""
    f32 = jnp.float32
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
    b, _, h, dk = q.shape

    def step(state, token):
        q_t, k_t, v_t, g_t, beta_t = token
        state = state * jnp.exp(g_t)[..., None, None]
        read = jnp.einsum("bhkd,bhk->bhd", state, k_t, precision=HIGHEST)
        u = beta_t[..., None] * (v_t - read)
        state = state + jnp.einsum("bhk,bhd->bhkd", k_t, u, precision=HIGHEST)
        return state, jnp.einsum("bhkd,bhk->bhd", state, q_t,
                                 precision=HIGHEST)

    tokens = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta))
    _, o = jax.lax.scan(step, jnp.zeros((b, h, dk, v.shape[-1]), f32), tokens)
    return jnp.moveaxis(o, 0, 1)
