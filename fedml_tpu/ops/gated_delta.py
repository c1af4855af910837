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
once, as batched matrix products in XLA; the hand-over from chunk to chunk
(three products on the state) is the one sequential part. ``A`` is strictly
lower triangular, so it is nilpotent and
``(I + A)^{-1} = (I - A)(I + A^2)(I + A^4)...`` ends after ``log2(chunk)``
factors: products again, no triangular solve, and an exact inverse in exact
arithmetic. Those small products run at the highest precision; the products
with ``q``, ``k``, ``v`` run in their dtype with float32 accumulation; gates,
decays and the state are float32.

Two implementations of the hand-over, chosen from the operands' shapes alone
(``takes_kernel``; no option anywhere):

* **Pallas kernels** where the heads tile the chip (``dk`` and ``dv``
  multiples of 128, the chunk a multiple of 8; Qwen3-Next's 128 / 128 / 64).
  ``gdn_fwd``: grid ``(B H / heads a step, N / chunks a step)``, the chunk
  axis last and sequential; a ``[heads, dk, dv]`` float32 VMEM scratch holds
  the states from a sequence's first chunk to its last, so no state goes to
  HBM between chunks. A step takes its chunks' ``u0 = T (beta V)`` (float32),
  ``w = T (beta gamma K)``, ``tril(Q K^T ...)``, ``gamma Q``,
  ``K gamma_C / gamma`` and ``gamma_C``, and gives ``o``. Under ``jax.vjp`` it
  also writes what the backward pass needs and cannot rebuild walking
  backwards: each chunk's start state (float32, ``N`` states of ``dk x dv`` a
  head: 134 MB a call at 32 heads x 64 chunks) and its ``u``. ``gdn_bwd`` is
  the same grid over the chunks in reverse with the state's cotangent
  resident in the scratch; it gives the cotangents of the six operands, and
  ``jax.vjp`` of the batched XLA algebra takes them to ``q, k, v, g, beta``.
  On the kernel path the inverse has its own derivative as well,
  ``dA = -T^T dT T^T`` (two products for the twenty of the series'
  transpose): on a v5e the series and its transpose were 58 % of the rule's
  time, the hand-overs 20 % (PERF.md section 5, PR 31).
* **``lax.scan``** everywhere else, with JAX's own backward through the
  batched products and the scan. It stays because narrow heads (the tests'
  16 x 16) do not fill a vreg's 128 lanes, as the kernels' chunk-level oracle,
  and so that the round's program at those sizes is the one it was.

Precision, product by product, kernels against the scan: ``K K^T``, ``Q K^T``,
``T (beta V)``, ``T (beta gamma K)`` are the same XLA products (operands in the
inputs' dtype, float32 accumulation); ``A`` and the series are float32 at
the highest precision in both, and so are the two products of the
inverse's derivative; the hand-over's ``w S``, ``(gamma Q) S``, ``tril(.) U``
and ``K^T U`` take the state and ``U`` cast to the inputs' dtype and
accumulate in float32, as in the scan; the state, its decay and the sum that
updates it are float32. Backwards, the scan's transposes are float32
cotangents against bf16 operands at the default precision (one bf16 pass on
the TPU) with every bf16 operand's cotangent rounded to bf16; the kernel
casts a cotangent to the inputs' dtype where it enters a product,
accumulates in float32, and keeps the cotangents of the state and of ``u0``
float32. ``o`` is rounded once to the inputs' dtype in both.

``gated_delta_rule_recurrent`` is the token-by-token rule, the oracle of the
tests; the model's reference (``benchmark/reference_qwen3_next.py``) has its own
copy and imports nothing from here.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fedml_tpu.ops.platform import pallas_interpret

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
# heads and chunks a grid step of the hand-over kernels at 128 x 128 heads
# (the largest divisors of the call's B x H and T / chunk that do not pass
# them; fewer heads where a head is larger). Swept on a v5e at 32 heads x 64
# chunks: (8, 2) 8.31 ms forward + backward, (4, 4) 8.38, (2, 2) 8.57, (1, 1)
# 9.59; 64 chunk-heads a step do not fit VMEM (PERF.md section 6, PR 31).
HEADS_A_STEP, CHUNKS_A_STEP = 8, 2


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


@jax.custom_vjp
def _inverse_unit_lower_saved(a):
    """``_inverse_unit_lower`` with the inverse's own derivative for a
    backward pass: ``T = (I + a)^{-1}`` gives ``da = -T^T dT T^T``, two
    products at the same precision for the twenty of the series' transpose."""
    return _inverse_unit_lower(a)


def _inverse_fwd(a):
    t_inv = _inverse_unit_lower(a)
    return t_inv, t_inv


def _inverse_bwd(t_inv, d_inv):
    t_t = jnp.swapaxes(t_inv, -1, -2)
    return (-jnp.matmul(jnp.matmul(t_t, d_inv, precision=HIGHEST), t_t,
                        precision=HIGHEST),)


_inverse_unit_lower_saved.defvjp(_inverse_fwd, _inverse_bwd)


def takes_kernel(dk: int, dv: int, chunk: int) -> bool:
    """Whether the hand-overs run as the Pallas kernels: heads that fill the
    MXU's 128 lanes and a chunk of whole sublanes. A pure function of the
    operands' shapes; everything else keeps the ``lax.scan``."""
    return dk % 128 == 0 and dv % 128 == 0 and chunk % 8 == 0


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               preferred_element_type=F32)


_NN, _TN, _NT = ((1,), (0,)), ((0,), (0,)), ((1,), (1,))


def _divisor(n: int, most: int) -> int:
    return max(d for d in range(1, max(most, 1) + 1) if n % d == 0)


def _step(bh: int, n: int, dk: int, dv: int) -> tuple:
    """``(heads, chunks)`` a grid step."""
    heads = HEADS_A_STEP * 128 * 128 // (dk * dv)
    return _divisor(bh, heads), _divisor(n, CHUNKS_A_STEP)


def _fwd_kernel(u0_ref, w_ref, qk_ref, gq_ref, ko_ref, dec_ref, o_ref, *rest,
                hb, cb):
    """One grid step: ``cb`` chunks of ``hb`` heads, the states resident in
    ``state`` (VMEM scratch, float32) from the sequence's first chunk on.
    ``saved`` are the outputs a backward pass needs (each chunk's start state
    and ``u``), there under ``jax.vjp`` only."""
    *saved, state = rest
    dt = w_ref.dtype

    @pl.when(pl.program_id(1) == 0)
    def _start():
        state[...] = jnp.zeros_like(state)

    for h in range(hb):
        s_f = state[h]
        for c in range(cb):
            s = s_f.astype(dt)
            u = (u0_ref[h, c] - _dot(w_ref[h, c], s, _NN)).astype(dt)
            o = _dot(gq_ref[h, c], s, _NN) + _dot(qk_ref[h, c], u, _NN)
            o_ref[h, c] = o.astype(o_ref.dtype)
            if saved:
                saved[0][h, c] = s_f
                saved[1][h, c] = u
            s_f = s_f * dec_ref[h, c] + _dot(ko_ref[h, c], u, _TN)
        state[h] = s_f


def _bwd_kernel(do_ref, u_ref, w_ref, qk_ref, gq_ref, ko_ref, dec_ref, s_ref,
                du0_ref, dw_ref, dqk_ref, dgq_ref, dko_ref, ddec_ref, dstate,
                *, hb, cb):
    """The same step walked backwards: ``dstate`` carries the cotangent of
    the state from the sequence's last chunk down."""
    dt = w_ref.dtype

    @pl.when(pl.program_id(1) == 0)
    def _start():
        dstate[...] = jnp.zeros_like(dstate)

    for h in range(hb):
        ds_f = dstate[h]
        for c in reversed(range(cb)):
            s_f = s_ref[h, c]
            s, ds = s_f.astype(dt), ds_f.astype(dt)
            do, u = do_ref[h, c], u_ref[h, c]
            du_f = _dot(qk_ref[h, c], do, _TN) + _dot(ko_ref[h, c], ds, _NN)
            du = du_f.astype(dt)
            du0_ref[h, c] = du_f
            dw_ref[h, c] = (-_dot(du, s, _NT)).astype(dt)
            dgq_ref[h, c] = _dot(do, s, _NT).astype(dt)
            dqk_ref[h, c] = _dot(do, u, _NT).astype(dt)
            dko_ref[h, c] = _dot(u, ds, _NT).astype(dt)
            ddec_ref[h, c] = jnp.sum(ds_f * s_f, axis=0, keepdims=True)
            ds_f = (ds_f * dec_ref[h, c] + _dot(gq_ref[h, c], do, _TN)
                    - _dot(w_ref[h, c], du, _TN))
        dstate[h] = ds_f


_DIMS = pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"))


def _blocks(arrays, hb, cb, index):
    return [pl.BlockSpec((hb, cb) + a.shape[2:], index) for a in arrays]


def _hand_over_fwd(u0, w, qk, gq, k_out, decay, save: bool):
    """``o`` (and, with ``save``, each chunk's start state and ``u``) from
    the per-chunk operands ``[B H, N, C, .]``."""
    bh, n, c, dv = u0.shape
    dk, dt = w.shape[-1], w.dtype
    hb, cb = _step(bh, n, dk, dv)
    ins = (u0, w, qk, gq, k_out, decay)
    outs = [jax.ShapeDtypeStruct((bh, n, c, dv), dt)]
    if save:
        outs += [jax.ShapeDtypeStruct((bh, n, dk, dv), F32),
                 jax.ShapeDtypeStruct((bh, n, c, dv), dt)]
    at = lambda i, j: (i, j, 0, 0)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, hb=hb, cb=cb),
        grid=(bh // hb, n // cb),
        in_specs=_blocks(ins, hb, cb, at),
        out_specs=_blocks(outs, hb, cb, at),
        out_shape=outs,
        scratch_shapes=[pltpu.VMEM((hb, dk, dv), F32)],
        compiler_params=_DIMS,
        interpret=pallas_interpret(),
        name="gdn_fwd",
    )(*ins)


def _hand_over_bwd(do, u, w, qk, gq, k_out, decay, states):
    bh, n, c, dv = do.shape
    dk, dt = w.shape[-1], w.dtype
    hb, cb = _step(bh, n, dk, dv)
    ins = (do, u, w, qk, gq, k_out, decay, states)
    outs = [jax.ShapeDtypeStruct((bh, n, c, dv), F32),      # du0
            jax.ShapeDtypeStruct(w.shape, dt),
            jax.ShapeDtypeStruct(qk.shape, dt),
            jax.ShapeDtypeStruct(gq.shape, dt),
            jax.ShapeDtypeStruct(k_out.shape, dt),
            jax.ShapeDtypeStruct(decay.shape, F32)]
    last = n // cb - 1
    at = lambda i, j: (i, last - j, 0, 0)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, hb=hb, cb=cb),
        grid=(bh // hb, n // cb),
        in_specs=_blocks(ins, hb, cb, at),
        out_specs=_blocks(outs, hb, cb, at),
        out_shape=outs,
        scratch_shapes=[pltpu.VMEM((hb, dk, dv), F32)],
        compiler_params=_DIMS,
        interpret=pallas_interpret(),
        name="gdn_bwd",
    )(*ins)


@jax.custom_vjp
def _hand_over(u0, w, qk, gq, k_out, decay):
    return _hand_over_fwd(u0, w, qk, gq, k_out, decay, save=False)[0]


def _hand_over_vjp_fwd(u0, w, qk, gq, k_out, decay):
    o, states, u = _hand_over_fwd(u0, w, qk, gq, k_out, decay, save=True)
    return o, (u, w, qk, gq, k_out, decay, states)


def _hand_over_vjp_bwd(saved, do):
    return tuple(_hand_over_bwd(do, *saved))


_hand_over.defvjp(_hand_over_vjp_fwd, _hand_over_vjp_bwd)


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

    kernel = takes_kernel(dk, dv, chunk)
    kk = mm(k, k, "bhnid,bhnjd->bhnij")
    a = jnp.where(strict, kk * ratio * beta[..., :, None], 0.0)
    inverse = _inverse_unit_lower_saved if kernel else _inverse_unit_lower
    t_inv = inverse(a)                              # [B, H, N, C, C] f32
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

    if kernel:
        def heads(x):       # [B, H, N, ...] -> [B H, N, ...]
            return x.reshape((b * h,) + x.shape[2:])

        decay = jnp.broadcast_to(jnp.exp(g_last)[..., None, None],
                                 (b, h, n, 1, dv))
        o = _hand_over(heads(u0), heads(w.astype(dt)), heads(qk.astype(dt)),
                       heads(gq), heads(k_out), heads(decay))
        o = jnp.moveaxis(o.reshape(b, h, n, chunk, dv), 1, 3)
        return o.reshape(b, n * chunk, h, dv)[:, :t]

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
