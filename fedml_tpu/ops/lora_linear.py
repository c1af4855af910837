"""A frozen projection with its low-rank pair, one pass over the output.

``lora_linear(x, w, a, b, scale, gate=..., out_dtype=...)`` is::

    s = x W + scale ((x A) as x.dtype) B          float32
    y = s                                         gate=False
    y = silu(s[..., :f]) * s[..., f:]             gate=True, f = n / 2

rounded once to ``out_dtype``. ``W [k, n]`` is the frozen base (bfloat16 in
the adapter round), ``A [k, r]`` and ``B [r, n]`` the client's pair; products
take ``x.dtype`` operands and accumulate in float32.

In XLA the sum of two ``[m, n]`` float32 products cannot be had without one
of them crossing HBM: the frozen product writes its float32 result, a second
fusion reads it, adds the pair's and writes it again, a third applies the
gate or the cast (PERF.md section 5, PR 33: 1.86 + 0.92 + 0.36 ms a call at
``[4096, 2048] x [2048, 16384]`` on a v5e). Two implementations, chosen from
the operands' shapes alone (``takes_kernel``; no option anywhere):

* **A Pallas kernel** where ``k`` and the rows fill whole tiles and the
  result is large enough to be worth a launch. Grid ``(m / tm, n / tn,
  k / tk)``, the ``k`` axis last and sequential; the tile's float32 sum stays
  in VMEM (a scratch over the ``k`` steps; no scratch where one step holds
  the whole depth, as at Granite's 2,048), the last step adds ``scale *
  t_tile B_tile`` (``t = (x A)`` is a plain product outside, ``[m, r]``) and
  writes the tile once, gated and cast. With ``gate`` a grid step holds both
  halves' sums, columns ``j`` and ``f + j`` of ``W`` and ``B``. A last ``n``
  tile may be partial (Mamba-2's ``in_proj`` is 66.5 x 128 wide): what it
  reads beyond the edge only reaches columns that are never written.
  ``jax.custom_vjp``: under differentiation the gated kernel also writes the
  float32 sum (the gate's derivative needs it; ``optimize_remat`` keeps the
  first forward of a rematerialised layer on the kernel that writes the
  output alone); the linear one saves nothing. The backward pass is plain
  JAX: the transposes of the plain expression, so the products are the ones
  differentiation ran before this kernel, the pair's under
  ``fed.model.lora``. Under ``vmap`` over clients ``x``, ``a``, ``b`` are
  batched and ``w`` is not: ``pallas_call``'s batching rule leaves an
  unmapped operand unmapped, so the base is read, never copied (test-pinned).
* **The plain expression** everywhere else, toy and dry-run widths included:
  the program there is the one it was.

The same operand dtypes, the same float32 accumulation, the same single
rounding: only the order of the float32 additions differs.
"""

from __future__ import annotations

import contextlib
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fedml_tpu.ops.platform import pallas_interpret

F32 = jnp.float32
# rows, columns (of each half of a gate; twice as many without one) and depth
# of a grid step: the largest that keep a step's buffers inside Mosaic's
# default 16 MiB of VMEM. Asking for more (``vmem_limit_bytes``) takes the
# room XLA keeps its own operands in between its fusions: with 64 MiB asked
# for, every bandwidth-bound fusion of the round ran 30-45 % slower on a v5e
# (PERF.md section 6, PR 33). Swept there at 4 clients x [1024, 2048] x
# [2048, 16384].
TM, TN, TK = 512, 256, 2048
# the smallest float32 result, in elements, that takes the kernel (Granite's
# ``input_linear`` at 1,024 tokens a client is exactly this). Measured in the
# round on a v5e (same section): at 8.7 M (``in_proj``, 2048 -> 8512) the
# kernel itself saves 0.35 ms a call, but XLA then lays the convolution's and
# the scan's operands out worse and the round loses 165 ms; at 2.1 M
# (``out_proj``, ``output_linear``, ``q/o``) XLA already fuses the pair's sum
# with the residual add and the next norm's reduction, and there is nothing
# to remove.
MIN_RESULT = 1 << 24
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))

_TALLIES: list = []


@contextlib.contextmanager
def tally():
    """Collects ``(m, k, n, rank, fused, experts)`` for every projection with
    a pair traced while it is open (host side, trace time): what
    ``FedAdapterAPI`` counts its ``lora_sites`` from. ``experts`` is 0 for a
    projection of this file, and the number of stacked experts for a grouped
    product that computes its pairs itself (:func:`note`). A grouped product
    that takes its kernel adds an ``ops.grouped_matmul.Traced`` besides."""
    calls: list = []
    _TALLIES.append(calls)
    try:
        yield calls
    finally:
        _TALLIES.remove(calls)


def note(m: int, k: int, n: int, rank: int, fused: bool,
         experts: int = 0) -> None:
    """Adds a projection with a pair to every open :func:`tally`:
    ``lora_linear`` notes its own; the model whose experts' pairs are
    computed beside their grouped product
    (``parallel.expert_parallel.held_lora_products``) notes those, unfused,
    with the rows of a chunk of that product as ``m`` and the number of
    experts stacked in one leaf."""
    record((m, k, n, rank, fused, experts))


def record(item) -> None:
    """Adds ``item`` to every open :func:`tally`."""
    for calls in _TALLIES:
        calls.append(item)


def _divisor(n: int, most: int, unit: int) -> int:
    """The largest multiple of ``unit`` up to ``most`` that divides ``n``."""
    return max(d for d in range(unit, most + 1, unit) if n % d == 0)


def takes_kernel(m: int, k: int, n: int, rank: int,
                 gate: bool = False) -> bool:
    """Whether the projection runs as the Pallas kernel: a depth of whole
    lanes (a partial ``k`` tile would put what lies beyond the edge into the
    sum), rows of whole packed sublanes, a pair, a result worth a launch and,
    with ``gate``, halves of whole lanes (the second half starts on a tile).
    A pure function of the operands' shapes."""
    return (rank > 0 and k % 128 == 0 and m % 16 == 0
            and m * n >= MIN_RESULT and (not gate or n % 256 == 0))


def _mm(x, w):
    return jnp.einsum("...d,de->...e", x, w.astype(x.dtype),
                      preferred_element_type=F32)


def _summed(x, w, a, b, scale):
    """The float32 sum as the model wrote it before the kernel."""
    y = _mm(x, w)
    # the pair's two products alone: the sum belongs to the layer, or a
    # fusion of the frozen product with it would carry this scope's name
    with jax.named_scope("fed.model.lora"):
        low = scale * _mm(_mm(x, a).astype(x.dtype), b)
    return y + low


def gated(s):
    """``silu(first half) * second half`` of the last axis."""
    f = s.shape[-1] // 2
    return jax.nn.silu(s[..., :f]) * s[..., f:]


def _kernel(x_ref, t_ref, *refs, scale, halves, save, steps):
    """One grid step. ``refs``: a ``W`` tile and a ``B`` tile for each half,
    the output tile, with ``save`` a float32 tile of the sum for each half,
    then (``steps`` > 1) an accumulator for each half."""
    w_refs, b_refs = refs[:halves], refs[halves:2 * halves]
    o_ref, saved = refs[2 * halves], refs[2 * halves + 1:][:halves * save]
    x = x_ref[...]
    products = [jnp.dot(x, w_ref[...].astype(x.dtype),
                        preferred_element_type=F32) for w_ref in w_refs]

    def finish(products):
        t = t_ref[...]
        sums = [p + scale * jnp.dot(t, b_ref[...], preferred_element_type=F32)
                for p, b_ref in zip(products, b_refs)]
        for ref, s in zip(saved, sums):
            ref[...] = s
        y = sums[0] if halves == 1 else jax.nn.silu(sums[0]) * sums[1]
        o_ref[...] = y.astype(o_ref.dtype)

    if steps == 1:          # the whole depth in one tile: no accumulator
        return finish(products)
    accs, step = refs[-halves:], pl.program_id(2)

    @pl.when(step == 0)
    def _start():
        for acc, p in zip(accs, products):
            acc[...] = p

    @pl.when(step > 0)
    def _add():
        for acc, p in zip(accs, products):
            acc[...] += p

    @pl.when(step == steps - 1)
    def _finish():
        finish([acc[...] for acc in accs])


def _fused(x, w, t, b, scale, gate: bool, save: bool, out_dtype):
    """``x [m, k]``, ``w [k, n]``, ``t [m, r]``, ``b [r, n]`` (``x``'s dtype
    but ``w``, cast a tile at a time) -> ``y`` and, with ``save``, the
    float32 halves of the sum."""
    m, k = x.shape
    n, r = w.shape[1], t.shape[1]
    halves = 2 if gate else 1
    f = n // halves
    tm, tk = _divisor(m, min(m, TM), 16), _divisor(k, min(k, TK), 128)
    # a last tile may be partial, but a gate's second half starts on a tile
    tn = _divisor(f, min(f, TN), 128) if gate else min(f, 2 * TN)
    shift, steps = f // tn, k // tk
    at = [lambda i, j, s, h=h: (s, j + h * shift) for h in range(halves)]
    low = [lambda i, j, s, h=h: (0, j + h * shift) for h in range(halves)]
    outs = [jax.ShapeDtypeStruct((m, f), out_dtype)]
    outs += [jax.ShapeDtypeStruct((m, f), F32)] * (halves if save else 0)
    tile = pl.BlockSpec((tm, tn), lambda i, j, s: (i, j))
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, halves=halves, save=save,
                          steps=steps),
        grid=(m // tm, pl.cdiv(f, tn), steps),
        in_specs=[pl.BlockSpec((tm, tk), lambda i, j, s: (i, s)),
                  pl.BlockSpec((tm, r), lambda i, j, s: (i, 0)),
                  *[pl.BlockSpec((tk, tn), ix) for ix in at],
                  *[pl.BlockSpec((r, tn), ix) for ix in low]],
        out_specs=[tile] * len(outs),
        out_shape=outs,
        scratch_shapes=[pltpu.VMEM((tm, tn), F32)] * (
            halves if steps > 1 else 0),
        compiler_params=_PARAMS,
        interpret=pallas_interpret(),
        name="lora_linear" + ("_gate" if gate else "") + (
            "_saved" if save else ""),
    )(x, t, *[w] * halves, *[b] * halves)


def _call(x, w, a, b, scale, gate, save, out_dtype):
    lead = x.shape[:-1]
    x2 = x.reshape(math.prod(lead), x.shape[-1])
    with jax.named_scope("fed.model.lora"):
        t = _mm(x2, a).astype(x.dtype)
    y, *sums = _fused(x2, w, t, b.astype(x.dtype), scale, gate, save,
                      out_dtype)
    return y.reshape(lead + y.shape[-1:]), sums


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _lora_linear(x, w, a, b, scale, gate, out_dtype):
    return _call(x, w, a, b, scale, gate, False, out_dtype)[0]


def _vjp_fwd(x, w, a, b, scale, gate, out_dtype):
    y, sums = _call(x, w, a, b, scale, gate, gate, out_dtype)
    return y, (x, w, a, b, sums)


def _vjp_bwd(scale, gate, out_dtype, saved, dy):
    x, w, a, b, sums = saved
    dy = (dy.astype(F32),)
    if gate:
        _, gate_vjp = jax.vjp(lambda g, u: jax.nn.silu(g) * u, *(
            s.reshape(dy[0].shape) for s in sums))
        dy = gate_vjp(*dy)

    def halves(x, w, a, b):
        """The plain sum as the gate reads it: its transposes are the ones
        differentiation ran before the kernel (the halves' cotangents padded
        and added, which XLA fuses into the products that read them)."""
        s = _summed(x, w, a, b, scale)
        f = s.shape[-1] // 2
        return (s[..., :f], s[..., f:]) if gate else (s,)

    # the forward of ``halves`` is dead code: only its transposes run
    return jax.vjp(halves, x, w, a, b)[1](dy)


_lora_linear.defvjp(_vjp_fwd, _vjp_bwd, optimize_remat=True)


def lora_linear(x, w, a, b, scale: float, *, gate: bool = False,
                out_dtype=F32):
    """``x [..., k]``, frozen ``w [k, n]``, the pair ``a [k, r]``,
    ``b [r, n]`` -> ``[..., n]`` (``[..., n / 2]`` with ``gate``) in
    ``out_dtype``; see the module's docstring."""
    m, (k, n) = math.prod(x.shape[:-1]), w.shape
    fused = takes_kernel(m, k, n, a.shape[-1], gate)
    note(m, k, n, a.shape[-1], fused)
    if fused:
        return _lora_linear(x, w, a, b, float(scale), gate, out_dtype)
    s = _summed(x, w, a, b, scale)
    return (gated(s) if gate else s).astype(out_dtype)
