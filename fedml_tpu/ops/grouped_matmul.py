"""A product grouped over sorted rows: each row by its own group's matrix.

``grouped_matmul(lhs, rhs, group_sizes)`` is, in float32::

    out[r] = lhs[r] @ rhs[g]        row r in group g
    out[r] = 0                      row r past the groups' total

``lhs [m, k]`` holds the groups' rows one group after another (group ``g``
is rows ``sum(group_sizes[:g]) .. + group_sizes[g] - 1``; the total may be
under ``m``), ``rhs [G, k, n]`` a matrix a group (``[G, n, k]`` with
``transpose_rhs``: ``lhs[r] @ rhs[g].T``). Products take ``lhs.dtype``
operands and accumulate in float32. The held experts of a mixture-of-experts
layer are the groups (``parallel.expert_parallel.held_lora_products``): the
assignments sorted by expert, the experts' stacked FROZEN matrices read where
they lie.

Two implementations, chosen from the operands' shapes alone (``takes_kernel``;
no option anywhere):

* **A Pallas kernel** where the rows fill whole sub-tiles and depth and width
  are a lane tile or more. Grid ``(ceil(n / tn), visits, ceil(k / tk))``: a
  depth or a width off the tile grid (an expert width of 1,856 is 14.5 lane
  tiles) ends in a partial tile, whose columns past the width are never
  written and whose depth past ``k`` is masked to zero in both operands
  before the last step's product. A *visit* is one row tile under one group, in
  the order of the rows, so a tile whose rows belong to two groups is visited
  once a group under a row mask (the design of
  ``jax.experimental.pallas.ops.tpu.megablox.gmm``). Which group and which row
  tile a visit has is scalar-prefetched and goes into the index maps: a visit
  reads ``rhs[g]``'s tile where it lies, and nothing gathers or copies the
  matrices. The grid is static, ``m / tm + G - 1`` visits; those past the
  last real row are skipped and their index maps repeat the last block, so
  nothing is fetched for them. Within a visit only the ``SUB``-row sub-tiles
  that hold a row of the group are multiplied: tall tiles keep the matrices'
  traffic low (a group's matrix is read once a visit) without paying products
  for the rows of a neighbour. Rows that no visit writes are masked to zero
  before anything reads them. Under a ``vmap`` of two or more clients that
  batches the rows and the group sizes and not ``rhs`` (the adapter round's),
  the kernel has its own batching rule: ONE call with the clients on its
  grid, their rows read as ``[B m, k]`` where they lie and the result
  written as ``[B m, n]``, each client's groups its own groups over its own
  row tiles (``jax.custom_batching.custom_vmap``). A batch of one is
  squeezed to the unbatched call; a batch of the matrices too runs as
  ``pallas_call``'s loop over the batch.
* **The plain expression** everywhere else, toy and dry-run widths included:
  one ``dot_general`` of the rows masked a group, ``[G, m, k] x [G, k, n]``
  contracted over group and depth, which also reads ``rhs`` as it lies (at
  ``G`` times the operations: for small shapes only).

``jax.custom_vjp`` over both: the backward is the same product with ``rhs``
transposed (``dlhs = dout rhs[g]^T``, rounded to ``lhs.dtype`` as JAX's
transpose of the plain product rounds it) and **no cotangent for ``rhs``**:
the matrices are frozen, and no product forms their gradient.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fedml_tpu.ops.lora_linear import _divisor, record
from fedml_tpu.ops.platform import pallas_interpret

F32 = jnp.float32
# rows, depth and width of a grid step, and the rows of a sub-tile that is
# multiplied only if it holds a row of the visit's group. Swept on a v5e at
# [8192, 6144] x [16, 6144, 4096] and [8192, 2048] x [16, 2048, 6144] with
# some 256 real rows a group (PERF.md section 6, PR 35); inside Mosaic's
# default 16 MiB of VMEM (asking for more slows XLA's own fusions:
# ``ops/lora_linear.py``).
TM, TK, TN, SUB = 512, 256, 2048, 128
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary", "arbitrary"))


class Traced(NamedTuple):
    """A grouped product that takes the kernel, as a trace saw it (host
    side, trace time; recorded in every open ``ops.lora_linear.tally``): a
    client's rows ``m``, depth ``k`` and width ``n``, and ``clients``, the
    width of the client axis on the kernel's grid. A product is recorded
    with 1 when its call is traced, and again with the clients when a
    ``vmap`` puts two or more on the grid (``FedAdapterAPI`` keeps a shape's
    widest)."""
    m: int
    k: int
    n: int
    clients: int


def takes_kernel(m: int, k: int, n: int) -> bool:
    """Whether the product runs as the Pallas kernel: rows in whole sub-tiles
    and a depth and width of a lane tile or more (whole tiles or not: the
    last may be partial). A pure function of the shapes."""
    return m % SUB == 0 and k >= 128 and n >= 128


def _tile(size: int, most: int) -> int:
    """A depth or width tile: ``most``, or the whole of a smaller ``size`` in
    whole lanes."""
    return min(most, -(-size // 128) * 128)


def _visits(group_sizes, m: int, tm: int):
    """``(offsets [G + 1], group [V], tile [V], active [1])`` for the
    ``V = m / tm + G - 1`` visits of the grid: a group with rows visits every
    row tile it touches, groups in order; visits past the last active one
    repeat it."""
    n_groups = group_sizes.shape[0]
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    first = starts // tm
    tiles = jnp.where(group_sizes > 0, (ends - 1) // tm - first + 1, 0)
    upto = jnp.cumsum(tiles)
    active = upto[-1]
    visit = jnp.minimum(jnp.arange(m // tm + n_groups - 1),
                        jnp.maximum(active - 1, 0))
    group = jnp.minimum(jnp.searchsorted(upto, visit, side="right"),
                        n_groups - 1)
    tile = jnp.clip(first[group] + visit - (upto - tiles)[group], 0,
                    m // tm - 1)
    as_i32 = lambda a: a.astype(jnp.int32)  # noqa: E731
    return (as_i32(jnp.concatenate([jnp.zeros(1, ends.dtype), ends])),
            as_i32(group), as_i32(tile), as_i32(active[None]))


def _kernel(offsets, group, tile, active, lhs_ref, rhs_ref, out_ref, acc_ref,
            *, tm, sub, steps, last, transpose_rhs):
    """``last``: the columns of depth the last step really has, 0 where the
    depth fills whole tiles. What a partial tile holds past them is
    undefined, in both operands: it is zeroed before that step's product."""
    visit, step = pl.program_id(1), pl.program_id(2)
    depth_axis = 1 if transpose_rhs else 0
    contract = (((1,), (depth_axis,)), ((), ()))

    def real_depth(v, axis: int):
        """``v`` with what lies past the depth's end zeroed (a last step's)."""
        return jnp.where(jax.lax.broadcasted_iota(jnp.int32, v.shape, axis)
                         < last, v, jnp.zeros_like(v))

    @pl.when(visit < active[0])
    def _visit():
        lo, hi = offsets[group[visit]], offsets[group[visit] + 1]
        row0 = tile[visit] * tm

        @pl.when(step == 0)
        def _start():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        for s in range(tm // sub):      # static: sub-tiles of the row tile
            rows = slice(s * sub, (s + 1) * sub)
            holds = (row0 + s * sub < hi) & (row0 + (s + 1) * sub > lo)
            # where the depth ends in a partial tile (static), the last step
            # is a second body, masked; every other step is the first
            for masked in ((False, True) if last else (False,)):
                @pl.when(holds & ((step == steps - 1) == masked) if last
                         else holds)
                def _multiply(rows=rows, masked=masked):
                    clip = real_depth if masked else (lambda v, axis: v)
                    lhs = clip(lhs_ref[rows, :], 1)
                    acc_ref[rows, :] += jax.lax.dot_general(
                        lhs, clip(rhs_ref[...], depth_axis).astype(lhs.dtype),
                        contract, preferred_element_type=F32)

        @pl.when(step == steps - 1)
        def _store():
            row = row0 + jax.lax.broadcasted_iota(
                jnp.int32, acc_ref.shape, 0)
            # a tile that two groups share is visited by each in turn and
            # stays in VMEM between the visits: keep the other's rows
            out_ref[...] = jnp.where((row >= lo) & (row < hi), acc_ref[...],
                                     out_ref[...])


def _fit(m: int, k: int, n: int, tiles):
    """``tiles`` fitted to a client's ``m`` rows, depth and width: a row tile
    that divides ``m`` in whole sub-tiles, depth and width tiles in whole
    lanes."""
    tm, tk, tn, sub = tiles
    return _divisor(m, min(m, tm), sub), _tile(k, tk), _tile(n, tn), sub


def _call(lhs, rhs, visits, transpose_rhs: bool, tiles, name: str,
          expert=lambda group: group):
    """The kernel over ``lhs [rows, k]`` at fitted ``tiles``: ``visits`` is
    ``(offsets, group, tile, active)``, ``expert`` a visit's group's matrix
    in ``rhs``."""
    k = lhs.shape[1]
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tm, tk, tn, sub = tiles
    steps = pl.cdiv(k, tk)

    def depth(visit, step, active):
        # a skipped visit asks for the block the last step left in VMEM
        return jnp.where(visit < active[0], step, steps - 1)

    def lhs_at(j, v, s, offsets, group, tile, active):
        return tile[v], depth(v, s, active)

    def rhs_at(j, v, s, offsets, group, tile, active):
        at = (j, depth(v, s, active)) if transpose_rhs else (
            depth(v, s, active), j)
        return (expert(group[v]),) + at

    def out_at(j, v, s, offsets, group, tile, active):
        return tile[v], j

    return pl.pallas_call(
        functools.partial(_kernel, tm=tm, sub=sub, steps=steps,
                          last=k % tk, transpose_rhs=transpose_rhs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(pl.cdiv(n, tn), visits[1].shape[0], steps),
            in_specs=[pl.BlockSpec((tm, tk), lhs_at),
                      pl.BlockSpec((None, tn, tk) if transpose_rhs
                                   else (None, tk, tn), rhs_at)],
            out_specs=pl.BlockSpec((tm, tn), out_at),
            scratch_shapes=[pltpu.VMEM((tm, tn), F32)]),
        out_shape=jax.ShapeDtypeStruct((lhs.shape[0], n), F32),
        compiler_params=_PARAMS,
        interpret=pallas_interpret(),
        name=name,
    )(*visits, lhs, rhs)


@functools.partial(jax.jit, static_argnames=("transpose_rhs", "tiles", "name"))
def _grouped(lhs, rhs, group_sizes, transpose_rhs: bool, tiles, name: str):
    """The kernel; rows that no visit wrote are undefined. Jitted, so that a
    program that calls it many times at the same shapes (a layer's forward,
    recomputed forward and backward, layer after layer) traces the kernel
    and lowers it to Mosaic once: with a lowering a call the benchmark
    cell's round took 4 s longer to trace (PERF.md section 6, PR 35). XLA
    inlines the calls and names each instance by its own call site's
    scopes."""
    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tiles = _fit(m, k, n, tiles)
    return _call(lhs, rhs, _visits(group_sizes, m, tiles[0]), transpose_rhs,
                 tiles, name)


def _client_visits(group_sizes, m: int, tm: int):
    """:func:`_visits` of ``B`` clients (``group_sizes [B, G]``) whose ``m``
    rows each lie end to end: client ``b``'s group ``g`` is group ``b (G + 1)
    + g`` and its row tile ``t`` is tile ``b m / tm + t``; ``offsets [B (G +
    1)]`` are each client's moved by ``b m``, so that the last of them, its
    total, starts a gap group that no visit has (the client's rows past its
    total); ``group``, ``tile [B V]`` the clients' active visits in turn, the
    last repeated after them; ``active [1]`` their sum."""
    clients, n_groups = group_sizes.shape
    offsets, group, tile, active = jax.vmap(
        lambda sizes: _visits(sizes, m, tm))(group_sizes)
    active = active[:, 0]
    upto = jnp.cumsum(active)
    visit = jnp.minimum(jnp.arange(group.size), jnp.maximum(upto[-1] - 1, 0))
    client = jnp.minimum(jnp.searchsorted(upto, visit, side="right"),
                         clients - 1)
    at = visit - (upto - active)[client]
    as_i32 = lambda a: a.astype(jnp.int32)  # noqa: E731
    return (as_i32(offsets + m * jnp.arange(clients)[:, None]).reshape(-1),
            as_i32(client * (n_groups + 1) + group[client, at]),
            as_i32(client * (m // tm) + tile[client, at]),
            as_i32(upto[-1:]))


@functools.partial(jax.jit, static_argnames=("transpose_rhs", "tiles", "name"))
def _grouped_clients(lhs, rhs, group_sizes, transpose_rhs: bool, tiles,
                     name: str):
    """``B`` clients' products as ONE kernel call, the clients on its grid:
    ``lhs [B, m, k]``, ``group_sizes [B, G]``, ``rhs`` shared -> ``[B, m,
    n]``. The rows are read as ``[B m, k]`` and the result written as ``[B m,
    n]`` (leading axes merged: no copy), the clients' groups are ``B (G +
    1)`` groups over them (:func:`_client_visits`) whose matrix is the group
    modulo ``G + 1``, and a row tile divides ``m``: none holds two clients'
    rows."""
    clients, m, k = lhs.shape
    n_groups = rhs.shape[0]
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tiles = _fit(m, k, n, tiles)
    out = _call(lhs.reshape(clients * m, k), rhs,
                _client_visits(group_sizes, m, tiles[0]), transpose_rhs,
                tiles, name, expert=lambda group: group % (n_groups + 1))
    return out.reshape(clients, m, n)


def _batched_by_clients(m: int, k: int, n: int, transpose_rhs: bool, tiles,
                        name: str):
    """The kernel at one client's shapes, with the rule that batches it:
    where a ``vmap`` of two or more clients batches the rows and the group
    sizes and not the matrices, ONE call with the clients on its grid
    (:func:`_grouped_clients`); any other batch (one client, or batched
    matrices) as ``pallas_call``'s own rule batches it: a batch of one
    squeezed, a wider one a loop over the batch. Each records what it
    traced (:class:`Traced`)."""
    call = functools.partial(_grouped, transpose_rhs=transpose_rhs,
                             tiles=tiles, name=name)

    @jax.custom_batching.custom_vmap
    def product(lhs, rhs, group_sizes):
        record(Traced(m, k, n, 1))
        return call(lhs, rhs, group_sizes)

    @product.def_vmap
    def _rule(clients, batched, lhs, rhs, group_sizes):
        if clients > 1 and batched == [True, False, True]:
            record(Traced(m, k, n, clients))
            return _grouped_clients(lhs, rhs, group_sizes, transpose_rhs,
                                    tiles, name), True
        return jax.vmap(call, in_axes=tuple(0 if b else None for b in batched)
                        )(lhs, rhs, group_sizes), True

    return product


def group_of_rows(group_sizes, m: int):
    """``[m]`` the group each row belongs to, ``G`` past the total."""
    return jnp.searchsorted(jnp.cumsum(group_sizes), jnp.arange(m),
                            side="right")


def plain(lhs, rhs, group_sizes, transpose_rhs: bool = False):
    """The product as one ``dot_general``: the rows masked a group,
    contracted over group and depth. ``rhs`` is an operand as it lies."""
    member = (group_of_rows(group_sizes, lhs.shape[0])[None, :]
              == jnp.arange(rhs.shape[0])[:, None])             # [G, m]
    masked = jnp.where(member[:, :, None], lhs[None], 0)
    return jnp.einsum("gmk,gnk->mn" if transpose_rhs else "gmk,gkn->mn",
                      masked, rhs.astype(lhs.dtype),
                      preferred_element_type=F32)


def _product(lhs, rhs, group_sizes, transpose_rhs, tiles, name):
    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    if not takes_kernel(m, k, n):
        return plain(lhs, rhs, group_sizes, transpose_rhs)
    out = _batched_by_clients(m, k, n, transpose_rhs, tiles, name)(
        lhs, rhs, group_sizes)
    real = jnp.arange(m) < jnp.sum(group_sizes)
    return jnp.where(real[:, None], out, 0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _grouped_matmul(lhs, rhs, group_sizes, transpose_rhs, tiles, name):
    return _product(lhs, rhs, group_sizes, transpose_rhs, tiles, name)


def _vjp_fwd(lhs, rhs, group_sizes, transpose_rhs, tiles, name):
    return (_product(lhs, rhs, group_sizes, transpose_rhs, tiles, name),
            (jnp.zeros((0,), lhs.dtype), rhs, group_sizes))


def _vjp_bwd(transpose_rhs, tiles, name, saved, dout):
    like, rhs, group_sizes = saved      # an empty array of ``lhs.dtype``
    dtype = like.dtype
    dlhs = _product(dout.astype(dtype), rhs, group_sizes, not transpose_rhs,
                    tiles, name + "_t")
    return dlhs.astype(dtype), None, None


_grouped_matmul.defvjp(_vjp_fwd, _vjp_bwd)


def grouped_matmul(lhs, rhs, group_sizes, *, transpose_rhs: bool = False,
                   name: str = "grouped_matmul"):
    """``lhs [m, k]``, frozen ``rhs [G, k, n]`` (``[G, n, k]`` with
    ``transpose_rhs``), ``group_sizes [G]`` int32 -> float32 ``[m, n]``; see
    the module's docstring. ``name`` is the kernel's in a trace, the
    backward's is ``name + "_t"``."""
    return _grouped_matmul(lhs, rhs, group_sizes, bool(transpose_rhs),
                           (TM, TK, TN, SUB), name)
