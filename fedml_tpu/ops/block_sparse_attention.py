"""Attention over blocks of keys chosen token by token (learned block-sparse
attention: MiniMax-M3's MSA, the selection branch of NSA, arXiv:2502.11089,
and MoBA, arXiv:2502.13189) as Pallas TPU kernels.

For key-value group ``g`` of ``Hkv`` and a query head ``n`` of its ``G = Hq /
Hkv`` heads, a token ``t`` attends to the keys of the blocks (``block``
consecutive tokens each) it chose, causally::

    o[t, n] = sum_{s in blocks chosen[t, g], s <= t}
              softmax_s(scale q[t, n] . k[s, g]) v[s, g]

``chosen [B, T, Hkv, S] int32`` lists a token's blocks (``-1`` in a slot that
holds none, a repeat counted once); a group's heads share them, as NSA's
selection does.

**The kernels.** A query tile is one block of ``block`` tokens; a tile's
rows of a group are ``[block, G, D]`` of q, o and dq ``[B, T, Hq, D]`` as
the projection lays them out (a token a leading index, its ``G`` heads on
sublanes). ``plan`` flattens a group's chosen lists into (token, block)
assignments and sorts each tile's by block, then token (as
``held_lora_products`` sorts rows by expert). The blocks a tile visits are
the UNION of its tokens' choices, ascending; a visit is packed into one
int32 (the block, how many of the tile's tokens chose it, where they start
in the tile's sorted list) and the lists are scalar-prefetched; a tile's
token list is an SMEM block beside them. A visit multiplies only the
tokens that chose the block, all ``G`` heads of a token stacked as rows:

- compacted: the tokens in chunks of ``chunk(block, G)`` (8 at 16 heads and
  blocks of 128: 128 rows, one MXU tile), each chunk's rows gathered by
  leading index and multiplied against the key block in one product, the
  padding rows after the list's end masked (and written to a spare row);
- whole tile: a block that every token of the tile chose (block 0 and the
  own block always; shared blocks as the indexer learns) is multiplied in
  contiguous pieces of the tile, nothing gathered. The switch reads the
  visit's count; nothing is a setting.

Only a visit to the tile's own block takes the causal mask.
``msa_pairs_computed`` (``counts``) is what the kernels multiply: ``block``
x ``block`` pairs a whole-tile visit, the padded chunk rows x ``block`` a
compacted one, so ``msa_pairs_computed`` over ``msa_pairs_selected`` is
the padding's cost.

- forward, grid ``(b, g, tile)``: the group's keys and values ``[T, D]``
  stay in VMEM (fetched once a group) and the tile's visits are a loop in
  the kernel, in ascending order, so a token meets its blocks in order; its
  running maximum and sum (lanes 0 and 1) and accumulator rows live in
  VMEM (``[block + 1, G, .]``) and are read and written back by token; ``o``
  and the row log-sum-exp ``[B, Hkv, T / block, G, block]`` (a tile's heads
  on sublanes, tokens on lanes) leave at the end;
- ``dq``: the same grid, keys and rows, ``delta = rowsum(dO o O)`` made
  first and written beside the log-sum-exp; a row's two picked from its
  token's lane;
- ``dk``, ``dv``: grid ``(b, g, key block)`` over the tiles whose union
  holds the key block (the transposed lists), the group's log-sum-exp and
  ``delta`` in VMEM; the kernel fetches each visit's q and dO rows and
  token list itself, ``IN_FLIGHT`` tiles ahead, and sums the key block's
  gradient in VMEM over its tiles' chosen tokens and the group's heads. No
  running state, no atomics, no ``[T, T]`` mask.

At 16,384 tokens a group's keys and values are 8 MB of the 16 MiB a call
may hold; a longer sequence needs the keys in slices.

On the CPU backend the kernels run interpreted (``ops/platform.py``).
``plain_block_sparse_attention`` is the same arithmetic as one masked softmax
in XLA at small shapes: what the tests hold the kernels to.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fedml_tpu.ops.platform import pallas_interpret

F32 = jnp.float32
NEG_INF = -1e30
#: the lanes of a row of a tile's token list (an SMEM block), and those a
#: row's running maximum and sum are kept in (lanes 0 and 1)
LANES = 128
#: the tiles whose q and dO rows the dk, dv kernel fetches ahead
IN_FLIGHT = 3


def chunk(block: int, group: int) -> int:
    """Tokens a compacted chunk holds: the most that divides ``block`` with
    their ``group`` heads' rows no more than ``block`` (one MXU tile of rows
    at blocks of 128)."""
    return max(c for c in range(1, block + 1)
               if block % c == 0 and c * group <= max(block, group))


def _piece(block: int, group: int) -> int:
    """Tokens a whole-tile visit takes at a time: four chunks' rows (the
    tile at small sizes), so that its float32 scores stay a few MXU tiles."""
    return min(block, 4 * chunk(block, group))


def _fields(n: int, block: int):
    """Bits of a packed visit: the block (or tile) id, then the count."""
    return max(1, (n - 1).bit_length()), block.bit_length()


def _unpack(word, n: int, block: int):
    """``(id, count, start)`` of a packed visit."""
    nb, cb = _fields(n, block)
    return (word & ((1 << nb) - 1), (word >> nb) & ((1 << cb) - 1),
            word >> (nb + cb))


class Plan(NamedTuple):
    """What the kernels visit, ``n = T / block``: ``kv [B, Hkv, n, n]`` a
    tile's union of chosen blocks ascending, the last repeated past
    ``kv_count [B, Hkv, n]``, each packed with its token count in the tile
    and where those tokens start in ``tokens``; ``q`` and ``q_count`` the
    tiles whose union holds each key block, packed with the same count and
    start; ``tokens [B, Hkv, n, block * S / 128, 128]`` a tile's (token,
    block) assignments sorted by block, then token, as the token's place in
    the tile (a tile's list in rows of 128 lanes, as an SMEM block takes
    it)."""

    kv: jax.Array
    kv_count: jax.Array
    q: jax.Array
    q_count: jax.Array
    tokens: jax.Array


def _ascending(mask):
    """``(ids, count)``: the positions where ``mask [..., n]`` holds,
    ascending, the last repeated after ``count`` (position 0 where none)."""
    n = mask.shape[-1]
    at = jnp.arange(n, dtype=jnp.int32)
    order = jnp.argsort(jnp.where(mask, at, n + at), axis=-1).astype(jnp.int32)
    count = jnp.sum(mask, axis=-1, dtype=jnp.int32)
    last = jnp.minimum(at, jnp.maximum(count, 1)[..., None] - 1)
    return jnp.take_along_axis(order, last, axis=-1), count


def _distinct(chosen, n: int):
    """``chosen [..., S]`` with an empty slot, a block out of range or a
    repeat of an earlier slot set to ``n`` (none)."""
    c = jnp.where((chosen < 0) | (chosen >= n), n, chosen).astype(jnp.int32)
    s = c.shape[-1]
    earlier = jnp.arange(s)[None, :] < jnp.arange(s)[:, None]
    repeat = jnp.any((c[..., :, None] == c[..., None, :]) & earlier, axis=-1)
    return jnp.where(repeat, n, c)


def _per_pair(blocks, block: int, n: int):
    """``[B, Hkv, n, n]`` int32: tile ``i``'s tokens that chose block ``j``,
    for ``blocks [B, Hkv, T, S]`` from ``_distinct``."""
    b, hkv = blocks.shape[:2]
    tile = blocks.reshape(b, hkv, n, -1)
    return jnp.sum(tile[..., None] == jnp.arange(n, dtype=jnp.int32),
                   axis=-2, dtype=jnp.int32)


def plan(chosen, block: int) -> Plan:
    """The forward's and the backward's visits for ``chosen [B, Hkv, T,
    S]``."""
    b, hkv, t, s = chosen.shape
    n = t // block
    blocks = _distinct(chosen, n)
    per = _per_pair(blocks, block, n)
    nb, cb = _fields(n, block)
    if (block * s) >> (31 - nb - cb):
        raise ValueError(f"{n} blocks of {block} with {s} choices a token do "
                         f"not pack into an int32 visit")
    start = jnp.cumsum(per, axis=-1) - per
    packed = (start << (nb + cb)) | (per << nb)
    at = jnp.arange(n, dtype=jnp.int32)
    hit = per > 0
    kv, kv_count = _ascending(hit)
    q, q_count = _ascending(jnp.swapaxes(hit, -1, -2))
    place = jnp.arange(t, dtype=jnp.int32) % block
    keys = jnp.where(blocks < n, blocks * block + place[:, None], n * block)
    keys = keys.reshape(b, hkv, n, block * s)
    wide = -(-block * s // LANES) * LANES
    keys = jnp.pad(keys, ((0, 0),) * 3 + ((0, wide - block * s),),
                   constant_values=n * block)
    tokens = (jnp.sort(keys, axis=-1) % block).reshape(
        b, hkv, n, wide // LANES, LANES)
    return Plan(jnp.take_along_axis(packed | at, kv, axis=-1), kv_count,
                jnp.take_along_axis(jnp.swapaxes(packed, -1, -2) | at, q,
                                    axis=-1), q_count, tokens)


def counts(chosen, block: int, group: int) -> dict:
    """What a call computes, as float32 totals over the batch and the groups
    (a group's heads share its choices, so a pair is a (token, key) pair of a
    group): ``msa_pairs_selected`` the visible pairs of the chosen blocks (a
    whole block before the token's own, the keys up to the token in its own),
    ``msa_pairs_computed`` the pairs the kernels multiply (a tile's visit to
    a block that every token of it chose, the whole tile against the block;
    any other visit, the chosen tokens padded to whole chunks), and
    ``msa_blocks_fetched`` the key-value blocks the forward reads."""
    t = chosen.shape[2]
    n = t // block
    blocks = _distinct(chosen, n)
    per = _per_pair(blocks, block, n)
    ch = chunk(block, group)
    rows = jnp.where(per == block, block, -(-per // ch) * ch)
    own = (jnp.arange(t, dtype=jnp.int32) // block)[None, None, :, None]
    seen = jnp.where(blocks == own,
                     (jnp.arange(t) % block + 1)[None, None, :, None], block)
    return {"msa_pairs_selected": jnp.sum(
                jnp.where(blocks < n, seen, 0)).astype(F32),
            "msa_pairs_computed": jnp.sum(rows).astype(F32) * block,
            "msa_blocks_fetched": jnp.sum(per > 0).astype(F32)}


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=F32)


class _Call(NamedTuple):
    """What the three calls are compiled for (hashable: a static argument)."""

    hkv: int
    group: int
    block: int
    scale: float


def _flat(n: int, hkv: int):
    """The flat index of ``(b, h, i, s)`` in a ``[B, Hkv, n, n]`` list, and
    of ``(b, h, i)`` in its ``[B, Hkv, n]`` counts."""
    return lambda b, h, i, s=None: (b * hkv + h) * n + i if s is None else (
        ((b * hkv + h) * n + i) * n + s)


class _Visit(NamedTuple):
    """A query ``tile``'s visit to key block ``blk``: the ``count`` tokens of
    the tile that chose it, from ``start`` of the tile's sorted list
    ``toks``."""

    tile: jax.Array
    blk: jax.Array
    count: jax.Array
    start: jax.Array
    toks: object


def _rows(ref, rows):
    """A row block of ``ref [tokens, G, X]``: ``[tokens * G, X]`` for
    ``rows`` a slice of tokens, or the listed tokens' rows one after the
    other."""
    if isinstance(rows, slice):
        x = ref[rows]
        return x.reshape(-1, x.shape[-1])
    return jnp.concatenate([ref[t] for t in rows], axis=0)


def _write(ref, rows, value, g: int):
    """Writes ``value [R, X]`` back where ``_rows`` read it."""
    if isinstance(rows, slice):
        ref[rows] = value.reshape(-1, g, value.shape[-1])
        return
    for k, t in enumerate(rows):
        ref[t] = value[k * g:(k + 1) * g]


def _column(values, g: int):
    """``[len(values) * g, 1]`` int32, each scalar repeated ``g`` times."""
    return jnp.concatenate(
        [jnp.full((g, 1), x, jnp.int32) for x in values], axis=0)


def _pick(a, tok):
    """``[R, 1]``: row ``r`` of a chunk takes ``a [G, block]`` (a tile's
    heads on sublanes, tokens on lanes) at head ``r % G`` and token
    ``tok[r]``."""
    g, blk = a.shape
    r = tok.shape[0]
    tiled = jnp.broadcast_to(a[None], (r // g, g, blk)).reshape(r, blk)
    lane = jax.lax.broadcasted_iota(jnp.int32, (r, blk), 1)
    return jnp.sum(jnp.where(lane == tok, tiled, 0.0), axis=1, keepdims=True)


def _causal(v: _Visit, tok, block: int):
    """``[R, block]`` bool: the key is not after the row's token."""
    k_pos = v.blk * block + jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)
    return k_pos <= v.tile * block + tok


def _each_chunk(v: _Visit, c: _Call, body):
    """Calls ``body(rd, wr, tok, keep)`` for each chunk of a visit: ``rd``
    the chunk's tokens as scalars to read rows at, ``wr`` where their
    running rows are (``block``, the spare row, for padding), ``tok [R, 1]``
    each row's token and ``keep`` the scores that count (broadcast to ``[R,
    block]``; None for all). A whole visit is read and written in
    contiguous pieces of the tile (``rd`` and ``wr`` the same slice). Only a
    visit to the tile's own block (or a later one) takes the causal mask."""
    g, ch, blk = c.group, chunk(c.block, c.group), c.block
    before = v.blk < v.tile

    def whole(causal):
        part = _piece(blk, g)
        for first in range(0, blk, part):
            rows = slice(first, first + part)
            tok = first + jax.lax.broadcasted_iota(
                jnp.int32, (part * g, 1), 0) // g
            body(rows, rows, tok, _causal(v, tok, blk) if causal else None)

    def compacted(causal):
        last = v.start + v.count - 1

        def one(i, carry):
            first = v.start + i * ch
            at = [jnp.minimum(first + k, last) for k in range(ch)]
            rd = [v.toks[x // LANES, x % LANES] for x in at]
            left = v.count - i * ch
            wr = [jnp.where(k < left, rd[k], blk) for k in range(ch)]
            tok = _column(rd, g)
            keep = jax.lax.broadcasted_iota(jnp.int32, (ch * g, 1), 0) < (
                left * g)
            body(rd, wr, tok, keep & _causal(v, tok, blk) if causal else keep)
            return carry

        jax.lax.fori_loop(0, (v.count + ch - 1) // ch, one, 0)

    for causal in (False, True):
        at_own = jnp.logical_not(before) if causal else before
        pl.when((v.count == blk) & at_own)(lambda causal=causal: whole(causal))
        pl.when((v.count < blk) & at_own)(
            lambda causal=causal: compacted(causal))


def _masked(sc, keep):
    return sc if keep is None else jnp.where(keep, sc, NEG_INF)


def _each_visit(kv_ref, count_ref, toks_ref, k_ref, v_ref, c: _Call,
                n: int, body):
    """Over the tile's union, ascending: ``body(visit, k, v)`` with the key
    block's rows read from the group's keys and values in VMEM."""
    b, h, i = (pl.program_id(a) for a in range(3))
    at = _flat(n, c.hkv)

    def one(s, carry):
        kid, cnt, start = _unpack(kv_ref[at(b, h, i, s)], n, c.block)
        rows = pl.ds(pl.multiple_of(kid * c.block, c.block), c.block)
        body(_Visit(i, kid, cnt, start, toks_ref), k_ref[rows, :],
             v_ref[rows, :])
        return carry

    jax.lax.fori_loop(0, count_ref[at(b, h, i)], one, 0)


def _lanes(rows_of, g: int, blk: int):
    """``[G, block]``: token ``t``'s ``[G, 1]`` column ``rows_of(t)`` in
    lane ``t``."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (g, blk), 1)
    return jax.lax.fori_loop(
        0, blk, lambda t, out: jnp.where(lane == t, rows_of(t), out),
        jnp.zeros((g, blk), F32))


def _fwd_kernel(kv_ref, count_ref, toks_ref, q_ref, k_ref, v_ref, o_ref,
                lse_ref, acc, ml_s, *, c: _Call, n: int):
    blk, g = c.block, c.group
    acc[...] = jnp.zeros_like(acc)
    lane = jax.lax.broadcasted_iota(jnp.int32, ml_s.shape, 2)
    ml_s[...] = jnp.where(lane == 0, NEG_INF, 0.0)

    def visit(vis, k, v):
        def body(rd, wr, tok, keep):
            q = _rows(q_ref, rd)
            sc = _masked(_dot(q, k, ((1,), (1,))) * c.scale, keep)
            # the first visit is block 0, which every token chooses: the
            # running maximum is finite from there on, and a masked key adds
            # exp(NEG_INF - m) = 0; a padding row keeps to the spare row
            ml = _rows(ml_s, wr)
            m_prev = ml[:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
            p = jnp.exp(sc - m_new)
            turn = jnp.exp(m_prev - m_new)
            l_new = ml[:, 1:2] * turn + jnp.sum(p, axis=1, keepdims=True)
            at_m = jax.lax.broadcasted_iota(jnp.int32, ml.shape, 1) == 0
            _write(ml_s, wr, jnp.where(at_m, m_new, l_new), g)
            _write(acc, wr, _rows(acc, wr) * turn + _dot(
                p.astype(v.dtype), v, ((1,), (0,))), g)

        _each_chunk(vis, c, body)

    _each_visit(kv_ref, count_ref, toks_ref, k_ref, v_ref, c, n, visit)
    o_ref[...] = (acc[:blk] / ml_s[:blk][..., 1:2]).astype(o_ref.dtype)
    lse_ref[...] = _lanes(
        lambda t: ml_s[t][:, :1] + jnp.log(ml_s[t][:, 1:2]), g, blk)


def _dq_kernel(kv_ref, count_ref, toks_ref, q_ref, k_ref, v_ref, do_ref,
               o_ref, lse_ref, dq_ref, delta_ref, acc, *, c: _Call, n: int):
    blk, g = c.block, c.group
    acc[...] = jnp.zeros_like(acc)
    delta_ref[...] = _lanes(lambda t: jnp.sum(
        do_ref[t].astype(F32) * o_ref[t].astype(F32), axis=1,
        keepdims=True), g, blk)

    def visit(vis, k, v):
        def body(rd, wr, tok, keep):
            q, do = _rows(q_ref, rd), _rows(do_ref, rd)
            sc = _masked(_dot(q, k, ((1,), (1,))) * c.scale, keep)
            p = jnp.exp(sc - _pick(lse_ref[...], tok))
            dp = _dot(do, v, ((1,), (1,)))
            ds = (p * (dp - _pick(delta_ref[...], tok))).astype(k.dtype)
            _write(acc, wr, _rows(acc, wr) + _dot(ds, k, ((1,), (0,))), g)

        _each_chunk(vis, c, body)

    _each_visit(kv_ref, count_ref, toks_ref, k_ref, v_ref, c, n, visit)
    dq_ref[...] = (acc[:blk] * c.scale).astype(dq_ref.dtype)


def _dkv_kernel(tiles_ref, count_ref, q_hbm, do_hbm, toks_hbm, lse_ref,
                delta_ref, k_ref, v_ref, dk_ref, dv_ref, q_buf, do_buf,
                toks_buf, sem, dk_acc, dv_acc, *, c: _Call, n: int):
    b, h, j = (pl.program_id(a) for a in range(3))
    at = _flat(n, c.hkv)
    blk, g = c.block, c.group
    count = count_ref[at(b, h, j)]

    def copies(s):
        """The DMAs of visit ``s``'s tile: its q and dO rows of the group
        and its token list, into buffer ``s % IN_FLIGHT``."""
        tile = _unpack(tiles_ref[at(b, h, j, s)], n, blk)[0]
        rows = (b, pl.ds(tile * blk, blk), pl.ds(h * g, g))
        slot = s % IN_FLIGHT
        return (pltpu.make_async_copy(q_hbm.at[rows], q_buf.at[slot],
                                      sem.at[0, slot]),
                pltpu.make_async_copy(do_hbm.at[rows], do_buf.at[slot],
                                      sem.at[1, slot]),
                pltpu.make_async_copy(toks_hbm.at[b, h, tile],
                                      toks_buf.at[slot], sem.at[2, slot]))

    def fetch(s):
        @pl.when(s < count)
        def _start():
            for cp in copies(s):
                cp.start()

    for s in range(IN_FLIGHT - 1):
        fetch(s)
    dk_acc[...] = jnp.zeros_like(dk_acc)
    dv_acc[...] = jnp.zeros_like(dv_acc)
    k, v = k_ref[...], v_ref[...]

    def one(s, carry):
        fetch(s + IN_FLIGHT - 1)
        for cp in copies(s):
            cp.wait()
        slot = s % IN_FLIGHT
        tile, cnt, start = _unpack(tiles_ref[at(b, h, j, s)], n, blk)
        q_ref, do_ref = q_buf.at[slot], do_buf.at[slot]
        lse, delta = lse_ref[tile], delta_ref[tile]

        def body(rd, wr, tok, keep):
            q, do = _rows(q_ref, rd), _rows(do_ref, rd)
            sc = _masked(_dot(q, k, ((1,), (1,))) * c.scale, keep)
            p = jnp.exp(sc - _pick(lse, tok))                  # [rows, keys]
            dv_acc[...] += _dot(p.astype(do.dtype), do, ((0,), (0,)))
            dp = _dot(do, v, ((1,), (1,)))
            ds = (p * (dp - _pick(delta, tok))).astype(q.dtype)
            dk_acc[...] += _dot(ds, q, ((0,), (0,)))

        _each_chunk(_Visit(tile, j, cnt, start, toks_buf.at[slot]), c, body)
        return carry

    jax.lax.fori_loop(0, count, one, 0)
    dk_ref[...] = (dk_acc[...] * c.scale).astype(dk_ref.dtype)
    dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _tile_specs(c: _Call, t: int, d: int):
    """Block specs on the grid ``(b, h, tile)``: the tile's rows and token
    list, and the group's keys and values whole (``[T, D]``, fetched once a
    group)."""
    return dict(
        heads=pl.BlockSpec((None, c.block, c.group, d),
                           lambda b, h, i, lst, cnt: (b, i, h, 0)),
        keys=pl.BlockSpec((None, t, d), lambda b, h, i, lst, cnt: (b, 0, h),
                          pipeline_mode=pl.Buffered(1)),
        rows=pl.BlockSpec((None, None, None, c.group, c.block),
                          lambda b, h, i, lst, cnt: (b, h, i, 0, 0)),
        toks=lambda rows: pl.BlockSpec(
            (None, None, None, rows, LANES),
            lambda b, h, i, lst, cnt: (b, h, i, 0, 0),
            memory_space=pltpu.SMEM))


def _key_specs(c: _Call, n: int, d: int):
    """Block specs on the grid ``(b, h, key block)``: the key block's rows,
    the group's row log-sum-exp and ``delta`` whole (``[n, G, block]``,
    fetched once a group), and q, dO and the token lists where they lie
    (the kernel fetches a visit's tile itself)."""
    return dict(
        head=pl.BlockSpec((None, c.block, d),
                          lambda b, h, j, lst, cnt: (b, j, h)),
        rows=pl.BlockSpec((None, None, n, c.group, c.block),
                          lambda b, h, j, lst, cnt: (b, h, 0, 0, 0),
                          pipeline_mode=pl.Buffered(1)),
        anywhere=pl.BlockSpec(memory_space=pl.ANY))


def _call(kernel, name, c: _Call, n: int, grid, in_specs, out_specs,
          out_shape, scratch):
    semantics = ("parallel",) * 3 + ("arbitrary",) * (len(grid) - 3)
    return pl.pallas_call(
        functools.partial(kernel, c=c, n=n),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=grid, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch),
        out_shape=out_shape, interpret=pallas_interpret(), name=name,
        compiler_params=pltpu.CompilerParams(dimension_semantics=semantics))


def _state(c: _Call, width: int):
    """A tile's running rows, ``[block + 1, G, width]`` float32: a row a
    token and head, and a spare token row where a chunk's padding goes (the
    accumulator, or a row's maximum and sum in lanes 0 and 1)."""
    return pltpu.VMEM((c.block + 1, c.group, width), F32)


@functools.partial(jax.jit, static_argnames="c")
def _forward(q, k, v, p: Plan, c: _Call):
    """``(o, lse)``."""
    b, t, _, d = q.shape
    n = t // c.block
    spec = _tile_specs(c, t, d)
    call = _call(
        _fwd_kernel, "msa_fwd", c, n, (b, c.hkv, n),
        [spec["toks"](p.tokens.shape[-2]), spec["heads"], spec["keys"],
         spec["keys"]],
        [spec["heads"], spec["rows"]],
        [jax.ShapeDtypeStruct(q.shape, q.dtype),
         jax.ShapeDtypeStruct((b, c.hkv, n, c.group, c.block), F32)],
        [_state(c, d), _state(c, LANES)])
    return call(p.kv.reshape(-1), p.kv_count.reshape(-1), p.tokens, q, k, v)


@functools.partial(jax.jit, static_argnames="c")
def _backward(q, k, v, p: Plan, o, lse, do, c: _Call):
    """``(dq, dk, dv)``."""
    b, t, _, d = q.shape
    n = t // c.block
    spec = _tile_specs(c, t, d)
    rows = jax.ShapeDtypeStruct((b, c.hkv, n, c.group, c.block), F32)
    dq, delta = _call(
        _dq_kernel, "msa_dq", c, n, (b, c.hkv, n),
        [spec["toks"](p.tokens.shape[-2]), spec["heads"], spec["keys"],
         spec["keys"], spec["heads"], spec["heads"], spec["rows"]],
        [spec["heads"], spec["rows"]],
        [jax.ShapeDtypeStruct(q.shape, q.dtype), rows], [_state(c, d)],
    )(p.kv.reshape(-1), p.kv_count.reshape(-1), p.tokens, q, k, v, do, o,
      lse)
    spec = _key_specs(c, n, d)
    width = p.tokens.shape[-2:]
    dk, dv = _call(
        _dkv_kernel, "msa_dkv", c, n, (b, c.hkv, n),
        [spec["anywhere"], spec["anywhere"], spec["anywhere"], spec["rows"],
         spec["rows"], spec["head"], spec["head"]],
        [spec["head"], spec["head"]],
        [jax.ShapeDtypeStruct(k.shape, k.dtype),
         jax.ShapeDtypeStruct(v.shape, v.dtype)],
        [pltpu.VMEM((IN_FLIGHT, c.block, c.group, d), q.dtype),
         pltpu.VMEM((IN_FLIGHT, c.block, c.group, d), do.dtype),
         pltpu.SMEM((IN_FLIGHT,) + width, jnp.int32),
         pltpu.SemaphoreType.DMA((3, IN_FLIGHT)),
         pltpu.VMEM((c.block, d), F32), pltpu.VMEM((c.block, d), F32)],
    )(p.q.reshape(-1), p.q_count.reshape(-1), q, do, p.tokens, lse, delta,
      k, v)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _msa(q, k, v, sel, c: _Call):
    return _forward(q, k, v, plan(sel, c.block), c)[0]


def _msa_fwd(q, k, v, sel, c):
    p = plan(sel, c.block)
    o, lse = _forward(q, k, v, p, c)
    return o, (q, k, v, p, o, lse)


def _msa_bwd(c, res, do):
    q, k, v, p, o, lse = res
    return (*_backward(q, k, v, p, o, lse, do, c), None)


_msa.defvjp(_msa_fwd, _msa_bwd)


def _checked(q, k, chosen, block: int):
    b, t, hq, d = q.shape
    hkv = k.shape[2]
    if hq % hkv or t % block or chosen.shape != (b, t, hkv, chosen.shape[-1]):
        raise ValueError(
            f"q {q.shape}, k {k.shape}, chosen {chosen.shape}: query heads "
            f"in whole groups of the key-value heads, the sequence in whole "
            f"blocks of {block}, a list of blocks a token and group")
    return b, t, hq, hkv, d


def block_sparse_attention(q, k, v, chosen, block: int,
                           scale: float | None = None):
    """``q [B, T, Hq, D]``, ``k``, ``v [B, T, Hkv, D]``, ``chosen [B, T, Hkv,
    S] int32`` (each token's blocks of ``block`` keys, ``-1`` in an empty
    slot; block 0 among them, the first slot the kernels visit) ->
    ``o [B, T, Hq, D]`` in ``q``'s dtype. The kernels above; ``chosen``
    takes no gradient."""
    b, t, hq, hkv, d = _checked(q, k, chosen, block)
    scale = float(d ** -0.5 if scale is None else scale)
    # [B, T, H, D] and [B, T, H*D] are tiled differently in HBM: as in
    # ``flash_attention``'s band, the barrier keeps the keys' reshape one
    # copy of what the caller made (ops/flash_attention.py, PERF.md
    # section 6); q, o and their gradients stay as the caller lays them out
    q, k, v = jax.lax.optimization_barrier((q, k, v))
    sel = jnp.swapaxes(jax.lax.stop_gradient(chosen), 1, 2).astype(jnp.int32)
    return _msa(q, k.reshape(b, t, hkv * d), v.reshape(b, t, hkv * d), sel,
                _Call(hkv, hq // hkv, block, scale))


def visible(chosen, block: int):
    """``[B, Hkv, T, T]`` bool: query ``t`` of group ``g`` sees key ``s``."""
    t = chosen.shape[1]
    mine = jnp.any(chosen[..., None] == (jnp.arange(t) // block),
                   axis=-2)                               # [B, T, Hkv, T]
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    return jnp.swapaxes(mine, 1, 2) & causal


def plain_block_sparse_attention(q, k, v, chosen, block: int,
                                 scale: float | None = None):
    """The same arithmetic in XLA: one softmax over ``[T, T]`` scores masked
    to the chosen blocks, products in ``q``'s dtype with float32
    accumulation. For small shapes."""
    b, t, hq, hkv, d = _checked(q, k, chosen, block)
    scale = d ** -0.5 if scale is None else scale
    group = hq // hkv
    qg = q.reshape(b, t, hkv, group, d)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k.astype(q.dtype),
                   preferred_element_type=F32) * scale
    s = jnp.where(visible(jax.lax.stop_gradient(chosen), block)[:, :, None],
                  s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(q.dtype),
                   preferred_element_type=F32)
    return o.reshape(b, t, hq, d).astype(q.dtype)
