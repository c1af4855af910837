"""Expert parallelism: top-1 mixture-of-experts with all_to_all dispatch.

New TPU capability (nothing comparable exists in the reference, SURVEY.md
§2.10): E experts' MLPs live one-per-device on an ``ep`` mesh axis; tokens
are sharded over the same axis. Each device routes its tokens (top-1 +
softmax gate), packs them into per-expert capacity buffers, and a single
``lax.all_to_all`` ships every buffer to its expert's device — the
canonical MoE dispatch that rides ICI. Expert compute is one batched MLP;
a second all_to_all returns outputs, which are unpacked and gate-weighted.

Tokens over capacity are dropped (output 0 — standard Switch-style
behavior); with ``capacity >= tokens_per_device`` no token can drop and the
sharded result equals the dense oracle exactly (tested).

``route_held`` / ``held_expert_products`` are the layer a model calls as ONE
SHARD of an expert-parallel layer: it is told how many experts exist and
which contiguous range it holds, routes top-k over all of them, keeps every
token (no capacity drops), and computes the part of the result its own
experts give. On one chip it runs without its exchange, and nothing stands
in for the absent shards: a token none of whose experts is held gets 0 here.

``route_sigmoid`` / ``sort_held`` / ``held_lora_products`` are the same shard
for FROZEN experts with a low-rank pair a client beside each matrix (the
federated adapter round): the router scores with a sigmoid and selects on
score + bias (the DeepSeek-V3 router), and the product is GROUPED over the
assignments sorted by expert (``ops/grouped_matmul.py``: a Mosaic kernel whose
row tiles read their own expert's matrices where they lie, whatever the
clients' ``vmap`` batches, and which computes the tiles that hold real rows
only), in chunks of ``chunk_rows`` rows of the sorted order.
"""

from __future__ import annotations

from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from fedml_tpu.ops.grouped_matmul import group_of_rows, grouped_matmul


class MoEParams(NamedTuple):
    w_router: jax.Array  # [d, E]
    w_in: jax.Array      # [E, d, h]
    b_in: jax.Array      # [E, h]
    w_out: jax.Array     # [E, h, d]
    b_out: jax.Array     # [E, d]


def init_moe(rng, d: int, hidden: int, n_experts: int) -> MoEParams:
    k1, k2, k3 = jax.random.split(rng, 3)
    s_in = 1.0 / jnp.sqrt(d)
    s_out = 1.0 / jnp.sqrt(hidden)
    return MoEParams(
        w_router=jax.random.normal(k1, (d, n_experts)) * s_in,
        w_in=jax.random.normal(k2, (n_experts, d, hidden)) * s_in,
        b_in=jnp.zeros((n_experts, hidden)),
        w_out=jax.random.normal(k3, (n_experts, hidden, d)) * s_out,
        b_out=jnp.zeros((n_experts, d)),
    )


def _expert_mlp(x, w_in, b_in, w_out, b_out):
    return jax.nn.gelu(x @ w_in + b_in) @ w_out + b_out


def moe_reference(params: MoEParams, x: jnp.ndarray) -> jnp.ndarray:
    """Dense oracle: every expert runs on every token, outputs masked by the
    top-1 routing decision and weighted by the softmax gate. [N, d] → [N, d]."""
    logits = x @ params.w_router  # [N, E]
    idx = jnp.argmax(logits, axis=-1)
    gate = jnp.take_along_axis(jax.nn.softmax(logits, -1), idx[:, None], -1)[:, 0]
    all_out = jax.vmap(
        lambda w_in, b_in, w_out, b_out: _expert_mlp(x, w_in, b_in, w_out, b_out)
    )(params.w_in, params.b_in, params.w_out, params.b_out)  # [E, N, d]
    sel = jnp.take_along_axis(
        all_out, idx[None, :, None], axis=0)[0]  # [N, d]
    return sel * gate[:, None]


def make_moe_ep(mesh, axis: str = "ep", capacity: int | None = None):
    """``moe(params, x) -> y`` with tokens AND experts sharded over
    ``mesh[axis]``; one expert per device (E == mesh size). ``capacity`` =
    max tokens each (source device → expert) pair can carry per call;
    defaults to tokens_per_device (lossless)."""
    n_dev = int(mesh.shape[axis])

    def validated(params, x):
        e = params.w_in.shape[0]
        if e != n_dev:
            raise ValueError(
                f"MoE has {e} experts but the '{axis}' mesh axis has "
                f"{n_dev} devices; this layout runs one expert per device "
                f"(a mismatch would silently drop tokens routed to experts "
                f">= {n_dev})")
        return _moe(params, x)

    @partial(shard_map, mesh=mesh,
             in_specs=(
                 MoEParams(P(), P(axis), P(axis), P(axis), P(axis)),
                 P(axis),
             ),
             out_specs=P(axis), check_vma=False)
    def _moe(params, x):
        n_local, d = x.shape
        cap = capacity or n_local
        # Local routing over the FULL router (replicated) --------------
        logits = x @ params.w_router  # [n_local, E]
        idx = jnp.argmax(logits, axis=-1)
        gate = jnp.take_along_axis(
            jax.nn.softmax(logits, -1), idx[:, None], -1)[:, 0]
        # Pack per-expert capacity buffers -----------------------------
        onehot = jax.nn.one_hot(idx, n_dev, dtype=jnp.int32)  # [n, E]
        pos = jnp.cumsum(onehot, axis=0) * onehot - 1  # slot per token, -1 if other expert
        pos = jnp.max(pos, axis=1)  # [n]
        keep = pos < cap
        dispatch = (
            jax.nn.one_hot(idx, n_dev, dtype=x.dtype)[:, :, None]
            * jax.nn.one_hot(jnp.where(keep, pos, cap), cap + 1,
                             dtype=x.dtype)[:, None, :]
        )[:, :, :cap]  # [n, E, cap] (overflow slot truncated)
        buf = jnp.einsum("nec,nd->ecd", dispatch, x)  # [E, cap, d]
        # Ship buffers to their expert's device ------------------------
        recv = jax.lax.all_to_all(buf, axis, split_axis=0, concat_axis=0,
                                  tiled=True)  # [n_dev*cap, d] for MY expert
        # Expert compute (device-local expert 0 of the sharded stack) --
        y = _expert_mlp(recv, params.w_in[0], params.b_in[0],
                        params.w_out[0], params.b_out[0])
        # Return outputs to the token owners ---------------------------
        back = jax.lax.all_to_all(
            y.reshape(n_dev, cap, d), axis, split_axis=0, concat_axis=0,
            tiled=True).reshape(n_dev, cap, d)  # [E, cap, d] from each expert
        out = jnp.einsum("nec,ecd->nd", dispatch, back)
        return out * (gate * keep.astype(x.dtype))[:, None]

    return validated


# ---------------------------------------------------------------------------
# One shard's part of a top-k expert layer (no exchange, no drops)
# ---------------------------------------------------------------------------

class HeldRouting(NamedTuple):
    """Top-k routing over ALL experts, laid out for the held range."""

    idx: jax.Array       # [N, k] the chosen experts (global ids)
    weight: jax.Array    # [N, k] float32 combine weights
    token: jax.Array     # [rows] source token of each row of the layout
    slot_weight: jax.Array  # [rows] float32; 0 on a padding row
    tile_expert: jax.Array  # [rows / tile] the held expert a tile belongs to
    counts: jax.Array    # [H] int32 tokens routed to each held expert
    unrouted: jax.Array  # [] int32 tokens none of whose experts is held
    overflow: jax.Array  # [] bool: the held assignments need more tiles


#: rows of the grouped product over the mean number of held assignments. A
#: router trained with no balancing loss sends its held experts 2-5 times
#: their share of the tokens within a benchmark window (PERF.md, PR 28); the
#: rows cost products whether filled or not, so not more than this.
CAPACITY_FACTOR = 3.0


def held_layout(n_tokens: int, top_k: int, n_experts: int,
                n_held: int) -> tuple:
    """``(tile, n_tiles)`` of the grouped product's rows: tiles of 128 rows
    (the MXU's; 8 where an expert's mean load is under 64 tokens), each
    belonging to one held expert, whose tokens fill as many tiles as they
    need. Room for ``CAPACITY_FACTOR`` times the mean number of held
    assignments plus one part-filled tile an expert. Not a drop threshold:
    past it the layer takes its dense path (``held_expert_products``). What
    overflows is the TOTAL, not one popular expert: an untrained router is
    flat, a trained one is not."""
    per_expert = n_tokens * top_k / n_experts
    tile = 128 if per_expert >= 64 else 8
    return tile, n_held + int(
        -(-CAPACITY_FACTOR * per_expert * n_held // tile))


def route_held(x, w_router, n_held: int, first: int, top_k: int, tile: int,
               n_tiles: int, renormalise: bool = True) -> HeldRouting:
    """``x [N, d]`` and ``w_router [d, E]`` in float32: softmax over all
    ``E`` experts, the ``top_k`` largest, weights renormalised over the
    chosen (``renormalise``). The assignments to held experts
    ``first .. first + n_held - 1`` are SORTED by expert (one stable sort of
    the ``N * top_k`` assignments, the others keyed past the last held
    expert) and laid out in ``n_tiles`` tiles of ``tile`` rows, an expert's
    tokens padded to whole tiles."""
    n = x.shape[0]
    probs = jax.nn.softmax(
        jnp.dot(x.astype(jnp.float32), w_router.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST), axis=-1)
    weight, idx = jax.lax.top_k(probs, top_k)
    if renormalise:
        weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
    local = idx - first
    held = (local >= 0) & (local < n_held)
    key = jnp.where(held, local, n_held).reshape(-1)        # [N * k]
    order = jnp.argsort(key, stable=True)
    counts = jnp.sum(
        (key[:, None] == jnp.arange(n_held)[None, :]).astype(jnp.int32),
        axis=0)
    start = jnp.cumsum(counts) - counts         # in the sorted assignments
    padded = -(-counts // tile) * tile
    end = jnp.cumsum(padded)                    # in the layout's rows
    tile_expert = jnp.minimum(
        jnp.searchsorted(end, jnp.arange(n_tiles) * tile, side="right"),
        n_held - 1).astype(jnp.int32)
    expert = jnp.repeat(tile_expert, tile)                  # [rows]
    rank = jnp.arange(n_tiles * tile) - (end - padded)[expert]
    valid = rank < counts[expert]       # false on padding and spare tiles
    source = order[jnp.clip(start[expert] + rank, 0, n * top_k - 1)]
    return HeldRouting(
        idx=idx, weight=weight, token=source // top_k,
        slot_weight=jnp.where(valid, weight.reshape(-1)[source], 0.0),
        tile_expert=tile_expert, counts=counts,
        unrouted=jnp.sum(1 - jnp.any(held, axis=-1).astype(jnp.int32)),
        overflow=end[-1] > n_tiles * tile)


def gated_mlp(x, w_gate_up, w_down, spec_in: str, spec_out: str):
    """``W_down (SiLU(W_gate x) * W_up x)`` with gate and up side by side in
    ``w_gate_up [..., d, 2f]``; float32 accumulation."""
    f32 = jnp.float32
    gate_up = jnp.einsum(spec_in, x, w_gate_up, preferred_element_type=f32)
    gate, up = jnp.split(gate_up, 2, axis=-1)
    hidden = (jax.nn.silu(gate) * up).astype(x.dtype)
    return jnp.einsum(spec_out, hidden, w_down, preferred_element_type=f32)


def held_expert_products(x, routing: HeldRouting, w_gate_up, w_down,
                         first: int):
    """The held experts' part of the layer: ``sum_{e in top-k(n), e held}
    w_ne E_e(x_n)`` as float32 ``[N, d]``. ``w_gate_up [H, d, 2f]``,
    ``w_down [H, f, d]``, in ``x``'s dtype.

    Grouped products: the layout's rows gathered tile by tile, each tile
    multiplied by its own expert's matrices (padded-group einsum: one
    batched product over ``[tiles, tile, d]``, standard MXU shapes),
    weighted and scatter-added back. Not ``jax.lax.ragged_dot`` on the
    sorted assignments (measured on the v5e, PERF.md section 6, PR 28): over
    all ``N * k`` of them, which needs no capacity, it is 1.8 times slower a
    layer, forward and backward, than these tiles; over the tiles' rows it
    is 0.73 times, but keeps the capacity and the dense arm, leaves the rows
    past the groups' total unwritten on the chip, and its gradient has no
    batching rule (jax 0.9.0), so it cannot run under the round's ``vmap``
    over clients. If the held
    assignments need more tiles than the layout has, the whole layer is
    computed the plain way instead (every held expert over every token,
    masked by the routing; ``lax.cond``, so it costs nothing while unused):
    slower, never lossy. Each arm is rematerialised, although the model's
    layer already is: the backward pass of a ``cond`` hands over the
    residuals of BOTH arms, zeros for the arm not taken, and the dense arm's
    are 0.9 GB a layer; with the arms' operands as the only residuals a
    round of the benchmark's cell is 1,544 ms, without 1,655 ms and 0.66 GB
    more (PERF.md section 6, PR 28)."""
    n, d = x.shape
    n_held = w_gate_up.shape[0]
    tile = routing.token.shape[0] // routing.tile_expert.shape[0]

    @jax.checkpoint
    def grouped(x, routing, w_gate_up, w_down):
        rows = jnp.take(x, routing.token, axis=0).reshape(-1, tile, d)
        out = gated_mlp(rows, jnp.take(w_gate_up, routing.tile_expert, axis=0),
                        jnp.take(w_down, routing.tile_expert, axis=0),
                        "tcd,tdf->tcf", "tcf,tfd->tcd")
        out = out.reshape(-1, d) * routing.slot_weight[:, None]
        return jnp.zeros((n, d), jnp.float32).at[routing.token].add(out)

    @jax.checkpoint
    def dense(x, routing, w_gate_up, w_down):
        def one(acc, expert):
            e, gate_up, down = expert
            w_e = jnp.sum(jnp.where(routing.idx == first + e,
                                    routing.weight, 0.0), axis=-1)
            out = gated_mlp(x, gate_up, down, "nd,df->nf", "nf,fd->nd")
            return acc + out * w_e[:, None], None

        acc, _ = jax.lax.scan(one, jnp.zeros((n, d), jnp.float32),
                              (jnp.arange(n_held), w_gate_up, w_down))
        return acc

    return jax.lax.cond(routing.overflow, dense, grouped, x, routing,
                        w_gate_up, w_down)


# ---------------------------------------------------------------------------
# One shard's part of a top-k layer of FROZEN experts with low-rank pairs
# ---------------------------------------------------------------------------

#: rows of a chunk of the grouped product over the mean number of held
#: assignments a client-step (``tokens * top_k * held / experts``). A chunk's
#: rows cost a gather, the elementwise passes and the combine whether filled
#: or not (the products themselves run over the real rows only), and what a
#: step's held assignments need beyond it costs a further chunk of the same
#: height. It is the TOTAL that has to fit, not the fullest expert: under a
#: balanced router a client-step of Zipf tokens sends one held expert 3.2
#: times the mean, but a layer's held experts together 0.93-1.18 times theirs
#: (PERF.md section 6, PR 34 and PR 35: at 2 no client-step of the seeds run
#: took a further chunk, at 1.5 one in ten of one seed's did).
CHUNK_FACTOR = 2.0


def chunk_rows(n_tokens: int, top_k: int, n_experts: int,
               n_held: int) -> int:
    """Rows of a chunk of the held experts' grouped product: ``CHUNK_FACTOR``
    times the mean number of held assignments, in whole row tiles of the
    kernel (512; sublane tiles of 8 where a chunk would not fill one)."""
    rows = CHUNK_FACTOR * n_tokens * top_k * n_held / n_experts
    tile = 512 if rows >= 512 else 8
    return max(tile, int(-(-rows // tile)) * tile)


def route_sigmoid(x, w_router, bias, top_k: int, scale: float = 1.0,
                  renormalise: bool = True):
    """``(idx [N, k], weight [N, k])``: scores ``s = sigmoid(x W_r)`` over all
    experts in float32 at the highest precision, the ``top_k`` largest of
    ``s + bias`` (the bias SELECTS only), weights ``scale * s[idx] /
    (sum s[idx] + 1e-20)`` (``renormalise``; ``scale * s[idx]`` without)."""
    scores = jax.nn.sigmoid(
        jnp.dot(x.astype(jnp.float32), w_router.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST))
    _, idx = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
    weight = jnp.take_along_axis(scores, idx, axis=-1)
    if renormalise:
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-20)
    return idx, scale * weight


class HeldAssignments(NamedTuple):
    """The ``N * k`` assignments sorted by held expert: ``order[: sum(counts)]``
    are the held experts' (expert 0's first), the rows a grouped product
    takes chunk by chunk; the others follow."""

    order: jax.Array     # [N * k] assignment ids, a held expert's together
    rank: jax.Array      # [N * k] where each assignment is in ``order``
    counts: jax.Array    # [H] int32 assignments of each held expert
    start: jax.Array     # [H] int32 where each expert's begin in ``order``
    unrouted: jax.Array  # [] int32 tokens none of whose experts is held


def sort_held(idx, n_held: int, first: int) -> HeldAssignments:
    """One stable sort of the chosen experts ``idx [N, k]``, the assignments
    to experts outside ``first .. first + n_held - 1`` keyed last."""
    local = idx - first
    held = (local >= 0) & (local < n_held)
    key = jnp.where(held, local, n_held).reshape(-1)
    counts = jnp.sum(
        (key[:, None] == jnp.arange(n_held)[None, :]).astype(jnp.int32),
        axis=0)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    return HeldAssignments(
        order=order, rank=jnp.zeros_like(order).at[order].set(
            jnp.arange(order.shape[0], dtype=jnp.int32), unique_indices=True),
        counts=counts, start=jnp.cumsum(counts) - counts,
        unrouted=jnp.sum(1 - jnp.any(held, axis=-1).astype(jnp.int32)))


class ExpertPairs(NamedTuple):
    """A low-rank pair beside each of a held expert's matrices: ``a [H, in,
    r]``, ``b [H, r, out]``. A gated expert has three (gate, up, down), a
    plain one two: its ``gate_a`` and ``gate_b`` are ``None``."""

    gate_a: Any
    gate_b: Any
    up_a: Any
    up_b: Any
    down_a: Any
    down_b: Any


#: a held expert's form, which the layer that calls says: ``W_down (silu(
#: W_gate x) * W_up x)`` with gate and up side by side in one ``[H, d, 2f]``
#: matrix (three matrices, three pairs), or ``W_down relu(W_up x)^2`` with
#: ``W_up`` as ``[H, f, d]``, a hidden unit a row as the source's linear maps
#: store it (two and two). Why that way round: the device lays a matrix whose
#: last axis is off the lane grid (a width of 1,856) the other way round, and
#: a kernel that wants it row-major then COPIES it at every call (TPU
#: compiler, PERF.md section 6, PR 39); ``[H, f, d]`` ends on the stream's
#: width, which is on the grid in every configuration so far
FORMS = ("swiglu", "relu2")


def _mm(spec, a, b):
    return jnp.einsum(spec, a, b.astype(a.dtype),
                      preferred_element_type=jnp.float32)


@jax.custom_vjp
def _sum_of(rows, token, at):
    """Combine: token ``n`` gets the float32 sum of its assignments' rows,
    ``rows[at[n, j]]`` (zero where ``at`` is past the end): at most ``top_k``
    terms a token, as a gather. Each row is one assignment's, so the
    transpose is a gather too: row ``r`` gets ``g[token[r]]``."""
    return jnp.sum(jnp.take(rows, at, axis=0, mode="fill",
                            fill_value=0).astype(jnp.float32), axis=1)


def _sum_fwd(rows, token, at):
    # an empty array hands the rows' dtype to the backward pass
    return _sum_of(rows, token, at), (token, jnp.zeros((0,), rows.dtype))


def _sum_bwd(saved, g):
    token, like = saved
    return (jnp.take(g, token, axis=0, mode="fill",
                     fill_value=0).astype(like.dtype), None, None)


_sum_of.defvjp(_sum_fwd, _sum_bwd)


def _chunk_pass(p, x, weight, held: HeldAssignments, w_in, w_down,
                pairs: ExpertPairs, scale: float, rows: int, form: str):
    """Rows ``p * rows .. (p + 1) * rows - 1`` of the assignments sorted by
    held expert: ``[N, d]`` float32. The frozen matrices are operands of
    grouped products as they lie (``w_in [H, d, 2f]`` gate and up, or ``[H, f,
    d]`` up alone, by ``form``; ``w_down [H, f, d]``;
    ``ops/grouped_matmul.py``): no gather reads them, so nothing copies them
    and a ``vmap`` over clients (which batches ``x``, the routing and the
    pairs) leaves them one operand. Rows past the held assignments' total
    are zero and are not combined. The combine is a gather by the inverse
    permutation and so is its transpose (``_sum_of``): a row belongs to one
    assignment, and in the round a scatter-add of the chunk's weighted rows
    costs twice that gather of ``N * k`` (measured on a v5e, PERF.md section
    6, PR 35); the rows' own gather keeps JAX's transpose, a scatter-add in
    the step's dtype, which is the cheaper there."""
    n, top_k = weight.shape
    f = w_down.shape[1]
    ends = held.start + held.counts
    lo = p * rows
    at = lo + jnp.arange(rows, dtype=jnp.int32)
    valid = at < ends[-1]
    source = held.order[jnp.minimum(at, n * top_k - 1)]
    token = jnp.where(valid, source // top_k, n)
    slot_weight = jnp.where(valid, weight.reshape(-1)[source], 0.0)
    # the chunk's row of each assignment, ``rows`` (past the end) if it has
    # none: not held, or in another chunk
    row = held.rank - lo
    row = jnp.where((held.rank < ends[-1]) & (row >= 0) & (row < rows), row,
                    rows).reshape(n, top_k)
    # each expert's [start, start + counts) clipped to the chunk
    sizes = jnp.clip(ends, lo, lo + rows) - jnp.clip(held.start, lo,
                                                     lo + rows)
    member = (group_of_rows(sizes, rows)[:, None]
              == jnp.arange(sizes.shape[0])[None, :])           # [rows, H]
    slab = jnp.take(x, token, axis=0, mode="fill", fill_value=0)  # [rows, d]

    def low(t, a, b):
        """The pairs of all held experts as two dense products under a
        block mask (``H r`` columns): a row keeps its own expert's block."""
        t = _mm("ci,hir->chr", t, a)
        t = jnp.where(member[:, :, None], t, 0.0).astype(slab.dtype)
        return scale * _mm("chr,hro->co", t, b)

    if form == "swiglu":
        gate_up = grouped_matmul(slab, w_in, sizes, name="held_gmm")
        gate = gate_up[:, :f] + low(slab, pairs.gate_a, pairs.gate_b)
        up = gate_up[:, f:] + low(slab, pairs.up_a, pairs.up_b)
        hidden = (jax.nn.silu(gate) * up).astype(x.dtype)
    else:
        up = grouped_matmul(slab, w_in, sizes, transpose_rhs=True,
                            name="held_gmm") + low(slab, pairs.up_a,
                                                   pairs.up_b)
        hidden = jnp.square(jax.nn.relu(up)).astype(x.dtype)
    out = grouped_matmul(hidden, w_down, sizes, name="held_gmm") + low(
        hidden, pairs.down_a, pairs.down_b)
    # weighted in float32, handed to the sum in the step's dtype: the sum
    # itself is float32 (at most top_k terms a token)
    return _sum_of((out * slot_weight[:, None]).astype(x.dtype), token, row)


def _chunks(counts, rows: int):
    """Chunks the held assignments fill; the first is always computed."""
    return jnp.maximum(-(-jnp.sum(counts) // rows), 1)


@partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def _further_chunks(y, x, weight, held, w_in, w_down, pairs, scale, rows,
                    form):
    """``y`` plus the chunks after the first: a ``while`` whose trip count
    is what the held assignments' total needs, none at a total under
    ``rows``."""
    def body(carry):
        p, acc = carry
        return p + 1, acc + _chunk_pass(p, x, weight, held, w_in, w_down,
                                        pairs, scale, rows, form)

    return jax.lax.while_loop(
        lambda carry: carry[0] < _chunks(held.counts, rows), body,
        (jnp.int32(1), y))[1]


def _further_fwd(y, x, weight, held, w_in, w_down, pairs, scale, rows, form):
    return (_further_chunks(y, x, weight, held, w_in, w_down, pairs, scale,
                            rows, form),
            (x, weight, held, w_in, w_down, pairs))


def _further_bwd(scale, rows, form, saved, dy):
    """A chunk's forward is computed again here and differentiated by
    ``x``, the weights and the pairs; the frozen matrices get no gradient
    (``None``: none is formed)."""
    x, weight, held, w_in, w_down, pairs = saved

    def body(carry):
        p, grads = carry
        _, vjp = jax.vjp(
            lambda x, weight, pairs: _chunk_pass(
                p, x, weight, held, w_in, w_down, pairs, scale, rows, form),
            x, weight, pairs)
        return p + 1, jax.tree.map(jnp.add, grads, vjp(dy))

    zeros = jax.tree.map(jnp.zeros_like, (x, weight, pairs))
    dx, dweight, dpairs = jax.lax.while_loop(
        lambda carry: carry[0] < _chunks(held.counts, rows), body,
        (jnp.int32(1), zeros))[1]
    return dy, dx, dweight, None, None, None, dpairs


_further_chunks.defvjp(_further_fwd, _further_bwd)


def held_lora_products(x, weight, held: HeldAssignments, w_in, w_down,
                       pairs: ExpertPairs, scale: float, rows: int,
                       form: str = "swiglu"):
    """The held experts' part of the layer, ``sum_{e in top-k(n), e held}
    w_ne E_e(x_n)`` as float32 ``[N, d]``, every matrix of ``E_e`` the frozen
    one plus ``scale`` times its client's pair: ``x [N, d]`` and
    ``weight [N, k]`` (float32) the client's; ``w_in`` and ``w_down [H, f,
    d]`` frozen, in ``x``'s dtype; ``form`` (:data:`FORMS`) says what an
    expert is: ``"swiglu"`` with ``w_in [H, d, 2f]`` (gate and up side by
    side) and three pairs, or ``"relu2"`` with ``w_in [H, f, d]`` and two;
    ``rows`` from :func:`chunk_rows`.

    One flat buffer of ``rows`` rows a chunk: the assignments sorted by held
    expert (``sort_held``), each row multiplied by its own expert's matrices
    in a product GROUPED over the sorted rows, which computes the tiles that
    hold real rows only and reads the matrices in place (``_chunk_pass``,
    ``ops/grouped_matmul.py``); the pairs of all held experts are dense
    products under a block mask. The first chunk is plain JAX and
    differentiated as such: ``dx`` through the frozen matrices (the grouped
    product's own backward, the same kernel on the transposed matrices) and
    the pairs' gradients; the matrices are no argument of the round's
    gradient, so no product forms theirs. What the held assignments' total
    needs beyond the first chunk takes further chunks (``_further_chunks``,
    a ``while`` with its own backward): no ``lax.cond``, no dense arm, no
    capacity past which an assignment is dropped. Returns ``(y, computed,
    further)``: ``computed`` the assignments the chunks covered,
    ``min(total, rows of all chunks)``, which is every one of them;
    ``further`` the chunks taken after the first."""
    if form not in FORMS:
        raise ValueError(f"form={form!r}: one of {FORMS}")
    y = _chunk_pass(0, x, weight, held, w_in, w_down, pairs, scale, rows,
                    form)
    y = _further_chunks(y, x, weight, held, w_in, w_down, pairs, scale, rows,
                        form)
    chunks = _chunks(held.counts, rows)
    return (y, jnp.minimum(jnp.sum(held.counts), chunks * rows), chunks - 1)
