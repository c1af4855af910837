"""Client-parallel FedAvg rounds.

``make_vmap_round``: all sampled clients train on one chip (vmap over the
client axis) — the single-device standalone simulator.

``make_sharded_round``: clients sharded over a mesh axis with ``shard_map``;
the server weighted average becomes per-shard partial weighted sums reduced
with ``lax.psum`` over ICI. This *is* the aggregation the reference performs
by MPI-sending pickled state_dicts to rank 0 and looping over keys
(FedAVGAggregator.py:59-88) — here it is one XLA collective.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from fedml_tpu.core.tree import tree_weighted_mean
from fedml_tpu.data.batching import gather_clients

#: Mesh-axis naming convention for the pod-scale compute plane
#: (parallel/multihost.py builds these meshes): a mesh whose FIRST axis
#: is named ``"hosts"`` carries a DCN×ICI factorization — that axis is
#: the slow inter-host (DCN) dimension and the client axis that follows
#: is intra-host ICI. The client dimension of every round operand is
#: then sharded over BOTH axes (hosts-major, so global client-slot order
#: is host order), and the reductions below keep their collectives on
#: the ICI axis wherever the math allows, crossing DCN only with
#: host-level partials (arXiv:1903.05133's sparse global reduction).
DCN_AXIS = "hosts"


def mesh_dcn_axis(mesh):
    """The mesh's DCN (inter-host) axis name, or ``None`` for a flat
    single-host mesh."""
    if mesh is not None and DCN_AXIS in mesh.axis_names:
        return DCN_AXIS
    return None


def client_axis(mesh):
    """The ICI client axis — the axis round builders vmap/shard clients
    over. On a flat mesh this is ``axis_names[0]`` (the historical
    contract); on a DCN×ICI mesh it is the first non-DCN axis."""
    for a in mesh.axis_names:
        if a != DCN_AXIS:
            return a
    raise ValueError(f"mesh {mesh.axis_names} has no client axis")


def client_axes(mesh, axis=None):
    """The mesh axes the CLIENT dimension is sharded over, DCN-major —
    ``("hosts", axis)`` on a hierarchical mesh, ``(axis,)`` otherwise.
    ``P(client_axes(mesh))`` is the partition spec of every
    client-stacked round operand."""
    if axis is None:
        axis = client_axis(mesh)
    d = mesh_dcn_axis(mesh)
    return (d, axis) if d else (axis,)


def client_shards(mesh, axis=None) -> int:
    """Total client shards = the product over the client axes (what the
    sampled cohort is padded to a multiple of)."""
    if mesh is None:
        return 1
    return int(np.prod([mesh.shape[a] for a in client_axes(mesh, axis)]))


def _psum_hier(v, axes):
    """``psum`` over the client axes, ICI first: on a flat mesh this is
    exactly the historical single-axis ``psum`` (bit-compatible with
    every existing pin); on a DCN×ICI mesh the ICI reduction completes
    HOST-LOCALLY and only the per-host partial crosses the DCN axis —
    the mean path's hierarchical reduction IS this association (one
    O(model) host partial per host on DCN instead of a flat all-reduce
    over every shard)."""
    for a in reversed(axes):
        v = jax.lax.psum(v, a)
    return v


def client_finite_mask(client_params) -> jnp.ndarray:
    """[C] float mask: 1.0 where EVERY leaf of that client's model is
    finite. Failure containment the reference lacks entirely (its only
    response to trouble is MPI Abort, fedml_api/utils/context.py:9-18): a
    client whose local training diverged to NaN/Inf must not poison the
    global average."""
    flags = [
        jnp.all(jnp.isfinite(leaf.reshape(leaf.shape[0], -1)), axis=1)
        for leaf in jax.tree.leaves(client_params)
    ]
    return jnp.all(jnp.stack(flags, axis=0), axis=0).astype(jnp.float32)


def run_clients_guarded(local_train, client_transform, nan_guard,
                        net, x, y, mask, rngs, corruptor=None, adv=None,
                        unbatched: bool = False):
    """Shared per-round client-training prelude: vmapped local training,
    optional ADVERSARIAL corruption, optional post-transform (robust
    clipping etc.), and the NaN-guard zeroing. Returns ``(client_nets,
    losses, finite)`` where ``finite [C]`` is 1.0 for clients whose
    trained model is wholly finite (all-ones when the guard is off) —
    callers fold it into their aggregation weights. Used by the vmap
    round, the sharded round, and q-FedAvg's fair round so the guard
    semantics can never drift between them.

    ``client_transform`` is ``(global_net, client_net) -> client_net``,
    or — when the builder marked it ``transform.wants_rng = True`` —
    ``(global_net, client_net, rng) -> client_net`` for randomized
    transforms (stochastic quantization): the 3-arg form receives a
    per-client stream forked from the round's client rngs (fold_in with
    a transform-reserved constant, so it never collides with the streams
    local training consumed for shuffling/dropout/DP noise). An explicit
    attribute, not signature sniffing: partials and C-implemented
    callables would defeat ``inspect`` silently.

    ``corruptor`` is the device-side attack model for robustness drills
    (``core.faults.UpdateCorruptor.device_fn()``): a pure
    ``(global_net, client_nets, adv, rngs) -> client_nets`` applied to
    the trained stack where ``adv [C] > 0`` flags the adversary slots.
    It runs BEFORE the transform and the guard — exactly the real threat
    order: the server's defenses see the already-corrupted updates. Its
    per-client streams are forked with their own reserved constant
    (0xC0), disjoint from training's and the transform's (0x7F).

    ``unbatched`` (the grouped round at one client a group): a stack of one
    needs no ``vmap``, and without it control flow inside a model
    (``lax.cond``, ``while_loop``) stays control flow instead of becoming a
    select that runs every arm."""
    with jax.named_scope("fed.local_train"):
        if unbatched:
            one_net, one_loss = local_train(net, x[0], y[0], mask[0], rngs[0])
            client_nets = jax.tree.map(lambda p: p[None], one_net)
            losses = one_loss[None]
        else:
            client_nets, losses = jax.vmap(
                local_train, in_axes=(None, 0, 0, 0, 0)
            )(net, x, y, mask, rngs)
    if corruptor is not None:
        crngs = jax.vmap(lambda r: jax.random.fold_in(r, 0xC0))(rngs)
        client_nets = corruptor(net, client_nets, adv, crngs)
    if client_transform is not None:
        if getattr(client_transform, "wants_rng", False):
            trngs = jax.vmap(
                lambda r: jax.random.fold_in(r, 0x7F))(rngs)
            client_nets = jax.vmap(client_transform, in_axes=(None, 0, 0))(
                net, client_nets, trngs)
        else:
            client_nets = jax.vmap(client_transform, in_axes=(None, 0))(
                net, client_nets)
    if not nan_guard:
        return client_nets, losses, jnp.ones_like(losses)
    finite = client_finite_mask(client_nets)
    # Zero via where — NaN * 0 is still NaN.
    client_nets = jax.tree.map(
        lambda p: jnp.where(
            finite.reshape((-1,) + (1,) * (p.ndim - 1)).astype(bool),
            p, jnp.zeros((), p.dtype)),
        client_nets)
    losses = jnp.where(jnp.isfinite(losses), losses, 0.0)
    return client_nets, losses, finite


def _is_mean(aggregator) -> bool:
    return aggregator is None or getattr(aggregator, "is_mean", False)


def _robust_avg(aggregator, client_params, weights, params):
    """Aggregate with a non-mean Aggregator (core/robust_agg protocol)
    and keep the PREVIOUS global model when no client carries weight:
    order statistics over an empty participant set are meaningless — the
    aggregators' ±inf exclusion sentinels would leak into the model (the
    mean path's equivalent guard is the nan_guard ``any_ok`` select)."""
    avg = aggregator(client_params, weights)
    any_ok = jnp.sum(jnp.where(weights > 0, 1.0, 0.0)) > 0
    return jax.tree.map(lambda a, p: jnp.where(any_ok, a, p), avg, params)


def whole_stack_needed(group: int, **users) -> None:
    """Refuse ``group`` clients at a time (``cfg.client_group_size``) for
    what reads the whole trained stack ``[C, ...]``: the grouped round
    keeps only a running weighted sum of it."""
    named = [name for name, user in users.items() if user is not None]
    if named:
        raise NotImplementedError(
            f"client_group_size={group} trains the cohort {group} clients "
            "at a time and folds each group into a running weighted sum; "
            f"{', '.join(named)} need(s) every client's trained model at "
            "once — use client_group_size=0 (the whole cohort)")


def fold_init(params):
    """The empty running sums of :func:`fold_group`: ``(sum w_i theta_i
    (float32, shaped like ``params``), sum w_i, sum lw_i loss_i, sum
    lw_i)``."""
    zero = jnp.zeros((), jnp.float32)
    return (jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params),
            zero, zero, zero)


def fold_group(local_train, nan_guard, params, carry, xg, yg, mg, wg, lwg,
               rg, unbatched: bool = False):
    """One group of clients ``[g, ...]`` trained from ``params`` under
    :func:`run_clients_guarded` (``nan_guard`` a group at a time) and folded
    into the running sums ``carry`` (:func:`fold_init`). The ONE fold: the
    scan body of :func:`fold_client_groups` (groups inside one program) and
    the step of :func:`make_size_group_round` (one dispatch a group).
    Returns ``(carry, losses [g])``."""
    acc, sum_w, sum_loss, sum_lw = carry
    nets, losses, finite = run_clients_guarded(
        local_train, None, nan_guard, params, xg, yg, mg, rg,
        unbatched=unbatched)
    with jax.named_scope("fed.aggregate"), \
            jax.named_scope("fed.client_fold"):
        wg = wg.astype(jnp.float32) * finite
        lwg = lwg.astype(jnp.float32) * finite
        acc = jax.tree.map(
            lambda a, p: a + jnp.einsum(
                "c,c...->...", wg, p.astype(jnp.float32)), acc, nets)
        carry = (acc, sum_w + jnp.sum(wg),
                 sum_loss + jnp.sum(losses * lwg), sum_lw + jnp.sum(lwg))
    return carry, losses


def fold_client_groups(local_train, nan_guard, group, params, x, y, mask,
                       weights, loss_weights, rngs):
    """The cohort ``[C, ...]`` trained ``group`` clients at a time: a
    ``lax.scan`` over ``C / group`` groups, ``vmap`` inside a group, each
    group's trained models folded into a running ``sum w_i theta_i`` — one
    model-sized accumulator instead of the stack ``[C, ...]``, for models a
    cohort of whose copies does not fit. ``nan_guard`` works a group at a
    time. Returns ``(sum w_i theta_i (float32), sum w_i, sum lw_i loss_i,
    sum lw_i, losses [C])``."""
    n = x.shape[0]
    # fedlint: disable=R4(group is cfg.client_group_size, a Python int fixed when the round is built, and n a static shape)
    if n % group:
        raise ValueError(
            f"client_group_size={group} does not divide the {n} clients a "
            "round trains here (the cohort, padded to the mesh's shards, "
            "over the shards)")

    def grouped(a):
        return a.reshape((n // group, group) + a.shape[1:])

    def body(carry, operands):
        return fold_group(local_train, nan_guard, params, carry, *operands,
                          unbatched=group == 1)

    # The loop's container, its empty carry and what XLA copies around it
    # get a name of their own; the body's operations keep theirs, which are
    # the LAST phase and the innermost scope of their path.
    with jax.named_scope("fed.client_groups"):
        (acc, sum_w, sum_loss, sum_lw), losses = jax.lax.scan(
            body, fold_init(params), tuple(grouped(a) for a in (
                x, y, mask, weights, loss_weights, rngs)))
        return acc, sum_w, sum_loss, sum_lw, losses.reshape(n)


def _grouped_mean(params, acc, sum_w, sum_loss, sum_lw):
    """``(avg, mean_loss)`` from :func:`fold_client_groups`' sums (after
    the ``psum`` on a mesh). No client carried weight (every one diverged
    under ``nan_guard``, or an all-pad round): the previous global model."""
    avg = jax.tree.map(
        lambda a, p: jnp.where(
            sum_w > 0, a / jnp.maximum(sum_w, 1e-12), p).astype(p.dtype),
        acc, params)
    return avg, sum_loss / jnp.maximum(sum_lw, 1e-12)


def make_vmap_round(local_train, client_transform=None, nan_guard: bool = False,
                    with_client_losses: bool = False, aggregator=None,
                    corruptor=None, group: int = 0):
    """``round_fn(params, x, y, mask, weights, loss_weights, rng) ->
    (avg_params, mean_loss)`` with client-stacked inputs ``[C, S, B, ...]``.

    ``weights [C]`` weight the model average; ``loss_weights [C]`` weight the
    reported train loss (true sample counts — algorithms like FedNova
    aggregate with n_i/τ_i weights but still report sample-weighted loss).
    Padded client slots carry weight 0 in both.

    ``client_transform(global_net, client_net) -> client_net`` is applied to
    every trained client model before averaging (robust clipping etc.).

    ``nan_guard`` zero-weights any client whose trained model contains a
    non-finite value (and its loss), so one diverged client cannot poison
    the round.

    ``with_client_losses`` appends the per-client training losses ``[C]``
    as a THIRD output — the in-round observable Oort's utility needs
    (Lai et al. §5), captured for free instead of a post-round eval pass.

    ``aggregator`` swaps the server reduction for a Byzantine-robust one
    (``core.robust_agg`` protocol — coord_median, trimmed_mean, krum,
    geometric_median). ``None`` or an ``is_mean`` aggregator keeps the
    existing weighted-mean path UNCHANGED (bit-equal). Under ``nan_guard``
    a diverged client's zeroed weight EXCLUDES it from the robust
    aggregator's order statistics (core/robust_agg weight semantics).

    ``corruptor`` enables the device-side attack drill: the round grows a
    trailing ``adv [C]`` operand (adversary mask) and the corruptor runs
    on the trained stack before the transform/guard — see
    :func:`run_clients_guarded`. The mask-driven form means the drill
    rides every tier, including the windowed ``lax.scan`` body.

    ``group`` (``cfg.client_group_size``) trains the cohort that many
    clients at a time (:func:`fold_client_groups`); 0, or the cohort's
    size, is the one ``vmap`` over the whole cohort. Mean aggregation
    only: what needs the stack refuses at the first trace, by name
    (:func:`whole_stack_needed`; ``FedAvgAPI`` refuses at construction)."""
    if _is_mean(aggregator):
        aggregator = None

    def round_core(params, x, y, mask, weights, loss_weights, rng, adv):
        rngs = client_rngs(rng, x.shape[0], 0)
        if group and group != x.shape[0]:
            whole_stack_needed(
                group, aggregator=aggregator,
                client_transform=client_transform, corruptor=corruptor)
            *sums, losses = fold_client_groups(
                local_train, nan_guard, group, params, x, y, mask, weights,
                loss_weights, rngs)
            with jax.named_scope("fed.aggregate"):
                avg, mean_loss = _grouped_mean(params, *sums)
            if with_client_losses:
                return avg, mean_loss, losses
            return avg, mean_loss
        client_params, losses, finite = run_clients_guarded(
            local_train, client_transform, nan_guard,
            params, x, y, mask, rngs, corruptor=corruptor, adv=adv)
        with jax.named_scope("fed.aggregate"):
            weights = weights * finite
            loss_weights = loss_weights * finite
            if aggregator is None:
                avg = tree_weighted_mean(client_params, weights)
                if nan_guard:
                    # Every sampled client diverged → keep the previous
                    # global model (a zero-total weighted mean would
                    # silently zero the params).
                    any_ok = jnp.sum(weights) > 0
                    avg = jax.tree.map(
                        lambda a, p: jnp.where(any_ok, a, p), avg, params)
            else:
                avg = _robust_avg(aggregator, client_params, weights, params)
            lw = loss_weights / jnp.maximum(jnp.sum(loss_weights), 1e-12)
            mean_loss = jnp.sum(losses * lw)
        if with_client_losses:
            return avg, mean_loss, losses
        return avg, mean_loss

    if corruptor is None:
        def round_fn(params, x, y, mask, weights, loss_weights, rng):
            return round_core(params, x, y, mask, weights, loss_weights,
                              rng, None)
        return round_fn
    return round_core


def client_rngs(rng, n_local, offset):
    """Per-client rng streams keyed by GLOBAL client slot, so the vmap and
    shard_map paths produce bitwise-identical randomness (shuffle order,
    dropout) for the same sampled round."""
    return jax.vmap(lambda i: jax.random.fold_in(rng, i))(offset + jnp.arange(n_local))


def make_sharded_round(local_train, mesh, axis: str = "clients",
                       client_transform=None, nan_guard: bool = False,
                       with_client_losses: bool = False, aggregator=None,
                       corruptor=None, group_reduce: bool = False,
                       group: int = 0):
    """Sharded round: client axis split over ``mesh[axis]``; output replicated.

    Weighted average = psum of per-shard weighted partial sums / psum of
    weights — exact regardless of how clients land on shards.
    ``nan_guard`` and ``with_client_losses`` as in :func:`make_vmap_round`
    (the per-client losses come back client-sharded over ``axis``).

    ``aggregator`` (core/robust_agg protocol): a non-mean aggregator needs
    the FULL client-stacked update, which the partial-sum reduction never
    materializes — the round ``all_gather``s the trained stack (and the
    weights) along the client axis and runs the aggregator replicated on
    every shard. ``tiled`` gathers concatenate in axis order, which is
    exactly the global-slot order the vmap path stacks, so the aggregator
    sees bit-identical inputs on one chip and on a mesh. ``None`` / mean
    keeps the partial-sum ``psum`` fast path untouched (bit-equal).

    ``group_reduce`` — the HIERARCHICAL SPARSE REDUCTION (group-level
    partial aggregation + sparse global step, the arXiv:1903.05133
    shape) for ``group_composable`` aggregators. On a flat mesh each
    shard is a group: stage 1 runs the aggregator SHARD-LOCALLY over the
    shard's own clients (no communication); stage 2 ``all_gather``s only
    the G group partials + participation weights and applies the same
    aggregator across groups (a group whose clients were all excluded
    carries weight 0 and drops out — the "sparse" in sparse global
    reduction; the collective shrinks from C client models to G ≪ C
    group partials). On a DCN×ICI mesh (``multihost.py``; the mesh
    carries a ``"hosts"`` axis) client groups are PINNED PER HOST:
    stage 1 gathers the host's own client stack over the ICI axis only —
    zero DCN traffic — and applies the aggregator per host; stage 2
    crosses the DCN axis with exactly G = n_hosts group partials +
    participation mass, O(G·model) inter-host bytes instead of the flat
    path's O(C·model) client-stack ``all_gather``. Mean is already this
    reduction EXACTLY (per-shard partial sums + the hierarchical
    ``psum`` — ICI first, one host partial across DCN — which the mean
    path runs with or without the flag) and keeps its bit-equal fast
    path; the coordinate-wise statistics compose as median-of-medians /
    trim-of-trims — the hierarchical robust construction, semantically
    distinct from the flat statistic by design (and on a DCN mesh the
    group is the HOST, not the shard). Non-composable aggregators
    (krum, geometric_median) refuse ``group_reduce`` LOUDLY here: their
    exact semantics need the full client-stacked ``all_gather`` fallback
    (``group_reduce=False``).

    ``corruptor`` as in :func:`make_vmap_round`: the round grows a
    trailing client-sharded ``adv`` operand.

    ``group`` as in :func:`make_vmap_round`, of each shard's own clients:
    every shard folds its groups into its partial sums, which meet in the
    same ``psum`` as the whole-cohort mean's."""
    if _is_mean(aggregator):
        aggregator = None
    if group_reduce and aggregator is not None \
            and not getattr(aggregator, "group_composable", False):
        raise ValueError(
            f"aggregator {getattr(aggregator, 'name', aggregator)!r} does "
            "not compose group-wise (krum needs pairwise client "
            "distances, geometric_median a joint Weiszfeld fixpoint); "
            "use group_reduce=False to keep the exact full client-stack "
            "all_gather path, or a composable aggregator "
            "(mean/coord_median/trimmed_mean) for the hierarchical "
            "sparse reduction")

    axes = client_axes(mesh, axis)
    dcn = axes[0] if len(axes) > 1 else None
    gather_ax = axes if dcn else axis  # collective name(s) spanning C

    @jax.named_scope("fed.aggregate")
    def aggregate(params, client_params, losses, weights, loss_weights):
        w = weights.astype(jnp.float32)
        if aggregator is None:
            total = _psum_hier(jnp.sum(w), axes)
            wn = w / jnp.maximum(total, 1e-12)
            avg = jax.tree.map(
                lambda p: _psum_hier(
                    jnp.einsum("c,c...->...", wn, p.astype(jnp.float32)),
                    axes).astype(p.dtype),
                client_params,
            )
            if nan_guard:
                # All-diverged round: keep the previous global model.
                avg = jax.tree.map(
                    lambda a, p: jnp.where(total > 0, a, p), avg, params)
        elif group_reduce:
            # Hierarchical sparse reduction. Stage 1's group is the
            # SHARD on a flat mesh (shard-local, zero communication) and
            # the HOST on a DCN×ICI mesh (the host's client stack
            # gathered over the ICI axis only — zero DCN traffic).
            # Stage 2 crosses the remaining axes with exactly G group
            # partials + participation mass. An all-excluded group's
            # partial may carry the aggregator's ±inf exclusion
            # sentinels — its zero participation weight gates it out of
            # stage 2, exactly the client-level weight semantics lifted
            # one level up.
            if dcn:
                g_params = jax.tree.map(
                    lambda p: jax.lax.all_gather(p, axis, axis=0,
                                                 tiled=True),
                    client_params)
                g_w = jax.lax.all_gather(w, axis, axis=0, tiled=True)
                part = aggregator(g_params, g_w)
                pw = jnp.sum(jnp.maximum(g_w, 0.0))
                stage2 = dcn
            else:
                part = aggregator(client_params, w)
                pw = jnp.sum(jnp.maximum(w, 0.0))
                stage2 = axis
            parts = jax.tree.map(
                lambda p: jax.lax.all_gather(p, stage2), part)  # [G, ...]
            pws = jax.lax.all_gather(pw, stage2)  # [G]
            avg = _robust_avg(aggregator, parts, pws, params)
        else:
            full = jax.tree.map(
                lambda p: jax.lax.all_gather(p, gather_ax, axis=0,
                                             tiled=True),
                client_params)
            w_full = jax.lax.all_gather(w, gather_ax, axis=0, tiled=True)
            avg = _robust_avg(aggregator, full, w_full, params)
        lw = loss_weights.astype(jnp.float32)
        lw = lw / jnp.maximum(_psum_hier(jnp.sum(lw), axes), 1e-12)
        loss = _psum_hier(jnp.sum(losses * lw), axes)
        return avg, loss

    def body(params, x, y, mask, weights, loss_weights, rng, adv):
        # Same global-slot-keyed streams as the vmap path. On a DCN×ICI
        # mesh the flattened (hosts-major) axis index IS the global
        # shard slot — exactly the order P(("hosts", axis)) lays the
        # client dimension out in.
        shard_idx = jax.lax.axis_index(gather_ax)
        rngs = client_rngs(rng, x.shape[0], shard_idx * x.shape[0])
        if group and group != x.shape[0]:
            whole_stack_needed(
                group, aggregator=aggregator,
                client_transform=client_transform, corruptor=corruptor)
            *sums, losses = fold_client_groups(
                local_train, nan_guard, group, params, x, y, mask, weights,
                loss_weights, rngs)
            with jax.named_scope("fed.aggregate"):
                acc, *scalars = sums
                acc = jax.tree.map(lambda a: _psum_hier(a, axes), acc)
                avg, loss = _grouped_mean(
                    params, acc, *(_psum_hier(v, axes) for v in scalars))
            if with_client_losses:
                return avg, loss, losses
            return avg, loss
        client_params, losses, finite = run_clients_guarded(
            local_train, client_transform, nan_guard,
            params, x, y, mask, rngs, corruptor=corruptor, adv=adv)
        avg, loss = aggregate(params, client_params, losses,
                              weights * finite, loss_weights * finite)
        if with_client_losses:
            return avg, loss, losses
        return avg, loss

    cs = P(axes)  # client-stacked operands: DCN-major on a hybrid mesh
    specs = (P(), cs, cs, cs, cs, cs, P())
    out_specs = ((P(), P(), cs) if with_client_losses
                 else (P(), P()))
    if corruptor is None:
        @partial(shard_map, mesh=mesh, in_specs=specs,
                 out_specs=out_specs, check_vma=False)
        def round_fn(params, x, y, mask, weights, loss_weights, rng):
            return body(params, x, y, mask, weights, loss_weights, rng, None)
    else:
        @partial(shard_map, mesh=mesh, in_specs=specs + (cs,),
                 out_specs=out_specs, check_vma=False)
        def round_fn(params, x, y, mask, weights, loss_weights, rng, adv):
            return body(params, x, y, mask, weights, loss_weights, rng, adv)

    return round_fn


def make_cohort_gather(mesh):
    """``gather(fed, idx) -> FederatedArrays``: the sampled clients of a
    resident federation, taken INSIDE the round's program under the
    device scope ``fed.gather``. On one chip it is ``gather_clients``;
    on a mesh ``fed`` is replicated, ``idx`` client-sharded, and each
    shard takes its own rows from its local copy — slot ``i`` lands on
    shard ``i // n_local``, the placement the round's operands have, with
    no collective: no chip waits for another's data."""

    def gather(fed, idx):
        with jax.named_scope("fed.gather"):
            return gather_clients(fed, idx)

    if mesh is None:
        return gather
    cs = P(client_axes(mesh))
    return shard_map(gather, mesh=mesh, in_specs=(P(), cs), out_specs=cs,
                     check_vma=False)


def make_fused_round_step(round_fn, server_update=None):
    """ONE dispatch per host-loop round: client training + weighted
    aggregation (``round_fn``) + the algorithm's PURE server update,
    fused — ``make_window_scan``'s shape at W=1, without the scan.

    The host loop used to dispatch the round and the server update as
    separate jit calls with undonated intermediates: the old global
    model, the round average, and the new global model were all live at
    once (3 model-sized HBM copies on the round's critical path), and
    the server update paid its own dispatch. Callers jit this with
    ``donate_argnums=(0, 1)`` — the incoming ``(net, extra)`` carry is
    always replaced by the step's outputs, exactly the windowed scan's
    donation discipline, so XLA reuses the old buffers in place
    (``obs.sanitizer.donation_audit`` pins the single-copy steady
    state).

    Signature matches the scan body: ``step(net, extra, x, y, mask,
    weights, key, *aux) -> ((net', extra'), loss)`` with ``weights``
    used for both the model average and the loss weighting (the
    streaming host loop's convention) and ``key`` the round's rng key
    (randomized server updates fold_in from it — same protocol slot as
    the windowed carry)."""

    def step_fn(net, extra, x, y, mask, weights, key, *aux):
        avg, loss = round_fn(net, x, y, mask, weights, weights, key, *aux)
        if server_update is None:
            return (avg, extra), loss
        with jax.named_scope("fed.aggregate"):
            new_net, new_extra = server_update(net, avg, extra, key)
        return (new_net, new_extra), loss

    return step_fn


def make_size_group_round(local_train, nan_guard: bool = False,
                          server_update=None):
    """The streamed round, one dispatch a SIZE GROUP: the host cuts the
    sampled cohort into groups of clients with a like step need
    (``FederatedStore.plan_groups``), each padded to its own step bucket
    instead of the cohort's largest, and the round is

    ``carry = init(net)``; for each group ``carry = group_step(net, carry,
    x, y, mask, counts, slots, wmask, key)``; ``((net', extra'), loss) =
    finish(net, extra, carry, key)``.

    ``group_step`` is :func:`fold_group` (the grouped round's own fold) on
    ``[g, S_g, B, ...]`` operands: one program a step bucket, whatever the
    round draws. ``slots [g]`` are the members' slots in the sampled
    cohort: each client's rng stream is ``fold_in(key, slot)``, what
    :func:`client_rngs` gives it in the whole-cohort round, and the
    trainer's streams are prefix-stable in the step count, so a client
    trained at its group's bucket ends where it ends at the cohort's; only
    the association of the float32 sums differs. ``wmask [k]`` is the
    cohort's pad mask, ``counts [g]`` the members' sample counts: weight
    ``counts * wmask[slots]`` for the model and the loss alike (the
    streaming host loop's convention). ``finish`` is :func:`_grouped_mean`
    + the algorithm's pure ``server_update``, as
    :func:`make_fused_round_step` ends. Callers jit ``group_step`` donating
    ``carry`` and ``finish`` donating ``(net, extra)``. Mean aggregation
    without a client transform or corruptor only
    (:func:`whole_stack_needed`); one device."""

    def group_step(net, carry, x, y, mask, counts, slots, wmask, key):
        w = counts.astype(jnp.float32) * wmask[slots]
        rngs = jax.vmap(lambda i: jax.random.fold_in(key, i))(slots)
        carry, _ = fold_group(local_train, nan_guard, net, carry, x, y,
                              mask, w, w, rngs)
        return carry

    def finish(net, extra, carry, key):
        with jax.named_scope("fed.aggregate"):
            avg, loss = _grouped_mean(net, *carry)
            if server_update is None:
                return (avg, extra), loss
            new_net, new_extra = server_update(net, avg, extra, key)
        return (new_net, new_extra), loss

    def init(net):
        with jax.named_scope("fed.client_groups"):
            return fold_init(net)

    return init, group_step, finish


def make_fused_stateful_round_step(round_fn):
    """Fused ONE-dispatch round for ``make_stateful_client_round``-shaped
    rounds (SCAFFOLD's controls, FedDyn's corrections): cohort state
    gather + the stateful round + the masked scatter-merge run in the
    SAME dispatch, with the carry ``(net, (s_global, s_clients))`` —
    ``s_clients`` the FULL client-stacked state ``[N, ...]``. Callers
    jit with ``donate_argnums=(0, 1)`` so the old model AND the old
    state stack are reused in place (the host loop used to pay three
    dispatches — eager gather, round, eager scatter — and hold the old
    plus new state stacks live simultaneously).

    Signature matches the capability protocol's step shape:
    ``step(net, extra, x, y, mask, weights, key, idx, umask) ->
    ((net', extra'), loss)`` where ``idx [k]`` is the round's padded
    cohort index map and ``umask [k]`` gates the scatter (only clients
    that actually trained write their slot — padded and empty-client
    slots are routed out of bounds and dropped)."""
    from fedml_tpu.core.tree import gather_stacked, scatter_stacked

    def step_fn(net, extra, x, y, mask, weights, key, idx, umask):
        s_global, s_clients = extra
        sub = gather_stacked(s_clients, idx)
        new_net, new_global, new_sub, loss = round_fn(
            net, s_global, sub, x, y, mask, weights, key)
        s_clients = scatter_stacked(s_clients, idx, new_sub, umask)
        return (new_net, (new_global, s_clients)), loss

    return step_fn


def make_step_window_scan(step_fn):
    """``lax.scan`` a capability-protocol fused round step over a window
    of PRE-GATHERED rounds: the ONE step definition an algorithm
    publishes (``_build_fused_step``) serves both the fused host round
    (jitted with donation at W=1) and this scan — so windowed rounds are
    bit-equal to fused host rounds BY CONSTRUCTION, not by parallel
    implementations kept in sync.

    Returns ``scan_fn(net, extra, x, y, mask, weights, keys, *aux) ->
    ((net', extra'), losses)`` with ``x/y/mask [W, ...]``, ``weights
    [W, C]``, ``keys [W, 2]`` the per-round rng keys in round order, and
    ``aux`` any per-round scanned operands with leading axis W (the
    ``_window_scan_extras`` slot: SCAFFOLD's cohort index maps, the
    corruption drill's adversary masks, FedNova's τ-normalized
    weights)."""

    def scan_fn(net, extra, x, y, mask, weights, keys, *aux):
        def body(carry, inp):
            (xw, yw, mw, ww, kw), auxw = inp[:5], inp[5:]
            return step_fn(carry[0], carry[1], xw, yw, mw, ww, kw, *auxw)

        return jax.lax.scan(body, (net, extra),
                            (x, y, mask, weights, keys) + tuple(aux))

    return scan_fn


def make_window_scan(round_fn, server_update=None):
    """``lax.scan`` over a window of PRE-GATHERED rounds: one jitted
    dispatch runs W whole federated rounds back-to-back — the windowed
    execution tier's device side (host syncs drop from O(rounds) to
    O(rounds/W); see ``FedAvgAPI.train_rounds_windowed``).

    The scan CARRY is ``(net, extra)`` — the windowed carry protocol.
    Between rounds the per-algorithm ``server_update(net, avg, extra,
    key) -> (net', extra')`` is folded over the round average: ``None``
    (the default) is plain FedAvg (``net' = avg``, ``extra`` threaded
    untouched — pass ``extra=None``); FedOpt passes its pure jitted
    optax server step with ``extra`` the server optimizer state, so the
    adaptive-server algorithms ride the same one-dispatch-per-W-rounds
    tier as plain FedAvg (the "keep state on device, talk to the host
    less" lever of Parallel Restarted SGD, arXiv:1807.06629, applied at
    the dispatch boundary). ``key`` is the ROUND's rng key — the same
    key the host loop's ``run_round`` split for that round — so a
    randomized server update (FedAvgRobust's weak-DP noise) derives its
    stream by ``fold_in`` from it and stays bit-equal to the host loop
    without carrying a split chain (the PR-2 prefix-stability
    discipline; fedlint R1 forbids carried split chains in scan bodies).

    ``round_fn`` is the SAME per-round function the host loop dispatches
    (vmap round on one chip, shard_map round on a client mesh — jitted is
    fine, jit-under-scan inlines), so windowed rounds are bit-equal to
    host-loop rounds fed the same cohorts, weights, and rng keys.

    Returns ``scan_fn(net, extra, x, y, mask, weights, keys, *aux) ->
    ((net', extra'), losses)`` with ``x/y/mask [W, C, S, B, ...]``,
    ``weights [W, C]`` (sample counts x pad mask — used for BOTH the
    model average and the loss weighting, as the streaming host loop
    does), ``keys [W, 2]`` the per-round rng keys in round order, and
    ``aux`` any extra per-round scanned inputs (leading axis W) the
    round takes as trailing operands — the "round"-protocol slot
    ``FedAvgAPI._window_scan_extras`` fills (the corruption drill's
    ``[W, C]`` adversary mask).

    Since the capability-record refactor this is literally
    ``make_step_window_scan(make_fused_round_step(...))`` — the scanned
    body and the fused host round are the SAME function."""
    return make_step_window_scan(make_fused_round_step(round_fn,
                                                       server_update))


def make_stateful_window_scan(round_fn):
    """Windowed scan for ``make_stateful_client_round``-shaped rounds
    (SCAFFOLD's control variates): the carry protocol's "custom" form,
    where the round itself consumes and produces the carried state
    instead of a post-round ``server_update``.

    The carry is ``(net, (s_global, s_clients))`` with ``s_clients`` the
    FULL client-stacked state ``[N, ...]``. Each scanned round gathers
    its cohort's slots, runs the stateful round, and scatter-merges the
    updated slots back — INSIDE the scan body, because a client sampled
    by two rounds of the same window must see round t's state update in
    round t' > t (a per-window pre-gather/post-scatter would replay
    stale slots for repeat clients and break host-loop bit-equality).

    Returns ``scan_fn(net, extra, x, y, mask, weights, keys, idx, umask)
    -> ((net', extra'), losses)`` where ``idx [W, k]`` is the window's
    padded cohort index map (the same map ``gather_window`` consumed)
    and ``umask [W, k]`` gates the scatter — only clients that actually
    trained write their slot back (padded and empty-client slots are
    routed out of bounds and dropped, exactly as the host loop's
    ``scatter_stacked``).

    Since the capability-record refactor this is literally
    ``make_step_window_scan(make_fused_stateful_round_step(...))`` — the
    scanned body and the fused host round are the SAME function."""
    return make_step_window_scan(make_fused_stateful_round_step(round_fn))


def window_put(mesh, axis: str = "clients"):
    """``put`` callable for ``FederatedStore.gather_window`` on a client
    mesh: lays each ``[W, C, ...]`` superbatch field out with the client
    axis (dim 1) sharded over ``mesh[axis]`` — over ``("hosts", axis)``
    on a DCN×ICI mesh, so each host's H2D gather lands HOST-LOCAL and
    the ``WindowPrefetcher`` overlaps the next window's host-local
    gather + transfer against the current window's compute — and the
    window axis replicated, so every scanned round slice arrives already
    client-sharded for the shard_map round.

    The ``np.array`` copy is load-bearing: ``device_put`` of a large
    aligned numpy array ZERO-COPY aliases its memory on the CPU backend
    (reproduced: mutate after put → the device array changes; today's
    sharded put happens to copy, but that is backend behavior, not a
    contract), and gather_window hands this callable a VIEW of its
    reused staging buffers — an aliased put would let the next window's
    refill silently corrupt this window's in-flight superbatch. Aliasing
    the fresh copy instead is fine: nobody ever mutates it, and jax
    keeps it alive for the device array's lifetime."""
    from jax.sharding import NamedSharding

    sharding = NamedSharding(mesh, P(None, client_axes(mesh, axis)))

    def put(a):
        return jax.device_put(np.array(a), sharding)

    # Contract with FederatedStore.gather_window (fedlint R2): this put
    # copies before putting, so the store must not insert a second
    # defensive copy of its staging buffers.
    put.copies = True
    return put


def make_stateful_client_round(body, mesh, axis: str = "clients"):
    """Round wrapper for algorithms carrying server + client-stacked
    state through the round (SCAFFOLD's controls, FedDyn's corrections).

    ``body(net, s_global, s_clients, x, y, mask, weights, rngs, cross)
    -> (net', s_global', s_clients', loss)`` is written ONCE by the
    algorithm; this wrapper supplies the per-client rng streams and the
    cross-shard reduction — identity on a single device, psum under
    shard_map (the hierarchical ICI-then-DCN association on a DCN×ICI
    mesh, like the mean round's reduction) — so the vmap and sharded
    paths cannot drift (the same shared-body discipline as
    make_vmap_round/make_sharded_round)."""
    if mesh is None:
        def round_fn(net, s_global, s_clients, x, y, mask, weights, rng):
            rngs = client_rngs(rng, x.shape[0], 0)
            return body(net, s_global, s_clients, x, y, mask, weights,
                        rngs, cross=lambda v: v)
        return round_fn

    axes = client_axes(mesh, axis)
    cs = P(axes)
    idx_ax = axes if len(axes) > 1 else axis

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(), P(), cs, cs, cs, cs, cs, P()),
        out_specs=(P(), P(), cs, P()),
        check_vma=False,
    )
    def round_fn(net, s_global, s_clients, x, y, mask, weights, rng):
        shard_idx = jax.lax.axis_index(idx_ax)
        rngs = client_rngs(rng, x.shape[0], shard_idx * x.shape[0])
        return body(net, s_global, s_clients, x, y, mask, weights, rngs,
                    cross=lambda v: _psum_hier(v, axes))

    return round_fn
