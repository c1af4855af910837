"""q-FedAvg — fair federated learning (Li et al. 2020, "Fair Resource
Allocation in Federated Learning").

New capability: the reference's only aggregation weighting is sample
counts, so well-fit clients keep dominating the average. q-FedAvg
reweights each round by the clients' local losses — the update direction
leans toward whoever is currently served worst:

    Delta_k = L * (w - w_k)                       (L = 1/lr)
    h_k     = q * F_k^(q-1) * ||Delta_k||^2 + L * F_k^q
    w      <- w - sum_k F_k^q Delta_k / sum_k h_k

with F_k the client's loss AT THE BROADCAST MODEL w^t (a post-adaptation
training loss would underweight disadvantaged clients whose local task is
easy to fit, inverting the fairness objective). ``q = 0`` recovers the
equal-weight FedAvg PARAMETER update exactly (F^0 = 1, h = L); larger q
trades average accuracy for uniformity of per-client performance.
Non-trainable collections (BN running stats) always aggregate with
FedAvg's sample-count weighting — so on stateful models with unequal
counts, q=0 matches FedAvg's state but the equal-weight mean for params.

TPU design: drops into FedAvgAPI's round hooks — client training stays
the same vmapped local_train; only the server combination changes, and it
is a handful of einsums over the client-stacked pytree. One shared core
(``_qffl_update``) serves both the single-device vmap round and the
mesh-sharded round; the only difference is the cross-shard reduction
(identity vs ``lax.psum``), so the fair-update math cannot drift between
the two paths.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from fedml_tpu.algos.fedavg import FedAvgAPI
from fedml_tpu.parallel.shard import (client_axis, client_rngs,
                                      run_clients_guarded)
from fedml_tpu.trainer.local import NetState


def _make_loss_at_global(apply_fn, loss_fn):
    """Per-client masked mean loss of the (broadcast) net on one client's
    packed shard ``[S, B, ...]``."""

    def loss_at_global(net, xc, yc, mc):
        def step(_, inp):
            xb, yb, mb = inp
            logits, _ = apply_fn(net, xb, train=False)
            per = loss_fn(logits, yb)
            return None, (jnp.sum(per * mb), jnp.sum(mb))

        _, (ls, ns) = jax.lax.scan(step, None, (xc, yc, mc))
        return jnp.sum(ls) / jnp.maximum(jnp.sum(ns), 1.0)

    return loss_at_global


def _qffl_update(net, client_nets, F_global, losses, weights, loss_weights,
                 active, q: float, L: float, cross):
    """The fair server update, shared by the vmap and sharded rounds.

    ``cross(x)`` reduces a locally-summed quantity across shards —
    identity on a single device, ``lax.psum`` under shard_map. Everything
    else (F clamp, masking, h/denominator, the all-diverged BN-state
    fallback, loss weighting) is written once so the two paths cannot
    silently diverge."""
    F = jnp.maximum(F_global, 1e-12)
    Fq = jnp.where(active > 0, F ** q, 0.0)
    Fq_m1 = jnp.where(active > 0, F ** (q - 1.0), 0.0)

    # Delta_k = L (w - w_k) over trainable params, client-stacked.
    deltas = jax.tree.map(
        lambda w_, wk: L * (w_.astype(jnp.float32)[None] -
                            wk.astype(jnp.float32)),
        net.params, client_nets.params)
    delta_sq = sum(
        jnp.sum(jnp.square(d).reshape(d.shape[0], -1), axis=1)
        for d in jax.tree.leaves(deltas))
    h = q * Fq_m1 * delta_sq + L * Fq
    denom = jnp.maximum(cross(jnp.sum(h * active)), 1e-12)
    new_params = jax.tree.map(
        lambda w_, d: (w_.astype(jnp.float32)
                       - cross(jnp.einsum("c,c...->...", Fq * active, d))
                       / denom).astype(w_.dtype),
        net.params, deltas)

    # Non-trainable collections (BN stats): sample-count-weighted mean
    # over active clients — the same weighting FedAvg's tree_weighted_mean
    # applies to NetState. (Parameters are governed by the q-update, whose
    # q=0 limit is the UNIFORM client mean — so q=0 equals FedAvg only
    # under equal counts; the state mean matches FedAvg's count weighting
    # always.) An all-diverged round (total weight 0) keeps the PREVIOUS
    # stats: a zero-weight einsum would silently zero the running
    # mean/var and corrupt every later eval. (The parameter update above
    # is already safe in that case — its numerator and h-sum both vanish,
    # leaving w unchanged.)
    w_state = weights.astype(jnp.float32) * active
    total_w = cross(jnp.sum(w_state))
    any_ok = total_w > 0
    wn = w_state / jnp.maximum(total_w, 1e-12)
    new_state = jax.tree.map(
        lambda s, old: jnp.where(
            any_ok,
            cross(jnp.einsum("c,c...->...", wn,
                             s.astype(jnp.float32))).astype(s.dtype),
            old),
        client_nets.model_state, net.model_state)

    lw = loss_weights * active
    lw = lw / jnp.maximum(cross(jnp.sum(lw)), 1e-12)
    return NetState(new_params, new_state), cross(jnp.sum(losses * lw))


def _make_qffl_body(local_train, q, L, apply_fn, loss_fn, client_transform,
                    nan_guard):
    """The whole round given per-client rng streams and a cross-shard
    reduction — shared verbatim by the vmap and sharded wrappers so no
    stage (F_global eval, guarded training, masking, fair update) can
    silently diverge between the two paths."""
    loss_at_global = _make_loss_at_global(apply_fn, loss_fn)

    def body(net, x, y, mask, weights, loss_weights, rngs, cross):
        F_global = jax.vmap(loss_at_global, in_axes=(None, 0, 0, 0))(
            net, x, y, mask)
        client_nets, losses, finite = run_clients_guarded(
            local_train, client_transform, nan_guard,
            net, x, y, mask, rngs)
        active = (weights > 0).astype(jnp.float32) * finite
        return _qffl_update(net, client_nets, F_global, losses, weights,
                            loss_weights, active, q, L, cross)

    return body


def make_qffl_round(local_train, q: float, lr: float, apply_fn, loss_fn,
                    client_transform=None, nan_guard: bool = False):
    """Same signature as ``make_vmap_round`` so FedAvgAPI's fused-gather
    and scan paths work unchanged."""
    body = _make_qffl_body(local_train, q, 1.0 / lr, apply_fn, loss_fn,
                           client_transform, nan_guard)

    def round_fn(net, x, y, mask, weights, loss_weights, rng):
        rngs = client_rngs(rng, x.shape[0], 0)
        return body(net, x, y, mask, weights, loss_weights, rngs,
                    cross=lambda v: v)

    return round_fn


def make_qffl_sharded_round(local_train, q: float, lr: float, apply_fn,
                            loss_fn, mesh, axis: str = "clients",
                            client_transform=None, nan_guard: bool = False):
    """Sharded q-FFL round: clients split over ``mesh[axis]``; the scalar
    reductions (Σ h_k) and the per-leaf numerators (Σ F_k^q Δ_k) become
    psums over ICI, so the fair update is exact regardless of how clients
    land on shards (mirrors make_sharded_round's weighted mean)."""
    from fedml_tpu.parallel.shard import _psum_hier, client_axes

    body = _make_qffl_body(local_train, q, 1.0 / lr, apply_fn, loss_fn,
                           client_transform, nan_guard)
    axes = client_axes(mesh, axis)
    cs = P(axes)
    idx_ax = axes if len(axes) > 1 else axis

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(), cs, cs, cs, cs, cs, P()),
        out_specs=(P(), P()),
        check_vma=False,
    )
    def round_fn(net, x, y, mask, weights, loss_weights, rng):
        shard_idx = jax.lax.axis_index(idx_ax)
        rngs = client_rngs(rng, x.shape[0], shard_idx * x.shape[0])
        return body(net, x, y, mask, weights, loss_weights, rngs,
                    cross=lambda v: _psum_hier(v, axes))

    return round_fn


class QFedAvgAPI(FedAvgAPI):
    """FedAvg with the q-FFL fair aggregation. ``q=0`` ≡ equal-weight
    FedAvg for the parameters (tested; model_state keeps FedAvg's
    sample-count weighting — see module docstring); typical fair settings
    use q in [0.1, 5]. Works on the single-device vmap simulator and
    sharded over a client mesh (tested numerically identical)."""

    window_carry = "— (fair q-update baked into round_fn)"

    def __init__(self, *args, q: float = 1.0, **kw):
        self.q = q
        super().__init__(*args, **kw)

    def _make_vmap_round(self, local_train, transform, guard):
        return make_qffl_round(local_train, self.q, self._client_lr,
                               self.fns.apply, self._loss_fn,
                               client_transform=transform, nan_guard=guard)

    def _make_sharded_round(self, local_train, mesh, transform, guard):
        return make_qffl_sharded_round(
            local_train, self.q, self._client_lr, self.fns.apply,
            self._loss_fn, mesh, client_axis(mesh),
            client_transform=transform, nan_guard=guard)
