"""FedNAS — federated neural architecture search over the DARTS space.

Parity target: reference fedml_api/distributed/fednas/ —
- clients run local bilevel search: architecture step on a held-out local
  valid split, then weight step on the train split
  (FedNASTrainer.local_search:82, darts/architect.py);
- the server averages BOTH model weights and architecture alphas, weighted
  by sample counts (FedNASAggregator.__aggregate_weight:71,
  __aggregate_alpha:95);
- after search, the genotype is derived from the averaged alphas
  (FedNASAggregator.record_model_global_architecture:173).

TPU-native: weights vs alphas is a partition of ONE flax params pytree
(alphas live at the network root as ``alphas_normal``/``alphas_reduce``),
so the bilevel update is two masked SGD steps inside the same jit-compiled
``lax.scan``; clients are vmapped; aggregation is the standard weighted
tree-mean (which covers w and α jointly, exactly the reference's two loops).
The 2nd-order arch gradient ∇α L_val(w − ξ∇w L_train(w,α), α) is an exact
``jax.grad`` through the unrolled inner step — no finite-difference
Hessian-vector approximation (architect.py:229) needed under XLA.

Capability record: since the record refactor ``FedNASAPI`` IS a
``FedAvgAPI`` whose local step is the bilevel search (server update =
plain client average, "round" protocol, no carry) — FedNAS rides the
fused round step, the windowed streaming scan and the on-device
scan. For that the train/valid split had to become
MASK-AWARE: the halves are cut at ``n_real // 2`` where ``n_real`` is
the client's true (non-padded) step count, so a store cohort forced onto
a larger window-max step bucket trains on exactly the same batches as
the per-round host loop (all-masked tail steps change nothing — the
prefix-stability contract every windowed algorithm must meet). On the
resident layout, where every cohort shares one fixed S, the split is
identical to the old static ``S // 2``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from fedml_tpu.algos.fedavg import FedAvgAPI
from fedml_tpu.core.tree import tree_select
from fedml_tpu.trainer.local import NetState, softmax_ce

ALPHA_KEYS = ("alphas_normal", "alphas_reduce")


def _split_mask(params):
    """Bool pytrees selecting (arch alphas, weights)."""
    flat = {k: (k in ALPHA_KEYS) for k in params}
    return flat, {k: not v for k, v in flat.items()}


def _masked(tree, mask):
    """Zero out leaves whose top-level key is masked False."""
    return jax.tree.map(
        lambda m, sub: jax.tree.map(
            (lambda a: a) if m else (lambda a: jnp.zeros_like(a)), sub),
        mask, tree, is_leaf=lambda n: isinstance(n, bool))


def make_fednas_local_search(apply_fn, lr_w: float, lr_a: float, xi: float,
                             local_epochs: int, unrolled: bool):
    """``local_search(net, x, y, mask, rng) -> (net', loss)`` — the
    bilevel DARTS step with the shared local-train signature, so the
    FedAvg round builders (vmap, shard_map, fused, windowed, on-device)
    consume it unchanged.

    The local data splits in half by TRUE step count: steps ``[0, h)``
    are the train queue, ``[h, 2h)`` the valid queue, ``h = n_real // 2``
    (the reference's 50/50 queue split, FedNASTrainer.py:22-30; with odd
    counts the final real step feeds neither half, deliberately). The
    scan runs over the STATIC bound ``S // 2`` and gates steps at
    ``i >= h`` off — exact no-ops, so a padded step bucket leaves the
    trajectory bit-identical (windowed == host)."""

    def ce_loss(p, state, xb, yb, mb, rng):
        logits, new_state = apply_fn(
            NetState(p, state), xb, train=True, rng=rng)
        per = softmax_ce(logits, yb)
        return (jnp.sum(per * mb) / jnp.maximum(jnp.sum(mb), 1.0),
                new_state)

    def local_search(net, x, y, mask, rng):
        S = x.shape[0]
        half = S // 2  # static scan bound (>= the dynamic h)
        amask, wmask_tree = _split_mask(net.params)
        # True (non-padded) step count: the trainer keeps padding at the
        # tail, and a real step always has at least one unmasked sample.
        n_real = jnp.sum(jnp.any(mask > 0, axis=1).astype(jnp.int32))
        h = n_real // 2

        def row(a, i):
            # Dynamic step gather (clipped — garbage rows are gated off
            # below). ``i`` is traced inside the scan.
            return jnp.take(a, i, axis=0, mode="clip")

        def step(carry, i):
            net, step_base = carry
            xt, yt, mt = row(x, i), row(y, i), row(mask, i)
            xv, yv, mv = row(x, h + i), row(y, h + i), row(mask, h + i)
            # Three per-step keys fork from disjoint children of the
            # fold_in-on-index key (fedlint R1): prefix-stable in the
            # step count, whatever bucket the cohort was forced onto.
            per_step = jax.random.fold_in(step_base, i)
            r1 = jax.random.fold_in(per_step, 0)
            r2 = jax.random.fold_in(per_step, 1)
            r3 = jax.random.fold_in(per_step, 2)

            # --- architecture step on the valid half ---------------
            def val_loss_wrt_alpha(p):
                if unrolled:
                    # exact 2nd-order: lookahead w' = w − ξ∇w L_train
                    gw, _ = jax.grad(ce_loss, has_aux=True)(
                        p, net.model_state, xt, yt, mt, r1)
                    p = jax.tree.map(
                        lambda a, g: a - xi * g, p, _masked(gw, wmask_tree))
                loss, state = ce_loss(p, net.model_state, xv, yv, mv, r2)
                return loss, state

            ga, _ = jax.grad(val_loss_wrt_alpha, has_aux=True)(net.params)
            params = jax.tree.map(
                lambda a, g: a - lr_a * g, net.params, _masked(ga, amask))

            # --- weight step on the train half ---------------------
            (loss, new_state), gw = jax.value_and_grad(
                ce_loss, has_aux=True)(
                    params, net.model_state, xt, yt, mt, r3)
            params = jax.tree.map(
                lambda a, g: a - lr_w * g, params, _masked(gw, wmask_tree))

            active = (i < h) & (jnp.sum(mt) > 0)
            ns = jnp.where(active, jnp.sum(mt), 0.0)
            net = tree_select(active, NetState(params, new_state), net)
            return (net, step_base), (loss, ns)

        def epoch(carry, e):
            # Sample-weighted epoch loss: gated steps (beyond the true
            # half, or all-masked) carry weight 0 and must not dilute
            # the reported search loss.
            net, _ = carry
            step_base = jax.random.fold_in(rng, e)
            carry, (losses, ns) = jax.lax.scan(
                step, (net, step_base), jnp.arange(half))
            return carry, jnp.sum(losses * ns) / jnp.maximum(jnp.sum(ns), 1.0)

        (net, _), losses = jax.lax.scan(
            epoch, (net, rng), jnp.arange(local_epochs))
        return net, jnp.mean(losses)

    return local_search


class FedNASAPI(FedAvgAPI):
    """Federated DARTS search (reference FedNASAPI.py:16) as a FedAvg-
    family algorithm: only the local step differs.

    ``xi``/``unrolled``: 2nd-order arch step w − ξ∇L_train lookahead
    (architect.py unrolled mode); ``unrolled=False`` is the reference's
    ``--arch_search_method`` default 1st-order (MiLeNAS-style)."""

    window_carry = "— (alphas average with the weights)"

    def __init__(self, model, train_fed, test_global, cfg,
                 arch_lr: float = 3e-4, xi: float = 0.0,
                 unrolled: bool = False, **kw):
        # Consumed by _build_local_train, which super().__init__ calls
        # through set_client_lr — set first.
        self.arch_lr = arch_lr
        self.xi = xi if unrolled else 0.0
        self.unrolled = unrolled
        # Architecture geometry for genotype() — taken from the model, not
        # re-guessed from alpha shapes.
        self._steps = int(getattr(model, "steps", 4))
        self._multiplier = int(getattr(model, "multiplier", 4))
        super().__init__(model, train_fed, test_global, cfg, **kw)
        # The bilevel step implements its own two plain-SGD updates; cfg
        # knobs the generic trainer honors must refuse, not no-op.
        self._require_plain_sgd_round("FedNASAPI's bilevel search step")
        # EVERY client must pack >= 2 real steps (the local data splits
        # into train/valid halves, FedNASTrainer.py:22-30): a 1-step
        # client has h = n_real // 2 = 0, so it would train NOTHING
        # while keeping full aggregation weight — refuse loudly on both
        # layouts instead of silently diluting every round it joins.
        steps = np.ceil(np.maximum(self._host_counts(), 1)
                        / cfg.batch_size)
        if int(steps.min()) < 2:
            raise ValueError(
                "FedNAS needs >= 2 packed steps for EVERY client (the "
                "local data is split into train/valid halves, "
                "FedNASTrainer.py:22-30); "
                f"min(ceil(count/batch)) = {int(steps.min())} — use a "
                "smaller batch_size so each client packs >= 2 batches")

    def _build_local_train(self, optimizer, loss_fn):
        # The bilevel step is self-contained plain SGD (weight lr = the
        # live client lr, arch lr = arch_lr); the generic optimizer is
        # unused and incompatible knobs were refused above.
        del optimizer, loss_fn
        return make_fednas_local_search(
            self.fns.apply, self._client_lr, self.arch_lr, self.xi,
            self.cfg.epochs, self.unrolled)

    def genotype(self):
        """Derive the searched architecture from the averaged alphas
        (reference record_model_global_architecture, FedNASAggregator.py:173)."""
        from fedml_tpu.models.darts import derive_genotype

        return derive_genotype(
            self.net.params["alphas_normal"],
            self.net.params["alphas_reduce"], steps=self._steps,
            multiplier=self._multiplier)
