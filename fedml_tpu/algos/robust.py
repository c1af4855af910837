"""FedAvg with robust aggregation (backdoor defenses) + attack harness.

Parity: fedml_api/distributed/fedavg_robust/FedAvgRobustAggregator.py —
per-client norm-difference clipping before the weighted average (:179-185)
and weak-DP Gaussian noise on the aggregate (:202-205), both built on
fedml_core/robustness/robust_aggregation.py. Clipping applies to trainable
params only; BatchNorm stats are excluded structurally (they live in
``NetState.model_state``), mirroring the reference's ``is_weight_param``
filter.

The ATTACK side of the reference's harness is here too: with
``cfg.attack_freq = k`` the adversary client(s) — whose data shards the
caller poisons via ``data.loaders.edge_case.make_backdoor_dataset`` — are
forced into the training cohort every k-th round (the reference's
poisoned worker joining every ``attack_freq`` rounds,
main_fedavg_robust.py:120), and :func:`attack_success_rate` measures the
model on a targeted test set (``test_target_accuracy``,
FedAvgRobustAggregator.py:270). tests/test_backdoor.py composes the two
and shows clipping+noise actually suppressing the attack.

Beyond reference parity, this class is now the algorithm layer of the
Byzantine-robustness stack (docs/ROBUSTNESS.md):

- ``cfg.aggregator`` (inherited from FedAvgAPI) swaps the server
  reduction for a robust one — coord_median / trimmed_mean / krum /
  geometric_median (``core/robust_agg``) — composable with the norm
  clip this class installs as its client transform;
- ``cfg.corrupt_mode`` arms the DEVICE-SIDE corruption drill: the
  adversary clients' trained updates are corrupted inside the jitted
  round (``UpdateCorruptor.device_fn``, mask-driven), so
  attack-vs-defense drills run on every execution tier, including the
  windowed ``lax.scan``;
- the weak-DP noise stream is now keyed by ``fold_in`` on the ROUND's
  rng key instead of a carried ``self.rng`` split chain (the PR-2
  prefix-stability discipline), which is what lets robust runs ride
  ``train_rounds_windowed`` bit-equal to the host loop.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from fedml_tpu.algos.fedavg import FedAvgAPI
from fedml_tpu.core.robustness import add_gaussian_noise, norm_diff_clipping
from fedml_tpu.trainer.local import NetState

#: fold_in constant reserving the weak-DP noise stream off each round's
#: rng key. The per-client training streams fork at the SAME level as
#: ``fold_in(round_key, slot)`` with slot ∈ [0, cohort) — so this tag
#: sits at the top of the int32 range, unreachable by any cohort slot
#: index (a small constant like 0x3D would be bit-identical to client
#: slot 61's stream root in a 62+-client round). The transform (0x7F)
#: and corruptor (0xC0) forks are second-level (folded on the per-client
#: key), so they cannot collide with this either.
_NOISE_TAG = 0x7FFFFF3D


def attack_success_rate(api, x_targeted, y_target, batch_size: int = 128):
    """Accuracy of the CURRENT global model on a targeted test set
    (triggered inputs labelled with the attack target — e.g. from
    ``make_targeted_test_set``): by construction this equals the backdoor
    attack success rate (FedAvgRobustAggregator.test_target_accuracy)."""
    from fedml_tpu.data.batching import batch_global

    xt, yt, mask = batch_global(np.asarray(x_targeted), np.asarray(y_target),
                                batch_size)
    m = api.eval_fn(api._eval_net(), xt, yt, mask)
    return float(m["accuracy"])


class FedAvgRobustAPI(FedAvgAPI):
    window_carry = ("— (round-keyed weak-DP noise; [W, C] adversary "
                    "mask rides the scanned aux slot)")

    def __init__(self, *args, adversary_clients=None, **kwargs):
        super().__init__(*args, **kwargs)
        cfg = self.cfg
        armed = (getattr(cfg, "attack_freq", 0)
                 or getattr(cfg, "corrupt_mode", "none") != "none")
        if armed and adversary_clients is None:
            k = max(1, int(getattr(cfg, "attack_num_adversaries", 1)))
            if k > cfg.client_num_in_total:
                # A negative id here would silently gather client 0's
                # (honest) shard — fail loudly instead.
                raise ValueError(
                    f"attack_num_adversaries={k} exceeds "
                    f"client_num_in_total={cfg.client_num_in_total}")
            adversary_clients = range(cfg.client_num_in_total - k,
                                      cfg.client_num_in_total)
        self.adversary_clients = np.asarray(
            list(adversary_clients) if adversary_clients is not None else [],
            np.int64)
        if cfg.compress and cfg.compress != "none":
            # This class replaces the client-transform hook with norm
            # clipping; accepting cfg.compress here would silently drop
            # the compression the user asked for.
            raise ValueError(
                "FedAvgRobustAPI's client transform is the robust norm "
                "clip; combining it with simulated compression is not "
                "supported — drop cfg.compress or use plain FedAvg")
        self._noise = jax.jit(
            lambda p, r: add_gaussian_noise(p, r, cfg.robust_stddev)
        )

    def _sample_round_uncached(self, round_idx: int):
        """On every ``attack_freq``-th round, force the adversary
        client(s) into the cohort (replacing honestly-sampled slots);
        other rounds sample exactly as the parent does."""
        idx, wmask = super()._sample_round_uncached(round_idx)
        freq = getattr(self.cfg, "attack_freq", 0)
        if (not freq or self.adversary_clients.size == 0
                or round_idx % freq != 0):
            return idx, wmask
        from fedml_tpu.core.sampling import pad_to_multiple

        active = np.asarray(idx)[np.asarray(wmask) > 0]
        adv = self.adversary_clients
        n_adv = min(len(adv), len(active))
        # Evict UNIFORMLY at random (seeded by the round, like
        # sample_clients): truncating np.setdiff1d's sorted output would
        # deterministically evict the highest-id honest clients on every
        # attack round — a systematic participation bias. Order-based
        # truncation is no better: selection policies like oort return
        # id-sorted cohorts, where sample order IS id order.
        honest = active[np.isin(active, adv, invert=True)]
        rs = np.random.RandomState(round_idx)
        keep = rs.choice(honest, size=min(len(honest),
                                          len(active) - n_adv),
                         replace=False) if len(honest) else honest
        cohort = np.sort(np.concatenate([keep, adv[:n_adv]])).astype(
            np.asarray(idx).dtype)
        return pad_to_multiple(cohort, self.n_shards)

    def _client_transform(self):
        cfg = self.cfg

        def clip(global_net, client_net):
            clipped = norm_diff_clipping(
                client_net.params, global_net.params, cfg.robust_norm_bound
            )
            return NetState(clipped, client_net.model_state)

        return clip

    # --- device-side corruption drill (cfg.corrupt_mode) -----------------
    def _corruptor(self):
        """Build (once) the mask-driven device corruptor from
        ``cfg.corrupt_mode`` — consulted by the base round builders
        during ``set_client_lr`` (which runs inside ``super().__init__``,
        hence cfg-only: ``adversary_clients`` is not resolved yet; the
        MASKS are computed per round in :meth:`_round_aux` /
        :meth:`_window_scan_extras`, after construction finished)."""
        mode = getattr(self.cfg, "corrupt_mode", "none")
        if mode == "none":
            return None
        fn = getattr(self, "_device_corruptor", None)
        if fn is None:
            from fedml_tpu.core.faults import UpdateCorruptor

            fn = self._device_corruptor = UpdateCorruptor(
                mode, scale=self.cfg.corrupt_scale).device_fn()
        return fn

    def _adv_mask(self, idx, wmask) -> np.ndarray:
        """Host math: 1.0 at cohort slots held by an adversary client
        (padded slots masked out — they repeat slot 0's id with weight 0
        and must not be corrupted into the order statistics)."""
        return (np.isin(np.asarray(idx), self.adversary_clients)
                .astype(np.float32) * np.asarray(wmask, np.float32))

    def _round_aux(self, round_idx: int, idx, wmask):
        if self._corruptor() is None:
            return ()
        return (jnp.asarray(self._adv_mask(idx, wmask)),)

    def _window_scan_extras(self, idx2d, wmask2d):
        if self._corruptor() is None:
            return ()
        from fedml_tpu.obs.sanitizer import planned_transfer

        # The [W, C] adversary mask is scanned alongside the weights and
        # forwarded into each round_fn call (make_window_scan *aux) — on
        # a mesh it ships client-sharded like every per-round [C] input.
        adv = self._adv_mask(idx2d, wmask2d)
        put = self._get_window_put()
        with planned_transfer():
            return ((put(adv) if put is not None else jnp.asarray(adv)),)

    # --- server update: weak-DP noise, round-keyed -----------------------
    def _server_update(self, old_net, avg_net):
        if self.cfg.robust_stddev > 0:
            # fold_in on the ROUND's key (stored by run_round) — not a
            # self.rng split chain: the windowed scan reproduces the same
            # per-round keys, so the noise stream is bit-equal across
            # tiers and never blocks the scan on carried host state.
            key = jax.random.fold_in(self._last_round_key, _NOISE_TAG)
            return NetState(
                self._noise(avg_net.params, key), avg_net.model_state
            )
        return avg_net

    def _window_server_update(self):
        """Windowed carry protocol ("round"): the weak-DP noise is a pure
        fold over the round average, keyed off the scanned round key —
        no carry needed. With ``robust_stddev == 0`` the server update is
        the plain average and the scan folds nothing."""
        if self.cfg.robust_stddev <= 0:
            return None
        noise = self._noise  # jitted; jit-under-scan inlines

        def update(net, avg, extra, key):
            p = noise(avg.params, jax.random.fold_in(key, _NOISE_TAG))
            return NetState(p, avg.model_state), extra

        return update
