"""FedNova — normalized averaging (Wang et al., NeurIPS'20).

Parity: fedml_api/standalone/fednova/ — the reference implements FedNova as
a torch Optimizer subclass accumulating ``cum_grad`` and a normalizing
vector (fednova.py:10-151), aggregated with ``tau_eff``-normalized averaging
(fednova_trainer.py:97).

TPU formulation (vanilla-SGD case, momentum=0, matching the reference's
default ``gmf=0`` path): client i runs τ_i local steps, producing
``d_i = (w_g − w_i)/τ_i``. The server applies

    w⁺ = w_g − τ_eff · Σ p_i d_i,   p_i = n_i/N,  τ_eff = Σ p_i τ_i.

Algebraically Σ p_i d_i = s · (w_g − avg_q) with q_i ∝ p_i/τ_i and
s = Σ p_i/τ_i — so the existing weighted-average round (weights n_i/τ_i)
is reused unchanged and the server step is one scalar-γ interpolation with
γ = τ_eff · s. When all τ_i are equal, γ = 1 and FedNova reduces exactly to
FedAvg (covered by a test).

Capability record: FedNova is a "round"-protocol algorithm whose round is
the SHARED builders' round fed per-round ``(q, γ)`` operands — τ_i is a
pure function of the cohort's sample counts, so the q-weights and the
interpolation scalar are host-computed (float64, exactly the pre-record
host loop's math) and ride the aux slot: ``_round_aux`` on the host/fused
tiers, ``_window_scan_extras`` as ``[W, C]``/``[W]`` scanned operands on
the windowed tier. That makes FedNova fused + windowed with
no carry at all; only the on-device scan (which samples inside the jit
and has no host-aux slot) refuses, with the record-derived reason.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from fedml_tpu.algos.fedavg import FedAvgAPI
from fedml_tpu.trainer.local import NetState


class FedNovaAPI(FedAvgAPI):
    window_carry = "— (per-round q-weights + γ ride the scanned aux slot)"

    def _local_steps(self, counts) -> np.ndarray:
        """τ_i = epochs × (non-empty scan steps for client i). Exact because
        the trainer's shuffle keeps padding at the tail (trailing all-masked
        steps are gated no-ops — see make_local_train_fn), so client i runs
        exactly ceil(n_i/B) optimizer updates per epoch. Zero-count slots
        clamp to one step — their weight is zero everywhere they appear, so
        the clamp only guards the division."""
        b = self.cfg.batch_size
        return np.maximum(np.ceil(np.asarray(counts) / b), 1.0) * self.cfg.epochs

    def _nova_operands(self, counts: np.ndarray):
        """``(q, γ)`` for one round from the cohort's (mask-zeroed) sample
        counts — float64 host math, identical to the pre-record host loop."""
        counts = np.asarray(counts, np.float64)
        tau = self._local_steps(counts)
        n_total = counts.sum()
        p = counts / max(n_total, 1.0)
        tau_eff = float((p * tau).sum())
        s = float((p / tau).sum())
        return counts / tau, np.float32(tau_eff * s)

    def _round_aux(self, round_idx: int, idx, wmask):
        counts = (self._host_counts()[np.asarray(idx)].astype(np.float64)
                  * np.asarray(wmask, np.float64))
        q, gamma = self._nova_operands(counts)
        return (jnp.asarray(q, jnp.float32), jnp.asarray(gamma))

    def _window_scan_extras(self, idx2d, wmask2d):
        from fedml_tpu.obs.sanitizer import planned_transfer

        counts2d = (self._host_counts()[np.asarray(idx2d)].astype(np.float64)
                    * np.asarray(wmask2d, np.float64))
        rows = [self._nova_operands(row) for row in counts2d]
        q = np.stack([r[0] for r in rows]).astype(np.float32)
        gamma = np.stack([r[1] for r in rows])
        put = self._get_window_put()
        with planned_transfer():
            # q is client-shaped [W, C]: on a mesh it arrives client-
            # sharded like the weights operand; γ [W] is replicated.
            return (put(q) if put is not None else jnp.asarray(q),
                    jnp.asarray(gamma))

    def _wrap_nova_round(self, base_round):
        """The shared builders' round re-weighted per FedNova: aggregate
        with the τ-normalized ``q`` weights, report the loss with the
        true sample counts, then apply the scalar-γ interpolation — all
        inside the one (jittable) round, so every tier that replays
        ``round_fn`` gets normalized averaging for free."""

        def round_fn(net, x, y, mask, weights, loss_weights, rng, q, gamma):
            out = base_round(net, x, y, mask, q, loss_weights, rng)
            avg, loss, rest = out[0], out[1], tuple(out[2:])
            new_params = jax.tree.map(
                lambda w, a: w - gamma * (w - a), net.params, avg.params)
            new_net = NetState(new_params, avg.model_state)
            return (new_net, loss) + rest

        return round_fn

    def _make_vmap_round(self, local_train, transform, guard):
        return self._wrap_nova_round(
            super()._make_vmap_round(local_train, transform, guard))

    def _make_sharded_round(self, local_train, mesh, transform, guard):
        return self._wrap_nova_round(
            super()._make_sharded_round(local_train, mesh, transform, guard))
