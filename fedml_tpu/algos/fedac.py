"""Accelerated server optimizers riding the carry capability record:
FedAc and server averaging.

Both are PURE server-state updates — exactly the shape the windowed
carry protocol scans — so they run fused + windowed + on-device from
day one, with their sequences living on device between
rounds. They are the "accuracy-per-round for free" counterpart to the
throughput story: same client compute, better round-for-round progress.

**FedAc** (Yuan & Ma, "Federated Accelerated Stochastic Gradient
Descent", NeurIPS 2020, arXiv:2006.08950): provably accelerates Local
SGD/FedAvg with Nesterov-style sequence coupling. The paper runs the
three-sequence recursion per LOCAL step; this implementation applies the
same recursion at the ROUND level — the aggregate progress of the K
local steps, ``Δ = x_md − avg``, plays the role of the (scaled) gradient
at the coupling point ``x_md``, which is the model the server broadcast:

    x_ag' = x_md − Δ                       (= avg, the FedAvg point)
    x'    = (1 − 1/α)·x + (1/α)·x_md − γ·Δ
    x_md' = (1/β)·x' + (1 − 1/β)·x_ag'     (the next broadcast)

``γ`` (in units of the local progress, γ ≥ 1) is the acceleration knob;
``α``/``β`` default to the FedAc-I couplings ``α = (3γ − 1)/2``,
``β = 2α − 1``. At ``γ = 1`` the recursion collapses to FedAvg
(α = β = 1 → x_md' = avg) — pinned by test.

**Server averaging** (Guo et al., "Server Averaging for Federated
Learning", arXiv:2103.11619): the broadcast model mixes the current
round average with the running mean of PAST global models —
averaging over the optimization path damps client-drift oscillation and
speeds convergence per round. Pure carry ``(acc, count, t)``:

    acc' = acc + avg, count' = count + 1      (from round avg_start on)
    net' = (1 − β)·avg + β·acc'/count'

``β = 0`` is exactly FedAvg (pinned by test).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from fedml_tpu.algos.fedavg import FedAvgAPI
from fedml_tpu.trainer.local import NetState


def _f32(x):
    return x.astype(jnp.float32)


class FedAcAPI(FedAvgAPI):
    """FedAvg + round-level FedAc acceleration (arXiv:2006.08950).

    ``gamma`` ≥ 1 scales the accelerated sequence's step in units of the
    round's aggregate local progress; ``alpha``/``beta`` override the
    FedAc-I couplings. All three are STATIC Python floats baked into the
    jitted update (changing them mid-run would recompile — construct a
    new API instead)."""

    window_carry = "(x, x_ag) acceleration sequences"

    def __init__(self, *args, gamma: float = 2.0, alpha: float = None,
                 beta: float = None, **kw):
        super().__init__(*args, **kw)
        if gamma < 1.0:
            raise ValueError(f"fedac gamma must be >= 1 (1 = FedAvg), "
                             f"got {gamma}")
        self.gamma = float(gamma)
        self.alpha = (float(alpha) if alpha is not None
                      else max((3.0 * self.gamma - 1.0) / 2.0, 1.0))
        self.beta = (float(beta) if beta is not None
                     else max(2.0 * self.alpha - 1.0, 1.0))
        if self.alpha < 1.0 or self.beta < 1.0:
            raise ValueError(
                f"fedac couplings must be >= 1, got alpha={self.alpha}, "
                f"beta={self.beta}")
        # Both sequences start at the init point (x = x_ag = x_md = w0).
        # DISTINCT buffers (jnp.array copies): the fused step donates the
        # whole (net, extra) carry, and donating one buffer twice is an
        # XLA error.
        self._fedac_state = (
            jax.tree.map(jnp.array, self.net.params),
            jax.tree.map(jnp.array, self.net.params))

    # --- the pure carry record ------------------------------------------
    def _window_server_update(self):
        inv_a = 1.0 / self.alpha
        inv_b = 1.0 / self.beta
        g = self.gamma

        def update(net, avg, extra, key):
            del key  # deterministic update; protocol slot unused
            x, _x_ag = extra
            # Δ = x_md − avg; x_md is the round's broadcast point (net).
            new_x = jax.tree.map(
                lambda xl, md, av: (
                    (1.0 - inv_a) * _f32(xl) + inv_a * _f32(md)
                    - g * (_f32(md) - _f32(av))).astype(xl.dtype),
                x, net.params, avg.params)
            new_x_ag = avg.params  # x_ag' = x_md − Δ, exactly the average
            md = jax.tree.map(
                lambda xl, agl: (inv_b * _f32(xl)
                                 + (1.0 - inv_b) * _f32(agl)).astype(
                                     agl.dtype),
                new_x, new_x_ag)
            # Non-trainable state (BN stats) keeps the plain client
            # average, like FedOpt.
            return NetState(md, avg.model_state), (new_x, new_x_ag)

        return update

    def _window_carry_init(self):
        return self._fedac_state

    def _window_carry_commit(self, extra) -> None:
        self._fedac_state = extra

    def _server_update(self, old_net, avg_net):
        # Host form = the pure form + commit (the fused tiers never call
        # this; kept consistent for any host path that does).
        new_net, self._fedac_state = self._window_server_update()(
            old_net, avg_net, self._fedac_state, None)
        return new_net

    # -- checkpoint/resume: the sequences are run state -------------------
    def checkpoint_extra_state(self):
        return {"fedac_x": self._fedac_state[0],
                "fedac_x_ag": self._fedac_state[1]}

    def load_checkpoint_extra_state(self, extra) -> None:
        self._fedac_state = (extra["fedac_x"], extra["fedac_x_ag"])


class ServerAvgAPI(FedAvgAPI):
    """FedAvg + server averaging (arXiv:2103.11619): broadcast
    ``(1 − β)·avg + β·mean(past globals)``.

    ``avg_coef`` is β (0 = plain FedAvg); ``avg_start`` skips the first
    rounds (early models are far from the optimum — averaging them in
    drags the iterate; the paper's partial/weighted averaging serves the
    same purpose)."""

    window_carry = "running mean of past globals (acc, count, t)"

    def __init__(self, *args, avg_coef: float = 0.5, avg_start: int = 0,
                 **kw):
        super().__init__(*args, **kw)
        if not 0.0 <= avg_coef < 1.0:
            raise ValueError(
                f"server-averaging avg_coef must be in [0, 1), got "
                f"{avg_coef}")
        self.avg_coef = float(avg_coef)
        self.avg_start = int(avg_start)
        self._savg_state = (
            jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32),
                         self.net.params),
            jnp.zeros((), jnp.float32),   # count of accumulated globals
            jnp.zeros((), jnp.int32),     # rounds seen (gates avg_start)
        )

    # --- the pure carry record ------------------------------------------
    def _window_server_update(self):
        beta = self.avg_coef
        start = self.avg_start

        def update(net, avg, extra, key):
            del net, key
            acc, count, t = extra
            take = (t >= start).astype(jnp.float32)
            acc = jax.tree.map(lambda a, p: a + take * _f32(p),
                               acc, avg.params)
            count = count + take
            denom = jnp.maximum(count, 1.0)
            have_mean = count > 0
            new_params = jax.tree.map(
                lambda p, a: jnp.where(
                    have_mean,
                    ((1.0 - beta) * _f32(p) + beta * (a / denom)),
                    _f32(p)).astype(p.dtype),
                avg.params, acc)
            return (NetState(new_params, avg.model_state),
                    (acc, count, t + 1))

        return update

    def _window_carry_init(self):
        return self._savg_state

    def _window_carry_commit(self, extra) -> None:
        self._savg_state = extra

    def _server_update(self, old_net, avg_net):
        new_net, self._savg_state = self._window_server_update()(
            old_net, avg_net, self._savg_state, None)
        return new_net

    # -- checkpoint/resume: the running mean is run state -----------------
    def checkpoint_extra_state(self):
        acc, count, t = self._savg_state
        return {"savg_acc": acc, "savg_count": count, "savg_t": t}

    def load_checkpoint_extra_state(self, extra) -> None:
        self._savg_state = (extra["savg_acc"], extra["savg_count"],
                            extra["savg_t"])
