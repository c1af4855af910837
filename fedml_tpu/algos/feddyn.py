"""FedDyn — federated learning with dynamic regularization (Acar et al.,
ICLR 2021, "Federated Learning Based on Dynamic Regularization").

New capability (the reference has no drift-corrected algorithm at all;
this completes the FedProx / SCAFFOLD / FedDyn correction family): each
client k minimizes a DYNAMICALLY regularized local objective

    f_k(w) - <g_k, w> + (alpha/2) ||w - w_t||^2

whose linear term g_k (the client's accumulated first-order correction)
makes the local optima consistent with the global stationary point:

    per-step gradient:  grad f_k(w) - g_k + alpha (w - w_t)
    after local run:    g_k <- g_k - alpha (w_k - w_t)
    server state:       h   <- h - alpha (1/N) sum_{k in S} (w_k - w_t)
    new global:         w   <- mean_{k in S} w_k - (1/alpha) h

Unlike SCAFFOLD there is no control-variate exchange — only the model
crosses the wire; the correction is reconstructed locally.

TPU design mirrors ScaffoldAPI: the N client corrections are ONE
client-stacked pytree on device, the corrected local run is a dedicated
``lax.scan`` trainer (the per-step term needs per-client inputs the
generic ``extra_grad_fn`` hook cannot carry), and one shared update body
serves the single-device vmap round and the shard_map round (psum'd
reductions), so the math cannot drift between paths.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from fedml_tpu.algos.fedavg import FedAvgAPI
from fedml_tpu.trainer.local import NetState


def make_feddyn_local_train(apply_fn, lr: float, alpha: float,
                            local_epochs: int, loss_fn,
                            remat: bool = False):
    """``local_train(net, (g_k, global_params), x, y, mask, rng) ->
    (net', loss)`` — SGD on the dynamically regularized objective; every
    step's gradient carries ``- g_k + alpha (w - w_global)``. Built on
    the shared corrected-SGD trainer (trainer/local.py)."""
    from fedml_tpu.trainer.local import make_corrected_local_train

    def step_update(params, grads, aux):
        g_k, global_params = aux
        return jax.tree.map(
            lambda p, g, gk, w0: p - lr * (g - gk + alpha * (p - w0)),
            params, grads, g_k, global_params)

    return make_corrected_local_train(apply_fn, local_epochs, loss_fn,
                                      step_update, remat=remat)


class FedDynAPI(FedAvgAPI):
    """FedAvg + dynamic regularization. Plain-SGD clients only (the
    correction is defined on the SGD update). ``alpha`` is the paper's
    regularization strength (typical 0.01-0.1).

    Streams from a ``FederatedStore`` too (the SCAFFOLD pattern): the
    client CORRECTIONS stay a device-resident ``[N, ...]`` stack —
    per-client state, not data — while the round's cohort arrives
    through the shared :meth:`FedAvgAPI._cohort` path. The carry
    capability record below is the whole fast-path story: the fused
    one-dispatch round and the W-rounds-per-dispatch windowed scan
    both derive from ONE ``_build_fused_step``,
    with carry ``(net, (server_h, client_grads))``."""

    supports_streaming = True  # corrections device-resident; cohort streams
    window_protocol = "custom"
    window_carry = "server h + client correction stack"

    def __init__(self, *args, alpha: float = 0.01, **kw):
        super().__init__(*args, **kw)
        if alpha <= 0:
            raise ValueError(f"feddyn alpha must be > 0, got {alpha}")
        self._require_plain_sgd_round("FedDynAPI's corrected SGD step")
        self.alpha = alpha
        n = int(self.train_fed.num_clients)
        zeros = jax.tree.map(jnp.zeros_like, self.net.params)
        self.server_h = zeros
        self.client_grads = jax.tree.map(
            lambda p: jnp.broadcast_to(p[None], (n,) + p.shape), zeros)
        self._feddyn_jit = None

    def _on_client_lr_change(self):
        self._feddyn_jit = None

    def _feddyn_update(self, net, h, gk_sub, trained, losses, weights,
                       cross):
        """The FedDyn server update, shared by the vmap and sharded
        rounds — ``cross`` is identity on one device, psum under
        shard_map (mirrors ScaffoldAPI._scaffold_update)."""
        alpha = self.alpha
        n_total = float(self.train_fed.num_clients)
        active = (weights > 0).astype(jnp.float32)
        total_active = cross(jnp.sum(active))
        any_ok = total_active > 0
        wn = active / jnp.maximum(total_active, 1e-12)

        # g_k' = g_k - alpha (w_k - w_t) for participants.
        gk_new = jax.tree.map(
            lambda gk, wk, w0: gk - alpha * (
                wk.astype(jnp.float32) - w0.astype(jnp.float32)[None]),
            gk_sub, trained.params, net.params)
        # h' = h - alpha (1/N) sum_k (w_k - w_t).
        h_new = jax.tree.map(
            lambda hh, wk, w0: hh - (alpha / n_total) * cross(jnp.einsum(
                "c,c...->...", active,
                wk.astype(jnp.float32) - w0.astype(jnp.float32)[None])),
            h, trained.params, net.params)
        # w' = mean_k w_k - (1/alpha) h' (uniform participant mean, per
        # the paper); model_state keeps FedAvg's sample-count weighting.
        new_params = jax.tree.map(
            lambda wk, hh, w0: jnp.where(
                any_ok,
                (cross(jnp.einsum("c,c...->...", wn,
                                  wk.astype(jnp.float32)))
                 - hh / alpha).astype(w0.dtype),
                w0),
            trained.params, h_new, net.params)
        # weights already carry the active zeros (counts x wmask), so they
        # ARE the sample-count weighting (scaffold's wn_w).
        w = weights.astype(jnp.float32)
        wns = w / jnp.maximum(cross(jnp.sum(w)), 1e-12)
        new_state = jax.tree.map(
            lambda s, old: jnp.where(
                any_ok,
                cross(jnp.einsum("c,c...->...", wns,
                                 s.astype(jnp.float32))).astype(s.dtype),
                old),
            trained.model_state, net.model_state)
        loss = cross(jnp.sum(losses * wns))
        return NetState(new_params, new_state), h_new, gk_new, loss

    def _feddyn_round_fn(self):
        if self._feddyn_jit is not None:
            return self._feddyn_jit
        local_train = make_feddyn_local_train(
            self.fns.apply, self._client_lr, self.alpha, self.cfg.epochs,
            self._loss_fn, remat=self.cfg.remat)

        def body(net, h, gk_sub, x, y, mask, weights, rngs, cross):
            trained, losses = jax.vmap(
                local_train, in_axes=(None, (0, None), 0, 0, 0, 0)
            )(net, (gk_sub, net.params), x, y, mask, rngs)
            return self._feddyn_update(net, h, gk_sub, trained, losses,
                                       weights, cross)

        from fedml_tpu.parallel.shard import make_stateful_client_round

        from fedml_tpu.parallel.shard import client_axis
        axis = None if self.mesh is None else client_axis(self.mesh)
        round_fn = make_stateful_client_round(
            body, self.mesh, axis or "clients")
        self._feddyn_jit = jax.jit(round_fn)
        return self._feddyn_jit

    # --- carry capability record ("custom"): corrections ride every tier -
    def _build_fused_step(self):
        """ONE FedDyn round as one donated dispatch: cohort correction
        gather + the stateful round + the masked scatter-merge, carry
        ``(net, (server_h, client_grads))`` — the same step the windowed
        scan replays W-deep (bit-equality by construction). The scatter
        gate: only clients that actually trained update their correction
        (a sampled empty client ran zero real steps; writing its
        "update" would drift nothing here since alpha*0 = 0, but masking
        keeps PADDED DUPLICATE slots from clobbering real state)."""
        from fedml_tpu.parallel.shard import make_fused_stateful_round_step

        return make_fused_stateful_round_step(self._feddyn_round_fn())

    def _window_carry_init(self):
        return (self.server_h, self.client_grads)

    def _window_carry_commit(self, extra) -> None:
        self.server_h, self.client_grads = extra

    def _window_scan_extras(self, idx2d, wmask2d):
        from fedml_tpu.obs.sanitizer import planned_transfer

        # Per-round cohort index map + trained mask (layout-agnostic
        # count gathers, shared with SCAFFOLD's extras).
        trained = self._window_update_mask(idx2d, wmask2d)
        with planned_transfer():
            return (jnp.asarray(np.asarray(idx2d), jnp.int32),
                    jnp.asarray(trained, jnp.float32))

    # -- checkpoint/resume: corrections are run state ---------------------
    def checkpoint_extra_state(self):
        return {"server_h": self.server_h,
                "client_grads": self.client_grads}

    def load_checkpoint_extra_state(self, extra) -> None:
        self.server_h = extra["server_h"]
        self.client_grads = extra["client_grads"]
