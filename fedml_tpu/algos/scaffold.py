"""SCAFFOLD — stochastic controlled averaging (Karimireddy et al. 2020).

New capability: under heterogeneous clients, FedAvg's local epochs drift
toward each client's own optimum ("client drift") and the average stalls.
SCAFFOLD corrects every local step with control variates:

    y   <- y - lr * (grad f_k(y) + c - c_k)          (local steps)
    c_k' = c_k - c + (x - y) / (K_k * lr)            (option II)
    x   <- x + mean_k(y_k - x)
    c   <- c + (|S| / N) * mean_k(c_k' - c_k)

where x is the global model, c the server control, c_k the client
controls, and K_k the client's true optimizer-step count.

TPU design: the N client controls are ONE client-stacked pytree on
device (like Ditto's personal models); the corrected local run is a
dedicated ``lax.scan`` trainer (the correction enters every step, which
the generic trainer's parameter-space ``extra_grad_fn`` cannot express —
that hook has no per-client input). K_k is computed from the mask
(padded trailing batches are no-op steps, trainer/local.py), so ragged
clients get exact control updates.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from fedml_tpu.algos.fedavg import FedAvgAPI
from fedml_tpu.trainer.local import tree_select


def make_scaffold_local_train(apply_fn, lr: float, local_epochs: int,
                              loss_fn, remat: bool = False):
    """``local_train(net, correction, x, y, mask, rng) -> (net', loss, K)``
    — plain SGD with the SCAFFOLD per-step correction ``c - c_k`` added to
    every gradient; ``K`` is the true number of non-empty optimizer steps.
    Built on the shared corrected-SGD trainer (trainer/local.py)."""
    from fedml_tpu.trainer.local import make_corrected_local_train

    def step_update(params, grads, correction):
        return jax.tree.map(lambda p, g, corr: p - lr * (g + corr),
                            params, grads, correction)

    return make_corrected_local_train(apply_fn, local_epochs, loss_fn,
                                      step_update, remat=remat,
                                      with_step_count=True)


class ScaffoldAPI(FedAvgAPI):
    """FedAvg + control variates. Plain-SGD clients only (the SCAFFOLD
    correction is defined on the SGD update; cfg.client_optimizer must be
    'sgd'). Sampling/eval/loop scaffolding is inherited.

    Streams from a ``FederatedStore`` too: the client CONTROLS stay a
    device-resident ``[N, ...]`` stack (per-client state, not data), but
    the round's training cohort arrives through the shared
    :meth:`FedAvgAPI._cohort` path — host-gathered and double-buffered at
    reference client scales. On the store, the windowed tier
    (``train_rounds_windowed``) runs W rounds per dispatch through the
    "custom" carry protocol below."""

    #: Windowed carry protocol: the round itself consumes/produces the
    #: carried state (server control + client-control stack), so the
    #: step is custom — see _build_fused_step, which serves the fused
    #: host round AND the windowed scan (the capability record derives
    #: both from it).
    window_protocol = "custom"
    window_carry = "server control + client-control stack"

    def __init__(self, *args, server_lr: float = 1.0, **kw):
        super().__init__(*args, **kw)
        # Reject (rather than silently ignore) cfg knobs the corrected
        # local step does not implement — a user who sets --dp_clip must
        # not believe DP is active. cfg.wd is NOT rejected: the generic
        # sgd client optimizer ignores it too (reference parity — the
        # reference pairs weight decay with Adam only, MyModelTrainer.py:
        # 26-31), so behavior matches FedAvg exactly.
        self._require_plain_sgd_round("ScaffoldAPI's corrected SGD step")
        self.server_lr = server_lr
        n = int(self.train_fed.num_clients)
        zeros = jax.tree.map(jnp.zeros_like, self.net.params)
        self.server_control = zeros
        self.client_controls = jax.tree.map(
            lambda p: jnp.broadcast_to(p[None], (n,) + p.shape), zeros)
        self._scaffold_jit = None

    def _on_client_lr_change(self):
        self._scaffold_jit = None

    def _scaffold_update(self, net, c_server, ck_sub, trained, losses,
                         k_steps, weights, cross):
        """The SCAFFOLD server update, shared by the vmap and sharded
        rounds. ``cross(x)`` reduces a locally-summed quantity across
        shards — identity on one device, ``lax.psum`` under shard_map —
        so the control/averaging math is written once and cannot drift."""
        lr = self._client_lr
        server_lr = self.server_lr
        n_total = float(self.train_fed.num_clients)

        active = (weights > 0).astype(jnp.float32)
        # Option II client-control update:
        #   c_k' = c_k - c + (x - y_k) / (K_k * lr)
        inv_klr = 1.0 / (k_steps * lr)
        ck_new = jax.tree.map(
            lambda ck, c, xg, yk: (
                ck - c[None]
                + (xg.astype(jnp.float32)[None] - yk.astype(jnp.float32))
                * inv_klr.reshape((-1,) + (1,) * (xg.ndim))),
            ck_sub, c_server, net.params, trained.params)

        # Server model: x + server_lr * weighted mean of (y_k - x). An
        # all-inactive round (every sampled client empty/weight-masked)
        # keeps the previous model: wn_w would be all-zero, the "average"
        # the zero tree, and with server_lr=1 the global would be zeroed.
        w = weights.astype(jnp.float32)
        total_w = cross(jnp.sum(w))
        wn_w = w / jnp.maximum(total_w, 1e-12)
        avg = jax.tree.map(
            lambda p: cross(jnp.einsum(
                "c,c...->...", wn_w, p.astype(jnp.float32))).astype(p.dtype),
            trained)
        new_net = jax.tree.map(
            lambda xg, a: (xg.astype(jnp.float32) * (1 - server_lr)
                           + server_lr * a.astype(jnp.float32)
                           ).astype(xg.dtype),
            net, avg)
        new_net = tree_select(total_w > 0, new_net, net)
        # Server control: c + (|S|/N) * mean_k Δc_k (active mean).
        total_active = cross(jnp.sum(active))
        wn = active / jnp.maximum(total_active, 1e-12)
        frac = total_active / n_total
        c_new = jax.tree.map(
            lambda c, ckn, ck: c + frac * cross(jnp.einsum(
                "c,c...->...", wn, ckn - ck)),
            c_server, ck_new, ck_sub)
        # wn_w is already the normalized sample weighting — reuse it for
        # the loss (recomputing would add a redundant psum per round).
        return new_net, c_new, ck_new, cross(jnp.sum(losses * wn_w))

    def _scaffold_round_fn(self):
        if self._scaffold_jit is not None:
            return self._scaffold_jit
        local_train = make_scaffold_local_train(
            self.fns.apply, self._client_lr, self.cfg.epochs, self._loss_fn,
            remat=self.cfg.remat)

        def body(net, c_server, ck_sub, x, y, mask, weights, rngs, cross):
            corrections = jax.tree.map(
                lambda c, ck: c[None] - ck, c_server, ck_sub)
            trained, losses, k_steps = jax.vmap(
                local_train, in_axes=(None, 0, 0, 0, 0, 0)
            )(net, corrections, x, y, mask, rngs)
            return self._scaffold_update(net, c_server, ck_sub, trained,
                                         losses, k_steps, weights, cross)

        from fedml_tpu.parallel.shard import make_stateful_client_round

        from fedml_tpu.parallel.shard import client_axis
        axis = None if self.mesh is None else client_axis(self.mesh)
        round_fn = make_stateful_client_round(
            body, self.mesh, axis or "clients")
        self._scaffold_jit = jax.jit(round_fn)
        return self._scaffold_jit

    # --- carry capability record ("custom"): controls ride every tier ----
    def _build_fused_step(self):
        """ONE SCAFFOLD round as one donated dispatch: cohort control
        gather + the stateful round + the masked scatter-merge, carry
        ``(net, (server_control, client_controls))``. The same step
        scanned W-deep IS the windowed tier (``_build_window_scan``
        derives from it), so a client sampled twice in one window sees
        its own earlier control update (bit-equality with the host
        loop). The scatter gate: only clients that actually trained
        update their control — a sampled EMPTY client runs zero real
        steps, so writing its ``ck - c + 0`` "update" would drift its
        stored control by ``-c`` each time it is sampled (the paper
        updates controls only for clients that computed updates)."""
        from fedml_tpu.parallel.shard import make_fused_stateful_round_step

        return make_fused_stateful_round_step(self._scaffold_round_fn())

    def _window_carry_init(self):
        return (self.server_control, self.client_controls)

    def _window_carry_commit(self, extra) -> None:
        self.server_control, self.client_controls = extra

    def _window_scan_extras(self, idx2d, wmask2d):
        from fedml_tpu.obs.sanitizer import planned_transfer

        # The step needs each round's cohort index map (control
        # gather/scatter) and its trained mask (empty clients must not
        # write their slot). Both are host gathers over counts
        # (layout-agnostic — the resident host loop and the store-backed
        # windowed scan consume the same operands); the H2D rides the
        # window's planned staging copies.
        trained = self._window_update_mask(idx2d, wmask2d)
        with planned_transfer():
            return (jnp.asarray(np.asarray(idx2d), jnp.int32),
                    jnp.asarray(trained, jnp.float32))

    # -- checkpoint/resume: controls are run state ------------------------
    def checkpoint_extra_state(self):
        return {"server_control": self.server_control,
                "client_controls": self.client_controls}

    def load_checkpoint_extra_state(self, extra) -> None:
        self.server_control = extra["server_control"]
        self.client_controls = extra["client_controls"]
