"""FedGAN — federated averaging over a generator+discriminator pair.

Parity targets:
- Local GAN training (reference fedml_api/distributed/fedgan/
  MyModelTrainer.py:32-71): per batch, one Adam discriminator step on
  BCE(real,1)+BCE(fake,0), then one Adam generator step on BCE(D(G(z)),1);
  optimizers recreated each round.
- Joint aggregation of both nets (reference FedGANAggregator.py:58-88, the
  doubly-nested weighted average over ``{'netg':…, 'netd':…}``): here the two
  nets live in ONE params pytree so the standard weighted tree-mean of the
  FedAvg round machinery already aggregates them jointly.

TPU-first: the per-net optimizer split is ``optax.multi_transform`` over the
``netg``/``netd`` subtrees (no Python-level parameter groups); the whole
local loop is a ``lax.scan`` vmapped over clients like every other
algorithm. The discriminator emits logits and losses use
``sigmoid_binary_cross_entropy`` (see fedml_tpu/models/gan.py docstring).

Capability record: since the record refactor ``FedGanAPI`` IS a
``FedAvgAPI`` whose local step is the adversarial D/G loop — the server
update is the plain client average ("round" protocol, no carry), so
FedGAN rides the fused round step, the windowed streaming scan and the
on-device scan like plain FedAvg (the GAN local
step is prefix-stable in the step count: per-step noise keys fold_in on
the step index, padded steps are tree_select no-ops). Only ``evaluate``
differs: GANs have no accuracy — the reference logs only losses.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import optax

from fedml_tpu.algos.fedavg import FedAvgAPI
from fedml_tpu.core.tree import tree_select
from fedml_tpu.trainer.local import NetState, make_epoch_shuffle


def _apply(module, net: NetState, method, *args, train: bool):
    """module.apply with mutable-collection plumbing (BN variant support)."""
    variables = {"params": net.params, **net.model_state}
    if train and net.model_state:
        out, new_state = module.apply(
            variables, *args, train=train, method=method,
            mutable=list(net.model_state.keys()),
        )
        return out, dict(new_state)
    out = module.apply(variables, *args, train=train, method=method)
    return out, net.model_state


def make_gan_local_train(module, lr: float, local_epochs: int,
                         latent_dim: int = 100):
    """Build ``local_train(net, x, y, mask, rng) -> (net', mean_loss)`` with
    the round-fn signature shared by all algorithms (``y`` is unused — GANs
    are unsupervised; ``mask [S,B]`` gates padded samples out of both
    losses). Reported loss is d_loss + g_loss, mean over steps."""

    def bce(logits, target):  # target ∈ {0., 1.}
        return optax.sigmoid_binary_cross_entropy(
            logits[:, 0], jnp.full(logits.shape[:1], target))

    # NOTE: optax.masked is wrong here — it passes masked-out leaves' raw
    # gradients through as updates (gradient ascent on the frozen net!);
    # multi_transform + set_to_zero freezes them properly.
    opt_d = optax.multi_transform(
        {"train": optax.adam(lr), "freeze": optax.set_to_zero()},
        {"netg": "freeze", "netd": "train"},
    )
    opt_g = optax.multi_transform(
        {"train": optax.adam(lr), "freeze": optax.set_to_zero()},
        {"netg": "train", "netd": "freeze"},
    )

    def local_train(net: NetState, x, y, mask, rng):
        del y
        d_state = opt_d.init(net.params)
        g_state = opt_g.init(net.params)

        def step(carry, inputs):
            net, d_state, g_state, step_base = carry
            xb, mb, idx = inputs
            # Per-step noise keys by fold_in on the STEP INDEX (fedlint
            # R1): the D and G draws fork from disjoint children of the
            # per-step key, and the streams are prefix-stable in the
            # step count (a forced step bucket never shifts them).
            per_step = jax.random.fold_in(step_base, idx)
            zd = jax.random.fold_in(per_step, 0)
            zg = jax.random.fold_in(per_step, 1)
            nb = jnp.maximum(jnp.sum(mb), 1.0)

            def d_loss_fn(p):
                n = NetState(p, net.model_state)
                real_logits, state1 = _apply(
                    module, n, module.discriminate, xb, train=True)
                noise = jax.random.normal(zd, (xb.shape[0], latent_dim))
                fake, state2 = _apply(
                    module, NetState(p, state1), module.generate, noise,
                    train=True)
                # The netg gradients would be frozen by opt_d anyway;
                # stop_gradient skips the generator backward pass entirely.
                fake = jax.lax.stop_gradient(fake)
                fake_logits, state3 = _apply(
                    module, NetState(p, state2), module.discriminate, fake,
                    train=True)
                per = bce(real_logits, 1.0) + bce(fake_logits, 0.0)
                return jnp.sum(per * mb) / nb, state3

            (d_loss, state_d), d_grads = jax.value_and_grad(
                d_loss_fn, has_aux=True)(net.params)
            d_updates, new_d_state = opt_d.update(d_grads, d_state, net.params)
            p_after_d = optax.apply_updates(net.params, d_updates)

            def g_loss_fn(p):
                n = NetState(p, state_d)
                noise = jax.random.normal(zg, (xb.shape[0], latent_dim))
                fake, state1 = _apply(module, n, module.generate, noise,
                                      train=True)
                fake_logits, state2 = _apply(
                    module, NetState(p, state1), module.discriminate, fake,
                    train=True)
                per = bce(fake_logits, 1.0)
                return jnp.sum(per * mb) / nb, state2

            (g_loss, new_model_state), g_grads = jax.value_and_grad(
                g_loss_fn, has_aux=True)(p_after_d)
            g_updates, new_g_state = opt_g.update(g_grads, g_state, p_after_d)
            new_params = optax.apply_updates(p_after_d, g_updates)

            nonempty = jnp.sum(mb) > 0
            new_net = NetState(new_params, new_model_state)
            net = tree_select(nonempty, new_net, net)
            d_state = tree_select(nonempty, new_d_state, d_state)
            g_state = tree_select(nonempty, new_g_state, g_state)
            return (net, d_state, g_state, step_base), (d_loss + g_loss,
                                                        jnp.sum(mb))

        def epoch(carry, epoch_rng):
            # Shuffle keys and step streams fork from DISJOINT children
            # of the epoch key (trainer/local.py discipline).
            reshuffle = make_epoch_shuffle(
                mask, jax.random.fold_in(epoch_rng, 0))
            net, d_state, g_state, _ = carry
            step_base = jax.random.fold_in(epoch_rng, 1)
            carry, (losses, ns) = jax.lax.scan(
                step, (net, d_state, g_state, step_base),
                (reshuffle(x), reshuffle(mask), jnp.arange(x.shape[0])))
            return carry, jnp.sum(losses * ns) / jnp.maximum(jnp.sum(ns), 1.0)

        rng, shuffle_rng = jax.random.split(rng)
        (net, _, _, _), epoch_losses = jax.lax.scan(
            epoch, (net, d_state, g_state, rng),
            jax.random.split(shuffle_rng, local_epochs))
        return net, jnp.mean(epoch_losses)

    return local_train


class FedGanAPI(FedAvgAPI):
    """Federated GAN trainer (reference FedGanAPI.py + FedGANAggregator.py).

    The model initializes from latent noise (``[B, latent_dim]``) via the
    ``_net_init_input`` hook; the local step is the adversarial D/G loop
    (``_build_local_train``); everything else — sampling, aggregation,
    every execution tier in the capability record — is the inherited
    FedAvg machinery. ``train_fed.y`` is ignored; GANs have no accuracy
    eval (the reference logs only losses), so ``evaluate`` returns {}."""

    def __init__(self, model, train_fed, cfg, mesh=None,
                 latent_dim: int = None):
        if latent_dim is None:
            latent_dim = getattr(model, "latent_dim", 100)
        self.module = model
        self.latent_dim = latent_dim
        super().__init__(model, train_fed, None, cfg, mesh=mesh)
        # The adversarial step builds its own per-net Adam pair; cfg
        # knobs the generic trainer honors (dp_clip/dp_noise/grad_clip/
        # client_optimizer/compress) must refuse, not silently no-op —
        # a user who set dp_noise_multiplier must not believe DP is
        # active (same convention as FedNAS/SCAFFOLD/FedDyn).
        self._require_plain_sgd_round("FedGanAPI's adversarial D/G step")

    def _net_init_input(self, sample_x):
        # One latent batch, matching the packed batch size — the joint
        # G→D __call__ initializes both subtrees from it.
        b = int(np.asarray(sample_x).shape[0])
        return jnp.zeros((b, self.latent_dim), jnp.float32)

    def _build_local_train(self, optimizer, loss_fn):
        # The adversarial step builds its OWN per-net Adam pair from the
        # live client lr; the generic optimizer/loss are unused.
        del optimizer, loss_fn
        return make_gan_local_train(self.module, self._client_lr,
                                    self.cfg.epochs, self.latent_dim)

    def evaluate(self):
        return {}

    def generate(self, n: int, rng=None):
        """Sample n images from the current global generator."""
        if rng is None:
            self.rng, rng = jax.random.split(self.rng)
        z = jax.random.normal(rng, (n, self.latent_dim))
        imgs, _ = _apply(self.module, self.net, self.module.generate, z,
                         train=False)
        return imgs
