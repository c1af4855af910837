"""Local (on-client) training as a jit-compiled ``lax.scan``.

Replaces the reference's per-client Python epoch/batch loop
(fedml_api/distributed/fedavg/MyModelTrainer.py:19-49 — HOT LOOP #3 in
SURVEY.md §3.1). One ``local_train`` call runs ``epochs × steps`` SGD steps
with static shapes; ``vmap`` over the leading client axis turns the
reference's sequential client for-loop
(fedml_api/standalone/fedavg/fedavg_api.py:58-66) into one batched XLA
program whose matmuls keep the MXU busy across clients.

Model state (BatchNorm running stats etc.) travels with the parameters in a
``NetState`` pytree: the reference ships the full ``state_dict`` (params +
BN buffers) over MPI and averages everything (FedAVGAggregator.py:74-82); we
do the same by weighted-averaging the whole ``NetState``.

The reference re-creates the client optimizer every round
(MyModelTrainer.py:26-31) — we mirror that deliberately (``optimizer.init``
inside ``local_train``), so Adam state does NOT persist across rounds, same
as the reference.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import optax
from flax import struct

from fedml_tpu.core.tree import tree_select


@struct.dataclass
class NetState:
    """Model parameters + non-trainable collections (batch_stats, ...)."""

    params: Any
    model_state: Any  # {} when the model has no mutable collections


class ModelFns(NamedTuple):
    """Functional model interface (the reference's ModelTrainer ABC,
    fedml_core/trainer/model_trainer.py:4-38, reduced to pure functions)."""

    init: Callable  # (rng, sample_x) -> NetState
    apply: Callable  # (net, x, train, rng) -> (logits, new_model_state)


def model_fns(module) -> ModelFns:
    """Wrap a flax.linen module (taking a ``train`` kwarg) into ModelFns."""

    def init(rng, sample_x) -> NetState:
        # One program, not an eager walk of the forward pass op by op (350 s
        # of small compiles for a 424 M-parameter transformer on the chip,
        # PR 28); the values are the eager init's bit for bit.
        variables = jax.jit(
            lambda r, x: module.init({"params": r}, x, train=False)
        )(rng, sample_x)
        params = variables["params"]
        state = {k: v for k, v in variables.items() if k != "params"}
        return NetState(params=params, model_state=state)

    def apply(net: NetState, x, train=False, rng=None):
        variables = {"params": net.params, **net.model_state}
        rngs = {"dropout": rng} if (train and rng is not None) else None
        mutable = list(net.model_state.keys()) if (train and net.model_state) else False
        if mutable:
            logits, new_state = module.apply(
                variables, x, train=train, rngs=rngs, mutable=mutable
            )
            return logits, dict(new_state)
        logits = module.apply(variables, x, train=train, rngs=rngs)
        return logits, net.model_state

    return ModelFns(init=init, apply=apply)


def make_client_optimizer(name: str, lr: float, wd: float = 0.0, grad_clip: float = 0.0):
    """Client optimizers matching the reference's choices
    (MyModelTrainer.py:26-31): plain SGD, or Adam with weight decay +
    amsgrad. ``momentum`` added as a TPU-era convenience. ``grad_clip`` > 0
    prepends global-norm clipping (fed_launch/main.py grad-clipping flag)."""
    if name == "sgd":
        opt = optax.sgd(lr)
    elif name == "momentum":
        opt = optax.sgd(lr, momentum=0.9)
    elif name == "adam":
        # Coupled L2 (decay added to the gradient BEFORE the amsgrad
        # preconditioner) — matches torch.optim.Adam(weight_decay=wd,
        # amsgrad=True) as used by the reference, not AdamW.
        opt = optax.chain(
            optax.add_decayed_weights(wd),
            optax.scale_by_amsgrad(),
            optax.scale(-lr),
        )
    else:
        raise ValueError(f"unknown client optimizer {name!r}")
    if grad_clip and grad_clip > 0:
        opt = optax.chain(optax.clip_by_global_norm(grad_clip), opt)
    return opt


def softmax_ce(logits, labels):
    """Per-example softmax cross-entropy with integer labels."""
    return optax.softmax_cross_entropy_with_integer_labels(logits, labels)


def seq_softmax_ce(logits, labels, pad_id: int = 0):
    """Per-example next-token CE for sequence models: ``logits [B, T, V]``,
    ``labels [B, T]``; mean over non-pad positions. Used by the Shakespeare /
    StackOverflow LSTM tasks (the reference masks padding in its
    language_utils)."""
    per_tok = optax.softmax_cross_entropy_with_integer_labels(logits, labels)
    tok_mask = (labels != pad_id).astype(per_tok.dtype)
    denom = jnp.maximum(tok_mask.sum(axis=-1), 1.0)
    return (per_tok * tok_mask).sum(axis=-1) / denom


def make_epoch_shuffle(mask, epoch_rng):
    """Per-epoch reshuffle closure over ``[S, B, ...]`` packed arrays
    (DataLoader(shuffle=True) semantics). REAL samples are permuted amongst
    themselves and padding stays at the tail (argsort of random keys offset
    by the mask), so trailing steps remain all-masked no-ops: the per-client
    optimizer-step count stays exactly ``epochs x ceil(n_i/B)`` (FedNova's τ
    depends on this) and at most one batch per epoch mixes real samples
    with padding. Returns ``reshuffle(a)`` applicable to every per-sample
    array of the pack (x, y, mask, teacher logits, ...).

    The per-slot keys are drawn PREFIX-STABLY — slot ``i``'s key depends
    only on ``(epoch_rng, i)``, via fold_in, never on the total slot
    count (a single batched ``uniform(epoch_rng, (S*B,))`` draw would
    change EVERY key when S changes). This is what makes a larger forced
    step bucket an exact training no-op: the real samples draw the same
    keys, so they permute identically, and the extra pad slots (copies of
    the client's first sample, masked) extend only the tail. The windowed
    execution tier (``FedAvgAPI.train_rounds_windowed``) forces a shared
    per-window bucket and leans on exactly this property for its
    bit-equality with the per-round host loop."""
    n_steps, batch = mask.shape[0], mask.shape[1]
    flat_mask = mask.reshape(n_steps * batch)
    keys = jax.vmap(
        lambda i: jax.random.uniform(jax.random.fold_in(epoch_rng, i))
    )(jnp.arange(n_steps * batch))
    # Padded slots get keys > 1 so argsort sends them to the tail.
    perm = jnp.argsort(keys + (1.0 - flat_mask) * 2.0)

    def reshuffle(a):
        flat = a.reshape((n_steps * batch,) + a.shape[2:])
        return jnp.take(flat, perm, axis=0).reshape(a.shape)

    return reshuffle


def _dp_batch_grad(apply_fn, loss_fn, net, xb, yb, mb, rng, noise_rng,
                   clip, noise_multiplier, remat):
    """One DP-SGD gradient: per-example grads (vmap), per-example L2 clip
    to ``clip``, masked sum, Gaussian noise ``N(0, (z*clip)^2)`` per
    parameter on the sum, normalized by the real-sample count. Returns
    (masked mean loss, unchanged model_state, noisy mean grad)."""

    def example_loss(p, xe, ye, key):
        logits, _ = apply_fn(
            NetState(p, net.model_state), xe[None], train=True, rng=key
        )
        return loss_fn(logits, ye[None])[0]

    if remat:  # wrap BEFORE differentiation or no rematerialization happens
        example_loss = jax.checkpoint(example_loss)
    grad_one = jax.value_and_grad(example_loss)
    # Per-example dropout keys: one shared key would correlate the dropout
    # masks of every example in the batch.
    keys = jax.random.split(rng, xb.shape[0])
    losses, per_grads = jax.vmap(grad_one, in_axes=(None, 0, 0, 0))(
        net.params, xb, yb, keys
    )

    # Clip each example's gradient to L2 norm ``clip``; masked examples
    # contribute zero.
    sq = sum(
        jnp.sum(jnp.square(g), axis=tuple(range(1, g.ndim)))
        for g in jax.tree.leaves(per_grads)
    )
    scale = jnp.minimum(1.0, clip / jnp.maximum(jnp.sqrt(sq), 1e-12)) * mb

    def reduce_leaf(g, key):
        summed = jnp.tensordot(scale, g, axes=(0, 0))
        if noise_multiplier and noise_multiplier > 0:
            summed = summed + noise_multiplier * clip * jax.random.normal(
                key, summed.shape, summed.dtype
            )
        return summed

    leaves, treedef = jax.tree.flatten(per_grads)
    keys = jax.random.split(noise_rng, len(leaves))
    denom = jnp.maximum(jnp.sum(mb), 1.0)
    grads = jax.tree.unflatten(
        treedef, [reduce_leaf(g, k) / denom for g, k in zip(leaves, keys)]
    )
    loss = jnp.sum(losses * mb) / denom
    return loss, net.model_state, grads


def make_corrected_local_train(apply_fn, local_epochs: int, loss_fn,
                               step_update, remat: bool = False,
                               with_step_count: bool = False):
    """Shared corrected-SGD client trainer for algorithms whose per-step
    update needs per-client inputs the generic ``extra_grad_fn`` hook
    cannot carry (SCAFFOLD's control variates, FedDyn's dynamic
    regularizer). ``step_update(params, grads, aux) -> params'`` applies
    the algorithm's correction; ``aux`` is an arbitrary per-client pytree
    the caller vmaps over. Masking / per-epoch reshuffle / gated no-op
    padded steps mirror :func:`make_local_train_fn` exactly.

    Returns ``local_train(net, aux, x, y, mask, rng) -> (net', loss)``,
    plus the true optimizer-step count K when ``with_step_count`` (padded
    trailing batches are no-op steps, so K = epochs x non-empty steps)."""

    def local_train(net: "NetState", aux, x, y, mask, rng):
        def step(carry, inputs):
            net, step_base = carry
            xb, yb, mb, idx = inputs
            # EXACTLY make_local_train_fn's per-step key derivation (the
            # prefix-stable fold_in discipline): the "first round with
            # zero corrections == plain FedAvg" equivalences (SCAFFOLD,
            # FedDyn) hold bit-wise only while the two trainers draw
            # identical streams.
            sub = jax.random.fold_in(jax.random.fold_in(step_base, idx), 0)

            def masked_loss(p):
                logits, new_state = apply_fn(
                    NetState(p, net.model_state), xb, train=True, rng=sub)
                per = loss_fn(logits, yb)
                return (jnp.sum(per * mb) / jnp.maximum(jnp.sum(mb), 1.0),
                        new_state)

            if remat:
                masked_loss = jax.checkpoint(masked_loss)
            (loss, new_state), grads = jax.value_and_grad(
                masked_loss, has_aux=True)(net.params)
            with jax.named_scope("fed.step.update"):
                new_params = step_update(net.params, grads, aux)
                nb = jnp.sum(mb)
                new_net = tree_select(
                    nb > 0, NetState(new_params, new_state), net)
            return (new_net, step_base), (loss, nb)

        def epoch(carry, epoch_rng):
            # Same fold_in(·, 0)/(·, 1) forks as make_local_train_fn.
            with jax.named_scope("fed.step.shuffle"):
                reshuffle = make_epoch_shuffle(
                    mask, jax.random.fold_in(epoch_rng, 0))
                ex, ey, em = reshuffle(x), reshuffle(y), reshuffle(mask)
            net, _ = carry
            step_base = jax.random.fold_in(epoch_rng, 1)
            carry, (losses, ns) = jax.lax.scan(
                step, (net, step_base),
                (ex, ey, em, jnp.arange(ex.shape[0])))
            return carry, jnp.sum(losses * ns) / jnp.maximum(jnp.sum(ns), 1.0)

        rng, shuffle_rng = jax.random.split(rng)
        (net, _), epoch_losses = jax.lax.scan(
            epoch, (net, rng), jax.random.split(shuffle_rng, local_epochs))
        if with_step_count:
            k_steps = local_epochs * jnp.sum(
                (jnp.sum(mask, axis=1) > 0).astype(jnp.float32))
            return net, jnp.mean(epoch_losses), jnp.maximum(k_steps, 1.0)
        return net, jnp.mean(epoch_losses)

    return local_train


def make_local_train_fn(
    apply_fn,
    optimizer,
    local_epochs: int,
    loss_fn=softmax_ce,
    extra_grad_fn=None,
    shuffle: bool = True,
    remat: bool = False,
    dp_clip: float = 0.0,
    dp_noise_multiplier: float = 0.0,
):
    """Build ``local_train(net, x, y, mask, rng) -> (net', mean_loss)``.

    ``x: [S, B, ...]``, ``y: [S, B]``, ``mask: [S, B]``. Masked samples
    contribute zero loss; an entirely-masked batch leaves net and optimizer
    state untouched (``tree_select`` gate), so padded steps are exact no-ops
    rather than zero-gradient optimizer ticks.

    ``extra_grad_fn(params, global_params) -> grads`` lets algorithms add
    parameter-space gradient terms (FedProx's μ(w − w_global), fedprox).

    ``remat`` rematerializes the model forward during backprop
    (``jax.checkpoint``): activations are recomputed instead of stored,
    trading ~1.3x FLOPs for peak-HBM that no longer scales with model
    depth — the lever for training big models (or many vmapped clients)
    on one chip.

    ``shuffle`` reshuffles each client's sample-to-batch assignment every
    epoch (the reference's DataLoader(shuffle=True) semantics) via an
    on-device permutation of the flattened ``[S*B]`` sample axis. REAL
    samples are permuted amongst themselves and padding stays at the tail
    (argsort of random keys offset by the mask), so trailing steps remain
    all-masked no-ops: the per-client optimizer-step count stays exactly
    ``epochs x ceil(n_i/B)`` (FedNova's τ depends on this) and at most one
    batch per epoch mixes real samples with padding.

    ``dp_clip`` > 0 switches the gradient computation to example-level
    DP-SGD (Abadi et al. 2016): per-example gradients (``vmap`` of
    ``value_and_grad`` over the batch — one batched XLA program, the
    TPU-native formulation), each clipped to L2 norm ``dp_clip``, summed,
    plus N(0, (dp_noise_multiplier * dp_clip)^2) noise per parameter, then
    normalized by the batch's real-sample count. New capability vs the
    reference, which only adds server-side noise (robust_aggregation.py:
    49-53). DP mode keeps the model state (BN stats) frozen during local
    training — per-example state updates are not well-defined under DP;
    use GroupNorm models (the federated-safe default here anyway).
    Privacy accounting: fedml_tpu.core.privacy.PrivacyAccountant.
    """
    dp = dp_clip and dp_clip > 0

    def local_train(net: NetState, x, y, mask, rng):
        opt_state = optimizer.init(net.params)
        global_params = net.params  # anchor for proximal-style terms
        n_steps, batch = x.shape[0], x.shape[1]

        def step(carry, inputs):
            net, opt_state, step_base = carry
            xb, yb, mb, idx = inputs
            # Per-step keys by fold_in on the STEP INDEX, not a carried
            # split chain: step s draws the same dropout/DP-noise keys
            # whatever the total step count, so the all-masked tail steps
            # a forced bucket appends never shift a later epoch's streams
            # (the prefix-stability the windowed tier's bit-equality
            # rests on — see make_epoch_shuffle).
            per_step = jax.random.fold_in(step_base, idx)
            sub = jax.random.fold_in(per_step, 0)
            noise_rng = jax.random.fold_in(per_step, 1) if dp else None

            def masked_loss(p):
                logits, new_state = apply_fn(
                    NetState(p, net.model_state), xb, train=True, rng=sub
                )
                per = loss_fn(logits, yb)
                loss = jnp.sum(per * mb) / jnp.maximum(jnp.sum(mb), 1.0)
                return loss, new_state

            if remat:
                masked_loss = jax.checkpoint(masked_loss)

            if dp:
                loss, new_state, grads = _dp_batch_grad(
                    apply_fn, loss_fn, net, xb, yb, mb, sub, noise_rng,
                    dp_clip, dp_noise_multiplier, remat,
                )
            else:
                (loss, new_state), grads = jax.value_and_grad(
                    masked_loss, has_aux=True
                )(net.params)
            if extra_grad_fn is not None:
                extra = extra_grad_fn(net.params, global_params)
                grads = jax.tree.map(jnp.add, grads, extra)
            with jax.named_scope("fed.step.update"):
                updates, new_opt = optimizer.update(grads, opt_state,
                                                    net.params)
                new_params = optax.apply_updates(net.params, updates)
                nb = jnp.sum(mb)
                nonempty = nb > 0
                new_net = NetState(new_params, new_state)
                net = tree_select(nonempty, new_net, net)
                opt_state = tree_select(nonempty, new_opt, opt_state)
            return (net, opt_state, step_base), (loss, nb)

        def epoch(carry, epoch_rng):
            if shuffle:
                # fold_in(·, 0): the shuffle keys and the step streams
                # must fork from DISJOINT children of the epoch key.
                with jax.named_scope("fed.step.shuffle"):
                    reshuffle = make_epoch_shuffle(
                        mask, jax.random.fold_in(epoch_rng, 0))
                    ex, ey, em = reshuffle(x), reshuffle(y), reshuffle(mask)
            else:
                ex, ey, em = x, y, mask
            net, opt_state, _ = carry
            step_base = jax.random.fold_in(epoch_rng, 1)
            carry, (losses, ns) = jax.lax.scan(
                step, (net, opt_state, step_base),
                (ex, ey, em, jnp.arange(ex.shape[0])))
            # Sample-weighted epoch loss: padded (all-masked) steps carry
            # weight 0, so small clients are not diluted by padding steps.
            return carry, jnp.sum(losses * ns) / jnp.maximum(jnp.sum(ns), 1.0)

        rng, shuffle_rng = jax.random.split(rng)
        (net, _, _), epoch_losses = jax.lax.scan(
            epoch,
            (net, opt_state, rng),
            jax.random.split(shuffle_rng, local_epochs),
        )
        # Mean over local epochs — the reference logs the average of
        # per-epoch means (MyModelTrainer.py:35-48).
        return net, jnp.mean(epoch_losses)

    return local_train


def make_local_train_fn_from_cfg(apply_fn, optimizer, cfg, loss_fn=softmax_ce,
                                 extra_grad_fn=None, shuffle: bool = True):
    """FedConfig-driven builder. Call sites that accept a config MUST use
    this (not raw ``make_local_train_fn``) so every cfg training field —
    epochs, remat, DP clipping/noise — takes effect everywhere; threading
    the fields by hand is how ``--dp_clip`` silently becomes a no-op on a
    forgotten path."""
    return make_local_train_fn(
        apply_fn, optimizer, cfg.epochs, loss_fn, extra_grad_fn, shuffle,
        remat=cfg.remat,
        dp_clip=getattr(cfg, "dp_clip", 0.0),
        dp_noise_multiplier=getattr(cfg, "dp_noise_multiplier", 0.0),
    )


def make_eval_fn(apply_fn, loss_fn=softmax_ce, pad_id: int = 0):
    """Build ``evaluate(net, x, y, mask) -> {loss, accuracy, num}`` over a
    batched ``[S, B, ...]`` set. On-device replacement for the reference's
    host-side per-client test loop (FedAVGAggregator.py:110-161).

    Sequence tasks ([B, T] labels): accuracy is averaged over non-pad
    positions only, consistent with ``seq_softmax_ce``.
    """

    def evaluate(net: NetState, x, y, mask):
        def step(_, inputs):
            xb, yb, mb = inputs
            logits, _ = apply_fn(net, xb, train=False)
            per = loss_fn(logits, yb)
            correct = (jnp.argmax(logits, -1) == yb).astype(jnp.float32)
            if correct.ndim > 1:  # sequence tasks: mean over non-pad tokens
                tok_mask = (yb != pad_id).astype(jnp.float32)
                tok_mask = tok_mask.reshape(correct.shape[0], -1)
                correct = correct.reshape(correct.shape[0], -1)
                correct = (correct * tok_mask).sum(-1) / jnp.maximum(
                    tok_mask.sum(-1), 1.0
                )
            return None, (jnp.sum(per * mb), jnp.sum(correct * mb), jnp.sum(mb))

        _, (losses, corrects, ns) = jax.lax.scan(step, None, (x, y, mask))
        n = jnp.maximum(jnp.sum(ns), 1.0)
        return {
            "loss": jnp.sum(losses) / n,
            "accuracy": jnp.sum(corrects) / n,
            "num": jnp.sum(ns),
        }

    return evaluate
