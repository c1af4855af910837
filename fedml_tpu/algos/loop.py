"""Shared federated training-loop policy (round cadence + eval frequency).

One implementation of the loop the reference re-implements in every
``*API``/``*Trainer`` class (e.g. standalone fedavg_api.py:40-82): train a
round, evaluate every ``frequency_of_the_test`` rounds and on the last
round, collect history.
"""

from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp

from fedml_tpu.algos.capability import ExcludedScanTiers


def eval_segments(comm_round: int, frequency_of_the_test: int,
                  start: int = 0):
    """Split ``[start, comm_round)`` into inclusive ``(lo, hi)`` spans
    each ending exactly at an eval round — the rounds
    :meth:`FederatedLoop.train` evaluates after (``round_idx % freq == 0``
    or the last round). Windowed execution plans its windows WITHIN these
    spans (``FedAvgAPI.train_windowed``) so a multi-round scan never runs
    past a point where the host must stop and evaluate."""
    freq = max(int(frequency_of_the_test), 1)
    r = start
    while r < comm_round:
        e = r
        while not (e % freq == 0 or e == comm_round - 1):
            e += 1
        yield r, e
        r = e + 1


class FederatedLoop(ExcludedScanTiers):
    """Mixin. Subclasses provide ``cfg``, ``train_one_round(round_idx)``,
    ``eval_fn``, ``test_global``, and ``_eval_net()``. Subclasses that also
    provide ``n_shards``, ``train_fed``, ``net``, ``rng`` and ``round_fn``
    get the shared round scaffold (``sample_round``/``run_round``) for free.

    ``round_fn_fused`` is an optional extension point: a jitted
    ``(net, train_fed, idx, wmask, rng)`` round with the client gather
    traced inside (built by FedAvgAPI for a resident federation).

    The scan-tier entry points come from :class:`ExcludedScanTiers`
    (record-derived refusals keyed on the carry capability
    declarations below); FedAvgAPI overrides both the declarations —
    derived structurally from the carry-protocol hooks — and the entry
    points."""

    round_fn_fused = None

    def _eval_net(self):
        raise NotImplementedError

    def train_one_round(self, round_idx: int) -> Dict[str, float]:
        raise NotImplementedError

    def sample_round(self, round_idx: int):
        """Reference-seeded sampling + padding to the shard-count multiple
        (FedAVGAggregator.client_sampling, FedAVGAggregator.py:90-99)."""
        sel = getattr(self.cfg, "client_selection", "random")
        if sel != "random":
            # Loss-biased selection is implemented in FedAvgAPI's override;
            # algorithms landing here would silently sample uniformly
            # while the user believes pow_d is active.
            raise NotImplementedError(
                f"client_selection={sel!r} is not supported by "
                f"{type(self).__name__}; only the FedAvg family implements "
                "loss-biased selection")
        from fedml_tpu.core.sampling import pad_to_multiple, sample_clients

        directory = getattr(self.train_fed, "directory", None)
        if directory is not None \
                and directory.num_clients == self.cfg.client_num_in_total:
            # Sharded store (data/directory.py): the ClientDirectory IS
            # the cohort sampler — a metadata-only service whose draw
            # delegates to the same reference-seeded stream, so the
            # cohort is bit-identical to the flat path (and invariant
            # under re-sharding, tested).
            idx = directory.sample_cohort(round_idx,
                                          self.cfg.client_num_per_round)
        else:
            idx = sample_clients(
                round_idx, self.cfg.client_num_in_total,
                self.cfg.client_num_per_round
            )
        idx, wmask = pad_to_multiple(idx, self.n_shards)
        return idx, wmask

    def _round_aux(self, round_idx: int, idx, wmask):
        """Extra trailing operands for ``round_fn`` beyond the standard
        seven — the hook the device-side corruption drill fills with its
        per-client adversary mask (``FedAvgRobustAPI``). Default: none.
        Rounds built without the matching builder option keep their
        7-operand signature, so this must return ``()`` unless the
        subclass also configured its round to consume the extras."""
        return ()

    def run_round(self, round_idx: int):
        """One sampled round through ``round_fn``: gather client shards,
        sample-count weights (padded slots weight 0), fresh round rng.
        Returns ``(avg_net, mean_loss)`` without touching ``self.net``.

        When the subclass built a fused round
        (``round_fn_fused``), the gather happens inside the jit — one
        dispatch per round instead of five. With a host-resident
        ``FederatedStore`` (``self._streaming``), the cohort was gathered
        on host (double-buffered) and the round consumes it directly."""
        self.rng, rnd_rng = jax.random.split(self.rng)
        # Server updates that need a round-keyed randomness stream
        # (FedAvgRobust's weak-DP noise) fold_in from THIS key instead of
        # splitting self.rng again: the windowed tier reproduces exactly
        # this per-round key chain, so fold_in children are bit-equal
        # across tiers (the PR-2 prefix-stability discipline).
        self._last_round_key = rnd_rng
        idx, wmask = self.sample_round(round_idx)
        aux = self._round_aux(round_idx, idx, wmask)
        if getattr(self, "_streaming", False):
            sub = self._stream_cohort(round_idx, idx)
            weights = sub.counts.astype(jnp.float32) * jnp.asarray(wmask)
            return self._unpack_round(self.round_fn(
                self.net, sub.x, sub.y, sub.mask, weights, weights, rnd_rng,
                *aux
            ))
        if self.round_fn_fused is not None and not aux:
            return self._unpack_round(self.round_fn_fused(
                self.net, self.train_fed,
                jnp.asarray(idx), jnp.asarray(wmask), rnd_rng))
        from fedml_tpu.data.batching import gather_clients

        sub = gather_clients(self.train_fed, idx)
        weights = sub.counts.astype(jnp.float32) * jnp.asarray(wmask)
        return self._unpack_round(self.round_fn(
            self.net, sub.x, sub.y, sub.mask, weights, weights, rnd_rng,
            *aux
        ))

    def _jit(self, fn, **kwargs):
        """``jax.jit`` for the loop's own programs. FedAdapterAPI binds its
        frozen base as every program's first operand here."""
        return jax.jit(fn, **kwargs)

    def _unpack_round(self, out):
        """Rounds built with ``with_client_losses`` return a third,
        per-client-loss output (oort's in-round utility observable);
        capture it on the instance so callers keep the 2-tuple
        contract."""
        if len(out) == 3:
            avg, loss, client_losses = out
            self._round_client_losses = client_losses
            return avg, loss
        return out

    def _per_client_eval(self):
        """Cached jitted vmapped eval over a client-stacked layout —
        shared by evaluate_on_clients and pow_d selection (vmapping the
        jit-wrapped eval_fn inline would re-trace the whole N-client pass
        on every call, and two call sites must not hold two executables
        of the same kernel)."""
        fn = getattr(self, "_clients_eval_fn", None)
        if fn is None:
            fn = self._jit(jax.vmap(
                lambda n, x, y, mask: self.eval_fn(n, x, y, mask),
                in_axes=(None, 0, 0, 0)))
            self._clients_eval_fn = fn
        return fn

    def evaluate(self) -> Dict[str, float]:
        if self.test_global is None:
            return {}
        x, y, mask = self.test_global
        m = self.eval_fn(self._eval_net(), x, y, mask)
        return {k: float(v) for k, v in m.items()}

    def evaluate_on_clients(self, arrays=None,
                            prefix: str = "clients_train") -> Dict[str, float]:
        """Per-client evaluation of the current global model on every
        client's LOCAL shard — the reference's
        ``_local_test_on_all_clients`` / ``test_on_server_for_all_clients``
        cadence (fedavg_api.py:117, FedAVGAggregator.py:110-161), which it
        runs as a host-side Python loop over clients each eval round; here
        it is one vmapped on-device pass (SURVEY.md §7 hard part #5).
        Returns the sample-weighted mean plus worst-client stats (the
        quantity fairness methods optimize).

        ``arrays`` defaults to the training shards; pass the per-client
        TEST layout (``to_federated_arrays(fed, bs, split="test")`` — the
        reference's ``test_data_local_dict``) with ``prefix=
        "clients_test"`` for the local-test leg of the reference cadence.
        Clients with no samples are excluded from the worst-client stats.
        """
        f = arrays if arrays is not None else self.train_fed
        if arrays is None and getattr(self, "_streaming", False):
            return self._evaluate_on_clients_streaming(prefix)
        net = self._eval_net()
        m = self._per_client_eval()(net, f.x, f.y, f.mask)
        num = m["num"]
        n = jnp.maximum(jnp.sum(num), 1.0)
        present = num > 0
        worst_acc = jnp.min(jnp.where(present, m["accuracy"], jnp.inf))
        worst_loss = jnp.max(jnp.where(present, m["loss"], -jnp.inf))
        return {
            f"{prefix}_acc": float(jnp.sum(m["accuracy"] * num) / n),
            f"{prefix}_loss": float(jnp.sum(m["loss"] * num) / n),
            f"worst_client_{prefix.split('_')[-1]}_acc": float(worst_acc),
            f"worst_client_{prefix.split('_')[-1]}_loss": float(worst_loss),
        }

    def _evaluate_on_clients_streaming(
            self, prefix: str, chunk: int = 256) -> Dict[str, float]:
        """Store-backed variant of evaluate_on_clients: iterate the client
        population in host-gathered chunks (device holds one chunk at a
        time), accumulating the same weighted-mean + worst-client stats.
        The reference walks all 3400 FEMNIST clients per eval the same
        way, one at a time (FedAVGAggregator.py:117-133)."""
        import numpy as np

        store = self.train_fed
        net = self._eval_net()
        per = self._per_client_eval()
        tot_acc = tot_loss = tot_n = 0.0
        worst_acc, worst_loss = float("inf"), float("-inf")
        for lo in range(0, store.num_clients, chunk):
            idx = np.arange(lo, min(lo + chunk, store.num_clients))
            sub = store.gather_cohort(idx)
            m = per(net, sub.x, sub.y, sub.mask)
            num = np.asarray(m["num"])
            acc = np.asarray(m["accuracy"])
            loss = np.asarray(m["loss"])
            present = num > 0
            tot_acc += float((acc * num).sum())
            tot_loss += float((loss * num).sum())
            tot_n += float(num.sum())
            if present.any():
                worst_acc = min(worst_acc, float(acc[present].min()))
                worst_loss = max(worst_loss, float(loss[present].max()))
        n = max(tot_n, 1.0)
        return {
            f"{prefix}_acc": tot_acc / n,
            f"{prefix}_loss": tot_loss / n,
            f"worst_client_{prefix.split('_')[-1]}_acc": worst_acc,
            f"worst_client_{prefix.split('_')[-1]}_loss": worst_loss,
        }

    def train(self) -> List[Dict[str, float]]:
        history = []
        for round_idx in range(self.cfg.comm_round):
            metrics = self.train_one_round(round_idx)
            if (
                round_idx % self.cfg.frequency_of_the_test == 0
                or round_idx == self.cfg.comm_round - 1
            ):
                metrics.update(self.evaluate())
            history.append(metrics)
        return history
