"""Frozen-base / low-rank-adapter model surgery for federated finetuning.

The cross-device workload that dominates federated learning today —
finetuning a shared transformer on-device — never ships the base model
back: clients train small low-rank (LoRA-style) adapter pairs injected
next to the frozen projections (arXiv:2108.06098's low-rank-update
framing; FedNLP, arXiv:2104.08815) and upload ONLY the adapter delta, so
the wire payload shrinks by the rank ratio BEFORE any codec runs.

This module is the pure seam between "a model with adapters injected"
(``models/transformer.py`` adds ``lora_*`` params next to the scoped
dense projections when built with ``adapter_rank > 0``) and the
federated machinery that should only ever see the adapter tree:

- :func:`split_frozen` / :func:`merge_params` — partition a param tree
  into ``(base, adapters)`` by the ``lora_`` leaf-name convention and
  reassemble it, a lossless bijection (``merge(split(p)) == p``, tested).
- :func:`adapter_model_fns` — a drop-in :class:`~fedml_tpu.trainer.
  local.ModelFns` twin whose ``init`` returns the ADAPTER tree as the
  trainable net (the frozen base is held once on device) and whose
  ``apply`` merges base + adapters per call; ``bind`` makes the base an
  OPERAND of a program instead of its constant. Everything downstream —
  the jitted client step, aggregation, codecs (``tree_spec`` of the
  adapter net), checkpoints, the wire — operates on the adapter tree
  without knowing adapters exist.
- :class:`PersonalAdapterStore` — per-client PERSONALIZED adapter state
  as one ``[N, adapter_dim]`` float32 host array (optionally
  memmap-spilled next to a sharded store), the storage shape that makes
  million-client personalization the problem ``ClientDirectory`` /
  ``ShardedFederatedStore`` already solved: O(clients x adapter_dim)
  bytes, cohort gathers page in only the sampled rows.
"""

from __future__ import annotations

import os
import threading
from typing import Any, Callable, NamedTuple, Optional

import numpy as np

#: The largest frozen base a program may hold as constants: ``apply``
#: outside ``bind`` refuses a larger one, so a jit that misses the operand
#: fails where it is built. The serving plane's and the message-passing
#: tiers' GPT-2-sized bases (under 0.3 GB a program) stay under it.
BAKED_BASE_LIMIT = 1 << 30

#: Leaf-name prefix marking adapter params (models/transformer._lora_delta
#: names every injected pair ``lora_<site>_a`` / ``lora_<site>_b``).
ADAPTER_PREFIX = "lora_"


def is_adapter_name(name) -> bool:
    return isinstance(name, str) and name.startswith(ADAPTER_PREFIX)


def split_frozen(params):
    """Partition a (nested-dict) param tree into ``(base, adapters)`` by
    the ``lora_`` leaf-name convention. Both halves keep their nesting;
    empty sub-dicts are dropped, so ``merge_params`` reassembles the
    exact original tree."""
    base, adapters = {}, {}
    for k, v in params.items():
        if isinstance(v, dict):
            b, a = split_frozen(v)
            if b:
                base[k] = b
            if a:
                adapters[k] = a
        elif is_adapter_name(k):
            adapters[k] = v
        else:
            base[k] = v
    return base, adapters


def merge_params(base, adapters):
    """Inverse of :func:`split_frozen`: reassemble the full param tree.
    A key present as a LEAF in both halves is a structure corruption
    (adapters drifted from the base they were split from) and raises."""
    out = dict(base)
    for k, v in adapters.items():
        cur = out.get(k)
        if isinstance(v, dict) and isinstance(cur, dict):
            out[k] = merge_params(cur, v)
        elif k in out:
            raise ValueError(
                f"adapter/base trees collide at key {k!r}: the adapter "
                "tree was not split from this base")
        else:
            out[k] = v
    return out


def param_count(tree) -> int:
    from fedml_tpu.obs.flops import count_params

    return count_params(tree)


class AdapterFns(NamedTuple):
    """:class:`~fedml_tpu.trainer.local.ModelFns`-compatible functional
    interface over the ADAPTER tree, plus the holder dict ``init``
    populates with the frozen base (``holder["base"]``) — exposed so
    drills can pin the base's bitwise invariance. ``bind(fn)`` is
    ``fn'(base, *args)``: while ``fn`` runs (is traced), ``apply`` merges
    the adapters with THAT base, so a jitted ``fn'`` takes the frozen tree
    as an operand (3.19 G parameters cannot be a program's constants);
    ``base()`` is the base ``apply`` would merge right now."""

    init: Callable
    apply: Callable
    holder: dict
    bind: Callable
    base: Callable


def adapter_model_fns(model, holder: Optional[dict] = None,
                      base_params=None) -> AdapterFns:
    """Build the adapter-level ModelFns for a model injected with
    ``lora_*`` params: ``init(rng, x)`` runs the FULL deterministic init,
    splits off the frozen base into ``holder["base"]`` (device-resident
    once, in the dtypes the model created it in, never re-uploaded or
    donated), and returns a NetState whose ``params`` are the adapter
    tree alone; ``apply`` merges base + adapters per call. Inside
    ``bind(fn)`` the base is the operand ``fn'`` was called with
    (``FedAdapterAPI`` jits every program so); outside it, ``apply`` reads
    ``holder["base"]`` at trace time and jit bakes it into the program
    (the serving plane's and the message-passing tiers' small bases; one
    above ``BAKED_BASE_LIMIT`` is refused there, so a jit that misses the
    operand fails where it is built).

    ``base_params`` swaps a PRETRAINED base in for the fresh init's (the
    finetuning story: a dense-trained checkpoint's params — adapter
    leaves absent since injection leaves base paths unchanged — become
    the frozen base while the adapters still start at the exact-identity
    LoRA init; only they are materialised then). Structure must match the
    split base or ``init`` raises.

    A model's other collections (an expert layer's ``counters``) are no
    part of the base: they ride in the net's ``model_state`` beside the
    adapters, and the round carries and averages them as it does for a
    whole-weights model.

    Raises when the model has NO adapter params (an adapter config
    against a dense model must refuse, not silently train the dense arm)
    or carries ``batch_stats`` (BatchNorm's running statistics belong to
    the "frozen" base's layers and would drift under it — transformers
    here are LayerNorm-only)."""
    import jax

    from fedml_tpu.trainer.local import NetState, model_fns

    full_fns = model_fns(model)
    holder = {} if holder is None else holder

    def init(rng, sample_x) -> "NetState":
        if base_params is None:
            full = full_fns.init(rng, sample_x)
        else:   # shapes only: the base handed in is the one that is held
            full = jax.eval_shape(full_fns.init, rng, sample_x)
        base, adapters = split_frozen(full.params)
        if not jax.tree.leaves(adapters):
            raise ValueError(
                "adapter finetuning needs a model with injected adapter "
                f"params (no '{ADAPTER_PREFIX}*' leaves found) — build it "
                "with adapter_rank > 0 (models/transformer.py)")
        if "batch_stats" in full.model_state:
            raise NotImplementedError(
                "adapter finetuning requires a frozen base without "
                "BatchNorm (its running statistics would drift under the "
                f"frozen layers); got {sorted(full.model_state)}")
        if base_params is not None:
            import jax.numpy as jnp

            want = jax.tree.structure(base)
            got = jax.tree.structure(base_params)
            if want != got:
                raise ValueError(
                    "base_params does not match the model's frozen-base "
                    f"structure: expected {want}, got {got} — pass the "
                    "dense checkpoint's params (adapter leaves excluded)")
            base = jax.tree.map(jnp.asarray, base_params)
            # One program whose only results are the adapters, the full
            # init's bit for bit: the compiler drops the fresh base's draws,
            # so a 6.4 GB base is never held beside a second one.
            adapters, state = jax.jit(lambda r, x: (lambda net: (
                split_frozen(net.params)[1], net.model_state))(
                    full_fns.init(r, x)))(rng, sample_x)
            full = NetState(full.params, state)
        holder["base"] = base
        return NetState(adapters, full.model_state)

    def base():
        return holder.get("operand", holder.get("base"))

    def bind(fn):
        def bound(operand, *args):
            outer = holder.get("operand")
            holder["operand"] = operand
            try:
                return fn(*args)
            finally:
                if outer is None:
                    del holder["operand"]
                else:
                    holder["operand"] = outer

        return bound

    def apply(net: "NetState", x, train=False, rng=None):
        frozen = base()
        if "operand" not in holder:
            baked = sum(a.nbytes for a in jax.tree.leaves(frozen))
            if baked > BAKED_BASE_LIMIT:
                raise ValueError(
                    f"outside bind() a jitted program would hold the frozen "
                    f"base as {baked / 1e9:.2f} GB of constants (the limit "
                    f"is {BAKED_BASE_LIMIT / 1e9:.2f} GB): jit it as "
                    "jax.jit(fns.bind(fn)) and call it with fns.base() first "
                    "(FedAdapterAPI._jit)")
        full = NetState(merge_params(frozen, net.params), net.model_state)
        return full_fns.apply(full, x, train=train, rng=rng)

    return AdapterFns(init=init, apply=apply, holder=holder, bind=bind,
                      base=base)


class PersonalAdapterStore:
    """Per-client personalized adapter state: ONE ``[n_clients, D]``
    float32 host array (``D`` = the flattened adapter dim), optionally
    memmap-spilled to disk so a million-client store costs disk, not
    RSS — the ShardedFederatedStore discipline applied to adapter state.
    Rows are keyed by GLOBAL client id (the ``ClientDirectory``'s id
    space), so the store composes with re-sharded deployments unchanged.

    Never-personalized clients read as the caller-provided default (the
    current global adapters), so a cohort gather always yields usable
    state.

    **Concurrency.** The serving plane (fedml_tpu.serve) gathers request
    rows WHILE the training fleet scatters personalization updates — the
    store's first concurrent reader. All row access is copy-on-read
    under ``self._lock``: ``gather`` copies the cohort slice and its
    ``seen`` mask inside the critical section, so a row is always one
    consistent scatter's bytes (never a torn half-write) and the
    returned array is private to the caller; ``scatter`` and the
    checkpoint surface take the same lock. The lock bounds only the
    memcpy, not the fallback fill or any downstream compute."""

    def __init__(self, n_clients: int, template_params, *,
                 spill_dir: Optional[str] = None):
        from fedml_tpu.comm.codec import tree_to_vector_np
        from fedml_tpu.core.compression import tree_spec

        self.n_clients = int(n_clients)
        self.spec = tree_spec(template_params)
        self.dim = int(sum(self.spec.sizes))
        self.memmapped = spill_dir is not None
        if self.memmapped:
            path = os.path.join(spill_dir, "personal_adapters.npy")
            self._data = np.lib.format.open_memmap(
                path, mode="w+", dtype=np.float32,
                shape=(self.n_clients, self.dim))
        else:
            self._data = np.zeros((self.n_clients, self.dim), np.float32)
        self.seen = np.zeros(self.n_clients, bool)
        self._to_vec = tree_to_vector_np
        self._lock = threading.Lock()

    def nbytes(self) -> int:
        return int(self._data.nbytes)

    def vec_of(self, params) -> np.ndarray:
        return self._to_vec(params)

    def tree_of(self, vec: np.ndarray):
        from fedml_tpu.comm.codec import vector_to_tree_np

        return vector_to_tree_np(np.asarray(vec, np.float32), self.spec)

    def gather(self, idx, default_params) -> np.ndarray:
        """``[k, D]`` personal vectors for the cohort; rows never
        scattered to read as ``default_params`` (the global adapters).
        Copy-on-read under the store lock: the returned array is a
        private snapshot whose rows are each one complete scatter."""
        idx = np.asarray(idx, np.int64)
        with self._lock:
            out = self._data[idx].astype(np.float32, copy=True)
            missing = ~self.seen[idx]
        if missing.any():
            out[missing] = self.vec_of(default_params)[None]
        return out

    def scatter(self, idx, vecs) -> None:
        idx = np.asarray(idx, np.int64)
        vecs = np.asarray(vecs, np.float32)
        with self._lock:
            self._data[idx] = vecs
            self.seen[idx] = True

    # -- checkpoint surface (bit-equal restore is test-pinned) ----------
    def state_dict(self) -> dict:
        with self._lock:
            return {"personal_vecs": np.array(self._data),
                    "personal_seen": np.array(self.seen)}

    def load_state_dict(self, state) -> None:
        vecs = np.asarray(state["personal_vecs"], np.float32)
        if vecs.shape != self._data.shape:
            raise ValueError(
                f"personal adapter checkpoint shape {vecs.shape} does not "
                f"match the store ({self._data.shape}) — different "
                "adapter rank/scope or client count")
        with self._lock:
            self._data[:] = vecs
            self.seen[:] = np.asarray(state["personal_seen"], bool)
