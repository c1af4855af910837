"""FedAdapter — parameter-efficient federated finetuning of a frozen-base
transformer with low-rank (LoRA-style) adapters.

The cross-device LLM scenario the reference predates (ROADMAP item 3;
FedNLP arXiv:2104.08815, low-rank updates arXiv:2108.06098): the base
transformer is FROZEN — initialized once, device-resident once, bitwise-
unchanged across rounds (test-pinned), the first OPERAND of every program
this class jits (never a constant of one, never donated, never copied per
client) — and the federated net IS the adapter tree. Every layer of the
existing machinery then applies unchanged to a model that is smaller by
the rank ratio:

- the jitted client step trains only the adapters (the optimizer inits
  on the adapter tree; gradients never materialize base-param updates),
- aggregation / the fused donated round / the windowed scan / the
  on-device scan all carry the adapter tree (``window_protocol =
  "round"`` with no extra carry — the capability record derives every
  scan tier structurally, PR 13),
- uploads on the message-passing tiers are adapter-only deltas that ride
  the negotiated ``topk+int8`` error-feedback codec path
  (``build_federation_setup`` builds the same adapter-level fns from
  ``cfg.adapter_rank``; the delta capability is negotiated per
  connection — comm/codec.py ``DELTA_OK_KEY``),
- per-client PERSONALIZED adapter state lives host-side in a
  :class:`~fedml_tpu.models.adapter.PersonalAdapterStore` (``[N, D]``
  float32, memmap-spillable) — ditto-style interpolation toward the
  global adapters plus a local finetune, so million-client
  personalization is the storage problem ``ClientDirectory`` /
  ``ShardedFederatedStore`` already solved (PR 7).
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from fedml_tpu.algos.fedavg import FedAvgAPI
from fedml_tpu.trainer.local import NetState, softmax_ce

#: fold_in child reserved for the personalization pass's per-client rng
#: streams (disjoint from the trainer's slot streams, the transform's
#: 0x7F, the corruptor's 0xC0 and ditto's 0xD1770).
_PERSONAL_TAG = 0xADA77


class FedAdapterAPI(FedAvgAPI):
    """FedAvg over the ADAPTER tree of a frozen-base transformer.

    ``model`` must be built with adapters injected (``create_model(
    "transformer_lm", adapter_rank=r, adapter_scope=...)``); the
    constructor refuses dense models loudly instead of silently training
    the dense arm. ``self.net`` is the adapter tree; ``self.base`` the
    frozen base params (never trained, never uploaded, never donated):
    ``_jit`` hands it to every program as its first operand, so the vmap
    over clients sees it unbatched and a 6.4 GB base costs its bytes once.

    Rides fused / windowed / on-device execution day one via
    the derived carry capability record ("round" protocol, no carry).
    Personalization: :meth:`personalize_cohort` runs the ditto-style
    interpolated local finetune for a cohort and persists the result in
    the host-side personal store; :meth:`evaluate_personalized` reports
    the personalized-vs-global quality gap."""

    capability_name = "FedAdapter"
    window_carry = "— (adapter tree is the net; base frozen off-scan)"
    supports_streaming = True
    window_protocol = "round"
    _consumes_adapter_cfg = True

    def __init__(self, model, train_fed, test_global, cfg, mesh=None,
                 loss_fn=softmax_ce, pad_id: int = 0,
                 nan_guard: bool = False, personal_interp: float = 0.5,
                 personal_spill_dir: Optional[str] = None,
                 base_params=None):
        if getattr(cfg, "compute_layout", "none") not in ("none", ""):
            raise NotImplementedError(
                "cfg.compute_layout pads the trainable tree, but the "
                "FedAdapter net is the ADAPTER tree while the compute "
                "runs through the merged full model — the lane-fill "
                "twin cannot apply; run the logical layout")
        if mesh is not None:
            raise NotImplementedError(
                "FedAdapterAPI hands the frozen base to its programs as "
                "one device's operand, which the client-mesh shard_map "
                "round does not replicate; run the single-device vmap "
                "simulator or the message-passing tiers "
                "(cfg.adapter_rank there)")
        if not 0.0 <= personal_interp <= 1.0:
            raise ValueError(
                f"personal_interp must be in [0, 1], got {personal_interp}")
        self._adapter_holder: dict = {}
        #: ``{(k, n, rank, experts): took the kernel}`` for every projection
        #: with a pair that ``ops.lora_linear.tally`` saw while a program of
        #: this class was traced (``experts`` > 0: a grouped product's)
        self._lora_traced: dict = {}
        #: ``{(m, k, n): clients}`` for every grouped product that took its
        #: kernel while a program of this class was traced: the widest
        #: client axis its grid had (``ops.grouped_matmul.note``)
        self._products_traced: dict = {}
        #: Optional PRETRAINED dense params to freeze as the base (the
        #: finetuning story); None = the deterministic fresh init.
        self._base_params = base_params
        super().__init__(model, train_fed, test_global, cfg, mesh=mesh,
                         loss_fn=loss_fn, pad_id=pad_id, nan_guard=nan_guard)
        #: The frozen base params — everything the clients never train.
        #: Pinned bitwise-invariant across rounds by tests.
        self.base = self._adapter_holder["base"]
        from fedml_tpu.models.adapter import param_count
        from fedml_tpu.obs.registry import MetricsRegistry, payload_nbytes

        self._adapter_bytes = payload_nbytes(self.net.params)
        reg = self._adapter_registry = MetricsRegistry()
        reg.gauge("base_bytes_operand").set(payload_nbytes(self.base))
        reg.gauge("adapter_params").set(param_count(self.net.params))
        reg.gauge("experts_held").set(_experts_held(self.base))
        self.personal_interp = float(personal_interp)
        self._personal_spill_dir = personal_spill_dir
        self._personal_store = None
        self._personal_train_jit = None
        self._personal_eval_jit = None

    def _model_fns(self, model):
        from fedml_tpu.models.adapter import adapter_model_fns

        return adapter_model_fns(model, holder=self._adapter_holder,
                                 base_params=self._base_params)

    def _jit(self, fn, donate_argnums=()):
        return _BaseOperand(self.fns, fn, donate_argnums, self._lora_traced,
                            self._products_traced)

    def _lora_sites(self):
        """``(sites, fused)``: the adapter tree's pairs (a stacked leaf is
        one a layer, or one a held expert) whose projection went through
        ``ops.lora_linear`` or a grouped product that notes its pairs when
        this class's programs were traced, and those of them whose shapes
        take ``lora_linear``'s one-pass kernel (``takes_kernel``, decided in
        that trace). Nothing before the first program has run; a pair
        computed any other way (``models/transformer``) is no site."""
        from flax.traverse_util import flatten_dict

        flat = flatten_dict(self.net.params)
        sites = fused = 0
        for path, a in flat.items():
            if not path[-1].endswith("_a"):
                continue
            b = flat[path[:-1] + (path[-1][:-2] + "_b",)]
            shape = (a.shape[-2], b.shape[-1], a.shape[-1])
            # a leaf of stacked experts first, else a stack of layers
            for key in (shape + a.shape[-3:-2], shape + (0,)):
                if key in self._lora_traced:
                    layers = int(np.prod(a.shape[:-2]))
                    sites += layers
                    fused += layers * self._lora_traced[key]
                    break
        return sites, fused

    def _emit_reduce_obs(self, n_rounds: int = 1) -> None:
        """Besides the base class's gauges, what the round just folded: an
        adapter tree for every client of its cohort whose weight was
        positive (a padded slot and a client with no samples upload
        nothing), by the round's own memoized cohort. Host-loop rounds
        only, like ``dispatch_profile``: a windowed span (``n_rounds`` > 1)
        and the on-device scan are not counted. A client that ``nan_guard``
        drops on the device still is: the host does not see it without a
        fence."""
        super()._emit_reduce_obs(n_rounds)
        if n_rounds != 1:
            return
        _, idx, wmask = self._sample_cache
        uploads = np.count_nonzero(
            self._host_counts()[np.asarray(idx)] * np.asarray(wmask))
        self._adapter_registry.counter("adapter_bytes_folded").inc(
            int(uploads) * self._adapter_bytes)

    def _on_client_lr_change(self):
        self._personal_train_jit = None  # bakes in the live optimizer/lr

    # -- introspection ----------------------------------------------------
    def adapter_profile(self) -> Dict[str, float]:
        """The rank-ratio story in numbers: trainable adapter params vs
        the frozen base, the wire-relevant ratio (uploads carry the
        adapter tree only), and the registry's running totals:
        ``base_bytes_operand`` (what every program is handed, never
        copied), ``adapter_bytes_folded`` (what the host-loop rounds'
        clients would have uploaded: the clients whose weight was positive
        x the adapter tree's bytes), and ``lora_sites`` /
        ``experts_held`` (the frozen expert MLPs in the base, all layers),
        ``lora_sites_fused`` (the projections that went through
        ``ops.lora_linear`` when the rounds' programs were traced, and those
        of them whose shapes take its one-pass kernel), and
        ``grouped_products`` / ``grouped_products_client_grid`` (the shapes
        of grouped products that took ``ops.grouped_matmul``'s kernel when
        the rounds' programs were traced, and those of them whose kernel had
        two or more clients on its grid: a ``vmap`` of the clients that
        became ONE call, not a loop over them)."""
        from fedml_tpu.models.adapter import param_count

        sites, fused = self._lora_sites()
        reg = self._adapter_registry
        reg.gauge("lora_sites").set(sites)
        reg.gauge("lora_sites_fused").set(fused)
        products = self._products_traced.values()
        reg.gauge("grouped_products").set(len(products))
        reg.gauge("grouped_products_client_grid").set(
            sum(clients >= 2 for clients in products))
        a = param_count(self.net.params)
        b = param_count(self.base)
        return {"base_params": b, "total_params": a + b,
                "adapter_ratio": a / max(a + b, 1),
                **self._adapter_registry.snapshot()}

    # -- personalization (ditto-style interpolation + local finetune) -----
    def personal_store(self):
        from fedml_tpu.models.adapter import PersonalAdapterStore

        if self._personal_store is None:
            self._personal_store = PersonalAdapterStore(
                self.cfg.client_num_in_total, self.net.params,
                spill_dir=self._personal_spill_dir)
        return self._personal_store

    def _personal_train_fn(self):
        """Cached jitted vmapped local adapter finetune over a cohort —
        the SAME local step the federated round runs (epochs, masking,
        prefix-stable rng streams), vmapped over per-client starting
        adapters."""
        fn = self._personal_train_jit
        if fn is None:
            local_train = self.local_train

            def rounds(nets, x, y, mask, rngs):
                return jax.vmap(local_train)(nets, x, y, mask, rngs)

            fn = self._personal_train_jit = self._jit(rounds)
        return fn

    def personalize_cohort(self, clients, seed: int = 0) -> np.ndarray:
        """One personalization pass for ``clients``: start each client
        from the ditto-style interpolation ``interp * global + (1 -
        interp) * personal`` (never-personalized clients start at the
        global), run the standard local adapter finetune on the client's
        own shard, and persist the trained adapters in the personal
        store. Returns the per-client training losses."""
        store = self.personal_store()
        idx = np.asarray(clients, np.int64)
        lam = self.personal_interp
        gvec = store.vec_of(self.net.params)
        start = (1.0 - lam) * store.gather(idx, self.net.params) + \
            lam * gvec[None]
        sub = _gather_shards(self.train_fed, idx)
        nets = _stack_netstates(
            [NetState(store.tree_of(v), self.net.model_state)
             for v in start])
        base = jax.random.fold_in(jax.random.PRNGKey(self.cfg.seed),
                                  _PERSONAL_TAG)
        base = jax.random.fold_in(base, seed)
        rngs = jnp.stack([jax.random.fold_in(base, int(c)) for c in idx])
        trained, losses = self._personal_train_fn()(
            nets, sub.x, sub.y, sub.mask, rngs)
        trained_np = np.stack(
            [store.vec_of(jax.tree.map(lambda l, i=i: np.asarray(l[i]),
                                       trained.params))
             for i in range(len(idx))])
        store.scatter(idx, trained_np)
        return np.asarray(losses)

    def _personal_eval_fn(self):
        fn = self._personal_eval_jit
        if fn is None:
            fn = self._personal_eval_jit = self._jit(jax.vmap(
                lambda net, x, y, mask: self.eval_fn(net, x, y, mask)))
        return fn

    def evaluate_personalized(self, arrays=None, clients=None,
                              chunk: int = 256) -> Dict[str, float]:
        """Sample-weighted per-client quality of the PERSONALIZED
        adapters vs the global adapters on each client's shard.
        ``arrays`` defaults to the training shards; pass per-client
        HELD-OUT arrays for the honest personalization delta (REPRO.md's
        pin does). Clients never personalized evaluate at the global (their
        stored state IS the global default)."""
        f = arrays if arrays is not None else self.train_fed
        store = self.personal_store()
        per = self._personal_eval_fn()
        n = int(getattr(f, "num_clients", None) or np.asarray(f.x).shape[0])
        ids = (np.asarray(clients, np.int64) if clients is not None
               else np.arange(n, dtype=np.int64))
        tot = {"p_acc": 0.0, "p_loss": 0.0, "g_acc": 0.0, "g_loss": 0.0,
               "n": 0.0}
        for lo in range(0, len(ids), chunk):
            idx = ids[lo:lo + chunk]
            sub = _gather_shards(f, idx)
            vecs = store.gather(idx, self.net.params)
            nets = _stack_netstates(
                [NetState(store.tree_of(v), self.net.model_state)
                 for v in vecs])
            pm = per(nets, sub.x, sub.y, sub.mask)
            gm = self._per_client_eval()(self.net, sub.x, sub.y, sub.mask)
            num = np.asarray(pm["num"])
            tot["p_acc"] += float((np.asarray(pm["accuracy"]) * num).sum())
            tot["p_loss"] += float((np.asarray(pm["loss"]) * num).sum())
            tot["g_acc"] += float((np.asarray(gm["accuracy"]) * num).sum())
            tot["g_loss"] += float((np.asarray(gm["loss"]) * num).sum())
            tot["n"] += float(num.sum())
        n = max(tot["n"], 1.0)
        return {
            "personal_accuracy": tot["p_acc"] / n,
            "personal_loss_eval": tot["p_loss"] / n,
            "global_local_accuracy": tot["g_acc"] / n,
            "global_local_loss": tot["g_loss"] / n,
            "personalized_delta": (tot["p_acc"] - tot["g_acc"]) / n,
        }

    # -- checkpoint/resume: personal adapter stacks are run state ---------
    def checkpoint_extra_state(self):
        extra = dict(super().checkpoint_extra_state())
        # Only persist the personal store if one was ever materialized —
        # personal_store() ALLOCATES the full [N, D] stack (or creates
        # the memmap spill file), which a never-personalized run must
        # not pay at every checkpoint; restore tolerates the absent key.
        if self._personal_store is not None:
            extra.update(self._personal_store.state_dict())
        return extra

    def load_checkpoint_extra_state(self, extra) -> None:
        super().load_checkpoint_extra_state(extra)
        if extra and "personal_vecs" in extra:
            self.personal_store().load_state_dict(extra)


class _BaseOperand:
    """``jax.jit`` of a program of the adapter round, with the frozen base
    bound as its first operand (``AdapterFns.bind``). Called inside another
    such program, it hands on the operand that one was given. A call that
    traces notes in ``traced`` which projections ``ops.lora_linear`` saw
    (``FedAdapterAPI._lora_sites``), and in ``products`` the grouped
    products' shapes with the widest client axis of their kernel's grid."""

    def __init__(self, fns, fn, donate_argnums, traced: dict,
                 products: dict):
        self._base = fns.base
        self._traced = traced
        self._products = products
        self._jitted = jax.jit(
            fns.bind(fn), donate_argnums=tuple(i + 1 for i in donate_argnums))

    def __call__(self, *args):
        from fedml_tpu.ops.grouped_matmul import Traced
        from fedml_tpu.ops.lora_linear import tally

        with tally() as sites:
            out = self._jitted(self._base(), *args)
        for site in sites:
            if isinstance(site, Traced):
                shape = site[:3]
                self._products[shape] = max(site.clients,
                                            self._products.get(shape, 1))
            else:
                _, k, n, rank, fused, experts = site
                self._traced[(k, n, rank, experts)] = fused
        return out

    def lower(self, *args):
        return self._jitted.lower(self._base(), *args)


def _experts_held(base) -> int:
    """Frozen expert MLPs in the base: the leading axis of every stacked
    ``experts_down`` leaf, summed over the layers (0 for a model without)."""
    from flax.traverse_util import flatten_dict

    return int(sum(leaf.shape[-3] for path, leaf in flatten_dict(base).items()
                   if path[-1] == "experts_down"))


def _gather_shards(fed, idx):
    """The cohort's ``[k, S, B, ...]`` shards from either layout: a
    host store (``gather_cohort``) or resident ``FederatedArrays``
    (device gather)."""
    if hasattr(fed, "gather_cohort"):
        return fed.gather_cohort(np.asarray(idx))
    from fedml_tpu.data.batching import gather_clients

    return gather_clients(fed, jnp.asarray(np.asarray(idx)))


def _stack_netstates(nets) -> NetState:
    """[NetState] → one NetState with stacked ``[k, ...]`` leaves (vmap
    layout). Host-side numpy stack — the cohorts here are small."""
    params = jax.tree.map(lambda *ls: jnp.stack(
        [jnp.asarray(l) for l in ls]), *[n.params for n in nets])
    return NetState(params, nets[0].model_state)
