"""FedAvg — the canonical synchronous federated-averaging loop.

Capability parity with both reference implementations:
- standalone simulator ``FedAvgAPI`` (fedml_api/standalone/fedavg/fedavg_api.py:12-116)
- distributed MPI pipeline (fedml_api/distributed/fedavg/FedAvgAPI.py:20 +
  FedAVGAggregator.py + manager classes)

On TPU both collapse into one object: sampled clients are a leading array
axis (vmap on one chip, shard_map over the ``clients`` mesh axis on many),
and the server aggregation is a weighted-mean reduction (psum over ICI).
"""

from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from fedml_tpu.algos.config import FedConfig
from fedml_tpu.algos.loop import FederatedLoop, eval_segments
from fedml_tpu.core.robust_agg import make_aggregator
from fedml_tpu.data.batching import FederatedArrays
from fedml_tpu.obs.sanitizer import planned_transfer
from fedml_tpu.obs.trace import span
from fedml_tpu.parallel.shard import (
    client_axis,
    client_shards,
    make_cohort_gather,
    make_sharded_round,
    make_vmap_round,
    mesh_dcn_axis,
)
from fedml_tpu.trainer.local import (
    make_client_optimizer,
    make_eval_fn,
    make_local_train_fn_from_cfg,
    model_fns,
    softmax_ce,
)


def plan_window_spans(buckets, window: int):
    """Split a run of rounds (given each round's cohort step bucket) into
    execution spans ``(offset, length, steps-or-None)`` covering the
    rounds in order: consecutive chunks of exactly ``window`` rounds
    become scan spans whose shared step bucket is the chunk's MAX bucket
    (every round's cohort fits; smaller rounds get extra masked pad —
    exact training no-ops under the trainer's prefix-stable rng streams,
    see ``trainer.local.make_epoch_shuffle``); the remainder (< window
    rounds) falls to the per-round host loop (``steps=None``).

    Fixing every scan's length at ``window`` and quantizing its step
    shape to the chunk-max power-of-two bucket bounds compilation at one
    scan executable per DISTINCT max bucket — a handful, like the
    per-round path's shape buckets."""
    if window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    spans, n = [], len(buckets)
    lo = 0
    while n - lo >= window:
        spans.append((lo, window, max(buckets[lo:lo + window])))
        lo += window
    if lo < n:
        spans.append((lo, n - lo, None))
    return spans


class FedAvgAPI(FederatedLoop):
    """Federated trainer. ``mesh=None`` → single-device vmap simulator;
    with a mesh, clients are sharded over ``mesh.axis_names[0]`` and a
    device-resident ``train_fed`` is held REPLICATED over the mesh (a
    copy of the caller's; as ``self.net`` is), so each shard takes its
    sampled clients from its own copy inside the round's program. A mesh
    this process cannot fully address (multi-host; read off
    ``mesh.devices``) keeps ``train_fed`` where the caller put it and
    gathers eagerly before the sharded round.

    ``train_fed`` may be a device-resident ``FederatedArrays`` (small
    client counts) or a host-resident ``data.store.FederatedStore``
    (reference-scale client counts — 3,400-writer FEMNIST, 342k-user
    StackOverflow): the store streams only each round's sampled cohort
    to the device, double-buffered against the round's compute."""

    #: Subclasses that read client-stacked arrays outside run_round
    #: (persistent per-client device state, direct gather_clients) set
    #: this False; FedAvgAPI raises at construction instead of failing
    #: deep inside their round.
    supports_streaming = True

    #: Subclasses whose round aggregates WITHIN groups and then ACROSS
    #: group partials (HierarchicalFedAvgAPI) set this True to accept
    #: group-composable robust aggregators (coord_median, trimmed_mean)
    #: through the custom-round guard below — the two-stage statistic is
    #: their documented semantics, not a silent drift. Non-composable
    #: aggregators (krum, geometric_median) are still refused loudly.
    #: The guard reads this from the concrete class's __dict__ — the
    #: opt-in is NOT inherited: a further subclass that re-customizes
    #: the round must re-declare it (or be refused).
    composes_group_aggregation = False

    def __init__(
        self,
        model,
        train_fed: FederatedArrays,
        test_global,  # (x, y, mask) batched [S, B, ...] or None
        cfg: FedConfig,
        mesh=None,
        loss_fn=softmax_ce,
        pad_id: int = 0,
        nan_guard: bool = False,
    ):
        """``pad_id`` marks padding positions in sequence-task labels
        (excluded from eval accuracy); it must match the pad id baked into a
        sequence ``loss_fn`` (e.g. ``partial(seq_softmax_ce, pad_id=...)``).
        Irrelevant for flat classification tasks.

        ``nan_guard``: zero-weight any client whose local training diverged
        to non-finite params (fedml_tpu.core.faults failure containment)."""
        from fedml_tpu.data.store import FederatedStore

        self.cfg = cfg
        self.mesh = mesh
        self.train_fed = train_fed
        self.test_global = test_global
        if getattr(cfg, "adapter_rank", 0) and not self._consumes_adapter_cfg:
            # PR 4 convention: cfg.adapter_rank configures the frozen-
            # base adapter finetune (FedAdapterAPI on the simulator
            # tiers; the message-passing setups read it directly) — on
            # any other class the flag would silently train the DENSE
            # arm while the user believes adapters are on.
            raise NotImplementedError(
                f"cfg.adapter_rank={cfg.adapter_rank} configures frozen-"
                "base adapter finetuning; use FedAdapterAPI (algos/"
                f"fedadapter.py) — on {type(self).__name__} the flag "
                "would be silently inert")
        self.fns = self._model_fns(model)
        self._streaming = isinstance(train_fed, FederatedStore)
        # A resident federation is gathered inside the round's program
        # (None: a streaming store, or a mesh beyond this process).
        self._cohort_gather = None
        if not self._streaming and (mesh is None or all(
                d.process_index == jax.process_index()
                for d in mesh.devices.flat)):
            self._cohort_gather = make_cohort_gather(mesh)
            if mesh is not None:
                self.train_fed = jax.device_put(
                    train_fed, NamedSharding(mesh, PartitionSpec()))
        if self._streaming and not type(self).supports_streaming:
            raise NotImplementedError(
                f"{type(self).__name__} keeps per-client state device-"
                "resident (or gathers clients on device) and does not "
                "support FederatedStore streaming; use the resident "
                "FederatedArrays layout")
        if cfg.batch_size != train_fed.batch_size:
            raise ValueError(
                f"cfg.batch_size={cfg.batch_size} != packed client batch size "
                f"{train_fed.batch_size}; build_federated_arrays with the same "
                "batch_size as the config"
            )

        if getattr(cfg, "wire_codec", "none") not in ("", "none"):
            # PR 4 convention: refuse a flag nothing here reads. The
            # simulator's on-device analogue is cfg.compress; the wire
            # codec belongs to the message-passing tiers.
            raise NotImplementedError(
                f"cfg.wire_codec={cfg.wire_codec!r} is a message-passing-"
                "tier capability (cross-silo / FedAsync / FedBuff, "
                "comm/codec.py); the simulator tiers compress on device "
                "via cfg.compress")
        if getattr(cfg, "ingest_workers", 0):
            # Same convention: the parallel ingest pool unblocks a
            # message-passing server's dispatch thread; the simulator
            # tiers aggregate inside the jitted round and have no such
            # thread to unblock.
            raise NotImplementedError(
                f"cfg.ingest_workers={cfg.ingest_workers} is a message-"
                "passing server capability (cross-silo / FedAsync / "
                "FedBuff, comm/ingest.py); the simulator tiers have no "
                "dispatch thread to parallelize")
        self._loss_fn = loss_fn
        self._nan_guard = nan_guard
        # Byzantine-robust server aggregation (core/robust_agg): resolved
        # once; "mean" keeps the existing weighted-mean reduction
        # bit-equal on every tier. Guards mirror the windowed carry
        # protocol's philosophy — refuse loudly instead of silently
        # keeping a subclass's own aggregation.
        self._aggregator = make_aggregator(getattr(cfg, "aggregator", "mean"))
        if not self._aggregator.is_mean:
            # The opt-in must be declared ON the concrete class itself
            # (__dict__, not inheritance): a subclass of an opted-in
            # class that customizes the round again would otherwise
            # inherit the exemption and silently drop the aggregator —
            # the exact drift the strict branch below exists to refuse.
            if type(self).__dict__.get("composes_group_aggregation", False):
                # The subclass runs the TWO-STAGE (within-group → across-
                # group) aggregation (HierarchicalFedAvgAPI): only group-
                # composable aggregators keep their semantics there.
                if not getattr(self._aggregator, "group_composable", False):
                    raise NotImplementedError(
                        f"cfg.aggregator={cfg.aggregator!r} does not "
                        "compose group-wise (krum needs pairwise client "
                        "distances, geometric_median a joint fixpoint); "
                        f"{type(self).__name__} aggregates within groups "
                        "then across group partials — use a composable "
                        "aggregator (coord_median, trimmed_mean<beta>) "
                        "here, or the flat FedAvg family for the exact "
                        "full-cohort all_gather path")
            else:
                # Capability-record facts: a custom round, custom round
                # BUILDERS, or a custom fused step (SCAFFOLD/FedDyn's
                # stateful one-dispatch rounds) all mean the aggregation
                # is not the shared builders' — the flag would silently
                # keep the algorithm's own reduction.
                rec = self.capability()
                if rec.custom_round or rec.custom_builders or rec.custom_step:
                    raise NotImplementedError(
                        f"{type(self).__name__} customizes the round or its "
                        f"aggregation; cfg.aggregator={cfg.aggregator!r} only "
                        "rides the FedAvg family's shared round builders (a "
                        "custom round would silently keep its own "
                        "aggregation)")
        self._group_reduce = bool(getattr(cfg, "group_reduce", False))
        if self._group_reduce:
            if mesh is None:
                raise NotImplementedError(
                    "cfg.group_reduce shrinks the client-mesh collective "
                    "(shard-local partials + a G-sized gather); on a "
                    "single device there are no shards to group — drop "
                    "the flag, or use HierarchicalFedAvgAPI for host-side "
                    "grouping")
            if not self._aggregator.is_mean and not getattr(
                    self._aggregator, "group_composable", False):
                raise NotImplementedError(
                    f"cfg.aggregator={cfg.aggregator!r} does not compose "
                    "group-wise; set group_reduce=False to keep the exact "
                    "full client-stack all_gather path (krum, "
                    "geometric_median), or pick a composable aggregator "
                    "(mean, coord_median, trimmed_mean<beta>)")
        if (getattr(cfg, "corrupt_mode", "none") != "none"
                and type(self)._corruptor is FedAvgAPI._corruptor):
            raise NotImplementedError(
                f"cfg.corrupt_mode={cfg.corrupt_mode!r} drives the device-"
                "side corruption drill, which needs adversary wiring "
                "(per-round adversary masks); use FedAvgRobustAPI — on "
                f"{type(self).__name__} the flag would be silently inert")
        self.n_shards = client_shards(mesh)
        # Clients trained at a time (0: the whole cohort, today's round).
        self._client_group = int(getattr(cfg, "client_group_size", 0) or 0)
        if self._client_group:
            self._check_client_group()
        # Pod-scale reduction observability (docs/OBSERVABILITY.md): on
        # a DCN×ICI mesh the O(G)-inter-host-traffic claim is an
        # OBSERVABLE — per-round ctrl/ gauges of how many model-sized
        # partials cross the DCN axis — not a comment. 0 = flat mesh /
        # single device (no emission, no registry).
        d = mesh_dcn_axis(mesh)
        self._dcn_groups = int(mesh.shape[d]) if d else 0
        sample_x = (train_fed.example_input() if self._streaming
                    else np.asarray(train_fed.x[0, 0]))
        # Hook for models whose init input is NOT a data batch (FedGAN's
        # generator initializes from latent noise). Default: identity.
        sample_x = self._net_init_input(sample_x)
        # Lane-fill compute layout (parallel/layout.py): the jitted
        # client step trains a lane-PADDED physical twin; everything
        # above the step — self.net, aggregation, checkpoints, the wire
        # — keeps the logical shapes. Resolved before the round builders
        # so _build_local_train can wrap the trainer.
        self._layout = None
        layout_cfg = getattr(cfg, "compute_layout", "none") or "none"
        if layout_cfg != "none":
            if layout_cfg not in ("auto", "im2col"):
                raise ValueError(
                    f"cfg.compute_layout={layout_cfg!r}: expected "
                    "'none', 'auto' or 'im2col'")
            if type(self)._build_local_train \
                    is not FedAvgAPI._build_local_train:
                raise NotImplementedError(
                    f"{type(self).__name__} builds its own local trainer; "
                    "cfg.compute_layout wraps the shared "
                    "_build_local_train only (the flag would otherwise "
                    "be silently inert)")
            if getattr(cfg, "dp_noise_multiplier", 0.0) > 0:
                # Same failure mode layout.py refuses dropout for: the
                # DP Gaussian draw's shapes follow the PHYSICAL layout
                # (per-parameter noise over padded leaves), so the
                # logical block gets different noise than a layout-off
                # run AND nonzero noise lands in the pad channels,
                # breaking the pad-stays-zero exactness invariant.
                # (dp_clip alone is exact: padded per-example grads are
                # zero, so clip norms are unchanged.)
                raise NotImplementedError(
                    "cfg.compute_layout cannot compose with DP noise "
                    "(dp_noise_multiplier > 0): the per-parameter noise "
                    "draw shapes follow the physical layout, which "
                    "breaks the padded-vs-logical exactness contract — "
                    "run DP-SGD at the logical layout")
            from fedml_tpu.parallel.layout import (compute_layout,
                                                   im2col_layout)

            layout = (im2col_layout(model, sample_x)
                      if layout_cfg == "im2col"
                      else compute_layout(model, sample_x))
            if not layout.is_identity:
                self._layout = layout
                self._phys_fns = model_fns(layout.physical_model)
        # bf16 client-step compute (parallel/layout.step_dtype_model):
        # the TRAINER's apply computes in bf16; params/grads/optimizer/
        # aggregation/eval all stay fp32. Resolved before set_client_lr
        # so _build_local_train sees it.
        self._step_dtype = None
        sd = getattr(cfg, "client_step_dtype", "fp32") or "fp32"
        if sd not in ("fp32", "bf16"):
            raise ValueError(
                f"cfg.client_step_dtype={sd!r}: expected 'fp32' or 'bf16'")
        if sd == "bf16":
            if type(self)._build_local_train \
                    is not FedAvgAPI._build_local_train:
                raise NotImplementedError(
                    f"{type(self).__name__} builds its own local trainer; "
                    "cfg.client_step_dtype wraps the shared "
                    "_build_local_train only (the flag would otherwise "
                    "be silently inert)")
            from fedml_tpu.parallel.layout import step_dtype_model

            # Refusal happens here (construction), not first trace: the
            # twin builder raises for families without a compute-dtype
            # field. Composed with the layout: the PHYSICAL twin is the
            # one the trainer applies, so it is the one cloned to bf16.
            base = (self._layout.physical_model if self._layout is not None
                    else model)
            self._step_fns = self._model_fns(
                step_dtype_model(base, jnp.bfloat16))
            self._step_dtype = jnp.bfloat16
        self._client_lr = None
        self._fused_step_fn = None
        self.set_client_lr(cfg.lr)
        self.eval_fn = self._jit(make_eval_fn(self.fns.apply, loss_fn, pad_id=pad_id))

        rng = jax.random.PRNGKey(cfg.seed)
        self.rng, init_rng = jax.random.split(rng)
        self.net = self.fns.init(init_rng, sample_x)
        if mesh is not None:
            # The sharded round returns the model replicated over the
            # mesh. Start it there: a round 0 fed from one device and a
            # round 1 fed the replicated result are two input shardings,
            # and the round compiled twice (chip_smoke.py multi_device).
            self.net = jax.device_put(
                self.net, NamedSharding(mesh, PartitionSpec()))

        if cfg.client_selection == "oort":
            rec = self.capability()
            if (rec.custom_round or rec.custom_step
                    or self.window_protocol != "round"):
                # The utility-update hook lives in FedAvgAPI's round; a
                # custom round/step that skips it would silently
                # degenerate oort to pure exploration (= uniform
                # sampling).
                raise NotImplementedError(
                    f"{type(self).__name__} runs a custom round (capability "
                    "record) and would skip oort's per-round utility "
                    "update; oort serves the FedAvg family's shared round "
                    "only")
            # Eager init: the checkpoint template must match the saved
            # structure (lazy init would save oort state but restore
            # against an empty template).
            n = cfg.client_num_in_total
            self._oort_utility = np.zeros(n, np.float64)
            self._oort_last = np.full(n, -1, np.int64)

    def set_client_lr(self, lr: float):
        """(Re)build the jitted round for a new client learning rate —
        the hook the round-level LR schedulers use (fed_launch
        schedulers decay the client LR across comm rounds). A no-op when
        the lr is unchanged; each distinct lr value costs one re-jit, so
        schedulers should quantize to a few buckets."""
        if lr == self._client_lr:
            return
        self._client_lr = lr
        self._rounds_scan_fn = None  # round_fn changes → cached scan stale
        self._window_scan_fn = None  # windowed scan rides round_fn too
        self._fused_step_fn = None  # fused round step rides round_fn too
        self._size_group_fns = None  # ... and the size-grouped round's steps
        self._on_client_lr_change()  # subclasses drop their own cached jits
        cfg, mesh = self.cfg, self.mesh
        optimizer = make_client_optimizer(
            cfg.client_optimizer, lr, cfg.wd, cfg.grad_clip
        )
        self.local_train = self._build_local_train(optimizer, self._loss_fn)
        transform = self._client_transform()
        guard = self._nan_guard
        if mesh is None:
            round_fn = self._make_vmap_round(
                self.local_train, transform, guard
            )
        else:
            # The sampled set is padded to the CLIENT axis size only (a
            # 2-D mesh's model axis does not multiply the client shards).
            round_fn = self._make_sharded_round(
                self.local_train, mesh, transform, guard
            )
        gather = self._cohort_gather
        if gather is not None and self._corruptor() is None:
            # Resident federation: the client gather is traced into the
            # round's program (FederatedArrays is a struct.dataclass
            # pytree, so it passes straight through jit). Dispatching the
            # takes eagerly costs ~40% of the round wall-clock on one chip
            # (4 un-jitted device ops + host sync per round) and, on a
            # mesh, half of it: one device gathers for all while the
            # others wait. (The streaming store gathers on HOST — its
            # cohort arrives pre-gathered. The corruption drill's rounds
            # take a trailing per-round adversary-mask operand run_round
            # computes host-side; the gather-inside-jit round has no slot
            # for it, so drilled rounds use the plain round_fn path.)
            def fused(net, fed, idx, wmask, rng):
                sub = gather(fed, idx)
                w = sub.counts.astype(jnp.float32) * wmask
                return round_fn(net, sub.x, sub.y, sub.mask, w, w, rng)

            self.round_fn_fused = self._jit(fused)
        self.round_fn = self._jit(round_fn)

    # --- hooks subclasses override (FedOpt/FedProx/...) -------------------
    #: Set True by the one subclass that READS cfg.adapter_rank
    #: (FedAdapterAPI); everyone else refuses the flag at construction.
    _consumes_adapter_cfg = False

    def _model_fns(self, model):
        """The functional model interface every round/eval builder uses.
        FedAdapterAPI overrides this to return the adapter-level fns
        (``init`` → the trainable ADAPTER tree, ``apply`` → frozen base
        merged with the adapters per call), so the whole FedAvg
        machinery — aggregation, codecs, checkpoints, the scan tiers —
        operates on the adapter tree without modification."""
        return model_fns(model)

    def _net_init_input(self, sample_x):
        """The array handed to ``fns.init`` (and the compute layout).
        Defaults to a sample data batch; models initialized from a
        different input shape (FedGAN's latent noise) override this."""
        return sample_x

    def _on_client_lr_change(self):
        """Called whenever the client lr actually changes (lr schedules).
        Subclasses holding their OWN lr-dependent jitted functions (Ditto's
        personal trainer, SCAFFOLD's corrected round) invalidate them here
        — forgetting this is how a subclass silently trains at a stale lr
        under --lr_schedule."""

    def _check_client_group(self) -> None:
        """``cfg.client_group_size`` rides the shared round builders and
        the weighted mean only: refuse what would silently ignore it (a
        class with a round, builders or step of its own) or needs every
        trained client at once."""
        from fedml_tpu.parallel.shard import whole_stack_needed

        k, cfg = self._client_group, self.cfg
        rec = self.capability()
        if k < 0 or rec.custom_round or rec.custom_builders \
                or rec.custom_step:
            raise NotImplementedError(
                f"cfg.client_group_size={k}: {type(self).__name__} "
                "customizes the round, its builders or its step; grouped "
                "client training rides the FedAvg family's shared round "
                "builders only (the field would be silently inert)")
        cohort = min(cfg.client_num_per_round, cfg.client_num_in_total)
        per_shard = -(-cohort // self.n_shards)
        if per_shard % k:
            raise ValueError(
                f"cfg.client_group_size={k} does not divide the {per_shard} "
                f"clients a round trains on each of {self.n_shards} "
                "shard(s)")
        if k != per_shard:
            whole_stack_needed(
                k, aggregator=self._round_aggregator(),
                client_transform=self._client_transform(),
                corruptor=self._corruptor())

    def _make_vmap_round(self, local_train, transform, guard):
        """Single-device round construction; q-FedAvg swaps in a
        loss-reweighted aggregation here. Under oort selection the round
        additionally returns the per-client training losses (the
        utility observable, Lai et al. §5) — run_round captures them so
        no post-round eval pass is needed."""
        return make_vmap_round(
            local_train, client_transform=transform, nan_guard=guard,
            with_client_losses=self.cfg.client_selection == "oort",
            aggregator=self._round_aggregator(),
            corruptor=self._corruptor(), group=self._client_group)

    def _make_sharded_round(self, local_train, mesh, transform, guard):
        return make_sharded_round(
            local_train, mesh, client_axis(mesh),
            client_transform=transform, nan_guard=guard,
            with_client_losses=self.cfg.client_selection == "oort",
            aggregator=self._round_aggregator(),
            corruptor=self._corruptor(),
            group_reduce=self._group_reduce, group=self._client_group)

    def _round_aggregator(self):
        """The aggregator handed to the round builders: ``None`` for mean
        (the builders' weighted-mean fast path — per-shard partial sums +
        psum on a mesh — stays byte-for-byte the compiled program it was
        before the protocol existed), the resolved ``core.robust_agg``
        callable otherwise."""
        return None if self._aggregator.is_mean else self._aggregator

    def _corruptor(self):
        """Device-side update-corruption hook for the attack drill
        (``None`` = no corruption; rounds keep their 7-operand
        signature). FedAvgRobustAPI builds
        ``UpdateCorruptor.device_fn()`` from ``cfg.corrupt_mode`` and
        supplies the per-round adversary masks via ``_round_aux`` /
        ``_window_scan_extras``."""
        return None

    def _build_local_train(self, optimizer, loss_fn):
        # bf16 client step: the trainer applies the compute-dtype twin
        # (of the physical model when a layout is active — the two
        # levers compose); everything else in this method is unchanged
        # because the twin's PARAM TREE is the fp32 one.
        apply = (self._step_fns.apply if self._step_dtype is not None
                 else None)
        if self._layout is not None:
            # Lane-fill layout: the trainer runs the PHYSICAL twin's
            # apply; the wrapper pads the incoming logical net and
            # slices the logical block back out, so every caller of
            # local_train (vmap round, sharded round, window scan) keeps
            # the logical-shape contract untouched.
            from fedml_tpu.parallel.layout import wrap_local_train

            inner = make_local_train_fn_from_cfg(
                apply or self._phys_fns.apply, optimizer, self.cfg,
                loss_fn)
            return wrap_local_train(inner, self._layout)
        return make_local_train_fn_from_cfg(apply or self.fns.apply,
                                            optimizer, self.cfg, loss_fn)

    def _server_update(self, old_net, avg_net):
        """FedAvg: the new global model is the client average."""
        return avg_net

    def _client_transform(self):
        """Optional ``(global_net, client_net) -> client_net`` applied to
        each trained client before averaging (robust clipping etc.). The
        base builds the simulated-compression transform from
        ``cfg.compress``; subclasses that replace this hook (robust
        clipping) must reject ``cfg.compress`` rather than drop it."""
        return self._compress_transform()

    def _compress_transform(self):
        """``cfg.compress`` → on-device transform applied to each
        client's delta before aggregation (simulates communication-
        constrained FL inside the jitted round): ``"topk<r>"``
        sparsifies to the top-k entries; ``"q<bits>"`` runs QSGD-style
        stochastic uniform quantization (unbiased — the per-client rng
        stream arrives via run_clients_guarded's 3-arg transform form).
        Error feedback lives on the cross-silo wire path, which carries
        state between rounds."""
        name = self.cfg.compress or "none"
        if name == "none":
            return None
        from fedml_tpu.core.compression import (
            dequantize,
            quantize_stochastic,
            topk_compress,
            topk_decompress,
            tree_spec,
            tree_to_vector,
            vector_to_tree,
        )
        from fedml_tpu.trainer.local import NetState

        if name.startswith("topk"):
            try:
                ratio = float(name[len("topk"):])
            except ValueError:
                raise ValueError(
                    f"cfg.compress={name!r}: expected 'topk<ratio>' with a "
                    f"numeric ratio, e.g. 'topk0.05'") from None
            if not 0 < ratio <= 1:
                raise ValueError(f"topk ratio must be in (0, 1], got {ratio}")

            def transform(global_net, client_net):
                gvec = tree_to_vector(global_net.params)
                delta = tree_to_vector(client_net.params) - gvec
                k = max(1, int(round(ratio * delta.shape[0])))
                values, idx, _ = topk_compress(delta, k)
                recon = topk_decompress(values, idx, delta.shape[0])
                params = vector_to_tree(gvec + recon,
                                        tree_spec(global_net.params))
                return NetState(params, client_net.model_state)

            return transform
        if name.startswith("q"):
            try:
                bits = int(name[1:])
            except ValueError:
                raise ValueError(
                    f"cfg.compress={name!r}: expected 'q<bits>', e.g. "
                    f"'q8'") from None
            from fedml_tpu.core.compression import _check_bits

            _check_bits(bits)  # fail at construction, not first-round trace

            def transform(global_net, client_net, rng):
                gvec = tree_to_vector(global_net.params)
                delta = tree_to_vector(client_net.params) - gvec
                q, scale = quantize_stochastic(delta, bits, rng)
                params = vector_to_tree(gvec + dequantize(q, scale),
                                        tree_spec(global_net.params))
                return NetState(params, client_net.model_state)

            transform.wants_rng = True  # run_clients_guarded's 3-arg form
            return transform
        raise ValueError(
            f"cfg.compress={name!r}: simulator rounds support "
            "'topk<ratio>' or 'q<bits>'")

    # ----------------------------------------------------------------------
    # sample_round/run_round come from FederatedLoop (shared scaffold).

    def sample_round(self, round_idx: int):
        """Adds Power-of-Choice selection (cfg.client_selection="pow_d",
        Cho et al. 2020) on top of the inherited uniform sampling: draw d
        candidates uniformly, evaluate the current global on their local
        shards (one vmapped pass), keep the highest-loss
        ``client_num_per_round``.

        The result is memoized per round: pow_d depends on the CURRENT
        net, so a subclass that samples again mid-round (Ditto's personal
        step runs after the global update) must see the same set the
        global round trained — recomputing would silently select a
        different cohort."""
        cached = getattr(self, "_sample_cache", None)
        if cached is not None and cached[0] == round_idx:
            return cached[1], cached[2]
        idx, wmask = self._sample_round_uncached(round_idx)
        self._sample_cache = (round_idx, idx, wmask)
        return idx, wmask

    def _sample_round_uncached(self, round_idx: int):
        if self.cfg.client_selection == "random":
            return super().sample_round(round_idx)
        if self.cfg.client_selection == "oort":
            return self._sample_oort(round_idx)
        if self.cfg.client_selection != "pow_d":
            raise ValueError(
                f"unknown client_selection {self.cfg.client_selection!r}; "
                "use 'random', 'pow_d' or 'oort'")
        from fedml_tpu.core.sampling import (
            pad_to_multiple,
            sample_clients_weighted,
        )

        cfg = self.cfg
        d = cfg.pow_d_candidates or 2 * cfg.client_num_per_round
        d = min(d, cfg.client_num_in_total)
        m = min(cfg.client_num_per_round, cfg.client_num_in_total)
        if d < m:
            raise ValueError(
                f"pow_d needs at least client_num_per_round candidates "
                f"(d={d} < m={m}); raise --pow_d_candidates")
        # Cho et al. 2020 draw the candidate set proportional to client
        # data fraction, not uniformly (matters on power-law partitions).
        # A sharded store's ClientDirectory serves the same draw from its
        # count metadata (identical stream — it delegates here).
        directory = getattr(self.train_fed, "directory", None)
        if directory is not None \
                and directory.num_clients == cfg.client_num_in_total:
            candidates = directory.sample_cohort_weighted(round_idx, d)
        else:
            candidates = sample_clients_weighted(
                round_idx, cfg.client_num_in_total, d, self.train_fed.counts)
        if self._streaming:
            # Store path: host-gather the candidate cohort, one vmapped
            # eval pass (same kernel the resident path jits the gather
            # into). d is small (~2x clients/round), so the extra H2D is
            # one cohort's worth.
            sub = self.train_fed.gather_cohort(candidates)
            losses = np.asarray(self._per_client_eval()(
                self._eval_net(), sub.x, sub.y, sub.mask)["loss"])
            order = np.argsort(-losses, kind="stable")[:m]
            idx = candidates[np.sort(order)]
            return pad_to_multiple(idx, self.n_shards)
        losses = self._cohort_losses_resident(candidates)
        order = np.argsort(-losses, kind="stable")[:m]
        idx = candidates[np.sort(order)]
        idx, wmask = pad_to_multiple(idx, self.n_shards)
        return idx, wmask

    def _stream_cohort(self, round_idx: int, idx, group: int = 0):
        """Fetch the round's cohort from the host store (prefetched when
        possible) and kick off the NEXT round's gather + H2D transfer so
        it overlaps this round's compute. Only seeded-random selection can
        prefetch — pow_d depends on the current net. ``group`` > 0 (the
        size-grouped round) fetches and prefetches the cohort as its size
        groups (``FederatedStore.gather_groups``)."""
        from fedml_tpu.data.store import CohortPrefetcher

        pf = getattr(self, "_cohort_prefetcher", None)
        if pf is None:
            pf = self._cohort_prefetcher = CohortPrefetcher(self.train_fed)
        sub = pf.get(round_idx, idx, group)
        if not group:
            # Post-round consumers (oort's utility eval) reuse this instead
            # of paying a second synchronous host gather of the same cohort.
            self._stream_last = (round_idx, np.asarray(idx), sub)
        if (self.cfg.client_selection == "random"
                and round_idx + 1 < self.cfg.comm_round):
            from fedml_tpu.core.sampling import pad_to_multiple, sample_clients

            nidx, _ = pad_to_multiple(
                sample_clients(round_idx + 1, self.cfg.client_num_in_total,
                               self.cfg.client_num_per_round),
                self.n_shards)
            pf.prefetch(round_idx + 1, nidx, group)
        return sub

    # --- Oort utility-based selection (Lai et al., OSDI'21) --------------
    def _sample_oort(self, round_idx: int):
        """Epsilon-greedy utility selection. Exploit: the highest-utility
        previously-seen clients, utility = observed loss x sqrt(n_i)
        (Oort's statistical utility) + staleness bonus
        ``oort_staleness_coef * sqrt(rounds since last seen)``. Explore:
        a seeded-uniform draw over never-seen clients. Utilities update
        from each trained cohort's IN-ROUND training losses, captured
        from the jitted round's outputs
        (:meth:`_update_oort_state`), so the very first rounds are pure
        exploration. Exploration is SUSTAINED (Oort §4's epsilon-greedy):
        once every client has been seen, the epsilon slice is drawn
        uniformly from seen-but-not-exploited clients rather than silently
        dropping to zero. Deterministic given round index and history."""
        from fedml_tpu.core.sampling import pad_to_multiple

        cfg = self.cfg
        n = cfg.client_num_in_total
        k = min(cfg.client_num_per_round, n)
        seen = self._oort_last >= 0
        rs = np.random.RandomState(round_idx)

        n_exploit = min(k - int(np.ceil(cfg.oort_epsilon * k)),
                        int(seen.sum()))
        n_explore = k - n_exploit  # epsilon slice + any exploit shortfall

        chosen = []
        if n_exploit:
            staleness = np.sqrt(np.maximum(round_idx - self._oort_last, 0))
            score = np.where(
                seen,
                self._oort_utility + cfg.oort_staleness_coef * staleness,
                -np.inf)
            chosen.append(np.argsort(-score, kind="stable")[:n_exploit])
        if n_explore:
            # Never-seen clients first; when they run short (everyone —
            # or nearly everyone — already seen) the remainder comes
            # uniformly from seen clients outside the exploit set, so the
            # epsilon fraction of each cohort keeps exploring forever.
            unseen_pool = np.flatnonzero(~seen)
            take_unseen = min(len(unseen_pool), n_explore)
            if take_unseen:
                chosen.append(rs.choice(unseen_pool, take_unseen,
                                        replace=False))
            rest = n_explore - take_unseen
            if rest:
                exploited = (chosen[0] if n_exploit
                             else np.array([], np.int64))
                pool = np.setdiff1d(np.flatnonzero(seen), exploited)
                chosen.append(rs.choice(pool, rest, replace=False))
        idx = np.sort(np.concatenate(chosen).astype(np.int32))
        return pad_to_multiple(idx, self.n_shards)

    def _update_oort_state(self, round_idx: int, idx, wmask) -> None:
        """Refresh utilities for the just-trained cohort from the
        IN-ROUND training losses (Lai et al. §5's exact observable): the
        round is built with ``with_client_losses`` under oort, so
        ``run_round`` captured each client's local training loss and no
        extra eval pass runs. Fallback for subclasses whose custom round
        doesn't expose per-client losses (q-FedAvg's fair round): one
        vmapped eval of the new global on the cohort's shards — the
        documented r2 proxy. Updates mask padded slots out either way."""
        idx = np.asarray(idx)
        active_mask = np.asarray(wmask) > 0
        captured = getattr(self, "_round_client_losses", None)
        if captured is not None:
            self._round_client_losses = None  # one round's observable
            losses = np.asarray(captured, np.float64)
            # A diverged client (nan_guard off) must not write NaN into
            # its utility: argsort ranks NaN last forever, silently
            # blacklisting the client from exploitation. Zero matches the
            # nan_guard convention (deprioritized, staleness bonus still
            # recovers it).
            losses = np.where(np.isfinite(losses), losses, 0.0)
        elif self._streaming:
            cached = getattr(self, "_stream_last", None)
            if cached is not None and cached[0] == round_idx and \
                    np.array_equal(cached[1], idx):
                sub = cached[2]
            else:
                sub = self.train_fed.gather_cohort(idx)
            losses = np.asarray(self._per_client_eval()(
                self._eval_net(), sub.x, sub.y, sub.mask)["loss"], np.float64)
        else:
            losses = self._cohort_losses_resident(idx).astype(np.float64)
        counts = self._host_counts()[idx].astype(np.float64)
        util = losses * np.sqrt(np.maximum(counts, 1))
        active = idx[active_mask]
        self._oort_utility[active] = util[active_mask]
        self._oort_last[active] = round_idx

    def _host_counts(self) -> np.ndarray:
        """Per-client sample counts as host numpy (fetched once)."""
        c = getattr(self, "_host_counts_np", None)
        if c is None:
            c = self._host_counts_np = np.asarray(self.train_fed.counts)
        return c

    def _cohort_losses_resident(self, idx) -> np.ndarray:
        """Per-client loss of the current net on a resident-layout cohort
        — gather traced INSIDE the jit (an eager gather would pay the
        multi-dispatch host sync the fused round path exists to avoid).
        Shared by pow_d candidate scoring and oort utility updates."""
        from fedml_tpu.data.batching import gather_clients

        fn = getattr(self, "_cohort_losses_jit", None)
        if fn is None:
            per_client = self._per_client_eval()  # shared cached kernel

            def losses_fn(net, fed, idx):
                sub = gather_clients(fed, idx)
                return per_client(net, sub.x, sub.y, sub.mask)["loss"]

            fn = self._jit(losses_fn)
            self._cohort_losses_jit = fn
        return np.asarray(fn(self._eval_net(), self.train_fed,
                             jnp.asarray(idx)))

    # -- checkpoint/resume: oort utilities are run state ------------------
    def checkpoint_extra_state(self):
        if self.cfg.client_selection == "oort":
            return {"oort_utility": self._oort_utility,
                    "oort_last": self._oort_last}
        return {}

    def load_checkpoint_extra_state(self, extra) -> None:
        if extra and "oort_utility" in extra:
            self._oort_utility = np.asarray(extra["oort_utility"])
            self._oort_last = np.asarray(extra["oort_last"])

    def _require_plain_sgd_round(self, what: str) -> None:
        """Shared constructor guard for corrected-SGD algorithms
        (SCAFFOLD, FedDyn): their dedicated local steps implement plain
        SGD plus the correction, so cfg knobs the generic trainer honors
        must be rejected loudly instead of silently dropped."""
        if self.cfg.client_optimizer != "sgd":
            raise ValueError(
                f"{what} applies to plain SGD local steps; got "
                f"client_optimizer={self.cfg.client_optimizer!r}")
        unsupported = {
            "grad_clip": self.cfg.grad_clip,
            "dp_clip": self.cfg.dp_clip,
            "dp_noise_multiplier": self.cfg.dp_noise_multiplier,
            "compress": (self.cfg.compress
                         if self.cfg.compress != "none" else None),
            # The corrected-SGD algorithms build their trainers outside
            # _build_local_train, where the lane-fill layout and the
            # bf16 step dtype are wired.
            "compute_layout": (
                getattr(self.cfg, "compute_layout", "none")
                if getattr(self.cfg, "compute_layout", "none") != "none"
                else None),
            "client_step_dtype": (
                getattr(self.cfg, "client_step_dtype", "fp32")
                if getattr(self.cfg, "client_step_dtype", "fp32")
                not in ("fp32", "") else None),
        }
        bad = [k for k, v in unsupported.items() if v]
        if self._nan_guard:
            bad.append("nan_guard")
        if bad:
            raise ValueError(
                f"{what} does not support: " + ", ".join(bad))

    def _cohort(self, round_idx: int, idx):
        """The round's sampled clients as a ``FederatedArrays``: device
        gather on the resident layout, host gather (double-buffered) on
        the streaming store. Subclasses that materialize the cohort
        themselves (FedNova's τ algebra, TurboAggregate's MPC) go through
        this so they stream for free."""
        if self._streaming:
            return self._stream_cohort(round_idx, idx)
        from fedml_tpu.data.batching import gather_clients

        return gather_clients(self.train_fed, jnp.asarray(idx))

    # --- pod-reduce observability (DCN×ICI mesh only) --------------------
    def _emit_reduce_obs(self, n_rounds: int = 1) -> None:
        """Per-round ``ctrl/`` gauges for the inter-host reduction: how
        many model-sized partials crossed the DCN axis this round
        (``dcn_partials``) and the byte payload they carry
        (``dcn_partials × payload_nbytes``). With ``group_reduce`` (or
        the mean fast path, which is hierarchical by construction) the
        partial count is G = n_hosts — INDEPENDENT of the cohort size;
        the flat non-mean ``all_gather`` fallback ships the whole padded
        cohort, C partials. ``dcn_flat_bytes_per_round`` is the flat
        fallback's cost for the same round — the ruler the O(G) claim is
        measured against. Also mirrors the numbers onto the active
        ``SpanTracer`` as a ``reduce.dcn`` instant event (null-tracer
        cheap when tracing is off)."""
        if not self._dcn_groups:
            return
        reg = getattr(self, "_reduce_registry", None)
        if reg is None:
            from fedml_tpu.obs.registry import (MetricsRegistry,
                                                payload_nbytes)

            reg = self._reduce_registry = MetricsRegistry()
            self._reduce_payload = payload_nbytes(self.net)
            self._g_dcn_parts = reg.gauge("dcn_partials")
            self._g_dcn_bytes = reg.gauge("dcn_bytes_per_round")
            self._g_dcn_flat = reg.gauge("dcn_flat_bytes_per_round")
            self._c_dcn_rounds = reg.counter("dcn_rounds")
        grouped = (self._aggregator.is_mean or self._group_reduce)
        cpr = min(self.cfg.client_num_per_round,
                  self.cfg.client_num_in_total)
        flat_parts = -(-cpr // self.n_shards) * self.n_shards  # padded C
        parts = self._dcn_groups if grouped else flat_parts
        self._g_dcn_parts.set(parts)
        self._g_dcn_bytes.set(parts * self._reduce_payload)
        self._g_dcn_flat.set(flat_parts * self._reduce_payload)
        self._c_dcn_rounds.inc(n_rounds)
        from fedml_tpu.obs import trace as obs_trace

        obs_trace.active().instant(
            "reduce.dcn", cat="reduce", partials=parts,
            nbytes=parts * self._reduce_payload, groups=self._dcn_groups,
            rounds=n_rounds)

    def reduce_profile(self) -> Dict[str, float]:
        """Snapshot of the pod-reduce gauges (empty off a DCN mesh, or
        before the first round emitted)."""
        reg = getattr(self, "_reduce_registry", None)
        return reg.snapshot() if reg is not None else {}

    # --- what the streamed round dispatched ------------------------------
    def _real_samples(self, idx, wmask) -> np.ndarray:
        """``[k]`` real samples of the sampled cohort's slots (0 at a pad
        slot), on the host: what the dispatch spans' ``samples`` and the
        ``samples_real`` counter are both summed from."""
        return self._host_counts()[np.asarray(idx)] * np.asarray(wmask)

    def _count_dispatch(self, groups: int, slots: int, samples: int) -> None:
        reg = getattr(self, "_dispatch_registry", None)
        if reg is None:
            from fedml_tpu.obs.registry import MetricsRegistry

            reg = self._dispatch_registry = MetricsRegistry()
        reg.counter("rounds_streamed").inc()
        reg.counter("groups_dispatched").inc(groups)
        reg.counter("slots_dispatched").inc(slots)
        reg.counter("samples_real").inc(samples)

    def dispatch_profile(self) -> Dict[str, int]:
        """Running totals of what the streamed rounds put on the device
        (empty before the first, and on a resident federation):
        ``rounds_streamed``; ``groups_dispatched`` (1 a whole-cohort round);
        ``slots_dispatched``, the sample slots trained an epoch (clients x
        the step bucket they were trained at x batch, padding included);
        ``samples_real``, the cohort's real samples (weight-masked).
        ``samples_real / slots_dispatched`` is the dispatched fill."""
        reg = getattr(self, "_dispatch_registry", None)
        return reg.snapshot() if reg is not None else {}

    # --- capability record (algos/capability.py) ------------------------
    def capability(self):
        """This algorithm's :class:`~fedml_tpu.algos.capability.
        CarryCapability` record — derived once per class from the carry
        protocol declarations; every scan-tier guard below keys on it
        (and refuses with the record-derived message)."""
        from fedml_tpu.algos.capability import record_for

        return record_for(type(self))

    def _build_fused_step(self):
        """The UNJITTED one-round step this algorithm publishes —
        ``step(net, extra, x, y, mask, weights, key, *extras) ->
        ((net', extra'), loss)`` — the SINGLE function both the fused
        host round (jitted with donation, W=1) and the windowed scan
        (``lax.scan`` over its leading-axis-W twin) execute, so the two
        tiers are bit-equal by construction.

        "round"-protocol algorithms get it for free from ``round_fn`` +
        the pure ``_window_server_update``; "custom"-protocol algorithms
        override this (SCAFFOLD/FedDyn wrap their stateful round with
        ``make_fused_stateful_round_step``; Ditto/FedBN build bespoke
        steps over their per-client state stacks)."""
        if self.window_protocol != "round":
            from fedml_tpu.algos.capability import refusal

            raise NotImplementedError(
                refusal(type(self), "the fused round step"))
        from fedml_tpu.parallel.shard import make_fused_round_step

        return make_fused_round_step(self.round_fn,
                                     self._window_server_update())

    def _fused_round_extras(self, round_idx: int, idx, wmask):
        """Per-round trailing operands for the fused step. "round"
        protocol: the ``_round_aux`` hook (the corruption drill's
        adversary mask, FedNova's τ-normalized weights). "custom"
        protocol: the W=1 slice of ``_window_scan_extras`` — the same
        cohort index maps / scatter masks the windowed scan feeds, so
        the fused host round and the scanned round consume identical
        operands."""
        if self.window_protocol == "custom":
            return tuple(
                a[0] for a in self._window_scan_extras(
                    np.asarray(idx)[None], np.asarray(wmask)[None]))
        return self._round_aux(round_idx, idx, wmask)

    # --- fused round step (one donated dispatch per host-loop round) ---
    def _fused_round_step(self):
        """The cached donated FUSED round step — client training +
        aggregation + the algorithm's carry update in ONE dispatch (the
        windowed scan's donation discipline at W=1) — or ``None`` when
        this algorithm/config must keep the separate ``run_round`` +
        ``_server_update`` procedure (capability record says no fused
        step; oort's three-output round). Returns ``(pre, gather)``:
        ``pre`` takes pre-gathered cohort operands; ``gather`` (resident
        federation, "round" protocol; one chip or a mesh) traces the
        client gather inside the same dispatch
        (``parallel.shard.make_cohort_gather``)."""
        if not self.capability().fused:
            return None
        if self.cfg.client_selection == "oort":
            return None  # with_client_losses: 3-output round
        fn = self._fused_step_fn
        if fn is None:
            step = self._build_fused_step()
            # Donate the (net, extra) carry: the caller always rebinds
            # self.net and commits the carry before anything reads the
            # donated originals — XLA reuses the old model's buffers
            # instead of holding old net + round average + new net live
            # (obs.sanitizer.donation_audit pins the 1-copy steady
            # state). For custom-protocol carries this also donates the
            # client-state STACK — one live copy instead of two.
            pre = self._jit(step, donate_argnums=(0, 1))
            gather = None
            take = self._cohort_gather
            if take is not None and self.window_protocol == "round":
                def gather_step(net, extra, fed, idx, wmask, key):
                    sub = take(fed, idx)
                    w = sub.counts.astype(jnp.float32) * wmask
                    return step(net, extra, sub.x, sub.y, sub.mask, w, key)

                gather = self._jit(gather_step, donate_argnums=(0, 1))
            fn = self._fused_step_fn = (pre, gather)
        return fn

    def _train_round_fused(self, round_idx: int):
        """One host-loop round through the fused step: the same sample/
        gather/rng prelude as ``run_round``, then ONE donated dispatch
        with the carry committed back (``_window_carry_commit``) — so
        checkpoints and remainder/eval host work read the new state.
        Returns the round's (device) loss."""
        pre, gather = self._fused_round_step()
        dispatched = {}     # a streamed round: the slots and samples it trains
        with span("fed.round.sample", round=round_idx):
            self.rng, rnd_rng = jax.random.split(self.rng)
            self._last_round_key = rnd_rng
            idx, wmask = self.sample_round(round_idx)
            aux = self._fused_round_extras(round_idx, idx, wmask)
            extra = self._window_carry_init()
        if gather is not None and not aux:
            operands = (self.train_fed, jnp.asarray(idx), jnp.asarray(wmask),
                        rnd_rng)
            step = gather
        else:
            if self._streaming:
                group = self._size_group()
                if group:
                    return self._train_round_size_grouped(
                        round_idx, idx, wmask, rnd_rng, extra, group)
                sub = self._stream_cohort(round_idx, idx)
                dispatched = {
                    "slots": len(idx) * sub.x.shape[1] * sub.x.shape[2],
                    "samples": int(self._real_samples(idx, wmask).sum())}
                self._count_dispatch(1, **dispatched)
            else:
                from fedml_tpu.data.batching import gather_clients

                # (eager ops take no named_scope: known by their programs)
                with span("fed.round.gather", round=round_idx):
                    sub = gather_clients(self.train_fed, idx)
            weights = sub.counts.astype(jnp.float32) * jnp.asarray(wmask)
            operands = (sub.x, sub.y, sub.mask, weights, rnd_rng, *aux)
            step = pre
        with span("fed.round.dispatch", round=round_idx, **dispatched):
            (self.net, extra), loss = step(self.net, extra, *operands)
        self._window_carry_commit(extra)
        self._emit_reduce_obs()
        return loss

    # --- the size-grouped streamed round (one dispatch a size group) ----
    def _size_group(self) -> int:
        """Clients a size group of the streamed round, or 0 for the
        whole-cohort round. Decided ONCE, from what the code can observe: a
        host store whose clients differ in step bucket (equal clients gain
        nothing from sorting), one device, the shared round builders with
        the weighted mean and no per-round operands (what reads the whole
        trained stack keeps the whole cohort, as under
        ``cfg.client_group_size``), and a cohort that ``size_group`` can
        cut. Not round by round: a round whose groups happen to share a
        bucket must not call for a whole-cohort program nobody compiled."""
        g = getattr(self, "_size_group_clients", None)
        if g is None:
            g = self._size_group_clients = self._resolve_size_group()
        return g

    def _cohort_slots(self) -> int:
        """Slots of a sampled cohort (one device: nothing pads it)."""
        return min(self.cfg.client_num_per_round,
                   self.cfg.client_num_in_total)

    def _resolve_size_group(self) -> int:
        from fedml_tpu.data.store import bucket_steps_for_counts, size_group

        rec = self.capability()
        if (not self._streaming or self.mesh is not None
                or self._client_group or self.window_protocol != "round"
                or not rec.fused or rec.custom_round or rec.custom_builders
                or rec.custom_step or rec.round_aux
                or self.cfg.client_selection == "oort"
                or any(user is not None for user in (
                    self._round_aggregator(), self._client_transform(),
                    self._corruptor()))):
            return 0
        store = self.train_fed
        if len(np.unique(bucket_steps_for_counts(
                store.counts, store.batch_size))) < 2:
            return 0
        return size_group(self._cohort_slots(), store.batch_size)

    def _size_group_steps(self):
        """The cached jitted ``(init, group_step, finish)`` of the
        size-grouped round (``parallel.shard.make_size_group_round``).
        Built, they are COMPILED, every one: ``group_step`` for each step
        bucket a group of this federation can have, on a zero-weight group
        of its smallest clients, and ``finish`` on a copy of the model
        (both donate), so that no later round compiles whatever it draws —
        the caller's warm-up sees only a few cohorts."""
        fns = self._size_group_fns
        if fns is None:
            from fedml_tpu.data.store import bucket_steps_for_counts
            from fedml_tpu.parallel.shard import make_size_group_round

            init, step, finish = make_size_group_round(
                self.local_train, self._nan_guard,
                self._window_server_update())
            fns = (self._jit(init), self._jit(step, donate_argnums=(1,)),
                   self._jit(finish, donate_argnums=(0, 1)))
            init, step, finish = fns
            store, group = self.train_fed, self._size_group()
            smallest = np.argsort(store.counts, kind="stable")[:group]
            buckets = bucket_steps_for_counts(store.counts, store.batch_size)
            with planned_transfer():
                no_weight = jnp.zeros((self._cohort_slots(),), jnp.float32)
                slots = jnp.arange(group, dtype=jnp.int32)

                def copy(tree):
                    return jax.tree.map(
                        lambda a: jnp.array(a, copy=True), tree)

                carry = init(self.net)
                # a group's bucket is its largest member's: none is under
                # the bucket of the federation's group-th smallest client
                for steps in np.unique(buckets[buckets
                                               >= buckets[smallest].max()]):
                    fed = store.gather_cohort(smallest, steps=int(steps))
                    carry = step(self.net, carry, fed.x, fed.y, fed.mask,
                                 fed.counts, slots, no_weight, self.rng)
                jax.block_until_ready(finish(
                    copy(self.net), copy(self._window_carry_init()), carry,
                    self.rng))
            self._size_group_fns = fns
        return fns

    def _train_round_size_grouped(self, round_idx: int, idx, wmask, key,
                                  extra, group: int):
        """The streamed round with the cohort cut into size groups: each
        group trained at ITS step bucket and folded into one running sum
        (one donated dispatch a group), then the mean and the server update
        (one more). Same clients, samples, steps and rng streams as the
        whole-cohort round; the padding is what is left out."""
        init, group_step, finish = self._size_group_steps()
        groups = self._stream_cohort(round_idx, idx, group)
        # What each group's dispatch trains, from the host's own copy of the
        # plan the groups were gathered by (their ``slots`` are on the
        # device): the spans' arguments and the registry's counters are the
        # same numbers.
        real = self._real_samples(idx, wmask)
        slots_of = [group * g.steps * self.cfg.batch_size for g in groups]
        samples_of = [int(real[members].sum()) for members, _ in
                      self.train_fed.plan_groups(idx, group)]
        with span("fed.round.dispatch", round=round_idx):
            on_device = jnp.asarray(wmask, jnp.float32)
            carry = init(self.net)
        for j, (fed, slots, steps) in enumerate(groups):
            with span("fed.round.dispatch", round=round_idx, group=j,
                      steps=steps, slots=slots_of[j], samples=samples_of[j]):
                carry = group_step(self.net, carry, fed.x, fed.y, fed.mask,
                                   fed.counts, slots, on_device, key)
        with span("fed.round.dispatch", round=round_idx):
            (self.net, extra), loss = finish(self.net, extra, carry, key)
        self._window_carry_commit(extra)
        self._emit_reduce_obs()
        self._count_dispatch(len(groups), sum(slots_of), sum(samples_of))
        return loss

    def train_one_round(self, round_idx: int) -> Dict[str, float]:
        with span("fed.round", round=round_idx):
            if self._fused_round_step() is not None:
                loss = self._train_round_fused(round_idx)
            else:
                loss = self._train_round_unfused(round_idx)
            with span("fed.round.loss_fetch", round=round_idx):
                loss = float(loss)  # the round's one host sync
        return {"round": round_idx, "train_loss": loss}

    def _train_round_unfused(self, round_idx: int):
        """One round as ``run_round`` + ``_server_update``, two
        dispatches, for the configurations with no fused step. Returns
        the round's (device) loss."""
        if self.window_protocol == "custom":
            # A custom-protocol class without its fused step must not
            # silently fall through to plain run_round rounds — that is
            # the exact drift the capability record exists to refuse.
            from fedml_tpu.algos.capability import refusal

            raise NotImplementedError(
                refusal(type(self), "train_one_round"))
        avg, loss = self.run_round(round_idx)
        self.net = self._server_update(self.net, avg)
        self._emit_reduce_obs()
        if self.cfg.client_selection == "oort":
            # Memoized — returns the cohort this round actually trained.
            idx, wmask = self.sample_round(round_idx)
            self._update_oort_state(round_idx, idx, wmask)
        return loss

    # --- windowed carry protocol ------------------------------------------
    #: How (whether) this algorithm rides the multi-round scan tiers
    #: (``train_rounds_windowed`` / ``train_rounds_on_device``):
    #:
    #: - ``"round"`` — the per-round procedure is exactly ``run_round``
    #:   + ``_server_update``. The windowed scan replays ``round_fn``
    #:   with the PURE server update from :meth:`_window_server_update`
    #:   folded between rounds (plain FedAvg and FedProx need no carry;
    #:   FedOpt carries its server optimizer state).
    #: - ``"custom"`` — the subclass builds its own scan body
    #:   (:meth:`_build_window_scan`) and threads its own carry
    #:   (SCAFFOLD: server control + the full client-control stack,
    #:   gathered/scattered per scanned round).
    #: - ``None`` — host loop only.
    #:
    #: The guards key on THIS declaration (plus a consistency check that
    #: a "round" declarer really left the round alone), not on
    #: ``type(self)`` identity lists — so a subclass that overrides only
    #: ``_server_update`` opts in by providing its pure windowed form
    #: instead of being rejected wholesale.
    window_protocol: Optional[str] = "round"

    def _window_server_update(self):
        """The PURE form of :meth:`_server_update` for the windowed scan:
        ``None`` means plain FedAvg (``net' = round average``, no carry);
        otherwise a jit-traceable ``(net, avg, extra, key) ->
        (net', extra')`` with ``extra`` the carried server state and
        ``key`` the round's rng key (the same key ``run_round`` split for
        that round — randomized server updates fold_in from it, see
        FedAvgRobustAPI's weak-DP noise; deterministic updates like
        FedOpt's ignore it). A subclass that overrides
        ``_server_update`` (host-loop, may touch ``self``) MUST also
        override this hook — inheriting the plain-average fold would
        silently change its semantics inside the scan."""
        if type(self)._server_update is not FedAvgAPI._server_update:
            raise NotImplementedError(
                f"{type(self).__name__} overrides _server_update without "
                "providing its pure windowed form; override "
                "_window_server_update (and the carry init/commit hooks) "
                "or set window_protocol = None")
        return None

    def _window_carry_init(self):
        """Extra carry entering the window scan (read from instance
        state). Plain FedAvg/FedProx carry nothing."""
        return None

    def _window_carry_commit(self, extra) -> None:
        """Write the scanned-out carry back to instance state, so host
        rounds / checkpoints after a window see it (FedOpt: the server
        optimizer state; SCAFFOLD: server + client controls)."""

    def _window_scan_extras(self, idx2d, wmask2d):
        """Extra per-round scanned inputs, as a tuple of ``[W, ...]``
        device arrays — "custom" protocol aux (SCAFFOLD passes the
        window's cohort index map and its scatter mask) OR trailing
        round operands for a "round"-protocol round built with extras
        (the corruption drill's ``[W, C]`` adversary mask, forwarded by
        ``make_window_scan`` into each scanned ``round_fn`` call).
        Default: none."""
        return ()

    def _window_update_mask(self, idx2d, wmask2d) -> np.ndarray:
        """``[W, k]`` float32 mask of slots that actually TRAIN in their
        round: active (un-padded) AND non-empty — the scatter gate for
        per-client state carried through the scan (SCAFFOLD's controls,
        FedDyn's corrections). Layout-agnostic: host counts serve both
        the resident arrays and the store (where it equals
        ``FederatedStore.window_trained_mask`` by construction)."""
        counts = self._host_counts()
        return (np.asarray(wmask2d, np.float32)
                * (counts[np.asarray(idx2d)] > 0).astype(np.float32))

    def _get_window_put(self):
        """The (cached) mesh layout ``put`` for window-scoped device
        arrays — the superbatch, the per-window weights, and any
        ``_window_scan_extras`` that must arrive client-sharded. ``None``
        on a single device (plain ``jnp.asarray`` suffices there)."""
        if self.mesh is None:
            return None
        put = getattr(self, "_window_put", None)
        if put is None:
            from fedml_tpu.parallel.shard import window_put

            put = self._window_put = window_put(
                self.mesh, client_axis(self.mesh))
        return put

    def _build_window_scan(self):
        """The UNJITTED window scan for this algorithm —
        ``scan(net, extra, x, y, mask, weights, keys, *extras) ->
        ((net', extra'), losses)``. Derived from the ONE fused step the
        algorithm publishes (:meth:`_build_fused_step`), so the windowed
        scan and the fused host round execute the same function and
        cannot drift."""
        from fedml_tpu.parallel.shard import make_step_window_scan

        return make_step_window_scan(self._build_fused_step())

    def _check_round_protocol(self, what: str) -> None:
        """Consistency guard for the tiers that replay the STANDARD
        round: the per-round procedure must be exactly ``run_round`` +
        ``_server_update`` — a subclass with its own round would
        silently run plain rounds here. Refusal text comes from the
        capability record."""
        if self.capability().custom_round:
            from fedml_tpu.algos.capability import refusal

            raise NotImplementedError(refusal(type(self), what))

    def _check_windowed_supported(self):
        """Shared guard for the windowed streaming tier — keyed on the
        capability record (algos/capability.py), not type identity."""
        from fedml_tpu.algos.capability import refusal

        if self.window_protocol not in (None, "round", "custom"):
            raise NotImplementedError(
                f"unknown window_protocol {self.window_protocol!r}; "
                "declare 'round', 'custom', or None")
        if (self.window_protocol == "custom"
                and type(self)._window_carry_init
                is not FedAvgAPI._window_carry_init
                and type(self)._window_carry_commit
                is FedAvgAPI._window_carry_commit):
            # State flows INTO the scan but the no-op default commit
            # would silently drop the scanned-out result — remainder
            # rounds/eval/checkpoints would read stale instance
            # state with no error (a forgotten init at least fails
            # loudly at trace time; a forgotten commit never does).
            raise NotImplementedError(
                f"{type(self).__name__} overrides _window_carry_init "
                "without _window_carry_commit; the scanned-out carry "
                "would be silently discarded")
        if not self.capability().windowed:
            raise NotImplementedError(
                refusal(type(self), "train_rounds_windowed"))
        if self.window_protocol == "round":
            self._window_server_update()  # raises when no pure form exists
        if not self._streaming:
            raise NotImplementedError(
                "windowed execution streams window superbatches from a "
                "FederatedStore; the resident layout already has the "
                "stronger train_rounds_on_device scan")
        if self.cfg.client_selection != "random":
            raise NotImplementedError(
                "windowed execution gathers the next W rounds' cohorts in "
                "advance, which only seeded-random selection permits; "
                "pow_d/oort depend on the current net — use the per-round "
                "host loop")

    def _get_window_scan(self):
        fn = self._window_scan_fn
        if fn is None:
            # Donate the incoming carry — net AND extra are always
            # replaced by the scan's outputs, so XLA reuses the old
            # buffers (the driver rebinds/commits before anything reads
            # the donated originals again).
            fn = self._jit(self._build_window_scan(), donate_argnums=(0, 1))
            self._window_scan_fn = fn
        return fn

    def train_rounds_windowed(self, n_rounds: int, start_round: int = 0,
                              window: int = 8):
        """Windowed streaming execution: run ``n_rounds`` store-backed
        rounds with host syncs amortized over windows of ``window``
        rounds. Seeded-random selection makes every upcoming cohort known
        in advance, so each window's cohorts are gathered into ONE
        ``[W, k, S, B, ...]`` superbatch (``FederatedStore.gather_window``
        — single fancy-index gather + single H2D transfer, double-
        buffered against the previous window's compute by
        ``WindowPrefetcher``) and the W rounds run in one jitted
        ``lax.scan`` dispatch — host round-trips drop from O(rounds) to
        O(rounds/window).

        Server state rides the scan as the CARRY (the windowed carry
        protocol, see :attr:`window_protocol`): FedOpt's adaptive server
        optimizer threads its optax state between scanned rounds,
        SCAFFOLD carries the server control plus the full client-control
        stack (cohort slots gathered/scattered inside the scan body),
        and plain FedAvg/FedProx carry nothing. The carry is committed
        back to instance state at every window boundary, so
        checkpointing between calls captures it.

        BIT-EQUAL to the per-round host loop under the same seeds (tested,
        including on a client mesh and with a window the round count
        doesn't divide). Precisely: the TRAINING TRAJECTORY — params,
        carried server state, SCAFFOLD's controls — is bit-exact at every
        round (the per-step update math is sequential and identical);
        the reported per-round LOSS scalar is bit-equal at the pinned
        test shapes but can differ by ~1 ulp at some shapes, because XLA
        may reassociate the loss-reduction sum differently inside the
        scan than in the standalone round dispatch (telemetry only —
        observed on plain FedAvg as well, never feeding back into
        training). Each window forces its rounds onto the window's
        MAX step bucket, which is an exact training no-op — pad slots all
        hold the client's own (masked) first sample, all-masked tail
        steps are ``tree_select``-gated out, and the trainer's rng
        streams are prefix-stable in the step count
        (``trainer.local.make_epoch_shuffle``) — and the per-round rng
        chain (``jax.random.split`` per round, in round order) is
        reproduced exactly. Remainder rounds (< window) run through the
        ordinary host loop (``run_round``). Compilation stays bounded at
        one scan executable per distinct window-max bucket.
        ``self._window_stats`` records the split for introspection.

        Returns the per-round losses as floats — ONE host sync at the
        end. Eval-cadence-aware splitting lives in
        :meth:`train_windowed`."""
        from fedml_tpu.data.store import WindowPrefetcher

        self._check_windowed_supported()
        store = self.train_fed

        # Plan: every round's cohort (seeded → known now) and its bucket.
        cohorts = [self.sample_round(start_round + t)
                   for t in range(n_rounds)]
        buckets = [store.cohort_steps(idx) for idx, _ in cohorts]
        spans = plan_window_spans(buckets, window)
        scan_spans = [s for s in spans if s[2] is not None]
        self._window_stats = {
            "windows": len(scan_spans),
            "scanned_rounds": sum(s[1] for s in scan_spans),
            "host_rounds": n_rounds - sum(s[1] for s in scan_spans),
        }

        put = self._get_window_put()
        pf = getattr(self, "_window_prefetcher", None)
        if pf is None or pf.store is not store or pf.put is not put:
            pf = self._window_prefetcher = WindowPrefetcher(store, put=put)

        def span_args(span):
            off, length, steps = span
            idx2d = np.stack([cohorts[off + t][0] for t in range(length)])
            return start_round + off, idx2d, steps

        if scan_spans:  # overlap the first gather with nothing-yet: cheap
            pf.prefetch(*span_args(scan_spans[0]))

        losses = []
        extra = self._window_carry_init()
        for off, length, steps in spans:
            if steps is None:  # host-loop leftover rounds (the per-round
                # path splits the rng chain itself); the carry was
                # committed after the last scan span, so these rounds see
                # fresh instance state.
                for t in range(length):
                    r = start_round + off + t
                    if self._fused_round_step() is not None:
                        # The fused donated step (the scan's discipline
                        # at W=1) — both protocols publish it through
                        # _build_fused_step; keeping the remainder on
                        # the same program as the scan body preserves
                        # host↔windowed bit-equality by construction.
                        # Its per-round prelude H2Ds (wmask, cohort
                        # weights, per-round extras) are the remainder
                        # path's deliberate design — planned, like the
                        # trailing loss fetch.
                        with planned_transfer():
                            losses.append(self._train_round_fused(r))
                    elif self.window_protocol == "round":
                        avg, loss = self.run_round(r)
                        self.net = self._server_update(self.net, avg)
                        self._emit_reduce_obs()
                        losses.append(loss)
                    else:
                        # "custom" without a fused step (scan-only
                        # classes): train_one_round IS the round. Its
                        # per-round host syncs (eager state gather/
                        # scatter scalars, the float(loss) fetch) are
                        # the remainder path's deliberate design — mark
                        # them planned so sanitized() regions accept a
                        # non-dividing window like they accept the
                        # trailing loss fetch.
                        with planned_transfer():
                            losses.append(
                                self.train_one_round(r)["train_loss"])
                continue
            key, idx2d, _ = span_args((off, length, steps))
            batch = pf.get(key, idx2d, steps)
            # Kick the NEXT window's gather + H2D before dispatching this
            # window's scan, so it overlaps the scan's compute.
            later = [s for s in scan_spans if s[0] > off]
            if later:
                pf.prefetch(*span_args(later[0]))
            # Reproduce the host loop's per-round rng chain exactly.
            keys = []
            for _ in range(length):
                # fedlint: disable=R1(round-order chain reproduced on purpose: bit-equality with run_round's per-round split is the windowed tier's contract)
                self.rng, rnd = jax.random.split(self.rng)
                keys.append(rnd)
            wmask2d = np.stack([cohorts[off + t][1] for t in range(length)])
            weights = store.window_weights(idx2d, wmask2d)
            # planned_transfer: the per-window weights H2D rides along
            # with the superbatch as a deliberate staging copy.
            with planned_transfer():
                weights = put(weights) if put is not None \
                    else jnp.asarray(weights)
            extras = self._window_scan_extras(idx2d, wmask2d)
            scan = self._get_window_scan()
            (self.net, extra), span_losses = scan(
                self.net, extra, batch.x, batch.y, batch.mask, weights,
                jnp.stack(keys), *extras)
            # Commit per span: the donated pre-scan carry is dead, and
            # anything host-side that runs next (remainder rounds, a
            # checkpoint at a window boundary, eval in train_windowed)
            # must read the scanned-out state.
            self._window_carry_commit(extra)
            self._emit_reduce_obs(n_rounds=length)
            losses.extend(list(span_losses))
        # ONE end-of-loop host sync for the losses — planned by design,
        # so mark it for sanitized() regions (the D2H fetch is implicit
        # and would otherwise trip the transfer guard on backends that
        # guard D2H).
        with planned_transfer():
            return [float(l) for l in losses]

    def train_windowed(self, window: int = 8):
        """The full training loop (:meth:`FederatedLoop.train` semantics —
        per-round history, eval every ``frequency_of_the_test`` rounds and
        on the last round) on the windowed streaming tier: rounds between
        eval points run through :meth:`train_rounds_windowed`, with window
        splitting aware of the eval cadence (a scan never crosses a round
        the host must stop at to evaluate)."""
        self._check_windowed_supported()
        history = []
        for lo, hi in eval_segments(self.cfg.comm_round,
                                    self.cfg.frequency_of_the_test):
            seg = self.train_rounds_windowed(hi - lo + 1, start_round=lo,
                                             window=window)
            for i, loss in enumerate(seg):
                history.append({"round": lo + i, "train_loss": loss})
            history[-1].update(self.evaluate())
        return history

    def train_rounds_on_device(self, n_rounds: int):
        """Run ``n_rounds`` WHOLE federated rounds in one jit: a
        ``lax.scan`` over rounds with on-device client sampling — zero
        host round-trips between rounds (the reference pays an MPI
        broadcast + gather per round; even our fused round pays one
        dispatch). Returns the per-round loss array.

        Semantics notes: sampling uses the jax PRNG stream (fold_in per
        round) rather than the reference's ``np.random.seed(round_idx)``
        — with FULL participation both are the identity and this method is
        bit-equal to the host loop (tested); with subsampling the client
        choice differs from host-loop runs. Any "round"-protocol
        algorithm with a PURE server update rides the scan — the carry
        protocol's ``(net, extra)`` threads between scanned rounds
        exactly as in the windowed tier (FedOpt's optimizer state,
        FedAc's acceleration sequences), committed back at the end;
        algorithms needing per-round host-computed aux operands
        (FedNova's τ weights, the corruption drill's masks) refuse with
        the record-derived reason. On a client mesh the scan rides the
        shard_map round under full participation (the gather is the
        identity there: each shard reads its own clients from its copy
        of the replicated federation, the operand the host loop's rounds
        take); subsampled mesh rounds use the host loop.

        The incoming ``self.net`` (and the algorithm's carry) is DONATED
        to the scan (``donate_argnums``): callers that want to compare
        params before vs after must copy ``api.net`` before calling —
        the pre-call reference points at a donated (deleted) buffer
        afterwards."""
        if not self.capability().on_device:
            from fedml_tpu.algos.capability import refusal

            raise NotImplementedError(
                refusal(type(self), "train_rounds_on_device"))
        if self._streaming:
            raise NotImplementedError(
                "train_rounds_on_device needs the whole dataset device-"
                "resident (the scan gathers clients on device each round); "
                "FederatedStore streams cohorts from host — use the host "
                "loop")
        if self.cfg.client_selection != "random":
            raise NotImplementedError(
                "train_rounds_on_device samples uniformly on device; "
                "loss-biased selection (pow_d/oort) needs the host loop")
        cfg = self.cfg
        n_total = int(self.train_fed.num_clients)
        cpr = min(cfg.client_num_per_round, n_total)
        if self.mesh is not None and (cpr != n_total
                                      or n_total % self.n_shards):
            # With FULL participation the gather is the identity, so the
            # sharded round rides the scan directly; the on-device sampler
            # below draws unpadded cohorts for one chip only.
            raise NotImplementedError(
                "the sharded scan requires full participation with the "
                "client count divisible by the mesh "
                f"(clients_per_round={cpr}, total={n_total}, "
                f"shards={self.n_shards}); subsampled mesh rounds use the "
                "host loop")

        scan_fn = getattr(self, "_rounds_scan_fn", None)
        if scan_fn is None:
            round_fn = self.round_fn  # jitted; nested jit is fine under scan
            server_update = self._window_server_update()

            from fedml_tpu.data.batching import gather_clients

            def body(fed, net, extra, key):
                if self.mesh is not None or cpr == n_total:
                    sub = fed  # full participation: gather is the identity
                else:
                    idx = jax.random.choice(
                        jax.random.fold_in(key, 0x5A), n_total, (cpr,),
                        replace=False)
                    sub = gather_clients(fed, idx)
                w = sub.counts.astype(jnp.float32)
                # The round key is used AS the host loop uses rnd_rng, so
                # with full participation this scan is bit-equal to it.
                avg, loss = round_fn(net, sub.x, sub.y, sub.mask, w, w, key)
                if server_update is None:
                    return (avg, extra), loss
                # The carry protocol's pure fold — exactly the windowed
                # scan's between-round step, so stateful-server
                # algorithms (FedOpt, FedAc, ServerAvg) ride on-device
                # with their state never leaving the device.
                return server_update(net, avg, extra, key), loss

            # fed and keys are jit ARGUMENTS (FederatedArrays is a struct
            # pytree): the dataset is not baked into the program as
            # constants, and the compiled scan is cached on self — repeat
            # calls with the same n_rounds reuse the executable.
            def scan_fn(net, extra, fed, keys):
                return jax.lax.scan(
                    lambda c, k: body(fed, c[0], c[1], k), (net, extra),
                    keys)

            # Donate the incoming (net, extra) carry: the caller always
            # replaces self.net / commits the carry from the scan
            # result, so XLA may reuse the old buffers instead of
            # holding both copies live.
            scan_fn = self._jit(scan_fn, donate_argnums=(0, 1))
            self._rounds_scan_fn = scan_fn

        # Reproduce the host loop's per-round rng chain exactly.
        keys = []
        for _ in range(n_rounds):
            # fedlint: disable=R1(round-order chain reproduced on purpose: full-participation bit-equality with the host loop is tested)
            self.rng, rnd = jax.random.split(self.rng)
            keys.append(rnd)
        # Distinct names for the donated operands: the carry that comes
        # BACK is what instance state rebinds to (fedlint R5 discipline
        # — the donated buffers are dead after the call).
        net0, extra0 = self.net, self._window_carry_init()
        carry, losses = scan_fn(net0, extra0, self.train_fed,
                                jnp.stack(keys))
        self.net, extra = carry
        self._window_carry_commit(extra)
        return losses

    def _eval_net(self):
        return self.net
