"""Run configuration shared by all federated algorithms.

Field names follow the reference's canonical argparse set
(fedml_experiments/distributed/fedavg/main_fedavg.py:46-130) so configs map
1:1 onto reference experiment flags.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass
class FedConfig:
    client_num_in_total: int = 10
    client_num_per_round: int = 10
    comm_round: int = 10
    epochs: int = 1  # local epochs per round
    batch_size: int = 32
    client_optimizer: str = "sgd"
    lr: float = 0.03
    wd: float = 0.0
    frequency_of_the_test: int = 5
    seed: int = 0
    # FedOpt family (fedml_experiments/distributed/fedopt/main_fedopt.py:54,60)
    server_optimizer: str = "sgd"
    server_lr: float = 1.0
    server_momentum: float = 0.9
    # FedProx proximal term (absent from the reference's fedprox snapshot —
    # SURVEY.md §2.3 — implemented properly here)
    fedprox_mu: float = 0.1
    # Robust aggregation (fedml_api/distributed/fedavg_robust/main_fedavg_robust.py
    # flags --norm_bound / --stddev)
    robust_norm_bound: float = 5.0
    robust_stddev: float = 0.0
    # Backdoor attack harness (fedavg_robust: the poisoned client joins
    # every attack_freq rounds, main_fedavg_robust.py:120). 0 = no attack;
    # k > 0 forces the adversary client(s) into the cohort on every
    # round_idx % k == 0. The adversaries default to the LAST
    # attack_num_adversaries client ids (their shards should hold
    # poisoned data, e.g. data.loaders.edge_case.make_backdoor_dataset).
    attack_freq: int = 0
    attack_num_adversaries: int = 1
    # Byzantine-robust server aggregation (core/robust_agg — new
    # capability; the reference's only reduction is the weighted mean):
    # "mean" (the bit-equal fast path), "coord_median",
    # "trimmed_mean<beta>", "krum<f>", "multi_krum<f>-<m>",
    # "geometric_median<iters>". Rides every execution tier (host loop,
    # windowed, on-device scan); on a client mesh non-mean
    # aggregators all_gather the cohort. docs/ROBUSTNESS.md.
    aggregator: str = "mean"
    # Hierarchical sparse reduction on a client mesh (parallel/shard.py):
    # group-composable aggregators (mean, coord_median, trimmed_mean)
    # aggregate shard-locally first, then across the G group partials —
    # the mesh collective shrinks from C client models to G ≪ C group
    # partials (arXiv:1903.05133 shape). On a DCN×ICI pod mesh
    # (parallel/multihost.dcn_client_mesh; the mesh carries a "hosts"
    # axis) client groups are pinned PER HOST: stage 1 runs as an
    # ICI-axis-only collective with zero DCN traffic and only
    # G = n_hosts group partials + participation mass cross the DCN
    # axis — O(G·model) inter-host bytes instead of the flat path's
    # O(C·model) (docs/PLATFORMS.md "Multi-host"). Mean keeps its
    # bit-equal partial-sum psum fast path (hierarchically associated
    # on a pod mesh); non-composable aggregators (krum,
    # geometric_median) refuse this flag loudly and keep the exact
    # all_gather path. docs/EXECUTION.md "Scale tiers".
    group_reduce: bool = False
    # Device-side update-corruption drill (core/faults.UpdateCorruptor
    # .device_fn, wired through FedAvgRobustAPI): adversary clients'
    # trained updates are corrupted INSIDE the jitted round — "none",
    # "sign_flip", "scale", "nan", or "random"; corrupt_scale is the
    # mode's magnitude. Pair with cfg.aggregator / nan_guard to run
    # attack-vs-defense drills in the windowed tier.
    corrupt_mode: str = "none"
    corrupt_scale: float = 10.0
    # Hierarchical FL (fedml_experiments/standalone/hierarchical_fl/main.py
    # flag --group_comm_round)
    group_comm_round: int = 1
    # fed_launch extras (fed_launch/main.py:148-165): client-side LR
    # schedule over rounds and gradient clipping.
    lr_schedule: str = "none"  # none | cosine | step
    lr_decay_rate: float = 0.992
    grad_clip: float = 0.0
    # Rematerialize forward activations during backprop (jax.checkpoint):
    # trades ~1.3x FLOPs for depth-independent peak HBM.
    remat: bool = False
    # Clients trained at a time inside one round (parallel/shard.
    # fold_client_groups): 0 (default) is the whole cohort under one
    # vmap, which keeps every client's trained model [C, ...] alive until
    # the mean; k > 0 scans over C/k groups of k and folds each group
    # into a running weighted sum, for models a cohort of whose copies
    # does not fit the chip (k of each shard's clients on a mesh). Mean
    # aggregation only: robust aggregators, client transforms
    # (cfg.compress, norm clipping) and the corruption drill need the
    # whole stack and refuse k < C loudly.
    client_group_size: int = 0
    # Client selection strategy (new capability — the reference only has
    # uniform seeded sampling, FedAVGAggregator.py:90-99): "random", or
    # "pow_d" (Power-of-Choice, Cho et al. 2020 — sample pow_d_candidates
    # uniformly, evaluate the CURRENT global model on each, keep the
    # client_num_per_round with the highest local loss; biases rounds
    # toward the worst-served clients for faster convergence).
    # ... or "oort" (Oort, Lai et al. OSDI'21 — epsilon-greedy
    # utility-based selection: exploit clients with high statistical
    # utility loss*sqrt(n) plus a staleness bonus, explore the unseen).
    client_selection: str = "random"
    pow_d_candidates: int = 0  # 0 → 2 * client_num_per_round
    oort_epsilon: float = 0.2  # explore fraction of each oort round
    oort_staleness_coef: float = 0.1  # weight of sqrt(rounds-since-seen)
    # Simulated update compression in the on-device rounds: "none",
    # "topk<ratio>" (e.g. "topk0.05" — each client's delta top-k
    # sparsified), or "q<bits>" (e.g. "q8" — QSGD-style stochastic
    # uniform quantization, unbiased, per-client rng streams), ON device
    # inside the jitted round (studies communication-constrained FL at
    # simulator speed; the cross-silo pipeline's --compress is the real
    # wire-level version with error feedback, fedavg_distributed.py).
    compress: str = "none"
    # Negotiated wire codec for the MESSAGE-PASSING tiers' uploads
    # (comm/codec.py): "none", "bf16", "fp16", "int8", "topk<ratio>",
    # "randmask<ratio>", composable as sparsifier+value (e.g.
    # "topk0.01+int8"). Sparsifiers carry per-client error feedback;
    # negotiation rides the init handshake and falls back loudly against
    # a codec-ignorant peer. The simulator tiers REFUSE this flag (their
    # on-device analogue is cfg.compress); mutually exclusive with
    # compress on the cross-silo path.
    wire_codec: str = "none"
    # Lane-fill compute layout (parallel/layout.py, docs/EXECUTION.md
    # "MFU playbook"): "none", or "auto" — the jitted client step runs a
    # lane-aligned PHYSICAL twin of the model (channel dims padded up to
    # MXU lane/sublane multiples; pad-on-entry / slice-on-exit around the
    # local trainer) while everything above the client step — aggregation,
    # robust aggregators, carry protocol, checkpoints, the wire — keeps
    # the LOGICAL reference shapes. Exact (fp32-bit-exact for the CIFAR
    # ResNet family, tested); supported model families only (refuses
    # loudly otherwise). A no-op when the policy pads nothing.
    # "im2col" — conv lane shaping beyond s2d
    # (parallel/layout.im2col_layout): the 5x5 stem conv is rephrased as
    # patch extraction + a 1x1 conv, growing the MXU contraction dim
    # from Cin to 25·Cin (CNNOriginalFedAvg only; ~1-ulp tolerance, the
    # CNN family's documented class).
    compute_layout: str = "none"
    # bf16 client-step compute (docs/EXECUTION.md "Client-step levers"):
    # "fp32" (default), or "bf16" — the jitted client step's layer
    # compute runs in bfloat16 (flax compute-dtype twin,
    # parallel/layout.step_dtype_model) while the PARAM TREE, gradients,
    # optimizer update, aggregation, and server carry all stay fp32.
    # Eval always runs the fp32 model, so measured accuracy deltas are
    # the training effect, not an eval artifact. Supported model
    # families expose a `dtype` compute field; others refuse loudly.
    # Composes with cfg.compute_layout (the pad-on-entry physical twin
    # is cloned to the bf16 compute dtype).
    client_step_dtype: str = "fp32"
    # Frozen-base adapter finetuning (models/adapter.py +
    # algos/fedadapter.py, --adapter_rank/--adapter_scope): rank of the
    # LoRA pairs injected next to the transformer's scoped projections
    # (0 = dense training, the default). With rank > 0 the federated
    # net IS the adapter tree — the base is frozen (fp32
    # bitwise-invariant, test-pinned) and uploads carry adapter-only
    # deltas that ride the negotiated delta+codec wire path
    # (comm/codec.py DELTA_OK_KEY). Read by FedAdapterAPI (simulator
    # tiers) and build_federation_setup (message-passing tiers); every
    # other driver refuses the flags loudly (exp/args.py
    # reject_adapter_flags, the PR 4/14 convention). adapter_scope:
    # "attn" (qkv + attention out), "mlp", or "all".
    adapter_rank: int = 0
    adapter_scope: str = "attn"
    # Example-level DP-SGD on clients (new capability — the reference only
    # has server-side weak DP, robust_aggregation.py:49-53): per-example
    # gradient clipping at this L2 norm (0 disables) and Gaussian noise of
    # std dp_noise_multiplier * dp_clip added to each summed batch gradient.
    # Account the privacy cost with fedml_tpu.core.privacy.PrivacyAccountant.
    dp_clip: float = 0.0
    dp_noise_multiplier: float = 0.0
    # Distributed control plane (algos/fedavg_distributed.py,
    # docs/ROBUSTNESS.md "Control plane"): checkpoint the server's run
    # state every N completed rounds (0 disables; async orbax save off
    # the round critical path — a killed server restarts from the latest
    # checkpoint and the federation continues), and abandon a round after
    # round_timeout_s wall-clock seconds by EVICTING the silent ranks and
    # aggregating over the survivors (0 = wait forever, reference
    # behavior). Workers beat every heartbeat_interval_s while training
    # long rounds (0 = uploads are the only liveness signal).
    checkpoint_every: int = 0
    round_timeout_s: float = 0.0
    heartbeat_interval_s: float = 0.0
    # Parallel server-ingest pool (comm/ingest.py, --ingest_workers):
    # N decode+fold worker threads pull codec decode / delta
    # reconstruction / accumulator folds off the message-passing
    # servers' single dispatch thread — the measured serving wall
    # (ingest_occupancy 0.78, arXiv:2307.06561). Mean aggregation only
    # (per-worker fixed-point partial accumulators merge associative-
    # exactly, so any worker count is bit-equal to the 1-worker pool
    # regardless of arrival interleaving; non-mean robust aggregators
    # keep the serialized stack-then-reduce path and REFUSE this flag).
    # 0 (default) keeps the legacy inline float fold untouched. The
    # simulator tiers refuse the flag loudly (their rounds have no
    # dispatch thread to unblock).
    ingest_workers: int = 0
    # Sharded aggregation plane (comm/shardplane.py, --agg_shards M):
    # M aggregator-shard processes — each running the full codec
    # negotiation + IngestPool + fixed-point fold over its own client
    # partition — whose serialized int64 partials the rank-0 coordinator
    # wire-merges BIT-EQUAL to the single-process pool (the same
    # associativity proof as ingest_workers, one level up, over the
    # wire). Mean aggregation + sync FedAvg only: FedAsync's sequential
    # server mix and FedBuff's global-arrival-order buffer REFUSE the
    # flag. 0 (default) keeps the single-server ingest path.
    agg_shards: int = 0
    # Dropout-robust secure aggregation (comm/secagg.py, --secagg at the
    # CLI; docs/ROBUSTNESS.md "Secure aggregation"): clients add
    # pairwise seed-expanded masks to their fixed-point int64 uploads so
    # the server only ever materializes the SUM — masks cancel exactly
    # in the pooled fold (and across the sharded plane's wire merge),
    # and a heartbeat eviction triggers a t-of-n Shamir seed reveal that
    # subtracts the orphaned masks. Sync FedAvg + mean aggregation +
    # all-arrive rounds only; needs ingest_workers > 0 or agg_shards > 0
    # (the masks live in the pool's fixed-point domain). The async tiers
    # and every non-supporting driver refuse the flag loudly.
    secagg: bool = False
    # Shamir reveal threshold t: survivors needed to reconstruct an
    # evicted rank's seeds. 0 (default) resolves to a majority
    # (n//2 + 1) of the handshake roster.
    secagg_t: int = 0
    # Federation flight recorder (obs/trace.py, --trace at the CLI;
    # docs/OBSERVABILITY.md): record upload-lifecycle spans (client
    # serialize → wire → codec decode → accumulator fold → round commit,
    # correlated by (epoch, round, sender, task_seq)) and dump a
    # Perfetto-loadable Chrome trace + JSONL into the run directory,
    # plus the server's bounded flight-recorder ring on eviction/abort/
    # codec refusal. Off (the default) is a strict no-op path — the
    # instrumented call sites hit the null tracer, pinned within 2% of
    # uninstrumented in tests/test_trace.py. The CLI layers resolve this
    # flag + --run_dir into the runners' trace_dir parameter.
    trace: bool = False
