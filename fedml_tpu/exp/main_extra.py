"""Experiment mains for the non-FedAvg-family algorithms — the L4 entries the
reference keeps under ``fedml_experiments/distributed/{fedgan,fedgkt,fednas,
split_nn,classical_vertical_fl,base,decentralized_demo}`` and
``fedml_experiments/standalone/{decentralized,hierarchical_fl}``.

Each ``run_<algo>`` wires args → data → models → API with the reference's
defaults; the module is executable:

    python -m fedml_tpu.exp.main_extra --algorithm FedGAN --comm_round 5 ...
"""

from __future__ import annotations

import argparse
import json
import logging

import numpy as np

from fedml_tpu.exp.args import (add_args, config_from_args,
                                reject_adapter_flags,
                                reject_agg_shards_flag,
                                reject_async_tier_flags,
                                reject_controller_flags,
                                reject_fedavg_family_flags,
                                reject_ingest_pool_flag,
                                reject_pod_plane_flags,
                                reject_secagg_flags,
                                reject_serve_flags)
from fedml_tpu.exp.setup import global_test_batches, load_data
from fedml_tpu.data.loaders import to_federated_arrays
from fedml_tpu.utils import use_compile_cache


def _setup(args):
    fed = load_data(args)
    arrays = to_federated_arrays(fed, args.batch_size)
    test = global_test_batches(fed, args.batch_size)
    cfg = config_from_args(args)
    cfg.client_num_in_total = fed.client_num
    cfg.client_num_per_round = min(cfg.client_num_per_round, fed.client_num)
    return fed, arrays, test, cfg


def run_fedgan(args):
    """main_fedgan.py parity: federated GAN on image data."""
    from fedml_tpu.algos import FedGanAPI
    from fedml_tpu.models import create_model

    _, arrays, _, cfg = _setup(args)
    api = FedGanAPI(create_model("mnist_gan"), arrays, cfg)
    return _loop(api, cfg)


def run_fedgkt(args):
    """main_fedgkt.py parity: small client CNN + big server net distillation."""
    from fedml_tpu.algos import FedGKTAPI
    from fedml_tpu.models import create_model

    fed, arrays, test, cfg = _setup(args)
    # --ci shrinks the model pair (the reference's CI flag exists to cut
    # compute the same way, FedAVGAggregator.py:127-132).
    server_name = "resnet20_server" if args.ci else "resnet56_server"
    client = create_model("resnet5_56", num_classes=fed.class_num)
    server = create_model(server_name, num_classes=fed.class_num)
    api = FedGKTAPI(client, server, arrays, test, cfg)
    return _loop(api, cfg)


def run_fednas(args):
    """main_fednas.py parity: federated DARTS search."""
    from fedml_tpu.algos import FedNASAPI
    from fedml_tpu.models import create_model

    fed, arrays, test, cfg = _setup(args)
    model = create_model("darts", num_classes=fed.class_num, c=8, layers=4)
    api = FedNASAPI(model, arrays, test, cfg)
    hist = _loop(api, cfg)
    logging.info("searched genotype: %s", api.genotype())
    return hist


def run_split_nn(args):
    """main_split_nn.py parity: relay-ring split learning. SplitNN is
    epoch-structured (one relay cycle per epoch), so --epochs drives it."""
    from fedml_tpu.algos import SplitNNAPI
    from fedml_tpu.models import create_model

    fed, arrays, test, cfg = _setup(args)
    server_name = "resnet20_server" if args.ci else "resnet56_server"
    client = create_model("resnet_split_bottom")
    server = create_model(server_name, num_classes=fed.class_num)
    api = SplitNNAPI(client, server, arrays, test, cfg)
    history = []
    for e in range(cfg.epochs):
        metrics = api.train_one_epoch(e)
        if e == cfg.epochs - 1:
            metrics.update(api.evaluate())
        logging.info(json.dumps(metrics))
        history.append(metrics)
    return history


def run_vfl(args):
    """main_vfl.py parity: two-party vertical FL on NUS-WIDE-shaped data."""
    from fedml_tpu.algos import VflAPI
    from fedml_tpu.data.loaders import load_two_party_nus_wide

    (xa, xb, y), (xat, xbt, yt) = load_two_party_nus_wide(
        data_dir=args.data_dir, n_samples=max(args.batch_size * 20, 500))
    api = VflAPI([xa.shape[1], xb.shape[1]], lr=args.lr)
    history = []
    for epoch in range(args.comm_round):
        losses = api.fit([xa, xb], y, epochs=1, batch_size=args.batch_size)
        metrics = {"round": epoch, "train_loss": float(np.mean(losses))}
        if epoch == args.comm_round - 1:
            metrics.update(api.evaluate([xat, xbt], yt))
        logging.info(json.dumps(metrics))
        history.append(metrics)
    return history


def run_decentralized(args):
    """main_dol.py / decentralized_demo parity: gossip DSGD or PushSum."""
    from fedml_tpu.algos import DecentralizedAPI
    from fedml_tpu.core.topology import SymmetricTopologyManager
    from fedml_tpu.models import create_model

    fed, arrays, test, cfg = _setup(args)
    topo = SymmetricTopologyManager(fed.client_num, neighbor_num=2)
    x0 = fed.train_data_global[0][0]
    model = create_model(
        "lr", num_classes=fed.class_num,
        input_dim=int(np.prod(np.asarray(x0).shape[1:])))
    api = DecentralizedAPI(model, arrays, test, cfg, topo,
                           mode=getattr(args, "dol_mode", "dsgd"))
    return _loop(api, cfg)


def _async_loss_kwargs(args):
    """Sequence-dataset loss for the async runners (run.py's make_api
    wiring, which these CLI paths bypass): without it a transformer_lm +
    shakespeare worker dies on the classification CE's label shape and
    the federation deadlocks waiting for its uploads."""
    from fedml_tpu.exp.run import SEQ_DATASETS

    if args.dataset not in SEQ_DATASETS:
        return {}
    from functools import partial

    from fedml_tpu.trainer.local import seq_softmax_ce

    pad_id = -1 if args.dataset == "shakespeare" else 0
    return {"loss_fn": partial(seq_softmax_ce, pad_id=pad_id)}


def _async_obs_kwargs(args):
    """Shared --run_dir/--trace wiring for the async-tier runners: a
    metrics.jsonl ctrl/ stream per model version (the same schema the
    sync server logs per round) and the flight recorder / span tracer.
    Returns ``(kwargs, metrics_logger_or_None)`` — the caller closes the
    logger after the run."""
    from fedml_tpu.exp.args import trace_dir_from

    metrics = None
    if getattr(args, "run_dir", None):
        from fedml_tpu.obs import MetricsLogger

        metrics = MetricsLogger.for_run(run_dir=args.run_dir, stdout=False)
    return {"metrics": metrics, "trace_dir": trace_dir_from(args)}, metrics


def run_fedasync(args):
    """Asynchronous FL (no barrier; staleness-weighted mixing) over the
    loopback message-passing backend — new capability, fedasync.py."""
    from fedml_tpu.algos.fedasync import FedML_FedAsync_distributed
    from fedml_tpu.exp.setup import create_model_for

    fed, arrays, test, cfg = _setup(args)
    model = create_model_for(args, fed)
    obs_kw, metrics = _async_obs_kwargs(args)
    from fedml_tpu.ctrl import controller_from_args

    try:
        srv = FedML_FedAsync_distributed(
            model, arrays, test, cfg,
            alpha=(0.6 if args.fedasync_alpha < 0 else args.fedasync_alpha),
            staleness_exp=args.staleness_exp, wire_codec=args.wire_codec,
            controller=controller_from_args(args),
            **_async_loss_kwargs(args), **obs_kw)
    finally:
        if metrics is not None:
            metrics.close()
    logging.info("fedasync staleness history: %s", srv.staleness_history)
    return srv.test_history or [{"version": srv.version}]


def run_fedbuff(args):
    """Buffered semi-sync FL (aggregate every ``--buffer_k`` arrivals
    with polynomial staleness discounting) — fedbuff.py. Composes with
    ``--aggregator`` (robust buffer reduction) and ``--corrupt_mode``
    (the first ``--attack_num_adversaries`` worker ranks turn
    Byzantine), so churn and Byzantine drills run from one CLI."""
    from fedml_tpu.algos.fedbuff import FedML_FedBuff_distributed
    from fedml_tpu.core.faults import UpdateCorruptor
    from fedml_tpu.exp.setup import create_model_for

    fed, arrays, test, cfg = _setup(args)
    model = create_model_for(args, fed)
    corruptor = None
    corrupt_ranks = ()
    if args.corrupt_mode != "none":
        corruptor = UpdateCorruptor(args.corrupt_mode, args.corrupt_scale,
                                    seed=cfg.seed)
        corrupt_ranks = tuple(range(1, 1 + args.attack_num_adversaries))
    obs_kw, metrics = _async_obs_kwargs(args)
    from fedml_tpu.ctrl import controller_from_args

    try:
        srv = FedML_FedBuff_distributed(
            model, arrays, test, cfg,
            alpha=(1.0 if args.fedasync_alpha < 0 else args.fedasync_alpha),
            staleness_exp=args.staleness_exp, buffer_k=args.buffer_k,
            aggregator=args.aggregator, wire_codec=args.wire_codec,
            corrupt_ranks=corrupt_ranks, corruptor=corruptor,
            controller=controller_from_args(args),
            **_async_loss_kwargs(args), **obs_kw)
    finally:
        if metrics is not None:
            metrics.close()
    logging.info("fedbuff staleness history: %s (guard_drops=%d)",
                 srv.staleness_history, srv.guard_drops)
    history = srv.test_history or [{"version": srv.version}]
    if getattr(args, "serve", False):
        history[-1] = dict(history[-1], **_serve_fedbuff_global(args, model,
                                                               srv))
    return history


def _serve_fedbuff_global(args, model, srv):
    """Stand up the multi-tenant serving plane (fedml_tpu.serve;
    docs/SERVING.md) on the trained FedBuff global: batched LoRA
    inference over the run's frozen base, ``--serve_requests`` smoke
    traffic through the micro-batcher, optional ``--serve_port`` JSON
    socket. Returns flat serve_* scalars for the output line."""
    from fedml_tpu.models.adapter import adapter_model_fns
    from fedml_tpu.serve import (AdapterDecoder, ServeForward, ServeManager,
                                 ServeSocketServer)

    holder = getattr(srv, "adapter_holder", None)
    if not holder or "base" not in holder:
        raise SystemExit(
            "--serve needs the frozen-base adapter run: pass "
            "--adapter_rank > 0 with --model transformer_lm (the serving "
            "plane batches per-request LoRA deltas over one frozen base)")
    fns = adapter_model_fns(model, holder=holder)
    glob = srv.net.params
    fwd = ServeForward(fns, glob)
    dec = AdapterDecoder(model, fns, glob)
    seq_len = min(int(getattr(model, "max_len", 32)), 32)
    vocab = int(getattr(model, "vocab_size", 64))
    mgr = ServeManager(fwd, None, glob, seq_len=seq_len,
                       max_batch=args.serve_max_batch,
                       deadline_s=args.serve_deadline_ms / 1e3,
                       decoder=dec).start()
    sock = None
    try:
        if args.serve_port:
            sock = ServeSocketServer(mgr, args.serve_port).start()
            logging.info("serve socket listening on 127.0.0.1:%d", sock.port)
        rng = np.random.default_rng(0)
        pending = []
        for i in range(int(args.serve_requests)):
            toks = rng.integers(0, vocab,
                                size=int(rng.integers(1, seq_len + 1)))
            pending.append(mgr.submit(i, toks.astype(np.int32),
                                      max_new_tokens=2))
            if len(pending) >= 64:
                for r in pending:
                    r.result(120)
                pending.clear()
        for r in pending:
            r.result(120)
        stats = mgr.stats()
    finally:
        if sock is not None:
            sock.close()
        mgr.close()
    return {k.replace("/", "_"): v for k, v in stats.items()
            if isinstance(v, (int, float))}


def run_base_framework(args):
    """main_base.py parity: the didactic scalar-sum message-passing demo over
    the loopback backend (local result = rank + round)."""
    from fedml_tpu.algos.base_framework import FedML_Base_distributed

    worker_num = max(2, args.client_num_per_round)
    results = FedML_Base_distributed(
        worker_num, args.comm_round,
        local_fn=lambda round_idx, _global: float(round_idx + 1))
    logging.info("base framework per-round aggregates: %s", results)
    return [{"round": i, "aggregate": float(r)} for i, r in enumerate(results)]


def _loop(api, cfg):
    history = []
    for r in range(cfg.comm_round):
        metrics = api.train_one_round(r)
        if hasattr(api, "evaluate") and (
            r % cfg.frequency_of_the_test == 0 or r == cfg.comm_round - 1
        ):
            metrics.update(api.evaluate())
        logging.info(json.dumps({k: v for k, v in metrics.items()
                                 if isinstance(v, (int, float))}))
        history.append(metrics)
    return history


RUNNERS = {
    "FedAsync": run_fedasync,
    "FedBuff": run_fedbuff,
    "FedGAN": run_fedgan,
    "FedGKT": run_fedgkt,
    "FedNAS": run_fednas,
    "SplitNN": run_split_nn,
    "VFL": run_vfl,
    "Decentralized": run_decentralized,
    "BaseFramework": run_base_framework,
}


def main(argv=None):
    parser = argparse.ArgumentParser()
    parser.add_argument("--algorithm", type=str, required=True,
                        choices=sorted(RUNNERS))
    parser.add_argument("--dol_mode", type=str, default="dsgd",
                        help="Decentralized only: dsgd | pushsum")
    add_args(parser)
    args = parser.parse_args(argv)
    use_compile_cache()
    # FedBuff composes with the robust aggregator + corruption drill
    # (buffered ingest reduces through core/robust_agg); every other
    # specialty algorithm must refuse those flags, not no-op. The
    # async-tier knobs are read by FedAsync/FedBuff only.
    if args.algorithm != "FedBuff":
        reject_fedavg_family_flags(args, args.algorithm)
        reject_async_tier_flags(args, args.algorithm,
                                allow_mixing=args.algorithm == "FedAsync")
        # Only the FedBuff runner stands up the serving plane
        # (fedml_tpu.serve) — every other specialty loop refuses the
        # serve knobs rather than silently training without serving.
        reject_serve_flags(args, args.algorithm)
    elif not getattr(args, "serve", False):
        # FedBuff without --serve: the tuning/traffic knobs would be
        # silently inert — same refuse-don't-noop convention.
        reject_serve_flags(args, f"{args.algorithm} without --serve")
    elif not getattr(args, "adapter_rank", 0):
        raise SystemExit(
            "--serve needs --adapter_rank > 0 (and --model "
            "transformer_lm): the serving plane batches per-request "
            "LoRA deltas over one frozen base (fedml_tpu.serve)")
    if (args.algorithm not in ("FedAsync", "FedBuff")
            and getattr(args, "wire_codec", "none") != "none"):
        raise SystemExit(
            f"{args.algorithm} does not support --wire_codec "
            f"{args.wire_codec}: the negotiated wire codec rides the "
            "message-passing upload path (FedAsync/FedBuff here, or the "
            "cross-silo CLI) — the flag would be silently inert")
    if args.algorithm not in ("FedAsync", "FedBuff"):
        # The parallel ingest pool likewise rides only the message-
        # passing server tiers (FedAsync/FedBuff here; cross-silo CLI).
        reject_ingest_pool_flag(args, args.algorithm)
        # ...as does the adaptive controller (fedml_tpu.ctrl): only the
        # FedAsync/FedBuff runners thread controller_from_args through
        # to the server's actuation seam — anywhere else the flags
        # would label a static run self-tuning.
        reject_controller_flags(args, args.algorithm)
    # The sharded aggregation plane is a synchronous-FedAvg capability
    # (comm/shardplane.py): FedAsync/FedBuff refuse cfg.agg_shards in
    # their server constructors (the sequential mix / global-arrival
    # buffer cannot be partitioned), and every other specialty loop
    # never stands up a message-passing server at all.
    reject_agg_shards_flag(args, args.algorithm)
    # Secure aggregation is likewise sync-FedAvg-only (comm/secagg.py):
    # FedAsync/FedBuff refuse cfg.secagg in their server constructors
    # (no roster-complete cohort sum for the masks to cancel in), and
    # no other specialty loop stands up the masked upload path — refuse
    # at the driver so a "privacy" run can never silently ship clear
    # uploads.
    reject_secagg_flags(args, args.algorithm)
    # The pod compute plane (bf16 client step, DCN group reduction)
    # rides the FedAvg family's shared rounds; every specialty loop
    # refuses here. FedAsync/FedBuff refuse client_step_dtype /
    # group_reduce via the shared distributed-setup CFG guard, but
    # --dcn_hosts never reaches a cfg field (it is consumed by the
    # mesh-building setup these runners skip — the same hole
    # main_cross_silo special-cases), so it must refuse at the driver.
    if args.algorithm in ("FedAsync", "FedBuff"):
        if getattr(args, "dcn_hosts", 0):
            raise SystemExit(
                f"{args.algorithm} does not support --dcn_hosts "
                f"{args.dcn_hosts}: the async tiers shard by rank, not "
                "over a device mesh (the flag would be silently inert)")
        # The async tiers DO run the frozen-base adapter finetune
        # (cfg.adapter_rank via build_federation_setup), but only a
        # transformer model has injection sites — any other model would
        # refuse deep inside adapter_model_fns; name the fix here.
        if (getattr(args, "adapter_rank", 0)
                and args.model != "transformer_lm"):
            raise SystemExit(
                f"--adapter_rank {args.adapter_rank} needs --model "
                f"transformer_lm (got {args.model!r}): adapter "
                "injection lives in models/transformer.py")
    else:
        reject_pod_plane_flags(args, args.algorithm)
        # Non-async specialty loops never read the adapter knobs — the
        # PR 4/14 convention: refuse, don't silently train dense.
        reject_adapter_flags(args, args.algorithm)
    logging.basicConfig(level=logging.INFO,
                        format=f"[{args.algorithm} %(asctime)s] %(message)s")
    history = RUNNERS[args.algorithm](args)
    print(json.dumps({k: v for k, v in history[-1].items()
                      if isinstance(v, (int, float))}))
    return history


if __name__ == "__main__":
    main()
