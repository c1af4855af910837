"""The per-round loop of ``runners/fed_round.py`` for a language model:
integer token sequences ``[N, T]`` with per-token labels, trained through the
same ``FedAvgAPI.train_one_round(r)`` + ``block_until_ready(params)``, with
the model's own token loss. A sample is one packed sequence.

It loads ``fed_round.py`` for the conventions the two share (``percentile``,
``CompileCounter``, ``_container``, ``_traced_rounds``, the warm-up of every
shape before the window, the window loop) and replaces its semantics check:
``correct`` is decided by the timed path's own product at the timed sizes.
The runner keeps the initial parameters, runs round ``round_base`` through
``api.train_one_round``, and compares that round's loss and its update
``theta_1 - theta_0``, per kind of tensor, with the configuration's plain
reference (``config["reference"]``) computed on the same device in float32 at
the highest matmul precision, client by client and a block of tokens at a
time. The round shuffles a client's sequences; the reference trains every
order of them (``MAX_ORDERS`` at most) and the combination of orders nearest
the round's small tensors is the one compared. TOLERANCES holds each limit
beside the reason for it.

Everything a cell needs comes from its configuration file, its mix file and
its ``chips``; no cell, configuration or mix is named here.
"""

from __future__ import annotations

import importlib
import itertools
import math
import os
import sys
import time
import traceback

import numpy as np

LAST_ROUNDS = 20
#: orders of one client's samples the reference is asked to train
MAX_ORDERS = 6
#: tensors at most this large decide which order each client trained in
PROBE_ELEMENTS = 1 << 16

#: ``|update - reference's| / |reference's update|`` (L2 over every tensor of
#: the kind) of the compared round, and the loss's absolute difference. A
#: state left unchanged reads 1. Each limit lies between two readings, both
#: at the cell's sizes (PERF.md section 6, PR 28).
#:
#: ``program``: the largest the bf16 round read over the builder's 13 seeds
#: on the chip. It is what bf16 operands cost, and nothing else: the reference
#: with the operands of every product rounded to bfloat16's 8 significand
#: bits reads the same as the program on every kind (one gradient: 0.035
#: against 0.038 under the attention layer, 0.117 against 0.118 on the held
#: experts, 0.019 against 0.020 on the head). One delta-rule layer is six
#: products in a row and costs 0.6 %; three of them, the head and the
#: backward pass compound that to 3.6 % a gradient, the second local step
#: doubles it, and a top-10 choice that flips on a near tie moves one or two
#: of a held expert's 80 tokens, which router and held experts read.
#:
#: ``control``: the reference computed with float8's 4 significand bits in
#: every product, the nearest precision below, in the program's place
#: (``mix["stand_in"] = "reference_bits:4"``): the smaller of its reading
#: through the harness on the chip and on the CPU, a seed each. It has to
#: come out not correct, and does by every limit of the update. The planted
#: faults read 1 (a state left unchanged) and 0.61-0.69 (a client's last
#: batch left out). The loss is no precision limit: the control moves it by 0.7e-3 to
#: 1.0e-3, as little as bf16 does, because the steps' differences cancel in
#: the mean; its second reading is the fault's (a batch left out moves it by
#: 0.1).
_BF16 = "bf16 operands of every product against float32"
_TIE = ("; a top-10 choice that flips on a near tie moves one or two of a "
        "held expert's 80 tokens")
TOLERANCES = {
    "delta_projections": {"limit": 0.25, "program": 0.088, "control": 0.684,
                          "why": _BF16},
    "conv": {"limit": 0.25, "program": 0.093, "control": 0.700, "why": _BF16},
    "gdn_gates": {"limit": 0.28, "program": 0.135, "control": 0.596,
                  "why": _BF16 + "; A_log and dt_bias see the products only "
                  "through the decay, 64 numbers a layer"},
    "attention_projections": {"limit": 0.09, "program": 0.026,
                              "control": 0.222, "why": _BF16 + "; the "
                              "attention layer is the last, one layer of "
                              "backward pass"},
    "router": {"limit": 0.4, "program": 0.186, "control": 0.863,
               "why": _BF16 + _TIE},
    "held_experts": {"limit": 0.4, "program": 0.172, "control": 0.828,
                     "why": _BF16 + _TIE},
    "shared_expert": {"limit": 0.25, "program": 0.083, "control": 0.688,
                      "why": _BF16},
    "norms": {"limit": 0.25, "program": 0.078, "control": 0.596,
              "why": _BF16 + " around float32 norms"},
    "embedding": {"limit": 0.25, "program": 0.088, "control": 0.689,
                  "why": _BF16},
    "head": {"limit": 0.12, "program": 0.038, "control": 0.330,
             "why": _BF16 + "; the head sees one layer of backward pass"},
    "loss": {"limit": 3e-3, "program": 7.3e-4, "control": 0.0999,
             "why": "absolute, on a loss near log(vocabulary); its control "
             "is the fault (a batch left out), not the precision"},
}

#: kind of tensor by the last names of its path
_KINDS = (
    ("delta_projections", ("in_proj_qkvz", "in_proj_ba", "out_proj")),
    ("conv", ("conv_weight",)),
    ("gdn_gates", ("A_log", "dt_bias")),
    ("attention_projections", ("q_proj", "k_proj", "v_proj", "o_proj")),
    ("router", ("router",)),
    ("held_experts", ("experts_gate_up", "experts_down")),
    ("shared_expert", ("shared_gate_up", "shared_down", "shared_gate")),
    ("norms", ("input_norm", "post_norm", "final_norm", "q_norm", "k_norm",
               "norm_weight")),
    ("embedding", ("embed",)),
    ("head", ("lm_head",)),
)


def kind_of(name: str) -> str:
    for kind, names in _KINDS:
        if name in names:
            return kind
    raise KeyError(f"no kind of tensor for parameter {name!r}")


def _flat(tree, prefix=()):
    """``{path tuple: array}`` of a nested dict of arrays."""
    out = {}
    for key, value in tree.items():
        if hasattr(value, "items"):
            out.update(_flat(value, prefix + (key,)))
        else:
            out[prefix + (key,)] = value
    return out


def compare_update(theta0, theta1, want) -> dict:
    """``{kind: |(theta1 - theta0) - (want - theta0)| / |want - theta0|}``,
    L2 over every tensor of the kind (float64 sums)."""
    got, ref = _flat(theta1), _flat(want)
    num, den = {}, {}
    for path, start in _flat(theta0).items():
        kind = kind_of(path[-1])
        start = np.asarray(start, np.float32)
        update = np.asarray(got[path], np.float32) - start
        wanted = np.asarray(ref[path], np.float32) - start
        num[kind] = num.get(kind, 0.0) + float(
            np.sum(np.square(update - wanted, dtype=np.float64)))
        den[kind] = den.get(kind, 0.0) + float(
            np.sum(np.square(wanted, dtype=np.float64)))
    return {kind: math.sqrt(num[kind] / den[kind]) if den[kind] > 0
            else float("inf") for kind in num}


def _host(tree):
    import jax

    return jax.tree.map(lambda a: np.asarray(a), dict(tree))


def _api(model, fed, mix: dict, config: dict, seed: int):
    from fedml_tpu.algos.config import FedConfig
    from fedml_tpu.algos.fedavg import FedAvgAPI

    module, _, attr = config["loss"].rpartition(".")
    loss_fn = getattr(importlib.import_module(module), attr)
    cfg = FedConfig(
        client_num_in_total=int(mix["clients"]),
        client_num_per_round=int(mix["cohort"]), comm_round=2 ** 40,
        epochs=int(mix["epochs"]), batch_size=int(mix["batch"]),
        client_optimizer=mix["client_optimizer"], lr=float(mix["lr"]),
        seed=seed % (2 ** 31 - 1))
    for k, v in config.get("fed_config", {}).items():
        if not hasattr(cfg, k):
            raise ValueError(f"FedConfig has no field {k!r}")
        setattr(cfg, k, v)
    return FedAvgAPI(model, fed, None, cfg, loss_fn=loss_fn)


def _train_clients(ctx, reference, shapes, mix, x, y, cohort, theta0,
                   orders_of, bits=None, drop_last: bool = False):
    """``[[(parameters on the host, loss) an order] a client]``: the
    reference's local training of every client of ``cohort`` from ``theta0``,
    once for each of ``orders_of[client]``. ``bits`` rounds the operands of
    every product to that many significand bits and ``drop_last`` leaves a
    client's last batch out: the stand-ins the limits have to catch."""
    import jax

    batch, lr, epochs = int(mix["batch"]), float(mix["lr"]), int(mix["epochs"])
    t0 = time.perf_counter()
    start = jax.tree.map(jax.numpy.asarray, theta0)
    reference.PRODUCT_BITS = bits       # read when loss_and_grad is traced
    trained = []
    try:
        with jax.default_matmul_precision("highest"):
            grad = reference.loss_and_grad(shapes)
            for c in cohort:
                per_order = []
                for order in orders_of[c]:
                    batches = [(x[list(order[i:i + batch])],
                                y[list(order[i:i + batch])])
                               for i in range(0, len(order), batch)]
                    params, loss = reference.client_update(
                        start, batches[:-1] if drop_last else batches,
                        shapes, lr, epochs, grad=grad)
                    per_order.append((_host(params), loss))
                trained.append(per_order)
                ctx.log(f"reference{'' if bits is None else f' ({bits} bits)'}"
                        f": client {c} trained in {len(per_order)} order(s), "
                        f"{time.perf_counter() - t0:.1f}s so far")
    finally:
        reference.PRODUCT_BITS = None
    return trained


def _weighted(trained, shares, combo):
    """The round's model and loss from one order a client."""
    import jax

    model = jax.tree.map(
        lambda *leaves: sum(s * leaf for s, leaf in zip(shares, leaves)),
        *(trained[i][o][0] for i, o in enumerate(combo)))
    return model, sum(s * trained[i][o][1]
                      for i, (s, o) in enumerate(zip(shares, combo)))


def _reference_round(ctx, config, mix, x, y, parts, counts, cohort, theta0,
                     theta1):
    """The reference's round ``round_base``: ``(parameters, loss, stand-in
    or None, seconds)`` for the combination of sample orders nearest
    ``theta1``. ``mix["stand_in"]`` (no committed mix has it; the tests and
    the builder's control run do) asks for what takes the program's place
    in the comparison: ``"unchanged_state"``, ``"last_batch_left_out"`` or
    ``"reference_bits:<n>"``, the last two trained by the reference in the
    orders the round took. Each has to come out not ``correct``."""
    reference = ctx.load_module(config["reference"])
    shapes = dict(config["factory_kwargs"])
    block = mix.get("reference_token_block")
    if block:
        shapes["token_block"] = int(block)
    t0 = time.perf_counter()
    orders_of = {}
    for c in cohort:
        orders_of[c] = list(itertools.permutations(list(parts[c])))
        if len(orders_of[c]) > MAX_ORDERS:
            raise ValueError(
                f"client {c} holds {len(parts[c])} samples: "
                f"{len(orders_of[c])} orders, the reference trains at most "
                f"{MAX_ORDERS}")
    trained = _train_clients(ctx, reference, shapes, mix, x, y, cohort, theta0,
                             orders_of)
    total = float(sum(counts[c] for c in cohort))
    shares = [counts[c] / total for c in cohort]
    got = {p: a for p, a in _flat(theta1).items() if a.size <= PROBE_ELEMENTS}
    small = [[{p: a for p, a in _flat(params).items() if p in got}
              for params, _ in per_order] for per_order in trained]

    def distance(combo):
        return sum(float(np.sum(np.square(
            sum(s * small[i][o][p] for i, (s, o) in enumerate(
                zip(shares, combo))) - got[p], dtype=np.float64)))
            for p in got)

    best = min(itertools.product(*(range(len(t)) for t in trained)),
               key=distance)
    want, loss = _weighted(trained, shares, best)
    stand_in, name = None, mix.get("stand_in")
    if name == "unchanged_state":
        stand_in = (theta0, loss)
    elif name:
        kind, _, bits = name.partition(":")
        if kind not in ("reference_bits", "last_batch_left_out"):
            raise ValueError(f"unknown stand_in {name!r}")
        taken = {c: [orders_of[c][o]] for c, o in zip(cohort, best)}
        stand_in = _weighted(_train_clients(
            ctx, reference, shapes, mix, x, y, cohort, theta0, taken,
            bits=int(bits) if bits else None,
            drop_last=kind == "last_batch_left_out"), shares, [0] * len(best))
    return want, loss, stand_in, time.perf_counter() - t0


def _counters(api) -> dict:
    """``{layer: {name: total}}``: the ``counters`` collection of the model
    the rounds have trained, on the host: running totals kept by the
    rounds' own program (``models/qwen3_next.SparseMoE._count``), each round
    adding the cohort's weighted mean of what a client's local steps
    counted. Empty for a model without the collection."""
    state = dict(api.net.model_state).get("counters", {})
    return {layer: {k: np.asarray(v, np.float64) for k, v in held["moe"].items()}
            for layer, held in sorted(state.items())}


def _counted(before: dict, after: dict) -> dict:
    """What the rounds between two readings of :func:`_counters` counted:
    the fullest held expert's tokens over the mean (worst layer), tokens
    with no held expert (a layer's mean), calls that took the dense arm,
    held assignments not computed."""
    tokens = np.stack([after[k]["expert_tokens"] - before[k]["expert_tokens"]
                       for k in after])                  # [layers, held]
    return {
        "expert_tokens": tokens,
        "load": float(np.max(tokens.max(axis=1)
                             / np.maximum(tokens.mean(axis=1), 1e-9))),
        "unrouted": float(np.mean([after[k]["unrouted_tokens"]
                                   - before[k]["unrouted_tokens"]
                                   for k in after])),
        "dense_arm_calls": float(sum(after[k]["dense_arm_calls"]
                                     - before[k]["dense_arm_calls"]
                                     for k in after)),
        "uncomputed": float(sum(after[k]["uncomputed_tokens"]
                                - before[k]["uncomputed_tokens"]
                                for k in after)),
    }


def run(ctx) -> dict:
    import jax

    base = ctx.load_module("runners/fed_round.py")
    mix, config, chips = ctx.mix, ctx.config, int(ctx.cell["chips"])
    if chips != 1:
        raise ValueError("fed_lm_round runs one-chip cells")
    if ctx.dryrun:
        mix = {**mix, **mix.get("dryrun", {})}
        config = {**config, **config.get("dryrun", {})}
    compiles = base.CompileCounter()
    gen = ctx.load_module(os.path.join("generators", mix["generator"] + ".py"))
    x, y, parts, counts = gen.generate(mix, config, ctx.seed)
    ctx.log(f"data: {len(x)} sequences of {x.shape[1]} tokens, "
            f"{len(counts)} clients, {x.nbytes / 1e6:.1f} MB on the host")
    batch = int(mix["batch"])
    fed = base._container(mix, x, y, parts, batch)
    model = base._make_model(config)
    api = _api(model, fed, mix, config, ctx.seed)
    n_params = sum(a.size for a in jax.tree.leaves(api.net.params))
    ctx.log(f"model: {n_params / 1e6:.1f} M parameters")

    base_round, cycle = int(mix["round_base"]), int(mix["round_cycle"])

    def round_at(i: int) -> int:
        return base_round + i % cycle

    def work_of(r: int):
        idx, wmask = api.sample_round(r)
        real = int((counts[np.asarray(idx)] * np.asarray(wmask)).sum())
        return real, len(idx) * fed.steps_per_epoch * batch * int(
            mix["epochs"])

    work = {base_round + j: work_of(base_round + j) for j in range(cycle)}

    def one_round(r: int, span=base._no_span) -> float:
        with span("bench.round"):
            loss = api.train_one_round(r)["train_loss"]
        with span("bench.fence"):
            jax.block_until_ready(api.net.params)
        return loss

    # The compared round is the first warm-up round; a second makes the
    # steady call warm too (every round of the horizon has one shape).
    theta0 = _host(api.net.params)
    t = time.perf_counter()
    first_loss = one_round(base_round)
    theta1 = _host(api.net.params)
    cohort = [int(c) for c, w in zip(*api.sample_round(base_round)) if w > 0]
    ctx.log(f"round {base_round} (compared, and the warm-up): "
            f"{time.perf_counter() - t:.1f}s, loss {first_loss:.4f}")
    t = time.perf_counter()
    one_round(round_at(cycle - 1))
    ctx.log(f"second warm round: {time.perf_counter() - t:.3f}s; "
            f"{compiles.count} programs compiled or loaded so far")

    # The window.
    counted_before = _counters(api)
    losses, times, failed, real, slots = [], [], 0, 0, 0
    compiled_before = compiles.count
    t_start = time.perf_counter()
    setup_s = t_start - ctx.t0
    i = 0
    while True:
        r = round_at(i)
        i += 1
        t_a = time.perf_counter()
        try:
            loss = one_round(r)
        except Exception:   # counted, reported, and the end of the window
            traceback.print_exc(file=sys.stderr)
            failed += 1
            break
        t_b = time.perf_counter()
        if math.isfinite(loss):
            losses.append(loss)
            times.append(t_b - t_a)
            real, slots = real + work[r][0], slots + work[r][1]
        else:
            failed += 1
        if t_b - t_start >= ctx.seconds:
            break
    window_s = time.perf_counter() - t_start
    in_window = compiles.count - compiled_before
    counted = _counted(counted_before, _counters(api))
    done = len(times)

    prior = math.log(int(config["classes"]) - 1)   # the ids but pad_id
    last = float(np.mean(losses[-LAST_ROUNDS:])) if losses else float("nan")
    on_device = all(d.platform == ctx.platform
                    for leaf in jax.tree.leaves(api.net.params)
                    for d in leaf.devices())
    ctx.log(f"window: {done} rounds in {window_s:.2f}s; every "
            f"{max(1, done // 8)}th loss "
            f"{[round(v, 3) for v in losses[::max(1, done // 8)]]}, "
            f"last-{LAST_ROUNDS} mean {last:.4f} (prior {prior:.4f}, round "
            f"{base_round} {first_loss:.4f}); compiled in window {in_window}")
    summary = {
        "chips": chips, "rounds": done, "window_s": window_s,
        "real_samples": real, "padded_slots": slots,
        "train_flops_per_sample": config["train_flops_per_sample"],
        "device_kind": jax.devices()[0].device_kind,
        "last_loss_mean": last, "first_round_loss": first_loss,
        "parameters": n_params,
        # what the roofline readers count from (reduce_scopes.roofline_pct)
        "counts": {"module": config["counts"],
                   "config": {"factory_kwargs": config["factory_kwargs"]},
                   "mix": {k: mix[k] for k in (
                       "sequence_length", "counts", "batch", "cohort",
                       "epochs")}},
    }
    if ctx.trace:
        summary["trace"] = base._traced_rounds(ctx, mix, one_round, round_at,
                                               i, chips)
    stats = jax.devices()[0].memory_stats() or {}
    summary["memory_peak_bytes_rounds"] = int(
        stats.get("peak_bytes_in_use", 0) + stats.get("peak_bytes_reserved", 0))
    ctx.log(f"memory after the rounds, before the reference: in use "
            f"{stats.get('peak_bytes_in_use', 0)}, reserved "
            f"{stats.get('peak_bytes_reserved', 0)} (peaks)")

    # The model's counters, as the window's own rounds kept them: per local
    # step and layer (a round adds the cohort's mean of a client's steps).
    steps = max(1, i) * fed.steps_per_epoch * int(mix["epochs"])
    tokens_a_step = batch * x.shape[1]
    summary["moe_load_max_over_mean"] = counted["load"]
    summary["moe_unrouted_share"] = counted["unrouted"] / steps / tokens_a_step
    summary["moe_dense_arm_calls"] = counted["dense_arm_calls"]
    # dropped: held assignments no arm computed, in every round since init
    # (the totals start at zero), the traced rounds included
    summary["moe_dropped_tokens"] = float(sum(
        c["uncomputed_tokens"] for c in _counters(api).values()))
    ctx.log(f"counters of the window's {i} rounds: tokens a held expert a "
            f"local step, layer by layer "
            f"{np.round(counted['expert_tokens'] / steps, 1).tolist()}; "
            f"fullest over mean {counted['load']:.3f}; share of tokens with "
            f"no held expert {summary['moe_unrouted_share']:.3f}; calls of "
            f"the dense arm {counted['dense_arm_calls']:.0f}; dropped since "
            f"init {summary['moe_dropped_tokens']:.0f}")

    want, want_loss, stand_in, ref_s = _reference_round(
        ctx, config, mix, x, y, parts, counts, cohort, theta0, theta1)
    if stand_in is not None:    # in the program's place; must not pass
        theta1, first_loss = stand_in
        summary["stand_in"] = mix["stand_in"]
        ctx.log(f"comparing the stand-in {mix['stand_in']!r}, not the round")
    errors = compare_update(theta0, theta1, want)
    errors["loss"] = abs(first_loss - want_loss)
    inside = {k: bool(errors[k] <= TOLERANCES[k]["limit"]) for k in errors}
    ctx.log(f"round {base_round} against {config['reference']} "
            f"({ref_s:.1f}s): loss {first_loss:.5f} / {want_loss:.5f}; "
            "error (limit) " + ", ".join(
                f"{k} {v:.4g} ({TOLERANCES[k]['limit']})"
                for k, v in errors.items()))
    summary["reference_errors"] = errors
    summary["reference_seconds"] = ref_s
    checks = {
        "reference": all(inside.values()) and set(errors) == set(TOLERANCES),
        "all_rounds_finite": failed == 0 and done > 0,
        "no_compile_in_window": in_window == 0,
        "params_on_device": on_device,
        "beats_prior": last < prior and last < first_loss,
        "no_token_dropped": summary["moe_dropped_tokens"] == 0,
    }
    ctx.log(f"checks {checks}")
    end_to_end = {
        "rounds_per_s": done / window_s,
        "samples_per_s_chip": real / window_s / chips,
        "setup_s": setup_s,
    }
    for q in (50, 90, 95, 99):
        end_to_end[f"round_ms_p{q}"] = (
            1e3 * base.percentile(times, q) if times else None)
    return {"correct": all(checks.values()), "attempted": i, "failed": failed,
            "end_to_end": end_to_end, "summary": summary}
