"""``runners/fed_adapter_lm_round.py``'s loop for a frozen language model
whose layers hold ROUTED EXPERTS: ``FedAdapterAPI.train_one_round(r)`` +
``block_until_ready`` over the adapter tree, with the base an operand of the
round's program and the model's ``counters`` collection (tokens a held
expert, tokens with no held expert, held assignments not computed) carried by
the round beside the adapters.

It loads ``fed_round.py``, ``fed_lm_round.py`` and ``fed_adapter_lm_round.py``
for what the four share (``percentile``, ``CompileCounter``, ``_container``,
``_traced_rounds``; the reference's round over every order of a client's
samples and its stand-ins; the API's construction, the base's fingerprint)
and differs from the last in three things. The seeded base's routers are
BALANCED before either side sees it (the reference's ``balance_router``: the
selection biases by the router's own balancing rule on the seed's text; a
drawn router sends every token to the same few experts). ``correct`` is decided by round
``round_base``'s update of the ADAPTERS per kind of tensor BY ITS PLACE in
the model (attention pairs, the dense MLP's, the shared experts', the held
experts'), and its loss, against the configuration's plain reference on the
same device and the same seeded base (that runner's kinds are keyed by
Granite's projection names, and its ``correct`` needs the errors' kinds to
equal them). And the counters are read before and after the window:
``no_token_dropped`` is a check of every round since init, and the fullest
held expert's load over the mean a per-layer metric. TOLERANCES holds each
limit beside the reason for it.

Everything a cell needs comes from its configuration file, its mix file and
its ``chips``; no cell, configuration or mix is named here.
"""

from __future__ import annotations

import math
import sys
import time
import traceback

import numpy as np

LAST_ROUNDS = 20
#: silos whose first sequence the routers' selection biases are balanced on
BALANCE_SILOS = 16

#: ``|update - reference's| / |reference's update|`` (L2 over every tensor of
#: the kind) of the compared round, and the loss's absolute difference. A
#: state left unchanged reads 1. Each limit lies between two readings at the
#: cell's sizes on the chip, on the base that ``init_base`` makes from the
#: seed and ``balance_router`` balances (PERF.md section 6, PR 34).
#:
#: ``program``: the largest the bf16 round read over the builder's eleven
#: seeds at the mix's learning rate (3400000101, 201-203, 301-303, 401-404). It is
#: what bf16 operands cost through five layers whose branches enter the
#: residual stream behind a norm of 0.05 beside a unit embedding, which the
#: rounding does not touch; the held experts' pairs read three times the
#: others' because a top-8 choice that flips on a near tie between the bf16
#: round's scores and the float32 reference's moves a token to another
#: expert, held or not.
#:
#: ``control``: the reference computed with float8's 4 significand bits in
#: every product, the nearest precision below, in the program's place
#: (``mix["stand_in"] = "reference_bits:4"``; the smaller reading of seeds
#: 3400000311 and 411). It has to
#: come out not correct, and does by every limit of the update. A limit of
#: the update is at least twice the first reading and at most half the
#: second, near their geometric mean (3.4 to 4.4 times of room each way),
#: held to that by a test.
#:
#: The loss is no precision limit here: a round's loss is a mean over
#: clients and steps in which the products' differences cancel, and the
#: 4-bit control moves it by 1.7e-4 and 6.4e-4 where the program's seeds read
#: 4.5e-6 to 1.0e-4: 1.7 times apart, no room for a factor two on both sides
#: (``precision``). Its limit is the harness's accepted one
#: (``fed_lm_round``'s 3e-3: 29 times over the program's largest reading)
#: and its second reading the planted fault's: a client's last batch left
#: out (``"last_batch_left_out"``; seed 3400000312) moves it by 0.077 and
#: reads 0.69-1.0 on the update's kinds.
_BF16 = "bf16 operands of every product against float32"
_TIE = ("; a top-8 choice that flips on a near tie moves a token between "
        "experts, held or not")
TOLERANCES = {
    "attention": {"limit": 0.017, "program": 0.004789, "control": 0.078,
                  "why": _BF16},
    "dense_mlp": {"limit": 0.017, "program": 0.004099, "control": 0.07197,
                  "why": _BF16},
    "shared_expert": {"limit": 0.016, "program": 0.003678,
                      "control": 0.0691, "why": _BF16},
    "held_experts": {"limit": 0.042, "program": 0.01242, "control": 0.1447,
                     "why": _BF16 + _TIE + ": the held experts' pairs see "
                     "it directly"},
    "loss": {"limit": 3e-3, "program": 1.024e-04, "control": 0.07676,
             "precision": 1.699e-4,
             "why": "absolute, on a loss near log(vocabulary); its control "
             "is the fault (a batch left out), not the precision"},
}


def kind_of(path: tuple) -> str:
    """The kind of a pair by its place: ``(..., "attn", "lora_q_proj_a")`` ->
    ``"attention"``; a held expert's by its leaf's name."""
    if path[-1].startswith("lora_experts_"):
        return "held_experts"
    for place, kind in (("attn", "attention"), ("mlp", "dense_mlp"),
                        ("shared", "shared_expert")):
        if place in path[:-1]:
            return kind
    raise KeyError(f"no kind of tensor for parameter {'/'.join(path)!r}")


def compare_update(lm, theta0, theta1, want) -> dict:
    """``{kind: |(theta1 - theta0) - (want - theta0)| / |want - theta0|}``,
    L2 over every tensor of the kind (float64 sums)."""
    got, ref = lm._flat(theta1), lm._flat(want)
    num, den = {}, {}
    for path, start in lm._flat(theta0).items():
        kind = kind_of(path)
        start = np.asarray(start, np.float32)
        update = np.asarray(got[path], np.float32) - start
        wanted = np.asarray(ref[path], np.float32) - start
        num[kind] = num.get(kind, 0.0) + float(
            np.sum(np.square(update - wanted, dtype=np.float64)))
        den[kind] = den.get(kind, 0.0) + float(
            np.sum(np.square(wanted, dtype=np.float64)))
    return {kind: math.sqrt(num[kind] / den[kind]) if den[kind] > 0
            else float("inf") for kind in num}


def counters_of(api) -> dict:
    """``{layer: {name: total}}``: the ``counters`` collection of the model
    the rounds have trained, on the host: running totals kept by the rounds'
    own program, each round adding the cohort's weighted mean of what a
    client's local steps counted. Empty for a model without the collection
    (a program whose adapter round carries none)."""
    state = dict(getattr(api.net, "model_state", None) or {}).get(
        "counters", {})
    return {layer: {k: np.asarray(v, np.float64)
                    for k, v in held["moe"].items()}
            for layer, held in sorted(state.items())}


def counted(before: dict, after: dict) -> dict:
    """What the rounds between two readings of :func:`counters_of` counted:
    the tokens a held expert layer by layer, the fullest held expert's over
    the mean (worst layer), the tokens with no held expert (a layer's
    mean), the held products' further passes (all layers)."""
    tokens = np.stack([after[k]["expert_tokens"] - before[k]["expert_tokens"]
                       for k in after])                  # [layers, held]
    return {
        "expert_tokens": tokens,
        "load": float(np.max(tokens.max(axis=1)
                             / np.maximum(tokens.mean(axis=1), 1e-9))),
        "unrouted": float(np.mean([after[k]["unrouted_tokens"]
                                   - before[k]["unrouted_tokens"]
                                   for k in after])),
        "further_passes": float(sum(
            after[k].get("further_passes", 0) - before[k].get(
                "further_passes", 0) for k in after)),
    }


def run(ctx) -> dict:
    import jax

    base = ctx.load_module("runners/fed_round.py")
    lm = ctx.load_module("runners/fed_lm_round.py")
    adapter = ctx.load_module("runners/fed_adapter_lm_round.py")
    mix, config, chips = ctx.mix, ctx.config, int(ctx.cell["chips"])
    if chips != 1:
        raise ValueError("fed_adapter_moe_lm_round runs one-chip cells")
    if ctx.dryrun:
        mix = {**mix, **mix.get("dryrun", {})}
        config = {**config, **config.get("dryrun", {})}
    compiles = base.CompileCounter()
    gen = ctx.load_module(f"generators/{mix['generator']}.py")
    x, y, parts, counts = gen.generate(mix, config, ctx.seed)
    ctx.log(f"data: {len(x)} sequences of {x.shape[1]} tokens, "
            f"{len(counts)} clients, {x.nbytes / 1e6:.1f} MB on the host")
    batch = int(mix["batch"])
    fed = base._container(mix, x, y, parts, batch)
    model = base._make_model(config)    # a program without it fails here
    t = time.perf_counter()
    reference = ctx.load_module(config["reference"])
    weights = reference.init_base(config["factory_kwargs"], ctx.seed)
    jax.block_until_ready(weights)
    ctx.log(f"base: made from the seed in {time.perf_counter() - t:.1f}s")
    # A drawn router is not balanced as a trained one is: its selection
    # biases are set by the router's own balancing rule on the seed's text
    # (the reference's ``balance_router``), before either side sees the base.
    t = time.perf_counter()
    block = mix.get("reference_token_block")
    shapes = {**config["factory_kwargs"],
              **({"token_block": int(block)} if block else {})}
    rows = [int(parts[c][0]) for c in sorted(parts)[:BALANCE_SILOS]]
    weights, balance = reference.balance_router(weights, shapes, x[rows])
    jax.block_until_ready(weights)
    ctx.log(f"routers balanced on {len(rows)} sequences in "
            f"{time.perf_counter() - t:.1f}s: fullest expert over the mean, "
            f"a sparse layer (before, after) "
            f"{[(round(a, 2), round(b, 2)) for a, b in balance]}")
    t = time.perf_counter()
    api = adapter._api(model, fed, mix, config, ctx.seed, weights)
    held = api.adapter_profile()
    base_leaves = jax.tree.leaves(api.base)
    handed = all(a is b for a, b in zip(base_leaves, jax.tree.leaves(weights)))
    ctx.log(f"model: {held['base_params'] / 1e6:.1f} M frozen parameters in "
            f"{held['base_bytes_operand'] / 1e9:.3f} GB "
            f"({sorted({str(a.dtype) for a in base_leaves})}), "
            f"{held['adapter_params'] / 1e6:.2f} M in the adapters, "
            f"{held.get('experts_held', 0):.0f} expert MLPs held; "
            f"{time.perf_counter() - t:.1f}s")
    base_before = adapter._fingerprint(api.base)

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
    theta0 = lm._host(api.net.params)
    t = time.perf_counter()
    first_loss = one_round(base_round)
    theta1 = lm._host(api.net.params)
    cohort = [int(c) for c, w in zip(*api.sample_round(base_round)) if w > 0]
    ctx.log(f"round {base_round} (compared, and the warm-up): "
            f"{time.perf_counter() - t:.1f}s, loss {first_loss:.4f}")
    t = time.perf_counter()
    one_round(round_at(cycle - 1))
    ctx.log(f"second warm round: {time.perf_counter() - t:.3f}s; "
            f"{compiles.count} programs compiled or loaded so far")

    # The window.
    counted_before = counters_of(api)
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
    counted_after = counters_of(api)
    done = len(times)

    prior = math.log(int(config["classes"]) - 1)   # the ids but pad_id
    last = float(np.mean(losses[-LAST_ROUNDS:])) if losses else float("nan")
    on_device = all(d.platform == ctx.platform
                    for leaf in jax.tree.leaves(api.net.params) + base_leaves
                    for d in leaf.devices())
    ctx.log(f"round times (ms): {[round(1e3 * v) for v in times]}")
    ctx.log(f"window: {done} rounds in {window_s:.2f}s; every "
            f"{max(1, done // 8)}th loss "
            f"{[round(v, 4) for v in losses[::max(1, done // 8)]]}, "
            f"last-{LAST_ROUNDS} mean {last:.4f} (prior {prior:.4f}, round "
            f"{base_round} {first_loss:.4f}); compiled in window {in_window}")
    summary = {
        "chips": chips, "rounds": done, "window_s": window_s,
        "real_samples": real, "padded_slots": slots,
        "train_flops_per_sample": config["train_flops_per_sample"],
        "device_kind": jax.devices()[0].device_kind,
        "last_loss_mean": last, "first_round_loss": first_loss,
        "base_parameters": held["base_params"],
        "base_bytes_operand": held["base_bytes_operand"],
        "adapter_parameters": held["adapter_params"],
        "experts_held": held.get("experts_held"),
        "router_load_before_after_balancing": balance,
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
    base_after = adapter._fingerprint(api.base)

    # The model's counters, as the window's own rounds kept them: per local
    # step and layer (a round adds the cohort's mean of a client's steps).
    # A program whose adapter round carries none reports none, and fails
    # ``no_token_dropped``: nothing was counted.
    carried = bool(counted_after)
    if carried:
        window = counted(counted_before, counted_after)
        steps = max(1, i) * fed.steps_per_epoch * int(mix["epochs"])
        summary["moe_held_load_max_over_mean"] = window["load"]
        summary["moe_unrouted_share"] = (
            window["unrouted"] / steps / (batch * x.shape[1]))
        summary["moe_further_passes_a_step"] = window["further_passes"] / steps
        # dropped: held assignments no pass computed, in every round since
        # init (the totals start at zero), the traced rounds included
        summary["moe_dropped_tokens"] = float(sum(
            c["uncomputed_tokens"] for c in counters_of(api).values()))
        ctx.log(f"counters of the window's {i} rounds: tokens a held expert "
                f"a local step, layer by layer "
                f"{np.round(window['expert_tokens'] / steps, 1).tolist()}; "
                f"fullest over mean {window['load']:.3f}; share of tokens "
                f"with no held expert {summary['moe_unrouted_share']:.3f}; "
                f"further passes a client-step, all layers "
                f"{window['further_passes'] / steps:.3f}; "
                f"dropped since init {summary['moe_dropped_tokens']:.0f}")

    # The round's program keeps its temporaries reserved for as long as it is
    # loaded (PERF.md section 7, PR 28): unload every program before the
    # reference asks for its own. The base and the adapters stay.
    jax.clear_caches()
    stats = jax.devices()[0].memory_stats() or {}
    ctx.log(f"programs unloaded: in use {stats.get('bytes_in_use', 0)}, "
            f"reserved {stats.get('bytes_reserved', 0)}")

    # The reference is handed the seeded base beside the sizes.
    with_base = {**config, "factory_kwargs": {**config["factory_kwargs"],
                                              "base": weights}}
    want, want_loss, stand_in, ref_s = lm._reference_round(
        ctx, with_base, mix, x, y, parts, counts, cohort, theta0, theta1)
    if stand_in is not None:    # in the program's place; must not pass
        theta1, first_loss = stand_in
        summary["stand_in"] = mix["stand_in"]
        ctx.log(f"comparing the stand-in {mix['stand_in']!r}, not the round")
    errors = compare_update(lm, theta0, theta1, want)
    errors["loss"] = abs(first_loss - want_loss)
    inside = {k: bool(errors[k] <= TOLERANCES[k]["limit"]) for k in errors}
    ctx.log(f"round {base_round} against {config['reference']} "
            f"({ref_s:.1f}s): loss {first_loss:.5f} / {want_loss:.5f}; "
            "error (limit) " + ", ".join(
                f"{k} {v:.4g} ({TOLERANCES[k]['limit']})"
                for k, v in errors.items()))
    summary["reference_errors"] = errors
    summary["reference_seconds"] = ref_s
    itemsize = {a.dtype.itemsize for a in base_leaves}
    checks = {
        "reference": all(inside.values()) and set(errors) == set(TOLERANCES),
        "all_rounds_finite": failed == 0 and done > 0,
        "no_compile_in_window": in_window == 0,
        "params_on_device": on_device,
        "beats_prior": last < prior and last < first_loss,
        "base_unchanged": handed and base_before == base_after,
        "base_is_one_operand": len(itemsize) == 1 and held[
            "base_bytes_operand"] == itemsize.pop() * held["base_params"],
        "no_token_dropped": carried and summary["moe_dropped_tokens"] == 0,
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
