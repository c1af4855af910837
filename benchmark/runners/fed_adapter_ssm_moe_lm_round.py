"""``runners/fed_adapter_moe_lm_round.py``'s loop for a frozen language model
whose blocks are SINGLE MIXERS of three kinds (state-space, attention, routed
experts): ``FedAdapterAPI.train_one_round(r)`` + ``block_until_ready`` over
the adapter tree, the base an operand of the round's program, the model's
``counters`` collection carried by the round beside the adapters.

It loads the four runners before it for what the five share (``percentile``,
``CompileCounter``, ``_container``, ``_traced_rounds``; the reference's round
over every order of a client's samples and its stand-ins; the API's
construction, the base's fingerprint; the counters' reading) and differs from
the last in three things. ``correct`` is decided by round ``round_base``'s
update of the ADAPTERS per kind of tensor BY ITS PLACE in this model (the
Mamba-2 blocks' pairs, the attention block's, the shared experts', the held
experts'), and its loss, against the configuration's plain reference on the
same device and the same seeded, balanced base: that runner's kinds are
K-EXAONE's (an attention and a feed-forward branch in every layer), and its
``correct`` needs the errors' kinds to equal them. The grouped product's fill
(``expert_tokens`` over ``grouped_rows``) is read beside the fullest held
expert's load. And the cell's own ``client_group_size_ssm_moe`` of the
configuration file, where it is set, is handed to ``FedConfig.
client_group_size`` (the accepted ``device_ms.client_groups.round`` applies
to a cell whose ``fed_config`` holds that key, and its list is not this
PR's to extend). TOLERANCES holds each limit beside the reason for it.

Everything a cell needs comes from its configuration file, its mix file and
its ``chips``; no cell, configuration or mix is named here.
"""

from __future__ import annotations

import math
import sys
import time
import traceback

import numpy as np

#: rounds at the window's end whose mean loss has to lie under the prior (and
#: under round ``round_base``'s). Four, not the other runners' twenty: the
#: window holds some sixteen rounds, and the mix's learning rate is the
#: largest that leaves the routers spread at the window's end (adapters that
#: learn the unigram law fastest do it by one vector common to every token,
#: which a pre-norm stack does not bound and which sends every token to the
#: same experts: PERF.md section 6, PR 39), so the loss passes the prior
#: inside the window and not before it
LAST_ROUNDS = 4

#: ``|update - reference's| / |reference's update|`` (L2 over every tensor of
#: the kind) of the compared round, and the loss's absolute difference. A
#: state left unchanged reads 1. Each limit lies between two readings at the
#: cell's sizes on the chip, on the base that ``init_base`` makes from the
#: seed and ``balance_router`` balances (PERF.md section 6, PR 39).
#:
#: ``program``: the largest the bf16 round read over the builder's eleven
#: seeds at the mix's learning rate (3900000501-508 and 801-803; 0.0031-0.0039
#: on ``mamba``, 0.0146-0.0181 on ``held_experts``, which a flipped top-6
#: choice and the square of ``relu^2`` raise over the others).
#:
#: ``control``: the reference computed with float8's 4 significand bits in
#: every product, the nearest precision below, in the program's place
#: (``mix["stand_in"] = "reference_bits:4"``): the smaller reading of seeds
#: 3900000311 (at lr 1) and 3900000511 (at the mix's rate). It has to come
#: out not correct, and does by every limit of the update. A limit of the
#: update is at least twice the first reading and at most half the second,
#: near their geometric mean (2.4 to 4.8 times of room each way), held to
#: that by a test. On the base's FIRST law (every matrix normal(0, 0.02): the
#: stream is what the blocks computed) the same two readings lay only 3.7 to
#: 4.6 times apart (0.054 / 0.25 on ``mamba``, 0.126 / 0.46 on
#: ``held_experts``): flipped choices, which grow as the square root of the
#: rounding, and no limit had a factor two on both sides; that is one of the
#: two reasons for the embedding-led law (``reference_nemotron_h.init_base``).
#:
#: The loss is held to the same rule. A round's loss is a mean in which the
#: products' differences cancel, so both readings are small beside the
#: update's, but they lie 12.7 times apart: the bf16 round reads 1.4e-6 to
#: 8.0e-6 (one to eight float32 steps of a loss near 9.7) and the 4-bit
#: control 1.0e-4 and 1.8e-4. Its limit is near their geometric mean, 3.6
#: times of room each way; ``fed_lm_round``'s 3e-3, which the accepted
#: adapter cells keep, would pass a fault thirty times the control's.
#:
#: A fault ACROSS blocks (``PAIR_LEFT_OUT``, seed 3900000811: the first
#: Mamba-2 block's ``out_proj`` pair left out of the reference's forward)
#: reads 0.608 on ``mamba``, and on the kinds that see it only through the
#: residual stream 0.227 (``held_experts``), 0.054 (``shared_expert``), 0.175
#: (``attention``) and 1.3e-4 on the loss: every limit catches it, by 4 to 16
#: times, on the law that lets a block add 5-8 % of the stream.
_BF16 = "bf16 operands of every product against float32"
_TIE = ("; a top-6 choice that flips on a near tie moves a token between "
        "experts, held or not")
TOLERANCES = {
    "mamba": {"limit": 0.013, "program": 0.003867, "control": 0.04909,
              "why": _BF16 + "; the chunked scan's decays and states are "
              "float32 on both sides"},
    "attention": {"limit": 0.011, "program": 0.002911, "control": 0.05328,
                  "why": _BF16},
    "shared_expert": {"limit": 0.0095, "program": 0.002758,
                      "control": 0.03916, "why": _BF16},
    "held_experts": {"limit": 0.043, "program": 0.01811, "control": 0.1204,
                     "why": _BF16 + _TIE + ": the held experts' pairs see "
                     "it directly, and the square of relu^2 doubles a "
                     "relative error"},
    "loss": {"limit": 3e-5, "program": 7.987e-06, "control": 1.018e-4,
             "why": "absolute, on a loss near log(vocabulary), " + _BF16
             + "; the products' differences cancel in the mean, so both "
             "readings are small and the limit lies between them like the "
             "update's"},
}


def kind_of(path: tuple) -> str:
    """The kind of a pair by its place: ``(..., "mamba", "lora_in_proj_a")``
    -> ``"mamba"``; a held expert's by its leaf's name."""
    if path[-1].startswith("lora_experts_"):
        return "held_experts"
    for place, kind in (("mamba", "mamba"), ("attn", "attention"),
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


def fill_pct(before: dict, after: dict):
    """The grouped product's fill over the rounds between two readings of the
    counters: the held experts' real assignments over the rows of the chunks
    taken, all expert blocks; ``None`` where the program keeps no
    ``grouped_rows``."""
    def total(name):
        return float(sum(np.sum(after[k][name] - before[k][name])
                         for k in after if name in after[k]))

    rows = total("grouped_rows")
    return 100.0 * total("expert_tokens") / rows if rows else None


#: ``mix["stand_in"]`` of this runner's own beside ``fed_lm_round``'s three:
#: ``"pair_left_out:layer_0/mamba/out_proj"`` is the reference's round with
#: that ONE pair's products left out of the forward, in the program's place: a
#: fault in one block's OUTPUT. The kinds downstream of it have to read it
#: (the base's law lets a block add 5-8 % of the stream: PERF.md section 6).
PAIR_LEFT_OUT = "pair_left_out:"


def reference_round(lm, ctx, config, mix, x, y, parts, counts, cohort, theta0,
                    theta1):
    """``fed_lm_round._reference_round``, and ``PAIR_LEFT_OUT``'s stand-in:
    the reference trained from ``theta0`` without the pair (its ``linear``
    adds a pair only where the tree holds one), the pair put back as it
    started, so that its own kind reads a state left unchanged there."""
    name = mix.get("stand_in") or ""
    if not name.startswith(PAIR_LEFT_OUT):
        return lm._reference_round(ctx, config, mix, x, y, parts, counts,
                                   cohort, theta0, theta1)
    sound = {k: v for k, v in mix.items() if k != "stand_in"}
    want, loss, _, ref_s = lm._reference_round(
        ctx, config, sound, x, y, parts, counts, cohort, theta0, theta1)
    *place, site = name[len(PAIR_LEFT_OUT):].split("/")
    pair = (f"lora_{site}_a", f"lora_{site}_b")

    def holder(tree):
        """A copy of ``tree`` down to the dict at ``place``, and that dict."""
        tree = at = dict(tree)
        for key in place:
            at[key] = at = dict(at[key])
        return tree, at

    def cut(tree):
        tree, at = holder(tree)
        for leaf in pair:
            del at[leaf]        # KeyError: no such pair in the model
        return tree

    faulty, faulty_loss, _, s = lm._reference_round(
        ctx, config, sound, x, y, parts, counts, cohort, cut(theta0),
        cut(theta1))
    faulty, at = holder(faulty)
    at.update({leaf: holder(theta0)[1][leaf] for leaf in pair})
    return want, loss, (faulty, faulty_loss), ref_s + s


def run(ctx) -> dict:
    import jax

    base = ctx.load_module("runners/fed_round.py")
    lm = ctx.load_module("runners/fed_lm_round.py")
    adapter = ctx.load_module("runners/fed_adapter_lm_round.py")
    moe = ctx.load_module("runners/fed_adapter_moe_lm_round.py")
    mix, config, chips = ctx.mix, ctx.config, int(ctx.cell["chips"])
    if chips != 1:
        raise ValueError("fed_adapter_ssm_moe_lm_round runs one-chip cells")
    if ctx.dryrun:
        mix = {**mix, **mix.get("dryrun", {})}
        config = {**config, **config.get("dryrun", {})}
    group = config.get("client_group_size_ssm_moe")
    if group:   # the cell's own key, handed to the API's client_group_size
        config = {**config, "fed_config": {**config.get("fed_config", {}),
                                           "client_group_size": int(group)}}
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
    rows = [int(parts[c][0]) for c in sorted(parts)[:moe.BALANCE_SILOS]]
    weights, balance = reference.balance_router(weights, shapes, x[rows])
    jax.block_until_ready(weights)
    ctx.log(f"routers balanced on {len(rows)} sequences in "
            f"{time.perf_counter() - t:.1f}s: fullest expert over the mean, "
            f"an expert block (before, after) "
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
            f"{held.get('experts_held', 0):.0f} expert MLPs held, clients "
            f"a group {group or 'the whole cohort'}; "
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
    counted_before = moe.counters_of(api)
    folded_before = api.adapter_profile()["adapter_bytes_folded"]
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
    counted_after = moe.counters_of(api)
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
        "clients_a_group": group,
        # what a round's clients would have uploaded (program counter)
        "adapter_upload_mb_round": (
            api.adapter_profile()["adapter_bytes_folded"] - folded_before
        ) / 1e6 / max(1, i),
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
    # step and block (a round adds the cohort's mean of a client's steps).
    # A program whose adapter round carries none reports none, and fails
    # ``no_token_dropped``: nothing was counted.
    carried = bool(counted_after)
    if carried:
        window = moe.counted(counted_before, counted_after)
        steps = max(1, i) * fed.steps_per_epoch * int(mix["epochs"])
        summary["moe_relu2_load_max_over_mean"] = window["load"]
        summary["moe_relu2_fill_pct"] = fill_pct(counted_before,
                                                 counted_after)
        summary["moe_unrouted_share"] = (
            window["unrouted"] / steps / (batch * x.shape[1]))
        summary["moe_further_passes_a_step"] = window["further_passes"] / steps
        # dropped: held assignments no pass computed, in every round since
        # init (the totals start at zero), the traced rounds included
        summary["moe_dropped_tokens"] = float(sum(
            c["uncomputed_tokens"] for c in moe.counters_of(api).values()))
        ctx.log(f"counters of the window's {i} rounds: tokens a held expert "
                f"a local step, block by block: mean "
                f"{np.round(window['expert_tokens'].mean(1) / steps, 1).tolist()}"
                f", fullest "
                f"{np.round(window['expert_tokens'].max(1) / steps, 1).tolist()}"
                f"; fullest over mean {window['load']:.3f}; fill of the "
                f"grouped rows {summary['moe_relu2_fill_pct']}; share of "
                f"tokens with no held expert "
                f"{summary['moe_unrouted_share']:.3f}; further passes a "
                f"client-step, all blocks "
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
    want, want_loss, stand_in, ref_s = reference_round(
        lm, ctx, with_base, mix, x, y, parts, counts, cohort, theta0, theta1)
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
