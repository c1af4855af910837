"""``runners/fed_lm_round.py``'s loop for a language model whose base is
FROZEN: ``FedAdapterAPI.train_one_round(r)`` + ``block_until_ready`` over the
adapter tree (a low-rank pair beside every linear projection), with the base
an operand of the round's program.

It loads ``fed_round.py`` and ``fed_lm_round.py`` for what the three share
(``percentile``, ``CompileCounter``, ``_container``, ``_traced_rounds``; the
reference's round over every order of a client's samples and its stand-ins)
and differs in what is compared: ``correct`` is decided by round
``round_base``'s update of the ADAPTERS, ``theta_1 - theta_0`` per kind of
tensor, and its loss, against the configuration's plain reference
(``config["reference"]``) computed on the same device in float32 at the
highest matmul precision, client by client and a block of tokens at a time.
The frozen base is the benchmark's: the reference file makes it from
``--seed`` by the configuration's ``assumed`` laws (``init_base``) and both
sides are handed that tree, the program as ``base_params``; the program's own
init of a base is not run. TOLERANCES holds each limit beside the reason for
it. Besides: the base is bit for bit what it was before the rounds, and as
many bytes as the program says it is handed.

Everything a cell needs comes from its configuration file, its mix file and
its ``chips``; no cell, configuration or mix is named here.
"""

from __future__ import annotations

import importlib
import math
import sys
import time
import traceback

import numpy as np

LAST_ROUNDS = 20

#: ``|update - reference's| / |reference's update|`` (L2 over every tensor of
#: the kind) of the compared round, and the loss's absolute difference. A
#: state left unchanged reads 1. Each limit lies between two readings at the
#: cell's sizes on the chip, on the base that ``init_base`` makes from the
#: seed (PERF.md section 6, PR 32).
#:
#: ``program``: the largest the bf16 round read over the builder's eleven
#: seeds at the mix's learning rate (3200000401-407, 411-414). It is what
#: bf16 operands cost through 40 layers whose branches enter the residual
#: stream times ``residual_multiplier`` 0.22. The four attention layers'
#: pairs read a third of the others', in the program and in the control
#: alike (why, not measured). On the base the program's own init used to
#: draw in bfloat16 (a normal cut at -2.9 / +2.5 sigma with a mean of -0.01
#: sigma: a common component in every matrix) every reading was a third of
#: these, program and control alike: nine seeds under 0.017, the control
#: 0.08-0.31.
#:
#: ``control``: the reference computed with float8's 4 significand bits in
#: every product, the nearest precision below, in the program's place
#: (``mix["stand_in"] = "reference_bits:4"``): the smaller reading of two
#: seeds (3200000408, 415). It has to come out not correct, and does on both
#: by every limit, the loss's too. A limit of the update is at least twice
#: the first reading and at most half the second, near their geometric mean
#: (3.7 to 4.3 times of room each way).
#:
#: The loss is the weak detector: a round's loss is a mean over clients and
#: steps in which the products' differences cancel, so its readings swing
#: with the seed (the program's 1.7e-6 to 7.3e-5, the control's 2.4e-4 and
#: 6.7e-4) and lie 3.3 times apart where the update's lie 14 to 18. Its limit
#: keeps twice the program's largest reading, because a sound run that reads
#: over it refuses a change, and lies 1.5 times under the control's smaller
#: one (PERF.md section 7 asks for the round's losses a client and a step).
#: ``fault`` (the loss only): what a planted fault read on the earlier base,
#: a client's last batch left out (``"last_batch_left_out"``; seed
#: 3200000104: 0.55-0.85 on the update's kinds); no limit is set from it.
_BF16 = "bf16 operands of every product against float32"
_THIRD = ("; the attention pairs read a third of the others', program and "
          "control alike")
TOLERANCES = {
    "ssm_a": {"limit": 0.14, "program": 0.03521, "control": 0.5796,
              "why": _BF16},
    "ssm_b": {"limit": 0.15, "program": 0.0391, "control": 0.634,
              "why": _BF16},
    "mlp_a": {"limit": 0.14, "program": 0.03452, "control": 0.5968,
              "why": _BF16},
    "mlp_b": {"limit": 0.14, "program": 0.03573, "control": 0.6042,
              "why": _BF16},
    "attention_a": {"limit": 0.048, "program": 0.013, "control": 0.1884,
                    "why": _BF16 + _THIRD},
    "attention_b": {"limit": 0.056, "program": 0.01355, "control": 0.2367,
                    "why": _BF16 + _THIRD},
    "loss": {"limit": 1.6e-4, "program": 7.308e-05, "control": 2.443e-4,
             "fault": 0.03775,
             "why": "absolute, on a loss near log(vocabulary); held against "
             "the 4-bit control like the update, with the less room on the "
             "control's side: its two readings lie 3.3 times apart"},
}

#: kind of tensor by the projection its pair stands beside
_SITES = (("ssm", ("in_proj", "out_proj")),
          ("attention", ("q_proj", "k_proj", "v_proj", "o_proj")),
          ("mlp", ("input_linear", "output_linear")))


def kind_of(name: str) -> str:
    """``lora_<site>_a`` / ``lora_<site>_b`` -> ``<layer kind>_a`` / ``_b``."""
    if name.startswith("lora_"):
        site, _, half = name[len("lora_"):].rpartition("_")
        for kind, sites in _SITES:
            if site in sites and half in ("a", "b"):
                return f"{kind}_{half}"
    raise KeyError(f"no kind of tensor for parameter {name!r}")


def compare_update(lm, theta0, theta1, want) -> dict:
    """``{kind: |(theta1 - theta0) - (want - theta0)| / |want - theta0|}``,
    L2 over every tensor of the kind (float64 sums)."""
    got, ref = lm._flat(theta1), lm._flat(want)
    num, den = {}, {}
    for path, start in lm._flat(theta0).items():
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


def _api(model, fed, mix: dict, config: dict, seed: int, weights):
    from fedml_tpu.algos.config import FedConfig
    from fedml_tpu.algos.fedadapter import FedAdapterAPI

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
    return FedAdapterAPI(model, fed, None, cfg, loss_fn=loss_fn,
                         base_params=weights)


def _fingerprint(tree) -> list:
    """One float32 sum a leaf, computed on the device: equal before and after
    the rounds where no leaf changed."""
    import jax
    import jax.numpy as jnp

    sums = jax.jit(lambda t: [jnp.sum(a.astype(jnp.float32))
                              for a in jax.tree.leaves(t)])(tree)
    return [float(s) for s in sums]


def run(ctx) -> dict:
    import jax

    base = ctx.load_module("runners/fed_round.py")
    lm = ctx.load_module("runners/fed_lm_round.py")
    mix, config, chips = ctx.mix, ctx.config, int(ctx.cell["chips"])
    if chips != 1:
        raise ValueError("fed_adapter_lm_round runs one-chip cells")
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
    weights = ctx.load_module(config["reference"]).init_base(
        config["factory_kwargs"], ctx.seed)
    jax.block_until_ready(weights)
    ctx.log(f"base: made from the seed in {time.perf_counter() - t:.1f}s")
    t = time.perf_counter()
    api = _api(model, fed, mix, config, ctx.seed, weights)
    held = api.adapter_profile()
    base_leaves = jax.tree.leaves(api.base)
    handed = all(a is b for a, b in zip(base_leaves, jax.tree.leaves(weights)))
    ctx.log(f"model: {held['base_params'] / 1e6:.1f} M frozen parameters in "
            f"{held['base_bytes_operand'] / 1e9:.3f} GB "
            f"({sorted({str(a.dtype) for a in base_leaves})}), "
            f"{held['adapter_params'] / 1e6:.2f} M in the adapters; "
            f"{time.perf_counter() - t:.1f}s")
    base_before = _fingerprint(api.base)

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
    counted_before = api.adapter_profile()
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
    counted = api.adapter_profile()
    done = len(times)

    prior = math.log(int(config["classes"]) - 1)   # the ids but pad_id
    last = float(np.mean(losses[-LAST_ROUNDS:])) if losses else float("nan")
    on_device = all(d.platform == ctx.platform
                    for leaf in jax.tree.leaves(api.net.params) + base_leaves
                    for d in leaf.devices())
    ctx.log(f"window: {done} rounds in {window_s:.2f}s; every "
            f"{max(1, done // 8)}th loss "
            f"{[round(v, 4) for v in losses[::max(1, done // 8)]]}, "
            f"last-{LAST_ROUNDS} mean {last:.4f} (prior {prior:.4f}, round "
            f"{base_round} {first_loss:.4f}); compiled in window {in_window}")
    folded = (counted["adapter_bytes_folded"]
              - counted_before["adapter_bytes_folded"])
    summary = {
        "chips": chips, "rounds": done, "window_s": window_s,
        "real_samples": real, "padded_slots": slots,
        "train_flops_per_sample": config["train_flops_per_sample"],
        "device_kind": jax.devices()[0].device_kind,
        "last_loss_mean": last, "first_round_loss": first_loss,
        "base_parameters": held["base_params"],
        "base_bytes_operand": held["base_bytes_operand"],
        "adapter_parameters": held["adapter_params"],
        # what a round's clients would have uploaded (program counter)
        "adapter_upload_mb_round": folded / 1e6 / max(1, i),
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
    base_after = _fingerprint(api.base)

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
