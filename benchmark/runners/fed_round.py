"""The per-round loop: one ``FedAvgAPI.train_one_round(r)`` per round, which
is what ``fedml_tpu.exp.run.run`` calls, followed by
``block_until_ready(api.net.params)``.

Everything a cell needs comes from its configuration file (model factory,
input, frozen FLOPs), its mix file (clients, cohort, batch, counts law,
placement, learning rate, warm-up and trace sizes) and its ``chips``; no
cell, configuration or mix is named here.
"""

from __future__ import annotations

import contextlib
import glob
import importlib
import math
import os
import shutil
import sys
import threading
import time
import traceback

import numpy as np

#: rtol / atol of FedAvgAPI against reference.py on the convex model (the
#: verify skill's bound for float32 sums associated in another order).
SEM_RTOL, SEM_ATOL = 1e-5, 1e-6
PROFILER_LIMIT_S = 240.0
#: the loss of the window's last rounds is averaged over this many
LAST_ROUNDS = 20


def _no_span(_name):
    return contextlib.nullcontext()


def percentile(values, q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``q`` % of
    the values at or below it."""
    v = sorted(values)
    return v[max(0, math.ceil(q / 100.0 * len(v)) - 1)]


class CompileCounter:
    """Programs compiled, or loaded from the persistent cache, so far in this
    process (``jax.monitoring`` duration events; the idea is
    ``fedml_tpu.obs.sanitizer.compile_count``'s, which misses cache loads)."""

    EVENTS = ("backend_compile_duration", "cache_retrieval_time_sec")

    def __init__(self):
        import jax

        self.count = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, _duration, **_kw):
        if name.endswith(self.EVENTS):
            with self._lock:
                self.count += 1


def _make_model(config: dict):
    module, _, attr = config["factory"].rpartition(".")
    return getattr(importlib.import_module(module), attr)(
        **config.get("factory_kwargs", {}))


def _container(mix: dict, x, y, parts, batch: int):
    """The federation where the mix places it: resident on the device, or
    in a host store that streams each round's cohort."""
    if mix["placement"] == "resident":
        from fedml_tpu.data.batching import build_federated_arrays

        return build_federated_arrays(x, y, parts, batch)
    if mix["placement"] == "host_store":
        from fedml_tpu.data.store import FederatedStore

        return FederatedStore(x, y, parts, batch_size=batch)
    raise ValueError(f"unknown placement {mix['placement']!r}")


def _api(model, fed, mix: dict, batch: int, lr: float, seed: int, mesh,
         **cfg_more):
    from fedml_tpu.algos.config import FedConfig
    from fedml_tpu.algos.fedavg import FedAvgAPI

    # comm_round bounds nothing here but the store's prefetch
    # (FedAvgAPI._stream_cohort stops prefetching at the last round).
    cfg = FedConfig(
        client_num_in_total=int(mix["clients"]),
        client_num_per_round=int(mix["cohort"]), comm_round=2 ** 40,
        epochs=int(mix["epochs"]), batch_size=batch,
        client_optimizer=mix["client_optimizer"], lr=lr,
        seed=seed % (2 ** 31 - 1))
    for k, v in cfg_more.items():
        if not hasattr(cfg, k):
            raise ValueError(f"FedConfig has no field {k!r}")
        setattr(cfg, k, v)
    return FedAvgAPI(model, fed, None, cfg, mesh=mesh)


def _semantics(ctx, mix, classes: int, x, y, parts, counts, mesh) -> dict:
    """FedAvgAPI against reference.py: the cell's own sampler, client sizes,
    placement and mesh drive the convex model for two rounds of one
    full-batch local step; parameters must agree within SEM_RTOL/SEM_ATOL."""
    import jax

    from fedml_tpu.models.lr import LogisticRegression

    reference = ctx.load_module("reference.py")
    batch = int(counts.max())
    rounds = [int(mix["round_base"]) + r for r in (0, 1)]
    lr = float(mix["semantics_lr"])
    t0 = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        api = _api(LogisticRegression(num_classes=classes),
                   _container(mix, x, y, parts, batch), mix, batch, lr,
                   ctx.seed, mesh)
        p0 = jax.tree.map(np.asarray, api.net.params)["linear"]
        t1 = time.perf_counter()
        for r in rounds:
            api.train_one_round(r)
        got = jax.tree.map(np.asarray, api.net.params)["linear"]
    t2 = time.perf_counter()
    flat = x.reshape(len(x), -1)
    w, b = reference.fedavg_rounds(
        p0["kernel"], p0["bias"], lambda c: (flat[parts[c]], y[parts[c]]),
        int(mix["clients"]), int(mix["cohort"]), rounds, lr)
    worst = max(
        float(np.max(np.abs(a - r) / (SEM_ATOL + SEM_RTOL * np.abs(r))))
        for a, r in ((got["kernel"], w), (got["bias"], b)))
    moved = float(np.max(np.abs(got["kernel"] - p0["kernel"])))
    ctx.log(f"semantics vs reference.py: worst |a-b|/(atol+rtol|b|) "
            f"{worst:.4f}, largest update {moved:.4f}; api {t1 - t0:.1f}s, "
            f"two rounds {t2 - t1:.1f}s, reference "
            f"{time.perf_counter() - t2:.1f}s")
    return {"worst_ratio": worst, "ok": bool(worst <= 1.0 and moved > 0.0)}


def _replicas_equal(params, chips: int) -> bool:
    """Every parameter held by ``chips`` distinct devices with bit-equal
    copies. After rounds in which each device trained other clients, only a
    collective inside the round gives that."""
    import jax

    for leaf in jax.tree.leaves(params):
        shards = leaf.addressable_shards
        if len({s.device for s in shards}) != chips:
            return False
        first = np.asarray(shards[0].data)
        if any(not np.array_equal(first, np.asarray(s.data))
               for s in shards[1:]):
            return False
    return True


def run(ctx) -> dict:
    import jax

    mix, config, chips = ctx.mix, ctx.config, int(ctx.cell["chips"])
    if ctx.dryrun:
        mix = {**mix, **mix.get("dryrun", {})}
        config = {**config, **config.get("dryrun", {})}
    compiles = CompileCounter()
    gen = ctx.load_module(os.path.join("generators", mix["generator"] + ".py"))
    x, y, parts, counts = gen.generate(mix, config, ctx.seed)
    ctx.log(f"data: {len(x)} samples, {len(counts)} clients, largest "
            f"{int(counts.max())}, {x.nbytes / 1e9:.2f} GB on the host")
    mesh = None
    if chips > 1:
        from fedml_tpu.parallel.mesh import client_mesh

        mesh = client_mesh(chips)

    sem = _semantics(ctx, mix, int(config["classes"]), x, y, parts, counts,
                     mesh)

    batch = int(mix["batch"])
    fed = _container(mix, x, y, parts, batch)
    api = _api(_make_model(config), fed, mix, batch, float(mix["lr"]),
               ctx.seed, mesh, **config.get("fed_config", {}))
    del x, y, parts

    # The rounds of a run: round_base, round_base + 1, ... (wrapping after
    # round_cycle, the horizon whose shapes are warmed up). The sampler is
    # RandomState(round), as the reference's, and client sizes do not depend
    # on --seed, so every seed meets the same cohort shapes round for round:
    # with rounds of up to 1.6 s, an order that moved with the seed would
    # move the rate by which rounds fall before the window's end.
    base, cycle = int(mix["round_base"]), int(mix["round_cycle"])

    def round_at(i: int) -> int:
        return base + i % cycle

    def work_of(r: int):
        """``(steps, real samples, padded slots)`` of round ``r``, from the
        sampler and the seeded client sizes. The steps (the store's bucket
        for the cohort) decide which program the round runs."""
        idx, wmask = api.sample_round(r)
        real = int((counts[np.asarray(idx)] * np.asarray(wmask)).sum())
        steps = (fed.cohort_steps(idx) if hasattr(fed, "cohort_steps")
                 else fed.steps_per_epoch)
        return steps, real, len(idx) * steps * batch * int(mix["epochs"])

    work = {base + j: work_of(base + j) for j in range(cycle)}

    def one_round(r: int, span=_no_span) -> float:
        with span("bench.round"):
            loss = api.train_one_round(r)["train_loss"]
        with span("bench.fence"):
            jax.block_until_ready(api.net.params)
        return loss

    # Warm-up through public calls: one round of every shape the horizon
    # holds, then one more so that the steady second call is warm too.
    t_warm = time.perf_counter()
    first_of = {}
    for r, (steps, _, _) in work.items():
        first_of.setdefault(steps, r)
    for shape, r in sorted(first_of.items()):
        t = time.perf_counter()
        loss = one_round(r)
        ctx.log(f"warm-up round {r} (shape {shape}): "
                f"{time.perf_counter() - t:.1f}s, loss {loss:.4f}")
    t = time.perf_counter()
    one_round(round_at(cycle - 1))
    ctx.log(f"second warm round: {time.perf_counter() - t:.3f}s; warm-up "
            f"{time.perf_counter() - t_warm:.1f}s, {compiles.count} programs "
            "compiled or loaded so far")

    # The window.
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
        except Exception:   # counted, reported, and the end of the window:
            traceback.print_exc(file=sys.stderr)    # the donated model is gone
            failed += 1
            break
        t_b = time.perf_counter()
        if math.isfinite(loss):
            losses.append(loss)
            times.append(t_b - t_a)
            real, slots = real + work[r][1], slots + work[r][2]
        else:
            failed += 1
        if t_b - t_start >= ctx.seconds:
            break
    window_s = time.perf_counter() - t_start
    in_window = compiles.count - compiled_before
    done = len(times)

    prior = math.log(int(config["classes"]))
    last = float(np.mean(losses[-LAST_ROUNDS:])) if losses else float("nan")
    leaves = jax.tree.leaves(api.net.params)
    on_device = all(d.platform == ctx.platform
                    for leaf in leaves for d in leaf.devices())
    checks = {
        "semantics": sem["ok"],
        "all_rounds_finite": failed == 0 and done > 0,
        "no_compile_in_window": in_window == 0,
        "params_on_device": on_device,
        "beats_prior": last < prior,
    }
    if chips > 1:
        checks["replicas_equal"] = _replicas_equal(api.net.params, chips)
    ctx.log(f"window: {done} rounds in {window_s:.2f}s; every "
            f"{max(1, done // 8)}th loss "
            f"{[round(v, 3) for v in losses[::max(1, done // 8)]]}, "
            f"last-{LAST_ROUNDS} mean {last:.4f} (prior {prior:.4f}); "
            f"compiled in window {in_window}; checks {checks}")

    end_to_end = {
        "rounds_per_s": done / window_s,
        "samples_per_s_chip": real / window_s / chips,
        "setup_s": setup_s,
    }
    for q in (50, 90, 95, 99):   # the manifest says which of them it reports
        end_to_end[f"round_ms_p{q}"] = (
            1e3 * percentile(times, q) if times else None)
    summary = {
        "chips": chips, "rounds": done, "window_s": window_s,
        "real_samples": real, "padded_slots": slots,
        "train_flops_per_sample": config["train_flops_per_sample"],
        "device_kind": jax.devices()[0].device_kind,
        "semantics_worst_ratio": sem["worst_ratio"],
        "last_loss_mean": last,
    }
    if ctx.trace:
        summary["trace"] = _traced_rounds(ctx, mix, one_round, round_at, i,
                                          chips)
    return {"correct": all(checks.values()), "attempted": i, "failed": failed,
            "end_to_end": end_to_end, "summary": summary}


def _traced_rounds(ctx, mix, one_round, round_at, i0: int, chips: int):
    """A handful of steady rounds under ``jax.profiler``, inside the
    benchmark's own host spans (``bench.round`` around the round,
    ``bench.fence`` around the wait for its parameters; what lies between
    them is the loop itself), reduced by reduce_trace.py."""
    import jax

    reduce_trace = ctx.load_module("reduce_trace.py")
    trace_dir = os.path.join(ctx.out_dir, "trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    n = int(mix["trace_rounds"])

    def expire():
        ctx.log(f"the profiler still running after {PROFILER_LIMIT_S}s")
        os._exit(3)

    watchdog = threading.Timer(PROFILER_LIMIT_S, expire)
    watchdog.daemon = True
    watchdog.start()
    try:
        jax.profiler.start_trace(trace_dir)
        try:
            for i in range(i0, i0 + n):
                one_round(round_at(i), span=jax.profiler.TraceAnnotation)
        finally:
            jax.profiler.stop_trace()
    finally:
        watchdog.cancel()
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise RuntimeError(f"the profiler wrote no .xplane.pb to {trace_dir}")
    events = reduce_trace.events_of(max(found, key=os.path.getmtime))
    return reduce_trace.reduce(events, rounds=n, chips=chips)
