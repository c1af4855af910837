"""North-star benchmark + secondary configs, with honest accounting.

Primary metric (BASELINE.json): FedAvg local samples/sec/chip AND
rounds/sec on CIFAR10-ResNet56, 128 simulated clients (batch 32, 1 local
epoch, 8 clients/round) — synthetic CIFAR-shaped data (zero-egress).
Whole-federation-in-one-jit via ``train_rounds_on_device`` (lax.scan over
rounds, on-device sampling).

Accounting:
- median + IQR over ``TRIALS`` timed trials (the remote device the
  earlier rounds measured on showed ~±25% run-to-run variance; a single
  sample cannot separate a regression from noise);
- MFU = delivered FLOP/s ÷ the chip's advertised bf16 peak, with
  delivered = 3 x forward-pass FLOPs (XLA cost analysis of the compiled
  forward, ``obs/flops.model_cost``) x samples/sec — the standard
  fwd+bwd≈3x-fwd estimate, stated as such;
- one XLA profile (``obs/timing.trace``) captured per bench run under
  ``runs/bench_profile`` (TensorBoard-loadable), best-effort;
- kernel A/B sections enforce a 0.4 s device-work floor per timed call
  (``_calibrated_side`` / ``_lm_scan_bench(min_call_s=...)``): chain
  lengths are sized from a measured warm-call rate with the fixed
  per-call dispatch cost cancelled by a two-point fit, and the floor is
  asserted — r3's fixed schedules left fast sides inside its noise band,
  deflating them 3-4x (r3 VERDICT #1);
- MFU is a FIRST-CLASS headline target (ROADMAP item 4): every training
  section reports ``mfu`` + ``delivered_tflops`` against the LOGICAL
  model's FLOPs (``_mfu_fields``), and the headline carries
  ``resnet56_mfu`` (the untouched primary) plus ``best_cnn_mfu`` (the
  best honest CNN-family utilization with the measured lane-fill levers
  applied) so the trajectory files track utilization round-over-round,
  not just samples/s;
- secondary configs as sub-metrics in the SAME JSON object: the
  3400-client FEMNIST-CNN federation (BASELINE.md north-star scale, on
  the host-resident FederatedStore), the store_windowed A/B (windowed
  superbatch execution vs the synced per-round loop on that same
  config), a ViT federation, the lane-fill story on one section
  (s2d stem at batch 32 and 128 — the measured levers; the redundant
  reference-stem batch-128 row rides only under BENCH_HEAVY=1), the
  compute-layout + fused-round-step section (pad A/B, fused-vs-separate
  dispatch A/B, donation audit), the shard_map
  round on a 1-device mesh (the multi-chip code path's single-chip
  throughput), the pallas flash-attention vs dense T-sweep (crossover +
  memory evidence + a labelled memory-cliff datum), and two federated-
  transformer sections (the high-MFU proof at d_model=512; the
  flash-in-training A/B curve at T ∈ {2048, 4096, 8192}).

Prints the full JSON blob (also written to ``docs/bench_local.json``)
followed by a compact (<1 KB) headline JSON as the FINAL stdout line —
{"metric", "value", "unit", "vs_baseline", "mfu", "tuned_best", one
scalar per submetric} — so the driver's bounded tail capture always
keeps a parseable record of the primary number (r4 VERDICT #1: the full
line outgrew the tail window and the r03/r04 driver records lost the
metric).
``vs_baseline`` keeps the round-1 convention — a ~1500 samples/sec
single-GPU PyTorch simulator assumption (RTX2080Ti-class ResNet-56/CIFAR;
the reference publishes no throughput number, BASELINE.md) — while the
absolute numbers + MFU above are the honest figures of merit.
``tuned_best`` carries the best honest number for the same task with the
measured tuning levers applied (s2d stem, batch 128), next to the
untouched comparable primary.

See docs/ROOFLINE.md for why the ResNet-56 number sits where it does
(16/32-channel stages under-fill the 128-lane MXU).
"""

from __future__ import annotations

import json
import statistics
import time

import numpy as np

BASELINE_SAMPLES_PER_SEC = 1500.0  # single-GPU torch simulator assumption
TRIALS = 5


class _SectionTimeout(Exception):
    """A bench section overran its per-section wall-clock cap."""


# Per-section deadline (absolute perf_counter value), set by main()
# around each section. The r5 postmortem: the BUDGET check runs BEFORE a
# section starts, so one long section (transformer_flash_e2e) still blew
# past the driver's kill timer — rc 124, headline never printed. The cap
# is enforced subprocess-free: every A/B repeat/calibration loop calls
# _check_section_deadline() between timed units and bails with
# _SectionTimeout, which main() records as {"timeout": ...} and moves on.
_SECTION_DEADLINE = None


def _check_section_deadline():
    if _SECTION_DEADLINE is not None \
            and time.perf_counter() > _SECTION_DEADLINE:
        raise _SectionTimeout(
            f"per-section cap exceeded "
            f"(+{time.perf_counter() - _SECTION_DEADLINE:.0f}s past "
            "deadline)")


def _rss_mb():
    """CURRENT host RSS in MB — single-sourced in
    :func:`fedml_tpu.utils.rss_mb` since PR 12 (sim.FleetResult.summary()
    reports the same memory axis without this harness). Sampled once per
    timed block by the section machinery, so every section's record
    carries its memory trajectory for free."""
    from fedml_tpu.utils import rss_mb

    return rss_mb()


# Cross-section scale-comparison state (the 342k flat-store point vs the
# 1M sharded-directory point must report RATIOS measured in the SAME
# process): section fns record {"rps": ..., "rss_peak_mb": ...} here.
_scale_state = {}

# Advertised peak bf16 TFLOP/s per chip (public spec sheets), keyed by
# device_kind substring. Unknown kinds → MFU omitted.
CHIP_PEAK_BF16_TFLOPS = {
    "v6": 918.0,
    "v5p": 459.0,
    "v5e": 197.0,
    "v5 lite": 197.0,
    "v4": 275.0,
    "v3": 123.0,
}


def _chip_peak(device_kind: str):
    kind = device_kind.lower()
    for key, peak in CHIP_PEAK_BF16_TFLOPS.items():
        if key in kind:
            return peak
    return None


_mfu_cost_cache = {}


def _mfu_fields(model, sample_x, sps, batch, prefix=""):
    """{"delivered_tflops", "mfu"} for a section's measured samples/sec:
    3x forward FLOPs per sample (fwd+bwd estimate, XLA cost analysis of
    the compiled forward — ``obs/flops.model_cost``) at the measured
    rate, against the chip's advertised bf16 peak. ALWAYS the LOGICAL
    model's FLOPs: lane-fill padding (parallel/layout.py) does extra
    multiplies on zeros that must never inflate the numerator. None/None
    on unknown chips or when the section produced no rate. The cost
    analysis is memoized per (model config, input shape) — three
    sections share the FEMNIST CNN, and each lower+compile would
    otherwise eat seconds of the section budget."""
    import jax

    from fedml_tpu.obs.flops import model_cost

    if not sps:
        return {f"{prefix}delivered_tflops": None, f"{prefix}mfu": None}
    key = (repr(model), np.shape(sample_x), str(np.asarray(sample_x).dtype))
    flops = _mfu_cost_cache.get(key)
    if flops is None:
        flops = _mfu_cost_cache[key] = model_cost(
            model, sample_x, train=False)["flops"]
    delivered = 3.0 * flops / batch * sps / 1e12
    peak = _chip_peak(jax.devices()[0].device_kind)
    return {f"{prefix}delivered_tflops": round(delivered, 3),
            f"{prefix}mfu": (round(delivered / peak, 4) if peak else None)}


def _med_iqr(vals):
    med = statistics.median(vals)
    if len(vals) >= 4:
        q = statistics.quantiles(vals, n=4)
        return med, [round(q[0], 4), round(q[2], 4)]
    return med, [round(min(vals), 4), round(max(vals), 4)]


def _synthetic_cifar_fed(n_clients, per_client, batch):
    """CIFAR-shaped synthetic federated data (zero-egress environment),
    shared by every image-model bench section."""
    from fedml_tpu.data.batching import build_federated_arrays
    from fedml_tpu.data.partition import partition_homo

    rng = np.random.RandomState(0)
    x = rng.randn(n_clients * per_client, 32, 32, 3).astype(np.float32)
    y = rng.randint(0, 10, size=len(x)).astype(np.int32)
    return build_federated_arrays(x, y, partition_homo(len(x), n_clients),
                                  batch)


def _timed_scan_trials(api, rounds, samples_per_round, n_trials=3):
    """samples/sec per trial of the whole-run scan, synced by a host
    scalar fetch (block_until_ready did not reliably wait on the remote
    device this was written against; chip_smoke.py ``timing_facts``
    re-measures that). Caller warms up first."""
    vals = []
    for _ in range(n_trials):
        _check_section_deadline()
        t0 = time.perf_counter()
        losses = api.train_rounds_on_device(rounds)
        float(np.asarray(losses).sum())
        vals.append(samples_per_round * rounds / (time.perf_counter() - t0))
    return vals


def _scan_bench(model, n_clients, per_client, batch, cpr, lr,
                rounds=3, mesh=None, with_iqr=False, min_call_s=0.5):
    """Median samples/sec of the whole-run scan for one (model, config):
    the shared scaffold behind every secondary image-model section.
    ``with_iqr=True`` → (median, [q1, q3]) so trend-sensitive submetrics
    carry their spread in the artifact (r3 VERDICT #7).

    The scan length is grown until a warm call exceeds ``min_call_s``
    (the r3 VERDICT #1 device-work floor, applied here in r4): each call
    carried ~0.1 s of fixed per-call dispatch cost when measured, so a
    3-round window on a fast config under-reports steady-state
    throughput by up to ~45% (measured on the s2d variant: 23k
    samples/s at 3 rounds vs 42.7k by two-point fit,
    scripts/sweep_s2d_attrib.py `bench_path`)."""
    import jax

    from fedml_tpu.algos.config import FedConfig
    from fedml_tpu.algos.fedavg import FedAvgAPI

    fed = _synthetic_cifar_fed(n_clients, per_client, batch)
    cfg = FedConfig(client_num_in_total=n_clients, client_num_per_round=cpr,
                    comm_round=1, epochs=1, batch_size=batch, lr=lr)
    api = FedAvgAPI(model, fed, None, cfg, mesh=mesh)
    api.train_rounds_on_device(rounds)  # warmup/compile
    jax.block_until_ready(api.net.params)
    for _ in range(4):
        _check_section_deadline()
        t0 = time.perf_counter()
        losses = api.train_rounds_on_device(rounds)
        float(np.asarray(losses).sum())
        dt = time.perf_counter() - t0
        if dt >= min_call_s:
            break
        rounds = max(rounds + 1,
                     int(np.ceil(rounds * min_call_s * 1.3 / dt)))
        api.train_rounds_on_device(rounds)  # recompile + warm new length
        jax.block_until_ready(api.net.params)
    trials = _timed_scan_trials(api, rounds, cpr * per_client)
    # The floor is asserted, matching _lm_scan_bench (r4 ADVICE: the
    # silent give-up here contradicted the module docstring).
    call_s = cpr * per_client * rounds / statistics.median(trials)
    assert call_s >= FLOOR_S, (
        f"timed call {call_s:.3f}s below the {FLOOR_S}s floor")
    if with_iqr:
        return _med_iqr(trials)
    return statistics.median(trials)


def bench_cifar_resnet56(profile_dir=None):
    import jax

    from fedml_tpu.algos.config import FedConfig
    from fedml_tpu.algos.fedavg import FedAvgAPI
    from fedml_tpu.models.resnet import resnet56
    from fedml_tpu.obs.flops import model_cost

    n_clients, per_client, batch = 128, 256, 32
    clients_per_round, rounds = 8, 3

    fed = _synthetic_cifar_fed(n_clients, per_client, batch)
    cfg = FedConfig(
        client_num_in_total=n_clients, client_num_per_round=clients_per_round,
        comm_round=1, epochs=1, batch_size=batch, lr=0.1,
    )
    # Mixed precision (bf16 compute, fp32 params/grads) — the standard TPU
    # training configuration; MXU runs bf16 natively (~1.6x over fp32 here).
    model = resnet56(num_classes=10, dtype="bf16")
    api = FedAvgAPI(model, fed, None, cfg)
    api.train_rounds_on_device(rounds)  # warmup/compile
    jax.block_until_ready(api.net.params)
    # Device-work floor (currently a no-op at ~0.6 s/call; guards the
    # metric's honesty if this config ever speeds past the fixed per-call
    # dispatch cost).
    for _ in range(4):
        _check_section_deadline()
        t0 = time.perf_counter()
        losses = api.train_rounds_on_device(rounds)
        float(np.asarray(losses).sum())
        if time.perf_counter() - t0 >= 0.5:
            break
        rounds *= 2
        api.train_rounds_on_device(rounds)
        jax.block_until_ready(api.net.params)

    sps_trials, rps_trials = [], []
    for trial in range(TRIALS):
        if sps_trials:
            # Primary cap (BENCH_PRIMARY_S): keep the trials already
            # timed — a 3-trial median beats a {"timeout": ...} hole in
            # the headline; raise only while there is nothing to report.
            try:
                _check_section_deadline()
            except _SectionTimeout:
                break
        else:
            _check_section_deadline()
        ctx = None
        if profile_dir is not None and trial == TRIALS - 1:
            try:  # best-effort: the profiler may not work on this device
                from fedml_tpu.obs.timing import trace

                ctx = trace(profile_dir)
                ctx.__enter__()
            except Exception:
                ctx, profile_dir = None, None
        t0 = time.perf_counter()
        losses = api.train_rounds_on_device(rounds)
        float(np.asarray(losses).sum())  # host fetch = reliable sync
        dt = time.perf_counter() - t0
        if ctx is not None:
            try:
                ctx.__exit__(None, None, None)
            except Exception:
                profile_dir = None
        sps_trials.append(clients_per_round * per_client * rounds / dt)
        rps_trials.append(rounds / dt)

    sps, sps_iqr = _med_iqr(sps_trials)
    rps, rps_iqr = _med_iqr(rps_trials)

    # MFU: 3x forward FLOPs per sample (fwd+bwd estimate) at the measured
    # samples/sec, against the chip's advertised bf16 peak.
    fwd = model_cost(model, np.zeros((batch, 32, 32, 3), np.float32),
                     train=False)
    flops_per_sample = 3.0 * fwd["flops"] / batch
    delivered_tflops = sps * flops_per_sample / 1e12
    kind = jax.devices()[0].device_kind
    peak = _chip_peak(kind)
    return {
        "samples_per_sec": round(sps, 2),
        "samples_per_sec_iqr": sps_iqr,
        "rounds_per_sec": round(rps, 3),
        "rounds_per_sec_iqr": rps_iqr,
        "trials": len(sps_trials),
        "chip": kind,
        "delivered_tflops": round(delivered_tflops, 3),
        "flops_model": "3x forward (XLA cost analysis), bf16 compute",
        "mfu": (round(delivered_tflops / peak, 4) if peak else None),
        "profile_dir": profile_dir,
    }


def _warm_store_buckets(api, store, counts, cpr, batch):
    """Warm EVERY cohort-shape bucket a FederatedStore can produce (a
    cohort's step count is the power-of-two bucket of its max client) so
    no XLA compile lands inside the timed window — sampled warmup rounds
    do not reliably cover all buckets. Shared by every store-backed
    bench section."""
    import jax

    from fedml_tpu.data.store import bucket_steps_for_counts

    # Vectorized (a per-client Python loop costs seconds of the section
    # cap at the 1M-client scale); single-sourced with the store's
    # bucket policy so warmed shapes can never drift from gathered ones.
    buckets = bucket_steps_for_counts(counts, batch)
    # The program the streaming host loop actually dispatches is the
    # FUSED donated step (capability record), a SEPARATE XLA executable
    # from round_fn — warm THAT per bucket, or its per-bucket compiles
    # land inside the timed windows. Custom-protocol records (FedDyn's
    # stateful carry) only ever run fused; "round" records with a fused
    # step also warm round_fn (the windowed scan inlines it, and the
    # run_round fallback paths dispatch it directly).
    fused = (api._fused_round_step()
             if hasattr(api, "_fused_round_step") else None)
    wmask1 = np.ones(cpr, np.float32)
    for bkt in sorted(set(buckets)):
        c = int(np.argmax(buckets == bkt))
        idx = np.full(cpr, c)
        sub = store.gather_cohort(idx)
        w = np.asarray(sub.counts, np.float32)
        if fused is not None:
            pre, _gather = fused
            extra = api._window_carry_init()
            aux = api._fused_round_extras(0, idx, wmask1)
            (api.net, extra), _ = pre(api.net, extra, sub.x, sub.y,
                                      sub.mask, w, jax.random.PRNGKey(0),
                                      *aux)
            api._window_carry_commit(extra)
        if getattr(api, "window_protocol", "round") == "round":
            # Rounds with per-round aux operands (FedNova's τ-weights +
            # γ) take them as trailing arguments — the capability-record
            # _round_aux hook supplies exactly what run_round would.
            aux = api._round_aux(0, idx, wmask1)
            api.round_fn(api.net, sub.x, sub.y, sub.mask, w, w,
                         jax.random.PRNGKey(0), *aux)
    api.train_one_round(0)
    jax.block_until_ready(api.net.params)


def _timed_store_windows(api, store, windows=5, window=10,
                         count_samples=False, min_window_s=6.0):
    """Median rounds/sec (and samples/sec) over ``windows`` timed windows
    of store-backed rounds, each window floor-calibrated to carry
    ``min_window_s`` seconds of work. Synced per-round loop BY DEFAULT:
    on the remote device of 2026-07-30 a flood of unsynced dispatches cost
    more than the per-round float(loss) sync saved (A/B: ~8.8 vs ~5.5
    rounds/sec — the prefetch worker already overlaps the next gather
    with the wait). That floor is a property of a high fixed per-call
    dispatch cost: on a directly-attached chip set BENCH_ATTACHED=1 to
    time the pipelined loop instead (docs/PLATFORMS.md).

    Window calibration (r4 VERDICT #2): the scan sections got the
    device-work floor in r4 but these per-round loops kept fixed 10-round
    windows (~3 s for femnist, inside the dispatch-cost noise band once
    divided per-round), so the submetric's IQR spanned 2.5x and round-over-round
    trends were unreadable. Now the window length is grown from a probe
    window until one window ≥ ``min_window_s``, then median-of-5 windows
    with IQR. Like FLOOR_S vs TARGET_S elsewhere in this file, the
    calibration aims at ``min_window_s`` but the post-measurement assert
    allows 2/3 of it — headroom so ordinary run-to-run variance cannot crash
    a section after its measurement succeeded."""
    import os

    attached = os.environ.get("BENCH_ATTACHED") == "1"
    window_floor_s = min_window_s * 2.0 / 3.0

    def run_window(r, window):
        _check_section_deadline()
        samples = 0
        if count_samples:
            for rr in range(r, r + window):
                idx, _ = api._sample_round_uncached(rr)
                samples += int(
                    np.asarray(store.counts)[np.asarray(idx)].sum())
        t0 = time.perf_counter()
        if attached:
            losses = api.train_rounds_pipelined(window, start_round=r)
            assert np.isfinite(losses).all()
        else:
            for rr in range(r, r + window):
                m = api.train_one_round(rr)
            assert np.isfinite(m["train_loss"])
        return time.perf_counter() - t0, samples

    # Calibrate: grow the window until a single window carries
    # min_window_s of wall work, then VERIFY on a second window before
    # accepting (r5 ADVICE: the old loop could exit on an unprobed
    # growth, or on a first crossing inflated by one-time warmup — a
    # compile tail or allocator growth — leaving the steady-state
    # windows under the floor the timed runs are asserted against).
    r = 1
    for _ in range(5):
        dt, _ = run_window(r, window)
        r += window
        if dt >= min_window_s:
            dt2, _ = run_window(r, window)
            r += window
            if dt2 >= window_floor_s:
                break
            dt = dt2  # steady-state is faster than the first crossing
        window = max(window + 5,
                     int(np.ceil(window * min_window_s * 1.2 / dt)))
    else:
        raise AssertionError(
            f"window calibration could not reach the {min_window_s:.1f}s "
            f"target (last window {window} rounds, {dt:.2f}s)")

    rps_w, sps_w, window_s, rss_w = [], [], [], []
    for _ in range(windows):
        dt, samples = run_window(r, window)
        rps_w.append(window / dt)
        sps_w.append(samples / dt)
        window_s.append(dt)
        rss_w.append(_rss_mb())  # one RSS sample per timed block
        r += window
    # EVERY timed window must clear the floor, not just the median — with
    # median-only, 2 of 5 windows could sit inside the RTT noise band
    # unflagged (r5 ADVICE; the committed r5 femnist median was 5.99s vs
    # a 6.0s target, so the margin is real).
    assert min(window_s) >= window_floor_s, window_s
    rps_med, rps_iqr = _med_iqr(rps_w)
    out = {"loop": "pipelined" if attached else "synced",
           "rounds_per_sec": round(rps_med, 3),
           "rounds_per_sec_iqr": rps_iqr, "windows": windows,
           "window_rounds": window,
           "window_s_floor": min_window_s,
           "window_s_median": round(statistics.median(window_s), 2),
           "rss_peak_mb": round(max(rss_w), 1)}
    if count_samples:
        sps_med, sps_iqr = _med_iqr(sps_w)
        out["samples_per_sec"] = round(sps_med, 2)
        out["samples_per_sec_iqr"] = sps_iqr
    return out


# Shared between the femnist submetric and the store_windowed A/B (they
# run back-to-back over the SAME federation): one store/api build + bucket
# warmup + synced measurement instead of two — duplicated minutes here are
# exactly what would push later sections past the wall-clock budget.
_femnist_state = {}


def _synthetic_femnist_store(n_clients, batch, seed=0):
    """FEMNIST-shaped synthetic streaming federation (28x28x1, 62
    classes, lognormal power-law-ish counts ≈140 samples/writer) —
    the SHARED builder for every store-backed FEMNIST section, so the
    windowed-FedOpt A/B can never silently drift from the federation
    shape its FedAvg comparison sections measure."""
    from fedml_tpu.data.store import FederatedStore

    rng = np.random.RandomState(seed)
    counts = np.maximum(1, rng.lognormal(3.6, 0.7, n_clients).astype(int))
    tot = int(counts.sum())
    x = rng.rand(tot, 28, 28, 1).astype(np.float32)
    y = rng.randint(0, 62, tot).astype(np.int32)
    edges = np.concatenate([[0], np.cumsum(counts)])
    parts = {c: np.arange(edges[c], edges[c + 1]) for c in range(n_clients)}
    return FederatedStore(x, y, parts, batch_size=batch), counts


def _femnist_3400_setup():
    """The FEMNIST-3400 streaming configuration (BASELINE.md shallow-NN
    row at its TRUE client count: 3400 writers, 10/round, batch 20,
    Reddi'20 CNN, power-law-ish counts) — built once, cached in
    ``_femnist_state`` for the store_windowed section."""
    if "api" in _femnist_state:
        return (_femnist_state["api"], _femnist_state["store"],
                _femnist_state["counts"], _femnist_state["cpr"],
                _femnist_state["batch"])
    from fedml_tpu.algos.config import FedConfig
    from fedml_tpu.algos.fedavg import FedAvgAPI
    from fedml_tpu.models.cnn import CNNDropOut

    n_clients, batch, cpr = 3400, 20, 10
    store, counts = _synthetic_femnist_store(n_clients, batch)
    # comm_round bounds prefetch (fedavg.py _stream_cohort only prefetches
    # while round_idx+1 < comm_round): the floor-calibrated windows run
    # well past 40 rounds, so keep the horizon above any window schedule
    # or the timed loop silently degrades to synchronous gathers mid-run.
    cfg = FedConfig(client_num_in_total=n_clients, client_num_per_round=cpr,
                    comm_round=100_000, epochs=1, batch_size=batch, lr=0.1)
    api = FedAvgAPI(CNNDropOut(num_classes=62), store, None, cfg)
    _warm_store_buckets(api, store, counts, cpr, batch)
    _femnist_state.update(api=api, store=store, counts=counts, cpr=cpr,
                          batch=batch)
    return api, store, counts, cpr, batch


def bench_femnist_cnn_3400():
    """FEMNIST-3400 streaming throughput (the configuration VERDICT r1
    flagged as never actually executed), synced per-round loop."""
    from fedml_tpu.models.cnn import CNNDropOut

    api, store, counts, cpr, batch = _femnist_3400_setup()
    timed = _timed_store_windows(api, store, count_samples=True)
    _femnist_state["synced"] = timed  # store_windowed's A/B denominator
    return {"clients": 3400, **timed,
            **_mfu_fields(CNNDropOut(num_classes=62),
                          np.zeros((batch, 28, 28, 1), np.float32),
                          timed.get("samples_per_sec"), batch),
            "host_dataset_mb": round(store.nbytes() / 1e6, 1)}


def _timed_windowed_blocks(api, window, blocks=3, min_block_s=4.0,
                           start_round=1, count_samples=False, store=None):
    """Median rounds/sec over ``blocks`` timed blocks of
    ``train_rounds_windowed`` calls, block length floor-calibrated like
    every other timed section (the block's trailing loss fetch is the
    windowed tier's natural sync cadence, so it belongs on the clock).
    ``count_samples`` (with ``store``) also reports samples/sec —
    cohorts re-derived from the seeded sampler exactly as
    ``_timed_store_windows`` does — so windowed sections can carry MFU
    submetrics."""
    floor_s = min_block_s * 2.0 / 3.0
    rounds, r = 4 * window, start_round

    def block_samples(r, rounds):
        if not count_samples:
            return 0
        counts = np.asarray(store.counts)
        return int(sum(
            counts[np.asarray(api._sample_round_uncached(rr)[0])].sum()
            for rr in range(r, r + rounds)))

    def run_block(r, rounds):
        _check_section_deadline()
        samples = block_samples(r, rounds)
        t0 = time.perf_counter()
        losses = api.train_rounds_windowed(rounds, start_round=r,
                                           window=window)
        dt = time.perf_counter() - t0
        assert np.isfinite(losses).all()
        return dt, samples

    # Same grow-then-verify calibration discipline as
    # _timed_store_windows: the first crossing can ride one-time warmup
    # (the window-scan compile lands in the first probe).
    for _ in range(5):
        dt, _ = run_block(r, rounds)
        r += rounds
        if dt >= min_block_s:
            dt2, _ = run_block(r, rounds)
            r += rounds
            if dt2 >= floor_s:
                break
            dt = dt2
        # Grow to a MULTIPLE of window: a remainder would run per-round
        # through the host loop inside every timed block, silently
        # diluting the windowed throughput this section exists to report.
        rounds = max(rounds + window,
                     int(np.ceil(rounds * min_block_s * 1.2 / dt)))
        rounds = -(-rounds // window) * window
    else:
        raise AssertionError(
            f"block calibration could not reach the {min_block_s:.1f}s "
            f"target (last block {rounds} rounds, {dt:.2f}s)")

    # Timed blocks run SANITIZED (obs.sanitizer): the transfer guard
    # makes any unplanned host<->device copy raise mid-block (the store's
    # staging H2D and the trailing loss fetch are marked planned), and
    # the compile counter reports whether the steady state re-traced.
    # Non-strict: on the power-law federation a late window can
    # legitimately surface a not-yet-seen window-max bucket (one fresh
    # scan executable) — that is a number to REPORT here, and a hard
    # zero to assert in tests/test_fedlint.py's uniform-bucket pin.
    from fedml_tpu.obs.sanitizer import sanitized

    rps, sps, block_s, rss_b = [], [], [], []
    with sanitized(strict=False) as san:
        for _ in range(blocks):
            dt, samples = run_block(r, rounds)
            rps.append(rounds / dt)
            sps.append(samples / dt)
            block_s.append(dt)
            rss_b.append(_rss_mb())  # one RSS sample per timed block
            r += rounds
    assert min(block_s) >= floor_s, block_s
    med, iqr = _med_iqr(rps)
    # Block lengths are window multiples, so every timed round rides a
    # scan by construction (api._window_stats would report coverage 1.0
    # tautologically — not a measurement, so not a metric).
    out = {"rounds_per_sec": round(med, 3), "rounds_per_sec_iqr": iqr,
           "block_rounds": rounds, "blocks": blocks,
           "steady_state_compiles": san.compiles,
           "rss_peak_mb": round(max(rss_b), 1)}
    if count_samples:
        sps_med, sps_iqr = _med_iqr(sps)
        out["samples_per_sec"] = round(sps_med, 2)
        out["samples_per_sec_iqr"] = sps_iqr
    return out


def bench_store_windowed():
    """Windowed vs synced streaming A/B on the FEMNIST-3400 config — the
    windowed execution tier's headline evidence. Synced: per-round host
    loop (one dispatch + one loss sync per round, prefetcher overlapping
    the next gather). Windowed: ``train_rounds_windowed`` — the next W
    same-bucket rounds' cohorts gathered as ONE superbatch, one H2D
    transfer, one lax.scan dispatch, host syncs amortized 1/W. Both sides
    measure the SAME store/model/config — the api/store build, bucket
    warmup, and the synced measurement are REUSED from the femnist
    section when it ran (one federation, one baseline; duplicating them
    is what would push later sections past the wall-clock budget). The
    timed blocks are window multiples, so every timed round rides a
    scan."""
    from fedml_tpu.models.cnn import CNNDropOut

    try:
        api, store, counts, cpr, batch = _femnist_3400_setup()
        window = 16
        synced = _femnist_state.get("synced")
        if synced is None:  # femnist section skipped/errored: own baseline
            synced = _timed_store_windows(api, store, windows=3,
                                          min_window_s=4.0)
        windowed = _timed_windowed_blocks(api, window, blocks=3,
                                          min_block_s=4.0,
                                          count_samples=True, store=store)
        return {"clients": 3400, "window": window,
                "synced_rounds_per_sec": synced["rounds_per_sec"],
                "synced_rounds_per_sec_iqr": synced["rounds_per_sec_iqr"],
                "windowed_rounds_per_sec": windowed["rounds_per_sec"],
                "windowed_rounds_per_sec_iqr":
                    windowed["rounds_per_sec_iqr"],
                "windowed_samples_per_sec": windowed.get("samples_per_sec"),
                **_mfu_fields(CNNDropOut(num_classes=62),
                              np.zeros((batch, 28, 28, 1), np.float32),
                              windowed.get("samples_per_sec"), batch),
                "block_rounds": windowed["block_rounds"],
                "steady_state_compiles": windowed["steady_state_compiles"],
                "speedup": round(windowed["rounds_per_sec"]
                                 / synced["rounds_per_sec"], 3)}
    finally:
        # Free the GB-scale host store before the later sections run.
        _femnist_state.clear()


def bench_store_windowed_fedopt():
    """Windowed FedOpt (server adam) A/B — the carry-protocol tier's
    headline evidence: W rounds per dispatch WITH the server optimizer
    state threaded through the scan carry, vs the same federation's
    per-round host loop. Before this tier, every adaptive-server run
    floored at dispatch RTT (the windowed guard rejected any
    _server_update override). Its own moderate federation (the 3400-
    client store is freed after its section; this one is sized to fit
    the per-section cap): 600 power-law writers, FEMNIST-shaped CNN,
    10 clients/round."""
    from fedml_tpu.algos.config import FedConfig
    from fedml_tpu.algos.fedopt import FedOptAPI
    from fedml_tpu.models.cnn import CNNDropOut

    n_clients, batch, cpr, window = 600, 20, 10, 16
    store, counts = _synthetic_femnist_store(n_clients, batch, seed=1)
    cfg = FedConfig(client_num_in_total=n_clients, client_num_per_round=cpr,
                    comm_round=100_000,  # > any window schedule (prefetch)
                    epochs=1, batch_size=batch, lr=0.1,
                    server_optimizer="adam", server_lr=0.01)
    api = FedOptAPI(CNNDropOut(num_classes=62), store, None, cfg)
    _warm_store_buckets(api, store, counts, cpr, batch)
    synced = _timed_store_windows(api, store, windows=3, min_window_s=3.0)
    windowed = _timed_windowed_blocks(api, window, blocks=3, min_block_s=3.0,
                                      count_samples=True, store=store)
    return {"clients": n_clients, "window": window,
            "server_optimizer": "adam",
            "synced_rounds_per_sec": synced["rounds_per_sec"],
            "synced_rounds_per_sec_iqr": synced["rounds_per_sec_iqr"],
            "windowed_rounds_per_sec": windowed["rounds_per_sec"],
            "windowed_rounds_per_sec_iqr": windowed["rounds_per_sec_iqr"],
            **_mfu_fields(CNNDropOut(num_classes=62),
                          np.zeros((batch, 28, 28, 1), np.float32),
                          windowed.get("samples_per_sec"), batch),
            "block_rounds": windowed["block_rounds"],
            "steady_state_compiles": windowed["steady_state_compiles"],
            "speedup": round(windowed["rounds_per_sec"]
                             / synced["rounds_per_sec"], 3)}


def bench_zoo_windowed():
    """Whole-zoo carry capability records (docs/EXECUTION.md generated
    matrix): the algorithms the windowed tier used to refuse now scan W
    rounds per dispatch. Two A/B arms measure the payoff on newly
    converted records — FedNova ("round" protocol, τ-normalized weights
    + γ riding the scanned aux slot) and FedDyn ("custom" protocol,
    server h + the client correction stack as the donated carry) — each
    windowed-vs-synced on a FEMNIST-shaped store federation, plus the
    accuracy-per-round arm: FedAc (arXiv:2006.08950) vs FedAvg on a
    LEARNABLE FEMNIST-shaped task at the same round budget, both running
    windowed (the acceleration is a pure carry, so better
    accuracy-per-round costs no throughput). Headline scalars:
    ``zoo_windowed_speedup`` (median windowed/synced across the
    converted arms) and ``fedac_acc_delta`` (FedAc − FedAvg held-out
    accuracy at the final shared eval round)."""
    from fedml_tpu.algos.config import FedConfig
    from fedml_tpu.algos.fedac import FedAcAPI
    from fedml_tpu.algos.fedavg import FedAvgAPI
    from fedml_tpu.algos.feddyn import FedDynAPI
    from fedml_tpu.algos.fednova import FedNovaAPI
    from fedml_tpu.data.store import FederatedStore
    from fedml_tpu.models.lr import LogisticRegression

    out = {}
    speedups = []

    # All arms run the FEMNIST-shaped LINEAR model: the windowed win is
    # host-sync amortization (most visible when the round's device work
    # is small — exactly the regime the converted zoo's tiny-model
    # members live in), and a conv model would spend the section cap
    # compiling per-bucket executables instead of measuring.
    def _ab_arm(api, store, counts, cpr, batch, window):
        _warm_store_buckets(api, store, counts, cpr, batch)
        synced = _timed_store_windows(api, store, windows=3,
                                      min_window_s=2.0)
        windowed = _timed_windowed_blocks(api, window, blocks=2,
                                          min_block_s=2.0)
        sp = round(windowed["rounds_per_sec"] / synced["rounds_per_sec"],
                   3)
        return synced, windowed, sp

    # --- arm 1: FedNova windowed vs synced ("round" + scanned aux) -----
    n_clients, batch, cpr, window = 300, 20, 10, 16
    store, counts = _synthetic_femnist_store(n_clients, batch, seed=2)
    cfg = FedConfig(client_num_in_total=n_clients, client_num_per_round=cpr,
                    comm_round=100_000,  # > any window schedule (prefetch)
                    epochs=1, batch_size=batch, lr=0.1)
    api = FedNovaAPI(LogisticRegression(num_classes=62), store, None, cfg)
    synced, windowed, sp = _ab_arm(api, store, counts, cpr, batch, window)
    speedups.append(sp)
    out.update(fednova_synced_rps=synced["rounds_per_sec"],
               fednova_windowed_rps=windowed["rounds_per_sec"],
               fednova_speedup=sp,
               fednova_steady_state_compiles=windowed[
                   "steady_state_compiles"])
    del api, store

    # --- arm 2: FedDyn windowed vs synced ("custom" carry stack) -------
    # The correction stack is O(total clients x model) device state —
    # the carry the scan donates round-to-round.
    _check_section_deadline()
    n_clients = 64
    store, counts = _synthetic_femnist_store(n_clients, batch, seed=3)
    cfg = FedConfig(client_num_in_total=n_clients, client_num_per_round=cpr,
                    comm_round=100_000, epochs=1, batch_size=batch, lr=0.05)
    api = FedDynAPI(LogisticRegression(num_classes=62), store, None, cfg,
                    alpha=0.05)
    synced, windowed, sp = _ab_arm(api, store, counts, cpr, batch, window)
    speedups.append(sp)
    out.update(feddyn_synced_rps=synced["rounds_per_sec"],
               feddyn_windowed_rps=windowed["rounds_per_sec"],
               feddyn_speedup=sp,
               feddyn_steady_state_compiles=windowed[
                   "steady_state_compiles"])
    del api, store
    out["zoo_windowed_speedup"] = round(float(np.median(speedups)), 3)

    # --- arm 3: FedAc vs FedAvg accuracy-per-round ---------------------
    # Learnable FEMNIST-shaped task (8 classes encoded as quadrant
    # offsets, weak enough signal that accuracy MOVES over the budget);
    # both arms run windowed with identical seeds/cohorts — the only
    # difference is the server carry. Measured on this config: FedAc
    # γ=2 reaches ~0.95 when FedAvg is at ~0.89 (delta ≈ +0.06 at the
    # final shared eval round, and the win holds POINTWISE along the
    # curve).
    _check_section_deadline()
    rng = np.random.RandomState(7)
    n_clients, per, classes = 64, 40, 8
    tot = n_clients * per
    y = rng.randint(0, classes, tot).astype(np.int32)
    x = (rng.rand(tot, 28, 28, 1) * 0.3).astype(np.float32)
    bits = np.stack([(y >> b) & 1 for b in range(3)], axis=1)
    x[:, :14, :14, 0] += 0.35 * bits[:, 0, None, None]
    x[:, 14:, :14, 0] += 0.35 * bits[:, 1, None, None]
    x[:, :14, 14:, 0] += 0.35 * bits[:, 2, None, None]
    parts = {c: np.arange(c * per, (c + 1) * per)
             for c in range(n_clients)}
    test_n = 256
    xt, yt = x[:test_n], y[:test_n]  # held-in probe (synthetic task)
    from fedml_tpu.data.batching import batch_global

    test_global = batch_global(xt, yt, 32)
    rounds, eval_every, win = 32, 8, 8

    def acc_curve(cls, **kw):
        cfg = FedConfig(client_num_in_total=n_clients,
                        client_num_per_round=8, comm_round=rounds + 1,
                        epochs=1, batch_size=20, lr=0.02,
                        frequency_of_the_test=1000)
        api = cls(LogisticRegression(num_classes=classes),
                  FederatedStore(x, y, parts, batch_size=20),
                  test_global, cfg, **kw)
        curve, r = [], 0
        while r < rounds:
            _check_section_deadline()
            api.train_rounds_windowed(eval_every, start_round=r,
                                      window=win)
            r += eval_every
            curve.append(round(api.evaluate()["accuracy"], 4))
        return curve

    fedavg_curve = acc_curve(FedAvgAPI)
    fedac_curve = acc_curve(FedAcAPI, gamma=2.0)
    out.update(fedavg_acc_curve=fedavg_curve, fedac_acc_curve=fedac_curve,
               acc_eval_every=eval_every, acc_rounds=rounds,
               fedac_final_acc=fedac_curve[-1],
               fedavg_final_acc=fedavg_curve[-1],
               fedac_acc_delta=round(fedac_curve[-1] - fedavg_curve[-1],
                                     4))
    return out


def bench_robust_agg():
    """Byzantine-robust aggregation cost (docs/ROBUSTNESS.md): windowed
    streaming rounds with aggregator ∈ {mean, coord_median, krum} on ONE
    moderate federation (300 power-law writers, FEMNIST-shaped CNN,
    10/round, window 8) — same store, same seeded cohorts, only the
    server reduction changes, so the RPS deltas are the aggregators'
    price. Sized to fit the per-section cap (three sides, each with its
    own warmup + floor-calibrated blocks). Headline scalar
    ``robust_agg_overhead`` = mean_rps / krum_rps — krum is the
    expensive end of the zoo (pairwise distances over the cohort), so
    this bounds what turning the defense on can cost."""
    from fedml_tpu.algos.config import FedConfig
    from fedml_tpu.algos.fedavg import FedAvgAPI
    from fedml_tpu.models.cnn import CNNDropOut

    n_clients, batch, cpr, window = 300, 20, 10, 8
    out = {"clients": n_clients, "window": window}
    rps = {}
    for agg in ("mean", "coord_median", "krum"):
        _check_section_deadline()
        store, counts = _synthetic_femnist_store(n_clients, batch, seed=2)
        cfg = FedConfig(client_num_in_total=n_clients,
                        client_num_per_round=cpr,
                        comm_round=100_000,  # > any window schedule
                        epochs=1, batch_size=batch, lr=0.1, aggregator=agg)
        api = FedAvgAPI(CNNDropOut(num_classes=62), store, None, cfg)
        _warm_store_buckets(api, store, counts, cpr, batch)
        timed = _timed_windowed_blocks(api, window, blocks=3,
                                       min_block_s=2.0)
        rps[agg] = timed["rounds_per_sec"]
        out[agg] = timed
    out["robust_agg_overhead"] = round(rps["mean"] / rps["krum"], 3)
    out["coord_median_overhead"] = round(rps["mean"] / rps["coord_median"],
                                         3)
    return out


def bench_chaos():
    """Control-plane resilience price (docs/ROBUSTNESS.md "Control
    plane"): every backend's ``send_message`` now runs through the
    unified RetryPolicy — this section measures what that wrapper costs
    on the CLEAN path (no faults, no retries), where it is pure
    overhead. A/B over the native TCP transport with a model-sized-ish
    64 KB payload: policy path = the production ``send_message``
    (serialize + RetryPolicy.run + one transport attempt); raw path =
    the same serialize + the same single attempt with the policy
    machinery bypassed. Headline scalar ``chaos_clean_overhead`` =
    policy_time / raw_time (1.0 = free). Also reports the
    ChaosTransport pass-through ratio with an all-zeros spec — the cost
    of LEAVING the drill wrapper installed in production."""
    import threading

    from fedml_tpu.comm.message import Message
    from fedml_tpu.comm.resilience import ChaosSpec, ChaosTransport
    from fedml_tpu.comm.tcp import TcpCommManager
    from fedml_tpu.comm.wire import serialize_message

    n_msgs, repeats = 400, 5
    table = {0: ("127.0.0.1", 0), 1: ("127.0.0.1", 0)}
    m0 = TcpCommManager(table, 0)
    m1 = TcpCommManager(table, 1)
    got = []

    class Obs:
        def receive_message(self, t, msg):
            got.append(t)

    m1.add_observer(Obs())
    rx = threading.Thread(target=m1.handle_receive_message, daemon=True)
    rx.start()
    msg = Message(type=3, sender_id=0, receiver_id=1)
    msg.add("round", 0)
    msg.add(Message.MSG_ARG_KEY_MODEL_PARAMS,
            {"w": np.zeros(16384, np.float32)})
    chaos_clean = ChaosTransport(m0, ChaosSpec(seed=0), rank=0)

    def _wait_drained(target):
        deadline = time.perf_counter() + 30
        while len(got) < target and time.perf_counter() < deadline:
            time.sleep(0.002)

    sent = [0]

    def timed(send_one):
        _check_section_deadline()
        t0 = time.perf_counter()
        for _ in range(n_msgs):
            send_one()
        dt = time.perf_counter() - t0  # sender-side cost only
        sent[0] += n_msgs
        _wait_drained(sent[0])  # isolate trials from each other (untimed)
        return dt

    def raw_send():
        blob = serialize_message(msg, m0._serializer)
        m0._send_once(1, *m0.ip_config[1], blob)

    try:
        raw_send()  # connect + warm both paths
        m0.send_message(msg)
        sent[0] = 2
        raw_t, policy_t, wrapped_t = [], [], []
        for _ in range(repeats):
            raw_t.append(timed(raw_send))
            policy_t.append(timed(lambda: m0.send_message(msg)))
            wrapped_t.append(timed(lambda: chaos_clean.send_message(msg)))
        raw_med, raw_iqr = _med_iqr(raw_t)
        pol_med, pol_iqr = _med_iqr(policy_t)
        wrap_med, _ = _med_iqr(wrapped_t)
    finally:
        m1.stop_receive_message()
        m0.close()
        m1.close()
    return {
        "messages_per_trial": n_msgs,
        "payload_bytes": 16384 * 4,
        "raw_send_s": round(raw_med, 4),
        "raw_send_s_iqr": raw_iqr,
        "policy_send_s": round(pol_med, 4),
        "policy_send_s_iqr": pol_iqr,
        "chaos_wrapped_send_s": round(wrap_med, 4),
        "delivered": len(got),
        "chaos_clean_overhead": round(pol_med / raw_med, 3),
        "chaos_wrapper_overhead": round(wrap_med / raw_med, 3),
        "send_retries_on_clean_path": m0.retry_count,
    }


def bench_wire_codec():
    """Compressed wire codec A/B (comm/codec.py + streaming ingest):
    bytes/upload and uploads/s for uncompressed vs bf16 vs int8 vs
    top-k+error-feedback on the loopback drill with the TENSOR wire
    round-trip live (bytes actually serialized, ByteLedger counted) and
    a ChaosTransport composed in (duplication + delay), so compression
    and fault injection are proven together — a duplicated compressed
    upload must stay idempotent at the server's streaming accumulator.

    Headline scalars: ``wire_bytes_ratio`` (uncompressed bytes/upload ÷
    top-k+EF bytes/upload — the bytes-on-wire reduction, acceptance
    floor 4x) and ``codec_acc_delta`` (top-k arm final accuracy −
    uncompressed arm; ~0 = compression is accuracy-free on this drill).
    """
    from fedml_tpu.algos.config import FedConfig
    from fedml_tpu.algos.fedavg_distributed import FedML_FedAvg_distributed
    from fedml_tpu.comm.resilience import ChaosSpec
    from fedml_tpu.data.batching import batch_global, build_federated_arrays
    from fedml_tpu.data.partition import partition_homo
    from fedml_tpu.models.lr import LogisticRegression

    # 784-d LR (MNIST-shaped): big enough that frame headers don't mask
    # the codec's ratio, small enough to jit+run 4 arms in seconds.
    C, D, K, rounds = 8, 784, 10, 8
    rng = np.random.RandomState(0)
    y = rng.randint(0, K, size=C * 64).astype(np.int32)
    protos = rng.randn(K, D).astype(np.float32)
    x = 0.8 * protos[y] + rng.randn(len(y), D).astype(np.float32)
    fed = build_federated_arrays(x, y, partition_homo(len(x), C),
                                 batch_size=16)
    test = batch_global(x[:256], y[:256], 64)
    cfg = FedConfig(client_num_in_total=C, client_num_per_round=4,
                    comm_round=rounds, epochs=1, batch_size=16, lr=0.2,
                    frequency_of_the_test=1000)

    arms = [("uncompressed", "none"), ("bf16", "bf16"), ("int8", "int8"),
            ("topk_ef", "topk0.05+int8")]
    out = {"rounds": rounds, "workers": cfg.client_num_per_round,
           "model_params": D * K + K, "wire": "tensor",
           "chaos": "dup_p=0.1 delay_p=0.1"}
    per_upload = {}
    for label, spec in arms:
        _check_section_deadline()
        t0 = time.perf_counter()
        # idle_timeout_s bounds the drill: a DELAYED terminal done whose
        # chaos timer dies with the server's transport close would
        # otherwise strand that worker's receive loop forever (and with
        # it this section, past any cap).
        agg = FedML_FedAvg_distributed(
            LogisticRegression(num_classes=K), fed, test, cfg,
            wire_codec=spec, loopback_wire="tensor",
            chaos=ChaosSpec(seed=11, dup_p=0.1, delay_p=0.1),
            idle_timeout_s=15.0)
        dt = time.perf_counter() - t0
        h = agg.test_history[-1] if agg.test_history else {}
        uploads = rounds * cfg.client_num_per_round
        # Uplink bytes: the server's ByteLedger rx total (heartbeats are
        # off here, so rx ≈ uploads — including chaos duplicates, which
        # honestly cross the wire twice), from the final health snapshot
        # the runner stamps on the aggregator.
        rx = agg.final_health["bytes_rx"]
        per_upload[label] = rx / max(uploads, 1)
        out[label] = {
            "bytes_rx_total": int(rx),
            "bytes_per_upload": round(per_upload[label], 1),
            "uploads_per_sec": round(uploads / dt, 2),
            "final_accuracy": round(float(h.get("accuracy", 0.0)), 4),
            "duplicate_drops": agg.final_health["duplicate_drops"],
        }
    out["wire_bytes_ratio"] = round(
        per_upload["uncompressed"] / max(per_upload["topk_ef"], 1e-9), 2)
    out["codec_acc_delta"] = round(
        out["topk_ef"]["final_accuracy"]
        - out["uncompressed"]["final_accuracy"], 4)
    return out


def bench_ingest_profile(C=8, D=4096, K=10, rounds=6):
    """The measured ruler for the server-ingest wall (ROADMAP item 1;
    arXiv:2307.06561 frames server ingest as *the* FL bottleneck): every
    upload funnels through ONE single-threaded dispatch loop doing
    decode + fold. This section runs the loopback ``topk+int8`` chaos
    drill with the ingest registry live (obs/registry.py; always on —
    the span tracer stays off, so this is the production-cost path) and
    reports WHERE an upload's server time goes:

    - ``ingest_occupancy`` (headline): dispatch-thread busy seconds over
      the first→last-message span — measured 0.78 in r11, the baseline
      the parallel ingest pool must drive DOWN at the same offered load;
    - decode/fold p50/p95 milliseconds + bytes/upload from the
      per-upload histograms (log-bucketed, ≤~9% quantile error);
    - a ``pooled`` arm (r12): the IDENTICAL drill with
      ``cfg.ingest_workers=2`` — decode+fold move to the pool
      (comm/ingest.py), so the before/after of the dispatch-thread
      occupancy is visible in one ruler. The serving-scale saturation
      curve lives in the ``serving_1m`` section.

    The model is deliberately bigger than the wire_codec section's
    (D=4096: ~41k params) so decode/fold cost is measurable above
    header noise while the section stays seconds-scale."""
    import dataclasses

    from fedml_tpu.algos.config import FedConfig
    from fedml_tpu.algos.fedavg_distributed import FedML_FedAvg_distributed
    from fedml_tpu.comm.resilience import ChaosSpec
    from fedml_tpu.data.batching import batch_global, build_federated_arrays
    from fedml_tpu.data.partition import partition_homo
    from fedml_tpu.models.lr import LogisticRegression

    rng = np.random.RandomState(0)
    y = rng.randint(0, K, size=C * 32).astype(np.int32)
    protos = rng.randn(K, D).astype(np.float32)
    x = 0.8 * protos[y] + rng.randn(len(y), D).astype(np.float32)
    fed = build_federated_arrays(x, y, partition_homo(len(x), C),
                                 batch_size=16)
    test = batch_global(x[:128], y[:128], 64)
    cfg = FedConfig(client_num_in_total=C, client_num_per_round=4,
                    comm_round=rounds, epochs=1, batch_size=16, lr=0.2,
                    frequency_of_the_test=1000)

    def drill(cfg):
        _check_section_deadline()
        t0 = time.perf_counter()
        # Same drill shape as wire_codec: tensor wire round-trip + chaos
        # (dup+delay), idle_timeout_s bounding chaos-stranded workers.
        agg = FedML_FedAvg_distributed(
            LogisticRegression(num_classes=K), fed, test, cfg,
            wire_codec="topk0.05+int8", loopback_wire="tensor",
            chaos=ChaosSpec(seed=11, dup_p=0.1, delay_p=0.1),
            idle_timeout_s=15.0)
        dt = time.perf_counter() - t0
        prof = dict(agg.ingest_profile)
        uploads = int(prof.get("uploads") or 0)
        return {
            "uploads_per_sec": round(uploads / dt, 2) if dt > 0 else None,
            "final_accuracy": round(float(
                (agg.test_history[-1] if agg.test_history else {}).get(
                    "accuracy", 0.0)), 4),
            **prof,
        }

    out = {
        "rounds": rounds, "workers": cfg.client_num_per_round,
        "model_params": D * K + K, "wire": "tensor",
        "codec": "topk0.05+int8", "chaos": "dup_p=0.1 delay_p=0.1",
        **drill(cfg),
        "pooled": drill(dataclasses.replace(cfg, ingest_workers=2)),
    }
    base, pooled = out.get("ingest_occupancy"), \
        out["pooled"].get("ingest_occupancy")
    out["pooled_occupancy_delta"] = (round(pooled - base, 4)
                                     if base is not None
                                     and pooled is not None else None)
    return out


def bench_serving_1m(C=1_048_576, G=64, n_devices=32, features=32,
                     classes=32_768, horizon_s=900.0, buffer_k=32,
                     saturation_uploads=480, workers_arms=(0, 1, 2, 4)):
    """The COMPOSED 1M-device serving drill (ROADMAP item 1): the three
    subsystems built since the last re-anchor run as ONE system, then
    the server-ingest wall they expose is broken with the parallel
    ingest pool (comm/ingest.py) and the break is measured.

    **Composition** — a diurnal-churn fleet of ``n_devices`` active
    device ranks serving a 2^20-client population: ``ClientDirectory``
    (PR 7) owns the million-client count metadata and samples every
    assignment; ``ShardedFederatedStore`` (PR 7) holds the population's
    data in G memmap-spilled shards (gathers page in only assigned
    clients); devices ship ``topk0.05+int8`` error-feedback deltas
    (PR 10's codec) over the SIM tensor wire (bytes counted per rank)
    into the FedBuff buffered server (PR 6) under ChaosTransport
    dup+delay — replayed on the virtual clock, so the same seed is
    event-for-event reproducible. Reported: uploads/s (virtual),
    bytes/s, staleness tails, evictions, churn-killed uploads, and host
    RSS (the memory axis). The drill runs twice — ``ingest_workers`` 1
    and 2 — and pins the pooled mean's interleaving-invariance at this
    scale: ``sim_nets_bitequal`` is the bit-comparison of the two final
    nets.

    **Ingest saturation** — the SIM replays client work on one event
    thread, so wall-clock uploads/s there measures the GIL, not the
    server. The saturation curve instead drives the SERVER ALONE at
    offered load (the fake-clock protocol-test pattern: pre-encoded
    topk+int8 frames of the same 1M-param model fed straight into the
    real ``FedBuffServerManager`` handler): ``uploads_per_sec`` vs
    ``ingest_workers`` ∈ {0, 1, 2, 4}, where workers=0 is the inline
    r11 baseline (``ingest_occupancy`` ≈ 1: the dispatch thread IS the
    wall) and the pool arms move decode+fold off the dispatch thread.
    Headline scalars: ``uploads_per_sec`` (the 4-worker arm) and
    ``ingest_speedup_4v1``."""
    import dataclasses
    import shutil
    import tempfile

    import jax

    from fedml_tpu.algos.config import FedConfig
    from fedml_tpu.algos.fedbuff import FedBuffServerManager
    from fedml_tpu.algos.fedasync import (MSG_ARG_KEY_MODEL_VERSION,
                                          MSG_ARG_KEY_TASK_SEQ)
    from fedml_tpu.algos.fedavg_distributed import (
        MSG_TYPE_C2S_SEND_MODEL_TO_SERVER)
    from fedml_tpu.comm.codec import CODEC_KEY, make_wire_codec, tree_spec
    from fedml_tpu.comm.loopback import LoopbackNetwork
    from fedml_tpu.comm.message import Message
    from fedml_tpu.comm.resilience import ChaosSpec
    from fedml_tpu.data.directory import ShardedFederatedStore
    from fedml_tpu.models.lr import LogisticRegression
    from fedml_tpu.sim import (FleetSimulator, FleetSpec, StoreFleetData,
                               make_fleet_trace)

    codec_spec = "topk0.05+int8"
    model = LogisticRegression(num_classes=classes)
    n_params = features * classes + classes
    out = {"clients": C, "shards": G, "devices": n_devices,
           "model_params": n_params, "codec": codec_spec, "wire": "tensor",
           "buffer_k": buffer_k, "chaos": "dup_p=0.05 delay_p=0.05",
           "virtual_horizon_s": horizon_s}

    # -- the 2^20-client population: directory + memmap-sharded store ----
    sizes = [C // G + (1 if s < C % G else 0) for s in range(G)]

    def builder(s):
        rng = np.random.RandomState(77_000 + s)
        n = sizes[s]
        counts = np.full(n, 2, np.int64)  # 2 samples per client
        tot = 2 * n
        return (rng.randn(tot, features).astype(np.float32),
                rng.randint(0, classes, tot).astype(np.int32), counts)

    spill = tempfile.mkdtemp(prefix="bench_serving1m_")
    try:
        t0 = time.perf_counter()
        store = ShardedFederatedStore.from_shard_builder(
            builder, G, batch_size=2, spill_dir=spill,
            progress=lambda s: _check_section_deadline())
        out["store_build_s"] = round(time.perf_counter() - t0, 1)
        out["dataset_disk_mb"] = round(store.nbytes() / 1e6, 1)
        out["directory_mb"] = round(store.directory.nbytes() / 1e6, 2)
        data = StoreFleetData(store)

        # -- composed SIM drill: churn × codec × chaos × pool ------------
        spec = FleetSpec(n_devices=n_devices, seed=11, horizon_s=horizon_s,
                         mean_online=0.8, base_round_s=30.0, slot_s=120.0,
                         speed_alpha=1.5, diurnal_amplitude=0.4,
                         diurnal_period_s=2400.0, arrival_spread_s=60.0)
        trace = make_fleet_trace(spec)
        cfg0 = FedConfig(client_num_in_total=C,
                         client_num_per_round=n_devices,
                         comm_round=10 ** 9, epochs=1, batch_size=2,
                         lr=0.05, frequency_of_the_test=10 ** 9)
        sim_nets = []
        for w in (1, 2):
            _check_section_deadline()
            sim = FleetSimulator(
                model, data, None,
                dataclasses.replace(cfg0, ingest_workers=w), trace,
                mode="fedbuff", buffer_k=buffer_k, wire_codec=codec_spec,
                sim_wire="tensor",
                chaos=ChaosSpec(seed=11, dup_p=0.05, delay_p=0.05),
                directory=store.directory)
            # Warm the shared jit cache outside the timed window.
            c0 = int(store.directory.sample_cohort(0, 1)[0])
            jax.block_until_ready(sim.local_train(
                sim.net0, data.x[c0], data.y[c0], data.mask[c0],
                jax.random.PRNGKey(0))[0])
            t0 = time.perf_counter()
            res = sim.run()
            dt = time.perf_counter() - t0
            uploads = len(res.arrival_log)
            h = sim.server.health()
            s = res.summary()
            virt = max(res.virtual_s, 1e-9)
            sim_nets.append(sim.server.net)
            out[f"sim_workers_{w}"] = {
                "uploads": uploads, "wall_s": round(dt, 2),
                "updates": res.updates,
                "uploads_per_vmin": round(60.0 * uploads / virt, 2),
                "bytes_rx_total": h["bytes_rx"],
                "bytes_per_upload": round(h["bytes_rx"] / max(uploads, 1),
                                          1),
                "bytes_per_vsec": round(h["bytes_rx"] / virt, 1),
                "staleness_p50": s.get("staleness_p50"),
                "staleness_p95": s.get("staleness_p95"),
                "staleness_max": s.get("staleness_max"),
                "evictions": s["evictions"],
                "churn_killed_uploads": s["churn_killed_uploads"],
                "host_rss_mb": s["host_rss_mb"],
            }
        out["sim_nets_bitequal"] = bool(all(
            np.array_equal(np.asarray(a), np.asarray(b))
            for a, b in zip(jax.tree.leaves(sim_nets[0]),
                            jax.tree.leaves(sim_nets[1]))))

        # -- ingest-saturation curve: the server alone at offered load --
        rng = np.random.RandomState(5)
        # The servers start from the composed drill's final net (host
        # numpy copy) — same shapes as the frames, zero extra init cost.
        net0 = jax.tree.map(np.asarray, sim_nets[0])
        spec_tree = tree_spec(net0)
        codec = make_wire_codec(codec_spec)
        frames = []
        for r in range(min(n_devices, 8)):
            delta = jax.tree.map(
                lambda l: (0.01 * rng.randn(*np.shape(l))).astype(
                    np.float32), net0)
            frames.append(codec.encode(delta, None, 1000 + r)[0])

        def saturation_arm(workers):
            _check_section_deadline()
            class A:  # the fake-clock protocol-test shim
                pass

            a = A()
            a.chaos = None
            a.network = LoopbackNetwork(n_devices + 1)
            # Full participation here (client_num_in_total = the device
            # count): the saturation sub-drill isolates the INGEST path,
            # and the per-version 2^20-population cohort draw is ~19 ms
            # of unrelated dispatch-thread work per flush that would
            # blur the curve. The composed SIM arms above keep the full
            # 1M directory sampling in the loop.
            cfg = dataclasses.replace(cfg0, ingest_workers=workers,
                                      client_num_in_total=n_devices)
            srv = FedBuffServerManager(a, net0, cfg, n_devices + 1,
                                       buffer_k=buffer_k)
            srv.register_message_receive_handlers()
            seqs = {}
            t0 = time.perf_counter()
            for i in range(saturation_uploads):
                worker = 1 + (i % n_devices)
                m = Message(MSG_TYPE_C2S_SEND_MODEL_TO_SERVER, worker, 0)
                m.add(Message.MSG_ARG_KEY_MODEL_PARAMS,
                      frames[i % len(frames)])
                m.add(CODEC_KEY, codec_spec)
                m.add(MSG_ARG_KEY_MODEL_VERSION, srv.version)
                m.add(MSG_ARG_KEY_TASK_SEQ, seqs.get(worker, 0))
                seqs[worker] = seqs.get(worker, 0) + 1
                # Through receive_message, not the bare handler: the
                # dispatch-thread occupancy clock lives there.
                srv.receive_message(MSG_TYPE_C2S_SEND_MODEL_TO_SERVER, m)
            if srv._pool is not None:
                srv._pool.drain()
            dt = time.perf_counter() - t0
            prof = srv.ingest_profile()
            pool = prof.get("ingest_pool") or {}
            occ = pool.get("occupancy_per_worker")
            arm = {
                "uploads": saturation_uploads, "wall_s": round(dt, 2),
                "uploads_per_sec": round(saturation_uploads / dt, 1),
                "versions": srv.version,
                "ingest_occupancy": prof.get("ingest_occupancy"),
                "pool_occupancy_mean": (round(float(np.mean(occ)), 4)
                                        if occ else None),
                "pool_task_ms_p50": prof.get("pool_task_ms_p50"),
            }
            if srv._pool is not None:
                srv._pool.close()
            return arm

        sat = {f"workers_{w}": saturation_arm(w) for w in workers_arms}
        out["saturation"] = sat
        u1 = sat.get("workers_1", {}).get("uploads_per_sec")
        u4 = sat.get("workers_4", {}).get("uploads_per_sec")
        out["uploads_per_sec"] = u4
        out["ingest_speedup_4v1"] = (round(u4 / u1, 2)
                                     if u1 and u4 else None)
        u0 = sat.get("workers_0", {}).get("uploads_per_sec")
        out["ingest_speedup_4v0"] = (round(u4 / u0, 2)
                                     if u0 and u4 else None)
        # -- adapter arm (PR 15): the same churn × codec × pool × chaos
        # composition shipping ADAPTER-only topk+int8 EF deltas from a
        # frozen-base transformer. Degraded to an error record instead
        # of discarding the measured scalars above (the PR 7
        # gather_probe_error discipline).
        try:
            out["adapter_arm"] = _serving_adapter_arm()
        except Exception as e:
            out["adapter_arm"] = {"error": f"{type(e).__name__}: {e}"[:200]}
        return out
    finally:
        shutil.rmtree(spill, ignore_errors=True)


def _serving_adapter_arm(n_devices=8, horizon_s=600.0, rank=8,
                         d_model=64, vocab=2004, seq_len=20):
    """serving_1m's adapter arm: a diurnal-churn FedBuff fleet of
    frozen-base transformers shipping adapter-only ``topk0.05+int8`` EF
    deltas through the 2-worker ingest pool over the SIM tensor wire
    under ChaosTransport — the million-client drill's composition with
    the upload shrunk by the rank ratio BEFORE the codec runs."""
    from functools import partial

    import jax
    import jax.numpy as jnp

    from fedml_tpu.algos.config import FedConfig
    from fedml_tpu.comm.resilience import ChaosSpec
    from fedml_tpu.data.batching import build_federated_arrays
    from fedml_tpu.data.synthetic import make_stackoverflow_nwp
    from fedml_tpu.models import create_model
    from fedml_tpu.models.adapter import param_count
    from fedml_tpu.sim import FleetSimulator, FleetSpec, make_fleet_trace
    from fedml_tpu.trainer.local import model_fns, seq_softmax_ce

    _check_section_deadline()
    model = create_model("transformer_lm", vocab_size=vocab,
                         d_model=d_model, n_heads=4, n_layers=2,
                         max_len=seq_len, adapter_rank=rank)
    x, y, parts = make_stackoverflow_nwp(64, seq_len=seq_len, vocab=vocab,
                                         seed=3)
    fed = build_federated_arrays(x, y, parts, 2)
    cfg = FedConfig(client_num_in_total=64, client_num_per_round=n_devices,
                    comm_round=10 ** 9, epochs=1, batch_size=2, lr=0.05,
                    frequency_of_the_test=10 ** 9, adapter_rank=rank,
                    ingest_workers=2)
    spec = FleetSpec(n_devices=n_devices, seed=11, horizon_s=horizon_s,
                     mean_online=0.8, base_round_s=30.0, slot_s=120.0,
                     speed_alpha=1.5, diurnal_amplitude=0.4,
                     diurnal_period_s=2400.0, arrival_spread_s=60.0)
    sim = FleetSimulator(model, fed, None, cfg, make_fleet_trace(spec),
                         mode="fedbuff", buffer_k=4,
                         wire_codec="topk0.05+int8", sim_wire="tensor",
                         chaos=ChaosSpec(seed=11, dup_p=0.05, delay_p=0.05),
                         loss_fn=partial(seq_softmax_ce, pad_id=0))
    jax.block_until_ready(sim.local_train(
        sim.net0, fed.x[0], fed.y[0], fed.mask[0],
        jax.random.PRNGKey(0))[0])  # jit warm, outside the timed window
    t0 = time.perf_counter()
    res = sim.run()
    dt = time.perf_counter() - t0
    uploads = len(res.arrival_log)
    h = sim.server.health()
    s = res.summary()
    adapter_params = param_count(sim.net0.params)
    dense_params = param_count(model_fns(
        create_model("transformer_lm", vocab_size=vocab, d_model=d_model,
                     n_heads=4, n_layers=2, max_len=seq_len)).init(
        jax.random.PRNGKey(0), jnp.zeros((1, seq_len), jnp.int32)).params)
    bpu = h["bytes_rx"] / max(uploads, 1)
    return {
        "devices": n_devices, "rank": rank,
        "adapter_params": adapter_params, "dense_params": dense_params,
        "codec": "topk0.05+int8", "ingest_workers": 2,
        "uploads": uploads, "wall_s": round(dt, 2),
        "updates": res.updates,
        "bytes_per_upload": round(bpu, 1),
        "bytes_vs_dense_wire": round(4.0 * dense_params / max(bpu, 1e-9),
                                     1),
        "staleness_p95": s.get("staleness_p95"),
        "evictions": s["evictions"],
        "churn_killed_uploads": s["churn_killed_uploads"],
        "codec_refusals": h["codec_refusals"],
        "host_rss_mb": s["host_rss_mb"],
    }


def bench_agg_shards(n_workers=32, rounds=3, features=32, classes=8192,
                     shard_arms=(1, 2, 4)):
    """The r16 sharded aggregation plane (comm/shardplane.py): M
    ``AggregatorShardManager`` ranks each decode+fold their client
    partition and ship ONE int64 fixed-point partial per flush; the
    rank-0 coordinator wire-merges the M partials through the same
    ``finalize_partial_mean`` division site as the in-process pool
    (bit-equality by construction — pinned in tests/test_shardplane.py).

    Each arm runs the REAL loopback federation control plane — live
    receive loops for the coordinator and the M shards — at offered
    load: driver threads play the workers, posting pre-encoded
    ``topk0.05+int8`` DELTA frames of a ~270k-param model straight into
    the routed shard's inbox the instant the new round's anchor lands
    (no local training in the loop, so uploads/s measures the
    aggregation plane alone). Reported per arm: uploads/s, the
    coordinator's dispatch-thread occupancy (the scale-out claim: the
    coordinator folds NOTHING — its per-upload cost is one ACCEPT
    notice, so occupancy stays low while the shards carry decode+fold),
    per-shard pool occupancy, and the health rollups. Headline pair:
    ``speedup_4v1`` (target ≥ 1.5 — thread-parallel shard folds, so the
    measured value is bounded by ``cpu_count``, recorded alongside) and
    ``coord_occupancy_m4`` (target < 0.5)."""
    import os

    from fedml_tpu.algos.config import FedConfig
    from fedml_tpu.algos.fedavg_distributed import (
        MSG_TYPE_C2S_SEND_MODEL_TO_SERVER, FedAVGAggregator)
    from fedml_tpu.comm.codec import CODEC_KEY, make_wire_codec
    from fedml_tpu.comm.loopback import (LoopbackCommManager,
                                         LoopbackNetwork, run_workers)
    from fedml_tpu.comm.message import Message
    from fedml_tpu.comm.shardplane import (AggregatorShardManager,
                                           ShardedFedAVGServerManager)

    codec_spec = "topk0.05+int8"
    n_params = features * classes + classes
    rng = np.random.RandomState(3)
    net0 = {"b": np.zeros(classes, np.float32),
            "w": np.zeros((features, classes), np.float32)}
    codec = make_wire_codec(codec_spec)
    frames = [codec.encode(
        {"b": (0.01 * rng.randn(classes)).astype(np.float32),
         "w": (0.01 * rng.randn(features, classes)).astype(np.float32)},
        None, 300 + s)[0] for s in range(min(n_workers, 8))]
    cfg = FedConfig(client_num_in_total=n_workers,
                    client_num_per_round=n_workers, comm_round=rounds,
                    epochs=1, batch_size=2, lr=0.05,
                    frequency_of_the_test=10 ** 9, ingest_workers=1)

    def arm(m):
        _check_section_deadline()

        class A:  # the protocol-shim args surface
            pass

        a = A()
        a.chaos = None
        size = n_workers + m + 1
        a.network = LoopbackNetwork(size)
        agg = FedAVGAggregator(net0, n_workers, cfg)
        srv = ShardedFedAVGServerManager(a, agg, cfg, size, m)
        shards = [AggregatorShardManager(a, r, size, cfg, net0)
                  for r in range(1, m + 1)]

        def driver(worker):
            com = LoopbackCommManager(a.network, worker)
            slot = worker - m - 1
            for r in range(rounds):
                # The anchor-before-upload fence, driver-side: post only
                # once the ROUTED shard adopted round r (in the real
                # federation local training provides this slack).
                sh = shards[slot % m]
                while (sh.round_idx < r or srv.round_idx < r) \
                        and not srv._stopped:
                    time.sleep(0.0005)
                if srv._stopped:
                    return
                msg = Message(MSG_TYPE_C2S_SEND_MODEL_TO_SERVER, worker,
                              sh.rank)
                msg.add(Message.MSG_ARG_KEY_MODEL_PARAMS,
                        frames[slot % len(frames)])
                msg.add(CODEC_KEY, codec_spec)
                msg.add(Message.MSG_ARG_KEY_NUM_SAMPLES, 2)
                msg.add("round", r)
                msg.add("epoch", 0)
                com.send_message(msg)

        t0 = time.perf_counter()
        run_workers([srv.run] + [sh.run for sh in shards]
                    + [lambda w=w: driver(w)
                       for w in range(m + 1, size)])
        dt = time.perf_counter() - t0
        uploads = rounds * n_workers
        h = srv.health()
        prof = srv.ingest_profile()
        shard_occ = [sh.ingest_profile().get("ingest_occupancy")
                     for sh in shards]
        shard_occ = [o for o in shard_occ if o is not None]
        return {
            "uploads": uploads, "wall_s": round(dt, 2),
            "uploads_per_sec": round(uploads / dt, 1),
            "rounds": srv.round_idx,
            "coord_occupancy": prof.get("ingest_occupancy"),
            "shard_occupancy_mean": (round(float(np.mean(shard_occ)), 4)
                                     if shard_occ else None),
            "shard_evictions": h["shard_evictions"],
            "bytes_rx_total": h["bytes_rx"],
        }

    out = {"workers": n_workers, "rounds": rounds,
           "model_params": n_params, "codec": codec_spec,
           "cpu_count": os.cpu_count(),
           **{f"shards_{m}": arm(m) for m in shard_arms}}
    u1 = out.get("shards_1", {}).get("uploads_per_sec")
    u4 = out.get("shards_4", {}).get("uploads_per_sec")
    out["speedup_4v1"] = round(u4 / u1, 2) if u1 and u4 else None
    out["coord_occupancy_m4"] = out.get("shards_4", {}).get(
        "coord_occupancy")
    return out


def bench_secagg(C=8, D=784, K=10, rounds=6):
    """Dropout-robust secure aggregation (comm/secagg.py, r19): the
    masked arm runs the SAME ``topk0.05+int8`` delta federation under
    ChaosTransport as the plain arm — pairwise seed-expanded masks over
    the fixed-point int64 contributions, cancelled exactly in the
    pooled fold — so the uploads/s ratio IS the masking cost (the
    DH/Shamir handshake round, per-upload self-decode + mask expansion,
    and the masked frames' dense int64 wire payload; the bytes ruler is
    honest about that last part — masking trades the sparsifier's wire
    ratio for the privacy bound, and only the adapter scope shrinks the
    MASKED payload). Headline scalar ``secagg_overhead`` = plain ÷
    masked uploads/s, target ≤ 1.3x. A third mini-drill kills one
    roster client mid-federation: heartbeat eviction triggers the
    t-of-n Shamir seed reveal, the round commits over survivors, and
    the server's ``secagg_reveal_ms`` histogram supplies the
    reveal-latency submetric."""
    from fedml_tpu.algos.config import FedConfig
    from fedml_tpu.algos.fedavg_distributed import (
        FedAVGAggregator, FedAVGClientManager, FedAVGServerManager,
        FedML_FedAvg_distributed, build_federation_setup)
    from fedml_tpu.comm.loopback import run_workers
    from fedml_tpu.comm.resilience import ChaosSpec
    from fedml_tpu.data.batching import batch_global, build_federated_arrays
    from fedml_tpu.data.partition import partition_homo
    from fedml_tpu.models.lr import LogisticRegression
    from fedml_tpu.trainer.local import softmax_ce

    rng = np.random.RandomState(0)
    y = rng.randint(0, K, size=C * 64).astype(np.int32)
    protos = rng.randn(K, D).astype(np.float32)
    x = 0.8 * protos[y] + rng.randn(len(y), D).astype(np.float32)
    fed = build_federated_arrays(x, y, partition_homo(len(x), C),
                                 batch_size=16)
    test = batch_global(x[:256], y[:256], 64)

    out = {"rounds": rounds, "workers": 4, "model_params": D * K + K,
           "codec": "topk0.05+int8", "chaos": "dup_p=0.1 delay_p=0.1"}
    per_ups = {}
    for label, masked in (("plain", False), ("masked", True)):
        _check_section_deadline()
        cfg = FedConfig(client_num_in_total=C, client_num_per_round=4,
                        comm_round=rounds, epochs=1, batch_size=16,
                        lr=0.2, frequency_of_the_test=1000,
                        ingest_workers=1, secagg=masked)
        t0 = time.perf_counter()
        agg = FedML_FedAvg_distributed(
            LogisticRegression(num_classes=K), fed, test, cfg,
            wire_codec="topk0.05+int8", loopback_wire="tensor",
            chaos=ChaosSpec(seed=11, dup_p=0.1, delay_p=0.1),
            idle_timeout_s=15.0)
        dt = time.perf_counter() - t0
        uploads = rounds * cfg.client_num_per_round
        per_ups[label] = uploads / dt
        h = agg.final_health
        out[label] = {
            "uploads_per_sec": round(per_ups[label], 2),
            "bytes_per_upload": round(
                h["bytes_rx"] / max(uploads, 1), 1),
            "duplicate_drops": h["duplicate_drops"],
            "seed_reveals": h.get("seed_reveals", 0),
            "final_accuracy": round(float(
                (agg.test_history[-1] if agg.test_history
                 else {}).get("accuracy", 0.0)), 4),
        }
    out["secagg_overhead"] = round(
        per_ups["plain"] / max(per_ups["masked"], 1e-9), 2)

    # The seed-reveal drill: 4 roster workers, one goes silent inside
    # round 1 (its local step outlasts the round deadline and its beats
    # stop) — the watchdog evicts it, >=t survivors return Shamir
    # shares, the orphaned masks are subtracted, the round commits.
    _check_section_deadline()
    cfgd = FedConfig(client_num_in_total=4, client_num_per_round=4,
                     comm_round=3, epochs=1, batch_size=16, lr=0.2,
                     frequency_of_the_test=10 ** 6, ingest_workers=1,
                     heartbeat_interval_s=0.05, secagg=True)
    size, net0, local_train, eval_fn, args = build_federation_setup(
        LogisticRegression(num_classes=K),
        build_federated_arrays(x[:256], y[:256],
                               partition_homo(256, 4), batch_size=16),
        None, cfgd, "LOOPBACK", softmax_ce)
    srv = FedAVGServerManager(args, FedAVGAggregator(net0, size - 1, cfgd),
                              cfgd, size, round_timeout_s=1.5,
                              heartbeat_timeout_s=0.4)

    def victim_train(*a, **kw):
        if srv.round_idx >= 1:
            time.sleep(3.5)  # outlast the 1.5s round deadline
        return local_train(*a, **kw)

    fed4 = build_federated_arrays(x[:256], y[:256], partition_homo(256, 4),
                                  batch_size=16)
    clients = [FedAVGClientManager(args, r, size, fed4,
                                   (victim_train if r == 1
                                    else local_train), cfgd)
               for r in range(1, size)]

    def killer():
        deadline = time.monotonic() + 20.0
        while srv.round_idx < 1 and time.monotonic() < deadline:
            time.sleep(0.01)
        clients[0].finish()  # beats stop: the watchdog owns it now

    run_workers([srv.run] + [c.run for c in clients] + [killer])
    snap = srv._h_reveal.snapshot()
    out["reveal_drill"] = {
        "rounds": srv.round_idx, "aborted": srv.aborted,
        "evictions": srv.health()["evictions"],
        "seed_reveals": srv.seed_reveals,
        "reveal_ms_p50": snap.get("p50"),
        "reveal_ms_max": snap.get("max"),
    }
    return out


def bench_serving_10m(C=2 ** 23, G=128, M=4, features=4, classes=64,
                      cohorts=32, cohort_size=1024):
    """The 10M-client serving drill (r16): the 2^23-client population
    lives in a ``ShardedFederatedStore`` (memmap spill — host RSS stays
    O(active cohort), not O(population)), its ``ClientDirectory`` owns
    the counts/shard metadata, and every cohort draw is routed onto the
    M=4 aggregator shards by ``directory.agg_shard_of`` (data-shard
    locality: clients of one store shard land on one aggregator shard).
    Measured: store build + disk/directory footprint at 8.4M clients,
    cohort-draw and shard-routing microseconds per client, the routing
    balance across shards, gather page-in for one cohort, and a
    directory-routed M-shard fold round — cohort uploads folded into
    per-shard int64 partials, wire-encoded, merged, finalized (the
    shardplane commit path) — as uploads/s. The full federation fabric
    at this population rides ``agg_shards``/``serving_1m``; this section
    pins the POPULATION axis: 8x serving_1m's 2^20."""
    import shutil
    import tempfile

    from fedml_tpu.comm.ingest import (PartialAccumulator,
                                       finalize_partial_mean)
    from fedml_tpu.comm.shardplane import decode_partial, encode_partial
    from fedml_tpu.data.directory import ShardedFederatedStore
    from fedml_tpu.sim import StoreFleetData

    sizes = [C // G + (1 if s < C % G else 0) for s in range(G)]

    def builder(s):
        rng = np.random.RandomState(88_000 + s)
        n = sizes[s]
        counts = np.ones(n, np.int64)  # 1 sample per client
        return (rng.randn(n, features).astype(np.float32),
                rng.randint(0, classes, n).astype(np.int32), counts)

    out = {"clients": C, "store_shards": G, "agg_shards": M,
           "features": features}
    spill = tempfile.mkdtemp(prefix="bench_serving10m_")
    try:
        t0 = time.perf_counter()
        store = ShardedFederatedStore.from_shard_builder(
            builder, G, batch_size=1, spill_dir=spill,
            progress=lambda s: _check_section_deadline())
        out["store_build_s"] = round(time.perf_counter() - t0, 1)
        out["dataset_disk_mb"] = round(store.nbytes() / 1e6, 1)
        out["directory_mb"] = round(store.directory.nbytes() / 1e6, 2)
        d = store.directory

        # -- the assignment plane: draw + route, per-shard balance ------
        _check_section_deadline()
        tally = np.zeros(M, np.int64)
        t0 = time.perf_counter()
        for k in range(cohorts):
            cohort = d.sample_cohort(k, cohort_size)
            route = d.agg_shard_of(cohort, M)
            tally += np.bincount(route, minlength=M)
        dt = time.perf_counter() - t0
        n_routed = cohorts * cohort_size
        out["route_us_per_client"] = round(1e6 * dt / n_routed, 3)
        out["shard_balance_max_over_mean"] = round(
            float(tally.max() / max(tally.mean(), 1e-9)), 3)

        # -- page-in: gather ONE cohort out of the 8.4M-client memmap ---
        _check_section_deadline()
        data = StoreFleetData(store)
        cohort = d.sample_cohort(0, cohort_size)
        t0 = time.perf_counter()
        for c in cohort[:64]:
            np.asarray(data.x[int(c)])
        out["gather_ms_per_client"] = round(
            1e3 * (time.perf_counter() - t0) / 64, 3)

        # -- directory-routed M-shard fold + wire merge (the shardplane
        # commit path at this population: route → per-shard int64 fold →
        # encode/decode partials → merge → ONE finalize) ----------------
        _check_section_deadline()
        rng = np.random.RandomState(9)
        net_ref = {"b": np.zeros(classes, np.float32),
                   "w": np.zeros((features, classes), np.float32)}
        deltas = [[(0.01 * rng.randn(classes)).astype(np.float32),
                   (0.01 * rng.randn(features, classes)).astype(np.float32)]
                  for _ in range(8)]
        route = d.agg_shard_of(cohort, M)
        accs = [PartialAccumulator() for _ in range(M)]
        t0 = time.perf_counter()
        for i, c in enumerate(cohort):
            accs[int(route[i])].add(deltas[i % len(deltas)], 1.0)
        total = PartialAccumulator()
        for acc in accs:
            decode_partial(encode_partial(acc)).merge_into(total)
        mean, count = finalize_partial_mean(total, net_ref)
        dt = time.perf_counter() - t0
        assert count == len(cohort)
        out["fold_uploads"] = int(count)
        out["uploads_per_sec"] = round(count / dt, 1)
        out["host_rss_mb"] = round(_rss_mb(), 1)
        return out
    finally:
        shutil.rmtree(spill, ignore_errors=True)


def bench_fleet_sim():
    """Serving under churn on the REAL control plane (fedml_tpu.sim):
    one fixed seeded fleet trace — staggered arrivals, diurnal
    availability windows, power-law device speeds, mid-round churn —
    replayed against sync first-k (fedavg_distributed), buffered
    semi-sync (fedbuff, aggregate every k arrivals with polynomial
    staleness discounting), and pure async (fedasync). Virtual clock:
    a four-virtual-hour diurnal scenario replays in wall seconds, the
    training math is exact (final_accuracy is real), and the whole
    interleaving is pinned by the seed (tests/test_fleet_sim.py diffs
    two runs' full arrival logs). The serving story the headline
    carries: buffered(k) beats first-k(k) round-throughput (no barrier,
    no discarded straggler work) while holding a lower staleness tail
    than pure async (docs/ROBUSTNESS.md "Serving under churn")."""
    import dataclasses

    from fedml_tpu.algos.config import FedConfig
    from fedml_tpu.data.batching import batch_global, build_federated_arrays
    from fedml_tpu.data.partition import partition_homo
    from fedml_tpu.data.synthetic import make_classification
    from fedml_tpu.models.lr import LogisticRegression
    from fedml_tpu.sim import FleetSimulator, FleetSpec, make_fleet_trace

    x, y = make_classification(320, n_features=10, n_classes=4, seed=1)
    fed = build_federated_arrays(x, y, partition_homo(len(x), 8),
                                 batch_size=16)
    test = batch_global(x[:96], y[:96], 16)
    cfg = FedConfig(client_num_in_total=8, client_num_per_round=8,
                    comm_round=12, epochs=1, batch_size=16, lr=0.3,
                    frequency_of_the_test=4)
    spec = FleetSpec(n_devices=8, seed=11, horizon_s=14400.0,
                     mean_online=0.75, base_round_s=30.0, slot_s=180.0,
                     speed_alpha=1.3, diurnal_amplitude=0.3,
                     arrival_spread_s=120.0)
    k = 4

    def go(mode, spec=spec, **kw):
        sim = FleetSimulator(LogisticRegression(num_classes=4), fed, test,
                             cfg, make_fleet_trace(spec), mode=mode, **kw)
        return sim.run()

    out = {"k": k, "trace": make_fleet_trace(spec).describe()}
    # Accuracy yardstick: the same federation on an always-on fleet.
    _check_section_deadline()
    clean = go("sync", spec=dataclasses.replace(spec, mean_online=1.0,
                                                diurnal_amplitude=0.0),
               aggregate_k=0)
    out["clean_accuracy"] = clean.final_accuracy
    runs = {}
    for label, mode, kw in (("sync_firstk", "sync", {"aggregate_k": k}),
                            ("buffered", "fedbuff", {"buffer_k": k}),
                            ("async", "fedasync", {})):
        _check_section_deadline()
        runs[label] = go(mode, **kw)
        out[label] = runs[label].summary()
    sync_tp = runs["sync_firstk"].updates_per_vmin
    buf_tp = runs["buffered"].updates_per_vmin
    out["buffered_vs_firstk_throughput"] = (round(buf_tp / sync_tp, 3)
                                            if sync_tp else None)
    bp = out["buffered"].get("staleness_p95")
    ap = out["async"].get("staleness_p95")
    out["buffered_vs_async_stale_p95"] = (round(bp / ap, 3)
                                          if bp is not None and ap else None)
    return out


def bench_adaptive_control(comm_round=24, static_ks=(2, 6)):
    """Self-tuning federation control under a load spike (fedml_tpu.ctrl,
    docs/ROBUSTNESS.md "Adaptive control"): one seeded fleet trace with a
    6x compute-slowdown window early in the run, replayed against static
    buffered arms (each ``buffer_k`` fixed for the whole run) and the
    adaptive controller (1807.06629-style window schedule + guard-band
    staleness admission) actuating the SAME fedbuff manager through its
    seam. The static arms frame the tradeoff the controller escapes: a
    small k is fast but its staleness tail blows through the spike, a
    large k holds the tail down but pays for it in virtual time all run
    long. Headline ``adaptive_ctrl_gain``: controller accuracy per
    virtual minute over the best static arm's — >= 1.0 means the closed
    loop beats every static configuration while (also asserted by
    tests/test_ctrl.py on this exact config) holding a lower accepted-
    staleness p95 than the best arm. Deterministic: the drill test pins
    two-run-identical actuation logs on this seed."""
    from fedml_tpu.algos.config import FedConfig
    from fedml_tpu.ctrl import (FederationController,
                                StalenessAdmissionPolicy,
                                WindowSchedulePolicy)
    from fedml_tpu.data.batching import batch_global, build_federated_arrays
    from fedml_tpu.data.partition import partition_homo
    from fedml_tpu.data.synthetic import make_classification
    from fedml_tpu.models.lr import LogisticRegression
    from fedml_tpu.sim import FleetSimulator, FleetSpec, make_fleet_trace

    x, y = make_classification(320, n_features=10, n_classes=4, seed=1)
    fed = build_federated_arrays(x, y, partition_homo(len(x), 8),
                                 batch_size=16)
    test = batch_global(x[:96], y[:96], 16)
    cfg = FedConfig(client_num_in_total=8, client_num_per_round=8,
                    comm_round=comm_round, epochs=1, batch_size=16, lr=0.3,
                    frequency_of_the_test=4)
    spec = FleetSpec(n_devices=8, seed=11, horizon_s=20000.0,
                     mean_online=0.92, base_round_s=20.0, slot_s=400.0,
                     arrival_spread_s=30.0, spike_t0=250.0, spike_t1=700.0,
                     spike_factor=6.0)

    def go(controller=None, buffer_k=2):
        _check_section_deadline()
        sim = FleetSimulator(LogisticRegression(num_classes=4), fed, test,
                             cfg, make_fleet_trace(spec), mode="fedbuff",
                             buffer_k=buffer_k, controller=controller)
        res = sim.run()
        acc_vmin = ((res.final_accuracy or 0.0) * 60.0
                    / max(res.virtual_s, 1e-9))
        return res, sim, {**res.summary(),
                          "acc_per_vmin": round(acc_vmin, 5)}

    out = {"trace": make_fleet_trace(spec).describe(),
           "spike": {"t0": spec.spike_t0, "t1": spec.spike_t1,
                     "factor": spec.spike_factor}}
    best_static = None
    for k in static_ks:
        _, _, rec = go(buffer_k=k)
        out[f"static_k{k}"] = rec
        if best_static is None \
                or rec["acc_per_vmin"] > best_static["acc_per_vmin"]:
            best_static = rec
    ctl = FederationController(
        [WindowSchedulePolicy(w_min=1, w_max=4),
         StalenessAdmissionPolicy(band_lo=2.0, band_hi=4.0, k_max=4,
                                  cap_slack=0, cooldown=2)],
        interval=1)
    _, sim, rec = go(controller=ctl)
    applied = [e for e in ctl.actuation_log if e["outcome"] == "applied"]
    snap = sim.server.registry.snapshot()
    out["controller"] = {
        **rec,
        "actuations_applied": len(applied),
        "actuations_refused": int(snap.get("actuation_refused", 0)),
        "admission_drops": int(snap.get("admission_drops", 0)),
        "final_knobs": sim.server.ctrl.values(),
        # The full decision trail (the reproducibility artifact the
        # drill test diffs across two runs) — blob-only, never headline.
        "actuation_log": ctl.actuation_log,
    }
    out["adaptive_ctrl_gain"] = (
        round(rec["acc_per_vmin"] / best_static["acc_per_vmin"], 3)
        if best_static and best_static["acc_per_vmin"] else None)
    out["ctrl_vs_best_static_stale_p95"] = (
        round(rec.get("staleness_p95", 0.0)
              / best_static["staleness_p95"], 3)
        if best_static and best_static.get("staleness_p95") else None)
    return out


def _gather_overlap_probe(api, store, probe_rounds=10, start=90_001):
    """Median SYNCHRONOUS cohort gather+H2D seconds per round, measured
    on rounds the timed windows never visit (fresh seeds, warm shapes).
    Divided by the measured round wall-clock this yields the
    prefetch-overlap ratio: the fraction of a round the prefetcher must
    hide (<1 = the host gather fits entirely under the device compute —
    the store's stated design point, now measured; >1 = gather-bound).
    Checks the section deadline per round (cold memmap page-ins at 1M
    clients are IO-bound); both callers catch the resulting
    _SectionTimeout as a probe error so an overrun never discards the
    primary measurement already taken."""
    import jax

    ts = []
    for r in range(start, start + probe_rounds):
        _check_section_deadline()
        idx, _ = api._sample_round_uncached(r)
        t0 = time.perf_counter()
        sub = store.gather_cohort(np.asarray(idx))
        jax.block_until_ready((sub.x, sub.y, sub.mask))
        ts.append(time.perf_counter() - t0)
    return statistics.median(ts)


def bench_stackoverflow_342k():
    """BASELINE.md's largest row at its TRUE scale: 342,477 clients
    (the reference enumerates exactly that many stackoverflow_nwp
    users), reference model dims (embed 96, LSTM 670, vocab 10004),
    50 clients/round, batch 16. Host-resident CSR store (~360 MB for
    ~2.25M synthetic sentences); each round's device cohort is a few MB
    regardless of the client count. Reports samples/sec and the
    measured host-gather vs round-time split (VERDICT r6 #8) so this
    point and the 1M sharded-directory point (``synthetic_1m``) carry
    comparable units."""
    from functools import partial

    from fedml_tpu.algos.config import FedConfig
    from fedml_tpu.algos.fedavg import FedAvgAPI
    from fedml_tpu.data.store import FederatedStore
    from fedml_tpu.models.rnn import RNNStackOverflow
    from fedml_tpu.trainer.local import seq_softmax_ce

    from fedml_tpu.data.synthetic import make_stackoverflow_nwp

    C, T, V, cpr, batch = 342_477, 20, 10004, 50, 16
    x, y, parts = make_stackoverflow_nwp(C, seq_len=T, vocab=V)
    counts = np.array([len(parts[c]) for c in range(C)])
    store = FederatedStore(x, y, parts, batch_size=batch)
    cfg = FedConfig(client_num_in_total=C, client_num_per_round=cpr,
                    comm_round=100_000,  # > any window schedule: keeps
                    # the cohort prefetcher live for every timed round
                    epochs=1, batch_size=batch,
                    lr=10 ** -0.5)  # BASELINE.md row lr
    api = FedAvgAPI(RNNStackOverflow(vocab_size=V), store, None, cfg,
                    loss_fn=partial(seq_softmax_ce, pad_id=0), pad_id=0)
    _warm_store_buckets(api, store, counts, cpr, batch)
    timed = _timed_store_windows(api, store, count_samples=True)
    # Record the scale point and assemble the result BEFORE the
    # auxiliary probe: a probe failure must not discard the primary
    # throughput/RSS measurement already taken.
    _scale_state["342k"] = {"rps": timed["rounds_per_sec"],
                            "rss_peak_mb": timed["rss_peak_mb"]}
    out = {"clients": C, **timed,
           "host_dataset_mb": round(store.nbytes() / 1e6, 1)}
    try:
        gather_s = _gather_overlap_probe(api, store)
        out["host_gather_ms_per_round"] = round(gather_s * 1e3, 1)
        out["prefetch_overlap_ratio"] = round(
            gather_s * timed["rounds_per_sec"], 3)
    except Exception as e:  # incl. _SectionTimeout: the probe is
        # auxiliary and deadline-checked per round — degrade to an
        # explicit hole, keep the timed measurement.
        out["gather_probe_error"] = f"{type(e).__name__}: {e}"[:120]
    return out


def bench_synthetic_1m(C=1_048_576, G=64, cpr=50, model_kw=None,
                       min_window_s=6.0):
    """The MILLION-CLIENT tier (ROADMAP open item 1): 2^20 = 1,048,576
    synthetic StackOverflow-NWP clients through the SHARDED client
    directory (``data/directory.py`` — G memmap-spilled shards built one
    at a time, directory metadata O(clients), gathers page in only the
    cohort's rows) on the same model/round config as
    ``stackoverflow_342k``, so the two points differ ONLY in client
    count and storage tier. The claims this section records, as
    measured ratios against the 342k flat-store point (same process,
    same units): host RSS stays FLAT as the client count grows 3x past
    the flat store's scale (``peak_rss_ratio`` — sampled current RSS
    per timed block, the flat-RSS story of the sharded tier), and
    rounds/sec stays within 2x (``rps_vs_342k`` — cohort cost is
    independent of the client count; the extra price is directory
    sampling at 1M and memmap page-ins). The parameters exist for the
    machinery test (tests/test_bench_headline.py) — the section always
    runs the defaults."""
    import shutil
    import tempfile
    from functools import partial

    from fedml_tpu.algos.config import FedConfig
    from fedml_tpu.algos.fedavg import FedAvgAPI
    from fedml_tpu.data.directory import ShardedFederatedStore
    from fedml_tpu.models.rnn import RNNStackOverflow
    from fedml_tpu.trainer.local import seq_softmax_ce

    from fedml_tpu.data.synthetic import make_stackoverflow_shard

    T, V, batch = 20, 10004, 16
    # Remainder-aware shard sizes: sum(sizes) == C exactly, so the
    # directory's client count always matches cfg.client_num_in_total
    # (the sampler-delegation guard) even for non-dividing C/G.
    sizes = [C // G + (1 if s < C % G else 0) for s in range(G)]

    def builder(s):
        # THE make_stackoverflow_nwp law (single source — data/
        # synthetic.py), seeded per shard so build peak RSS is O(one
        # shard).
        return make_stackoverflow_shard(sizes[s], seq_len=T, vocab=V,
                                        seed=10_000 + s)

    spill = tempfile.mkdtemp(prefix="bench_synth1m_")
    try:
        store = ShardedFederatedStore.from_shard_builder(
            builder, G, batch_size=batch, spill_dir=spill,
            progress=lambda s: _check_section_deadline())
        build_rss = _rss_mb()
        cfg = FedConfig(client_num_in_total=C, client_num_per_round=cpr,
                        comm_round=100_000, epochs=1, batch_size=batch,
                        lr=10 ** -0.5)
        api = FedAvgAPI(RNNStackOverflow(vocab_size=V, **(model_kw or {})),
                        store, None, cfg,
                        loss_fn=partial(seq_softmax_ce, pad_id=0), pad_id=0)
        _warm_store_buckets(api, store, np.asarray(store.counts), cpr,
                            batch)
        timed = _timed_store_windows(api, store, count_samples=True,
                                     min_window_s=min_window_s)
        ref = _scale_state.get("342k")
        out = {"clients": C, "shards": G, "memmap_spill": True, **timed,
               "dataset_disk_mb": round(store.nbytes() / 1e6, 1),
               "directory_mb": round(store.directory.nbytes() / 1e6, 2),
               "build_rss_mb": round(build_rss, 1),
               # Ratios vs the flat-store 342k point (None if its
               # section was skipped/errored this run):
               "rps_vs_342k": (round(timed["rounds_per_sec"] / ref["rps"],
                                     3) if ref else None),
               "peak_rss_ratio": (round(timed["rss_peak_mb"]
                                        / ref["rss_peak_mb"], 3)
                                  if ref else None)}
        try:  # auxiliary (incl. _SectionTimeout — deadline-checked per
            # round): must not discard the measurements above
            gather_s = _gather_overlap_probe(api, store)
            out["host_gather_ms_per_round"] = round(gather_s * 1e3, 1)
            out["prefetch_overlap_ratio"] = round(
                gather_s * timed["rounds_per_sec"], 3)
        except Exception as e:
            out["gather_probe_error"] = f"{type(e).__name__}: {e}"[:120]
        return out
    finally:
        shutil.rmtree(spill, ignore_errors=True)


def bench_vit():
    """ViT federation (new capability beyond reference parity): CIFAR-
    shaped inputs, patch 4, d=128, 4 heads x 4 layers."""
    from fedml_tpu.models import create_model

    model = create_model("vit", num_classes=10, patch=4, d_model=128,
                         n_heads=4, n_layers=4)
    sps = _scan_bench(model, n_clients=64, per_client=256, batch=32,
                      cpr=8, lr=0.01)
    return {"samples_per_sec": round(sps, 2),
            **_mfu_fields(model, np.zeros((32, 32, 32, 3), np.float32),
                          sps, 32)}


def bench_resnet56_b128():
    """The primary config with the per-client batch raised 32 → 128 (the
    measured MXU tiling sweet spot, docs/ROOFLINE.md): same model, same
    federation semantics, ~1.6x the samples/sec. BENCH_HEAVY=1 only
    since r9: it measures the same lane-fill story as the
    ``resnet56_s2d_stem`` section, whose b128 row (now with its own MFU
    submetrics) keeps the coverage inside the fast-bench budget — the
    two levers compose there, and ``tuned_best`` still picks the best
    honest number across whatever ran."""
    from fedml_tpu.models.resnet import resnet56

    model = resnet56(num_classes=10, dtype="bf16")
    sps = _scan_bench(model, n_clients=128, per_client=256, batch=128,
                      cpr=8, lr=0.1)
    return {"samples_per_sec": round(sps, 2),
            **_mfu_fields(model, np.zeros((128, 32, 32, 3), np.float32),
                          sps, 128)}


def bench_resnet56_s2d():
    """The space-to-depth stem variant (docs/ROOFLINE.md's first named
    lane-fill lever, first-class in the model registry as
    ``resnet56_s2d``): 2x2 s2d input + doubled stage widths (32/64/128)
    at half spatial — per-conv FLOPs ~equal to the reference model
    (0.170 vs 0.186 GFLOP/sample) with 2x the MXU lane fill per stage.
    Same federation config as the primary; reported as a VARIANT row
    because the model differs (4x params) — the primary stays on the
    reference stem for comparability. The b128 row composes the two
    measured lane-fill levers and carries its own MFU submetrics — the
    ``best_cnn_mfu`` headline scalar typically comes from here."""
    from fedml_tpu.models.resnet import resnet56

    model = resnet56(num_classes=10, dtype="bf16", stem="s2d")
    sps = _scan_bench(model, n_clients=128, per_client=256, batch=32,
                      cpr=8, lr=0.1)
    # s2d + batch 128: the two levers composed — the repo's best honest
    # CIFAR-ResNet56 number, feeding the top-level ``tuned_best`` field
    # (r3 VERDICT #8). Measured fresh every round, not quoted from docs.
    sps_b128 = _scan_bench(resnet56(num_classes=10, dtype="bf16",
                                    stem="s2d"),
                           n_clients=128, per_client=256, batch=128,
                           cpr=8, lr=0.1)
    return {"samples_per_sec": round(sps, 2),
            **_mfu_fields(model, np.zeros((32, 32, 32, 3), np.float32),
                          sps, 32),
            "s2d_b128_samples_per_sec": round(sps_b128, 2),
            **_mfu_fields(model, np.zeros((128, 32, 32, 3), np.float32),
                          sps_b128, 128, prefix="s2d_b128_")}


def bench_sharded_path():
    """The shard_map round (the multi-chip code path) on a 1-device mesh:
    full-participation whole-run scan with client shards pinned — the
    dryrun validates N>1 correctness on a virtual mesh; this measures the
    sharded machinery's throughput on the real chip vs the vmap path
    (primary metric). Same model/data scale as the primary config."""
    from fedml_tpu.models.resnet import resnet56
    from fedml_tpu.parallel.mesh import client_mesh

    n_clients = 8  # full participation: cpr == total
    sps, iqr = _scan_bench(resnet56(num_classes=10, dtype="bf16"),
                           n_clients=n_clients, per_client=256, batch=32,
                           cpr=n_clients, lr=0.1, mesh=client_mesh(1),
                           with_iqr=True)
    return {"samples_per_sec": round(sps, 2),
            "samples_per_sec_iqr": iqr,
            "rounds_per_sec": round(sps / (n_clients * 256), 3)}


def _timed_host_rounds(round_fn, r0, rounds, min_s, reps,
                       units_per_round=1.0):
    """Grow-then-verify floor calibration at the per-round grain: grow
    the window of host-loop ``round_fn`` calls until one carries
    ``min_s`` of work, then report ``_med_iqr`` of units/sec over
    ``reps`` windows (``units_per_round=1`` → rounds/s; pass
    samples-per-round for samples/s). The ONE copy of the discipline
    shared by the per-round sections (the scan sections calibrate whole
    windows in ``_timed_store_windows``)."""
    r = r0

    def window(r, rounds):
        _check_section_deadline()
        t0 = time.perf_counter()
        for rr in range(r, r + rounds):
            round_fn(rr)
        return time.perf_counter() - t0

    for _ in range(5):  # grow-then-verify floor calibration
        dt = window(r, rounds)
        r += rounds
        if dt >= min_s:
            break
        rounds = max(rounds + 1,
                     int(np.ceil(rounds * min_s * 1.2 / dt)))
    vals = []
    for _ in range(reps):
        dt = window(r, rounds)
        vals.append(rounds * units_per_round / dt)
        r += rounds
    return _med_iqr(vals), r


def bench_pod_reduce(n_clients=16, per_client=64, batch=16, cpr=8,
                     d=32, min_s=1.0, reps=3):
    """Pod-scale compute plane (r14): the host-grouped hierarchical
    reduction on a SIMULATED 2×4 DCN×ICI mesh (single process, forced
    factorization — the compiled program is the pod one, the DCN hop
    isn't physically here). Three arms, same federation:

    - ``mean`` — the partial-sum fast path, hierarchically associated
      (ICI stage 1, one host partial across DCN);
    - ``flat`` — coord_median with ``group_reduce=False``: the exact
      flat statistic, full client-stack ``all_gather`` across the DCN
      axis (O(C·model) inter-host bytes);
    - ``grouped`` — coord_median with ``group_reduce=True``:
      median-of-host-medians, stage-1 ICI-only, G=2 partials across DCN
      (O(G·model)).

    ``dcn_bytes_ratio`` (flat/grouped = C/G) is the STRUCTURAL claim,
    read from the live ``FedAvgAPI.reduce_profile`` gauges — on real DCN
    it is the wire-bytes win; the rounds/s A/B here measures the
    single-host cost of the reshaped collective (the gather shrinks
    C→G models, so grouped should never be slower)."""
    import jax

    from fedml_tpu.algos.config import FedConfig
    from fedml_tpu.algos.fedavg import FedAvgAPI
    from fedml_tpu.data.batching import build_federated_arrays
    from fedml_tpu.data.partition import partition_homo
    from fedml_tpu.models.lr import LogisticRegression
    from fedml_tpu.parallel.multihost import simulated_dcn_mesh

    rng = np.random.RandomState(7)
    x = rng.randn(n_clients * per_client, d).astype(np.float32)
    y = (x @ rng.randn(d) > 0).astype(np.int32)
    fed = build_federated_arrays(x, y, partition_homo(len(x), n_clients),
                                 batch)
    mesh = simulated_dcn_mesh(2, 4)

    def make_api(**kw):
        cfg = FedConfig(client_num_in_total=n_clients,
                        client_num_per_round=cpr, comm_round=100_000,
                        epochs=1, batch_size=batch, lr=0.1, **kw)
        return FedAvgAPI(LogisticRegression(num_classes=2), fed, None,
                         cfg, mesh=mesh)

    def timed_rps(api, r0):
        return _timed_host_rounds(api.train_one_round, r0, 8, min_s, reps)

    out = {"mesh": "2x4 DCN x ICI (simulated)", "clients": n_clients,
           "clients_per_round": cpr}
    arms = (("mean", {}),
            ("flat", {"aggregator": "coord_median"}),
            ("grouped", {"aggregator": "coord_median",
                         "group_reduce": True}))
    profs = {}
    for name, kw in arms:
        api = make_api(**kw)
        api.train_one_round(0)  # warm the executable
        jax.block_until_ready(api.net.params)
        (rps, iqr), _ = timed_rps(api, 1)
        out[f"{name}_rounds_per_sec"] = round(rps, 3)
        out[f"{name}_rounds_per_sec_iqr"] = iqr
        profs[name] = api.reduce_profile()
        del api
    out.update({
        "dcn_partials_grouped": profs["grouped"]["dcn_partials"],
        "dcn_partials_flat": profs["flat"]["dcn_partials"],
        "dcn_bytes_grouped": profs["grouped"]["dcn_bytes_per_round"],
        "dcn_bytes_flat": profs["flat"]["dcn_bytes_per_round"],
        "dcn_bytes_ratio": round(
            profs["flat"]["dcn_bytes_per_round"]
            / profs["grouped"]["dcn_bytes_per_round"], 3),
        "grouped_vs_flat_rps": round(
            out["grouped_rounds_per_sec"] / out["flat_rounds_per_sec"],
            3),
    })
    return out


def bench_cnn_mfu_levers(n_clients=16, per_client=64, batch=16, cpr=8,
                         acc_rounds=10, min_s=2.0, reps=3):
    """The MFU playbook's two remaining levers, measured (r14):

    - **bf16 client step** (``cfg.client_step_dtype="bf16"``): layer
      compute in bfloat16 inside the jitted client step, fp32 params/
      gradients/aggregation/eval — A/B'd against the fp32 arm for
      samples/s, ``mfu``/``delivered_tflops`` (always the LOGICAL fp32
      model's FLOPs), and held-out ACCURACY DELTA at the same round
      budget (eval always runs fp32, so the delta is the training
      effect). On CPU bf16 is emulated and usually SLOWER — the honest
      expectation here is the accuracy-delta measurement plus the TPU
      projection stated in docs/EXECUTION.md, not a CPU speedup.
    - **im2col conv lane shaping** (``cfg.compute_layout="im2col"``):
      the 5x5 stem conv rephrased as patches + a 1x1 GEMM
      (contraction dim 25 vs 1 input channel) — samples/s and MFU vs
      the same fp32 baseline.
    """
    import jax

    from fedml_tpu.algos.config import FedConfig
    from fedml_tpu.algos.fedavg import FedAvgAPI
    from fedml_tpu.data.batching import build_federated_arrays
    from fedml_tpu.data.partition import partition_homo
    from fedml_tpu.models.cnn import CNNOriginalFedAvg

    rng = np.random.RandomState(11)
    n = n_clients * per_client
    # Learnable image task (held-out accuracy must move): label = which
    # half of the image carries the brighter blob.
    x = rng.rand(n, 28, 28, 1).astype(np.float32) * 0.1
    y = rng.randint(0, 2, n).astype(np.int32)
    for i in range(n):
        r0 = 4 if y[i] == 0 else 18
        x[i, r0:r0 + 6, 8:20, 0] += 1.0
    fed = build_federated_arrays(x, y, partition_homo(len(x), n_clients),
                                 batch)
    xt = rng.rand(256, 28, 28, 1).astype(np.float32) * 0.1
    yt = rng.randint(0, 2, 256).astype(np.int32)
    for i in range(256):
        r0 = 4 if yt[i] == 0 else 18
        xt[i, r0:r0 + 6, 8:20, 0] += 1.0
    test = (xt.reshape(-1, batch, 28, 28, 1), yt.reshape(-1, batch),
            np.ones((256 // batch, batch), np.float32))
    model = CNNOriginalFedAvg(num_classes=2)
    samples_per_round = cpr * per_client

    def make_api(**kw):
        cfg = FedConfig(client_num_in_total=n_clients,
                        client_num_per_round=cpr, comm_round=100_000,
                        epochs=1, batch_size=batch, lr=0.1,
                        frequency_of_the_test=1000, **kw)
        return FedAvgAPI(model, fed, test, cfg)

    def timed_sps(api, r0):
        return _timed_host_rounds(api.train_one_round, r0, 2, min_s,
                                  reps, samples_per_round)

    sample = np.zeros((batch, 28, 28, 1), np.float32)
    out = {"clients": n_clients, "acc_rounds": acc_rounds}
    accs, losses = {}, {}
    arms = (("fp32", {}),
            ("bf16", {"client_step_dtype": "bf16"}),
            ("im2col", {"compute_layout": "im2col"}))
    for name, kw in arms:
        api = make_api(**kw)
        # Accuracy at a fixed round budget FIRST (fresh model), then the
        # throughput windows continue on the warm executable. The task
        # converges inside the budget by design: a STABLE accuracy
        # delta (0.0 = "no accuracy cost measured") beats a mid-descent
        # operating point that flips between 0.2 and 1.0 across seeds
        # (measured — the transition is cliff-like); the train-loss
        # delta below is the finer-grained sensitivity observable.
        for rr in range(acc_rounds):
            loss = api.train_one_round(rr)["train_loss"]
        accs[name] = float(np.asarray(api.evaluate()["accuracy"]))
        losses[name] = float(loss)
        jax.block_until_ready(api.net.params)
        (sps, iqr), _ = timed_sps(api, acc_rounds)
        prefix = "" if name == "fp32" else f"{name}_"
        out.update({f"{prefix}samples_per_sec": round(sps, 2),
                    f"{prefix}samples_per_sec_iqr": iqr,
                    f"{prefix}accuracy": round(accs[name], 4),
                    f"{prefix}final_train_loss": round(losses[name], 5),
                    **_mfu_fields(model, sample, sps, batch,
                                  prefix=prefix)})
        del api
    out["bf16_speedup"] = round(
        out["bf16_samples_per_sec"] / out["samples_per_sec"], 3)
    out["bf16_acc_delta"] = round(accs["bf16"] - accs["fp32"], 4)
    out["bf16_loss_delta"] = round(losses["bf16"] - losses["fp32"], 5)
    out["im2col_speedup"] = round(
        out["im2col_samples_per_sec"] / out["samples_per_sec"], 3)
    out["im2col_acc_delta"] = round(accs["im2col"] - accs["fp32"], 4)
    out["im2col_loss_delta"] = round(losses["im2col"] - losses["fp32"], 5)
    return out


def bench_layout_fused_round(n_clients=64, per_client=128, batch=20,
                             cpr=10, widths=(120, 120), min_s=2.0,
                             reps=5):
    """The r9 tentpole pair measured together on a CNN hot path:

    - **fused donated round step** (``parallel/shard.make_fused_round_
      step``): one dispatch per host-loop round (train + aggregate +
      server update, ``(net, extra)`` donated) vs the pre-r9 separate
      ``run_round`` + ``_server_update`` procedure — same federation,
      same per-round loss sync, so ``fused_speedup`` is the dispatch +
      undonated-intermediate cost. The donation audit
      (``obs.sanitizer.donation_audit``) and the compile counter pin the
      steady state: ``live_model_copies`` ≈ 1 and
      ``steady_state_compiles`` == 0.
    - **lane-fill compute layout** (``parallel/layout.py``): the SAME
      model with deliberately just-under-lane conv widths (120 → padded
      128) trained through ``cfg.compute_layout="auto"`` vs the logical
      layout — ``layout_pad_ratio`` is what squaring up to the lane
      width buys (docs/EXECUTION.md "MFU playbook": padding pays just
      under a lane multiple, hurts far below one). MFU for both sides
      uses the LOGICAL FLOPs, so padding can never inflate it.

    The parameters exist for the machinery test
    (tests/test_bench_headline.py); the section always runs the
    defaults."""
    import jax

    from fedml_tpu.algos.config import FedConfig
    from fedml_tpu.algos.fedavg import FedAvgAPI
    from fedml_tpu.data.batching import build_federated_arrays
    from fedml_tpu.data.partition import partition_homo
    from fedml_tpu.models.cnn import CNNOriginalFedAvg
    from fedml_tpu.obs.sanitizer import donation_audit, sanitized

    rng = np.random.RandomState(3)
    x = rng.rand(n_clients * per_client, 28, 28, 1).astype(np.float32)
    y = rng.randint(0, 62, len(x)).astype(np.int32)
    fed = build_federated_arrays(x, y, partition_homo(len(x), n_clients),
                                 batch)
    model = CNNOriginalFedAvg(num_classes=62, widths=tuple(widths))
    samples_per_round = cpr * per_client  # homo partition: equal counts

    def make_api(layout):
        cfg = FedConfig(client_num_in_total=n_clients,
                        client_num_per_round=cpr, comm_round=100_000,
                        epochs=1, batch_size=batch, lr=0.05,
                        compute_layout=layout)
        return FedAvgAPI(model, fed, None, cfg)

    def timed_sps(round_fn, r0, rounds=4):
        """Median samples/sec over ``reps`` floor-calibrated windows of
        per-round host-loop rounds (each round pays its loss sync, both
        sides identically)."""
        r = r0

        def window(r, rounds):
            _check_section_deadline()
            t0 = time.perf_counter()
            for rr in range(r, r + rounds):
                round_fn(rr)
            return time.perf_counter() - t0

        for _ in range(5):  # grow-then-verify, like every timed section
            dt = window(r, rounds)
            r += rounds
            if dt >= min_s:
                dt2 = window(r, rounds)
                r += rounds
                if dt2 >= min_s * 2.0 / 3.0:
                    break
                dt = dt2
            rounds = max(rounds + 1,
                         int(np.ceil(rounds * min_s * 1.2 / dt)))
        vals = []
        for _ in range(reps):
            dt = window(r, rounds)
            vals.append(rounds * samples_per_round / dt)
            r += rounds
        return _med_iqr(vals), r

    out = {"clients": n_clients, "widths": list(widths)}

    # --- fused vs separate dispatch, logical layout ------------------
    api = make_api("none")

    def separate_round(rr):
        avg, loss = api.run_round(rr)
        api.net = api._server_update(api.net, avg)
        assert np.isfinite(float(loss))

    def fused_round(rr):
        assert np.isfinite(api.train_one_round(rr)["train_loss"])

    fused_round(0)  # warm both executables
    separate_round(1)
    jax.block_until_ready(api.net.params)
    (fused_sps, fused_iqr), r = timed_sps(fused_round, 2)
    (sep_sps, sep_iqr), r = timed_sps(separate_round, r)
    out.update({"fused_samples_per_sec": round(fused_sps, 2),
                "fused_samples_per_sec_iqr": fused_iqr,
                "separate_samples_per_sec": round(sep_sps, 2),
                "separate_samples_per_sec_iqr": sep_iqr,
                "fused_speedup": round(fused_sps / sep_sps, 3),
                **_mfu_fields(model, np.zeros((batch, 28, 28, 1),
                                              np.float32),
                              fused_sps, batch)})

    # Donation + recompile audit on the fused steady state: the model-
    # sized live-buffer count must hold at ~one copy (the donated carry
    # is reused in place) and nothing may re-trace. Sampled OUTSIDE any
    # other live API's lifetime — signature matching counts every live
    # net in the process.
    with sanitized(transfer="allow", strict=False) as san:
        with donation_audit(api.net) as audit:
            for rr in range(r, r + 5):
                fused_round(rr)
                audit.sample()
            r += 5
    out["live_model_copies"] = round(audit.peak, 2)
    out["steady_state_compiles"] = san.compiles
    del api  # free its net before the padded twin's audit window

    # --- lane-fill layout A/B (padded physical twin, same model) -----
    api = make_api("auto")
    layout = api._layout
    out["layout"] = (None if layout is None else layout.describe())
    fused_round(0)
    jax.block_until_ready(api.net.params)
    (pad_sps, pad_iqr), _ = timed_sps(fused_round, 2)
    out.update({"layout_samples_per_sec": round(pad_sps, 2),
                "layout_samples_per_sec_iqr": pad_iqr,
                "layout_pad_ratio": round(pad_sps / fused_sps, 3),
                **_mfu_fields(model, np.zeros((batch, 28, 28, 1),
                                              np.float32),
                              pad_sps, batch, prefix="layout_")})
    return out


FLOOR_S = 0.4   # required device work per timed call (asserted, not assumed)
TARGET_S = 0.6  # calibration aims a margin above the floor


def _calibrated_side(f, q, k, v, tokens_per_iter, n_timed=5):
    """Median tokens/sec for one side of a kernel A/B, with the iteration
    count CALIBRATED from a measured warm-call rate so every timed call
    carries ≥ FLOOR_S seconds of device work — enforced, not assumed (r3
    VERDICT: the fixed iters schedule left the fast side at ~0.15 s/call,
    inside the ±30 ms noise band of the per-call dispatch cost).

    ``f(q, k, v, iters)`` must accept the chain length as a DYNAMIC
    operand (no recompile across iters). Per-iteration device time is fit
    two-point — (t(n2) − t(n1)) / (n2 − n1) — which cancels the fixed
    per-call dispatch cost; the estimate of that cost itself
    is kept to refine the fit from the timed calls, and the floor is
    re-checked against the refined rate (retry with more iters if a noisy
    first fit under-sized the chain)."""
    def call(iters):
        _check_section_deadline()
        t0 = time.perf_counter()
        float(f(q, k, v, iters))
        return time.perf_counter() - t0

    call(1)  # warm + compile (host fetch = the sync this file trusts)
    n1, n2 = 1, 5
    t1 = min(call(n1) for _ in range(2))
    t2 = min(call(n2) for _ in range(2))
    per_iter = max((t2 - t1) / (n2 - n1), 1e-4)
    rtt = max(t1 - per_iter * n1, 0.0)
    for _attempt in range(4):
        iters = max(1, min(4096, int(np.ceil(TARGET_S / per_iter))))
        calls = sorted(call(iters) for _ in range(n_timed))
        med = calls[n_timed // 2]
        refined = max((med - rtt) / iters, 1e-4)
        if refined * iters >= FLOOR_S:
            return {"tokens_per_sec": round(tokens_per_iter * iters / med),
                    "iters": iters, "call_s": round(med, 3),
                    "device_s_per_call_est": round(refined * iters, 3)}
        per_iter = refined  # noisy first fit under-sized the chain: retry
    raise RuntimeError(
        f"could not reach the {FLOOR_S}s device-work floor "
        f"(per_iter≈{per_iter:.4f}s, iters≈{iters})")


def bench_flash_attention_sweep():
    """Pallas fused attention vs XLA dense attention across sequence
    lengths, in the TRAINING configuration (bf16 activations, causal).
    Each point chains data-dependent iterations inside one jit (output
    feeds the next query) with a single device sync — per-call timing
    measures the fixed per-call dispatch cost, not the kernel. The
    chain length is calibrated per side (``_calibrated_side``) so every
    timed call clears the 0.4 s device-work floor.

    Reports tokens/sec for both, the per-T speedup, the crossover T, and
    each side's compiled temp-memory (the O(T) vs O(T²) claim, measured
    rather than asserted — r2 VERDICT). Dense is EXPECTED to fail at the
    longest T (its [B, H, T, T] scores exceed HBM); that failure is
    recorded as a data point, not an error. All comparable points run
    batch 1 at T≥8192; the r3-era T=8192 batch-2 configuration — where
    dense's 8.6 GB compiled temp sits against the HBM boundary and its
    throughput collapses ~9x — is kept as an explicitly-labelled
    memory-cliff datum (r3 VERDICT #1: a memory effect must not be
    presented as an O(T²) kernel property)."""
    import jax
    import jax.numpy as jnp

    from fedml_tpu.ops.flash_attention import flash_attention

    h, d = 8, 64

    def chained(attn):
        def run(q, k, v, iters):
            out = jax.lax.fori_loop(
                0, iters, lambda i, acc: attn(acc, k, v), q)
            return jnp.sum(out)  # scalar → float() forces a real sync
        return jax.jit(run)

    def temp_mb(f, q, k, v):
        try:
            ma = f.lower(q, k, v, 1).compile().memory_analysis()
            return round(ma.temp_size_in_bytes / 1e6, 1)
        except Exception:
            return None

    def measure(t, b):
        rng = np.random.RandomState(0)
        q, k, v = (jnp.asarray(rng.randn(b, t, h, d), jnp.bfloat16)
                   for _ in range(3))

        def naive(q, k, v, t=t):
            logits = (jnp.einsum("bqhd,bkhd->bhqk", q, k)
                      .astype(jnp.float32) / np.sqrt(d))
            mask = jnp.tril(jnp.ones((t, t), bool))
            logits = jnp.where(mask[None, None], logits, -1e30)
            p = jax.nn.softmax(logits, -1).astype(q.dtype)
            return jnp.einsum("bhqk,bkhd->bqhd", p, v)

        f_flash = chained(lambda q, k, v: flash_attention(
            q, k, v, causal=True))
        f_naive = chained(naive)
        fl = _calibrated_side(f_flash, q, k, v, b * t)
        pt = {"batch": b,
              "flash_tokens_per_sec": fl["tokens_per_sec"],
              "flash_iters": fl["iters"], "flash_call_s": fl["call_s"],
              "flash_temp_mb": temp_mb(f_flash, q, k, v)}
        try:
            de = _calibrated_side(f_naive, q, k, v, b * t)
            pt.update({"dense_tokens_per_sec": de["tokens_per_sec"],
                       "dense_iters": de["iters"],
                       "dense_call_s": de["call_s"],
                       "dense_temp_mb": temp_mb(f_naive, q, k, v),
                       "speedup": round(fl["tokens_per_sec"]
                                        / de["tokens_per_sec"], 3)})
        except _SectionTimeout:  # the per-section cap must abort the
            raise                # section, not masquerade as a dense OOM
        except Exception as e:  # the T² wall: dense cannot allocate
            pt["dense_tokens_per_sec"] = None
            pt["dense_failed"] = f"{type(e).__name__}: {e}"[:120]
        return pt

    points, crossover = {}, None
    for t, b in [(2048, 4), (8192, 1), (16384, 1), (32768, 1), (65536, 1)]:
        _check_section_deadline()
        pt = measure(t, b)
        if (crossover is None and pt.get("speedup")
                and pt["speedup"] > 1.0):
            crossover = t
        points[f"t{t}"] = pt
    cliff = measure(8192, 2)
    cliff["note"] = ("memory-cliff datum, NOT comparable: dense's b=2 "
                     "compiled temp (~8.6 GB) sits against the HBM "
                     "boundary, so its collapse here is memory pressure, "
                     "not an O(T^2) kernel property — compare the b=1 row")
    points["t8192_b2_memcliff"] = cliff
    return {"points": points, "crossover_T": crossover,
            "floor_s": FLOOR_S,
            "config": "bf16, causal, h8 d64, tuned blocks"}


def _token_fed(n_clients, per_client, batch, t, vocab, seed=0):
    """Synthetic next-token federated data: [N, t] inputs, [N, t] shifted
    targets, tokens in [1, vocab) so pad_id=0 never collides."""
    from fedml_tpu.data.batching import build_federated_arrays
    from fedml_tpu.data.partition import partition_homo

    rng = np.random.RandomState(seed)
    seqs = rng.randint(1, vocab, size=(n_clients * per_client, t + 1))
    x = seqs[:, :t].astype(np.int32)
    y = seqs[:, 1:].astype(np.int32)
    return build_federated_arrays(x, y, partition_homo(len(x), n_clients),
                                  batch)


def _lm_scan_bench(model, n_clients, per_client, batch, cpr, t, vocab,
                   lr=0.1, rounds=3, min_call_s=None, api_cls=None,
                   api_kw=None):
    """Median seqs/sec of the whole-run scan for a token LM federation.

    With ``min_call_s`` set, the scan length is grown until a measured
    warm call exceeds it (the 0.4 s device-work floor of r3 VERDICT #1,
    with headroom for ~0.1 s of fixed per-call dispatch cost) — each growth
    recompiles once (scan length is static), so the loop converges in
    one or two steps. Returns (seqs/sec, rounds, call_s) then.

    ``api_cls``/``api_kw`` swap the algorithm (default FedAvgAPI) —
    the fed_adapter section measures FedAdapterAPI on the identical
    harness so the adapter-vs-dense tokens/s A/B shares every knob."""
    from functools import partial

    import jax

    from fedml_tpu.algos.config import FedConfig
    from fedml_tpu.algos.fedavg import FedAvgAPI
    from fedml_tpu.trainer.local import seq_softmax_ce

    fed = _token_fed(n_clients, per_client, batch, t, vocab)
    cfg = FedConfig(client_num_in_total=n_clients, client_num_per_round=cpr,
                    comm_round=1, epochs=1, batch_size=batch, lr=lr)
    api = (api_cls or FedAvgAPI)(model, fed, None, cfg,
                                 loss_fn=partial(seq_softmax_ce, pad_id=0),
                                 **(api_kw or {}))
    api.train_rounds_on_device(rounds)  # warmup/compile
    jax.block_until_ready(api.net.params)
    if min_call_s is None:
        return statistics.median(
            _timed_scan_trials(api, rounds, cpr * per_client))
    for _ in range(4):
        _check_section_deadline()
        t0 = time.perf_counter()
        losses = api.train_rounds_on_device(rounds)
        float(np.asarray(losses).sum())
        dt = time.perf_counter() - t0
        if dt >= min_call_s:
            break
        rounds = max(rounds + 1,
                     int(np.ceil(rounds * min_call_s * 1.3 / dt)))
        api.train_rounds_on_device(rounds)  # recompile + warm new length
        jax.block_until_ready(api.net.params)
    trials = _timed_scan_trials(api, rounds, cpr * per_client)
    med = statistics.median(trials)
    call_s = cpr * per_client * rounds / med
    assert call_s >= FLOOR_S, (
        f"timed call {call_s:.3f}s below the {FLOOR_S}s floor")
    return med, rounds, round(call_s, 3)


def bench_transformer_fed_mfu():
    """The high-MFU proof point (r2 VERDICT #3): a federated
    transformer_lm round at d_model=512 — lane-filling by construction —
    with MFU reported. Separates "the framework adds overhead" from
    "ResNet-56 is lane-starved": if the scan/vmap/aggregation scaffolding
    were the bottleneck, this config could not reach a healthy MFU
    either."""
    import jax

    from fedml_tpu.models import create_model
    from fedml_tpu.obs.flops import model_cost

    t, vocab, batch = 512, 10004, 8
    model = create_model("transformer_lm", vocab_size=vocab, d_model=512,
                         n_heads=8, n_layers=4, max_len=t, dtype="bf16")
    sps = _lm_scan_bench(model, n_clients=16, per_client=32, batch=batch,
                         cpr=8, t=t, vocab=vocab)
    fwd = model_cost(model, np.ones((batch, t), np.int32), train=False)
    delivered = 3.0 * fwd["flops"] / batch * sps / 1e12
    peak = _chip_peak(jax.devices()[0].device_kind)
    return {"seqs_per_sec": round(sps, 2),
            "tokens_per_sec": round(sps * t, 0),
            "d_model": 512, "seq_len": t,
            "delivered_tflops": round(delivered, 3),
            "mfu": (round(delivered / peak, 4) if peak else None)}


def _pretrain_dense_lm(x, y, vocab, seq_len, d_model, n_heads, n_layers,
                       steps=500, batch=32, lr=3e-3, seed=0):
    """Adam-pretrain a dense transformer_lm on the pooled token set —
    the 'shared pretrained LM' every fed_adapter arm finetunes FROM
    (LoRA is a finetuning method; a random frozen base has nothing for
    rank-r adapters to steer). Returns the host param tree."""
    from functools import partial

    import jax
    import jax.numpy as jnp
    import optax

    from fedml_tpu.models import create_model
    from fedml_tpu.trainer.local import NetState, model_fns, seq_softmax_ce

    fns = model_fns(create_model("transformer_lm", vocab_size=vocab,
                                 d_model=d_model, n_heads=n_heads,
                                 n_layers=n_layers, max_len=seq_len))
    net = fns.init(jax.random.PRNGKey(seed),
                   jnp.zeros((1, seq_len), jnp.int32))
    opt = optax.adam(lr)
    loss_fn = partial(seq_softmax_ce, pad_id=0)

    def loss(params, xb, yb):
        logits, _ = fns.apply(NetState(params, net.model_state), xb)
        return loss_fn(logits, yb).mean()

    @jax.jit
    def step(params, ost, xb, yb):
        l, g = jax.value_and_grad(loss)(params, xb, yb)
        u, ost = opt.update(g, ost)
        return optax.apply_updates(params, u), ost, l

    params, ost = net.params, opt.init(net.params)
    rng = np.random.RandomState(seed)
    xs, ys = jnp.asarray(x), jnp.asarray(y)
    for it in range(steps):
        if it % 50 == 0:
            _check_section_deadline()
        idx = rng.randint(0, len(x), batch)
        params, ost, l = step(params, ost, xs[idx], ys[idx])
    return jax.tree.map(np.asarray, params), float(l)


def bench_fed_adapter(n_clients=24, seq_len=8, vocab=1004, d_model=64,
                      n_heads=2, n_layers=2, rank=8, kgroup=8,
                      active_tokens=32, count_scale=8, pretrain_steps=500,
                      agg_rounds=12, buffer_k=2, batch=8, fed_rounds=8,
                      personal_passes=4, codec="topk0.1+int8",
                      mfu_rank=16):
    """Parameter-efficient federated finetuning, measured end to end
    (ROADMAP item 3; FedNLP arXiv:2104.08815, low-rank updates
    arXiv:2108.06098).

    **Wire story** — three FedBuff arms on the loopback tensor wire
    under ChaosTransport (dup+delay), all finetuning the SAME adam-
    pretrained dense base on the StackOverflow-NWP dialect law
    (data/synthetic.make_stackoverflow_shard ``law="dialect"``):
    ``dense_wire`` ships uncompressed dense deltas (the wire ruler),
    ``dense_codec`` ships topk+int8 EF dense deltas (the PR 10 codec
    point), ``adapter_codec`` ships topk+int8 EF ADAPTER-only deltas
    (cfg.adapter_rank — the upload shrinks by the rank ratio BEFORE the
    codec runs). ``adapter_bytes_ratio`` = dense_codec / adapter_codec
    bytes-per-upload (the ≥8x acceptance); ``adapter_vs_dense_wire`` the
    ≥~100x ruler; ``adapter_acc_delta`` the held-out NWP accuracy gap
    between the codec arms (≈0 = the bytes win is free).

    **Personalization story** — FedAdapterAPI on the same law: federated
    adapter rounds, then ditto-style per-client personalization passes
    into the PersonalAdapterStore; ``personalized_delta`` is the
    held-out personalized-vs-global accuracy gap (positive = the
    per-client adapter stacks beat one global adapter set).

    **Throughput story** — tokens/s + MFU (vs LOGICAL FLOPs of the
    injected model) for the federated ADAPTER round at the
    transformer_fed_mfu scale (d_model=512), A/B'd against the dense
    round on the identical ``_lm_scan_bench`` harness; guarded so a
    compile-bound box records an honest hole without discarding the
    wire/personalization numbers."""
    import dataclasses
    from functools import partial

    import jax

    from fedml_tpu.algos.config import FedConfig
    from fedml_tpu.algos.fedadapter import FedAdapterAPI
    from fedml_tpu.algos.fedbuff import FedML_FedBuff_distributed
    from fedml_tpu.comm.resilience import ChaosSpec
    from fedml_tpu.data.batching import batch_global, build_federated_arrays
    from fedml_tpu.data.synthetic import make_stackoverflow_nwp
    from fedml_tpu.models import create_model
    from fedml_tpu.models.adapter import param_count
    from fedml_tpu.obs.flops import model_cost
    from fedml_tpu.trainer.local import seq_softmax_ce

    loss_fn = partial(seq_softmax_ce, pad_id=0)
    law = dict(seq_len=seq_len, vocab=vocab, law="dialect", kgroup=kgroup,
               active_tokens=active_tokens, count_scale=count_scale)
    x, y, parts = make_stackoverflow_nwp(n_clients, seed=0, **law)
    xh, yh, parts_h = make_stackoverflow_nwp(n_clients, seed=1, **law)
    fed = build_federated_arrays(x, y, parts, batch)
    test = batch_global(xh, yh, batch)

    _check_section_deadline()
    base, pre_loss = _pretrain_dense_lm(x, y, vocab, seq_len, d_model,
                                        n_heads, n_layers,
                                        steps=pretrain_steps)

    def mk_model(r, scope="attn"):
        # Wire arms: "attn" scope — the steepest rank ratio (the MLP
        # pair dominates adapter bytes at small d_model). The
        # personalization arm uses "all" (more steering capacity; its
        # own profile is reported).
        return create_model("transformer_lm", vocab_size=vocab,
                            d_model=d_model, n_heads=n_heads,
                            n_layers=n_layers, max_len=seq_len,
                            adapter_rank=r, adapter_scope=scope)

    cfg0 = FedConfig(client_num_in_total=n_clients, client_num_per_round=8,
                     comm_round=agg_rounds, epochs=2, batch_size=batch,
                     lr=0.1, seed=0, frequency_of_the_test=10 ** 9)
    chaos = ChaosSpec(seed=11, dup_p=0.1, delay_p=0.1)

    def arm(wire_codec, adapter):
        _check_section_deadline()
        cfg = (dataclasses.replace(cfg0, adapter_rank=rank) if adapter
               else cfg0)
        srv = FedML_FedBuff_distributed(
            mk_model(rank if adapter else 0), fed, test, cfg,
            wire_codec=wire_codec, loopback_wire="tensor",
            buffer_k=buffer_k, chaos=chaos, idle_timeout_s=15.0,
            loss_fn=loss_fn, pretrained_params=base)
        h = srv.final_health
        uploads = len(srv.arrival_log)
        acc = ((srv.test_history[-1] if srv.test_history else {})
               .get("accuracy"))
        return {"codec": wire_codec, "uploads": uploads,
                "bytes_per_upload": round(h["bytes_rx"] / max(uploads, 1),
                                          1),
                "codec_refusals": h["codec_refusals"],
                "heldout_accuracy": (round(float(acc), 4)
                                     if acc is not None else None)}

    arms = {"dense_wire": arm("none", False),
            "dense_codec": arm(codec, False),
            "adapter_codec": arm(codec, True)}
    dense_params = param_count(base)
    out = {
        "law": {k: v for k, v in law.items()},
        "pretrain": {"steps": pretrain_steps, "final_loss":
                     round(pre_loss, 4)},
        "dense_params": dense_params,
        "chaos": "dup_p=0.1 delay_p=0.1", "wire": "tensor",
        "buffer_k": buffer_k, "rank": rank,
        "arms": arms,
    }
    d, a = (arms["dense_codec"]["bytes_per_upload"],
            arms["adapter_codec"]["bytes_per_upload"])
    w = arms["dense_wire"]["bytes_per_upload"]
    out["adapter_bytes_ratio"] = round(d / a, 2) if a else None
    out["adapter_vs_dense_wire"] = round(w / a, 2) if a else None
    acc_d = arms["dense_codec"]["heldout_accuracy"]
    acc_a = arms["adapter_codec"]["heldout_accuracy"]
    out["adapter_acc_delta"] = (round(acc_a - acc_d, 4)
                                if None not in (acc_a, acc_d) else None)

    # -- personalization: per-client adapter stacks vs the global set --
    _check_section_deadline()
    papi = FedAdapterAPI(mk_model(rank, "all"), fed, None,
                         dataclasses.replace(cfg0, lr=0.3,
                                             comm_round=fed_rounds),
                         loss_fn=loss_fn, base_params=base,
                         personal_interp=1.0)
    papi.train()
    fedh = build_federated_arrays(xh, yh, parts_h, batch)
    # personal_interp=1.0 restarts every pass from the GLOBAL adapters,
    # so only the last pass's state survives the store scatter — run
    # that pass directly (bit-identical to looping personal_passes
    # times, at 1/personal_passes the compute).
    _check_section_deadline()
    papi.personalize_cohort(np.arange(n_clients), seed=personal_passes - 1)
    pm = papi.evaluate_personalized(fedh)
    out["personalization"] = {k: round(float(v), 4) for k, v in pm.items()}
    out["personalized_delta"] = round(float(pm["personalized_delta"]), 4)
    out["adapter_profile"] = {k: (round(v, 5) if isinstance(v, float)
                                  else v)
                              for k, v in papi.adapter_profile().items()}

    # -- tokens/s + MFU at the transformer_fed_mfu scale (guarded) -----
    try:
        _check_section_deadline()
        t, mv, mb = 512, 10004, 8
        mk_big = lambda r: create_model(
            "transformer_lm", vocab_size=mv, d_model=512, n_heads=8,
            n_layers=4, max_len=t, dtype="bf16", adapter_rank=r,
            adapter_scope="attn")
        kw = dict(n_clients=16, per_client=32, batch=mb, cpr=8, t=t,
                  vocab=mv)
        a_sps = _lm_scan_bench(mk_big(mfu_rank), api_cls=FedAdapterAPI,
                               **kw)
        d_sps = _lm_scan_bench(mk_big(0), **kw)
        fwd = model_cost(mk_big(mfu_rank), np.ones((mb, t), np.int32),
                         train=False)
        delivered = 3.0 * fwd["flops"] / mb * a_sps / 1e12
        peak = _chip_peak(jax.devices()[0].device_kind)
        out["throughput"] = {
            "adapter_seqs_per_sec": round(a_sps, 2),
            "adapter_tokens_per_sec": round(a_sps * t, 0),
            "dense_seqs_per_sec": round(d_sps, 2),
            "adapter_vs_dense_step": round(a_sps / d_sps, 3),
            "d_model": 512, "seq_len": t, "adapter_rank": mfu_rank,
            "delivered_tflops": round(delivered, 3),
            "mfu": (round(delivered / peak, 4) if peak else None)}
        out["adapter_tokens_per_sec"] = out["throughput"][
            "adapter_tokens_per_sec"]
    except _SectionTimeout as e:
        # Keep the measured wire/personalization numbers — the MFU A/B
        # is the TPU round's axis; a compile-bound box records the hole.
        out["throughput"] = {"timeout": str(e)}
        out["adapter_tokens_per_sec"] = None
    return out


def bench_serving_plane(N=1_048_576, d_model=64, n_heads=2, n_layers=2,
                        vocab=256, seq_len=16, rank=4, max_batch=32,
                        decode_tokens=8, personalized=1024,
                        min_window_s=1.5, max_requests=1024,
                        max_seq_requests=256, deadline_s=0.01):
    """The r18 multi-tenant serving plane (ROADMAP item 2's "heavy
    traffic" half): requests/s + tokens/s through ``ServeManager``'s
    micro-batcher at N=2^20 STORED adapters, A/B'd against
    one-adapter-at-a-time serving, while a training-fleet writer keeps
    scattering personalization updates into the same store.

    **Store** — a ``PersonalAdapterStore`` over the full 2^20-client id
    space, memmap-spilled (``open_memmap`` w+ creates the [N, D] file
    sparse, so only TOUCHED rows cost pages — ``store_nominal_gb`` is
    the addressable size, not RSS); ``personalized`` rows are scattered
    with per-client perturbations, and request traffic draws half from
    those rows and half from never-personalized ids (the
    fallback-to-global gather path). Request ids come from an
    ACTIVE-USER working set whose pages are pre-faulted during setup:
    on this box a FIRST touch of a sparse-spill row costs ~100-500 ms
    of synchronous fault I/O (measured; virtio-backed ext4), which
    would make both arms a disk-fault bench — serving traffic
    concentrates on a working set anyway, and the cold-row cost is an
    environment property, not a plane property. ``personalized`` is
    sized by the same constraint: WRITE faults on fresh sparse rows run
    ~80 ms/row here, so materializing the personalized set is the
    section's dominant setup cost (deadline-checked per chunk).

    **Batched arm** — the real plane: requests submitted through the
    started ``ServeManager`` (bounded queue → deadline-or-batch-full
    micro-batches padded to ONE compiled [max_batch, seq_len] shape →
    locked store gather → vmapped frozen-base prefill → KV-cached
    greedy decode of ``decode_tokens``), p50/p95 from the plane's own
    latency histogram. **Sequential arm** — the same work one request
    at a time (single-row gather → jitted per-row prefill → B=1
    decode): per-request dispatch is exactly the overhead the batched
    plane amortizes ``max_batch``-fold, which is the serving story at
    this model size (the per-request LoRA FLOPs are tiny; dispatch
    dominates). ``serve_batch_speedup`` = batched rps / sequential rps
    (the ≥4x acceptance). Both arms run under the SAME concurrent
    fleet-writer load (copy-on-read lock discipline, tests/test_serve's
    torn-row drill at bench scale); both windows are floor-calibrated
    (``min_window_s``) so neither side sits in timer noise."""
    import shutil
    import tempfile
    import threading

    import jax
    import jax.numpy as jnp

    from fedml_tpu.models import create_model
    from fedml_tpu.models.adapter import (PersonalAdapterStore,
                                          adapter_model_fns)
    from fedml_tpu.serve import AdapterDecoder, ServeForward, ServeManager

    model = create_model("transformer_lm", vocab_size=vocab,
                         d_model=d_model, n_heads=n_heads,
                         n_layers=n_layers, max_len=seq_len + decode_tokens,
                         adapter_rank=rank, adapter_scope="all")
    fns = adapter_model_fns(model)
    net = fns.init(jax.random.PRNGKey(0),
                   jnp.zeros((1, seq_len), jnp.int32))
    glob = net.params

    spill = tempfile.mkdtemp(prefix="bench_serveplane_")
    mgr = None
    stop = threading.Event()
    try:
        store = PersonalAdapterStore(N, glob, spill_dir=spill)
        glob_vec = store.vec_of(glob)
        rng = np.random.RandomState(17)
        ids_p = rng.choice(N, personalized, replace=False).astype(np.int64)
        for lo in range(0, personalized, 512):
            _check_section_deadline()
            chunk = ids_p[lo:lo + 512]
            store.scatter(chunk, glob_vec[None]
                          + 0.02 * rng.randn(len(chunk),
                                             store.dim).astype(np.float32))

        fwd = ServeForward(fns, glob)
        dec = AdapterDecoder(model, fns, glob)
        mgr = ServeManager(fwd, store, glob, seq_len=seq_len,
                           max_batch=max_batch, deadline_s=deadline_s,
                           queue_cap=4 * max_batch, decoder=dec).start()

        req_rng = np.random.RandomState(3)
        # Active-user working set: half personalized rows, half
        # never-personalized (fallback-path) ids — page-warmed below so
        # the timed windows measure serving, not first-touch faults.
        pool = np.concatenate([
            ids_p[:personalized // 2],
            req_rng.choice(N, personalized // 2, replace=False)])
        for lo in range(0, len(pool), 256):
            _check_section_deadline()
            store.gather(pool[lo:lo + 256], glob)

        def make_request(i):
            cid = int(pool[(7 * i) % len(pool)])
            return cid, req_rng.randint(0, vocab, seq_len).astype(np.int32)

        def drive_wave(n):
            pend = [mgr.submit(*make_request(i),
                               max_new_tokens=decode_tokens)
                    for i in range(n)]
            for r in pend:
                r.result(timeout=300.0)
            return n

        # Warm every compiled program OUTSIDE the timed windows: the
        # padded [max_batch, T] prefill + decode (batched arm) and the
        # per-row prefill + B=1 decode (sequential arm).
        drive_wave(max_batch)
        # Fresh meters after the warm wave: its compile-bound waiters
        # would otherwise own the latency histogram's p95 tail.
        from fedml_tpu.obs.registry import MetricsRegistry

        mgr.registry = MetricsRegistry()
        one_vec = store.gather(ids_p[:1], glob)
        one_tok = req_rng.randint(0, vocab, (1, seq_len)).astype(np.int32)
        jax.block_until_ready(fwd.prefill_sequential(one_vec, one_tok))
        dec.generate(fwd.stacked_tree(one_vec), jnp.asarray(one_tok),
                     decode_tokens)

        # -- the training-fleet writer (runs under BOTH arms) ----------
        wrote = [0]

        def fleet_writer():
            wr = np.random.RandomState(5)
            while not stop.is_set():
                idx = ids_p[wr.randint(0, personalized, 8)]
                store.scatter(idx, glob_vec[None]
                              + 0.02 * wr.randn(8, store.dim)
                              .astype(np.float32))
                wrote[0] += 8
                time.sleep(0.001)  # a fleet cadence, not a spin loop

        writer = threading.Thread(target=fleet_writer, daemon=True,
                                  name="bench-fleet-writer")
        writer.start()

        # -- batched arm ------------------------------------------------
        served = 0
        t0 = time.perf_counter()
        while (time.perf_counter() - t0 < min_window_s
               and served < max_requests):
            served += drive_wave(4 * max_batch)
            _check_section_deadline()
        batched_s = time.perf_counter() - t0
        serve_rps = served / batched_s
        stats = mgr.stats()

        # -- sequential arm (one adapter at a time) ---------------------
        seq_done = 0
        t0 = time.perf_counter()
        while (time.perf_counter() - t0 < min_window_s
               and seq_done < max_seq_requests):
            cid, toks = make_request(seq_done)
            vec = store.gather([cid], glob)
            logits = fwd.prefill_sequential(vec, toks[None])
            dec.generate(fwd.stacked_tree(vec), jnp.asarray(toks[None]),
                         decode_tokens)
            jax.block_until_ready(logits)
            seq_done += 1
            if seq_done % 16 == 0:
                _check_section_deadline()
        seq_s = time.perf_counter() - t0
        seq_rps = seq_done / seq_s
        stop.set()
        writer.join(timeout=5.0)

        tokens_per_req = seq_len + decode_tokens
        return {
            "stored_adapters": N, "adapter_dim": store.dim,
            "store_nominal_gb": round(store.nbytes() / 1e9, 2),
            "memmap_spill": True, "personalized_rows": personalized,
            "model": {"d_model": d_model, "n_layers": n_layers,
                      "vocab": vocab, "rank": rank, "scope": "all"},
            "seq_len": seq_len, "decode_tokens": decode_tokens,
            "max_batch": max_batch, "deadline_ms": deadline_s * 1e3,
            "requests_served": served,
            "serve_rps": round(serve_rps, 1),
            "serve_tokens_per_sec": round(serve_rps * tokens_per_req, 0),
            "latency_ms_p50": stats.get("serve/latency_ms_p50"),
            "latency_ms_p95": stats.get("serve/latency_ms_p95"),
            "batch_fill_mean": stats.get("serve/batch_fill_mean"),
            "shed": stats.get("serve/shed", 0),
            "refused": stats.get("serve/refused", 0),
            "sequential_requests": seq_done,
            "sequential_rps": round(seq_rps, 2),
            "serve_batch_speedup": round(serve_rps / seq_rps, 2),
            "fleet_scatters_during_drill": wrote[0],
        }
    finally:
        stop.set()
        if mgr is not None:
            mgr.close()
        shutil.rmtree(spill, ignore_errors=True)


def bench_transformer_flash_e2e():
    """Flash attention inside REAL federated training rounds (not a
    kernel microbench): transformer_lm federations at T ∈ {2048, 4096,
    8192} with attn="flash" vs attn="dense" — fwd+bwd through the
    training loss, so the three backward kernels are on the clock too.
    The full training A/B curve lives HERE, in the driver-captured
    artifact, rather than in offline script runs quoted by the docs
    (r3 VERDICT #1c); each side's scan length is floor-calibrated
    (``_lm_scan_bench(min_call_s=...)``) so no point sits inside the
    dispatch-cost noise band."""
    from fedml_tpu.models import create_model

    vocab, out = 1004, {"points": {}}
    for t, per_client in [(2048, 8), (4096, 4), (8192, 2)]:
        mk = lambda attn: create_model(
            "transformer_lm", vocab_size=vocab, d_model=256, n_heads=4,
            n_layers=2, max_len=t, dtype="bf16", attn=attn)
        kw = dict(n_clients=8, per_client=per_client, batch=1, cpr=8,
                  t=t, vocab=vocab, min_call_s=0.5)
        flash_sps, fr, fcs = _lm_scan_bench(mk("flash"), **kw)
        dense_sps, dr, dcs = _lm_scan_bench(mk("dense"), **kw)
        out["points"][f"t{t}"] = {
            "flash_seqs_per_sec": round(flash_sps, 2),
            "dense_seqs_per_sec": round(dense_sps, 2),
            "flash_rounds_timed": fr, "dense_rounds_timed": dr,
            "flash_call_s": fcs, "dense_call_s": dcs,
            "speedup": round(flash_sps / dense_sps, 3)}
    return out


def main():
    import sys

    from fedml_tpu.utils import use_compile_cache

    use_compile_cache()

    def _log(msg):
        print(f"[bench +{time.perf_counter() - _t0:.0f}s] {msg}",
              file=sys.stderr, flush=True)

    import os

    # XLA profile capture is env-gated: jax.profiler hung against the
    # remote device of 2026-07-30 (the trace started, then blocked the
    # program indefinitely). Set BENCH_PROFILE=1 (or BENCH_ATTACHED=1,
    # which also switches the store-backed sections to the pipelined
    # round loop) to get the TensorBoard trace — docs/PLATFORMS.md
    # "Bench loop selection".
    attached = os.environ.get("BENCH_ATTACHED") == "1"
    profile_dir = ("runs/bench_profile"
                   if (os.environ.get("BENCH_PROFILE") == "1" or attached)
                   else None)
    # Wall-clock budget re-fit (r7; the r5-era scheme stopped bounding
    # the REAL wall clock and the r05 driver run exited rc=124 with no
    # headline):
    # 1. the PRIMARY now runs under its own cap (BENCH_PRIMARY_S — its
    #    calibration/trial loops check the section deadline, keeping
    #    whatever trials completed), so an uncapped primary can no
    #    longer eat the whole driver window before the budget loop even
    #    starts;
    # 2. a section is started only if its WORST CASE fits — elapsed +
    #    BENCH_SECTION_S <= BENCH_BUDGET_S — instead of merely starting
    #    before the budget line and overrunning it by a full section cap;
    # 3. the chronically compile-bound transformer_flash_e2e section
    #    (single uninterruptible XLA compiles at T=8192 that no
    #    between-units deadline check can preempt — what actually blew
    #    r05) is rotated out of the default list; BENCH_HEAVY=1 restores
    #    it, and flash/MFU coverage stays via flash_attention_sweep +
    #    transformer_fed_mfu.
    # Worst case is now BENCH_PRIMARY_S-bounded primary, sections ending
    # AT the budget line, + the JSON dump. Sections the budget skips are
    # recorded as {"skipped": ...}, capped sections as {"timeout": ...}
    # — explicit holes, not silent ones — and the headline ALWAYS lands
    # as the final line.
    global _SECTION_DEADLINE
    budget_s = float(os.environ.get("BENCH_BUDGET_S", "900"))
    section_s = float(os.environ.get("BENCH_SECTION_S", "240"))
    primary_s = float(os.environ.get("BENCH_PRIMARY_S", "420"))
    _t0 = time.perf_counter()
    _SECTION_DEADLINE = time.perf_counter() + primary_s
    try:
        primary = bench_cifar_resnet56(profile_dir=profile_dir)
    except _SectionTimeout as e:
        # Not even one timed trial inside the cap: an honest hole beats
        # a headline that never prints.
        primary = {"samples_per_sec": None,
                   "timeout": f"primary cap {primary_s:.0f}s: {e}"}
    finally:
        _SECTION_DEADLINE = None
    _log("primary done")
    sections = [("femnist_cnn_3400clients", bench_femnist_cnn_3400),
                ("store_windowed", bench_store_windowed),
                ("store_windowed_fedopt", bench_store_windowed_fedopt),
                ("zoo_windowed", bench_zoo_windowed),
                ("robust_agg", bench_robust_agg),
                ("chaos", bench_chaos),
                ("wire_codec", bench_wire_codec),
                ("fed_adapter", bench_fed_adapter),
                ("serving_plane", bench_serving_plane),
                ("ingest_profile", bench_ingest_profile),
                ("serving_1m", bench_serving_1m),
                ("agg_shards", bench_agg_shards),
                ("secagg", bench_secagg),
                ("fleet_sim", bench_fleet_sim),
                ("adaptive_control", bench_adaptive_control),
                ("stackoverflow_342k", bench_stackoverflow_342k),
                ("synthetic_1m", bench_synthetic_1m),
                ("serving_10m", bench_serving_10m),
                ("vit_cifar_shaped", bench_vit),
                ("layout_fused_round", bench_layout_fused_round),
                ("pod_reduce", bench_pod_reduce),
                ("cnn_mfu_levers", bench_cnn_mfu_levers),
                ("resnet56_s2d_stem", bench_resnet56_s2d),
                ("sharded_path_mesh1", bench_sharded_path),
                ("flash_attention_sweep", bench_flash_attention_sweep),
                ("transformer_fed_mfu", bench_transformer_fed_mfu)]
    if os.environ.get("BENCH_HEAVY") == "1":
        # Rotated out of the fast bench (budget hygiene, ROADMAP item
        # 4): resnet56_batch128_tuned measures the same lane-fill story
        # the s2d section's b128 row now carries with MFU submetrics;
        # transformer_flash_e2e is the compile-bound section that blew
        # the r05 wall clock.
        sections.append(("resnet56_batch128_tuned", bench_resnet56_b128))
        sections.append(("transformer_flash_e2e", bench_transformer_flash_e2e))
    sub = {}
    for name, fn in sections:
        elapsed = time.perf_counter() - _t0
        if elapsed + section_s > budget_s:
            sub[name] = {"skipped": (f"wall-clock budget {budget_s:.0f}s "
                                     f"cannot fit a {section_s:.0f}s "
                                     f"section cap at +{elapsed:.0f}s")}
            _log(f"{name} SKIPPED (budget)")
            continue
        _SECTION_DEADLINE = time.perf_counter() + section_s
        try:
            sub[name] = fn()
        except _SectionTimeout as e:
            sub[name] = {"timeout": (f"section cap {section_s:.0f}s: {e}")}
            _log(f"{name} TIMED OUT (section cap)")
        except Exception as e:  # one broken submetric must not kill the line
            sub[name] = {"error": f"{type(e).__name__}: {e}"[:200]}
        finally:
            _SECTION_DEADLINE = None
        if isinstance(sub[name], dict):
            # Memory trajectory for free: every section's record carries
            # the process RSS right after it ran (current, not the
            # monotone ru_maxrss peak — see _rss_mb).
            sub[name]["rss_after_mb"] = round(_rss_mb(), 1)
        _log(f"{name} done")

    sps = primary.pop("samples_per_sec")
    # The best honest number for the SAME task (CIFAR10 ResNet-56 FedAvg)
    # with the measured tuning levers applied — machine-readable next to
    # the untouched comparable primary (r3 VERDICT #8). The primary keeps
    # the reference stem + batch 32 for round-over-round comparability.
    tuned = None
    s2d = sub.get("resnet56_s2d_stem", {})
    candidates = [
        (s2d.get("s2d_b128_samples_per_sec"),
         "resnet56 stem=s2d + per-client batch 128"),
        (s2d.get("samples_per_sec"), "resnet56 stem=s2d, batch 32"),
        (sub.get("resnet56_batch128_tuned", {}).get("samples_per_sec"),
         "resnet56 reference stem, per-client batch 128"),
    ]
    candidates = [(v, c) for v, c in candidates if v]
    if candidates:
        best, config = max(candidates)
        tuned = {"samples_per_sec": best, "config": config,
                 "vs_baseline": round(best / BASELINE_SAMPLES_PER_SEC, 3)}
    # MFU as a first-class headline pair (ROADMAP item 4):
    # ``resnet56_mfu`` is the untouched comparable primary;
    # ``best_cnn_mfu`` is the best honest utilization for the same task
    # family with the measured lane-fill levers applied (s2d stem, b128,
    # compute layout) — always against LOGICAL FLOPs.
    cnn_mfus = [primary.get("mfu")] + [
        sub.get(sec, {}).get(key)
        for sec, key in (("resnet56_s2d_stem", "mfu"),
                         ("resnet56_s2d_stem", "s2d_b128_mfu"),
                         ("resnet56_batch128_tuned", "mfu"),
                         ("femnist_cnn_3400clients", "mfu"),
                         ("store_windowed", "mfu"),
                         ("layout_fused_round", "mfu"),
                         ("layout_fused_round", "layout_mfu"),
                         ("cnn_mfu_levers", "mfu"),
                         ("cnn_mfu_levers", "bf16_mfu"),
                         ("cnn_mfu_levers", "im2col_mfu"))]
    cnn_mfus = [m for m in cnn_mfus if isinstance(m, (int, float))]
    out = {
        "metric": "fedavg_cifar10_resnet56_samples_per_sec_per_chip",
        "value": sps,
        "unit": "samples/sec/chip",
        "vs_baseline": (round(sps / BASELINE_SAMPLES_PER_SEC, 3)
                        if sps else None),
        **primary,
        "resnet56_mfu": primary.get("mfu"),
        "best_cnn_mfu": max(cnn_mfus) if cnn_mfus else None,
        "tuned_best": tuned,
        "submetrics": sub,
    }
    # Full blob → a file the repo keeps (round-over-round comparison
    # material), plus stdout for anyone reading the whole log. The local
    # open() is anchored to THIS file's directory so it lands in the repo
    # wherever bench.py is launched from, but the HEADLINE records the
    # stable repo-relative pointer, not a machine-specific absolute path
    # (r5 ADVICE: the final stdout line is an artifact other machines
    # read).
    # Round-agnostic default blob name (r9 satellite: the hardcoded
    # docs/bench_r<N>_local.json default went stale every round and
    # misled readers about which round produced it). BENCH_BLOB still
    # overrides for archival copies.
    blob_rel = os.environ.get("BENCH_BLOB", "docs/bench_local.json")
    blob_path = (blob_rel if os.path.isabs(blob_rel)
                 else os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                   *blob_rel.split("/")))
    try:
        with open(blob_path, "w") as f:
            json.dump(out, f, indent=1)
    except OSError as e:
        print(f"[bench] could not write {blob_path}: {e}", file=sys.stderr)
        blob_rel = None
    print(json.dumps(out))
    sys.stdout.flush()
    print(json.dumps(build_headline(out, full_path=blob_rel)))


def build_headline(out, full_path="docs/bench_local.json"):
    """Compact headline emitted as the FINAL stdout line (r4 VERDICT #1):
    the driver records a bounded TAIL of stdout, and by r3/r4 the full
    line had outgrown it — the r03/r04 driver records carried neither the
    primary metric nor tuned_best (parsed: null). One scalar per submetric, <1 KB
    total (pinned by tests/test_bench_headline.py), so any tail window
    keeps the number that matters and the driver's JSON parse works."""
    sub = out.get("submetrics", {})
    tuned = out.get("tuned_best")

    def _scalar(name, *path):
        node = sub.get(name, {})
        for p in path:
            node = node.get(p, {}) if isinstance(node, dict) else {}
        return node if isinstance(node, (int, float)) else None

    return {
        "metric": out["metric"],
        "value": out["value"],
        "unit": out["unit"],
        "vs_baseline": out["vs_baseline"],
        "samples_per_sec_iqr": out.get("samples_per_sec_iqr"),
        "rounds_per_sec": out.get("rounds_per_sec"),
        "mfu": out.get("mfu"),
        "delivered_tflops": out.get("delivered_tflops"),
        # Utilization as a first-class trajectory pair (ROADMAP item 4):
        # the untouched primary's MFU under its canonical name, and the
        # best honest CNN-family MFU with the lane-fill levers applied
        # (every per-section mfu/delivered_tflops lives in the full
        # blob; the <1KB tail budget carries the two that define the
        # trajectory).
        "resnet56_mfu": out.get("resnet56_mfu", out.get("mfu")),
        "best_cnn_mfu": out.get("best_cnn_mfu"),
        "tuned_best": ({"samples_per_sec": tuned["samples_per_sec"],
                        "vs_baseline": tuned["vs_baseline"]}
                       if tuned else None),
        "sub": {
            "femnist_3400_rps": _scalar("femnist_cnn_3400clients",
                                        "rounds_per_sec"),
            # store_windowed_rps rotated out in r13 (the speedup carries
            # the windowed story; the rps lives in the full blob) to
            # fund the whole-zoo carry-record scalars under <1KB.
            "store_windowed_speedup": _scalar("store_windowed", "speedup"),
            # fedopt_windowed_speedup rotated out in r14 (the carry-
            # protocol story is carried by zoo_windowed_speedup since
            # r13, and store_windowed_speedup pins the windowed tier;
            # the blob keeps both fedopt scalars) to fund the pod-plane
            # scalars under the <1KB tail budget.
            # The whole-zoo carry capability records (r13): median
            # windowed/synced speedup across the newly converted
            # algorithms, and FedAc's accuracy-per-round win over FedAvg
            # at the same round budget (curves live in the full blob).
            "zoo_windowed_speedup": _scalar("zoo_windowed",
                                            "zoo_windowed_speedup"),
            # fedac_acc_delta rotated out in r18 (stable since r13;
            # zoo_windowed_speedup carries the whole-zoo carry story and
            # the blob keeps the accuracy delta) to fund the
            # serving-plane scalars under the <1KB tail budget.
            # robust_agg_overhead rotated out in r14 (stable since r4;
            # the blob keeps it) to fund the pod-plane scalars.
            # The r14 pod compute plane: the bf16 client-step A/B
            # (CPU-measured speedup + held-out accuracy delta at a
            # fixed round budget; per-arm MFU in the blob).
            # pod_dcn_bytes_ratio rotated out in r20 (structural —
            # measured exactly 4.0 since r14, the dcn_partials ratio is
            # C(padded)/G by construction; the blob keeps it) to fund
            # adaptive_ctrl_gain under the <1KB tail budget.
            "bf16_step_speedup": _scalar("cnn_mfu_levers",
                                         "bf16_speedup"),
            # The r20 adaptive control loop: controller accuracy per
            # virtual minute over the best static buffer_k arm on the
            # seeded load-spike drill — >= 1.0 means the closed loop
            # beats every static configuration (the staleness-p95 ratio
            # it holds while doing so lives in the blob).
            "adaptive_ctrl_gain": _scalar("adaptive_control",
                                          "adaptive_ctrl_gain"),
            # bf16_acc_delta rotated out in r16 (measured ~0 since r14 —
            # the speedup scalar carries the lever story and the blob
            # keeps the accuracy delta) to fund the sharded-aggregation-
            # plane scalars under the <1KB tail budget.
            # chaos_clean_overhead rotated out in r11 (stable ~1.08
            # since r5, and the wire_codec + ingest_profile arms both
            # run UNDER chaos now; the full blob keeps it) to fund
            # ingest_occupancy under the <1KB tail budget.
            "wire_bytes_ratio": _scalar("wire_codec", "wire_bytes_ratio"),
            # codec_acc_delta rotated out in r15 (measured 0.0 since
            # r10, and the fed_adapter section re-measures the
            # accuracy-under-codec story as adapter_acc_delta in the
            # blob); ingest_occupancy rotated out in r15 too (the r12
            # serving pair uploads_per_sec/ingest_speedup_4v1 carries
            # the ingest story; the blob keeps both) — funding the
            # adapter scalars under the <1KB tail budget.
            # The r15 adapter finetune: bytes-per-upload ratio of
            # adapter-only topk+int8 EF deltas over the dense-delta
            # codec point (both under ChaosTransport; the ~100x
            # vs-uncompressed ruler + held-out accuracy deltas +
            # personalized-vs-global live in the blob), and tokens/s of
            # the federated adapter round at the transformer_fed_mfu
            # scale.
            "adapter_bytes_ratio": _scalar("fed_adapter",
                                           "adapter_bytes_ratio"),
            "adapter_tokens_per_sec": _scalar("fed_adapter",
                                              "adapter_tokens_per_sec"),
            # The r18 serving plane: requests/s + tokens/s through the
            # micro-batched multi-adapter forward at 2^20 stored
            # adapters, and its speedup over one-adapter-at-a-time
            # serving under the same fleet-writer load (p50/p95 + arm
            # records live in the full blob).
            "serve_rps": _scalar("serving_plane", "serve_rps"),
            "serve_tokens_per_sec": _scalar("serving_plane",
                                            "serve_tokens_per_sec"),
            "serve_batch_speedup": _scalar("serving_plane",
                                           "serve_batch_speedup"),
            # uploads_per_sec rotated out in r18 (ingest_speedup_4v1
            # carries the ingest-wall story and serving_10m pins the
            # absolute uploads/s at 8x the population; the blob keeps
            # it) to fund the serving-plane scalars under <1KB.
            "ingest_speedup_4v1": _scalar("serving_1m",
                                          "ingest_speedup_4v1"),
            # The r16 sharded aggregation plane: uploads/s ratio of the
            # M=4 shard scale-out over M=1 on the live loopback control
            # plane (core-bounded; the per-arm records + cpu_count live
            # in the blob), the coordinator's dispatch occupancy at M=4
            # (the scale-out claim: the coordinator folds nothing), and
            # the 2^23-client drill's directory-routed fold rate.
            "agg_shard_speedup_4v1": _scalar("agg_shards", "speedup_4v1"),
            # agg_shard_coord_occupancy rotated out in r19 (structural,
            # not trajectory — measured ~0.13-0.16 << 0.5 since r16 and
            # speedup_4v1 carries the scale-out section; the blob keeps
            # the occupancy) to fund the secagg scalar under <1KB.
            # The r19 secure-aggregation plane: uploads/s cost of the
            # masked arm over the plain topk+int8 chaos drill (target
            # <= 1.3x; bytes/upload per arm + the seed-reveal drill's
            # latency live in the full blob).
            "secagg_overhead": _scalar("secagg", "secagg_overhead"),
            "serving_10m_uploads_per_sec": _scalar("serving_10m",
                                                   "uploads_per_sec"),
            "fleet_buffered_vs_firstk": _scalar(
                "fleet_sim", "buffered_vs_firstk_throughput"),
            # fleet_buffered_stale_p95_vs_async rotated out in r16
            # (stable since r6; buffered_vs_firstk carries the serving-
            # tier story and the blob keeps the staleness ratio) to fund
            # the sharded-plane scalars under the <1KB tail budget.
            # fleet_buffered_acc rotated out in r13 (stable 0.896 since
            # r6; the throughput/staleness pair carries the serving
            # story and the blob keeps the accuracy) to fund the
            # whole-zoo carry-record scalars under the <1KB tail budget.
            "stackoverflow_342k_rps": _scalar("stackoverflow_342k",
                                              "rounds_per_sec"),
            "synthetic_1m_rps": _scalar("synthetic_1m", "rounds_per_sec"),
            # synthetic_1m_peak_rss_ratio rotated out in r16 (stable
            # sublinear since r8; the serving_10m section now pins the
            # memory axis at 8x the population, host_rss_mb in the blob)
            # to fund the sharded-plane scalars under <1KB.
            # b128_sps / s2d_b128_sps rotated out in r9, s2d_sps in r10
            # (tuned_best and the s2d section's MFU pair carry the s2d
            # story), vit_sps + sharded_sps in r12 (stable since r4; the
            # full blob keeps them) to fund the layout/fused/MFU,
            # wire_codec and serving_1m scalars under the <1KB budget.
            "fused_speedup": _scalar("layout_fused_round",
                                     "fused_speedup"),
            # layout_pad_ratio rotated out in r18 (stable since r9 —
            # the pad A/B is structural, not trajectory; fused_speedup
            # carries the section and the blob keeps the ratio) to fund
            # the serving-plane scalars under the <1KB tail budget.
            "flash_speedup_t16384": _scalar("flash_attention_sweep",
                                            "points", "t16384", "speedup"),
            "transformer_mfu": _scalar("transformer_fed_mfu", "mfu"),
            # transformer_flash_e2e rides only under BENCH_HEAVY=1 (it
            # is what blew the r05 wall clock); its scalar stays out of
            # the default headline so the <1KB tail budget funds the
            # fleet_sim serving story instead.
        },
        "full": full_path,
    }


if __name__ == "__main__":
    main()
