"""The driver-artifact contract (r4 VERDICT #1): bench.py's FINAL stdout
line must be a compact headline that survives any bounded tail capture.

The r03/r04 driver records lost the primary metric because the full JSON line
outgrew the driver's tail window (parsed: null). ``build_headline`` is
the fix; these tests pin its contract against the REAL round-4 blob
(docs/bench_r4_local.json) so output growth can never silently break the
capture again.
"""

import json
import pathlib

import pytest

import bench

R4_BLOB = pathlib.Path(__file__).parent.parent / "docs" / "bench_r4_local.json"


@pytest.fixture
def r4_out():
    if not R4_BLOB.exists():
        pytest.skip("docs/bench_r4_local.json not checked in")
    return json.loads(R4_BLOB.read_text())


def test_headline_under_1kb_on_real_blob(r4_out):
    line = json.dumps(bench.build_headline(r4_out))
    assert len(line) < 1024, f"headline grew to {len(line)} bytes"


def test_headline_carries_the_primary_number(r4_out):
    h = bench.build_headline(r4_out)
    assert h["metric"] == "fedavg_cifar10_resnet56_samples_per_sec_per_chip"
    assert h["value"] == r4_out["value"] == 10484.75
    assert h["vs_baseline"] == 6.99
    assert h["mfu"] == 0.0291
    # The r9 utilization pair: resnet56_mfu falls back to the primary's
    # mfu on pre-r9 blobs; best_cnn_mfu is honest-null there.
    assert h["resnet56_mfu"] == 0.0291
    assert h["best_cnn_mfu"] is None
    assert h["tuned_best"]["samples_per_sec"] == 45633.22
    # One scalar per submetric section, numbers only (no nested blobs).
    for k, v in h["sub"].items():
        assert v is None or isinstance(v, (int, float)), (k, v)
    assert h["sub"]["transformer_mfu"] == pytest.approx(
        r4_out["submetrics"]["transformer_fed_mfu"]["mfu"])


def test_headline_roundtrips_and_tolerates_errored_submetrics():
    out = {"metric": "m", "value": 1.0, "unit": "u", "vs_baseline": 2.0,
           "submetrics": {"femnist_cnn_3400clients":
                          {"error": "RuntimeError: boom"}},
           "tuned_best": None}
    h = json.loads(json.dumps(bench.build_headline(out)))
    assert h["value"] == 1.0
    assert h["sub"]["femnist_3400_rps"] is None
    assert len(json.dumps(h)) < 1024


def test_main_budget_refit_headline_always_prints(monkeypatch, tmp_path,
                                                  capsys):
    """The r05 postmortem machinery, end-to-end with stubbed sections:
    the primary runs under BENCH_PRIMARY_S (a timeout degrades to an
    honest null, not a missing headline), a section starts only if its
    full BENCH_SECTION_S cap still fits inside BENCH_BUDGET_S (skipped
    otherwise), and the headline is ALWAYS the final stdout line."""
    fake_clock = [0.0]
    real_perf = bench.time.perf_counter
    monkeypatch.setattr(bench.time, "perf_counter",
                        lambda: fake_clock[0] or real_perf())

    def slow_primary(profile_dir=None):
        fake_clock[0] = 100.0  # primary ends at +100s on the fake clock
        return {"samples_per_sec": 1000.0, "trials": 5}

    def quick_section():
        fake_clock[0] += 50.0
        return {"ok": 1.0}

    fake_clock[0] = 1.0
    monkeypatch.setattr(bench, "bench_cifar_resnet56", slow_primary)
    for name in ("bench_femnist_cnn_3400", "bench_store_windowed",
                 "bench_store_windowed_fedopt", "bench_zoo_windowed",
                 "bench_robust_agg",
                 "bench_chaos", "bench_wire_codec", "bench_fed_adapter",
                 "bench_serving_plane",
                 "bench_ingest_profile",
                 "bench_serving_1m", "bench_agg_shards",
                 "bench_secagg",
                 "bench_fleet_sim", "bench_adaptive_control",
                 "bench_stackoverflow_342k", "bench_synthetic_1m",
                 "bench_serving_10m",
                 "bench_vit",
                 "bench_layout_fused_round", "bench_pod_reduce",
                 "bench_cnn_mfu_levers", "bench_resnet56_s2d",
                 "bench_sharded_path", "bench_flash_attention_sweep",
                 "bench_transformer_fed_mfu"):
        monkeypatch.setattr(bench, name, quick_section)
    # Budget 300s: primary ends at +100, sections take 50s each under a
    # 120s cap — only sections whose WORST CASE (+120s) fits start, so
    # the loop admits at +100, +150 (ends 170 < 180=300-120 boundary ok)
    # and skips once elapsed + 120 > 300.
    monkeypatch.setenv("BENCH_BUDGET_S", "300")
    monkeypatch.setenv("BENCH_SECTION_S", "120")
    monkeypatch.setenv("BENCH_PRIMARY_S", "400")
    monkeypatch.setenv("BENCH_BLOB", str(tmp_path / "blob.json"))
    monkeypatch.delenv("BENCH_HEAVY", raising=False)  # un-stubbed section
    bench.main()
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln.strip()]
    headline = json.loads(lines[-1])  # the FINAL line parses
    assert headline["value"] == 1000.0
    blob = json.loads((tmp_path / "blob.json").read_text())
    ran = [k for k, v in blob["submetrics"].items() if "ok" in v]
    skipped = [k for k, v in blob["submetrics"].items() if "skipped" in v]
    assert ran and skipped  # reservation admitted some, skipped the rest
    # Every section that RAN finished inside the budget: elapsed at its
    # start + the full section cap fit under 300s.
    assert len(ran) * 50 + 100 <= 300
    assert len(ran) + len(skipped) == 26


def test_main_primary_timeout_is_an_honest_hole(monkeypatch, tmp_path,
                                                capsys):
    def dead_primary(profile_dir=None):
        raise bench._SectionTimeout("compile ate the cap")

    monkeypatch.setattr(bench, "bench_cifar_resnet56", dead_primary)
    for name in ("bench_femnist_cnn_3400", "bench_store_windowed",
                 "bench_store_windowed_fedopt", "bench_zoo_windowed",
                 "bench_robust_agg",
                 "bench_chaos", "bench_wire_codec", "bench_fed_adapter",
                 "bench_serving_plane",
                 "bench_ingest_profile",
                 "bench_serving_1m", "bench_agg_shards",
                 "bench_secagg",
                 "bench_fleet_sim", "bench_adaptive_control",
                 "bench_stackoverflow_342k", "bench_synthetic_1m",
                 "bench_serving_10m",
                 "bench_vit",
                 "bench_layout_fused_round", "bench_pod_reduce",
                 "bench_cnn_mfu_levers", "bench_resnet56_s2d",
                 "bench_sharded_path", "bench_flash_attention_sweep",
                 "bench_transformer_fed_mfu"):
        monkeypatch.setattr(bench, name, lambda: {"ok": 1.0})
    monkeypatch.setenv("BENCH_BUDGET_S", "9999")
    monkeypatch.setenv("BENCH_SECTION_S", "9999")
    monkeypatch.setenv("BENCH_BLOB", str(tmp_path / "blob.json"))
    monkeypatch.delenv("BENCH_HEAVY", raising=False)  # un-stubbed section
    bench.main()
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln.strip()]
    headline = json.loads(lines[-1])
    assert headline["value"] is None  # null, not a missing headline
    assert headline["vs_baseline"] is None
    blob = json.loads((tmp_path / "blob.json").read_text())
    assert "timeout" in blob  # the hole is recorded, not silent


@pytest.mark.slow  # LSTM rounds on the 2-core CPU box (~1-2 min)
def test_bench_synthetic_1m_machinery_toy_scale():
    """The million-client section's machinery (shard builder → memmap
    spill → directory → warm → timed windows → overlap probe → scale
    ratios) end-to-end at toy scale; the real section runs the 2^20
    defaults."""
    bench._scale_state["342k"] = {"rps": 5.0, "rss_peak_mb": 500.0}
    try:
        out = bench.bench_synthetic_1m(
            C=2048, G=4, cpr=10,
            model_kw=dict(embedding_dim=8, hidden_size=16),
            min_window_s=1.0)
    finally:
        bench._scale_state.clear()
    assert out["clients"] == 2048 and out["shards"] == 4
    assert out["memmap_spill"] and out["rounds_per_sec"] > 0
    assert out["samples_per_sec"] > 0
    assert out["peak_rss_ratio"] is not None
    assert out["rps_vs_342k"] is not None
    assert out["prefetch_overlap_ratio"] >= 0
    assert out["directory_mb"] < 1.0  # O(clients) ints, not samples


@pytest.mark.slow  # calibrated timed windows on the 2-core box (~1 min)
def test_bench_layout_fused_round_machinery_toy_scale():
    """The r9 section's machinery end-to-end at toy scale: fused vs
    separate A/B, donation + recompile audit, and the compute-layout
    pad A/B (widths (12, 20) → padded) — the real section runs the
    (120, 120) just-under-lane defaults."""
    out = bench.bench_layout_fused_round(
        n_clients=8, per_client=16, batch=8, cpr=4, widths=(12, 20),
        min_s=0.4, reps=2)
    assert out["fused_samples_per_sec"] > 0
    assert out["separate_samples_per_sec"] > 0
    assert out["fused_speedup"] > 0
    assert out["steady_state_compiles"] == 0
    # signature matching is an upper bound, but inside one fresh section
    # the fused steady state must not hold a second full model copy
    assert out["live_model_copies"] < 2.0
    assert out["layout"] and not out["layout"]["identity"]
    assert out["layout_samples_per_sec"] > 0 and out["layout_pad_ratio"] > 0


@pytest.mark.slow  # three CNN-arm compiles on the 2-core box (~2-4 min)
def test_bench_cnn_mfu_levers_machinery_toy_scale():
    """The r14 MFU-lever section's machinery end-to-end at toy scale:
    fp32/bf16/im2col arms each land samples/s + delivered_tflops +
    accuracy, and the delta fields populate — the real section runs the
    FEMNIST-CNN defaults."""
    out = bench.bench_cnn_mfu_levers(n_clients=4, per_client=8, batch=4,
                                     cpr=4, acc_rounds=2, min_s=0.2,
                                     reps=2)
    for prefix in ("", "bf16_", "im2col_"):
        assert out[f"{prefix}samples_per_sec"] > 0
        assert out[f"{prefix}delivered_tflops"] is not None
        assert 0.0 <= out[f"{prefix}accuracy"] <= 1.0
    assert out["bf16_speedup"] > 0 and out["im2col_speedup"] > 0
    assert out["bf16_acc_delta"] is not None
    assert out["bf16_loss_delta"] is not None


@pytest.mark.slow  # LR mesh compiles x3 arms (~1 min)
def test_bench_pod_reduce_machinery_toy_scale():
    """The r14 pod-reduce section's machinery at toy scale: three arms
    on the simulated 2×4 DCN×ICI mesh, byte gauges read from the live
    reduce_profile — the DCN-vs-flat ratio is C(padded)/G exactly."""
    out = bench.bench_pod_reduce(n_clients=8, per_client=16, batch=8,
                                 cpr=4, min_s=0.2, reps=2)
    for arm in ("mean", "flat", "grouped"):
        assert out[f"{arm}_rounds_per_sec"] > 0
    assert out["dcn_partials_grouped"] == 2  # G = hosts
    assert out["dcn_partials_flat"] == 8  # cpr=4 padded to the 8 shards
    assert out["dcn_bytes_ratio"] == 4.0
    assert out["grouped_vs_flat_rps"] > 0


@pytest.mark.slow  # two spiked fleet-drill arms on the 2-core box (~10s)
def test_bench_adaptive_control_machinery_toy_scale():
    """The r20 adaptive-control section's machinery at toy scale: one
    static arm + the controller arm on the seeded spike trace, gain and
    staleness-ratio scalars populated, the decision trail in the blob —
    the real section runs the comm_round=24 two-static default (whose
    gain > 1 claim tests/test_ctrl.py pins on the full drill)."""
    out = bench.bench_adaptive_control(comm_round=12, static_ks=(2,))
    assert out["spike"]["factor"] == 6.0
    assert out["static_k2"]["acc_per_vmin"] > 0
    assert out["controller"]["acc_per_vmin"] > 0
    assert out["controller"]["actuations_applied"] >= 1
    assert out["controller"]["actuation_log"]  # the reproducibility trail
    assert out["controller"]["final_knobs"]["buffer_k"] >= 1
    assert out["adaptive_ctrl_gain"] is not None
    assert out["ctrl_vs_best_static_stale_p95"] is not None


def test_headline_tolerates_budget_skipped_submetrics():
    """Sections the wall-clock budget skips land as {"skipped": ...} in
    the blob; the headline must still build, carry None scalars for
    them, and stay under the tail-capture size."""
    out = {"metric": "m", "value": 1.0, "unit": "u", "vs_baseline": 2.0,
           "submetrics": {
               "store_windowed": {"windowed_rounds_per_sec": 12.5,
                                  "speedup": 1.7},
               "store_windowed_fedopt": {"windowed_rounds_per_sec": 9.25,
                                         "speedup": 1.4},
               "flash_attention_sweep":
                   {"skipped": "wall-clock budget 1350s exhausted"},
               "transformer_fed_mfu":
                   {"skipped": "wall-clock budget 1350s exhausted"}},
           "tuned_best": None}
    h = json.loads(json.dumps(bench.build_headline(out)))
    # store_windowed_rps rotated out of the headline in r13 (the full
    # blob keeps it; the speedup scalar carries the story).
    assert "store_windowed_rps" not in h["sub"]
    assert h["sub"]["store_windowed_speedup"] == 1.7
    # fedopt_windowed_rps rotated out of the headline in r10, the
    # speedup in r14 (zoo_windowed_speedup carries the carry-protocol
    # story; the full blob keeps both).
    assert "fedopt_windowed_rps" not in h["sub"]
    assert "fedopt_windowed_speedup" not in h["sub"]
    # The r14 pod-plane scalars: pod_dcn_bytes_ratio rotated out in r20
    # (structural 4.0 since r14; the blob keeps it) to fund
    # adaptive_ctrl_gain; bf16_acc_delta rotated out in r16 to fund the
    # sharded-plane scalars.
    assert "pod_dcn_bytes_ratio" not in h["sub"]
    assert h["sub"]["bf16_step_speedup"] is None
    assert "bf16_acc_delta" not in h["sub"]
    # The r20 adaptive-control scalar rides (None when skipped).
    assert h["sub"]["adaptive_ctrl_gain"] is None
    assert "robust_agg_overhead" not in h["sub"]  # rotated out in r14
    # The r16 sharded-aggregation-plane scalar rides (None when skipped).
    assert h["sub"]["agg_shard_speedup_4v1"] is None
    assert "agg_shard_coord_occupancy" not in h["sub"]  # rotated out, r19
    # The r19 secure-aggregation scalar rides (None when skipped).
    assert h["sub"]["secagg_overhead"] is None
    assert h["sub"]["serving_10m_uploads_per_sec"] is None
    assert "fleet_buffered_stale_p95_vs_async" not in h["sub"]  # r16
    assert "synthetic_1m_peak_rss_ratio" not in h["sub"]  # r16
    # The r13 whole-zoo scalars ride (None when the section was skipped).
    assert h["sub"]["zoo_windowed_speedup"] is None
    assert "fleet_buffered_acc" not in h["sub"]  # rotated out in r13
    # The r18 serving-plane scalars ride (None when the section was
    # skipped); uploads_per_sec, fedac_acc_delta and layout_pad_ratio
    # rotated out in r18 to fund them under the <1KB tail budget.
    assert h["sub"]["serve_rps"] is None
    assert h["sub"]["serve_tokens_per_sec"] is None
    assert h["sub"]["serve_batch_speedup"] is None
    assert "uploads_per_sec" not in h["sub"]
    assert "fedac_acc_delta" not in h["sub"]
    assert "layout_pad_ratio" not in h["sub"]
    assert h["sub"]["flash_speedup_t16384"] is None
    assert h["sub"]["transformer_mfu"] is None
    assert len(json.dumps(h)) < 1024


def test_headline_carries_serving_plane_scalars():
    """The r18 serving-plane trio rides the headline when the section
    ran (only the three scalars — p50/p95 and the arm records stay in
    the full blob)."""
    out = {"metric": "m", "value": 1.0, "unit": "u", "vs_baseline": 2.0,
           "submetrics": {"serving_plane": {"serve_rps": 120.5,
                                            "serve_tokens_per_sec": 2892.0,
                                            "serve_batch_speedup": 6.1,
                                            "latency_ms_p95": 40.2}},
           "tuned_best": None}
    h = json.loads(json.dumps(bench.build_headline(out)))
    assert h["sub"]["serve_rps"] == 120.5
    assert h["sub"]["serve_tokens_per_sec"] == 2892.0
    assert h["sub"]["serve_batch_speedup"] == 6.1
    assert "latency_ms_p95" not in h["sub"]
    assert len(json.dumps(h)) < 1024


@pytest.mark.slow  # serve-plane compiles (batched + B=1 decode) ~1-2 min
def test_bench_serving_plane_machinery_toy_scale():
    """The r18 serving-plane section's machinery end-to-end at toy
    scale: memmap store build → personalization scatter → warm →
    fleet-writer thread → batched window → sequential window → speedup
    — the real section runs the 2^20 defaults."""
    out = bench.bench_serving_plane(
        N=4096, d_model=16, n_heads=2, n_layers=1, vocab=64, seq_len=8,
        rank=2, max_batch=8, decode_tokens=2, personalized=64,
        min_window_s=0.3, max_requests=128, max_seq_requests=32)
    assert out["stored_adapters"] == 4096 and out["memmap_spill"]
    assert out["serve_rps"] > 0 and out["serve_tokens_per_sec"] > 0
    assert out["sequential_rps"] > 0 and out["serve_batch_speedup"] > 0
    assert out["latency_ms_p95"] is not None
    assert out["shed"] == 0 and out["refused"] == 0
    assert out["fleet_scatters_during_drill"] > 0
