"""chip_smoke.py and the compile-cache helper, as far as the CPU can say:
the helper leaves JAX alone when the cache is placed from outside and uses
``<checkout>/.jax_cache`` otherwise; the smoke refuses to run without a
chip; one failing phase fails the run without stopping the others; the
named CPU dry run says what it is."""

import json
import os
import re
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


CACHE_OPTIONS = ("jax_compilation_cache_dir",
                 "jax_compilation_cache_include_metadata_in_key",
                 "jax_hlo_source_file_canonicalization_regex")


@pytest.fixture
def cache_dir_config():
    """What ``use_compile_cache`` sets, as the test found it, put back
    after."""
    before = {name: getattr(jax.config, name) for name in CACHE_OPTIONS}
    yield
    for name, value in before.items():
        jax.config.update(name, value)


def test_compile_cache_placed_from_outside_sets_nothing(
        monkeypatch, cache_dir_config, tmp_path):
    from fedml_tpu.utils import use_compile_cache

    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir is None  # JAX reads the env


def test_compile_cache_defaults_to_checkout(monkeypatch, cache_dir_config):
    from fedml_tpu.utils import use_compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert use_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    # stale metadata from the cache would mislabel every profile
    assert jax.config.jax_compilation_cache_include_metadata_in_key
    here = os.path.join(REPO, "fedml_tpu", "utils.py")
    assert re.sub(jax.config.jax_hlo_source_file_canonicalization_regex, "",
                  here) == os.path.join("fedml_tpu", "utils.py")


def test_chip_smoke_without_a_chip_runs_nothing():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert out.stdout == ""  # no result line, and no phase ran
    assert "needs backend 'tpu'" in out.stderr and "phase" not in out.stderr


def test_a_failing_phase_fails_the_run_and_the_rest_still_run(capsys):
    import chip_smoke

    ran = []

    def fine(ctx, out):
        ran.append("fine")
        out["compile_s"] = 0.0

    def broken(ctx, out):
        ran.append("broken")
        out["partial"] = 1
        raise RuntimeError("forced")

    report = {"phases": {}}
    ok = chip_smoke.run_phases(
        {"a": fine, "b": broken, "c": fine}, ctx=None,
        cache=chip_smoke.CacheWatch("/nonexistent"), report=report)
    assert not ok and ran == ["fine", "broken", "fine"]
    assert [p["ok"] for p in report["phases"].values()] == [True, False, True]
    failed = report["phases"]["b"]
    assert failed["partial"] == 1 and "RuntimeError: forced" in failed["error"]
    # the per-phase stdout lines are JSON, and carry the failure
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    assert lines[1]["b"]["ok"] is False


def test_the_verdict_line_has_the_contract_keys_and_no_others():
    import chip_smoke

    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    report = {"ok": True, "device": device, "versions": {}, "dryrun": True,
              "compile_cache": "/x", "phases": {"a": {"ok": True}}}
    assert json.loads(chip_smoke.verdict(report)) == {"ok": True,
                                                      "device": device}


@pytest.mark.parametrize("sizes", ["REAL", "TOY"])
def test_the_kernels_phase_runs_lora_linear_where_it_takes_the_kernel(sizes):
    """``lora_linear_m.._k.._n..`` and its gated twin: on the chip Granite
    4.0-H's ``input_linear`` a client, in the dry run a shape that the same
    rule sends through the (interpreted) kernel."""
    import chip_smoke
    from fedml_tpu.ops.lora_linear import takes_kernel

    m, k, n, rank = getattr(chip_smoke, sizes).lora_linear
    assert takes_kernel(m, k, n, rank) and takes_kernel(m, k, n, rank, True)
    if sizes == "REAL":
        assert (m, k, n, rank) == (1024, 2048, 2 * 8192, 16)


@pytest.mark.slow  # ~2 min: every phase at toy width on the CPU
def test_dryrun_cpu_says_it_is_a_dry_run():
    import chip_smoke

    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), "--dryrun-cpu"],
        env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    summary, last = map(json.loads, out.stdout.strip().splitlines()[-2:])
    assert set(last) == {"ok", "device"} and last["ok"] is True
    assert last["device"]["platform"] == "cpu"
    assert summary["ok"] is True and summary["dryrun"] is True
    assert set(summary["phases"]) == set(chip_smoke.PHASES)
    assert all(p["ok"] for p in summary["phases"].values())
