"""The benchmark's own checks, in tier-1 (PR 30): nothing under ``benchmark/``
is edited, and a PR that renames something a runner or a reader depends on
(``api.dispatch_profile()``, ``api.net.model_state``, a span name, a file the
manifest names) learns it here and not on the chip.

(a) ``benchmark/selfcheck.py``'s five checks, each a case; (b) the four cells
on ``runners/fed_round.py`` rehearsed as committed (``--dryrun-cpu``, each in
a process of its own, as the driver starts them); (c) the plain references
import nothing of the program they judge; (d) PERF.md names every cell and
every per-layer metric of the manifest. A rehearsal's numbers are CPU numbers:
only their presence and their units are asserted. ``qwen3next_c4_s4k`` is
rehearsed by tests/test_benchmark_lm.py; ``granite4h_lora_c4_s1k`` (PR 32,
``runners/fed_adapter_lm_round.py``) here, traced, and there with its
stand-ins.
"""

import ast
import importlib.util
import json
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCHMARK = os.path.join(ROOT, "benchmark")

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)


@pytest.fixture(scope="module")
def selfcheck():
    """``benchmark/selfcheck.py`` as a module (it puts ``benchmark/`` on
    ``sys.path`` for its ``import run``; both are undone afterwards)."""
    path, had_run = list(sys.path), sys.modules.get("run")
    spec = importlib.util.spec_from_file_location(
        "bench_test_selfcheck", os.path.join(BENCHMARK, "selfcheck.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    yield module
    sys.path[:] = path
    if had_run is None:
        sys.modules.pop("run", None)
    else:
        sys.modules["run"] = had_run


@pytest.mark.parametrize("check", [
    "check_manifest", "check_arithmetic", "check_reducer", "check_generator",
    "check_reference"])
def test_selfcheck(selfcheck, check):
    said = getattr(selfcheck, check)()     # raises AssertionError on a fault
    assert isinstance(said, str) and said


@pytest.mark.parametrize("cell", [
    "resnet56_c16", "femnist_cnn_3400", "resnet56_c64_4chip",
    "resnet56_lda_c10"])
def test_cell_rehearses_on_the_cpu(cell):
    """``python3 benchmark/run.py --workload <cell> --dryrun-cpu --seconds 2
    --trace 0`` from the checkout's root: exit 0 and a last line that is the
    result. ``run.py`` itself asks for as many CPU devices as the cell has
    chips. Not ``correct``: two seconds of rounds need not beat the prior."""
    entry = next(w for w in MANIFEST["workloads"] if w["name"] == cell)
    mix_file = os.path.join(BENCHMARK, "traffic", entry["traffic"] + ".json")
    with open(mix_file) as f:
        assert json.load(f)["runner"] == "fed_round"
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    done = subprocess.run(
        [sys.executable, os.path.join("benchmark", "run.py"), "--workload",
         cell, "--seed", "0", "--seconds", "2", "--trace", "0",
         "--dryrun-cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-4000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["dryrun"] is True
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] >= entry["chips"]
    want = {m["name"]: m["unit"] for m in MANIFEST["end_to_end"]}
    assert len(want) == 4
    assert {k: v["unit"] for k, v in line["metrics"].items()} == want
    assert all(v["value"] > 0 for v in line["metrics"].values())


def test_the_adapter_cell_rehearses_on_the_cpu():
    """``granite4h_lora_c4_s1k`` as the driver starts it, at the files'
    dryrun sizes, traced: exit 0, ``correct`` (round 0 against the
    reference, the base unchanged and one operand), the program counter's
    metric on the line, and never a device number."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    done = subprocess.run(
        [sys.executable, os.path.join("benchmark", "run.py"), "--workload",
         "granite4h_lora_c4_s1k", "--seed", "3200000777", "--seconds", "2",
         "--trace", "1", "--dryrun-cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-4000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["dryrun"] is True and line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert line["device"]["platform"] == "cpu"
    assert line["metrics"]["adapter_upload_mb.round"]["unit"] == "MB"
    assert line["metrics"]["step_fill_pct"]["value"] == 100.0
    assert not [k for k in line["metrics"] if k.startswith("device_")
                or "roofline" in k or k.startswith("mfu")]


def test_the_expert_adapter_cell_rehearses_on_the_cpu():
    """``kexaone_lora_c4_s4k`` as the driver starts it, at the files' dryrun
    sizes, traced: exit 0, ``correct`` (round 0 against the reference, the
    base unchanged and one operand, no token dropped), the program counter's
    metric on the line, and never a device number."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    done = subprocess.run(
        [sys.executable, os.path.join("benchmark", "run.py"), "--workload",
         "kexaone_lora_c4_s4k", "--seed", "3400000777", "--seconds", "2",
         "--trace", "1", "--dryrun-cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-4000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["dryrun"] is True and line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert line["device"]["platform"] == "cpu"
    assert line["metrics"]["moe_held_load_max_over_mean"]["unit"] == "ratio"
    assert line["metrics"]["moe_held_load_max_over_mean"]["value"] >= 1
    assert line["metrics"]["step_fill_pct"]["value"] == 100.0
    assert not [k for k in line["metrics"] if k.startswith("device_")
                or "roofline" in k or k.startswith("mfu")]


def test_the_single_mixer_adapter_cell_rehearses_on_the_cpu():
    """``nemotron3nano_lora_c4_s4k`` as the driver starts it, at the files'
    dryrun sizes, traced: exit 0, ``correct`` (round 0 against the reference
    per kind of block, the base unchanged and one operand, no token dropped),
    both program counters' metrics on the line, every new reader called (a
    CPU rehearsal gives the device's none), and never a device number."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    done = subprocess.run(
        [sys.executable, os.path.join("benchmark", "run.py"), "--workload",
         "nemotron3nano_lora_c4_s4k", "--seed", "3900000777", "--seconds",
         "2", "--trace", "1", "--dryrun-cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-4000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["dryrun"] is True and line["correct"] is True, \
        done.stderr[-3000:]
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert line["device"]["platform"] == "cpu"
    assert line["metrics"]["moe_relu2_load_max_over_mean"]["unit"] == "ratio"
    assert line["metrics"]["moe_relu2_load_max_over_mean"]["value"] >= 1
    assert 0 < line["metrics"]["moe_relu2_fill_pct"]["value"] <= 100
    assert line["metrics"]["step_fill_pct"]["value"] == 100.0
    assert line["metrics"]["adapter_upload_mb_ssm_moe.round"]["value"] > 0
    assert not [k for k in line["metrics"] if k.startswith("device_")
                or "roofline" in k or k.startswith("mfu")]
    # two clients a group: the cell's own key reached the API
    assert "clients a group 2" in done.stderr


@pytest.mark.parametrize("reference", [
    "reference.py", "reference_qwen3_next.py", "reference_granite_hybrid.py",
    "reference_k_exaone.py", "reference_nemotron_h.py"])
def test_reference_imports_nothing_of_the_program(reference):
    """The yardstick is independent of the code under test: by its syntax
    tree, no import of ``fedml_tpu`` (at any depth of the file), and no
    ``__import__`` / ``importlib`` by which one could hide."""
    with open(os.path.join(BENCHMARK, reference)) as f:
        tree = ast.parse(f.read())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            assert node.level == 0, f"{reference}: a relative import"
            imported.add(node.module)
        elif isinstance(node, ast.Name):
            assert node.id != "__import__", reference
    roots = {name.split(".")[0] for name in imported}
    assert roots, f"{reference} imports nothing at all?"
    assert not roots & {"fedml_tpu", "importlib"}, roots


def _perf_section(number: int) -> str:
    with open(os.path.join(ROOT, "PERF.md")) as f:
        text = f.read()
    found = re.search(rf"^## {number}\. .*?(?=^## {number + 1}\. )", text,
                      re.M | re.S)
    assert found, f"PERF.md has no section {number}"
    return found.group(0)


def test_every_cell_has_its_row_in_perf_md():
    rows = re.findall(r"^\| `([A-Za-z0-9_]+)` = ", _perf_section(4), re.M)
    assert sorted(rows) == sorted(w["name"] for w in MANIFEST["workloads"])


def test_every_per_layer_metric_is_named_in_perf_md():
    named = set(re.findall(r"`([A-Za-z0-9_.]+)`", _perf_section(3)))
    missing = [m["name"] for m in MANIFEST["per_layer"]
               if m["name"] not in named]
    assert not missing, f"PERF.md section 3 does not name {missing}"
