"""The benchmark files of the Qwen3-Next cell and of the LDA cell (PR 28):
the configuration against the catalog entry, the model it builds and the
work counts; the two generators; the scope reducer on a hand-made trace; the
nine readers against the manifest. The same of the Granite 4.0-H adapter cell
(PR 32), at the end. CPU; nothing here reads a device number.
"""

import importlib.util
import json
import os
import re

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCHMARK = os.path.join(ROOT, "benchmark")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CONFIG = "qwen3_next_80b_a3b"
CELL = "qwen3next_c4_s4k"
NEW_READERS = [
    "device_ms.gdn.round", "device_ms.attn.round", "device_ms.moe.round",
    "device_ms.head.round", "device_ms.client_fold.round",
    "gdn_scan_roofline_pct", "attn_core_roofline_pct",
    "moe_experts_roofline_pct", "moe_load_max_over_mean"]


def _load(relative: str):
    path = os.path.join(BENCHMARK, relative)
    name = "bench_test_" + relative.replace("/", "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _json(*relative):
    with open(os.path.join(ROOT, *relative)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    return _json("benchmark", "configs", CONFIG + ".json")


@pytest.fixture(scope="module")
def mix():
    return _json("benchmark", "traffic", "resident_silos_c4_s4k.json")


# --- the configuration ------------------------------------------------------

def test_every_published_number_is_in_the_file_or_in_reduced(config):
    """The catalog entry's ``config`` key for key: equal, or listed in
    ``reduced`` with the published value beside it; ``reduced`` names no
    width."""
    if not os.path.exists(CATALOG):
        pytest.skip("the model-configs catalog is not on this machine")
    with open(CATALOG) as f:
        entry, = [row for row in map(json.loads, f)
                  if row["source_url"] == config["source"]]
    for key, value in entry["config"].items():
        if key in config["reduced"]:
            assert config["published"][key] == value, key
            assert config[key] != value, key
        else:
            assert config[key] == value, key
    assert sorted(config["reduced"]) == sorted(config["published"])
    widths = ("hidden_size", "intermediate", "_dim", "_rank", "per_tok")
    assert not [k for k in config["reduced"]
                if any(w in k for w in widths)]
    manifest = _json("BENCHMARK.json")
    listed, = [c for c in manifest["configs"] if c["name"] == CONFIG]
    assert listed["reduced"] == config["reduced"]
    assert listed["source"] == config["source"]
    assert len(config["reduced_detail"]) == len(config["reduced"])


def test_the_factory_builds_what_the_file_states(config):
    """``factory_kwargs`` is the file's own top level (the router keeps the
    published width, ``num_experts_held`` is the file's ``num_experts``),
    and the model it builds has the parameters the file counts."""
    from fedml_tpu.models.qwen3_next import qwen3_next

    kwargs = config["factory_kwargs"]
    for key, value in kwargs.items():
        if key == "num_experts":
            assert value == config["published"]["num_experts"]
        elif key in config:
            assert config[key] == value, key
    assert kwargs["num_experts_held"] == config["num_experts"]
    assert config["classes"] == config["vocab_size"] == kwargs["vocab_size"]
    model = qwen3_next(**kwargs)
    ids = jax.ShapeDtypeStruct((1, 128), np.int32)
    shapes = jax.eval_shape(
        lambda i: model.init({"params": jax.random.PRNGKey(0)}, i), ids)
    counted = sum(int(np.prod(leaf.shape))
                  for leaf in jax.tree.leaves(shapes["params"]))
    assert counted == config["parameters"]
    counts = _load(config["counts"])
    assert counts.parameters(kwargs) == config["parameters"]
    from fedml_tpu.algos.config import FedConfig

    assert all(hasattr(FedConfig(), k) for k in config["fed_config"])


def test_the_frozen_flops_are_the_counts(config, mix):
    counts = _load(config["counts"])
    assert (counts.train_flops_per_sequence(config, mix)
            == config["train_flops_per_sample"])
    per_token = counts.forward_flops_per_token(config["factory_kwargs"],
                                               mix["sequence_length"])
    assert {k: round(v) for k, v in per_token.items()} \
        == config["forward_flops_per_token"]
    # by hand: the delta rule a value head a token, and the causal core
    s = config["factory_kwargs"]
    ops, moved = counts.gdn_scan_forward(s, 1)
    assert ops == 32 * (7 * 128 * 128 + 3 * 128)
    assert moved == (2 * 16 * 128 + 2 * 32 * 128) * 2 + 2 * 32 * 4
    ops, _ = counts.attn_core_forward(s, 4)
    assert ops == 16 * 4 * 256 * (4 * 5 // 2)
    assert counts.held_assignments_per_token(s) == 10 * 16 / 512
    assert counts.steps_per_round(mix) == 8
    peaks = _json("benchmark", "peaks.json")["TPU v5 lite"]
    for kernel in ("gdn_scan", "attn_core", "moe_experts"):
        assert counts.roofline_ms_per_round(kernel, config, mix, peaks) > 0


def test_the_dryrun_sizes_are_the_cpu_tests_sizes(config):
    import test_qwen3_next

    small = dict(test_qwen3_next.SMALL, attention="flash")
    assert config["dryrun"]["factory_kwargs"] == small


# --- the generators ---------------------------------------------------------

def test_packed_documents_from_a_seed(mix):
    gen = _load("generators/lm_zipf_docs.py")
    small = {**mix, **mix["dryrun"]}
    cfg = {"classes": 256}
    a = gen.generate(small, cfg, 2 ** 31 + 11)
    b = gen.generate(small, cfg, 2 ** 31 + 11)
    c = gen.generate(small, cfg, 7)
    x, y, parts, counts = a
    assert x.dtype == np.int32 and x.shape == (8, 128) == y.shape
    assert all(np.array_equal(u, v) for u, v in zip(a[:2], b[:2]))
    assert not np.array_equal(x, c[0]) and np.array_equal(counts, c[3])
    assert x.min() >= gen.SEPARATOR and x.max() < 256     # never pad_id
    np.testing.assert_array_equal(y[:, :-1], x[:, 1:])    # the next token
    assert (y[:, -1] == gen.PAD).all()
    assert (x == gen.SEPARATOR).any()
    assert sorted(np.concatenate(list(parts.values()))) == list(range(8))
    # Zipf: a client's commonest word is far commoner than its median one
    freq = np.sort(np.bincount(x[:2].ravel(), minlength=256))[::-1]
    assert freq[0] > 8 * max(1, freq[64])


def test_lda_silos_do_not_move_with_the_seed():
    gen = _load("generators/class_templates_lda.py")
    mix = _json("benchmark", "traffic", "resident_lda_c10.json")
    small = {**mix, **mix["dryrun"]}
    cfg = {"classes": 10, "input_shape": [8, 8, 3]}
    a, b = gen.generate(small, cfg, 2 ** 31 + 5), gen.generate(small, cfg, 9)
    assert np.array_equal(a[1], b[1]) and np.array_equal(a[3], b[3])
    assert all(np.array_equal(a[2][c], b[2][c]) for c in a[2])
    assert not np.array_equal(a[0], b[0])
    assert a[3].sum() == len(a[0]) == 160 and len(set(a[3])) > 1
    # the label skew of a Dirichlet split: some silo lacks some class
    held = [set(a[1][a[2][c]]) for c in a[2]]
    assert any(len(h) < 10 for h in held)
    # the cell's own sizes: 10 silos of 835 to 1,720 samples, 27 steps
    from fedml_tpu.data.partition import partition_dirichlet

    own = np.random.RandomState(0)
    y = own.permutation(np.repeat(np.arange(10, dtype=np.int32), 1280))
    sizes = [len(p) for p in partition_dirichlet(y, 10, 0.5, seed=0).values()]
    assert (min(sizes), max(sizes), -(-max(sizes) // 64)) == (835, 1720, 27)


# --- the scope reducer ------------------------------------------------------

def test_scope_of_takes_the_innermost_and_survives_transforms():
    rsc = _load("reduce_scopes.py")
    path = ("jit(gather_step)/fed.local_train/transpose(jvp(fed.model.gdn))/"
            "fed.model.gdn.scan/while/body/dot_general")
    assert rsc.scope_of([path]) == "fed.model.gdn.scan"
    assert rsc.scope_of(["jit(f)/fed.aggregate/fed.client_fold/add"]) \
        == "fed.client_fold"
    assert rsc.scope_of(["x/fed.model.moe/fed.model.moe.route/sort"]) \
        == "fed.model.moe.route"
    assert rsc.scope_of(["jit(f)/fed.local_train/mul", ""]) == ""


def test_reduce_books_self_time_by_scope():
    """Two rounds; a ``while`` of 60 with a 40 body under the scan scope, an
    attention op clipped by the window's end, an op with no model scope."""
    rsc = _load("reduce_scopes.py")
    events = [
        ["host", "bench.round", 0, 100, ""], ["host", "fed.round", 5, 90, ""],
        ["host", "bench.round", 100, 100, ""],
        ["host", "fed.round", 105, 90, ""],
        ["op", "while.1", 10, 60, "fed.model.gdn"],
        ["op", "fusion.2", 20, 40, "fed.model.gdn.scan"],
        ["op", "fusion.3", 80, 10, ""],
        ["op", "custom-call.4", 190, 30, "fed.model.attn.core"]]
    got = rsc.reduce(events)
    assert got["rounds"] == 2
    assert got["device_ns_by_scope"] == {
        "": 10, "fed.model.attn.core": 10, "fed.model.gdn": 20,
        "fed.model.gdn.scan": 40}
    assert got["top_ops"][0] == ["fed.model.gdn.scan", "fusion.2", 40]
    assert rsc.reduce(events[4:]) is None
    assert "fed.model.gdn.scan" in rsc.table(got)


def test_scopes_are_read_from_the_event_metadata(tmp_path):
    """The wire reader's walk (reduce_spans' ``_fields``) on a hand-made
    ``.xplane.pb``: one plane, two events' metadata, a string stat and an
    interned one."""
    import test_round_spans as spans

    rsc = _load("reduce_scopes.py")
    pb = spans._pb
    path = "jit(f)/fed.local_train/%s"
    plane = pb(
        (1, 3), (2, b"/device:TPU:0"),
        (5, pb((1, 300), (2, pb((1, 300), (2, (
            path % "fed.model.moe/fed.model.moe.experts/dot").encode()))))),
        (4, pb((1, 1), (2, pb(
            (1, 1), (2, b"%fusion.1 = f32[8]"),
            (5, pb((1, 7), (5, (path % "fed.model.head/take").encode()))))))),
        (4, pb((1, 2), (2, pb(
            (1, 2), (2, b"%fusion.2 = f32[8]"),
            (5, pb((1, 7), (7, 300))))))),
        (4, pb((1, 3), (2, pb((1, 3), (2, b"%copy.3 = f32[8]"))))))
    file = tmp_path / "t.xplane.pb"
    file.write_bytes(pb((1, plane)))
    assert rsc.metadata_scopes(str(file), "/device:TPU:0") == {
        "%fusion.1 = f32[8]": "fed.model.head",
        "%fusion.2 = f32[8]": "fed.model.moe.experts"}
    assert rsc.metadata_scopes(str(file), "/device:TPU:1") == {}


# --- the readers ------------------------------------------------------------

@pytest.mark.parametrize("name", NEW_READERS)
def test_reader_agrees_with_the_manifest_and_reads_none_without_a_trace(
        name, tmp_path, monkeypatch):
    manifest = _json("BENCHMARK.json")
    entry, = [m for m in manifest["per_layer"] if m["name"] == name]
    reader = _load(f"layer_metrics/{name}.py")
    assert {k: entry[k] for k in ("layer", "unit", "moves")} == reader.META
    cells = [w["name"] for w in manifest["workloads"] if reader.applies(w)]
    assert cells == entry["workloads"] == [CELL]
    monkeypatch.setattr(reader.rsc.rs, "TRACE_DIR", str(tmp_path))
    assert reader.read({"chips": 1, "rounds": 3,
                        "device_kind": "TPU v5 lite"}) is None


def test_readers_on_a_reduction(config, mix, monkeypatch):
    """Scope times through the readers: milliseconds a round by prefix, a
    roofline share from the counts, ``None`` for a program with no model
    scope (the parent of this PR), and the counter."""
    reduction = {"rounds": 4, "device_ns_by_scope": {
        "": 5e6, "fed.model.gdn": 300e6, "fed.model.gdn.scan": 100e6,
        "fed.model.attn.core": 80e6, "fed.client_fold": 8e6}}
    summary = {"device_kind": "TPU v5 lite", "moe_load_max_over_mean": 1.5,
               "counts": {"module": config["counts"], "config": config,
                          "mix": mix}}
    gdn = _load("layer_metrics/device_ms.gdn.round.py")
    monkeypatch.setattr(gdn.rsc, "traced", lambda: reduction)
    assert gdn.read(summary) == pytest.approx(100.0)
    scan = _load("layer_metrics/gdn_scan_roofline_pct.py")
    monkeypatch.setattr(scan.rsc, "traced", lambda: reduction)
    counts = _load(config["counts"])
    least = counts.roofline_ms_per_round(
        "gdn_scan", config, mix, _json("benchmark", "peaks.json")["TPU v5 lite"])
    assert scan.read(summary) == pytest.approx(100.0 * least / 25.0)
    assert 0 < scan.read(summary) < 100
    moe = _load("layer_metrics/device_ms.moe.round.py")
    monkeypatch.setattr(moe.rsc, "traced", lambda: reduction)
    assert moe.read(summary) == 0.0         # scopes named, none of them moe's
    monkeypatch.setattr(moe.rsc, "traced", lambda: {
        "rounds": 4, "device_ns_by_scope": {"": 5e6}})
    assert moe.read(summary) is None        # a program that names none
    with pytest.raises(KeyError):
        monkeypatch.setattr(scan.rsc, "traced", lambda: reduction)
        scan.read({**summary, "device_kind": "TPU v9"})
    load = _load("layer_metrics/moe_load_max_over_mean.py")
    assert load.read(summary) == 1.5 and load.read({}) is None


# --- the comparison that decides ``correct``, through the harness ------------

@pytest.mark.parametrize("stand_in, failing", [
    (None, set()),
    # the contract's planted faults and the lower-precision control, each in
    # the program's place in the comparison: every one has to fail a limit
    ("unchanged_state", {
        "embedding", "norms", "gdn_gates", "conv", "delta_projections",
        "held_experts", "router", "shared_expert", "attention_projections",
        "head"}),
    ("last_batch_left_out", None),
    ("reference_bits:4", None),
])
def test_the_cell_is_correct_and_its_stand_ins_are_not(config, mix, stand_in,
                                                       failing):
    """``benchmark/run.py``'s own context and runner at the rehearsal's
    sizes on the CPU: the round passes every limit of ``TOLERANCES``; a
    state left unchanged reads 1 on every kind; a client's last batch left
    out and the reference with float8's 4-bit products each fail at least
    one limit, so ``correct`` comes out false."""
    import argparse

    run = _load("run.py")
    manifest = run.load_manifest()
    cell = run.by_name(manifest["workloads"], CELL, "workload")
    args = argparse.Namespace(seed=2800000555, seconds=0.2, trace=0,
                              dryrun_cpu=True)
    if stand_in:
        mix = {**mix, "stand_in": stand_in}
    ctx = run.Ctx(manifest, cell, config, mix, args, "cpu")
    runner = ctx.load_module(f"runners/{mix['runner']}.py")
    result = runner.run(ctx)
    errors = result["summary"]["reference_errors"]
    over = {k for k, v in errors.items()
            if v > runner.TOLERANCES[k]["limit"]}
    assert result["summary"]["moe_dropped_tokens"] == 0
    assert result["correct"] == (stand_in is None), errors
    if failing is None:
        assert over, errors
    else:
        assert over == failing, errors
    if stand_in == "unchanged_state":
        assert all(errors[k] == pytest.approx(1.0) for k in failing)


def test_every_limit_lies_between_its_two_readings():
    """``TOLERANCES`` carries both readings of every limit: the largest the
    program read on the chip, and the control (the reference at the nearest
    precision below; for the loss, the planted fault). At least twice the
    first and at most half the second, so that fresh seeds have room and
    the control has none."""
    runner = _load("runners/fed_lm_round.py")
    assert set(runner.TOLERANCES) == {
        kind for kind, _ in runner._KINDS} | {"loss"}
    for kind, t in runner.TOLERANCES.items():
        assert 2 * t["program"] <= t["limit"] <= t["control"] / 2, kind


# --- Granite 4.0-H in the adapter round (PR 32) ------------------------------

GRANITE, GRANITE_CELL = "granite_4_0_h_micro", "granite4h_lora_c4_s1k"
GRANITE_READERS = [
    "device_ms.ssm.round", "device_ms.mlp.round", "device_ms.lora.round",
    "ssm_scan_roofline_pct", "adapter_upload_mb.round"]
SHARED_READERS = [
    "device_ms.attn.round", "device_ms.head.round", "attn_core_roofline_pct"]


@pytest.fixture(scope="module")
def granite():
    return _json("benchmark", "configs", GRANITE + ".json")


@pytest.fixture(scope="module")
def lora_mix():
    return _json("benchmark", "traffic", "resident_silos_c4_s1k_lora.json")


def test_granite_holds_every_published_number_uncut(granite):
    """The catalog entry's ``config`` key for key, equal: ``reduced`` is
    empty, in the file and in the manifest; the factory is handed the same
    dictionary plus the adapters and the compute choices."""
    if not os.path.exists(CATALOG):
        pytest.skip("the model-configs catalog is not on this machine")
    with open(CATALOG) as f:
        entry, = [row for row in map(json.loads, f)
                  if row["source_url"] == granite["source"]]
    for key, value in entry["config"].items():
        assert granite[key] == value, key
        assert granite["factory_kwargs"][key] == value, key
    assert granite["reduced"] == []
    manifest = _json("BENCHMARK.json")
    listed, = [c for c in manifest["configs"] if c["name"] == GRANITE]
    assert listed["reduced"] == [] and listed["source"] == granite["source"]
    extra = set(granite["factory_kwargs"]) - set(entry["config"])
    assert extra == {"adapter_rank", "adapter_alpha", "adapter_b_std",
                     "attention", "base_dtype"}
    assert granite["adapter"]["rank"] == granite["factory_kwargs"][
        "adapter_rank"] == 16
    assert set(granite["assumed"]) >= {"A_log", "dt_bias", "D", "adapter",
                                       "adapter_b_std"}
    from fedml_tpu.algos.config import FedConfig

    assert all(hasattr(FedConfig(), k) for k in granite["fed_config"])


def test_granite_parameters_and_flops_recounted(granite, lora_mix):
    """The file's counts against the model's own trees (shapes only: no
    3 G parameters are made) and against ``counts/granite_hybrid.py``; the
    frozen FLOPs against the derivation, part by part and by hand."""
    from fedml_tpu.models.adapter import split_frozen
    from fedml_tpu.models.granite_hybrid import granite_hybrid

    kwargs = granite["factory_kwargs"]
    model = granite_hybrid(**kwargs)
    ids = jax.ShapeDtypeStruct((1, 16), np.int32)
    shapes = jax.eval_shape(
        lambda i: model.init({"params": jax.random.PRNGKey(0)}, i), ids)
    base, adapters = split_frozen(shapes["params"])

    def count(tree):
        return sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(tree))

    assert {"base": count(base), "adapters": count(adapters)} \
        == granite["parameters"] == {"base": 3191396096,
                                     "adapters": 28823552}
    assert {str(leaf.dtype) for leaf in jax.tree.leaves(base)} == {"bfloat16"}
    counts = _load(granite["counts"])
    assert counts.parameters(kwargs) == granite["parameters"]
    assert (counts.train_flops_per_sequence(granite, lora_mix)
            == granite["train_flops_per_sample"])
    per_token = counts.forward_flops_per_token(kwargs, 1024)
    assert {k: round(v) for k, v in per_token.items()} \
        == granite["forward_flops_per_token"]
    # by hand: every frozen matrix but the embedding's rows is a product
    matrices = 36 * (2048 * 8512 + 4096 * 2048) + 4 * (
        2 * 2048 * 2048 + 2 * 2048 * 512) + 40 * 3 * 2048 * 8192
    assert per_token["matrices"] == 2 * matrices
    assert per_token["head"] == 2 * 2048 * 100352
    assert per_token["lora"] == 2 * granite["parameters"]["adapters"]
    ops, moved = counts.ssm_scan_forward(kwargs, 1)
    assert ops == 64 * (5 * 64 * 128 + 64)
    assert moved == (2 * 64 * 64 + 2 * 128) * 2 + 64 * 4
    ops, _ = counts.attn_core_forward(kwargs, 4)
    assert ops == 32 * 4 * 64 * (4 * 5 // 2)
    assert granite["train_flops_per_sample"] == round(1024 * (
        2 * (per_token["matrices"] + per_token["conv"] + per_token["head"])
        + 3 * (per_token["ssm_scan"] + per_token["attn_core"]
               + per_token["lora"])))
    assert counts.steps_per_round(lora_mix) == 8
    peaks = _json("benchmark", "peaks.json")["TPU v5 lite"]
    for kernel in ("ssm_scan", "attn_core"):
        assert counts.roofline_ms_per_round(kernel, granite, lora_mix,
                                            peaks) > 0


def test_granite_dryrun_sizes_are_the_cpu_tests_sizes(granite):
    import test_granite_hybrid

    assert granite["dryrun"]["factory_kwargs"] == {
        **test_granite_hybrid.CFG, "attention": "flash",
        "base_dtype": "bfloat16"}
    assert granite["dryrun"]["classes"] == 257


def test_hybrid_reducer_books_the_low_rank_pairs_apart():
    """``reduce_scopes_hybrid.py``: ``reduce_scopes``' walk with another
    list. A pair's products inside a mixer are ``fed.model.lora``'s there
    and the mixer's in the Qwen cell's reducer, which does not know them."""
    rsh, rsc = _load("reduce_scopes_hybrid.py"), _load("reduce_scopes.py")
    path = ("jit(f)/fed.local_train/transpose(jvp(fed.model.attn))/"
            "fed.model.lora/dot_general")
    assert rsh._reducer.scope_of([path]) == "fed.model.lora"
    assert rsc.scope_of([path]) == "fed.model.attn"
    assert rsh._reducer.scope_of(
        ["x/fed.model.ssm/fed.model.ssm.scan/while/body/mul"]) \
        == "fed.model.ssm.scan"
    assert rsc.scope_of(["x/fed.model.ssm/fed.model.ssm.scan/mul"]) == ""
    assert rsc.SCOPES[0] == "fed.model.gdn.scan"      # the other is untouched


@pytest.mark.parametrize("name", GRANITE_READERS)
def test_granite_reader_agrees_with_the_manifest(name, tmp_path, monkeypatch):
    manifest = _json("BENCHMARK.json")
    entry, = [m for m in manifest["per_layer"] if m["name"] == name]
    reader = _load(f"layer_metrics/{name}.py")
    assert {k: entry[k] for k in ("layer", "unit", "moves")} == reader.META
    cells = [w["name"] for w in manifest["workloads"] if reader.applies(w)]
    assert cells == entry["workloads"] == [GRANITE_CELL]
    rs = (reader.rsh.rsc if hasattr(reader, "rsh") else reader.rsc).rs
    monkeypatch.setattr(rs, "TRACE_DIR", str(tmp_path))
    assert reader.read({"chips": 1, "rounds": 3,
                        "device_kind": "TPU v5 lite"}) is None


@pytest.mark.parametrize("name", SHARED_READERS)
def test_one_partition_reads_the_granite_cell(name, granite):
    """The accepted readers of the attention and head scopes read through
    ``reduce_scopes.py``, which books a low-rank pair under the layer it
    stands in: they keep their lists, and the cell's file names their
    scopes apart (``scopes_unread``), so that every reader of the cell
    reads ``reduce_scopes_hybrid.py``'s one partition."""
    manifest = _json("BENCHMARK.json")
    entry, = [m for m in manifest["per_layer"] if m["name"] == name]
    assert entry["workloads"] == [CELL]
    reader = _load(f"layer_metrics/{name}.py")
    cell, = [w for w in manifest["workloads"] if w["name"] == GRANITE_CELL]
    assert not reader.applies(cell)
    assert reader.SCOPE in granite["scopes_unread"]
    assert reader.SCOPE not in granite["scopes"]
    for metric in manifest["per_layer"]:
        if GRANITE_CELL in metric.get("workloads", []):
            module = _load(f"layer_metrics/{metric['name']}.py")
            assert not hasattr(module, "SCOPE") or hasattr(module, "rsh")


@pytest.mark.parametrize("scope", [
    "fed.model.attn.core", "fed.model.head", "fed.client_fold"])
def test_the_two_reducers_agree_where_both_know_the_scope(scope, monkeypatch):
    """``reduce_scopes_hybrid.py`` is a second copy of ``reduce_scopes.py``
    with another list written over its private names: until scopes come
    from the configuration (PERF.md section 7) this holds the copy to the
    original. The names it overwrites exist; a scope both lists know, with
    no low-rank pair inside, is booked alike by both and reads the same
    time from the same reduction."""
    rsh, rsc = _load("reduce_scopes_hybrid.py"), _load("reduce_scopes.py")
    for private in ("SCOPES", "_SCOPE", "scope_of", "scope_ms", "traced",
                    "roofline_pct"):
        assert hasattr(rsc, private), private
    assert scope in rsc.SCOPES and scope in rsh.SCOPES
    assert rsh._reducer.SCOPES == rsh.SCOPES and rsc.SCOPES != rsh.SCOPES
    paths = [f"jit(f)/fed.local_train/jvp({scope})/dot_general",
             f"jit(f)/fed.local_train/transpose(jvp({scope}))/mul",
             f"jit(f)/{scope}/while/body/add"]
    for path in paths:
        assert rsh._reducer.scope_of([path]) == rsc.scope_of([path]) == scope
    reduction = {"rounds": 4, "device_ns_by_scope": {"": 5e6, scope: 12e6}}
    monkeypatch.setattr(rsh._reducer, "traced", lambda: reduction)
    monkeypatch.setattr(rsc, "traced", lambda: reduction)
    assert rsh.scope_ms(scope) == rsc.scope_ms(scope) == pytest.approx(3.0)


def test_granite_readers_on_a_reduction(granite, lora_mix, monkeypatch):
    reduction = {"rounds": 4, "device_ns_by_scope": {
        "": 5e6, "fed.model.ssm": 300e6, "fed.model.ssm.conv": 20e6,
        "fed.model.ssm.scan": 400e6, "fed.model.mlp": 200e6,
        "fed.model.lora": 40e6, "fed.model.attn.core": 8e6}}
    summary = {"device_kind": "TPU v5 lite", "adapter_upload_mb_round": 461.2,
               "counts": {"module": granite["counts"], "config": granite,
                          "mix": lora_mix}}
    rsh = _load("reduce_scopes_hybrid.py")
    monkeypatch.setattr(rsh._reducer, "traced", lambda: reduction)
    assert rsh.scope_ms("fed.model.ssm") == pytest.approx(180.0)
    assert rsh.scope_ms("fed.model.lora") == pytest.approx(10.0)
    counts = _load(granite["counts"])
    least = counts.roofline_ms_per_round(
        "ssm_scan", granite, lora_mix,
        _json("benchmark", "peaks.json")["TPU v5 lite"])
    share = rsh.roofline_pct(summary, "ssm_scan", "fed.model.ssm.scan")
    assert share == pytest.approx(100.0 * least / 100.0) and 0 < share < 100
    monkeypatch.setattr(rsh._reducer, "traced", lambda: {
        "rounds": 4, "device_ns_by_scope": {"": 5e6}})
    assert rsh.scope_ms("fed.model.ssm") is None    # a program with no scope
    upload = _load("layer_metrics/adapter_upload_mb.round.py")
    assert upload.read(summary) == 461.2 and upload.read({}) is None


@pytest.mark.parametrize("stand_in, failing", [
    (None, set()),
    ("unchanged_state", {"ssm_a", "ssm_b", "attention_a", "attention_b",
                         "mlp_a", "mlp_b"}),
    ("last_batch_left_out", None),
    ("reference_bits:4", None),
])
def test_the_adapter_cell_is_correct_and_its_stand_ins_are_not(
        granite, lora_mix, stand_in, failing):
    """``benchmark/run.py``'s own context and ``fed_adapter_lm_round`` at
    the rehearsal's sizes on the CPU: the round passes every limit; a state
    left unchanged reads 1 on every kind of pair; a client's last batch left
    out and the reference with float8's 4-bit products each fail a limit."""
    import argparse

    run = _load("run.py")
    manifest = run.load_manifest()
    cell = run.by_name(manifest["workloads"], GRANITE_CELL, "workload")
    args = argparse.Namespace(seed=3200000555, seconds=0.2, trace=0,
                              dryrun_cpu=True)
    mix = {**lora_mix, "stand_in": stand_in} if stand_in else lora_mix
    ctx = run.Ctx(manifest, cell, granite, mix, args, "cpu")
    runner = ctx.load_module(f"runners/{mix['runner']}.py")
    result = runner.run(ctx)
    summary = result["summary"]
    errors = summary["reference_errors"]
    over = {k for k, v in errors.items()
            if v > runner.TOLERANCES[k]["limit"]}
    assert result["correct"] == (stand_in is None), errors
    if failing is None:
        assert over, errors
    else:
        assert over == failing, errors
    if stand_in == "unchanged_state":
        assert all(errors[k] == pytest.approx(1.0) for k in failing)
    assert summary["base_bytes_operand"] == 2 * summary["base_parameters"]
    assert summary["adapter_upload_mb_round"] == pytest.approx(
        2 * 4 * summary["adapter_parameters"] / 1e6)


def test_every_adapter_limit_lies_between_its_two_readings():
    runner = _load("runners/fed_adapter_lm_round.py")
    assert set(runner.TOLERANCES) == {
        f"{kind}_{half}" for kind, _ in runner._SITES
        for half in "ab"} | {"loss"}
    for kind, t in runner.TOLERANCES.items():
        # the loss's two readings lie 3.3 times apart: it keeps twice the
        # program's and has 1.5 times under the control's
        least = 1.5 if kind == "loss" else 2
        assert 2 * t["program"] <= t["limit"] <= t["control"] / least, kind
    # the loss is held against the 4-bit control like the rest; what a
    # planted fault reads is kept beside it and sets no limit
    loss = runner.TOLERANCES["loss"]
    assert loss["control"] < loss["fault"] and loss["limit"] < loss["control"]
    assert [k for k, t in runner.TOLERANCES.items() if "fault" in t] == [
        "loss"]
    assert runner.kind_of("lora_in_proj_a") == "ssm_a"
    assert runner.kind_of("lora_output_linear_b") == "mlp_b"
    with pytest.raises(KeyError):
        runner.kind_of("in_proj")


# --- K-EXAONE in the adapter round (PR 34) ------------------------------------

KEXAONE, KEXAONE_CELL = "k_exaone_236b_a23b", "kexaone_lora_c4_s4k"
KEXAONE_READERS = [
    "device_ms.attn_window.round", "device_ms.attn_full.round",
    "device_ms.moe_held.round", "attn_window_roofline_pct",
    "moe_held_lora_roofline_pct", "moe_held_load_max_over_mean"]


@pytest.fixture(scope="module")
def kexaone():
    return _json("benchmark", "configs", KEXAONE + ".json")


@pytest.fixture(scope="module")
def moe_lora_mix():
    return _json("benchmark", "traffic", "resident_silos_c4_s4k_lora.json")


def test_kexaone_holds_every_published_number_or_names_it_reduced(kexaone):
    """The catalog row's ``config`` key for key: equal, or one of the three
    cuts of scale with the published value beside it; every width as
    published, in the file and in what the factory is handed."""
    assert kexaone["reduced"] == ["num_hidden_layers", "num_experts",
                                  "vocab_size"]
    assert kexaone["published"] == {"num_hidden_layers": 48,
                                    "num_experts": 128, "vocab_size": 153600}
    held = {k: kexaone[k] for k in kexaone["reduced"]}
    assert held == {"num_hidden_layers": 5, "num_experts": 16,
                    "vocab_size": 19200}
    kwargs = kexaone["factory_kwargs"]
    # the router stays 128 wide and top-8; 16 experts from 0 are held
    assert (kwargs["num_experts"], kwargs["num_experts_held"],
            kwargs["first_expert_held"], kwargs["num_experts_per_tok"]) == (
                128, 16, 0, 8)
    for key, value in {"hidden_size": 6144, "num_attention_heads": 64,
                       "num_key_value_heads": 8, "head_dim": 128,
                       "intermediate_size": 18432,
                       "moe_intermediate_size": 2048, "sliding_window": 128,
                       "routed_scaling_factor": 2.5}.items():
        assert kexaone[key] == kwargs[key] == value, key
    manifest = _json("BENCHMARK.json")
    listed, = [c for c in manifest["configs"] if c["name"] == KEXAONE]
    assert listed["reduced"] == kexaone["reduced"]
    assert listed["source"] == kexaone["source"]
    assert set(kexaone["assumed"]) >= {"block", "qk_norm", "positions",
                                       "router_bias", "weight_scale",
                                       "adapter_b_std"}
    assert any("multi-token-prediction" in d for d in kexaone["departures"])
    assert "8 chips share each layer" in kexaone["deployment"]
    from fedml_tpu.algos.config import FedConfig

    assert all(hasattr(FedConfig(), k) for k in kexaone["fed_config"])
    if not os.path.exists(CATALOG):
        pytest.skip("the model-configs catalog is not on this machine")
    with open(CATALOG) as f:
        entry, = [row for row in map(json.loads, f)
                  if row["source_url"] == kexaone["source"]]
    for key, value in entry["config"].items():
        if key in kexaone["reduced"]:
            assert kexaone["published"][key] == value, key
        else:
            assert kexaone[key] == value, key
            assert kwargs[key] == value, key


def test_kexaone_parameters_and_flops_recounted(kexaone, moe_lora_mix):
    """The file's counts against the model's own trees (shapes only: no
    3.7 G parameters are made) and against ``counts/k_exaone.py``; the
    frozen FLOPs against the derivation, part by part and by hand."""
    from fedml_tpu.models.adapter import split_frozen
    from fedml_tpu.models.k_exaone import k_exaone

    kwargs = kexaone["factory_kwargs"]
    model = k_exaone(**kwargs)
    ids = jax.ShapeDtypeStruct((1, 16), np.int32)
    shapes = jax.eval_shape(
        lambda i: model.init({"params": jax.random.PRNGKey(0)}, i), ids)
    base, adapters = split_frozen(shapes["params"])

    def count(tree):
        return sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(tree))

    # ISSUE 34's 3,711,959,040 of matrices + 69,376 of norms and bias
    assert {"base": count(base), "adapters": count(adapters)} \
        == kexaone["parameters"] == {"base": 3711959040 + 69376,
                                     "adapters": 31358976}
    assert {str(leaf.dtype) for leaf in jax.tree.leaves(base)} == {"bfloat16"}
    assert set(shapes["counters"]) == {f"layer_{i}" for i in range(1, 5)}
    counts = _load(kexaone["counts"])
    assert counts.parameters(kwargs) == kexaone["parameters"]
    assert (counts.train_flops_per_sequence(kexaone, moe_lora_mix)
            == kexaone["train_flops_per_sample"])
    per_token = counts.forward_flops_per_token(kwargs, 4096)
    assert {k: round(v) for k, v in per_token.items()} \
        == kexaone["forward_flops_per_token"]
    attn = 6144 * 8192 + 2 * 6144 * 1024 + 8192 * 6144
    expert = 3 * 6144 * 2048
    assert per_token["attn_projections"] == 2 * 5 * attn
    assert per_token["dense_mlp"] == 2 * 3 * 6144 * 18432
    assert per_token["shared_experts"] == per_token["held_experts"] \
        == 2 * 4 * expert        # one held assignment a token at 16 of 128
    assert per_token["head"] == 2 * 6144 * 19200
    assert per_token["lora"] == 2 * (
        5 * 688128 + 1179648 + 4 * 2 * 393216)
    # the window core counts the visible pairs only
    ops, _ = counts.attn_core_forward(kwargs, 4096, 128)
    assert ops == 64 * 4 * 128 * (128 * 129 // 2 + (4096 - 128) * 128)
    full, _ = counts.attn_core_forward(kwargs, 4096, 0)
    assert full == 64 * 4 * 128 * (4096 * 4097 // 2) and ops < full / 15
    frozen, low, moved, matrices = counts.held_experts_forward(kwargs, 4096)
    assert frozen == 4096 * 2 * expert and matrices == 16 * expert * 2
    assert low == 4096 * 2 * 16 * 3 * (6144 + 2048)
    assert kexaone["train_flops_per_sample"] == round(4096 * (
        2 * sum(v for k, v in per_token.items()
                if k not in ("attn_core", "lora"))
        + 3 * (per_token["attn_core"] + per_token["lora"])))
    assert round(sum(per_token.values()) / 1e7) == 276     # 2.76 GFLOP
    assert counts.steps_per_round(moe_lora_mix) == 8
    peaks = _json("benchmark", "peaks.json")["TPU v5 lite"]
    for kernel in ("attn_window", "moe_held_lora"):
        assert counts.roofline_ms_per_round(kernel, kexaone, moe_lora_mix,
                                            peaks) > 0
    with pytest.raises(KeyError):
        counts.roofline_ms_per_round("ssm_scan", kexaone, moe_lora_mix, peaks)


def test_kexaone_mix_is_the_two_accepted_mixes_crossed(moe_lora_mix, mix):
    """``resident_silos_c4_s4k``'s documents and sizes, the adapter mix's
    runner family; only the learning rate is this cell's own."""
    for key in ("clients", "counts", "cohort", "batch", "epochs",
                "sequence_length", "zipf_exponent", "rank_offset_max",
                "doc_length", "generator", "client_optimizer", "placement",
                "trace_rounds"):
        assert moe_lora_mix[key] == mix[key], key
    assert moe_lora_mix["runner"] == "fed_adapter_moe_lm_round"
    assert os.path.exists(os.path.join(
        BENCHMARK, "runners", moe_lora_mix["runner"] + ".py"))


@pytest.mark.parametrize("name", KEXAONE_READERS)
def test_kexaone_reader_agrees_with_the_manifest(name, tmp_path, monkeypatch):
    manifest = _json("BENCHMARK.json")
    entry, = [m for m in manifest["per_layer"] if m["name"] == name]
    reader = _load(f"layer_metrics/{name}.py")
    assert {k: entry[k] for k in ("layer", "unit", "moves")} == reader.META
    cells = [w["name"] for w in manifest["workloads"] if reader.applies(w)]
    assert cells == entry["workloads"] == [KEXAONE_CELL]
    monkeypatch.setattr(reader.rsm.rsc.rs, "TRACE_DIR", str(tmp_path))
    assert reader.read({"chips": 1, "rounds": 3,
                        "device_kind": "TPU v5 lite"}) is None


def test_no_accepted_scope_reader_applies_to_the_kexaone_cell(kexaone):
    """The accepted readers of ``fed.model.moe``, ``.attn``, ``.head``,
    ``.mlp``, ``.lora`` and of ``expert_tokens`` keep their lists: the
    cell's file keeps what they key on (``scopes``, ``counters``) clear of
    their names and lists its own partition's apart."""
    manifest = _json("BENCHMARK.json")
    cell, = [w for w in manifest["workloads"] if w["name"] == KEXAONE_CELL]
    for metric in manifest["per_layer"]:
        reader = _load(f"layer_metrics/{metric['name']}.py")
        listed = KEXAONE_CELL in metric.get("workloads", [KEXAONE_CELL])
        assert reader.applies(cell) == listed, metric["name"]
    assert kexaone["scopes"] == []
    assert "expert_tokens" not in kexaone["counters"]
    rsm = _load("reduce_scopes_swa_moe.py")
    assert set(kexaone["scopes_swa_moe"]) | set(kexaone["scopes_unread"]) \
        == set(rsm.SCOPES) - {"fed.client_fold"}


def test_swa_moe_reducer_books_window_full_and_pairs_apart():
    """``reduce_scopes_swa_moe.py``: ``reduce_scopes``' walk with this
    model's list; the copy is held to the original as the hybrid one is."""
    rsm, rsc = _load("reduce_scopes_swa_moe.py"), _load("reduce_scopes.py")
    for private in ("SCOPES", "_SCOPE", "scope_of", "scope_ms", "traced",
                    "roofline_pct", "_config_of"):
        assert hasattr(rsc, private), private
    of = rsm._reducer.scope_of
    assert of(["jit(f)/fed.local_train/jvp(fed.model.attn.window)/"
               "fed.model.attn.window.core/pallas_call"]) \
        == "fed.model.attn.window.core"
    assert of(["x/transpose(jvp(fed.model.attn.full))/fed.model.lora/dot"]) \
        == "fed.model.lora"
    assert of(["x/fed.model.moe/fed.model.moe.experts/while/body/dot"]) \
        == "fed.model.moe.experts"
    assert of(["x/fed.model.moe/fed.model.moe.shared/fed.model.lora/dot"]) \
        == "fed.model.lora"
    assert of(["x/fed.model.mlp/dot_general"]) == "fed.model.mlp"
    assert rsc.SCOPES[0] == "fed.model.gdn.scan"      # the other is untouched
    for scope in ("fed.model.head", "fed.client_fold",
                  "fed.model.moe.route"):
        path = f"jit(f)/fed.local_train/jvp({scope})/dot_general"
        assert of([path]) == rsc.scope_of([path]) == scope


def test_kexaone_readers_on_a_reduction(kexaone, moe_lora_mix, monkeypatch):
    reduction = {"rounds": 4, "device_ns_by_scope": {
        "": 5e6, "fed.model.attn.window": 300e6,
        "fed.model.attn.window.core": 100e6, "fed.model.attn.full": 90e6,
        "fed.model.attn.full.core": 30e6, "fed.model.moe": 40e6,
        "fed.model.moe.route": 60e6, "fed.model.moe.experts": 2000e6,
        "fed.model.moe.shared": 100e6, "fed.model.lora": 40e6}}
    summary = {"device_kind": "TPU v5 lite",
               "moe_held_load_max_over_mean": 1.9,
               "counts": {"module": kexaone["counts"], "config": kexaone,
                          "mix": moe_lora_mix}}
    rsm = _load("reduce_scopes_swa_moe.py")
    monkeypatch.setattr(rsm._reducer, "traced", lambda: reduction)
    assert rsm.scope_ms("fed.model.attn.window") == pytest.approx(100.0)
    assert rsm.scope_ms("fed.model.attn.full") == pytest.approx(30.0)
    assert rsm.scope_ms("fed.model.moe") == pytest.approx(550.0)
    counts = _load(kexaone["counts"])
    peaks = _json("benchmark", "peaks.json")["TPU v5 lite"]
    for kernel, scope, ms in (
            ("attn_window", "fed.model.attn.window.core", 25.0),
            ("moe_held_lora", "fed.model.moe.experts", 500.0)):
        least = counts.roofline_ms_per_round(kernel, kexaone, moe_lora_mix,
                                             peaks)
        share = rsm.roofline_pct(summary, kernel, scope)
        assert share == pytest.approx(100.0 * least / ms) and 0 < share < 100
    monkeypatch.setattr(rsm._reducer, "traced", lambda: {
        "rounds": 4, "device_ns_by_scope": {"": 5e6}})
    assert rsm.scope_ms("fed.model.moe") is None    # a program with no scope
    load = _load("layer_metrics/moe_held_load_max_over_mean.py")
    assert load.read(summary) == 1.9 and load.read({}) is None


@pytest.mark.parametrize("stand_in, failing", [
    (None, set()),
    ("unchanged_state", {"attention", "dense_mlp", "shared_expert",
                         "held_experts"}),
    ("last_batch_left_out", None),
    ("reference_bits:4", None),
])
def test_the_kexaone_cell_is_correct_and_its_stand_ins_are_not(
        kexaone, moe_lora_mix, stand_in, failing):
    """``benchmark/run.py``'s own context and ``fed_adapter_moe_lm_round``
    at the rehearsal's sizes on the CPU: the round passes every limit and
    drops no token; a state left unchanged reads 1 on every kind of pair; a
    client's last batch left out and the reference with float8's 4-bit
    products each fail a limit."""
    import argparse

    run = _load("run.py")
    manifest = run.load_manifest()
    cell = run.by_name(manifest["workloads"], KEXAONE_CELL, "workload")
    # a window of a dozen toy rounds: the first two alone end at the prior
    args = argparse.Namespace(seed=3400000124, seconds=2.0, trace=0,
                              dryrun_cpu=True)
    mix = {**moe_lora_mix, "stand_in": stand_in} if stand_in else moe_lora_mix
    ctx = run.Ctx(manifest, cell, kexaone, mix, args, "cpu")
    runner = ctx.load_module(f"runners/{mix['runner']}.py")
    result = runner.run(ctx)
    summary = result["summary"]
    errors = summary["reference_errors"]
    over = {k for k, v in errors.items()
            if v > runner.TOLERANCES[k]["limit"]}
    assert result["correct"] == (stand_in is None), errors
    if failing is None:
        assert over, errors
    else:
        assert over == failing, errors
    if stand_in == "unchanged_state":
        assert all(errors[k] == pytest.approx(1.0) for k in failing)
    assert summary["base_bytes_operand"] == 2 * summary["base_parameters"]
    assert summary["moe_dropped_tokens"] == 0
    assert summary["moe_held_load_max_over_mean"] >= 1
    assert summary["experts_held"] == 4 * 4


def test_every_kexaone_limit_lies_between_its_two_readings():
    runner = _load("runners/fed_adapter_moe_lm_round.py")
    assert set(runner.TOLERANCES) == {
        "attention", "dense_mlp", "shared_expert", "held_experts", "loss"}
    for kind, t in runner.TOLERANCES.items():
        assert 2 * t["program"] <= t["limit"] <= t["control"] / 2, kind
        assert t["why"]
    # the loss is held against the fault; what the 4-bit control moves it by
    # is kept beside it and lies too near the program's reading for a limit
    loss = runner.TOLERANCES["loss"]
    assert loss["program"] < loss["precision"] < loss["limit"]
    assert [k for k, t in runner.TOLERANCES.items() if "precision" in t] == [
        "loss"]
    assert runner.kind_of(("layer_3", "attn", "lora_q_proj_a")) == "attention"
    assert runner.kind_of(("layer_0", "mlp", "lora_up_proj_b")) == "dense_mlp"
    assert runner.kind_of(("layer_2", "moe", "shared",
                           "lora_down_proj_a")) == "shared_expert"
    assert runner.kind_of(("layer_2", "moe",
                           "lora_experts_gate_b")) == "held_experts"
    with pytest.raises(KeyError):
        runner.kind_of(("layer_2", "moe", "router"))
    # a program whose adapter round carries no counters reports none
    assert runner.counters_of(type("Api", (), {"net": type(
        "Net", (), {"model_state": {}})()})()) == {}


def test_kexaone_dryrun_sizes_name_every_kind_of_layer(kexaone):
    kwargs = kexaone["dryrun"]["factory_kwargs"]
    assert kwargs["layer_types"].count("full_attention") == 1
    assert kwargs["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert kwargs["num_experts_held"] < kwargs["num_experts"]
    assert kexaone["dryrun"]["classes"] == kwargs["vocab_size"] == 257


# --- PR 36: the residual stream's own work has a scope ------------------------

@pytest.mark.parametrize("name, sites", [
    # the layer-input norm (the post-mixer norm is the expert layer's)
    ("qwen3_next_80b_a3b", 1),
    # the layer-input norm; the mixer's residual add with the post norm
    ("granite_4_0_h_micro", 2),
    # the second branch's output norm with its residual add (the first is
    # the attention's)
    ("k_exaone_236b_a23b", 1)])
def test_the_toy_forward_names_the_residual_streams_scope(name, sites):
    """``fed.model.norm`` in each LM model's lowered forward, at the dryrun
    sizes: once a site and kind of layer, and no accepted reader lists it."""
    import importlib

    config = _json("benchmark", "configs", name + ".json")
    module, _, attr = config["factory"].rpartition(".")
    model = getattr(importlib.import_module(module), attr)(
        **config["dryrun"]["factory_kwargs"])
    ids = jax.ShapeDtypeStruct((1, 32), np.int32)
    variables = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), np.zeros((1, 32), np.int32)))
    text = jax.jit(model.apply).lower(variables, ids).as_text(debug_info=True)
    named = set(re.findall(r'"([^"]*fed\.model\.norm[^"]*)"', text))
    assert named, "no op under fed.model.norm"
    layers = {re.sub(r"fed\.model\.norm.*", "", p) for p in named}
    assert len(layers) >= sites
    # the scan over stacked periods has a scope of its own (Granite only)
    assert ("fed.model.stack" in text) == (name == "granite_4_0_h_micro")
    for reducer in ("reduce_scopes.py", "reduce_scopes_hybrid.py",
                    "reduce_scopes_swa_moe.py"):
        assert not {"fed.model.norm", "fed.model.stack"} & set(
            _load(reducer).SCOPES)
    assert _load("reduce_scopes.py").scope_of(
        ["jit(f)/fed.local_train/layer_0/fed.model.norm/mul"]) == ""


# --- PR 39: Nemotron-H in the adapter round -----------------------------------

NEMOTRON, NEMOTRON_CELL = "nemotron_3_nano_30b_a3b", "nemotron3nano_lora_c4_s4k"
NEMOTRON_READERS = [
    "device_ms.ssm_groups.round", "device_ms.moe_relu2.round",
    "device_ms.attn_gqa16.round", "ssm_groups_scan_roofline_pct",
    "moe_relu2_roofline_pct", "moe_relu2_load_max_over_mean",
    "moe_relu2_fill_pct", "device_ms.unbooked_ssm_moe.round",
    "device_ms.remat_ssm_moe.round", "device_ms.lora_ssm_moe.round",
    "device_ms.head_ssm_moe.round", "adapter_upload_mb_ssm_moe.round",
    "device_ms.client_groups_ssm_moe.round"]


@pytest.fixture(scope="module")
def nemotron():
    return _json("benchmark", "configs", NEMOTRON + ".json")


@pytest.fixture(scope="module")
def ssm_moe_mix():
    return _json("benchmark", "traffic",
                 "resident_silos_c4_s4k_lora_ssm_moe.json")


def test_nemotron_holds_every_published_number_or_names_it_reduced(nemotron):
    """The catalog row's ``config`` key for key: equal, or one of the three
    cuts of scale with the published value beside it; every width as
    published, in the file and in what the factory is handed."""
    assert nemotron["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                   "vocab_size"]
    assert nemotron["published"]["hybrid_override_pattern"].startswith(
        nemotron["hybrid_override_pattern"])
    assert {k: nemotron["published"][k] for k in nemotron["reduced"]} == {
        "num_hidden_layers": 52, "n_routed_experts": 128,
        "vocab_size": 131072}
    assert {k: nemotron[k] for k in nemotron["reduced"]} == {
        "num_hidden_layers": 9, "n_routed_experts": 64, "vocab_size": 16384}
    assert nemotron["hybrid_override_pattern"] == "MEMEM*EME"
    kwargs = nemotron["factory_kwargs"]
    # the router stays 128 wide and top-6; 64 experts from 0 are held
    assert (kwargs["n_routed_experts"], kwargs["num_experts_held"],
            kwargs["first_expert_held"], kwargs["num_experts_per_tok"]) == (
                128, 64, 0, 6)
    for key, value in {"hidden_size": 2688, "mamba_num_heads": 64,
                       "mamba_head_dim": 64, "ssm_state_size": 128,
                       "n_groups": 8, "conv_kernel": 4, "chunk_size": 128,
                       "num_attention_heads": 32, "num_key_value_heads": 2,
                       "head_dim": 128, "moe_intermediate_size": 1856,
                       "moe_shared_expert_intermediate_size": 3712,
                       "routed_scaling_factor": 2.5}.items():
        assert nemotron[key] == kwargs[key] == value, key
    manifest = _json("BENCHMARK.json")
    listed, = [c for c in manifest["configs"] if c["name"] == NEMOTRON]
    assert listed["reduced"] == nemotron["reduced"]
    assert listed["source"] == nemotron["source"]
    assert set(nemotron["assumed"]) >= {"positions", "gated_norm_group",
                                        "router_bias", "weight_scale",
                                        "adapter_b_std",
                                        "fed_config.client_group_size"}
    assert "2 chips share each layer" in nemotron["deployment"]
    from fedml_tpu.algos.config import FedConfig

    assert all(hasattr(FedConfig(), k) for k in nemotron["fed_config"])
    # the accepted group-loop reader keys on fed_config: the cell's group
    # size is a key of its own, which its runner hands to the API
    assert "client_group_size" not in nemotron["fed_config"]
    assert nemotron["client_group_size_ssm_moe"] in (1, 2)
    if not os.path.exists(CATALOG):
        pytest.skip("the model-configs catalog is not on this machine")
    with open(CATALOG) as f:
        entry, = [row for row in map(json.loads, f)
                  if row["source_url"] == nemotron["source"]]
    for key, value in entry["config"].items():
        if key in nemotron["reduced"]:
            assert nemotron["published"][key] == value, key
        elif key == "hybrid_override_pattern":
            assert value[:9] == nemotron[key] == kwargs[key]
        else:
            assert nemotron[key] == value, key
            assert kwargs[key] == value, key


def test_nemotron_parameters_and_flops_recounted(nemotron, ssm_moe_mix):
    """The file's counts against the model's own trees (shapes only: no
    2.9 G parameters are made) and against ``counts/nemotron_h.py``; the
    frozen FLOPs against the derivation, part by part and by hand."""
    from fedml_tpu.models.adapter import split_frozen
    from fedml_tpu.models.nemotron_h import nemotron_h

    kwargs = nemotron["factory_kwargs"]
    model = nemotron_h(**kwargs)
    ids = jax.ShapeDtypeStruct((1, 16), np.int32)
    shapes = jax.eval_shape(
        lambda i: model.init({"params": jax.random.PRNGKey(0)}, i), ids)
    base, adapters = split_frozen(shapes["params"])

    def count(tree):
        return sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(tree))

    # ISSUE 39's count: 4 Mamba-2 blocks, the attention block, 4 expert
    # blocks of 64 held experts, embedding and head, norms and biases
    mamba = 2688 * 10304 + 4096 * 2688 + 6144 * 4 + 6144 + 3 * 64 + 4096
    attn = 2 * 2688 * 4096 + 2 * 2688 * 256
    expert = 2 * 2688 * 1856
    sparse = 64 * expert + 2 * expert + 2688 * 128 + 128
    assert (mamba, attn, expert) == (38742208, 23396352, 9977856)
    assert {"base": count(base), "adapters": count(adapters)} \
        == nemotron["parameters"] == {
            "base": (4 * mamba + attn + 4 * sparse + 2 * 16384 * 2688
                     + 10 * 2688),
            "adapters": 39620608}
    assert nemotron["parameters"]["base"] == 2902003200
    assert {str(leaf.dtype) for leaf in jax.tree.leaves(base)} == {"bfloat16"}
    assert set(shapes["counters"]) == {"layer_1", "layer_3", "layer_6",
                                       "layer_8"}
    # the held experts' matrices end on the stream's width, on the lane grid
    assert base["layer_1"]["moe"]["experts_up"].shape == (64, 1856, 2688)
    assert base["layer_1"]["moe"]["experts_down"].shape == (64, 1856, 2688)
    counts = _load(nemotron["counts"])
    assert counts.parameters(kwargs) == nemotron["parameters"]
    assert (counts.train_flops_per_sequence(nemotron, ssm_moe_mix)
            == nemotron["train_flops_per_sample"])
    per_token = counts.forward_flops_per_token(kwargs, 4096)
    assert {k: round(v) for k, v in per_token.items()} \
        == nemotron["forward_flops_per_token"]
    assert per_token["mamba_projections"] == 2 * 4 * (
        2688 * 10304 + 4096 * 2688)
    assert per_token["attn_projections"] == 2 * attn
    assert per_token["shared_experts"] == 2 * 4 * 2 * expert
    # three held assignments a token at 64 of 128, top-6
    assert counts.held_per_token(kwargs) == 3
    assert per_token["held_experts"] == 2 * 4 * 3 * expert
    assert per_token["head"] == 2 * 2688 * 16384
    assert per_token["ssm_scan"] == 4 * 64 * (5 * 64 * 128 + 64)
    assert per_token["attn_core"] == 32 * 4 * 128 * 4097 / 2
    rank = 2 * 16
    assert per_token["lora"] == rank * (
        4 * (2688 + 10304 + 4096 + 2688) + (2 * (2688 + 4096)
                                            + 2 * (2688 + 256))
        + 4 * (2 * (2688 + 3712) + 3 * 2 * (2688 + 1856)))
    frozen, low, moved, matrices = counts.held_experts_forward(kwargs, 4096)
    assert frozen == 4096 * 3 * 2 * expert and matrices == 64 * expert * 2
    assert low == 4096 * 3 * 2 * 16 * 2 * (2688 + 1856)
    assert nemotron["train_flops_per_sample"] == round(4096 * (
        2 * sum(v for k, v in per_token.items()
                if k not in ("ssm_scan", "attn_core", "lora"))
        + 3 * (per_token["ssm_scan"] + per_token["attn_core"]
               + per_token["lora"])))
    assert round(sum(per_token.values()) / 1e7) == 90      # 0.90 GFLOP
    assert counts.steps_per_round(ssm_moe_mix) == 8
    peaks = _json("benchmark", "peaks.json")["TPU v5 lite"]
    for kernel in ("ssm_groups_scan", "moe_relu2"):
        assert counts.roofline_ms_per_round(kernel, nemotron, ssm_moe_mix,
                                            peaks) > 0
    with pytest.raises(KeyError):
        counts.roofline_ms_per_round("attn_window", nemotron, ssm_moe_mix,
                                     peaks)


def test_nemotron_mix_is_the_accepted_adapter_mix_but_for_its_rate(
        ssm_moe_mix, moe_lora_mix):
    """``resident_silos_c4_s4k_lora``'s federation and round; only the runner
    and the learning rate are this cell's own."""
    for key in ("clients", "counts", "cohort", "batch", "epochs",
                "sequence_length", "zipf_exponent", "rank_offset_max",
                "doc_length", "generator", "client_optimizer", "placement",
                "trace_rounds", "round_base", "round_cycle"):
        assert ssm_moe_mix[key] == moe_lora_mix[key], key
    assert ssm_moe_mix["runner"] == "fed_adapter_ssm_moe_lm_round"
    assert os.path.exists(os.path.join(
        BENCHMARK, "runners", ssm_moe_mix["runner"] + ".py"))


@pytest.mark.parametrize("name", NEMOTRON_READERS)
def test_nemotron_reader_agrees_with_the_manifest(name, tmp_path,
                                                  monkeypatch):
    manifest = _json("BENCHMARK.json")
    entry, = [m for m in manifest["per_layer"] if m["name"] == name]
    reader = _load(f"layer_metrics/{name}.py")
    assert {k: entry[k] for k in ("layer", "unit", "moves")} == reader.META
    cells = [w["name"] for w in manifest["workloads"] if reader.applies(w)]
    assert cells == entry["workloads"] == [NEMOTRON_CELL]
    rsc = reader.rsm.rsc if hasattr(reader, "rsm") else reader.rsc
    monkeypatch.setattr(rsc.rs, "TRACE_DIR", str(tmp_path))
    assert reader.read({"chips": 1, "rounds": 3,
                        "device_kind": "TPU v5 lite"}) is None


def test_no_accepted_reader_keys_on_the_nemotron_cells_file(nemotron):
    """Every accepted reader applies to exactly the cells its list names with
    the new file in place: the file has no ``scopes``, ``scopes_swa_moe`` or
    ``scopes_unread`` and keeps ``expert_tokens`` and
    ``adapter_bytes_folded`` out of ``counters`` (its own
    ``counters_ssm_moe`` lists both); its own partition's lists are the
    reducer's."""
    manifest = _json("BENCHMARK.json")
    cell, = [w for w in manifest["workloads"] if w["name"] == NEMOTRON_CELL]
    for metric in manifest["per_layer"]:
        reader = _load(f"layer_metrics/{metric['name']}.py")
        listed = NEMOTRON_CELL in metric.get("workloads", [NEMOTRON_CELL])
        assert reader.applies(cell) == listed, metric["name"]
    assert not {"scopes", "scopes_swa_moe", "scopes_unread",
                "counters_swa_moe"} & set(nemotron)
    assert not {"expert_tokens", "adapter_bytes_folded"} & set(
        nemotron["counters"])
    assert {"expert_tokens", "grouped_rows", "adapter_bytes_folded"} <= set(
        nemotron["counters_ssm_moe"])
    assert {"fed.model.lora", "fed.model.head"} <= set(
        nemotron["scopes_ssm_moe"])
    rsm = _load("reduce_scopes_ssm_moe.py")
    assert set(nemotron["scopes_ssm_moe"]) | set(
        nemotron["scopes_ssm_moe_unread"]) == set(rsm.SCOPES) - {
            "fed.client_fold"}


def test_ssm_moe_reducer_books_mixers_norm_and_pairs_apart():
    """``reduce_scopes_ssm_moe.py``: ``reduce_scopes``' walk with this
    model's list; the copy is held to the original as the others are."""
    rsm, rsc = _load("reduce_scopes_ssm_moe.py"), _load("reduce_scopes.py")
    of = rsm._reducer.scope_of
    assert of(["jit(f)/fed.local_train/jvp(fed.model.ssm)/"
               "fed.model.ssm.scan/dot_general"]) == "fed.model.ssm.scan"
    assert of(["x/transpose(jvp(fed.model.ssm))/fed.model.lora/dot"]) \
        == "fed.model.lora"
    assert of(["x/fed.model.moe/fed.model.moe.experts/while/body/dot"]) \
        == "fed.model.moe.experts"
    assert of(["x/checkpoint/layer_5/fed.model.attn/fed.model.attn.core/"
               "pallas_call"]) == "fed.model.attn.core"
    assert of(["x/checkpoint/layer_5/fed.model.norm/mul"]) == "fed.model.norm"
    assert rsc.SCOPES[0] == "fed.model.gdn.scan"      # the other is untouched
    for scope in ("fed.model.head", "fed.client_fold",
                  "fed.model.moe.route"):
        path = f"jit(f)/fed.local_train/jvp({scope})/dot_general"
        assert of([path]) == rsc.scope_of([path]) == scope


def test_nemotron_readers_on_a_reduction(nemotron, ssm_moe_mix, monkeypatch):
    reduction = {"rounds": 4, "device_ns_by_scope": {
        "": 5e6, "fed.model.ssm": 300e6, "fed.model.ssm.conv": 20e6,
        "fed.model.ssm.scan": 480e6, "fed.model.attn": 90e6,
        "fed.model.attn.core": 30e6, "fed.model.moe": 40e6,
        "fed.model.moe.route": 60e6, "fed.model.moe.experts": 2000e6,
        "fed.model.moe.shared": 100e6, "fed.model.lora": 40e6,
        "fed.model.norm": 8e6}}
    summary = {"device_kind": "TPU v5 lite",
               "moe_relu2_load_max_over_mean": 1.9,
               "moe_relu2_fill_pct": 48.5,
               "adapter_upload_mb_round": 633.9,
               "counts": {"module": nemotron["counts"], "config": nemotron,
                          "mix": ssm_moe_mix}}
    rsm = _load("reduce_scopes_ssm_moe.py")
    monkeypatch.setattr(rsm._reducer, "traced", lambda: reduction)
    assert rsm.scope_ms("fed.model.ssm") == pytest.approx(200.0)
    assert rsm.scope_ms("fed.model.attn") == pytest.approx(30.0)
    assert rsm.scope_ms("fed.model.moe") == pytest.approx(550.0)
    # the pairs beside the dense projections are no mixer's; a scope this
    # trace does not hold reads 0 beside the others
    assert rsm.scope_ms("fed.model.lora") == pytest.approx(10.0)
    assert rsm.scope_ms("fed.model.head") == 0
    counts = _load(nemotron["counts"])
    peaks = _json("benchmark", "peaks.json")["TPU v5 lite"]
    for kernel, scope, ms in (
            ("ssm_groups_scan", "fed.model.ssm.scan", 120.0),
            ("moe_relu2", "fed.model.moe.experts", 500.0)):
        least = counts.roofline_ms_per_round(kernel, nemotron, ssm_moe_mix,
                                             peaks)
        share = rsm.roofline_pct(summary, kernel, scope)
        assert share == pytest.approx(100.0 * least / ms) and 0 < share < 100
    monkeypatch.setattr(rsm._reducer, "traced", lambda: {
        "rounds": 4, "device_ns_by_scope": {"": 5e6}})
    assert rsm.scope_ms("fed.model.moe") is None    # a program with no scope
    for name, key in (("moe_relu2_load_max_over_mean",) * 2,
                      ("moe_relu2_fill_pct",) * 2,
                      ("adapter_upload_mb_ssm_moe.round",
                       "adapter_upload_mb_round")):
        reader = _load(f"layer_metrics/{name}.py")
        assert reader.read(summary) == summary[key]
        assert reader.read({}) is None


def test_every_nemotron_limit_lies_between_its_two_readings():
    """Each limit, the loss's too, at least twice the program's largest
    reading and at most half the 4-bit control's."""
    runner = _load("runners/fed_adapter_ssm_moe_lm_round.py")
    assert set(runner.TOLERANCES) == {
        "mamba", "attention", "shared_expert", "held_experts", "loss"}
    for kind, t in runner.TOLERANCES.items():
        assert t["why"] and set(t) == {"limit", "program", "control", "why"}
        assert 2 * t["program"] <= t["limit"] <= t["control"] / 2, kind
    assert runner.kind_of(("layer_0", "mamba", "lora_in_proj_a")) == "mamba"
    assert runner.kind_of(("layer_5", "attn", "lora_q_proj_a")) == "attention"
    assert runner.kind_of(("layer_1", "moe", "shared",
                           "lora_down_proj_a")) == "shared_expert"
    assert runner.kind_of(("layer_1", "moe",
                           "lora_experts_up_b")) == "held_experts"
    with pytest.raises(KeyError):
        runner.kind_of(("layer_1", "moe", "router"))
    before = {"layer_1": {"expert_tokens": np.zeros(4),
                          "grouped_rows": np.float64(0)}}
    after = {"layer_1": {"expert_tokens": np.asarray([10., 20., 0., 18.]),
                         "grouped_rows": np.float64(96)}}
    assert runner.fill_pct(before, after) == pytest.approx(50.0)
    assert runner.fill_pct(before, before) is None


def test_a_pair_left_out_of_one_block_is_read_by_the_kinds_downstream(
        nemotron, ssm_moe_mix):
    """``fed_adapter_ssm_moe_lm_round``'s own stand-in at the rehearsal's
    sizes on the CPU: the reference's round with the first Mamba-2 block's
    ``out_proj`` pair left out of the forward, in the program's place. Not
    ``correct``: the pair's own kind over its limit (it is left as it
    started), and the kinds of the blocks after it too, which see the fault
    only through the residual stream."""
    import argparse

    run = _load("run.py")
    manifest = run.load_manifest()
    cell = run.by_name(manifest["workloads"], NEMOTRON_CELL, "workload")
    args = argparse.Namespace(seed=3900000778, seconds=1.0, trace=0,
                              dryrun_cpu=True)
    mix = {**ssm_moe_mix, "stand_in": "pair_left_out:layer_0/mamba/out_proj"}
    ctx = run.Ctx(manifest, cell, nemotron, mix, args, "cpu")
    runner = ctx.load_module(f"runners/{mix['runner']}.py")
    result = runner.run(ctx)
    summary = result["summary"]
    errors = summary["reference_errors"]
    over = {k for k, v in errors.items()
            if v > runner.TOLERANCES[k]["limit"]}
    assert not result["correct"] and summary["stand_in"] == mix["stand_in"]
    assert "mamba" in over and over & {"held_experts", "shared_expert",
                                       "attention"}, errors
    assert summary["moe_dropped_tokens"] == 0
    assert summary["adapter_upload_mb_round"] > 0


def test_nemotron_dryrun_sizes_name_every_kind_of_block(nemotron):
    kwargs = nemotron["dryrun"]["factory_kwargs"]
    assert kwargs["hybrid_override_pattern"] == "MEMEM*EME"
    assert kwargs["num_hidden_layers"] == 9 and kwargs["hidden_size"] == 64
    assert kwargs["n_groups"] > 1
    assert kwargs["num_attention_heads"] * kwargs["head_dim"] \
        != kwargs["hidden_size"]
    assert kwargs["moe_intermediate_size"] % 8      # off every grid
    assert kwargs["num_experts_held"] < kwargs["n_routed_experts"]
    assert nemotron["dryrun"]["classes"] == kwargs["vocab_size"] == 257
