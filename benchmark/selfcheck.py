#!/usr/bin/env python3
"""CPU rehearsal of the benchmark's own arithmetic, run by hand:

    JAX_PLATFORMS=cpu python3 benchmark/selfcheck.py

It checks the manifest against the files it names, the percentile and rate
arithmetic on a fixed list, the trace reducer on the recorded fixture, the
generator's promise that a seed never changes the amount of work, and
reference.py against a two-client round computed by hand. It touches no
device metric and is no part of tests/.
"""

from __future__ import annotations

import json
import os
import re
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import run as bench  # noqa: E402  (benchmark/run.py)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def check(cond, msg):
    if not cond:
        raise AssertionError(msg)


def check_manifest():
    m = bench.load_manifest()
    cells = {w["name"]: w for w in m["workloads"]}
    e2e = {e["name"]: e for e in m["end_to_end"]}
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in m[group]]
        check(len(set(names)) == len(names), f"{group}: a name twice")
        for n in names:
            check(NAME.match(n), f"{group}: illegal name {n!r}")
    check("setup_s" in e2e, "no setup_s")
    for e in m["end_to_end"] + m["per_layer"]:
        check(UNIT.match(e["unit"]), f"{e['name']}: illegal unit")
        check(e["better"] in ("lower", "higher"), e["name"])
        check(e["source"] in SOURCES, e["name"])
        for w in e.get("workloads", []):
            check(w in cells, f"{e['name']}: unknown workload {w}")
    for e in m["end_to_end"]:
        check(e["source"] in ("host_clock", "device_trace"), e["name"])
        check(0 < e["bound"] <= 0.1, f"{e['name']}: bound {e['bound']}")
    used = set()
    for w in m["workloads"]:
        entry = bench.by_name(m["configs"], w["config"], "configuration")
        used.add(entry["name"])
        with open(os.path.join(bench.ROOT, entry["file"])) as f:
            config = json.load(f)
        check(config["reduced"] == entry["reduced"], f"{entry['name']}: "
              "reduced differs between the manifest and the file")
        check(config["source"] == entry["source"], entry["name"])
        with open(bench.find(m, f"traffic/{w['traffic']}.json")) as f:
            mix = json.load(f)
        bench.find(m, f"runners/{mix['runner']}.py")
        bench.find(m, f"generators/{mix['generator']}.py")
        check(w["chips"] in (1, 4) and len(w["why"]) <= 200, w["name"])
        check(len(bench.metrics_of(m, "end_to_end", w["name"])) >= 2
              and bench.metrics_of(m, "per_layer", w["name"]), w["name"])
    check(used == {c["name"] for c in m["configs"]}, "a configuration "
          "no cell uses")
    four = sum(w["chips"] == 4 for w in m["workloads"])
    check(four <= max(1, len(cells) // 4), f"{four} four-chip cells")
    for p in m["per_layer"]:
        reader = bench.load_module(
            bench.find(m, f"layer_metrics/{p['name']}.py"))
        for key in ("layer", "unit", "moves"):
            check(reader.META[key] == p[key], f"{p['name']}: {key} differs "
                  "between the manifest and the reader")
        applies = sorted(n for n, w in cells.items() if reader.applies(w))
        listed = sorted(p.get("workloads", cells))
        check(applies == listed, f"{p['name']}: applies to {applies}, the "
              f"manifest lists {listed}")
        moved = e2e.get(p["moves"])
        check(moved is not None, f"{p['name']} moves no end-to-end metric")
        for n in listed:   # the moved metric is reported wherever this is
            check(n in moved.get("workloads", cells), f"{p['name']}: "
                  f"{p['moves']} is not reported in {n}")
    return f"{len(cells)} cells, {len(m['per_layer'])} per-layer metrics"


def check_arithmetic():
    m = bench.load_manifest()
    runner = bench.load_module(bench.find(m, "runners/fed_round.py"))
    times = [0.1 * k for k in range(1, 21)]          # 0.1 .. 2.0
    check(abs(runner.percentile(times, 50) - 1.0) < 1e-12, "p50")
    check(abs(runner.percentile(times, 90) - 1.8) < 1e-12, "p90")
    check(abs(runner.percentile(times, 95) - 1.9) < 1e-12, "p95")
    check(runner.percentile([3.0], 90) == 3.0, "one value")
    step_fill = bench.load_module(
        bench.find(m, "layer_metrics/step_fill_pct.py"))
    check(step_fill.read({"real_samples": 448, "padded_slots": 1600}) == 28.0,
          "step_fill_pct")
    mfu = bench.load_module(bench.find(m, "layer_metrics/mfu.client_step.py"))
    got = mfu.read({"device_kind": "TPU v5 lite", "real_samples": 197000,
                    "window_s": 10.0, "chips": 2,
                    "train_flops_per_sample": 1e9})
    check(abs(got - 5.0) < 1e-9, f"mfu {got}")       # 9.85e12 / 197e12
    try:
        mfu.read({"device_kind": "TPU v9", "real_samples": 1, "window_s": 1.0,
                  "chips": 1, "train_flops_per_sample": 1.0})
    except KeyError:
        pass
    else:
        raise AssertionError("an unknown device kind must raise")
    return "percentiles, step fill, mfu"


def check_reducer():
    m = bench.load_manifest()
    rt = bench.load_module(bench.find(m, "reduce_trace.py"))
    check(rt.union([[5, 7], [0, 2], [1, 3], [7, 8]]) == [[0, 3], [5, 8]],
          "union")
    # One device, two ops, a gap inside a host span and one between spans.
    toy = [["/host:CPU", "t", "bench.round", 0, 100],
           ["/host:CPU", "t", "bench.fence", 100, 50],
           ["/device:TPU:0", "XLA Ops", "fusion.1", 10, 40],
           ["/device:TPU:0", "XLA Ops", "all-reduce-start.2 = f32[8]", 60, 70],
           ["/device:TPU:0", "Async XLA Ops", "all-reduce-start.2 = f32[8]",
            60, 85]]
    got = rt.reduce(toy, rounds=1, chips=1)
    check(got["window_s"] == 150e-9 and got["busy_s"] == 110e-9, got)
    check(got["collective_s_first"] == 85e-9, got)   # the async span counts
    check(got["device_ops"][0] == ["all-reduce-start.2 = f32[8]", 70e-9], got)
    check(got["idle_gaps"] == [["bench.fence", 20e-9], ["bench.round", 10e-9],
                               ["bench.round", 10e-9]], got)
    check(rt.reduce(toy[:2], 1, 1) is None, "no device events")
    fixture = bench.find(m, "fixtures/resnet56_rounds.events.json.gz")
    with open(bench.find(m, "fixtures/resnet56_rounds.expected.json")) as f:
        want = json.load(f)
    got = rt.reduce(rt.load_events(fixture), **want["arguments"])
    for key, value in want["result"].items():
        have = got[key][:len(value)] if isinstance(value, list) else got[key]
        check(json.loads(json.dumps(have)) == value,
              f"{key}: {have} != {value}")
    for name, value in want["derived"].items():   # through the readers
        reader = bench.load_module(bench.find(m, f"layer_metrics/{name}.py"))
        have = reader.read({"trace": got, "chips": 1})
        check(abs(have - value) <= 1e-9 * abs(value),
              f"{name}: {have} != {value}")
    return f"toy trace and fixture ({got['device_events']} device events)"


def check_generator():
    m = bench.load_manifest()
    gen = bench.load_module(bench.find(m, "generators/class_templates.py"))
    mix = {"clients": 50, "template_strength": 1.0, "template_grid": 2,
           "counts": {"law": "lognormal", "mu": 2.0, "sigma": 0.7, "min": 1,
                      "seed": 0}}
    config = {"input_shape": [4, 4, 1], "classes": 5}
    a = gen.generate(mix, config, 2 ** 31 + 11)
    b = gen.generate(mix, config, 2 ** 31 + 11)
    c = gen.generate(mix, config, 7)
    check(all(np.array_equal(u, v) for u, v in zip(a[:2], b[:2])),
          "the same seed gave other data")
    check(np.array_equal(a[3], c[3]) and not np.array_equal(a[1], c[1]),
          "another seed must give the same client sizes and other samples")
    check(sum(len(p) for p in a[2].values()) == len(a[0]) == a[3].sum(),
          "parts do not cover the samples")
    return "same seed same data; every seed the same client sizes"


def check_reference():
    """Two clients, full participation, one round, against the closed form
    in float64: g_W = X^T (softmax(XW + b) - onehot(y)) / n."""
    m = bench.load_manifest()
    ref = bench.load_module(bench.find(m, "reference.py"))
    rng = np.random.default_rng(0)
    d, k, lr = 6, 3, 0.5
    w0, b0 = rng.normal(size=(d, k)), rng.normal(size=k)
    data = {0: (rng.normal(size=(4, d)), np.array([0, 1, 2, 1])),
            1: (rng.normal(size=(7, d)), np.array([2, 2, 0, 1, 0, 0, 1]))}
    acc_w, acc_b = 0.0, 0.0
    for x, y in data.values():
        z = x @ w0 + b0
        p = np.exp(z - z.max(1, keepdims=True))
        p /= p.sum(1, keepdims=True)
        p[np.arange(len(y)), y] -= 1.0
        acc_w = acc_w + len(y) * (w0 - lr * x.T @ p / len(y))
        acc_b = acc_b + len(y) * (b0 - lr * p.sum(0) / len(y))
    want_w, want_b = acc_w / 11, acc_b / 11
    got_w, got_b = ref.fedavg_rounds(
        w0, b0, lambda c: (data[c][0].astype(np.float32),
                           data[c][1].astype(np.int32)), 2, 2, [0], lr)
    err = max(np.abs(got_w - want_w).max(), np.abs(got_b - want_b).max())
    check(err < 1e-5, f"reference.py is {err} from the closed form")
    check(list(ref.sample_cohort(3, 10, 4)) == list(
        np.random.RandomState(3).choice(10, 4, replace=False)), "sampler")
    return f"max error {err:.1e} against the closed form"


def main() -> int:
    for fn in (check_manifest, check_arithmetic, check_reducer,
               check_generator, check_reference):
        print(f"{fn.__name__}: ok - {fn()}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
