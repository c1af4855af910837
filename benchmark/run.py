#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is data: ``BENCHMARK.json`` names its configuration
(a file of sizes), its traffic mix (``traffic/<mix>.json``, which names the
runner and the generator) and its chips; each per-layer metric is a reader
of its own under ``layer_metrics/``. A new cell, mix, configuration, runner
or metric is new files and one entry; nothing here names any of them.

Without a TPU, or with fewer chips than the cell asks for, this exits 2 and
prints no result. ``--dryrun-cpu`` is a named rehearsal, never a fallback:
the mix's and configuration's ``dryrun`` sizes on CPU devices, reported as
``"platform": "cpu", "dryrun": true``.

The last line of stdout is the result, one JSON object:
``correct, attempted, failed, metrics, device`` (and ``breakdown`` with
``--trace 1``); diagnostics go to stderr.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()   # set-up is counted from here

import argparse
import importlib.util
import json
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_manifest(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_module(path: str):
    name = "bench_" + re.sub(r"\W", "_", os.path.relpath(path, ROOT))
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


def find(manifest: dict, relative: str, root: str = ROOT) -> str:
    """A benchmark file by its path under any of the manifest's ``paths``."""
    for base in manifest["paths"]:
        path = os.path.join(root, base, relative)
        if os.path.exists(path):
            return path
    raise FileNotFoundError(
        f"{relative} under none of {manifest['paths']}")


def by_name(entries: list, name: str, what: str) -> dict:
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise KeyError(f"no {what} named {name!r}; known: "
                   f"{[e['name'] for e in entries]}")


def metrics_of(manifest: dict, group: str, workload: str) -> list:
    """The metrics of ``end_to_end`` or ``per_layer`` this cell reports."""
    return [m for m in manifest[group]
            if workload in m.get("workloads", [workload])]


def memory_peak_bytes(stats: dict) -> int:
    """Peak HBM use from ``device.memory_stats()``. The TPU runtime does not
    allocate a program's temporaries, it reserves them: a ResNet-56 round
    whose compiled program needs 6.7 GB read 0.55 GB of ``peak_bytes_in_use``
    and 6.69 GB of ``peak_bytes_reserved``, and libtpu's own
    ``hbm_capacity_usage`` is the sum of the two (PERF.md, PR 24). The two
    peaks need not coincide, so the sum can overstate by what was allocated
    only outside the rounds."""
    return int(stats.get("peak_bytes_in_use", 0)
               + stats.get("peak_bytes_reserved", 0))


class Ctx:
    """What a runner gets: the cell's three entries, the arguments, the
    clock's origin, and how to reach the benchmark's other files."""

    def __init__(self, manifest, cell, config, mix, args, platform):
        self.manifest, self.cell = manifest, cell
        self.config, self.mix = config, mix
        self.seed, self.seconds = args.seed, args.seconds
        self.trace, self.dryrun = bool(args.trace), args.dryrun_cpu
        self.platform = platform
        self.t0 = _T0
        self.out_dir = os.path.join(ROOT, "benchmark_out")

    def load_module(self, relative: str):
        return load_module(find(self.manifest, relative))

    def log(self, msg: str) -> None:
        print(f"[bench +{time.perf_counter() - _T0:.1f}s] {msg}",
              file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dryrun-cpu", action="store_true",
                    help="rehearse on the CPU at the files' dryrun sizes "
                         "(never a chip result)")
    args = ap.parse_args(argv)
    manifest = load_manifest()
    cell = by_name(manifest["workloads"], args.workload, "workload")
    entry = by_name(manifest["configs"], cell["config"], "configuration")
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    with open(find(manifest, f"traffic/{cell['traffic']}.json")) as f:
        mix = json.load(f)
    if args.seconds is None:
        args.seconds = float(manifest["run_seconds"])
    chips = int(cell["chips"])

    if args.dryrun_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={chips}").strip()
    if not os.path.isdir(os.path.join(ROOT, "fedml_tpu")):
        print(f"benchmark: no system under test (fedml_tpu/) in {ROOT}; "
              "nothing was run", file=sys.stderr)
        return 2
    sys.path.insert(0, ROOT)    # the system under test is this checkout's
    import jax

    expected = "cpu" if args.dryrun_cpu else "tpu"
    if jax.default_backend() != expected or len(jax.devices()) < chips:
        print(f"benchmark: {args.workload} needs {chips} {expected} "
              f"device(s); JAX found {jax.default_backend()!r} "
              f"{jax.devices()}; nothing was run", file=sys.stderr)
        return 2

    cache_dir = None
    if not args.dryrun_cpu:     # a rehearsal leaves nothing in the cache
        from fedml_tpu.utils import use_compile_cache

        cache_dir = use_compile_cache()
        # JAX leaves programs that compile in under a second out of its
        # cache; most of a model's set-up programs are such: keep them all.
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    ctx = Ctx(manifest, cell, config, mix, args, expected)
    ctx.log(f"{args.workload}: seed {args.seed}, {args.seconds}s, trace "
            f"{args.trace}; {jax.devices()[0].device_kind} x"
            f"{len(jax.devices())}; compile cache {cache_dir}")
    runner = ctx.load_module(f"runners/{mix['runner']}.py")
    result = runner.run(ctx)

    summary = result["summary"]
    devices = jax.devices()[:chips]
    peaks = [memory_peak_bytes(d.memory_stats() or {}) for d in devices]
    summary["memory_peak_bytes"] = max(peaks)
    ctx.log(f"memory stats of {devices[0]}: {devices[0].memory_stats()}")
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": max(peaks)}
    line = {"correct": bool(result["correct"]),
            "attempted": result["attempted"], "failed": result["failed"]}
    if args.dryrun_cpu:
        line["dryrun"] = True
    metrics = {}
    if args.trace:
        for m in metrics_of(manifest, "per_layer", args.workload):
            reader = ctx.load_module(f"layer_metrics/{m['name']}.py")
            try:
                value = reader.read(summary)
            except KeyError:    # no peaks for a CPU: only a rehearsal may
                if not args.dryrun_cpu:
                    raise
                value = None
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        traced = summary.get("trace")
        if traced:
            device["busy_s"] = traced["busy_s"]
            device["window_s"] = traced["window_s"]
            line["breakdown"] = {"device_ops": traced["device_ops"],
                                 "idle_gaps": traced["idle_gaps"]}
    else:
        for m in metrics_of(manifest, "end_to_end", args.workload):
            value = result["end_to_end"].get(m["name"])
            if value is None:
                raise RuntimeError(
                    f"the runner gave no {m['name']} for {args.workload}")
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    line["metrics"] = metrics
    line["device"] = device
    ctx.log("end to end: " + json.dumps(result["end_to_end"]))
    ctx.log("summary: " + json.dumps(summary))
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
