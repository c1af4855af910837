"""Device self time by the model's own scopes, from the traced rounds.

The program names its layers with ``jax.named_scope`` inside the round's
``fed.local_train`` / ``fed.aggregate`` phases: ``fed.model.gdn`` (and
``.scan`` around the delta rule alone), ``fed.model.attn`` (``.core``),
``fed.model.moe`` (``.route``, ``.experts``, ``.shared``), ``fed.model.head``,
``fed.client_fold``. reduce_spans.py books an operation under the last of its
three PHASES and leaves these alone; this file books the same operations
under the innermost of SCOPES in their ``op_name`` path, forward, backward and
rematerialised alike (a transformed scope keeps its name:
``transpose(jvp(fed.model.gdn))``). It is built on reduce_spans' wire reader
(the ``op_name`` path is a stat of the event METADATA, which
``jax.profiler.ProfileData`` does not show) and on its window: the first
``bench.*`` span's start to the last one's end, first device, self time.

A program without the scopes (the parent of the PR that added them, or a
cell whose model has none) gives an empty table and every reader ``None``.
``roofline_pct`` puts a kernel's counted work (``counts/<module>.py``: from
the configuration and the mix alone) over its scope's measured time.
"""

from __future__ import annotations

import glob
import importlib.util
import json
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)
import reduce_spans as rs  # noqa: E402  (benchmark/reduce_spans.py)
import reduce_trace as rt  # noqa: E402  (benchmark/reduce_trace.py)

SCOPES = ("fed.model.gdn.scan", "fed.model.gdn", "fed.model.attn.core",
          "fed.model.attn", "fed.model.moe.route", "fed.model.moe.experts",
          "fed.model.moe.shared", "fed.model.moe", "fed.model.head",
          "fed.client_fold")
# longest first, so that ``fed.model.gdn.scan`` is not read as its parent
_SCOPE = re.compile("|".join(
    re.escape(s) for s in sorted(SCOPES, key=len, reverse=True)))


def scope_of(texts) -> str:
    """The innermost scope named in an operation's ``op_name`` path."""
    for text in texts:
        found = _SCOPE.findall(text)
        if found:
            return found[-1]
    return ""


def metadata_scopes(xplane_path: str, plane_name: str) -> dict:
    """``{event name: scope}`` from the stats of a plane's event metadata;
    reduce_spans.metadata_phases' walk with another reading of the texts."""
    with open(xplane_path, "rb") as f:
        space = memoryview(f.read())
    for number, plane in rs._fields(space):
        if number != 1:
            continue
        name, events, interned = "", [], {}
        for number, value in rs._fields(plane):
            if number == 2:
                name = bytes(value).decode()
            elif number == 4:
                events.append(dict(rs._fields(value))[2])
            elif number == 5:
                entry = dict(rs._fields(value))
                interned[entry[1]] = entry[2]
        if name != plane_name:
            continue
        names = {k: bytes(dict(rs._fields(v)).get(2, b"")).decode()
                 for k, v in interned.items()}
        out = {}
        for metadata in events:
            event_name, texts = "", []
            for number, value in rs._fields(metadata):
                if number == 2:
                    event_name = bytes(value).decode()
                elif number == 5:
                    for n, v in rs._fields(value):
                        if n == 5:
                            texts.append(bytes(v).decode(errors="replace"))
                        elif n == 7:
                            texts.append(names.get(v, ""))
            scope = scope_of(texts)
            if scope:
                out[event_name] = scope
        return out
    return {}


def events_of(xplane_path: str) -> list:
    """``[kind, name, start_ns, duration_ns, scope]``: ``"host"`` for the
    ``bench.*`` spans and ``fed.round``, ``"op"`` for an ``XLA Ops`` event
    of the first device."""
    from jax.profiler import ProfileData

    planes = list(ProfileData.from_file(xplane_path).planes)
    out = []
    for plane in planes:
        if plane.name != rs.HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(rs.WINDOW_PREFIX) or ev.name == rs.ROUND:
                    out.append(["host", ev.name, int(ev.start_ns),
                                int(ev.duration_ns), ""])
    devices = sorted(
        (p for p in planes if p.name.startswith(rt.DEVICE_PREFIX)
         and any(ln.name == rt.OPS_LINE for ln in p.lines)),
        key=lambda p: p.name)
    for plane in devices[:1]:
        scopes = metadata_scopes(xplane_path, plane.name)
        for line in plane.lines:
            if line.name == rt.OPS_LINE:
                for ev in line.events:
                    out.append(["op", rt.short_name(ev.name),
                                int(ev.start_ns), int(ev.duration_ns),
                                scopes.get(ev.name, "")])
    return out


def reduce(events: list):
    """``{"rounds", "device_ns_by_scope", "top_ops"}`` over the window of
    the ``bench.*`` spans, or ``None`` where there is none. Self time: an
    operation's time less its direct children's (a ``while`` and its
    body's operations are both events of the line)."""
    bench = [e for e in events
             if e[0] == "host" and e[1].startswith(rs.WINDOW_PREFIX)]
    if not bench:
        return None
    lo = min(e[2] for e in bench)
    hi = max(e[2] + e[3] for e in bench)
    rounds = sum(1 for e in events if e[0] == "host" and e[1] == rs.ROUND
                 and lo <= e[2] < hi)
    ops = []
    for e in events:
        if e[0] == "op":
            s, t = max(lo, e[2]), min(hi, e[2] + e[3])
            if t > s:
                ops.append((s, t, e[1], e[4]))
    by_scope, by_op = {}, {}
    if ops:
        _, inner = rs.nested([(s, t) for s, t, _, _ in ops])
        for (s, t, name, scope), child in zip(ops, inner):
            by_scope[scope] = by_scope.get(scope, 0) + t - s - child
            if scope:
                key = (scope, name)
                by_op[key] = by_op.get(key, 0) + t - s - child
    top = sorted(by_op.items(), key=lambda kv: -kv[1])[:12]
    return {"rounds": rounds,
            "device_ns_by_scope": dict(sorted(by_scope.items())),
            "top_ops": [[scope, name, ns] for (scope, name), ns in top]}


def table(r: dict) -> str:
    n = max(1, r["rounds"])
    out = [f"device self time by scope, {r['rounds']} traced rounds, per "
           "round (ms):"]
    for scope, ns in r["device_ns_by_scope"].items():
        out.append(f"  {scope or '(no model scope)':<26}{ns / n / 1e6:10.3f}")
    for scope, name, ns in r["top_ops"]:
        out.append(f"    {ns / n / 1e6:10.3f}  {scope:<22} {name[:60]}")
    return "\n".join(out)


_REDUCED = {}


def traced():
    """The reduction of the newest trace this process wrote, or ``None``
    (reduce_spans.traced's rule: never a trace older than the process)."""
    started = rs._process_start()
    found = [p for p in glob.glob(
        os.path.join(rs.TRACE_DIR, "**", "*.xplane.pb"), recursive=True)
        if os.path.getmtime(p) >= started]
    if not found:
        return None
    path = max(found, key=os.path.getmtime)
    key = (os.path.abspath(path), os.path.getmtime(path))
    if key not in _REDUCED:
        _REDUCED[key] = reduce(events_of(path))
        if _REDUCED[key] is not None:
            print(table(_REDUCED[key]), file=sys.stderr, flush=True)
    return _REDUCED[key]


def scope_ms(prefix: str):
    """Milliseconds a traced round of device self time under the scopes
    that start with ``prefix``; ``None`` where the program names no model
    scope at all (or nothing was traced), 0 where it names others."""
    r = traced()
    if not r or not r["rounds"]:
        return None
    named = {s: ns for s, ns in r["device_ns_by_scope"].items() if s}
    if not named:
        return None
    return sum(ns for s, ns in named.items()
               if s.startswith(prefix)) / r["rounds"] / 1e6


def _load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _config_of(cell: dict) -> dict:
    with open(os.path.join(HERE, os.pardir, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry, = [c for c in manifest["configs"] if c["name"] == cell["config"]]
    with open(os.path.join(HERE, os.pardir, entry["file"])) as f:
        return json.load(f)


def lists_scope(cell: dict, scope: str) -> bool:
    """Whether the cell's configuration file lists ``scope`` among the
    device scopes its model names (``scopes``)."""
    return scope in _config_of(cell).get("scopes", [])


def lists_counter(cell: dict, counter: str) -> bool:
    return counter in _config_of(cell).get("counters", [])


def roofline_pct(summary: dict, kernel: str, scope: str):
    """The least time ``kernel``'s counted work could take on this chip
    (``peaks.json``) over its scope's device time, in per cent; ``None``
    without a trace, the scope, or the runner's note of what to count."""
    measured = scope_ms(scope)
    note = summary.get("counts")
    if not measured or not note:
        return None
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)
    kind = summary["device_kind"]
    if kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    counts = _load(os.path.join(HERE, note["module"]), "bench_counts")
    least = counts.roofline_ms_per_round(kernel, note["config"], note["mix"],
                                         peaks[kind])
    return 100.0 * least / measured
