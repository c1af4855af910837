"""From a profiler trace to numbers: the device's busy union, per-op times,
collective time, and the idle gaps labelled by the benchmark's host spans.

``events_of`` reads a ``.xplane.pb`` (``jax.profiler.ProfileData``) into plain
events - ``[plane, line, name, start_ns, duration_ns]`` - keeping what the
reduction needs (the devices' ``XLA Ops``, the collectives' asynchronous
spans, the benchmark's host spans); ``reduce`` works on that list alone, so it is checked
against a recorded fixture (selfcheck.py) with no profiler and no device.
"""

from __future__ import annotations

import gzip
import json
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"   # where an asynchronous collective's span lies
HOST_SPAN_PREFIX = "bench."
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
TOP = 10
NAME_CHARS = 96
_LAYOUT = re.compile(r"\{[^{}]*\}")


def short_name(hlo: str) -> str:
    """An op's trace name is its whole HLO line: keep the name, the result
    shapes without their layouts and the opcode, up to NAME_CHARS."""
    return _LAYOUT.sub("", hlo).lstrip("%")[:NAME_CHARS]


def events_of(xplane_path: str) -> list:
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(xplane_path).planes:
        device = plane.name.startswith(DEVICE_PREFIX)
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, ASYNC_LINE):
                continue
            for ev in line.events:
                if device:
                    name = short_name(ev.name)
                    keep = line.name == OPS_LINE or is_collective(name)
                else:
                    name = ev.name
                    keep = name.startswith(HOST_SPAN_PREFIX)
                if keep:
                    out.append([plane.name, line.name, name,
                                int(ev.start_ns), int(ev.duration_ns)])
    return out


def is_collective(name: str) -> bool:
    return any(c in name.split(" = ")[0] for c in COLLECTIVES)


def save_events(events: list, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(events, f, separators=(",", ":"))


def load_events(path: str) -> list:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def union(intervals: list) -> list:
    """Sorted, disjoint ``[start, end]`` covering the same points."""
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def covered(intervals: list) -> int:
    return sum(b - a for a, b in union(intervals))


def self_times(ops: list) -> dict:
    """Seconds by op name, each op's time less that of the ops nested in it
    (a ``while`` spans its body's ops on the same line), so the parts add up
    to the whole."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    inner = [0] * len(ops)
    stack = []
    for i in order:
        _, s, e = ops[i]
        while stack and ops[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            inner[stack[-1]] += e - s
        stack.append(i)
    out = {}
    for (n, s, e), child in zip(ops, inner):
        out[n] = out.get(n, 0) + (e - s - child)
    return out


def _label(a: int, b: int, spans: list) -> str:
    """The host span that covers most of ``[a, b]``; what lies between the
    benchmark's spans is its own loop: ``bench.between``."""
    best, best_ns = HOST_SPAN_PREFIX + "between", 0
    for name, s, e in spans:
        ns = min(b, e) - max(a, s)
        if ns > best_ns:
            best, best_ns = name, ns
    return best


def reduce(events: list, rounds: int, chips: int):
    """``None`` where the trace holds no device operation or no host span
    (a CPU rehearsal). The window runs from the first host span's start to
    the last one's end; busy is the union of ``XLA Ops`` intervals on a
    device, averaged over the ``chips`` devices with most work."""
    spans = sorted((n, s, s + d) for p, _, n, s, d in events
                   if not p.startswith(DEVICE_PREFIX))
    per_device, collective = {}, {}
    for p, line, n, s, d in events:
        if p.startswith(DEVICE_PREFIX):
            if line == OPS_LINE:
                per_device.setdefault(p, []).append((n, s, s + d))
            if is_collective(n):
                collective.setdefault(p, []).append([s, s + d])
    if not spans or not per_device:
        return None
    lo = min(s for _, s, _ in spans)
    hi = max(e for _, _, e in spans)
    busy = {p: covered([[s, e] for _, s, e in evs])
            for p, evs in per_device.items()}
    used = sorted(busy, key=lambda p: (-busy[p], p))[:chips]
    first = min(used)   # the device whose ops and gaps are broken down
    ops = self_times(per_device[first])
    merged = union([[s, e] for _, s, e in per_device[first]])
    edges = [lo] + [t for ab in merged for t in ab] + [hi]
    longest = []
    for a, b in zip(edges[0::2], edges[1::2]):
        if b > a:
            longest.append((b - a, _label(a, b, spans)))
    by_label = {}
    for ns, name in longest:
        by_label[name] = by_label.get(name, 0) + ns
    return {
        "rounds": rounds,
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy[p] for p in used) / len(used) / 1e9,
        "busy_s_first": busy[first] / 1e9,
        "collective_s_first": covered(collective.get(first, [])) / 1e9,
        "device_events": sum(len(per_device[p]) for p in used),
        "device_ops": [[n, ns / 1e9] for n, ns in sorted(
            ops.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[name, ns / 1e9]
                      for ns, name in sorted(longest, reverse=True)[:TOP]],
        "idle_s_by_span": {n: ns / 1e9 for n, ns in sorted(by_label.items())},
    }
