"""From a profiler trace to the round's own spans: what the host was doing
while the device idled, and which phase of the round the device's time
belongs to.

The program marks its round with host spans (``fedml_tpu.obs.trace.span``:
``fed.round``, ``fed.cohort.wait``, ``fed.store.put``, ...) and its device
operations with ``jax.named_scope`` phases (``fed.gather``,
``fed.local_train``, ``fed.aggregate``); both land in the ``.xplane.pb`` the
runner's traced rounds leave under ``benchmark_out/trace``. ``events_of``
reads that file into plain events; ``reduce`` works on that list alone, so it
is checked on a hand-made list and on a recorded fixture with no profiler
(tests/test_round_spans.py). ``traced`` is what the readers under
``layer_metrics/`` call: the reduction of this process's own trace, or
``None``. A program without the spans (the parent of the PR that added them)
gives a reduction whose span tables are empty, and the readers leave their
metric out. A scope reaches the trace only in a freshly compiled program's
metadata: ``fedml_tpu.utils.use_compile_cache`` keys the cache on it.

Events: ``[kind, line, name, start_ns, duration_ns, extra]``. ``kind`` is
``"host"`` (``extra`` the span's stats, ``line`` the position of its thread's
line in the host plane), ``"op"`` (an ``XLA Ops`` event of the first device,
named as reduce_trace.py names it; ``extra`` its phase or ``""``) or
``"module"`` (its ``XLA Modules`` line).
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)
import reduce_trace as rt  # noqa: E402  (benchmark/reduce_trace.py)

TRACE_DIR = os.path.join(HERE, os.pardir, "benchmark_out", "trace")
HOST_PLANE = "/host:CPU"
MODULES_LINE = "XLA Modules"
SPAN_PREFIXES = ("fed.", "bench.")
WINDOW_PREFIX = "bench."
ROUND, WAIT, SYNC = "fed.round", "fed.cohort.wait", "fed.round.loss_fetch"
EAGER_GATHER = "fed.round.gather"
GATHER, PUT = "fed.store.gather", "fed.store.put"
PHASES = ("fed.gather", "fed.local_train", "fed.aggregate")
#: what lies between the benchmark's own spans is its loop
BETWEEN = "bench.between"
NO_WORKER = "-"
TOP = 5
_PHASE = re.compile("|".join(re.escape(p) for p in PHASES))
save_events, load_events = rt.save_events, rt.load_events


def phase_of(texts) -> str:
    """The innermost phase named in an operation's ``op_name`` path (any of
    the strings a trace event carries), or ``""``."""
    for text in texts:
        found = _PHASE.findall(text)
        if found:
            return found[-1]
    return ""


def _varint(buf, i: int):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one protobuf message: an int for a
    varint, a memoryview for a length-delimited or fixed-width field."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        else:
            if wire == 2:
                size, i = _varint(buf, i)
            else:
                size = {1: 8, 5: 4}[wire]
            value, i = buf[i:i + size], i + size
        yield key >> 3, value


def metadata_phases(xplane_path: str, plane_name: str) -> dict:
    """``{event name: phase}`` from the stats of a plane's *event metadata*.
    An ``XLA Ops`` event's ``op_name`` path (stat ``tf_op``) is a stat of its
    metadata, shared by all runs of the operation, and
    ``jax.profiler.ProfileData`` shows an event's own stats only; so the
    file's ``event_metadata`` and ``stat_metadata`` maps are read here, from
    the protobuf's wire format (tsl/profiler/protobuf/xplane.proto: XSpace.
    planes = 1; XPlane.name = 2, .event_metadata = 4, .stat_metadata = 5;
    XEventMetadata.name = 2, .stats = 5; XStatMetadata.name = 2;
    XStat.str_value = 5, .ref_value = 7, a reference to a stat metadata
    whose name is the string). The lines are skipped, not parsed."""
    with open(xplane_path, "rb") as f:
        space = memoryview(f.read())
    for number, plane in _fields(space):
        if number != 1:
            continue
        name, events, interned = "", [], {}
        for number, value in _fields(plane):
            if number == 2:
                name = bytes(value).decode()
            elif number == 4:
                events.append(dict(_fields(value))[2])  # map entry: value 2
            elif number == 5:
                entry = dict(_fields(value))            # key 1, value 2
                interned[entry[1]] = entry[2]
        if name != plane_name:
            continue
        names = {k: bytes(dict(_fields(v)).get(2, b"")).decode()
                 for k, v in interned.items()}
        out = {}
        for metadata in events:
            event_name, texts = "", []
            for number, value in _fields(metadata):
                if number == 2:
                    event_name = bytes(value).decode()
                elif number == 5:
                    for n, v in _fields(value):
                        if n == 5:
                            texts.append(bytes(v).decode(errors="replace"))
                        elif n == 7:
                            texts.append(names.get(v, ""))
            phase = phase_of(texts)
            if phase:
                out[event_name] = phase
        return out
    return {}


def events_of(xplane_path: str) -> list:
    from jax.profiler import ProfileData

    planes = list(ProfileData.from_file(xplane_path).planes)
    out = []
    for plane in planes:
        if plane.name != HOST_PLANE:
            continue
        for position, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIXES):
                    stats = {k: v for k, v in ev.stats
                             if isinstance(v, (int, float, str))}
                    out.append(["host", position, ev.name, int(ev.start_ns),
                                int(ev.duration_ns), stats])
    devices = sorted(
        (p for p in planes if p.name.startswith(rt.DEVICE_PREFIX)
         and any(ln.name == rt.OPS_LINE for ln in p.lines)),
        key=lambda p: p.name)
    for plane in devices[:1]:
        phases = metadata_phases(xplane_path, plane.name)
        for line in plane.lines:
            if line.name == rt.OPS_LINE:
                for ev in line.events:
                    out.append(["op", 0, rt.short_name(ev.name),
                                int(ev.start_ns), int(ev.duration_ns),
                                phases.get(ev.name, "")])
            elif line.name == MODULES_LINE:
                for ev in line.events:
                    out.append(["module", 0, ev.name, int(ev.start_ns),
                                int(ev.duration_ns), ""])
    return out


def nested(intervals: list) -> tuple:
    """For ``(start, end)`` intervals of one trace line, which nest or are
    disjoint: ``(parent, inner)``, the index of each one's innermost
    enclosing interval (``-1`` for none) and the time its direct children
    take, so that ``end - start - inner`` is its self time."""
    order = sorted(range(len(intervals)),
                   key=lambda i: (intervals[i][0], -intervals[i][1]))
    parent, inner, stack = [-1] * len(intervals), [0] * len(intervals), []
    for i in order:
        s, e = intervals[i]
        while stack and intervals[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            parent[i] = stack[-1]
            inner[stack[-1]] += e - s
        stack.append(i)
    return parent, inner


def innermost(spans: list) -> list:
    """Disjoint ``(start, end, name)`` pieces, sorted: over each piece
    ``name`` is the span that started last among those open there (the
    innermost, where spans nest). ``spans`` are ``(name, start, end)``."""
    points = sorted({t for _, s, e in spans for t in (s, e)})
    opened = sorted(spans, key=lambda x: x[1])
    pieces, live, k = [], [], 0
    for a, b in zip(points, points[1:]):
        while k < len(opened) and opened[k][1] <= a:
            live.append(opened[k])
            k += 1
        live = [x for x in live if x[2] > a]
        if live:
            name = max(live, key=lambda x: (x[1], -x[2]))[0]
            if pieces and pieces[-1][2] == name and pieces[-1][1] == a:
                pieces[-1][1] = b
            else:
                pieces.append([a, b, name])
    return pieces


def _cut(a: int, b: int, pieces: list, starts: list, default: str):
    """``[a, b]`` cut at the pieces' edges: ``(name, start, end)`` parts that
    add up to it, ``default`` where no piece lies."""
    k = max(0, bisect.bisect_right(starts, a) - 1)
    at = a
    while at < b:
        if k < len(pieces) and pieces[k][1] <= at:
            k += 1
            continue
        if k < len(pieces) and pieces[k][0] <= at:
            end, name = min(b, pieces[k][1]), pieces[k][2]
        else:
            end = min(b, pieces[k][0]) if k < len(pieces) else b
            name = default
        yield name, at, end
        at = end


def eager_gather(ops: list, modules: list) -> list:
    """``ops`` with the eager ``gather_clients`` of the mesh / aux branch
    booked under ``fed.gather``. A jitted function called eagerly starts a
    name stack of its own (``jit(_take)/gather``), so those operations carry
    no scope; they are known by their programs instead: an operation with no
    phase, in a program (``modules``: sorted ``(start, end, name)``) none of
    whose operations has one. Called only where the trace holds a
    ``fed.round.gather`` span; the round's two rng programs (microseconds)
    are booked with it."""
    starts = [m[0] for m in modules]

    def module_of(s):
        k = bisect.bisect_right(starts, s) - 1
        return modules[k][2] if k >= 0 and s < modules[k][1] else None

    scoped = {module_of(s) for s, _, _, phase in ops if phase} | {None}
    return [(s, t, name,
             phase or ("" if module_of(s) in scoped else PHASES[0]))
            for s, t, name, phase in ops]


def reduce(events: list):
    """``None`` where the trace holds no ``bench.*`` span. The window runs
    from the first ``bench.*`` span's start to the last one's end, as
    reduce_trace.reduce's; every span and operation is clipped to it. Times
    are nanoseconds over the whole window; ``rounds`` is what the readers
    divide by: the ``fed.round`` spans of the main thread, which is the
    line that holds the ``bench.*`` spans; every other host line is a
    worker. Idle time of the device is booked under the path of the
    innermost main-thread span over it (``idle_ns_by_span``)."""
    host = [e for e in events if e[0] == "host"]
    bench = [e for e in host if e[2].startswith(WINDOW_PREFIX)]
    if not bench:
        return None
    lo = min(e[3] for e in bench)
    hi = max(e[3] + e[4] for e in bench)
    main = bench[0][1]

    def clip(s, d):
        return max(lo, s), min(hi, s + d)

    # Host spans: count, total, self, bytes, by name and by thread. On the
    # main thread each span also gets its path ("bench.round/fed.round/
    # fed.cohort.wait"), which is what idle time is booked under.
    spans, on_main, by_line, main_paths = {}, {}, {}, []
    for e in host:
        s, t = clip(e[3], e[4])
        if t > s:
            by_line.setdefault(e[1], []).append((e[2], s, t, e[5]))
    misses = waits = rounds = 0
    for line, rows in by_line.items():
        parent, inner = nested([(s, t) for _, s, t, _ in rows])
        for i, (name, s, t, stats) in enumerate(rows):
            row = spans.setdefault(name, {"count": 0, "total_ns": 0,
                                          "self_ns": 0, "bytes": 0})
            row["count"] += 1
            row["total_ns"] += t - s
            row["self_ns"] += t - s - inner[i]
            row["bytes"] += int(stats.get("bytes", 0))
            if line != main:
                continue
            on_main[name] = on_main.get(name, 0) + t - s
            path, up = [name], parent[i]
            while up >= 0:
                path.append(rows[up][0])
                up = parent[up]
            main_paths.append(("/".join(reversed(path)), s, t))
            rounds += name == ROUND
            waits += name == WAIT
            misses += name == GATHER and WAIT in path
    # The first device: busy union, idle partition, self time by phase.
    ops = []
    for e in events:
        if e[0] == "op":
            s, t = clip(e[3], e[4])
            if t > s:
                ops.append((s, t, e[2], e[5]))
    if EAGER_GATHER in spans:
        ops = eager_gather(ops, sorted(
            (e[3], e[3] + e[4], e[2]) for e in events if e[0] == "module"))
    merged = rt.union([[s, t] for s, t, _, _ in ops])
    busy = sum(t - s for s, t in merged)
    idle_by_span, idle_by_pair = {}, {}
    by_phase, unscoped = {}, {}
    if ops:
        main_pieces = innermost(main_paths)
        main_starts = [p[0] for p in main_pieces]
        worker_pieces = innermost([r[:3] for line, rows in by_line.items()
                                   if line != main for r in rows])
        worker_starts = [p[0] for p in worker_pieces]
        edges = [lo] + [x for st in merged for x in st] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            for name, s, t in _cut(a, b, main_pieces, main_starts, BETWEEN):
                idle_by_span[name] = idle_by_span.get(name, 0) + t - s
                for worker, u, v in _cut(s, t, worker_pieces, worker_starts,
                                         NO_WORKER):
                    pair = idle_by_pair.setdefault(name, {})
                    pair[worker] = pair.get(worker, 0) + v - u
        _, inner = nested([(s, t) for s, t, _, _ in ops])
        for (s, t, name, phase), child in zip(ops, inner):
            by_phase[phase] = by_phase.get(phase, 0) + t - s - child
            if not phase:
                unscoped[name] = unscoped.get(name, 0) + t - s - child
    modules = {}
    for e in events:
        if e[0] == "module":
            s, t = clip(e[3], e[4])
            if t > s:
                row = modules.setdefault(e[2], [0, 0])
                row[0] += 1
                row[1] += t - s
    return {
        "rounds": rounds, "window_ns": hi - lo,
        "device": bool(ops), "busy_ns": busy,
        "idle_ns": (hi - lo - busy) if ops else None,
        "spans": dict(sorted(spans.items())),
        "main_ns": dict(sorted(on_main.items())),
        "waits": waits, "misses": misses,
        "idle_ns_by_span": dict(sorted(idle_by_span.items())),
        "idle_ns_by_span_and_worker": idle_by_pair,
        "device_ns_by_phase": dict(sorted(by_phase.items())),
        "unscoped_ops": sorted(unscoped.items(), key=lambda kv: -kv[1])[:TOP],
        "modules": dict(sorted(modules.items(), key=lambda kv: -kv[1][1])),
    }


def table(r: dict) -> str:
    """The whole reduction, for a person: per round, in milliseconds."""
    n = max(1, r["rounds"])

    def ms(ns):
        return f"{ns / n / 1e6:10.3f}"

    out = [f"spans of {r['rounds']} traced rounds, per round (ms); window "
           f"{r['window_ns'] / 1e6:.1f} ms, device busy "
           f"{r['busy_ns'] / 1e6:.1f} ms",
           f"  {'span':<24}{'count':>6}{'total':>10}{'self':>10}"
           f"{'on main':>10}{'bytes':>14}"]
    for name, row in r["spans"].items():
        out.append(f"  {name:<24}{row['count']:>6}{ms(row['total_ns'])}"
                   f"{ms(row['self_ns'])}{ms(r['main_ns'].get(name, 0))}"
                   f"{row['bytes'] // n:>14}")
    out.append(f"  waits {r['waits']}, of them misses {r['misses']}")
    if r["device"]:
        out.append("  device idle under the main thread's span (and the "
                   "worker's span over it):")
        for name, ns in sorted(r["idle_ns_by_span"].items(),
                               key=lambda kv: -kv[1]):
            under = ", ".join(
                f"{w} {v / n / 1e6:.3f}" for w, v in sorted(
                    r["idle_ns_by_span_and_worker"][name].items(),
                    key=lambda kv: -kv[1]))
            out.append(f"  {name:<24}{ms(ns)}   ({under})")
        out.append("  device self time by phase:")
        for phase, ns in r["device_ns_by_phase"].items():
            out.append(f"  {phase or '(no phase)':<24}{ms(ns)}")
        for name, ns in r["unscoped_ops"]:
            out.append(f"    no phase: {ms(ns)}  {name}")
        out.append("  programs on the device (runs, ms a round):")
        for name, (count, ns) in list(r["modules"].items())[:TOP]:
            out.append(f"  {name[:40]:<40}{count:>6}{ms(ns)}")
    return "\n".join(out)


def _process_start() -> float:
    """When this process started, on the clock of file times (Linux). Where
    it cannot be told, now: every trace then counts as too old."""
    import time

    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/stat") as f:
            boot = next(int(ln.split()[1]) for ln in f
                        if ln.startswith("btime"))
        return boot + ticks / os.sysconf("SC_CLK_TCK") - 1.0
    except (OSError, ValueError, IndexError, StopIteration):
        return time.time()


_REDUCED = {}


def traced():
    """The reduction of the newest trace under ``TRACE_DIR`` that this
    process wrote (never one older than the process), or ``None``. Reduced
    once a file; the table goes to stderr then."""
    started = _process_start()
    found = [p for p in glob.glob(os.path.join(TRACE_DIR, "**", "*.xplane.pb"),
                                  recursive=True)
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


def phase_ns(r: dict, phase: str):
    """Device self time under ``phase``; ``None`` where the program names
    no phase at all, 0 where it names others."""
    if not any(p in r["device_ns_by_phase"] for p in PHASES):
        return None
    return r["device_ns_by_phase"].get(phase, 0)


def idle_ns(r: dict, inside=(), outside=()):
    """Idle time of the device under main-thread spans whose path holds
    every span of ``inside`` and none of ``outside``; ``None`` where the
    trace holds no device operation."""
    if not r["device"]:
        return None
    return sum(ns for path, ns in r["idle_ns_by_span"].items()
               if all(x in path.split("/") for x in inside)
               and not any(x in path.split("/") for x in outside))


def per_round(ns):
    """Milliseconds a traced round, for the readers: ``ns`` is a function of
    the reduction that gives nanoseconds over the window, or ``None``."""
    r = traced()
    if not r or not r["rounds"]:
        return None
    value = ns(r)
    return None if value is None else value / r["rounds"] / 1e6


def host_store(cell: dict) -> bool:
    """Whether the cell's mix streams its cohorts from a host store."""
    with open(os.path.join(HERE, "traffic", cell["traffic"] + ".json")) as f:
        return json.load(f)["placement"] == "host_store"
