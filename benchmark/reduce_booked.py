"""Device self time by BOOKING, by loop container and by pass, and the
integer arguments of the dispatch spans, from the traced rounds.

reduce_spans.py books an operation under the last of three phases and
reduce_scopes.py (and its two copies) under the innermost of one model's
scopes; each leaves the rest under "no phase" / "no model scope", and the
rest was 100-210 ms a round in the three LM cells (PERF.md, PR 36). This
file reads the same ``op_name`` paths with one rule for every cell and no
list of scopes:

- an operation is a CONTAINER (``while``, ``conditional``, ``call``: an event
  of the ``XLA Ops`` line that spans its body's operations) or a leaf. A
  container's self time is the space between its body's operations: busy time
  by ``device_ms.round``'s rule, in which nothing runs;
- every operation's self time goes to the INNERMOST (last) ``fed.*`` name of
  its path, so ``fed.step.update`` or ``fed.client_groups`` is read without a
  reader that lists it;
- a leaf is *booked* if that innermost name is anything but a bare
  ``fed.local_train`` (a phase or a scope that says what the operation is
  for), else *unbooked*: the operations a ``perf_opt`` issue cannot be
  written against. The innermost and not "any name of the path": the group
  loop's ``fed.client_groups`` encloses its whole body, and would book all of
  it;
- by pass: a path that holds ``rematted_computation`` is the forward computed
  again by ``jax.checkpoint`` / ``nn.remat``; one that holds ``transpose(`` and
  not that is the backward; the rest the forward. (On jax 0.9.0 the
  recomputed forward lies INSIDE ``transpose(jvp(...))/checkpoint/``: the
  strings are pinned on a toy program by tests/test_round_spans.py.)

Booked + unbooked + containers, and forward + recomputed + backward, each add
up to the union of the device's operations (``device_ms.round``). The
``fed.round.dispatch`` spans of the main thread carry what the program
dispatched as integer arguments (``slots``, ``samples``: ``obs.trace.span``'s
arguments come back as the event's stats); their sums over the window are
read here too.

Built on reduce_spans' wire reader, window and ``nested``, and on
reduce_scopes' ``events_of`` (a private copy of that module whose ``scope_of``
keeps an operation's whole path instead of one scope of a list). A trace
without device operations (a CPU rehearsal) gives every device reader
``None``.
"""

from __future__ import annotations

import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)
import reduce_scopes as rsc  # noqa: E402  (benchmark/reduce_scopes.py)
import reduce_spans as rs  # noqa: E402  (benchmark/reduce_spans.py)

CONTAINERS = ("while", "conditional", "call")
DISPATCH = "fed.round.dispatch"
BARE = "fed.local_train"
FORWARD, REMAT, BACKWARD = "forward", "recomputed", "backward"
REMAT_MARK, BACKWARD_MARK = "rematted_computation", "transpose("
TOP = 16
_NAME = re.compile(r"fed\.[a-z_]+(?:\.[a-z_]+)*")
_STEM = re.compile(r"[A-Za-z_\-]+")


def path_of(texts) -> str:
    """An operation's ``op_name`` path among the strings its metadata
    carries (``jit(step_fn)/...``; the others are source lines), or ``""``
    for an operation XLA made and gave no metadata."""
    return next((t for t in texts if t.startswith("jit(")), "")


# A private copy of reduce_scopes.py (as its two re-readings load one) that
# keeps an operation's path where that file keeps a scope, and whose
# ``traced`` (the newest trace this process wrote, reduced once, its table
# to stderr) reduces by this file's rule: see the end of the file.
_paths = rsc._load(os.path.join(HERE, "reduce_scopes.py"),
                   "bench_reduce_scopes_paths")
_paths.scope_of = path_of
_op_events = _paths.events_of


def names_of(path: str) -> list:
    """The ``fed.*`` names of a path, outermost first; a transformed scope
    keeps its name (``transpose(jvp(fed.model.gdn))``)."""
    return _NAME.findall(path)


def is_container(name: str) -> bool:
    """By the instruction's name (``while.12 = (...) while(...)``): its
    result shape can be longer than what reduce_trace keeps of the line."""
    stem = _STEM.match(name)
    return bool(stem) and stem.group(0).rstrip("-_") in CONTAINERS


def kind_of(name: str, path: str) -> str:
    """``<instruction stem>:<the primitive its path ends in>``: a fusion is
    known by its root (``add_select_fusion:select_n``)."""
    stem = _STEM.match(name)
    return ((stem.group(0) if stem else "?") + ":"
            + (path.rsplit("/", 1)[-1].rstrip(":") if path else "-"))


def name_class(path: str) -> str:
    """Why a leaf is unbooked: ``empty`` (XLA made it and gave it no
    ``op_name``: prefetches, layout copies, expanded sorts and scatters),
    ``loop`` (its path ends in a loop's own ``while``: the copies of a carry
    and the prefetches XLA names after the loop they serve), ``transforms``
    (a path, no ``fed.*`` name in it) or ``bare`` (inside
    ``fed.local_train`` and no scope that says what it is)."""
    if not path:
        return "empty"
    if path.rstrip(":").endswith("/while"):
        return "loop"
    return "bare" if names_of(path) else "transforms"


def pass_of(path: str) -> str:
    if REMAT_MARK in path:
        return REMAT
    return BACKWARD if BACKWARD_MARK in path else FORWARD


def events_of(xplane_path: str) -> list:
    """reduce_scopes' events with an operation's whole path in the place of
    its scope, and the main thread's dispatch spans as ``["span", name,
    start_ns, duration_ns, stats]``."""
    out = _op_events(xplane_path)
    host = [e for e in rs.events_of(xplane_path) if e[0] == "host"]
    main = next((e[1] for e in host if e[2].startswith(rs.WINDOW_PREFIX)),
                None)
    out += [["span", e[2], e[3], e[4], e[5]] for e in host
            if e[1] == main and e[2] == DISPATCH]
    return out


def reduce(events: list):
    """``None`` where the trace holds no ``bench.*`` span. Nanoseconds over
    the window of the ``bench.*`` spans, first device, self time (an
    operation's time less its direct children's)."""
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
    booked = unbooked = 0
    containers, innermost, passes, named = {}, {}, {}, set()
    why, left = {}, {}
    if ops:
        _, inner = rs.nested([(s, t) for s, t, _, _ in ops])
        for (s, t, name, path), child in zip(ops, inner):
            ns = t - s - child
            found = names_of(path)
            named.update(found)
            last = found[-1] if found else ""
            innermost[last] = innermost.get(last, 0) + ns
            phase = pass_of(path)
            passes[phase] = passes.get(phase, 0) + ns
            if is_container(name):
                containers[last] = containers.get(last, 0) + ns
            elif last and last != BARE:
                booked += ns
            else:
                unbooked += ns
                cls = name_class(path)
                why[cls] = why.get(cls, 0) + ns
                key = (cls, kind_of(name, path))
                row = left.setdefault(key, [0, 0, name])
                row[0] += ns
                row[1] += 1
    args = {}
    for e in events:
        if e[0] == "span" and lo <= e[2] < hi:
            args["spans"] = args.get("spans", 0) + 1
            for k, v in e[4].items():
                if isinstance(v, int) and not isinstance(v, bool):
                    args[k] = args.get(k, 0) + v
    return {
        "rounds": rounds, "device": bool(ops),
        "booked_ns": booked, "unbooked_ns": unbooked,
        "container_ns": dict(sorted(containers.items())),
        "innermost_ns": dict(sorted(innermost.items())),
        "named": sorted(named),
        "pass_ns": dict(sorted(passes.items())),
        "unbooked_ns_by_class": dict(sorted(why.items())),
        "unbooked_ops": [[cls, kind, ns, count, name] for (cls, kind),
                         (ns, count, name) in sorted(
                             left.items(), key=lambda kv: -kv[1][0])[:TOP]],
        "dispatch_args": args,
    }


def table(r: dict) -> str:
    n = max(1, r["rounds"])

    def ms(ns):
        return f"{ns / n / 1e6:10.3f}"

    loops = sum(r["container_ns"].values())
    out = [f"device self time by booking, {r['rounds']} traced rounds, per "
           "round (ms):",
           f"  {'booked leaves':<28}{ms(r['booked_ns'])}",
           f"  {'unbooked leaves':<28}{ms(r['unbooked_ns'])}   ("
           + ", ".join(f"{c} {v / n / 1e6:.3f}" for c, v in
                       r["unbooked_ns_by_class"].items()) + ")",
           f"  {'containers (loops) self':<28}{ms(loops)}"]
    for scope, ns in r["container_ns"].items():
        out.append(f"    {scope or '(no name)':<26}{ms(ns)}")
    out.append("  by pass:")
    for phase, ns in r["pass_ns"].items():
        out.append(f"  {phase:<28}{ms(ns)}")
    out.append("  by the innermost fed.* name:")
    for scope, ns in r["innermost_ns"].items():
        out.append(f"  {scope or '(no name)':<28}{ms(ns)}")
    out.append("  unbooked leaves, by why and kind (ms a round, ops a round, "
               "one of them):")
    for cls, kind, ns, count, name in r["unbooked_ops"]:
        out.append(f"    {ms(ns)} {count / n:8.1f}  {cls:<10} {kind:<40} "
                   f"{name[:50]}")
    if r["dispatch_args"]:
        out.append(f"  {DISPATCH} arguments, summed: {r['dispatch_args']}")
    return "\n".join(out)


_paths.events_of, _paths.reduce, _paths.table = events_of, reduce, table
traced = _paths.traced


def per_round(ns):
    """Milliseconds a traced round of device time, for the readers: ``ns``
    is a function of the reduction that gives nanoseconds over the window,
    or ``None``. ``None`` too without a trace, a round or a device."""
    r = traced()
    if not r or not r["rounds"] or not r["device"]:
        return None
    value = ns(r)
    return None if value is None else value / r["rounds"] / 1e6


def innermost_ms(name: str, family: str = ""):
    """Device self time a traced round of the operations (leaves and
    containers) whose innermost ``fed.*`` name is ``name``. ``None`` where no
    path of the trace holds a name that starts with ``family`` (``name``
    itself unless given: ``fed.step.`` for ``fed.step.update``): a program
    from before the scope. 0 where the program names it and XLA rooted no
    operation there (an update fused into the convolution that makes its
    gradient keeps the convolution's name)."""
    def ns(r):
        if not any(n.startswith(family or name) for n in r["named"]):
            return None
        return r["innermost_ns"].get(name, 0)

    return per_round(ns)


def client_groups(cell: dict) -> bool:
    """Whether the cell trains its cohort ``client_group_size`` clients at a
    time inside one program (``parallel.shard.fold_client_groups``)."""
    fed = rsc._config_of(cell).get("fed_config", {})
    return bool(fed.get("client_group_size"))


def names_model_scopes(cell: dict) -> bool:
    """Whether the cell's configuration file lists device scopes of its
    model (read by a metric or not): the three LM cells, whose models also
    compute every layer again in the backward pass (``nn.remat``)."""
    config = rsc._config_of(cell)
    return any(config.get(key) for key in (
        "scopes", "scopes_swa_moe", "scopes_unread"))
