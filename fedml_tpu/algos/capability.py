"""Carry capability records — the algorithm zoo's ONE declaration of how
(whether) each algorithm rides the multi-round execution tiers.

The windowed carry protocol (PR 3) already defines the shape every
stateful server update must take to scan: ``window_protocol`` plus the
``_window_*`` hooks ``(carry_init, server_update, carry_commit)`` and the
optional per-round ``_window_scan_extras``. What used to sit NEXT to that
protocol was a pile of per-class ``type(self)`` identity guards — each
tier hand-rolled its own exclusion list, the EXECUTION.md support matrix
was maintained by hand, and a newly converted algorithm had to win an
argument with three different guards before it ran fast.

This module derives ONE record per algorithm class from its declarations
(:func:`record_for`) and makes everything downstream consume it:

- the tier entry points (``train_rounds_windowed`` / ``_on_device``
  and the fused round step) key their guards on the record and refuse
  with :func:`refusal` — a message derived from the record, naming the
  reason the class declared;
- the EXECUTION.md algorithm × tier support matrix is GENERATED from the
  records (:func:`render_matrix`, ``scripts/gen_support_matrix.py``) and
  drift-tested, so the docs cannot silently diverge from the guards;
- an algorithm opts in by declaring the protocol hooks (FedOpt's pure
  optax fold, SCAFFOLD/FedDyn's ``_build_fused_step``), and opts out by
  declaring ``window_protocol = None`` with a ``window_exclusion``
  reason — never by being added to an identity list.

Class-level declaration surface (all optional beyond ``window_protocol``):

``capability_name``
    Display name for the matrix (default: the class name).
``window_carry``
    Human description of the scan carry (matrix column), e.g.
    ``"server optimizer state"``; default ``"—"`` (no carry).
``window_exclusion``
    Why the algorithm sits out every scan tier. Required (by the drift
    test) when ``window_protocol`` is None; woven into every refusal.
``capability_tiers``
    Explicit tier dict for classes OUTSIDE the FedAvg family whose
    entry points are their own (DecentralizedAPI's on-device gossip
    scan). FedAvg-family records are derived structurally and must not
    set this.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import lru_cache
from typing import Optional

#: (display name, module under fedml_tpu.algos, class name) — the zoo the
#: generated support matrix covers, in matrix row order. The simulator
#: tiers only: the message-passing servers (cross-silo, FedAsync,
#: FedBuff) are a different execution plane with their own matrix
#: (docs/EXECUTION.md "Wire formats × codecs × backends").
ZOO = (
    ("FedAvg", "fedavg", "FedAvgAPI"),
    ("FedProx", "fedprox", "FedProxAPI"),
    ("FedOpt", "fedopt", "FedOptAPI"),
    ("FedAc", "fedac", "FedAcAPI"),
    ("ServerAvg", "fedac", "ServerAvgAPI"),
    ("q-FedAvg", "qfedavg", "QFedAvgAPI"),
    ("FedNova", "fednova", "FedNovaAPI"),
    ("FedAvgRobust", "robust", "FedAvgRobustAPI"),
    ("SCAFFOLD", "scaffold", "ScaffoldAPI"),
    ("FedDyn", "feddyn", "FedDynAPI"),
    ("Ditto", "ditto", "DittoAPI"),
    ("FedAdapter", "fedadapter", "FedAdapterAPI"),
    ("FedBN", "fedbn", "FedBNAPI"),
    ("FedGAN", "fedgan", "FedGanAPI"),
    ("FedNAS", "fednas", "FedNASAPI"),
    ("FedSeg", "fedseg", "FedSegAPI"),
    ("TurboAggregate", "turboaggregate", "TurboAggregateAPI"),
    ("HierarchicalFL", "hierarchical", "HierarchicalFedAvgAPI"),
    ("Decentralized", "decentralized", "DecentralizedAPI"),
    ("FedGKT", "fedgkt", "FedGKTAPI"),
    ("SplitNN", "split_nn", "SplitNNAPI"),
    ("VerticalFL", "vertical_fl", "VflAPI"),
)


@dataclass(frozen=True)
class CarryCapability:
    """One algorithm's declared + structurally derived capability record.

    ``fused``/``windowed``/``on_device`` are the STATIC
    tier eligibilities (what the class can ever do); runtime conditions
    — a resident layout where windowed needs a store, oort selection,
    a subsampled mesh for the on-device scan — still gate per call."""

    algorithm: str
    protocol: Optional[str]       # "round" | "custom" | None
    carry: str                    # matrix annotation of the scan carry
    excluded: Optional[str]       # declared reason when sitting out
    custom_round: bool            # per-round procedure != run_round + _server_update
    custom_builders: bool         # round_fn not from the shared vmap/sharded builders
    custom_step: bool             # provides its own _build_fused_step
    pure_server_update: bool      # a pure windowed server_update exists
    round_aux: bool               # per-round host-computed aux operands
    streaming: bool               # supports FederatedStore cohorts
    fused: bool
    windowed: bool
    on_device: bool


def _fedavg_family_record(cls, name, carry, excluded) -> CarryCapability:
    from fedml_tpu.algos.fedavg import FedAvgAPI
    from fedml_tpu.algos.loop import FederatedLoop

    proto = cls.window_protocol
    custom_round = (cls.train_one_round is not FedAvgAPI.train_one_round
                    or cls.run_round is not FederatedLoop.run_round)
    custom_builders = (
        cls._make_vmap_round is not FedAvgAPI._make_vmap_round
        or cls._make_sharded_round is not FedAvgAPI._make_sharded_round)
    custom_step = cls._build_fused_step is not FedAvgAPI._build_fused_step
    # Pure windowed server update: either nothing to fold (plain
    # ``net' = avg``) or the class provides the pure hook alongside its
    # host-side override.
    pure = (cls._server_update is FedAvgAPI._server_update
            or cls._window_server_update is not FedAvgAPI._window_server_update)
    aux = (cls._round_aux is not FederatedLoop._round_aux
           or cls._window_scan_extras is not FedAvgAPI._window_scan_extras)
    streaming = bool(cls.supports_streaming)
    fused = windowed = on_device = False
    if proto == "round":
        fused = not custom_round and pure
        windowed = fused and streaming
        # The on-device scan threads the same pure carry between rounds
        # but samples (or keeps full participation) INSIDE the jit — a
        # host-computed per-round aux operand has no slot there.
        on_device = fused and not aux
    elif proto == "custom":
        has_scan = (custom_step or cls._build_window_scan
                    is not FedAvgAPI._build_window_scan)
        fused = custom_step
        windowed = has_scan and streaming
    return CarryCapability(
        algorithm=name, protocol=proto, carry=carry, excluded=excluded,
        custom_round=custom_round, custom_builders=custom_builders,
        custom_step=custom_step, pure_server_update=pure, round_aux=aux,
        streaming=streaming, fused=fused, windowed=windowed,
        on_device=on_device)


@lru_cache(maxsize=None)
def record_for(cls) -> CarryCapability:
    """The capability record for an algorithm CLASS (cached per class).

    FedAvg-family classes are derived structurally from the carry
    protocol's hooks; standalone classes (their own training loops)
    declare ``capability_tiers`` explicitly or default to host-loop
    only with their ``window_exclusion`` reason."""
    from fedml_tpu.algos.fedavg import FedAvgAPI

    name = getattr(cls, "capability_name", cls.__name__)
    carry = getattr(cls, "window_carry", "—")
    excluded = getattr(cls, "window_exclusion", None)
    if isinstance(cls, type) and issubclass(cls, FedAvgAPI):
        return _fedavg_family_record(cls, name, carry, excluded)
    tiers = getattr(cls, "capability_tiers", {})
    proto = getattr(cls, "window_protocol", None)
    if proto is None and excluded is None:
        excluded = ("no windowed carry capability record declared "
                    "(window_protocol=None and no window_exclusion)")
    return CarryCapability(
        algorithm=name, protocol=proto, carry=carry, excluded=excluded,
        custom_round=True, custom_builders=True,
        custom_step=bool(tiers.get("fused")),
        pure_server_update=False, round_aux=False,
        streaming=bool(getattr(cls, "supports_streaming", False)),
        fused=bool(tiers.get("fused", False)),
        windowed=bool(tiers.get("windowed", False)),
        on_device=bool(tiers.get("on_device", False)))


def refusal(cls, tier: str) -> str:
    """The record-derived refusal message for ``cls`` on ``tier`` —
    every scan-tier guard raises with THIS, so the reason a class
    declared (or the structural fact that disqualifies it) reaches the
    user verbatim instead of a hand-rolled per-guard paraphrase."""
    rec = record_for(cls)
    name = cls.__name__
    if (tier == "train_rounds_windowed" and not rec.windowed
            and rec.excluded and rec.protocol is not None):
        # A class that rides other tiers but declares WHY the windowed
        # store tier does not apply (DecentralizedAPI's gossip).
        return (f"{name} opts out of the windowed tier: {rec.excluded}")
    if (tier == "train_rounds_windowed" and not rec.streaming
            and (rec.fused or rec.custom_step)):
        # The class rides the scan tiers but keeps client data
        # device-resident — the windowed tier is a STORE tier.
        return (f"{name} declares supports_streaming=False; "
                f"{tier} streams window superbatches from a "
                "FederatedStore — use the resident on-device scan or "
                "the per-round host loop")
    if rec.protocol is None:
        why = rec.excluded or "no reason declared"
        return (f"{name} opts out of the windowed carry protocol "
                f"(window_protocol=None): {why} — use the per-round "
                "host loop")
    if rec.protocol == "round":
        if rec.custom_round:
            return (f"{name} customizes the round itself; {tier} only "
                    "serves algorithms whose per-round procedure is "
                    "run_round + _server_update (declare the 'custom' "
                    "windowed carry protocol with a _build_fused_step "
                    "for a bespoke one-dispatch round)")
        if not rec.pure_server_update:
            return (f"{name} overrides _server_update without providing "
                    f"its pure windowed form; {tier} needs the pure "
                    "carry record — override _window_server_update (and "
                    "the carry init/commit hooks) or set "
                    "window_protocol = None")
        if tier == "train_rounds_on_device" and rec.round_aux:
            return (f"{name} feeds its round per-round host-computed aux "
                    "operands (_round_aux/_window_scan_extras), which "
                    "the on-device scan — sampling inside the jit — has "
                    "no slot for; use the windowed streaming scan or "
                    "the host loop")
        return (f"{name} does not ride {tier} "
                f"(capability record: {rec})")
    # protocol == "custom"
    if not rec.custom_step and tier != "train_rounds_windowed":
        return (f"{name} declares window_protocol='custom' but does not "
                f"provide _build_fused_step; {tier} replays the fused "
                "one-dispatch round, which only the step hook defines")
    if tier == "train_rounds_on_device":
        return (f"{name} carries client-stacked state through a custom "
                "scan body; the on-device scan serves 'round'-protocol "
                "algorithms — use the windowed streaming scan")
    return (f"{name} declares window_protocol='custom' but provides "
            "neither _build_fused_step nor _build_window_scan; the "
            "custom carry protocol needs the scan body (plus the carry "
            "init/commit hooks)")


class ExcludedScanTiers:
    """The scan-tier entry points as record-derived refusals — the ONE
    implementation behind both ``FederatedLoop`` (so every loop-family
    algorithm that doesn't override them fails with its declared reason)
    and the standalone training loops outside it (FedGKT's alternating
    distillation, SplitNN's relay ring, vertical FL), instead of an
    AttributeError that says nothing. FedAvgAPI overrides both with the
    real tiers."""

    #: Carry capability declarations (see module docstring): subclasses
    #: publish explicit tiers (``capability_tiers``) or declare WHY they
    #: sit the scan tiers out (``window_exclusion``).
    window_protocol = None
    window_exclusion = None

    def train_rounds_windowed(self, *a, **k):
        raise NotImplementedError(refusal(type(self),
                                          "train_rounds_windowed"))

    def train_rounds_on_device(self, *a, **k):
        raise NotImplementedError(refusal(type(self),
                                          "train_rounds_on_device"))


def zoo_records():
    """``[(display_name, cls, CarryCapability)]`` for the whole zoo, in
    matrix order. Imports lazily — this walks every algorithm module."""
    import importlib

    out = []
    for name, module, clsname in ZOO:
        mod = importlib.import_module(f"fedml_tpu.algos.{module}")
        cls = getattr(mod, clsname)
        out.append((name, cls, record_for(cls)))
    return out


def _cell(flag: bool) -> str:
    return "✓" if flag else "✗"


def render_matrix() -> str:
    """The EXECUTION.md algorithm × tier support matrix, generated from
    the capability records (drift-tested by tests/test_zoo_windowed.py;
    regenerate with ``python scripts/gen_support_matrix.py --write``).
    Every ✓ is backed by the record the tier guards consume — the table
    CANNOT say yes where the guard says no."""
    lines = [
        "| algorithm | protocol | carry | fused round | "
        "windowed scan | on-device scan |",
        "|---|---|---|---|---|---|",
    ]
    excluded = []
    for name, cls, rec in zoo_records():
        proto = rec.protocol if rec.protocol else "—"
        lines.append(
            f"| {name} | {proto} | {rec.carry} | {_cell(rec.fused)} | "
            f"{_cell(rec.windowed)} | {_cell(rec.on_device)} |")
        if rec.excluded:
            excluded.append(f"- **{name}** — {rec.excluded}")
    out = "\n".join(lines)
    if excluded:
        out += ("\n\nRecord-derived exclusions (the refusal each guard "
                "raises):\n\n" + "\n".join(excluded))
    return out


#: Markers bounding the generated region inside docs/EXECUTION.md.
MATRIX_BEGIN = ("<!-- BEGIN GENERATED capability-matrix "
                "(python scripts/gen_support_matrix.py --write) -->")
MATRIX_END = "<!-- END GENERATED capability-matrix -->"


def matrix_block() -> str:
    """The full marker-bounded block embedded in docs/EXECUTION.md."""
    return f"{MATRIX_BEGIN}\n{render_matrix()}\n{MATRIX_END}"
