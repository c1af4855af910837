"""Self-tuning federation control (fedml_tpu.ctrl) — docs/ROBUSTNESS.md
"Adaptive control".

Fast lane: the actuation seam's validation surface (range / cast /
constraint / busy refusals, each with its named reason and counter), the
shipped policies on synthetic telemetry, controller plumbing (merge
order, interval gating, failure containment with detach-after-3), the
controller-off bit-equality pins, a seconds-scale spiked-sim actuation
smoke, and the same-controller-object sim→loopback portability pin. The
full load-spike drill (controller vs static arms, two-run reproducible)
is ``slow``-marked.
"""

import hashlib

import numpy as np
import pytest

from fedml_tpu.algos import FedConfig
from fedml_tpu.algos.fedasync import (
    MSG_ARG_KEY_MODEL_VERSION,
    MSG_ARG_KEY_TASK_SEQ,
)
from fedml_tpu.algos.fedavg_distributed import (
    MSG_ARG_KEY_MODEL_PARAMS,
    MSG_TYPE_C2S_SEND_MODEL_TO_SERVER,
)
from fedml_tpu.algos.fedbuff import (
    FedBuffServerManager,
    FedML_FedBuff_distributed,
)
from fedml_tpu.comm.ingest import IngestPool
from fedml_tpu.comm.loopback import LoopbackNetwork
from fedml_tpu.comm.message import Message
from fedml_tpu.ctrl import (
    ActuationRefused,
    ActuationSeam,
    FederationController,
    Knob,
    StalenessAdmissionPolicy,
    TimeoutAutoscalePolicy,
    WindowSchedulePolicy,
    controller_from_args,
    read_telemetry,
)
from fedml_tpu.data.batching import batch_global, build_federated_arrays
from fedml_tpu.data.partition import partition_homo
from fedml_tpu.data.synthetic import make_classification
from fedml_tpu.models.lr import LogisticRegression
from fedml_tpu.obs.registry import MetricsRegistry
from fedml_tpu.obs.trace import FlightRecorder
from fedml_tpu.sim import FleetSimulator, FleetSpec, make_fleet_trace


# --------------------------------------------------------------------------
# The actuation seam as its own validated surface (no manager, no policy)


class _Box:
    """Plain attribute holder for knob get/set closures."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def _seam(busy=None):
    box = _Box(alpha=0.5, k=2, workers=2)
    reg = MetricsRegistry()
    flight = FlightRecorder(clock=lambda: 0.0)
    seam = ActuationSeam(
        "TestOwner",
        [
            Knob("alpha", lambda: box.alpha,
                 lambda v: setattr(box, "alpha", v), 1e-6, 1.0),
            Knob("k", lambda: box.k,
                 lambda v: setattr(box, "k", v), 1, 8, cast=int),
            Knob("workers", lambda: box.workers,
                 lambda v: setattr(box, "workers", v), 1, 64, cast=int,
                 constraint=lambda v: ("pool_shrink_unsupported"
                                       if v < box.workers else None)),
        ],
        registry=reg, flight=flight, busy=busy, progress=lambda: 7)
    return seam, box, reg, flight


def _kinds(flight):
    return [e["kind"] for e in flight.snapshot()]


def test_seam_apply_counts_and_flight_records():
    seam, box, reg, flight = _seam()
    assert seam.names == ("alpha", "k", "workers")
    got = seam.apply("alpha", 0.25, reason="test")
    assert got == 0.25 and box.alpha == 0.25
    assert reg.counter("actuation_applied").value == 1
    ev = flight.snapshot()[-1]
    assert ev["kind"] == "actuation"
    assert ev["knob"] == "alpha" and ev["old"] == 0.5 and ev["new"] == 0.25
    assert ev["reason"] == "test" and ev["progress"] == 7
    # Applying the CURRENT value is a no-op: nothing counted, no event.
    seam.apply("alpha", 0.25)
    assert reg.counter("actuation_applied").value == 1
    assert len(flight.snapshot()) == 1


@pytest.mark.parametrize("knob,value,reason", [
    ("alpha", 2.0, "out_of_range[1e-06,1.0]"),
    ("alpha", -1.0, "out_of_range[1e-06,1.0]"),
    ("k", 2.5, "not_integral"),
    ("k", "nope", "uncastable"),
    ("k", 0, "out_of_range[1,8]"),
    ("workers", 1, "pool_shrink_unsupported"),
    ("no_such", 1, "unknown_knob"),
])
def test_seam_refusals_are_loud_and_named(knob, value, reason):
    """Every refusal class raises with its machine-readable reason,
    bumps ``actuation_refused``, and flight-records the attempt — a
    buggy policy is diagnosable post-mortem, never silently clamped."""
    seam, box, reg, flight = _seam()
    before = dict(box.__dict__)
    with pytest.raises(ActuationRefused) as ei:
        seam.apply(knob, value)
    assert ei.value.reason == reason
    assert box.__dict__ == before  # nothing mutated
    assert reg.counter("actuation_refused").value == 1
    assert reg.counter("actuation_applied").value == 0
    ev = flight.snapshot()[-1]
    assert ev["kind"] == "actuation_refused" and ev["reason"] == reason


def test_seam_busy_probe_refuses_unsafe_time():
    busy = ["mid_flush"]
    seam, box, reg, _ = _seam(busy=lambda: busy[0])
    with pytest.raises(ActuationRefused) as ei:
        seam.apply("alpha", 0.1)
    assert ei.value.reason == "mid_flush" and box.alpha == 0.5
    busy[0] = None  # boundary reached
    assert seam.apply("alpha", 0.1) == 0.1


def test_seam_request_queue_drains_at_boundary():
    seam, box, reg, _ = _seam()
    seam.request("alpha", 0.9)
    seam.request("k", 99)  # out of range: refused AT APPLY, not queued out
    assert box.alpha == 0.5  # nothing applied yet
    applied = seam.apply_pending()
    assert applied == 1 and box.alpha == 0.9 and box.k == 2
    assert reg.counter("actuation_refused").value == 1
    # Unknown knobs refuse at request time — the caller's bug should not
    # surface rounds later.
    with pytest.raises(ActuationRefused):
        seam.request("no_such", 1)
    # Queue is drained: a second apply_pending is a no-op.
    assert seam.apply_pending() == 0


# --------------------------------------------------------------------------
# Manager knob surfaces + the admission gate


def _buff_server(workers=2, buffer_k=2, comm_round=10, **kw):
    class A:
        pass

    args = A()
    args.network = LoopbackNetwork(workers + 1)
    cfg = kw.pop("cfg", None) or FedConfig(
        client_num_in_total=workers, client_num_per_round=workers,
        comm_round=comm_round)
    srv = FedBuffServerManager(
        args, {"w": np.zeros(2, np.float32)}, cfg, workers + 1,
        buffer_k=buffer_k, staleness_exp=0.5, **kw)
    return srv, args.network


def _upload(srv, worker, base_ver, task, delta=(1.0, 1.0)):
    m = Message(MSG_TYPE_C2S_SEND_MODEL_TO_SERVER, worker, 0)
    m.add(MSG_ARG_KEY_MODEL_PARAMS, {"w": np.asarray(delta, np.float32)})
    m.add(MSG_ARG_KEY_MODEL_VERSION, base_ver)
    m.add(MSG_ARG_KEY_TASK_SEQ, task)
    srv.handle_upload(m)


def test_fedbuff_knob_surface():
    srv, _ = _buff_server()
    assert set(srv.ctrl.names) >= {"alpha", "buffer_k", "max_staleness",
                                   "staleness_exp"}
    # done_timeout_s arms only when the watchdog was armed at
    # construction (the thread starts at run(); arming later would be a
    # silent no-op).
    assert "done_timeout_s" not in srv.ctrl.names
    srv2, _ = _buff_server(clock=lambda: 0.0, done_timeout_s=5.0)
    assert "done_timeout_s" in srv2.ctrl.names
    # buffer_k's ceiling is the worker count: a buffer the fleet can
    # never fill would halt progress.
    with pytest.raises(ActuationRefused) as ei:
        srv.ctrl.apply("buffer_k", 3)
    assert "out_of_range" in ei.value.reason


def test_buffer_k_refuses_mid_flush():
    """The one genuinely unsafe window on the buffered tier: resizing
    the buffer while ``_flush_buffer`` is reducing it."""
    srv, _ = _buff_server(workers=3, buffer_k=3)
    srv._in_flush = True
    with pytest.raises(ActuationRefused) as ei:
        srv.ctrl.apply("buffer_k", 2)
    assert ei.value.reason == "mid_flush"
    srv._in_flush = False
    assert srv.ctrl.apply("buffer_k", 2) == 2 and srv.buffer_k == 2


def test_sync_manager_knob_surface():
    fed, test = _tiny_problem()
    cfg = FedConfig(client_num_in_total=4, client_num_per_round=4,
                    comm_round=2, epochs=1, batch_size=16, lr=0.3)
    spec = FleetSpec(n_devices=4, seed=1, horizon_s=100.0)
    sim = FleetSimulator(LogisticRegression(num_classes=2), fed, test, cfg,
                         make_fleet_trace(spec), mode="sync")
    names = sim.server.ctrl.names
    # round_timeout_s arms because the sim defaults a round deadline in.
    assert "aggregate_k" in names and "round_timeout_s" in names
    old_hb = sim.server.heartbeat.timeout_s
    assert old_hb == sim.server.round_timeout_s
    sim.server.ctrl.apply("round_timeout_s", old_hb * 2)
    # The heartbeat silence threshold tracks the round deadline when it
    # defaulted from it.
    assert sim.server.heartbeat.timeout_s == old_hb * 2


def test_ingest_workers_knob_grows_but_never_shrinks():
    cfg = FedConfig(client_num_in_total=2, client_num_per_round=2,
                    comm_round=4, ingest_workers=2)
    srv, _ = _buff_server(cfg=cfg)
    try:
        assert "ingest_workers" in srv.ctrl.names
        assert srv.ctrl.apply("ingest_workers", 3) == 3
        assert srv._pool.workers == 3
        with pytest.raises(ActuationRefused) as ei:
            srv.ctrl.apply("ingest_workers", 2)
        assert ei.value.reason == "pool_shrink_unsupported"
        assert srv._pool.workers == 3
    finally:
        srv._pool.close()


def test_ingest_pool_resize_surface():
    pool = IngestPool(1)
    try:
        pool.resize(3)
        assert pool.workers == 3 and len(pool._threads) == 3
        with pytest.raises(ValueError, match="shrink unsupported"):
            pool.resize(2)
        pool.resize(3)  # no-op at current width
        assert pool.workers == 3 and len(pool._threads) == 3
    finally:
        pool.close()
    with pytest.raises(RuntimeError):
        pool.resize(4)


def test_admission_cap_sheds_stale_arrivals_loudly():
    """``max_staleness`` drops an over-stale upload BEFORE it reaches
    the buffer, counts it (attribute + registry + health()), flight-
    records it, and still re-assigns the sender (reply discipline: a
    shed worker must not be stranded). Offered staleness stays in the
    telemetry window — an armed cap cannot blind the guard band."""
    srv, net = _buff_server(buffer_k=1)
    srv.ctrl.apply("max_staleness", 1)
    _upload(srv, 1, 0, 0)            # staleness 0 → version 1
    _upload(srv, 2, 0, 0)            # staleness 1: at cap, admitted
    assert srv.version == 2 and srv.admission_drops == 0
    inbox_before = net.inbox(1).qsize()
    _upload(srv, 1, 0, 1)            # staleness 2 > cap: shed
    assert srv.version == 2
    assert srv.admission_drops == 1
    assert srv.health()["admission_drops"] == 1
    assert srv.registry.snapshot()["admission_drops"] == 1
    assert srv.arrival_log == [(1, 0), (2, 0)]  # never entered the log
    assert list(srv._stale_recent) == [0, 1, 2]  # offered, not admitted
    ev = [e for e in srv.flight.snapshot() if e["kind"] == "admission_drop"]
    assert ev and ev[-1]["sender"] == 1 and ev[-1]["staleness"] == 2
    # The shed worker got a fresh assignment, not silence.
    assert net.inbox(1).qsize() == inbox_before + 1
    # Disarming (cap 0) admits anything again.
    srv.ctrl.apply("max_staleness", 0)
    _upload(srv, 1, 0, 2)            # staleness 2, cap off
    assert srv.version == 3 and srv.admission_drops == 1


# --------------------------------------------------------------------------
# Policies on synthetic telemetry (pure decision functions)


def test_staleness_policy_guard_band_and_relax_order():
    p = StalenessAdmissionPolicy(2.0, 4.0, k_max=4, cap_slack=1, cooldown=2)
    knobs = {"buffer_k": 2, "max_staleness": 0}

    out = p.propose({"staleness_p95": 3.0, "progress": 0.0}, knobs)
    assert out == {}  # inside the band: nothing moves
    out = p.propose({"staleness_p95": 6.0, "progress": 1.0}, knobs)
    assert out == {"buffer_k": 3, "max_staleness": 5}  # ceil(4)+1 slack
    knobs = {"buffer_k": 3, "max_staleness": 5}
    out = p.propose({"staleness_p95": 6.0, "progress": 2.0}, knobs)
    assert out == {}  # cooldown: 2 progress units must elapse
    out = p.propose({"staleness_p95": 6.0, "progress": 3.0}, knobs)
    assert out["buffer_k"] == 4
    knobs["buffer_k"] = 4
    out = p.propose({"staleness_p95": 6.0, "progress": 5.0}, knobs)
    assert "buffer_k" not in out  # k_max reached
    # Recovery relaxes in REVERSE order: k back toward baseline first...
    out = p.propose({"staleness_p95": 1.0, "progress": 7.0}, knobs)
    assert out == {"buffer_k": 3}
    knobs["buffer_k"] = 3
    out = p.propose({"staleness_p95": 1.0, "progress": 9.0}, knobs)
    assert out == {"buffer_k": 2}
    knobs["buffer_k"] = 2
    # ...and the cap disarms only once k is back at its baseline.
    out = p.propose({"staleness_p95": 1.0, "progress": 11.0}, knobs)
    assert out == {"max_staleness": 0}


def test_staleness_policy_missing_telemetry_is_a_noop():
    p = StalenessAdmissionPolicy(2.0, 4.0)
    assert p.propose({"progress": 1.0}, {"buffer_k": 2}) == {}


def test_window_policy_tracks_improvement_rate():
    p = WindowSchedulePolicy(w_min=1, w_max=4, rate_thresh=0.01)
    knobs = {"buffer_k": 2}
    # First sample only latches the baseline.
    assert p.propose({"accuracy": 0.5, "progress": 4.0}, knobs) == {}
    # Same progress (same eval sample): no action.
    assert p.propose({"accuracy": 0.5, "progress": 4.0}, knobs) == {}
    # Improving fast → widen the averaging window.
    out = p.propose({"accuracy": 0.6, "progress": 8.0}, knobs)
    assert out == {"buffer_k": 3}
    knobs["buffer_k"] = 3
    # Flat → decay back toward w_min.
    out = p.propose({"accuracy": 0.601, "progress": 12.0}, knobs)
    assert out == {"buffer_k": 2}
    knobs["buffer_k"] = 1
    out = p.propose({"accuracy": 0.601, "progress": 16.0}, knobs)
    assert out == {}  # already at w_min


def test_window_policy_sync_tier_uses_aggregate_k():
    p = WindowSchedulePolicy(w_min=1, w_max=4, metric="loss")
    p.propose({"loss": 2.0, "progress": 0.0}, {"aggregate_k": 2})
    out = p.propose({"loss": 1.0, "progress": 4.0}, {"aggregate_k": 2})
    assert out == {"aggregate_k": 3}  # falling loss = improvement


def test_timeout_policy_grows_on_evictions_and_calms_back():
    p = TimeoutAutoscalePolicy(grow=2.0, timeout_cap=4.0, calm_steps=2)
    knobs = {"round_timeout_s": 10.0}
    assert p.propose({"evictions": 0.0}, knobs) == {}  # baseline latch
    out = p.propose({"evictions": 1.0}, knobs)
    assert out == {"round_timeout_s": 20.0}
    knobs = {"round_timeout_s": 20.0}
    assert p.propose({"evictions": 1.0}, knobs) == {}   # calm 1
    out = p.propose({"evictions": 1.0}, knobs)          # calm 2 → shrink
    assert out == {"round_timeout_s": 10.0}
    # The cap bounds growth at timeout_cap x the initial deadline.
    knobs = {"round_timeout_s": 40.0}
    assert p.propose({"evictions": 5.0}, knobs) == {}


def test_timeout_policy_occupancy_arm_adds_ingest_worker():
    p = TimeoutAutoscalePolicy(occ_hi=0.8, workers_max=3)
    out = p.propose({"occupancy": 0.9}, {"ingest_workers": 2})
    assert out == {"ingest_workers": 3}
    assert p.propose({"occupancy": 0.9}, {"ingest_workers": 3}) == {}
    assert p.propose({"occupancy": 0.5}, {"ingest_workers": 2}) == {}


@pytest.mark.parametrize("bad", [
    lambda: StalenessAdmissionPolicy(5.0, 2.0),
    lambda: StalenessAdmissionPolicy(-1.0, 2.0),
    lambda: WindowSchedulePolicy(w_min=0),
    lambda: WindowSchedulePolicy(w_min=5, w_max=2),
    lambda: TimeoutAutoscalePolicy(grow=0.9),
    lambda: FederationController([], interval=0),
])
def test_policy_constructor_validation(bad):
    with pytest.raises(ValueError):
        bad()


# --------------------------------------------------------------------------
# Controller plumbing


class _Always:
    """Test policy: always propose the given targets."""

    def __init__(self, name, targets):
        self.name = name
        self.targets = dict(targets)

    def reset(self):
        pass

    def propose(self, telemetry, knobs):
        return dict(self.targets)


def test_controller_merges_later_policy_wins_and_logs():
    srv, _ = _buff_server()
    ctl = FederationController(
        [_Always("optimist", {"alpha": 0.9, "buffer_k": 1}),
         _Always("safety", {"alpha": 0.2, "nonexistent_knob": 7})])
    srv.attach_controller(ctl)
    srv.version = 1  # telemetry progress must clear the interval gate
    applied = ctl.step(srv)
    # safety's alpha overrode optimist's; its unknown-knob proposal was
    # DROPPED (tier portability), not refused.
    assert srv.alpha == 0.2 and srv.buffer_k == 1
    assert applied == 2
    assert srv.registry.snapshot().get("actuation_refused", 0) == 0
    knobs = [(e["knob"], e["policy"], e["outcome"]) for e in ctl.actuation_log]
    assert ("alpha", "safety", "applied") in knobs
    assert ("buffer_k", "optimist", "applied") in knobs


def test_controller_interval_gates_on_progress():
    srv, _ = _buff_server()
    ctl = FederationController([_Always("p", {"alpha": 0.9})], interval=4)
    srv.attach_controller(ctl)
    srv.version = 1
    assert ctl.step(srv) == 1  # first step always runs (gap from -inf)
    srv.alpha = 0.5
    srv.version = 3
    assert ctl.step(srv) == 0 and srv.alpha == 0.5  # gap 2 < 4
    srv.version = 5
    assert ctl.step(srv) == 1 and srv.alpha == 0.9


def test_controller_refusal_is_logged_not_raised():
    srv, _ = _buff_server()
    ctl = FederationController([_Always("p", {"alpha": 99.0})])
    srv.attach_controller(ctl)
    srv.version = 1
    assert ctl.step(srv) == 0
    assert srv.alpha != 99.0
    assert ctl.actuation_log[-1]["outcome"].startswith("refused:out_of_range")
    assert srv.registry.snapshot()["actuation_refused"] == 1


def test_attach_controller_requires_a_seam():
    from fedml_tpu.comm.managers import ServerManager

    class Bare:
        ctrl = None

    with pytest.raises(ValueError, match="actuation seam"):
        ServerManager.attach_controller(Bare(), FederationController([]))


def test_boundary_contains_policy_errors_and_detaches_after_three():
    """A crashing policy must not take the federation down: each failure
    is counted + flight-recorded, and after three consecutive failing
    steps the controller is detached — the manager runs on with its
    last-applied knobs (static behavior, not an outage)."""

    class Bomb:
        name = "bomb"

        def reset(self):
            pass

        def propose(self, telemetry, knobs):
            raise RuntimeError("policy bug")

    srv, _ = _buff_server()
    ctl = FederationController([Bomb()])
    srv.attach_controller(ctl)
    for v in (1, 2):
        srv.version = v
        srv._ctrl_boundary()
        assert srv._controller is ctl  # still attached, error contained
    srv.version = 3
    srv._ctrl_boundary()
    assert srv._controller is None
    assert srv.registry.snapshot()["actuation_policy_errors"] == 3
    kinds = [e["kind"] for e in srv.flight.snapshot()]
    assert kinds.count("policy_error") == 3
    assert kinds[-1] == "controller_detached"
    # Later boundaries are quiet no-ops.
    srv.version = 4
    srv._ctrl_boundary()
    assert srv.registry.snapshot()["actuation_policy_errors"] == 3


def test_read_telemetry_windowed_staleness_and_health():
    srv, _ = _buff_server(buffer_k=1)
    for s in (0, 0, 0, 5):
        srv._stale_recent.append(s)
    t = read_telemetry(srv)
    assert t["progress"] == 0.0
    assert t["staleness_p95"] == 5.0 and t["staleness_p50"] == 0.0
    assert t["evictions"] == 0.0 and t["admission_drops"] == 0.0


def test_controller_from_args_builds_safety_last():
    class A:
        controller = "adaptive"
        controller_interval = 2
        controller_band_lo = 1.0
        controller_band_hi = 3.0

    ctl = controller_from_args(A())
    assert ctl.interval == 2
    assert [p.name for p in ctl.policies] == [
        "window_schedule", "timeout_autoscale", "staleness_admission"]
    assert ctl.policies[-1].band_hi == 3.0
    A.controller = "none"
    assert controller_from_args(A()) is None
    A.controller = "bogus"
    with pytest.raises(SystemExit):
        controller_from_args(A())


# --------------------------------------------------------------------------
# Controller-off bit-equality + the spiked-sim drills


def _tiny_problem(n_clients=4, samples=160, n_features=8, n_classes=2,
                  seed=3, test_n=64):
    x, y = make_classification(samples, n_features=n_features,
                               n_classes=n_classes, seed=seed)
    fed = build_federated_arrays(x, y, partition_homo(len(x), n_clients),
                                 batch_size=16)
    test = batch_global(x[:test_n], y[:test_n], 16)
    return fed, test


def _golden_run(mode, **kw):
    fed, test = _tiny_problem()
    cfg = FedConfig(client_num_in_total=4, client_num_per_round=4,
                    comm_round=12, epochs=1, batch_size=16, lr=0.3,
                    frequency_of_the_test=4)
    spec = FleetSpec(n_devices=4, seed=5, horizon_s=4000.0, mean_online=0.8,
                     base_round_s=25.0, slot_s=150.0)
    sim = FleetSimulator(LogisticRegression(num_classes=2), fed, test, cfg,
                        make_fleet_trace(spec), mode=mode, **kw)
    res = sim.run()
    return hashlib.sha256(repr((
        res.arrival_log, res.staleness, res.updates, round(res.virtual_s, 3),
        [round(t, 3) for t in res.completion_times])).encode()).hexdigest()


# Pinned from the pre-controller tree: the seam, the admission gate (cap
# 0 = unlimited), the windowed-staleness deque, and the boundary hook
# must all be bit-invisible while no controller is attached.
GOLDEN = {
    "fedbuff": "e2b90d4c28ed5e1e0efd6ccf5c79088535fd77ef6781a46b1bbbdeadd8dd433b",
    "sync": "9f40e8e70672a86b3784a0ea78c401db1c9f9df91c4dc5116c05ec7abc882434",
    "fedasync": "103c70a520f463545b56f94c015810e0046d0b72f21c63c3f9e690d4a9da3c33",
}


@pytest.mark.parametrize("mode", ["fedbuff", "sync", "fedasync"])
def test_controller_off_is_bit_equal_to_pre_controller_tree(mode):
    kw = {"buffer_k": 2} if mode == "fedbuff" else {}
    assert _golden_run(mode, **kw) == GOLDEN[mode]


def test_spike_defaults_are_inert():
    """``spike_factor`` defaults to exactly 1.0 — a bit-exact multiply —
    so traces that never ask for a spike schedule are unchanged, and an
    explicit factor-1 spike window is indistinguishable from none."""
    spec = FleetSpec(n_devices=3, seed=2)
    tr = make_fleet_trace(spec)
    assert tr.load_factor(0.0) == 1.0 and tr.load_factor(1e9) == 1.0
    spiked = make_fleet_trace(
        FleetSpec(n_devices=3, seed=2, spike_t0=10.0, spike_t1=20.0,
                  spike_factor=1.0))
    assert spiked.load_factor(15.0) == 1.0
    hot = make_fleet_trace(
        FleetSpec(n_devices=3, seed=2, spike_t0=10.0, spike_t1=20.0,
                  spike_factor=6.0))
    assert hot.load_factor(15.0) == 6.0
    assert hot.load_factor(9.9) == 1.0 and hot.load_factor(20.0) == 1.0


# -- the load-spike drill (pinned config) ------------------------------------

DRILL_SPEC = FleetSpec(n_devices=8, seed=11, horizon_s=20000.0,
                       mean_online=0.92, base_round_s=20.0, slot_s=400.0,
                       arrival_spread_s=30.0, spike_t0=250.0, spike_t1=700.0,
                       spike_factor=6.0)


def _drill_problem():
    x, y = make_classification(320, n_features=10, n_classes=4, seed=1)
    fed = build_federated_arrays(x, y, partition_homo(len(x), 8),
                                 batch_size=16)
    test = batch_global(x[:96], y[:96], 16)
    return fed, test


def _drill_cfg(comm_round=24):
    return FedConfig(client_num_in_total=8, client_num_per_round=8,
                     comm_round=comm_round, epochs=1, batch_size=16, lr=0.3,
                     frequency_of_the_test=4)


def _drill_controller():
    return FederationController(
        [WindowSchedulePolicy(w_min=1, w_max=4),
         StalenessAdmissionPolicy(band_lo=2.0, band_hi=4.0, k_max=4,
                                  cap_slack=0, cooldown=2)],
        interval=1)


def _drill_sim(controller=None, buffer_k=2, comm_round=24):
    fed, test = _drill_problem()
    return FleetSimulator(LogisticRegression(num_classes=4), fed, test,
                          _drill_cfg(comm_round), make_fleet_trace(DRILL_SPEC),
                          mode="fedbuff", buffer_k=buffer_k,
                          controller=controller)


def _drill_run(controller=None, buffer_k=2, comm_round=24):
    return _drill_sim(controller, buffer_k, comm_round).run()


def _acc_per_vmin(res):
    return (res.final_accuracy or 0.0) * 60.0 / max(res.virtual_s, 1e-9)


def _p95(vals):
    if not vals:
        return 0.0
    s = sorted(vals)
    return float(s[min(len(s) - 1, int(round(0.95 * (len(s) - 1))))])


def test_controller_actuates_on_spiked_sim():
    """Fast lane: the spike trips the guard band and the admission
    policy actuates — visible all three ways (the controller's log, the
    ctrl counters, the flight ring)."""
    ctl = _drill_controller()
    sim = _drill_sim(controller=ctl, comm_round=12)
    sim.run()
    applied = [e for e in ctl.actuation_log if e["outcome"] == "applied"]
    assert applied, ctl.actuation_log
    assert any(e["policy"] == "staleness_admission" for e in applied)
    snap = sim.server.registry.snapshot()
    assert snap["actuation_applied"] == len(applied)
    kinds = [e["kind"] for e in sim.server.flight.snapshot()]
    assert "actuation" in kinds


@pytest.mark.slow
def test_controller_beats_best_static_on_load_spike_drill():
    """The acceptance drill: on the seeded spike trace the controller
    keeps accepted-staleness p95 below the best static arm's cliff while
    matching or beating its accuracy-per-virtual-minute — and does it
    reproducibly (same seed, two runs, identical actuation logs and
    result streams)."""
    statics = {k: _drill_run(buffer_k=k) for k in (2, 6)}
    ctl = _drill_controller()
    res = _drill_run(controller=ctl)
    log1 = list(ctl.actuation_log)

    best_static = max(statics.values(), key=_acc_per_vmin)
    assert _p95(res.staleness) < _p95(best_static.staleness)
    assert _acc_per_vmin(res) >= _acc_per_vmin(best_static)
    applied = [e for e in log1 if e["outcome"] == "applied"
               and e["policy"] == "staleness_admission"]
    assert applied  # the win came from actuation, not luck

    # Reproducibility: the SAME controller object, rebound, replays the
    # identical actuation sequence and result streams.
    res2 = _drill_run(controller=ctl)
    assert list(ctl.actuation_log) == log1
    assert res2.arrival_log == res.arrival_log
    assert res2.staleness == res.staleness
    assert res2.updates == res.updates


def test_same_controller_object_drives_sim_then_loopback():
    """The portability acceptance bar: ONE controller object first
    drives a FleetSimulator run, then — rebound by attach_controller —
    a REAL loopback federation, actuating through the identical seam
    and leaving the identical observability trail (flight events +
    ctrl counters)."""

    class PokeAlpha:
        """Deterministic in both worlds: keys on progress only."""

        name = "poke_alpha"

        def reset(self):
            self._done = False

        def propose(self, telemetry, knobs):
            if not self._done and telemetry.get("progress", 0) >= 1 \
                    and "alpha" in knobs:
                self._done = True
                return {"alpha": 0.37}
            return {}

    ctl = FederationController([PokeAlpha()])
    spec = FleetSpec(n_devices=4, seed=5, horizon_s=4000.0, mean_online=0.8,
                     base_round_s=25.0, slot_s=150.0)
    fed, test = _tiny_problem()
    cfg = FedConfig(client_num_in_total=4, client_num_per_round=4,
                    comm_round=6, epochs=1, batch_size=16, lr=0.3,
                    frequency_of_the_test=4)
    sim = FleetSimulator(LogisticRegression(num_classes=2), fed, test, cfg,
                         make_fleet_trace(spec), mode="fedbuff", buffer_k=2,
                         controller=ctl)
    sim.run()
    assert [e["knob"] for e in ctl.actuation_log] == ["alpha"]
    assert sim.server.alpha == 0.37

    srv = FedML_FedBuff_distributed(
        LogisticRegression(num_classes=2), fed, test, cfg, buffer_k=2,
        controller=ctl)
    # bind() reset the log; the real run replayed the same actuation.
    assert [(e["knob"], e["outcome"]) for e in ctl.actuation_log] == [
        ("alpha", "applied")]
    assert srv.alpha == 0.37
    assert srv.registry.snapshot()["actuation_applied"] == 1
    ev = [e for e in srv.flight.snapshot() if e["kind"] == "actuation"]
    assert ev and ev[0]["knob"] == "alpha" and ev[0]["new"] == 0.37
