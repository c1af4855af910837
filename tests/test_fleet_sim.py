"""Trace-driven fleet simulation (fedml_tpu.sim) + buffered semi-sync
aggregation (fedml_tpu.algos.fedbuff) — docs/ROBUSTNESS.md "Serving
under churn".

Fast lane: trace determinism (same seed + spec → identical arrival/
availability/speed schedules and identical fedbuff aggregation order),
``staleness_weight`` edge cases, the buffered server's fake-clock
eviction/staleness accounting, the task-seq dedupe regression, a
seconds-scale loopback fedbuff smoke, and a tiny SIM-fabric run. The
churn serving drill (sync
first-k vs buffered(k) vs pure async on one seeded diurnal trace) is
``slow``-marked.
"""

import dataclasses

import numpy as np
import pytest

from fedml_tpu.algos import FedConfig
from fedml_tpu.algos.fedasync import (
    MSG_ARG_KEY_MODEL_VERSION,
    MSG_ARG_KEY_TASK_SEQ,
    staleness_weight,
)
from fedml_tpu.algos.fedavg_distributed import (
    MSG_ARG_KEY_MODEL_PARAMS,
    MSG_TYPE_C2S_SEND_MODEL_TO_SERVER,
    MSG_TYPE_S2C_SYNC_MODEL_TO_CLIENT,
    MSG_TYPE_SRV_TICK,
)
from fedml_tpu.algos.fedbuff import (
    FedBuffClientManager,
    FedBuffServerManager,
    FedML_FedBuff_distributed,
)
from fedml_tpu.comm import codec as wire_codec
from fedml_tpu.comm.loopback import LoopbackNetwork
from fedml_tpu.comm.message import Message
from fedml_tpu.data.batching import batch_global, build_federated_arrays
from fedml_tpu.data.partition import partition_homo
from fedml_tpu.data.synthetic import make_classification
from fedml_tpu.models.lr import LogisticRegression
from fedml_tpu.sim import FleetSimulator, FleetSpec, make_fleet_trace


# --------------------------------------------------------------------------
# Trace determinism


def test_trace_same_seed_identical():
    spec = FleetSpec(n_devices=6, seed=7, horizon_s=3600.0,
                     diurnal_amplitude=0.4, mean_online=0.7)
    a, b = make_fleet_trace(spec), make_fleet_trace(spec)
    assert a.arrivals == b.arrivals
    assert a.speeds == b.speeds
    assert a.windows == b.windows
    for r in range(1, 7):
        for t in range(0, 16):
            assert a.compute_time(r, t) == b.compute_time(r, t)


def test_trace_seed_changes_schedule():
    spec = FleetSpec(n_devices=6, seed=7)
    other = make_fleet_trace(dataclasses.replace(spec, seed=8))
    base = make_fleet_trace(spec)
    assert (base.arrivals != other.arrivals or base.speeds != other.speeds
            or base.windows != other.windows)


def test_trace_streams_are_independent():
    """Randomness is keyed per (seed, stream, device, draw): turning the
    per-task jitter off must not reshuffle arrivals, speeds, or
    availability — no global RNG order dependence."""
    spec = FleetSpec(n_devices=5, seed=3, compute_jitter=0.2)
    a = make_fleet_trace(spec)
    b = make_fleet_trace(dataclasses.replace(spec, compute_jitter=0.0))
    assert a.arrivals == b.arrivals
    assert a.speeds == b.speeds
    assert a.windows == b.windows
    # And with jitter off, compute time is exactly base x speed.
    for r in range(1, 6):
        assert b.compute_time(r, 0) == pytest.approx(
            spec.base_round_s * b.speeds[r])


def test_trace_speeds_power_law_support():
    spec = FleetSpec(n_devices=64, seed=0, speed_alpha=1.5,
                     max_speed_mult=20.0)
    tr = make_fleet_trace(spec)
    speeds = np.array([tr.speeds[r] for r in range(1, 65)])
    assert (speeds >= 1.0).all() and (speeds <= 20.0).all()
    assert speeds.max() > 2.0  # the tail exists
    assert np.median(speeds) < 3.0  # most devices are fine


def test_trace_window_queries():
    spec = FleetSpec(n_devices=4, seed=1, horizon_s=2000.0, slot_s=100.0,
                     mean_online=0.5, arrival_spread_s=300.0)
    tr = make_fleet_trace(spec)
    for r in range(1, 5):
        for s, e in tr.windows[r]:
            assert s >= tr.arrivals[r] - 1e-9
            mid = (s + e) / 2
            assert tr.online_at(r, mid)
            assert tr.online_through(r, s, e - 1e-6)
            # A window edge inside the interval IS mid-round churn.
            assert not tr.online_through(r, mid, e + 1.0)
        assert not tr.online_at(r, tr.arrivals[r] - 1.0)
    # Rank 0 (the server) is always online.
    assert tr.online_at(0, 0.0) and tr.online_through(0, 0.0, 1e9)
    assert tr.next_online(0, 5.0) == 5.0


# --------------------------------------------------------------------------
# staleness_weight edge cases (previously only an indirect pin)


def test_staleness_weight_edges():
    assert staleness_weight(0.6, 0, 0.5) == pytest.approx(0.6)  # s=0
    assert staleness_weight(0.6, 1000, 0.0) == pytest.approx(0.6)  # a=0
    w = staleness_weight(1.0, 10 ** 9, 0.5)  # huge s: tiny but finite
    assert 0.0 < w < 1e-4 and np.isfinite(w)
    # Negative staleness (clock skew artifacts) clamps to s=0.
    assert staleness_weight(0.5, -3, 0.5) == pytest.approx(0.5)
    assert staleness_weight(1.0, 3, 1.0) == pytest.approx(0.25)


# --------------------------------------------------------------------------
# FedBuff server: fake-clock protocol accounting


def _buff_server(workers=2, buffer_k=2, comm_round=10, clock=None, **kw):
    class A:
        pass

    args = A()
    args.network = LoopbackNetwork(workers + 1)
    cfg = FedConfig(client_num_in_total=workers,
                    client_num_per_round=workers, comm_round=comm_round)
    srv = FedBuffServerManager(
        args, {"w": np.zeros(2, np.float32)}, cfg, workers + 1,
        buffer_k=buffer_k, staleness_exp=0.5,
        **({} if clock is None else {"clock": clock, "done_timeout_s": 5.0}),
        **kw)
    return srv, args.network


def _upload(srv, worker, base_ver, task, delta):
    m = Message(MSG_TYPE_C2S_SEND_MODEL_TO_SERVER, worker, 0)
    m.add(MSG_ARG_KEY_MODEL_PARAMS, {"w": np.asarray(delta, np.float32)})
    m.add(MSG_ARG_KEY_MODEL_VERSION, base_ver)
    m.add(MSG_ARG_KEY_TASK_SEQ, task)
    srv.handle_upload(m)


def test_fedbuff_accumulates_and_flushes_every_k():
    """The accumulate-on-arrival mean path: version bumps only on the
    k-th accepted arrival, the buffered aggregate is the discounted mean
    of the DELTAS, and staleness is accounted per arrival."""
    srv, _ = _buff_server(buffer_k=2)
    _upload(srv, 1, 0, 0, [1.0, 1.0])
    assert srv.version == 0 and srv._count == 1  # buffered, not applied
    _upload(srv, 2, 0, 0, [3.0, 1.0])
    assert srv.version == 1  # k-th arrival flushed
    np.testing.assert_allclose(np.asarray(srv.net["w"]), [2.0, 1.0])
    assert srv.staleness_history == [0, 0]
    assert srv.arrival_log == [(1, 0), (2, 0)]
    # Worker 1's next upload trained from version 0 — staleness 1 now.
    _upload(srv, 1, 0, 1, [1.0, 0.0])
    _upload(srv, 2, 1, 1, [0.0, 1.0])
    assert srv.version == 2
    assert srv.staleness_history == [0, 0, 1, 0]
    d1, d2 = staleness_weight(1.0, 1, 0.5), 1.0
    want = np.array([2.0, 1.0]) + (
        d1 * np.array([1.0, 0.0]) + d2 * np.array([0.0, 1.0])) / (d1 + d2)
    np.testing.assert_allclose(np.asarray(srv.net["w"]), want, rtol=1e-6)


def test_fedbuff_nan_guard_and_all_excluded_buffer():
    """A non-finite delta is weight-zeroed (excluded, not averaged), and
    an ALL-excluded buffer keeps the previous net while the version
    still advances (the arrivals were consumed)."""
    srv, _ = _buff_server(buffer_k=2)
    _upload(srv, 1, 0, 0, [2.0, 2.0])
    _upload(srv, 2, 0, 0, [np.nan, 1.0])
    assert srv.guard_drops == 1 and srv.version == 1
    np.testing.assert_allclose(np.asarray(srv.net["w"]), [2.0, 2.0])
    _upload(srv, 1, 1, 1, [np.nan, 0.0])
    _upload(srv, 2, 1, 1, [np.inf, 0.0])
    assert srv.guard_drops == 3
    assert srv.version == 2  # consumed the buffer...
    np.testing.assert_allclose(np.asarray(srv.net["w"]), [2.0, 2.0])  # ...kept net


def test_fedbuff_robust_aggregator_buffer():
    """A non-mean aggregator retains the k-deep buffer and reduces it
    through core/robust_agg: the coordinate median shrugs off one
    Byzantine outlier the mean would swallow."""
    srv, _ = _buff_server(workers=3, buffer_k=3, aggregator="coord_median")
    _upload(srv, 1, 0, 0, [1.0, 1.0])
    _upload(srv, 2, 0, 0, [2.0, 2.0])
    assert srv.version == 0 and len(srv._pending) == 2
    _upload(srv, 3, 0, 0, [1000.0, -1000.0])
    assert srv.version == 1 and srv._pending == []
    np.testing.assert_allclose(np.asarray(srv.net["w"]), [2.0, 1.0])


@pytest.mark.parametrize("agg", ["krum1", "geometric_median"])
def test_fedbuff_nan_delta_cannot_poison_robust_buffer(agg):
    """Regression: a guard-dropped non-finite delta used to enter the
    stacked buffer RAW — weight 0 excludes it from the statistics, but
    0 x NaN = NaN still poisoned krum / geometric median's weighted
    recombination. The delta is now zeroed before buffering (the
    windowed tier's where-zeroing, for the same reason)."""
    srv, _ = _buff_server(workers=3, buffer_k=3, aggregator=agg)
    _upload(srv, 1, 0, 0, [1.0, 1.0])
    _upload(srv, 2, 0, 0, [1.0, 1.0])
    _upload(srv, 3, 0, 0, [np.nan, 1.0])
    assert srv.version == 1 and srv.guard_drops == 1
    got = np.asarray(srv.net["w"])
    assert np.isfinite(got).all(), got
    np.testing.assert_allclose(got, [1.0, 1.0], rtol=1e-5)


def test_fedbuff_fake_clock_eviction_accounting():
    """The acceptance pin: heartbeat liveness on a FAKE clock — a rank
    that stops beating past done_timeout_s is reported failed, the tick
    path evicts it (counted once), and its next upload re-admits it."""
    t = [0.0]
    srv, _ = _buff_server(buffer_k=2, clock=lambda: t[0])
    srv.heartbeat.beat(1)
    srv.heartbeat.beat(2)
    t[0] = 3.0
    srv.heartbeat.beat(1)  # rank 2 goes silent
    t[0] = 6.5  # past done_timeout_s=5 since rank 2's last beat
    assert srv.heartbeat.failed() == [2]
    tick = Message(MSG_TYPE_SRV_TICK, 0, 0)
    tick.add("failed", [2])
    srv._handle_tick(tick)
    assert srv.evictions == 1
    with srv._lock:
        assert srv._members == {1}
    srv._handle_tick(tick)  # idempotent: not double-counted
    assert srv.evictions == 1
    _upload(srv, 2, 0, 0, [1.0, 1.0])  # the rank returns
    with srv._lock:
        assert srv._members == {1, 2}


def test_fedbuff_task_seq_dedupe_not_version():
    """Regression: the buffered tier re-assigns a worker at an UNCHANGED
    model version until the buffer flushes, so upload dedupe must key on
    the assignment task id. Version-keyed dedupe dropped the second
    upload as a 'duplicate' and starved the fleet (the original
    FedBuff CLI run hung forever)."""
    srv, _ = _buff_server(buffer_k=3)
    _upload(srv, 1, 0, 0, [1.0, 0.0])
    _upload(srv, 1, 0, 1, [1.0, 0.0])  # same version, NEW task: accepted
    assert srv.duplicate_drops == 0 and srv._count == 2
    _upload(srv, 1, 0, 1, [1.0, 0.0])  # true duplicate (same task)
    assert srv.duplicate_drops == 1 and srv._count == 2
    assert srv.arrival_log == [(1, 0), (1, 0)]


def test_fedbuff_client_trains_same_version_new_task():
    """The client twin: an assignment at an already-seen version but a
    new task id is fresh work (buffered tier); only a repeated task id
    is a transport duplicate."""
    class A:
        pass

    args = A()
    args.network = LoopbackNetwork(2)
    cfg = FedConfig(client_num_in_total=2, client_num_per_round=1)

    class F:
        pass

    fed = F()
    fed.x = fed.y = fed.mask = np.zeros((2, 1, 1), np.float32)
    fed.counts = np.array([4, 4])
    cm = FedBuffClientManager(
        args, 1, 2, fed,
        lambda *a: ({"w": np.zeros(2, np.float32)}, 0.0), cfg)

    def assign(version, task):
        m = Message(MSG_TYPE_S2C_SYNC_MODEL_TO_CLIENT, 0, 1)
        m.add(Message.MSG_ARG_KEY_CLIENT_INDEX, 0)
        m.add(MSG_ARG_KEY_MODEL_PARAMS, {"w": np.zeros(2, np.float32)})
        m.add(MSG_ARG_KEY_MODEL_VERSION, version)
        m.add(MSG_ARG_KEY_TASK_SEQ, task)
        # The real server always advertises the delta capability (PR
        # 15); a delta-shipping client refuses a delta-ignorant peer at
        # negotiation (tests/test_fedadapter.py pins that refusal).
        m.add(wire_codec.DELTA_OK_KEY, True)
        cm.handle_model(m)

    assign(0, 0)
    assign(0, 1)  # same version, new task: train it
    assert cm.steps == 2 and cm.duplicate_drops == 0
    assign(0, 1)  # repeated task: transport duplicate
    assert cm.steps == 2 and cm.duplicate_drops == 1
    # Uploads carry the task id the server dedupes on.
    up = args.network.inbox(0).queue[-1]
    assert up.get(MSG_ARG_KEY_TASK_SEQ) == 1


# --------------------------------------------------------------------------
# Federation smokes


def _tiny_problem(n_clients=4, samples=160):
    x, y = make_classification(samples, n_features=8, n_classes=4, seed=1)
    fed = build_federated_arrays(x, y, partition_homo(len(x), n_clients),
                                 batch_size=16)
    test = batch_global(x[:64], y[:64], 16)
    return fed, test


def test_fedbuff_loopback_smoke():
    """Tier-1 lane: the buffered federation end-to-end over loopback
    threads (the REAL wire path), seconds-scale."""
    fed, test = _tiny_problem()
    cfg = FedConfig(client_num_in_total=4, client_num_per_round=3,
                    comm_round=4, epochs=1, batch_size=16, lr=0.3,
                    frequency_of_the_test=2)
    srv = FedML_FedBuff_distributed(
        LogisticRegression(num_classes=4), fed, test, cfg, buffer_k=2)
    assert srv.version == 4
    assert len(srv.arrival_log) == 8  # k arrivals per aggregation
    assert srv.test_history and np.isfinite(srv.test_history[-1]["loss"])


def _sim_run(mode="fedbuff", seed=5, chaos=None, comm_round=5, **kw):
    fed, test = _tiny_problem()
    cfg = FedConfig(client_num_in_total=4, client_num_per_round=4,
                    comm_round=comm_round, epochs=1, batch_size=16, lr=0.3,
                    frequency_of_the_test=4)
    spec = FleetSpec(n_devices=4, seed=seed, horizon_s=4000.0,
                     mean_online=0.8, base_round_s=25.0, slot_s=150.0)
    sim = FleetSimulator(LogisticRegression(num_classes=4), fed, test, cfg,
                         make_fleet_trace(spec), mode=mode, chaos=chaos, **kw)
    return sim.run()


def test_sim_fedbuff_completes_and_is_deterministic():
    """Same seed + spec → event-for-event identical federation: the full
    accepted-arrival order (the fedbuff aggregation order) and staleness
    stream diff clean across two independent runs."""
    a = _sim_run(buffer_k=2)
    b = _sim_run(buffer_k=2)
    assert a.completed and a.updates == 5
    assert a.arrival_log == b.arrival_log and len(a.arrival_log) >= 10
    assert a.staleness == b.staleness
    assert a.virtual_s == b.virtual_s


def test_sim_chaos_composes_deterministically():
    """ChaosTransport under the virtual clock: faults reroute through
    the event queue, so even a drop/delay/duplicate drill replays
    identically from one seed."""
    from fedml_tpu.comm.resilience import ChaosSpec

    mk = lambda: ChaosSpec(seed=9, drop_p=0.05, delay_p=0.2,
                           max_delay_s=1.0, dup_p=0.05)
    a = _sim_run(chaos=mk(), buffer_k=2)
    b = _sim_run(chaos=mk(), buffer_k=2)
    assert a.completed
    assert a.arrival_log == b.arrival_log
    assert a.staleness == b.staleness


def test_sim_collapsed_fleet_reports_not_completed():
    """Regression: the async managers have no `aborted` flag, so a
    federation whose whole fleet died used to report completed=True
    (its run() finishes with the version short of comm_round). The
    progress check distinguishes collapse from completion."""
    fed, test = _tiny_problem()
    cfg = FedConfig(client_num_in_total=4, client_num_per_round=4,
                    comm_round=5, epochs=1, batch_size=16, lr=0.3)
    spec = FleetSpec(n_devices=4, seed=5, horizon_s=2000.0,
                     mean_online=0.0)  # no device is ever reachable
    sim = FleetSimulator(LogisticRegression(num_classes=4), fed, test, cfg,
                         make_fleet_trace(spec), mode="fedbuff", buffer_k=2)
    r = sim.run()
    assert r.updates == 0
    assert not r.completed


def test_sim_chaos_duplicate_cannot_outrun_the_original():
    """Regression: virtual compute is charged at TRAINING time keyed by
    the task the upload answers, not popped once at send time — a
    ChaosTransport duplicate used to ship the second copy compute-free,
    arrive before the real upload, and win the server's dedupe, erasing
    the device's compute latency from the drill. A pure-duplication
    drill must now be timing-identical to the clean run (every copy
    derives from the same recorded completion; dedupe eats the rest)."""
    from fedml_tpu.comm.resilience import ChaosSpec

    clean = _sim_run(buffer_k=2)
    dup = _sim_run(chaos=ChaosSpec(seed=3, dup_p=1.0), buffer_k=2)
    assert dup.arrival_log == clean.arrival_log
    assert dup.completion_times == clean.completion_times
    assert dup.staleness == clean.staleness


@pytest.mark.slow
def test_sim_sync_chaos_duplicate_cannot_outrun_the_original():
    """The sync-tier twin: round-keyed uploads charge from the per-rank
    completion timestamp, so a duplicated straggler upload cannot land
    compute-free ahead of the original and steal a first-k slot."""
    from fedml_tpu.comm.resilience import ChaosSpec

    clean = _sim_run(mode="sync", aggregate_k=3, comm_round=4)
    dup = _sim_run(mode="sync", aggregate_k=3, comm_round=4,
                   chaos=ChaosSpec(seed=3, dup_p=1.0))
    assert dup.completed
    assert dup.completion_times == clean.completion_times


@pytest.mark.slow
def test_sim_sync_mode_drives_real_first_k_path():
    r = _sim_run(mode="sync", aggregate_k=3, comm_round=4)
    assert r.completed and r.updates == 4
    assert r.staleness == []  # barrier rounds have no staleness stream


@pytest.mark.slow
def test_fleet_churn_serving_drill():
    """The fleet-sim acceptance, pinned as a test: on one fixed
    seeded diurnal trace with mid-round churn, buffered(k) sustains
    strictly higher round-throughput than sync first-k(k), holds a lower
    staleness tail than pure async, and lands in the clean-run accuracy
    ballpark."""
    x, y = make_classification(320, n_features=10, n_classes=4, seed=1)
    fed = build_federated_arrays(x, y, partition_homo(len(x), 8),
                                 batch_size=16)
    test = batch_global(x[:96], y[:96], 16)
    cfg = FedConfig(client_num_in_total=8, client_num_per_round=8,
                    comm_round=12, epochs=1, batch_size=16, lr=0.3,
                    frequency_of_the_test=4)
    spec = FleetSpec(n_devices=8, seed=11, horizon_s=14400.0,
                     mean_online=0.75, base_round_s=30.0, slot_s=180.0,
                     speed_alpha=1.3, diurnal_amplitude=0.3,
                     arrival_spread_s=120.0)

    def go(mode, spec=spec, **kw):
        sim = FleetSimulator(LogisticRegression(num_classes=4), fed, test,
                             cfg, make_fleet_trace(spec), mode=mode, **kw)
        return sim.run()

    clean = go("sync", spec=dataclasses.replace(spec, mean_online=1.0,
                                                diurnal_amplitude=0.0),
               aggregate_k=0)
    firstk = go("sync", aggregate_k=4)
    buffered = go("fedbuff", buffer_k=4)
    async_ = go("fedasync")
    assert clean.completed and firstk.completed
    assert buffered.completed and async_.completed
    # Churn actually happened on this trace.
    assert (firstk.churn_killed + buffered.churn_killed
            + firstk.health.get("evictions", 0)) > 0
    # Round-throughput: buffered(k) strictly beats sync first-k(k).
    assert buffered.updates_per_vmin > firstk.updates_per_vmin
    # Staleness tail: buffered(k) strictly under pure async.
    bp = float(np.percentile(buffered.staleness, 95))
    ap = float(np.percentile(async_.staleness, 95))
    assert bp < ap
    # Accuracy: buffered lands in the clean ballpark.
    assert buffered.final_accuracy >= clean.final_accuracy - 0.1


# --------------------------------------------------------------------------
# Watchdog-twin lockstep (the sim/fleet.py drift risk called out in
# _schedule_watchdog's CAUTION note): the event-driven twin's eviction
# decision must match what the REAL detector code would decide on the
# same server state at the same virtual instant — same round, same rank
# set. The twin re-states the thread loops' predicates rather than
# sharing code with them; these tests are the tripwire a policy change
# in either copy hits.


def _lockstep_sim(mode, **kw):
    fed, test = _tiny_problem()
    cfg = FedConfig(client_num_in_total=4, client_num_per_round=4,
                    comm_round=3, epochs=1, batch_size=16, lr=0.3)
    spec = FleetSpec(n_devices=4, seed=5, horizon_s=4000.0, mean_online=0.8,
                     base_round_s=25.0, slot_s=150.0)
    from fedml_tpu.models.lr import LogisticRegression as LR

    sim = FleetSimulator(LR(num_classes=4), fed, test, cfg,
                         make_fleet_trace(spec), mode=mode, **kw)
    posts = []
    if mode == "sync":
        sim.server._post_tick = (
            lambda r, failed: posts.append((r, tuple(failed))))
    else:
        sim.server._post_tick = lambda failed: posts.append(tuple(failed))
    return sim, posts


def test_watchdog_twin_sync_heartbeat_expiry_lockstep():
    """Rank 4 stops beating mid-round: the twin's `_sync_watch` and the
    real detector path (`wait_all_or_failed` over the same monitor, the
    decision `_watchdog_loop` posts from) must evict the same rank set
    at the same virtual deadline."""
    sim, posts = _lockstep_sim("sync")
    srv = sim.server
    for r in (1, 2, 3, 4):
        srv.heartbeat.beat(r)
    with srv._lock:
        srv._arrived.update({1, 2, 3})
    sim._sync_watch()
    assert posts == []  # nothing expired yet
    sim.clock.advance_to(srv.heartbeat.timeout_s + 1.0)
    for r in (1, 2, 3):
        srv.heartbeat.beat(r)  # rank 4 stays silent past the deadline
    sim._sync_watch()
    real = tuple(srv.heartbeat.wait_all_or_failed(
        [1, 2, 3, 4], have=srv._arrived_snapshot, poll_s=0.001,
        deadline_s=srv.round_timeout_s))
    assert posts == [(0, (4,))]
    assert real == posts[-1][1]


def test_watchdog_twin_sync_round_deadline_lockstep():
    """The missing-but-beating branch: rank 4's heartbeat stays alive
    but its upload never lands. Past round_timeout_s both the twin and
    the real detector must declare it failed (the deadline clause, not
    the liveness clause)."""
    import threading

    sim, posts = _lockstep_sim("sync")
    srv = sim.server
    srv.heartbeat.timeout_s = 1e9  # operator heartbeat: everyone "alive"
    for r in (1, 2, 3, 4):
        srv.heartbeat.beat(r)
    with srv._lock:
        srv._arrived.update({1, 2, 3})
    sim._sync_watch()  # latches the twin's round-deadline epoch at t=0
    assert posts == []
    real = []
    th = threading.Thread(target=lambda: real.append(tuple(
        srv.heartbeat.wait_all_or_failed(
            [1, 2, 3, 4], have=srv._arrived_snapshot, poll_s=0.002,
            deadline_s=srv.round_timeout_s))))
    th.start()
    sim.clock.advance_to(srv.round_timeout_s + 1.0)
    sim._sync_watch()
    th.join(timeout=10.0)
    assert not th.is_alive() and real
    assert posts == [(0, (4,))]
    assert real[0] == posts[-1][1]


def test_watchdog_twin_async_done_deadline_lockstep():
    """The buffered tier's terminal handshake: version has reached
    comm_round, rank 4 never reports done. Twin `_async_watch` and the
    real detector must both declare it failed once done_timeout_s
    elapses — and not a poll earlier."""
    import threading

    sim, posts = _lockstep_sim("fedbuff", buffer_k=2)
    srv = sim.server
    srv.heartbeat.timeout_s = 1e9
    for r in (1, 2, 3, 4):
        srv.heartbeat.beat(r)
    with srv._lock:
        srv.version = sim.cfg.comm_round  # terminal
        srv._done_set.update({1, 2, 3})
    sim._async_watch()  # latches _term_t0 at t=0
    assert posts == []
    real = []
    th = threading.Thread(target=lambda: real.append(tuple(
        srv.heartbeat.wait_all_or_failed(
            [1, 2, 3, 4], have=srv._done_snapshot, poll_s=0.002,
            deadline_s=srv.done_timeout_s))))
    th.start()
    sim.clock.advance_to(srv.done_timeout_s + 1.0)
    sim._async_watch()
    th.join(timeout=10.0)
    assert not th.is_alive() and real
    assert posts == [(4,)]
    assert real[0] == posts[-1]
