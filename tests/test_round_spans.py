"""The simulator round's spans (``obs.trace.span``: ``fed.*``) and their
reduction beside the benchmark (``benchmark/reduce_spans.py`` and the readers
under ``benchmark/layer_metrics/`` that call it); since PR 36 also the device
scopes of the step and of the group loop, the dispatch spans' ``slots`` /
``samples``, and ``benchmark/reduce_booked.py`` with its readers.

CPU only: the spans are read back from ``jax.profiler``'s own trace, which
holds host annotations on any backend; device time by phase and the idle
partition are checked on a hand-made event list and on two rounds recorded
on the chip (``benchmark/fixtures/femnist_rounds.spans.json.gz``).
"""

import contextlib
import glob
import importlib.util
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.algos.config import FedConfig
from fedml_tpu.algos.fedavg import FedAvgAPI
from fedml_tpu.data.batching import build_federated_arrays
from fedml_tpu.data.store import FederatedStore
from fedml_tpu.models.lr import LogisticRegression
from fedml_tpu.obs import trace as obs_trace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCHMARK = os.path.join(ROOT, "benchmark")

ROUND_SPANS = {"fed.round", "fed.round.sample", "fed.round.dispatch",
               "fed.round.loss_fetch"}
STORE_SPANS = {"fed.store.gather", "fed.store.put"}
#: placement -> (names on the main thread, names on a worker's line)
EXPECTED = {
    "resident": (ROUND_SPANS, set()),
    "mesh": (ROUND_SPANS, set()),   # gathered inside the sharded program
    "store": (ROUND_SPANS | {"fed.cohort.wait"},
              STORE_SPANS | {"fed.cohort.prefetch"}),
}


def _load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def rs():
    return _load(os.path.join(BENCHMARK, "reduce_spans.py"), "reduce_spans")


def _api(placement: str, **cfg_more) -> FedAvgAPI:
    rng = np.random.default_rng(0)
    clients, per_client = 12, 16
    x = rng.normal(size=(clients * per_client, 6)).astype(np.float32)
    y = rng.integers(0, 3, clients * per_client).astype(np.int32)
    parts = {c: np.arange(c * per_client, (c + 1) * per_client)
             for c in range(clients)}
    mesh = None
    if placement == "store":
        fed = FederatedStore(x, y, parts, batch_size=8)
    else:
        fed = build_federated_arrays(x, y, parts, 8)
        if placement == "mesh":
            from fedml_tpu.parallel.mesh import client_mesh

            mesh = client_mesh(4)
    cfg = FedConfig(client_num_in_total=clients, client_num_per_round=4,
                    comm_round=100, epochs=1, batch_size=8, lr=0.1, seed=0,
                    **cfg_more)
    return FedAvgAPI(LogisticRegression(num_classes=3), fed, None, cfg,
                     mesh=mesh)


def _join_prefetch(api) -> None:
    pf = getattr(api, "_cohort_prefetcher", None)
    for t in list(pf._pending.values()) if pf else []:
        t.join(timeout=30)
        assert not t.is_alive()


def _profiled(api, rounds, trace_dir, before=None):
    """``fed.*`` events of ``rounds`` run under a profiler session, as
    ``(line, name, start, end, stats)``; ``before(r)`` runs ahead of round
    ``r`` inside the session."""
    jax.profiler.start_trace(str(trace_dir))
    try:
        for r in rounds:
            if before:
                before(r)
            api.train_one_round(r)
        _join_prefetch(api)
        jax.block_until_ready(api.net.params)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for position, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("fed."):
                    s = int(ev.start_ns)
                    out.append((position, ev.name, s,
                                s + int(ev.duration_ns), dict(ev.stats)))
    return out


def _inside(inner, outer) -> bool:
    return (inner[0] == outer[0] and outer[2] <= inner[2]
            and inner[3] <= outer[3])


@pytest.mark.parametrize("placement", ["resident", "mesh", "store"])
def test_profiled_rounds_hold_the_table_of_spans(placement, tmp_path):
    main_names, worker_names = EXPECTED[placement]
    api = _api(placement)
    api.train_one_round(0)
    events = _profiled(api, (1, 2), tmp_path)
    rounds = [e for e in events if e[1] == "fed.round"]
    assert [int(e[4]["round"]) for e in rounds] == [1, 2]
    main = rounds[0][0]
    assert {e[1] for e in events if e[0] == main} == main_names
    assert {e[1] for e in events if e[0] != main} == worker_names
    for e in events:
        if e[0] != main or e[1] == "fed.round":
            continue
        # every main-thread span lies in its own round's fed.round
        outer, = [r for r in rounds if _inside(e, r)]
        assert int(e[4]["round"]) == int(outer[4]["round"]), e
    for r in rounds:   # once a round each, in the table's order
        inside = sorted((e for e in events
                         if e[0] == main and e is not r and _inside(e, r)),
                        key=lambda e: e[2])
        assert [e[1] for e in inside][0] == "fed.round.sample"
        assert [e[1] for e in inside][-2:] == ["fed.round.dispatch",
                                               "fed.round.loss_fetch"]
        assert len(inside) == len(main_names) - 1
    if placement != "store":
        return
    # The worker prepares round r + 1 while round r runs; the store's spans
    # carry no round of their own and lie in the prefetch that does.
    prefetches = [e for e in events if e[1] == "fed.cohort.prefetch"]
    assert [int(e[4]["round"]) for e in prefetches] == [2, 3]
    for p, r in zip(prefetches, rounds):
        assert r[2] <= p[2]     # started by round r
        gather, put = sorted((e for e in events if e[1] in STORE_SPANS
                              and _inside(e, p)), key=lambda e: e[2])
        assert (gather[1], put[1]) == ("fed.store.gather", "fed.store.put")
        assert int(gather[4]["clients"]) == 4 and int(gather[4]["steps"]) == 2
        # x [4,2,8,6] f32 + y [4,2,8] i32 + mask [4,2,8] f32 + counts [4] i32
        assert int(put[4]["bytes"]) == 4 * 16 * (6 + 1 + 1) * 4 + 4 * 4


def test_a_missed_prefetch_gathers_on_the_main_thread(tmp_path):
    api = _api("store")
    api.train_one_round(0)

    def spoil(r):
        """The indices change between the prefetch of round ``r`` and its
        ``get``: what was prepared is for another cohort."""
        if r == 2:
            _join_prefetch(api)
            idx, group, cohort = api._cohort_prefetcher._ready[2]
            api._cohort_prefetcher._ready[2] = (np.roll(idx, 1), group,
                                                cohort)

    events = _profiled(api, (1, 2), tmp_path, before=spoil)
    main = next(e[0] for e in events if e[1] == "fed.round")
    waits = [e for e in events if e[1] == "fed.cohort.wait"]
    assert [int(e[4]["round"]) for e in waits] == [1, 2]
    on_main = [e for e in events if e[0] == main and e[1] in STORE_SPANS]
    assert [e[1] for e in on_main] == ["fed.store.gather", "fed.store.put"]
    assert all(_inside(e, waits[1]) for e in on_main)      # the miss
    assert not any(_inside(e, waits[0]) for e in on_main)  # the hit


@pytest.mark.parametrize("placement", ["resident", "store"])
def test_an_installed_span_tracer_sees_the_same_names(placement):
    api = _api(placement)
    api.train_one_round(0)
    _join_prefetch(api)
    tracer = obs_trace.SpanTracer()
    with obs_trace.using(tracer):
        api.train_one_round(1)
        _join_prefetch(api)
    assert obs_trace.active() is obs_trace.NULL
    api.train_one_round(2)      # nobody is tracing: nothing is recorded
    _join_prefetch(api)
    events = tracer.events()
    main_names, worker_names = EXPECTED[placement]
    assert {e["name"] for e in events} == main_names | worker_names
    assert all(e["cat"] == "fed" and e["ph"] == "X" for e in events)
    by_name = {e["name"]: e for e in events}
    assert by_name["fed.round"]["args"] == {"round": 1}
    if placement == "store":
        assert by_name["fed.cohort.prefetch"]["args"] == {"round": 2}
        assert by_name["fed.cohort.prefetch"]["tid"] != \
            by_name["fed.round"]["tid"]
        assert by_name["fed.store.gather"]["args"] == {"clients": 4,
                                                       "steps": 2}


@pytest.mark.parametrize("placement", ["resident", "store"])
def test_a_profiler_session_changes_no_parameter(placement, tmp_path):
    plain, traced = _api(placement), _api(placement)
    for r in range(3):
        plain.train_one_round(r)
    traced.train_one_round(0)
    _profiled(traced, (1, 2), tmp_path)
    for a, b in zip(jax.tree.leaves(plain.net.params),
                    jax.tree.leaves(traced.net.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --- the reducer ---------------------------------------------------------

def _toy():
    """One round and a half on a main thread (line 1) and a worker (line 0),
    in ns. The window is 0..1000 (bench.round 0..900, bench.fence 900..1000);
    a worker span runs over its end. Device: ops over 100..400 (a while with
    two nested ops), 500..600 and 650..880."""
    h, o = "host", "op"
    return [
        [h, 1, "bench.round", 0, 900, {}],
        [h, 1, "fed.round", 10, 880, {"round": 7}],
        [h, 1, "fed.round.sample", 20, 30, {"round": 7}],
        [h, 1, "fed.cohort.wait", 60, 30, {"round": 7}],
        [h, 1, "fed.round.dispatch", 90, 20, {"round": 7}],
        [h, 1, "fed.round.loss_fetch", 420, 465, {"round": 7}],
        [h, 1, "bench.fence", 900, 100, {}],
        [h, 0, "fed.cohort.prefetch", 70, 300, {"round": 8}],
        [h, 0, "fed.store.gather", 80, 100, {"clients": 4, "steps": 2}],
        [h, 0, "fed.store.put", 180, 150, {"bytes": 4096}],
        [h, 0, "fed.cohort.prefetch", 950, 200, {"round": 9}],   # cut at 1000
        [h, 0, "fed.store.gather", 960, 100, {"clients": 4, "steps": 2}],
        [o, 0, "while.1", 100, 300, "fed.local_train"],
        [o, 0, "fusion.2", 120, 100, "fed.local_train"],
        [o, 0, "fusion.3", 230, 150, ""],
        [o, 0, "fusion.4", 500, 100, "fed.aggregate"],
        [o, 0, "fusion.5", 650, 230, "fed.local_train"],
        ["module", 0, "jit_step_fn(1)", 100, 780, ""],
    ]


def test_reduce_on_a_hand_made_trace(rs):
    r = rs.reduce(_toy())
    assert (r["rounds"], r["window_ns"], r["busy_ns"]) == (1, 1000, 630)
    spans = r["spans"]
    assert spans["fed.round"] == {"count": 1, "total_ns": 880,
                                  "self_ns": 880 - 30 - 30 - 20 - 465,
                                  "bytes": 0}
    assert spans["bench.round"]["self_ns"] == 20
    # the worker's spans count on any thread; the second prefetch is cut at
    # the window's edge (50 of its 200 ns, 40 of its gather's 100)
    assert spans["fed.cohort.prefetch"]["total_ns"] == 300 + 50
    assert spans["fed.cohort.prefetch"]["self_ns"] == 50 + 10
    assert spans["fed.store.gather"]["total_ns"] == 100 + 40
    assert spans["fed.store.put"]["bytes"] == 4096
    assert "fed.store.gather" not in r["main_ns"]
    assert (r["waits"], r["misses"]) == (1, 0)
    # idle is cut at span edges, each piece to the innermost main-thread span
    assert r["idle_ns"] == 1000 - 630
    assert sum(r["idle_ns_by_span"].values()) == r["idle_ns"]
    assert r["idle_ns_by_span"] == {
        "bench.round": 10 + 10,                      # 0..10, 890..900
        "bench.round/fed.round": 10 + 10 + 20 + 5,   # 10..20 50..60 400..420 885..890
        "bench.round/fed.round/fed.round.sample": 30,
        "bench.round/fed.round/fed.cohort.wait": 30,
        "bench.round/fed.round/fed.round.dispatch": 10,      # 90..100
        "bench.round/fed.round/fed.round.loss_fetch": 80 + 50 + 5,
        "bench.fence": 100,
    }
    pairs = r["idle_ns_by_span_and_worker"]
    assert pairs["bench.round/fed.round/fed.cohort.wait"] == {
        "-": 10, "fed.cohort.prefetch": 10, "fed.store.gather": 10}
    assert pairs["bench.fence"] == {"-": 50, "fed.cohort.prefetch": 10,
                                    "fed.store.gather": 40}
    assert rs.idle_ns(r, inside=(rs.WAIT,)) == 30
    assert rs.idle_ns(r, inside=(rs.SYNC,)) == 135
    assert rs.idle_ns(r, inside=(rs.ROUND,),
                      outside=(rs.WAIT, rs.SYNC)) == 45 + 30 + 10
    assert rs.idle_ns(r, outside=(rs.ROUND,)) == 120
    # device self time by phase: the while's 300 less its two children
    assert r["device_ns_by_phase"] == {
        "": 150, "fed.aggregate": 100, "fed.local_train": 50 + 100 + 230}
    assert sum(r["device_ns_by_phase"].values()) == r["busy_ns"]
    assert r["unscoped_ops"] == [("fusion.3", 150)]
    assert rs.phase_ns(r, "fed.gather") == 0
    assert r["modules"] == {"jit_step_fn(1)": [1, 780]}
    assert "fed.round.loss_fetch" in rs.table(r)


def test_reduce_a_miss_a_parent_program_and_no_window(rs):
    toy = _toy()
    # a miss: the store's spans on the main thread, inside the wait
    toy[3] = ["host", 1, "fed.cohort.wait", 50, 40, {"round": 7}]
    toy += [["host", 1, "fed.store.gather", 55, 20, {}],
            ["host", 1, "fed.store.put", 75, 10, {"bytes": 8}]]
    r = rs.reduce(toy)
    assert (r["waits"], r["misses"]) == (1, 1)
    assert r["main_ns"]["fed.store.gather"] == 20
    assert rs.idle_ns(r, inside=(rs.WAIT,)) == 40
    assert r["idle_ns_by_span"][
        "bench.round/fed.round/fed.cohort.wait/fed.store.gather"] == 20
    # a program without the spans or the scopes: the window and the idle
    # total stand, nothing is booked under fed.*, no phase is named
    parent = [[k, ln, n, s, d, "" if k == "op" else x]
              for k, ln, n, s, d, x in _toy() if not n.startswith("fed.")]
    r = rs.reduce(parent)
    assert r["rounds"] == 0 and r["idle_ns"] == 370
    assert r["idle_ns_by_span"] == {"bench.round": 270, "bench.fence": 100}
    assert rs.phase_ns(r, "fed.local_train") is None
    # no device operation (a CPU rehearsal): host spans only
    r = rs.reduce([e for e in _toy() if e[0] == "host"])
    assert r["rounds"] == 1 and not r["device"] and r["idle_ns"] is None
    assert rs.idle_ns(r, inside=(rs.SYNC,)) is None
    assert r["main_ns"]["fed.round.loss_fetch"] == 465
    # no bench.* span: no window
    assert rs.reduce([e for e in _toy() if "bench." not in e[2]]) is None


def test_the_eager_gather_is_known_by_its_programs(rs):
    """The mesh branch: ``gather_clients`` runs eagerly, as programs of its
    own whose ops carry no scope, between the rounds' steps."""
    toy = _toy() + [
        ["host", 1, "fed.round.gather", 50, 10, {"round": 7}],
        ["module", 0, "jit__take(2)", 40, 50, ""],
        ["op", 0, "gather.1", 40, 30, ""],
        ["op", 0, "select_n.3", 70, 20, ""]]
    r = rs.reduce(toy)
    assert r["device_ns_by_phase"] == {
        "": 150, "fed.aggregate": 100, "fed.gather": 50,
        "fed.local_train": 380}     # fusion.3 lies in the step: no phase
    # without the host span (the fused gather_step) nothing is guessed
    r = rs.reduce([e for e in toy if e[2] != "fed.round.gather"])
    assert r["device_ns_by_phase"][""] == 200
    assert "fed.gather" not in r["device_ns_by_phase"]


def test_phase_of_takes_the_innermost_scope(rs):
    path = "jit(gather_step)/jit(main)/fed.local_train/vmap(while)/body/conv"
    assert rs.phase_of(["x", path]) == "fed.local_train"
    assert rs.phase_of([path + "/fed.aggregate/psum"]) == "fed.aggregate"
    assert rs.phase_of(["fusion.3 = f32[8] fusion(...)"]) == ""


def _pb(*fields) -> bytes:
    """A protobuf message from ``(number, int | bytes)`` fields."""
    def varint(n):
        out = b""
        while True:
            out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
            n >>= 7
            if not n:
                return out

    out = b""
    for number, value in fields:
        if isinstance(value, int):
            out += varint(number << 3) + varint(value)
        else:
            out += varint(number << 3 | 2) + varint(len(value)) + value
    return out


def test_phases_are_read_from_the_event_metadata(rs, tmp_path):
    """``tf_op`` is a stat of an op's *metadata*, as a string or as a
    reference to an interned one; ProfileData shows neither."""
    path = "jit(step_fn)/jit(round_fn)/%s/vmap(while)/body/dot_general:"
    plane = _pb(
        (1, 3), (2, b"/device:TPU:0"),
        (3, _pb((1, 9), (2, b"XLA Ops"), (4, b"events, never parsed"))),
        (5, _pb((1, 7), (2, _pb((1, 7), (2, b"tf_op"))))),
        (5, _pb((1, 300), (2, _pb((1, 300),
                                  (2, (path % "fed.aggregate").encode()))))),
        (4, _pb((1, 1), (2, _pb(
            (1, 1), (2, b"%fusion.1 = f32[8]{0} fusion()"),
            (5, _pb((1, 7), (5, (path % "fed.local_train").encode()))))))),
        (4, _pb((1, 2), (2, _pb(
            (1, 2), (2, b"%all-reduce.2 = f32[8]{0} all-reduce()"),
            (5, _pb((1, 7), (7, 300))))))),
        (4, _pb((1, 3), (2, _pb((1, 3), (2, b"%while.3 = () while()"))))))
    other = _pb((1, 4), (2, b"/host:CPU"),
                (4, _pb((1, 1), (2, _pb((1, 1), (2, b"fed.gather"))))))
    file = tmp_path / "hand.xplane.pb"
    file.write_bytes(_pb((1, other), (1, plane)))
    assert rs.metadata_phases(str(file), "/device:TPU:0") == {
        "%fusion.1 = f32[8]{0} fusion()": "fed.local_train",
        "%all-reduce.2 = f32[8]{0} all-reduce()": "fed.aggregate"}
    assert rs.metadata_phases(str(file), "/device:TPU:1") == {}


def _manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


NEW_READERS = [
    "host_work_ms.round", "sync_wait_ms.round", "data_wait_ms.round",
    "prefetch_hit_pct", "cohort_gather_ms.round", "cohort_put_ms.round",
    "device_ms.gather.round", "device_ms.local_train.round",
    "device_ms.aggregate.round", "idle_ms.host_work.round",
    "idle_ms.data_wait.round", "idle_ms.sync.round", "idle_ms.outside.round"]


def _reader(name: str):
    return _load(os.path.join(BENCHMARK, "layer_metrics", name + ".py"),
                 "reader_" + name.replace(".", "_"))


@pytest.mark.parametrize("name", NEW_READERS)
def test_reader_agrees_with_the_manifest_and_reads_none_without_a_trace(
        name, tmp_path, monkeypatch):
    manifest = _manifest()
    entry, = [m for m in manifest["per_layer"] if m["name"] == name]
    reader = _reader(name)
    assert {k: entry[k] for k in ("layer", "unit", "moves")} == reader.META
    cells = [w["name"] for w in manifest["workloads"] if reader.applies(w)]
    assert cells == entry.get("workloads",
                              [w["name"] for w in manifest["workloads"]])
    # no trace of this process's own: None, whatever an earlier run left
    rs = reader.rs
    monkeypatch.setattr(rs, "TRACE_DIR", str(tmp_path))
    assert reader.read({"chips": 1, "rounds": 3}) is None
    stale = tmp_path / "plugins" / "profile" / "old"
    stale.mkdir(parents=True)
    (stale / "host.xplane.pb").write_bytes(b"not read")
    os.utime(stale / "host.xplane.pb", (1e9, 1e9))
    assert reader.read({"chips": 1, "rounds": 3}) is None


def test_readers_on_the_recorded_chip_rounds(rs, monkeypatch):
    """Two rounds of ``femnist_cnn_3400`` cut from a traced chip run
    (PR 25): the reduction and every reader, against the recorded answers."""
    fixtures = os.path.join(BENCHMARK, "fixtures")
    with open(os.path.join(fixtures, "femnist_rounds.expected.json")) as f:
        want = json.load(f)
    got = rs.reduce(rs.load_events(
        os.path.join(fixtures, "femnist_rounds.spans.json.gz")))
    for key, value in want["result"].items():
        assert json.loads(json.dumps(got[key])) == value, key
    assert sum(got["idle_ns_by_span"].values()) == got["idle_ns"]
    assert sum(got["device_ns_by_phase"].values()) == got["busy_ns"]
    for name, value in want["derived"].items():
        reader = _reader(name)
        monkeypatch.setattr(reader.rs, "traced", lambda got=got: got)
        assert reader.read({}) == pytest.approx(value, rel=1e-12), name
    assert set(want["derived"]) == set(NEW_READERS)
    idle = sum(want["derived"][n] for n in NEW_READERS
               if n.startswith("idle_ms."))
    assert idle == pytest.approx(got["idle_ns"] / got["rounds"] / 1e6)


# --- PR 36: the step's and the group loop's scopes, the booking reader -------

STEP_SCOPES = ("fed.step.update", "fed.step.shuffle")
GROUP_SCOPE = "fed.client_groups"
MODEL_SCOPES = ("fed.model.norm", "fed.model.stack")
BOOKED_READERS = [
    "device_ms.unbooked.round", "device_ms.loop_self.round",
    "device_ms.step_update.round", "device_ms.client_groups.round",
    "device_ms.remat.round", "dispatched_fill_pct", "prefetch_busy_pct"]


@pytest.fixture(scope="module")
def rb():
    return _load(os.path.join(BENCHMARK, "reduce_booked.py"), "reduce_booked")


def _lowered_round(api):
    """The fused round of a resident toy (gather, train, aggregate: one
    program), lowered for the operands ``train_one_round`` hands it."""
    _, gather = api._fused_round_step()
    idx, wmask = api.sample_round(0)
    return gather.lower(api.net, api._window_carry_init(), api.train_fed,
                        jnp.asarray(idx), jnp.asarray(wmask), api.rng)


def _op_names(lowered) -> list:
    """Every instruction's ``op_name`` path in the COMPILED program (the
    CPU's): what a trace's event metadata carries, transforms and loops
    spelled out. (The ``add`` inside a ``reduce``'s own computation keeps
    the innermost fragment only; it is no instruction of the timeline.)"""
    return [p for p in re.findall(r'op_name="([^"]*)"',
                                  lowered.compile().as_text())
            if p.startswith("jit(")]


@pytest.mark.parametrize("group", [0, 1])
def test_the_lowered_round_names_the_steps_and_the_group_loops_scopes(
        group, rs, rb):
    lowered = _lowered_round(_api("resident", client_group_size=group))
    text = lowered.as_text(debug_info=True)
    for scope in STEP_SCOPES:
        assert scope in text, scope
    assert (GROUP_SCOPE in text) == bool(group)
    paths = _op_names(lowered)
    updates = [p for p in paths if "fed.step.update" in p]
    assert updates and all(rs.phase_of([p]) == "fed.local_train"
                           and rb.names_of(p)[-1] == "fed.step.update"
                           for p in updates)
    if not group:
        return
    # the loop's own operations end in the new name; its body's keep the
    # phase they had: fed.local_train / fed.aggregate is still the LAST phase
    inside = [p for p in paths if GROUP_SCOPE in p]
    own = [p for p in inside if rb.names_of(p)[-1] == GROUP_SCOPE]
    assert own and all(rs.phase_of([p]) == "" for p in own)
    body = [p for p in inside if GROUP_SCOPE + "/while/body/" in p
            and rs.phase_of([p])]
    assert {rs.phase_of([p]) for p in body} == {"fed.local_train",
                                                "fed.aggregate"}
    assert all(p.index(GROUP_SCOPE) < p.index(rs.phase_of([p]))
               for p in body)
    fold = [p for p in body if "fed.client_fold" in p]
    assert fold and all(rb.names_of(p)[-1] == "fed.client_fold"
                        for p in fold)


@pytest.mark.parametrize("group", [0, 1])
def test_a_named_scope_is_metadata_the_program_is_the_same(group,
                                                           monkeypatch):
    """The round lowered with every ``jax.named_scope`` a no-op is the same
    program, text for text, once locations are left out."""
    scoped = _lowered_round(
        _api("resident", client_group_size=group)).as_text()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = _lowered_round(_api("resident", client_group_size=group))
    assert "fed.step.update" not in plain.as_text(debug_info=True)
    assert plain.as_text() == scoped


def test_the_pass_strings_of_this_jax(rb):
    """``reduce_booked.pass_of`` on a ``jax.checkpoint`` + ``jax.grad`` toy
    inside a scan, as the round's step is: the forward computed again lies
    under ``rematted_computation`` (INSIDE ``transpose(jvp(...))``), the
    backward under ``transpose(`` alone, the first forward under neither. A
    jax that renames them fails here and not in a metric."""
    def layer(w, x):
        with jax.named_scope("fed.model.gdn"):
            return jnp.tanh(x @ w)

    def loss(w, x):
        f = jax.checkpoint(layer)
        return jnp.sum(f(w, f(w, x)))

    def local_train(w, xs):
        def step(w, x):
            value, grad = jax.value_and_grad(loss)(w, x)
            with jax.named_scope("fed.step.update"):
                return w - 0.1 * grad, value

        with jax.named_scope("fed.local_train"):
            return jax.lax.scan(step, w, xs)

    lowered = jax.jit(local_train).lower(jnp.ones((8, 8)),
                                         jnp.ones((3, 4, 8)))
    by_pass = {}
    for path in _op_names(lowered):
        by_pass.setdefault(rb.pass_of(path), []).append(path)
    tanh = {k: [p for p in v if p.endswith("/tanh")]
            for k, v in by_pass.items()}
    assert set(by_pass) == {rb.FORWARD, rb.REMAT, rb.BACKWARD}
    assert tanh[rb.FORWARD] and tanh[rb.REMAT] and not tanh[rb.BACKWARD]
    assert all("jvp(fed.model.gdn)" in p for p in tanh[rb.FORWARD])
    assert all("transpose(jvp(" in p and "/checkpoint/rematted_computation/"
               in p for p in tanh[rb.REMAT])
    assert all("transpose(jvp(" in p for p in by_pass[rb.BACKWARD])
    # every pass keeps the scope's name, and the update is forward work
    assert all("fed.model.gdn" in p for p in tanh[rb.REMAT])
    assert any("fed.step.update" in p for p in by_pass[rb.FORWARD])
    assert not any("fed.step.update" in p
                   for p in by_pass[rb.REMAT] + by_pass[rb.BACKWARD])


def _accepted_names(rs):
    lm = _load(os.path.join(BENCHMARK, "reduce_scopes.py"), "rsc_names")
    hybrid = _load(os.path.join(BENCHMARK, "reduce_scopes_hybrid.py"),
                   "rsh_names")
    swa = _load(os.path.join(BENCHMARK, "reduce_scopes_swa_moe.py"),
                "rsm_names")
    return sorted(set(lm.SCOPES) | set(hybrid.SCOPES) | set(swa.SCOPES)
                  | set(rs.PHASES))


NEW_SCOPES = STEP_SCOPES + (GROUP_SCOPE,) + MODEL_SCOPES


@pytest.mark.parametrize("new", NEW_SCOPES)
def test_no_new_name_is_a_prefix_of_an_accepted_one_or_the_reverse(new, rs):
    """``reduce_scopes._SCOPE`` and ``reduce_spans._PHASE`` match by
    substring: a ``fed.model.head_norm`` would be read as
    ``fed.model.head``."""
    accepted = _accepted_names(rs)
    assert len(accepted) >= 20
    for old in accepted:
        assert not new.startswith(old), (new, old)
        assert not old.startswith(new), (new, old)
    assert rs.phase_of([f"jit(f)/{new}/add"]) == ""
    for other in NEW_SCOPES:
        assert other == new or not other.startswith(new)


def _booked_toy():
    """One round, window 0..1000. A group loop ``while.1`` (100..700) under
    ``fed.client_groups`` holds a step loop ``while.2`` (120..620) under a
    bare ``fed.local_train``; the body: a forward gdn op, a recomputed one,
    a backward one with no scope, the update, and 100 ns of gaps that are
    the step loop's own; 100 ns of the group loop are its own. Outside: a
    copy XLA named nothing, an op whose path names transforms only, the
    aggregate, and a ``call`` with a nested op."""
    lt = "jit(step)/fed.client_groups/while/body/fed.local_train/"
    back = lt + "while/body/closed_call/transpose(jvp(jvp()))/"
    return [
        ["host", "bench.round", 0, 900, ""], ["host", "fed.round", 5, 890, ""],
        ["host", "bench.fence", 900, 100, ""],
        ["op", "while.1 = (f32[8]) while(f32[8] %a)", 100, 600,
         "jit(step)/fed.client_groups/while"],
        ["op", "while.2 = (f32[8]) while(f32[8] %b)", 120, 500,
         lt + "while"],
        ["op", "fusion.3", 130, 100,
         lt + "while/body/closed_call/jvp(fed.model.gdn)/dot_general"],
        ["op", "fusion.4", 240, 100, back
         + "checkpoint/rematted_computation/fed.model.gdn/dot_general"],
        ["op", "fusion.5", 350, 100, back + "checkpoint/mul"],
        ["op", "add_select_fusion.6", 460, 100,
         lt + "while/body/fed.step.update/select_n"],
        ["op", "fusion.7", 640, 40, "jit(step)/fed.client_groups/while/body/"
         "fed.aggregate/fed.client_fold/add"],
        ["op", "copy.8", 720, 30, ""],
        ["op", "copy-done.13", 750, 10, "jit(step)/jit(round_fn)/while:"],
        ["op", "fusion.9", 760, 20, "jit(step)/jit(_take)/gather"],
        ["op", "fusion.10", 790, 50, "jit(step)/fed.aggregate/div"],
        ["op", "call.11 = f32[8] call()", 850, 60,
         "jit(step)/fed.aggregate/call"],
        ["op", "fusion.12", 860, 40, "jit(step)/fed.aggregate/mul"],
        ["span", "fed.round.dispatch", 10, 5,
         {"round": 3, "group": 0, "steps": 2, "slots": 160, "samples": 116}],
        ["span", "fed.round.dispatch", 20, 5,
         {"round": 3, "group": 1, "steps": 4, "slots": 320, "samples": 100}],
        ["span", "fed.round.dispatch", 30, 5, {"round": 3}],
        ["span", "fed.round.dispatch", 1200, 5,
         {"round": 4, "slots": 999, "samples": 999}],    # past the window
    ]


def test_reduce_booked_on_a_hand_made_trace(rb):
    r = rb.reduce(_booked_toy())
    assert r["rounds"] == 1 and r["device"]
    # booked: gdn forward and recomputed, the update, the fold, the two
    # aggregate ops; unbooked: the bare backward op, the copy, the gather
    assert r["booked_ns"] == 100 + 100 + 100 + 40 + 50 + 40
    assert r["unbooked_ns"] == 100 + 30 + 10 + 20
    assert r["unbooked_ns_by_class"] == {"bare": 100, "empty": 30,
                                         "loop": 10, "transforms": 20}
    # a while's gaps land in the container, by its innermost name
    assert r["container_ns"] == {
        "fed.aggregate": 20, "fed.client_groups": 600 - 500 - 40,
        "fed.local_train": 500 - 400}
    busy = 600 + 30 + 10 + 20 + 50 + 60
    assert (r["booked_ns"] + r["unbooked_ns"]
            + sum(r["container_ns"].values())) == busy
    assert r["pass_ns"] == {"backward": 100, "forward": busy - 200,
                            "recomputed": 100}
    assert sum(r["pass_ns"].values()) == busy
    assert sum(r["innermost_ns"].values()) == busy
    assert r["innermost_ns"]["fed.step.update"] == 100
    assert r["innermost_ns"]["fed.client_groups"] == 60
    assert r["innermost_ns"]["fed.model.gdn"] == 200
    assert r["innermost_ns"][""] == 60
    assert r["named"] == ["fed.aggregate", "fed.client_fold",
                          "fed.client_groups", "fed.local_train",
                          "fed.model.gdn", "fed.step.update"]
    assert r["unbooked_ops"][0][:4] == ["bare", "fusion:mul", 100, 1]
    assert r["dispatch_args"] == {"spans": 3, "round": 9, "group": 1,
                                  "steps": 6, "slots": 480, "samples": 216}
    said = rb.table(r)
    assert "fed.client_groups" in said and "empty" in said
    # no window, and a CPU rehearsal's trace: host spans, no device op
    assert rb.reduce([e for e in _booked_toy() if e[0] != "host"]) is None
    host_only = rb.reduce([e for e in _booked_toy() if e[0] != "op"])
    assert not host_only["device"] and host_only["dispatch_args"]["slots"] \
        == 480
    assert rb.is_container("while.12 = (s32[], f32[8]) while(")
    assert rb.is_container("conditional.3") and rb.is_container("call.1")
    assert not rb.is_container("while_fusion.3") \
        and not rb.is_container("fusion.2") \
        and not rb.is_container("call-start.1")
    assert rb.path_of(["a.py:3", "jit(f)/mul", "x"]) == "jit(f)/mul"
    assert rb.path_of(["a.py:3"]) == ""


def test_booked_readers_on_a_reduction(rb, monkeypatch):
    r = rb.reduce(_booked_toy())
    spans = {"spans": {"fed.cohort.prefetch": {"total_ns": 600}},
             "window_ns": 1000, "rounds": 1}
    want = {"device_ms.unbooked.round": 160e-6,
            "device_ms.loop_self.round": 180e-6,
            "device_ms.step_update.round": 100e-6,
            "device_ms.client_groups.round": 60e-6,
            "device_ms.remat.round": 100e-6,
            "dispatched_fill_pct": 100.0 * 216 / 480,
            "prefetch_busy_pct": 60.0}
    assert sorted(want) == sorted(BOOKED_READERS)
    for name, value in want.items():
        reader = _reader(name)
        if hasattr(reader, "rb"):
            monkeypatch.setattr(reader.rb, "traced", lambda: r)
        else:
            monkeypatch.setattr(reader.rs, "traced", lambda: spans)
        assert reader.read({}) == pytest.approx(value), name
    # the parent of PR 36: no such scope, no such argument -> left out
    parent = [e if e[0] != "span" else e[:4] + [{"round": 3}]
              for e in _booked_toy()]
    parent = [e[:4] + [e[4].replace("fed.step.update/", "")
                       .replace("fed.client_groups/", "")]
              if e[0] == "op" else e for e in parent]
    r0 = rb.reduce(parent)
    for name in ("device_ms.step_update.round",
                 "device_ms.client_groups.round", "dispatched_fill_pct"):
        reader = _reader(name)
        monkeypatch.setattr(reader.rb, "traced", lambda: r0)
        assert reader.read({}) is None, name
    unbooked = _reader("device_ms.unbooked.round")
    monkeypatch.setattr(unbooked.rb, "traced", lambda: r0)
    assert unbooked.read({}) == pytest.approx(260e-6)   # the update too
    # the step's scopes named, none of them an op's innermost: 0, not None
    fused = rb.reduce([e[:4] + [e[4].replace(
        "fed.step.update/select_n", "fed.step.shuffle/x/fed.model.gdn/y")]
        if e[0] == "op" else e for e in _booked_toy()])
    update = _reader("device_ms.step_update.round")
    monkeypatch.setattr(update.rb, "traced", lambda: fused)
    assert update.read({}) == 0.0
    # a CPU rehearsal: spans and their arguments, never a device number
    host_only = rb.reduce([e for e in _booked_toy() if e[0] != "op"])
    for name in BOOKED_READERS[:5]:
        reader = _reader(name)
        monkeypatch.setattr(reader.rb, "traced", lambda: host_only)
        assert reader.read({}) is None, name


@pytest.mark.parametrize("name", BOOKED_READERS)
def test_booked_reader_agrees_with_the_manifest_and_reads_none_without_a_trace(
        name, tmp_path, monkeypatch):
    manifest = _manifest()
    entry, = [m for m in manifest["per_layer"] if m["name"] == name]
    reader = _reader(name)
    assert {k: entry[k] for k in ("layer", "unit", "moves")} == reader.META
    cells = [w["name"] for w in manifest["workloads"] if reader.applies(w)]
    assert cells == entry.get("workloads",
                              [w["name"] for w in manifest["workloads"]])
    rs = reader.rb.rs if hasattr(reader, "rb") else reader.rs
    monkeypatch.setattr(rs, "TRACE_DIR", str(tmp_path))
    assert reader.read({"chips": 1, "rounds": 3}) is None


@pytest.mark.parametrize("grouped", [False, True])
def test_the_dispatch_spans_carry_what_the_registry_counts(grouped):
    """A streamed round of both kinds: the whole cohort in one dispatch, and
    a size group a dispatch. ``slots`` / ``samples`` on the spans are the
    host values ``dispatch_profile()``'s counters are incremented by."""
    if grouped:
        import test_size_groups

        api = test_size_groups._api()
    else:
        api = _api("store")
    assert bool(api._size_group()) == grouped
    api.train_one_round(0)
    _join_prefetch(api)
    before = api.dispatch_profile()
    tracer = obs_trace.SpanTracer()
    with obs_trace.using(tracer):
        api.train_one_round(1)
        api.train_one_round(2)
        _join_prefetch(api)
    after = api.dispatch_profile()
    carrying = [e["args"] for e in tracer.events()
                if e["name"] == "fed.round.dispatch" and "slots" in e["args"]]
    assert len(carrying) == (after["groups_dispatched"]
                             - before["groups_dispatched"])
    assert len(carrying) == (2 * 8 if grouped else 2)
    for key, counter in (("slots", "slots_dispatched"),
                         ("samples", "samples_real")):
        assert all(type(a[key]) is int for a in carrying)
        assert sum(a[key] for a in carrying) == after[counter] \
            - before[counter] > 0
