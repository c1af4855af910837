"""The simulator round's spans (``obs.trace.span``: ``fed.*``) and their
reduction beside the benchmark (``benchmark/reduce_spans.py`` and the readers
under ``benchmark/layer_metrics/`` that call it).

CPU only: the spans are read back from ``jax.profiler``'s own trace, which
holds host annotations on any backend; device time by phase and the idle
partition are checked on a hand-made event list and on two rounds recorded
on the chip (``benchmark/fixtures/femnist_rounds.spans.json.gz``).
"""

import glob
import importlib.util
import json
import os

import jax
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


def _api(placement: str) -> FedAvgAPI:
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
                    comm_round=100, epochs=1, batch_size=8, lr=0.1, seed=0)
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
