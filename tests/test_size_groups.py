"""The size-grouped streamed round: a host store's sampled cohort sorted by
step need and cut into ``size_group(k)`` clients a group, each group gathered
and trained at ITS power-of-two step bucket (one donated dispatch a group,
folded into one running float32 sum) instead of the cohort's largest
(``FederatedStore.gather_groups``, ``parallel.shard.make_size_group_round``,
``FedAvgAPI._train_round_size_grouped``). Same clients, samples, steps and rng
streams as the whole-cohort round; where the mechanism does not engage, the
whole-cohort round runs as it did.

CPU, float32 at the highest precision, a small dropout model so that the
per-slot rng streams are exercised.
"""

import argparse
import importlib.util
import json
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.algos.config import FedConfig
from fedml_tpu.algos.fedavg import FedAvgAPI
from fedml_tpu.algos.fednova import FedNovaAPI
from fedml_tpu.algos.fedopt import FedOptAPI
from fedml_tpu.algos.qfedavg import QFedAvgAPI
from fedml_tpu.data.directory import ShardedFederatedStore
from fedml_tpu.data.store import (CohortGroup, FederatedStore, _bucket_steps,
                                  bucket_steps_for_counts, size_group)
from fedml_tpu.obs import trace as obs_trace
from fedml_tpu.obs.sanitizer import compile_count

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: the benchmark's bound for float32 sums associated in another order
SEM_RTOL, SEM_ATOL = 1e-5, 1e-6
CLIENTS, COHORT, BATCH, CLASSES = 160, 64, 20, 5
GROUP = 8      # size_group(64, 20)
KINDS = ["lognormal", "sharded", "equal"]


class TinyDropout(nn.Module):
    @nn.compact
    def __call__(self, x, train: bool = False):
        x = x.reshape((x.shape[0], -1))
        x = nn.relu(nn.Dense(16)(x))
        x = nn.Dropout(0.25, deterministic=not train)(x)
        return nn.Dense(CLASSES)(x)


def _sizes(kind: str) -> np.ndarray:
    if kind == "equal":
        return np.full(CLIENTS, 45, np.int64)
    # the benchmark mix's law: median 35, largest 241: buckets 1..16
    sizes = np.random.RandomState(0).lognormal(3.6, 0.7, CLIENTS)
    return np.maximum(sizes.astype(np.int64), 1)


def _store(kind: str):
    sizes = _sizes(kind)
    rng = np.random.default_rng(1)
    n = int(sizes.sum())
    x = rng.normal(size=(n, 6, 6, 1)).astype(np.float32)
    y = rng.integers(0, CLASSES, n).astype(np.int32)
    edges = np.concatenate([[0], np.cumsum(sizes)])
    parts = {c: np.arange(edges[c], edges[c + 1]) for c in range(CLIENTS)}
    if kind == "sharded":
        return ShardedFederatedStore.from_flat(x, y, parts, BATCH,
                                               num_shards=3)
    return FederatedStore(x, y, parts, batch_size=BATCH)


def _api(kind="lognormal", cls=FedAvgAPI, whole=False, cohort=COHORT,
         nan_guard=False, mesh=None, store=None, **more):
    cfg = FedConfig(client_num_in_total=CLIENTS, client_num_per_round=cohort,
                    comm_round=10 ** 6, epochs=1, batch_size=BATCH, lr=0.05,
                    seed=3, **more)
    api = cls(TinyDropout(), store or _store(kind), None, cfg, mesh=mesh,
              nan_guard=nan_guard)
    if whole:   # the whole-cohort round of the same federation, for a twin
        api._size_group_clients = 0
    return api


def _leaves(api):
    return [np.asarray(leaf) for leaf in jax.tree.leaves(api.net)]


def _join_prefetch(api) -> None:
    pf = getattr(api, "_cohort_prefetcher", None)
    for t in list(pf._pending.values()) if pf else []:
        t.join(timeout=30)
        assert not t.is_alive()


# --- the rule and the grouped gather -------------------------------------

@pytest.mark.parametrize("cohort, batch, group", [
    (200, 20, 10), (1000, 20, 50), (100, 20, 5), (64, 20, 8), (40, 20, 5),
    (128, 64, 8), (200, 4, 25),
    (32, 20, 0), (10, 20, 0), (64, 4, 0), (199, 20, 0), (202, 20, 0)])
def test_the_group_is_the_smallest_divisor_wide_enough(cohort, batch, group):
    """At least 100 samples a step and 8 to 20 groups a round; no such
    divisor of the cohort (a small cohort, a small batch, a prime, twice a
    prime): the whole-cohort round."""
    assert size_group(cohort, batch) == group
    if group:
        assert cohort % group == 0 and group * batch >= 100
        assert 8 <= cohort // group <= 20


@pytest.mark.parametrize("kind", KINDS)
def test_each_group_is_gather_cohort_of_its_members(kind):
    """``gather_groups``: the cohort's slots, each once, ordered by step need
    (ties by slot); each group ``gather_cohort(idx[slots], steps)`` byte for
    byte at the bucket of ITS largest member, which is one of the
    federation's own buckets. A ``ShardedFederatedStore`` overrides only the
    storage primitive and is served by the same code."""
    store = _store(kind)
    idx = np.random.RandomState(5).choice(CLIENTS, COHORT, replace=False)
    groups = store.gather_groups(idx, GROUP)
    assert len(groups) == COHORT // GROUP
    assert all(isinstance(g, CohortGroup) for g in groups)
    slots = np.concatenate([np.asarray(g.slots) for g in groups])
    need = -(-store.counts[idx].astype(np.int64) // BATCH)
    np.testing.assert_array_equal(slots, np.argsort(need, kind="stable"))
    of_the_federation = set(
        bucket_steps_for_counts(store.counts, BATCH).tolist())
    for g in groups:
        members = idx[np.asarray(g.slots)]
        assert g.steps == _bucket_steps(int(need[np.asarray(g.slots)].max()))
        assert g.steps in of_the_federation
        want = store.gather_cohort(members, steps=g.steps)
        assert g.fed.x.shape == (GROUP, g.steps, BATCH, 6, 6, 1)
        for got, ref in zip(jax.tree.leaves(g.fed), jax.tree.leaves(want)):
            assert got.dtype == ref.dtype
            np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    if kind == "equal":
        assert {g.steps for g in groups} == {store.cohort_steps(idx)}
    else:       # the cohort's largest client pads its own group alone
        assert groups[-1].steps == store.cohort_steps(idx)
        assert sum(g.steps for g in groups) < len(groups) * groups[-1].steps
    with pytest.raises(ValueError, match="do not divide"):
        store.plan_groups(idx, 7)


# --- the round ------------------------------------------------------------

@pytest.mark.parametrize("kind, cls, more", [
    ("lognormal", FedAvgAPI, {}),
    ("sharded", FedAvgAPI, {}),
    ("lognormal", FedOptAPI, {"server_optimizer": "adam",
                              "server_lr": 0.01}),
    ("lognormal", FedAvgAPI, {"client_selection": "pow_d"}),
])
def test_a_grouped_round_is_the_whole_cohort_round(kind, cls, more):
    """Two rounds with dropout on, the same cohorts and keys: parameters and
    loss within the benchmark's semantics bound. What differs is the
    association of one float32 sum. FedOpt's server update sees the grouped
    mean like any other; ``pow_d`` cannot prefetch and gathers its groups in
    the round."""
    with jax.default_matmul_precision("highest"):
        grouped = _api(kind, cls, **more)
        whole = _api(kind, cls, whole=True, **more)
        assert grouped._size_group() == GROUP and whole._size_group() == 0
        for r in range(2):
            a = whole.train_one_round(r)["train_loss"]
            b = grouped.train_one_round(r)["train_loss"]
            assert b == pytest.approx(a, rel=SEM_RTOL, abs=SEM_ATOL)
    moved = False
    for want, got in zip(_leaves(whole), _leaves(grouped)):
        np.testing.assert_allclose(got, want, rtol=SEM_RTOL, atol=SEM_ATOL)
        moved |= bool(np.any(want != got))
    assert moved or cls is not FedAvgAPI    # two programs, not one twice


def test_a_client_ends_bit_equal_at_its_own_bucket_and_the_cohorts():
    """The trainer's rng streams (dropout, the epoch's shuffle) are
    prefix-stable in the step count, so the steps a larger bucket adds are
    all-masked no-ops: each client's trained model, on its slot's stream,
    is the same bits at its own bucket and at the cohort's."""
    api = _api()
    store = api.train_fed
    idx, _ = api.sample_round(0)
    cohort_steps = store.cohort_steps(idx)
    key = jax.random.PRNGKey(11)
    train = jax.jit(api.local_train)
    checked = 0
    for slot in (0, 7, 19, 27, 40, 51, 63):
        own = _bucket_steps(-(-int(store.counts[idx[slot]]) // BATCH))
        if own == cohort_steps:
            continue
        rng = jax.random.fold_in(key, slot)
        small = store.gather_cohort(idx[slot:slot + 1], steps=own)
        large = store.gather_cohort(idx[slot:slot + 1], steps=cohort_steps)
        a, la = train(api.net, small.x[0], small.y[0], small.mask[0], rng)
        b, lb = train(api.net, large.x[0], large.y[0], large.mask[0], rng)
        for u, v in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            np.testing.assert_array_equal(np.asarray(u), np.asarray(v))
        # the reported loss is a mean over the steps: XLA may associate it
        # differently at another step count (telemetry, as in the windowed
        # tier: docs/EXECUTION.md)
        assert float(la) == pytest.approx(float(lb), rel=1e-6)
        checked += 1
    assert checked >= 3


@pytest.mark.parametrize("whole", [False, True])
def test_the_nan_guard_drops_a_diverged_client_in_either_path(whole):
    """One sampled client's samples are NaN: its group's fold (or the
    whole-cohort mean) leaves it out, and the round equals the round of a
    clean store in which that client has weight 0 — the same model either
    way."""
    store = _store("lognormal")
    probe = _api(store=store)
    idx, _ = probe.sample_round(0)
    victim = int(idx[np.argmax(store.counts[idx])])
    lo, hi = store.offsets[victim], store.offsets[victim + 1]
    store._x[lo:hi] = np.nan
    with jax.default_matmul_precision("highest"):
        guarded = _api(store=store, whole=whole, nan_guard=True)
        loss = guarded.train_one_round(0)["train_loss"]
        other = _api(store=store, whole=not whole, nan_guard=True)
        other.train_one_round(0)
        unguarded = _api(store=store, whole=whole)
        unguarded.train_one_round(0)
    assert np.isfinite(loss)
    assert all(np.isfinite(leaf).all() for leaf in _leaves(guarded))
    assert not all(np.isfinite(leaf).all() for leaf in _leaves(unguarded))
    for want, got in zip(_leaves(other), _leaves(guarded)):
        np.testing.assert_allclose(got, want, rtol=SEM_RTOL, atol=SEM_ATOL)


# --- the prefetcher, the spans, the counters ------------------------------

def _hand_count(store, idx):
    """``(steps of each group, slots dispatched, real samples)`` of a cohort,
    by the rule in words: sort by steps needed, cut into eights, round each
    eight's largest up to a power of two."""
    need = sorted(-(-int(c) // BATCH) for c in store.counts[idx])
    steps = []
    for lo in range(0, len(need), GROUP):
        s = 1
        while s < need[lo + GROUP - 1]:
            s *= 2
        steps.append(s)
    return steps, GROUP * BATCH * sum(steps), int(store.counts[idx].sum())


def test_a_prefetched_grouped_cohort_is_a_hit_with_the_same_spans():
    """Round 1's groups are prepared on the worker during round 0: eight
    ``fed.store.gather`` (clients 8, the group's own steps) and eight
    ``fed.store.put`` inside one ``fed.cohort.prefetch``, none on the main
    thread; the main thread dispatches init, eight groups (``group``,
    ``steps``) and the finish; the running counters equal the hand count."""
    api = _api()
    api.train_one_round(0)
    _join_prefetch(api)
    before = api.dispatch_profile()
    tracer = obs_trace.SpanTracer()
    with obs_trace.using(tracer):
        api.train_one_round(1)
        _join_prefetch(api)
    events = tracer.events()
    main = next(e["tid"] for e in events if e["name"] == "fed.round")
    idx1, _ = api.sample_round(1)
    idx2, _ = api.sample_round(2)
    steps1, slots1, real1 = _hand_count(api.train_fed, idx1)
    steps2, _, _ = _hand_count(api.train_fed, idx2)

    gathers = [e for e in events if e["name"] == "fed.store.gather"]
    puts = [e for e in events if e["name"] == "fed.store.put"]
    prefetch, = [e for e in events if e["name"] == "fed.cohort.prefetch"]
    wait, = [e for e in events if e["name"] == "fed.cohort.wait"]
    assert prefetch["args"] == {"round": 2} and prefetch["tid"] != main
    assert wait["args"] == {"round": 1} and wait["tid"] == main
    assert len(gathers) == len(puts) == COHORT // GROUP
    assert all(e["tid"] == prefetch["tid"] for e in gathers + puts)
    assert [e["args"] for e in gathers] == [
        {"clients": GROUP, "steps": s} for s in steps2]
    assert sum(e["args"]["bytes"] for e in puts) == sum(
        GROUP * s * BATCH * (36 + 1 + 1) * 4 + GROUP * 4 for s in steps2)

    dispatches = [e for e in events if e["name"] == "fed.round.dispatch"]
    assert all(e["tid"] == main for e in dispatches)
    assert [{k: v for k, v in e["args"].items()
             if k not in ("slots", "samples")} for e in dispatches] == (
        [{"round": 1}]
        + [{"round": 1, "group": j, "steps": s}
           for j, s in enumerate(steps1)]
        + [{"round": 1}])
    # a group's dispatch says what it trains (PR 36); the init and the
    # finish train nothing and say nothing
    assert [e["args"].get("slots") for e in dispatches] == (
        [None] + [GROUP * s * BATCH for s in steps1] + [None])
    assert all(isinstance(e["args"]["samples"], int)
               and 0 < e["args"]["samples"] <= e["args"]["slots"]
               for e in dispatches[1:-1])
    assert sum(e["args"]["slots"] for e in dispatches[1:-1]) == slots1
    assert sum(e["args"]["samples"] for e in dispatches[1:-1]) == real1

    after = api.dispatch_profile()
    assert after["rounds_streamed"] - before["rounds_streamed"] == 1
    assert after["groups_dispatched"] - before["groups_dispatched"] == 8
    assert after["slots_dispatched"] - before["slots_dispatched"] == slots1
    assert after["samples_real"] - before["samples_real"] == real1
    assert slots1 < COHORT * BATCH * api.train_fed.cohort_steps(idx1)


def test_a_missed_grouped_prefetch_gathers_on_the_main_thread():
    """What was prepared is for another cohort: the groups are gathered
    inside ``fed.cohort.wait`` on the caller's thread — the same miss signal
    as the flat cohort's — and the round trains the right clients."""
    api, twin = _api(), _api()
    for a in (api, twin):
        a.train_one_round(0)
        _join_prefetch(a)
    idx, group, cohort = api._cohort_prefetcher._ready[1]
    assert group == GROUP and len(cohort) == COHORT // GROUP
    api._cohort_prefetcher._ready[1] = (np.roll(idx, 1), group, cohort)
    tracer = obs_trace.SpanTracer()
    with obs_trace.using(tracer):
        api.train_one_round(1)
    twin.train_one_round(1)
    _join_prefetch(api)
    events = tracer.events()
    wait, = [e for e in events if e["name"] == "fed.cohort.wait"]
    on_main = [e for e in events if e["tid"] == wait["tid"]
               and e["name"] in ("fed.store.gather", "fed.store.put")]
    assert len(on_main) == 2 * COHORT // GROUP
    assert all(wait["ts"] <= e["ts"]
               and e["ts"] + e["dur"] <= wait["ts"] + wait["dur"] + 1e-3
               for e in on_main)
    for want, got in zip(_leaves(twin), _leaves(api)):
        np.testing.assert_array_equal(got, want)
    # a cohort prepared in the other form is a miss too, not a wrong type
    flat = api._cohort_prefetcher.get(2, api.sample_round(2)[0])
    assert flat.x.shape[0] == COHORT


def test_no_program_is_compiled_after_the_first_round():
    """The first streamed round compiles the group step of every bucket a
    group of this federation can have, and the finish: a 256-round horizon
    of lognormal cohorts, whatever buckets each draws, compiles nothing
    more. (The benchmark warms one round per COHORT bucket, which no longer
    decides the programs a round runs.)"""
    api = _api()
    api.train_one_round(0)
    _join_prefetch(api)
    first = compile_count()
    seen = set()
    for r in range(1, 257):
        api.train_one_round(r)
        seen |= {s for _, s in api.train_fed.plan_groups(
            api.sample_round(r)[0], GROUP)}
    assert compile_count() == first
    assert len(seen) >= 4 and max(seen) == 16      # several programs ran
    # a new learning rate is a new trainer: its steps are compiled at once,
    # again all of them, in the round that meets it
    api.set_client_lr(0.02)
    api.train_one_round(257)
    again = compile_count()
    assert again > first
    for r in range(258, 290):
        api.train_one_round(r)
    assert compile_count() == again


# --- where the mechanism does not engage ----------------------------------

def _whole_cohort_round_by_hand(api, r):
    """``round_fn`` on ``gather_cohort`` of round ``r``'s cohort, with the
    key ``train_one_round`` will split: the parent's streamed round."""
    idx, wmask = api.sample_round(r)
    sub = api.train_fed.gather_cohort(idx)
    w = sub.counts.astype(jnp.float32) * jnp.asarray(wmask)
    key = jax.random.split(api.rng)[1]
    return api.round_fn(api.net, sub.x, sub.y, sub.mask, w, w, key)


@pytest.mark.parametrize("what, kind, cls, more", [
    ("equal clients", "equal", FedAvgAPI, {}),
    ("a robust aggregator", "lognormal", FedAvgAPI,
     {"aggregator": "coord_median"}),
    ("a client transform", "lognormal", FedAvgAPI, {"compress": "topk0.5"}),
    ("a cohort too small to cut", "lognormal", FedAvgAPI, {"cohort": 32}),
    ("client_group_size", "lognormal", FedAvgAPI, {"client_group_size": 8}),
    ("a custom round builder (q-FedAvg)", "lognormal", QFedAvgAPI, {}),
    ("oort", "lognormal", FedAvgAPI, {"client_selection": "oort"}),
    ("aux operands (FedNova)", "lognormal", FedNovaAPI, {}),
    ("a client mesh", "lognormal", FedAvgAPI, {"mesh": 2}),
])
def test_the_whole_cohort_round_runs_where_the_mechanism_does_not_engage(
        what, kind, cls, more):
    """Decided by what the code observes, once: the store's clients share a
    step bucket, or the round needs the whole trained stack, per-round
    operands, three outputs or a mesh. Then one flat cohort is gathered and
    dispatched, as before this mechanism: for the shared round builders,
    bit-equal to ``round_fn`` on ``gather_cohort``."""
    more = dict(more)
    if "mesh" in more:
        from fedml_tpu.parallel.mesh import client_mesh

        more["mesh"] = client_mesh(more["mesh"])
    api = _api(kind, cls, **more)
    assert api._size_group() == 0, what
    by_hand = None
    if cls is FedAvgAPI and not {"mesh", "client_selection"} & set(more):
        by_hand = _whole_cohort_round_by_hand(api, 0)
    tracer = obs_trace.SpanTracer()
    with obs_trace.using(tracer):
        loss = api.train_one_round(0)["train_loss"]
        _join_prefetch(api)
    assert np.isfinite(loss)
    gathers = [e["args"] for e in tracer.events()
               if e["name"] == "fed.store.gather"]
    cohort = more.get("cohort", COHORT)
    assert gathers and all(g["clients"] == cohort for g in gathers)
    profile = api.dispatch_profile()
    if profile:     # the fused streamed round counts its one dispatch
        assert profile["groups_dispatched"] == profile["rounds_streamed"] == 1
        assert profile["slots_dispatched"] == (
            cohort * BATCH * gathers[0]["steps"])
    if by_hand is not None:
        avg, want_loss = by_hand
        assert loss == float(want_loss)
        for want, got in zip(jax.tree.leaves(avg), jax.tree.leaves(api.net)):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# --- the benchmark's runner ------------------------------------------------

def _load(relative: str):
    path = os.path.join(ROOT, "benchmark", relative)
    spec = importlib.util.spec_from_file_location(
        os.path.basename(relative)[:-3], path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("grouped", [False, True])
def test_the_store_cell_rehearses_correct(grouped):
    """``femnist_cnn_3400`` through ``benchmark/run.py``'s own context and
    runner on the CPU. As committed, ``--dryrun-cpu`` samples 4 of 40: the
    whole-cohort round. With the rehearsal's sizes grown (64 of 240, a
    linear model in the CNN's place) the rounds are size-grouped, and
    ``no_compile_in_window`` holds although the runner's warm-up meets only
    one round per COHORT bucket; the semantics check's federation (batch =
    the largest client: one step each) keeps the whole-cohort round."""
    run = _load("run.py")
    manifest = run.load_manifest()
    cell = run.by_name(manifest["workloads"], "femnist_cnn_3400", "workload")
    entry = run.by_name(manifest["configs"], cell["config"], "configuration")
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    with open(run.find(manifest, f"traffic/{cell['traffic']}.json")) as f:
        mix = json.load(f)
    if grouped:
        config = {**config, "dryrun": {
            "factory": "fedml_tpu.models.lr.LogisticRegression",
            "factory_kwargs": {"num_classes": 62}}}
        mix = {**mix, "dryrun": {**mix["dryrun"], "clients": 240,
                                 "cohort": 64, "round_cycle": 12}}
    args = argparse.Namespace(seed=2900000555, seconds=0.5, trace=0,
                              dryrun_cpu=True)
    ctx = run.Ctx(manifest, cell, config, mix, args, "cpu")
    runner = ctx.load_module(os.path.join("runners", mix["runner"] + ".py"))
    seen = []
    real = FedAvgAPI._size_group

    def spy(self):
        seen.append(real(self))
        return seen[-1]

    FedAvgAPI._size_group = spy
    try:
        result = runner.run(ctx)
    finally:
        FedAvgAPI._size_group = real
    assert result["correct"] and result["failed"] == 0, result
    assert set(seen) == ({0, 8} if grouped else {0})
