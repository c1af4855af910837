"""Byzantine-robust pluggable aggregation (core/robust_agg) across every
execution tier.

Three claims are pinned here:

1. ``aggregator="mean"`` is the IDENTITY of the old weighted-average
   path — bit-equal on the host loop and the
   windowed tier, single-device and mesh (the protocol must cost nothing
   when unused).
2. Every robust aggregator is windowed-vs-host bit-equal (the order
   statistics are deterministic; the scan replays the same round_fn) and
   runs with zero steady-state recompiles under the sanitizer.
3. The attack-vs-defense matrix: with f < n/2 clients corrupted
   (``UpdateCorruptor`` device drill: sign_flip / scale / nan / random),
   coord_median / trimmed_mean / krum keep the model in the clean run's
   accuracy ballpark while plain mean degrades — measured in the
   WINDOWED tier itself, which is the point of the device-side,
   mask-driven corruptor.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.algos.config import FedConfig
from fedml_tpu.algos.fedavg import FedAvgAPI
from fedml_tpu.algos.robust import FedAvgRobustAPI
from fedml_tpu.core.robust_agg import (
    coord_median,
    geometric_median,
    krum,
    make_aggregator,
    multi_krum,
    trimmed_mean,
)
from fedml_tpu.data.batching import batch_global, build_federated_arrays
from fedml_tpu.data.store import FederatedStore
from fedml_tpu.data.synthetic import make_classification
from fedml_tpu.models.lr import LogisticRegression


# ---------------------------------------------------------------------------
# Aggregator math against numpy references


def _stack(seed=0, c=7, shapes=((3, 2), (4,))):
    rng = np.random.RandomState(seed)
    return {f"l{i}": jnp.asarray(rng.randn(c, *s).astype(np.float32))
            for i, s in enumerate(shapes)}


def test_coord_median_matches_numpy_and_excludes_zero_weight():
    st = _stack()
    w = jnp.ones(7)
    got = jax.jit(coord_median())(st, w)
    for k in st:
        np.testing.assert_allclose(np.asarray(got[k]),
                                   np.median(np.asarray(st[k]), axis=0),
                                   rtol=1e-6)
    # weight 0 EXCLUDES from the order statistics (not averaged-at-zero):
    # poison the excluded client arbitrarily — the median cannot move.
    poisoned = {k: np.array(v) for k, v in st.items()}
    for k in poisoned:
        poisoned[k][3] = 1e9
    got2 = jax.jit(coord_median())(
        {k: jnp.asarray(v) for k, v in poisoned.items()}, w.at[3].set(0.0))
    for k in st:
        ref = np.median(np.delete(np.asarray(st[k]), 3, axis=0), axis=0)
        np.testing.assert_allclose(np.asarray(got2[k]), ref, rtol=1e-6)


def test_coord_median_even_participant_count():
    st = _stack(c=6)
    got = jax.jit(coord_median())(st, jnp.ones(6))
    for k in st:
        np.testing.assert_allclose(np.asarray(got[k]),
                                   np.median(np.asarray(st[k]), axis=0),
                                   rtol=1e-6)


def test_trimmed_mean_matches_numpy():
    st = _stack(c=10)
    got = jax.jit(trimmed_mean(0.2))(st, jnp.ones(10))
    for k in st:
        s = np.sort(np.asarray(st[k]), axis=0)
        np.testing.assert_allclose(np.asarray(got[k]),
                                   s[2:8].mean(axis=0), rtol=1e-5)
    # beta=0 with full participation degenerates to the plain mean.
    got0 = jax.jit(trimmed_mean(0.0))(st, jnp.ones(10))
    for k in st:
        np.testing.assert_allclose(np.asarray(got0[k]),
                                   np.asarray(st[k]).mean(axis=0), rtol=1e-5)


def test_trimmed_mean_trims_the_outlier():
    x = np.ones((8, 4), np.float32)
    x[0] = 1e6  # one Byzantine coordinate-pusher
    got = jax.jit(trimmed_mean(0.2))({"w": jnp.asarray(x)}, jnp.ones(8))
    assert np.abs(np.asarray(got["w"]) - 1.0).max() < 1e-4


def test_krum_selects_the_clustered_update():
    rng = np.random.RandomState(1)
    x = np.concatenate([
        1.0 + 0.01 * rng.randn(6, 5).astype(np.float32),
        np.full((2, 5), 50.0, np.float32)])
    got = jax.jit(krum(2))({"w": jnp.asarray(x)}, jnp.ones(8))
    assert np.abs(np.asarray(got["w"]) - 1.0).max() < 0.1
    # multi-krum averages the m best-supported — still inside the cluster.
    got_m = jax.jit(multi_krum(2, 3))({"w": jnp.asarray(x)}, jnp.ones(8))
    assert np.abs(np.asarray(got_m["w"]) - 1.0).max() < 0.1


def test_krum_excludes_zero_weight_clients_entirely():
    """A weight-0 client must be neither selectable NOR counted as a
    neighbor: park the honest cluster at 1, put THREE zero-weighted
    clients in a tight cluster at 90 next to one Byzantine at 91 with
    weight 1 — if excluded clients leaked into the neighbor distances,
    the Byzantine's score would beat the honest cluster's."""
    x = np.concatenate([
        np.ones((4, 3), np.float32),
        np.full((3, 3), 90.0, np.float32),
        np.full((1, 3), 91.0, np.float32)])
    w = jnp.asarray(np.array([1, 1, 1, 1, 0, 0, 0, 1], np.float32))
    got = jax.jit(krum(1))({"w": jnp.asarray(x)}, w)
    assert np.abs(np.asarray(got["w"]) - 1.0).max() < 1e-4


def test_krum_single_survivor_is_selected_not_an_excluded_slot():
    """Regression (review finding): with every client but one excluded
    (nan_guard zeroed three diverged clients), the survivor has no
    finite-distance neighbor, so every score is +inf — the selection
    must still pick the VALID survivor, not let argsort's stable tie
    order hand the round to excluded slot 0's zeroed params."""
    x = np.zeros((4, 3), np.float32)
    x[2] = 5.0  # the lone survivor's update
    w = jnp.asarray(np.array([0, 0, 1, 0], np.float32))
    for agg in (krum(1), multi_krum(1, 2)):
        got = jax.jit(agg)({"w": jnp.asarray(x)}, w)
        np.testing.assert_array_equal(np.asarray(got["w"]),
                                      np.full(3, 5.0, np.float32))


def test_geometric_median_resists_the_outlier_mean_does_not():
    x = np.concatenate([np.ones((6, 4), np.float32),
                        np.full((1, 4), 1000.0, np.float32)])
    w = jnp.ones(7)
    gm = jax.jit(geometric_median(32))({"w": jnp.asarray(x)}, w)
    assert np.abs(np.asarray(gm["w"]) - 1.0).max() < 0.5
    from fedml_tpu.core.tree import tree_weighted_mean

    mn = tree_weighted_mean({"w": jnp.asarray(x)}, w)
    assert np.abs(np.asarray(mn["w"]) - 1.0).max() > 100.0


def test_make_aggregator_specs_and_errors():
    assert make_aggregator("mean").is_mean
    assert make_aggregator("coord_median").name == "coord_median"
    assert make_aggregator("trimmed_mean0.25").name == "trimmed_mean0.25"
    assert make_aggregator("krum").name == "krum1"
    assert make_aggregator("krum3").name == "krum3"
    assert make_aggregator("multi_krum2-4").name == "multi_krum2-4"
    assert make_aggregator("geometric_median16").name == "geometric_median16"
    custom = make_aggregator(lambda st, w: st)
    assert callable(custom) and not custom.is_mean
    for bad in ("foo", "trimmed_mean0.6", "krumX", "multi_krum1-0",
                "geometric_median0"):
        with pytest.raises(ValueError):
            make_aggregator(bad)


# ---------------------------------------------------------------------------
# Tier integration: mean identity + robust windowed bit-equality


def _power_law(seed=0, n_clients=12, d=6):
    rng = np.random.RandomState(seed)
    counts = np.concatenate([[600], rng.randint(20, 90, n_clients - 1)])
    tot = int(counts.sum())
    x = rng.randn(tot, d).astype(np.float32)
    y = (x @ rng.randn(d) > 0).astype(np.int32)
    edges = np.concatenate([[0], np.cumsum(counts)])
    parts = {c: np.arange(edges[c], edges[c + 1])
             for c in range(n_clients)}
    return x, y, parts


def _cfg(n, cpr, rounds, batch=16, **kw):
    kw.setdefault("lr", 0.3)
    kw.setdefault("frequency_of_the_test", 1000)
    return FedConfig(client_num_in_total=n, client_num_per_round=cpr,
                     comm_round=rounds, epochs=1, batch_size=batch, **kw)


def _assert_nets_bit_equal(a, b):
    for pa, pb in zip(jax.tree.leaves(a.net.params),
                      jax.tree.leaves(b.net.params)):
        np.testing.assert_array_equal(np.asarray(pa), np.asarray(pb))


@pytest.mark.slow  # >7 s drill; tier-1 re-fit to the 870 s budget on the 2-core box (r20 audit)
def test_mean_aggregator_bit_equal_host_windowed():
    """cfg.aggregator="mean" resolves to the builders' existing
    weighted-mean fast path — bit-equal to a default-config run on the
    host loop and the windowed tier."""
    x, y, parts = _power_law()
    mk = lambda **kw: FedAvgAPI(
        LogisticRegression(num_classes=2),
        FederatedStore(x, y, parts, batch_size=16), None,
        _cfg(12, 4, 9, **kw))
    base = mk()
    la = [base.train_one_round(r)["train_loss"] for r in range(9)]

    host = mk(aggregator="mean")
    lb = [host.train_one_round(r)["train_loss"] for r in range(9)]
    np.testing.assert_array_equal(la, lb)
    _assert_nets_bit_equal(base, host)

    win = mk(aggregator="mean")
    ld = win.train_rounds_windowed(9, window=4)
    np.testing.assert_array_equal(la, ld)
    _assert_nets_bit_equal(base, win)


@pytest.mark.slow  # >8 s drill; tier-1 re-fit to the 870 s budget on the 1-core box (r16 audit)
def test_mean_aggregator_bit_equal_on_mesh():
    from fedml_tpu.parallel.mesh import client_mesh

    x, y, parts = _power_law(seed=2, n_clients=16)
    mk = lambda **kw: FedAvgAPI(
        LogisticRegression(num_classes=2),
        FederatedStore(x, y, parts, batch_size=16), None,
        _cfg(16, 8, 4, **kw), mesh=client_mesh(4))
    base, agg = mk(), mk(aggregator="mean")
    la = [base.train_one_round(r)["train_loss"] for r in range(4)]
    lb = agg.train_rounds_windowed(4, window=2)
    np.testing.assert_array_equal(la, lb)
    _assert_nets_bit_equal(base, agg)


@pytest.mark.parametrize("agg", [
    "coord_median",
    # The rest of the zoo rides the identical code path — keep the
    # fast lane at one representative, full sweep in the slow lane.
    pytest.param("krum", marks=pytest.mark.slow),
    pytest.param("trimmed_mean0.2", marks=pytest.mark.slow),
    pytest.param("multi_krum1-2", marks=pytest.mark.slow),
    pytest.param("geometric_median4", marks=pytest.mark.slow),
])
def test_robust_aggregator_windowed_bit_equal_host(agg):
    """Every zoo member rides the windowed scan bit-equal to its own
    host loop — non-dividing window, power-law buckets (the forced
    window-max path), host-loop remainder included."""
    x, y, parts = _power_law()
    host = FedAvgAPI(LogisticRegression(num_classes=2),
                     FederatedStore(x, y, parts, batch_size=16), None,
                     _cfg(12, 4, 9, aggregator=agg))
    win = FedAvgAPI(LogisticRegression(num_classes=2),
                    FederatedStore(x, y, parts, batch_size=16), None,
                    _cfg(12, 4, 9, aggregator=agg))
    la = [host.train_one_round(r)["train_loss"] for r in range(9)]
    lb = win.train_rounds_windowed(9, window=4)
    assert win._window_stats["scanned_rounds"] == 8
    np.testing.assert_array_equal(la, lb)
    _assert_nets_bit_equal(host, win)


@pytest.mark.slow  # ~13 s for the pair; the fast lane keeps mesh
# coverage via test_robust_aggregator_mesh_windowed_bit_equal_host and
# the mean-mesh identity pin (r6 fast-lane budget discipline)
@pytest.mark.parametrize("agg", ["coord_median", "krum"])
def test_robust_aggregator_mesh_matches_vmap(agg):
    """The mesh path all_gathers the client-stacked update in global-slot
    order, so the aggregator sees the same stack the vmap path builds —
    results match to float tolerance (the local-train math reorders
    slightly across shard boundaries, as in the nan_guard mesh test)."""
    from fedml_tpu.parallel.mesh import client_mesh

    x, y, parts = _power_law(seed=2, n_clients=16)
    a = FedAvgAPI(LogisticRegression(num_classes=2),
                  FederatedStore(x, y, parts, batch_size=16), None,
                  _cfg(16, 8, 3, aggregator=agg))
    b = FedAvgAPI(LogisticRegression(num_classes=2),
                  FederatedStore(x, y, parts, batch_size=16), None,
                  _cfg(16, 8, 3, aggregator=agg), mesh=client_mesh(4))
    la = [a.train_one_round(r)["train_loss"] for r in range(3)]
    lb = [b.train_one_round(r)["train_loss"] for r in range(3)]
    np.testing.assert_allclose(la, lb, rtol=2e-6, atol=2e-6)
    for p, q in zip(jax.tree.leaves(a.net.params),
                    jax.tree.leaves(b.net.params)):
        np.testing.assert_allclose(np.asarray(p), np.asarray(q),
                                   rtol=2e-6, atol=2e-6)


@pytest.mark.slow  # >8 s drill; tier-1 re-fit to the 870 s budget on the 1-core box (r16 audit)
def test_robust_aggregator_mesh_windowed_bit_equal_host():
    """Windowed robust aggregation on a client mesh == its own sharded
    host loop, exactly."""
    from fedml_tpu.parallel.mesh import client_mesh

    x, y, parts = _power_law(seed=2, n_clients=16)
    mesh = client_mesh(4)
    host = FedAvgAPI(LogisticRegression(num_classes=2),
                     FederatedStore(x, y, parts, batch_size=16), None,
                     _cfg(16, 8, 6, aggregator="coord_median"), mesh=mesh)
    win = FedAvgAPI(LogisticRegression(num_classes=2),
                    FederatedStore(x, y, parts, batch_size=16), None,
                    _cfg(16, 8, 6, aggregator="coord_median"), mesh=mesh)
    la = [host.train_one_round(r)["train_loss"] for r in range(6)]
    lb = win.train_rounds_windowed(6, window=3)
    np.testing.assert_array_equal(la, lb)
    _assert_nets_bit_equal(host, win)


def test_robust_aggregator_on_device_scan_matches_host():
    """The on-device tier: full participation, resident layout — the
    scan replays the aggregator-equipped round_fn, bit-equal to the
    host loop (the same guarantee plain FedAvg has there)."""
    x, y, parts = _power_law(seed=5, n_clients=8)
    mk = lambda: FedAvgAPI(
        LogisticRegression(num_classes=2),
        build_federated_arrays(x, y, parts, batch_size=16), None,
        _cfg(8, 8, 4, aggregator="coord_median"))
    host, scan = mk(), mk()
    la = [host.train_one_round(r)["train_loss"] for r in range(4)]
    lb = np.asarray(scan.train_rounds_on_device(4))
    np.testing.assert_array_equal(np.asarray(la, np.float32),
                                  lb.astype(np.float32))
    _assert_nets_bit_equal(host, scan)


def test_robust_windowed_steady_state_sanitized():
    """Acceptance pin: steady-state windowed rounds under a robust
    aggregator (uniform buckets) — zero recompiles, no unplanned
    transfers. The order-statistics block is static-shape by
    construction (fixed-iteration Weiszfeld, sorts, static trims)."""
    from fedml_tpu.obs.sanitizer import sanitized

    rng = np.random.RandomState(3)
    x = rng.randn(12 * 32, 6).astype(np.float32)
    y = (x @ rng.randn(6) > 0).astype(np.int32)
    parts = {c: np.arange(c * 32, (c + 1) * 32) for c in range(12)}
    api = FedAvgAPI(LogisticRegression(num_classes=2),
                    FederatedStore(x, y, parts, batch_size=8), None,
                    _cfg(12, 4, 32, batch=8, aggregator="trimmed_mean0.2"))
    api.train_rounds_windowed(8, start_round=0, window=4)  # warmup
    with sanitized() as rep:
        losses = api.train_rounds_windowed(8, start_round=8, window=4)
    assert len(losses) == 8
    assert rep.compiles == 0


def test_aggregator_guards_refuse_custom_round_algorithms():
    """Algorithms whose rounds bypass the shared builders must refuse a
    non-mean aggregator instead of silently keeping their own
    aggregation; mean stays allowed everywhere."""
    from fedml_tpu.algos.qfedavg import QFedAvgAPI
    from fedml_tpu.algos.scaffold import ScaffoldAPI

    x, y, parts = _power_law(seed=6)
    fed = build_federated_arrays(x, y, parts, batch_size=16)
    for cls in (QFedAvgAPI, ScaffoldAPI):
        with pytest.raises(NotImplementedError, match="aggregation"):
            cls(LogisticRegression(num_classes=2), fed, None,
                _cfg(12, 4, 2, aggregator="krum"))
    # FedOpt rides the shared round builders — robust aggregation composes
    # with its server optimizer.
    from fedml_tpu.algos.fedopt import FedOptAPI

    api = FedOptAPI(LogisticRegression(num_classes=2), fed, None,
                    _cfg(12, 4, 2, aggregator="coord_median",
                         server_optimizer="adam"))
    assert np.isfinite(api.train_one_round(0)["train_loss"])


# ---------------------------------------------------------------------------
# Attack-vs-defense matrix (the acceptance drill), in the windowed tier

N_CLIENTS = 8
N_ADV = (N_CLIENTS - 1) // 2 - 1  # f = floor((n-1)/2) - 1 = 2


def _drill_data(seed=0, per_client=50):
    x, y = make_classification(N_CLIENTS * per_client + 400, n_features=10,
                               n_classes=4, seed=seed)
    xt, yt = x[-400:], y[-400:]
    parts = {c: np.arange(c * per_client, (c + 1) * per_client)
             for c in range(N_CLIENTS)}
    return x[:-400], y[:-400], parts, batch_global(xt, yt, 64)


def _drill_run(aggregator, corrupt_mode, rounds=14, nan_guard=False,
               window=4, seed=0):
    """A WINDOWED attack-vs-defense run: f adversary clients corrupt
    their trained updates inside the scan body (device drill); returns
    final test accuracy (NaN-poisoned models score ~chance)."""
    x, y, parts, test = _drill_data(seed=seed)
    cfg = _cfg(N_CLIENTS, N_CLIENTS, rounds, aggregator=aggregator,
               corrupt_mode=corrupt_mode, attack_freq=1,
               attack_num_adversaries=N_ADV, robust_norm_bound=1e9)
    api = FedAvgRobustAPI(LogisticRegression(num_classes=4),
                          FederatedStore(x, y, parts, batch_size=16),
                          test, cfg, nan_guard=nan_guard)
    api.train_rounds_windowed(rounds, window=window)
    assert api._window_stats["host_rounds"] in (0, rounds % window)
    return api.evaluate()["accuracy"]


@pytest.fixture(scope="module")
def clean_acc():
    return _drill_run("mean", "none")


def test_clean_run_learns(clean_acc):
    assert clean_acc > 0.7, clean_acc


@pytest.mark.parametrize("mode", [
    "sign_flip",  # the acceptance attack: fast lane
    pytest.param("scale", marks=pytest.mark.slow),
    pytest.param("random", marks=pytest.mark.slow),
])
@pytest.mark.parametrize("agg", ["coord_median", "trimmed_mean0.25",
                                 "krum2"])
def test_robust_aggregators_survive_corruption(mode, agg, clean_acc):
    """f = ⌊(n−1)/2⌋−1 corrupted clients, every round, in the windowed
    tier: the robust aggregators stay in the clean run's ballpark."""
    acc = _drill_run(agg, mode)
    assert acc > clean_acc - 0.12, (agg, mode, acc, clean_acc)


def test_mean_degrades_under_the_same_corruption(clean_acc):
    """The acceptance contrast: sign-flip model replacement (the attack
    the criterion names) actively reverses learning, and the weighted
    mean follows it. (A pure `scale` attack on an honestly-trained
    logistic update barely moves ACCURACY — positive scaling preserves
    the argmax — which is why the degradation pin uses sign_flip.)"""
    acc = _drill_run("mean", "sign_flip")
    assert acc < clean_acc - 0.2, (acc, clean_acc)


@pytest.mark.slow  # >8 s drill; tier-1 re-fit to the 870 s budget on the 1-core box (r16 audit)
def test_nan_attack_mean_poisoned_robust_with_guard_survives(clean_acc):
    """NaN faults: undefended mean is destroyed outright (non-finite
    params); nan_guard + a robust aggregator EXCLUDES the diverged
    clients from the order statistics and the run stays in the clean
    ballpark. nan_guard + mean survives too (zero-weighting suffices
    for means) — pinned so guard/aggregator unification can't drift."""
    x, y, parts, test = _drill_data()
    cfg = _cfg(N_CLIENTS, N_CLIENTS, 8, aggregator="mean",
               corrupt_mode="nan", attack_freq=1,
               attack_num_adversaries=N_ADV, robust_norm_bound=1e9)
    api = FedAvgRobustAPI(LogisticRegression(num_classes=4),
                          FederatedStore(x, y, parts, batch_size=16),
                          test, cfg, nan_guard=False)
    api.train_rounds_windowed(8, window=4)
    assert not all(np.isfinite(np.asarray(l)).all()
                   for l in jax.tree.leaves(api.net.params))

    for agg in ("trimmed_mean0.25", "krum2", "mean"):
        acc = _drill_run(agg, "nan", nan_guard=True)
        assert acc > clean_acc - 0.12, (agg, acc, clean_acc)


@pytest.mark.slow  # >5.8 s drill; tier-1 re-fit to the 870 s budget on the 2-core box (r20 audit)
def test_drill_windowed_bit_equal_host_loop():
    """The device-side corruptor inside the scan produces EXACTLY the
    host loop's trajectory — corruption, defense, and noise all ride
    the same per-round keys."""
    x, y, parts, test = _drill_data()

    def mk():
        cfg = _cfg(N_CLIENTS, 6, 9, aggregator="krum2",
                   corrupt_mode="sign_flip", attack_freq=2,
                   attack_num_adversaries=2, robust_norm_bound=1e9,
                   robust_stddev=0.01)
        return FedAvgRobustAPI(LogisticRegression(num_classes=4),
                               FederatedStore(x, y, parts, batch_size=16),
                               test, cfg)

    host, win = mk(), mk()
    la = [host.train_one_round(r)["train_loss"] for r in range(9)]
    lb = win.train_rounds_windowed(9, window=4)
    np.testing.assert_array_equal(la, lb)
    _assert_nets_bit_equal(host, win)


def test_drill_mesh_windowed_runs_and_matches_host():
    """Corruption drill on a client mesh: the adv mask ships
    client-sharded through the windowed extras; the sharded windowed
    run equals the sharded host loop bit-for-bit."""
    from fedml_tpu.parallel.mesh import client_mesh

    x, y, parts, test = _drill_data(seed=2)
    mesh = client_mesh(4)

    def mk():
        cfg = _cfg(N_CLIENTS, N_CLIENTS, 6, aggregator="coord_median",
                   corrupt_mode="scale", attack_freq=1,
                   attack_num_adversaries=2, robust_norm_bound=1e9)
        return FedAvgRobustAPI(LogisticRegression(num_classes=4),
                               FederatedStore(x, y, parts, batch_size=16),
                               test, cfg, mesh=mesh)

    host, win = mk(), mk()
    la = [host.train_one_round(r)["train_loss"] for r in range(6)]
    lb = win.train_rounds_windowed(6, window=3)
    np.testing.assert_array_equal(la, lb)
    _assert_nets_bit_equal(host, win)
