"""Windowed streaming execution: W rounds per dispatch, bit-equal to the
per-round host loop.

The windowed tier's whole value rests on one claim — gathering the next W
seeded-random cohorts as ONE superbatch and scanning them in ONE jitted
dispatch changes NOTHING about the training trajectory. These tests pin
that claim exactly (``assert_array_equal``, not allclose): on a power-law
partition where the forced window-max bucket pads smaller rounds, with a
window that does not divide the round count (host-loop remainder), on a
client mesh, across multiple local epochs, and under dropout (the
per-step rng streams must be prefix-stable in the step count).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.algos.config import FedConfig
from fedml_tpu.algos.fedavg import FedAvgAPI, plan_window_spans
from fedml_tpu.algos.loop import eval_segments
from fedml_tpu.data.store import FederatedStore, WindowPrefetcher
from fedml_tpu.models.lr import LogisticRegression


def _power_law(seed=0, n_clients=12, d=6):
    """Counts spanning several step buckets so window-max forcing is
    actually exercised (one giant + varied small clients)."""
    rng = np.random.RandomState(seed)
    counts = np.concatenate([[600], rng.randint(20, 90, n_clients - 1)])
    tot = int(counts.sum())
    x = rng.randn(tot, d).astype(np.float32)
    y = (x @ rng.randn(d) > 0).astype(np.int32)
    edges = np.concatenate([[0], np.cumsum(counts)])
    parts = {c: np.arange(edges[c], edges[c + 1])
             for c in range(n_clients)}
    return x, y, parts


def _cfg(n, cpr, rounds, batch=16, epochs=1, **kw):
    kw.setdefault("lr", 0.3)
    kw.setdefault("frequency_of_the_test", 1000)
    return FedConfig(client_num_in_total=n, client_num_per_round=cpr,
                     comm_round=rounds, epochs=epochs, batch_size=batch,
                     **kw)


def _assert_nets_bit_equal(a, b):
    for pa, pb in zip(jax.tree.leaves(a.net.params),
                      jax.tree.leaves(b.net.params)):
        np.testing.assert_array_equal(np.asarray(pa), np.asarray(pb))


def test_plan_window_spans():
    # Chunks of exactly `window` with the chunk-MAX forced bucket;
    # remainder -> host loop (None).
    assert plan_window_spans([8, 4, 8, 16, 4, 4, 8, 4, 2], 4) == \
        [(0, 4, 16), (4, 4, 8), (8, 1, None)]
    assert plan_window_spans([4, 4], 4) == [(0, 2, None)]
    assert plan_window_spans([4, 4, 4], 1) == [(0, 1, 4), (1, 1, 4),
                                               (2, 1, 4)]
    assert plan_window_spans([], 4) == []
    with pytest.raises(ValueError, match="window"):
        plan_window_spans([4], 0)


def test_eval_segments():
    # train() evaluates when round % freq == 0 or on the last round;
    # every segment must END at exactly such a round.
    assert list(eval_segments(7, 3)) == [(0, 0), (1, 3), (4, 6)]
    assert list(eval_segments(5, 1000)) == [(0, 0), (1, 4)]
    assert list(eval_segments(1, 5)) == [(0, 0)]


@pytest.mark.parametrize("epochs", [1, 2])
def test_windowed_bit_equal_host_loop(epochs):
    """Power-law cohorts (buckets vary inside windows → the window-max
    forcing path runs) with a window that does NOT divide the round
    count (host-loop remainder). Multi-epoch run pins the per-epoch
    shuffle + step-rng prefix stability."""
    x, y, parts = _power_law()
    host = FedAvgAPI(LogisticRegression(num_classes=2),
                     FederatedStore(x, y, parts, batch_size=16), None,
                     _cfg(12, 4, 9, epochs=epochs))
    win = FedAvgAPI(LogisticRegression(num_classes=2),
                    FederatedStore(x, y, parts, batch_size=16), None,
                    _cfg(12, 4, 9, epochs=epochs))
    la = [host.train_one_round(r)["train_loss"] for r in range(9)]
    lb = win.train_rounds_windowed(9, window=4)
    assert win._window_stats == {"windows": 2, "scanned_rounds": 8,
                                 "host_rounds": 1}
    np.testing.assert_array_equal(la, lb)
    _assert_nets_bit_equal(host, win)


class _TinyDropoutNet:
    """Module factory deferred so flax imports lazily like the zoo."""

    def __new__(cls, num_classes=5):
        import flax.linen as nn

        class Net(nn.Module):
            @nn.compact
            def __call__(self, x, train=False):
                x = x.reshape((x.shape[0], -1))
                x = nn.relu(nn.Dense(16)(x))
                x = nn.Dropout(0.5, deterministic=not train)(x)
                return nn.Dense(num_classes)(x)

        return Net()


@pytest.mark.slow  # >8 s drill; tier-1 re-fit to the 870 s budget on the 1-core box (r16 audit)
def test_windowed_bit_equal_dropout_model():
    """Dropout consumes the per-step rng streams: forced buckets must not
    shift them (prefix-stable fold_in per step index, not a carried
    split chain). A tiny dense net keeps the compile cost out of the
    fast lane; the stream discipline is model-independent."""
    rng = np.random.RandomState(1)
    x = rng.rand(240, 12).astype(np.float32)
    y = rng.randint(0, 5, 240).astype(np.int32)
    counts = np.array([100, 20, 40, 30, 50])
    edges = np.concatenate([[0], np.cumsum(counts)])
    parts = {c: np.arange(edges[c], edges[c + 1]) for c in range(5)}
    host = FedAvgAPI(_TinyDropoutNet(),
                     FederatedStore(x, y, parts, batch_size=10), None,
                     _cfg(5, 2, 4, batch=10, epochs=2, lr=0.05))
    win = FedAvgAPI(_TinyDropoutNet(),
                    FederatedStore(x, y, parts, batch_size=10), None,
                    _cfg(5, 2, 4, batch=10, epochs=2, lr=0.05))
    la = [host.train_one_round(r)["train_loss"] for r in range(4)]
    lb = win.train_rounds_windowed(4, window=2)
    np.testing.assert_array_equal(la, lb)
    _assert_nets_bit_equal(host, win)


def test_windowed_mesh_bit_equal():
    """The windowed scan over the shard_map round (clients sharded over
    the mesh axis, superbatch laid out [W, C-sharded, ...]) must equal
    the per-round sharded host loop exactly — including a SUBSAMPLED
    cohort, which the on-device scan tier refuses on a mesh.

    The trajectory (params) is bit-exact. The reported loss scalars are
    telemetry and agree to the last ulp only, since jaxlib 0.9.0 (triaged
    in PR 21: round 5's differs by 1.5e-8): XLA:CPU vectorizes the loss's
    float32 reduction at its preferred vector width, and inside the scan
    body the partial sums associate differently than in the standalone
    round. With ``XLA_FLAGS=--xla_cpu_prefer_vector_width=128`` the six
    scalars are bit-equal again."""
    from fedml_tpu.parallel.mesh import client_mesh

    x, y, parts = _power_law(seed=2, n_clients=16)
    mesh = client_mesh(8)
    host = FedAvgAPI(LogisticRegression(num_classes=2),
                     FederatedStore(x, y, parts, batch_size=16), None,
                     _cfg(16, 8, 6), mesh=mesh)
    win = FedAvgAPI(LogisticRegression(num_classes=2),
                    FederatedStore(x, y, parts, batch_size=16), None,
                    _cfg(16, 8, 6), mesh=mesh)
    la = [host.train_one_round(r)["train_loss"] for r in range(6)]
    lb = win.train_rounds_windowed(6, window=3)
    assert win._window_stats["scanned_rounds"] == 6
    np.testing.assert_allclose(la, lb, rtol=1e-6, atol=0)
    _assert_nets_bit_equal(host, win)


def test_train_windowed_matches_train_history():
    """Eval-cadence-aware splitting: the full windowed loop must produce
    train()'s exact history — same per-round losses, eval metrics at the
    same rounds (freq boundaries + last round), identical values."""
    from fedml_tpu.data.batching import batch_global

    x, y, parts = _power_law(seed=3)
    test_global = batch_global(x[:64], y[:64], 16)
    a = FedAvgAPI(LogisticRegression(num_classes=2),
                  FederatedStore(x, y, parts, batch_size=16), test_global,
                  _cfg(12, 4, 7, frequency_of_the_test=3))
    b = FedAvgAPI(LogisticRegression(num_classes=2),
                  FederatedStore(x, y, parts, batch_size=16), test_global,
                  _cfg(12, 4, 7, frequency_of_the_test=3))
    ha = a.train()
    hb = b.train_windowed(window=3)
    assert len(ha) == len(hb) == 7
    for ea, eb in zip(ha, hb):
        assert set(ea) == set(eb), (ea, eb)
        assert ea["round"] == eb["round"]
        for k in ea:
            np.testing.assert_array_equal(ea[k], eb[k])
    _assert_nets_bit_equal(a, b)


def test_gather_window_matches_per_round_gather():
    """Each round slice of the superbatch == that round's own
    gather_cohort at the forced bucket; and the REUSED staging buffers
    must never alias live device arrays (gathering window B must not
    corrupt window A's batch)."""
    x, y, parts = _power_law(seed=4)
    store = FederatedStore(x, y, parts, batch_size=16)
    idx_a = np.array([[1, 3, 5], [0, 2, 4]])  # includes the giant
    idx_b = np.array([[6, 7, 8], [9, 10, 11]])
    steps = store.cohort_steps(idx_a.ravel())
    a = store.gather_window(idx_a, steps)
    # np.array (forced copy): np.asarray of a CPU jax array can be a
    # zero-copy view, which would hide exactly the staging-buffer
    # aliasing this test exists to catch.
    a_host = [np.array(l) for l in jax.tree.leaves(a)]
    b = store.gather_window(idx_b, steps)  # refills the staging buffers
    for l, fresh in zip(jax.tree.leaves(a), a_host):
        np.testing.assert_array_equal(np.asarray(l), fresh)
    for w in range(2):
        per_round = store.gather_cohort(idx_a[w], steps=steps)
        got = jax.tree.leaves(a.round_arrays(w))
        want = jax.tree.leaves(per_round)
        for l1, l2 in zip(got, want):
            np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))
    with pytest.raises(ValueError, match="forced steps|window_indices"):
        store.gather_window(idx_a, steps=1)


def test_gather_window_mesh_put_does_not_alias_staging():
    """device_put of a large aligned numpy array zero-copy aliases its
    memory on the CPU backend (demonstrably, for the unsharded put);
    gather_window hands the put a VIEW of the reused staging buffers, so
    window_put must copy first — otherwise gathering window B corrupts
    window A's in-flight superbatch whenever the backend takes the
    zero-copy path. This pins the no-aliasing CONTRACT on a 1-device
    mesh (today's sharded put happens to copy; the contract must not
    depend on that)."""
    from fedml_tpu.parallel.mesh import client_mesh
    from fedml_tpu.parallel.shard import window_put

    x, y, parts = _power_law(seed=7)
    store = FederatedStore(x, y, parts, batch_size=16)
    put = window_put(client_mesh(1))
    idx_a = np.array([[1, 3, 5], [0, 2, 4]])
    idx_b = np.array([[6, 7, 8], [9, 10, 11]])
    steps = store.cohort_steps(idx_a.ravel())
    a = store.gather_window(idx_a, steps, put=put)
    a_host = [np.array(l) for l in jax.tree.leaves(a)]  # forced copies
    store.gather_window(idx_b, steps, put=put)  # refills the staging
    for l, before in zip(jax.tree.leaves(a), a_host):
        np.testing.assert_array_equal(np.asarray(l), before)


def test_window_prefetcher_failure_containment():
    """A worker exception (bad index, host OOM) surfaces in the caller's
    get() — no deadlock, no silent drop — and the prefetcher keeps
    working afterwards."""
    x, y, parts = _power_law(seed=5)
    store = FederatedStore(x, y, parts, batch_size=16)
    pf = WindowPrefetcher(store)
    idx = np.array([[1, 2], [3, 4]])
    steps = store.cohort_steps(idx.ravel())

    boom = RuntimeError("worker exploded")
    orig = store.gather_window
    store.gather_window = lambda *a, **kw: (_ for _ in ()).throw(boom)
    pf.prefetch(0, idx, steps)
    with pytest.raises(RuntimeError, match="worker exploded"):
        pf.get(0, idx, steps)
    store.gather_window = orig
    # Still usable: un-prefetched get falls through to a direct gather,
    # and a fresh prefetch round-trips.
    got = pf.get(1, idx, steps)
    pf.prefetch(2, idx, steps)
    got2 = pf.get(2, idx, steps)
    direct = store.gather_window(idx, steps)
    for g in (got, got2):
        for l1, l2 in zip(jax.tree.leaves(g), jax.tree.leaves(direct)):
            np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))
    # Mismatched indices/steps at get(): prefetched buffer is discarded,
    # fresh gather served.
    pf.prefetch(3, idx, steps)
    other = pf.get(3, idx[::-1], steps)
    want = store.gather_window(idx[::-1], steps)
    for l1, l2 in zip(jax.tree.leaves(other), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))


# ---------------------------------------------------------------------------
# Windowed carry protocol: FedOpt / SCAFFOLD / FedProx ride the scan


def _fedopt_cfg(server_opt, rounds=9, **kw):
    cfg = _cfg(12, 4, rounds, **kw)
    cfg.server_optimizer = server_opt
    cfg.server_lr = 0.05
    return cfg


@pytest.mark.parametrize("server_opt", ["adam", "yogi"])
def test_windowed_fedopt_bit_equal(server_opt):
    """The carried server-optimizer state: W FedOpt rounds per dispatch
    (optax state threaded through the scan carry) must equal the
    per-round host loop exactly — params AND optimizer state — with a
    window that does not divide the round count, so the carry is
    committed back before the host-loop remainder consumes it."""
    from fedml_tpu.algos.fedopt import FedOptAPI

    x, y, parts = _power_law()
    host = FedOptAPI(LogisticRegression(num_classes=2),
                     FederatedStore(x, y, parts, batch_size=16), None,
                     _fedopt_cfg(server_opt))
    win = FedOptAPI(LogisticRegression(num_classes=2),
                    FederatedStore(x, y, parts, batch_size=16), None,
                    _fedopt_cfg(server_opt))
    la = [host.train_one_round(r)["train_loss"] for r in range(9)]
    lb = win.train_rounds_windowed(9, window=4)
    assert win._window_stats == {"windows": 2, "scanned_rounds": 8,
                                 "host_rounds": 1}
    np.testing.assert_array_equal(la, lb)
    _assert_nets_bit_equal(host, win)
    for a, b in zip(jax.tree.leaves(host.server_opt_state),
                    jax.tree.leaves(win.server_opt_state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.slow  # >5.8 s drill; tier-1 re-fit to the 870 s budget on the 2-core box (r20 audit)
def test_windowed_fedopt_mesh_bit_equal():
    """The carry rides the shard_map round too (optimizer state
    replicated, clients sharded)."""
    from fedml_tpu.algos.fedopt import FedOptAPI
    from fedml_tpu.parallel.mesh import client_mesh

    x, y, parts = _power_law(seed=2, n_clients=16)
    mesh = client_mesh(8)
    cfg = _cfg(16, 8, 6)
    cfg.server_optimizer = "adam"
    cfg.server_lr = 0.05
    host = FedOptAPI(LogisticRegression(num_classes=2),
                     FederatedStore(x, y, parts, batch_size=16), None,
                     cfg, mesh=mesh)
    cfg2 = _cfg(16, 8, 6)
    cfg2.server_optimizer = "adam"
    cfg2.server_lr = 0.05
    win = FedOptAPI(LogisticRegression(num_classes=2),
                    FederatedStore(x, y, parts, batch_size=16), None,
                    cfg2, mesh=mesh)
    la = [host.train_one_round(r)["train_loss"] for r in range(6)]
    lb = win.train_rounds_windowed(6, window=3)
    np.testing.assert_array_equal(la, lb)
    _assert_nets_bit_equal(host, win)


def _assert_scaffold_state_bit_equal(a, b):
    for sa, sb in zip(jax.tree.leaves(a.server_control),
                      jax.tree.leaves(b.server_control)):
        np.testing.assert_array_equal(np.asarray(sa), np.asarray(sb))
    for ca, cb in zip(jax.tree.leaves(a.client_controls),
                      jax.tree.leaves(b.client_controls)):
        np.testing.assert_array_equal(np.asarray(ca), np.asarray(cb))


def test_windowed_scaffold_bit_equal():
    """SCAFFOLD's "custom" carry: server control + the FULL client-
    control stack ride the scan, cohort slots gathered/scattered INSIDE
    the body (12 clients, 4/round, 9 rounds → repeat clients across
    rounds of one window, which a per-window pre-gather/post-scatter
    would corrupt). Params, both control states, and losses must equal
    the streaming host loop exactly, incl. the host-loop remainder."""
    from fedml_tpu.algos.scaffold import ScaffoldAPI

    x, y, parts = _power_law()
    host = ScaffoldAPI(LogisticRegression(num_classes=2),
                       FederatedStore(x, y, parts, batch_size=16), None,
                       _cfg(12, 4, 9))
    win = ScaffoldAPI(LogisticRegression(num_classes=2),
                      FederatedStore(x, y, parts, batch_size=16), None,
                      _cfg(12, 4, 9))
    la = [host.train_one_round(r)["train_loss"] for r in range(9)]
    lb = win.train_rounds_windowed(9, window=4)
    assert win._window_stats["scanned_rounds"] == 8
    np.testing.assert_array_equal(la, lb)
    _assert_nets_bit_equal(host, win)
    _assert_scaffold_state_bit_equal(host, win)


@pytest.mark.slow  # >7 s drill; tier-1 re-fit to the 870 s budget on the 2-core box (r20 audit)
def test_windowed_scaffold_mesh_bit_equal():
    """SCAFFOLD windowed on a client mesh: the stateful shard_map round
    under the scan, control gather/scatter crossing shards."""
    from fedml_tpu.algos.scaffold import ScaffoldAPI
    from fedml_tpu.parallel.mesh import client_mesh

    x, y, parts = _power_law(seed=2, n_clients=16)
    mesh = client_mesh(8)
    host = ScaffoldAPI(LogisticRegression(num_classes=2),
                       FederatedStore(x, y, parts, batch_size=16), None,
                       _cfg(16, 8, 6), mesh=mesh)
    win = ScaffoldAPI(LogisticRegression(num_classes=2),
                      FederatedStore(x, y, parts, batch_size=16), None,
                      _cfg(16, 8, 6), mesh=mesh)
    la = [host.train_one_round(r)["train_loss"] for r in range(6)]
    lb = win.train_rounds_windowed(6, window=3)
    np.testing.assert_array_equal(la, lb)
    _assert_nets_bit_equal(host, win)
    _assert_scaffold_state_bit_equal(host, win)


def test_scaffold_streaming_matches_resident():
    """ScaffoldAPI now streams: the same federation through a
    FederatedStore host loop must train bit-equal to the resident-layout
    host loop (the controls stay device-resident either way; only the
    data path differs, and it is step-count prefix-stable)."""
    from fedml_tpu.algos.scaffold import ScaffoldAPI
    from fedml_tpu.data.batching import build_federated_arrays

    x, y, parts = _power_law(seed=8)
    res = ScaffoldAPI(LogisticRegression(num_classes=2),
                      build_federated_arrays(x, y, parts, batch_size=16),
                      None, _cfg(12, 4, 4))
    st = ScaffoldAPI(LogisticRegression(num_classes=2),
                     FederatedStore(x, y, parts, batch_size=16), None,
                     _cfg(12, 4, 4))
    la = [res.train_one_round(r)["train_loss"] for r in range(4)]
    lb = [st.train_one_round(r)["train_loss"] for r in range(4)]
    np.testing.assert_array_equal(la, lb)
    _assert_nets_bit_equal(res, st)
    _assert_scaffold_state_bit_equal(res, st)


def test_windowed_fedprox_bit_equal():
    """FedProx rides the protocol with NO carry: the μ term lives in the
    local trainer the scan replays."""
    from fedml_tpu.algos.fedprox import FedProxAPI

    x, y, parts = _power_law(seed=9)
    host = FedProxAPI(LogisticRegression(num_classes=2),
                      FederatedStore(x, y, parts, batch_size=16), None,
                      _cfg(12, 4, 6, fedprox_mu=0.1))
    win = FedProxAPI(LogisticRegression(num_classes=2),
                     FederatedStore(x, y, parts, batch_size=16), None,
                     _cfg(12, 4, 6, fedprox_mu=0.1))
    la = [host.train_one_round(r)["train_loss"] for r in range(6)]
    lb = win.train_rounds_windowed(6, window=3)
    np.testing.assert_array_equal(la, lb)
    _assert_nets_bit_equal(host, win)


@pytest.mark.slow  # >7 s drill; tier-1 re-fit to the 870 s budget on the 2-core box (r20 audit)
def test_windowed_fedopt_checkpoint_restore_mid_run():
    """Checkpoint at a window boundary mid-run: the carried server
    optimizer state is committed back to the instance at every boundary,
    so save → fresh api → restore → continue windowed must equal one
    uninterrupted host-loop run exactly."""
    from fedml_tpu.algos.fedopt import FedOptAPI
    from fedml_tpu.obs.checkpoint import (CheckpointManager, restore_run,
                                          save_run)

    x, y, parts = _power_law(seed=10)

    def mk():
        return FedOptAPI(LogisticRegression(num_classes=2),
                         FederatedStore(x, y, parts, batch_size=16), None,
                         _fedopt_cfg("adam", rounds=8))

    host = mk()
    la = [host.train_one_round(r)["train_loss"] for r in range(8)]

    import tempfile
    with tempfile.TemporaryDirectory() as d:
        a = mk()
        lb = a.train_rounds_windowed(4, window=4)  # one whole window
        mgr = CheckpointManager(d)
        save_run(mgr, a, 3)  # after round 3 = the window boundary
        b = mk()  # fresh: different params until restore
        nxt = restore_run(mgr, b)
        mgr.close()
        assert nxt == 4
        lb += b.train_rounds_windowed(4, start_round=4, window=4)
    np.testing.assert_array_equal(la, lb)
    _assert_nets_bit_equal(host, b)
    for x1, x2 in zip(jax.tree.leaves(host.server_opt_state),
                      jax.tree.leaves(b.server_opt_state)):
        np.testing.assert_array_equal(np.asarray(x1), np.asarray(x2))


def test_windowed_fedopt_steady_state_sanitized():
    """Acceptance pin: after warmup, windowed FedOpt (uniform buckets)
    runs under the sanitizer with ZERO jit-cache misses and no unplanned
    transfers — the carried optimizer state stays on device."""
    from fedml_tpu.algos.fedopt import FedOptAPI
    from fedml_tpu.obs.sanitizer import sanitized

    rng = np.random.RandomState(3)
    x = rng.randn(12 * 32, 6).astype(np.float32)
    y = (x @ rng.randn(6) > 0).astype(np.int32)
    parts = {c: np.arange(c * 32, (c + 1) * 32) for c in range(12)}
    api = FedOptAPI(LogisticRegression(num_classes=2),
                    FederatedStore(x, y, parts, batch_size=8), None,
                    _fedopt_cfg("adam", rounds=32, batch=8))
    api.train_rounds_windowed(8, start_round=0, window=4)  # warmup
    with sanitized() as rep:
        losses = api.train_rounds_windowed(8, start_round=8, window=4)
    assert len(losses) == 8
    assert rep.compiles == 0


def test_windowed_scaffold_steady_state_sanitized():
    """Acceptance pin for the "custom" carry: steady-state windowed
    SCAFFOLD — control gather/scatter inside the scan, idx/mask aux H2D
    marked planned — zero recompiles, no unplanned transfers. Uses a
    NON-dividing window: the host-loop remainder round runs the custom
    per-round procedure, whose deliberate syncs must be planned too
    (regression: the remainder used to trip the transfer guard)."""
    from fedml_tpu.algos.scaffold import ScaffoldAPI
    from fedml_tpu.obs.sanitizer import sanitized

    rng = np.random.RandomState(4)
    x = rng.randn(12 * 32, 6).astype(np.float32)
    y = (x @ rng.randn(6) > 0).astype(np.int32)
    parts = {c: np.arange(c * 32, (c + 1) * 32) for c in range(12)}
    api = ScaffoldAPI(LogisticRegression(num_classes=2),
                      FederatedStore(x, y, parts, batch_size=8), None,
                      _cfg(12, 4, 32, batch=8))
    api.train_rounds_windowed(9, start_round=0, window=4)  # warmup
    with sanitized() as rep:
        losses = api.train_rounds_windowed(9, start_round=9, window=4)
    assert len(losses) == 9
    assert rep.compiles == 0


def test_windowed_guards():
    """Incompatible configurations refuse loudly instead of silently
    changing semantics — keyed on the windowed CARRY PROTOCOL, not
    type-identity lists (FedOpt/SCAFFOLD/FedProx now ride the scan; see
    the bit-equality tests below)."""
    from fedml_tpu.data.batching import build_federated_arrays

    x, y, parts = _power_law(seed=6)
    # Resident layout: the on-device scan tier owns that.
    api = FedAvgAPI(LogisticRegression(num_classes=2),
                    build_federated_arrays(x, y, parts, batch_size=16),
                    None, _cfg(12, 4, 4))
    with pytest.raises(NotImplementedError, match="FederatedStore"):
        api.train_rounds_windowed(4)
    # Loss-biased selection depends on the current net.
    api = FedAvgAPI(LogisticRegression(num_classes=2),
                    FederatedStore(x, y, parts, batch_size=16), None,
                    _cfg(12, 4, 4, client_selection="pow_d",
                         pow_d_candidates=8))
    with pytest.raises(NotImplementedError, match="random"):
        api.train_rounds_windowed(4)

    # A stateful _server_update override WITHOUT its pure windowed form:
    # the protocol refuses — inheriting the plain-average fold would
    # silently change the algorithm inside the scan.
    class _StatefulUpdate(FedAvgAPI):
        def _server_update(self, old_net, avg_net):
            self._booster = getattr(self, "_booster", 0) + 1
            return avg_net

    api = _StatefulUpdate(LogisticRegression(num_classes=2),
                          FederatedStore(x, y, parts, batch_size=16), None,
                          _cfg(12, 4, 4))
    with pytest.raises(NotImplementedError, match="pure windowed form"):
        api.train_rounds_windowed(4)

    # A custom per-round procedure that inherits window_protocol="round":
    # replaying run_round would silently drop it — refuse and point at
    # the protocol.
    class _CustomRound(FedAvgAPI):
        def train_one_round(self, round_idx):
            out = super().train_one_round(round_idx)
            out["extra_metric"] = 0.0
            return out

    api = _CustomRound(LogisticRegression(num_classes=2),
                       FederatedStore(x, y, parts, batch_size=16), None,
                       _cfg(12, 4, 4))
    with pytest.raises(NotImplementedError, match="customizes the round"):
        api.train_rounds_windowed(4)

    # "custom" WITHOUT a custom scan body would inherit the plain round
    # replay — refuse (symmetric to the inherited-"round" check).
    class _CustomSansScan(FedAvgAPI):
        window_protocol = "custom"

    api = _CustomSansScan(LogisticRegression(num_classes=2),
                          FederatedStore(x, y, parts, batch_size=16), None,
                          _cfg(12, 4, 4))
    with pytest.raises(NotImplementedError, match="_build_window_scan"):
        api.train_rounds_windowed(4)

    # Carry flowing IN without a commit hook: the scanned-out state
    # would be silently discarded — refuse.
    from fedml_tpu.algos.scaffold import ScaffoldAPI

    class _CustomSansCommit(ScaffoldAPI):
        _window_carry_commit = FedAvgAPI._window_carry_commit

    api = _CustomSansCommit(LogisticRegression(num_classes=2),
                            FederatedStore(x, y, parts, batch_size=16),
                            None, _cfg(12, 4, 4))
    with pytest.raises(NotImplementedError, match="_window_carry_commit"):
        api.train_rounds_windowed(4)

    # window_protocol=None opts out entirely.
    class _OptedOut(FedAvgAPI):
        window_protocol = None

    api = _OptedOut(LogisticRegression(num_classes=2),
                    FederatedStore(x, y, parts, batch_size=16), None,
                    _cfg(12, 4, 4))
    with pytest.raises(NotImplementedError, match="opts out"):
        api.train_rounds_windowed(4)
