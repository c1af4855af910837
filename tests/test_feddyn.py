"""FedDyn (Acar et al. 2021): the server-state invariant holds, drift
correction helps under heterogeneous clients, sharded equals vmap, state
checkpoints, and unsupported knobs are rejected."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.algos.config import FedConfig
from fedml_tpu.algos.fedavg import FedAvgAPI
from fedml_tpu.algos.feddyn import FedDynAPI
from fedml_tpu.data.batching import batch_global, build_federated_arrays
from fedml_tpu.models.lr import LogisticRegression


def _shifted_clients(n_clients=4, per_client=64, d=8, shift=4.0, seed=0):
    """Same decision rule, strongly shifted per-client covariate means —
    the client-drift regime (same fixture family as test_scaffold)."""
    rng = np.random.RandomState(seed)
    w = rng.randn(d)
    xs, ys = [], []
    for c in range(n_clients):
        mu = shift * rng.randn(d)
        x = (rng.randn(per_client, d) + mu).astype(np.float32)
        ys.append((x @ w > 0).astype(np.int32))
        xs.append(x)
    x = np.concatenate(xs)
    y = np.concatenate(ys)
    parts = {c: np.arange(c * per_client, (c + 1) * per_client)
             for c in range(n_clients)}
    return build_federated_arrays(x, y, parts, batch_size=16), \
        batch_global(x, y, 16)


def _cfg(rounds, epochs, lr=0.3, cpr=4):
    return FedConfig(client_num_in_total=4, client_num_per_round=cpr,
                     comm_round=rounds, epochs=epochs, batch_size=16, lr=lr,
                     frequency_of_the_test=1000)


def test_feddyn_server_state_invariant():
    """h must equal -alpha/N x the accumulated participant drifts; the
    global params must equal the participant mean minus h/alpha — checked
    against a from-scratch recomputation of one round."""
    fed, _ = _shifted_clients()
    alpha = 0.05
    api = FedDynAPI(LogisticRegression(num_classes=2), fed, None,
                    _cfg(2, 1), alpha=alpha)
    w0 = jax.tree.map(lambda a: np.asarray(a, np.float64), api.net.params)
    # Capture trained client models by re-running the jitted round parts:
    # easier — derive from the update equations using returned state.
    api.train_one_round(0)
    h = jax.tree.map(lambda a: np.asarray(a, np.float64), api.server_h)
    gk = jax.tree.map(lambda a: np.asarray(a, np.float64), api.client_grads)
    w1 = jax.tree.map(lambda a: np.asarray(a, np.float64), api.net.params)
    # g_k = -alpha (w_k - w0)  =>  sum_k (w_k - w0) = -sum_k g_k / alpha
    # h = -alpha/N sum_k (w_k - w0) = sum_k g_k / N
    for hleaf, gleaf in zip(jax.tree.leaves(h), jax.tree.leaves(gk)):
        np.testing.assert_allclose(hleaf, gleaf.sum(0) / 4, rtol=1e-5,
                                   atol=1e-7)
    # w1 = mean_k w_k - h/alpha, and mean_k w_k = w0 - mean_k g_k / alpha
    for w1l, w0l, gl, hl in zip(jax.tree.leaves(w1), jax.tree.leaves(w0),
                                jax.tree.leaves(gk), jax.tree.leaves(h)):
        expect = w0l - gl.mean(0) / alpha - hl / alpha
        np.testing.assert_allclose(w1l, expect, rtol=1e-4, atol=1e-6)


def test_feddyn_beats_fedavg_under_drift():
    """Many local epochs on strongly shifted clients: dynamic
    regularization should reach a lower global train loss than FedAvg at
    the same budget (the paper's core claim).

    lr re-pinned 0.3 -> 0.03 in PR 21. At 0.3 the local steps on inputs
    shifted by 4 sigma are unstable and which algorithm ends lower is a
    seed lottery (3 of 6 seeds each way, under the old and the new
    threefry stream alike); seed 0 happened to win until JAX 0.5 made
    jax_threefry_partitionable the default and changed the draw. At 0.03
    FedDyn ends 3-5x lower on every seed tried (0.02-0.03 vs 0.10-0.12)."""
    fed, test = _shifted_clients(shift=4.0)
    rounds, epochs = 20, 5

    fa = FedAvgAPI(LogisticRegression(num_classes=2), fed, test,
                   _cfg(rounds, epochs, lr=0.03))
    fd = FedDynAPI(LogisticRegression(num_classes=2), fed, test,
                   _cfg(rounds, epochs, lr=0.03), alpha=0.1)
    for r in range(rounds):
        fa.train_one_round(r)
        fd.train_one_round(r)
    la = float(fa.eval_fn(fa.net, *test)["loss"])
    ld = float(fd.eval_fn(fd.net, *test)["loss"])
    assert np.isfinite(ld)
    assert ld < la, (ld, la)


def test_feddyn_sharded_matches_vmap():
    from fedml_tpu.parallel.mesh import client_mesh

    rng = np.random.RandomState(3)
    xs = rng.randn(8 * 32, 8).astype(np.float32)
    ys = (xs @ rng.randn(8) > 0).astype(np.int32)
    parts = {c: np.arange(c * 32, (c + 1) * 32) for c in range(8)}
    fed8 = build_federated_arrays(xs, ys, parts, batch_size=16)
    cfg = FedConfig(client_num_in_total=8, client_num_per_round=8,
                    comm_round=3, epochs=2, batch_size=16, lr=0.1,
                    frequency_of_the_test=1000)
    vm = FedDynAPI(LogisticRegression(num_classes=2), fed8, None, cfg,
                   alpha=0.05)
    sh = FedDynAPI(LogisticRegression(num_classes=2), fed8, None, cfg,
                   alpha=0.05, mesh=client_mesh(8))
    for r in range(3):
        vm.train_one_round(r)
        sh.train_one_round(r)
    for tree_a, tree_b in ((vm.net.params, sh.net.params),
                           (vm.server_h, sh.server_h),
                           (vm.client_grads, sh.client_grads)):
        for a, b in zip(jax.tree.leaves(tree_a), jax.tree.leaves(tree_b)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=1e-6)


def test_feddyn_checkpoint_roundtrip(tmp_path):
    from fedml_tpu.obs import CheckpointManager, restore_run, save_run

    fed, _ = _shifted_clients()
    a = FedDynAPI(LogisticRegression(num_classes=2), fed, None,
                  _cfg(6, 1), alpha=0.05)
    for r in range(4):
        a.train_one_round(r)

    b = FedDynAPI(LogisticRegression(num_classes=2), fed, None,
                  _cfg(6, 1), alpha=0.05)
    for r in range(2):
        b.train_one_round(r)
    mgr = CheckpointManager(str(tmp_path / "ckpt"))
    save_run(mgr, b, 1)
    c = FedDynAPI(LogisticRegression(num_classes=2), fed, None,
                  _cfg(6, 1), alpha=0.05)
    nxt = restore_run(mgr, c)
    mgr.close()
    assert nxt == 2
    for r in range(nxt, 4):
        c.train_one_round(r)
    for tree_a, tree_c in ((a.net.params, c.net.params),
                           (a.server_h, c.server_h),
                           (a.client_grads, c.client_grads)):
        for x, yv in zip(jax.tree.leaves(tree_a), jax.tree.leaves(tree_c)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(yv))


def test_feddyn_guards():
    fed, _ = _shifted_clients()
    with pytest.raises(ValueError, match="alpha"):
        FedDynAPI(LogisticRegression(num_classes=2), fed, None,
                  _cfg(2, 1), alpha=0.0)
    cfg = _cfg(2, 1)
    cfg.client_optimizer = "adam"
    with pytest.raises(ValueError, match="SGD"):
        FedDynAPI(LogisticRegression(num_classes=2), fed, None, cfg,
                  alpha=0.05)
    cfg2 = _cfg(2, 1)
    cfg2.compress = "topk0.1"
    with pytest.raises(ValueError, match="compress"):
        FedDynAPI(LogisticRegression(num_classes=2), fed, None, cfg2,
                  alpha=0.05)
    from fedml_tpu.data.store import FederatedStore

    # FedDyn STREAMS since the capability-record conversion (the
    # SCAFFOLD pattern: corrections stay device-resident, the cohort
    # arrives through the shared _cohort path) — a store-backed host
    # loop must train, not refuse. Streaming-vs-resident and
    # windowed-vs-host bit-equality are pinned in test_zoo_windowed.py.
    rng = np.random.RandomState(0)
    x = rng.randn(4 * 32, 8).astype(np.float32)
    y = (rng.rand(4 * 32) > 0.5).astype(np.int32)
    parts = {c: np.arange(c * 32, (c + 1) * 32) for c in range(4)}
    api = FedDynAPI(LogisticRegression(num_classes=2),
                    FederatedStore(x, y, parts, batch_size=16), None,
                    _cfg(4, 2), alpha=0.05)
    m = api.train_one_round(0)
    assert np.isfinite(m["train_loss"])


def test_feddyn_cli():
    from fedml_tpu.exp import parse_args, run

    args = parse_args([
        "--model", "lr", "--dataset", "synthetic_1_1",
        "--client_num_in_total", "6", "--client_num_per_round", "6",
        "--batch_size", "8", "--comm_round", "3", "--epochs", "1",
        "--lr", "0.1", "--feddyn_alpha", "0.05",
        "--frequency_of_the_test", "2",
    ])
    _, history = run(args, algorithm="FedDyn")
    assert len(history) == 3
    assert np.isfinite(history[-1]["train_loss"])
